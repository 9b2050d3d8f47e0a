"""obs.trace.span: the program's timed regions, on the profiler's host
plane and in the process's per-kind totals.

A span is a ``jax.profiler.TraceAnnotation`` (so it lands on the
``/host:CPU`` plane of any profile, on the device trace's clock), adds
its host-clock wall (with the executables JAX compiled inside it and,
when asked, its thread CPU time) to ``span_totals()`` whether or not a
tracer is given, and, with an enabled tracer, emits its kind's event
with the wall under ``"w"``.  The replay path (``merge_patches`` ->
``compile_local_patches`` -> ``fuse_steps`` -> ``make_replayer_rle``),
the remote path (``compile_remote_txns`` -> ``make_replayer_rle_mixed``)
and the batcher's tick phases are timed with it.
"""
import glob

import numpy as np
import pytest

from text_crdt_rust_tpu.obs.trace import (
    EVENT_SCHEMA,
    SPAN_KINDS,
    WALL_KEY,
    Tracer,
    reset_span_totals,
    span,
    span_totals,
)


@pytest.fixture(autouse=True)
def fresh_totals():
    reset_span_totals()
    yield
    reset_span_totals()


def counts():
    return {k: v["count"] for k, v in span_totals().items()}


def test_span_adds_to_totals_with_or_without_a_tracer():
    tr = Tracer(keep_all=True)
    with span("rle.stage") as a:
        pass
    with span("rle.stage", tr):
        pass
    with tr.span("tick.barrier", shard=3):
        pass
    tot = span_totals()
    assert tot["rle.stage"]["count"] == 2
    assert tot["tick.barrier"]["count"] == 1
    assert a.wall_s == a.t1 - a.t0 >= 0.0
    assert 0.0 <= tot["rle.stage"]["max_s"] <= tot["rle.stage"]["total_s"]
    # Only the spans given the tracer emitted events, wall under "w".
    kinds = [e["k"] for e in tr.events]
    assert kinds == ["trace.header", "rle.stage", "tick.barrier"]
    assert tr.events[2]["shard"] == 3 and "ms" in tr.events[2][WALL_KEY]


def test_nested_spans_each_count():
    with span("compiler.compile") as outer:
        with span("compiler.fuse") as inner:
            with span("compiler.fuse"):
                pass
    assert counts() == {"compiler.compile": 1, "compiler.fuse": 2}
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    tot = span_totals()
    assert tot["compiler.compile"]["total_s"] >= inner.wall_s


def test_span_counts_even_when_the_region_raises():
    tr = Tracer(keep_all=True)
    with pytest.raises(ValueError):
        with span("rle.dispatch", tr):
            raise ValueError("boom")
    assert counts() == {"rle.dispatch": 1}
    assert tr.events[-1]["k"] == "rle.dispatch"


def test_disabled_tracer_still_counts_and_emits_nothing():
    tr = Tracer(enabled=False)
    with span("rle.build", tr):
        pass
    assert counts() == {"rle.build": 1} and tr.seq == 0


def test_spans_keep_logical_bytes_identical():
    """Two tracers given the same spans agree byte for byte once the
    walls are stripped: a span's wall never reaches a logical field."""
    def stream():
        tr = Tracer(keep_all=True)
        tr.set_tick(4)
        for shard in range(3):
            with tr.span("tick.barrier", shard=shard):
                sum(range(1000 * shard))
        with span("rle.dispatch", tr):
            pass
        return tr.logical_bytes()

    assert stream() == stream()


def test_cpu_spans_leave_out_waits():
    """``cpu=True`` reads the thread's CPU time, so a wait inside the
    region is in the wall and not in ``cpu_s``; other spans read none."""
    import time

    with span("rle.dispatch", cpu=True) as s:
        time.sleep(0.05)
    with span("rle.stage"):
        pass
    tot = span_totals()
    assert s.wall_s >= 0.05
    assert 0.0 <= tot["rle.dispatch"]["cpu_s"] < 0.025
    assert tot["rle.stage"]["cpu_s"] is None


def test_spans_count_what_jax_compiled_inside_them():
    """A first call compiles (counted in its span, and in every span
    around it); a second call of the same shape compiles nothing; an
    executable loaded from the persistent compile cache is a compile
    JAX reports as a cache hit, and counts as none."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.arange(7, dtype=jnp.int32)
    with span("rle.build"):
        with span("rle.dispatch"):
            f(x)
    with span("rle.dispatch"):
        f(x)
    with span("rle.stage"):
        jax.jit(lambda x: x - 2)(x)
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    tot = span_totals()
    assert tot["rle.build"]["compiles"] == 1
    assert tot["rle.dispatch"]["compiles"] == 1
    assert tot["rle.stage"]["compiles"] == 0


def test_span_kinds_are_declared_and_reset_clears():
    assert all(k in EVENT_SCHEMA for k in SPAN_KINDS)
    assert {"tick.drain", "tick.device", "tick.barrier"} <= set(SPAN_KINDS)
    assert {"compiler.remote", "compiler.prefill"} <= set(SPAN_KINDS)
    with span("rle.stage"):
        pass
    reset_span_totals()
    assert span_totals() == {}


def _host_spans(log_dir):
    """{kind: [(start_ns, end_ns)]} of the ``SPAN_KINDS`` events on the
    ``/host:CPU`` plane of the one profile under ``log_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    (host,) = [p for p in pd.planes if p.name == "/host:CPU"]
    spans = {}
    for line in host.lines:
        for e in line.events:
            if e.name in SPAN_KINDS:
                spans.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns))
    return spans


def _tiny_replay(calls: int):
    """The replay path at a tiny size, in interpret mode: ``calls``
    calls of one replayer."""
    from text_crdt_rust_tpu.ops import batch as B
    from text_crdt_rust_tpu.ops import rle as R
    from text_crdt_rust_tpu.utils.testdata import TestPatch

    text = "the quick brown fox"
    patches = [TestPatch(i, 0, c) for i, c in enumerate(text)]
    patches += [TestPatch(4, 6, ""), TestPatch(4, 0, "slow ")]
    merged = B.merge_patches(patches)
    ops, _ = B.compile_local_patches(merged, lmax=8)
    ops, _ = B.fuse_steps(ops, fuse_w=1)
    run = R.make_replayer_rle(ops, capacity=64, batch=8, block_k=16,
                              chunk=8, interpret=True)
    results = [run() for _ in range(calls)]
    return ops, results


def test_replay_spans_land_on_the_profilers_host_plane(tmp_path):
    """A CPU profile around a tiny replay holds every replay span on
    ``/host:CPU``, as many times as ``span_totals`` counts it: one
    build, then a dispatch for every later call."""
    import jax

    calls = 3
    jax.profiler.start_trace(str(tmp_path))
    try:
        ops, results = _tiny_replay(calls)
        jax.block_until_ready(results[-1].ordp)
    finally:
        jax.profiler.stop_trace()
    got = counts()
    assert got == {"compiler.merge": 1, "compiler.compile": 1,
                   "compiler.fuse": 1, "rle.stage": 1, "rle.build": 1,
                   "rle.dispatch": calls - 1}
    assert span_totals()["rle.dispatch"]["cpu_s"] is not None
    # The replay is right: every call gives the same document.
    from text_crdt_rust_tpu.ops.rle import expand_runs

    first = expand_runs(results[0])
    assert len(first) == len("the slow brown fox") + 6
    assert all(np.array_equal(expand_runs(r), first) for r in results)

    spans = _host_spans(tmp_path)
    assert {k: len(v) for k, v in spans.items()} == got
    # On one clock: the host compiler ran before the build, the build
    # before every dispatch.
    assert max(b for _, b in spans["compiler.fuse"]) \
        <= min(a for a, _ in spans["rle.build"])
    assert max(b for _, b in spans["rle.build"]) \
        <= min(a for a, _ in spans["rle.dispatch"])


def test_remote_spans_land_on_the_profilers_host_plane(tmp_path):
    """The remote path at a tiny size: the remote compiler (timed by the
    caller that builds the program; a bare ``compile_remote_txns``, as
    the serve path calls it per txn, is no span), the tables' prefill,
    the staging, one build and a dispatch for every later call, on
    ``/host:CPU`` in that order, as many as ``span_totals`` counts."""
    import jax

    from text_crdt_rust_tpu.common import RemoteId, RemoteIns, RemoteTxn
    from text_crdt_rust_tpu.ops import batch as B
    from text_crdt_rust_tpu.ops import rle_mixed as RM

    root = RemoteId("ROOT", 0xFFFFFFFF)
    txns = [RemoteTxn(id=RemoteId(n, 0), parents=[],
                      ops=[RemoteIns(root, root, t)])
            for n, t in [("amy", "aa"), ("zed", "zz")]]
    B.compile_remote_txns(txns, B.AgentTable(["amy", "zed"]), lmax=4)
    assert counts() == {}
    calls = 3
    jax.profiler.start_trace(str(tmp_path))
    try:
        table = B.AgentTable(["amy", "zed"])
        with span("compiler.remote"):
            ops, _ = B.compile_remote_txns(txns, table, lmax=4)
        run = RM.make_replayer_rle_mixed(ops, capacity=64, batch=8,
                                         block_k=8, chunk=8, interpret=True)
        results = [run() for _ in range(calls)]
        jax.block_until_ready(results[-1].ordp)
    finally:
        jax.profiler.stop_trace()
    got = counts()
    assert got == {"compiler.remote": 1, "compiler.prefill": 1,
                   "rle.stage": 1, "rle.build": 1, "rle.dispatch": calls - 1}
    assert all(r.integrate_counts() == (2, results[0].integrate_counts()[1])
               for r in results)
    spans = _host_spans(tmp_path)
    assert {k: len(v) for k, v in spans.items()} == got
    order = ("compiler.remote", "compiler.prefill", "rle.stage",
             "rle.build", "rle.dispatch")
    for a, b in zip(order, order[1:]):
        assert max(e for _, e in spans[a]) <= min(s for s, _ in spans[b])


def test_lanes_replayer_opens_the_replay_spans(tmp_path):
    """The per-lane engine (``ops.rle_lanes``'s blocked replayer) at a
    tiny size: two distinct documents, staged once, built on the first
    call and dispatched on every later one, on ``/host:CPU`` in that
    order, as many as ``span_totals`` counts."""
    import jax

    from text_crdt_rust_tpu.ops import batch as B
    from text_crdt_rust_tpu.ops import rle_lanes as RL
    from text_crdt_rust_tpu.utils.testdata import TestPatch

    streams = [B.compile_local_patches(ps, lmax=8)[0] for ps in (
        [TestPatch(0, 0, "hello"), TestPatch(5, 0, " world")],
        [TestPatch(0, 0, "abc"), TestPatch(1, 1, "XY")])]
    stacked = B.stack_ops(streams)
    reset_span_totals()
    calls = 3
    jax.profiler.start_trace(str(tmp_path))
    try:
        run = RL.make_replayer_lanes_blocked(stacked, capacity=32,
                                             block_k=8, chunk=8,
                                             interpret=True)
        results = [run() for _ in range(calls)]
        jax.block_until_ready(results[-1].ordp)
    finally:
        jax.profiler.stop_trace()
    got = counts()
    assert got == {"rle.stage": 1, "rle.build": 1,
                   "rle.dispatch": calls - 1}
    assert span_totals()["rle.dispatch"]["cpu_s"] is not None
    assert len(RL.expand_lane(results[-1], 0)) == len("hello world")
    spans = _host_spans(tmp_path)
    assert {k: len(v) for k, v in spans.items()} == got
    order = ("rle.stage", "rle.build", "rle.dispatch")
    for a, b in zip(order, order[1:]):
        assert max(e for _, e in spans[a]) <= min(s for s, _ in spans[b])


def test_serve_tick_phases_are_spans():
    """The batcher's drain, dispatch and sync walls go through ``span``:
    the totals count them, and the tracer's events carry the same walls
    as before."""
    from text_crdt_rust_tpu.config import ServeConfig
    from text_crdt_rust_tpu.serve import DocServer

    srv = DocServer(ServeConfig(num_shards=1, lanes_per_shard=2,
                                trace_keep=True, interpret=True))
    srv.admit_doc("d")
    srv.submit_local("d", "editor", 0, ins_content="hi")
    srv.tick()
    srv.submit_local("d", "editor", 2, ins_content=" there")
    srv.tick()
    srv.flush_pipeline()
    assert srv.verify_doc("d")
    got = counts()
    events = srv.tracer.events
    for kind in ("tick.drain", "tick.device", "tick.barrier"):
        assert got.get(kind, 0) >= sum(e["k"] == kind for e in events) > 0
        assert all("ms" in e[WALL_KEY] for e in events if e["k"] == kind)
    assert got["compiler.compile"] >= 2
    srv.close_obs()
