"""Chip smoke: the replay and serve paths, end to end, on the TPU.

    python chip_smoke.py             # one chip: replay, then serve twice
    python chip_smoke.py --chips 4   # the (dp, sp) mesh apply on 4 chips

Phases (one process; each checks its own results and raises on any
mismatch):

- replay: the full automerge-paper trace through ``merge_patches`` ->
  ``compile_local_patches`` -> ``fuse_steps`` -> ``ops.rle`` at the
  north-star geometry (512 lanes, 20,992 run rows, K=128, chunk 1024),
  compiled.  Lane 0 must equal the trace's final content and all 512
  lanes must be identical (compared on the device).
- serve: ``serve.loadgen``'s own path at 4 shards x 128 lanes with 768
  documents, 10% faults (so eviction -> checkpoint -> restore runs),
  once on the
  ``flat`` backend and once on ``rle-lanes-mixed``.  Every document
  must converge and every lane must equal its host oracle.
- mesh (``--chips 4`` only, and nothing else): ``parallel.mesh``'s
  sharded apply of the sveltecomponent trace over 64 distinct
  documents at dp=4, compared bit for bit with the same batch on one
  chip and with the host oracle.

Timings printed per phase are smoke timings of one call (the first call
includes compilation), not benchmark figures.  Without a TPU the script
exits non-zero; it never falls back to the CPU.  The last line of
stdout is the contract line ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def require_tpu(chips: int):
    """The first ``chips`` TPU devices, or a loud failure."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (jax sees "
                         f"{len(devs)} {devs[0].platform} device(s)); "
                         f"this script never falls back to the CPU")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, jax sees {len(devs)}")
    return devs[:chips]


def _contents_at(patches, cuts):
    """Host splice oracle: the document text after each prefix length
    in ``cuts`` (one pass over the patches)."""
    want, s, done = {}, "", 0
    for c in sorted(set(cuts)):
        for p in patches[done:c]:
            s = s[:p.pos] + p.ins_content + s[p.pos + p.del_len:]
        done = c
        want[c] = s
    return want


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def phase_replay(trace: str = "automerge-paper", patches: int = 0,
                 batch: int = 512, capacity: int = 20992,
                 block_k: int = 128, chunk: int = 1024, fuse_w: int = 8,
                 interpret: bool = False) -> dict:
    """The north-star replay through ``ops.rle``; ``patches`` > 0 cuts
    the trace to a prefix (the CPU rehearsal)."""
    import jax
    import jax.numpy as jnp

    from text_crdt_rust_tpu.ops import batch as B
    from text_crdt_rust_tpu.ops import rle as R
    from text_crdt_rust_tpu.ops import span_arrays as SA
    from text_crdt_rust_tpu.utils.testdata import (
        flatten_patches,
        load_testing_data,
        trace_path,
    )

    data = load_testing_data(trace_path(trace))
    pts = flatten_patches(data)
    if patches:
        pts = pts[:patches]
        want = _contents_at(pts, [len(pts)])[len(pts)]
    else:
        want = data.end_content
    t0 = time.perf_counter()
    merged = B.merge_patches(pts)
    lmax = max([len(p.ins_content) for p in merged] + [1])
    ops, _ = B.compile_local_patches(merged, lmax=lmax, dmax=None)
    ops, _ = B.fuse_steps(ops, fuse_w=fuse_w)
    host_s = time.perf_counter() - t0
    run = R.make_replayer_rle(ops, capacity=capacity, batch=batch,
                              block_k=block_k, chunk=chunk,
                              interpret=interpret)
    t0 = time.perf_counter()
    res = run()
    res.check()
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = run()
    res.check()
    run_s = time.perf_counter() - t0

    got = SA.to_string(R.rle_to_flat(ops, res))
    if got != want:
        raise AssertionError(f"replay: lane 0 ({len(got)} chars) != the "
                             f"trace's final content ({len(want)} chars)")
    planes = (res.ordp, res.lenp, res.blkord, res.rows, res.meta, res.ol,
              res.orr)
    same = jax.jit(lambda ps: jnp.all(jnp.stack(
        [jnp.all(p == p[:, :1]) for p in ps])))(planes)
    if not bool(same):
        raise AssertionError("replay: the 512 lanes of one stream differ")
    out = dict(trace=trace, patches=len(pts), steps=ops.num_steps,
               lanes=batch, chars=len(got),
               state_bytes=sum(int(p.nbytes) for p in planes),
               host_compile_s=round(host_s, 3),
               first_call_s=round(first_s, 3), run_s=round(run_s, 3))
    say("replay", **out)
    return out


def phase_serve(engine: str, docs: int = 768, shards: int = 4,
                lanes: int = 128, ticks: int = 20, events: int = 96,
                zipf: float = 0.8, compiled: bool = True) -> dict:
    """``serve.loadgen``'s normal path on the default backend: every doc
    must converge and every lane must equal its host oracle.

    The traffic is the loadgen's, with 96 events per tick at Zipf 0.8:
    at its defaults (48 at 1.1) 20 ticks touch about 264 of 768 docs, so
    no lane is ever evicted."""
    from text_crdt_rust_tpu.serve import loadgen

    a = loadgen.parse_args(
        ["--engine", engine, "--docs", str(docs), "--shards", str(shards),
         "--lanes", str(lanes), "--ticks", str(ticks),
         "--events-per-tick", str(events), "--zipf", str(zipf),
         "--pipeline-ticks", "2", "--train-ticks", "4"])
    gen = loadgen.loadgen_from_args(a)
    srv = gen.server
    # Pallas backends carry ``interpret``; the flat backend is XLA.
    modes = {b.interpret for b in srv.residency.backends
             if hasattr(b, "interpret")}
    if modes - {not compiled}:
        raise AssertionError(f"serve[{engine}]: lane backends run with "
                             f"interpret={modes}, want {not compiled}")
    t0 = time.perf_counter()
    report = gen.run()
    wall = time.perf_counter() - t0
    if not report["converged"]:
        raise AssertionError(f"serve[{engine}]: diverged: "
                             f"{report['mismatches']}")
    # More docs than lanes: a doc left without a lane is given one (its
    # checkpointed state uploaded, evicting an already-checked doc), so
    # every doc is compared on a device lane, not only the resident set.
    in_lane = host_only = 0
    for i, world in enumerate(gen.worlds):
        doc = srv.ensure_resident(world.doc_id)
        if not doc.in_lane and not doc.degraded:
            srv.residency.try_assign_lane(doc, srv.tick_no + 1 + i)
        if not srv.verify_doc(world.doc_id):
            raise AssertionError(f"serve[{engine}]: {world.doc_id} lane "
                                 f"!= host oracle")
        if doc.in_lane:
            in_lane += 1
        else:
            host_only += 1
    st = report["server"]
    if in_lane == 0 or not st.get("evictions") or not st.get("restores"):
        raise AssertionError(f"serve[{engine}]: the run never exercised "
                             f"lanes ({in_lane}) and eviction/restore "
                             f"({st.get('evictions')}/"
                             f"{st.get('restores')})")
    out = dict(engine=engine, docs=docs, lanes=shards * lanes,
               ticks=ticks, item_ops=report["item_ops_applied"],
               lanes_verified=in_lane, host_only=host_only,
               evictions=st["evictions"], restores=st["restores"],
               device_compiles=report["obs"]["device_compiles"],
               loop_wall_s=report["device_ticks_wall_s"],
               wall_s=round(wall, 3))
    say("serve", **out)
    return out


def phase_mesh(devices, trace: str = "sveltecomponent", docs: int = 64,
               patches: int = 0) -> dict:
    """``parallel.mesh``'s sharded apply at dp=len(devices) over ``docs``
    distinct documents (doc i replays a shorter prefix of the trace),
    against the same batch on one device and the host oracles."""
    import jax
    import numpy as np

    from text_crdt_rust_tpu.common import LocalOp
    from text_crdt_rust_tpu.models.oracle import ListCRDT
    from text_crdt_rust_tpu.ops import batch as B
    from text_crdt_rust_tpu.ops import span_arrays as SA
    from text_crdt_rust_tpu.parallel import (
        make_mesh,
        make_sharded_apply,
        shard_ops,
    )
    from text_crdt_rust_tpu.serve.batcher import oracle_signed
    from text_crdt_rust_tpu.utils.testdata import (
        flatten_patches,
        load_testing_data,
        trace_path,
    )

    n = len(devices)
    pts = flatten_patches(load_testing_data(trace_path(trace)))
    if patches:
        pts = pts[:patches]
    stride = max(1, len(pts) // (2 * docs))
    cuts = [len(pts) - i * stride for i in range(docs)]
    want = _contents_at(pts, cuts)
    streams, next_orders = [], []
    for c in cuts:
        ops, nxt = B.compile_local_patches(pts[:c], lmax=16)
        streams.append(ops)
        next_orders.append(nxt)
    ops = B.stack_ops(streams)
    capacity = _round_up(sum(len(p.ins_content) for p in pts), 1024)
    ocap = _round_up(max(next_orders), 1024)
    base = SA.stack_docs(SA.make_flat_doc(capacity, ocap), docs)

    results, walls = {}, {}
    for name, devs in (("dp", devices), ("one", devices[:1])):
        mesh = make_mesh(devices=devs, dp=len(devs), sp=1)
        apply = make_sharded_apply(mesh, donate=False)
        t0 = time.perf_counter()
        out = jax.block_until_ready(apply(base, shard_ops(ops, mesh)))
        walls[name] = round(time.perf_counter() - t0, 3)
        if name == "dp":
            placed = {s.device for s in out.signed.addressable_shards}
            if placed != set(devices):
                raise AssertionError(f"mesh: shards sit on {placed}, "
                                     f"want all of {set(devices)}")
        results[name] = jax.tree.map(np.asarray, out)
    same = jax.tree.map(np.array_equal, results["dp"], results["one"])
    if not all(jax.tree.leaves(same)):
        raise AssertionError(f"mesh: dp={n} and one device differ: {same}")
    host = results["dp"]
    for i, c in enumerate(cuts):
        got = SA.to_string(jax.tree.map(lambda f: f[i], host))
        if got != want[c]:
            raise AssertionError(f"mesh: doc {i} ({c} patches) != the "
                                 f"host splice oracle")
    oracle = ListCRDT()
    agent = oracle.get_or_create_agent_id("mesh")
    for p in pts[:cuts[0]]:
        oracle.apply_local_txn(agent, [LocalOp(
            pos=p.pos, ins_content=p.ins_content, del_span=p.del_len)])
    rows = int(host.n[0])
    if not np.array_equal(host.signed[0][:rows], oracle_signed(oracle)):
        raise AssertionError("mesh: doc 0 != the host ListCRDT oracle")
    out = dict(trace=trace, docs=docs, devices=n, steps=ops.num_steps,
               capacity=capacity, order_capacity=ocap,
               state_bytes=sum(int(v.nbytes) for v in jax.tree.leaves(host)),
               dp_first_call_s=walls["dp"], one_first_call_s=walls["one"])
    say("mesh", **out)
    return out


def count_cache_events():
    """A live count of JAX's persistent-compile-cache events in this
    process (``compile_requests_use_cache``, ``cache_hits``,
    ``cache_misses``): whether a run found its programs cached."""
    import collections

    import jax

    seen = collections.Counter()

    def on_event(event, **_):
        if event.startswith("/jax/compilation_cache/"):
            seen[event.rsplit("/", 1)[1]] += 1

    jax.monitoring.register_event_listener(on_event)
    return seen


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the mesh phase alone, on four chips")
    args = ap.parse_args(argv)
    devs = require_tpu(args.chips)
    try:
        from text_crdt_rust_tpu.utils.compile_cache import (
            enable_compile_cache,
        )
    except ImportError as e:
        raise SystemExit(f"chip_smoke: run from the repo checkout ({e})")
    say("setup", compile_cache=enable_compile_cache(),
        device=devs[0].device_kind, chips=len(devs))
    cache = count_cache_events()
    if args.chips == 4:
        phases = [("mesh", lambda: phase_mesh(devs))]
    else:
        phases = [("replay", phase_replay)] + [
            (f"serve[{e}]", lambda e=e: phase_serve(e))
            for e in ("flat", "rle-lanes-mixed")]
    for name, run in phases:
        run()
        say("compile-cache", after=name,
            requests=cache["compile_requests_use_cache"],
            hits=cache["cache_hits"], misses=cache["cache_misses"])
    import jax

    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
