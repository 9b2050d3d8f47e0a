"""Driver ``divergent``: a fleet of distinct documents replayed through
``ops.rle_lanes``, every lane of a pass its own history.

The configuration's ``traces`` are whole editing histories, each
compiled once through the program's local path
(``ops.batch.merge_patches`` -> ``compile_local_patches`` ->
``fuse_steps``); the pass's ``lanes_per_pass`` streams, one per lane by
the configuration's lane map, are stacked with ragged lengths
(``stack_ops``, the shorter lanes running no-op tail steps) and
replayed by ``ops.rle_lanes.make_replayer_lanes_blocked``.  The lane map
is a permutation drawn from the configuration's own seed, not the
run's, so that every run does the same work.  The fleet and the window
are the replay driver's: resident slots, passes back to back, each
overwriting its slot, at most ``in_flight`` ahead of the host.

Correct means every document equals the plain splice reference of its
own trace: one canonical lane per trace is drawn from the seed, every
lane of every slot is compared on the device with the canonical lane of
its trace, and the canonical lanes and a seed-drawn sample of others
are read back, decoded by the benchmark's reader and compared with
``splice_orders`` of their trace.  A lane holds ``ops.rle``'s block
layout with a lane of block tables per document (``nlog`` the logical
blocks in use, ``blkord`` the physical block of each logical slot,
``rws`` its rows, ``liv`` its live characters), so the reader is
``decode_rle_lane``.  The numbers compared are the count of documents
that differ and of passes that raised an error flag: both must be 0.

A program without the per-lane padding counter
(``ops.rle_lanes.LaneSteps``) cannot run the cell: the driver stops
before anything is compiled.

Faults (``--fault``, for the control and the tests only): ``control``
puts the reference's own states, each trace less one seed-drawn patch,
in the program's place; ``unchanged``, ``half`` and ``altered`` are the
replay driver's; ``collapsed`` gives every lane the state of one
automerge-paper lane, as a program that took the lanes for copies.
"""
from __future__ import annotations

import collections
import dataclasses
import random
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.drivers.replay import _copy, _host_mark, _pick, _stalls
from benchmark.harness import BenchError, Check, Outcome
from benchmark.reference import (
    decode_rle_lane,
    encode_rle_lane,
    load_trace,
    splice_orders,
    visible_orders,
)

PLANES = ("ordp", "lenp", "nlog", "blkord", "rws", "liv", "ol", "orr")


def lane_map(cfg: dict) -> list:
    """The trace index of every lane of a pass: ``lanes`` of each trace,
    in an order drawn from the configuration's ``lane_seed``."""
    lanes = [t for t, tr in enumerate(cfg["traces"])
             for _ in range(tr["lanes"])]
    if len(lanes) != cfg["lanes_per_pass"]:
        raise BenchError(f"the traces' lanes sum to {len(lanes)}, not "
                         f"lanes_per_pass {cfg['lanes_per_pass']}")
    random.Random(cfg["lane_seed"]).shuffle(lanes)
    return lanes


def encode_lanes_lane(orders: np.ndarray, capacity: int, block_k: int,
                      nbt: int):
    """Visible orders -> one lane's (ordp, lenp, nlog, blkord, rws, liv):
    ``encode_rle_lane``'s runs and blocks, every character live.  The
    control puts a reference's state in the program's place with it."""
    ordp, lenp, blkord, rws, nlog = encode_rle_lane(orders, capacity,
                                                    block_k, nbt)
    liv = np.zeros(nbt, np.int32)
    for slot in range(nlog):
        b = int(blkord[slot])
        liv[slot] = int(lenp[b * block_k: b * block_k + rws[slot]].sum())
    return ordp, lenp, nlog, blkord, rws, liv


def _program():
    """The program's per-lane engine, or a clean refusal."""
    from text_crdt_rust_tpu.ops import rle_lanes as RL

    if not hasattr(RL, "LaneSteps"):
        raise BenchError("ops.rle_lanes has no per-lane padding counter "
                         "(LaneSteps): this program cannot run the cell")
    return RL


def _build(cfg: dict, traces, lanes, interpret: bool):
    """The program's local path for each trace, its lanes stacked."""
    from text_crdt_rust_tpu.ops import batch as B
    from text_crdt_rust_tpu.utils.testdata import TestPatch

    RL = _program()
    eng = cfg["engine"]
    streams = []
    for patches in traces:
        merged = B.merge_patches([TestPatch(p, d, s) for p, d, s in patches])
        ops, _ = B.compile_local_patches(merged, lmax=eng["lmax"], dmax=None)
        ops, _ = B.fuse_steps(ops, fuse_w=eng["fuse_w"])
        streams.append(ops)
    stacked = B.stack_ops([streams[t] for t in lanes], chars=False)
    run = RL.make_replayer_lanes_blocked(
        stacked, capacity=eng["capacity"], block_k=eng["block_k"],
        chunk=eng["chunk"], interpret=interpret)
    return streams, run


def _arrays(res):
    return [getattr(res, p) for p in PLANES] + [res.err]


def _clone(res):
    """A copy of a pass's state in buffers of its own."""
    return dataclasses.replace(res, **dict(zip(
        PLANES + ("err",), _copy(_arrays(res)))))


def _control(shape, cfg, traces, lanes, seed):
    """The reference's own states, each trace less one seed-drawn patch,
    encoded into the program's place."""
    eng = cfg["engine"]
    nbt = shape.blkord.shape[0]
    rng = random.Random(seed)
    cols = []
    for patches in traces:
        drop = rng.randrange(len(patches))
        orders, _ = splice_orders(patches[:drop] + patches[drop + 1:])
        cols.append(encode_lanes_lane(orders, eng["capacity"],
                                      eng["block_k"], nbt))
    state = {}
    for i, p in enumerate(("ordp", "lenp", "nlog", "blkord", "rws",
                           "liv")):
        state[p] = jnp.asarray(np.stack(
            [np.reshape(cols[t][i], -1) for t in lanes], axis=1),
            jnp.int32)
    state.update(ol=shape.ol * 0, orr=shape.orr * 0, err=shape.err * 0)
    return lambda: dataclasses.replace(shape, **{
        k: v + 0 for k, v in state.items()})


def _plant(fault, run, shape, cfg, traces, lanes, seed):
    """``run`` with the named fault under it."""
    if fault is None:
        return run
    if fault == "control":
        return _control(shape, cfg, traces, lanes, seed)
    if fault == "unchanged":
        return lambda: dataclasses.replace(run(), **{
            p: jnp.zeros_like(getattr(shape, p)) for p in PLANES})
    if fault == "half":
        def half():
            res = run()
            b = res.batch // 2
            return dataclasses.replace(res, **{
                p: getattr(res, p).at[..., b:].set(0) for p in PLANES})
        return half
    if fault == "altered":
        def altered():
            res = run()
            return dataclasses.replace(
                res, ordp=res.ordp.at[0, res.batch - 1].add(1))
        return altered
    if fault == "collapsed":
        lane = lanes.index(0)

        def collapsed():
            res = run()
            return dataclasses.replace(res, **{
                p: jnp.broadcast_to(getattr(res, p)[:, lane:lane + 1],
                                    getattr(res, p).shape) + 0
                for p in PLANES})
        return collapsed
    raise ValueError(f"unknown fault {fault!r}")


@jax.jit
def _differing(planes, refs, lane_trace):
    """Per lane, whether it differs from its trace's canonical lane in
    any plane (``refs``: each plane's canonical columns, one per
    trace)."""
    same = None
    for p, r in zip(planes, refs):
        eq = jnp.all(p == jnp.take(r, lane_trace, axis=1), axis=0)
        same = eq if same is None else same & eq
    return ~same


def _decode(res, lanes, block_k: int, width: int):
    """Read ``lanes`` of one slot back (one gather of ``width``
    columns, so that no lane index compiles a program of its own) and
    decode each with the benchmark's own reader."""
    idx = np.zeros(width, np.int32)
    idx[:len(lanes)] = lanes
    cols = jax.device_get(_pick([res.ordp, res.lenp, res.nlog, res.blkord,
                                 res.rws], jnp.asarray(idx)))
    return [decode_rle_lane(cols[0][:, i], cols[1][:, i], cols[3][:, i],
                            cols[4][:, i], int(cols[2][0, i]), block_k)
            for i in range(len(lanes))]


def run(ctx) -> Outcome:
    from text_crdt_rust_tpu.obs.trace import span_totals

    cfg, mix = ctx.config, ctx.traffic
    _program()
    lanes = lane_map(cfg)
    B = cfg["lanes_per_pass"]
    n_slots = cfg["documents"] // B
    traces = []
    for tr in cfg["traces"]:
        patches, _ = load_trace(tr["name"])
        if cfg.get("patches"):
            patches = patches[:cfg["patches"]]
        traces.append(patches)
    streams, program = _build(cfg, traces, lanes, ctx.interpret)
    first = program()
    first.check()
    build = span_totals()["rle.build"]
    step = _plant(ctx.fault, program, first, cfg, traces, lanes, ctx.seed)
    # A window runs fewer passes than the fleet has slots, so set-up
    # fills them from the step: a planted fault is in every slot.
    fill = first if ctx.fault is None else step()
    slots = [fill] + [_clone(fill) for _ in range(n_slots - 1)]
    jax.block_until_ready([_arrays(r) for r in slots])
    steps = program.lane_steps
    ctx.log(f"[divergent] traces={[tr['name'] for tr in cfg['traces']]} "
            f"patches={[len(p) for p in traces]} "
            f"steps={[int(s.num_steps) for s in streams]} "
            f"lane_steps={steps} slots={n_slots} lanes={B} "
            f"blocks_used={int(np.asarray(first.nlog).max())} "
            f"build_s={build['total_s']} "
            f"build_compiles={build['compiles']} "
            f"state_bytes_per_slot="
            f"{sum(int(a.nbytes) for a in _arrays(slots[0]))}")

    depth = min(mix["in_flight"], n_slots - 1)
    inflight = collections.deque()
    passes = 0
    err_passes = 0
    marks = []
    with ctx.window(labels=("dispatch", "sync")):
        setup_s = ctx.setup_done()
        t0, t0_unix = time.perf_counter(), time.time()
        while True:
            marks.append(_host_mark())
            with ctx.annotate("dispatch"):
                res = step()
            slots[passes % n_slots] = res
            inflight.append(res)
            passes += 1
            if len(inflight) > depth:
                with ctx.annotate("sync"):
                    err_passes += int(np.asarray(
                        inflight.popleft().err).max() != 0)
            if time.perf_counter() - t0 >= ctx.seconds:
                break
        marks.append(_host_mark())
        with ctx.annotate("sync"):
            jax.block_until_ready([_arrays(r) for r in inflight])
        wall = time.perf_counter() - t0
    ctx.read_memory_peak()
    for r in inflight:
        err_passes += int(np.asarray(r.err).max() != 0)
    per_pass = sum(len(traces[t]) for t in lanes)
    ctx.log(f"[divergent] window passes={passes} docs={passes * B} "
            f"wall_s={wall} setup_s={setup_s} in_flight={depth} "
            f"window_t0_unix={t0_unix} host_stalls={_stalls(marks, t0)}")

    # The reference, once the window has closed.
    t_ref = time.perf_counter()
    wants = [splice_orders(p)[0] for p in traces]
    rng = random.Random(ctx.seed)
    by_trace = [[b for b in range(B) if lanes[b] == t]
                for t in range(len(traces))]
    canon = [(rng.randrange(n_slots), rng.choice(bs)) for bs in by_trace]
    refs = [jnp.stack([getattr(slots[s], p)[:, b] for s, b in canon],
                      axis=1) for p in PLANES]
    lane_trace = jnp.asarray(lanes, jnp.int32)
    masks = [np.asarray(_differing([getattr(r, p) for p in PLANES], refs,
                                   lane_trace)) for r in slots]
    block_k = cfg["engine"]["block_k"]
    width = mix["sample_docs"]
    sample = canon + [(rng.randrange(n_slots), rng.randrange(B))
                      for _ in range(width - len(canon))]
    by_slot = collections.defaultdict(list)
    for s, b in sample:
        by_slot[s].append(b)
    decoded_wrong = set()
    for s, bs in by_slot.items():
        for b, signed in zip(bs, _decode(slots[s], bs, block_k, width)):
            if not np.array_equal(visible_orders(signed), wants[lanes[b]]):
                decoded_wrong.add((s, b))
    canon_bad = [c in decoded_wrong for c in canon]
    wrong = set(decoded_wrong)
    for s, mask in enumerate(masks):
        for b in range(B):
            if mask[b] or canon_bad[lanes[b]]:
                wrong.add((s, b))
    ctx.log(f"[divergent] reference_s={time.perf_counter() - t_ref} "
            f"canonical={canon} canonical_bad={canon_bad} "
            f"sampled={len(sample)}")
    chunk = cfg["engine"]["chunk"]
    s_max = max(int(s.num_steps) for s in streams)
    return Outcome(
        e2e={"replay_ops_per_s": per_pass * passes / wall,
             "setup_s": setup_s},
        checks=[Check("docs_wrong", len(wrong), 0),
                Check("err_passes", err_passes, 0)],
        attempted=n_slots * B, failed=len(wrong),
        records={"passes": passes, "window_s": wall,
                 "lane_steps": {"real": steps.real,
                                "stepped": steps.stepped},
                 "rle_lanes_shape": {
                     "steps_padded": -(-s_max // chunk) * chunk,
                     "batch": B, "capacity": cfg["engine"]["capacity"],
                     "block_k": block_k}})
