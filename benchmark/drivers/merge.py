"""Driver ``merge``: concurrent histories merged into a resident fleet through
``ops.rle_mixed``.

The configuration's ``generator`` (crdt-benchmarks B2.4: ``clients``
editing concurrently from an empty document, ``rounds`` each, seeded
from the configuration and not from ``--seed``, so that every run does
the same work) gives each client's update, made by the benchmark's own
replicas (``merge_reference.b24_updates``).  The updates reach the
program as wire bytes (one columnar mux frame each, written by the
benchmark's ``ref/columnar.py``) and go through its normal remote path:
``net.codec.decode_frames`` -> ``ops.batch.compile_remote_txns`` ->
``ops.rle_mixed.make_replayer_rle_mixed``, ``lanes_per_pass``
documents to a pass, every document applying client A's update, then
client B's.  The fleet and the window are the replay driver's: resident
slots, passes back to back, each overwriting its slot, at most
``in_flight`` ahead of the host.

Correct means every document equals the sync server's replica in the
benchmark's plain reference (``ref/oracle.py``), tombstones included:
every lane of every slot is compared on the device with one canonical
lane drawn from the seed, and that lane and a seed-drawn sample of
others are read back, decoded by the benchmark's reader and compared
with the reference's ±(order+1) per character.  The numbers compared
are the count of documents that differ and of passes that raised an
error flag: both must be 0.

Faults (``--fault``, for the control and the tests only): ``control``
puts the reference's own state, with the last remote op of a seed-drawn
client left out, in the program's place; ``unchanged``, ``half`` and
``altered`` are the replay driver's.
"""
from __future__ import annotations

import collections
import dataclasses
import random
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.drivers.replay import (
    _arrays,
    _clone,
    _decode,
    _host_mark,
    _lanes_differing,
    _plant,
    _stalls,
)
from benchmark.harness import Check, Outcome
from benchmark.merge_reference import (
    b24_updates,
    encode_signed_lane,
    server_replica,
    signed_orders,
)
from benchmark.ref import columnar
from benchmark.ref.common import RemoteTxn

DOC_ID = "doc"


def _wire(updates) -> bytes:
    """Each client's update as one columnar mux frame."""
    return b"".join(columnar.encode_mux([(DOC_ID, u)]) for u in updates)


def _build(cfg: dict, updates, interpret: bool):
    """The program's remote path, from the wire bytes to the replayer."""
    from text_crdt_rust_tpu.net import codec
    from text_crdt_rust_tpu.obs.trace import span
    from text_crdt_rust_tpu.ops import batch as B
    from text_crdt_rust_tpu.ops import rle_mixed as RM

    eng = cfg["engine"]
    txns = [t for _, groups in codec.decode_frames(_wire(updates))
            for _, ts in groups for t in ts]
    table = B.AgentTable()
    for t in txns:
        table.add(t.id.agent)
    with span("compiler.remote"):
        ops, _ = B.compile_remote_txns(txns, table, lmax=eng["lmax"],
                                       dmax=None)
    geometry = dict(capacity=eng["capacity"], batch=cfg["lanes_per_pass"],
                    block_k=eng["block_k"], chunk=eng["chunk"])
    # The kernel's VMEM budget for this geometry, before its compile: a
    # program that states none (its whole-plane passes cannot hold the
    # source's N) stops here rather than minutes into a Mosaic refusal.
    vmem = RM.vmem_bytes(ops, **geometry)
    run = RM.make_replayer_rle_mixed(ops, interpret=interpret, **geometry)
    return ops, run, vmem


def _dropped_last_op(updates, seed: int):
    """The updates with the last op of a seed-drawn client left out:
    nothing depends on it, and every later order shifts."""
    c = random.Random(seed).randrange(len(updates))
    out = [list(u) for u in updates]
    last = out[c][-1]
    if len(last.ops) == 1:
        out[c].pop()
    else:
        out[c][-1] = RemoteTxn(last.id, last.parents, last.ops[:-1])
    return out


def _control(res_shape, cfg, updates, seed):
    eng = cfg["engine"]
    signed = signed_orders(server_replica(_dropped_last_op(updates, seed)))
    nblp = res_shape.blkord.shape[0]
    ordp, lenp, blkord, rows, nlog = encode_signed_lane(
        signed, eng["capacity"], eng["block_k"], nblp)
    b = res_shape.batch

    def lanes(col):
        return jnp.asarray(np.repeat(col[:, None], b, axis=1))

    meta = np.zeros((8, b), np.int32)
    meta[0] = nlog
    state = dict(ordp=lanes(ordp), lenp=lanes(lenp), blkord=lanes(blkord),
                 rows=lanes(rows), meta=jnp.asarray(meta),
                 ol=res_shape.ol * 0, orr=res_shape.orr * 0,
                 err=res_shape.err * 0)
    return lambda: dataclasses.replace(res_shape, **{
        k: v + 0 for k, v in state.items()})


def run(ctx) -> Outcome:
    cfg, mix = ctx.config, ctx.traffic
    gen = cfg["generator"]
    lanes = cfg["lanes_per_pass"]
    n_slots = cfg["documents"] // lanes
    updates, edits = b24_updates(gen["rounds"], gen["clients"], gen["seed"])
    ops, program, vmem = _build(cfg, updates, ctx.interpret)
    first = program()
    first.check()
    meta0 = np.asarray(first.meta)[:, 0]
    if ctx.fault == "control":
        step = _control(first, cfg, updates, ctx.seed)
    else:
        step = _plant(ctx.fault, program, first, cfg, None, ctx.seed)
    slots = [first] + [_clone(first) for _ in range(n_slots - 1)]
    jax.block_until_ready([_arrays(r) for r in slots])
    ctx.log(f"[merge] rounds={gen['rounds']} clients={gen['clients']} "
            f"edits={edits} steps={ops.num_steps} slots={n_slots} "
            f"lanes={lanes} blocks_used={int(meta0[0])} "
            f"remote_inserts={int(meta0[1])} "
            f"exact_fallbacks={int(meta0[2])} vmem_bytes={vmem} "
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
    docs = passes * lanes
    ctx.log(f"[merge] window passes={passes} docs={docs} wall_s={wall} "
            f"setup_s={setup_s} in_flight={depth} window_t0_unix={t0_unix} "
            f"host_stalls={_stalls(marks, t0)}")

    # The reference, once the window has closed.
    t_ref = time.perf_counter()
    want = signed_orders(server_replica(updates))
    rng = random.Random(ctx.seed)
    canon = (rng.randrange(n_slots), rng.randrange(lanes))
    differ = _lanes_differing(slots, *canon)
    block_k = cfg["engine"]["block_k"]
    chunk = cfg["engine"]["chunk"]
    width = mix["sample_docs"] + 1
    sample = [canon] + [(rng.randrange(n_slots), rng.randrange(lanes))
                        for _ in range(mix["sample_docs"])]
    by_slot = collections.defaultdict(list)
    for s, b in sample:
        by_slot[s].append(b)
    decoded_wrong = set()
    for s, bs in by_slot.items():
        for b, signed in zip(bs, _decode(slots[s], bs, block_k, width)):
            if not np.array_equal(signed, want):
                decoded_wrong.add((s, b))
    canon_ok = canon not in decoded_wrong
    wrong = set(decoded_wrong)
    for s, (_, mask) in enumerate(differ):
        for b in np.flatnonzero(mask | (not canon_ok)):
            wrong.add((s, int(b)))
    ctx.log(f"[merge] reference_s={time.perf_counter() - t_ref} "
            f"canonical={canon} canonical_ok={canon_ok} "
            f"sampled={len(sample)} chars={len(want)}")
    total = n_slots * lanes
    steps = int(ops.num_steps)
    orders = int(np.asarray(ops.order_advance, dtype=np.int64).sum())
    return Outcome(
        e2e={"replay_ops_per_s": edits * docs / wall, "setup_s": setup_s},
        checks=[Check("docs_wrong", len(wrong), 0),
                Check("err_passes", err_passes, 0)],
        attempted=total, failed=len(wrong),
        records={"passes": passes, "window_s": wall,
                 "integrate": {"remote_inserts": int(meta0[1]),
                               "exact_fallbacks": int(meta0[2])},
                 "rle_mixed_shape": {
                     "steps_padded": -(-steps // chunk) * chunk,
                     "steps": steps, "orders": orders,
                     "lmax": cfg["engine"]["lmax"], "batch": lanes,
                     "capacity": cfg["engine"]["capacity"],
                     "block_k": block_k}})
