"""Production sync pipeline demo: N documents, each streaming three
peers' remote ops through the causal buffer onto the per-lane engine.

The end-to-end shape a reference user needs for "apply_remote_txn at
scale" (`doc.rs:242-348` × N documents): per doc, three peers edit
concurrently, their RemoteTxns arrive interleaved and OUT OF ORDER
from the network, ``parallel.causal.CausalBuffer`` holds them until
causally ready, ``ops.batch.compile_remote_txns`` turns the released
stream into device steps, and ``ops.rle_lanes_mixed`` applies every
document's own stream — one op per lane per kernel step — with
device-resident state (runs + by-order tables) carried across chunks.
Every chunk is verified against the Python oracle.

Usage::

    python -m text_crdt_rust_tpu.examples.sync_stream \
        [--docs N] [--chunks C] [--ops-per-chunk K] [--seed S]

The platform comes from ``JAX_PLATFORMS``: on the chip the kernel runs
compiled, elsewhere (``JAX_PLATFORMS=cpu``) in Pallas interpret mode.
"""
from __future__ import annotations

import argparse
import random
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs", type=int, default=8)
    ap.add_argument("--chunks", type=int, default=3)
    ap.add_argument("--ops-per-chunk", type=int, default=15,
                    help="patches per peer per chunk")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax

    interpret = jax.default_backend() != "tpu"

    from ..common import txn_len
    from ..models.oracle import ListCRDT
    from ..models.sync import export_txns_since
    from ..ops import batch as B
    from ..ops import rle_lanes as RL
    from ..ops import rle_lanes_mixed as RLM
    from ..parallel.causal import CausalBuffer
    from ..utils.randedit import random_patches

    rng = random.Random(args.seed)
    n = args.docs
    print(f"sync_stream: {n} docs x {args.chunks} chunks x "
          f"3 peers x {args.ops_per_chunk} patches (seed={args.seed})")

    # Each doc's "network": three peer replicas editing concurrently;
    # their txn streams interleave and arrive shuffled per chunk.
    peers = []
    for d in range(n):
        pair = []
        for name in ("ann", "bob", "cyd"):
            doc = ListCRDT()
            agent = doc.get_or_create_agent_id(name)
            pair.append((doc, agent, [0]))  # [watermark]
        peers.append(pair)

    def peer_chunk(doc, agent, wm):
        patches, _ = random_patches(rng, args.ops_per_chunk)
        # Continue this peer's own replica with fresh random edits.
        for p in patches:
            ln = len(doc)
            pos = min(p.pos, ln)
            if p.del_len and ln:
                doc.local_delete(agent, min(pos, ln - 1),
                                 min(p.del_len, ln - min(pos, ln - 1)))
            if p.ins_content:
                doc.local_insert(agent, min(pos, len(doc)),
                                 p.ins_content)
        txns = export_txns_since(doc, wm[0])
        wm[0] = doc.get_next_order()
        return txns

    import numpy as np

    buffers = [CausalBuffer() for _ in range(n)]
    tables = [B.AgentTable() for _ in range(n)]
    assigners = [None] * n
    oracles = [ListCRDT() for _ in range(n)]
    state = None
    rkl_acc = None  # host-accumulated author ranks: the YATA tiebreak
    #                 reads EXISTING items' ranks from the read-only rkl
    #                 input, so earlier chunks' entries must stay visible
    applied_txns = 0
    applied_ops = 0
    total_steps = 0
    t0 = time.perf_counter()
    for c in range(args.chunks):
        opses = []
        for d in range(n):
            arrivals = []
            for doc, agent, wm in peers[d]:
                arrivals.extend(peer_chunk(doc, agent, wm))
            rng.shuffle(arrivals)  # the network reorders
            released = buffers[d].add_all(arrivals)
            for t in released:
                tables[d].add(t.id.agent)
                oracles[d].apply_remote_txn(t)
            ops, assigners[d] = B.compile_remote_txns(
                released, tables[d], assigner=assigners[d], lmax=8,
                dmax=None)
            opses.append(ops)
            applied_txns += len(released)
            applied_ops += sum(txn_len(t) for t in released)
        stacked = B.stack_ops(opses)
        # Rows accumulate across chunks (<= 2 per compiled step), so
        # the capacity bound is CUMULATIVE steps, not this chunk's.
        total_steps += stacked.num_steps
        capacity = ((1 + 2 * total_steps + 63) // 64) * 64
        adv = int(np.asarray(stacked.order_advance,
                             np.int64).sum(axis=0).max())
        base = rkl_acc.shape[0] if rkl_acc is not None else 0
        ocap = ((base + adv + 8 + 7) // 8) * 8
        _, _, rkl_c = RLM.lane_tables(stacked, ocap)
        if rkl_acc is not None:
            grown = np.zeros((ocap, n), np.int32)
            grown[: rkl_acc.shape[0]] = rkl_acc
            rkl_acc = np.where(rkl_c != 0, rkl_c, grown)
        else:
            rkl_acc = rkl_c
        run = RLM.make_replayer_lanes_mixed(
            stacked, capacity=capacity, order_capacity=ocap,
            chunk=16, init=state, rkl=rkl_acc, interpret=interpret)
        res = run()
        res.check()
        state = res.state()

        for d in range(n):
            want = [(-1 if oracles[d].deleted[i] else 1)
                    * (int(oracles[d].order[i]) + 1)
                    for i in range(oracles[d].n)]
            got = RL.expand_lane(res, d).tolist()
            assert got == want, f"doc {d} diverged from oracle"
        print(f"  chunk {c + 1}/{args.chunks}: {applied_txns} txns / "
              f"{applied_ops} char-ops applied, capacity {capacity}, "
              f"all {n} docs == oracle")
    for d in range(n):
        assert buffers[d].pending == 0, (
            f"doc {d}: {buffers[d].pending} txns never became ready "
            f"({buffers[d].missing()})")
    wall = time.perf_counter() - t0
    print(f"  done: {applied_txns} remote txns ({applied_ops} char-ops) "
          f"across {n} docs in {wall:.1f}s; every chunk oracle-checked")
    return 0


if __name__ == "__main__":
    sys.exit(main())
