"""Mixed-stream RLE run engine (remote ops on run rows) vs oracle.

Interpreter-mode differential tests. Tiny blocks (block_k=8) force leaf
SPLITS between remote lookups, exercising the stale-ordblk fallback and
self-heal on the run representation; the scenarios mirror
``test_blocked_mixed`` (the `doc.rs:242-348` apply paths) plus the
config-4 concurrent-insert storm and cross-engine local equality with
``ops.rle``, and the kernel's integrate counters.
"""
import random

import numpy as np
import pytest

from text_crdt_rust_tpu.common import (
    RemoteDel,
    RemoteId,
    RemoteIns,
    RemoteTxn,
)
from text_crdt_rust_tpu.models.oracle import ListCRDT
from text_crdt_rust_tpu.models.sync import export_txns_since
from text_crdt_rust_tpu.ops import batch as B
from text_crdt_rust_tpu.ops import rle as R
from text_crdt_rust_tpu.ops import rle_mixed as RM
from text_crdt_rust_tpu.ops import span_arrays as SA
from text_crdt_rust_tpu.utils.randedit import make_storm

from test_device_flat import (
    oracle_from_patches,
    random_patches,
)

ROOT = RemoteId("ROOT", 0xFFFFFFFF)


def replay_txns(txns, capacity, block_k=8, lmax=4, chunk=128, dmax=16):
    table = B.AgentTable()
    for t in txns:
        table.add(t.id.agent)
        for op in t.ops:
            if hasattr(op, "id"):
                table.add(op.id.agent)
    ops, _ = B.compile_remote_txns(txns, table, lmax=lmax, dmax=dmax)
    res = RM.replay_mixed_rle(ops, capacity=capacity, batch=8,
                              block_k=block_k, chunk=chunk, interpret=True)
    return R.rle_to_flat(ops, res)


def oracle_txns(txns):
    doc = ListCRDT()
    for t in txns:
        doc.apply_remote_txn(t)
    return doc


# The scenario classes are a heavy interpret-mode matrix: slow tier.
# Tier-1 keeps rle-mixed coverage via test_rle_mixed_fast.TestTier1Smoke,
# the blocked-lanes fuzz and ``TestIntegrateCounters`` below.
@pytest.mark.slow
class TestMixedRleLocal:
    def test_local_stream_matches_rle(self):
        # KIND_LOCAL handling must stay bit-identical to ops.rle.
        rng = random.Random(13)
        patches, content = random_patches(rng, 60)
        merged = B.merge_patches(patches)
        ops, _ = B.compile_local_patches(merged, lmax=8, dmax=None)
        res = RM.replay_mixed_rle(ops, capacity=256, batch=8, block_k=8,
                                  chunk=128, interpret=True)
        doc = R.rle_to_flat(ops, res)
        ref = R.replay_local_rle(ops, capacity=256, batch=8, block_k=8,
                                 chunk=128, interpret=True)
        ref_doc = R.rle_to_flat(ops, ref)
        assert SA.to_string(doc) == SA.to_string(ref_doc) == content
        assert SA.doc_spans(doc) == SA.doc_spans(ref_doc)


@pytest.mark.slow
class TestMixedRleRemote:
    def test_concurrent_root_inserts_tiebreak(self):
        # Config-4 storm shape: peers insert at the same point with the
        # same origins; order = the name tiebreak (`doc.rs:206-216`).
        txns = [
            RemoteTxn(id=RemoteId(name, 0), parents=[],
                      ops=[RemoteIns(ROOT, ROOT, text)])
            for name, text in [("zed", "zz"), ("amy", "aa"), ("mia", "mm")]
        ]
        oracle = oracle_txns(txns)
        doc = replay_txns(txns, capacity=64, block_k=8)
        assert SA.to_string(doc) == oracle.to_string()
        assert SA.doc_spans(doc) == oracle.doc_spans()

    @pytest.mark.parametrize("seed", [3, 21])
    def test_two_peer_random_merge(self, seed):
        rng = random.Random(seed)
        pa, _ = random_patches(rng, 40)
        pb, _ = random_patches(rng, 40)
        a = oracle_from_patches(pa, agent="peer-a")
        bdoc = oracle_from_patches(pb, agent="peer-b")
        txns = export_txns_since(a, 0) + export_txns_since(bdoc, 0)
        oracle = oracle_txns(txns)
        doc = replay_txns(txns, capacity=512, block_k=8)
        assert SA.to_string(doc) == oracle.to_string()
        assert SA.doc_spans(doc) == oracle.doc_spans()

    def test_order_contiguous_unchained_no_merge(self):
        # Round-5 regression: three single-char root inserts get
        # order-contiguous orders (0,1,2); zed's char must NOT merge
        # into amy's run (its origin_left is ROOT, not amy), else the
        # YATA run-skip hides it from mid's scan and the doc diverges
        # (was: "azm" instead of "amz").
        txns = [
            RemoteTxn(id=RemoteId(n, 0), parents=[],
                      ops=[RemoteIns(ROOT, ROOT, t)])
            for n, t in [("amy", "a"), ("zed", "z"), ("mid", "m")]
        ]
        oracle = oracle_txns(txns)
        doc = replay_txns(txns, capacity=64, block_k=8)
        assert SA.to_string(doc) == oracle.to_string() == "amz"
        assert SA.doc_spans(doc) == oracle.doc_spans()

    def test_remote_delete_fragmented_and_double(self):
        base = RemoteTxn(id=RemoteId("amy", 0), parents=[],
                         ops=[RemoteIns(ROOT, ROOT, "abcdef")])
        d1 = RemoteTxn(id=RemoteId("bob", 0), parents=[RemoteId("amy", 5)],
                       ops=[RemoteDel(RemoteId("amy", 1), 3)])
        d2 = RemoteTxn(id=RemoteId("cat", 0), parents=[RemoteId("amy", 5)],
                       ops=[RemoteDel(RemoteId("amy", 2), 3)])
        txns = [base, d1, d2]
        oracle = oracle_txns(txns)
        doc = replay_txns(txns, capacity=64, block_k=8)
        assert SA.to_string(doc) == oracle.to_string() == "af"
        assert SA.doc_spans(doc) == oracle.doc_spans()

    def test_local_remote_convergence(self):
        # The reference's `remote_txns` convergence check (`doc.rs:620-676`).
        rng = random.Random(5)
        patches, _ = random_patches(rng, 60)
        local = oracle_from_patches(patches, agent="conv")
        txns = export_txns_since(local, 0)
        doc = replay_txns(txns, capacity=512, block_k=8)
        assert SA.to_string(doc) == local.to_string()
        assert SA.doc_spans(doc) == local.doc_spans()

    def test_storm_interleaved_peers(self):
        # N peers typing concurrently at interleaved positions, merged into
        # one causal stream — splits hit between remote integrations,
        # exercising the stale-index fallback + heal on run rows.
        rng = random.Random(99)
        peers = []
        for name in ("ada", "bea", "cyd", "dot"):
            patches, _ = random_patches(rng, 25)
            peers.append(oracle_from_patches(patches, agent=name))
        txns = []
        for p in peers:
            txns.extend(export_txns_since(p, 0))
        oracle = oracle_txns(txns)
        doc = replay_txns(txns, capacity=1024, block_k=8)
        assert SA.to_string(doc) == oracle.to_string()
        assert SA.doc_spans(doc) == oracle.doc_spans()

    @pytest.mark.parametrize("dmax", [16, None])
    def test_long_remote_delete(self, dmax):
        # A 40-target delete both dmax-chunked and UNCHUNKED (the
        # one-pass interval delete takes any length in one step) must
        # converge; the unchunked form spans multiple 16-row blocks,
        # exercising the plane-wide flip + slot-count gather.
        base = RemoteTxn(id=RemoteId("amy", 0), parents=[],
                         ops=[RemoteIns(ROOT, ROOT, "x" * 50)])
        kill = RemoteTxn(id=RemoteId("bob", 0), parents=[RemoteId("amy", 49)],
                         ops=[RemoteDel(RemoteId("amy", 5), 40)])
        txns = [base, kill]
        oracle = oracle_txns(txns)
        doc = replay_txns(txns, capacity=128, block_k=16, lmax=16,
                          dmax=dmax)
        assert SA.to_string(doc) == oracle.to_string() == "x" * 10
        assert SA.doc_spans(doc) == oracle.doc_spans()

    def test_unchunked_delete_spans_many_fragmented_blocks(self):
        # Interleave two peers' typing so amy's chars are fragmented
        # across blocks, then delete amy's whole range unchunked: full
        # covers flip plane-wide in ONE step while bob's interleaved
        # chars survive.
        txns = []
        for k in range(12):
            txns.append(RemoteTxn(
                id=RemoteId("amy", 2 * k), parents=[],
                ops=[RemoteIns(ROOT if k == 0 else RemoteId("amy", 2 * k - 1),
                               ROOT, "aa")]))
        for k in range(12):
            txns.append(RemoteTxn(
                id=RemoteId("bob", k), parents=[],
                ops=[RemoteIns(ROOT if k == 0 else RemoteId("bob", k - 1),
                               RemoteId("amy", 2 * k), "B")]))
        txns.append(RemoteTxn(
            id=RemoteId("cat", 0), parents=[RemoteId("amy", 23)],
            ops=[RemoteDel(RemoteId("amy", 2), 20)]))
        oracle = oracle_txns(txns)
        doc = replay_txns(txns, capacity=128, block_k=8, lmax=4,
                          dmax=None)
        assert SA.to_string(doc) == oracle.to_string()
        assert SA.doc_spans(doc) == oracle.doc_spans()

    def test_delete_inside_merged_run_then_insert(self):
        # Insert into the middle of a TOMBSTONE run: the raw-position
        # splice must preserve the dead tail's sign/start (the
        # `_insert_splice_raw` negative-run fix-up).
        base = RemoteTxn(id=RemoteId("amy", 0), parents=[],
                         ops=[RemoteIns(ROOT, ROOT, "abcdefgh")])
        kill = RemoteTxn(id=RemoteId("amy", 8), parents=[RemoteId("amy", 7)],
                         ops=[RemoteDel(RemoteId("amy", 2), 4)])
        # bob saw only the base: inserts between d (amy,3) and e (amy,4),
        # both of which are now tombstones.
        mid = RemoteTxn(id=RemoteId("bob", 0), parents=[RemoteId("amy", 7)],
                        ops=[RemoteIns(RemoteId("amy", 3),
                                       RemoteId("amy", 4), "XY")])
        txns = [base, kill, mid]
        oracle = oracle_txns(txns)
        doc = replay_txns(txns, capacity=64, block_k=8)
        assert SA.to_string(doc) == oracle.to_string()
        assert SA.doc_spans(doc) == oracle.doc_spans()

    def test_config4_storm_oracle(self):
        # The bench config-4 workload shape end-to-end.
        txns, receiver = make_storm(4, 6, 2, seed=7)
        oracle = oracle_txns(txns)
        assert oracle.to_string() == receiver.to_string()
        doc = replay_txns(txns, capacity=512, block_k=8, lmax=8)
        assert SA.to_string(doc) == receiver.to_string()
        assert SA.doc_spans(doc) == oracle.doc_spans()

    def test_config4_delete_heavy_storm_oracle(self):
        # The bench delete-heavy variant: peers merge earlier rounds
        # and delete cross-peer spans (remote deletes, double deletes)
        # between the concurrent inserts.
        txns, receiver = make_storm(4, 8, 3, seed=7, del_prob=0.4)
        kinds = {type(op).__name__ for t in txns for op in t.ops}
        assert "RemoteDel" in kinds, "variant generated no deletes"
        oracle = oracle_txns(txns)
        assert oracle.to_string() == receiver.to_string()
        doc = replay_txns(txns, capacity=1024, block_k=8, lmax=8)
        assert SA.to_string(doc) == receiver.to_string()
        assert SA.doc_spans(doc) == oracle.doc_spans()

    @pytest.mark.parametrize("seed", [1, 17])
    def test_n_peer_random_interleavings_converge(self, seed):
        # SURVEY §4's missing `random_concurrency` test, on the device
        # engine: N peers editing independently, their txn streams
        # applied in DIFFERENT causally-valid interleavings, must
        # converge to one content — and match the oracle under the same
        # interleaving.
        rng = random.Random(seed)
        streams = []
        for name in ("kim", "lou", "max"):
            patches, _ = random_patches(rng, 20)
            streams.append(export_txns_since(
                oracle_from_patches(patches, agent=name), 0))

        def interleave(order_rng):
            queues = [list(s) for s in streams]
            out = []
            while any(queues):
                live = [q for q in queues if q]
                out.append(order_rng.choice(live).pop(0))
            return out

        results = []
        for k in range(2):
            txns = interleave(random.Random(seed * 100 + k))
            oracle = oracle_txns(txns)
            doc = replay_txns(txns, capacity=1024, block_k=8)
            assert SA.to_string(doc) == oracle.to_string()
            assert SA.doc_spans(doc) == oracle.doc_spans()
            results.append(SA.to_string(doc))
        assert results[0] == results[1], "interleavings diverged"


class TestIntegrateCounters:
    """``meta`` rows 1 and 2 of a pass count its remote inserts and those
    whose fast conflict scan flagged its window and ran the exact serial
    walk; counting changes nothing the kernel computes."""

    @pytest.fixture(scope="class")
    def stream(self):
        rng = random.Random(3)
        pa, _ = random_patches(rng, 24)
        pb, _ = random_patches(rng, 24)
        txns = (export_txns_since(oracle_from_patches(pa, agent="peer-a"), 0)
                + export_txns_since(oracle_from_patches(pb, agent="peer-b"),
                                    0))
        table = B.AgentTable()
        for t in txns:
            table.add(t.id.agent)
        ops, _ = B.compile_remote_txns(txns, table, lmax=4)
        return txns, ops

    def _replay(self, ops, fast):
        res = RM.replay_mixed_rle(ops, capacity=256, batch=8, block_k=8,
                                  chunk=128, interpret=True,
                                  fast_integrate=fast)
        res.check()
        return res

    @pytest.mark.parametrize("fast", [True, False])
    def test_counts_and_the_merged_document(self, stream, fast):
        txns, ops = stream
        res = self._replay(ops, fast)
        inserts = int(np.sum((np.asarray(ops.kind) == B.KIND_REMOTE_INS)
                             & (np.asarray(ops.ins_len) > 0)))
        counts = {res.integrate_counts(b) for b in range(res.batch)}
        assert counts == {res.integrate_counts()}
        remote, exact = res.integrate_counts()
        assert remote == inserts > 0
        assert 0 <= exact <= remote
        if not fast:
            assert exact == remote
        oracle = oracle_txns(txns)
        doc = R.rle_to_flat(ops, res)
        assert SA.to_string(doc) == oracle.to_string()
        assert SA.doc_spans(doc) == oracle.doc_spans()

    def test_vmem_budget_refuses_a_geometry_over_the_limit(self, stream):
        """``vmem_bytes`` counts the planes, tables and output blocks a
        pass keeps in VMEM; the replayer refuses a geometry over the
        kernel's limit before anything is staged or compiled."""
        _, ops = stream
        fits = RM.vmem_bytes(ops, 46208, batch=128, block_k=128, chunk=1024)
        assert 5 * 46208 * 128 * 4 < fits <= RM.VMEM_LIMIT_BYTES
        with pytest.raises(ValueError, match="VMEM"):
            RM.make_replayer_rle_mixed(ops, capacity=2 * 46208, batch=128,
                                       block_k=128, chunk=1024,
                                       interpret=True)
