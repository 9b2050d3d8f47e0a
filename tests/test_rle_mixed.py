"""Mixed-stream RLE run engine (remote ops on run rows) vs oracle.

Interpreter-mode differential tests. Tiny blocks (block_k=8) force leaf
SPLITS between remote lookups, exercising the order index's upkeep
through splits on the run representation; the scenarios cover the
`doc.rs:242-348` apply paths (root tiebreak, two-peer and N-peer merges,
fragmented, double and long remote deletes) plus the config-4 concurrent-insert storm and cross-engine local equality with
``ops.rle``, the kernel's integrate and lookup counters, and both paths
of the remote delete.
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
        # exercising the order index's upkeep on run rows.
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


def _word(rng, lo, hi):
    n = rng.randint(lo, hi)
    return "".join(rng.choice("abcdefghij") for _ in range(n))


def b24_history(agent, rounds, rng, doc=None):
    """A history shaped like crdt-benchmarks B2.4 ("concurrently insert &
    delete"), made by the oracle: each round a word of 3-9 letters at a
    uniform position, then with p = 0.5 a second word of 2-10 letters at
    the same index, else a delete of 1 to min(9, rest) chars there."""
    doc = ListCRDT() if doc is None else doc
    a = doc.get_or_create_agent_id(agent)
    for _ in range(rounds):
        pos = rng.randint(0, len(doc))
        doc.local_insert(a, pos, _word(rng, 3, 9))
        if rng.random() < 0.5:
            doc.local_insert(a, pos, _word(rng, 2, 10))
        else:
            doc.local_delete(a, pos, rng.randint(1, min(9, len(doc) - pos)))
    return doc


class TestExactOrderIndex:
    """``ordblk`` stays exact through block splits, the only move of run
    rows across blocks: on streams whose splits move runs (block_k 8),
    every order lookup hits its indexed block, so ``lookup_counts`` reads
    lookups and no walk, and the document equals the oracle's,
    tombstones included."""

    @staticmethod
    def _replay(txns, capacity):
        table = B.AgentTable()
        for t in txns:
            table.add(t.id.agent)
        ops, _ = B.compile_remote_txns(txns, table, lmax=16, dmax=None)
        res = RM.replay_mixed_rle(ops, capacity=capacity, batch=8,
                                  block_k=8, chunk=128, interpret=True)
        res.check()
        for lane in range(res.batch):
            lookups, walks = res.lookup_counts(lane)
            assert lookups > 0 and walks == 0, (lane, lookups, walks)
        oracle = oracle_txns(txns)
        doc = R.rle_to_flat(ops, res)
        assert SA.to_string(doc) == oracle.to_string()
        assert SA.doc_spans(doc) == oracle.doc_spans()
        return res

    @staticmethod
    def _blocks_of(res, lo, hi):
        """Physical blocks of lane 0 holding a run that meets orders
        ``[lo, hi)``."""
        o = np.asarray(res.ordp)[:, 0]
        n = np.asarray(res.lenp)[:, 0]
        s = np.abs(o) - 1
        hit = (o != 0) & (s < hi) & (s + n > lo)
        return set((np.flatnonzero(hit) // res.block_k).tolist())

    def test_b24_two_clients_merge_without_a_walk(self):
        rng = random.Random(2024)
        txns = []
        for agent in ("A", "B"):
            txns += export_txns_since(b24_history(agent, 40, rng), 0)
        res = self._replay(txns, capacity=1024)
        assert int(np.asarray(res.meta)[0, 0]) >= 24  # dozens of splits

    def test_long_run_moved_by_a_split_then_deleted(self):
        # amy types 300 chars in order: ONE run (the chain merge), wider
        # than the 2-row window of a run write.  Single chars typed at
        # the head push it down its block until a split moves it to a new
        # block; bob then deletes a range past its 256th order.
        doc = ListCRDT()
        a = doc.get_or_create_agent_id("amy")
        for k in range(30):
            doc.local_insert(a, 10 * k, "x" * 10)
        for _ in range(8):
            doc.local_insert(a, 0, "h")
        txns = export_txns_since(doc, 0)
        txns.append(RemoteTxn(
            id=RemoteId("bob", 0), parents=[RemoteId("amy", 307)],
            ops=[RemoteDel(RemoteId("amy", 270), 12)]))
        res = self._replay(txns, capacity=64)
        assert 0 not in self._blocks_of(res, 0, 300)

    @pytest.fixture
    def delete_run_cost(self, request, monkeypatch):
        """The kernel reads ``DELETE_RUN_COST`` when it is traced: patch
        it, and drop the cached builds on both sides of the test."""
        monkeypatch.setattr(RM, "DELETE_RUN_COST", request.param)
        RM._build_mixed_call.cache_clear()
        yield
        RM._build_mixed_call.cache_clear()

    # DELETE_RUN_COST 0 sends every remote delete run by run; 2**20 sends
    # every one through the walk over the blocks in use.
    @pytest.mark.parametrize("delete_run_cost", [0, 1 << 20],
                             ids=["runs", "walk"], indirect=True)
    def test_remote_delete_paths(self, delete_run_cost):
        rng = random.Random(11)
        doc = b24_history("amy", 20, rng)
        a = doc.get_or_create_agent_id("amy")
        s0 = doc.get_next_order()
        for _ in range(6):  # order-contiguous words at scattered places
            doc.local_insert(a, rng.randint(0, len(doc)), _word(rng, 4, 6))
        s1 = doc.get_next_order()
        b24_history("amy", 20, rng, doc)  # later rounds kill some of them
        txns = export_txns_since(doc, 0)
        last = [RemoteId("amy", doc.get_next_order() - 1)]
        # Two concurrent, overlapping deletes, each starting and ending
        # inside a word: the second is idempotent where they overlap.
        t, n = s0 + 2, s1 - s0 - 4
        kills = [
            RemoteTxn(id=RemoteId("zed", 0), parents=last,
                      ops=[RemoteDel(RemoteId("amy", t), n)]),
            RemoteTxn(id=RemoteId("yan", 0), parents=last,
                      ops=[RemoteDel(RemoteId("amy", t - 1), n - 2)]),
        ]
        before = oracle_txns(txns)
        orders = before.order[:before.n]
        in_range = (orders >= t) & (orders < t + n)
        dead = before.deleted[:before.n][in_range]
        assert dead.any() and not dead.all()  # live and dead runs
        res = self._replay(txns + kills, capacity=512)
        assert len(self._blocks_of(res, t, t + n)) >= 3
