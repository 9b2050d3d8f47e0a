"""BLOCKED per-lane engines vs the un-blocked engines and the oracle.

The ISSUE-2 tentpole bar: the K-row-block restructure of
``rle_lanes`` / ``rle_lanes_mixed`` must be BIT-IDENTICAL to the
un-blocked kernels — same expanded per-char state, same per-op origins,
same by-order tables — across splits (tiny K forces them), warm-started
chunk chains with growing capacities, lane tiling, and every remote
shape the mixed engine runs.  Interpreter mode.
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
from text_crdt_rust_tpu.models.sync import export_txns_since
from text_crdt_rust_tpu.ops import batch as B
from text_crdt_rust_tpu.ops import rle_lanes as RL
from text_crdt_rust_tpu.ops import rle_lanes_mixed as RLM

from test_device_flat import oracle_from_patches, random_patches
from test_rle_lanes import compile_stack
from test_rle_lanes_mixed import (
    compile_txn_lanes,
    oracle_signed,
    oracle_txns,
)

ROOT = RemoteId("ROOT", 0xFFFFFFFF)


def assert_same_doc(ref, blk, docs):
    """Blocked and un-blocked results describe the same documents and
    emitted origins."""
    for d in range(docs):
        assert (RL.expand_lane(ref, d).tolist()
                == RL.expand_lane(blk, d).tolist()), f"lane {d}"
    assert np.array_equal(np.asarray(ref.ol), np.asarray(blk.ol))
    assert np.array_equal(np.asarray(ref.orr), np.asarray(blk.orr))


class TestBlockedLocalLanes:
    @pytest.mark.parametrize("seed,block_k", [
        (7, 16), pytest.param(42, 8, marks=pytest.mark.slow)])
    def test_divergent_vs_unblocked(self, seed, block_k):
        rng = random.Random(seed)
        streams = [random_patches(rng, 30 + rng.randint(0, 20))[0]
                   for _ in range(8)]
        stacked, _ = compile_stack(streams)
        ref = RL.replay_lanes(stacked, capacity=256, chunk=16,
                              interpret=True)
        blk = RL.make_replayer_lanes_blocked(
            stacked, capacity=256, block_k=block_k, chunk=16,
            interpret=True)()
        ref.check()
        blk.check()
        # Tiny K must actually exercise splits or the test is vacuous.
        assert int(np.asarray(blk.nlog).max()) > 1
        assert_same_doc(ref, blk, 8)

    def test_warm_start_growing_capacity(self):
        rng = random.Random(31)
        docs = 4
        nexts = [0] * docs
        state = refstate = None
        for cap in (64, 128, 192):
            streams = [random_patches(rng, 15)[0] for _ in range(docs)]
            opses = []
            for d, ps in enumerate(streams):
                ops, nexts[d] = B.compile_local_patches(
                    ps, lmax=8, dmax=None, start_order=nexts[d])
                opses.append(ops)
            stacked = B.stack_ops(opses)
            blk = RL.make_replayer_lanes_blocked(
                stacked, capacity=cap, block_k=16, chunk=16,
                interpret=True)(state)
            blk.check()
            state = blk.state()
            ref = RL.make_replayer_lanes(
                stacked, capacity=cap, chunk=16,
                interpret=True)(refstate)
            ref.check()
            refstate = ref.state()
        assert_same_doc(ref, blk, docs)

    def test_tiled_equals_whole(self):
        rng = random.Random(99)
        streams = [random_patches(rng, 25)[0] for _ in range(8)]
        stacked, _ = compile_stack(streams)
        kw = dict(capacity=128, block_k=16, chunk=8, interpret=True)
        whole = RL.make_replayer_lanes_blocked(stacked, **kw)()
        tiled = RL.make_replayer_lanes_blocked(stacked, lane_tile=4,
                                               **kw)()
        whole.check()
        tiled.check()
        for f in ("ordp", "lenp", "nlog", "blkord", "rws", "liv", "ol",
                  "orr"):
            assert np.array_equal(np.asarray(getattr(whole, f)),
                                  np.asarray(getattr(tiled, f))), f

    def test_out_of_blocks_flag_per_lane(self):
        # Lane 1 outgrows a 2-block capacity (inserts interleaved with
        # deletes so runs can't merge); lane 0 stays legal.
        from text_crdt_rust_tpu.utils.testdata import TestPatch

        busy = []
        for k in range(24):
            busy.append(TestPatch(0, 0, "ab"))
            if k % 2:
                busy.append(TestPatch(1, 1, ""))
        streams = [[TestPatch(0, 0, "ab")], busy]
        stacked, _ = compile_stack(streams)
        res = RL.make_replayer_lanes_blocked(
            stacked, capacity=16, block_k=8, chunk=8, interpret=True)()
        with pytest.raises(RuntimeError, match="lanes \\[1\\]"):
            res.check()

    def test_bad_delete_flag(self):
        from text_crdt_rust_tpu.utils.testdata import TestPatch

        streams = [[TestPatch(0, 0, "abc"), TestPatch(0, 10, "")]]
        stacked, _ = compile_stack(streams)
        res = RL.make_replayer_lanes_blocked(
            stacked, capacity=16, block_k=8, chunk=8, interpret=True)()
        with pytest.raises(RuntimeError, match="past the end"):
            res.check()


class TestBlockedMixedLanes:
    # Both seeds are slow-tier (ISSUE 11 budget satellite: ~16 s of
    # interpret compile each): the tier-1 representative of the
    # blocked-mixed differential surface is test_fuzz_blocked's
    # 60-seed blocked-vs-flat-vs-oracle sweep, which covers two-peer
    # merge streams at a fraction of the wall.
    @pytest.mark.parametrize("seed", [
        pytest.param(3, marks=pytest.mark.slow),
        pytest.param(21, marks=pytest.mark.slow)])
    def test_two_peer_merges_vs_unblocked_and_oracle(self, seed):
        rng = random.Random(seed)
        lane_txns = []
        for _ in range(3):
            pa, _ = random_patches(rng, 20)
            pb, _ = random_patches(rng, 20)
            a = oracle_from_patches(pa, agent="peer-a")
            b = oracle_from_patches(pb, agent="peer-b")
            lane_txns.append(export_txns_since(a, 0)
                             + export_txns_since(b, 0))
        stacked = compile_txn_lanes(lane_txns)
        ref = RLM.replay_lanes_mixed(stacked, capacity=256, chunk=16,
                                     interpret=True)
        blk = RLM.replay_lanes_mixed_blocked(
            stacked, capacity=256, block_k=16, chunk=16, interpret=True)
        ref.check()
        blk.check()
        assert int(np.asarray(blk.nlog).max()) > 1
        for d, txns in enumerate(lane_txns):
            want = oracle_signed(oracle_txns(txns))
            assert RL.expand_lane(blk, d).tolist() == want, f"lane {d}"
        assert_same_doc(ref, blk, len(lane_txns))
        for f in ("oll", "orl"):
            assert np.array_equal(np.asarray(getattr(ref, f)),
                                  np.asarray(getattr(blk, f))), f

    @pytest.mark.slow
    def test_storms_with_deletes(self):
        from text_crdt_rust_tpu.utils.randedit import make_storm

        lane_txns = [make_storm(3, 5, 2, seed=50 + k, del_prob=0.35)[0]
                     for k in range(3)]
        stacked = compile_txn_lanes(lane_txns, lmax=4)
        ref = RLM.replay_lanes_mixed(stacked, capacity=256, chunk=16,
                                     interpret=True)
        blk = RLM.replay_lanes_mixed_blocked(
            stacked, capacity=256, block_k=8, chunk=16, interpret=True)
        ref.check()
        blk.check()
        for d, txns in enumerate(lane_txns):
            want = oracle_signed(oracle_txns(txns))
            assert RL.expand_lane(blk, d).tolist() == want, f"lane {d}"
        assert_same_doc(ref, blk, len(lane_txns))

    @pytest.mark.slow
    def test_long_remote_delete_spans_blocks(self):
        # A 40-char interval delete crosses several 8-row blocks: full
        # covers flip plane-wide, both endpoint runs 3-way-split in
        # their own blocks; plus a double delete for idempotency.
        l0 = [
            RemoteTxn(id=RemoteId("amy", 0), parents=[],
                      ops=[RemoteIns(ROOT, ROOT, "x" * 50)]),
            RemoteTxn(id=RemoteId("bob", 0),
                      parents=[RemoteId("amy", 49)],
                      ops=[RemoteDel(RemoteId("amy", 5), 40)]),
            RemoteTxn(id=RemoteId("cat", 0),
                      parents=[RemoteId("amy", 49)],
                      ops=[RemoteDel(RemoteId("amy", 3), 10)]),
        ]
        # Fragment the run first so the interval covers MANY runs.
        l1 = [RemoteTxn(id=RemoteId("amy", 0), parents=[],
                        ops=[RemoteIns(ROOT, ROOT, "abcdefgh")])]
        for k, s in enumerate((1, 3, 5)):
            l1.append(RemoteTxn(
                id=RemoteId("bob", k), parents=[],
                ops=[RemoteDel(RemoteId("amy", s), 1)]))
        l1.append(RemoteTxn(id=RemoteId("cat", 0), parents=[],
                            ops=[RemoteDel(RemoteId("amy", 1), 6)]))
        lane_txns = [l0, l1]
        stacked = compile_txn_lanes(lane_txns, lmax=50)
        ref = RLM.replay_lanes_mixed(stacked, capacity=128, chunk=16,
                                     interpret=True)
        blk = RLM.replay_lanes_mixed_blocked(
            stacked, capacity=128, block_k=8, chunk=16, interpret=True)
        ref.check()
        blk.check()
        for d, txns in enumerate(lane_txns):
            want = oracle_signed(oracle_txns(txns))
            assert RL.expand_lane(blk, d).tolist() == want, f"lane {d}"
        assert_same_doc(ref, blk, 2)

    @pytest.mark.slow
    def test_mixed_local_and_remote_lanes_same_step(self):
        rng = random.Random(11)
        patches, content = random_patches(rng, 25)
        local_ops, _ = B.compile_local_patches(
            B.merge_patches(patches), lmax=8, dmax=None)
        pa, _ = random_patches(rng, 18)
        a = oracle_from_patches(pa, agent="peer-a")
        txns = export_txns_since(a, 0)
        table = B.AgentTable()
        for t in txns:
            table.add(t.id.agent)
        remote_ops, _ = B.compile_remote_txns(txns, table, lmax=8,
                                              dmax=16)
        stacked = B.stack_ops([local_ops, remote_ops])
        ref = RLM.replay_lanes_mixed(stacked, capacity=256, chunk=16,
                                     interpret=True)
        blk = RLM.replay_lanes_mixed_blocked(
            stacked, capacity=256, block_k=16, chunk=16, interpret=True)
        ref.check()
        blk.check()
        assert_same_doc(ref, blk, 2)

    @pytest.mark.slow
    def test_warm_start_chunks_grow_capacity(self):
        rng = random.Random(42)
        docs = 3
        peers = [oracle_from_patches(random_patches(rng, 30)[0],
                                     agent=f"p{d}") for d in range(docs)]
        lane_txns = [export_txns_since(p, 0) for p in peers]
        halves = [(t[: len(t) // 2], t[len(t) // 2:])
                  for t in lane_txns]
        tables = [B.AgentTable() for _ in range(docs)]
        assigners = [None] * docs

        def compile_chunk(which):
            opses = []
            for d in range(docs):
                for t in halves[d][which]:
                    tables[d].add(t.id.agent)
                ops, assigners[d] = B.compile_remote_txns(
                    halves[d][which], tables[d],
                    assigner=assigners[d], lmax=4, dmax=None)
                opses.append(ops)
            return B.stack_ops(opses)

        c0 = compile_chunk(0)
        r0 = RLM.make_replayer_lanes_mixed_blocked(
            c0, capacity=128, block_k=16, order_capacity=512, chunk=16,
            interpret=True)()
        r0.check()
        c1 = compile_chunk(1)
        _, _, rkl0 = RLM.lane_tables(c0, 512)
        _, _, rkl1 = RLM.lane_tables(c1, 512)
        rkl = np.where(rkl1 != 0, rkl1, rkl0)
        r1 = RLM.make_replayer_lanes_mixed_blocked(
            c1, capacity=256, block_k=16, order_capacity=512,
            init=r0.state(), rkl=rkl, chunk=16, interpret=True)()
        r1.check()
        for d in range(docs):
            want = oracle_signed(oracle_txns(lane_txns[d]))
            assert RL.expand_lane(r1, d).tolist() == want, f"lane {d}"

    def test_remote_delete_out_of_blocks_is_clean_noop(self):
        # A remote delete whose endpoint split cannot be housed (table
        # full) must flag AND leave the lane untouched — the blocked
        # twin of the un-blocked tight-gate regression.
        txns = [RemoteTxn(id=RemoteId("amy", 0), parents=[],
                          ops=[RemoteIns(ROOT, ROOT, "aaaaaaaa")])]
        for k, s in enumerate((1, 3, 5, 6)):
            txns.append(RemoteTxn(
                id=RemoteId("bob", k), parents=[],
                ops=[RemoteDel(RemoteId("amy", s), 1)]))
        stacked = compile_txn_lanes([txns], lmax=8)
        res = RLM.replay_lanes_mixed_blocked(
            stacked, capacity=8, block_k=8, chunk=8, interpret=True)
        with pytest.raises(RuntimeError, match="lanes \\[0\\]"):
            res.check()


# ---------------------------------------------------------------------------
# A fleet of distinct real documents: the first 300 patches of each of the
# three editing traces, 128 lanes in a seeded three-trace map, ragged
# stacked streams (the replay-divergent-fleet cell at a test's size).
# ---------------------------------------------------------------------------

TRACES = ("automerge-paper", "rustcode", "sveltecomponent")
PREFIX = 300


def fleet_map(seed: int = 2029):
    lanes = [0] * 43 + [1] * 43 + [2] * 42
    random.Random(seed).shuffle(lanes)
    return lanes


@pytest.fixture(scope="module")
def trace_fleet():
    from text_crdt_rust_tpu.utils import load_testing_data, trace_path

    prefixes, fused, unfused = [], [], []
    for name in TRACES:
        data = load_testing_data(trace_path(name))
        patches = [p for t in data.txns for p in t.patches][:PREFIX]
        prefixes.append(patches)
        merged = B.merge_patches(patches)
        ops, _ = B.compile_local_patches(merged, lmax=8192, dmax=None)
        unfused.append(ops)
        fused.append(B.fuse_steps(ops, fuse_w=8)[0])
    oracles = [oracle_signed(oracle_from_patches(p)) for p in prefixes]
    return dict(lanes=fleet_map(), fused=fused, unfused=unfused,
                oracles=oracles)


def fleet_replay(fleet, which, **kw):
    streams = fleet[which]
    stacked = B.stack_ops([streams[t] for t in fleet["lanes"]],
                          chars=False)
    run = RL.make_replayer_lanes_blocked(stacked, interpret=True, **kw)
    res = run()
    res.check()
    return run, res


class TestEditingTraceFleet:
    GEOMETRY = dict(capacity=1024, block_k=32, chunk=64)

    def test_every_lane_is_its_own_trace(self, trace_fleet):
        _, res = fleet_replay(trace_fleet, "fused", **self.GEOMETRY)
        lens = {int(s.num_steps) for s in trace_fleet["fused"]}
        assert len(lens) == 3, "the traces' streams must be ragged"
        for b, t in enumerate(trace_fleet["lanes"]):
            assert (RL.expand_lane(res, b).tolist()
                    == trace_fleet["oracles"][t]), f"lane {b}"

    def test_fused_and_unfused_stacks_agree(self, trace_fleet):
        assert any(B.fused_width(s) > 1 for s in trace_fleet["fused"])
        _, fused = fleet_replay(trace_fleet, "fused", **self.GEOMETRY)
        _, plain = fleet_replay(trace_fleet, "unfused", **self.GEOMETRY)
        for b in range(128):
            assert (RL.expand_lane(fused, b).tolist()
                    == RL.expand_lane(plain, b).tolist()), f"lane {b}"

    def test_lane_steps_count_real_and_stepped(self, trace_fleet):
        run, _ = fleet_replay(trace_fleet, "fused", **self.GEOMETRY)
        streams = [trace_fleet["fused"][t] for t in trace_fleet["lanes"]]
        s_max = max(int(s.num_steps) for s in streams)
        s_pad = -(-s_max // 64) * 64
        assert run.lane_steps == RL.LaneSteps(
            real=sum(int(s.num_steps) for s in streams),
            stepped=s_pad * 128)
        assert run.lane_steps.real < run.lane_steps.stepped

    def test_many_blocks(self, trace_fleet):
        # 96 blocks of 8 rows: the loop over the blocks runs far past
        # the tiny geometries, and the longest prefix fills dozens.
        _, res = fleet_replay(trace_fleet, "fused", capacity=8 * 96,
                              block_k=8, chunk=64)
        assert int(np.asarray(res.nlog).max()) > 16
        for b, t in enumerate(trace_fleet["lanes"]):
            assert (RL.expand_lane(res, b).tolist()
                    == trace_fleet["oracles"][t]), f"lane {b}"
