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


# ---------------------------------------------------------------------------
# Bounded block walks: every per-lane gather and scatter of the local
# blocked kernel visits only blocks [0, min(max nlog + 1, NB)) of its lane
# tile.  Each case checks bit-identity with the un-blocked kernel and the
# oracle where a lane's blocks, or the tile's largest nlog, move.
# ---------------------------------------------------------------------------


def at_front(n: int, every: int = 0):
    """``n`` one-char inserts at position 0 (a new run, one row, each
    time), with a two-char delete at position 2 after every ``every``-th
    insert when ``every`` is set."""
    from text_crdt_rust_tpu.utils.testdata import TestPatch

    out = []
    for k in range(n):
        out.append(TestPatch(0, 0, "q"))
        if every and k % every == every - 1:
            out.append(TestPatch(2, 2, ""))
    return out


def typing(n: int):
    """``n`` chars typed forward (one merged run), last one deleted."""
    from text_crdt_rust_tpu.utils.testdata import TestPatch

    return ([TestPatch(i, 0, "t") for i in range(n)]
            + [TestPatch(n - 1, 1, "")])


def packed_states(prefixes, *, K: int, NB: int):
    """Host-packed warm starts of each lane's prefix: the blocked 6-tuple
    (numpy, ``pack_lane_blocks``' identity order, ``nlog`` per lane) and
    the un-blocked ``(ordp, lenp, rows)`` with the same runs."""
    from text_crdt_rust_tpu.ops.lane_blocks import (
        oracle_runs,
        pack_lane_blocks,
    )

    cap, nbt, lanes = K * NB, max(8, NB), len(prefixes)
    cols = []
    planes = (np.zeros((cap, lanes), np.int32),
              np.zeros((cap, lanes), np.int32),
              np.zeros((1, lanes), np.int32))
    nexts = []
    for d, ps in enumerate(prefixes):
        oracle = oracle_from_patches(ps)
        starts, lens = oracle_runs(oracle)
        packed, _ = pack_lane_blocks(starts, lens, K=K, NB=NB, NBT=nbt,
                                     capacity=cap)
        cols.append(packed[:6])
        planes[0][:len(starts), d] = starts
        planes[1][:len(starts), d] = lens
        planes[2][0, d] = len(starts)
        nexts.append(oracle.get_next_order())
    blocked = tuple(np.stack([c[i].reshape(-1) for c in cols], axis=1)
                    for i in range(6))
    return blocked, planes, nexts


def continue_stack(rests, nexts):
    opses = []
    for ps, nxt in zip(rests, nexts):
        ops, _ = B.compile_local_patches(ps, lmax=8, dmax=None,
                                         start_order=nxt)
        opses.append(ops)
    return B.stack_ops(opses)


def replay_both(stacked, *, capacity, block_k, chunk, init=None,
                planes=None):
    blk = RL.make_replayer_lanes_blocked(
        stacked, capacity=capacity, block_k=block_k, chunk=chunk,
        init=init, interpret=True)()
    ref = RL.make_replayer_lanes(stacked, capacity=capacity, chunk=chunk,
                                 init=planes, interpret=True)()
    blk.check()
    ref.check()
    return ref, blk


def assert_matches_oracle(blk, histories):
    for d, ps in enumerate(histories):
        assert (RL.expand_lane(blk, d).tolist()
                == oracle_signed(oracle_from_patches(ps))), f"lane {d}"


def host_walks(n: int, K: int, NB: int):
    """``(blocks visited, walks)`` of ``at_front(n)`` on lane 0 beside
    lanes that never split, from the kernel's own split rule: slot 0
    splits when its rows leave no room for a row (+ 1 spare), keeping
    half; a split walks 4 times, an insert 6, each over ``min(max nlog
    + 1, NB)`` blocks."""
    rows, nlog, seen, walks = 0, 1, 0, 0
    for _ in range(n):
        if rows + 2 > K:
            seen += 4 * min(nlog + 1, NB)
            walks += 4
            rows, nlog = rows // 2, nlog + 1
        seen += 6 * min(nlog + 1, NB)
        walks += 6
        rows += 1
    return seen, walks


class TestBoundedBlockWalks:
    K, NB = 8, 16

    def test_skewed_pass(self):
        # Lane 0 grows to many blocks, so every split lands its fresh
        # block at the tile's largest nlog; the other lanes stay at one.
        hist = [at_front(48, every=8), typing(5), typing(3), typing(6)]
        stacked, _ = compile_stack(hist)
        ref, blk = replay_both(stacked, capacity=self.K * self.NB,
                               block_k=self.K, chunk=16)
        nlog = np.asarray(blk.nlog)[0]
        assert nlog[0] > 6 and (nlog[1:] == 1).all(), nlog
        assert_same_doc(ref, blk, 4)
        assert_matches_oracle(blk, hist)
        assert blk.block_walk_share < 1.0

    def test_warm_start_packed_nlogs_differ(self):
        rng = random.Random(3030)
        full = [random_patches(rng, n)[0] for n in (12, 30, 55, 80)]
        cut = [4, 18, 40, 60]
        blocked, planes, nexts = packed_states(
            [ps[:c] for ps, c in zip(full, cut)], K=self.K, NB=2 * self.NB)
        nlog0 = blocked[2][0]
        assert len(set(nlog0.tolist())) > 2, nlog0
        stacked = continue_stack([ps[c:] for ps, c in zip(full, cut)],
                                 nexts)
        ref, blk = replay_both(stacked, capacity=self.K * 2 * self.NB,
                               block_k=self.K, chunk=8, init=blocked,
                               planes=planes)
        assert_same_doc(ref, blk, 4)
        assert_matches_oracle(blk, full)

    def test_split_on_the_fullest_lane(self):
        # Lane 0 starts with the tile's largest nlog m and half-full
        # blocks; its fifth insert at the front splits slot 0, and the
        # moved half goes to physical block m = max(nlog): the "+ 1" of
        # the bound.
        from text_crdt_rust_tpu.utils.testdata import TestPatch

        rng = random.Random(3031)
        prefixes = [random_patches(rng, 40)[0], typing(4), typing(9),
                    random_patches(rng, 6)[0]]
        nb = 24
        blocked, planes, nexts = packed_states(prefixes, K=self.K, NB=nb)
        m = int(blocked[2][0, 0])
        assert m == blocked[2].max() and m < nb - 1
        assert (blocked[2][0, 1:] < m).all()
        rests = [[TestPatch(0, 0, "z")] * 5, [TestPatch(0, 0, "y")],
                 [TestPatch(1, 0, "x")], [TestPatch(0, 1, "")]]
        stacked = continue_stack(rests, nexts)
        ref, blk = replay_both(stacked, capacity=self.K * nb,
                               block_k=self.K, chunk=8, init=blocked,
                               planes=planes)
        assert int(np.asarray(blk.nlog)[0, 0]) == m + 1
        assert int(np.asarray(blk.blkord)[1, 0]) == m
        assert_same_doc(ref, blk, 4)
        assert_matches_oracle(blk, [p + r for p, r in zip(prefixes,
                                                           rests)])

    def test_walk_share_is_whole_when_a_lane_holds_every_block(self):
        from text_crdt_rust_tpu.utils.testdata import TestPatch

        rng = random.Random(3032)
        nb = 6
        per = (self.K - 1) // 2
        # Lane 0: one-char inserts at the front, never merging, until
        # its runs fill all nb blocks of the pack.
        prefixes = [at_front(per * nb - 2), typing(3), typing(5),
                    random_patches(rng, 5)[0]]
        blocked, planes, nexts = packed_states(prefixes, K=self.K, NB=nb)
        assert int(blocked[2][0, 0]) == nb
        rests = [[TestPatch(1, 0, "z")] * 3, typing(2),
                 [TestPatch(0, 1, "")], [TestPatch(0, 0, "w")]]
        full = [p + r for p, r in zip(prefixes, rests)]
        stacked = continue_stack(rests, nexts)
        ref, blk = replay_both(stacked, capacity=self.K * nb,
                               block_k=self.K, chunk=8, init=blocked,
                               planes=planes)
        assert blk.block_walk_share == 1.0
        assert_same_doc(ref, blk, 4)
        assert_matches_oracle(blk, full)

    def test_walk_share_matches_host_count(self):
        from text_crdt_rust_tpu.utils.testdata import TestPatch

        n = 40
        hist = [at_front(n)] + [[TestPatch(0, 0, "x")]] * 3
        stacked, _ = compile_stack(hist)
        ref, blk = replay_both(stacked, capacity=self.K * self.NB,
                               block_k=self.K, chunk=16)
        assert_same_doc(ref, blk, 4)
        seen, walks = host_walks(n, self.K, self.NB)
        splits = int(np.asarray(blk.nlog)[0, 0]) - 1
        assert splits > 4 and walks == 6 * n + 4 * splits
        # Every lane of the tile carries the tile's counts.
        stats = np.asarray(blk.stats)
        assert (stats[0] == seen).all()
        assert (stats[1] == walks * self.NB).all()
        assert blk.block_walk_share == pytest.approx(
            seen / (walks * self.NB))
        assert blk.block_walk_share < 0.5

    def test_warm_start_refuses_blocks_past_nlog(self):
        blocked, _, _ = packed_states([typing(3), typing(4)], K=self.K,
                                      NB=self.NB)
        bad = list(blocked)
        bad[3] = bad[3].copy()
        bad[3][0, 1] = 2  # lane 1 holds one block, yet names block 2
        stacked = continue_stack([typing(1), typing(1)], [3, 4])
        with pytest.raises(ValueError, match="lanes \\[1\\]"):
            RL.make_replayer_lanes_blocked(
                stacked, capacity=self.K * self.NB, block_k=self.K,
                chunk=8, init=tuple(bad), interpret=True)
