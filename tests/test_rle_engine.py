"""RLE run-blocked engine vs the flat engine and string oracle.

Interpreter-mode differential tests: tiny blocks (block_k as low as 8 RUNS) force constant leaf SPLITS — the
engine's replacement for the global rebalance — so the logical-block-order
machinery is exercised on every few ops, the analog of the reference's
shrunken debug node sizes (`range_tree/mod.rs:29-39`). Streams are
compiled through ``merge_patches`` (the production path) AND raw, so both
run-granular and per-keystroke ops hit the kernel.
"""
import collections
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from text_crdt_rust_tpu.ops import batch as B
from text_crdt_rust_tpu.ops import flat as F
from text_crdt_rust_tpu.ops import rle as R
from text_crdt_rust_tpu.ops import rle_hbm as RH
from text_crdt_rust_tpu.ops import rle_mixed as RM
from text_crdt_rust_tpu.ops import span_arrays as SA
from text_crdt_rust_tpu.utils.testdata import (
    TestPatch,
    flatten_patches,
    load_testing_data,
    trace_path,
)

from test_device_flat import random_patches


def run_rle(patches, capacity, block_k, merge=True, chunk=128):
    plist = B.merge_patches(patches) if merge else patches
    lmax = max([len(p.ins_content) for p in plist] + [1])
    ops, _ = B.compile_local_patches(plist, lmax=lmax, dmax=None)
    res = R.replay_local_rle(ops, capacity=capacity, batch=8,
                             block_k=block_k, chunk=chunk, interpret=True)
    return ops, R.rle_to_flat(ops, res)


def ref_doc(patches, capacity=1024):
    """Flat-engine reference on the UNMERGED per-keystroke stream."""
    ops, _ = B.compile_local_patches(patches, lmax=16, dmax=None)
    return F.apply_ops(SA.make_flat_doc(capacity), ops)


class TestRleReplay:
    def test_smoke(self):
        patches = [TestPatch(0, 0, "hello world"), TestPatch(5, 0, ","),
                   TestPatch(2, 3, "LLO"), TestPatch(0, 1, "H")]
        _, doc = run_rle(patches, capacity=64, block_k=8)
        ref = ref_doc(patches, 64)
        assert SA.to_string(doc) == SA.to_string(ref) == "HeLLO, world"
        assert SA.doc_spans(doc) == SA.doc_spans(ref)

    @pytest.mark.parametrize("seed", [7, 11, 99])
    @pytest.mark.parametrize("merge", [True, False])
    def test_random_vs_flat(self, seed, merge):
        rng = random.Random(seed)
        patches, content = random_patches(rng, 80)
        _, doc = run_rle(patches, capacity=256, block_k=8, merge=merge)
        ref = ref_doc(patches, 512)
        assert SA.to_string(doc) == SA.to_string(ref) == content
        assert SA.doc_spans(doc) == SA.doc_spans(ref)

    def test_mid_run_split_insert(self):
        # One long run, then an insert strictly inside it: 3-way splice.
        patches = [TestPatch(0, 0, "abcdefghij"), TestPatch(5, 0, "XY")]
        _, doc = run_rle(patches, capacity=64, block_k=8)
        assert SA.to_string(doc) == "abcdeXYfghij"

    def test_delete_three_way_split(self):
        # Delete strictly inside one run: head + tombstone + tail rows.
        patches = [TestPatch(0, 0, "abcdefghij"), TestPatch(3, 4, "")]
        _, doc = run_rle(patches, capacity=64, block_k=8)
        ref = ref_doc(patches, 64)
        assert SA.to_string(doc) == SA.to_string(ref) == "abchij"
        assert SA.doc_spans(doc) == SA.doc_spans(ref)

    def test_delete_spanning_blocks(self):
        # Many tiny runs (discontiguous inserts), then one delete across
        # several blocks: boundary splits in two different blocks.
        patches = []
        for _ in range(24):
            patches.append(TestPatch(0, 0, "ab"))
        patches.append(TestPatch(2, 40, ""))
        _, doc = run_rle(patches, capacity=128, block_k=8, merge=False)
        ref = ref_doc(patches, 128)
        assert SA.to_string(doc) == SA.to_string(ref)
        assert SA.doc_spans(doc) == SA.doc_spans(ref)

    def test_insert_before_tombstones(self):
        # Insert at a position whose successor is a tombstone: the raw
        # successor (doc.rs:452 — not skipped) feeds origin_right.
        patches = [TestPatch(0, 0, "abcdef"), TestPatch(2, 2, ""),
                   TestPatch(2, 0, "XY")]
        _, doc = run_rle(patches, capacity=64, block_k=8)
        ref = ref_doc(patches, 64)
        assert SA.to_string(doc) == SA.to_string(ref) == "abXYef"
        assert SA.doc_spans(doc) == SA.doc_spans(ref)

    def test_insert_at_zero_before_leading_tombstone(self):
        patches = [TestPatch(0, 0, "abc"), TestPatch(0, 2, ""),
                   TestPatch(0, 0, "Z")]
        _, doc = run_rle(patches, capacity=64, block_k=8)
        ref = ref_doc(patches, 64)
        assert SA.to_string(doc) == SA.to_string(ref) == "Zc"
        assert SA.doc_spans(doc) == SA.doc_spans(ref)

    def test_prepend_heavy_splits(self):
        # kevin shape: every insert at pos 0 — runs can't merge, slot 0
        # splits over and over; logical order must stay consistent.
        patches = [TestPatch(0, 0, "ab") for _ in range(40)]
        _, doc = run_rle(patches, capacity=256, block_k=8, merge=False)
        ref = ref_doc(patches, 256)
        assert SA.to_string(doc) == SA.to_string(ref) == "ab" * 40
        assert SA.doc_spans(doc) == SA.doc_spans(ref)

    def test_append_merge_compresses(self):
        # Order-contiguous typing compiled UNMERGED must still compress
        # into one device run via the in-kernel append fast path.
        patches = [TestPatch(i, 0, "x") for i in range(50)]
        ops, _ = B.compile_local_patches(patches, lmax=1, dmax=None)
        res = R.replay_local_rle(ops, capacity=64, batch=8, block_k=8,
                                 chunk=128, interpret=True)
        rows_used = int(np.asarray(res.rows).sum(axis=0)[0])
        assert rows_used == 1  # 50 keystrokes -> one run row
        assert SA.to_string(R.rle_to_flat(ops, res)) == "x" * 50

    def test_far_jump_edits(self):
        patches = [TestPatch(0, 0, "abcdefgh")]
        for k in range(12):
            patches.append(TestPatch(0, 0, "xy"))
            patches.append(TestPatch(8 + 2 * k, 0, "pq"))
        _, doc = run_rle(patches, capacity=128, block_k=8, merge=False)
        ref = ref_doc(patches, 128)
        assert SA.to_string(doc) == SA.to_string(ref)
        assert SA.doc_spans(doc) == SA.doc_spans(ref)

    @pytest.mark.slow
    def test_trace_prefix(self):
        data = load_testing_data(trace_path("automerge-paper"))
        patches = flatten_patches(data)[:400]
        _, doc = run_rle(patches, capacity=256, block_k=16)
        ref = ref_doc(patches, 1024)
        assert SA.to_string(doc) == SA.to_string(ref)
        assert SA.doc_spans(doc) == SA.doc_spans(ref)

    def test_block_exhaustion_flagged(self):
        # Discontiguous runs overflow a tiny capacity: the kernel must
        # raise the block-capacity flag, not corrupt state.
        patches = [TestPatch(0, 0, "ab") for _ in range(40)]
        ops, _ = B.compile_local_patches(patches, lmax=2, dmax=None)
        res = R.replay_local_rle(ops, capacity=16, batch=8, block_k=8,
                                 chunk=128, interpret=True)
        with pytest.raises(RuntimeError, match="out of blocks"):
            res.check()

    def test_bad_delete_flagged(self):
        patches = [TestPatch(0, 0, "abc"), TestPatch(0, 10, "")]
        ops, _ = B.compile_local_patches(patches, lmax=4, dmax=None)
        res = R.replay_local_rle(ops, capacity=32, batch=8, block_k=8,
                                 chunk=128, interpret=True)
        with pytest.raises(RuntimeError, match="past the end"):
            res.check()


class TestRleGroups:
    def test_divergent_streams(self):
        rng = random.Random(404)
        opses, contents = [], []
        for gi in range(3):
            patches, content = random_patches(rng, 40 + 10 * gi)
            merged = B.merge_patches(patches)
            lmax = max(len(p.ins_content) for p in merged if p.ins_content)
            ops, _ = B.compile_local_patches(merged, lmax=lmax, dmax=None)
            opses.append(ops)
            contents.append(content)
        run = R.make_replayer_rle(opses, capacity=256, batch=8,
                                  block_k=8, chunk=128, interpret=True)
        results = run()
        assert len(results) == 3
        for ops, res, content in zip(opses, results, contents):
            assert SA.to_string(R.rle_to_flat(ops, res)) == content


class TestExpandRuns:
    def test_signs_and_orders(self):
        patches = [TestPatch(0, 0, "abcd"), TestPatch(1, 2, "")]
        ops, _ = B.compile_local_patches(
            B.merge_patches(patches), lmax=4, dmax=None)
        res = R.replay_local_rle(ops, capacity=32, batch=8, block_k=8,
                                 chunk=128, interpret=True)
        flat = R.expand_runs(res)
        # orders 0..3 in doc order; chars b,c (orders 1,2) tombstoned.
        assert list(flat) == [1, -2, -3, 4]


class TestVsNativeEngine:
    """Direct device<->C++ bit-equality (SURVEY §4: CPU<->TPU equality of
    order arrays + tombstone signs per batch): the rle engine's canonical
    spans must equal the native engine's on a real trace prefix."""

    def test_trace_prefix_spans_equal_native(self):
        from text_crdt_rust_tpu.models.native import NativeListCRDT

        data = load_testing_data(trace_path("automerge-paper"))
        patches = flatten_patches(data)[:600]
        _, doc = run_rle(patches, capacity=512, block_k=16)

        nd = NativeListCRDT()
        agent = nd.get_or_create_agent_id("bench")
        cps = np.frombuffer(
            "".join(p.ins_content for p in patches).encode("utf-32-le"),
            np.uint32)
        nd.replay_trace(agent, [p.pos for p in patches],
                        [p.del_len for p in patches],
                        [len(p.ins_content) for p in patches], cps)
        from text_crdt_rust_tpu.ops import span_arrays as SA2
        assert SA2.doc_spans(doc) == nd.doc_spans()
        assert SA2.to_string(doc) == nd.to_string()


def _prepends(n):
    """``n`` discontiguous two-char runs (each typed at position 0)."""
    return [TestPatch(0, 0, "ab") for _ in range(n)]


def _slot0_boundary_insert():
    # Twelve runs at block_k 8 fill two slots; an insert at the end of
    # slot 0 takes its raw successor from row 0 of slot 1.
    prefix = _prepends(12)
    ops, _ = B.compile_local_patches(prefix, lmax=2, dmax=None)
    res = R.replay_local_rle(ops, capacity=64, batch=8, block_k=8,
                             chunk=128, interpret=True)
    rows = np.asarray(res.rows)[:, 0]
    assert int(np.asarray(res.meta)[0, 0]) >= 2 and 0 < rows[0] < 12
    return prefix + [TestPatch(2 * int(rows[0]), 0, "Q")]


ROW_READ_EDGES = {
    # i_r + 1 == r0 and l + 1 == nlog: the successor is the root.
    "append_after_last_row": lambda: [
        TestPatch(0, 0, "abc"), TestPatch(0, 0, "x"), TestPatch(4, 0, "Z")],
    "successor_in_next_slot": _slot0_boundary_insert,
    "insert_into_empty_doc": lambda: [TestPatch(0, 0, "abc")],
    # abcd|XY|efgh, then one delete from inside the first run to inside
    # the last: both boundary partials in one block (npart == 2).
    "delete_two_partials": lambda: [
        TestPatch(0, 0, "abcdefgh"), TestPatch(4, 0, "XY"),
        TestPatch(2, 6, "")],
    # Seven runs fill slot 0 (block_k 8); the append splits it and lands
    # in the freshly split slot 1.
    "insert_after_split": lambda: _prepends(7) + [TestPatch(14, 0, "Z")],
}


class TestRowReads:
    """The kernel reads control scalars and single rows as one-row loads
    (``_rle_kernel``'s ``row_at``/``block_rows``); ``rle_hbm`` keeps the
    masked-reduction default of the shared block math, so it is the
    reference for every edge of those reads."""

    @pytest.mark.parametrize("case", sorted(ROW_READ_EDGES))
    def test_row_read_edges_vs_hbm(self, case):
        patches = ROW_READ_EDGES[case]()
        lmax = max(len(p.ins_content) for p in patches)
        ops, _ = B.compile_local_patches(patches, lmax=lmax, dmax=None)
        kw = dict(capacity=64, batch=8, block_k=8, chunk=128,
                  interpret=True)
        got = R.replay_local_rle(ops, **kw)
        want = RH.replay_local_rle_hbm(ops, **kw)
        np.testing.assert_array_equal(R.expand_runs(got),
                                      R.expand_runs(want))
        for a, b in ((got.ol, want.ol), (got.orr, want.orr)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        ref = ref_doc(patches, 64)
        assert SA.to_string(R.rle_to_flat(ops, got)) == SA.to_string(ref)


def _kernel_reductions(fn, args):
    """``(primitive, operand shape) -> count`` of the reductions in the
    kernel jaxpr of the one ``pallas_call`` that ``fn`` traces to."""
    counts = collections.Counter()

    def subs(eqn):
        for v in eqn.params.values():
            for x in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(x, jcore.ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, jcore.Jaxpr):
                    yield x

    def walk(jaxpr, in_kernel):
        for eqn in jaxpr.eqns:
            if in_kernel and eqn.primitive.name.startswith("reduce_"):
                shape = tuple(eqn.invars[0].aval.shape)
                counts[(eqn.primitive.name, shape)] += 1
            for sub in subs(eqn):
                walk(sub, in_kernel or eqn.primitive.name == "pallas_call")

    walk(jax.make_jaxpr(fn)(*args).jaxpr, False)
    return counts


class TestWholePlaneReductions:
    """The mechanism's engagement record: a reduction over a whole
    ``[K, B]`` block or ``[NBLp, B]`` slot table is left only where it
    computes an aggregate (the descent, the ``i_r`` count, the delete's
    cover counts, ``split``'s upper live count).  Before the one-row
    loads the benchmark's geometry traced 20 table and 20 block sums."""

    def test_rle_kernel_at_benchmark_geometry(self):
        # capacity 20,992 runs of K=128: 164 slots, B=512 lanes.
        fn = R._build_call(1, 8192, 512, 20992, 128, 1024, 8, False)
        c = _kernel_reductions(fn, [jax.ShapeDtypeStruct(
            (8192,), jnp.int32)] * 5)
        # slot_of_live_rank twice on each path.
        assert c[("reduce_sum", (164, 512))] <= 4
        # insert: the i_r count; delete: tot, npart; split's liv_hi in
        # each path's copy.
        assert c[("reduce_sum", (128, 512))] <= 5
        assert c[("reduce_min", (128, 512))] == 1      # i1
        assert c[("reduce_max", (128, 512))] == 1      # i2

    @pytest.mark.parametrize("engine", ["rle_hbm", "rle_mixed"])
    def test_other_engines_keep_masked_default(self, engine):
        # Their kernels pass no row reader: every row of the shared
        # block math is still a masked reduction, as many as before.
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
        if engine == "rle_hbm":
            fn = RH._build_call(1, 1024, 8, 256, 8, 128, 8, True, False)
            args = [i32(1024)] * 5
            want = {("reduce_sum", (8, 8)): 29, ("reduce_sum", (64, 8)): 8}
        else:
            fn = RM._build_mixed_call(128, 8, 256, 8, 128, 8, False, True)
            args = [i32(128)] * 9 + [i32(8, RM.LANES)] * 3
            # Its block maps are SMEM scalars, not slot-table sums, and
            # its remote delete's plane walk sums covers block by block.
            # The exact order index took 74 block and 46 slot-table sums
            # to 66 and 44: the delete's partial-run fix-up (traced twice,
            # as before: once in the run-by-run delete, once for the
            # walk's two endpoints) reads its run and slot with one-row
            # loads, and so does ``split``'s index upkeep.
            want = {("reduce_sum", (8, 8)): 66, ("reduce_sum", (32, 8)): 44}
        c = _kernel_reductions(fn, args)
        assert {k: c[k] for k in want} == want
