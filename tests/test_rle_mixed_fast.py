"""The vectorized YATA conflict scan (`ops/rle_mixed.py` integrate_fast)
must be BIT-IDENTICAL to the serial run-walk it replaces, on every
window shape — siblings, split pieces, tombstones, merge-appended runs
— falling back to the serial loop (via its flag) wherever its
classification cannot prove the window trivial.  Reference semantics:
`/root/reference/src/list/doc.rs:183-222` with the pinned-scan_start
rule (tests/test_integrate_divergence.py)."""
import random

import numpy as np
import pytest

from text_crdt_rust_tpu.models.oracle import ListCRDT
from text_crdt_rust_tpu.models.sync import export_txns_since
from text_crdt_rust_tpu.ops import batch as B
from text_crdt_rust_tpu.ops import rle as R
from text_crdt_rust_tpu.ops import rle_mixed as RM
from text_crdt_rust_tpu.ops import span_arrays as SA
from text_crdt_rust_tpu.utils.randedit import make_storm


def replay_both(txns, capacity, block_k=8, lmax=4, chunk=128, dmax=None):
    """[(flat, integrate_counts)] of the fast run, then the serial, for
    one txn stream; tiny blocks force splits so the aux planes' motion
    paths are all exercised."""
    table = B.AgentTable()
    for t in txns:
        table.add(t.id.agent)
        for op in t.ops:
            if hasattr(op, "id"):
                table.add(op.id.agent)
    ops, _ = B.compile_remote_txns(txns, table, lmax=lmax, dmax=dmax)
    outs = []
    for fast in (True, False):
        res = RM.replay_mixed_rle(ops, capacity=capacity, batch=8,
                                  block_k=block_k, chunk=chunk,
                                  interpret=True, fast_integrate=fast)
        res.check()
        outs.append((R.rle_to_flat(ops, res), res.integrate_counts()))
    return outs


def oracle_txns(txns):
    doc = ListCRDT()
    for t in txns:
        doc.apply_remote_txn(t)
    return doc


def assert_fast_exact(txns, capacity=512):
    """Fast == serial == oracle; returns the (fast, serial) runs'
    ``integrate_counts``."""
    (fast, fast_counts), (serial, serial_counts) = replay_both(
        txns, capacity)
    want = oracle_txns(txns).to_string()
    assert SA.to_string(serial) == want
    assert SA.to_string(fast) == want
    assert np.array_equal(np.asarray(fast.signed),
                          np.asarray(serial.signed))
    return fast_counts, serial_counts


def typed(name, see, edit):
    """The txns ``name`` exports after seeing ``see`` and making
    ``edit(doc, agent)``."""
    doc = ListCRDT()
    agent = doc.get_or_create_agent_id(name)
    for t in see:
        doc.apply_remote_txn(t)
    m = doc.get_next_order()
    edit(doc, agent)
    return export_txns_since(doc, m)


class TestTier1Smoke:
    """The fast-vs-serial property at its smallest: one small storm with
    deletes through both scan paths, bit-identical and oracle-equal.  A
    failure here names the property before the matrix below runs its
    larger shapes."""

    def test_small_delete_storm(self):
        txns, receiver = make_storm(3, 4, 2, seed=7, del_prob=0.3)
        (fast, _), (serial, _) = replay_both(txns, capacity=256, chunk=32)
        want = oracle_txns(txns).to_string()
        assert want == receiver.to_string()
        assert SA.to_string(serial) == want
        assert SA.to_string(fast) == want
        assert np.array_equal(np.asarray(fast.signed),
                              np.asarray(serial.signed))


class TestFastIntegrate:
    def test_insert_storm(self):
        # The config-4 shape: every window run is a ROOT-origin sibling.
        txns, receiver = make_storm(4, 8, 3, seed=7)
        assert_fast_exact(txns)
        assert oracle_txns(txns).to_string() == receiver.to_string()

    def test_delete_heavy_storm(self):
        # Splits + tombstones inside scan windows (chain pieces, the
        # -2 origin-right sentinel, full/partial covers).
        txns, receiver = make_storm(4, 10, 3, seed=11, del_prob=0.4)
        assert_fast_exact(txns)
        assert oracle_txns(txns).to_string() == receiver.to_string()

    @pytest.mark.parametrize("seed", range(6))
    def test_two_peer_random_merge(self, seed):
        # Random concurrent edits with periodic cross-merges: windows
        # contain descendants, split tails, and mid-run cursors.
        rng = random.Random(400 + seed)
        a_doc, b_doc = ListCRDT(), ListCRDT()
        a = a_doc.get_or_create_agent_id("amy")
        b = b_doc.get_or_create_agent_id("bob")
        marks = {"amy": 0, "bob": 0}
        flat = []

        def edit(doc, agent, r):
            n = len(doc)
            if n == 0 or r.random() < 0.6:
                pos = r.randint(0, n)
                doc.local_insert(agent, pos, "".join(
                    r.choice("abcdef") for _ in range(r.randint(1, 3))))
            else:
                pos = r.randint(0, n - 1)
                doc.local_delete(agent, pos,
                                 min(r.randint(1, 3), n - pos))

        applied = {"amy": set(), "bob": set()}
        for round_ in range(6):
            for doc, agent, name in ((a_doc, a, "amy"), (b_doc, b, "bob")):
                for _ in range(rng.randint(1, 4)):
                    edit(doc, agent, rng)
                txns = export_txns_since(doc, marks[name])
                flat.extend(txns)
            # cross-merge everything so far (valid causal order), then
            # re-mark so merged remote ops are never re-exported.
            for doc, me in ((a_doc, "amy"), (b_doc, "bob")):
                for t in flat:
                    key = (t.id.agent, t.id.seq)
                    if t.id.agent != me and key not in applied[me]:
                        applied[me].add(key)
                        doc.apply_remote_txn(t)
            marks["amy"] = a_doc.get_next_order()
            marks["bob"] = b_doc.get_next_order()
        assert_fast_exact(flat, capacity=1024)

    def test_merge_appended_or_divergence_window(self):
        # Regression guard for the stale-orp hole: agent Q's second txn
        # merge-appends into its first run (chain), a split later
        # separates them, and a concurrent sibling probes the piece.
        q_doc = ListCRDT()
        q = q_doc.get_or_create_agent_id("quin")
        q_doc.local_insert(q, 0, "XY")          # txn1: run [XY]
        t1 = export_txns_since(q_doc, 0)
        m = q_doc.get_next_order()
        q_doc.local_insert(q, 2, "Z")           # txn2: appends, chains
        t2 = export_txns_since(q_doc, m)

        c_doc = ListCRDT()
        c = c_doc.get_or_create_agent_id("cara")
        for t in t1:                            # cara sees txn1 only
            c_doc.apply_remote_txn(t)
        m3 = c_doc.get_next_order()
        c_doc.local_insert(c, 1, "a")           # between X and Y
        t3 = export_txns_since(c_doc, m3)

        # Receiver integrates in both causal orders.
        for stream in ([*t1, *t2, *t3], [*t1, *t3, *t2]):
            assert_fast_exact(stream, capacity=256)

    def test_split_tail_requalifies_as_sibling(self):
        # ADVICE r5 item 3: an insert-split used to poison the tail's
        # aux origin-right with -2, forcing the serial walk forever on
        # any window holding it.  The tail's TRUE origin-right is now
        # read from the orl table at split time, so a later concurrent
        # sibling probing a window that contains the split tail must
        # classify it exactly (same tiebreak outcome as the serial
        # walk and the oracle), in both causal orders.
        # mmm types "ab", APPENDS "cd" (merge-appends into one run
        # [abcd]; c's table origin-right is ROOT, the head's is not),
        # then SPLITS at 2 with "Q" -> [ab][Q][cd].  The tail [cd]'s
        # head chains to b, and its orl entry (ROOT) differs from the
        # head run's — exactly the "unknowable from the head" case.
        t1 = typed("mmm", [], lambda d, g: d.local_insert(g, 0, "ab"))
        t2 = typed("mmm", t1, lambda d, g: d.local_insert(g, 2, "cd"))
        t3 = typed("mmm", [*t1, *t2],
                   lambda d, g: d.local_insert(g, 2, "Q"))
        # Concurrent peers who saw ONLY "ab" insert after b with
        # origin_right ROOT: their scan windows run to the doc end and
        # contain the split tail as a SIBLING (origin_left == b ==
        # the tail head's) — zzz outranks mmm (scan continues past),
        # aaa ranks below with a matching origin-right (breaks AT the
        # tail), covering both tiebreak arms of the repaired path.
        t4 = typed("zzz", t1, lambda d, g: d.local_insert(g, 2, "z"))
        t5 = typed("aaa", t1, lambda d, g: d.local_insert(g, 2, "a"))
        for stream in ([*t1, *t2, *t3, *t4, *t5],
                       [*t1, *t2, *t4, *t3, *t5],
                       [*t1, *t2, *t5, *t4, *t3]):
            assert_fast_exact(stream, capacity=256)

    def test_pseudo_breaker_beats_stale_window_kss(self):
        # Review r5 regression: the pseudo candidate (mid-run char at
        # cursor0) BREAKS the scan (rank > mine, same origin_right),
        # while the window still holds a higher-ranked different-
        # origin-right sibling (kss).  kss was reduced against the
        # pre-pseudo kfb; the winner must be the pseudo's cursor0, not
        # the stale kss run.
        t1 = typed("mmm", [], lambda d, g: d.local_insert(g, 0, "X"))
        t2 = typed("mmm", t1, lambda d, g: d.local_insert(g, 1, "Y"))
        # ppp saw only X: W after X (ol=X, or=ROOT) — my SGO window run.
        t3 = typed("ppp", t1, lambda d, g: d.local_insert(g, 1, "W"))
        # zzz saw X and W: z between them (ol=X, or=W) — the SGN run.
        t4 = typed("zzz", [*t1, *t3],
                   lambda d, g: d.local_insert(g, 1, "z"))
        # aaa (lowest rank) saw only X: a after X (ol=X, or=ROOT); at
        # the receiver its scan window starts MID-RUN at Y (chained
        # into X's run, rank mmm > aaa, or ROOT == mine -> break).
        t5 = typed("aaa", t1, lambda d, g: d.local_insert(g, 1, "a"))
        assert_fast_exact([*t1, *t2, *t3, *t4, *t5], capacity=256)

    def test_root_origin_window_of_descendants_is_fast(self):
        # The crdt-benchmarks B2.4 shape: two clients edit from empty,
        # neither seeing the other, and the server applies A's update,
        # then B's.  A types at random positions, so its document holds
        # descendants and split tails (runs neither siblings nor chained
        # pieces); B's first inserts go at position 0, ROOT origin-left,
        # so each scans all of A's text.  left_cursor is 0 there and no
        # such run can break the walk: the fast scan takes the window
        # whole and no insert runs the serial walk.
        rng = random.Random(0)

        def history(name, rounds, front):
            def edit(doc, agent):
                n = 0
                for r in range(rounds):
                    pos = 0 if r < front else rng.randint(0, n)
                    word = "".join(rng.choice("abcdefgh")
                                   for _ in range(rng.randint(1, 3)))
                    doc.local_insert(agent, pos, word)
                    n += len(word)
                    if rng.random() < 0.3 and n > pos:
                        d = rng.randint(1, min(2, n - pos))
                        doc.local_delete(agent, pos, d)
                        n -= d
            return typed(name, [], edit)

        txns = [*history("amy", 12, 0), *history("bob", 4, 3)]
        fast_counts, serial_counts = assert_fast_exact(txns)
        assert fast_counts[0] > 0
        assert fast_counts[1] == 0
        assert serial_counts == (fast_counts[0], fast_counts[0])

    def test_non_root_origin_break_still_flags(self):
        # The classic YATA break: aaa types "a"; bbb and ccc each put a
        # char after it (siblings, bbb ranks lower so "abc"); ddd saw
        # "ab" only and puts "d" after b.  d's window holds c, whose
        # origin-left a lies BEFORE d's (olc < left_cursor): the serial
        # walk stops there, so d lands before c.  Only a ROOT origin-left
        # rules that out, so this window must still run the exact walk.
        # c arrives before b: its order follows a's, so only the check
        # that its predecessor sits in the previous row keeps it from
        # passing for a chained piece.
        t1 = typed("aaa", [], lambda d, g: d.local_insert(g, 0, "a"))
        t2 = typed("bbb", t1, lambda d, g: d.local_insert(g, 1, "b"))
        t3 = typed("ccc", t1, lambda d, g: d.local_insert(g, 1, "c"))
        t4 = typed("ddd", [*t1, *t2],
                   lambda d, g: d.local_insert(g, 2, "d"))
        stream = [*t1, *t3, *t2, *t4]
        assert oracle_txns(stream).to_string() == "abdc"
        fast_counts, _ = assert_fast_exact(stream, capacity=256)
        assert fast_counts[1] >= 1
