"""Sequence-parallel RLE MUTATION: the FULL op surface for one huge doc
sharded over the mesh's ``sp`` axis.

``parallel.sp_runs`` gave the read side (live prefix / rank / order
lookups).  This module is the write side: local edits (r3 missing #4)
AND remote ops (r4 missing #4) — sharded YATA integrate + sharded
remote delete — whose final state equals the single-device engines.

Layout: shard ``s`` owns a PACKED local slice of ``R`` run rows
``(±(order+1), len)`` plus a row count; global document order is the
concatenation of the shards' packed prefixes in ``sp`` order (the mesh
axis plays the B-tree's top levels, `range_tree/mod.rs:85-93`).  The
by-order origin/rank tables (the YATA scan's inputs) are sharded by
ORDER RANGE: shard ``s`` owns orders ``[s*OTS, (s+1)*OTS)``; reads are
one masked local lookup + a psum, writes a masked pass over the owner's
range (an insert run crossing a range boundary writes on both owners).

Per op:

- **local delete** (`mutations.rs:520-570`): every shard clips the
  target live span ``[p, p+d)`` against its own carry-adjusted cumsum
  and flips / boundary-splits INDEPENDENTLY — a delete spanning many
  shards is one fully-parallel pass; the only communication is the
  carry all-gather (one i32 per shard over ICI).
- **local insert** (`mutations.rs:17-179`): exactly one shard owns live
  rank ``p`` (the `root.rs:54-88` descent over shard totals); it
  splices locally (<= 3 touched rows); discovered origins psum-extract
  to every shard, which then records them in its table slice.
- **remote delete** (`doc.rs:295-340`): runs are disjoint ORDER
  intervals, so the target range fully covers every run it touches
  except at most the two holding its endpoints — the same one-pass
  clip as the local delete, keyed by orders; covered DEAD runs count
  toward the idempotency total without flipping
  (`double_delete.rs:6-9`).
- **remote insert** (`doc.rs:167-234`): the YATA conflict scan walks
  raw positions with replicated scan state; each probe resolves its
  char via the owning shard (psum) and its origins via the owning
  table shard (psum).  Conflict-free ops break on the first probe
  (`doc.rs:192-194`), so the while-loop's collective cost is paid per
  CONFLICT, not per op.
- a shard whose slice fills raises the capacity error flag and skips
  the splice; ``SpDoc(auto_reshard=True)`` catches the flag between
  streams, rebalances rows evenly (host-side resharding — the B-tree
  rebuild analog), and retries.

All collectives are XLA-emitted over the ``sp`` axis (shard_map +
all_gather/psum); the same code compiles for a real ICI mesh unchanged.
Tested on the virtual 8-device CPU mesh against ``ops.rle``, the
single-device ``ops.rle_mixed`` storm, and the oracle
(``tests/test_sp_apply.py``); exercised multi-chip by
``__graft_entry__.dryrun_multichip``.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common import ROOT_ORDER
from ..ops.batch import (
    KIND_LOCAL,
    KIND_REMOTE_DEL,
    KIND_REMOTE_INS,
    OpTensors,
    require_unfused,
)

ROOT_I = np.int32(np.uint32(ROOT_ORDER))  # -1
TAB_UNKNOWN = -2  # by-order table sentinel: entry not yet known

# Error flag bits (SpDoc.apply_stream decodes).
ERR_CAPACITY = 1
ERR_BAD_DELETE = 2
ERR_NO_OWNER = 4
ERR_ORDER_MISS = 8


def _shift2(x, amt):
    """Rows shifted toward higher indices by traced ``amt`` in {0,1,2}."""
    return jnp.where(amt == 0, x,
                     jnp.where(amt == 1, jnp.roll(x, 1), jnp.roll(x, 2)))


@lru_cache(maxsize=16)
def make_sp_apply(mesh: Mesh, R: int, OTS: int):
    """Build the sharded FULL-SURFACE replayer for ``mesh`` (jitted).

    ``R`` = run-row capacity PER SHARD; ``OTS`` = by-order table rows
    per shard (total order space = nsp*OTS).  Returns ``replay(ordp,
    lenp, rows, oll, orl, rkl, kind, pos, dlen, dtgt, olop, orop, rank,
    ilen, start)`` mapping sharded state + a replicated op stream [S]
    to (new state, per-op origin logs, error flags).

    lru-cached by the full static geometry ``(mesh, R, OTS)`` (Mesh is
    hashable) — two SpDocs with the same geometry share ONE compiled
    replayer instead of re-tracing per doc (the ``_build_call``
    pattern, round-17 allowlist burn-down).
    """
    spec = P("sp")
    none = P()
    nsp = mesh.shape["sp"]

    @partial(shard_map, mesh=mesh,
             in_specs=(spec,) * 6 + (none,) * 9,
             out_specs=(spec,) * 6 + (none, none, none),
             check_vma=False)
    def replay(ordp0, lenp0, rows0, oll0, orl0, rkl0,
               kind, pos, dlen, dtgt, olop, orop, rank, ilen, start):
        idx = jnp.arange(R)
        sidx = lax.axis_index("sp")
        tab_base = sidx * OTS
        tab_g = tab_base + jnp.arange(OTS)  # my slice's global orders

        def gather_carry(lv_total):
            totals = lax.all_gather(lv_total, "sp")
            carry = jnp.sum(jnp.where(jnp.arange(nsp) < sidx, totals, 0))
            return carry, totals

        # ---- by-order table ops (sharded by order range) ---------------

        def tab_read(tab, o):
            """tab[o] (replicated); o < 0 reads 0 — callers mask ROOT."""
            j = jnp.clip(o - tab_base, 0, OTS - 1)
            mine = (o >= tab_base) & (o < tab_base + OTS)
            return lax.psum(jnp.where(mine, tab[j], 0), "sp")

        def tab_write_run(tab, on, st, ln, v):
            """tab[st:st+ln] = v on the owning range shard(s)."""
            hit = on & (tab_g >= st) & (tab_g < st + ln)
            return jnp.where(hit, v, tab)

        def tab_write_chain(tab, on, st, ln, head_val):
            """The insert-run origin_left column: head gets ``head_val``,
            char k > 0 gets its predecessor's order (`span.rs:9-13`)."""
            hit = on & (tab_g >= st) & (tab_g < st + ln)
            return jnp.where(hit,
                             jnp.where(tab_g == st, head_val, tab_g - 1),
                             tab)

        # ---- order -> run / raw-position lookups -----------------------

        def find_order_local(ordp, lenp, o):
            so = jnp.abs(ordp) - 1
            hit = (ordp != 0) & (so <= o) & (o < so + lenp)
            return jnp.any(hit), jnp.argmax(hit)

        def raw_pos_of_order(ordp, lenp, o, need, err):
            """Replicated RAW position of the char with order ``o``."""
            found_l, row = find_order_local(ordp, lenp, o)
            rawcum = jnp.cumsum(lenp)
            raw_before = rawcum[row] - lenp[row]
            off = o - (jnp.abs(ordp[row]) - 1)
            carry, _ = gather_carry(rawcum[-1])
            p = lax.psum(jnp.where(found_l, carry + raw_before + off, 0),
                         "sp")
            found = lax.psum(found_l.astype(jnp.int32), "sp") > 0
            err = err | jnp.where(need & ~found, ERR_ORDER_MISS, 0)
            return p, err

        def cursor_after(ordp, lenp, o, need, err):
            is_root = o == ROOT_I
            # A TAB_UNKNOWN origin (load_tables skipped after a snapshot
            # load) must flag, not silently resolve as order 0 (review
            # r5: jnp.maximum would alias it to an existing char).
            err = err | jnp.where(need & (o == TAB_UNKNOWN),
                                  ERR_ORDER_MISS, 0)
            p, err = raw_pos_of_order(ordp, lenp, jnp.maximum(o, 0),
                                      need & ~is_root, err)
            return jnp.where(is_root, 0, p + 1), err

        def apply_partial(act, i_p, ordp, lenp, cs, ce):
            o = ordp[i_p]
            ln = lenp[i_p]
            cs_i = cs[i_p]
            ce_i = ce[i_p]
            cov_i = ce_i - cs_i
            has_head = (cs_i > 0) & act
            has_tail = (ce_i < ln) & act
            amt = has_head.astype(jnp.int32) + has_tail.astype(jnp.int32)
            so = _shift2(ordp, amt)
            sl = _shift2(lenp, amt)
            no = jnp.where(idx <= i_p, ordp, so)
            nl = jnp.where(idx <= i_p, lenp, sl)
            p0o = jnp.where(has_head, o, -(o + cs_i))
            p0l = jnp.where(has_head, cs_i, cov_i)
            p1o = jnp.where(has_head, -(o + cs_i), o + ce_i)
            p1l = jnp.where(has_head, cov_i, ln - ce_i)
            w0 = act & (idx == i_p)
            no = jnp.where(w0, p0o, no)
            nl = jnp.where(w0, p0l, nl)
            w1 = act & (idx == i_p + 1) & (amt >= 1)
            no = jnp.where(w1, p1o, no)
            nl = jnp.where(w1, p1l, nl)
            w2 = act & (idx == i_p + 2) & (amt == 2)
            no = jnp.where(w2, o + ce_i, no)
            nl = jnp.where(w2, ln - ce_i, nl)
            return no, nl, amt

        def do_delete(ordp, lenp, nrows, err, on, p, d):
            """Every shard retires its intersection of the live span
            [p, p+d) in one clip pass — cross-shard deletes are
            embarrassingly parallel.  No-op (collectives still run,
            keeping the SPMD program unconditional) when ``on`` is
            false."""
            lv = jnp.where(ordp > 0, lenp, 0)
            local = jnp.cumsum(lv)
            carry, _ = gather_carry(local[-1])
            before = carry + local - lv
            rem = jnp.where(on, d, 0)
            cs = jnp.clip(p - before, 0, lv)
            ce = jnp.clip(p + rem - before, 0, lv)
            cov = ce - cs
            covered = lax.psum(jnp.sum(cov), "sp")
            err = err | jnp.where(on & (covered < rem), ERR_BAD_DELETE, 0)

            cap_bad = nrows + 2 > R
            full = (cov > 0) & (cov == lenp)
            part = (cov > 0) & jnp.logical_not(full)
            npart = jnp.sum(part.astype(jnp.int32))
            err = err | jnp.where((npart > 0) & cap_bad, ERR_CAPACITY, 0)
            act = jnp.logical_not(cap_bad)
            i1 = jnp.min(jnp.where(part, idx, R))
            i2 = jnp.max(jnp.where(part, idx, -1))
            # Full-cover flips share the capacity gate: a flagged delete
            # must be a clean no-op, not a half-applied one.
            ordp = jnp.where(full & act, -ordp, ordp)
            ordp, lenp, a2 = apply_partial(
                act & (npart >= 1), i2, ordp, lenp, cs, ce)
            ordp, lenp, a1 = apply_partial(
                act & (npart == 2), i1, ordp, lenp, cs, ce)
            return ordp, lenp, nrows + jnp.where(act, a1 + a2, 0), err

        def do_insert(ordp, lenp, nrows, err, on, p, il, st):
            """One owner shard splices; heads/carries ride two small
            all-gathers; origins psum-extract to every shard.  No-op
            (collectives still run) when ``on`` is false."""
            lv = jnp.where(ordp > 0, lenp, 0)
            local = jnp.cumsum(lv)
            carry, _totals = gather_carry(local[-1])
            owner = on & jnp.where(p == 0, sidx == 0,
                                   (carry < p) & (p <= carry + local[-1]))
            err = err | jnp.where(
                on & (lax.psum(owner.astype(jnp.int32), "sp") == 0),
                ERR_NO_OWNER, 0)
            cap_bad = nrows + 2 > R
            err = err | jnp.where(owner & cap_bad, ERR_CAPACITY, 0)
            active = owner & jnp.logical_not(cap_bad)

            local_rank = p - carry
            i_r = jnp.sum(((local < local_rank) & (idx < nrows))
                          .astype(jnp.int32))
            i_r = jnp.minimum(i_r, R - 1)
            o_r = ordp[i_r]
            l_r = lenp[i_r]
            off = local_rank - (local[i_r] - lv[i_r])

            # Successor across the shard boundary: each shard publishes
            # its head row; the first occupied head PAST this shard is
            # the raw successor when the splice lands at the local end.
            heads = lax.all_gather(jnp.where(nrows > 0, ordp[0], 0), "sp")
            after = (jnp.arange(nsp) > sidx) & (heads != 0)
            nxt_head = jnp.where(jnp.any(after),
                                 heads[jnp.argmax(after)], 0)
            first_head = jnp.where(jnp.any(heads != 0),
                                   heads[jnp.argmax(heads != 0)], 0)

            mrg = (p > 0) & (off == l_r) & ((st + 1) == (o_r + l_r))
            is_split = (p > 0) & (off < l_r)
            left = jnp.where(p == 0, ROOT_I, (o_r - 1) + (off - 1))
            nxt_in_rows = jnp.where(i_r + 1 < nrows,
                                    ordp[jnp.minimum(i_r + 1, R - 1)],
                                    nxt_head)
            succ = jnp.where(p == 0, first_head,
                             jnp.where(is_split, o_r + off, nxt_in_rows))
            right = jnp.where(succ == 0, ROOT_I, jnp.abs(succ) - 1)

            ins_at = jnp.where(p == 0, 0, i_r + 1)
            amt = jnp.where(jnp.logical_not(active) | mrg, 0,
                            jnp.where(is_split, 2, 1))
            so = _shift2(ordp, amt)
            sl = _shift2(lenp, amt)
            no = jnp.where(idx < ins_at, ordp, so)
            nl = jnp.where(idx < ins_at, lenp, sl)
            nl = jnp.where(active & is_split & (idx == i_r), off, nl)
            new_run = active & jnp.logical_not(mrg) & (idx == ins_at)
            no = jnp.where(new_run, st + 1, no)
            nl = jnp.where(new_run, il, nl)
            tail = active & is_split & (idx == ins_at + 1)
            no = jnp.where(tail, o_r + off, no)
            nl = jnp.where(tail, l_r - off, nl)
            nl = jnp.where(active & mrg & (idx == i_r), l_r + il, nl)
            nrows = nrows + amt

            ol = lax.psum(jnp.where(active, left, 0), "sp")
            orr = lax.psum(jnp.where(active, right, 0), "sp")
            any_act = lax.psum(active.astype(jnp.int32), "sp") > 0
            return (no, nl, nrows, err,
                    jnp.where(any_act, ol, 0),
                    jnp.where(any_act, orr, 0), any_act)

        def do_remote_delete(ordp, lenp, nrows, err, on, t, d):
            """One-pass ORDER-interval tombstone (`doc.rs:295-340`):
            runs are disjoint order intervals, so the target range fully
            covers every run it touches except at most the two holding
            its endpoints — the local-delete clip keyed by orders, fully
            parallel across shards.  Covered DEAD runs count toward the
            idempotency total without flipping (`double_delete.rs:6-9`)."""
            so = jnp.abs(ordp) - 1
            occ = ordp != 0
            rem = jnp.where(on, d, 0)
            cs = jnp.clip(t - so, 0, lenp)
            ce = jnp.clip(t + rem - so, 0, lenp)
            cov = jnp.where(occ, ce - cs, 0)
            covered = lax.psum(jnp.sum(cov), "sp")
            err = err | jnp.where(on & (covered < rem), ERR_BAD_DELETE, 0)

            live = ordp > 0
            full = live & (cov > 0) & (cov == lenp)
            part = live & (cov > 0) & jnp.logical_not(cov == lenp)
            npart = jnp.sum(part.astype(jnp.int32))
            # Max growth is +2: one run holding both endpoints 3-way
            # splits (+2), or the two endpoint runs each split one-sided
            # (+1 each) — never +2 per partial (review r5).
            cap_bad = nrows + 2 > R
            err = err | jnp.where(on & (npart > 0) & cap_bad,
                                  ERR_CAPACITY, 0)
            act = on & jnp.logical_not(cap_bad)
            i1 = jnp.min(jnp.where(part, idx, R))
            i2 = jnp.max(jnp.where(part, idx, -1))
            ordp = jnp.where(full & act, -ordp, ordp)
            ordp, lenp, a2 = apply_partial(
                act & (npart >= 1), i2, ordp, lenp, cs, ce)
            ordp, lenp, a1 = apply_partial(
                act & (npart == 2), i1, ordp, lenp, cs, ce)
            return ordp, lenp, nrows + jnp.where(act, a1 + a2, 0), err

        def integrate(ordp, lenp, nrows, oll, orl, rkl, on, my_rank,
                      o_left, o_right, err):
            """The YATA conflict scan (`doc.rs:183-222`) with REPLICATED
            scan state: each probe resolves its char via the owning run
            shard and its origins via the owning table shard (psums).
            Conflict-free ops break on the first probe
            (`doc.rs:192-194`)."""
            rawcum = jnp.cumsum(lenp)
            carry, _ = gather_carry(rawcum[-1])
            n = lax.psum(rawcum[-1], "sp")
            cursor0, err = cursor_after(ordp, lenp, o_left, on, err)
            left_cursor = cursor0

            def cond(state):
                cursor, scanning, scan_start, done, err = state
                return ~done & (cursor < n)

            def body(state):
                cursor, scanning, scan_start, done, err = state
                own = (cursor >= carry) & (cursor < carry + rawcum[-1])
                local = cursor - carry
                i_r = jnp.sum(((rawcum <= local) & (idx < nrows))
                              .astype(jnp.int32))
                i_r = jnp.minimum(i_r, R - 1)
                o_r = lax.psum(jnp.where(own, ordp[i_r], 0), "sp")
                l_r = lax.psum(jnp.where(own, lenp[i_r], 0), "sp")
                off = lax.psum(jnp.where(
                    own, local - (rawcum[i_r] - lenp[i_r]), 0), "sp")
                so = jnp.abs(o_r) - 1
                other_order = so + off
                other_left = tab_read(oll, other_order)
                other_right = tab_read(orl, other_order)
                other_rank = tab_read(rkl, other_order)
                olc, err = cursor_after(ordp, lenp, other_left, ~done,
                                        err)
                brk = (other_order == o_right) | (olc < left_cursor)
                eq = ~brk & (olc == left_cursor)
                gt = my_rank > other_rank
                brk = brk | (eq & ~gt & (o_right == other_right))
                starts_scan = eq & ~gt & (o_right != other_right)
                scan_start = jnp.where(starts_scan & ~scanning, cursor,
                                       scan_start)
                scanning = jnp.where(
                    eq, jnp.where(gt, False,
                                  jnp.where(o_right == other_right,
                                            scanning, True)),
                    scanning)
                contains_right = ((o_right > other_order)
                                  & (o_right < so + l_r))
                stp = jnp.where(contains_right, o_right - other_order,
                                l_r - off)
                cursor = jnp.where(brk, cursor, cursor + stp)
                return cursor, scanning, scan_start, done | brk, err

            f = jnp.asarray(False)
            cursor, scanning, scan_start, _, err = lax.while_loop(
                cond, body, (cursor0, f, cursor0, ~on, err))
            # The scan mutates nothing, so rawcum/carry stay valid for
            # the caller's splice (saves one all-gather per op).
            return (jnp.where(scanning, scan_start, cursor), rawcum,
                    carry, err)

        def do_remote_insert(ordp, lenp, nrows, oll, orl, rkl, err, on,
                             my_rank, o_left, o_right, il, st):
            """`doc.rs:274-293` sharded: integrate to a raw position,
            splice on the owner shard (tombstone-sign-preserving tail;
            merge gated on the origin chain so the YATA run-skip stays
            sound — see ops.rle_lanes_mixed), record origins in the
            order-range tables."""
            c, rawcum, carry, err = integrate(
                ordp, lenp, nrows, oll, orl, rkl, on, my_rank, o_left,
                o_right, err)
            owner = on & jnp.where(c == 0, sidx == 0,
                                   (carry < c) & (c <= carry + rawcum[-1]))
            err = err | jnp.where(
                on & (lax.psum(owner.astype(jnp.int32), "sp") == 0),
                ERR_NO_OWNER, 0)
            cap_bad = nrows + 2 > R
            err = err | jnp.where(owner & cap_bad, ERR_CAPACITY, 0)
            active = owner & jnp.logical_not(cap_bad)

            local = c - carry
            i_r = jnp.sum(((rawcum < local) & (idx < nrows))
                          .astype(jnp.int32))
            i_r = jnp.minimum(i_r, R - 1)
            o_r = ordp[i_r]
            l_r = lenp[i_r]
            off = local - (rawcum[i_r] - lenp[i_r])

            mrg = ((c > 0) & (o_r > 0) & (off == l_r)
                   & ((st + 1) == (o_r + l_r))
                   & (o_left == o_r + l_r - 2))
            is_split = (c > 0) & (off < l_r)
            ins_at = jnp.where(c == 0, 0, i_r + 1)
            amt = jnp.where(jnp.logical_not(active) | mrg, 0,
                            jnp.where(is_split, 2, 1))
            so_s = _shift2(ordp, amt)
            sl_s = _shift2(lenp, amt)
            no = jnp.where(idx < ins_at, ordp, so_s)
            nl = jnp.where(idx < ins_at, lenp, sl_s)
            nl = jnp.where(active & is_split & (idx == i_r), off, nl)
            new_run = active & jnp.logical_not(mrg) & (idx == ins_at)
            no = jnp.where(new_run, st + 1, no)
            nl = jnp.where(new_run, il, nl)
            tail = active & is_split & (idx == ins_at + 1)
            tail_o = jnp.where(o_r > 0, o_r + off, o_r - off)
            no = jnp.where(tail, tail_o, no)
            nl = jnp.where(tail, l_r - off, nl)
            nl = jnp.where(active & mrg & (idx == i_r), l_r + il, nl)
            any_act = lax.psum(active.astype(jnp.int32), "sp") > 0
            return no, nl, nrows + amt, err, any_act

        def step(carry, op):
            ordp, lenp, nrows, oll, orl, rkl, err = carry
            kd, p, d, t, olv, orv, rk, il, st = op
            is_local = kd == KIND_LOCAL
            ri_on = (kd == KIND_REMOTE_INS) & (il > 0)
            ordp, lenp, nrows, err = do_delete(
                ordp, lenp, nrows, err, is_local & (d > 0), p, d)
            ordp, lenp, nrows, err, ol1, or1, li_act = do_insert(
                ordp, lenp, nrows, err, is_local & (il > 0), p, il, st)
            ordp, lenp, nrows, err = do_remote_delete(
                ordp, lenp, nrows, err,
                (kd == KIND_REMOTE_DEL) & (d > 0), t, d)
            ordp, lenp, nrows, err, ri_act = do_remote_insert(
                ordp, lenp, nrows, oll, orl, rkl, err,
                ri_on, rk, olv, orv, il, st)

            # Table upkeep (replicated values, masked to the order-range
            # owners): a local insert records its DISCOVERED origins, a
            # remote insert its given ones; at most one is active per
            # step, and a capacity-blocked splice records nothing.
            ins_on = li_act | ri_act
            head_ol = jnp.where(ri_act, olv, ol1)
            run_or = jnp.where(ri_act, orv, or1)
            oll = tab_write_chain(oll, ins_on, st, il, head_ol)
            orl = tab_write_run(orl, ins_on, st, il, run_or)
            rkl = tab_write_run(rkl, ins_on, st, il, rk)
            ol_out = jnp.where(ri_act, olv, ol1)
            or_out = jnp.where(ri_act, orv, or1)
            return ((ordp, lenp, nrows, oll, orl, rkl, err),
                    (ol_out, or_out))

        nrows0 = rows0[0]
        err0 = jnp.int32(0)
        (ordp, lenp, nrows, oll, orl, rkl, err), (ols, ors) = lax.scan(
            step, (ordp0, lenp0, nrows0, oll0, orl0, rkl0, err0),
            (kind, pos, dlen, dtgt, olop, orop, rank, ilen, start))
        # Bitmask-OR across shards (psum would collide flag bits).
        errs = lax.all_gather(err, "sp")
        err_all = jnp.int32(0)
        for s in range(nsp):
            err_all = err_all | errs[s]
        return (ordp, lenp, nrows[jnp.newaxis], oll, orl, rkl,
                ols.astype(jnp.uint32), ors.astype(jnp.uint32),
                err_all)

    return jax.jit(replay)


class SpDoc:
    """One huge document sharded over the ``sp`` axis: packed per-shard
    run-row slices + counts + order-range table slices, with a host-side
    apply/expand surface for the FULL op stream (local + remote)."""

    def __init__(self, mesh: Mesh, shard_rows: int,
                 order_rows: int = 1024, auto_reshard: bool = False):
        self.mesh = mesh
        self.nsp = mesh.shape["sp"]
        self.R = shard_rows
        self.OTS = order_rows
        self.auto_reshard = auto_reshard
        self._replay = make_sp_apply(mesh, shard_rows, order_rows)
        sharding = NamedSharding(mesh, P("sp"))
        self.ordp = jax.device_put(
            jnp.zeros(self.nsp * shard_rows, jnp.int32), sharding)
        self.lenp = jax.device_put(
            jnp.zeros(self.nsp * shard_rows, jnp.int32), sharding)
        self.rows = jax.device_put(
            jnp.zeros(self.nsp, jnp.int32), sharding)
        self.oll = jax.device_put(
            jnp.full(self.nsp * order_rows, TAB_UNKNOWN, jnp.int32),
            sharding)
        self.orl = jax.device_put(
            jnp.full(self.nsp * order_rows, TAB_UNKNOWN, jnp.int32),
            sharding)
        self.rkl = jax.device_put(
            jnp.zeros(self.nsp * order_rows, jnp.int32), sharding)
        self.ol_log = {}
        self.or_log = {}

    def load(self, ordp: np.ndarray, lenp: np.ndarray) -> None:
        """Reshard an existing document's packed global runs evenly
        across the sp axis (row-balanced).  This is the between-streams
        rebalance: a fresh ``SpDoc`` holds every live rank in shard 0
        (empty shards own no ranks), so long-lived streams load a
        distributed snapshot first and re-load when a shard approaches
        its row budget — the host-side analog of a B-tree rebuild.  The
        by-order tables are keyed by ORDER, not position, so they are
        untouched; a doc loaded from a snapshot must also
        ``load_tables`` before applying REMOTE ops."""
        n = len(ordp)
        assert n <= self.nsp * self.R, (n, self.nsp * self.R)
        per = -(-n // self.nsp)  # ceil: heads get the extra row
        assert per <= self.R
        o2 = np.zeros((self.nsp, self.R), np.int32)
        l2 = np.zeros((self.nsp, self.R), np.int32)
        rows = np.zeros(self.nsp, np.int32)
        at = 0
        for s in range(self.nsp):
            take = min(per, n - at)
            o2[s, :take] = ordp[at:at + take]
            l2[s, :take] = lenp[at:at + take]
            rows[s] = take
            at += take
        sharding = NamedSharding(self.mesh, P("sp"))
        self.ordp = jax.device_put(jnp.asarray(o2.reshape(-1)), sharding)
        self.lenp = jax.device_put(jnp.asarray(l2.reshape(-1)), sharding)
        self.rows = jax.device_put(jnp.asarray(rows), sharding)

    def load_tables(self, oll: np.ndarray, orl: np.ndarray,
                    rkl: np.ndarray) -> None:
        """Load by-order origin/rank tables (1-D [order] arrays, i32,
        ROOT = −1, unknown = −2) — required before REMOTE ops touch
        history that predates this ``SpDoc``."""
        ocap = self.nsp * self.OTS
        sharding = NamedSharding(self.mesh, P("sp"))

        def put(a, fill):
            a = np.asarray(a, np.int32)
            assert len(a) <= ocap, (len(a), ocap)
            out = np.full(ocap, fill, np.int32)
            out[:len(a)] = a
            return jax.device_put(jnp.asarray(out), sharding)

        self.oll = put(oll, TAB_UNKNOWN)
        self.orl = put(orl, TAB_UNKNOWN)
        self.rkl = put(rkl, 0)

    def apply_stream(self, ops: OpTensors) -> None:
        """Apply a compiled op stream (unbatched ``[S]`` columns, any
        kind mix) to the sharded state (one jitted scan; collectives
        over sp).  With ``auto_reshard``, a shard-capacity flag triggers
        one even host-side rebalance + retry (state commits only on a
        clean stream, so the retry replays from the pre-stream state)."""
        kinds = np.asarray(ops.kind)
        assert kinds.ndim == 1, "sp apply takes one unbatched stream"
        require_unfused(ops, "sp apply")
        # Local-only streams may run past the table range (local ops
        # never READ the tables, so SpDoc's local capability stays
        # unbounded); remote ops probe by order, so their order space
        # must fit — out-of-range table writes would silently drop and
        # later probes would mis-resolve.
        if bool((kinds != KIND_LOCAL).any()):
            top_order = int((np.asarray(ops.ins_order_start, np.int64)
                             + np.asarray(ops.ins_len, np.int64)).max(
                                 initial=0))
            assert top_order <= self.nsp * self.OTS, (
                f"order space {top_order} exceeds the table capacity "
                f"{self.nsp * self.OTS}; raise order_rows")
        cols = tuple(
            jnp.asarray(np.asarray(c, dtype=np.uint32).view(np.int32))
            for c in (ops.kind, ops.pos, ops.del_len, ops.del_target,
                      ops.origin_left, ops.origin_right, ops.rank,
                      ops.ins_len, ops.ins_order_start))
        for attempt in (0, 1):
            out = self._replay(self.ordp, self.lenp, self.rows,
                               self.oll, self.orl, self.rkl, *cols)
            ordp, lenp, rows, oll, orl, rkl, ols, ors, err = out
            # Commit state only on a clean stream: a flagged stream is
            # half-applied and the pre-stream state is what recovery
            # (reshard + replay) needs.
            err = int(np.asarray(err).max())
            if not err:
                self.ordp, self.lenp, self.rows = ordp, lenp, rows
                self.oll, self.orl, self.rkl = oll, orl, rkl
                break
            if (err & ERR_CAPACITY) and self.auto_reshard and attempt == 0:
                # Even rebalance, then retry once from pre-stream state.
                self.load(*self.runs())
                continue
            if err & ERR_CAPACITY:
                raise RuntimeError(
                    "sp shard capacity exhausted; reshard with a larger "
                    "per-shard row budget")
            if err & ERR_BAD_DELETE:
                raise RuntimeError(
                    "delete ran past the end of the document")
            if err & ERR_NO_OWNER:
                raise RuntimeError(
                    "insert rank beyond the document length")
            if err & ERR_ORDER_MISS:
                raise RuntimeError(
                    "order lookup missed: an op referenced an order "
                    "absent from device state (load_tables missing?)")
        starts = np.asarray(ops.ins_order_start, np.int64)
        ilens = np.asarray(ops.ins_len, np.int64)
        ol_np = np.asarray(ols)
        or_np = np.asarray(ors)
        for s, (st, il) in enumerate(zip(starts, ilens)):
            if il > 0:
                self.ol_log[int(st)] = int(ol_np[s])
                self.or_log[int(st)] = int(or_np[s])

    def runs(self) -> tuple:
        """Host copy of the global packed runs: (ordp, lenp) 1-D."""
        o = np.asarray(self.ordp).reshape(self.nsp, self.R)
        l = np.asarray(self.lenp).reshape(self.nsp, self.R)
        r = np.asarray(self.rows)
        o_parts = [o[s, :r[s]] for s in range(self.nsp)]
        l_parts = [l[s, :r[s]] for s in range(self.nsp)]
        return (np.concatenate(o_parts) if o_parts else np.zeros(0, np.int32),
                np.concatenate(l_parts) if l_parts else np.zeros(0, np.int32))

    def expand(self) -> np.ndarray:
        """Per-char ±(order+1) column in document order (host)."""
        o, ln = self.runs()
        if len(o) == 0:
            return np.zeros(0, np.int32)
        o = o.astype(np.int64)
        ln = ln.astype(np.int64)
        assert (ln > 0).all(), "occupied run with non-positive length"
        total = int(ln.sum())
        base = np.repeat(np.abs(o), ln)
        within = np.arange(total) - np.repeat(np.cumsum(ln) - ln, ln)
        return (np.repeat(np.sign(o), ln) * (base + within)).astype(np.int32)

    def to_string(self, ops_list) -> str:
        """Materialize content from the expanded orders + op streams'
        char logs (the device state stores orders, not text)."""
        chars = {}
        for ops in ops_list:
            ilens = np.asarray(ops.ins_len)
            starts = np.asarray(ops.ins_order_start, np.int64)
            cps = np.asarray(ops.chars)
            for s in np.nonzero(ilens)[0]:
                for j in range(int(ilens[s])):
                    chars[int(starts[s]) + j] = chr(int(cps[s, j]))
        flat = self.expand()
        return "".join(chars[int(x) - 1] for x in flat if x > 0)
