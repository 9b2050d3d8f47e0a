"""Kernel-exact step-cost probe for the BLOCKED lanes engines (ISSUE 2):
touched rows per step, before vs after, on the config-5 and config-5r
workloads — a CPU count, not a chip measurement (CPU-only by
construction: it never touches the chip).

Modeled on perf/merge_sim.py: a host replay of the kernels' EXACT row
algebra (runs, K-row blocks, logical block tables, leaf splits, the
order->block hint with cold/stale fallbacks) over the same workload
generators and growing per-chunk capacities bench.py uses, counting two
metrics per step:

- **touched rows** — unique state/table rows the step's algorithm
  examines or writes: the un-blocked kernels' position->run scan,
  splice, and interval clip each span the whole allocated [CAP, B]
  plane, so an un-blocked step touches CAP rows; a blocked step touches
  the NBT-row logical table + the K-row target block (+ K+NBT per extra
  delete block / split / hint fallback).  This is the O(NB+K)-vs-O(CAP)
  claim the restructure makes, and the acceptance metric (>= 10x).
- **pass traffic** — row-reads summed over every vector pass the kernel
  actually makes, including the un-blocked cumsum's log2(CAP) rolls and
  the blocked kernels' NB-way select-chain gathers (which stream CAP
  rows to address one block).  This is the honest wall-clock predictor:
  smaller than the touched-rows ratio because lane-addressed gathers
  still stream the plane; the chip run decides the final number.

Single-author remote streams (the 5r shape) integrate with a
first-probe YATA break (each physically-following char either IS the
op's origin_right or has an earlier-positioned origin_left), so the
scan cost is one probe — the same count the kernels pay on these
streams.

Round 7 (ISSUE 4) adds ``--serve``: replay the SERVE loadgen tick trace
— the per-doc compiled streams the continuous batcher actually ships to
the device, tapped via ``ContinuousBatcher.step_trace``, with per-lane
sims re-seeded from the oracle at every residency upload exactly as
``serve/lanes_backend.upload_lane`` re-seeds the device — through the
same two cost models, plus the live acceptance proof: the
``rle-lanes-mixed`` loadgen run must end bit-identical per doc to a
``flat``-backend twin run of the same seed AND to the host oracles.
Writes ``perf/serve_lanes_r7.json`` and prints one bench-row-ready JSON
line (bench.py's ``serve-lanes`` config wraps it).

Run: python perf/blocked_lanes_sim.py [--docs N] [--block-k K] [--serve]
"""
import argparse
import json
import math
import random
import sys
import time

sys.path.insert(0, ".")

from text_crdt_rust_tpu.config import lane_block_geometry  # noqa: E402
from text_crdt_rust_tpu.ops.batch import row_growth_bound  # noqa: E402


class Counter:
    def __init__(self):
        self.unb_touched = 0
        self.unb_traffic = 0
        self.blk_touched = 0
        self.blk_traffic = 0
        self.steps = 0
        self.splits = 0
        self.hint_misses = 0
        self.hint_probes = 0


class UnblockedCost:
    """Pass counts of the un-blocked kernels (rle_lanes /
    rle_lanes_mixed): every phase spans the allocated [CAP] plane."""

    def __init__(self, cap):
        self.cap = cap
        self.logc = max(1, math.ceil(math.log2(max(cap, 2))))

    def local_insert(self, c: Counter):
        c.unb_touched += self.cap
        # live prefix (1 + log2 rolls) + locate reduces (5) + splice (8)
        c.unb_traffic += (self.logc + 14) * self.cap

    def local_delete(self, c: Counter):
        c.unb_touched += self.cap
        # live prefix + clip + two apply_partial transforms
        c.unb_traffic += (self.logc + 19) * self.cap

    def remote_insert(self, c: Counter, ocap):
        c.unb_touched += self.cap + 3  # 3 indexed by-order entries
        # hoisted raw cumsum + cursor_after (3) + 1 scan probe
        # (3 t_reads over OCAP + cursor_after 3 + run_at 3) + splice 13
        c.unb_traffic += (self.logc + 22) * self.cap + 3 * ocap

    def remote_delete(self, c: Counter):
        c.unb_touched += self.cap
        # interval clip + per-slot updates + two apply_partials
        c.unb_traffic += 24 * self.cap


class BlockedLaneSim:
    """One lane's EXACT blocked-kernel row algebra: K-row physical
    blocks, logical block order, leaf splits, liv/raw tables, and the
    order->block hint with cold/stale fallback accounting."""

    def __init__(self, K, cap, counter, ocap=0):
        self.K = K
        self.cap = cap
        self.ocap = ocap
        self.c = counter
        self.nbt = max(8, cap // K)
        # physical blocks: list of lists of [start_order, length, live]
        self.blocks = [[]]
        self.order = [0]      # logical slot -> physical block
        self.hint = {}        # order -> physical block (may be stale)
        self.fwd = {}         # block -> split destination (last)
        self._sb = set()      # per-step: distinct blocks touched
        self._st = False      # per-step: logical tables examined
        self._sf = 0          # per-step: whole-plane fallbacks
        self._se = 0          # per-step: indexed table entries read

    def begin_step(self):
        self._sb = set()
        self._st = False
        self._sf = 0
        self._se = 0

    def end_step(self):
        """UNIQUE rows examined this step: each distinct block once,
        the logical tables once, each plane-scan fallback, each indexed
        table entry."""
        self.c.blk_touched += (self.K * len(self._sb)
                               + (self.nbt if self._st else 0)
                               + self.cap * self._sf + self._se)

    def grow(self, cap, ocap=0):
        self.cap = cap
        self.ocap = ocap
        self.nbt = max(8, cap // self.K)
        # hints PERSIST across chunks (the kernel carries ordblk in the
        # warm-start state tuple)

    # -- bookkeeping ------------------------------------------------------

    def _runs(self):
        for b in self.order:
            for r in self.blocks[b]:
                yield r

    def _locate_order(self, o):
        """Hint-guided order locate: (block, run) + cost accounting."""
        self.c.hint_probes += 1
        self.c.blk_traffic += 2 * self.K + self.ocap  # verify + hint read
        self._se += 1
        hb = self.hint.get(o)
        if hb is not None and hb < len(self.blocks):
            for r in self.blocks[hb]:
                if r[0] <= o < r[0] + r[1]:
                    self._sb.add(hb)
                    return hb, r
        # stale hint: chase up to two split forward pointers (one K-row
        # verify each) before the plane-scan fallback
        cand = hb
        for _hop in range(2):
            cand = self.fwd.get(cand) if cand is not None else None
            if cand is None or cand >= len(self.blocks):
                break
            self.c.blk_traffic += 2 * self.K
            for r in self.blocks[cand]:
                if r[0] <= o < r[0] + r[1]:
                    self._sb.add(cand)
                    for oo in range(r[0], r[0] + r[1]):
                        self.hint[oo] = cand
                    return cand, r
        # fallback: whole-plane scan + heal the whole found RUN's span
        self.c.hint_misses += 1
        self._sf += 1
        self.c.blk_traffic += self.cap
        for b in self.order:
            for r in self.blocks[b]:
                if r[0] <= o < r[0] + r[1]:
                    for oo in range(r[0], r[0] + r[1]):
                        self.hint[oo] = b
                    return b, r
        raise AssertionError(f"order {o} absent")

    def _slot_of_live(self, rank1):
        self._st = True
        self.c.blk_traffic += self.nbt
        before = 0
        for li, b in enumerate(self.order):
            lv = sum(r[1] for r in self.blocks[b] if r[2])
            if before + lv >= rank1:
                return li, before
            before += lv
        return len(self.order) - 1, before - lv

    def _slot_of_raw(self, rank1):
        self._st = True
        self.c.blk_traffic += self.nbt
        before = 0
        for li, b in enumerate(self.order):
            rw = sum(r[1] for r in self.blocks[b])
            if before + rw >= rank1:
                return li, before
            before += rw
        return len(self.order) - 1, before - rw

    def _maybe_split(self, li, w=1):
        """Returns True when a split fired (the kernel re-descends
        under ``lax.cond`` only then).  ``w`` > 1 is a fused W-row
        splice needing W + 1 rows of headroom (the kernel's
        ``r0 + w + 1 > K`` check)."""
        b = self.order[li]
        if len(self.blocks[b]) + w + 1 <= self.K:
            return False
        assert len(self.blocks) < self.cap // self.K, "out of blocks"
        rows = self.blocks[b]
        keep = len(rows) // 2
        nb = len(self.blocks)
        self.blocks.append(rows[keep:])
        self.blocks[b] = rows[:keep]
        self.order.insert(li + 1, nb)
        # moved rows' hints go stale (NOT updated — kernel heals on
        # probe); cost: gather + two scatters + table shift
        self.c.splits += 1
        self.fwd[b] = nb
        self._sb.add(b)
        self._sb.add(nb)
        self._st = True
        self.c.blk_traffic += 4 * self.cap + self.nbt
        return True

    def _block_cost(self, b):
        """One gathered-block locate + splice of block ``b``."""
        self._sb.add(b)
        # gather x2 + in-block cumsum/splice (~log2 K + 10 K-passes)
        # + scatter x2 (each streams the plane in the select chain)
        self.c.blk_traffic += 4 * self.cap + \
            (math.ceil(math.log2(self.K)) + 10) * self.K

    # -- ops --------------------------------------------------------------

    def insert_local(self, pos, il, st, w=1):
        """``w`` > 1 is a FUSED backwards-burst step: W stride-L runs
        (descending orders in doc order) land in ONE splice — same
        one-block cost, W + 1 rows of split headroom, merge w==1-only
        (the kernels' contract)."""
        li, before = self._slot_of_live(pos) if pos else (0, 0)
        if self._maybe_split(li, w):
            li, before = self._slot_of_live(pos) if pos else (0, 0)
        b = self.order[li]
        self._block_cost(b)
        rows = self.blocks[b]
        local = pos - before
        L = il // w
        new = [[st + il - (j + 1) * L, L, True] for j in range(w)]
        if pos == 0:
            rows[0:0] = new
        else:
            at = 0
            for i, r in enumerate(rows):
                lv = r[1] if r[2] else 0
                if at + lv >= local:
                    off_live = local - at
                    # char offset of the off_live-th live char's end
                    off = off_live
                    if (w == 1 and r[2] and off == r[1]
                            and st == r[0] + r[1]):
                        r[1] += il
                    elif off == r[1]:
                        rows[i + 1: i + 1] = new
                    elif off < r[1]:
                        tail = [r[0] + off, r[1] - off, r[2]]
                        rows[i: i + 1] = [[r[0], off, r[2]],
                                          *new, tail]
                    break
                at += lv
        for o in range(st, st + il):
            self.hint[o] = b

    def delete_local(self, pos, d):
        rem = d
        while rem > 0:
            li, before = self._slot_of_live(pos + 1)
            if self._maybe_split(li):
                li, before = self._slot_of_live(pos + 1)
            b = self.order[li]
            self._block_cost(b)
            rows = self.blocks[b]
            # One block pass mirrors the kernel exactly: pre-delete
            # cumsums, ``rem`` held fixed for the whole pass.
            covered = 0
            out = []
            at = before
            for r in rows:
                lv = r[1] if r[2] else 0
                cs = min(max(pos - at, 0), lv)
                ce = min(max(pos + rem - at, 0), lv)
                cov = ce - cs
                if cov > 0:
                    if cs > 0:
                        out.append([r[0], cs, True])
                    out.append([r[0] + cs, cov, False])
                    if ce < r[1]:
                        out.append([r[0] + ce, r[1] - ce, True])
                    covered += cov
                else:
                    out.append(r)
                at += lv
            self.blocks[b] = out
            if covered == 0:
                raise AssertionError("delete past end")
            rem -= covered

    def remote_insert(self, o_left, il, st):
        # cursor_after: hint locate + slot inverse + in-block prefix
        if o_left is not None:
            hb, r = self._locate_order(o_left)
            self._st = True
            self.c.blk_traffic += self.nbt + self.K
            # raw position of o_left + 1
            raw = 0
            for b in self.order:
                if b == hb:
                    break
                raw += sum(x[1] for x in self.blocks[b])
            for x in self.blocks[hb]:
                if x is r:
                    break
                raw += x[1]
            cursor = raw + (o_left - r[0]) + 1
        else:
            cursor = 0
        # one YATA probe (first-probe break on single-author streams):
        # run_at_raw descent+gather + 3 table reads + cursor_after of
        # the probed char's origin_left (its block joins the step set)
        self._st = True
        self._se += 4
        raw_at = 0
        for pb in self.order:
            w = sum(x[1] for x in self.blocks[pb])
            if raw_at + w > cursor:
                self._sb.add(pb)
                break
            raw_at += w
        self.c.blk_traffic += self.nbt + 3 * self.K + 3 * self.ocap \
            + self.nbt
        # splice at raw cursor
        li, before = self._slot_of_raw(cursor) if cursor else (0, 0)
        if self._maybe_split(li):
            li, before = self._slot_of_raw(cursor) if cursor else (0, 0)
        b = self.order[li]
        self._block_cost(b)
        rows = self.blocks[b]
        local = cursor - before
        if cursor == 0:
            rows.insert(0, [st, il, True])
        else:
            at = 0
            for i, r in enumerate(rows):
                if at + r[1] >= local:
                    off = local - at
                    if (r[2] and off == r[1] and st == r[0] + r[1]
                            and o_left == r[0] + r[1] - 1):
                        r[1] += il
                    elif off == r[1]:
                        rows.insert(i + 1, [st, il, True])
                    else:
                        tail = [r[0] + off, r[1] - off, r[2]]
                        rows[i: i + 1] = [[r[0], off, r[2]],
                                          [st, il, True], tail]
                    break
                at += r[1]
        for o in range(st, st + il):
            self.hint[o] = b

    def remote_delete(self, t, d):
        o = t
        end = t + d
        while o < end:
            hb, r = self._locate_order(o)
            li = self.order.index(hb)
            self._st = True
            self.c.blk_traffic += self.nbt
            aa = o - r[0]
            ee = min(r[1], end - r[0])
            cov = ee - aa
            if r[2]:
                if aa == 0 and ee == r[1]:
                    r[2] = False
                    self._sb.add(hb)
                    self.c.blk_traffic += 2 * self.cap + self.K
                else:
                    if self._maybe_split(li):
                        hb, r = self._locate_order(o)
                    rows = self.blocks[hb]
                    i = rows.index(r)
                    parts = []
                    if aa > 0:
                        parts.append([r[0], aa, True])
                    parts.append([r[0] + aa, cov, False])
                    if ee < r[1]:
                        parts.append([r[0] + ee, r[1] - ee, True])
                    rows[i: i + 1] = parts
                    self._block_cost(hb)
            o = r[0] + ee


def config5_workload(docs, chunks, steps_per_chunk, block_k, remote):
    """Replay the bench config-5/5r workload shape through both cost
    models (same generators and growing capacities as bench.py)."""
    from bench import _PeerSynth, _continue_patches
    from text_crdt_rust_tpu.ops import batch as B

    c = Counter()
    rngs = [random.Random((7000 if remote else 1000) + d)
            for d in range(docs)]
    contents = [""] * docs
    synths = [_PeerSynth(f"peer{d}") for d in range(docs)]
    tables = [B.AgentTable([f"peer{d}"]) for d in range(docs)]
    assigners = [None] * docs
    sims = [None] * docs
    caps = []
    cum_steps = 0
    for ci in range(chunks):
        chunk_ops = []
        for d in range(docs):
            patches, contents[d] = _continue_patches(
                rngs[d], contents[d], steps_per_chunk, ins_prob=0.45)
            if remote:
                txns = synths[d].apply(patches)
                ops, assigners[d] = B.compile_remote_txns(
                    txns, tables[d], assigner=assigners[d], lmax=4,
                    dmax=None)
            else:
                start = assigners[d] or 0
                ops, assigners[d] = B.compile_local_patches(
                    patches, lmax=4, dmax=None, start_order=start)
            chunk_ops.append(ops)
        cum_steps += max(o.num_steps for o in chunk_ops)
        cap = max(lane_block_geometry(row_growth_bound(cum_steps),
                                      block_k)[0], 4 * block_k)
        caps.append(cap)
        unb = UnblockedCost(cap)
        for d, ops in enumerate(chunk_ops):
            ocap = 4 * steps_per_chunk * (ci + 1) + 4
            if sims[d] is None:
                sims[d] = BlockedLaneSim(block_k, cap, c, ocap)
            else:
                sims[d].grow(cap, ocap)
            sim = sims[d]
            import numpy as np
            kind = np.asarray(ops.kind)
            pos = np.asarray(ops.pos)
            dln = np.asarray(ops.del_len)
            dtg = np.asarray(ops.del_target)
            olp = np.asarray(ops.origin_left).astype(np.int64)
            iln = np.asarray(ops.ins_len)
            stt = np.asarray(ops.ins_order_start)
            for s in range(ops.num_steps):
                k, p, dl, il = (int(kind[s]), int(pos[s]), int(dln[s]),
                                int(iln[s]))
                st = int(stt[s])
                if k == 0 and dl:
                    c.steps += 1
                    unb.local_delete(c)
                    sim.begin_step()
                    sim.delete_local(p, dl)
                    sim.end_step()
                if k == 0 and il:
                    c.steps += 1
                    unb.local_insert(c)
                    sim.begin_step()
                    sim.insert_local(p, il, st)
                    sim.end_step()
                if k == 1 and il:
                    c.steps += 1
                    unb.remote_insert(c, sim.ocap)
                    ol = None if olp[s] == 0xFFFFFFFF else int(olp[s])
                    sim.begin_step()
                    sim.remote_insert(ol, il, st)
                    sim.end_step()
                if k == 2 and dl:
                    c.steps += 1
                    unb.remote_delete(c)
                    sim.begin_step()
                    sim.remote_delete(int(dtg[s]), dl)
                    sim.end_step()
    return c, caps


def _seed_sim_from_oracle(sim: BlockedLaneSim, oracle) -> None:
    """Re-seed a lane sim from a host oracle the way
    ``serve/lanes_backend.upload_lane`` seeds the device: the SAME
    packer call (``pack_lane_blocks`` owns the occupancy rule), its
    run->block assignment expanded into the sim's block lists and warm
    hints, forward pointers cleared."""
    from text_crdt_rust_tpu.ops.lane_blocks import (
        oracle_runs,
        pack_lane_blocks,
    )

    starts, lens = oracle_runs(oracle)
    nb = sim.cap // sim.K
    _, run_block = pack_lane_blocks(starts, lens, K=sim.K, NB=nb,
                                    NBT=max(8, nb), capacity=sim.cap)
    nblocks = max(int(run_block[-1]) + 1, 1) if len(run_block) else 1
    sim.blocks = [[] for _ in range(nblocks)]
    sim.order = list(range(nblocks))
    sim.hint = {}
    sim.fwd = {}
    for s, ln, b in zip(starts, lens, run_block):
        o0 = int(abs(s)) - 1
        sim.blocks[int(b)].append([o0, int(ln), bool(s > 0)])
        for oo in range(o0, o0 + int(ln)):
            sim.hint[oo] = int(b)


def _replay_stream(sim: BlockedLaneSim, unb: UnblockedCost, c: Counter,
                   ops) -> None:
    """One per-doc compiled tick stream through both cost models (the
    config5_workload inner loop, unbatched [S] columns)."""
    import numpy as np

    kind = np.asarray(ops.kind)
    pos = np.asarray(ops.pos)
    dln = np.asarray(ops.del_len)
    dtg = np.asarray(ops.del_target)
    olp = np.asarray(ops.origin_left).astype(np.int64)
    iln = np.asarray(ops.ins_len)
    stt = np.asarray(ops.ins_order_start)
    wcol = np.maximum(np.asarray(ops.rows_per_step), 1)
    for s in range(ops.num_steps):
        k, p, dl, il = (int(kind[s]), int(pos[s]), int(dln[s]),
                        int(iln[s]))
        st = int(stt[s])
        if k == 0 and dl:
            c.steps += 1
            unb.local_delete(c)
            sim.begin_step(); sim.delete_local(p, dl); sim.end_step()
        if k == 0 and il:
            c.steps += 1
            unb.local_insert(c)
            sim.begin_step()
            sim.insert_local(p, il, st, int(wcol[s]))
            sim.end_step()
        if k == 1 and il:
            c.steps += 1
            unb.remote_insert(c, sim.ocap)
            ol = None if olp[s] == 0xFFFFFFFF else int(olp[s])
            sim.begin_step(); sim.remote_insert(ol, il, st); sim.end_step()
        if k == 2 and dl:
            c.steps += 1
            unb.remote_delete(c)
            sim.begin_step(); sim.remote_delete(int(dtg[s]), dl); sim.end_step()


def serve_workload(smoke: bool = False, block_k: int = 0,
                   engines=("rle-lanes-mixed", "flat")):
    """The ISSUE-4 acceptance + perf probe: run the seeded serve
    loadgen on BOTH lane backends (bit-identity proof), replaying the
    lanes run's tick trace through the kernel-exact blocked cost model
    and the flat engine's whole-[CAP]-plane-per-step model.

    The flat serve engine (`ops/flat.py`) splices the whole [CAP] char
    plane per step exactly like the un-blocked lanes kernels splice
    their [CAP] run plane, so ``UnblockedCost`` doubles as its
    touched-rows model (CAP = the serve lane capacity).  Both models
    assume shallow YATA scans (serve edits are small and conflicts
    rare); splice/locate/split costs are kernel-exact.

    ``block_k`` overrides ``ServeConfig.lanes_block_k`` (the --sweep-k
    driver); ``engines`` narrows the run (the sweep skips the flat twin
    — it is K-independent — and leans on the loadgen's built-in
    always-resident oracle twin for convergence).
    """
    from text_crdt_rust_tpu.config import ServeConfig, lane_block_geometry
    from text_crdt_rust_tpu.serve.loadgen import ServeLoadGen

    docs, ticks, events = (24, 10, 16) if smoke else (200, 60, 48)
    base = ServeConfig()
    K = block_k or base.lanes_block_k
    cap_runs, NB, NBT = lane_block_geometry(base.lane_capacity, K)
    OCAP = base.order_capacity
    c = Counter()
    unb = UnblockedCost(base.lane_capacity)
    sims = {}
    reports = {}
    strings = {}
    shapes = None

    for engine in engines:
        scfg = ServeConfig(engine=engine, num_shards=2,
                           lanes_per_shard=16, lanes_block_k=K)
        gen = ServeLoadGen(docs=docs, agents_per_doc=3, ticks=ticks,
                           events_per_tick=events, zipf_alpha=1.1,
                           fault_rate=0.10, local_prob=0.25, seed=7,
                           cfg=scfg)
        if engine == "rle-lanes-mixed":
            # Tap every compiled per-doc tick stream; re-seed the doc's
            # sim at every residency upload (the device does the same).
            res = gen.server.residency

            def trace(doc_id, ops):
                sim = sims.get(doc_id)
                if sim is None:
                    sim = sims[doc_id] = BlockedLaneSim(
                        K, cap_runs, c, OCAP)
                _replay_stream(sim, unb, c, ops)

            gen.server.batcher.step_trace = trace
            for si, backend in enumerate(res.backends):
                def wrap(orig, si):
                    def upload(b, oracle, ranks):
                        doc_id = res.lane_owner[si][b]
                        sim = sims.get(doc_id)
                        if sim is None:
                            sim = sims[doc_id] = BlockedLaneSim(
                                K, cap_runs, c, OCAP)
                        _seed_sim_from_oracle(sim, oracle)
                        orig(b, oracle, ranks)
                    return upload
                backend.upload_lane = wrap(backend.upload_lane, si)
        t0 = time.perf_counter()
        report = gen.run()
        report["probe_wall_s"] = round(time.perf_counter() - t0, 3)
        assert report["converged"], (engine, report["mismatches"][:4])
        reports[engine] = report
        strings[engine] = {w.doc_id: gen.server.doc_string(w.doc_id)
                           for w in gen.worlds}
        if engine == "rle-lanes-mixed":
            shapes = sorted(set().union(
                *(b.shapes_seen
                  for b in gen.server.residency.backends)))

    bit_identical = (strings["rle-lanes-mixed"] == strings["flat"]
                     if "flat" in strings else None)
    tr = c.unb_touched / max(c.blk_touched, 1)
    pr = c.unb_traffic / max(c.blk_traffic, 1)
    from text_crdt_rust_tpu.utils.metrics import device_identity

    out = {
        "device": device_identity(),
        "workload": {
            "docs": docs, "agents_per_doc": 3, "ticks": ticks,
            "events_per_tick": events, "fault_rate": 0.10,
            "zipf_alpha": 1.1, "seed": 7,
            "num_shards": 2, "lanes_per_shard": 16,
            "lane_capacity": base.lane_capacity,
            "block_k": K, "NB": NB, "NBT": NBT,
            "order_capacity": OCAP,
        },
        "bit_identical_flat_vs_lanes": bit_identical,
        "trace_steps": c.steps,
        "splits": c.splits,
        "hint_misses": c.hint_misses,
        "hint_probes": c.hint_probes,
        "touched_rows_per_step": {
            "flat": round(c.unb_touched / max(c.steps, 1), 1),
            "lanes_blocked": round(c.blk_touched / max(c.steps, 1), 1),
            "ratio": round(tr, 2),
        },
        "pass_traffic_per_step": {
            "flat": round(c.unb_traffic / max(c.steps, 1), 1),
            "lanes_blocked": round(c.blk_traffic / max(c.steps, 1), 1),
            "ratio": round(pr, 2),
        },
        "lanes_shapes_seen": shapes,
        "per_engine": {
            eng: {
                "converged": r["converged"],
                "item_ops_applied": r["item_ops_applied"],
                "device_steps": r["server"].get("device_steps", 0),
                "device_ticks_wall_s": r["device_ticks_wall_s"],
                "tick_ms": r["tick_ms"],
                "latency_us": r["latency_us"],
                "evictions": r["server"].get("evictions", 0),
                "restores": r["server"].get("restores", 0),
                "docs_degraded": r["server"].get("docs_degraded", 0),
                # ISSUE 7: the lanes backend serves the columnar wire +
                # delta checkpoints (ServeConfig defaults) — byte
                # counters prove the evict path writes O(new ops).
                "wire": r.get("wire"),
                "ckpt": r.get("ckpt"),
                "ckpt_delta_bytes_per_evict": r["server"].get(
                    "ckpt_delta_bytes_per_evict_mean", 0.0),
                "ckpt_full_bytes_per_evict": r["server"].get(
                    "ckpt_full_bytes_per_evict_mean", 0.0),
                # ISSUE 8: the obs registry/tracer block rides along so
                # the serve-lanes bench row records the same
                # observability fields as the serve row.
                "obs": r.get("obs"),
                # ISSUE 11: per-op provenance census (spans, audit
                # verdict, op-age percentiles) for the flow_* row
                # fields.
                "flow": r.get("flow"),
                # ISSUE 14: pipeline depth + prefill byte economy ride-
                # alongs (the lanes backend's by-order tables are
                # device-resident already, so its prefill block is the
                # no-surface default; the flat twin reports the cut).
                "pipeline": r.get("pipeline"),
                "prefill": r.get("prefill"),
            }
            for eng, r in reports.items()
        },
        "note": "CPU run: the lanes backend executes the real blocked "
                "kernel via the pallas interpreter (jitted to XLA "
                "CPU), so tick latencies are NOT silicon numbers; "
                "touched-rows/pass-traffic come from the kernel-exact "
                "step-cost replay of the lanes run's tick trace "
                "(shallow-YATA-scan model). Tick latencies need a "
                "chip run.",
    }
    return out


def sweep_k_workload(smoke: bool = False, ks=(8, 16, 32, 64)):
    """Serve-tuned K sweep (ROADMAP item 5 remainder): re-run the
    seeded serve loadgen on the lanes backend at several
    ``lanes_block_k`` values and replay each run's tick trace through
    the kernel-exact cost model.  The flat twin is skipped (its cost is
    K-independent); convergence per run leans on the loadgen's built-in
    always-resident oracle twin.  The chosen default minimizes blocked
    touched rows/step (NBT + K is the per-step floor, so the sweep is
    a real tradeoff: small K inflates the NBT logical table and the
    NB-way select chains, large K inflates every in-block pass), with
    pass traffic as the tiebreak."""
    rows = []
    for k in ks:
        t0 = time.perf_counter()
        out = serve_workload(smoke=smoke, block_k=k,
                             engines=("rle-lanes-mixed",))
        lanes = out["per_engine"]["rle-lanes-mixed"]
        assert lanes["converged"], f"K={k} loadgen diverged"
        rows.append({
            "lanes_block_k": k,
            "NB": out["workload"]["NB"],
            "NBT": out["workload"]["NBT"],
            "trace_steps": out["trace_steps"],
            "splits": out["splits"],
            "hint_misses": out["hint_misses"],
            "touched_rows_per_step":
                out["touched_rows_per_step"]["lanes_blocked"],
            "pass_traffic_per_step":
                out["pass_traffic_per_step"]["lanes_blocked"],
            "vs_flat_touched_ratio":
                out["touched_rows_per_step"]["ratio"],
            "tick_ms": lanes["tick_ms"],
            "wall_s": round(time.perf_counter() - t0, 1),
        })
        print(f"K={k}: touched/step "
              f"{rows[-1]['touched_rows_per_step']}, traffic/step "
              f"{rows[-1]['pass_traffic_per_step']}, splits "
              f"{rows[-1]['splits']} ({rows[-1]['wall_s']}s)",
              file=sys.stderr)
    best = min(rows, key=lambda r: (r["touched_rows_per_step"],
                                    r["pass_traffic_per_step"]))
    return {
        "workload": "serve loadgen tick trace (see serve_workload)",
        "smoke": smoke,
        "sweep": rows,
        "chosen_lanes_block_k": best["lanes_block_k"],
        "note": "CPU sim (kernel-exact step-cost replay; tick_ms is "
                "interpreter wall, not silicon). ServeConfig."
                "lanes_block_k carries the chosen default; re-validate "
                "on the chip.",
    }


def report(name, c: Counter, caps):
    tr = c.unb_touched / max(c.blk_touched, 1)
    pr = c.unb_traffic / max(c.blk_traffic, 1)
    print(f"{name}: caps {caps[0]}..{caps[-1]}, {c.steps} steps, "
          f"{c.splits} splits, hint misses "
          f"{c.hint_misses}/{max(c.hint_probes, 1)}")
    print(f"  touched rows/step: unblocked {c.unb_touched / c.steps:.0f}"
          f" vs blocked {c.blk_touched / c.steps:.0f}  -> "
          f"{tr:.1f}x fewer")
    print(f"  pass traffic/step: unblocked "
          f"{c.unb_traffic / c.steps:.0f} vs blocked "
          f"{c.blk_traffic / c.steps:.0f}  -> {pr:.1f}x less")
    return tr, pr


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs", type=int, default=48,
                    help="lanes to simulate (iid workload; bench runs "
                         "2048 of the same distribution)")
    ap.add_argument("--chunks", type=int, default=8)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--block-k", type=int, default=32)
    ap.add_argument("--serve", action="store_true",
                    help="replay the serve loadgen tick trace instead "
                         "of configs 5/5r (ISSUE 4); writes "
                         "perf/serve_lanes_r7.json")
    ap.add_argument("--sweep-k", action="store_true",
                    help="with --serve: sweep the lanes backend's "
                         "lanes_block_k over --ks and record the "
                         "chosen default (writes perf/serve_k_sweep"
                         ".json unless --smoke)")
    ap.add_argument("--ks", default="8,16,32,64",
                    help="comma-separated K values for --sweep-k")
    ap.add_argument("--smoke", action="store_true",
                    help="with --serve: tiny workload (CI)")
    ap.add_argument("--out", default="perf/serve_lanes_r7.json")
    args = ap.parse_args()
    if args.serve and args.sweep_k:
        import jax

        jax.config.update("jax_platforms", "cpu")
        ks = tuple(int(x) for x in args.ks.split(","))
        out = sweep_k_workload(smoke=args.smoke, ks=ks)
        if not args.smoke:
            path = "perf/serve_k_sweep.json"
            with open(path, "w") as f:
                json.dump(out, f, indent=1)
            print(f"wrote {path}", file=sys.stderr)
        print(json.dumps(out))
        return 0
    if args.serve:
        import jax

        jax.config.update("jax_platforms", "cpu")
        out = serve_workload(smoke=args.smoke)
        if not args.smoke:
            with open(args.out, "w") as f:
                json.dump(out, f, indent=1)
            print(f"wrote {args.out}", file=sys.stderr)
        print(json.dumps(out))
        ratio = out["touched_rows_per_step"]["ratio"]
        ok = out["bit_identical_flat_vs_lanes"] and ratio >= 5
        print(f"acceptance (bit-identical + >=5x touched-rows): "
              f"{'PASS' if ok else 'FAIL'} (ratio {ratio}x)",
              file=sys.stderr)
        return 0 if ok else 1
    c5, caps5 = config5_workload(args.docs, args.chunks, args.steps,
                                 args.block_k, remote=False)
    t5, _ = report("config 5  (local lanes)", c5, caps5)
    c5r, caps5r = config5_workload(args.docs, args.chunks, args.steps,
                                   args.block_k, remote=True)
    t5r, _ = report("config 5r (remote lanes)", c5r, caps5r)
    ok = t5 >= 10 and t5r >= 10
    print(f"acceptance (>=10x touched-rows on both): "
          f"{'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
