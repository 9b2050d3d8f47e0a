"""Host-side op compiler: edit traces / RemoteTxn streams -> device op tensors.

The reference replays edits through per-op O(log n) B-tree walks
(`benches/yjs.rs:41-48` -> `doc.rs:376-469`). The TPU engines instead consume
*pre-compiled, fixed-shape op tensors*: one row per device step, everything
an op needs resolved to dense integers host-side:

- agent names     -> name *ranks* (the Yjs tiebreak is on agent name,
                     `doc.rs:206-209`, so the device compares ranks);
- remote ids      -> orders (`doc.rs:236-240`, via per-agent seq->order RLE
                     maps, `list/mod.rs:33-43`);
- order allocation (`doc.rs:155-165`) — the compiler threads ``next_order``
  through the stream and bakes each insert run's first order into its step;
- remote delete targets are walked in *seq space* and split at the target
  agent's item_orders run boundaries so each step's target range is
  order-contiguous (the fragmentation loop of `doc.rs:311-334`);
- insert runs longer than the static ``lmax`` are split into chained chunks —
  chunk k's origin_left is the last item of chunk k-1, exactly the implicit
  origin chain a split span keeps (`span.rs:24-28,33-45`).

Time-DAG bookkeeping (frontier advance `doc.rs:34-48`, txn spans, causal
order) stays host-side per SURVEY §7; the compiler only asserts txns arrive
causally ready (see ``parallel.causal`` for the buffering layer).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..common import (
    CLIENT_INVALID,
    ROOT_ORDER,
    RemoteDel,
    RemoteId,
    RemoteIns,
    RemoteTxn,
    txn_len,
)
from ..obs.trace import span
from ..utils.rle import KOrderSpan, Rle
from ..utils.testdata import TestData, TestPatch, flatten_patches

# Op kinds (device-side dispatch in ops.flat / ops.rle_mixed).
KIND_LOCAL = 0        # delete del_len live chars at pos, then insert at pos
KIND_REMOTE_INS = 1   # YATA-integrate an insert run at resolved origins
KIND_REMOTE_DEL = 2   # tombstone an order-contiguous target range


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "kind", "pos", "del_len", "del_target", "origin_left", "origin_right",
        "ins_len", "ins_order_start", "order_advance", "rank",
        "rows_per_step", "chars",
    ],
    meta_fields=[],
)
@dataclasses.dataclass
class OpTensors:
    """One device step per row; all u32. Batched streams stack a trailing
    doc axis *after* the step axis (time-major for ``lax.scan``)."""

    kind: jax.Array             # u32[S, ...]
    pos: jax.Array              # u32[S, ...]   KIND_LOCAL: content position
    del_len: jax.Array          # u32[S, ...]   local del span / remote target len
    del_target: jax.Array       # u32[S, ...]   KIND_REMOTE_DEL: first target order
    origin_left: jax.Array      # u32[S, ...]   KIND_REMOTE_INS
    origin_right: jax.Array     # u32[S, ...]   KIND_REMOTE_INS
    ins_len: jax.Array          # u32[S, ...]
    ins_order_start: jax.Array  # u32[S, ...]   first order of the insert run
    order_advance: jax.Array    # u32[S, ...]   orders consumed by this step
    rank: jax.Array             # u32[S, ...]   author agent's name rank
    rows_per_step: jax.Array    # u32[S, ...]   W: run rows this step splices
    #   (1 = plain op; W > 1 = a FUSED backwards-contiguous insert burst:
    #   W same-length runs spliced in one step, orders DESCENDING in doc
    #   order with stride L = ins_len/W — the split-batch prepare for the
    #   kevin prepend shape. 0 only on no-op padding rows.)
    chars: jax.Array            # u32[S, ..., LMAX]

    @property
    def num_steps(self) -> int:
        return self.kind.shape[0]

    @property
    def lmax(self) -> int:
        return self.chars.shape[-1]


class AgentTable:
    """Agent name <-> dense id + *name rank* table.

    The device tiebreak compares ranks; ranks are the index of each name in
    the sorted name list, so rank order == name order (`doc.rs:206-209`).
    Within ONE compiled stream the table must not change (the steps bake
    ranks in). ACROSS compiled epochs peers may join freely: agent IDS are
    append-only (``OrderAssigner`` state stays valid), and persisted rank
    logs are re-based through ``rank_remap`` at the epoch boundary — the
    mid-stream onboarding the reference punts on (`doc.rs:66-89` creates
    agents on the fly but has no compiled state to re-base).
    """

    def __init__(self, names: Iterable[str] = ()):
        self.names: List[str] = []
        self._ids: Dict[str, int] = {}
        for n in names:
            self.add(n)

    def add(self, name: str) -> int:
        if name == "ROOT":
            return CLIENT_INVALID
        if name not in self._ids:
            self._ids[name] = len(self.names)
            self.names.append(name)
        return self._ids[name]

    def id_of(self, name: str) -> int:
        if name == "ROOT":
            return CLIENT_INVALID
        return self._ids[name]

    def __contains__(self, name: str) -> bool:
        return name in self._ids

    def rank_of_agent(self) -> np.ndarray:
        """rank_of_agent[dense agent id] -> name rank (u32)."""
        order = sorted(range(len(self.names)), key=lambda i: self.names[i])
        ranks = np.zeros(len(self.names), dtype=np.uint32)
        for r, i in enumerate(order):
            ranks[i] = r
        return ranks

    def rank_of(self, name: str) -> int:
        return int(self.rank_of_agent()[self.id_of(name)])


def rank_remap(old_names: Sequence[str], table: AgentTable) -> np.ndarray:
    """old-epoch rank -> new-epoch rank (u32[len(old_names)]).

    When a peer joins between compiled epochs, the sorted-name ranks of
    existing agents shift; device state that PERSISTED ranks (the by-order
    ``rank_log`` a ``FlatDoc`` carries for the Yjs tiebreak) must be
    re-based before applying steps compiled against the new table. Apply
    with ``span_arrays.remap_rank_log``.
    """
    for n in old_names:
        assert n in table._ids, f"agent {n!r} missing from the new table"
    old_sorted = sorted(old_names)
    out = np.zeros(len(old_names), dtype=np.uint32)
    for old_rank, name in enumerate(old_sorted):
        out[old_rank] = table.rank_of(name)
    return out


class OrderAssigner:
    """Host twin of the order-allocation metadata (`doc.rs:155-165`):
    per-agent seq->order RLE maps (`list/mod.rs:33-43`) + the dense
    ``next_order`` counter. Shared by the compiler and the causal layer."""

    def __init__(self, table: AgentTable):
        self.table = table
        self.item_orders: List[Rle[KOrderSpan]] = [
            Rle() for _ in table.names
        ]
        self.next_order = 0

    @classmethod
    def from_oracle(cls, doc, table: "AgentTable") -> "OrderAssigner":
        """Rebuild the compiler's order metadata from a live (or
        checkpoint-restored) oracle document, so compilation can resume
        mid-history — the serve layer's restore path
        (`serve/residency.py`): a doc evicted to a checkpoint loses its
        in-memory assigner, and the restored oracle's per-agent
        ``item_orders`` are exactly the state to resume from.

        ``table`` must list the oracle's agents in dense-id order (the
        checkpoint meta's ``agents`` list) so agent ids align."""
        assert table.names == [cd.name for cd in doc.client_data], (
            "agent table order must match the oracle's dense agent ids")
        out = cls(table)
        for aid, cd in enumerate(doc.client_data):
            io = out._orders_of(aid)
            for e in cd.item_orders:
                io.append(KOrderSpan(e.seq, e.order, e.length))
        out.next_order = doc.get_next_order()
        return out

    def _orders_of(self, agent_id: int) -> Rle:
        while agent_id >= len(self.item_orders):
            self.item_orders.append(Rle())
        return self.item_orders[agent_id]

    def next_seq(self, agent_id: int) -> int:
        io = self._orders_of(agent_id)
        last = io.last()
        return last.seq + last.length if last is not None else 0

    def assign(self, agent_id: int, seq: int, length: int) -> int:
        """Allocate ``length`` dense orders to (agent, seq..) and return the
        first (`doc.rs:155-165`)."""
        first = self.next_order
        self._orders_of(agent_id).append(KOrderSpan(seq, first, length))
        self.next_order += length
        return first

    def seq_to_order(self, agent_id: int, seq: int) -> int:
        found = self._orders_of(agent_id).find(seq)
        assert found is not None, f"unknown seq {seq} for agent {agent_id}"
        entry, off = found
        return entry.order + off

    def resolve(self, rid: RemoteId) -> int:
        if rid.agent == "ROOT":
            return ROOT_ORDER
        return self.seq_to_order(self.table.id_of(rid.agent), rid.seq)

    def target_runs(self, agent_id: int, seq: int,
                    length: int) -> List[Tuple[int, int]]:
        """Split a (agent, seq, len) delete target into order-contiguous
        (first_order, len) runs (the `doc.rs:311-334` fragmentation walk,
        done in seq space like the oracle)."""
        runs: List[Tuple[int, int]] = []
        io = self._orders_of(agent_id)
        remaining = length
        while remaining > 0:
            found = io.find(seq)
            assert found is not None, f"delete target seq {seq} unknown"
            entry, off = found
            take = min(entry.length - off, remaining)
            runs.append((entry.order + off, take))
            seq += take
            remaining -= take
        return runs


class _Rows:
    """Column accumulator for compiled steps."""

    def __init__(self, lmax: int):
        self.lmax = lmax
        self.cols: Dict[str, list] = {
            f.name: [] for f in dataclasses.fields(OpTensors)
        }

    def emit(self, *, kind=0, pos=0, del_len=0, del_target=0,
             origin_left=ROOT_ORDER, origin_right=ROOT_ORDER, ins_len=0,
             ins_order_start=0, order_advance=0, rank=0, rows=1,
             content="") -> None:
        # ``content``: str, or a uint32 codepoint array (``fuse_steps``
        # re-emits rows it already holds as codepoints — the serve tick
        # hot path — without a utf-32 decode/encode round trip).
        assert ins_len <= self.lmax
        assert rows >= 1 and (rows == 1 or ins_len % rows == 0)
        cps = np.zeros(self.lmax, dtype=np.uint32)
        if len(content):
            assert len(content) == ins_len
            if isinstance(content, str):
                cps[:ins_len] = np.frombuffer(
                    content.encode("utf-32-le"), dtype=np.uint32)
            else:
                cps[:ins_len] = content
        c = self.cols
        c["kind"].append(kind); c["pos"].append(pos)
        c["del_len"].append(del_len); c["del_target"].append(del_target)
        c["origin_left"].append(origin_left)
        c["origin_right"].append(origin_right)
        c["ins_len"].append(ins_len)
        c["ins_order_start"].append(ins_order_start)
        c["order_advance"].append(order_advance)
        c["rank"].append(rank)
        c["rows_per_step"].append(rows)
        c["chars"].append(cps)

    def to_tensors(self) -> OpTensors:
        c = self.cols
        return OpTensors(
            **{k: np.asarray(v, dtype=np.uint32) for k, v in c.items()
               if k != "chars"},
            chars=(np.stack(c["chars"]) if c["chars"]
                   else np.zeros((0, self.lmax), dtype=np.uint32)),
        )


def merge_patches(patches: Sequence[TestPatch]) -> List[TestPatch]:
    """RLE-coalesce adjacent same-kind, position-contiguous patches.

    The op-stream analog of the reference's in-tree merge fast paths
    (`mutations.rs:57-109`): a typing run (each insert continuing at
    ``pos + len(prev)``), a forward-delete run (same ``pos``), or a
    backspace run (next delete ending at the previous ``pos``) collapses
    to ONE op. The merged stream is *semantically identical* to the
    per-keystroke stream — same final state, same per-char orders, same
    origins:

    - insert runs: char k of the merged run gets origin_left = char k-1
      and the shared origin_right, exactly the implicit origin chain a
      span keeps (`span.rs:9-18,24-28`); the unmerged stream's per-patch
      head origins resolve to the same values because nothing intervenes
      between the coalesced patches;
    - delete runs: the same char set is tombstoned and the same number
      of orders is consumed (order totals are preserved patch-for-patch),
      so device state and ``next_order`` are bit-identical.  CAVEAT
      (advisor r3): coalescing a BACKSPACE run into one forward delete
      span reverses the delete-order -> target-char attribution relative
      to the unmerged stream (final state, origins and next_order are
      unchanged, but a per-delete-op version log derived from a merged
      stream would attribute delete orders to the wrong chars — emit
      such logs from the unmerged stream, as ``models.sync`` does);
    - mixed (delete+insert) patches and any position discontinuity break
      the run, so no reordering across unrelated edits ever happens.

    automerge-paper: 259,778 patches -> 10,712 merged ops (24.3x fewer
    device steps). Callers report ops/s against the ORIGINAL patch
    count; the merged stream is an execution strategy, not a workload
    reduction (the native baseline replays the unmerged stream).
    """
    with span("compiler.merge"):
        return _merge_runs(patches)


def _merge_runs(patches: Sequence[TestPatch]) -> List[TestPatch]:
    out: List[TestPatch] = []
    for p in patches:
        if out:
            q = out[-1]
            if (q.del_len == 0 and p.del_len == 0 and p.ins_content
                    and q.ins_content
                    and p.pos == q.pos + len(q.ins_content)):
                q.ins_content += p.ins_content
                continue
            if (not q.ins_content and not p.ins_content
                    and q.del_len and p.del_len):
                if p.pos == q.pos:               # forward-delete run
                    q.del_len += p.del_len
                    continue
                if p.pos + p.del_len == q.pos:   # backspace run
                    q.pos = p.pos
                    q.del_len += p.del_len
                    continue
        out.append(TestPatch(p.pos, p.del_len, p.ins_content))
    return out


def fused_width(ops: OpTensors) -> int:
    """Max ``rows_per_step`` of a compiled stream (1 for empty streams).
    Engines without W-row splice support gate on this; the fused
    engines size their shift bound and block headroom from it."""
    r = np.asarray(ops.rows_per_step)
    return max(int(r.max()) if r.size else 1, 1)


def fused_engine_names() -> Tuple[str, ...]:
    """Engines whose insert splice accepts W-row fused steps, from the
    ONE registry (``config.ENGINE_REGISTRY`` ``fused_steps``) — error
    messages and serve gating derive from this instead of hard-coded
    module lists that rot as engines gain the splice."""
    from ..config import ENGINE_REGISTRY

    return tuple(n for n, spec in ENGINE_REGISTRY.items()
                 if spec.get("fused_steps"))


def _require(cond: bool, msg: str) -> None:
    """Config/capacity precheck that must fire even under ``python -O``
    (a violated precondition corrupts device state silently, no crash)."""
    if not cond:
        raise ValueError(msg)


def require_unfused(ops: OpTensors, engine: str) -> None:
    """The ONE reject guard for engines without the W-row splice (every
    engine without a registry ``fused_steps`` flag calls this at build
    time — a fused stream on an unfused engine would silently misapply,
    its row columns read as one wide plain insert)."""
    if fused_width(ops) > 1:
        raise ValueError(
            f"{engine} has no fused multi-row splice; compile with "
            f"fuse_w=1 (fused streams run on the registry fused_steps "
            f"engines: {', '.join(fused_engine_names())})")


def fused_width_checked(streams, block_k: int) -> int:
    """WMAX of a stream set, validated against the fused engines' ONE
    rule: ``WMAX <= K//2 - 1`` — a freshly split block holds up to
    ceil(K/2) rows and must fit W new rows + one split tail, so a
    single amortized-O(1) leaf split always makes room for a fused
    step.  Shared by ops.rle / ops.rle_hbm so the headroom contract
    cannot drift between them."""
    wmax = max(fused_width(st) for st in streams)
    if wmax > 1 and wmax > block_k // 2 - 1:
        raise ValueError(
            f"fused rows_per_step {wmax} exceeds the one-split headroom "
            f"of block_k {block_k} (need WMAX <= K//2 - 1: a freshly "
            f"split block holds up to ceil(K/2) rows and must fit W+1 "
            f"more)")
    return wmax


def _burst_len(patches: Sequence[TestPatch], i: int) -> int:
    """Length of the maximal backwards-contiguous insert burst starting
    at patch ``i``: consecutive insert-only patches at the SAME document
    position with EQUAL insert lengths (the kevin prepend shape — each
    patch's text lands immediately BEFORE the previous patch's, so the
    relative run layout is statically known)."""
    p0 = patches[i]
    if p0.del_len or not p0.ins_content:
        return 1
    L = len(p0.ins_content)
    j = i + 1
    while (j < len(patches) and not patches[j].del_len
           and len(patches[j].ins_content) == L
           and patches[j].pos == p0.pos):
        j += 1
    return j - i


def compile_local_patches(
    patches: Sequence[TestPatch],
    rank: int = 0,
    lmax: int = 16,
    start_order: int = 0,
    dmax: Optional[int] = None,
    fuse_w: int = 1,
    fuse_shapes: str = "burst",
) -> Tuple[OpTensors, int]:
    """Single-author local edit stream -> op tensors.

    Returns ``(ops, next_order)``. Each patch deletes then inserts at
    ``pos`` (`doc.rs:392-464` op order: delete ops take the earlier order
    numbers, then the insert run). ``dmax`` additionally chunks deletes
    (for an engine that bounds per-step delete spans; the flat engine's
    live-rank window op handles any span, so None = unchunked).

    ``fuse_w > 1`` enables SPLIT-BATCH PREPARE: a backwards-contiguous
    insert burst (``_burst_len``) is compiled into fused multi-row
    steps of up to ``fuse_w`` patches each — ONE device step splicing W
    pre-built run rows (descending orders, stride L) instead of W
    steps.  Semantically identical to the unfused stream: orders,
    chars, and origins are unchanged (patch k's origin_left is the
    shared left neighbour, its origin_right is patch k-1's head — the
    successor at its insert time), and the engines' expanded state is
    bit-identical (a burst never exercises the in-kernel append-merge:
    only the burst's FIRST patch could merge, and the second patch's
    splice would split that merged run at the exact same boundary the
    unfused stream does).  Only the fused engines (``ENGINE_REGISTRY``
    entries with ``fused_steps``) accept W > 1 streams.

    ``fuse_shapes="all"`` additionally runs the GENERALIZED step fuser
    (``fuse_steps``: typing runs, delete sweeps, replace pairs, remote
    runs — ISSUE 6) over the compiled rows before returning; "burst"
    keeps today's behavior (the patch-level kevin detector only).
    """
    assert dmax is None or dmax >= 1, f"dmax must be >= 1, got {dmax}"
    assert fuse_w >= 1, f"fuse_w must be >= 1, got {fuse_w}"
    assert fuse_shapes in ("burst", "all"), fuse_shapes
    with span("compiler.compile"):
        ops, next_order = _compile_rows(list(patches), rank, lmax,
                                        start_order, dmax, fuse_w)
    if fuse_shapes == "all":
        ops, _ = fuse_steps(ops, fuse_w=fuse_w, dmax=dmax)
    return ops, next_order


def _compile_rows(patches: List[TestPatch], rank: int, lmax: int,
                  next_order: int, dmax: Optional[int], fuse_w: int
                  ) -> Tuple[OpTensors, int]:
    """The body of ``compile_local_patches``, fusion aside."""
    rows = _Rows(lmax)
    i = 0
    while i < len(patches):
        p = patches[i]
        L = len(p.ins_content)
        w_cap = min(fuse_w, lmax // L) if L else 1
        # Scan for a burst only when one could actually fuse — an
        # unfusable shape (w_cap < 2) must not re-walk the remaining
        # run from every index (quadratic on long uniform streams).
        burst = _burst_len(patches, i) if (fuse_w > 1 and w_cap >= 2) \
            else 1
        if burst >= 2 and w_cap >= 2:
            while burst > 0:
                w = min(w_cap, burst)
                group = patches[i:i + w]
                # Chars are ORDER-major (patch k at [k*L, (k+1)*L)); the
                # device splices the rows in reverse patch order.
                rows.emit(
                    kind=KIND_LOCAL, pos=p.pos, ins_len=w * L,
                    ins_order_start=next_order, order_advance=w * L,
                    rank=rank, rows=w,
                    content="".join(g.ins_content for g in group),
                )
                next_order += w * L
                burst -= w
                i += w
            continue
        i += 1
        ins = p.ins_content
        first_chunk = ins[:lmax]
        dfirst = p.del_len if dmax is None else min(p.del_len, dmax)
        # First step: (a chunk of) the delete + the first insert chunk.
        rows.emit(
            kind=KIND_LOCAL, pos=p.pos, del_len=dfirst,
            ins_len=len(first_chunk),
            ins_order_start=next_order + p.del_len,
            order_advance=dfirst + len(first_chunk),
            rank=rank, content=first_chunk,
        )
        next_order += p.del_len + len(first_chunk)
        # Remaining delete chunks run after the first insert chunk landed
        # at pos, so the chars still to delete now sit after it: target
        # pos + len(first_chunk).
        doff = dfirst
        while doff < p.del_len:
            chunk_len = min(p.del_len - doff, dmax)
            rows.emit(
                kind=KIND_LOCAL, pos=p.pos + len(first_chunk),
                del_len=chunk_len, order_advance=chunk_len, rank=rank,
            )
            doff += chunk_len
        off = len(first_chunk)
        while off < len(ins):
            chunk = ins[off:off + lmax]
            rows.emit(
                kind=KIND_LOCAL, pos=p.pos + off, ins_len=len(chunk),
                ins_order_start=next_order, order_advance=len(chunk),
                rank=rank, content=chunk,
            )
            next_order += len(chunk)
            off += len(chunk)
    return rows.to_tensors(), next_order


def compile_trace(data: TestData, rank: int = 0, lmax: int = 16
                  ) -> Tuple[OpTensors, int]:
    """Whole-trace convenience wrapper (the `benches/yjs.rs:32-49` replay)."""
    return compile_local_patches(flatten_patches(data), rank=rank, lmax=lmax)


# -- generalized step fusion (ISSUE 6) ---------------------------------------

# Fusable shapes, named for the histogram.  Each entry counts ROWS
# ELIMINATED (ops that piggybacked on an earlier step's row).
FUSE_SHAPES = ("typing", "sweep", "replace", "burst",
               "remote_ins_run", "remote_del_run")


@dataclasses.dataclass
class FuseStats:
    """Per-shape accounting of one ``fuse_steps`` pass.

    ``step_map`` maps each INPUT step index to the OUTPUT (fused) step
    that absorbed it — monotone non-decreasing, length ``steps_in`` —
    so a caller that knows which input rows an op compiled into can
    name the fused super-step the op landed in (the obs/flow per-op
    provenance join).  ``None`` until a ``fuse_steps`` pass fills it;
    ``merge`` drops it (per-stream maps don't concatenate)."""

    steps_in: int = 0
    steps_out: int = 0
    fused: Dict[str, int] = dataclasses.field(
        default_factory=lambda: {s: 0 for s in FUSE_SHAPES})
    step_map: Optional[List[int]] = None

    @property
    def rows_saved(self) -> int:
        return self.steps_in - self.steps_out

    @property
    def reduction_x(self) -> float:
        return self.steps_in / self.steps_out if self.steps_out else 1.0

    def to_dict(self) -> Dict[str, object]:
        return {"steps_in": self.steps_in, "steps_out": self.steps_out,
                "rows_saved": self.rows_saved,
                "reduction_x": round(self.reduction_x, 3),
                "fused": dict(self.fused)}

    def merge(self, other: "FuseStats") -> None:
        self.steps_in += other.steps_in
        self.steps_out += other.steps_out
        self.step_map = None  # per-stream; meaningless across merges
        for k, v in other.fused.items():
            self.fused[k] = self.fused.get(k, 0) + v


class _FRow:
    """One mutable step row while the fuser walks the stream."""

    __slots__ = ("kind", "pos", "del_len", "del_target", "origin_left",
                 "origin_right", "ins_len", "st", "order_advance", "rank",
                 "w", "chars")

    def __init__(self, kind, pos, del_len, del_target, origin_left,
                 origin_right, ins_len, st, order_advance, rank, w,
                 chars):
        self.kind = kind; self.pos = pos; self.del_len = del_len
        self.del_target = del_target; self.origin_left = origin_left
        self.origin_right = origin_right; self.ins_len = ins_len
        self.st = st; self.order_advance = order_advance
        self.rank = rank; self.w = w
        self.chars = chars  # logical content only (ins_len entries)

    @property
    def stride(self) -> int:
        return self.ins_len // self.w if self.w else self.ins_len

    def is_noop(self) -> bool:
        return self.del_len == 0 and self.ins_len == 0


def _try_fuse(cur: _FRow, nxt: _FRow, lmax: int, fuse_w: int,
              dmax=None):
    """Try to fold step ``nxt`` into ``cur`` (adjacent in the stream, so
    nothing intervenes).  Returns the shape name on success (``cur``
    mutated), else None.  Every rule preserves the device-visible state
    bit-exactly (final runs/tombstones, by-order origin/rank/char logs,
    ``next_order``); see the per-rule notes — the correctness burden is
    carried by ``tests/test_rle_fused.py``'s fused-vs-unfused fuzz.

    Cross-agent fusion (YATA commutativity of causally-independent ops,
    PAPERS.md Nicolaescu et al.) is admitted exactly where no insert
    attribution is merged: delete sweeps, remote delete runs, and the
    delete half of a replace carry no rank into device state (ranks are
    only logged for inserted chars), so differing authors fuse safely.
    Insert-bearing rules require equal ranks — a merged run's whole
    span logs ONE rank — and an op whose origin lands inside the other
    op's span can never satisfy the chain/contiguity conditions below,
    so it falls back to its own step (the overlap rejection)."""
    if nxt.w != 1 or nxt.is_noop() or cur.is_noop():
        return None
    loc = KIND_LOCAL
    # ``dmax`` mirrors the compile-time per-step delete-span bound: a
    # stream chunked at dmax (e.g. for an engine with a hard per-step
    # target cap) must not have its delete runs re-merged past it.
    del_fits = (dmax is None
                or cur.del_len + nxt.del_len <= dmax)

    # (a→kevin) backwards-contiguous insert burst -> one W-row step:
    # same position, equal lengths L, ascending orders.  In doc order
    # the burst is W runs with DESCENDING orders (each patch lands
    # before its predecessor); origins: shared left, patch k's right =
    # patch k-1's head (the W-row splice contract, PERF.md §11).
    if (fuse_w > 1 and cur.kind == loc and nxt.kind == loc
            and cur.del_len == 0 and nxt.del_len == 0
            and cur.ins_len > 0 and nxt.ins_len > 0
            and nxt.pos == cur.pos and cur.rank == nxt.rank
            and nxt.ins_len == cur.stride
            and nxt.st == cur.st + cur.ins_len
            and cur.w + 1 <= fuse_w
            and cur.ins_len + nxt.ins_len <= lmax):
        cur.w += 1
        cur.ins_len += nxt.ins_len
        cur.order_advance += nxt.order_advance
        cur.chars = np.concatenate([cur.chars, nxt.chars])
        return "burst"

    if cur.w != 1:
        return None

    # (a) forward typing run -> ONE coalesced row: position- and
    # order-contiguous, same author.  Identical to the host coalescer's
    # merge (``merge_patches`` semantics): the combined run keeps every
    # char's order, the implicit origin chain covers the old run heads
    # (head k's left IS its predecessor char), and the shared raw
    # successor is unchanged because nothing intervenes.  ``cur`` may
    # carry a delete (a replace's insert tail extends the same way).
    if (cur.kind == loc and nxt.kind == loc and nxt.del_len == 0
            and cur.ins_len > 0 and nxt.ins_len > 0
            and cur.rank == nxt.rank
            and nxt.pos == cur.pos + cur.ins_len
            and nxt.st == cur.st + cur.ins_len
            and cur.ins_len + nxt.ins_len <= lmax):
        cur.ins_len += nxt.ins_len
        cur.order_advance += nxt.order_advance
        cur.chars = np.concatenate([cur.chars, nxt.chars])
        return "typing"

    # (b) local delete sweep -> one covered-range walk: forward-delete
    # (same position) or backspace (next range ends where this one
    # starts).  Deletes log no rank, so cross-agent sweeps fuse.
    if (cur.kind == loc and nxt.kind == loc and cur.ins_len == 0
            and nxt.ins_len == 0 and cur.del_len > 0 and nxt.del_len > 0
            and del_fits):
        if nxt.pos == cur.pos:                     # forward-delete run
            cur.del_len += nxt.del_len
            cur.order_advance += nxt.order_advance
            return "sweep"
        if nxt.pos + nxt.del_len == cur.pos:       # backspace run
            cur.pos = nxt.pos
            cur.del_len += nxt.del_len
            cur.order_advance += nxt.order_advance
            return "sweep"
        return None

    # (c) replace fusion: a pure delete followed by a pure insert at
    # the SAME position is exactly the delete+insert pair one compiled
    # KIND_LOCAL row already expresses (every engine fires the delete
    # branch, then the insert branch, with the same arguments the two
    # separate steps would use).  The delete's author logs nothing, so
    # the pair fuses across agents too.
    if (cur.kind == loc and nxt.kind == loc and cur.ins_len == 0
            and cur.del_len > 0 and nxt.del_len == 0 and nxt.ins_len > 0
            and nxt.pos == cur.pos):
        cur.ins_len = nxt.ins_len
        cur.st = nxt.st
        cur.rank = nxt.rank
        cur.order_advance += nxt.order_advance
        cur.chars = nxt.chars
        return "replace"

    # (a-remote) remote insert run: the next run's origin_left chains
    # to this run's tail, shares its origin_right, and continues its
    # orders — the continued-typing shape ``compile_remote_txns`` emits
    # for chunked runs, now fused ACROSS txns.  The combined run
    # integrates at the same cursor: any run the unfused tail-scan
    # would meet has an origin_left strictly left of the tail (a char's
    # left origin precedes it; referencing the tail itself would be
    # causally impossible before this step), so the scan breaks
    # immediately and the tail lands flush after the head either way.
    if (cur.kind == KIND_REMOTE_INS and nxt.kind == KIND_REMOTE_INS
            and cur.ins_len > 0 and nxt.ins_len > 0
            and cur.rank == nxt.rank
            and nxt.origin_left == cur.st + cur.ins_len - 1
            and nxt.origin_right == cur.origin_right
            and nxt.st == cur.st + cur.ins_len
            and cur.ins_len + nxt.ins_len <= lmax):
        cur.ins_len += nxt.ins_len
        cur.order_advance += nxt.order_advance
        cur.chars = np.concatenate([cur.chars, nxt.chars])
        return "remote_ins_run"

    # (b-remote) remote delete run: order-contiguous target ranges
    # (forward sweep or backspace sweep in order space) tombstone one
    # union interval; disjoint adjacent ranges applied back-to-back
    # equal the single interval op, including the dead-run idempotency
    # accounting.  Rank-free -> cross-agent.
    if (cur.kind == KIND_REMOTE_DEL and nxt.kind == KIND_REMOTE_DEL
            and cur.del_len > 0 and nxt.del_len > 0
            and del_fits):
        if nxt.del_target == cur.del_target + cur.del_len:
            cur.del_len += nxt.del_len
            cur.order_advance += nxt.order_advance
            return "remote_del_run"
        if nxt.del_target + nxt.del_len == cur.del_target:
            cur.del_target = nxt.del_target
            cur.del_len += nxt.del_len
            cur.order_advance += nxt.order_advance
            return "remote_del_run"
        return None

    return None


def fuse_steps(ops: OpTensors, lmax: Optional[int] = None,
               fuse_w: int = 1, dmax: Optional[int] = None
               ) -> Tuple[OpTensors, FuseStats]:
    """Generalized step fusion: one greedy adjacent pass over a compiled
    stream, folding the fusable shapes (``FUSE_SHAPES``) into multi-op
    device steps.  The kevin detector (`compile_local_patches(fuse_w)`)
    only sees backwards bursts inside ONE patch list; this pass runs on
    any compiled stream — notably the serve batcher's per-doc tick
    streams, where each event compiles separately and the host
    coalescer never gets a look (ROADMAP item 4's "one device step per
    op" tax on typing runs, backspace sweeps, replaces and same-tick
    cross-agent ops).

    ``fuse_w`` > 1 additionally emits W-row backwards-burst steps
    (``rows_per_step`` > 1) and requires an engine with the registry
    ``fused_steps`` splice; the coalescing shapes emit plain W=1 rows
    every engine accepts.  ``lmax`` caps merged insert lengths (default:
    the stream's chars width); ``dmax`` caps merged delete spans — pass
    the bound the stream was compiled with so fusion never re-merges
    delete runs past an engine's per-step target cap.  Returns
    ``(fused_ops, FuseStats)``;
    orders, origins, ranks and chars are preserved column-for-column, so
    the fused stream is bit-identical in device state to the unfused
    one (the ``tests/test_rle_fused.py`` contract)."""
    with span("compiler.fuse"):
        return _fuse_rows(ops, lmax, fuse_w, dmax)


def _fuse_rows(ops: OpTensors, lmax: Optional[int], fuse_w: int,
               dmax: Optional[int]) -> Tuple[OpTensors, FuseStats]:
    kinds = np.asarray(ops.kind)
    assert kinds.ndim == 1, (
        "fuse_steps takes one unbatched [S] stream; fuse per-doc "
        "streams BEFORE stack_ops")
    assert fuse_w >= 1
    lmax = ops.lmax if lmax is None else min(lmax, ops.lmax)
    stats = FuseStats(steps_in=int(kinds.shape[0]))
    if kinds.shape[0] == 0:
        return ops, stats

    cols = {f: np.asarray(getattr(ops, f if f != "st" else
                                  "ins_order_start"))
            for f in ("kind", "pos", "del_len", "del_target",
                      "origin_left", "origin_right", "ins_len", "st",
                      "order_advance", "rank")}
    w_col = np.asarray(ops.rows_per_step)
    chars = np.asarray(ops.chars)

    def row(i) -> _FRow:
        il = int(cols["ins_len"][i])
        return _FRow(*(int(cols[f][i]) for f in
                       ("kind", "pos", "del_len", "del_target",
                        "origin_left", "origin_right", "ins_len", "st",
                        "order_advance", "rank")),
                     max(int(w_col[i]), 1), chars[i, :il].copy())

    out = _Rows(ops.lmax)

    def emit(r: _FRow) -> None:
        content = r.chars if r.ins_len else ""
        out.emit(kind=r.kind, pos=r.pos, del_len=r.del_len,
                 del_target=r.del_target, origin_left=r.origin_left,
                 origin_right=r.origin_right, ins_len=r.ins_len,
                 ins_order_start=r.st, order_advance=r.order_advance,
                 rank=r.rank, rows=r.w, content=content)

    step_map = [0] * stats.steps_in
    cur_inputs = [0]
    emitted_n = 0
    cur = row(0)
    for i in range(1, stats.steps_in):
        nxt = row(i)
        shape = _try_fuse(cur, nxt, lmax, fuse_w, dmax)
        if shape is None:
            for j in cur_inputs:
                step_map[j] = emitted_n
            emitted_n += 1
            emit(cur)
            cur = nxt
            cur_inputs = [i]
        else:
            stats.fused[shape] += 1
            cur_inputs.append(i)
    for j in cur_inputs:
        step_map[j] = emitted_n
    emit(cur)
    stats.step_map = step_map
    fused = out.to_tensors()
    stats.steps_out = fused.num_steps
    assert (int(np.asarray(fused.order_advance, dtype=np.int64).sum())
            == int(np.asarray(ops.order_advance, dtype=np.int64).sum())), \
        "fusion changed the stream's order consumption"
    return fused, stats


def merge_fused_origins(ol_log, or_log, ops: OpTensors,
                        ol_np, or_np) -> None:
    """Merge a replay's per-step kernel origins into the by-order logs
    in place, expanding fused W-row steps (shared by ``rle.rle_to_flat``
    and ``rle_lanes.lanes_to_flat`` so the chain convention lives
    ONCE): a fused step's kernel origins are patch 0's — left is
    SHARED by every sub-run head (orders st + k*L), and rights chain
    statically (patch k's raw successor at insert time is patch k-1's
    head, order st + (k-1)*L)."""
    starts = np.asarray(ops.ins_order_start, dtype=np.int64)
    ilens = np.asarray(ops.ins_len, dtype=np.int64)
    ws = np.maximum(np.asarray(ops.rows_per_step, dtype=np.int64), 1)
    for st, il, w, left, right in zip(starts, ilens, ws, ol_np, or_np):
        if il > 0:
            L = il // w
            for k in range(w):
                ol_log[st + k * L] = left
                or_log[st + k * L: st + (k + 1) * L] = (
                    right if k == 0 else st + (k - 1) * L)


def compile_remote_txns(
    txns: Sequence[RemoteTxn],
    table: AgentTable,
    assigner: Optional[OrderAssigner] = None,
    lmax: int = 16,
    dmax: Optional[int] = None,
) -> Tuple[OpTensors, OrderAssigner]:
    """Causally-ordered RemoteTxn stream -> op tensors (`doc.rs:242-348`).

    The ``assigner`` carries the peer-local order metadata between calls
    (streaming apply); txns must arrive causally ready — buffering
    out-of-order arrivals is ``parallel.causal``'s job. ``dmax`` chunks
    remote delete target runs (for an engine that bounds per-step
    targets; the flat engine masks whole order ranges, so None there).
    """
    assert dmax is None or dmax >= 1, f"dmax must be >= 1, got {dmax}"
    if assigner is None:
        assigner = OrderAssigner(table)
    ranks = table.rank_of_agent()
    rows = _Rows(lmax)
    for txn in txns:
        agent = table.id_of(txn.id.agent)
        assert assigner.next_seq(agent) == txn.id.seq, (
            f"remote txn out of order: expected seq "
            f"{assigner.next_seq(agent)}, got {txn.id.seq} "
            f"(buffer with parallel.causal.CausalBuffer)"
        )
        length = txn_len(txn)
        assert length > 0, "empty remote txn"
        # Orders for the whole txn are allocated up front (`doc.rs:265-269`)
        # so intra-txn origin references resolve.
        cursor = assigner.assign(agent, txn.id.seq, length)
        for op in txn.ops:
            if isinstance(op, RemoteIns):
                ins = op.ins_content
                if not ins:
                    continue
                origin_left = assigner.resolve(op.origin_left)
                origin_right = assigner.resolve(op.origin_right)
                off = 0
                while off < len(ins):
                    chunk = ins[off:off + lmax]
                    rows.emit(
                        kind=KIND_REMOTE_INS,
                        origin_left=origin_left,
                        origin_right=origin_right,
                        ins_len=len(chunk), ins_order_start=cursor,
                        order_advance=len(chunk),
                        rank=int(ranks[agent]), content=chunk,
                    )
                    origin_left = cursor + len(chunk) - 1
                    cursor += len(chunk)
                    off += len(chunk)
            else:
                assert isinstance(op, RemoteDel)
                target_agent = table.id_of(op.id.agent)
                for first, run_len in assigner.target_runs(
                        target_agent, op.id.seq, op.len):
                    off = 0
                    while off < run_len:
                        take = (run_len - off if dmax is None
                                else min(run_len - off, dmax))
                        rows.emit(
                            kind=KIND_REMOTE_DEL, del_target=first + off,
                            del_len=take, order_advance=take,
                            rank=int(ranks[agent]),
                        )
                        off += take
                    cursor += run_len
    return rows.to_tensors(), assigner


# -- log prefill -------------------------------------------------------------


def _prefill_scatter(ops: OpTensors):
    """The compile-time-known log writes of one unbatched op stream, as
    (positions, values) pairs. See ``prefill_logs``."""
    ins_len = np.asarray(ops.ins_len, dtype=np.int64)
    starts = np.asarray(ops.ins_order_start, dtype=np.int64)
    kinds = np.asarray(ops.kind)
    op_chars = np.asarray(ops.chars)
    ranks = np.asarray(ops.rank)
    ol_ops = np.asarray(ops.origin_left)
    or_ops = np.asarray(ops.origin_right)
    wsteps = np.maximum(np.asarray(ops.rows_per_step, dtype=np.int64), 1)

    sel = ins_len > 0
    if not sel.any():
        return None
    reps = ins_len[sel]
    total = int(reps.sum())
    step_idx = np.repeat(np.nonzero(sel)[0], reps)
    within = np.arange(total) - np.repeat(
        np.cumsum(reps) - reps, reps)
    pos = starts[sel].repeat(reps) + within

    # Within-run implicit origin chain (`span.rs:9-13,24-28`): item k's
    # origin_left is order+k-1. The run head's origins are known at compile
    # time only for remote inserts; local heads are written on device.
    # A FUSED step carries rows_per_step sub-runs of stride L = il/W —
    # the chain breaks at every sub-run head (their origins come from the
    # device/host fused-origin merge, `rle.rle_to_flat`).
    stride = np.repeat(ins_len[sel] // wsteps[sel], reps)
    chain = (within % stride) != 0
    remote = kinds[step_idx] == KIND_REMOTE_INS
    head = ~chain & remote
    return {
        "chars": (pos, op_chars[step_idx, within]),
        "rank": (pos, ranks[step_idx]),
        "ol": (np.concatenate([pos[chain], pos[head]]),
               np.concatenate([(pos[chain] - 1).astype(np.uint32),
                               ol_ops[step_idx[head]]])),
        "or": (pos[remote], or_ops[step_idx[remote]]),
    }


def _apply_scatter(ol, orr, rank, chars, sc) -> None:
    """Apply a scatter to 1-D ``[OCAP]`` or 2-D ``[B, OCAP]`` logs (the
    trailing-axis fancy index broadcasts over the doc axis)."""
    if sc is None:
        return
    chars[..., sc["chars"][0]] = sc["chars"][1]
    rank[..., sc["rank"][0]] = sc["rank"][1]
    ol[..., sc["ol"][0]] = sc["ol"][1]
    orr[..., sc["or"][0]] = sc["or"][1]


def prefill_logs(doc, ops: OpTensors):
    """Fill a ``FlatDoc``'s by-order logs with everything the compiler
    already knows about ``ops``: chars, author ranks, remote origins, and
    every insert run's implicit origin chain. The device then only writes
    the two origins a *local* insert discovers at apply time
    (`doc.rs:447-453`).

    ``ops`` may be unbatched ``[S, ...]`` (doc unbatched, or one stream
    shared by every doc of a batched doc) or batched ``[S, B, ...]`` (doc
    batched ``[B, ...]``). Tiled batches (every doc's column identical,
    the ``tile_ops`` output) are detected and prefilled with one scatter
    broadcast across the doc axis. Returns a new doc; host-side numpy.
    """
    import jax.numpy as jnp

    ops_batched = np.asarray(ops.kind).ndim == 2
    ol = np.array(doc.ol_log)
    orr = np.array(doc.or_log)
    rank = np.array(doc.rank_log)
    chars = np.array(doc.chars_log)
    if ol.ndim == 1:
        assert not ops_batched, "batched ops need a batched doc"
        _apply_scatter(ol, orr, rank, chars, _prefill_scatter(ops))
    elif not ops_batched:
        _apply_scatter(ol, orr, rank, chars, _prefill_scatter(ops))
    else:
        def tiled(a):
            a = np.asarray(a)
            return bool((a == a[:, :1] if a.ndim == 2
                         else a == a[:, :1, ...]).all())

        if all(tiled(np.asarray(c)) for c in
               (ops.kind, ops.ins_len, ops.ins_order_start, ops.rank,
                ops.origin_left, ops.origin_right, ops.chars)):
            one = jax.tree.map(lambda a: np.asarray(a)[:, 0], ops)
            _apply_scatter(ol, orr, rank, chars, _prefill_scatter(one))
        else:
            for b in range(ol.shape[0]):
                per_doc = jax.tree.map(lambda a: np.asarray(a)[:, b], ops)
                _apply_scatter(ol[b], orr[b], rank[b], chars[b],
                               _prefill_scatter(per_doc))
    return dataclasses.replace(
        doc, ol_log=jnp.asarray(ol), or_log=jnp.asarray(orr),
        rank_log=jnp.asarray(rank), chars_log=jnp.asarray(chars))


# -- device-resident prefill (ISSUE 14) --------------------------------------
# The serve tick used to round-trip the four FULL [B, OCAP] by-order
# logs through host numpy every tick (``prefill_logs`` materializes,
# scatters a few hundred compile-time-known values, re-uploads) — an
# O(state) host cost on an O(ops) edit, and a hidden device sync on the
# previous tick's output that eats the pipelined overlap under real
# async dispatch.  ``prefill_delta`` ships ONLY the scatter — fixed-
# shape padded (positions, values) tensors — and ``ops.flat.
# apply_prefill_delta`` applies it on device (``.at[pos].set(...,
# mode="drop")``), so the logs stay device-resident for the life of a
# lane.  Scatter lengths are padded to a small geometric bucket set
# (``scatter_bucket``) so steady-state serving compiles one scatter
# program per bucket, exactly the step-bucket discipline of the tick.

#: Padding position for scatter tensors: positive, out of range for any
#: real order capacity (< 2^31), so ``mode="drop"`` discards it.  The
#: value columns pad with 0 (never read — the position is dropped).
PREFILL_PAD = np.uint32(0x7FFFFFFF)

#: Smallest scatter bucket; buckets grow geometrically (x4) from here,
#: so a serve shape sees at most ~4-5 distinct scatter programs no
#: matter how ragged the per-tick insert volume is.
PREFILL_BUCKET_BASE = 32


def scatter_bucket(n: int) -> int:
    """Smallest bucket (PREFILL_BUCKET_BASE * 4^k) holding ``n`` scatter
    entries — the fixed-shape pad target that keeps the jitted device
    scatter's compile cache bounded (geometric growth: any workload sees
    O(log n) distinct shapes, and a serve tick's scatter is capped at
    S_bucket * lmax entries anyway)."""
    b = PREFILL_BUCKET_BASE
    while b < n:
        b *= 4
    return b


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["ins_pos", "chars_val", "rank_val", "ol_pos", "ol_val",
                 "or_pos", "or_val"],
    meta_fields=["bucket"],
)
@dataclasses.dataclass
class PrefillDelta:
    """The compile-time-known by-order log writes of an op stream as
    fixed-shape padded scatter tensors (``[L]`` for one stream,
    ``[B, L]`` for a stacked batch; ``L = bucket``).

    ``chars_log`` and ``rank_log`` share one position column (every
    inserted char gets both); ``ol``/``or`` carry their own (the chain
    subset / the remote subset).  Padding rows hold ``PREFILL_PAD``
    positions, dropped by the device scatter's ``mode="drop"``.
    ``bucket`` is static metadata (part of the jit cache key) — it is
    drawn from ``scatter_bucket``'s geometric series, so the compiled
    scatter set stays bounded."""

    ins_pos: jax.Array    # u32[..., L] chars/rank write positions
    chars_val: jax.Array  # u32[..., L]
    rank_val: jax.Array   # u32[..., L]
    ol_pos: jax.Array     # u32[..., L] origin_left writes (chain + heads)
    ol_val: jax.Array     # u32[..., L]
    or_pos: jax.Array     # u32[..., L] origin_right writes (remote runs)
    or_val: jax.Array     # u32[..., L]
    bucket: int

    def nbytes(self) -> int:
        """Bytes this delta moves host->device (the whole cost of a
        device-resident prefill; compare 2 * 4 * OCAP * B * 4 for the
        full-log round trip)."""
        return sum(np.asarray(getattr(self, f)).nbytes for f in
                   ("ins_pos", "chars_val", "rank_val", "ol_pos",
                    "ol_val", "or_pos", "or_val"))


def _delta_rows(sc, L: int):
    """One lane's scatter dict -> seven padded length-L u32 rows."""
    ins_pos = np.full(L, PREFILL_PAD, np.uint32)
    chars_val = np.zeros(L, np.uint32)
    rank_val = np.zeros(L, np.uint32)
    ol_pos = np.full(L, PREFILL_PAD, np.uint32)
    ol_val = np.zeros(L, np.uint32)
    or_pos = np.full(L, PREFILL_PAD, np.uint32)
    or_val = np.zeros(L, np.uint32)
    if sc is not None:
        p, v = sc["chars"]
        ins_pos[:len(p)] = p
        chars_val[:len(p)] = v
        rank_val[:len(p)] = sc["rank"][1]
        p, v = sc["ol"]
        ol_pos[:len(p)] = p
        ol_val[:len(p)] = v
        p, v = sc["or"]
        or_pos[:len(p)] = p
        or_val[:len(p)] = v
    return ins_pos, chars_val, rank_val, ol_pos, ol_val, or_pos, or_val


def prefill_delta(ops: OpTensors) -> Optional[PrefillDelta]:
    """``_prefill_scatter`` as fixed-shape padded device tensors: the
    delta-prefill twin of ``prefill_logs`` (ISSUE 14).  ``ops`` may be
    unbatched ``[S, ...]`` or batched ``[S, B, ...]`` (one scatter row
    per lane).  Returns ``None`` when the stream inserts nothing (a
    pure-delete or all-padding tick writes no log values, and skipping
    the scatter call entirely keeps the compile set minimal) — callers
    skip the device scatter in that case.

    Correctness contract (pinned by ``tests/test_device_prefill.py``):
    applying the delta on device (``ops.flat.apply_prefill_delta``) is
    bit-identical to ``prefill_logs`` on every log, for local, remote,
    mixed, fused (``rows_per_step`` > 1) and tiled streams — both paths
    are projections of the SAME ``_prefill_scatter``."""
    batched = np.asarray(ops.kind).ndim == 2
    if not batched:
        scs = [_prefill_scatter(ops)]
    else:
        host = jax.tree.map(np.asarray, ops)
        scs = [_prefill_scatter(jax.tree.map(lambda a: a[:, b], host))
               for b in range(np.asarray(ops.kind).shape[1])]
    if all(sc is None for sc in scs):
        return None
    need = max(len(sc["chars"][0]) for sc in scs if sc is not None)
    L = scatter_bucket(need)
    cols = [np.stack(rows) if batched else rows[0]
            for rows in zip(*(_delta_rows(sc, L) for sc in scs))]
    return PrefillDelta(*cols, bucket=L)


def concat_deltas(deltas) -> Optional[PrefillDelta]:
    """Concatenate T per-tick ``PrefillDelta``s (same batch shape) into
    one padded delta for a tick train (ISSUE 20).  Per-tick scatter
    positions land in disjoint fresh order ranges (orders are allocated
    uniquely and monotonically per lane), so applying the concatenation
    once before the train scan is bit-identical to applying each delta
    before its tick.  ``None`` entries (no-insert ticks) contribute
    nothing; returns ``None`` when every tick was insert-free.  The
    result is re-padded to the ``scatter_bucket`` series, so the train
    path draws from the SAME compiled scatter set as the serial path."""
    live = [d for d in deltas if d is not None]
    if not live:
        return None
    if len(live) == 1:
        return live[0]
    total = sum(d.bucket for d in live)
    L = scatter_bucket(total)
    fields = ("ins_pos", "chars_val", "rank_val", "ol_pos", "ol_val",
              "or_pos", "or_val")
    pads = {"ins_pos": PREFILL_PAD, "ol_pos": PREFILL_PAD,
            "or_pos": PREFILL_PAD}
    cols = []
    for f in fields:
        col = np.concatenate(
            [np.asarray(getattr(d, f)) for d in live], axis=-1)
        if col.shape[-1] < L:
            width = [(0, 0)] * (col.ndim - 1) + [(0, L - col.shape[-1])]
            col = np.pad(col, width,
                         constant_values=pads.get(f, np.uint32(0)))
        cols.append(col)
    return PrefillDelta(*cols, bucket=L)


def row_growth_bound(num_steps: int) -> int:
    """Sound per-lane run-row bound after ``num_steps`` compiled device
    steps: every step splices at most 2 new rows (insert splice / delete
    boundary splits / remote-delete endpoint retires), so a stream of S
    steps can never need more than ``1 + 2*S`` rows.  The growing
    per-chunk capacities of the streaming configs (and the blocked-lanes
    NB-per-chunk sizing) derive from this exact invariant — no sampling
    (PERF.md §7.2/§9)."""
    return 1 + 2 * num_steps


def row_growth_bound_ops(ops: OpTensors) -> int:
    """Fused-aware sound row bound for ONE compiled stream: a plain step
    splices at most 2 new rows (see ``row_growth_bound``); a fused
    W-row step splices at most W + 1 (W new runs + one split tail).
    Equals ``row_growth_bound(num_steps)`` on unfused streams."""
    w = np.maximum(
        np.asarray(ops.rows_per_step, dtype=np.int64).reshape(-1), 1)
    return 1 + int(np.maximum(2, w + 1).sum())


# -- batching ----------------------------------------------------------------


def pad_ops(ops: OpTensors, num_steps: int) -> OpTensors:
    """Pad a step stream with no-ops (KIND_LOCAL, all-zero lengths is an
    exact no-op in both engines)."""
    s = ops.num_steps
    assert s <= num_steps
    if s == num_steps:
        return ops

    def pad(a):
        width = [(0, num_steps - s)] + [(0, 0)] * (a.ndim - 1)
        return np.pad(np.asarray(a), width)

    return jax.tree.map(pad, ops)


def empty_ops(lmax: int) -> OpTensors:
    """A zero-step stream (idle lanes in a serve batch tick)."""
    return _Rows(lmax).to_tensors()


def concat_ops(streams: Sequence[OpTensors]) -> OpTensors:
    """Concatenate step streams along the step axis (equal lmax).

    The serve batcher compiles one stream per drained event and fuses
    them into the doc's tick stream; orders were threaded through one
    assigner, so plain concatenation preserves the compiled invariants.
    """
    streams = [s for s in streams if s.num_steps > 0]
    if not streams:
        return empty_ops(1)
    lmax = streams[0].lmax
    assert all(s.lmax == lmax for s in streams), "mixed lmax streams"
    if len(streams) == 1:
        return streams[0]
    return jax.tree.map(
        lambda *xs: np.concatenate([np.asarray(x) for x in xs], axis=0),
        *streams)


def stack_ops(streams: Sequence[OpTensors],
              chars: bool = True) -> OpTensors:
    """Ragged per-doc streams -> one time-major [S, B, ...] tensor batch
    (config 3's mixed-corpus batch; shorter docs run no-op tail steps).

    ``chars=False`` stacks the step columns alone, with a zero-width
    ``chars``: the run engines (``ops.rle_lanes``) read lengths and
    orders, never codepoints, and a fleet's stacked codepoints would
    take ``S * B * lmax * 4`` bytes (43 GB at 10,240 steps, 128 lanes
    and lmax 8,192).  Streams of different ``lmax`` stack then too."""
    if not chars:
        streams = [dataclasses.replace(
            o, chars=np.zeros((o.num_steps, 0), np.uint32))
            for o in streams]
    s_max = max(o.num_steps for o in streams)
    padded = [pad_ops(o, s_max) for o in streams]
    return jax.tree.map(lambda *xs: np.stack(xs, axis=1), *padded)


def stack_ticks(ticks: Sequence[OpTensors]) -> OpTensors:
    """T equal-shape stacked tick streams ([S, B, ...] each) -> one
    train-major [T, S, B, ...] tensor batch for ``ops.flat.apply_train``
    (ISSUE 20).  The caller re-pads every tick to a common step bucket
    first (``pad_ops``) and pads short trains with all-zero no-op ticks
    — a zero ``OpTensors`` row is an exact no-op in the device step, so
    no-op ticks are exact no-op ticks."""
    return jax.tree.map(
        lambda *xs: np.stack([np.asarray(x) for x in xs], axis=0),
        *ticks)


def tile_ops(ops: OpTensors, batch: int) -> OpTensors:
    """One stream -> B identical docs (config 2: `random_edits` x 1k docs)."""
    return jax.tree.map(
        lambda a: np.broadcast_to(
            np.asarray(a)[:, None, ...], (a.shape[0], batch) + a.shape[1:]
        ),
        ops,
    )
