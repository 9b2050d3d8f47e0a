"""Device-mesh sharding for batched CRDT documents.

TPU-native scale-out (SURVEY §2 parallelism inventory, net-new vs the
reference):

- **dp axis** — independent documents. The reference's analog is "run the
  replay loop once per doc" (`benches/yjs.rs:41-48`); here the doc batch
  axis of ``FlatDoc`` is sharded across chips and every step runs SPMD.
- **sp axis** — the capacity (item) axis of *one* document, the
  long-context / sequence-parallel analog (SURVEY §5 "sharding one huge
  document's span array across chips with carry-propagating scans over
  ICI"). The step kernel is pure ``cumsum`` / ``searchsorted`` / masked
  gathers, so the XLA SPMD partitioner inserts the carry collectives
  itself; we only annotate shardings and let it.

No NCCL/MPI translation: collectives are whatever XLA emits for the
annotated shardings, riding ICI inside a pod and DCN across hosts.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.batch import prefill_logs
from ..ops.flat import _check_capacity, step
from ..ops.span_arrays import FlatDoc


def make_mesh(
    n_devices: Optional[int] = None,
    dp: Optional[int] = None,
    sp: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """A 2-D ``(dp, sp)`` mesh over ``n_devices`` (default: all attached).

    ``dp`` defaults to ``n_devices // sp``. A single-chip mesh (the bench
    machine) is just ``dp=sp=1`` — the same code path compiles unchanged.
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    devices = list(devices)[:n_devices]
    if dp is None:
        assert n_devices % sp == 0, (n_devices, sp)
        dp = n_devices // sp
    assert dp * sp == n_devices, f"dp({dp}) * sp({sp}) != {n_devices}"
    grid = np.asarray(devices).reshape(dp, sp)
    return Mesh(grid, axis_names=("dp", "sp"))


def doc_pspecs(batched: bool = True) -> FlatDoc:
    """PartitionSpecs for every ``FlatDoc`` field.

    Batched docs: columns ``[B, N]`` -> ``P('dp', 'sp')``; per-doc scalars
    ``[B]`` -> ``P('dp')``. Unbatched (one huge doc, pure
    sequence-parallel): columns ``[N]`` -> ``P('sp')``, scalars replicated.
    """
    if batched:
        col, scalar = P("dp", "sp"), P("dp")
    else:
        col, scalar = P("sp"), P()
    return FlatDoc(
        signed=col, ol_log=col, or_log=col, rank_log=col,
        chars_log=col, n=scalar, next_order=scalar,
    )


def ops_pspecs(ops, batched: bool = True):
    """PartitionSpecs for an ``OpTensors`` batch: time axis replicated
    (it is scanned), doc axis sharded over ``dp``, the char chunk axis
    replicated."""
    def spec(a):
        if not batched:
            return P()
        extra = (None,) * (a.ndim - 2)
        return P(None, "dp", *extra)

    return jax.tree.map(spec, ops)


def shard_docs(docs: FlatDoc, mesh: Mesh, batched: bool = True) -> FlatDoc:
    """Place a (batch of) document(s) onto the mesh."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        docs, doc_pspecs(batched),
    )


def shard_ops(ops, mesh: Mesh, batched: bool = True):
    """Place a compiled op stream onto the mesh (doc axis over ``dp``)."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        ops, ops_pspecs(ops, batched),
    )


@functools.lru_cache(maxsize=16)
def make_sharded_apply(mesh: Mesh, donate: bool = True,
                       prefill: bool = True):
    """The full multi-chip apply step, jitted over the mesh.

    lru-cached by ``(mesh, donate, prefill)`` — ``jax.sharding.Mesh``
    hashes by (devices, axis names), so re-building for the same mesh
    returns the SAME jitted closure instead of re-tracing (the
    ``_build_call`` pattern, round-17 allowlist burn-down; the old
    grant claimed Mesh was not lru-hashable, which stopped being true
    several jax versions ago).

    Returns ``apply(docs, ops) -> docs`` where docs are sharded
    ``P('dp','sp')`` and the time-major op stream is scanned with the doc
    axis sharded ``P(None,'dp')``. This is the framework's "training step"
    equivalent: the whole op-apply pipeline (position scan, YATA integrate,
    splice, tombstoning) under one pjit.

    ``prefill`` runs ``batch.prefill_logs`` on the docs before each apply
    (host-side; see ``ops.flat.apply_ops``). The device step only writes
    the origins a *local* insert discovers, so a fresh ``make_flat_doc``
    applied without prefilled logs gives silently wrong results (NUL
    chars, wrong tiebreak ranks). Pass ``prefill=False`` only when the
    docs' logs were already prefilled for this op stream.
    """
    vstep = jax.vmap(step)

    def apply(docs: FlatDoc, ops) -> FlatDoc:
        def body(d, op):
            return vstep(d, op), None

        out, _ = jax.lax.scan(body, docs, ops)
        return out

    in_doc_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), doc_pspecs(batched=True))

    jitted = jax.jit(
        apply,
        in_shardings=(in_doc_shardings, None),
        out_shardings=in_doc_shardings,
        donate_argnums=(0,) if donate else (),
    )

    def checked(docs: FlatDoc, ops) -> FlatDoc:
        _check_capacity(docs, ops)
        if prefill:
            docs = shard_docs(prefill_logs(docs, ops), mesh)
        return jitted(docs, ops)

    checked.jitted = jitted  # the bare program, for AOT compiles
    return checked


@functools.lru_cache(maxsize=16)
def make_sharded_apply_1doc(mesh: Mesh, prefill: bool = True):
    """Sequence-parallel apply for ONE huge document: capacity axis sharded
    ``P('sp')`` across every chip in the mesh (long-context path).

    ``prefill`` as in ``make_sharded_apply`` — required for fresh docs;
    lru-cached per mesh like it too."""
    specs = doc_pspecs(batched=False)
    in_doc_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)

    def apply(doc: FlatDoc, ops) -> FlatDoc:
        def body(d, op):
            return step(d, op), None

        out, _ = jax.lax.scan(body, doc, ops)
        return out

    jitted = jax.jit(
        apply,
        in_shardings=(in_doc_shardings, None),
        out_shardings=in_doc_shardings,
    )

    def checked(doc: FlatDoc, ops) -> FlatDoc:
        _check_capacity(doc, ops)
        if prefill:
            doc = shard_docs(prefill_logs(doc, ops), mesh, batched=False)
        return jitted(doc, ops)

    return checked
