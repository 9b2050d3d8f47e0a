"""AOT compiles of the main-path programs for a described v5e chip.

Nothing runs: each program is lowered and compiled for a ``v5e:2x2``
topology that JAX describes without an attached chip, so what the
chip's compiler refuses (unaligned slices, VMEM over the limit, a
program that does not fit HBM or cannot be partitioned) fails here, at
no chip time.  Geometries are the ones the chip path launches
(``perf/compile_pin.py`` stays as the on-chip pin):

- ``ops.rle._build_call`` at the north-star shape (``chip_smoke``'s
  replay phase, ``bench.py`` northstar);
- ``ops.rle_lanes_mixed._build_blocked_call`` at the serve lanes
  geometry (B=128, CAP 512, K 32, OCAP 1536, chunk 128);
- ``ops.flat`` 's tick-train program at one serve train bucket;
- ``parallel.mesh``'s sharded apply over a 4-chip dp mesh at
  ``chip_smoke --chips 4``'s shape.

The topology is described inside a module fixture (never at import):
only the worker that runs this file loads the TPU compiler library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-chip compile can be written to the persistent cache
    # but never read back without a chip: keep the cache off.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _structs(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=sharding), tree)


def _kernel_in(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_northstar_rle_kernel_compiles(one_chip):
    from text_crdt_rust_tpu.ops import rle as R

    G, s_pad, batch, cap, k, chunk, wmax = 1, 32768, 512, 20992, 128, \
        1024, 8
    jitted = R._build_call(G, s_pad, batch, cap, k, chunk, wmax, False)
    arg = jax.ShapeDtypeStruct((G * s_pad,), jnp.int32, sharding=one_chip)
    compiled = jitted.lower(*[arg] * 5).compile()
    assert _kernel_in(compiled)
    out_bytes = compiled.memory_analysis().output_size_in_bytes
    assert out_bytes >= 2 * cap * batch * 4


def test_serve_lanes_kernel_compiles(one_chip):
    from text_crdt_rust_tpu.config import ServeConfig, lane_block_geometry
    from text_crdt_rust_tpu.ops import rle_lanes_mixed as RLM

    d = ServeConfig()
    B, chunk = 128, 128
    cap, _nb, nbt = lane_block_geometry(d.lane_capacity, d.lanes_block_k)
    ocap = d.order_capacity
    jitted = RLM._build_blocked_call(chunk, B, cap, d.lanes_block_k, ocap,
                                     chunk, False)
    i32 = lambda rows: jax.ShapeDtypeStruct((rows, B), jnp.int32,
                                            sharding=one_chip)
    cols = [i32(chunk)] * 10
    state = [i32(cap), i32(cap), i32(1), i32(nbt), i32(nbt), i32(nbt),
             i32(nbt), i32(ocap), i32(ocap), i32(ocap), i32(nbt)]
    deltas = [i32(ocap)] * 3
    compiled = jitted.lower(*cols, *state, *deltas).compile()
    assert _kernel_in(compiled)


def test_serve_flat_train_compiles(one_chip):
    from text_crdt_rust_tpu.config import ServeConfig
    from text_crdt_rust_tpu.ops import batch as B
    from text_crdt_rust_tpu.ops import flat as F
    from text_crdt_rust_tpu.ops import span_arrays as SA
    from text_crdt_rust_tpu.utils.testdata import TestPatch

    d = ServeConfig()
    lanes, T, S = 128, 4, d.step_buckets[1]
    ops, _ = B.compile_local_patches([TestPatch(0, 0, "x")], lmax=d.lmax)
    tick = B.tile_ops(B.pad_ops(ops, S), lanes)
    train = B.stack_ticks([tick] * T)
    docs = jax.eval_shape(lambda: SA.stack_docs(
        SA.make_flat_doc(d.lane_capacity, d.order_capacity), lanes))
    compiled = F._apply_train_batch.lower(
        _structs(docs, one_chip), _structs(train, one_chip)).compile()
    assert compiled.memory_analysis() is not None


def test_mesh_apply_compiles_on_four_chips(topo):
    """``chip_smoke --chips 4``'s program: 64 sveltecomponent docs
    sharded dp=4; every device holds a quarter of the batch."""
    from text_crdt_rust_tpu.ops import batch as B
    from text_crdt_rust_tpu.ops import span_arrays as SA
    from text_crdt_rust_tpu.parallel import make_mesh, make_sharded_apply
    from text_crdt_rust_tpu.parallel.mesh import doc_pspecs, ops_pspecs
    from text_crdt_rust_tpu.utils.testdata import (
        flatten_patches,
        load_testing_data,
        trace_path,
    )

    docs_n = 64
    pts = flatten_patches(load_testing_data(trace_path("sveltecomponent")))
    ops, next_order = B.compile_local_patches(pts, lmax=16)
    cap = -(-sum(len(p.ins_content) for p in pts) // 1024) * 1024
    ocap = -(-next_order // 1024) * 1024
    mesh = make_mesh(devices=topo.devices, dp=4, sp=1)
    docs = jax.eval_shape(lambda: SA.stack_docs(
        SA.make_flat_doc(cap, ocap), docs_n))
    batch_ops = B.tile_ops(ops, docs_n)
    doc_in = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        docs, doc_pspecs())
    ops_in = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        batch_ops, ops_pspecs(batch_ops))
    jitted = make_sharded_apply(mesh, donate=False).jitted
    compiled = jitted.lower(doc_in, ops_in).compile()
    per_device = compiled.memory_analysis().output_size_in_bytes
    total = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(docs))
    # A quarter of the batch per device (plus tile padding of [B] scalars).
    assert total // 4 <= per_device < total // 4 + 64 * 1024
