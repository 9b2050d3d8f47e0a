"""Bench the sharded SpDoc engine (bench.py --config sp backend).

One committed row for the sequence-parallel engine (VERDICT r5 missing
#5 / next #6): the automerge-paper replay on ``SpDoc`` at virtual sp=8
(CPU mesh — the same mesh shape ``dryrun_multichip`` validates), plus
sp=1 parity against ``ops/rle``'s final state, with an EXPLICIT
collectives-per-op count read off the compiled HLO (the ICI cost model,
stated before real multi-chip exists).

Runs in its own process because the sp mesh needs
``xla_force_host_platform_device_count`` set before the CPU client
exists; bench.py shells out here. CPU-only by construction: it never
touches the chip, so it can run beside a process that holds it.  Prints
one JSON object per row on stdout, each with this process's device
identity.

    python perf/sp_bench.py [--patches 2000] [--smoke] [--skip-parity]
"""
import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from text_crdt_rust_tpu.ops import batch as B  # noqa: E402
from text_crdt_rust_tpu.ops import rle as R  # noqa: E402
from text_crdt_rust_tpu.ops import span_arrays as SA  # noqa: E402
from text_crdt_rust_tpu.parallel import make_mesh  # noqa: E402
from text_crdt_rust_tpu.parallel.sp_apply import SpDoc  # noqa: E402
from text_crdt_rust_tpu.utils.metrics import device_identity  # noqa: E402
from text_crdt_rust_tpu.utils.testdata import (  # noqa: E402
    flatten_patches,
    load_testing_data,
    trace_path,
)

# Collective op spellings across HLO/StableHLO renderings.
_COLLECTIVE_RE = re.compile(
    r"all-gather|all_gather|all-reduce|all_reduce|collective-permute|"
    r"collective_permute|all-to-all|all_to_all", re.IGNORECASE)


def expected_content(patches) -> str:
    s = ""
    for p in patches:
        s = s[:p.pos] + p.ins_content + s[p.pos + p.del_len:]
    return s


def sp_cols(ops):
    """The exact column tuple ``SpDoc.apply_stream`` feeds the jitted
    replay (duplicated here to lower the SAME computation for the
    collective count)."""
    return tuple(
        jnp.asarray(np.asarray(c, dtype=np.uint32).view(np.int32))
        for c in (ops.kind, ops.pos, ops.del_len, ops.del_target,
                  ops.origin_left, ops.origin_right, ops.rank,
                  ops.ins_len, ops.ins_order_start))


def count_collectives(sdoc: SpDoc, ops) -> dict:
    """Static per-step collective count off the compiled HLO: the scan
    body is emitted once, so textual occurrences = collectives per
    device step (every step pays them; XLA does not specialize by op
    kind inside the scan)."""
    lowered = sdoc._replay.lower(
        sdoc.ordp, sdoc.lenp, sdoc.rows, sdoc.oll, sdoc.orl, sdoc.rkl,
        *sp_cols(ops))
    try:
        text = lowered.compile().as_text()
    except Exception:
        text = lowered.as_text()
    hits = _COLLECTIVE_RE.findall(text)
    kinds = {}
    for h in hits:
        k = h.lower().replace("_", "-")
        kinds[k] = kinds.get(k, 0) + 1
    return {"collectives_per_step": len(hits),
            "collectives_by_kind": kinds}


def run_sp(patches, want, nsp, label, count_comms, chunks=4):
    """Chunked streaming apply with ``auto_reshard``: a fresh SpDoc
    holds every live rank in shard 0, so long streams MUST rebalance
    between chunks (the host-side B-tree-rebuild analog) — sizing each
    shard for post-balance occupancy + one chunk's worst-case growth
    (<= 2 rows per compiled step, ``batch.row_growth_bound``)."""
    merged = B.merge_patches(patches)
    lmax = max([len(p.ins_content) for p in merged] + [1])
    ops, _ = B.compile_local_patches(merged, lmax=lmax, dmax=None)
    peak, _ = R.simulate_run_rows(merged)
    s_chunk = -(-ops.num_steps // chunks)
    ops_chunks = [
        B.pad_ops(jax.tree.map(lambda a: np.asarray(a)[i:i + s_chunk], ops),
                  s_chunk)
        for i in range(0, ops.num_steps, s_chunk)
    ]
    mesh = make_mesh(n_devices=nsp, dp=1, sp=nsp)
    shard_rows = ((int(peak * 2.5) // nsp + 2 * s_chunk) // 8 + 2) * 8
    # Local-only streams never read the order tables; keep them small.
    sdoc = SpDoc(mesh, shard_rows=shard_rows, order_rows=64,
                 auto_reshard=True)

    def replay():
        sdoc.load(np.zeros(0, np.int32), np.zeros(0, np.int32))
        for ch in ops_chunks:
            sdoc.apply_stream(ch)

    t0 = time.perf_counter()
    replay()   # includes the one-time compile
    first = time.perf_counter() - t0
    got = sdoc.to_string([ops])
    assert got == want, f"{label}: sp replay diverged from string oracle"
    occupied = [int(r) for r in np.asarray(sdoc.rows)]
    # Timed pass on the warm kernel, from empty state.
    t0 = time.perf_counter()
    replay()
    wall = time.perf_counter() - t0
    assert sdoc.to_string([ops]) == want
    row = {
        "label": label,
        "sp": nsp,
        "ops": len(patches),
        "device_steps": int(ops.num_steps),
        "chunks": len(ops_chunks),
        "wall_s": round(wall, 4),
        "first_run_s_incl_compile": round(first, 4),
        "ops_per_sec": round(len(patches) / wall, 1),
        "shard_rows": shard_rows,
        "peak_run_rows": int(peak),
        "rows_per_shard_final": occupied,
        "hbm_bytes_accounted": int(nsp * (2 * shard_rows + 3 * 64) * 4),
        "oracle_equal": True,
    }
    if count_comms:
        row.update(count_collectives(sdoc, ops_chunks[0]))
        row["collectives_per_op"] = round(
            row["collectives_per_step"] * ops.num_steps / len(patches), 3)
    return row, sdoc, ops


def rle_parity(patches, want, interpret=True):
    """sp=1 vs ops/rle: identical final content from the same merged
    stream (the parity bar; rle runs interpret on CPU, so only content
    is compared — relative throughput needs silicon)."""
    merged = B.merge_patches(patches)
    lmax = max([len(p.ins_content) for p in merged] + [1])
    ops, _ = B.compile_local_patches(merged, lmax=lmax, dmax=None)
    peak, _ = R.simulate_run_rows(merged)
    capacity = ((int(peak * 2.5) + 255) // 256) * 256
    run = R.make_replayer_rle(ops, capacity=max(capacity, 512), batch=8,
                              block_k=64, chunk=128, interpret=interpret)
    res = run()
    res.check()
    got = SA.to_string(R.rle_to_flat(ops, res))
    assert got == want, "ops/rle replay diverged"
    return got


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--patches", type=int, default=2000,
                    help="automerge-paper prefix length")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--skip-parity", action="store_true",
                    help="skip the interpret-mode ops/rle parity pass")
    a = ap.parse_args()

    n = 400 if a.smoke else a.patches
    data = load_testing_data(trace_path("automerge-paper"))
    patches = flatten_patches(data)[:n]
    want = expected_content(patches)

    row8, _, _ = run_sp(patches, want, nsp=8,
                        label="config_sp_automerge_sp8_virtual",
                        count_comms=True)
    row8["note"] = ("virtual 8-device CPU mesh (no ICI): ops/s is a "
                    "host-mesh logic number; collectives_per_step is the "
                    "static ICI cost model")
    row8["device"] = device_identity()
    print(json.dumps(row8), flush=True)

    row1, _, _ = run_sp(patches, want, nsp=1,
                        label="config_sp_parity_sp1", count_comms=False)
    if not a.skip_parity:
        parity_n = min(n, 400)
        parity_patches = patches[:parity_n]
        rle_parity(parity_patches, expected_content(parity_patches))
        row1["rle_parity"] = f"content-equal vs ops/rle on {parity_n} patches"
    row1["device"] = device_identity()
    print(json.dumps(row1), flush=True)


if __name__ == "__main__":
    main()
