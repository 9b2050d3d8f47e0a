"""Step-cost probe for the fused split-batch prepare (ISSUE 5
acceptance): the kevin prepend workload at smoke scale on CPU
interpret, fused vs unfused, on BOTH fused engines.

Proves, per engine:
- device-step count reduced >= 8x at EQUAL workload (the acceptance
  floor; at the bench width W=64 the reduction is 64x),
- fused output bit-identical to the unfused engine AND the analytic
  oracle (``expand_runs`` full order sequence: prepends reverse
  insertion order, so the doc must read orders N-1..0),
- the by-order logs (origins/ranks/chars via ``rle_to_flat``) match
  the unfused stream's exactly — the fused rows bake in origin chains
  the unfused path derives step-by-step.

Writes ``perf/fused_kevin_r8.json`` including the compile-time step
table for the full 5M chip workload.

Run: python perf/fused_kevin_probe.py [--n 4096] [--fuse-w 64]
"""
import argparse
import json
import sys
import time

sys.path.insert(0, ".")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from text_crdt_rust_tpu.ops import batch as B  # noqa: E402
from text_crdt_rust_tpu.ops import rle as R  # noqa: E402
from text_crdt_rust_tpu.ops import rle_hbm as RH  # noqa: E402
from text_crdt_rust_tpu.utils.testdata import TestPatch  # noqa: E402


def probe_engine(name, make, ops_u, ops_f, n, kw):
    want = np.arange(n, 0, -1, dtype=np.int32)
    t0 = time.perf_counter()
    res_u = make(ops_u, **kw)
    wall_u = time.perf_counter() - t0
    t0 = time.perf_counter()
    res_f = make(ops_f, **kw)
    wall_f = time.perf_counter() - t0
    eu, ef = R.expand_runs(res_u), R.expand_runs(res_f)
    assert np.array_equal(eu, ef), f"{name}: fused diverged from unfused"
    assert np.array_equal(ef, want), f"{name}: diverged from the oracle"
    du = R.rle_to_flat(ops_u, res_u)
    df = R.rle_to_flat(ops_f, res_f)
    for fld in ("signed", "ol_log", "or_log", "rank_log", "chars_log",
                "n", "next_order"):
        assert np.array_equal(np.asarray(getattr(du, fld)),
                              np.asarray(getattr(df, fld))), (name, fld)
    return {
        "engine": name,
        "steps_unfused": ops_u.num_steps,
        "steps_fused": ops_f.num_steps,
        "step_reduction_x": round(ops_u.num_steps / ops_f.num_steps, 2),
        "bit_identical_expand_runs": True,
        "bit_identical_order_logs": True,
        "oracle_equal": True,
        "interpret_wall_s":
            {"unfused": round(wall_u, 2), "fused": round(wall_f, 2)},
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--fuse-w", type=int, default=64)
    ap.add_argument("--out", default="perf/fused_kevin_r8.json")
    args = ap.parse_args()
    n, w = args.n, args.fuse_w
    patches = [TestPatch(0, 0, " ")] * n
    ops_u, _ = B.compile_local_patches(patches, lmax=w)
    ops_f, _ = B.compile_local_patches(patches, lmax=w, fuse_w=w)
    block_k = 256
    cap = ((int(n * 2.1) + block_k - 1) // block_k) * block_k
    kw = dict(capacity=cap, batch=8, block_k=block_k, chunk=128,
              interpret=True)
    rows = [
        probe_engine("rle-hbm", RH.replay_local_rle_hbm, ops_u, ops_f,
                     n, kw),
        probe_engine("rle", R.replay_local_rle, ops_u, ops_f, n, kw),
    ]
    full_n = 5_000_000
    out = {
        "workload": {"n": n, "fuse_w": w, "shape":
                     "kevin single-char prepends (benches/yjs.rs:51-62)"},
        "geometry": {k: v for k, v in kw.items() if k != "interpret"},
        "engines": rows,
        "full_scale_step_table": {
            "n": full_n,
            "steps_unfused": full_n,
            "steps_fused_w64": -(-full_n // 64),
            "step_reduction_x": 64.0,
            "note": "compile-time arithmetic for the 5M chip "
                    "workload; its wall needs a chip run",
        },
        "acceptance": {
            "floor_x": 8,
            "measured_x": min(r["step_reduction_x"] for r in rows),
            "pass": all(r["step_reduction_x"] >= 8 for r in rows),
        },
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    print(f"wrote {args.out}; acceptance "
          f"{'PASS' if out['acceptance']['pass'] else 'FAIL'}",
          file=sys.stderr)
    return 0 if out["acceptance"]["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
