"""Step-cost probe for GENERALIZED fused multi-row steps (ISSUE 6
acceptance): the real editing traces (automerge-paper + the northstar
code traces rustcode/sveltecomponent) compiled at EVENT granularity —
the serve shape, one compiled step per patch, where the host coalescer
never runs — then fused by ``ops.batch.fuse_steps``.

Proves, per trace:
- device-step count reduced >= 3x (the acceptance floor) by the fusion
  pass alone, with the per-shape histogram (typing runs / delete sweeps
  / replace pairs / backwards bursts) recorded;
- on a trace PREFIX at CPU-interpret scale, the fused stream is
  bit-identical to the unfused stream AND the flat-engine oracle on
  all four fused-splice surfaces: ``ops.rle`` / ``ops.rle_hbm``
  (expand_runs + the full by-order logs via ``rle_to_flat``) and the
  BLOCKED lanes engines ``ops.rle_lanes`` / ``ops.rle_lanes_mixed``
  (per-lane expansion + the in-kernel by-order origin tables).

Writes ``perf/fused_traces_r9.json``; the fused bench rows' walls need
a chip run.

Run: python perf/fused_trace_probe.py [--identity-patches 1200]
     [--fuse-w 8] [--smoke]
"""
import argparse
import json
import sys
import time

sys.path.insert(0, ".")

import jax  # noqa: E402

try:
    jax.config.update("jax_platforms", "cpu")
except RuntimeError:
    pass  # in-process import after backend init (the tier-1 smoke):
    #       the caller already pinned the platform

import numpy as np  # noqa: E402

from text_crdt_rust_tpu.ops import batch as B  # noqa: E402
from text_crdt_rust_tpu.ops import flat as F  # noqa: E402
from text_crdt_rust_tpu.ops import rle as R  # noqa: E402
from text_crdt_rust_tpu.ops import rle_hbm as RH  # noqa: E402
from text_crdt_rust_tpu.ops import rle_lanes as RL  # noqa: E402
from text_crdt_rust_tpu.ops import rle_lanes_mixed as RLM  # noqa: E402
from text_crdt_rust_tpu.ops import span_arrays as SA  # noqa: E402
from text_crdt_rust_tpu.utils.testdata import (  # noqa: E402
    flatten_patches,
    load_testing_data,
    trace_path,
)

TRACES = ("automerge-paper", "rustcode", "sveltecomponent")
LMAX = 256          # merged-run cap (bench lmax_cap scale; typing runs
#                     in the code traces coalesce past 64 chars)
FLOOR_X = 3.0


def full_trace_cut(name: str, fuse_w: int):
    """Event-granularity compile of the WHOLE trace + one fusion pass
    (host arithmetic — the exact device-step counts, no replay)."""
    patches = flatten_patches(load_testing_data(trace_path(name)))
    t0 = time.perf_counter()
    ops_u, _ = B.compile_local_patches(patches, lmax=LMAX, dmax=None)
    ops_f, st = B.fuse_steps(ops_u, fuse_w=fuse_w)
    assert B.fused_width(ops_f) <= fuse_w
    return {
        "trace": name,
        "patches": len(patches),
        "steps_unfused": st.steps_in,
        "steps_fused": st.steps_out,
        "step_reduction_x": round(st.reduction_x, 2),
        "fuse_shapes": dict(st.fused),
        "compile_wall_s": round(time.perf_counter() - t0, 2),
    }


def expand_signed(res, b=0):
    """Un-blocked lanes plane -> per-char signed order sequence."""
    o = np.asarray(res.ordp)[:, b]
    ln = np.asarray(res.lenp)[:, b]
    out = []
    for oo, ll in zip(o, ln):
        if oo == 0:
            continue
        s = abs(int(oo)) - 1
        out.extend((np.sign(int(oo))
                    * (s + np.arange(int(ll)) + 1)).tolist())
    return out


def blocked_mixed_signed(res, b=0):
    """Blocked mixed state -> per-char signed order sequence."""
    ordp = np.asarray(res.ordp)[:, b]
    lenp = np.asarray(res.lenp)[:, b]
    nlog = int(np.asarray(res.nlog)[0, b])
    blk = np.asarray(res.blkord)[:, b]
    rws = np.asarray(res.rws)[:, b]
    K = res.block_k
    out = []
    for sl in range(nlog):
        bb, r = int(blk[sl]), int(rws[sl])
        for oo, ll in zip(ordp[bb * K: bb * K + r],
                          lenp[bb * K: bb * K + r]):
            if oo == 0:
                continue
            s = abs(int(oo)) - 1
            out.extend((np.sign(int(oo))
                        * (s + np.arange(int(ll)) + 1)).tolist())
    return out


def _bounded_prefix(patches, n_patches: int, char_budget: int):
    """Interpret-feasible prefix of a real trace: total inserted chars
    bounded (interpret wall scales with the state plane).  A trace that
    OPENS with an oversized paste (rustcode: one 42k-char paste — no
    literal prefix is feasible) is rebased instead: the edits after the
    paste are cursor-localized, so a synthetic base insert covering
    exactly the touched window stands in for the paste and every edit
    shifts into it — offsets, delete spans and the shape mix are
    preserved verbatim.  Edits left referencing out-of-range content
    are dropped (count returned); the result is a valid standalone
    edit history."""
    from text_crdt_rust_tpu.utils.testdata import TestPatch

    if patches and len(patches[0].ins_content) > char_budget:
        return _windowed_prefix(patches, n_patches, char_budget)
    out, live, total_ins, dropped = [], 0, 0, 0
    for p in patches[:n_patches]:
        ins = p.ins_content
        if len(ins) > char_budget // 2:
            ins = ins[:char_budget // 2]
        if p.pos > live or p.pos + p.del_len > live:
            dropped += 1
            continue
        out.append(TestPatch(p.pos, p.del_len, ins))
        live += len(ins) - p.del_len
        total_ins += len(ins)
        if total_ins > char_budget:
            break
    return out, dropped


def _windowed_prefix(patches, n_patches: int, char_budget: int):
    """Rebase a giant-opening-paste trace onto the touched window (see
    ``_bounded_prefix``): pass 1 grows the window [lo, hi) over the
    maximal run of post-paste edits staying inside the budget; pass 2
    replays them shifted by -lo over a synthetic base insert of the
    window's real pasted content."""
    from text_crdt_rust_tpu.utils.testdata import TestPatch

    lo = hi = None
    kept = []
    for p in patches[1:n_patches]:
        nlo = p.pos if lo is None else min(lo, p.pos)
        nhi = (p.pos + p.del_len if hi is None
               else max(hi, p.pos + p.del_len))
        if nhi - nlo > char_budget:
            break
        lo, hi = nlo, nhi
        kept.append(p)
    if lo is None:
        return [patches[0]], 0
    span = hi - lo
    base = patches[0].ins_content[lo:hi].ljust(span, "x")
    out, live, dropped = [TestPatch(0, 0, base)], span, 0
    for p in kept:
        sp = p.pos - lo
        if sp < 0 or sp + p.del_len > live:
            dropped += 1
            continue
        out.append(TestPatch(sp, p.del_len, p.ins_content))
        live += len(p.ins_content) - p.del_len
    return out, dropped


def identity_prefix(name: str, n_patches: int, fuse_w: int,
                    char_budget: int = 2500, chunk: int = 128):
    """Replay a (bounded) trace prefix fused vs unfused through every
    fused-splice surface on CPU interpret; all comparisons bit-exact.
    ``chunk`` pads the step axis (interpret wall scales with padded
    steps — the smoke path shrinks it)."""
    patches, dropped = _bounded_prefix(
        flatten_patches(load_testing_data(trace_path(name))),
        n_patches, char_budget)
    lmax = 64
    ops_u, no_u = B.compile_local_patches(patches, lmax=lmax, dmax=None)
    fused, st = B.fuse_steps(ops_u, fuse_w=fuse_w)
    assert no_u == int(np.asarray(
        fused.order_advance, dtype=np.int64).sum())
    chars = no_u
    t0 = time.perf_counter()

    # Oracle: the flat engine on the UNFUSED stream.
    ref = F.apply_ops(SA.make_flat_doc(2 * chars + lmax), ops_u)
    want_spans = SA.doc_spans(ref)

    block_k = 64
    cap = ((int(chars * 2.1) + block_k - 1) // block_k) * block_k
    kw = dict(capacity=cap, batch=8, block_k=block_k, chunk=chunk,
              interpret=True)
    verdicts = {}

    # rle + rle_hbm: expand_runs + full by-order logs.
    for ename, mk in (("rle", R.replay_local_rle),
                      ("rle-hbm", RH.replay_local_rle_hbm)):
        res_u = mk(ops_u, **kw)
        res_f = mk(fused, **kw)
        same = np.array_equal(R.expand_runs(res_u), R.expand_runs(res_f))
        du = R.rle_to_flat(ops_u, res_u, capacity=2 * chars + lmax)
        df = R.rle_to_flat(fused, res_f, capacity=2 * chars + lmax)
        logs = all(
            np.array_equal(np.asarray(getattr(du, fld)),
                           np.asarray(getattr(df, fld)))
            for fld in ("signed", "ol_log", "or_log", "rank_log",
                        "chars_log", "n", "next_order"))
        verdicts[ename] = bool(
            same and logs and SA.doc_spans(df) == want_spans)

    # Blocked lanes engines ([S, B] streams, 2 lanes).
    smax = ((max(ops_u.num_steps, fused.num_steps) + chunk - 1)
            // chunk) * chunk
    su = B.stack_ops([B.pad_ops(ops_u, smax)] * 2)
    sf = B.stack_ops([B.pad_ops(fused, smax)] * 2)
    lkw = dict(capacity=cap, block_k=block_k, chunk=chunk, interpret=True)
    ru = RL.make_replayer_lanes_blocked(su, **lkw)()
    rf = RL.make_replayer_lanes_blocked(sf, **lkw)()
    ru.check()
    rf.check()
    verdicts["rle-lanes-blocked"] = bool(np.array_equal(
        RL.expand_lane_blocked(ru, 0), RL.expand_lane_blocked(rf, 0)))

    mu = RLM.replay_lanes_mixed_blocked(su, **lkw)
    mf = RLM.replay_lanes_mixed_blocked(sf, **lkw)
    mu.check()
    mf.check()
    verdicts["rle-lanes-mixed-blocked"] = bool(
        blocked_mixed_signed(mu) == blocked_mixed_signed(mf)
        and np.array_equal(np.asarray(mu.oll), np.asarray(mf.oll))
        and np.array_equal(np.asarray(mu.orl), np.asarray(mf.orl)))

    return {
        "trace": name,
        "identity_patches": len(patches),
        "patches_dropped_out_of_range": dropped,
        "steps_unfused": st.steps_in,
        "steps_fused": st.steps_out,
        "prefix_reduction_x": round(st.reduction_x, 2),
        "bit_identical": verdicts,
        "oracle_equal": all(verdicts.values()),
        "interpret_wall_s": round(time.perf_counter() - t0, 2),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--identity-patches", type=int, default=400)
    ap.add_argument("--fuse-w", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes, automerge only (the tier-1 smoke "
                         "path, tests/test_fused_trace_probe.py)")
    ap.add_argument("--out", default="perf/fused_traces_r9.json")
    args = ap.parse_args()
    traces = TRACES[:1] if args.smoke else TRACES
    n_id = min(args.identity_patches, 200) if args.smoke \
        else args.identity_patches

    cuts = [full_trace_cut(t, args.fuse_w) for t in traces] \
        if not args.smoke else []
    idents = [identity_prefix(t, n_id, args.fuse_w,
                              chunk=64 if args.smoke else 128)
              for t in traces]

    out = {
        "workload": {
            "granularity": "event (one compiled step per patch — the "
                           "serve-batcher shape; the host coalescer "
                           "never runs on per-event streams)",
            "lmax": LMAX, "fuse_w": args.fuse_w, "smoke": args.smoke,
        },
        "full_trace_step_cut": cuts,
        "bit_identity_prefix": idents,
        "acceptance": {
            "floor_x": FLOOR_X,
            "measured_x": (min(c["step_reduction_x"] for c in cuts)
                           if cuts else
                           min(i["prefix_reduction_x"] for i in idents)),
            "bit_identical_all": all(i["oracle_equal"] for i in idents),
            "pass": (all(c["step_reduction_x"] >= FLOOR_X for c in cuts)
                     if cuts else True)
            and all(i["oracle_equal"] for i in idents),
        },
    }
    if not args.smoke:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {args.out}", file=sys.stderr)
    print(json.dumps(out))
    print(f"acceptance {'PASS' if out['acceptance']['pass'] else 'FAIL'}",
          file=sys.stderr)
    return 0 if out["acceptance"]["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
