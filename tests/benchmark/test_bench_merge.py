"""The merge cell at a tiny size on the CPU (Pallas in interpret mode):
its generator, its reference, its comparison and its metric readers.

A tiny copy of ``merge-concurrent-fleet`` (B2.4 at a few dozen rounds,
a capacity of a few blocks, 8 lanes) is added to a temporary checkout
as new files, the way a later PR adds a cell."""
import json
import os

import numpy as np
import pytest

from bench_testlib import make_root, run_tiny

MERGE_CONFIG = "merge-crdtbench-b24-n6000"
TINY_MERGE = {"generator": {"rounds": 40}, "documents": 16,
              "lanes_per_pass": 8,
              "engine": {"capacity": 512, "block_k": 32, "chunk": 128}}
FAULTS = ("control", "unchanged", "half", "altered")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("bench"))
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", MERGE_CONFIG + ".json")) as f:
        cfg = json.load(f)
    for k, v in TINY_MERGE.items():
        cfg[k] = dict(cfg[k], **v) if isinstance(v, dict) else v
    cfg["name"] = "tiny-merge"
    with open(os.path.join(bench, "configs", "tiny-merge.json"), "w") as f:
        json.dump(cfg, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tiny-merge", "source": "test", "reduced": [],
        "why": "test", "file": "benchmark/configs/tiny-merge.json"})
    manifest["workloads"].append({
        "name": "tiny-merge-fleet", "config": "tiny-merge",
        "traffic": "merge-back-to-back", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "merge-concurrent-fleet" in m.get("workloads", ()):
            m["workloads"].append("tiny-merge-fleet")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return root


@pytest.mark.parametrize("fault", (None,) + FAULTS)
def test_merge_fault_is_caught(root, fault):
    line = run_tiny(root, "tiny-merge-fleet", 2**33 + 7, fault=fault,
                    seconds=0.5)
    assert line["correct"] is (fault is None), line["checks"]
    wrong = line["checks"]["docs_wrong"]["value"]
    if fault is None:
        assert line["failed"] == 0 and line["attempted"] == 16
        assert line["metrics"]["replay_ops_per_s"]["value"] > 0
    elif fault in ("control", "unchanged"):
        assert wrong == line["attempted"] == 16
    elif fault == "half":
        # Half the lanes of each pass the window ran (all 16 when the
        # canonical lane is among them).
        assert wrong >= 4
    else:
        assert wrong > 0


def test_merge_traced_line_reads_the_program_metrics(root):
    """A traced run on the CPU: the counters, the host compiler's spans
    and the replayer's dispatch spans are read; the device metrics have
    no TPU plane to read."""
    from text_crdt_rust_tpu.obs.trace import reset_span_totals

    reset_span_totals()
    line = run_tiny(root, "tiny-merge-fleet", 11, seconds=0.3, trace=True)
    assert line["correct"], line["checks"]
    m = line["metrics"]
    assert 0.0 <= m["integrate.exact_fallback_share"]["value"] <= 100.0
    assert m["compiler.remote_host_s"]["value"] > 0.0
    assert m["dispatch.host_us_per_pass"]["value"] > 0.0
    assert "kernel.rle_mixed_roofline_share" not in m


def test_generator_is_deterministic_from_its_seed():
    from benchmark.merge_reference import b24_updates

    a, na = b24_updates(30, ("A", "B"), 5)
    b, nb = b24_updates(30, ("A", "B"), 5)
    c, _ = b24_updates(30, ("A", "B"), 6)
    assert na == nb == 2 * 2 * 30
    assert a == b and a != c
    # Every round is an insert and then an insert or a delete.
    from benchmark.ref.common import RemoteDel, RemoteIns

    ops = [op for t in a[0] for op in t.ops]
    ins = sum(isinstance(op, RemoteIns) for op in ops)
    dels = sum(isinstance(op, RemoteDel) for op in ops)
    assert ins >= 30 and dels > 0


@pytest.mark.parametrize("seed", [1, 2])
def test_reference_converges_in_both_orders(seed):
    from benchmark.merge_reference import (
        b24_updates,
        server_replica,
        signed_orders,
    )

    ups, _ = b24_updates(60, ("A", "B"), seed)
    ab = server_replica(ups)
    ba = server_replica(ups[::-1])
    ab.check()
    ba.check()
    assert ab.to_string() == ba.to_string()
    assert len(ab.to_string()) > 0
    # Orders depend on the order of application; the body's length and
    # its tombstone count do not.
    sa, sb = signed_orders(ab), signed_orders(ba)
    assert len(sa) == len(sb) and (sa < 0).sum() == (sb < 0).sum()


def test_signed_lane_encoding_round_trips():
    from benchmark.merge_reference import (
        b24_updates,
        encode_signed_lane,
        server_replica,
        signed_orders,
    )
    from benchmark.reference import decode_rle_lane

    want = signed_orders(server_replica(b24_updates(50, ("A", "B"), 3)[0]))
    ordp, lenp, blkord, rows, nlog = encode_signed_lane(want, 4096, 32, 128)
    got = decode_rle_lane(ordp, lenp, blkord, rows, nlog, 32)
    assert np.array_equal(got, want)
    assert (want < 0).any() and (want > 0).any()


def _reader(name):
    from benchmark import harness

    return harness.load_module(
        os.path.join(os.path.dirname(harness.__file__), "metrics",
                     name + ".py"), "test_metric_" + name.replace(".", "_")
    ).read


def test_readers_read_synthetic_records():
    from benchmark.trace_reduce import Reduced

    ms = 1e6
    trace = Reduced(lo_ns=0.0, window_ns=1000 * ms, ops={0: [
        ("%rle_mixed_replay.1 = (u32[7168,128]) custom-call(...)", 0.0,
         400 * ms),
        ("%rle_mixed_replay.1 = (u32[7168,128]) custom-call(...)",
         450 * ms, 400 * ms),
        ("%copy.1 = s32[8,128] copy(...)", 900 * ms, 10 * ms)]})
    shape = {"steps_padded": 7168, "steps": 6673, "orders": 34591,
             "lmax": 16, "batch": 128, "capacity": 11136, "block_k": 128}
    rec = {"trace": trace, "passes": 2, "rle_mixed_shape": shape,
           "device_kind": "TPU v5 lite",
           "integrate": {"remote_inserts": 400, "exact_fallbacks": 3}}
    from benchmark.hbm_rle_mixed import rle_mixed_pass_bytes, table_rows

    assert table_rows(34591, 16) == 272
    want = 100.0 * rle_mixed_pass_bytes(7168, 128, 11136, 128, 34591, 16) \
        / 819e9 / 0.4
    share = _reader("kernel.rle_mixed_roofline_share")(rec)
    assert share == pytest.approx(want) and 0.0 < share < 100.0
    # A count of calls that is not the count of passes reads nothing.
    assert _reader("kernel.rle_mixed_roofline_share")(
        dict(rec, passes=3)) is None
    assert _reader("integrate.exact_fallback_share")(rec) == 0.75
    assert _reader("integrate.exact_fallback_share")(
        dict(rec, integrate={"remote_inserts": 0,
                             "exact_fallbacks": 0})) is None
    assert _reader("device.idle_share.replay")(rec) == pytest.approx(19.0)
    totals = {"compiler.remote": {"total_s": 0.25},
              "compiler.prefill": {"total_s": 0.5}}
    read = _reader("compiler.remote_host_s")
    assert read(rec, totals) == 0.75
    assert read(rec, {"compiler.remote": {"total_s": 0.25}}) is None
