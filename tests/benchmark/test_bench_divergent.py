"""The divergent-fleet cell at a tiny size on the CPU (Pallas in interpret
mode): its lane map, its reader of the per-lane layout, its comparison,
its faults and its metric readers.

A tiny copy of ``replay-divergent-fleet`` (the first 300 patches of each
of the three traces, a capacity of a few blocks, the 128 lanes of the
configured map) is added to a temporary checkout as new files, the way a
later PR adds a cell."""
import json
import os

import numpy as np
import pytest

from bench_testlib import make_root, run_tiny

CONFIG = "replay-editingtraces-divergent"
TINY = {"patches": 300, "documents": 256,
        "engine": {"capacity": 1024, "block_k": 32, "chunk": 128}}
FAULTS = ("control", "unchanged", "half", "altered", "collapsed")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("bench"))
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    for k, v in TINY.items():
        cfg[k] = dict(cfg[k], **v) if isinstance(v, dict) else v
    cfg["name"] = "tiny-divergent"
    with open(os.path.join(bench, "configs", "tiny-divergent.json"),
              "w") as f:
        json.dump(cfg, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tiny-divergent", "source": "test", "reduced": [],
        "why": "test", "file": "benchmark/configs/tiny-divergent.json"})
    manifest["workloads"].append({
        "name": "tiny-divergent-fleet", "config": "tiny-divergent",
        "traffic": "divergent-back-to-back", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "replay-divergent-fleet" in m.get("workloads", ()):
            m["workloads"].append("tiny-divergent-fleet")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return root


@pytest.mark.parametrize("fault", (None,) + FAULTS)
def test_divergent_fault_is_caught(root, fault):
    line = run_tiny(root, "tiny-divergent-fleet", 2**33 + 29, fault=fault,
                    seconds=0.5)
    assert line["correct"] is (fault is None), line["checks"]
    wrong = line["checks"]["docs_wrong"]["value"]
    total = line["attempted"]
    assert total == 256
    if fault is None:
        assert line["failed"] == 0
        assert line["metrics"]["replay_ops_per_s"]["value"] > 0
    elif fault in ("control", "unchanged"):
        assert wrong == total
    elif fault == "collapsed":
        # Every lane of the two other traces: 85 of each slot's 128.
        assert wrong == total * 85 // 128
    elif fault == "half":
        # Half the lanes of every slot.
        assert wrong >= total // 2
    else:
        assert wrong > 0


def test_traced_line_reads_the_program_metrics(root):
    """A traced run on the CPU: the padding counter, the host compiler's
    spans and the replayer's spans are read; the device metrics have no
    TPU plane to read."""
    from text_crdt_rust_tpu.obs.trace import reset_span_totals

    reset_span_totals()
    line = run_tiny(root, "tiny-divergent-fleet", 13, seconds=0.3,
                    trace=True)
    assert line["correct"], line["checks"]
    m = line["metrics"]
    assert 0.0 < m["lanes.pad_share"]["value"] < 100.0
    assert m["compiler.host_s"]["value"] > 0.0
    assert m["dispatch.host_us_per_pass"]["value"] > 0.0
    assert "kernel.rle_lanes_roofline_share" not in m


def test_lane_map_is_deterministic_from_the_config_seed():
    from benchmark.drivers.divergent import lane_map
    from benchmark.harness import load_json

    from bench_testlib import REPO

    cfg = load_json(os.path.join(REPO, "benchmark", "configs",
                                 CONFIG + ".json"))
    a, b = lane_map(cfg), lane_map(cfg)
    c = lane_map(dict(cfg, lane_seed=cfg["lane_seed"] + 1))
    assert a == b and a != c
    assert len(a) == cfg["lanes_per_pass"] == 128
    assert [a.count(t) for t in range(3)] == [43, 43, 42]
    assert [t["name"] for t in cfg["traces"]] == [
        "automerge-paper", "rustcode", "sveltecomponent"]


def test_lanes_reader_round_trips_its_encoder():
    from benchmark.drivers.divergent import encode_lanes_lane
    from benchmark.reference import (
        decode_rle_lane,
        load_trace,
        splice_orders,
        visible_orders,
    )

    patches, _ = load_trace("sveltecomponent")
    want, _ = splice_orders(patches[:2000])
    ordp, lenp, nlog, blkord, rws, liv = encode_lanes_lane(
        want, 4096, 32, 128)
    assert nlog > 1
    got = decode_rle_lane(ordp, lenp, blkord, rws, nlog, 32)
    assert np.array_equal(visible_orders(got), want)
    assert int(liv.sum()) == len(want)


def test_every_trace_is_whole_in_the_benchmark_data():
    from benchmark.reference import load_trace, splice_orders

    for name, patches_n in (("rustcode", 40173),
                            ("sveltecomponent", 19749)):
        patches, end = load_trace(name)
        assert len(patches) == patches_n
        assert splice_orders(patches)[1] == end


def _reader(name):
    from benchmark import harness

    return harness.load_module(
        os.path.join(os.path.dirname(harness.__file__), "metrics",
                     name + ".py"), "test_metric_" + name.replace(".", "_")
    ).read


def test_readers_read_synthetic_records():
    from benchmark.hbm_rle_lanes import rle_lanes_pass_bytes
    from benchmark.trace_reduce import Reduced

    ms = 1e6
    trace = Reduced(lo_ns=0.0, window_ns=1000 * ms, ops={0: [
        ("%rle_lanes_replay.1 = (u32[10240,128]) custom-call(...)", 0.0,
         400 * ms),
        ("%rle_lanes_replay.1 = (u32[10240,128]) custom-call(...)",
         450 * ms, 400 * ms),
        ("%copy.1 = s32[8,128] copy(...)", 900 * ms, 10 * ms)]})
    shape = {"steps_padded": 10240, "batch": 128, "capacity": 22656,
             "block_k": 128}
    rec = {"trace": trace, "passes": 2, "rle_lanes_shape": shape,
           "device_kind": "TPU v5 lite",
           "lane_steps": {"real": 948424, "stepped": 1310720}}
    # Five op columns, the state read and written, two origin planes.
    assert rle_lanes_pass_bytes(10240, 128, 22656, 128) == 4 * (
        7 * 10240 * 128 + 2 * (2 * 22656 * 128 + 128 + 3 * 177 * 128)
        + 8 * 128)
    want = 100.0 * rle_lanes_pass_bytes(10240, 128, 22656, 128) \
        / 819e9 / 0.4
    read = _reader("kernel.rle_lanes_roofline_share")
    share = read(rec)
    assert share == pytest.approx(want) and 0.0 < share < 100.0
    # A count of calls that is not the count of passes reads nothing.
    assert read(dict(rec, passes=3)) is None
    assert read(dict(rec, trace=None)) is None
    pad = _reader("lanes.pad_share")
    assert pad(rec) == pytest.approx(100.0 * (1 - 948424 / 1310720))
    assert pad(dict(rec, lane_steps=None)) is None
    assert pad({"lane_steps": {"real": 0, "stepped": 0}}) is None
