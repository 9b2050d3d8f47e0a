"""The three readers of the program's span totals: ``compiler.host_s``,
``program.build_s`` and ``dispatch.host_us_per_pass``.

Each is checked on totals worked out by hand, including the cases that
must read nothing (a span missing, as in a program without it; a count
of dispatches that differs from the window's passes; a build that
compiled; dispatch spans without CPU time), and on traced tiny replay
runs on the CPU, where all three read once the build is warm."""
import os

import pytest

from bench_testlib import REPO, make_root, run_tiny


def reader(name):
    from benchmark import harness

    return harness.load_module(
        os.path.join(REPO, "benchmark", "metrics", name + ".py"),
        "test_metric_" + name.replace(".", "_")).read


def tot(count, total_s, max_s=None, compiles=0, cpu_s=None):
    return {"count": count, "total_s": total_s,
            "max_s": total_s if max_s is None else max_s,
            "compiles": compiles, "cpu_s": cpu_s}


TOTALS = {"compiler.merge": tot(1, 0.35), "compiler.compile": tot(1, 0.11),
          "compiler.fuse": tot(1, 0.09), "rle.stage": tot(1, 0.25),
          "rle.build": tot(1, 2.5),
          "rle.dispatch": tot(4, 0.14, 0.036, cpu_s=0.0012),
          "tick.drain": tot(9, 5.0)}


@pytest.mark.parametrize("name, rec, want", [
    ("compiler.host_s", {}, 0.35 + 0.11 + 0.09),
    ("program.build_s", {}, 0.25 + 2.5),
    ("dispatch.host_us_per_pass", {"passes": 4}, 300.0),
])
def test_reader_on_hand_made_totals(name, rec, want):
    assert reader(name)(rec, TOTALS) == pytest.approx(want)


@pytest.mark.parametrize("name, missing", [
    ("compiler.host_s", "compiler.fuse"),
    ("program.build_s", "rle.build"),
    ("dispatch.host_us_per_pass", "rle.dispatch"),
])
def test_reader_without_its_span_reads_nothing(name, missing):
    totals = {k: v for k, v in TOTALS.items() if k != missing}
    assert reader(name)({"passes": 4}, totals) is None
    assert reader(name)({"passes": 4}, {}) is None


@pytest.mark.parametrize("passes", (0, 3, 5))
def test_dispatch_count_other_than_passes_reads_nothing(passes, capsys):
    assert reader("dispatch.host_us_per_pass")(
        {"passes": passes}, TOTALS) is None
    if passes:
        assert "nothing read" in capsys.readouterr().err


@pytest.mark.parametrize("name, kind, change, said", [
    ("program.build_s", "rle.build", {"compiles": 1}, "cold build"),
    ("program.build_s", "rle.stage", {"compiles": 2}, "cold build"),
    ("dispatch.host_us_per_pass", "rle.dispatch", {"cpu_s": None}, ""),
])
def test_reader_refuses_what_it_cannot_read(name, kind, change, said,
                                            capsys):
    """A cold build is not the warm build the metric reads; dispatch
    wall without CPU time would hold the wait on the device."""
    totals = dict(TOTALS, **{kind: dict(TOTALS[kind], **change)})
    assert reader(name)({"passes": 4}, totals) is None
    assert said in capsys.readouterr().err


def test_traced_tiny_replay_reports_all_three(tmp_path):
    """All three read in a traced run whose build compiled nothing (the
    second run in this process); a build that compiled reads nothing
    for ``program.build_s`` and the other two still read."""
    from text_crdt_rust_tpu.obs.trace import reset_span_totals, span_totals

    root = make_root(tmp_path)
    names = ("compiler.host_s", "program.build_s",
             "dispatch.host_us_per_pass")
    for run in range(2):
        reset_span_totals()
        line = run_tiny(root, "tiny-replay-fleet", 2**35 + 11, seconds=0.5,
                        trace=True)
        assert line["correct"], line["checks"]
        got = {k: v["value"] for k, v in line["metrics"].items()}
        cold = span_totals()["rle.build"]["compiles"] > 0
        assert run == 0 or not cold
        assert ("program.build_s" in got) == (not cold)
        assert all(got[n] > 0 for n in names if n in got)
        assert {"compiler.host_s", "dispatch.host_us_per_pass"} <= set(got)
    assert line["metrics"]["dispatch.host_us_per_pass"]["unit"] == "us"
