"""tcrlint self-tests (ISSUE 13): per-family injection + the tier-1 gate.

Two proof obligations per check family:

- **injection**: a minimal violating snippet written to a temp tree
  makes the lint exit 1 naming that exact file:line and check id;
- **clean pass**: the sanctioned spelling of the same code passes.

Plus the gate itself: ONE subprocess runs the full lint (tcrlint +
ruff-or-fallback, the shared entry point) over the real package and
must exit 0 — so a determinism hazard fails tier-1 CI with a named
finding, not a flaky fuzz seed three PRs later (the ``--check-ledger``
gate pattern).
"""
import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from text_crdt_rust_tpu.analysis import run_lint
from text_crdt_rust_tpu.analysis.checks_schema import surface_state
from text_crdt_rust_tpu.analysis.tcrlint import load_allowlist

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_tree(tmp_path, files, allow=None):
    """Write ``files`` ({rel: source}) into a temp tree and lint it
    in-process (no committed allowlist/pins unless provided)."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    allow_path = str(tmp_path / "allow.json")
    if allow is not None:
        (tmp_path / "allow.json").write_text(json.dumps({"allow": allow}))
    return run_lint(str(tmp_path), allowlist_path=allow_path,
                    pins_path=str(tmp_path / "pins.json"))


def the(findings, check):
    hits = [f for f in findings if f.check == check]
    assert hits, f"no {check} finding in {[f.format() for f in findings]}"
    return hits


# ---------------------------------------------- family 1: wall-clock --------


def test_wallclock_leak_named_by_file_and_line(tmp_path):
    findings, _ = lint_tree(tmp_path, {"mod.py": """\
        import time


        def emit(tracer):
            tracer_field = time.time()
            return tracer_field
        """})
    f = the(findings, "TCR-W001")[0]
    assert (f.path, f.line) == ("mod.py", 5)
    assert f.scope == "emit"


def test_wallclock_from_import_and_datetime(tmp_path):
    findings, _ = lint_tree(tmp_path, {"mod.py": """\
        from time import perf_counter
        import datetime


        def f():
            return perf_counter(), datetime.datetime.now()
        """})
    assert len(the(findings, "TCR-W001")) == 2


def test_wallclock_allowlisted_scope_passes(tmp_path):
    findings, _ = lint_tree(
        tmp_path,
        {"mod.py": "import time\n\n\ndef probe():\n"
                   "    return time.perf_counter()\n"},
        allow=[{"check": "TCR-W001", "path": "mod.py", "scope": "probe",
                "why": "test probe"}])
    assert not [f for f in findings if f.check == "TCR-W001"]


def test_stale_allowlist_entry_is_a_finding(tmp_path):
    findings, _ = lint_tree(
        tmp_path, {"mod.py": "X = 1\n"},
        allow=[{"check": "TCR-W001", "path": "mod.py", "scope": "gone",
                "why": "stale"}])
    assert the(findings, "TCR-A001")


def test_unjustified_allowlist_entry_refused(tmp_path):
    with pytest.raises(ValueError, match="justification"):
        lint_tree(tmp_path, {"mod.py": "X = 1\n"},
                  allow=[{"check": "TCR-W001", "path": "mod.py",
                          "scope": "f", "why": ""}])


# ---------------------------------------------- family 2: determinism -------


def test_builtin_hash_flagged(tmp_path):
    findings, _ = lint_tree(tmp_path, {"mod.py": """\
        def key(x):
            return hash(x) % 16
        """})
    f = the(findings, "TCR-D001")[0]
    assert (f.path, f.line) == ("mod.py", 2)


def test_set_iteration_flagged_sorted_passes(tmp_path):
    findings, _ = lint_tree(tmp_path, {"mod.py": """\
        def emit(names):
            for n in set(names):
                print(n)
            ordered = list({1, 2, 3})
            fine = sorted(set(names))
            count = len(set(names))
            return ordered, fine, count
        """})
    hits = the(findings, "TCR-D002")
    assert [f.line for f in hits] == [2, 4]


def test_unsorted_listdir_flagged_sorted_passes(tmp_path):
    findings, _ = lint_tree(tmp_path, {"mod.py": """\
        import glob
        import os


        def walk(d):
            bad = os.listdir(d)
            worse = glob.glob(d + "/*.npz")
            good = sorted(os.listdir(d))
            return bad, worse, good
        """})
    hits = the(findings, "TCR-D003")
    assert [f.line for f in hits] == [6, 7]


def test_unseeded_randomness_flagged_seeded_passes(tmp_path):
    findings, _ = lint_tree(tmp_path, {"mod.py": """\
        import random

        import numpy as np


        def gen():
            rng = random.Random(7)          # fine: seeded instance
            a = rng.random()
            b = random.random()             # global state
            c = np.random.rand(3)           # legacy global
            d = np.random.default_rng(7)    # fine: seeded
            e = np.random.default_rng()     # entropy-seeded
            return a, b, c, d, e
        """})
    hits = the(findings, "TCR-D004")
    assert [f.line for f in hits] == [9, 10, 12]


# ---------------------------------------------- family 3: schema drift ------


def test_unknown_trace_kind_flagged(tmp_path):
    findings, _ = lint_tree(tmp_path, {"mod.py": """\
        def f(tracer):
            tracer.event("tick.drain", shard=0, events=1, steps=1)
            tracer.event("bogus.kind", x=1)
        """})
    f = the(findings, "TCR-S001")[0]
    assert f.line == 3 and "bogus.kind" in f.message


def test_unknown_span_kind_flagged(tmp_path):
    """The module-level ``span(kind)`` is held to the schema too."""
    findings, _ = lint_tree(tmp_path, {"mod.py": """\
        from text_crdt_rust_tpu.obs.trace import span

        def f():
            with span("rle.dispatch"):
                pass
            with span("rle.bogus"):
                pass
        """})
    f = the(findings, "TCR-S001")[0]
    assert f.line == 6 and "rle.bogus" in f.message


def test_unknown_ledger_family_flagged(tmp_path):
    findings, _ = lint_tree(tmp_path, {"mod.py": """\
        from text_crdt_rust_tpu.obs.ledger import metric

        GOOD = metric(1, "steps")
        BAD = metric(1, "nonsense")
        """})
    f = the(findings, "TCR-S002")[0]
    assert f.line == 4 and "nonsense" in f.message


def test_schema_drift_without_version_bump_flagged(tmp_path):
    """Real-repo S003: a pin whose fingerprint disagrees while the
    version agrees = someone edited the field set without bumping."""
    pins = json.load(open(
        os.path.join(REPO, "text_crdt_rust_tpu/analysis/SCHEMA_PINS.json")))
    pins["pins"]["trace-events"]["fingerprint"] ^= 0xDEAD
    mutated = tmp_path / "pins.json"
    mutated.write_text(json.dumps(pins))
    findings, _ = run_lint(
        REPO, ["text_crdt_rust_tpu/obs/trace.py"],
        pins_path=str(mutated))
    f = the(findings, "TCR-S003")[0]
    assert f.path == "text_crdt_rust_tpu/obs/trace.py"
    assert "without" in f.message or "still" in f.message


def test_schema_pins_match_live_surfaces():
    """The committed pins agree with the live field sets — i.e. the
    shipped tree carries no unpinned schema drift."""
    pins = json.load(open(
        os.path.join(REPO, "text_crdt_rust_tpu/analysis/SCHEMA_PINS.json")))
    from text_crdt_rust_tpu.analysis.checks_schema import SURFACES

    assert {s["name"] for s in SURFACES} == set(pins["pins"])
    for s in SURFACES:
        st = surface_state(REPO, s)
        pin = pins["pins"][s["name"]]
        assert st["fingerprint"] == pin["fingerprint"], s["name"]
        assert st["version"] == pin["version"], s["name"]


# ---------------------------------------------- family 4: recompile ---------


def test_uncached_kernel_build_flagged_cached_passes(tmp_path):
    findings, _ = lint_tree(tmp_path, {"mod.py": """\
        import functools

        import jax
        from jax.experimental import pallas as pl


        def build_bad(k, shape):
            call = pl.pallas_call(k, out_shape=shape)
            return jax.jit(lambda a: call(a))


        @functools.lru_cache(maxsize=32)
        def _build_call(k, shape):
            call = pl.pallas_call(k, out_shape=shape)
            return jax.jit(lambda a: call(a))


        top_level = jax.jit(abs)


        @jax.jit
        def decorated(x):
            return x
        """})
    assert [f.line for f in the(findings, "TCR-R001")] == [8]
    assert [f.line for f in the(findings, "TCR-R002")] == [9]


# ---------------------------------------------- family 6: exceptions --------


def test_silent_swallow_in_serve_flagged(tmp_path):
    findings, _ = lint_tree(tmp_path, {"serve/mod.py": """\
        def ingest(frame):
            try:
                return frame.decode()
            except ValueError:
                pass
        """})
    hits = the(findings, "TCR-X001")
    assert hits[0].path == "serve/mod.py"
    assert hits[0].line == 4
    assert "ValueError" in hits[0].message


def test_swallow_outside_serve_net_not_flagged(tmp_path):
    findings, _ = lint_tree(tmp_path, {"ops/mod.py": """\
        def probe(x):
            try:
                return x()
            except ValueError:
                pass
        """})
    assert not [f for f in findings if f.check == "TCR-X001"]


def test_reported_handlers_pass(tmp_path):
    """Every sanctioned discipline: re-raise, typed conversion (raised
    OR constructed by value), notifier call, rejection recorder, and
    the inline-tally AugAssign."""
    findings, _ = lint_tree(tmp_path, {"net/mod.py": """\
        class WireError(Exception):
            pass


        def a(frame):
            try:
                return frame.decode()
            except ValueError:
                raise WireError("bad frame")


        def b(frame, counters):
            try:
                return frame.decode()
            except ValueError:
                counters.incr("frames_rejected")


        def c(frame, stats):
            try:
                return frame.decode()
            except ValueError:
                stats["rejected"] += 1


        def d(frame, router):
            try:
                return frame.decode()
            except ValueError as e:
                router.reject_frame(str(e))


        def e(frame):
            try:
                return frame.decode(), None
            except ValueError as exc:
                return None, WireError(str(exc))
        """})
    assert not [f for f in findings if f.check == "TCR-X001"]


def test_swallow_allowlist_grantable(tmp_path):
    findings, _ = lint_tree(tmp_path, {"serve/mod.py": """\
        def skip_foreign(names):
            out = []
            for n in names:
                try:
                    out.append(int(n))
                except ValueError:
                    continue
            return out
        """}, allow=[{"check": "TCR-X001", "path": "serve/mod.py",
                      "scope": "skip_foreign",
                      "why": "filename-pattern filter, not an op-path fault"}])
    assert not [f for f in findings if f.check == "TCR-X001"]


# ---------------------------------------------- ruff fallback ---------------


def test_unused_import_flagged_noqa_passes(tmp_path):
    findings, _ = lint_tree(tmp_path, {"mod.py": """\
        import json
        import os  # noqa: F401
        import sys

        print(sys.argv)
        """})
    hits = the(findings, "TCR-F401")
    assert [f.line for f in hits] == [1]
    assert "json" in hits[0].message


# ---------------------------------------------- the committed allowlist -----


def test_committed_allowlist_loads_and_every_entry_justified():
    entries = load_allowlist()
    assert entries, "the audited allowlist ships non-empty"
    for e in entries:
        assert len(e["why"]) > 20, f"thin justification: {e}"


# ---------------------------------------------- the tier-1 gate -------------


def test_lint_gate_clean_tree_exits_zero():
    """THE tier-1 lint gate, full-tree flavor: the shared entry point
    (tcrlint v2 + ruff or its fallback) over the shipped package must
    be clean — the authoritative clean-tree proof behind the
    ``--changed`` incremental gate (test_analysis_dataflow.py, which
    honors the ``TCR_LINT_FULL=1`` weekly-style knob to force this
    flavor there too).  Budget: < 15 s wall (ISSUE 15 acceptance;
    measured ~3 s cold, ~0.3 s cache-warm)."""
    t0 = time.perf_counter()
    r = subprocess.run(
        [sys.executable, "-m", "text_crdt_rust_tpu.analysis.lint",
         "--json"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    wall = time.perf_counter() - t0
    assert r.returncode == 0, (r.stdout[-4000:], r.stderr[-2000:])
    out = json.loads(r.stdout)
    assert out["ok"] and not out["findings"]
    assert out["stats"]["files"] > 50  # the whole package walked
    assert wall < 15, f"lint gate took {wall:.1f}s (15s budget)"


def test_lint_gate_fails_loud_on_all_four_families(tmp_path):
    """The other half of the gate contract (ISSUE 13 acceptance): ONE
    violating tree exercises every check family through the real CLI,
    which exits 1 with each file:line-named finding on stdout."""
    (tmp_path / "bad.py").write_text(textwrap.dedent("""\
        import time

        import jax
        from jax.experimental import pallas as pl


        def leak():
            return time.time()


        def key(x):
            return hash(x)


        def emit(names):
            return list(set(names))


        def build(k, shape):
            call = pl.pallas_call(k, out_shape=shape)
            return jax.jit(lambda a: call(a))
        """))
    r = subprocess.run(
        [sys.executable, "-m", "text_crdt_rust_tpu.analysis.lint",
         "--root", str(tmp_path), "--allowlist",
         str(tmp_path / "none.json"), "--pins",
         str(tmp_path / "none_pins.json"), "bad.py"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert r.returncode == 1, (r.stdout, r.stderr)
    assert "bad.py:8: TCR-W001" in r.stdout     # wall-clock leak
    assert "bad.py:12: TCR-D001" in r.stdout    # builtin hash()
    assert "bad.py:16: TCR-D002" in r.stdout    # set-order hazard
    assert "bad.py:20: TCR-R001" in r.stdout    # uncached kernel build
    assert "bad.py:21: TCR-R002" in r.stdout


def test_lint_gate_fails_loud_on_schema_drift(tmp_path):
    """Family 3 through the CLI: a fingerprint/version disagreement on
    a real surface exits 1 naming the surface file."""
    pins = json.load(open(
        os.path.join(REPO, "text_crdt_rust_tpu/analysis/SCHEMA_PINS.json")))
    pins["pins"]["bench-row"]["fingerprint"] ^= 0xBEEF
    mutated = tmp_path / "pins.json"
    mutated.write_text(json.dumps(pins))
    r = subprocess.run(
        [sys.executable, "-m", "text_crdt_rust_tpu.analysis.lint",
         "--pins", str(mutated), "text_crdt_rust_tpu/analysis"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert r.returncode == 1, (r.stdout, r.stderr)
    assert "bench.py" in r.stdout and "TCR-S003" in r.stdout
    assert "bump the version" in r.stdout
