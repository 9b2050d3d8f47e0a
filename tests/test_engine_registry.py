"""The engine registry (``config.ENGINE_REGISTRY``) is the ONE source
of engine names (VERDICT r5 weak #6: ``rle-lanes-mixed`` was missing
from ``ENGINE_CHOICES`` while bench.py recorded rows under it).  These
tests hold the registry, bench.py, and README's tables to each other.
"""
import importlib
import os
import re

from text_crdt_rust_tpu.config import (
    ENGINE_CHOICES,
    ENGINE_REGISTRY,
    ENGINE_ROW_ALIASES,
    engines_for,
    lane_block_geometry,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _known(name: str) -> bool:
    if name in ENGINE_REGISTRY:
        return True
    if name in ENGINE_ROW_ALIASES:
        alias = ENGINE_ROW_ALIASES[name]
        return alias is None or alias in ENGINE_REGISTRY
    return False


def test_choices_derive_from_registry():
    assert ENGINE_CHOICES == tuple(ENGINE_REGISTRY)
    assert "rle-lanes-mixed" in ENGINE_REGISTRY  # the r5 drift


def test_registry_modules_import():
    for name, spec in ENGINE_REGISTRY.items():
        mod = importlib.import_module(
            f"text_crdt_rust_tpu.{spec['module']}")
        assert mod is not None, name


def test_bench_engine_rows_are_registered():
    """Every engine label bench.py records (literal strings passed to
    make_row) resolves through the registry or the alias map."""
    with open(os.path.join(ROOT, "bench.py")) as f:
        src = f.read()
    # make_row(config, engine, ...): literal engine labels only (the
    # args.engine call sites are constrained by ENGINE_CHOICES already).
    labels = re.findall(
        r"make_row\(\s*\"[^\"]+\",\s*\n?\s*\"([^\"]+)\"", src)
    labels += re.findall(r"make_row\(f\"[^\"]+\", \"([^\"]+)\"", src)
    assert labels, "no literal engine labels found — regex drifted?"
    for label in labels:
        assert _known(label), (
            f"bench.py records rows under engine {label!r} which is "
            f"neither in ENGINE_REGISTRY nor ENGINE_ROW_ALIASES")


def test_readme_engine_table_is_registered():
    """Every engine named in README's measured-results table resolves
    through the registry or the alias map."""
    with open(os.path.join(ROOT, "README.md")) as f:
        lines = f.readlines()
    seen = []
    for ln in lines:
        # Bench-table rows: | workload | engine | ops/s | vs |
        cells = [c.strip() for c in ln.split("|")]
        if len(cells) >= 5 and cells[3].endswith(("G", "M", "k", "×")):
            label = re.sub(r"\s*\(.*\)", "", cells[2]).strip()
            if label and not set(label) <= {"-"}:
                seen.append(label.replace(" ", "-"))
    assert seen, "README bench table not found — format drifted?"
    for label in seen:
        assert _known(label), (
            f"README names engine {label!r} which is neither in "
            f"ENGINE_REGISTRY nor ENGINE_ROW_ALIASES")


def test_engines_for_covers_streaming_configs():
    assert "rle-lanes" in engines_for("5")
    assert "rle-lanes-mixed" in engines_for("5r")
    assert set(engines_for("northstar")) == {"rle", "rle-hbm"}


def test_lane_block_geometry_rounds_up():
    cap, nb, nbt = lane_block_geometry(201, 64)
    assert (cap, nb, nbt) == (256, 4, 8)
    cap, nb, nbt = lane_block_geometry(1664, 64)
    assert (cap, nb, nbt) == (1664, 26, 26)
