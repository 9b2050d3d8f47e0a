"""TCR-C00x: perf-claims consistency — docs vs committed artifacts
(ISSUE 15).

The repo's evidence discipline says a measured number is only a claim
when its artifact is committed (README "Measured vs pending silicon",
PERF.md, the ``perf/*_r*.json`` probes).  Claims rot structurally: a
probe JSON gets superseded or renamed and the prose keeps citing the
old name.  Nothing executes markdown, so no test catches it — a docs
cross-check does:

- **TCR-C001** — a ``perf/<file>`` reference in README.md / PERF.md
  that does not exist on disk: the cited evidence is gone (deleted,
  renamed, or never committed).
- **TCR-C003** — a row of README's claims table whose status column
  says "measured" but whose row cites NO committed artifact (no
  existing ``perf/*`` file, ``BENCH_ALL.json`` or ``COST_LEDGER.json``):
  a measured number with no committed source.

Pure project-level pass (markdown is not walked by the .py file
iterator); temp trees without the doc files skip silently.
"""
from __future__ import annotations

import os
import re
from typing import List, Optional, Tuple

from .tcrlint import Finding

DOC_FILES = ("README.md", "PERF.md")

CLAIMS_HEADING = "## Measured vs pending silicon"

_PERF_REF = re.compile(r"perf/[A-Za-z0-9_\-]+\.(?:json|sh|py|log)")
_ARTIFACT = re.compile(r"(perf/[A-Za-z0-9_\-]+\.(?:json|log)|"
                       r"BENCH_ALL\.json|COST_LEDGER\.json)")


def _claims_region(lines: List[str]) -> Optional[Tuple[int, int]]:
    """[start, end) line span (0-based) of the README claims section."""
    start = None
    for i, line in enumerate(lines):
        if start is None:
            if line.strip() == CLAIMS_HEADING:
                start = i
        elif line.startswith("## "):
            return (start, i)
    return (start, len(lines)) if start is not None else None


def check_claims(root: str) -> List[Finding]:
    out: List[Finding] = []
    for doc in DOC_FILES:
        path = os.path.join(root, doc)
        if not os.path.exists(path):
            continue
        with open(path, encoding="utf-8") as f:
            lines = f.read().splitlines()
        # C001: every perf/ file reference must exist.
        for i, line in enumerate(lines):
            for m in _PERF_REF.finditer(line):
                if not os.path.exists(os.path.join(root, m.group(0))):
                    out.append(Finding(
                        check="TCR-C001", path=doc, line=i + 1,
                        scope="<doc>",
                        message=f"cites {m.group(0)} which does not "
                                f"exist — the evidence artifact was "
                                f"renamed, superseded or never "
                                f"committed; fix the reference or "
                                f"commit the artifact"))
        if doc != "README.md":
            continue
        region = _claims_region(lines)
        if region is None:
            continue
        start, end = region
        for i in range(start, end):
            line = lines[i]
            # C003: a "measured" row must cite a committed artifact.
            cells = [c.strip() for c in line.split("|")]
            if len(cells) < 4 or not line.lstrip().startswith("|"):
                continue
            status = cells[2].lower()
            if ("measured" not in status or "not measured" in status
                    or cells[1] in ("claim", "---")):
                continue
            cited = [m.group(1) for m in _ARTIFACT.finditer(line)]
            committed = [c for c in cited
                         if os.path.exists(os.path.join(root, c))]
            if not committed:
                out.append(Finding(
                    check="TCR-C003", path=doc, line=i + 1,
                    scope="<doc>",
                    message=f"claims row {cells[1][:60]!r} is marked "
                            f"measured but cites no committed "
                            f"artifact (perf/*.json, perf/*.log, "
                            f"BENCH_ALL.json or COST_LEDGER.json) — "
                            f"commit the source or mark the row "
                            f"pending"))
    return out
