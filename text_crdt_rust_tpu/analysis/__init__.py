"""tcrlint — project-invariant static analysis (ISSUE 13 tentpole).

Every load-bearing contract in this repo — byte-identical logical
trace streams (PERF.md §14), exact cost-ledger re-derivation (§15),
YATA convergence, the hard-rejection codec discipline — is a
*determinism* contract, and determinism bugs are the kind tests catch
three PRs after they ship (a new wall-clock read leaking into a trace
field only fails when someone diffs two runs).  ``tcrlint`` moves the
enforcement to lint time: an AST pass over the package with one module
per check family, a committed allowlist for the audited intentional
sites, and a tier-1 gate so a violation fails CI with a file:line
finding, not a flaky fuzz seed later.

Check families (one module each):

==========================  ================================================
``checks_wallclock``        TCR-W001: wall-clock reads (``time.time``,
                            ``perf_counter``, ``datetime.now``) outside the
                            audited obs/perf sites — wall time may feed
                            obs ``"w"`` fields and perf probes, NEVER a
                            logical trace field, ledger metric, bench-row
                            logical field, or wire byte
``checks_determinism``      TCR-D001 builtin ``hash()`` (per-process salt),
                            TCR-D002 order-sensitive set iteration,
                            TCR-D003 unsorted ``os.listdir``/``glob`` walks,
                            TCR-D004 unseeded global randomness
``checks_schema``           TCR-S001 trace kinds missing from EVENT_SCHEMA,
                            TCR-S002 ledger metrics with unregistered
                            families, TCR-S003 schema field-set drift
                            without the matching version bump (pinned
                            fingerprints, ``SCHEMA_PINS.json``)
``checks_recompile``        TCR-R001 ``pallas_call`` / TCR-R002 ``jax.jit``
                            build sites that are neither lru-cached nor
                            module-level (the ``_build_call`` pattern) —
                            dynamic-shape retrace leaks
``checks_pyflakes``         TCR-F401 unused module-level imports — the
                            built-in fallback for the ruff baseline when
                            ruff is not installed
==========================  ================================================

**v2 — interprocedural dataflow families** (ISSUE 15): ``dataflow.py``
grows per-function CFGs, reaching definitions, alias closures and
one-level call summaries over the stdlib ``ast``; four flow-aware
check families consume them:

==========================  ================================================
``checks_pipeline``         TCR-P001: dispatch-buffer escape — a host
                            write that may alias a buffer handed to
                            ``backend.apply``/the flat jits before its
                            staged sync (the static twin of the PR-12
                            runtime aliasing sanitizer, which stays on
                            as defense-in-depth)
``checks_mirror``           TCR-M001 a device-state write site without
                            its paired host-mirror update (the PR-13
                            capacity-contract model), TCR-M002 a serve
                            backend class with device writes missing
                            from ``MIRROR_CONTRACTS``
``checks_shape``            TCR-K001 a static call-site shape off the
                            declared bucket series, TCR-K002 series
                            drift vs the pinned ``SHAPE_CONTRACTS.json``
                            (refreshed via ``--update-pins``)
``checks_claims``           TCR-C001 a cited ``perf/`` artifact that
                            does not exist, TCR-C003 a "measured"
                            claims row with no committed source
==========================  ================================================

CLI: ``python -m text_crdt_rust_tpu.analysis.lint`` (exit 1 with
file:line-named findings; ``--changed`` for the incremental tier-1
mode, content-hash cached under ``.tcrlint_cache/``).  Allowlist:
``LINT_ALLOWLIST.json`` next to this file — every entry names
(check, path, scope) plus a one-line justification, and a stale entry
(matching nothing) is itself a finding, so the allowlist can only
shrink or be re-justified.
"""
from .tcrlint import (  # noqa: F401
    ALLOWLIST_PATH,
    PINS_PATH,
    Finding,
    changed_files,
    load_allowlist,
    run_lint,
)
