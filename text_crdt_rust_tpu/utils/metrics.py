"""Metrics & observability: the reference's ``print_stats`` family, TPU-ified.

The reference instruments itself with a counting global allocator
(`src/alloc.rs:13-50`) and per-container ``print_stats`` dumps — entry
histograms, node counts, RLE compaction ratio ("compacts to N entries",
`split_list/mod.rs:418`), actual-vs-efficient memory (`root.rs:293-326`).
The TPU build's equivalents (SURVEY §5 "Tracing/profiling" row):

- ``doc_stats``   — one dict per document: items/live/tombstones, merged
                    span count + compaction ratio (the RLE health metric
                    that decides device array sizes), span-length
                    histogram, log entry counts;
- ``memory_stats``— bytes per column for host oracle docs and device
                    ``FlatDoc``s (device bytes ARE the HBM footprint);
- ``Throughput``  — ops/sec accumulator for bench loops (wall-clock via
                    ``time.perf_counter``, explicit ``ops`` counts).

All functions accept either an oracle ``ListCRDT`` or a device ``FlatDoc``
(anything exposing ``doc_spans``-compatible state).
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import numpy as np


def _spans_of(doc) -> List[Tuple[int, int, int, int]]:
    if hasattr(doc, "doc_spans"):
        return doc.doc_spans()
    from ..ops.span_arrays import doc_spans
    return doc_spans(doc)


def _counts_of(doc, spans) -> Tuple[int, int]:
    """(total items, live items) for oracle or FlatDoc. Derived from the
    merged spans for device docs (avoids a second device->host download)."""
    if hasattr(doc, "deleted"):  # oracle
        n = doc.n
        return n, int(np.count_nonzero(~doc.deleted[:n]))
    lens = [s[3] for s in spans]
    return sum(abs(l) for l in lens), sum(l for l in lens if l > 0)


def span_histogram(spans, bins=(1, 2, 4, 8, 16, 32, 64, 128)) -> Dict[str, int]:
    """Span-length histogram (the reference's entry-size histograms,
    `root.rs:293-326`)."""
    lens = np.asarray([abs(s[3]) for s in spans] or [0])
    out: Dict[str, int] = {}
    lo = 1
    for hi in bins:
        out[f"{lo}-{hi}"] = int(((lens >= lo) & (lens <= hi)).sum())
        lo = hi + 1
    out[f">{bins[-1]}"] = int((lens > bins[-1]).sum())
    return out


def doc_stats(doc, spans=None) -> dict:
    """Document-health metrics; ``compaction`` is items per merged span —
    the reference's "compacts to N entries" ratio. Pass precomputed
    ``spans`` to avoid re-downloading a device doc."""
    if spans is None:
        spans = _spans_of(doc)
    items, live = _counts_of(doc, spans)
    stats = {
        "items": items,
        "live": live,
        "tombstones": items - live,
        "merged_spans": len(spans),
        "compaction": items / max(1, len(spans)),
        "span_histogram": span_histogram(spans),
    }
    if hasattr(doc, "deletes"):  # oracle-side logs
        stats["deletes_entries"] = doc.deletes.num_entries()
        stats["double_delete_entries"] = doc.double_deletes.num_entries()
        stats["txn_entries"] = doc.txns.num_entries()
    return stats


def memory_stats(doc, spans=None) -> dict:
    """Bytes per column. For a device ``FlatDoc`` these are the actual HBM
    buffer sizes; ``efficient_bytes`` is what a fully RLE-compacted span
    store would need (16B/span, `span.rs:126-129`) — the reference's
    actual-vs-efficient comparison. Pass precomputed ``spans`` to avoid
    re-downloading a device doc."""
    if spans is None:
        spans = _spans_of(doc)
    if hasattr(doc, "deleted"):  # oracle numpy columns
        cols = {k: getattr(doc, k).nbytes
                for k in ("order", "origin_left", "origin_right",
                          "deleted", "chars")}
    elif hasattr(doc, "memory_bytes"):  # native engine: measured total
        cols = {"native_engine": int(doc.memory_bytes())}
    else:
        cols = {k: int(np.prod(getattr(doc, k).shape)
                       * getattr(doc, k).dtype.itemsize)
                for k in ("signed", "ol_log", "or_log", "rank_log",
                          "chars_log")}
    total = sum(cols.values())
    return {
        "columns": cols,
        "total_bytes": total,
        "efficient_bytes": 16 * len(spans),
        "overhead": total / max(1, 16 * len(spans)),
    }


class Counters:
    """Named monotonic counters + high-water and mean gauges for the
    replication and serving stacks (`net/`, `serve/`): frames
    sent/rejected, retries, buffer high-water — and the serve layer's
    admitted / rejected_* / evictions / restores counts plus the
    ``batch_fill_ratio`` mean gauge (`serve/batcher.py`).

    The wire-layer analog of the reference's counting-allocator
    instrumentation (`src/alloc.rs:13-50`): cheap increments everywhere,
    one ``summary()`` dump. ``incr`` counts events; ``hiwater`` keeps the
    max of a gauge (e.g. causal-buffer pending size); ``sample`` feeds a
    running mean/min/max (e.g. per-tick batch fill ratio), reported as
    ``<name>_mean``/``<name>_min``/``<name>_max`` with its sample count
    as ``<name>_samples`` — means alone hid the PR-6 ``ops_per_step``
    skew, so the extremes now always ride along (ISSUE 8).  For full
    distributions (percentiles) use ``obs.registry.MetricsRegistry``,
    which extends this class with bounded histograms.
    """

    def __init__(self) -> None:
        self._counts: Dict[str, int] = {}
        self._hiwater: Dict[str, int] = {}
        # name -> (total, count, min, max)
        self._samples: Dict[str, Tuple[float, int, float, float]] = {}

    def incr(self, name: str, by: int = 1) -> None:
        self._counts[name] = self._counts.get(name, 0) + by

    def hiwater(self, name: str, value: int) -> None:
        if value > self._hiwater.get(name, 0):
            self._hiwater[name] = value

    def sample(self, name: str, value: float) -> None:
        v = float(value)
        total, count, vmin, vmax = self._samples.get(
            name, (0.0, 0, float("inf"), float("-inf")))
        self._samples[name] = (total + v, count + 1,
                               min(vmin, v), max(vmax, v))

    def mean(self, name: str) -> float:
        total, count, _vmin, _vmax = self._samples.get(
            name, (0.0, 0, 0.0, 0.0))
        return total / count if count else 0.0

    def _sample_stats(self, name: str) -> Tuple[float, int, float, float]:
        """(total, count, min, max) of one sample gauge (zeros when
        empty) — the registry exporters read through this."""
        total, count, vmin, vmax = self._samples.get(
            name, (0.0, 0, 0.0, 0.0))
        if not count:
            return 0.0, 0, 0.0, 0.0
        return total, count, vmin, vmax

    def get(self, name: str) -> int:
        return self._counts.get(name, self._hiwater.get(name, 0))

    def summary(self) -> Dict[str, float]:
        out: Dict[str, float] = dict(self._counts)
        for k, v in self._hiwater.items():
            out[k] = v
        for k in self._samples:
            total, count, vmin, vmax = self._sample_stats(k)
            out[f"{k}_mean"] = round(total / count, 6) if count else 0.0
            out[f"{k}_samples"] = count
            out[f"{k}_min"] = vmin
            out[f"{k}_max"] = vmax
        return out


def percentiles(samples, points=(50, 99)) -> Dict[str, float]:
    """Nearest-rank percentiles of a sample list as ``{"p50": ..}``.

    The serve layer's admission→applied latency summary (and the bench
    rows') share this one definition so p99 can't silently mean
    different things in different reports. Empty input -> zeros.
    """
    out: Dict[str, float] = {}
    ss = sorted(float(s) for s in samples)
    for p in points:
        if not ss:
            out[f"p{p}"] = 0.0
        else:
            idx = min(len(ss) - 1, int(round((len(ss) - 1) * p / 100.0)))
            out[f"p{p}"] = ss[idx]
    return out


def measured_hbm_bytes():
    """(bytes, reason) live device allocation from the runtime.

    Fills bench rows' ``hbm_bytes_measured`` from
    ``jax.local_devices()[0].memory_stats()`` where the backend exposes
    it (TPU, and newer CPU runtimes); returns ``(None, reason)`` with a
    human-readable reason otherwise, so rows carry an explanation
    instead of a bare null (VERDICT r5 missing #3 / next #5).
    """
    try:
        import jax

        dev = jax.local_devices()[0]
    except Exception as e:  # backend down / not initialized
        return None, f"no device backend available ({type(e).__name__})"
    stats = None
    try:
        stats = dev.memory_stats()
    except Exception:
        stats = None
    if not stats:
        return None, (f"{dev.platform} runtime exposes no device "
                      f"memory_stats on this platform")
    # Usage counters ONLY: bytes_limit is device capacity, not live
    # allocation — reporting it as "measured" would be off by orders of
    # magnitude.
    for key in ("bytes_in_use", "peak_bytes_in_use"):
        if key in stats:
            return int(stats[key]), None
    return None, (f"memory_stats present but carries no usage counter "
                  f"(keys: {sorted(stats)[:8]})")


def causal_buffer_stats(buf) -> dict:
    """Introspection snapshot of a ``parallel.causal.CausalBuffer`` for
    the session layer and dashboards: pending count and high-water,
    duplicate-drop / eviction counters, per-agent watermark gaps."""
    return {
        "pending": buf.pending,
        "high_water": buf.high_water,
        "duplicates_dropped": buf.duplicates_dropped,
        "evictions": buf.evictions,
        "watermarks": buf.watermarks(),
        "agent_gaps": buf.gap_stats(),
    }


class Throughput:
    """Ops/sec accumulator for bench loops.

    >>> meter = Throughput()
    >>> with meter.measure(ops=1000): ...   # doctest: +SKIP
    >>> meter.ops_per_sec                   # doctest: +SKIP
    """

    def __init__(self) -> None:
        self.ops = 0
        self.seconds = 0.0
        self.samples = 0

    def add(self, ops: int, seconds: float) -> None:
        self.ops += ops
        self.seconds += seconds
        self.samples += 1

    def measure(self, ops: int):
        meter = self

        class _Ctx:
            def __enter__(self):
                self.t0 = time.perf_counter()
                return self

            def __exit__(self, *exc):
                meter.add(ops, time.perf_counter() - self.t0)
                return False

        return _Ctx()

    @property
    def ops_per_sec(self) -> float:
        return self.ops / self.seconds if self.seconds else 0.0

    def summary(self) -> dict:
        return {"ops": self.ops, "seconds": round(self.seconds, 6),
                "ops_per_sec": round(self.ops_per_sec, 1),
                "samples": self.samples}


def run_stats(res, doc_index: int = 0) -> dict:
    """Device RUN-state health metrics for the block engines' results
    (``RleResult``/``RleMixedResult``) — the `print_stats` family
    (`root.rs:293-326`) read directly off the run representation:

    - ``run_rows`` / ``live_rows`` / ``tombstone_rows``
    - ``chars`` / ``live_chars`` and ``chars_per_run`` (the compaction
      ratio that decides VMEM plane sizes, PERF.md §3)
    - ``blocks_used`` / ``block_fill`` (occupied rows / (blocks * K) —
      the leaf-split half-fullness the 2.5x capacity budget covers)
    - run-length histogram (`split_list/mod.rs:418`'s "compacts to N")
    """
    K = res.block_k
    ordc = np.asarray(res.ordp)[:, doc_index]
    lenc = np.asarray(res.lenp)[:, doc_index]
    rows = np.asarray(res.rows)[:, doc_index]
    nlog = int(np.asarray(res.meta)[0, doc_index])
    blk = np.asarray(res.blkord)[:, doc_index]
    o_parts, l_parts = [], []
    for sl in range(nlog):
        b, r = int(blk[sl]), int(rows[sl])
        o_parts.append(ordc[b * K: b * K + r])
        l_parts.append(lenc[b * K: b * K + r])
    o = (np.concatenate(o_parts) if o_parts else np.zeros(0, np.int32))
    ln = (np.concatenate(l_parts) if l_parts else np.zeros(0, np.int32))
    live = o > 0
    spans = [(0, 0, 0, int(l if lv else -l)) for l, lv in zip(ln, live)]
    total_rows = int(len(o))
    return {
        "run_rows": total_rows,
        "live_rows": int(live.sum()),
        "tombstone_rows": int((~live & (o != 0)).sum()),
        "chars": int(ln.sum()),
        "live_chars": int(ln[live].sum()),
        "chars_per_run": round(float(ln.sum()) / max(total_rows, 1), 2),
        "blocks_used": nlog,
        "block_fill": round(total_rows / max(nlog * K, 1), 3),
        "run_histogram": span_histogram(spans),
    }


def print_stats(doc, detailed: bool = False) -> None:
    """Human-readable dump (`doc.rs:492-498` analog). Downloads a device
    doc once and shares the spans across both stat passes."""
    spans = _spans_of(doc)
    d = doc_stats(doc, spans=spans)
    m = memory_stats(doc, spans=spans)
    print(f"doc: {d['items']} items ({d['live']} live, "
          f"{d['tombstones']} tombstones), {d['merged_spans']} merged spans "
          f"(compaction {d['compaction']:.1f}x)")
    print(f"  memory: {m['total_bytes']:,} B actual vs "
          f"{m['efficient_bytes']:,} B compacted "
          f"({m['overhead']:.1f}x overhead)")
    if detailed:
        print(f"  span histogram: {d['span_histogram']}")
        for k in ("deletes_entries", "double_delete_entries", "txn_entries"):
            if k in d:
                print(f"  {k}: {d[k]}")


def device_identity() -> dict:
    """The default backend of THIS process, as JAX reports it: every
    bench row carries it, so a CPU row can never pass for a chip row."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}
