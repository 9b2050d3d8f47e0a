"""Logical-clock-first span/event tracer (ISSUE 8 tentpole, part 1).

The backbone is CAUSALITY, not wall time: every event is stamped with
the server's logical tick and a per-tracer monotonic sequence number,
and every wall-clock measurement lives in a segregated ``"w"`` sub-dict
— so the *logical* projection of a trace is a pure function of the
seeded workload.  Two same-seed loadgen runs emit byte-identical
logical JSONL streams (``tests/test_obs_trace.py`` pins this), which is
exactly the property the serve twin-check's cross-backend bit-identity
proof needs from its observability layer: the trace can be diffed
between a good and a bad run to find the first diverging *event*, not
just the diverged end state.

Like automerge's binary document format (PAPERS.md), the trace is a
versioned, schema-checked artifact: every stream opens with a header
event carrying ``TRACE_SCHEMA_VERSION``, every kind declares its
required logical fields in ``EVENT_SCHEMA``, and ``validate_event``
refuses unknown kinds or missing fields — ad-hoc dict drift (how the
PR-3..7 report dicts grew apart) cannot happen silently here.

Event kinds cover the serving loop end to end:

===================  =======================================================
kind                 emitted by / meaning
===================  =======================================================
``trace.header``     stream start: schema version
``tick.drain``       batcher, per shard: events drained + steps compiled
``tick.fuse``        batcher, per lane doc: pre/post-fusion step counts
``tick.capacity``    batcher, per shard: lane streams probed / degraded
``tick.device``      batcher, per shard: one [S,B] device pass (bucket,
                     lanes, steps; dispatch wall in ``w``)
``tick.barrier``     batcher, per shard: device sync (wall in ``w``)
``device.compile``   batcher: a step-bucket shape compiled for the first
                     time (steady state must stop emitting these)
``apply``            batcher, per applied event: doc, author agent, seq,
                     item count — the event-level audit log the
                     divergence post-mortem joins against
``residency.evict``  residency: doc checkpointed out (kind, bytes)
``residency.restore`` residency: doc restored from its checkpoint
``residency.degrade`` residency: lane-capacity overflow -> host-only
``admission.reject`` admission: typed refusal (reason)
``codec.reject``     router: a frame failed ``net/codec`` validation
``divergence``       router/verifier: equal watermarks, unequal digests
                     (or a twin/lane bit-identity mismatch)
``resync.round``     session/router: anti-entropy round (wants emitted)
``compiler.merge``   replay path (``ops.batch.merge_patches``):
                     keystroke patches merged into runs
``compiler.compile`` ``ops.batch.compile_local_patches``: runs compiled
                     to op tensors
``compiler.fuse``    ``ops.batch.fuse_steps``: steps fused to ``fuse_w``
``compiler.remote``  a whole remote stream compiled to op tensors for
                     a replay program (``compile_remote_txns``, timed by
                     the caller that builds the program, as the
                     benchmark's merge driver does: the serve path's
                     per-txn calls are not spans)
``compiler.prefill`` ``ops.rle_mixed.make_replayer_rle_mixed``: the
                     by-order logs prefilled (``prefill_logs``) and packed
                     into the kernel's origin and rank tables
``rle.stage``        ``ops.rle.make_replayer_rle`` /
                     ``ops.rle_mixed.make_replayer_rle_mixed`` /
                     ``ops.rle_lanes.make_replayer_lanes_blocked``: op
                     columns padded and put on the device
``rle.build``        an ``ops.rle``, ``ops.rle_mixed`` or blocked
                     ``ops.rle_lanes`` replayer's first call: trace,
                     lower, compile (or compile-cache load), first
                     enqueue
``rle.dispatch``     every later call: kernel enqueue + per-group unpack
                     (wall and thread CPU; the unpack can wait on the
                     device)
``flow.*``           per-op provenance spans (obs/flow.py): emit /
                     frame / reject / buffer / ready / apply — one
                     ``(agent, seq)`` span's journey through the
                     serving loop, agent-sampled
===================  =======================================================

The ``compiler.*``/``rle.*`` kinds and the batcher's ``tick.drain``/
``tick.device``/``tick.barrier`` walls (``SPAN_KINDS``) are timed with
``span``: each region is a ``jax.profiler.TraceAnnotation`` on the
``/host:CPU`` plane of any profile (the device trace's clock), and its
host-clock wall, the executables compiled inside it and, for
``rle.build``/``rle.dispatch``, its thread CPU time are added to
process-wide per-kind totals (``span_totals``), with or without a
tracer.
"""
from __future__ import annotations

import functools
import json
import time
from typing import Callable, Dict, List, Optional, Tuple

TRACE_SCHEMA_VERSION = 4  # v2: journal.segment /
#                           journal.refuse / recovery.replay /
#                           chaos.crash event kinds; v3: the replay
#                           path's span kinds, ``profile`` removed;
#                           v4: the remote path's compiler spans

# kind -> required logical field names (beyond the envelope "i"/"t"/"k").
# Extra fields are allowed — the schema pins the floor, not the ceiling.
EVENT_SCHEMA: Dict[str, Tuple[str, ...]] = {
    "trace.header": ("schema",),
    "tick.drain": ("shard", "events", "steps"),
    "tick.fuse": ("doc", "steps_in", "steps_out"),
    "tick.capacity": ("shard", "probed", "degraded"),
    "tick.device": ("shard", "bucket", "lanes", "steps"),
    "tick.barrier": ("shard",),
    "device.compile": ("shard", "bucket"),
    "apply": ("doc", "ev", "agent", "seq", "n"),
    "residency.evict": ("doc", "ckpt", "bytes"),
    "residency.restore": ("doc",),
    "residency.degrade": ("doc", "reason"),
    "admission.reject": ("reason",),
    "codec.reject": ("err",),
    "divergence": ("doc",),
    "resync.round": ("wants",),
    # The replay path's spans (``SPAN_KINDS``): host compiler, program
    # build and dispatch.  They carry no logical fields.
    "compiler.merge": (),
    "compiler.compile": (),
    "compiler.fuse": (),
    "compiler.remote": (),
    "compiler.prefill": (),
    "rle.stage": (),
    "rle.build": (),
    "rle.dispatch": (),
    # Per-op provenance spans (ISSUE 11, obs/flow.py): one op's journey
    # on the logical tick axis.  Remote spans carry (agent, seq, n);
    # local edits carry a per-doc ordinal ``lk`` until the oracle
    # realizes their seq at apply.  The floor requires doc+agent — seq
    # vs lk is the span-identity split the flow module owns.
    # Durability + recovery (ISSUE 16, serve/journal.py + chaos.py).
    "journal.segment": ("shard", "seg"),
    "journal.refuse": ("segment", "offset", "reason"),
    # Reopen-time repair: a refused suffix truncated/quarantined to a
    # ``.refused`` sidecar so post-recovery segments survive the next
    # scan (same fields as the refusal it repairs).
    "journal.repair": ("segment", "offset", "reason"),
    "recovery.replay": ("records", "ops", "ticks"),
    "chaos.crash": ("phase",),
    "flow.emit": ("doc", "agent", "n"),
    "flow.frame": ("doc", "agent", "seq", "n", "frame"),
    "flow.reject": ("doc", "agent", "reason"),
    "flow.buffer": ("doc", "agent", "seq", "n", "state"),
    "flow.ready": ("doc", "agent", "seq", "n"),
    "flow.apply": ("doc", "agent", "seq", "n", "mode"),
}

#: Every kind the program times with ``span``: the replay path's, in the
#: order the work happens, then the batcher's tick phases.
SPAN_KINDS = ("compiler.merge", "compiler.compile", "compiler.fuse",
              "compiler.remote", "compiler.prefill",
              "rle.stage", "rle.build", "rle.dispatch",
              "tick.drain", "tick.device", "tick.barrier")

# The one reserved envelope key wall-clock data lives under; stripping
# it is the whole logical projection.
WALL_KEY = "w"
_ENVELOPE = ("i", "t", "k")


def validate_event(ev: dict) -> None:
    """Raise ``ValueError`` unless ``ev`` is a schema-valid trace event:
    known kind, full envelope, every required logical field present, and
    wall-clock data only under the reserved ``"w"`` key."""
    for key in _ENVELOPE:
        if key not in ev:
            raise ValueError(f"trace event missing envelope field {key!r}")
    kind = ev["k"]
    req = EVENT_SCHEMA.get(kind)
    if req is None:
        raise ValueError(f"unknown trace event kind {kind!r}")
    missing = [f for f in req if f not in ev]
    if missing:
        raise ValueError(f"trace event {kind!r} missing fields {missing}")
    wall = ev.get(WALL_KEY)
    if wall is not None and not isinstance(wall, dict):
        raise ValueError(f"wall field {WALL_KEY!r} must be a dict")


def event_line(ev: dict, logical_only: bool = False) -> str:
    """One JSONL line for an event — sorted keys and fixed separators so
    equal logical content is equal bytes."""
    if logical_only and WALL_KEY in ev:
        ev = {k: v for k, v in ev.items() if k != WALL_KEY}
    return json.dumps(ev, sort_keys=True, separators=(",", ":"))


# kind -> [count, total_s, max_s, compiles, cpu_s] of every span this
# process closed (``cpu_s`` None until a span of the kind reads it).
_TOTALS: Dict[str, list] = {}

# Executables JAX has compiled from scratch since the first span opened:
# every backend compile, less those loaded from the persistent compile
# cache (a cache hit is reported as a compile too).
_COMPILES = [0]
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def _on_jax_event(event: str, *args, **kwargs) -> None:
    if event == _BACKEND_COMPILE:
        _COMPILES[0] += 1
    elif event == _CACHE_HIT:
        _COMPILES[0] -= 1


@functools.lru_cache(maxsize=None)
def _jax_hooks():
    """``TraceAnnotation``, with the compile counter listening (once)."""
    import jax
    from jax.profiler import TraceAnnotation

    jax.monitoring.register_event_listener(_on_jax_event)
    jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
    return TraceAnnotation


class _Span:
    """One timed region (see ``span``).  After exit, ``t0``/``t1`` are
    its ``perf_counter`` bounds and ``wall_s`` its wall."""

    __slots__ = ("tracer", "kind", "fields", "cpu", "t0", "t1", "c0",
                 "k0", "_ann")

    def __init__(self, kind: str, tracer: Optional["Tracer"], cpu: bool,
                 fields: dict):
        self.tracer = tracer
        self.kind = kind
        self.cpu = cpu
        self.fields = fields

    @property
    def wall_s(self) -> float:
        return self.t1 - self.t0

    def __enter__(self) -> "_Span":
        self._ann = ann = _jax_hooks()(self.kind)
        ann.__enter__()
        self.k0 = _COMPILES[0]
        if self.cpu:
            self.c0 = time.thread_time()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.t1 = t1 = time.perf_counter()
        cpu = time.thread_time() - self.c0 if self.cpu else None
        self._ann.__exit__(exc_type, exc, tb)
        wall = t1 - self.t0
        compiles = _COMPILES[0] - self.k0
        tot = _TOTALS.get(self.kind)
        if tot is None:
            _TOTALS[self.kind] = [1, wall, wall, compiles, cpu]
        else:
            tot[0] += 1
            tot[1] += wall
            if wall > tot[2]:
                tot[2] = wall
            tot[3] += compiles
            if cpu is not None:
                tot[4] = cpu if tot[4] is None else tot[4] + cpu
        tr = self.tracer
        if tr is not None and tr.enabled:
            tr.event(self.kind, wall={"ms": round(wall * 1e3, 3)},
                     **self.fields)
        return False


def span(kind: str, tracer: Optional["Tracer"] = None, *, cpu: bool = False,
         **fields) -> _Span:
    """``with span("rle.dispatch"): ...`` times one region of the
    program three ways: a ``jax.profiler.TraceAnnotation`` named
    ``kind`` (on the profiler's host plane, on the device trace's
    clock, whenever a profile is being taken), the process's per-kind
    totals (always), and -- with an enabled ``tracer`` -- the kind's
    event at exit, ``fields`` logical and the wall under ``"w"``.
    ``cpu=True`` also reads the calling thread's CPU time, which leaves
    out the region's waits (on the device, say); it costs two more
    clock reads.  ``kind`` must be in ``EVENT_SCHEMA``."""
    return _Span(kind, tracer, cpu, fields)


def span_totals() -> Dict[str, dict]:
    """``{kind: {"count", "total_s", "max_s", "compiles", "cpu_s"}}``
    over every span this process closed since the last
    ``reset_span_totals``: ``compiles`` counts the executables JAX
    compiled from scratch inside them (not those loaded from the
    persistent compile cache), and ``cpu_s`` is the thread CPU time of
    those opened with ``cpu=True`` (None if none was)."""
    return {k: {"count": c, "total_s": t, "max_s": m, "compiles": n,
                "cpu_s": u}
            for k, (c, t, m, n, u) in _TOTALS.items()}


def reset_span_totals() -> None:
    _TOTALS.clear()


class Tracer:
    """Bounded event tracer for one server (or one test harness).

    - ``ring`` holds the last N events for the flight recorder;
    - ``keep_all=True`` additionally retains the full stream in memory
      (the determinism tests read it back via ``logical_bytes``);
    - ``path`` streams every event to a JSONL file as it happens;
    - ``rotate_bytes`` caps each stream segment: when the active file
      passes the cap it is closed and the stream continues in
      ``<path>.1``, ``<path>.2``, ... (``segment_paths`` lists them in
      order) — a long loadgen/cluster run no longer grows ONE unbounded
      JSONL, and segments concatenate back to the identical stream
      (``obs.analyze.load_events`` accepts the segment list);
    - ``enabled=False`` turns every entry point into a cheap no-op
      (the overhead-probe baseline arm).

    Events are dicts with a three-field envelope — ``i`` (monotonic
    sequence), ``t`` (logical tick, set via ``set_tick``), ``k`` (kind)
    — plus the kind's logical fields and an optional ``"w"`` wall dict.
    """

    def __init__(self, *, enabled: bool = True, ring: int = 512,
                 keep_all: bool = False, path: Optional[str] = None,
                 rotate_bytes: Optional[int] = None,
                 validate: bool = True):
        from collections import deque

        self.enabled = enabled
        self.ring = deque(maxlen=max(1, ring))
        self.keep_all = keep_all
        self.events: List[dict] = []
        self.validate = validate
        self.seq = 0
        self.tick = 0
        # Line-buffered: the events adjacent to a crash are exactly the
        # ones a flight recorder exists to preserve — they must be on
        # disk, not in a stdio buffer, when the process dies.
        self._path = path
        self.rotate_bytes = rotate_bytes
        self.segment_paths: List[str] = []
        self._segment_bytes = 0
        self._file = None
        if enabled and path:
            self._file = open(path, "w", buffering=1)
            self.segment_paths.append(path)
        self._subscribers: List[Callable[[dict], None]] = []
        if enabled:
            self.event("trace.header", schema=TRACE_SCHEMA_VERSION)

    # -- emit ----------------------------------------------------------------

    def set_tick(self, tick: int) -> None:
        self.tick = tick

    def subscribe(self, fn: Callable[[dict], None]) -> None:
        """Register a callback invoked on every event (the flight
        recorder taps ``apply`` events through this)."""
        self._subscribers.append(fn)

    def event(self, kind: str, wall: Optional[dict] = None,
              **fields) -> Optional[dict]:
        if not self.enabled:
            return None
        ev = {"i": self.seq, "t": self.tick, "k": kind}
        ev.update(fields)
        if wall:
            ev[WALL_KEY] = wall
        if self.validate:
            validate_event(ev)
        self.seq += 1
        self.ring.append(ev)
        if self.keep_all:
            self.events.append(ev)
        if self._file is not None:
            line = event_line(ev) + "\n"
            self._file.write(line)
            self._segment_bytes += len(line)
            # Size-capped segment rollover: rotation happens BETWEEN
            # events (a line is never split), so the concatenated
            # segments are byte-identical to an unrotated stream.
            if (self.rotate_bytes
                    and self._segment_bytes >= self.rotate_bytes):
                self._file.close()
                seg = f"{self._path}.{len(self.segment_paths)}"
                self._file = open(seg, "w", buffering=1)
                self.segment_paths.append(seg)
                self._segment_bytes = 0
        for fn in self._subscribers:
            fn(ev)
        return ev

    def span(self, kind: str, **fields) -> _Span:
        """``with tracer.span("tick.barrier", shard=0): ...`` — the
        module's ``span`` with this tracer: one event at exit, wall
        duration under ``"w"``."""
        return span(kind, self, **fields)

    # -- read back -----------------------------------------------------------

    def last(self, n: int, doc: Optional[str] = None,
             shard: Optional[int] = None) -> List[dict]:
        """Last ``n`` ring events, newest last; ``doc``/``shard`` filter
        to events touching that doc or shard (envelope-level events with
        neither field always pass — they are context)."""
        out = []
        for ev in reversed(self.ring):
            if doc is not None and "doc" in ev and ev["doc"] != doc:
                continue
            if shard is not None and "shard" in ev and ev["shard"] != shard:
                continue
            out.append(ev)
            if len(out) >= n:
                break
        out.reverse()
        return out

    def logical_bytes(self) -> bytes:
        """The retained stream's logical projection as JSONL bytes —
        requires ``keep_all=True``; this is what two same-seed runs must
        agree on byte for byte."""
        assert self.keep_all, "logical_bytes needs Tracer(keep_all=True)"
        return ("\n".join(event_line(ev, logical_only=True)
                          for ev in self.events) + "\n").encode()

    def flush(self) -> None:
        if self._file is not None:
            self._file.flush()

    def close(self) -> None:
        if self._file is not None:
            self._file.close()
            self._file = None
