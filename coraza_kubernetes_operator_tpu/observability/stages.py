"""One stage record per device window, stamped where the work happens.

A window's host time between "request read" and "reply written" is
split into the named stages of :data:`STAGES`. The code that does a
stage's work takes one ``time.monotonic()`` stamp when it starts and one
when it ends, on the window's :class:`WindowStages` record; no stage
time is computed from another. The record then feeds three readers and
nothing else measures a stage:

- ``/waf/v1/stats`` block ``stages`` and the
  ``cko_window_stage_seconds{stage,lane}`` histogram
  (:class:`StageStats`): cumulative count, sum and buckets, so that a
  reader takes after − before and gets exactly its own interval;
- the flight recorder: ``MicroBatcher._trace_group`` copies the stamps
  onto the traced requests' ``SpanContext``\\ s
  (:meth:`WindowStages.trace_onto`);
- the profiler's trace: every stage is also a
  ``jax.profiler.TraceAnnotation("cko.<stage>", window_id=, lane=)``
  entered and left at the same two points, so a ``jax.profiler`` capture
  carries the program's spans on the clock of ``XLA Ops``. With no
  session active that is the TraceMe fast path (one atomic read).

The record is owned by one thread at a time (event loop → lane thread →
collector → event loop; the hand-offs are queues), so stamping is plain
list appends. A stage that begins on one thread and ends on another
(``queue_wait``, ``inflight_wait``, ``loop_hop``) shows in the profiler
on the thread that ended it.
"""

from __future__ import annotations

import itertools
import threading
import time

from .metrics import Histogram

# In the order a window passes them. ``lane_wait`` counts requests, every
# other stage windows.
STAGES = (
    "lane_wait",
    "queue_wait",
    "depth_wait",
    "route",
    "assemble",
    "tier_enqueue",
    "prefilter_wait",
    "prefilter_confirm",
    "post_enqueue",
    "inflight_wait",
    "readback_wait",
    "decode",
    "resolve",
    "loop_hop",
    "reply_write",
)
WINDOW_WALL = "window_wall"
# What BatcherStats.host_stage_s / device_stage_s (cko_host_stage_s,
# cko_device_stage_s) are the sums of.
HOST_STAGES = frozenset(
    ("assemble", "tier_enqueue", "prefilter_wait", "prefilter_confirm", "post_enqueue")
)
DEVICE_STAGES = frozenset(("readback_wait", "decode"))

# Log-spaced, 2 per octave-and-a-half: 10 us .. 10 s.
STAGE_BUCKETS = (
    1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 1e-2,
    2.5e-2, 5e-2, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)

# Flight-recorder chain span -> the record's stages it spans, first to
# last (observability/tracing.py PIPELINE_CHAIN keeps its eight names;
# the stages appear as children inside them).
CHAIN_STAGES = {
    "queue": ("lane_wait", "queue_wait", "depth_wait"),
    "assemble": ("route", "assemble"),
    "dispatch": ("tier_enqueue", "prefilter_wait", "prefilter_confirm", "post_enqueue"),
    "readback": ("readback_wait",),
    "decode": ("decode",),
}
_CHAIN_TRACK = {"readback": "device", "decode": "device"}

_ANNOTATION = {s: f"cko.{s}" for s in STAGES}
_window_ids = itertools.count(1)  # process-wide; next() is atomic under the GIL
# The record of the window a thread is dispatching: the batcher binds it
# around its calls into the engine (``WindowStages.bound``), the engine
# stamps its stages on it (``current``). Engines keep their two-stage
# signature, which stubs and direct callers share.
_bound = threading.local()


def current() -> "WindowStages":
    """The record bound to this thread, or a fresh one that nothing
    reads (a direct ``engine.prepare`` call: canary, rollout, tests)."""
    return getattr(_bound, "rec", None) or WindowStages("direct")


_trace_annotation = None


def _annotate(stage: str, window_id: int, lane: str):
    """Enter the profiler annotation of one stage. jax is imported at
    the first window, not with this module: the package's JAX-free
    parents import ``observability``."""
    global _trace_annotation
    if _trace_annotation is None:
        from jax.profiler import TraceAnnotation

        _trace_annotation = TraceAnnotation
    ann = _trace_annotation(_ANNOTATION[stage], window_id=window_id, lane=lane)
    ann.__enter__()
    return ann


class _Bound:
    """``with record.bound():`` — the engine calls inside stamp on it."""

    __slots__ = ("_rec",)

    def __init__(self, rec: "WindowStages"):
        self._rec = rec

    def __enter__(self):
        _bound.rec = self._rec

    def __exit__(self, *_exc):
        _bound.rec = None
        return False


class _Stage:
    """``with record.stage(name):`` — one lexical stage."""

    __slots__ = ("_rec", "_name")

    def __init__(self, rec: "WindowStages", name: str):
        self._rec, self._name = rec, name

    def __enter__(self):
        self._rec.begin(self._name)

    def __exit__(self, *_exc):
        self._rec.end(self._name)
        return False


class WindowStages:
    """The stamps of one window: ``spans`` is the list of
    ``(stage, t_start, t_end)`` in the order the stages ended. A stage
    may hold several spans (one ``prefilter_wait`` per tier, one engine
    pass per model group); its time is their sum."""

    __slots__ = (
        "window_id", "lane", "n_req", "reads", "spans", "_open", "t_first",
        "t_last", "aborted_at", "closed", "replies_left", "ruleset",
    )

    def __init__(self, lane: str, n_req: int = 0):
        self.window_id = next(_window_ids)
        self.lane = lane
        self.n_req = n_req
        # [t_read, requests it delivered], one entry per socket read
        # (see close_lane).
        self.reads: list[list] = []
        self.spans: list[tuple[str, float, float]] = []
        self._open: dict[str, tuple] = {}
        self.t_first = 0.0  # first stamp of the window (start of window_wall)
        self.t_last = 0.0  # newest stamp
        self.aborted_at: str | None = None
        self.closed = False
        self.replies_left = 0
        # Rule-set uuid of the engine group the window was formed for
        # (a frontend that trusts the tenant header); None otherwise.
        self.ruleset: str | None = None

    # -- stamping ----------------------------------------------------------

    def begin(self, stage: str, t: float | None = None) -> float:
        if t is None:
            t = time.monotonic()
        if self.closed:  # abandoned window whose readback lands late
            return t
        if not self.t_first:
            self.t_first = t
        self._open[stage] = (t, _annotate(stage, self.window_id, self.lane))
        self.t_last = t
        return t

    def end(self, stage: str, t: float | None = None) -> float:
        if t is None:
            t = time.monotonic()
        entry = self._open.pop(stage, None)
        if entry is None:  # closed under it (abort on another thread)
            return t
        t0, ann = entry
        ann.__exit__(None, None, None)
        self.spans.append((stage, t0, t))
        self.t_last = t
        return t

    def next(self, prev: str, stage: str) -> float:
        """``prev`` ends and ``stage`` begins on one stamp."""
        return self.begin(stage, self.end(prev))

    def stage(self, name: str) -> _Stage:
        return _Stage(self, name)

    def bound(self) -> _Bound:
        return _Bound(self)

    def close_lane(self, n_req: int) -> None:
        """The lane's window closes with ``n_req`` requests: every one's
        ``lane_wait`` ends, and as many replies are owed (the frontend
        counts ``replies_left`` down and closes the record at 0). The
        frontend appended ``[t_read, index of the first request it
        delivered]`` to ``reads`` at every new socket read; the indexes
        become counts here."""
        self.end("lane_wait")
        self.n_req = self.replies_left = n_req
        reads = self.reads
        for i, read in enumerate(reads):
            nxt = reads[i + 1][1] if i + 1 < len(reads) else n_req
            read[1] = nxt - read[1]

    def opened(self, stage: str) -> float | None:
        """The start stamp of a stage that is running, else None."""
        entry = self._open.get(stage)
        return entry[0] if entry else None

    # -- reading -----------------------------------------------------------

    def total(self, stages, since: int = 0) -> float:
        """Seconds in ``stages`` over the spans from index ``since`` on."""
        return sum(t1 - t0 for s, t0, t1 in self.spans[since:] if s in stages)

    def durations(self) -> dict[str, float]:
        out: dict[str, float] = {}
        for s, t0, t1 in self.spans:
            out[s] = out.get(s, 0.0) + (t1 - t0)
        return out

    def trace_onto(self, contexts, stamped: list) -> None:
        """Copy stamps onto the ``SpanContext`` of each traced request:
        ``stamped`` is the part of ``spans`` that concerns them (the
        window's queue stages and their group's engine stages). The
        chain spans ``queue`` … ``decode`` run from the first start to
        the last end of the stages they hold and carry ``window_id``;
        ``queue`` starts at the request's own entry into the pipeline
        (its submit or accept stamp). The stages themselves ride along
        as one shared list (``SpanContext.window``) that the export
        expands into ``cko.<stage>`` children, so a traced request costs
        five events whatever its window holds. Where a chain span's
        stages were not stamped (a stub engine), it collapses onto the
        previous span's end so that the chain stays complete."""
        chain = []
        t = None
        for name, stages in CHAIN_STAGES.items():
            found = [(t0, t1) for s, t0, t1 in stamped if s in stages]
            t0, t1 = (found[0][0], found[-1][1]) if found else (t, t)
            chain.append((name, t0, t1, _CHAIN_TRACK.get(name, "pipeline")))
            t = t1
        args = {"window_id": self.window_id, "lane": self.lane, "window": self.n_req}
        if self.ruleset is not None:
            args["ruleset"] = self.ruleset
        window = (self.window_id, stamped)
        for ctx in contexts:
            t_in = ctx.t_submit or ctx.t_accept
            for name, t0, t1, track in chain:
                if t1 is None:  # nothing stamped yet: the request's own entry
                    t0 = t1 = t_in
                elif name == "queue":
                    t0 = min(t_in, t1)
                ctx.event(name, t0, t1, track=track, args=args)
            ctx.window = window

    # -- leaving -----------------------------------------------------------

    def _shut(self) -> bool:
        if self.closed:
            return False
        self.closed = True
        for stage in list(self._open):
            self.end(stage)
        return True

    def abort(self, stats: "StageStats | None") -> None:
        """The window leaves the promoted path here (fallback, shed,
        breaker, abandon, error): counted under the stage it had
        reached, its stage times are not observed."""
        last = next(reversed(self._open), None) or (
            self.spans[-1][0] if self.spans else STAGES[0]
        )
        if self._shut():
            self.aborted_at = last
            if stats is not None:
                stats.aborted(last, self.lane)

    def close(self, stats: "StageStats | None") -> None:
        """The window is answered: observe every stage and the wall."""
        if self._shut() and stats is not None:
            stats.observe(self)


class StageStats:
    """Cumulative per-(stage, lane) count, sum and histogram of window
    stage seconds, plus the count of windows that left the promoted
    path at each stage. ``histogram`` holds the series: the sidecar
    hands in its registry's ``cko_window_stage_seconds`` (labels
    ``stage``, ``lane``; buckets ``STAGE_BUCKETS``), a bare batcher
    keeps one of its own."""

    def __init__(self, histogram: Histogram | None = None):
        self.histogram = histogram or Histogram(
            "cko_window_stage_seconds", "", ("stage", "lane"), buckets=STAGE_BUCKETS
        )
        self._aborted: dict[tuple[str, str], int] = {}
        self._lock = threading.Lock()  # both lanes may abort at once

    def observe(self, rec: WindowStages) -> None:
        observe, lane = self.histogram.observe_key, rec.lane
        t_close = None
        for stage, seconds in rec.durations().items():
            if stage == "lane_wait":
                t_close = next(t1 for s, _t0, t1 in rec.spans if s == "lane_wait")
                continue
            observe((stage, lane), seconds)
        if t_close is not None:
            for t_read, n in rec.reads:
                observe(("lane_wait", lane), t_close - t_read, n)
        observe((WINDOW_WALL, lane), rec.t_last - rec.t_first)

    def aborted(self, stage: str, lane: str) -> None:
        key = (stage, lane)
        with self._lock:
            self._aborted[key] = self._aborted.get(key, 0) + 1

    def snapshot(self) -> dict:
        """``{"buckets_s": [...], <stage>: {<lane>: {count, sum_s,
        buckets}, ..., "aborted": n}}`` — ``buckets`` are per-bucket
        counts (not cumulative), the last one the overflow."""
        out: dict = {"buckets_s": list(self.histogram.buckets)}
        for (stage, lane), series in self.histogram.snapshot().items():
            out.setdefault(stage, {"aborted": 0})[lane] = series
        with self._lock:
            aborted = dict(self._aborted)
        for (stage, _lane), n in aborted.items():
            out.setdefault(stage, {"aborted": 0})["aborted"] += n
        return out
