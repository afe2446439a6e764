"""StageTracer — sampling stage timer for the serving hot path.

The paper's §3 analysis works because lookup latency is decomposed into
stages; this tracer does the same for the serving read path (admission,
coalesce, cache probe, dispatch, device compute, resolve, value fetch)
at a cost low enough to leave on in production:

* **pre-bound handles** — each stage is resolved to a :class:`StageHandle`
  once at server construction.  Per batch the hot path does
  ``t0 = h.begin(); ...; h.end(t0)``: no dict lookup, no string
  formatting, no allocation.
* **tick sampling** — ``begin_tick`` arms the handles on every
  ``sample_every``-th tick only; an unarmed ``begin()`` returns 0.0 and
  ``end(0.0)`` is a no-op, so the unsampled cost is one attribute read
  and a float compare.
* **timeline** — sampled ticks append one per-stage-microseconds row to
  a bounded ring, the raw material for a paper-style stage-breakdown
  plot over time.
* **profiler spans** — while a ``jax.profiler`` session records,
  ``begin``/``end`` also open and close a ``repro.<stage>`` host span on
  the profiler's own trace, on *every* tick (the histograms keep their
  sampling), so device idle gaps can be named by the host stage they
  fall in.  ``begin_tick`` reads ``TraceMe.is_enabled()`` once per tick;
  with no session the per-call cost is one boolean read and nothing is
  allocated.  Spans open and close on the caller's thread, so they nest
  as the call sites do; a stage that crosses ticks (``compute``) is
  bound with ``span=False``.

Obs-off code paths hold :data:`NULL_HANDLE` / :data:`NULL_TRACER`
(null-object singletons) so instrumented call sites never branch on
"is obs enabled".
"""

from __future__ import annotations

import gc
import time
from collections import deque

from jax.profiler import TraceAnnotation

__all__ = ["EventLog", "GcPauseHook", "NullTracer", "StageHandle",
           "StageTracer", "NULL_HANDLE", "NULL_TRACER", "SPAN_PREFIX"]

_now = time.perf_counter
# host spans written on the profiler's trace: "repro.<stage>"
SPAN_PREFIX = "repro."
# TraceMe.is_enabled: True while a profiler session records host events
profiling = TraceAnnotation.is_enabled


class StageHandle:
    """Pre-bound timer for one stage.  ``begin`` returns a start stamp
    (0.0 when the tracer is not sampling this tick — ``end`` then
    no-ops), so cross-tick spans survive the sampling state changing
    between begin and end."""

    __slots__ = ("_tracer", "name", "hist", "count", "total_us", "tick_us",
                 "span", "_open")

    def __init__(self, tracer: "StageTracer", name: str, hist,
                 span: bool = True) -> None:
        self._tracer = tracer
        self.name = name
        self.hist = hist
        self.count = 0          # sampled observations
        self.total_us = 0.0     # sampled microseconds
        self.tick_us = 0.0      # accumulator drained by end_tick
        # profiler span name (None: this stage writes no span)
        self.span = SPAN_PREFIX + name if span else None
        self._open: list = []   # profiler spans begun and not yet ended

    def begin(self) -> float:
        tr = self._tracer
        if tr._prof and self.span is not None:
            sp = TraceAnnotation(self.span)
            sp.__enter__()
            self._open.append(sp)
        return _now() if tr._on else 0.0

    def end(self, t0: float) -> None:
        if self._open:
            self._open.pop().__exit__(None, None, None)
        if t0:
            dt = (_now() - t0) * 1e6
            self.count += 1
            self.total_us += dt
            self.tick_us += dt
            self.hist.observe(dt)


class StageTracer:
    def __init__(self, registry, sample_every: int = 4,
                 timeline_ticks: int = 512,
                 family: str = "server_stage_us") -> None:
        self._registry = registry
        self._family = family
        self.sample_every = max(int(sample_every), 1)
        self._on = False
        self._prof = False      # a profiler session records (per tick)
        self._n = 0
        self._stages: dict[str, StageHandle] = {}
        self._timeline: deque = deque(maxlen=int(timeline_ticks))
        self.ticks_seen = 0
        self.sampled_ticks = 0

    def stage(self, name: str, span: bool = True) -> StageHandle:
        """Pre-bind a handle for ``name`` (get-or-create).  Call once at
        construction time, never per batch.  ``span=False`` for a stage
        whose begin and end do not nest with the others (it crosses
        ticks): it writes no profiler span."""
        h = self._stages.get(name)
        if h is None:
            hist = self._registry.histogram(self._family, stage=name)
            h = self._stages[name] = StageHandle(self, name, hist, span)
        return h

    def begin_tick(self) -> int:
        """Arm (or disarm) the handles for this tick; returns the tick
        index to hand back to :meth:`end_tick`."""
        self._prof = profiling()
        self._on = self._n % self.sample_every == 0
        self._n += 1
        self.ticks_seen += 1
        if self._on:
            self.sampled_ticks += 1
        return self.ticks_seen - 1

    def end_tick(self, tick: int) -> None:
        if not self._on:
            return
        row = {"tick": int(tick)}
        nonzero = False
        for name, h in self._stages.items():
            if h.tick_us:
                row[name] = round(h.tick_us, 3)
                h.tick_us = 0.0
                nonzero = True
        if nonzero:
            self._timeline.append(row)

    def timeline(self) -> list[dict]:
        """Sampled per-tick stage breakdown rows, oldest first."""
        return list(self._timeline)


class _NullHandle:
    """Obs-off stand-in: same interface, zero state, no branches at the
    call site."""

    __slots__ = ()

    def begin(self) -> float:
        return 0.0

    def end(self, t0: float) -> None:
        pass


class NullTracer:
    __slots__ = ()
    _on = False

    def stage(self, name: str, span: bool = True) -> _NullHandle:
        return NULL_HANDLE

    def begin_tick(self) -> int:
        return 0

    def end_tick(self, tick: int) -> None:
        pass

    def timeline(self) -> list:
        return []


NULL_HANDLE = _NullHandle()
NULL_TRACER = NullTracer()


class GcPauseHook:
    """Python's garbage collector, timed where it runs: a
    ``gc.callbacks`` entry that adds each collection's pause to
    ``pause_us`` and counts it by generation.  While a profiler session
    records it also writes a ``repro.gc`` span from the collection's
    start to its stop, on whichever thread the collection ran.

    It holds no reference to its owner, so an owner that is never shut
    down can still be collected; ``weakref.finalize`` on the owner (or
    :meth:`remove`) takes the hook out of ``gc.callbacks``."""

    __slots__ = ("pause_us", "collections", "_t0", "_span")

    def __init__(self) -> None:
        self.pause_us = 0.0
        self.collections = [0, 0, 0]      # by generation
        self._t0 = 0.0
        self._span = None
        gc.callbacks.append(self)

    def remove(self) -> None:
        if self in gc.callbacks:
            gc.callbacks.remove(self)

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            if profiling():
                self._span = TraceAnnotation(SPAN_PREFIX + "gc")
                self._span.__enter__()
            self._t0 = _now()
            return
        if self._t0:
            self.pause_us += (_now() - self._t0) * 1e6
            self.collections[info["generation"]] += 1
            self._t0 = 0.0
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None


class EventLog:
    """Bounded log of maintenance-plane events (learn / GC / checkpoint),
    each carrying the CBA cost/benefit estimates that drove the decision
    — the paper's §4.4 inputs, made observable."""

    def __init__(self, cap: int = 1024) -> None:
        self._events: deque = deque(maxlen=int(cap))
        self.total = 0
        # optional ambient-context hook (set by Obs): a callable
        # returning fields merged under every entry — the serving stack
        # stamps `tick` and `trace_id` so a GC/learn/checkpoint decision
        # correlates with the causal spans of the tick it ran in
        self.stamp = None

    def log(self, kind: str, **fields) -> None:
        if self.stamp is None:
            self._events.append({"kind": kind, **fields})
        else:
            self._events.append({"kind": kind, **self.stamp(), **fields})
        self.total += 1

    def tail(self, n: int | None = None) -> list[dict]:
        ev = list(self._events)
        return ev if n is None else ev[-n:]

    def __len__(self) -> int:
        return len(self._events)
