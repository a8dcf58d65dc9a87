"""Structured tracing for the PERKS execution layers (DESIGN.md §11).

PERKS hides its wins in places host-side timers can't see — barrier
cadence, on-chip residency, HBM passes avoided — so the repo needs a
trace of *execution structure*, not just end-to-end seconds. This module
is a low-overhead :class:`Tracer` emitting typed span/event records for
the taxonomy the executor and services agree on (``CATEGORIES``):

    plan        candidate enumeration / ranking
    compile     a runner's first dispatch (trace, lower, compile, enqueue)
    dispatch    one execute()/runner invocation
    chunk       a later dispatch of a runner (one fused step chunk)
    barrier     a host sync: the read-back and the decision (scheduler)
    collective  a collective round projected/executed per barrier
    lane        lane admission / retirement / harvest (continuous batching)
    cache       one CacheDecision (bytes resident vs streamed)
    measure     an autotune timing sample (predicted vs measured)

Design points:

* **Injectable clock** — ``Tracer(clock=...)`` takes any ``() -> float``
  returning *seconds*; with a deterministic fake clock two identical runs
  produce byte-identical JSON-lines exports (asserted in
  ``tests/test_obs.py``), which is what makes traces diffable artifacts.
* **Spans reach the profiler** — every ``span`` (the
  :class:`NullTracer`'s included) opens a
  ``jax.profiler.TraceAnnotation`` named ``repro.<cat>`` around its body,
  with the span's name and args as stats. Under a profiler session the
  spans land on the host plane, on the same clock as the device's
  operations, so an idle gap on the device can be put down to the
  program step that was running; with no session a span costs about a
  microsecond. Instant events are not forwarded.
* **Recording is opt-in** — the ambient tracer is a :class:`NullTracer`
  that records nothing in memory; call sites guard only args that cost
  something to build behind ``tracer.enabled``.
* **Two exporters** — JSON-lines (one event per line, sorted keys) for
  grepping/diffing, and Chrome trace-event JSON for Perfetto
  (``ui.perfetto.dev`` → *Open trace file*), with one named track per
  ``track`` string (tier or lane group).
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Callable

from jax.profiler import TraceAnnotation

#: The event taxonomy (DESIGN.md §11). Free-form categories are allowed
#: but everything the repo emits uses these. A span of category ``cat``
#: is the profiler span ``repro.<cat>``.
CATEGORIES = ("plan", "compile", "dispatch", "chunk", "barrier",
              "collective", "lane", "cache", "measure")


@dataclasses.dataclass(frozen=True)
class TraceEvent:
    """One typed trace record.

    ``ph`` follows the Chrome trace-event phase alphabet: ``"X"`` is a
    complete span (``ts_us`` start + ``dur_us``), ``"i"`` an instant
    event. ``track`` names the horizontal track the event renders on —
    one per tier or lane group — and ``args`` is a flat, JSON-safe dict.
    """

    name: str
    cat: str
    ph: str                       # "X" span | "i" instant
    ts_us: float
    dur_us: float = 0.0
    track: str = "main"
    args: tuple = ()              # sorted (key, value) pairs — hashable

    def to_dict(self) -> dict[str, Any]:
        d = {"name": self.name, "cat": self.cat, "ph": self.ph,
             "ts_us": self.ts_us, "track": self.track,
             "args": dict(self.args)}
        if self.ph == "X":
            d["dur_us"] = self.dur_us
        return d


def _freeze_args(kw: dict) -> tuple:
    """Args as sorted (key, value) pairs with JSON-safe values only —
    deterministic export order, no id()s/addresses leaking in."""
    out = []
    for k in sorted(kw):
        v = kw[k]
        if not isinstance(v, (str, int, float, bool, type(None))):
            v = str(v)
        out.append((k, v))
    return tuple(out)


def _annotation(name: str, cat: str, args: dict) -> TraceAnnotation:
    """The profiler span of a tracer span: ``repro.<cat>``, readers match
    on that; the span's own name and args are its stats."""
    return TraceAnnotation(f"repro.{cat}", name=name, **args)


class _Span:
    """Context manager recording one complete ("X") event on exit, inside
    the span's profiler annotation."""

    __slots__ = ("_tracer", "_name", "_cat", "_track", "_args", "_t0",
                 "_annotation")

    def __init__(self, tracer, name, cat, track, args):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._track = track
        self._args = args

    def __enter__(self):
        self._annotation = _annotation(self._name, self._cat,
                                       dict(self._args))
        self._annotation.__enter__()
        self._t0 = self._tracer._clock()
        return self

    def __exit__(self, *exc):
        t1 = self._tracer._clock()
        self._tracer._record(TraceEvent(
            name=self._name, cat=self._cat, ph="X",
            ts_us=self._t0 * 1e6, dur_us=(t1 - self._t0) * 1e6,
            track=self._track, args=self._args))
        self._annotation.__exit__(*exc)
        return False


class Tracer:
    """Collects typed :class:`TraceEvent` records with an injectable clock.

    >>> tr = Tracer()
    >>> with tr.span("execute:stencil", cat="dispatch", track="resident"):
    ...     run()
    >>> tr.event("barrier", cat="barrier", track="lanes", occupied=3)
    >>> tr.write_chrome("trace.json")     # open in Perfetto
    """

    enabled = True

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self._clock = clock
        self.events: list[TraceEvent] = []

    # -- recording ------------------------------------------------------------

    def _record(self, ev: TraceEvent) -> None:
        self.events.append(ev)

    def event(self, name: str, *, cat: str, track: str = "main",
              **args) -> None:
        """Record one instant event."""
        self._record(TraceEvent(name=name, cat=cat, ph="i",
                                ts_us=self._clock() * 1e6, track=track,
                                args=_freeze_args(args)))

    def span(self, name: str, *, cat: str, track: str = "main", **args):
        """Context manager: a complete event spanning the ``with`` body,
        and the profiler span ``repro.<cat>`` around it."""
        return _Span(self, name, cat, track, _freeze_args(args))

    def clear(self) -> None:
        self.events.clear()

    def __len__(self) -> int:
        return len(self.events)

    # -- queries --------------------------------------------------------------

    def by_cat(self, cat: str) -> list[TraceEvent]:
        return [e for e in self.events if e.cat == cat]

    def tracks(self) -> list[str]:
        """Distinct track names, in first-appearance order."""
        seen: dict[str, None] = {}
        for e in self.events:
            seen.setdefault(e.track, None)
        return list(seen)

    # -- exporters ------------------------------------------------------------

    def to_jsonl(self) -> str:
        """One event per line, keys sorted — byte-stable given the same
        clock readings (the determinism tests diff this)."""
        return "".join(json.dumps(e.to_dict(), sort_keys=True) + "\n"
                       for e in self.events)

    def write_jsonl(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_jsonl())

    def to_chrome(self) -> dict:
        """Chrome trace-event JSON (dict form): loads in Perfetto with one
        named track (tid) per distinct ``track`` string. Spans become
        complete ("X") events; instants render as thread instants."""
        tids = {t: i for i, t in enumerate(self.tracks())}
        out: list[dict] = [
            {"name": "thread_name", "ph": "M", "pid": 0, "tid": tid,
             "args": {"name": track}}
            for track, tid in tids.items()
        ]
        for e in self.events:
            d: dict[str, Any] = {
                "name": e.name, "cat": e.cat, "ph": e.ph, "pid": 0,
                "tid": tids[e.track], "ts": e.ts_us, "args": dict(e.args),
            }
            if e.ph == "X":
                d["dur"] = e.dur_us
            else:
                d["s"] = "t"          # instant scope: thread
            out.append(d)
        return {"traceEvents": out, "displayTimeUnit": "ms"}

    def write_chrome(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f, sort_keys=True)
            f.write("\n")


class NullTracer(Tracer):
    """The disabled tracer: records nothing in memory.

    This is the ambient default — recording is off unless a real tracer
    is installed (``repro.obs.use_tracer``); its spans still open their
    profiler annotation, which costs about a microsecond when no profiler
    session runs. Call sites that build expensive args should guard on
    ``tracer.enabled``.
    """

    enabled = False

    def __init__(self):
        super().__init__(clock=lambda: 0.0)

    def event(self, name: str, *, cat: str, track: str = "main",
              **args) -> None:
        pass

    def span(self, name: str, *, cat: str, track: str = "main", **args):
        return _annotation(name, cat, args)

    def _record(self, ev: TraceEvent) -> None:
        pass
