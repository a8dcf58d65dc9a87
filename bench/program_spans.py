"""Device idle time put down to the program's own spans.

The program marks its steps in the profiler trace with host spans named
``repro.<cat>`` (DESIGN.md §11): ``repro.dispatch`` around each
``execute``, ``repro.compile`` around a runner's first dispatch (trace,
lower, compile or cache load, enqueue), ``repro.chunk`` around each later
dispatch, and ``repro.barrier`` around each host sync (the read-back and
the decision). The profiler stamps them on the clock of the device's
operations, so each nanosecond the device idles can be given to the
innermost program span open at that moment. A program without these spans
leaves nothing to read: the functions then return None.
"""
from __future__ import annotations

import bisect
import collections
from typing import Optional

PREFIX = "repro."
DISPATCH = "repro.dispatch"
COMPILE = "repro.compile"
CHUNK = "repro.chunk"
BARRIER = "repro.barrier"


def program_spans(red) -> list:
    """The ``repro.*`` host spans of a ``trace_reduce.Reduction``."""
    return [s for s in red.host if s.name.startswith(PREFIX)]


def span_counts(red) -> collections.Counter:
    """How many spans of each ``repro.*`` name the trace holds."""
    return collections.Counter(s.name for s in program_spans(red))


def _innermost_segments(spans) -> tuple[list[int], list[Optional[str]]]:
    """Cut the time line at every span boundary: ``cuts[i]`` to
    ``cuts[i + 1]`` lies in the span ``names[i]``, the innermost open one
    (the latest started; the shorter on a tie), or None."""
    cuts = sorted({t for s in spans for t in (s.start_ns, s.end_ns)})
    names: list[Optional[str]] = []
    starts = sorted(spans, key=lambda s: s.start_ns)
    open_, nxt = [], 0
    for a in cuts[:-1]:
        while nxt < len(starts) and starts[nxt].start_ns <= a:
            open_.append(starts[nxt])
            nxt += 1
        open_ = [s for s in open_ if s.end_ns > a]
        inner = max(open_, key=lambda s: (s.start_ns, -s.ns), default=None)
        names.append(inner.name if inner is not None else None)
    return cuts, names


def idle_by_span(red) -> Optional[dict]:
    """Nanoseconds of the first device's idle gaps (``Device.gaps()``) by
    the innermost ``repro.*`` span open during them, None for idle under
    no program span; None where the trace holds no device or no
    ``repro.dispatch`` span."""
    spans = program_spans(red)
    if not red.devices or not any(s.name == DISPATCH for s in spans):
        return None
    cuts, names = _innermost_segments(spans)
    out: dict = collections.defaultdict(int)
    for a, b in red.devices[0].gaps():
        t = a
        i = bisect.bisect_right(cuts, a) - 1
        while t < b:
            if i < 0 or i >= len(names):
                end = cuts[0] if i < 0 else b
                name = None
            else:
                end, name = cuts[i + 1], names[i]
            end = min(end, b)
            out[name] += end - t
            t = end
            i += 1
    return dict(out)


def idle_pct(ctx, names) -> Optional[float]:
    """Share of the traced window in which the device idled inside one of
    ``names`` (None: under no program span), in %; None where the trace
    has no program spans to read."""
    idle = idle_by_span(ctx.trace)
    if idle is None:
        return None
    return 100.0 * sum(idle.get(n, 0) for n in names) * 1e-9 / ctx.window_s
