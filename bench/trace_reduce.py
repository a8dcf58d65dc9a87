"""From a profiler trace (``.xplane.pb``) to device intervals and times.

Device planes are those named ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per HLO operation that ran. On a TPU the event's name is
the instruction's HLO text, ``%stencil_perks_deep.1 = f32[...] custom-call
(...)``; the reduction keeps the instruction name, ``stencil_perks_deep.1``
(a Mosaic kernel's instruction carries the kernel's name). Events nest: a
``while`` holds the operations of its body. Host planes (``/host:...``)
hold the benchmark's and JAX's own host spans, which name what the host
was doing during an idle gap.
"""
from __future__ import annotations

import dataclasses
import pathlib
import re
from typing import Iterable, Optional, Sequence

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE_PREFIX = "/host:"
OPS_LINE = "XLA Ops"
HLO_TEXT = re.compile(r"^%?([\w.\-]+) = ")
#: HLO operations that move data between chips.
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|send|recv)")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: int
    end_ns: int

    @property
    def ns(self) -> int:
        return self.end_ns - self.start_ns


def union(spans: Iterable[Span]) -> list[tuple[int, int]]:
    """Disjoint, sorted intervals covering every span."""
    out: list[list[int]] = []
    for s in sorted(spans, key=lambda s: s.start_ns):
        if out and s.start_ns <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s.end_ns)
        else:
            out.append([s.start_ns, s.end_ns])
    return [(a, b) for a, b in out]


@dataclasses.dataclass
class Device:
    """The operations one device ran."""

    index: int
    ops: list[Span]

    def busy_ns(self) -> int:
        return sum(b - a for a, b in union(self.ops))

    def op_ns(self, match) -> int:
        return sum(s.ns for s in self.ops if match(s.name))

    def self_ns(self) -> dict[str, int]:
        """Each operation name's time less the time of the operations
        nested in it, summed."""
        out: dict[str, int] = {}
        stack: list[list] = []

        def close(entry):
            span, inner = entry
            out[span.name] = out.get(span.name, 0) + max(0, span.ns - inner)

        for s in sorted(self.ops, key=lambda s: (s.start_ns, -s.end_ns)):
            while stack and stack[-1][0].end_ns <= s.start_ns:
                close(stack.pop())
            if stack:
                stack[-1][1] += s.ns
            stack.append([s, 0])
        while stack:
            close(stack.pop())
        return out

    def gaps(self) -> list[tuple[int, int]]:
        """Idle intervals between the first and the last operation."""
        iv = union(self.ops)
        return [(a[1], b[0]) for a, b in zip(iv, iv[1:]) if b[0] > a[1]]


@dataclasses.dataclass
class Reduction:
    devices: list[Device]
    host: list[Span]

    def _mean(self, per_device) -> Optional[float]:
        if not self.devices:
            return None
        return sum(per_device(d) for d in self.devices) / len(self.devices)

    def busy_s(self) -> Optional[float]:
        """Seconds in which some operation ran, averaged over devices."""
        return self._mean(lambda d: d.busy_ns() * 1e-9)

    def op_s(self, prefix: str) -> Optional[float]:
        """Summed seconds of operations whose name starts with ``prefix``,
        averaged over devices; None where no device ran one."""
        if not any(s.name.startswith(prefix) for d in self.devices
                   for s in d.ops):
            return None
        return self._mean(
            lambda d: d.op_ns(lambda n: n.startswith(prefix)) * 1e-9)

    def collective_s(self) -> Optional[float]:
        """Summed seconds of collective operations, averaged over devices;
        None where no device ran one."""
        if not any(COLLECTIVE.match(s.name) for d in self.devices
                   for s in d.ops):
            return None
        return self._mean(lambda d: d.op_ns(COLLECTIVE.match) * 1e-9)

    def top_ops(self, k: int = 10) -> list[list]:
        """The ``k`` operation names (numeric suffix dropped) that took the
        most device time of their own, nested operations' time excluded,
        with their seconds averaged over devices."""
        tot: dict[str, int] = {}
        for d in self.devices:
            for name, ns in d.self_ns().items():
                name = re.sub(r"\.\d+$", "", name)
                tot[name] = tot.get(name, 0) + ns
        n = max(1, len(self.devices))
        top = sorted(tot.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
        return [[name, ns * 1e-9 / n] for name, ns in top]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """The ``k`` longest idle gaps of the first device, each named by
        the innermost host span that covers its middle."""
        if not self.devices:
            return []
        gaps = sorted(self.devices[0].gaps(), key=lambda g: g[0] - g[1])[:k]
        out = []
        for a, b in gaps:
            mid = (a + b) // 2
            cover = [s for s in self.host if s.start_ns <= mid < s.end_ns]
            name = min(cover, key=lambda s: s.ns).name if cover else "(none)"
            out.append([name, (b - a) * 1e-9])
        return out


def op_name(text: str) -> str:
    """The HLO instruction name of a device event's name."""
    m = HLO_TEXT.match(text)
    return m.group(1) if m else text


def reduce_profile(profile) -> Reduction:
    """A :class:`Reduction` of a ``jax.profiler.ProfileData``."""
    devices, host = [], []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = [Span(op_name(e.name), int(e.start_ns),
                        int(e.start_ns + e.duration_ns))
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices.append(Device(int(m.group(1)), ops))
        elif plane.name.startswith(HOST_PLANE_PREFIX):
            host += [Span(e.name, int(e.start_ns),
                          int(e.start_ns + e.duration_ns))
                     for line in plane.lines for e in line.events]
    devices.sort(key=lambda d: d.index)
    return Reduction(devices, host)


def find_xplane(trace_dir) -> Optional[pathlib.Path]:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


def load(path, only_devices: Optional[Sequence[int]] = None) -> Reduction:
    """Reduce the trace at ``path`` (a file, or a directory holding one),
    keeping the devices listed in ``only_devices`` where given."""
    from jax.profiler import ProfileData
    path = pathlib.Path(path)
    if path.is_dir():
        found = find_xplane(path)
        if found is None:
            return Reduction([], [])
        path = found
    red = reduce_profile(ProfileData.from_file(str(path)))
    if only_devices is not None:
        keep = set(only_devices)
        red.devices = [d for d in red.devices if d.index in keep]
    return red
