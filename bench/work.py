"""The work a call has to do, counted from its shapes alone.

These counts are the yardstick of every roofline and peak share. They
read the same whatever implements the call, so no honest change can move
them: a change can only take less time for the same work.
"""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class Work:
    """Compulsory HBM bytes and useful operations of one call."""

    bytes: int
    flops: int

    def __mul__(self, n: int) -> "Work":
        return Work(self.bytes * n, self.flops * n)

    __rmul__ = __mul__


def stencil_call(shape, points: int, steps: int, itemsize: int) -> Work:
    """One call of ``steps`` sweeps of a ``points``-point stencil with equal
    weights over a field of ``shape``.

    Bytes: one read and one write of the field for the whole call; no
    implementation can move less. Flops: ``points`` per cell and step,
    the least an equal-weight stencil needs (``points - 1`` additions and
    one multiplication). The frozen border is counted as updated: at these
    sizes it is under 0.1% of the cells.
    """
    cells = math.prod(shape)
    return Work(bytes=2 * cells * itemsize, flops=points * cells * steps)


def least_time_s(work: Work, peak: dict) -> tuple[float, str]:
    """The least time a chip with ``peak`` needs for ``work``, and which
    bound sets it: ``"bytes"`` (HBM bandwidth) or ``"flops"``."""
    t_bytes = work.bytes / peak["hbm_bytes_per_s"]
    t_flops = work.flops / peak["flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
