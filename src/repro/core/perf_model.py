"""Performance models.

Part 1 — the paper's projected-peak model (§IV, Eqs. 4–13), reproduced
faithfully enough to check against the paper's own worked examples
(§IV-B gives two A100 numbers we reproduce to <1%).

Part 2 — the three-term TPU roofline demanded by the assignment
(compute / memory / collective), fed by ``compiled.cost_analysis()`` and
collective bytes parsed from post-SPMD HLO. Used by launch/dryrun.py.
"""
from __future__ import annotations

import dataclasses
import math
import re
from collections import Counter
from typing import Mapping, Optional

from repro.core.hardware import Chip, TPU_V5E


# ---------------------------------------------------------------------------
# Part 1: the paper's model (Eqs. 4-13)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PerksProjection:
    """Projected best-case runtime/throughput of a PERKS solver (Eq. 10/11)."""

    t_gm: float          # main-memory time for the domain traffic (Eq. 6)
    t_gm_halo: float     # main-memory time for unavoidable halo traffic (Eq. 9)
    t_sm: float          # on-chip-memory time (Eq. 8)
    t_total: float       # Eq. 10: max(t_gm + t_gm_halo, t_sm)
    cells_per_s: float   # Eq. 11 in cells/s (the paper's GCells/s FOM * 1e9)
    bound: str           # "main_memory" | "onchip_memory"


def gm_bytes_accessed(
    n_steps: int,
    domain_bytes: int,
    cached_bytes: int,
) -> float:
    """Eq. 5: A_gm = 2*N*D_uncache + 2*D_cache.

    The uncached portion is stored+loaded every step; the cached portion
    pays only the initial load and the final store.
    """
    uncached = max(0, domain_bytes - cached_bytes)
    return 2.0 * n_steps * uncached + 2.0 * cached_bytes


def sm_bytes_accessed(n_steps: int, sm_cached_bytes: int) -> float:
    """Eq. 7: A_sm = 2*(N-1)*D_cache_sm (store at step k, load at k+1)."""
    return 2.0 * max(0, n_steps - 1) * sm_cached_bytes


def project_perks(
    chip: Chip,
    *,
    n_steps: int,
    domain_cells: int,
    dtype_bytes: int,
    cached_cells: int,
    halo_bytes_per_step: float = 0.0,
    kernel_sm_bytes_per_step: float = 0.0,
) -> PerksProjection:
    """Paper Eqs. 5-11 for a PERKS solver on ``chip``.

    ``kernel_sm_bytes_per_step`` is A_sm(KERNEL)/N — on-chip traffic the
    baseline kernel already does for its own locality optimisation.
    """
    d_bytes = domain_cells * dtype_bytes
    c_bytes = cached_cells * dtype_bytes
    a_gm = gm_bytes_accessed(n_steps, d_bytes, c_bytes)
    t_gm = a_gm / chip.hbm_bw
    t_gm_halo = n_steps * halo_bytes_per_step / chip.hbm_bw
    a_sm = sm_bytes_accessed(n_steps, c_bytes) + n_steps * kernel_sm_bytes_per_step
    t_sm = a_sm / chip.onchip_bw
    t_total = max(t_gm + t_gm_halo, t_sm)
    bound = "main_memory" if t_gm + t_gm_halo >= t_sm else "onchip_memory"
    cells_per_s = domain_cells * n_steps / t_total if t_total > 0 else math.inf
    return PerksProjection(t_gm, t_gm_halo, t_sm, t_total, cells_per_s, bound)


def project_host_loop(
    chip: Chip, *, n_steps: int, domain_cells: int, dtype_bytes: int,
) -> PerksProjection:
    """The non-persistent baseline: the full domain is loaded and stored from
    main memory every step (cached_cells = 0)."""
    return project_perks(
        chip,
        n_steps=n_steps,
        domain_cells=domain_cells,
        dtype_bytes=dtype_bytes,
        cached_cells=0,
    )


def projected_speedup(chip: Chip, *, n_steps: int, domain_cells: int,
                      dtype_bytes: int, cached_cells: int,
                      halo_bytes_per_step: float = 0.0) -> float:
    """Upper-bound PERKS speedup over the host-loop baseline (both projected)."""
    base = project_host_loop(chip, n_steps=n_steps, domain_cells=domain_cells,
                             dtype_bytes=dtype_bytes)
    perks = project_perks(chip, n_steps=n_steps, domain_cells=domain_cells,
                          dtype_bytes=dtype_bytes, cached_cells=cached_cells,
                          halo_bytes_per_step=halo_bytes_per_step)
    return base.t_total / perks.t_total


def efficiency(c_sw: float, c_hw: float) -> float:
    """Eq. 12: the efficiency function. Peak efficiency once the software
    exposes at least the hardware's required concurrency (Little's law);
    below that we degrade linearly (a standard latency-bound assumption)."""
    if c_hw <= 0:
        return 1.0
    return min(1.0, c_sw / c_hw)


def hw_concurrency(throughput_ops: float, latency_s: float) -> float:
    """Eq. 13 (Little's law): in-flight operations needed to saturate."""
    return throughput_ops * latency_s


# ---------------------------------------------------------------------------
# Part 2: three-term TPU roofline (assignment §ROOFLINE)
# ---------------------------------------------------------------------------

_COLLECTIVE_RE = re.compile(
    r"(?P<shape>[a-z0-9]+\[[0-9,]*\][^=]*)=\s*(?P<op>all-gather|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute)"
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Bytes of one HLO shape string like 'bf16[8,128,4096]' (tuples summed)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclasses.dataclass
class CollectiveStats:
    """Per-device collective traffic parsed from post-SPMD HLO."""

    bytes_by_op: dict[str, int]
    count_by_op: dict[str, int]

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_op.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum result-shape bytes of every collective op in (post-SPMD) HLO.

    Note: `lowered.as_text()` of a pjit program contains *no* collectives —
    they are materialised by the SPMD partitioner — so callers must pass
    ``compiled.as_text()``. Shapes there are per-device; the roofline
    divides by link bandwidth only (per-chip time), matching the
    assignment's ``collective_bytes / (chips × link_bw)`` with
    ``collective_bytes`` taken as the global sum (= per-device × chips).
    """
    bytes_by_op: Counter = Counter()
    count_by_op: Counter = Counter()
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        # `-start` variants would double count with their `-done` halves;
        # HLO text from XLA CPU uses plain ops, async wrappers keep the name
        # on the start op only. Skip `-done` lines defensively.
        if "-done" in line:
            continue
        nbytes = _shape_bytes(m.group("shape"))
        bytes_by_op[op] += nbytes
        count_by_op[op] += 1
    return CollectiveStats(dict(bytes_by_op), dict(count_by_op))


@dataclasses.dataclass
class Roofline:
    """The three roofline terms, in seconds per executed step, per chip."""

    compute_s: float
    memory_s: float
    collective_s: float
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    n_devices: int
    model_flops: float = 0.0          # 6*N*D analytic model FLOPs (global)
    chip: Chip = TPU_V5E

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / HLO_FLOPS (global): <1 means remat/redundant compute,
        >1 means HLO undercounts (e.g. fused ops) — reported either way."""
        hlo_global = self.flops_per_device * self.n_devices
        return self.model_flops / hlo_global if hlo_global else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the single-term roofline this step achieves if it runs
        exactly at the dominant term (perfect overlap assumption): the
        useful-compute time over the bound time."""
        if self.bound_s <= 0:
            return 0.0
        ideal_compute_s = (self.model_flops / self.n_devices) / self.chip.peak_flops
        return min(1.0, ideal_compute_s / self.bound_s)


def roofline_from_analysis(
    *,
    cost_analysis: Optional[Mapping[str, float]],
    collective: CollectiveStats,
    n_devices: int,
    model_flops: float = 0.0,
    chip: Chip = TPU_V5E,
) -> Roofline:
    """Build the roofline from ``compiled.cost_analysis()`` (per-device SPMD
    program costs) + parsed collective bytes.

      compute term    = HLO_FLOPs  / (chips × peak)      [global HLO flops]
      memory term     = HLO_bytes  / (chips × HBM bw)
      collective term = coll_bytes / (chips × link bw)

    cost_analysis of the compiled SPMD module reports *per-device* numbers,
    so global = per_device × chips and each term reduces to
    per_device / unit — which is what we compute.
    """
    ca = dict(cost_analysis or {})
    flops = float(ca.get("flops", 0.0) or 0.0)
    nbytes = float(ca.get("bytes accessed", 0.0) or 0.0)
    coll = float(collective.total_bytes)
    return Roofline(
        compute_s=flops / chip.peak_flops,
        memory_s=nbytes / chip.hbm_bw,
        collective_s=coll / chip.ici_bw_per_link if chip.ici_bw_per_link else 0.0,
        flops_per_device=flops,
        bytes_per_device=nbytes,
        collective_bytes_per_device=coll,
        n_devices=n_devices,
        model_flops=model_flops,
        chip=chip,
    )
