"""Hardware constants used by the cache policy and the performance models.

The TPU entries are looked up by the attached device's ``device_kind``
(``attached_chip``); the GPU entries mirror Table I of the paper and are
used only by the paper-fidelity performance-model checks.
"""
from __future__ import annotations

import dataclasses

GiB = 1024**3
MiB = 1024**2


@dataclasses.dataclass(frozen=True)
class Chip:
    """Per-chip capabilities relevant to the PERKS model and the roofline."""

    name: str
    # Peak dense compute (FLOP/s). For v5e this is the bf16 MXU peak.
    peak_flops: float
    # Main-memory (HBM / device-memory) bandwidth, bytes/s.
    hbm_bw: float
    # HBM capacity in bytes.
    hbm_bytes: float
    # Fast on-chip memory capacity usable for PERKS caching, bytes.
    #   GPU: register file + shared memory (paper Table I).
    #   TPU: VMEM.
    onchip_bytes: float
    # On-chip memory bandwidth, bytes/s (shared-memory BW / VMEM BW).
    onchip_bw: float
    # Inter-chip interconnect bandwidth per link, bytes/s (ICI for TPU).
    ici_bw_per_link: float = 0.0
    # Number of ICI links per chip participating in a collective (torus).
    ici_links: int = 1
    # Pallas kernels run in interpret mode, not through Mosaic: the CPU
    # test backend's stand-in for a TPU (``attached_chip``), where every
    # kernel form runs, including those Mosaic refuses. The planner reads
    # it through ``Problem.resident_compiles``.
    interpret: bool = False


# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s (Google Cloud, "TPU v5e");
# ~50 GB/s/link ICI.
# VMEM: 128 MiB, the figure Mosaic itself enforces. Compiling for a
# described v5e topology (jax.experimental.topologies, "v5e:2x2") accepts
# a kernel holding 128 MiB of VMEM scratch and refuses 129 MiB with
# "would exceed memory (size=134217728)". A kernel's scoped-VMEM limit
# (``vmem_limit_bytes``, 16 MiB by default) may be raised to that figure,
# so every kernel requests its own footprint
# (``kernels.common.compiler_params``) and the planner admits a plan only
# when that footprint fits ``onchip_bytes``.
# VMEM bandwidth is taken as ~22x the HBM bandwidth (public Mosaic/TPU
# guidance of O(10 TB/s)); not measured.
TPU_V5E = Chip(
    name="tpu_v5e",
    peak_flops=197e12,
    hbm_bw=819e9,
    hbm_bytes=16 * GiB,
    onchip_bytes=128 * MiB,
    onchip_bw=18e12,
    ici_bw_per_link=50e9,
    ici_links=4,  # 2D torus on v5e: 4 links (+x,-x,+y,-y)
)

# Earlier/later TPU generations, for planner sensitivity studies (the
# executor's `--chip` flag threads these through examples/ and
# benchmarks/). Public specs:
#   v4:  275 TFLOP/s bf16, 32 GiB HBM2 @ 1228 GB/s, 2400 Gbps ICI per chip
#        over a 3D torus (6 links -> 50 GB/s/link)
#        [cloud.google.com/tpu/docs/v4, TPU v4 ISCA'23 paper arXiv:2304.01433]
#   v5p: 459 TFLOP/s bf16, 95 GiB HBM2e @ 2765 GB/s, 4800 Gbps ICI per chip
#        over a 3D torus (6 links -> 100 GB/s/link)
#        [cloud.google.com/tpu/docs/v5p]
# VMEM is taken as 128 MiB per core for both (public Pallas/Mosaic guidance
# quotes the same order as v5e); VMEM bandwidth scaled ~22x HBM like v5e.
TPU_V4 = Chip(
    name="tpu_v4",
    peak_flops=275e12,
    hbm_bw=1228e9,
    hbm_bytes=32 * GiB,
    onchip_bytes=128 * MiB,
    onchip_bw=27e12,
    ici_bw_per_link=50e9,
    ici_links=6,  # 3D torus
)

TPU_V5P = Chip(
    name="tpu_v5p",
    peak_flops=459e12,
    hbm_bw=2765e9,
    hbm_bytes=95 * GiB,
    onchip_bytes=128 * MiB,
    onchip_bw=61e12,
    ici_bw_per_link=100e9,
    ici_links=6,  # 3D torus
)

# Paper Table I (used to sanity-check the reproduced performance model
# against the paper's own worked examples in Section IV-B).
A100 = Chip(
    name="a100",
    peak_flops=19.5e12,             # fp64 tensor? paper uses mem-bound only
    hbm_bw=1555e9,
    hbm_bytes=40 * GiB,
    onchip_bytes=(27 + 17.29) * MiB,  # register file + shared memory
    onchip_bw=19.4e12,              # ~108 SMX * 128 B/clk * 1.41 GHz
    ici_bw_per_link=0.0,
)

V100 = Chip(
    name="v100",
    peak_flops=7.8e12,
    hbm_bw=900e9,
    hbm_bytes=16 * GiB,
    onchip_bytes=(20 + 7.5) * MiB,
    onchip_bw=13.7e12,
    ici_bw_per_link=0.0,
)

CHIPS = {c.name: c for c in (TPU_V5E, TPU_V4, TPU_V5P, A100, V100)}

#: What the CPU test backend plans for: the v5e's figures, with every
#: Pallas kernel run in interpret mode.
CPU_INTERPRET = dataclasses.replace(TPU_V5E, interpret=True)

#: ``jax.Device.device_kind`` of an attached TPU -> its Chip. A kind that
#: is not listed is an error, never a default. Only the v5e is listed: its
#: VMEM figure is the one established by compiling for it (above).
DEVICE_KINDS = {"TPU v5 lite": TPU_V5E}


def attached_chip() -> Chip:
    """The Chip of the first attached device, looked up by its
    ``device_kind``. On the CPU (the test suite, where every kernel runs
    in interpret mode) plans are made for ``CPU_INTERPRET``; any other
    platform is an error."""
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return CPU_INTERPRET
    if dev.platform != "tpu":
        raise ValueError(
            f"no Chip for platform {dev.platform!r}: the kernels run on a "
            "TPU, or interpreted on the CPU")
    try:
        return DEVICE_KINDS[dev.device_kind]
    except KeyError:
        raise ValueError(
            f"no Chip for device kind {dev.device_kind!r}; add its published "
            "figures to repro.core.hardware.DEVICE_KINDS") from None


def vmem_cache_budget(chip: Chip, working_set_bytes: float) -> float:
    """On-chip bytes available for PERKS caching after the kernel's own
    working set (paper: "unused registers + shared memory"; TPU: VMEM not
    needed by the compute tile double-buffers)."""
    return max(0.0, chip.onchip_bytes - working_set_bytes)
