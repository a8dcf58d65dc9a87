"""The ``Problem`` protocol: what a solver must expose to the executor.

The paper's claim is that PERKS is an execution model "largely independent
of the solver's implementation". This module is that claim as an
interface: an iterative problem is a step function ``state -> state``, an
initial state, a list of :class:`~repro.core.cache_policy.CacheableArray`
regions the cache planner can reason about, a halo/partition spec for the
distributed tier, and an oracle for equivalence checking. Anything that
satisfies it runs under every tier via ``repro.exec.execute`` and is
planned by ``repro.exec.plan`` — a new workload is an adapter
(:mod:`repro.exec.adapters`), not a new solver file.
"""
from __future__ import annotations

import abc
import dataclasses
import zlib
from typing import Any, Callable, Optional, Sequence

import jax
import numpy as np

from repro.core.cache_policy import CacheableArray


def operand_fingerprint(*operands) -> str:
    """Content digest of solver operands, for cache-safe problem names.

    Two same-shaped problems over *different* operators must never alias
    in a plan/runner cache (``runtime.solver_service``) — a size-only name
    like ``cg_n4096`` does exactly that. This digest folds each operand's
    shape/dtype plus up to 16 sampled element values into one crc32, so
    the name is stable for a given operator and (within crc32 collision
    odds) distinct across different ones. Abstract values — tracers,
    ``ShapeDtypeStruct`` planner probes — contribute shape/dtype only;
    opaque callables contribute their identity (content is unknowable).
    The sample is a fixed 16-element gather, so fingerprinting a device
    array transfers O(16) elements, never the array.
    """
    h = zlib.crc32(b"operands")
    for a in operands:
        if a is None:
            h = zlib.crc32(b"|none", h)
            continue
        if callable(a) and not hasattr(a, "shape"):
            h = zlib.crc32(f"|fn:{id(a):x}".encode(), h)
            continue
        shape = tuple(int(d) for d in getattr(a, "shape", ()))
        dtype = str(getattr(a, "dtype", type(a).__name__))
        h = zlib.crc32(repr((shape, dtype)).encode(), h)
        sample = _sample_elements(a, shape)
        if sample is not None:
            h = zlib.crc32(np.ascontiguousarray(sample).tobytes(), h)
    return f"{h:08x}"


def _sample_elements(a, shape, k: int = 16):
    """Up to ``k`` evenly-spaced elements of a concrete array as a host
    ndarray; None for abstract values (tracers, ShapeDtypeStructs)."""
    size = 1
    for d in shape:
        size *= d
    if size == 0:
        return None
    idx = np.linspace(0, size - 1, num=min(k, size)).astype(np.int64)
    if isinstance(a, np.ndarray):
        return a.reshape(-1)[idx]
    if isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer):
        return np.asarray(a.reshape(-1)[idx])
    return None


@dataclasses.dataclass(frozen=True)
class HaloSpec:
    """How a problem shards over one mesh axis (distributed tier).

    ``axis`` is the array axis that row-partitions; ``halo`` is how many
    rows of neighbour data ONE step needs (0 = no neighbour dependency —
    the barrier is a reduction, not an exchange); ``partitions`` lists the
    row-repacking strategies the problem supports.
    """

    axis: int = 0
    halo: int = 0
    partitions: tuple[str, ...] = ("rows",)


class Problem(abc.ABC):
    """One iterative workload, described for the PERKS executor.

    Subclasses (adapters) must provide the four abstract pieces; the tier
    hooks ``run_resident``/``run_distributed`` raise by default — a
    problem that does not override them simply does not support the tier
    (``supports`` reports which do).
    """

    #: problem family, used by the planner to pick a candidate generator
    kind: str = "generic"
    #: human-readable instance name (logged into Plan.problem)
    name: str = "problem"
    #: number of time steps / iterations this instance runs
    n_steps: int = 0
    #: how many independent instances this problem carries (1 = a single
    #: instance; ``repro.exec.batch.BatchedProblem`` overrides)
    batch: int = 1

    # -- required surface -----------------------------------------------------

    @abc.abstractmethod
    def initial_state(self) -> Any:
        """The state fed to the first step (a pytree of arrays)."""

    @abc.abstractmethod
    def step_fn(self) -> Callable[[Any], Any]:
        """The pure step function ``state -> state`` (one iteration)."""

    @abc.abstractmethod
    def cacheable_arrays(self, *, fuse_steps: int = 1) -> Sequence[CacheableArray]:
        """The arrays/regions a cache plan may keep on-chip (paper §III-B)."""

    @abc.abstractmethod
    def oracle(self) -> Any:
        """Reference result after ``n_steps`` (jnp oracle, host-loop order)."""

    # -- optional surface -----------------------------------------------------

    def finalize(self, state: Any) -> Any:
        """Map the final loop state to the user-facing result."""
        return state

    def convergence(self) -> Optional[tuple[Callable[[Any, Any], Any], Any]]:
        """Traceable convergence contract: ``(pred, params)``.

        ``pred(state, params)`` is a *pure, traceable* predicate returning
        a boolean scalar (True = this instance is converged) and ``params``
        is the pytree of per-instance arrays it consumes (e.g. the CG
        threshold ``tol * ||b||^2``). The predicate must be structurally
        identical across every instance of a batch key — only ``params``
        varies — so the batched tier can evaluate ALL lanes with ONE
        stacked ``vmap(pred)`` reduction, and the continuous-batching
        engine can swap a lane's check by swapping its params row.
        None = no convergence check (run all steps)."""
        return None

    def on_sync(self) -> Optional[Callable[[Any, int], bool]]:
        """Host-sync callback for chunked execution (e.g. CG convergence);
        returning True stops early. None = run all steps.

        Defaults to evaluating :meth:`convergence` on-device (ONE
        device->host bool transfer per sync point); override only for
        checks that cannot be expressed as a traceable predicate."""
        conv = self.convergence()
        if conv is None:
            return None
        pred, params = conv
        return lambda state, k: bool(pred(state, params))

    def halo_spec(self) -> Optional[HaloSpec]:
        """Partition description for the distributed tier (None = cannot
        shard)."""
        return None

    def domain_bytes(self) -> int:
        """Total bytes of the per-step working set (for planner reporting)."""
        return sum(a.bytes for a in self.cacheable_arrays())

    # -- batching surface (repro.exec.batch) ----------------------------------

    def payload(self) -> Any:
        """The per-instance data that varies across a batch (a pytree of
        arrays). Everything else — operators, specs, step counts — is
        *shared* by every instance of a batch; two instances may be packed
        together only when their ``batch_key`` matches. Defaults to the
        initial state."""
        return self.initial_state()

    def with_payload(self, payload: Any) -> "Problem":
        """A copy of this problem carrying ``payload`` instead of its own
        per-instance data. Must be traceable (called under ``jax.vmap`` by
        the batched tier); adapters implement it as a dataclass replace."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support batched execution "
            f"(no with_payload)")

    def batch_key(self) -> tuple:
        """Hashable compatibility key: instances may share one batched
        dispatch iff their keys are equal (same family, same shapes/dtypes,
        same shared operands, same step count). The default is
        conservative: shape/dtype of every payload leaf plus kind/name/
        n_steps."""
        leaves = jax.tree.leaves(self.payload())
        return (self.kind, self.name, self.n_steps,
                tuple((tuple(a.shape), str(a.dtype)) for a in leaves))

    def array_scales_with_batch(self, name: str) -> bool:
        """Whether the cacheable array ``name`` grows with batch size
        (per-instance state) or is shared by every instance of a batch
        (e.g. a common operator). Default: everything is per-instance."""
        return True

    # -- precision surface (repro.exec.precision) ------------------------------

    def with_precision(self, precision: str) -> "Problem":
        """A copy of this problem running under ``precision`` (a
        ``Plan.precision`` value). 'uniform' is always the identity;
        adapters that support mixed precision override this with a
        dataclass replace that swaps their reduction (see
        ``repro.exec.precision.dot_for``)."""
        if precision == "uniform":
            return self
        raise NotImplementedError(
            f"{type(self).__name__} does not support precision="
            f"{precision!r}")

    # -- tier hooks -----------------------------------------------------------

    def run_resident(self, plan) -> Any:
        raise NotImplementedError(
            f"{type(self).__name__} does not implement the resident tier")

    def run_distributed(self, plan, mesh) -> Any:
        raise NotImplementedError(
            f"{type(self).__name__} does not implement the distributed tier")

    def supports(self, tier: str) -> bool:
        """Which Plan tiers this problem can execute."""
        if tier in ("host_loop", "device_loop"):
            return True
        if tier == "resident":
            return type(self).run_resident is not Problem.run_resident
        if tier == "distributed":
            return type(self).run_distributed is not Problem.run_distributed
        return False

    def resident_compiles(self, chip) -> bool:
        """Whether this problem's resident-tier kernel can be built for
        ``chip`` (a :class:`~repro.core.hardware.Chip`). Mosaic refuses
        some kernel forms that interpret mode (``chip.interpret``) runs;
        the planner offers no resident plan where this is False."""
        return True
