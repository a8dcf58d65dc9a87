"""Batched multi-tenant execution: B instances, one persistent dispatch.

PERKS amortizes kernel-launch and barrier cost by moving the *time* loop
inside one dispatch; this module applies the same economics across
*instances*. A service solving thousands of small stencil/CG problems for
concurrent users should not pay a dispatch (and, distributed, a
collective barrier) per user — it should stack the per-instance payloads
and advance all of them through ONE persistent dispatch per step chunk.

:class:`BatchedProblem` is that transform, expressed inside the existing
``Problem -> plan -> execute`` pipeline (DESIGN.md §7/§8): it wraps B
shape-compatible instances (equal :meth:`Problem.batch_key`) and is
itself a :class:`~repro.exec.problem.Problem`, so ``execute`` and
``autotune`` need no new entry points:

* loop tiers — the step function becomes ``jax.vmap(step)``; the
  host/device loop runs unchanged over the stacked state, so the per-step
  dispatch is paid once per *batch*, not once per instance;
* resident tier — the Pallas kernel dispatch is vmapped (the batch
  becomes a leading grid dimension; per-instance VMEM residency shrinks
  to budget/B, which the planner accounts for);
* distributed tier — ``jax.vmap`` composes over the ``shard_map``
  programs, so one halo exchange / psum round serves every instance in
  the batch (collectives batch their payloads instead of multiplying
  their latency floors).

Results are bit-identical to running each instance alone on the same
tier (asserted over all 13 stencil specs and the sparse registry in
``tests/test_batch.py``); the queueing/packing layer that feeds fleets of
heterogeneous requests into these batches is
``repro.runtime.solver_service``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.cache_policy import CacheableArray
from repro.exec.problem import HaloSpec, Problem


def stack_payloads(problems: Sequence[Problem]):
    """Stack every instance's payload pytree along a new leading axis."""
    return jax.tree.map(lambda *ls: jnp.stack(ls),
                        *[p.payload() for p in problems])


def per_instance_chip(chip, batch: int):
    """The on-chip budget ONE instance of a B-wide batch may plan against.

    A vmapped resident dispatch runs B kernel instances concurrently, so
    residency *and scratch* (shallow streaming windows, deep wavefront
    buffers — ``core.cache_policy.deep_scratch_rows``) share the physical
    VMEM. Scaling ``onchip_bytes`` by 1/B is how the planner makes a
    batched problem first demote temporal-blocking depth (whose scratch
    is per-instance) and then resident rows, rather than emitting plans
    whose combined working set oversubscribes the chip (DESIGN.md §8/§12).
    """
    if batch <= 1:
        return chip
    return dataclasses.replace(chip, onchip_bytes=chip.onchip_bytes / batch)


class BatchedProblem(Problem):
    """B independent instances of one problem family as a single Problem.

    Instances must agree on :meth:`Problem.batch_key` — same family, same
    shapes/dtypes, same shared operands (e.g. the CG matrix), same step
    count — so one traced program serves the whole batch. ``pad_to``
    replicates the last instance up to a fixed dispatch width (the
    serving layer uses it to keep ONE jit cache entry per batch key);
    padded lanes are dropped by :meth:`split`.
    """

    kind = "batched"

    def __init__(self, instances: Sequence[Problem], *,
                 pad_to: Optional[int] = None):
        instances = tuple(instances)
        if not instances:
            raise ValueError("BatchedProblem needs at least one instance")
        keys = {p.batch_key() for p in instances}
        if len(keys) > 1:
            raise ValueError(
                f"instances are not batch-compatible; got {len(keys)} "
                f"distinct batch keys: {sorted(map(str, keys))[:3]} ...")
        if any(isinstance(p, BatchedProblem) for p in instances):
            raise ValueError("BatchedProblem instances cannot nest")
        self.pad = 0
        if pad_to is not None:
            if pad_to < len(instances):
                raise ValueError(
                    f"pad_to={pad_to} < {len(instances)} instances")
            self.pad = pad_to - len(instances)
            instances = instances + (instances[-1],) * self.pad
        self.instances = instances
        self.template = instances[0]
        self.batch = len(instances)
        self.kind = self.template.kind
        self.n_steps = self.template.n_steps
        self.name = f"batch{self.batch}_{self.template.name}"
        self.payload_stack = stack_payloads(instances)

    @classmethod
    def from_instances(cls, instances: Sequence[Problem], *,
                       pad_to: Optional[int] = None) -> "BatchedProblem":
        return cls(instances, pad_to=pad_to)

    # -- protocol -------------------------------------------------------------

    def initial_state(self):
        build = lambda pay: self.template.with_payload(pay).initial_state()
        return jax.vmap(build)(self.payload_stack)

    def step_fn(self) -> Callable[[Any], Any]:
        return jax.vmap(self.template.step_fn())

    def finalize(self, state):
        # adapters' finalize is structural (tuple re-selection), so it maps
        # over the stacked state unchanged
        return self.template.finalize(state)

    def oracle(self):
        return jax.tree.map(lambda *ls: jnp.stack(ls),
                            *[p.oracle() for p in self.instances])

    def convergence(self):
        """The instances' shared predicate vmapped over the lane axis, with
        every instance's params stacked: ``vec(state, params)`` is a
        bool[B] lane vector from ONE device-side reduction. None if any
        instance declares no contract."""
        confs = [p.convergence() for p in self.instances]
        if any(c is None for c in confs):
            return None
        pred = confs[0][0]  # structurally identical across the batch key
        params = jax.tree.map(lambda *ls: jnp.stack([jnp.asarray(x)
                                                     for x in ls]),
                              *[c[1] for c in confs])
        return jax.vmap(pred), params

    def on_sync(self) -> Optional[Callable[[Any, int], bool]]:
        """Batched convergence check: stop only when EVERY instance's own
        check passes (the batch shares one dispatch, so the slowest
        instance owns the step count). None if any instance never stops.

        Problems with a traceable :meth:`Problem.convergence` contract are
        checked with a single stacked all-lanes reduction — one device
        dispatch and ONE host bool transfer per sync point, regardless of
        B. Only legacy host-callback-only instances fall back to the
        per-lane loop (B transfers per sync)."""
        conv = self.convergence()
        if conv is not None:
            vec, params = conv
            all_lanes = jax.jit(lambda s: jnp.all(vec(s, params)))
            return lambda state, k: bool(all_lanes(state))
        cbs = [p.on_sync() for p in self.instances]
        if any(cb is None for cb in cbs):
            return None

        def all_done(state, k) -> bool:
            for i, cb in enumerate(cbs):
                s_i = jax.tree.map(lambda a: a[i], state)
                if not cb(s_i, k):
                    return False
            return True

        return all_done

    def cacheable_arrays(self, *, fuse_steps: int = 1) -> Sequence[CacheableArray]:
        """Per-instance regions scale by B; shared operands (e.g. the CG
        matrix — ``array_scales_with_batch``) keep one copy. This is the
        B-scaled working set the planner prices (DESIGN.md §8)."""
        out = []
        for a in self.template.cacheable_arrays(fuse_steps=fuse_steps):
            if self.template.array_scales_with_batch(a.name):
                a = dataclasses.replace(a, bytes=a.bytes * self.batch)
            out.append(a)
        return out

    def domain_bytes(self) -> int:
        return self.template.domain_bytes() * self.batch

    def halo_spec(self) -> Optional[HaloSpec]:
        return self.template.halo_spec()

    def halo_split(self, plan, mesh) -> Optional[dict]:
        # every instance's halo rides the same exchange
        split = self.template.halo_split(plan, mesh)
        if split is not None:
            split = dict(split, halo_bytes=split["halo_bytes"] * self.batch)
        return split

    def supports(self, tier: str) -> bool:
        return self.template.supports(tier)

    @property
    def spmv_format(self) -> Optional[str]:
        return self.template.spmv_format

    # -- batching surface -----------------------------------------------------

    def payload(self):
        return self.payload_stack

    def with_payload(self, payload) -> "BatchedProblem":
        # rebuild only the real instances and re-pad to the same width, so
        # the clone's split() keeps dropping the padded lanes
        real = self.batch - self.pad
        rebuilt = [
            self.template.with_payload(
                jax.tree.map(lambda a, i=i: a[i], payload))
            for i in range(real)
        ]
        return type(self)(rebuilt, pad_to=self.batch if self.pad else None)

    def batch_key(self) -> tuple:
        return ("batched", self.batch, self.template.batch_key())

    def with_precision(self, precision: str) -> "BatchedProblem":
        """Precision applies uniformly to every lane (one traced program
        serves the batch, so the reduction must be shared)."""
        if precision == "uniform":
            return self
        real = self.batch - self.pad
        rebuilt = [p.with_precision(precision)
                   for p in self.instances[:real]]
        return type(self)(rebuilt, pad_to=self.batch if self.pad else None)

    def split(self, result) -> list:
        """Per-instance results (padded lanes dropped), in instance order."""
        real = self.batch - self.pad
        return [jax.tree.map(lambda a: a[i], result) for i in range(real)]

    # -- tiers ----------------------------------------------------------------

    def run_resident(self, plan):
        """One vmapped kernel dispatch: the batch rides as a leading grid
        dimension over the template's resident Pallas kernel."""
        run = lambda pay: self.template.with_payload(pay).run_resident(plan)
        return jax.vmap(run)(self.payload_stack)

    def run_distributed(self, plan, mesh):
        """vmap over the template's shard_map program: every instance's
        halo exchange / reduction rides in the SAME ppermute/psum round,
        so the per-barrier collective latency is paid once per batch."""
        if plan.partition == "nnz":
            raise NotImplementedError(
                "batched distributed CG supports partition='rows' only "
                "(the nnz repack is a host-side permutation; apply it to "
                "the operator before batching)")
        run = lambda pay: self.template.with_payload(pay).run_distributed(
            plan, mesh)
        return jax.vmap(run)(self.payload_stack)


# -----------------------------------------------------------------------------
# Lane-level batching: the substrate of the continuous-batching engine
# -----------------------------------------------------------------------------

def _lane_select(active, new, old):
    """Per-leaf lane select: keep ``new`` where the lane is active, ``old``
    otherwise; ``active`` is bool[B] broadcast over the trailing dims."""
    mask = active.reshape(active.shape + (1,) * (new.ndim - 1))
    return jnp.where(mask, new, old)


@dataclasses.dataclass
class LaneState:
    """Device-side state of one lane group (width fixed at construction).

    ``state`` is the stacked solver state (leading axis = lanes);
    ``steps_done`` is int32[width] — a lane with ``steps_done >= n_steps``
    is *frozen* (free or retired) and is masked out of every group step;
    ``params`` is the stacked convergence-params pytree (None when the
    family declares no contract).
    """

    state: Any
    steps_done: jax.Array
    params: Any = None


class LaneRunner:
    """Per-batch-key compiled lane programs for continuous batching.

    Where :class:`BatchedProblem` stacks a *fixed* membership for one
    dispatch sequence, a LaneRunner owns ``width`` lanes whose membership
    churns: the engine admits a new instance into a free lane at a barrier
    (:meth:`admit` — the mid-flight payload swap-in), advances every
    occupied lane through the same masked group step (:meth:`step_fn`),
    reads a per-lane convergence vector with ONE stacked reduction
    (:meth:`convergence_vector`), and retires individually-converged lanes
    early (:meth:`harvest` + :meth:`retire`) without disturbing the rest.

    All jitted programs (group step chunks, admit, convergence vector) are
    built once per runner and reused for the key's whole lifetime, so the
    persistent dispatch stays hot while membership churns. Masking is what
    makes heterogeneous progress safe inside one fused dispatch: a frozen
    lane's step output is computed but discarded (``jnp.where`` select),
    so an admitted lane that started 3 chunks late and a lane one step
    from convergence ride the same program.
    """

    def __init__(self, template: Problem, width: int,
                 tracer: Optional["obs.Tracer"] = None):
        if isinstance(template, BatchedProblem):
            raise TypeError("LaneRunner wants a single-instance template; "
                            "it owns the lane stacking itself")
        if width < 1:
            raise ValueError(f"width must be >= 1, got {width}")
        self.template = template
        self.width = width
        # a tracer pinned here at construction wins; otherwise every emit
        # resolves the ambient tracer at call time, so a runner built
        # before `use_tracer(...)` still lands in the trace
        self._tracer = tracer
        self.n_steps = int(template.n_steps)
        self._vstep = jax.vmap(template.step_fn())
        conv = template.convergence()
        self.has_convergence = conv is not None
        if self.has_convergence:
            pred, _ = conv
            self._conv_vec = jax.jit(jax.vmap(pred))
        self._slice = jax.jit(lambda s, i: jax.tree.map(lambda a: a[i], s))

        def _admit(state, steps, init, lane):
            state = jax.tree.map(lambda grp, x: grp.at[lane].set(x),
                                 state, init)
            return state, steps.at[lane].set(0)

        self._admit = jax.jit(_admit)
        self._set_row = jax.jit(
            lambda grp, x, lane: jax.tree.map(
                lambda g, v: g.at[lane].set(v), grp, x))
        self._freeze = jax.jit(
            lambda steps, lane: steps.at[lane].set(self.n_steps))
        obs.get_metrics().counter("executor_retraces_total",
                                  tier="lane_runner").inc()
        tr = self._trace()
        if tr.enabled:
            tr.event("lane_compile", cat="compile", track=self._track(),
                     template=template.name, width=width,
                     n_steps=self.n_steps)

    def _trace(self) -> "obs.Tracer":
        return self._tracer if self._tracer is not None else obs.get_tracer()

    def _track(self) -> str:
        return f"lanes:{self.template.name}"

    # -- group stepping --------------------------------------------------------

    def step_fn(self) -> Callable[[Any], Any]:
        """Masked group step over the carry ``(state, steps_done)``: lanes
        advance only while ``steps_done < n_steps``; frozen lanes keep
        their state bit-for-bit (their computed update is discarded)."""
        n, vstep = self.n_steps, self._vstep

        def group_step(carry):
            state, steps = carry
            active = steps < n
            new = vstep(state)
            state = jax.tree.map(
                lambda a, b: _lane_select(active, a, b), new, state)
            return state, steps + active.astype(steps.dtype)

        return group_step

    # -- lane lifecycle --------------------------------------------------------

    def fresh(self) -> LaneState:
        """An all-free lane group: every lane holds a frozen replica of
        the template's initial state (masked out until admitted), so the
        group step is well-defined from the first chunk."""
        init = self.template.initial_state()
        state = jax.tree.map(lambda a: jnp.stack([a] * self.width), init)
        steps = jnp.full((self.width,), self.n_steps, jnp.int32)
        params = None
        if self.has_convergence:
            _, p = self.template.convergence()
            params = jax.tree.map(
                lambda a: jnp.stack([jnp.asarray(a)] * self.width), p)
        return LaneState(state=state, steps_done=steps, params=params)

    def admit(self, lanes: LaneState, lane: int, problem: Problem) -> LaneState:
        """Swap ``problem``'s fresh state into a free lane mid-flight: the
        lane's state row and convergence-params row are overwritten on
        device and its step counter reset — no retrace, no recompile."""
        if problem.batch_key() != self.template.batch_key():
            raise ValueError(
                f"cannot admit {problem.name}: batch key differs from this "
                f"runner's template ({self.template.name})")
        idx = jnp.int32(lane)
        state, steps = self._admit(lanes.state, lanes.steps_done,
                                   problem.initial_state(), idx)
        params = lanes.params
        if self.has_convergence:
            _, p = problem.convergence()
            params = self._set_row(params,
                                   jax.tree.map(jnp.asarray, p), idx)
        tr = self._trace()
        if tr.enabled:
            tr.event("lane_admit", cat="lane", track=self._track(),
                     lane=lane, problem=problem.name)
        obs.get_metrics().counter("lane_admissions_total").inc()
        return LaneState(state=state, steps_done=steps, params=params)

    def convergence_vector(self, lanes: LaneState):
        """bool[width] of per-lane convergence — ONE stacked device-side
        reduction and ONE host transfer, never a per-lane round trip.
        None when the family declares no contract."""
        if not self.has_convergence:
            return None
        return np.asarray(self._conv_vec(lanes.state, lanes.params))

    def harvest(self, lanes: LaneState, lane: int):
        """The finalized result of one lane (device slice + finalize)."""
        return self.template.finalize(self._slice(lanes.state,
                                                  jnp.int32(lane)))

    def retire(self, lanes: LaneState, lane: int) -> LaneState:
        """Freeze a lane (converged or exhausted): its counter jumps to
        ``n_steps`` so the group step masks it out from now on."""
        tr = self._trace()
        if tr.enabled:
            tr.event("lane_retire", cat="lane", track=self._track(),
                     lane=lane)
        obs.get_metrics().counter("lane_retirements_total").inc()
        return dataclasses.replace(
            lanes, steps_done=self._freeze(lanes.steps_done,
                                           jnp.int32(lane)))


def execute_sequential(problems: Sequence[Problem], plan, *, mesh=None) -> list:
    """The unbatched baseline: run each instance through its own dispatch
    sequence (``execute`` per instance, same plan). This is what a naive
    service does per user — the comparison target for ``batch_bench``."""
    from repro.exec.executor import execute
    if plan.batch != 1:
        raise ValueError("execute_sequential wants a single-instance plan")
    return [execute(p, plan, mesh=mesh) for p in problems]


def autotune_batch_sweep(instances: Sequence[Problem],
                         batches: Sequence[int] = (1, 2, 4, 8),
                         **autotune_kw) -> dict:
    """``autotune`` at several batch widths: for each B, measure the
    planner's top candidates on a B-wide :class:`BatchedProblem` built
    from the first B instances. Returns ``{B: AutotuneResult}``; each
    winning plan's *per-instance* time is ``measured_s / B`` (the curve a
    service operator reads to pick ``max_batch``)."""
    from repro.exec.executor import autotune
    instances = list(instances)
    out = {}
    for b in batches:
        if b < 1 or b > len(instances):
            raise ValueError(
                f"batch {b} needs 1..{len(instances)} instances")
        out[b] = autotune(BatchedProblem.from_instances(instances[:b]),
                          **autotune_kw)
    return out
