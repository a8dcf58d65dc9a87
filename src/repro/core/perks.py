"""PERKS: the persistent execution model, as composable JAX combinators.

The paper's contribution is an *execution scheme*, not a solver: take an
iterative method ``x_{k+1} = F(x_k)`` whose reference GPU implementation is

    host loop:  for k in range(N):  launch kernel F   (barrier = relaunch)

and transform it so the time loop lives on the device, with the inter-step
state held in on-chip memory instead of round-tripping through device memory.

On TPU/JAX this maps to three execution tiers (see DESIGN.md §2):

``HOST_LOOP``
    The baseline: one ``jit`` dispatch per time step. Inter-step state is
    materialised in HBM between dispatches and every step re-reads it —
    exactly the CUDA host-side loop of Fig. 3 (left).

``DEVICE_LOOP``
    The time loop is moved inside a single ``jit`` region as a
    ``lax.fori_loop``/``lax.scan`` with **donated** carries. One dispatch for
    all N steps; XLA keeps the carry in place (no dispatch overhead, no
    host sync, buffer reuse). This is the PERKS *control-flow* transform;
    on TPU it alone removes the per-step launch + output re-load that the
    paper attributes to kernel termination.

``RESIDENT``
    The full PERKS scheme: the step function is a Pallas kernel whose body
    contains the time loop, with the (subset of the) domain pinned in VMEM
    ``scratch_shapes`` across iterations — HBM is touched only for the
    initial load, the final store, and the per-step halo/uncached traffic.
    Kernels under ``repro.kernels`` implement this tier.

All tiers compute bit-identical results for the same step function (the
barrier semantics of the host loop are preserved: step k+1 only ever sees
completed step-k output), which the test-suite asserts.

The loops carry the program's spans into the profiler trace (DESIGN.md
§11): every dispatch of a runner runs under ``repro.compile`` (its first)
or ``repro.chunk`` (the rest), and every host sync under
``repro.barrier``. Given a metrics registry, they count the steps, host
syncs and barriers that ran.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Any, Callable, Optional

import jax

from repro import obs


class Execution(enum.Enum):
    HOST_LOOP = "host_loop"      # paper's baseline (one launch per step)
    DEVICE_LOOP = "device_loop"  # time loop fused into one jit region
    RESIDENT = "resident"        # Pallas kernel w/ VMEM-resident domain


@dataclasses.dataclass(frozen=True)
class PerksConfig:
    """Knobs of the persistent execution scheme.

    Attributes:
      execution: which tier to run (see module docstring).
      sync_every: fuse this many time steps per device dispatch, returning to
        the host in between (PERKS with periodic host sync — used for e.g.
        convergence checks in CG; ``None`` fuses all steps).
      fuse_steps: temporal blocking (DESIGN.md §4): advance this many time
        steps per *barrier*. What the barrier is depends on the tier — a
        host dispatch for HOST_LOOP, a halo exchange for the distributed
        stencil (``solvers/stencil.py``), an HBM streaming pass for the
        RESIDENT kernels (``kernels/stencil2d.py``). The consumer pays for
        the fusion with a ``radius * fuse_steps`` wide halo that is
        redundantly recomputed (arXiv:2306.03336's deep temporal blocking);
        barrier count drops from N to ceil(N / fuse_steps).
      donate: donate the state buffers to each dispatch. Donation is what
        lets XLA update the domain in place instead of allocating a fresh
        output each step — the DEVICE_LOOP analogue of "the kernel never
        terminates so its buffers never die".
    """

    execution: Execution = Execution.DEVICE_LOOP
    sync_every: Optional[int] = None
    fuse_steps: int = 1
    donate: bool = True

    def __post_init__(self):
        if self.fuse_steps < 1:
            raise ValueError(f"fuse_steps must be >= 1, got {self.fuse_steps}")
        if self.sync_every is not None and self.sync_every < 1:
            raise ValueError(f"sync_every must be >= 1, got {self.sync_every}")


StepFn = Callable[[Any], Any]  # state -> state


def _jit_step(step_fn: StepFn, donate: bool):
    return jax.jit(step_fn, donate_argnums=(0,) if donate else ())


def _own(state):
    """Defensive copy of the initial state so donation never invalidates
    caller-held buffers (and duplicate leaves never double-donate)."""
    return jax.tree.map(
        lambda a: a.copy() if isinstance(a, jax.Array) else a, state)


class _Observed:
    """One loop's spans, and its counters where a registry is given.

    A dispatch runs under ``repro.compile`` if it is its runner's first
    (trace, lower, compile or cache load, then enqueue) and under
    ``repro.chunk`` otherwise, with stat ``steps``; a host sync (the
    read-back and decision of ``on_sync`` / ``on_barrier``) runs under
    ``repro.barrier``, with stat ``steps_done``. The counters are labelled
    with the loop's tier. A host-loop barrier is a dispatch; a device-loop
    barrier is a step, since the loop-carried dependency is the
    device-wide barrier.
    """

    def __init__(self, name: str, execution: Execution, metrics=None):
        self.name = name
        self.track = f"tier:{execution.value}"
        self.barrier_per_step = execution is not Execution.HOST_LOOP
        self.counted = metrics is not None
        if self.counted:
            tier = execution.value
            self.steps = metrics.counter("executor_steps_total", tier=tier)
            self.syncs = metrics.counter("executor_host_syncs_total",
                                         tier=tier)
            self.barriers = metrics.counter("executor_barriers_total",
                                            tier=tier)

    def runner(self, jitted, steps: int):
        """``jitted`` (``steps`` fused steps a call), dispatched under its
        span and counted."""
        first = True

        def dispatch(state):
            nonlocal first
            cat, first = ("compile" if first else "chunk"), False
            with obs.get_tracer().span(self.name, cat=cat, track=self.track,
                                       steps=steps):
                state = jitted(state)
            if self.counted:
                self.steps.inc(steps)
                self.barriers.inc(steps if self.barrier_per_step else 1)
            return state

        return dispatch

    def host_sync(self, done: int):
        """The span of one host sync after ``done`` steps; counts it."""
        if self.counted:
            self.syncs.inc()
        return obs.get_tracer().span(self.name, cat="barrier",
                                     track=self.track, steps_done=done)


def host_loop(
    step_fn: StepFn,
    n_steps: int,
    *,
    donate: bool = True,
    on_sync: Optional[Callable[[Any, int], bool]] = None,
    metrics=None,
) -> Callable[[Any], Any]:
    """Baseline execution: one device dispatch per time step.

    Mirrors the traditional CUDA pattern: kernel termination is the barrier,
    and the domain is re-read from main memory at every step. Every step IS
    a host sync, so ``on_sync(state, k)`` — if given — is evaluated after
    each one; returning True stops early (the baseline tier honors a
    convergence contract at the finest possible cadence). ``metrics`` (a
    ``repro.obs.MetricsRegistry``) counts what ran (see ``_Observed``).
    """
    seen = _Observed("host_loop", Execution.HOST_LOOP, metrics)
    dispatch = seen.runner(_jit_step(step_fn, donate), 1)

    def run(state):
        if donate:
            state = _own(state)
        for k in range(n_steps):
            state = dispatch(state)
            if on_sync is not None:
                with seen.host_sync(k + 1):
                    stop = on_sync(state, k + 1)
                if stop:
                    break
        return state

    return run


def _fused_runner(step_fn: StepFn, n_steps: int, donate: bool):
    """Jitted ``step_fn^n_steps`` via fori_loop; donates its input buffers
    when asked, with NO defensive copy — callers own protecting theirs."""

    def run_all(state):
        return jax.lax.fori_loop(0, n_steps, lambda _, s: step_fn(s), state)

    return jax.jit(run_all, donate_argnums=(0,) if donate else ())


def device_loop(step_fn: StepFn, n_steps: int, *, donate: bool = True,
                metrics=None) -> Callable[[Any], Any]:
    """PERKS control-flow transform: the whole time loop in one dispatch.

    ``grid.sync()`` of the paper corresponds to the loop-carried data
    dependency: iteration k+1 of ``fori_loop`` cannot start before iteration
    k's state is complete. Across a mesh the dependency is carried by
    whatever collective the step function performs (halo exchange, psum),
    which is exactly the device-wide barrier semantics PERKS relies on.
    """
    seen = _Observed("device_loop", Execution.DEVICE_LOOP, metrics)
    dispatch = seen.runner(_fused_runner(step_fn, n_steps, donate), n_steps)
    return (lambda state: dispatch(_own(state))) if donate else dispatch


def chunked_loop(
    step_fn: StepFn,
    n_steps: Optional[int],
    *,
    sync_every: int,
    donate: bool = True,
    on_sync: Optional[Callable[[Any, int], bool]] = None,
    on_barrier: Optional[Callable[[Any, int], tuple[Any, bool]]] = None,
    execution: Execution = Execution.DEVICE_LOOP,
    metrics=None,
) -> Callable[[Any], Any]:
    """PERKS with periodic host synchronisation.

    Fuses ``sync_every`` steps per dispatch and calls ``on_sync(state, k)``
    between dispatches (e.g. a CG convergence check); returning True stops
    early. This matches how a production PERKS solver is actually run: the
    persistent kernel owns the inner loop, the host owns termination.

    ``n_steps`` need not divide by ``sync_every``: the final dispatch fuses
    only the remaining steps, so the total is exactly ``n_steps`` (and the
    dispatch count is ceil(n_steps / sync_every)).

    ``on_barrier(state, k) -> (state, stop)`` is the *scheduler* hook: unlike
    ``on_sync`` it may REPLACE the state at the barrier (the continuous-
    batching engine admits/retires lanes there), and it runs before
    ``on_sync``. With ``n_steps=None`` the loop is open-ended — it runs one
    fused chunk per barrier until ``on_barrier`` says stop (required in that
    mode); the compiled chunk runner persists across every barrier, so
    membership can churn while the dispatch stays hot.

    ``execution`` is HOST_LOOP where the chunks stand for fused host-loop
    steps (``persistent`` with ``fuse_steps`` > 1): each dispatch is then
    one barrier. ``metrics`` counts what ran (see ``_Observed``).
    """
    # The loop below already owns `state` (one defensive copy at entry), so
    # the inner runners donate WITHOUT re-copying per dispatch — each chunk
    # updates the same buffers in place, as the persistent scheme intends.
    seen = _Observed("chunked_loop", execution, metrics)
    inner = seen.runner(_fused_runner(step_fn, sync_every, donate),
                        sync_every)

    if n_steps is None:
        if on_barrier is None:
            raise ValueError(
                "open-ended chunked_loop (n_steps=None) needs an on_barrier "
                "scheduler callback to terminate it")

        def run_open(state):
            if donate:
                state = _own(state)
            done = 0
            while True:
                state = inner(state)
                done += sync_every
                with seen.host_sync(done):
                    state, stop = on_barrier(state, done)
                if stop:
                    return state

        return run_open

    rem = n_steps % sync_every
    inner_rem = (seen.runner(_fused_runner(step_fn, rem, donate), rem)
                 if rem else None)

    def run(state):
        if donate:
            state = _own(state)
        done = 0
        while done < n_steps:
            chunk = min(sync_every, n_steps - done)
            state = (inner if chunk == sync_every else inner_rem)(state)
            done += chunk
            if on_barrier is None and on_sync is None:
                continue
            with seen.host_sync(done):
                stop = False
                if on_barrier is not None:
                    state, stop = on_barrier(state, done)
                if not stop and on_sync is not None:
                    stop = on_sync(state, done)
            if stop:
                break
        return state

    return run


def persistent(
    step_fn: StepFn,
    n_steps: int,
    config: PerksConfig = PerksConfig(),
    *,
    on_sync: Optional[Callable[[Any, int], bool]] = None,
    metrics=None,
) -> Callable[[Any], Any]:
    """Build a runner for ``n_steps`` applications of ``step_fn`` under the
    requested execution tier. The RESIDENT tier is kernel-specific and is
    selected by passing a step function that already wraps a resident Pallas
    kernel (see ``repro.kernels.ops``); at this level it behaves like
    DEVICE_LOOP with ``sync_every`` = kernel's fused step count.

    ``config.fuse_steps`` > 1 under HOST_LOOP fuses that many steps per
    dispatch (the dispatch *is* the barrier there), cutting barrier count to
    ceil(n_steps / fuse_steps). DEVICE_LOOP is already fully fused, so the
    knob is a no-op at this level — the distributed/RESIDENT consumers
    (``solvers/stencil.py``, ``kernels/stencil2d.py``) implement it as
    wide-halo exchange / multi-step HBM passes instead.

    ``metrics`` (a ``repro.obs.MetricsRegistry``) counts the steps, host
    syncs and barriers that ran, under the tier's name.
    """
    if config.execution == Execution.HOST_LOOP:
        if config.fuse_steps > 1:
            return chunked_loop(
                step_fn, n_steps, sync_every=config.fuse_steps,
                donate=config.donate, on_sync=on_sync,
                execution=Execution.HOST_LOOP, metrics=metrics,
            )
        return host_loop(step_fn, n_steps, donate=config.donate,
                         on_sync=on_sync, metrics=metrics)
    if config.sync_every is not None and config.sync_every < n_steps:
        return chunked_loop(
            step_fn, n_steps, sync_every=config.sync_every,
            donate=config.donate, on_sync=on_sync, metrics=metrics,
        )
    return device_loop(step_fn, n_steps, donate=config.donate,
                       metrics=metrics)


def scan_loop(
    step_fn: Callable[[Any, Any], tuple[Any, Any]],
    n_steps: int,
    *,
    donate: bool = True,
) -> Callable[[Any], tuple[Any, Any]]:
    """Like ``device_loop`` but for steps with per-step outputs (lax.scan).

    Used by the persistent decode loop (per-token sampled ids are stacked
    outputs) and by trainers that fuse K optimizer steps per dispatch.
    """

    def run_all(state):
        return jax.lax.scan(lambda s, _: step_fn(s, None), state, None, length=n_steps)

    jitted = jax.jit(run_all, donate_argnums=(0,) if donate else ())
    return (lambda state: jitted(_own(state))) if donate else jitted
