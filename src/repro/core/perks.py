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

The loops build each runner once per step *function*: a runner is cached
by ``(step_fn, steps, donate)``, and the arrays the step reads come in as
``operands``, jit arguments rather than constants, so a solver that runs
one step structure over new data dispatches the executable it already
has. The loops carry the program's spans into the profiler trace
(DESIGN.md §11): a dispatch that traces and compiles runs under
``repro.compile``, every other one under ``repro.chunk``, and every host
sync under ``repro.barrier``. Given a metrics registry, they count the
steps, host syncs and barriers that ran, and the runners built and
reused.
"""
from __future__ import annotations

import collections
import dataclasses
import enum
import functools
import threading
import weakref
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

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
        stencil (``exec/adapters.py``), an HBM streaming pass for the
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


StepFn = Callable[..., Any]  # (*operands, state) -> state


def _own(state):
    """Defensive copy of the initial state so donation never invalidates
    caller-held buffers (and duplicate leaves never double-donate)."""
    return jax.tree.map(
        lambda a: a.copy() if isinstance(a, jax.Array) else a, state)


def _signature(tree):
    """The abstract signature a jitted call is compiled for: the tree's
    structure, and each leaf's type and, for a concrete array, its
    placement."""
    leaves, treedef = jax.tree.flatten(tree)
    return treedef, tuple(
        (jax.typeof(a), None if isinstance(a, jax.core.Tracer)
         else getattr(a, "sharding", None)) for a in leaves)


class _Runner:
    """``step(*operands, state)`` applied ``n_steps`` times by ``fori_loop``
    (once, with no loop, where ``n_steps`` is None) in one jitted program
    of ``(operands, state)``. It donates ``state`` when asked, with NO
    defensive copy (callers own protecting theirs), and never
    ``operands``, which the caller's next solve reads again.

    ``step_ref()`` returns the step: the cache holds it weakly, and a
    dispatch holds it while it traces."""

    def __init__(self, step_ref: Callable[[], StepFn],
                 n_steps: Optional[int], donate: bool):
        def run_all(operands, state):
            step = step_ref()
            if n_steps is None:
                return step(*operands, state)
            return jax.lax.fori_loop(0, n_steps,
                                     lambda _, s: step(*operands, s), state)

        self.jitted = jax.jit(run_all, donate_argnums=(1,) if donate else ())
        self._built: set = set()

    def builds(self, operands, state) -> bool:
        """Whether dispatching ``(operands, state)`` traces and compiles:
        the first time this runner sees its abstract signature."""
        sig = _signature((operands, state))
        if sig in self._built:
            return False
        self._built.add(sig)
        return True


#: Loop runners, most recently used last: ``(id(step_fn), n_steps,
#: donate) -> (weak reference to step_fn, _Runner)``. An entry dies with
#: its step function, so a caller that passes a fresh closure every call
#: pins nothing it captured; none holds an operand.
_RUNNERS: "collections.OrderedDict[tuple, tuple]" = collections.OrderedDict()
_RUNNERS_SIZE = 32
_RUNNERS_LOCK = threading.Lock()


def _forget(key, ref) -> None:
    # a weakref callback: it may run inside a collection anywhere, so it
    # takes no lock and pops only its own entry
    entry = _RUNNERS.get(key)
    if entry is not None and entry[0] is ref:
        _RUNNERS.pop(key, None)


def _fused_runner(step_fn: StepFn, n_steps: Optional[int],
                  donate: bool) -> _Runner:
    """The runner of ``step_fn^n_steps``, built on the first request for
    this step function and reused while it lives (``_RUNNERS``). A step
    that takes no weak reference gets a runner of its own, uncached."""
    key = (id(step_fn), n_steps, donate)
    with _RUNNERS_LOCK:
        hit = _RUNNERS.get(key)
        if hit is not None and hit[0]() is step_fn:
            _RUNNERS.move_to_end(key)
            return hit[1]
        try:
            ref = weakref.ref(step_fn, functools.partial(_forget, key))
        except TypeError:
            return _Runner(lambda: step_fn, n_steps, donate)
        runner = _Runner(ref, n_steps, donate)
        _RUNNERS[key] = (ref, runner)
        if len(_RUNNERS) > _RUNNERS_SIZE:
            _RUNNERS.popitem(last=False)
        return runner


class _Observed:
    """One loop's spans, and its counters where a registry is given.

    A dispatch runs under ``repro.compile`` where it builds (traces,
    lowers, compiles or loads from the compilation cache, then enqueues):
    the first dispatch of a runner for an abstract signature it has not
    seen. Every other dispatch runs under ``repro.chunk``, with stat
    ``steps``, the first of a later solve on a cached runner included. A
    host sync (the read-back and decision of ``on_sync`` /
    ``on_barrier``) runs under ``repro.barrier``, with stat
    ``steps_done``. The counters are labelled with the loop's tier. A
    host-loop barrier is a dispatch; a device-loop barrier is a step,
    since the loop-carried dependency is the device-wide barrier.
    ``executor_retraces_total`` counts the dispatches that build and
    ``executor_runner_hits_total`` the runners whose first dispatch in
    this loop reused an executable.
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
            self.retraces = metrics.counter("executor_retraces_total",
                                            tier=tier)
            self.hits = metrics.counter("executor_runner_hits_total",
                                        tier=tier)

    def runner(self, step_fn: StepFn, operands: tuple,
               n_steps: Optional[int], donate: bool):
        """The cached runner of ``n_steps`` fused steps of ``step_fn``
        (one step, unlooped, where None) over ``operands``, dispatched
        under its span and counted."""
        runner = _fused_runner(step_fn, n_steps, donate)
        operands = jax.tree.map(jnp.asarray, tuple(operands))
        steps = n_steps or 1
        first = True

        def dispatch(state, _step=step_fn):  # holds what the runner traces
            nonlocal first
            # a loop's later dispatches carry the state its first returned
            build = first and runner.builds(operands, state)
            cat = "compile" if build else "chunk"
            with obs.get_tracer().span(self.name, cat=cat, track=self.track,
                                       steps=steps):
                state = runner.jitted(operands, state)
            if self.counted:
                self.steps.inc(steps)
                self.barriers.inc(steps if self.barrier_per_step else 1)
                if build:
                    self.retraces.inc()
                elif first:
                    self.hits.inc()
            first = False
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
    operands: tuple = (),
) -> Callable[[Any], Any]:
    """Baseline execution: one device dispatch per time step.

    Mirrors the traditional CUDA pattern: kernel termination is the barrier,
    and the domain is re-read from main memory at every step. Every step IS
    a host sync, so ``on_sync(state, k)`` — if given — is evaluated after
    each one; returning True stops early (the baseline tier honors a
    convergence contract at the finest possible cadence). ``metrics`` (a
    ``repro.obs.MetricsRegistry``) counts what ran (see ``_Observed``).
    ``operands`` are the step's leading arguments, ``step_fn(*operands,
    state)``: arrays passed to every dispatch, never donated.
    """
    seen = _Observed("host_loop", Execution.HOST_LOOP, metrics)
    dispatch = seen.runner(step_fn, operands, None, donate)

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


def device_loop(step_fn: StepFn, n_steps: int, *, donate: bool = True,
                metrics=None, operands: tuple = ()) -> Callable[[Any], Any]:
    """PERKS control-flow transform: the whole time loop in one dispatch.

    ``grid.sync()`` of the paper corresponds to the loop-carried data
    dependency: iteration k+1 of ``fori_loop`` cannot start before iteration
    k's state is complete. Across a mesh the dependency is carried by
    whatever collective the step function performs (halo exchange, psum),
    which is exactly the device-wide barrier semantics PERKS relies on.
    """
    seen = _Observed("device_loop", Execution.DEVICE_LOOP, metrics)
    dispatch = seen.runner(step_fn, operands, n_steps, donate)
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
    operands: tuple = (),
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
    one barrier. ``metrics`` counts what ran (see ``_Observed``);
    ``operands`` are the step's leading arguments (see ``host_loop``).
    """
    # The loop below already owns `state` (one defensive copy at entry), so
    # the inner runners donate WITHOUT re-copying per dispatch — each chunk
    # updates the same buffers in place, as the persistent scheme intends.
    seen = _Observed("chunked_loop", execution, metrics)
    inner = seen.runner(step_fn, operands, sync_every, donate)

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
    inner_rem = seen.runner(step_fn, operands, rem, donate) if rem else None

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
    operands: tuple = (),
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
    (``exec/adapters.py``, ``kernels/stencil2d.py``) implement it as
    wide-halo exchange / multi-step HBM passes instead.

    ``metrics`` (a ``repro.obs.MetricsRegistry``) counts the steps, host
    syncs and barriers that ran, and the runners built and reused, under
    the tier's name. ``operands`` are ``step_fn``'s leading arguments,
    ``step_fn(*operands, state)``: the arrays the step reads, passed to
    each dispatch so that the runner, cached by ``step_fn``, serves new
    arrays of the same shapes without a build.
    """
    kw = dict(donate=config.donate, metrics=metrics, operands=operands)
    if config.execution == Execution.HOST_LOOP:
        if config.fuse_steps > 1:
            return chunked_loop(
                step_fn, n_steps, sync_every=config.fuse_steps,
                on_sync=on_sync, execution=Execution.HOST_LOOP, **kw)
        return host_loop(step_fn, n_steps, on_sync=on_sync, **kw)
    if config.sync_every is not None and config.sync_every < n_steps:
        return chunked_loop(step_fn, n_steps, sync_every=config.sync_every,
                            on_sync=on_sync, **kw)
    return device_loop(step_fn, n_steps, **kw)


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
