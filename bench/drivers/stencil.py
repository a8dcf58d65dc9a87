"""Stencil cells: a field advanced by chained calls of ``execute``.

Set-up makes the field on the device from the seed, plans it with
``plan(problem, chip=attached_chip())``, and jits
``execute(StencilProblem(field, spec, steps), plan)`` once, donating its
input, as a simulation that hands its field back every ``steps_per_call``
steps would. The warm-up dispatch compiles it; one more, timed, sets how
many dispatches make ``dispatch_ahead_s`` seconds of work. The window's first
input is that call's output.

The window keeps the chip fed while the host stands still: each call's
input is the previous call's output, and calls are dispatched ahead of
the one the host waits for. The TPU runtime holds about 32 dispatches in
flight, so where a call is short, ``calls_per_dispatch`` chained calls
make one dispatch (one program that runs ``execute`` that many times in
a loop, each call handing its field back to HBM as before). Every
``ahead`` dispatches a marker (one cell read back) is placed, and the host
waits for the marker before the last, so that between
``dispatch_ahead_s`` and twice that is queued. ``drain`` waits for every
dispatch sent.

What is checked, once the window has closed, each against the
configuration's plain reference run on the same input, in full:

- a dispatch of the window drawn from the seed (a reservoir of one over
  the window's dispatches): its input and output are copied on the device
  as it is sent, before the next dispatch takes the output;
- one more dispatch of the same compiled program on a fresh field drawn
  from the seed. By the end of a window the chained field is smooth, so one
  step more or less changes it by little; on a rough field one step
  changes it by about 1e-4, so a call that drops steps shows here.
"""
from __future__ import annotations

import collections
import math
import time

import numpy as np

import seeding
import work as work_counts


def build(*, config, traffic, limits, seed, devices, reference,
          control=False):
    return StencilCell(config, traffic, limits, seed, devices, reference,
                       control)


class StencilCell:
    def __init__(self, config, traffic, limits, seed, devices, reference,
                 control):
        import jax
        import jax.numpy as jnp
        from jax.sharding import SingleDeviceSharding
        from repro.core.hardware import attached_chip
        from repro.exec import StencilProblem, execute, plan
        from repro.kernels.common import get_spec

        self.name = config["name"]
        self.shape = tuple(int(n) for n in traffic["domain"])
        self.steps = int(traffic["steps_per_call"])
        self.per = int(traffic["calls_per_dispatch"])
        self.radius = int(config["radius"])
        self.points = len(config["offsets"])
        self.itemsize = np.dtype(config["dtype"]).itemsize
        spec = get_spec(config["stencil"])
        if (spec.npoints, spec.radius) != (self.points, self.radius):
            raise ValueError(f"the program's {config['stencil']} has "
                             f"{spec.npoints} points of radius {spec.radius}")
        self.limits = limits
        self.reference = reference
        self.device = devices[0]
        sharding = SingleDeviceSharding(self.device)

        dtype = jnp.dtype(config["dtype"])
        self.field = jax.jit(
            lambda k: jax.random.uniform(k, self.shape, dtype),
            out_shardings=sharding)
        key = seeding.key(seed)
        self.fresh_key = jax.random.fold_in(key, 1)
        x0 = self.field(key)
        steps = self.steps
        self.plan = plan(StencilProblem(x0, spec, steps),
                         chip=attached_chip())
        if control:
            def one(a):
                return reference.run(a, steps=steps, dtype=jnp.bfloat16)
        else:
            def one(a):
                return execute(StencilProblem(a, spec, steps), self.plan)

        def timed(a):
            if self.per == 1:
                return one(a)
            return jax.lax.fori_loop(0, self.per, lambda _, y: one(y), a)
        self.fn = jax.jit(timed, out_shardings=sharding, donate_argnums=0)
        self.mark = jax.jit(lambda a: a[0, 0])
        self.draw = seeding.rng(seed, 2)

        x = self.fn(x0)
        jax.block_until_ready((x, x.copy(), self.mark(x)))
        t = time.perf_counter()
        self.x = self.fn(x).block_until_ready()
        dispatch_s = time.perf_counter() - t
        self.ahead = max(1, math.ceil(float(traffic["dispatch_ahead_s"])
                                      / dispatch_s))
        self.marks = collections.deque()
        self.sample = None
        self.dispatches = 0
        self.calls = 0

    def describe(self) -> str:
        p = self.plan
        return (f"{self.name} {'x'.join(map(str, self.shape))}, "
                f"{self.steps} steps per call, {self.per} calls per "
                f"dispatch: tier={p.tier} "
                f"schedule={p.schedule} fuse_steps={p.fuse_steps} "
                f"cached_rows={p.cached_rows} sub_rows={p.sub_rows}; "
                f"{self.ahead} dispatches ahead")

    def call(self) -> None:
        """Send one dispatch; wait only where the queue holds twice
        ``ahead`` dispatches."""
        i = self.dispatches
        self.dispatches += 1
        self.calls += self.per
        sampled = self.draw.random() * (i + 1) < 1.0
        x_in = self.x.copy() if sampled else None
        self.x = self.fn(self.x)
        if sampled:
            self.sample = (x_in, self.x.copy())
        if self.dispatches % self.ahead == 0:
            self.marks.append(self.mark(self.x))
            if len(self.marks) > 1:
                self.marks.popleft().block_until_ready()

    def drain(self) -> None:
        self.x.block_until_ready()
        self.marks.clear()

    @property
    def attempted(self) -> int:
        return self.calls

    @property
    def failed(self) -> int:
        return 0

    def end_to_end(self, window_s: float, calls: int) -> dict:
        cells = math.prod(self.shape)
        return {"stencil_gcells_s": cells * self.steps * calls / window_s
                / 1e9}

    def info(self) -> dict:
        return {"kernel_prefix": "stencil_perks",
                "steps_per_call": self.steps,
                "work_per_call": work_counts.stencil_call(
                    self.shape, self.points, self.steps, self.itemsize)}

    def finish(self) -> dict:
        """Compare with the reference, freeing the program's state as it
        goes, so that no more than three fields and a reference are
        held."""
        import jax
        import jax.numpy as jnp

        worst = jax.jit(lambda a, b: jnp.max(jnp.abs(a - b)))
        self.x = None
        x_in, y = self.sample
        self.sample = None
        sampled = float(worst(self._reference(x_in), y))
        del x_in, y

        x = self.field(self.fresh_key)
        ref = self._reference(x)
        y = self.fn(x)
        self.fn = None
        fresh = float(worst(ref, y))
        del x, y, ref
        limit = self.limits["max_abs_err"]
        return {"max_abs_err.sampled_call": {"value": seeding.worst([sampled]),
                                             "limit": limit},
                "max_abs_err.fresh_call": {"value": seeding.worst([fresh]),
                                           "limit": limit}}

    def _reference(self, x):
        return self.reference.run(x, steps=self.steps * self.per)

