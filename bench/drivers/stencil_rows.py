"""Row-sharded stencil cells: a field too large for one chip, split by
rows over the cell's chips and advanced by chained calls of ``execute``.

Set-up makes the field on the chips from the seed, one band of rows on
each (``NamedSharding`` over a one-axis mesh of the cell's devices), plans
it with ``plan(problem, chip=attached_chip(), mesh=mesh)`` and refuses to
go on unless the planner picked the ``distributed`` tier: a planner that
offers a one-chip plan for a field no chip holds fails here, at once,
instead of running out of memory. The rest is the one-chip stencil cell's
(``stencil.py``): ``execute`` jitted once with its input donated, the same
window of chained calls dispatched ``dispatch_ahead_s`` ahead, the same
sampled and fresh calls compared in full with the configuration's plain
reference, which runs band by band (``run_banded``), since the whole field
does not fit one chip either. A sampled call's copies replace the last
sample's, which is freed first, so a chip holds at most the field, its
temporaries and two copies of its band.
"""
from __future__ import annotations

import collections
import importlib.util
import math
import pathlib
import time

import seeding

_spec = importlib.util.spec_from_file_location(
    "bench_drivers_stencil", pathlib.Path(__file__).with_name("stencil.py"))
_stencil = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_stencil)
StencilCell = _stencil.StencilCell


def build(*, config, traffic, limits, seed, devices, reference,
          control=False):
    return RowShardedCell(config, traffic, limits, seed, devices, reference,
                          control)


class RowShardedCell(StencilCell):
    def __init__(self, config, traffic, limits, seed, devices, reference,
                 control):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
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
        part = config["partition"]
        self.shards = int(part["chips"])
        if int(part["array_axis"]) != 0 or len(devices) != self.shards:
            raise ValueError(f"{self.name} splits the leading axis over "
                             f"{self.shards} chips; {len(devices)} given")
        spec = get_spec(config["stencil"])
        if (spec.npoints, spec.radius) != (self.points, self.radius):
            raise ValueError(f"the program's {config['stencil']} has "
                             f"{spec.npoints} points of radius {spec.radius}")
        self.limits = limits
        self.reference = reference
        mesh = Mesh(np.array(devices), ("data",))
        sharding = NamedSharding(mesh, P("data", None))

        dtype = jnp.dtype(config["dtype"])
        self.field = jax.jit(
            lambda k: jax.random.uniform(k, self.shape, dtype),
            out_shardings=sharding)
        key = seeding.key(seed)
        self.fresh_key = jax.random.fold_in(key, 1)
        x0 = self.field(key)
        steps = self.steps
        self.plan = plan(StencilProblem(x0, spec, steps),
                         chip=attached_chip(), mesh=mesh)
        if self.plan.tier != "distributed":
            raise RuntimeError(
                f"{self.name}: the planner picked tier={self.plan.tier} for a "
                f"{'x'.join(map(str, self.shape))} field over "
                f"{self.shards} chips, not distributed")

        def one(a):
            return execute(StencilProblem(a, spec, steps), self.plan,
                           mesh=mesh)

        def timed(a):
            if self.per == 1:
                return one(a)
            return jax.lax.fori_loop(0, self.per, lambda _, y: one(y), a)
        if control:
            self.fn = lambda a: reference.run_banded(
                a, steps=steps * self.per, bands=self.shards,
                dtype=jnp.bfloat16)
        else:
            self.fn = jax.jit(timed, out_shardings=sharding,
                              donate_argnums=0)
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
        return (f"{self.name} {'x'.join(map(str, self.shape))} over "
                f"{self.shards} chips, {self.steps} steps per call, "
                f"{self.per} calls per dispatch: tier={p.tier} "
                f"shards={self.shards} shard_rows={self.shape[0] // self.shards} "
                f"fuse_steps={p.fuse_steps} inner_tier={p.inner_tier}; "
                f"{self.ahead} dispatches ahead")

    def call(self) -> None:
        """Send one dispatch as ``StencilCell.call`` does, except that a
        sampled one frees the last sample before it copies its input."""
        i = self.dispatches
        self.dispatches += 1
        self.calls += self.per
        sampled = self.draw.random() * (i + 1) < 1.0
        if sampled:
            self.sample = None
            x_in = self.x.copy()
        self.x = self.fn(self.x)
        if sampled:
            self.sample = (x_in, self.x.copy())
        if self.dispatches % self.ahead == 0:
            self.marks.append(self.mark(self.x))
            if len(self.marks) > 1:
                self.marks.popleft().block_until_ready()

    def info(self) -> dict:
        info = super().info()
        del info["kernel_prefix"]       # no stencil_perks kernel runs
        return info

    def _reference(self, x):
        return self.reference.run_banded(x, steps=self.steps * self.per,
                                         bands=self.shards)
