"""``plan(problem)``: the one planner behind every PERKS solver.

Before this layer, *how to run* was decided by five separate entry
points — ``kernels.stencil3d.plan_resident_planes`` (VMEM occupancy),
``core.cache_policy.plan_caching`` (what-to-cache knapsack),
``core.cache_policy.plan_fuse_steps`` (temporal-blocking depth),
``solvers.stencil.plan_for`` (stencil reporting) and
``solvers.cg.plan_policy`` (Fig.-9 policy pick) — each consumed by a
different ``run_*`` signature. This module subsumes them: it enumerates
candidate :class:`~repro.exec.plan.Plan`\\ s per tier × fuse depth ×
cache assignment, prices each with the paper's performance model
(``core.perf_model``, Eqs. 5–11 generalized by ``gm_bytes_fused``) plus
a per-dispatch launch-overhead term, and returns them ranked by
projected time — not by the ad-hoc byte heuristics the old entry points
used. ``autotune`` (``repro.exec.executor``) then measures the top
candidates and picks the winner empirically.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

from repro.core.cache_policy import (
    cg_arrays,
    gm_bytes_deep,
    gm_bytes_fused,
    plan_caching,
)
from repro.core.hardware import CHIPS, Chip, TPU_V5E, attached_chip
from repro.core.perf_model import project_host_loop, sm_bytes_accessed
from repro.exec.plan import CacheDecision, Plan
from repro.exec.problem import Problem
from repro.kernels.stencil2d import (
    deep_vmem_bytes,
    default_sub_rows,
    perks_vmem_bytes,
)
from repro.kernels.stencil3d import plan_resident_planes

#: Host→device dispatch cost charged per kernel launch (the overhead the
#: paper's Fig. 3 attributes to kernel termination; O(5 µs) on current
#: stacks). HOST_LOOP pays it n_steps times, fused tiers once.
DISPATCH_OVERHEAD_S = 5e-6

#: Per-collective latency floor (one psum/ppermute round on the ICI).
COLLECTIVE_LATENCY_S = 2e-6

#: Depth ceiling for DEEP resident candidates (DESIGN.md §12). The shallow
#: schedule's r*t redundant-recompute window makes depths past ~4 a net
#: loss, so ``max_fuse`` defaults to 4 — but the wavefront schedule has no
#: such window, so when deep is legal the planner enumerates depths up to
#: max(max_fuse, DEEP_MAX_FUSE), gated only by the wavefront scratch
#: fitting in VMEM next to the resident rows.
DEEP_MAX_FUSE = 32


def _as_chip(chip) -> Chip:
    if chip is None:
        return attached_chip()
    if isinstance(chip, Chip):
        return chip
    return CHIPS[chip]


def _budget_chip(chip: Chip, budget_bytes: Optional[int]) -> Chip:
    """Override the chip's on-chip capacity (planner sensitivity studies,
    proxy-capacity regimes)."""
    if budget_bytes is None:
        return chip
    return dataclasses.replace(chip, onchip_bytes=float(budget_bytes))


def _rank(cands: list[Plan]) -> list[Plan]:
    # predicted time first; ties prefer fewer barriers (deeper fusion),
    # then more cached bytes — both directions the monotonicity contract
    # (tests/test_exec.py) relies on.
    return sorted(cands, key=lambda p: (p.predicted_s, p.barriers,
                                        -p.cached_bytes))


# -----------------------------------------------------------------------------
# Stencil candidates
# -----------------------------------------------------------------------------

def _stencil_candidates(problem, chip: Chip, mesh, *, max_fuse: int,
                        shard_axis: str, sub_rows: Optional[int],
                        batch: int = 1,
                        name: Optional[str] = None) -> list[Plan]:
    shape = problem.x.shape
    db = problem.x.dtype.itemsize
    cells = int(math.prod(shape))
    row_cells = int(math.prod(shape[1:]))
    row_bytes = row_cells * db
    domain_bytes = cells * db
    n = problem.n_steps
    r = problem.spec.radius
    B = batch
    base = project_host_loop(chip, n_steps=n, domain_cells=cells,
                             dtype_bytes=db)
    common = dict(n_steps=n, problem=name or problem.name, chip=chip.name,
                  batch=B)

    # every instance's domain is independent, so memory traffic scales by
    # B; the per-dispatch launch overhead does NOT (the whole batch rides
    # one dispatch) — which is the entire economics of the batched tier.
    cands = [
        Plan(tier="host_loop", predicted_s=B * base.t_total
             + n * DISPATCH_OVERHEAD_S, predicted_bound=base.bound, **common),
        Plan(tier="device_loop", predicted_s=B * base.t_total
             + DISPATCH_OVERHEAD_S, predicted_bound=base.bound, **common),
    ]

    # RESIDENT × fuse depth: VMEM occupancy decides the resident rows per
    # depth (the wider streaming window of deeper fusion evicts planes).
    # Each instance of a batch gets 1/B of the on-chip budget — the
    # B-scaled working set (DESIGN.md §8) — so large batches naturally
    # demote toward the loop tiers. Streamed block rows are planned per
    # schedule and depth unless the caller fixes ``sub_rows``.
    from repro.exec.batch import per_instance_chip
    chip_per_inst = per_instance_chip(chip, B)
    dtype = problem.x.dtype

    def block_rows(schedule, t):
        if sub_rows is not None:
            return sub_rows if schedule == "shallow" else max(sub_rows, r)
        return default_sub_rows(shape, dtype, problem.spec,
                                schedule=schedule, fuse_steps=t)

    def fits(footprint, sub, t):
        # the kernel's whole VMEM request with nothing resident — the
        # scoped limit it asks Mosaic for — must fit this instance's VMEM
        return footprint(shape, dtype, problem.spec, cached_rows=0,
                         sub_rows=sub, fuse_steps=t) \
            <= chip_per_inst.onchip_bytes

    t = 1
    while t <= max(1, min(max_fuse, n)):
        sub = block_rows("shallow", t)
        if not fits(perks_vmem_bytes, sub, t):
            break
        rows = plan_resident_planes(shape, db, problem.spec,
                                    chip=chip_per_inst,
                                    sub_rows=sub, fuse_steps=t)
        cached_bytes = rows * row_bytes
        gm = gm_bytes_fused(n, domain_bytes, cached_bytes,
                            row_bytes=row_bytes, radius=r, fuse_steps=t)
        t_gm = B * gm / chip.hbm_bw
        t_sm = B * sm_bytes_accessed(n, cached_bytes) / chip.onchip_bw
        bound = "main_memory" if t_gm >= t_sm else "onchip_memory"
        cands.append(Plan(
            tier="resident", fuse_steps=t, cached_rows=rows,
            sub_rows=sub,
            cache=(CacheDecision("domain_rows", B * cached_bytes,
                                 B * domain_bytes),),
            predicted_s=max(t_gm, t_sm) + DISPATCH_OVERHEAD_S,
            predicted_bound=bound, **common))
        t *= 2

    # RESIDENT × DEEP wavefront schedule (DESIGN.md §12): each streaming
    # pass reads and writes every uncached row exactly once regardless of
    # t, so depth is no longer capped by the shallow r*t recompute window.
    # The B-scaled footprint gate runs BEFORE the candidate is emitted —
    # the planner must never offer a deep plan whose wavefront buffers
    # (per-instance, so ×B across a batched dispatch) exceed the chip's
    # VMEM, and since the footprint grows monotonically in t the first
    # overflow terminates the depth sweep (batches thus demote depth
    # before resident rows).
    t = 2
    while t <= max(1, min(max(max_fuse, DEEP_MAX_FUSE), n)):
        deep_sub = block_rows("deep", t)
        if not fits(deep_vmem_bytes, deep_sub, t):
            break
        rows = plan_resident_planes(shape, db, problem.spec,
                                    chip=chip_per_inst, sub_rows=deep_sub,
                                    fuse_steps=t, schedule="deep")
        cached_bytes = rows * row_bytes
        gm = gm_bytes_deep(n, domain_bytes, cached_bytes, fuse_steps=t)
        t_gm = B * gm / chip.hbm_bw
        t_sm = B * sm_bytes_accessed(n, cached_bytes) / chip.onchip_bw
        bound = "main_memory" if t_gm >= t_sm else "onchip_memory"
        cands.append(Plan(
            tier="resident", schedule="deep", fuse_steps=t,
            cached_rows=rows, sub_rows=deep_sub,
            cache=(CacheDecision("domain_rows", B * cached_bytes,
                                 B * domain_bytes),),
            predicted_s=max(t_gm, t_sm) + DISPATCH_OVERHEAD_S,
            predicted_bound=bound, **common))
        t *= 2

    if mesh is not None:
        n_chips = int(dict(mesh.shape)[shard_axis])
        shard_rows = shape[0] // n_chips
        shard_bytes = shard_rows * row_bytes
        t = 1
        while t <= max(1, min(max_fuse, n)) and r * min(t, n) <= shard_rows:
            barriers = math.ceil(n / t)
            gm = gm_bytes_fused(n, shard_bytes, 0, row_bytes=row_bytes,
                                radius=r, fuse_steps=t)
            # one ppermute round per barrier carries EVERY instance's halo:
            # the latency floor is paid once per barrier, the payload B×.
            coll = barriers * (COLLECTIVE_LATENCY_S
                               + B * 2 * r * t * row_bytes
                               / max(chip.ici_bw_per_link, 1.0))
            cands.append(Plan(
                tier="distributed", fuse_steps=t, shard_axis=shard_axis,
                predicted_s=B * gm / chip.hbm_bw + coll + DISPATCH_OVERHEAD_S,
                predicted_bound="collective" if coll > B * gm / chip.hbm_bw
                else "main_memory", **common))
            t *= 2
    return cands


# -----------------------------------------------------------------------------
# CG candidates
# -----------------------------------------------------------------------------

def cg_policy_from_arrays(arrays, budget_bytes: int) -> dict:
    """The Fig.-9 policy decision (IMP/VEC/MIX) from a cache plan — the
    exact logic of the legacy ``solvers.cg.plan_policy``, factored here so
    both the legacy shim and the candidate generator share it. "Vectors"
    are every array that is not the operator A (for CG: r/p/x/Ap; for
    BiCGStab the seven working vectors; for GMRES the basis V rides with
    them), so one policy function serves the whole Krylov family."""
    cplan = plan_caching(arrays, budget_bytes)
    vec_frac = min(cplan.fraction_of(a.name) for a in arrays
                   if a.name != "A")
    mat_frac = cplan.fraction_of("A")
    if vec_frac < 1.0:
        policy = "IMP"          # vectors don't even fit -> rely on caches
    elif mat_frac >= 1.0:
        policy = "MIX"
    elif mat_frac > 0.0:
        policy = "MIX"          # partial matrix residency
    else:
        policy = "VEC"
    return {"policy": policy, "vector_fraction": vec_frac,
            "matrix_fraction": mat_frac,
            "traffic_saved_per_iter": cplan.traffic_saved_per_step,
            "_plan": cplan}


def _cg_candidates(problem, chip: Chip, mesh, *, shard_axis: str,
                   sync_every: Optional[int], batch: int = 1,
                   name: Optional[str] = None) -> list[Plan]:
    from repro.exec.adapters import fused_block_rows

    # B-scaled working set (DESIGN.md §8): the Krylov vectors are
    # per-instance (bytes ×B — both footprint and traffic), while the
    # matrix is SHARED by every instance of the batch: one resident copy
    # serves all B solves, and a batched SpMV streams A once per
    # iteration for the whole batch (the block-Krylov amortization).
    arrays = [
        a if not problem.array_scales_with_batch(a.name) or batch == 1
        else dataclasses.replace(a, bytes=a.bytes * batch)
        for a in problem.cacheable_arrays()
    ]
    budget = int(chip.onchip_bytes * 0.9)
    pol = cg_policy_from_arrays(arrays, budget)
    cplan = pol["_plan"]
    n = problem.n_steps
    if sync_every is None and problem.on_sync() is not None and n > 1:
        # the problem declares a convergence check (tol); DEVICE_LOOP plans
        # need host-sync points to evaluate it — default to the usual check
        # cadence, capped so at least one check lands before the end.
        # host_loop is back on the host after every dispatch and honors the
        # check natively (executor.honors_on_sync), so the cadence rides
        # along there purely as documentation of the check interval.
        sync_every = min(25, max(1, n - 1))

    total_bytes = sum(a.bytes * (a.loads_per_step + a.stores_per_step)
                      for a in arrays)
    vec_traffic = sum(a.bytes * (a.loads_per_step + a.stores_per_step)
                      for a in arrays if a.name != "A")
    cache = tuple(CacheDecision(a.array.name, a.cached_bytes, a.array.bytes)
                  for a in cplan.assignments)
    common = dict(n_steps=n, problem=name or problem.name, chip=chip.name,
                  sync_every=sync_every, batch=batch)

    cands = [
        Plan(tier="host_loop",
             predicted_s=n * (total_bytes / chip.hbm_bw
                              + DISPATCH_OVERHEAD_S), **common),
        Plan(tier="device_loop", policy="IMP",
             predicted_s=n * total_bytes / chip.hbm_bw
             + DISPATCH_OVERHEAD_S, **common),
    ]
    kind = problem.kind
    has_ell = problem.data is not None
    if has_ell and pol["vector_fraction"] >= 1.0:
        bm = fused_block_rows(problem.b.shape[0])
        # cached bytes still move through on-chip memory every iteration
        # (Eq. 7) — without this term a fully-cached solve would predict
        # a batch-independent dispatch constant and the projection gate
        # could never see a regression on small CG problems
        vec_cache = tuple(c for c in cache if c.name != "A")
        t_sm_vec = sm_bytes_accessed(n, sum(c.cached_bytes
                                            for c in vec_cache))
        if kind != "gmres":
            cands.append(Plan(
                tier="resident", policy="VEC", block_rows=bm,
                cache=vec_cache,
                predicted_s=max(n * (total_bytes - vec_traffic)
                                / chip.hbm_bw, t_sm_vec / chip.onchip_bw)
                + DISPATCH_OVERHEAD_S, **common))
        if pol["matrix_fraction"] > 0.0 and (
                kind != "gmres" or pol["matrix_fraction"] >= 1.0):
            # the GMRES cycle kernel pins the WHOLE operator next to the
            # basis (no streamed-A variant), so a partial-A MIX plan has
            # no kernel to run on — gate it out rather than lie.
            saved = cplan.traffic_saved_per_step
            t_sm_all = sm_bytes_accessed(n, sum(c.cached_bytes
                                                for c in cache))
            cands.append(Plan(
                tier="resident", policy="MIX", block_rows=bm, cache=cache,
                predicted_s=max(n * max(0.0, total_bytes - saved)
                                / chip.hbm_bw, t_sm_all / chip.onchip_bw)
                + DISPATCH_OVERHEAD_S, **common))

    if mesh is not None and has_ell:
        n_chips = int(dict(mesh.shape)[shard_axis])
        local = total_bytes / n_chips
        # psum counts per iteration: textbook CG pays 2 dependent
        # reductions, pipelined CG 1 (PR 2); textbook BiCGStab 5,
        # pipelined 3 (the stacked stabilization dots + omega
        # recurrence); a GMRES(m) cycle pays 3m+2 (two CGS2 projection
        # rounds + one norm per inner step, plus beta and the final
        # residual) and has no fused variant.
        variants = {"cg": ((False, 2), (True, 1)),
                    "bicgstab": ((False, 5), (True, 3)),
                    "gmres": ((False, 3 * getattr(problem, "m", 0) + 2),)}
        for fused, psums in variants[kind]:
            cands.append(Plan(
                tier="distributed", shard_axis=shard_axis,
                fuse_reductions=fused, policy=pol["policy"],
                predicted_s=n * (local / chip.hbm_bw
                                 + psums * COLLECTIVE_LATENCY_S)
                + DISPATCH_OVERHEAD_S, **common))
        if kind == "cg" and n > 1:
            # s-step (communication-avoiding) CG: ONE psum per s
            # iterations at the price of (2s-1)/s SpMV passes per
            # iteration — redundant traffic for fewer latency-bound
            # barriers, the Krylov face of temporal blocking.
            s = min(4, n)
            cands.append(Plan(
                tier="distributed", shard_axis=shard_axis, s_step=s,
                policy=pol["policy"],
                predicted_s=n * ((2.0 - 1.0 / s) * local / chip.hbm_bw
                                 + COLLECTIVE_LATENCY_S / s)
                + DISPATCH_OVERHEAD_S, **common))
    return cands


# -----------------------------------------------------------------------------
# ML candidates (decode attention / SSM scan, DESIGN.md §13)
# -----------------------------------------------------------------------------

def _ml_candidates(problem, chip: Chip, *, sync_every: Optional[int],
                   batch: int = 1, name: Optional[str] = None) -> list[Plan]:
    """Candidates for the ML Problems (``repro.exec.ml``): decode
    attention (KV-bytes-per-token traffic model) and the SSD scan
    (VMEM-resident state ``h``).

    The structure is shared: per-step streamed traffic from
    ``cacheable_arrays`` prices the loop tiers; the resident tier elides
    the ``carry_names`` arrays' round-trips (they live on-chip for the
    whole time loop) and is gated on ``resident_scratch_bytes`` fitting
    the per-instance VMEM (``per_instance_chip``, DESIGN.md §8); for the
    SSD scan that is the Pallas kernel's own ``vmem_limit_bytes``.
    """
    from repro.exec.batch import per_instance_chip

    # B-scaled working set: per-instance arrays (KV cache, SSM state,
    # streams) scale bytes ×B; shared ones (params, decay coefficients)
    # are read once for the whole batch.
    arrays = [
        a if not problem.array_scales_with_batch(a.name) or batch == 1
        else dataclasses.replace(a, bytes=a.bytes * batch)
        for a in problem.cacheable_arrays()
    ]
    n = problem.n_steps
    carry_names = frozenset(getattr(problem, "carry_names", ()))
    total = sum(a.bytes * (a.loads_per_step + a.stores_per_step)
                for a in arrays)
    carry = sum(a.bytes * (a.loads_per_step + a.stores_per_step)
                for a in arrays if a.name in carry_names)
    carry_bytes = sum(a.bytes for a in arrays if a.name in carry_names)

    has_sync = problem.on_sync() is not None
    if sync_every is None and has_sync and n > 1:
        # decode declares a convergence check (EOS); DEVICE_LOOP honors
        # it at barrier points. Short check cadence: retiring a finished
        # lane early is worth far more per step than a CG residual check.
        sync_every = min(8, max(1, n - 1))

    common = dict(n_steps=n, problem=name or problem.name, chip=chip.name,
                  sync_every=sync_every, batch=batch)
    cands = [
        Plan(tier="host_loop",
             predicted_s=n * (total / chip.hbm_bw + DISPATCH_OVERHEAD_S),
             predicted_bound="main_memory", **common),
        Plan(tier="device_loop",
             predicted_s=n * total / chip.hbm_bw + DISPATCH_OVERHEAD_S,
             predicted_bound="main_memory", **common),
    ]

    # RESIDENT: the whole time loop in one fused program (decode_loop /
    # the Pallas SSD kernel) with the carry pinned on-chip. Never offered
    # when the problem declares a convergence check — the fused program
    # has no host-sync points, so it cannot honor early retirement
    # (executor.honors_on_sync); EOS decode lands on device_loop+sync.
    chip_per_inst = per_instance_chip(chip, batch)
    scratch = problem.resident_scratch_bytes()
    if (not has_sync and n > 0 and scratch is not None
            and scratch <= chip_per_inst.onchip_bytes):
        t_gm = n * max(0.0, total - carry) / chip.hbm_bw
        t_sm = sm_bytes_accessed(n, carry_bytes) / chip.onchip_bw
        bound = "main_memory" if t_gm >= t_sm else "onchip_memory"
        cands.append(Plan(
            tier="resident", fuse_steps=max(1, n),
            cache=tuple(CacheDecision(a.name, a.bytes, a.bytes)
                        for a in arrays if a.name in carry_names),
            predicted_s=max(t_gm, t_sm) + DISPATCH_OVERHEAD_S,
            predicted_bound=bound, **common))
    return cands


# -----------------------------------------------------------------------------
# Public entry points
# -----------------------------------------------------------------------------

def plan_candidates(problem: Problem, *, chip=None, mesh=None,
                    budget_bytes: Optional[int] = None, max_fuse: int = 4,
                    shard_axis: str = "data", sub_rows: Optional[int] = None,
                    sync_every: Optional[int] = None,
                    batch: int = 1, ledger=None) -> list[Plan]:
    """Every candidate Plan for ``problem``, ranked by projected time.

    ``chip`` is a :class:`~repro.core.hardware.Chip` or a name from
    ``CHIPS``, by default the attached device's (``attached_chip``; on the
    CPU, ``CPU_INTERPRET``). No resident candidate is offered whose
    kernel Mosaic refuses for that chip (``Problem.resident_compiles``);
    ``budget_bytes`` overrides its on-chip capacity (e.g. the
    ``PROXY_ONCHIP_BYTES`` regime); ``mesh`` enables distributed
    candidates over ``shard_axis``; ``max_fuse`` caps temporal blocking;
    ``sub_rows`` fixes the resident stencil's streamed block rows (planned
    per schedule and depth when omitted).

    ``batch`` plans for B instances served by ONE dispatch
    (``repro.exec.batch``): per-step traffic and per-instance VMEM
    budgets scale with B, dispatch/barrier overheads do not, so tiers and
    fuse depths re-rank under the B-scaled working set. Passing a
    :class:`~repro.exec.batch.BatchedProblem` infers ``batch`` from it.

    ``ledger`` (default: the ambient ``repro.obs.get_ledger()``) re-ranks
    with measured evidence: candidates the drift ledger has timed on this
    chip/jax version outrank the purely-projected ones, ordered by their
    measured seconds (DESIGN.md §11).
    """
    from repro import obs
    from repro.exec.batch import BatchedProblem
    chip = _budget_chip(_as_chip(chip), budget_bytes)
    if max_fuse < 1:
        raise ValueError(f"max_fuse must be >= 1, got {max_fuse}")
    name = problem.name
    template = problem
    if isinstance(problem, BatchedProblem):
        if batch not in (1, problem.batch):
            raise ValueError(
                f"batch={batch} conflicts with problem.batch="
                f"{problem.batch}")
        batch = problem.batch
        template = problem.template
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    if template.kind == "stencil":
        cands = _stencil_candidates(template, chip, mesh, max_fuse=max_fuse,
                                    shard_axis=shard_axis, sub_rows=sub_rows,
                                    batch=batch, name=name)
    elif template.kind in ("cg", "bicgstab", "gmres"):
        cands = _cg_candidates(template, chip, mesh, shard_axis=shard_axis,
                               sync_every=sync_every, batch=batch, name=name)
    elif template.kind in ("decode", "ssm"):
        cands = _ml_candidates(template, chip, sync_every=sync_every,
                               batch=batch, name=name)
    else:
        raise NotImplementedError(
            f"no candidate generator for problem kind {template.kind!r}")
    # no resident plan whose kernel the chip's compiler would refuse
    cands = [c for c in cands if problem.supports(c.tier) and (
        c.tier != "resident" or template.resident_compiles(chip))]
    cands = _rank(cands)
    if ledger is None:
        ledger = obs.get_ledger()
    if ledger is not None:
        cands = ledger.rerank(problem, cands)
    tr = obs.get_tracer()
    if tr.enabled and cands:
        tr.event(f"plan:{name}", cat="plan", track="planner",
                 n_candidates=len(cands), best_tier=cands[0].tier,
                 best_predicted_s=cands[0].predicted_s, batch=batch)
    return cands


def plan(problem: Problem, *, chip=None, mesh=None,
         budget_bytes: Optional[int] = None, max_fuse: int = 4,
         shard_axis: str = "data", sub_rows: Optional[int] = None,
         sync_every: Optional[int] = None, batch: int = 1,
         ledger=None) -> Plan:
    """The planner's top candidate for ``problem``: lowest measured time
    where the drift ledger has evidence, lowest projected time otherwise."""
    return plan_candidates(
        problem, chip=chip, mesh=mesh, budget_bytes=budget_bytes,
        max_fuse=max_fuse, shard_axis=shard_axis, sub_rows=sub_rows,
        sync_every=sync_every, batch=batch, ledger=ledger)[0]


# -- legacy planner surfaces (delegated to by the solver shims) ----------------

def stencil_plan_summary(x_shape: Sequence[int], dtype_bytes: int, spec, *,
                         chip=TPU_V5E, sub_rows: int = 128,
                         fuse_steps: int = 1) -> dict:
    """Cache plan + fractions for reporting (the legacy ``plan_for`` dict).
    Host-side arithmetic on static shapes only — no device ops."""
    chip = _as_chip(chip)
    rows = plan_resident_planes(tuple(x_shape), dtype_bytes, spec, chip=chip,
                                sub_rows=sub_rows, fuse_steps=fuse_steps)
    row_elems = math.prod(x_shape[1:])
    domain = math.prod(x_shape)
    cached = rows * row_elems
    return {"cached_rows": rows, "cached_cells": cached,
            "cached_fraction": cached / domain}


def cg_policy(n_rows: Optional[int] = None, nnz: Optional[int] = None,
              dtype_bytes: int = 4, *, chip=TPU_V5E, matrix=None,
              budget_bytes: Optional[int] = None) -> dict:
    """The legacy ``plan_policy`` dict (Fig.-9 policy + fractions)."""
    from repro.core.cache_policy import cg_arrays_for
    chip = _as_chip(chip)
    if matrix is not None:
        arrays = cg_arrays_for(matrix)
    else:
        arrays = cg_arrays(n_rows, nnz, dtype_bytes)
    budget = (int(chip.onchip_bytes * 0.9) if budget_bytes is None
              else int(budget_bytes))
    out = cg_policy_from_arrays(arrays, budget)
    out.pop("_plan")
    return out
