"""Logical-axis sharding rules + the ``smap`` shard_map wrapper.

Model code never names mesh axes. It names *logical* axes — "batch",
"seq", "embed", "heads", "ffn", "expert", ... — and this module maps them
onto whatever mesh the launcher built:

  * ``make_rules(mesh)``   — build the logical->mesh table for a mesh,
  * ``use_rules(rules)``   — activate it for a region of model code,
  * ``constrain(x, axes)`` — ``with_sharding_constraint`` through the
    active rules (identity when none are active: the same model code runs
    unmodified on one chip),
  * ``Rules.spec_for``     — PartitionSpec for an array shape with
    divisibility fallback (indivisible dims replicate, recorded in
    ``Rules.fallbacks`` for the dry-run report),
  * ``Rules.param_shardings`` — NamedSharding tree for a ParamSpec tree,
  * ``smap``               — ``jax.shard_map`` with explicit specs.

Default table (axes absent from the mesh are dropped):

  batch -> (pod, data)       embed -> data (FSDP)     layers -> replicated
  seq   -> model             vocab/heads/kv_heads/ffn/expert -> model
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist.mesh import mesh_axis_size

def smap(fn, *, mesh: Mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with explicit mesh/specs and replication checking
    off by default (the solvers' psum/ppermute patterns are manual SPMD;
    the checker predates several of them)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


# -- the rule table -------------------------------------------------------------

_DEFAULT_TABLE: dict[str, tuple[str, ...]] = {
    "batch":    ("pod", "data"),
    "seq":      ("model",),
    "vocab":    ("model",),
    "heads":    ("model",),
    "kv_heads": ("model",),
    "ffn":      ("model",),
    "expert":   ("model",),
    "embed":    ("data",),      # FSDP: shard params over the DP axis
    "state":    (),             # SSM state dim: small, keep replicated
    "head_dim": (),
    "layers":   (),             # scan dim, never sharded
}


@dataclasses.dataclass
class Rules:
    """Logical-axis -> mesh-axis mapping for one mesh.

    ``fallbacks`` records every dim that *wanted* a mesh axis but had to
    replicate, as ``(name, logical_axis, dim, reason)`` — the dry-run
    surfaces these so a silently-replicated 235B expert table is visible.
    """

    mesh: Mesh
    table: dict[str, tuple[str, ...]]
    fallbacks: list[tuple[str, str, int, str]] = dataclasses.field(
        default_factory=list)

    def _record_fallback(self, entry: tuple[str, str, int, str]):
        # spec_for runs as a tracing side effect (constrain per layer,
        # retraces) — dedupe so the dry-run report lists each once
        if entry not in self.fallbacks:
            self.fallbacks.append(entry)

    def mesh_axes_for(self, logical: Optional[str]) -> tuple[str, ...]:
        if logical is None:
            return ()
        return tuple(a for a in self.table.get(logical, ())
                     if a in self.mesh.axis_names)

    def spec_for(self, shape: Sequence[int], axes: Sequence[Optional[str]],
                 *, is_param: bool = True, name: str = "param") -> P:
        """PartitionSpec for ``shape`` with logical ``axes``.

        Per dim: take the logical axis' mesh axes, drop any already used
        by an earlier dim (an axis can appear once per spec — this is what
        makes ("expert", "embed", "ffn") come out expert-parallel with the
        ffn dim replicated), then shrink from the right until the dim size
        divides the product of the remaining axis sizes.
        """
        if not axes:
            axes = (None,) * len(shape)
        assert len(axes) == len(shape), (name, shape, axes)
        used: set[str] = set()
        entries: list[Any] = []
        for d, (size, logical) in enumerate(zip(shape, axes)):
            want = self.mesh_axes_for(logical)
            cand = tuple(a for a in want if a not in used)
            if want and not cand:
                self._record_fallback((name, logical, d, "axis-taken"))
            while cand and size % math.prod(
                    mesh_axis_size(self.mesh, a) for a in cand):
                cand = cand[:-1]
                if not cand:
                    self._record_fallback((name, logical, d, "indivisible"))
            used.update(cand)
            if not cand:
                entries.append(None)
            elif len(cand) == 1:
                entries.append(cand[0])
            else:
                entries.append(cand)
        while entries and entries[-1] is None:
            entries.pop()
        return P(*entries)

    def param_shardings(self, spec_tree):
        """NamedSharding tree for a tree of ``ParamSpec`` leaves."""
        from repro.nn.param import is_spec

        def one(path, s):
            pspec = self.spec_for(s.shape, s.axes or (None,) * len(s.shape),
                                  is_param=True,
                                  name=jax.tree_util.keystr(path))
            return NamedSharding(self.mesh, pspec)

        return jax.tree_util.tree_map_with_path(one, spec_tree,
                                                is_leaf=is_spec)


def make_rules(mesh: Mesh, *, fsdp: bool = True,
               seq_shard: bool = True) -> Rules:
    """Build the rule table for ``mesh``. ``fsdp=False`` keeps params
    replicated over the DP axis; ``seq_shard=False`` keeps activations
    unsharded along sequence between layers."""
    table = dict(_DEFAULT_TABLE)
    if not fsdp:
        table["embed"] = ()
    if not seq_shard:
        table["seq"] = ()
    return Rules(mesh=mesh, table=table)


# -- activation constraints through the active rules ----------------------------

_ACTIVE: list[Rules] = []


@contextlib.contextmanager
def use_rules(rules: Rules):
    """Activate ``rules`` for a region of (traced) model code."""
    _ACTIVE.append(rules)
    try:
        yield rules
    finally:
        _ACTIVE.pop()


def active_rules() -> Optional[Rules]:
    return _ACTIVE[-1] if _ACTIVE else None


def constrain(x, axes: Sequence[Optional[str]]):
    """``with_sharding_constraint(x)`` via the active rules' logical axes.

    Identity when no rules are active, so single-chip execution pays
    nothing and model code carries no mesh conditionals.
    """
    rules = active_rules()
    if rules is None:
        return x
    spec = rules.spec_for(x.shape, axes, is_param=False, name="activation")
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec))
