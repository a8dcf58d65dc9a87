"""Collectives: the multi-chip analogue of the paper's device-wide barrier.

Inside a persistent kernel, PERKS separates time steps with ``grid.sync()``.
Inside ``shard_map``, the same role is played by the collective each step
performs: a halo ``ppermute`` for stencils, a ``psum`` for CG dot products,
an expert ``psum`` for MoE. Iteration k+1 cannot start before iteration k's
collective completes — that data dependency *is* the barrier (DESIGN.md §3).

Everything here runs inside ``shard_map`` bodies (named-axis collectives),
except ``sharded_decode_attention`` which wraps its own ``smap``.
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

AxisName = Union[str, Sequence[str]]


# -- thin reduction wrappers (so solvers/models import one module) ---------------

def psum(x, axis: AxisName):
    return jax.lax.psum(x, axis)


def pmean(x, axis: AxisName):
    return jax.lax.pmean(x, axis)


def pmax(x, axis: AxisName):
    return jax.lax.pmax(x, axis)


def all_gather(x, axis: str, *, tiled: bool = True, gather_dim: int = 0):
    """Gather the shards of ``x`` along ``axis`` into every shard.

    ``tiled=True`` concatenates along ``gather_dim`` (the layout the CG
    SpMV needs to index global columns); ``tiled=False`` stacks a new
    leading shard dim.
    """
    return jax.lax.all_gather(x, axis, axis=gather_dim, tiled=tiled)


# -- halo exchange ---------------------------------------------------------------

def halo_exchange(x, radius: int, axis: str, *, periodic: bool = False):
    """Exchange ``radius`` boundary rows with leading-dim neighbours.

    Shard i sends its last ``radius`` rows forward (they become shard
    i+1's top halo) and its first ``radius`` rows backward (shard i-1's
    bottom halo). Returns ``(top, bot)`` of shape ``(radius, *x.shape[1:])``.

    ``periodic=False`` leaves the outermost shards' missing halos at zero
    (``ppermute`` semantics) — correct for the Dirichlet borders used
    throughout this repo, where the global edge rows are frozen anyway.
    ``periodic=True`` wraps the ring.

    Temporal blocking (DESIGN.md §4) calls this with ``radius = r*t`` —
    one wide exchange standing in for t narrow ones. The halo still only
    comes from the *adjacent* neighbour, so the width is capped by the
    shard: ``radius <= x.shape[0]`` (checked; a silent slice-clamp here
    would corrupt results instead of failing).
    """
    if radius > x.shape[0]:
        raise ValueError(
            f"halo radius {radius} exceeds shard extent {x.shape[0]}; "
            f"lower fuse_steps or use more rows per shard")
    n = jax.lax.axis_size(axis)
    fwd = [(i, (i + 1) % n) for i in range(n if periodic else n - 1)]
    bwd = [((i + 1) % n, i) for i in range(n if periodic else n - 1)]
    if n == 1:
        z = jnp.zeros((radius,) + x.shape[1:], x.dtype)
        return (x[-radius:], x[:radius]) if periodic else (z, z)
    top = jax.lax.ppermute(x[-radius:], axis, fwd)   # from neighbour i-1
    bot = jax.lax.ppermute(x[:radius], axis, bwd)    # from neighbour i+1
    return top, bot


# -- sharded flash decode --------------------------------------------------------

def sharded_decode_attention(q, k, v, *, mesh: Mesh, seq_axis: str = "model",
                             length: Optional[jax.Array] = None):
    """GQA decode attention with the KV cache sharded along sequence.

    q (B, Hq, D); k, v (B, S, Hkv, D) sharded on S over ``seq_axis``.
    Each shard computes attention over its KV slice with a local running
    max/sum, then one log-sum-exp combine (pmax + two psums) merges the
    partial softmaxes — flash-decode's split-KV reduction, with the
    cross-chip psum as the barrier. Matches ``ref.decode_attention``.
    """
    from repro.dist.sharding import smap

    B, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    if length is None:
        length = jnp.full((B,), S, jnp.int32)

    def local(q, k_l, v_l, length):
        s_l = k_l.shape[1]
        offset = jax.lax.axis_index(seq_axis) * s_l
        qg = q.reshape(B, Hkv, g, D)
        logits = jnp.einsum("bkgd,bskd->bkgs", qg, k_l) / jnp.sqrt(
            D).astype(q.dtype)
        pos = offset + jnp.arange(s_l)
        mask = pos[None, :] < length[:, None]                     # (B, s_l)
        logits = jnp.where(mask[:, None, None, :], logits.astype(jnp.float32),
                           -jnp.inf)
        m = jax.lax.pmax(logits.max(axis=-1), seq_axis)           # (B,Hkv,g)
        # fully-masked shards are all -inf; exp(-inf - m) underflows to 0,
        # and the nan from (-inf) - (-inf) is zeroed explicitly
        w = jnp.exp(logits - m[..., None])
        w = jnp.where(jnp.isfinite(logits), w, 0.0)
        denom = jax.lax.psum(w.sum(axis=-1), seq_axis)            # (B,Hkv,g)
        num = jax.lax.psum(
            jnp.einsum("bkgs,bskd->bkgd", w.astype(q.dtype), v_l), seq_axis)
        out = num / denom[..., None].astype(q.dtype)
        return out.reshape(B, Hq, D)

    kv_spec = P(None, seq_axis, None, None)
    return smap(local, mesh=mesh,
                in_specs=(P(), kv_spec, kv_spec, P()),
                out_specs=P())(q, k, v, length)
