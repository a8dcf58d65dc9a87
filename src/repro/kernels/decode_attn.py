"""GQA decode attention (flash-decode): the memory-bound hot spot of the
``decode_*`` shapes, executed PERKS-style.

Single-token decode is the LM instance of the paper's iterative pattern:
per step the KV cache (hundreds of GB across the mesh) is streamed once and
the arithmetic intensity is O(1) — exactly the memory-bound regime PERKS
targets. The kernel streams KV blocks HBM->VMEM while the *iteration state*
(running max ``m``, normaliser ``l``, weighted accumulator ``acc`` — the
online-softmax carry) stays resident in VMEM scratch across the whole sweep,
never touching HBM.

Grid: (batch, kv-blocks), kv innermost so the scratch carry is reused
sequentially; at the last kv block the normalised output is written once.

Oracle: ``repro.kernels.ref.decode_attention``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import VMEM_HEADROOM_BYTES, vmem_bytes


def _decode_kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *, blocks: int):
    sb = pl.program_id(1)

    @pl.when(sb == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, -jnp.inf)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0].astype(jnp.float32)            # (Hq, D)
    k = k_ref[0].astype(jnp.float32)            # (Sb, Hkv, D)
    v = v_ref[0].astype(jnp.float32)            # (Sb, Hkv, D)
    hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qg = q.reshape(hkv, g, d) / jnp.sqrt(d).astype(jnp.float32)

    logits = jnp.einsum("kgd,skd->kgs", qg, k)  # (Hkv, G, Sb)
    m_prev, l_prev = m_s[...], l_s[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)             # rescale old accumulator
    p = jnp.exp(logits - m_new[..., None])      # (Hkv, G, Sb)
    l_new = l_prev * alpha + p.sum(axis=-1)
    acc_s[...] = acc_s[...] * alpha[..., None] + jnp.einsum("kgs,skd->kgd", p, v)
    m_s[...] = m_new
    l_s[...] = l_new

    @pl.when(sb == blocks - 1)
    def _finalize():
        out = acc_s[...] / l_s[...][..., None]
        o_ref[0] = out.reshape(hq, d).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    block_s: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """q (B, Hq, D); k, v (B, S, Hkv, D) — full-cache single-token decode.
    Returns (B, Hq, D)."""
    bsz, hq, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    assert hq % hkv == 0
    sb = min(block_s, s)
    assert s % sb == 0, "pad cache length to a multiple of block_s"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    blocks = s // sb
    g = hq // hkv
    f32 = jnp.float32
    # double-buffered q/k/v/out blocks, the f32 carry, the step's f32
    # copies of k/v and its logits/probabilities
    need = (2 * (2 * vmem_bytes((1, hq, d), q.dtype)
                 + 2 * vmem_bytes((1, sb, hkv, d), k.dtype))
            + 2 * vmem_bytes((hkv, g), f32) + vmem_bytes((hkv, g, d), f32)
            + 2 * vmem_bytes((sb, hkv, d), f32)
            + 2 * vmem_bytes((hkv, g, sb), f32))
    return pl.pallas_call(
        functools.partial(_decode_kernel, blocks=blocks),
        grid=(bsz, blocks),
        out_shape=jax.ShapeDtypeStruct((bsz, hq, d), q.dtype),
        in_specs=[
            pl.BlockSpec((1, hq, d), lambda b, i: (b, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, sb, hkv, d), lambda b, i: (b, i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, sb, hkv, d), lambda b, i: (b, i, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, hq, d), lambda b, i: (b, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((hkv, g), jnp.float32),
            pltpu.VMEM((hkv, g), jnp.float32),
            pltpu.VMEM((hkv, g, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=need + VMEM_HEADROOM_BYTES),
        name="decode_attention",
        interpret=interpret,
    )(q, k, v)
