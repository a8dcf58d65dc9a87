"""Mamba2 SSD chunk scan as a PERKS kernel: the SSM state never leaves VMEM.

The SSD recurrence is *literally* the paper's Eq. 1 — ``h_{t+1} = F(h_t)``
iterated along the sequence — and the baseline execution materialises the
inter-chunk state to HBM between chunk kernels. Here the chunk loop is the
Pallas grid (sequential on a TensorCore) and the state ``h`` lives in a VMEM
scratch accumulator that persists across grid steps: HBM sees x/B/C/dt
streamed in once and y streamed out once; the state pays zero HBM traffic.

Math (per head h; chunk length C; cum[i] = sum_{k<=i} dt_k * a_h):

  intra:  y[i] += sum_{j<=i} e^{cum[i]-cum[j]} dt_j (c_i . b_j) x_j
  cross:  y[i] += e^{cum[i]} c_i . h_prev
  state:  h    = e^{cum[C-1]} h_prev
               + sum_j e^{cum[C-1]-cum[j]} dt_j outer(b_j, x_j)
  skip:   y[i] += d_h * x[i]

Oracle: ``repro.kernels.ref.ssm_scan`` (plain per-step recurrence).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import VMEM_HEADROOM_BYTES, vmem_bytes


def _ssd_kernel(x_ref, dt_ref, a_ref, bt_ref, c_ref, d_ref, y_ref, h_s):
    """One chunk, head by head. Head-major layout so that every product is
    a 2-D matmul and every per-head access a leading-axis or sublane
    index: x/y (H, C, P), dt (H, C), a (H, 1), d (H, P), b transposed
    (N, C), c (C, N), state (H, N, P). No value is broadcast along both
    tile axes at once, which Mosaic does not lower."""
    c_idx = pl.program_id(0)

    @pl.when(c_idx == 0)
    def _init():
        h_s[...] = jnp.zeros_like(h_s)

    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    n_heads, ck, p = x_ref.shape
    bt = bt_ref[...].astype(f32)            # (N, C)
    c = c_ref[...].astype(f32)              # (C, N)
    scores = jnp.dot(c, bt, precision=hi)   # (C, C)  c_i . b_j
    row = jax.lax.broadcasted_iota(jnp.int32, (ck, ck), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (ck, ck), 1)
    causal = row >= col                     # j <= i
    lower = causal.astype(f32)
    upper = (row <= col).astype(f32)
    # cum[i] broadcast along a row: (lower * g) @ ones. Prefix sums are
    # products with triangular masks (Mosaic lowers no cumsum) and every
    # operand stays 2-D.
    ones = jnp.ones((ck, max(ck, p)), f32)

    def head(h, carry):
        dt = dt_ref[pl.ds(h, 1), :].astype(f32)            # (1, C)
        g = dt * a_ref[pl.ds(h, 1), :].astype(f32)         # (1, C) log-decay
        cum = jnp.dot(g, upper, precision=hi)              # (1, C) inclusive
        cum_i = jnp.dot(lower * g, ones, precision=hi)     # (C, W) cum[i]
        # intra-chunk (quadratic in C, on the MXU). Mask BEFORE exp: the
        # upper triangle has cum[i]-cum[j] > 0, which overflows exp for
        # long chunks; masking after would give inf * 0 = NaN.
        li = jnp.where(causal, cum_i[:, :ck] - cum, -jnp.inf)
        m = jnp.exp(li) * scores * dt                      # (i, j)
        x = x_ref[h].astype(f32)                           # (C, P)
        y = jnp.dot(m, x, precision=hi)
        # cross-chunk from the resident state, then the skip connection
        h_prev = h_s[h]                                    # (N, P)
        y += jnp.exp(cum_i[:, :p]) * jnp.dot(c, h_prev, precision=hi)
        y += d_ref[pl.ds(h, 1), :].astype(f32) * x
        # state update (stays in VMEM)
        w = jnp.exp(cum[:, ck - 1:] - cum) * dt            # (1, C)
        upd = jnp.dot(bt * w, x, precision=hi)             # (N, P)
        decay = jnp.exp(cum_i[ck - 1:, :p])                # (1, P) e^{cum[C-1]}
        h_s[h] = decay * h_prev + upd
        y_ref[h] = y.astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n_heads, head, 0)


def chunk_compiles(t: int, chunk: int) -> bool:
    """Whether Mosaic takes this chunk: the (H, chunk) and (N, chunk)
    blocks put it on the lane axis, so it must fill whole 128-lane tiles
    or be the whole sequence."""
    return chunk == t or chunk % 128 == 0


def ssm_vmem_bytes(x, dt, a, b, c, d, *, chunk: int) -> int:
    """The kernel's VMEM request (``vmem_limit_bytes``): double-buffered
    blocks, the f32 state, one head's temporaries, headroom. Takes arrays
    or ShapeDtypeStructs; the planner gates the resident tier on it."""
    t, h, p = x.shape
    n = b.shape[-1]
    ck = min(chunk, t)
    f32 = jnp.float32
    blocks = [((h, ck, p), x.dtype), ((h, ck), dt.dtype), ((h, 1), a.dtype),
              ((n, ck), b.dtype), ((ck, n), c.dtype), ((h, p), d.dtype),
              ((h, ck, p), x.dtype)]
    need = (2 * sum(vmem_bytes(s, dt_) for s, dt_ in blocks)
            + vmem_bytes((h, n, p), f32)
            + 12 * vmem_bytes((ck, max(ck, p)), f32))
    return need + VMEM_HEADROOM_BYTES


def ssm_scan(
    x: jax.Array,
    dt: jax.Array,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    d: jax.Array,
    *,
    chunk: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Single-sequence SSD scan. Shapes as in ``ref.ssm_scan``:
    x (T,H,P), dt (T,H), a (H,), b (T,N), c (T,N), d (H,). Returns (T,H,P).
    vmap over a batch axis for batched use (see kernels/ops.py).
    """
    t, h, p = x.shape
    n = b.shape[-1]
    ck = min(chunk, t)
    assert t % ck == 0, "pad T to a multiple of chunk"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not interpret and not chunk_compiles(t, ck):
        raise ValueError(f"on a TPU the SSD chunk must be a multiple of 128 "
                         f"or the whole sequence (T={t}); got {ck}")
    grid = (t // ck,)
    vmem = ssm_vmem_bytes(x, dt, a, b, c, d, chunk=ck)
    y = pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        out_shape=jax.ShapeDtypeStruct((h, t, p), x.dtype),
        in_specs=[
            pl.BlockSpec((h, ck, p), lambda i: (0, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((h, ck), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((h, 1), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((n, ck), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((ck, n), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((h, p), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((h, ck, p), lambda i: (0, i, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((h, n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        name="ssm_scan",
        interpret=interpret,
    )(x.transpose(1, 0, 2), dt.T, a.reshape(h, 1), b.T, c,
      jnp.broadcast_to(d.reshape(h, 1), (h, p)))
    return y.transpose(1, 0, 2)
