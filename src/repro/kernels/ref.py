"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernel tests ``assert_allclose`` against,
and the implementations the models fall back to off-TPU (robust HLO for the
dry-run). No Pallas, no scratch, no DMA — just jnp.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import StencilSpec


# -- stencils ---------------------------------------------------------------

def stencil_step(x: jax.Array, spec: StencilSpec) -> jax.Array:
    """One time step: interior updated, outermost ``radius`` cells frozen.

    Written without the kernels' ``StencilSpec.apply_rows``, so the tests
    compare two implementations: the whole interior is one shifted-slice
    sum, wrapped in the frozen border one axis at a time."""
    r = spec.radius
    acc = None
    for off, w in zip(spec.offsets, spec.weights):
        term = w * x[tuple(slice(r + d, n - r + d)
                           for d, n in zip(off, x.shape))]
        acc = term if acc is None else acc + term
    out = acc.astype(x.dtype)
    for ax in reversed(range(x.ndim)):
        n = x.shape[ax]
        inner = tuple(slice(r, m - r) for m in x.shape[:ax])
        lo = x[inner + (slice(0, r),)]
        hi = x[inner + (slice(n - r, n),)]
        out = jnp.concatenate([lo, out, hi], axis=ax)
    return out


def stencil_run(x: jax.Array, spec: StencilSpec, steps: int) -> jax.Array:
    """``steps`` time steps via lax.scan (oracle for the PERKS kernels)."""
    def body(s, _):
        return stencil_step(s, spec), None
    y, _ = jax.lax.scan(body, x, None, length=steps)
    return y


# -- block-ELL SpMV ----------------------------------------------------------

def spmv_ell(data: jax.Array, cols: jax.Array, x: jax.Array) -> jax.Array:
    """y = A @ x for A in ELL format.

    data: (n_rows, K) padded per-row nonzeros (0.0 in padding slots)
    cols: (n_rows, K) column indices (0 in padding slots — padding
          contributes data * x[0] * 0 = 0)
    """
    return jnp.sum(data * x[cols], axis=1)


def spmv_sell(data, cols, slice_offsets, slice_k, x, *, c: int):
    """y_perm = A_perm @ x for A in SELL-C-σ flat slot-major layout
    (oracle for ``kernels/spmv_sell.py``; same permuted padded output).

    Host loop over slices with per-slice exact widths — no masking, so
    it cross-checks the kernel's fixed-window masking logic. Not jit-
    friendly (slice widths become Python ints); test/oracle use only.
    """
    import numpy as np
    offs = np.asarray(slice_offsets)
    ks = np.asarray(slice_k)
    ys = []
    for s in range(len(ks)):
        k, off = int(ks[s]), int(offs[s])
        blk_d = data[off:off + c * k].reshape(k, c)
        blk_c = cols[off:off + c * k].reshape(k, c)
        ys.append(jnp.sum(blk_d * x[blk_c], axis=0))
    return jnp.concatenate(ys)


# -- conjugate gradient (one iteration; fused-kernel oracle runs many) -------

def _safe_div(a, b):
    """a/b with 0 when b underflows — keeps fully-converged CG iterations
    (rr -> exact 0 in f32) as fixed points instead of NaNs."""
    return jnp.where(jnp.abs(b) > 0, a / jnp.where(b == 0, 1.0, b), 0.0)


def cg_iteration_matvec(state, matvec, dot=jnp.vdot):
    """One textbook CG iteration with a pluggable SpMV (ELL kernel, SELL
    kernel, distributed local matvec...) and a pluggable reduction
    (``dot`` — jnp.vdot, or the compensated dot of Plan.precision=mixed).
    state = (x, r, p, rr)."""
    x, r, p, rr = state
    ap = matvec(p)
    alpha = _safe_div(rr, dot(p, ap))
    x = x + alpha * p
    r = r - alpha * ap
    rr_new = dot(r, r)
    beta = _safe_div(rr_new, rr)
    p = r + beta * p
    return (x, r, p, rr_new)


def cg_iteration(state, data, cols):
    """One textbook CG iteration on ELL-format A. state = (x, r, p, rr)."""
    return cg_iteration_matvec(state, lambda p: spmv_ell(data, cols, p))


def cg_run(data, cols, b, iters: int):
    """`iters` CG iterations from x0 = 0 (oracle for kernels/cg_fused)."""
    x0 = jnp.zeros_like(b)
    r0 = b
    state = (x0, r0, r0, jnp.vdot(r0, r0))
    def body(s, _):
        return cg_iteration(s, data, cols), None
    (x, r, p, rr), _ = jax.lax.scan(body, state, None, length=iters)
    return x, rr


# -- BiCGStab (nonsymmetric Krylov; oracle for exec/krylov.py) ----------------

def bicgstab_iteration_matvec(state, matvec, dot=jnp.vdot):
    """One BiCGStab iteration (van der Vorst 1992) with a pluggable SpMV
    and reduction. state = (x, r, rhat, p, v, rho, alpha, omega, rr).

    Every quotient goes through ``_safe_div`` so a fully-converged state
    (r -> exact 0) is a fixed point: rho'=0 forces beta=alpha'=omega'=0
    and every vector update vanishes — no NaNs past convergence, same
    contract the CG iteration carries.
    """
    x, r, rhat, p, v, rho, alpha, omega, rr = state
    rho_new = dot(rhat, r)
    beta = _safe_div(rho_new, rho) * _safe_div(alpha, omega)
    p = r + beta * (p - omega * v)
    v = matvec(p)
    alpha = _safe_div(rho_new, dot(rhat, v))
    s = r - alpha * v
    t = matvec(s)
    omega = _safe_div(dot(t, s), dot(t, t))
    x = x + alpha * p + omega * s
    r = s - omega * t
    return (x, r, rhat, p, v, rho_new, alpha, omega, dot(r, r))


def bicgstab_initial_state(b):
    """x=0 start: r = rhat = b; p = v = 0; the scalar carries seed at 1 so
    the first iteration reduces to p = r (textbook start)."""
    one = jnp.ones((), b.dtype)
    zero = jnp.zeros_like(b)
    return (zero, b, b, zero, zero, one, one, one, jnp.vdot(b, b))


def bicgstab_run(data, cols, b, iters: int):
    """``iters`` BiCGStab iterations from x0 = 0 on ELL-format A (oracle
    for the fused kernel and the distributed variant). Returns (x, rr)."""
    mv = lambda q: spmv_ell(data, cols, q)

    def body(s, _):
        return bicgstab_iteration_matvec(s, mv), None
    state, _ = jax.lax.scan(body, bicgstab_initial_state(b), None,
                            length=iters)
    return state[0], state[8]


# -- restarted GMRES(m) (nonsymmetric Krylov; oracle for exec/krylov.py) ------

def gmres_cycle_matvec(state, matvec, b, m: int, dot=jnp.vdot,
                       basis_reduce=None):
    """One GMRES restart cycle: build an (m+1)-vector Arnoldi basis with
    CGS2 (two-pass classical Gram-Schmidt — fully vectorized: rows of V
    beyond the current column are zero, so the projections need no
    masking), solve the (m+1) x m least-squares problem, update x, and
    recompute the residual explicitly (one extra SpMV per cycle; the
    price of a restart-robust ``rr``). state = (x, rr).

    ``basis_reduce`` completes the basis-projection products ``V @ w``
    (identity on one device; a psum over the shard axis when V's columns
    are row-partitioned — the distributed tier passes it so this one
    implementation serves both).
    """
    red = (lambda z: z) if basis_reduce is None else basis_reduce
    x, _ = state
    n = b.shape[0]
    r = b - matvec(x)
    beta = jnp.sqrt(dot(r, r))
    V = jnp.zeros((m + 1, n), b.dtype).at[0].set(r * _safe_div(1.0, beta))
    H = jnp.zeros((m + 1, m), b.dtype)

    def body(j, carry):
        V, H = carry
        vj = jax.lax.dynamic_slice(V, (j, 0), (1, n))[0]
        w = matvec(vj)
        h1 = red(V @ w)
        w = w - V.T @ h1
        h2 = red(V @ w)                 # second CGS pass (re-orthogonalize)
        w = w - V.T @ h2
        hn = jnp.sqrt(dot(w, w))
        H = jax.lax.dynamic_update_slice(H, (h1 + h2)[:, None], (0, j))
        H = jax.lax.dynamic_update_slice(H, hn.reshape(1, 1), (j + 1, j))
        V = jax.lax.dynamic_update_slice(
            V, (w * _safe_div(1.0, hn))[None], (j + 1, 0))
        return V, H

    V, H = jax.lax.fori_loop(0, m, body, (V, H))
    e1 = jnp.zeros((m + 1,), b.dtype).at[0].set(beta)
    y, _, _, _ = jnp.linalg.lstsq(H, e1)
    x = x + y @ V[:m]
    r = b - matvec(x)
    return (x, dot(r, r))


def gmres_run(data, cols, b, cycles: int, m: int):
    """``cycles`` GMRES(m) restart cycles from x0 = 0 on ELL-format A.
    Returns (x, rr)."""
    mv = lambda q: spmv_ell(data, cols, q)

    def body(s, _):
        return gmres_cycle_matvec(s, mv, b, m), None
    state, _ = jax.lax.scan(body, (jnp.zeros_like(b), jnp.vdot(b, b)),
                            None, length=cycles)
    return state[0], state[1]


# -- Mamba2 / SSD scan --------------------------------------------------------

def ssm_scan(x, dt, a, b, c, d):
    """Selective-state-space (Mamba2 SSD) reference via per-step recurrence.

    Shapes (single sequence):
      x:  (T, H, P)   per-head inputs (P = head dim)
      dt: (T, H)      softplus-activated step sizes
      a:  (H,)        per-head decay (negative)
      b:  (T, N)      input projection (shared across heads, ngroups=1)
      c:  (T, N)      output projection
      d:  (H,)        skip connection
    Returns y: (T, H, P).

    Recurrence per head h:
      h_t = exp(dt_t * a_h) * h_{t-1} + dt_t * outer(b_t, x_t)
      y_t = c_t @ h_t + d_h * x_t
    """
    T, H, P = x.shape
    N = b.shape[-1]

    def step(h_state, inputs):
        xt, dtt, bt, ct = inputs          # (H,P), (H,), (N,), (N,)
        decay = jnp.exp(dtt * a)          # (H,)
        upd = dtt[:, None, None] * bt[None, :, None] * xt[:, None, :]  # (H,N,P)
        h_state = decay[:, None, None] * h_state + upd
        yt = jnp.einsum("n,hnp->hp", ct, h_state) + d[:, None] * xt
        return h_state, yt

    h0 = jnp.zeros((H, N, P), x.dtype)
    _, y = jax.lax.scan(step, h0, (x, dt, b, c))
    return y


# -- decode attention ---------------------------------------------------------

def decode_attention(q, k, v, *, length=None):
    """Single-token GQA attention against a KV cache (oracle).

    q: (B, Hq, D); k, v: (B, S, Hkv, D); Hq % Hkv == 0.
    ``length``: optional (B,) valid-prefix lengths (rest masked).
    Returns (B, Hq, D).
    """
    B, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    qg = q.reshape(B, Hkv, g, D)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, k) / jnp.sqrt(D).astype(q.dtype)
    if length is not None:
        mask = jnp.arange(S)[None, :] < length[:, None]          # (B, S)
        logits = jnp.where(mask[:, None, None, :], logits, -jnp.inf)
    w = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", w, v)
    return out.reshape(B, Hq, D)
