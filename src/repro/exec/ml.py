"""ML workloads as Problem adapters: LM decode and the Mamba2 SSD scan.

The paper's thesis is not stencil-specific: *any* iterative memory-bound
kernel benefits from moving the time loop inside one persistent dispatch
and pinning its carried state on-chip. The repo's two ML workloads are
exactly that shape, and this module makes them first-class citizens of
the ``Problem -> plan -> execute`` pipeline (DESIGN.md §7/§13):

* :class:`DecodeAttentionProblem` — token-by-token LM decode. The time
  axis is the generated-token index; the cacheable operand is the KV
  cache (read in full every step, appended one slot per step); the state
  advance is ``decode_step`` + greedy argmax. The resident tier delegates
  to ``Model.decode_loop`` — the fused scan-with-donated-cache program
  whose attention core is the flash-decode kernel
  (``kernels/decode_attn.py``) on TPU — and ``convergence()`` maps the
  EOS contract onto the batchable retirement predicate, so
  ``repro.exec.batch.LaneRunner`` and the async engine serve
  continuous-batching decode with zero decode-specific code.
* :class:`SSMScanProblem` — the Mamba2 SSD scan over one sequence. The
  time axis is the *chunk* index; the cached array is the recurrent
  state ``h`` (H, N, P), which round-trips HBM once per chunk on the
  loop tiers and lives in VMEM scratch inside the PERKS kernel
  (``kernels/ssm_scan.py``) on the resident tier.

Both adapters expose the cost terms the planner's ``_ml_candidates``
branch prices (``repro.exec.planner``): per-step streamed bytes via
``cacheable_arrays`` (the KV-bytes-per-token traffic model), the
resident-elidable carry via ``carry_names``, and the VMEM footprint the
resident tier must fit via ``resident_scratch_bytes`` (gated against
``per_instance_chip`` for batched dispatches, DESIGN.md §8).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.cache_policy import CacheableArray
from repro.exec.problem import Problem, operand_fingerprint


def _tree_bytes(tree) -> int:
    """Total bytes of a pytree of arrays/ShapeDtypeStructs (shape-only)."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        total += int(math.prod(shape)) * jnp.dtype(dtype).itemsize
    return total


def _copy_tree(tree):
    """Defensive copy so donation inside a fused program never invalidates
    the problem's own buffers (same idiom as ``core.perks._own``)."""
    return jax.tree.map(
        lambda a: a.copy() if isinstance(a, jax.Array) else a, tree)


# =============================================================================
# LM decode
# =============================================================================

@dataclasses.dataclass(frozen=True, eq=False)
class DecodeAttentionProblem(Problem):
    """Autoregressive greedy decode of ``n_steps`` tokens as one Problem.

    ``cache`` is a prefilled decode cache (``Model.prefill``);
    ``first_tokens`` (B,) seeds the generation (the argmax of the prefill
    logits, exactly as ``runtime/server.py`` computes it). One step =
    ``model.decode_step`` + argmax + append into the output buffer, so
    the loop tiers reproduce the legacy per-token serving loop
    bit-for-bit, and the resident tier — ``Model.decode_loop``, the
    scan-fused program with a donated cache — is token-identical to both
    (asserted in ``tests/test_ml_problems.py``).

    ``eos_id`` declares the convergence contract: an instance is done
    when every row's latest token is EOS. The predicate is structurally
    shared (only the EOS id rides in the params), so the batched tier and
    the continuous-batching lanes retire decode instances through the
    same stacked reduction CG uses for its residual check.
    """

    model: Any                       # repro.models.lm.Model
    params: Any
    cache: Any                       # prefilled decode cache pytree
    first_tokens: jax.Array          # (B,) int32
    n_steps: int                     # tokens to generate beyond first_tokens
    eos_id: Optional[int] = None

    kind = "decode"
    #: cacheable-array names the resident tier keeps on-chip (the
    #: flash-decode online-softmax carry never materializes to HBM)
    carry_names = ("attn_carry",)

    @property
    def name(self) -> str:  # type: ignore[override]
        fp = operand_fingerprint(self.first_tokens,
                                 *jax.tree.leaves(self.cache)[:2])
        b = self.first_tokens.shape[0]
        return f"decode_{self.model.cfg.name}_b{b}_n{self.n_steps}_{fp}"

    # -- protocol -------------------------------------------------------------

    def initial_state(self):
        b = self.first_tokens.shape[0]
        return (self.cache,
                jnp.asarray(self.first_tokens, jnp.int32),
                jnp.zeros((b, self.n_steps), jnp.int32),
                jnp.int32(0))

    def step_fn(self):
        model, params = self.model, self.params

        def step(state):
            cache, tok, out, i = state
            logits, cache = model.decode_step(params, cache, tok)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out = jax.lax.dynamic_update_slice_in_dim(
                out, nxt[:, None], i, axis=1)
            return (cache, nxt, out, i + 1)

        return step

    def finalize(self, state):
        cache, _, out, _ = state
        return out, cache

    def oracle(self):
        """The legacy per-token serving loop (host-loop order): one
        jitted ``decode_step`` + argmax per token on a defensively copied
        cache. This is the exact arithmetic of ``runtime/server.py``'s
        baseline path (which jits ``decode_step``), so every tier's
        tokens must match it bit-for-bit."""
        step = jax.jit(self.model.decode_step)
        cache = _copy_tree(self.cache)
        tok = jnp.asarray(self.first_tokens, jnp.int32)
        outs = []
        for _ in range(self.n_steps):
            logits, cache = step(self.params, cache, tok)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            outs.append(tok)
        if outs:
            out = jnp.stack(outs, axis=1)
        else:
            out = jnp.zeros((self.first_tokens.shape[0], 0), jnp.int32)
        return out, cache

    def convergence(self):
        # retired when every row's latest token is EOS. The predicate is
        # shared across the batch key; only the EOS id (a per-instance
        # scalar) rides in params — the LaneRunner retirement contract.
        if self.eos_id is None:
            return None
        return (lambda s, eos: jnp.all(s[1] == eos)), jnp.int32(self.eos_id)

    def cacheable_arrays(self, *, fuse_steps: int = 1) -> Sequence[CacheableArray]:
        """The KV-bytes-per-token traffic model. Each generated token
        re-reads the whole decode cache and the whole parameter set;
        ring-buffer leaves (k/v/ckv) append one slot per step (stores
        amortize to 1/len), recurrent leaves (conv/h) rewrite fully.
        ``attn_carry`` is the per-step attention score matrix the unfused
        path materializes per layer and the flash-decode kernel keeps in
        VMEM (loads/stores per step = attention-layer count)."""
        cfg = self.model.cfg
        b = int(self.first_tokens.shape[0])
        arrays = [CacheableArray("params", _tree_bytes(self.params),
                                 loads_per_step=1.0, stores_per_step=0.0)]
        kv_len = 1
        ring_b = state_b = 0
        for key, leaf in self.cache.items():
            shape = getattr(leaf, "shape", ())
            nbytes = _tree_bytes(leaf)
            if key in ("k", "v", "ckv", "shared_k", "shared_v"):
                ring_b += nbytes
                if len(shape) >= 3:
                    kv_len = max(kv_len, int(shape[-3]))
            elif key != "pos":
                state_b += nbytes
        if ring_b:
            arrays.append(CacheableArray(
                "kv_cache", ring_b, loads_per_step=1.0,
                stores_per_step=1.0 / kv_len))
        if state_b:
            arrays.append(CacheableArray(
                "ssm_state", state_b, loads_per_step=1.0,
                stores_per_step=1.0))
        n_attn = self._n_attn_layers()
        if n_attn and ring_b:
            arrays.append(CacheableArray(
                "attn_carry", b * cfg.n_heads * kv_len * 4,
                loads_per_step=float(n_attn),
                stores_per_step=float(n_attn)))
        return arrays

    def _n_attn_layers(self) -> int:
        cfg = self.model.cfg
        if cfg.family in ("dense", "encdec"):
            return cfg.n_layers
        if cfg.family == "hybrid":
            every = max(1, cfg.shared_attn_every or 1)
            return max(1, cfg.n_layers // every)
        return 0                       # pure SSM: no attention carry

    def resident_scratch_bytes(self) -> int:
        """VMEM the fused decode program needs live at once: one layer's
        attention scores plus the online-softmax carry (m/l/acc)."""
        cfg = self.model.cfg
        b = int(self.first_tokens.shape[0])
        arrays = {a.name: a for a in self.cacheable_arrays()}
        carry = arrays.get("attn_carry")
        scores = carry.bytes if carry is not None else 0
        return scores + b * cfg.n_heads * (cfg.head_dim + 2) * 4

    def domain_bytes(self) -> int:
        return _tree_bytes(self.cache)

    # -- batching -------------------------------------------------------------

    def payload(self):
        return (self.cache, self.first_tokens)

    def with_payload(self, payload) -> "DecodeAttentionProblem":
        cache, first = payload
        return dataclasses.replace(self, cache=cache, first_tokens=first)

    def batch_key(self) -> tuple:
        # instances batch iff they decode the SAME weights at the same
        # shapes for the same budget; the EOS id stays out (it is
        # convergence *params*, free to vary per lane)
        shapes = tuple(sorted(
            (k, tuple(getattr(v, "shape", ())), str(getattr(v, "dtype", "")))
            for k, v in self.cache.items()))
        return ("decode", self.model.cfg.name, id(self.params), shapes,
                tuple(self.first_tokens.shape), self.n_steps)

    def array_scales_with_batch(self, name: str) -> bool:
        return name != "params"

    # -- tiers ----------------------------------------------------------------

    def run_resident(self, plan):
        """The fused persistent decode: ``Model.decode_loop`` — the whole
        generation in ONE dispatch via ``lax.scan`` with the cache as
        donated carry (flash-decode attention on TPU). The cache is
        copied first so donation never invalidates this problem's own
        buffers (the executor may run it again under another plan)."""
        cache = _copy_tree(self.cache)
        toks, cache = self.model.decode_loop(
            self.params, cache, jnp.asarray(self.first_tokens, jnp.int32),
            self.n_steps)
        return toks, cache


# =============================================================================
# Mamba2 SSD scan
# =============================================================================

def _ssd_chunk(h_prev, xc, dtc, bc, cc, a, d, out_dtype):
    """One SSD chunk on a single sequence — the chunk decomposition of
    ``nn/ssd.py`` / ``kernels/ssm_scan.py`` without the batch axis.
    xc (C,H,P); dtc (C,H); bc/cc (C,N); h_prev (H,N,P) f32."""
    xc32 = xc.astype(jnp.float32)
    dtc32 = dtc.astype(jnp.float32)
    a32 = a.astype(jnp.float32)
    d32 = d.astype(jnp.float32)
    g = dtc32 * a32[None, :]                            # (C,H) log decay
    cum = jnp.cumsum(g, axis=0)                         # inclusive
    scores = jnp.einsum("in,jn->ij", cc, bc,
                        preferred_element_type=jnp.float32)
    li = cum[:, None, :] - cum[None, :, :]              # (i,j,H)
    causal = jnp.tril(jnp.ones((xc.shape[0], xc.shape[0]), bool))
    li = jnp.where(causal[:, :, None], li, -jnp.inf)
    m = jnp.exp(li) * scores[..., None] * dtc32[None]
    y = jnp.einsum("ijh,jhp->ihp", m, xc32)
    y += jnp.exp(cum)[..., None] * jnp.einsum(
        "in,hnp->ihp", cc, h_prev, preferred_element_type=jnp.float32)
    y += d32[None, :, None] * xc32
    tail = jnp.exp(cum[-1:, :] - cum)                   # (C,H)
    upd = jnp.einsum("jh,jn,jhp->hnp", tail * dtc32, bc, xc32)
    h_new = jnp.exp(cum[-1])[:, None, None] * h_prev + upd
    return h_new, y.astype(out_dtype)


@dataclasses.dataclass(frozen=True, eq=False)
class SSMScanProblem(Problem):
    """The Mamba2 SSD scan over one sequence, chunk index as time axis.

    One step consumes a ``chunk``-long slice of the input streams
    (x, dt, b, c), advances the recurrent state ``h`` (H, N, P) f32, and
    writes the matching output slice — the exact chunk decomposition of
    ``nn/ssd.py``. On the loop tiers ``h`` round-trips HBM once per
    chunk; the resident tier runs the PERKS kernel
    (``kernels/ssm_scan.py``) with ``h`` pinned in VMEM scratch for the
    whole scan — zero state traffic, the paper's caching claim applied
    to a recurrence instead of a stencil. A chunk that does not divide T
    is shrunk to the largest divisor (per-timestep chunks at worst), so
    every sequence length is legal on every tier.
    """

    x: jax.Array                     # (T, H, P)
    dt: jax.Array                    # (T, H)
    a: jax.Array                     # (H,)
    b: jax.Array                     # (T, N)
    c: jax.Array                     # (T, N)
    d: jax.Array                     # (H,)
    chunk: int = 128

    kind = "ssm"
    carry_names = ("h_state",)

    def __post_init__(self):
        if self.chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {self.chunk}")

    @property
    def chunk_eff(self) -> int:
        """Largest chunk <= the requested one that divides T."""
        t = int(self.x.shape[0])
        ck = min(self.chunk, t)
        while ck > 1 and t % ck:
            ck -= 1
        return max(ck, 1)

    @property
    def n_steps(self) -> int:  # type: ignore[override]
        return int(self.x.shape[0]) // self.chunk_eff

    @property
    def name(self) -> str:  # type: ignore[override]
        t, h, p = self.x.shape
        n = self.b.shape[-1]
        fp = operand_fingerprint(self.x, self.dt, self.a, self.b, self.c,
                                 self.d)
        return f"ssm_t{t}_h{h}_p{p}_n{n}_{fp}"

    # -- protocol -------------------------------------------------------------

    def initial_state(self):
        t, h, p = self.x.shape
        n = self.b.shape[-1]
        return (jnp.zeros((h, n, p), jnp.float32),
                jnp.zeros((t, h, p), self.x.dtype),
                jnp.int32(0))

    def step_fn(self):
        ck = self.chunk_eff
        x, dt, a, b, c, d = self.x, self.dt, self.a, self.b, self.c, self.d

        def step(state):
            h, y, i = state
            o = i * ck
            xc = jax.lax.dynamic_slice_in_dim(x, o, ck, 0)
            dtc = jax.lax.dynamic_slice_in_dim(dt, o, ck, 0)
            bc = jax.lax.dynamic_slice_in_dim(b, o, ck, 0)
            cc = jax.lax.dynamic_slice_in_dim(c, o, ck, 0)
            h, yc = _ssd_chunk(h, xc, dtc, bc, cc, a, d, x.dtype)
            y = jax.lax.dynamic_update_slice_in_dim(y, yc, o, axis=0)
            return (h, y, i + 1)

        return step

    def finalize(self, state):
        return state[1]

    def oracle(self):
        from repro.kernels import ref as kref
        return kref.ssm_scan(self.x, self.dt, self.a, self.b, self.c,
                             self.d)

    def cacheable_arrays(self, *, fuse_steps: int = 1) -> Sequence[CacheableArray]:
        t, h, p = (int(s) for s in self.x.shape)
        n = int(self.b.shape[-1])
        db = jnp.dtype(self.x.dtype).itemsize
        steps = max(1, self.n_steps)
        in_bytes = (t * h * p + t * h + 2 * t * n) * db
        return [
            # the recurrent state: read + rewritten every chunk on the
            # loop tiers, VMEM-resident in the PERKS kernel
            CacheableArray("h_state", h * n * p * 4,
                           loads_per_step=1.0, stores_per_step=1.0),
            # streamed once over the whole scan: 1/n_steps of the stream
            # per chunk — caching them saves nothing (each byte is
            # touched once), which the knapsack sees as near-zero density
            CacheableArray("seq_stream", in_bytes,
                           loads_per_step=1.0 / steps, stores_per_step=0.0),
            CacheableArray("y_stream", t * h * p * db,
                           loads_per_step=0.0, stores_per_step=1.0 / steps),
            CacheableArray("ab_coeffs", 2 * h * 4,
                           loads_per_step=1.0, stores_per_step=0.0),
        ]

    def resident_scratch_bytes(self) -> int:
        """The kernel's whole VMEM request: the f32 state, its
        double-buffered chunk tiles, one head's temporaries."""
        from repro.kernels.ssm_scan import ssm_vmem_bytes
        return ssm_vmem_bytes(self.x, self.dt, self.a, self.b, self.c,
                              self.d, chunk=self.chunk_eff)

    def resident_compiles(self, chip) -> bool:
        from repro.kernels.ssm_scan import chunk_compiles
        return chip.interpret or chunk_compiles(int(self.x.shape[0]),
                                                self.chunk_eff)

    def domain_bytes(self) -> int:
        return sum(a.bytes for a in self.cacheable_arrays()
                   if a.name != "h_state")

    # -- batching -------------------------------------------------------------

    def payload(self):
        return (self.x, self.dt, self.b, self.c)

    def with_payload(self, payload) -> "SSMScanProblem":
        x, dt, b, c = payload
        return dataclasses.replace(self, x=x, dt=dt, b=b, c=c)

    def batch_key(self) -> tuple:
        return ("ssm", tuple(self.x.shape), str(self.x.dtype),
                int(self.b.shape[-1]), self.chunk_eff,
                operand_fingerprint(self.a, self.d))

    def array_scales_with_batch(self, name: str) -> bool:
        # the decay/skip coefficients are shared across a batch of
        # sequences; state and streams are per-sequence
        return name != "ab_coeffs"

    # -- tiers ----------------------------------------------------------------

    def run_resident(self, plan):
        from repro.kernels.ssm_scan import ssm_scan as pallas_ssm
        return pallas_ssm(self.x, self.dt, self.a, self.b, self.c, self.d,
                          chunk=self.chunk_eff)
