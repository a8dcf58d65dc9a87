"""AdamW with decoupled weight decay, global-norm clipping and schedules.

Pure pytree functions (no optax). Optimizer moments inherit each
parameter's dtype by default — for the 235B-class configs that means bf16
moments (a documented distributed-optimization trade; see DESIGN.md) —
or can be forced to f32 via ``moment_dtype``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.nn.param import ParamSpec, is_spec


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: Optional[float] = 1.0
    moment_dtype: Optional[Any] = None   # None = same as param
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1


def schedule(cfg: AdamWConfig, step):
    """Linear warmup + cosine decay to min_lr_ratio."""
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - cfg.warmup_steps) /
                    jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1), 0, 1)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return cfg.lr * warm * (cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * cos)


def init(cfg: AdamWConfig, params):
    def mom(p):
        dt = cfg.moment_dtype or p.dtype
        return jnp.zeros(p.shape, dt)
    return {
        "m": jax.tree.map(mom, params),
        "v": jax.tree.map(mom, params),
        "step": jnp.zeros((), jnp.int32),
    }


def init_spec(cfg: AdamWConfig, spec_tree):
    """ParamSpec tree for the optimizer state (same logical axes as params,
    so the sharding rules apply verbatim — fully sharded optimizer)."""
    def mom(s: ParamSpec):
        dt = cfg.moment_dtype or s.dtype
        return ParamSpec(s.shape, dt, "zeros", s.axes)
    return {
        "m": jax.tree.map(mom, spec_tree, is_leaf=is_spec),
        "v": jax.tree.map(mom, spec_tree, is_leaf=is_spec),
        "step": ParamSpec((), jnp.int32, "zeros", ()),
    }


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))


def apply(cfg: AdamWConfig, params, opt_state, grads, *,
          scan_key: Optional[str] = "layers"):
    """One AdamW update. Returns (params, opt_state, metrics).

    Leaves under ``params[scan_key]`` (the stacked per-layer weights) are
    updated inside a ``lax.scan`` over the layer axis: the update math
    upcasts to f32, and letting XLA schedule all layers' f32 temporaries
    concurrently was measured at +10 GB live on the 235B config; the scan
    serialises them to one layer's worth.
    """
    step = opt_state["step"] + 1
    lr = schedule(cfg, step)
    gnorm = global_norm(grads)
    if cfg.clip_norm is not None:
        scale = jnp.minimum(1.0, cfg.clip_norm / (gnorm + 1e-9))
    else:
        scale = jnp.float32(1.0)

    bc1 = 1 - cfg.b1 ** step.astype(jnp.float32)
    bc2 = 1 - cfg.b2 ** step.astype(jnp.float32)

    def upd(p, m, v, g):
        g = g.astype(jnp.float32) * scale
        m32, v32 = m.astype(jnp.float32), v.astype(jnp.float32)
        m_new = cfg.b1 * m32 + (1 - cfg.b1) * g
        v_new = cfg.b2 * v32 + (1 - cfg.b2) * jnp.square(g)
        update = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + cfg.eps)
        p32 = p.astype(jnp.float32)
        p_new = p32 - lr * (update + cfg.weight_decay * p32)
        return p_new.astype(p.dtype), m_new.astype(m.dtype), v_new.astype(v.dtype)

    def upd_tree(ps, ms, vs, gs):
        out = jax.tree.map(upd, ps, ms, vs, gs)
        istup = lambda t: isinstance(t, tuple)
        return (jax.tree.map(lambda t: t[0], out, is_leaf=istup),
                jax.tree.map(lambda t: t[1], out, is_leaf=istup),
                jax.tree.map(lambda t: t[2], out, is_leaf=istup))

    scannable = (isinstance(params, dict) and scan_key is not None
                 and scan_key in params)
    if scannable:
        rest_p = {k: v for k, v in params.items() if k != scan_key}
        rest_m = {k: v for k, v in opt_state["m"].items() if k != scan_key}
        rest_v = {k: v for k, v in opt_state["v"].items() if k != scan_key}
        rest_g = {k: v for k, v in grads.items() if k != scan_key}
        rp, rm, rv = upd_tree(rest_p, rest_m, rest_v, rest_g)

        g_l = grads[scan_key]
        n_layers = jax.tree.leaves(g_l)[0].shape[0]
        take = lambda t, i: jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), t)
        put = lambda t, u, i: jax.tree.map(
            lambda a, b: jax.lax.dynamic_update_index_in_dim(a, b, i, 0),
            t, u)

        def body(i, carry):
            p_l, m_l, v_l = carry
            np_, nm, nv = upd_tree(take(p_l, i), take(m_l, i), take(v_l, i),
                                   take(g_l, i))
            return put(p_l, np_, i), put(m_l, nm, i), put(v_l, nv, i)

        # fori_loop carries alias in place under donation: one layer's f32
        # temporaries live at a time, no stacked-ys duplication.
        lp, lm, lv = jax.lax.fori_loop(
            0, n_layers, body,
            (params[scan_key], opt_state["m"][scan_key],
             opt_state["v"][scan_key]))
        params_new = {**rp, scan_key: lp}
        m_new = {**rm, scan_key: lm}
        v_new = {**rv, scan_key: lv}
    else:
        params_new, m_new, v_new = upd_tree(params, opt_state["m"],
                                            opt_state["v"], grads)
    metrics = {"grad_norm": gnorm, "lr": lr}
    return params_new, {"m": m_new, "v": v_new, "step": step}, metrics
