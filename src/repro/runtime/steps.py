"""Step-function builders shared by the trainer, server and dry-run."""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models.lm import Model
from repro.optim import adamw


def make_train_step(model: Model, opt_cfg: adamw.AdamWConfig, *,
                    accum: int = 1):
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    ``accum`` > 1 folds gradient accumulation into the step as a scan over
    microbatches (activation memory / accum; the optimizer update and its
    collectives happen once — a PERKS-style fusion of the update loop).
    """

    def train_step(params, opt_state, batch):
        if accum == 1:
            loss, grads = jax.value_and_grad(model.loss)(params, batch)
        else:
            split = jax.tree.map(
                lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
                batch)

            def micro(carry, mb):
                loss_sum, g_sum = carry
                l, g = jax.value_and_grad(model.loss)(params, mb)
                g_sum = jax.tree.map(lambda a, b: a + b.astype(a.dtype),
                                     g_sum, g)
                return (loss_sum + l, g_sum), None

            # accumulate in the PARAM dtype: an f32 accumulator for a
            # bf16-param 235B model is an extra 2 bytes/param live
            # (+1.9 GB/chip measured). Grad noise
            # dominates bf16 rounding over <=8 microbatches.
            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype),
                                 params)
            (loss, grads), _ = jax.lax.scan(
                micro, (jnp.float32(0.0), zeros), split)
            loss = loss / accum
            grads = jax.tree.map(lambda g: g / accum, grads)

        params, opt_state, metrics = adamw.apply(opt_cfg, params, opt_state,
                                                 grads)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step


def make_serve_step(model: Model):
    """(params, cache, tokens) -> (logits, cache): the dry-run serve_step."""

    def serve_step(params, cache, tokens):
        return model.decode_step(params, cache, tokens)

    return serve_step


def make_prefill_step(model: Model):
    def prefill_step(params, batch):
        return model.prefill(params, batch)

    return prefill_step
