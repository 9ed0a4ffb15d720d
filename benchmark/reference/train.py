"""The plain reference of a QLoRA training step: the loss of
``model.nll_sum`` differentiated with respect to the adapters, the
global-norm clip, and AdamW under a linear warm-up into a cosine decay,
written out. It imports nothing of the program.

A dense model's loss separates over the rows of a batch once the count
of unmasked positions is known, so a dense step is taken a row at a
time and the gradients summed: half the activations. The mixture's
auxiliary loss couples the rows (it multiplies two means over the whole
batch), so a mixture step sees the batch at once."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference import model

ADAM_EPS = 1e-8


def learning_rate(count, opt: dict):
    """optax.warmup_cosine_decay_schedule(0, peak, warmup, decay, 0.1 peak)."""
    peak, warm = opt["learning_rate"], opt["warmup_steps"]
    decay = max(opt["total_steps"], warm + 1)
    end = 0.1 * peak
    if count < warm:
        return peak * count / warm
    frac = min((count - warm) / (decay - warm), 1.0)
    return end + (peak - end) * 0.5 * (1 + math.cos(math.pi * frac))


@functools.partial(jax.jit, static_argnames=("cfg_items", "prec"))
def _value_and_grad(params, lora, batch, total, cfg_items, prec):
    # the weights are an ARGUMENT: closed over, 7 GB would be lowered as
    # constants of the program
    cfg = dict(cfg_items)

    def batch_loss(lora):
        nll, aux = model.nll_sum(params, lora, batch, cfg, prec)
        return nll / total + aux

    return jax.value_and_grad(batch_loss)(lora)


def loss_and_grads(params, lora, batch, cfg, prec=model.SOUND):
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    total = jnp.maximum(jnp.sum(batch["loss_mask"].astype(jnp.float32)), 1.0)
    items = model.static_cfg(cfg)
    if cfg.get("family") == "moe":
        return _value_and_grad(params, lora, batch, total, items, prec)
    loss, grads = None, None
    for r in range(batch["tokens"].shape[0]):
        row = {k: v[r : r + 1] for k, v in batch.items()}
        l, g = _value_and_grad(params, lora, row, total, items, prec)
        loss = l if loss is None else loss + l
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return loss, grads


@jax.jit
def _global_norm(tree):
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree))
    )


def adamw_step(lora, grads, state, opt: dict):
    """One optimizer step; ``state`` is (count, m, v). Returns the new
    adapters, the new state and the gradient as Adam got it (clipped)."""
    count, m, v = state
    gnorm = _global_norm(grads)
    factor = opt["max_grad_norm"] / jnp.maximum(gnorm, opt["max_grad_norm"])
    grads = jax.tree.map(lambda g: g * factor, grads)
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    t = count + 1
    lr = learning_rate(count, opt)

    def upd(p, m_, v_):
        step = (m_ / (1 - b1**t)) / (jnp.sqrt(v_ / (1 - b2**t)) + ADAM_EPS)
        return p - lr * (step + opt["weight_decay"] * p)

    return jax.tree.map(upd, lora, m, v), (t, m, v), grads


def run_steps(params, lora, batches, cfg, opt, prec=model.SOUND):
    """Follow the first ``len(batches)`` steps from the given adapters.
    Returns the losses, the first clipped gradient and the adapters
    after the last step."""
    zeros = jax.tree.map(jnp.zeros_like, lora)
    state = (0, zeros, zeros)
    losses, first_grad = [], None
    for batch in batches:
        loss, grads = loss_and_grads(params, lora, batch, cfg, prec)
        lora, state, clipped = adamw_step(lora, grads, state, opt)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = clipped
    return losses, first_grad, lora
