"""The check of served tokens against the plain reference. Each sampled
request's prompt and served tokens go through the reference ONCE, as one
sequence padded to a fixed length (padding sits after the real tokens,
where a causal model cannot see it). At every served position the gap is
the reference's best logit minus the reference's logit of the token that
was served: 0 where the program chose what the reference would have."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import model

PAD_TO = 256


@functools.partial(jax.jit, static_argnames=("cfg_items", "prec"))
def _row_logits(params, tokens, cfg_items, prec):
    cfg = dict(cfg_items)
    seg = jnp.ones_like(tokens)
    return model.logits(params, None, tokens[None], seg[None], cfg, prec)[0]


def padded_length(longest: int) -> int:
    """One length for every request of a mix, so that one program is
    compiled: the mix's longest prompt and output, rounded up."""
    return -(-longest // PAD_TO) * PAD_TO


def request_gaps(params, cfg, prompt, served, length, prec=model.SOUND,
                 chosen_by=None):
    """Gaps at the served positions of one request. With ``chosen_by``
    (a lower Precision) the token read at each position is the one that
    precision puts first, not the one that was served: the control."""
    seq = np.zeros(length, np.int32)
    seq[: len(prompt) + len(served)] = list(prompt) + list(served)
    items = model.static_cfg(cfg)
    lg = _row_logits(params, jnp.asarray(seq), items, prec)
    pos = len(prompt) - 1 + np.arange(len(served))
    lg = lg[pos]
    if chosen_by is not None:
        low = _row_logits(params, jnp.asarray(seq), items, chosen_by)[pos]
        tok = jnp.argmax(low, axis=-1)
    else:
        tok = jnp.asarray(np.asarray(served, np.int32))
    picked = jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
    return np.asarray(jnp.max(lg, axis=-1) - picked)


def all_gaps(params, cfg, requests, length, prec=model.SOUND,
             chosen_by=None) -> np.ndarray:
    """The gaps at every served position of every request."""
    return np.concatenate([
        request_gaps(params, cfg, p, t, length, prec, chosen_by)
        for p, t in requests
    ])


def widest_gap(params, cfg, requests, length, prec=model.SOUND,
               chosen_by=None) -> float:
    return float(all_gaps(params, cfg, requests, length, prec, chosen_by).max())
