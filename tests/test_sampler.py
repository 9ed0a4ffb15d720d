"""The engine's per-row sampler (models/engine.py) against the plain one.

``sample_logits_rowwise`` finds its top-k and nucleus cut-offs by a
search over the logits' ordered bit patterns; ``generate.sample_logits``
(static knobs, two sorts) is the plain reference. Row by row, with that
row's knobs, both must keep the SAME tokens and draw the SAME token under
the same key. Nothing on the chip checks a sampled token (the benchmark's
``correct`` compares greedy requests only), so this file is the guarantee
that the distribution sampled is the one a request's knobs state.

One honest difference is known: the reference sums the nucleus by a
float32 prefix scan, the search by a masked float32 sum. A row whose edge
token's strictly-greater mass lies within ~3e-7 of ``top_p`` can differ by
that one token (2 of 256 flat 32768-token rows, 2 of 80 rows on the chip;
float64 sides now with one, now with the other). The seeds below are
cases where the two agree; a failure BY ONE TOKEN AT THE EDGE after a
change to either sum's order is that rounding, anything else is a fault.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from odh_kubeflow_tpu.models import LlamaConfig, init_params
from odh_kubeflow_tpu.models import engine as engine_mod
from odh_kubeflow_tpu.models.engine import (
    DecodeEngine,
    mask_logits_rowwise,
    sample_logits_rowwise,
)
from odh_kubeflow_tpu.models.generate import sample_logits

GRANITE_V = 100352  # granite-4.0-h-small's vocabulary, 32 slots
MISTRAL_V = 32768  # mistral-7b-v0.3's and Command A+'s share, 16 slots


def _normal(seed, rows, vocab, std, shift=0.0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(rows, vocab)) * std + shift).astype(np.float32)


def _quantised(seed, rows, vocab, std, step):
    """Logits on a grid: many exact ties, at the k-th value and at the
    nucleus's edge among them."""
    x = _normal(seed, rows, vocab, std)
    return (np.round(x / step) * step).astype(np.float32)


def _with(x, fill, share, seed):
    """``x`` with ``share`` of its entries replaced by ``fill`` (an array
    of values to draw from)."""
    rng = np.random.default_rng(seed)
    hit = rng.random(x.shape) < share
    return np.where(hit, rng.choice(fill, size=x.shape), x).astype(np.float32)


def _cycle(values, rows, dtype):
    return np.resize(np.asarray(values, dtype), rows)


# (id, logits (made when the case runs), temperature, top_k, top_p): the
# knobs are cycled over the rows
CASES = [
    # the three serving cells' traffic: temperature 0.7, top_p 0.95, one
    # request in ten greedy; seeded weights give flat rows
    ("granite-flat-32-cell-traffic", lambda: _normal(1, 32, GRANITE_V, 0.0052),
     [0.7] * 9 + [0.0], [0], [0.95]),
    ("granite-flat-1-admission", lambda: _normal(2, 1, GRANITE_V, 0.0052),
     [0.7], [0], [0.95]),
    ("granite-std1-8-mixed", lambda: _normal(3, 8, GRANITE_V, 1.0),
     [0.3, 0.7, 1.0, 1.5], [0, 7, 50, 1000, 0], [0.2, 0.5, 0.9, 0.95, 0.99, 1.0, 0.0]),
    ("granite-std4-8-mixed", lambda: _normal(4, 8, GRANITE_V, 4.0),
     [0.7, 1.0, 1.5, 0.3], [0, 0, 1, 1000], [0.95, 0.5, 0.99, 0.2, 0.0]),
    ("granite-std1-32-mixed", lambda: _normal(5, 32, GRANITE_V, 1.0),
     [0.7, 0.0, 1.0, 1.5], [0, 1000], [0.95, 0.5]),
    ("granite-std1-1-topk7-topp.5", lambda: _normal(6, 1, GRANITE_V, 1.0),
     [1.0], [7], [0.5]),
    ("mistral-flat-16-cell-traffic", lambda: _normal(7, 16, MISTRAL_V, 0.02),
     [0.7] * 9 + [0.0], [0], [0.95]),
    ("mistral-std1-16-mixed", lambda: _normal(8, 16, MISTRAL_V, 1.0),
     [0.7, 1.5, 0.0, 0.3], [0, 1, 7, 50, 1000], [0.95, 0.9, 0.5, 0.0, 1.0, 0.2]),
    ("mistral-std4-1-admission", lambda: _normal(9, 1, MISTRAL_V, 4.0),
     [0.7], [0], [0.95]),
    # ties straddling the k-th value and the nucleus's edge
    ("granite-ties-8", lambda: _quantised(10, 8, GRANITE_V, 1.0, 0.125),
     [1.0, 0.7], [7, 50, 1000, 0], [0.5, 0.95, 0.9, 1.0, 0.2]),
    ("mistral-ties-coarse-8", lambda: _quantised(11, 8, MISTRAL_V, 1.0, 0.5),
     [1.0, 0.5], [1, 7, 50, 0], [0.5, 0.95, 0.2, 0.99]),
    ("four-values-4096", lambda: _quantised(12, 6, 4096, 0.6, 1.0).clip(-1, 2),
     [1.0], [1, 7, 4000, 0], [0.5, 0.95, 1e-6]),
    ("all-equal-512", lambda: np.full((3, 512), 1.5, np.float32),
     [1.0, 0.7, 0.0], [0, 7, 1], [0.95, 0.5, 1e-6]),
    # top_k 0, 1, 7, >= V
    ("topk-1", lambda: _normal(13, 8, MISTRAL_V, 1.0), [0.7, 1.0], [1], [0.95, 0.0, 1.0, 0.5]),
    ("topk-7", lambda: _normal(14, 8, MISTRAL_V, 1.0), [0.7, 1.0], [7], [0.95, 0.0, 1e-6, 0.5]),
    ("topk-at-and-past-V", lambda: _normal(15, 6, 4096, 1.0),
     [1.0], [4096, 8192, 4095], [0.0, 0.95, 0.5]),
    ("topk-0", lambda: _normal(16, 8, MISTRAL_V, 2.0), [0.7, 1.0], [0], [0.0, 1.0]),
    # top_p 0, 1e-6, 0.5, 0.95, 1.0
    ("topp-off-0-and-1", lambda: _normal(17, 8, GRANITE_V, 1.0), [1.0], [0, 50], [0.0, 1.0]),
    ("topp-1e-6", lambda: _normal(18, 8, MISTRAL_V, 1.0), [1.0, 0.7], [0, 50], [1e-6]),
    ("topp-.5", lambda: _normal(19, 8, MISTRAL_V, 1.0), [1.0, 0.7], [0, 50], [0.5]),
    ("topp-.95", lambda: _normal(20, 8, MISTRAL_V, 1.0), [1.0, 0.7], [0, 50], [0.95]),
    # -inf (a masked part of the vocabulary) and both zeros
    ("minus-inf-half-the-row", lambda: _with(_normal(21, 8, MISTRAL_V, 1.0), [-np.inf], 0.5, 1),
     [0.7, 1.0], [0, 7, 20000, 32768], [0.95, 0.5, 0.0]),
    ("minus-inf-all-but-five", lambda: _with(_normal(22, 4, 4096, 1.0), [-np.inf], 0.999, 2),
     [1.0], [0, 7, 1], [0.95, 0.5]),
    ("both-zeros", lambda: _with(_quantised(23, 8, MISTRAL_V, 1.0, 0.25), [0.0, -0.0], 0.3, 3),
     [1.0, 0.7], [0, 7, 50, 12000], [0.5, 0.95, 0.2, 0.9]),
    ("both-zeros-on-top", lambda: _with(-np.abs(_normal(24, 6, 4096, 1.0)), [0.0, -0.0], 0.2, 4),
     [1.0], [0, 7, 500, 1000], [0.5, 0.95, 0.0]),
    # the sign branch of the key: every logit negative (log-probabilities)
    ("all-negative", lambda: _normal(25, 8, MISTRAL_V, 3.0, shift=-40.0),
     [0.7, 1.0, 1.5], [0, 50], [0.95, 0.5]),
    ("positive-and-large", lambda: _normal(26, 8, 4096, 30.0, shift=100.0),
     [0.3, 1.0], [0, 7], [0.95, 0.5]),
    # temperature 0 rows beside sampled ones, and alone
    ("greedy-mixed-32", lambda: _normal(27, 32, MISTRAL_V, 1.0),
     [0.0, 0.7, 0.0, 1.0, 1.5], [0, 7], [0.95, 0.5, 0.0]),
    ("greedy-all", lambda: _normal(28, 4, 4096, 1.0), [0.0], [0, 7], [0.95]),
    # a vocabulary that is no multiple of 128 (the tiny test models)
    ("odd-vocabulary-257", lambda: _normal(29, 5, 257, 1.0),
     [1.0, 0.7, 0.0], [0, 3, 300], [0.9, 0.5, 0.0]),
]


def _reference(monkeypatch, logits, key, temperature, top_k, top_p):
    """``generate.sample_logits`` row by row: its tokens, and the rows it
    handed to ``jax.random.categorical`` (``None`` for a greedy row). The
    noise of row r is the batch's, so a group of rows with equal knobs is
    one call on the whole batch, read at those rows."""
    rows, vocab = logits.shape
    drawn_from = []
    real = jax.random.categorical

    def spy(key, logits, *args, **kwargs):
        drawn_from.append(logits)
        return real(key, logits, *args, **kwargs)

    tokens = np.zeros((rows,), np.int32)
    masked = [None] * rows
    knobs = list(zip(temperature.tolist(), top_k.tolist(), top_p.tolist()))
    with monkeypatch.context() as patched:
        patched.setattr(jax.random, "categorical", spy)
        for t, k, p in sorted(set(knobs)):
            drawn_from.clear()
            got = sample_logits(
                jnp.asarray(logits), key,
                temperature=max(t, 0.0),
                top_k=min(k, vocab) if k > 0 else None,
                top_p=p if 0 < p < 1 else None,
            )
            for r in range(rows):
                if knobs[r] != (t, k, p):
                    continue
                tokens[r] = int(got[r])
                if t > 0:
                    masked[r] = np.asarray(drawn_from[0][r])
    return tokens, masked


@jax.jit
def _engine_sampler(logits, key, temperature, top_k, top_p):
    """The row the engine's sampler draws from and the token it draws: one
    program, so the search is compiled once a case."""
    return (
        mask_logits_rowwise(logits, temperature, top_k, top_p),
        sample_logits_rowwise(logits, key, temperature, top_k, top_p),
    )


@pytest.mark.parametrize(
    "logits,temperature,top_k,top_p",
    [pytest.param(*case[1:], id=case[0]) for case in CASES],
)
def test_rowwise_sampler_keeps_and_draws_what_the_sort_form_does(
    monkeypatch, logits, temperature, top_k, top_p
):
    logits = logits()
    rows, vocab = logits.shape
    temperature = _cycle(temperature, rows, np.float32)
    top_k = _cycle(top_k, rows, np.int32)
    top_p = _cycle(top_p, rows, np.float32)
    key = jax.random.key(rows * 7919 + vocab)

    want_tokens, want_masked = _reference(
        monkeypatch, logits, key, temperature, top_k, top_p
    )
    got_masked, got_tokens = map(
        np.asarray, _engine_sampler(logits, key, temperature, top_k, top_p)
    )
    for r in range(rows):
        if want_masked[r] is None:
            continue
        want_kept, got_kept = np.isfinite(want_masked[r]), np.isfinite(got_masked[r])
        assert (want_kept == got_kept).all(), (
            f"row {r} (temperature {temperature[r]}, top_k {top_k[r]}, "
            f"top_p {top_p[r]}): the sort form keeps {want_kept.sum()} "
            f"tokens, the search {got_kept.sum()}"
        )
        # what survives is the scaled logit itself, bit for bit
        assert (want_masked[r][want_kept] == got_masked[r][got_kept]).all()
    assert got_tokens.tolist() == want_tokens.tolist()


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    return cfg, init_params(jax.random.key(0), cfg=cfg, dtype=jnp.float32)


def _sorts(lowered) -> list[str]:
    return [
        line.strip() for line in lowered.as_text().splitlines()
        if "stablehlo.sort" in line
    ]


def test_the_sampler_lowers_to_no_sort_at_the_largest_vocabulary():
    rows = 32
    lowered = jax.jit(sample_logits_rowwise).lower(
        jax.ShapeDtypeStruct((rows, GRANITE_V), jnp.float32),
        jax.random.key(0),
        jax.ShapeDtypeStruct((rows,), jnp.float32),
        jax.ShapeDtypeStruct((rows,), jnp.int32),
        jax.ShapeDtypeStruct((rows,), jnp.float32),
    )
    assert _sorts(lowered) == []
    # the helper of this test does find one where there is one
    assert _sorts(jax.jit(jnp.sort).lower(jnp.zeros((4, 8)))) != []


def test_the_sampled_decode_chunk_lowers_to_no_sort(model):
    """The regression this file guards against is two sorts of the whole
    vocabulary in every step of the sampled decode chunk: it should fail
    here, not wait for a trace."""
    cfg, params = model
    engine = DecodeEngine(
        params, cfg, n_slots=4, max_len=64, chunk=4,
        prompt_buckets=(16,), cache_dtype=jnp.float32,
    )
    try:
        args = ((engine.params, engine.lora), engine._state)
        sampled = engine._decode_fn.lower(*args)
        assert "module @jit_" + engine_mod.DECODE_PROGRAM in sampled.as_text()
        assert _sorts(sampled) == []
        assert _sorts(engine._decode_greedy_fn.lower(*args)) == []
    finally:
        engine.stop()


def test_sampled_chunks_are_counted_beside_decode_calls(model):
    """``decode_calls_sampled``: the chunk programs that ran the sampler,
    a host count (the ``engine.dispatch`` span's ``program`` says the same
    per turn)."""
    cfg, params = model
    engine = DecodeEngine(
        params, cfg, n_slots=2, max_len=64, chunk=4,
        prompt_buckets=(16,), cache_dtype=jnp.float32,
    )
    try:
        engine.submit([5, 9, 13], max_tokens=9).result(timeout=120)
        assert engine.decode_calls > 0
        assert engine.decode_calls_sampled == 0
        greedy_calls = engine.decode_calls
        engine.submit(
            [5, 9, 13], max_tokens=9, temperature=0.8, top_p=0.9
        ).result(timeout=120)
        assert engine.decode_calls > greedy_calls
        assert engine.decode_calls_sampled == engine.decode_calls - greedy_calls
    finally:
        engine.stop()
