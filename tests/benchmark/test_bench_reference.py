"""The plain references against the program's models at tiny sizes on
the CPU: dense and mixture of experts (grouped dispatch), packed rows
with segment masks, the harness's int8 weights.

Both sides compute in float32 here (the program's ``dtype`` is set to
float32 and the test suite runs matmuls at ``highest``), so they differ
only by the order of sums: the tolerance is 2e-3 of the logits' spread.
The same reference computed in bfloat16 has to FAIL that tolerance:
bfloat16 carries 8 bits, a relative 4e-3 per rounding, compounded over
the layers."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import core, weights
from benchmark.reference import model

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny")
ROOTS = (TINY, core.BENCH_DIR)
TOLERANCE = 2e-3


def load_config(name):
    with open(os.path.join(TINY, "configs", name + ".json")) as f:
        return json.load(f)


def packed_inputs(vocab, B, S, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(1, vocab, size=(B, S)).astype(np.int32)
    seg = np.zeros((B, S), np.int32)
    for b in range(B):
        cuts = np.sort(rng.choice(np.arange(8, S - 8), size=3, replace=False))
        seg[b] = 1 + np.searchsorted(cuts, np.arange(S), side="right")
        seg[b, S - 5 :] = 0  # padding at the end of the row
    return jnp.asarray(tokens), jnp.asarray(seg)


def relative_gap(got, ref):
    return float(jnp.max(jnp.abs(got - ref)) / jnp.std(ref))


@pytest.mark.parametrize("config_name,B,S", [("tiny-dense", 2, 64), ("tiny-moe", 2, 512)])
def test_reference_agrees_with_the_program_and_bf16_does_not(config_name, B, S):
    config = load_config(config_name)
    # the mixture's family is a file of the test's own directory
    family = core.load_module(ROOTS, "families", config["family"])
    params = weights.make_params(config, 2**31 + 3, family)
    lora = weights.make_lora(config, {"rank": 4, "alpha": 8.0}, 5)
    tokens, seg = packed_inputs(config["vocab_size"], B, S)
    cfg = family.program_config(config)
    if config["family"] == "moe":
        from odh_kubeflow_tpu.models import moe

        cfg = dataclasses.replace(
            cfg, base=dataclasses.replace(cfg.base, dtype=jnp.float32)
        )
        got, aux = jax.jit(
            lambda p, l, t, s: moe.forward(p, t, cfg, lora=l, segment_ids=s)
        )(params, lora, tokens, seg)
    else:
        from odh_kubeflow_tpu.models import llama

        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
        got = jax.jit(
            lambda p, l, t, s: llama.forward(p, t, cfg, lora=l, segment_ids=s)
        )(params, lora, tokens, seg)
        aux = None
    ref = model.logits(params, lora, tokens, seg, config)
    real = np.asarray(seg) > 0  # a padding position's output is nobody's
    gap = relative_gap(got[real], ref[real])
    assert gap < TOLERANCE, gap
    if aux is not None:
        with jax.default_matmul_precision("highest"):
            _, ref_aux = model.hidden_states(params, lora, tokens, seg, config)
        assert abs(float(aux) - float(ref_aux)) < 1e-5
    low = model.logits(params, lora, tokens, seg, config, model.Precision(act="bf16"))
    low_gap = relative_gap(low[real], ref[real])
    assert low_gap > 3 * TOLERANCE, low_gap


def test_segments_wall_off_documents_in_the_reference():
    config = load_config("tiny-dense")
    params = weights.make_params(
        config, 1, core.load_module(ROOTS, "families", "dense")
    )
    tokens, seg = packed_inputs(config["vocab_size"], 1, 64)
    whole = model.logits(params, None, tokens, seg, config)
    # the second document alone gives the same logits as inside the row
    idx = np.flatnonzero(np.asarray(seg[0]) == 2)
    alone = model.logits(
        params, None, tokens[:, idx], jnp.ones((1, len(idx)), jnp.int32), config
    )
    # rotary embeddings are relative, so the shift in position is invisible
    assert relative_gap(alone[0], whole[0, idx]) < 1e-4
