"""The ``keye_vl`` family (Keye-VL-2.0-30B-A3B's language model) as ONE
CHIP'S SHARE of a layer group in a pipeline: what the harness needs of
it, found by the ``family`` a configuration file names. Every layer's
GQA attends only the ``sa_config.topk`` keys a learned indexer picks (a
cache for the indexer's keys beside keys and values), then a
softmax-over-all mixture of which ``num_experts`` experts are held here
(the router keeps the published width) and NO shared expert; untied head
over this chip's rows of the vocabulary.

It brings its own builder of the seeded frozen base (``make_params``)
and its own counts: what a position costs the cache, the bytes a decode
step must move, by part, from the program's own counters of the
positions its queries could see and those they attended, and the
operations and bytes of the indexer's scores and of attention under a
selection, a causal (query, key) pair. Everything reads the
configuration FILE."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.harness.weights import int8_leaf, seed_key


def held(config: dict) -> tuple:
    h = config["deployment"]["experts_held"]
    assert h["count"] == config["num_experts"], (h, config["num_experts"])
    return h["first"], h["count"]


def router_width(config: dict) -> int:
    """The experts the ROUTER chooses among: the published count."""
    return config["reduced_from"]["num_experts"]


def indexer(config: dict) -> tuple:
    """(heads, head dim, keys a query keeps)."""
    sa = config["sa_config"]
    assert sa["indexer_num_kv_heads"] == 1, sa
    return sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]


def program_config(config: dict):
    """The program's config object for a configuration file. A program
    without the family fails here, before any weight is drawn."""
    from odh_kubeflow_tpu.models.keye_vl import KeyeVLConfig

    assert not config["mlp_only_layers"] and config["decoder_sparse_step"] == 1
    assert config["norm_topk_prob"] and not config["tie_word_embeddings"]
    assert not config["attention_bias"] and not config["use_sliding_window"]
    Hi, di, topk = indexer(config)
    return KeyeVLConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        mrope_section=tuple(config["rope_scaling"]["mrope_section"]),
        index_heads=Hi, index_dim=di, index_topk=topk,
        num_experts=router_width(config),
        experts_held=held(config),
        num_experts_per_tok=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        rms_norm_eps=config["rms_norm_eps"],
        # the file states it (the tests' tiny file states float32, to
        # hold the controls to limits that the program's own rounding
        # does not reach)
        dtype=jnp.dtype(config["activation_dtype"]),
    )


def _off_one(key, shape):
    """A norm's plain weight, drawn off 1 so that it shows."""
    return 1 + 0.1 * jax.random.normal(key, shape, jnp.float32)


def layer(key, cfg: dict) -> dict:
    """One layer: two norms, attention with its per-head norms, the
    indexer, the router over ALL the published experts and the held
    experts' banks."""
    D, F, hd = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["head_dim"]
    q_dim, kv_dim = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    Hi, di, _ = indexer(cfg)
    E = held(cfg)[1]
    keys = iter(jax.random.split(key, 20))

    def bank(key, shape, fan_in):
        return jax.lax.map(
            lambda kk: int8_leaf(kk, shape, fan_in), jax.random.split(key, E)
        )

    return {
        "norm1": _off_one(next(keys), (D,)),
        "norm2": _off_one(next(keys), (D,)),
        "wq": int8_leaf(next(keys), (D, q_dim), D),
        "wk": int8_leaf(next(keys), (D, kv_dim), D),
        "wv": int8_leaf(next(keys), (D, kv_dim), D),
        "wo": int8_leaf(next(keys), (q_dim, D), q_dim),
        "q_norm": _off_one(next(keys), (hd,)),
        "k_norm": _off_one(next(keys), (hd,)),
        "wq_idx": int8_leaf(next(keys), (D, Hi * di), D),
        "wk_idx": int8_leaf(next(keys), (D, di), D),
        # float32, and small: the heads' sum then has a spread of ~1
        "w_idx": jax.random.normal(next(keys), (D, Hi), jnp.float32) * (D * Hi) ** -0.5,
        "ik_norm_w": _off_one(next(keys), (di,)),
        "ik_norm_b": 0.1 * jax.random.normal(next(keys), (di,), jnp.float32),
        # float32: its eight largest of 128 probabilities are the selection
        "router": jax.random.normal(
            next(keys), (D, router_width(cfg)), jnp.float32
        ) * D**-0.5,
        "moe_gate": bank(next(keys), (D, F), D),
        "moe_up": bank(next(keys), (D, F), D),
        "moe_down": bank(next(keys), (F, D), F),
    }


def make_params(cfg: dict, seed: int):
    """The frozen base on the default device, in ONE jitted call, in the
    program's layout (every layer's leaves stacked under ``layers``).
    int8 matmul weights with a float32 scale per output channel; the
    indexer's head weights, its key's LayerNorm, norms and router
    float32; the embedding and the untied head bfloat16, standard
    deviation ``hidden_size ** -0.5`` (a row of norm 1, as the other
    staged configurations')."""
    D, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]

    def build(key):
        ke, kh, kl, kn = jax.random.split(key, 4)
        return {
            "embed": jax.random.normal(ke, (V, D), jnp.bfloat16) * D**-0.5,
            "lm_head": jax.random.normal(kh, (D, V), jnp.bfloat16) * D**-0.5,
            "layers": jax.lax.map(
                lambda kk: layer(kk, cfg), jax.random.split(kl, L)
            ),
            "final_norm": _off_one(kn, (D,)),
        }

    return jax.jit(build)(seed_key(seed))


# ---- counts: matmul weights are int8 (one byte a weight) -------------------


def attention_matmul_weights(cfg: dict) -> int:
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return D * H * hd + 2 * D * Hkv * hd + H * hd * D


def indexer_bytes(cfg: dict) -> int:
    """``W_qI`` and ``W_kI`` a byte a weight, ``W_w`` float32."""
    Hi, di, _ = indexer(cfg)
    return cfg["hidden_size"] * (Hi * di + di) + 4 * cfg["hidden_size"] * Hi


def expert_weights(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def cache_bytes_per_position(cfg: dict) -> dict:
    """What one position of one stream costs the cache in ONE layer, by
    part: keys and values (bf16), the indexer's one key head (bf16)."""
    return {
        "kv": 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2,
        "index": indexer(cfg)[1] * 2,
    }


def routed_bytes(cfg: dict, experts_hit: float) -> float:
    """The expert banks a step read: ``experts_hit`` distinct (layer,
    expert) pairs, from the program's counter."""
    return experts_hit * expert_weights(cfg)


def decode_step_bytes(cfg: dict, steps: float, causal_rows: float,
                      attended_rows: float, experts_hit: float) -> dict:
    """Bytes ONE decode step must move, by part, from what ``steps``
    steps counted: ``weights`` (every layer's attention and indexer
    projections, its float32 router over all the published experts, and
    the routed experts HIT: ``experts_hit`` distinct (layer, expert)
    banks), ``head`` (bf16, this chip's rows), ``index`` (the indexer's
    keys at every position a query could see: ``causal_rows``, summed
    over slots and layers by the program) and ``rows`` (the keys and
    values of the positions ATTENDED, ``attended_rows``: a selected row
    is counted ONCE, whatever gathers it and however often the gathered
    copy is written and read again)."""
    L, steps = cfg["num_hidden_layers"], max(steps, 1)
    by_position = cache_bytes_per_position(cfg)
    fixed = L * (
        attention_matmul_weights(cfg) + indexer_bytes(cfg)
        + cfg["hidden_size"] * router_width(cfg) * 4
    )
    return {
        "weights": fixed + routed_bytes(cfg, experts_hit / steps),
        "head": cfg["vocab_size"] * cfg["hidden_size"] * 2,
        "index": by_position["index"] * causal_rows / steps,
        "rows": by_position["kv"] * attended_rows / steps,
    }


def index_scores_work(cfg: dict, pairs: float) -> dict:
    """Operations and bytes of the indexer's scores over ``pairs`` causal
    (query, key) pairs of one layer: ``2 x heads x head dim`` a pair;
    each score written once in float32 (the keys themselves, read once a
    block of queries, are a thousandth of that)."""
    Hi, di, _ = indexer(cfg)
    return {"flops": 2 * Hi * di * pairs, "bytes": 4 * pairs}


def sparse_prefill_work(cfg: dict, pairs: float) -> dict:
    """Operations and bytes of a part's attention under the selection as
    a MASK, over ``pairs`` causal pairs of one layer: the dense ``q k^T``
    and ``p v`` of every query head (``4 x heads x head dim`` a pair:
    the selection saves nothing here) and each score read once."""
    H, hd = cfg["num_attention_heads"], cfg["head_dim"]
    return {"flops": 4 * H * hd * pairs, "bytes": 4 * pairs}
