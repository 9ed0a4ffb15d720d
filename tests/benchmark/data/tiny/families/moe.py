"""Test data: a family added by adding a file. The mixture of experts
(the dense backbone with a router and banks of SwiGLU experts in place
of the MLP), for the plain mixture reference's test against
``models/moe.py``. No cell of the benchmark runs it yet: the PR that adds
one brings a file like this under ``benchmark/families/``, proved at the
published widths on the chip."""

import jax

from benchmark.harness import core
from benchmark.harness.weights import int8_leaf

dense = core.load_module((core.BENCH_DIR,), "families", "dense")


def program_config(config: dict):
    from odh_kubeflow_tpu.models.moe import MoeConfig

    program = dict(config["deployment"].get("program", {}))
    dispatch = program.pop("dispatch")
    base = dense.program_config(
        dict(config, deployment=dict(config["deployment"], program=program))
    )
    return MoeConfig(
        base=base,
        num_experts=config["num_local_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        router_aux_loss_coef=config["router_aux_loss_coef"],
        dispatch=dispatch,
    )


def layer(key, cfg: dict) -> dict:
    D, F, E = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_local_experts"]
    keys = iter(jax.random.split(key, 12))
    out = dense.attention_weights(keys, cfg)

    def bank(key, shape, fan_in):
        # one expert at a time: a whole bank's float32 draw is large
        return jax.lax.map(
            lambda kk: int8_leaf(kk, shape, fan_in), jax.random.split(key, E)
        )

    out["router"] = int8_leaf(next(keys), (D, E), D)
    out["moe_gate"] = bank(next(keys), (D, F), D)
    out["moe_up"] = bank(next(keys), (D, F), D)
    out["moe_down"] = bank(next(keys), (F, D), F)
    return out


def _expert(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def _router(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["num_local_experts"]


def token_weights_per_layer(cfg: dict) -> int:
    """Only the experts a token is routed to."""
    return (
        dense.attention_matmul_weights(cfg) + _router(cfg)
        + cfg["num_experts_per_tok"] * _expert(cfg)
    )


def step_weights_per_layer(cfg: dict) -> int:
    """A decode step reads every expert, whichever the tokens chose."""
    return (
        dense.attention_matmul_weights(cfg) + _router(cfg)
        + cfg["num_local_experts"] * _expert(cfg)
    )
