"""Llama-3-8B int8 serving smoke on a single v5e chip.

The north-star model (BASELINE.json: Llama-3-8B) cannot even load in
bf16 on one v5e — 15.0GiB of parameters against 15.75GiB of HBM leaves
no room for cache or activations. Weight-only int8
(``models/quant.py``) halves that to 7.5GiB, and
``forward_with_cache`` dequantizes per layer inside the scan so the
bf16 copy of only one layer ever materialises. This script builds the
8B tree leaf-by-leaf on device (streaming init+quantize keeps the peak
under HBM), then measures greedy decode.

Run: ``python -m loadtest.int8_8b_smoke`` (real TPU required).
"""

from __future__ import annotations

import json
import sys
import time


def main() -> None:
    import jax
    import jax.numpy as jnp

    from odh_kubeflow_tpu.models import GenerateConfig, LlamaConfig, generate
    from odh_kubeflow_tpu.models import llama
    from odh_kubeflow_tpu.models.quant import streaming_quantized_init

    import os
    w8a8 = os.environ.get("SMOKE_W8A8", "") == "1"
    cfg = LlamaConfig.llama3_8b(dtype=jnp.bfloat16, w8a8_decode=w8a8)
    t0 = time.time()
    qparams = streaming_quantized_init(cfg, jax.random.key(7))
    jax.block_until_ready(qparams)
    init_s = time.time() - t0
    resident_gib = sum(
        l.size * l.dtype.itemsize for l in jax.tree_util.tree_leaves(qparams)
    ) / 2**30

    gen_cfg = GenerateConfig(max_new_tokens=32, temperature=0.0)
    B, S = 4, 128
    prompt = jnp.ones((B, S), jnp.int32)
    run = jax.jit(lambda p, t: generate(p, t, cfg, gen_cfg))
    t0 = time.time()
    out = run(qparams, prompt)
    int(out["lengths"][0])
    compile_s = time.time() - t0
    t0 = time.time()
    out = run(qparams, prompt)
    int(out["lengths"][0])
    decode_tok_s = B * gen_cfg.max_new_tokens / (time.time() - t0)

    # 128-token row: one jitted generate() call carries a fixed
    # dispatch + fetch cost that a 32-token measurement charges to
    # decode — at 128 new tokens/stream (the serving loadtests' shape)
    # the same cost spreads over four times the steps
    gen_cfg_l = GenerateConfig(max_new_tokens=128, temperature=0.0)
    run_l = jax.jit(lambda p, t: generate(p, t, cfg, gen_cfg_l))
    out = run_l(qparams, prompt)
    int(out["lengths"][0])
    t0 = time.time()
    out = run_l(qparams, prompt)
    int(out["lengths"][0])
    decode_long_tok_s = B * 128 / (time.time() - t0)

    print(
        json.dumps(
            {
                "model": "llama3-8b-int8",
                "device": getattr(jax.devices()[0], "device_kind", "cpu"),
                "resident_params_gib": round(resident_gib, 2),
                "streaming_init_s": round(init_s, 1),
                "compile_s": round(compile_s, 1),
                "decode_tokens_per_s": round(decode_tok_s, 1),
                "decode_128tok_tokens_per_s": round(decode_long_tok_s, 1),
                "batch": B,
                "w8a8": w8a8,
            }
        )
    )


if __name__ == "__main__":
    sys.exit(main())
