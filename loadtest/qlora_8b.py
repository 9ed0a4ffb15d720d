"""Llama-3-8B QLoRA fine-tune on a single v5e chip — the north-star
workload (BASELINE.json: 8B LoRA >= 50% MFU) made measurable on the one
real chip this environment has.

bf16 8B weights are 15.0GiB against 15.75GiB of HBM — training cannot
even load them. QLoRA path (``Trainer(quantize_base=True)``): the
frozen base lives as int8 (+per-channel scales, ~7.6GiB), LoRA adapters
and optimizer state are the only trainable state, and
``llama._decoder_layer`` dequantizes per layer *inside* the remat
boundary so forward and backward both hold one layer's bf16 copy at a
time. The MFU accounting is identical to the bf16 path (dequant
multiplies are not credited).

Run: ``python -m loadtest.qlora_8b [--batch 2] [--seq 4096]
[--remat-policy none] [--steps 5]`` (real TPU required).
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--rank", type=int, default=16)
    ap.add_argument(
        "--remat-policy",
        default="none",
        choices=["dots", "attn", "none"],
        help="8B on one chip is HBM-limited; 'none' minimises residency",
    )
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from odh_kubeflow_tpu.models.llama import LlamaConfig
    from odh_kubeflow_tpu.models.lora import LoraConfig
    from odh_kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
    from odh_kubeflow_tpu.train.trainer import TrainConfig, Trainer

    cfg = LlamaConfig.llama3_8b(
        dtype=jnp.bfloat16, remat=True, remat_policy=args.remat_policy
    )
    t0 = time.time()
    trainer = Trainer(
        cfg,
        TrainConfig(warmup_steps=2, total_steps=100),
        lora_cfg=LoraConfig(rank=args.rank),
        mesh=build_mesh(MeshConfig(), jax.devices()[:1]),
        quantize_base=True,
    )
    jax.block_until_ready(trainer.params)
    build_s = time.time() - t0
    resident_gib = sum(
        l.size * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves(trainer.params)
    ) / 2**30

    t0 = time.time()
    bench = trainer.benchmark(args.batch, args.seq, steps=args.steps, warmup=1)
    wall_s = time.time() - t0

    peak = jax.local_devices()[0].memory_stats() or {}
    peak_gib = peak.get("peak_bytes_in_use", 0) / 2**30

    device_kind = getattr(jax.devices()[0], "device_kind", "cpu")
    # v5e: 197 TF/s bf16 peak (utils/tpu.py table keys off device kind)
    from odh_kubeflow_tpu.utils.tpu import peak_flops_per_chip

    peak_fl = peak_flops_per_chip(jax.devices()[0])
    mfu = bench["flops_per_s"] / peak_fl
    mfu_3x = bench["train_equiv_flops_per_s"] / peak_fl
    print(
        json.dumps(
            {
                "model": "llama3-8b-qlora-int8-base",
                "device": device_kind,
                "batch": args.batch,
                "seq": args.seq,
                "lora_rank": args.rank,
                "remat_policy": args.remat_policy,
                "resident_base_gib": round(resident_gib, 2),
                "peak_hbm_gib": round(peak_gib, 2),
                "build_s": round(build_s, 1),
                "bench_wall_s": round(wall_s, 1),
                "step_time_s": round(bench["step_time_s"], 4),
                "tokens_per_s": round(bench["tokens_per_s"], 1),
                "mfu_strict": round(mfu, 4),
                "mfu_train_equiv_3x": round(mfu_3x, 4),
                "loss": round(bench["loss"], 4),
            }
        )
    )


if __name__ == "__main__":
    sys.exit(main())
