"""Benchmark: Llama LoRA fine-tune MFU on the attached TPU.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "detail": {...}}

and exits non-zero when JAX finds no TPU or when any row raised (the
row's traceback goes to stderr, its ``detail`` entry is ``{"error":
...}``, and ``detail.failed_rows`` names it; the rows after it still
run, so one run says which rows work).

The reference platform publishes no perf numbers (BASELINE.md); the
north star from BASELINE.json is >=50% MFU on a Llama-3-**8B** LoRA
fine-tune from a notebook, so ``vs_baseline`` is measured MFU / 0.50.

Headline: **Llama-3-8B QLoRA** (int8 frozen base + LoRA r16, seq 4096)
on the attached chip — the north-star model itself, which bf16 cannot
even load on one v5e. The value is **strict MFU**: useful FLOPs only,
where frozen matmuls credit 2× forward (their dW is never computed)
and attention credits 3× (its backward is required to reach the
adapters) — see Trainer.benchmark. The laxer 6ND/3× figure most
published "LoRA MFU" numbers use is reported alongside as
``mfu_train_equiv_3x``. BENCH_HEADLINE=1b makes the 1B row the
headline (metric ``llama1b_lora_train_mfu``) and skips the 8B rows;
a failed headline row is reported with ``value: null``, never
replaced by another row.

Also measured:
- Llama-3.2-1B LoRA at seq 1024 — round-1/2 continuity numbers;
- long context: 1B at seq 16384, where attention dominates and the
  pallas flash kernel (ops/pallas_attention.py, causal block skip) is
  the difference between running and OOM;
- dense-vs-flash attention op at seq 4096;
- KV-cache decode smoke.

MFU accounting counts causally-required attention FLOPs only
(models/llama.py flops_per_token), so block-skipping cannot inflate it.
Set BENCH_FAST=1 to skip everything but the headline and the 1B row.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time
import traceback


def _attention_op_compare(jax, jnp, seq: int = 4096):
    """Dense vs flash attention step time at the 1B model's head shape.

    The op runs inside a ``lax.scan`` (8 iterations per dispatch), so
    one timed call is eight kernel executions back to back and the
    host's per-call dispatch is an eighth of what a bare loop would
    charge each of them."""
    from jax import lax

    from odh_kubeflow_tpu.ops.attention import dense_attention
    from odh_kubeflow_tpu.ops.pallas_attention import flash_attention

    key = jax.random.PRNGKey(0)
    B, Hq, Hkv, hd = 1, 32, 8, 64
    q = jax.random.normal(key, (B, seq, Hq, hd), jnp.bfloat16)
    k = jax.random.normal(key, (B, seq, Hkv, hd), jnp.bfloat16)
    v = jax.random.normal(key, (B, seq, Hkv, hd), jnp.bfloat16)
    N = 8
    out = {}
    for name, fn in (
        ("dense", lambda q, k, v: dense_attention(q, k, v, causal=True)),
        ("flash", lambda q, k, v: flash_attention(q, k, v, causal=True)),
    ):
        def scanned(q, k, v, fn=fn):
            def body(c, _):
                o = fn(c, k, v)
                return o * 1e-3 + c * 0.999, None
            return lax.scan(body, q, None, length=N)[0]

        jf = jax.jit(scanned)
        float(jf(q, k, v).sum())  # compile + warm (host transfer = sync)
        best = None
        for _ in range(2):
            t0 = time.time()
            float(jf(q, k, v).sum())
            dt = (time.time() - t0) / N
            best = dt if best is None else min(best, dt)
        out[name] = round(best * 1e3, 2)
    return out


def _generate_smoke(jax, jnp, trainer):
    """KV-cache decode on the real chip (models/generate.py): prefill a
    prompt, decode 32 tokens, report decode tokens/s — the notebook
    fine-tune→try-it loop's serving half."""
    from odh_kubeflow_tpu.models.generate import GenerateConfig, generate

    gen_cfg = GenerateConfig(max_new_tokens=32, temperature=0.0)
    B, S = 4, 128
    prompt = jnp.ones((B, S), jnp.int32)
    run = jax.jit(
        lambda params, prompt: generate(params, prompt, trainer.model_cfg, gen_cfg)
    )
    t0 = time.time()
    out = run(trainer.params, prompt)
    int(out["lengths"][0])  # host transfer = sync (compile incl.)
    compile_s = time.time() - t0
    t0 = time.time()
    out = run(trainer.params, prompt)
    int(out["lengths"][0])
    steady_s = time.time() - t0
    return {
        "batch": B,
        "prompt_len": S,
        "new_tokens": gen_cfg.max_new_tokens,
        "compile_s": round(compile_s, 2),
        "decode_tokens_per_s": round(B * gen_cfg.max_new_tokens / steady_s, 1),
    }


def main() -> int:
    os.environ.setdefault("JAX_TRACEBACK_FILTERING", "off")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        # a CPU run of this table is not a slower measurement of the
        # same thing — it is a measurement of something nobody deploys
        print(
            f"bench.py: JAX found no TPU (platform "
            f"{devices[0].platform!r}, JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}); nothing measured",
            file=sys.stderr,
        )
        return 1

    import jax.numpy as jnp

    from odh_kubeflow_tpu.models import LlamaConfig, LoraConfig
    from odh_kubeflow_tpu.models.llama import resolved_attention_impl
    from odh_kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
    from odh_kubeflow_tpu.train import TrainConfig, Trainer
    from odh_kubeflow_tpu.utils.tpu import peak_flops_per_chip

    n = len(devices)
    peak = peak_flops_per_chip(devices[0]) * n
    fast = os.environ.get("BENCH_FAST", "").lower() in ("1", "true")

    batch_size = int(os.environ.get("BENCH_BATCH", "8"))
    seq_len = int(os.environ.get("BENCH_SEQ", "1024"))
    steps = int(os.environ.get("BENCH_STEPS", "10"))
    # batch must tile the data-parallel extent (= all devices here)
    batch_size = -(-max(batch_size, n) // n) * n

    cfg = LlamaConfig.llama3_1b(dtype=jnp.bfloat16)
    impl = resolved_attention_impl(cfg)
    mesh = build_mesh(MeshConfig(fsdp=n), devices)
    detail = {
        "platform": devices[0].platform,
        "devices": n,
        "device_kind": devices[0].device_kind,
        "attention_impl": impl,
    }
    failed: list[str] = []

    def run_row(name: str, fn) -> None:
        """One row of the table. A row that raises is recorded and the
        run goes on to the next — but it fails the run (exit code), and
        its traceback is printed, not summarised away. Each row frees
        what it built, raised or not, so the next starts from a
        drained arena."""
        try:
            detail[name] = fn()
        except Exception as e:  # noqa: BLE001 — boundary: report, go on
            traceback.print_exc()
            detail[name] = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
            failed.append(name)
        finally:
            gc.collect()
            jax.clear_caches()

    def mfu_fields(st: dict, strict_key: str = "mfu_strict") -> dict:
        return {
            strict_key: round(st["flops_per_s"] / peak, 4),
            "mfu_train_equiv_3x": round(
                st["train_equiv_flops_per_s"] / peak, 4
            ),
        }

    def lora_trainer(model_cfg, **kw):
        return Trainer(
            model_cfg,
            TrainConfig(warmup_steps=2, total_steps=100),
            lora_cfg=LoraConfig(rank=16),
            mesh=mesh,
            **kw,
        )

    want_8b = os.environ.get("BENCH_HEADLINE", "8b") != "1b"

    # -- headline: 8B QLoRA (north-star model), single chip or mesh ----
    def row_8b_qlora():
        batch8 = max(2, n)
        s8 = lora_trainer(
            LlamaConfig.llama3_8b(dtype=jnp.bfloat16, remat_policy="attn"),
            quantize_base=True,
        ).benchmark(batch8, 4096, steps=3, warmup=1)
        return {
            "batch": batch8,
            "seq": 4096,
            "lora_rank": 16,
            "int8_base": True,
            "step_time_s": round(s8["step_time_s"], 4),
            "tokens_per_s": round(s8["tokens_per_s"], 1),
            **mfu_fields(s8),
            "loss": round(s8["loss"], 4),
        }

    if want_8b:
        run_row("headline_8b_qlora", row_8b_qlora)

    # -- 1B LoRA (round-1/2 continuity regime) + KV-cache decode -------
    def row_1b():
        trainer = lora_trainer(cfg)
        stats = trainer.benchmark(batch_size, seq_len, steps=steps, warmup=2)
        row = {
            "batch": batch_size,
            "seq": seq_len,
            "step_time_s": round(stats["step_time_s"], 4),
            "tokens_per_s": round(stats["tokens_per_s"], 1),
            "loss": round(stats["loss"], 4),
            **mfu_fields(stats),
        }
        if not fast:
            # the decode smoke reuses this trainer's params (its own
            # row would build a second 1B tree for 32 tokens)
            run_row(
                "generate", lambda: _generate_smoke(jax, jnp, trainer)
            )
        return row

    run_row("llama1b_lora", row_1b)

    if not fast:
        # the hard regime: 16k context, attention-dominant. Needs all
        # three long-context levers at once: the pallas flash kernel
        # (dense logits at 16k OOM), chunked cross-entropy (full
        # [S,V] logits are 8.4GB), and aggressive remat. Primary row:
        # the north-star 8B model itself, QLoRA at 16k on one chip
        # (full remat — the flash-residual "attn" policy's ~4GB of
        # saved residuals doesn't fit next to the int8 base at this
        # length). Secondary row: the 1B continuity config under
        # "attn_mlp" (pins ~6.5GB of residuals — it only fits on a
        # drained arena, which run_row's cleanup provides).
        import dataclasses as _dc

        long_seq = int(os.environ.get("BENCH_LONG_SEQ", "16384"))

        def long_row(model: str, trainer_):
            batch_ = max(1, n)
            st = trainer_.benchmark(batch_, long_seq, steps=3, warmup=1)
            return {
                "model": model,
                "seq": long_seq,
                "batch": batch_,
                "attention_impl": impl,
                "step_time_s": round(st["step_time_s"], 4),
                "tokens_per_s": round(st["tokens_per_s"], 1),
                **mfu_fields(st),
            }

        if want_8b:
            run_row(
                "long_context",
                lambda: long_row(
                    "llama3-8b-qlora-int8",
                    lora_trainer(
                        LlamaConfig.llama3_8b(
                            dtype=jnp.bfloat16, remat_policy="none"
                        ),
                        quantize_base=True,
                    ),
                ),
            )
        run_row(
            "long_context_1b",
            lambda: long_row(
                "llama3.2-1b-lora",
                lora_trainer(_dc.replace(cfg, remat_policy="attn_mlp")),
            ),
        )
        run_row(
            "attention_op_ms", lambda: _attention_op_compare(jax, jnp)
        )

    # BENCH_FULL=1: the Mixtral-class MoE row (8×1B QLoRA, grouped
    # dropless dispatch): streaming int8 init + a fresh compile, so it
    # is opt-in; loadtest/moe_qlora_8x1b is the standalone command and
    # BASELINE.md pins the measured numbers (incl. the ragged
    # cf=1.25 / cf=1.0 dual accounting).
    if os.environ.get("BENCH_FULL", "") == "1":

        def row_moe():
            from odh_kubeflow_tpu.models.moe import MoeConfig

            sm = lora_trainer(
                MoeConfig.mixtral_8x1b(
                    base=LlamaConfig.llama3_1b(
                        dtype=jnp.bfloat16, remat_policy="attn"
                    ),
                    dispatch="grouped",
                    pin_expert_acts=True,
                ),
                quantize_base=True,
            ).benchmark(2, 4096, steps=3, warmup=1)
            return {
                "dispatch": "grouped-dropless",
                "batch": 2,
                "seq": 4096,
                "step_time_s": round(sm["step_time_s"], 4),
                "tokens_per_s": round(sm["tokens_per_s"], 1),
                **mfu_fields(sm, "mfu_strict_sparse"),
            }

        run_row("moe_8x1b_qlora", row_moe)

    # the headline is the row that was asked for; if it raised, the
    # value is null — no other row stands in for it
    if want_8b:
        metric, head = "llama8b_qlora_train_mfu", detail["headline_8b_qlora"]
    else:
        metric, head = "llama1b_lora_train_mfu", detail["llama1b_lora"]
    value = head.get("mfu_strict")
    if failed:
        detail["failed_rows"] = failed
    print(
        json.dumps(
            {
                "metric": metric,
                "value": value,
                "unit": "mfu",
                # north-star: 50% MFU
                "vs_baseline": None if value is None else round(value / 0.50, 4),
                "detail": detail,
            }
        )
    )
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
