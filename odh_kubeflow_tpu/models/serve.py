"""Minimal completion server: the "try your fine-tune" HTTP surface.

The platform story ends with a user who just LoRA-tuned a model in
their notebook wanting to poke it over HTTP. This is that surface —
stdlib-only (the notebook images ship no web framework), wrapping
``models/generate.py``:

    POST /v1/completions   {"prompt": [[ids...], ...] | [ids...],
                            "max_tokens": N, "temperature": t,
                            "top_k": k, "top_p": p}
      → {"completions": [[ids...], ...], "usage": {...}}
    GET  /healthz

Design constraints honored:
- requests are batched per call; each distinct (batch, prompt-pad,
  max_tokens) shape compiles once and is cached by jit — the server
  pads prompts to the configured bucket sizes so arbitrary requests
  reuse a handful of compiled programs (XLA static-shape discipline);
- params may be the bf16 tree, a LoRA-merged tree, or the int8 tree
  from ``models/quant.py`` (dequantized per layer inside the cache
  scan — the 8B-on-one-v5e path);
- tokenization is out of scope: the platform is model-agnostic and the
  notebook owns the tokenizer; ids in, ids out.
"""

from __future__ import annotations

import collections
import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

from odh_kubeflow_tpu.models.generate import GenerateConfig, generate
from odh_kubeflow_tpu.models.llama import LlamaConfig

Params = dict[str, Any]

DEFAULT_PROMPT_BUCKETS = (64, 256, 1024)
DEFAULT_BATCH_BUCKETS = (1, 4)


def _bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


class CompletionService:
    """Pads to shape buckets and drives jitted generation."""

    def __init__(
        self,
        params: Params,
        cfg: LlamaConfig,
        *,
        lora: Optional[Params] = None,
        draft_params: Optional[Params] = None,
        draft_cfg=None,
        spec_k: int = 4,
        prompt_buckets: Sequence[int] = DEFAULT_PROMPT_BUCKETS,
        batch_buckets: Sequence[int] = DEFAULT_BATCH_BUCKETS,
        pad_id: int = 0,
        engine_slots: int = 0,
        engine_max_len: int = 2048,
    ):
        self.params = params
        self.cfg = cfg
        self.lora = lora
        # optional draft model: greedy single-prompt requests then run
        # speculative decoding (models/spec_decode.py) — exact same
        # output, fewer target weight streams
        self.draft_params = draft_params
        self.draft_cfg = draft_cfg
        self.spec_k = spec_k
        self.prompt_buckets = tuple(prompt_buckets)
        self.batch_buckets = tuple(batch_buckets)
        self.pad_id = pad_id
        self._lock = threading.Lock()  # one TPU program at a time
        # LRU-bounded: every distinct (max_tokens, sampling...) combo
        # compiles a program — unbounded growth would let arbitrary
        # request params exhaust memory on a long-running server
        self._compiled: "collections.OrderedDict" = collections.OrderedDict()
        self.max_compiled = 32
        # continuous batching (models/engine.py): concurrent requests
        # join a persistent slot-batched decode loop instead of
        # serialising behind the lock (measured by the benchmark's
        # serving cells, which build the engine themselves).
        # Off (0) takes the one-shot bucketed path for every request.
        self.engine = None
        if engine_slots > 0:
            from odh_kubeflow_tpu.models.engine import DecodeEngine

            self.engine = DecodeEngine(
                params,
                cfg,
                lora=lora,
                n_slots=engine_slots,
                max_len=engine_max_len,
                prompt_buckets=self.prompt_buckets,
                pad_id=pad_id,
            )

    def _runner(self, gen_cfg: GenerateConfig):
        key = (gen_cfg.max_new_tokens, gen_cfg.temperature, gen_cfg.top_k,
               gen_cfg.top_p, gen_cfg.eos_id)
        if key in self._compiled:
            self._compiled.move_to_end(key)
        else:
            while len(self._compiled) >= self.max_compiled:
                self._compiled.popitem(last=False)
            self._compiled[key] = jax.jit(
                lambda p, lora, prompt, lengths, rng: generate(
                    p,
                    prompt,
                    self.cfg,
                    gen_cfg,
                    prompt_lengths=lengths,
                    lora=lora,
                    key=rng,
                )
            )
        return self._compiled[key]

    def _spec_runner(self, max_tokens: int, eos_id: Optional[int]):
        from odh_kubeflow_tpu.models.spec_decode import (
            SpecDecodeConfig,
            speculative_generate,
        )

        key = ("spec", max_tokens, eos_id, self.spec_k)
        if key in self._compiled:
            self._compiled.move_to_end(key)
            return self._compiled[key]
        while len(self._compiled) >= self.max_compiled:
            self._compiled.popitem(last=False)
        if key not in self._compiled:
            spec_cfg = SpecDecodeConfig(
                max_new_tokens=max_tokens,
                num_draft_tokens=self.spec_k,
                eos_id=eos_id,
                pad_id=self.pad_id,
            )
            self._compiled[key] = jax.jit(
                lambda tp, dp, lora, prompt, lengths: speculative_generate(
                    tp,
                    self.cfg,
                    dp,
                    self.draft_cfg,
                    prompt,
                    spec_cfg,
                    prompt_lengths=lengths,
                    target_lora=lora,
                )
            )
        return self._compiled[key]

    def complete(
        self,
        prompts: list[list[int]],
        *,
        max_tokens: int = 64,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 0.0,
        eos_id: Optional[int] = None,
        seed: Optional[int] = None,
    ) -> dict:
        """``seed`` semantics (API change, round 4): *presence* of a
        seed — including an explicit 0 — requests per-call reproducible
        sampling and takes the one-shot path (the engine's shared rng
        stream cannot honor per-request seeds). Omit it for the
        continuous-batching path. Previously ``seed: 0`` meant
        "default/unseeded"; clients that always send it now get
        deterministic one-shot decodes (and a 400 on streams)."""
        if not prompts or any(not p for p in prompts):
            raise ValueError("prompts must be non-empty token-id lists")
        eng = self.engine
        if eng is not None and eng.failure is not None:
            # a server built around an engine whose engine died is
            # down: answering from the one-shot path would report 200
            # over a lost device (the stream handler says 500 too)
            raise RuntimeError(f"decode engine is down: {eng.failure!r}")

        # greedy single-prompt requests take the speculative path when
        # a draft model is attached: identical output, lower latency
        speculate = (
            self.draft_params is not None
            and len(prompts) == 1
            and temperature == 0.0
        )
        # the engine path first (it needs only the raw prompt lists —
        # no padded device arrays): submit every prompt as its own
        # stream; they decode concurrently with other in-flight HTTP
        # requests. Deterministic-seed requests keep the one-shot path,
        # whose rng is reproducible per call. ALL prompts are checked
        # against the engine bounds before any is submitted, so a
        # too-long prompt can't strand its batchmates in running slots
        # while the one-shot path recomputes everything.
        if (
            eng is not None
            and not speculate
            and seed is None
            and all(
                len(p) <= eng.prompt_buckets[-1]
                and len(p) + max_tokens <= eng.max_len
                for p in prompts
            )
        ):
            handles = [
                eng.submit(
                    p,
                    max_tokens=max_tokens,
                    temperature=temperature,
                    top_k=top_k,
                    top_p=top_p,
                    eos_id=eos_id,
                )
                for p in prompts
            ]
            completions = [h.result(timeout=600) for h in handles]
            return {
                "completions": completions,
                "usage": {
                    "prompt_tokens": sum(len(p) for p in prompts),
                    "completion_tokens": sum(len(c) for c in completions),
                    "engine": True,
                },
            }

        B = _bucket(len(prompts), self.batch_buckets)
        S = _bucket(max(len(p) for p in prompts), self.prompt_buckets)
        if max(len(p) for p in prompts) > S:
            raise ValueError(f"prompt longer than max bucket {S}")

        tokens = jnp.full((B, S), self.pad_id, jnp.int32)
        lengths = jnp.zeros((B,), jnp.int32)
        for i, p in enumerate(prompts):
            tokens = tokens.at[i, : len(p)].set(jnp.asarray(p, jnp.int32))
            lengths = lengths.at[i].set(len(p))
        gen_cfg = GenerateConfig(
            max_new_tokens=max_tokens,
            temperature=temperature,
            top_k=top_k or None,
            top_p=top_p or None,
            eos_id=eos_id,
            pad_id=self.pad_id,
        )
        with self._lock:
            if speculate:
                out = self._spec_runner(max_tokens, eos_id)(
                    self.params,
                    self.draft_params,
                    self.lora,
                    tokens[:1],
                    lengths[:1],
                )
            else:
                out = self._runner(gen_cfg)(
                    self.params, self.lora, tokens, lengths,
                    jax.random.key(0 if seed is None else seed),
                )
            toks = jax.device_get(out["tokens"])
            lens = jax.device_get(out["lengths"])
        completions = [
            toks[i, : int(lens[i])].tolist() for i in range(len(prompts))
        ]
        return {
            "completions": completions,
            "usage": {
                "prompt_tokens": sum(len(p) for p in prompts),
                "completion_tokens": int(sum(lens[: len(prompts)])),
                "padded_shape": [B, S],
            },
        }


def _gen_params(req: dict) -> dict:
    """The sampling knobs shared verbatim by the one-shot and
    streaming paths — one parser so their defaults can't drift."""
    return {
        "max_tokens": int(req.get("max_tokens", 64)),
        "temperature": float(req.get("temperature", 0.0)),
        "top_k": int(req.get("top_k", 0)),
        "top_p": float(req.get("top_p", 0.0)),
        "eos_id": req.get("eos_id"),
    }


def serve(
    service: CompletionService, host: str = "0.0.0.0", port: int = 8000
) -> ThreadingHTTPServer:
    """Start the HTTP surface on a daemon thread; returns the server."""

    class Handler(BaseHTTPRequestHandler):
        def _reply(self, code: int, body: dict):
            data = json.dumps(body).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def do_GET(self):
            if self.path.rstrip("/").endswith("/healthz"):
                self._reply(200, {"status": "ok"})
            else:
                self._reply(404, {"error": "not found"})

        def do_POST(self):
            if not self.path.rstrip("/").endswith("/v1/completions"):
                self._reply(404, {"error": "not found"})
                return
            try:
                length = int(self.headers.get("Content-Length") or 0)
                req = json.loads(self.rfile.read(length).decode() or "{}")
                prompts = req.get("prompt") or []
                if prompts and isinstance(prompts[0], int):
                    prompts = [prompts]
                if req.get("stream"):
                    return self._stream(prompts, req)
                result = service.complete(
                    prompts,
                    seed=(
                        None
                        if req.get("seed") is None
                        else int(req["seed"])
                    ),
                    **_gen_params(req),
                )
                self._reply(200, result)
            except ValueError as e:
                self._reply(400, {"error": str(e)})
            except Exception as e:  # noqa: BLE001 — surface, keep serving
                self._reply(500, {"error": f"{type(e).__name__}: {e}"})

        def _stream(self, prompts, req):
            """``"stream": true`` → Server-Sent Events: one
            ``data: {"token": id}`` frame per decoded token as the
            engine's decode loop produces them, a final
            ``data: {"done": true, "tokens": [...]}`` frame, ids-only
            like the rest of the surface. Requires the continuous-
            batching engine (streaming a bucketed one-shot decode
            would be fake — tokens only exist when the whole batch
            finishes)."""
            if len(prompts) != 1:
                return self._reply(
                    400, {"error": "stream requires exactly one prompt"}
                )
            if req.get("seed") is not None:
                # the engine samples from its own rng stream shared by
                # all slots — a per-request seed cannot be honored;
                # reject rather than silently ignore (the one-shot
                # path honors seeds, without streaming)
                return self._reply(
                    400,
                    {"error": "stream does not support seed; omit it"},
                )
            eng = service.engine
            if eng is None:
                return self._reply(
                    400,
                    {"error": "streaming requires engine_slots > 0"},
                )
            if eng.failure is not None:
                return self._reply(
                    500,
                    {"error": f"decode engine is down: {eng.failure!r}"},
                )
            try:
                handle = eng.submit(
                    prompts[0], stream=True, **_gen_params(req)
                )
            except ValueError as e:  # caller's request is malformed
                return self._reply(400, {"error": str(e)})
            except RuntimeError as e:  # engine died under us → server-side
                return self._reply(500, {"error": str(e)})
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.end_headers()
            try:
                for tok in handle.iter_tokens():
                    self.wfile.write(
                        f"data: {json.dumps({'token': tok})}\n\n".encode()
                    )
                    self.wfile.flush()
                final = {"done": True, "tokens": handle.tokens}
            except OSError:
                # client went away mid-stream: release the slot so it
                # stops decoding the rest of max_tokens for nobody
                handle.cancel()
                return
            except Exception as e:  # noqa: BLE001 — end the stream honestly
                final = {"done": True, "error": f"{type(e).__name__}: {e}"}
            try:
                self.wfile.write(
                    f"data: {json.dumps(final)}\n\n".encode()
                )
                self.wfile.flush()
            except OSError:
                pass  # client went away on the final frame

        def log_message(self, *a):
            pass

    httpd = ThreadingHTTPServer((host, port), Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd


def build_service(argv: Optional[list] = None):
    """Parse the CLI, load the params and build the service — everything
    ``python -m odh_kubeflow_tpu.models.serve`` does before it binds a
    port. Returns ``(service, args)``.

    Loads base params (random-init demo mode without --checkpoint; a
    LoRA adapter checkpoint from ``train/checkpoint.py`` gets merged
    when one is given) and optionally quantizes to int8.
    """
    import argparse

    from odh_kubeflow_tpu.models.llama import init_params

    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--config",
        default="llama3_1b",
        choices=[
            "tiny",
            "llama3_1b",
            "llama3_8b",
            "mixtral_tiny",
            "mixtral_8x1b",
        ],
    )
    parser.add_argument("--checkpoint", default="", help="LoRA ckpt dir (orbax)")
    parser.add_argument("--lora-rank", type=int, default=16)
    parser.add_argument(
        "--seed",
        type=int,
        default=0,
        help="base-param init seed; MUST match the training run's "
        "Trainer seed — adapter checkpoints exclude the frozen base, "
        "so a mismatch silently merges onto the wrong weights",
    )
    parser.add_argument("--int8", action="store_true", help="quantize weights")
    parser.add_argument(
        "--draft-config",
        default="",
        choices=["", "tiny", "llama3_1b"],
        help="attach a draft model: greedy single-stream requests use "
        "speculative decoding (identical output, lower latency)",
    )
    parser.add_argument("--spec-k", type=int, default=4)
    parser.add_argument(
        "--engine-slots",
        type=int,
        default=4,
        help="continuous-batching decode slots (0 = one-shot path only)",
    )
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8000)
    args = parser.parse_args(argv)

    if args.config.startswith("mixtral"):
        from odh_kubeflow_tpu.models.moe import MoeConfig
        from odh_kubeflow_tpu.models import moe as moe_lib

        cfg = getattr(MoeConfig, args.config)()
        if args.checkpoint:
            # MoE LoRA checkpoint: adapters on the attention projections
            # (models/moe.py), restored into a same-seed trainer and
            # merged — the same contract as the dense path below
            from odh_kubeflow_tpu.models.lora import LoraConfig, merge_lora
            from odh_kubeflow_tpu.train import TrainConfig, Trainer
            from odh_kubeflow_tpu.train.checkpoint import CheckpointManager

            trainer = Trainer(
                cfg,
                TrainConfig(),
                lora_cfg=LoraConfig(rank=args.lora_rank),
                seed=args.seed,
            )
            with CheckpointManager(args.checkpoint) as mgr:
                step = trainer.restore_checkpoint(mgr)
            params = merge_lora(trainer.params, trainer.lora_params)
            print(f"restored MoE LoRA adapters at step {step}; merged", flush=True)
        else:
            params = jax.jit(
                lambda k: moe_lib.init_params(k, cfg, dtype=jnp.bfloat16)
            )(jax.random.key(args.seed))
        if args.int8:
            from odh_kubeflow_tpu.models.quant import quantize_params

            params = jax.jit(quantize_params, donate_argnums=0)(params)
        return (
            CompletionService(params, cfg, engine_slots=args.engine_slots),
            args,
        )

    cfg = getattr(LlamaConfig, args.config)(dtype=jnp.bfloat16)

    if args.checkpoint:
        from odh_kubeflow_tpu.models.lora import LoraConfig, merge_lora
        from odh_kubeflow_tpu.train import TrainConfig, Trainer
        from odh_kubeflow_tpu.train.checkpoint import CheckpointManager

        trainer = Trainer(
            cfg,
            TrainConfig(),
            lora_cfg=LoraConfig(rank=args.lora_rank),
            seed=args.seed,
        )
        with CheckpointManager(args.checkpoint) as mgr:
            step = trainer.restore_checkpoint(mgr)
        params = merge_lora(trainer.params, trainer.lora_params)
        print(f"restored LoRA adapters at step {step}; merged", flush=True)
        if args.int8:
            from odh_kubeflow_tpu.models.quant import quantize_params

            # donate: bf16 leaves free as their int8 twins materialise
            params = jax.jit(quantize_params, donate_argnums=0)(params)
            print("quantized to int8", flush=True)
    elif args.int8:
        # demo mode + int8: stream init+quantize per leaf so the bf16
        # tree never fully materialises (8B bf16 alone is 15GiB)
        from odh_kubeflow_tpu.models.quant import streaming_quantized_init

        params = streaming_quantized_init(cfg, jax.random.key(args.seed))
        print("streamed int8 init", flush=True)
    else:
        params = jax.jit(
            lambda k: init_params(k, cfg, dtype=jnp.bfloat16)
        )(jax.random.key(args.seed))

    draft_params, draft_cfg = None, None
    if args.draft_config:
        draft_cfg = getattr(LlamaConfig, args.draft_config)(dtype=jnp.bfloat16)
        if args.int8:
            from odh_kubeflow_tpu.models.quant import streaming_quantized_init

            draft_params = streaming_quantized_init(
                draft_cfg, jax.random.key(args.seed)
            )
        else:
            draft_params = jax.jit(
                lambda k: init_params(k, draft_cfg, dtype=jnp.bfloat16)
            )(jax.random.key(args.seed))

    service = CompletionService(
        params,
        cfg,
        draft_params=draft_params,
        draft_cfg=draft_cfg,
        spec_k=args.spec_k,
        engine_slots=args.engine_slots,
    )
    return service, args


def main(argv: Optional[list] = None) -> None:
    """``python -m odh_kubeflow_tpu.models.serve`` — build the service
    (:func:`build_service`), bind the port, and block."""
    import time

    service, args = build_service(argv)
    httpd = serve(service, host=args.host, port=args.port)
    print(
        f"completion server on http://{args.host}:{httpd.server_address[1]}"
        f" (config={args.config}, int8={args.int8}, "
        f"draft={args.draft_config or 'none'})",
        flush=True,
    )
    while True:
        time.sleep(3600)


if __name__ == "__main__":
    main()
