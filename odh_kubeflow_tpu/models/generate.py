"""KV-cache autoregressive generation (the fine-tune → try-it story).

The reference platform has no inference code at all (SURVEY.md §2.4);
generation exists here because the TPU notebook workflow it serves —
LoRA fine-tune in the notebook, then sample from the adapter — needs
it. Design is TPU-first:

- **Two compiles total.** Prefill (S = prompt length) and the decode
  step (S = 1) are the only two traced shapes; the decode loop is a
  ``lax.scan`` over a preallocated ``[L, B, S_max, Hkv * hd]`` cache, so
  there are no per-step retraces and no dynamic shapes anywhere.
- **Physical vs logical positions.** Ragged (right-padded) prompts
  share one physical write index — slot ``prompt_pad + step`` — while
  rope uses each row's *logical* position ``prompt_len + step``. The
  pad slots in between are never attended: ``kv_mask`` marks valid
  cache slots and flows into ``dense_attention``.
- **Sharding by annotation**, same as training: params via
  ``param_specs``, the cache via ``cache_specs`` (batch on data/fsdp,
  KV heads on tensor). XLA inserts the collectives.

Sampling: greedy, temperature, top-k, and nucleus (top-p), composed in
that order, matching the semantics of the usual HF ``generate`` knobs.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from odh_kubeflow_tpu.models.llama import (
    CACHE_KINDS,
    INDEXED,
    STATE,
    LlamaConfig,
    Params,
    forward_with_cache,
    kind_of,
    layer_kinds,
    stack_kind,
)
from odh_kubeflow_tpu.parallel.mesh import (
    AXIS_DATA,
    AXIS_FSDP,
    AXIS_TENSOR,
)


@dataclasses.dataclass(frozen=True)
class GenerateConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0  # 0 = greedy
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_id: Optional[int] = None
    pad_id: int = 0
    cache_dtype: Any = jnp.bfloat16


def init_cache(
    cfg: LlamaConfig, batch_size: int, max_len: int, dtype=jnp.bfloat16,
    widest_part: Optional[int] = None,
) -> Params:
    """Preallocated cache, a few stacks a KIND of layer
    (``llama.CACHE_KINDS``, from ``llama.layer_kinds(cfg)``):
    ``{"k","v"}: [L_full, B, max_len, Hkv * hd]`` for the layers that
    see every position, ``{"wk","wv"}: [L_window, B, ring, Hkv * hd]``
    for window layers, and for recurrent layers, which keep NO keys and
    values, their state: ``cfg.state_leaves(dtype)`` names each leaf's
    shape behind ``[L_state, B]`` and its dtype (a Mamba-2 layer: the
    float32 SSM state and the last inputs of its convolution). A layer
    whose queries attend the keys an indexer picks (``INDEXED``) keeps
    keys and values ``{"sk","sv"}`` as a full layer does and the
    indexer's keys ``{"ik"}: [L_indexed, B, index_dim, max_len]``.

    A window layer can only ever be asked for the ``window`` positions
    that end at a query, so it keeps a RING: position ``p`` in slot ``p
    % ring``, ``ring`` = the window plus ``widest_part`` (the most
    positions one call writes before it attends: the engine's prefill
    part, ``generate``'s prompt), rounded up to a multiple of the part
    so that aligned parts never straddle its end. Without
    ``widest_part``, or where that is no shorter, it is ``max_len``
    long and never wraps.

    The stacks are the CARRY of the ``lax.scan`` over the stack's
    periods in ``forward_with_cache`` (``llama.scan_layers_with_cache``):
    a step writes its tokens at ``[layer, row, index]`` and attention
    reads the layer where it lies; no layer is ever sliced out. A
    position's KV heads lie side by side in one row of ``Hkv * hd``
    lanes, so a head is a lane slice of a ``[positions, Hkv * hd]`` tile
    (what ``ops/pallas_decode_attention.py`` walks) and a token's write
    is one contiguous row.
    """
    kinds = layer_kinds(cfg)
    periods = cfg.num_layers // len(kinds)
    layers = collections.Counter(kind_of(k) for k in kinds)

    def stacks(kind, length, names=None):
        shape = (periods * layers[kind], batch_size, length, cfg.kv_dim)
        return {n: jnp.zeros(shape, dtype) for n in names or CACHE_KINDS[kind]}

    cache = {}
    if layers["full"]:
        cache.update(stacks("full", max_len))
    if layers[INDEXED]:
        *kv, ik = CACHE_KINDS[INDEXED]
        cache.update(stacks(INDEXED, max_len, kv))
        # the indexer's keys, one narrow head: positions along the LANES
        # (a row of ``index_dim`` = 64 lanes would be padded to 128)
        cache[ik] = jnp.zeros(
            (periods * layers[INDEXED], batch_size, cfg.index_dim, max_len), dtype
        )
        # a call's counters (``ops/sparse_attention.py``): the positions
        # its queries could see and those they attended
        cache["sel_stats"] = jnp.zeros((2,), jnp.int32)
    if layers["window"]:
        ring = max_len
        if widest_part is not None:
            widest = max(k for k in kinds if kind_of(k) == "window")
            ring = min(max_len, -(-(widest + widest_part) // widest_part) * widest_part)
        cache.update(stacks("window", ring))
    if layers[STATE]:
        lead = (periods * layers[STATE], batch_size)
        cache.update({
            name: jnp.zeros(lead + shape, dt)
            for name, (shape, dt) in cfg.state_leaves(dtype).items()
        })
    if hasattr(cfg, "experts_held"):
        # a call's expert counters (``moe.local_expert_ffn``): the
        # engine zeroes them before a decode chunk and reads them with it
        cache["moe_stats"] = jnp.zeros((4,), jnp.int32)
    return cache


def cache_bytes(cache: Params) -> dict[str, int]:
    """Bytes the cache holds, by kind of layer."""
    return {
        kind: sum(
            cache[n].size * cache[n].dtype.itemsize for n in names if n in cache
        )
        for kind, names in CACHE_KINDS.items()
    }


def cache_specs(cfg: LlamaConfig) -> Params:
    """PartitionSpec tree for ``init_cache`` output.

    Batch shards with the data axes; KV heads shard on tensor (whole
    heads: a shard of the ``Hkv * hd`` axis is what the tensor-sharded
    wk/wv projections produce, so the cache write is collective-free).
    A recurrent layer's state shards over the batch alone.
    """
    batch = (AXIS_DATA, AXIS_FSDP)
    by_kind = {
        "full": P(None, batch, None, AXIS_TENSOR),
        "window": P(None, batch, None, AXIS_TENSOR),
        STATE: P(None, batch),
        # attention and indexer whole on every chip: rows over the batch
        INDEXED: P(None, batch),
        None: P(),
    }
    return {
        name: by_kind[stack_kind(name)]
        for name in jax.eval_shape(lambda: init_cache(cfg, 1, 128))
    }


def sample_logits(
    logits: jnp.ndarray,  # [B, V] float32
    key: jax.Array,
    *,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
) -> jnp.ndarray:
    """Sample next-token ids [B] from final-position logits."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / jnp.float32(temperature)
    if top_k is not None and top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None and top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix whose mass reaches top_p (the token
        # that crosses the threshold is included, per nucleus sampling)
        keep = cum - probs < top_p
        cutoff = jnp.min(
            jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


def family_forward(cfg):
    """(cache-shape config, cached-forward fn) for a config of any
    family — the single model-family dispatch point shared by
    ``generate``, the engine and ``models/spec_decode.py``. A family
    other than the dense one names its module in ``cfg.family_module``
    (imported when first asked for: a replica that serves one family
    loads no other) and its config shapes its own cache; a MoeConfig
    wraps a dense backbone whose shapes drive the cache, and its own
    cached forward routes the MLP through the experts."""
    if hasattr(cfg, "family_module"):
        import importlib

        return cfg, importlib.import_module(cfg.family_module).forward_with_cache
    if hasattr(cfg, "base"):
        from odh_kubeflow_tpu.models import moe as _moe

        return cfg.base, _moe.forward_with_cache
    return cfg, forward_with_cache


def generate(
    params: Params,
    prompt_tokens: jnp.ndarray,  # [B, S_prompt] int32, right-padded
    cfg: LlamaConfig,
    gen_cfg: GenerateConfig,
    *,
    prompt_lengths: Optional[jnp.ndarray] = None,  # [B] int32
    lora: Optional[Params] = None,
    key: Optional[jax.Array] = None,
) -> dict[str, jnp.ndarray]:
    """Autoregressive generation. Pure and jittable.

    Returns ``{"tokens": [B, max_new_tokens], "lengths": [B]}`` where
    ``lengths`` counts generated tokens up to and including the first
    ``eos_id`` (or ``max_new_tokens`` when eos never fires); positions
    past a row's eos hold ``pad_id``.
    """
    B, S_prompt = prompt_tokens.shape
    N = gen_cfg.max_new_tokens
    max_len = S_prompt + N
    if prompt_lengths is None:
        prompt_lengths = jnp.full((B,), S_prompt, jnp.int32)
    prompt_lengths = prompt_lengths.astype(jnp.int32)
    if key is None:
        key = jax.random.key(0)

    cache_cfg, fwd = family_forward(cfg)

    cache = init_cache(
        cache_cfg, B, max_len, gen_cfg.cache_dtype, widest_part=S_prompt
    )
    slots = jnp.arange(max_len, dtype=jnp.int32)[None, :]  # [1, S_max]
    kv_mask = slots < prompt_lengths[:, None]  # prompt region valid

    # --- prefill: whole prompt at physical slots [0, S_prompt) -------
    positions = jnp.broadcast_to(
        jnp.arange(S_prompt, dtype=jnp.int32), (B, S_prompt)
    )
    logits, cache = fwd(
        params,
        prompt_tokens,
        cfg,
        cache,
        jnp.int32(0),
        positions=positions,
        kv_mask=kv_mask,
        lora=lora,
        # right-padded prompts: pad positions are not real tokens (the
        # MoE family's router must not let them consume capacity)
        token_mask=kv_mask[:, :S_prompt],
    )
    # next token comes from each row's last *real* prompt position
    last = jnp.take_along_axis(
        logits, (prompt_lengths - 1)[:, None, None], axis=1
    )[:, 0, :]
    key, sub = jax.random.split(key)
    token = sample_logits(
        last,
        sub,
        temperature=gen_cfg.temperature,
        top_k=gen_cfg.top_k,
        top_p=gen_cfg.top_p,
    )

    # --- decode: one token per step at physical slot S_prompt + i ----
    def step(carry, xs):
        cache, kv_mask, token, done, key = carry
        i, = xs
        write_index = jnp.int32(S_prompt) + i
        kv_mask = kv_mask | (slots == write_index)
        positions = (prompt_lengths + i)[:, None]  # logical rope position
        logits, cache = fwd(
            params,
            token[:, None],
            cfg,
            cache,
            write_index,
            positions=positions,
            kv_mask=kv_mask,
            lora=lora,
        )
        key, sub = jax.random.split(key)
        next_token = sample_logits(
            logits[:, 0, :],
            sub,
            temperature=gen_cfg.temperature,
            top_k=gen_cfg.top_k,
            top_p=gen_cfg.top_p,
        )
        emitted = jnp.where(done, jnp.int32(gen_cfg.pad_id), token)
        if gen_cfg.eos_id is not None:
            done = done | (token == gen_cfg.eos_id)
        next_token = jnp.where(done, jnp.int32(gen_cfg.pad_id), next_token)
        return (cache, kv_mask, next_token, done, key), emitted

    done = jnp.zeros((B,), bool)
    (_, _, _, done, _), tokens = jax.lax.scan(
        step,
        (cache, kv_mask, token, done, key),
        (jnp.arange(N, dtype=jnp.int32),),
    )
    tokens = tokens.T  # [N, B] → [B, N]
    lengths = jnp.sum(tokens != gen_cfg.pad_id, axis=1).astype(jnp.int32)
    return {"tokens": tokens, "lengths": lengths}
