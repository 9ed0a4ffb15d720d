"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py          (on a machine with a TPU; `make chip-smoke`)

Drives the main path once, through the entry points a user has, at the
full width and depth of Llama-3.2-1B (16 layers, D 2048, vocab 128256,
bf16; random weights from a seed), in ONE process on ONE device:

1. *kernels* — the three flash-attention kernels (forward, dq, dkv) at
   the model's head shape (32 q / 8 kv heads × 64), with and without
   ``segment_ids`` and on a 4096-long row of ``pack_documents`` (the
   walk built from the row's ids), compiled by Mosaic and compared with
   the XLA ``dense_attention`` reference (the comparison tests/ makes
   in interpret mode on the CPU);
2. *train* — ``Trainer`` with LoRA r16 at batch 8 × seq 1024: steps on
   one repeated ``make_fake_batch`` (loss finite and falling), then on
   batches from ``train.data.pack_documents`` (``segment_ids`` and
   ``loss_mask`` present), every step run by the ahead-of-time
   executable;
3. *serve* — what ``python -m odh_kubeflow_tpu.models.serve --config
   llama3_1b --int8`` builds, on a local port: concurrent completions
   of different prompt lengths, two identical greedy prompts, one SSE
   stream — every reply from the decode engine, which has not failed;
4. *parts_ahead* — one period of Qwen3-Next at published widths behind
   a small engine: a prompt admitted in three parts beside a decoding
   stream, each part dispatched behind a decode chunk before its fetch,
   the last one narrow (40 tokens in the bucket of 64, through that
   bucket's one program), token for token what the same prompt gives
   admitted whole.

It checks that no fallback that hides the device fired: attention
resolved to ``flash``, no pallas op defaulted to interpret mode, the
compiled steps contain Mosaic custom calls, no step ran the lazy jit
(the trainer's own ``aot_steps`` / ``lazy_steps``), no completion came
from the one-shot path.

Exit code 0 and, as the LAST line of stdout,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
only if every phase passed. Without a TPU (including an inherited
``JAX_PLATFORMS=cpu``) it exits 2 and prints no result. It starts no
process; the threads it starts (engine, HTTP server, clients) are
stopped before it returns.
"""

from __future__ import annotations

import functools
import json
import math
import os
import sys
import threading
import time
import traceback

import jax
import jax.numpy as jnp

BATCH, SEQ = 8, 1024
FAKE_KEYS = ("tokens", "targets")
PACKED_KEYS = ("tokens", "targets", "segment_ids", "loss_mask")


class _CompileCounters:
    """What jax's own monitoring says about compilation in this run:
    compile requests that consulted the persistent cache, how many of
    them it answered, how many entries it wrote, and the seconds the
    backend spent compiling the rest."""

    def __init__(self):
        self.requests = self.hits = self.writes = 0
        self.backend_compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1  # jax counts a miss when it writes the entry

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compile_s += duration

    def report(self) -> dict:
        return {
            "requests": self.requests,
            "hits": self.hits,
            "compiled_again": self.requests - self.hits,
            "entries_written": self.writes,
            "backend_compile_s": round(self.backend_compile_s, 1),
        }


def _rel_err(got, ref) -> float:
    got = got.astype(jnp.float32)
    ref = ref.astype(jnp.float32)
    return float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))


def assert_kernels_compile() -> None:
    """The pallas ops pick interpret mode from the backend; on the chip
    path none of them may."""
    from odh_kubeflow_tpu.ops import (
        pallas_attention,
        pallas_grouped_matmul,
        pallas_int4,
    )

    for mod in (pallas_attention, pallas_grouped_matmul, pallas_int4):
        if mod._interpret_default():
            raise AssertionError(
                f"{mod.__name__} would run in interpret mode on this backend"
            )


def _flash_against_dense(q, k, v, tangent, cases: dict) -> dict:
    """Flash forward and all three gradients against ``dense_attention``
    for each named ``segment_ids`` (None: a plain causal call). bf16
    operands, so agreement is norm-wise: about 1e-2 on the chip, 4e-3 in
    interpret mode, O(1) when a kernel is wrong — bound 5e-2."""
    from odh_kubeflow_tpu.ops import pallas_attention
    from odh_kubeflow_tpu.ops.attention import dense_attention

    # operands travel as arguments: an array closed over by a jitted
    # function is lowered as a literal constant of the program
    def fwd_and_grads(fn, q, k, v, tangent, segment_ids):
        def loss(q, k, v):
            out = fn(q, k, v, causal=True, segment_ids=segment_ids)
            return jnp.sum(out.astype(jnp.float32) * tangent), out

        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True
        )(q, k, v)
        return (out, *grads)

    flash = jax.jit(
        functools.partial(fwd_and_grads, pallas_attention.flash_attention)
    )
    dense = jax.jit(functools.partial(fwd_and_grads, dense_attention))
    errs = {}
    for name, segment_ids in cases.items():
        got = flash(q, k, v, tangent, segment_ids)
        with jax.default_matmul_precision("highest"):
            ref = dense(q, k, v, tangent, segment_ids)
        for part, g, r in zip(("out", "dq", "dk", "dv"), got, ref):
            err = _rel_err(g, r)
            errs[f"{name}.{part}"] = round(err, 5)
            if not err < 5e-2:  # also catches NaN
                raise AssertionError(
                    f"flash {name} {part} disagrees with dense_attention: "
                    f"relative error {err}"
                )
    return errs


def _qkv_tangent(cfg, B: int, S: int):
    kq, kk, kv, kt = jax.random.split(jax.random.key(7), 4)
    shape_q = (B, S, cfg.num_heads, cfg.head_dim)
    shape_kv = (B, S, cfg.num_kv_heads, cfg.head_dim)
    return (
        jax.random.normal(kq, shape_q, jnp.bfloat16),
        jax.random.normal(kk, shape_kv, jnp.bfloat16),
        jax.random.normal(kv, shape_kv, jnp.bfloat16),
        jax.random.normal(kt, shape_q, jnp.bfloat16),
    )


def check_kernels(cfg) -> dict:
    """Flash fwd + bwd against the dense reference: at the training
    leg's geometry (one 1024 block), with and without segment walls, and
    on one 4096-long row of ``pack_documents`` (four 1024 blocks walked
    in tiles from the row's own tables: a document over two block edges,
    walls inside blocks, one wall on a tile's edge), whose live tile
    count must come out below its causal count."""
    import numpy as np

    from odh_kubeflow_tpu.ops import pallas_attention
    from odh_kubeflow_tpu.train.data import pack_documents

    B, S = 2, SEQ
    # three documents a row, walls off any block grid
    pos = jnp.arange(S)[None, :]
    seg = (
        1 + (pos >= (S * 3) // 10) + (pos >= (S * 3) // 4 + 9)
    ).astype(jnp.int32) * jnp.ones((B, 1), jnp.int32)
    errs = _flash_against_dense(
        *_qkv_tangent(cfg, B, S), {"plain": None, "segments": seg}
    )

    long_s = 4 * pallas_attention.DEFAULT_BLOCK_Q
    lengths = (700, 1500, 360, 1024, long_s - 3584)
    docs = [np.full(n, 1 + i, np.int32) for i, n in enumerate(lengths)]
    (row,) = pack_documents(docs, 1, long_s)
    packed = jnp.asarray(row["segment_ids"])
    errs.update(_flash_against_dense(
        *_qkv_tangent(cfg, 1, long_s), {"packed4k": packed}
    ))
    live, causal = pallas_attention.live_block_counts(packed)
    if not 0 < int(live) < causal:
        raise AssertionError(
            f"a packed row's flash walk is not below the causal one: "
            f"{int(live)} live tiles of {causal}"
        )
    errs.update(check_decode_attend(cfg))
    errs.update(check_ring_and_held_experts())
    return {
        "rel_err_vs_dense": errs,
        "packed4k_flash_tiles": {"live": int(live), "causal": causal},
        "state_space": check_state_space_kernels(),
        "delta_rule": check_delta_rule_kernels(),
        "retention": check_retention_kernels(),
        "sparse_attention": check_sparse_attention(),
    }


def check_decode_attend(cfg) -> dict:
    """The cached forward's read (``decode_attend``: a layer of the
    stacked cache, where it lies) against ``dense_attention`` on that
    layer sliced out, at the serve leg's geometry: the engine's decode
    (a [B] index, one token, ragged depths), a speculative window, and a
    prefill (scalar index, a bucket of tokens). Same bound as flash."""
    from odh_kubeflow_tpu.ops.attention import dense_attention
    from odh_kubeflow_tpu.ops.pallas_decode_attention import decode_attend

    L, B, S_max = 3, 4, 2048
    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kq, kk, kv, km = jax.random.split(jax.random.key(11), 4)
    cache_k = jax.random.normal(kk, (L, B, S_max, Hkv * hd), jnp.bfloat16)
    cache_v = jax.random.normal(kv, (L, B, S_max, Hkv * hd), jnp.bfloat16)
    kv_mask = (jax.random.uniform(km, (B, S_max)) < 0.9).at[:, 0].set(True)
    layer = jnp.int32(L - 1)

    @jax.jit
    def dense(q, cache_k, cache_v, layer, index, kv_mask):
        k, v = (
            c[layer].reshape(-1, S_max, Hkv, hd) for c in (cache_k, cache_v)
        )
        return dense_attention(
            q, k, v, causal=True, q_offset=index, kv_mask=kv_mask
        )

    errs = {}
    for name, rows, S, index in (
        ("decode", B, 1, jnp.asarray([0, 300, 1100, S_max - 1], jnp.int32)),
        ("window", B, 5, jnp.asarray([7, 509, 1020, S_max - 5], jnp.int32)),
        ("prefill", 1, 256, jnp.int32(384)),
    ):
        q = jax.random.normal(kq, (rows, S, Hq, hd), jnp.bfloat16)
        operands = (cache_k[:, :rows], cache_v[:, :rows], layer, index,
                    kv_mask[:rows])
        err = _rel_err(decode_attend(q, *operands), dense(q, *operands))
        errs[f"decode_attend.{name}"] = round(err, 5)
        if not err < 5e-2:
            raise AssertionError(
                f"decode_attend {name} disagrees with dense_attention: "
                f"relative error {err}"
            )
    return errs


def check_ring_and_held_experts() -> dict:
    """What a windowed mixture adds to the cached forward (PR 26), each
    against its plain form: ``decode_attend`` over a RING (a window
    layer's cache, wrapped) against ``dense_attention`` over the same
    keys laid out by position, and ``moe_local_ffn`` (held experts read
    from the stacked int8 banks) against plain dots on the dequantised
    layer. Same bound as flash."""
    from odh_kubeflow_tpu.models import moe
    from odh_kubeflow_tpu.ops.attention import dense_attention
    from odh_kubeflow_tpu.ops.pallas_decode_attention import (
        decode_attend,
        slot_positions,
    )

    errs = {}
    L, B, ring, window, Hq, Hkv, hd = 2, 4, 1536, 1024, 32, 8, 128
    index = jnp.asarray([40, 1500, 4000, 9000], jnp.int32)
    last = int(index.max()) + 1
    kq, kk, kv = jax.random.split(jax.random.key(13), 3)
    q = jax.random.normal(kq, (B, 1, Hq, hd), jnp.bfloat16)
    by_pos = [
        jax.random.normal(k, (L, B, last, Hkv * hd), jnp.bfloat16)
        for k in (kk, kv)
    ]
    held = jnp.clip(slot_positions(index, 1, ring), 0, last - 1)
    stack = [a[:, jnp.arange(B)[:, None], held] for a in by_pos]
    got = decode_attend(q, *stack, jnp.int32(1), index, None, window=window)
    want = dense_attention(
        q, *(a[1].reshape(B, last, Hkv, hd) for a in by_pos), causal=True,
        q_offset=index, window=window,
    )
    errs["decode_attend.ring"] = round(_rel_err(got, want), 5)

    E, D, F, k = 4, 1024, 1024, 2
    keys = jax.random.split(jax.random.key(17), 6)

    def bank(key, shape, fan_in):
        w = jax.random.normal(key, shape, jnp.float32) * fan_in**-0.5
        scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        return {"q": jnp.round(w / scale).astype(jnp.int8), "scale": scale}

    banks = {
        "moe_gate": bank(keys[0], (L, E, D, F), D),
        "moe_up": bank(keys[1], (L, E, D, F), D),
        "moe_down": bank(keys[2], (L, E, F, D), F),
    }
    for name, T in (("decode", 16), ("part", 512)):
        h = jax.random.normal(keys[3], (T, D), jnp.bfloat16)
        w, idx = moe.route_sigmoid_topk(
            jax.random.normal(keys[4], (T, 16), jnp.float32), k
        )
        args = (h, w, idx, banks, jnp.int32(1), (8, E))
        kernel, stats = moe.local_expert_ffn(*args, in_place=True)
        plain, _ = moe.local_expert_ffn(*args, in_place=False)
        errs[f"moe_local_ffn.{name}"] = round(_rel_err(kernel, plain), 5)
        assert int(stats[2]) == 0, stats
    for name, err in errs.items():
        if not err < 5e-2:
            raise AssertionError(f"{name}: relative error {err}")
    return errs


def _ms_a_call(fn, *args, n=10) -> float:
    """Wall milliseconds a call of a jitted ``fn``, warm. A single
    argument is a donated state handed from call to call (``fn``'s last
    result)."""
    out = jax.block_until_ready(fn(*args))
    t = time.monotonic()
    for _ in range(n):
        out = fn(out[-1]) if len(args) == 1 else fn(*args)
    jax.block_until_ready(out)
    return round((time.monotonic() - t) * 1e3 / n, 3)


def _all_layers(step, args, layers: int, out_shape: tuple):
    """A decode step's state update in every layer of a stacked state,
    as the layer scan calls it: ``state -> (sum of the outputs,
    state)``, the state donated."""
    def run(state):
        def body(i, carry):
            y, state = carry
            y_i, state = step(*args, state, i)
            return y + y_i, state

        return jax.lax.fori_loop(
            0, layers, body, (jnp.zeros(out_shape, jnp.float32), state)
        )

    return jax.jit(run, donate_argnums=0)


def check_state_space_kernels() -> dict:
    """What a stack with recurrent layers adds (PR 31), each kernel
    Mosaic-compiled at Granite 4.0-H's published widths (128 heads x 64,
    state 128, chunks of 256) against its plain ``jax.numpy`` form:
    ``ssd_chunk_scan`` over a part of 2048 positions with a state in, a
    padded tail and the final state out, against the recurrence token by
    token; ``ssm_decode_update`` for 32 slots on a stacked state against
    the one step written out. Same bound as flash. Also times the decode
    kernel beside its plain form (XLA's own fusion), on the stack a
    pipeline stage holds."""
    from odh_kubeflow_tpu.ops import pallas_ssm as ps

    H, P, N, S, slots, L = 128, 64, 128, 2048, 32, 9
    f32, bf16 = jnp.float32, jnp.bfloat16
    k = jax.random.split(jax.random.key(31), 8)
    dt = jax.nn.softplus(jax.random.normal(k[1], (1, S, H), f32) - 4.0)
    dt = dt * (jnp.arange(S) < S - 300)[None, :, None]  # a padded tail
    A = -jax.random.uniform(k[2], (H,), f32, 1.0, 16.0)
    x = jax.random.normal(k[0], (1, S, H, P), bf16)
    Bm, Cm = (jax.random.normal(kk, (1, S, N), bf16) for kk in k[3:5])
    init = ps.to_state(jax.random.normal(k[5], (1, H, P, N), f32))
    y, fin = ps.ssd_chunk_scan(x, dt, A, Bm, Cm, init)
    y0, fin0 = jax.jit(ps.ssm_scan_plain)(x, dt, A, Bm, Cm, init)
    errs = {
        "ssd_chunk_scan.y": round(_rel_err(y, y0), 5),
        "ssd_chunk_scan.state": round(_rel_err(fin, fin0), 5),
    }

    state = jax.random.normal(k[6], (L, slots) + init.shape[1:], f32)
    xs = jax.random.normal(k[7], (slots, H, P), bf16)
    dts = jnp.broadcast_to(dt[0, :slots], (slots, H)).at[3].set(0.0)
    args = (xs, dts, A, Bm[0, :slots], Cm[0, :slots])
    y1, s1 = ps.ssm_decode_update(*args, state, 4)
    y2, s2 = jax.jit(ps.ssm_step_plain)(*args, state, 4)
    errs["ssm_decode_update.y"] = round(_rel_err(y1, y2), 6)
    errs["ssm_decode_update.state"] = round(_rel_err(s1, s2), 6)
    if not bool(jnp.all(s1[4, 3] == state[4, 3])):
        raise AssertionError("a row with dt = 0 moved its state")
    for name, err in errs.items():
        if not err < 5e-2:
            raise AssertionError(f"{name}: relative error {err}")

    ms = {
        name: _ms_a_call(_all_layers(step, args, L, (slots, H, P)), state + 0)
        for name, step in (
            ("kernel", ps.ssm_decode_update), ("plain", ps.ssm_step_plain),
        )
    }
    return {"rel_err": errs, "ssm_decode_ms_a_step_of_9_layers": ms}


def check_delta_rule_kernels() -> dict:
    """What a stack with Gated DeltaNet layers adds (PR 34), each kernel
    Mosaic-compiled at Qwen3-Next's published widths (16 key heads onto
    32 value heads, 128 x 128, chunks of 64) against its plain
    ``jax.numpy`` form: ``gdn_chunk_scan`` over a part of 2048 positions
    with a state in, a padded tail and the final state out, against the
    recurrence token by token; ``gdn_decode_update`` for 32 slots on a
    stacked state against the one step written out; and ``decode_attend``
    at that family's attention shape (head 256, 8 query heads a KV head,
    a row of 512), which the kernel's ``supported`` admits and no other
    configuration runs. Same bound as flash. Also times both kernels
    beside their plain forms (XLA's own fusions), the scan at each of
    the cell's buckets too (``gdn_chunk_scan_at_<positions>``, PR 41)."""
    from odh_kubeflow_tpu.ops import pallas_gdn as pg
    from odh_kubeflow_tpu.ops.attention import dense_attention
    from odh_kubeflow_tpu.ops.pallas_decode_attention import decode_attend

    Hk, H, dk, dv, S, slots, L = 16, 32, 128, 128, 2048, 32, 9
    f32, bf16 = jnp.float32, jnp.bfloat16
    k = jax.random.split(jax.random.key(34), 10)
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True))  # noqa: E731
    real = (jnp.arange(S) < S - 300)[None, :, None]  # a padded tail
    q = (unit(jax.random.normal(k[0], (1, S, Hk, dk), f32)) * dk**-0.5).astype(bf16)
    kk = unit(jax.random.normal(k[1], (1, S, Hk, dk), f32)).astype(bf16)
    v = jax.random.normal(k[2], (1, S, H, dv), bf16)
    A = jax.random.uniform(k[3], (H,), f32, 1.0, 16.0)
    g = -A * jax.nn.softplus(jax.random.normal(k[4], (1, S, H), f32) - 4.0) * real
    beta = jax.nn.sigmoid(jax.random.normal(k[5], (1, S, H), f32)) * real
    init = jax.random.normal(k[6], (1, H, dk, dv), f32)
    o, fin = pg.gdn_chunk_scan(q, kk, v, g, beta, init)
    o0, fin0 = jax.jit(pg.gdn_scan_plain)(q, kk, v, g, beta, init)
    errs = {
        "gdn_chunk_scan.o": round(_rel_err(o, o0), 5),
        "gdn_chunk_scan.state": round(_rel_err(fin, fin0), 5),
    }
    ms = {
        f"gdn_chunk_scan_{c}": _ms_a_call(
            jax.jit(functools.partial(pg.gdn_chunk_scan, chunk=c)),
            q, kk, v, g, beta, init,
        )
        for c in (64, 128)
    }
    # at each of the cell's buckets (chunks of 64): wall time a call, so
    # a short bucket reads the host's dispatch, not the kernel
    for positions in (64, 256, 1024, 2048):
        ms[f"gdn_chunk_scan_at_{positions}"] = _ms_a_call(
            jax.jit(pg.gdn_chunk_scan),
            *(a[:, :positions] for a in (q, kk, v, g, beta)), init,
        )

    state = jax.random.normal(k[7], (L, slots, H, dk, dv), f32)
    args = (
        q[0, :slots], kk[0, :slots], v[0, :slots],
        g[0, :slots].at[3].set(0.0), beta[0, :slots].at[3].set(0.0),
    )
    y1, s1 = pg.gdn_decode_update(*args, state, 4)
    y2, s2 = jax.jit(pg.gdn_step_plain)(*args, state, 4)
    errs["gdn_decode_update.o"] = round(_rel_err(y1, y2), 6)
    errs["gdn_decode_update.state"] = round(_rel_err(s1, s2), 6)
    if not bool(jnp.all(s1[4, 3] == state[4, 3])):
        raise AssertionError("a row with g = 0 and beta = 0 moved its state")

    for name, step in (
        ("kernel", pg.gdn_decode_update), ("plain", pg.gdn_step_plain),
    ):
        ms[f"gdn_decode_9_layers_{name}"] = _ms_a_call(
            _all_layers(step, args, L, (slots, H, dv)), state + 0
        )

    # the family's attention read: 16 query heads x 256 onto 2 KV heads
    Hq, Hkv, hd, S_max = 16, 2, 256, 2048
    cache_k = jax.random.normal(k[8], (3, 4, S_max, Hkv * hd), bf16)
    cache_v = jax.random.normal(k[9], (3, 4, S_max, Hkv * hd), bf16)
    kv_mask = jnp.ones((4, S_max), bool)
    for name, rows, Sq, index in (
        ("decode", 4, 1, jnp.asarray([0, 300, 1100, S_max - 1], jnp.int32)),
        ("prefill", 1, 256, jnp.int32(384)),
    ):
        qa = jax.random.normal(k[0], (rows, Sq, Hq, hd), bf16)
        got = decode_attend(
            qa, cache_k[:, :rows], cache_v[:, :rows], jnp.int32(2), index,
            kv_mask[:rows],
        )
        want = dense_attention(
            qa, cache_k[2, :rows].reshape(rows, S_max, Hkv, hd),
            cache_v[2, :rows].reshape(rows, S_max, Hkv, hd),
            causal=True, q_offset=index, kv_mask=kv_mask[:rows],
        )
        errs[f"decode_attend_hd256.{name}"] = round(_rel_err(got, want), 5)
    for name, err in errs.items():
        if not err < 5e-2:
            raise AssertionError(f"{name}: relative error {err}")
    return {"rel_err": errs, "ms": ms}


def check_retention_kernels() -> dict:
    """What a stack of power-retention layers adds (PR 40), each kernel
    Mosaic-compiled at Brumby-14B's published head shapes (40 query
    heads onto 8 key/value heads of 128: a state of 65 x 128 x 128 a
    head, 34 MB a layer a stream) against its plain ``jax.numpy`` form:
    ``retention_chunk_scan`` over a part of 2048 positions in chunks of
    128 with a state and a normaliser in, a padded tail and both out,
    against the recurrence token by token; ``retention_decode_update``
    for 16 slots on a stage's stacked state (ten layers, 5.5 GB)
    against the one step written out, one row masked. Same bound as
    flash. From the decode update's compiled module: state and
    normaliser are aliased and no layer's state is a temporary (one
    pass, in place). Also times both kernels, the update beside its
    plain form (XLA's own fusion, on a stack of two layers: 2.56 ms a
    layer against the kernel's 1.70, my chip run, PR 40)."""
    from odh_kubeflow_tpu.ops import pallas_retention as pr

    Hq, Hkv, d, S, slots, L = 40, 8, 128, 2048, 16, 10
    R = pr.phi_rows(d)
    f32, bf16 = jnp.float32, jnp.bfloat16
    k = jax.random.split(jax.random.key(40), 8)
    real = (jnp.arange(S) < S - 300)[None, :, None]  # a padded tail
    q = jax.random.normal(k[0], (1, S, Hq, d), bf16)
    kk = (jax.random.normal(k[1], (1, S, Hkv, d), f32) * real[..., None]).astype(bf16)
    v = jax.random.normal(k[2], (1, S, Hkv, d), bf16)
    log_g = jax.nn.log_sigmoid(jax.random.normal(k[3], (1, S, Hkv), f32) + 5.0) * real
    init = jax.random.normal(k[4], (1, Hkv, R, d, d), f32)
    norm0 = jnp.abs(jax.random.normal(k[5], (1, Hkv, R, d), f32)) + 1.0
    y, fin, zfin = pr.retention_chunk_scan(q, kk, v, log_g, init, norm0)
    y0, fin0, zfin0 = jax.jit(pr.retention_scan_plain)(q, kk, v, log_g, init, norm0)
    errs = {
        "retention_chunk_scan.y": round(_rel_err(y, y0), 5),
        "retention_chunk_scan.state": round(_rel_err(fin, fin0), 5),
        "retention_chunk_scan.norm": round(_rel_err(zfin, zfin0), 5),
    }
    ms = {"retention_chunk_scan_2048": _ms_a_call(
        jax.jit(pr.retention_chunk_scan), q, kk, v, log_g, init, norm0
    )}
    del init, fin, fin0, y, y0

    args = (
        q[0, :slots], kk[0, :slots].at[3].set(0), v[0, :slots],
        log_g[0, :slots].at[3].set(0.0),
    )

    def all_layers(step, layers):
        def run(carry):
            def body(i, c):
                y, (state, norm) = c
                y_i, state, norm = step(*args, state, norm, i)
                return y + y_i, (state, norm)

            return jax.lax.fori_loop(
                0, layers, body, (jnp.zeros((slots, Hq, d), f32), carry)
            )

        return jax.jit(run, donate_argnums=0)

    two = (
        jax.random.normal(k[6], (2, slots, Hkv, R, d, d), f32),
        jnp.abs(jax.random.normal(k[7], (2, slots, Hkv, R, d), f32)) + 1.0,
    )
    y1, s1, z1 = pr.retention_decode_update(*args, *two, 1)
    y2, s2, z2 = jax.jit(pr.retention_step_plain)(*args, *two, 1)
    errs["retention_decode_update.y"] = round(_rel_err(y1, y2), 6)
    errs["retention_decode_update.state"] = round(_rel_err(s1, s2), 6)
    errs["retention_decode_update.norm"] = round(_rel_err(z1, z2), 6)
    if not bool(jnp.all(s1[1, 3] == two[0][1, 3]) & jnp.all(z1[1, 3] == two[1][1, 3])):
        raise AssertionError("a row with log g = 0 and a zero key moved its state")
    if not bool(jnp.all(s1[0] == two[0][0])):
        raise AssertionError("the update of layer 1 moved layer 0")
    del y1, s1, z1, y2, s2, z2
    ms["retention_decode_2_layers_plain"] = _ms_a_call(
        all_layers(pr.retention_step_plain, 2), two
    )
    del two
    for name, err in errs.items():
        if not err < 5e-2:
            raise AssertionError(f"{name}: relative error {err}")

    stack = (
        jnp.zeros((L, slots, Hkv, R, d, d), f32), jnp.ones((L, slots, Hkv, R, d), f32)
    )
    update = jax.jit(pr.retention_decode_update, donate_argnums=(4, 5))
    mem = update.lower(*args, *stack, 4).compile().memory_analysis()
    one_layer = stack[0].nbytes // L
    if mem.alias_size_in_bytes < stack[0].nbytes + stack[1].nbytes:
        raise AssertionError(
            f"the update aliases {mem.alias_size_in_bytes} bytes of a state of "
            f"{stack[0].nbytes + stack[1].nbytes}"
        )
    if mem.temp_size_in_bytes >= one_layer // 4:
        raise AssertionError(
            f"the update's temporaries ({mem.temp_size_in_bytes} bytes) are a "
            f"layer's state ({one_layer}) or near it: not one pass in place"
        )
    ms["retention_decode_10_layers_kernel"] = _ms_a_call(
        all_layers(pr.retention_decode_update, L), stack
    )
    moved = 2 * L * one_layer
    return {
        "rel_err": errs, "ms": ms,
        "decode_update_gb_per_s": round(
            moved / ms["retention_decode_10_layers_kernel"] / 1e6, 1
        ),
        "decode_update_temp_bytes": mem.temp_size_in_bytes,
    }


def check_sparse_attention() -> dict:
    """What a stack whose layers attend only the keys an indexer picks
    adds (PR 42), at Keye-VL-2.0's published shapes (32 query heads onto
    4 key/value heads of 128, an indexer of 16 heads of 64 that keeps
    2048 keys; 16 slots x 24576), each piece against its plain form:
    ``index_scores`` for one query a slot over the stacked indexer keys
    and for a part of 2048 queries at offset 22528; ``kth_largest`` at
    ``[16, 24576]`` and ``[2048, 24576]`` against ``lax.top_k``'s k-th
    value (timed too: it is what the reference's selection costs); the
    kept positions compacted and the key rows gathered from the 4-D
    stack against ``take``; attention under the selection as a mask
    (``decode_attend``'s ``select``) against the masked dense form; a
    decode step's keys written in place as columns. From the gather's
    compiled module: no layer of the stack is copied out. ms a call."""
    from odh_kubeflow_tpu.ops import select
    from odh_kubeflow_tpu.ops import sparse_attention as sa
    from odh_kubeflow_tpu.ops.pallas_decode_attention import decode_attend

    slots, S_max, Hq, Hkv, hd, Hi, di, topk, L, P = 16, 24576, 32, 4, 128, 16, 64, 2048, 2, 2048
    f32, bf16 = jnp.float32, jnp.bfloat16
    k = jax.random.split(jax.random.key(42), 12)
    errs, ms = {}, {}

    # ---- a decode step's pieces, 16 slots at their own depths
    ik = jax.random.normal(k[0], (L, slots, di, S_max), bf16)
    qi = jax.random.normal(k[1], (slots, 1, Hi, di), bf16)
    w = jax.random.normal(k[2], (slots, 1, Hi), f32) / 4
    depth = jnp.asarray(
        [5, 700, 2047, 2048, 4097, 9000, 12000, 24575] * 2, jnp.int32
    )
    seen = sa.visible(depth[:, None], None, S_max)
    scores = sa.index_scores(qi, w, ik, 1, depth)
    plain = jax.jit(sa.index_scores_plain)(qi, w, ik, 1)
    errs["index_scores.decode"] = round(
        _rel_err(jnp.where(seen, scores, 0), jnp.where(seen, plain, 0)), 5
    )
    ms["index_scores_16x1"] = _ms_a_call(jax.jit(sa.index_scores), qi, w, ik, 1, depth)
    ms["index_scores_16x1_plain"] = _ms_a_call(
        jax.jit(sa.index_scores_plain), qi, w, ik, 1
    )
    kth = jax.jit(select.kth_largest, static_argnums=1)
    got = kth(scores[:, 0], topk, seen[:, 0])
    # (two arguments: ``_ms_a_call`` hands a single one on as a state)
    top = jax.jit(lambda x, seen: jax.lax.top_k(
        jnp.where(seen, x, -jnp.inf), topk
    )[0][:, -1])
    want = top(scores[:, 0], seen[:, 0])
    if not bool(jnp.all(got == want)):
        raise AssertionError(f"kth_largest {got} is not lax.top_k's {want}")
    ms["kth_largest_16x24576"] = _ms_a_call(kth, scores[:, 0], topk, seen[:, 0])
    ms["lax_top_k_16x24576"] = _ms_a_call(top, scores[:, 0], seen[:, 0])

    @jax.jit
    def pick(scores, seen):
        thr, cut = sa.select_threshold(scores, seen, topk)
        return sa.compact_positions(sa.selected(scores, seen, thr, cut), topk)

    ids, count = pick(scores[:, 0], seen[:, 0])
    if count.tolist() != jnp.minimum(depth + 1, topk).tolist():
        raise AssertionError(f"kept {count.tolist()} of {(depth + 1).tolist()}")
    _, want_ids = jax.lax.top_k(jnp.where(seen[:, 0], scores[:, 0], -jnp.inf), topk)
    for row in (3, 5, 15):
        if not bool(jnp.all(jnp.sort(want_ids[row]) == ids[row])):
            raise AssertionError(f"row {row}: the kept positions are not lax.top_k's")
    ms["select_and_compact_16x24576"] = _ms_a_call(pick, scores[:, 0], seen[:, 0])

    stack_k = jax.random.normal(k[3], (L, slots, S_max, Hkv * hd), bf16)
    stack_v = jax.random.normal(k[4], (L, slots, S_max, Hkv * hd), bf16)
    gather = jax.jit(sa.gather_rows)
    rows_k, rows_v = gather(stack_k, 1, ids), gather(stack_v, 1, ids)
    take = jnp.take_along_axis(stack_k[1], jnp.minimum(ids, S_max - 1)[..., None], 1)
    if not bool(jnp.all(rows_k == take)):
        raise AssertionError("the gathered rows are not the stack's")
    text = gather.lower(stack_k, 1, ids).compile().as_text()
    copies = cache_layer_copies(text, stack_k)
    if copies:
        raise AssertionError(f"the gather copies a layer out of the stack: {copies[:2]}")
    ms["gather_rows_16x2048"] = _ms_a_call(gather, stack_k, 1, ids)
    q1 = jax.random.normal(k[5], (slots, 1, Hq, hd), bf16)
    attend = jax.jit(decode_attend)
    out = attend(q1, rows_k[None], rows_v[None], 0, count - 1)
    keep = sa.selected(scores, seen, *sa.select_threshold(scores, seen, topk))
    heads = lambda c: c[1].reshape(slots, S_max, Hkv, hd)  # noqa: E731
    dense = jax.jit(sa.masked_attention)(q1, heads(stack_k), heads(stack_v), keep)
    errs["gathered_decode_attend"] = round(_rel_err(out, dense), 5)
    ms["decode_attend_gathered_16x2048"] = _ms_a_call(
        attend, q1, rows_k[None], rows_v[None], 0, count - 1
    )
    write = jax.jit(sa.write_index_keys, donate_argnums=0)
    new = jax.random.normal(k[6], (slots, di), bf16)
    ik_before = ik[1, 3, :, 2040:2056]
    ik = write(ik, new, 1, depth)
    if not bool(jnp.all(ik[1, jnp.arange(slots), :, depth] == new)):
        raise AssertionError("a key was not written at its position")
    if not bool(jnp.all(ik[1, 3, :, 2040:2048] == ik_before[:, :8])):
        raise AssertionError("the write moved its neighbours")
    del stack_k, stack_v, rows_k, rows_v, dense, keep, scores, plain

    # ---- a part of a prompt: 2048 queries at offset 22528
    off = jnp.int32(S_max - P)
    ik1 = jax.random.normal(k[7], (L, 1, di, S_max), bf16)
    qp = jax.random.normal(k[8], (1, P, Hi, di), bf16)
    wp = jax.random.normal(k[9], (1, P, Hi), f32) / 4
    q_pos = off + jnp.arange(P)[None]
    seen = sa.visible(q_pos, None, S_max)
    scores = sa.index_scores(qp, wp, ik1, 1, off)
    plain = jax.jit(sa.index_scores_plain)(qp, wp, ik1, 1)
    errs["index_scores.part"] = round(
        _rel_err(jnp.where(seen, scores, 0), jnp.where(seen, plain, 0)), 5
    )
    ms["index_scores_1x2048"] = _ms_a_call(jax.jit(sa.index_scores), qp, wp, ik1, 1, off)
    got = kth(scores[0], topk, seen[0])
    want = top(scores[0], seen[0])
    if not bool(jnp.all(got == want)):
        raise AssertionError("kth_largest at [2048, 24576] is not lax.top_k's")
    ms["kth_largest_2048x24576"] = _ms_a_call(kth, scores[0], topk, seen[0])
    ms["lax_top_k_2048x24576"] = _ms_a_call(top, scores[0], seen[0], n=2)
    threshold = jax.jit(lambda s, v: sa.select_threshold(s, v, topk))
    thr, cut = threshold(scores, seen)
    ms["select_threshold_2048x24576"] = _ms_a_call(threshold, scores, seen)
    ck = jax.random.normal(k[10], (L, 1, S_max, Hkv * hd), bf16)
    cv = jax.random.normal(k[11], (L, 1, S_max, Hkv * hd), bf16)
    qa = jax.random.normal(k[5], (1, P, Hq, hd), bf16)
    masked = jax.jit(lambda q, k, v, s, t, c: decode_attend(
        q, k, v, 1, off, None, select=(s, t, c)
    ))
    out = masked(qa, ck, cv, scores, thr, cut)
    # the plain form a block of queries at a time: its scores are [32, 256, 24576]
    keep = sa.selected(scores, seen, thr, cut)
    heads = lambda c: c[1].reshape(1, S_max, Hkv, hd)  # noqa: E731
    dense = jax.jit(sa.masked_attention)(
        qa[:, :256], heads(ck), heads(cv), keep[:, :256]
    )
    errs["masked_prefill_attend"] = round(_rel_err(out[:, :256], dense), 5)
    ms["decode_attend_masked_1x2048"] = _ms_a_call(masked, qa, ck, cv, scores, thr, cut)
    ms["decode_attend_plain_1x2048"] = _ms_a_call(
        jax.jit(lambda q, k, v: decode_attend(q, k, v, 1, off, None)), qa, ck, cv
    )
    for name, err in errs.items():
        if not err < 5e-2:
            raise AssertionError(f"{name}: relative error {err}")
    return {"rel_err": errs, "ms": ms}


def cache_layer_copies(hlo_text: str, cache_leaf) -> list:
    """The instructions of a compiled module that produce one whole
    layer of the stacked cache (``[slots, S_max, Hkv * hd]``, with or
    without a leading 1): what copying a layer out of the stack, or back
    into it, looks like."""
    import re

    hlo_type = {"bfloat16": "bf16", "float16": "f16", "float32": "f32"}[
        cache_leaf.dtype.name
    ]
    dims = ",".join(str(d) for d in cache_leaf.shape[1:])
    a_layer = re.compile(rf"= {hlo_type}\[(?:1,)?{dims}\]")
    return [
        line.strip()[:200]
        for line in hlo_text.splitlines()
        if a_layer.search(line) and " parameter(" not in line
    ]


def assert_decode_reads_cache_in_place(engine) -> dict:
    """From the decode chunk's compiled executable: the donated cache is
    aliased to the cache that comes back, nothing the size of the cache
    is a temporary, and no instruction produces a whole layer's keys or
    values. The last is what a relapse looks like: a layer scan that has
    the cache as a scanned input (or a read that XLA serves by copying
    the layer out of the stack) materialises ``[slots, S_max, Hkv * hd]``
    every layer of every step (PERF.md, PR 25). Temporaries as a whole
    cannot be held under one layer's cache: dequantised weights are
    temporaries too, and larger."""
    cache_k = engine._state["cache"]["k"]
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        ((engine.params, engine.lora), engine._state),
    )
    compiled = engine._decode_fn.lower(*shapes).compile()
    mem = compiled.memory_analysis()
    cache_bytes = 2 * cache_k.nbytes
    if mem.alias_size_in_bytes < cache_bytes:
        raise AssertionError(
            f"the decode chunk aliases {mem.alias_size_in_bytes} bytes of its "
            f"donated state; the cache alone is {cache_bytes}"
        )
    if mem.temp_size_in_bytes >= cache_bytes:
        raise AssertionError(
            f"the decode chunk's temporaries ({mem.temp_size_in_bytes} bytes) "
            f"could hold a second cache ({cache_bytes})"
        )
    copies = cache_layer_copies(compiled.as_text(), cache_k)
    if copies:
        raise AssertionError(
            f"the decode chunk materialises a layer of the cache "
            f"{cache_k.shape}: {copies[:3]}"
        )
    return {
        "decode_chunk_temp_bytes": mem.temp_size_in_bytes,
        "cache_layer_bytes": cache_bytes // cache_k.shape[0],
        "cache_aliased_bytes": mem.alias_size_in_bytes,
    }


def _documents(vocab: int, n_tokens: int, seed: int):
    """Seeded documents of heavy-tailed length (a few past one row)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    docs, total = [], 0
    while total < n_tokens:
        n = int(min(2 + rng.pareto(1.2) * 120, 2.5 * SEQ))
        docs.append(rng.integers(1, vocab, size=n, dtype=np.int32))
        total += n
    return docs


def _finite_losses(metrics_list, what: str) -> list:
    losses = [float(m["loss"]) for m in metrics_list]
    gnorms = [float(m["grad_norm"]) for m in metrics_list]
    if not all(math.isfinite(x) for x in losses + gnorms):
        raise AssertionError(f"{what}: non-finite loss/grad {losses} {gnorms}")
    if not all(g > 0 for g in gnorms):
        raise AssertionError(
            f"{what}: zero gradient norm — the backward did not reach "
            f"the adapters: {gnorms}"
        )
    return losses


def train_leg(cfg, device) -> dict:
    from odh_kubeflow_tpu import native
    from odh_kubeflow_tpu.models import LoraConfig
    from odh_kubeflow_tpu.models.llama import resolved_attention_impl
    from odh_kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
    from odh_kubeflow_tpu.train import TrainConfig, Trainer
    from odh_kubeflow_tpu.train.data import pack_documents

    # Trainer(mesh=None) takes ALL of jax.devices(): on a four-chip
    # host that silently becomes an fsdp=4 run
    mesh = build_mesh(MeshConfig(fsdp=1), [device])
    trainer = Trainer(
        cfg,
        TrainConfig(warmup_steps=2, total_steps=100),
        lora_cfg=LoraConfig(rank=16),
        mesh=mesh,
        precompile_batch=(BATCH, SEQ, FAKE_KEYS),
    )
    trainer.precompile_async(BATCH, SEQ, PACKED_KEYS)
    with jax.set_mesh(mesh):
        impl = resolved_attention_impl(cfg)
    if impl != "flash":
        raise AssertionError(f"attention resolved to {impl!r}, not 'flash'")
    used = sorted(str(d) for d in trainer.params["embed"].devices())
    if used != [str(device)]:
        raise AssertionError(f"trainer placed params on {used}, not {device}")

    fake = trainer.make_fake_batch(BATCH, SEQ)
    fake_losses = _finite_losses(
        [trainer.train_step(fake) for _ in range(6)], "fake batch"
    )
    if not fake_losses[-1] < fake_losses[0]:
        raise AssertionError(
            f"loss did not fall on a repeated batch: {fake_losses}"
        )

    docs = _documents(cfg.vocab_size, 3 * BATCH * SEQ + SEQ, seed=11)
    packed_metrics, n_segments = [], []
    for batch in pack_documents(docs, BATCH, SEQ):
        if set(batch) != set(PACKED_KEYS):
            raise AssertionError(f"packed batch keys {sorted(batch)}")
        n_segments.append(int(batch["segment_ids"].max()))
        packed_metrics.append(trainer.train_step(batch))
    if len(packed_metrics) < 3 or max(n_segments) < 2:
        raise AssertionError(
            f"packing gave {len(packed_metrics)} batches, max segments "
            f"{n_segments}"
        )
    packed_losses = _finite_losses(packed_metrics, "packed batches")

    # the steps above ran the ahead-of-time executables, and those hold
    # Mosaic kernels: the trainer counted no lazy step, both shapes
    # resolve to a Compiled, and its HLO calls tpu_custom_call
    n_steps = len(fake_losses) + len(packed_losses)
    if trainer.lazy_steps or trainer.aot_steps != n_steps:
        raise AssertionError(
            f"{trainer.lazy_steps} of {n_steps} steps ran the lazy jit "
            f"({trainer.aot_steps} the ahead-of-time executable)"
        )
    for keys in (FAKE_KEYS, PACKED_KEYS):
        exe = trainer.compiled_step(BATCH, SEQ, keys)
        if not isinstance(exe, jax.stages.Compiled):
            raise AssertionError(f"no AOT executable for {keys}: {exe!r}")
        if "tpu_custom_call" not in exe.as_text():
            raise AssertionError(
                f"the compiled step for {keys} holds no Mosaic kernel"
            )
    return {
        "mesh": {k: v for k, v in mesh.shape.items() if v > 1} or "1 device",
        "devices_used": used,
        "attention_impl": impl,
        "fake_batch_losses": [round(x, 4) for x in fake_losses],
        "packed_losses": [round(x, 4) for x in packed_losses],
        "packed_max_segments_per_row": n_segments,
        "packer": "native" if native.available() else "python",
        "aot_steps": trainer.aot_steps,
        "lazy_steps": trainer.lazy_steps,
    }


def _post(port: int, body: dict, timeout: float = 600.0):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(
            "POST",
            "/v1/completions",
            body=json.dumps(body),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        return resp.status, resp.getheader("Content-Type"), resp.read()
    finally:
        conn.close()


def serve_leg(cfg) -> dict:
    import numpy as np

    from odh_kubeflow_tpu.models.serve import build_service, serve

    # the CLI's own construction path (models/serve.py main)
    service, args = build_service(
        ["--config", "llama3_1b", "--int8", "--host", "127.0.0.1",
         "--port", "0"]
    )
    engine = service.engine
    httpd = serve(service, host=args.host, port=args.port)
    port = httpd.server_address[1]
    try:
        rng = np.random.default_rng(5)
        max_tokens = 16
        prompts = [
            rng.integers(1, cfg.vocab_size, size=n).tolist()
            for n in (5, 40, 200)  # buckets 64, 64, 256
        ]
        twin = prompts[1]
        bodies = [
            {"prompt": p, "max_tokens": max_tokens} for p in prompts
        ] + [{"prompt": twin, "max_tokens": max_tokens}]
        replies: dict = {}

        def client(i, body):
            try:
                replies[i] = _post(port, body)
            except Exception as e:  # noqa: BLE001 — reported below
                replies[i] = e

        threads = [
            threading.Thread(target=client, args=(i, b), daemon=True)
            for i, b in enumerate(bodies)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        completions = []
        for i in range(len(bodies)):
            got = replies.get(i)
            if not isinstance(got, tuple) or got[0] != 200:
                raise AssertionError(f"completion {i}: {got!r}")
            out = json.loads(got[2])
            if out["usage"].get("engine") is not True:
                raise AssertionError(
                    f"completion {i} did not come from the engine: "
                    f"{out['usage']}"
                )
            toks = out["completions"][0]
            if len(toks) != max_tokens or not all(
                isinstance(t, int) and 0 <= t < cfg.vocab_size for t in toks
            ):
                raise AssertionError(f"completion {i}: bad tokens {toks}")
            completions.append(toks)
        if completions[1] != completions[3]:
            raise AssertionError(
                "two identical greedy prompts gave different tokens: "
                f"{completions[1]} vs {completions[3]}"
            )

        # one SSE stream of the same prompt: frames are tokens, the
        # last frame repeats them, and they are the non-streamed answer
        status, ctype, raw = _post(
            port, {"prompt": twin, "max_tokens": max_tokens, "stream": True}
        )
        if status != 200 or ctype != "text/event-stream":
            raise AssertionError(f"stream: {status} {ctype} {raw[:300]!r}")
        frames = [
            json.loads(line[len("data: "):])
            for line in raw.decode().split("\n\n")
            if line.startswith("data: ")
        ]
        streamed = [f["token"] for f in frames if "token" in f]
        final = frames[-1]
        if final.get("done") is not True or "error" in final:
            raise AssertionError(f"stream ended badly: {final}")
        if streamed != final["tokens"] or streamed != completions[1]:
            raise AssertionError(
                f"stream {streamed} / final {final['tokens']} / "
                f"non-streamed {completions[1]}"
            )
        if engine.failure is not None:
            raise AssertionError(f"engine failed: {engine.failure!r}")
        used = sorted(
            str(d) for d in engine._state["cache"]["k"].devices()
        )
        return {
            **assert_decode_reads_cache_in_place(engine),
            "requests": len(bodies) + 1,
            "prompt_lengths": [len(p) for p in prompts] + [len(twin)] * 2,
            "tokens_each": max_tokens,
            "all_from_engine": True,
            "engine_failure": None,
            "devices_used": used,
            "decode_steps": engine.decode_steps,
            "tokens_emitted": engine.tokens_emitted,
        }
    finally:
        httpd.shutdown()
        httpd.server_close()
        engine.stop()


def check_parts_go_ahead() -> dict:
    """The engine's turn on the real device (PR 36): one period of
    Qwen3-Next at published widths (three Gated DeltaNet layers to one
    gated-attention layer; 32 int8 expert banks, so the chunk's expert
    counters live in the cache), a prompt admitted in three parts, two
    of 256 and a final one of 40 tokens that runs NARROW, in the bucket
    of 64 (PR 39: through that bucket's one program, at offset 512),
    while another stream decodes. Each part is dispatched BEHIND a
    decode chunk, before that chunk's tokens are fetched, and the final
    one donates the state those tokens' counters came in. Holds:
    ``parts_ahead`` > 0, the final part ran 64 positions, the stream
    beside it and the admitted request both end, and the admitted
    request's greedy tokens are those of the same prompt admitted whole
    (a bucket of 1024 on an engine of its own): the kernels' per-part
    path (``gdn_chunk_scan`` handed a state from part to part and then
    one chunk of 64 from that state, ``decode_attend`` at head 256 over
    a part of 64 queries at an offset, ``moe_local_ffn`` at a part's
    tile) against their whole-prompt path."""
    import numpy as np

    from odh_kubeflow_tpu.models import qwen3_next as qn
    from odh_kubeflow_tpu.models.engine import DecodeEngine
    from odh_kubeflow_tpu.models.quant import quantize_tensor

    cfg = qn.Qwen3NextConfig(
        num_layers=4, vocab_size=4096, num_experts=32, experts_held=(0, 32)
    )
    params = qn.init_params(jax.random.key(36), cfg, jnp.bfloat16)
    for name in ("moe_gate", "moe_up", "moe_down"):
        params["layers"][name] = quantize_tensor(params["layers"][name])
    rng = np.random.default_rng(36)
    short = rng.integers(1, cfg.vocab_size, size=20).tolist()
    long = rng.integers(1, cfg.vocab_size, size=2 * 256 + 40).tolist()
    shape = dict(n_slots=4, max_len=2048, chunk=4, prompt_buckets=(64, 1024))
    n = 8

    engine = DecodeEngine(params, cfg, prefill_chunk=256, **shape)
    try:
        # every program compiled before the order of dispatches is read
        engine.submit(long, max_tokens=2).result(timeout=900)
        engine.submit(short, max_tokens=n).result(timeout=900)
        parts, ahead = engine.parts, engine.parts_ahead
        t = time.monotonic()
        beside = engine.submit(short, max_tokens=64, stream=True)
        stream = beside.iter_tokens(timeout=900)
        first = [next(stream)]  # it decodes
        admitted = engine.submit(long, max_tokens=n)
        in_parts = admitted.result(timeout=900)
        beside_tokens = first + list(stream)
        wall = time.monotonic() - t
        parts, ahead = engine.parts - parts, engine.parts_ahead - ahead
        programs = set(engine._prefill_fns)
        experts_hit, failure = engine.moe_experts_hit, engine.failure
    finally:
        engine.stop()
    if failure is not None:
        raise AssertionError(f"engine failed: {failure!r}")
    if parts != 3 or not 0 < ahead <= parts:
        raise AssertionError(f"{ahead} of {parts} parts went out ahead")
    if admitted.bucket != 64 or programs != {64, ("part", 256)}:
        raise AssertionError(
            f"the final part ran {admitted.bucket} positions; the engine "
            f"holds the prefill programs {programs}"
        )
    if len(beside_tokens) != 64 or experts_hit <= 0:
        raise AssertionError(
            f"{len(beside_tokens)} tokens beside the admission, "
            f"{experts_hit} expert banks counted"
        )
    engine = DecodeEngine(params, cfg, **shape)
    try:
        whole = engine.submit(long, max_tokens=n).result(timeout=900)
        alone = engine.submit(short, max_tokens=64).result(timeout=900)
    finally:
        engine.stop()
    if in_parts != whole or beside_tokens != alone:
        raise AssertionError(
            f"in parts {in_parts} / whole {whole}; beside the admission "
            f"{beside_tokens} / alone {alone}"
        )
    return {
        "parts": parts, "parts_ahead": ahead,
        "final_part_ran": admitted.bucket, "tokens": in_parts,
        "tokens_beside": len(beside_tokens), "wall_s": round(wall, 3),
    }


def main() -> int:
    t0 = time.monotonic()
    try:
        devices = jax.devices()
    except RuntimeError as e:  # the named platform could not start
        print(f"chip_smoke: no chip found: {e}", file=sys.stderr)
        return 2
    if devices[0].platform != "tpu":
        print(
            "chip_smoke: no chip found: jax.devices()[0].platform is "
            f"{devices[0].platform!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}); this smoke runs on a "
            "TPU only",
            file=sys.stderr,
        )
        return 2

    import jaxlib

    from odh_kubeflow_tpu.models import LlamaConfig
    from odh_kubeflow_tpu.utils.compile_cache import install_process_cache

    assert_kernels_compile()
    # the kernels phase compiles before any Trainer or engine exists:
    # join the cache now, by the same rule they follow
    cache_dir = install_process_cache()
    counters = _CompileCounters()
    device = devices[0]
    device_info = {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(devices),
    }
    cfg = LlamaConfig.llama3_1b(dtype=jnp.bfloat16)
    report: dict = {
        "device": {**device_info, "used": [str(device)]},
        "versions": {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "libtpu": _libtpu_version(),
        },
        "model": {
            "config": "llama3_1b",
            "layers": cfg.num_layers,
            "hidden": cfg.hidden_size,
            "vocab": cfg.vocab_size,
            "params": cfg.num_params(),
        },
    }
    phases = (
        ("kernels", lambda: check_kernels(cfg)),
        ("train", lambda: train_leg(cfg, device)),
        ("serve", lambda: serve_leg(cfg)),
        ("parts_ahead", check_parts_go_ahead),
    )
    failed = []
    for name, fn in phases:
        t_phase = time.monotonic()
        try:
            result = fn()
            result["ok"] = True
        except Exception as e:  # noqa: BLE001 — a phase fails the smoke
            traceback.print_exc()
            result = {"ok": False, "error": f"{type(e).__name__}: {e}"[:500]}
            failed.append(name)
        result["wall_s"] = round(time.monotonic() - t_phase, 1)
        report[name] = result
        print(f"chip_smoke phase {name}: {json.dumps(result)}", flush=True)
    report["compile_cache"] = {
        "dir": cache_dir,
        "placed_by": "JAX_COMPILATION_CACHE_DIR"
        if os.environ.get("JAX_COMPILATION_CACHE_DIR")
        else "checkout",
        **counters.report(),
    }
    report["wall_s"] = round(time.monotonic() - t0, 1)
    print(f"chip_smoke report: {json.dumps(report)}", flush=True)
    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device_info}), flush=True)
    return 0


def _libtpu_version() -> str:
    from importlib import metadata

    try:
        return metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        return "not installed"


if __name__ == "__main__":
    sys.exit(main())
