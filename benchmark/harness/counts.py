"""Operations and bytes from shapes, and the table of peaks. Everything
here reads the configuration FILE (the source's own keys), never the
program's config objects, so that no program PR can move the yardstick;
how many matmul weights a layer holds is the model family's count
(``families/<family>.py``).

Strict accounting for a frozen (QLoRA) base, as Trainer.benchmark had
it: every frozen matmul costs forward + dx = 2 x forward (dW of a
frozen weight is not computed), attention's score and value products
cost 3 x forward (dq, dk, dv are needed to reach the adapters), and a
recomputed operation is never credited. Attention counts only the
(query, key) pairs inside one document of a packed row."""

from __future__ import annotations

import json
import os
from typing import Iterable

_PEAKS_FILE = os.path.join(os.path.dirname(__file__), "peaks.json")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip. A kind that is not in the table is
    an error: a share of an unknown peak is not a number."""
    with open(_PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks known for device_kind {device_kind!r}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]


def _dims(cfg: dict):
    D = cfg["hidden_size"]
    hd = cfg["head_dim"]
    return (
        D, cfg["intermediate_size"], cfg["num_hidden_layers"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"], hd,
        cfg["vocab_size"],
    )


def attention_pairs(segment_lengths: Iterable[int]) -> int:
    """Causal (query, key) pairs inside the documents of packed rows."""
    return sum(n * (n + 1) // 2 for n in segment_lengths)


def forward_flops(cfg: dict, tokens: int, pairs: int, token_weights: int) -> dict:
    """Forward FLOPs of ``tokens`` positions with ``pairs`` attended
    pairs: weight matmuls (2 per weight per token) and attention's two
    products (2 x head_dim each per pair per query head).
    ``token_weights`` is the family's count of the matmul weights one
    token passes through in one layer."""
    _, _, L, H, _, hd, V = _dims(cfg)
    weights = 2 * tokens * (L * token_weights + cfg["hidden_size"] * V)
    attn = L * pairs * H * 2 * 2 * hd
    return {"weights": weights, "attention": attn}


def qlora_step_flops(cfg: dict, tokens: int, pairs: int, token_weights: int) -> float:
    f = forward_flops(cfg, tokens, pairs, token_weights)
    return 2 * f["weights"] + 3 * f["attention"]


def segment_lengths(segment_ids) -> list[int]:
    """Lengths of the documents (pieces) in packed rows; 0 is padding."""
    import numpy as np

    out = []
    for row in np.asarray(segment_ids):
        ids, counts = np.unique(row[row > 0], return_counts=True)
        out.extend(int(c) for c in counts)
    return out


def flash_bound_s(cfg: dict, rows: int, seq: int, pairs: int, pk: dict) -> dict:
    """Least time for one layer's attention kernels over a step: forward
    plus the two backward kernels (dq; dk and dv), each recomputing the
    scores. FLOPs: forward 2 products, backward 5 (s, dp, dq, dk, dv) per
    pair, which is what the algorithm needs, not what remat repeats.
    Bytes: q, k, v, o and their gradients once each in bf16."""
    _, _, _, H, Hkv, hd, _ = _dims(cfg)
    flops = pairs * H * 2 * hd * (2 + 5)
    qo = rows * seq * H * hd * 2
    kv = rows * seq * Hkv * hd * 2
    byts = (2 * qo + 2 * kv) + (4 * qo + 4 * kv)  # fwd r/w; bwd reads + grads
    t_f = flops / pk["bf16_flops_per_s"]
    t_b = byts / pk["hbm_bytes_per_s"]
    return {"seconds": max(t_f, t_b), "bound": "compute" if t_f >= t_b else "memory"}


def decode_step_bytes(cfg: dict, live_kv_tokens: float, step_weights: int) -> float:
    """Bytes one decode step must read: every matmul weight once in
    int8 (embedding rows are a lookup; ``step_weights`` is the family's
    count for one layer) and the live keys and values in bf16."""
    D, _, L, _, Hkv, hd, V = _dims(cfg)
    weights = L * step_weights + D * V
    kv = live_kv_tokens * L * 2 * Hkv * hd * 2
    return weights + kv
