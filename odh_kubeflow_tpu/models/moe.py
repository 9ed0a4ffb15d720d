"""Sparse Mixture-of-Experts Llama (Mixtral-shaped), expert-parallel.

The reference platform ships no model code at all (SURVEY.md §2.4); the
TPU rebuild carries models as first-class runtime components. This
module adds the MoE family on top of the dense Llama blocks
(``models/llama.py``): same attention stack, but every decoder layer's
MLP is a top-k router over E expert FFNs.

TPU-first design (GShard/Switch einsum dispatch, not gather/scatter):

- **Static shapes everywhere.** Token→expert routing uses one-hot
  dispatch/combine tensors of shape [B, S, E, C] (C = per-expert
  capacity derived from ``capacity_factor``); overflow tokens are
  dropped (their combine weight is 0) rather than reshaping — XLA/MXU
  want fixed shapes, and the aux loss keeps overflow rare.
- **Expert parallelism via sharding, not message passing.** Expert
  weights are [E, D, F] sharded over the ``expert`` mesh axis
  (``parallel/mesh.py``); the dispatch einsum's contraction against
  expert-sharded operands makes GSPMD insert the token⇄expert
  all-to-all on ICI. No hand-written collective anywhere.
- **The expert axis doubles as a data axis** for the dense parts
  (attention, norms, embeddings) — see ``mesh.batch_spec``.

Aux load-balancing loss is the Switch-Transformer form:
``E * Σ_e f_e·p_e`` (fraction dispatched × mean router prob).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from odh_kubeflow_tpu.models import llama
from odh_kubeflow_tpu.models.llama import LlamaConfig
from odh_kubeflow_tpu.ops.norms import rms_norm
from odh_kubeflow_tpu.ops.rope import rope_angles
from odh_kubeflow_tpu.parallel.mesh import (
    AXIS_CONTEXT,
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_PIPE,
    AXIS_TENSOR,
    constrain,
)

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    """MoE extension of a Llama backbone config."""

    base: LlamaConfig = dataclasses.field(default_factory=LlamaConfig)
    num_experts: int = 8
    num_experts_per_tok: int = 2
    capacity_factor: float = 1.25
    router_aux_loss_coef: float = 0.02
    # "ragged": index-table gather/scatter dispatch (no O(B·S·E·C·D)
    # bookkeeping matmuls — the small-batch winner); "einsum": the
    # GShard one-hot reference form; "grouped": dropless sorted
    # grouped-GEMM pallas kernels (ops/pallas_grouped_matmul.py)
    dispatch: str = "ragged"
    # Expert-parallel row budget for the grouped path under a sharded
    # mesh (``_moe_mlp_grouped_ep``): each expert-shard's sorted buffer
    # holds ``ceil(group_assignments · ep_capacity_factor / ep)`` rows.
    # ``None`` (default) sizes the buffer for the worst case — every
    # assignment landing on one shard — which keeps the path EXACTLY
    # dropless (the honest default) at the cost of per-device GEMM work
    # not shrinking with ep; production deployments with balanced
    # routers set ~1.25–2.0 for true ep-fold compute scaling, accepting
    # bounded drops (weight-0, like the ragged path's capacity drops)
    # under pathological imbalance. The budget bounds the DEVICE's
    # whole expert set, not each expert — far slacker than per-expert
    # capacity at equal memory.
    ep_capacity_factor: Optional[float] = None
    # with remat on, additionally pin the grouped path's gate
    # activation ("moe_g", [B·S·k, F] bf16 per layer): with frozen
    # (QLoRA) banks the backward needs g and u only for silu', so
    # pinning g leaves exactly one recomputed expert matmul (u) —
    # executed expert units drop 8 → 7 per layer per step at ~M·F
    # bytes/layer of residency (8×1B @ 4k: ~0.27GB/layer, which fits
    # beside the int8 base; pinning u as well would not)
    pin_expert_acts: bool = False

    @staticmethod
    def mixtral_tiny(**kw) -> "MoeConfig":
        """Unit-test shape (Mixtral topology, milliseconds on CPU)."""
        d = dict(base=LlamaConfig.tiny(), num_experts=4, num_experts_per_tok=2)
        d.update(kw)
        return MoeConfig(**d)

    @staticmethod
    def mixtral_8x1b(**kw) -> "MoeConfig":
        """8-expert MoE on the Llama-3.2-1B backbone (the single-chip
        benchable shape; Mixtral-8x7B is the same topology scaled).

        The base defaults to ``remat_policy="attn"``: "dots" would pin
        every expert einsum output (~10GiB at seq 4096 batch 2), while
        "attn" pins only the flash residuals + combined expert output
        (~1.6GiB) — the measured single-chip sweet spot."""
        d = dict(
            base=LlamaConfig.llama3_1b(remat_policy="attn"),
            num_experts=8,
            num_experts_per_tok=2,
        )
        d.update(kw)
        return MoeConfig(**d)

    @property
    def vocab_size(self) -> int:
        return self.base.vocab_size

    def capacity(self, tokens_per_group: int) -> int:
        """Per-expert slot count for a routing group (static)."""
        c = (
            tokens_per_group
            * self.num_experts_per_tok
            * self.capacity_factor
            / self.num_experts
        )
        return max(int(-(-c // 1)), 1)

    def num_params(self) -> int:
        b = self.base
        dense = b.num_params()
        per_layer_mlp = 3 * b.hidden_size * b.intermediate_size
        # replace the dense MLP with E experts + router
        return dense + b.num_layers * (
            (self.num_experts - 1) * per_layer_mlp
            + b.hidden_size * self.num_experts
        )

    def flops_per_token(self, seq_len: int) -> float:
        """Forward matmul FLOPs per token: dense model minus its MLP,
        plus k active experts + router (the sparse-MoE accounting)."""
        b = self.base
        dense = b.flops_per_token(seq_len)
        mlp = 2 * 3 * b.hidden_size * b.intermediate_size
        router = 2 * b.hidden_size * self.num_experts
        return dense + b.num_layers * (
            (self.num_experts_per_tok - 1) * mlp + router
        )


# ---------------------------------------------------------------------------
# params


def init_params(key: jax.Array, cfg: MoeConfig, dtype=jnp.float32) -> Params:
    b = cfg.base
    params = llama.init_params(key, b, dtype=dtype)
    D, F, E, L = b.hidden_size, b.intermediate_size, cfg.num_experts, b.num_layers
    k_router, k_gate, k_up, k_down = jax.random.split(jax.random.fold_in(key, 7), 4)
    scale = 1.0 / (D ** 0.5)
    layers = params["layers"]
    # the dense MLP weights are replaced by expert banks + router
    for name in ("w_gate", "w_up", "w_down"):
        del layers[name]
    layers["router"] = (
        jax.random.normal(k_router, (L, D, E), dtype) * scale
    )
    layers["moe_gate"] = jax.random.normal(k_gate, (L, E, D, F), dtype) * scale
    layers["moe_up"] = jax.random.normal(k_up, (L, E, D, F), dtype) * scale
    layers["moe_down"] = jax.random.normal(k_down, (L, E, F, D), dtype) * (
        1.0 / (F ** 0.5)
    )
    return params


def param_specs(cfg: MoeConfig) -> Params:
    specs = llama.param_specs(cfg.base)
    layers = specs["layers"]
    for name in ("w_gate", "w_up", "w_down"):
        del layers[name]
    layers["router"] = P(None, AXIS_FSDP, None)
    if cfg.dispatch == "grouped":
        # grouped kernels run on full [K, N] expert blocks per device:
        # banks shard over the expert axis ONLY (the EP memory story —
        # 1/ep of the banks per device); fsdp/tensor shard the dense
        # weights as usual
        layers["moe_gate"] = P(None, AXIS_EXPERT, None, None)
        layers["moe_up"] = P(None, AXIS_EXPERT, None, None)
        layers["moe_down"] = P(None, AXIS_EXPERT, None, None)
    else:
        # expert banks: E over the expert axis, F over tensor, D over fsdp
        layers["moe_gate"] = P(None, AXIS_EXPERT, AXIS_FSDP, AXIS_TENSOR)
        layers["moe_up"] = P(None, AXIS_EXPERT, AXIS_FSDP, AXIS_TENSOR)
        layers["moe_down"] = P(None, AXIS_EXPERT, AXIS_TENSOR, AXIS_FSDP)
    return specs


# ---------------------------------------------------------------------------
# routing + expert compute


def _routing_topk(
    router_logits: jnp.ndarray,  # [B, S, E] float32
    cfg: MoeConfig,
    token_mask: Optional[jnp.ndarray] = None,  # [B, S] bool; False = pad
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Shared routing preamble for both dispatch representations:
    renormalised top-k probs/ids + the Switch aux loss (balance
    fraction-routed vs mean prob per expert). One copy, so the
    einsum-vs-ragged equivalence the tests pin cannot drift.

    ``token_mask`` excludes padding from the aux statistics (a
    bucket-padded prefill or packed batch must not skew the balance
    objective with phantom tokens)."""
    top_p, top_idx, f, p = _routing_stats(router_logits, cfg, token_mask)
    E = router_logits.shape[-1]
    aux_loss = E * jnp.sum(f * p) * cfg.router_aux_loss_coef
    return top_p, top_idx, aux_loss


def _routing_stats(
    router_logits: jnp.ndarray,  # [B, S, E] float32
    cfg: MoeConfig,
    token_mask: Optional[jnp.ndarray] = None,  # [B, S] bool; False = pad
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k probs/ids plus the per-expert balance statistics ``(f, p)``
    the Switch aux loss is built from — split out so the expert-
    parallel path can average f/p ACROSS batch shards before taking the
    product (matching the global-batch aux exactly; averaging the
    per-shard products would not)."""
    top_p, top_idx, probs = route_softmax_topk(
        router_logits, cfg.num_experts_per_tok
    )
    E = router_logits.shape[-1]
    first_choice = jax.nn.one_hot(top_idx[..., 0], E, dtype=jnp.float32)
    if token_mask is None:
        f = first_choice.mean(axis=(0, 1))  # fraction routed per expert
        p = probs.mean(axis=(0, 1))
    else:
        m = token_mask.astype(jnp.float32)[..., None]
        denom = jnp.maximum(m.sum(), 1.0)
        f = (first_choice * m).sum(axis=(0, 1)) / denom
        p = (probs * m).sum(axis=(0, 1)) / denom
    return top_p, top_idx, f, p


def route_tokens(
    router_logits: jnp.ndarray,  # [B, S, E] float32
    cfg: MoeConfig,
    token_mask: Optional[jnp.ndarray] = None,  # [B, S] bool; False = pad
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k routing with per-(batch-row) capacity.

    Returns ``(dispatch [B,S,E,C] bool, combine [B,S,E,C] f32,
    aux_loss scalar)``. Group = batch row (the GShard grouping): the
    cumulative-sum position is per row, so capacity stays static under
    any batch sharding.

    ``token_mask`` (False = padding) keeps pad tokens out of the
    expert buffers entirely: without it a bucket-padded prefill's pad
    positions CONSUME CAPACITY and can evict real tokens' expert
    slots — real outputs would then differ between padded and
    unpadded execution of the same prompt.
    """
    B, S, E = router_logits.shape
    k = cfg.num_experts_per_tok
    C = cfg.capacity(S)
    top_p, top_idx, aux_loss = _routing_topk(router_logits, cfg, token_mask)

    dispatch = jnp.zeros((B, S, E, C), jnp.bool_)
    combine = jnp.zeros((B, S, E, C), jnp.float32)
    # running per-expert fill count per batch row, across the k slots
    fill = jnp.zeros((B, E), jnp.int32)
    for slot in range(k):
        onehot = jax.nn.one_hot(top_idx[..., slot], E, dtype=jnp.int32)  # [B,S,E]
        if token_mask is not None:
            onehot = onehot * token_mask.astype(jnp.int32)[..., None]
        # position of each token within its expert's capacity buffer
        pos = jnp.cumsum(onehot, axis=1) - onehot + fill[:, None, :]  # [B,S,E]
        keep = (pos < C) & (onehot > 0)
        pos_oh = jax.nn.one_hot(pos, C, dtype=jnp.float32) * keep[..., None]
        dispatch = dispatch | (pos_oh > 0)
        combine = combine + pos_oh * top_p[..., slot, None, None] * onehot[..., None]
        fill = fill + onehot.sum(axis=1)
    return dispatch, combine, aux_loss


def route_tables(
    router_logits: jnp.ndarray,  # [B, S, E] float32
    cfg: MoeConfig,
    token_mask: Optional[jnp.ndarray] = None,  # [B, S] bool; False = pad
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Ragged-dispatch form of :func:`route_tokens`: the inverse index
    tables instead of the one-hot [B,S,E,C] tensors.

    Returns ``(idx [B,E,C] int32, w [B,E,C] f32, aux_loss)`` where
    ``idx[b,e,c]`` is the source token position s assigned to expert
    e's capacity slot c in row b (-1 = empty slot) and ``w`` its
    combine weight. Same routing decisions as route_tokens (same top-k,
    same per-row cumulative-sum capacity, same aux loss) — the
    einsum-path tests pin the equivalence. Cost is k scatters of B·S
    elements; the [B,S,E,C] one-hots (whose dispatch/combine einsums
    are O(B·S·E·C·D) MACs — at 8×1B/seq-4096 ~170 TFLOP per layer,
    dwarfing the actual expert MLPs) never materialise.
    """
    B, S, E = router_logits.shape
    k = cfg.num_experts_per_tok
    C = cfg.capacity(S)
    top_p, top_idx, aux_loss = _routing_topk(router_logits, cfg, token_mask)

    b_grid = jnp.arange(B, dtype=jnp.int32)[:, None]
    s_grid = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    # idx via add on a -1 base: capacity guarantees each (b,e,c) cell
    # receives at most one assignment, so add(s+1) reconstructs s
    idx = jnp.full((B, E, C), -1, jnp.int32)
    w = jnp.zeros((B, E, C), jnp.float32)
    fill = jnp.zeros((B, E), jnp.int32)
    for slot in range(k):
        e_sel = top_idx[..., slot]  # [B,S]
        onehot = jax.nn.one_hot(e_sel, E, dtype=jnp.int32)
        if token_mask is not None:
            # pad tokens neither consume capacity (onehot) nor write
            # table entries (keep)
            onehot = onehot * token_mask.astype(jnp.int32)[..., None]
        pos = jnp.cumsum(onehot, axis=1) - onehot + fill[:, None, :]
        p_sel = jnp.take_along_axis(pos, e_sel[..., None], 2)[..., 0]
        keep = p_sel < C
        if token_mask is not None:
            keep = keep & token_mask
        c_clip = jnp.clip(p_sel, 0, C - 1)
        idx = idx.at[b_grid, e_sel, c_clip].add(
            jnp.where(keep, s_grid + 1, 0)
        )
        w = w.at[b_grid, e_sel, c_clip].add(
            jnp.where(keep, top_p[..., slot], 0.0)
        )
        fill = fill + onehot.sum(axis=1)
    return idx, w, aux_loss


def moe_mlp(
    x: jnp.ndarray,  # [B, S, D]
    layer: Params,  # router [D,E], moe_gate/up [E,D,F], moe_down [E,F,D]
    cfg: MoeConfig,
    token_mask: Optional[jnp.ndarray] = None,  # [B, S] bool; False = pad
    bank_base: Optional[jnp.ndarray] = None,  # int32 [1]; stacked banks
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (out [B,S,D], aux_loss). Dispatch/combine implementation
    selected by ``cfg.dispatch``: "grouped" (dropless sorted-token
    pallas grouped-GEMM — the single-chip perf path), "ragged"
    (default — index-table gather/scatter, zero bookkeeping matmul
    FLOPs) or "einsum" (the GShard one-hot form, kept as the reference
    semantics).

    ``bank_base``: the expert-bank leaves of ``layer`` hold EVERY
    layer's banks ([L·E, ...], ``forward``'s stacked-bank scan) and
    this layer's groups start at ``bank_base`` — grouped dispatch
    only."""
    if cfg.dispatch == "grouped":
        if _grouped_usable(x, cfg):
            return _moe_mlp_grouped(
                x, layer, cfg, token_mask, bank_base=bank_base
            )
        if _grouped_ep_usable(x, cfg):
            return _moe_mlp_grouped_ep(
                x, layer, cfg, token_mask, bank_base=bank_base
            )
        reason = _grouped_mesh_blocker(x, cfg)
        if reason is not None:
            # an EXPLICIT error, never a silent dropping fallback
            # (round-4 verdict item 1): anything that is not the
            # by-design tiny-batch decode case raises with the reason
            raise ValueError(
                f"dispatch='grouped': {reason}; use dispatch='ragged' "
                "for this configuration"
            )
        if bank_base is not None:
            raise ValueError(
                "stacked expert banks (bank_base) require the grouped "
                "dispatch path; forward() only selects them when "
                "_grouped_usable/_grouped_ep_usable holds for the "
                "whole scan"
            )
        # tiny per-device batches (decode steps: a handful of tokens)
        # take the ragged path by design — no kernel launch for
        # group·k < 2048 assignments. Capacity is forced to the
        # provably drop-free bound (cf = E/k ⇒ per-row capacity = S):
        # the over-compute is trivial at these sizes and keeps this
        # fallback EXACT for any S, not just the S=1 decode step —
        # grouped dispatch never silently drops anywhere.
        cfg_exact = dataclasses.replace(
            cfg,
            capacity_factor=max(
                cfg.capacity_factor,
                cfg.num_experts / cfg.num_experts_per_tok,
            ),
        )
        layer = llama._maybe_dequant(layer, x.dtype)
        return _moe_mlp_ragged(x, layer, cfg_exact, token_mask)
    if cfg.dispatch == "ragged":
        return _moe_mlp_ragged(x, layer, cfg, token_mask)
    if cfg.dispatch != "einsum":
        raise ValueError(
            f"unknown dispatch {cfg.dispatch!r}; expected 'grouped', "
            "'ragged' or 'einsum'"
        )
    dtype = x.dtype
    router_logits = _router_logits(x, layer)
    dispatch, combine, aux = route_tokens(router_logits, cfg, token_mask)

    # token→expert all-to-all: contraction against expert-sharded
    # operands; GSPMD inserts the collective
    xin = jnp.einsum("bsec,bsd->ebcd", dispatch.astype(dtype), x)
    out_e = _expert_mlp(xin, layer, dtype)
    # expert→token all-to-all back
    out = jnp.einsum("bsec,ebcd->bsd", combine.astype(dtype), out_e)
    out = constrain(out, llama._activation_spec())
    return out, aux


def _router_logits(x, layer):
    router_logits = jnp.einsum(
        "bsd,de->bse", x, layer["router"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    )
    return constrain(
        router_logits, P((AXIS_DATA, AXIS_FSDP, AXIS_EXPERT), None, None)
    )


def _expert_mlp(xin, layer, dtype):
    """The expert SwiGLU block on [E,B,C,D], shared by both dispatch
    paths. Inside it the batch dim keeps its data×fsdp parallelism
    (e over expert, b over data+fsdp) — all devices stay busy in the
    expert MLPs — and BOTH ends are pinned (xin and out_e): an
    unconstrained boundary lets the partitioner invent d-split operand
    shardings for the dispatch/combine transposes, which it can only
    realise by full rematerialization ("[SPMD] Involuntary full
    rematerialization" in the r2 multichip dryrun)."""
    expert_spec = P(AXIS_EXPERT, (AXIS_DATA, AXIS_FSDP), None, None)
    xin = constrain(xin, expert_spec)
    gate = jnp.einsum("ebcd,edf->ebcf", xin, layer["moe_gate"].astype(dtype))
    up = jnp.einsum("ebcd,edf->ebcf", xin, layer["moe_up"].astype(dtype))
    h = jax.nn.silu(gate) * up
    out_e = jnp.einsum("ebcf,efd->ebcd", h, layer["moe_down"].astype(dtype))
    return constrain(out_e, expert_spec)


def _moe_mlp_ragged(
    x: jnp.ndarray,  # [B, S, D]
    layer: Params,
    cfg: MoeConfig,
    token_mask: Optional[jnp.ndarray] = None,  # [B, S] bool; False = pad
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Index-table dispatch: gather tokens into [E,B,C,D], run the
    expert MLPs (identical einsums to the GShard path), scatter-add the
    weighted outputs back. Data movement is O(E·C·D) per row — the
    dispatch/combine matmuls of the one-hot form are gone, which is
    what was limiting the 8×1B QLoRA config at batch 2 (VERDICT r2
    item 6). Gather/scatter transpose to each other, so the backward
    is the mirror image with the same cost."""
    dtype = x.dtype
    B, S, D = x.shape
    E = cfg.num_experts
    C = cfg.capacity(S)

    idx, w, aux = route_tables(_router_logits(x, layer), cfg, token_mask)
    # pinned by the same remat names as the grouped path (tiny): the
    # backward re-runs gather/experts/scatter but not the routing
    idx = llama._checkpoint_name(idx, "moe_route_src")
    w = llama._checkpoint_name(w, "moe_route_w")

    flat_idx = idx.reshape(B, E * C)
    valid = (flat_idx >= 0)[..., None].astype(dtype)
    gath = jnp.take_along_axis(
        x, jnp.clip(flat_idx, 0, S - 1)[..., None], axis=1
    ) * valid  # [B, E*C, D]; empty slots read token 0, zeroed here
    xin = gath.reshape(B, E, C, D).transpose(1, 0, 2, 3)  # [E,B,C,D]
    out_e = _expert_mlp(xin, layer, dtype)

    # weighted scatter-add back to token order; w is 0 on empty slots,
    # so the clipped index-0 writes contribute nothing
    contrib = out_e.transpose(1, 0, 2, 3).reshape(B, E * C, D)
    contrib = contrib * w.reshape(B, E * C)[..., None].astype(dtype)
    contrib = constrain(
        contrib, P((AXIS_DATA, AXIS_FSDP, AXIS_EXPERT), None, None)
    )
    out = jnp.zeros((B, S, D), dtype).at[
        jnp.arange(B, dtype=jnp.int32)[:, None],
        jnp.clip(flat_idx, 0, S - 1),
    ].add(contrib)
    out = constrain(out, llama._activation_spec())
    return out, aux


def _grouped_usable(x: jnp.ndarray, cfg: MoeConfig) -> bool:
    """The grouped-GEMM path runs one unpartitioned pallas kernel, so
    it is the right choice exactly when the expert compute is local:
    single chip (or a mesh whose model axes are trivial) and enough
    assignments that the 512-row alignment padding is noise. Decode
    steps (tiny B·S·k) and expert/tensor/fsdp-sharded meshes fall back
    to the ragged path, whose einsums GSPMD knows how to shard."""
    B, S, _ = x.shape
    if B * S * cfg.num_experts_per_tok < 2048:
        return False
    am = jax.sharding.get_abstract_mesh()
    if not am.empty:
        for ax in (
            AXIS_EXPERT, AXIS_TENSOR, AXIS_FSDP, AXIS_DATA, AXIS_CONTEXT,
        ):
            if am.shape.get(ax, 1) > 1:
                return False
    return True


def route_sorted(
    router_logits: jnp.ndarray,  # [B, S, E] float32
    cfg: MoeConfig,
    token_mask: Optional[jnp.ndarray] = None,  # [B, S] bool; False = pad
) -> tuple[
    jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray
]:
    """Dropless sorted-by-expert routing for the grouped-GEMM path.

    Returns ``(src [M] int32, w [M] f32, offsets [E+1] int32,
    inv [B·S, k] int32, aux)``:
    row ``r`` of the sorted layout reads flat token ``src[r]`` and
    contributes with combine weight ``w[r]`` (0 on alignment-padding
    rows); rows ``[offsets[e], offsets[e+1])`` belong to expert ``e``.
    Every group start is 128-aligned (``pallas_grouped_matmul.ALIGN``)
    — groups are padded up, never truncated, so *no assignment is ever
    dropped*: there is no capacity concept at all, which is the whole
    point vs ``route_tokens``/``route_tables`` (capacity_factor > 1
    buys zero drops there by computing cf× extra rows; here the only
    overhead is the ≤127-row pad per expert). M is static:
    ``round_up(B·S·k + E·128, 512)``. Pad tokens (``token_mask``
    False) are sorted past every real group with weight 0 — they
    consume neither expert capacity (there is none) nor aux-loss mass.
    The tail region beyond the last real group is computed with expert
    E-1's weights and discarded via w=0 (the kernel's offsets[E] is
    pinned to M so every row is written — 0·finite, never 0·garbage).
    """
    from odh_kubeflow_tpu.ops.pallas_grouped_matmul import (
        ALIGN,
        DEFAULT_BM_B,
    )

    B, S, E = router_logits.shape
    k = cfg.num_experts_per_tok
    Na = B * S * k
    M = -(-(Na + E * ALIGN) // DEFAULT_BM_B) * DEFAULT_BM_B
    top_p, top_idx, aux_loss = _routing_topk(router_logits, cfg, token_mask)

    mask_flat = (
        None if token_mask is None else token_mask.reshape(B * S)
    )
    tok_ids = jnp.arange(B * S, dtype=jnp.int32)

    # Counting sort, not comparison sort: an XLA sort of B·S·k keys is
    # ~log²(N) latency-bound passes per layer (and again in the remat
    # recompute); the one-hot cumsum below is one vectorized pass —
    # the same trick route_tables uses, with a global (not per-row)
    # running fill because there is no per-row capacity here.
    counts = jnp.zeros((E,), jnp.int32)
    ranks = []  # per slot: position of each token within its expert
    experts = []
    for slot in range(k):
        e_sel = top_idx[..., slot].reshape(B * S)  # [B*S]
        onehot = jax.nn.one_hot(e_sel, E, dtype=jnp.int32)
        if mask_flat is not None:
            onehot = onehot * mask_flat.astype(jnp.int32)[:, None]
        pos = jnp.cumsum(onehot, axis=0) - onehot + counts[None, :]
        ranks.append(jnp.take_along_axis(pos, e_sel[:, None], 1)[:, 0])
        experts.append(e_sel)
        counts = counts + onehot.sum(axis=0)

    aligned = -(-counts // ALIGN) * ALIGN
    astarts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(aligned)]
    ).astype(jnp.int32)
    offsets = jnp.concatenate(
        [astarts[:E], jnp.full((1,), M, jnp.int32)]
    ).astype(jnp.int32)

    src = jnp.zeros((M,), jnp.int32)
    w = jnp.zeros((M,), jnp.float32)
    sent_fill = astarts[E]  # pad tokens go past every aligned group
    dsts = []  # per slot: each token's row in the sorted layout
    for slot in range(k):
        e_sel, rank = experts[slot], ranks[slot]
        w_sel = top_p[..., slot].reshape(B * S)
        if mask_flat is None:
            dst = astarts[e_sel] + rank
        else:
            # masked tokens: rank past the sentinel fill pointer
            n_masked = jnp.cumsum(~mask_flat) - (~mask_flat)
            dst = jnp.where(
                mask_flat,
                astarts[e_sel] + rank,
                sent_fill + n_masked,
            )
            sent_fill = sent_fill + (~mask_flat).sum()
            w_sel = jnp.where(mask_flat, w_sel, 0.0)
        src = src.at[dst].set(tok_ids)
        w = w.at[dst].set(w_sel)
        dsts.append(dst)
    # inverse table [B·S, k]: token t's k rows in the sorted layout —
    # what lets dispatch/combine run scatter-free (_gather_sorted /
    # _combine_sorted)
    inv = jnp.stack(dsts, axis=1)
    return src, w, offsets, inv, aux_loss


@jax.custom_vjp
def _gather_sorted(x2d, src, inv):
    """``x2d[src]`` with a scatter-free transpose.

    A plain gather's AD backward is a scatter-add, which XLA lowers
    row-serially on TPU (~24 ms/step at the 8×1B shape). Dropless
    routing means every flat token appears EXACTLY once per slot in
    the sorted layout, so the transpose is itself a gather via the
    inverse table: dx[t] = Σ_j dxs[inv[t, j]]. Alignment-pad and
    masked-sentinel rows carry zero cotangents (their whole backward
    chain is scaled by their combine weight w = 0), so skipping them
    is exact."""
    return jnp.take(x2d, src, axis=0)


def _gather_sorted_fwd(x2d, src, inv):
    return jnp.take(x2d, src, axis=0), (src, inv)


def _gather_sorted_bwd(res, dxs):
    _, inv = res
    dx = jnp.take(dxs, inv[:, 0], axis=0)
    for j in range(1, inv.shape[1]):
        dx = dx + jnp.take(dxs, inv[:, j], axis=0)
    return dx, None, None


_gather_sorted.defvjp(_gather_sorted_fwd, _gather_sorted_bwd)


@jax.custom_vjp
def _combine_sorted(contrib, src, inv):
    """Weighted combine as a k-row gather per token instead of a
    [M, D] scatter-add into token order (same argument as
    ``_gather_sorted``, in the other direction: the forward gathers by
    ``inv``, the backward by ``src``). The backward fills
    alignment-pad rows with ``dout[0]`` garbage instead of zero — dead
    by construction: dy pad rows are zeroed by w = 0, and w's own
    gradient is read back only at real dst rows (w is assembled by
    ``.at[dst].set``, whose transpose gathers at dst)."""
    out = jnp.take(contrib, inv[:, 0], axis=0)
    for j in range(1, inv.shape[1]):
        out = out + jnp.take(contrib, inv[:, j], axis=0)
    return out


def _combine_sorted_fwd(contrib, src, inv):
    return _combine_sorted(contrib, src, inv), (src,)


def _combine_sorted_bwd(res, dout):
    (src,) = res
    return jnp.take(dout, src, axis=0), None, None


_combine_sorted.defvjp(_combine_sorted_fwd, _combine_sorted_bwd)


def _default_unpack(bank):
    if isinstance(bank, dict) and "q" in bank:
        return bank["q"], bank["scale"]
    return bank, None


def _grouped_expert_ffn(
    xs: jnp.ndarray,  # [M, D] expert-sorted rows
    gate_bank,
    up_bank,
    down_bank,
    offsets: jnp.ndarray,
    span_base: Optional[jnp.ndarray],
    dtype,
    unpack=_default_unpack,
):
    """The three grouped expert projections, shared by the single-chip
    (:func:`_moe_mlp_grouped`) and expert-sharded
    (:func:`_moe_mlp_grouped_ep`) paths so kernel-selection details
    cannot drift between them. ``unpack`` maps a bank leaf to
    ``(weights, scale-or-None)`` — the identity for per-layer /
    [L·E]-stacked banks, the local [L, E/ep]→[L·E/ep] reshape for EP.

    int8 banks with K inside the fused VMEM budget take the fused
    gate+up+silu·mul kernel: u never reaches HBM and the standalone
    [M, F] silu/dsilu fusions disappear; g IS written (the op's vjp
    pins it as "moe_g") — both designs were measured and the pin beats
    recomputing g with an extra backward dot (0.91 vs 0.96 s/step at
    8×1B/4k), the custom backward fusing the u-recompute with the
    dsilu epilogue. Larger K (kernel B) and full-precision banks take
    separate gmms. Returns the down projection, pinned as "moe_y"."""
    from odh_kubeflow_tpu.ops.pallas_grouped_matmul import gmm, swiglu_gmm

    def bank_gmm(lhs, bank):
        q, sc = unpack(bank)
        if sc is None:
            if span_base is not None:
                # stacked mode is int8-only (forward's all-dict
                # guard); a stacked full-precision bank here would
                # silently read layer 0
                raise NotImplementedError(
                    "stacked expert banks (bank_base) require int8 "
                    "{'q','scale'} leaves"
                )
            return gmm(lhs, q.astype(dtype), offsets)
        # positional args: custom_vjp functions reject kwargs;
        # span_base selects this layer's span of a stacked [L·E, ...]
        # bank (no per-layer 100+MB slice copies)
        return gmm(lhs, q, offsets, False, None, sc, span_base)

    gq, gs = unpack(gate_bank)
    uq, us = unpack(up_bank)
    h = None
    if gs is not None and us is not None:
        try:
            h, _g = swiglu_gmm(xs, gq, uq, gs, us, offsets, span_base)
            # the op pins g as "moe_g" on its OWN residual (see
            # _swiglu_vjp_fwd) — naming the returned copy here would
            # pin a second, never-consumed value
            h = h.astype(dtype)
        except NotImplementedError:
            # hidden size past the fused kernel's VMEM budget: the
            # separate-gmm path below handles any shape (kernel B)
            h = None
    if h is None:
        g = bank_gmm(xs, gate_bank)
        u = bank_gmm(xs, up_bank)
        g = llama._checkpoint_name(g, "moe_g")
        u = llama._checkpoint_name(u, "moe_u")
        h = (
            jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
        ).astype(dtype)
    return llama._checkpoint_name(bank_gmm(h, down_bank), "moe_y")


def _moe_mlp_grouped(
    x: jnp.ndarray,  # [B, S, D]
    layer: Params,
    cfg: MoeConfig,
    token_mask: Optional[jnp.ndarray] = None,
    bank_base: Optional[jnp.ndarray] = None,  # int32 [1]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sorted-token dropless dispatch through the pallas grouped GEMM
    (``ops/pallas_grouped_matmul.py``): gather tokens into
    expert-sorted order, run the three expert projections as grouped
    matmuls that compute every assignment exactly once (no capacity
    padding — the einsum/ragged paths at cf=1.25 spend 25% of their
    expert FLOPs on empty capacity slots, which is why their
    strict-sparse MFU is capped at 0.8·dense), and weighted
    scatter-add back to token order."""
    dtype = x.dtype
    B, S, D = x.shape
    src, w, offsets, inv, aux = route_sorted(
        _router_logits(x, layer), cfg, token_mask
    )
    # named so the remat policies can pin them (~300KB/layer): the
    # backward then re-runs gather→gmm→silu but never the routing
    # chain (softmax, top-k, cumsum ranking)
    src = llama._checkpoint_name(src, "moe_route_src")
    w = llama._checkpoint_name(w, "moe_route_w")
    offsets = llama._checkpoint_name(offsets, "moe_route_offs")
    inv = llama._checkpoint_name(inv, "moe_route_inv")
    x_sorted = _gather_sorted(x.reshape(B * S, D), src, inv)
    y = _grouped_expert_ffn(
        x_sorted,
        layer["moe_gate"],
        layer["moe_up"],
        layer["moe_down"],
        offsets,
        bank_base,
        dtype,
    )
    contrib = y * w[:, None].astype(dtype)
    out = _combine_sorted(contrib, src, inv).reshape(B, S, D)
    out = constrain(out, llama._activation_spec())
    return out, aux


# ---------------------------------------------------------------------------
# expert-parallel grouped path: shard_map over (data, fsdp, expert)


def _auto_axes() -> tuple[Any, set]:
    """Active abstract mesh + the set of axis names still under GSPMD
    (Auto) — Manual axes (inside an enclosing ``shard_map``, e.g. the
    pipeline combinator's ``pipe``) are excluded: a nested shard_map may
    only manualize Auto axes."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return am, set()
    return am, {
        n
        for n, t in zip(am.axis_names, am.axis_types)
        if t == jax.sharding.AxisType.Auto
    }


def _grouped_ep_usable(x: jnp.ndarray, cfg: MoeConfig) -> bool:
    """True when the grouped kernels should run expert-sharded: a
    nontrivial batch mesh over (data, fsdp, expert) with NO tensor/
    context sharding (the kernels need full D/F/S per device), expert
    count divisible over the expert axis, batch divisible over the
    batch axes, and enough tokens per (data, fsdp) group that the
    128-row alignment padding is noise."""
    am, auto = _auto_axes()
    if am.empty or not auto:
        return False
    for ax in (AXIS_TENSOR, AXIS_CONTEXT):
        if ax in auto and am.shape.get(ax, 1) > 1:
            return False
    sizes = {
        a: am.shape.get(a, 1)
        for a in (AXIS_DATA, AXIS_FSDP, AXIS_EXPERT)
        if a in auto
    }
    if not sizes or all(v == 1 for v in sizes.values()):
        return False
    ep = sizes.get(AXIS_EXPERT, 1)
    if cfg.num_experts % ep:
        return False
    B, S, _ = x.shape
    nbatch = 1
    for v in sizes.values():
        nbatch *= v
    if B % nbatch:
        return False
    dp = nbatch // ep
    return (B * S // dp) * cfg.num_experts_per_tok >= 2048


def _grouped_mesh_blocker(x: jnp.ndarray, cfg: MoeConfig) -> Optional[str]:
    """Why a LARGE-batch grouped dispatch cannot run on the active
    mesh — ``None`` when the mesh is trivial or the per-group batch is
    tiny (the by-design exact ragged decode fallback). Everything else
    must be an explicit error in :func:`moe_mlp`, never a silent drop
    to the capacity path."""
    am, auto = _auto_axes()
    if am.empty or not auto:
        return None
    sizes = {
        a: am.shape.get(a, 1)
        for a in (AXIS_DATA, AXIS_FSDP, AXIS_EXPERT)
        if a in auto
    }
    dp = 1
    for v in sizes.values():
        dp *= v
    group = 1
    for a in (AXIS_DATA, AXIS_FSDP):
        group *= sizes.get(a, 1)
    B, S, _ = x.shape
    # per-(data, fsdp)-GROUP assignment count — the SAME divisor
    # _grouped_ep_usable applies (the gathered group is what the EP
    # path would actually process), so every batch the EP path would
    # accept but for a real blocker reaches the explicit error below
    if (B * S // max(group, 1)) * cfg.num_experts_per_tok < 2048:
        return None
    for ax in (AXIS_TENSOR, AXIS_CONTEXT):
        if ax in auto and am.shape.get(ax, 1) > 1:
            return (
                "tensor/context-sharded meshes are unsupported (the "
                "grouped kernels run on full hidden/expert extents "
                "per device); keep tensor=context=1 and shard over "
                "data/fsdp/expert"
            )
    ep = sizes.get(AXIS_EXPERT, 1)
    if cfg.num_experts % ep:
        return (
            f"num_experts={cfg.num_experts} is not divisible by the "
            f"expert axis extent {ep}"
        )
    if B % dp:
        return (
            f"batch {B} is not divisible by the data×fsdp×expert "
            f"extent {dp}"
        )
    return "unsupported mesh for the grouped kernels"


def route_sorted_ep(
    logits: jnp.ndarray,  # [N, E] f32 — one (data, fsdp) group's tokens
    cfg: MoeConfig,
    first_expert,  # scalar int32: first LOCAL expert's global id
    n_local: int,
    m_loc: int,
    token_mask: jnp.ndarray,  # [N] bool
) -> tuple[jnp.ndarray, ...]:
    """Local-expert dropless routing for the expert-sharded grouped
    path. Same counting-sort as :func:`route_sorted`, restricted to the
    ``n_local`` experts this shard owns and packed into an ``m_loc``-row
    buffer.

    Returns ``(src [M], w_row [M], w_tok [N,k], keep [N,k], offsets
    [n_local+1], inv [N,k], (f_sum [E], p_sum [E], mask_sum))`` — the
    last triple are this group's balance-statistic SUMS, which the
    caller psums over (data, fsdp) before forming the Switch aux so it
    matches the global-batch aux exactly. Unlike ``route_sorted``
    there is no
    sentinel region: non-local / masked / over-budget assignments are
    simply dropped from the buffer (their scatter index goes out of
    bounds, ``mode="drop"``) and their combine weight ``w_tok`` is 0 —
    the combine is weight-at-gather (:func:`_combine_weighted`), so a
    dropped assignment's ``inv`` entry can point at row 0 harmlessly.
    ``offsets[n_local]`` is pinned to ``m_loc`` so the kernels write
    every row (tail rows compute with the last local expert's weights
    and carry ``w_row = 0`` — finite, never uninitialised).

    With the worst-case ``m_loc`` (``ep_capacity_factor=None``) every
    unmasked local assignment fits and the path is exactly dropless;
    with a budget, assignments whose row lands past ``m_loc`` drop —
    bounded by the budget, mirroring the ragged path's capacity-drop
    semantics at the device (not per-expert) granularity."""
    N, E = logits.shape
    k = cfg.num_experts_per_tok
    top_p, top_idx, f, p = _routing_stats(
        logits[None], cfg, token_mask[None]
    )
    top_p, top_idx = top_p[0], top_idx[0]
    # return balance SUMS, not means: the caller psums them over the
    # (data, fsdp) axes and divides once, so the aux matches the
    # global-batch statistics exactly even when groups carry different
    # mask counts (means-of-means would not)
    ms = token_mask.astype(jnp.float32).sum()
    denom = jnp.maximum(ms, 1.0)
    stats = (f * denom, p * denom, ms)

    counts = jnp.zeros((n_local,), jnp.int32)
    ranks, lsels, localss = [], [], []
    for slot in range(k):
        e_sel = top_idx[:, slot]  # [N] global expert id
        local = (
            (e_sel >= first_expert)
            & (e_sel < first_expert + n_local)
            & token_mask
        )
        l_sel = jnp.clip(e_sel - first_expert, 0, n_local - 1)
        onehot = jax.nn.one_hot(l_sel, n_local, dtype=jnp.int32) * local[
            :, None
        ].astype(jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - onehot + counts[None, :]
        ranks.append(jnp.take_along_axis(pos, l_sel[:, None], 1)[:, 0])
        lsels.append(l_sel)
        localss.append(local)
        counts = counts + onehot.sum(axis=0)

    from odh_kubeflow_tpu.ops.pallas_grouped_matmul import ALIGN

    aligned = -(-counts // ALIGN) * ALIGN
    astarts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(aligned)]
    ).astype(jnp.int32)
    offsets = jnp.minimum(astarts, m_loc).at[-1].set(m_loc)

    src = jnp.zeros((m_loc,), jnp.int32)
    w_row = jnp.zeros((m_loc,), jnp.float32)
    tok_ids = jnp.arange(N, dtype=jnp.int32)
    invs, wtoks, keeps = [], [], []
    for slot in range(k):
        dst_raw = astarts[lsels[slot]] + ranks[slot]
        kept = localss[slot] & (dst_raw < m_loc)
        dst = jnp.where(kept, dst_raw, m_loc)  # OOB rows drop
        src = src.at[dst].set(tok_ids, mode="drop")
        w_row = w_row.at[dst].set(top_p[:, slot], mode="drop")
        invs.append(jnp.where(kept, dst_raw, 0))
        wtoks.append(jnp.where(kept, top_p[:, slot], 0.0))
        keeps.append(kept)
    inv = jnp.stack(invs, axis=1)
    w_tok = jnp.stack(wtoks, axis=1)
    keep = jnp.stack(keeps, axis=1)
    # w_row duplicates w_tok's information per-row for the combine's
    # backward formula only — the differentiable path is w_tok
    return (
        src, jax.lax.stop_gradient(w_row), w_tok, keep, offsets, inv,
        stats,
    )


@jax.custom_vjp
def _gather_sorted_ep(x2d, src, inv, keep):
    """``x2d[src]`` with the scatter-free inverse-table transpose, EP
    variant: ``keep`` masks inverse entries whose assignment was
    dropped (they point at row 0 and must not pull its cotangent)."""
    return jnp.take(x2d, src, axis=0)


def _gather_sorted_ep_fwd(x2d, src, inv, keep):
    return jnp.take(x2d, src, axis=0), (inv, keep)


def _gather_sorted_ep_bwd(res, dxs):
    inv, keep = res
    dx = jnp.where(
        keep[:, 0, None], jnp.take(dxs, inv[:, 0], axis=0), 0
    )
    for j in range(1, inv.shape[1]):
        dx = dx + jnp.where(
            keep[:, j, None], jnp.take(dxs, inv[:, j], axis=0), 0
        )
    return dx, None, None, None


_gather_sorted_ep.defvjp(_gather_sorted_ep_fwd, _gather_sorted_ep_bwd)


@jax.custom_vjp
def _combine_weighted(y, w_tok, src, w_row, inv):
    """Weight-at-combine: ``out[t] = Σ_j w_tok[t,j] · y[inv[t,j]]``.

    Unlike :func:`_combine_sorted` the weight multiplies at the gather,
    not baked into the rows — so dropped assignments (``w_tok = 0``,
    ``inv = 0``) contribute exactly zero without needing a guaranteed
    zero-weight row to point at. Backward: ``dy[r] = w_row[r] ·
    dout[src[r]]`` (each buffer row has at most one kept assignment;
    pad/tail rows have ``w_row = 0``), ``dw_tok[t,j] = dout[t] ·
    y[inv[t,j]]`` — both gathers, no scatter anywhere."""
    out = w_tok[:, 0, None].astype(y.dtype) * jnp.take(
        y, inv[:, 0], axis=0
    )
    for j in range(1, inv.shape[1]):
        out = out + w_tok[:, j, None].astype(y.dtype) * jnp.take(
            y, inv[:, j], axis=0
        )
    return out


def _combine_weighted_fwd(y, w_tok, src, w_row, inv):
    return _combine_weighted(y, w_tok, src, w_row, inv), (
        y, w_tok, src, w_row, inv,
    )


def _combine_weighted_bwd(res, dout):
    y, w_tok, src, w_row, inv = res
    dy = jnp.take(dout, src, axis=0) * w_row[:, None].astype(dout.dtype)
    dw = jnp.stack(
        [
            jnp.sum(
                dout.astype(jnp.float32)
                * jnp.take(y, inv[:, j], axis=0).astype(jnp.float32),
                axis=-1,
            )
            for j in range(inv.shape[1])
        ],
        axis=1,
    )
    return dy.astype(y.dtype), dw, None, jnp.zeros_like(w_row), None


_combine_weighted.defvjp(_combine_weighted_fwd, _combine_weighted_bwd)


def _moe_mlp_grouped_ep(
    x: jnp.ndarray,  # [B, S, D]
    layer: Params,
    cfg: MoeConfig,
    token_mask: Optional[jnp.ndarray] = None,
    bank_base: Optional[jnp.ndarray] = None,  # int32 [1]: LAYER index
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Grouped-GEMM MoE under a sharded mesh, ``shard_map``-manual over
    the batch axes (data, fsdp, expert).

    The TPU-native dispatch is gather-based expert parallelism (no
    ragged all-to-all — XLA wants static shapes): within each
    (data, fsdp) group, every expert-shard all-gathers the group's
    tokens + router logits over the ``expert`` axis (ICI), sorts the
    assignments that land on ITS local experts into a local grouped
    buffer (:func:`route_sorted_ep`), runs the same pallas grouped
    GEMMs / fused SwiGLU the single-chip path uses — on local banks
    with local ``group_offsets`` — and a ``psum_scatter`` over
    ``expert`` combines the weighted contributions back to the sharded
    token layout (the transpose of the all-gather, so the backward's
    collectives are the mirror pair). Expert banks shard over
    ``expert`` ONLY (``param_specs`` grouped branch): the kernels need
    full [K, N] blocks per device.

    Differences from the single-chip path, by necessity of static
    shapes under sharding: the local buffer is ``m_loc`` rows
    (worst-case exact by default, budgeted via
    ``cfg.ep_capacity_factor``), and the combine multiplies weights at
    gather time (``_combine_weighted``) so dropped assignments need no
    sentinel rows. ``bank_base`` here is the LAYER index (the local
    stacked bank is [L·E/ep, ...], so the span base is
    ``layer · E/ep`` — computed inside, where the shard size is
    known)."""
    from odh_kubeflow_tpu.ops.pallas_grouped_matmul import (
        ALIGN,
        DEFAULT_BM_B,
    )

    dtype = x.dtype
    B, S, D = x.shape
    E = cfg.num_experts
    k = cfg.num_experts_per_tok
    am, auto = _auto_axes()
    batch_axes = tuple(
        a for a in (AXIS_DATA, AXIS_FSDP, AXIS_EXPERT) if a in auto
    )
    ep = am.shape.get(AXIS_EXPERT, 1) if AXIS_EXPERT in auto else 1
    E_loc = E // ep
    stacked = bank_base is not None

    router_logits = _router_logits(x, layer)
    mask = (
        token_mask
        if token_mask is not None
        else jnp.ones((B, S), jnp.bool_)
    )
    banks = {
        nm: layer[nm] for nm in ("moe_gate", "moe_up", "moe_down")
    }
    base = bank_base if stacked else jnp.zeros((1,), jnp.int32)

    bspec = P(batch_axes, None, None)
    mspec = P(batch_axes, None)
    e_ax = AXIS_EXPERT if AXIS_EXPERT in auto else None

    def bank_spec(leaf):
        # per-layer banks are [E, ...] (expert axis 0); EP-stacked int8
        # banks stay [L, E, ...] (axis 1) — the local reshape to
        # [L·E_loc, ...] happens inside the shard, where it is a free
        # contiguous merge (a GLOBAL [L·E] reshape of an expert-sharded
        # array would force an all-gather)
        parts = [None] * leaf.ndim
        parts[1 if leaf.ndim == 4 else 0] = e_ax
        return P(*parts)

    bank_specs = jax.tree.map(bank_spec, banks)

    # XLA's CPU backend aborts ("Invalid binary instruction opcode
    # copy") promoting bf16 all-reduces under a partial-manual
    # shard_map (same bug parallel/pipeline.py documents). On CPU
    # (tests / dryrun) transit the expert-axis collectives in f32 —
    # bit-exact, since the carried values are already bf16-rounded;
    # real TPU backends keep native bf16 collectives.
    transit_f32 = (
        dtype == jnp.bfloat16 and jax.default_backend() == "cpu"
    )

    def body(x_loc, logits_loc, mask_loc, banks_loc, base_loc):
        Bl = x_loc.shape[0]

        def ag(v):
            if ep == 1:
                return v
            if transit_f32 and v.dtype == dtype:
                return jax.lax.all_gather(
                    v.astype(jnp.float32), AXIS_EXPERT, axis=0,
                    tiled=True,
                ).astype(dtype)
            return jax.lax.all_gather(
                v, AXIS_EXPERT, axis=0, tiled=True
            )

        xg = ag(x_loc.reshape(Bl * S, D))
        lg = ag(logits_loc.reshape(Bl * S, E))
        mg = ag(mask_loc.reshape(Bl * S))
        Ng = xg.shape[0]
        first = (
            jax.lax.axis_index(AXIS_EXPERT) * E_loc
            if ep > 1
            else jnp.int32(0)
        )
        Na = Ng * k
        if cfg.ep_capacity_factor is None:
            budget = Na
        else:
            budget = min(
                Na, int(-(-Na * cfg.ep_capacity_factor // ep))
            )
        m_loc = -(-(budget + E_loc * ALIGN) // DEFAULT_BM_B) * DEFAULT_BM_B
        src, w_row, w_tok, keep, offsets, inv, stats = route_sorted_ep(
            lg, cfg, first, E_loc, m_loc, mg
        )
        src = llama._checkpoint_name(src, "moe_route_src")
        w_row = llama._checkpoint_name(w_row, "moe_route_w")
        offsets = llama._checkpoint_name(offsets, "moe_route_offs")
        inv = llama._checkpoint_name(inv, "moe_route_inv")
        w_tok = llama._checkpoint_name(w_tok, "moe_route_wtok")
        keep = llama._checkpoint_name(keep, "moe_route_keep")
        xs = _gather_sorted_ep(xg, src, inv, keep)

        def local_unpack(bank):
            q, sc = _default_unpack(bank)
            if sc is not None and stacked:
                q = q.reshape((-1,) + q.shape[2:])
                sc = sc.reshape((-1,) + sc.shape[2:])
            return q, sc

        span_base = base_loc * E_loc if stacked else None
        y = _grouped_expert_ffn(
            xs,
            banks_loc["moe_gate"],
            banks_loc["moe_up"],
            banks_loc["moe_down"],
            offsets,
            span_base,
            dtype,
            unpack=local_unpack,
        )
        out_g = _combine_weighted(y, w_tok, src, w_row, inv)
        # aux from GLOBAL balance statistics: psum the per-group f/p
        # SUMS over the (data, fsdp) axes (every shard of an expert
        # group already computed identical sums from the same gathered
        # logits — summing over expert would multiply by ep) and divide
        # once, reproducing the unsharded aux exactly
        fs, ps, ms = stats
        dp_axes = tuple(
            a for a in (AXIS_DATA, AXIS_FSDP) if a in batch_axes
        )
        if dp_axes:
            fs = jax.lax.psum(fs, dp_axes)
            ps = jax.lax.psum(ps, dp_axes)
            ms = jax.lax.psum(ms, dp_axes)
        denom = jnp.maximum(ms, 1.0)
        aux = (
            E
            * jnp.sum((fs / denom) * (ps / denom))
            * cfg.router_aux_loss_coef
        )
        if ep > 1:
            out_c = (
                out_g.astype(jnp.float32) if transit_f32 else out_g
            )
            out_loc = jax.lax.psum_scatter(
                out_c, AXIS_EXPERT, scatter_dimension=0, tiled=True
            ).astype(dtype)
        else:
            out_loc = out_g
        return out_loc.reshape(Bl, S, D), aux

    out, aux = jax.shard_map(
        body,
        mesh=am,
        in_specs=(bspec, bspec, mspec, bank_specs, P(None)),
        out_specs=(bspec, P()),
        # manual over EVERY Auto axis, not just the batch axes the
        # specs name: Mosaic refuses to lower a kernel while any mesh
        # axis is left to GSPMD, trivial ones included (tensor/context
        # are size 1 here — _grouped_ep_usable — so they only replicate)
        axis_names=frozenset(auto),
        check_vma=False,
    )(x, router_logits, mask, banks, base)
    out = constrain(out, llama._activation_spec())
    return out, aux


# ---------------------------------------------------------------------------
# decoder layer + forward (mirrors llama.forward's API)


def _moe_decoder_layer(
    cfg: MoeConfig, attention_fn, x, layer, lora_layer, sin, cos,
    segment_ids, bank_base=None,
):
    """LoRA adapters attach to the attention projections only (the
    standard MoE-LoRA recipe — expert banks stay frozen); int8 leaves
    (``models/quant.py``) dequantize here inside the remat boundary,
    mirroring the dense family's QLoRA memory story."""
    b = cfg.base
    B, S, D = x.shape
    x = constrain(x, llama._activation_spec())
    if cfg.dispatch == "grouped":
        # int8 expert banks stay quantized: the grouped kernels read
        # them natively (half the weight bytes per pass, no dequantized
        # [E,D,F] bank ever materialised in HBM)
        banks = {
            k: layer[k]
            for k in ("moe_gate", "moe_up", "moe_down")
            # int8 only: the grouped kernels read {"q","scale"} banks
            # natively; int4 ({"q4","scale4"}) banks dequantize below
            # like any other leaf
            if isinstance(layer[k], dict) and "q" in layer[k]
        }
        rest = {k: v for k, v in layer.items() if k not in banks}
        layer = {**llama._maybe_dequant(rest, b.dtype), **banks}
    else:
        layer = llama._maybe_dequant(layer, b.dtype)

    h = rms_norm(x, layer["attn_norm"], b.rms_norm_eps)
    q = llama._maybe_lora("wq", h, layer["wq"], lora_layer).reshape(
        B, S, b.num_heads, b.head_dim
    )
    k = llama._maybe_lora("wk", h, layer["wk"], lora_layer).reshape(
        B, S, b.num_kv_heads, b.head_dim
    )
    v = llama._maybe_lora("wv", h, layer["wv"], lora_layer).reshape(
        B, S, b.num_kv_heads, b.head_dim
    )
    q = llama.apply_rope(q, sin, cos)
    k = llama.apply_rope(k, sin, cos)
    # named for the "attn_mlp" policy (same contract as the dense
    # family): pinning the roped q/k/v removes the qkv projection +
    # rope from the backward's recompute
    q = llama._checkpoint_name(q, "q_rope")
    k = llama._checkpoint_name(k, "k_rope")
    v = llama._checkpoint_name(v, "v_proj")
    attn = attention_fn(q, k, v, segment_ids=segment_ids).reshape(B, S, b.q_dim)
    attn = llama._checkpoint_name(attn, "attn_out")
    x = x + llama._maybe_lora("wo", attn, layer["wo"], lora_layer)

    h = rms_norm(x, layer["mlp_norm"], b.rms_norm_eps)
    # packed batches mark padding with segment id 0 (train/data.py):
    # those tokens must not consume router capacity or skew the aux
    moe_out, aux = moe_mlp(
        h, layer, cfg,
        token_mask=None if segment_ids is None else segment_ids > 0,
        bank_base=bank_base,
    )
    # named so the remat policy can pin the combined expert output:
    # the backward needs gate/up for silu' but never the down einsum's
    # value, so saving this skips down + combine in the recompute
    moe_out = llama._checkpoint_name(moe_out, "moe_out")
    return x + moe_out, aux


def forward_with_cache(
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32
    cfg: MoeConfig,
    cache: Params,  # {"k","v"}: [L, B, S_max, Hkv * hd]
    cache_index,  # scalar int32, or [B] int32: write offset
    *,
    positions: jnp.ndarray,  # [B, S]
    kv_mask: Optional[jnp.ndarray] = None,
    lora: Optional[Params] = None,
    token_mask: Optional[jnp.ndarray] = None,  # [B, S] bool; False = pad
) -> tuple[jnp.ndarray, Params]:
    """KV-cached MoE forward (the ``models/generate.py`` decode path).

    Attention is the dense family's cache path (``llama.
    cache_write_and_attend`` inside ``llama.scan_layers_with_cache``:
    the stacked cache is the layer scan's carry); the MLP is
    the router+experts. Routing a 1-token decode step degenerates to
    capacity-1 per expert, which top-k's distinct choices always fit.
    int8-quantized trees (``models/quant.py``) dequantize per layer
    like the dense path. ``lora`` carries attention-projection
    adapters (the MoE-LoRA targets), so a LoRA-tuned MoE decodes
    without merging.
    """
    b = cfg.base
    sin, cos = rope_angles(positions, b.head_dim, b.rope_theta)
    x = jnp.take(params["embed"], tokens, axis=0).astype(b.dtype)
    B, S, D = x.shape
    lora_layers = lora["layers"] if lora is not None else None
    # Router token-validity: pads must not consume expert capacity
    # (they would evict real tokens' slots and make padded vs unpadded
    # execution of the SAME prompt disagree). Callers that know the
    # window pass ``token_mask`` explicitly; the fallback inference
    # covers the prefill layout (S>1, cache_index 0 — input positions
    # map 1:1 onto cache slots, so kv_mask's prompt region IS the
    # validity mask). Decode steps (S=1) always carry a real token.
    if token_mask is None:
        token_mask = (
            kv_mask[:, :S] if (kv_mask is not None and S > 1) else None
        )

    def layer_fn(x, layer, lora_layer, cache, layer_index):
        layer = llama._maybe_dequant(layer, b.dtype)
        h = rms_norm(x, layer["attn_norm"], b.rms_norm_eps)
        q = llama._maybe_lora("wq", h, layer["wq"], lora_layer).reshape(
            B, S, b.num_heads, b.head_dim
        )
        k = llama._maybe_lora("wk", h, layer["wk"], lora_layer).reshape(
            B, S, b.num_kv_heads, b.head_dim
        )
        v = llama._maybe_lora("wv", h, layer["wv"], lora_layer).reshape(
            B, S, b.num_kv_heads, b.head_dim
        )
        q = llama.apply_rope(q, sin, cos)
        k = llama.apply_rope(k, sin, cos)
        attn, cache = llama.cache_write_and_attend(
            q, k, v, cache, layer_index, cache_index, kv_mask
        )
        attn = attn.reshape(B, S, b.q_dim)
        x = x + llama._maybe_lora("wo", attn, layer["wo"], lora_layer)
        h = rms_norm(x, layer["mlp_norm"], b.rms_norm_eps)
        moe_out, _aux = moe_mlp(h, layer, cfg, token_mask=token_mask)
        return x + moe_out, cache

    x, new_cache = llama.scan_layers_with_cache(
        layer_fn, x, params["layers"], lora_layers, cache
    )
    x = rms_norm(x, params["final_norm"], b.rms_norm_eps)
    head = llama.lm_head_weight(params, b)  # dequantizes int8 lm_head
    logits = jnp.einsum(
        "bsd,dv->bsv", x, head.astype(b.dtype),
        preferred_element_type=jnp.float32,
    )
    return logits, new_cache


def forward(
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32
    cfg: MoeConfig,
    lora: Optional[Params] = None,
    positions: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    return_hidden: bool = False,
    pipeline_microbatches: int = 8,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (logits [B,S,V] f32 — or hidden [B,S,D] with
    ``return_hidden`` — , total_aux_loss).

    When the active mesh shards the ``pipe`` axis, the layer stack runs
    through the GPipe combinator like the dense family, with the router
    aux loss riding the pipeline's scalar output channel. Router
    statistics are then per-microbatch (aux averaged over microbatches)
    — the standard MoE×PP semantics; numerically close to, but not
    bit-equal with, full-batch routing statistics."""
    b = cfg.base
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    sin, cos = rope_angles(positions, b.head_dim, b.rope_theta)

    x = jnp.take(params["embed"], tokens, axis=0).astype(b.dtype)
    b = dataclasses.replace(
        b, attention_impl=llama.resolved_attention_impl(b)
    )
    attention_fn = llama._select_attention(b)
    def make_layer_fn(pin_acts: bool, policy: Optional[str] = None,
                      gather_from=None, stacked_banks=None,
                      stacked_base=None):
        """``gather_from`` = (stacked_layers, stacked_lora): returned
        fn takes a layer index and gathers INSIDE the rematted region
        (outside, each gathered layer slice becomes a saved residual —
        a full extra copy of the expert banks across the scan).
        ``stacked_banks``: [L·E, ...] (single-chip) or [L, E, ...]
        (expert-parallel) int8 bank dict kept OUT of the gathered tree
        — the grouped kernels fetch via ``stacked_base(i)`` instead of
        the gather slicing a 100+MB bank copy per layer."""
        raw_fn = partial(_moe_decoder_layer, cfg, attention_fn)
        if gather_from is None:
            layer_fn = raw_fn
        else:
            stacked_layers, stacked_lora = gather_from
            if stacked_banks is not None:
                stacked_layers = {
                    k: v for k, v in stacked_layers.items()
                    if k not in stacked_banks
                }

            def layer_fn(x, i, _unused, sin, cos, segment_ids):
                lyr = jax.tree.map(lambda a: a[i], stacked_layers)
                lora_l = (
                    None
                    if stacked_lora is None
                    else jax.tree.map(lambda a: a[i], stacked_lora)
                )
                if stacked_banks is not None:
                    return raw_fn(
                        x, {**lyr, **stacked_banks}, lora_l, sin, cos,
                        segment_ids, stacked_base(i),
                    )
                return raw_fn(x, lyr, lora_l, sin, cos, segment_ids)

        if not b.remat:
            return layer_fn
        policy = policy or b.remat_policy
        # same policy vocabulary as the dense family
        # (llama._make_layer_fn), with the MoE extra that "attn" and
        # "dots" also pin the combined expert output: the backward
        # needs gate/up for silu' but never the down einsum's value,
        # so saving "moe_out" drops down + combine + attention from
        # the recompute.
        names = [
            "moe_out", "moe_y", "moe_route_src", "moe_route_w",
            "moe_route_offs", "moe_route_inv",
        ] + (
            # "moe_g" alone: with frozen (QLoRA) banks the backward
            # needs g and u only for silu' — pinning g leaves one
            # recomputed unit (u) at half the residency of pinning
            # both, which is what fits beside the int8 base at 4k
            ["moe_g"] if pin_acts else []
        ) + (
            ["flash_out", "flash_lse"]
            if b.attention_impl == "flash"
            else ["attn_out"]
        )
        if policy == "attn_mlp":
            # dense-family "attn_mlp" analogue: also pin the roped
            # q/k/v (the flash backward's inputs), removing the qkv
            # projection + rope from the recompute; the MoE MLP's
            # equivalent is pin_expert_acts ("moe_g")
            names += ["q_rope", "k_rope", "v_proj"]
        named = jax.checkpoint_policies.save_only_these_names(*names)
        if policy == "none":
            return jax.checkpoint(layer_fn)
        if policy in ("attn", "attn_mlp"):
            return jax.checkpoint(layer_fn, policy=named)
        if policy == "attn_offload":
            # same vocabulary as the dense family (llama._make_layer_fn)
            return jax.checkpoint(
                layer_fn,
                policy=jax.checkpoint_policies
                .save_and_offload_only_these_names(
                    names_which_can_be_saved=[],
                    names_which_can_be_offloaded=names,
                    offload_src="device",
                    offload_dst="pinned_host",
                ),
            )
        if policy == "dots":
            # dense-family semantics (save every matmul output) plus
            # the named kernel residuals. NOTE: at MoE scale the expert
            # einsum outputs are large — mixtral_8x1b's factory
            # defaults its base to "attn" for exactly that reason.
            return jax.checkpoint(
                layer_fn,
                policy=jax.checkpoint_policies.save_from_both_policies(
                    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                    named,
                ),
            )
        raise ValueError(
            f"unknown remat_policy {policy!r}; expected 'dots', "
            "'attn', 'attn_mlp', 'attn_offload', or 'none'"
        )

    layer_fn = make_layer_fn(cfg.pin_expert_acts)
    lora_layers = lora["layers"] if lora is not None else None

    am = jax.sharding.get_abstract_mesh()
    pipe = 0 if am.empty else am.shape.get(AXIS_PIPE, 1)
    if pipe > 1:
        x, aux_total = _apply_layers_pipelined(
            cfg,
            layer_fn,
            params["layers"],
            lora_layers,
            x,
            positions,
            segment_ids,
            pipeline_microbatches,
        )
    else:

        def body_with(fn):
            def body(carry, scanned):
                x, aux = carry
                layer, lora_layer = scanned
                x, layer_aux = fn(
                    x, layer, lora_layer, sin, cos, segment_ids
                )
                return (x, aux + layer_aux), None

            return body

        carry = (x, jnp.zeros((), jnp.float32))
        layers_xs = params["layers"]
        bank_names = ("moe_gate", "moe_up", "moe_down")
        # Stacked-bank mode: the int8 expert banks (the bulk of the
        # params — 400+MB/layer at 8×1B) stay OUT of the scanned /
        # gathered trees; the layer body closes over the full
        # [L·E, ...] reshape and the grouped kernels fetch this
        # layer's span via bank_base. A scanned bank leaf would be
        # dynamic-sliced into a fresh contiguous copy every layer
        # (fwd + backward recompute) just to feed the custom call —
        # ~39 ms/step measured at 8×1B/4k.
        all_int8 = all(
            isinstance(layers_xs[nm], dict) and "q" in layers_xs[nm]
            for nm in bank_names
        )
        ep_stacked = (
            cfg.dispatch == "grouped"
            and all_int8
            and not _grouped_usable(x, cfg)
            and _grouped_ep_usable(x, cfg)
        )
        stacked = (
            cfg.dispatch == "grouped"
            and all_int8
            and (_grouped_usable(x, cfg) or ep_stacked)
        )
        banks = None
        if stacked and ep_stacked:
            # EP mode: keep the [L, E, ...] leaves 4-D — the shard_map
            # in-spec shards E and the LOCAL [L·E/ep] reshape happens
            # inside the shard (a global [L·E] reshape of an expert-
            # sharded array would all-gather); bank_base is the layer
            # index, scaled by the local expert count inside
            banks = {nm: layers_xs[nm] for nm in bank_names}
        elif stacked:
            banks = {
                nm: {
                    "q": layers_xs[nm]["q"].reshape(
                        (-1,) + layers_xs[nm]["q"].shape[2:]
                    ),
                    "scale": layers_xs[nm]["scale"].reshape(
                        (-1,) + layers_xs[nm]["scale"].shape[2:]
                    ),
                }
                for nm in bank_names
            }
        pin = b.remat_pin_layers
        if (
            b.remat
            and b.remat_policy != "none"
            and pin is not None
            and 0 < pin < b.num_layers
        ):
            # Memory-budgeted suffix pinning (llama semantics): the
            # LAST ``remat_pin_layers`` layers keep the configured
            # policy (incl. "moe_g" under pin_expert_acts — freed
            # earliest in the backward sweep); the prefix drops to the
            # cheap tier (no "moe_g", or full recompute when
            # pin_expert_acts is off). Two scans because per-layer
            # policies can't vary inside one; the scans iterate over
            # layer indices and gather in-body so the stacked params
            # are never sliced into prefix/suffix copies.
            n_first = b.num_layers - pin
            gf = (params["layers"], lora_layers)
            base_of = (
                (lambda i: i[None])
                if ep_stacked
                else (lambda i: (i * cfg.num_experts)[None])
            )
            prefix_fn = (
                make_layer_fn(False, gather_from=gf, stacked_banks=banks,
                              stacked_base=base_of)
                if cfg.pin_expert_acts
                else make_layer_fn(
                    False, policy="none", gather_from=gf,
                    stacked_banks=banks, stacked_base=base_of,
                )
            )
            suffix_fn = make_layer_fn(
                cfg.pin_expert_acts, gather_from=gf, stacked_banks=banks,
                stacked_base=base_of,
            )

            def body_gather(fn):
                def body(carry, i):
                    x, aux = carry
                    x, layer_aux = fn(x, i, None, sin, cos, segment_ids)
                    return (x, aux + layer_aux), None

                return body

            carry, _ = jax.lax.scan(
                body_gather(prefix_fn),
                carry,
                jnp.arange(n_first, dtype=jnp.int32),
            )
            carry, _ = jax.lax.scan(
                body_gather(suffix_fn),
                carry,
                jnp.arange(n_first, b.num_layers, dtype=jnp.int32),
            )
        elif stacked:
            rest = {
                k: v for k, v in layers_xs.items() if k not in banks
            }
            E = cfg.num_experts

            def body_stacked(carry, scanned):
                x, aux = carry
                i, rest_layer, lora_layer = scanned
                layer = {**rest_layer, **banks}
                x, layer_aux = layer_fn(
                    x, layer, lora_layer, sin, cos, segment_ids,
                    i[None] if ep_stacked else (i * E)[None],
                )
                return (x, aux + layer_aux), None

            carry, _ = jax.lax.scan(
                body_stacked,
                carry,
                (
                    jnp.arange(b.num_layers, dtype=jnp.int32),
                    rest,
                    lora_layers,
                ),
            )
        else:
            carry, _ = jax.lax.scan(
                body_with(layer_fn),
                carry,
                (params["layers"], lora_layers),
            )
        x, aux_total = carry

    x = rms_norm(x, params["final_norm"], b.rms_norm_eps)
    if return_hidden:
        return x, aux_total
    head = llama.lm_head_weight(params, b)
    logits = jnp.einsum(
        "bsd,dv->bsv", x, head.astype(b.dtype), preferred_element_type=jnp.float32
    )
    return logits, aux_total


def _apply_layers_pipelined(
    cfg: MoeConfig,
    layer_fn,
    layers: Params,
    lora_layers: Optional[Params],
    x: jnp.ndarray,  # [B, S, D]
    positions: jnp.ndarray,  # [B, S]
    segment_ids: Optional[jnp.ndarray],
    num_microbatches: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """MoE decoder stack over the pipe axis: the shared combinator
    wrapper (``llama._apply_layers_pipelined``) with the router aux
    loss accumulated through the pipeline's scalar output channel."""
    return llama._apply_layers_pipelined(
        cfg.base,
        layer_fn,
        layers,
        lora_layers,
        x,
        positions,
        segment_ids,
        num_microbatches,
        accumulate_aux=True,
    )


# ---------------------------------------------------------------------------
# an expert-parallel share's expert layer: told which experts it holds


def route_softmax_topk(
    router_logits: jnp.ndarray,  # [..., E] float32
    k: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """A softmax over ALL the experts, the ``k`` largest probabilities,
    divided by their sum (``norm_topk_prob``): the training path's
    selection (``_routing_stats``) and a served share's alike. Returns
    ``(weights [..., k] float32, ids [..., k], probs [..., E])``."""
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return top_p, top_idx, probs


def route_sigmoid_topk(
    router_logits: jnp.ndarray,  # [..., E] float32
    k: int,
    normalise: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The other published selection beside ``route_softmax_topk``:
    each expert's score is the SIGMOID of its own logit, the ``k``
    largest are chosen and (``norm_topk_prob``) their scores divided by
    their sum. Returns ``(weights [..., k] float32, ids [..., k])``."""
    top_s, top_idx = jax.lax.top_k(jax.nn.sigmoid(router_logits), k)
    if normalise:
        top_s = top_s / jnp.maximum(top_s.sum(-1, keepdims=True), 1e-20)
    return top_s, top_idx


def local_dispatch(top_idx, token_mask, experts_held, block_m: int):
    """The sorted layout ``ops/pallas_moe_local.py`` walks, for the
    assignments that fall on the experts held here.

    ``top_idx`` [T, k] are ids among ALL the layer's experts;
    ``experts_held = (first, count)``. Rows are the local assignments
    sorted by expert, each expert's group padded to ``block_m``; nothing
    is dropped: there is room for every token choosing held experts
    only. Returns ``token_of_row`` [M], ``row_of`` [T, k] (M where the
    assignment is not local), ``local`` [T, k] bool, ``tile_expert``
    [M // block_m], ``n_live`` [1], and ``sizes`` [count]."""
    first, count = experts_held
    T, k = top_idx.shape
    A = T * k
    M = -(-T * min(k, count) // block_m) * block_m + count * block_m
    le = top_idx - first
    local = (le >= 0) & (le < count)
    if token_mask is not None:
        local = local & token_mask.reshape(T, 1)
    flat = jnp.where(local, le, count).reshape(A)  # count: not here
    sizes_all = jnp.sum(
        jax.nn.one_hot(flat, count + 1, dtype=jnp.int32), axis=0
    )
    sizes = sizes_all[:count]
    padded = -(-sizes // block_m) * block_m
    ends = jnp.cumsum(padded)
    starts = ends - padded
    order = jnp.argsort(flat, stable=True)
    sorted_le = flat[order]
    group_first = jnp.cumsum(sizes_all) - sizes_all  # in sorted order
    rank = jnp.arange(A, dtype=jnp.int32) - group_first[sorted_le]
    row_sorted = jnp.where(
        sorted_le < count, starts[jnp.minimum(sorted_le, count - 1)] + rank, M
    ).astype(jnp.int32)
    token_of_row = (
        jnp.zeros((M + 1,), jnp.int32).at[row_sorted].set(order // k)[:M]
    )
    row_of = jnp.zeros((A,), jnp.int32).at[order].set(row_sorted).reshape(T, k)
    n_live = ends[-1] // block_m
    tiles = jnp.minimum(
        jnp.arange(M // block_m, dtype=jnp.int32), jnp.maximum(n_live - 1, 0)
    )
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, tiles * block_m, side="right"), count - 1
    ).astype(jnp.int32)
    return dict(
        token_of_row=token_of_row, row_of=row_of, local=local,
        tile_expert=tile_expert, n_live=n_live.reshape(1).astype(jnp.int32),
        sizes=sizes,
    )


def _bank_layer(bank, layer, dtype):
    """One layer of a stacked expert bank, dequantised: the plain read
    (off the TPU, or for banks that are not int8)."""
    if isinstance(bank, dict):
        take = lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, False)  # noqa: E731
        return (
            take(bank["q"]).astype(jnp.float32) * take(bank["scale"])
        ).astype(dtype)
    return jax.lax.dynamic_index_in_dim(bank, layer, 0, False).astype(dtype)


def reads_banks_in_place(banks: dict) -> bool:
    """Whether the held experts go through ``moe_local_ffn`` on the
    stacked int8 banks (one TPU chip) or through plain dots on a
    dequantised copy of the layer's banks."""
    am = jax.sharding.get_abstract_mesh()
    return (
        jax.default_backend() == "tpu"
        and (am.empty or am.size == 1)
        and all(isinstance(b, dict) and set(b) == {"q", "scale"}
                for b in banks.values())
    )


def local_expert_ffn(
    h: jnp.ndarray,  # [T, D]
    top_w: jnp.ndarray,  # [T, k] float32 combine weights
    top_idx: jnp.ndarray,  # [T, k] ids among ALL the layer's experts
    banks: dict,  # "moe_gate"/"moe_up" [L, E_held, D, F], "moe_down" [L, E_held, F, D]
    layer,  # scalar int32: the layer of the stacked banks
    experts_held: tuple,  # (first, count) among the layer's experts
    token_mask: Optional[jnp.ndarray] = None,  # [T] bool; False = not a token
    in_place: Optional[bool] = None,
    interpret: bool = False,
    num_experts: Optional[int] = None,  # the router's width, where it sizes the tile
):
    """The part of a mixture layer's output that the experts HELD HERE
    give: ``sum over a token's chosen experts e that are held of w_e *
    (silu(h G_e) * (h U_e)) D_e``. The router ran over all the layer's
    experts; what the experts held elsewhere would add is their chips'
    to compute and is not stood in for. Dropless. Returns ``(out [T, D]
    in h's dtype, stats int32 [4])``: local assignments, distinct held
    experts hit, assignments dropped (always 0: counted, not assumed),
    rows of the sorted layout that hold an expert's group (what the
    kernel computes: ``n_live`` tiles of ``block_m``).

    ``num_experts`` is the router's width: with it the row tile follows
    the rows an expert can expect of this call (``pallas_moe_local.
    block_m_for``: 512 experts give a part of 2048 tokens 40 rows each,
    not 128); without it the tile follows the call's tokens alone.
    """
    from odh_kubeflow_tpu.ops import pallas_moe_local as pml

    T, D = h.shape
    first, count = experts_held
    if in_place is None:
        in_place = reads_banks_in_place(banks)
    block_m = pml.block_m_for(
        T, None if num_experts is None else T * top_idx.shape[1] / num_experts
    )
    d = local_dispatch(top_idx, token_mask, experts_held, block_m)
    M = d["token_of_row"].shape[0]
    w = jnp.where(d["local"], top_w, 0.0)
    stats = jnp.stack([
        jnp.sum(d["local"]), jnp.sum(d["sizes"] > 0),
        jnp.sum(d["local"] & (d["row_of"] >= M)), d["n_live"][0] * block_m,
    ]).astype(jnp.int32)
    if in_place:
        with jax.named_scope("moe_local_ffn"):
            y = pml.moe_local_ffn(
                h[d["token_of_row"]], d["tile_expert"], d["n_live"], layer,
                banks["moe_gate"], banks["moe_up"], banks["moe_down"],
                block_m=block_m, interpret=interpret,
            )
        picked = y[jnp.minimum(d["row_of"], M - 1)]  # [T, k, D]
        # a row of a tile that was never computed holds anything
        picked = jnp.where(d["local"][..., None], picked, 0).astype(jnp.float32)
        out = jnp.einsum("tk,tkd->td", w, picked)
    else:
        gate, up, down = (
            _bank_layer(banks[n], layer, h.dtype)
            for n in ("moe_gate", "moe_up", "moe_down")
        )
        # [T, count] combine weights over the held experts
        combine = jnp.einsum(
            "tk,tke->te", w,
            jax.nn.one_hot(top_idx - first, count, dtype=jnp.float32),
        )
        act = jax.nn.silu(
            jnp.einsum("td,edf->etf", h, gate, preferred_element_type=jnp.float32)
        ) * jnp.einsum("td,edf->etf", h, up, preferred_element_type=jnp.float32)
        y = jnp.einsum(
            "etf,efd->etd", act.astype(h.dtype), down,
            preferred_element_type=jnp.float32,
        )
        out = jnp.einsum("te,etd->td", combine, y)
    return out.astype(h.dtype), stats
