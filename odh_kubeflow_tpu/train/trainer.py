"""Sharded training loop for Llama models (full fine-tune or LoRA).

One jitted ``train_step`` compiled against a ``jax.sharding.Mesh``:
- the *trainable* tree (LoRA adapters, or the full params) carries
  optimizer state sharded like the params themselves;
- the frozen base params are closed over as sharded donated inputs;
- XLA derives every collective from the in/out shardings — there is no
  hand-written pmap/all-reduce anywhere.

This is the workload behind BASELINE.json's north-star metric (Llama-3-8B
LoRA on a v5p-8 notebook at >=50% MFU); ``benchmark/run.py`` times it
in the training cells of ``BENCHMARK.json``.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from odh_kubeflow_tpu.models import llama, lora as lora_lib
from odh_kubeflow_tpu.parallel.mesh import batch_spec, build_mesh, constrain
from odh_kubeflow_tpu.utils import prometheus
from odh_kubeflow_tpu.utils.compile_cache import install_process_cache
from odh_kubeflow_tpu.utils.profiling import hot_span

Params = dict[str, Any]

# step times span ms-scale tiny test models to minutes-long 8B steps
# (the first observation includes the cold compile — visible on
# purpose: compile stalls are the spawn-latency north star's enemy)
_STEP_TIME_BUCKETS = (
    0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 15.0, 60.0,
)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    weight_decay: float = 0.0
    warmup_steps: int = 10
    total_steps: int = 1000
    max_grad_norm: float = 1.0
    b1: float = 0.9
    b2: float = 0.95
    z_loss: float = 0.0
    # microbatches per GPipe schedule when the mesh shards `pipe`
    # (bubble = (S-1)/(M+S-1); must divide the batch)
    pipeline_microbatches: int = 8


def cross_entropy_loss(
    logits: jnp.ndarray,  # [B, S, V] float32
    targets: jnp.ndarray,  # [B, S] int32
    loss_mask: Optional[jnp.ndarray] = None,  # [B, S]
    z_loss: float = 0.0,
) -> jnp.ndarray:
    logz = jax.scipy.special.logsumexp(logits, axis=-1)  # [B, S]
    target_logit = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - target_logit
    if z_loss:
        nll = nll + z_loss * jnp.square(logz)
    if loss_mask is None:
        return jnp.mean(nll)
    loss_mask = loss_mask.astype(jnp.float32)
    return jnp.sum(nll * loss_mask) / jnp.maximum(jnp.sum(loss_mask), 1.0)


def chunked_cross_entropy(
    hidden: jnp.ndarray,  # [B, S, D] model dtype
    head: jnp.ndarray,  # [D, V]
    targets: jnp.ndarray,  # [B, S] int32
    loss_mask: Optional[jnp.ndarray] = None,  # [B, S]
    z_loss: float = 0.0,
    chunk: int = 1024,
) -> jnp.ndarray:
    """Cross entropy without ever materialising the full [B, S, V]
    logits tensor: the LM head + NLL run chunk-by-chunk over the
    sequence under ``lax.map`` with rematerialisation, so peak memory
    is [B, chunk, V] for both forward and backward. At S=16k, V=128k
    this is the difference between 8.4GB of logits (OOM on one v5e)
    and 0.5GB — the big-vocab long-context recipe.

    ``chunk`` must divide S (callers pad the sequence; training shapes
    here are powers of two).
    """
    B, S, D = hidden.shape
    if S % chunk:
        raise ValueError(f"chunk {chunk} must divide sequence length {S}")
    n = S // chunk
    if loss_mask is None:
        loss_mask = jnp.ones((B, S), dtype=jnp.float32)

    @jax.checkpoint  # backward recomputes this chunk's logits
    def one_chunk(i):
        # slice chunks out of the live activations instead of
        # pre-stacking a [n, B, c, D] scan input: the stack (and its
        # backward's unstack) is a full relayout of hidden at a
        # different tiling — two more ~45 ms passes the slice avoids
        h = jax.lax.dynamic_slice_in_dim(hidden, i * chunk, chunk, axis=1)
        t = jax.lax.dynamic_slice_in_dim(targets, i * chunk, chunk, axis=1)
        m = jax.lax.dynamic_slice_in_dim(loss_mask, i * chunk, chunk, axis=1)
        logits = jnp.einsum(
            "bcd,dv->bcv",
            h,
            head.astype(h.dtype),
            preferred_element_type=jnp.float32,
        )
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        # target logit via a head-column gather + rowwise dot, NOT
        # take_along_axis on the [B, c, V] logits — whose backward is
        # a scatter XLA lowers through a linear-layout relayout of the
        # whole 0.5GB f32 chunk (~90 ms/step at 16k); the gather's
        # backward is a gather. Gathering columns of [D, V] directly
        # (axis=1) avoids materialising a [V, D] transposed copy of
        # the head (1.05GB at 8B — an OOM at 16k).
        # cast the gathered columns to the activation dtype FIRST so
        # both the logsumexp path (head.astype(h.dtype) above) and the
        # target-logit path see identically rounded head values — a
        # higher-precision head here would bias nll = logz - target
        # and can push it slightly negative on confident tokens
        ht = jnp.take(head, t.reshape(-1), axis=1).astype(h.dtype)  # [D, B·c]
        ht = ht.T.reshape(h.shape).astype(jnp.float32)
        target_logit = jnp.sum(h.astype(jnp.float32) * ht, axis=-1)
        nll = logz - target_logit
        if z_loss:
            nll = nll + z_loss * jnp.square(logz)
        m = m.astype(jnp.float32)
        return jnp.sum(nll * m), jnp.sum(m)

    with jax.named_scope("chunked_cross_entropy"):
        nll_sum, mask_sum = jax.lax.map(one_chunk, jnp.arange(n))
        return jnp.sum(nll_sum) / jnp.maximum(jnp.sum(mask_sum), 1.0)


def _pipe_shard_layer_specs(spec_tree):
    """Prepend the pipe axis onto every per-layer stacked leaf spec
    (everything under a 'layers' subtree: leading dim is L)."""
    from odh_kubeflow_tpu.parallel.mesh import AXIS_PIPE

    def walk(tree, in_layers):
        if isinstance(tree, dict):
            return {
                k: walk(v, in_layers or k == "layers") for k, v in tree.items()
            }
        if not in_layers:
            return tree
        rest = list(tree)[1:] if len(tree) else []
        return P(AXIS_PIPE, *rest)

    return walk(spec_tree, False)


def _make_optimizer(cfg: TrainConfig) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=cfg.learning_rate,
        warmup_steps=cfg.warmup_steps,
        decay_steps=max(cfg.total_steps, cfg.warmup_steps + 1),
        end_value=cfg.learning_rate * 0.1,
    )
    return optax.chain(
        optax.clip_by_global_norm(cfg.max_grad_norm),
        optax.adamw(
            schedule, b1=cfg.b1, b2=cfg.b2, weight_decay=cfg.weight_decay
        ),
    )


class Trainer:
    """Owns mesh, sharded state, and the compiled train step.

    ``lora_cfg=None`` → full fine-tune (grads w.r.t. all params);
    otherwise base params are frozen and only adapters train.
    """

    def __init__(
        self,
        model_cfg,  # LlamaConfig (dense, LoRA-able) or MoeConfig
        train_cfg: TrainConfig = TrainConfig(),
        lora_cfg: Optional[lora_lib.LoraConfig] = None,
        mesh: Optional[Mesh] = None,
        seed: int = 0,
        quantize_base: "bool | str" = False,  # True/"int8" or "int4"
        precompile_batch: Optional[tuple] = None,  # (batch, seq[, keys])
        metrics_registry: Optional[prometheus.Registry] = None,
    ):
        from odh_kubeflow_tpu.models import moe as moe_lib

        # join jax's persistent compilation cache before any
        # trace/compile below: the directory JAX_COMPILATION_CACHE_DIR
        # names, else the fixed in-checkout one (warmup/ subsystem)
        install_process_cache()

        self.model_cfg = model_cfg
        self.is_moe = isinstance(model_cfg, moe_lib.MoeConfig)
        if self.is_moe and lora_cfg is not None:
            bad = set(lora_cfg.targets) - set(lora_lib.ATTENTION_TARGETS)
            if bad:
                raise ValueError(
                    f"MoE LoRA adapts attention projections only "
                    f"(expert banks replace the dense MLP); invalid "
                    f"targets: {sorted(bad)}"
                )
        if quantize_base and lora_cfg is None:
            raise ValueError(
                "quantize_base freezes the base weights as int8/int4 — "
                "it requires LoRA adapters to have anything to train"
            )
        if quantize_base not in (False, True, "int8", "int4"):
            raise ValueError(
                f"quantize_base must be False/True/'int8'/'int4', got "
                f"{quantize_base!r}"
            )
        self.quant_bits = (
            4 if quantize_base == "int4" else (8 if quantize_base else 0)
        )
        self.train_cfg = train_cfg
        self.lora_cfg = lora_cfg
        self.quantize_base = quantize_base
        self.mesh = mesh if mesh is not None else build_mesh()
        self.optimizer = _make_optimizer(train_cfg)
        self._m_step_time = (
            metrics_registry or prometheus.default_registry
        ).histogram(
            "train_step_time_seconds",
            "Wall-clock time per train_step call (first call includes "
            "compile)",
            buckets=_STEP_TIME_BUCKETS,
        )

        # "rbg" keys: jax.random.* on them lowers to XLA's builtin
        # RngBitGenerator instead of an inlined threefry graph — the
        # threefry init graph for a 1B-param tree takes XLA ~17s to
        # COMPILE (measured; zeros-init compiles in 0.7s), and init
        # compile was the bulk of the 25s cold trainer build the
        # spawn-latency north star pays. Same per-backend determinism;
        # split/fold_in still derive via threefry (cheap — they hash
        # keys, not param-sized tensors).
        key = jax.random.key(seed, impl="rbg")
        k_params, k_lora = jax.random.split(key)

        pipe = dict(zip(self.mesh.axis_names, self.mesh.devices.shape)).get(
            "pipe", 1
        )
        self.pipelined = pipe > 1
        if self.is_moe:
            p_specs = moe_lib.param_specs(model_cfg)
            init_partial = partial(
                moe_lib.init_params, cfg=model_cfg, dtype=model_cfg.base.dtype
            )
        else:
            p_specs = llama.param_specs(model_cfg)
            init_partial = partial(
                llama.init_params, cfg=model_cfg, dtype=model_cfg.dtype
            )
        if quantize_base:
            from odh_kubeflow_tpu.models import quant as quant_lib

            p_specs = quant_lib.quantized_param_specs(
                p_specs, bits=self.quant_bits
            )
        if self.pipelined:
            # stage ownership: every stacked per-layer leaf shards its
            # leading L dim over the pipe axis (device p holds its
            # stage's layers; parallel/pipeline.py runs the schedule)
            p_specs = _pipe_shard_layer_specs(p_specs)
        self._frozen_specs = p_specs

        # ---- everything ABSTRACT first (no device work): specs and
        # shape trees, so the train-step AOT compile can start on a
        # background thread BEFORE the inits run — the step compile
        # (~14s cold on 1B) then overlaps the init compiles instead of
        # adding to them (spawn→first-step north star).
        frozen_shapes = jax.eval_shape(init_partial, k_params)
        if quantize_base:
            frozen_shapes = jax.eval_shape(
                lambda t: quant_lib.quantize_params(t, bits=self.quant_bits),
                frozen_shapes,
            )
        lora_init_partial = None
        if lora_cfg is not None:
            # adapters mirror the *backbone* dims (for MoE that is
            # cfg.base — targets are the attention projections)
            lora_dims_cfg = model_cfg.base if self.is_moe else model_cfg
            l_specs = lora_lib.lora_specs(lora_dims_cfg, lora_cfg)
            if self.pipelined:
                l_specs = _pipe_shard_layer_specs(l_specs)
            lora_init_partial = partial(
                lora_lib.init_lora_params, cfg=lora_dims_cfg, lora=lora_cfg
            )
            self._train_specs = l_specs
            trainable_shapes = jax.eval_shape(lora_init_partial, k_lora)
        else:
            self._train_specs = p_specs
            trainable_shapes = frozen_shapes
        self._opt_specs = self._opt_state_specs(
            trainable_shapes, self._train_specs
        )
        self.step = 0
        # which executable the steps ran: the one compiled ahead of
        # time for the batch's shape (precompile_async), or the lazy
        # jit's (which compiles on its first call with a shape)
        self.aot_steps = 0
        self.lazy_steps = 0
        # what the segmented flash kernels walked, over all steps whose
        # results have arrived: [tiles run, tiles the causal geometry
        # alone would run] (ops/pallas_attention.live_block_counts)
        self._flash_blocks = [0, 0]
        self._flash_pending: collections.deque = collections.deque()
        self._compiled = self._build_step()
        self._aot: dict = {}
        self._aot_threads: dict = {}
        self._abstract_state = (trainable_shapes, frozen_shapes)
        if precompile_batch is not None:
            self.precompile_async(*precompile_batch)

        # ---- device work
        with jax.set_mesh(self.mesh):
            def init_rest(kl, params):
                """Adapters + optimizer state given the frozen/base
                params — shared by both init flavors (traced into the
                fused program below, or jitted standalone after the
                streaming quantized init)."""
                lora = (
                    lora_init_partial(kl)
                    if lora_cfg is not None
                    else None
                )
                trainable = lora if lora_cfg is not None else params
                return lora, self.optimizer.init(trainable)

            rest_shardings = (
                self._sh(self._train_specs)
                if lora_cfg is not None
                else None,
                self._sh(self._opt_specs),
            )
            if quantize_base:
                # leaf-streamed int8 init: never holds the bf16 tree
                # (8B bf16 alone would OOM the 16GiB v5e this targets)
                self.params = quant_lib.streaming_quantized_init(
                    model_cfg, k_params, mesh=self.mesh, specs=p_specs,
                    bits=self.quant_bits,
                )
                self.lora_params, self.opt_state = jax.jit(
                    init_rest, out_shardings=rest_shardings
                )(k_lora, self.params)
            else:
                # ONE jitted program for params + adapters + optimizer
                # state: separate jits pay separate traces and
                # (persistent-)cache lookups — host-side time the warm
                # spawn path cannot hide (the compiles themselves are
                # cached; the tracing is GIL-bound Python)
                def init_all(kp, kl):
                    params = init_partial(kp)
                    return (params, *init_rest(kl, params))

                init_fn = jax.jit(
                    init_all,
                    out_shardings=(self._sh(p_specs), *rest_shardings),
                )
                self.params, self.lora_params, self.opt_state = init_fn(
                    k_params, k_lora
                )

    # -- sharding helpers ---------------------------------------------------

    def _sh(self, spec_tree):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s),
            spec_tree,
            is_leaf=lambda s: isinstance(s, P),
        )

    def _opt_state_specs(self, trainable, train_specs):
        """Optimizer state shards like the param it mirrors; non-param
        state (step counts, schedule state) replicates."""
        shapes = jax.eval_shape(self.optimizer.init, trainable)
        return optax.tree_map_params(
            self.optimizer,
            lambda _leaf, spec: spec,
            shapes,
            train_specs,
            transform_non_params=lambda _leaf: P(),
        )

    # -- train step ---------------------------------------------------------

    def _loss_fn(self, trainable, frozen, batch):
        if self.lora_cfg is not None:
            params, lora_params = frozen, trainable
        else:
            params, lora_params = trainable, None
        if self.is_moe:
            return self._moe_loss_fn(params, lora_params, batch)
        seq_len = batch["tokens"].shape[1]
        if seq_len > 2048 and seq_len % 1024 == 0:
            # long context: never materialise [B, S, V] logits
            hidden = llama.forward(
                params,
                batch["tokens"],
                self.model_cfg,
                lora=lora_params,
                segment_ids=batch.get("segment_ids"),
                return_hidden=True,
                pipeline_microbatches=self.train_cfg.pipeline_microbatches,
            )
            return chunked_cross_entropy(
                hidden,
                llama.lm_head_weight(params, self.model_cfg),
                batch["targets"],
                batch.get("loss_mask"),
                z_loss=self.train_cfg.z_loss,
            )
        logits = llama.forward(
            params,
            batch["tokens"],
            self.model_cfg,
            lora=lora_params,
            segment_ids=batch.get("segment_ids"),
            pipeline_microbatches=self.train_cfg.pipeline_microbatches,
        )
        loss = cross_entropy_loss(
            logits,
            batch["targets"],
            batch.get("loss_mask"),
            z_loss=self.train_cfg.z_loss,
        )
        return loss

    def _moe_loss_fn(self, params, lora_params, batch):
        """MoE: router aux (load-balancing) loss rides on the LM loss;
        the long-context chunked path applies the same way. With LoRA,
        the (possibly int8) base params stay frozen and only the
        attention adapters train, exactly like the dense family."""
        from odh_kubeflow_tpu.models import moe as moe_lib

        cfg = self.model_cfg
        seq_len = batch["tokens"].shape[1]
        if seq_len > 2048 and seq_len % 1024 == 0:
            hidden, aux = moe_lib.forward(
                params,
                batch["tokens"],
                cfg,
                lora=lora_params,
                segment_ids=batch.get("segment_ids"),
                return_hidden=True,
                pipeline_microbatches=self.train_cfg.pipeline_microbatches,
            )
            return (
                chunked_cross_entropy(
                    hidden,
                    llama.lm_head_weight(params, cfg.base),
                    batch["targets"],
                    batch.get("loss_mask"),
                    z_loss=self.train_cfg.z_loss,
                )
                + aux
            )
        logits, aux = moe_lib.forward(
            params,
            batch["tokens"],
            cfg,
            lora=lora_params,
            segment_ids=batch.get("segment_ids"),
            pipeline_microbatches=self.train_cfg.pipeline_microbatches,
        )
        return (
            cross_entropy_loss(
                logits,
                batch["targets"],
                batch.get("loss_mask"),
                z_loss=self.train_cfg.z_loss,
            )
            + aux
        )

    def _build_step(self):
        def step_fn(trainable, frozen, opt_state, batch):
            loss, grads = jax.value_and_grad(self._loss_fn)(
                trainable, frozen, batch
            )
            with jax.named_scope("optimizer_update"):
                updates, opt_state = self.optimizer.update(
                    grads, opt_state, params=trainable
                )
                trainable = optax.apply_updates(trainable, updates)
            gnorm = optax.global_norm(grads)
            metrics = {"loss": loss, "grad_norm": gnorm}
            cfg = self.model_cfg.base if self.is_moe else self.model_cfg
            if (
                "segment_ids" in batch
                and llama.resolved_attention_impl(cfg) == "flash"
            ):
                # from the same function that builds the kernels' tables;
                # rides home beside the loss
                from odh_kubeflow_tpu.ops.pallas_attention import (
                    live_block_counts,
                )

                live, causal = live_block_counts(batch["segment_ids"])
                metrics["flash_blocks"] = jnp.stack([live, jnp.int32(causal)])
            return trainable, opt_state, metrics

        train_sh = self._sh(self._train_specs)
        # frozen tree shards as initialised (quantized or not); on the
        # full-fine-tune path frozen IS the trainable tree.
        frozen_specs = self._frozen_specs
        opt_sh = self._sh(self._opt_specs)
        return jax.jit(
            step_fn,
            in_shardings=(train_sh, self._sh(frozen_specs), opt_sh, None),
            # pin outputs too: without this GSPMD is free to pick a
            # different layout for step N's outputs than step N+1's
            # pinned inputs, which raises a sharding mismatch on call 2.
            out_shardings=(train_sh, opt_sh, None),
            donate_argnums=(0, 2),
        )

    def eval_step(self, batch: dict) -> dict:
        """Loss on a held-out batch: same sharded loss function, no
        gradient, no optimizer-state touch. Compiled once, cached."""
        if not hasattr(self, "_compiled_eval"):
            train_sh = self._sh(self._train_specs)
            frozen_sh = self._sh(self._frozen_specs)
            self._compiled_eval = jax.jit(
                lambda trainable, frozen, batch: self._loss_fn(
                    trainable, frozen, batch
                ),
                in_shardings=(train_sh, frozen_sh, None),
            )
        trainable = self.lora_params if self.lora_cfg is not None else self.params
        with jax.set_mesh(self.mesh):
            loss = self._compiled_eval(trainable, self.params, batch)
        return {"loss": loss}

    # -- async step precompile ---------------------------------------------

    def _batch_abstract(self, batch_size: int, seq_len: int, keys):
        from odh_kubeflow_tpu.parallel.mesh import batch_spec

        bsh = NamedSharding(self.mesh, batch_spec())
        dt = {"loss_mask": jnp.float32, "segment_ids": jnp.int32}
        return {
            k: jax.ShapeDtypeStruct(
                (batch_size, seq_len), dt.get(k, jnp.int32), sharding=bsh
            )
            for k in keys
        }

    def precompile_async(
        self,
        batch_size: int,
        seq_len: int,
        keys: tuple = ("tokens", "targets", "loss_mask"),
    ) -> None:
        """Start compiling the train step for this batch shape on a
        background thread, from ABSTRACT shapes — no params needed, so
        the (expensive, ~14s cold at 1B) step compile runs concurrently
        with the trainer's own init work instead of serially on the
        first ``train_step``. A notebook's first cell (or
        ``Trainer(precompile_batch=(B, S))``) calls this right after
        construction; ``train_step`` joins the thread and uses the
        ahead-of-time executable."""
        import threading

        akey = (batch_size, seq_len, tuple(sorted(keys)))
        if akey in self._aot or akey in self._aot_threads:
            return
        trainable_shapes, frozen_shapes = self._abstract_state

        def annotate(shapes, specs):
            return jax.tree_util.tree_map(
                lambda sh, sp: jax.ShapeDtypeStruct(
                    sh.shape, sh.dtype, sharding=NamedSharding(self.mesh, sp)
                ),
                shapes,
                specs,
            )

        a_train = annotate(trainable_shapes, self._train_specs)
        a_frozen = annotate(frozen_shapes, self._frozen_specs)
        a_opt = annotate(
            jax.eval_shape(self.optimizer.init, trainable_shapes),
            self._opt_specs,
        )
        a_batch = self._batch_abstract(batch_size, seq_len, keys)

        def work():
            try:
                with jax.set_mesh(self.mesh):
                    self._aot[akey] = self._compiled.lower(
                        a_train, a_frozen, a_opt, a_batch
                    ).compile()
            except Exception as e:  # noqa: BLE001 — raised where joined
                self._aot[akey] = e

        th = threading.Thread(target=work, daemon=True)
        self._aot_threads[akey] = th
        th.start()

    def compiled_step(self, batch_size: int, seq_len: int, keys: tuple):
        """The ahead-of-time executable (``jax.stages.Compiled``) for
        this batch shape, joined if it is still compiling; None where
        ``precompile_async`` was never asked for it. Raises what its
        compile raised."""
        akey = (batch_size, seq_len, tuple(sorted(keys)))
        th = self._aot_threads.pop(akey, None)
        if th is not None:
            with hot_span("trainer.aot_wait"):
                th.join()
        exe = self._aot.get(akey)
        if isinstance(exe, Exception):
            # a failed ahead-of-time compile is an error of the step
            # that asked for it — compiling again lazily would pay the
            # compile twice and hide why the first one failed
            raise exe
        return exe

    def _fold_flash_blocks(self) -> list:
        """Add the counts of the steps that have ended to the totals.
        Never waits: a step in flight is folded in by a later call."""
        pending = self._flash_pending
        while pending and pending[0].is_ready():
            for i, n in enumerate(np.asarray(pending.popleft())):
                self._flash_blocks[i] += int(n)
        return self._flash_blocks

    @property
    def flash_blocks_live(self) -> int:
        """Tiles the flash kernels ran, a head and a layer, over the
        steps on packed rows that have ended."""
        return self._fold_flash_blocks()[0]

    @property
    def flash_blocks_walked(self) -> int:
        """Tiles the causal walk alone would have run for them."""
        return self._fold_flash_blocks()[1]

    def train_step(self, batch: dict) -> dict:
        """One optimizer step, recorded as ``trainer.step`` (host time
        inside this call) over ``trainer.aot_wait`` (the join on the
        compile thread, first step of a shape only), ``trainer.h2d``
        (the batch's ``device_put``) and ``trainer.dispatch`` (the
        executable's call; the lazy jit compiles inside it). On packed
        rows under flash the span carries ``flash_live_share``:
        ``flash_blocks_live / flash_blocks_walked`` so far."""
        t_start = time.perf_counter()
        trainable = self.lora_params if self.lora_cfg is not None else self.params
        frozen = self.params
        akey = (*batch["tokens"].shape, tuple(sorted(batch)))
        aot = akey in self._aot or akey in self._aot_threads
        attrs = {}
        live, walked = self._fold_flash_blocks()
        if walked:
            attrs["flash_live_share"] = round(live / walked, 4)
        with hot_span(
            "trainer.step", step=self.step,
            executable="aot" if aot else "lazy", **attrs,
        ), jax.set_mesh(self.mesh):
            if aot:
                exe = self.compiled_step(*akey)
                with hot_span("trainer.h2d"):
                    bsh = NamedSharding(self.mesh, batch_spec())
                    batch = {
                        k: jax.device_put(v, bsh) for k, v in batch.items()
                    }
                with hot_span("trainer.dispatch"):
                    trainable, self.opt_state, metrics = exe(
                        trainable, frozen, self.opt_state, batch
                    )
                self.aot_steps += 1
            else:
                with hot_span("trainer.dispatch"):
                    trainable, self.opt_state, metrics = self._compiled(
                        trainable, frozen, self.opt_state, batch
                    )
                self.lazy_steps += 1
        if "flash_blocks" in metrics:
            self._flash_pending.append(metrics["flash_blocks"])
        if self.lora_cfg is not None:
            self.lora_params = trainable
        else:
            self.params = trainable
        self.step += 1
        # dispatch time as the host loop sees it (async dispatch: the
        # device may still be running; steady-state the loop is
        # device-bound and this converges on true step time)
        self._m_step_time.observe(time.perf_counter() - t_start)
        return metrics

    # -- checkpoint / resume ------------------------------------------------
    #
    # The trainable tree + optimizer state + step round-trip through
    # `train.checkpoint.CheckpointManager` (orbax). Base params are NOT
    # saved on the LoRA path — they are frozen and reproducible from the
    # pretrained weights, so adapter checkpoints stay megabytes.

    def _checkpoint_state(self) -> dict:
        trainable = self.lora_params if self.lora_cfg is not None else self.params
        return {"trainable": trainable, "opt_state": self.opt_state}

    def save_checkpoint(self, manager, *, force: bool = False) -> bool:
        """``manager`` is a ``train.checkpoint.CheckpointManager`` (kept
        by the caller so its GC/interval policy spans the whole run);
        ``force=True`` bypasses its save_interval_steps policy."""
        return manager.save(self.step, self._checkpoint_state(), force=force)

    def restore_checkpoint(self, manager, step: Optional[int] = None) -> int:
        """Restores trainable + optimizer state *into this trainer's
        mesh* — the checkpoint may have been written on a different
        topology; orbax reshards each array onto the target shardings.
        Returns the restored step."""
        from odh_kubeflow_tpu.train.checkpoint import _abstract_like

        target = {
            "trainable": _abstract_like(
                self._checkpoint_state()["trainable"], self.mesh, self._train_specs
            ),
            "opt_state": _abstract_like(
                self.opt_state, self.mesh, self._opt_specs
            ),
        }
        step = manager.latest_step() if step is None else step
        state = manager.restore(target, step=step)
        if self.lora_cfg is not None:
            self.lora_params = state["trainable"]
        else:
            self.params = state["trainable"]
        self.opt_state = state["opt_state"]
        self.step = int(step)
        return self.step

    # -- convenience --------------------------------------------------------

    def make_fake_batch(self, batch_size: int, seq_len: int, seed: int = 0) -> dict:
        key = jax.random.key(seed)
        tokens = jax.random.randint(
            key, (batch_size, seq_len), 0, self.model_cfg.vocab_size, jnp.int32
        )
        targets = jnp.roll(tokens, -1, axis=1)
        sharding = NamedSharding(self.mesh, batch_spec())
        return {
            "tokens": jax.device_put(tokens, sharding),
            "targets": jax.device_put(targets, sharding),
        }
