import jax
import jax.numpy as jnp
import numpy as np

from odh_kubeflow_tpu.models import LlamaConfig, LoraConfig
from odh_kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
from odh_kubeflow_tpu.train import TrainConfig, Trainer


def _loss_decreases(trainer, steps=8, batch_size=8):
    batch = trainer.make_fake_batch(batch_size, 32)
    losses = [float(trainer.train_step(batch)["loss"]) for _ in range(steps)]
    assert losses[-1] < losses[0], losses
    return losses


def test_lora_training_single_device():
    """Also the AOT contract: with ``precompile_batch`` the step that
    runs IS the ahead-of-time executable (the lazy jit compiles
    nothing), and a failed ahead-of-time compile is raised where it is
    joined — not stored and compiled again lazily."""
    import pytest

    keys = ("targets", "tokens")
    trainer = Trainer(
        LlamaConfig.tiny(dtype=jnp.float32),
        TrainConfig(learning_rate=1e-2, warmup_steps=1, total_steps=20),
        lora_cfg=LoraConfig(rank=4),
        mesh=build_mesh(MeshConfig(), jax.devices()[:1]),
        precompile_batch=(8, 32, keys),
    )
    _loss_decreases(trainer)
    assert isinstance(trainer._aot[(8, 32, keys)], jax.stages.Compiled)
    assert trainer._compiled._cache_size() == 0

    class Refuses:
        def lower(self, *a, **k):
            raise RuntimeError("mosaic refused the kernel")

    lazy, trainer._compiled = trainer._compiled, Refuses()
    trainer.precompile_async(8, 64, keys)
    trainer._aot_threads[(8, 64, keys)].join(timeout=60)
    trainer._compiled = lazy
    with pytest.raises(RuntimeError, match="mosaic refused"):
        trainer.train_step(trainer.make_fake_batch(8, 64))
    assert lazy._cache_size() == 0


def test_full_finetune_sharded_fsdp_tp(devices8):
    mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2), devices8)
    trainer = Trainer(
        LlamaConfig.tiny(dtype=jnp.float32),
        TrainConfig(learning_rate=1e-2, warmup_steps=1, total_steps=20),
        mesh=mesh,
    )
    _loss_decreases(trainer)


def test_lora_sharded_matches_single_device(devices8):
    """Same seed, same data: a sharded LoRA step must produce the same
    loss trajectory as single-device (SPMD is semantics-preserving).
    The sharded side runs FLASH attention, which GSPMD cannot partition
    (on the TPU Mosaic refuses to lower it outside a fully-manual
    shard_map): ``llama._flash_per_shard`` maps it over the mesh — rows
    over fsdp, heads over tensor — forward and backward."""
    import dataclasses

    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    tc = TrainConfig(learning_rate=1e-2, warmup_steps=1, total_steps=20)
    t1 = Trainer(cfg, tc, LoraConfig(rank=4), build_mesh(MeshConfig(), jax.devices()[:1]))
    t8 = Trainer(
        dataclasses.replace(cfg, attention_impl="flash"), tc,
        LoraConfig(rank=4), build_mesh(MeshConfig(fsdp=4, tensor=2), devices8),
    )
    l1 = _loss_decreases(t1)
    l8 = _loss_decreases(t8)
    np.testing.assert_allclose(l1, l8, rtol=2e-3)


def test_lora_keeps_base_frozen():
    trainer = Trainer(
        LlamaConfig.tiny(dtype=jnp.float32),
        TrainConfig(learning_rate=1e-2, warmup_steps=1, total_steps=20),
        lora_cfg=LoraConfig(rank=4),
        mesh=build_mesh(MeshConfig(), jax.devices()[:1]),
    )
    before = jax.tree_util.tree_map(lambda x: np.asarray(x).copy(), trainer.params)
    batch = trainer.make_fake_batch(2, 16)
    for _ in range(3):
        trainer.train_step(batch)
    after = trainer.params
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        before,
        after,
    )


def test_chunked_cross_entropy_matches_dense():
    """chunked_cross_entropy (the long-context loss that never
    materialises [B,S,V] logits) must agree with the dense loss to
    float32 tolerance, masked and unmasked."""
    from odh_kubeflow_tpu.train.trainer import (
        chunked_cross_entropy,
        cross_entropy_loss,
    )

    key = jax.random.PRNGKey(7)
    B, S, D, V = 2, 8, 16, 32
    hidden = jax.random.normal(key, (B, S, D), jnp.float32)
    head = jax.random.normal(jax.random.PRNGKey(8), (D, V), jnp.float32)
    targets = jax.random.randint(jax.random.PRNGKey(9), (B, S), 0, V)
    mask = (jnp.arange(S)[None, :] < jnp.array([[6], [3]])).astype(jnp.float32)

    logits = jnp.einsum("bsd,dv->bsv", hidden, head)
    for m in (None, mask):
        dense = cross_entropy_loss(logits, targets, m, z_loss=1e-4)
        chunked = chunked_cross_entropy(
            hidden, head, targets, m, z_loss=1e-4, chunk=4
        )
        np.testing.assert_allclose(
            np.asarray(dense), np.asarray(chunked), rtol=1e-5
        )

    # gradients flow identically through the chunked path
    g_dense = jax.grad(
        lambda h: cross_entropy_loss(
            jnp.einsum("bsd,dv->bsv", h, head), targets, mask
        )
    )(hidden)
    g_chunked = jax.grad(
        lambda h: chunked_cross_entropy(h, head, targets, mask, chunk=4)
    )(hidden)
    np.testing.assert_allclose(
        np.asarray(g_dense), np.asarray(g_chunked), rtol=1e-4, atol=1e-6
    )


def test_long_seq_loss_path_runs_end_to_end(devices8):
    """A >2048 sequence selects the chunked loss inside the jitted
    train step and still trains (loss finite, step completes) on the
    virtual mesh."""
    trainer = Trainer(
        LlamaConfig.tiny(dtype=jnp.float32),
        TrainConfig(warmup_steps=1, total_steps=4),
        lora_cfg=LoraConfig(rank=2),
        mesh=build_mesh(MeshConfig(data=2, fsdp=2, tensor=2), devices8),
    )
    B, S = 2, 3072  # > 2048 and 1024-divisible → chunked path
    tokens = jnp.zeros((B, S), jnp.int32)
    batch = {
        "tokens": tokens,
        "targets": jnp.ones((B, S), jnp.int32),
        "loss_mask": jnp.ones((B, S), jnp.float32),
    }
    metrics = trainer.train_step(batch)
    assert np.isfinite(float(metrics["loss"]))


def test_moe_trainer_end_to_end(devices8):
    """The Trainer drives the MoE family too: full-parameter training
    with the expert axis >1, loss (LM + aux) decreases, checkpoint
    round-trips through the same path as dense."""
    from odh_kubeflow_tpu.models import MoeConfig

    trainer = Trainer(
        MoeConfig.mixtral_tiny(),
        TrainConfig(warmup_steps=1, total_steps=8, learning_rate=1e-2),
        mesh=build_mesh(MeshConfig(fsdp=2, expert=2, tensor=2), devices8),
    )
    batch = trainer.make_fake_batch(4, 16)
    losses = [float(trainer.train_step(batch)["loss"]) for _ in range(6)]
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]

    # expert banks actually shard over the expert axis
    assert "expert" in str(trainer.params["layers"]["moe_gate"].sharding.spec)

    # LoRA on MoE adapts attention projections (tests/test_moe.py has
    # the full train/decode coverage); MLP targets are rejected there.
    lora_trainer = Trainer(
        MoeConfig.mixtral_tiny(),
        TrainConfig(),
        lora_cfg=LoraConfig(rank=2),
        mesh=build_mesh(MeshConfig(fsdp=8), devices8),
    )
    from odh_kubeflow_tpu.models.lora import ATTENTION_TARGETS

    assert set(lora_trainer.lora_params["layers"]) == set(ATTENTION_TARGETS)


def test_pipelined_trainer_matches_unpipelined(devices8):
    """pipe=2 through the Trainer: layer specs shard over the pipe
    axis, forward routes through the GPipe combinator, and the first
    step's loss equals the pipe=1 run bit-for-bit-ish (same init key,
    same batch; fp32 tolerance)."""
    losses = {}
    for name, mesh_cfg in {
        "flat": MeshConfig(fsdp=8),
        "piped": MeshConfig(pipe=2, fsdp=4),
    }.items():
        trainer = Trainer(
            LlamaConfig.tiny(dtype=jnp.float32),
            TrainConfig(warmup_steps=1, total_steps=4, pipeline_microbatches=4),
            lora_cfg=LoraConfig(rank=2),
            mesh=build_mesh(mesh_cfg, devices8),
        )
        if name == "piped":
            # layer leaves really live on the pipe axis
            assert "pipe" in str(
                trainer.params["layers"]["wq"].sharding.spec
            )
        batch = trainer.make_fake_batch(8, 16)
        losses[name] = [
            float(trainer.train_step(batch)["loss"]) for _ in range(3)
        ]
    np.testing.assert_allclose(losses["piped"], losses["flat"], rtol=2e-5)


def test_pipelined_trainer_with_segment_ids(devices8):
    """Packed batches (segment walls) train through the pipeline — the
    aux channel carries per-microbatch segment ids."""
    from odh_kubeflow_tpu.train.data import pack_documents, prefetch_to_device

    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    mesh = build_mesh(MeshConfig(pipe=2, data=4), devices8)
    trainer = Trainer(
        cfg,
        TrainConfig(warmup_steps=1, total_steps=4, pipeline_microbatches=2),
        lora_cfg=LoraConfig(rank=2),
        mesh=mesh,
    )
    rng = np.random.default_rng(1)
    docs = [
        rng.integers(1, cfg.vocab_size, size=rng.integers(3, 14)).tolist()
        for _ in range(48)
    ]
    stream = prefetch_to_device(
        pack_documents(docs, batch_size=4, seq_len=16), mesh
    )
    losses = [float(trainer.train_step(b)["loss"]) for b in stream]
    assert losses and all(np.isfinite(losses))


def test_maximal_axis_composition_pp_cp_tp(devices8):
    """pipe × context × tensor in ONE mesh: the pipeline schedule is
    manual over pipe, ring attention runs over context inside each
    stage, tensor shards the matmuls — all composed through the same
    Trainer. Loss matches a flat-mesh run to ring-vs-dense numerics."""
    piped = Trainer(
        LlamaConfig.tiny(dtype=jnp.float32),
        TrainConfig(warmup_steps=1, total_steps=4, pipeline_microbatches=2),
        lora_cfg=LoraConfig(rank=2),
        mesh=build_mesh(MeshConfig(pipe=2, context=2, tensor=2), devices8),
    )
    flat = Trainer(
        LlamaConfig.tiny(dtype=jnp.float32),
        TrainConfig(warmup_steps=1, total_steps=4),
        lora_cfg=LoraConfig(rank=2),
        mesh=build_mesh(MeshConfig(fsdp=8), devices8),
    )
    lp = float(piped.train_step(piped.make_fake_batch(8, 32))["loss"])
    lf = float(flat.train_step(flat.make_fake_batch(8, 32))["loss"])
    assert np.isfinite(lp) and np.isfinite(lf)
    assert abs(lp - lf) / lf < 5e-3  # ring vs dense fp accumulation


def test_eval_step_no_state_mutation():
    """eval_step reports the same loss train_step would see (pre-
    update) and leaves params/opt_state/step untouched."""
    import numpy as np

    trainer = Trainer(
        LlamaConfig.tiny(dtype=jnp.float32),
        TrainConfig(warmup_steps=1, total_steps=10),
        lora_cfg=LoraConfig(rank=2),
        mesh=build_mesh(MeshConfig(), jax.devices()[:1]),
    )
    batch = trainer.make_fake_batch(2, 16)
    before = jax.tree_util.tree_map(
        lambda x: np.asarray(x).copy(), trainer.lora_params
    )
    eval_loss = float(trainer.eval_step(batch)["loss"])
    # adapters untouched, step not advanced
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, np.asarray(b)),
        before,
        trainer.lora_params,
    )
    assert trainer.step == 0
    # the first train step computes its loss BEFORE applying updates —
    # it must equal the eval loss on the same batch
    train_loss = float(trainer.train_step(batch)["loss"])
    np.testing.assert_allclose(eval_loss, train_loss, rtol=1e-5)
    assert trainer.step == 1
