"""Driver for a training job: the program's ``Trainer`` (QLoRA on an
int8 base) fed by the program's ``pack_documents`` and
``prefetch_to_device`` with documents from the mix.

Set-up builds ONE trainer, gives it the harness's seeded weights, drives
it through its first ``check_steps`` steps with the window's own call
and feed, and hands that same trainer to the window. Once the window has
closed, the plain reference follows those first steps from the same
adapters on the same batches, and the two are compared.

The cell passes the program only what a deployer chooses: the sizes of
the configuration, int8 storage, the adapter's rank, batch x sequence,
the optimizer's settings and (``deployment.program``) the remat policy
needed to fit. Every other argument stays at the program's default."""

from __future__ import annotations

import math
import statistics
import time

import numpy as np

PACKED_KEYS = ("tokens", "targets", "segment_ids", "loss_mask")


def _adam_mu(opt_state):
    """The first moment in the program's optimizer state."""
    import jax

    found = [
        x for x in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "mu")
        )
        if hasattr(x, "mu")
    ]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0].mu


def _copy(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(jnp.copy, tree)


def leaf_norms(tree) -> dict:
    """{leaf path: [per-layer norms]} of a stacked adapter tree."""
    import jax
    import jax.numpy as jnp

    out = {}
    for path, x in jax.tree_util.tree_leaves_with_path(tree):
        x = jnp.asarray(x, jnp.float32)
        out[jax.tree_util.keystr(path)] = np.asarray(
            jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)), axis=1))
        )
    return out


def worst_leaf_gap(got: dict, ref: dict) -> float:
    """The widest gap between the program's norm of a leaf and the
    reference's, measured against the reference's norm of that leaf or
    of the median leaf, whichever is larger."""
    median = statistics.median(float(v) for r in ref.values() for v in r)
    worst = 0.0
    for key, r in ref.items():
        gap = np.abs(got[key] - r) / np.maximum(r, median)
        worst = max(worst, float(np.max(gap)))
    return worst


def program_first_steps(trainer, feed, n: int, b1: float) -> dict:
    """Drive the trainer through its first ``n`` steps with the window's
    own call and feed; what the comparison reads of them."""
    import jax

    lora0 = _copy(trainer.lora_params)
    losses, mu1 = [], None
    for i in range(n):
        metrics = trainer.train_step(next(feed))
        losses.append(float(metrics["loss"]))
        if i == 0:
            mu1 = _adam_mu(trainer.opt_state)
            # the gradient as Adam got it (after the clip): m1 = (1 - b1) g
            grad = leaf_norms(jax.tree.map(lambda m: m / (1 - b1), mu1))
    delta = leaf_norms(
        jax.tree.map(lambda a, b: a - b, trainer.lora_params, lora0)
    )
    return {"lora0": lora0, "losses": losses, "grad": grad, "delta": delta}


def reference_numbers(params, lora0, batches, config, opt, prec=None) -> dict:
    """The same numbers from the plain reference (``prec``: a lower
    precision, for the control)."""
    import jax

    from benchmark.reference import model, train as ref_train

    losses, grad, lora = ref_train.run_steps(
        params, lora0, batches, config, opt, prec or model.SOUND
    )
    return {
        "losses": losses,
        "grad": leaf_norms(grad),
        "delta": leaf_norms(jax.tree.map(lambda a, b: a - b, lora, lora0)),
    }


def gaps(got: dict, ref: dict) -> dict:
    """The numbers compared: each step's loss, the first gradient and the
    adapters' change, the last two by the worst leaf."""
    out = {
        f"loss_gap.step{i + 1}": abs(a - b)
        for i, (a, b) in enumerate(zip(got["losses"], ref["losses"]))
    }
    out["first_grad_leaf_gap"] = worst_leaf_gap(got["grad"], ref["grad"])
    out["update_leaf_gap"] = worst_leaf_gap(got["delta"], ref["delta"])
    return out


def build(run, seed31: int):
    """The trainer with the harness's weights in it, the device feed and
    the host copies of the batches it yields."""
    import jax
    from odh_kubeflow_tpu.models import LoraConfig
    from odh_kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
    from odh_kubeflow_tpu.train import TrainConfig, Trainer
    from odh_kubeflow_tpu.train.data import pack_documents, prefetch_to_device

    from benchmark.harness import traffic, weights

    mix, config = run.mix, run.config
    B, S = mix["batch"], mix["sequence"]
    mesh = build_mesh(MeshConfig(**config["deployment"]["mesh"]), run.devices)
    trainer = Trainer(
        run.family.program_config(config),
        TrainConfig(**mix["optimizer"]),
        lora_cfg=LoraConfig(rank=mix["lora"]["rank"], alpha=mix["lora"]["alpha"]),
        mesh=mesh,
        seed=seed31,
        quantize_base="int8",
        precompile_batch=(B, S, PACKED_KEYS),
    )
    # the trainer made weights of its own; the run uses the harness's,
    # which the reference reads too. Free the first before making the
    # second: the chip does not hold both.
    shardings = jax.tree.map(lambda x: x.sharding, trainer.params)
    lora_sh = jax.tree.map(lambda x: x.sharding, trainer.lora_params)
    jax.tree.map(lambda x: x.delete(), trainer.params)
    with jax.default_device(run.devices[0]):
        trainer.params = jax.device_put(
            weights.make_params(config, run.seed, run.family), shardings
        )
        trainer.lora_params = jax.device_put(
            weights.make_lora(config, mix["lora"], run.seed), lora_sh
        )
    docs = traffic.documents(
        mix, config["vocab_size"], run.seed, mix["max_steps"] * B * S
    )
    host_batches = []

    def recorded():
        for batch in pack_documents(docs, B, S):
            host_batches.append(batch)
            yield batch

    feed = prefetch_to_device(recorded(), mesh)
    return trainer, feed, host_batches


def run(run) -> dict:
    import jax
    from jax.profiler import TraceAnnotation

    from benchmark.harness import core, counts

    mix, config = run.mix, run.config
    seed31 = run.seed % (2**31 - 1)
    t_start = time.monotonic()
    trainer, feed, host_batches = build(run, seed31)
    t_built = time.monotonic()
    n_check = mix["check_steps"]

    # ---- the first steps, through the window's own call and feed
    first = program_first_steps(trainer, feed, n_check, mix["optimizer"]["b1"])
    first_losses = first["losses"]
    step = n_check  # index into host_batches of the next batch to run
    run.ready()
    core.log(
        f"set-up {run.values['setup_s']:.1f} s beside "
        f"{run.runtime_start_s:.1f} of runtime start ({t_start - run.t0:.1f} "
        f"to the driver, {t_built - t_start:.1f} trainer and weights, "
        f"{time.monotonic() - t_built:.1f} first steps); first losses {first_losses}"
    )

    # ---- the window: one step in flight while the last one's loss is
    # fetched, so the device never waits and the host's clock follows it
    compiles_before = run.counters.snapshot()
    trace_after = run.seconds - mix["trace_s"] if run.trace else math.inf
    tracing = False
    window_span = None
    step_ends, waits, losses = [], [], []
    tokens = positions = unmasked = 0
    pending = None
    t_open = time.monotonic()
    while True:
        elapsed = time.monotonic() - t_open
        if not tracing and elapsed >= trace_after:
            jax.profiler.start_trace(run.trace_dir)
            window_span = TraceAnnotation("bench.window")
            window_span.__enter__()
            tracing = True
        if elapsed >= run.seconds:
            break
        t_wait = time.monotonic()
        with TraceAnnotation("train.next_batch"):
            batch = next(feed)
        waits.append((time.monotonic() - t_wait) * 1e3)
        with TraceAnnotation("train.dispatch"):
            metrics = trainer.train_step(batch)
        hb = host_batches[step]
        step += 1
        tokens += int((hb["segment_ids"] > 0).sum())
        positions += hb["tokens"].size
        unmasked += int(hb["loss_mask"].sum())
        if pending is not None:
            with TraceAnnotation("train.fetch"):
                losses.append(float(pending))
            step_ends.append(time.monotonic())
        pending = metrics["loss"]
    with TraceAnnotation("train.fetch"):
        losses.append(float(pending))
    t_close = time.monotonic()
    step_ends.append(t_close)
    if tracing:
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    window = t_close - t_open
    compiled_in_window = run.counters.snapshot()[0] - compiles_before[0]
    memory_peak = core.memory_peak_bytes(run.devices)

    steps = len(losses)
    timed = host_batches[n_check : n_check + steps]
    pairs = sum(
        counts.attention_pairs(counts.segment_lengths(b["segment_ids"]))
        for b in timed
    )
    flops = counts.qlora_step_flops(
        config, positions, pairs, run.family.token_weights_per_layer(config)
    )
    v = run.values
    v["train_tokens_per_s"] = tokens / window
    v["step_ms"] = [
        (b - a) * 1e3 for a, b in zip([t_open] + step_ends[:-1], step_ends)
    ]
    v["input_wait_ms"] = waits
    v["pad_share"] = 100.0 * (1 - unmasked / positions)
    v["window_s"] = window
    v["steps"] = steps
    v["step_flops"] = flops / steps
    v["segment_pairs_per_step"] = pairs / steps
    v["rows_per_step"] = mix["batch"]
    if run.peaks is not None:
        v["train_mfu"] = 100.0 * flops / window / (
            run.peaks["bf16_flops_per_s"] * len(run.devices)
        )
    v["memory_peak_gb"] = None if memory_peak is None else memory_peak / 1e9

    # ---- the comparison, outside the window and outside set-up
    t_ref = time.monotonic()
    ref = reference_numbers(
        trainer.params, first["lora0"], host_batches[:n_check], config,
        mix["optimizer"],
    )
    core.log(
        f"reference {time.monotonic() - t_ref:.1f} s; losses {ref['losses']} "
        f"against the program's {first_losses}"
    )
    limits = run.cell["limits"]
    for name, value in gaps(first, ref).items():
        run.check(name, value, limits[name.split(".")[0]])
    run.check("nonfinite_losses", sum(not math.isfinite(x) for x in losses), 0)
    run.check("compiles_in_window", compiled_in_window, 0)
    return {
        "attempted": steps,
        "failed": sum(not math.isfinite(x) for x in losses),
        "memory_peak_bytes": memory_peak,
    }
