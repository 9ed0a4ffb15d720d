"""Driver for open-loop serving of ONE CHIP'S SHARE of a deployment
through the program's ``DecodeEngine``: ``drivers/engine.py``'s load
generator, clients and window (imported, not copied), with what a
family that is not ``dense`` needs beside them:

- the family builds its own seeded base (``family.make_params``) and the
  program's config object is built BEFORE any weight is drawn, so a
  program without the family fails in seconds;
- warm-up also sends a prompt long enough to be admitted in parts (the
  part and the final-part programs);
- the engine's counters for held experts and windowed caches are read
  into ``run.values`` for the per-layer metrics;
- the check: a sample of the finished greedy requests, the longest
  always among them, each run ONCE through the family's plain reference
  (``reference/<family>.py``: one full forward pass, float32, no cache)
  as prompt and served tokens together. At every served position the gap
  is the reference's best logit minus the reference's logit of the token
  that was served: prefill in parts and decoding through both kinds of
  cache against the reference's full forward. Logged beside it, from
  the program's cached forward over the same tokens (in parts, a fresh
  cache, no engine): the share of (token, layer) pairs whose chosen
  experts differ from the reference's, and at how many served positions
  that forward chooses another token than the engine served, which
  tells a fault of the engine from the arithmetic's."""

from __future__ import annotations

import math
import threading
import time

import numpy as np

from benchmark.drivers.engine import (
    Client,
    close_on_a_token,
    warm_up,
)

PAD_TO = 1024


def live_positions(clients, t_lo: float, t_hi: float, window: int,
                   points: int = 40) -> tuple:
    """Mean over the interval of the positions the requests being
    decoded hold: all of them (a full layer reads these), and each row's
    ``min(context, window)`` (a window layer reads these)."""
    full = windowed = 0.0
    for t in np.linspace(t_lo, t_hi, points):
        for c in clients:
            if c.times and c.times[0] <= t <= c.times[-1]:
                ctx = len(c.spec["prompt"]) + int(np.searchsorted(c.times, t))
                full += ctx
                windowed += min(ctx, window)
    return full / points, windowed / points


def check_against_reference(run, params, sample, program_cfg):
    """Gaps at every served position of every sampled request, and the
    share of (token, layer) pairs routed otherwise than the reference."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import core
    from odh_kubeflow_tpu.models.generate import family_forward, init_cache

    ref = core.load_module(run.roots, "reference", run.config["family"])
    config = run.config
    longest = max(len(c.spec["prompt"]) + len(c.tokens) for c in sample)
    length = -(-longest // PAD_TO) * PAD_TO
    # the reference walks queries 128 at a time; the program's routing
    # pass takes the prompt in the engine's own parts
    part = run.cell["program"]["prefill_chunk"]
    length = -(-length // part) * part
    n_at = max(len(c.tokens) for c in sample)

    @jax.jit
    def ref_logits(params, seq, at):
        return ref.logits(params, seq, config, at=at)

    _, fwd = family_forward(program_cfg)

    @jax.jit
    def program_routing(params, seq, at):
        cache = init_cache(program_cfg, 1, length, widest_part=part)
        k = config["num_experts_per_tok"]
        cache["moe_topk"] = jnp.zeros(
            (config["num_hidden_layers"], 1, length, k), jnp.int32
        )

        def one(carry, start):
            cache, best = carry
            pos = start + jnp.arange(part, dtype=jnp.int32)[None]
            toks = jax.lax.dynamic_slice_in_dim(seq, start, part)[None]
            lg, cache = fwd(
                params, toks, program_cfg, cache, start, positions=pos,
                kv_mask=jnp.arange(length)[None] < start + part,
                token_mask=jnp.ones((1, part), bool),
            )
            here = (at >= start) & (at < start + part)
            row = jnp.argmax(lg[0], axis=-1)[jnp.clip(at - start, 0, part - 1)]
            return (cache, jnp.where(here, row.astype(jnp.int32), best)), None

        (cache, best), _ = jax.lax.scan(
            one, (cache, jnp.zeros(at.shape, jnp.int32)),
            jnp.arange(0, length, part, dtype=jnp.int32),
        )
        return cache["moe_topk"][:, 0], best

    gaps, differ, pairs = [], 0, 0
    for c in sample:
        prompt, served = c.spec["prompt"], c.tokens
        n = len(prompt) + len(served)
        seq = np.zeros(length, np.int32)
        seq[:n] = list(prompt) + list(served)
        at = np.zeros(n_at, np.int32)
        at[: len(served)] = len(prompt) - 1 + np.arange(len(served))
        lg, ref_top = ref_logits(params, jnp.asarray(seq), jnp.asarray(at))
        lg = lg[: len(served)]
        picked = jnp.take_along_axis(
            lg, jnp.asarray(served, jnp.int32)[:, None], axis=-1
        )[:, 0]
        gaps.append(np.asarray(jnp.max(lg, axis=-1) - picked))
        got_top, own = program_routing(params, jnp.asarray(seq), jnp.asarray(at))
        # the program's own forward over the same tokens, a fresh cache
        # and no engine: where a served token is not the reference's
        # best and this chooses it too, the gap is the arithmetic's;
        # where this chooses the reference's, it is the engine's
        own = np.asarray(own)[: len(served)]
        tok = np.asarray(served)
        off = np.flatnonzero(gaps[-1] > 0)
        core.log(
            f"request {c.spec['id']}: prompt {len(prompt)}, {len(served)} "
            f"served ({len(set(served))} distinct); gap max "
            f"{gaps[-1].max():.4f}, mean {gaps[-1].mean():.6f}; {len(off)} "
            f"served tokens are not the reference's best, and the program's "
            f"own forward (no engine) chooses {int((own[off] == tok[off]).sum())} "
            f"of them; it differs from the engine at "
            f"{int((own != tok).sum())} of {len(served)}"
        )
        same = jnp.all(
            jnp.sort(got_top[:, :n], -1) == jnp.sort(ref_top[:, :n], -1), -1
        )
        differ += int(same.size - jnp.sum(same))
        pairs += int(same.size)
    core.log(
        f"routing: {differ} of {pairs} (token, layer) pairs chose other "
        f"experts than the reference ({100.0 * differ / max(pairs, 1):.4f} %)"
    )
    run.values["routing_differs_share"] = 100.0 * differ / max(pairs, 1)
    return np.concatenate(gaps)


def run(run) -> dict:
    import jax
    from jax.profiler import TraceAnnotation

    from benchmark.harness import core, stats, traffic

    mix, config, family = run.mix, run.config, run.family
    # the program's config first: a program that lacks the family stops
    # here, before 9 GB of weights are drawn
    program_cfg = family.program_config(config)
    from odh_kubeflow_tpu.models.engine import DecodeEngine

    seed31 = run.seed % (2**31 - 1)
    t_start = time.monotonic()
    with jax.default_device(run.devices[0]):
        params = family.make_params(config, run.seed)
    jax.block_until_ready(params)
    t_weights = time.monotonic()
    program = dict(run.cell["program"])
    program["prompt_buckets"] = tuple(program["prompt_buckets"])
    engine = DecodeEngine(params, program_cfg, seed=seed31, **program)
    reqs = traffic.requests(mix, config["vocab_size"], run.seed, run.seconds)
    try:
        warm_up(engine, config["vocab_size"], mix["sampling"])
        # a prompt admitted in parts: two whole parts and a final one
        long = np.random.default_rng(1).integers(
            1, config["vocab_size"], size=2 * engine.prefill_chunk + 5
        ).tolist()
        engine.submit(
            long, max_tokens=10, temperature=mix["sampling"]["temperature"],
            top_p=mix["sampling"]["top_p"],
        ).result(timeout=1200)
        run.ready()
        core.log(
            f"set-up {run.values['setup_s']:.1f} s beside "
            f"{run.runtime_start_s:.1f} of runtime start "
            f"({t_start - run.t0:.1f} to the driver, "
            f"{t_weights - t_start:.1f} weights, "
            f"{time.monotonic() - t_weights:.1f} engine and warm-up); "
            f"{len(reqs)} requests due; cache {engine.cache_bytes}"
        )

        compiles_before = run.counters.snapshot()
        counters = lambda: np.asarray([  # noqa: E731
            engine.decode_steps, engine.tokens_emitted,
            engine.moe_local_assignments, engine.moe_experts_hit,
            engine.moe_dropped, engine.window_blocks_skipped,
        ])
        c0 = counters()
        t_open = time.monotonic()
        t_close = t_open + run.seconds
        clients = [Client(r, t_open + r["due_s"]) for r in reqs]

        tracer = None
        if run.trace:
            def traced():
                time.sleep(max(run.seconds - mix["trace_s"], 0))
                jax.profiler.start_trace(run.trace_dir)
                a = time.monotonic()
                with TraceAnnotation("bench.window"):
                    time.sleep(max(t_close - time.monotonic(), 0.5))
                run.values["traced"] = (a, time.monotonic())
                jax.profiler.stop_trace()

            tracer = threading.Thread(target=traced, daemon=True)
            tracer.start()

        for c in clients:
            with TraceAnnotation("loadgen.wait"):
                time.sleep(max(c.due_at - time.monotonic(), 0))
            with TraceAnnotation("loadgen.submit"):
                c.send(engine, mix["sampling"])
        time.sleep(max(t_close - time.monotonic(), 0))
        c1 = counters()
        if mix["drain"]:
            t_rate_end, stalled = t_close, False
            give_up = t_close + mix["drain_timeout_s"]
            for c in clients:
                if c.thread is not None:
                    c.thread.join(timeout=max(give_up - time.monotonic(), 0))
        else:
            t_rate_end, stalled = close_on_a_token(
                clients, t_close, mix["close_timeout_s"]
            )
        t_end = time.monotonic()
        compiled_in_window = run.counters.snapshot()[0] - compiles_before[0]
        # what the run itself cuts short is not a failure of the program
        cut_short = [c for c in clients if not c.done and c.req is not None]
        for c in cut_short:
            c.req.cancel()
        if tracer is not None:
            tracer.join(timeout=120)
        failure = engine.failure
    finally:
        engine.stop()
    memory_peak = core.memory_peak_bytes(run.devices)
    n_slots, chunk, cache_bytes = engine.n_slots, engine.chunk, engine.cache_bytes
    # its cache goes with it (nothing here may keep the engine alive: the
    # reference needs the room); the weights stay for the reference
    del engine, counters

    judged = (
        clients if mix["drain"] else [c for c in clients if c not in cut_short]
    )
    failed = [c for c in judged if not c.complete]
    v = run.values
    gave_up = lambda c: (t_end - c.due_at) * 1e3  # noqa: E731
    ttft = [
        stats.ttft_ms(c.due_at, c.times[0]) if c.times else gave_up(c)
        for c in clients
        if mix["drain"] or c.times
    ]
    v["ttft_ms"] = ttft
    v["late_ms"] = [(c.sent_at - c.due_at) * 1e3 for c in clients]
    if mix["drain"]:
        v["ttft_p95_ms"] = stats.percentile(ttft, 95)[0]
    in_window = sum(
        int(np.searchsorted(c.times, t_rate_end, side="left")) for c in clients
    )
    if not mix["drain"]:
        # every token streamed in the window over ALL of the window
        v["serve_tokens_per_s"] = in_window / (t_rate_end - t_open)
    steps, emitted, assigned, hit, dropped, skipped = (c1 - c0).tolist()
    v["tokens_in_window"] = in_window
    v["slot_occupancy"] = 100.0 * emitted / max(steps * n_slots, 1)
    v["decode_chunk"], v["n_slots"] = chunk, n_slots
    v["decode_steps"] = steps
    v["moe_local_assignments"], v["moe_experts_hit"] = assigned, hit
    v["moe_dropped"], v["window_blocks_skipped"] = dropped, skipped
    held = config["num_hidden_layers"] * config["num_experts"]
    v["moe_experts_hit_per_step"] = hit / max(steps, 1)
    v["moe_experts_hit_share"] = 100.0 * hit / max(steps * held, 1)
    v["kv_cache_gb"] = sum(cache_bytes.values()) / 1e9
    v["memory_peak_gb"] = None if memory_peak is None else memory_peak / 1e9
    if run.trace:
        v["live_full"], v["live_window"] = live_positions(
            clients, *v["traced"], config["sliding_window"]
        )
    core.log(
        f"{len(clients)} due, {sum(c.complete for c in clients)} complete, "
        f"{len(failed)} failed, {in_window} tokens in a window of "
        f"{t_rate_end - t_open:.3f} s; {steps} decode steps, {assigned} "
        f"local assignments, {hit} expert banks read, {dropped} dropped, "
        f"{skipped} window blocks skipped; cache {cache_bytes}"
    )

    # ---- the comparison: finished greedy requests against the reference
    greedy = [c for c in clients if c.complete and c.spec["greedy"]]
    rng = np.random.default_rng([run.seed, 3])
    greedy.sort(key=lambda c: len(c.spec["prompt"]) + len(c.tokens))
    sample = greedy[-1:] + [
        greedy[i] for i in rng.permutation(len(greedy) - 1)[: mix["check_requests"] - 1]
    ] if greedy else []
    t_ref = time.monotonic()
    gaps = check_against_reference(
        run, params, sample, program_cfg
    ) if sample else np.array([math.nan])
    core.log(
        f"reference {time.monotonic() - t_ref:.1f} s over {len(sample)} "
        f"requests (contexts "
        f"{[len(c.spec['prompt']) + len(c.tokens) for c in sample]}), "
        f"{sum(len(c.tokens) for c in sample)} served tokens"
    )
    limits = run.cell["limits"]
    run.check("served_logit_gap_max", float(gaps.max()), limits["served_logit_gap_max"])
    run.check("served_logit_gap_mean", float(gaps.mean()), limits["served_logit_gap_mean"])
    run.check("failed_requests", len(failed), 0)
    run.check("engine_failure", 0 if failure is None else 1, 0)
    run.check("stalled_at_close", int(stalled), 0)
    run.check("compiles_in_window", compiled_in_window, 0)
    run.check("moe_dropped", dropped, 0)
    return {
        "attempted": len(clients),
        "failed": len(failed),
        "memory_peak_bytes": memory_peak,
    }
