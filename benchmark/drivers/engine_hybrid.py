"""Driver for open-loop serving of a stack that keeps recurrent STATE
beside its keys and values (``granitemoehybrid``) through the program's
``DecodeEngine``: ``drivers/engine.py``'s load generator, clients and
closing burst and ``drivers/engine_share.py``'s check against the
family's plain reference (imported, not copied). ``engine_share.run``
itself reads ``config["sliding_window"]`` and ``config["num_experts"]``,
which this family's source does not have, and it gives the engine up
before anything can be read from it; so the window below is written
out a third time (PERF.md section 7: it should be ONE function of
``drivers/engine.py``, which only a ``benchmark`` PR may make). What
this ``run`` does beside it:

- ``decode_calls`` is read with ``decode_steps``, so the per-layer
  metrics take a decode step's time from the steps the engine counted (a
  chunk's length is the program's business), and a prefill's padding
  from ``prefill_tokens`` / ``prefill_positions``;
- ``run.values`` gets the cache by kind (``DecodeEngine.cache_bytes``:
  keys and values, state), the slots that were decoding in the traced
  interval and the positions they held;
- the rate is ``engine_share``'s: every token streamed in the window
  over the window, which ends at the first token at or after
  ``--seconds``. Beside it, as a per-layer number, ``mean_rate``: the
  mean of that quotient over the window's last ``RATE_OVER_S`` seconds,
  which a burst crossing the close cannot move (PERF.md section 6, PR
  31: for the ``benchmark`` issue that looks at all three saturated
  cells);
- the routing's disagreement with the reference (``engine_share``
  computes it) is held to a limit too;
- **the state the ENGINE holds is held against the reference's**
  (``ssm_state_gap``). At the close the engine is stopped with its
  slots as they are (no request cancelled first: a freed slot would be
  given to the next in the queue), and of the requests then decoding
  the one served the most tokens (the most decode steps on its state)
  is watched: its row of the
  slots' SSM state (``DecodeEngine.slot_state``: spliced in by its
  prefill, handed from part to part, then read and written by every
  decode step beside 31 other rows) against the reference's recurrence
  token by token over its prompt and the tokens it was served, in the
  first Mamba-2 layer (below it the layers' own inputs differ), in the
  HEAD that lies furthest off: a state kept in a lower precision drifts
  in the heads that remember longest (the smallest ``dt * A``), where
  the served tokens' logits, and the layer's state taken whole, hardly
  show it.

``state_rounded_to_bf16`` is the control for that number: the PROGRAM
with every decode step's state write rounded to bfloat16. ``control_readings``
is the other controls' (the reference in each lower precision put in the
program's place). The limits were calibrated with both (PERF.md) and the
tests hold them to both. No run of the benchmark calls either."""

from __future__ import annotations

import contextlib
import math
import threading
import time

import numpy as np

from benchmark.drivers.engine import Client, close_on_a_token, warm_up
from benchmark.drivers.engine_share import (
    PAD_TO,
    check_against_reference,
    live_positions,
)

COUNTERS = (
    "decode_steps", "decode_calls", "tokens_emitted", "moe_local_assignments",
    "moe_experts_hit", "moe_dropped", "prefill_calls", "prefill_tokens",
    "prefill_positions",
)
RATE_OVER_S = 5.0


def read_counters(engine) -> dict:
    return {name: getattr(engine, name) for name in COUNTERS}


def mean_rate(clients, t_open: float, seconds: float, over_s: float) -> float:
    """The mean over the window's last ``over_s`` seconds of (tokens
    streamed so far) / (time since the window opened). Between two
    stamps the count stands still, so the integral is a sum of count x
    log(end / start). Every stamp enters it continuously: a burst that
    lands just before the close instead of just after moves it by the
    burst's share of the run times the part of ``over_s`` it gained,
    not by the whole burst."""
    times = np.sort(np.concatenate(
        [np.asarray(c.times, np.float64) for c in clients] + [np.empty(0)]
    )) - t_open
    over_s = min(over_s, seconds / 2)  # a window shorter than asked for
    lo = seconds - over_s
    edges = np.concatenate(
        [[lo], times[(times > lo) & (times < seconds)], [seconds]]
    )
    streamed = np.searchsorted(times, edges[:-1], side="right")
    return float((streamed * np.log(edges[1:] / edges[:-1])).sum() / over_s)


def live_slots(clients, t_lo: float, t_hi: float, points: int = 40) -> float:
    """Mean over the interval of the requests being decoded."""
    return sum(
        sum(1 for c in clients if c.times and c.times[0] <= t <= c.times[-1])
        for t in np.linspace(t_lo, t_hi, points)
    ) / points


def held_state(engine, clients, d_head: int):
    """Of a STOPPED engine: the request that was decoding and had been
    served the most tokens, as ``(tokens its state has taken in, that state
    [Mamba-2 layers, heads, d_head, d_state], the request's id)``; None
    where nothing was decoding. A stream's state has taken in its
    prompt and every token served but the last."""
    from odh_kubeflow_tpu.ops.pallas_ssm import from_state

    live = [
        c for c in clients
        if c.req is not None and c.req.tokens and not c.req.complete
    ]
    if not live:
        return None
    c = max(live, key=lambda c: len(c.req.tokens))
    tokens = list(c.spec["prompt"]) + list(c.req.tokens)[:-1]
    ssm = engine.slot_state(c.req.slot)["ssm"]
    return tokens, np.asarray(from_state(ssm, d_head)), c.spec["id"]


def reference_state(run, params, tokens, prec=None) -> np.ndarray:
    """The reference's SSM state ``[heads, d_head, d_state]`` of the
    first Mamba-2 layer after ``tokens``, the recurrence token by token:
    the layers down to that one alone (what lies below it moves nothing
    in it)."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import core

    ref = core.load_module(run.roots, "reference", run.config["family"])
    config = run.config
    depth = config["layer_types"].index("mamba") + 1
    n = len(tokens)
    seq = np.zeros(-(-n // PAD_TO) * PAD_TO, np.int32)
    seq[:n] = tokens

    @jax.jit
    def states(params, seq, n):
        top = jax.tree_util.tree_map(lambda a: a[:depth], params["layers"])
        return ref.hidden_states(
            {**params, "layers": top}, seq, config, prec or ref.SOUND, stop=n
        )[2]

    with jax.default_matmul_precision("highest"):
        return np.asarray(states(params, jnp.asarray(seq), n)[0])


def _rel(a, b) -> np.ndarray:
    """Relative distance of states a from b ``[heads, d_head,
    d_state]``, a head at a time."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.sqrt(((a - b) ** 2).sum((-2, -1)) / (b**2).sum((-2, -1)))


@contextlib.contextmanager
def state_rounded_to_bf16():
    """The program with a bfloat16 SSM state, as far as a decode step
    goes: inside this, every decode step's update of a layer's state
    (``ops/pallas_ssm.py``: the kernel and its plain form) is rounded to
    bfloat16 where it is written. An engine BUILT inside it runs so. The
    control that ``ssm_state_gap``'s limit must fail."""
    import jax

    from odh_kubeflow_tpu.ops import pallas_ssm

    def rounded(update):
        def step(x, dt, A, Bm, Cm, state, layer, **kw):
            y, state = update(x, dt, A, Bm, Cm, state, layer, **kw)
            S = jax.lax.dynamic_index_in_dim(state, layer, 0, False)
            # not a cast there and back: XLA takes such a pair out
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
            return y, jax.lax.dynamic_update_index_in_dim(state, S, layer, 0)

        return step

    names = ("ssm_decode_update", "ssm_step_plain")
    sound = {name: getattr(pallas_ssm, name) for name in names}
    for name in names:
        setattr(pallas_ssm, name, rounded(sound[name]))
    try:
        yield
    finally:
        for name in names:
            setattr(pallas_ssm, name, sound[name])


def control_readings(run, params, sample, watched_tokens) -> dict:
    """What the check reads with the reference in a lower precision in
    the program's place: at every served position the token that
    precision puts first, its gap under the sound reference, the share
    of (token, layer) pairs it routes otherwise, and its first Mamba-2
    layer's state after ``watched_tokens`` against the sound one's."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import core

    ref = core.load_module(run.roots, "reference", run.config["family"])
    config = run.config
    longest = max(len(c.spec["prompt"]) + len(c.tokens) for c in sample)
    part = run.cell["program"]["prefill_chunk"]
    step = part if longest <= 128 else math.lcm(part, 128)
    length = -(-longest // step) * step
    n_at = max(len(c.tokens) for c in sample)
    sound = jax.jit(lambda p, s, at: ref.logits(p, s, config, at=at))
    sound_state = reference_state(run, params, watched_tokens)

    out = {}
    for name, prec in (
        ("int8_activations", ref.Precision(act="int8")),
        ("bf16_state", ref.Precision(state="bf16")),
    ):
        low = jax.jit(
            lambda p, s, at, prec=prec: ref.logits(p, s, config, prec, at)
        )
        gaps, differ, pairs = [], 0, 0
        for c in sample:
            n = len(c.spec["prompt"]) + len(c.tokens)
            seq = np.zeros(length, np.int32)
            seq[:n] = list(c.spec["prompt"]) + list(c.tokens)
            at = np.zeros(n_at, np.int32)
            at[: len(c.tokens)] = len(c.spec["prompt"]) - 1 + np.arange(len(c.tokens))
            lg, top = sound(params, jnp.asarray(seq), jnp.asarray(at))
            lg_low, top_low = low(params, jnp.asarray(seq), jnp.asarray(at))
            lg, lg_low = lg[: len(c.tokens)], lg_low[: len(c.tokens)]
            picked = jnp.take_along_axis(
                lg, jnp.argmax(lg_low, axis=-1)[:, None], axis=-1
            )[:, 0]
            gaps.append(np.asarray(jnp.max(lg, axis=-1) - picked))
            same = jnp.all(
                jnp.sort(top[:, :n], -1) == jnp.sort(top_low[:, :n], -1), -1
            )
            differ += int(same.size - jnp.sum(same))
            pairs += int(same.size)
        gaps = np.concatenate(gaps)
        out[name] = {
            "served_logit_gap_max": float(gaps.max()),
            "served_logit_gap_mean": float(gaps.mean()),
            "routing_differs_share": 100.0 * differ / max(pairs, 1),
            "ssm_state_gap": float(_rel(
                reference_state(run, params, watched_tokens, prec), sound_state
            ).max()),
        }
        core.log(f"control {name}: {out[name]}")
    return out


def run(run) -> dict:
    import jax
    from jax.profiler import TraceAnnotation

    from benchmark.harness import core, traffic

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
        c0 = read_counters(engine)
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
        c1 = read_counters(engine)
        t_rate_end, stalled = close_on_a_token(
            clients, t_close, mix["close_timeout_s"]
        )
        compiled_in_window = run.counters.snapshot()[0] - compiles_before[0]
        # what the run itself cuts short is not a failure of the program
        cut_short = [c for c in clients if not c.done and c.req is not None]
        failure = engine.failure
    finally:
        # with its slots as they are: nothing was cancelled first, and
        # the trace (clipped to its ``bench.window``) is not waited for
        engine.stop()
    if tracer is not None:
        tracer.join(timeout=120)
    watched = held_state(engine, cut_short, config["mamba_d_head"])
    memory_peak = core.memory_peak_bytes(run.devices)
    n_slots, cache_bytes = engine.n_slots, engine.cache_bytes
    # its cache goes with it (nothing here may keep the engine alive: the
    # reference needs the room); the weights stay for the reference
    del engine

    failed = [c for c in clients if c not in cut_short and not c.complete]
    v = run.values
    in_window = sum(
        int(np.searchsorted(c.times, t_rate_end, side="left")) for c in clients
    )
    # every token streamed in the window over ALL of the window
    v["serve_tokens_per_s"] = in_window / (t_rate_end - t_open)
    v["serve_rate_mean"] = mean_rate(clients, t_open, run.seconds, RATE_OVER_S)
    d = {k: c1[k] - c0[k] for k in COUNTERS}
    steps = max(d["decode_steps"], 1)
    v["n_slots"] = n_slots
    v["slot_occupancy"] = 100.0 * d["tokens_emitted"] / (steps * n_slots)
    v["decode_steps_per_call"] = d["decode_steps"] / max(d["decode_calls"], 1)
    v["moe_experts_hit_per_step"] = d["moe_experts_hit"] / steps
    v["moe_experts_hit_share"] = 100.0 * d["moe_experts_hit"] / (
        steps * config["num_hidden_layers"] * family.held(config)[1]
    )
    v["kv_cache_gb"] = (cache_bytes["full"] + cache_bytes["window"]) / 1e9
    v["state_cache_gb"] = cache_bytes["state"] / 1e9
    v["memory_peak_gb"] = None if memory_peak is None else memory_peak / 1e9
    if run.trace:
        v["live_full"], _ = live_positions(clients, *v["traced"], 2**31)
        v["live_slots"] = live_slots(clients, *v["traced"])
    core.log(
        f"{len(clients)} due, {sum(c.complete for c in clients)} complete, "
        f"{len(failed)} failed, {in_window} tokens in a window of "
        f"{t_rate_end - t_open:.3f} s ({v['serve_rate_mean']:.2f} a second in "
        f"the mean over its last seconds); counters over the "
        f"window {d}; cache {cache_bytes}"
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
    state = np.array([math.nan])
    if watched is not None:
        tokens, held, rid = watched
        t_ref = time.monotonic()
        # the first Mamba-2 layer's: below it the layers' own inputs differ
        state = _rel(held[0], reference_state(run, params, tokens))
        core.log(
            f"state: request {rid} was decoding at the close with "
            f"{len(tokens)} tokens in its state; its row of the engine's SSM "
            f"state lies {state.max():.5f} of the reference's away in the "
            f"head furthest off (the heads' median {np.median(state):.5f}; "
            f"reference {time.monotonic() - t_ref:.1f} s)"
        )
    limits = run.cell["limits"]
    run.check("served_logit_gap_max", float(gaps.max()), limits["served_logit_gap_max"])
    run.check("served_logit_gap_mean", float(gaps.mean()), limits["served_logit_gap_mean"])
    run.check(
        "routing_differs_share", v.get("routing_differs_share", math.nan),
        limits["routing_differs_share"],
    )
    run.check("ssm_state_gap", float(state.max()), limits["ssm_state_gap"])
    run.check("failed_requests", len(failed), 0)
    run.check("engine_failure", 0 if failure is None else 1, 0)
    run.check("stalled_at_close", int(stalled), 0)
    run.check("compiles_in_window", compiled_in_window, 0)
    run.check("moe_dropped", d["moe_dropped"], 0)
    return {
        "attempted": len(clients),
        "failed": len(failed),
        "memory_peak_bytes": memory_peak,
    }
