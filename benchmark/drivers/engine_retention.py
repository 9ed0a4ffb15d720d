"""Driver for open-loop serving of a stack that keeps NO keys and values
at all: every layer a power-retention STATE and its normaliser
(``brumby``), through the program's ``DecodeEngine``.

``drivers/engine_hybrid.py`` and ``drivers/engine_gdn.py`` do the same
for stacks that keep a state BESIDE keys and values, and everything of
theirs that does not name their families' keys is imported, not copied:
the clients, the warm-up and the closing burst (``drivers/engine.py``),
the counters, the mean rate, the live slots and the distance of two
states (``drivers/engine_hybrid.py``), the padded length
(``drivers/engine_share.py``). Their ``run`` and ``engine_share``'s
check read the experts' keys (``num_experts_per_tok``, the routing
leaf), which this source does not have, so the window stands here a
FIFTH time (PERF.md section 7: one function of ``drivers/engine.py``,
which only a ``benchmark`` PR may make). What differs:

- the check is of the logits alone (there is no router): a sample of
  the finished greedy requests, the longest always among them, each run
  ONCE through the family's plain reference (the ATTENTION form: no
  feature map, no state), prompt and served tokens together; at every
  served position the reference's best logit less its logit of the
  served token;
- the state the engine holds is ``[layers, Hkv, R, d_v, d]`` as the
  program lays it (cyclic diagonals of the symmetric half), read out in
  the order of the distinct pairs (the family's ``to_symmetric_half``)
  and held against ``reference.state_at``: the reference's direct sum
  ``sum_j exp(G_t - G_j) phi(k_j) v_j^T`` in the first layer
  (``retention_state_gap``, the head furthest off).

``state_rounded_to_bf16`` is ``retention_state_gap``'s control: the
PROGRAM with every decode step's state write rounded to bfloat16.
``control_readings`` is the other controls' (the reference in each lower
precision put in the program's place). The limits were calibrated with
both (PERF.md) and the tests hold them to both.
``scan_operands_float32`` is no control but the other side of an
attribution: the program with NOTHING of a prefill part's scan rounded
to bfloat16, which says how much of a sound run's ``retention_state_gap``
is the scan's own rounding and how much the bfloat16 activations' above
it (the configuration's ``precision``, PERF.md section 6). No run of the
benchmark calls any of the three."""

from __future__ import annotations

import contextlib
import math
import threading
import time

import numpy as np

from benchmark.drivers.engine import Client, close_on_a_token, warm_up
from benchmark.drivers.engine_hybrid import (
    RATE_OVER_S,
    _rel,
    live_slots,
    mean_rate,
    read_counters,
)
from benchmark.drivers.engine_share import PAD_TO


def held_state(engine, clients):
    """Of a STOPPED engine: the request that was decoding and had been
    served the most tokens, as ``(tokens its state has taken in, the
    first layer's state [Hkv, R, d_v, d] as the program lays it, the
    request's id)``; None where nothing was decoding. A stream's state
    has taken in its prompt and every token served but the last."""
    live = [
        c for c in clients
        if c.req is not None and c.req.tokens and not c.req.complete
    ]
    if not live:
        return None
    c = max(live, key=lambda c: len(c.req.tokens))
    tokens = list(c.spec["prompt"]) + list(c.req.tokens)[:-1]
    first = np.asarray(engine.slot_state(c.req.slot)["ssm"][0])
    return tokens, first, c.spec["id"]


def _padded(tokens, length=None) -> np.ndarray:
    n = len(tokens)
    seq = np.zeros(length or -(-n // PAD_TO) * PAD_TO, np.int32)
    seq[:n] = tokens
    return seq


def reference_state(run, params, tokens, prec=None) -> np.ndarray:
    """The reference's state ``[Hkv, d (d + 1) / 2, d_v]`` of the first
    layer after ``tokens``: the direct sum over its positions."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import core

    ref = core.load_module(run.roots, "reference", run.config["family"])
    config = run.config
    seq = _padded(tokens)
    state = jax.jit(lambda p, seq, n: ref.state_at(
        p, seq, config, layer=0, stop=n, prec=prec or ref.SOUND,
        block=min(PAD_TO, len(seq)),
    ))
    return np.asarray(state(params, jnp.asarray(seq), len(tokens)))


@contextlib.contextmanager
def state_rounded_to_bf16():
    """The program with a bfloat16 retention state, as far as a decode
    step goes: inside this, every decode step's update of a layer's
    state (``ops/pallas_retention.py``: the kernel and its plain form)
    is rounded to bfloat16 where it is written. An engine BUILT inside
    it runs so. The control that ``retention_state_gap``'s limit must
    fail."""
    import jax

    from odh_kubeflow_tpu.ops import pallas_retention

    def rounded(update):
        def step(q, k, v, log_g, state, norm, layer, **kw):
            y, state, norm = update(q, k, v, log_g, state, norm, layer, **kw)
            S = jax.lax.dynamic_index_in_dim(state, layer, 0, False)
            # not a cast there and back: XLA takes such a pair out
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
            return y, jax.lax.dynamic_update_index_in_dim(state, S, layer, 0), norm

        return step

    names = ("retention_decode_update", "retention_step_plain")
    sound = {name: getattr(pallas_retention, name) for name in names}
    for name in names:
        setattr(pallas_retention, name, rounded(sound[name]))
    try:
        yield
    finally:
        for name in names:
            setattr(pallas_retention, name, sound[name])


@contextlib.contextmanager
def scan_operands_float32():
    """The program with a prefill part's scan at float32 operands:
    inside this, ``retention_chunk_scan`` is handed ``q``, ``k`` and
    ``v`` as float32, so that every product of it (the state as read,
    ``phi(q)``, ``phi(k)``, the decayed weights, ``v`` times its decay)
    runs at float32 ``HIGHEST`` and not as one bfloat16 pass. ``k`` and
    ``v`` are still what bfloat16 activations made them. An engine BUILT
    inside it runs so."""
    import jax.numpy as jnp

    from odh_kubeflow_tpu.ops import pallas_retention

    sound = pallas_retention.retention_chunk_scan

    def scan(q, k, v, *rest, **kw):
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        return sound(q, k, v, *rest, **kw)

    pallas_retention.retention_chunk_scan = scan
    try:
        yield
    finally:
        pallas_retention.retention_chunk_scan = sound


def _rows(sample, length):
    """Each sampled request once: its padded row and the positions
    served."""
    import jax.numpy as jnp

    n_at = max(len(c.tokens) for c in sample)
    for c in sample:
        at = np.zeros(n_at, np.int32)
        at[: len(c.tokens)] = len(c.spec["prompt"]) - 1 + np.arange(len(c.tokens))
        seq = _padded(list(c.spec["prompt"]) + list(c.tokens), length)
        yield c, jnp.asarray(seq), jnp.asarray(at)


def _length(sample) -> int:
    longest = max(len(c.spec["prompt"]) + len(c.tokens) for c in sample)
    return -(-longest // PAD_TO) * PAD_TO


def check_against_reference(run, params, sample) -> np.ndarray:
    """Gaps at every served position of every sampled request: the
    reference's best logit less its logit of the token that was served
    (prefill in parts and decoding through the state against the
    reference's full forward in attention form)."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import core

    ref = core.load_module(run.roots, "reference", run.config["family"])
    config = run.config
    ref_logits = jax.jit(lambda p, seq, at: ref.logits(p, seq, config, at=at))
    gaps = []
    for c, seq, at in _rows(sample, _length(sample)):
        served = c.tokens
        lg = ref_logits(params, seq, at)[: len(served)]
        picked = jnp.take_along_axis(
            lg, jnp.asarray(served, jnp.int32)[:, None], axis=-1
        )[:, 0]
        gaps.append(np.asarray(jnp.max(lg, axis=-1) - picked))
        core.log(
            f"request {c.spec['id']}: prompt {len(c.spec['prompt'])}, "
            f"{len(served)} served ({len(set(served))} distinct); gap max "
            f"{gaps[-1].max():.4f}, mean {gaps[-1].mean():.6f}; "
            f"{int((gaps[-1] > 0).sum())} served tokens are not the "
            f"reference's best"
        )
    return np.concatenate(gaps)


def control_readings(run, params, sample, watched_tokens) -> dict:
    """What the check reads with the reference in a lower precision in
    the program's place: at every served position the token that
    precision puts first and its gap under the sound reference, and the
    first layer's state after ``watched_tokens``, carried in that
    precision, against the sound direct sum."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import core

    ref = core.load_module(run.roots, "reference", run.config["family"])
    config = run.config
    sound = jax.jit(lambda p, s, at: ref.logits(p, s, config, at=at))
    sound_state = reference_state(run, params, watched_tokens)
    rows = [
        (seq, at, sound(params, seq, at)[: len(c.tokens)])
        for c, seq, at in _rows(sample, _length(sample))
    ]
    out = {}
    for name, prec in (
        ("int8_activations", ref.Precision(act="int8")),
        ("bf16_state", ref.Precision(state="bf16")),
    ):
        low = jax.jit(lambda p, s, at, prec=prec: ref.logits(p, s, config, prec, at))
        gaps = []
        for seq, at, lg in rows:
            lg_low = low(params, seq, at)[: lg.shape[0]]
            picked = jnp.take_along_axis(
                lg, jnp.argmax(lg_low, axis=-1)[:, None], axis=-1
            )[:, 0]
            gaps.append(np.asarray(jnp.max(lg, axis=-1) - picked))
        gaps = np.concatenate(gaps)
        out[name] = {
            "served_logit_gap_max": float(gaps.max()),
            "served_logit_gap_mean": float(gaps.mean()),
            "retention_state_gap": float(_rel(
                reference_state(run, params, watched_tokens, prec), sound_state
            ).max()),
        }
        core.log(f"control {name}: {out[name]}")
    return out


def run(run) -> dict:
    import jax
    from jax.profiler import TraceAnnotation

    from benchmark.harness import core, stats, traffic

    mix, config, family = run.mix, run.config, run.family
    # the program's config first: a program that lacks the family stops
    # here, before 6 GB of weights are drawn
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
    watched = held_state(engine, cut_short)
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
    d = {k: c1[k] - c0[k] for k in c0}
    steps = max(d["decode_steps"], 1)
    v["n_slots"] = n_slots
    v["slot_occupancy"] = 100.0 * d["tokens_emitted"] / (steps * n_slots)
    v["decode_steps_per_call"] = d["decode_steps"] / max(d["decode_calls"], 1)
    v["kv_cache_gb"] = (cache_bytes["full"] + cache_bytes["window"]) / 1e9
    v["state_cache_gb"] = cache_bytes["state"] / 1e9
    v["memory_peak_gb"] = None if memory_peak is None else memory_peak / 1e9
    late = [(c.sent_at - c.due_at) * 1e3 for c in clients if c.sent_at is not None]
    if run.trace:
        v["live_slots"] = live_slots(clients, *v["traced"])
    core.log(
        f"{len(clients)} due, {sum(c.complete for c in clients)} complete, "
        f"{len(failed)} failed, {in_window} tokens in a window of "
        f"{t_rate_end - t_open:.3f} s ({v['serve_rate_mean']:.2f} a second in "
        f"the mean over its last seconds); the load generator sent "
        f"{stats.percentile(late, 95)[0]:.2f} ms late at the 95th percentile "
        f"(the latest {max(late):.2f}); counters over the window {d}; "
        f"cache {cache_bytes}"
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
        run, params, sample
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
        # the first layer's: below it the layers' own inputs differ
        state = _rel(
            family.to_symmetric_half(held), reference_state(run, params, tokens)
        )
        core.log(
            f"state: request {rid} was decoding at the close with "
            f"{len(tokens)} tokens in its state; its row of the engine's "
            f"retention state lies {state.max():.5f} of the reference's away "
            f"in the head furthest off (the heads' median "
            f"{np.median(state):.5f}; reference {time.monotonic() - t_ref:.1f} s)"
        )
    limits = run.cell["limits"]
    run.check("served_logit_gap_max", float(gaps.max()), limits["served_logit_gap_max"])
    run.check("served_logit_gap_mean", float(gaps.mean()), limits["served_logit_gap_mean"])
    run.check("retention_state_gap", float(state.max()), limits["retention_state_gap"])
    run.check("failed_requests", len(failed), 0)
    run.check("engine_failure", 0 if failure is None else 1, 0)
    run.check("stalled_at_close", int(stalled), 0)
    run.check("compiles_in_window", compiled_in_window, 0)
    return {
        "attempted": len(clients),
        "failed": len(failed),
        "memory_peak_bytes": memory_peak,
    }
