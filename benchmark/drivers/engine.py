"""Driver for open-loop serving through the program's ``DecodeEngine``
on its public streaming path (``submit(stream=True)`` /
``iter_tokens``): one dispatcher sends each request when it is due, and
each request is read by a client thread of its own that stamps every
token on the benchmark's clock.

Times are taken from when a request was DUE. A request that fails or is
refused counts with the time until the run gave it up. Above the knee
(a mix that is not drained) the rate is every token streamed in the
window over all of the window, which ends on the first token at or after
``--seconds`` (``close_on_a_token``). Once the window
has closed, a sample of the finished greedy requests (the longest among
them) is run through the plain reference, prompt and served tokens
together, and the gaps by which the served tokens' logits lie below the
reference's best (the widest, and the mean) are compared with their
limits."""

from __future__ import annotations

import math
import threading
import time

import numpy as np


class Client:
    """One request's stream, read on a thread of its own."""

    def __init__(self, spec: dict, due_at: float):
        self.spec = spec
        self.due_at = due_at
        self.sent_at = None
        self.times: list[float] = []
        self.tokens: list[int] = []
        self.error = None
        self.done = False
        self.req = None
        self.thread = None

    def send(self, engine, sampling: dict) -> None:
        self.sent_at = time.monotonic()
        greedy = self.spec["greedy"]
        try:
            self.req = engine.submit(
                self.spec["prompt"],
                max_tokens=self.spec["max_tokens"],
                temperature=0.0 if greedy else sampling["temperature"],
                top_p=0.0 if greedy else sampling["top_p"],
                stream=True,
            )
        except Exception as e:  # refused: counts as failed
            self.error = e
            self.done = True
            return
        self.thread = threading.Thread(target=self._read, daemon=True)
        self.thread.start()

    def _read(self) -> None:
        try:
            for tok in self.req.iter_tokens():
                self.times.append(time.monotonic())
                self.tokens.append(tok)
        except Exception as e:  # the stream's error, raised at its end
            self.error = e
        self.done = True

    @property
    def complete(self) -> bool:
        return (
            self.done
            and self.error is None
            and len(self.tokens) == self.spec["max_tokens"]
        )


def warm_up(engine, vocab: int, sampling: dict) -> None:
    """Every program the window can call: the greedy chunk alone, then
    each prompt bucket's prefill with the sampling chunk."""
    rng = np.random.default_rng(0)

    def go(n, **kw):
        r = engine.submit(
            rng.integers(1, vocab, size=n).tolist(), max_tokens=10, **kw
        )
        return r

    go(8).result(timeout=1200)
    prev = 0
    reqs = []
    for b in engine.prompt_buckets:
        reqs.append(go(
            (prev + b) // 2 + 1,
            temperature=sampling["temperature"], top_p=sampling["top_p"],
        ))
        prev = b
    for r in reqs:
        r.result(timeout=1200)


def close_on_a_token(clients, t_close: float, timeout_s: float):
    """Where the rate's window ends: at the first token streamed at or
    after ``t_close``. Tokens come in bursts of chunk x slots, so a
    window cut at ``t_close`` itself would count in steps of a burst
    (0.7 % of a run); this one is never shorter than ``--seconds``, holds
    every token streamed before its end, and all of the wait for the
    burst that ends it. If nothing is streamed for ``timeout_s`` the
    engine has stalled: the window then ends there, the rate pays for
    the silence, and the run is not correct."""
    give_up = t_close + timeout_s
    while time.monotonic() < give_up:
        if any(c.times and c.times[-1] >= t_close for c in clients):
            break
        time.sleep(0.005)
    time.sleep(0.05)  # a reader that stamped earlier may not have appended yet
    after = [
        c.times[i] for c in clients
        if (i := int(np.searchsorted(c.times, t_close, side="left"))) < len(c.times)
    ]
    return (min(after), False) if after else (give_up, True)


def live_kv_tokens(clients, t_lo: float, t_hi: float, points: int = 40) -> float:
    """Mean over the interval of the tokens held in the cache by the
    requests being decoded."""
    total = 0.0
    for t in np.linspace(t_lo, t_hi, points):
        for c in clients:
            if c.times and c.times[0] <= t <= c.times[-1]:
                total += len(c.spec["prompt"]) + np.searchsorted(c.times, t)
    return total / points


def run(run) -> dict:
    import jax
    from jax.profiler import TraceAnnotation
    from odh_kubeflow_tpu.models.engine import DecodeEngine

    from benchmark.harness import core, stats, traffic, weights
    from benchmark.reference import serve as ref_serve

    mix, config = run.mix, run.config
    seed31 = run.seed % (2**31 - 1)
    t_start = time.monotonic()
    with jax.default_device(run.devices[0]):
        params = weights.make_params(config, run.seed, run.family)
    jax.block_until_ready(params)
    t_weights = time.monotonic()
    engine = DecodeEngine(
        params, run.family.program_config(config), seed=seed31, **run.cell["program"]
    )
    reqs = traffic.requests(mix, config["vocab_size"], run.seed, run.seconds)
    try:
        warm_up(engine, config["vocab_size"], mix["sampling"])
        run.ready()
        core.log(
            f"set-up {run.values['setup_s']:.1f} s beside "
            f"{run.runtime_start_s:.1f} of runtime start "
            f"({t_start - run.t0:.1f} to the driver, "
            f"{t_weights - t_start:.1f} weights, "
            f"{time.monotonic() - t_weights:.1f} engine and warm-up); "
            f"{len(reqs)} requests due"
        )

        compiles_before = run.counters.snapshot()
        steps0, emitted0 = engine.decode_steps, engine.tokens_emitted
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
        steps1, emitted1 = engine.decode_steps, engine.tokens_emitted
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
    n_slots, chunk = engine.n_slots, engine.chunk
    del engine  # its cache goes with it; the weights stay for the reference

    judged = (
        clients if mix["drain"]
        else [c for c in clients if c not in cut_short]
    )
    failed = [c for c in judged if not c.complete]
    v = run.values
    # a request with no first token, or one that did not complete,
    # counts with the time until the run gave it up: the worst
    gave_up = lambda c: (t_end - c.due_at) * 1e3  # noqa: E731
    ttft = [
        stats.ttft_ms(c.due_at, c.times[0]) if c.times else gave_up(c)
        for c in clients
        if mix["drain"] or c.times
    ]
    tpot = [
        x for x in (
            stats.tpot_ms(c.times) if c.complete else gave_up(c)
            for c in judged
        ) if x is not None
    ]
    stall = [x for x in (stats.max_gap_ms(c.times) for c in judged) if x is not None]
    v["ttft_ms"], v["tpot_ms"], v["stall_ms"] = ttft, tpot, stall
    v["late_ms"] = [(c.sent_at - c.due_at) * 1e3 for c in clients]
    if mix["drain"]:
        v["ttft_p95_ms"] = stats.percentile(ttft, 95)[0]
    in_window = sum(
        int(np.searchsorted(c.times, t_rate_end, side="left")) for c in clients
    )
    if not mix["drain"]:
        # every token streamed in the window over ALL of the window
        v["serve_tokens_per_s"] = in_window / (t_rate_end - t_open)
    v["tokens_in_window"] = in_window
    v["slot_occupancy"] = 100.0 * (emitted1 - emitted0) / max(
        (steps1 - steps0) * n_slots, 1
    )
    v["decode_chunk"] = chunk
    v["n_slots"] = n_slots
    v["memory_peak_gb"] = None if memory_peak is None else memory_peak / 1e9
    if run.trace:
        v["live_kv_tokens"] = live_kv_tokens(clients, *v["traced"])
    core.log(
        f"{len(clients)} due, {sum(c.complete for c in clients)} complete, "
        f"{len(failed)} failed, {in_window} tokens in a window of "
        f"{t_rate_end - t_open:.3f} s"
    )

    # ---- the comparison: finished greedy requests against the reference
    greedy = [c for c in clients if c.complete and c.spec["greedy"]]
    rng = np.random.default_rng([run.seed, 3])
    greedy.sort(key=lambda c: len(c.spec["prompt"]) + len(c.tokens))
    sample = greedy[-1:] + [
        greedy[i] for i in rng.permutation(len(greedy) - 1)[: mix["check_requests"] - 1]
    ] if greedy else []
    t_ref = time.monotonic()
    gaps = ref_serve.all_gaps(
        params, config,
        [(c.spec["prompt"], c.tokens) for c in sample],
        ref_serve.padded_length(mix["prompt"]["max"] + mix["output"]["max"]),
    ) if sample else np.array([math.nan])
    core.log(
        f"reference {time.monotonic() - t_ref:.1f} s over {len(sample)} "
        f"requests, {sum(len(c.tokens) for c in sample)} served tokens"
    )
    limits = run.cell["limits"]
    # the widest gap swings from sample to sample: it is held against one
    # altered token. The mean over the served tokens is steady, and is
    # what computing in a lower precision moves
    run.check("served_logit_gap_max", float(gaps.max()), limits["served_logit_gap_max"])
    run.check("served_logit_gap_mean", float(gaps.mean()), limits["served_logit_gap_mean"])
    run.check("failed_requests", len(failed), 0)
    run.check("engine_failure", 0 if failure is None else 1, 0)
    run.check("stalled_at_close", int(stalled), 0)
    run.check("compiles_in_window", compiled_in_window, 0)
    return {
        "attempted": len(clients),
        "failed": len(failed),
        "memory_peak_bytes": memory_peak,
    }
