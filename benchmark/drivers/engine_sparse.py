"""Driver for open-loop serving of a stack whose every layer attends only
the keys a learned INDEXER picks (``keye_vl``: a third stack in the
cache, a selection a query) through the program's ``DecodeEngine``.

``drivers/engine_gdn.py`` does the same for a stack that keeps a state
beside keys and values, and everything of the accepted drivers that does
not name their families' keys is imported, not copied: the clients, the
warm-up and the closing burst (``drivers/engine.py``), the mean rate
(``drivers/engine_hybrid.py``), the padded length
(``drivers/engine_share.py``). Their ``run`` reads a recurrent state and
``engine_share``'s check fills no selection leaf, so the window stands
here a SIXTH time (PERF.md section 7: one function of
``drivers/engine.py``, which only a ``benchmark`` PR may make). What
differs:

- the counters: what the decode steps' queries could see and what they
  attended (``sel_causal_rows``, ``sel_attended_rows``: the program's
  own, a chunk at a time) and the causal pairs its prefill programs ran
  (``prefill_pairs``; those of the programs that started a stream are
  ``prefill_pairs_first``), over the window and, for the roofline
  shares, over the TRACED seconds alone;
- the check is of the logits, of the routing AND of the selection: the
  program's cached forward over the sampled requests in the engine's
  parts fills ``index_topk`` beside ``moe_topk``, and at sampled (query,
  layer) pairs past ``topk`` the share of the program's kept positions
  that are not the reference's is ``selection_differs_share``. That
  share is mostly the bfloat16 ACTIVATIONS' (the indexer's queries and
  keys differ from the float32 reference's before any score is made), so
  the same pass also keeps each sampled query's ``q^I`` and ``w`` as the
  scores took them (the leaf ``index_inputs``) and the check computes
  the selection AGAIN from them and the cached keys, in float32 with
  ``lax.top_k``: ``selection_inexact_share`` is the share of the
  program's kept positions that this is not, and holds the scores'
  accumulation, the threshold, the tie rule and the compaction apart
  from everything above them;
- what the ENGINE holds in the third stack: stopped with its slots as
  they are, the indexer keys of the stream served the most tokens, first
  layer, against the reference's ``k^I`` position by position
  (``index_key_gap``: the position furthest off; it holds the splice,
  the admission in parts and the decode steps' writes to that stack).

``selection_is_the_last_keys`` and ``index_scores_rounded_to_bf16`` are
controls: the PROGRAM with its selection replaced by a window, and with
its indexer's scores rounded to bfloat16 where they are written.
``control_readings`` is the third's (the reference with int8 activations
put in the program's place). The limits were calibrated with all three
(PERF.md) and the tests hold them to all three. No run of the benchmark
calls any of them."""

from __future__ import annotations

import contextlib
import math
import threading
import time

import numpy as np

from benchmark.drivers.engine import Client, close_on_a_token, warm_up
from benchmark.drivers.engine_hybrid import RATE_OVER_S, mean_rate
from benchmark.drivers.engine_share import PAD_TO

COUNTERS = (
    "decode_steps", "decode_calls", "tokens_emitted", "moe_local_assignments",
    "moe_experts_hit", "moe_dropped", "prefill_calls", "prefill_tokens",
    "prefill_positions", "prefill_pairs", "prefill_pairs_first",
    "sel_causal_rows", "sel_attended_rows",
)
# queries a sampled request's selection is compared at
KEEP = 48


def counters(engine) -> dict:
    return {name: getattr(engine, name) for name in COUNTERS}


def _padded(tokens, step: int) -> np.ndarray:
    n = len(tokens)
    seq = np.zeros(-(-n // step) * step, np.int32)
    seq[:n] = tokens
    return seq


def held_index_keys(engine, clients):
    """Of a STOPPED engine: the request that was decoding and had been
    served the most tokens, as ``(tokens its cache has taken in, the
    first layer's indexer keys [positions, d_i] of its slot, the
    request's id)``; None where nothing was decoding. A stream's cache
    has taken in its prompt and every token served but the last."""
    live = [
        c for c in clients
        if c.req is not None and c.req.tokens and not c.req.complete
    ]
    if not live:
        return None
    c = max(live, key=lambda c: len(c.req.tokens))
    tokens = list(c.spec["prompt"]) + list(c.req.tokens)[:-1]
    held = engine.slot_state(c.req.slot, "indexed")["ik"][0]  # [d_i, S_max]
    return tokens, np.asarray(held, np.float32).T[: len(tokens)], c.spec["id"]


def reference_index_keys(run, params, tokens, prec=None) -> np.ndarray:
    """The reference's ``k^I`` [len(tokens), d_i] of the first layer."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import core

    ref = core.load_module(run.roots, "reference", run.config["family"])
    config = run.config
    seq = _padded(tokens, PAD_TO)
    # only the first layer: the jitted call takes no other weight
    first = {
        "embed": params["embed"],
        "layers": jax.tree_util.tree_map(lambda a: a[:1], params["layers"]),
    }
    keys = jax.jit(lambda p, s: ref.index_keys(p, s, config, prec or ref.SOUND))
    return np.asarray(keys(first, jnp.asarray(seq)))[: len(tokens)]


def key_gap(held, want) -> np.ndarray:
    """Per position, the distance of a held key from the reference's
    over the reference's norm."""
    return np.linalg.norm(held - want, axis=-1) / np.maximum(
        np.linalg.norm(want, axis=-1), 1e-12
    )


@contextlib.contextmanager
def _scores_through(change):
    """The program with ``change`` applied to its indexer's scores, the
    kernel's and the plain form's alike. An engine BUILT AND RUN inside
    it runs so (its programs are traced at their first call)."""
    from odh_kubeflow_tpu.ops import sparse_attention as sa

    names = ("index_scores", "index_scores_plain")
    sound = {name: getattr(sa, name) for name in names}
    for name in names:
        setattr(sa, name, lambda *a, _f=sound[name], **kw: change(_f(*a, **kw)))
    try:
        yield
    finally:
        for name in names:
            setattr(sa, name, sound[name])


def selection_is_the_last_keys():
    """The program with a WINDOW in the selection's place: every query
    keeps the last ``topk`` positions it can see (its indexer's scores
    are the positions themselves). It reads as many keys as a sound run
    and other ones: the control that says the check sees WHICH keys are
    read."""
    import jax.numpy as jnp

    return _scores_through(lambda scores: jnp.broadcast_to(
        jnp.arange(scores.shape[-1], dtype=scores.dtype), scores.shape
    ))


def index_scores_rounded_to_bf16():
    """The program with its indexer's scores rounded to bfloat16 where
    they are written: the least an accumulation in bfloat16 does."""
    import jax

    # not a cast there and back: XLA takes such a pair out
    return _scores_through(lambda scores: jax.lax.reduce_precision(
        scores, exponent_bits=8, mantissa_bits=7
    ))


def _check_rows(run, sample):
    """Each sampled request once: its row padded to whole parts, the
    served positions, and the queries past ``topk`` whose selection is
    compared (evenly spread, the last served position among them)."""
    part = run.cell["program"]["prefill_chunk"]
    topk = run.config["sa_config"]["topk"]
    rows = []
    for c in sample:
        prompt, served = c.spec["prompt"], c.tokens
        n = len(prompt) + len(served)
        seq = _padded(list(prompt) + list(served), math.lcm(part, 128))
        at = len(prompt) - 1 + np.arange(len(served))
        keep = np.unique(np.linspace(topk, n - 1, KEEP).astype(np.int64)) if (
            n - 1 > topk
        ) else np.zeros(0, np.int64)
        rows.append((c, seq, n, at.astype(np.int32), keep.astype(np.int32)))
    return rows


def _fixed(a, size):
    """``a`` padded with its first entry to ``size`` (one compiled shape
    a row length)."""
    out = np.full(size, a[0] if len(a) else 0, np.int32)
    out[: len(a)] = a
    return out


def _selection_differs(got, ids, valid) -> tuple:
    """(kept positions of ``got`` [L, n, topk] (ascending, -1 past the
    count) that are not among the reference's ``ids`` where ``valid``,
    positions compared)."""
    differ = total = 0
    for layer in range(got.shape[0]):
        for j in range(got.shape[1]):
            mine = got[layer, j][got[layer, j] >= 0]
            theirs = ids[layer, j][valid[layer, j]]
            differ += int((~np.isin(mine, theirs)).sum())
            total += len(mine)
    return differ, total


def check_against_reference(run, params, sample, program_cfg, prec=None):
    """Gaps at every served position of every sampled request, the share
    of (token, layer) pairs routed otherwise than the reference and the
    share of the sampled queries' kept positions that are not the
    reference's. With ``prec`` the REFERENCE in that lower precision
    stands in the program's place (``control_readings``)."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import core
    from odh_kubeflow_tpu.models.generate import family_forward, init_cache

    ref = core.load_module(run.roots, "reference", run.config["family"])
    config = run.config
    part = run.cell["program"]["prefill_chunk"]
    sa_config = config["sa_config"]
    topk, Hi, di = (
        sa_config["topk"], sa_config["indexer_num_heads"],
        sa_config["indexer_head_dim"],
    )
    n_at = max(len(c.tokens) for c in sample)
    _, fwd = family_forward(program_cfg)
    # the engine's own (the tests' tiny cell states float32)
    cache_dtype = jnp.dtype(run.cell["program"].get("cache_dtype", "bfloat16"))

    @jax.jit
    def ref_pass(params, seq, at, keep):
        return ref.logits_and_selection(params, seq, config, at=at, keep=keep)

    low_pass = None if prec is None else jax.jit(
        lambda params, seq, at, keep: ref.logits_and_selection(
            params, seq, config, prec, at=at, keep=keep
        )
    )

    @jax.jit
    def program_pass(params, seq, at, keep):
        length = seq.shape[0]
        cache = init_cache(program_cfg, 1, length, cache_dtype, widest_part=part)
        L, k = config["num_hidden_layers"], config["num_experts_per_tok"]
        cache["moe_topk"] = jnp.zeros((L, 1, length, k), jnp.int32)
        cache["index_topk"] = jnp.full((L, 1, length, min(topk, length)), -1, jnp.int32)
        cache["index_inputs"] = jnp.zeros((L, 1, length, Hi * (di + 1)), jnp.float32)

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
        def again(layer):
            """The kept queries' selection from what the scores took."""
            inputs, keys = layer  # [n, Hi * (di + 1)], [di, length]
            q = inputs[:, : Hi * di].reshape(-1, Hi, di)
            s = jnp.einsum(
                "qhd,dk->hqk", q, keys.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )
            scores = jnp.einsum(
                "hqk,qh->qk", jnp.maximum(s, 0.0), inputs[:, Hi * di:],
                precision=jax.lax.Precision.HIGHEST,
            )
            scores = jnp.where(scores == 0.0, 0.0, scores)  # no -0.0
            causal = jnp.arange(length)[None, :] <= keep[:, None]
            _, ids = jax.lax.top_k(
                jnp.where(causal, scores, -jnp.inf), min(topk, length)
            )
            return jnp.sort(ids, axis=-1)

        own = jax.lax.map(
            again, (cache["index_inputs"][:, 0][:, keep], cache["ik"][:, 0])
        )
        return (
            cache["moe_topk"][:, 0], best, cache["index_topk"][:, 0][:, keep], own
        )

    gaps, differ, pairs, sel_differ, sel_total, inexact = [], 0, 0, 0, 0, 0
    everything = np.ones((config["num_hidden_layers"], KEEP, topk), bool)
    for c, seq, n, at, keep in _check_rows(run, sample):
        served = np.asarray(c.tokens)
        seq_d, at_d = jnp.asarray(seq), jnp.asarray(_fixed(at, n_at))
        keep_d = jnp.asarray(_fixed(keep, KEEP))
        lg, ref_top, ids, valid = ref_pass(params, seq_d, at_d, keep_d)
        lg = lg[: len(served)]
        if low_pass is None:
            got_top, own, got_sel, again = program_pass(params, seq_d, at_d, keep_d)
            chosen = served
            inexact += _selection_differs(
                np.asarray(got_sel)[:, : len(keep)],
                np.asarray(again)[:, : len(keep)], everything[:, : len(keep)],
            )[0]
        else:
            # the lower precision chooses the tokens, routes and selects
            lg_low, got_top, low_ids, _ = low_pass(params, seq_d, at_d, keep_d)
            chosen = own = np.asarray(jnp.argmax(lg_low[: len(served)], axis=-1))
            got_sel = jnp.sort(low_ids, -1)  # past topk every kept one is valid
        picked = jnp.take_along_axis(
            lg, jnp.asarray(chosen, jnp.int32)[:, None], axis=-1
        )[:, 0]
        gaps.append(np.asarray(jnp.max(lg, axis=-1) - picked))
        own = np.asarray(own)[: len(served)]
        off = np.flatnonzero(gaps[-1] > 0)
        same = jnp.all(
            jnp.sort(got_top[:, :n], -1) == jnp.sort(ref_top[:, :n], -1), -1
        )
        differ += int(same.size - jnp.sum(same))
        pairs += int(same.size)
        d, t = _selection_differs(
            np.asarray(got_sel)[:, : len(keep)], np.asarray(ids)[:, : len(keep)],
            np.asarray(valid)[:, : len(keep)],
        )
        sel_differ, sel_total = sel_differ + d, sel_total + t
        core.log(
            f"request {c.spec['id']}: prompt {len(c.spec['prompt'])}, "
            f"{len(served)} served; gap max {gaps[-1].max():.4f}, mean "
            f"{gaps[-1].mean():.6f}; {len(off)} served tokens are not the "
            f"reference's best, and the program's own forward (no engine) "
            f"chooses {int((own[off] == served[off]).sum())} of them; it differs "
            f"from the engine at {int((own != served).sum())} of {len(served)}; "
            f"of {t} kept positions at {len(keep)} queries a layer {d} are not "
            f"the reference's ({inexact} so far are not its own inputs')"
        )
    core.log(
        f"routing: {differ} of {pairs} (token, layer) pairs chose other experts "
        f"than the reference; selection: {sel_differ} of {sel_total} kept "
        f"positions are not the reference's"
    )
    return {
        "gaps": np.concatenate(gaps),
        "routing_differs_share": 100.0 * differ / max(pairs, 1),
        "selection_differs_share": (
            100.0 * sel_differ / sel_total if sel_total else math.nan
        ),
        # the reference in a lower precision takes the sound one's inputs:
        # what its accumulation alone moves is all of what it moves
        "selection_inexact_share": (
            math.nan if not sel_total
            else 100.0 * inexact / sel_total if prec is None
            else 100.0 * sel_differ / sel_total if prec.index != "f32"
            else 0.0
        ),
    }


def control_readings(run, params, sample, watched_tokens) -> dict:
    """What the check reads with the reference in each lower precision
    in the program's place: the gaps under the sound reference of the
    tokens it puts first, the routing and the selection it differs by,
    and its first layer's indexer keys over ``watched_tokens`` against
    the sound one's."""
    from benchmark.harness import core

    ref = core.load_module(run.roots, "reference", run.config["family"])
    want = reference_index_keys(run, params, watched_tokens)
    out = {}
    for name, prec in (
        ("int8_activations", ref.Precision(act="int8")),
        ("bf16_index", ref.Precision(index="bf16")),
    ):
        r = check_against_reference(run, params, sample, None, prec)
        out[name] = {
            "served_logit_gap_max": float(r["gaps"].max()),
            "served_logit_gap_mean": float(r["gaps"].mean()),
            "routing_differs_share": r["routing_differs_share"],
            "selection_differs_share": r["selection_differs_share"],
            "selection_inexact_share": r["selection_inexact_share"],
            "index_key_gap": float(key_gap(
                reference_index_keys(run, params, watched_tokens, prec), want
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
    # here, before the weights are drawn
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
        # a prompt admitted in parts: two whole parts and a final one,
        # the later ones under the selection
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
        c0 = counters(engine)
        t_open = time.monotonic()
        t_close = t_open + run.seconds
        clients = [Client(r, t_open + r["due_s"]) for r in reqs]

        tracer = None
        if run.trace:
            def traced():
                time.sleep(max(run.seconds - mix["trace_s"], 0))
                jax.profiler.start_trace(run.trace_dir)
                a, before = time.monotonic(), counters(engine)
                with TraceAnnotation("bench.window"):
                    time.sleep(max(t_close - time.monotonic(), 0.5))
                after = counters(engine)
                run.values["traced"] = (a, time.monotonic())
                run.values["traced_counters"] = {
                    k: after[k] - before[k] for k in before
                }
                jax.profiler.stop_trace()

            tracer = threading.Thread(target=traced, daemon=True)
            tracer.start()

        for c in clients:
            with TraceAnnotation("loadgen.wait"):
                time.sleep(max(c.due_at - time.monotonic(), 0))
            with TraceAnnotation("loadgen.submit"):
                c.send(engine, mix["sampling"])
        time.sleep(max(t_close - time.monotonic(), 0))
        c1 = counters(engine)
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
    watched = held_index_keys(engine, cut_short)
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
    v["moe_experts_hit_per_step"] = d["moe_experts_hit"] / steps
    v["moe_experts_hit_share"] = 100.0 * d["moe_experts_hit"] / (
        steps * config["num_hidden_layers"] * family.held(config)[1]
    )
    # how sparse the traffic really is: of the positions the decode
    # steps' queries could see, those they attended
    v["selected_share"] = 100.0 * d["sel_attended_rows"] / max(d["sel_causal_rows"], 1)
    v["kv_cache_gb"] = cache_bytes["indexed"] / 1e9
    v["memory_peak_gb"] = None if memory_peak is None else memory_peak / 1e9
    late = [(c.sent_at - c.due_at) * 1e3 for c in clients if c.sent_at is not None]
    core.log(
        f"{len(clients)} due, {sum(c.complete for c in clients)} complete, "
        f"{len(failed)} failed, {in_window} tokens in a window of "
        f"{t_rate_end - t_open:.3f} s ({v['serve_rate_mean']:.2f} a second in "
        f"the mean over its last seconds); the load generator sent "
        f"{stats.percentile(late, 95)[0]:.2f} ms late at the 95th percentile "
        f"(the latest {max(late):.2f}); counters over the window {d}; over the "
        f"traced seconds {v.get('traced_counters')}; cache {cache_bytes}"
    )

    # ---- the comparison: finished greedy requests against the reference
    greedy = [c for c in clients if c.complete and c.spec["greedy"]]
    rng = np.random.default_rng([run.seed, 3])
    greedy.sort(key=lambda c: len(c.spec["prompt"]) + len(c.tokens))
    sample = greedy[-1:] + [
        greedy[i] for i in rng.permutation(len(greedy) - 1)[: mix["check_requests"] - 1]
    ] if greedy else []
    t_ref = time.monotonic()
    nan = np.array([math.nan])
    read = check_against_reference(run, params, sample, program_cfg) if sample else {
        "gaps": nan, "routing_differs_share": math.nan,
        "selection_differs_share": math.nan, "selection_inexact_share": math.nan,
    }
    core.log(
        f"reference {time.monotonic() - t_ref:.1f} s over {len(sample)} "
        f"requests (contexts "
        f"{[len(c.spec['prompt']) + len(c.tokens) for c in sample]}), "
        f"{sum(len(c.tokens) for c in sample)} served tokens"
    )
    gap = nan
    if watched is not None:
        tokens, held, rid = watched
        gap = key_gap(held, reference_index_keys(run, params, tokens))
        core.log(
            f"index keys: request {rid} was decoding at the close with "
            f"{len(tokens)} positions in its cache; its slot's indexer keys lie "
            f"{gap.max():.5f} of the reference's away at the position furthest "
            f"off (the positions' median {np.median(gap):.5f})"
        )
    gaps, limits = read["gaps"], run.cell["limits"]
    run.check("served_logit_gap_max", float(gaps.max()), limits["served_logit_gap_max"])
    run.check("served_logit_gap_mean", float(gaps.mean()), limits["served_logit_gap_mean"])
    for name in (
        "routing_differs_share", "selection_differs_share", "selection_inexact_share"
    ):
        run.check(name, read[name], limits[name])
    run.check("index_key_gap", float(gap.max()), limits["index_key_gap"])
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
