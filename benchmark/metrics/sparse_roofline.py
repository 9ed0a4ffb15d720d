"""Roofline shares of a served stack whose layers attend only the keys an
indexer picks (``keye_vl``), from the device trace: the least time the
chip could take for the work (bytes over peak bytes/s, or operations
over peak FLOP/s where those take longer; ``families/<family>.py``
``decode_step_bytes``, ``routed_bytes``, ``index_scores_work`` and
``sparse_prefill_work``, from the configuration FILE and the program's
own counters OVER THE TRACED SECONDS: the positions its decode steps'
queries could see and attended, the expert banks they hit, the causal
pairs its prefill programs ran) over the device time of

- ``decode``: the whole decode program (``XLA Modules`` line), its steps
  counted by the engine, against every part of ``decode_step_bytes``:
  what the compaction and the gather cost shows here;
- ``index``: the decode steps' ``index_scores`` (one query a row: an
  output ``f32[slots,1,max_len]``) against the indexer's keys at every
  position a query could see;
- ``attend``: the decode steps' reading of the selected rows: the two
  gathers that take them out of the stacks (the operations whose result
  is ``bf16[slots * topk, width]``) AND the attention over what they wrote
  (``decode_attend`` on a one-layer stack ``[1,slots,topk,width]``),
  against the selected rows counted ONCE. The attention alone is no
  share of a roofline: it reads the gathered copy, which need not come
  from HBM (41.6 us a layer for 67 MB, 1.6 TB/s; my chip run, PR 42);
- ``moe``: the held experts' kernel, the decode step's calls of it only;
- ``prefill``: the parts' ``index_scores`` (several queries a row) and
  their attention under the selection (``decode_attend`` with the
  float32 scores among its operands) against the operations of both over
  the causal pairs of the parts that ran under a selection: every
  program but those that started a stream, which holds while a part is
  no wider than ``topk`` (else nothing is read).

A kernel's calls are told apart by the operand shapes in the event's
name, which is the whole HLO instruction (PERF.md section 3). A trace
without programs (the CPU rehearsal), a program without the counters or
a family without the work functions gives nothing to read."""

import re

from benchmark.harness import core
from benchmark.metrics.hybrid_roofline import _DECODE_TILE, _op_seconds, _programs


def read(run, params):
    r, cfg, v, family = run.reduced, run.config, run.values, run.family
    t = v.get("traced_counters")
    if (
        not r["modules"] or not t or t.get("sel_causal_rows") is None
        or not hasattr(family, "index_scores_work")
    ):
        return None
    kind = params["kernel"]
    bw, peak = run.peaks["hbm_bytes_per_s"], run.peaks["bf16_flops_per_s"]
    L, topk = cfg["num_hidden_layers"], cfg["sa_config"]["topk"]
    program = run.cell["program"]
    slots, max_len = program["n_slots"], program["max_len"]
    if kind == "prefill":
        if program["prefill_chunk"] > topk:
            return None  # a first part may then run under a selection too
        pairs = t["prefill_pairs"] - t["prefill_pairs_first"]
        if pairs <= 0:
            return None  # no part under a selection lies in the traced seconds
        scores = family.index_scores_work(cfg, pairs)
        attend = family.sparse_prefill_work(cfg, pairs)
        least = L * sum(
            max(w["flops"] / peak, w["bytes"] / bw) for w in (scores, attend)
        )
        seconds = _op_seconds(
            r["ops"], params["ops"][0], rf"f32\[1,(?!1,)\d+,{max_len}\]"
        ) + _op_seconds(r["ops"], params["ops"][1], rf"f32\[1,\d+,{max_len}\]")
        core.log(
            f"prefill: {seconds * 1e3:.2f} ms of scores and attention under a "
            f"selection over {pairs:g} pairs a layer; least {least * 1e3:.2f} ms"
        )
        return 100.0 * least / seconds
    hits = _programs(r["modules"], params["patterns"])
    if not hits:
        raise LookupError(
            f"no program matches {params['patterns']}: {sorted(r['modules'])}"
        )
    steps = sum(c for _, c in hits.values()) * v["decode_steps_per_call"]
    by_part = family.decode_step_bytes(
        cfg, t["decode_steps"], t["sel_causal_rows"], t["sel_attended_rows"],
        t["moe_experts_hit"],
    )
    if kind == "decode":
        seconds = sum(s for s, _ in hits.values())
        least = sum(by_part.values()) / bw
    elif kind == "index":
        seconds = _op_seconds(r["ops"], params["op"], rf"f32\[{slots},1,{max_len}\]")
        least = by_part["index"] / bw
    elif kind == "attend":
        width = cfg["num_key_value_heads"] * cfg["head_dim"]
        # the attention's operand is [1, slots, topk, width]; XLA writes the
        # gather's result with slots and positions as one dimension
        gathered = rf"bf16\[(?:(?:1,)?{slots},{topk}|{slots * topk}),{width}\]"
        seconds = _op_seconds(r["ops"], params["op"], gathered) + _op_seconds(
            r["ops"], params["gather"], " = " + gathered
        )
        least = by_part["rows"] / bw
    elif kind == "moe":
        k, held = cfg["num_experts_per_tok"], family.held(cfg)[1]
        rows = -(-slots * min(k, held) // _DECODE_TILE) * _DECODE_TILE
        rows += held * _DECODE_TILE
        seconds = _op_seconds(
            r["ops"], params["op"], rf"bf16\[{rows},{cfg['hidden_size']}\]"
        )
        least = family.routed_bytes(
            cfg, t["moe_experts_hit"] / max(t["decode_steps"], 1)
        ) / bw
    else:
        raise ValueError(f"unknown kernel {kind!r}")
    core.log(
        f"{kind}: {seconds / steps * 1e3:.3f} ms a step over {steps:g} steps; "
        f"memory bound {least * 1e3:.3f} ms" + (
            "; bytes a step " + ", ".join(
                f"{n} {b / 1e6:.1f} MB" for n, b in by_part.items()
            ) if kind == "decode" else ""
        )
    )
    return 100.0 * least * steps / seconds
