"""Roofline shares of a served SHARE's decode step (a family with held
experts and two kinds of cache), from the device trace: the least time
the chip could take to read the bytes the work needs
(``families/<family>.py`` ``decode_step_bytes``, from the configuration
FILE, the program's counter of expert banks hit and the live positions
the driver's clients held) over the device time of

- ``decode``: the whole decode program (``XLA Modules`` line);
- ``moe``: the held experts' kernel, the decode step's calls of it only
  (a prefill part calls it with more rows: another operand shape);
- ``attend``: the cache read, the decode step's calls of it only.

A kernel's calls are told apart by the operand shape in the event's
name (an event's name is the whole HLO instruction; PERF.md section 3).
A trace without programs (the CPU rehearsal) or a program without the
counters gives nothing to read."""

import re

from benchmark.harness import core

# ops/pallas_moe_local.py: a decode step's rows are sorted into tiles of
# 16, every held expert's group padded to one
_DECODE_TILE = 16


def read(run, params):
    r, cfg, v = run.reduced, run.config, run.values
    if not r["modules"] or v.get("moe_experts_hit_per_step") is None:
        return None
    hits = {
        k: sc for k, sc in r["modules"].items()
        if any(p in k for p in params["patterns"])
    }
    if not hits:
        raise LookupError(
            f"no program matches {params['patterns']}: {sorted(r['modules'])}"
        )
    steps = sum(c for _, c in hits.values()) * v["decode_chunk"]
    by_part = run.family.decode_step_bytes(
        cfg, v["moe_experts_hit_per_step"], v["live_full"], v["live_window"]
    )
    bw = run.peaks["hbm_bytes_per_s"]
    kind = params["kernel"]
    if kind == "decode":
        seconds = sum(s for s, _ in hits.values())
        least = sum(by_part.values()) / bw
    else:
        n_slots, k = v["n_slots"], cfg["num_experts_per_tok"]
        held = cfg["num_experts"]
        if kind == "moe":
            rows = -(-n_slots * min(k, held) // _DECODE_TILE) * _DECODE_TILE
            rows += held * _DECODE_TILE
            shape = rf"bf16\[{rows},{cfg['hidden_size']}\]"
            least = by_part["routed"] / bw
        elif kind == "attend":
            group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
            rows = -(-group // 16) * 16
            shape = (
                rf"bf16\[{n_slots},{cfg['num_key_value_heads']},{rows},"
                rf"{cfg['head_dim']}\]"
            )
            least = by_part["kv"] / bw
        else:
            raise ValueError(f"unknown kernel {kind!r}")
        rx = re.compile(params["op"])
        ops = {
            name: s for name, s in r["ops"].items()
            if rx.search(name.split(" = ")[0]) and re.search(shape, name)
        }
        if not ops:
            raise LookupError(
                f"no device operation is named {params['op']} with an "
                f"operand {shape}: "
                f"{[n[:160] for n in r['ops'] if rx.search(n.split(' = ')[0])][:4]}"
            )
        # events are clipped to the traced window; the programs counted
        # are the whole calls inside it: scale to them
        seconds = sum(ops.values())
    core.log(
        f"{kind}: {seconds / steps * 1e3:.3f} ms a step over {steps} steps; "
        f"memory bound {least * 1e3:.3f} ms; bytes a step "
        + ", ".join(f"{n} {b / 1e6:.1f} MB" for n, b in by_part.items())
    )
    return 100.0 * least * steps / seconds
