"""Roofline shares of a served stack that keeps recurrent state beside
its keys and values (``granitemoehybrid``), from the device trace: the
least time the chip could take for the work (bytes over peak bytes/s,
or operations over peak FLOP/s where those take longer;
``families/<family>.py`` ``decode_step_bytes`` and ``ssd_scan_work``,
from the configuration FILE, the program's counters and what the
driver's clients held) over the device time of

- ``decode``: the whole decode program (``XLA Modules`` line), its steps
  counted by the engine (``decode_steps`` over ``decode_calls``);
- ``moe``: the held experts' kernel, the decode step's calls of it only
  (a prefill part calls it with more rows: another operand shape);
- ``ssm``: the decode step's state update (``ssm_decode_update``): the
  live slots' state read and written;
- ``ssd``: the prefill's scan (``ssd_chunk_scan``): every whole prefill
  program in the window runs it once a Mamba-2 layer over its bucket's
  positions, padding included.

A kernel's events are found by its name in the event's name, which is
the whole HLO instruction (PERF.md section 3). A trace without programs
(the CPU rehearsal) or a program without the counters gives nothing to
read."""

import re

from benchmark.harness import core

# ops/pallas_moe_local.py: a decode step's rows are sorted into tiles of
# 16, every held expert's group padded to one
_DECODE_TILE = 16


def _programs(modules, patterns):
    return {
        k: sc for k, sc in modules.items() if any(p in k for p in patterns)
    }


def _op_seconds(ops, op, shape=None):
    rx = re.compile(op)
    hits = {
        name: s for name, s in ops.items()
        if rx.search(name.split(" = ")[0])
        and (shape is None or re.search(shape, name))
    }
    if not hits:
        raise LookupError(
            f"no device operation is named {op} with an operand {shape}: "
            f"{[n[:160] for n in ops if rx.search(n.split(' = ')[0])][:4]}"
        )
    return sum(hits.values())


def read(run, params):
    r, cfg, v, family = run.reduced, run.config, run.values, run.family
    if not r["modules"] or v.get("decode_steps_per_call") is None:
        return None
    kind = params["kernel"]
    bw, peak = run.peaks["hbm_bytes_per_s"], run.peaks["bf16_flops_per_s"]
    if kind == "ssd":
        # each whole prefill program: one scan a Mamba-2 layer over the
        # positions its name ends in
        n_mamba, _ = family.kinds(cfg)
        least = 0.0
        for name, (_, calls) in _programs(r["modules"], params["patterns"]).items():
            work = family.ssd_scan_work(cfg, int(name.rsplit("_", 1)[1].split("(")[0]))
            least += calls * n_mamba * max(work["flops"] / peak, work["bytes"] / bw)
        if not least:
            return None  # no prefill lies wholly inside the traced window
        seconds = _op_seconds(r["ops"], params["op"])
        core.log(f"ssd: {seconds * 1e3:.2f} ms of scan; least {least * 1e3:.2f} ms")
        return 100.0 * least / seconds
    hits = _programs(r["modules"], params["patterns"])
    if not hits:
        raise LookupError(
            f"no program matches {params['patterns']}: {sorted(r['modules'])}"
        )
    steps = sum(c for _, c in hits.values()) * v["decode_steps_per_call"]
    by_part = family.decode_step_bytes(
        cfg, v["moe_experts_hit_per_step"], v["live_full"], v["live_slots"]
    )
    if kind == "decode":
        seconds = sum(s for s, _ in hits.values())
        least = sum(by_part.values()) / bw
    elif kind == "moe":
        k, held = cfg["num_experts_per_tok"], family.held(cfg)[1]
        rows = -(-v["n_slots"] * min(k, held) // _DECODE_TILE) * _DECODE_TILE
        rows += held * _DECODE_TILE
        seconds = _op_seconds(
            r["ops"], params["op"], rf"bf16\[{rows},{cfg['hidden_size']}\]"
        )
        least = by_part["routed"] / bw
    elif kind == "ssm":
        seconds = _op_seconds(r["ops"], params["op"])
        least = by_part["state"] / bw
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
