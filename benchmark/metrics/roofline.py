"""A kernel's share of its roofline: the least time the chip could take
for the work (operations over peak FLOP/s or bytes over peak bytes/s,
whichever is larger, from ``harness/counts.py``) over the device time of
the kernel's events in the trace. The events are found by the patterns
in the metric's own file; none found raises."""

import statistics

from benchmark.harness import core, counts, trace


def read(run, params):
    r, cfg, v = run.reduced, run.config, run.values
    kind = params["kernel"]
    if kind == "decode":
        hits = {
            k: sc for k, sc in r["modules"].items()
            if any(p in k for p in params["patterns"])
        }
        if not hits:
            raise LookupError(
                f"no program matches {params['patterns']}: {sorted(r['modules'])}"
            )
        seconds = sum(s for s, _ in hits.values())
        steps = sum(c for _, c in hits.values()) * v["decode_chunk"]
        least = counts.decode_step_bytes(
            cfg, v["live_kv_tokens"], run.family.step_weights_per_layer(cfg)
        ) / run.peaks["hbm_bytes_per_s"]
        core.log(f"decode: {seconds / steps * 1e3:.3f} ms a step over {steps} steps; memory bound {least * 1e3:.3f} ms")
        return 100.0 * least * steps / seconds
    # training kernels: the traced window holds window / step-time steps
    steps = r["window_s"] / (statistics.median(v["step_ms"]) / 1e3)
    seconds = trace.op_seconds(r, params["patterns"]) / steps
    L = cfg["num_hidden_layers"]
    if kind == "flash":
        b = counts.flash_bound_s(
            cfg, v["rows_per_step"], run.mix["sequence"],
            v["segment_pairs_per_step"], run.peaks,
        )
    else:
        raise ValueError(f"unknown kernel {kind!r}")
    core.log(f"{kind}: {seconds * 1e3:.2f} ms a step; {b['bound']} bound {L * b['seconds'] * 1e3:.2f} ms")
    return 100.0 * L * b["seconds"] / seconds
