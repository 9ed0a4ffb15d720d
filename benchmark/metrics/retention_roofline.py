"""Roofline shares of a served stack that keeps no keys and values but a
power-retention state a layer a slot (``brumby``), from the device
trace: the least time the chip could take for the work (bytes over peak
bytes/s, or operations over peak FLOP/s where those take longer;
``families/<family>.py`` ``decode_step_bytes`` and
``retention_scan_work``, from the configuration FILE, the program's
counters and the slots the driver's clients held) over the device time
of

- ``decode``: the whole decode program (``XLA Modules`` line), its steps
  counted by the engine (``decode_steps`` over ``decode_calls``),
  against every part of ``decode_step_bytes``;
- ``update``: the decode step's state update
  (``retention_decode_update``) against the live slots' state read and
  written (the kernel walks the idle slots' too: that is its own);
- ``scan``: the prefill's scan (``retention_chunk_scan``): every whole
  prefill program in the window runs it once a layer over the positions
  its name ends in, padding included.

A kernel's events are found by its name in the event's name, which is
the whole HLO instruction (PERF.md section 3). A trace without programs
(the CPU rehearsal), a program without the counters or a family without
the work functions gives nothing to read."""

from benchmark.harness import core
from benchmark.metrics.hybrid_roofline import _op_seconds, _programs


def read(run, params):
    r, cfg, v, family = run.reduced, run.config, run.values, run.family
    if (
        not r["modules"] or v.get("decode_steps_per_call") is None
        or not hasattr(family, "retention_scan_work")
    ):
        return None
    kind = params["kernel"]
    bw, peak = run.peaks["hbm_bytes_per_s"], run.peaks["bf16_flops_per_s"]
    layers = cfg["num_hidden_layers"]
    if kind == "scan":
        least = 0.0
        for name, (_, calls) in _programs(r["modules"], params["patterns"]).items():
            work = family.retention_scan_work(
                cfg, int(name.rsplit("_", 1)[1].split("(")[0])
            )
            least += calls * layers * max(work["flops"] / peak, work["bytes"] / bw)
        if not least:
            return None  # no prefill lies wholly inside the traced window
        seconds = _op_seconds(r["ops"], params["op"])
        core.log(f"scan: {seconds * 1e3:.2f} ms of scan; least {least * 1e3:.2f} ms")
        return 100.0 * least / seconds
    hits = _programs(r["modules"], params["patterns"])
    if not hits:
        raise LookupError(
            f"no program matches {params['patterns']}: {sorted(r['modules'])}"
        )
    steps = sum(c for _, c in hits.values()) * v["decode_steps_per_call"]
    by_part = family.decode_step_bytes(cfg, v["live_slots"])
    if kind == "decode":
        seconds = sum(s for s, _ in hits.values())
        least = sum(by_part.values()) / bw
    elif kind == "update":
        seconds = _op_seconds(r["ops"], params["op"])
        least = by_part["state"] / bw
    else:
        raise ValueError(f"unknown kernel {kind!r}")
    core.log(
        f"{kind}: {seconds / steps * 1e3:.3f} ms a step over {steps:g} steps "
        f"at {v['live_slots']:.2f} live slots; memory bound {least * 1e3:.3f} ms"
        + (
            "; bytes a step " + ", ".join(
                f"{n} {b / 1e6:.1f} MB" for n, b in by_part.items()
            ) if kind == "decode" else ""
        )
    )
    return 100.0 * least * steps / seconds
