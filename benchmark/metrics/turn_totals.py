"""Reader for the running totals that ``DecodeEngine`` puts on every
``engine.turn`` span as it closes (``models/engine.py``, ``_close_turn``):
the slot-steps of its decode chunks by state (``slot_steps_live``,
``_ended``, ``_admitting``, ``_free_lane``, ``_free_no_work``), the
waiting requests' seconds (``wait_lane_s``, ``wait_slot_s``) and the
counts no driver snapshots (``parts``, ``parts_ahead``,
``prefill_tokens``, ``prefill_positions``; ``turn`` is the span's own).

The totals are cumulative, so a window's count is the difference of two
spans: the last turn that closed before the window opened and the last
that closed before it did. The window is ``program_spans``'s (it opens
where set-up ended and lasts ``run.seconds``) and is placed, and the
ring checked for a wrap, by that reader's own ``_window`` and
``_spans``: this one raises where that one does. Where the ring holds no
turn from before the window, the first turn inside it is the base and
that one turn is left out (logged). A program whose turns carry no
totals (the parent commit under these files, an engine beside a draft)
gives nothing to read.

Before any share is read the window's difference is held to the
ledger's identity: the five states sum to ``chunk x n_slots`` for every
decode chunk the window's turns dispatched (their ``engine.dispatch``
children). The five shares go to the log for the window's first
``RAMP_S`` seconds and for the rest, so the ramp is told from the
plateau, with the spans the window wrote against the ring's room.

``params``: ``num`` and ``den`` name the totals whose differences are
summed above and below the line; the value is their quotient in percent
(``complement``: 100 less that). A denominator of 0 returns nothing."""

from benchmark.harness import core

STATES = ("live", "ended", "admitting", "free_lane", "free_no_work")
STEPS = tuple(f"slot_steps_{s}" for s in STATES)
RAMP_S = 10.0
_KEY = "turn_totals.window"


def _diff(base, last):
    return {
        k: last.attrs[k] - base.attrs[k]
        for k in last.attrs if k in base.attrs and k != "held"
    }


def _shares(d):
    total = sum(d[k] for k in STEPS)
    return ", ".join(
        f"{s} {100.0 * d[k] / total:.2f}" for s, k in zip(STATES, STEPS)
    ) if total else "no chunk"


def _window_totals(run):
    """The window's difference of every total, or None."""
    spans_lib = core.load_module(run.roots, "metrics", "program_spans")
    _end = spans_lib._end
    lo, hi = spans_lib._window(run)
    spans = spans_lib._spans("engine.", lo)
    turns = sorted(
        (s for s in spans if s.name == "engine.turn" and STEPS[0] in s.attrs),
        key=_end,
    )
    inside = [t for t in turns if lo < _end(t) <= hi]
    if not inside:
        return None
    before = [t for t in turns if _end(t) <= lo]
    if before:
        base = before[-1]
    else:
        base, inside = inside[0], inside[1:]
        core.log(
            "turn totals: the ring holds no turn from before the window: "
            "its first turn is the base and is left out"
        )
        if not inside:
            return None
    d = _diff(base, inside[-1])

    # the identity the engine keeps chunk by chunk, on the window's sums
    ids = {t.span_id for t in inside}
    chunks = sum(
        s.name == "engine.dispatch" and s.parent_span_id in ids for s in spans
    )
    steps = sum(d[k] for k in STEPS)
    n_slots = run.values["n_slots"]
    chunk = run.values.get("decode_chunk")
    per_chunk, rest = divmod(steps, chunks) if chunks else (0, steps)
    if rest or per_chunk % n_slots or chunk not in (None, per_chunk // n_slots):
        raise RuntimeError(
            f"the five states sum to {steps} slot-steps over {chunks} chunks "
            f"of {n_slots} slots"
            + (f" x {chunk} steps" if chunk else "")
            + ": not a whole chunk's each, nothing is read"
        )

    ramp = [t for t in inside if _end(t) <= lo + RAMP_S]
    if ramp and ramp[-1] is not inside[-1]:
        core.log(
            f"turn totals: first {RAMP_S:.0f} s: "
            f"{_shares(_diff(base, ramp[-1]))}; the rest: "
            f"{_shares(_diff(ramp[-1], inside[-1]))}"
        )
    occupancy = run.values.get("slot_occupancy")
    core.log(
        f"turn totals: {len(inside)} turns, {chunks} chunks of "
        f"{per_chunk} slot-steps: {_shares(d)} %"
        + ("" if occupancy is None else
           f"; the driver's slot_occupancy {occupancy:.2f} % counts first "
           f"tokens too")
        + f"; parts {d['parts']} ({d['parts_ahead']} ahead), waited for the "
        f"lane {d['wait_lane_s']:.1f} and for a slot {d['wait_slot_s']:.1f} "
        f"request-seconds, held at the close {inside[-1].attrs['held']}"
    )
    from odh_kubeflow_tpu.utils import tracing

    ring = tracing.collector()
    written = sum(lo <= _end(s) <= hi for s in ring.spans_named(""))
    core.log(
        f"turn totals: the window wrote {written} spans into a ring of "
        f"{ring.capacity}"
    )
    return d


def read(run, params):
    if _KEY not in run.values:
        run.values[_KEY] = _window_totals(run)
    d = run.values[_KEY]
    if d is None:
        return None
    num = sum(d[k] for k in params["num"])
    den = sum(d[k] for k in params["den"])
    if not den:
        return None
    share = 100.0 * num / den
    return 100.0 - share if params.get("complement") else share
