"""Reader for what the PROGRAM recorded about itself: the spans that
``models/engine.py`` and ``train/trainer.py`` write into the process's
span ring (``odh_kubeflow_tpu.utils.tracing.collector()``), read in the
driver's process after the run, on the clock the drivers stamp with
(``time.monotonic()``, a span's ``start_mono``).

Serving metrics are bounded to the measured window: it opens where
set-up ended (``run.ready()``: ``run.t0`` + ``setup_s`` +
``runtime_start_s``; the driver opens its window in the same
millisecond and submits nothing between the two) and lasts
``run.seconds``. The driver hands over no stamp of the window's open,
so that placement is CHECKED against the two things it does hand over,
and the reader raises where they disagree: the window has to close
where the driver's tracer saw it close (``run.values["traced"][1]``:
within ``TRACED_AFTER_CLOSE_S`` after it, where the tracer slept to the
close),
and (``request_phase`` of ``first_token``) the requests the ring holds
for the window have to be the driver's, one to one, each with
``queued + first_token`` equal to the driver's own time to first token
less its lateness within ``params["ttft_tolerance_ms"]``. A window out
of place by a driver's later change pairs other requests and reads a
turn (0.3 s) off, not milliseconds. Training takes the last
``run.values["steps"]`` ``trainer.step`` spans.

The driver runs the traced cells of the PARENT commit with these files
laid over it: a program whose collector cannot be read by name, or that
records no such spans, gives nothing to read, and the reader returns
nothing. A ring that may have wrapped since the window opened is an
error: spans of the window are gone, and a share or a percentile of
the rest would be wrong. It cannot have while it holds fewer spans that
ended since then than it has room for.

``params["what"]``:

- ``request_phase``: the ``stat``-th percentile (ms) of the spans named
  ``params["span"]`` (``engine.request.queued``, ``.first_token``) over
  the requests submitted in the window that reached their first token;
- ``turn_max``: the longest ``engine.turn`` begun in the window (ms);
  its phases go to the log, and a turn whose phases leave more than
  ``params["tiling_tolerance_ms"]`` of it uncovered is an error;
- ``host_share``: the share (%) of the window the loop thread spent in a
  turn and not in its ``engine.fetch``: admit + dispatch + emit + the
  turn's self time, neither blocked on the device nor idle;
- ``step_host``: the ``stat``-th percentile (ms) of ``trainer.step``."""

import bisect
import collections

from benchmark.harness import core, stats

# the driver's tracer stamps ``traced`` = (a, b): a as ``start_trace``
# returns, b after sleeping to the close of the window, or for 0.5 s
# where the close is nearer than that. Having slept to the close it
# wakes within milliseconds of it (0.7-1.5 ms on the chip); the room
# left is for a wake-up that a stall of the machine delays
TRACED_AFTER_CLOSE_S = 2.0


def _spans(prefix, since):
    """The program's finished spans named ``prefix``*, oldest first;
    ``since`` (monotonic) is where the reader's window opens."""
    from odh_kubeflow_tpu.utils import tracing

    ring = tracing.collector()
    if not hasattr(ring, "spans_named"):
        return []  # the parent commit under these files: see above
    held = ring.spans_named("")
    recent = sum(_end(s) >= since for s in held)
    if recent >= ring.capacity:
        raise RuntimeError(
            f"{recent} spans ended since the window opened and the ring "
            f"holds {ring.capacity}: it may have wrapped, nothing is read"
        )
    return [s for s in held if s.name.startswith(prefix) and s.start_mono > 0]


def _end(s):
    return s.start_mono + s.duration


def _window(run):
    v = run.values
    lo = run.t0 + v["setup_s"] + v["runtime_start_s"]
    hi = lo + run.seconds
    if v.get("traced"):
        a, b = v["traced"]
        after = b - hi
        # b - a under a second: the tracer slept its least (the CPU
        # rehearsal, where start_trace outlasts the traced seconds) and
        # saw only that the window had closed by b
        slept_to_close = b - a >= 1.0
        if after < -0.05 or (slept_to_close and after > TRACED_AFTER_CLOSE_S):
            raise RuntimeError(
                f"the window rebuilt from set-up's end closes {after:.3f} s "
                f"before the driver's tracer saw it close: the driver no "
                f"longer opens its window where set-up ends, nothing is read"
            )
    return lo, hi


def _ms(x):
    return f"{x * 1e3:.2f}"


def _requests(run):
    """The traces of the requests submitted in the window, by name
    (nothing is submitted after it: a request is sent when it is due)."""
    lo, _hi = _window(run)
    traces = collections.defaultdict(dict)
    for s in _spans("engine.request", lo):
        traces[s.trace_id][s.name] = s
    return [
        t for t in traces.values()
        if "engine.request" in t and lo <= t["engine.request"].start_mono
    ]


def _request_phase(run, params):
    served = [t for t in _requests(run) if "engine.request.decode" in t]
    if not served:
        return None
    value = stats.percentile(
        [t[params["span"]].duration * 1e3 for t in served], params["stat"]
    )[0]
    if params["span"].endswith("first_token"):
        _check_first_token(run, served, params["ttft_tolerance_ms"])
    return value


def _check_first_token(run, served, tolerance_ms):
    """The arithmetic the split rests on: against the driver's own
    stamps (raises where they disagree), and, for the log, how much of
    the wait came after the prefill had been dispatched (the fetch
    deferred to the chunk)."""
    served.sort(key=lambda t: t["engine.request"].start_mono)
    inside = [
        (t["engine.request.queued"].duration
         + t["engine.request.first_token"].duration) * 1e3
        for t in served
    ]
    ttft, late = run.values["ttft_ms"], run.values["late_ms"]
    if not len(ttft) == len(late) == len(inside):
        raise RuntimeError(
            f"{len(inside)} requests in the ring for the window against "
            f"{len(ttft)} times to first token and {len(late)} requests of "
            f"the driver's: not the same requests, nothing is read"
        )
    # due -> first token as the client stamped it, less due -> sent: the
    # client thread stamps after the engine does, by the GIL's handover
    off = [(b - c) - a for a, b, c in zip(inside, ttft, late)]
    core.log(
        f"spans: the driver's ttft less lateness against queued + "
        f"first_token over {len(off)} requests: later by {min(off):.3f} to "
        f"{max(off):.3f} ms"
    )
    if max(abs(x) for x in off) > tolerance_ms:
        raise RuntimeError(
            f"queued + first_token is up to {max(abs(x) for x in off):.3f} ms "
            f"from the driver's ttft less lateness, over {tolerance_ms} ms: "
            f"the ring's requests are not the driver's, nothing is read"
        )
    admits = sorted(
        _spans("engine.admit", _window(run)[0]), key=lambda s: s.start_mono
    )
    starts = [s.start_mono for s in admits]
    after = []
    for t in served:
        first = t["engine.request.first_token"]
        i = bisect.bisect_right(starts, first.start_mono) - 1
        if i >= 0 and _end(admits[i]) >= first.start_mono:
            after.append((_end(first) - _end(admits[i])) * 1e3)
    if after:
        core.log(
            f"spans: of first_token, the part after its turn's admit phase "
            f"(prefill dispatched; dispatch and fetch of the chunk): p50 "
            f"{stats.percentile(after, 50)[0]:.2f} ms over {len(after)}"
        )


def _turns(run):
    lo, hi = _window(run)
    spans = _spans("engine.", lo)
    turns = [
        s for s in spans
        if s.name == "engine.turn" and _end(s) > lo and s.start_mono < hi
    ]
    kids = collections.defaultdict(list)
    for s in spans:
        if s.parent_span_id:
            kids[s.parent_span_id].append(s)
    return lo, hi, turns, kids, spans


def _turn_max(run, params):
    lo, hi, turns, kids, _ = _turns(run)
    turns = [t for t in turns if t.start_mono >= lo]
    if not turns:
        return None
    worst = max(turns, key=lambda s: s.duration)
    phases = sorted(kids[worst.span_id], key=lambda s: s.start_mono)
    core.log(
        f"spans: longest of {len(turns)} turns {_ms(worst.duration)} ms at "
        f"+{worst.start_mono - lo:.2f} s: "
        + ", ".join(
            f"{p.name.split('.', 1)[1]} {_ms(p.duration)}"
            + (f" ({len(p.events)} prefills)" if p.events else "")
            for p in phases
        )
    )
    untiled = max(
        t.duration - sum(p.duration for p in kids[t.span_id]) for t in turns
    )
    core.log(f"spans: a turn less its phases: at most {_ms(untiled)} ms")
    if untiled * 1e3 > params["tiling_tolerance_ms"]:
        raise RuntimeError(
            f"a turn's phases leave {_ms(untiled)} ms of it uncovered, over "
            f"{params['tiling_tolerance_ms']} ms: the phases no longer tile "
            f"the turn, nothing is read"
        )
    return worst.duration * 1e3


def _clipped(s, lo, hi):
    return max(min(_end(s), hi) - max(s.start_mono, lo), 0.0)


def _host_share(run, params):
    lo, hi, turns, kids, spans = _turns(run)
    if not turns:
        return None
    in_turns = sum(_clipped(t, lo, hi) for t in turns)
    by_phase = collections.Counter()
    for t in turns:
        for p in kids[t.span_id]:
            by_phase[p.name] += _clipped(p, lo, hi)
    idle = sum(
        _clipped(s, lo, hi) for s in spans if s.name == "engine.idle"
    )
    core.log(
        f"spans: window {hi - lo:.2f} s: in turns {in_turns:.3f} s ("
        + ", ".join(f"{k.split('.', 1)[1]} {v:.3f}" for k, v in sorted(by_phase.items()))
        + f"), idle {idle:.3f} s, {len(turns)} turns"
    )
    return 100.0 * (in_turns - by_phase["engine.fetch"]) / (hi - lo)


def _step_host(run, params):
    # the window's steps are the last ones taken: the ring is checked
    # from where set-up ended, before the first of them
    steps = _spans("trainer.step", _window(run)[0])
    steps = steps[-int(run.values["steps"]):]
    if not steps:
        return None
    return stats.percentile(
        [s.duration * 1e3 for s in steps], params["stat"]
    )[0]


_WHAT = {
    "request_phase": _request_phase,
    "turn_max": _turn_max,
    "host_share": _host_share,
    "step_host": _step_host,
}


def read(run, params):
    return _WHAT[params["what"]](run, params)
