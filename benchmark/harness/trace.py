"""The reduction from a profiler trace (``.xplane.pb``, read with
``jax.profiler.ProfileData``) to what the metrics read: per chip the
union of the intervals in which an operation ran, the time of each
operation by name, and each idle gap attributed to the host span that
covers most of it. Spans come from ``jax.profiler.TraceAnnotation``s
that the DRIVERS put around their own calls; the traced window is the
span named ``bench.window``."""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "bench.window"
OP_LINE = "XLA Ops"
# an operation that only holds others (a loop, a branch, a call): its
# event spans its children's, so it is neither busy time nor a row
_CONTAINER = re.compile(r"^%?(while|conditional|call)[.\d]*$")
MODULE_LINE = "XLA Modules"


def find_xplane(logdir: str) -> str:
    files = sorted(
        glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def _union(intervals):
    """Merged, sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def reduce_profile(profile, device_prefix: str, span_prefixes=("bench.", "train.", "loadgen.")) -> dict:
    """``profile``: a ProfileData. Device planes are those whose name
    starts with ``device_prefix``; their ``XLA Ops`` line (every line,
    where a plane has no line of that name) holds the operations.

    Returns ``window_s``, ``busy_s`` (mean over chips), ``chips``,
    ``ops`` {name: seconds, summed over chips / chips}, ``modules``
    {program: (seconds, calls)} for the calls that lie wholly inside,
    ``gaps`` [(label, seconds)] longest first, and ``spans``
    {name: [(start_s, end_s)]} relative to the window's start."""
    spans = defaultdict(list)
    device_planes = []
    for plane in profile.planes:
        if plane.name.startswith(device_prefix):
            device_planes.append(plane)
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(span_prefixes):
                    spans[ev.name].append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns)
                    )
    if not device_planes:
        raise ValueError(
            f"the trace holds no plane named {device_prefix}*: "
            f"{[p.name for p in profile.planes]}"
        )
    if not spans.get(WINDOW_SPAN):
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo = min(s for s, _ in spans[WINDOW_SPAN])
    hi = max(e for _, e in spans[WINDOW_SPAN])

    ops = defaultdict(float)
    modules = defaultdict(lambda: [0.0, 0])  # whole programs: seconds, calls
    busy_total = 0.0
    gap_list = []
    for plane in device_planes:
        # a CPU rehearsal has no device plane: there the host plane's
        # runtime threads stand in, without the python tracer's line
        lines = [ln for ln in plane.lines if ln.name == OP_LINE] or [
            ln for ln in plane.lines if ln.name != "python"
        ]
        intervals = []
        for line in lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= lo or s >= hi or ev.name.startswith(span_prefixes):
                    continue
                if _CONTAINER.match(ev.name.split(" = ", 1)[0]):
                    continue
                intervals.append((s, e))
                ops[ev.name] += (min(e, hi) - max(s, lo)) / 1e9
        for line in plane.lines:
            if line.name == MODULE_LINE:
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if s >= lo and e <= hi:  # whole calls only
                        modules[ev.name][0] += (e - s) / 1e9
                        modules[ev.name][1] += 1
        merged = _union(_clip(intervals, lo, hi))
        busy_total += sum(e - s for s, e in merged) / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gap_list += [
            (edges[i], edges[i + 1])
            for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]
        ]
    n = len(device_planes)
    if busy_total <= 0:
        raise ValueError("no operation ran on the device inside the traced window")

    host = [
        (s, e, name)
        for name, ivs in spans.items()
        if name != WINDOW_SPAN
        for s, e in ivs
    ]
    by_label = defaultdict(float)
    for gs, ge in gap_list:
        cover = defaultdict(float)
        for s, e, name in host:
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                cover[name] += ov
        label = max(cover, key=cover.get) if cover else "no_span"
        by_label[label] += (ge - gs) / 1e9 / n
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / n,
        "chips": n,
        "ops": {k: v / n for k, v in ops.items()},
        "modules": {k: (v[0] / n, v[1] / n) for k, v in modules.items()},
        "gaps": sorted(by_label.items(), key=lambda kv: -kv[1]),
        "spans": {
            k: [((s - lo) / 1e9, (e - lo) / 1e9) for s, e in v]
            for k, v in spans.items()
        },
    }


def reduce_logdir(logdir: str, device_prefix: str) -> dict:
    from jax.profiler import ProfileData

    return reduce_profile(
        ProfileData.from_file(find_xplane(logdir)), device_prefix
    )


def short_name(op: str) -> str:
    """An event's name is the whole HLO instruction: keep the
    instruction's own name, its opcode and a custom call's target."""
    head = op.split(" = ")[0]
    code = re.search(r"[\s)}]([a-z][a-z\-]*)\(", op[len(head):][:4000])
    target = re.search(r'custom_call_target="([^"]+)"', op)
    return " ".join(
        x for x in (head, code and code.group(1), target and target.group(1)) if x
    )[:120]


def breakdown(reduced: dict, top: int = 10) -> dict:
    ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[short_name(k), v] for k, v in ops],
        "idle_gaps": [[k, v] for k, v in reduced["gaps"][:top]],
    }


def op_seconds(reduced: dict, patterns) -> float:
    """Total device seconds of the operations whose name matches one of
    ``patterns`` (regular expressions). No match is an error: a kernel
    that cannot be found has no time, not zero time."""
    rx = [re.compile(p) for p in patterns]
    hits = {k: v for k, v in reduced["ops"].items() if any(r.search(k) for r in rx)}
    if not hits:
        raise LookupError(
            f"no device operation matches {patterns}; the longest are "
            f"{sorted(reduced['ops'], key=reduced['ops'].get)[-8:]}"
        )
    return sum(hits.values())
