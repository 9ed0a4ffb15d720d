"""``benchmark/run.py`` with the engine's turn totals read as well:

    python3 benchmark/run_turn_totals.py --workload <cell> --seed <n> --seconds <s> --trace 1

The eight metrics of ``benchmark/turn_totals/metrics/`` (reader
``metrics/turn_totals.py``) read what ``DecodeEngine`` records on its
``engine.turn`` spans: where a decode chunk's slot-steps went, what the
waiting requests waited for, how busy the part-by-part lane was.
The harness reports what a cell's file lists, and a cell's file is a
``benchmark`` PR's to edit, so they are in no cell's list and not in
``BENCHMARK.json``. ``turn_totals/cells.json`` says which cell reports
which; this entry point does what ``run_program_spans.py`` does, for its
list and that one's together: copies of those cells' files with the
names appended, and ``turn_totals/`` as one more place to find a
metric's file, in front of the benchmark's own directory. A
``benchmark`` PR that moves the eight files into ``metrics/``, appends
their names to the four cells' lists and declares them in
``BENCHMARK.json`` makes this file and ``turn_totals/cells.json``
unnecessary."""

import argparse
import json
import os
import sys
import tempfile
import time

T0 = time.monotonic()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
TOTALS_DIR = os.path.join(BENCH_DIR, "turn_totals")


def extra_metrics() -> dict:
    """{cell: metric names} of ``program_spans.json`` and of
    ``turn_totals/cells.json``, the latter's after the former's."""
    out: dict = {}
    for path in (
        os.path.join(BENCH_DIR, "program_spans.json"),
        os.path.join(TOTALS_DIR, "cells.json"),
    ):
        with open(path) as f:
            for cell, names in json.load(f).items():
                out.setdefault(cell, []).extend(names)
    return out


def main(argv) -> int:
    from benchmark import run_program_spans
    from benchmark.harness import core

    ap = argparse.ArgumentParser()  # benchmark/run.py's own arguments
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    with tempfile.TemporaryDirectory(prefix="cells-") as tmp:  # under TMPDIR
        over = run_program_spans.overlay((BENCH_DIR,), extra_metrics(), tmp)
        result = core.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace), t0=T0,
            roots=(over, TOTALS_DIR, BENCH_DIR),
        )
    del result["checks"]  # printed by the run, as benchmark/run.py does
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    # as benchmark/run.py: the compile cache's place, before jax is imported
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_compile_cache")
    )
    sys.path.insert(0, ROOT)
    sys.exit(main(sys.argv[1:]))
