"""``benchmark/run.py`` with the program's own spans read as well:

    python3 benchmark/run_program_spans.py --workload <cell> --seed <n> --seconds <s> --trace 1

The harness reports the per-layer metrics that ``cells/<cell>.json``
lists, and a PR that changes the program may not edit a cell's file. The
metrics that read what the program records about itself
(``metrics/program_spans.py``, ``metrics/program_share.py``) are
therefore in no cell's list yet. ``program_spans.json`` says which of
them belongs to which cell; this entry point lays a copy of each such
cell's file, with those names appended, in front of the benchmark's own
directory and runs the harness unchanged. A ``benchmark`` PR that
appends the names to the cells' files and to ``BENCHMARK.json`` makes
this file and ``program_spans.json`` unnecessary."""

import argparse
import json
import os
import sys
import tempfile
import time

T0 = time.monotonic()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def overlay(roots, extra: dict, into: str) -> str:
    """Under ``into``/cells, each cell of ``extra`` as ``roots`` hold it
    with ``extra``'s metric names appended to its ``per_layer`` list."""
    from benchmark.harness import core

    os.makedirs(os.path.join(into, "cells"), exist_ok=True)
    for name, metrics in extra.items():
        cell = core.load_json(roots, "cells", name)
        cell["per_layer"] = cell["per_layer"] + [
            m for m in metrics if m not in cell["per_layer"]
        ]
        with open(os.path.join(into, "cells", name + ".json"), "w") as f:
            json.dump(cell, f)
    return into


def main(argv) -> int:
    from benchmark.harness import core

    ap = argparse.ArgumentParser()  # benchmark/run.py's own arguments
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    with open(os.path.join(BENCH_DIR, "program_spans.json")) as f:
        extra = json.load(f)
    with tempfile.TemporaryDirectory(prefix="cells-") as tmp:  # under TMPDIR
        result = core.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace), t0=T0,
            roots=(overlay((BENCH_DIR,), extra, tmp), BENCH_DIR),
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
