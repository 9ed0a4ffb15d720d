"""A CPU rehearsal of benchmark/run.py's code path on the tiny files
that live beside this test, and a check that everything BENCHMARK.json
names exists. The harness refuses a CPU unless it is told that this is
a rehearsal; the printed device says ``cpu``."""

import json
import os
import re
import time

import pytest

from benchmark.harness import core

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny")
ROOTS = (TINY, core.BENCH_DIR)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def tiny_manifest():
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        return json.load(f)


def rehearse(workload, trace, manifest, seed=2**31 + 11):
    return core.run_cell(
        workload, seed, 1.0, bool(trace), t0=time.monotonic(), roots=ROOTS,
        manifest=manifest, rehearsal=True,
    )


E2E = {
    "tiny-train": {"train_tokens_per_s", "setup_s"},
    "tiny-serve": {"ttft_p95_ms", "setup_s"},
    "tiny-serve-saturated": {"serve_tokens_per_s", "setup_s"},
}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize(
    "workload", ["tiny-train", "tiny-serve", "tiny-serve-saturated"]
)
def test_rehearsal_prints_the_contracts_last_line(workload, trace, tiny_manifest):
    result = rehearse(workload, trace, tiny_manifest)
    line = json.loads(json.dumps(result))  # it is what gets printed
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and UNIT.match(m["unit"])
    with open(os.path.join(TINY, "cells", workload + ".json")) as f:
        cell = json.load(f)
    if trace:
        # every per-layer metric the cell lists, the tiny one that only
        # the test's own directory holds among them
        assert set(line["metrics"]) == set(cell["per_layer"])
        assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert line["metrics"]["programs_compiled"]["value"] >= 0
    else:
        assert set(line["metrics"]) == E2E[workload]
        assert "breakdown" not in line
        assert all(m["value"] > 0 for m in line["metrics"].values())
    assert {c["name"] for c in line["checks"]} >= {"compiles_in_window"}


def test_a_cpu_is_refused_unless_this_is_a_rehearsal(tiny_manifest):
    with pytest.raises(SystemExit, match="no accelerator"):
        core.prepare(
            "tiny-train", 1, 1.0, False, t0=0.0, roots=ROOTS,
            manifest=tiny_manifest, rehearsal=False,
        )
    with pytest.raises(SystemExit, match="no workload"):
        core.prepare("no-such-cell", 1, 1.0, False, t0=0.0, rehearsal=True)


# ---- BENCHMARK.json names only things that exist ---------------------------


@pytest.fixture(scope="module")
def manifest():
    return core.load_manifest()


def test_manifest_has_the_contracts_keys_and_shapes(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert manifest["command"][:2] == ["python3", "benchmark/run.py"]
    assert manifest["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51
    cells = [w["name"] for w in manifest["workloads"]]
    assert len(set(cells)) == len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) <= max(len(cells) // 4, 1)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(set(names)) == len(names)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert len(json.dumps(manifest)) < 64 * 1024


def test_every_file_the_manifest_names_loads_and_agrees(manifest):
    roots = (core.BENCH_DIR,)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    used_configs, reported = set(), {name: set() for name in per_layer}
    for w in manifest["workloads"]:
        cell = core.load_json(roots, "cells", w["name"])
        assert {k: cell[k] for k in w} == w
        used_configs.add(cell["config"])
        mix = core.load_json(roots, "traffic", cell["traffic"])
        assert hasattr(core.load_module(roots, "drivers", mix["driver"]), "run")
        cell_e2e = {
            n for n, m in e2e.items()
            if "workloads" not in m or w["name"] in m["workloads"]
        }
        assert "setup_s" in cell_e2e and len(cell_e2e) >= 2
        assert cell["per_layer"], "every cell reports a per-layer metric"
        for name in cell["per_layer"]:
            spec = core.load_json(roots, "metrics", name)
            assert hasattr(core.load_module(roots, "metrics", spec["reader"]), "read")
            entry = per_layer[name]
            for key in ("name", "unit", "better", "source", "layer", "moves"):
                assert spec[key] == entry[key], (name, key)
            assert entry["moves"] in cell_e2e, (w["name"], name)
            reported[name].add(w["name"])
    for name, entry in per_layer.items():
        # with a `workloads` key: exactly the cells that report it;
        # without: every cell that reports the end-to-end metric it moves
        moved = e2e[entry["moves"]]
        expect = set(entry.get("workloads") or moved.get(
            "workloads", [w["name"] for w in manifest["workloads"]]
        ))
        assert reported[name] == expect, name
    assert used_configs == set(configs)
    for name, c in configs.items():
        assert c["file"] == f"benchmark/configs/{name}.json"
        body = core.load_json(roots, "configs", name)
        assert body["source"] == c["source"] and body["reduced"] == c["reduced"]
        assert c["source"].startswith("https://") and len(c["source"]) <= 200
        widths = re.compile(r"(hidden_size|intermediate|head_dim|_dim$|_rank$|experts_per_tok)")
        assert not any(widths.search(k) for k in c["reduced"])
        for key in c["reduced"]:
            assert key in body["reduced_from"] and body[key] != body["reduced_from"][key]
    for m in manifest["end_to_end"]:
        for cell in m.get("workloads", []):
            assert cell in {w["name"] for w in manifest["workloads"]}
