"""The share of a deployment (``cohere2_moe``: held experts, window
rings) through the benchmark on the CPU at a tiny size: the new driver
(``drivers/engine_share.py``), the family's own weights and counts, the
reference's copy, and every new reader, on the tiny files beside this
test. The manifest it runs under is ``data/tiny/BENCHMARK.share.json``."""

import json
import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import core

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny")
ROOTS = (TINY, core.BENCH_DIR)
CELL = "tiny-share-saturated"
COUNTERS = {"moe_experts_hit_share.longmix", "kv_cache_gb.longmix"}
DEVICE_TRACE = {
    "decode_hbm_roofline.longmix", "moe_decode_roofline.longmix",
    "decode_attend_roofline.longmix",
}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(TINY, "BENCHMARK.share.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_share_prints_the_contracts_last_line(trace, manifest):
    result = core.run_cell(
        CELL, 2**31 + 17, 1.0, bool(trace), t0=time.monotonic(), roots=ROOTS,
        manifest=manifest, rehearsal=True,
    )
    line = json.loads(json.dumps(result))
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    checks = {c["name"]: c for c in line["checks"]}
    assert checks["moe_dropped"]["value"] == 0
    assert checks["compiles_in_window"]["ok"]
    if trace:
        cell = core.load_json(ROOTS, "cells", CELL)
        # the counters are read wherever the program has them; a roofline
        # share is a device number, and the CPU's trace has no programs
        got = set(line["metrics"])
        assert got == set(cell["per_layer"]) - DEVICE_TRACE
        assert COUNTERS <= got
        assert 0 < line["metrics"]["moe_experts_hit_share.longmix"]["value"] <= 100
        # 2 full layers x 4 slots x 128 and 6 window layers x rings of 32
        assert line["metrics"]["kv_cache_gb.longmix"]["value"] == pytest.approx(
            (2 * 4 * 128 + 6 * 4 * 32) * 2 * 32 * 2 / 1e9
        )
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_a_program_without_the_family_fails_before_weights(manifest, monkeypatch):
    """The parent commit has no ``models/cohere2.py``: the driver must
    stop at the program's config object, in seconds."""
    import sys

    monkeypatch.setitem(sys.modules, "odh_kubeflow_tpu.models.cohere2", None)
    run, _ = core.prepare(
        CELL, 1, 1.0, False, t0=time.monotonic(), roots=ROOTS,
        manifest=manifest, rehearsal=True,
    )
    drawn = []
    monkeypatch.setattr(run.family, "make_params", lambda *a: drawn.append(a))
    driver = core.load_module(ROOTS, "drivers", run.mix["driver"])
    with pytest.raises(ImportError):
        driver.run(run)
    assert not drawn


def test_the_benchmarks_reference_is_the_repos_byte_for_byte():
    root = os.path.dirname(core.BENCH_DIR)
    with open(os.path.join(root, "odh_kubeflow_tpu/reference/cohere2_moe.py")) as f:
        ours = f.read()
    with open(os.path.join(core.BENCH_DIR, "reference/cohere2_moe.py")) as f:
        assert f.read() == ours
    assert "odh_kubeflow_tpu" not in "".join(
        line for line in ours.splitlines() if line.startswith(("import", "from"))
    )


def test_the_copy_computes_what_the_repos_reference_computes():
    from odh_kubeflow_tpu.reference import cohere2_moe as repo

    copy = core.load_module(ROOTS, "reference", "cohere2_moe")
    config = core.load_json(ROOTS, "configs", "tiny-share")
    family = core.load_module(ROOTS, "families", "cohere2_moe")
    params = family.make_params(config, 2**31 + 5)
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 256, size=48))
    a, top_a = repo.logits(params, tokens, config)
    b, top_b = copy.logits(params, tokens, config)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(top_a), np.asarray(top_b))
    # and a lower precision is told from it
    low, _ = copy.logits(params, tokens, config, copy.Precision(act="int8"))
    assert float(jnp.abs(low - a).max()) > 1e-3


def test_family_counts_read_the_configuration_file():
    family = core.load_module(ROOTS, "families", "cohere2_moe")
    config = core.load_json((core.BENCH_DIR,), "configs", "command-a-plus-05-2026-ep8")
    assert family.layer_windows(config) == (4096, 4096, 4096, None)
    assert family.held(config) == (32, 16)
    assert family.kinds(config) == (6, 2)
    assert family.attention_matmul_weights(config) == 2 * 4096 * 16384 + 2 * 4096 * 1024
    assert family.expert_weights(config) == 3 * 4096 * 4096
    parts = family.decode_step_bytes(config, 80.0, 1000.0, 600.0)
    assert parts["routed"] == 80 * 3 * 4096 * 4096
    assert parts["kv"] == 4096 * (2 * 1000 + 6 * 600)
    assert parts["head"] == 32768 * 4096 * 2
    # the program's config object, from the file alone
    cfg = family.program_config(config)
    assert (cfg.num_experts, cfg.experts_held, cfg.num_layers) == (128, (32, 16), 8)
    # every source key is quoted as published, bar the reduced ones
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f]
    source = next(r for r in rows if r["name"] == "command-a-plus-05-2026")
    for key, value in source["config"].items():
        if key in config["reduced"]:
            assert config["reduced_from"][key] == value
        else:
            assert config[key] == value, key
