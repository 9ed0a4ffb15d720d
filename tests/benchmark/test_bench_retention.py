"""A pipeline stage of a stack that keeps NO keys and values but a
power-retention state a layer a slot (``brumby``) through the benchmark
on the CPU at a tiny size: the driver (``drivers/engine_retention.py``),
the family's own weights and counts, the reference's copy, both controls
and every new reader, on the tiny files beside this test. The manifest
it runs under is ``data/tiny/BENCHMARK.retention.json``."""

import json
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import core

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny")
ROOTS = (TINY, core.BENCH_DIR)
CELL = "tiny-retention-saturated"
REAL_CELL = "brumby14b-stage-longgen-saturated"
REAL_CONFIG = "brumby-14b-base-stage"
DEVICE_TRACE = {
    "decode_hbm_roofline.retention", "retention_decode_roofline.retention",
    "retention_prefill_roofline.retention", "prefill_device_share.retention",
}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(TINY, "BENCHMARK.retention.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family():
    return core.load_module(ROOTS, "families", "brumby")


@pytest.fixture(scope="module")
def published():
    return core.load_json((core.BENCH_DIR,), "configs", REAL_CONFIG)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_stage_prints_the_contracts_last_line(trace, manifest):
    result = core.run_cell(
        CELL, 2**31 + 19, 1.0, bool(trace), t0=time.monotonic(), roots=ROOTS,
        manifest=manifest, rehearsal=True,
    )
    line = json.loads(json.dumps(result))
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    checks = {c["name"]: c for c in line["checks"]}
    assert checks["compiles_in_window"]["ok"]
    # a request was decoding at the close (the outputs outlast the
    # window) and its row of the engine's state was read: float32
    # against float32, so rounding alone
    assert 0 < checks["retention_state_gap"]["value"] < 1e-4
    if trace:
        cell = core.load_json(ROOTS, "cells", CELL)
        # the counters are read wherever the program has them; a roofline
        # share is a device number, and the CPU's trace has no programs
        got = set(line["metrics"])
        assert got == set(cell["per_layer"]) - DEVICE_TRACE
        assert line["metrics"]["serve_rate_mean5s.retention"]["value"] > 0
        # no keys and values: 4 layers x 4 slots x 2 heads x 9 rows of
        # phi x 16 x (16 of state + 1 of normaliser), float32
        assert line["metrics"]["state_cache_gb.retention"]["value"] == pytest.approx(
            4 * 4 * 2 * 9 * 16 * 17 * 4 / 1e9
        )
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_a_program_without_the_family_fails_before_weights(manifest, monkeypatch):
    """The parent commit has no ``models/brumby.py``: the driver must
    stop at the program's config object, in seconds."""
    import sys

    monkeypatch.setitem(sys.modules, "odh_kubeflow_tpu.models.brumby", None)
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
    with open(os.path.join(root, "odh_kubeflow_tpu/reference/brumby.py")) as f:
        ours = f.read()
    with open(os.path.join(core.BENCH_DIR, "reference/brumby.py")) as f:
        assert f.read() == ours
    assert "odh_kubeflow_tpu" not in "".join(
        line for line in ours.splitlines() if line.startswith(("import", "from"))
    )


@pytest.fixture(scope="module")
def tiny_reference(family):
    config = core.load_json(ROOTS, "configs", "tiny-retention")
    params = family.make_params(config, 2**31 + 5)
    ref = core.load_module(ROOTS, "reference", "brumby")
    return config, params, ref


def test_the_copy_computes_what_the_repos_reference_computes(tiny_reference):
    from odh_kubeflow_tpu.reference import brumby as repo

    config, params, copy = tiny_reference
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 256, size=48))
    np.testing.assert_array_equal(
        np.asarray(repo.logits(params, tokens, config)),
        np.asarray(copy.logits(params, tokens, config)),
    )
    np.testing.assert_array_equal(
        np.asarray(repo.state_at(params, tokens, config, layer=1, stop=40, block=16)),
        np.asarray(copy.state_at(params, tokens, config, layer=1, stop=40, block=16)),
    )
    assert params["layers"]["wq"]["q"].shape == (4, 64, 64)
    assert params["layers"]["gate_w"].shape == (4, 64, 2)
    # the seeded gates remember: g in 0.98-0.9995
    g = jax.nn.sigmoid(params["layers"]["gate_b"])
    assert float(g.min()) >= 0.98 and float(g.max()) <= 0.9995


@pytest.mark.parametrize(
    "prec", [{"act": "int8"}, {"state": "bf16"}], ids=["int8-activations", "bf16-state"]
)
def test_a_lower_precision_is_told_from_the_reference(tiny_reference, prec):
    config, params, ref = tiny_reference
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 256, size=48))
    low = ref.Precision(**prec)
    if "act" in prec:
        sound = ref.logits(params, tokens, config)
        assert float(jnp.abs(ref.logits(params, tokens, config, low) - sound).max()) > 1e-4
    else:
        # the attention form carries no state: the lower precision shows
        # where the state is asked for
        sound = ref.state_at(params, tokens, config, block=16)
        carried = ref.state_at(params, tokens, config, prec=low)
        assert float(jnp.abs(carried - sound).max() / jnp.abs(sound).max()) > 1e-4


def test_both_controls_fail_the_limits_a_sound_run_passes(manifest, tiny_reference):
    """The reference in each lower precision, put in the program's
    place on tokens the reference itself chose: each must fail at least
    one of the tiny cell's limits, which the sound choice (gap 0, the
    state the reference's own) passes."""
    config, params, ref = tiny_reference
    run, _ = core.prepare(
        CELL, 2**31 + 5, 1.0, False, t0=time.monotonic(), roots=ROOTS,
        manifest=manifest, rehearsal=True,
    )
    driver = core.load_module(ROOTS, "drivers", run.mix["driver"])
    rng = np.random.default_rng(1)
    sound = jax.jit(lambda p, seq: ref.logits(p, seq, config))  # compiled once
    sample = []
    for n in (40, 23):
        prompt = rng.integers(1, 256, size=n).tolist()
        served = []
        for _ in range(12):
            seq = np.zeros(64, np.int32)
            seq[: n + len(served)] = prompt + served
            lg = sound(params, jnp.asarray(seq))
            served.append(int(jnp.argmax(lg[n + len(served) - 1])))
        sample.append(types.SimpleNamespace(spec={"prompt": prompt, "id": n}, tokens=served))
    limits = run.cell["limits"]
    watched = sample[0].spec["prompt"] + sample[0].tokens[:-1]
    readings = driver.control_readings(run, params, sample, watched)
    assert set(readings) == {"int8_activations", "bf16_state"}
    for name, got in readings.items():
        assert any(got[k] > limits[k] for k in limits), (name, got, limits)
    assert readings["bf16_state"]["retention_state_gap"] > limits["retention_state_gap"]
    # and the sound reading of the same sample passes every one of them
    gaps = driver.check_against_reference(run, params, sample)
    assert float(gaps.max()) == 0.0


def test_a_program_that_keeps_its_state_in_bf16_comes_out_not_correct(manifest):
    """The PROGRAM's own lower precision, through the engine and the
    cell's check: with every decode step's state write rounded to
    bfloat16 (``state_rounded_to_bf16``) the run serves every request
    and fails ``retention_state_gap``, the number read from the row of
    the state that the engine holds."""
    driver = core.load_module(ROOTS, "drivers", "engine_retention")
    with driver.state_rounded_to_bf16():
        result = core.run_cell(
            CELL, 2**31 + 19, 1.0, False, t0=time.monotonic(), roots=ROOTS,
            manifest=manifest, rehearsal=True,
        )
    checks = {c["name"]: c for c in result["checks"]}
    assert result["correct"] is False and result["failed"] == 0
    assert not checks["retention_state_gap"]["ok"], checks["retention_state_gap"]
    assert checks["failed_requests"]["ok"] and checks["engine_failure"]["ok"]
    from odh_kubeflow_tpu.ops import pallas_retention

    restored = pallas_retention.retention_step_plain
    assert restored.__module__ == pallas_retention.__name__


def test_the_scan_at_float32_operands_leaves_the_activations_rounding_alone():
    """``scan_operands_float32``, the other side of PERF.md's attribution
    of a sound run's state gap: with bfloat16 activations the scan's own
    roundings (``phi(k)`` and ``v`` times its decay, one bfloat16 pass)
    show in the state it hands on; inside the context nothing of them is
    left, against the token recurrence on the same bfloat16 ``k`` and
    ``v``."""
    from odh_kubeflow_tpu.ops import pallas_retention as pr

    driver = core.load_module(ROOTS, "drivers", "engine_retention")
    S, Hq, Hkv, d = 40, 4, 2, 16
    kq, kk, kv, kg = jax.random.split(jax.random.key(7), 4)
    q, k, v = (
        jax.random.normal(key, (1, S, H, d)).astype(jnp.bfloat16)
        for key, H in ((kq, Hq), (kk, Hkv), (kv, Hkv))
    )
    log_g = -0.05 * jax.random.uniform(kg, (1, S, Hkv))
    zero = (
        jnp.zeros((1, Hkv, pr.phi_rows(d), d, d)), jnp.zeros((1, Hkv, pr.phi_rows(d), d)),
    )
    want = pr.retention_scan_plain(q, k, v, log_g, *zero)[1]

    def gap():
        got = pr.retention_chunk_scan(q, k, v, log_g, *zero, chunk=8, interpret=True)[1]
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    sound = pr.retention_chunk_scan
    rounded = gap()
    with driver.scan_operands_float32():
        exact = gap()
    assert exact < 1e-5 and 5e-4 < rounded < 1e-2, (exact, rounded)
    assert pr.retention_chunk_scan is sound


# ---- the counts, against numbers worked by hand ----------------------------


def test_family_reads_the_configuration_file(family, published):
    assert family.heads(published) == (40, 8, 128) and family.phi_rows(published) == 65
    cfg = family.program_config(published)
    assert (cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (10, 40, 8, 128)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size) == (5120, 17408, 151936)
    assert cfg.rope_theta == 1e6 and cfg.rms_norm_eps == 1e-6
    assert cfg.layer_kinds == ("state",) and cfg.retention_chunk == 128
    assert cfg.dtype == jnp.bfloat16
    from odh_kubeflow_tpu.ops import pallas_retention

    assert published["retention_chunk"] == pallas_retention.DEFAULT_CHUNK
    assert published["retention_eps"] == pallas_retention.EPS
    # every item the issue lists under ``assumed`` is there
    assert {
        "retention_degree", "scale", "normaliser", "one_gate_a_key_value_head",
        "gate_form", "qk_norm_and_rope", "retention_chunk", "state_padded_width",
        "switch_over", "weights", "gate_bias", "norm_weights",
    } <= set(published["assumed"])
    for key in ("source", "reduced_from", "reduced_why", "published", "precision", "deployment"):
        assert key in published, key
    assert published["deployment"]["stages"] == 4
    assert published["deployment"]["chips_sharing_a_layer"] == 1
    # every source key is quoted as published, bar the reduced one
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f]
    source = next(r for r in rows if r["name"] == "Brumby-14B-Base")
    assert published["source"] == source["source_url"]
    assert published["reduced"] == ["num_hidden_layers"]
    for key, value in source["config"].items():
        if key in published["reduced"]:
            assert published["reduced_from"][key] == value
        else:
            assert published[key] == value, key
    # the floors: whole periods (every layer is one) and at least four layers
    assert published["num_hidden_layers"] * 4 == source["config"]["num_hidden_layers"]


def test_decode_step_bytes_against_hand_arithmetic(family, published):
    # a layer: 5120 x 5120 (q) + 2 x 5120 x 1024 (k, v) + 5120 x 5120 (o)
    # + 3 x 5120 x 17408 (SwiGLU) = 330.3 M, a byte each
    assert family.matmul_weights_per_layer(published) == (
        26_214_400 + 2 * 5_242_880 + 26_214_400 + 267_386_880
    ) == 330_301_440
    # a slot's state in a layer AS ALLOCATED: 8 heads x 65 rows x 128 x
    # (128 of state + 1 of normaliser) float32 = 34.3 MB (the 8256
    # distinct products alone: 8 x 8256 x 129 x 4 = 34.1 MB)
    assert family.state_bytes_per_slot_layer(published) == 8 * 65 * 128 * 129 * 4
    assert 8 * 8256 * 129 * 4 <= family.state_bytes_per_slot_layer(published) <= 1.01 * (
        8 * 8256 * 129 * 4
    )
    parts = family.decode_step_bytes(published, 16.0)
    # read AND written: 16 slots x 10 layers x 2 x 34.3 MB = 10.99 GB
    assert parts["state"] == 16 * 10 * 2 * 34_344_960
    assert parts["weights"] == 10 * (330_301_440 + 4 * 5120 * 8)  # 3.30 GB
    assert parts["head"] == 151_936 * 5120 * 2  # 1.56 GB
    assert set(parts) == {"state", "weights", "head"}
    total = sum(parts.values())
    assert total == pytest.approx(15.85e9, rel=0.01)
    # the state is 69 % of a step's bytes, and a step at most ~830 tokens/s
    assert parts["state"] / total == pytest.approx(0.69, abs=0.01)
    assert 16 / (total / 819e9) == pytest.approx(827, rel=0.01)
    assert family.decode_step_bytes(published, 8.0)["state"] * 2 == parts["state"]


def test_retention_scan_work_against_hand_arithmetic(family, published):
    work = family.retention_scan_work(published, 2048)
    # a position a layer: 40 query heads read 2 x 8256 x 129 = 2.130 M
    # each, 8 states take as much each, and in its chunk of 128 every
    # query head spends 4 x 128 x 128 = 65.5 K: 104.9 MFLOP
    a_position = 40 * 2 * 8256 * 129 + 8 * 2 * 8256 * 129 + 40 * 4 * 128 * 128
    assert a_position == 104_863_744
    assert work["flops"] == 2048 * a_position
    # q and y 2 x 2048 x 5120 bf16, k and v 2 x 2048 x 1024 bf16, the
    # gates' logs 2048 x 8 float32, state and normaliser in and out
    assert work["bytes"] == 41_943_040 + 8_388_608 + 65_536 + 2 * 34_344_960
    short = family.retention_scan_work(published, 64)
    assert short["flops"] == 64 * (48 * 2 * 8256 * 129 + 40 * 4 * 64 * 128)
    # operations bind it: 1.09 ms a layer a part at the bf16 peak
    assert work["flops"] / 197e12 > work["bytes"] / 819e9


# ---- the readers, on a trace made by hand ----------------------------------


def fake_run(family, published, **values):
    ops = {
        "%retention_decode_update.3 = (f32[10,16,8,65,128,128], f32[10,16,8,65,128]) custom-call(%s)": 1.9,
        "%retention_chunk_scan.4 = (bf16[1,16,128,5120], f32[1,8,65,128,128]) custom-call(%x)": 0.35,
        "%fusion.5 = bf16[16,5120] fusion(%y)": 0.4,
    }
    modules = {
        "jit__decode_chunk(1)": (2.6, 13), "jit__decode_chunk_greedy(4)": (0.2, 1),
        "jit__prefill_part_2048(2)": (0.9, 5), "jit__prefill_256(3)": (0.03, 1),
    }
    v = {"decode_steps_per_call": 8.0, "live_slots": 15.5, "n_slots": 16, **values}
    return types.SimpleNamespace(
        reduced={"modules": modules, "ops": ops, "window_s": 6.0},
        config=published, values=v, family=family,
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
    )


@pytest.mark.parametrize(
    "name", sorted(DEVICE_TRACE), ids=lambda n: n.split(".")[0]
)
def test_every_new_reader_returns_a_finite_share(family, published, name):
    spec = core.load_json(ROOTS, "metrics", name)
    reader = core.load_module(ROOTS, "metrics", spec["reader"])
    run = fake_run(family, published)
    share = reader.read(run, spec.get("params", {}))
    assert share is not None and 0 < share <= 100, share
    by = family.decode_step_bytes(published, 15.5)
    least = lambda n: max(  # noqa: E731
        family.retention_scan_work(published, n)["flops"] / 197e12,
        family.retention_scan_work(published, n)["bytes"] / 819e9,
    )
    steps = 14 * 8
    want = {
        "decode_hbm_roofline": 100 * sum(by.values()) / 819e9 * steps / 2.8,
        "retention_decode_roofline": 100 * by["state"] / 819e9 * steps / 1.9,
        "retention_prefill_roofline": 100 * 10 * (5 * least(2048) + least(256)) / 0.35,
        "prefill_device_share": 100 * 0.93 / 6.0,
    }[name.split(".")[0]]
    assert share == pytest.approx(want)


def test_readers_find_nothing_where_the_program_has_nothing(family, published):
    spec = core.load_json(ROOTS, "metrics", "retention_decode_roofline.retention")
    reader = core.load_module(ROOTS, "metrics", spec["reader"])
    run = fake_run(family, published)
    run.reduced["modules"] = {}
    assert reader.read(run, spec["params"]) is None
    run = fake_run(family, published, decode_steps_per_call=None)
    assert reader.read(run, spec["params"]) is None
    # another family's run (the driver lays these files over the parent):
    # no work functions to ask
    other = core.load_module(ROOTS, "families", "dense")
    assert reader.read(fake_run(other, published), spec["params"]) is None
    # no prefill wholly inside the window: the scan's share is not read
    scan = core.load_json(ROOTS, "metrics", "retention_prefill_roofline.retention")
    run = fake_run(family, published)
    run.reduced["modules"] = {"jit__decode_chunk(1)": (2.6, 13)}
    assert reader.read(run, scan["params"]) is None
    value = core.load_module(ROOTS, "metrics", "value")
    assert value.read(run, {"key": "state_cache_gb"}) is None


def test_the_cell_is_the_issues(published):
    cell = core.load_json((core.BENCH_DIR,), "cells", REAL_CELL)
    mix = core.load_json((core.BENCH_DIR,), "traffic", cell["traffic"])
    assert cell["program"] == {
        "n_slots": 16, "max_len": 20480, "prefill_chunk": 2048,
        "prompt_buckets": [64, 256, 1024, 2048],
    }
    assert mix["driver"] == "engine_retention" and mix["drain"] is False
    assert mix["prompt"] == {
        "dist": "lognormal", "median": 2048, "sigma": 1.2, "min": 64, "max": 16384,
    }
    assert mix["output"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.8, "min": 32, "max": 4096,
    }
    assert mix["sampling"] == {"temperature": 0.7, "top_p": 0.95}
    assert (mix["greedy_share"], mix["check_requests"], mix["trace_s"]) == (0.25, 4, 6)
    assert mix["schedule_seed"] == 43
    assert mix["arrivals"]["dist"] == "exponential" and "rate_from" in mix
    # the longest prompt and the longest output fit a slot together
    assert mix["prompt"]["max"] + mix["output"]["max"] <= cell["program"]["max_len"]
    assert set(cell["limits"]) == set(cell["limits_why"]) == {
        "served_logit_gap_max", "served_logit_gap_mean", "retention_state_gap",
    }
    manifest = core.load_manifest()
    names = [w["name"] for w in manifest["workloads"]]
    entry = manifest["workloads"][names.index(REAL_CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        cell["config"], cell["traffic"], 1,
    )
    assert entry["why"] == cell["why"] and len(entry["why"]) <= 200
    # appended behind what was there
    assert names.index(REAL_CELL) > names.index("qwen3next-ep2-stage-longmix-saturated")
    config = next(c for c in manifest["configs"] if c["name"] == REAL_CONFIG)
    assert config["reduced"] == published["reduced"] == ["num_hidden_layers"]
    assert config["source"] == published["source"]
    rate = next(m for m in manifest["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert REAL_CELL in rate["workloads"]
    declared = {m["name"]: m for m in manifest["per_layer"]}
    for name in cell["per_layer"]:
        assert name in declared
        if name.endswith(".retention"):
            assert declared[name]["workloads"] == [REAL_CELL]
            assert declared[name]["moves"] == "serve_tokens_per_s"
            spec = core.load_json((core.BENCH_DIR,), "metrics", name)
            for key in ("unit", "better", "layer", "source", "moves"):
                assert spec[key] == declared[name][key], (name, key)
    assert sum(n.endswith(".retention") for n in cell["per_layer"]) == 6
