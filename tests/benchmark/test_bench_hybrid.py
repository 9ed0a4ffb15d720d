"""A stage of a stack with recurrent state beside its keys and values
(``granitemoehybrid``) through the benchmark on the CPU at a tiny size:
the driver (``drivers/engine_hybrid.py``), the family's own weights and
counts, the reference's copy, both controls and every new reader, on the
tiny files beside this test. The manifest it runs under is
``data/tiny/BENCHMARK.hybrid.json``."""

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
CELL = "tiny-hybrid-saturated"
REAL_CELL = "granite4h-stage-longmix-saturated"
COUNTERS = {
    "moe_experts_hit_share.hybrid", "state_cache_gb.hybrid", "kv_cache_gb.hybrid",
}
DEVICE_TRACE = {
    "decode_hbm_roofline.hybrid", "moe_decode_roofline.hybrid",
    "ssm_decode_roofline.hybrid", "ssd_prefill_roofline.hybrid",
    "prefill_device_share.hybrid",
}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(TINY, "BENCHMARK.hybrid.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family():
    return core.load_module(ROOTS, "families", "granitemoehybrid")


@pytest.fixture(scope="module")
def published():
    return core.load_json((core.BENCH_DIR,), "configs", "granite-4.0-h-small-stage")


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
    assert checks["moe_dropped"]["value"] == 0
    assert checks["compiles_in_window"]["ok"]
    assert checks["routing_differs_share"]["ok"] and checks["ssm_state_gap"]["ok"]
    # a request was decoding at the close and its row of the engine's
    # state was read: float32 against float32, so rounding alone
    assert 0 < checks["ssm_state_gap"]["value"] < 1e-4
    if trace:
        cell = core.load_json(ROOTS, "cells", CELL)
        # the counters are read wherever the program has them; a roofline
        # share is a device number, and the CPU's trace has no programs
        got = set(line["metrics"])
        assert got == set(cell["per_layer"]) - DEVICE_TRACE
        assert COUNTERS <= got
        # beside the judged quotient, the mean over the window's end
        rate = line["metrics"]["serve_rate_mean5s.hybrid"]["value"]
        assert rate > 0
        assert 0 < line["metrics"]["moe_experts_hit_share.hybrid"]["value"] <= 100
        # the tiny file states a float32 cache: 1 attention layer x 4
        # slots x 128 positions of 2 x 32; 3 Mamba-2 layers x 4 slots x
        # (8 x 16 x 16 of SSM state + 3 x 160 of convolution inputs)
        assert line["metrics"]["kv_cache_gb.hybrid"]["value"] == pytest.approx(
            1 * 4 * 128 * 2 * 32 * 4 / 1e9
        )
        assert line["metrics"]["state_cache_gb.hybrid"]["value"] == pytest.approx(
            3 * 4 * (8 * 16 * 16 + 3 * 160) * 4 / 1e9
        )
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_a_program_without_the_family_fails_before_weights(manifest, monkeypatch):
    """The parent commit has no ``models/granite_hybrid.py``: the driver
    must stop at the program's config object, in seconds."""
    import sys

    monkeypatch.setitem(sys.modules, "odh_kubeflow_tpu.models.granite_hybrid", None)
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


@pytest.mark.parametrize("last_burst_at", [9.99, 10.01])
def test_the_rate_does_not_jump_when_a_burst_crosses_the_close(last_burst_at):
    """Bursts of 100 tokens a second from an open at 50.0; the tenth
    lands 10 ms before the close or 10 ms after it. The quotient at the
    burst that ends the window (the judged rate) reads 900 / 10.01 or
    1000 / 11: the mean over the last 4 s, reported beside it, must read
    the same to a part in a thousand."""
    driver = core.load_module(ROOTS, "drivers", "engine_hybrid")
    stamps = [float(t) for t in range(1, 10)] + [last_burst_at, 11.0]
    clients = [
        types.SimpleNamespace(times=[50.0 + t for t in stamps for _ in range(50)]),
        types.SimpleNamespace(times=[50.0 + t for t in stamps for _ in range(50)]),
        types.SimpleNamespace(times=[]),
    ]
    # by hand: the count stands at 600, 700, 800 between 6, 7, 8, 9 and at
    # 900 from 9 until the tenth burst
    by_hand = (
        600 * np.log(7 / 6) + 700 * np.log(8 / 7) + 800 * np.log(9 / 8)
        + 900 * np.log(min(last_burst_at, 10.0) / 9)
        + 1000 * np.log(10.0 / min(last_burst_at, 10.0))
    ) / 4
    got = driver.mean_rate(clients, 50.0, 10.0, 4.0)
    assert got == pytest.approx(by_hand, rel=1e-9)
    assert got == pytest.approx(93.766, rel=5e-4)


def test_the_benchmarks_reference_is_the_repos_byte_for_byte():
    root = os.path.dirname(core.BENCH_DIR)
    with open(os.path.join(root, "odh_kubeflow_tpu/reference/granitemoehybrid.py")) as f:
        ours = f.read()
    with open(os.path.join(core.BENCH_DIR, "reference/granitemoehybrid.py")) as f:
        assert f.read() == ours
    assert "odh_kubeflow_tpu" not in "".join(
        line for line in ours.splitlines() if line.startswith(("import", "from"))
    )


@pytest.fixture(scope="module")
def tiny_reference(family):
    config = core.load_json(ROOTS, "configs", "tiny-hybrid")
    params = family.make_params(config, 2**31 + 5)
    ref = core.load_module(ROOTS, "reference", "granitemoehybrid")
    return config, params, ref


def test_the_copy_computes_what_the_repos_reference_computes(tiny_reference):
    from odh_kubeflow_tpu.reference import granitemoehybrid as repo

    config, params, copy = tiny_reference
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 256, size=48))
    a, top_a = repo.logits(params, tokens, config)
    b, top_b = copy.logits(params, tokens, config)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(top_a), np.asarray(top_b))
    assert top_a.shape == (4, 48, 3)


@pytest.mark.parametrize(
    "prec", [{"act": "int8"}, {"state": "bf16"}], ids=["int8-activations", "bf16-state"]
)
def test_a_lower_precision_is_told_from_the_reference(tiny_reference, prec):
    config, params, ref = tiny_reference
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 256, size=48))
    sound, _ = ref.logits(params, tokens, config)
    low, _ = ref.logits(params, tokens, config, ref.Precision(**prec))
    assert float(jnp.abs(low - sound).max()) > 1e-4


def test_both_controls_fail_the_limits_a_sound_run_passes(manifest, tiny_reference):
    """The reference in each lower precision, put in the program's
    place on tokens the reference itself chose: each must fail at least
    one of the tiny cell's limits, which the sound choice (gap 0,
    routing and state the reference's own) passes."""
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
            lg, _ = sound(params, jnp.asarray(seq))
            served.append(int(jnp.argmax(lg[n + len(served) - 1])))
        sample.append(types.SimpleNamespace(spec={"prompt": prompt, "id": n}, tokens=served))
    limits = run.cell["limits"]
    watched = sample[0].spec["prompt"] + sample[0].tokens[:-1]
    readings = driver.control_readings(run, params, sample, watched)
    assert set(readings) == {"int8_activations", "bf16_state"}
    for name, got in readings.items():
        assert any(got[k] > limits[k] for k in limits), (name, got, limits)
    assert readings["bf16_state"]["ssm_state_gap"] > limits["ssm_state_gap"]


def test_the_first_layers_reference_state_is_the_whole_references(tiny_reference, manifest):
    """``reference_state`` runs the layers down to the first Mamba-2
    layer alone: the state it gives is the whole reference's there."""
    config, params, ref = tiny_reference
    run, _ = core.prepare(
        CELL, 1, 1.0, False, t0=time.monotonic(), roots=ROOTS,
        manifest=manifest, rehearsal=True,
    )
    driver = core.load_module(ROOTS, "drivers", run.mix["driver"])
    tokens = np.random.default_rng(2).integers(1, 256, size=37).tolist()
    seq = np.zeros(64, np.int32)
    seq[:37] = tokens
    whole = ref.logits_and_states(params, jnp.asarray(seq), config, stop=37)[2]
    got = driver.reference_state(run, params, tokens)
    assert got.shape == (8, 16, 16)
    np.testing.assert_allclose(got, np.asarray(whole[0]), rtol=1e-5, atol=1e-7)


def test_a_program_that_keeps_its_state_in_bf16_comes_out_not_correct(manifest):
    """The PROGRAM's own lower precision, through the engine and the
    cell's check: with every decode step's state write rounded to
    bfloat16 (``state_rounded_to_bf16``) the run serves every request
    and fails ``ssm_state_gap``, the number read from the row of the
    state that the engine holds."""
    driver = core.load_module(ROOTS, "drivers", "engine_hybrid")
    with driver.state_rounded_to_bf16():
        result = core.run_cell(
            CELL, 2**31 + 19, 1.0, False, t0=time.monotonic(), roots=ROOTS,
            manifest=manifest, rehearsal=True,
        )
    checks = {c["name"]: c for c in result["checks"]}
    assert result["correct"] is False and result["failed"] == 0
    assert not checks["ssm_state_gap"]["ok"], checks["ssm_state_gap"]
    assert checks["failed_requests"]["ok"] and checks["engine_failure"]["ok"]
    from odh_kubeflow_tpu.ops import pallas_ssm

    assert pallas_ssm.ssm_step_plain.__module__ == pallas_ssm.__name__  # restored


# ---- the counts, against numbers worked by hand ----------------------------


def test_family_reads_the_configuration_file(family, published):
    assert family.layer_kinds(published) == ("state",) * 5 + (None,) + ("state",) * 4
    assert family.held(published) == (0, 72)
    assert family.kinds(published) == (9, 1)
    assert family.mamba_dims(published) == (128, 64, 128, 8192, 8448, 16768)
    cfg = family.program_config(published)
    assert (cfg.num_experts, cfg.experts_held, cfg.num_layers) == (72, (0, 72), 10)
    assert (cfg.head_dim, cfg.d_inner, cfg.conv_dim) == (128, 8192, 8448)
    assert cfg.layer_kinds == family.layer_kinds(published)
    # every source key is quoted as published, bar the reduced one
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f]
    source = next(r for r in rows if r["name"] == "granite-4.0-h-small")
    assert published["source"] == source["source_url"]
    assert published["reduced"] == ["num_hidden_layers"]
    for key, value in source["config"].items():
        if key in published["reduced"]:
            assert published["reduced_from"][key] == value
        else:
            assert published[key] == value, key


def test_decode_step_bytes_against_hand_arithmetic(family, published):
    # a Mamba-2 layer: 4096 x 16768 in, 8192 x 4096 out = 102.24 M; the
    # attention layer 2 x 4096 x 4096 + 2 x 4096 x 1024 = 41.94 M
    assert family.mamba_matmul_weights(published) == 102_236_160
    assert family.attention_matmul_weights(published) == 41_943_040
    assert family.expert_weights(published) == 9_437_184
    assert family.shared_weights(published) == 18_874_368
    # a slot's state in a layer: 128 x 64 x 128 float32 + 3 x 8448 bf16
    assert family.state_bytes_per_slot_layer(published) == 4_194_304 + 50_688
    parts = family.decode_step_bytes(published, 712.0, 80_000.0, 32.0)
    assert parts["mamba"] == 9 * 102_236_160
    assert parts["attention"] == 41_943_040
    assert parts["shared"] == 10 * 18_874_368
    assert parts["router"] == 10 * 4096 * 72 * 4
    assert parts["routed"] == 712 * 9_437_184
    assert parts["head"] == 100_352 * 4096 * 2
    assert parts["kv"] == 4096 * 80_000  # one attention layer, 4 KB a position
    # read AND written: 9 layers x 32 slots x 4.245 MB x 2 = 2.445 GB
    assert parts["state"] == 2 * 9 * 32 * 4_244_992
    assert sum(parts.values()) == pytest.approx(11.5e9, rel=0.03)


def test_ssd_scan_work_against_hand_arithmetic(family, published):
    work = family.ssd_scan_work(published, 2048)
    # 8 chunks of 256: C B^T 2 x 256^2 x 128 = 16.8 M once; per head
    # 2 x 256^2 x 64 + 4 x 256 x 128 x 64 = 16.8 M, x 128 heads
    assert work["flops"] == 8 * (16_777_216 + 128 * 16_777_216)
    # x and y 2 x 2048 x 8192 bf16, B and C 2 x 2048 x 128 bf16, dt
    # 2048 x 128 float32, the state in and out 2 x 4.19 MB
    assert work["bytes"] == 67_108_864 + 1_048_576 + 1_048_576 + 8_388_608
    short = family.ssd_scan_work(published, 64)
    assert short["flops"] == 2 * 64 * 64 * 128 + 128 * (2 * 64 * 64 * 64 + 4 * 64 * 128 * 64)


# ---- the readers, on a trace made by hand ----------------------------------


def fake_run(family, published, **values):
    moe_rows = 32 * 10 + 72 * 16
    ops = {
        f"%moe_local_ffn.1 = bf16[{moe_rows},4096]{{1,0}} custom-call(bf16[{moe_rows},4096] %x)": 0.9,
        "%moe_local_ffn.2 = bf16[29696,4096]{1,0} custom-call(bf16[29696,4096] %x)": 5.0,
        "%ssm_decode_update.3 = (f32[9,32,64,128,128]) custom-call(%s)": 0.5,
        "%ssd_chunk_scan.4 = (bf16[1,2048,8192]) custom-call(%x)": 0.02,
    }
    modules = {
        "jit__decode_chunk(1)": (2.4, 10), "jit__prefill_part_2048(2)": (0.5, 4),
        "jit__prefill_256(3)": (0.03, 1),
    }
    v = {
        "decode_steps_per_call": 8.0, "moe_experts_hit_per_step": 712.0,
        "live_full": 80_000.0, "live_slots": 30.0, "n_slots": 32, **values,
    }
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
    by = family.decode_step_bytes(published, 712.0, 80_000.0, 30.0)
    want = {
        "decode_hbm_roofline": 100 * sum(by.values()) / 819e9 * 80 / 2.4,
        "moe_decode_roofline": 100 * by["routed"] / 819e9 * 80 / 0.9,
        "ssm_decode_roofline": 100 * by["state"] / 819e9 * 80 / 0.5,
        "ssd_prefill_roofline": 100 * 9 * (
            4 * max(family.ssd_scan_work(published, 2048)["flops"] / 197e12,
                    family.ssd_scan_work(published, 2048)["bytes"] / 819e9)
            + max(family.ssd_scan_work(published, 256)["flops"] / 197e12,
                  family.ssd_scan_work(published, 256)["bytes"] / 819e9)
        ) / 0.02,
        "prefill_device_share": 100 * 0.53 / 6.0,
    }[name.split(".")[0]]
    assert share == pytest.approx(want)


def test_readers_find_nothing_where_the_program_has_nothing(family, published):
    spec = core.load_json(ROOTS, "metrics", "ssm_decode_roofline.hybrid")
    reader = core.load_module(ROOTS, "metrics", spec["reader"])
    run = fake_run(family, published)
    run.reduced["modules"] = {}
    assert reader.read(run, spec["params"]) is None
    run = fake_run(family, published, decode_steps_per_call=None)
    assert reader.read(run, spec["params"]) is None


def test_the_cell_is_the_issues(published):
    cell = core.load_json((core.BENCH_DIR,), "cells", REAL_CELL)
    mix = core.load_json((core.BENCH_DIR,), "traffic", cell["traffic"])
    assert cell["program"] == {
        "n_slots": 32, "max_len": 13312, "prefill_chunk": 2048,
        "prompt_buckets": [64, 256, 1024, 2048],
    }
    assert mix["driver"] == "engine_hybrid" and mix["drain"] is False
    assert mix["prompt"] == {
        "dist": "lognormal", "median": 1024, "sigma": 1.4, "min": 64, "max": 12288,
    }
    assert mix["output"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.7, "min": 16, "max": 1024,
    }
    assert mix["schedule_seed"] in (37, 41, 43) and mix["greedy_share"] == 0.1
    assert set(cell["limits"]) == set(cell["limits_why"]) == {
        "served_logit_gap_max", "served_logit_gap_mean", "routing_differs_share",
        "ssm_state_gap",
    }
    manifest = core.load_manifest()
    entry = next(w for w in manifest["workloads"] if w["name"] == REAL_CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        cell["config"], cell["traffic"], 1,
    )
    declared = {m["name"]: m for m in manifest["per_layer"]}
    for name in cell["per_layer"]:
        assert name in declared
        if name.endswith(".hybrid"):
            assert declared[name]["workloads"] == [REAL_CELL]
            assert declared[name]["moves"] == "serve_tokens_per_s"
