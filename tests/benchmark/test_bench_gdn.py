"""One chip's share of a stack with a DELTA-RULE state beside its keys
and values (``qwen3_next``) through the benchmark on the CPU at a tiny
size: the driver (``drivers/engine_gdn.py``), the family's own weights
and counts, the reference's copy, both controls and every new reader, on
the tiny files beside this test. The manifest it runs under is
``data/tiny/BENCHMARK.gdn.json``."""

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
CELL = "tiny-gdn-saturated"
REAL_CELL = "qwen3next-ep2-stage-longmix-saturated"
REAL_CONFIG = "qwen3-next-80b-a3b-ep2-stage"
COUNTERS = {
    "moe_experts_hit_share.gdn", "moe_tile_fill.gdn", "state_cache_gb.gdn",
    "kv_cache_gb.gdn",
}
DEVICE_TRACE = {
    "decode_hbm_roofline.gdn", "moe_decode_roofline.gdn", "gdn_decode_roofline.gdn",
    "gdn_prefill_roofline.gdn", "prefill_device_share.gdn",
}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(TINY, "BENCHMARK.gdn.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family():
    return core.load_module(ROOTS, "families", "qwen3_next")


@pytest.fixture(scope="module")
def published():
    return core.load_json((core.BENCH_DIR,), "configs", REAL_CONFIG)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_share_prints_the_contracts_last_line(trace, manifest):
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
    assert checks["routing_differs_share"]["ok"] and checks["gdn_state_gap"]["ok"]
    # a request was decoding at the close and its row of the engine's
    # state was read: float32 against float32, so rounding alone
    assert 0 < checks["gdn_state_gap"]["value"] < 1e-4
    if trace:
        cell = core.load_json(ROOTS, "cells", CELL)
        # the counters are read wherever the program has them; a roofline
        # share is a device number, and the CPU's trace has no programs
        got = set(line["metrics"])
        assert got == set(cell["per_layer"]) - DEVICE_TRACE
        assert COUNTERS <= got
        assert line["metrics"]["serve_rate_mean5s.gdn"]["value"] > 0
        assert 0 < line["metrics"]["moe_experts_hit_share.gdn"]["value"] <= 100
        assert 0 < line["metrics"]["moe_tile_fill.gdn"]["value"] <= 100
        # the tiny file states a float32 cache: 1 attention layer x 4
        # slots x 128 positions of 2 x 32; 3 DeltaNet layers x 4 slots x
        # (4 x 8 x 16 of state + 3 x 96 of convolution inputs)
        assert line["metrics"]["kv_cache_gb.gdn"]["value"] == pytest.approx(
            1 * 4 * 128 * 2 * 32 * 4 / 1e9
        )
        assert line["metrics"]["state_cache_gb.gdn"]["value"] == pytest.approx(
            3 * 4 * (4 * 8 * 16 + 3 * 96) * 4 / 1e9
        )
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_a_program_without_the_family_fails_before_weights(manifest, monkeypatch):
    """The parent commit has no ``models/qwen3_next.py``: the driver must
    stop at the program's config object, in seconds."""
    import sys

    monkeypatch.setitem(sys.modules, "odh_kubeflow_tpu.models.qwen3_next", None)
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
    with open(os.path.join(root, "odh_kubeflow_tpu/reference/qwen3_next.py")) as f:
        ours = f.read()
    with open(os.path.join(core.BENCH_DIR, "reference/qwen3_next.py")) as f:
        assert f.read() == ours
    assert "odh_kubeflow_tpu" not in "".join(
        line for line in ours.splitlines() if line.startswith(("import", "from"))
    )


@pytest.fixture(scope="module")
def tiny_reference(family):
    config = core.load_json(ROOTS, "configs", "tiny-gdn")
    params = family.make_params(config, 2**31 + 5)
    ref = core.load_module(ROOTS, "reference", "qwen3_next")
    return config, params, ref


def test_the_copy_computes_what_the_repos_reference_computes(tiny_reference):
    from odh_kubeflow_tpu.reference import qwen3_next as repo

    config, params, copy = tiny_reference
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 256, size=48))
    a, top_a = repo.logits(params, tokens, config)
    b, top_b = copy.logits(params, tokens, config)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(top_a), np.asarray(top_b))
    # the router's width is the published one (16), the held experts 8
    assert top_a.shape == (4, 48, 3) and int(top_a.max()) >= 8
    assert params["layers"]["router"].shape == (4, 64, 16)
    assert params["layers"]["moe_gate"]["q"].shape == (4, 8, 64, 32)


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
    assert readings["bf16_state"]["gdn_state_gap"] > limits["gdn_state_gap"]


def test_the_first_layers_reference_state_is_the_whole_references(tiny_reference, manifest):
    """``reference_state`` runs layer 0 alone: the state it gives is the
    whole reference's there."""
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
    assert whole.shape == (3, 4, 8, 16)
    got = driver.reference_state(run, params, tokens)
    np.testing.assert_allclose(got, np.asarray(whole[0]), rtol=1e-5, atol=1e-7)


def test_a_program_that_keeps_its_state_in_bf16_comes_out_not_correct(manifest):
    """The PROGRAM's own lower precision, through the engine and the
    cell's check: with every decode step's state write rounded to
    bfloat16 (``state_rounded_to_bf16``) the run serves every request
    and fails ``gdn_state_gap``, the number read from the row of the
    state that the engine holds."""
    driver = core.load_module(ROOTS, "drivers", "engine_gdn")
    with driver.state_rounded_to_bf16():
        result = core.run_cell(
            CELL, 2**31 + 19, 1.0, False, t0=time.monotonic(), roots=ROOTS,
            manifest=manifest, rehearsal=True,
        )
    checks = {c["name"]: c for c in result["checks"]}
    assert result["correct"] is False and result["failed"] == 0
    assert not checks["gdn_state_gap"]["ok"], checks["gdn_state_gap"]
    assert checks["failed_requests"]["ok"] and checks["engine_failure"]["ok"]
    from odh_kubeflow_tpu.ops import pallas_gdn

    assert pallas_gdn.gdn_step_plain.__module__ == pallas_gdn.__name__  # restored


# ---- the counts, against numbers worked by hand ----------------------------


def test_family_reads_the_configuration_file(family, published):
    assert family.layer_kinds(published) == ("state",) * 3 + (None,)
    assert family.held(published) == (0, 256) and family.router_width(published) == 512
    assert family.kinds(published) == (9, 3)
    assert family.gdn_dims(published) == (16, 32, 128, 128, 2048, 4096, 8192)
    cfg = family.program_config(published)
    assert (cfg.num_experts, cfg.experts_held, cfg.num_layers) == (512, (0, 256), 12)
    assert (cfg.head_dim, cfg.rotary_dim, cfg.kv_dim, cfg.conv_dim) == (256, 64, 512, 8192)
    assert cfg.layer_kinds == family.layer_kinds(published)
    assert cfg.vocab_size == 75968 and cfg.rope_theta == 1e7
    # every source key is quoted as published, bar the reduced ones
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f]
    source = next(r for r in rows if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert published["source"] == source["source_url"]
    assert published["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in source["config"].items():
        if key in published["reduced"]:
            assert published["reduced_from"][key] == value
        else:
            assert published[key] == value, key
    # the floors: whole periods, >= 8 experts, >= an eighth of the vocabulary
    assert published["num_hidden_layers"] % published["full_attention_interval"] == 0
    assert published["vocab_size"] * 2 == source["config"]["vocab_size"]


def test_decode_step_bytes_against_hand_arithmetic(family, published):
    # a DeltaNet mixer: 2048 x 12288 in + 4096 x 2048 out = 33.55 M; the
    # attention mixer 2048 x 8192 + 2 x 2048 x 512 + 4096 x 2048 = 27.26 M
    assert family.gdn_matmul_weights(published) == 33_554_432
    assert family.gdn_float32_bytes(published) == 4 * (2048 * 64 + 4 * 8192)
    assert family.attention_matmul_weights(published) == 27_262_976
    assert family.expert_weights(published) == 3_145_728
    assert family.shared_weights(published) == 3_145_728
    # 6144 B a position in the three attention layers together
    assert 3 * family.kv_bytes_per_token_layer(published) == 6144
    # a slot's state in a layer: 32 x 128 x 128 float32 + 3 x 8192 bf16
    assert family.state_bytes_per_slot_layer(published) == 2_097_152 + 49_152
    parts = family.decode_step_bytes(published, 12 * 119.0, 80_000.0, 32.0)
    assert parts["gdn"] == 9 * (33_554_432 + 655_360)
    assert parts["attention"] == 3 * 27_262_976
    assert parts["shared"] == 12 * 3_145_728
    assert parts["router"] == 12 * 2048 * 512 * 4
    assert parts["routed"] == 12 * 119 * 3_145_728  # 4.49 GB
    assert parts["head"] == 75_968 * 2048 * 2
    assert parts["kv"] == 6144 * 80_000
    # read AND written: 9 layers x 32 slots x 2.146 MB x 2 = 1.236 GB
    assert parts["state"] == 2 * 9 * 32 * 2_146_304
    assert sum(parts.values()) == pytest.approx(7.0e9, rel=0.03)


def test_gdn_scan_work_against_hand_arithmetic(family, published):
    work = family.gdn_scan_work(published, 2048)
    # 32 chunks of 64. Per key head K K^T and Q K^T: 4 x 64^2 x 128 = 2.097 M,
    # x 16. Per value head: the solve 64^2 x 256 = 1.049 M, three products
    # on the state 6 x 64 x 128 x 128 = 6.291 M, the in-chunk product
    # 2 x 64^2 x 128 = 1.049 M: 8.389 M, x 32
    assert work["flops"] == 32 * (16 * 2_097_152 + 32 * 8_388_608)
    # q and k 2 x 2048 x 2048 bf16, v and o 2 x 2048 x 4096 bf16, g and
    # beta 2 x 2048 x 32 float32, the state in and out 2 x 2.097 MB
    assert work["bytes"] == 16_777_216 + 33_554_432 + 524_288 + 4_194_304
    short = family.gdn_scan_work(published, 64)
    assert short["flops"] == 16 * 2_097_152 + 32 * 8_388_608
    assert family.ssd_scan_work is family.gdn_scan_work
    from odh_kubeflow_tpu.ops import pallas_gdn

    assert family.GDN_CHUNK == pallas_gdn.DEFAULT_CHUNK


# ---- the readers, on a trace made by hand ----------------------------------


def fake_run(family, published, **values):
    moe_rows = 32 * 10 + 256 * 16
    ops = {
        f"%moe_local_ffn.1 = bf16[{moe_rows},2048]{{1,0}} custom-call(bf16[{moe_rows},2048] %x)": 0.9,
        "%moe_local_ffn.2 = bf16[36864,2048]{1,0} custom-call(bf16[36864,2048] %x)": 5.0,
        "%gdn_decode_update.3 = (f32[9,32,32,128,128], f32[32,32,128]) custom-call(%s)": 0.5,
        "%gdn_chunk_scan.4 = (bf16[1,32,64,4096], f32[1,32,128,128]) custom-call(%x)": 0.02,
    }
    modules = {
        "jit__decode_chunk(1)": (2.4, 10), "jit__prefill_part_2048(2)": (0.5, 4),
        "jit__prefill_256(3)": (0.03, 1),
    }
    v = {
        "decode_steps_per_call": 8.0, "moe_experts_hit_per_step": 1428.0,
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
    by = family.decode_step_bytes(published, 1428.0, 80_000.0, 30.0)
    least = lambda n: max(  # noqa: E731
        family.gdn_scan_work(published, n)["flops"] / 197e12,
        family.gdn_scan_work(published, n)["bytes"] / 819e9,
    )
    want = {
        "decode_hbm_roofline": 100 * sum(by.values()) / 819e9 * 80 / 2.4,
        "moe_decode_roofline": 100 * by["routed"] / 819e9 * 80 / 0.9,
        "gdn_decode_roofline": 100 * by["state"] / 819e9 * 80 / 0.5,
        "gdn_prefill_roofline": 100 * 9 * (4 * least(2048) + least(256)) / 0.02,
        "prefill_device_share": 100 * 0.53 / 6.0,
    }[name.split(".")[0]]
    assert share == pytest.approx(want)


def test_readers_find_nothing_where_the_program_has_nothing(family, published):
    spec = core.load_json(ROOTS, "metrics", "gdn_decode_roofline.gdn")
    reader = core.load_module(ROOTS, "metrics", spec["reader"])
    run = fake_run(family, published)
    run.reduced["modules"] = {}
    assert reader.read(run, spec["params"]) is None
    run = fake_run(family, published, decode_steps_per_call=None)
    assert reader.read(run, spec["params"]) is None
    value = core.load_module(ROOTS, "metrics", "value")
    assert value.read(run, {"key": "moe_tile_fill"}) is None


def test_the_cell_is_the_issues(published):
    cell = core.load_json((core.BENCH_DIR,), "cells", REAL_CELL)
    mix = core.load_json((core.BENCH_DIR,), "traffic", cell["traffic"])
    hybrid = core.load_json((core.BENCH_DIR,), "traffic", "longmix-saturated-hybrid")
    assert cell["program"] == {
        "n_slots": 32, "max_len": 13312, "prefill_chunk": 2048,
        "prompt_buckets": [64, 256, 1024, 2048],
    }
    assert mix["driver"] == "engine_gdn" and mix["drain"] is False
    # longmix-saturated's lengths: the same queue before a third architecture
    for key in ("prompt", "output", "sampling", "greedy_share", "close_timeout_s",
                "check_requests", "trace_s"):
        assert mix[key] == hybrid[key], key
    assert mix["schedule_seed"] in (37, 41, 43)
    assert mix["arrivals"]["dist"] == "exponential"
    assert set(cell["limits"]) == set(cell["limits_why"]) == {
        "served_logit_gap_max", "served_logit_gap_mean", "routing_differs_share",
        "gdn_state_gap",
    }
    manifest = core.load_manifest()
    entry = next(w for w in manifest["workloads"] if w["name"] == REAL_CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        cell["config"], cell["traffic"], 1,
    )
    assert entry["why"] == cell["why"] and len(entry["why"]) <= 200
    assert manifest["workloads"][-1] is entry and manifest["configs"][-1]["name"] == REAL_CONFIG
    rate = next(m for m in manifest["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert rate["workloads"][-1] == REAL_CELL
    declared = {m["name"]: m for m in manifest["per_layer"]}
    for name in cell["per_layer"]:
        assert name in declared
        if name.endswith(".gdn"):
            assert declared[name]["workloads"] == [REAL_CELL]
            assert declared[name]["moves"] == "serve_tokens_per_s"
            spec = core.load_json((core.BENCH_DIR,), "metrics", name)
            for key in ("unit", "better", "layer", "source", "moves"):
                assert spec[key] == declared[name][key], (name, key)
    assert sum(n.endswith(".gdn") for n in cell["per_layer"]) == 10
