"""One chip's share of a stage of a stack whose layers attend only the
keys an indexer picks (``keye_vl``) through the benchmark on the CPU at a
tiny size: the driver (``drivers/engine_sparse.py``), the family's own
weights and counts, the reference's copy, the three controls and every
new reader, on the tiny files beside this test. The manifest it runs
under is ``data/tiny/BENCHMARK.sparse.json``."""

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
CELL = "tiny-sparse-saturated"
REAL_CELL = "keyevl2-ep8-stage-longdoc-saturated"
REAL_CONFIG = "keye-vl-2.0-30b-a3b-ep8-stage"
DEVICE_TRACE = {
    "index_scores_roofline.sparse", "sparse_attend_roofline.sparse",
    "sparse_prefill_roofline.sparse", "decode_hbm_roofline.sparse",
    "moe_decode_roofline.sparse", "prefill_device_share.sparse",
}
NEW = DEVICE_TRACE | {
    "moe_experts_hit_share.sparse", "selected_share.sparse", "kv_cache_gb.sparse",
    "serve_rate_mean5s.sparse",
}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(TINY, "BENCHMARK.sparse.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family():
    return core.load_module(ROOTS, "families", "keye_vl")


@pytest.fixture(scope="module")
def published():
    return core.load_json((core.BENCH_DIR,), "configs", REAL_CONFIG)


def rehearse(manifest, trace=False):
    return core.run_cell(
        CELL, 2**31 + 19, 1.0, trace, t0=time.monotonic(), roots=ROOTS,
        manifest=manifest, rehearsal=True,
    )


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_share_prints_the_contracts_last_line(trace, manifest):
    line = json.loads(json.dumps(rehearse(manifest, bool(trace))))
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    checks = {c["name"]: c for c in line["checks"]}
    assert checks["compiles_in_window"]["ok"] and checks["moe_dropped"]["ok"]
    # float32 against float32: the program's selection IS the reference's,
    # and a request was decoding at the close whose slot's indexer keys
    # were read from the stopped engine
    assert checks["selection_differs_share"]["value"] == 0.0
    assert checks["selection_inexact_share"]["value"] == 0.0
    assert 0 <= checks["index_key_gap"]["value"] < 1e-4
    if trace:
        cell = core.load_json(ROOTS, "cells", CELL)
        # the counters are read wherever the program has them; a roofline
        # share is a device number, and the CPU's trace has no programs
        got = set(line["metrics"])
        assert got == set(cell["per_layer"]) - DEVICE_TRACE
        assert line["metrics"]["serve_rate_mean5s.sparse"]["value"] > 0
        # the traffic is sparse: topk 8 of contexts of tens of positions
        assert 5 < line["metrics"]["selected_share.sparse"]["value"] < 60
        # 3 layers x 4 slots x 160 positions x (2 x 2 x 16 of keys and
        # values + 8 of indexer keys), float32
        assert line["metrics"]["kv_cache_gb.sparse"]["value"] == pytest.approx(
            3 * 4 * 160 * (64 + 8) * 4 / 1e9
        )
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_a_program_without_the_family_fails_before_weights(manifest, monkeypatch):
    """The parent commit has no ``models/keye_vl.py``: the driver must
    stop at the program's config object, in seconds."""
    import sys

    monkeypatch.setitem(sys.modules, "odh_kubeflow_tpu.models.keye_vl", None)
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
    with open(os.path.join(root, "odh_kubeflow_tpu/reference/keye_vl.py")) as f:
        ours = f.read()
    with open(os.path.join(core.BENCH_DIR, "reference/keye_vl.py")) as f:
        assert f.read() == ours
    assert "odh_kubeflow_tpu" not in "".join(
        line for line in ours.splitlines() if line.startswith(("import", "from"))
    )


@pytest.fixture(scope="module")
def tiny_reference(family):
    config = core.load_json(ROOTS, "configs", "tiny-sparse")
    params = family.make_params(config, 2**31 + 5)
    ref = core.load_module(ROOTS, "reference", "keye_vl")
    return config, params, ref


def test_the_copy_computes_what_the_repos_reference_computes(tiny_reference):
    from odh_kubeflow_tpu.reference import keye_vl as repo

    config, params, copy = tiny_reference
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 256, size=128))
    keep = jnp.arange(16, 128, 8)
    for a, b in zip(
        repo.logits_and_selection(params, tokens, config, keep=keep),
        copy.logits_and_selection(params, tokens, config, keep=keep),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert params["layers"]["wq"]["q"].shape == (3, 64, 64)
    assert params["layers"]["wk_idx"]["q"].shape == (3, 64, 8)
    assert params["layers"]["w_idx"].shape == (3, 64, 2)
    assert params["layers"]["moe_gate"]["q"].shape == (3, 4, 64, 32)
    assert params["layers"]["router"].shape == (3, 64, 8)  # the published width
    # the seeded indexer's scores have a spread of ~1
    scores, _, _, _ = copy.index_and_selection(params, tokens, config, layer=0)
    spread = float(jnp.std(scores[jnp.isfinite(scores)]))
    assert 0.2 < spread < 5, spread


@pytest.mark.parametrize(
    "prec", [{"act": "int8"}, {"index": "bf16"}], ids=["int8-activations", "bf16-index"]
)
def test_a_lower_precision_is_told_from_the_reference(tiny_reference, prec):
    config, params, ref = tiny_reference
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 256, size=128))
    keep = jnp.arange(16, 128, 2)
    sound = ref.logits_and_selection(params, tokens, config, keep=keep)
    low = ref.logits_and_selection(
        params, tokens, config, ref.Precision(**prec), keep=keep
    )
    assert float(jnp.abs(low[0] - sound[0]).max()) > 1e-4
    # WHICH keys are read moves
    assert bool(jnp.any(jnp.sort(low[2], -1) != jnp.sort(sound[2], -1)))


def _served_by_the_reference(config, params, ref, lengths=(70, 33), n=12):
    rng = np.random.default_rng(1)
    sound = jax.jit(lambda p, seq: ref.logits(p, seq, config)[0])  # compiled once
    sample = []
    for length in lengths:
        prompt = rng.integers(1, 256, size=length).tolist()
        served = []
        for _ in range(n):
            seq = np.zeros(128, np.int32)
            seq[: length + len(served)] = prompt + served
            lg = sound(params, jnp.asarray(seq))
            served.append(int(jnp.argmax(lg[length + len(served) - 1])))
        sample.append(
            types.SimpleNamespace(spec={"prompt": prompt, "id": length}, tokens=served)
        )
    return sample


def test_both_lower_precisions_fail_the_limits_a_sound_run_passes(
    manifest, tiny_reference
):
    """The reference in each lower precision, put in the program's place
    on tokens the reference itself chose: each must fail at least one of
    the tiny cell's limits, which the program's sound reading of the
    same sample passes."""
    config, params, ref = tiny_reference
    run, _ = core.prepare(
        CELL, 2**31 + 5, 1.0, False, t0=time.monotonic(), roots=ROOTS,
        manifest=manifest, rehearsal=True,
    )
    driver = core.load_module(ROOTS, "drivers", run.mix["driver"])
    sample = _served_by_the_reference(config, params, ref)
    limits = run.cell["limits"]
    watched = sample[0].spec["prompt"] + sample[0].tokens[:-1]
    readings = driver.control_readings(run, params, sample, watched)
    assert set(readings) == {"int8_activations", "bf16_index"}
    for name, got in readings.items():
        assert set(got) == set(limits)
        assert any(got[k] > limits[k] for k in limits), (name, got, limits)
    # the scores' accumulation moves WHICH keys are kept and nothing
    # above them; int8 activations move the keys themselves
    assert readings["bf16_index"]["selection_inexact_share"] > limits[
        "selection_inexact_share"
    ]
    assert readings["bf16_index"]["index_key_gap"] == 0.0
    assert readings["int8_activations"]["selection_inexact_share"] == 0.0
    assert readings["int8_activations"]["index_key_gap"] > limits["index_key_gap"]
    sound = driver.check_against_reference(
        run, params, sample, run.family.program_config(config)
    )
    assert float(sound["gaps"].max()) == 0.0
    assert sound["routing_differs_share"] <= limits["routing_differs_share"]
    assert sound["selection_differs_share"] <= limits["selection_differs_share"]
    assert sound["selection_inexact_share"] <= limits["selection_inexact_share"]


@pytest.mark.parametrize(
    "control", ["selection_is_the_last_keys", "index_scores_rounded_to_bf16"]
)
def test_the_program_with_another_selection_comes_out_not_correct(manifest, control):
    """The PROGRAM's own controls, through the engine and the cell's
    check: with a window in the selection's place, and with its indexer's
    scores rounded to bfloat16, the run serves every request and fails
    ``selection_inexact_share`` (the kept positions are not those its
    own indexer inputs give): the check sees WHICH keys are read."""
    from odh_kubeflow_tpu.ops import sparse_attention as sa

    driver = core.load_module(ROOTS, "drivers", "engine_sparse")
    sound = sa.index_scores_plain
    with getattr(driver, control)():
        result = rehearse(manifest)
    checks = {c["name"]: c for c in result["checks"]}
    assert result["correct"] is False and result["failed"] == 0
    assert not checks["selection_inexact_share"]["ok"], checks
    assert checks["failed_requests"]["ok"] and checks["engine_failure"]["ok"]
    assert checks["index_key_gap"]["ok"]  # the keys themselves are sound
    assert sa.index_scores_plain is sound


# ---- the counts, against numbers worked by hand ----------------------------


def test_family_reads_the_configuration_file(family, published):
    assert family.held(published) == (0, 16) and family.router_width(published) == 128
    assert family.indexer(published) == (16, 64, 2048)
    cfg = family.program_config(published)
    assert (cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (12, 32, 4, 128)
    assert (cfg.hidden_size, cfg.expert_width, cfg.vocab_size) == (2048, 768, 18992)
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk) == (16, 64, 2048)
    assert cfg.mrope_section == (16, 24, 24) and cfg.rope_theta == 1e7
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok) == (128, (0, 16), 8)
    assert cfg.layer_kinds == ("indexed",) and cfg.dtype == jnp.bfloat16
    # every item the issue lists under ``assumed`` is there
    assert {
        "qk_norm", "mrope", "indexer_form", "indexer_rotation", "selection_grain",
        "tie_rule", "hadamard_and_fp8", "int8_storage", "weights", "indexer_weights",
        "norm_weights",
    } <= set(published["assumed"])
    for key in ("source", "reduced_from", "reduced_why", "published", "precision", "deployment"):
        assert key in published, key
    assert "vision_tower" in published["published"]
    deployment = published["deployment"]
    assert (deployment["chips"], deployment["stages"]) == (1, 4)
    assert deployment["chips_sharing_a_layer"] == 8
    assert deployment["experts_held"] == {"first": 0, "count": 16}
    # every source key is quoted as published, bar the reduced ones
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f]
    source = next(r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert published["source"] == source["source_url"]
    assert published["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in source["config"].items():
        if key in published["reduced"]:
            assert published["reduced_from"][key] == value
        else:
            assert published[key] == value, key
    # the floors: whole periods and >= 4 layers, >= 8 experts, >= 1/8 of the rows
    assert published["num_hidden_layers"] * 4 == source["config"]["num_hidden_layers"]
    assert published["num_experts"] * 8 == source["config"]["num_experts"]
    assert published["vocab_size"] * 8 == source["config"]["vocab_size"]


def test_cache_bytes_per_position_against_hand_arithmetic(family, published):
    # keys and values: 2 x 4 heads x 128 x bf16 = 2048 B; one indexer key
    # head of 64 x bf16 = 128 B: 2176 B a position a layer
    assert family.cache_bytes_per_position(published) == {"kv": 2048, "index": 128}
    a_position = sum(family.cache_bytes_per_position(published).values())
    # 16 slots x 24576 x 12 layers: 10.27 GB (9.66 + 0.60)
    assert 16 * 24576 * 12 * a_position == pytest.approx(10.27e9, rel=0.001)
    assert 16 * 24576 * 12 * 128 == pytest.approx(0.60e9, rel=0.01)
    # and it is what the program allocates
    from odh_kubeflow_tpu.models.generate import cache_bytes, init_cache

    cache = jax.eval_shape(
        lambda: init_cache(family.program_config(published), 16, 24576)
    )
    assert cache["ik"].shape == (12, 16, 64, 24576)
    assert cache_bytes(cache)["indexed"] == 16 * 24576 * 12 * a_position


def test_decode_step_bytes_against_hand_arithmetic(family, published):
    # attention 2048 x 4096 (q) + 2 x 2048 x 512 (k, v) + 4096 x 2048 (o) = 18.87 M
    assert family.attention_matmul_weights(published) == 18_874_368
    # the indexer: 2048 x 1024 + 2048 x 64 a byte each, 2048 x 16 float32
    assert family.indexer_bytes(published) == 2_097_152 + 131_072 + 131_072
    assert family.expert_weights(published) == 3 * 2048 * 768 == 4_718_592
    # 16 live slots at a context of 9216, 64 % of the 16 x 12 banks hit
    slots, context, hit = 16, 9216, 0.64 * 16 * 12
    parts = family.decode_step_bytes(
        published, 1, slots * 12 * context, slots * 12 * 2048, hit
    )
    assert set(parts) == {"weights", "head", "index", "rows"}
    assert parts["index"] == 16 * 12 * 9216 * 128  # 0.23 GB
    assert parts["rows"] == 16 * 12 * 2048 * 2048  # 0.81 GB: counted once
    assert parts["head"] == 18992 * 2048 * 2  # 0.08 GB
    fixed = 12 * (18_874_368 + 2_359_296 + 2048 * 128 * 4)
    assert parts["weights"] == pytest.approx(fixed + hit * 4_718_592)
    assert parts["weights"] == pytest.approx(0.85e9, rel=0.02)
    total = sum(parts.values())
    assert total == pytest.approx(1.97e9, rel=0.02)  # 2.4 ms at 819 GB/s
    # the indexer's keys and the rows it selects: half of the step
    assert (parts["index"] + parts["rows"]) / total == pytest.approx(0.52, abs=0.02)
    # a step's bytes of keys and values do not grow with the context
    longer = family.decode_step_bytes(
        published, 1, slots * 12 * 20000, slots * 12 * 2048, hit
    )
    assert longer["rows"] == parts["rows"] and longer["index"] > 2 * parts["index"]
    # the counters are totals over ``steps`` steps
    assert family.decode_step_bytes(
        published, 8, 8 * slots * 12 * context, 8 * slots * 12 * 2048, 8 * hit
    ) == pytest.approx(parts)


def test_index_scores_work_against_hand_arithmetic(family, published):
    # a causal pair: 2 x 16 heads x 64 = 2048 FLOPs, one float32 score
    assert family.index_scores_work(published, 1e6) == {"flops": 2048e6, "bytes": 4e6}
    # attention under the selection as a mask: 4 x 32 x 128 a pair
    assert family.sparse_prefill_work(published, 1e6) == {"flops": 16384e6, "bytes": 4e6}
    # a prompt of S positions a layer: 8192 S^2 of attention, 1024 S^2 of indexer
    S = 8500
    pairs = S * (S + 1) / 2
    assert family.sparse_prefill_work(published, pairs)["flops"] == pytest.approx(
        8192 * S * S, rel=1e-3
    )
    assert family.index_scores_work(published, pairs)["flops"] == pytest.approx(
        1024 * S * S, rel=1e-3
    )
    # operations bind both at the bf16 peak
    for work in (family.index_scores_work, family.sparse_prefill_work):
        w = work(published, pairs)
        assert w["flops"] / 197e12 > w["bytes"] / 819e9


# ---- the readers, on a trace made by hand ----------------------------------

TRACED = {
    "decode_steps": 112, "moe_experts_hit": 112 * 120, "sel_causal_rows": 112 * 16 * 12 * 9000,
    "sel_attended_rows": 112 * 16 * 12 * 2048, "prefill_pairs": 400e6,
    "prefill_pairs_first": 10e6,
}


def fake_run(family, published, traced=TRACED, **values):
    ops = {
        "%index_scores.3 = f32[16,1,24576]{2,1,0} custom-call(%l, %o, bf16[16,16,64] %q, f32[16,16,1] %w, bf16[12,16,64,24576] %ik)": 0.08,
        "%index_scores.4 = f32[1,2048,24576]{2,1,0} custom-call(%l, %o, bf16[1,16,2048,64] %q, f32[1,2048,16] %w, bf16[12,1,64,24576] %ik)": 0.5,
        "%index_scores.5 = f32[1,64,24576]{2,1,0} custom-call(%l, %o, bf16[1,16,64,64] %q, f32[1,64,16] %w, bf16[12,1,64,24576] %ik)": 0.02,
        "%decode_attend.6 = bf16[16,4,16,128]{3,2,1,0} custom-call(%l, %o, %p, bf16[16,4,16,128] %q, bf16[1,16,2048,512] %k, bf16[1,16,2048,512] %v, s32[16,1,2048] %kp)": 0.3,
        "%decode_attend.7 = bf16[1,4,16384,128]{3,2,1,0} custom-call(%l, %o, %p, %q, bf16[12,1,24576,512] %k, bf16[12,1,24576,512] %v, %kp, f32[1,2048,24576] %sc, %thr, %cut)": 1.4,
        "%decode_attend.8 = bf16[1,4,16384,128]{3,2,1,0} custom-call(%l, %o, %p, %q, bf16[12,1,24576,512] %k, bf16[12,1,24576,512] %v, %kp)": 0.2,
        "%fusion.461 = bf16[32768,512]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[12,16,24576,512]{3,2,1,0:T(8,128)(2,1)} %fusion.455, s32[32768]{0:T(1024)S(1)} %bitcast.780), kind=kCustom": 0.9,
        "%fusion.463 = bf16[32768,512]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[12,16,24576,512]{3,2,1,0:T(8,128)(2,1)} %fusion.462, s32[32768]{0:T(1024)S(1)} %bitcast.781), kind=kCustom": 0.9,
        "%moe_local_ffn.9 = bf16[384,2048]{1,0} custom-call(bf16[384,2048] %x)": 0.25,
        "%moe_local_ffn.10 = bf16[18432,2048]{1,0} custom-call(bf16[18432,2048] %x)": 0.2,
        "%fusion.11 = bf16[16,2048] fusion(%y)": 0.4,
    }
    modules = {
        "jit__decode_chunk(1)": (2.6, 13), "jit__decode_chunk_greedy(4)": (0.2, 1),
        "jit__prefill_part_2048(2)": (2.4, 12), "jit__prefill_256(3)": (0.03, 1),
    }
    v = {
        "decode_steps_per_call": 8.0, "n_slots": 16, "traced_counters": traced, **values
    }
    cell = core.load_json((core.BENCH_DIR,), "cells", REAL_CELL)
    return types.SimpleNamespace(
        reduced={"modules": modules, "ops": ops, "window_s": 6.0},
        config=published, values=v, family=family, cell=cell,
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
    )


@pytest.mark.parametrize("name", sorted(DEVICE_TRACE), ids=lambda n: n.split(".")[0])
def test_every_new_reader_returns_a_finite_share(family, published, name):
    spec = core.load_json(ROOTS, "metrics", name)
    reader = core.load_module(ROOTS, "metrics", spec["reader"])
    run = fake_run(family, published)
    share = reader.read(run, spec.get("params", {}))
    assert share is not None and 0 < share <= 100, share
    t = TRACED
    by = family.decode_step_bytes(
        published, t["decode_steps"], t["sel_causal_rows"], t["sel_attended_rows"],
        t["moe_experts_hit"],
    )
    steps, pairs = 14 * 8, 390e6
    want = {
        "decode_hbm_roofline": 100 * sum(by.values()) / 819e9 * steps / 2.8,
        "index_scores_roofline": 100 * by["index"] / 819e9 * steps / 0.08,
        # the two gathers and the attention over what they wrote
        "sparse_attend_roofline": 100 * by["rows"] / 819e9 * steps / (0.3 + 0.9 + 0.9),
        "moe_decode_roofline": 100 * 120 * 4_718_592 / 819e9 * steps / 0.25,
        # operations bind both: (2048 + 16384) x pairs x 12 layers at the peak,
        # over the parts' scores (not the decode steps') and masked attention
        # (not the plain first parts')
        "sparse_prefill_roofline": 100 * 12 * 18432 * pairs / 197e12 / (0.5 + 0.02 + 1.4),
        "prefill_device_share": 100 * 2.43 / 6.0,
    }[name.split(".")[0]]
    assert share == pytest.approx(want)


def test_readers_find_nothing_where_the_program_has_nothing(family, published):
    spec = core.load_json(ROOTS, "metrics", "index_scores_roofline.sparse")
    reader = core.load_module(ROOTS, "metrics", spec["reader"])
    run = fake_run(family, published)
    run.reduced["modules"] = {}
    assert reader.read(run, spec["params"]) is None
    # the parent's engine has no such counters (nor its driver the key)
    assert reader.read(fake_run(family, published, traced=None), spec["params"]) is None
    assert reader.read(
        fake_run(family, published, traced={"decode_steps": 5}), spec["params"]
    ) is None
    # another family's run: no work functions to ask
    other = core.load_module(ROOTS, "families", "dense")
    assert reader.read(fake_run(other, published), spec["params"]) is None
    # no part under a selection in the traced seconds: nothing is read
    part = core.load_json(ROOTS, "metrics", "sparse_prefill_roofline.sparse")
    first_only = {**TRACED, "prefill_pairs_first": TRACED["prefill_pairs"]}
    assert reader.read(fake_run(family, published, traced=first_only), part["params"]) is None
    value = core.load_module(ROOTS, "metrics", "value")
    assert value.read(run, {"key": "selected_share"}) is None


def test_the_cell_is_the_issues(published):
    cell = core.load_json((core.BENCH_DIR,), "cells", REAL_CELL)
    mix = core.load_json((core.BENCH_DIR,), "traffic", cell["traffic"])
    assert cell["program"] == {
        "n_slots": 16, "max_len": 24576, "prefill_chunk": 2048,
        "prompt_buckets": [64, 256, 1024, 2048],
    }
    assert cell["traffic"] == "longdoc-saturated-sparse"
    assert mix["driver"] == "engine_sparse" and mix["drain"] is False
    assert mix["prompt"] == {
        "dist": "lognormal", "median": 6144, "sigma": 0.8, "min": 1024, "max": 20480,
    }
    assert mix["output"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.8, "min": 32, "max": 4096,
    }
    assert mix["sampling"] == {"temperature": 0.7, "top_p": 0.95}
    assert (mix["greedy_share"], mix["check_requests"], mix["trace_s"]) == (0.25, 4, 6)
    assert mix["schedule_seed"] == 47
    assert mix["arrivals"]["dist"] == "exponential" and "rate_from" in mix
    # the longest prompt and the longest output fill a slot together
    assert mix["prompt"]["max"] + mix["output"]["max"] == cell["program"]["max_len"]
    # nine prompts of ten lie past the 2048 keys a query keeps
    from benchmark.harness import traffic

    prompts = traffic.quantile_values(mix["prompt"], 1000)
    assert 0.88 < (prompts > 2048).mean() < 0.94
    assert 7500 < prompts.mean() < 8500  # 8.46 k before the cut at 20480
    assert set(cell["limits"]) == set(cell["limits_why"]) == {
        "served_logit_gap_max", "served_logit_gap_mean", "routing_differs_share",
        "selection_differs_share", "selection_inexact_share", "index_key_gap",
    }
    manifest = core.load_manifest()
    names = [w["name"] for w in manifest["workloads"]]
    # MEMBERSHIP, never the last place: the next cell breaks nothing here
    assert REAL_CELL in names
    entry = manifest["workloads"][names.index(REAL_CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        cell["config"], cell["traffic"], 1,
    )
    assert entry["why"] == cell["why"] and len(entry["why"]) <= 200
    # appended behind what was there
    assert names.index(REAL_CELL) > names.index("brumby14b-stage-longgen-saturated")
    config = next(c for c in manifest["configs"] if c["name"] == REAL_CONFIG)
    assert config["reduced"] == published["reduced"]
    assert config["source"] == published["source"]
    rate = next(m for m in manifest["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert REAL_CELL in rate["workloads"]
    declared = {m["name"]: m for m in manifest["per_layer"]}
    for name in cell["per_layer"]:
        assert name in declared
        if name.endswith(".sparse"):
            assert REAL_CELL in declared[name]["workloads"]
            assert declared[name]["moves"] == "serve_tokens_per_s"
            spec = core.load_json((core.BENCH_DIR,), "metrics", name)
            for key in ("unit", "better", "layer", "source", "moves"):
                assert spec[key] == declared[name][key], (name, key)
    assert {n for n in cell["per_layer"] if n.endswith(".sparse")} == NEW
    # what exists, unedited, in the cell's list
    assert {
        "compile_s", "programs_compiled", "runtime_start_s",
        "slot_occupancy.saturated", "device_idle.saturated", "peak_hbm_gb.saturated",
    } <= set(cell["per_layer"])
