"""The harness's arithmetic and the trace reduction, on made-up inputs."""

import json
import math
import os

import numpy as np
import pytest

from benchmark.harness import core, counts, stats, trace, traffic

DATA = os.path.join(os.path.dirname(__file__), "data")
ROOTS = (os.path.join(DATA, "tiny"), core.BENCH_DIR)
TINY = {
    "family": "dense", "hidden_size": 8, "intermediate_size": 16,
    "num_hidden_layers": 2, "num_attention_heads": 2,
    "num_key_value_heads": 1, "head_dim": 4, "vocab_size": 32,
}


def test_percentile_with_its_sample_count():
    assert stats.percentile([4, 1, 3, 2], 50) == (2.5, 4)
    assert stats.percentile([10.0], 95) == (10.0, 1)
    assert stats.percentile(range(101), 95) == (95.0, 101)
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_ttft_is_taken_from_the_due_time_and_tpot_over_all_tokens():
    # due at 10.0, sent late at 10.4, first token at 10.9: the user
    # waited 0.9 s, not 0.5 s
    assert stats.ttft_ms(10.0, 10.9) == pytest.approx(900.0)
    assert stats.ttft_ms(10.0, None) == math.inf
    # bursts of 4: gaps 0, 0, 0, 0.3 ... tpot counts the stall in proportion
    times = [1.0, 1.0, 1.0, 1.0, 1.3, 1.3, 1.3, 1.3]
    assert stats.tpot_ms(times) == pytest.approx(300.0 / 7)
    assert stats.max_gap_ms(times) == pytest.approx(300.0)
    assert stats.tpot_ms([1.0]) is None


def test_iqr_share_is_the_contracts_spread():
    vals = [100, 101, 102, 103, 104, 105]
    import statistics

    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert stats.iqr_share(vals) == pytest.approx((q3 - q1) / 102.5)


def test_strict_flops_of_a_packed_batch_against_a_hand_count():
    # one row of 6 positions holding documents of 4 and 2 tokens
    seg = np.array([[1, 1, 1, 1, 2, 2]])
    lengths = counts.segment_lengths(seg)
    assert sorted(lengths) == [2, 4]
    pairs = counts.attention_pairs(lengths)
    assert pairs == 4 * 5 // 2 + 2 * 3 // 2  # 10 + 3
    D, F, H, Hkv, hd, V, L = 8, 16, 2, 1, 4, 32, 2
    per_layer = D * H * hd + 2 * D * Hkv * hd + H * hd * D + 3 * D * F
    weights_fwd = 2 * 6 * (L * per_layer + D * V)
    attn_fwd = L * pairs * H * 2 * 2 * hd
    dense = core.load_module(ROOTS, "families", "dense")
    assert dense.token_weights_per_layer(TINY) == per_layer
    assert counts.forward_flops(TINY, 6, pairs, per_layer) == {
        "weights": weights_fwd, "attention": attn_fwd,
    }
    # frozen base: matmuls 2 x forward, attention 3 x, recompute never
    assert counts.qlora_step_flops(TINY, 6, pairs, per_layer) == (
        2 * weights_fwd + 3 * attn_fwd
    )
    # a full causal triangle would have credited 21 pairs, not 13
    assert counts.attention_pairs([6]) == 21


def test_a_family_of_the_tests_own_counts_the_experts_a_token_passes():
    cfg = dict(TINY, family="moe", num_local_experts=4, num_experts_per_tok=2)
    dense = core.load_module(ROOTS, "families", "dense")
    moe = core.load_module(ROOTS, "families", "moe")
    extra = moe.token_weights_per_layer(cfg) - dense.token_weights_per_layer(TINY)
    assert extra == (2 - 1) * 3 * 8 * 16 + 8 * 4
    # a decode step reads every expert, whichever the tokens chose
    dense_bytes = counts.decode_step_bytes(TINY, 0, dense.step_weights_per_layer(TINY))
    moe_bytes = counts.decode_step_bytes(cfg, 0, moe.step_weights_per_layer(cfg))
    assert moe_bytes - dense_bytes == 2 * (3 * 3 * 8 * 16 + 8 * 4)
    # the live keys and values are read too: 2 layers x k, v x 1 head x 4 x bf16
    assert counts.decode_step_bytes(TINY, 10, 0) - counts.decode_step_bytes(TINY, 0, 0) == (
        10 * 2 * 2 * 1 * 4 * 2
    )


def test_a_roofline_share_cannot_pass_100_on_a_made_up_kernel():
    pk = counts.peaks("TPU v5 lite")
    cfg = dict(TINY, head_dim=128, num_attention_heads=32, num_key_value_heads=8)
    pairs = 2 * counts.attention_pairs([4096])
    b = counts.flash_bound_s(cfg, 2, 4096, pairs, pk)
    assert b["bound"] == "compute"
    # a kernel that ran exactly at the peak takes the bound's time: 100 %
    flops = pairs * 32 * 2 * 128 * 7
    at_peak_s = flops / pk["bf16_flops_per_s"]
    assert 100.0 * b["seconds"] / at_peak_s == pytest.approx(100.0)
    # any real kernel is slower than the peak, so its share is below 100
    assert 100.0 * b["seconds"] / (at_peak_s * 1.37) < 100.0


def test_unknown_device_kind_raises():
    assert counts.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("cpu")


def test_every_seed_gets_the_same_work_in_another_order():
    with open(os.path.join(DATA, "tiny", "traffic", "tiny-chat.json")) as f:
        mix = json.load(f)
    a = traffic.requests(mix, 256, 3, 2.0)
    b = traffic.requests(mix, 256, 2**31 + 12345, 2.0)  # past 32 signed bits
    assert len(a) == len(b) == 24
    # a serving schedule is the mix's own: the seed changes the tokens only
    strip = lambda rs: [{k: v for k, v in r.items() if k != "prompt"} for r in rs]  # noqa: E731
    assert strip(a) == strip(b)
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in b]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    gaps = np.diff([0.0] + [r["due_s"] for r in a])
    assert gaps.min() > 0 and gaps.max() / gaps.min() > 20  # exponential, not even
    assert 0.0 < a[0]["due_s"] and a[-1]["due_s"] < 2.0
    assert sum(r["greedy"] for r in a) == 10
    other = traffic.requests(dict(mix, schedule_seed=24), 256, 3, 2.0)
    assert sorted(r["max_tokens"] for r in other) == sorted(r["max_tokens"] for r in a)
    assert [r["max_tokens"] for r in other] != [r["max_tokens"] for r in a]
    assert traffic.requests(mix, 256, 3, 2.0) == a  # the same seed, the same inputs
    with open(os.path.join(DATA, "tiny", "traffic", "tiny-docs.json")) as f:
        job = json.load(f)
    d1 = traffic.documents(job, 256, 1, 1000)
    d2 = traffic.documents(job, 256, 2, 1000)
    assert sorted(map(len, d1[:32])) == sorted(map(len, d2[:32]))
    assert [len(x) for x in d1[:32]] != [len(x) for x in d2[:32]]


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "two_chip_trace.textproto")) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    return trace.reduce_profile(
        ProfileData.from_serialized_xspace(raw), "/device:TPU:"
    )


def test_trace_reduction_busy_and_idle_shares(reduced):
    # chip 0 busy 20 + 20 + 10 us (the while op that spans them does not
    # count), chip 1 busy 50 us, window 100 us
    assert reduced["chips"] == 2
    assert reduced["window_s"] == pytest.approx(100e-6)
    assert reduced["busy_s"] == pytest.approx(50e-6)


def test_trace_reduction_kernel_times_and_programs(reduced):
    flash = ['f32\\[\\d+,\\d+,\\d+,1\\].*custom_call_target="tpu_custom_call"']
    assert trace.op_seconds(reduced, flash) == pytest.approx(15e-6)  # 30 us on one of two chips
    assert trace.op_seconds(reduced, ["%fusion\\.1 "]) == pytest.approx(35e-6)
    with pytest.raises(LookupError):
        trace.op_seconds(reduced, ["no_such_kernel"])
    # only the program call that lies wholly inside the window counts
    assert reduced["modules"] == {"jit_step_fn(123)": (pytest.approx(35e-6), 0.5)}
    top = trace.breakdown(reduced)["device_ops"]
    assert top[0][0] == "%fusion.1 fusion"
    assert top[1][0] == "%checkpoint.2 custom-call tpu_custom_call"


def test_trace_reduction_attributes_gaps_to_host_spans(reduced):
    # chip 0 idles 0-10 (dispatch), 50-70 (5 dispatch, 15 fetch: fetch),
    # 80-100 (fetch); chip 1 idles 50-100 (5 dispatch, 45 fetch: fetch)
    assert dict(reduced["gaps"]) == {
        "train.fetch": pytest.approx(45e-6),
        "train.dispatch": pytest.approx(5e-6),
    }
    assert reduced["gaps"][0][0] == "train.fetch"


def test_the_rates_window_ends_on_a_token_and_is_never_shorter_than_asked():
    import time
    from types import SimpleNamespace

    from benchmark.drivers import engine

    now = time.monotonic()
    t_close = now - 1.0
    clients = [
        SimpleNamespace(times=[t_close - 0.4, t_close - 0.1, t_close + 0.2]),
        SimpleNamespace(times=[t_close - 0.1, t_close + 0.21]),
        SimpleNamespace(times=[]),
    ]
    end, stalled = engine.close_on_a_token(clients, t_close, 0.5)
    # the first token at or after the close ends it; the three before count
    assert (end, stalled) == (t_close + 0.2, False)
    assert sum(np.searchsorted(c.times, end, side="left") for c in clients) == 3
    # an engine that streams nothing more pays for the whole silence
    quiet = [SimpleNamespace(times=[t_close - 0.3])]
    end, stalled = engine.close_on_a_token(quiet, t_close, 0.5)
    assert (end, stalled) == (t_close + 0.5, True)
