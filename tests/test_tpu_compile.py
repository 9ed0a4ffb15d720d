"""The cached forward's TPU read, compiled here for a described v5e (no
chip): Mosaic refuses what interpret mode lets through (tiling, scoped
VMEM), and the compiled decode step shows whether a layer's cache is
still copied out of the stack. One file, one fixture: only the worker
that runs it loads the TPU's compiler (on-chip-measurement guide)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from odh_kubeflow_tpu.models import LlamaConfig, forward_with_cache, init_cache
from odh_kubeflow_tpu.models import llama
from odh_kubeflow_tpu.ops.pallas_attention import flash_attention
from odh_kubeflow_tpu.ops.pallas_decode_attention import decode_attend


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _on(chip, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree
    )


# the serving cell (Mistral-7B: 32 / 8 heads x 128, 16 slots x 2048) as
# the engine calls the read, and Llama-3.2-1B's heads of 64
@pytest.mark.parametrize(
    "B,S,hd,vector",
    [(16, 1, 128, True), (16, 5, 128, True), (1, 1024, 128, False),
     (4, 1, 64, True)],
    ids=["decode", "window", "prefill1024", "decode-hd64"],
)
def test_decode_attend_compiles_at_real_widths(one_chip, B, S, hd, vector):
    L, S_max, Hq, Hkv = 32, 2048, 32, 8
    q = jax.ShapeDtypeStruct((B, S, Hq, hd), jnp.bfloat16)
    cache = jax.ShapeDtypeStruct((L, B, S_max, Hkv * hd), jnp.bfloat16)
    index = jax.ShapeDtypeStruct((B,) if vector else (), jnp.int32)
    compiled = jax.jit(decode_attend).lower(
        *_on(one_chip, (q, cache, cache, jax.ShapeDtypeStruct((), jnp.int32),
                        index, jax.ShapeDtypeStruct((B, S_max), jnp.bool_)))
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the stack goes to the kernel as it is: no copy of a layer beside it
    assert compiled.memory_analysis().temp_size_in_bytes < cache.size // L


# the training cell (Mistral-7B: 2 x 4096 packed, 32 / 8 heads x 128) and
# Llama-3.2-1B's heads of 64 (the augmented operands), each with the
# walk built from segment ids (1024 blocks in 512 tiles, addressed by a
# loop: aligned dynamic slices on both axes) and with the static one
@pytest.mark.parametrize("segmented", [True, False], ids=["packed", "plain"])
@pytest.mark.parametrize("hd", [128, 64])
def test_flash_compiles_at_real_widths(one_chip, hd, segmented):
    B, S, Hq, Hkv = 2, 4096, 32, 8
    q = jax.ShapeDtypeStruct((B, S, Hq, hd), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, S, Hkv, hd), jnp.bfloat16)
    seg = jax.ShapeDtypeStruct((B, S), jnp.int32)

    def grads(q, k, v, tangent, seg):
        def loss(q, k, v):
            out = flash_attention(
                q, k, v, segment_ids=seg if segmented else None,
                interpret=False,  # the backend here is the CPU
            )
            return jnp.sum(out.astype(jnp.float32) * tangent)

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    # conftest asks for float32 matmuls on the CPU; the chip runs the
    # default, and Mosaic refuses a bf16 dot at float32 precision
    with jax.default_matmul_precision("default"):
        text = jax.jit(grads).lower(
            *_on(one_chip, (q, kv, kv, q, seg))
        ).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    # what the benchmark's flash_roofline finds the three calls by
    assert text.count(f"f32[{B},{Hq},{S},1]") >= 3


@pytest.mark.parametrize("in_place", [True, False], ids=["kernel", "dense"])
def test_decode_step_copies_no_layer_of_the_cache(one_chip, monkeypatch, in_place):
    """A decode step of two Mistral-wide layers, compiled for the chip
    with the read the TPU takes: the stack is updated in place (aliased),
    and no instruction produces a layer's whole keys or values. With the
    dense read (what the CPU and a mesh take) XLA does copy the layer
    out, and the smoke's detector has to see it."""
    # the backend here is the CPU; the test, not the program, steers
    monkeypatch.setattr(
        llama, "_reads_cache_in_place", lambda leaf, hd: in_place
    )
    cfg = LlamaConfig(
        vocab_size=32768, hidden_size=4096, intermediate_size=14336,
        num_layers=2, num_heads=32, num_kv_heads=8, head_dim=128,
        dtype=jnp.bfloat16, tie_embeddings=False,
    )
    B, S_max = 16, 2048
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cfg, jnp.bfloat16)
    )
    cache = jax.eval_shape(lambda: init_cache(cfg, B, S_max))

    def step(params, cache, tokens, index, kv_mask):
        return forward_with_cache(
            params, tokens, cfg, cache, index, positions=index[:, None],
            kv_mask=kv_mask,
        )

    compiled = jax.jit(step, donate_argnums=1).lower(
        *_on(one_chip, (
            params, cache, jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B, S_max), jnp.bool_),
        ))
    ).compile()
    mem = compiled.memory_analysis()
    cache_bytes = 2 * cache["k"].size * 2
    assert mem.alias_size_in_bytes >= cache_bytes
    text = compiled.as_text()
    assert ("decode_attend" in text) is in_place
    # the detector the chip's smoke holds the engine's decode chunk to
    copies = _chip_smoke().cache_layer_copies(text, cache["k"])
    assert (not copies) is in_place, copies[:3]


def _chip_smoke():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(__file__)), "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ---- Command A+'s share (cohere2_moe): 128 / 8 heads x 128, window 4096,
# a ring of 6144 (window + a part of 2048) beside 13312 full positions, 16
# held experts of width 4096


@pytest.mark.parametrize(
    "B,S,S_kind,window,vector",
    [(16, 1, 6144, 4096, True), (16, 1, 13312, None, True),
     (1, 2048, 6144, 4096, False), (1, 2048, 13312, None, False)],
    ids=["decode-ring", "decode-full", "part-ring", "part-full"],
)
def test_decode_attend_compiles_for_windows_and_rings(
    one_chip, B, S, S_kind, window, vector
):
    L, Hq, Hkv, hd = 6, 128, 8, 128
    q = jax.ShapeDtypeStruct((B, S, Hq, hd), jnp.bfloat16)
    cache = jax.ShapeDtypeStruct((L, B, S_kind, Hkv * hd), jnp.bfloat16)
    index = jax.ShapeDtypeStruct((B,) if vector else (), jnp.int32)
    compiled = jax.jit(
        lambda *a: decode_attend(*a, window=window)
    ).lower(
        *_on(one_chip, (q, cache, cache, jax.ShapeDtypeStruct((), jnp.int32),
                        index, jax.ShapeDtypeStruct((B, S_kind), jnp.bool_)))
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < cache.size // L


def _int8_bank(shape):
    return {
        "q": jax.ShapeDtypeStruct(shape, jnp.int8),
        "scale": jax.ShapeDtypeStruct(shape[:-2] + (1, shape[-1]), jnp.float32),
    }


@pytest.mark.parametrize("T", [16, 2048], ids=["decode", "part"])
def test_moe_local_ffn_compiles_at_real_widths(one_chip, T):
    """The held experts' kernel on the stacked int8 banks: compiled, and
    no bank (0.8 GB a layer) is copied to feed it."""
    from odh_kubeflow_tpu.models import moe

    L, E, D, F, k = 2, 16, 4096, 4096, 8
    banks = {
        "moe_gate": _int8_bank((L, E, D, F)), "moe_up": _int8_bank((L, E, D, F)),
        "moe_down": _int8_bank((L, E, F, D)),
    }

    def fn(h, w, idx, banks, layer):
        return moe.local_expert_ffn(
            h, w, idx, banks, layer, (32, E), in_place=True
        )

    compiled = jax.jit(fn).lower(*_on(one_chip, (
        jax.ShapeDtypeStruct((T, D), jnp.bfloat16),
        jax.ShapeDtypeStruct((T, k), jnp.float32),
        jax.ShapeDtypeStruct((T, k), jnp.int32),
        banks, jax.ShapeDtypeStruct((), jnp.int32),
    ))).compile()
    text = compiled.as_text()
    assert "moe_local_ffn" in text
    assert not [
        line for line in text.splitlines()
        if f"= s8[{E},{D},{F}]" in line or f"= s8[{L},{E},{D},{F}]" in line
        if " parameter(" not in line
    ]
    # the sorted rows and their outputs (2048 tokens x 8 choices), never
    # a layer's three banks
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * E * D * F


def test_cohere2_decode_step_reads_banks_and_both_caches_in_place(
    one_chip, monkeypatch
):
    """One period of the served share at its real widths, a decode step
    compiled for the chip: both kinds of cache stack are aliased, the
    attention and expert kernels are there, and the step's temporaries
    stay under one layer's expert banks."""
    from odh_kubeflow_tpu.models import cohere2, moe

    monkeypatch.setattr(llama, "_reads_cache_in_place", lambda leaf, hd: True)
    monkeypatch.setattr(moe, "reads_banks_in_place", lambda banks: True)
    cfg = cohere2.Cohere2MoeConfig(
        vocab_size=32768, num_layers=4, experts_held=(32, 16)
    )
    B, max_len = 16, 13312
    params = jax.eval_shape(
        lambda: cohere2.init_params(jax.random.key(0), cfg, jnp.bfloat16)
    )
    for name, leaf in params["layers"].items():
        if name not in ("norm", "router"):
            params["layers"][name] = _int8_bank(leaf.shape)
    cache = jax.eval_shape(
        lambda: init_cache(cfg, B, max_len, widest_part=2048)
    )
    assert cache["wk"].shape == (3, B, 6144, 1024)
    assert cache["k"].shape == (1, B, max_len, 1024)

    def step(params, cache, tokens, index, kv_mask):
        return cohere2.forward_with_cache(
            params, tokens, cfg, cache, index, positions=index[:, None],
            kv_mask=kv_mask, token_mask=kv_mask[:, :1],
        )

    compiled = jax.jit(step, donate_argnums=1).lower(*_on(one_chip, (
        params, cache, jax.ShapeDtypeStruct((B, 1), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((B, max_len), jnp.bool_),
    ))).compile()
    mem = compiled.memory_analysis()
    stacks = sum(v.size * 2 for n, v in cache.items() if n != "moe_stats")
    assert mem.alias_size_in_bytes >= stacks
    text = compiled.as_text()
    assert "decode_attend" in text and "moe_local_ffn" in text
    # no instruction produces a layer's bank (or all of them)
    import re

    made = [
        line[:120] for line in text.splitlines()
        if re.search(r"= s8\[(?:\d+,)?16,4096,4096\]", line)
        and " parameter(" not in line
    ]
    assert not made, made[:3]
    assert mem.temp_size_in_bytes < 3 * 16 * 4096 * 4096, mem.temp_size_in_bytes


# ---- Granite 4.0-H's stage (granitemoehybrid): 128 Mamba-2 heads x 64,
# state 128, chunks of 256; 32 slots; 72 held experts of width 768


@pytest.mark.parametrize("S", [2048, 64], ids=["part", "bucket-64"])
def test_ssd_chunk_scan_compiles_at_published_widths(one_chip, S):
    from odh_kubeflow_tpu.ops import pallas_ssm

    H, P, N = 128, 64, 128
    f32, bf16 = jnp.float32, jnp.bfloat16
    compiled = jax.jit(pallas_ssm.ssd_chunk_scan).lower(*_on(one_chip, (
        jax.ShapeDtypeStruct((1, S, H, P), bf16),
        jax.ShapeDtypeStruct((1, S, H), f32), jax.ShapeDtypeStruct((H,), f32),
        jax.ShapeDtypeStruct((1, S, N), bf16), jax.ShapeDtypeStruct((1, S, N), bf16),
        jax.ShapeDtypeStruct((1,) + pallas_ssm.state_shape(H, P, N), f32),
    ))).compile()
    assert "ssd_chunk_scan" in compiled.as_text()


def test_ssm_decode_update_is_one_pass_over_the_stacked_state(one_chip):
    from odh_kubeflow_tpu.ops import pallas_ssm

    L, B, H, P, N = 9, 32, 128, 64, 128
    f32, bf16 = jnp.float32, jnp.bfloat16
    state = jax.ShapeDtypeStruct((L, B) + pallas_ssm.state_shape(H, P, N), f32)
    compiled = jax.jit(pallas_ssm.ssm_decode_update, donate_argnums=5).lower(
        *_on(one_chip, (
            jax.ShapeDtypeStruct((B, H, P), bf16), jax.ShapeDtypeStruct((B, H), f32),
            jax.ShapeDtypeStruct((H,), f32), jax.ShapeDtypeStruct((B, N), bf16),
            jax.ShapeDtypeStruct((B, N), bf16), state,
            jax.ShapeDtypeStruct((), jnp.int32),
        ))
    ).compile()
    mem = compiled.memory_analysis()
    assert "ssm_decode_update" in compiled.as_text()
    assert mem.alias_size_in_bytes >= state.size * 4
    # the rows laid along the lanes (a few MB), never a layer's state
    assert mem.temp_size_in_bytes < state.size * 4 // L // 4


def _granite_stage(monkeypatch, periods=1):
    from odh_kubeflow_tpu.models import granite_hybrid as gh, moe

    monkeypatch.setattr(llama, "_reads_cache_in_place", lambda leaf, hd: True)
    monkeypatch.setattr(moe, "reads_banks_in_place", lambda banks: True)
    monkeypatch.setattr(gh, "_uses_kernels", lambda: True)
    cfg = gh.GraniteHybridConfig(num_layers=10 * periods)
    params = jax.eval_shape(
        lambda: gh.init_params(jax.random.key(0), cfg, jnp.bfloat16)
    )
    quantised = {
        "layers": ("moe_gate", "moe_up", "moe_down", "sh_gate", "sh_up", "sh_down"),
        "mamba": ("in_proj", "out_proj"), "attn": ("wq", "wk", "wv", "wo"),
    }
    for group, names in quantised.items():
        for name in names:
            params[group][name] = _int8_bank(params[group][name].shape)
    return gh, cfg, params


@pytest.mark.parametrize("B,S", [(32, 1), (1, 2048)], ids=["decode", "part"])
def test_granite_stage_updates_state_and_cache_in_place(one_chip, monkeypatch, B, S):
    """One period at published widths, a decode step of 32 slots and a
    part of 2048 positions, compiled for the chip: the state and the
    attention layer's keys and values are aliased, the four kernels are
    there, and the temporaries are megabytes (no layer's state, expert
    banks or dequantised projection is written out)."""
    gh, cfg, params = _granite_stage(monkeypatch)
    max_len = 13312
    cache = jax.eval_shape(lambda: init_cache(cfg, B, max_len, widest_part=2048))
    assert cache["ssm"].shape == (9, B, 64, 128, 128)
    assert cache["conv"].shape == (9, B, 3, 8448)
    assert cache["k"].shape == (1, B, max_len, 1024)

    def step(params, cache, tokens, index, kv_mask):
        return gh.forward_with_cache(
            params, tokens, cfg, cache, index,
            positions=jnp.broadcast_to(jnp.arange(S), (B, S)),
            kv_mask=kv_mask, token_mask=kv_mask[:, :S],
        )

    compiled = jax.jit(step, donate_argnums=1).lower(*_on(one_chip, (
        params, cache, jax.ShapeDtypeStruct((B, S), jnp.int32),
        jax.ShapeDtypeStruct((B,) if S == 1 else (), jnp.int32),
        jax.ShapeDtypeStruct((B, max_len), jnp.bool_),
    ))).compile()
    mem = compiled.memory_analysis()
    stacks = sum(
        v.size * v.dtype.itemsize for n, v in cache.items() if n != "moe_stats"
    )
    assert mem.alias_size_in_bytes >= stacks
    text = compiled.as_text()
    assert "decode_attend" in text and "moe_local_ffn" in text
    assert ("ssm_decode_update" if S == 1 else "ssd_chunk_scan") in text
    print(f"granite {B}x{S}: temp {mem.temp_size_in_bytes / 1e6:.1f} MB")
    if S == 1:
        # one slot's state in one layer is 4.2 MB, a layer's banks 680 MB,
        # a dequantised in_proj 137 MB
        assert mem.temp_size_in_bytes < 100e6, mem.temp_size_in_bytes
    else:
        # a part's activations: the sorted rows of 2048 tokens x 10
        # choices and the projection's [2048, 16768] outputs
        assert mem.temp_size_in_bytes < 2.5e9, mem.temp_size_in_bytes


# ---- Qwen3-Next's share (qwen3_next): 16 key heads onto 32 value heads of
# 128 x 128, chunks of 64; 32 slots; 256 held experts of width 512 under a
# router of 512; attention at head 256, 16 / 2 heads


def _gdn_scan_operands(S):
    Hk, H, dk, dv = 16, 32, 128, 128
    f32, bf16 = jnp.float32, jnp.bfloat16
    return (
        jax.ShapeDtypeStruct((1, S, Hk, dk), bf16),
        jax.ShapeDtypeStruct((1, S, Hk, dk), bf16),
        jax.ShapeDtypeStruct((1, S, H, dv), bf16),
        jax.ShapeDtypeStruct((1, S, H), f32), jax.ShapeDtypeStruct((1, S, H), f32),
        jax.ShapeDtypeStruct((1, H, dk, dv), f32),
    )


@pytest.mark.parametrize("S", [2048, 64], ids=["part", "bucket-64"])
def test_gdn_chunk_scan_compiles_at_published_widths(one_chip, S):
    from odh_kubeflow_tpu.ops import pallas_gdn

    compiled = jax.jit(pallas_gdn.gdn_chunk_scan).lower(
        *_on(one_chip, _gdn_scan_operands(S))
    ).compile()
    assert "gdn_chunk_scan" in compiled.as_text()


@pytest.mark.parametrize("S", [64, 256, 2048], ids=["bucket-64", "bucket-256", "part"])
def test_every_kernel_of_the_gdn_scan_carries_its_name(one_chip, S):
    """``gdn_prefill_roofline.gdn`` divides the scan's counted work by the
    seconds of the device events named ``gdn_chunk_scan``: a kernel that
    held part of the scan under another name would read as a gain."""
    import re

    from odh_kubeflow_tpu.ops import pallas_gdn

    text = jax.jit(pallas_gdn.gdn_chunk_scan).lower(
        *_on(one_chip, _gdn_scan_operands(S))
    ).as_text()
    calls = [line for line in text.splitlines() if "@tpu_custom_call" in line]
    assert calls
    for line in calls:
        name = re.search(r'kernel_name = "([^"]*)"', line)
        assert name and "gdn_chunk_scan" in name.group(1), line[-300:]


def test_gdn_decode_update_is_one_pass_over_the_stacked_state(one_chip):
    from odh_kubeflow_tpu.ops import pallas_gdn

    L, B, Hk, H, dk, dv = 9, 32, 16, 32, 128, 128
    f32, bf16 = jnp.float32, jnp.bfloat16
    state = jax.ShapeDtypeStruct((L, B, H, dk, dv), f32)
    compiled = jax.jit(pallas_gdn.gdn_decode_update, donate_argnums=5).lower(
        *_on(one_chip, (
            jax.ShapeDtypeStruct((B, Hk, dk), bf16),
            jax.ShapeDtypeStruct((B, Hk, dk), bf16),
            jax.ShapeDtypeStruct((B, H, dv), bf16),
            jax.ShapeDtypeStruct((B, H), f32), jax.ShapeDtypeStruct((B, H), f32),
            state, jax.ShapeDtypeStruct((), jnp.int32),
        ))
    ).compile()
    mem = compiled.memory_analysis()
    assert "gdn_decode_update" in compiled.as_text()
    assert mem.alias_size_in_bytes >= state.size * 4
    # q and k as columns and three rows a head (a few MB), never a layer's state
    assert mem.temp_size_in_bytes < state.size * 4 // L // 4


@pytest.mark.parametrize("B,S", [(32, 1), (1, 2048)], ids=["decode", "part"])
def test_qwen3_next_share_updates_state_and_cache_in_place(one_chip, monkeypatch, B, S):
    """One period at published widths, a decode step of 32 slots and a
    part of 2048 positions, compiled for the chip: the delta-rule state
    and the attention layer's keys and values are aliased, the kernels
    are there (``decode_attend`` at head 256, ``moe_local_ffn`` on 256
    banks at the tile its rows fill), and the temporaries are megabytes
    (no layer's state, expert banks or dequantised projection is written
    out)."""
    from odh_kubeflow_tpu.models import moe
    from odh_kubeflow_tpu.models import qwen3_next as qn

    monkeypatch.setattr(llama, "_reads_cache_in_place", lambda leaf, hd: True)
    monkeypatch.setattr(moe, "reads_banks_in_place", lambda banks: True)
    monkeypatch.setattr(qn, "_uses_kernels", lambda: True)
    cfg = qn.Qwen3NextConfig(num_layers=4, vocab_size=75968, experts_held=(0, 256))
    params = jax.eval_shape(
        lambda: qn.init_params(jax.random.key(0), cfg, jnp.bfloat16)
    )
    quantised = {
        "layers": ("moe_gate", "moe_up", "moe_down", "sh_gate", "sh_up", "sh_down"),
        "gdn": ("in_qkvz", "out_proj"), "attn": ("wq", "wk", "wv", "wo"),
    }
    for group, names in quantised.items():
        for name in names:
            params[group][name] = _int8_bank(params[group][name].shape)
    max_len = 13312
    cache = jax.eval_shape(lambda: init_cache(cfg, B, max_len, widest_part=2048))
    assert cache["ssm"].shape == (3, B, 32, 128, 128)
    assert cache["conv"].shape == (3, B, 3, 8192)
    assert cache["k"].shape == (1, B, max_len, 512)

    def step(params, cache, tokens, index, kv_mask):
        return qn.forward_with_cache(
            params, tokens, cfg, cache, index,
            positions=jnp.broadcast_to(jnp.arange(S), (B, S)),
            kv_mask=kv_mask, token_mask=kv_mask[:, :S],
        )

    compiled = jax.jit(step, donate_argnums=1).lower(*_on(one_chip, (
        params, cache, jax.ShapeDtypeStruct((B, S), jnp.int32),
        jax.ShapeDtypeStruct((B,) if S == 1 else (), jnp.int32),
        jax.ShapeDtypeStruct((B, max_len), jnp.bool_),
    ))).compile()
    mem = compiled.memory_analysis()
    stacks = sum(
        v.size * v.dtype.itemsize for n, v in cache.items() if n != "moe_stats"
    )
    assert mem.alias_size_in_bytes >= stacks
    text = compiled.as_text()
    assert "decode_attend" in text and "moe_local_ffn" in text
    assert ("gdn_decode_update" if S == 1 else "gdn_chunk_scan") in text
    # the expert kernel's rows: 32 x 10 choices and a tile of 16 a bank;
    # 2048 x 10 and a tile of 64 a bank (40 rows expected, not 128)
    rows = 320 + 256 * 16 if S == 1 else 20480 + 256 * 64
    assert f"bf16[{rows},2048]" in text
    print(f"qwen3_next {B}x{S}: temp {mem.temp_size_in_bytes / 1e6:.1f} MB")
    if S == 1:
        # one slot's state in one layer is 2.1 MB, a layer's banks 805 MB,
        # a dequantised W_qkvz 50 MB
        assert mem.temp_size_in_bytes < 60e6, mem.temp_size_in_bytes
    else:
        # a part's activations: the sorted rows of 2048 tokens x 10
        # choices and the projections' [2048, 12288] outputs
        assert mem.temp_size_in_bytes < 0.6e9, mem.temp_size_in_bytes


# ---- Brumby's stage (brumby): 40 query heads onto 8 states of 65 x 128 x
# 128 a layer a slot (34 MB), chunks of 128; 16 slots; no keys and values


@pytest.mark.parametrize("S", [2048, 64], ids=["part", "bucket-64"])
def test_retention_chunk_scan_compiles_at_published_widths(one_chip, S):
    from odh_kubeflow_tpu.ops import pallas_retention as pr

    Hq, Hkv, d = 40, 8, 128
    R = pr.phi_rows(d)
    f32, bf16 = jnp.float32, jnp.bfloat16
    compiled = jax.jit(pr.retention_chunk_scan).lower(*_on(one_chip, (
        jax.ShapeDtypeStruct((1, S, Hq, d), bf16),
        jax.ShapeDtypeStruct((1, S, Hkv, d), bf16),
        jax.ShapeDtypeStruct((1, S, Hkv, d), bf16),
        jax.ShapeDtypeStruct((1, S, Hkv), f32),
        jax.ShapeDtypeStruct((1, Hkv, R, d, d), f32),
        jax.ShapeDtypeStruct((1, Hkv, R, d), f32),
    ))).compile()
    assert "retention_chunk_scan" in compiled.as_text()


def test_retention_decode_update_is_one_pass_over_the_stacked_state(one_chip):
    from odh_kubeflow_tpu.ops import pallas_retention as pr

    L, B, Hq, Hkv, d = 10, 16, 40, 8, 128
    R = pr.phi_rows(d)
    f32, bf16 = jnp.float32, jnp.bfloat16
    state = jax.ShapeDtypeStruct((L, B, Hkv, R, d, d), f32)
    norm = jax.ShapeDtypeStruct((L, B, Hkv, R, d), f32)
    compiled = jax.jit(pr.retention_decode_update, donate_argnums=(4, 5)).lower(
        *_on(one_chip, (
            jax.ShapeDtypeStruct((B, Hq, d), bf16),
            jax.ShapeDtypeStruct((B, Hkv, d), bf16),
            jax.ShapeDtypeStruct((B, Hkv, d), bf16),
            jax.ShapeDtypeStruct((B, Hkv), f32),
            state, norm, jax.ShapeDtypeStruct((), jnp.int32),
        ))
    ).compile()
    mem = compiled.memory_analysis()
    assert "retention_decode_update" in compiled.as_text()
    assert mem.alias_size_in_bytes >= (state.size + norm.size) * 4
    # a few rows a head beside a 4.3 MB state, never a layer's state
    assert mem.temp_size_in_bytes < state.size * 4 // L // 4


@pytest.mark.parametrize("B,S", [(16, 1), (1, 2048)], ids=["decode", "part"])
def test_brumby_stage_updates_its_state_in_place(one_chip, monkeypatch, B, S):
    """Two layers at published widths, a decode step of 16 slots and a
    part of 2048 positions, compiled for the chip: state and normaliser
    (the WHOLE cache: there are no keys and values) are aliased, the
    kernel is there, and the temporaries are far under a layer's state
    (none is sliced out of the stack, no dequantised projection is
    written out beside it)."""
    from odh_kubeflow_tpu.models import brumby as bm

    monkeypatch.setattr(bm, "_uses_kernels", lambda: True)
    cfg = bm.BrumbyConfig(num_layers=2)
    params = jax.eval_shape(
        lambda: bm.init_params(jax.random.key(0), cfg, jnp.bfloat16)
    )
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        params["layers"][name] = _int8_bank(params["layers"][name].shape)
    cache = jax.eval_shape(lambda: init_cache(cfg, B, 20480, widest_part=2048))
    assert set(cache) == set(llama.STATE_STACKS)
    assert cache["ssm"].shape == (2, B, 8, 65, 128, 128)
    assert cache["conv"].shape == (2, B, 8, 65, 128)

    def step(params, cache, tokens, index, token_mask):
        return bm.forward_with_cache(
            params, tokens, cfg, cache, index,
            positions=jnp.broadcast_to(jnp.arange(S), (B, S)), token_mask=token_mask,
        )

    compiled = jax.jit(step, donate_argnums=1).lower(*_on(one_chip, (
        params, cache, jax.ShapeDtypeStruct((B, S), jnp.int32),
        jax.ShapeDtypeStruct((B,) if S == 1 else (), jnp.int32),
        jax.ShapeDtypeStruct((B, S), jnp.bool_),
    ))).compile()
    mem = compiled.memory_analysis()
    stacks = sum(v.size * v.dtype.itemsize for v in cache.values())
    assert mem.alias_size_in_bytes >= stacks
    text = compiled.as_text()
    assert ("retention_decode_update" if S == 1 else "retention_chunk_scan") in text
    one_layer = stacks // 2
    print(f"brumby {B}x{S}: temp {mem.temp_size_in_bytes / 1e6:.1f} MB")
    assert mem.temp_size_in_bytes < (one_layer // 4 if S == 1 else 1.5e9)


# ---- Keye-VL-2.0's share (keye_vl): 32 query heads onto 4 key/value heads
# of 128, an indexer of 16 heads of 64 that keeps 2048 keys; 16 slots x 24576


@pytest.mark.parametrize(
    "B,S", [(16, 1), (1, 2048), (1, 64)], ids=["decode", "part", "bucket-64"]
)
def test_index_scores_compiles_at_published_widths(one_chip, B, S):
    from odh_kubeflow_tpu.ops import sparse_attention as sa

    sds = jax.ShapeDtypeStruct
    stack = sds((12, B, 64, 24576), jnp.bfloat16)
    compiled = jax.jit(sa.index_scores).lower(*_on(one_chip, (
        sds((B, S, 16, 64), jnp.bfloat16), sds((B, S, 16), jnp.float32), stack,
        sds((), jnp.int32), sds((B,) if S == 1 else (), jnp.int32),
    ))).compile()
    assert "index_scores" in compiled.as_text()
    # the stack goes to the kernel as it is: no layer of it beside it
    assert compiled.memory_analysis().temp_size_in_bytes < stack.size * 2 // 12


def test_a_decode_steps_index_keys_are_written_in_place(one_chip):
    from odh_kubeflow_tpu.ops import sparse_attention as sa

    sds = jax.ShapeDtypeStruct
    stack = sds((12, 16, 64, 24576), jnp.bfloat16)
    compiled = sa.write_index_keys.lower(*_on(one_chip, (
        stack, sds((16, 64), jnp.bfloat16), sds((), jnp.int32), sds((16,), jnp.int32),
    ))).compile()
    mem = compiled.memory_analysis()
    assert "index_key_write" in compiled.as_text()
    assert mem.alias_size_in_bytes >= stack.size * 2
    assert mem.temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("S", [2048, 64], ids=["part", "bucket-64"])
def test_decode_attend_under_a_selection_compiles_at_published_widths(one_chip, S):
    sds = jax.ShapeDtypeStruct
    S_max = 24576
    cache = sds((12, 1, S_max, 512), jnp.bfloat16)

    def attend(q, k, v, layer, offset, kv_mask, scores, thr, cut):
        return decode_attend(
            q, k, v, layer, offset, kv_mask, select=(scores, thr, cut)
        )

    compiled = jax.jit(attend).lower(*_on(one_chip, (
        sds((1, S, 32, 128), jnp.bfloat16), cache, cache, sds((), jnp.int32),
        sds((), jnp.int32), sds((1, S_max), jnp.bool_),
        sds((1, S, S_max), jnp.float32), sds((1, S), jnp.float32),
        sds((1, S), jnp.int32),
    ))).compile()
    assert "decode_attend" in compiled.as_text()
    # neither a layer of the cache nor the scores are copied
    assert compiled.memory_analysis().temp_size_in_bytes < S * S_max * 4 // 2


@pytest.mark.parametrize("B,S", [(16, 1), (1, 2048)], ids=["decode", "part"])
def test_keye_vl_share_reads_its_three_stacks_in_place(one_chip, monkeypatch, B, S):
    """Two layers at published widths, a decode step of 16 slots and a
    part of 2048 positions at 24576, compiled for the chip: the three
    stacks are aliased, the kernels are there, and a decode step takes
    its 2048 rows a slot from the 4-D key and value stacks by ONE gather
    each: no layer of any stack is sliced, copied or transposed out."""
    import re

    from odh_kubeflow_tpu.models import keye_vl as kv
    from odh_kubeflow_tpu.models import moe

    monkeypatch.setattr(llama, "_reads_cache_in_place", lambda leaf, hd: True)
    monkeypatch.setattr(moe, "reads_banks_in_place", lambda banks: True)
    cfg = kv.KeyeVLConfig(num_layers=2, vocab_size=18992, experts_held=(0, 16))
    params = jax.eval_shape(
        lambda: kv.init_params(jax.random.key(0), cfg, jnp.bfloat16)
    )
    for name in ("moe_gate", "moe_up", "moe_down", "wq", "wk", "wv", "wo",
                 "wq_idx", "wk_idx"):
        params["layers"][name] = _int8_bank(params["layers"][name].shape)
    max_len = 24576
    cache = jax.eval_shape(lambda: init_cache(cfg, B, max_len, widest_part=2048))
    assert cache["sk"].shape == cache["sv"].shape == (2, B, max_len, 512)
    assert cache["ik"].shape == (2, B, 64, max_len)

    def step(params, cache, tokens, index, kv_mask):
        positions = index[:, None] if S == 1 else index + jnp.arange(S)[None]
        return kv.forward_with_cache(
            params, tokens, cfg, cache, index, positions=positions,
            kv_mask=kv_mask, token_mask=kv_mask[:, :S],
        )

    compiled = jax.jit(step, donate_argnums=1).lower(*_on(one_chip, (
        params, cache, jax.ShapeDtypeStruct((B, S), jnp.int32),
        jax.ShapeDtypeStruct((B,) if S == 1 else (), jnp.int32),
        jax.ShapeDtypeStruct((B, max_len), jnp.bool_),
    ))).compile()
    mem = compiled.memory_analysis()
    stacks = sum(
        v.size * v.dtype.itemsize for n, v in cache.items()
        if llama.stack_kind(n)
    )
    assert mem.alias_size_in_bytes >= stacks
    text = compiled.as_text()
    for kernel in ("index_scores", "decode_attend", "moe_local_ffn"):
        assert kernel in text, kernel
    whole_layer = re.compile(
        rf"= bf16\[(?:\d+,)?{B},(?:{max_len},512|64,{max_len})\][^ ]* "
        r"(dynamic-slice|copy|transpose|gather)\("
    )
    assert not whole_layer.findall(text)
    print(f"keye_vl {B}x{S}: temp {mem.temp_size_in_bytes / 1e6:.1f} MB")
    if S == 1:
        assert "index_key_write" in text
        gathers = re.findall(
            r"= bf16\[16,2048,512\][^ ]* gather\([^\n]*slice_sizes=\{1,1,1,512\}", text
        )
        assert len(gathers) == 2, gathers
        # a slot's layer of keys is 25 MB, of indexer keys 3 MB: the
        # step's temporaries are the rows gathered and little else
        assert mem.temp_size_in_bytes < 100e6, mem.temp_size_in_bytes
    else:
        # the scores [2048, 24576] float32 (201 MB) and the search's keys
        assert mem.temp_size_in_bytes < 1.2e9, mem.temp_size_in_bytes
