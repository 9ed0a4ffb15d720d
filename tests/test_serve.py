"""Completion server: bucketed batching, HTTP surface, quantized-tree
serving — the fine-tune→try-it HTTP half."""

import json
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from odh_kubeflow_tpu.models import GenerateConfig, LlamaConfig, generate
from odh_kubeflow_tpu.models import llama
from odh_kubeflow_tpu.models.serve import CompletionService, serve


@pytest.fixture(scope="module")
def service():
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return CompletionService(
        params, cfg, prompt_buckets=(8, 16), batch_buckets=(1, 2)
    )


def test_complete_matches_direct_generate(service):
    prompt = [1, 2, 3, 4]
    out = service.complete([prompt], max_tokens=6)
    direct = generate(
        service.params,
        jnp.asarray([prompt + [0] * 4], jnp.int32),  # padded to bucket 8
        service.cfg,
        GenerateConfig(max_new_tokens=6, temperature=0.0),
        prompt_lengths=jnp.asarray([4], jnp.int32),
    )
    want = np.asarray(direct["tokens"])[0, : int(direct["lengths"][0])].tolist()
    assert out["completions"][0] == want
    assert out["usage"]["padded_shape"] == [1, 8]


def test_bucketing_and_batched_prompts(service):
    # 2 ragged prompts → batch bucket 2, prompt bucket 16
    out = service.complete([[1, 2, 3], list(range(1, 13))], max_tokens=4)
    assert len(out["completions"]) == 2
    assert all(len(c) == 4 for c in out["completions"])
    assert out["usage"]["padded_shape"] == [2, 16]
    # same buckets → cached compile (one entry per gen-config key)
    assert len(service._compiled) >= 1

    with pytest.raises(ValueError):
        service.complete([list(range(99))])  # beyond max bucket
    with pytest.raises(ValueError):
        service.complete([[]])


def test_http_surface(service):
    httpd = serve(service, host="127.0.0.1", port=0)
    port = httpd.server_address[1]
    base = f"http://127.0.0.1:{port}"
    try:
        with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
            assert json.loads(r.read())["status"] == "ok"

        req = urllib.request.Request(
            f"{base}/v1/completions",
            data=json.dumps({"prompt": [1, 2, 3], "max_tokens": 4}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            body = json.loads(r.read())
        assert len(body["completions"]) == 1
        assert len(body["completions"][0]) == 4

        # bad request → 400 with an error message, server keeps serving
        req = urllib.request.Request(
            f"{base}/v1/completions",
            data=json.dumps({"prompt": [[]]}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=10)
        assert e.value.code == 400
        with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
            assert r.status == 200
    finally:
        httpd.shutdown()


def test_serves_quantized_tree():
    """The int8 tree (models/quant.py) plugs straight in — the
    8B-on-one-v5e serving configuration, tiny-sized here."""
    from odh_kubeflow_tpu.models.quant import quantize_params

    cfg = LlamaConfig.tiny(dtype=jnp.bfloat16)
    params = llama.init_params(jax.random.PRNGKey(1), cfg, dtype=jnp.bfloat16)
    svc = CompletionService(
        quantize_params(params), cfg, prompt_buckets=(8,), batch_buckets=(1,)
    )
    out = svc.complete([[5, 6, 7]], max_tokens=4)
    assert len(out["completions"][0]) == 4


def test_full_story_finetune_checkpoint_restore_merge_serve(tmp_path):
    """The platform's whole runtime story in one pass: LoRA fine-tune →
    orbax checkpoint → restore into a fresh trainer → merge adapters →
    quantize → serve completions over HTTP. Every seam the notebook
    user crosses."""
    from odh_kubeflow_tpu.models import LoraConfig
    from odh_kubeflow_tpu.models.lora import merge_lora
    from odh_kubeflow_tpu.models.quant import quantize_params
    from odh_kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
    from odh_kubeflow_tpu.train import TrainConfig, Trainer
    from odh_kubeflow_tpu.train.checkpoint import CheckpointManager

    devices = jax.devices()[:8]
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    trainer = Trainer(
        cfg,
        TrainConfig(warmup_steps=1, total_steps=6, learning_rate=1e-2),
        lora_cfg=LoraConfig(rank=2),
        mesh=build_mesh(MeshConfig(fsdp=8), devices),
    )
    batch = trainer.make_fake_batch(8, 16)
    for _ in range(3):
        trainer.train_step(batch)
    with CheckpointManager(str(tmp_path)) as mgr:
        trainer.save_checkpoint(mgr, force=True)
        mgr.wait_until_finished()

        # "the notebook restarts": fresh trainer restores the adapters
        trainer2 = Trainer(
            cfg,
            TrainConfig(warmup_steps=1, total_steps=6),
            lora_cfg=LoraConfig(rank=2),
            mesh=build_mesh(MeshConfig(fsdp=8), devices),
        )
        assert trainer2.restore_checkpoint(mgr) == 3

    merged = merge_lora(trainer2.params, trainer2.lora_params)
    svc = CompletionService(
        quantize_params(jax.device_get(merged)),
        cfg,
        prompt_buckets=(8,),
        batch_buckets=(1,),
    )
    httpd = serve(svc, host="127.0.0.1", port=0)
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{httpd.server_address[1]}/v1/completions",
            data=json.dumps({"prompt": [1, 2, 3], "max_tokens": 5}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            body = json.loads(r.read())
        assert len(body["completions"][0]) == 5
        assert all(isinstance(t, int) for t in body["completions"][0])
    finally:
        httpd.shutdown()


def test_full_story_moe_lora(tmp_path):
    """The MoE family's version of the full story: attention-adapter
    LoRA fine-tune → checkpoint → restore → merge → serve. Exercises
    the seam the serve CLI's mixtral --checkpoint branch crosses."""
    from odh_kubeflow_tpu.models import LoraConfig
    from odh_kubeflow_tpu.models.lora import merge_lora
    from odh_kubeflow_tpu.models.moe import MoeConfig
    from odh_kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
    from odh_kubeflow_tpu.train import TrainConfig, Trainer
    from odh_kubeflow_tpu.train.checkpoint import CheckpointManager

    devices = jax.devices()[:8]
    cfg = MoeConfig.mixtral_tiny()
    mesh = build_mesh(MeshConfig(fsdp=2, expert=2, data=2), devices)
    trainer = Trainer(
        cfg,
        TrainConfig(warmup_steps=1, total_steps=6, learning_rate=1e-2),
        lora_cfg=LoraConfig(rank=2),
        mesh=mesh,
    )
    batch = trainer.make_fake_batch(8, 16)
    for _ in range(2):
        trainer.train_step(batch)
    with CheckpointManager(str(tmp_path)) as mgr:
        trainer.save_checkpoint(mgr, force=True)
        mgr.wait_until_finished()
        trainer2 = Trainer(
            cfg,
            TrainConfig(warmup_steps=1, total_steps=6),
            lora_cfg=LoraConfig(rank=2),
            mesh=build_mesh(MeshConfig(fsdp=8), devices),  # new topology
        )
        assert trainer2.restore_checkpoint(mgr) == 2

    merged = merge_lora(trainer2.params, trainer2.lora_params)
    svc = CompletionService(
        jax.device_get(merged), cfg, prompt_buckets=(8,), batch_buckets=(1,)
    )
    out = svc.complete([[1, 2, 3]], max_tokens=4)["completions"]
    assert len(out[0]) == 4 and all(isinstance(t, int) for t in out[0])


def test_cli_entrypoint_demo_mode():
    """`python -m odh_kubeflow_tpu.models.serve --config tiny` comes up
    and answers completions (demo mode: random init, no checkpoint)."""
    import re
    import subprocess
    import sys

    proc = subprocess.Popen(
        [
            sys.executable,
            "-m",
            "odh_kubeflow_tpu.models.serve",
            "--config",
            "tiny",
            "--host",
            "127.0.0.1",
            "--port",
            "0",
            "--int8",
        ],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )
    try:
        import select
        import time

        port = None
        deadline = time.time() + 120
        while time.time() < deadline:
            # bounded wall-time read: a silent-but-alive subprocess must
            # fail the test, not hang it
            ready, _, _ = select.select([proc.stdout], [], [], 1.0)
            if not ready:
                continue
            line = proc.stdout.readline()
            if not line:
                break
            m = re.search(r"http://127\.0\.0\.1:(\d+)", line)
            if m:
                port = m.group(1)
                break
        assert port, "server never announced its port"
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions",
            data=json.dumps({"prompt": [1, 2, 3], "max_tokens": 3}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as r:
            assert len(json.loads(r.read())["completions"][0]) == 3
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def test_serves_moe_family():
    """CompletionService drives the MoE decode path (generate's config
    dispatch) — ids in, ids out, same surface as dense."""
    from odh_kubeflow_tpu.models import MoeConfig
    from odh_kubeflow_tpu.models import moe as moe_lib

    cfg = MoeConfig.mixtral_tiny()
    params = moe_lib.init_params(jax.random.PRNGKey(5), cfg)
    svc = CompletionService(
        params, cfg, prompt_buckets=(8,), batch_buckets=(1,)
    )
    out = svc.complete([[2, 7, 1]], max_tokens=4)
    assert len(out["completions"][0]) == 4
    assert all(0 <= t < cfg.vocab_size for t in out["completions"][0])


def test_compile_cache_bounded(service):
    """Distinct request params each compile a program; the cache is
    LRU-bounded so arbitrary max_tokens values cannot exhaust memory
    on a long-running server."""
    svc = CompletionService(
        service.params, service.cfg, prompt_buckets=(8,), batch_buckets=(1,)
    )
    svc.max_compiled = 3
    for n in (2, 3, 4, 5, 6):
        svc.complete([[1, 2, 3]], max_tokens=n)
    assert len(svc._compiled) == 3
    # most-recent entries survive
    assert any(k[0] == 6 for k in svc._compiled)
    assert not any(k[0] == 2 for k in svc._compiled)
    # evicted shapes still serve (recompile on demand)
    out = svc.complete([[1, 2, 3]], max_tokens=2)
    assert len(out["completions"][0]) == 2


def test_engine_mode_http_concurrent():
    """engine_slots>0: concurrent HTTP requests join the continuous-
    batching decode loop; greedy output matches the one-shot path and
    the response is marked usage.engine."""
    import threading

    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    svc = CompletionService(
        params, cfg, prompt_buckets=(8, 16), batch_buckets=(1, 2),
        engine_slots=2, engine_max_len=64,
    )
    try:
        want = CompletionService(
            params, cfg, prompt_buckets=(8, 16), batch_buckets=(1, 2)
        ).complete([[1, 2, 3, 4]], max_tokens=6)["completions"][0]

        httpd = serve(svc, host="127.0.0.1", port=0)
        base = f"http://127.0.0.1:{httpd.server_address[1]}"

        results = {}

        def post(name, prompt):
            req = urllib.request.Request(
                base + "/v1/completions",
                data=json.dumps(
                    {"prompt": prompt, "max_tokens": 6}
                ).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=120) as r:
                results[name] = json.loads(r.read())

        threads = [
            threading.Thread(target=post, args=(i, [1, 2, 3, 4]))
            for i in range(3)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert len(results) == 3
        for out in results.values():
            assert out["usage"]["engine"] is True
            assert out["completions"][0] == want
        httpd.shutdown()
    finally:
        if svc.engine is not None:
            svc.engine.stop()


def test_engine_failure_is_an_error_not_a_one_shot_answer():
    """A dead engine (device failure marked in engine.failure) is a
    dead server: complete() raises and the HTTP surface answers 500 —
    it does not serve a 200 from the one-shot path over a lost
    device. Seeded requests (which never used the engine) included."""
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    svc = CompletionService(
        params, cfg, prompt_buckets=(8, 16), batch_buckets=(1, 2),
        engine_slots=2, engine_max_len=64,
    )
    httpd = serve(svc, host="127.0.0.1", port=0)
    try:
        ok = svc.complete([[1, 2, 3]], max_tokens=4)
        assert ok["usage"].get("engine") is True

        svc.engine.failure = RuntimeError("simulated device loss")
        with pytest.raises(RuntimeError, match="decode engine is down"):
            svc.complete([[1, 2, 3]], max_tokens=4)
        with pytest.raises(RuntimeError, match="decode engine is down"):
            svc.complete([[1, 2, 3]], max_tokens=4, seed=0)
        req = urllib.request.Request(
            f"http://127.0.0.1:{httpd.server_address[1]}/v1/completions",
            data=json.dumps({"prompt": [1, 2, 3], "max_tokens": 4}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=60)
        assert err.value.code == 500
        assert "decode engine is down" in json.loads(err.value.read())["error"]
    finally:
        httpd.shutdown()
        svc.engine.stop()


def test_streaming_completions_sse():
    """"stream": true → SSE frames arrive one token at a time from the
    running decode loop, and the concatenation equals the non-streamed
    greedy result."""
    import http.client

    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    svc = CompletionService(
        params, cfg, prompt_buckets=(8, 16), batch_buckets=(1, 2),
        engine_slots=2, engine_max_len=64,
    )
    try:
        want = svc.complete([[1, 2, 3, 4]], max_tokens=6)["completions"][0]

        httpd = serve(svc, host="127.0.0.1", port=0)
        conn = http.client.HTTPConnection(
            "127.0.0.1", httpd.server_address[1], timeout=120
        )
        conn.request(
            "POST",
            "/v1/completions",
            body=json.dumps(
                {"prompt": [1, 2, 3, 4], "max_tokens": 6, "stream": True}
            ),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("Content-Type") == "text/event-stream"
        frames = []
        buf = b""
        while True:
            chunk = resp.read(1)
            if not chunk:
                break
            buf += chunk
            while b"\n\n" in buf:
                frame, buf = buf.split(b"\n\n", 1)
                assert frame.startswith(b"data: ")
                frames.append(json.loads(frame[len(b"data: "):]))
            if frames and frames[-1].get("done"):
                break
        conn.close()
        httpd.shutdown()

        tokens = [f["token"] for f in frames if "token" in f]
        assert frames[-1]["done"] is True
        assert frames[-1]["tokens"] == want
        assert tokens == want
        assert len(frames) == len(want) + 1  # one frame per token + done
    finally:
        svc.engine.stop()
