"""The controls and the broken paths, at a size a test run can hold.

A control is the plain reference computed in the nearest precision below
the configuration's, put in the program's place: the comparison that
decides ``correct`` has to fail it, under the same limits that a sound
run of the program passes. The on-chip readings at the cells' own sizes
are in PERF.md; these tests keep the mechanism honest.

A broken path is a run of the harness (all of it but the look for a
chip) with the timed path broken underneath: ``correct`` has to come
out false."""

import json
import os
import time

import numpy as np
import pytest

from benchmark.drivers import train as train_driver
from benchmark.harness import core
from benchmark.reference import model, serve as ref_serve

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny")
ROOTS = (TINY, core.BENCH_DIR)


@pytest.fixture(scope="module")
def tiny_manifest():
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        return json.load(f)


def prepare(workload, seed, manifest):
    return core.prepare(
        workload, seed, 1.0, False, t0=time.monotonic(), roots=ROOTS,
        manifest=manifest, rehearsal=True,
    )[0]


def test_training_control_fails_the_limits_a_sound_run_passes(tiny_manifest):
    run = prepare("tiny-train", 5, tiny_manifest)
    limits, opt, n = run.cell["limits"], run.mix["optimizer"], run.mix["check_steps"]
    trainer, feed, host = train_driver.build(run, 5)
    first = train_driver.program_first_steps(trainer, feed, n, opt["b1"])
    args = (trainer.params, first["lora0"], host[:n], run.config, opt)
    ref = train_driver.reference_numbers(*args)
    sound = train_driver.gaps(first, ref)
    assert all(v <= limits[k.split(".")[0]] for k, v in sound.items()), sound
    # int8 base weights stored as int4: one of the numbers has to fail
    low = train_driver.reference_numbers(*args, model.Precision(weights="int4"))
    control = train_driver.gaps(low, ref)
    assert any(v > limits[k.split(".")[0]] for k, v in control.items()), control
    assert control["first_grad_leaf_gap"] > 3 * sound["first_grad_leaf_gap"]


def test_serving_control_reads_a_wider_gap_than_sound_tokens(tiny_manifest):
    from benchmark.harness import weights

    run = prepare("tiny-serve", 9, tiny_manifest)
    params = weights.make_params(run.config, 9, run.family)
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 256, size=24).tolist()
    # greedy tokens of the reference itself: the sound gap is exactly 0
    served = []
    for _ in range(12):
        lg = model.logits(
            params, None, np.asarray([prompt + served], np.int32),
            np.ones((1, len(prompt) + len(served)), np.int32), run.config,
        )
        served.append(int(np.argmax(lg[0, -1])))
    length = ref_serve.padded_length(64)
    assert ref_serve.widest_gap(params, run.config, [(prompt, served)], length) == 0.0
    # a token altered where it is produced opens a gap
    wrong = list(served)
    wrong[5] = (wrong[5] + 1) % 256
    assert ref_serve.widest_gap(params, run.config, [(prompt, wrong)], length) > (
        run.cell["limits"]["served_logit_gap_max"]
    )
    # the control reads, at every position, the token that int4 weights
    # put first: not always the reference's, so its widest gap is above 0
    gap = ref_serve.widest_gap(
        params, run.config, [(prompt, served)], length,
        chosen_by=model.Precision(weights="int4"),
    )
    assert gap > 0.0


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
    tiny_manifest, monkeypatch
):
    from odh_kubeflow_tpu.train import Trainer

    real = Trainer.train_step

    def stuck(self, batch):
        before = train_driver._copy((self.lora_params, self.opt_state))
        metrics = real(self, batch)
        self.lora_params, self.opt_state = before
        return metrics

    monkeypatch.setattr(Trainer, "train_step", stuck)
    result = core.run_cell(
        "tiny-train", 3, 1.0, False, t0=time.monotonic(), roots=ROOTS,
        manifest=tiny_manifest, rehearsal=True,
    )
    assert result["correct"] is False
    failed = {c["name"] for c in result["checks"] if not c["ok"]}
    assert "update_leaf_gap" in failed, result["checks"]


def test_a_token_altered_where_it_is_produced_is_not_correct(
    tiny_manifest, monkeypatch
):
    from odh_kubeflow_tpu.models import engine

    real = engine._Request._emit

    def altered(self, tok):
        # every fifth token of a stream comes out as its neighbour
        real(self, (tok + 1) % 256 if len(self.tokens) % 5 == 4 else tok)

    monkeypatch.setattr(engine._Request, "_emit", altered)
    result = core.run_cell(
        "tiny-serve", 3, 1.0, False, t0=time.monotonic(), roots=ROOTS,
        manifest=tiny_manifest, rehearsal=True,
    )
    assert result["correct"] is False
    failed = {c["name"] for c in result["checks"] if not c["ok"]}
    assert failed == {"served_logit_gap_max", "served_logit_gap_mean"}, result["checks"]


def test_an_engine_that_falls_silent_before_the_close_is_not_correct(
    tiny_manifest, monkeypatch
):
    from odh_kubeflow_tpu.models import engine

    real = engine._Request._emit
    emitted = []

    def silent_after_a_while(self, tok):
        # the warm-up streams 30 tokens; 40 more, and nothing comes out
        # (the engine goes on; its tokens stop reaching the clients)
        emitted.append(tok)
        queue = self.token_q
        if len(emitted) > 70:
            self.token_q = None
        try:
            real(self, tok)
        finally:
            self.token_q = queue

    monkeypatch.setattr(engine._Request, "_emit", silent_after_a_while)
    result = core.run_cell(
        "tiny-serve-saturated", 3, 1.0, False, t0=time.monotonic(), roots=ROOTS,
        manifest=tiny_manifest, rehearsal=True,
    )
    assert result["correct"] is False
    failed = {c["name"] for c in result["checks"] if not c["ok"]}
    assert "stalled_at_close" in failed, result["checks"]
    # the silence is paid for: 40 tokens over the window and the wait
    rate = result["metrics"]["serve_tokens_per_s"]["value"]
    assert rate <= 40 / (1.0 + run_timeout(tiny_manifest)) + 1e-9, rate


def run_timeout(manifest):
    return prepare("tiny-serve-saturated", 3, manifest).mix["close_timeout_s"]
