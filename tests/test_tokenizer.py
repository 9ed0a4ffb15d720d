"""Byte-level BPE tokenizer + the real-text fine-tune leg (VERDICT r2
item 5): text → tokens → pack_documents → Trainer, loss dropping well
below the uniform baseline on a frozen excerpt of this repo's docs."""

import math
import pathlib

import numpy as np
import pytest

from odh_kubeflow_tpu.train.tokenizer import (
    BOS_ID,
    EOS_ID,
    MIN_VOCAB,
    PAD_ID,
    Tokenizer,
    train_bpe,
)

# A corpus that does not grow: four control-plane sections of
# docs/GUIDE.md as they stood at PR 27 (24.7 KB), one document a
# section. The fine-tune test trains a fixed 100 steps against a fixed
# bound (it reaches 3.60 against 0.65 * ln 512 = 4.05), so it must not
# train on documents that every PR lengthens.
CORPUS = pathlib.Path(__file__).resolve().parent / "data" / "tokenizer_corpus.md"


def _docs_corpus() -> list[str]:
    sections = CORPUS.read_text().split("\n## ")
    return [sections[0]] + ["## " + s for s in sections[1:]]


@pytest.fixture(scope="module")
def tok() -> Tokenizer:
    return train_bpe(_docs_corpus(), vocab_size=512)


def test_roundtrip_lossless(tok):
    for s in (
        "hello world",
        "TPU v5e — bfloat16 µ-benchmarks: 2×2 mesh, ≥50 % MFU?",
        "  leading spaces\nand\nnewlines\t\ttabs",
        "日本語テキスト and émojis 🎉",
        "",
    ):
        assert tok.decode(tok.encode(s)) == s


def test_bpe_compresses_in_domain_text(tok):
    text = _docs_corpus()[0][:2000]
    ids = tok.encode(text)
    n_bytes = len(text.encode("utf-8"))
    assert len(ids) < 0.6 * n_bytes, (len(ids), n_bytes)
    # out-of-domain text still encodes (byte fallback), just longer
    weird = "zzqxj αβγδε \x00\x01"
    assert tok.decode(tok.encode(weird)) == weird


def test_specials_and_determinism(tok, tmp_path):
    ids = tok.encode("make test", bos=True, eos=True)
    assert ids[0] == BOS_ID and ids[-1] == EOS_ID
    assert PAD_ID == 0  # pack_documents' default pad id
    # interior ids are real content tokens (bytes or merges), never specials
    assert all(3 <= i < tok.vocab_size for i in ids[1:-1])
    p = tmp_path / "tok.json"
    tok.save(str(p))
    again = Tokenizer.load(str(p))
    assert again.encode("make test", bos=True, eos=True) == ids
    assert again.vocab_size == tok.vocab_size
    # retraining on the same corpus is bit-identical (ordered merges)
    retrained = train_bpe(_docs_corpus(), vocab_size=512)
    assert retrained.merges == tok.merges


def test_cli_train_and_encode(tmp_path):
    from odh_kubeflow_tpu.train.tokenizer import main

    out = tmp_path / "tok.json"
    rc = main(
        [
            "train",
            "--corpus",
            str(CORPUS),
            "--vocab-size",
            "400",
            "--out",
            str(out),
        ]
    )
    assert rc == 0
    assert Tokenizer.load(str(out)).vocab_size <= 400


def test_finetune_on_real_text_loss_drops(tok):
    """The full data leg: repo docs → BPE ids → pack_documents →
    Trainer on tiny Llama. The loss must fall materially below the
    uniform-distribution baseline ln(V) — proof the model is learning
    *text statistics*, which fake random-int batches can never show."""
    import jax.numpy as jnp

    from odh_kubeflow_tpu.models import LlamaConfig, LoraConfig
    from odh_kubeflow_tpu.train import TrainConfig, Trainer
    from odh_kubeflow_tpu.train.data import pack_documents

    docs = [
        tok.encode(text, bos=True, eos=True) for text in _docs_corpus()
    ]
    cfg = LlamaConfig.tiny(vocab_size=tok.vocab_size, dtype=jnp.float32)
    trainer = Trainer(
        cfg,
        TrainConfig(learning_rate=1e-2, warmup_steps=5, total_steps=100),
    )

    uniform = math.log(tok.vocab_size)
    first = last = None
    step = 0
    while step < 100:
        for batch in pack_documents(docs, batch_size=8, seq_len=128):
            metrics = trainer.train_step(
                {k: np.asarray(v) for k, v in batch.items()}
            )
            loss = float(metrics["loss"])
            if first is None:
                first = loss
            last = loss
            step += 1
            if step >= 100:
                break
    assert first is not None and last is not None
    # initial loss ~ uniform baseline; trained loss far below it
    assert first > 0.8 * uniform, (first, uniform)
    assert last < 0.65 * uniform, (last, uniform)
    assert last < first - 2.0, (first, last)
