"""Native C++ packer: builds with the system toolchain, matches the
Python reference implementation bit-for-bit, and is actually faster on
the host-side hot loop."""

import numpy as np
import pytest

from odh_kubeflow_tpu import native
from odh_kubeflow_tpu.train.data import pack_documents

pytestmark = pytest.mark.skipif(
    not native.available(), reason="no C++ compiler in this environment"
)


def _random_docs(n, rng, max_len=300):
    return [
        list(rng.integers(1, 1000, size=rng.integers(1, max_len)))
        for _ in range(n)
    ]


@pytest.mark.parametrize("drop_remainder", [True, False])
def test_native_pack_matches_python_bitwise(drop_remainder):
    rng = np.random.default_rng(0)
    docs = _random_docs(40, rng)
    kw = dict(batch_size=3, seq_len=128, drop_remainder=drop_remainder)
    py = list(pack_documents(docs, engine="python", **kw))
    nat = list(pack_documents(docs, engine="native", **kw))
    assert len(py) == len(nat) and len(py) > 0
    for b_py, b_nat in zip(py, nat):
        for k in ("tokens", "targets", "segment_ids", "loss_mask"):
            np.testing.assert_array_equal(b_py[k], b_nat[k], err_msg=k)


def test_native_pack_long_doc_split_across_rows():
    # one 1000-token doc at seq_len 64: pieces resegment per row
    docs = [list(range(1, 1001))]
    py = list(pack_documents(docs, 2, 64, engine="python"))
    nat = list(pack_documents(docs, 2, 64, engine="native"))
    assert len(py) == len(nat)
    for b_py, b_nat in zip(py, nat):
        for k in b_py:
            np.testing.assert_array_equal(b_py[k], b_nat[k])


def test_generator_input_streams_through_python_path():
    rng = np.random.default_rng(1)
    docs = _random_docs(20, rng)
    from_gen = list(pack_documents(iter(docs), 2, 128))
    from_list = list(pack_documents(docs, 2, 128, engine="python"))
    assert len(from_gen) == len(from_list)
    for a, b in zip(from_gen, from_list):
        np.testing.assert_array_equal(a["tokens"], b["tokens"])


def test_native_engine_rejects_generators():
    with pytest.raises(RuntimeError, match="materialised"):
        list(pack_documents(iter([[1, 2]]), 1, 8, engine="native"))


def test_native_pack_rows_validates_lengths():
    with pytest.raises(ValueError, match="doc_lens"):
        native.pack_rows(
            np.arange(5, dtype=np.int32), np.array([3], np.int64), 8
        )


def test_native_and_python_agree_at_scale():
    """Larger stream for batch-boundary coverage. Nothing times the
    two packers against each other; the native one's place in a step
    is the cell mistral7b-qlora-packed4k's ``input_wait_ms_p50``."""
    rng = np.random.default_rng(2)
    docs = _random_docs(500, rng, max_len=200)
    py = list(pack_documents(docs, 8, 1024, engine="python"))
    nat = list(pack_documents(docs, 8, 1024, engine="native"))
    assert len(py) == len(nat) > 0
    for a, b in zip(py, nat):
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])


def test_jsontree_deepcopy_matches_python():
    """The C extension and the Python fallback must agree exactly:
    independent trees (mutating the copy leaves the source alone),
    scalar identity, exotic-leaf fallback."""
    from odh_kubeflow_tpu import native
    from odh_kubeflow_tpu.machinery.objects import _py_deepcopy

    fn = native.jsontree_deepcopy()
    if fn is None:
        pytest.skip("no C++ compiler")

    src = {
        "metadata": {"name": "nb", "labels": {"a": "1"}, "n": 3},
        "spec": {"containers": [{"env": [{"name": "X", "value": "y"}]}]},
        "flag": True,
        "none": None,
        "f": 1.5,
        "exotic": {1, 2},  # set → copy.deepcopy fallback on both paths
    }
    for impl in (fn, _py_deepcopy):
        out = impl(src)
        assert out == src and out is not src
        out["spec"]["containers"][0]["env"].append({"name": "Z"})
        out["metadata"]["labels"]["b"] = "2"
        assert "b" not in src["metadata"]["labels"]
        assert len(src["spec"]["containers"][0]["env"]) == 1
        assert out["exotic"] == {1, 2} and out["exotic"] is not src["exotic"]


def test_store_uses_fast_copy_isolation():
    """Store get/list isolation semantics survive the native copy:
    mutating a returned object never leaks into the store."""
    from odh_kubeflow_tpu.machinery.store import APIServer

    api = APIServer()
    api.create(
        {
            "apiVersion": "v1",
            "kind": "Namespace",
            "metadata": {"name": "iso", "labels": {"x": "1"}},
        }
    )
    got = api.get("Namespace", "iso")
    got["metadata"]["labels"]["x"] = "mutated"
    assert api.get("Namespace", "iso")["metadata"]["labels"]["x"] == "1"


def test_native_pack_fuzz_edge_cases():
    """Property fuzz: random doc-length distributions incl. exact
    row-fills, seq_len-multiple docs, and singleton tokens — native
    and Python packers must agree bit-for-bit on every draw."""
    rng = np.random.default_rng(7)
    for trial in range(10):
        kind = trial % 4
        if kind == 0:  # many tiny docs
            docs = [list(rng.integers(1, 99, size=rng.integers(1, 4)))
                    for _ in range(rng.integers(1, 40))]
        elif kind == 1:  # docs exactly seq_len / multiples
            docs = [list(rng.integers(1, 99, size=s)) for s in (32, 64, 96, 32)]
        elif kind == 2:  # one giant doc
            docs = [list(rng.integers(1, 99, size=500))]
        else:  # mixed, numpy-backed
            docs = [rng.integers(1, 99, size=rng.integers(1, 120), dtype=np.int32)
                    for _ in range(20)]
        for drop in (True, False):
            py = list(pack_documents(list(docs), 2, 32, engine="python",
                                     drop_remainder=drop))
            nat = list(pack_documents(list(docs), 2, 32, engine="native",
                                      drop_remainder=drop))
            assert len(py) == len(nat), (trial, drop)
            for a, b in zip(py, nat):
                for k in a:
                    np.testing.assert_array_equal(a[k], b[k], err_msg=f"{trial}/{k}")


def test_built_object_is_keyed_on_the_content_of_its_source(tmp_path, monkeypatch):
    """A copy or a checkout makes mtimes arbitrary (and the chip tool
    copies ignored files too): the object's name carries the digest of
    its source, so one built from another tree is never loaded."""
    import os
    import shutil

    src = tmp_path / "packer.cpp"
    shutil.copy(native._SRC, src)
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    built = native._compile(str(src), "libx", [], False)
    os.utime(built, (0, 0))  # older than the source: still the right one
    assert native._compile(str(src), "libx", [], False) == built
    assert native._compile(str(src), "libx", ["-DX"], False) != built

    stale = tmp_path / "libx.0123456789abcdef.so"
    stale.write_bytes(b"built from some other tree")
    src.write_text(src.read_text() + "\n// edited\n")
    rebuilt = native._compile(str(src), "libx", [], False)
    assert rebuilt != built and os.path.getsize(rebuilt) > 1000
    # what was built from a source that is gone is swept, never loaded
    assert not stale.exists() and not os.path.exists(built)
