"""Native (C++) runtime components, loaded via ctypes.

The TPU compute path is JAX/XLA; these are the host-side hot loops
around it. Each component ships as a single .cpp with a plain C ABI
(this image has no pybind11) plus a ctypes wrapper here. The shared
object is built on first use with the system g++ and cached next to the
source under a name that carries the digest of that source (and of the
build flags), so an object built from another tree — a copy, a
checkout, a stale build — is never loaded: its name does not match.
Everything degrades gracefully to the pure-Python implementation when
no compiler is available (``available()`` → False), so the package has
no hard native dependency.

Build explicitly with ``make native`` (top-level Makefile) or let the
first import compile lazily.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import shutil
import subprocess
import tempfile
import threading
from typing import Optional

import numpy as np

_DIR = os.path.dirname(__file__)
_SRC = os.path.join(_DIR, "packer.cpp")
_SO_STEM = "libodhkf_native"
_JT_SRC = os.path.join(_DIR, "jsontree.cpp")
_JT_SO_STEM = "_odhkf_jsontree"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False
_jt_mod = None
_jt_tried = False


def _compile(src: str, stem: str, extra: list[str], force: bool) -> Optional[str]:
    """Build ``src`` into ``<stem>.<digest>.so`` beside it (or reuse
    that file), where the digest covers the source bytes and the extra
    flags. mtimes say nothing after a copy or a checkout; the content
    does."""
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update("\0".join(extra).encode())
    out = os.path.join(_DIR, f"{stem}.{h.hexdigest()[:16]}.so")
    if not force and os.path.exists(out):
        return out
    cxx = shutil.which("g++") or shutil.which("c++") or shutil.which("clang++")
    if cxx is None:
        return None
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_DIR)
    os.close(fd)
    try:
        subprocess.run(
            [cxx, "-O3", "-shared", "-fPIC", "-std=c++17", *extra, src, "-o", tmp],
            check=True,
            capture_output=True,
        )
        os.replace(tmp, out)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    for stale in glob.glob(os.path.join(_DIR, f"{stem}.*.so")):
        if stale != out:
            try:
                os.unlink(stale)  # built from a source that is gone
            except OSError:
                pass
    return out


def build(force: bool = False) -> Optional[str]:
    """Compile the native components; returns the packer .so path (or
    None when no compiler exists). Compiles into temp files then
    atomically renames, so concurrent builders race benignly. Also
    builds the jsontree CPython extension (machinery's hot deepcopy);
    its failure is non-fatal — everything degrades to Python."""
    import sysconfig

    try:
        _compile(
            _JT_SRC,
            _JT_SO_STEM,
            ["-I" + sysconfig.get_paths()["include"]],
            force,
        )
    except (OSError, subprocess.CalledProcessError):
        pass
    return _compile(_SRC, _SO_STEM, [], force)


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        try:
            so = build()
            if so is None:
                _load_failed = True
                return None
            lib = ctypes.CDLL(so)
            lib.pack_documents_c.restype = ctypes.c_long
            lib.pack_documents_c.argtypes = [
                ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.c_long,
                ctypes.c_long,
                ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_float),
                ctypes.c_long,
            ]
            _lib = lib
        except (OSError, subprocess.CalledProcessError):
            _load_failed = True
    return _lib


def available() -> bool:
    return _load() is not None


def _jsontree_module():
    """The lazily built+loaded jsontree extension module, or None.
    One compile+load serves both entry points (deepcopy and dumps)."""
    global _jt_mod, _jt_tried
    if _jt_tried:
        return _jt_mod
    with _lock:
        if _jt_tried:
            return _jt_mod
        try:
            import sysconfig

            so = _compile(
                _JT_SRC,
                _JT_SO_STEM,
                ["-I" + sysconfig.get_paths()["include"]],
                False,
            )
            if so is not None:
                from importlib.machinery import ExtensionFileLoader
                from importlib.util import module_from_spec, spec_from_loader

                loader = ExtensionFileLoader("_odhkf_jsontree", so)
                spec = spec_from_loader("_odhkf_jsontree", loader)
                mod = module_from_spec(spec)
                loader.exec_module(mod)
                _jt_mod = mod
        except (OSError, subprocess.CalledProcessError, ImportError):
            _jt_mod = None
        _jt_tried = True
    return _jt_mod


def jsontree_deepcopy():
    """The C deepcopy for JSON-shaped trees (machinery/objects.py's
    hot path), or None when it can't build/load. Lazy-built and cached
    like the packer; parity with the Python fallback is contract-tested
    in tests/test_native.py."""
    mod = _jsontree_module()
    return None if mod is None else mod.deepcopy


def jsontree_dumps():
    """The C serializer for JSON-shaped trees (the web/API tier's hot
    response path; machinery/serialize.py fronts it), or None when it
    can't build/load. The returned callable has EXACT ``json.dumps(obj)
    .encode()`` parity: the extension raises its ``Fallback`` exception
    for any input it cannot prove it serializes identically (non-str
    dict keys, exotic leaves) and this wrapper re-serializes with the
    stdlib — so behaviour, output bytes, and error messages all match.
    Capability-probed: a stale prebuilt .so without the ``dumps`` entry
    point degrades to None (callers use the pure-Python path)."""
    mod = _jsontree_module()
    if mod is None or not hasattr(mod, "dumps") or not hasattr(mod, "Fallback"):
        return None  # stale .so from before the dumps entry point
    import json as _json

    c_dumps = mod.dumps
    fallback = mod.Fallback

    def dumps(obj):
        try:
            return c_dumps(obj)
        except fallback:
            return _json.dumps(obj).encode()

    return dumps


def _i32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def pack_rows(
    flat: np.ndarray,  # int32 [total] concatenated tokens
    doc_lens: np.ndarray,  # int64 [n_docs]
    seq_len: int,
    pad_id: int = 0,
) -> dict:
    """Pack the whole document stream into [n_rows, seq_len] arrays in
    one native pass. Raises RuntimeError when the native library is
    unavailable — callers (train/data.py) decide the fallback."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native packer unavailable (no C++ compiler)")
    flat = np.ascontiguousarray(flat, np.int32)
    doc_lens = np.ascontiguousarray(doc_lens, np.int64)
    total = int(doc_lens.sum())
    if total != flat.size:
        raise ValueError(f"doc_lens sum {total} != flat size {flat.size}")
    max_rows = max((total + seq_len - 1) // seq_len, 1)
    tokens = np.full((max_rows, seq_len), pad_id, np.int32)
    targets = np.full((max_rows, seq_len), pad_id, np.int32)
    seg_ids = np.zeros((max_rows, seq_len), np.int32)
    loss_mask = np.zeros((max_rows, seq_len), np.float32)
    n = lib.pack_documents_c(
        _i32p(flat),
        doc_lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(doc_lens),
        seq_len,
        _i32p(tokens),
        _i32p(targets),
        _i32p(seg_ids),
        loss_mask.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        max_rows,
    )
    if n < 0:
        raise RuntimeError("native packer overflowed its row bound (bug)")
    return {
        "tokens": tokens[:n],
        "targets": targets[:n],
        "segment_ids": seg_ids[:n],
        "loss_mask": loss_mask[:n],
    }
