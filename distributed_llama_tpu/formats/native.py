"""ctypes loader for the native BPE merge engine (native/bpe_encoder.cpp).

Builds the shared library on first use with g++ and caches it next to the
source under a name that carries a hash of the source and the build flags,
so a library built from other source — a stale one, or one that arrived with
a copied tree whose mtimes say nothing — is never loaded in its place. Every
caller must tolerate unavailability (no compiler, read-only fs) and fall back
to the Python merge loop in tokenizer.py, the semantic reference.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading

import numpy as np

_lock = threading.Lock()

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)


def _build_and_load(src: str, stem: str, extra_flags: tuple = ()):
    """dlopen the library built from exactly `src` with exactly these flags,
    compiling it first if it is not there; None on any failure."""
    if os.environ.get("DLT_NO_NATIVE"):
        return None
    try:
        with open(src, "rb") as f:
            digest = hashlib.sha256(f.read() + repr(extra_flags).encode()).hexdigest()[:16]
    except OSError:
        return None
    so = f"{stem}.{digest}.so"
    if not os.path.exists(so):
        # pid-suffixed temp: concurrent builders (server + CLI, pytest-xdist)
        # must not interleave writes into one temp and install a corrupt .so
        tmp = f"{so}.{os.getpid()}.tmp"
        cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", *extra_flags, src, "-o", tmp]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
            os.replace(tmp, so)
        except (OSError, subprocess.SubprocessError):
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return None
        for old in glob.glob(f"{stem}.*.so") + glob.glob(f"{stem}.so"):
            if old != so:  # builds of other source: never loaded again
                try:
                    os.unlink(old)
                except OSError:
                    pass
    try:
        return ctypes.CDLL(so)
    except OSError:
        return None


_BPE_SRC = os.path.join(_NATIVE_DIR, "bpe_encoder.cpp")
_BPE_STEM = os.path.join(_NATIVE_DIR, "libbpeencoder")
_bpe_lib = None
_bpe_tried = False


def _load_bpe():
    global _bpe_lib, _bpe_tried
    with _lock:
        if _bpe_tried:
            return _bpe_lib
        _bpe_tried = True
        lib = _build_and_load(_BPE_SRC, _BPE_STEM)
        if lib is None:
            return None
        lib.bpe_create.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int32, ctypes.c_int32,
        ]
        lib.bpe_create.restype = ctypes.c_void_p
        lib.bpe_free.argtypes = [ctypes.c_void_p]
        lib.bpe_free.restype = None
        lib.bpe_merge.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]
        lib.bpe_merge.restype = ctypes.c_int64
        _bpe_lib = lib
        return _bpe_lib


def bpe_available() -> bool:
    """Did the native merge engine build and load in this process?"""
    return _load_bpe() is not None


class NativeBpe:
    """Handle over the C++ merge engine for one vocabulary. `create` returns
    None when the native path is unavailable."""

    @staticmethod
    def create(vocab: list, scores, n_regular: int) -> "NativeBpe | None":
        lib = _load_bpe()
        if lib is None:
            return None
        blob = b"".join(vocab)
        offsets = np.zeros(len(vocab) + 1, dtype=np.int64)
        np.cumsum([len(v) for v in vocab], out=offsets[1:])
        scores_arr = np.ascontiguousarray(scores, dtype=np.float32)
        buf = np.frombuffer(blob, dtype=np.uint8) if blob else np.zeros(1, np.uint8)
        handle = lib.bpe_create(
            buf.ctypes.data, offsets.ctypes.data, scores_arr.ctypes.data,
            len(vocab), n_regular,
        )
        if not handle:
            return None
        obj = NativeBpe()
        obj._lib = lib
        obj._handle = handle
        return obj

    def merge(self, tokens: list) -> list:
        arr = np.asarray(tokens, dtype=np.int32)
        new_n = self._lib.bpe_merge(self._handle, arr.ctypes.data, len(arr))
        return arr[:new_n].tolist()

    def __del__(self):
        lib = getattr(self, "_lib", None)
        handle = getattr(self, "_handle", None)
        if lib is not None and handle:
            lib.bpe_free(handle)
