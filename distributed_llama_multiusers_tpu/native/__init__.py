"""Native (C++) runtime components, bound via ctypes.

The reference implements its host-side runtime (quant codecs, weight
splitting, mmap IO) in C++ (src/nn/nn-quants.cpp, src/mmap.hpp); this package
provides the TPU framework's equivalents. The shared library is built by the
repo Makefile (`make native`) or on demand by :func:`ensure_built`; every
consumer falls back to the numpy codecs when the library is unavailable.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

from ..lockcheck import make_lock

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO_PATH = os.path.join(_DIR, "libdllama_native.so")
_SRC = os.path.join(_DIR, "quant_codec.cpp")

# witness-wrappable (DLLAMA_LOCKCHECK=1, lockcheck.py); module-level locks
# qualify by module stem in the static lock graph
_lock = make_lock("native._lock")
_lib: ctypes.CDLL | None = None
_load_failed = False


# single source of truth for the build lines; the Makefile targets shell out
# to this module so the paths cannot drift
# no -march=native: the .so is git-ignored but travels with a copied tree,
# and one built for another machine's CPU would die on an illegal instruction
BUILD_FLAGS = ["-O3", "-shared", "-fPIC", "-std=c++17"]
# ASan+UBSan build (the reference's test strategy leans on sanitizer CI,
# SURVEY.md §5.2): `make sanitize` builds this variant and runs the native
# test suite against it with libasan preloaded
SANITIZE_FLAGS = [
    "-O1", "-g", "-fno-omit-frame-pointer",
    "-fsanitize=address,undefined", "-fno-sanitize-recover=all",
    "-shared", "-fPIC", "-std=c++17",
]
_SO_SAN_PATH = os.path.join(_DIR, "libdllama_native_asan.so")


def ensure_built(quiet: bool = True, sanitize: bool = False) -> bool:
    """Compile the shared library if missing/stale (g++). Returns success.
    Compiles to a per-pid temp file then renames, so concurrent first runs
    cannot corrupt the .so. ``sanitize`` builds the ASan+UBSan variant to
    its own path (load it via DLLAMA_NATIVE_SO with libasan preloaded)."""
    so_path = _SO_SAN_PATH if sanitize else _SO_PATH
    flags = SANITIZE_FLAGS if sanitize else BUILD_FLAGS
    try:
        if os.path.exists(so_path) and os.path.getmtime(so_path) >= os.path.getmtime(_SRC):
            return True
    except OSError:
        # source missing: usable iff a prebuilt .so is loadable
        return os.path.exists(so_path)
    tmp = f"{so_path}.{os.getpid()}.tmp"
    cmd = ["g++", *flags, "-o", tmp, _SRC, "-lpthread"]
    try:
        subprocess.run(cmd, check=True, capture_output=quiet)
        os.replace(tmp, so_path)
        return True
    except (subprocess.CalledProcessError, FileNotFoundError, OSError):
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def load() -> ctypes.CDLL | None:
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _load_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _load_failed:
            return None
        # test hook: point at an alternate build (e.g. the sanitized .so)
        override = os.environ.get("DLLAMA_NATIVE_SO")
        # dlint: ok[lock-blocking] first-load compile is serialized behind the load lock on purpose: concurrent importers must block until one .so exists rather than race the compiler
        if not override and not ensure_built():
            _load_failed = True
            return None
        try:
            lib = ctypes.CDLL(override or _SO_PATH)
        except OSError:
            _load_failed = True
            return None
        c_f32p = ctypes.POINTER(ctypes.c_float)
        c_u8p = ctypes.POINTER(ctypes.c_uint8)
        c_i8p = ctypes.POINTER(ctypes.c_int8)
        c_u16p = ctypes.POINTER(ctypes.c_uint16)
        lib.dlq_q40_quantize.argtypes = [c_f32p, c_u8p, ctypes.c_int64, ctypes.c_int]
        lib.dlq_q40_dequantize.argtypes = [c_u8p, c_f32p, ctypes.c_int64, ctypes.c_int]
        lib.dlq_q40_to_planar.argtypes = [c_u8p, c_i8p, c_f32p, ctypes.c_int64, ctypes.c_int]
        lib.dlq_q80_quantize.argtypes = [c_f32p, c_u8p, ctypes.c_int64, ctypes.c_int, ctypes.c_int]
        lib.dlq_q80_dequantize.argtypes = [c_u8p, c_f32p, ctypes.c_int64, ctypes.c_int]
        lib.dlq_f16_to_f32.argtypes = [c_u16p, c_f32p, ctypes.c_int64, ctypes.c_int]
        lib.dlq_f32_to_f16.argtypes = [c_f32p, c_u16p, ctypes.c_int64, ctypes.c_int]
        lib.dlq_abi_version.restype = ctypes.c_int
        # version gate FIRST: a stale v1 build (or a DLLAMA_NATIVE_SO
        # override) must fall back cleanly, not AttributeError on symbols
        # that predate it
        if lib.dlq_abi_version() != 2:
            _load_failed = True
            return None
        c_i32p = ctypes.POINTER(ctypes.c_int32)
        c_i64p = ctypes.POINTER(ctypes.c_int64)
        lib.dllama_bpe_create.argtypes = [
            c_u8p, c_i64p, ctypes.c_int32, ctypes.c_int32, c_f32p,
        ]
        lib.dllama_bpe_create.restype = ctypes.c_void_p
        lib.dllama_bpe_destroy.argtypes = [ctypes.c_void_p]
        lib.dllama_bpe_merge.argtypes = [
            ctypes.c_void_p, c_i32p, ctypes.c_int32, c_i32p,
        ]
        lib.dllama_bpe_merge.restype = ctypes.c_int32
        lib.dllama_bpe_encode.argtypes = [
            ctypes.c_void_p, c_u8p, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_int, c_i32p,
        ]
        lib.dllama_bpe_encode.restype = ctypes.c_int32
        _lib = lib
        return _lib


def _threads() -> int:
    return min(os.cpu_count() or 1, 16)


def available() -> bool:
    return load() is not None


def _ptr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


def quantize_q40(x: np.ndarray) -> np.ndarray | None:
    lib = load()
    if lib is None:
        return None
    x = np.ascontiguousarray(x, dtype=np.float32).reshape(-1)
    assert x.size % 32 == 0
    n_blocks = x.size // 32
    out = np.empty((n_blocks, 18), np.uint8)
    lib.dlq_q40_quantize(_ptr(x, ctypes.c_float), _ptr(out, ctypes.c_uint8), n_blocks, _threads())
    return out


def dequantize_q40(blocks: np.ndarray) -> np.ndarray | None:
    lib = load()
    if lib is None:
        return None
    blocks = np.ascontiguousarray(blocks, dtype=np.uint8).reshape(-1, 18)
    out = np.empty(blocks.shape[0] * 32, np.float32)
    lib.dlq_q40_dequantize(_ptr(blocks, ctypes.c_uint8), _ptr(out, ctypes.c_float), blocks.shape[0], _threads())
    return out


def q40_to_planar(blocks: np.ndarray):
    lib = load()
    if lib is None:
        return None
    blocks = np.ascontiguousarray(blocks, dtype=np.uint8).reshape(-1, 18)
    n = blocks.shape[0]
    values = np.empty((n, 32), np.int8)
    scales = np.empty(n, np.float32)
    lib.dlq_q40_to_planar(
        _ptr(blocks, ctypes.c_uint8), _ptr(values, ctypes.c_int8), _ptr(scales, ctypes.c_float), n, _threads()
    )
    return values, scales


def quantize_q80(x: np.ndarray, mode: str = "runtime") -> np.ndarray | None:
    lib = load()
    if lib is None:
        return None
    x = np.ascontiguousarray(x, dtype=np.float32).reshape(-1)
    assert x.size % 32 == 0
    n_blocks = x.size // 32
    out = np.empty((n_blocks, 34), np.uint8)
    lib.dlq_q80_quantize(
        _ptr(x, ctypes.c_float), _ptr(out, ctypes.c_uint8), n_blocks,
        1 if mode == "converter" else 0, _threads(),
    )
    return out


def dequantize_q80(blocks: np.ndarray) -> np.ndarray | None:
    lib = load()
    if lib is None:
        return None
    blocks = np.ascontiguousarray(blocks, dtype=np.uint8).reshape(-1, 34)
    out = np.empty(blocks.shape[0] * 32, np.float32)
    lib.dlq_q80_dequantize(_ptr(blocks, ctypes.c_uint8), _ptr(out, ctypes.c_float), blocks.shape[0], _threads())
    return out


class NativeBpe:
    """C++ BPE pair-merge context (tokenizer encode hot path). Holds the
    vocab/score tables native-side; ``merge`` is a single ctypes call per
    prompt. Token-identical to Tokenizer._merge (tests/test_native.py
    A/Bs them); falls back to None when the library is unavailable."""

    def __init__(self, vocab: list, regular_size: int, scores: list):
        lib = load()
        if lib is None:
            raise OSError("native library unavailable")
        concat = b"".join(vocab)
        buf = np.frombuffer(concat, np.uint8) if concat else np.zeros(1, np.uint8)
        offsets = np.zeros(len(vocab) + 1, np.int64)
        np.cumsum([len(v) for v in vocab], out=offsets[1:])
        sc = np.ascontiguousarray(scores, np.float32)
        self._lib = lib
        self._handle = lib.dllama_bpe_create(
            _ptr(np.ascontiguousarray(buf), ctypes.c_uint8),
            _ptr(offsets, ctypes.c_int64),
            len(vocab), regular_size,
            _ptr(sc, ctypes.c_float),
        )
        if not self._handle:
            raise OSError("dllama_bpe_create failed")

    def merge(self, ids: list) -> list:
        arr = np.ascontiguousarray(ids, np.int32)
        out = np.empty(max(len(arr), 1), np.int32)
        m = self._lib.dllama_bpe_merge(
            self._handle,
            _ptr(arr, ctypes.c_int32), len(arr),
            _ptr(out, ctypes.c_int32),
        )
        return out[:m].tolist()

    def encode(self, text: bytes, bos: int, add_special: bool):
        """Full scan+merge in one native call; None when the text has an
        untokenizable buffer (caller falls back to the Python encoder for
        the exact exception)."""
        data = np.frombuffer(text, np.uint8) if text else np.zeros(1, np.uint8)
        out = np.empty(len(text) + 1, np.int32)
        m = self._lib.dllama_bpe_encode(
            self._handle,
            _ptr(np.ascontiguousarray(data), ctypes.c_uint8), len(text),
            bos, int(add_special),
            _ptr(out, ctypes.c_int32),
        )
        if m < 0:
            return None
        return out[:m].tolist()

    def __del__(self):
        h = getattr(self, "_handle", None)
        if h:
            self._lib.dllama_bpe_destroy(h)
            self._handle = None
