"""ctypes loader for the native runtime library.

The reference's runtime is C (`csrc/` — SURVEY.md §2.2); this module
holds the framework's native CPU components: currently the K=7 Viterbi
decoder (SORA-brick analogue), a host-side fallback decoder and the
C side of the ops' cross-checks. Builds on demand with
``make`` (gcc); everything degrades gracefully to the numpy/jax paths
if no toolchain is present.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as np

_DIR = os.path.join(os.path.dirname(__file__), "native")
_SO = os.path.join(_DIR, "libziria_native.so")

_lib: Optional[ctypes.CDLL] = None
_failed = False

# every symbol the bindings below touch; a stale .so missing any of them
# (built before a source was added, rebuild failing) means the library is
# unusable and callers must take their numpy fallbacks
_REQUIRED_SYMS = (
    "ziria_viterbi_decode", "ziria_pack_bits", "ziria_unpack_bits",
    "ziria_parse_dbg_bits", "ziria_format_dbg_bits",
    "ziria_parse_dbg_ints", "ziria_format_dbg_ints",
)


def load(build: bool = True) -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable.
    A failed build attempt is cached so stream I/O doesn't re-spawn make
    on every call."""
    global _lib, _failed
    if _lib is not None or _failed:
        return _lib
    if build:
        # always delegate to make: it no-ops when the .so is newer than
        # the sources and rebuilds after edits (the .so is built with
        # -march=native, so it must never ship prebuilt — .gitignore'd)
        try:
            subprocess.run(["make", "-C", _DIR], check=True,
                           capture_output=True)
        except (OSError, subprocess.CalledProcessError):
            pass
    if not os.path.exists(_SO):
        _failed = _failed or build
        return None
    lib = ctypes.CDLL(_SO)
    if not all(hasattr(lib, s) for s in _REQUIRED_SYMS):
        _failed = _failed or build   # stale .so and rebuild didn't fix it
        return None
    lib.ziria_viterbi_decode.argtypes = [
        ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_uint8)]
    lib.ziria_viterbi_decode.restype = ctypes.c_int
    u8p, i64p = ctypes.POINTER(ctypes.c_uint8), \
        ctypes.POINTER(ctypes.c_int64)
    lib.ziria_pack_bits.argtypes = [u8p, ctypes.c_int64, u8p]
    lib.ziria_unpack_bits.argtypes = [u8p, ctypes.c_int64, u8p]
    lib.ziria_parse_dbg_bits.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                         u8p]
    lib.ziria_parse_dbg_bits.restype = ctypes.c_int64
    lib.ziria_format_dbg_bits.argtypes = [u8p, ctypes.c_int64,
                                          ctypes.c_char_p]
    lib.ziria_parse_dbg_ints.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                         i64p]
    lib.ziria_parse_dbg_ints.restype = ctypes.c_int64
    lib.ziria_format_dbg_ints.argtypes = [i64p, ctypes.c_int64,
                                          ctypes.c_char_p]
    lib.ziria_format_dbg_ints.restype = ctypes.c_int64
    _lib = lib
    return _lib


def viterbi_decode_native(llrs: np.ndarray) -> np.ndarray:
    """Native C Viterbi: llrs (T,2) or (2T,) float32 -> (T,) uint8 bits.
    Raises RuntimeError if the library is unavailable."""
    lib = load()
    if lib is None:
        raise RuntimeError("native library unavailable (no gcc/make?)")
    llrs = np.ascontiguousarray(np.asarray(llrs, np.float32).reshape(-1, 2))
    T = llrs.shape[0]
    out = np.zeros(T, np.uint8)
    rc = lib.ziria_viterbi_decode(
        llrs.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.c_int64(T),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    if rc != 0:
        raise RuntimeError(f"native viterbi failed rc={rc}")
    return out


# --------------------------------------------------------------------------
# Stream buffer helpers (buf.c): dbg parse/format + bit pack/unpack.
# Each returns None when the native library is unavailable, so callers
# (runtime/buffers.py) keep their numpy fallback.
# --------------------------------------------------------------------------


def _u8p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def parse_dbg_bits_native(text: str) -> Optional[np.ndarray]:
    lib = load()
    if lib is None:
        return None
    raw = text.encode("ascii", "replace")
    out = np.empty(len(raw), np.uint8)
    n = lib.ziria_parse_dbg_bits(raw, len(raw), _u8p(out))
    return out[:n].copy()


def format_dbg_bits_native(bits: np.ndarray) -> Optional[str]:
    lib = load()
    if lib is None:
        return None
    bits = np.ascontiguousarray(np.asarray(bits, np.uint8).ravel())
    buf = ctypes.create_string_buffer(bits.size + 1)
    lib.ziria_format_dbg_bits(_u8p(bits), bits.size, buf)
    return buf.value.decode("ascii")


def parse_dbg_ints_native(text: str) -> Optional[np.ndarray]:
    lib = load()
    if lib is None:
        return None
    raw = text.encode("ascii", "replace")
    out = np.empty(len(raw) // 2 + 2, np.int64)
    n = lib.ziria_parse_dbg_ints(
        raw, len(raw), out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    if n < 0:
        raise ValueError("malformed dbg integer stream")
    return out[:n].copy()


def format_dbg_ints_native(vals: np.ndarray) -> Optional[str]:
    lib = load()
    if lib is None:
        return None
    vals = np.ascontiguousarray(np.asarray(vals, np.int64).ravel())
    buf = ctypes.create_string_buffer(int(vals.size) * 21 + 1)
    n = lib.ziria_format_dbg_ints(
        vals.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        vals.size, buf)
    return buf.raw[:n].decode("ascii")


def pack_bits_native(bits: np.ndarray) -> Optional[bytes]:
    lib = load()
    if lib is None:
        return None
    bits = np.ascontiguousarray(np.asarray(bits, np.uint8).ravel())
    out = np.zeros((bits.size + 7) // 8, np.uint8)
    lib.ziria_pack_bits(_u8p(bits), bits.size, _u8p(out))
    return out.tobytes()


def unpack_bits_native(data: bytes) -> Optional[np.ndarray]:
    lib = load()
    if lib is None:
        return None
    src = np.frombuffer(data, np.uint8)
    out = np.empty(src.size * 8, np.uint8)
    lib.ziria_unpack_bits(_u8p(np.ascontiguousarray(src)), src.size,
                          _u8p(out))
    return out
