"""Build-on-demand loader for the native runtime shared library.

First use compiles the C++ sources under ``native/`` (JSON codec +
data-loader primitives) with ``g++`` into
``native/build/libtdn_native.so`` (rebuilt when any source is newer)
and loads it via ctypes. Any failure — no compiler, read-only tree,
bad toolchain — degrades to ``None`` and callers use the pure-Python
path; set ``TDN_NATIVE=0`` to skip the native path entirely or
``TDN_NATIVE=require`` to make failures raise (for CI of the native
build itself).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from pathlib import Path

_REPO_ROOT = Path(__file__).resolve().parent.parent.parent
_SRCS = (
    _REPO_ROOT / "native" / "tdn_codec.cc",
    _REPO_ROOT / "native" / "tdn_loader.cc",
)
_LIB = _REPO_ROOT / "native" / "build" / "libtdn_native.so"

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_attempted = False


class NativeBuildError(RuntimeError):
    pass


def _build() -> None:
    _LIB.parent.mkdir(parents=True, exist_ok=True)
    cmd = [
        os.environ.get("CXX", "g++"),
        "-O2", "-fPIC", "-std=c++17", "-Wall", "-Wextra",
        "-shared", "-o", str(_LIB), *[str(s) for s in _SRCS],
    ]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise NativeBuildError(
            f"native build failed: {' '.join(cmd)}\n{proc.stderr}"
        )


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    lib.tdn_model_parse.restype = c.c_void_p
    lib.tdn_model_parse.argtypes = [c.c_char_p, c.c_long, c.c_char_p, c.c_int]
    lib.tdn_model_unsupported.restype = c.c_int
    lib.tdn_model_unsupported.argtypes = [c.c_void_p]
    lib.tdn_model_num_layers.restype = c.c_int
    lib.tdn_model_num_layers.argtypes = [c.c_void_p]
    lib.tdn_model_layers_span.restype = c.c_int
    lib.tdn_model_layers_span.argtypes = [
        c.c_void_p, c.POINTER(c.c_long), c.POINTER(c.c_long)]
    lib.tdn_model_layer_dims.restype = c.c_int
    lib.tdn_model_layer_dims.argtypes = [
        c.c_void_p, c.c_int, c.POINTER(c.c_long), c.POINTER(c.c_long)]
    lib.tdn_model_layer_activation.restype = c.c_char_p
    lib.tdn_model_layer_activation.argtypes = [c.c_void_p, c.c_int]
    lib.tdn_model_layer_type.restype = c.c_char_p
    lib.tdn_model_layer_type.argtypes = [c.c_void_p, c.c_int]
    lib.tdn_model_layer_fill.restype = c.c_int
    lib.tdn_model_layer_fill.argtypes = [
        c.c_void_p, c.c_int, c.POINTER(c.c_double), c.POINTER(c.c_double)]
    lib.tdn_model_free.restype = None
    lib.tdn_model_free.argtypes = [c.c_void_p]

    lib.tdn_parse_examples.restype = c.c_int
    lib.tdn_parse_examples.argtypes = [
        c.c_char_p, c.c_long,
        c.POINTER(c.POINTER(c.c_double)), c.POINTER(c.c_long),
        c.POINTER(c.c_long), c.POINTER(c.POINTER(c.c_int32)),
        c.c_char_p, c.c_int]
    lib.tdn_write_examples.restype = c.c_long
    lib.tdn_write_examples.argtypes = [
        c.POINTER(c.c_double), c.POINTER(c.c_int32), c.c_long, c.c_long,
        c.POINTER(c.c_char_p)]
    lib.tdn_buffer_free.restype = None
    lib.tdn_buffer_free.argtypes = [c.c_void_p]

    lib.tdn_gather_rows.restype = c.c_int
    lib.tdn_gather_rows.argtypes = [
        c.c_void_p, c.c_long, c.c_long,
        c.POINTER(c.c_long), c.c_long, c.c_void_p, c.c_int]
    lib.tdn_gather_norm_u8.restype = c.c_int
    lib.tdn_gather_norm_u8.argtypes = [
        c.POINTER(c.c_uint8), c.c_long, c.c_long,
        c.POINTER(c.c_long), c.c_long, c.POINTER(c.c_float), c.c_float,
        c.c_int]
    return lib


def get_library() -> ctypes.CDLL | None:
    """The loaded native library, or None when unavailable/disabled."""
    global _lib, _attempted
    mode = os.environ.get("TDN_NATIVE", "1").lower()
    if mode in ("0", "off", "false"):
        return None
    with _lock:
        if _lib is not None:
            return _lib
        if _attempted and mode != "require":
            return None
        _attempted = True
        # Imported here: the obs package is not worth loading for a
        # process that only ever parses a model file.
        from tpu_dist_nn.obs.log import get_logger

        slog = get_logger(__name__)
        try:
            missing = [s for s in _SRCS if not s.exists()]
            stale = not _LIB.exists() or any(
                s.stat().st_mtime > _LIB.stat().st_mtime for s in _SRCS
                if s.exists()
            )
            if stale:
                if missing:
                    raise NativeBuildError(
                        f"native source missing: {missing[0]}"
                    )
                _build()
            _lib = _bind(ctypes.CDLL(str(_LIB)))
            slog.info("native.codec", loaded=True, built=stale)
            return _lib
        except Exception as e:
            if mode == "require":
                raise
            # Said once per process, so a missing compiler is seen.
            slog.warning("native.codec", loaded=False,
                         error=f"{type(e).__name__}: {e}"[:300])
            return None
