"""Native (C++) host runtime pieces, loaded via ctypes.

The shared library is built on demand with g++ (see `_build`). Everything
here degrades gracefully: if no compiler is available the Python
implementations in `ytpu.encoding` / `ytpu.core` are used instead —
`available()` reports which path is active.
"""

from __future__ import annotations

import contextlib
import ctypes
import fcntl
import glob
import hashlib
import os
import subprocess
import threading
import time
from typing import Optional

__all__ = [
    "load",
    "available",
    "startup",
    "NativeColumns",
    "decode_update_columns",
    "build_capi",
    "NativeEngine",
    "NativeUnsupported",
    "engine_available",
    "native_replay_v1",
]

_HERE = os.path.dirname(os.path.abspath(__file__))
_SOURCES = tuple(
    os.path.join(_HERE, name)
    for name in ("lib0_codec.cpp", "engine.cpp", "encode_finisher.cpp")
)
_GXX = ("g++", "-O2", "-shared", "-fPIC", "-pthread", "-std=c++17")
_BUILD_LOCK = os.path.join(_HERE, ".build.lock")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False

#: what the process's one `load()` cost: whether g++ ran, its seconds (the
#: wait on another process's build among them) and the `dlopen`'s. Written
#: once, by the load that succeeded: it happens before any recorder can be
#: on, and is part of a server's start all the same.
startup = {"built": False, "build_s": 0.0, "load_s": 0.0}

_COLUMNS = [
    "client",
    "clock",
    "length",
    "kind",
    "origin_client",
    "origin_clock",
    "ror_client",
    "ror_clock",
    "parent_kind",
    "parent_name_start",
    "parent_name_len",
    "parent_id_client",
    "parent_id_clock",
    "parent_sub_start",
    "parent_sub_len",
    "content_start",
    "content_len_bytes",
]
_DEL_COLUMNS = ["del_client", "del_start", "del_end"]


def _lib_path() -> str:
    """`_libytpu-<hash>.so`, named by the sources on disk and the compiler
    argv: a library under this name was built from exactly these files."""
    h = hashlib.sha256()
    for src in _SOURCES:
        with open(src, "rb") as f:
            h.update(f.read())
    h.update("\0".join(_GXX).encode())
    return os.path.join(_HERE, f"_libytpu-{h.hexdigest()[:12]}.so")


@contextlib.contextmanager
def _build_lock():
    """One builder per directory at a time, across processes."""
    with open(_BUILD_LOCK, "w") as f:
        fcntl.flock(f, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(f, fcntl.LOCK_UN)


def _compile(argv, out: str, timeout: int) -> bool:
    """Run `argv -o <unique name beside out>` and rename the result into
    place, so `out` is either absent or a whole library. Caller holds
    `_build_lock`."""
    tmp = f"{out[:-3]}.{os.getpid()}.tmp.so"
    try:
        subprocess.run(
            [*argv, "-o", tmp], check=True, capture_output=True, timeout=timeout
        )
        os.replace(tmp, out)
        return True
    except Exception:
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        return False


def _build(path: str) -> bool:
    """Make sure `path` (from `_lib_path`) exists; drop libraries of older
    sources. A process that waited on the lock finds the winner's file."""
    try:
        with _build_lock():
            if os.path.exists(path):
                return True
            if not _compile([*_GXX, *_SOURCES], path, timeout=120):
                return False
            for old in glob.glob(os.path.join(_HERE, "_libytpu*.so")):
                if old != path and not old.endswith(".tmp.so"):
                    with contextlib.suppress(OSError):
                        os.unlink(old)
            return True
    except OSError:
        return False


_CAPI_SRC = os.path.join(_HERE, "capi.cpp")
_CAPI_LIB = os.path.join(_HERE, "libytpu_capi.so")


def build_capi(force: bool = False) -> Optional[str]:
    """Build the yffi-parity C ABI library (`libytpu_capi.so`).

    Embeds CPython: links against the running interpreter's libpython so
    arbitrary C programs can drive the engine (see include/ytpu.h).
    Returns the library path, or None if the toolchain is unavailable.
    The name is fixed (C programs link against it); the file is built
    under the same lock and renamed into place like `_libytpu-*.so`.
    """
    import sysconfig

    header = os.path.join(_HERE, "include", "ytpu.h")
    support = os.path.join(_HERE, "support.py")
    inputs = [p for p in (_CAPI_SRC, header, support) if os.path.exists(p)]
    include = sysconfig.get_paths()["include"]
    libdir = sysconfig.get_config_var("LIBDIR") or "/usr/local/lib"
    version = sysconfig.get_config_var("LDVERSION") or sysconfig.get_config_var(
        "VERSION"
    )
    argv = [
        "g++",
        "-O2",
        "-shared",
        "-fPIC",
        "-std=c++17",
        _CAPI_SRC,
        f"-I{include}",
        f"-L{libdir}",
        f"-lpython{version}",
        f"-Wl,-rpath,{libdir}",
    ]
    try:
        with _build_lock():
            if (
                not force
                and os.path.exists(_CAPI_LIB)
                and os.path.getmtime(_CAPI_LIB)
                >= max(os.path.getmtime(p) for p in inputs)
            ):
                return _CAPI_LIB
            return _CAPI_LIB if _compile(argv, _CAPI_LIB, timeout=180) else None
    except OSError:
        return None


class FinishIn(ctypes.Structure):
    """Mirror of `FinishIn` in encode_finisher.cpp (field order must match)."""

    _fields_ = [
        ("n_docs_total", ctypes.c_int32),
        ("n_blocks_cap", ctypes.c_int32),
        ("client", ctypes.POINTER(ctypes.c_int32)),
        ("clock", ctypes.POINTER(ctypes.c_int32)),
        ("length", ctypes.POINTER(ctypes.c_int32)),
        ("origin_client", ctypes.POINTER(ctypes.c_int32)),
        ("origin_clock", ctypes.POINTER(ctypes.c_int32)),
        ("ror_client", ctypes.POINTER(ctypes.c_int32)),
        ("ror_clock", ctypes.POINTER(ctypes.c_int32)),
        ("kind", ctypes.POINTER(ctypes.c_int32)),
        ("content_ref", ctypes.POINTER(ctypes.c_int32)),
        ("content_off", ctypes.POINTER(ctypes.c_int32)),
        ("key", ctypes.POINTER(ctypes.c_int32)),
        ("parent", ctypes.POINTER(ctypes.c_int32)),
        ("ship", ctypes.POINTER(ctypes.c_uint8)),
        ("offsets", ctypes.POINTER(ctypes.c_int32)),
        ("deleted", ctypes.POINTER(ctypes.c_uint8)),
        ("sel", ctypes.POINTER(ctypes.c_int32)),
        ("n_sel", ctypes.c_int32),
        ("from_idx", ctypes.POINTER(ctypes.c_int64)),
        ("n_interned", ctypes.c_int32),
        ("key_blob", ctypes.POINTER(ctypes.c_uint8)),
        ("key_off", ctypes.POINTER(ctypes.c_int64)),
        ("n_keys", ctypes.c_int32),
        ("root_name", ctypes.POINTER(ctypes.c_uint8)),
        ("root_name_len", ctypes.c_int32),
        ("text_arena", ctypes.POINTER(ctypes.c_uint8)),
        ("text_arena_len", ctypes.c_int64),
        ("item_text_off", ctypes.POINTER(ctypes.c_int64)),
        ("item_text_units", ctypes.POINTER(ctypes.c_int64)),
        ("blob_arena", ctypes.POINTER(ctypes.c_uint8)),
        ("blob_arena_len", ctypes.c_int64),
        ("item_blob_off", ctypes.POINTER(ctypes.c_int64)),
        ("item_blob_len", ctypes.POINTER(ctypes.c_int64)),
        ("item_elem_base", ctypes.POINTER(ctypes.c_int64)),
        ("item_elem_count", ctypes.POINTER(ctypes.c_int64)),
        ("elem_off", ctypes.POINTER(ctypes.c_int64)),
        ("elem_arena", ctypes.POINTER(ctypes.c_uint8)),
        ("elem_arena_len", ctypes.c_int64),
        ("n_items", ctypes.c_int64),
        ("wire", ctypes.POINTER(ctypes.c_uint8)),
        ("wire_len", ctypes.c_int64),
    ]


def load() -> Optional[ctypes.CDLL]:
    """The library built from the sources on disk, or None where it cannot
    be built. Only a failed compile is remembered; a process that waited
    for another's build loads the file that build left."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        path = _lib_path()
        t0 = time.perf_counter()
        built = not os.path.exists(path)
        if built and not _build(path):
            _tried = True
            return None
        t1 = time.perf_counter()
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            return None
        startup.update(
            built=built,
            build_s=t1 - t0 if built else 0.0,
            load_s=time.perf_counter() - t1,
        )
        lib.ytpu_decode_update_v1.restype = ctypes.c_void_p
        lib.ytpu_decode_update_v1.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        lib.ytpu_columns_error.restype = ctypes.c_int
        lib.ytpu_columns_error.argtypes = [ctypes.c_void_p]
        lib.ytpu_columns_n_blocks.restype = ctypes.c_size_t
        lib.ytpu_columns_n_blocks.argtypes = [ctypes.c_void_p]
        lib.ytpu_columns_n_dels.restype = ctypes.c_size_t
        lib.ytpu_columns_n_dels.argtypes = [ctypes.c_void_p]
        lib.ytpu_columns_n_client_sections.restype = ctypes.c_size_t
        lib.ytpu_columns_n_client_sections.argtypes = [ctypes.c_void_p]
        lib.ytpu_columns_n_ds_sections.restype = ctypes.c_size_t
        lib.ytpu_columns_n_ds_sections.argtypes = [ctypes.c_void_p]
        lib.ytpu_columns_n_zero_len_blocks.restype = ctypes.c_size_t
        lib.ytpu_columns_n_zero_len_blocks.argtypes = [ctypes.c_void_p]
        lib.ytpu_columns_n_value_steps.restype = ctypes.c_size_t
        lib.ytpu_columns_n_value_steps.argtypes = [ctypes.c_void_p]
        lib.ytpu_columns_n_complex_any.restype = ctypes.c_size_t
        lib.ytpu_columns_n_complex_any.argtypes = [ctypes.c_void_p]
        lib.ytpu_columns_free.argtypes = [ctypes.c_void_p]
        for name in _COLUMNS + _DEL_COLUMNS:
            fn = getattr(lib, f"ytpu_col_{name}")
            fn.restype = ctypes.POINTER(ctypes.c_int64)
            fn.argtypes = [ctypes.c_void_p]
        lib.ytpu_decode_var_uints.restype = ctypes.c_size_t
        lib.ytpu_decode_var_uints.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_size_t,
        ]
        lib.ytpu_engine_new.restype = ctypes.c_void_p
        lib.ytpu_engine_free.argtypes = [ctypes.c_void_p]
        lib.ytpu_engine_apply.restype = ctypes.c_int
        lib.ytpu_engine_apply.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_size_t,
        ]
        lib.ytpu_engine_text.restype = ctypes.c_void_p  # freed manually
        lib.ytpu_engine_text.argtypes = [ctypes.c_void_p]
        lib.ytpu_engine_text_root.restype = ctypes.c_void_p
        lib.ytpu_engine_text_root.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.ytpu_engine_root_json.restype = ctypes.c_void_p
        lib.ytpu_engine_root_json.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_int,
        ]
        lib.ytpu_engine_encode_diff.restype = ctypes.c_void_p
        lib.ytpu_engine_encode_diff.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.ytpu_engine_str_free.argtypes = [ctypes.c_void_p]
        lib.ytpu_engine_n_items.restype = ctypes.c_size_t
        lib.ytpu_engine_n_items.argtypes = [ctypes.c_void_p]
        # the finisher passes a 40+ field struct by pointer; refuse to bind
        # unless the C++ and ctypes layouts agree byte-for-byte (a field
        # added/reordered on one side would otherwise corrupt memory)
        lib.ytpu_finish_in_sizeof.restype = ctypes.c_int64
        lib.finisher_ok = (
            int(lib.ytpu_finish_in_sizeof()) == ctypes.sizeof(FinishIn)
        )
        if lib.finisher_ok:
            lib.ytpu_finish_batch.restype = ctypes.c_void_p
            lib.ytpu_finish_batch.argtypes = [ctypes.POINTER(FinishIn)]
            lib.ytpu_finish_batch_mt.restype = ctypes.c_void_p
            lib.ytpu_finish_batch_mt.argtypes = [
                ctypes.POINTER(FinishIn),
                ctypes.c_int32,
            ]
            lib.ytpu_finish_status.restype = ctypes.c_int32
            lib.ytpu_finish_status.argtypes = [ctypes.c_void_p, ctypes.c_int32]
            lib.ytpu_finish_data.restype = ctypes.POINTER(ctypes.c_uint8)
            lib.ytpu_finish_data.argtypes = [ctypes.c_void_p]
            lib.ytpu_finish_span.argtypes = [
                ctypes.c_void_p,
                ctypes.c_int32,
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64),
            ]
            lib.ytpu_finish_free.argtypes = [ctypes.c_void_p]
            # the strided packed-arena entry (one host tensor, zero
            # per-plane copies) and the vectorized span/status readout
            lib.ytpu_finish_batch_strided.restype = ctypes.c_void_p
            lib.ytpu_finish_batch_strided.argtypes = [
                ctypes.POINTER(FinishIn),
                ctypes.c_int64,
                ctypes.c_int32,
            ]
            lib.ytpu_finish_total_len.restype = ctypes.c_int64
            lib.ytpu_finish_total_len.argtypes = [ctypes.c_void_p]
            lib.ytpu_finish_spans.argtypes = [
                ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int32),
            ]
        _lib = lib
        return _lib


def available() -> bool:
    return load() is not None


class NativeColumns:
    """Columnar view over one decoded update (owns the native handle)."""

    def __init__(self, lib: ctypes.CDLL, handle: int, payload: bytes):
        self._lib = lib
        self._handle = handle
        self.payload = payload  # original wire bytes; spans index into this
        self.error = bool(lib.ytpu_columns_error(handle))
        self.n_blocks = int(lib.ytpu_columns_n_blocks(handle))
        self.n_dels = int(lib.ytpu_columns_n_dels(handle))
        self.n_client_sections = int(lib.ytpu_columns_n_client_sections(handle))
        self.n_ds_sections = int(lib.ytpu_columns_n_ds_sections(handle))
        self.n_zero_len_blocks = int(lib.ytpu_columns_n_zero_len_blocks(handle))
        self.n_value_steps = int(lib.ytpu_columns_n_value_steps(handle))
        self.n_complex_any = int(lib.ytpu_columns_n_complex_any(handle))
        import numpy as np

        def grab(name: str, count: int):
            if count == 0:
                return np.empty(0, dtype=np.int64)
            ptr = getattr(lib, f"ytpu_col_{name}")(handle)
            return np.ctypeslib.as_array(ptr, shape=(count,)).copy()

        for name in _COLUMNS:
            setattr(self, name, grab(name, self.n_blocks))
        for name in _DEL_COLUMNS:
            setattr(self, name, grab(name, self.n_dels))
        lib.ytpu_columns_free(handle)
        self._handle = None

    def span(self, start: int, length: int) -> bytes:
        return self.payload[start : start + length]

    def parent_name(self, i: int) -> str:
        s, n = int(self.parent_name_start[i]), int(self.parent_name_len[i])
        return self.span(s, n).decode("utf-8")

    def parent_sub(self, i: int):
        s, n = int(self.parent_sub_start[i]), int(self.parent_sub_len[i])
        if s < 0:
            return None
        return self.span(s, n).decode("utf-8")

    def content_bytes(self, i: int) -> bytes:
        return self.span(int(self.content_start[i]), int(self.content_len_bytes[i]))


def decode_update_columns(payload: bytes) -> Optional[NativeColumns]:
    """Decode a v1 update into block columns via the native codec.

    Returns None if the native library is unavailable.
    """
    lib = load()
    if lib is None:
        return None
    handle = lib.ytpu_decode_update_v1(payload, len(payload))
    return NativeColumns(lib, handle, payload)


class NativeUnsupported(RuntimeError):
    """The C++ engine hit a feature outside its scope (GC ranges, move
    ranges, sub-documents) — use the host oracle."""


class NativeEngine:
    """Scalar single-doc YATA engine in C++ (`engine.cpp`).

    The native-speed performance baseline: reference-equivalent integrate
    / apply_delete semantics for text, array, map and nested-XML update
    streams (String / Deleted / Any / JSON / Binary / Embed / Format /
    Type content, root-name and branch-id parents, map key chains with
    last-write-wins shadowing). Raises `NativeUnsupported` for
    out-of-scope features (GC ranges, moves, subdocs).
    """

    def __init__(self):
        lib = load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._handle = lib.ytpu_engine_new()

    def apply_update_v1(self, payload: bytes) -> None:
        rc = self._lib.ytpu_engine_apply(self._handle, payload, len(payload))
        if rc == 2:
            raise NativeUnsupported("update outside native engine scope")
        if rc != 0:
            raise RuntimeError(f"native engine apply failed (rc={rc})")

    def text(self) -> str:
        ptr = self._lib.ytpu_engine_text(self._handle)
        if not ptr:
            raise MemoryError("ytpu_engine_text")
        try:
            return ctypes.string_at(ptr).decode("utf-8")
        finally:
            self._lib.ytpu_engine_str_free(ptr)

    def text_root(self, name: str) -> str:
        ptr = self._lib.ytpu_engine_text_root(self._handle, name.encode())
        if not ptr:
            raise MemoryError("ytpu_engine_text_root")
        try:
            return ctypes.string_at(ptr).decode("utf-8")
        finally:
            self._lib.ytpu_engine_str_free(ptr)

    def encode_diff_v1(self, sv: dict) -> bytes:
        """V1 update bytes for the diff vs a remote state vector (mapping
        client-id -> clock). Semantics parity with the host's
        `encode_state_as_update_v1` (reference store.rs:204-248); block
        granularity may differ (the engine splits but never squashes), so
        validate by applying to a fresh doc, not by byte compare. Raises
        `NativeUnsupported` when the state cannot be re-encoded natively."""
        n = len(sv)
        clients = (ctypes.c_uint64 * n)(*sv.keys())
        clocks = (ctypes.c_uint64 * n)(*sv.values())
        out_len = ctypes.c_size_t(0)
        ptr = self._lib.ytpu_engine_encode_diff(
            self._handle, clients, clocks, n, ctypes.byref(out_len)
        )
        if not ptr:
            raise NativeUnsupported("state has no native diff encoding")
        try:
            return ctypes.string_at(ptr, out_len.value)
        finally:
            self._lib.ytpu_engine_str_free(ptr)

    def root_json(self, name: str, shape: str = "seq"):
        """Parsed visible state of a named root ("seq" = array / xml
        children order, "map" = key/value object). Raises
        `NativeUnsupported` when the root holds content with no native
        JSON projection (binary, subdocs, hooks)."""
        import json as _json

        shapes = {"seq": 0, "map": 1}
        ptr = self._lib.ytpu_engine_root_json(
            self._handle, name.encode(), shapes[shape]
        )
        if not ptr:
            raise NativeUnsupported(f"no native JSON projection for {name!r}")
        try:
            return _json.loads(ctypes.string_at(ptr).decode("utf-8"))
        finally:
            self._lib.ytpu_engine_str_free(ptr)

    @property
    def n_items(self) -> int:
        return int(self._lib.ytpu_engine_n_items(self._handle))

    def close(self) -> None:
        if self._handle:
            self._lib.ytpu_engine_free(self._handle)
            self._handle = None

    def __del__(self):  # pragma: no cover - best effort
        try:
            self.close()
        except Exception:
            pass


def engine_available() -> bool:
    return available()


def native_replay_v1(payloads) -> str:
    """Replay a V1 update stream through the C++ engine; returns the final
    root text. Raises `NativeUnsupported` when the stream needs features
    beyond the engine's scope (caller falls back to the host oracle)."""
    eng = NativeEngine()
    try:
        for p in payloads:
            eng.apply_update_v1(p)
        return eng.text()
    finally:
        eng.close()
