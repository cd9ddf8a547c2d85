"""ctypes bindings for the C++ native core, built on demand with g++.

No pybind11 in this image (see repo docs) — the C ABI in native/src/parse.cc
is loaded with ctypes and arrays are wrapped as numpy views that own the
malloc'd buffers via a finalizer (zero copies on the handoff).

Falls back cleanly: ``available()`` is False when the toolchain or build is
missing, and the Python parsers keep working.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
import weakref
from typing import Optional

import numpy as np

from dmlc_tpu.utils.check import DMLCError, get_logger


class NeedsCsrError(DMLCError):
    """Input the dense scanner can't express (e.g. qid rows) — explicit
    signal (DenseResult.needs_csr) for callers to fall back to CSR, so no
    routing ever depends on error-message wording."""

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC_DIR = os.path.join(_REPO_ROOT, "native", "src")
# keep in sync with Makefile NATIVE_SRCS, native/CMakeLists.txt, and
# native/run_sanitizers.sh SRCS
_SRCS = [os.path.join(_SRC_DIR, f)
         for f in ("parse.cc", "reader.cc", "recordio.cc")]
_HDRS = [os.path.join(_SRC_DIR, f)
         for f in ("api.h", "strtonum.h", "parse_internal.h",
                   "buffer_pool.h")]
_BUILD_DIR = os.path.join(_REPO_ROOT, "native", "build")
_SO_PATH = os.path.join(_BUILD_DIR, "libdmlc_tpu_native.so")
# the source hash the .so was built from, stored beside it: staleness is
# decided by content, never by mtime (a copied or checked-out tree keeps
# no useful mtimes, and a stale .so can be the newest file in it)
_HASH_PATH = _SO_PATH + ".srchash"
_ABI_VERSION = 17

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


class _CsrBlockResult(ctypes.Structure):
    _fields_ = [
        ("n_rows", ctypes.c_int64),
        ("nnz", ctypes.c_int64),
        ("offset", ctypes.POINTER(ctypes.c_int64)),
        ("label", ctypes.POINTER(ctypes.c_float)),
        ("weight", ctypes.POINTER(ctypes.c_float)),
        ("qid", ctypes.POINTER(ctypes.c_int64)),
        ("index", ctypes.POINTER(ctypes.c_uint64)),
        ("field", ctypes.POINTER(ctypes.c_uint64)),
        ("value", ctypes.POINTER(ctypes.c_float)),
        ("error", ctypes.c_char_p),
    ]


class _DenseResult(ctypes.Structure):
    _fields_ = [
        ("n_rows", ctypes.c_int64),
        ("n_cols", ctypes.c_int64),
        ("x", ctypes.POINTER(ctypes.c_float)),
        ("label", ctypes.POINTER(ctypes.c_float)),
        ("weight", ctypes.POINTER(ctypes.c_float)),
        ("error", ctypes.c_char_p),
        ("needs_csr", ctypes.c_int32),
        ("x_bf16", ctypes.c_int32),
        ("packed_aux", ctypes.c_int32),
    ]


class _CsvResult(ctypes.Structure):
    _fields_ = [
        ("n_rows", ctypes.c_int64),
        ("n_cols", ctypes.c_int64),
        ("cells", ctypes.POINTER(ctypes.c_float)),
        ("error", ctypes.c_char_p),
    ]


class _CsvIntResult(ctypes.Structure):
    _fields_ = [
        ("n_rows", ctypes.c_int64),
        ("n_cols", ctypes.c_int64),
        ("cells", ctypes.c_void_p),
        ("bits", ctypes.c_int32),
        ("error", ctypes.c_char_p),
    ]


class _CsvHashedResult(ctypes.Structure):
    _fields_ = [
        ("n_rows", ctypes.c_int64),
        ("n_cols", ctypes.c_int64),
        ("cells", ctypes.c_void_p),
        ("bits", ctypes.c_int32),
        ("empty_cells", ctypes.c_int64),
        ("error", ctypes.c_char_p),
    ]


class _CsvSplitResult(ctypes.Structure):
    _fields_ = [
        ("n_rows", ctypes.c_int64),
        ("n_feat_cols", ctypes.c_int64),
        ("values", ctypes.POINTER(ctypes.c_float)),
        ("label", ctypes.POINTER(ctypes.c_float)),
        ("weight", ctypes.POINTER(ctypes.c_float)),
        ("error", ctypes.c_char_p),
    ]


class _CooResult(ctypes.Structure):
    _fields_ = [
        ("n_rows", ctypes.c_int64),
        ("nnz", ctypes.c_int64),
        ("rows_padded", ctypes.c_int64),
        ("nnz_padded", ctypes.c_int64),
        ("coords", ctypes.POINTER(ctypes.c_int32)),
        ("values", ctypes.POINTER(ctypes.c_float)),
        ("label", ctypes.POINTER(ctypes.c_float)),
        ("weight", ctypes.POINTER(ctypes.c_float)),
        ("error", ctypes.c_char_p),
        ("values_elided", ctypes.c_int32),
        ("csr_wire", ctypes.c_int32),
        ("row_ptr", ctypes.POINTER(ctypes.c_int32)),
    ]


class _RecordBatchResult(ctypes.Structure):
    _fields_ = [
        ("n_records", ctypes.c_int64),
        ("data_len", ctypes.c_int64),
        ("data", ctypes.POINTER(ctypes.c_char)),
        ("offsets", ctypes.POINTER(ctypes.c_int64)),
        ("error", ctypes.c_char_p),
    ]


def _compile_flags() -> list:
    # no -march=native: the artifact may outlive the build host (shared FS,
    # copied checkouts) and ISA-specific code would SIGILL with no fallback
    flags = ["-O3", "-std=c++17", "-fPIC", "-shared", "-pthread",
             "-D_FILE_OFFSET_BITS=64"]
    san = os.environ.get("DMLC_TPU_SANITIZE", "")
    if san:
        # ASan/TSan toggle, mirroring the reference's DMLC_USE_SANITIZER
        # CMake option (cmake/Sanitizer.cmake)
        flags += [f"-fsanitize={san}", "-g", "-fno-omit-frame-pointer"]
    return flags


def _source_hash() -> str:
    """sha256 over the compile flags and every source and header, in the
    fixed ``_SRCS + _HDRS`` order (names included, so a rename counts)."""
    h = hashlib.sha256(" ".join(_compile_flags()).encode())
    for path in _SRCS + _HDRS:
        h.update(os.path.basename(path).encode())
        try:
            with open(path, "rb") as f:
                h.update(f.read())
        except OSError:
            h.update(b"<missing>")
    return h.hexdigest()


def _recorded_hash() -> Optional[str]:
    try:
        with open(_HASH_PATH) as f:
            return f.read().strip()
    except OSError:
        return None


def _build() -> bool:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    src_hash = _source_hash()
    # build under a process-unique name and publish by rename: concurrent
    # first imports (launcher workers on one host) never load a torn .so
    tmp = f"{_SO_PATH}.{os.getpid()}.tmp"
    cmd = ["g++"] + _compile_flags() + ["-o", tmp] + _SRCS
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as exc:
        get_logger().warning("native build failed to run: %s", exc)
        return False
    if proc.returncode != 0:
        get_logger().warning("native build failed:\n%s", proc.stderr[-2000:])
        return False
    os.replace(tmp, _SO_PATH)
    with open(tmp, "w") as f:
        f.write(src_hash + "\n")
    os.replace(tmp, _HASH_PATH)
    return True


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        if os.environ.get("DMLC_TPU_NO_NATIVE", "0") not in ("", "0"):
            _build_failed = True
            return None
        need_build = (not os.path.exists(_SO_PATH)
                      or _recorded_hash() != _source_hash())
        if need_build and not _build():
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError as exc:
            # stale/corrupt artifact: rebuild once before giving up
            get_logger().warning("native load failed (%s); rebuilding", exc)
            try:
                os.unlink(_SO_PATH)
            except OSError:
                pass
            if not _build():
                _build_failed = True
                return None
            try:
                lib = ctypes.CDLL(_SO_PATH)
            except OSError as exc2:
                get_logger().warning("native load failed after rebuild: %s", exc2)
                _build_failed = True
                return None
        # version-check BEFORE declaring the full symbol table: a .so whose
        # hash file was copied from another build would otherwise raise
        # AttributeError on symbols this ABI added, bypassing the rebuild
        if not _abi_ok(lib):
            get_logger().warning("native ABI mismatch; rebuilding")
            try:
                os.unlink(_SO_PATH)
                if not _build():
                    _build_failed = True
                    return None
                lib = ctypes.CDLL(_SO_PATH)
                if not _abi_ok(lib):
                    get_logger().warning("native ABI still mismatched after rebuild")
                    _build_failed = True
                    return None
            except OSError as exc:
                get_logger().warning("native ABI rebuild failed: %s", exc)
                _build_failed = True
                return None
        _declare(lib)
        _lib = lib
        return _lib


def _abi_ok(lib: ctypes.CDLL) -> bool:
    """True when the .so exports the expected ABI version. Tolerates
    binaries so old they predate the version symbol."""
    try:
        fn = lib.dmlc_native_abi_version
    except AttributeError:
        return False
    fn.restype = ctypes.c_int
    return fn() == _ABI_VERSION


def _declare(lib: ctypes.CDLL) -> None:
    lib.dmlc_parse_libsvm.restype = ctypes.POINTER(_CsrBlockResult)
    lib.dmlc_parse_libsvm.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, ctypes.c_int]
    lib.dmlc_parse_libfm.restype = ctypes.POINTER(_CsrBlockResult)
    lib.dmlc_parse_libfm.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, ctypes.c_int]
    lib.dmlc_parse_csv.restype = ctypes.POINTER(_CsvResult)
    lib.dmlc_parse_csv.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, ctypes.c_char]
    lib.dmlc_parse_libsvm_dense.restype = ctypes.POINTER(_DenseResult)
    lib.dmlc_parse_libsvm_dense.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, ctypes.c_int64,
        ctypes.c_int]
    lib.dmlc_free_dense.argtypes = [ctypes.c_void_p]
    # void* so finalizers never depend on ctypes class identity (which
    # changes across importlib.reload) — they may fire at interpreter exit
    lib.dmlc_free_block.argtypes = [ctypes.c_void_p]
    lib.dmlc_free_csv.argtypes = [ctypes.c_void_p]
    lib.dmlc_parse_csv_int.restype = ctypes.POINTER(_CsvIntResult)
    lib.dmlc_parse_csv_int.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, ctypes.c_char,
        ctypes.c_int32]
    lib.dmlc_free_csv_int.argtypes = [ctypes.c_void_p]
    lib.dmlc_parse_csv_hashed.restype = ctypes.POINTER(_CsvHashedResult)
    lib.dmlc_parse_csv_hashed.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, ctypes.c_char,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int64]
    lib.dmlc_free_csv_hashed.argtypes = [ctypes.c_void_p]
    lib.dmlc_parse_csv_split.restype = ctypes.POINTER(_CsvSplitResult)
    lib.dmlc_parse_csv_split.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, ctypes.c_char,
        ctypes.c_int32, ctypes.c_int32]
    lib.dmlc_free_csv_split.argtypes = [ctypes.c_void_p]
    lib.dmlc_native_abi_version.restype = ctypes.c_int
    lib.dmlc_recordio_extract.restype = ctypes.POINTER(_RecordBatchResult)
    lib.dmlc_recordio_extract.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.dmlc_free_records.argtypes = [ctypes.c_void_p]
    lib.dmlc_parse_coo.restype = ctypes.POINTER(_CooResult)
    lib.dmlc_parse_coo.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int32, ctypes.c_int32]
    lib.dmlc_free_coo.argtypes = [ctypes.c_void_p]
    lib.dmlc_reader_create.restype = ctypes.c_void_p
    lib.dmlc_reader_create.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int32, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
        ctypes.c_int64, ctypes.c_int32, ctypes.c_char, ctypes.c_int32,
        ctypes.c_int64, ctypes.c_int32, ctypes.c_int64, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32]
    lib.dmlc_reader_next.restype = ctypes.c_void_p
    lib.dmlc_reader_next.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32)]
    lib.dmlc_reader_before_first.argtypes = [ctypes.c_void_p]
    lib.dmlc_reader_bytes_read.restype = ctypes.c_int64
    lib.dmlc_reader_bytes_read.argtypes = [ctypes.c_void_p]
    lib.dmlc_reader_error.restype = ctypes.c_char_p
    lib.dmlc_reader_error.argtypes = [ctypes.c_void_p]
    lib.dmlc_reader_destroy.argtypes = [ctypes.c_void_p]
    lib.dmlc_feeder_create.restype = ctypes.c_void_p
    lib.dmlc_feeder_create.argtypes = [
        ctypes.c_int32, ctypes.c_int64, ctypes.c_int32, ctypes.c_char,
        ctypes.c_int32, ctypes.c_int64, ctypes.c_int32, ctypes.c_int64,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32]
    lib.dmlc_feeder_push.restype = ctypes.c_int32
    lib.dmlc_feeder_push.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
    lib.dmlc_feeder_finish.argtypes = [ctypes.c_void_p]
    lib.dmlc_feeder_abort.argtypes = [ctypes.c_void_p]
    lib.dmlc_feeder_fail.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.dmlc_feeder_next.restype = ctypes.c_void_p
    lib.dmlc_feeder_next.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32)]
    lib.dmlc_feeder_before_first.argtypes = [ctypes.c_void_p]
    lib.dmlc_feeder_bytes_read.restype = ctypes.c_int64
    lib.dmlc_feeder_bytes_read.argtypes = [ctypes.c_void_p]
    lib.dmlc_feeder_error.restype = ctypes.c_char_p
    lib.dmlc_feeder_error.argtypes = [ctypes.c_void_p]
    lib.dmlc_feeder_destroy.argtypes = [ctypes.c_void_p]
    lib.dmlc_indexed_reader_create.restype = ctypes.c_void_p
    lib.dmlc_indexed_reader_create.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int32, ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
        ctypes.c_uint64, ctypes.c_int32]
    lib.dmlc_indexed_reader_next.restype = ctypes.c_void_p
    lib.dmlc_indexed_reader_next.argtypes = [ctypes.c_void_p]
    lib.dmlc_indexed_reader_before_first.argtypes = [ctypes.c_void_p]
    lib.dmlc_indexed_reader_skip.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64]
    lib.dmlc_indexed_reader_bytes_read.restype = ctypes.c_int64
    lib.dmlc_indexed_reader_bytes_read.argtypes = [ctypes.c_void_p]
    lib.dmlc_indexed_reader_error.restype = ctypes.c_char_p
    lib.dmlc_indexed_reader_error.argtypes = [ctypes.c_void_p]
    lib.dmlc_indexed_reader_destroy.argtypes = [ctypes.c_void_p]


def available() -> bool:
    return _load() is not None


def default_nthread() -> int:
    """min(user, cores/2) in the spirit of text_parser.h:33-34."""
    env = os.environ.get("DMLC_TPU_PARSE_THREADS")
    if env:
        return max(1, int(env))
    return max(2, (os.cpu_count() or 2) // 2)


class _HeldBuffer:
    """Array-interface shim binding a raw pointer to its _Owner.

    ``np.asarray`` on this object yields a zero-copy view whose ``base`` IS
    this shim — so the owner (and thus the malloc'd buffer) stays alive for
    as long as ANY derived view exists, including views JAX is still
    transferring from. No consumer bookkeeping required.
    """

    __slots__ = ("owner", "__array_interface__")

    def __init__(self, addr: int, nbytes: int, owner):
        self.owner = owner
        self.__array_interface__ = {
            "data": (addr, False),
            "shape": (nbytes,),
            "typestr": "|u1",
            "version": 3,
        }


def _view(ptr, n, dtype, owner):
    """Zero-copy numpy view over a malloc'd buffer; the view's base chain
    pins ``owner`` so the buffer cannot be freed while any view lives."""
    if not ptr or n == 0:
        return None
    dtype = np.dtype(dtype)
    addr = ctypes.cast(ptr, ctypes.c_void_p).value
    raw = np.asarray(_HeldBuffer(addr, n * dtype.itemsize, owner))
    return raw.view(dtype)


class _Owner:
    """Frees the C result when garbage collected."""

    __slots__ = ("__weakref__",)

    def __init__(self, lib, res, free_fn):
        weakref.finalize(self, free_fn, lib, ctypes.cast(res, ctypes.c_void_p).value)


def _free_block(lib, addr):
    lib.dmlc_free_block(addr)


def _free_csv(lib, addr):
    lib.dmlc_free_csv(addr)


def _free_csv_int(lib, addr):
    lib.dmlc_free_csv_int(addr)


def _free_csv_hashed(lib, addr):
    lib.dmlc_free_csv_hashed(addr)


def _free_csv_split(lib, addr):
    lib.dmlc_free_csv_split(addr)


def _chunk_buf(chunk):
    """``bytes | memoryview`` -> (c_char_p-compatible arg, length, keepalive).

    A memoryview (e.g. an mmap slice from the zero-copy chunk reader)
    passes its buffer ADDRESS straight through — no bytes() copy, no GIL
    held for a memcpy. Safe because every native scanner is strictly
    ``[data, data + len)`` bounded and copies what it keeps (the result
    arrays are its own mallocs). ``keepalive`` must stay referenced until
    the call returns.
    """
    if isinstance(chunk, bytes):
        return chunk, len(chunk), chunk
    if isinstance(chunk, bytearray):
        # c_char_p argtypes reject bytearray: materialize once
        data = bytes(chunk)
        return data, len(data), data
    view = memoryview(chunk)
    if view.nbytes == 0 or not view.c_contiguous:
        data = bytes(view)
        return data, len(data), data
    arr = np.frombuffer(view, np.uint8)
    return ctypes.c_char_p(arr.ctypes.data), arr.nbytes, (view, arr)


def parse_libsvm(chunk, nthread: int = 0, indexing_mode: int = 0):
    """Parse a libsvm chunk (bytes or memoryview) natively; returns dict
    of numpy arrays or None."""
    lib = _load()
    if lib is None:
        return None
    buf, n, keep = _chunk_buf(chunk)
    res = lib.dmlc_parse_libsvm(
        buf, n, nthread or default_nthread(), indexing_mode)
    del keep
    return _wrap_block(lib, res)


def parse_libfm(chunk, nthread: int = 0, indexing_mode: int = 0):
    lib = _load()
    if lib is None:
        return None
    buf, n, keep = _chunk_buf(chunk)
    res = lib.dmlc_parse_libfm(
        buf, n, nthread or default_nthread(), indexing_mode)
    del keep
    return _wrap_block(lib, res)


def _wrap_block(lib, res):
    r = res.contents
    if r.error:
        msg = r.error.decode()
        lib.dmlc_free_block(res)
        raise DMLCError(msg)
    owner = _Owner(lib, res, _free_block)
    n, nnz = r.n_rows, r.nnz
    out = {
        "offset": _view(r.offset, n + 1, np.int64, owner),
        "label": _view(r.label, n, np.float32, owner),
        "weight": _view(r.weight, n, np.float32, owner),
        "qid": _view(r.qid, n, np.int64, owner),
        "index": _view(r.index, nnz, np.uint64, owner),
        "field": _view(r.field, nnz, np.uint64, owner),
        "value": _view(r.value, nnz, np.float32, owner),
        "_owner": owner,
    }
    if n == 0:
        out["offset"] = np.zeros(1, np.int64)
        out["label"] = np.empty(0, np.float32)
    if out["index"] is None:
        out["index"] = np.empty(0, np.uint64)
    return out


def _free_dense(lib, addr):
    lib.dmlc_free_dense(addr)


def parse_libsvm_dense(chunk, num_col: int, nthread: int = 0,
                       indexing_mode: int = -1):
    """Parse libsvm straight to the dense device layout.

    Returns (x [n, num_col] float32, label, weight-or-None, owner) or None
    when native is unavailable. Raises DMLCError for inputs the dense scanner
    does not support (e.g. qid rows) — callers fall back to the CSR path.
    """
    lib = _load()
    if lib is None:
        return None
    buf, n, keep = _chunk_buf(chunk)
    res = lib.dmlc_parse_libsvm_dense(
        buf, n, nthread or default_nthread(), num_col, indexing_mode)
    del keep
    return _wrap_dense(lib, res, num_col)


def _wrap_dense(lib, res, num_col: int):
    r = res.contents
    if r.error:
        msg = r.error.decode()
        needs_csr = bool(r.needs_csr)
        lib.dmlc_free_dense(res)
        raise NeedsCsrError(msg) if needs_csr else DMLCError(msg)
    owner = _Owner(lib, res, _free_dense)
    n = r.n_rows
    x_dtype = bf16_dtype() if r.x_bf16 else np.float32
    if n == 0:
        return (np.zeros((0, num_col), x_dtype),
                np.empty(0, np.float32), None, owner, False)
    if r.packed_aux:
        # packed layout: x is [n, num_col + 2] with label/weight as the
        # trailing columns (ONE device_put per batch downstream); the
        # label/weight views alias those columns for host-side consumers
        xp = _view(r.x, n * (num_col + 2), x_dtype, owner).reshape(
            n, num_col + 2)
        return xp, xp[:, num_col], xp[:, num_col + 1], owner, True
    x = _view(r.x, n * num_col, x_dtype, owner).reshape(n, num_col)
    label = _view(r.label, n, np.float32, owner)
    weight = _view(r.weight, n, np.float32, owner)
    return x, label, weight, owner, False


def bf16_dtype():
    """bfloat16 as a numpy dtype (ml_dtypes ships with jax) — the ONE
    lookup shared by the native view wrapper and the Python fallbacks."""
    import ml_dtypes

    return np.dtype(ml_dtypes.bfloat16)


def parse_csv(chunk, delimiter: str = ",", nthread: int = 0,
              dtype="float32"):
    """Parse a csv chunk (bytes or memoryview) natively -> (cells [n, ncol]
    of ``dtype``, owner) or None. ``dtype`` is float32, int32 or int64
    (csv_parser.h's three instantiations): integer cells are scanned as
    integers, and a cell that is no whole number or does not fit the type
    raises.

    The caller must keep ``owner`` referenced while using ``cells``.
    """
    lib = _load()
    if lib is None:
        return None
    dtype = np.dtype(dtype)
    buf, n, keep = _chunk_buf(chunk)
    delim = delimiter.encode()[0] if delimiter else b","[0]
    nthread = nthread or default_nthread()
    if dtype == np.float32:
        res, free = lib.dmlc_parse_csv(buf, n, nthread, delim), _free_csv
    elif dtype in (np.int32, np.int64):
        res, free = lib.dmlc_parse_csv_int(
            buf, n, nthread, delim, 8 * dtype.itemsize), _free_csv_int
    else:
        raise DMLCError(f"parse_csv: no scanner for dtype {dtype}")
    del keep
    return _wrap_csv(lib, res, dtype, free)


def parse_csv_hashed(chunk, hash_bins: int, delimiter: str = ",",
                     nthread: int = 0, dtype="int32", label_column: int = -1,
                     weight_column: int = -1):
    """:func:`parse_csv` with hashed cells (docs/data.md, "Hashed cells")
    -> (cells [n, ncol] of the integer ``dtype``, owner, empty cells) or
    None: every cell but the label's and the weight's is ``FNV-1a-64(its
    position among such cells as one byte, then its bytes) % hash_bins``;
    the label and weight cells are whole numbers. The last is the count of
    hashed cells that had no bytes."""
    lib = _load()
    if lib is None:
        return None
    dtype = np.dtype(dtype)
    if dtype not in (np.int32, np.int64):
        raise DMLCError(f"parse_csv_hashed: hash_bins gives integer ids, "
                        f"not {dtype}")
    buf, n, keep = _chunk_buf(chunk)
    delim = delimiter.encode()[0] if delimiter else b","[0]
    res = lib.dmlc_parse_csv_hashed(
        buf, n, nthread or default_nthread(), delim, 8 * dtype.itemsize,
        int(label_column), int(weight_column), int(hash_bins))
    del keep
    empty = res.contents.empty_cells
    return _wrap_csv(lib, res, dtype, _free_csv_hashed) + (empty,)


def _wrap_csv(lib, res, dtype=np.dtype(np.float32), free=_free_csv):
    r = res.contents
    if r.error:
        msg = r.error.decode()
        free(lib, res)
        raise DMLCError(msg)
    owner = _Owner(lib, res, free)
    n, c = r.n_rows, r.n_cols
    if n == 0 or c == 0:
        return np.zeros((0, 0), dtype), owner
    cells = _view(r.cells, n * c, dtype, owner)
    return cells.reshape(n, c), owner


def _wrap_csv_split(lib, res):
    """(values[n,k], label|None, weight|None, n_rows, owner) — all views
    zero-copy over the C buffers; the RowBlock skeleton (index/offset) is
    format-implied and supplied by the caller's cache."""
    r = res.contents
    if r.error:
        msg = r.error.decode()
        lib.dmlc_free_csv_split(res)
        raise DMLCError(msg)
    owner = _Owner(lib, res, _free_csv_split)
    n, k = r.n_rows, r.n_feat_cols
    if n == 0:
        return np.zeros((0, 0), np.float32), None, None, 0, owner
    values = (_view(r.values, n * k, np.float32, owner).reshape(n, k)
              if k else np.zeros((n, 0), np.float32))
    label = _view(r.label, n, np.float32, owner)
    weight = _view(r.weight, n, np.float32, owner)
    return values, label, weight, int(n), owner


def _free_records(lib, addr):
    lib.dmlc_free_records(addr)


def recordio_extract(data) -> "tuple[np.ndarray, np.ndarray]":
    """Extract all records from a span of RecordIO bytes (must start at a
    record head and hold only whole records). Returns (payload u8 array,
    offsets int64 [n+1]) — record i is ``payload[offsets[i]:offsets[i+1]]``.
    Zero-copy over the native buffer. None when native is unavailable."""
    lib = _load()
    if lib is None:
        return None
    data = bytes(data) if not isinstance(data, bytes) else data
    res = lib.dmlc_recordio_extract(data, len(data))
    if not res:
        raise DMLCError("recordio: out of memory")
    return _wrap_records(lib, res)


def _wrap_records(lib, res):
    r = res.contents
    if r.error:
        msg = r.error.decode()
        lib.dmlc_free_records(res)
        raise DMLCError(msg)
    owner = _Owner(lib, res, _free_records)
    n = r.n_records
    offsets = _view(r.offsets, n + 1, np.int64, owner)
    payload = _view(r.data, r.data_len, np.uint8, owner)
    if offsets is None:
        offsets = np.zeros(1, np.int64)
    if payload is None:
        payload = np.empty(0, np.uint8)
    return payload, offsets


# ---------------- streaming reader ----------------

FMT_LIBSVM = 0
FMT_LIBSVM_DENSE = 1
FMT_CSV = 2
FMT_LIBFM = 3
FMT_RECORDIO = 4
FMT_RECORDIO_CHUNK = 5
FMT_LIBSVM_COO = 6
FMT_LIBFM_COO = 7
FMT_CSV_SPLIT = 8


def _free_coo(lib, addr):
    lib.dmlc_free_coo(addr)


def _wrap_coo(lib, res):
    """Wrap a CooResult as a dict of zero-copy views.

    ``coords`` is int32 [nnz_padded, 2] — or, on csr_wire blocks, cols-only
    int32 [nnz_padded] with ``row_ptr`` int32 [rows_padded + 1] (half the
    coordinate transfer bytes; the consumer rebuilds row ids on device,
    data/device.py); ``values`` is None when the block is all-ones and
    elision was requested (consumer synthesizes on device);
    ``n_rows``/``nnz`` are the REAL counts (shape dims carry bucket pad)."""
    r = res.contents
    if r.error:
        msg = r.error.decode()
        lib.dmlc_free_coo(res)
        raise DMLCError(msg)
    owner = _Owner(lib, res, _free_coo)
    if r.csr_wire:
        coords = _view(r.coords, r.nnz_padded, np.int32, owner)
        coords = coords if coords is not None else np.zeros((0,), np.int32)
        row_ptr = _view(r.row_ptr, r.rows_padded + 1, np.int32, owner)
    else:
        coords = _view(r.coords, 2 * r.nnz_padded, np.int32, owner)
        coords = coords.reshape(r.nnz_padded, 2) if coords is not None \
            else np.zeros((0, 2), np.int32)
        row_ptr = None
    return {
        "n_rows": int(r.n_rows),
        "nnz": int(r.nnz),
        "rows_padded": int(r.rows_padded),
        "coords": coords,
        "row_ptr": row_ptr,
        "values": (None if r.values_elided
                   else _view(r.values, r.nnz_padded, np.float32, owner)),
        "label": _view(r.label, r.rows_padded, np.float32, owner),
        "weight": _view(r.weight, r.rows_padded, np.float32, owner),
        "_owner": owner,
    }


def _wrap_stream_result(lib, ptr, fmt_value, num_col):
    """Wrap a dmlc_reader_next/dmlc_feeder_next result by format tag."""
    if fmt_value in (FMT_LIBSVM, FMT_LIBFM):
        return fmt_value, _wrap_block(
            lib, ctypes.cast(ptr, ctypes.POINTER(_CsrBlockResult)))
    if fmt_value == FMT_LIBSVM_DENSE:
        return fmt_value, _wrap_dense(
            lib, ctypes.cast(ptr, ctypes.POINTER(_DenseResult)), num_col)
    if fmt_value in (FMT_RECORDIO, FMT_RECORDIO_CHUNK):
        return fmt_value, _wrap_records(
            lib, ctypes.cast(ptr, ctypes.POINTER(_RecordBatchResult)))
    if fmt_value in (FMT_LIBSVM_COO, FMT_LIBFM_COO):
        return fmt_value, _wrap_coo(
            lib, ctypes.cast(ptr, ctypes.POINTER(_CooResult)))
    if fmt_value == FMT_CSV_SPLIT:
        return fmt_value, _wrap_csv_split(
            lib, ctypes.cast(ptr, ctypes.POINTER(_CsvSplitResult)))
    return fmt_value, _wrap_csv(
        lib, ctypes.cast(ptr, ctypes.POINTER(_CsvResult)))


class Reader:
    """Native read->chunk->parse pipeline over a byte-range partition.

    Wraps reader.cc: a C++ producer thread loads record-aligned chunks of
    this partition and parses them with worker threads; :meth:`next` blocks
    (GIL released) until a parsed block is ready and wraps it zero-copy.
    """

    def __init__(self, paths, sizes, part_index: int, num_parts: int,
                 fmt: int, num_col: int = 0, indexing_mode: int = 0,
                 delimiter: str = ",", nthread: int = 0,
                 chunk_bytes: int = 1 << 20, queue_depth: int = 4,
                 batch_rows: int = 0, label_col: int = -1,
                 weight_col: int = -1, out_bf16: bool = False,
                 row_bucket: int = 0, nnz_bucket: int = 0,
                 elide_unit: bool = False, csr_wire: bool = False,
                 pack_aux: bool = False):
        lib = _load()
        if lib is None:
            raise DMLCError("native core unavailable")
        self._lib = lib
        self._fmt = fmt
        self._num_col = num_col
        arr_p = (ctypes.c_char_p * len(paths))(
            *[os.fsencode(p) for p in paths])
        arr_s = (ctypes.c_int64 * len(sizes))(*sizes)
        self._h = lib.dmlc_reader_create(
            arr_p, arr_s, len(paths), part_index, num_parts, fmt, num_col,
            indexing_mode, delimiter.encode()[0] if delimiter else b","[0],
            nthread or default_nthread(), chunk_bytes, queue_depth,
            batch_rows, label_col, weight_col, 1 if out_bf16 else 0,
            row_bucket, nnz_bucket, 1 if elide_unit else 0,
            1 if csr_wire else 0, 1 if pack_aux else 0)
        if not self._h:
            raise DMLCError(
                "native reader creation failed (out of memory or threads)")
        self._check_error()

    def _check_error(self) -> None:
        err = self._lib.dmlc_reader_error(self._h)
        if err:
            raise DMLCError(err.decode())

    def next(self):
        """Next parsed block as ``(fmt, wrapped)`` where wrapped is:
        FMT_LIBSVM/FMT_LIBFM -> dict of CSR arrays (like parse_libsvm);
        FMT_LIBSVM_DENSE -> (x, label, weight, owner);
        FMT_CSV -> (cells, owner). None at end of partition. ``fmt`` can
        downgrade from FMT_LIBSVM_DENSE to FMT_LIBSVM mid-stream when the
        dense scanner meets qid rows."""
        if self._h is None:
            return None
        fmt = ctypes.c_int32(self._fmt)
        ptr = self._lib.dmlc_reader_next(self._h, ctypes.byref(fmt))
        if not ptr:
            self._check_error()
            return None
        return _wrap_stream_result(self._lib, ptr, fmt.value, self._num_col)

    def before_first(self) -> None:
        if self._h is not None:
            self._lib.dmlc_reader_before_first(self._h)

    @property
    def bytes_read(self) -> int:
        return self._lib.dmlc_reader_bytes_read(self._h) if self._h is not None else 0

    def close(self) -> None:
        if self._h is not None:
            self._lib.dmlc_reader_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class Feeder:
    """Push-mode native pipeline: the caller streams raw partition bytes in
    (from ANY filesystem — S3/GCS/HTTP range reads) and pulls parsed blocks
    out; chunking at record boundaries, threaded parsing, and batch repack
    run in C++ exactly as in :class:`Reader`.

    Contract: one feed thread calls ``push`` repeatedly then ``finish``;
    ``push`` blocks (GIL released) for backpressure. Before ``before_first``
    or ``close``, call ``abort`` and JOIN the feed thread.
    """

    def __init__(self, fmt: int, num_col: int = 0, indexing_mode: int = 0,
                 delimiter: str = ",", nthread: int = 0,
                 chunk_bytes: int = 1 << 20, queue_depth: int = 4,
                 batch_rows: int = 0, label_col: int = -1,
                 weight_col: int = -1, out_bf16: bool = False,
                 row_bucket: int = 0, nnz_bucket: int = 0,
                 elide_unit: bool = False, csr_wire: bool = False,
                 pack_aux: bool = False):
        lib = _load()
        if lib is None:
            raise DMLCError("native core unavailable")
        self._lib = lib
        self._fmt = fmt
        self._num_col = num_col
        self._h = lib.dmlc_feeder_create(
            fmt, num_col, indexing_mode,
            delimiter.encode()[0] if delimiter else b","[0],
            nthread or default_nthread(), chunk_bytes, queue_depth,
            batch_rows, label_col, weight_col, 1 if out_bf16 else 0,
            row_bucket, nnz_bucket, 1 if elide_unit else 0,
            1 if csr_wire else 0, 1 if pack_aux else 0)
        if not self._h:
            raise DMLCError("native feeder creation failed")

    def push(self, data) -> bool:
        """Feed bytes; False when the pipeline stopped (error/abort)."""
        if self._h is None:
            return False
        return self._lib.dmlc_feeder_push(self._h, bytes(data), len(data)) == 0

    def finish(self) -> None:
        if self._h is not None:
            self._lib.dmlc_feeder_finish(self._h)

    def abort(self) -> None:
        if self._h is not None:
            self._lib.dmlc_feeder_abort(self._h)

    def fail(self, msg: str) -> None:
        """Record a feed-side failure and end the stream; the consumer's
        next() raises once queued results drain."""
        if self._h is not None:
            self._lib.dmlc_feeder_fail(self._h, msg.encode()[:512])

    def next(self):
        if self._h is None:
            return None
        fmt = ctypes.c_int32(self._fmt)
        ptr = self._lib.dmlc_feeder_next(self._h, ctypes.byref(fmt))
        if not ptr:
            err = self._lib.dmlc_feeder_error(self._h)
            if err:
                raise DMLCError(err.decode())
            return None
        return _wrap_stream_result(self._lib, ptr, fmt.value, self._num_col)

    def before_first(self) -> None:
        if self._h is not None:
            self._lib.dmlc_feeder_before_first(self._h)

    def error(self):
        """The sticky pipeline error string, or None. Errors survive
        before_first (the native reader stays stopped) — callers that want
        a clean restart after a failure must rebuild the Feeder."""
        if self._h is None:
            return None
        err = self._lib.dmlc_feeder_error(self._h)
        return err.decode() if err else None

    @property
    def bytes_read(self) -> int:
        return (self._lib.dmlc_feeder_bytes_read(self._h)
                if self._h is not None else 0)

    def close(self) -> None:
        if self._h is not None:
            self._lib.dmlc_feeder_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class IndexedReader:
    """Native indexed-recordio pipeline: record-count partitioning over an
    external index, batched contiguous reads, per-epoch shuffled seeks —
    reader.cc IndexedReader (indexed_recordio_split.cc:12-41,159-233).

    :meth:`next` blocks (GIL released) until a batch of extracted record
    payloads is ready and wraps it zero-copy as ``(payload, offsets)``.
    """

    def __init__(self, paths, sizes, index_offsets, part_index: int,
                 num_parts: int, batch_records: int = 256,
                 shuffle: bool = False, seed: int = 0, queue_depth: int = 4):
        lib = _load()
        if lib is None:
            raise DMLCError("native core unavailable")
        self._lib = lib
        arr_p = (ctypes.c_char_p * len(paths))(
            *[os.fsencode(p) for p in paths])
        arr_s = (ctypes.c_int64 * len(sizes))(*sizes)
        arr_i = (ctypes.c_int64 * len(index_offsets))(*index_offsets)
        self._h = lib.dmlc_indexed_reader_create(
            arr_p, arr_s, len(paths), arr_i, len(index_offsets),
            part_index, num_parts, batch_records, 1 if shuffle else 0,
            seed, queue_depth)
        if not self._h:
            raise DMLCError(
                "native indexed reader creation failed (out of memory)")
        self._check_error()

    def _check_error(self) -> None:
        err = self._lib.dmlc_indexed_reader_error(self._h)
        if err:
            raise DMLCError(err.decode())

    def next(self):
        """Next batch as ``(payload, offsets)`` numpy views; None at end."""
        if self._h is None:
            return None
        ptr = self._lib.dmlc_indexed_reader_next(self._h)
        if not ptr:
            self._check_error()
            return None
        return _wrap_records(
            self._lib, ctypes.cast(ptr, ctypes.POINTER(_RecordBatchResult)))

    def before_first(self) -> None:
        """Epoch reset; under shuffle the NEXT epoch's permutation is drawn."""
        if self._h is not None:
            self._lib.dmlc_indexed_reader_before_first(self._h)

    def skip(self, epochs: int, records: int) -> None:
        """Native resume: land in epoch `epochs` at record `records` with no
        prefix I/O (missing permutations are drawn by pure rng replay).
        Forward-only — use a fresh reader to revisit an earlier epoch."""
        if self._h is not None:
            self._lib.dmlc_indexed_reader_skip(self._h, epochs, records)
            self._check_error()

    @property
    def bytes_read(self) -> int:
        return (self._lib.dmlc_indexed_reader_bytes_read(self._h)
                if self._h is not None else 0)

    def close(self) -> None:
        if self._h is not None:
            self._lib.dmlc_indexed_reader_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
