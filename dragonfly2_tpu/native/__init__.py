"""ctypes bindings for the native (C++) runtime.

``load()`` builds the shared library on first use (g++ via the Makefile —
pybind11 isn't available in this image, and ctypes keeps the ABI surface
explicit).  Services treat native as an optimization: ``available()``
gates it, and the Python implementations (records/columnar.py) remain the
spec & fallback.
"""

from __future__ import annotations

import _ctypes
import ctypes
import fcntl
import json
import os
import struct
import subprocess
import threading
from typing import Optional

import numpy as np

from ..records import abi_contracts as _abi

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "libdragonfly_native.so")
_BUILD_LOCK_PATH = os.path.join(_DIR, ".build.lock")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None

# Shared engine constants, sourced from the ABI registry so the Python
# side can never restate a value the C++ side has moved away from
# (records/abi_contracts.py is the single source; DF020 pins both sides
# to it).
BATCH_MAX = _abi.constant("kBatchMax")
BATCH_BYTES_MAX = _abi.constant("kBatchBytesMax")
FETCH_BURST_MAX = _abi.constant("kFetchBurstMax")
MAX_FETCH_BODY = _abi.constant("kMaxFetchBody")


def _declare(lib: ctypes.CDLL) -> None:
    i64, u32, i32 = ctypes.c_int64, ctypes.c_uint32, ctypes.c_int
    lib.re_open.restype = i64
    lib.re_open.argtypes = [ctypes.c_char_p, ctypes.c_char_p, u32]
    lib.re_append.restype = i64
    lib.re_append.argtypes = [i64, ctypes.POINTER(ctypes.c_float), i64]
    lib.re_flush.restype = i32
    lib.re_flush.argtypes = [i64]
    lib.re_rows.restype = i64
    lib.re_rows.argtypes = [i64]
    lib.re_close.restype = i32
    lib.re_close.argtypes = [i64]

    p8 = ctypes.POINTER(ctypes.c_uint8)
    lib.ps_open.restype = i64
    lib.ps_open.argtypes = [ctypes.c_char_p]
    lib.ps_create_task.restype = i32
    lib.ps_create_task.argtypes = [i64, ctypes.c_char_p, u32, i64]
    lib.ps_load_task.restype = i32
    lib.ps_load_task.argtypes = [i64, ctypes.c_char_p]
    lib.ps_write_piece.restype = i64
    lib.ps_write_piece.argtypes = [i64, ctypes.c_char_p, u32, p8, u32]
    lib.ps_read_piece.restype = i64
    lib.ps_read_piece.argtypes = [i64, ctypes.c_char_p, u32, p8, u32, i32]
    lib.ps_piece_count.restype = i64
    lib.ps_piece_count.argtypes = [i64, ctypes.c_char_p]
    lib.ps_piece_bitmap.restype = i32
    lib.ps_piece_bitmap.argtypes = [i64, ctypes.c_char_p, p8, u32]
    lib.ps_task_bytes.restype = i64
    lib.ps_task_bytes.argtypes = [i64, ctypes.c_char_p]
    lib.ps_piece_size.restype = i64
    lib.ps_piece_size.argtypes = [i64, ctypes.c_char_p]
    lib.ps_content_length.restype = i64
    lib.ps_content_length.argtypes = [i64, ctypes.c_char_p]
    lib.ps_delete_task.restype = i32
    lib.ps_delete_task.argtypes = [i64, ctypes.c_char_p]
    lib.ps_close.restype = i32
    lib.ps_close.argtypes = [i64]
    lib.ps_serve.restype = i64
    lib.ps_serve.argtypes = [i64, ctypes.c_char_p, ctypes.c_uint16, i32]
    lib.ps_serve_stop.restype = i32
    lib.ps_serve_stop.argtypes = [i64]
    lib.ps_serve_stats2.restype = i32
    lib.ps_serve_stats2.argtypes = [
        i64, ctypes.POINTER(i64), ctypes.POINTER(i64),
        ctypes.POINTER(i64), ctypes.POINTER(i64)
    ]
    lib.ps_leak_stats.restype = i32
    lib.ps_leak_stats.argtypes = [ctypes.POINTER(i64), ctypes.POINTER(i64)]

    lib.pf_open.restype = i64
    lib.pf_open.argtypes = [i64, i32, ctypes.c_char_p]
    lib.pf_parent.restype = i32
    lib.pf_parent.argtypes = [i64, i32, ctypes.c_char_p, ctypes.c_uint16]
    lib.pf_submit.restype = i32
    lib.pf_submit.argtypes = [i64, ctypes.c_char_p, i32, u32, u32]
    lib.pf_complete.restype = i32
    lib.pf_complete.argtypes = [i64, p8, i32, i32]
    lib.pf_pending.restype = i64
    lib.pf_pending.argtypes = [i64]
    lib.pf_close.restype = i32
    lib.pf_close.argtypes = [i64]

    f32p = ctypes.POINTER(ctypes.c_float)
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(i64)
    f64p = ctypes.POINTER(ctypes.c_double)
    dbl = ctypes.c_double
    lib.oi_create.restype = i64
    lib.oi_create.argtypes = [ctypes.c_int32, i64, ctypes.c_int32,
                              ctypes.c_int32, dbl, i64]
    lib.oi_destroy.restype = i32
    lib.oi_destroy.argtypes = [i64]
    lib.oi_feed_download_rows.restype = i64
    lib.oi_feed_download_rows.argtypes = [i64, f32p, i64, dbl, i32]
    lib.oi_map_buckets.restype = i32
    lib.oi_map_buckets.argtypes = [i64, f32p, i64, dbl, i32p]
    lib.oi_lookup.restype = i32
    lib.oi_lookup.argtypes = [i64, f32p, i64, i32p]
    lib.oi_take_edges.restype = i64
    lib.oi_take_edges.argtypes = [i64, i64, i32p, i32p, f32p, i64]
    lib.oi_eof.restype = None
    lib.oi_eof.argtypes = [i64]
    lib.oi_node_features.restype = i32
    lib.oi_node_features.argtypes = [i64, f32p]
    lib.oi_take_recycled.restype = i64
    lib.oi_take_recycled.argtypes = [i64, i32p, i64]
    lib.oi_pending_recycled.restype = i64
    lib.oi_pending_recycled.argtypes = [i64]
    lib.oi_stats.restype = i32
    lib.oi_stats.argtypes = [i64, i64p, i64p, i64p, i64p]
    lib.oi_export_state.restype = i64
    lib.oi_export_state.argtypes = [i64, i32p, i64p, f64p, i32p, i64,
                                    f32p, f32p, i64p]
    lib.oi_import_state.restype = i32
    lib.oi_import_state.argtypes = [i64, i32p, i64p, f64p, i32p, i64,
                                    f32p, f32p, i64, i64, i64]

    # ABI manifest witness (DESIGN.md §30): df_abi_manifest returns a
    # process-lifetime static string — c_char_p is safe (no free).
    lib.df_abi_manifest.restype = ctypes.c_char_p
    lib.df_abi_manifest.argtypes = []
    lib.df_abi_probe_fetchdone.restype = ctypes.c_int32
    lib.df_abi_probe_fetchdone.argtypes = [p8, u32]


def _try_open() -> Optional[ctypes.CDLL]:
    """The library, or None where it is missing or was built from an
    older source tree and lacks a symbol."""
    if not os.path.exists(_LIB_PATH):
        return None
    lib = ctypes.CDLL(_LIB_PATH)
    try:
        _declare(lib)
    except AttributeError:
        # Unmap it, or dlopen answers the next CDLL of this path with the
        # same stale image whatever the file then holds.
        _ctypes.dlclose(lib._handle)
        return None
    return lib


def _open_or_build() -> ctypes.CDLL:
    """Open the library, building it first where ``_try_open`` finds none.

    The Makefile links to a temporary name and renames it onto the
    target, so a reader never maps a partial file; the ``flock`` makes
    concurrent first loads (six test workers at collection) build once:
    whoever gets the lock looks again before running ``make``.
    """
    lib = _try_open()
    if lib is not None:
        return lib
    with open(_BUILD_LOCK_PATH, "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        lib = _try_open()
        if lib is None:
            subprocess.run(
                ["make", "-C", _DIR, "-s", "-B"],
                check=True, capture_output=True, text=True, timeout=120,
            )
            lib = ctypes.CDLL(_LIB_PATH)
            _declare(lib)
        return lib


def load() -> Optional[ctypes.CDLL]:
    """Build (if needed) and load the native library; None on failure."""
    global _lib, _build_error
    with _lock:
        if _lib is None and _build_error is None:
            try:
                _lib = _open_or_build()
            except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
                    OSError, AttributeError) as exc:
                _build_error = getattr(exc, "stderr", None) or str(exc)
        return _lib


def available() -> bool:
    return load() is not None


def build_error() -> Optional[str]:
    return _build_error


def leaked_servers() -> tuple:
    """(leaked_servers, stuck_conns): process-wide wedged-shutdown counters.

    A ``ps_serve_stop`` that times out past its grace leaks the server
    struct rather than freeing memory live threads still reference; this
    surfaces the count so teardowns can ASSERT it stayed zero instead of
    scraping stderr.  (0, 0) when the library never loaded.
    """
    lib = load()
    if lib is None:
        return (0, 0)
    s = ctypes.c_int64(0)
    c = ctypes.c_int64(0)
    lib.ps_leak_stats(ctypes.byref(s), ctypes.byref(c))
    return (int(s.value), int(c.value))


# ---------------------------------------------------------------------------
# Pythonic wrappers
# ---------------------------------------------------------------------------


class NativeError(RuntimeError):
    pass


class NativeColumnarWriter:
    """Drop-in for records.columnar.ColumnarWriter backed by the C++ engine.

    Same on-disk format — ColumnarReader reads its files unchanged.
    """

    def __init__(self, path: str, columns, dtype: str = "float32"):
        if dtype != "float32":
            raise ValueError("native writer is float32-only")
        lib = load()
        if lib is None:
            raise NativeError(f"native library unavailable: {_build_error}")
        self._lib = lib
        self.path = path
        self.columns = tuple(columns)
        if os.path.exists(path) and os.path.getsize(path) > 0:
            # Same contract as the Python writer: appending to an existing
            # shard requires an identical column set (columnar.py:83-86).
            from ..records.columnar import read_header

            existing, _ = read_header(path)
            if existing.columns != self.columns:
                raise ValueError(
                    f"{path}: existing columns {existing.columns} != {self.columns}"
                )
        header = json.dumps(
            {"columns": list(self.columns), "dtype": "float32", "created_at_ns": 0}
        ).encode()
        self._h = lib.re_open(path.encode(), header, len(self.columns))
        if self._h < 0:
            raise NativeError(f"re_open({path}) -> {self._h}")

    def append(self, rows: np.ndarray) -> int:
        rows = np.ascontiguousarray(rows, dtype=np.float32)
        if rows.ndim == 1:
            rows = rows[None, :]
        if rows.shape[-1] != len(self.columns):
            raise ValueError(f"row width {rows.shape[-1]} != {len(self.columns)}")
        n = self._lib.re_append(
            self._h,
            rows.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            rows.shape[0],
        )
        if n < 0:
            raise NativeError(f"re_append -> {n}")
        if n != rows.shape[0]:
            # Short write (disk full): silently dropped rows would corrupt
            # the shard for every downstream reader.
            raise NativeError(
                f"re_append wrote {n}/{rows.shape[0]} rows (disk full?)"
            )
        return int(n)

    def flush(self) -> None:
        self._lib.re_flush(self._h)

    def tell_rows(self) -> int:
        return int(self._lib.re_rows(self._h))

    def close(self) -> None:
        if self._h >= 0:
            self._lib.re_close(self._h)
            self._h = -1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class NativePieceStore:
    """The daemon's local piece store (C++ engine).

    Mirrors client/daemon/storage semantics: per-task metadata+data files,
    crc-verified reads, crash reload (re-open sees committed pieces).
    """

    def __init__(self, root: str):
        lib = load()
        if lib is None:
            raise NativeError(f"native library unavailable: {_build_error}")
        self._lib = lib
        self.root = root
        self._h = lib.ps_open(root.encode())
        if self._h < 0:
            raise NativeError(f"ps_open({root}) -> {self._h}")

    def create_task(self, task_id: str, piece_size: int, content_length: int) -> None:
        rc = self._lib.ps_create_task(self._h, task_id.encode(), piece_size, content_length)
        if rc != 0:
            raise NativeError(f"ps_create_task -> {rc}")

    def load_task(self, task_id: str) -> bool:
        """Open an existing task (crash reload); False if absent."""
        return self._lib.ps_load_task(self._h, task_id.encode()) == 0

    def write_piece(self, task_id: str, number: int, data: bytes) -> int:
        buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data)
        n = self._lib.ps_write_piece(self._h, task_id.encode(), number, buf, len(data))
        if n < 0:
            raise NativeError(f"ps_write_piece -> {n}")
        return int(n)

    def piece_size(self, task_id: str) -> int:
        return int(self._lib.ps_piece_size(self._h, task_id.encode()))

    def read_piece(self, task_id: str, number: int, *, max_len: Optional[int] = None, verify: bool = True) -> bytes:
        if max_len is None:
            # A committed piece is never longer than the task's piece size.
            ps = self.piece_size(task_id)
            max_len = ps if ps > 0 else 8 << 20
        buf = (ctypes.c_uint8 * max_len)()
        n = self._lib.ps_read_piece(
            self._h, task_id.encode(), number, buf, max_len, 1 if verify else 0
        )
        if n == -3:
            raise KeyError(f"piece {number} of {task_id} not present")
        if n == -6:
            raise NativeError(f"piece {number} of {task_id} failed crc verification")
        if n < 0:
            raise NativeError(f"ps_read_piece -> {n}")
        # string_at: one memcpy.  Slicing a ctypes array (`buf[:n]`)
        # materializes n Python ints first — measured 98 ms per 4 MiB
        # piece vs 1.8 ms for the whole python-engine read.
        return ctypes.string_at(buf, int(n))

    def piece_count(self, task_id: str) -> int:
        n = self._lib.ps_piece_count(self._h, task_id.encode())
        return max(int(n), 0)

    def piece_bitmap(self, task_id: str, n_pieces: int) -> np.ndarray:
        buf = (ctypes.c_uint8 * n_pieces)()
        rc = self._lib.ps_piece_bitmap(self._h, task_id.encode(), buf, n_pieces)
        if rc != 0:
            raise NativeError(f"ps_piece_bitmap -> {rc}")
        return np.frombuffer(bytes(buf), dtype=np.uint8)

    def task_bytes(self, task_id: str) -> int:
        return max(int(self._lib.ps_task_bytes(self._h, task_id.encode())), 0)

    def content_length(self, task_id: str) -> int:
        return int(self._lib.ps_content_length(self._h, task_id.encode()))

    def delete_task(self, task_id: str) -> None:
        self._lib.ps_delete_task(self._h, task_id.encode())

    def serve(self, host: str = "127.0.0.1", port: int = 0,
              *, concurrent_limit: int = 64) -> int:
        """Start the in-engine HTTP piece server (native.cpp ps_serve):
        piece/bitmap/range GETs served via sendfile, no GIL on the data
        path.  Returns the bound port."""
        p = self._lib.ps_serve(self._h, host.encode(), port, concurrent_limit)
        if p < 0:
            raise NativeError(f"ps_serve -> {p}")
        return int(p)

    def serve_stop(self) -> None:
        self._lib.ps_serve_stop(self._h)

    def serve_stats(self) -> tuple:
        """(pieces_served, bytes_served) while the server runs.

        Narrow view over ``serve_stats_full`` — the legacy two-pointer
        ``ps_serve_stats`` export is gone (one out-pointer list fewer to
        keep in sync with the ABI registry)."""
        full = self.serve_stats_full()
        return full["pieces"], full["bytes"]

    def serve_stats_full(self) -> dict:
        """Extended counters: adds the batched-burst piece count and the
        live connection-thread count (ps_serve_stats2)."""
        vals = [ctypes.c_int64(0) for _ in range(4)]
        rc = self._lib.ps_serve_stats2(
            self._h, *[ctypes.byref(v) for v in vals]
        )
        if rc != 0:
            return {"pieces": 0, "bytes": 0, "batched": 0, "conns": 0}
        return {
            "pieces": int(vals[0].value),
            "bytes": int(vals[1].value),
            "batched": int(vals[2].value),
            "conns": int(vals[3].value),
        }

    def close(self) -> None:
        if self._h >= 0:
            self._lib.ps_close(self._h)
            self._h = -1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class NativePieceFetcher:
    """The in-engine piece fetch loop (pf_* in native.cpp, DESIGN.md §28).

    Python keeps scheduling ownership — it registers parents into slots,
    submits (piece, slot) pairs, and drains a bounded completion queue;
    the engine runs the pooled keep-alive fetch → length check →
    crc+fsync commit per piece with zero Python per-piece overhead.
    Every non-zero completion status simply returns the piece to the
    ordinary Python retry/hedge path (conductor fetch_one is the spec).
    """

    # native.cpp FetchDone: u32 number, i32 status, u32 length,
    # i32 parent slot, i64 cost_ns — format and size come from the ABI
    # registry (DF020 + the runtime witness pin both to the compiled
    # struct).
    RECORD = _abi.record_format("FetchDone")
    RECORD_SIZE = _abi.record_size("FetchDone")
    MAX_DRAIN = 256

    def __init__(self, store: "NativePieceStore", *, workers: int = 4,
                 tenant: str = ""):
        lib = load()
        if lib is None:
            raise NativeError(f"native library unavailable: {_build_error}")
        self._lib = lib
        self._h = lib.pf_open(store._h, workers, tenant.encode())
        if self._h < 0:
            raise NativeError(f"pf_open -> {self._h}")
        self._buf = (ctypes.c_uint8 * (self.RECORD_SIZE * self.MAX_DRAIN))()

    def set_parent(self, slot: int, ip: str, port: int) -> None:
        rc = self._lib.pf_parent(self._h, slot, ip.encode(), port)
        if rc != 0:
            raise NativeError(f"pf_parent({slot}, {ip}:{port}) -> {rc}")

    # dflint: hotpath submit
    def submit(self, task_id: str, slot: int, number: int,
               expected_len: int) -> bool:
        return self._lib.pf_submit(
            self._h, task_id.encode(), slot, number, expected_len
        ) == 0

    # dflint: hotpath complete
    def complete(self, *, timeout_ms: int = 1000) -> list:
        """Drain completions: [(number, status, length, slot, cost_ns)].
        Blocks up to timeout_ms for the first record; [] on timeout."""
        n = self._lib.pf_complete(
            self._h, self._buf, self.MAX_DRAIN, timeout_ms
        )
        if n < 0:
            raise NativeError(f"pf_complete -> {n}")
        return list(struct.iter_unpack(
            self.RECORD, ctypes.string_at(self._buf, n * self.RECORD_SIZE)
        ))

    def pending(self) -> int:
        return max(int(self._lib.pf_pending(self._h)), 0)

    def close(self) -> None:
        """Release the engine handle.  Queued (not yet in-flight) jobs
        are DISCARDED, not fetched — by the time the conductor closes,
        its window deadline has already routed unfinished pieces to the
        Python retry path, so close never stalls on a wedged parent."""
        if self._h >= 0:
            self._lib.pf_close(self._h)
            self._h = -1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def _fp(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _ip(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def _lp(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _dp(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_double))


class NativeOnlineIngest:
    """The wire→trainer hot path in C++ (oi_* in native.cpp): bucket→id
    mapping with the TTL lifecycle, host-feature accumulation, and the
    dispatch-block edge ring — one GIL-free call per wire chunk.
    ``trainer.online_graph.WireIngestAdapter`` is the semantic spec and
    delegates here when the library is available."""

    def __init__(self, num_nodes: int, n_buckets: int, feat_dim: int,
                 row_width: int, node_ttl: float, ring_capacity: int):
        lib = load()
        if lib is None:
            raise NativeError(f"native library unavailable: {_build_error}")
        self._lib = lib
        self.num_nodes = int(num_nodes)
        self.n_buckets = int(n_buckets)
        self.feat_dim = int(feat_dim)
        self.row_width = int(row_width)
        self._h = lib.oi_create(num_nodes, n_buckets, feat_dim, row_width,
                                float(node_ttl), ring_capacity)
        if self._h < 0:
            raise NativeError(f"oi_create -> {self._h}")

    def feed_download_rows(self, rows: np.ndarray, now: float,
                           *, block: bool = True) -> int:
        rows = np.ascontiguousarray(rows, np.float32)
        if rows.ndim != 2 or rows.shape[1] != self.row_width:
            # The engine strides by ITS row_width — a mismatched shape
            # would be an out-of-bounds read, not an error.
            raise NativeError(
                f"rows shape {rows.shape} != [n, {self.row_width}]"
            )
        kept = self._lib.oi_feed_download_rows(
            self._h, _fp(rows), rows.shape[0], now, 1 if block else 0
        )
        if kept < 0:
            raise NativeError(f"oi_feed_download_rows -> {kept}")
        return int(kept)

    def map_buckets(self, buckets: np.ndarray, now: float) -> np.ndarray:
        b = np.ascontiguousarray(buckets, np.float32)
        out = np.empty(len(b), np.int32)
        rc = self._lib.oi_map_buckets(self._h, _fp(b), len(b), now, _ip(out))
        if rc != 0:
            raise NativeError(f"oi_map_buckets -> {rc}")
        return out

    def lookup(self, buckets: np.ndarray) -> np.ndarray:
        """Read-only mapping probe — never allocates ids."""
        b = np.ascontiguousarray(buckets, np.float32)
        out = np.empty(len(b), np.int32)
        rc = self._lib.oi_lookup(self._h, _fp(b), len(b), _ip(out))
        if rc != 0:
            raise NativeError(f"oi_lookup -> {rc}")
        return out

    def take_edges(self, need: int, timeout_s: float):
        """Exactly-`need` edges as (src, dst, y), or None on timeout/EOF
        with fewer than `need` buffered."""
        src = np.empty(need, np.int32)
        dst = np.empty(need, np.int32)
        y = np.empty(need, np.float32)
        got = self._lib.oi_take_edges(
            self._h, need, _ip(src), _ip(dst), _fp(y),
            max(int(timeout_s * 1000), 0),
        )
        if got < 0:
            raise NativeError(f"oi_take_edges -> {got}")
        if got == 0:
            return None
        return src, dst, y

    def eof(self) -> None:
        self._lib.oi_eof(self._h)

    def node_features(self) -> np.ndarray:
        out = np.empty((self.num_nodes, self.feat_dim), np.float32)
        rc = self._lib.oi_node_features(self._h, _fp(out))
        if rc != 0:
            raise NativeError(f"oi_node_features -> {rc}")
        return out

    def take_recycled(self, cap: int = 65536) -> np.ndarray:
        out = np.empty(cap, np.int32)
        n = self._lib.oi_take_recycled(self._h, _ip(out), cap)
        if n < 0:
            raise NativeError(f"oi_take_recycled -> {n}")
        return out[:n].copy()

    def pending_recycled(self) -> int:
        return int(self._lib.oi_pending_recycled(self._h))

    def stats(self) -> dict:
        vals = [ctypes.c_int64(0) for _ in range(4)]
        rc = self._lib.oi_stats(self._h, *[ctypes.byref(v) for v in vals])
        if rc != 0:
            raise NativeError(f"oi_stats -> {rc}")
        return {
            "overflow_edges": vals[0].value,
            "evicted_nodes": vals[1].value,
            "next_id": vals[2].value,
            "rows_in": vals[3].value,
        }

    def export_state(self):
        """Snapshot the mapping for a checkpoint; None while recycled ids
        still await their embedding-row reset (caller drains + retries)."""
        id_table = np.empty(self.n_buckets, np.int32)
        bucket_of = np.empty(self.num_nodes, np.int64)
        last_seen = np.empty(self.num_nodes, np.float64)
        free = np.empty(self.num_nodes, np.int32)
        feat_sum = np.empty((self.num_nodes, self.feat_dim), np.float32)
        feat_cnt = np.empty(self.num_nodes, np.float32)
        scalars = np.zeros(3, np.int64)
        n = self._lib.oi_export_state(
            self._h, _ip(id_table), _lp(bucket_of), _dp(last_seen),
            _ip(free), self.num_nodes, _fp(feat_sum), _fp(feat_cnt),
            _lp(scalars),
        )
        if n == -1:
            return None
        if n < 0:
            raise NativeError(f"oi_export_state -> {n}")
        return {
            "id_table": id_table,
            "bucket_of": bucket_of,
            "last_seen": last_seen,
            "free": free[:n].copy(),
            "feat_sum": feat_sum,
            "feat_cnt": feat_cnt,
            "next_id": int(scalars[0]),
            "overflow_edges": int(scalars[1]),
            "evicted_nodes": int(scalars[2]),
        }

    def import_state(self, id_table, bucket_of, last_seen, free,
                     feat_sum, feat_cnt, next_id, overflow, evicted) -> None:
        id_table = np.ascontiguousarray(id_table, np.int32)
        bucket_of = np.ascontiguousarray(bucket_of, np.int64)
        last_seen = np.ascontiguousarray(last_seen, np.float64)
        free = np.ascontiguousarray(free, np.int32)
        feat_sum = np.ascontiguousarray(feat_sum, np.float32)
        feat_cnt = np.ascontiguousarray(feat_cnt, np.float32)
        # The engine memcpys its OWN sizes out of these buffers — a
        # shape mismatch would be an out-of-bounds read, not an error.
        if (
            len(id_table) != self.n_buckets
            or len(bucket_of) != self.num_nodes
            or len(last_seen) != self.num_nodes
            or len(feat_cnt) != self.num_nodes
            or feat_sum.size != self.num_nodes * self.feat_dim
        ):
            raise NativeError(
                f"import_state shape mismatch: engine has num_nodes="
                f"{self.num_nodes}/n_buckets={self.n_buckets}"
            )
        rc = self._lib.oi_import_state(
            self._h, _ip(id_table), _lp(bucket_of), _dp(last_seen),
            _ip(free), len(free), _fp(feat_sum), _fp(feat_cnt),
            int(next_id), int(overflow), int(evicted),
        )
        if rc != 0:
            raise NativeError(
                f"oi_import_state -> {rc} (corrupt adapter state?)"
            )

    def close(self) -> None:
        if self._h >= 0:
            self._lib.oi_destroy(self._h)
            self._h = -1

    def __del__(self):  # belt & suspenders; close() is the contract
        try:
            self.close()
        except Exception:  # dflint: disable=DF001 — __del__ during
            pass          # interpreter teardown must never raise or log
