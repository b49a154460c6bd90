"""ctypes bindings for the native host runtime (``native/wf_host.cpp``).

The native layer mirrors the reference's C++ runtime surface (SURVEY.md
§2.2 keyby hashing, §5.8 watermark plumbing): bulk ingest parsing, key
partitioning, and the watermark fold, plus the log-structured KV
(``wf_kv.cpp``).  The library is compiled on first use from the sources
shipped next to this module and loaded via ctypes.  Every entry point
keeps a numpy twin for installs without a C++ toolchain, but selecting
it is never silent: a failed build warns with the compiler's message,
and ``WF_TPU_NO_NATIVE=1`` is the explicit opt-out.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import shutil
import subprocess
import tempfile
import warnings
from typing import Optional

import numpy as np

# native sources ship as package data next to this module, so wheels and
# editable checkouts build identically
_NATIVE_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ("wf_host.cpp", "wf_kv.cpp")

_lib = None
_load_attempted = False


class NativeBuildError(RuntimeError):
    """The native library could not be compiled from its sources."""


def build_dir() -> str:
    """Where the compiled library lives: a fixed git-ignored directory
    next to the sources, or the user cache for a read-only install
    (site-packages)."""
    if os.access(_NATIVE_DIR, os.W_OK):
        return os.path.join(_NATIVE_DIR, "_build")
    return os.path.join(os.path.expanduser("~"), ".cache", "windflow_tpu",
                        "native")


def _source_digest() -> str:
    """Content hash of everything the build reads.  It names the
    artifact, so a library built from other sources is never loaded —
    file times say nothing after a copy or a checkout."""
    h = hashlib.sha256()
    for name in _SOURCES + ("Makefile",):
        with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def build(force: bool = False) -> str:
    """Compile the library for the current sources unless it is already
    there (``force`` recompiles regardless); returns its path.  Built in
    a private temp dir and published with an atomic rename, so a
    concurrent reader never dlopens a half-written file.  Raises
    :class:`NativeBuildError` carrying make's output."""
    out_dir = build_dir()
    final = os.path.join(out_dir, f"libwfhost-{_source_digest()}.so")
    if os.path.exists(final) and not force:
        return final
    try:
        os.makedirs(out_dir, exist_ok=True)
        with tempfile.TemporaryDirectory(dir=out_dir) as tmp:
            for src in _SOURCES + ("Makefile",):
                shutil.copy(os.path.join(_NATIVE_DIR, src), tmp)
            subprocess.run(["make", "-C", tmp], check=True,
                           capture_output=True, text=True, timeout=300)
            os.replace(os.path.join(tmp, "libwfhost.so"), final)
    except subprocess.CalledProcessError as e:
        raise NativeBuildError(
            f"make failed ({e.returncode}): {e.stderr.strip()[-2000:]}") \
            from e
    except (OSError, subprocess.TimeoutExpired) as e:
        raise NativeBuildError(f"{type(e).__name__}: {e}") from e
    for old in glob.glob(os.path.join(out_dir, "libwfhost-*.so")):
        if old != final:
            os.remove(old)
    return final


def lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, building it first if needed.  ``None``
    selects the numpy twins: under ``WF_TPU_NO_NATIVE``, or — with a
    warning naming the failure — when the build or the load failed."""
    global _lib, _load_attempted
    if _lib is not None or _load_attempted:
        return _lib
    _load_attempted = True
    if os.environ.get("WF_TPU_NO_NATIVE"):
        return None
    try:
        L = ctypes.CDLL(build())
    except (NativeBuildError, OSError) as e:
        warnings.warn(
            f"windflow_tpu native runtime unavailable ({e}); using the "
            "numpy parsers and the pure-Python KV store — set "
            "WF_TPU_NO_NATIVE=1 to choose them on purpose",
            RuntimeWarning, stacklevel=2)
        return None
    i8, i4, u8 = ctypes.c_int64, ctypes.c_int32, ctypes.c_uint64
    p = ctypes.c_void_p
    L.wf_hash64.restype = u8
    L.wf_hash64.argtypes = [i8]
    L.wf_keyby_partition.restype = None
    L.wf_keyby_partition.argtypes = [p, i8, i4, p, p]
    L.wf_frame_record_bytes.restype = i8
    L.wf_frame_record_bytes.argtypes = [i4]
    L.wf_parse_frames.restype = i8
    L.wf_parse_frames.argtypes = [p, i8, i4, p, p, p, i8]
    L.wf_frames_key_range.restype = i8
    L.wf_frames_key_range.argtypes = [p, i8, i4, p]
    L.wf_parse_frames_packed.restype = i8
    L.wf_parse_frames_packed.argtypes = [p, i8, i4, p, p, i8, i4, i4, i8,
                                         i8, p, p]
    L.wf_parse_csv.restype = i8
    L.wf_parse_csv.argtypes = [p, i8, i4, p, p, p, i8, p]
    L.wf_min_watermark.restype = i8
    L.wf_min_watermark.argtypes = [p, i4, i8]
    c = ctypes.c_char_p
    L.wf_kv_open.restype = p
    L.wf_kv_open.argtypes = [c, i4]
    L.wf_kv_put.restype = i4
    L.wf_kv_put.argtypes = [p, c, i4, c, i8]
    L.wf_kv_get.restype = i8
    L.wf_kv_get.argtypes = [p, c, i4, p, i8]
    L.wf_kv_del.restype = i4
    L.wf_kv_del.argtypes = [p, c, i4]
    L.wf_kv_count.restype = i8
    L.wf_kv_count.argtypes = [p]
    L.wf_kv_log_bytes.restype = i8
    L.wf_kv_log_bytes.argtypes = [p]
    L.wf_kv_live_bytes.restype = i8
    L.wf_kv_live_bytes.argtypes = [p]
    L.wf_kv_compact.restype = i4
    L.wf_kv_compact.argtypes = [p]
    L.wf_kv_flush.restype = i4
    L.wf_kv_flush.argtypes = [p]
    L.wf_kv_close.restype = None
    L.wf_kv_close.argtypes = [p, i4]
    L.wf_kv_iter_new.restype = p
    L.wf_kv_iter_new.argtypes = [p]
    L.wf_kv_iter_next.restype = i4
    L.wf_kv_iter_next.argtypes = [p, p, i4]
    L.wf_kv_iter_destroy.restype = None
    L.wf_kv_iter_destroy.argtypes = [p]
    _lib = L
    return _lib


def is_available() -> bool:
    return lib() is not None


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.c_void_p)


# ---------------------------------------------------------------------------
# High-level wrappers (numpy in / numpy out, with pure-numpy fallbacks)
# ---------------------------------------------------------------------------

_SM_C1 = np.uint64(0xBF58476D1CE4E5B9)
_SM_C2 = np.uint64(0x94D049BB133111EB)
_SM_ADD = np.uint64(0x9E3779B97F4A7C15)


def hash64(keys: np.ndarray) -> np.ndarray:
    """Vectorized splitmix64 (matches the native wf_hash64 bit-for-bit)."""
    x = keys.astype(np.uint64) + _SM_ADD
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint64(30))) * _SM_C1
        x = (x ^ (x >> np.uint64(27))) * _SM_C2
    return x ^ (x >> np.uint64(31))


def keyby_partition(keys: np.ndarray, ndest: int):
    """(dests int32[n], counts int64[ndest]): hash-routing of each tuple."""
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    n = len(keys)
    L = lib()
    if L is not None:
        dests = np.empty(n, np.int32)
        counts = np.empty(ndest, np.int64)
        L.wf_keyby_partition(_ptr(keys), n, ndest, _ptr(dests), _ptr(counts))
        return dests, counts
    dests = (hash64(keys) % np.uint64(ndest)).astype(np.int32)
    counts = np.bincount(dests, minlength=ndest).astype(np.int64)
    return dests, counts


def frame_record_bytes(nv: int) -> int:
    return 16 + 8 * nv


def parse_frames(buf: bytes, nv: int, max_records: int = 2 ** 62):
    """Parse binary records (int64 key, int64 ts, nv×float64) into columns.
    Returns (keys, tss, vals[n, nv], consumed_bytes)."""
    rec = frame_record_bytes(nv)
    n = min(len(buf) // rec, max_records)
    L = lib()
    if L is not None:
        keys = np.empty(n, np.int64)
        tss = np.empty(n, np.int64)
        vals = np.empty((n, nv), np.float64)
        raw = np.frombuffer(buf, np.uint8)
        got = L.wf_parse_frames(_ptr(raw), len(buf), nv, _ptr(keys),
                                _ptr(tss), _ptr(vals), n)
        assert got == n
        return keys, tss, vals, n * rec
    arr = np.frombuffer(buf[:n * rec], np.uint8).reshape(n, rec)
    keys = arr[:, 0:8].copy().view(np.int64).reshape(n)
    tss = arr[:, 8:16].copy().view(np.int64).reshape(n)
    vals = arr[:, 16:].copy().view(np.float64).reshape(n, nv)
    return keys, tss, vals, n * rec


#: value-lane dtypes ``parse_frames_packed`` writes, by the kind code of
#: ``wf_parse_frames_packed``; any other value dtype parses to columns
PACKED_VALUE_KINDS = {np.dtype(np.float32): 0, np.dtype(np.int32): 1,
                      np.dtype(np.int64): 2}


def frames_key_range(buf, nv: int):
    """(min, max) of the keys of the whole records in ``buf``: the
    pre-scan that chooses the key width of a chunk that will split a
    batch, before its first rows ship.  Native library only (``lib()``
    is not None)."""
    out = np.empty(2, np.int64)
    raw = np.frombuffer(buf, np.uint8)
    lib().wf_frames_key_range(_ptr(raw), len(raw), nv, _ptr(out))
    return int(out[0]), int(out[1])


def parse_frames_packed(buf, start: int, nv: int, dst: np.ndarray,
                        lane_off: np.ndarray, capacity: int, key_words: int,
                        val_kind: int, row: int, room: int,
                        ts_fixed: Optional[int] = None):
    """Parse up to ``room`` whole records of ``buf[start:]`` straight into the
    packed staging buffer ``dst`` (``staging.PackedBatchBuilder`` layout,
    ``capacity`` rows: an int64 lane's high words lie ``capacity`` words
    after its low words) from row ``row`` on; ``lane_off`` is
    int64[nv + 2]: the word offsets of the key lane, the value lanes in
    wire order and the ts lane.
    ``ts_fixed`` stamps every row with one timestamp instead of the
    record's own.  Returns (rows written, ts min, ts max, key min, key
    max).  Native library only: the numpy twins parse to columns."""
    raw = np.frombuffer(buf, np.uint8, offset=start)
    ranges = np.empty(4, np.int64)
    fixed = None if ts_fixed is None else \
        ctypes.byref(ctypes.c_int64(ts_fixed))
    m = lib().wf_parse_frames_packed(
        _ptr(raw), len(raw), nv, _ptr(dst), _ptr(lane_off), capacity,
        key_words, val_kind, row, room, fixed, _ptr(ranges))
    assert m >= 0, (key_words, val_kind)
    return (m, *ranges.tolist())


def parse_csv(buf: bytes, nv: int, max_records: int = 2 ** 62):
    """Parse "key,ts,v0[,v1...]\\n" lines into columns.
    Returns (keys, tss, vals[n, nv], consumed_bytes)."""
    L = lib()
    if L is not None:
        cap = min(max_records, buf.count(b"\n") + 1)
        keys = np.empty(cap, np.int64)
        tss = np.empty(cap, np.int64)
        vals = np.empty((cap, nv), np.float64)
        consumed = np.zeros(1, np.int64)
        raw = np.frombuffer(buf, np.uint8)
        n = L.wf_parse_csv(_ptr(raw), len(buf), nv, _ptr(keys), _ptr(tss),
                           _ptr(vals), cap, _ptr(consumed))
        return keys[:n].copy(), tss[:n].copy(), vals[:n].copy(), \
            int(consumed[0])
    keys, tss, rows = [], [], []
    consumed = 0
    for line in buf.split(b"\n")[:-1]:
        end = consumed + len(line) + 1
        if len(keys) >= max_records:
            break
        consumed = end
        parts = line.split(b",")
        if len(parts) != 2 + nv:
            continue
        try:
            k, t = int(parts[0]), int(parts[1])
            vs = [float(x) for x in parts[2:]]
        except ValueError:
            continue
        keys.append(k)
        tss.append(t)
        rows.append(vs)
    return (np.array(keys, np.int64), np.array(tss, np.int64),
            np.array(rows, np.float64).reshape(len(keys), nv), consumed)



def min_watermark(channel_wms: np.ndarray, wm_none: int) -> int:
    """Min over channel maxima; wm_none if any channel is still unset."""
    channel_wms = np.ascontiguousarray(channel_wms, np.int64)
    L = lib()
    if L is not None:
        return int(L.wf_min_watermark(_ptr(channel_wms), len(channel_wms),
                                      wm_none))
    if (channel_wms == wm_none).any() or len(channel_wms) == 0:
        return wm_none
    return int(channel_wms.min())
