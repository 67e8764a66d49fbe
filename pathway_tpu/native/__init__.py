"""ctypes bindings for the native C++ runtime (``native/`` at the repo root).

The reference implements its host-side hot loops — connector scanners/parsers,
value serialization for key hashing, snapshot framing, shard routing — in Rust
(src/connectors/, src/engine/value.rs, src/persistence/); here they live in
C++ and are loaded through ctypes.  ``build()`` holds the one compile command;
the library is ``native/build/libpathway_native-<hex>.so``, ``<hex>`` the hash
of that command and of every source and header, so a file of that name IS the
library of these sources: it is loaded if it exists and built (to a temporary
name, then renamed into place) if it does not.  If it cannot be built (one
warning, with the compiler's words) or ``PATHWAY_TPU_DISABLE_NATIVE=1``,
pure-Python fallbacks with identical semantics take over — tests assert
native/fallback agreement bit-for-bit.
"""

from __future__ import annotations

import ctypes
import hashlib
import importlib.util
import logging
import os
import subprocess
import threading
from pathlib import Path
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .. import config

__all__ = [
    "available",
    "lib",
    "build",
    "csv_scan",
    "csv_unescape",
    "parse_int64",
    "parse_float64",
    "serialize_rows",
    "hash_rows",
    "crc32",
    "frame_scan",
    "shard_rows",
    "encode_batch",
    "encode_pairs",
    "pack_rows",
]

_log = logging.getLogger(__name__)

_NATIVE_DIR = Path(__file__).resolve().parents[2] / "native"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
# the same library through a handle whose calls KEEP the GIL (``_lib_for``)
_lib_held: Optional[ctypes.PyDLL] = None
_tried = False

# A call through ``ctypes.CDLL`` drops the GIL and has to win it back when
# it returns; under the serve path's 32 callers that wait outlasts a short
# call itself (PERF.md section 6, ISSUE 29).  Calls that scan at most this
# many bytes keep it; a connector's CSV scan or a bulk tokenise of
# megabytes goes on releasing it.
_HOLD_GIL_BYTES = 1 << 20

_i64 = ctypes.c_int64
_u64 = ctypes.c_uint64
_u32 = ctypes.c_uint32
_i32 = ctypes.c_int32
_u8 = ctypes.c_uint8
_p_u8 = ctypes.POINTER(_u8)
_p_i32 = ctypes.POINTER(_i32)
_p_i64 = ctypes.POINTER(_i64)
_p_u64 = ctypes.POINTER(_u64)


def _recipe() -> List[str]:
    """The compile command, run in ``native/``, without its ``-o``.  xxh3
    row hashing and the tokenizer need the header-only xxHash, which pyarrow
    vendors in every environment we target; without it ``hash.cc`` compiles
    to a stub and Python hashes rows itself (slower, same results)."""
    argv = [os.environ.get("CXX") or "g++", "-O3", "-fPIC", "-std=c++17",
            "-Wall", "-Wextra", "-Iinclude"]
    pyarrow = importlib.util.find_spec("pyarrow")
    if pyarrow is not None and pyarrow.submodule_search_locations:
        xxhash = Path(pyarrow.submodule_search_locations[0],
                      "include", "arrow", "vendored", "xxhash")
        if (xxhash / "xxhash.h").exists():
            argv.append(f"-I{xxhash}")
    srcs = sorted(p.name for p in (_NATIVE_DIR / "src").glob("*.cc"))
    return [*argv, "-shared", *(f"src/{name}" for name in srcs)]


def _library_path(recipe: Sequence[str]) -> Path:
    """Where the library of ``recipe`` and of the sources as they stand
    lives: the name is the hash of everything the object code depends on."""
    h = hashlib.sha256("\0".join(recipe).encode())
    for p in sorted([*(_NATIVE_DIR / "src").glob("*.cc"),
                     *(_NATIVE_DIR / "include").glob("*.h")]):
        h.update(b"\0" + p.name.encode() + b"\0" + p.read_bytes())
    return _NATIVE_DIR / "build" / f"libpathway_native-{h.hexdigest()[:16]}.so"


def build() -> Optional[Path]:
    """The library of this checkout's sources, compiled first if no file of
    its name exists; None (after one warning) if it cannot be compiled.
    The compiler writes a name of this process's own, which is then renamed
    onto the final one: concurrent first users each publish the same bytes
    whole, and a file that a process may have mapped is never written."""
    if not (_NATIVE_DIR / "src").is_dir():
        return None
    recipe = _recipe()
    so = _library_path(recipe)
    if so.exists():
        return so
    tmp = so.with_name(f"{so.name}.{os.getpid()}-{threading.get_ident()}.tmp")
    try:
        so.parent.mkdir(exist_ok=True)
        subprocess.run(
            [*recipe, "-o", str(tmp)], cwd=_NATIVE_DIR, check=True,
            capture_output=True, text=True, timeout=120,
        )
        os.replace(tmp, so)
    except (subprocess.SubprocessError, OSError) as e:
        tmp.unlink(missing_ok=True)
        said = (getattr(e, "stderr", None) or "").strip()[-2000:]
        _log.warning(
            "native library not built (%s); Python fallbacks serve%s",
            e, f":\n{said}" if said else "",
        )
        return None
    return so


def _declare(dll: ctypes.CDLL) -> ctypes.CDLL:
    dll.pn_csv_count.restype = _i32
    dll.pn_csv_count.argtypes = [_p_u8, _i64, _u8, _u8, _p_i64, _p_i64]
    dll.pn_csv_scan.restype = _i32
    dll.pn_csv_scan.argtypes = [_p_u8, _i64, _u8, _u8, _p_i64, _p_i64, _p_i64, _p_u8]
    dll.pn_csv_unescape.restype = _i64
    dll.pn_csv_unescape.argtypes = [_p_u8, _i64, _u8, _p_u8]
    dll.pn_parse_int64.restype = None
    dll.pn_parse_int64.argtypes = [_p_u8, _p_i64, _p_i64, _i64, _p_i64, _p_u8]
    dll.pn_parse_float64.restype = None
    dll.pn_parse_float64.argtypes = [
        _p_u8, _p_i64, _p_i64, _i64, ctypes.POINTER(ctypes.c_double), _p_u8,
    ]
    dll.pn_serialize_rows.restype = _i64
    dll.pn_serialize_rows.argtypes = [
        _i64, _i32, _p_u8,
        ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_void_p),
        _p_u8, _i64, _p_i64,
    ]
    dll.pn_hash_rows.restype = _i32
    dll.pn_hash_rows.argtypes = [_p_u8, _i64, _p_i64, _i64, _p_u64]
    dll.pn_crc32.restype = _u32
    dll.pn_crc32.argtypes = [_p_u8, _i64, _u32]
    dll.pn_frame_scan.restype = _i64
    dll.pn_frame_scan.argtypes = [_p_u8, _i64, _p_i64, _p_i64, _i64, _p_i64]
    dll.pn_shard_rows.restype = None
    dll.pn_shard_rows.argtypes = [_p_u64, _i64, _u32, _u64, _p_i64, _p_i64]
    dll.pn_encode_batch.restype = _i32
    dll.pn_encode_batch.argtypes = [
        _p_u8, _p_i64, _i64, _i32, _i32, _i64, _p_i64, _i64, _i32, _i32,
        _i32, _i64, _p_i32, _p_i32, _p_i64,
    ]
    dll.pn_encode_pairs.restype = _i32
    dll.pn_encode_pairs.argtypes = [
        _p_u8, _p_i64, _i64, _i32, _i32, _p_i64, _p_i64, _i64, _i64,
        _i32, _i32, _i64, ctypes.POINTER(_i32), _p_i64,
        ctypes.POINTER(_i32), ctypes.POINTER(_i32), _p_i64,
    ]
    dll.pn_pack_rows.restype = _i32
    dll.pn_pack_rows.argtypes = [
        _p_i32, _i64, _i64, _p_i64, _i64, _i64, _p_i64, _i64, _p_i64,
        _p_i32, _i32, _p_i32, _p_i32, _p_i32, _p_i32, _p_i64, _p_i64,
        _p_i64,
    ]
    return dll


def lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, building it on first use; None if disabled
    or unbuildable."""
    global _lib, _lib_held, _tried
    if _lib is not None:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if config.get("native.disable"):
            return None
        so = build()
        if so is None:
            return None
        try:
            # the twin first: whoever sees ``_lib`` set finds it there
            _lib_held = _declare(ctypes.PyDLL(str(so)))
            _lib = _declare(ctypes.CDLL(str(so)))
        except OSError as e:
            _lib = _lib_held = None
            _log.warning("native library %s does not load (%s); Python fallbacks serve", so, e)
        return _lib


def _lib_for(nbytes: int) -> Optional[ctypes.CDLL]:
    """``lib()`` for a call that scans ``nbytes``: the GIL-keeping handle
    for a short one (``_HOLD_GIL_BYTES``)."""
    dll = lib()
    return _lib_held if dll is not None and nbytes <= _HOLD_GIL_BYTES else dll


def available() -> bool:
    return lib() is not None


def _as_u8_ptr(buf: bytes):
    return ctypes.cast(ctypes.c_char_p(buf), _p_u8)


def _np_ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


# ---------------------------------------------------------------- CSV


def csv_scan(
    data: bytes, delim: str = ",", quote: str = '"'
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Scan a CSV byte buffer into columnar extents:
    (row_cell_start[n_rows+1], cell_off, cell_len, cell_quoted)."""
    dll = lib()
    if dll is None:
        from . import fallback

        return fallback.csv_scan(data, delim, quote)
    d, q = ord(delim), ord(quote)
    n_rows = _i64(0)
    n_cells = _i64(0)
    buf = _as_u8_ptr(data)
    dll.pn_csv_count(buf, len(data), d, q, ctypes.byref(n_rows), ctypes.byref(n_cells))
    rcs = np.empty(n_rows.value + 1, dtype=np.int64)
    off = np.empty(n_cells.value, dtype=np.int64)
    ln = np.empty(n_cells.value, dtype=np.int64)
    quoted = np.empty(n_cells.value, dtype=np.uint8)
    dll.pn_csv_scan(
        buf, len(data), d, q,
        _np_ptr(rcs, _i64), _np_ptr(off, _i64), _np_ptr(ln, _i64), _np_ptr(quoted, _u8),
    )
    return rcs, off, ln, quoted


def _py_csv_unescape(cell: bytes, qb: bytes) -> bytes:
    """Mirror of pn_csv_unescape: '""' -> '"' inside the quoted body; the lone
    closing quote is dropped and the tail after it is copied verbatim."""
    out = bytearray()
    in_quotes = True
    i, n = 0, len(cell)
    while i < n:
        c = cell[i : i + 1]
        if in_quotes and c == qb:
            if cell[i + 1 : i + 2] == qb:
                out += qb
                i += 2
                continue
            in_quotes = False
            i += 1
        else:
            out += c
            i += 1
    return bytes(out)


def csv_unescape(cell: bytes, quote: str = '"') -> bytes:
    dll = lib()
    if dll is None:
        return _py_csv_unescape(cell, quote.encode())
    out = ctypes.create_string_buffer(len(cell))
    n = dll.pn_csv_unescape(
        _as_u8_ptr(cell), len(cell), ord(quote), ctypes.cast(out, _p_u8)
    )
    return out.raw[:n]


def csv_rows(data: bytes, delim: str = ",", quote: str = '"') -> List[List[str]]:
    """Decode a CSV buffer into rows of str (skipping zero-cell rows)."""
    rcs, off, ln, quoted = csv_scan(data, delim, quote)
    qb = quote.encode()
    rows: List[List[str]] = []
    for r in range(len(rcs) - 1):
        lo, hi = rcs[r], rcs[r + 1]
        if lo == hi:
            continue
        row = []
        for c in range(lo, hi):
            cell = data[off[c] : off[c] + ln[c]]
            if quoted[c] and qb in cell:
                cell = _py_csv_unescape(cell, qb)
            row.append(cell.decode("utf-8", errors="replace"))
        rows.append(row)
    return rows


# ---------------------------------------------------------------- typed parse


def parse_int64(
    data: bytes, off: np.ndarray, ln: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    dll = lib()
    if dll is None:
        from . import fallback

        return fallback.parse_int64(data, off, ln)
    n = len(off)
    out = np.empty(n, dtype=np.int64)
    ok = np.empty(n, dtype=np.uint8)
    off = np.ascontiguousarray(off, dtype=np.int64)
    ln = np.ascontiguousarray(ln, dtype=np.int64)
    dll.pn_parse_int64(
        _as_u8_ptr(data), _np_ptr(off, _i64), _np_ptr(ln, _i64), n,
        _np_ptr(out, _i64), _np_ptr(ok, _u8),
    )
    return out, ok


def parse_float64(
    data: bytes, off: np.ndarray, ln: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    dll = lib()
    if dll is None:
        from . import fallback

        return fallback.parse_float64(data, off, ln)
    n = len(off)
    out = np.empty(n, dtype=np.float64)
    ok = np.empty(n, dtype=np.uint8)
    off = np.ascontiguousarray(off, dtype=np.int64)
    ln = np.ascontiguousarray(ln, dtype=np.int64)
    dll.pn_parse_float64(
        _as_u8_ptr(data), _np_ptr(off, _i64), _np_ptr(ln, _i64), n,
        _np_ptr(out, ctypes.c_double), _np_ptr(ok, _u8),
    )
    return out, ok


# ---------------------------------------------------------------- serialize

# column type tags shared with native/src/serialize.cc
COL_NONE, COL_BOOL, COL_INT64, COL_FLOAT64, COL_STR, COL_BYTES, COL_POINTER = range(7)


def serialize_rows(
    n_rows: int,
    col_types: Sequence[int],
    col_arrays: Sequence[object],
    col_nulls: Sequence[Optional[np.ndarray]],
) -> Tuple[bytes, np.ndarray]:
    """Serialize typed columns into per-row key-derivation buffers.

    ``col_arrays[c]``: np.int64/float64/uint8/uint64 array, or
    ``(blob: bytes, offsets: np.int64[n_rows+1])`` for str/bytes columns.
    Returns (buffer, row_offsets[n_rows+1]) matching
    internals.keys._serialize_value byte-for-byte."""
    dll = lib()
    if dll is None:
        from . import fallback

        return fallback.serialize_rows(n_rows, col_types, col_arrays, col_nulls)
    n_cols = len(col_types)
    types = np.asarray(col_types, dtype=np.uint8)
    data_ptrs = (ctypes.c_void_p * n_cols)()
    off_ptrs = (ctypes.c_void_p * n_cols)()
    null_ptrs = (ctypes.c_void_p * n_cols)()
    keepalive = []
    for c, t in enumerate(col_types):
        if t in (COL_STR, COL_BYTES):
            blob, offs = col_arrays[c]
            offs = np.ascontiguousarray(offs, dtype=np.int64)
            keepalive.append((blob, offs))
            data_ptrs[c] = ctypes.cast(ctypes.c_char_p(blob), ctypes.c_void_p)
            off_ptrs[c] = ctypes.c_void_p(offs.ctypes.data)
        elif t == COL_NONE:
            data_ptrs[c] = None
            off_ptrs[c] = None
        else:
            arr = np.ascontiguousarray(col_arrays[c])
            keepalive.append(arr)
            data_ptrs[c] = ctypes.c_void_p(arr.ctypes.data)
            off_ptrs[c] = None
        mask = col_nulls[c] if col_nulls else None
        if mask is not None:
            mask = np.ascontiguousarray(mask, dtype=np.uint8)
            keepalive.append(mask)
            null_ptrs[c] = ctypes.c_void_p(mask.ctypes.data)
        else:
            null_ptrs[c] = None
    row_offsets = np.empty(n_rows + 1, dtype=np.int64)
    needed = dll.pn_serialize_rows(
        n_rows, n_cols, _np_ptr(types, _u8),
        data_ptrs, off_ptrs, null_ptrs,
        ctypes.cast(None, _p_u8), 0, _np_ptr(row_offsets, _i64),
    )
    out = ctypes.create_string_buffer(max(int(needed), 1))
    dll.pn_serialize_rows(
        n_rows, n_cols, _np_ptr(types, _u8),
        data_ptrs, off_ptrs, null_ptrs,
        ctypes.cast(out, _p_u8), needed, _np_ptr(row_offsets, _i64),
    )
    return out.raw[:needed], row_offsets


def hash_rows(buf: bytes, row_offsets: np.ndarray) -> Optional[np.ndarray]:
    """xxh3-64 of each serialized row slice (the pn_serialize_rows layout);
    None when the library is absent or was built without xxhash — callers
    hash row-by-row in Python instead (internals/keys.ref_scalars_batch)."""
    dll = lib()
    if dll is None:
        return None
    n = len(row_offsets) - 1
    offs = np.ascontiguousarray(row_offsets, dtype=np.int64)
    out = np.empty(n, dtype=np.uint64)
    rc = dll.pn_hash_rows(
        _as_u8_ptr(buf), len(buf), _np_ptr(offs, _i64), n, _np_ptr(out, _u64)
    )
    if rc != 0:
        return None
    return out


# ---------------------------------------------------------------- crc / frames


def crc32(data: bytes, value: int = 0) -> int:
    dll = lib()
    if dll is None:
        import zlib

        return zlib.crc32(data, value) & 0xFFFFFFFF
    return int(dll.pn_crc32(_as_u8_ptr(data), len(data), value & 0xFFFFFFFF))


def frame_scan(data: bytes) -> Tuple[np.ndarray, np.ndarray, int]:
    """Scan concatenated [len][crc][payload] frames; returns
    (payload_offsets, payload_lengths, consumed_bytes) of the valid prefix."""
    dll = lib()
    if dll is None:
        from . import fallback

        return fallback.frame_scan(data)
    max_frames = max(len(data) // 8, 1)
    offs = np.empty(max_frames, dtype=np.int64)
    lens = np.empty(max_frames, dtype=np.int64)
    consumed = _i64(0)
    n = dll.pn_frame_scan(
        _as_u8_ptr(data), len(data), _np_ptr(offs, _i64), _np_ptr(lens, _i64),
        max_frames, ctypes.byref(consumed),
    )
    return offs[:n].copy(), lens[:n].copy(), consumed.value


# ---------------------------------------------------------------- sharding


def shard_rows(
    keys: np.ndarray, n_shards: int, shard_mask: int
) -> Tuple[np.ndarray, np.ndarray]:
    """(counts[n_shards], order[n]) — stable grouping of row indices by
    shard(key) = (key & mask) % n_shards."""
    dll = lib()
    if dll is None:
        from . import fallback

        return fallback.shard_rows(keys, n_shards, shard_mask)
    keys = np.ascontiguousarray(keys, dtype=np.uint64)
    counts = np.empty(n_shards, dtype=np.int64)
    order = np.empty(len(keys), dtype=np.int64)
    dll.pn_shard_rows(
        _np_ptr(keys, _u64), len(keys), n_shards, shard_mask,
        _np_ptr(counts, _i64), _np_ptr(order, _i64),
    )
    return counts, order


# ---------------------------------------------------------------- tokenizer


def encode_batch(
    blob: bytes,
    offsets: np.ndarray,
    vocab_size: int,
    reserved: int,
    max_length: int,
    widths: np.ndarray,
    width_cap: int,
    cls_id: int,
    sep_id: int,
    pad_id: int,
    rows: int,
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Rows ``CLS t... SEP`` of the ASCII texts of ``blob`` (``offsets``
    their boundaries), each truncated to ``max_length - 2`` tokens
    (models/tokenizer.py ``encode`` semantics), padded with ``pad_id`` to
    the shared width ``widths[longest row]`` (the caller's width rule as a
    table int64[max_length + 1], ``width_cap`` its largest entry) and to
    ``rows >= n_texts`` rows, in ONE native call that keeps the GIL for a
    short blob.  Returns (ids [rows, L] int32, mask [rows, L]), views of the
    head of buffers sized for the widest L, or None when the native path is
    unavailable or refuses the input (caller keeps the Python tokenizer)."""
    dll = _lib_for(len(blob))
    if dll is None:
        return None
    n_texts = len(offsets) - 1
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    widths = np.ascontiguousarray(widths, dtype=np.int64)
    if (
        n_texts < 1
        or rows < n_texts
        or len(widths) != max_length + 1
        # every boundary inside the blob (the call refuses ones that descend)
        or offsets[0] != 0
        or offsets[-1] != len(blob)
    ):
        return None
    # sized for the widest row, written at the width taken: the head of
    # each buffer IS the [rows, L] array, no copy
    ids = np.empty(rows * width_cap, dtype=np.int32)
    mask = np.empty(rows * width_cap, dtype=np.int32)
    width = _i64(0)
    rc = dll.pn_encode_batch(
        _as_u8_ptr(blob), _np_ptr(offsets, _i64), n_texts, vocab_size, reserved,
        max_length, _np_ptr(widths, _i64), width_cap, cls_id, sep_id, pad_id,
        rows, _np_ptr(ids, _i32), _np_ptr(mask, _i32), ctypes.byref(width),
    )
    if rc != 0:
        return None
    L = width.value
    return ids[: rows * L].reshape(rows, L), mask[: rows * L].reshape(rows, L)


def encode_pairs(
    blob: bytes,
    offsets: np.ndarray,
    a_slot: np.ndarray,
    b_slot: np.ndarray,
    vocab_size: int,
    reserved: int,
    budget: int,
    cls_id: int,
    sep_id: int,
    width: int,
) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Pair rows ``CLS a SEP b SEP`` over the DISTINCT ASCII texts of
    ``blob`` (``offsets`` their boundaries): pair i joins texts
    ``a_slot[i]`` and ``b_slot[i]``, truncated longest-first to ``budget``
    tokens (models/tokenizer.py ``encode`` semantics).  Returns (ids
    [n, width] int32 zero-padded, mask [n, width], lens int64[n]) with
    ``width >= budget + 3``, or None when the native path is unavailable
    (caller keeps the Python tokenizer)."""
    dll = _lib_for(len(blob))
    if dll is None:
        return None
    n_texts, n = len(offsets) - 1, len(a_slot)
    if width < budget + 3 or budget < 2:
        raise ValueError(f"width {width} cannot hold budget {budget} + 3")
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    a_slot = np.ascontiguousarray(a_slot, dtype=np.int64)
    b_slot = np.ascontiguousarray(b_slot, dtype=np.int64)
    if n and not (
        len(b_slot) == n
        and 0 <= min(a_slot.min(), b_slot.min())
        and max(a_slot.max(), b_slot.max()) < n_texts
    ):
        raise ValueError("pair slots must index the texts")
    tok_ids = np.empty(max(len(blob), 1), dtype=np.int32)
    tok_offsets = np.empty(n_texts + 1, dtype=np.int64)
    ids = np.zeros((n, width), dtype=np.int32)
    mask = np.zeros((n, width), dtype=np.int32)
    lens = np.empty(n, dtype=np.int64)
    rc = dll.pn_encode_pairs(
        _as_u8_ptr(blob), _np_ptr(offsets, _i64), n_texts, vocab_size, reserved,
        _np_ptr(a_slot, _i64), _np_ptr(b_slot, _i64), n, budget, cls_id, sep_id,
        width, _np_ptr(tok_ids, _i32), _np_ptr(tok_offsets, _i64),
        _np_ptr(ids, _i32), _np_ptr(mask, _i32), _np_ptr(lens, _i64),
    )
    if rc != 0:
        return None
    return ids, mask, lens


# ---------------------------------------------------------------- packing


def pack_rows(
    ids_b: np.ndarray,
    lens: np.ndarray,
    L: int,
    max_docs_per_row: int,
    row_buckets: np.ndarray,
    seg_buckets: np.ndarray,
    slot_ids: Optional[np.ndarray] = None,
    drop_slot: int = 0,
) -> Optional[tuple]:
    """``models.packing.pack_rows`` + ``pad_packed_rows`` (+ the rerank
    pipeline's ``pair_slot`` scatter when ``slot_ids`` is given) in ONE
    native call, same layout to the element.  ``row_buckets`` (ascending
    padded row counts, last >= n) and ``seg_buckets`` (segment width by the
    fullest row's sequence count, 1..max_docs_per_row) carry the callers'
    bucket rules.  Returns (ids [Rb, L], segments, positions, pair_slot
    [Rb * Sb] or None, row_of int64[n], seg_of int64[n], R, n_seg, Sb), or
    None when the native path is unavailable or refuses the input (caller
    keeps the Python body)."""
    ids_b = np.ascontiguousarray(ids_b, dtype=np.int32)
    dll = _lib_for(ids_b.nbytes)
    if dll is None:
        return None
    n = len(lens)
    row_buckets = np.ascontiguousarray(row_buckets, dtype=np.int64)
    seg_buckets = np.ascontiguousarray(seg_buckets, dtype=np.int64)
    if (
        n == 0
        or ids_b.ndim != 2
        or len(ids_b) != n
        or len(row_buckets) == 0
        or row_buckets[-1] < n
        or len(seg_buckets) != max_docs_per_row
    ):
        return None
    lens = np.ascontiguousarray(lens, dtype=np.int64)
    rows_cap = int(row_buckets[-1])
    # sized for the worst case (a row per sequence), sliced to [Rb, L]
    # below: a leading slice of a C array is itself contiguous
    ids = np.empty((rows_cap, L), dtype=np.int32)
    segments = np.empty((rows_cap, L), dtype=np.int32)
    positions = np.empty((rows_cap, L), dtype=np.int32)
    row_of = np.empty(n, dtype=np.int64)
    seg_of = np.empty(n, dtype=np.int64)
    dims = np.empty(4, dtype=np.int64)
    pair_slot = None
    slot_ptr = slot_out = None
    if slot_ids is not None:
        slot_ids = np.ascontiguousarray(slot_ids, dtype=np.int32)
        if len(slot_ids) != n:
            raise ValueError("one slot id per sequence")
        pair_slot = np.empty(rows_cap * int(seg_buckets[-1]), dtype=np.int32)
        slot_ptr, slot_out = _np_ptr(slot_ids, _i32), _np_ptr(pair_slot, _i32)
    rc = dll.pn_pack_rows(
        _np_ptr(ids_b, _i32), n, ids_b.shape[1], _np_ptr(lens, _i64), L,
        max_docs_per_row, _np_ptr(row_buckets, _i64), len(row_buckets),
        _np_ptr(seg_buckets, _i64), slot_ptr, drop_slot,
        _np_ptr(ids, _i32), _np_ptr(segments, _i32), _np_ptr(positions, _i32),
        slot_out, _np_ptr(row_of, _i64), _np_ptr(seg_of, _i64),
        _np_ptr(dims, _i64),
    )
    if rc != 0:
        return None
    R, n_seg, Rb, Sb = dims.tolist()
    if pair_slot is not None:
        pair_slot = pair_slot[: Rb * Sb]
    return (
        ids[:Rb], segments[:Rb], positions[:Rb], pair_slot,
        row_of, seg_of, R, n_seg, Sb,
    )
