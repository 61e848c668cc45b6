"""ctypes bindings for the native parser (no pybind11 in this image —
plain C ABI + ctypes, the same "embed as a library" shape the
reference's C API intended, c_api.h:26-41).

The shared library is built on demand with g++ (see build.py) and
cached next to the sources.  If it cannot be built or loaded (no
toolchain), ``available()`` is False and callers fall back to the
pure-Python parser — roughly 100x slower, so the failure is reported
once on stderr with the compiler's own error; ``chip_smoke.py`` treats
it as a failure, because every recorded rate assumes the native parser.
"""

from __future__ import annotations

import ctypes
import subprocess
import sys
import threading

import numpy as np

from xflow_tpu.io.batch import ParsedBlock

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_load_failed = False
_has_dict_encode = False


def load_library() -> ctypes.CDLL | None:
    global _lib, _load_failed
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        try:
            from xflow_tpu.native.build import build_if_needed

            path = build_if_needed()
            lib = ctypes.CDLL(str(path))
            _bind(lib)
        except (OSError, AttributeError, subprocess.CalledProcessError) as e:
            _load_failed = True
            detail = (getattr(e, "stderr", None) or str(e)).strip()
            print(
                "xflow_tpu.native: parser library unavailable, using the "
                f"pure-Python parser (~100x slower): {type(e).__name__}: "
                f"{detail}",
                file=sys.stderr,
            )
            return None
        _lib = lib
        return _lib


def _bind(lib: ctypes.CDLL) -> None:
    """Declare signatures; raises (caught by load_library) if a symbol
    is missing — e.g. a stale cached .so from an older source version
    whose mtime check passed (equal-mtime extraction)."""
    lib.xf_murmur64.restype = ctypes.c_uint64
    lib.xf_murmur64.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.c_uint64,
    ]
    lib.xf_parse_block_values.restype = ctypes.c_int64
    lib.xf_parse_block_values.argtypes = [
        ctypes.c_char_p,  # data
        ctypes.c_int64,  # len
        ctypes.c_int64,  # table_size
        ctypes.c_int,  # hash_mode
        ctypes.c_uint64,  # seed
        ctypes.c_int64,  # numeric_fields
        ctypes.POINTER(ctypes.c_float),  # labels
        ctypes.c_int64,  # max_rows
        ctypes.POINTER(ctypes.c_int64),  # row_ptr
        ctypes.POINTER(ctypes.c_int64),  # keys
        ctypes.POINTER(ctypes.c_int32),  # slots
        ctypes.POINTER(ctypes.c_float),  # vals
        ctypes.c_int64,  # max_nnz
        ctypes.POINTER(ctypes.c_int64),  # out_nnz
    ]
    f32p = ctypes.POINTER(ctypes.c_float)
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    # Optional (added after the first shipped .so): a cached library
    # missing it must still serve the parser/pack fast paths, so bind
    # it best-effort instead of letting a missing symbol fail _bind.
    global _has_dict_encode
    try:
        lib.xf_dict_encode.restype = ctypes.c_int64
        lib.xf_dict_encode.argtypes = [
            i64p,  # keys
            ctypes.c_int64,  # n
            ctypes.c_int64,  # dict_cap
            i64p,  # uniq_out
            ctypes.POINTER(ctypes.c_uint32),  # code_out
        ]
        _has_dict_encode = True
    except AttributeError:
        _has_dict_encode = False
    lib.xf_pack_batch.restype = ctypes.c_int64
    lib.xf_pack_batch.argtypes = [
        i64p,  # row_ptr
        f32p,  # labels_in
        i64p,  # keys_in
        i32p,  # slots_in
        f32p,  # vals_in
        ctypes.c_int64,  # start
        ctypes.c_int64,  # end
        ctypes.c_int64,  # batch_size
        i32p,  # remap (nullable)
        ctypes.c_int64,  # hot_size
        ctypes.c_int64,  # hot_nnz
        ctypes.c_int64,  # cold_nnz
        i32p, i32p, f32p, f32p,  # keys, slots, vals, mask
        i32p, i32p, f32p, f32p,  # hot_keys/slots/vals/mask (nullable)
        f32p,  # labels
        f32p,  # weights
    ]


def available() -> bool:
    return load_library() is not None


def has_dict_encode() -> bool:
    return load_library() is not None and _has_dict_encode


def native_dict_encode(
    keys: np.ndarray, dict_cap: int
) -> tuple[np.ndarray, np.ndarray]:
    """Drop-in replacement for io.compact.dedup_select's numpy path
    (same selected SET by construction; dictionary order differs —
    parity enforced by tests/test_compact.py)."""
    lib = load_library()
    assert lib is not None and _has_dict_encode, "xf_dict_encode unavailable"
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    n = len(keys)
    uniq = np.empty(dict_cap, np.int64)
    codes = np.empty(n, np.uint32)
    nd = lib.xf_dict_encode(
        _ptr(keys, ctypes.c_int64),
        n,
        dict_cap,
        _ptr(uniq, ctypes.c_int64),
        _ptr(codes, ctypes.c_uint32),
    )
    if nd < 0:
        raise MemoryError("xf_dict_encode: allocation failed")
    return uniq[:nd].copy(), codes


def native_murmur64(data: bytes, seed: int = 0) -> int:
    lib = load_library()
    assert lib is not None, "native library unavailable"
    return int(lib.xf_murmur64(data, len(data), seed))


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def native_parse_block(
    data: bytes,
    table_size: int,
    hash_mode: bool = True,
    hash_seed: int = 0,
    numeric_fields: int = 0,
) -> ParsedBlock:
    """Drop-in replacement for io.libffm.parse_block (parity enforced by
    tests/test_native.py)."""
    lib = load_library()
    assert lib is not None, "native library unavailable"
    # Keys must survive the downstream int32 batch cast (xf_pack_batch);
    # Config guards table_size_log2 <= 30 on the CLI path, but this
    # entry point is callable directly (round-2 advisor finding).
    # table_size == 0 = no reduction (full 64-bit keys for the binary
    # block cache / collision accounting — never fed to pack directly).
    if table_size != 0 and not 0 < table_size <= (1 << 31):
        raise ValueError(
            f"table_size {table_size} out of range (0, 2^31] — parsed "
            "keys must fit int32 batch arrays (0 = keep full keys)"
        )
    # capacity bounds: every sample has one line; every feature token has
    # exactly 2 of the block's ':' bytes
    max_rows = data.count(b"\n") + 1
    max_nnz = data.count(b":") // 2 + 1
    labels = np.empty(max_rows, dtype=np.float32)
    row_ptr = np.empty(max_rows + 1, dtype=np.int64)
    keys = np.empty(max_nnz, dtype=np.int64)
    slots = np.empty(max_nnz, dtype=np.int32)
    vals = np.empty(max_nnz, dtype=np.float32)
    out_nnz = np.zeros(1, dtype=np.int64)
    n_rows = lib.xf_parse_block_values(
        data,
        len(data),
        table_size,
        1 if hash_mode else 0,
        hash_seed,
        numeric_fields,
        _ptr(labels, ctypes.c_float),
        max_rows,
        _ptr(row_ptr, ctypes.c_int64),
        _ptr(keys, ctypes.c_int64),
        _ptr(slots, ctypes.c_int32),
        _ptr(vals, ctypes.c_float),
        max_nnz,
        _ptr(out_nnz, ctypes.c_int64),
    )
    if n_rows < 0:
        raise RuntimeError("native parser capacity overflow (bound bug)")
    nnz = int(out_nnz[0])
    return ParsedBlock(
        labels=labels[:n_rows].copy(),
        row_ptr=row_ptr[: n_rows + 1].copy(),
        keys=keys[:nnz].copy(),
        slots=slots[:nnz].copy(),
        vals=vals[:nnz].copy(),
    )


def native_pack_batch(
    block: ParsedBlock,
    start: int,
    end: int,
    batch_size: int,
    max_nnz: int,
    hot_size: int = 0,
    hot_nnz: int = 0,
    remap: np.ndarray | None = None,
):
    """Drop-in replacement for io.batch.pack_batch with the frequency
    remap folded in (parity enforced by tests/test_native.py).  ``block``
    must hold RAW (un-remapped) keys when ``remap`` is given."""
    from xflow_tpu.io.batch import Batch

    lib = load_library()
    assert lib is not None, "native library unavailable"
    n = end - start
    assert 0 < n <= batch_size
    kh = hot_nnz if hot_size else 0
    row_ptr = np.ascontiguousarray(block.row_ptr, dtype=np.int64)
    labels_in = np.ascontiguousarray(block.labels, dtype=np.float32)
    keys_in = np.ascontiguousarray(block.keys, dtype=np.int64)
    slots_in = np.ascontiguousarray(block.slots, dtype=np.int32)
    vals_in = np.ascontiguousarray(block.vals, dtype=np.float32)
    if remap is not None:
        remap = np.ascontiguousarray(remap, dtype=np.int32)

    keys = np.empty((batch_size, max_nnz), np.int32)
    slots = np.empty((batch_size, max_nnz), np.int32)
    vals = np.empty((batch_size, max_nnz), np.float32)
    mask = np.empty((batch_size, max_nnz), np.float32)
    hot_keys = np.empty((batch_size, kh), np.int32)
    hot_slots = np.empty((batch_size, kh), np.int32)
    hot_vals = np.empty((batch_size, kh), np.float32)
    hot_mask = np.empty((batch_size, kh), np.float32)
    labels = np.empty(batch_size, np.float32)
    weights = np.empty(batch_size, np.float32)
    null_i32 = ctypes.POINTER(ctypes.c_int32)()
    rc = lib.xf_pack_batch(
        _ptr(row_ptr, ctypes.c_int64),
        _ptr(labels_in, ctypes.c_float),
        _ptr(keys_in, ctypes.c_int64),
        _ptr(slots_in, ctypes.c_int32),
        _ptr(vals_in, ctypes.c_float),
        start,
        end,
        batch_size,
        _ptr(remap, ctypes.c_int32) if remap is not None else null_i32,
        hot_size if kh else 0,
        kh,
        max_nnz,
        _ptr(keys, ctypes.c_int32),
        _ptr(slots, ctypes.c_int32),
        _ptr(vals, ctypes.c_float),
        _ptr(mask, ctypes.c_float),
        _ptr(hot_keys, ctypes.c_int32),
        _ptr(hot_slots, ctypes.c_int32),
        _ptr(hot_vals, ctypes.c_float),
        _ptr(hot_mask, ctypes.c_float),
        _ptr(labels, ctypes.c_float),
        _ptr(weights, ctypes.c_float),
    )
    if rc == -2:
        raise ValueError(
            "pack_batch: a (remapped) key exceeds int32 — table_size or "
            "remap values too large for the int32 batch arrays"
        )
    if rc < 0:
        raise RuntimeError(f"native pack_batch failed (rc={rc})")
    if not kh:
        return Batch(
            keys=keys, slots=slots, vals=vals, mask=mask,
            labels=labels, weights=weights,
        )
    return Batch(
        keys=keys, slots=slots, vals=vals, mask=mask,
        labels=labels, weights=weights,
        hot_keys=hot_keys, hot_slots=hot_slots,
        hot_vals=hot_vals, hot_mask=hot_mask,
    )
