"""libffm text parsing with block streaming.

Behavioral spec is the reference's only production loader,
``load_minibatch_hash_data_fread`` (load_data_from_disk.cc:103-210):

* reads a fixed-size byte block per pass and carries the partial last
  line over to the next pass (:108-124);
* a line is ``label<SEP>fgid:fid:val ...`` — whitespace-separated
  feature tokens after the label;
* the label is binarized ``y > 1e-7 → 1`` (:131-134);
* ``fgid`` parses as an integer field/group id;
* in hash mode the ``fid`` token is hashed **as a string** and the
  value field is discarded — features are implicitly binary (:151);
  with ``numeric_fields`` > 0 (Config.numeric_fields; not the
  reference's) a token whose ``fgid`` lies in ``[0, numeric_fields)``
  keeps its value, parsed and range-checked as in numeric mode: libffm's
  ``field:index:value`` for a real-valued feature.  Every other token
  stays binary, and ``numeric_fields`` = 0 is the reference's loader;
* in numeric mode (reference loaders at :11-57) ``fid`` parses as an
  integer and ``val`` as a float and both are kept.

Differences from the reference, on purpose: the hash is MurmurHash64A,
not ``std::hash<string>`` (see hashing.py); malformed tokens are skipped
with a count rather than undefined behavior.
"""

from __future__ import annotations

import io as _stdio
from typing import BinaryIO, Iterator

import numpy as np

from xflow_tpu.io.batch import ParsedBlock
from xflow_tpu.io.hashing import murmur64_batch

LABEL_THRESHOLD = 1e-7  # reference: load_data_from_disk.cc:131-134


class BlockReader:
    """Streams a binary file in ~block_bytes chunks of whole lines,
    carrying the partial last line between reads (reference
    load_data_from_disk.cc:108-124)."""

    def __init__(self, f: BinaryIO, block_bytes: int):
        self._f = f
        self._block_bytes = max(int(block_bytes), 1)
        self._carry = b""

    def __iter__(self) -> Iterator[bytes]:
        while True:
            chunk = self._f.read(self._block_bytes)
            if not chunk:
                if self._carry:
                    carry, self._carry = self._carry, b""
                    yield carry
                return
            buf = self._carry + chunk
            cut = buf.rfind(b"\n")
            if cut == -1:
                self._carry = buf
                continue
            self._carry = buf[cut + 1 :]
            yield buf[: cut + 1]


def parse_block(
    data: bytes,
    table_size: int,
    hash_mode: bool = True,
    hash_seed: int = 0,
    numeric_fields: int = 0,
) -> ParsedBlock:
    """Parse one block of libffm lines into a CSR ParsedBlock.

    Keys are reduced modulo ``table_size`` (the TPU table is a dense
    array, unlike the reference's unbounded server-side hash map,
    ftrl.h:84).  ``table_size=0`` keeps FULL keys — the 64-bit hash
    (two's-complement int64 view) in hash mode, the raw fid in numeric
    mode — for the binary block cache (io/binary.py, table-size-
    independent) and collision accounting.
    """
    labels: list[float] = []
    row_ptr: list[int] = [0]
    slots: list[int] = []
    vals: list[float] = []
    tokens: list[bytes] = []  # fid tokens (hash mode)
    fids: list[int] = []  # numeric fids (no-hash mode)

    for line in data.split(b"\n"):
        line = line.strip()
        if not line:
            continue
        parts = line.split()
        try:
            y = float(parts[0])
        except ValueError:
            continue
        labels.append(1.0 if y > LABEL_THRESHOLD else 0.0)
        for tok in parts[1:]:
            pieces = tok.split(b":")
            if len(pieces) != 3:
                continue
            try:
                fgid = int(pieces[0])
            except ValueError:
                continue
            if not -(2**31) <= fgid < 2**31:
                continue  # slot arrays are int32; reject, never wrap
            if hash_mode:
                val = 1.0  # value field discarded: binary features
                if 0 <= fgid < numeric_fields:
                    # a numeric field's token keeps its value, under
                    # numeric mode's finite-in-float32 rule (below)
                    try:
                        val = float(pieces[2])
                    except ValueError:
                        continue
                    if not abs(val) < 3.4028235677973366e38:
                        continue
                tokens.append(pieces[1])
                vals.append(val)
            else:
                try:
                    fid = int(pieces[1])
                    val = float(pieces[2])
                except ValueError:
                    continue
                if not -(2**63) <= fid < 2**63:
                    continue  # keys are int64; reject, never wrap
                # reject values not finite IN FLOAT32: inf/nan literals
                # and "1e999"/"1e39"-style overflows the float32 cast
                # would silently turn into inf (round-1 weak point 8).
                # (2-2^-24)*2^127 is the exact round-to-nearest overflow
                # boundary; `not <` also rejects nan.  Native parser
                # matches exactly (parser.cc isfinite after narrowing).
                if not abs(val) < 3.4028235677973366e38:
                    continue
                fids.append(fid)
                vals.append(val)
            slots.append(fgid)
        row_ptr.append(len(slots))

    if hash_mode:
        hashed = murmur64_batch(tokens, seed=hash_seed)
        if table_size:
            keys = (hashed % np.uint64(table_size)).astype(np.int64)
        else:
            keys = hashed.view(np.int64)
    else:
        keys = np.asarray(fids, dtype=np.int64)
        if table_size:
            keys = keys % table_size

    return ParsedBlock(
        labels=np.asarray(labels, dtype=np.float32),
        row_ptr=np.asarray(row_ptr, dtype=np.int64),
        keys=keys,
        slots=np.asarray(slots, dtype=np.int32),
        vals=np.asarray(vals, dtype=np.float32),
    )


def parse_file(
    path: str, table_size: int, hash_mode: bool = True, hash_seed: int = 0,
    numeric_fields: int = 0,
) -> ParsedBlock:
    """Parse an entire file at once (reference ``load_all_*`` loaders,
    load_data_from_disk.cc:11-33,59-79)."""
    # whole-file test/tool helper — production streaming goes through
    # ShardLoader, which carries the loader.* sites (xf: ignore[XF018])
    with open(path, "rb") as f:
        return parse_block(
            f.read(), table_size, hash_mode, hash_seed, numeric_fields
        )


def open_block_stream(path: str, block_mib: int) -> BlockReader:
    # bare-stream helper for tools/tests — ShardLoader.iter_batches is
    # the chaos-covered production opener (xf: ignore[XF018])
    f: BinaryIO = open(path, "rb", buffering=_stdio.DEFAULT_BUFFER_SIZE)
    return BlockReader(f, block_mib << 20)
