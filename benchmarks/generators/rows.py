"""Criteo-shaped rows from a seed: the benchmark's own generator.

A copy of the idea of ``scripts/gen_synth.py`` (zipf ranks per field, a
planted logistic model, fixed-width libffm text, everything decided by
the seed) with the key space made to match tables of 2^24..2^29 rows:

* 13 "integer" fields of ``int_vocab`` bucket ids each and 26
  categorical fields whose vocabularies are log-spaced from
  ``cat_vocab_min`` to ``cat_vocab_max`` — about 10^8 ids in all with
  the defaults, the order of the Criteo 1 TB click logs;
* within a field, rank r (1-based) has weight r^-a.  The first ``head``
  ranks are drawn from that exact pmf; the rest of the vocabulary is one
  bucket of mass ``integral_{head+.5}^{V+.5} x^-a dx`` (the midpoint rule
  for the remaining sum) inverted in closed form, so no table is as long
  as a vocabulary;
* the planted weight of an id is a pure function of (seed, global id):
  no ``[fields, vocab]`` array exists;
* a token is ``FF:XXXXXXXXXX:1 `` — two-digit field, ten-digit global id,
  binary value, 16 bytes so that it is written as two machine words — and
  the program hashes the ten digits as a string.

``keys`` is the benchmark's own MurmurHash64A of those ten digits, so a
client (the serve cells) and the geometry rule can compute table rows
without the program's parser; ``benchmarks/tests/test_generators.py``
holds it to ``native/parser.cc``.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

TOKEN_W = 16  # b"FF:XXXXXXXXXX:1 "
_M = np.uint64(0xC6A4A7935BD1E995)  # MurmurHash64A
_R = np.uint64(47)
_MASK = (1 << 64) - 1


@dataclasses.dataclass(frozen=True)
class RowSpec:
    int_fields: int = 13
    int_vocab: int = 128
    cat_fields: int = 26
    cat_vocab_min: int = 100
    cat_vocab_max: int = 40_000_000
    zipf_a: float = 1.2
    head: int = 4096
    w_scale: float = 0.22
    bias: float = -1.0

    @classmethod
    def from_params(cls, params: dict) -> "RowSpec":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(params) - known
        if unknown:
            raise ValueError(f"unknown row parameters: {sorted(unknown)}")
        return cls(**params)

    @property
    def fields(self) -> int:
        return self.int_fields + self.cat_fields

    def vocabs(self) -> np.ndarray:
        cat = np.round(np.geomspace(
            self.cat_vocab_min, self.cat_vocab_max, self.cat_fields
        )).astype(np.int64)
        ints = np.full(self.int_fields, self.int_vocab, np.int64)
        return np.concatenate([ints, cat])


class RowGenerator:
    """Rows of one RowSpec under one seed.  ``draw`` is a pure function of
    (seed, stream): the same stream gives the same rows whatever was drawn
    before, so shards can be made on several threads."""

    def __init__(self, spec: RowSpec, seed: int):
        if spec.zipf_a == 1.0:
            raise ValueError("zipf_a must differ from 1 (closed-form tail)")
        self.spec = spec
        self.seed = int(seed)
        self.vocab = spec.vocabs()
        if int(self.vocab.sum()) >= 2**32 or spec.fields > 99:
            raise ValueError("ids must fit 32 bits, fields two digits")
        # global id = offset of the field + rank within it (0-based)
        self.offset = np.concatenate([[0], np.cumsum(self.vocab)[:-1]])
        self._head = np.minimum(self.vocab, spec.head)
        a = spec.zipf_a
        # Walker alias tables per field over its head ranks plus, where the
        # vocabulary is longer, one last bucket that stands for the tail
        self._alias: list[tuple[np.ndarray, np.ndarray]] = []
        self._tail_lo = np.ones(spec.fields)
        self._tail_hi = np.ones(spec.fields)
        for f, (v, k) in enumerate(zip(self.vocab, self._head)):
            mass = np.arange(1, k + 1, dtype=np.float64) ** -a
            if v > k:
                lo, hi = (k + 0.5) ** (1 - a), (v + 0.5) ** (1 - a)
                self._tail_lo[f], self._tail_hi[f] = lo, hi
                mass = np.append(mass, (lo - hi) / (a - 1))
            self._alias.append(_alias_table(mass / mass.sum()))

    def draw(self, n: int, stream: tuple[int, ...]) -> tuple[np.ndarray, np.ndarray]:
        """(global ids int64 [n, fields], labels uint8 [n]).  Works through
        the rows in pieces small enough to stay in the cache."""
        rng = np.random.default_rng([self.seed, *stream])
        gid = np.empty((n, self.spec.fields), np.int64)
        labels = np.empty(n, np.uint8)
        for lo in range(0, n, PIECE):
            hi = min(lo + PIECE, n)
            gid[lo:hi], labels[lo:hi] = self._draw_piece(rng, hi - lo)
        return gid, labels

    def _draw_piece(self, rng: np.random.Generator, n: int):
        spec = self.spec
        u = rng.random((spec.fields, n))
        t = rng.random((spec.fields, n))
        rank = np.empty((spec.fields, n), np.int64)
        inv = 1.0 / (1.0 - spec.zipf_a)
        for f, (prob, alias) in enumerate(self._alias):
            y = u[f] * len(prob)
            k = y.astype(np.int64)
            k = np.where(y - k < prob[k], k, alias[k])
            head = self._head[f]
            if self.vocab[f] > head:  # bucket ``head`` is the tail
                lo, hi = self._tail_lo[f], self._tail_hi[f]
                x = (lo - t[f] * (lo - hi)) ** inv
                tail = np.clip(
                    np.floor(x + 0.5).astype(np.int64), head + 1, self.vocab[f]
                ) - 1
                k = np.where(k >= head, tail, k)
            rank[f] = k
        gid = (rank + self.offset[:, None]).T
        logit = self.planted_weights(gid).sum(axis=1) + spec.bias
        labels = rng.random(n) < 1.0 / (1.0 + np.exp(-logit))
        return gid, labels.astype(np.uint8)

    def planted_weights(self, gid: np.ndarray) -> np.ndarray:
        """float32 weight of each global id: a 64-bit mix of (seed, id),
        its two halves summed (triangular, unit variance after scaling)
        times ``w_scale``."""
        with np.errstate(over="ignore"):
            x = gid.astype(np.uint64) + np.uint64(
                ((self.seed + 1) * 0x9E3779B97F4A7C15) & _MASK
            )
            x *= np.uint64(0xBF58476D1CE4E5B9)
            x ^= x >> np.uint64(29)
            x *= np.uint64(0x94D049BB133111EB)
            x ^= x >> np.uint64(32)
        s = (x & np.uint64(0xFFFFFFFF)) + (x >> np.uint64(32))
        z = s.astype(np.float32) * np.float32(2.0**-32) - np.float32(1.0)
        return z * np.float32(math.sqrt(6.0) * self.spec.w_scale)

    # -- the two forms a row leaves in --------------------------------------

    def text(self, gid: np.ndarray, labels: np.ndarray) -> bytes:
        """libffm lines, fixed width, no per-line Python: every token is two
        little-endian 8-byte words, ``FF:XXXXX`` and ``XXXXX:1 ``."""
        n, fields = gid.shape
        hi5, lo5 = _groups5(gid)
        tok = np.empty((n, fields, 2), np.uint64)
        f = np.arange(fields, dtype=np.uint64)
        field3 = (
            (48 + f // 10) | ((48 + f % 10) << np.uint64(8))
            | np.uint64(58 << 16)
        )  # b"FF:"
        tok[:, :, 0] = field3 | (_PACKED5[hi5] << np.uint64(24))
        tok[:, :, 1] = _PACKED5[lo5] | np.uint64(
            (58 << 40) | (49 << 48) | (32 << 56)
        )  # b":1 "
        buf = np.empty((n, 2 + fields * TOKEN_W), np.uint8)
        buf[:, 0] = 48 + labels
        buf[:, 1] = 9  # tab
        buf[:, 2:] = tok.view(np.uint8).reshape(n, fields * TOKEN_W)
        buf[:, -1] = 10  # the last token's space becomes the newline
        return buf.tobytes()

    def keys(self, gid: np.ndarray, table_size: int, hash_seed: int = 0) -> np.ndarray:
        """Table rows (before any hot remap) the program's hash-mode parser
        gives these ids: MurmurHash64A of the ten ASCII digits, mod T."""
        if len(gid) > PIECE:
            return np.concatenate([
                self.keys(gid[lo : lo + PIECE], table_size, hash_seed)
                for lo in range(0, len(gid), PIECE)
            ])
        # the ten digits as murmur reads them: one 8-byte word, two tail bytes
        hi5, lo5 = _groups5(gid)
        word = _PACKED5[hi5] | (
            (_PACKED5[lo5] & np.uint64(0xFFFFFF)) << np.uint64(40)
        )
        tail = _PACKED5[lo5] >> np.uint64(24)
        with np.errstate(over="ignore"):
            h = np.uint64((hash_seed ^ ((10 * int(_M)) & _MASK)) & _MASK)
            k = word * _M
            k ^= k >> _R
            k *= _M
            h = (h ^ k) * _M
            h = (h ^ tail) * _M
            h ^= h >> _R
            h *= _M
            h ^= h >> _R
        return (h % np.uint64(table_size)).astype(np.int64)


PIECE = 8192  # rows drawn at a time: the temporaries stay in the cache


def _alias_table(p: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Walker/Vose alias table of a pmf: (prob, alias), one uniform a draw."""
    n = len(p)
    scaled = (p * n).tolist()
    prob, alias = [1.0] * n, list(range(n))
    small = [i for i, v in enumerate(scaled) if v < 1.0]
    large = [i for i, v in enumerate(scaled) if v >= 1.0]
    while small and large:
        s, l = small.pop(), large.pop()
        prob[s], alias[s] = scaled[s], l
        scaled[l] += scaled[s] - 1.0
        (small if scaled[l] < 1.0 else large).append(l)
    return np.asarray(prob), np.asarray(alias, np.int64)


def _packed5() -> np.ndarray:
    """uint64 [100000]: the five ASCII digits of 00000..99999 as one
    little-endian integer (first digit in the lowest byte)."""
    v = np.arange(100_000, dtype=np.uint64)
    out = np.zeros(100_000, np.uint64)
    for i in range(5):
        digit = (v // np.uint64(10 ** (4 - i))) % np.uint64(10)
        out |= (np.uint64(48) + digit) << np.uint64(8 * i)
    return out


_PACKED5 = _packed5()


def _groups5(gid: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """An id < 2^32 as its upper and lower five decimal digits, in 32-bit
    unsigned arithmetic (numpy's signed 64-bit ``%`` is several times
    slower)."""
    g = gid.astype(np.uint32)
    hi = g // np.uint32(100_000)
    return hi, g - hi * np.uint32(100_000)


def write_text_shards(
    gen: RowGenerator, prefix: str, shards: int, rows_per_shard: int,
) -> list[str]:
    """``<prefix>-%05d`` libffm text shards.  Shard s is drawn PIECE rows at
    a time, piece c from stream (s, c).  One thread a shard: numpy releases
    the interpreter lock in its inner loops only, and more threads than that
    were slower."""
    from concurrent.futures import ThreadPoolExecutor

    def one(s: int) -> str:
        path = f"{prefix}-{s:05d}"
        with open(path, "wb", buffering=1 << 22) as f:
            for c, lo in enumerate(range(0, rows_per_shard, PIECE)):
                n = min(PIECE, rows_per_shard - lo)
                f.write(gen.text(*gen.draw(n, (s, c))))
        return path

    with ThreadPoolExecutor(shards) as ex:
        return list(ex.map(one, range(shards)))
