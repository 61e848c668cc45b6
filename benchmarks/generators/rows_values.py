"""Criteo-shaped rows whose integer fields carry VALUES: the stock generator
(``generators/rows.py``) with the same draws and the same global ids, so that
whatever counts table rows from ``draw`` and ``keys`` (the hot remap, the
capacity rule, ``train_cell``'s dropped share) counts what the program sees,
and with two differences in what a row says:

* the token of a numeric field f is ``FF:XXXXXXXXXX:<value>`` with value =
  log(1 + c), the transform DLRM's Criteo scripts apply to the raw integer
  features, printed in ten characters that read back to the same float32
  (checked for every count when the generator is built).  c is an integer
  count in ``[0, count_max]`` with a zipf tail, rank c + 1 of the continuous
  power law of exponent ``count_zipf_a`` on ``[1, count_max + 2)`` cut down
  to whole numbers, and a pure function of (seed, the row's own ids, field):
  ``text`` is handed ids and labels alone, so a row's values have to follow
  from those; no stream, no state, any thread;
* the label's planted logit reads the values: the planted weight of a numeric
  field's id (with ``int_vocab`` 1, the one id the field has: a draw from the
  seed) times ``u_scale / w_scale`` multiplies the field's value less the
  values' mean, where the stock rows add that weight itself.  So a model
  that reads values has something to learn from them; centred, the term
  leaves the mean logit where the stock rows have it (its spread raises the
  click rate from a quarter to three eighths at the mix's ``u_scale``).

Mix parameters (``values`` of a traffic mix, beside ``rows``): ``fields`` (the
numeric fields, ids ``0 .. fields - 1``: the rows' integer fields),
``count_zipf_a``, ``count_max``, ``u_scale``.
"""

from __future__ import annotations

import numpy as np

from benchmarks.generators.rows import _MASK, TOKEN_W, RowGenerator, RowSpec

VALUE_W = 10  # characters of a printed value
VALUE_TOKEN_W = TOKEN_W - 2 + VALUE_W + 1  # b"FF:XXXXXXXXXX:" + value + b" "
_GOLDEN = 0x9E3779B97F4A7C15


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer, on uint64 arrays."""
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


class ValueRowGenerator(RowGenerator):
    """``RowGenerator`` whose first ``values["fields"]`` fields carry
    log(1 + count) values."""

    def __init__(self, spec: RowSpec, seed: int, values: dict):
        super().__init__(spec, seed)
        unknown = set(values) - {"fields", "count_zipf_a", "count_max", "u_scale"}
        if unknown:
            raise ValueError(f"unknown value parameters: {sorted(unknown)}")
        self.value_fields = int(values["fields"])
        if not 0 < self.value_fields <= spec.int_fields:
            raise ValueError("the numeric fields are the rows' integer fields")
        self.count_max = int(values["count_max"])
        self._a = float(values["count_zipf_a"])
        self._u_over_w = float(values["u_scale"]) / spec.w_scale
        # every value a token can hold, as a number and as it is printed
        self._table = np.log1p(
            np.arange(self.count_max + 1, dtype=np.float64)
        ).astype(np.float32)
        printed = [f"{v:.8f}"[:VALUE_W].encode() for v in self._table.tolist()]
        back = np.asarray([float(p) for p in printed], np.float32)
        if not np.array_equal(back, self._table):
            raise ValueError("a printed value does not read back as it was")
        self._printed = np.frombuffer(b"".join(printed), np.uint8).reshape(
            -1, VALUE_W
        )
        # the mean value under the counts' law: what the planted term centres on
        edges = np.arange(1, self.count_max + 3, dtype=np.float64) ** (1 - self._a)
        mass = edges[:-1] - edges[1:]
        self.value_mean = float(
            (mass / mass.sum() * self._table.astype(np.float64)).sum()
        )

    def counts(self, gid: np.ndarray) -> np.ndarray:
        """int64 [n, value_fields]: each row's counts, from (seed, the row's
        ids, field)."""
        with np.errstate(over="ignore"):
            h = np.full(len(gid), (self.seed + 1) * _GOLDEN & _MASK, np.uint64)
            for column in gid.T.astype(np.uint64):
                h = _mix(h ^ column)
            f = np.arange(1, self.value_fields + 1, dtype=np.uint64)
            bits = _mix(h[:, None] + f[None, :] * np.uint64(_GOLDEN))
        u = (bits >> np.uint64(11)).astype(np.float64) * 2.0**-53
        top = (self.count_max + 2.0) ** (1 - self._a)
        x = (1.0 - u * (1.0 - top)) ** (1.0 / (1 - self._a))
        return np.clip(np.floor(x).astype(np.int64) - 1, 0, self.count_max)

    def values(self, gid: np.ndarray) -> np.ndarray:
        """float32 [n, value_fields]: log(1 + count), what the tokens hold."""
        return self._table[self.counts(gid)]

    def planted_weights(self, gid: np.ndarray) -> np.ndarray:
        w = super().planted_weights(gid)
        centred = self.values(gid) - np.float32(self.value_mean)
        w[:, : self.value_fields] *= centred * np.float32(self._u_over_w)
        return w

    def text(self, gid: np.ndarray, labels: np.ndarray) -> bytes:
        """libffm lines, fixed width: the stock line with ``:1`` of each
        numeric field's token replaced by ``:<value>``."""
        n, fields = gid.shape
        stock = np.frombuffer(super().text(gid, labels), np.uint8).reshape(n, -1)
        vf = self.value_fields
        cut = 2 + vf * TOKEN_W  # label, tab and the numeric fields' tokens
        tokens = np.empty((n, vf, VALUE_TOKEN_W), np.uint8)
        tokens[:, :, : TOKEN_W - 2] = stock[:, 2:cut].reshape(n, vf, TOKEN_W)[
            :, :, : TOKEN_W - 2
        ]
        tokens[:, :, TOKEN_W - 2 : -1] = self._printed[self.counts(gid)]
        tokens[:, :, -1] = 32  # space
        out = np.concatenate(
            [stock[:, :2], tokens.reshape(n, -1), stock[:, cut:]], axis=1
        )
        out[:, -1] = 10  # a row of numeric fields alone ends in a token of ours
        return out.tobytes()
