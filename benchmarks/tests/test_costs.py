"""``harness/costs.py``: what a train step has to move and to multiply, and
the share of the roofline a time is.  The numbers of the configurations that
have no dense parameters are pinned at what the module returned before it
learned of FLOPs (PR 37's tree, computed there): ``train_step_roofline`` of
their cells may not move by a digit."""

import json
import os

import pytest

from benchmarks.harness import costs, manifest
from benchmarks.reference import dcn, wide_deep

with open(os.path.join(manifest.BENCH_DIR, "harness", "peaks.json")) as f:
    PEAKS = json.load(f)["TPU v5 lite"]

# configuration, family -> (costs.train_step at 38.5 entries a row of which
# 0.83 hot, the step's seconds, roofline_share of them), as PR 37's tree has them
PINNED = {
    ("lr_ftrl_criteo_tb", "lr"): ({
        "hbm_bytes": 8600245370.88, "hbm_bytes_gather": 3447848.960000001,
        "hbm_bytes_scatter": 6862929.920000002, "hbm_bytes_dense_pass": 8589934592,
        "gather_slices": 857866.2400000002, "scatter_slices": 857866.2400000002,
        "devices": 1,
    }, 0.047238, 22.229794019125652),
    ("ffm_ftrl_criteo_tb", "ffm"): ({
        "hbm_bytes": 11022253096.960001, "hbm_bytes_gather": 79609528.32000002,
        "hbm_bytes_scatter": 138116464.64000005, "hbm_bytes_dense_pass": 10804527104,
        "gather_slices": 214466.56000000006, "scatter_slices": 214466.56000000006,
        "devices": 1,
    }, 0.11457, 11.746691533187263),
    ("fm_ftrl_criteo_tb", "fm"): ({
        "hbm_bytes": 47358599495.68, "hbm_bytes_gather": 38467010.56000001,
        "hbm_bytes_scatter": 75492229.12000002, "hbm_bytes_dense_pass": 47244640256,
        "gather_slices": 1715732.4800000004, "scatter_slices": 1715732.4800000004,
        "devices": 4,
    }, 0.2498, 5.7871204772951454),
}


def _fields(config: str) -> dict:
    doc = manifest.config_file(f"benchmarks/configs/{config}.json")
    return {k: v for k, v in doc.items() if k not in manifest.CONFIG_META}


@pytest.mark.parametrize("config, family", PINNED)
def test_a_table_only_configuration_costs_what_it_did(config, family):
    want, seconds, share = PINNED[config, family]
    fields = _fields(config)
    got = costs.train_step(
        fields, manifest.reference(family).TABLES,
        entries_per_step=fields["batch_size"] * 38.5, hot_share=0.83,
    )
    assert got == {**want, "flops": 0.0}  # to the last digit
    assert costs.roofline_share(
        got["hbm_bytes"], seconds, PEAKS, got["devices"], got["flops"]
    ) == share == costs.roofline_share(got["hbm_bytes"], seconds, PEAKS, got["devices"])


def test_a_dense_family_counts_its_matmuls_forward_and_backward():
    """The hidden layer and the head of wide&deep at 40 fields x 8, B = 16384:
    2 B k n operations forward, twice that backward."""
    fields = {"table_size_log2": 24, "hot_size_log2": 14, "batch_size": 16384}
    # the products are counted from the shapes of the program's dense arrays
    shapes = {"w1": (320, 1024), "b1": (1024,), "w2": (1024, 1), "b2": (1,)}
    assert wide_deep.matmuls(shapes) == [(320, 1024), (1024, 1)]
    assert wide_deep.matmuls({"w1": [256, 64], "w2": [64, 1]}) == [(256, 64), (64, 1)]
    crossed = {"cross_w": (2, 320), "cross_b": (2, 320), "w1": (320, 1024),
               "b1": (1024,), "w_out": (1344, 1), "b_out": (1,)}
    assert dcn.matmuls(crossed) == [(320, 1024), (1344, 1), (320, 1), (320, 1)]
    assert dcn.matmuls({**crossed, "cross_w": (1, 320)})[2:] == [(320, 1)]
    got = costs.train_step(fields, wide_deep.TABLES, 16384 * 38.5, 0.9, wide_deep.matmuls(shapes))
    assert got["flops"] == 6.0 * 16384 * (320 * 1024 + 1024)
    plain = costs.train_step(fields, wide_deep.TABLES, 16384 * 38.5, 0.9)
    assert {**got, "flops": 0.0} == plain


def test_the_roofline_is_the_slower_of_the_two_bounds():
    seconds = 1e-3
    by_bytes = costs.roofline_share(819e6, seconds, PEAKS)  # 1 ms of HBM traffic
    assert by_bytes == pytest.approx(100.0)
    assert costs.roofline_share(819e6, seconds, PEAKS, flops=197e9 / 2) == by_bytes
    assert costs.roofline_share(819e6 / 4, seconds, PEAKS, flops=197e9 / 2) == pytest.approx(50.0)
    assert costs.roofline_share(819e6 / 4, seconds, PEAKS, 4, flops=197e9 / 2) == pytest.approx(12.5)
