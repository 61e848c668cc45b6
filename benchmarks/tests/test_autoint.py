"""AutoInt's cell as data: the operations its reference and its roofline
reader count at the paper's Criteo sizes, and the files the manifest finds for
it by name."""

from benchmarks.harness import costs, manifest
from benchmarks.layer_metrics import attn_ms_per_step, attn_mxu_roofline
from benchmarks.reference import autoint_criteo

DOC = manifest.load()
CELL = "autoint_tb.train_packed"
# the paper's sizes as the program's dense arrays state them: d = 16, three
# layers of 2 heads of 32 over 40 field buckets
SHAPES = {
    **{
        f"attn_{p}{n}": (16 if n == 1 else 64, 64)
        for n in (1, 2, 3) for p in "qkvr"
    },
    "w_out": (40 * 64, 1), "b_out": (1,),
}


def test_the_reference_counts_every_product_of_a_forward_pass():
    """Projections 4 * 40 * (16 + 64 + 64) * 64 = 1 474 560 multiply-adds an
    example, attention 3 * 2 * 2 * 40 * 40 * 32 = 614 400, output 2 560."""
    products = autoint_criteo.matmuls(SHAPES)
    assert len(products) == 3 * 6 + 1
    assert products[:6] == [(40 * 16, 64)] * 4 + [(2 * 40 * 32, 40), (2 * 40 * 40, 32)]
    assert products[-1] == (2560, 1)
    assert sum(k * n for k, n in products) == 2_091_520
    fields = {"table_size_log2": 25, "hot_size_log2": 14, "batch_size": 16384}
    got = costs.train_step(fields, autoint_criteo.TABLES, 16384 * 38.5, 0.9, products)
    assert got["flops"] == 6.0 * 16384 * 2_091_520


def test_the_roofline_reader_counts_the_block_without_the_output_product():
    fields = manifest.config(DOC, manifest.cell(DOC, CELL)["config"])
    assert attn_mxu_roofline.attn_macs_per_example(fields) == 2_088_960
    assert attn_mxu_roofline.attn_flops(fields) == 6.0 * 16384 * 2_088_960
    peaks = {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}
    run = {"fields": fields, "peaks": peaks, "trace": None}
    assert attn_mxu_roofline.read(run) is None  # no xf.attn time: nothing
    # a configuration without interacting layers reports nothing either
    other = manifest.config(DOC, "xdeepfm_ftrl_criteo_tb")
    assert "attn_heads" not in other


def test_the_manifest_resolves_the_cells_files():
    entry = manifest.cell(DOC, CELL)
    assert (entry["chips"], entry["traffic"]) == (1, "replay_packed_zipf")
    config = manifest.config(DOC, entry["config"])
    assert manifest.reference(config["family"]) is autoint_criteo
    assert config["reduced"].keys() == {"table_size_log2"}
    assert (config["emb_dim"], config["cross_layers"]) == (16, 3)
    assert (config["attn_heads"], config["attn_dim"]) == (autoint_criteo.HEADS, 32)
    assert autoint_criteo.TABLES == {"emb": config["emb_dim"]}
    assert config["beta"] * config["batch_size"] == 1.0
    assert config["lambda2"] * config["batch_size"] == 10.0
    mine = {m["name"] for m in manifest.metrics_of(DOC, "per_layer", CELL)}
    assert {"attn_ms_per_step", "attn_mxu_roofline", "dense_ms_per_step"} <= mine
    assert not {"cin_ms_per_step", "cin_mxu_roofline", "dense_matmul_roofline"} & mine
    for name in ("attn_ms_per_step", "attn_mxu_roofline"):
        only = next(m for m in DOC["per_layer"] if m["name"] == name)
        assert only["workloads"] == [CELL]
    assert manifest.layer_metric("attn_ms_per_step") is attn_ms_per_step
    assert attn_ms_per_step.SCOPE == "xf.attn"
