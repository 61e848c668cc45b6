"""FiBiNET's cell as data: the operations its reference and its roofline
reader count at the paper's Criteo sizes, and the files the manifest finds for
it by name."""

from benchmarks.harness import costs, manifest
from benchmarks.layer_metrics import bilinear_ms_per_step, bilinear_mxu_roofline
from benchmarks.reference import fibinet_criteo

DOC = manifest.load()
CELL = "fibinet_tb.train_packed"
# the paper's sizes as the program's dense arrays state them: D = 10, r = 3,
# 780 pairs of 40 field buckets on two towers, three hidden layers of 400
SHAPES = {
    "senet_w1": (40, 13), "senet_w2": (13, 40),
    "bil_p": (780, 10, 10), "bil_q": (780, 10, 10),
    "w1": (15600, 400), "b1": (400,), "w2": (400, 400), "b2": (400,),
    "w3": (400, 400), "b3": (400,), "w_out": (400, 1), "b_out": (1,),
}
PEAKS = {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}


def test_the_reference_declares_the_hidden_and_the_output_products():
    """15 600 x 400 + 2 x 400 x 400 + 400 = 6 560 400 multiply-adds an
    example; the pair products are the roofline reader's, not these."""
    products = fibinet_criteo.matmuls(SHAPES)
    assert products == [(15600, 400), (400, 400), (400, 400), (400, 1)]
    fields = {"table_size_log2": 25, "hot_size_log2": 14, "batch_size": 16384}
    got = costs.train_step(fields, fibinet_criteo.TABLES, 16384 * 38.5, 0.9, products)
    assert got["flops"] == 6.0 * 16384 * 6_560_400  # 6.45e11


def test_the_roofline_reader_counts_the_block_from_the_files_fields():
    fields = manifest.config(DOC, manifest.cell(DOC, CELL)["config"])
    # 2 * 780 * 10 * 10 on two towers + the excitation's 2 * 40 * 13
    assert bilinear_mxu_roofline.bilinear_macs_per_example(fields) == 157_040
    assert bilinear_mxu_roofline.bilinear_flops(fields) == 6.0 * 16384 * 157_040
    run = {"fields": fields, "peaks": PEAKS, "trace": None}
    assert bilinear_mxu_roofline.read(run) is None  # no xf.bilinear time: nothing
    # a configuration without the block reports nothing either
    assert "senet_reduction" not in manifest.config(DOC, "xdeepfm_ftrl_criteo_tb")


def test_the_share_cannot_pass_100(monkeypatch):
    """Only the operations set the roofline (c's bytes are left out), so the
    least time the scope could take is 1.54e10 / 197e12 = 78 us: at the 30 ms
    it is expected to take the share reads a quarter of a percent, and under
    100 at any time the six float32 passes allow."""
    fields = manifest.config(DOC, manifest.cell(DOC, CELL)["config"])
    run = {"fields": fields, "peaks": PEAKS}
    for ms, want in ((30.0, 0.2612), (6 * 0.0784, 16.66)):
        monkeypatch.setattr(bilinear_ms_per_step, "read", lambda run, ms=ms: ms)
        share = bilinear_mxu_roofline.read(run)
        assert abs(share - want) < 0.01 * want and share < 100.0


def test_the_manifest_resolves_the_cells_files():
    entry = manifest.cell(DOC, CELL)
    assert (entry["chips"], entry["traffic"]) == (1, "replay_packed_zipf")
    config = manifest.config(DOC, entry["config"])  # sets no path selector
    assert not manifest.PATH_SELECTORS & set(config)
    assert manifest.reference(config["family"]) is fibinet_criteo
    assert config["reduced"].keys() == {"table_size_log2"}
    assert (config["emb_dim"], config["max_fields"]) == (10, 40)
    assert (config["senet_reduction"], config["deep_layers"], config["hidden_dim"]) == (3, 3, 400)
    assert fibinet_criteo.TABLES == {"w": 1, "emb": config["emb_dim"]}
    assert config["beta"] * config["batch_size"] == 1.0
    assert config["lambda2"] * config["batch_size"] == 10.0
    # the sparse half is xDeepFM's: the same rows, table, head and capacities
    other = manifest.config(DOC, "xdeepfm_ftrl_criteo_tb")
    for key in ("table_size_log2", "batch_size", "hot_size_log2", "max_nnz", "hot_nnz"):
        assert config[key] == other[key]
    mine = {m["name"] for m in manifest.metrics_of(DOC, "per_layer", CELL)}
    assert {
        "bilinear_ms_per_step", "bilinear_mxu_roofline", "dense_ms_per_step",
        "dense_matmul_roofline", "wire_slots_bytes_per_example",
    } <= mine
    assert not {"cin_ms_per_step", "cin_mxu_roofline", "attn_ms_per_step"} & mine
    for name in ("bilinear_ms_per_step", "bilinear_mxu_roofline"):
        only = next(m for m in DOC["per_layer"] if m["name"] == name)
        assert only["workloads"] == [CELL]
    assert manifest.layer_metric("bilinear_ms_per_step") is bilinear_ms_per_step
    assert bilinear_ms_per_step.SCOPE == "xf.bilinear"
