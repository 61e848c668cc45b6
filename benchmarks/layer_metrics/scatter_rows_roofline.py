"""Share of the HBM roofline the step's scatter-adds reach: the bytes of
gradient-buffer rows a step's scatter-adds read and write
(``scatter_row_bytes_per_step`` of the ``wire`` row: two row moves a padded
slot, an opted-out table's hot slots among them) over 819 GB/s over the
device time under ``xf.scatter``, which also holds the buffers' zeroing
(``gather_rows_roofline.share`` is the arithmetic).  A scatter-add of
10-word rows pays ~100 ns a slot whatever the bytes; FFM's rows are 640 B."""

from benchmarks.layer_metrics import gather_rows_roofline

LAYER, UNIT, MOVES, SOURCE = "step", "%", "train_examples_per_s", "device_trace"


def read(run: dict):
    return gather_rows_roofline.share(
        run, "scatter_row_bytes_per_step", "xf.scatter"
    )
