"""Data (host): the device path of ``FullBatchLoader.fill_indices`` (span
``loader.gather``, histogram ``loader.gather_s``: the upload of the index
window and the dispatch of the two gather programs), per train step of
the window; on the Prefetcher's worker thread where one runs."""

from benchmark import span_metrics

LAYER = "Data (host)"
UNIT = "us"
MOVES = "train_images_per_s"
SOURCE = "program_span"


def read(context):
    return span_metrics.per_train_step(context, "loader.gather_s", 1e6)
