"""Fused step (device): the share of the causal (q tile, k tile) pairs
of the attention kernels in which some query kept a key — the program's
``sparse.occupied_tiles`` counter over the window ÷ its
``sparse.causal_tiles`` (the tiles a causal mask holds, counted by the
same layers and steps), read as ``moe_buffer_fill_pct.train`` reads its
counters.  What the rest saves is what a selection-aware kernel skips;
a seeded indexer scatters a query's keys over its whole prefix, so this
reads near 100 % where a trained one clusters them.  Nothing in an
untraced run, or where the program counts no such tiles."""

LAYER = "Fused step (device)"
UNIT = "%"
MOVES = "train_images_per_s"
SOURCE = "program_counter"


def read(context):
    if context["trace"] is None:
        return None
    registry = context["registry"]
    causal = registry.get("sparse.causal_tiles")
    if not causal or "sparse.occupied_tiles" not in registry:
        return None
    return 100.0 * registry["sparse.occupied_tiles"] / causal
