"""Checkpoint: the Snapshotter's own unit timer over the whole run, per
save (registry counter ``snapshot.exports``).  The whole run, not the
window: the cell's snapshot settings put the one save into set-up.
Nothing where the run saved nothing."""

LAYER = "Checkpoint"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "program_span"


def read(context):
    saves = context["registry_whole_run"].get("snapshot.exports", 0)
    row = context["units_whole_run"].get(context["snapshotter_unit"])
    if not saves or not row:
        return None
    return 1e3 * row.get("run", 0.0) / saves
