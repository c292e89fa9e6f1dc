"""Fused step (host side): the FusedTrainer's unit timer over the
window, per minibatch it ran (train and eval): staging, dispatch, and
the wait for the device where the program waits."""

LAYER = "Fused step (host side)"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "program_span"


def read(context):
    row = context["units"].get(context["trainer_unit"])
    if not row or not row["runs"]:
        return None
    return 1e3 * row.get("run", 0.0) / row["runs"]
