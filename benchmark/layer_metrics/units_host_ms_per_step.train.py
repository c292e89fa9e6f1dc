"""Entry: what the Workflow's own per-unit run timers (the data behind
``Workflow.print_stats``) charge to every unit but the fused trainer,
over the window, per train step.  The benchmark's own window unit is
left out."""

LAYER = "Entry"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "program_span"


def read(context):
    skip = [context["trainer_unit"]] + context["benchmark_units"]
    seconds = sum(row.get("run", 0.0)
                  for name, row in context["units"].items()
                  if name not in skip)
    return 1e3 * seconds / context["steps"] if context["steps"] else None
