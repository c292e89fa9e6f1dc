"""What the readers of the program's own spans and kernel names share.

The program (``veles_tpu/observe/trace.py``'s scope primitive) adds every
span's duration to a registry histogram that is registered when its
owner initialises, so ``context["registry"]`` holds ``<name>.sum`` and
``<name>.count`` over the window and reads 0 where the path did not run.
A program without the span (an older commit) has no such key: the
reader then finds nothing to read and returns None.

A Pallas kernel's HLO instruction is named by the kernel's ``name=``
constant (``%veles_conv_wgrad.7 = f32[25,128,256]... custom-call(...)``),
which is what ``context["trace"]["op_seconds"]`` is keyed by.
"""

from benchmark import reduce_trace


def histogram_sum(context, name):
    """(sum, count) of the histogram over the window, or None where the
    run was not traced (per-layer metrics are read in the traced run
    only) or the program has no such histogram."""
    if context["trace"] is None:
        return None
    registry = context["registry"]
    if name + ".count" not in registry:
        return None
    return registry[name + ".sum"], registry[name + ".count"]


def per_train_step(context, name, scale):
    """The histogram's seconds over the window ÷ the window's train
    steps, times ``scale``."""
    found = histogram_sum(context, name)
    if found is None or not context["steps"]:
        return None
    return scale * found[0] / context["steps"]


def per_observation(context, name, scale):
    """The histogram's mean over the window, times ``scale``; 0.0
    where it observed nothing."""
    found = histogram_sum(context, name)
    if found is None:
        return None
    total, count = found
    return scale * total / count if count else 0.0


def kernel_ms_per_step(context, kernel):
    """Device ms per traced step in the instructions named
    ``%<kernel>`` or ``%<kernel>.<n>``; 0.0 where the trace holds none."""
    trace = context["trace"]
    if trace is None:
        return None
    head = "%" + kernel

    def named(text):
        name = text.split(" = ", 1)[0]
        return name == head or name.startswith(head + ".")

    return 1e3 * reduce_trace.op_seconds_where(trace, named)
