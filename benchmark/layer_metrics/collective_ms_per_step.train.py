"""Distribution: device ms per traced train step in collective
instructions — the data-parallel step's bucketed gradient all-reduce
(``parallel/bucketed.py``) and whatever else crosses chips — by OPCODE:
``all-reduce``, ``reduce-scatter``, ``all-gather``,
``collective-permute`` (the ring), ``all-to-all`` and their
``-start``/``-done`` halves.  Not by instruction name: XLA names an
all-reduce after the primitive that made it (``%psum.43 = f32[...]
all-reduce(...)``).  Mean over the chips that ran a step.  A ``-done``
half's time is the wait for the wire, so this is the time the chip's op
line spends ON the collectives, not their span: what overlaps the
backward is not in it.  0.0 where a step runs on one chip."""

import re

from benchmark import reduce_trace

LAYER = "Distribution"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "device_trace"

#: the opcode follows the result's type and a space; an operand that
#: names a collective's result (``fusion(%all-reduce.3)``) follows a
#: ``%`` and is followed by ``)`` or ``,``
COLLECTIVE = re.compile(
    r" (all-reduce|reduce-scatter|all-gather|collective-permute|"
    r"all-to-all)(-start|-done)?\(")


def read(context):
    trace = context["trace"]
    if trace is None:
        return None
    return 1e3 * reduce_trace.op_seconds_where(
        trace, lambda text: COLLECTIVE.search(
            text.split(" = ", 1)[-1]) is not None)
