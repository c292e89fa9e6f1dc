"""The language-model training runner for a cell of ONE sequence a step.

Everything is ``train_lm.py``'s, imported and not edited: the path
(``Launcher`` -> ``StandardWorkflow`` -> auto-fuse -> ``FusedTrainer``,
``Prefetcher``, snapshotter, rows resident, each checked), the window,
the end-to-end arithmetic, the per-layer context, and every comparison
behind ``correct`` but one.  ``train_lm.py``'s FAULT, "a step that trains
on half the minibatch", is the reference's gradient of the minibatch's
FIRST SEQUENCE alone; where the minibatch is one sequence that is the
whole step's gradient, the fault reads 0 off and every run says a limit
has gone slack.  Here the same fault is cut from what one row has, its
targets:

- ``half_batch_grad_diff_above``: the reference's gradient of the mean
  loss over the FIRST HALF of the row's targets (the others set to -1,
  which the reference's loss leaves out) against its gradient over all of
  them, ||g_half - g|| / ||g||, the largest of any parameter array as
  there; it must FAIL ``max_grad_diff``.  Both gradients come from the
  reference's own ``row_gradients`` through the programs the check has
  compiled by then; two more passes of the reference over the row.

The number keeps its name in ``compared``; ``train_lm.py``'s own reading
of it (0) is printed by its line and replaced.  Traffic file parameters
are ``train_lm``'s, with ``batch`` 1 (anything else is refused: such a
cell names ``train_lm``).
"""

import time

import numpy

from benchmark.runners import train_lm
from benchmark.runners.train import check, reference_of

FAULT = "half_batch_grad_diff_above"

_accepted_check = train_lm.against_reference


def half_row_gradient_diff(ctx, sw, initial):
    """{array: ||g_half - g|| / ||g||} over the parameter arrays whose
    reference gradient is not all zero, on the first train row from the
    seed's weights."""
    import jax
    import jax.numpy as jnp
    limits = ctx.config["reference"]
    reference = reference_of(ctx.config)
    sw.loader.original_data.map_read()
    row = numpy.array(sw.loader.original_data.mem[
        ctx.config["dataset"]["validation_rows"]])
    x, targets = row[:-1], row[1:]
    first_half = targets.copy()
    first_half[len(targets) // 2:] = -1
    params = [{key: None if value is None else jax.device_put(value)
               for key, value in entry.items()} for entry in initial]

    def mean_gradient(wanted):
        _, n, _, grads, _ = reference.row_gradients(
            sw.layers_config, params, x, wanted, lowered=False,
            operand=limits["control_operand"])
        return reference.scale_gradients(grads, 1.0 / n)

    whole, half = mean_gradient(targets), mean_gradient(first_half)
    off = {}
    for i, (g, g_half) in enumerate(zip(whole, half)):
        for key in ("weights", "bias"):
            if g[key] is None:
                continue
            size = float(jnp.sum(jnp.square(g[key])))
            if size:
                off["%d.%s" % (i, key)] = float(jnp.sqrt(
                    jnp.sum(jnp.square(g_half[key] - g[key])) / size))
    return off


def against_reference(ctx, sw, initial):
    """``train_lm.against_reference``'s numbers and problems, the
    fault's taken from half the row's targets."""
    numbers, problems = _accepted_check(ctx, sw, initial)
    started = time.perf_counter()
    off = half_row_gradient_diff(ctx, sw, initial)
    worst, least = max(off, key=off.get), min(off, key=off.get)
    limit = numbers[FAULT][1]
    ctx.say("  the fault where the minibatch is one row, a step on the "
            "first half of its targets: gradients %.3g off (array %s; the "
            "least of any array %.3g, %s); the gradients' limit %g must "
            "refuse it; this reading stands for the line above's; %.1f s",
            off[worst], worst, off[least], least, -limit,
            time.perf_counter() - started)
    numbers[FAULT] = [-off[worst], limit]
    problems = [p for p in problems if not p.startswith(FAULT)]
    if not -off[worst] <= limit:
        problems.append("%s %.3g beyond its limit %g"
                        % (FAULT, -off[worst], limit))
    return numbers, problems


def run(ctx):
    check(int(ctx.traffic["batch"]) == 1, "this runner is for one row a "
          "step; a cell of more names the runner train_lm")
    train_lm.against_reference = against_reference
    try:
        return train_lm.run(ctx)
    finally:
        train_lm.against_reference = _accepted_check
