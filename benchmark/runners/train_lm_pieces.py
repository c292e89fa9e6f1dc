"""The one-row language-model runner that also compares named pieces of
a layer one by one.

Everything is ``train_lm_b1.py``'s, and through it ``train_lm.py``'s,
imported and not edited: the path, the window, the end-to-end
arithmetic, the per-layer context and every comparison behind
``correct``.  Their gradient comparison takes the worst relative error
over WHOLE packed arrays, so a piece that is a small share of its
layer's vector can be wrong, zero or of the wrong sign inside that
reading (a learned indexer's matrices are 2.3 M of a layer's 96.9 M
weights, and their gradient a few tenths beside the output projection's
fifty).  Here, after those comparisons, the first train step's gradient
of each piece that ``reference.pieces`` names is compared with the
reference's piece alone, in every layer whose packed layout holds it
(the reference's ``layer_pieces``):

- ``piece_grad_diff`` = the largest ||g - g_ref|| / ||g_ref|| over the
  named pieces of every layer, at most ``max_piece_grad_diff``; g is
  the program's first step read from AdamW's first moment, g_ref the
  reference's gradient of the row's objective, as ``train_lm.py`` takes
  both.
- the FAULT, ``piece_fault_grad_diff_above``: the reference's own
  gradient with the pieces ``reference.piece_fault`` names set to zero
  (what a program whose gradient by those operands went missing would
  give), read the same way, must FAIL ``max_piece_grad_diff``.

Two more readings are printed beside the accepted limits and compared
with nothing: the loss of the control (the reference with every
product's operands rounded to ``control_operand``) and of the one-row
fault (the first half of the row's targets) against the float32
reference's loss, both on the row the first step takes.  Traffic file
parameters are ``train_lm_b1``'s.
"""

import time

import numpy

from benchmark.runners import train_lm, train_lm_b1
from benchmark.runners.train import check, reference_of

FAULT = "piece_fault_grad_diff_above"

_accepted_first_step = train_lm.first_step_of_the_program


def spans(reference, layers, width, names):
    """[(layer, "weights" or "bias", piece, start, end)] of the pieces
    ``names`` in every layer between the embedding and the head, as the
    reference's ``layer_pieces`` packs them."""
    out = []
    for i, spec in enumerate(layers[1:-1], 1):
        for key, pieces in zip(("weights", "bias"),
                               reference.layer_pieces(spec, width)):
            offset = 0
            for name, shape in pieces:
                size = int(numpy.prod(shape))
                if name in names:
                    out.append((i, key, name, offset, offset + size))
                offset += size
    return out


def piece_diffs(where, got, want):
    """{"<layer>.<piece>": ||got - want|| / ||want||} over the pieces
    ``where`` lists (:func:`spans`) whose reference gradient is not all
    zero; ``got``/``want`` are lists of ``{"weights", "bias"}`` a spec."""
    out = {}
    for i, key, name, start, end in where:
        g = numpy.asarray(got[i][key], numpy.float64).ravel()[start:end]
        w = numpy.asarray(want[i][key], numpy.float64).ravel()[start:end]
        size = float(numpy.sum(w * w))
        if size:
            out["%d.%s" % (i, name)] = float(numpy.sqrt(
                numpy.sum((g - w) ** 2) / size))
    return out


def zeroed(where, grads):
    """``grads`` with the pieces ``where`` lists set to zero."""
    out = [dict(entry) for entry in grads]
    for i, key, _, start, end in where:
        vector = numpy.array(out[i][key], numpy.float32).ravel()
        vector[start:end] = 0
        out[i][key] = vector
    return out


def program_gradients(layers, moments):
    """The first step's gradients from AdamW's first moments after it,
    m = (1 - beta1) g, as ``train_lm.py`` reads them."""
    return [{key: None if entry[key] is None
             else entry[key] / (1 - spec["gradient_moment"])
             for key in ("weights", "bias")}
            for spec, entry in zip(layers, moments)]


def against_reference(ctx, sw, initial, seen):
    """``train_lm_b1.against_reference``'s numbers and problems, and the
    named pieces' comparison, its fault and the two loss readings."""
    import jax
    numbers, problems = train_lm_b1.against_reference(ctx, sw, initial)
    started = time.perf_counter()
    limits = ctx.config["reference"]
    reference = reference_of(ctx.config)
    layers = sw.layers_config
    width = initial[0]["weights"].shape[-1]
    sw.loader.original_data.map_read()
    row = numpy.array(sw.loader.original_data.mem[
        ctx.config["dataset"]["validation_rows"]])
    x, targets = row[:-1], row[1:]
    params = [{key: None if value is None else jax.device_put(value)
               for key, value in entry.items()} for entry in initial]
    total, n, logits, grads, _ = reference.row_gradients(
        layers, params, x, targets, lowered=False,
        operand=limits["control_operand"])
    want = [{key: None if value is None else numpy.asarray(value)
             for key, value in entry.items()}
            for entry in reference.scale_gradients(grads, 1.0 / n)]
    del grads
    names = limits["pieces"]
    where = spans(reference, layers, width, names)
    off = piece_diffs(where, program_gradients(layers, seen["moments"]),
                      want)
    check(off, "no layer holds any of the pieces %s" % (names,))
    lost = spans(reference, layers, width, limits["piece_fault"])
    fault = piece_diffs(lost, zeroed(lost, want), want)
    worst = max(off, key=off.get)
    least = min(fault, key=fault.get)
    limit = limits["max_piece_grad_diff"]
    ctx.say("  the pieces %s one by one: the program's first step within "
            "%.3g of the reference's (piece %s; limit %g; all: %s)",
            ", ".join(names), off[worst], worst, limit,
            " ".join("%s %.3g" % item for item in off.items()))
    ctx.say("  the fault, the reference's gradient with %s set to zero: "
            "%.3g off at the least (%s); the pieces' limit must refuse it",
            ", ".join(limits["piece_fault"]), fault[least], least)

    # the loss of the control and of the one-row fault, printed only
    want_loss = float(total) / n
    control = reference.forward(layers, params, x[None],
                                operand=limits["control_operand"])
    control_loss = float(reference.loss(control, targets[None]))
    first_half = targets.copy()
    first_half[len(targets) // 2:] = -1
    half_loss = float(reference.loss(logits, first_half))
    del control, logits
    ctx.say("  the loss against the float32 reference's %.5f: the control "
            "(%s operands) %.5f, %.3g apart; the one-row fault (the first "
            "half of the targets) %.5f, %.3g apart; the loss's limit %g; "
            "%.1f s", want_loss, limits["control_operand"], control_loss,
            abs(control_loss - want_loss) / want_loss, half_loss,
            abs(half_loss - want_loss) / want_loss, limits["max_loss_diff"],
            time.perf_counter() - started)
    numbers["piece_grad_diff"] = [off[worst], limit]
    numbers[FAULT] = [-fault[least], -limit]
    if not off[worst] <= limit:
        problems.append("piece_grad_diff %.3g beyond its limit %g"
                        % (off[worst], limit))
    if not -fault[least] <= -limit:
        problems.append("%s %.3g beyond its limit %g"
                        % (FAULT, -fault[least], -limit))
    return numbers, problems


def run(ctx):
    check(int(ctx.traffic["batch"]) == 1, "this runner is for one row a "
          "step; a cell of more names the runner train_lm")
    seen = {}

    def first_step(trainer, initial, x, targets):
        loss, after, moments, compiled = _accepted_first_step(
            trainer, initial, x, targets)
        seen["moments"] = moments
        return loss, after, moments, compiled

    train_lm.first_step_of_the_program = first_step
    train_lm.against_reference = (
        lambda ctx_, sw, initial: against_reference(ctx_, sw, initial,
                                                    seen))
    try:
        return train_lm.run(ctx)
    finally:
        train_lm.first_step_of_the_program = _accepted_first_step
        train_lm.against_reference = train_lm_b1._accepted_check
