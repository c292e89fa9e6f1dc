"""What the readers of device time by the program's own scopes share.

The counterpart of ``span_metrics.py`` for the device: every layer's ops
run under ``jax.named_scope`` (``l<k>_<Class>``, ``loss``, ``grad_sync``,
``update``, a decoder layer's parts), and the program can say which
scope each instruction of its compiled train step belongs to
(``veles_tpu/observe/xla_introspect.py``: ``instruction_scopes``,
``scope_of``, ``device_seconds_by_scope``).  ``by_scope`` asks it for
that table (the program builds it once and keeps it) and joins it with
``context["trace"]["op_seconds"]`` by instruction name and result shape,
leaves only (a ``while``'s event spans its body's, which the trace also
holds).  Ops of the window's other programs (the loader's gather, the
eval step) and instructions with no scope are under
``(None, None, None)``.

A trace of another program (a recorded one fed to a toy run) joins
nothing: all of it reads as unattributed, which is what
``scope_unattributed_pct.train`` is there to say.

A program without the API (an older commit), one whose table is None or
an API that raises: the readers find nothing to read and return None,
and one line on standard error says why.
"""

import sys

PROGRAM = "fused.step"

_said = set()  # what standard error was told already: each once a process


def _say(why):
    if why not in _said:
        _said.add(why)
        sys.stderr.write("benchmark: no device time by scope: %s\n" % why)
        sys.stderr.flush()


def by_scope(context):
    """{(layer class, part, phase): device seconds over the traced whole
    steps}, or None where the run was not traced or the program gives no
    table (it builds the table on the first ask and keeps it, so every
    reader asks).  Never raises."""
    trace = context["trace"]
    if trace is None:
        return None
    try:
        from veles_tpu.observe import xla_introspect
        table = xla_introspect.instruction_scopes(PROGRAM)
        if table is not None:
            return xla_introspect.device_seconds_by_scope(
                trace["op_seconds"], table,
                **xla_introspect.scope_names(PROGRAM))
        _say("the program gave no table for %s" % PROGRAM)
    except Exception as exc:  # also a program from before the API
        _say("%s: %s" % (type(exc).__name__, exc))
    return None


def ms_per_step_where(context, keep):
    """Device ms per traced train step in the leaves whose
    ``keep(layer class, part, phase)`` holds; 0.0 where none does."""
    joined = by_scope(context)
    if joined is None:
        return None
    return 1e3 * sum(seconds for key, seconds in joined.items()
                     if keep(*key)) / context["trace"]["steps"]


def share_pct_where(context, keep):
    """The same leaves' share of all the window's leaves, per cent."""
    joined = by_scope(context)
    if joined is None or not sum(joined.values()):
        return None
    return 100.0 * sum(seconds for key, seconds in joined.items()
                       if keep(*key)) / sum(joined.values())
