"""Concatenate tensors along the feature axis.

TPU-native counterpart of reference ocl/join.jcl / cuda/join.jcu (a
Jinja2-templated concat of N device buffers, used by InputJoiner).  The
kernel writes each input into its column window of the output; the N-way
structure is unrolled at trace time, replacing the reference's template
expansion with Python-level metaprogramming over the kernel body.
"""

import jax
from jax.experimental import pallas as pl

from veles_tpu.ops.common import interpret_for, kernel_cast

__all__ = ["join"]

#: the kernel's name in compiled HLO and device traces (``%veles_join``)
KERNEL_NAME = "veles_join"


def _make_join_kernel(widths):
    offsets = []
    total = 0
    for width in widths:
        offsets.append(total)
        total += width

    def kernel(*refs):
        in_refs, out_ref = refs[:-1], refs[-1]
        for ref, offset, width in zip(in_refs, offsets, widths):
            out_ref[:, offset:offset + width] = \
                kernel_cast(ref[:], out_ref.dtype)
    return kernel


def join(*arrays, out_dtype=None):
    """Concatenate (B, Fi) arrays -> (B, sum Fi) along axis 1."""
    if not arrays:
        raise ValueError("join needs at least one input")
    batch = arrays[0].shape[0]
    for i, a in enumerate(arrays):
        if a.shape[0] != batch:
            raise ValueError(
                "join: input %d has batch %d, expected %d" %
                (i, a.shape[0], batch))
    flats = [a.reshape(batch, -1) for a in arrays]
    widths = tuple(f.shape[1] for f in flats)
    out_dtype = out_dtype or flats[0].dtype
    total = sum(widths)
    out = pl.pallas_call(
        _make_join_kernel(widths),
        name=KERNEL_NAME,
        out_shape=jax.ShapeDtypeStruct((batch, total), out_dtype),
        interpret=interpret_for(*flats),
    )(*flats)
    return out
