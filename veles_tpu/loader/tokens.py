"""TokenRowLoader — rows of token ids resident in HBM, served as
(tokens, next tokens).

A row is ``T + 1`` ids.  The rows live in the row store every
``FullBatchLoader`` keeps (``ops/gather.py``: 4-byte elements pad to
whole lanes, so 8,193 ids take 8,320), a step gathers its rows with the
one row-DMA kernel, and a small jitted program over the gathered rows
splits each into the input ``ids[:-1]`` (``minibatch_data``, (B, T)) and
the target ``ids[1:]`` (``minibatch_labels``, (B, T)): the target is the
same row shifted, so the table is held once.  The rows of a short last
minibatch carry target -1 past its size, which the loss and the error
count leave out, as for class labels.

There are no class labels: ``original_labels`` stays empty and
``has_labels`` is false, so nothing of the label mapping runs.  Ids are
not normalised.
"""

import numpy

from veles_tpu import ops
from veles_tpu.loader.base import Loader
from veles_tpu.loader.fullbatch import FullBatchLoader
from veles_tpu.observe.trace import tracer as _tracer

__all__ = ["TokenRowLoader", "split_rows"]

_SPLIT = None


def split_rows(rows, count):
    """(B, T + 1) ids -> ((B, T) inputs, (B, T) next-token targets), the
    rows from ``count`` on zeroed and their targets -1."""
    global _SPLIT
    if _SPLIT is None:
        import jax
        import jax.numpy as jnp

        def split_rows(rows, count):  # PjitFunction(split_rows) in a trace
            with jax.named_scope(ops.gather.SCOPE):
                live = (jnp.arange(rows.shape[0]) < count)[:, None]
                return (jnp.where(live, rows[:, :-1], 0),
                        jnp.where(live, rows[:, 1:], -1))
        _SPLIT = jax.jit(split_rows)
    return _SPLIT(rows, count)


class TokenRowLoader(FullBatchLoader):
    """``original_data``: (N, T + 1) int32 ids, filled by
    ``load_data()`` after ``create_originals((T + 1,), labels=False)``."""

    def __init__(self, workflow, **kwargs):
        kwargs.setdefault("dtype", Loader.LABEL_DTYPE)
        super(TokenRowLoader, self).__init__(workflow, **kwargs)

    @property
    def tokens(self):
        """T: the tokens a row feeds the model."""
        return self.shape[0] - 1

    def create_minibatch_data(self):
        shape = (self.max_minibatch_size, self.tokens)
        self.minibatch_data.mem = numpy.zeros(shape, self.dtype)
        self.minibatch_labels.mem = numpy.zeros(shape, self.dtype)

    def analyze_original_dataset(self):
        pass  # ids are not normalised

    def fill_indices(self, start_offset, count):
        if not self._use_device_path():
            return Loader.fill_indices(self, start_offset, count)
        window = self._index_window(start_offset, count)
        with _tracer.scope("loader.gather", cat="loader",
                           hist=self._m_gather_):
            # every row as it is: split_rows masks the tail itself
            rows, = self._gather(window, self.max_minibatch_size)
            data, targets = split_rows(rows, numpy.int32(count))
            self.minibatch_data.set_device_array(data, self.device)
            self.minibatch_labels.set_device_array(targets, self.device)
        return True

    def fill_minibatch(self):
        size = self.minibatch_size
        idx = self.minibatch_indices.mem[:size]
        self.original_data.map_read()
        rows = self.original_data.mem[idx]
        for array, part, tail in (
                (self.minibatch_data, rows[:, :-1], 0),
                (self.minibatch_labels, rows[:, 1:], -1)):
            array.map_write()
            array.mem[:size] = part
            array.mem[size:] = tail
