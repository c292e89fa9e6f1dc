"""The benchmark's seeded dataset, as a ``FullBatchLoader`` the product
serves from HBM.

Copied from ``chip_smoke.SeededImages`` (the original stays where it is;
PERF.md lists it under Open questions) and made cheap to build: every
run of every cell pays this in ``setup_s``.  A row is its class's fixed
pattern under one of a pool of noise rows, so a few dozen steps lower
the loss whatever the model; the rows, their labels and their order are
a function of ``data_seed`` alone.
"""

import os
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy

from veles_tpu.loader.fullbatch import FullBatchLoader
from veles_tpu.memory import Array

#: float32 elements one worker holds at a time (two scratch buffers)
CHUNK_ELEMS = 1 << 23
NOISE_POOL = 61
PATTERN_PERIOD = 4099


class SeededDataset(FullBatchLoader):
    """``label_kinds`` classes over ``lengths`` = (test, validation,
    train) rows of ``sample_shape``.  Module-level: a snapshot pickles
    the loader by its import path."""

    def __init__(self, workflow, **kwargs):
        super(SeededDataset, self).__init__(workflow, **kwargs)
        self.sample_shape = tuple(kwargs["sample_shape"])
        self.label_kinds = int(kwargs["label_kinds"])
        self.lengths = tuple(kwargs["lengths"])
        self.data_seed = int(kwargs["data_seed"])

    def load_data(self):
        self.class_lengths[:] = self.lengths
        self._calc_class_end_offsets()
        self.create_originals(self.sample_shape)
        labels = fill_rows(self.original_data.mem, self.label_kinds,
                           self.data_seed)
        self.original_labels[:] = labels.tolist()

    def _getstate_quiesced(self):
        # the dataset is a function of data_seed, and load_data() makes
        # it again at every initialize: a snapshot carries the seed
        state = super(SeededDataset, self)._getstate_quiesced()
        state["_original_data"] = Array()
        return state


def fill_rows(out, label_kinds, seed):
    """Fill ``out`` (rows, *sample) in place and return the labels.

    Labels are a shuffled ``arange(rows) % label_kinds``: every class is
    present and equally often, for every seed.  The arithmetic runs in
    float32 over chunks, on a few threads (numpy releases the GIL), and
    is cast to ``out``'s type on the store."""
    rows = out.shape[0]
    flat = out.reshape(rows, -1)
    width = flat.shape[1]
    rng = numpy.random.Generator(numpy.random.PCG64(seed % (1 << 32)))
    labels = numpy.arange(rows) % label_kinds
    rng.shuffle(labels)
    # a class's pattern repeats a short random vector: label_kinds
    # full-width random rows would cost more than the dataset
    period = min(PATTERN_PERIOD, width)
    short = rng.random((label_kinds, period), dtype=numpy.float32)
    kinds = numpy.tile(short, (1, -(-width // period)))[:, :width]
    kinds *= 0.75
    kinds -= 0.5
    noise = rng.random((NOISE_POOL, width), dtype=numpy.float32)
    noise *= 0.25
    which = rng.integers(0, NOISE_POOL, rows)
    step = max(1, CHUNK_ELEMS // width)
    scratch = threading.local()

    def fill(start):
        count = min(step, rows - start)
        if not hasattr(scratch, "a"):
            scratch.a = numpy.empty((step, width), numpy.float32)
            scratch.b = numpy.empty((step, width), numpy.float32)
        a, b = scratch.a[:count], scratch.b[:count]
        numpy.take(kinds, labels[start:start + count], axis=0, out=a)
        numpy.take(noise, which[start:start + count], axis=0, out=b)
        a += b
        flat[start:start + count] = a

    workers = max(1, min(8, (os.cpu_count() or 2) - 1))
    with ThreadPoolExecutor(workers) as pool:
        list(pool.map(fill, range(0, rows, step)))
    return labels
