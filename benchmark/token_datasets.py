"""The benchmark's seeded token rows, as a ``TokenRowLoader`` the product
serves from HBM (the decoder cells' counterpart of ``datasets.py``).

Ids are Zipf-distributed over the vocabulary rows held: rank ``r`` (from
1) has weight ``r ** -exponent``, and a seeded permutation says which id
has which rank.  So the loss falls from ``ln(vocabulary)`` as soon as
the model learns the frequencies, whatever else it learns, and the
router sees the same few tokens often: routing is uneven, as on text.
Rows, their order and the permutation are a function of ``data_seed``
alone.
"""

import numpy

from veles_tpu.loader.tokens import TokenRowLoader
from veles_tpu.memory import Array


class SeededTokens(TokenRowLoader):
    """``lengths`` = (test, validation, train) rows of ``row_ids`` ids
    below ``vocabulary``.  Module-level: a snapshot pickles the loader by
    its import path."""

    def __init__(self, workflow, **kwargs):
        super(SeededTokens, self).__init__(workflow, **kwargs)
        self.row_ids = int(kwargs["row_ids"])
        self.vocabulary = int(kwargs["vocabulary"])
        self.exponent = float(kwargs.get("exponent", 1.0))
        self.lengths = tuple(kwargs["lengths"])
        self.data_seed = int(kwargs["data_seed"])

    def load_data(self):
        self.class_lengths[:] = self.lengths
        self._calc_class_end_offsets()
        self.create_originals((self.row_ids,), labels=False)
        fill_ids(self.original_data.mem, self.vocabulary, self.exponent,
                 self.data_seed)

    def _getstate_quiesced(self):
        # the rows are a function of data_seed, and load_data() makes
        # them again at every initialize: a snapshot carries the seed
        state = super(SeededTokens, self)._getstate_quiesced()
        state["_original_data"] = Array()
        return state


def fill_ids(out, vocabulary, exponent, seed):
    """Fill ``out`` (rows, ids) in place with Zipf-distributed ids."""
    rng = numpy.random.Generator(numpy.random.PCG64(seed % (1 << 32)))
    weights = numpy.arange(1, vocabulary + 1, dtype=numpy.float64) \
        ** -exponent
    cdf = numpy.cumsum(weights / weights.sum())
    id_of_rank = rng.permutation(vocabulary).astype(out.dtype)
    for row in range(0, out.shape[0], 256):  # bounded scratch
        block = out[row:row + 256]
        ranks = numpy.searchsorted(cdf, rng.random(block.shape))
        block[...] = id_of_rank[numpy.minimum(ranks, vocabulary - 1)]
    return out
