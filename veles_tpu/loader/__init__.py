"""Data layer: minibatch loaders.

TPU-native counterpart of reference veles/loader/ (18 files).  The
minibatch *contract* — TEST/VALID/TRAIN class triple, per-epoch shuffling,
epoch/last-minibatch flags, the master–slave index-window protocol with
failed-minibatch requeue — is preserved verbatim so the distributed
semantics carry over; the device path is redesigned: the dataset lives in
HBM as a jax.Array and every minibatch is one Pallas gather
(ops.gather), not a host-side copy loop.
"""

from veles_tpu.loader.base import (  # noqa: F401
    Loader, LoaderMSEMixin, LoaderError, TEST, VALID, TRAIN, CLASS_NAME)
from veles_tpu.loader.fullbatch import (  # noqa: F401
    FullBatchLoader, FullBatchLoaderMSE)
from veles_tpu.loader.tokens import TokenRowLoader  # noqa: F401
from veles_tpu.loader.audio import AudioFileLoader  # noqa: F401
from veles_tpu.loader.hdfs import (  # noqa: F401
    HdfsTextLoader, WebHdfsClient)
