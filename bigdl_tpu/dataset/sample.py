"""Sample and MiniBatch.

Parity: DL/dataset/Sample.scala:138 (feature/label record) and
DL/dataset/MiniBatch.scala:34 (batched tensors with slice/getInput/getTarget).
Host-side numpy: batching happens on CPU feeding the device queue, exactly as
the reference keeps Samples in Spark RDDs off the compute path. The
reference's `MiniBatch.slice` existed to split a batch across executor
threads; under SPMD the analogous split is the per-device sharding done by
the distributed plane, but slice is kept for API parity.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Union

import numpy as np


class Sample:
    """One training record: feature tensor(s) + label tensor(s)."""

    def __init__(self, features, labels=None):
        self.features = [np.asarray(f) for f in _as_list(features)]
        self.labels = ([np.asarray(l) for l in _as_list(labels)]
                       if labels is not None else [])

    @property
    def feature(self):
        return self.features[0]

    @property
    def label(self):
        return self.labels[0] if self.labels else None

    def feature_size(self):
        return [f.shape for f in self.features]

    def label_size(self):
        return [l.shape for l in self.labels]

    def __repr__(self):
        return (f"Sample(features={[f.shape for f in self.features]}, "
                f"labels={[l.shape for l in self.labels]})")


def _as_list(x):
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


class PaddingParam:
    """Variable-length padding spec (DL/dataset/MiniBatch.scala:523-586).
    `padding_value` fills; `padding_length` fixes the padded length (None =
    longest in batch, which the reference calls 'pad to max')."""

    def __init__(self, padding_value: float = 0.0,
                 padding_length: Optional[int] = None):
        self.padding_value = padding_value
        self.padding_length = padding_length


class MiniBatch:
    """A batch of stacked features/labels.

    Host batches are normalized to numpy; DEVICE-RESIDENT batches
    (jax.Array) pass through untouched — forcing np.asarray on one would
    silently round-trip it device->host->device every step (the
    reference's broadcast-and-persist perf driver,
    DistriOptimizerPerf.scala:108-118, exists precisely to avoid
    per-iteration ingest).

    Example:
        >>> import numpy as np
        >>> from bigdl_tpu.dataset.sample import MiniBatch
        >>> mb = MiniBatch(np.ones((4, 3), np.float32),
        ...                np.ones((4,), np.int32))
        >>> mb.size()
        4
    """

    @staticmethod
    def _norm(x):
        import jax
        if isinstance(x, jax.Array):
            return x  # committed device array: no host round-trip
        return np.asarray(x)

    def __init__(self, inputs, targets=None):
        self.inputs = [self._norm(i) for i in _as_list(inputs)]
        self.targets = [self._norm(t) for t in _as_list(targets)] \
            if targets is not None else []

    def get_input(self):
        return self.inputs[0] if len(self.inputs) == 1 else self.inputs

    def get_target(self):
        if not self.targets:
            return None
        return self.targets[0] if len(self.targets) == 1 else self.targets

    def size(self) -> int:
        return self.inputs[0].shape[0]

    def slice(self, offset: int, length: int) -> "MiniBatch":
        """1-based offset like the reference MiniBatch.slice:49."""
        o = offset - 1
        return MiniBatch([i[o:o + length] for i in self.inputs],
                         [t[o:o + length] for t in self.targets] or None)

    @staticmethod
    def from_samples(samples: Sequence[Sample],
                     feature_padding: Optional[PaddingParam] = None,
                     label_padding: Optional[PaddingParam] = None) -> "MiniBatch":
        n_feat = len(samples[0].features)
        n_lab = len(samples[0].labels)
        inputs = [_stack([s.features[i] for s in samples], feature_padding)
                  for i in range(n_feat)]
        targets = ([_stack([s.labels[i] for s in samples], label_padding)
                    for i in range(n_lab)] or None)
        return MiniBatch(inputs, targets)


def _stack(arrs: List[np.ndarray], padding: Optional[PaddingParam]):
    shapes = {a.shape for a in arrs}
    if len(shapes) == 1 and padding is None:
        return np.stack(arrs)
    # variable-length: pad every dim to the max (or fixed padding_length dim 0)
    nd = max(a.ndim for a in arrs)
    arrs = [a.reshape(a.shape + (1,) * (nd - a.ndim)) for a in arrs]
    maxshape = [max(a.shape[d] for a in arrs) for d in range(nd)]
    value = 0.0
    if padding is not None:
        value = padding.padding_value
        if padding.padding_length is not None:
            maxshape[0] = padding.padding_length
    out = np.full((len(arrs),) + tuple(maxshape), value, dtype=arrs[0].dtype)
    for i, a in enumerate(arrs):
        sl = (i,) + tuple(slice(0, s) for s in a.shape)
        out[sl] = a
    return out
