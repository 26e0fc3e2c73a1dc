"""BatchNorm+ReLU fusion: pattern matching over the module graph.

Existing models get the fused elementwise tail
(`ops/bn_relu_kernel.py::bn_relu`) WITHOUT edits: the containers
pattern-match the `nn/normalization.py` -> `nn/activation.py` adjacency
at apply time:

- `Sequential`: a `BatchNormalization` child immediately followed by a
  `ReLU` child collapses into one `apply_with_activation` call (ResNet's
  basic/bottleneck blocks and the conv stem all hit this).
- `Graph`: a `ReLU` node whose ONLY input is a `BatchNormalization` node
  with no other consumer (and which is not itself a graph output)
  collapses the same way.

Matching is deliberately conservative: exact `ReLU` only (ReLU6/PReLU/
leaky variants keep their own semantics), NHWC BatchNorm only (the NCHW
path transposes around the tail), and frozen / stop-gradient modules are
skipped so the `Module.apply` gating wrapper keeps owning those
semantics. The match runs at trace time (inside jit it costs nothing per
step) and is re-evaluated every apply, so toggling fusion never requires
rebuilding a model.

What the match collapses into lowers as `bn_relu` says: since PR 37 the
reference jnp expressions on every backend, the TPU included (the Mosaic
kernel pair lost to the compiler's own fusion at every ResNet-50 shape on
the v5e: PERF.md section 6), bit-identical to the unfused graph (the CPU
CI parity gate in scripts/run_ci.sh pins this). So the toggle changes no
numerics and, today, no speed: it is process-global, default ON
(`BIGDL_TPU_FUSE_BN_RELU=0` disarms from the environment), `bench_cli
--fusion` drives the A/B through `fusion_scope`, and ROADMAP D4 decides
whether it stays.
"""

from __future__ import annotations

import contextlib
import os

from bigdl_tpu.nn.activation import ReLU
from bigdl_tpu.nn.normalization import BatchNormalization

_ENABLED = os.environ.get("BIGDL_TPU_FUSE_BN_RELU", "1").lower() \
    not in ("0", "false", "no")


def set_fusion(enabled: bool = True) -> bool:
    """Enable/disable BN+ReLU pattern fusion process-wide; returns the
    previous setting."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(enabled)
    return prev


def fusion_enabled() -> bool:
    """Whether BN+ReLU pattern fusion is currently armed (the containers
    consult this at trace time)."""
    return _ENABLED


@contextlib.contextmanager
def fusion_scope(enabled: bool):
    """Temporarily force fusion on/off (the A/B drivers alternate modes
    with this; restores the previous setting on exit)."""
    prev = set_fusion(enabled)
    try:
        yield
    finally:
        set_fusion(prev)


def fusible_bn(m) -> bool:
    """A BN module the fused tail can stand in for: NHWC layout (the
    trailing axis is the channel), not frozen (the freeze gate lives in
    the wrapped `apply`), not gradient-cut."""
    return (isinstance(m, BatchNormalization)
            and getattr(m, "data_format", "NHWC") == "NHWC"
            and not getattr(m, "_frozen", False)
            and not getattr(m, "_stop_gradient", False))


def fusible_activation(m) -> bool:
    """Exact ReLU only — subclasses would change the fused math."""
    return type(m) is ReLU and not getattr(m, "_stop_gradient", False)
