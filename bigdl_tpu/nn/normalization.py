"""Normalization layers.

Parity: BatchNormalization (DL/nn/BatchNormalization.scala),
SpatialBatchNormalization, Normalize, NormalizeScale. Running stats are kept
in the ApplyContext state pytree (not in-object mutation) so a jitted train
step stays pure; the moving-average update matches the reference's
`momentum` convention (new = (1-m)*old + m*batch).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.module import ApplyContext, Module


class BatchNormalization(Module):
    """BN over the last axis of [B, C] input (reference 1-D BN).

    Example:
        >>> import jax.numpy as jnp
        >>> from bigdl_tpu.nn import BatchNormalization
        >>> bn = BatchNormalization(4)
        >>> out = bn.forward(jnp.arange(8.0).reshape(2, 4), training=True)
        >>> out.shape
        (2, 4)
        >>> bool(abs(float(out.mean())) < 1e-5)  # normalized over batch
        True
    """

    def __init__(self, n_output: int, eps: float = 1e-5, momentum: float = 0.1,
                 affine: bool = True, name: Optional[str] = None, dtype=jnp.float32):
        super().__init__(name)
        self.n_output = n_output
        self.eps, self.momentum, self.affine = eps, momentum, affine
        self.dtype = dtype
        # which axes to reduce over; subclasses override
        self._axes: Tuple[int, ...] = (0,)

    def init(self, rng):
        if not self.affine:
            return {}
        k1, k2 = jax.random.split(rng)
        # reference reset(): weight ~ U(0,1), bias = 0 — we use ones/zeros
        # (the modern and Keras-parity default; reference Keras path also ones)
        return {"weight": jnp.ones((self.n_output,), self.dtype),
                "bias": jnp.zeros((self.n_output,), self.dtype)}

    def _init_state(self):
        return {"mean": jnp.zeros((self.n_output,), self.dtype),
                "var": jnp.ones((self.n_output,), self.dtype)}

    def _stats_scale_shift(self, params, input, ctx: ApplyContext):
        """Statistics + folded affine coefficients, shared by the plain
        and the fused (BN+ReLU) tails: returns (x_f32, scale, shift,
        out_dtype). State updates happen here, so both tails keep the
        running-stat semantics identical."""
        x = input
        # mixed-precision guard: statistics always accumulate in f32 —
        # a bf16 mean over batch*H*W elements loses ~3 decimal digits and
        # destabilizes the running stats. The normalize itself runs in f32
        # registers and is cast back, so HBM traffic stays half-width.
        out_dtype = x.dtype
        if jnp.issubdtype(x.dtype, jnp.floating) and \
                jnp.finfo(x.dtype).bits < 32:
            x = x.astype(jnp.float32)
        st = ctx.get_state(self._init_state)
        if ctx.training:
            mean = jnp.mean(x, axis=self._axes)
            var = jnp.var(x, axis=self._axes)
            n = 1.0
            for a in self._axes:
                n *= x.shape[a]
            unbiased = var * n / max(n - 1.0, 1.0)
            m = self.momentum
            ctx.put_state({
                "mean": (1 - m) * st["mean"] + m * mean,
                "var": (1 - m) * st["var"] + m * unbiased,
            })
        else:
            mean, var = st["mean"], st["var"]
        inv = jax.lax.rsqrt(var + self.eps)
        if self.affine:
            # fold scale into one fused multiply-add (XLA fuses this with the
            # surrounding conv under jit)
            scale = params["weight"].astype(x.dtype) * inv
            shift = params["bias"].astype(x.dtype) - mean * scale
        else:
            scale, shift = inv, -mean * inv
        return x, scale, shift, out_dtype

    def apply(self, params, input, ctx: ApplyContext):
        x, scale, shift, out_dtype = self._stats_scale_shift(params, input,
                                                             ctx)
        return (x * scale + shift).astype(out_dtype)

    def apply_with_activation(self, params, input, ctx: ApplyContext,
                              relu: bool = True):
        """BN + activation as ONE elementwise tail
        (ops/bn_relu_kernel.py::bn_relu): the exact unfused expressions
        on every backend (bit-identical — the containers' pattern matcher
        relies on this), which XLA fuses into the neighbouring
        convolutions; the Mosaic kernel pair behind it lost to that on
        the v5e at every ResNet-50 shape (PR 37). Statistics, state
        updates, and the folded coefficients are shared with the plain
        `apply`."""
        if getattr(self, "data_format", "NHWC") != "NHWC":
            # NCHW transposes around the tail; keep a correct fallback
            # (the pattern matcher never fuses NCHW — belt and braces)
            y = self.apply(params, input, ctx)
            return jax.nn.relu(y) if relu else y
        from bigdl_tpu.ops.bn_relu_kernel import bn_relu
        x, scale, shift, out_dtype = self._stats_scale_shift(params, input,
                                                             ctx)
        return bn_relu(x, scale, shift, relu, out_dtype)


class SpatialBatchNormalization(BatchNormalization):
    """BN over NHWC [B, H, W, C] (reference DL/nn/SpatialBatchNormalization
    is NCHW; we normalize the trailing channel axis, TPU-native layout)."""

    def __init__(self, n_output: int, eps: float = 1e-5, momentum: float = 0.1,
                 affine: bool = True, data_format: str = "NHWC", name=None):
        super().__init__(n_output, eps, momentum, affine, name)
        self.data_format = data_format
        self._axes = (0, 1, 2)

    def apply(self, params, input, ctx):
        if self.data_format == "NCHW":
            x = jnp.transpose(input, (0, 2, 3, 1))
            y = super().apply(params, x, ctx)
            return jnp.transpose(y, (0, 3, 1, 2))
        return super().apply(params, input, ctx)


class Normalize(Module):
    """Lp-normalize along the channel axis (DL/nn/Normalize.scala)."""

    def __init__(self, p: float = 2.0, eps: float = 1e-10, axis: int = -1, name=None):
        super().__init__(name)
        self.p, self.eps, self.axis = p, eps, axis

    def apply(self, params, input, ctx):
        if self.p == float("inf"):
            norm = jnp.max(jnp.abs(input), axis=self.axis, keepdims=True)
        else:
            norm = jnp.power(
                jnp.sum(jnp.power(jnp.abs(input), self.p), axis=self.axis, keepdims=True),
                1.0 / self.p)
        return input / (norm + self.eps)


class NormalizeScale(Module):
    """Normalize + learned per-channel scale (DL/nn/NormalizeScale.scala,
    the SSD conv4_3 trick)."""

    def __init__(self, p: float = 2.0, scale: float = 1.0, size=None,
                 eps: float = 1e-10, name=None):
        super().__init__(name)
        self.norm = Normalize(p, eps)
        self.scale_init = scale
        self.size = tuple(size) if size is not None else None

    def init(self, rng):
        return {"scale": jnp.full(self.size or (1,), self.scale_init)}

    def apply(self, params, input, ctx):
        return self.norm.apply({}, input, ctx) * params["scale"]


class LayerNormalization(Module):
    """Layer norm over the last axis — present in the reference's keras2/
    transformer extensions; included here as a core primitive."""

    def __init__(self, hidden_size: int, eps: float = 1e-5, name=None):
        super().__init__(name)
        self.hidden_size, self.eps = hidden_size, eps

    def init(self, rng):
        return {"weight": jnp.ones((self.hidden_size,)),
                "bias": jnp.zeros((self.hidden_size,))}

    def apply(self, params, input, ctx):
        mean = jnp.mean(input, axis=-1, keepdims=True)
        var = jnp.var(input, axis=-1, keepdims=True)
        y = (input - mean) * jax.lax.rsqrt(var + self.eps)
        return y * params["weight"] + params["bias"]


def rms_norm(x, weight, eps: float):
    """x / rms(x) * weight over the last axis; the statistics in float32
    whatever the input's type, the result in the input's type."""
    y = x.astype(jnp.float32)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return (y * weight).astype(x.dtype)


class RMSNorm(Module):
    """Root-mean-square norm over the last axis: x / rms(x) * weight, no
    mean and no bias. The statistics are taken in float32 whatever the
    input's type; the result keeps the input's type."""

    def __init__(self, hidden_size: int, eps: float = 1e-6, name=None):
        super().__init__(name)
        self.hidden_size, self.eps = hidden_size, eps

    def init(self, rng):
        return {"weight": jnp.ones((self.hidden_size,))}

    def apply(self, params, input, ctx):
        return rms_norm(input, params["weight"], self.eps)


def _gaussian_kernel(size: int, sigma: float = None):
    """Default smoothing kernel used by the Torch-style normalization layers
    when none is given (reference passes an explicit kernel tensor)."""
    sigma = sigma or (size / 4.0)
    r = jnp.arange(size, dtype=jnp.float32) - (size - 1) / 2.0
    g = jnp.exp(-(r ** 2) / (2 * sigma ** 2))
    k = g[:, None] * g[None, :]
    return k / jnp.sum(k)


def _smooth2d(x2d, kernel):
    """SAME-padded 2-D correlation of [B, H, W] with [kh, kw], plus the
    border-coefficient map (reference adjusts means near edges by dividing
    by the local kernel mass, Torch SpatialSubtractiveNormalization)."""
    kh, kw = kernel.shape
    k4 = kernel[:, :, None, None]
    y = jax.lax.conv_general_dilated(
        x2d[..., None], k4, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))[..., 0]
    ones = jnp.ones_like(x2d[:1])
    coef = jax.lax.conv_general_dilated(
        ones[..., None], k4, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))[..., 0]
    return y / coef


class SpatialSubtractiveNormalization(Module):
    """Subtract the weighted local neighbourhood mean, NHWC
    (DL/nn/SpatialSubtractiveNormalization.scala). The kernel is normalized
    to unit mass and averaged across channels, matching Torch semantics."""

    def __init__(self, n_input_plane: int = 1, kernel=None, name=None):
        super().__init__(name)
        self.n_input_plane = n_input_plane
        k = _gaussian_kernel(9) if kernel is None else jnp.asarray(kernel, jnp.float32)
        if k.ndim == 1:
            k = k[:, None] * k[None, :]
        self.kernel = k / jnp.sum(k)

    def _local_mean(self, x):
        return _smooth2d(jnp.mean(x, axis=-1), self.kernel)

    def apply(self, params, input, ctx):
        return input - self._local_mean(input)[..., None]


class SpatialDivisiveNormalization(Module):
    """Divide by the weighted local neighbourhood stdev, thresholded by its
    per-image mean (DL/nn/SpatialDivisiveNormalization.scala)."""

    def __init__(self, n_input_plane: int = 1, kernel=None,
                 threshold: float = 1e-4, thresval: float = None, name=None):
        super().__init__(name)
        self.sub = SpatialSubtractiveNormalization(n_input_plane, kernel)
        self.threshold = threshold
        self.thresval = threshold if thresval is None else thresval

    def apply(self, params, input, ctx):
        local_var = _smooth2d(jnp.mean(input * input, axis=-1), self.sub.kernel)
        local_std = jnp.sqrt(jnp.maximum(local_var, 0.0))
        # Torch Threshold(threshold, thresval) semantics: stds at or below
        # `threshold` are replaced by `thresval` before dividing
        denom = jnp.where(local_std > self.threshold, local_std, self.thresval)
        return input / denom[..., None]


class SpatialContrastiveNormalization(Module):
    """Subtractive then divisive normalization
    (DL/nn/SpatialContrastiveNormalization.scala)."""

    def __init__(self, n_input_plane: int = 1, kernel=None,
                 threshold: float = 1e-4, thresval: float = None, name=None):
        super().__init__(name)
        self.sub = SpatialSubtractiveNormalization(n_input_plane, kernel)
        self.div = SpatialDivisiveNormalization(n_input_plane, kernel,
                                                threshold, thresval)

    def apply(self, params, input, ctx):
        return self.div.apply({}, self.sub.apply({}, input, ctx), ctx)


class SpatialWithinChannelLRN(Module):
    """Within-channel local response normalization over a spatial window,
    NHWC (DL/nn/SpatialWithinChannelLRN.scala; Caffe WITHIN_CHANNEL LRN):
    y = x / (1 + alpha/size^2 * avg_window(x^2))^beta."""

    def __init__(self, size: int = 5, alpha: float = 1.0, beta: float = 0.75,
                 name=None):
        super().__init__(name)
        self.size, self.alpha, self.beta = size, alpha, beta

    def apply(self, params, input, ctx):
        sq = input * input
        win = jax.lax.reduce_window(
            sq, 0.0, jax.lax.add, (1, self.size, self.size, 1), (1, 1, 1, 1),
            "SAME")
        avg = win / (self.size * self.size)
        return input / jnp.power(1.0 + self.alpha * avg, self.beta)
