"""Weight initialization methods.

Parity: reference `InitializationMethod` (DL/nn/InitializationMethod.scala) —
Zeros, Ones, ConstInitMethod, RandomUniform, RandomNormal, Xavier,
MsraFiller (He), BilinearFiller. Implemented on jax.random; fan computation
follows the reference's (fanIn, fanOut) from VariableFormat.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


def _fans(shape: Sequence[int]) -> Tuple[int, int]:
    if len(shape) == 0:
        return 1, 1
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:  # (in, out) linear convention used throughout this lib
        return shape[0], shape[1]
    # conv kernels stored HWIO (TPU-native layout): receptive = H*W
    receptive = math.prod(shape[:-2])
    fan_in = shape[-2] * receptive
    fan_out = shape[-1] * receptive
    return fan_in, fan_out


class InitializationMethod:
    def __call__(self, rng: jax.Array, shape: Sequence[int],
                 dtype=jnp.float32) -> jnp.ndarray:
        raise NotImplementedError


class Zeros(InitializationMethod):
    """Fill with zeros (DL/nn/InitializationMethod.scala Zeros)."""
    def __call__(self, rng, shape, dtype=jnp.float32):
        return jnp.zeros(shape, dtype)


class Ones(InitializationMethod):
    """Fill with ones (DL/nn/InitializationMethod.scala Ones)."""
    def __call__(self, rng, shape, dtype=jnp.float32):
        return jnp.ones(shape, dtype)


class ConstInitMethod(InitializationMethod):
    """Fill with a constant (DL/nn/InitializationMethod.scala ConstInitMethod)."""
    def __init__(self, value: float):
        self.value = value

    def __call__(self, rng, shape, dtype=jnp.float32):
        return jnp.full(shape, self.value, dtype)


class RandomUniform(InitializationMethod):
    """Uniform init in [lower, upper] (DL/nn/InitializationMethod.scala RandomUniform)."""
    def __init__(self, lower: Optional[float] = None, upper: Optional[float] = None):
        self.lower, self.upper = lower, upper

    def __call__(self, rng, shape, dtype=jnp.float32):
        if self.lower is None:
            fan_in, _ = _fans(shape)
            stdv = 1.0 / math.sqrt(max(fan_in, 1))
            lo, hi = -stdv, stdv
        else:
            lo, hi = self.lower, self.upper
        return jax.random.uniform(rng, tuple(shape), dtype, minval=lo, maxval=hi)


class RandomNormal(InitializationMethod):
    """Gaussian init with given mean/std (DL/nn/InitializationMethod.scala RandomNormal)."""
    def __init__(self, mean: float = 0.0, stdv: float = 1.0):
        self.mean, self.stdv = mean, stdv

    def __call__(self, rng, shape, dtype=jnp.float32):
        return self.mean + self.stdv * jax.random.normal(rng, tuple(shape), dtype)


class Xavier(InitializationMethod):
    """Glorot uniform, same formula as reference Xavier."""

    def __call__(self, rng, shape, dtype=jnp.float32):
        fan_in, fan_out = _fans(shape)
        limit = math.sqrt(6.0 / (fan_in + fan_out))
        return jax.random.uniform(rng, tuple(shape), dtype, minval=-limit, maxval=limit)


class MsraFiller(InitializationMethod):
    """He init; varianceNormAverage=False => 2/fan_in as in the reference."""

    def __init__(self, variance_norm_average: bool = False):
        self.avg = variance_norm_average

    def __call__(self, rng, shape, dtype=jnp.float32):
        fan_in, fan_out = _fans(shape)
        n = (fan_in + fan_out) / 2.0 if self.avg else fan_in
        std = math.sqrt(2.0 / max(n, 1))
        return std * jax.random.normal(rng, tuple(shape), dtype)


class BilinearFiller(InitializationMethod):
    """Bilinear upsampling kernel for full (transposed) convolution."""

    def __call__(self, rng, shape, dtype=jnp.float32):
        # shape HWIO
        kh, kw = shape[0], shape[1]
        f_h, f_w = math.ceil(kh / 2.0), math.ceil(kw / 2.0)
        c_h, c_w = (2 * f_h - 1 - f_h % 2) / (2.0 * f_h), (2 * f_w - 1 - f_w % 2) / (2.0 * f_w)
        ys = jnp.arange(kh)[:, None]
        xs = jnp.arange(kw)[None, :]
        ker = (1 - jnp.abs(ys / f_h - c_h)) * (1 - jnp.abs(xs / f_w - c_w))
        out = jnp.zeros(tuple(shape), dtype)
        n = min(shape[2], shape[3])
        idx = jnp.arange(n)
        return out.at[:, :, idx, idx].set(ker[:, :, None].astype(dtype))
