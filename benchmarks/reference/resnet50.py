"""Plain reference for the `resnet50` configuration.

ResNet-50 v1 as He et al. 2015 (arXiv:1512.03385, table 1) and BigDL's
`DL/models/resnet/ResNet.scala` build it: 7x7/2 stem, 3x3/2 max pool,
bottleneck stages of 3, 4, 6 and 3 blocks at 64..512 mid channels with
expansion 4, the stride on the 3x3 convolution, projection shortcuts
(type B), batch normalisation with batch statistics (training mode, eps
1e-5, biased variance), global average pool, a 1000-way linear layer and
log-softmax; the loss is the mean negative log-likelihood of 1-based
labels. NHWC, float32, straightforward `jax.numpy`. It imports nothing
of the program and makes its own weights and batch from the seed.

Weights are a flat dict, drawn to stand for a net early in training
rather than at the recipe's very first step: BN scales in [0.5, 1.5),
but the last BN of each block in [0.05, 0.15). The recipe starts that
one at 0, which leaves every convolution inside a block without a
gradient at the first step; at 1 the sixteen branches make the net
chaotic (PR 26, on the chip: the bfloat16 step's first gradient then
differs from the float32 reference's by 70% of its norm in the median
leaf, and so does an fp8 one, so no comparison could tell them apart).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import numerics as nx

STAGES = ((64, 3), (128, 4), (256, 6), (512, 3))
EXPANSION = 4
BN_EPS = 1e-5


def layout(cfg: Dict[str, Any]) -> List[Tuple[str, Tuple[int, ...], str]]:
    """(name, shape, kind) of every leaf, kind in conv / gamma / beta /
    fc_w / fc_b."""
    out = [("conv1.w", (7, 7, cfg["in_channels"], 64), "conv"),
           ("bn1.g", (64,), "gamma"), ("bn1.b", (64,), "beta")]
    n_in = 64
    for s, (mid, reps) in enumerate(STAGES, 1):
        n_out = mid * EXPANSION
        for b in range(reps):
            p = f"s{s}.b{b}."
            for j, (k, ci, co) in enumerate(
                    ((1, n_in, mid), (3, mid, mid), (1, mid, n_out)), 1):
                out += [(p + f"conv{j}.w", (k, k, ci, co), "conv"),
                        (p + f"bn{j}.g", (co,), "gamma"),
                        (p + f"bn{j}.b", (co,), "beta")]
            if b == 0:
                out += [(p + "down.conv.w", (1, 1, n_in, n_out), "conv"),
                        (p + "down.bn.g", (n_out,), "gamma"),
                        (p + "down.bn.b", (n_out,), "beta")]
            n_in = n_out
    out += [("fc.w", (n_in, cfg["num_classes"]), "fc_w"),
            ("fc.b", (cfg["num_classes"],), "fc_b")]
    return out


@partial(jax.jit, static_argnums=(0,))
def _init(leaves, seed):
    key = jax.random.PRNGKey(seed)
    w = {}
    for i, (name, shape, kind) in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        if kind == "conv":  # He et al. 2015b, fan-out
            std = (2.0 / (shape[0] * shape[1] * shape[3])) ** 0.5
            w[name] = jax.random.normal(k, shape, jnp.float32) * std
        elif kind == "gamma":
            lo, hi = (0.05, 0.15) if name.endswith(".bn3.g") else (0.5, 1.5)
            w[name] = jax.random.uniform(k, shape, jnp.float32, lo, hi)
        elif kind == "fc_w":
            w[name] = jax.random.normal(k, shape, jnp.float32) * 0.01
        else:
            w[name] = jax.random.normal(k, shape, jnp.float32) * 0.1
    return w


def init_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """All weights in one jitted call from the seed, float32."""
    return _init(tuple(layout(cfg)), jnp.uint32(seed % (2 ** 32)))


@partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _batch(rows, size, channels, classes, seed):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(jax.random.fold_in(key, 1000001),
                          (rows, size, size, channels), jnp.float32)
    y = jax.random.randint(jax.random.fold_in(key, 1000002), (rows,), 1,
                           classes + 1, jnp.int32)
    return x, y


def train_batch(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int,
                chips: int):
    """The one resident batch: normalised-image-like inputs and 1-based
    labels, every row different."""
    return _batch(mix["per_chip_batch"] * chips, cfg["image_size"],
                  cfg["in_channels"], cfg["num_classes"],
                  jnp.uint32(seed % (2 ** 32)))


def row_block(cfg, mix):
    """None: batch normalisation couples the rows, so the float32 step
    runs on the whole batch (block by block through `jax.checkpoint`)."""
    return None


def _bn(x, g, b):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + BN_EPS) * g + b


def _block(w, p, x, stride, precision):
    h = jax.nn.relu(_bn(nx.conv2d(x, w[p + "conv1.w"], 1, 0, precision),
                        w[p + "bn1.g"], w[p + "bn1.b"]))
    h = jax.nn.relu(_bn(nx.conv2d(h, w[p + "conv2.w"], stride, 1, precision),
                        w[p + "bn2.g"], w[p + "bn2.b"]))
    h = _bn(nx.conv2d(h, w[p + "conv3.w"], 1, 0, precision),
            w[p + "bn3.g"], w[p + "bn3.b"])
    if p + "down.conv.w" in w:
        x = _bn(nx.conv2d(x, w[p + "down.conv.w"], stride, 0, precision),
                w[p + "down.bn.g"], w[p + "down.bn.b"])
    return jax.nn.relu(h + x)


def loss(cfg: Dict[str, Any], w: Dict[str, Any], x, y, precision: str = "f32"):
    """Mean NLL of the 1-based labels `y` over the rows of `x`."""
    h = nx.conv2d(x, w["conv1.w"], 2, 3, precision)
    h = jax.nn.relu(_bn(h, w["bn1.g"], w["bn1.b"]))
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    for s, (_, reps) in enumerate(STAGES, 1):
        for b in range(reps):
            p = f"s{s}.b{b}."
            sub = {k: v for k, v in w.items() if k.startswith(p)}
            stride = 2 if (s > 1 and b == 0) else 1
            h = jax.checkpoint(
                partial(_block, p=p, stride=stride, precision=precision)
            )(sub, x=h)
    h = jnp.mean(h, axis=(1, 2))
    logp = jax.nn.log_softmax(nx.matmul(h, w["fc.w"], precision) + w["fc.b"])
    return -jnp.mean(jnp.take_along_axis(logp, (y - 1)[:, None], axis=1))
