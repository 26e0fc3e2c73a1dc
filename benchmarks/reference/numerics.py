"""What the plain references share: emulated precisions, the optimizers'
arithmetic, per-leaf norms and the three-step training trace.

Nothing here imports the program. A reference computes in float32 with
`Precision.HIGHEST`; a *control* is the same code with every matmul and
convolution operand first rounded to a lower precision (products of the
rounded operands are then exact in float32), which is what a step that
computed in that precision would see.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST

#: precisions a reference can be asked for, from the one every
#: configuration is compared against down to the controls
PRECISIONS = ("f32", "bf16", "fp8")


def _round(x, precision: str, gradient: bool = False):
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        # one scale per tensor, the largest magnitude at the type's top:
        # e4m3 (448) forward, e5m2 (57344) for gradients, as fp8 training
        # feeds its matmuls
        dtype, top = (jnp.float8_e5m2, 57344.0) if gradient \
            else (jnp.float8_e4m3fn, 448.0)
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        return (x / scale).astype(dtype).astype(jnp.float32) * scale
    raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rounded(x, precision):
    return _round(x, precision)


def _rounded_fwd(x, precision):
    return _round(x, precision), None


def _rounded_bwd(precision, _, ct):
    return (_round(ct, precision, gradient=True),)


_rounded.defvjp(_rounded_fwd, _rounded_bwd)


def q(x, precision: str):
    """`x` rounded to `precision` and held in float32, for a matmul's or
    a convolution's operand. The backward pass sees the rounded operands
    and rounds the gradient that comes back to each of them as well (a
    plain cast's derivative would round it unscaled, and e4m3 then
    flushes it to zero)."""
    return x if precision == "f32" else _rounded(x, precision)


def matmul(a, b, precision: str):
    return jnp.matmul(q(a, precision), q(b, precision), precision=HI)


def conv2d(x, w, stride: int, pad: int, precision: str):
    """NHWC x HWIO convolution, symmetric zero padding."""
    return lax.conv_general_dilated(
        q(x, precision), q(w, precision), (stride, stride),
        [(pad, pad), (pad, pad)], dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HI)


# ------------------------------------------------------------ optimizers

def opt_init(spec: Dict[str, Any], weights):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, weights)
    if spec["method"] == "sgd":
        return {"velocity": zeros}
    if spec["method"] == "adam":
        return {"m": zeros, "v": jax.tree_util.tree_map(jnp.zeros_like,
                                                       weights),
                "t": jnp.zeros((), jnp.float32)}
    raise ValueError(f"unknown optimizer {spec['method']!r}")


def opt_update(spec: Dict[str, Any], weights, state, grads):
    """One update as BigDL's SGD.scala / Adam.scala define it."""
    tm = jax.tree_util.tree_map
    lr = spec["learning_rate"]
    if spec["method"] == "sgd":
        mom, damp = spec["momentum"], spec["dampening"]
        vel = tm(lambda v, g: mom * v + (1.0 - damp) * g,
                 state["velocity"], grads)
        return tm(lambda p, v: p - lr * v, weights, vel), {"velocity": vel}
    b1, b2, eps = spec["beta1"], spec["beta2"], spec["epsilon"]
    t = state["t"] + 1.0
    m = tm(lambda m_, g: b1 * m_ + (1 - b1) * g, state["m"], grads)
    v = tm(lambda v_, g: b2 * v_ + (1 - b2) * g * g, state["v"], grads)
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    new = tm(lambda p, m_, v_: p - lr * (m_ / bc1)
             / (jnp.sqrt(v_ / bc2) + eps), weights, m, v)
    return new, {"m": m, "v": v, "t": t}


# ----------------------------------------------------------------- norms

@jax.jit
def leaf_norms(tree) -> Dict[str, Any]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def delta_norms(new, old) -> Dict[str, Any]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        new[k].astype(jnp.float32) - old[k].astype(jnp.float32))))
        for k in new}


#: projections a leaf is sketched by
SKETCH_K = 4


def _sketch(i: int, v):
    """`v` projected on SKETCH_K fixed random sign vectors (leaf number
    `i` picks them). For an error e, E[(e . r)^2] = |e|^2: the difference
    of two sketches measures the norm of the difference of two tensors
    that are never held side by side."""
    v = v.astype(jnp.float32)
    key = jax.random.fold_in(jax.random.PRNGKey(0x5CE7C4), i)
    return jnp.stack([jnp.sum(v * jax.random.rademacher(
        jax.random.fold_in(key, j), v.shape, jnp.float32))
        for j in range(SKETCH_K)])


@jax.jit
def sketches(tree) -> Dict[str, Any]:
    return {k: _sketch(i, tree[k]) for i, k in enumerate(sorted(tree))}


@jax.jit
def delta_sketches(new, old) -> Dict[str, Any]:
    return {k: _sketch(i, new[k].astype(jnp.float32)
                       - old[k].astype(jnp.float32))
            for i, k in enumerate(sorted(new))}


def to_floats(d) -> Dict[str, Any]:
    return {k: (float(v) if v.ndim == 0 else [float(x) for x in v])
            for k, v in jax.device_get(d).items()}


# ------------------------------------------------------- the three steps

def train_trace(loss_fn: Callable, fresh: Callable[[], Dict[str, Any]], x,
                y, opt: Dict[str, Any], steps: int = 3,
                row_block: Optional[int] = None) -> Dict[str, Any]:
    """Follow `steps` training steps on the one batch (x, y).

    `fresh()` makes the starting weights (called again at the end for the
    change, so that no second copy is held through the steps);
    `loss_fn(weights, x, y)` is the mean loss over the rows it is given.
    With `row_block`, the gradient is accumulated over blocks of that
    many rows (only for a loss that is a plain mean over rows: no batch
    statistics), so that the float32 step fits the chip. Returns the loss
    before each update, and per leaf the norm and the sketch of the first
    gradient and of the weights' change over all the steps."""
    vg = jax.value_and_grad(loss_fn)
    tm = jax.tree_util.tree_map
    if row_block is None:
        grad_step = jax.jit(vg)
    else:
        n_blocks = x.shape[0] // row_block
        if n_blocks * row_block != x.shape[0]:
            raise ValueError(f"{x.shape[0]} rows do not split into blocks "
                             f"of {row_block}")

        @partial(jax.jit, donate_argnums=(1, 2))
        def accumulate(w, l_acc, g_acc, xb, yb):
            l, g = vg(w, xb, yb)
            return l_acc + l / n_blocks, tm(
                lambda a, b: a + b / n_blocks, g_acc, g)

        def grad_step(w, x, y):
            l_acc = jnp.zeros((), jnp.float32)
            g_acc = tm(jnp.zeros_like, w)
            for i in range(n_blocks):
                rows = slice(i * row_block, (i + 1) * row_block)
                l_acc, g_acc = accumulate(w, l_acc, g_acc, x[rows], y[rows])
            return l_acc, g_acc

    update = jax.jit(partial(opt_update, opt), donate_argnums=(0, 1, 2))
    w = fresh()
    state = opt_init(opt, w)
    losses, gnorm, gsketch = [], None, None
    for step in range(steps):
        loss, grads = grad_step(w, x, y)
        losses.append(float(loss))
        if step == 0:
            gnorm = to_floats(leaf_norms(grads))
            gsketch = to_floats(sketches(grads))
        w, state = update(w, state, grads)
    del grads, state
    start = fresh()
    dnorm = to_floats(delta_norms(w, start))
    dsketch = to_floats(delta_sketches(w, start))
    return {"losses": losses, "gnorm": gnorm, "dnorm": dnorm,
            "gsketch": gsketch, "dsketch": dsketch}
