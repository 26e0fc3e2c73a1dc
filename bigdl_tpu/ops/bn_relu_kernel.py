"""Pallas kernel for the fused BatchNorm/bias + activation tail.

After every conv in ResNet-50, the BatchNorm normalize-affine and the
ReLU each cost an HBM read-modify-write of the [B, H, W, C] activation
unless XLA fuses them into a neighbour. This kernel states the tail as
ONE VMEM-resident pass:

    y = max(x * scale + shift, 0)        (relu=True)
    y =     x * scale + shift            (relu=False — bias+identity tails)

with `scale`/`shift` the per-channel folded BN coefficients the module
already computes (nn/normalization.py folds weight/rsqrt(var) into one
multiply-add). The backward fuses the same way (`custom_vjp`): one kernel
produces dx and per-tile partial reductions for dscale/dshift, so training
never materializes the mask or the pre-activation in HBM. The kernels
first compiled under Mosaic and ran on a v5e in PR 22 (PERF.md).

Routing: `bn_relu` INLINES the exact unfused op sequence on every
backend, the TPU included, with no custom-derivative boundary: XLA fuses
the chain into its neighbours, and autodiff and trajectories stay
bit-identical to the unfused graph (the CI parity gate pins this; a
custom_vjp boundary measurably perturbs XLA's fusion/FMA grouping at the
~1e-7 level). The chip decided it (ROADMAP S1's pair-run, PR 37; the
table is in `bn_relu`'s docstring and PERF.md section 6): a custom call
is a wall to XLA, so the float32 copy of every activation has to exist in
memory, be relaid for the kernel, and be kept as the backward's residual,
and at no ResNet-50 shape did the kernel pair beat the compiler's own
fusion. The Pallas pair stays reachable as `bn_relu_pallas`, and behind
the test hooks: `INTERPRET`, an explicit `interpret=True` (the
raw-kernel parity tests and the `_pick_tile_n` boundary suite), and
`FORCE_PALLAS`, which routes the public op through the custom_vjp for
end-to-end kernel drills (interpreter mode off-TPU) — forward
bit-identical, backward within 1e-6 of the unfused autodiff (the tiled
partial reductions regroup sums). ROADMAP D4 decides what of the pair
stays.

No reference counterpart: the reference's CPU BN calls MKL's fused
batchnorm primitive.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from bigdl_tpu.ops.partitioning import (DATA_AXIS, MODEL_AXIS, per_shard,
                                        split_axis)

# test hook, same convention as ops/attention_kernel.py: run the Pallas
# kernels in interpreter mode (CPU) when True
INTERPRET = False

# test/drill hook: route the public `bn_relu` through the Pallas kernels
# even off-TPU (interpreter mode) — the end-to-end kernel path on CPU
FORCE_PALLAS = False

#: VMEM the row-tile picker sizes one grid step against: every [tile_n, C]
#: in/out block twice (Pallas double-buffers each block against the next
#: step's DMA) plus `_LIVE_F32_TEMPS` f32 intermediates the kernel body
#: keeps live. Half of the v5e's 16 MiB default scoped-VMEM limit, so the
#: estimate may be off by 2x before Mosaic refuses the kernel.
_VMEM_BUDGET_BYTES = 8 * 2 ** 20
_LIVE_F32_TEMPS = 4
_LANES = 128


def _sublanes(itemsize: int) -> int:
    """Rows of one native Mosaic tile: 8 for 4-byte, 16 for 2-byte
    (bf16), 32 for 1-byte dtypes — narrower dtypes pack along sublanes."""
    return 8 * max(1, 4 // itemsize)


def _pick_tile_n(n: int, c: int, tile_n: Optional[int] = None,
                 itemsizes: Tuple[int, ...] = (4, 4)) -> int:
    """Largest row tile that (a) divides n, (b) is a multiple of the
    sublane quantum of the NARROWEST [tile, c] block (`itemsizes`: bytes
    per element of every row-tiled in/out block; 8 rows for f32, 16 for a
    bf16 output or cotangent), and (c) keeps the double-buffered blocks
    plus the live f32 temporaries under the VMEM budget, counting c
    padded to the 128 lanes a VMEM row occupies. Falls back to the full
    n when no candidate exists (tiny or odd row counts: a block equal to
    the array is legal at any size)."""
    q = _sublanes(min(itemsizes))
    if tile_n is None:
        c_pad = -(-max(c, 1) // _LANES) * _LANES
        row_bytes = c_pad * (2 * sum(itemsizes) + 4 * _LIVE_F32_TEMPS)
        tile_n = max(q, _VMEM_BUDGET_BYTES // row_bytes)
    cands = [d for d in range(min(tile_n, n) // q * q, 0, -q) if n % d == 0]
    return cands[0] if cands else n


def _fwd_kernel(x_ref, s_ref, b_ref, o_ref, *, relu: bool):
    """One program = one row tile: fused normalize-affine (+ ReLU).

    Everything runs in f32 registers (the v5e VPU has no bf16 ALU) and
    the one cast to the output dtype comes last. Rounding is monotonic
    and 0 is exact, so max-then-cast equals the unfused graph's
    cast-then-max bit for bit."""
    v = x_ref[...] * s_ref[...] + b_ref[...]
    if relu:
        v = jnp.maximum(v, 0.0)
    o_ref[...] = v.astype(o_ref.dtype)


def _row(v):
    """[C] coefficients as the [1, C] operand the kernels broadcast over
    rows: Mosaic wants >= 2-D operands, and a (1, C) block of a [1, C]
    array is legal because it equals the array's own dims."""
    return v.reshape(1, -1)


def bn_relu_forward(x2, scale, shift, relu: bool = True,
                    out_dtype=None, tile_n: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Pallas forward for the fused tail over a [N, C] view.

    x2: [N, C] f32 activations (the module flattens leading axes)
    scale/shift: [C] folded BN coefficients (f32)
    out_dtype: output dtype (the module's activation dtype, e.g. bf16)
    """
    from jax.experimental import pallas as pl

    if interpret is None:
        interpret = INTERPRET or FORCE_PALLAS
    n, c = x2.shape
    out_dtype = jnp.dtype(out_dtype or x2.dtype)
    tn = _pick_tile_n(n, c, tile_n,
                      (x2.dtype.itemsize, out_dtype.itemsize))
    kernel = functools.partial(_fwd_kernel, relu=relu)
    coef = pl.BlockSpec((1, c), lambda i: (0, 0))  # lint: tiling-ok(equals the [1, C] array)
    return pl.pallas_call(
        kernel,
        grid=(n // tn,),
        in_specs=[pl.BlockSpec((tn, c), lambda i: (i, 0)), coef, coef],
        out_specs=pl.BlockSpec((tn, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, c), out_dtype),
        interpret=interpret,
        name="bn_relu_fwd",
    )(x2, _row(scale), _row(shift))


def _bwd_kernel(x_ref, s_ref, b_ref, g_ref, dx_ref, ds_ref, db_ref, *,
                relu: bool):
    """One program = one row tile of the fused backward: recompute the
    pre-activation in VMEM (nothing was saved to HBM), apply the ReLU
    mask to the cotangent, and emit dx plus this tile's PARTIAL
    dscale/dshift row sums (the caller reduces over tiles). All in f32:
    the cotangent widens on load, and the mask compares the f32
    pre-activation (its sign survives the forward's cast, bar f32
    subnormals the TPU flushes anyway)."""
    x = x_ref[...]
    s = s_ref[...]
    g = g_ref[...].astype(jnp.float32)
    if relu:
        g = jnp.where(x * s + b_ref[...] > 0, g, 0.0)
    dx_ref[...] = g * s
    ds_ref[0] = jnp.sum(g * x, axis=0, keepdims=True)
    db_ref[0] = jnp.sum(g, axis=0, keepdims=True)


def bn_relu_backward(x2, scale, shift, g2, relu: bool = True,
                     tile_n: Optional[int] = None,
                     interpret: Optional[bool] = None
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pallas backward for the fused tail: (dx [N,C], dscale [C],
    dshift [C]) from the cotangent g2 [N, C] (activation dtype)."""
    from jax.experimental import pallas as pl

    if interpret is None:
        interpret = INTERPRET or FORCE_PALLAS
    n, c = x2.shape
    tn = _pick_tile_n(n, c, tile_n,
                      (x2.dtype.itemsize, g2.dtype.itemsize, 4))
    n_tiles = n // tn
    kernel = functools.partial(_bwd_kernel, relu=relu)
    rows = pl.BlockSpec((tn, c), lambda i: (i, 0))
    coef = pl.BlockSpec((1, c), lambda i: (0, 0))  # lint: tiling-ok(equals the [1, C] array)
    # per-tile partial sums ride as [n_tiles, 1, C] with block (1, 1, C):
    # a (1, C) block over [n_tiles, C] breaks Mosaic's rule that the last
    # two block dims divide (8, 128) or equal the array's (same layout as
    # the flash kernel's lse, ops/attention_kernel.py)
    part = pl.BlockSpec((1, 1, c), lambda i: (i, 0, 0))
    dx, ds_part, db_part = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[rows, coef, coef, rows],
        out_specs=[rows, part, part],
        out_shape=[
            jax.ShapeDtypeStruct((n, c), jnp.float32),
            jax.ShapeDtypeStruct((n_tiles, 1, c), jnp.float32),
            jax.ShapeDtypeStruct((n_tiles, 1, c), jnp.float32),
        ],
        interpret=interpret,
        name="bn_relu_bwd",
    )(x2, _row(scale), _row(shift), g2)
    return dx, jnp.sum(ds_part, axis=(0, 1)), jnp.sum(db_part, axis=(0, 1))


# ---------------------------------------------------------------------- #
# reference (unfused-equivalent) expressions the kernels are held to
# ---------------------------------------------------------------------- #

def _reference_forward(x, scale, shift, relu: bool, out_dtype):
    """EXACTLY the unfused graph's op sequence (normalization.py tail,
    then jax.nn.relu = maximum(·, 0)): multiply-add in x's dtype, cast,
    max. Elementwise, so XLA fuses it — and the CPU CI fused-vs-unfused
    trajectory parity gate is bit-exact."""
    y = (x * scale + shift).astype(out_dtype)
    return jnp.maximum(y, 0) if relu else y


def _reference_backward(x, scale, shift, g, relu: bool, out_dtype):
    """The unfused graph's autodiff, written out: relu's custom_jvp mask
    on the cast pre-activation, convert adjoint back to f32, then the
    broadcast-multiply adjoints."""
    if relu:
        pre = (x * scale + shift).astype(out_dtype)
        g = jnp.where(pre > 0, g, 0)
    g32 = g.astype(x.dtype)
    axes = tuple(range(x.ndim - 1))
    return g32 * scale, jnp.sum(g32 * x, axis=axes), jnp.sum(g32, axis=axes)


# ---------------------------------------------------------------------- #
# partitioning: how the kernels split over a device mesh
# ---------------------------------------------------------------------- #
# A Mosaic custom call carries no partitioning rule: under a multi-device
# `jit` its lowering refuses outright ("Mosaic kernels cannot be
# automatically partitioned"), and libtpu has no `custom_partitioning`.
# The tail is elementwise over rows and channels, so under the mesh the
# training loop traces in (ops/partitioning.py) each device runs the
# kernel on its own shard: batch rows over 'data', channels over 'model'
# while every shard keeps whole 128-lane rows (where a column-parallel
# conv leaves them). No activation moves; the one collective is the
# backward's psum of the [C] partial sums over the row axis.

def _flat(x):
    return x.reshape(-1, x.shape[-1])


def _specs(x):
    """(mesh axis splitting the rows, [..., C] spec, [C] spec) for x
    under the context mesh; all None where there is nothing to split."""
    rows = split_axis(DATA_AXIS, x.shape[0])
    chans = split_axis(MODEL_AXIS, x.shape[-1], _LANES)
    return rows, P(rows, *(None,) * (x.ndim - 2), chans), P(chans)


def _forward_nd(x, scale, shift, relu, out_dtype):
    def local(x, scale, shift):
        return bn_relu_forward(_flat(x), scale, shift, relu,
                               out_dtype).reshape(x.shape)
    _, xs, cs = _specs(x)
    return per_shard(local, (xs, cs, cs), xs)(x, scale, shift)


def _backward_nd(x, scale, shift, g, relu):
    rows, xs, cs = _specs(x)

    def local(x, scale, shift, g):
        dx, ds, db = bn_relu_backward(_flat(x), scale, shift, _flat(g), relu)
        if rows:
            ds, db = jax.lax.psum((ds, db), rows)
        return dx.reshape(x.shape), ds, db
    return per_shard(local, (xs, cs, cs, xs), (xs, cs, cs))(
        x, scale, shift, g)


# ---------------------------------------------------------------------- #
# public op: the inline expressions; the custom_vjp kernel pair by name
# ---------------------------------------------------------------------- #

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def bn_relu_pallas(x, scale, shift, relu: bool = True, out_dtype=None):
    """The fused tail as a custom_vjp over the Pallas kernels (forward
    AND backward fuse), compiled by Mosaic; interpreter mode only under
    the `INTERPRET`/`FORCE_PALLAS` test hooks. `relu`/`out_dtype` are
    static. No model reaches it through `bn_relu` (PR 37): chip_smoke.py
    and the parity tests call it by name."""
    return _forward_nd(x, scale, shift, relu,
                       jnp.dtype(out_dtype or x.dtype))


def _bn_relu_fwd_rule(x, scale, shift, relu, out_dtype):
    return bn_relu_pallas(x, scale, shift, relu, out_dtype), (x, scale,
                                                              shift)


def _bn_relu_bwd_rule(relu, out_dtype, res, g):
    x, scale, shift = res
    return _backward_nd(x, scale, shift, g, relu)


bn_relu_pallas.defvjp(_bn_relu_fwd_rule, _bn_relu_bwd_rule)


def bn_relu(x, scale, shift, relu: bool = True, out_dtype=None):
    """Fused `activation(x * scale + shift)` over the trailing channel
    axis of x (any leading rank).

    On every backend this inlines the EXACT unfused op sequence
    (multiply-add, cast, `jax.nn.relu`) with no custom-derivative
    boundary, so the fused graph autodiffs bit-identically to the unfused
    one and XLA fuses the chain into its neighbours. The chip's table
    (v5e, ResNet-50 b128 bf16 step, device ms with the Mosaic pair at one
    class of tails alone, PR 37): nowhere 50.39; stem [1605632, 64] 59.91;
    C=64 65.90; C=128 57.92; C=256 55.62; C=512 51.79; everywhere 89.76.
    The rule: a shape keeps the pair only where it beat "nowhere"; none
    did, so no backend takes it. `FORCE_PALLAS` (a test hook) routes
    through `bn_relu_pallas`. With scale=1 this is the bias+activation
    tail; nn/normalization.py feeds it the folded BN coefficients."""
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    if FORCE_PALLAS:
        return bn_relu_pallas(x, scale, shift, relu, out_dtype)
    y = (x * scale + shift).astype(out_dtype)
    # jax.nn.relu, not jnp.maximum: its custom_jvp zeroes the gradient at
    # 0 exactly like the standalone ReLU module the pattern replaced
    return jax.nn.relu(y) if relu else y


def count_fused_calls(jaxpr) -> int:
    """Number of `bn_relu_pallas` custom_vjp call sites in a (closed)
    jaxpr, recursing through sub-jaxprs — the jaxpr-level fusion
    assertion the suite and chip_smoke.py pin (a fused graph on the
    kernel route must carry one per matched BN+ReLU pair)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    total = 0
    for eqn in inner.eqns:
        if eqn.primitive.name == "custom_vjp_call" and \
                eqn.params["call_jaxpr"].jaxpr.debug_info.func_name == \
                bn_relu_pallas.__name__:
            total += 1
            continue
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr", "body_jaxpr"):
            if key in eqn.params:
                total += count_fused_calls(eqn.params[key])
                break
    return total
