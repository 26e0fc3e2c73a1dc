"""Pallas kernel for the space-to-depth ResNet stem convolution.

The round-3 perf work (docs/PERF.md) identified the stem as the last
memory-bound MXU-hostile stage: after the 2x2 space-to-depth restatement
(nn/conv.py SpaceToDepthStemConvolution) the op is a stride-1 kt x kt
conv over C2 = 4*C_in channels — for ResNet-50, 4x4 over 12 channels at
112x112 — whose reduction depth (12) starves the 128-lane MXU when
expressed as a plain conv.

This kernel restates it once more, as an im2col GEMM assembled ON THE
FLY in VMEM: each program owns a (batch, row-tile) cell, gathers its
kt*kt taps from the VMEM-resident padded image into a
[tile_h * W, kt*kt*C2] patch tile (192-deep for ResNet-50 — 1.5 MXU
passes instead of 16 shallow 12-deep accumulations), and runs a single
[tile, 192] @ [192, C_out] matmul, with the bias fused. No patch matrix
ever exists in HBM (the XLA `conv_general_dilated_patches` fallback in
nn/conv.py materializes it per microbatch).

Forward-only by design: the stem backward is a small share of the step
(PERF.md), so `stem_conv` wraps the kernel in `jax.custom_vjp` with the
mathematically-identical XLA conv supplying the gradients.

MEASURED OUTCOME (v5e, 2026-08-01, docs/PERF.md round-5 section): after
two Mosaic-legality fixes (pre-rolled dx shifts, W grid tiling) the
kernel compiles and is bit-close to the XLA restatement on hardware —
and is 9.4x SLOWER (40.9 ms vs 4.37 ms at b128; -44.5% through the full
framework loop). The 12-channel taps occupy 12/128 lanes of every
vector register, wasting ~10x vector bandwidth that no tile shape
recovers, while XLA's conv keeps full layouts throughout. The kernel is
therefore env-gated (`BIGDL_TPU_PALLAS_STEM=1`), kept as a
parity-tested negative result; the XLA space-to-depth restatement
(nn/conv.py) is the production stem.

No reference counterpart (the reference's CPU im2col is
layout-insensitive; this exists because of the MXU's tiling rules).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

# test hook, same convention as ops/attention_kernel.py
INTERPRET = False


def _pick_tile_w(w: int, tile_w: int) -> int:
    """Largest Mosaic-legal W tile <= tile_w: must divide w AND be a
    multiple of 8 (the sublane block dim must divide 8 or equal the full
    array dim — live-TPU finding, round 5). Falls back to the full width
    when no candidate exists."""
    cands = [d for d in range(min(tile_w, w), 0, -1)
             if w % d == 0 and d % 8 == 0]
    return cands[0] if cands else w


def _stem_kernel(x_ref, w_ref, b_ref, o_ref, *, kt: int, c2: int,
                 tile_h: int, tile_w: int, n_out: int):
    """One program = one (batch, row-tile): assemble the patch tile and
    run the fused GEMM + bias.

    The caller hands the padded image PRE-SHIFTED along W, one copy per
    dx tap, stacked on a leading axis. Slicing a tap at a nonzero dx
    offset gives it a nonzero sublane offset, and Mosaic's concatenate
    refuses operands whose offsets differ on a non-concat dimension
    (live-TPU finding, round 5: "result/input offset mismatch on
    non-concat dimension"). With the shifts hoisted to XLA, every tap
    here is sliced at W offset 0, so all concat operands share sublane
    offset 0 and only differ on the lane (concat) dim — which Mosaic
    handles. dy stays an in-kernel slice: H is an untiled leading dim of
    the 3D vector, so dy offsets carry no layout."""
    from jax.experimental import pallas as pl

    j = pl.program_id(1)
    taps = []
    for dx in range(kt):            # static tap loop -> fused VMEM copies
        # rows this tile reads from the dx-shifted copy:
        # [tile_h + kt - 1, tile_w, c2], W offset 0 by construction (the
        # W tile itself is selected by the block index map)
        rows = x_ref[dx, 0, pl.ds(j * tile_h, tile_h + kt - 1), :, :]
        rows = rows.astype(jnp.float32)
        for dy in range(kt):
            taps.append(rows[dy:dy + tile_h])
    # kernel layout is (dy, dx, c) tap-major — reorder the dx-major list
    patches = jnp.concatenate(
        [taps[dx * kt + dy] for dy in range(kt) for dx in range(kt)],
        axis=-1)                              # [tile_h, tile_w, kt*kt*c2]
    patches = patches.reshape(tile_h * tile_w, kt * kt * c2)
    acc = jax.lax.dot_general(
        patches, w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    acc = acc + b_ref[...].astype(jnp.float32)
    o_ref[0] = acc.reshape(tile_h, tile_w, n_out).astype(o_ref.dtype)


def stem_conv_forward(x2, wk, bias, pad_front: int, pad_rear: int,
                      tile_h: int = 8, tile_w: int = 56,
                      interpret: Optional[bool] = None):
    """Pallas forward for the s2d stem.

    x2:  [B, H, W, C2] space-to-depth input (H = W = 112 for R50)
    wk:  [kt, kt, C2, O] transformed kernel (nn/conv.py re-blocking)
    bias: [O] or None
    pad_front/pad_rear: the stem's asymmetric padding.
    """
    from jax.experimental import pallas as pl

    if interpret is None:
        interpret = INTERPRET
    b, h, w, c2 = x2.shape
    kt, _, _, n_out = wk.shape
    assert pad_front + pad_rear == kt - 1, (pad_front, pad_rear, kt)
    xp = jnp.pad(x2, ((0, 0), (pad_front, pad_rear),
                      (pad_front, pad_rear), (0, 0)))
    hp = xp.shape[1]
    while h % tile_h:
        tile_h //= 2               # h is even for every real stem input
    # w tiling bounds live VMEM registers (the full-width tile OOMed
    # scoped vmem at 224x224/b128)
    tile_w = _pick_tile_w(w, tile_w)
    # one W-shifted copy of the padded image per dx tap, trimmed back to
    # the output width (see _stem_kernel: in-kernel dx slices are
    # Mosaic-illegal under concatenate; the roll is a cheap XLA op paid
    # once per step, the wraparound columns land past w and are trimmed)
    xs = jnp.stack([jnp.roll(xp, -dx, axis=2)[:, :, :w] for dx in range(kt)])
    w2 = wk.reshape(-1, n_out)     # [kt*kt*c2, O] — tap-major like taps
    # nn/conv.py kernel layout is (dy, dx, c) tap order; the kernel's
    # concat reorders its dx-major tap list to the same (dy, dx) order,
    # so a plain reshape lines up.
    bvec = bias if bias is not None else jnp.zeros((n_out,), x2.dtype)

    kernel = functools.partial(_stem_kernel, kt=kt, c2=c2, tile_h=tile_h,
                               tile_w=tile_w, n_out=n_out)
    out = pl.pallas_call(
        kernel,
        grid=(b, h // tile_h, w // tile_w),
        in_specs=[
            pl.BlockSpec((kt, 1, hp, tile_w, c2),
                         lambda i, j, kw: (0, i, 0, kw, 0)),
            pl.BlockSpec((kt * kt * c2, n_out), lambda i, j, kw: (0, 0)),
            pl.BlockSpec((n_out,), lambda i, j, kw: (0,)),
        ],
        out_specs=pl.BlockSpec((1, tile_h, tile_w, n_out),
                               lambda i, j, kw: (i, j, kw, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, w, n_out), x2.dtype),
        interpret=interpret,
        name="stem_conv",
    )(xs, w2, bvec)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def stem_conv(x2, wk, bias, pad_front: int, pad_rear: int):
    """s2d stem conv: Pallas forward, XLA-conv gradients (identical math
    — lax.conv_general_dilated with the same padding).

    The caller (nn/conv.py) owns the routing decision; calling this IS
    choosing the kernel, so off-TPU it runs in interpreter mode rather
    than silently substituting the XLA path (which would make A/B
    comparisons meaningless)."""
    interpret = jax.default_backend() != "tpu"
    return stem_conv_forward(x2, wk, bias, pad_front, pad_rear,
                             interpret=interpret)


def _stem_xla(x2, wk, bias, pad_front, pad_rear):
    y = lax.conv_general_dilated(
        x2, wk, window_strides=(1, 1),
        padding=((pad_front, pad_rear), (pad_front, pad_rear)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if bias is not None:
        y = y + bias
    return y


def _stem_fwd_rule(x2, wk, bias, pad_front, pad_rear):
    return stem_conv(x2, wk, bias, pad_front, pad_rear), (x2, wk, bias)


def _stem_bwd_rule(pad_front, pad_rear, res, g):
    x2, wk, bias = res
    _, vjp = jax.vjp(
        lambda a, b, c: _stem_xla(a, b, c, pad_front, pad_rear),
        x2, wk, bias)
    return vjp(g)


stem_conv.defvjp(_stem_fwd_rule, _stem_bwd_rule)
