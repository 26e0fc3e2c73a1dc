"""Attention kernels: online-softmax blockwise attention + Pallas flash
forward.

No counterpart exists in the reference (SURVEY.md §5.7: BigDL has no
attention layer at all); this is the TPU-native long-context foundation the
new framework adds. Design:

- `blockwise_attention` — pure-XLA flash-style attention: lax.scan over KV
  blocks carrying (acc, row_max, row_sum). O(T) memory in the KV direction,
  differentiable by autodiff (scan rematerialises), and reusable as the
  inner step of ring attention (accumulators can be carried across devices).
- `flash_attention` — Pallas TPU forward kernel (one (batch*head, q-block)
  program per grid cell, KV streamed through VMEM) wrapped in
  `jax.custom_vjp`; backward recomputes via the blockwise XLA path.

Layouts: q, k, v are [B, H, T, D] (head-major, the layout that keeps the
per-head [T, D] @ [D, T] matmuls MXU-shaped).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from bigdl_tpu.ops.partitioning import (DATA_AXIS, MODEL_AXIS, per_shard,
                                        split_axis)

NEG_INF = -1e30

# test hook: run the Pallas kernels in interpreter mode (CPU) when True —
# lets the full custom_vjp fwd+bwd path run off-TPU in the suite
INTERPRET = False


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def naive_attention(q, k, v, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    mask: Optional[jax.Array] = None):
    """Reference O(T^2)-memory attention (for tests and tiny shapes)."""
    sm_scale = sm_scale or q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * sm_scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        idx_q = lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        idx_k = lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        s = jnp.where(idx_q >= idx_k, s, NEG_INF)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _block_step(q, k_blk, v_blk, acc, m, l, sm_scale,
                q_offset, k_offset, causal):
    """One online-softmax update of (acc, m, l) with a KV block.

    q: [B,H,Tq,D]; k_blk/v_blk: [B,H,Bk,D]; acc: [B,H,Tq,D];
    m, l: [B,H,Tq] running max / normaliser. Offsets are the global
    positions of q[...,0,:] and k_blk[...,0,:] (for causal masking across
    ring/sequence shards)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk) * sm_scale  # [B,H,Tq,Bk]
    if causal:
        tq, bk = s.shape[-2], s.shape[-1]
        gq = lax.broadcasted_iota(jnp.int32, (tq, bk), 0) + q_offset
        gk = lax.broadcasted_iota(jnp.int32, (tq, bk), 1) + k_offset
        s = jnp.where(gq >= gk, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # guard fully-masked rows (m_new == NEG_INF): exp(s - NEG_INF) would
    # overflow; shift by 0 there instead.
    shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - shift[..., None])
    scale_old = jnp.exp(jnp.where(m <= NEG_INF / 2, NEG_INF, m) - shift)
    scale_old = jnp.where(m <= NEG_INF / 2, 0.0, scale_old)
    l_new = l * scale_old + jnp.sum(p, axis=-1)
    acc_new = acc * scale_old[..., None] + jnp.einsum("bhqk,bhkd->bhqd",
                                                      p, v_blk)
    return acc_new, m_new, l_new


def attention_state_init(q):
    """Fresh (acc, m, l) accumulators for online-softmax attention.

    Derived arithmetically from q (not fresh constants) so that under
    shard_map the accumulators inherit q's varying-manual-axes type — a
    constant init would fail lax.scan's carry typing inside ring attention."""
    zero = q.astype(jnp.float32) * 0.0
    row = zero[..., 0]
    return (zero, row + NEG_INF, row)


def attention_state_finish(acc, m, l):
    """Normalize blockwise partial sums into the final attention output."""
    den = jnp.where(l == 0.0, 1.0, l)
    return acc / den[..., None]


def blockwise_attention(q, k, v, causal: bool = False,
                        sm_scale: Optional[float] = None,
                        block_k: int = 512,
                        q_offset: int = 0, k_offset: int = 0,
                        carry: Optional[Tuple] = None,
                        finish: bool = True):
    """Flash-style attention via lax.scan over KV blocks.

    With `carry`/`finish=False` the accumulators are exposed so callers
    (ring attention) can continue the same softmax across KV shards living
    on other devices."""
    orig_dtype = q.dtype
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    sm_scale = sm_scale or q.shape[-1] ** -0.5
    b, h, tk, d = kf.shape
    block_k = min(block_k, tk)
    n_blocks = -(-tk // block_k)
    pad = n_blocks * block_k - tk
    if pad:
        kf = jnp.pad(kf, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, 0), (0, pad), (0, 0)))
    # reshape to [n_blocks, B, H, block_k, D] for scan
    ks = jnp.moveaxis(kf.reshape(b, h, n_blocks, block_k, d), 2, 0)
    vs = jnp.moveaxis(vf.reshape(b, h, n_blocks, block_k, d), 2, 0)

    state = carry if carry is not None else attention_state_init(qf)

    def step(state, inp):
        i, k_blk, v_blk = inp
        acc, m, l = state
        acc, m, l = _block_step(qf, k_blk, v_blk, acc, m, l, sm_scale,
                                q_offset, k_offset + i * block_k, causal)
        return (acc, m, l), None

    if pad:
        # ragged tail: scan the full blocks, then one explicit step on the
        # unpadded tail (padded keys must never receive softmax weight)
        full = tk // block_k
        if full:
            idxs = jnp.arange(full)
            state, _ = lax.scan(step, state,
                                (idxs, ks[:full], vs[:full]))
        tail_k = kf[:, :, full * block_k: tk]
        tail_v = vf[:, :, full * block_k: tk]
        acc, m, l = state
        state = _block_step(qf, tail_k, tail_v, acc, m, l, sm_scale,
                            q_offset, k_offset + full * block_k, causal)
    else:
        idxs = jnp.arange(n_blocks)
        state, _ = lax.scan(step, state, (idxs, ks, vs))

    if not finish:
        return state
    out = attention_state_finish(*state)
    return out.astype(orig_dtype)


# --------------------------------------------------------------------------- #
# Pallas flash forward (TPU fast path)
# --------------------------------------------------------------------------- #

def _kernel_block_update(q, k_blk, v_blk, acc, m, l, sm_scale, causal,
                         q_off, k_off):
    """One online-softmax update inside a Pallas kernel — the single
    numerics body shared by the dense forward and the ring-hop carry
    kernels (they must stay provably identical)."""
    s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale
    if causal:
        gq = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + q_off
        gk = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + k_off
        s = jnp.where(gq >= gk, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - shift[:, None])
    scale_old = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - shift))
    l_new = l * scale_old + jnp.sum(p, axis=-1)
    acc_new = acc * scale_old[:, None] + jax.lax.dot_general(
        p, v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return acc_new, m_new, l_new


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                      sm_scale: float, causal: bool, seq_k: int):
    """One program = one (batch*head, q-block); K/V streamed with
    fori_loop over VMEM-resident refs sliced dynamically. Also emits the
    per-row logsumexp the backward kernels reconstruct softmax from."""
    from jax.experimental import pallas as pl

    q = q_ref[0].astype(jnp.float32)          # [block_q, d]
    block_q, d = q.shape
    i_q = pl.program_id(1)
    q_off = i_q * block_q

    n_kb = seq_k // block_k

    def body(ib, carry):
        acc, m, l = carry
        k_blk = k_ref[0, pl.ds(ib * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(ib * block_k, block_k), :].astype(jnp.float32)
        return _kernel_block_update(q, k_blk, v_blk, acc, m, l, sm_scale,
                                    causal, q_off, ib * block_k)

    acc = jnp.zeros((block_q, d), jnp.float32)
    m = jnp.full((block_q,), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    if causal:
        # only blocks with k_start <= q_end participate
        n_needed = jnp.minimum(n_kb, (q_off + block_q + block_k - 1)
                               // block_k)
        acc, m, l = jax.lax.fori_loop(0, n_needed, body, (acc, m, l))
    else:
        acc, m, l = jax.lax.fori_loop(0, n_kb, body, (acc, m, l))
    den = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / den[:, None]).astype(o_ref.dtype)
    # logsumexp per row; fully-masked rows get shift=0, den=1 -> lse=0,
    # and the backward's exp(NEG_INF - 0) correctly vanishes.
    # lse rides as [bh, 1, T]: Mosaic requires the 2nd-minor block dim to
    # divide 8 or equal the array dim, which a (1, block_q) block over
    # [bh, T] violates whenever block_q < T (live-TPU finding, round 5)
    shift = jnp.where(m <= NEG_INF / 2, 0.0, m)
    lse_ref[0, 0] = shift + jnp.log(den)


def flash_attention_forward(q, k, v, causal: bool = False,
                            sm_scale: Optional[float] = None,
                            block_q: int = 256, block_k: int = 512,
                            interpret: Optional[bool] = None,
                            return_lse: bool = False,
                            window: Optional[int] = None):
    """Pallas flash-attention forward. q,k,v: [B,H,T,D]; T must be padded to
    the block sizes by the caller (`flash_attention` handles it).
    `return_lse=True` also returns the [B,H,T] logsumexp (backward input).

    `k`, `v` may hold fewer heads than `q` ([B, Hkv, T, D], H a multiple
    of Hkv: query head j reads K/V head j // (H // Hkv)), `v` may have a
    width of its own ([B, Hkv, T, Dv]: latent attention's values are
    narrower than its keys; the result is then Dv wide and the scale
    still the query's width's), and `window` keeps, for the query at
    position p, the keys at p - window + 1 .. p. Any of the three takes
    the grouped kernel below (causal, no logsumexp: the serving
    prefill's); with none this is the call it always was."""
    from jax.experimental import pallas as pl

    if interpret is None:
        interpret = INTERPRET
    if window is not None or k.shape[1] != q.shape[1] \
            or v.shape[-1] != q.shape[-1]:
        if not causal or return_lse:
            raise ValueError("the windowed / grouped-query forward is "
                             "causal and returns no logsumexp")
        return _flash_forward_grouped(q, k, v, sm_scale, block_q, block_k,
                                      interpret, window)
    b, h, tq, d = q.shape
    tk = k.shape[2]
    sm_scale = sm_scale or d ** -0.5
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    assert tq % block_q == 0 and tk % block_k == 0
    bh = b * h
    qr = q.reshape(bh, tq, d)
    kr = k.reshape(bh, tk, d)
    vr = v.reshape(bh, tk, d)

    kernel = functools.partial(_flash_fwd_kernel, block_k=block_k,
                               sm_scale=sm_scale, causal=causal, seq_k=tk)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, tq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, tq), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qr, kr, vr)
    out = out.reshape(b, h, tq, d)
    if return_lse:
        return out, lse.reshape(b, h, tq)
    return out


def _flash_carry_kernel(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                        off_ref, oacc_ref, om_ref, ol_ref, *, block_k: int,
                        sm_scale: float, causal: bool, seq_k: int):
    """Online-softmax update of carried (acc, m, l) with this device's
    KV shard — the ring-attention hop, in Pallas. Offsets arrive as data
    (off_ref = [q_offset, k_offset]) because ring hops compute them from
    lax.axis_index, a traced value."""
    from jax.experimental import pallas as pl

    q = q_ref[0].astype(jnp.float32)            # [bq, d]
    block_q, d = q.shape
    acc = acc_ref[0].astype(jnp.float32)
    m = m_ref[0, 0].astype(jnp.float32)         # [bh, 1, T] ride (see
    l = l_ref[0, 0].astype(jnp.float32)         # _flash_fwd_kernel lse)
    q_off = off_ref[0] + pl.program_id(1) * block_q
    k_off = off_ref[1]
    n_kb = seq_k // block_k

    def body(ib, carry):
        acc, m, l = carry
        k_blk = k_ref[0, pl.ds(ib * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(ib * block_k, block_k), :].astype(jnp.float32)
        return _kernel_block_update(q, k_blk, v_blk, acc, m, l, sm_scale,
                                    causal, q_off, k_off + ib * block_k)

    if causal:
        # dynamic bound: offsets are traced; blocks fully in the masked
        # future contribute nothing — skip them
        n_needed = jnp.clip(
            (q_off + block_q - k_off + block_k - 1) // block_k, 0, n_kb)
        acc, m, l = jax.lax.fori_loop(0, n_needed, body, (acc, m, l))
    else:
        acc, m, l = jax.lax.fori_loop(0, n_kb, body, (acc, m, l))
    oacc_ref[0] = acc
    om_ref[0, 0] = m
    ol_ref[0, 0] = l


def _offs_spec(interpret):
    from jax.experimental import pallas as pl
    if interpret:
        return pl.BlockSpec((2,), lambda i, j: (0,))
    from jax.experimental.pallas import tpu as pltpu
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def flash_attention_carry(q, k, v, carry, causal: bool = False,
                          sm_scale: Optional[float] = None,
                          q_offset=0, k_offset=0, block_q: int = 256,
                          block_k: int = 512,
                          interpret: Optional[bool] = None):
    """One ring-attention hop through the Pallas kernel: continue the
    online softmax carried in `carry` (= attention_state_init shapes)
    with this KV shard. Returns the updated (acc, m, l) — call
    `attention_state_finish` after the last hop. Shapes that do not tile
    the kernel blocks take the XLA blockwise step (same math)."""
    if interpret is None:
        interpret = INTERPRET
    from jax.experimental import pallas as pl

    b, h, tq, d = q.shape
    tk = k.shape[2]
    sm_scale = sm_scale or d ** -0.5
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    if tq % block_q or tk % block_k:
        return blockwise_attention(q, k, v, causal=causal,
                                   sm_scale=sm_scale, block_k=block_k,
                                   q_offset=q_offset, k_offset=k_offset,
                                   carry=carry, finish=False)
    bh = b * h
    acc, m, l = carry
    offs = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(k_offset, jnp.int32)])
    kernel = functools.partial(_flash_carry_kernel, block_k=block_k,
                               sm_scale=sm_scale, causal=causal, seq_k=tk)
    oacc, om, ol = pl.pallas_call(
        kernel,
        grid=(bh, tq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
            # offsets feed control flow (the causal loop bound):
            # Mosaic requires such scalars in SMEM; interpret mode
            # ignores the memory space
            _offs_spec(interpret),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, tq), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, tq), jnp.float32),
        ],
        interpret=interpret,
        name="flash_carry",
    )(q.reshape(bh, tq, d), k.reshape(bh, tk, d), v.reshape(bh, tk, d),
      acc.reshape(bh, tq, d), m.reshape(bh, 1, tq), l.reshape(bh, 1, tq),
      offs)
    return (oacc.reshape(b, h, tq, d), om.reshape(b, h, tq),
            ol.reshape(b, h, tq))


# --------------------------------------------------------------------------- #
# Pallas flash backward (TPU fast path for training)
# --------------------------------------------------------------------------- #

def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, block_k: int, sm_scale: float,
                         causal: bool, seq_k: int):
    """dq for one (batch*head, q-block): stream K/V blocks, rebuild the
    softmax rows from the saved logsumexp (no [T,T] materialization), and
    accumulate dq = sum_k (p * (dO V^T - delta)) K * scale."""
    from jax.experimental import pallas as pl

    q = q_ref[0].astype(jnp.float32)            # [bq, d]
    do = do_ref[0].astype(jnp.float32)          # [bq, d]
    lse = lse_ref[0, 0].astype(jnp.float32)     # [bq] ([bh, 1, T] ride)
    delta = delta_ref[0, 0].astype(jnp.float32)  # [bq]
    block_q, d = q.shape
    q_off = pl.program_id(1) * block_q
    n_kb = seq_k // block_k

    def body(ib, dq):
        k_blk = k_ref[0, pl.ds(ib * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(ib * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        if causal:
            gq = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + q_off
            gk = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
                + ib * block_k
            s = jnp.where(gq >= gk, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])           # [bq, bk]
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        return dq + jax.lax.dot_general(ds, k_blk, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq = jnp.zeros((block_q, d), jnp.float32)
    if causal:
        n_needed = jnp.minimum(n_kb, (q_off + block_q + block_k - 1)
                               // block_k)
        dq = jax.lax.fori_loop(0, n_needed, body, dq)
    else:
        dq = jax.lax.fori_loop(0, n_kb, body, dq)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q: int, sm_scale: float,
                          causal: bool, seq_q: int):
    """dk and dv for one (batch*head, k-block): stream Q/dO blocks.
    dv = sum_q p^T dO;   dk = sum_q (p * (dO V^T - delta))^T Q * scale."""
    from jax.experimental import pallas as pl

    k_blk = k_ref[0].astype(jnp.float32)        # [bk, d]
    v_blk = v_ref[0].astype(jnp.float32)        # [bk, d]
    block_k, d = k_blk.shape
    k_off = pl.program_id(1) * block_k
    n_qb = seq_q // block_q

    def body(ib, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(ib * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(ib * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(ib * block_q, block_q)].astype(
            jnp.float32)
        delta = delta_ref[0, 0, pl.ds(ib * block_q, block_q)].astype(
            jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                        # [bq, bk]
        if causal:
            gq = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
                + ib * block_q
            gk = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + k_off
            s = jnp.where(gq >= gk, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])           # [bq, bk]
        dv = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    dk = jnp.zeros((block_k, d), jnp.float32)
    dv = jnp.zeros((block_k, d), jnp.float32)
    if causal:
        # only q-blocks whose END reaches past this k-block participate
        start = k_off // block_q
        dk, dv = jax.lax.fori_loop(start, n_qb, body, (dk, dv))
    else:
        dk, dv = jax.lax.fori_loop(0, n_qb, body, (dk, dv))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def flash_attention_backward(q, k, v, out, lse, g, causal: bool = False,
                             sm_scale: Optional[float] = None,
                             block_q: int = 256, block_k: int = 512,
                             interpret: Optional[bool] = None):
    """Pallas flash-attention backward: (dq, dk, dv) from the saved
    forward logsumexp — two kernels (dq over q-blocks; dk/dv over
    k-blocks), each rebuilding its softmax tile on the fly, so the
    training path never materializes [T, T] either."""
    from jax.experimental import pallas as pl

    if interpret is None:
        interpret = INTERPRET
    b, h, tq, d = q.shape
    tk = k.shape[2]
    sm_scale = sm_scale or d ** -0.5
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    assert tq % block_q == 0 and tk % block_k == 0
    bh = b * h
    qr, kr, vr = (x.reshape(bh, -1, d) for x in (q, k, v))
    dor = g.reshape(bh, tq, d)
    lser = lse.reshape(bh, 1, tq)  # [bh, 1, T] ride (see _flash_fwd_kernel)
    # delta_i = rowsum(dO * O): tiny elementwise reduce, XLA fuses it
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, tq)

    dq_kernel = functools.partial(_flash_bwd_dq_kernel, block_k=block_k,
                                  sm_scale=sm_scale, causal=causal,
                                  seq_k=tk)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, tq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qr, kr, vr, dor, lser, delta)

    dkv_kernel = functools.partial(_flash_bwd_dkv_kernel, block_q=block_q,
                                   sm_scale=sm_scale, causal=causal,
                                   seq_q=tq)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh, tk // block_k),
        in_specs=[
            pl.BlockSpec((1, tq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, tq), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, tq), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tk, d), v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qr, kr, vr, dor, lser, delta)
    return (dq.reshape(b, h, tq, d), dk.reshape(b, h, tk, d),
            dv.reshape(b, h, tk, d))


# --------------------------------------------------------------------------- #
# grouped-query / sliding-window forward (serving prefill)
# --------------------------------------------------------------------------- #

def _grouped_kv_range(j, block_q: int, block_k: int, window: Optional[int]):
    """First and last K/V block that the causal (and windowed) mask leaves
    anything of for q block `j`."""
    last = ((j + 1) * block_q - 1) // block_k
    if window is None:
        return 0, last
    return jnp.maximum(j * block_q - (window - 1), 0) // block_k, last


def _flash_fwd_grouped_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                              l_ref, *, block_q: int, block_k: int,
                              sm_scale: float, window: Optional[int]):
    """One program = one (batch * K/V head, q block, K/V step). The q
    block holds the `group` query heads that share the K/V head, folded
    into its rows, so a K/V block is read once for all of them; `v`, the
    accumulator and the result are `v`'s own width; K/V
    blocks come through the grid (never a whole head in VMEM) and the
    online-softmax state lives in scratch across the K/V steps. A step
    past the q block's last needed K/V block computes nothing, and its
    index map repeats the block before, so nothing is fetched either."""
    from jax.experimental import pallas as pl

    group, _, d = q_ref.shape[1:]
    d_v = v_ref.shape[-1]
    rows = group * block_q
    j, step = pl.program_id(1), pl.program_id(2)
    first, last = _grouped_kv_range(j, block_q, block_k, window)

    @pl.when(step == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(first + step <= last)
    def _():
        q = q_ref[0].reshape(rows, d)
        s = jax.lax.dot_general(q, k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        gq = j * block_q + jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 0), block_q)
        gk = (first + step) * block_k \
            + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = gq >= gk
        if window is not None:
            keep = jnp.logical_and(keep, gk > gq - window)
        s = jnp.where(keep, s, NEG_INF)
        m = m_ref[...]                                  # [rows, 1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # a row's own position is always kept, and its block is never
        # skipped, so m_new is finite from the row's first step on
        p = jnp.exp(s - m_new)
        scale_old = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * scale_old + jnp.sum(p, axis=-1,
                                                      keepdims=True)
        acc_ref[...] = acc_ref[...] * scale_old + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = (acc_ref[...] / l_ref[...]).reshape(
            group, block_q, d_v).astype(o_ref.dtype)


def _flash_forward_grouped(q, k, v, sm_scale, block_q, block_k, interpret,
                           window):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, t, d = q.shape
    hk, d_v = k.shape[1], v.shape[-1]
    if h % hk or k.shape != (b, hk, t, d) or v.shape != (b, hk, t, d_v):
        raise ValueError(f"q {q.shape} against k {k.shape}, v {v.shape}")
    group = h // hk
    sm_scale = sm_scale or d ** -0.5
    # `group` query heads share a q block's rows: keep it near 1024 rows
    block_q = min(block_q, t, max(128, 1024 // group // 128 * 128))
    block_k = min(block_k, t)
    assert t % block_q == 0 and t % block_k == 0
    n_kb = t // block_k
    if window is None:
        steps = n_kb
    else:  # the most K/V blocks a q block's window can touch
        steps = min(n_kb, (window - 1 + block_q - 1) // block_k + 2)

    def kv_map(i, j, s):
        first, last = _grouped_kv_range(j, block_q, block_k, window)
        return i, jnp.minimum(first + s, last), 0

    kernel = functools.partial(_flash_fwd_grouped_kernel, block_q=block_q,
                               block_k=block_k, sm_scale=sm_scale,
                               window=window)
    rows = group * block_q
    out = pl.pallas_call(
        kernel,
        grid=(b * hk, t // block_q, steps),
        in_specs=[
            pl.BlockSpec((1, group, block_q, d), lambda i, j, s: (i, 0, j, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d_v), kv_map),
        ],
        out_specs=pl.BlockSpec((1, group, block_q, d_v),
                               lambda i, j, s: (i, 0, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hk, group, t, d_v), q.dtype),
        scratch_shapes=[pltpu.VMEM((rows, d_v), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd_gqa" if window is None else "flash_fwd_window",
    )(q.reshape(b * hk, group, t, d), k.reshape(b * hk, t, d),
      v.reshape(b * hk, t, d_v))
    return out.reshape(b, h, t, d_v)


def grouped_attention(q, k, v, keep):
    """Plain-XLA attention of q [B, H, Tq, D] over k [B, Hkv, Tk, D] and
    v [B, Hkv, Tk, Dv] (query head j reads K/V head j // (H // Hkv), so
    K/V are read once a K/V head) under the mask `keep`, broadcastable
    to [B, Hkv, H // Hkv, Tq, Tk]; scores and softmax in float32; the
    result [B, H, Tq, Dv]."""
    b, h, tq, d = q.shape
    hk = k.shape[1]
    s = jnp.einsum("bkgqd,bktd->bkgqt", q.reshape(b, hk, h // hk, tq, d), k,
                   preferred_element_type=jnp.float32) * d ** -0.5
    p = jax.nn.softmax(jnp.where(keep, s, NEG_INF), axis=-1)
    o = jnp.einsum("bkgqt,bktd->bkgqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype).reshape(b, h, tq, v.shape[-1])


def causal_grouped_attention(q, k, v, window: Optional[int] = None):
    """Inference-only causal self-attention, q [B, H, T, D] over k
    [B, Hkv, T, D] and v [B, Hkv, T, Dv], the last `window` keys a query
    (all with None): the
    grouped flash kernel on a TPU where T fills its 128-row blocks,
    else plain XLA with the mask written out (tests, tiny shapes)."""
    use_pallas = jax.default_backend() == "tpu" or INTERPRET
    t = q.shape[2]
    if use_pallas and t % 128 == 0 and (t <= 512 or t % 512 == 0):
        return flash_attention_forward(q, k, v, causal=True, window=window)
    i = lax.broadcasted_iota(jnp.int32, (t, t), 0)
    j = lax.broadcasted_iota(jnp.int32, (t, t), 1)
    keep = j <= i
    if window is not None:
        keep = jnp.logical_and(keep, j > i - window)
    return grouped_attention(q, k, v, keep)


# --------------------------------------------------------------------------- #
# partitioning: how the dense kernels split over a device mesh
# --------------------------------------------------------------------------- #

def _per_shard(kernel, n_in, n_out, q):
    """`kernel` over [B, H, ...] operands, run per device under the mesh
    the training loop traces in (ops/partitioning.py; a Mosaic call
    cannot be partitioned automatically). A (batch, head) program never
    reads another's rows, so batch splits over 'data' and heads over
    'model' (where column-parallel q/k/v projections leave them) with no
    collective; sequence and head width stay whole on every device."""
    spec = P(split_axis(DATA_AXIS, q.shape[0]),
             split_axis(MODEL_AXIS, q.shape[1]))
    return per_shard(kernel, (spec,) * n_in, (spec,) * n_out)


def _flash_plan(q_shape, k_shape, causal, use_pallas):
    """Static routing shared by forward and backward: (pallas?, bq, bk,
    pad_q, pad_k). Deterministic in shapes + static args, so the vjp
    rules recompute it instead of smuggling Python values through
    residuals."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu" or INTERPRET
    t, tk = q_shape[2], k_shape[2]
    if not use_pallas:
        return False, 0, 0, 0, 0
    # block_k 1024: +7% at 16k tokens vs 512 on v5e (neutral at 8k).
    # Prefer it only when it divides tk — padding would push non-causal
    # odd-multiple-of-512 key lengths (1536, 2560, ...) off the Pallas
    # path entirely. block_q 512 when it divides t: +6-8% on the fwd+bwd
    # training path vs 256 (22.0/40.1 TF/s at 8k/16k). Both captured
    # 2026-07-31..08-01 on pre-PR-2 code, not re-measured. Otherwise 256,
    # or t rounded up to 128 lanes when shorter: the backward slices lse
    # along lanes at `ib * block_q`, which Mosaic must prove 128-aligned
    # (a 104-row block for t=100 fails to compile).
    bq = 512 if t % 512 == 0 else min(256, _ceil_to(t, 128))
    for bk in (1024, 512):
        if tk % bk == 0:
            break
    else:
        bk = min(512, _ceil_to(tk, 8))
    pq, pk = _ceil_to(t, bq) - t, _ceil_to(tk, bk) - tk
    if pk and (not causal or t > tk):
        # padded keys must never receive weight; the causal mask only hides
        # them when every query position is < tk (self-attention). Otherwise
        # fall back to the XLA path, which masks the ragged tail exactly.
        return False, 0, 0, 0, 0
    return True, bq, bk, pq, pk


def _pad_t(x, pad):
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    use_pallas: Optional[bool] = None):
    """Flash attention: Pallas forward AND backward on TPU (blockwise-XLA
    path elsewhere). `use_pallas=None` auto-detects the backend."""
    return _flash_fwd_rule(q, k, v, causal, sm_scale, use_pallas)[0]


def _flash_fwd_rule(q, k, v, causal, sm_scale, use_pallas):
    pallas, bq, bk, pq, pk = _flash_plan(q.shape, k.shape, causal,
                                         use_pallas)
    if not pallas:
        out = blockwise_attention(q, k, v, causal=causal, sm_scale=sm_scale)
        return out, (q, k, v, None, None)
    t = q.shape[2]
    fwd = functools.partial(flash_attention_forward, causal=causal,
                            sm_scale=sm_scale, block_q=bq, block_k=bk,
                            return_lse=True)
    out_p, lse = _per_shard(fwd, 3, 2, q)(
        _pad_t(q, pq), _pad_t(k, pk), _pad_t(v, pk))
    return out_p[:, :, :t], (q, k, v, out_p, lse)


def _flash_bwd_rule(causal, sm_scale, use_pallas, res, g):
    q, k, v, out_p, lse = res
    pallas, bq, bk, pq, pk = _flash_plan(q.shape, k.shape, causal,
                                         use_pallas)
    if not pallas or lse is None:
        _, vjp = jax.vjp(
            lambda q_, k_, v_: blockwise_attention(q_, k_, v_, causal=causal,
                                                   sm_scale=sm_scale),
            q, k, v)
        return vjp(g)
    t, tk = q.shape[2], k.shape[2]
    bwd = functools.partial(flash_attention_backward, causal=causal,
                            sm_scale=sm_scale, block_q=bq, block_k=bk)
    dq, dk, dv = _per_shard(bwd, 6, 3, q)(
        _pad_t(q, pq), _pad_t(k, pk), _pad_t(v, pk), out_p, lse,
        _pad_t(g, pq))
    return dq[:, :, :t], dk[:, :, :tk], dv[:, :, :tk]


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)
