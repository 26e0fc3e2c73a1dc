"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

Net-new vs the reference (SURVEY.md §5.7 marks long-context absent in
BigDL); first-class here because it shapes the core design. Two schemes,
both SPMD over a mesh 'sequence' axis:

- **Ring attention**: Q stays put, KV shards rotate around the ring via
  `lax.ppermute` (XLA lowers to ICI neighbor sends); each hop continues the
  SAME online softmax by carrying (acc, m, l) accumulators from
  ops/attention_kernel.blockwise_attention. Memory O(T/n) per device,
  exact — not an approximation.
- **Ulysses**: all-to-all swaps sequence sharding for head sharding, runs
  dense local attention, swaps back. Cheaper collectives when
  n_heads >= n_devices; ring wins for very long T.

Use inside `shard_map` over a Mesh axis (helpers below build the mapped fn).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.ops.attention_kernel import (attention_state_finish,
                                            blockwise_attention)


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   sm_scale: Optional[float] = None, block_k: int = 512,
                   axis_size: Optional[int] = None):
    """Exact attention with sequence-sharded q/k/v ([B,H,T/n,D] per device).

    Must run inside shard_map/pmap with `axis_name` a mesh axis laid out on
    the ring. Each device computes its Q block against every KV shard as the
    shards rotate; causal masking uses global offsets so semantics match the
    unsharded computation exactly.

    On TPU each hop runs the Pallas carry kernel
    (ops/attention_kernel.flash_attention_carry — same online softmax,
    MXU-tiled); the backward recomputes through the XLA blockwise ring
    via custom_vjp (Pallas calls are not auto-differentiable).
    """
    from bigdl_tpu.ops import attention_kernel as ak
    if jax.default_backend() == "tpu" or ak.INTERPRET:
        return _ring_pallas(q, k, v, axis_name, causal, sm_scale, block_k,
                            axis_size)
    return _ring_impl(q, k, v, False, axis_name, causal, sm_scale,
                      block_k, axis_size)


def _ring_impl(q, k, v, use_pallas, axis_name, causal, sm_scale, block_k,
               axis_size):
    from bigdl_tpu.ops import attention_kernel as ak
    n = axis_size if axis_size is not None else int(lax.psum(1, axis_name))
    idx = lax.axis_index(axis_name)
    t_local = q.shape[2]
    sm_scale = sm_scale or q.shape[-1] ** -0.5

    q_offset = idx * t_local
    perm = [(i, (i + 1) % n) for i in range(n)]

    state = ak.attention_state_init(q.astype(jnp.float32))
    k_cur, v_cur = k, v
    # unrolled python loop: n is static (the mesh size), which keeps each
    # ppermute visible to XLA's collective scheduler for compute/comm overlap
    for i in range(n):
        src = (idx - i) % n  # device where the held KV shard originated
        if use_pallas:
            # offsets are traced (axis_index); the kernel takes them as data
            state = ak.flash_attention_carry(
                q, k_cur, v_cur, state, causal=causal, sm_scale=sm_scale,
                q_offset=q_offset, k_offset=src * t_local, block_k=block_k)
        else:
            state = blockwise_attention(
                q, k_cur, v_cur, causal=causal, sm_scale=sm_scale,
                block_k=block_k, q_offset=q_offset, k_offset=src * t_local,
                carry=state, finish=False)
        if i + 1 < n:  # last hop needs no rotation
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
    out = attention_state_finish(*state)
    return out.astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_pallas(q, k, v, axis_name, causal, sm_scale, block_k, axis_size):
    return _ring_impl(q, k, v, True, axis_name, causal, sm_scale, block_k,
                      axis_size)


def _ring_pallas_fwd(q, k, v, axis_name, causal, sm_scale, block_k,
                     axis_size):
    out = _ring_impl(q, k, v, True, axis_name, causal, sm_scale, block_k,
                     axis_size)
    return out, (q, k, v)


def _ring_pallas_bwd(axis_name, causal, sm_scale, block_k, axis_size, res,
                     g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _ring_impl(q_, k_, v_, False, axis_name, causal,
                                      sm_scale, block_k, axis_size),
        q, k, v)
    return vjp(g)


_ring_pallas.defvjp(_ring_pallas_fwd, _ring_pallas_bwd)


def zigzag_ring_attention(q, k, v, axis_name: str, causal: bool = True,
                          sm_scale: Optional[float] = None,
                          block_k: int = 512,
                          axis_size: Optional[int] = None):
    """Load-balanced ("zigzag"/striped) causal ring attention.

    Plain contiguous ring + causal mask is 2x wasteful: every (q-shard,
    kv-shard) pair is computed even though half are fully masked, and
    SPMD lockstep means conditional skipping would just idle the early
    devices while the last one grinds. Zigzag sharding fixes the
    balance: device d holds sequence chunks d AND 2n-1-d concatenated
    ([B, H, 2c, D] local, c = T/2n), so when fully-masked chunk pairs
    are skipped (lax.cond — real branches on TPU), every device computes
    exactly n+1 masked-pair-eligible updates plus n always-unmasked
    ones. Net: ~2x causal throughput over the plain ring at the same
    exactness (same online softmax, global offsets).

    Chunk-pair case analysis per hop (src = originating device of the
    held KV; A = src's low chunk, B = its high chunk):
      q_low  vs A: diagonal/unmasked iff src <= d  (cond)
      q_low  vs B: ALWAYS fully masked             (statically skipped)
      q_high vs A: always fully unmasked           (causal=False path)
      q_high vs B: diagonal/unmasked iff src >= d  (cond)

    Requires causal=True (zigzag exists only to balance the causal
    triangle) and even local length. Layout helpers
    `zigzag_order`/`zigzag_inverse` convert natural global order;
    `make_sequence_parallel_attention(scheme="zigzag")` applies them
    around the shard_map so callers keep natural-order tensors (feed
    the zigzag layout straight from the data pipeline to skip the
    reorder gather in production)."""
    from bigdl_tpu.ops import attention_kernel as ak
    if not causal:
        raise ValueError("zigzag ring is a causal-balance scheme; use "
                         "scheme='ring' for non-causal")
    if jax.default_backend() == "tpu" or ak.INTERPRET:
        return _zigzag_pallas(q, k, v, axis_name, sm_scale, block_k,
                              axis_size)
    return _zigzag_impl(q, k, v, False, axis_name, sm_scale, block_k,
                        axis_size)


def _zigzag_impl(q, k, v, use_pallas, axis_name, sm_scale, block_k,
                 axis_size):
    from bigdl_tpu.ops import attention_kernel as ak
    n = axis_size if axis_size is not None else int(lax.psum(1, axis_name))
    idx = lax.axis_index(axis_name)
    if q.shape[2] % 2:
        raise ValueError("zigzag needs an even local sequence length")
    c = q.shape[2] // 2
    sm_scale = sm_scale or q.shape[-1] ** -0.5

    def update(state, qq, kk, vv, q_off, k_off, causal_pair):
        if use_pallas:
            return ak.flash_attention_carry(
                qq, kk, vv, state, causal=causal_pair, sm_scale=sm_scale,
                q_offset=q_off, k_offset=k_off, block_k=block_k)
        return blockwise_attention(
            qq, kk, vv, causal=causal_pair, sm_scale=sm_scale,
            block_k=block_k, q_offset=q_off, k_offset=k_off,
            carry=state, finish=False)

    q1, q2 = q[:, :, :c], q[:, :, c:]
    off_q1 = idx * c
    off_q2 = (2 * n - 1 - idx) * c
    s1 = ak.attention_state_init(q1.astype(jnp.float32))
    s2 = ak.attention_state_init(q2.astype(jnp.float32))
    perm = [(i, (i + 1) % n) for i in range(n)]
    k_cur, v_cur = k, v
    for i in range(n):
        src = (idx - i) % n
        a_off, b_off = src * c, (2 * n - 1 - src) * c
        kA, vA = k_cur[:, :, :c], v_cur[:, :, :c]
        kB, vB = k_cur[:, :, c:], v_cur[:, :, c:]
        # q_high vs A: strictly below the diagonal for every (d, src)
        s2 = update(s2, q2, kA, vA, off_q2, a_off, False)
        # q_low vs A: on/below the diagonal only when src <= d
        s1 = lax.cond(
            src <= idx,
            lambda s: update(s, q1, kA, vA, off_q1, a_off, True),
            lambda s: s, s1)
        # q_high vs B: on/below the diagonal only when src >= d
        s2 = lax.cond(
            src >= idx,
            lambda s: update(s, q2, kB, vB, off_q2, b_off, True),
            lambda s: s, s2)
        if i + 1 < n:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
    out = jnp.concatenate([attention_state_finish(*s1),
                           attention_state_finish(*s2)], axis=2)
    return out.astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _zigzag_pallas(q, k, v, axis_name, sm_scale, block_k, axis_size):
    return _zigzag_impl(q, k, v, True, axis_name, sm_scale, block_k,
                        axis_size)


def _zigzag_pallas_fwd(q, k, v, axis_name, sm_scale, block_k, axis_size):
    out = _zigzag_impl(q, k, v, True, axis_name, sm_scale, block_k,
                       axis_size)
    return out, (q, k, v)


def _zigzag_pallas_bwd(axis_name, sm_scale, block_k, axis_size, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _zigzag_impl(q_, k_, v_, False, axis_name,
                                        sm_scale, block_k, axis_size),
        q, k, v)
    return vjp(g)


_zigzag_pallas.defvjp(_zigzag_pallas_fwd, _zigzag_pallas_bwd)


def zigzag_order(n: int, t: int):
    """Global T-length permutation: natural order -> zigzag layout
    (device d's shard = chunks d and 2n-1-d). Apply to q/k/v along the
    sequence axis before contiguous sharding over the ring axis."""
    import numpy as np
    c = t // (2 * n)
    if t % (2 * n):
        raise ValueError(f"T={t} must divide by 2*axis_size={2 * n}")
    order = []
    for d in range(n):
        order.extend(range(d * c, (d + 1) * c))
        order.extend(range((2 * n - 1 - d) * c, (2 * n - d) * c))
    return np.asarray(order)


def zigzag_inverse(n: int, t: int):
    import numpy as np
    order = zigzag_order(n, t)
    inv = np.empty_like(order)
    inv[order] = np.arange(t)
    return inv


def ulysses_attention(q, k, v, axis_name: str, causal: bool = False,
                      sm_scale: Optional[float] = None):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style).

    In: [B, H, T/n, D] sequence-sharded. all_to_all regroups to
    [B, H/n, T, D] (full sequence, subset of heads), dense flash attention
    locally, then the inverse all_to_all restores sequence sharding.
    Requires n_head % n_devices == 0."""
    n = lax.psum(1, axis_name)
    b, h, t_loc, d = q.shape
    if h % n:
        raise ValueError(f"n_head {h} must divide by axis size {n}")

    def scatter_heads(x):
        # [B,H,Tl,D] -> [B,H/n,Tl*n,D]: split heads across devices, gather seq
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def gather_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    o = blockwise_attention(qh, kh, vh, causal=causal, sm_scale=sm_scale)
    return gather_heads(o).astype(q.dtype)


def make_sequence_parallel_attention(mesh: Mesh, scheme: str = "ring",
                                     axis_name: str = "data",
                                     causal: bool = False):
    """Build a jit-ready fn(q, k, v) -> out with q,k,v sequence-sharded over
    `axis_name`. q,k,v/out are [B,H,T,D] global arrays.

    Example (ring attention over 4 devices == single-device attention):
        >>> import jax, numpy as np
        >>> import jax.numpy as jnp
        >>> from jax.sharding import Mesh
        >>> from bigdl_tpu.parallel.sequence import (
        ...     make_sequence_parallel_attention)
        >>> from bigdl_tpu.ops.attention_kernel import naive_attention
        >>> mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        >>> attn = make_sequence_parallel_attention(mesh, "ring")
        >>> ks = jax.random.split(jax.random.PRNGKey(0), 3)
        >>> q, k, v = (jax.random.normal(kk, (1, 2, 16, 8)) for kk in ks)
        >>> bool(jnp.allclose(attn(q, k, v), naive_attention(q, k, v),
        ...                   atol=1e-5))
        True
    """
    if scheme not in ("ring", "ulysses", "zigzag"):
        raise ValueError(f"scheme must be ring|ulysses|zigzag, got {scheme}")
    n = int(mesh.shape[axis_name])
    if scheme == "ring":
        fn = functools.partial(ring_attention, axis_name=axis_name,
                               causal=causal, axis_size=n)
    elif scheme == "zigzag":
        fn = functools.partial(zigzag_ring_attention, axis_name=axis_name,
                               causal=causal, axis_size=n)
    else:
        fn = functools.partial(ulysses_attention, axis_name=axis_name,
                               causal=causal)
    spec = P(None, None, axis_name, None)

    from bigdl_tpu.ops import attention_kernel as ak
    # the Pallas hop kernel is traced with varying-axes typing off: ops
    # inside a kernel body drop the type while loads from its refs keep
    # it, so the hop's fori_loop carry would mismatch. The XLA blockwise
    # ring keeps the check.
    pallas = scheme != "ulysses" and (jax.default_backend() == "tpu"
                                      or ak.INTERPRET)
    mapped = jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec, check_vma=not pallas)
    if scheme == "zigzag":
        # callers keep natural order: reorder in, inverse-reorder out.
        # (Feed zigzag-ordered data directly and call the shard_mapped fn
        # to skip these gathers in a production loop.)
        def natural_order_fn(q, k, v, _mapped=mapped):
            t = q.shape[2]
            order = jnp.asarray(zigzag_order(n, t))
            inv = jnp.asarray(zigzag_inverse(n, t))
            o = _mapped(jnp.take(q, order, axis=2),
                        jnp.take(k, order, axis=2),
                        jnp.take(v, order, axis=2))
            return jnp.take(o, inv, axis=2)
        return natural_order_fn
    return mapped


class SequenceParallelAttention:
    """Module-flavoured wrapper: holds the mesh + scheme, exposes
    __call__(q, k, v). (Thin; the sharded projections live in the model's
    pjit partitioning, matching the scaling-book recipe of annotating
    shardings and letting XLA insert collectives.)"""

    def __init__(self, mesh: Mesh, scheme: str = "ring",
                 axis_name: str = "data", causal: bool = False):
        self.fn = make_sequence_parallel_attention(mesh, scheme, axis_name,
                                                   causal)
        self.mesh, self.axis_name = mesh, axis_name

    def __call__(self, q, k, v):
        return self.fn(q, k, v)
