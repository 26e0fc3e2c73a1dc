"""The block loop of a ragged decode kernel: one step of attention a slot,
reading each slot's cache only to its length.

A decode step attends one new query row (or a few, grouped) a slot over
that slot's cached positions. Plain XLA reads every slot's buffer to its
whole padded depth. The loop here reads, for each program of the call,
only the blocks that hold live positions:

- program b reads the blocks of slot b alone: a cache `depth` deep
  holds `min(positions[b] + 1, depth)` live positions at indices 0 ..
  that - 1 (a full cache, and a ring before it has wrapped and after),
  and the program reads `ceil(live / block)` blocks of `block`
  positions; an idle slot, at position 0, reads one;
- each block of every cache comes from HBM (`memory_space=pl.ANY`) into
  VMEM by a double-buffered DMA started while the block before it is
  computed; a program's last block starts the next program's first, so
  the programs run in order and `count` (SMEM) says which buffer a block
  lies in;
- an online softmax in float32 (`m`, `l`, `acc`), as the flash kernels
  keep: the scores `score(queries, blocks)` [..., block] float32 are
  scaled by `sm_scale` and masked past the live positions, the
  unnormalised probabilities meet the blocks in `value(p, blocks)`
  [..., width] float32, and the result is `acc / l` in the output's
  type.

The kinds of decode kernel differ only in the products and in how a
program's blocks lie in the caches (`views`): `mla_decode`
(ops/latent_decode_kernel.py) and `gqa_decode`
(ops/gqa_decode_kernel.py) run on this one loop.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.ops import attention_kernel
from bigdl_tpu.ops.attention_kernel import NEG_INF


def on_kernel_path() -> bool:
    """Whether a decode step may take a Pallas kernel: a TPU backend, or
    `attention_kernel.INTERPRET` (the tests' hook)."""
    return jax.default_backend() == "tpu" or attention_kernel.INTERPRET


def positions_read(positions, depth: int, block: Optional[int]):
    """Cache positions a decode step reads of one layer `depth` deep for
    the slots at `positions` [B], float32: each slot's live blocks,
    whole, in blocks of `block`; slots x `depth` where `block` is None
    (the plain-XLA form)."""
    if block is None:
        return jnp.float32(positions.shape[0] * depth)
    live = jnp.minimum(positions + 1, depth)
    return jnp.sum(((live - 1) // block + 1) * block).astype(jnp.float32)


def _loop_kernel(pos_ref, *refs, n_queries: int, views: Sequence[Callable],
                 score: Callable, value: Callable, block: int, depth: int,
                 sm_scale: float):
    """One program = one slot's blocks. `count` holds the blocks read
    before this program, so block g of the whole call lies in buffer
    g % 2."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_caches = len(views)
    queries = refs[:n_queries]
    caches = refs[n_queries:n_queries + n_caches]
    o_ref = refs[n_queries + n_caches]
    bufs = refs[n_queries + n_caches + 1:-2]
    sem, count = refs[-2], refs[-1]
    i, programs = pl.program_id(0), pl.num_programs(0)

    def fetch(program, j, buf):
        return [pltpu.make_async_copy(view(cache, program, j * block),
                                      dst.at[buf], sem.at[k, buf])
                for k, (view, cache, dst) in enumerate(
                    zip(views, caches, bufs))]

    @pl.when(i == 0)
    def _():
        count[0] = 0
        for copy in fetch(0, 0, 0):
            copy.start()

    live = jnp.minimum(pos_ref[i] + 1, depth)
    n = (live - 1) // block + 1
    first = count[0]
    qs = [q[0] for q in queries]

    def body(j, carry):
        acc, m, l = carry
        buf = (first + j) % 2

        @pl.when(j + 1 < n)
        def _():
            for copy in fetch(i, j + 1, 1 - buf):
                copy.start()

        @pl.when(jnp.logical_and(j + 1 == n, i + 1 < programs))
        def _():
            for copy in fetch(i + 1, 0, 1 - buf):
                copy.start()

        for copy in fetch(i, j, buf):
            copy.wait()
        blocks = [b[buf] for b in bufs]
        s = score(qs, blocks)
        idx = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                   s.ndim - 1)
        s = jnp.where(idx < live, s * sm_scale, NEG_INF)
        # index 0 is live in every slot and lies in its first block: m is
        # finite from the first block on
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + value(p, blocks)
        return acc, m_new, l

    rows = o_ref.shape[1:-1]
    init = (jnp.zeros(o_ref.shape[1:], jnp.float32),
            jnp.full(rows + (1,), NEG_INF, jnp.float32),
            jnp.zeros(rows + (1,), jnp.float32))
    acc, _, l = jax.lax.fori_loop(0, n, body, init)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    count[0] = first + n


def ragged_decode(score: Callable, value: Callable, queries: Sequence,
                  caches: Sequence, views: Sequence[Callable],
                  buf_shapes: Sequence[Tuple[int, ...]], positions, *,
                  depth: int, block: int, sm_scale: float,
                  out_shape: jax.ShapeDtypeStruct, name: str,
                  interpret: Optional[bool] = None):
    """Run the block loop: one program a slot, program b given row b of
    each of `queries` ([B, ...]) and of `out_shape` [B, ..., width].
    `caches` stay in HBM; `views[k](cache, b, start)` is the slice of
    cache k that program b reads at its position `start` (`block`
    positions), landing in a VMEM buffer of `buf_shapes[k]`. `positions`
    [B] are the slots' positions, `depth` the positions a cache holds a
    slot."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = attention_kernel.INTERPRET

    def row(shape):
        return pl.BlockSpec((1,) + tuple(shape[1:]),
                            lambda i, pos: (i,) + (0,) * (len(shape) - 1))

    kernel = functools.partial(
        _loop_kernel, n_queries=len(queries), views=tuple(views),
        score=score, value=value, block=block, depth=depth,
        sm_scale=sm_scale)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(out_shape.shape[0],),
            in_specs=[row(q.shape) for q in queries]
            + [any_space] * len(caches),
            out_specs=row(out_shape.shape),
            scratch_shapes=[pltpu.VMEM((2,) + tuple(s), c.dtype)
                            for s, c in zip(buf_shapes, caches)]
            + [pltpu.SemaphoreType.DMA((len(caches), 2)),
               pltpu.SMEM((1,), jnp.int32)]),
        out_shape=out_shape,
        # a program's last block fetches the next program's first: in
        # order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(positions.astype(jnp.int32), *queries, *caches)
