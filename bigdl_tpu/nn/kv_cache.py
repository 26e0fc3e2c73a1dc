"""What a layer keeps per serving slot, and how a decode step reads it.
The one owner of the per-slot state's format (ROADMAP D3): the models
build their caches from `init`, `init_recurrent` and `init_latent`,
prefill lands through `commit`, a decode step writes what it keeps by
position through `write` and masks through `step_mask`.

Four kinds of layer. Two keep keys and values, told apart by `window`:

* full (`window` None): `[slots, kv_heads, max_len, head_dim]`, position
  p at index p.
* window: a ring of the last `window` positions,
  `[slots, kv_heads, window, head_dim]`, position p at index
  p % window. After the step at position p has written, index j holds
  position p - ((p - j) % window): the window, whole, once p >= window
  - 1; before that the indices past p hold nothing yet (a negative
  position) and are masked.

A decode step over a full layer reads the cache only as deep as a rung
of `depth_rungs(max_len)` that covers the deepest slot of the step: the
ladder is a constant of the format, chosen by `rung_index` from the
step's positions.

* recurrent (`init_recurrent`; nn/linear_attention.py): a state of fixed
  size, `[slots, heads, key_dim, value_dim]` float32, and the tail of
  the layer's short causal convolution, `[slots, taps - 1, channels]`:
  the last rows that came before it. No depth, no mask, no position
  index, no ladder. A prefill SETS both whole at its slot ids
  (`commit`); a decode step REPLACES both, for every slot it runs over.
  Unlike a K/V write that is destructive: nothing of the state before
  the step is left. What keeps that harmless (an idle slot riding along,
  a bucket's padding row, warm-up against the live cache, a step
  computed for a request that had ended): every request's prefill
  overwrites its slot's state whole, `commit` is idempotent under
  repeated slot ids, and the layer's update never grows a state
  (tests/test_hybrid_decoder.py).

* latent (`init_latent`; nn/latent_attention.py): ONE compressed vector
  a position for all heads, `[slots, max_len, rank]`, and the one rotary
  key a position that every head shares, `[slots, max_len, rope_dim]`,
  both in the cache's type; no axis of heads, and no K or V at all (a
  decode step reads the latent as it lies). Position p at index p,
  written by `write`, landed by `commit`, masked by `step_mask` as a
  full layer's K/V are; a decode step reads the whole depth (no ladder).
  On the v5e the compiler lays the 64-wide rotary keys out with the
  positions minor, so their lanes are not padded to 128: the device
  holds the 576 values a position and layer that the leaves count
  (PERF.md PR 39: 3.62 GB at 32 slots x 16384 x 6 layers).
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def depth(max_len: int, window: Optional[int]) -> int:
    """Positions a slot holds."""
    return max_len if window is None else min(int(window), max_len)


def _at_least_one(**sizes):
    for name, n in sizes.items():
        if n < 1:
            raise ValueError(f"{name} must be >= 1, got {n}")


def init(slots: int, kv_heads: int, max_len: int, head_dim: int,
         window: Optional[int] = None, dtype=jnp.float32):
    """One layer's K (or V) buffer, zeroed."""
    _at_least_one(slots=slots, max_len=max_len)
    return jnp.zeros((slots, kv_heads, depth(max_len, window), head_dim),
                     dtype)


def init_recurrent(slots: int, heads: int, key_dim: int, value_dim: int,
                   taps: int, channels: int, tail_dtype=jnp.float32):
    """One recurrent layer's (state, tail), zeroed: the state float32
    whatever the cache's type, the tail in the type of the rows it
    holds."""
    _at_least_one(slots=slots)
    return (jnp.zeros((slots, heads, key_dim, value_dim), jnp.float32),
            jnp.zeros((slots, taps - 1, channels), tail_dtype))


def init_latent(slots: int, max_len: int, rank: int, rope_dim: int,
                dtype=jnp.float32):
    """One latent layer's (latent, rotary keys), zeroed."""
    _at_least_one(slots=slots, max_len=max_len)
    return (jnp.zeros((slots, max_len, rank), dtype),
            jnp.zeros((slots, max_len, rope_dim), dtype))


def depth_rungs(max_len: int) -> Tuple[int, ...]:
    """The depths, ascending, a decode step may read a full layer's cache
    to: `max_len` and its half, quarter and eighth, those below `max_len`
    rounded up to a multiple of 128 positions; a rung under 128, over
    `max_len` or equal to another is dropped (2048 has 256 / 512 / 1024 /
    2048, 64 has 64 alone). Coarse on purpose: a serving mix's median
    step has to lie well inside one rung, or its median latency flips
    between two (docs/serving.md)."""
    rungs = {max_len}
    for parts in (2, 4, 8):
        d = 128 * math.ceil(max_len / parts / 128)
        if 128 <= d < max_len:
            rungs.add(d)
    return tuple(sorted(rungs))


def rung_index(rungs: Sequence[int], positions):
    """Index of the smallest of `rungs` that covers every slot of the
    step at `positions` [B] (the deepest, just written, included). Idle
    slots ride at position 0 and ask for nothing. `positions` may be
    traced (the decode step, on the device) or a numpy array (the
    engine's counters, on the host)."""
    return (positions.max() + 1 > np.asarray(rungs[:-1])).sum()


def write(cache, new, positions, window: Optional[int] = None):
    """Write `new` [B, H, T, hd] into `cache` [B, H, L, hd] (or a
    latent layer's `new` [B, T, w] into `cache` [B, L, w]: positions are
    the axis before the last) starting at per-row sequence position
    `positions` [B] — a per-row `lax.dynamic_update_slice`, so under
    donation the decode step updates its preallocated buffers in place
    (O(1) memory and step cost per token; never a per-token
    concat/retrace). A window layer's ring takes one position at a time
    (T = 1), at `position % L`. Under the scope `kv write`, wherever it
    is called from."""
    with jax.named_scope("kv write"):
        if window is not None:
            positions = positions % cache.shape[2]
        lead = (0,) * (cache.ndim - 3)

        def one(c, n, p):
            return lax.dynamic_update_slice(c, n, lead + (p, 0))
        return jax.vmap(one)(cache, new, positions)


def commit(cache, new, slot_ids, lengths=None,
           window: Optional[int] = None):
    """Commit per-request prefill K/V `new` [B, H, T, hd] into slots of a
    fleet-wide cache [S, H, L, hd] at sequence position 0 (a latent
    layer's `new` [B, T, w] into [S, L, w] likewise), or a recurrent
    layer's state or tail `new` [B, ...] into `cache` [S, ...] whole. Rows may repeat (bucket padding replicates the last
    request's row INCLUDING its slot id): the scan writes in request
    order, so a padded duplicate rewrites identical values and the last
    write wins.

    A window layer whose prompt bucket is longer than its ring commits,
    per row, the last L positions of the row's real `lengths` [B] at the
    ring's indices (position p at p % L). Under the scope `kv commit`."""
    with jax.named_scope("kv commit"):
        ring = cache.shape[2]
        if window is not None and new.shape[2] > ring:
            last = lengths.astype(jnp.int32)[:, None] - 1           # [B, 1]
            held = last - (last - jnp.arange(ring)[None, :]) % ring  # [B, L]
            held = jnp.clip(held, 0, new.shape[2] - 1)  # < 0: masked anyway
            new = jnp.take_along_axis(new, held[:, None, :, None], axis=2)

        def body(c, inp):
            n, s = inp
            return lax.dynamic_update_slice(
                c, n[None], (s,) + (0,) * (c.ndim - 1)), None
        out, _ = lax.scan(body, cache, (new, slot_ids))
        return out


def step_mask(length: int, positions, window: Optional[int] = None):
    """[B, 1, 1, L] mask of the cache indices the step at `positions` [B]
    may read, its own (just written) included."""
    idx = jnp.arange(length)[None, :]
    if window is None:
        keep = idx <= positions[:, None]
    else:
        keep = (positions[:, None] - idx) % length <= positions[:, None]
    return keep[:, None, None, :]


def positions_skipped(positions, window: int):
    """Cache positions a one-depth cache would have given the step at
    `positions` [B] to read, and the ring did not."""
    return jnp.sum(jnp.maximum(positions + 1 - window, 0))
