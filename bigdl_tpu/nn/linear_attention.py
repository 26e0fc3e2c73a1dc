"""Linear attention with a fixed-size recurrent state: the gated delta
rule (Yang et al. 2024, "Gated Delta Networks").

Each head keeps a matrix S [key_dim, value_dim] in place of keys and
values. A token decays it, takes out what it holds under the token's
key, and writes the token's value there:

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

with alpha_t in (0, 1) and beta_t in (0, 2) computed from the token
(beta past 1 gives the step a negative eigenvalue along k_t), and q, k,
v the token's projections after a short causal depthwise convolution,
SiLU and, for q and k, an l2 norm a head. `GatedDeltaRule` has the
attention layers' three entry points over ONE recurrence:

* `apply_step`: one token a slot from (S, the convolution's tail).
* `apply_prefill`: right-padded rows of real `lengths`, in the chunked
  form: inside a chunk of `chunk` tokens the WY representation, one
  triangular solve a chunk and head, every product batched over all
  chunks at once; across chunks a `lax.scan` that carries S through
  three small products a chunk. A 1024-token prompt is 16 sequential
  steps a layer, not 1024. A padded position takes beta = 0 and
  alpha = 1, which leaves S alone, so the state handed to the cache is
  the one after each row's LAST REAL token, and the tail is the last
  real rows before the convolution (zeros before position 0).
* `apply`: `apply_prefill` over whole rows.

Float32: S, alpha, beta, the l2 norms, the triangular solve, every
product of the recurrence (at `Precision.HIGHEST`: on a TPU a float32
product is otherwise rounded to bfloat16 operands) and the scan's
carry. The projections and the convolution take their operands in the
weights' type with float32 accumulators, as the attention layers do.
What a slot keeps is nn/kv_cache.py's recurrent kind.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy.linalg import solve_triangular

from bigdl_tpu.nn import kv_cache
from bigdl_tpu.nn.initialization import Xavier
from bigdl_tpu.nn.module import Module

F32 = jnp.float32
HI = lax.Precision.HIGHEST
#: chunks solved at once in a prefill (see `chunk_scan`)
SPAN = 8


def l2norm(x, eps: float = 1e-6):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def delta_step(state, q, k, v, log_alpha, beta):
    """One token of the recurrence for every row and head: `state`
    [B, H, dk, dv], q, k [B, H, dk], v [B, H, dv], log_alpha, beta
    [B, H], all float32. Returns (o [B, H, dv], the new state).
    S^T k and S^T q are taken from the OLD state in one reading of it
    (o = S_new^T q = alpha S^T q + (k . q) u), so the new state is read
    by no one here."""
    alpha = jnp.exp(log_alpha)[..., None]
    held = jnp.sum(state * k[..., None], axis=-2)            # S^T k
    seen = jnp.sum(state * q[..., None], axis=-2)            # S^T q
    u = beta[..., None] * (v - alpha * held)
    new = alpha[..., None] * state + k[..., None] * u[..., None, :]
    o = alpha * seen + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return o, new


def chunk_scan(q, k, v, log_alpha, beta, chunk: int, state=None):
    """The same recurrence over whole rows, chunk by chunk: q, k
    [B, T, H, dk], v [B, T, H, dv], log_alpha, beta [B, T, H], float32,
    T a multiple of `chunk`; `state` [B, H, dk, dv] or None for zeros.
    Returns (o [B, T, H, dv], the state after the last token).

    With g_r the chunk's running sum of log alpha and U the chunk's
    pseudo-values (row r: beta_r (v_r - alpha_r S_{r-1}^T k_r)),

        (I + A) U = diag(beta) V - diag(beta e^g) K S_0,
            A_ri = beta_r e^(g_r - g_i) (k_r . k_i),  i < r
        O   = diag(e^g) Q S_0 + (Q K^T * e^(g_r - g_i), i <= r) U
        S_C = e^(g_C) S_0 + (diag(e^(g_C - g)) K)^T U

    so everything but S_0's part is computed for `SPAN` chunks at once
    (all of them up to 512 tokens; beyond, what a span holds in float32
    is what bounds a long prefill's memory: 2.4 GB for 4 x 2048 tokens
    of 30 heads if solved whole, a quarter of it so), and the chunks are
    then a `lax.scan` of three small products each over the state."""
    b, t, h, dk = q.shape
    dv, n = v.shape[-1], t // chunk
    if state is None:
        state = jnp.zeros((b, h, dk, dv), F32)
    if n > SPAN and n % SPAN == 0:
        def spans(x):  # [B, T, ...] -> [T / (SPAN chunk), B, SPAN chunk, ...]
            return jnp.moveaxis(
                x.reshape((b, n // SPAN, SPAN * chunk) + x.shape[2:]), 1, 0)
        state, o = lax.scan(
            lambda s, xs: _solved_span(*xs, chunk, s)[::-1], state,
            tuple(spans(x) for x in (q, k, v, log_alpha, beta)))
        return jnp.moveaxis(o, 0, 1).reshape(b, t, h, dv), state
    return _solved_span(q, k, v, log_alpha, beta, chunk, state)


def _solved_span(q, k, v, log_alpha, beta, chunk: int, state):
    b, t, h, dk = q.shape
    dv, n = v.shape[-1], t // chunk

    def chunks(x):  # [B, T, H, ...] -> [N, B, H, C, ...]
        x = x.reshape((b, n, chunk, h) + x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)
    q, k, v, la, beta = (chunks(x) for x in (q, k, v, log_alpha, beta))
    g = jnp.cumsum(la, axis=-1)                              # [N, B, H, C]
    r = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    i = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # masked before the exponential: above the diagonal g_r - g_i > 0
    decay = jnp.exp(jnp.where(i <= r, g[..., :, None] - g[..., None, :],
                              -jnp.inf))
    kk = jnp.einsum("nbhrd,nbhid->nbhri", k, k, precision=HI)
    a = jnp.where(i < r, beta[..., None] * decay * kk, 0.0)
    rhs = jnp.concatenate(
        [beta[..., None] * v, (beta * jnp.exp(g))[..., None] * k], axis=-1)
    # one batch axis: the TPU's solve lays the batch out minor-most and
    # pads each axis of it apart (32 chunks to 128: four times the bytes)
    sol = solve_triangular(
        (a + jnp.eye(chunk, dtype=F32)).reshape(-1, chunk, chunk),
        rhs.reshape(-1, chunk, dv + dk), lower=True,
        unit_diagonal=True).reshape(rhs.shape)
    uv, wk = sol[..., :dv], sol[..., dv:]
    qk = jnp.einsum("nbhrd,nbhid->nbhri", q, k, precision=HI) * decay
    qg = q * jnp.exp(g)[..., None]
    g_end = g[..., -1:]
    kd = k * jnp.exp(g_end - g)[..., None]

    def body(s, xs):
        uv, wk, qk, qg, kd, g_end = xs
        u = uv - jnp.einsum("bhck,bhkv->bhcv", wk, s, precision=HI)
        o = jnp.einsum("bhck,bhkv->bhcv", qg, s, precision=HI) \
            + jnp.einsum("bhri,bhiv->bhrv", qk, u, precision=HI)
        s = jnp.exp(g_end)[..., None] * s \
            + jnp.einsum("bhck,bhcv->bhkv", kd, u, precision=HI)
        return s, o

    state, o = lax.scan(body, state, (uv, wk, qk, qg, kd, g_end))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)           # [B, N, C, H, dv]
    return o.reshape(b, t, h, dv), state


class GatedDeltaRule(Module):
    """`n_head` heads of `key_dim` (q, k) and `value_dim` (v, the
    output), a causal depthwise convolution of `taps` taps over the
    q, k, v projections, no bias anywhere; the output is RMS-normed a
    head and gated by silu(x @ wz) before the output projection. Input
    [B, T, E] in any float type; the result is float32 (the output
    projection's accumulator), for a residual stream kept in float32."""

    def __init__(self, embed_dim: int, n_head: int, key_dim: int,
                 value_dim: int, taps: int = 4, chunk: int = 64,
                 eps: float = 1e-6, name=None):
        super().__init__(name)
        self.e, self.h, self.dk, self.dv = embed_dim, n_head, key_dim, \
            value_dim
        self.taps, self.chunk, self.eps = taps, chunk, eps
        self.channels = n_head * (2 * key_dim + value_dim)

    def init(self, rng):
        ks = jax.random.split(rng, 10)
        xav, h = Xavier(), self.h
        # alpha as the layer is usually started: exp(a_log) uniform in
        # (0, 16), softplus(dt_bias) log-uniform in (0.001, 0.1)
        dt = jnp.exp(jax.random.uniform(ks[8], (h,), F32, math.log(1e-3),
                                        math.log(1e-1)))
        return {"wq": xav(ks[0], (self.e, h * self.dk)),
                "wk": xav(ks[1], (self.e, h * self.dk)),
                "wv": xav(ks[2], (self.e, h * self.dv)),
                "wz": xav(ks[3], (self.e, h * self.dv)),
                "wa": xav(ks[4], (self.e, h)),
                "wb": xav(ks[5], (self.e, h)),
                "conv": jax.random.normal(ks[6], (self.taps, self.channels))
                * self.taps ** -0.5,
                "a_log": jnp.log(jax.random.uniform(ks[7], (h,), F32,
                                                    1e-3, 16.0)),
                "dt_bias": jnp.log(jnp.expm1(dt)),
                "norm": jnp.ones((self.dv,)),
                "wo": xav(ks[9], (h * self.dv, self.e))}

    # ------------------------------------------------------------- parts
    def _rows(self, params, x):
        """The q, k, v projections before the convolution, each
        [B, T, its channels] in the weights' type. (Apart, not side by
        side: over 4 x 2048 tokens the three side by side are 180 MB,
        and their convolution in float32 360 MB more, a layer.)"""
        return [x @ params[w] for w in ("wq", "wk", "wv")]

    def _tail(self, params, x, lengths):
        """[B, taps - 1, channels]: the rows before the convolution at
        each row's last taps - 1 real positions, zeros before position
        0. Projected again from those positions of `x` (a few rows)
        rather than cut out of the whole sequence's rows, which would
        then live until the cache commit reads them: the engine warms up
        `prefill_batch` rows of `max_len` tokens against the live cache,
        and that program has to fit beside it."""
        at = lengths.astype(jnp.int32)[:, None] - self.taps + 1 \
            + jnp.arange(self.taps - 1)[None, :]
        last = jnp.take_along_axis(x, jnp.maximum(at, 0)[..., None], axis=1)
        rows = jnp.concatenate(self._rows(params, last), axis=-1)
        return jnp.where((at >= 0)[..., None], rows, 0)

    def _gates(self, params, x):
        """(log alpha, beta) [B, T, H] float32."""
        a = jnp.dot(x, params["wa"], preferred_element_type=F32)
        b = jnp.dot(x, params["wb"], preferred_element_type=F32)
        log_alpha = -jnp.exp(params["a_log"].astype(F32)) * jax.nn.softplus(
            a + params["dt_bias"].astype(F32))
        return log_alpha, 2.0 * jax.nn.sigmoid(b)

    def _conv(self, params, rows):
        """`rows`: the three of `_rows`, each [B, taps - 1 + T, .] with
        what came before in its first taps - 1 -> (q, k [B, T, H, dk],
        v [B, T, H, dv]) float32: out[t] = sum_i conv[i] * rows[t + i],
        SiLU, and the l2 norm of q (then / sqrt(dk)) and k a head."""
        with jax.named_scope("gdn conv"):
            b, t = rows[0].shape[0], rows[0].shape[1] - self.taps + 1
            out, lo = [], 0
            for z, d in zip(rows, (self.dk, self.dk, self.dv)):
                c = params["conv"][:, lo:lo + self.h * d].astype(F32)
                y = sum(z[:, i:i + t].astype(F32) * c[i]
                        for i in range(self.taps))
                out.append(jax.nn.silu(y).reshape(b, t, self.h, d))
                lo += self.h * d
            q, k, v = out
            return l2norm(q, self.eps) * self.dk ** -0.5, \
                l2norm(k, self.eps), v

    def _finish(self, params, o, x):
        """o [B, T, H, dv] float32 -> [B, T, E] float32: the norm a
        head, the gate, the output projection."""
        z = (x @ params["wz"]).astype(F32).reshape(o.shape)
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + self.eps)
        o = o * params["norm"].astype(F32) * jax.nn.silu(z)
        b, t = o.shape[:2]
        return jnp.dot(o.reshape(b, t, self.h * self.dv).astype(x.dtype),
                       params["wo"], preferred_element_type=F32)

    # ------------------------------------------------------ entry points
    def apply_prefill(self, params, x, lengths=None):
        """(out [B, T, E], state [B, H, dk, dv] after each row's last
        real token, tail [B, taps - 1, channels]: the last real rows
        before the convolution); `lengths` [B] or None for whole rows."""
        b, t, _ = x.shape
        if lengths is None:
            lengths = jnp.full((b,), t, jnp.int32)
        with jax.named_scope("linear attention"):
            x = x.astype(params["wq"].dtype)
            q, k, v = self._conv(params, [
                jnp.pad(z, ((0, 0), (self.taps - 1, 0), (0, 0)))
                for z in self._rows(params, x)])
            log_alpha, beta = self._gates(params, x)
            real = (jnp.arange(t)[None, :] < lengths[:, None])[..., None]
            log_alpha = jnp.where(real, log_alpha, 0.0)
            beta = jnp.where(real, beta, 0.0)
            with jax.named_scope("gdn chunk scan"):
                pad = -t % self.chunk
                if pad:
                    q, k, v, log_alpha, beta = (
                        jnp.pad(z, ((0, 0), (0, pad)) + ((0, 0),)
                                * (z.ndim - 2))
                        for z in (q, k, v, log_alpha, beta))
                o, state = chunk_scan(q, k, v, log_alpha, beta, self.chunk)
            return self._finish(params, o[:, :t], x), state, \
                self._tail(params, x, lengths)

    def apply(self, params, input, ctx):
        return self.apply_prefill(params, input)[0]

    def init_cache(self, slots: int, max_len: int, dtype=jnp.float32):
        return kv_cache.init_recurrent(slots, self.h, self.dk, self.dv,
                                       self.taps, self.channels, dtype)

    def apply_step(self, params, x, state, tail, positions=None):
        """One new token a row: `x` [B, 1, E] from the row's `state` and
        `tail`; both are replaced. `positions` is the attention layers'
        argument and is not read: the state carries the order. Returns
        (out [B, 1, E], state, tail)."""
        with jax.named_scope("linear attention"):
            x = x.astype(params["wq"].dtype)
            rows = jnp.concatenate([tail, jnp.concatenate(
                self._rows(params, x), axis=-1).astype(tail.dtype)], axis=1)
            nk = self.h * self.dk
            q, k, v = self._conv(params, [rows[..., :nk],
                                          rows[..., nk:2 * nk],
                                          rows[..., 2 * nk:]])
            log_alpha, beta = self._gates(params, x)
            with jax.named_scope("gdn step"):
                o, state = delta_step(state, q[:, 0], k[:, 0], v[:, 0],
                                      log_alpha[:, 0], beta[:, 0])
            return self._finish(params, o[:, None], x), state, rows[:, 1:]
