"""`nn.experts.route`'s second way of scoring (sigmoid: chosen by score
plus bias, weighed by the score alone, normalised and scaled), the
experts' SiLU gate, the shared expert beside the routed sum, and the
router that reads the feed-forward's own input; and that softmax routing
with the ReLU gate lowers to the jaxpr it had before any of it."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.models.decoder import (DecoderBlock, ExpertsKind, LatentDims,
                                      LayerSpec)
from bigdl_tpu.nn.attention import GroupedQueryAttention
from bigdl_tpu.nn.experts import GatedFFN, RoutedExperts, route

SCALE = 2.448


def _logits(seed=0, n=40, e=16):
    return 2.0 * jax.random.normal(jax.random.PRNGKey(seed), (n, e))


def test_the_choice_follows_score_plus_bias_and_the_weights_the_score():
    logits = _logits()
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    idx, w = route(logits, 3, "sigmoid", bias, SCALE)
    p = np.asarray(jax.nn.sigmoid(logits))
    want = np.argsort(-(p + np.asarray(bias)), axis=-1)[:, :3]
    assert np.array_equal(np.sort(np.asarray(idx)), np.sort(want))
    chosen = np.take_along_axis(p, np.asarray(idx), axis=-1)
    np.testing.assert_allclose(
        np.asarray(w), SCALE * chosen / chosen.sum(-1, keepdims=True),
        rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), SCALE, rtol=1e-6)
    assert idx.dtype == jnp.int32 and w.dtype == jnp.float32
    # the bias moved some token's choice, or it tells nothing
    plain, _ = route(logits, 3, "sigmoid", None, SCALE)
    assert not np.array_equal(np.sort(np.asarray(plain)),
                              np.sort(np.asarray(idx)))


def test_a_bias_that_changes_the_choice_changes_no_unnormalised_weight():
    """An expert chosen with and without the bias weighs score / sum in
    both: its share of the sum moves only because its companions did."""
    logits = _logits(3)
    bias = jnp.zeros((16,)).at[5].set(10.0)      # expert 5 always chosen
    idx_b, w_b = (np.asarray(a) for a in route(logits, 3, "sigmoid", bias,
                                               1.0))
    idx_0, w_0 = (np.asarray(a) for a in route(logits, 3, "sigmoid", None,
                                               1.0))
    assert (idx_b == 5).any(axis=-1).all()
    p = np.asarray(jax.nn.sigmoid(logits))
    for n in range(logits.shape[0]):
        for idx, w in ((idx_b, w_b), (idx_0, w_0)):
            total = p[n, idx[n]].sum()
            np.testing.assert_allclose(w[n] * total, p[n, idx[n]],
                                       rtol=1e-5)
    # expert 5 weighs by its own small score, never by score + 10
    five = w_b[idx_b == 5]
    assert five.max() < 1.0 and np.all(five > 0)


def test_a_score_of_zero_everywhere_divides_by_the_epsilon_not_by_zero():
    _, w = route(jnp.full((2, 8), -200.0), 3, "sigmoid", None, SCALE)
    assert np.all(np.isfinite(np.asarray(w))) and np.all(np.asarray(w) == 0)
    with pytest.raises(ValueError, match="scoring"):
        route(jnp.zeros((2, 8)), 3, "tanh")
    with pytest.raises(ValueError, match="gate"):
        RoutedExperts(8, 4, 4, 2, gate="gelu")


def _route_before(logits, top_k):
    """`route` as it stood before it could score by sigmoid (PR 37)."""
    vals, idx = lax.top_k(logits.astype(jnp.float32), top_k)
    return idx.astype(jnp.int32), jax.nn.softmax(vals, axis=-1)


def _experts_before(m, params, x, logits):
    """`RoutedExperts.apply_routed` as it stood with the ReLU gate
    written in (PR 37's text)."""
    def chunk(x, logits):
        n, k = x.shape[0], m.top_k
        experts, weights = _route_before(logits, k)
        x = x.astype(params["wg"].dtype)
        if m.n_experts <= n * k and n <= m.n_experts:
            with jax.named_scope("moe gate up"):
                gate = jnp.einsum("nd,edh->neh", x, params["wg"])
                up = jnp.einsum("nd,edh->neh", x, params["wu"])
            with jax.named_scope("moe down"):
                chosen = experts[..., None] == jnp.arange(
                    m.n_experts, dtype=experts.dtype)
                mix = jnp.sum(jnp.where(chosen, weights[..., None], 0.0),
                              axis=1)
                hidden = (jax.nn.relu(gate) * up).astype(jnp.float32) \
                    * mix[..., None]
                return jnp.einsum("neh,ehd->nd", hidden.astype(x.dtype),
                                  params["wd"],
                                  preferred_element_type=jnp.float32), experts
        flat = experts.reshape(-1)
        order = jnp.argsort(flat)
        sizes = jnp.zeros((m.n_experts,), jnp.int32).at[flat].add(1)
        rows = x[order // k]
        gate = lax.ragged_dot(rows, params["wg"], sizes)
        up = lax.ragged_dot(rows, params["wu"], sizes)
        out = lax.ragged_dot(jax.nn.relu(gate) * up, params["wd"], sizes,
                             preferred_element_type=jnp.float32)
        out = out[jnp.argsort(order)].reshape(n, k, m.d)
        return jnp.sum(out * weights[..., None], axis=1), experts
    with jax.named_scope("moe experts"):
        n, c = x.shape[0], m.token_chunk
        if n <= c or n % c:
            return chunk(x, logits)
        y, experts = lax.map(lambda a: chunk(*a), (
            x.reshape(n // c, c, -1), logits.reshape(n // c, c, -1)))
        return y.reshape(n, -1), experts.reshape(n, -1)


@pytest.mark.parametrize("rows", [4, 40, 64])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_softmax_routing_and_the_relu_gate_lower_to_the_jaxpr_they_had(
        rows, dtype):
    """A decode step's few rows, a prefill's sorted rows, and rows taken
    a chunk at a time."""
    m = RoutedExperts(16, 8, 8, 2, token_chunk=32)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(dtype), m.init(jax.random.PRNGKey(0)))
    x = jnp.ones((rows, 16))
    logits = jnp.ones((rows, 8))
    new = jax.make_jaxpr(m.apply_routed)(params, x, logits)
    old = jax.make_jaxpr(lambda p, x, r: _experts_before(m, p, x, r))(
        params, x, logits)
    assert str(new) == str(old)
    assert str(jax.make_jaxpr(lambda r: route(r, 2))(logits)) == \
        str(jax.make_jaxpr(lambda r: _route_before(r, 2))(logits))


@pytest.mark.parametrize("rows", [4, 40, 64])
def test_the_silu_gate_is_the_gated_ffn_of_each_chosen_expert(rows):
    """Every path of the layer (few rows, sorted rows, chunks) against
    each expert applied alone as a `GatedFFN` and weighed."""
    m = RoutedExperts(16, 8, 8, 2, token_chunk=32, gate="silu",
                      scoring="sigmoid", scale=SCALE)
    params = m.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (rows, 16))
    logits = _logits(2, rows, 8)
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(3), (8,))
    with jax.default_matmul_precision("highest"):
        y, chosen = m.apply_routed(params, x, logits, bias)
        idx, w = route(logits, 2, "sigmoid", bias, SCALE)
        assert np.array_equal(np.asarray(chosen), np.asarray(idx))
        one = GatedFFN(16, 8)
        want = np.zeros((rows, 16), np.float32)
        for e in range(8):
            out = np.asarray(one.apply({n: params[n][e] for n in params}, x,
                                       None))
            weight = np.asarray(jnp.sum(jnp.where(idx == e, w, 0.0), -1))
            want += weight[:, None] * out
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)


def _block(shared=12, reads="ffn"):
    spec = LayerSpec(rope_base=1e4, shared=shared, router_reads=reads)
    return DecoderBlock(
        16, spec, GroupedQueryAttention(16, 2, 1, 8, rope_base=1e4),
        RoutedExperts(16, 8, 4, 2, gate="silu", scoring="sigmoid",
                      scale=SCALE))


def test_the_shared_expert_is_added_once_to_every_token_whatever_the_routing():
    blk = _block()
    p = blk.init(jax.random.PRNGKey(0))
    assert p["router_bias"].shape == (4,) and p["shared"]["wg"].shape == \
        (16, 12)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 16))
    with jax.default_matmul_precision("highest"):
        out, _, _, chosen = blk.apply_prefill(p, x)
        # another routing: the routed sum moves, the shared part does not
        q = dict(p, router_bias=jnp.array([5.0, 5.0, 0.0, 0.0]))
        other, _, _, chosen_q = blk.apply_prefill(q, x)
        assert not np.array_equal(np.asarray(chosen), np.asarray(chosen_q))
        silent = dict(p, experts={**p["experts"], "wd": jnp.zeros_like(
            p["experts"]["wd"])})
        no_routed, _, _, _ = blk.apply_prefill(silent, x)
        no_routed_q, _, _, _ = blk.apply_prefill(
            dict(silent, router_bias=q["router_bias"]), x)
        np.testing.assert_array_equal(np.asarray(no_routed),
                                      np.asarray(no_routed_q))
        # what is left of the feed-forward is the shared expert of u
        mixed, _, _, _ = blk._mix(p, x, lambda pa, h: blk.attn.apply_prefill(
            pa, h))
        u = blk.ln2.apply(p["ln2"], mixed, None)
        want = mixed + blk.shared.apply(p["shared"], u, None)
    np.testing.assert_allclose(np.asarray(no_routed), np.asarray(want),
                               atol=1e-5)
    assert float(jnp.abs(out - other).max()) > 1e-3


def test_the_router_reads_what_the_layer_spec_names():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 16))
    late, early = _block(0, "ffn"), _block(0, "attention")
    p = late.init(jax.random.PRNGKey(0))
    assert "shared" not in p
    with jax.default_matmul_precision("highest"):
        mixed, _, _, logits = late._mix(
            p, x, lambda pa, h: late.attn.apply_prefill(pa, h))
        assert logits is None
        _, _, _, from_h = early._mix(
            p, x, lambda pa, h: early.attn.apply_prefill(pa, h))
        h = early.ln1.apply(p["ln1"], x, None)
        np.testing.assert_allclose(np.asarray(from_h),
                                   np.asarray(h @ p["router"]), atol=1e-5)
        u = late.ln2.apply(p["ln2"], mixed, None).reshape(-1, 16)
        want, _ = route(u @ p["router"], 2, "sigmoid", p["router_bias"],
                        SCALE)
        _, _, _, chosen = late.apply_prefill(p, x)
    assert np.array_equal(np.asarray(chosen).reshape(-1, 2),
                          np.asarray(want))


def test_the_kinds_are_small_frozen_records():
    assert ExpertsKind() == ExpertsKind("relu", "softmax", 1.0)
    assert LatentDims(128, 64, 128, 512).rank == 512
    with pytest.raises(Exception):
        ExpertsKind().gate = "silu"
