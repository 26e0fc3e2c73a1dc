"""`nn.GatedDeltaRule` (nn/linear_attention.py) against the plain
reference in `hybrid_decoder_reference.py`, which runs the recurrence
token by token exactly as ISSUE 36 writes it: the chunked prefill, the
one-token step and the reference are three forms of ONE recurrence. At
the small size: hidden 64, 4 heads of key 8 / value 16, 4 taps, chunk 8.

Tolerances: float32 on the CPU with the matmul precision at "highest",
so the forms differ by summation order and by the triangular solve's
rounding: 2e-5 on outputs and states of size O(1) is some hundred
float32 roundings of room (the solve of a 8 x 8 unit-triangular system
whose entries are at most 2 amplifies a rounding some ten times).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hybrid_decoder_reference as ref
from bigdl_tpu.nn import kv_cache
from bigdl_tpu.nn.linear_attention import (SPAN, GatedDeltaRule, chunk_scan,
                                           delta_step)

CFG = ref.SMALL
TOL = 2e-5
E, H, DK, DV = CFG["hidden"], CFG["lin_heads"], CFG["lin_key"], \
    CFG["lin_value"]


@pytest.fixture(scope="module")
def layer():
    return GatedDeltaRule(E, H, DK, DV, CFG["taps"], CFG["chunk"], CFG["eps"])


@pytest.fixture(scope="module")
def weights():
    return ref.sub(ref.init_weights(CFG, 7), 0)


@pytest.fixture(scope="module")
def params(weights):
    return ref.mixer_to_program(weights, "linear")


def _x(seed, rows, t):
    return jax.random.normal(jax.random.PRNGKey(seed), (rows, t, E))


def _reference(weights, x):
    """(mixer output, every state [B, T, H, dk, dv], the rows before the
    convolution [B, T, channels])."""
    with jax.default_matmul_precision("highest"):
        q, k, v = ref.linear_qkv(CFG, weights, x)
        _, states = ref.delta_rule(q, k, v, *ref.gates(weights, x))
        rows = jnp.concatenate([x @ weights[n] for n in ("wq", "wk", "wv")],
                               axis=-1)
        return ref.linear_attention(CFG, weights, x), states, rows


@pytest.mark.parametrize("t", [1, 2, 3, 7, 8, 9, 63])
def test_chunked_prefill_is_the_token_by_token_recurrence(
        layer, params, weights, t):
    """Shorter than the taps (1, 2, 3), one under, at and over the chunk
    (7, 8, 9), and not a multiple of it (63)."""
    x = _x(t, 2, t)
    want, states, rows = _reference(weights, x)
    out, state, tail = jax.jit(layer.apply_prefill)(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=TOL)
    np.testing.assert_allclose(np.asarray(state), np.asarray(states[:, -1]),
                               atol=TOL)
    # the last three rows before the convolution, zeros before position 0
    padded = np.concatenate([np.zeros((2, 3, rows.shape[-1]), np.float32),
                             np.asarray(rows)], axis=1)
    np.testing.assert_allclose(np.asarray(tail), padded[:, -3:], atol=TOL)
    np.testing.assert_allclose(
        np.asarray(layer.apply(params, x, None)), np.asarray(want), atol=TOL)


def test_right_padded_rows_hand_over_the_state_after_the_last_real_token(
        layer, params, weights):
    t, lengths = 24, np.array([24, 1, 2, 9, 16, 17], np.int32)
    x = _x(3, len(lengths), t)
    _, states, rows = _reference(weights, x)
    out, state, tail = jax.jit(layer.apply_prefill)(
        params, x, jnp.asarray(lengths))
    padded = np.concatenate([np.zeros((len(lengths), 3, rows.shape[-1]),
                                      np.float32), np.asarray(rows)], axis=1)
    for j, n in enumerate(lengths):
        np.testing.assert_allclose(np.asarray(state[j]),
                                   np.asarray(states[j, n - 1]), atol=TOL)
        np.testing.assert_allclose(np.asarray(tail[j]), padded[j, n:n + 3],
                                   atol=TOL)
        # the padding changes no real position's output either
        alone = layer.apply(params, x[j:j + 1, :n], None)
        np.testing.assert_allclose(np.asarray(out[j, :n]),
                                   np.asarray(alone[0]), atol=TOL)


@pytest.mark.parametrize("prompt", [1, 2, 5, 8, 13])
def test_steps_continue_a_prefill_as_the_reference_does(
        layer, params, weights, prompt):
    """Prefill `prompt` tokens, then one token at a time from the state
    and the tail: every position's output and state are the reference's
    over the whole row."""
    t = 20
    x = _x(prompt, 3, t)
    want, states, _ = _reference(weights, x)
    _, state, tail = layer.apply_prefill(params, x[:, :prompt])
    step = jax.jit(layer.apply_step)
    for p in range(prompt, t):
        out, state, tail = step(params, x[:, p:p + 1], state, tail)
        np.testing.assert_allclose(np.asarray(out[:, 0]),
                                   np.asarray(want[:, p]), atol=TOL)
        np.testing.assert_allclose(np.asarray(state), np.asarray(states[:, p]),
                                   atol=TOL)
    assert tail.shape == (3, 3, H * (2 * DK + DV))


def test_a_scan_can_start_from_a_state():
    """`chunk_scan` over the second half from the first half's state is
    the scan over the whole: the carry is the whole of what is kept."""
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (2, 32, H, DK))
    k = ref._l2norm(jax.random.normal(ks[1], (2, 32, H, DK)), 1e-6)
    v = jax.random.normal(ks[2], (2, 32, H, DV))
    la = -jax.random.uniform(ks[3], (2, 32, H), jnp.float32, 0.0, 2.0)
    beta = jax.random.uniform(ks[4], (2, 32, H), jnp.float32, 0.0, 2.0)
    whole, s_whole = chunk_scan(q, k, v, la, beta, 8)
    _, s_half = chunk_scan(q[:, :16], k[:, :16], v[:, :16], la[:, :16],
                           beta[:, :16], 8)
    rest, s_rest = chunk_scan(q[:, 16:], k[:, 16:], v[:, 16:], la[:, 16:],
                              beta[:, 16:], 8, s_half)
    np.testing.assert_allclose(np.asarray(rest), np.asarray(whole[:, 16:]),
                               atol=TOL)
    np.testing.assert_allclose(np.asarray(s_rest), np.asarray(s_whole),
                               atol=TOL)
    # nor do spans: 16 chunks of 2 are two spans of SPAN chunks, solved
    # one after the other with the state carried over
    assert 32 // 2 == 2 * SPAN
    spanned, s_spanned = chunk_scan(q, k, v, la, beta, 2)
    np.testing.assert_allclose(np.asarray(spanned), np.asarray(whole),
                               atol=TOL)
    np.testing.assert_allclose(np.asarray(s_spanned), np.asarray(s_whole),
                               atol=TOL)
    # and the chunk's size changes no number
    other, s_other = chunk_scan(q, k, v, la, beta, 16)
    np.testing.assert_allclose(np.asarray(other), np.asarray(whole), atol=TOL)
    np.testing.assert_allclose(np.asarray(s_other), np.asarray(s_whole),
                               atol=TOL)


def test_beta_reaches_two_and_a_negative_eigenvalue_step_is_exact(layer):
    """`linear_allow_neg_eigval`: beta = 2 sigmoid(.) reaches 2, where
    the step I - beta k k^T has the eigenvalue -1 along k: a state that
    holds w under a unit key k holds -w after a token of that key with
    nothing to write (v = 0, alpha = 1), in both forms, to the bit."""
    p = layer.init(jax.random.PRNGKey(0))
    p = dict(p, wb=jnp.full_like(p["wb"], 40.0 / E))
    _, beta = layer._gates(p, jnp.ones((1, 1, E)))
    assert np.all(np.asarray(beta) == 2.0)
    k = jnp.zeros((1, H, DK)).at[:, :, 2].set(1.0)
    w = jax.random.normal(jax.random.PRNGKey(1), (1, H, DV))
    s = k[..., None] * w[..., None, :]
    zeros, two = jnp.zeros((1, H)), jnp.full((1, H), 2.0)
    o, new = delta_step(s, k, k, jnp.zeros_like(w), zeros, two)
    assert np.array_equal(np.asarray(new), -np.asarray(s))
    assert np.array_equal(np.asarray(o), -np.asarray(w))
    # the chunked form: eight such tokens turn the state over eight times
    rep = lambda z: jnp.repeat(z[:, None], 8, axis=1)
    o8, s8 = chunk_scan(rep(k), rep(k), rep(jnp.zeros_like(w)), rep(zeros),
                        rep(two), 8, s)
    np.testing.assert_allclose(np.asarray(s8), np.asarray(s), atol=1e-6)
    np.testing.assert_allclose(np.asarray(o8[:, 0]), -np.asarray(w), atol=1e-6)
    np.testing.assert_allclose(np.asarray(o8[:, 1]), np.asarray(w), atol=1e-6)
    # beta = 1 (not doubled) would have wiped the key's row instead
    _, wiped = delta_step(s, k, k, jnp.zeros_like(w), zeros, two / 2)
    assert np.all(np.asarray(wiped) == 0.0)


def test_the_update_never_grows_a_state_beyond_what_it_writes(layer, params):
    """An idle slot rides along in every decode step, its state replaced
    200 times by the same token: alpha (I - beta k k^T) has no eigenvalue
    outside [-1, 1] for a unit k, so the state stays finite and of the
    size of what is written, beta |v| / (1 - alpha)."""
    x = _x(9, 2, 1)
    state, tail = layer.init_cache(2, 64)
    step = jax.jit(layer.apply_step)
    sizes = []
    for _ in range(200):
        out, state, tail = step(params, x, state, tail)
        sizes.append(float(jnp.abs(state).max()))
    assert np.all(np.isfinite(np.asarray(state)))
    assert np.all(np.isfinite(np.asarray(out)))
    assert max(sizes) < 50.0 and abs(sizes[-1] - sizes[-2]) < 1e-3


def test_a_layers_cache_is_the_recurrent_kind(layer):
    state, tail = layer.init_cache(5, 64, jnp.bfloat16)
    assert state.shape == (5, H, DK, DV) and state.dtype == jnp.float32
    assert tail.shape == (5, 3, H * (2 * DK + DV))
    assert tail.dtype == jnp.bfloat16
    with pytest.raises(ValueError):
        kv_cache.init_recurrent(0, H, DK, DV, 4, 128)


def test_commit_sets_a_slots_state_whole_and_is_idempotent():
    """A bucket's padding repeats the last request's row, slot id
    included: the repeated commit rewrites what the first wrote; slots
    not named keep theirs; whatever a slot held before is gone."""
    cache = jnp.full((4, H, DK, DV), -1.0)
    new = jnp.arange(3 * H * DK * DV, dtype=jnp.float32).reshape(3, H, DK, DV)
    new = new.at[2].set(new[1])
    out = kv_cache.commit(cache, new, jnp.array([2, 0, 0]))
    assert np.array_equal(np.asarray(out[2]), np.asarray(new[0]))
    assert np.array_equal(np.asarray(out[0]), np.asarray(new[1]))
    assert np.all(np.asarray(out[1]) == -1.0) and np.all(
        np.asarray(out[3]) == -1.0)
    once = kv_cache.commit(cache, new[:2], jnp.array([2, 0]))
    assert np.array_equal(np.asarray(out), np.asarray(once))
    tail = kv_cache.commit(jnp.zeros((4, 3, 128)), jnp.ones((1, 3, 128)),
                           jnp.array([3]))
    assert float(tail.sum()) == 3 * 128 and float(tail[3].sum()) == 3 * 128
