"""The forward flash kernel with a window and with K/V heads shared by
several query heads, in Pallas interpret mode on the CPU, against
`naive_attention` with the mask written out; and that the call with
neither argument is the kernel it always was.

Tolerance: float32 inputs, float32 accumulation in both; the online
softmax rescales block by block, so 2e-5 on outputs of size O(1) leaves
some twenty roundings of room.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.ops import attention_kernel as ak


def explicit(q, k, v, window):
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    t = q.shape[2]
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    keep = j <= i
    if window is not None:
        keep = keep & (j > i - window)
    return ak.naive_attention(q, k, v, mask=keep[None, None])


def qkv(h, hk, t, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (2, h, t, d)),
            jax.random.normal(ks[1], (2, hk, t, d)),
            jax.random.normal(ks[2], (2, hk, t, d)))


@pytest.mark.parametrize("h,hk,t,d,window,bq,bk", [
    (4, 1, 256, 64, None, 128, 128),    # 4-for-1 grouped heads, no window
    (4, 1, 256, 64, 40, 128, 64),       # grouped and windowed
    (2, 2, 256, 64, 100, 128, 128),     # a window alone
    (8, 2, 512, 128, 200, 128, 128),    # window across three K/V blocks
    (7, 1, 384, 128, 130, 128, 128),    # the 7-for-1 of 28 heads over 4
    (4, 2, 256, 32, 1, 128, 128),       # a window of one: the row itself
    (4, 2, 256, 32, 256, 128, 128),     # a window as long as the sequence
])
def test_grouped_and_windowed_forward_matches_the_explicit_mask(
        h, hk, t, d, window, bq, bk):
    q, k, v = qkv(h, hk, t, d)
    got = ak.flash_attention_forward(q, k, v, causal=True, interpret=True,
                                     window=window, block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(explicit(q, k, v, window)),
                               atol=2e-5)


def _pallas_eqns(fn, *args):
    return [e for e in jax.make_jaxpr(fn)(*args).eqns
            if e.primitive.name == "pallas_call"]


def test_with_neither_argument_it_is_the_kernel_it_always_was():
    q, k, v = qkv(4, 4, 512, 64)
    (eqn,) = _pallas_eqns(lambda a, b, c: ak.flash_attention_forward(
        a, b, c, causal=True, interpret=True), q, k, v)
    (same,) = _pallas_eqns(lambda a, b, c: ak.flash_attention_forward(
        a, b, c, causal=True, interpret=True, window=None), q, k, v)
    assert str(eqn) == str(same)
    assert eqn.params["name"] == "flash_fwd"
    # [B*H, T, D] operands, one program a (head, q block), lse beside out
    assert [tuple(x.aval.shape) for x in eqn.invars] == [(8, 512, 64)] * 3
    assert [tuple(x.aval.shape) for x in eqn.outvars] == \
        [(8, 512, 64), (8, 1, 512)]
    assert tuple(eqn.params["grid_mapping"].grid) == (8, 2)


@pytest.mark.parametrize("window,name", [(None, "flash_fwd_gqa"),
                                         (64, "flash_fwd_window")])
def test_the_new_calls_have_names_of_their_own(window, name):
    q, k, v = qkv(4, 2, 256, 64)
    (eqn,) = _pallas_eqns(lambda a, b, c: ak.flash_attention_forward(
        a, b, c, causal=True, interpret=True, window=window), q, k, v)
    assert eqn.params["name"] == name
    # q rides as [B*Hkv, group, T, D]: one K/V block serves the group
    assert tuple(eqn.invars[0].aval.shape) == (4, 2, 256, 64)
    assert tuple(eqn.invars[1].aval.shape) == (4, 256, 64)


def test_a_window_skips_whole_blocks_not_only_masks_them():
    """The K/V axis of the grid is as long as a q block's window needs,
    not as long as the sequence."""
    q, k, v = qkv(2, 1, 1024, 64)
    grid = {}
    for window in (None, 128):
        (eqn,) = _pallas_eqns(lambda a, b, c: ak.flash_attention_forward(
            a, b, c, causal=True, interpret=True, window=window,
            block_q=128, block_k=128), q, k, v)
        grid[window] = tuple(eqn.params["grid_mapping"].grid)
    assert grid[None] == (2, 8, 8)
    assert grid[128] == (2, 8, 3)


def test_the_grouped_forward_is_causal_and_returns_no_logsumexp():
    q, k, v = qkv(4, 2, 256, 64)
    with pytest.raises(ValueError):
        ak.flash_attention_forward(q, k, v, causal=False, interpret=True)
    with pytest.raises(ValueError):
        ak.flash_attention_forward(q, k, v, causal=True, interpret=True,
                                   window=8, return_lse=True)


@pytest.mark.parametrize("t,window", [(20, None), (20, 6), (256, 100)])
def test_causal_grouped_attention_takes_either_path(t, window, monkeypatch):
    """Plain XLA at sizes that fill no block, the kernel (interpreted
    here) where they do; both are the explicit mask."""
    q, k, v = qkv(4, 2, t, 32)
    want = np.asarray(explicit(q, k, v, window))
    np.testing.assert_allclose(
        np.asarray(ak.causal_grouped_attention(q, k, v, window)), want,
        atol=2e-5)
    monkeypatch.setattr(ak, "INTERPRET", True)
    np.testing.assert_allclose(
        np.asarray(ak.causal_grouped_attention(q, k, v, window)), want,
        atol=2e-5)
