"""`nn.ShortConv` (nn/short_conv.py), the gated short convolution, and
the two attention-side pieces that came with it into `DecoderLM`: an
RMSNorm a head on q and k (`qk_norm_per_head`), and a head tied to the
embedding (`tie_head`); and the cache's folded heads (a head of 64
lanes beside another in a row of 128, nn/kv_cache.py).

The mixer is held to a plain float32 form written here: B, C, x =
split(h @ Win); u = B * x; z_t = sum_j w_j u_{t-2+j}; y = (C * z) @ Wout.
With float32 weights the program's only rounding is XLA's order of
float sums, so the tolerances are 1e-5 relative (a bfloat16 rounding of
u, the one point where the program rounds with bfloat16 weights, moves
the result by some 4e-3 of its scale and would fail them)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models.decoder import DecoderLM, LayerSpec
from bigdl_tpu.nn import kv_cache
from bigdl_tpu.nn.attention import GroupedQueryAttention
from bigdl_tpu.nn.short_conv import ShortConv

E, TAPS = 32, 3
HI = jax.lax.Precision.HIGHEST
#: float32 program against a float32 form: the order of sums alone
TOL = 1e-5


def _plain(params, x):
    """The mixer over whole rows [B, T, E], float32, written out."""
    bcx = jnp.dot(x, params["in_proj"], precision=HI)
    b, c, z = bcx[..., :E], bcx[..., E:2 * E], bcx[..., 2 * E:]
    u = b * z
    t = x.shape[1]
    padded = jnp.pad(u, ((0, 0), (TAPS - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + t] * params["conv"][j] for j in range(TAPS))
    return jnp.dot(c * conv, params["out_proj"], precision=HI), u


@pytest.fixture(scope="module")
def layer():
    mixer = ShortConv(E, TAPS)
    params = mixer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 9, E))
    return mixer, params, x


def _close(a, b, tol=TOL):
    scale = float(jnp.max(jnp.abs(b)))
    assert float(jnp.max(jnp.abs(a - b))) <= tol * scale, \
        (float(jnp.max(jnp.abs(a - b))), scale)


def test_prefill_is_the_plain_convolution(layer):
    mixer, params, x = layer
    with jax.default_matmul_precision("highest"):
        out, tail = mixer.apply_prefill(params, x)
    want, u = _plain(params, x)
    _close(out, want)
    _close(tail, u[:, -2:])
    assert tail.shape == (3, TAPS - 1, E)


def test_a_bfloat16_tail_would_fail_the_tolerance(layer):
    """The tolerance can see a rounding of u to bfloat16: the same
    mixer over bfloat16 weights lies outside it."""
    mixer, params, x = layer
    half = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    out, _ = mixer.apply_prefill(half, x)
    want, _ = _plain(params, x)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(out - want))) > 10 * TOL * scale


@pytest.mark.parametrize("lengths", [(9, 9, 9), (1, 2, 7), (2, 9, 1)])
def test_step_by_step_decode_is_the_prefill(layer, lengths):
    """Prefill of each row's first `length` tokens (right-padded in one
    batch), then one step a token from the tail it left: every output
    the whole sequence's, and the tail at positions 0 and 1 holds zeros
    before the row's first token."""
    mixer, params, x = layer
    lengths = jnp.array(lengths)
    with jax.default_matmul_precision("highest"):
        want, u = _plain(params, x)
        out, tail = mixer.apply_prefill(params, x, lengths)
        for r, n in enumerate(lengths.tolist()):
            _close(out[r, :n], want[r, :n])
            kept = np.asarray(tail[r])
            for i in range(TAPS - 1):
                at = n - TAPS + 1 + i
                if at < 0:
                    assert not kept[i].any()
                else:
                    _close(kept[i], u[r, at])
        # one step a token from each row's own length on, all rows at
        # once (a row past the end repeats its last token, not read)
        for t in range(int(lengths.min()), x.shape[1]):
            at = jnp.minimum(jnp.maximum(lengths, t), x.shape[1] - 1)
            step_x = x[jnp.arange(3), at][:, None]
            y, new = mixer.apply_step(params, step_x, tail)
            for r, n in enumerate(lengths.tolist()):
                if t >= n:
                    _close(y[r, 0], want[r, t])
            tail = jnp.where((t >= lengths)[:, None, None], new, tail)


def test_tail_buffer_is_a_tail_alone():
    mixer = ShortConv(E, TAPS)
    (tail,) = mixer.init_cache(5, 64, jnp.bfloat16)
    assert tail.shape == (5, TAPS - 1, E) and tail.dtype == jnp.bfloat16
    assert kv_cache.init_tail(5, TAPS, E).shape == (5, TAPS - 1, E)
    with pytest.raises(ValueError):
        ShortConv(E, 1)


# ----------------------------------------------------- the q/k norm a head
def _gqa(per_head):
    return GroupedQueryAttention(64, 8, 2, 8, rope_base=1e6, qk_norm=1e-5,
                                 qk_norm_per_head=per_head)


def test_per_head_qk_norm_normalises_each_head_apart():
    """Each head of q and k has unit root mean square times the one
    shared scale; the whole-projection form (olmo's) normalises the 64
    values of all heads together and does not."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 64))
    layer = _gqa(True)
    p = layer.init(jax.random.PRNGKey(3))
    assert p["q_norm"].shape == p["k_norm"].shape == (8,)
    p = dict(p, q_norm=1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(4), (8,)))
    q = (x @ p["wq"]).reshape(2, 5, 8, 8)
    want = q / jnp.sqrt(jnp.mean(q * q, -1, keepdims=True) + 1e-5) \
        * p["q_norm"]
    no_rope = GroupedQueryAttention(64, 8, 2, 8, qk_norm=1e-5,
                                    qk_norm_per_head=True)
    got, _, _ = no_rope.project_qkv(p, x)
    _close(jnp.transpose(got, (0, 2, 1, 3)), want)
    whole = GroupedQueryAttention(64, 8, 2, 8, qk_norm=1e-5)
    pw = whole.init(jax.random.PRNGKey(3))
    assert pw["q_norm"].shape == (64,) and pw["k_norm"].shape == (16,)
    got_whole, _, _ = whole.project_qkv(
        dict(p, q_norm=jnp.tile(p["q_norm"], 8),
             k_norm=jnp.tile(p["k_norm"], 2)), x)
    rms = jnp.sqrt(jnp.mean(got_whole * got_whole, -1))
    assert float(jnp.max(jnp.abs(rms - 1.0))) > 0.05
    gap = float(jnp.max(jnp.abs(got_whole - got)))
    assert gap > 100 * TOL * float(jnp.max(jnp.abs(got)))


# ------------------------------------------------------ the tied head
def _tiny(tie):
    layers = [LayerSpec(mixer="short_conv", ffn="dense"),
              LayerSpec(rope_base=1e6, ffn="dense")]
    return DecoderLM(48, 32, 4, 2, 8, layers, ffn_dim=40, eps=1e-5,
                     conv_taps=TAPS, qk_norm=True, qk_norm_per_head=True,
                     tie_head=tie)


def test_a_tied_head_is_the_embeddings_transpose():
    tied, free = _tiny(True), _tiny(False)
    p = tied.init(jax.random.PRNGKey(5))
    assert "head" not in p and "head" in free.init(jax.random.PRNGKey(5))
    toks = jnp.array([[3, 9, 27, 4, 12]])
    with jax.default_matmul_precision("highest"):
        got = tied.apply(p, toks, None)
        want = free.apply(dict(p, head=p["embed"].T), toks, None)
    _close(got, want)
    # the sum of parameters: the embedding counted once
    n = sum(a.size for a in jax.tree_util.tree_leaves(p))
    m = sum(a.size for a in jax.tree_util.tree_leaves(
        free.init(jax.random.PRNGKey(5))))
    assert m - n == 48 * 32


def test_a_short_conv_layer_keeps_no_positions():
    with pytest.raises(ValueError, match="short_conv"):
        LayerSpec(mixer="short_conv", window=8)
    model = _tiny(True)
    cache = model.init_cache(3, 16)
    assert cache["conv_tail"][0].shape == (3, TAPS - 1, 32)
    assert cache["conv_tail"][1] is None and cache["k"][0] is None
    stats = model.cache_stats(cache)
    assert stats["conv_tail_bytes"] == 3 * 2 * 32 * 4
    assert stats["conv_slot_steps"] == 0


# ------------------------------------------------ heads folded in lanes
@pytest.mark.parametrize("kv_heads,head_dim,fold", [
    (8, 64, 2), (2, 64, 2), (4, 32, 4), (8, 128, 1), (3, 64, 1),
    (2, 16, 1), (4, 96, 1)])
def test_narrow_heads_fold_into_128_lanes(kv_heads, head_dim, fold):
    assert kv_cache.lane_fold(kv_heads, head_dim) == fold
    buf = kv_cache.init(2, kv_heads, 32, head_dim)
    assert buf.shape == (2, kv_heads // fold, 32, fold * head_dim)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, kv_heads, 5, head_dim))
    folded = kv_cache.fold_heads(x, fold)
    assert folded.shape == (2, kv_heads // fold, 5, fold * head_dim)
    assert bool((kv_cache.unfold_heads(folded, fold) == x).all())
    if fold > 1:  # head i * fold + j in lanes j * head_dim of row i
        assert bool((folded[:, 0, :, head_dim:2 * head_dim]
                     == x[:, 1]).all())
