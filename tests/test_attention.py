"""Attention + sequence-parallel tests.

Correctness oracle = naive O(T^2) attention; ring/Ulysses run on the
virtual 8-device CPU mesh (conftest) and must match the unsharded result
exactly (same online softmax, fp32 accumulation).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.ops.attention_kernel import (blockwise_attention,
                                            flash_attention,
                                            flash_attention_forward,
                                            naive_attention)
from bigdl_tpu.parallel.mesh import build_mesh
from bigdl_tpu.parallel.sequence import make_sequence_parallel_attention


def _qkv(b=2, h=4, t=64, d=16, seed=0):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.randn(b, h, t, d).astype(np.float32))
    return mk(), mk(), mk()


class TestBlockwise:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_naive(self, causal):
        q, k, v = _qkv()
        ref = naive_attention(q, k, v, causal=causal)
        out = blockwise_attention(q, k, v, causal=causal, block_k=16)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_ragged_tail_block(self):
        q, k, v = _qkv(t=50)  # 50 % 16 != 0 -> tail path
        ref = naive_attention(q, k, v)
        out = blockwise_attention(q, k, v, block_k=16)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_cross_attention_lengths(self):
        rs = np.random.RandomState(1)
        q = jnp.asarray(rs.randn(2, 2, 10, 8).astype(np.float32))
        k = jnp.asarray(rs.randn(2, 2, 33, 8).astype(np.float32))
        v = jnp.asarray(rs.randn(2, 2, 33, 8).astype(np.float32))
        ref = naive_attention(q, k, v)
        out = blockwise_attention(q, k, v, block_k=8)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_grad_flows(self):
        q, k, v = _qkv(t=32)

        def f(q, k, v):
            return blockwise_attention(q, k, v, causal=True,
                                       block_k=8).sum()

        gq, gk, gv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)

        def fr(q, k, v):
            return naive_attention(q, k, v, causal=True).sum()

        rq, rk, rv = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(gq, rq, atol=1e-4)
        np.testing.assert_allclose(gk, rk, atol=1e-4)
        np.testing.assert_allclose(gv, rv, atol=1e-4)


class TestPallasFlash:
    @pytest.mark.parametrize("causal", [False, True])
    def test_kernel_interpret_matches_naive(self, causal):
        q, k, v = _qkv(t=64, d=16)
        ref = naive_attention(q, k, v, causal=causal)
        out = flash_attention_forward(q, k, v, causal=causal,
                                      block_q=16, block_k=16, interpret=True)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_flash_wrapper_cpu_path(self):
        q, k, v = _qkv(t=40)
        ref = naive_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, True, None, False)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_flash_backward(self):
        q, k, v = _qkv(t=32)
        g = jax.grad(lambda q: flash_attention(q, k, v, True, None,
                                               False).sum())(q)
        gr = jax.grad(lambda q: naive_attention(q, k, v,
                                                causal=True).sum())(q)
        np.testing.assert_allclose(g, gr, atol=1e-4)


class TestPallasFlashBackward:
    """The Pallas backward kernels (round-3 review #2): dq/dk/dv from the
    saved forward logsumexp must match autodiff of the naive reference —
    the training path no longer leaves Pallas."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_bwd_kernels_match_naive_vjp(self, causal):
        rs = np.random.RandomState(0)
        B, H, T, D = 1, 2, 256, 32
        q, k, v = (jnp.asarray(rs.randn(B, H, T, D), jnp.float32) * 0.3
                   for _ in range(3))
        g = jnp.asarray(rs.randn(B, H, T, D), jnp.float32)
        from bigdl_tpu.ops.attention_kernel import flash_attention_backward
        out, lse = flash_attention_forward(q, k, v, causal=causal,
                                           block_q=64, block_k=64,
                                           interpret=True, return_lse=True)
        dq, dk, dv = flash_attention_backward(q, k, v, out, lse, g,
                                              causal=causal, block_q=64,
                                              block_k=64, interpret=True)
        _, vjp = jax.vjp(lambda a, b, c: naive_attention(a, b, c,
                                                         causal=causal),
                         q, k, v)
        for got, want, name in zip((dq, dk, dv), vjp(g),
                                   ("dq", "dk", "dv")):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-4, atol=2e-4, err_msg=name)

    @pytest.mark.parametrize("causal", [False, True])
    def test_custom_vjp_pallas_path(self, causal, monkeypatch):
        """grad through the public flash_attention with the Pallas path
        forced (interpret mode): the full fwd(lse)+bwd pipeline."""
        from bigdl_tpu.ops import attention_kernel as ak
        monkeypatch.setattr(ak, "INTERPRET", True)
        rs = np.random.RandomState(1)
        q, k, v = (jnp.asarray(rs.randn(1, 2, 512, 32), jnp.float32) * 0.3
                   for _ in range(3))

        def loss(q_, k_, v_):
            return jnp.sum(ak.flash_attention(q_, k_, v_, causal) ** 2)

        gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        rq, rk, rv = jax.grad(
            lambda a, b, c: jnp.sum(naive_attention(a, b, c,
                                                    causal=causal) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for got, want, name in zip((gq, gk, gv), (rq, rk, rv),
                                   ("dq", "dk", "dv")):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=3e-4, atol=3e-4, err_msg=name)

    def test_carry_kernel_continues_softmax_across_shards(self):
        """flash_attention_carry must continue ONE online softmax across
        KV shards — the ring-attention hop — exactly matching dense
        attention after the final normalize."""
        from bigdl_tpu.ops.attention_kernel import (
            attention_state_finish, attention_state_init,
            flash_attention_carry)
        rs = np.random.RandomState(3)
        B, H, T, D = 1, 2, 256, 32
        for causal in (False, True):
            q, k, v = (jnp.asarray(rs.randn(B, H, T, D), jnp.float32) * 0.3
                       for _ in range(3))
            half = T // 2
            state = attention_state_init(q)
            for k_off in (0, half):
                state = flash_attention_carry(
                    q, k[:, :, k_off:k_off + half],
                    v[:, :, k_off:k_off + half], state, causal=causal,
                    k_offset=k_off, block_q=64, block_k=64,
                    interpret=True)
            out = attention_state_finish(*state)
            ref = naive_attention(q, k, v, causal=causal)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=3e-5, atol=3e-5)

    def test_ring_attention_pallas_path(self, monkeypatch):
        """Ring attention with the Pallas hop kernel (forced via
        INTERPRET): forward parity vs dense AND gradients through the
        custom_vjp (blockwise-recompute backward)."""
        from bigdl_tpu.ops import attention_kernel as ak
        monkeypatch.setattr(ak, "INTERPRET", True)
        from jax.sharding import Mesh
        from bigdl_tpu.parallel.sequence import (
            make_sequence_parallel_attention)
        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        rs = np.random.RandomState(4)
        q, k, v = (jnp.asarray(rs.randn(1, 2, 256, 32), jnp.float32) * 0.3
                   for _ in range(3))
        attn = make_sequence_parallel_attention(mesh, "ring", causal=True)
        out = attn(q, k, v)
        ref = naive_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)
        g = jax.grad(lambda q_: jnp.sum(attn(q_, k, v) ** 2))(q)
        gr = jax.grad(lambda q_: jnp.sum(
            naive_attention(q_, k, v, causal=True) ** 2))(q)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                   rtol=3e-4, atol=3e-4)

    def test_zigzag_pallas_path(self, monkeypatch):
        """Zigzag ring with the Pallas hop kernel under lax.cond (forced
        via INTERPRET): forward parity + custom_vjp gradients."""
        from bigdl_tpu.ops import attention_kernel as ak
        monkeypatch.setattr(ak, "INTERPRET", True)
        from jax.sharding import Mesh
        from bigdl_tpu.parallel.sequence import (
            make_sequence_parallel_attention)
        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        rs = np.random.RandomState(5)
        q, k, v = (jnp.asarray(rs.randn(1, 2, 256, 32), jnp.float32) * 0.3
                   for _ in range(3))
        attn = make_sequence_parallel_attention(mesh, "zigzag", causal=True)
        out = attn(q, k, v)
        ref = naive_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)
        g = jax.grad(lambda q_: jnp.sum(attn(q_, k, v) ** 2))(q)
        gr = jax.grad(lambda q_: jnp.sum(
            naive_attention(q_, k, v, causal=True) ** 2))(q)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                   rtol=3e-4, atol=3e-4)

    def test_torch_sdpa_golden_fwd_bwd(self):
        """Cross-library oracle: torch scaled_dot_product_attention
        forward AND input gradients."""
        torch = pytest.importorskip("torch")
        rs = np.random.RandomState(2)
        B, H, T, D = 1, 2, 128, 16
        qn, kn, vn = (rs.randn(B, H, T, D).astype(np.float32) * 0.4
                      for _ in range(3))
        gn = rs.randn(B, H, T, D).astype(np.float32)

        qt, kt, vt = (torch.tensor(x, requires_grad=True)
                      for x in (qn, kn, vn))
        ot = torch.nn.functional.scaled_dot_product_attention(
            qt, kt, vt, is_causal=True)
        ot.backward(torch.tensor(gn))

        from bigdl_tpu.ops.attention_kernel import flash_attention_backward
        q, k, v = (jnp.asarray(x) for x in (qn, kn, vn))
        out, lse = flash_attention_forward(q, k, v, causal=True,
                                           block_q=32, block_k=32,
                                           interpret=True, return_lse=True)
        np.testing.assert_allclose(np.asarray(out), ot.detach().numpy(),
                                   rtol=2e-4, atol=2e-4)
        dq, dk, dv = flash_attention_backward(q, k, v, out, lse,
                                              jnp.asarray(gn), causal=True,
                                              block_q=32, block_k=32,
                                              interpret=True)
        np.testing.assert_allclose(np.asarray(dq), qt.grad.numpy(),
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(dk), kt.grad.numpy(),
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(dv), vt.grad.numpy(),
                                   rtol=3e-4, atol=3e-4)


class TestLayers:
    def test_mha_self_attention_shapes_and_grad(self):
        m = nn.MultiHeadAttention(32, 4, causal=True)
        x = jnp.asarray(np.random.RandomState(0)
                        .randn(2, 10, 32).astype(np.float32))
        params = m.init(jax.random.PRNGKey(0))
        out = m.apply(params, x, __import__(
            "bigdl_tpu.nn.module", fromlist=["m"]).ApplyContext())
        assert out.shape == (2, 10, 32)
        g = jax.grad(lambda p: (m.apply(p, x, __import__(
            "bigdl_tpu.nn.module", fromlist=["m"]).ApplyContext()) ** 2)
            .sum())(params)
        assert all(np.all(np.isfinite(l))
                   for l in jax.tree_util.tree_leaves(g))

    def test_mha_causality(self):
        # causal: output at t must not depend on inputs after t
        m = nn.MultiHeadAttention(16, 2, causal=True, use_flash=False)
        params = m.init(jax.random.PRNGKey(1))
        from bigdl_tpu.nn.module import ApplyContext
        x = jnp.asarray(np.random.RandomState(2)
                        .randn(1, 8, 16).astype(np.float32))
        o1 = m.apply(params, x, ApplyContext())
        x2 = x.at[:, -1].set(99.0)
        o2 = m.apply(params, x2, ApplyContext())
        np.testing.assert_allclose(o1[:, :-1], o2[:, :-1], atol=1e-5)

    def test_mha_cross_attention(self):
        from bigdl_tpu.utils.table import T
        m = nn.MultiHeadAttention(16, 2)
        params = m.init(jax.random.PRNGKey(0))
        from bigdl_tpu.nn.module import ApplyContext
        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.randn(2, 5, 16).astype(np.float32))
        kv = jnp.asarray(rs.randn(2, 9, 16).astype(np.float32))
        out = m.apply(params, T(q, kv), ApplyContext())
        assert out.shape == (2, 5, 16)

    def test_rope_rotation_property(self):
        # RoPE: dot(q_i, k_j) depends only on i - j
        d = 8
        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.randn(1, 1, 16, d).astype(np.float32))
        k = jnp.asarray(rs.randn(1, 1, 16, d).astype(np.float32))
        qr, kr = nn.rope(q), nn.rope(k)
        s = jnp.einsum("bhqd,bhkd->bhqk", qr, kr)[0, 0]
        # same relative offset, same base vectors -> same score: compare
        # (i=5,j=3) built from constant vectors
        qc = jnp.tile(q[:, :, :1], (1, 1, 16, 1))
        kc = jnp.tile(k[:, :, :1], (1, 1, 16, 1))
        sc = jnp.einsum("bhqd,bhkd->bhqk", nn.rope(qc), nn.rope(kc))[0, 0]
        np.testing.assert_allclose(sc[5, 3], sc[9, 7], atol=1e-4)
        np.testing.assert_allclose(sc[5, 3], sc[14, 12], atol=1e-4)

    def test_transformer_block_trains(self):
        blk = nn.TransformerBlock(16, 2, causal=True)
        params = blk.init(jax.random.PRNGKey(0))
        from bigdl_tpu.nn.module import ApplyContext
        x = jnp.asarray(np.random.RandomState(0)
                        .randn(2, 6, 16).astype(np.float32))

        @jax.jit
        def loss(p):
            return (blk.apply(p, x, ApplyContext()) ** 2).sum()

        g = jax.grad(loss)(params)
        assert all(np.all(np.isfinite(l))
                   for l in jax.tree_util.tree_leaves(g))


class TestSequenceParallel:
    @pytest.mark.parametrize("scheme", ["ring", "ulysses"])
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_unsharded(self, scheme, causal):
        mesh = build_mesh(data=8, model=1)
        q, k, v = _qkv(b=2, h=8, t=64, d=16)
        ref = naive_attention(q, k, v, causal=causal)
        fn = make_sequence_parallel_attention(mesh, scheme=scheme,
                                              axis_name="data",
                                              causal=causal)
        out = jax.jit(fn)(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4)

    def test_ring_grad_matches(self):
        mesh = build_mesh(data=4, model=2)
        q, k, v = _qkv(b=1, h=4, t=32, d=8)
        fn = make_sequence_parallel_attention(mesh, scheme="ring",
                                              axis_name="data", causal=True)
        g = jax.grad(lambda q: jax.jit(fn)(q, k, v).sum())(q)
        gr = jax.grad(lambda q: naive_attention(q, k, v,
                                                causal=True).sum())(q)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-4)

    def test_ulysses_head_divisibility_error(self):
        mesh = build_mesh(data=8, model=1)
        q, k, v = _qkv(b=1, h=4, t=64, d=8)  # 4 heads, 8 devices
        fn = make_sequence_parallel_attention(mesh, scheme="ulysses",
                                              axis_name="data")
        with pytest.raises(ValueError):
            jax.jit(fn)(q, k, v)

    def test_zigzag_matches_unsharded(self):
        """Load-balanced causal ring: natural-order in/out, exact vs
        naive (the zigzag reorder + skip logic changes scheduling, not
        math)."""
        mesh = build_mesh(data=8, model=1)
        q, k, v = _qkv(b=2, h=4, t=64, d=16)
        ref = naive_attention(q, k, v, causal=True)
        fn = make_sequence_parallel_attention(mesh, scheme="zigzag",
                                              axis_name="data", causal=True)
        out = jax.jit(fn)(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4)

    def test_zigzag_grads_match(self):
        mesh = build_mesh(data=4, model=2)
        q, k, v = _qkv(b=1, h=4, t=32, d=8)
        fn = make_sequence_parallel_attention(mesh, scheme="zigzag",
                                              axis_name="data", causal=True)
        for argnum in range(3):
            g = jax.grad(lambda *a: jax.jit(fn)(*a).sum(),
                         argnums=argnum)(q, k, v)
            gr = jax.grad(
                lambda *a: naive_attention(*a, causal=True).sum(),
                argnums=argnum)(q, k, v)
            np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                       atol=1e-4)

    def test_zigzag_refuses_non_causal(self):
        mesh = build_mesh(data=8, model=1)
        q, k, v = _qkv(b=1, h=2, t=64, d=8)
        fn = make_sequence_parallel_attention(mesh, scheme="zigzag",
                                              axis_name="data", causal=False)
        with pytest.raises(Exception, match="causal"):
            jax.jit(fn)(q, k, v)

    def test_zigzag_order_round_trip(self):
        from bigdl_tpu.parallel.sequence import zigzag_inverse, zigzag_order
        n, t = 4, 64
        order, inv = zigzag_order(n, t), zigzag_inverse(n, t)
        np.testing.assert_array_equal(np.arange(t), order[inv])
        # device 0's shard = chunks 0 and 2n-1
        c = t // (2 * n)
        np.testing.assert_array_equal(order[:c], np.arange(c))
        np.testing.assert_array_equal(order[c:2 * c],
                                      np.arange(t - c, t))


class TestLongContext:
    """Long-sequence blockwise path: 4k tokens on CPU must match naive
    numerically — the correctness backbone of the long-context story."""

    def test_blockwise_4k_tokens_matches_naive(self):
        q, k, v = _qkv(b=1, h=2, t=4096, d=32, seed=3)
        want = naive_attention(q, k, v, causal=True)
        got = blockwise_attention(q, k, v, causal=True, block_k=512)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_ring_attention_long_sequence(self):
        # 2048 tokens sharded over the 8-device mesh sequence axis
        mesh = build_mesh(data=8)
        attn = make_sequence_parallel_attention(mesh, scheme="ring",
                                                causal=True)
        q, k, v = _qkv(b=1, h=2, t=2048, d=16, seed=4)
        want = naive_attention(q, k, v, causal=True)
        got = attn(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)


def test_flash_plan_block_q_tuned_default():
    """bq=512 on 512-divisible lengths (+6-8% fwd+bwd on v5e, r05 sweep);
    ragged lengths keep the 256 fallback and its padding behavior."""
    from bigdl_tpu.ops.attention_kernel import _flash_plan
    use, bq, bk, pq, pk = _flash_plan((1, 8, 2048, 64), (1, 8, 2048, 64),
                                      True, True)
    assert use and bq == 512 and bk == 1024
    use, bq, bk, pq, pk = _flash_plan((1, 8, 8192, 64), (1, 8, 8192, 64),
                                      True, True)
    assert use and bq == 512 and bk == 1024
    # ragged: not divisible by 512 -> legacy 256 path with padding
    use, bq, bk, pq, pk = _flash_plan((1, 8, 300, 64), (1, 8, 300, 64),
                                      True, True)
    assert use and bq == 256
