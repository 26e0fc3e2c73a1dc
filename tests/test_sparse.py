"""Sparse tensor math surface + sparse-layer gradient goldens.

Covers the reference's REAL sparse surface (round-4 review missing #4):
SparseTensorMath vdot/addmv/addmm in both orderings
(DL/tensor/SparseTensorMath.scala, SparseTensorBLAS.scala:232,348), the
implemented SparseTensor methods (sum, numNonZeroByRow, cast, applyFun,
get, resize/set/copy, concat on either dim), and torch-oracle gradient
goldens for LookupTableSparse (vs torch EmbeddingBag with
per_sample_weights) and SparseLinear (vs a dense matmul on the scattered
input) — the Wide&Deep building blocks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.nn.module import functional_apply
from bigdl_tpu.tensor import SparseTensor, SparseTensorMath


def _rand_sparse(rs, shape, density=0.3):
    dense = rs.randn(*shape).astype(np.float32)
    dense[rs.rand(*shape) > density] = 0.0
    return dense, SparseTensor.from_dense(dense)


class TestSparseTensorMath:
    def test_vdot(self):
        rs = np.random.RandomState(0)
        dense, sp = _rand_sparse(rs, (7, 5))
        v = rs.randn(7, 5).astype(np.float32)
        got = SparseTensorMath.vdot(jnp.asarray(v), sp)
        np.testing.assert_allclose(float(got), float((dense * v).sum()),
                                   rtol=1e-5)

    def test_addmv(self):
        rs = np.random.RandomState(1)
        dense, sp = _rand_sparse(rs, (6, 4))
        vec = rs.randn(4).astype(np.float32)
        t = rs.randn(6).astype(np.float32)
        got = SparseTensorMath.addmv(0.5, jnp.asarray(t), 2.0, sp,
                                     jnp.asarray(vec))
        np.testing.assert_allclose(np.asarray(got),
                                   0.5 * t + 2.0 * (dense @ vec),
                                   rtol=1e-5, atol=1e-6)

    def test_addmv_shape_checks(self):
        _, sp = _rand_sparse(np.random.RandomState(2), (6, 4))
        with pytest.raises(ValueError):
            sp.addmv(jnp.zeros((5,)))

    def test_addmm_sparse_dense(self):
        rs = np.random.RandomState(3)
        dense, sp = _rand_sparse(rs, (6, 4))
        m = rs.randn(4, 3).astype(np.float32)
        m3 = rs.randn(6, 3).astype(np.float32)
        got = SparseTensorMath.addmm(0.25, jnp.asarray(m3), 2.0, sp,
                                     jnp.asarray(m))
        np.testing.assert_allclose(np.asarray(got),
                                   0.25 * m3 + 2.0 * (dense @ m),
                                   rtol=1e-5, atol=1e-6)

    def test_addmm_dense_sparse(self):
        rs = np.random.RandomState(4)
        dense, sp = _rand_sparse(rs, (4, 7))
        m = rs.randn(5, 4).astype(np.float32)
        m3 = rs.randn(5, 7).astype(np.float32)
        got = SparseTensorMath.addmm(0.5, jnp.asarray(m3), 3.0,
                                     jnp.asarray(m), sp)
        np.testing.assert_allclose(np.asarray(got),
                                   0.5 * m3 + 3.0 * (m @ dense),
                                   rtol=1e-5, atol=1e-5)

    def test_addmm_neither_sparse_raises(self):
        with pytest.raises(TypeError):
            SparseTensorMath.addmm(0.0, None, 1.0, jnp.zeros((2, 2)),
                                   jnp.zeros((2, 2)))


class TestSparseTensorSurface:
    def test_sum_total_and_dim(self):
        """Torch semantics: sum(dim) COLLAPSES the 1-based dim — sum(1)
        on [5, 6] is the 6 per-column sums, sum(2) the 5 per-row sums."""
        rs = np.random.RandomState(5)
        dense, sp = _rand_sparse(rs, (5, 6))
        np.testing.assert_allclose(float(sp.sum()), dense.sum(), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(sp.sum(1)), dense.sum(axis=0),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(sp.sum(2)), dense.sum(axis=1),
                                   rtol=1e-5, atol=1e-6)
        # 3-D: collapse the middle dim
        dense3 = rs.randn(3, 4, 2).astype(np.float32)
        dense3[rs.rand(3, 4, 2) > 0.4] = 0.0
        sp3 = SparseTensor.from_dense(dense3)
        np.testing.assert_allclose(np.asarray(sp3.sum(2)),
                                   dense3.sum(axis=1), rtol=1e-5, atol=1e-6)

    def test_num_non_zero_by_row(self):
        dense = np.array([[1, 0, 2], [0, 0, 0], [3, 4, 5]], np.float32)
        sp = SparseTensor.from_dense(dense)
        np.testing.assert_array_equal(np.asarray(sp.num_non_zero_by_row()),
                                      [2, 0, 3])

    def test_cast_and_apply_fun(self):
        dense = np.array([[1.5, 0.0], [0.0, -2.5]], np.float32)
        sp = SparseTensor.from_dense(dense)
        assert sp.cast(jnp.bfloat16).values.dtype == jnp.bfloat16
        doubled = sp.apply_fun(lambda v: v * 2)
        np.testing.assert_allclose(doubled.to_dense().to_numpy(), dense * 2)

    def test_get_element(self):
        dense = np.array([[0.0, 7.0], [3.0, 0.0]], np.float32)
        sp = SparseTensor.from_dense(dense)
        assert sp.get(1, 2) == 7.0
        assert sp.get(2, 1) == 3.0
        assert sp.get(1, 1) == 0.0  # implicit zero

    def test_resize_set_copy(self):
        sp = SparseTensor.from_dense(np.eye(3, dtype=np.float32))
        sp.resize((4, 4), nnz=5)
        assert sp.shape == (4, 4) and sp.nnz() == 5
        other = SparseTensor.from_dense(np.eye(2, dtype=np.float32))
        sp.set_(other)
        assert sp == other
        fresh = SparseTensor.from_dense(np.zeros((2, 2), np.float32))
        fresh.resize((2, 2), nnz=2)
        fresh.copy_(other)
        np.testing.assert_allclose(fresh.to_dense().to_numpy(), np.eye(2))

    def test_resize_shrink_drops_out_of_bounds(self):
        sp = SparseTensor.from_dense(np.diag([1.0, 2.0, 3.0, 4.0])
                                     .astype(np.float32))
        sp.resize((2, 2))
        assert sp.nnz() == 2
        np.testing.assert_allclose(sp.to_dense().to_numpy(),
                                   [[1, 0], [0, 2]])

    def test_unhashable_mutable_container(self):
        sp = SparseTensor.from_dense(np.eye(2, dtype=np.float32))
        with pytest.raises(TypeError):
            hash(sp)

    def test_addmm_shape_mismatch_raises(self):
        _, sp = _rand_sparse(np.random.RandomState(6), (4, 7))
        with pytest.raises(ValueError):
            sp.addmm(jnp.zeros((5, 3)))
        with pytest.raises(ValueError):
            SparseTensorMath.addmm(0.0, None, 1.0, jnp.zeros((5, 3)), sp)

    def test_concat_dim1(self):
        a = SparseTensor.from_dense(np.array([[1.0, 0.0]], np.float32))
        b = SparseTensor.from_dense(np.array([[0.0, 2.0]], np.float32))
        j = SparseTensor.concat([a, b], dim=1)
        np.testing.assert_allclose(j.to_dense().to_numpy(),
                                   [[1, 0], [0, 2]])

    def test_scalar_ops(self):
        dense = np.array([[2.0, 0.0], [0.0, 4.0]], np.float32)
        sp = SparseTensor.from_dense(dense)
        np.testing.assert_allclose((sp * 3).to_dense().to_numpy(), dense * 3)
        np.testing.assert_allclose((3 * sp).to_dense().to_numpy(), dense * 3)
        np.testing.assert_allclose((sp / 2).to_dense().to_numpy(), dense / 2)


class TestSparseLayerGoldens:
    """Gradient goldens vs torch oracles (round-4 review weak #3: no gradient
    golden for the sparse layers)."""

    def test_lookup_table_sparse_grads_vs_embedding_bag(self):
        torch = pytest.importorskip("torch")
        import bigdl_tpu.nn as nn
        from bigdl_tpu.utils.table import Table

        rs = np.random.RandomState(0)
        n_index, n_out, B, L = 10, 6, 4, 3
        W = rs.randn(n_index, n_out).astype(np.float32)
        ids = rs.randint(1, n_index + 1, size=(B, L)).astype(np.int32)
        ids[0, 2] = 0  # padding slot
        wts = rs.rand(B, L).astype(np.float32)
        wts_masked = wts * (ids > 0)

        for combiner in ("sum", "mean"):
            layer = nn.LookupTableSparse(n_index, n_out, combiner=combiner)
            params = {"embed": {"weight": jnp.asarray(W)}}

            def loss(p):
                out, _ = functional_apply(
                    layer, p, Table(jnp.asarray(ids), jnp.asarray(wts)),
                    training=False)
                return jnp.sum(out * out)

            g = jax.grad(loss)(params)["embed"]["weight"]

            # torch oracle: EmbeddingBag with per_sample_weights; padding
            # slots emulated with zero weights on a clamped id
            tw = torch.tensor(W, requires_grad=True)
            tids = torch.tensor(np.maximum(ids - 1, 0), dtype=torch.long)
            twts = torch.tensor(wts_masked)
            if combiner == "mean":
                # torch 'mean' divides by bag length, not weight sum; use
                # sum mode with pre-normalized weights (same math as ours)
                norm = twts / twts.sum(1, keepdim=True).clamp_min(1e-12)
                out = torch.nn.functional.embedding_bag(
                    tids, tw, per_sample_weights=norm, mode="sum")
            else:
                out = torch.nn.functional.embedding_bag(
                    tids, tw, per_sample_weights=twts, mode="sum")
            (out * out).sum().backward()
            np.testing.assert_allclose(np.asarray(g), tw.grad.numpy(),
                                       rtol=1e-4, atol=1e-5,
                                       err_msg=f"combiner={combiner}")

    def test_sparse_linear_grads_vs_dense_matmul(self):
        torch = pytest.importorskip("torch")
        import bigdl_tpu.nn as nn
        from bigdl_tpu.utils.table import Table

        rs = np.random.RandomState(1)
        in_dim, out_dim, B, L = 20, 5, 3, 4
        W = rs.randn(in_dim, out_dim).astype(np.float32)
        bias = rs.randn(out_dim).astype(np.float32)
        idx = np.stack([rs.choice(in_dim, L, replace=False)
                        for _ in range(B)]).astype(np.int32)
        idx[1, 3] = -1  # padding
        vals = rs.randn(B, L).astype(np.float32)

        layer = nn.SparseLinear(in_dim, out_dim)
        params = {"weight": jnp.asarray(W), "bias": jnp.asarray(bias)}

        def loss(p):
            out, _ = functional_apply(
                layer, p, Table(jnp.asarray(idx), jnp.asarray(vals)),
                training=False)
            return jnp.sum(out * out)

        g = jax.grad(loss)(params)

        # torch oracle: scatter the sparse rows into a dense [B, in] input
        X = np.zeros((B, in_dim), np.float32)
        for b in range(B):
            for l in range(L):
                if idx[b, l] >= 0:
                    X[b, idx[b, l]] += vals[b, l]
        tw = torch.tensor(W, requires_grad=True)
        tb = torch.tensor(bias, requires_grad=True)
        out = torch.tensor(X) @ tw + tb
        (out * out).sum().backward()
        np.testing.assert_allclose(np.asarray(g["weight"]), tw.grad.numpy(),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(g["bias"]), tb.grad.numpy(),
                                   rtol=1e-4, atol=1e-5)
