"""Pipeline (GPipe) and expert (MoE) parallelism on the virtual mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.module import ApplyContext
from bigdl_tpu.parallel.moe import MoE
from bigdl_tpu.parallel.pipeline import GPipe


def _pipe_mesh(n):
    return Mesh(np.array(jax.devices()[:n]).reshape(n), ("pipe",))


class TestGPipe:
    def _setup(self, n_stages=4, n_micro=4, width=16):
        block = nn.Sequential().add(nn.Linear(width, width)).add(nn.Tanh())
        gp = GPipe(block, n_stages=n_stages, n_micro=n_micro)
        params = gp.init(jax.random.PRNGKey(0))
        return gp, params

    def test_matches_sequential(self):
        gp, params = self._setup()
        mesh = _pipe_mesh(4)
        placed = gp.place_params(mesh, params)
        x = jnp.asarray(np.random.RandomState(0).randn(8, 16), jnp.float32)
        seq = gp.apply(params, x, ApplyContext())
        pipe = gp.pipeline_apply(mesh, placed, x)
        np.testing.assert_allclose(np.asarray(pipe), np.asarray(seq),
                                   rtol=1e-5, atol=1e-6)

    def test_more_microbatches_than_stages(self):
        gp, params = self._setup(n_stages=2, n_micro=8)
        mesh = _pipe_mesh(2)
        placed = gp.place_params(mesh, params)
        x = jnp.asarray(np.random.RandomState(1).randn(16, 16), jnp.float32)
        seq = gp.apply(params, x, ApplyContext())
        pipe = gp.pipeline_apply(mesh, placed, x)
        np.testing.assert_allclose(np.asarray(pipe), np.asarray(seq),
                                   rtol=1e-5, atol=1e-6)

    def test_grad_flows(self):
        gp, params = self._setup()
        mesh = _pipe_mesh(4)
        placed = gp.place_params(mesh, params)
        x = jnp.ones((4, 16), jnp.float32)
        g = jax.grad(lambda p: jnp.sum(
            gp.pipeline_apply(mesh, p, x) ** 2))(placed)
        leaves = jax.tree_util.tree_leaves(g)
        assert all(np.isfinite(np.asarray(l)).all() for l in leaves)
        # every stage received gradient
        assert all(float(np.abs(np.asarray(l)).sum()) > 0 for l in leaves)

    def test_pipeline_grads_match_sequential(self):
        """Training THROUGH the pipeline is exact: gradients from the
        pipelined schedule equal gradients from the sequential reference
        (ppermute/scan adjoints are linear, so autodiff reverses the
        schedule into the correct backward pipeline)."""
        gp, params = self._setup(n_stages=4, n_micro=8)
        mesh = _pipe_mesh(4)
        x = jnp.asarray(np.random.RandomState(3).randn(16, 16), jnp.float32)

        def loss_seq(p):
            return jnp.sum(gp.apply(p, x, ApplyContext()) ** 2)

        def loss_pipe(p):
            return jnp.sum(gp.pipeline_apply(mesh, p, x) ** 2)

        g_seq = jax.grad(loss_seq)(params)
        g_pipe = jax.grad(loss_pipe)(gp.place_params(mesh, params))
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
            g_seq, jax.device_get(g_pipe))

    def test_bubble_fraction(self):
        gp, _ = self._setup(n_stages=4, n_micro=4)
        assert abs(gp.bubble_fraction - 3 / 7) < 1e-9
        gp16, _ = self._setup(n_stages=4, n_micro=16)
        assert gp16.bubble_fraction < gp.bubble_fraction  # amortizes

    def test_stage_mesh_mismatch_raises(self):
        gp, params = self._setup(n_stages=4)
        mesh = _pipe_mesh(2)
        with pytest.raises(ValueError, match="pipe"):
            gp.pipeline_apply(mesh, params, jnp.ones((4, 16)))

    def test_bad_microbatch_split_raises(self):
        gp, params = self._setup(n_stages=4, n_micro=3)
        mesh = _pipe_mesh(4)
        placed = gp.place_params(mesh, params)
        with pytest.raises(ValueError, match="divisible"):
            gp.pipeline_apply(mesh, placed, jnp.ones((8, 16)))


class TestHeteroPipeline:
    """PipelineStages: heterogeneous stages + 1F1B (round-3 review #5).

    Reference ambition bar: DL/optim/ParallelOptimizer.scala is the
    reference's second parallelism engine; this pipelines models whose
    stages differ in shape, which no homogeneous-GPipe restriction
    allows."""

    def _mesh(self, n=4):
        return Mesh(np.array(jax.devices()[:n]).reshape(n), ("pipe",))

    def _stages(self):
        import bigdl_tpu.nn as nn
        return [
            nn.Sequential().add(nn.Linear(8, 16)).add(nn.Tanh()),
            nn.Sequential().add(nn.Linear(16, 12)).add(nn.ReLU()),
            nn.Sequential().add(nn.Linear(12, 6)).add(nn.Tanh()),
            nn.Linear(6, 4),
        ]

    def test_hetero_forward_parity(self):
        from bigdl_tpu.parallel.pipeline import PipelineStages
        pipe = PipelineStages(self._stages(), n_micro=8,
                              example_input=jnp.zeros((4, 8)))
        params = pipe.init(jax.random.PRNGKey(0))
        x = jnp.asarray(np.random.RandomState(0).randn(32, 8), jnp.float32)
        seq = pipe.apply(params, x)
        out = pipe.pipeline_apply(self._mesh(), params, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(seq),
                                   rtol=1e-4, atol=1e-5)

    def test_1f1b_grad_parity(self):
        """1F1B gradients must equal sequential autodiff exactly — the
        schedule is an execution order, not an approximation."""
        from bigdl_tpu.parallel.pipeline import PipelineStages
        pipe = PipelineStages(self._stages(), n_micro=8,
                              example_input=jnp.zeros((4, 8)))
        params = pipe.init(jax.random.PRNGKey(1))
        rs = np.random.RandomState(1)
        x = jnp.asarray(rs.randn(32, 8), jnp.float32)
        y = jnp.asarray(rs.randn(32, 4), jnp.float32)

        def loss_fn(pred, yy):
            return jnp.mean((pred - yy) ** 2)

        loss_pp, grads_pp = pipe.train_step_1f1b(self._mesh(), params, x,
                                                 y, loss_fn)
        loss_ref, grads_ref = jax.value_and_grad(
            lambda ps: loss_fn(pipe.apply(ps, x), y))(params)
        assert float(loss_pp) == pytest.approx(float(loss_ref), rel=1e-5)
        for gp, gr in zip(grads_pp, grads_ref):
            jax.tree_util.tree_map(
                lambda a, b: np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
                gp, gr)

    def test_1f1b_schedule_properties(self):
        """The static table is a valid 1F1B schedule: every F precedes
        its B, per-stage ops are ordered, in-flight depth ≤ S (the
        memory bound that distinguishes 1F1B from GPipe), and the
        measured bubble fraction is counted from the table."""
        from bigdl_tpu.parallel.pipeline import (PipelineStages,
                                                 _schedule_1f1b)
        S, M = 4, 8
        rows = _schedule_1f1b(S, M)
        f_tick = {}
        b_tick = {}
        inflight = [0] * S
        max_inflight = 0
        for t, row in enumerate(rows):
            for s, (op, m) in enumerate(row):
                if op == "F":
                    f_tick[(s, m)] = t
                    inflight[s] += 1
                elif op == "B":
                    b_tick[(s, m)] = t
                    inflight[s] -= 1
                max_inflight = max(max_inflight, inflight[s])
        assert len(f_tick) == S * M and len(b_tick) == S * M
        for s in range(S):
            for m in range(M):
                assert f_tick[(s, m)] < b_tick[(s, m)]
                if s + 1 < S:
                    assert f_tick[(s, m)] < f_tick[(s + 1, m)]
                    assert b_tick[(s + 1, m)] < b_tick[(s, m)]
        assert max_inflight <= S
        pipe_bubble = PipelineStages(self._stages(), n_micro=M,
                                     example_input=jnp.zeros((4, 8))
                                     ).bubble_fraction
        idle = sum(1 for row in rows for op, _ in row if op == "I")
        assert pipe_bubble == pytest.approx(idle / (len(rows) * S))

    def test_resnet50_splits_and_pipelines(self):
        """The real zoo model: ResNet-50 split at stage boundaries runs
        the 4-device hetero pipeline with parity vs sequential."""
        from bigdl_tpu.models.resnet import ResNet
        from bigdl_tpu.parallel.pipeline import (PipelineStages,
                                                 split_sequential)
        model = ResNet(class_num=10, depth=50)
        stages = split_sequential(model, 4)
        pipe = PipelineStages(stages, n_micro=4,
                              example_input=jnp.zeros((2, 32, 32, 3)))
        params = pipe.init(jax.random.PRNGKey(2))
        x = jnp.asarray(np.random.RandomState(2).rand(8, 32, 32, 3),
                        jnp.float32)
        seq = pipe.apply(params, x)
        out = pipe.pipeline_apply(self._mesh(), params, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(seq),
                                   rtol=2e-4, atol=2e-4)

    def test_split_sequential_boundaries(self):
        import bigdl_tpu.nn as nn
        from bigdl_tpu.parallel.pipeline import split_sequential
        m = nn.Sequential()
        for _ in range(10):
            m.add(nn.Identity())
        stages = split_sequential(m, 3, boundaries=[2, 7])
        assert [len(s.children) for s in stages] == [2, 5, 3]
        with pytest.raises(ValueError):
            split_sequential(m, 3, boundaries=[7, 2])

    def test_mesh_mismatch_raises(self):
        from bigdl_tpu.parallel.pipeline import PipelineStages
        pipe = PipelineStages(self._stages(), n_micro=4,
                              example_input=jnp.zeros((4, 8)))
        params = pipe.init(jax.random.PRNGKey(3))
        with pytest.raises(ValueError, match="pipe"):
            pipe.pipeline_apply(self._mesh(2), params,
                                jnp.zeros((16, 8)))


class TestMoE:
    def _mesh(self, n=4):
        return Mesh(np.array(jax.devices()[:n]).reshape(n), ("expert",))

    def test_expert_parallel_matches_dense(self):
        moe = MoE(d_model=8, d_hidden=16, n_experts=4, capacity_factor=8.0)
        params = moe.init(jax.random.PRNGKey(0))
        x = jnp.asarray(np.random.RandomState(0).randn(16, 8), jnp.float32)
        dense = moe.apply(params, x, ApplyContext())
        ep = moe.expert_parallel_apply(self._mesh(), params, x)
        np.testing.assert_allclose(np.asarray(ep), np.asarray(dense),
                                   rtol=1e-4, atol=1e-5)

    def test_multiple_experts_per_device(self):
        moe = MoE(d_model=8, d_hidden=16, n_experts=8, capacity_factor=8.0)
        params = moe.init(jax.random.PRNGKey(1))
        x = jnp.asarray(np.random.RandomState(1).randn(16, 8), jnp.float32)
        dense = moe.apply(params, x, ApplyContext())
        ep = moe.expert_parallel_apply(self._mesh(4), params, x)
        np.testing.assert_allclose(np.asarray(ep), np.asarray(dense),
                                   rtol=1e-4, atol=1e-5)

    def test_capacity_overflow_drops_to_zero(self):
        """With capacity ~0, every token overflows -> gated zeros
        (Switch-Transformer drop semantics)."""
        moe = MoE(d_model=8, d_hidden=16, n_experts=4,
                  capacity_factor=1e-9)
        params = moe.init(jax.random.PRNGKey(2))
        x = jnp.asarray(np.random.RandomState(2).randn(16, 8), jnp.float32)
        ep = np.asarray(moe.expert_parallel_apply(self._mesh(), params, x))
        # per-group cap bottoms out at 1: one token per expert per device
        # survives; the rest are zero rows
        zero_rows = (np.abs(ep).sum(axis=1) == 0).sum()
        assert zero_rows > 0

    def test_realistic_capacity_parity_with_drop_accounting(self):
        """capacity_factor=1.25 (the production Switch setting): the EP
        path must match the dense capacity oracle EXACTLY — same kept
        units, same outputs, zero contribution for the same dropped
        units — not just in the nothing-drops regime."""
        n_dev = 4
        moe = MoE(d_model=8, d_hidden=16, n_experts=4,
                  capacity_factor=1.25)
        params = moe.init(jax.random.PRNGKey(3))
        # skew the router so experts genuinely overflow at cf=1.25
        params = dict(params)
        params["router"] = params["router"] + jnp.asarray(
            np.random.RandomState(3).randn(8, 4) * 2.0, jnp.float32)
        x = jnp.asarray(np.random.RandomState(4).randn(64, 8), jnp.float32)

        ref, ref_mask = moe.dense_capacity_apply(params, x,
                                                 n_groups=n_dev,
                                                 return_mask=True)
        ep, ep_mask = moe.expert_parallel_apply(self._mesh(n_dev), params,
                                                x, return_mask=True)
        # identical drop masks, and drops actually happened
        np.testing.assert_array_equal(np.asarray(ep_mask),
                                      np.asarray(ref_mask))
        dropped = int((~np.asarray(ep_mask)).sum())
        assert dropped > 0, "cf=1.25 skewed router should drop tokens"
        kept = int(np.asarray(ep_mask).sum())
        # accounting: kept units respect per-expert-per-group capacity
        cap = moe.group_capacity(64 // n_dev)
        assert kept <= n_dev * moe.E * cap
        np.testing.assert_allclose(np.asarray(ep), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_capacity_free_oracle_matches_dense_when_no_drops(self):
        """At a generous capacity the new oracle degenerates to the
        capacity-free dense path — ties the two references together."""
        moe = MoE(d_model=8, d_hidden=16, n_experts=4, capacity_factor=8.0)
        params = moe.init(jax.random.PRNGKey(5))
        x = jnp.asarray(np.random.RandomState(5).randn(16, 8), jnp.float32)
        y_cap, mask = moe.dense_capacity_apply(params, x, n_groups=4,
                                               return_mask=True)
        assert bool(np.asarray(mask).all())
        np.testing.assert_allclose(
            np.asarray(y_cap),
            np.asarray(moe.apply(params, x, ApplyContext())),
            rtol=1e-4, atol=1e-5)

    def test_realistic_capacity_top2(self):
        """Same exact-parity bar for top-2 (GShard) routing at cf=1.25."""
        n_dev = 4
        moe = MoE(d_model=8, d_hidden=16, n_experts=4,
                  capacity_factor=1.25, top_k=2)
        params = moe.init(jax.random.PRNGKey(6))
        params = dict(params)
        params["router"] = params["router"] + jnp.asarray(
            np.random.RandomState(6).randn(8, 4) * 2.0, jnp.float32)
        x = jnp.asarray(np.random.RandomState(7).randn(64, 8), jnp.float32)
        ref, ref_mask = moe.dense_capacity_apply(params, x, n_groups=n_dev,
                                                 return_mask=True)
        ep, ep_mask = moe.expert_parallel_apply(self._mesh(n_dev), params,
                                                x, return_mask=True)
        np.testing.assert_array_equal(np.asarray(ep_mask),
                                      np.asarray(ref_mask))
        assert int((~np.asarray(ep_mask)).sum()) > 0
        np.testing.assert_allclose(np.asarray(ep), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_grad_flows_through_dispatch(self):
        moe = MoE(d_model=8, d_hidden=16, n_experts=4, capacity_factor=8.0)
        params = moe.init(jax.random.PRNGKey(3))
        x = jnp.asarray(np.random.RandomState(3).randn(16, 8), jnp.float32)
        g = jax.grad(lambda p: jnp.sum(
            moe.expert_parallel_apply(self._mesh(), p, x) ** 2))(params)
        assert all(np.isfinite(np.asarray(l)).all()
                   for l in jax.tree_util.tree_leaves(g))

    def test_top2_expert_parallel_matches_dense(self):
        """GShard-style top-2 routing: expert-parallel dispatch (each
        (token, choice) pair a routing unit) matches the dense reference
        at generous capacity."""
        moe = MoE(d_model=8, d_hidden=16, n_experts=4,
                  capacity_factor=8.0, top_k=2)
        params = moe.init(jax.random.PRNGKey(5))
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("expert",))
        x = jnp.asarray(np.random.RandomState(5).randn(16, 8), jnp.float32)
        dense = moe.apply(params, x, ApplyContext())
        ep = moe.expert_parallel_apply(mesh, params, x)
        np.testing.assert_allclose(np.asarray(ep), np.asarray(dense),
                                   rtol=1e-4, atol=1e-5)

    def test_top2_gates_normalized(self):
        moe = MoE(d_model=8, d_hidden=16, n_experts=4, top_k=2)
        params = moe.init(jax.random.PRNGKey(0))
        x = jnp.asarray(np.random.RandomState(0).randn(8, 8), jnp.float32)
        _, gates, _ = moe._gates(params, x)
        np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0,
                                   rtol=1e-5)

    def test_aux_loss_balances_skewed_router(self):
        """The Switch load-balancing loss actually balances: training the
        router on aux_loss alone takes a collapsed (one-expert) routing to
        near-uniform load."""
        moe = MoE(d_model=8, d_hidden=16, n_experts=4)
        params = moe.init(jax.random.PRNGKey(1))
        # collapse the router onto expert 0 (positive inputs make the
        # boosted column dominate every token's logits; +1.0 saturates
        # routing without saturating softmax gradients)
        params["router"] = params["router"].at[:, 0].add(1.0)
        x = jnp.asarray(np.abs(np.random.RandomState(2).randn(64, 8)),
                        jnp.float32)
        _, aux0 = moe.apply_with_aux(params, x)
        assert float(aux0["max_load"]) == 1.0  # fully collapsed

        def aux_only(p):
            return moe.apply_with_aux(p, x)[1]["aux_loss"]

        grad_fn = jax.jit(jax.grad(aux_only))
        for _ in range(200):
            g = grad_fn(params)
            params["router"] = params["router"] - 0.5 * g["router"]
        _, aux1 = moe.apply_with_aux(params, x)
        assert float(aux1["aux_loss"]) < float(aux0["aux_loss"])
        assert float(aux1["max_load"]) < 0.5, aux1["expert_fraction"]
        # aux_loss -> 1.0 at uniform routing
        assert float(aux1["aux_loss"]) < 1.2

    def test_bad_divisibility_raises(self):
        moe = MoE(d_model=8, d_hidden=16, n_experts=6)
        params = moe.init(jax.random.PRNGKey(4))
        with pytest.raises(ValueError, match="divide"):
            moe.expert_parallel_apply(self._mesh(4), params,
                                      jnp.ones((16, 8)))
