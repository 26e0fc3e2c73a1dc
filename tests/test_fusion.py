"""Fused BatchNorm+ReLU tail: kernel parity, pattern matching, and the
training-step hot-path contracts (ops/bn_relu_kernel.py, nn/fusion.py).

Mirrors the stem kernel's test discipline: interpret-mode parity at
boundary tile shapes, jaxpr-level structural asserts, and bit-identity
of the production routing against the unfused graph."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.nn import fusion
from bigdl_tpu.nn.module import ApplyContext, functional_apply
from bigdl_tpu.ops import bn_relu_kernel as K


def _rand(rs, *shape):
    return jnp.asarray(rs.randn(*shape), jnp.float32)


class TestPickTile:
    def test_divides_and_multiple_of_8(self):
        for n in (8, 16, 64, 4096):
            t = K._pick_tile_n(n, 64)
            assert n % t == 0 and t % 8 == 0

    def test_fallback_full_rows_when_no_candidate(self):
        # odd / tiny row counts: no multiple-of-8 divisor exists
        for n in (1, 2, 7, 9, 49):
            assert K._pick_tile_n(n, 64) == n

    def test_vmem_budget_shrinks_tile_for_wide_channels(self):
        assert K._pick_tile_n(4096, 2048) < K._pick_tile_n(4096, 16)


#: boundary shapes: batch 1 vs 2 (leading dims fold into rows),
#: non-multiple-of-tile channel counts (5, 12, 129, 130), rows that are
#: not multiples of 8 (fallback full-row tile)
BOUNDARY_SHAPES = [(8, 8), (7, 5), (1, 129), (2, 12), (16, 130), (64, 33)]


class TestKernelParity:
    @pytest.mark.parametrize("n,c", BOUNDARY_SHAPES)
    @pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("relu", [True, False])
    def test_forward_interpret_bit_identical(self, n, c, out_dtype, relu):
        # elementwise tiling cannot change values: jitted interpret
        # kernel output == jitted reference, BITWISE, f32 and bf16
        rs = np.random.RandomState(0)
        x, s, b = _rand(rs, n, c), _rand(rs, c), _rand(rs, c)
        ref = jax.jit(lambda *a: K._reference_forward(*a, relu, out_dtype))(
            x, s, b)
        out = jax.jit(lambda *a: K.bn_relu_forward(
            *a, relu, out_dtype=out_dtype, interpret=True))(x, s, b)
        assert out.dtype == jnp.dtype(out_dtype)
        np.testing.assert_array_equal(
            np.asarray(out, np.float32), np.asarray(ref, np.float32))

    @pytest.mark.parametrize("n,c", BOUNDARY_SHAPES)
    @pytest.mark.parametrize("g_dtype", [jnp.float32, jnp.bfloat16])
    def test_backward_interpret_bounded(self, n, c, g_dtype):
        # the tiled partial reductions regroup sums: parity within 1e-6
        # fp32 (the acceptance bound), dx exactly elementwise
        rs = np.random.RandomState(1)
        x, s, b = _rand(rs, n, c), _rand(rs, c), _rand(rs, c)
        g = _rand(rs, n, c).astype(g_dtype)
        dx, ds, db = jax.jit(lambda *a: K.bn_relu_backward(
            *a, True, interpret=True))(x, s, b, g)
        rdx, rds, rdb = K._reference_backward(x, s, b, g, True, g_dtype)
        np.testing.assert_allclose(dx, rdx, rtol=0, atol=1e-6)
        np.testing.assert_allclose(ds, rds, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(db, rdb, rtol=1e-5, atol=1e-5)

    def test_custom_vjp_grad_vs_unfused_autodiff(self):
        # end-to-end through the FORCE_PALLAS route: forward bitwise,
        # grads within the 1e-6 acceptance bound of plain autodiff
        rs = np.random.RandomState(2)
        x, s, b = _rand(rs, 24, 17), _rand(rs, 17), _rand(rs, 17)

        def unfused(x, s, b):
            return jnp.sum(jax.nn.relu((x * s + b).astype(jnp.float32)) ** 2)

        prev = K.FORCE_PALLAS
        K.FORCE_PALLAS = True
        try:
            def fused(x, s, b):
                return jnp.sum(K.bn_relu(x, s, b, True, jnp.float32) ** 2)
            yf = jax.jit(lambda *a: K.bn_relu(*a, True, jnp.float32))(x, s, b)
            # jit the reference too: eager XLA groups the multiply-add
            # FMA differently from compiled code at the last ulp
            yu = jax.jit(
                lambda *a: jax.nn.relu(
                    (a[0] * a[1] + a[2]).astype(jnp.float32)))(x, s, b)
            np.testing.assert_array_equal(np.asarray(yf), np.asarray(yu))
            gf = jax.jit(jax.grad(fused, argnums=(0, 1, 2)))(x, s, b)
        finally:
            K.FORCE_PALLAS = prev
        gu = jax.jit(jax.grad(unfused, argnums=(0, 1, 2)))(x, s, b)
        for a, bb in zip(gu, gf):
            np.testing.assert_allclose(a, bb, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("backend", ["cpu", "tpu"])
    def test_routing_is_bit_identical_including_grads(self, monkeypatch,
                                                      backend):
        # the production route inlines the unfused ops whatever backend
        # JAX names (PR 37: the TPU no longer takes the kernel pair):
        # autodiff must agree BITWISE (this is what keeps the CI
        # trajectory parity gate exact)
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        rs = np.random.RandomState(3)
        x, s, b = _rand(rs, 40, 12), _rand(rs, 12), _rand(rs, 12)

        def unfused(x, s, b):
            return jnp.sum(jax.nn.relu((x * s + b).astype(jnp.float32)) ** 2)

        def fused(x, s, b):
            return jnp.sum(K.bn_relu(x, s, b, True, jnp.float32) ** 2)

        gu = jax.jit(jax.grad(unfused, argnums=(0, 1, 2)))(x, s, b)
        gf = jax.jit(jax.grad(fused, argnums=(0, 1, 2)))(x, s, b)
        for a, bb in zip(gu, gf):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(bb))


def _bn_relu_chain(c=6):
    return (nn.Sequential()
            .add(nn.SpatialConvolution(3, c, 3, 3, with_bias=False))
            .add(nn.SpatialBatchNormalization(c))
            .add(nn.ReLU())
            .add(nn.SpatialConvolution(c, c, 3, 3, with_bias=False))
            .add(nn.SpatialBatchNormalization(c))
            .add(nn.ReLU()))


class TestPatternMatching:
    def _apply(self, model, x, fused, training=True):
        params = model.init(jax.random.PRNGKey(0))
        state = model.state_init()
        with fusion.fusion_scope(fused):
            out, new_state = jax.jit(
                lambda p, xx: functional_apply(model, p, xx, state=state,
                                               training=training))(params, x)
        return out, new_state

    def test_sequential_fused_output_and_state_bitwise(self):
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.rand(2, 8, 8, 3), jnp.float32)
        model = _bn_relu_chain()
        for training in (True, False):
            o1, s1 = self._apply(model, x, True, training)
            o0, s0 = self._apply(model, x, False, training)
            np.testing.assert_array_equal(np.asarray(o1), np.asarray(o0))
            assert set(s1) == set(s0)
            for k in s1:
                for f in s1[k]:
                    np.testing.assert_array_equal(np.asarray(s1[k][f]),
                                                  np.asarray(s0[k][f]))

    def test_jaxpr_has_fused_calls_and_no_standalone_bn_relu(self):
        # structural assert on the kernel-routed graph: every BN+ReLU
        # pair becomes ONE bn_relu custom_vjp call; no standalone relu
        # custom_jvp eqns and no standalone BN normalize tail remain
        model = _bn_relu_chain()
        params = model.init(jax.random.PRNGKey(0))
        state = model.state_init()
        x = jnp.zeros((2, 8, 8, 3))

        def make_fwd():
            # a FRESH closure per trace: jax.make_jaxpr shares the jit
            # trace cache keyed on function identity, so re-tracing the
            # same function object after a fusion toggle would return
            # the FIRST mode's cached jaxpr
            return lambda p, xx: functional_apply(model, p, xx,
                                                  state=state,
                                                  training=True)[0]

        def count(jaxpr, match):
            inner = getattr(jaxpr, "jaxpr", jaxpr)
            tot = 0
            for eqn in inner.eqns:
                if match(eqn):
                    tot += 1
                for key in ("jaxpr", "call_jaxpr", "fun_jaxpr",
                            "body_jaxpr"):
                    if key in eqn.params:
                        tot += count(eqn.params[key], match)
                        break
            return tot

        relu_eqns = lambda e: e.primitive.name.startswith("custom_jvp_call")
        prev = K.FORCE_PALLAS
        K.FORCE_PALLAS = True
        try:
            with fusion.fusion_scope(True):
                jx = jax.make_jaxpr(make_fwd())(params, x)
        finally:
            K.FORCE_PALLAS = prev
        assert K.count_fused_calls(jx) == 2
        assert count(jx, relu_eqns) == 0  # no standalone ReLU survives
        with fusion.fusion_scope(False):
            jx0 = jax.make_jaxpr(make_fwd())(params, x)
        assert K.count_fused_calls(jx0) == 0
        assert count(jx0, relu_eqns) == 2  # the unfused graph has them

    def test_resnet_auto_applied_without_model_edits(self):
        # models/resnet.py untouched: CIFAR ResNet-8 has 4 BN+ReLU
        # adjacencies (stem + one per basic block); the 3 block-tail
        # ReLUs (after CAddTable) are NOT BN-adjacent and must survive
        from bigdl_tpu.models.resnet import ResNet
        model = ResNet(class_num=10, depth=8, data_set="cifar10")
        params = model.init(jax.random.PRNGKey(0))
        state = model.state_init()
        x = jnp.zeros((2, 32, 32, 3))
        prev = K.FORCE_PALLAS
        K.FORCE_PALLAS = True
        try:
            with fusion.fusion_scope(True):
                jx = jax.make_jaxpr(
                    lambda p, xx: functional_apply(
                        model, p, xx, state=state, training=True)[0])(
                            params, x)
        finally:
            K.FORCE_PALLAS = prev
        assert K.count_fused_calls(jx) == 4

    def test_non_relu_and_frozen_and_nchw_not_fused(self):
        assert not fusion.fusible_activation(nn.ReLU6())
        assert not fusion.fusible_activation(nn.LeakyReLU())
        assert fusion.fusible_activation(nn.ReLU())
        bn = nn.SpatialBatchNormalization(4)
        assert fusion.fusible_bn(bn)
        bn.freeze()
        assert not fusion.fusible_bn(bn)
        nchw = nn.SpatialBatchNormalization(4, data_format="NCHW")
        assert not fusion.fusible_bn(nchw)

    def test_graph_container_fuses_single_consumer_only(self):
        inp = nn.InputNode()
        h = nn.Linear(4, 6).inputs(inp)
        b1 = nn.BatchNormalization(6).inputs(h)
        r1 = nn.ReLU().inputs(b1)
        out = nn.Linear(6, 2).inputs(r1)
        g = nn.Graph([inp], [out])
        fused, skip = g._fusion_plan()
        assert len(fused) == 1 and len(skip) == 1
        # fan-out: BN feeding the ReLU AND a second consumer must not fuse
        inp2 = nn.InputNode()
        b2 = nn.BatchNormalization(4).inputs(inp2)
        r2 = nn.ReLU().inputs(b2)
        j = nn.CAddTable().inputs(r2, b2)
        g2 = nn.Graph([inp2], [j])
        fused2, skip2 = g2._fusion_plan()
        assert not fused2 and not skip2

    def test_graph_fused_output_bitwise(self):
        rs = np.random.RandomState(0)
        inp = nn.InputNode()
        h = nn.Linear(4, 6).inputs(inp)
        b1 = nn.BatchNormalization(6).inputs(h)
        r1 = nn.ReLU().inputs(b1)
        out = nn.Linear(6, 2).inputs(r1)
        g = nn.Graph([inp], [out])
        x = jnp.asarray(rs.rand(5, 4), jnp.float32)
        o1, s1 = self._apply(g, x, True)
        o0, s0 = self._apply(g, x, False)
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o0))
        for k in s1:
            for f in s1[k]:
                np.testing.assert_array_equal(np.asarray(s1[k][f]),
                                              np.asarray(s0[k][f]))

    def test_toggle_and_scope(self):
        assert fusion.fusion_enabled()  # default ON
        with fusion.fusion_scope(False):
            assert not fusion.fusion_enabled()
        assert fusion.fusion_enabled()


class TestTrainingTrajectoryParity:
    def test_local_loop_fused_trajectory_bit_identical(self):
        # the CI gate's exact leg, in-suite: same init, same data, fusion
        # on vs off through the REAL LocalOptimizer — losses bitwise equal
        from bigdl_tpu.dataset.dataset import LocalDataSet
        from bigdl_tpu.dataset.sample import MiniBatch
        from bigdl_tpu.optim.local_optimizer import LocalOptimizer
        from bigdl_tpu.optim.trigger import max_iteration
        import bigdl_tpu.optim as optim

        rs = np.random.RandomState(0)
        batches = [MiniBatch(rs.rand(4, 8, 8, 3).astype(np.float32),
                             (rs.randint(0, 4, 4) + 1).astype(np.int32))
                   for _ in range(3)]

        def run(fused):
            with fusion.fusion_scope(fused):
                model = (_bn_relu_chain(4)
                         .add(nn.Pooler()).add(nn.Linear(4, 4))
                         .add(nn.LogSoftMax()))
                model.ensure_params(jax.random.PRNGKey(0))
                opt = LocalOptimizer(model, LocalDataSet(list(batches)),
                                     nn.ClassNLLCriterion(), 4)
                opt.set_optim_method(optim.SGD(learning_rate=0.05,
                                               momentum=0.9))
                opt.set_end_when(max_iteration(4))
                losses = []
                opt.set_iteration_hook(lambda s: losses.append(s["loss"]))
                opt.optimize()
            return losses

        assert run(True) == run(False)
