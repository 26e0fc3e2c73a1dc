"""IR / backend-conversion tests (reference TEST/utils/intermediate +
mkldnn Fusion specs, SURVEY.md C12): BN folding preserves outputs exactly,
noise layers vanish at inference, predictor path converts automatically.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.ir import ConversionUtils, IRGraph


def _train_bn_model():
    m = nn.Sequential()
    m.add(nn.SpatialConvolution(3, 8, 3, 3, 1, 1, 1, 1))
    m.add(nn.SpatialBatchNormalization(8))
    m.add(nn.ReLU())
    m.add(nn.Reshape([8 * 6 * 6]))
    m.add(nn.Linear(8 * 6 * 6, 4))
    m.add(nn.BatchNormalization(4))
    m.add(nn.Dropout(0.5))
    m.add(nn.LogSoftMax())
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(8, 6, 6, 3), jnp.float32)
    import jax
    m.forward(x, training=True, rng=jax.random.PRNGKey(0))  # build stats
    m.evaluate()
    return m, x


class TestFoldBatchnorm:
    def test_outputs_preserved_and_bn_removed(self):
        m, x = _train_bn_model()
        want = np.asarray(m.forward(x))
        converted = ConversionUtils.convert(m, inference=True)
        got = np.asarray(converted.forward(x))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
        types = [type(c).__name__ for c in converted.children]
        assert "SpatialBatchNormalization" not in types
        assert "BatchNormalization" not in types
        assert "Dropout" not in types
        # two Identities replaced BNs + one replaced Dropout
        assert types.count("Identity") == 3

    def test_folded_weights_differ(self):
        m, x = _train_bn_model()
        w_before = np.asarray(m.ensure_params()["0_SpatialConvolution"]
                              ["weight"]).copy()
        converted = ConversionUtils.convert(m, inference=True)
        w_after = np.asarray(
            converted.ensure_params()["0_SpatialConvolution"]["weight"])
        assert not np.allclose(w_before, w_after)

    def test_train_mode_bn_not_folded(self):
        m, x = _train_bn_model()
        m.training()
        for c in m.children:
            c.training()
        converted = ConversionUtils.convert(m, inference=False)
        types = [type(c).__name__ for c in converted.children]
        assert "SpatialBatchNormalization" in types


class TestIRGraph:
    def test_elements_flatten(self):
        m, _ = _train_bn_model()
        ir = IRGraph.from_module(m)
        ops = [e.op_type for e in ir.elements()]
        assert ops[0] == "SpatialConvolution"
        assert "LogSoftMax" in ops
        assert len(ops) == 8


class TestPredictorConversion:
    def test_predictor_applies_conversion(self):
        from bigdl_tpu.optim.predictor import LocalPredictor
        from bigdl_tpu.dataset.sample import Sample
        m, x = _train_bn_model()
        want = np.asarray(m.forward(x))
        pred = LocalPredictor(m, batch_size=4)
        types = [type(c).__name__ for c in pred.model.children]
        assert "SpatialBatchNormalization" not in types
        samples = [Sample(np.asarray(x)[i]) for i in range(8)]
        outs = pred.predict(samples)
        np.testing.assert_allclose(np.stack(outs), want, rtol=1e-4,
                                   atol=1e-5)


class TestS2DStemRestatement:
    """The s2d-stem rewrite is an IR pass (round-4 review weak #6), not a
    model-code hand-edit: eligible stems restate with bit-identical math
    and param tree; non-stems are untouched."""

    def _stem_model(self):
        return (nn.Sequential()
                .add(nn.SpatialConvolution(3, 16, 7, 7, 2, 2, 3, 3,
                                           with_bias=False, name="conv1"))
                .add(nn.ReLU())
                .add(nn.SpatialConvolution(16, 8, 3, 3, 2, 2, 1, 1,
                                           name="conv2"))  # 16ch: not a stem
                .add(nn.Pooler())
                .add(nn.Linear(8, 4)))

    def test_restates_stem_only_with_identical_outputs(self):
        m = self._stem_model()
        x = jnp.asarray(np.random.RandomState(0).rand(2, 32, 32, 3),
                        jnp.float32)
        want = np.asarray(m.forward(x, training=False))
        out = ConversionUtils.apply_tpu_restatements(m)
        kinds = [type(c).__name__ for c in out.children]
        assert kinds[0] == "SpaceToDepthStemConvolution"
        assert kinds[2] == "SpatialConvolution"  # 16-channel conv untouched
        got = np.asarray(out.forward(x, training=False))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_ineligible_stems_untouched(self):
        # stride 1, and even kernel: both ineligible
        m = (nn.Sequential()
             .add(nn.SpatialConvolution(3, 8, 7, 7, 1, 1, 3, 3))
             .add(nn.SpatialConvolution(8, 8, 5, 5, 2, 2, 2, 2)))
        out = ConversionUtils.apply_tpu_restatements(m)
        assert all(type(c).__name__ == "SpatialConvolution"
                   for c in out.children)

    def test_graph_container_stem_restates(self):
        inp = nn.InputNode()
        h = nn.SpatialConvolution(3, 8, 7, 7, 2, 2, 3, 3,
                                  with_bias=False).inputs(inp)
        o = nn.ReLU().inputs(h)
        g = nn.Graph([inp], [o])
        x = jnp.asarray(np.random.RandomState(1).rand(1, 16, 16, 3),
                        jnp.float32)
        want = np.asarray(g.forward(x, training=False))
        out = ConversionUtils.apply_tpu_restatements(g)
        assert any(type(n.module).__name__ == "SpaceToDepthStemConvolution"
                   for n in out.exec_order)
        got = np.asarray(out.forward(x, training=False))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


class TestOptimizerGraphOptimizations:
    def test_set_graph_optimizations_restates_and_trains(self):
        """Opt-in optimizer knob: the stem restates before the step
        builds, training runs, and the param tree stays checkpoint-
        compatible (identical shapes)."""
        import bigdl_tpu.optim as optim
        rs = np.random.RandomState(0)
        X = rs.rand(32, 16, 16, 3).astype(np.float32)
        Y = (rs.randint(0, 4, size=32) + 1).astype(np.int32)
        m = (nn.Sequential()
             .add(nn.SpatialConvolution(3, 8, 7, 7, 2, 2, 3, 3,
                                        with_bias=False))
             .add(nn.ReLU()).add(nn.Pooler())
             .add(nn.Linear(8, 4)).add(nn.LogSoftMax()))
        shapes_before = [tuple(l.shape) for l in
                         jax.tree_util.tree_leaves(m.ensure_params())]
        o = optim.Optimizer(m, (X, Y), nn.ClassNLLCriterion(),
                            batch_size=16, local=True)
        o.set_graph_optimizations(True)
        o.set_optim_method(optim.SGD(learning_rate=0.05))
        o.set_end_when(optim.max_iteration(4))
        trained = o.optimize()
        assert type(trained.children[0]).__name__ == \
            "SpaceToDepthStemConvolution"
        shapes_after = [tuple(l.shape) for l in
                        jax.tree_util.tree_leaves(trained.ensure_params())]
        assert shapes_before == shapes_after
