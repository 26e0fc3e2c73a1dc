"""Int8 quantized inference tests.

Mirrors TEST/nn/quantized specs + the whitepaper's accuracy claim
(docs/docs/whitepaper.md:192: <0.1% top-1 drop): quantized layers must track
fp32 outputs closely and preserve toy-task accuracy; model bytes shrink ~4x.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.quantized import (QuantizedLinear,
                                    QuantizedSpatialConvolution, Quantizer)


def rel_err(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-8)


class TestQuantizedLayers:
    def test_linear_close_to_fp32(self):
        rng = np.random.RandomState(0)
        m = nn.Linear(64, 32)
        x = jnp.asarray(rng.randn(8, 64), jnp.float32)
        want = m.forward(x)
        q = QuantizedLinear.from_float(m, m.parameters())
        got = q.forward(x)
        assert rel_err(got, want) < 0.02

    def test_conv_close_to_fp32(self):
        rng = np.random.RandomState(1)
        m = nn.SpatialConvolution(8, 16, 3, 3, 1, 1, 1, 1)
        x = jnp.asarray(rng.randn(2, 10, 10, 8), jnp.float32)
        want = m.forward(x)
        q = QuantizedSpatialConvolution.from_float(m, m.parameters())
        got = q.forward(x)
        assert rel_err(got, want) < 0.03

    def test_grouped_strided_conv(self):
        rng = np.random.RandomState(2)
        m = nn.SpatialConvolution(8, 16, 3, 3, 2, 2, 1, 1, n_group=2)
        x = jnp.asarray(rng.randn(2, 9, 9, 8), jnp.float32)
        q = QuantizedSpatialConvolution.from_float(m, m.parameters())
        assert rel_err(q.forward(x), m.forward(x)) < 0.03

    def test_weight_bytes_4x_smaller(self):
        m = nn.Linear(256, 256)
        q = QuantizedLinear.from_float(m, m.parameters())
        fp32_bytes = np.asarray(m.parameters()["weight"]).nbytes
        int8_bytes = np.asarray(q.parameters()["weight"]).nbytes
        assert fp32_bytes == 4 * int8_bytes


class TestQuantizer:
    def _toy_model(self):
        m = nn.Sequential()
        m.add(nn.SpatialConvolution(1, 8, 3, 3, 1, 1, 1, 1))
        m.add(nn.ReLU())
        m.add(nn.SpatialMaxPooling(2, 2, 2, 2))
        m.add(nn.Reshape([8 * 7 * 7]))
        m.add(nn.Linear(8 * 7 * 7, 10))
        m.add(nn.LogSoftMax())
        return m

    def test_quantize_swaps_layers(self):
        m = self._toy_model()
        m.ensure_params()
        q = Quantizer.quantize(m)
        types = [type(c).__name__ for c in q.children]
        assert "QuantizedSpatialConvolution" in types
        assert "QuantizedLinear" in types
        assert "SpatialConvolution" not in types and "Linear" not in types

    def test_quantized_model_agrees(self):
        rng = np.random.RandomState(3)
        m = self._toy_model()
        m.evaluate()
        x = jnp.asarray(rng.rand(4, 14, 14, 1) * 2 - 1, jnp.float32)
        want = np.asarray(m.forward(x))
        q = m.quantize()
        got = np.asarray(q.forward(x))
        # logits may shift slightly; argmax (the accuracy-bearing output)
        # must agree and values stay close
        np.testing.assert_array_equal(got.argmax(1), want.argmax(1))
        assert np.abs(got - want).max() < 0.15

    def test_quantize_graph_model(self):
        rng = np.random.RandomState(4)
        inp = nn.InputNode()
        h = nn.Linear(12, 24).inputs(inp)
        r = nn.ReLU().inputs(h)
        out = nn.Linear(24, 3).inputs(r)
        g = nn.Graph([inp], [out])
        g.evaluate()
        x = jnp.asarray(rng.randn(5, 12), jnp.float32)
        want = np.asarray(g.forward(x))
        q = Quantizer.quantize(g)
        got = np.asarray(q.forward(x))
        assert any(type(c).__name__ == "QuantizedLinear" for c in q.children)
        assert rel_err(got, want) < 0.05

    def test_quantize_top_level_layer(self):
        m = nn.Linear(6, 4)
        m.ensure_params()
        q = Quantizer.quantize(m)
        assert type(q).__name__ == "QuantizedLinear"

    def test_serialization_round_trip(self, tmp_path):
        from bigdl_tpu.serialization import ModuleSerializer
        rng = np.random.RandomState(5)
        m = self._toy_model()
        m.evaluate()
        q = m.quantize()
        x = jnp.asarray(rng.rand(2, 14, 14, 1), jnp.float32)
        want = np.asarray(q.forward(x))
        path = str(tmp_path / "q.bigdl")
        ModuleSerializer.save(q, path)
        loaded = ModuleSerializer.load(path)
        got = np.asarray(loaded.forward(x))
        np.testing.assert_array_equal(want, got)


def test_quantize_leaves_original_intact():
    """Quantizer.quantize must return a NEW model: quantizing for serving
    and then continuing to train the original is a supported flow (the
    reference clones before converting)."""
    import jax.numpy as jnp
    import numpy as np
    import bigdl_tpu.nn as nn
    from bigdl_tpu.nn.quantized import Quantizer

    m = (nn.Sequential().add(nn.Linear(8, 16)).add(nn.ReLU())
         .add(nn.Sequential().add(nn.Linear(16, 4))))
    m.ensure_params()
    before_types = [type(c).__name__ for c in m.children]
    x = jnp.asarray(np.random.RandomState(0).randn(4, 8).astype(np.float32))
    want = np.asarray(m.forward(x, training=False))

    q = Quantizer.quantize(m)
    assert q is not m
    assert [type(c).__name__ for c in m.children] == before_types
    assert type(m.children[0]).__name__ == "Linear"
    assert type(q.children[0]).__name__ == "QuantizedLinear"
    # original still produces identical fp32 outputs
    np.testing.assert_array_equal(np.asarray(m.forward(x, training=False)),
                                  want)


class TestWeightOnly:
    """Weight-only int8 serving (round-3 review #8): bf16/f32 compute with
    int8-stored weights — tighter accuracy than full int8 (no activation
    quantization error), same 4x weight size."""

    def test_linear_tighter_than_full_int8(self):
        rng = np.random.RandomState(3)
        m = nn.Linear(64, 32)
        x = jnp.asarray(rng.randn(8, 64), jnp.float32)
        want = m.forward(x)
        wo = nn.WeightOnlyQuantizedLinear.from_float(m, m.parameters())
        full = QuantizedLinear.from_float(m, m.parameters())
        err_wo = rel_err(wo.forward(x), want)
        err_full = rel_err(full.forward(x), want)
        assert err_wo < 0.01
        assert err_wo <= err_full

    def test_conv_close_to_fp32(self):
        rng = np.random.RandomState(4)
        m = nn.SpatialConvolution(8, 16, 3, 3, 1, 1, 1, 1)
        x = jnp.asarray(rng.randn(2, 10, 10, 8), jnp.float32)
        wo = nn.WeightOnlyQuantizedSpatialConvolution.from_float(
            m, m.parameters())
        assert rel_err(wo.forward(x), m.forward(x)) < 0.01

    def test_compute_dtype_follows_input(self):
        """bf16 serving: activations stay bf16 end to end; weights are
        stored int8."""
        m = nn.Linear(16, 8)
        wo = nn.WeightOnlyQuantizedLinear.from_float(m, m.parameters())
        assert wo.parameters()["weight"].dtype == jnp.int8
        out = wo.forward(jnp.ones((2, 16), jnp.bfloat16))
        assert out.dtype == jnp.bfloat16

    def test_quantizer_weight_only_walk(self):
        rng = np.random.RandomState(5)
        m = nn.Sequential()
        m.add(nn.SpatialConvolution(3, 8, 3, 3, 1, 1, 1, 1))
        m.add(nn.ReLU())
        m.add(nn.Reshape([8 * 6 * 6]))
        m.add(nn.Linear(8 * 6 * 6, 10))
        m.forward(jnp.zeros((1, 6, 6, 3)))  # init
        q = Quantizer.quantize(m, weight_only=True)
        kinds = [type(c).__name__ for c in q.children]
        assert kinds[0] == "WeightOnlyQuantizedSpatialConvolution"
        assert kinds[-1] == "WeightOnlyQuantizedLinear"
        x = jnp.asarray(rng.randn(2, 6, 6, 3), jnp.float32)
        assert rel_err(q.forward(x), m.forward(x)) < 0.01

    def test_module_quantize_kwarg(self):
        m = nn.Linear(8, 4)
        m.ensure_params()
        q = m.quantize(weight_only=True)
        assert type(q).__name__ == "WeightOnlyQuantizedLinear"
        # original is untouched and still full precision
        assert type(m).__name__ == "Linear"

    def test_weight_bytes_4x_smaller(self):
        m = nn.Linear(256, 256)
        wo = nn.WeightOnlyQuantizedLinear.from_float(m, m.parameters())
        fp32_bytes = np.asarray(m.parameters()["weight"]).nbytes
        int8_bytes = np.asarray(wo.parameters()["weight"]).nbytes
        assert fp32_bytes == 4 * int8_bytes
