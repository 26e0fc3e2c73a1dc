"""The `bigdl.*` compat-namespace tail (round-4 review missing #3 / weak #4).

Covers: the previously-stubbed Layer methods (update_parameters, freeze,
stop_gradient, save_graph_topology), the `bigdl.keras` converter
namespace, `bigdl.dataset.{news20,movielens,sentence}`, the
`bigdl.models` tail (inception / rnn / textclassifier / local_lenet /
ml_pipeline / utils) — and the flagship proof: the REFERENCE repo's own
`local_lenet.py` executed VERBATIM (runpy, unmodified file) against this
package, training on real handwritten-digit images staged as MNIST idx
files.
"""

import gzip
import json
import os
import runpy
import struct
import sys

import numpy as np
import pytest

_REF_LOCAL_LENET = ("/root/reference/pyspark/bigdl/models/local_lenet/"
                    "local_lenet.py")


def _stage_digits_as_mnist(data_dir, n_train=512, n_test=128):
    """Write real UCI-digits images (upsampled to 28x28 uint8) in MNIST
    idx format so mnist.load_data serves genuine handwritten digits."""
    from sklearn.datasets import load_digits
    from bigdl.dataset import mnist as M
    d = load_digits()
    X = np.repeat(np.repeat(d.images, 3, axis=1), 3, axis=2)  # 8->24
    X = np.pad(X, ((0, 0), (2, 2), (2, 2)))                   # ->28
    X = (X * (255.0 / 16.0)).astype(np.uint8)
    Y = d.target.astype(np.uint8)
    splits = [(M.TRAIN_IMAGES, M.TRAIN_LABELS, X[:n_train], Y[:n_train]),
              (M.TEST_IMAGES, M.TEST_LABELS,
               X[n_train:n_train + n_test], Y[n_train:n_train + n_test])]
    for img_name, lab_name, xs, ys in splits:
        with gzip.open(os.path.join(data_dir, img_name), "wb") as f:
            f.write(struct.pack(">iiii", 2051, len(xs), 28, 28))
            f.write(xs.tobytes())
        with gzip.open(os.path.join(data_dir, lab_name), "wb") as f:
            f.write(struct.pack(">ii", 2049, len(ys)))
            f.write(ys.tobytes())


@pytest.mark.skipif(not os.path.exists(_REF_LOCAL_LENET),
                    reason="reference checkout not present")
class TestReferenceExampleVerbatim:
    @pytest.mark.slow
    def test_reference_local_lenet_runs_unmodified(self, tmp_path, capsys):
        """Execute the reference's local_lenet.py AS-IS: same file, same
        imports, same Optimizer/validation calls — resolved against this
        package, trained on real digit images."""
        _stage_digits_as_mnist(str(tmp_path))
        argv = sys.argv
        try:
            sys.argv = ["local_lenet.py", "-b", "64", "-m", "1",
                        "-d", str(tmp_path)]
            runpy.run_path(_REF_LOCAL_LENET, run_name="__main__")
        finally:
            sys.argv = argv
        out = capsys.readouterr().out
        assert "[" in out  # predict_class result printed by the script


class TestLayerMethodsFormerlyStubbed:
    def _seq(self):
        from bigdl.nn.layer import Linear, ReLU, Sequential
        m = Sequential()
        m.add(Linear(4, 8).set_name("feat")).add(ReLU()) \
         .add(Linear(8, 2).set_name("head"))
        return m

    def test_manual_training_loop(self):
        """forward / backward / update_parameters / zero_grad_parameters
        — the torch-style loop the reference supports — must converge."""
        from bigdl.nn.criterion import MSECriterion
        lay = self._seq()
        X = np.random.RandomState(0).rand(8, 4).astype(np.float32)
        Yt = np.zeros((8, 2), np.float32)
        crit = MSECriterion()
        for _ in range(120):
            out = lay.forward(X)
            loss = crit.forward(out, Yt)
            gout = crit.backward(out, Yt)
            lay.backward(X, gout)
            lay.update_parameters(0.1)
            lay.zero_grad_parameters()
        assert float(loss) < 1e-3, loss

    def test_update_parameters_without_backward_raises(self):
        with pytest.raises(RuntimeError, match="backward"):
            self._seq().update_parameters(0.1)

    def test_freeze_blocks_updates(self):
        """Frozen sublayer must not move under an Optimizer step; after
        unfreeze it must."""
        import bigdl.optim.optimizer as bo
        from bigdl.nn.criterion import MSECriterion
        lay = self._seq()
        lay.freeze(["feat"])
        X = np.random.RandomState(1).rand(16, 4).astype(np.float32)
        Y = np.random.RandomState(2).rand(16, 2).astype(np.float32)

        def feat_params():
            params = lay.parameters()
            key = next(k for k in params if "feat" in k)
            return params[key]

        before = {k: v.copy() for k, v in feat_params().items()}
        o = bo.Optimizer.create(model=lay, training_set=(X, Y),
                                criterion=MSECriterion(),
                                optim_method=bo.SGD(learningrate=0.5),
                                end_trigger=bo.MaxIteration(4),
                                batch_size=8)
        o.optimize()
        after = feat_params()
        for k in before:
            np.testing.assert_array_equal(before[k], after[k])
        lay.unfreeze(["feat"])
        o2 = bo.Optimizer.create(model=lay, training_set=(X, Y),
                                 criterion=MSECriterion(),
                                 optim_method=bo.SGD(learningrate=0.5),
                                 end_trigger=bo.MaxIteration(4),
                                 batch_size=8)
        o2.optimize()
        assert any(not np.array_equal(before[k], feat_params()[k])
                   for k in before)

    def test_stop_gradient_cuts_upstream(self):
        """stop_gradient at a mid layer: upstream params get zero grads."""
        import jax
        import jax.numpy as jnp
        from bigdl.nn.layer import Input, Linear, Model
        from bigdl_tpu.nn.module import functional_apply
        inp = Input()
        a = Linear(4, 6).set_name("up")(inp)
        b = Linear(6, 3).set_name("cut")(a)
        c = Linear(3, 2).set_name("down")(b)
        model = Model([inp], [c])
        model.stop_gradient(["cut"])
        g = model.value
        params = g.ensure_params()
        x = jnp.ones((2, 4))

        def loss(p):
            out, _ = functional_apply(g, p, x, training=False)
            return jnp.sum(out ** 2)

        grads = jax.grad(loss)(params)
        flat = jax.tree_util.tree_flatten_with_path(grads)[0]
        for path, leaf in flat:
            spath = "/".join(str(getattr(p, "key", p)) for p in path)
            total = float(jnp.abs(leaf).sum())
            if "up" in spath or "cut" in spath:
                assert total == 0.0, (spath, total)
            if "down" in spath:
                assert total > 0.0, (spath, total)

    def test_save_graph_topology_writes_graphdef(self, tmp_path):
        from bigdl.nn.layer import Input, Linear, Model, ReLU
        inp = Input()
        h = ReLU()(Linear(4, 8).set_name("fc1")(inp))
        out = Linear(8, 2).set_name("fc2")(h)
        model = Model([inp], [out])
        model.save_graph_topology(str(tmp_path))
        events = [f for f in os.listdir(tmp_path) if "tfevents" in f]
        assert len(events) == 1
        # the event round-trips into a GraphDef with our layer names
        from bigdl_tpu.native import NativeTFRecordReader
        from bigdl_tpu.proto import tb_event_pb2, tf_graph_pb2
        path = os.path.join(str(tmp_path), events[0])
        found = False
        with NativeTFRecordReader(path) as reader:
            for r in reader:
                ev = tb_event_pb2.Event.FromString(r)
                if ev.graph_def:
                    gd = tf_graph_pb2.GraphDef.FromString(ev.graph_def)
                    names = [n.name for n in gd.node]
                    assert any("fc1" in n for n in names), names
                    # edges: fc2 consumes fc1's relu output
                    by_name = {n.name: list(n.input) for n in gd.node}
                    assert any(ins for ins in by_name.values())
                    found = True
        assert found


class TestKerasNamespace:
    def _mlp_json(self):
        return json.dumps({
            "class_name": "Sequential",
            "config": [
                {"class_name": "Dense", "config": {
                    "name": "d1", "output_dim": 8, "activation": "relu",
                    "batch_input_shape": [None, 6], "bias": True}},
                {"class_name": "Dense", "config": {
                    "name": "d2", "output_dim": 3, "activation": "softmax",
                    "bias": True}},
            ],
        })

    def test_definition_loader_from_json(self, tmp_path):
        from bigdl.keras.converter import DefinitionLoader
        p = tmp_path / "m.json"
        p.write_text(self._mlp_json())
        bmodel = DefinitionLoader.from_json_path(str(p))
        out = bmodel.forward(np.random.rand(2, 6).astype(np.float32))
        assert out.shape == (2, 3)
        np.testing.assert_allclose(out.sum(1), 1.0, atol=1e-5)

    def test_optim_converter_losses(self):
        from bigdl.keras.optimization import OptimConverter
        from bigdl.nn.criterion import (BCECriterion,
                                        CategoricalCrossEntropy,
                                        ClassNLLCriterion, MSECriterion)
        assert isinstance(OptimConverter.to_bigdl_criterion("mse"),
                          MSECriterion)
        assert isinstance(
            OptimConverter.to_bigdl_criterion("categorical_crossentropy"),
            CategoricalCrossEntropy)
        assert isinstance(
            OptimConverter.to_bigdl_criterion("binary_crossentropy"),
            BCECriterion)
        assert isinstance(
            OptimConverter.to_bigdl_criterion(
                "sparse_categorical_crossentropy"), ClassNLLCriterion)
        with pytest.raises(Exception, match="Not supported"):
            OptimConverter.to_bigdl_criterion("nope")

    def test_optim_converter_methods(self):
        from bigdl.keras.optimization import OptimConverter

        class SGD:
            lr, decay, momentum, nesterov = 0.1, 1e-4, 0.9, False

        class Adam:
            lr, decay = 1e-3, 0.0
            beta_1, beta_2, epsilon = 0.9, 0.999, 1e-8

        m1 = OptimConverter.to_bigdl_optim_method(SGD())
        m2 = OptimConverter.to_bigdl_optim_method(Adam())
        assert type(m1).__name__ == "SGD" and type(m2).__name__ == "Adam"

    def test_metrics_and_helper(self):
        from bigdl.keras.optimization import OptimConverter
        from bigdl.keras.ToBigDLHelper import (to_bigdl_2d_ordering,
                                               to_bigdl_2d_padding,
                                               to_bigdl_init)
        assert len(OptimConverter.to_bigdl_metrics(["accuracy"])) == 1
        assert to_bigdl_2d_ordering("tf") == "NHWC"
        assert to_bigdl_2d_padding("valid") == (0, 0)
        assert type(to_bigdl_init("glorot_uniform")).__name__ == "Xavier"


class TestDatasetTail:
    def test_news20_parse(self, tmp_path):
        from bigdl.dataset import news20
        root = tmp_path / "20news-18828"
        for cls in ["alt.atheism", "comp.graphics"]:
            d = root / cls
            d.mkdir(parents=True)
            (d / "10001").write_text("Hello news body.", encoding="latin-1")
        texts = news20.get_news20(str(tmp_path))
        assert len(texts) == 2
        assert texts[0] == ("Hello news body.", 1)
        assert texts[1][1] == 2
        assert news20.CLASS_NUM == 20

    def test_news20_missing_data_actionable(self, tmp_path):
        from bigdl.dataset import news20
        with pytest.raises(FileNotFoundError, match="egress"):
            news20.get_news20(str(tmp_path))

    def test_glove_parse(self, tmp_path):
        from bigdl.dataset import news20
        d = tmp_path / "glove.6B"
        d.mkdir()
        (d / "glove.6B.50d.txt").write_text(
            "the " + " ".join(["0.1"] * 50) + "\n"
            "cat " + " ".join(["0.2"] * 50) + "\n")
        w2v = news20.get_glove_w2v(str(tmp_path), dim=50)
        assert len(w2v["the"]) == 50 and w2v["cat"][0] == 0.2

    def test_movielens_parse(self, tmp_path):
        from bigdl.dataset import movielens
        d = tmp_path / "ml-1m"
        d.mkdir()
        (d / "ratings.dat").write_text(
            "1::1193::5::978300760\n2::661::3::978302109\n")
        data = movielens.read_data_sets(str(tmp_path))
        assert data.shape == (2, 4) and data.dtype.kind == "i"
        np.testing.assert_array_equal(
            movielens.get_id_pairs(str(tmp_path)), [[1, 1193], [2, 661]])
        assert movielens.get_id_ratings(str(tmp_path)).shape == (2, 3)

    def test_sentence_helpers(self, tmp_path):
        from bigdl.dataset import sentence
        p = tmp_path / "t.txt"
        p.write_text("First sentence. Second one!\n")
        lines = sentence.read_localfile(str(p))
        assert len(lines) == 1
        sents = sentence.sentences_split(lines[0])
        assert len(sents) == 2
        padded = sentence.sentences_bipadding(sents[0])
        assert padded.startswith("SENTENCESTART") and \
            padded.endswith("SENTENCEEND")
        toks = sentence.sentence_tokenizer("hello, world")
        assert "hello" in toks and "world" in toks


class TestModelsTail:
    def test_inception_block_and_model_build(self):
        from bigdl.models.inception.inception import (
            inception_layer_v1, inception_v1_no_aux_classifier, t)
        blk = inception_layer_v1(
            192, t([t([64]), t([96, 128]), t([16, 32]), t([32])]), "i3a/")
        out = blk.forward(
            np.random.rand(1, 192, 28, 28).astype(np.float32))
        assert out.shape == (1, 256, 28, 28)
        model = inception_v1_no_aux_classifier(1000, has_dropout=False)
        assert len(model.flattened_layers()) > 40

    def test_rnn_build_model(self):
        from bigdl.models.rnn.rnnexample import build_model
        out = build_model(10, 8, 10).forward(
            np.random.rand(2, 5, 10).astype(np.float32))
        assert out.shape == (2, 5, 10)

    def test_rnn_prepare_data(self, tmp_path):
        from bigdl.models.rnn import rnnexample
        (tmp_path / "input.txt").write_text(
            "The cat sat. The dog ran. A bird flew away today.\n" * 4)
        train, val, vocab, w2i = rnnexample.prepare_data(
            None, str(tmp_path), vocabsize=10, training_split=0.75)
        assert len(train) > len(val) > 0
        assert all(1 <= i <= vocab for seq in train + val for i in seq)

    def test_textclassifier_builders(self):
        from bigdl.models.textclassifier import textclassifier as tc
        tc.sequence_len, tc.embedding_dim = 20, 16
        x = np.random.rand(2, 20, 16).astype(np.float32)
        for mt in ("cnn", "lstm", "gru"):
            tc.model_type = mt
            out = tc.build_model(3).forward(x)
            assert out.shape == (2, 3), mt
        tc.model_type = "cnn"
        assert tc.pad([1, 2], 0, 4) == [1, 2, 0, 0]
        assert tc.pad([1, 2, 3], 0, 2) == [1, 2]
        ordered = tc.analyze_texts([("b b a", 1)])
        assert ordered[0][0] == "b" and ordered[0][1] == (1, 2)

    def test_model_broadcast_roundtrip(self):
        from bigdl.models.utils.model_broadcast import broadcast_model
        from bigdl.nn.layer import Linear
        lay = Linear(4, 3)
        X = np.random.rand(2, 4).astype(np.float32)
        want = lay.forward(X)
        bc = broadcast_model(None, lay)
        got = bc.value.forward(X)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_ml_pipeline_reexport(self):
        from bigdl.models.ml_pipeline.dl_classifier import (DLClassifier,
                                                            DLEstimator)
        from bigdl.dlframes.dl_classifier import DLClassifier as D2
        assert DLClassifier is D2

    def test_local_lenet_get_mnist(self, tmp_path):
        from bigdl.models.local_lenet.local_lenet import get_mnist
        _stage_digits_as_mnist(str(tmp_path))
        X, Y = get_mnist("test", str(tmp_path))
        assert X.shape[1:] == (28, 28, 1)
        assert Y.min() >= 1  # 1-based


class TestRecurrentAddOrder:
    def test_add_to_container_before_cell(self):
        """Reference-legal order: the Recurrent joins a Sequential BEFORE
        its cell arrives; the later add(cell) must be visible through the
        container (the wrapper object is stable, not swapped)."""
        from bigdl.nn.layer import LSTM, Linear, Recurrent, Sequential
        seq = Sequential()
        rec = Recurrent()
        seq.add(rec)                      # placeholder inside the chain
        rec.add(LSTM(6, 5))               # cell arrives afterwards
        seq.add(Linear(5, 2))
        out = seq.forward(np.random.rand(3, 4, 6).astype(np.float32))
        assert out.shape == (3, 4, 2)


class TestKerasBackendWrapper:
    """with_bigdl_backend over a duck-typed compiled Keras-1 model:
    fit / predict / evaluate run on this stack (local mode)."""

    def _kmodel(self, tmp_path):
        h5py = pytest.importorskip("h5py")
        rs = np.random.RandomState(0)
        W1, b1 = rs.randn(6, 8).astype("f"), np.zeros(8, "f")
        W2, b2 = rs.randn(8, 3).astype("f"), np.zeros(3, "f")
        cfg = {
            "class_name": "Sequential",
            "config": [
                {"class_name": "Dense", "config": {
                    "name": "d1", "output_dim": 8, "activation": "relu",
                    "batch_input_shape": [None, 6], "bias": True}},
                {"class_name": "Dense", "config": {
                    "name": "d2", "output_dim": 3,
                    "activation": "softmax", "bias": True}},
            ],
        }

        class FakeSGD:
            lr, decay, momentum, nesterov = 0.05, 0.0, 0.0, False
        FakeSGD.__name__ = "SGD"

        class FakeKModel:
            loss = "sparse_categorical_crossentropy"
            optimizer = FakeSGD()
            metrics = ["accuracy"]

            def to_json(self):
                return json.dumps(cfg)

            def save_weights(self, path, overwrite=True):
                with h5py.File(path, "w") as f:
                    g = f.create_group("model_weights")
                    g.attrs["layer_names"] = [b"d1", b"d2"]
                    for n, ws in [("d1", [("W", W1), ("b", b1)]),
                                  ("d2", [("W", W2), ("b", b2)])]:
                        lg = g.create_group(n)
                        lg.attrs["weight_names"] = [
                            f"{n}_{w[0]}".encode() for w in ws]
                        for wn, arr in ws:
                            lg.create_dataset(f"{n}_{wn}", data=arr)

        return FakeKModel()

    def test_fit_predict_evaluate(self, tmp_path):
        from bigdl.keras.backend import with_bigdl_backend
        rs = np.random.RandomState(1)
        X = rs.rand(96, 6).astype(np.float32)
        w = rs.rand(6) - 0.5
        Y = (X @ w > 0).astype(np.int64) + 1  # 1-based classes
        wrapper = with_bigdl_backend(self._kmodel(tmp_path))
        assert wrapper.criterion is not None
        assert type(wrapper.optim_method).__name__ == "SGD"
        wrapper.fit(X, Y, batch_size=16, nb_epoch=20)
        preds = wrapper.predict(X)
        assert preds.shape == (96, 3)
        acc = wrapper.evaluate(X, Y)[0]
        assert acc > 0.8, acc
        with pytest.raises(Exception, match="Spark-free"):
            wrapper.fit(X, Y, is_distributed=True)


class TestDatasetImageFrameWrapper:
    def test_dataset_over_image_frame(self, tmp_path):
        """bigdl.dataset.dataset.DataSet wraps an ImageFrame and applies
        FeatureTransformers (reference createDatasetFromImageFrame /
        featureTransformDataset roles)."""
        from bigdl.dataset.dataset import DataSet
        from bigdl.transform.vision.image import LocalImageFrame, Resize
        imgs = [np.random.RandomState(i).rand(12, 10, 3)
                .astype(np.float32) for i in range(3)]
        frame = LocalImageFrame(imgs)
        ds = DataSet.image_frame(frame)
        assert ds.get_image_frame() is frame
        out = ds.transform(Resize(6, 6))
        got = out.get_image_frame().get_image()  # CHW, reference default
        assert all(g.shape == (3, 6, 6) for g in got)
        with pytest.raises(ValueError, match="Unsupported"):
            ds.transform(object())


class TestDLImageCompatShims:
    def test_read_and_transform(self, tmp_path):
        """bigdl.dlframes.{dl_image_reader,dl_image_transformer}: read a
        directory of images into the image-struct frame, transform
        through a vision FeatureTransformer pipeline stage."""
        pytest.importorskip("PIL")
        from PIL import Image
        for i in range(2):
            arr = (np.random.RandomState(i).rand(10, 8, 3) * 255)
            Image.fromarray(arr.astype(np.uint8)).save(
                str(tmp_path / f"img{i}.jpg"))
        from bigdl.dlframes.dl_image_reader import DLImageReader
        from bigdl.dlframes.dl_image_transformer import DLImageTransformer
        from bigdl.transform.vision.image import Resize
        df = DLImageReader.readImages(str(tmp_path) + "/*.jpg")
        assert len(df) == 2
        assert df["image"][0]["height"] == 10
        out = DLImageTransformer(Resize(6, 6)) \
            .setOutputCol("resized").transform(df)
        assert np.asarray(out["resized"][0]["data"]).shape[:2] == (6, 6)
