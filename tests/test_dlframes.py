"""dlframes tests (reference pyspark/test/bigdl/test_dl_classifier.py +
TEST/dlframes specs, SURVEY.md C31): estimator fit/transform over DataFrames,
classifier argmax semantics, image reader/transformer stages.
"""

import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.dlframes import (DLClassifier, DLEstimator, DLImageReader,
                                DLImageTransformer, DLModel)

pd = pytest.importorskip("pandas")


def _toy_df(n=96, d=6, classes=3, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    W = rng.randn(d, classes).astype(np.float32) * 2
    y = np.argmax(X @ W, axis=1) + 1  # 1-based labels
    return pd.DataFrame({"features": [x for x in X],
                         "label": y.astype(np.float64)}), X, y


class TestDLClassifier:
    def test_fit_transform(self):
        df, X, y = _toy_df()
        model = nn.Sequential().add(nn.Linear(6, 16)).add(nn.ReLU()) \
            .add(nn.Linear(16, 3)).add(nn.LogSoftMax())
        est = DLClassifier(model, nn.ClassNLLCriterion(), [6]) \
            .set_batch_size(16).set_max_epoch(30).set_learning_rate(1e-2)
        fitted = est.fit(df)
        out = fitted.transform(df)
        acc = (np.asarray(out["prediction"]) == y).mean()
        assert acc > 0.9, acc
        assert "prediction" in out.columns

    def test_regression_estimator(self):
        rng = np.random.RandomState(1)
        X = rng.randn(128, 4).astype(np.float32)
        w = np.asarray([1.0, -2.0, 0.5, 3.0], np.float32)
        y = X @ w
        df = pd.DataFrame({"features": [x for x in X],
                           "label": [np.asarray([v]) for v in y]})
        model = nn.Sequential().add(nn.Linear(4, 1))
        est = DLEstimator(model, nn.MSECriterion(), [4], [1]) \
            .set_batch_size(32).set_max_epoch(60).set_learning_rate(5e-2)
        fitted = est.fit(df)
        out = fitted.transform(df)
        preds = np.asarray([p.reshape(-1)[0] for p in out["prediction"]])
        assert np.abs(preds - y).mean() < 0.3

    def test_dict_frame_support(self):
        df, X, y = _toy_df(n=32)
        plain = {"features": list(df["features"]), "label": list(df["label"])}
        model = nn.Sequential().add(nn.Linear(6, 3)).add(nn.LogSoftMax())
        est = DLClassifier(model, nn.ClassNLLCriterion(), [6]) \
            .set_max_epoch(2)
        fitted = est.fit(plain)
        out = fitted.transform(plain)
        assert len(out["prediction"]) == 32


class TestDLImage:
    def _img_dir(self, tmp_path):
        from PIL import Image
        rng = np.random.RandomState(0)
        for cls in ("a", "b"):
            d = tmp_path / cls
            d.mkdir()
            for i in range(3):
                arr = rng.randint(0, 255, (12, 10, 3)).astype(np.uint8)
                Image.fromarray(arr).save(str(d / f"{i}.png"))
        return str(tmp_path)

    def test_reader_schema(self, tmp_path):
        df = DLImageReader.read(self._img_dir(tmp_path), with_label=True)
        assert len(df) == 6
        row = df.iloc[0]["image"]
        assert row["height"] == 12 and row["width"] == 10
        assert row["n_channels"] == 3
        assert set(df["label"]) == {1.0, 2.0}

    def test_transformer_stage(self, tmp_path):
        from bigdl_tpu.transform.vision.augmentation import Resize
        df = DLImageReader.read(self._img_dir(tmp_path))
        out = DLImageTransformer(Resize(6, 5)).transform(df)
        assert out.iloc[0]["output"]["height"] == 6
        assert out.iloc[0]["output"]["width"] == 5
        # original column untouched
        assert out.iloc[0]["image"]["height"] == 12


class TestRowTransformer:
    """DL/dataset/datamining/RowTransformer.scala parity over pandas rows."""

    def _df(self):
        import pandas as pd
        return pd.DataFrame({"a": [1.0, 2.0], "b": [3.0, 4.0],
                             "tag": ["x", "y"]})

    def test_numeric_all(self):
        import numpy as np
        from bigdl_tpu.dlframes.row_transformer import RowTransformer
        t = RowTransformer.numeric()
        out = t.transform_row({"a": 1.0, "b": 2.5})
        np.testing.assert_allclose(out["all"], [1.0, 2.5])

    def test_numeric_grouped_and_atomic(self):
        import numpy as np
        from bigdl_tpu.dlframes.row_transformer import RowTransformer
        t = RowTransformer.atomic_with_numeric(
            ["tag"], {"feats": ["a", "b"]})
        rows = t.apply_frame(self._df())
        assert len(rows) == 2
        np.testing.assert_allclose(rows[1]["feats"], [2.0, 4.0])
        assert rows[0]["tag"][0] == "x"

    def test_atomic_by_index(self):
        import numpy as np
        from bigdl_tpu.dlframes.row_transformer import RowTransformer
        t = RowTransformer.atomic(indices=[0, 2], row_size=3)
        out = t.transform_row((7.0, 8.0, 9.0))
        np.testing.assert_allclose(out["0"], [7.0])
        np.testing.assert_allclose(out["2"], [9.0])

    def test_duplicate_key_rejected(self):
        import pytest
        from bigdl_tpu.dlframes.row_transformer import (ColsToNumeric,
                                                        RowTransformer)
        with pytest.raises(ValueError, match="replicated schemaKey"):
            RowTransformer([ColsToNumeric("k"), ColsToNumeric("k")])

    def test_index_bound_check(self):
        import pytest
        from bigdl_tpu.dlframes.row_transformer import (ColsToNumeric,
                                                        RowTransformer)
        with pytest.raises(ValueError, match="out of bound"):
            RowTransformer([ColsToNumeric("k", indices=[5])], row_size=3)


class _FakeRow:
    def __init__(self, d):
        self._d = d

    def __getitem__(self, k):
        return self._d[k]


class _FakeSession:
    """Stands in for SparkSession.createDataFrame: records the call and
    hands back the pandas frame (a real session would build a Spark DF)."""

    def __init__(self):
        self.calls = 0

    def createDataFrame(self, pdf):
        self.calls += 1
        return pdf


class _FakeSparkDF:
    """Duck-typed pyspark.sql.DataFrame: schema/select/toLocalIterator/
    toPandas/sparkSession — the exact surface the dlframes spark ingest
    consumes. Lets the Spark code path run without a JVM; the
    pyspark-marked test below runs the same flow on a real local-mode
    session when pyspark is installed."""

    def __init__(self, columns, session=None):
        self._cols = columns  # name -> list
        self.schema = list(columns)
        self.sparkSession = session or _FakeSession()

    def select(self, *names):
        return _FakeSparkDF({n: self._cols[n] for n in names},
                            self.sparkSession)

    def toLocalIterator(self):
        n = len(next(iter(self._cols.values())))
        for i in range(n):
            yield _FakeRow({k: v[i] for k, v in self._cols.items()})

    def toPandas(self):
        import pandas as pd
        return pd.DataFrame({k: list(v) for k, v in self._cols.items()})


class TestSparkDataFrameIngest:
    """round-4 review missing #2: DLEstimator/DLClassifier over Spark
    DataFrames — partition-streamed column extraction, ML-Vector cells,
    and a Spark frame handed back from transform."""

    def _xy(self):
        rs = np.random.RandomState(0)
        X = rs.rand(64, 4).astype(np.float32)
        w = rs.rand(4) - 0.5
        Y = (X @ w > 0).astype(np.float32) + 1
        return X, Y

    def test_classifier_fit_transform_on_sparklike_df(self):
        import bigdl_tpu.nn as nn
        from bigdl_tpu.dlframes import DLClassifier

        X, Y = self._xy()

        class _Vec:  # pyspark.ml DenseVector surface
            def __init__(self, a):
                self._a = a

            def toArray(self):
                return self._a

        df = _FakeSparkDF({"features": [_Vec(x) for x in X],
                           "label": list(Y)})
        model = (nn.Sequential().add(nn.Linear(4, 8)).add(nn.ReLU())
                 .add(nn.Linear(8, 2)).add(nn.LogSoftMax()))
        est = DLClassifier(model, nn.ClassNLLCriterion(), [4])
        est.set_batch_size(16).set_max_epoch(30).set_learning_rate(1e-2)
        fitted = est.fit(df)
        out = fitted.transform(df)
        # transform went back through the session (spark contract)
        assert df.sparkSession.calls == 1
        acc = float((np.asarray(out["prediction"]) == Y).mean())
        assert acc > 0.85, acc

    def test_real_pyspark_local_mode(self):
        """Runs only where pyspark is installed (not in this image):
        same flow on a genuine local-mode SparkSession."""
        pyspark = pytest.importorskip("pyspark")
        from pyspark.sql import SparkSession

        import bigdl_tpu.nn as nn
        from bigdl_tpu.dlframes import DLClassifier

        spark = SparkSession.builder.master("local[2]").getOrCreate()
        try:
            X, Y = self._xy()
            rows = [(x.tolist(), float(y)) for x, y in zip(X, Y)]
            df = spark.createDataFrame(rows, ["features", "label"])
            model = (nn.Sequential().add(nn.Linear(4, 8)).add(nn.ReLU())
                     .add(nn.Linear(8, 2)).add(nn.LogSoftMax()))
            est = DLClassifier(model, nn.ClassNLLCriterion(), [4])
            est.set_batch_size(16).set_max_epoch(30).set_learning_rate(1e-2)
            out = est.fit(df).transform(df)
            assert "prediction" in out.columns
            preds = [r["prediction"] for r in out.collect()]
            acc = float(np.mean(np.asarray(preds) == Y))
            assert acc > 0.85, acc
        finally:
            spark.stop()
