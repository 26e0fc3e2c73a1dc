"""Storage-integration tier (round-4 review missing #6).

Role parity: the reference proves its persistence paths against real
remote stores in the integration tier
(spark/dl/src/test/scala/.../integration/{HdfsSpec,S3Spec}.scala:
checkpoint + model + TFRecord IO over hdfs://). This zero-egress build
cannot reach a live HDFS/S3, so the same flows run against

- `file://` URIs — a REAL second filesystem path through the URI
  dispatch (not the plain-path bypass), and
- `mockhdfs://namenode:8020/...` — an authority-carrying fsspec
  filesystem registered for the tests, proving the dispatch layer's
  authority handling (the part that actually differs between local and
  HDFS-style stores) over the full checkpoint/record/event surface.

A deployment with s3fs / gcsfs / the hdfs driver installed gets the
real stores through the identical code path (`fsspec.filesystem(scheme)`).
"""

import os

import numpy as np
import pytest

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu.utils import filesystem as fsys


# --------------------------------------------------------------------------
# an authority-aware fake remote store: mockhdfs://<authority>/<path>
# maps to <tmproot>/<authority>/<path>, like HDFS resolves paths under a
# namenode. Registered once per session.
# --------------------------------------------------------------------------

_MOCK_ROOT = {"dir": None}


def _register_mockhdfs(tmp_root):
    import fsspec
    from fsspec.implementations.dirfs import DirFileSystem
    from fsspec.implementations.local import LocalFileSystem

    _MOCK_ROOT["dir"] = str(tmp_root)

    class MockHdfsFileSystem(DirFileSystem):
        """HDFS path semantics over a local directory: the scheme AND
        authority strip away (exactly what real fsspec-hdfs does —
        the behavior the dispatch layer's authority restoration exists
        for), leaving namenode-rooted absolute paths resolved under the
        authority's local root."""

        protocol = "mockhdfs"

        def __init__(self, **kw):
            super().__init__(
                path=os.path.join(_MOCK_ROOT["dir"], "namenode:8020"),
                fs=LocalFileSystem())

        @classmethod
        def _strip_protocol(cls, path):
            path = str(path)
            if path.startswith("mockhdfs://"):
                rest = path[len("mockhdfs://"):]
                _, _, p = rest.partition("/")
                return "/" + p
            return path

    fsspec.register_implementation("mockhdfs", MockHdfsFileSystem,
                                   clobber=True)


@pytest.fixture(scope="module")
def mockhdfs(tmp_path_factory):
    root = tmp_path_factory.mktemp("mockhdfs_store")
    (root / "namenode:8020").mkdir()
    _register_mockhdfs(root)
    return "mockhdfs://namenode:8020"


def _train_ckpt_resume(ckpt_uri):
    """Checkpoint to the URI mid-run, then resume a fresh optimizer from
    it and finish — the HdfsSpec flow (save/getLatest/load over a
    remote store)."""
    from bigdl_tpu.utils.random_generator import RNG
    rs = np.random.RandomState(0)
    X = rs.rand(64, 6).astype(np.float32)
    Y = (rs.randint(0, 2, size=64) + 1).astype(np.int32)

    def run(end_iter, resume=False):
        RNG.setSeed(11)
        m = (nn.Sequential().add(nn.Linear(6, 8)).add(nn.ReLU())
             .add(nn.Linear(8, 2)).add(nn.LogSoftMax()))
        o = optim.Optimizer(m, (X, Y), nn.ClassNLLCriterion(),
                            batch_size=16, local=True)
        o.set_optim_method(optim.SGD(learning_rate=0.1))
        o.set_end_when(optim.max_iteration(end_iter))
        o.set_checkpoint(ckpt_uri, optim.several_iteration(6))
        if resume:
            assert o.resume_from_latest_checkpoint()
        o.optimize()
        return m

    import jax
    oracle = jax.tree_util.tree_leaves(run(10).ensure_params())
    run(6)
    resumed = jax.tree_util.tree_leaves(run(10, resume=True)
                                        .ensure_params())
    for a, b in zip(oracle, resumed):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _tfrecord_round_trip(uri_dir):
    """Write TFRecords to the store, read them back through both the
    record writer and the native/pure reader (TFRecord-on-HDFS role)."""
    from bigdl_tpu.native import NativeTFRecordReader
    from bigdl_tpu.visualization.record_writer import TFRecordFileWriter
    path = fsys.join(uri_dir, "data", "part-0.tfrecord")
    fsys.makedirs(fsys.join(uri_dir, "data"), exist_ok=True)
    payloads = [f"record-{i}".encode() for i in range(7)]
    w = TFRecordFileWriter(path)
    for p in payloads:
        w.write(p)
    w.close()
    with NativeTFRecordReader(path) as reader:
        got = list(reader)
    assert got == payloads
    # glob finds the shard; file:// bypasses to plain local paths by
    # design, remote schemes keep scheme (+authority)
    hits = fsys.glob(fsys.join(uri_dir, "data", "*.tfrecord"))
    want = path[len("file://"):] if path.startswith("file://") else path
    assert hits == [want], hits


def _model_file_round_trip(uri_dir):
    """Serialize a model to the store and load it back (File.scala
    save/load-over-URI role)."""
    from bigdl_tpu.serialization.module_serializer import ModuleSerializer
    m = nn.Sequential().add(nn.Linear(4, 3)).add(nn.Tanh())
    m.ensure_params()
    path = fsys.join(uri_dir, "models", "net.bigdl")
    fsys.makedirs(fsys.join(uri_dir, "models"), exist_ok=True)
    ModuleSerializer.save(m, path)
    loaded = ModuleSerializer.load(path)
    import jax.numpy as jnp
    x = jnp.asarray(np.random.RandomState(1).rand(2, 4), jnp.float32)
    np.testing.assert_allclose(np.asarray(loaded.forward(x)),
                               np.asarray(m.forward(x)),
                               rtol=1e-6, atol=1e-7)


class TestFileURI:
    """file:// is a real second path through the dispatch (URI form, not
    the plain-path bypass)."""

    def test_checkpoint_resume(self, tmp_path):
        _train_ckpt_resume("file://" + str(tmp_path / "ck"))

    def test_tfrecords(self, tmp_path):
        _tfrecord_round_trip("file://" + str(tmp_path))

    def test_model_file(self, tmp_path):
        _model_file_round_trip("file://" + str(tmp_path))


class TestMockHdfsURI:
    """Authority-carrying remote-store emulation over the same flows."""

    def test_checkpoint_resume(self, mockhdfs):
        _train_ckpt_resume(mockhdfs + "/user/ckpts")

    def test_tfrecords(self, mockhdfs):
        _tfrecord_round_trip(mockhdfs + "/user/tfr")

    def test_model_file(self, mockhdfs):
        _model_file_round_trip(mockhdfs + "/user/models")

    def test_glob_preserves_authority(self, mockhdfs):
        d = mockhdfs + "/user/globtest"
        fsys.makedirs(d, exist_ok=True)
        for n in ("a.rec", "b.rec"):
            with fsys.open_file(fsys.join(d, n), "wb") as f:
                f.write(b"x")
        hits = fsys.glob(fsys.join(d, "*.rec"))
        assert hits == [fsys.join(d, "a.rec"), fsys.join(d, "b.rec")]
        for h in hits:
            assert h.startswith("mockhdfs://namenode:8020/"), h
            assert fsys.exists(h)
