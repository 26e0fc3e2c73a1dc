"""Compile-only checks at the cells' real sizes, for a v5e that is
described and not attached (the `on-chip-measurement` guide, section 2):
the plain references' programs and the program's own step programs fit
one chip's memory and hold the kernels they should. Nothing runs, so
nothing here is a timing. Minutes of compiling: marked slow.
"""

import os
from functools import partial

import pytest

pytestmark = pytest.mark.slow

GIB = 2 ** 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def manifest():
    from benchmarks.files import Manifest
    return Manifest()


def _abstract(tree, sharding):
    import jax
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _planned_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


def _reference_grad(manifest, cell, one_chip, rows):
    import jax
    from benchmarks.files import load_py
    c = manifest.cell(cell)
    cfg, mix = manifest.config(c["config"]), manifest.traffic(c["traffic"])
    ref = load_py("reference", cfg["reference"])
    w = _abstract(jax.eval_shape(lambda: ref.init_weights(cfg, 0)), one_chip)
    x, y = jax.eval_shape(lambda: ref.train_batch(cfg, mix, 0, 1))
    x, y = (jax.ShapeDtypeStruct((rows,) + a.shape[1:], a.dtype,
                                 sharding=one_chip) for a in (x, y))
    fn = jax.jit(jax.value_and_grad(partial(ref.loss, cfg, precision="f32")))
    return fn.lower(w, x, y).compile()


def test_resnet50_reference_step_fits(manifest, one_chip):
    compiled = _reference_grad(manifest, "resnet50.train-b128", one_chip, 128)
    assert _planned_bytes(compiled) < 13 * GIB


def test_neox_reference_row_fits_beside_adam_state(manifest, one_chip):
    # weights, accumulated gradient and Adam's two moments stay resident
    # while one row's gradient is computed: 4 x 2.25 GB beside this
    compiled = _reference_grad(manifest, "neox-3.6b.train-t2048", one_chip, 1)
    assert _planned_bytes(compiled) + 3 * 2.25e9 < 15 * GIB


def test_neox_reference_served_block_fits(manifest, one_chip):
    import jax
    import jax.numpy as jnp
    from benchmarks.files import load_py
    c = manifest.cell("neox-3.6b.serve-chat")
    cfg, mix = manifest.config(c["config"]), manifest.traffic(c["traffic"])
    ref = load_py("reference", cfg["reference"])
    w = _abstract(jax.eval_shape(lambda: ref.init_weights(cfg, 0)), one_chip)
    block = mix["check_block"]
    t = mix["prompt_len"]["max"] + mix["output_len"]["max"]
    toks = jax.ShapeDtypeStruct((block, t), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((block, mix["output_len"]["max"]), jnp.int32,
                               sharding=one_chip)
    fn = jax.jit(lambda w, t, p: ref.logits_at(cfg, w, t, p, "f32"))
    assert _planned_bytes(fn.lower(w, toks, pos).compile()) < 12 * GIB


# ---------------------------------------------- the program's own steps

@pytest.fixture()
def tpu_routing(monkeypatch):
    """The program asks `jax.default_backend()` before it takes its
    Pallas kernels; here that still says cpu, so the test steers it."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _train_step(manifest, cell, devices):
    """The cell's compiled-to-be training step and abstract arguments,
    built as the driver builds it, on described devices."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from benchmarks.files import load_py
    from bigdl_tpu.dataset.dataset import LocalDataSet
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.parallel.mesh import build_mesh
    c = manifest.cell(cell)
    cfg, mix = manifest.config(c["config"]), manifest.traffic(c["traffic"])
    ref = load_py("reference", cfg["reference"])
    adapter = load_py("models", cfg["model"]).Adapter(cfg, mix)
    mesh = build_mesh(devices=list(devices))
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    flat = jax.eval_shape(lambda: ref.init_weights(cfg, 0))
    params = _abstract(adapter.to_program(flat), rep)
    state = _abstract(jax.eval_shape(adapter.model.state_init), rep)
    x, y = (jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rows)
            for a in jax.eval_shape(
                lambda: ref.train_batch(cfg, mix, 0, len(devices))))
    opt = DistriOptimizer(adapter.model, LocalDataSet([]),
                          adapter.criterion(), mesh=mesh)
    opt.set_optim_method(adapter.optim_method())
    opt.set_compute_precision(cfg["compute_precision"])
    slots = _abstract(jax.eval_shape(
        opt.optim_method.init_state_with_masters, params), rep)
    step = opt._build_step((jax.tree_util.tree_map(lambda a: rep, params),
                            jax.tree_util.tree_map(lambda a: rep, slots))
                           if len(devices) > 1 else None)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
    return step.lower(params, slots, state, x, y, 0.01, rng).compile()


@pytest.mark.parametrize("cell,chips,kernels", [
    ("resnet50.train-b128", 1, 66), ("resnet50.train-dp4", 4, 66),
    ("neox-3.6b.train-t2048", 1, 12)])
def test_training_step_compiles_with_its_kernels(manifest, topo, tpu_routing,
                                                 cell, chips, kernels):
    if cell not in [w["name"] for w in manifest.doc["workloads"]]:
        pytest.skip(f"{cell} is not in BENCHMARK.json")
    compiled = _train_step(manifest, cell, topo.devices[:chips])
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= kernels
    assert _planned_bytes(compiled) < 15 * GIB
    assert ("all-reduce" in text) == (chips > 1)
