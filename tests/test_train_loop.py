"""The one training iteration and the one train-step body of `optim/`.

`BaseOptimizer` owns the loss closure, the step body, the iteration and
its tail; `LocalOptimizer`, `DistriOptimizer` and the elastic loop hand it
placement and keep what is theirs. Held here: every loop shows the same
iteration to a tracer, a hook and the telemetry stream; the local and the
one-device SPMD loop compute, bit for bit, what a plain loop written out
below computes; gradient accumulation means the same on one device as on
a mesh; and each of the shared pieces has exactly one owner.
"""

import ast
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu.dataset.dataset import DataSet
from bigdl_tpu.dataset.transformer import SampleToMiniBatch
from bigdl_tpu.nn.module import functional_apply
from bigdl_tpu.observability import InMemorySink, Telemetry
from bigdl_tpu.observability.spans import SpanTracer
from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
from bigdl_tpu.optim.local_optimizer import LocalOptimizer
from bigdl_tpu.optim.trigger import max_iteration, several_iteration
from bigdl_tpu.parallel.mesh import build_mesh
from bigdl_tpu.resilience import (FaultInjector, FaultSpec,
                                  PermanentInjectedFault)

OPTIM_DIR = pathlib.Path(optim.__file__).parent
STEPS = 6


def _model():
    m = (nn.Sequential().add(nn.Linear(8, 16)).add(nn.ReLU())
         .add(nn.Dropout(0.3)).add(nn.Linear(16, 4)).add(nn.LogSoftMax()))
    m.set_params(m.init(jax.random.PRNGKey(1)))
    return m


def _dataset():
    """64 samples in batches of 8: the six steps stay inside one epoch, so
    the stream is one permutation of the dataset's own seeded rng."""
    rs = np.random.RandomState(0)
    return DataSet.from_arrays(
        rs.randn(64, 8).astype(np.float32),
        rs.randint(1, 5, (64,)).astype(np.float32)).transform(
            SampleToMiniBatch(8, drop_remainder=True))


def _batches():
    stream = _dataset().data(train=True)
    return [next(stream) for _ in range(STEPS)]


def _method():
    return optim.SGD(learning_rate=0.1, momentum=0.9)


def _optimizer(loop, steps=STEPS, sync=1):
    """`loop`: local | distri1 | distri4 | elastic."""
    model, data, crit = _model(), _dataset(), nn.ClassNLLCriterion()
    if loop == "local":
        opt = LocalOptimizer(model, data, crit, batch_size=8)
    else:
        n = {"distri1": 1, "distri4": 4, "elastic": 2}[loop]
        opt = DistriOptimizer(model, data, crit, retry_times=0,
                              mesh=build_mesh(data=n, model=1,
                                              devices=jax.devices()[:n]))
        if loop == "elastic":
            opt.set_elastic()
    opt.set_optim_method(_method()).set_sync_interval(sync)
    opt.set_end_when(max_iteration(steps))
    return opt


def _losses(opt):
    seen = []
    opt.set_iteration_hook(lambda s: seen.append(s["loss"]))
    opt.optimize()
    return seen


# --------------------------------------------------------------------- #
# (a) one iteration, whatever the loop
# --------------------------------------------------------------------- #
#: what the loop's lane shows between two iterations' ends, outermost
#: spans in order; the lookahead loops fetch the NEXT batch behind the
#: dispatched step, the elastic loop (a replay queue feeds it) before it
_BEFORE_SYNC = {
    "local": ["step prepare", "step dispatch", "data fetch"],
    "distri1": ["step prepare", "step dispatch", "data fetch",
                "put batch on mesh"],
    "distri4": ["step prepare", "step dispatch", "data fetch",
                "put batch on mesh"],
    "elastic": ["data fetch", "step prepare", "step dispatch"],
}
_RUN_START = {"local": "local", "distri1": "distri", "distri4": "distri",
              "elastic": "distri_elastic"}


def _iterations(tracer):
    """The driver lane's spans as one list per iteration: `(name, [names
    nested in it])`, an iteration ending with its `step bookkeeping`."""
    evs = sorted((e for e in tracer.events if e.get("ph") == "X"
                  and not e["name"].startswith("optimize/")),
                 key=lambda e: (e["ts"], -e["dur"]))
    out, cur, open_until = [[]], None, -1.0
    for e in evs:
        if e["ts"] < open_until:  # begins inside the span before it
            cur[1].append(e["name"])
            continue
        cur, open_until = (e["name"], []), e["ts"] + e["dur"]
        out[-1].append(cur)
        if e["name"] == "step bookkeeping":
            out.append([])
    return out


@pytest.mark.parametrize("loop", sorted(_BEFORE_SYNC))
def test_every_loop_shows_the_same_iteration(loop, tmp_path):
    opt = _optimizer(loop, steps=4)
    opt.set_checkpoint(str(tmp_path), several_iteration(2))
    tracer, sink = SpanTracer(annotate=False), InMemorySink()
    opt.set_tracer(tracer)
    opt.set_telemetry(Telemetry(sink, resources=False, flight=False))
    keys = []
    opt.set_iteration_hook(lambda s: keys.append(sorted(s)))
    opt.optimize()

    its = _iterations(tracer)
    assert len(its) == 5
    want = _BEFORE_SYNC[loop] + ["loss sync", "step bookkeeping"]
    for i, it in enumerate(its[:4]):
        names = [n for n, _ in it]
        assert names[-len(want):] == want, (i, names)
        # before the first iteration: placement, the lookahead's first pull
        assert names[:-len(want)] == (
            [] if i else {"local": ["data fetch"], "elastic": []}
            .get(loop, ["place params", "data fetch", "put batch on mesh"]))
        nested = dict(it)
        assert nested["step bookkeeping"] == (
            ["validation", "checkpoint"] if i % 2 else ["validation"])
        assert all(not v for k, v in nested.items()
                   if k != "step bookkeeping")
    assert [n for n, _ in its[4]] == ([] if loop == "local"
                                      else ["gather params"])
    # the hook's view and the stream's
    assert len(keys) == 4 and all(k == keys[0] for k in keys)
    assert {"neval", "loss", "epoch", "recordsProcessedThisEpoch"} \
        <= set(keys[0])
    starts = [r for r in sink.records if r["type"] == "run_start"]
    assert [r["loop"] for r in starts] == [_RUN_START[loop]]
    assert sink.records[-1]["type"] == "run_end"
    # one deliberate union: every loop times its checkpoints
    assert "checkpoint time" in opt.metrics.as_dict()


# --------------------------------------------------------------------- #
# (b) the reference the loops are held to
# --------------------------------------------------------------------- #
def _plain_loop():
    """The training loop written out: a host `split` chain from
    `PRNGKey(0)`, `value_and_grad` of `criterion(model(x))`, the optim
    method's update. Returns the losses and the chain's last key."""
    model, crit, method = _model(), nn.ClassNLLCriterion(), _method()
    params = model.ensure_params()
    slots = method.init_state(params)

    @jax.jit
    def step(params, slots, x, y, lr, rng):
        def loss_fn(p):
            out, _ = functional_apply(model, p, x, state=model._state,
                                      training=True, rng=rng)
            return crit.apply(out, y)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        return (*method.update(grads, slots, params, lr), loss)

    key, losses = jax.random.PRNGKey(0), []
    for b in _batches():
        key, step_key = jax.random.split(key)
        params, slots, loss = step(params, slots, b.get_input(),
                                   b.get_target(), method.current_lr(),
                                   step_key)
        losses.append(float(loss))
    return losses, np.asarray(key)


@pytest.mark.parametrize("sync", [1, 3])
def test_local_and_one_device_spmd_equal_the_plain_loop(sync):
    want, last_key = _plain_loop()
    for loop in ("local", "distri1"):
        opt = _optimizer(loop, sync=sync)
        got = _losses(opt)
        # between syncs the hook sees the last synced loss
        seen = [want[i - (i + 1) % sync] if i + 1 >= sync else None
                for i in range(STEPS)]
        assert [g for g, s in zip(got, seen) if s is not None] \
            == [s for s in seen if s is not None], loop
        assert all(np.isnan(g) for g, s in zip(got, seen) if s is None)
        np.testing.assert_array_equal(np.asarray(opt.rng), last_key, loop)


# --------------------------------------------------------------------- #
# (c) gradient accumulation is the body's, so every loop has it
# --------------------------------------------------------------------- #
def test_local_accumulates_like_one_device_spmd():
    runs = {}
    for loop in ("local", "distri1"):
        opt = _optimizer(loop).set_gradient_accumulation(2)
        runs[loop] = _losses(opt)
    assert runs["local"] == runs["distri1"]
    # micro-batches draw their own keys: not the plain loop's floats
    assert runs["local"] != _plain_loop()[0]

    opt = _optimizer("local").set_gradient_accumulation(2)
    params = opt.model.ensure_params()
    b = _batches()[0]
    jaxpr = opt._build_step().trace(
        params, opt.optim_method.init_state_with_masters(params),
        opt.model._state, jnp.asarray(b.get_input()),
        jnp.asarray(b.get_target()), 0.1, jax.random.PRNGKey(0)).jaxpr
    assert "scan" in {e.primitive.name for e in jaxpr.eqns}


# --------------------------------------------------------------------- #
# (d) one owner each
# --------------------------------------------------------------------- #
def _owners(match):
    """`file::outermost function` of every node under `optim/` that
    `match` takes."""
    found = []
    for path in sorted(OPTIM_DIR.glob("*.py")):
        def walk(node, owner):
            for child in ast.iter_child_nodes(node):
                inside = owner
                if owner is None and isinstance(
                        child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    inside = f"{path.name}::{child.name}"
                if match(child):
                    found.append(inside)
                walk(child, inside)
        walk(ast.parse(path.read_text()), None)
    return found


@pytest.mark.parametrize("what,match,owner", [
    ("the iteration's tail",
     lambda n: isinstance(n, ast.Constant) and n.value == "step bookkeeping",
     "local_optimizer.py::_finish_iteration"),
    ("the loss closure",
     lambda n: isinstance(n, ast.Call) and getattr(
         n.func, "attr", getattr(n.func, "id", None)) == "value_and_grad",
     "local_optimizer.py::_loss_and_grads"),
])
def test_one_owner(what, match, owner):
    assert _owners(match) == [owner], what


def test_no_loop_is_written_twice():
    """Neither `_optimize_impl` holds a loop of its own; the elastic one
    holds one, whose body is per-shard dispatch and recovery."""
    whiles = [w for w in _owners(lambda n: isinstance(n, ast.While))
              if w.endswith("_impl")]
    assert whiles == ["distri_optimizer.py::_optimize_elastic_impl"]


# --------------------------------------------------------------------- #
# (e) a failed local run
# --------------------------------------------------------------------- #
def test_a_failed_local_run_leaves_rng_and_model_as_they_were():
    opt = _optimizer("local")
    start_rng = np.asarray(opt.rng).copy()
    start = jax.device_get(opt.model.ensure_params())
    with FaultInjector(FaultSpec("train.step", at_hit=3,
                                 exc=PermanentInjectedFault)):
        with pytest.raises(PermanentInjectedFault):
            opt.optimize()
    assert opt.optim_method.state["neval"] == 2
    np.testing.assert_array_equal(np.asarray(opt.rng), start_rng)
    jax.tree_util.tree_map(np.testing.assert_array_equal,
                           jax.device_get(opt.model.ensure_params()), start)
    out = opt.model.evaluate().forward(_batches()[0].get_input())
    assert np.isfinite(np.asarray(out)).all()
