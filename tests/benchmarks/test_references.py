"""Each plain reference against the program at a tiny size on the CPU,
both in float32: the same weights and batch give the same loss and the
same gradients, leaf by leaf; the lower precisions move the loss."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.files import Manifest, load_py
from benchmarks.reference import numerics as nx
from bigdl_tpu.nn.module import functional_apply


def _program_loss(adapter, weights, x, y, state=None):
    def loss(w):
        out, _ = functional_apply(adapter.model, adapter.to_program(w), x,
                                  state=state, training=True)
        return adapter.criterion().apply(out, y)
    return jax.value_and_grad(loss)(weights)


def test_neox_loss_and_gradients_match_the_program(tiny_manifest):
    cfg = tiny_manifest.config("tiny-lm")
    mix = tiny_manifest.traffic("tiny-train")
    ref = load_py("reference", cfg["reference"])
    adapter = load_py("models", cfg["model"]).Adapter(cfg, mix)
    w = ref.init_weights(cfg, 5)
    x, y = ref.train_batch(cfg, mix, 5, 1)
    want, want_g = jax.value_and_grad(partial(ref.loss, cfg))(w, x, y)
    got, got_g = _program_loss(adapter, w, x, y)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for k in want_g:
        np.testing.assert_allclose(got_g[k], want_g[k], rtol=2e-3,
                                   atol=1e-6 * float(jnp.abs(want_g[k]).max()
                                                     + 1e-12) + 1e-9,
                                   err_msg=k)
    # the adapter's two directions are inverse
    back = adapter.from_program(adapter.to_program(w))
    assert set(back) == set(w) and all(back[k] is w[k] for k in w)
    # lower precisions are different numbers, not the same ones
    assert abs(float(ref.loss(cfg, w, x, y, "fp8")) - float(want)) \
        > 3 * abs(float(ref.loss(cfg, w, x, y, "bf16")) - float(want)) > 0


def test_neox_prefill_and_decode_logits_match_the_reference(tiny_manifest):
    cfg = tiny_manifest.config("tiny-lm")
    ref = load_py("reference", cfg["reference"])
    adapter = load_py("models", cfg["model"]).Adapter(
        cfg, tiny_manifest.traffic("tiny-chat"))
    w = ref.init_weights(cfg, 9)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 1,
                              cfg["vocab_size"] + 1)
    pos = jnp.tile(jnp.arange(24)[None], (2, 1))
    want = jax.nn.log_softmax(ref.logits_at(cfg, w, toks, pos), -1)
    got = adapter.model.apply(adapter.to_program(w), toks, None)
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_resnet50_loss_matches_the_program():
    cfg = dict(Manifest().config("resnet50"), image_size=32)
    mix = {"per_chip_batch": 4}
    ref = load_py("reference", "resnet50")
    adapter = load_py("models", "resnet50").Adapter(cfg, mix)
    w = ref.init_weights(cfg, 3)
    x, y = ref.train_batch(cfg, mix, 3, 1)
    assert len(w) == 161 and sum(v.size for v in w.values()) == 25557032
    out, _ = functional_apply(adapter.model, adapter.to_program(w), x,
                              state=adapter.model.state_init(), training=True)
    got = float(adapter.criterion().apply(out, y))
    assert got == pytest.approx(float(ref.loss(cfg, w, x, y)), rel=2e-4)
    # every flat leaf lands on a leaf of its own shape in the program's tree
    tree = adapter.to_program(w)
    flat = adapter.from_program(tree)
    assert all(flat[k] is w[k] for k in w)
    assert len(jax.tree_util.tree_leaves(tree)) == 161


@pytest.mark.parametrize("method", ["sgd", "adam"])
def test_reference_optimizers_match_the_programs(method):
    import bigdl_tpu.optim as optim
    spec = {"sgd": {"method": "sgd", "learning_rate": 0.1, "momentum": 0.9,
                    "dampening": 0.0},
            "adam": {"method": "adam", "learning_rate": 0.01, "beta1": 0.9,
                     "beta2": 0.999, "epsilon": 1e-8}}[method]
    prog = optim.SGD(0.1, momentum=0.9, dampening=0.0) if method == "sgd" \
        else optim.Adam(0.01)
    w = {"a": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones((3,))}
    state, pstate, pw = nx.opt_init(spec, w), prog.init_state(w), w
    for i in range(3):
        g = jax.tree_util.tree_map(lambda v: jnp.sin(v + i), w)
        w, state = nx.opt_update(spec, w, state, g)
        pw, pstate = prog.update(g, pstate, pw, spec["learning_rate"])
        for k in w:
            np.testing.assert_allclose(w[k], pw[k], rtol=1e-6)


def test_train_trace_in_row_blocks_equals_the_whole_batch(tiny_manifest):
    cfg = tiny_manifest.config("tiny-lm")
    mix = tiny_manifest.traffic("tiny-train")
    ref = load_py("reference", cfg["reference"])
    x, y = ref.train_batch(cfg, mix, 2, 1)
    runs = [nx.train_trace(partial(ref.loss, cfg),
                           lambda: ref.init_weights(cfg, 2), x, y,
                           cfg["optimizer"], steps=3, row_block=rb)
            for rb in (None, 1)]
    assert runs[0]["losses"] == pytest.approx(runs[1]["losses"], rel=1e-5)
    for key in ("gnorm", "dnorm"):
        for leaf, v in runs[0][key].items():
            assert runs[1][key][leaf] == pytest.approx(v, rel=2e-3), leaf
