"""Synthetic-data throughput benchmark driver.

Parity: `DistriOptimizerPerf` / `LocalOptimizerPerf`
(DL/models/utils/DistriOptimizerPerf.scala:32, SURVEY.md C36) — the
reference's in-repo perf harness: train the chosen zoo model on synthetic
data and report the same "Throughput is X records/second" line the training
loop logs (DistriOptimizer.scala:405-410).

Models: lenet | inception_v1 | inception_v2 | vgg16 | vgg19 | resnet50 |
ptb — the reference driver's choices (inception_v1/v2, vgg16/19) plus the
baseline-config models. --distributed shards the step over the full
device mesh (all local chips).
"""

from __future__ import annotations

import os as _os
import sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import argparse
import time

import numpy as np


def build(model_name: str, class_num: int = 1000):
    from bigdl_tpu import models
    from bigdl_tpu.models.lenet import LeNet5
    from bigdl_tpu.models.inception import Inception_v1_NoAuxClassifier
    from bigdl_tpu.models.vgg import Vgg_16, Vgg_19
    from bigdl_tpu.models.resnet import ResNet50
    from bigdl_tpu.models.rnn import PTBModel
    if model_name == "lenet":
        return LeNet5(10), (28, 28), 10
    if model_name == "inception_v1":
        return Inception_v1_NoAuxClassifier(class_num), (224, 224, 3), class_num
    if model_name == "inception_v2":
        from bigdl_tpu.models.inception import Inception_v2_NoAuxClassifier
        return Inception_v2_NoAuxClassifier(class_num), (224, 224, 3), class_num
    if model_name == "vgg16":
        return Vgg_16(class_num), (224, 224, 3), class_num
    if model_name == "vgg19":
        return Vgg_19(class_num), (224, 224, 3), class_num
    if model_name == "resnet50":
        return ResNet50(class_num), (224, 224, 3), class_num
    if model_name == "ptb":
        return PTBModel(10001, 200, 10001), (20,), 10001
    if model_name == "transformer":
        from bigdl_tpu.models.transformer import TransformerLM
        return (TransformerLM(10001, embed_dim=512, n_layer=4, n_head=8),
                (128,), 10001)
    raise ValueError(f"unknown model {model_name}")


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="inception_v1")
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--iterations", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--class-num", type=int, default=1000)
    p.add_argument("--distributed", action="store_true",
                   help="shard over all local devices (DistriOptimizerPerf)")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.nn.module import functional_apply

    model, in_shape, n_class = build(args.model, args.class_num)
    rng = np.random.RandomState(0)
    if args.model in ("ptb", "transformer"):
        x_np = rng.randint(1, 10000, (args.batch_size,) + in_shape).astype(
            np.float32)
        y_np = rng.randint(1, 10000, (args.batch_size,) + in_shape).astype(
            np.float32)
        crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion())
    else:
        x_np = rng.rand(args.batch_size, *in_shape).astype(np.float32)
        y_np = rng.randint(1, n_class + 1, args.batch_size).astype(
            np.float32)
        crit = nn.ClassNLLCriterion()

    params = model.ensure_params()
    state = model._state
    method = optim.SGD(learning_rate=0.01)
    opt_state = method.init_state(params)

    def step(params, opt_state, state, x, y):
        def loss_fn(p):
            # bf16 matmuls = MXU native mode; f32 master params
            with jax.default_matmul_precision("bfloat16"):
                out, new_s = functional_apply(model, p, x, state=state,
                                              training=True,
                                              rng=jax.random.PRNGKey(0))
            return crit.apply(out, y), new_s

        (loss, new_s), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        if args.distributed:
            grads = jax.lax.pmean(grads, "data")
            loss = jax.lax.pmean(loss, "data")
        new_params, new_opt = method.update(grads, opt_state, params, 0.01)
        return new_params, new_opt, new_s, loss

    if args.distributed:
        from bigdl_tpu.parallel.mesh import build_mesh, shard_batch
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = build_mesh(model=1)
        n_dev = mesh.devices.size
        x = jnp.asarray(np.tile(x_np, (n_dev,) + (1,) * (x_np.ndim - 1)))
        y = jnp.asarray(np.tile(y_np, (n_dev,) + (1,) * (y_np.ndim - 1)))
        records = args.batch_size * n_dev

        run = jax.jit(jax.shard_map(
            step, mesh=mesh,
            in_specs=(P(), P(), P(), P("data"), P("data")),
            out_specs=(P(), P(), P(), P())))
    else:
        records = args.batch_size
        x, y = jnp.asarray(x_np), jnp.asarray(y_np)
        # donate param/opt/state buffers: saves an HBM copy per step
        run = jax.jit(step, donate_argnums=(0, 1, 2))

    for _ in range(args.warmup):
        params, opt_state, state, loss = run(params, opt_state, state, x, y)
    float(loss)  # value fetch = real completion barrier (see profiling.device_sync)

    times = []
    for i in range(args.iterations):
        t0 = time.perf_counter()
        params, opt_state, state, loss = run(params, opt_state, state, x, y)
        loss_v = float(loss)
        dt = time.perf_counter() - t0
        times.append(dt)
        print(f"[Iteration {i + 1}] Training cost {loss_v:.4f}. "
              f"Throughput is {records / dt:.2f} records/second. ")

    med = float(np.median(times))
    print(f"Median throughput: {records / med:.2f} records/second "
          f"({args.model}, batch {records})")
    return records / med


if __name__ == "__main__":
    main()
