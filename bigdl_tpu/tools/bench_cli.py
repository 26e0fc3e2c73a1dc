"""Benchmark driver: prints ONE JSON line with the headline metric.

Headline metric (BASELINE.json north star): ResNet-50 training throughput,
imgs/sec/chip, synthetic ImageNet-shaped data — the TPU analogue of the
reference's DistriOptimizerPerf (DL/models/utils/DistriOptimizerPerf.scala:32)
and its per-iteration "Throughput is X records/second" log line
(DistriOptimizer.scala:405-410).

Unlike a hand-rolled jit loop, this drives the REAL framework path:
`DistriOptimizer` over the device mesh, the Metrics phase table (the
reference's Metrics.scala:36-103 breakdown), and an MFU estimate from
XLA's own per-step FLOP count. Data feeding matches the reference driver
exactly: DistriOptimizerPerf broadcasts ONE synthetic MiniBatch and
persists it in executor memory, re-read every iteration
(DistriOptimizerPerf.scala:108-118) — here that is a device-resident
batch reused each step (headline), with a secondary stderr figure for a
fresh host->device transfer per step (the input-pipeline cost the
reference driver does not pay either). Multi-chip hosts report PER-CHIP
throughput (global / device count), and MFU compares whole-mesh FLOP/s
against whole-mesh peak.

vs_baseline: the reference publishes no absolute imgs/sec in-tree
(BASELINE.md; whitepaper positioning is "comparable with mainstream GPU" on
a Xeon cluster). We compare against 55 imgs/sec — a representative published
figure for BigDL-era ResNet-50 training on one dual-socket Xeon node (the
reference's per-node unit).

The headline and the secondary suites measure the chip: with no TPU, or
one whose peak the cost table does not know, `bench.py` exits non-zero
and prints no metric line. The A/B modes (`--serve`, `--generate`,
`--fusion`, ...) are parity gates that also run on the CPU; every result
line names the device it ran on.

Compute dtype: bf16 matmuls (set_compute_precision("bfloat16")) — the MXU's
native mode; params stay f32 (matching the reference's fp32 master weights
with fp16 wire compression, FP16CompressedTensor.scala:143).
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import sys
import time

import numpy as np

# peak dense bf16 FLOP/s chip registry: single source of truth lives in
# the observability cost-accounting module (the telemetry stream computes
# per-step MFU from the same table this offline report uses)
from bigdl_tpu.observability.costs import peak_flops as _peak_flops
from bigdl_tpu.utils import compile_cache


def _step_flops(model, crit, method, params, state, batch_size, in_shape):
    """Per-step FLOPs from XLA's cost model, lowered from the SAME step the
    optimizer runs (momentum update + bf16 matmul scope)."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.nn.module import functional_apply

    opt_state = method.init_state(params)

    def step(p, o, x, y):
        def loss_fn(p):
            with jax.default_matmul_precision("bfloat16"):
                out, _ = functional_apply(model, p, x, state=state,
                                          training=True)
                return crit(out, y)
        loss, grads = jax.value_and_grad(loss_fn)(p)
        new_p, new_o = method.update(grads, o, p, 0.01)
        return new_p, new_o, loss

    x_s = jax.ShapeDtypeStruct((batch_size, *in_shape), jnp.float32)
    y_s = jax.ShapeDtypeStruct((batch_size,), jnp.int32)
    compiled = jax.jit(step).lower(params, opt_state, x_s, y_s).compile()
    return float(compiled.cost_analysis()["flops"])


_TELEMETRY_RUNS = 0  # distinguishes multiple runs inside one process


@contextlib.contextmanager
def _bench_telemetry(opt):
    """When BIGDL_TPU_TELEMETRY names a directory (set by the parent's
    --telemetry flag; inherited by every child suite), wire a structured
    telemetry stream (JSONL) and a span tracer (Chrome trace JSON) onto
    the optimizer for the enclosed run — one file pair per run, keyed by
    pid + in-process run counter, closed/exported even when the run
    fails. No-op when the env var is unset."""
    global _TELEMETRY_RUNS
    tel_dir = os.environ.get("BIGDL_TPU_TELEMETRY")
    if not tel_dir:
        yield
        return
    from bigdl_tpu.observability import JsonlSink, SpanTracer, Telemetry
    os.makedirs(tel_dir, exist_ok=True)
    _TELEMETRY_RUNS += 1
    stem = os.path.join(tel_dir,
                        f"bench_{os.getpid()}_r{_TELEMETRY_RUNS}")
    telemetry = Telemetry(JsonlSink(stem + ".jsonl"))
    tracer = SpanTracer(process_name=f"bench[{os.getpid()}]")
    opt.set_telemetry(telemetry)
    opt.set_tracer(tracer)
    try:
        yield
    finally:
        telemetry.close()
        tracer.export(stem + ".trace.json")
        if os.environ.get("BIGDL_TPU_ATTRIBUTION"):
            # --attribution: print the per-run attribution report
            # (host-vs-device breakdown, MFU trend, top compile costs)
            # to stderr right next to the phase table
            try:
                from bigdl_tpu.tools import metrics_cli
                metrics_cli.report(stem + ".jsonl", out=sys.stderr)
            except Exception as e:
                print(f"attribution report failed: {e!r}", file=sys.stderr)


def _framework_throughput(model, in_shape, n_class, batch_size, warmup,
                          iters, resident=True, sync=4):
    """Train via DistriOptimizer; return (global imgs/sec, metrics,
    flops_per_step).

    resident=True is the headline mode and matches the reference driver
    EXACTLY: DistriOptimizerPerf broadcasts ONE synthetic MiniBatch and
    persists it in executor memory, so every iteration re-reads the same
    resident batch with no fresh host ingest
    (DistriOptimizerPerf.scala:108-118). The TPU analogue of
    broadcast+persist is device_put once, reuse every step — the loop
    still runs the full DistriOptimizer path (metrics, donation, loss
    sync). resident=False additionally pays a fresh host->device transfer
    per step (a rotation of distinct host batches), reported as the
    secondary input-pipeline figure.

    Throughput is measured over SYNC WINDOWS: the loop runs with
    `set_sync_interval(sync)` so steps dispatch asynchronously and the
    host blocks only every `sync` iterations. Donation chains the steps,
    so each sync timestamp is the exact completion time of every step
    dispatched so far; the median window interval over `iters` timed
    iterations gives imgs/sec. `warmup` and `iters` must be multiples of
    `sync`."""
    import jax
    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.dataset import LocalDataSet
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.optim.trigger import max_iteration
    from bigdl_tpu.parallel.mesh import build_mesh, shard_batch

    rs = np.random.RandomState(0)
    mesh = build_mesh()
    batches = [
        MiniBatch(rs.rand(batch_size, *in_shape).astype(np.float32),
                  (rs.randint(0, n_class, size=batch_size) + 1)
                  .astype(np.int32))
        for _ in range(1 if resident else 4)
    ]
    if resident:
        # broadcast+persist analogue: place once; the loop's shard_batch
        # is then an identity device_put on the committed arrays
        batches = [MiniBatch(shard_batch(mesh, b.get_input()),
                             shard_batch(mesh, b.get_target()))
                   for b in batches]
    dataset = LocalDataSet(batches)
    crit = nn.ClassNLLCriterion()
    method = optim.SGD(learning_rate=0.01, momentum=0.9)

    import math
    sync = math.gcd(math.gcd(warmup, iters), sync)  # windows must tile runs
    opt = DistriOptimizer(model, dataset, crit, mesh=mesh)
    opt.set_optim_method(method)
    opt.set_compute_precision("bfloat16")  # full mixed precision
    opt.set_sync_interval(sync)
    opt.set_end_when(max_iteration(warmup + iters))

    times = []

    def hook(state):
        if state["neval"] % sync == 0:  # device drained at sync points
            times.append(time.perf_counter())
        if state["neval"] == warmup:
            opt.metrics.reset()  # keep compile time out of the phase table

    opt.set_iteration_hook(hook)
    with _bench_telemetry(opt):
        opt.optimize()

    timed = times[warmup // sync - 1:]  # drop warmup/compile windows
    intervals = np.diff(timed)
    throughput = sync * batch_size / float(np.median(intervals))

    params = model.ensure_params()
    flops = _step_flops(model, crit, method, params, model._state,
                        batch_size, in_shape)
    return throughput, opt.metrics, flops


def bench_resnet50(batch_size: int = 128, warmup: int = 216,
                   iters: int = 648,  # 3 timed windows (median needs >2)
                   resident: bool = True, sync: int = 216, s2d: bool = True):
    # s2d: same model/math (parity-tested in test_conv_properties.py),
    # restated so the 7x7/s2 stem tiles the MXU; s2d=False re-measures
    # the plain stem. sync=216: the loss fetch every k steps is
    # monitoring cadence, not training semantics (production TPU loops
    # log every ~100-500 steps). Whether k still matters on a directly
    # attached chip is ROADMAP D5's to re-measure.
    from bigdl_tpu.models.resnet import ResNet50
    return _framework_throughput(ResNet50(class_num=1000, s2d_stem=s2d),
                                 (224, 224, 3), 1000, batch_size, warmup,
                                 iters, resident=resident, sync=sync)


def bench_lenet(batch_size: int = 512, warmup: int = 4, iters: int = 20,
                resident: bool = True):
    # the same loop at toy size: what the CPU tests drive, not a result
    from bigdl_tpu.models.lenet import LeNet5
    return _framework_throughput(LeNet5(10), (28, 28), 10, batch_size,
                                 warmup, iters, resident=resident)


def bench_attention():
    """Long-context secondary figures (stderr): Pallas flash attention vs
    XLA naive at 8k-16k tokens, and a small-transformer train step through
    the framework loop. The §5.7 long-context story, evidenced on the
    device the headline ran on."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.ops.attention_kernel import (flash_attention,
                                                naive_attention)
    B, H, D = 1, 8, 64
    key = jax.random.PRNGKey(0)

    def timed(fn, qkv, tag, t_len, n=20):
        # sync by scalar fetch (utils.profiling.device_sync's barrier):
        # the on-device sum is noise vs the attention itself and the
        # fetch is 4 bytes
        f = jax.jit(
            lambda q, k, v: jnp.sum(fn(q, k, v, True).astype(jnp.float32)))
        float(f(*qkv))  # compile + drain
        t0 = time.perf_counter()
        for _ in range(n):
            s = f(*qkv)
        float(s)
        dt = (time.perf_counter() - t0) / n
        # causal attention: 2 matmuls x 2*B*H*T^2*D flops, half masked
        fl = 2 * B * H * t_len * t_len * D * 2 / 2
        print(f"attention {tag} T={t_len}: {dt * 1e3:.1f} ms "
              f"({fl / dt / 1e12:.1f} TFLOP/s fwd)", file=sys.stderr)
        return dt

    def timed_bwd(qkv, t_len, n=10):
        """Fwd+bwd step time through the custom_vjp (Pallas both ways on
        TPU) — the training-path figure the r3 verdict asked for."""
        # all three cotangents, or XLA dead-code-eliminates the dk/dv
        # kernel and the 7-matmul FLOP count below over-reports
        f = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, True).astype(jnp.float32)),
            argnums=(0, 1, 2)))

        def drain(gs):
            return float(sum(jnp.sum(g.astype(jnp.float32)) for g in gs))

        drain(f(*qkv))  # compile + drain
        t0 = time.perf_counter()
        for _ in range(n):
            gs = f(*qkv)
        drain(gs)
        dt = (time.perf_counter() - t0) / n
        # fwd 2 matmuls + bwd 5 matmuls of the same shape, half masked
        fl = 7 * B * H * t_len * t_len * D * 2 / 2
        print(f"attention fwd+bwd T={t_len}: {dt * 1e3:.1f} ms "
              f"({fl / dt / 1e12:.1f} TFLOP/s)", file=sys.stderr)

    for t_len in (8192, 16384):
        qkv = [jax.random.normal(k, (B, H, t_len, D), jnp.bfloat16)
               for k in jax.random.split(key, 3)]
        ft = timed(flash_attention, qkv, "flash(pallas)", t_len)
        timed_bwd(qkv, t_len)
        # naive materializes the [T, T] score matrix — 0.5-2 GiB in bf16
        # at these lengths; keep it to 8k so the comparison fits HBM
        if t_len <= 8192:
            nt = timed(naive_attention, qkv, "naive(XLA)", t_len)
            print(f"  flash vs naive speedup: {nt / ft:.2f}x",
                  file=sys.stderr)

    # causal ring vs zigzag over the local mesh (multi-chip pods ride the
    # same code path over ICI): zigzag's cond-skipping of fully-masked
    # chunk pairs should approach 2x on causal workloads
    import numpy as _np
    from jax.sharding import Mesh as _Mesh
    from bigdl_tpu.parallel.sequence import (
        make_sequence_parallel_attention)
    n_dev = jax.device_count()
    if n_dev >= 2:
        smesh = _Mesh(_np.array(jax.devices()), ("seq",))
        # nearest multiple of 2*n_dev (zigzag needs T % 2n == 0)
        t_ring = max(1, 8192 // (2 * n_dev)) * 2 * n_dev
        qkv = [jax.random.normal(k, (B, H, t_ring, D), jnp.bfloat16)
               for k in jax.random.split(jax.random.PRNGKey(7), 3)]
        for scheme in ("ring", "zigzag"):
            fn = make_sequence_parallel_attention(smesh, scheme, "seq",
                                                  causal=True)
            f = jax.jit(lambda q, k, v: jnp.sum(
                fn(q, k, v).astype(jnp.float32)))
            float(f(*qkv))
            t0 = time.perf_counter()
            for _ in range(10):
                s = f(*qkv)
            float(s)
            dt = (time.perf_counter() - t0) / 10
            print(f"sp {scheme} causal T={t_ring} x{n_dev}dev: "
                  f"{dt * 1e3:.1f} ms", file=sys.stderr)

    # small-transformer train step through the REAL DistriOptimizer loop
    from bigdl_tpu.models.transformer import TransformerLM
    import bigdl_tpu.nn as nn_
    seq, vocab, bs = 2048, 1024, 8
    model = TransformerLM(vocab, embed_dim=512, n_layer=4, n_head=8)
    rs = np.random.RandomState(0)

    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.dataset import LocalDataSet
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.optim.trigger import max_iteration
    from bigdl_tpu.parallel.mesh import build_mesh, shard_batch

    mesh = build_mesh()
    toks = rs.randint(1, vocab + 1, (bs, seq + 1)).astype(np.int32)
    batch = MiniBatch(shard_batch(mesh, toks[:, :-1]),
                      shard_batch(mesh, toks[:, 1:]))
    opt = DistriOptimizer(model, LocalDataSet([batch]),
                          nn_.TimeDistributedCriterion(
                              nn_.ClassNLLCriterion()), mesh=mesh)
    opt.set_optim_method(optim.SGD(learning_rate=0.01, momentum=0.9))
    opt.set_compute_precision("bfloat16")
    opt.set_sync_interval(12)  # same monitoring-cadence rationale as the
    opt.set_end_when(max_iteration(48))  # resnet headline (see PERF.md)
    times = []
    opt.set_iteration_hook(
        lambda s: times.append(time.perf_counter())
        if s["neval"] % 12 == 0 else None)
    opt.optimize()
    dt = float(np.median(np.diff(times[1:]))) / 12
    print(f"transformer-LM train (T={seq}, 512d x 4L, flash): "
          f"{bs * seq / dt:.0f} tokens/sec", file=sys.stderr)


def bench_int8_serving():
    """Serving A/B (stderr): ResNet-50 inference throughput, bf16 vs
    weight-only int8 vs full int8, plus weight bytes — answers the
    whitepaper's 2x-int8-serving claim (docs/docs/whitepaper.md:192-196)
    with the TPU-honest result: compute stays bf16 (the r03 capture
    showed full int8 losing on convs); the int8 win is 4x weight
    memory/bandwidth, taken by the weight-only path."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models.resnet import ResNet
    from bigdl_tpu.nn.quantized import Quantizer

    rs = np.random.RandomState(0)
    if os.environ.get("BIGDL_TPU_SERVING_MODEL", "resnet50") == "lenet":
        # CPU smoke-test scale (full-int8 R50 convs compile for minutes
        # on the CPU backend); same code path, tiny model
        from bigdl_tpu.models.lenet import LeNet5
        model = LeNet5(10)
        in_shape = (28, 28)
    else:
        model = ResNet(class_num=1000, depth=50)
        in_shape = (224, 224, 3)
    model.ensure_params()
    variants = {
        "bf16": model,
        "weight-only int8": Quantizer.quantize(model, weight_only=True),
        "full int8": Quantizer.quantize(model),
    }
    bs = int(_env_num("BIGDL_TPU_SERVING_BATCH", int, 256))
    x = jnp.asarray(rs.rand(bs, *in_shape), jnp.bfloat16)

    for name, m in variants.items():
        m.evaluate()
        params = jax.tree_util.tree_map(
            lambda l: l if l.dtype == jnp.int8 or
            not jnp.issubdtype(l.dtype, jnp.floating)
            else l.astype(jnp.bfloat16) if name != "full int8" else l,
            m.ensure_params())
        from bigdl_tpu.nn.module import functional_apply

        @jax.jit
        def fwd(p, xx):
            out, _ = functional_apply(m, p, xx, training=False)
            return jnp.sum(out.astype(jnp.float32))

        float(fwd(params, x))   # compile + drain
        n = 10
        t0 = time.perf_counter()
        for _ in range(n):
            s = fwd(params, x)
        float(s)                # scalar fetch = completion barrier
        dt = (time.perf_counter() - t0) / n
        wbytes = sum(np.asarray(l).nbytes for l in
                     jax.tree_util.tree_leaves(params)
                     if hasattr(l, "nbytes"))
        print(f"serving {name}: {bs / dt:.1f} imgs/sec (b{bs}), "
              f"params {wbytes / 1e6:.2f} MB", file=sys.stderr)


def bench_input_pipeline(input_cost_ms: float, batch_size: int = 256,
                         segments: int = 40, seg_iters: int = 12,
                         workers: int = None):
    """Input-pipeline A/B: serial transformer chain vs the prefetching
    pipeline (dataset/prefetch.py), with a synthetic per-batch
    augmentation sleep of `input_cost_ms` standing in for a transformer
    chain slower than one device step. Runs an MNIST-shaped MLP through
    the REAL LocalOptimizer loop on whatever backend is active (designed
    to be meaningful on CPU — the overlap is host-side; the model is
    sized so a ~20 ms input cost is visible next to the step, which a
    CPU ResNet/LeNet step would bury). Prints ONE json line: serial and
    prefetched records/sec plus the speedup.

    `--input-cost-ms 0` measures pure pipeline overhead (acceptance bar:
    no regression vs the serial loop). Measurement: `segments` SHORT runs
    per mode, strictly alternated serial/prefetch, per-iteration wall
    times pooled per mode and reduced by median — machine-speed drift
    between runs (large on small shared hosts) then hits both modes
    equally instead of biasing whichever mode ran last."""
    import bigdl_tpu.nn as nn_
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.dataset import LocalDataSet
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.dataset.transformer import FuncTransformer
    from bigdl_tpu.optim.local_optimizer import LocalOptimizer
    from bigdl_tpu.optim.trigger import max_iteration

    if workers is None:
        # supply-rate matching: one worker delivers a batch every
        # `input_cost_ms`, the loop consumes one every ~device step —
        # size the pool to cover the cost with ~2x headroom, capped at
        # Engine.io_threads. A cheap chain gets ONE background thread
        # (still overlaps the generator/batching work) instead of an
        # idle pool whose wakeups are pure scheduler churn on small hosts.
        from bigdl_tpu.utils.engine import Engine
        io = int(Engine.config["io_threads"])
        workers = max(1, min(io, int(np.ceil(input_cost_ms / 5.0))))

    rs = np.random.RandomState(0)
    batches = [
        MiniBatch(rs.rand(batch_size, 28, 28).astype(np.float32),
                  (rs.randint(0, 10, batch_size) + 1).astype(np.int32))
        for _ in range(16)
    ]

    def mlp():
        return (nn_.Sequential().add(nn_.Reshape([784]))
                .add(nn_.Linear(784, 256)).add(nn_.Tanh())
                .add(nn_.Linear(256, 256)).add(nn_.Tanh())
                .add(nn_.Linear(256, 10)).add(nn_.LogSoftMax()))

    def augment(b):
        # stands in for decode/resize/jitter work per batch
        if input_cost_ms > 0:
            time.sleep(input_cost_ms / 1e3)
        return b

    def run(prefetch: bool, iters: int, warmup: int = 5):
        ds = LocalDataSet(list(batches)).transform(FuncTransformer(augment))
        opt = LocalOptimizer(mlp(), ds, nn_.ClassNLLCriterion(),
                             batch_size)
        opt.set_optim_method(optim.SGD(learning_rate=0.01, momentum=0.9))
        opt.set_end_when(max_iteration(warmup + iters))
        if prefetch:
            opt.set_prefetch(workers=workers)
        times = []
        opt.set_iteration_hook(lambda s: times.append(time.perf_counter()))
        with _bench_telemetry(opt):
            opt.optimize()
        return list(np.diff(times)[warmup:])

    run(False, 5)  # throwaway pair: compile + allocator warmup
    run(True, 5)
    ser, pair_ratios = [], []
    for _ in range(segments):
        s_seg = run(False, seg_iters)
        p_seg = run(True, seg_iters)
        ser += s_seg
        # per-pair ratio: adjacent segments see ~the same machine speed,
        # so slow host-speed drift cancels inside each pair
        pair_ratios.append(float(np.median(s_seg) / np.median(p_seg)))
    serial = batch_size / float(np.median(ser))
    speedup = float(np.median(pair_ratios))
    # derived, not directly pooled: the pair-ratio median is the drift-
    # robust estimator, so the prefetch rate is reported consistent with it
    prefetched = serial * speedup
    out = {
        "metric": "input_pipeline_ab",
        "input_cost_ms": input_cost_ms,
        "batch_size": batch_size,
        "workers": workers,
        "serial_records_per_sec": round(serial, 1),
        "prefetch_records_per_sec": round(prefetched, 1),
        "speedup": round(speedup, 3),
    }
    _emit(out)
    return out


def bench_serving_ab(clients: int = 8, segments: int = 20,
                     seg_requests: int = 64, max_batch: int = 32,
                     max_wait_ms: float = 2.0):
    """Serving A/B: closed-loop concurrent clients, single-sample serial
    forwards vs the micro-batching engine (bigdl_tpu/serving).

    Serial mode is the pre-engine `PredictionService` path: every request
    pays its own batch-1 jitted forward + fetch, so N concurrent callers
    queue N tiny executions on the device. Engine mode submits the same
    closed loop through `InferenceEngine`, which coalesces concurrent
    requests into padded micro-batches. Both modes run the SAME converted
    model and warmed executables; measurement uses the alternated
    pair-ratio estimator from docs/PERF.md (strictly alternated
    serial/engine segments, per-pair throughput ratios, median) so
    container machine-speed drift cancels inside each pair. Prints ONE
    json line: serial and engine requests/sec, the speedup, and the
    engine's p50/p95/p99 request latency."""
    import threading

    import jax.numpy as jnp

    import bigdl_tpu.nn as nn_
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.optim.predictor import LocalPredictor
    from bigdl_tpu.serving import InferenceEngine

    model = (nn_.Sequential().add(nn_.Reshape([784]))
             .add(nn_.Linear(784, 256)).add(nn_.Tanh())
             .add(nn_.Linear(256, 256)).add(nn_.Tanh())
             .add(nn_.Linear(256, 10)).add(nn_.LogSoftMax()))
    model.ensure_params()
    rs = np.random.RandomState(0)
    samples = [Sample(rs.rand(28, 28).astype(np.float32))
               for _ in range(64)]

    serial_pred = LocalPredictor(model, batch_size=max_batch)
    sp_params = serial_pred.model.ensure_params()
    sp_state = serial_pred.model._state

    def serial_one(s):
        y = serial_pred._forward(sp_params, sp_state,
                                 jnp.asarray(s.feature)[None])
        return np.asarray(y)[0]

    engine = InferenceEngine(model, max_batch_size=max_batch,
                             max_wait_ms=max_wait_ms)
    engine.warmup(samples[0])
    serial_one(samples[0])  # compile the batch-1 path too

    per_client = max(1, seg_requests // clients)

    def run_mode(fn):
        """One closed-loop segment: every client issues its requests
        back-to-back; returns requests/sec over the segment wall time."""
        barrier = threading.Barrier(clients + 1)

        def worker(k):
            barrier.wait()
            for i in range(per_client):
                fn(samples[(k * 31 + i) % len(samples)])

        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(clients)]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        return clients * per_client / (time.perf_counter() - t0)

    try:
        run_mode(serial_one)  # throwaway pair: allocator/scheduler warmup
        run_mode(lambda s: engine.predict(s, timeout=60.0))
        serial_rates, pair_ratios = [], []
        for _ in range(segments):
            s_rps = run_mode(serial_one)
            e_rps = run_mode(lambda s: engine.predict(s, timeout=60.0))
            serial_rates.append(s_rps)
            pair_ratios.append(e_rps / s_rps)
        stats = engine.stats()
    finally:
        engine.close()

    serial = float(np.median(serial_rates))
    speedup = float(np.median(pair_ratios))
    out = {
        "metric": "serving_ab",
        "clients": clients,
        "max_batch_size": max_batch,
        "max_wait_ms": max_wait_ms,
        "serial_rps": round(serial, 1),
        # derived from the drift-robust pair-ratio median, same policy as
        # the input-pipeline A/B
        "engine_rps": round(serial * speedup, 1),
        "speedup": round(speedup, 3),
        "engine_batch_size_p50": stats.get("batch_size_p50"),
        "engine_bucket_hit_rate": stats.get("bucket_hit_rate"),
    }
    for k in ("latency_ms_p50", "latency_ms_p95", "latency_ms_p99"):
        if k in stats:
            out[f"engine_{k}"] = stats[k]
    _emit(out)
    return out


def bench_generation_ab(clients: int = 8, segments: int = 4,
                        streams_per_client: int = 2,
                        max_new_tokens: int = 24, slots: int = None,
                        n_prompts: int = 16):
    """Generation A/B: closed-loop concurrent clients, one-request-at-a-
    time FULL-RECOMPUTE greedy decode (the O(L^2) serial path: every
    emitted token pays a whole padded-sequence forward, and concurrent
    callers serialize through one device) vs the continuous-batching
    `GenerationEngine` (prefill buckets + the O(1) per-slot KV decode
    cache + ONE fixed-shape decode step over all slots).

    Both modes run the SAME model and params. Serial uses one fixed
    [1, max_len] jitted full apply (one compile — the honest baseline);
    the engine is warmed. Measurement is the alternated pair-ratio
    estimator from docs/PERF.md (strictly alternated serial/engine
    segments, per-pair aggregate tokens/sec ratios, median) so container
    machine-speed drift cancels inside each pair.

    Before measuring, the drill verifies the PARITY contract: every
    prompt's continuous-batched token sequence must equal its serial
    full-recompute sequence exactly (`parity` in the output; the CLI
    exits nonzero on a break — the CI generation smoke leans on this).
    When BIGDL_TPU_TELEMETRY names a directory, the engine's stream
    (generation snapshots + kind=generate trace records) lands in
    `generate_<pid>.jsonl` for the `metrics_cli slo --check` gate.
    Prints ONE json line."""
    import threading

    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.observability import InMemorySink, Telemetry
    from bigdl_tpu.serving import (GenerationEngine,
                                   greedy_decode_reference)

    vocab, max_len = 256, 64
    model = TransformerLM(vocab, embed_dim=64, n_layer=2, n_head=4,
                          use_flash=False, max_len=max_len)
    model.ensure_params(jax.random.PRNGKey(0))
    params = model.ensure_params()
    rs = np.random.RandomState(0)
    prompts = [rs.randint(1, vocab + 1,
                          size=rs.randint(4, 17)).astype(np.int32)
               for _ in range(n_prompts)]
    slots = slots or max(8, clients)

    sinks = [InMemorySink()]
    tel_dir = os.environ.get("BIGDL_TPU_TELEMETRY")
    if tel_dir:
        from bigdl_tpu.observability import JsonlSink
        os.makedirs(tel_dir, exist_ok=True)
        sinks.append(JsonlSink(os.path.join(
            tel_dir, f"generate_{os.getpid()}.jsonl")))
    telemetry = Telemetry(*sinks, resources=False)

    engine = GenerationEngine(model, slots=slots, max_len=max_len,
                              max_new_tokens=max_new_tokens,
                              telemetry=telemetry)
    engine.warmup()
    # serial baseline: ONE fixed-shape compile shared by every request
    fwd = jax.jit(lambda p, t: model.apply(p, t, None))
    serial_lock = threading.Lock()

    def serial_one(prompt):
        # one-request-at-a-time: the pre-engine story — requests
        # serialize through the single device
        with serial_lock:
            return greedy_decode_reference(model, params, prompt,
                                           max_new_tokens,
                                           pad_to=max_len, fwd=fwd)

    def engine_one(prompt):
        return engine.generate(prompt).result(timeout=120.0)

    try:
        serial_one(prompts[0])  # compile the serial path
        # parity gate: continuous-batched greedy decode must reproduce
        # the serial sequences token-for-token, under real concurrency
        refs = [serial_one(p) for p in prompts]
        outs = [None] * len(prompts)

        def check(i):
            outs[i] = engine_one(prompts[i])

        threads = [threading.Thread(target=check, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        parity = outs == refs

        def run_mode(fn):
            barrier = threading.Barrier(clients + 1)
            counts = [0] * clients

            def worker(k):
                barrier.wait()
                for i in range(streams_per_client):
                    counts[k] += len(
                        fn(prompts[(k * 7 + i) % len(prompts)]))

            threads = [threading.Thread(target=worker, args=(k,))
                       for k in range(clients)]
            for t in threads:
                t.start()
            barrier.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            return sum(counts) / (time.perf_counter() - t0)

        run_mode(serial_one)  # throwaway pair: scheduler warmup
        run_mode(engine_one)
        serial_rates, pair_ratios = [], []
        for _ in range(segments):
            s_tps = run_mode(serial_one)
            e_tps = run_mode(engine_one)
            serial_rates.append(s_tps)
            pair_ratios.append(e_tps / s_tps)
        gen_stats = engine.generation_stats()
        compiles = engine.compile_count()
    finally:
        engine.close()
        telemetry.close()

    serial = float(np.median(serial_rates))
    speedup = float(np.median(pair_ratios))
    out = {
        "metric": "generation_ab",
        "clients": clients,
        "slots": slots,
        "max_new_tokens": max_new_tokens,
        "max_len": max_len,
        "serial_tokens_per_sec": round(serial, 1),
        # derived from the drift-robust pair-ratio median, same policy
        # as the serving/input-pipeline A/Bs
        "engine_tokens_per_sec": round(serial * speedup, 1),
        "speedup": round(speedup, 3),
        "parity": parity,
        "decode_occupancy": gen_stats.get("decode_occupancy"),
        "compile_count": compiles,
    }
    _emit(out)
    return out


def _loss_trajectory(model_fn, batches, fused: bool, iters: int,
                     force_pallas: bool = False, lr: float = 0.05):
    """One deterministic LocalOptimizer run (fixed init, fixed data);
    returns the per-iteration loss list. `fused` toggles BN+ReLU pattern
    fusion; `force_pallas` routes the fused tail through the Pallas
    kernels in interpreter mode (the parity gate's configuration)."""
    import jax

    import bigdl_tpu.nn as nn_
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.dataset import LocalDataSet
    from bigdl_tpu.nn import fusion
    from bigdl_tpu.ops import bn_relu_kernel
    from bigdl_tpu.optim.local_optimizer import LocalOptimizer
    from bigdl_tpu.optim.trigger import max_iteration

    prev_force = bn_relu_kernel.FORCE_PALLAS
    bn_relu_kernel.FORCE_PALLAS = force_pallas and fused
    try:
        with fusion.fusion_scope(fused):
            model = model_fn()
            model.ensure_params(jax.random.PRNGKey(0))
            opt = LocalOptimizer(model, LocalDataSet(list(batches)),
                                 nn_.ClassNLLCriterion(),
                                 batches[0].size())
            opt.set_optim_method(optim.SGD(learning_rate=lr, momentum=0.9))
            opt.set_end_when(max_iteration(iters))
            losses = []
            opt.set_iteration_hook(lambda s: losses.append(s["loss"]))
            opt.optimize()
        return losses
    finally:
        bn_relu_kernel.FORCE_PALLAS = prev_force


def bench_fusion_ab(segments: int = 10, seg_iters: int = 6,
                    batch_size: int = 16, parity_iters: int = 6):
    """Fusion A/B: pattern-fused BN+ReLU tails vs the unfused graph on
    the ResNet/CIFAR config, through the REAL LocalOptimizer loop.

    Gates the PARITY contract first (same pattern as the generation
    smoke), two legs per model (LeNet — no BN, fusion must be a no-op —
    and ResNet-8/CIFAR):
    (1) production routing: fused loss trajectories BIT-identical to
        the unfused graph (the inline tail is structurally the unfused
        ops);
    (2) kernel routing (Pallas custom_vjp FORCED, interpreter mode):
        step-0 loss bit-identical (fused forward is exact) and every
        step's |Δloss| <= 1e-6 (the fused backward's tiled partial
        reductions regroup sums at the last-ulp level).
    The CLI exits nonzero on a break.

    Then measures: per-step `bytes_accessed`/`flops` of the compiled
    fused vs unfused step executables (the PR 8 attribution stream —
    compile records off the CompiledFunction wrapper), and wall-clock
    step time via the alternated pair-ratio estimator from docs/PERF.md.
    The fused tail lowers to the same XLA-fused elementwise expressions
    on every backend (PR 37: on the v5e the kernel pair lost to them,
    docs/PERF.md "Fusion and overlap"), so the ratio measures only the
    pattern rewrite (~1.0x expected). Prints ONE json line."""
    import jax

    import bigdl_tpu.nn as nn_
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.dataset import LocalDataSet
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.models.lenet import LeNet5
    from bigdl_tpu.models.resnet import ResNet
    from bigdl_tpu.nn import fusion
    from bigdl_tpu.observability import InMemorySink, Telemetry
    from bigdl_tpu.optim.local_optimizer import LocalOptimizer
    from bigdl_tpu.optim.trigger import max_iteration

    rs = np.random.RandomState(0)
    resnet_batches = [
        MiniBatch(rs.rand(batch_size, 32, 32, 3).astype(np.float32),
                  (rs.randint(0, 10, batch_size) + 1).astype(np.int32))
        for _ in range(4)]
    lenet_batches = [
        MiniBatch(rs.rand(batch_size, 28, 28).astype(np.float32),
                  (rs.randint(0, 10, batch_size) + 1).astype(np.int32))
        for _ in range(4)]
    resnet_fn = lambda: ResNet(class_num=10, depth=8, data_set="cifar10")
    lenet_fn = lambda: LeNet5(10)

    # -- parity gate: exact leg (CPU routing) + bounded kernel leg ------
    parity = True
    for name, fn, bs in (("resnet8_cifar", resnet_fn, resnet_batches),
                         ("lenet", lenet_fn, lenet_batches)):
        ref = _loss_trajectory(fn, bs, fused=False, iters=parity_iters)
        got = _loss_trajectory(fn, bs, fused=True, iters=parity_iters)
        if ref != got:
            parity = False
            print(f"fusion parity BREAK on {name} (production routing, "
                  f"bit-identity): unfused {ref} vs fused {got}",
                  file=sys.stderr)
        krn = _loss_trajectory(fn, bs, fused=True, iters=parity_iters,
                               force_pallas=True)
        if krn[0] != ref[0] or any(abs(a - b) > 1e-6
                                   for a, b in zip(ref, krn)):
            parity = False
            print(f"fusion parity BREAK on {name} (interpret-mode "
                  f"kernels, step-0 exact + |d|<=1e-6): unfused {ref} "
                  f"vs fused(pallas) {krn}", file=sys.stderr)

    # -- attribution: bytes/flops of the compiled step, per mode --------
    def step_costs(fused):
        sink = InMemorySink()
        tel = Telemetry(sink, resources=False)
        with fusion.fusion_scope(fused):
            model = resnet_fn()
            model.ensure_params(jax.random.PRNGKey(0))
            opt = LocalOptimizer(model, LocalDataSet(list(resnet_batches)),
                                 nn_.ClassNLLCriterion(), batch_size)
            opt.set_optim_method(optim.SGD(learning_rate=0.05,
                                           momentum=0.9))
            opt.set_end_when(max_iteration(2))
            opt.set_telemetry(tel)
            opt.optimize()
        tel.close()
        rec = next((r for r in sink.records if r.get("type") == "compile"
                    and str(r.get("label", "")).startswith("local.step")),
                   {})
        return rec.get("bytes_accessed"), rec.get("flops")

    bytes_fused, flops_fused = step_costs(True)
    bytes_unfused, flops_unfused = step_costs(False)

    # -- throughput: alternated pair-ratio segments ---------------------
    def run_seg(fused):
        with fusion.fusion_scope(fused):
            model = resnet_fn()
            model.ensure_params(jax.random.PRNGKey(0))
            opt = LocalOptimizer(model, LocalDataSet(list(resnet_batches)),
                                 nn_.ClassNLLCriterion(), batch_size)
            opt.set_optim_method(optim.SGD(learning_rate=0.05,
                                           momentum=0.9))
            opt.set_end_when(max_iteration(2 + seg_iters))
            times = []
            opt.set_iteration_hook(
                lambda s: times.append(time.perf_counter()))
            opt.optimize()
        return list(np.diff(times)[2:])  # drop compile/warmup iterations

    speedup = None
    if segments > 0:
        run_seg(True)   # throwaway pair: allocator/compile warmup
        run_seg(False)
        pair_ratios = []
        for _ in range(segments):
            f_seg = run_seg(True)
            u_seg = run_seg(False)
            pair_ratios.append(float(np.median(u_seg) / np.median(f_seg)))
        speedup = float(np.median(pair_ratios))

    delta = None
    if bytes_fused and bytes_unfused:
        delta = round(1.0 - bytes_fused / bytes_unfused, 4)
    out = {
        "metric": "fusion_ab",
        "parity": parity,
        "batch_size": batch_size,
        "speedup": round(speedup, 3) if speedup is not None else None,
        "bytes_accessed_fused": bytes_fused,
        "bytes_accessed_unfused": bytes_unfused,
        "bytes_accessed_reduction": delta,
        "flops_fused": flops_fused,
        "flops_unfused": flops_unfused,
        "backend": __import__("jax").default_backend(),
        "cpu_guard": __import__("jax").default_backend() != "tpu",
    }
    _emit(out)
    return out


def bench_overlap_ab(segments: int = 6, seg_iters: int = 8,
                     batch_size: int = 64, bucket_kb: int = 64,
                     parity_iters: int = 6):
    """Overlap A/B: size-bucketed comm/compute-overlapped gradient
    exchange vs the single post-backward barrier reduction, through the
    REAL elastic DistriOptimizer loop on >= 2 (virtual) devices.

    Gates the PARITY contract first: bucketed and barrier exchanges must
    produce BIT-identical parameters at matched step counts (the elastic
    trajectory contract with bucketing on); exits nonzero on a break.
    Then the alternated pair-ratio estimator (docs/PERF.md) compares
    per-iteration step time. CPU guard: virtual devices share host
    cores, so the CPU ratio mostly reflects dispatch-chain overhead, not
    ICI overlap — the TPU capture is the real figure. Prints ONE json
    line with the ratio, bucket plan, and the compile budget (one
    accumulate executable per bucket layout)."""
    import jax

    import bigdl_tpu.nn as nn_
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.dataset import LocalDataSet
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.observability import InMemorySink, Telemetry
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.optim.trigger import max_iteration
    from bigdl_tpu.parallel.mesh import build_mesh

    n_dev = jax.device_count()
    if n_dev < 2:
        out = {"metric": "overlap_ab", "skipped": True,
               "reason": f"{n_dev} device(s); need >= 2 "
                         "(set --xla_force_host_platform_device_count)"}
        _emit(out)
        return out
    n_use = min(4, n_dev)
    rs = np.random.RandomState(0)
    batches = [
        MiniBatch(rs.rand(batch_size, 28, 28).astype(np.float32),
                  (rs.randint(0, 10, batch_size) + 1).astype(np.int32))
        for _ in range(4)]

    def run(bucketed, iters, telemetry=None):
        model = (nn_.Sequential().add(nn_.Reshape([784]))
                 .add(nn_.Linear(784, 256)).add(nn_.Tanh())
                 .add(nn_.Linear(256, 256)).add(nn_.Tanh())
                 .add(nn_.Linear(256, 10)).add(nn_.LogSoftMax()))
        model.ensure_params(jax.random.PRNGKey(0))
        opt = DistriOptimizer(model, LocalDataSet(list(batches)),
                              nn_.ClassNLLCriterion(),
                              mesh=build_mesh(data=n_use, model=1,
                                              devices=jax.devices()[:n_use]),
                              retry_times=0)
        opt.set_optim_method(optim.SGD(learning_rate=0.05, momentum=0.9))
        opt.set_end_when(max_iteration(iters))
        opt.set_elastic()
        if telemetry is not None:
            opt.set_telemetry(telemetry)
        if bucketed:
            opt.set_gradient_bucketing(bucket_mb=bucket_kb / 1024.0)
        times = []
        opt.set_iteration_hook(lambda s: times.append(time.perf_counter()))
        opt.optimize()
        return model, list(np.diff(times)[2:])

    # -- parity gate: bucketed == barrier, bitwise ----------------------
    sink = InMemorySink()
    tel = Telemetry(sink, resources=False)
    m_b, _ = run(True, parity_iters, telemetry=tel)
    tel.close()
    m_s, _ = run(False, parity_iters)
    import jax.tree_util as jtu
    parity = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jtu.tree_leaves(m_b.parameters()),
                        jtu.tree_leaves(m_s.parameters())))
    plan_ev = next((r for r in sink.records
                    if r.get("event") == "bucket_plan"), {})
    add_compiles = sum(1 for r in sink.records
                       if r.get("type") == "compile"
                       and r.get("label") == "distri.bucket_add")

    # -- throughput: alternated pair-ratio segments ---------------------
    pair_ratios = []
    for _ in range(segments):
        _, b_seg = run(True, 2 + seg_iters)
        _, s_seg = run(False, 2 + seg_iters)
        if b_seg and s_seg:
            pair_ratios.append(float(np.median(s_seg) / np.median(b_seg)))
    speedup = float(np.median(pair_ratios)) if pair_ratios else None  # None when parity-only (segments=0)

    out = {
        "metric": "overlap_ab",
        "devices": n_use,
        "parity": parity,
        "speedup": round(speedup, 3) if speedup else None,
        "n_buckets": plan_ev.get("n_buckets"),
        "n_layouts": plan_ev.get("n_layouts"),
        "bucket_kb": bucket_kb,
        "bucket_add_compiles": add_compiles,
        "backend": jax.default_backend(),
        "cpu_guard": jax.default_backend() != "tpu",
    }
    _emit(out)
    return out


def bench_chaos(crash_at: int = 8, iters: int = 16, ckpt_every: int = 4,
                batch_size: int = 64, n_samples: int = 1024,
                keep_last_n: int = 3):
    """Chaos drill: measure MTTR (mean time to recovery) of the training
    retry loop under a deterministic injected fault plan.

    Runs an MNIST-shaped MLP through the REAL `DistriOptimizer` loop with
    durable checkpointing every `ckpt_every` iterations, installs a
    `FaultInjector` that crashes `train.step` at iteration `crash_at`
    (transient class), and lets the resilience machinery recover: the
    retry policy backs off with jitter, reloads the newest VALID
    checkpoint, and resumes. MTTR is read from the telemetry stream
    itself — the wall-clock gap between the `fault_injected` event and
    the first post-fault `step` record — so the figure measures exactly
    what an operator's dashboard would show. Prints ONE json line:
    MTTR, retry count, lost iterations (re-trained since the reload
    point), and the final step count as the recovery proof."""
    import shutil
    import tempfile

    import bigdl_tpu.nn as nn_
    import bigdl_tpu.optim as optim
    from bigdl_tpu.observability import InMemorySink, Telemetry
    from bigdl_tpu.optim.optimizer import Optimizer
    from bigdl_tpu.optim.trigger import max_iteration, several_iteration
    from bigdl_tpu.resilience import FaultInjector, FaultSpec, RetryPolicy

    rs = np.random.RandomState(0)
    X = rs.rand(n_samples, 28, 28).astype(np.float32)
    Y = (rs.randint(0, 10, n_samples) + 1).astype(np.int32)
    model = (nn_.Sequential().add(nn_.Reshape([784]))
             .add(nn_.Linear(784, 128)).add(nn_.Tanh())
             .add(nn_.Linear(128, 10)).add(nn_.LogSoftMax()))
    sink = InMemorySink()
    telemetry = Telemetry(sink, resources=False)
    ckpt_dir = tempfile.mkdtemp(prefix="bigdl_tpu_chaos_")
    opt = Optimizer(model, (X, Y), nn_.ClassNLLCriterion(),
                    batch_size=batch_size, local=False,
                    retry_policy=RetryPolicy(max_retries=3,
                                             base_delay_s=0.05,
                                             seed=0, name="chaos"))
    opt.set_optim_method(optim.SGD(learning_rate=0.05, momentum=0.9))
    opt.set_end_when(max_iteration(iters))
    opt.set_checkpoint(ckpt_dir, several_iteration(ckpt_every),
                       keep_last_n=keep_last_n)
    opt.set_telemetry(telemetry)
    plan = FaultInjector(FaultSpec("train.step", at_hit=crash_at),
                         telemetry=telemetry)
    try:
        with plan:
            opt.optimize()
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    t_fault = next((r["time"] for r in sink.records
                    if r.get("event") == "fault_injected"), None)
    post = [r for r in sink.records
            if r.get("type") == "step" and t_fault is not None
            and r["time"] > t_fault]
    retries = [r for r in sink.records if r.get("event") == "retry"]
    final_step = int(opt.optim_method.state.get("neval", 0))
    # recovery = the loop trained a step again after the fault; "lost
    # work" = iterations re-trained because the reload point trails the
    # crash point
    recovered = bool(post) and final_step >= iters
    out = {
        "metric": "chaos_mttr",
        "fault_site": "train.step",
        "crash_at_iteration": crash_at,
        "recovered": recovered,
        "mttr_s": round(post[0]["time"] - t_fault, 4) if post else None,
        "retries": len(retries),
        "backoff_s": round(sum(r.get("delay_s", 0.0) for r in retries), 4),
        "lost_iterations": (crash_at - 1) - min(
            (int(r["step"]) for r in post), default=crash_at) + 1
        if post else None,
        "final_step": final_step,
        "checkpoint_every": ckpt_every,
    }
    _emit(out)
    return out


def bench_chaos_device_loss(lose_at: int = 5, rejoin_at: int = 12,
                            iters: int = 18, batch_size: int = 64,
                            n_samples: int = 512, sync: int = 2):
    """Elastic chaos drill: lose a worker mid-run, measure MTTR and the
    degraded-capacity throughput off the telemetry stream.

    Trains an MNIST-shaped MLP through the REAL `DistriOptimizer` with
    `set_elastic` over a 2-worker `SimulatedCluster` (first two local
    devices). A `FaultInjector` raises `mesh.device_loss` (losing
    worker1) at iteration `lose_at`; the elastic loop shrinks to the
    survivor, rolls back to the committed boundary, replays the
    interrupted batches, and keeps training degraded; at `rejoin_at` the
    lost worker heartbeats back and the loop grows at the next committed
    boundary. Recovery proof is the loss trajectory staying bit-identical
    to an uninterrupted run at matched sample counts (asserted in
    tests/test_elastic.py; here the run must simply finish). MTTR = the
    wall-clock gap between the `worker_lost` event and the first
    post-recovery `step` record; degraded throughput compares step
    records inside the shrink..grow window against the healthy ones.
    Prints ONE json line. Needs >= 2 local devices (CI forces 8 via
    XLA_FLAGS); otherwise reports `skipped`."""
    import jax

    import bigdl_tpu.nn as nn_
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.dataset import LocalDataSet
    from bigdl_tpu.dataset.transformer import SampleToMiniBatch
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.observability import InMemorySink, Telemetry
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.optim.trigger import max_iteration
    from bigdl_tpu.parallel.mesh import build_mesh
    from bigdl_tpu.resilience import (DeviceLossError, FaultInjector,
                                      FaultSpec, SimulatedCluster)

    if jax.device_count() < 2:
        out = {"metric": "chaos_device_loss", "skipped": True,
               "reason": f"{jax.device_count()} device(s); need >= 2 "
                         "(set --xla_force_host_platform_device_count)"}
        _emit(out)
        return out

    rs = np.random.RandomState(0)
    samples = [Sample(rs.rand(28, 28).astype(np.float32),
                      np.int32(rs.randint(0, 10) + 1))
               for _ in range(n_samples)]
    model = (nn_.Sequential().add(nn_.Reshape([784]))
             .add(nn_.Linear(784, 128)).add(nn_.Tanh())
             .add(nn_.Linear(128, 10)).add(nn_.LogSoftMax()))
    sink = InMemorySink()
    sinks = [sink]
    tel_dir = os.environ.get("BIGDL_TPU_TELEMETRY")
    if tel_dir:
        # the recovery stream on disk: `metrics_cli slo --check` replays
        # it as the CI gate (scripts/run_ci.sh) — the MTTR judgment and
        # the live monitor share one engine instead of ad-hoc JSON pokes
        from bigdl_tpu.observability import JsonlSink
        os.makedirs(tel_dir, exist_ok=True)
        sinks.append(JsonlSink(os.path.join(
            tel_dir, f"chaos_device_loss_{os.getpid()}.jsonl")))
    telemetry = Telemetry(*sinks, resources=False)
    cluster = SimulatedCluster(2, devices=jax.devices()[:2],
                               telemetry=telemetry)
    ds = LocalDataSet(samples).transform(
        SampleToMiniBatch(batch_size, drop_remainder=True))
    opt = DistriOptimizer(model, ds, nn_.ClassNLLCriterion(),
                          mesh=build_mesh(data=2, model=1,
                                          devices=jax.devices()[:2]),
                          retry_times=0)
    opt.set_optim_method(optim.SGD(learning_rate=0.05, momentum=0.9))
    opt.set_end_when(max_iteration(iters))
    opt.set_sync_interval(sync)
    opt.set_elastic(registry=cluster.registry)
    # bucketed exchange ON in the chaos drill: the recovery smoke gates
    # that bucketing preserves the elastic shrink/replay/grow contract
    opt.set_gradient_bucketing()
    opt.set_telemetry(telemetry)
    opt.set_iteration_hook(
        lambda s: cluster.restore("worker1")
        if s["neval"] == rejoin_at else None)
    plan = FaultInjector(
        FaultSpec("mesh.device_loss", at_hit=lose_at,
                  exc=lambda ctx: DeviceLossError(
                      "injected preemption", lost=("worker1",))),
        telemetry=telemetry)
    try:
        with plan:
            opt.optimize()
    finally:
        telemetry.close()

    t_lost = next((r["time"] for r in sink.records
                   if r.get("event") == "worker_lost"), None)
    t_grow = next((r["time"] for r in sink.records
                   if r.get("event") == "elastic_grow"), None)
    steps = [r for r in sink.records if r.get("type") == "step"]
    post = [r for r in steps if t_lost is not None and r["time"] > t_lost]
    degraded = [r for r in post
                if t_grow is None or r["time"] <= t_grow]
    healthy = [r for r in steps if r not in degraded]
    replays = [r for r in sink.records
               if r.get("event") == "elastic_replay"]

    def mean_tp(rs_):
        vals = [r["throughput"] for r in rs_
                if isinstance(r.get("throughput"), (int, float))]
        return float(np.mean(vals)) if vals else None

    tp_d, tp_h = mean_tp(degraded), mean_tp(healthy)
    final_step = int(opt.optim_method.state.get("neval", 0))
    out = {
        "metric": "chaos_device_loss",
        "fault_site": "mesh.device_loss",
        "lost_at_iteration": lose_at,
        "rejoin_at_iteration": rejoin_at,
        "recovered": bool(post) and final_step >= iters,
        "mttr_s": round(post[0]["time"] - t_lost, 4) if post else None,
        "replayed_batches": int(sum(r.get("batches", 0)
                                    for r in replays)),
        "grew_back": t_grow is not None,
        "degraded_throughput": round(tp_d, 1) if tp_d else None,
        "degraded_throughput_frac":
            round(tp_d / tp_h, 3) if tp_d and tp_h else None,
        "final_step": final_step,
    }
    _emit(out)
    return out


def bench_serve_fleet(replicas: int = 3, clients: int = 6,
                      requests_per_client: int = 40,
                      crash: bool = False, deadline_ms: float = 15_000.0,
                      maintain_every_s: float = 0.005):
    """Serving-fleet drill: closed-loop clients against a replicated
    `ServingFleet`; with `crash`, a `serve.replica_crash` fault plan
    kills one replica mid-traffic and the drill measures the recovery.

    Every client thread issues its requests back-to-back through
    `fleet.predict` with session affinity, while the main thread ticks
    `fleet.maintain()` (heartbeats + the chaos site). The crash plan
    targets replica1 on the second maintenance tick — after traffic is
    flowing — so the drill exercises the full drain path: in-flight
    grace, exactly-once re-route of queued work to survivors, and the
    router's transient re-route of requests the dead engine failed.

    Figures come off the telemetry stream itself (the operator's view):
    MTTR is the gap between the `worker_lost` event and the first
    subsequent status-ok `trace` record; degraded throughput compares
    completed-request rates in equal windows after vs before the loss.
    When BIGDL_TPU_TELEMETRY names a directory the stream also lands in
    `serve_fleet_<pid>.jsonl`, which `metrics_cli slo --check --mttr-s N`
    replays as the CI gate (scripts/run_ci.sh). Prints ONE json line:
    outcome tallies (every request must resolve — ok, deadline timeout,
    or ServingReroutedError), reroute count, MTTR, and the
    degraded-throughput fraction."""
    import threading
    from concurrent.futures import TimeoutError as FuturesTimeoutError

    import bigdl_tpu.nn as nn_
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.observability import InMemorySink, Telemetry
    from bigdl_tpu.resilience import FaultInjector, FaultSpec
    from bigdl_tpu.serving import (ServingFleet, ServingReroutedError,
                                   ServingTimeoutError)

    model = (nn_.Sequential().add(nn_.Reshape([784]))
             .add(nn_.Linear(784, 64)).add(nn_.Tanh())
             .add(nn_.Linear(64, 10)).add(nn_.LogSoftMax()))
    model.ensure_params()
    rs = np.random.RandomState(0)
    samples = [Sample(rs.rand(28, 28).astype(np.float32))
               for _ in range(32)]

    sink = InMemorySink()
    sinks = [sink]
    tel_dir = os.environ.get("BIGDL_TPU_TELEMETRY")
    if tel_dir:
        from bigdl_tpu.observability import JsonlSink
        os.makedirs(tel_dir, exist_ok=True)
        sinks.append(JsonlSink(os.path.join(
            tel_dir, f"serve_fleet_{os.getpid()}.jsonl")))
    telemetry = Telemetry(*sinks, resources=False)

    fleet = ServingFleet(
        model, n_replicas=replicas, warmup_sample=samples[0],
        telemetry=telemetry, drain_grace_s=0.5, lease_s=30.0,
        engine_kwargs={"max_batch_size": 8, "max_wait_ms": 1.0,
                       "queue_capacity": 256})
    counts = {"ok": 0, "timed_out": 0, "rerouted": 0, "other": 0}
    clock = threading.Lock()

    def worker(k, burst=4):
        # each client keeps a small submit window in flight (not one
        # blocking predict at a time) so the fleet carries real queue
        # depth — the crash then catches queued work, which is exactly
        # what the drain/re-route machinery exists for
        futs = []

        def collect():
            for fut in futs:
                try:
                    fut.result(timeout=60.0)
                    key = "ok"
                except ServingReroutedError:
                    key = "rerouted"
                except FuturesTimeoutError:
                    key = "timed_out"
                except ServingTimeoutError:
                    key = "timed_out"
                except Exception as e:
                    key = "other"
                    print(f"fleet request failed: {e!r}", file=sys.stderr)
                with clock:
                    counts[key] += 1
            futs.clear()

        for i in range(requests_per_client):
            s = samples[(k * 31 + i) % len(samples)]
            try:
                futs.append(fleet.submit(s, deadline_ms=deadline_ms,
                                         session=f"client{k}"))
            except Exception as e:
                print(f"fleet submit failed: {e!r}", file=sys.stderr)
                with clock:
                    counts["other"] += 1
            if len(futs) >= burst:
                collect()
        collect()

    total = clients * requests_per_client

    def _mid_traffic(ctx):
        # fire only while traffic is genuinely mid-flight (25%..75%
        # resolved): a crash before warm traffic proves nothing, and one
        # after the last request leaves no post-loss stream to measure
        # recovery on — the progress gate makes the drill timing-robust
        if ctx.get("replica") != "replica1":
            return False
        with clock:
            done = sum(counts.values())
        return total * 0.25 <= done < total * 0.75

    plan = FaultInjector(
        FaultSpec("serve.replica_crash", at_hit=1, when=_mid_traffic),
        telemetry=telemetry)
    threads = [threading.Thread(target=worker, args=(k,))
               for k in range(clients)]
    try:
        cm = plan if crash else contextlib.nullcontext()
        with cm:
            for t in threads:
                t.start()
            while any(t.is_alive() for t in threads):
                fleet.maintain()
                time.sleep(maintain_every_s)
            for t in threads:
                t.join()
            fleet.maintain()
    finally:
        fleet.close()
        telemetry.close()

    stats = fleet.stats()
    resolved = sum(counts.values())
    t_lost = next((r["time"] for r in sink.records
                   if r.get("event") == "worker_lost"), None)
    ok_times = sorted(r["time"] for r in sink.records
                      if r.get("type") == "trace"
                      and r.get("status") == "ok")
    mttr = None
    degraded_frac = None
    if t_lost is not None and ok_times:
        post = [t for t in ok_times if t > t_lost]
        mttr = round(post[0] - t_lost, 4) if post else None
        # equal windows either side of the loss: completed-request rate
        # after vs before — the operator's "how much service survived"
        w = min(1.0, t_lost - ok_times[0],
                (ok_times[-1] - t_lost) if post else 0.0)
        if w > 0:
            before = sum(1 for t in ok_times if t_lost - w <= t <= t_lost)
            after = sum(1 for t in ok_times if t_lost < t <= t_lost + w)
            if before:
                degraded_frac = round(after / before, 3)
    recovered = (resolved == total and counts["other"] == 0
                 and (not crash or (t_lost is not None
                                    and mttr is not None)))
    out = {
        "metric": "serve_fleet",
        "replicas": replicas,
        "clients": clients,
        "requests": total,
        "chaos_replica_loss": crash,
        **counts,
        "reroutes": stats.get("reroutes_total"),
        "drains": stats.get("drains_total"),
        "mttr_s": mttr,
        "degraded_throughput_frac": degraded_frac,
        "recovered": recovered,
    }
    _emit(out)
    return out


def bench_replay_invariance(replicas: int = 3, requests: int = 90,
                            sessions: int = 6, seed: int = 7,
                            deadline_ms: float = 60_000.0):
    """Replay-invariance drill (the CI gate behind `metrics_cli diff`):
    record a short fleet run into a workload file, embed a seeded
    chaos plan (kill one replica a third of the way in, restore it at
    two thirds), replay the file THREE times against fresh fleets —
    twice with the same seed, once perturbed — and check the
    SLO-replay invariance contract both ways: the same-seed pair must
    be stream-identical under `workload.diff.compare_streams`, and the
    perturbed replay must be reported divergent with a pointer.

    When BIGDL_TPU_TELEMETRY names a directory the three canonical
    streams land in `replay_invariance_{a,b,perturbed}_<pid>.jsonl`
    (plus the workload file itself), which scripts/run_ci.sh re-judges
    through `metrics_cli diff` and `metrics_cli slo --check` — the
    same verdict from the CLI an operator would use. Prints ONE json
    line; `recovered`-style gate: `invariant` AND
    `perturbation_detected` must both hold."""
    import bigdl_tpu.nn as nn_
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.observability import InMemorySink, Telemetry
    from bigdl_tpu.observability.slo import SloEngine, default_slos
    from bigdl_tpu.serving import ServingFleet
    from bigdl_tpu.workload import (ChaosAction, ChaosSchedule,
                                    VirtualClock, Workload,
                                    WorkloadRecorder, WorkloadReplayer,
                                    compare_streams)

    def build_model():
        m = (nn_.Sequential().add(nn_.Reshape([784]))
             .add(nn_.Linear(784, 32)).add(nn_.Tanh())
             .add(nn_.Linear(32, 10)).add(nn_.LogSoftMax()))
        m.ensure_params()
        return m

    rs = np.random.RandomState(0)
    samples = [Sample(rs.rand(28, 28).astype(np.float32))
               for _ in range(16)]
    tel_dir = os.environ.get("BIGDL_TPU_TELEMETRY")
    if tel_dir:
        os.makedirs(tel_dir, exist_ok=True)

    # --- phase 1: record a live run (with a mid-run kill+restore, so
    # the recorded traffic includes rerouting noise the recorder must
    # distill away) into a workload file
    recorder = WorkloadRecorder(name="ci_fleet_run", seed=seed)
    rec_tel = Telemetry(recorder, resources=False)
    fleet = ServingFleet(build_model(), n_replicas=replicas,
                         warmup_sample=samples[0], telemetry=rec_tel,
                         drain_grace_s=0.5, lease_s=30.0, seed=0,
                         engine_kwargs={"max_batch_size": 8,
                                        "max_wait_ms": 1.0,
                                        "queue_capacity": 256})
    try:
        futs = []
        for i in range(requests):
            futs.append(fleet.submit(samples[i % len(samples)],
                                     deadline_ms=deadline_ms,
                                     session=f"s{i % sessions}",
                                     idempotent=True))
            if i == requests // 3:
                fleet.fail("replica1", reason="recorded chaos kill")
            elif i == (2 * requests) // 3:
                fleet.restore("replica1")
        for f in futs:
            try:
                f.result(timeout=60.0)
            except Exception:
                pass  # outcomes are the REPLAY's to re-derive
    finally:
        fleet.close()
        rec_tel.close()
    # the seeded chaos plan: entry-boundary triggers (deterministic
    # under time compression), targets left to the schedule's rng so
    # the seed genuinely matters
    chaos_plan = [ChaosAction("kill", after_entries=requests // 3),
                  ChaosAction("restore",
                              after_entries=(2 * requests) // 3)]
    workload = recorder.workload(
        chaos=[a.to_dict() for a in chaos_plan])
    wl_path = os.path.join(tel_dir or ".",
                           f"replay_workload_{os.getpid()}.jsonl")
    workload.save(wl_path)
    workload = Workload.load(wl_path)  # replay what CI would replay

    # --- phase 2: three replays against fresh fleets
    def replay(replay_seed: int, tag: str):
        sink = InMemorySink()
        sinks = [sink]
        path = None
        if tel_dir:
            from bigdl_tpu.observability import JsonlSink
            path = os.path.join(
                tel_dir, f"replay_invariance_{tag}_{os.getpid()}.jsonl")
            sinks.append(JsonlSink(path, append=False))
        tel = Telemetry(*sinks, resources=False)
        SloEngine(default_slos(latency_p99_ms=deadline_ms),
                  emit_every_s=0.25).attach(tel)
        target = ServingFleet(build_model(), n_replicas=replicas,
                              warmup_sample=samples[0], telemetry=None,
                              drain_grace_s=0.5, lease_s=30.0, seed=0,
                              engine_kwargs={"max_batch_size": 8,
                                             "max_wait_ms": 1.0,
                                             "queue_capacity": 256})
        try:
            summary = WorkloadReplayer(
                target, workload,
                chaos=ChaosSchedule.from_dicts(workload.chaos,
                                               seed=replay_seed),
                seed=replay_seed, telemetry=tel, clock=VirtualClock(),
                progress_every=max(1, len(workload) // 5)).run()
        finally:
            target.close()
            tel.close()
        return sink.records, summary, path

    a_records, a_summary, a_path = replay(seed, "a")
    b_records, _, b_path = replay(seed, "b")
    p_records, _, p_path = replay(seed + 1, "perturbed")

    same = compare_streams(a_records, b_records)
    perturbed = compare_streams(a_records, p_records)
    out = {
        "metric": "replay_invariance",
        "workload_entries": len(workload),
        "replicas": replicas,
        "seed": seed,
        "chaos_fired": a_summary.get("chaos_fired"),
        "outcomes": {k: a_summary.get(k) for k in
                     ("ok", "errors", "timeouts", "shed")},
        "invariant": not same.divergent,
        "invariance_break": same.first,
        "perturbation_detected": perturbed.divergent,
        "perturbation_pointer": perturbed.first,
        "streams": [p for p in (a_path, b_path, p_path) if p],
        "workload_file": wl_path,
    }
    _emit(out)
    return out


def bench_baseline_configs():
    """One stderr line per remaining BASELINE.md config (the headline
    already covers ResNet-50): LeNet-5, Inception-v1, PTB LSTM, and
    Wide&Deep — the reference's five DistriOptimizerPerf-style targets,
    each through the real DistriOptimizer loop in bf16 mixed precision."""
    import bigdl_tpu.nn as nn_
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.dataset import LocalDataSet
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.optim.trigger import max_iteration
    from bigdl_tpu.parallel.mesh import build_mesh, shard_batch

    mesh = build_mesh()
    rs = np.random.RandomState(0)
    # sync: monitoring cadence (PERF.md). iters=48 gives 4 timed windows
    # after the dropped first diff; with only 2 timed windows a cold-cache
    # run was observed to report a contaminated median (13x low on
    # inception), so keep >=4
    sync, iters = 8, 48

    def run(name, model, crit, x, y):
        place = lambda v: [shard_batch(mesh, e) for e in v] \
            if isinstance(v, list) else shard_batch(mesh, v)
        batch = MiniBatch(place(x), place(y))
        n = batch.size()
        opt = DistriOptimizer(model, LocalDataSet([batch]), crit, mesh=mesh)
        opt.set_optim_method(optim.SGD(learning_rate=0.01, momentum=0.9))
        opt.set_compute_precision("bfloat16")
        opt.set_sync_interval(sync)
        opt.set_end_when(max_iteration(iters))
        times = []
        opt.set_iteration_hook(
            lambda s: times.append(time.perf_counter())
            if s["neval"] % sync == 0 else None)
        with _bench_telemetry(opt):
            opt.optimize()
        dt = float(np.median(np.diff(times)[1:])) / sync  # drop compile win
        print(f"{name}: {n / dt:.1f} records/sec", file=sys.stderr)

    from bigdl_tpu.models.lenet import LeNet5
    run("lenet train (b512)", LeNet5(10), nn_.ClassNLLCriterion(),
        rs.rand(512, 28, 28).astype(np.float32),
        rs.randint(1, 11, 512).astype(np.int32))

    from bigdl_tpu.models.inception import Inception_v1_NoAuxClassifier
    run("inception_v1 train (b64, s2d stem)", Inception_v1_NoAuxClassifier(1000, s2d_stem=True),
        nn_.ClassNLLCriterion(),
        rs.rand(64, 224, 224, 3).astype(np.float32),
        rs.randint(1, 1001, 64).astype(np.int32))

    from bigdl_tpu.models.rnn import PTBModel
    run("ptb_lstm train (b64, seq 20)", PTBModel(10001, 200, 10001),
        nn_.TimeDistributedCriterion(nn_.ClassNLLCriterion()),
        rs.randint(1, 10001, (64, 20)).astype(np.int32),
        rs.randint(1, 10001, (64, 20)).astype(np.int32))

    from bigdl_tpu.models.widedeep import WideAndDeep
    b = 1024
    run("wide_n_deep train (b1024)",
        WideAndDeep(2, wide_dim=100, embed_vocabs=(10, 10), embed_dim=4,
                    cont_dim=3),
        nn_.ClassNLLCriterion(),
        [rs.randint(0, 100, (b, 3)).astype(np.int32),
         np.ones((b, 3), np.float32),
         rs.randint(1, 10, (b, 2)).astype(np.int32),
         rs.rand(b, 3).astype(np.float32)],
        (rs.randint(0, 2, b) + 1).astype(np.int32))


def _env_num(name, cast, default):
    """Parse a numeric env knob; malformed values are logged and ignored —
    a bad knob must never forfeit the once-per-round artifact."""
    try:
        return cast(os.environ.get(name, default))
    except (TypeError, ValueError):
        print(f"ignoring malformed {name}={os.environ[name]!r}",
              file=sys.stderr)
        return default


def _repo_root() -> str:
    """Repo root from this file's location (bigdl_tpu/tools/ -> two up)."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _telemetry_dir() -> str:
    """Default `--telemetry` directory: under `chiprun_out/`, the one
    directory a chip run brings back (git-ignored)."""
    return os.path.join(_repo_root(), "chiprun_out", "telemetry")


def _device_tag() -> str:
    """`platform:device_kind xN` of the backend this process runs on —
    every result line names the device its numbers came from."""
    import jax
    devs = jax.devices()
    return f"{devs[0].platform}:{devs[0].device_kind} x{len(devs)}"


def _emit(out: dict):
    """Print one result as a JSON line on stdout, tagged with the device."""
    out["device"] = _device_tag()
    print(json.dumps(out), flush=True)


def _require_tpu():
    """The measuring entry points run on a TPU whose peak the cost table
    knows, or not at all: a number from another backend is not a device
    metric, and a chip without a peak would print a null MFU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench: no TPU: JAX found platform {dev.platform!r} "
            f"({dev.device_kind!r}); nothing is measured on it")
    if _peak_flops(dev.device_kind) is None:
        raise SystemExit(
            f"bench: no peak FLOP/s known for {dev.device_kind!r}; add it "
            "to observability/costs.py PEAK_BF16_FLOPS with its source")
    return dev


def _spawn_child(name: str, timeout_s: float):
    """Spawn `python -m bigdl_tpu.tools.bench_cli --secondary name` with the
    repo on PYTHONPATH and a hard timeout, forwarding the child's stderr
    (phase table / failure diagnostics) whether it finished or was killed.
    Returns the CompletedProcess; raises subprocess.TimeoutExpired on
    stall."""
    import subprocess
    cmd = [sys.executable, "-m", "bigdl_tpu.tools.bench_cli",
           "--secondary", name]
    # the package may not be pip-installed (driver runs repo-root
    # bench.py); make the child's -m lookup independent of cwd
    env = dict(os.environ)
    env["PYTHONPATH"] = _repo_root() + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    try:
        r = subprocess.run(cmd, timeout=timeout_s, capture_output=True,
                           text=True, env=env)
    except subprocess.TimeoutExpired as e:
        err = e.stderr or ""
        sys.stderr.write(err if isinstance(err, str)
                         else err.decode(errors="replace"))
        raise
    sys.stderr.write(r.stderr or "")
    return r


def _run_secondary(name: str, timeout_s: float) -> bool:
    """Run one secondary suite in a child process with a hard timeout, so
    a suite that hangs costs bounded wall-clock. One process holds the
    chip at a time: this parent never touches the backend, and each child
    has it to itself. Returns False when the child was killed at its time
    limit: a killed child may leave the chip held, so the caller stops
    instead of starting another."""
    import subprocess
    try:
        r = _spawn_child(name, timeout_s)
    except subprocess.TimeoutExpired:
        print(f"secondary '{name}' timed out after {timeout_s:.0f}s; "
              f"figures above are partial", file=sys.stderr)
        return False
    if r.returncode != 0:
        print(f"secondary '{name}' exited rc={r.returncode}",
              file=sys.stderr)
    return True


def _secondary_main(name: str):
    """Child-process entry for one suite. `resnet` is the headline child:
    it prints ONE json line on stdout ({throughput, flops, device_*,
    n_dev}; phase table on stderr) for the parent to assemble into the
    result line. Every suite here measures the chip, so each refuses to
    run without one."""
    logging.getLogger("bigdl_tpu.optim").setLevel(logging.WARNING)
    dev = _require_tpu()
    compile_cache.configure()
    import jax
    if name == "attention":
        bench_attention()
    elif name == "configs":
        bench_baseline_configs()
    elif name == "int8_serving":
        bench_int8_serving()
    elif name == "host_pipeline":
        # secondary figure: fresh host batches + H2D every step
        host_tp, _, _ = bench_resnet50(warmup=4, iters=8, resident=False)
        print(f"host-pipeline (fresh H2D per step): "
              f"{host_tp / jax.device_count():.1f} imgs/sec/chip",
              file=sys.stderr)
    elif name == "resnet":
        bs = 128
        thr, metrics, flops = bench_resnet50(batch_size=bs)
        print(metrics.summary(), file=sys.stderr)
        print(json.dumps({
            "throughput": thr, "flops": flops, "batch_size": bs,
            "device_platform": dev.platform, "device_kind": dev.device_kind,
            "n_dev": jax.device_count(),
        }), flush=True)
    else:
        raise SystemExit(f"unknown secondary {name!r}")


def _headline_child(name: str, timeout_s: float):
    """Run the headline child and parse its json line. Raises on timeout,
    nonzero exit, or missing output."""
    r = _spawn_child(name, timeout_s)
    if r.returncode != 0:
        raise RuntimeError(f"headline child '{name}' rc={r.returncode}: "
                           f"{(r.stderr or '').strip()[-200:]}")
    lines = [l for l in (r.stdout or "").splitlines() if l.strip()]
    if not lines:
        raise RuntimeError(f"headline child '{name}' produced no output")
    return json.loads(lines[-1])


def main():
    # --telemetry[=DIR]: record the structured observability stream for
    # every suite this bench runs — per-process JSONL step records plus a
    # Chrome/Perfetto host trace under DIR (default: telemetry/ inside the
    # bench-records dir). Implemented as an env var so the watchdogged
    # child processes inherit it.
    argv = []
    input_cost_ms = None
    serve = False
    serve_clients = 8
    chaos = False
    chaos_crash_at = 8
    device_loss = False
    serve_fleet = False
    replica_loss = False
    replay_invariance = False
    generate = False
    generate_clients = 8
    fusion_ab = False
    overlap_ab = False
    ab_segments = None  # --parity-only sets 0
    it = iter(sys.argv[1:])
    for a in it:
        if a == "--telemetry":
            os.environ["BIGDL_TPU_TELEMETRY"] = _telemetry_dir()
        elif a.startswith("--telemetry="):
            os.environ["BIGDL_TPU_TELEMETRY"] = a.split("=", 1)[1]
        elif a == "--attribution":
            # implies --telemetry (needs the JSONL stream) and makes every
            # telemetry-wired run print its attribution report on stderr;
            # env-var passthrough so watchdogged children inherit it
            os.environ["BIGDL_TPU_ATTRIBUTION"] = "1"
            os.environ.setdefault("BIGDL_TPU_TELEMETRY", _telemetry_dir())
        elif a.startswith("--input-cost-ms="):
            input_cost_ms = float(a.split("=", 1)[1])
        elif a == "--input-cost-ms":
            input_cost_ms = float(next(it, "0"))
        elif a == "--serve":
            serve = True
        elif a.startswith("--serve-clients="):
            serve = True
            serve_clients = int(a.split("=", 1)[1])
        elif a == "--serve-clients":
            serve = True
            serve_clients = int(next(it, "8"))
        elif a == "--chaos":
            chaos = True
        elif a.startswith("--chaos-crash-at="):
            chaos = True
            chaos_crash_at = int(a.split("=", 1)[1])
        elif a == "--device-loss":
            chaos = True  # the flag alone must run the drill, never be
            device_loss = True  # silently swallowed by the headline path
        elif a == "--serve-fleet":
            serve_fleet = True
        elif a == "--replay-invariance":
            replay_invariance = True
        elif a == "--generate":
            generate = True
        elif a.startswith("--generate-clients="):
            generate = True
            generate_clients = int(a.split("=", 1)[1])
        elif a == "--generate-clients":
            generate = True
            generate_clients = int(next(it, "8"))
        elif a == "--replica-loss":
            chaos = True  # same policy as --device-loss: the flag alone
            replica_loss = True  # must run the drill
        elif a == "--fusion":
            fusion_ab = True
        elif a == "--overlap":
            overlap_ab = True
        elif a == "--parity-only":
            # CI mode: run the bit-identity/bounded parity gates and the
            # attribution A/B but skip the wall-clock segments — on CPU
            # the throughput ratio is documented as meaningless anyway
            ab_segments = 0
        else:
            argv.append(a)
    if fusion_ab:
        # fusion A/B: pattern-fused BN+ReLU tails vs the unfused graph,
        # WITH the interpret-mode trajectory parity gate (exits nonzero
        # on a break — the CI fusion smoke); one json line on stdout,
        # see docs/PERF.md "Fusion and overlap"
        logging.getLogger("bigdl_tpu.optim").setLevel(logging.ERROR)
        compile_cache.configure()
        out = bench_fusion_ab(**({} if ab_segments is None
                                 else {"segments": ab_segments}))
        if not out.get("parity"):
            raise SystemExit(1)
        return
    if overlap_ab:
        # overlap A/B: bucketed vs barrier gradient exchange through the
        # elastic loop, WITH the bitwise params-parity gate (exits
        # nonzero on a break); one json line on stdout
        logging.getLogger("bigdl_tpu.optim").setLevel(logging.ERROR)
        logging.getLogger("bigdl_tpu.resilience").setLevel(logging.ERROR)
        compile_cache.configure()
        out = bench_overlap_ab(**({} if ab_segments is None
                                  else {"segments": ab_segments}))
        if not (out.get("parity") or out.get("skipped")):
            raise SystemExit(1)
        return
    if generate:
        # generation A/B: serial full-recompute greedy decode vs the
        # continuous-batching engine, WITH the token-parity gate (exits
        # nonzero on a parity break — the CI generation smoke); one json
        # line on stdout, see docs/PERF.md "Generation"
        logging.getLogger("bigdl_tpu.optim").setLevel(logging.ERROR)
        logging.getLogger("bigdl_tpu.serving").setLevel(logging.ERROR)
        compile_cache.configure()
        out = bench_generation_ab(clients=generate_clients)
        if not out.get("parity"):
            raise SystemExit(1)
        return
    if replay_invariance:
        # SLO-replay invariance drill: record a short fleet run, embed
        # a seeded kill/restore chaos plan, replay it three times
        # (same seed twice, perturbed once) and gate on the contract:
        # same workload + same seed => identical canonical stream;
        # perturbed seed => divergent with a first-divergence pointer.
        # The streams land in BIGDL_TPU_TELEMETRY for the metrics_cli
        # diff / slo --check re-judgment in scripts/run_ci.sh.
        logging.getLogger("bigdl_tpu.optim").setLevel(logging.ERROR)
        logging.getLogger("bigdl_tpu.serving").setLevel(logging.ERROR)
        logging.getLogger("bigdl_tpu.resilience").setLevel(logging.ERROR)
        logging.getLogger("bigdl_tpu.workload").setLevel(logging.ERROR)
        compile_cache.configure()
        out = bench_replay_invariance()
        if not (out.get("invariant") and out.get("perturbation_detected")):
            raise SystemExit(1)
        return
    if serve_fleet or replica_loss:
        # serving-fleet drill: closed-loop clients over N replicas;
        # with --chaos --replica-loss an injected serve.replica_crash
        # drains one replica mid-traffic and the drill measures reroute
        # count, recovery MTTR, and degraded throughput off the
        # telemetry stream (CI smoke gate: nonzero exit on a failed
        # recovery; the stream itself gates through metrics_cli slo)
        logging.getLogger("bigdl_tpu.optim").setLevel(logging.ERROR)
        logging.getLogger("bigdl_tpu.serving").setLevel(logging.ERROR)
        logging.getLogger("bigdl_tpu.resilience").setLevel(logging.ERROR)
        compile_cache.configure()
        out = bench_serve_fleet(crash=chaos and replica_loss)
        if not out.get("recovered"):
            raise SystemExit(1)
        return
    if chaos and device_loss:
        # elastic chaos drill: injected device loss -> shrink -> replay
        # -> grow; MTTR + degraded throughput off the telemetry stream
        # (CI smoke gate: nonzero exit when recovery fails)
        logging.getLogger("bigdl_tpu.optim").setLevel(logging.ERROR)
        logging.getLogger("bigdl_tpu.resilience").setLevel(logging.ERROR)
        compile_cache.configure()
        out = bench_chaos_device_loss()
        if not (out.get("recovered") or out.get("skipped")):
            raise SystemExit(1)
        return
    if chaos:
        # chaos drill: deterministic injected fault -> retry/reload ->
        # MTTR from the telemetry stream; measurable off-TPU; one json
        # line on stdout, see docs/resilience.md
        logging.getLogger("bigdl_tpu.optim").setLevel(logging.ERROR)
        logging.getLogger("bigdl_tpu.resilience").setLevel(logging.ERROR)
        compile_cache.configure()
        bench_chaos(crash_at=chaos_crash_at)
        return
    if serve:
        # serving A/B (closed-loop concurrent clients, serial batch-1 vs
        # micro-batching engine) — measurable off-TPU; one json line on
        # stdout, see docs/PERF.md "Serving"
        logging.getLogger("bigdl_tpu.optim").setLevel(logging.WARNING)
        compile_cache.configure()
        bench_serving_ab(clients=serve_clients)
        return
    if input_cost_ms is not None:
        # standalone input-pipeline A/B (serial vs prefetch, synthetic
        # per-batch augmentation sleep) — measurable off-TPU; one json
        # line on stdout, see docs/PERF.md "Input pipeline"
        logging.getLogger("bigdl_tpu.optim").setLevel(logging.WARNING)
        compile_cache.configure()
        bench_input_pipeline(input_cost_ms)
        return
    if len(argv) >= 2 and argv[0] == "--secondary":
        _secondary_main(argv[1])
        return
    logging.getLogger("bigdl_tpu.optim").setLevel(logging.WARNING)
    # one process holds the chip at a time: the headline and every
    # secondary run in children and this parent never touches the
    # backend. A child that finds no TPU, or fails for any other reason,
    # ends the bench with its error and a non-zero exit; no metric line
    # is printed for a run that measured nothing.
    budget = _env_num("BIGDL_TPU_HEADLINE_TIMEOUT", float, 1500.0)
    try:
        info = _headline_child("resnet", budget)
    except Exception as e:
        raise SystemExit(f"bench: headline failed, nothing measured: {e}")
    throughput, flops = info["throughput"], info["flops"]
    # single source of truth: the child reports the batch size it actually
    # ran, so parent-side MFU math can't drift from child defaults
    batch_size = info["batch_size"]
    dev_platform, dev_kind = info["device_platform"], info["device_kind"]
    n_dev = info["n_dev"]
    baseline = 55.0  # BigDL-era ResNet-50 imgs/sec on one Xeon node

    per_chip = throughput / n_dev
    # child already forwarded the phase table on stderr; MFU -> stderr,
    # headline JSON line alone on stdout
    achieved = flops * throughput / batch_size  # whole-mesh FLOP/s
    peak = _peak_flops(dev_kind)  # the child refused an unknown chip
    mfu = achieved / (peak * n_dev)
    print(f"model flops/step (XLA cost model): {flops:.3e}  "
          f"achieved: {achieved / 1e12:.1f} TFLOP/s over {n_dev} "
          f"device(s)", file=sys.stderr)
    print(f"MFU vs {peak * n_dev / 1e12:.0f} TFLOP/s mesh peak "
          f"bf16: {mfu:.1%}", file=sys.stderr)

    # headline FIRST: if a driver kills the process mid-secondaries the
    # result is already on stdout
    print(json.dumps({
        "metric": "resnet50_train_imgs_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "imgs/sec",
        "vs_baseline": round(per_chip / baseline, 2),
        "baseline": baseline,  # denominator, imgs/sec
        "mfu": round(mfu, 4),
        "device": f"{dev_platform}:{dev_kind} x{n_dev}",
    }), flush=True)

    if not os.environ.get("BIGDL_TPU_BENCH_FAST"):
        # host-pipeline figure, long-context attention + transformer LM,
        # then the remaining BASELINE.md configs
        sec_budget = _env_num("BIGDL_TPU_SECONDARY_TIMEOUT", float, 900.0)
        for name in ("host_pipeline", "attention", "configs",
                     "int8_serving"):
            if not _run_secondary(name, sec_budget):
                break


if __name__ == "__main__":
    main()
