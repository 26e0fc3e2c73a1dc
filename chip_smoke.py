"""chip_smoke.py: the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py

One process, no children. Drives the main paths once through the entry
points a user calls, at the full width of the models, with random
weights made from a seed:

  device   JAX found a TPU whose kind the peak table knows
  train    ResNet-50 224x224, 128 per chip, bf16 compute over f32
           masters, through DistriOptimizer over build_mesh(), BN+ReLU
           pattern fusion at its default; the step that ran holds the
           Mosaic kernels `bn_relu`'s routing implies (none since PR 37),
           the loss is finite and falls; on several chips the step
           all-reduces gradients and gathers no batch, and a second run
           splits the mesh data=2 x model=2
  kernels  the BN+ReLU Mosaic pair, called by name, against its
           reference expressions;
           flash attention fwd+bwd against naive attention; a
           TransformerLM train step through optim.Optimizer with the
           flash kernels in it; on several chips ring and zigzag
           sequence parallelism against single-device flash
  serve    GenerationEngine (flash prefill) answers prompts of mixed
           length with the requested token counts and compiles nothing
           after warm-up
  barrier  whether block_until_ready waits for the device

It exits non-zero at the first phase that fails, and when JAX finds no
TPU. The last line of standard output is then the only result:

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}

Times are printed for information (compile seconds show whether the
compile cache was warm); they are not a claim. Nothing here is tuned.
"""

import json
import logging
import re
import sys
import time
from importlib.metadata import version

import numpy as np

import jax
import jax.numpy as jnp

# Largest error accepted between a kernel and its reference, as a share
# of the reference's largest magnitude. The kernels accumulate in f32 and
# round once to bf16 (8 mantissa bits: 2**-8 = 0.4% per rounding); the
# gradients pass through two such roundings and a bf16 cotangent. 2% is
# several roundings wide and far below what a wrong mask, a dropped block
# or a mis-scaled softmax would produce (tens of percent).
BF16_TOL = 2e-2

PER_CHIP_BATCH = 128
TRAIN_STEPS = 30
LM_VOCAB, LM_SEQ, LM_BATCH, LM_STEPS = 1024, 2048, 8, 4
#: ResNet-50's 33 BatchNorm+ReLU pairs that the containers collapse into
#: `bn_relu` (the stem and two per bottleneck block), as (rows an image,
#: channels, how many)
RESNET50_TAILS = ((112 * 112, 64, 1), (56 * 56, 64, 6), (56 * 56, 128, 1),
                  (28 * 28, 128, 7), (28 * 28, 256, 1), (14 * 14, 256, 11),
                  (14 * 14, 512, 1), (7 * 7, 512, 5))


def say(msg=""):
    print(msg, flush=True)


def check(cond, what):
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")
    say(f"  ok: {what}")


def rel_err(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def hlo_ops(compiled):
    """(Mosaic kernel count, {collective: count}, all-gather result
    shapes) of a compiled executable, read from its HLO text."""
    txt = compiled.as_text()
    ops = {}
    for op in re.findall(r"\s(all-gather|all-reduce|reduce-scatter|all-to-all"
                         r"|collective-permute)(?:-start)?\(", txt):
        ops[op] = ops.get(op, 0) + 1
    gathered = [tuple(int(d) for d in dims.split(",") if d)
                for dims in re.findall(
                    r"= \(?\w+\[([\d,]*)\][^=]*? all-gather(?:-start)?\(", txt)]
    return txt.count('custom_call_target="tpu_custom_call"'), ops, gathered


# --------------------------------------------------------------- device

def phase_device():
    say("== device")
    devs = jax.devices()
    dev = devs[0]
    say(f"  platform={dev.platform} device_kind={dev.device_kind!r} "
        f"count={len(devs)} jax={jax.__version__} "
        f"jaxlib={version('jaxlib')} libtpu={version('libtpu')}")
    if dev.platform != "tpu":
        # with JAX_PLATFORMS unset JAX itself drops to the CPU with only
        # a warning when libtpu cannot open the chip
        raise SystemExit(f"chip_smoke: no TPU: JAX found platform "
                         f"{dev.platform!r} ({dev.device_kind!r})")
    from bigdl_tpu.observability.costs import peak_flops
    from bigdl_tpu.utils import compile_cache
    peak = peak_flops(dev.device_kind)
    check(peak is not None,
          f"peak table knows {dev.device_kind!r}: {peak} FLOP/s bf16")
    say(f"  compile cache: {compile_cache.configure()}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------- train

def train_resnet50(mesh, steps):
    """`steps` DistriOptimizer steps of ResNet-50 on one fixed synthetic
    batch resident on the mesh; returns (optimizer, losses, telemetry
    sink, placed input)."""
    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.dataset import LocalDataSet
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.models.resnet import ResNet50
    from bigdl_tpu.observability import InMemorySink, Telemetry
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.optim.trigger import max_iteration
    from bigdl_tpu.parallel.mesh import shard_batch

    batch = PER_CHIP_BATCH * mesh.shape["data"]
    rs = np.random.RandomState(0)
    x = shard_batch(mesh, rs.rand(batch, 224, 224, 3).astype(np.float32))
    y = shard_batch(mesh, (rs.randint(0, 1000, size=batch) + 1)
                    .astype(np.int32))
    model = ResNet50(class_num=1000, s2d_stem=True)
    model.ensure_params(jax.random.PRNGKey(0))
    sink = InMemorySink()
    opt = DistriOptimizer(model, LocalDataSet([MiniBatch(x, y)]),
                          nn.ClassNLLCriterion(), mesh=mesh)
    opt.set_optim_method(optim.SGD(learning_rate=0.01, momentum=0.9))
    opt.set_compute_precision("bfloat16")
    opt.set_telemetry(Telemetry(sink))
    opt.set_end_when(max_iteration(steps))
    losses = []
    opt.set_iteration_hook(lambda state: losses.append(state["loss"]))
    opt.optimize()
    return opt, losses, sink, x


def report_run(tag, opt, losses, sink, steps):
    compiles = [r for r in sink.records if r["type"] == "compile"]
    steady = [r["step_time_s"] for r in sink.steps()[2:]]
    say(f"  {tag}: lower {sum(r['lower_s'] for r in compiles):.1f} s, "
        f"compile {sum(r['compile_s'] for r in compiles):.1f} s, "
        f"median step {np.median(steady) * 1e3:.1f} ms "
        f"(for information)")
    say(f"  {tag}: loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    check(len(losses) == steps and np.all(np.isfinite(losses)),
          f"{tag}: {steps} steps, loss finite on every one")
    check(losses[-1] < losses[0], f"{tag}: loss fell on the fixed batch")
    check(len(compiles) == 1 and opt._step_fn.last_info is not None,
          f"{tag}: one compile for the whole run, and the last step ran "
          f"that executable (no plain-jit fallback)")
    return hlo_ops(opt._step_fn.executables()[0])


def device_peaks():
    """Peak bytes each device has held: what the allocator had in use
    plus what programs reserved for their temporaries (the v5e reports
    the two apart, and a train step's gigabytes are in the second)."""
    stats = [d.memory_stats() for d in jax.devices()]
    return [s["peak_bytes_in_use"] + s["peak_bytes_reserved"] for s in stats]


def resnet50_fused_pairs(batch):
    """How many of ResNet-50's 33 BN+ReLU tails `bn_relu` sends to the
    Mosaic pair at `batch` images: asked of the router itself, shape by
    shape. Each is one forward and one backward kernel in the step."""
    from bigdl_tpu.ops import bn_relu_kernel as bk
    pairs = 0
    for rows, c, n in RESNET50_TAILS:
        coef = jax.ShapeDtypeStruct((c,), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda x, s, b: bk.bn_relu(x, s, b, True, jnp.bfloat16))(
                jax.ShapeDtypeStruct((batch * rows, c), jnp.float32),
                coef, coef)
        pairs += n * bk.count_fused_calls(jaxpr)
    return pairs


def phase_train(n_dev):
    from bigdl_tpu.nn import fusion
    from bigdl_tpu.parallel.mesh import build_mesh
    say("== train: ResNet-50 224x224 bf16 through DistriOptimizer")
    check(fusion.fusion_enabled(),
          "BN+ReLU pattern fusion is at its default (on): the 33 tails "
          "collapse into bn_relu")
    pairs = resnet50_fused_pairs(PER_CHIP_BATCH)
    say(f"  bn_relu sends {pairs} of the {sum(t[2] for t in RESNET50_TAILS)}"
        f" tails to the Mosaic pair at {PER_CHIP_BATCH} images a chip, "
        f"and inlines the rest for XLA to fuse")
    layouts = [build_mesh()]
    if n_dev >= 4 and n_dev % 2 == 0:
        layouts.append(build_mesh(data=n_dev // 2, model=2))
    for mesh in layouts:
        tag = "x".join(f"{k}={v}" for k, v in mesh.shape.items())
        steps = TRAIN_STEPS if mesh is layouts[0] else TRAIN_STEPS // 3
        opt, losses, sink, x = train_resnet50(mesh, steps)
        mosaic, ops, gathered = report_run(tag, opt, losses, sink, steps)
        check(mosaic == 2 * pairs,
              f"{tag}: {mosaic} Mosaic kernels in the compiled step (a "
              f"forward and a backward one for each of the {pairs} tails "
              f"bn_relu keeps)")
        peaks = device_peaks()
        say(f"  {tag}: peak device memory "
            f"{[round(p / 2 ** 30, 2) for p in peaks]} GiB")
        if n_dev == 1:
            continue
        batch = x.shape[0]
        shards = {s.device.id: s.data.shape for s in x.addressable_shards}
        say(f"  {tag}: input shards {shards}")
        check(len(shards) == n_dev and all(
                  s[0] == PER_CHIP_BATCH for s in shards.values()),
              f"{tag}: every device holds its {PER_CHIP_BATCH} rows of "
              f"the batch")
        check(max(peaks) < 2 * min(peaks),
              f"{tag}: memory is of the same order on every device")
        say(f"  {tag}: collectives in the step {ops}")
        check(ops.get("all-reduce", 0) > 0,
              f"{tag}: the step all-reduces gradients")
        # a partitioner that cannot split a kernel replicates it: it
        # gathers the whole batch of activations onto every device first
        check(not [g for g in gathered if len(g) >= 2 and g[0] == batch],
              f"{tag}: no all-gather rebuilds the global batch of {batch} "
              f"around the Pallas calls")
        if mesh.shape["model"] == 1:
            check(not gathered,
                  f"{tag}: pure data parallelism gathers nothing at all")


# -------------------------------------------------------------- kernels

def kernel_parity():
    from bigdl_tpu.ops import bn_relu_kernel as bk
    from bigdl_tpu.ops.attention_kernel import (flash_attention,
                                                naive_attention)
    ks = jax.random.split(jax.random.PRNGKey(1), 8)

    # BN+ReLU tail at one ResNet-50 tail shape, bf16 out and cotangent
    x = jax.random.normal(ks[0], (16, 56, 56, 64), jnp.float32)
    scale = jax.random.normal(ks[1], (64,), jnp.float32)
    shift = jax.random.normal(ks[2], (64,), jnp.float32)
    g = jax.random.normal(ks[3], x.shape, jnp.bfloat16)
    y, vjp = jax.vjp(
        lambda *a: bk.bn_relu_pallas(*a, True, jnp.dtype(jnp.bfloat16)),
        x, scale, shift)
    want_y = jax.jit(bk._reference_forward, static_argnums=(3, 4))(
        x, scale, shift, True, jnp.bfloat16)
    check(y.dtype == jnp.bfloat16 and y.shape == x.shape
          and bool(jnp.array_equal(y, want_y)),
          "bn_relu_pallas forward equals the unfused expressions bit for "
          "bit")
    want = jax.jit(bk._reference_backward, static_argnums=(4, 5))(
        x, scale, shift, g, True, jnp.bfloat16)
    for name, got, ref in zip(("dx", "dscale", "dshift"), vjp(g), want):
        err = rel_err(got, ref)
        check(err < 1e-5, f"bn_relu_pallas backward {name} within 1e-5 of "
                          f"the unfused autodiff (f32 sums regrouped): "
                          f"{err:.1e}")

    # flash attention fwd+bwd against naive attention in f32
    q, k, v = (jax.random.normal(kk, (2, 8, 2048, 64), jnp.bfloat16)
               for kk in ks[4:7])

    def loss(attn, q, k, v):
        return jnp.sum(attn(q, k, v, True).astype(jnp.float32) ** 2)

    out = jax.jit(lambda *a: flash_attention(*a, True))(q, k, v)
    grads = jax.jit(jax.grad(lambda *a: loss(flash_attention, *a),
                             argnums=(0, 1, 2)))(q, k, v)
    with jax.default_matmul_precision("highest"):
        f32 = [a.astype(jnp.float32) for a in (q, k, v)]
        want_out = jax.jit(lambda *a: naive_attention(*a, True))(*f32)
        want_grads = jax.jit(jax.grad(lambda *a: loss(naive_attention, *a),
                                      argnums=(0, 1, 2)))(*f32)
    check(out.shape == q.shape and out.dtype == jnp.bfloat16,
          "flash output has the input's shape and dtype")
    for name, got, ref in zip(("out", "dq", "dk", "dv"), (out, *grads),
                              (want_out, *want_grads)):
        err = rel_err(got, ref)
        check(err < BF16_TOL, f"flash {name} vs naive attention at "
                              f"[2,8,2048,64] bf16 causal: {err:.1e} "
                              f"< {BF16_TOL}")


def lm_step():
    """TransformerLM(1024, 512d x 4L x 8H) at T=2048, b=8, through the
    `optim.Optimizer` factory (LocalOptimizer on one chip, DistriOptimizer
    on several); returns the model for the serve phase."""
    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.dataset import LocalDataSet
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.observability import InMemorySink, Telemetry
    from bigdl_tpu.optim.trigger import max_iteration

    model = TransformerLM(LM_VOCAB, embed_dim=512, n_layer=4, n_head=8,
                          max_len=LM_SEQ)
    model.ensure_params(jax.random.PRNGKey(2))
    toks = np.random.RandomState(2).randint(
        1, LM_VOCAB + 1, (LM_BATCH, LM_SEQ + 1)).astype(np.int32)
    sink = InMemorySink()
    opt = optim.Optimizer(
        model, LocalDataSet([MiniBatch(toks[:, :-1], toks[:, 1:])]),
        # the mean NLL over tokens. TimeDistributedCriterion computes the
        # same number through T unrolled per-step slices, which at T=2048
        # costs more to compile than the whole model
        nn.TimeDistributedMaskCriterion(nn.ClassNLLCriterion()),
        batch_size=LM_BATCH)
    say(f"  optim.Optimizer built a {type(opt).__name__}")
    opt.set_optim_method(optim.SGD(learning_rate=0.01, momentum=0.9))
    opt.set_compute_precision("bfloat16")
    opt.set_telemetry(Telemetry(sink))
    opt.set_end_when(max_iteration(LM_STEPS))
    losses = []
    opt.set_iteration_hook(lambda state: losses.append(state["loss"]))
    opt.optimize()
    # per layer: one forward kernel, and dq and dk/dv backward kernels
    mosaic, _, _ = report_run("transformer-LM", opt, losses, sink, LM_STEPS)
    check(mosaic >= 3 * model.n_layer,
          f"transformer-LM: {mosaic} Mosaic kernels in the compiled step "
          f"(>= {3 * model.n_layer})")
    return model


def sequence_parallel(n_dev):
    from jax.sharding import Mesh
    from bigdl_tpu.ops.attention_kernel import flash_attention
    from bigdl_tpu.parallel.sequence import make_sequence_parallel_attention
    mesh = Mesh(np.array(jax.devices()), ("seq",))
    t = 8192 // (2 * n_dev) * 2 * n_dev
    q, k, v = (jax.random.normal(kk, (1, 8, t, 64), jnp.bfloat16)
               for kk in jax.random.split(jax.random.PRNGKey(7), 3))
    want = jax.jit(lambda *a: flash_attention(*a, True))(q, k, v)
    for scheme in ("ring", "zigzag"):
        fn = jax.jit(make_sequence_parallel_attention(mesh, scheme, "seq",
                                                      causal=True))
        got = fn(q, k, v)
        mosaic, ops, _ = hlo_ops(fn.lower(q, k, v).compile())
        err = rel_err(got, want)
        check(mosaic > 0 and ops.get("collective-permute", 0) > 0,
              f"{scheme}: {mosaic} Mosaic hop kernels, collectives {ops}")
        check(err < BF16_TOL,
              f"{scheme} over {n_dev} chips vs single-device flash at "
              f"[1,8,{t},64] bf16 causal: {err:.1e} < {BF16_TOL}")


def phase_kernels(n_dev):
    say("== kernels: Pallas kernels against their references")
    kernel_parity()
    model = lm_step()
    if n_dev >= 2:
        sequence_parallel(n_dev)
    return model


# ---------------------------------------------------------------- serve

def phase_serve(model):
    from bigdl_tpu.serving import GenerationEngine
    say("== serve: GenerationEngine over the same LM, use_flash default")
    check(all(b.attn.use_flash for b in model.blocks),
          "the served model attends through flash_attention")
    n_new, lengths = 16, (5, 17, 31, 40, 64, 100, 130, 200)
    eng = GenerationEngine(model, slots=8, max_len=256,
                           max_new_tokens=n_new, prefill_batch=2,
                           seq_buckets=(32, 128))
    try:
        t0 = time.perf_counter()
        warm = eng.warmup()
        say(f"  warm-up: {warm} executables in "
            f"{time.perf_counter() - t0:.1f} s (for information)")
        rs = np.random.RandomState(3)
        prompts = [rs.randint(1, model.vocab + 1, n).astype(np.int32)
                   for n in lengths]
        t0 = time.perf_counter()
        streams = [eng.generate(p) for p in prompts]
        answers = [s.result(timeout=300) for s in streams]
        say(f"  {len(prompts)} prompts of lengths {lengths} answered in "
            f"{time.perf_counter() - t0:.2f} s (for information)")
        check(all(s.status == "ok" for s in streams),
              "every stream resolved ok")
        check(all(len(a) == n_new and min(a) >= 1 and max(a) <= model.vocab
                  for a in answers),
              f"every answer has the {n_new} requested tokens, all valid ids")
        check(eng.compile_count() == warm,
              f"compile count unchanged after warm-up ({warm})")
    finally:
        eng.close()
    # the engine's first token against a plain full forward of the model:
    # its log-prob under the reference must be the best one, give or take
    # what bf16-pass matmuls move a near-tie by
    params = jax.device_put(model.ensure_params())
    logp = jax.jit(lambda p, t: model.apply(p, t, None))(
        params, prompts[3][None])[0, -1]
    gap = float(jnp.max(logp) - logp[answers[3][0] - 1])
    check(gap < 5e-2, f"first generated token is the reference forward's "
                      f"best (log-prob gap {gap:.1e} < 5e-2)")


# -------------------------------------------------------------- barrier

def phase_barrier():
    say("== barrier: does block_until_ready wait for the device?")

    @jax.jit
    def work(x):  # 64 chained 4096^3 bf16 matmuls: 8.8 TFLOP
        return jax.lax.fori_loop(
            0, 64, lambda _, a: (a @ x).astype(jnp.bfloat16) * 0.01, x)

    x = jax.random.normal(jax.random.PRNGKey(4), (4096, 4096), jnp.bfloat16)
    float(work(x)[0, 0])  # compile, and drain
    t0 = time.perf_counter()
    y = work(x)
    dispatched = time.perf_counter() - t0
    y.block_until_ready()
    blocked = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(work(x)[0, 0])
    fetched = time.perf_counter() - t0
    say(f"  dispatch returned after {dispatched * 1e3:.2f} ms, "
        f"block_until_ready after {blocked * 1e3:.2f} ms, "
        f"a value fetch after {fetched * 1e3:.2f} ms")
    say(f"  block_until_ready is a barrier here: {blocked > 0.8 * fetched}")


def main():
    logging.getLogger("bigdl_tpu").setLevel(logging.WARNING)
    t0 = time.perf_counter()
    device = phase_device()
    phase_train(device["count"])
    model = phase_kernels(device["count"])
    phase_serve(model)
    phase_barrier()
    if device["count"] == 1:
        say("== multi-chip phases (data=4, data=2 x model=2, ring, zigzag) "
            "NOT RUN: this host has one chip")
    say(f"== all phases passed in {time.perf_counter() - t0:.0f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
