"""A/B the Pallas fused stem vs the XLA s2d restatement on the chip.

    python scripts/ab_stem.py            # stem-only microbench + full loop

Prints per-variant stem time and the framework-loop ResNet-50 imgs/sec,
so the bench default (BIGDL_TPU_PALLAS_STEM) can be flipped on a
measured win.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def stem_micro(pallas: bool, batch: int = 128, iters: int = 30):
    import bigdl_tpu.nn as nn
    m = nn.SpaceToDepthStemConvolution(3, 64, 7, pallas_stem=pallas)
    params = m.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.RandomState(0).rand(batch, 224, 224, 3),
                    jnp.bfloat16)
    params = jax.tree_util.tree_map(
        lambda l: l.astype(jnp.bfloat16), params)
    from bigdl_tpu.nn.module import functional_apply

    @jax.jit
    def f(p, xx):
        out, _ = functional_apply(m, p, xx, training=False)
        return jnp.sum(out.astype(jnp.float32))

    float(f(params, x))
    t0 = time.perf_counter()
    for _ in range(iters):
        s = f(params, x)
    float(s)
    dt = (time.perf_counter() - t0) / iters
    print(f"stem {'pallas' if pallas else 'xla-s2d'}: {dt * 1e3:.3f} ms "
          f"(b{batch})", flush=True)
    return dt


def full_loop(pallas: bool):
    os.environ["BIGDL_TPU_PALLAS_STEM"] = "1" if pallas else ""
    from bigdl_tpu.tools.bench_cli import bench_resnet50
    thr, metrics, flops = bench_resnet50(warmup=24, iters=72)
    print(f"resnet50 loop {'pallas' if pallas else 'xla-s2d'} stem: "
          f"{thr / jax.device_count():.1f} imgs/sec/chip", flush=True)
    return thr


if __name__ == "__main__":
    t_xla = stem_micro(False)
    t_pl = stem_micro(True)
    print(f"stem speedup: {t_xla / t_pl:.2f}x", flush=True)
    if "--micro-only" not in sys.argv:
        thr_x = full_loop(False)
        thr_p = full_loop(True)
        print(f"loop delta: {(thr_p / thr_x - 1) * 100:+.1f}%", flush=True)
