"""Driver `train_window`: one optimizer object, driven from the seed
through its first steps and then through the measured window.

The program's training loop is one call, `optimize()`, so set-up and
window are one run of it. Weights and the one resident batch are made on
the device from the seed. Steps 1 to 3 are the steps the reference
follows: their losses, the first gradient from the optimizer's state
after step 1 and the parameters' change after step 3 are all read where
the compiled step hands its outputs back to the loop. Step 4 drains
those readings; the window opens once it has ended on the device.

The loop syncs the loss at the mix's `sync_interval` (null: the
program's default, every step). Between syncs the host dispatches ahead
of the device, as far as the runtime's queue lets it, so the host's own
time a step is off the device's path. When `--seconds` have passed,
nothing more is dispatched: the window closes once every step sent has
ended on the device, and the rate is all items of all those steps over
that whole time.
"""

from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

#: steps before the window: three that the reference follows and one
#: that drains the probe's readings
SETUP_STEPS = 4
#: the traced slice is the window's last seconds; the profiler is
#: stopped (which takes a second or more) once the window has closed
TRACE_SECONDS = 3.0


class StepProbe:
    """Sits where `_build_step` hands the compiled step to the loop: the
    same callable, with the new state read after calls 1 and 3."""

    def __init__(self, ctx, adapter):
        self.ctx, self.adapter = ctx, adapter
        self.calls = 0
        self.first_grad = None
        self.change = None
        self.losses = []  # steps 1 to 3, as the step returns them
        self.last_loss = None  # the newest step's, on the device

    def wrap(self, step):
        import jax
        from benchmarks.reference import numerics as nx

        def probed(*args):
            out = step(*args)
            self.calls += 1
            self.last_loss = out[3]
            if self.calls <= 3:
                self.losses.append(out[3])
            if self.calls == 1:
                g = self.adapter.first_gradient(out[1])
                self.first_grad = (nx.leaf_norms(g), nx.sketches(g))
            elif self.calls == 3:
                start = self.ctx.reference.init_weights(self.ctx.cfg,
                                                        self.ctx.seed)
                new = self.adapter.from_program(out[0])
                # beside the program's leaves, wherever the mesh put them
                start = {k: jax.device_put(v, new[k].sharding)
                         for k, v in start.items()}
                self.change = (nx.delta_norms(new, start),
                               nx.delta_sketches(new, start))
            return out
        # the loops look at these on the compiled step
        for attr in ("last_info", "executables", "_cache_size"):
            if hasattr(step, attr):
                setattr(probed, attr, getattr(step, attr))
        return probed

    def install(self, optimizer):
        build = optimizer._build_step
        optimizer._build_step = lambda *a, **k: self.wrap(build(*a, **k))


def build_optimizer(ctx, adapter, x, y):
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.dataset import LocalDataSet
    from bigdl_tpu.dataset.sample import MiniBatch
    entry = ctx.mix["entry"]
    if entry == "DistriOptimizer":
        from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
        from bigdl_tpu.parallel.mesh import build_mesh, shard_batch
        mesh = build_mesh(devices=ctx.devices)
        data = LocalDataSet([MiniBatch(shard_batch(mesh, x),
                                       shard_batch(mesh, y))])
        opt = DistriOptimizer(adapter.model, data, adapter.criterion(),
                              mesh=mesh)
    elif entry == "optim.Optimizer":
        # the factory picks its loop from jax.devices(); a one-chip cell
        # stays on one chip whatever the host holds
        opt = optim.Optimizer(adapter.model, LocalDataSet([MiniBatch(x, y)]),
                              adapter.criterion(), batch_size=x.shape[0],
                              local=ctx.chips == 1)
    else:
        raise ValueError(f"unknown training entry {entry!r}")
    opt.set_optim_method(adapter.optim_method())
    opt.set_compute_precision(ctx.cfg["compute_precision"])
    if ctx.mix.get("sync_interval") is not None:
        opt.set_sync_interval(ctx.mix["sync_interval"])
    return opt


def run(ctx) -> Dict[str, Any]:
    import jax
    from benchmarks.reference import numerics as nx
    adapter = ctx.adapter
    rows = ctx.mix["per_chip_batch"] * ctx.chips
    items_per_step = rows * adapter.items_per_row()

    weights = ctx.reference.init_weights(ctx.cfg, ctx.seed)
    x, y = ctx.reference.train_batch(ctx.cfg, ctx.mix, ctx.seed, ctx.chips)
    adapter.model.set_params(adapter.to_program(weights))
    adapter.model._state = adapter.model.state_init()
    del weights
    jax.block_until_ready((adapter.model.ensure_params(), x, y))
    marks = {"weights": time.perf_counter()}
    opt = build_optimizer(ctx, adapter, x, y)
    del x, y
    tracer = None
    if ctx.trace:
        from bigdl_tpu.observability.spans import SpanTracer
        tracer = SpanTracer()
        opt.set_tracer(tracer)
    probe = StepProbe(ctx, adapter)
    probe.install(opt)

    every = max(1, int(ctx.mix.get("sync_interval") or 1))
    w = {"t0": None, "deadline": None, "t_end": None, "last": None,
         "syncs": [], "lowerings0": 0, "prog": None}

    def hook(state):
        now = time.perf_counter()
        n = state["neval"]
        if n == 1:
            jax.block_until_ready(probe.last_loss)
            marks["step1"] = time.perf_counter()
        elif n == 3:
            w["prog"] = {"losses": [float(l) for l in probe.losses],
                         "gnorm": nx.to_floats(probe.first_grad[0]),
                         "gsketch": nx.to_floats(probe.first_grad[1]),
                         "dnorm": nx.to_floats(probe.change[0]),
                         "dsketch": nx.to_floats(probe.change[1])}
        elif n == SETUP_STEPS:
            w["lowerings0"] = ctx.lowerings()
            jax.block_until_ready(probe.last_loss)
            w["t0"] = time.perf_counter()
            w["deadline"] = w["t0"] + ctx.seconds
        elif n > SETUP_STEPS:
            if n % every == 0:  # the loop has just synced this step
                w["syncs"].append((n, now))
            if ctx.trace:
                ctx.trace_from(now - w["t0"], ctx.seconds - TRACE_SECONDS)

    def done(state):
        if w["deadline"] is None or time.perf_counter() < w["deadline"]:
            return False
        # send nothing more; the window ends with the last step sent
        jax.block_until_ready(probe.last_loss)
        w["t_end"] = time.perf_counter()
        w["last"] = state["neval"]
        return True

    opt.set_iteration_hook(hook)
    opt.set_end_when(done)
    opt.optimize()
    ctx.trace_stop()
    lowerings = ctx.lowerings() - w["lowerings0"]

    steps = w["last"] - SETUP_STEPS
    if steps < 1:
        raise RuntimeError("no whole step fit the window")
    elapsed = float(w["t_end"] - w["t0"])
    rate = steps * items_per_step / elapsed / ctx.chips
    # the time per step from one sync to the next (the last interval ends
    # where the window does)
    syncs = [(SETUP_STEPS, w["t0"])] + w["syncs"]
    if syncs[-1][0] < w["last"]:
        syncs.append((w["last"], w["t_end"]))
    step_ms = np.asarray([(t1 - t0) / (n1 - n0) * 1e3 for (n0, t0), (n1, t1)
                          in zip(syncs, syncs[1:])])
    phases = (f"set-up: the chip at {ctx.t_chip - ctx.t_start:.1f} s, "
              f"weights and batch {marks['weights'] - ctx.t_chip:.1f}, "
              f"step 1 {marks['step1'] - marks['weights']:.1f}, steps 2 to "
              f"{SETUP_STEPS} {w['t0'] - marks['step1']:.1f}")

    # free the program's state before the reference runs
    adapter.model.set_params(None)
    adapter.model._state = None
    del opt, probe

    slow = int((step_ms > 1.5 * np.median(step_ms)).sum())
    return {
        "t_window": w["t0"], "window_s": elapsed,
        "note": f"{steps} steps, loss synced every {every}; a step from "
                f"sync to sync: median {np.median(step_ms):.2f} ms, longest "
                f"{step_ms.max():.1f} ms, {slow} of {len(step_ms)} over 1.5 x "
                f"the median; {lowerings} programs lowered in the window; "
                + phases,
        "attempted": steps, "failed": 0,
        "end_to_end": {"train_items_per_s_per_chip": rate},
        "counters": {"steps": steps, "items_per_step": items_per_step,
                     "items_per_s_per_chip": rate,
                     "step_ms": step_ms.tolist(),
                     "lowerings_in_window": lowerings},
        "program": w["prog"],
    }


def check(ctx, out) -> Dict[str, Any]:
    """The reference follows the same three steps on the same batch; the
    readings compared are in `benchmarks/compare.py`."""
    from functools import partial
    import jax
    from benchmarks import compare
    from benchmarks.reference import numerics as nx
    ref = ctx.reference
    x, y = ref.train_batch(ctx.cfg, ctx.mix, ctx.seed, ctx.chips)
    fresh = lambda: ref.init_weights(ctx.cfg, ctx.seed)  # noqa: E731
    if ctx.chips > 1:
        # the reference's rows lie over the chips as the cell's do, its
        # weights on each: plain jnp, partitioned by XLA
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.asarray(ctx.devices), ("data",))
        x, y = (jax.device_put(a, NamedSharding(mesh, P("data")))
                for a in (x, y))
        fresh = lambda: jax.device_put(  # noqa: E731
            ref.init_weights(ctx.cfg, ctx.seed), NamedSharding(mesh, P()))
    want = nx.train_trace(
        partial(ref.loss, ctx.cfg, precision="f32"), fresh, x, y,
        ctx.cfg["optimizer"], steps=3,
        row_block=ref.row_block(ctx.cfg, ctx.mix))
    return compare.training(out["program"], want)
