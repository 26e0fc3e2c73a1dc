"""Pipeline parallelism: GPipe and 1F1B over a 'pipe' mesh axis.

Beyond-parity (the reference's second parallelism engine,
DL/optim/ParallelOptimizer.scala, still scales only by data parallelism):
stage parameters live one-stage-per-device on the mesh's 'pipe' axis, the
batch splits into microbatches, and activations flow stage-to-stage with
`lax.ppermute` — XLA lowers the shifts to ICI neighbor sends, and its
scheduler overlaps them with the next microbatch's compute (the same
mechanism ring attention uses, parallel/sequence.py).

Two shape contracts:

- `GPipe` (classic homogeneous): every stage is the same block module, so
  inter-stage activations share one shape and the stage loop is a single
  traced body under `lax.scan` — one compilation regardless of stage or
  microbatch count.
- `PipelineStages` (heterogeneous): arbitrary per-stage modules with
  differing activation/parameter shapes. Fixed SPMD shapes come from a
  padded inter-stage contract: activations and per-stage parameter
  pytrees travel as zero-padded flat vectors sized to the largest stage,
  and each tick `lax.switch`es into the owning stage's statically-shaped
  body. Real zoo models (ResNet-50 split at its stage boundaries) pipe
  through this path.

Schedules: GPipe fill-drain for inference, and 1F1B for training
(`PipelineStages.train_step_1f1b`) — a host-computed static action table
(one F, B or idle per device per tick) drives the traced loop; backward
ticks recompute the stage forward from a stashed input (activation
recomputation), so the live stash is bounded by the 1F1B in-flight depth
(≤ S) instead of GPipe's n_micro.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.nn.module import ApplyContext, Module


def _varying(a):
    """Mark an array device-varying over 'pipe' (shard_map's
    varying-axes type system); idempotent."""
    try:
        return lax.pcast(a, ("pipe",), to="varying")
    except ValueError:
        return a  # already varying


class GPipe(Module):
    """`n_stages` copies of `block` run as a pipeline.

    `init` returns the block's params STACKED on a leading stage axis —
    shard that axis over the mesh's 'pipe' dimension (`place_params`).
    `pipeline_apply` runs the schedule inside shard_map; microbatch count
    defaults to the stage count (fill efficiency n_micro/(n_micro+S-1)).

    Example (2 pipeline stages over 2 devices, 8 microbatches):
        >>> import jax, jax.numpy as jnp, numpy as np
        >>> import bigdl_tpu.nn as nn
        >>> from jax.sharding import Mesh
        >>> from bigdl_tpu.parallel.pipeline import GPipe
        >>> pipe = GPipe(nn.Linear(4, 4), n_stages=2, n_micro=8)
        >>> round(pipe.bubble_fraction, 3)  # (S-1)/(n_micro+S-1)
        0.111
        >>> params = pipe.init(jax.random.PRNGKey(0))
        >>> mesh = Mesh(np.array(jax.devices()[:2]), ("pipe",))
        >>> x = jnp.ones((16, 4))
        >>> out = pipe.pipeline_apply(mesh, pipe.place_params(mesh, params), x)
        >>> out.shape
        (16, 4)
        >>> seq = pipe.forward(x)  # single-device sequential reference
        >>> bool(jnp.allclose(out, seq, atol=1e-5))
        True
    """

    def __init__(self, block: Module, n_stages: int,
                 n_micro: Optional[int] = None, name=None):
        super().__init__(name)
        self.block = block
        self.n_stages = n_stages
        self.n_micro = n_micro or n_stages

    @property
    def bubble_fraction(self) -> float:
        """Idle fraction of the GPipe fill/drain schedule:
        (S-1)/(n_micro+S-1). Raise n_micro to amortize — e.g. 4 stages,
        4 micro -> 43%; 4 stages, 16 micro -> 16%. (The schedule runs
        n_micro+S-1 ticks of which S-1 are fill/drain per device.)"""
        return (self.n_stages - 1) / (self.n_micro + self.n_stages - 1)

    # -- params ----------------------------------------------------------
    def init(self, rng):
        keys = jax.random.split(rng, self.n_stages)
        per_stage = [self.block.init(k) for k in keys]
        return jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves), *per_stage)

    def place_params(self, mesh: Mesh, params):
        """Shard the stacked stage axis over 'pipe'."""
        sh = NamedSharding(mesh, P("pipe"))
        return jax.tree_util.tree_map(
            lambda leaf: jax.device_put(leaf, sh), params)

    # -- sequential reference (single device; also the Module contract) --
    def apply(self, params, input, ctx: ApplyContext):
        out, _ = lax.scan(lambda h, p: (self.block.apply(p, h, ctx), None),
                          input, params)
        return out

    # -- pipelined execution --------------------------------------------
    def pipeline_apply(self, mesh: Mesh, params, x, training: bool = False):
        """Run the GPipe schedule over mesh axis 'pipe'.

        x: [B, ...] host/global batch, B divisible by n_micro. Returns the
        same result as sequential `apply`, computed with each stage on its
        own device."""
        n_micro, S = self.n_micro, self.n_stages
        mesh_pipe = int(dict(zip(mesh.axis_names,
                                 mesh.devices.shape)).get("pipe", 0))
        if mesh_pipe != S:
            raise ValueError(
                f"mesh 'pipe' axis has {mesh_pipe} devices but the "
                f"pipeline has {S} stages")
        B = x.shape[0]
        if B % n_micro:
            raise ValueError(f"batch {B} not divisible by n_micro {n_micro}")
        micro = x.reshape((n_micro, B // n_micro) + x.shape[1:])
        block = self.block
        ctx = ApplyContext(training=training)

        def staged(params_stage, micro_all):
            # params_stage: this device's stage params (leading axis
            # sliced to 1 by shard_map) — drop the stage dim
            params_local = jax.tree_util.tree_map(
                lambda l: l[0], params_stage)
            idx = lax.axis_index("pipe")
            # scan carry must be device-varying like the loop outputs
            zeros = lax.pcast(jnp.zeros_like(micro_all[0]), ("pipe",),
                              to="varying")
            T = n_micro + S - 1
            perm = [(i, (i + 1) % S) for i in range(S)]

            def tick(state, t):
                inject = lax.dynamic_index_in_dim(
                    micro_all, jnp.minimum(t, n_micro - 1), axis=0,
                    keepdims=False)
                h_in = jnp.where(idx == 0, inject, state)
                h_out = block.apply(params_local, h_in, ctx)
                return lax.ppermute(h_out, "pipe", perm), h_out

            _, hs = lax.scan(tick, zeros, jnp.arange(T))
            # the LAST stage's outputs at ticks [S-1, S-1+n_micro) are the
            # pipeline results; broadcast them to every device
            out_local = lax.dynamic_slice_in_dim(hs, S - 1, n_micro, axis=0)
            out_local = jnp.where(idx == S - 1, out_local,
                                  jnp.zeros_like(out_local))
            return lax.psum(out_local, "pipe")

        stage_spec = jax.tree_util.tree_map(lambda _: P("pipe"), params)
        mapped = jax.shard_map(
            staged, mesh=mesh,
            in_specs=(stage_spec, P()),   # params by stage, batch replicated
            out_specs=P())
        out_micro = mapped(params, micro)
        return out_micro.reshape((B,) + out_micro.shape[2:])


def _schedule_1f1b(S: int, M: int):
    """Static 1F1B action table: rows[t][s] = (op, micro) with op in
    {'I', 'F', 'B'}.

    Dependency-driven simulation of the classic non-interleaved 1F1B
    policy (PipeDream-Flush): stage s runs min(S - s, M) warmup forwards,
    then strictly alternates backward/forward until drained. Computed
    host-side once per (S, M); the traced loop just follows the table, so
    the schedule costs nothing on device."""
    warm = [min(S - s, M) for s in range(S)]
    next_f, next_b = [0] * S, [0] * S
    fwd_ready = [set(range(M))] + [set() for _ in range(S - 1)]
    bwd_ready = [set() for _ in range(S)]
    rows, done = [], 0
    while done < S * M:
        row = []
        for s in range(S):
            can_b = next_b[s] < M and next_b[s] in bwd_ready[s]
            # the 1F1B memory bound: a stage never runs more than its
            # warmup depth of forwards ahead of its backwards — it IDLES
            # instead (that idling is the pipeline bubble), keeping the
            # stash ≤ warm[s] ≤ S microbatches
            can_f = next_f[s] < M and next_f[s] in fwd_ready[s] \
                and next_f[s] - next_b[s] < warm[s]
            if can_b:
                row.append(("B", next_b[s]))
                next_b[s] += 1
            elif can_f:
                row.append(("F", next_f[s]))
                next_f[s] += 1
            else:
                row.append(("I", 0))
        for s, (op, m) in enumerate(row):   # effects land next tick
            if op == "F":
                (fwd_ready[s + 1] if s + 1 < S else bwd_ready[s]).add(m)
            elif op == "B":
                done += 1
                if s > 0:
                    bwd_ready[s - 1].add(m)
        rows.append(row)
        if len(rows) > 4 * (S + M) + 8:   # safety: schedule must drain
            raise RuntimeError("1F1B schedule failed to drain")
    return rows


class PipelineStages:
    """Heterogeneous pipeline: arbitrary per-stage modules.

    SPMD needs one traced program with fixed shapes on every device, but
    hetero stages differ in both activation and parameter shapes. The
    padded inter-stage contract restores fixed shapes:

    - each stage's parameter pytree is raveled to a flat vector and
      zero-padded to the largest stage's size -> params travel as one
      [S, P_max] array sharded over 'pipe' (per-device memory = the
      LARGEST stage, not the sum — the pipeline memory-scaling property
      holds);
    - inter-stage activations (and backward gradients) travel as flat
      vectors padded to the largest boundary size;
    - every tick, `lax.switch` enters the owning stage's body, which
      unpads/unravels to its static shapes, computes, and re-pads.

    All stage bodies are compiled once into the shared program (standard
    SPMD multi-branch cost); each device executes only its own.

    Reference contrast: DL/optim/ParallelOptimizer.scala is the
    reference's second parallelism engine; it still replicates the whole
    model. This pipelines models that do NOT fit one device.
    """

    def __init__(self, stages: Sequence[Module], n_micro: int,
                 example_input, name: Optional[str] = None):
        """`example_input`: one MICRObatch-shaped array (its shapes fix
        the traced program; the global batch must split into microbatches
        of exactly this shape)."""
        if len(stages) < 2:
            raise ValueError("need at least 2 stages")
        self.stages = list(stages)
        self.S = len(stages)
        self.n_micro = n_micro
        self.name = name or "PipelineStages"
        # static per-boundary shapes via abstract evaluation (no FLOPs,
        # no allocation: params and activations are ShapeDtypeStructs)
        ctx = ApplyContext()
        shapes = [jax.eval_shape(lambda: jnp.asarray(example_input))]
        for stage in self.stages:
            prev = shapes[-1]
            try:
                p_shape = jax.eval_shape(stage.init, jax.random.PRNGKey(0))
            except jax.errors.ConcretizationTypeError:
                # some initializers need concrete shapes (e.g. MsraFiller
                # fan computation): pay one real init, keep only structure
                concrete = stage.init(jax.random.PRNGKey(0))
                p_shape = jax.tree_util.tree_map(
                    lambda l: jax.ShapeDtypeStruct(jnp.shape(l),
                                                   jnp.asarray(l).dtype),
                    concrete)
                del concrete
            shapes.append(jax.eval_shape(
                lambda p, a, st=stage: st.apply(p, a, ctx),
                p_shape, jax.ShapeDtypeStruct(prev.shape, prev.dtype)))
        self.boundary_shapes = shapes          # S+1 entries: in of each + out
        self.act_pad = max(int(np.prod(s.shape)) for s in shapes)

    @property
    def bubble_fraction(self) -> float:
        """Idle fraction of the 1F1B table for this (S, n_micro) —
        counted from the actual schedule, not a formula."""
        rows = _schedule_1f1b(self.S, self.n_micro)
        idle = sum(1 for row in rows for op, _ in row if op == "I")
        return idle / (len(rows) * self.S)

    # -- params ---------------------------------------------------------
    def init(self, rng):
        """Per-stage param trees (list — shapes differ by stage)."""
        keys = jax.random.split(rng, self.S)
        return [st.init(k) for st, k in zip(self.stages, keys)]

    def _ravel_specs(self, params):
        """(padded [S, P_max] array, per-stage unravel fns, sizes).
        The unravel fns and sizes depend only on the param STRUCTURE, so
        they are cached — repeat calls with a pre-raveled array skip the
        host-side ravel entirely (see train_step_1f1b).

        Constraint: the pipelined paths carry every stage's params and
        grads through one padded float32 [S, P_max] array, so leaves
        must round-trip float32 exactly (f32/bf16/f16). Wider or
        integer leaves would silently lose precision — refuse them."""
        flats, unravels = [], []
        for p in params:
            for leaf in jax.tree_util.tree_leaves(p):
                d = jnp.result_type(leaf)  # no device materialization
                if d not in (jnp.float32, jnp.bfloat16, jnp.float16):
                    raise TypeError(
                        f"PipelineStages params must be f32-compatible "
                        f"(f32/bf16/f16); got leaf dtype {d}. Cast "
                        f"integer buffers out of the param tree or use "
                        f"the sequential apply() path.")
            flat, unravel = ravel_pytree(p)
            flats.append(flat)
            unravels.append(unravel)
        pmax = max(f.size for f in flats)
        stacked = jnp.stack([jnp.pad(f.astype(jnp.float32),
                                     (0, pmax - f.size)) for f in flats])
        self._spec_cache = (unravels, [f.size for f in flats], pmax)
        return stacked, unravels, [f.size for f in flats]

    def place_params(self, mesh: Mesh, params):
        """Per-stage param list -> padded [S, P_max] array sharded over
        'pipe'. Do this ONCE and thread the placed array through the
        training loop (train_step_1f1b accepts it directly) — re-raveling
        the whole model per step is host work the loop doesn't need."""
        stacked, _, _ = self._ravel_specs(params)
        return jax.device_put(stacked, NamedSharding(mesh, P("pipe")))

    def unravel_stacked(self, stacked):
        """Inverse of place_params: padded [S, P_max] -> per-stage param
        list (e.g. to read updated params back after a training loop)."""
        unravels, sizes, _ = self._spec_cache
        return [unravels[s](stacked[s, :sizes[s]]) for s in range(self.S)]

    def _pad_act(self, a):
        flat = a.reshape(-1).astype(jnp.float32)
        return jnp.pad(flat, (0, self.act_pad - flat.size))

    def _unpad_act(self, vec, boundary: int):
        sd = self.boundary_shapes[boundary]
        n = int(np.prod(sd.shape))
        return vec[:n].reshape(sd.shape).astype(sd.dtype)

    # -- sequential reference -------------------------------------------
    def apply(self, params, x, ctx: Optional[ApplyContext] = None):
        ctx = ctx or ApplyContext()
        h = x
        for st, p in zip(self.stages, params):
            h = st.apply(p, h, ctx)
        return h

    forward = apply

    # -- pipelined forward (GPipe fill-drain over the padded contract) --
    def pipeline_apply(self, mesh: Mesh, params, x,
                       training: bool = False):
        """Forward the full batch through the hetero pipeline. `params`
        is the plain per-stage list (raveled/placed internally)."""
        S, M = self.S, self.n_micro
        self._check_mesh(mesh)
        B = x.shape[0]
        if B % M:
            raise ValueError(f"batch {B} not divisible by n_micro {M}")
        mshape = self.boundary_shapes[0].shape
        if x.shape[1:] != mshape[1:] or B // M != mshape[0]:
            raise ValueError(
                f"microbatch shape {(B // M,) + x.shape[1:]} != example "
                f"shape {mshape}")
        stacked, unravels, sizes = self._ravel_specs(params)
        stacked = jax.device_put(stacked,
                                 NamedSharding(mesh, P("pipe")))
        micro = x.reshape((M,) + mshape)
        ctx = ApplyContext(training=training)
        pipeline = self

        def make_fwd(s):
            unravel, size = unravels[s], sizes[s]

            def body(pvec, in_vec, micro_all, m):
                x_in = lax.dynamic_index_in_dim(micro_all, m, 0, False) \
                    if s == 0 else pipeline._unpad_act(in_vec, s)
                p = unravel(pvec[:size])
                y = pipeline.stages[s].apply(p, x_in, ctx)
                return pipeline._pad_act(y)
            return body

        fwd_bodies = [make_fwd(s) for s in range(S)]

        def staged(pvec_stage, micro_all):
            pvec = pvec_stage[0]
            idx = lax.axis_index("pipe")
            zero = _varying(jnp.zeros((pipeline.act_pad,), jnp.float32))
            T = M + S - 1
            perm = [(i, (i + 1) % S) for i in range(S)]

            def tick(carry, t):
                in_vec = carry
                m = jnp.clip(t - idx, 0, M - 1)
                active = (t - idx >= 0) & (t - idx < M)

                def run(i):
                    return lambda: fwd_bodies[i](pvec, in_vec, micro_all,
                                                 m)
                out = lax.switch(idx, [run(i) for i in range(S)])
                out = jnp.where(active, out, jnp.zeros_like(out))
                # collect the last stage's result at its active ticks
                res = jnp.where((idx == S - 1) & active, out,
                                jnp.zeros_like(out))
                return lax.ppermute(out, "pipe", perm), res

            _, res = lax.scan(tick, zero, jnp.arange(T))
            # ticks S-1 .. S-1+M-1 on the last device hold the outputs
            res = lax.dynamic_slice_in_dim(res, S - 1, M, axis=0)
            return lax.psum(res, "pipe")

        mapped = jax.shard_map(staged, mesh=mesh,
                               in_specs=(P("pipe"), P()), out_specs=P())
        out_pad = mapped(stacked, micro)             # [M, act_pad]
        out_sd = self.boundary_shapes[-1]
        n = int(np.prod(out_sd.shape))
        out = out_pad[:, :n].reshape((M,) + out_sd.shape).astype(
            out_sd.dtype)
        return out.reshape((B,) + out_sd.shape[1:])

    def _check_mesh(self, mesh):
        mesh_pipe = int(dict(zip(mesh.axis_names,
                                 mesh.devices.shape)).get("pipe", 0))
        if mesh_pipe != self.S:
            raise ValueError(
                f"mesh 'pipe' axis has {mesh_pipe} devices but the "
                f"pipeline has {self.S} stages")

    # -- 1F1B training step ---------------------------------------------
    def train_step_1f1b(self, mesh: Mesh, params, x, y, loss_fn,
                        training: bool = True):
        """One training step under the 1F1B schedule.

        loss_fn(pred_micro, y_micro) -> scalar mean loss of one
        microbatch. Returns (mean loss over microbatches, per-stage grad
        list matching `params`). Backward ticks recompute their stage's
        forward from the stashed INPUT (activation recomputation), so at
        most the 1F1B in-flight depth (≤ S+1 microbatch inputs) is
        stashed per device — the memory property GPipe's full-batch
        stash lacks.
        """
        S, M = self.S, self.n_micro
        self._check_mesh(mesh)
        B = x.shape[0]
        if B % M:
            raise ValueError(f"batch {B} not divisible by n_micro {M}")
        mshape = self.boundary_shapes[0].shape
        micro_x = x.reshape((M,) + mshape)
        micro_y = y.reshape((M, B // M) + y.shape[1:])
        if isinstance(params, (list, tuple)):
            stacked, unravels, sizes = self._ravel_specs(list(params))
            stacked = jax.device_put(stacked,
                                     NamedSharding(mesh, P("pipe")))
        else:
            # pre-placed [S, P_max] from place_params: no per-step ravel
            if getattr(self, "_spec_cache", None) is None:
                raise ValueError(
                    "pass the per-stage param list once (or call "
                    "place_params) before using a pre-placed array")
            stacked = params
            unravels, sizes, _ = self._spec_cache
        pmax = stacked.shape[1]
        # memoize the traced step: rebuilding the shard_map function per
        # call would retrace (and recompile) every training step
        # the cache entry retains the mesh and loss_fn objects so the
        # identity check below can never hit a recycled id() of a
        # garbage-collected original
        fn_key = (x.shape, str(x.dtype), y.shape, str(y.dtype),
                  training, pmax)
        cached = getattr(self, "_1f1b_fn_cache", None)
        if (cached is not None and cached[0] == fn_key
                and cached[2] is mesh and cached[3] is loss_fn):
            mapped = cached[1]
            gpad, loss_sum = mapped(stacked, micro_x, micro_y)
            grads = [unravels[s](gpad[s, :sizes[s]]) for s in range(S)]
            return loss_sum / M, grads
        ctx = ApplyContext(training=training)
        pipeline = self

        rows = _schedule_1f1b(S, M)
        T = len(rows)
        # stash depth: max in-flight microbatches per stage, +1 margin
        # because an activation ARRIVES one tick before its F can run
        depth, inflight = 0, [0] * S
        for row in rows:
            for s, (op, _) in enumerate(row):
                inflight[s] += (op == "F") - (op == "B")
            depth = max(depth, max(inflight))
        K = depth + 1
        # device-side tables: op[t, s] (0 idle, 1 F, 2 B), micro[t, s]
        op_tab = jnp.asarray([[{"I": 0, "F": 1, "B": 2}[op]
                               for op, _ in row] for row in rows],
                             jnp.int32)
        mi_tab = jnp.asarray([[m for _, m in row] for row in rows],
                             jnp.int32)

        def make_f(s):
            unravel, size = unravels[s], sizes[s]

            def body(pvec, stash, gstash, gacc, m, micro_all, _y):
                # input: the arrival-stashed activation (stage 0 reads
                # its microbatch directly)
                x_in = lax.dynamic_index_in_dim(micro_all, m, 0, False) \
                    if s == 0 else pipeline._unpad_act(
                        lax.dynamic_index_in_dim(stash, m % K, 0, False),
                        s)
                p = unravel(pvec[:size])
                out = pipeline.stages[s].apply(p, x_in, ctx)
                z = _varying(jnp.zeros((pipeline.act_pad,), jnp.float32))
                return (pipeline._pad_act(out), z, gacc,
                        _varying(jnp.zeros((), jnp.float32)))
            return body

        def make_b(s):
            unravel, size = unravels[s], sizes[s]
            last = s == S - 1

            def body(pvec, stash, gstash, gacc, m, micro_all, y_all):
                # recompute this stage's forward from the stashed input
                x_in = lax.dynamic_index_in_dim(micro_all, m, 0, False) \
                    if s == 0 else pipeline._unpad_act(
                        lax.dynamic_index_in_dim(stash, m % K, 0, False),
                        s)
                p = unravel(pvec[:size])

                if last:
                    y_m = lax.dynamic_index_in_dim(y_all, m, 0, False)

                    def f(pp, xx):
                        pred = pipeline.stages[s].apply(pp, xx, ctx)
                        return loss_fn(pred, y_m)
                    loss_m, vjp = jax.vjp(f, p, x_in)
                    gp, gx = vjp(_varying(jnp.asarray(1.0 / M,
                                                      loss_m.dtype)))
                else:
                    g_out = pipeline._unpad_act(
                        lax.dynamic_index_in_dim(gstash, m % K, 0,
                                                 False), s + 1)

                    def f(pp, xx):
                        return pipeline.stages[s].apply(pp, xx, ctx)
                    _, vjp = jax.vjp(f, p, x_in)
                    gp, gx = vjp(g_out)
                    loss_m = jnp.zeros(())
                gflat, _ = ravel_pytree(gp)
                gacc = gacc + jnp.pad(gflat.astype(jnp.float32),
                                      (0, pmax - gflat.size))
                z = _varying(jnp.zeros((pipeline.act_pad,), jnp.float32))
                return (z, pipeline._pad_act(gx), gacc,
                        _varying(loss_m.astype(jnp.float32)))
            return body

        def make_idle():
            def body(pvec, stash, gstash, gacc, m, micro_all, _y):
                z = _varying(jnp.zeros((pipeline.act_pad,), jnp.float32))
                return z, z, gacc, _varying(jnp.zeros((), jnp.float32))
            return body

        bodies = [make_idle()] + [make_f(s) for s in range(S)] + \
            [make_b(s) for s in range(S)]

        def staged(pvec_stage, micro_all, y_all):
            pvec = pvec_stage[0]
            idx = lax.axis_index("pipe")

            z = _varying(jnp.zeros((pipeline.act_pad,), jnp.float32))
            stash0 = _varying(jnp.zeros((K, pipeline.act_pad),
                                        jnp.float32))
            gstash0 = _varying(jnp.zeros((K, pipeline.act_pad),
                                         jnp.float32))
            gacc0 = _varying(jnp.zeros((pmax,), jnp.float32))
            loss0 = _varying(jnp.zeros(()))
            fperm = [(i, (i + 1) % S) for i in range(S)]
            bperm = [(i, (i - 1) % S) for i in range(S)]

            def tick(carry, t):
                fwd_in, bwd_in, stash, gstash, gacc, loss_acc = carry
                tprev = jnp.maximum(t - 1, 0)
                # bank arrivals FIRST (sender acted last tick; the wire
                # value dies this tick, but the consume tick may be
                # later — 1F1B lets a stage prefer a B over this F)
                left = jnp.maximum(idx - 1, 0)
                has_f = (idx > 0) & (t > 0) & \
                    (op_tab[tprev, left] == 1)
                fslot = mi_tab[tprev, left] % K
                cur = lax.dynamic_index_in_dim(stash, fslot, 0, False)
                stash = lax.dynamic_update_index_in_dim(
                    stash, jnp.where(has_f, fwd_in, cur), fslot, 0)
                right = jnp.minimum(idx + 1, S - 1)
                has_b = (idx < S - 1) & (t > 0) & \
                    (op_tab[tprev, right] == 2)
                bslot = mi_tab[tprev, right] % K
                curg = lax.dynamic_index_in_dim(gstash, bslot, 0, False)
                gstash = lax.dynamic_update_index_in_dim(
                    gstash, jnp.where(has_b, bwd_in, curg), bslot, 0)

                op = op_tab[t, idx]
                m = mi_tab[t, idx]
                branch = jnp.where(op == 0, 0,
                                   jnp.where(op == 1, 1 + idx,
                                             1 + S + idx))
                fwd_out, bwd_out, gacc, loss_m = lax.switch(
                    branch,
                    [lambda pv, st, gs, ga, mm, ma, ya, b=b:
                     b(pv, st, gs, ga, mm, ma, ya)
                     for b in bodies],
                    pvec, stash, gstash, gacc, m, micro_all, y_all)
                return ((lax.ppermute(fwd_out, "pipe", fperm),
                         lax.ppermute(bwd_out, "pipe", bperm),
                         stash, gstash, gacc, loss_acc + loss_m), None)

            (f_in, b_in, _st, _gs, gacc, loss_acc), _ = lax.scan(
                tick, (z, z, stash0, gstash0, gacc0, loss0),
                jnp.arange(T))
            return gacc[None, :], lax.psum(loss_acc, "pipe")

        mapped = jax.jit(jax.shard_map(staged, mesh=mesh,
                                       in_specs=(P("pipe"), P(), P()),
                                       out_specs=(P("pipe"), P())))
        self._1f1b_fn_cache = (fn_key, mapped, mesh, loss_fn)
        gpad, loss_sum = mapped(stacked, micro_x, micro_y)
        grads = [unravels[s](gpad[s, :sizes[s]])
                 for s in range(S)]
        return loss_sum / M, grads


def split_sequential(model, n_stages: int,
                     boundaries: Optional[Sequence[int]] = None):
    """Split a Sequential's children into `n_stages` contiguous stage
    Sequentials for `PipelineStages` — e.g. ResNet-50 at its natural
    stage boundaries (reference topology DL/models/resnet/ResNet.scala).

    `boundaries`: child indices where stages START (len n_stages-1,
    strictly increasing); default: even split by child count."""
    from bigdl_tpu import nn as _nn
    children = list(model.children)
    n = len(children)
    if n < n_stages:
        raise ValueError(f"{n} children cannot make {n_stages} stages")
    if boundaries is None:
        step = n / n_stages
        boundaries = [round(step * i) for i in range(1, n_stages)]
    cuts = [0] + list(boundaries) + [n]
    if sorted(set(cuts)) != cuts:
        raise ValueError(f"boundaries must be strictly increasing: {cuts}")
    stages = []
    for a, b in zip(cuts, cuts[1:]):
        st = _nn.Sequential(name=f"stage{len(stages)}")
        for child in children[a:b]:
            st.add(child)
        stages.append(st)
    return stages
