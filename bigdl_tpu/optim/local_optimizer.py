"""Single-chip training loop.

Parity: DL/optim/LocalOptimizer.scala:45 — the in-process optimizer. The
reference clones N thread-replicas with shared weights and sums their
gradients (LocalOptimizer.scala:64-82); on TPU the replicas disappear: one
jitted train step consumes the whole batch, XLA owns the parallelism. The
driver loop (triggers, LR schedule, checkpoint, validation, summary,
throughput logging) mirrors the reference's structure so behavior and logs
line up with DistriOptimizer.scala:405-410.
"""

from __future__ import annotations

import contextlib
import logging
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.criterion import Criterion
from bigdl_tpu.nn.module import Module, functional_apply, merge_state
from bigdl_tpu.optim.metrics import Metrics, Timer
from bigdl_tpu.optim.optim_method import OptimMethod, SGD
from bigdl_tpu.optim.trigger import Trigger, every_epoch
from bigdl_tpu.optim.validation import ValidationMethod
from bigdl_tpu.resilience import faults
from bigdl_tpu.utils.table import Table

logger = logging.getLogger("bigdl_tpu.optim")


def _to_device(x):
    if x is None:  # FakeCriterion graphs carry no target
        return None
    if isinstance(x, (list, tuple)):
        return Table(*[_to_device(v) for v in x])
    if isinstance(x, np.ndarray) and x.dtype.kind in ("U", "S", "O"):
        return x  # string/bytes columns stay host-side (feature-col ops)
    return jnp.asarray(x)


class BaseOptimizer:
    """Shared driver-loop machinery for Local/Distri optimizers."""

    def __init__(self, model: Module, dataset, criterion: Criterion):
        self.model = model
        self.dataset = dataset
        self.criterion = criterion
        self.optim_method: OptimMethod = SGD()
        self.end_trigger: Trigger = every_epoch()
        self.checkpoint_trigger: Optional[Trigger] = None
        self.checkpoint_path: Optional[str] = None
        self.overwrite_checkpoint = True
        self.validation_trigger: Optional[Trigger] = None
        self.validation_dataset = None
        self.validation_methods: List[ValidationMethod] = []
        self.train_summary = None
        self.validation_summary = None
        self.grad_clip_norm: Optional[float] = None
        self.grad_clip_const: Optional[tuple] = None
        self.metrics = Metrics()
        self.telemetry = None
        self.tracer = None
        self.worker_tracers: Dict = {}  # worker_id -> per-lane SpanTracer
        self.health_monitors: List = []
        self.rng = jax.random.PRNGKey(0)
        self.matmul_precision: Optional[str] = None
        self.sync_interval: int = 1
        self.iteration_hook: Optional[Callable[[Dict], None]] = None
        self.graph_optimizations = False
        self.grad_accum_steps: int = 1
        self._prefetch: Optional[Dict] = None
        self._active_pipeline = None
        self._preemption = None
        self._resume_cursor = None
        # host snapshot for donation-safe failure recovery: the jitted
        # step donates the model's device arrays, so an aborted run must
        # restore the model from this instead of leaving it holding
        # deleted buffers
        self._pristine_params = None
        self._pristine_state = None

    # fluent setters (Optimizer.scala:93-452)
    def set_gradient_accumulation(self, steps: int):
        """Split each batch into `steps` micro-batches inside the jitted
        step (lax.scan), accumulating gradients before one weight update —
        peak activation memory drops ~steps-fold for the same effective
        batch (beyond-parity TPU feature; batch size must divide evenly)."""
        if steps < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")
        self.grad_accum_steps = int(steps)
        return self

    def set_optim_method(self, method: OptimMethod):
        self.optim_method = method
        return self

    setOptimMethod = set_optim_method

    def set_optim_methods(self, methods: Dict[str, "OptimMethod"]):
        """Per-submodule optimization methods keyed by top-level child
        name (Optimizer.scala:120 setOptimMethods)."""
        from bigdl_tpu.optim.optim_method import CompositeOptimMethod
        self.optim_method = CompositeOptimMethod(self.model, methods)
        return self

    setOptimMethods = set_optim_methods

    def set_end_when(self, trigger: Trigger):
        self.end_trigger = trigger
        return self

    setEndWhen = set_end_when

    def set_checkpoint(self, path: str, trigger: Trigger,
                       sharded: bool = False,
                       keep_last_n: Optional[int] = None):
        """`sharded=True` writes the array payload via orbax with every
        process saving only its addressable shards (multi-host scale
        path, serialization/sharded_checkpoint.py); default is the
        host-side durable pickle format (atomic rename + sha256 digests,
        serialization/checkpoint.py). `keep_last_n` bounds disk: after
        each successful save the oldest valid checkpoints beyond the
        newest n are pruned."""
        if keep_last_n is not None and keep_last_n < 1:
            # fail at configure time, not at the first trigger mid-run
            raise ValueError(
                f"keep_last_n must be >= 1, got {keep_last_n}")
        self.checkpoint_path = path
        self.checkpoint_trigger = trigger
        self.checkpoint_sharded = sharded
        self.checkpoint_keep_last_n = keep_last_n
        return self

    setCheckpoint = set_checkpoint

    def resume_from_latest_checkpoint(self) -> bool:
        """Cold-start resume: load the newest checkpoint under
        `checkpoint_path` into the model/optim method before `optimize()`.

        This is the reference's job-level recovery contract
        (DL/optim/DistriOptimizer.scala:862-943 retries reload the newest
        snapshot; a RESUBMITTED job with the same checkpoint dir does the
        same through getLatestFile) at real process granularity: a fresh
        process calls this after a crash/SIGKILL and continues the run —
        params, optimizer slots (Adam moments / SGD velocity), epoch and
        iteration counters, and the mid-epoch data position all resume.
        Returns False when there is nothing to resume from.

        Resilience: loads through `load_latest_valid` — checkpoints are
        digest-verified on read, a corrupt newest snapshot is quarantined
        (telemetry `checkpoint_quarantined`) and resume falls back to the
        next older one instead of dying on an unpickling error."""
        from bigdl_tpu.serialization.checkpoint import (load_latest_valid,
                                                        restore_optim_method)
        if getattr(self, "checkpoint_path", None) is None:
            return False
        got = load_latest_valid(self.checkpoint_path,
                                telemetry=self.telemetry)
        if got is None:
            return False
        _, params, mstate, oblob = got
        self.model.set_params(params)
        self.model._state = mstate or {}
        restore_optim_method(self.optim_method, oblob)
        if oblob.get("slots") is not None:
            self._resume_slots = oblob["slots"]
        # data-iterator cursor (v2 checkpoints since the elastic PR):
        # pass-start rng state + item order + boundary-shuffle positions,
        # restored by _fast_forward_data so the resumed stream continues
        # mid-epoch exactly without replaying completed passes
        self._resume_cursor = oblob.get("cursor")
        # tells the next optimize()'s _fast_forward_data that completed
        # epochs must be replayed (fresh process, dataset rng at origin) —
        # a warm re-optimize() on a live instance must NOT replay
        self._resumed = True
        return True

    def _fast_forward_data(self, data_iter, driver_state):
        """Replay the already-consumed data so a resumed run continues at
        the position the checkpoint was taken at (reference
        recordsProcessedThisEpoch semantics, DistriOptimizer.scala:130).

        Completed epochs replay as full dataset passes with the same
        `shuffle()` call the original run made at each boundary — the
        iterator's per-pass permutations and the shuffles draw from the
        SAME dataset-owned seeded rng, so a fresh process reproduces the
        identical draw sequence.

        Interleaving detail that makes the replay EXACT: the live loops
        prefetch one batch (the next iteration's) right after dispatching
        a step, i.e. BEFORE the epoch-boundary bookkeeping runs
        `dataset.shuffle()`. So at every boundary the original run drew
        the next pass's permutation from the rng before the shuffle — the
        replay peels that one batch ahead of each shuffle() to reproduce
        the draw order, then credits it against the next epoch's consumed
        records (chaining it back into the stream if the checkpoint
        landed exactly on the boundary, where the prefetched batch was
        never trained on)."""
        num_hosts = getattr(self.dataset, "num_hosts", 1)
        # Completed-epoch replay applies only to a COLD resume (fresh
        # process, dataset rng at its origin). A warm re-optimize() on a
        # live instance continues with an already-advanced dataset rng —
        # replaying there would burn a pass of host fetches and shuffle
        # the stream out from under epoch 2. driver_state["epoch"] is the
        # live loops' 0-based completed-epoch counter (starts 0, +1 per
        # boundary).
        cold_resume = getattr(self, "_resumed", False)
        self._resumed = False
        cursor = getattr(self, "_resume_cursor", None)
        self._resume_cursor = None
        if cold_resume and cursor is not None \
                and self._active_pipeline is None \
                and hasattr(self.dataset, "restore_cursor"):
            # checkpoint carried a data cursor: rewind the dataset itself
            # (rng state + item order + boundary shuffles + the trained
            # item offset, all as of the checkpoint's pass) instead of
            # replaying completed passes — the resumed stream continues
            # at the exact next untrained item. Skipped under prefetch
            # (workers are already pulling — the cursor cannot be
            # installed under them) and on datasets without cursor
            # support, where the full-pass replay below remains the
            # resume path.
            try:
                self.dataset.restore_cursor(cursor)
            except Exception as e:
                logger.warning("data cursor restore failed (%r); falling "
                               "back to full-pass replay", e)
            else:
                return data_iter
        epochs_done = max(0, driver_state.get("epoch", 0)) if cold_resume \
            else 0
        pass_items = self.dataset.size()
        pending = None  # the boundary-prefetched batch, not yet credited
        for _ in range(epochs_done):
            seen = pending.size() if pending is not None else 0
            while seen < pass_items:
                b = next(data_iter, None)
                if b is None:
                    return data_iter
                seen += b.size()
            pending = next(data_iter, None)  # live prefetch pre-shuffle
            self._shuffle_dataset()
        already = driver_state.get("recordsProcessedThisEpoch", 0) \
            // max(num_hosts, 1)
        skipped = pending.size() if pending is not None else 0
        if pending is not None and skipped > already:
            import itertools
            return itertools.chain([pending], data_iter)
        while skipped < already:
            b = next(data_iter, None)
            if b is None:
                break
            skipped += b.size()
        return data_iter

    def set_validation(self, trigger: Trigger, dataset, methods: Sequence[ValidationMethod],
                       batch_size: Optional[int] = None):
        self.validation_trigger = trigger
        self.validation_dataset = dataset
        self.validation_methods = list(methods)
        self.validation_batch_size = batch_size or 32
        return self

    setValidation = set_validation

    def set_train_summary(self, summary):
        self.train_summary = summary
        return self

    setTrainSummary = set_train_summary

    def set_validation_summary(self, summary):
        self.validation_summary = summary
        return self

    setValidationSummary = set_validation_summary

    def set_gradient_clipping_by_l2_norm(self, clip_norm: float):
        self.grad_clip_norm = clip_norm
        return self

    setGradientClippingByl2Norm = set_gradient_clipping_by_l2_norm

    def set_constant_gradient_clipping(self, min_v: float, max_v: float):
        self.grad_clip_const = (min_v, max_v)
        return self

    setConstantGradientClipping = set_constant_gradient_clipping

    def disable_gradient_clipping(self):
        self.grad_clip_norm = None
        self.grad_clip_const = None
        return self

    def set_compute_precision(self, precision: Optional[str]):
        """Compute precision for the train step.

        "bfloat16" = standard TPU mixed precision: f32 master weights and
        optimizer slots, but the forward/backward runs with params and
        float activations cast to bf16 (half the HBM traffic, MXU-native
        matmuls; grads come back f32 through the cast's adjoint). BN
        statistics stay f32 (normalization.py upcasts internally) and the
        loss is computed on an f32-upcast model output. The reference's
        analogue is fp32 master weights with fp16 wire compression
        (FP16CompressedTensor.scala:143) — here the half-precision is the
        COMPUTE dtype, not just the wire format.

        "bfloat16-matmul" = the weaker knob: only `dot/conv` inputs are
        reduced to one bf16 MXU pass (jax.default_matmul_precision);
        everything stays f32 in memory. "float32"/"highest" = three-pass
        f32 matmuls."""
        self.matmul_precision = precision
        return self

    def set_sync_interval(self, k: int):
        """Fetch the loss to host every k-th iteration instead of every
        iteration (default 1 = reference semantics: a loss line per step,
        DistriOptimizer.scala:405-410).

        With k > 1 the driver dispatches steps asynchronously and only
        blocks on the device every k iterations, hiding host->device
        dispatch latency. In between, logged loss / min_loss triggers see the last
        synced value (k-1 iterations stale, at most); throughput is
        reported per sync window. Validation, checkpointing, and the final
        returned model still see fully-updated state (steps are chained by
        donation, so syncing step k implies steps 1..k completed)."""
        self.sync_interval = max(1, int(k))
        return self

    def set_prefetch(self, depth: Optional[int] = None,
                     workers: Optional[int] = None,
                     deterministic: bool = True,
                     retry_policy=None):
        """Enable the pipelined host data plane (dataset/prefetch.py):
        background worker threads run the transformer chain into a bounded
        queue so the driver only pays a queue pop before starting the next
        async H2D transfer — the reference's concurrent data-fetch task
        (DistriOptimizer.scala:330-339) plus MTImageFeatureToBatch's
        thread-pool batching, in one subsystem.

        `workers` defaults to `Engine.io_threads`; `depth` (total
        lookahead: ready + in-flight batches) defaults to 4x workers —
        deep enough that the driver thread never drains it while worker
        refill bursts wait out the driver's GIL slices.
        `deterministic=True` keeps batch order byte-identical to serial
        iteration (reordering buffer); `False` yields in completion order.
        `retry_policy` (a `resilience.RetryPolicy`) arms bounded
        in-worker retry of transient per-item failures (flaky remote
        reads) without breaking deterministic ordering.
        Caveat: across EPOCH BOUNDARIES the `shuffle()` interleaving is
        timing-dependent under prefetch, so multi-epoch streams (and
        their checkpoint-resume replay) are approximate — disable
        prefetch for workflows that need exact multi-epoch replay (see
        `_shuffle_dataset`). `set_prefetch(depth=0)` disables. Threads
        are started per `optimize()` call and joined before it returns —
        also on failure."""
        if depth == 0:
            self._prefetch = None
            return self
        if workers is None:
            from bigdl_tpu.utils.engine import Engine
            workers = int(Engine.config["io_threads"])
        if workers < 1:
            raise ValueError(f"workers must be >= 1, got {workers}")
        if depth is None:
            depth = 4 * workers
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self._prefetch = {"depth": int(depth), "workers": int(workers),
                          "deterministic": bool(deterministic),
                          "retry_policy": retry_policy}
        return self

    setPrefetch = set_prefetch

    def _open_data_pipeline(self):
        """Training-stream source for _optimize_impl: a prefetching
        InputPipeline when set_prefetch is armed (stash it for telemetry
        gauges + the finally-close), else the plain dataset iterator."""
        if self._prefetch is None:
            self._active_pipeline = None
            return None, self.dataset.data(train=True)
        from bigdl_tpu.dataset.prefetch import build_input_pipeline
        pipeline = build_input_pipeline(self.dataset, train=True,
                                        **self._prefetch)
        self._active_pipeline = pipeline
        return pipeline, pipeline

    def _close_data_pipeline(self, pipeline):
        self._active_pipeline = None
        if pipeline is not None:
            pipeline.close()

    def _shuffle_dataset(self):
        """Epoch-boundary reshuffle. With prefetch armed the shuffle is
        made atomic against worker pulls (pipeline source_guard), but
        WHERE it lands between pulls depends on thread timing — so
        cross-epoch-boundary streams are NOT exactly reproducible under
        prefetch, and a cold checkpoint resume of a multi-epoch
        prefetched run replays an approximate stream (the
        _fast_forward_data exact-replay contract assumes the serial
        loop's one-batch lookahead). Runs needing exact multi-epoch
        replay should train with prefetch disabled; within one epoch
        deterministic mode is exact (suite-asserted)."""
        if self._active_pipeline is not None:
            with self._active_pipeline.source_guard():
                self.dataset.shuffle()
        else:
            self.dataset.shuffle()

    def set_iteration_hook(self, fn: Optional[Callable[[Dict], None]]):
        """Call `fn(driver_state)` after every completed iteration (used by
        perf drivers and external monitors)."""
        self.iteration_hook = fn
        return self

    def set_preemption_handler(self, handler=None, grace_s: float = 30.0):
        """Arm preemption handling (resilience/preemption.py): while
        `optimize()` runs, SIGTERM opens a grace window — the loop drains
        the in-flight step at the next iteration boundary, writes an
        immediate durable v2 checkpoint (with the data cursor), emits a
        `preempted` event plus a clean `run_abort`, and returns early.
        The previous signal disposition is restored when `optimize()`
        exits. Pass a configured `PreemptionHandler` to control the
        signal set / grace window, or rely on the default (SIGTERM,
        `grace_s`). `set_preemption_handler(handler=False)` disarms."""
        if handler is False:
            self._preemption = None
            return self
        if handler is None:
            from bigdl_tpu.resilience.preemption import PreemptionHandler
            handler = PreemptionHandler(grace_s=grace_s)
        self._preemption = handler
        return self

    def _check_preemption(self, params, model_state, opt_slots,
                          driver_state, loss) -> bool:
        """Iteration-boundary poll of the preemption latch. On a
        triggered handler: drain the in-flight step (the snapshot must be
        a completed step's state), write the immediate checkpoint, emit
        `preempted` + `run_abort`, and tell the loop to stop (True)."""
        h = self._preemption
        if h is None or not h.triggered:
            return False
        logger.warning(
            "preemption (signal %s): draining and checkpointing at "
            "iteration %d (%.1fs of grace remaining)", h.signum,
            driver_state.get("neval", 0), h.deadline_remaining() or 0.0)
        if loss is not None:
            try:  # drain: the loss fetch is the step-completion barrier
                float(loss)
            except Exception:
                pass
        checkpointed = False
        if self.checkpoint_path is not None:
            try:
                self._save_checkpoint(
                    params, model_state,
                    tag=f"iter{driver_state.get('neval', 0)}",
                    opt_slots=opt_slots)
                checkpointed = True
            except Exception:
                logger.exception("preemption checkpoint failed; aborting "
                                 "without one")
        if self.telemetry is not None:
            self.telemetry.event(
                "preempted", step=driver_state.get("neval", 0),
                signal=h.signum, checkpointed=checkpointed,
                grace_remaining_s=round(h.deadline_remaining() or 0.0, 3))
        from bigdl_tpu.resilience.preemption import PreemptedError
        self._telemetry_run_abort(
            PreemptedError(f"preempted by signal {h.signum}"))
        return True

    def set_telemetry(self, telemetry):
        """Attach a structured run-metrics collector
        (observability.Telemetry): one `step` record per sync point plus
        run_start/run_end, fanned out to its sinks. With
        `Telemetry(grad_norms=True)` the jitted step also computes the
        global gradient/parameter L2 norms per step. Step records carry
        cost attribution (`flops_per_step`, `bytes_accessed`, `mfu`) read
        off the compiled step executable, and every distinct step
        signature emits one `compile` record."""
        self.telemetry = telemetry
        self._link_flight()
        return self

    setTelemetry = set_telemetry

    def set_tracer(self, tracer):
        """Attach a SpanTracer: the loop's host phases (data fetch, step
        dispatch, loss sync, validation, checkpoint) record as nested
        spans, exportable as Chrome/Perfetto trace JSON
        (observability.spans)."""
        self.tracer = tracer
        self._link_flight()
        return self

    setTracer = set_tracer

    def _link_flight(self):
        """Give the telemetry's crash flight recorder (when both are
        attached) the tracer, so auto-dumps carry the span tail next to
        the record tail."""
        flight = getattr(self.telemetry, "flight", None)
        if flight is not None and self.tracer is not None:
            flight.attach_tracer(self.tracer)

    def set_health_monitors(self, *monitors):
        """Attach health monitors (observability.health): each observes
        every sync-point step record. A NanGuard with action="skip"
        additionally arms the in-step update revert for non-finite
        steps — set it BEFORE optimize() so the step compiles with the
        guard."""
        self.health_monitors = list(monitors)
        return self

    setHealthMonitors = set_health_monitors

    def set_graph_optimizations(self, enable: bool = True):
        """Run the IR restatement passes over the model before building
        the train step (`ir.ConversionUtils.apply_tpu_restatements`):
        math-preserving rewrites with identical parameter trees (e.g.
        the space-to-depth stem), so checkpoints stay interchangeable.
        Off by default; the restatements pay on TPU MXU tiling."""
        self.graph_optimizations = enable
        return self

    def _maybe_optimize_graph(self):
        if getattr(self, "graph_optimizations", False):
            from bigdl_tpu.ir import ConversionUtils
            self.model = ConversionUtils.apply_tpu_restatements(self.model)

    def _precision_scope(self):
        if self.matmul_precision is None:
            return contextlib.nullcontext()
        prec = {"bfloat16-matmul": "bfloat16"}.get(self.matmul_precision,
                                                   self.matmul_precision)
        return jax.default_matmul_precision(prec)

    @property
    def _mixed_bf16(self) -> bool:
        return self.matmul_precision == "bfloat16"

    @staticmethod
    def _cast_floats(tree, dtype):
        """Cast float leaves of a pytree (params / activations / Table
        inputs) to `dtype`, leaving ints/bools (labels, indices) alone."""
        def cast(leaf):
            if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype,
                                                         jnp.floating):
                return leaf.astype(dtype)
            return leaf
        return jax.tree_util.tree_map(cast, tree)

    # -- observability helpers --
    def _span(self, name: str, **args):
        """Tracer span when a tracer is attached, else a free nullcontext
        (the loops call this on every iteration — no tracer, no cost)."""
        if self.tracer is None:
            return contextlib.nullcontext()
        return self.tracer.span(name, **args)

    def _worker_span(self, worker_id, name: str, **args):
        """Span on a PER-WORKER tracer (elastic per-replica dispatch):
        each fleet worker gets its own process lane — `export_trace`
        merges them with the driver lane into one Perfetto file. The
        span joins the driver's active trace (same trace_id) so one
        step's shard dispatches filter together across lanes."""
        if self.tracer is None or worker_id is None:
            return contextlib.nullcontext()
        wt = self.worker_tracers.get(worker_id)
        if wt is None:
            from bigdl_tpu.observability.spans import SpanTracer
            wt = SpanTracer(process_name=f"worker:{worker_id}",
                            annotate=False)
            self.worker_tracers[worker_id] = wt
        ctx = None
        cur = getattr(self.tracer, "current_context", lambda: None)()
        if cur is not None:
            ctx = cur.child()
        return wt.span(name, cat="elastic", ctx=ctx, **args)

    def export_trace(self, path: str) -> str:
        """Write ONE Perfetto/Chrome trace file: the driver tracer plus
        every per-worker elastic lane (distinct process lanes per
        worker). Requires `set_tracer`."""
        if self.tracer is None:
            raise ValueError("no tracer attached; call set_tracer first")
        from bigdl_tpu.observability.spans import export_merged
        return export_merged(
            path, [self.tracer, *self.worker_tracers.values()])

    def _nan_guard(self):
        from bigdl_tpu.observability.health import NanGuard
        for m in self.health_monitors:
            if isinstance(m, NanGuard):
                return m
        return None

    @staticmethod
    def _lr_scalar(lr) -> float:
        """Scalar view of the current lr (composite methods carry a tuple
        of per-group rates — report their mean, reference log parity)."""
        if isinstance(lr, tuple):
            return float(np.mean([v for v in lr if v]) if any(lr) else 0.0)
        return float(lr)

    @staticmethod
    def _global_norm(tree):
        """Global L2 norm over the float leaves of a pytree (traced)."""
        leaves = [l for l in jax.tree_util.tree_leaves(tree)
                  if hasattr(l, "dtype") and jnp.issubdtype(l.dtype,
                                                            jnp.floating)]
        if not leaves:
            return jnp.float32(0.0)
        return jnp.sqrt(sum(jnp.sum(l.astype(jnp.float32) ** 2)
                            for l in leaves))

    def _aux_flags(self):
        """Build-time instrumentation config for the jitted step:
        (nan_guard, need_norms)."""
        guard = self._nan_guard()
        need_norms = bool(
            (self.telemetry is not None and self.telemetry.grad_norms)
            or (guard is not None and guard.check_grads))
        return guard, need_norms

    @staticmethod
    def _revert_partial_state(bad, new_ms, old_ms):
        """Skip-mode revert for the model state, honoring the module
        contract that new_state may be a PARTIAL update with a different
        dict structure than the full state (module.py functional_apply:
        "merge with the old state dict outside") — a plain tree_map of
        new vs old would crash on the mismatch. Each new leaf reverts to
        its old value where one exists; a key with no old counterpart
        (first update of a freshly-loaded/set_params model) keeps the new
        value — there is nothing to revert to."""
        if isinstance(new_ms, dict):
            old = old_ms if isinstance(old_ms, dict) else {}
            return {k: BaseOptimizer._revert_partial_state(bad, v,
                                                           old.get(k))
                    for k, v in new_ms.items()}
        if old_ms is None:
            return new_ms
        return jnp.where(bad, old_ms, new_ms)

    def _apply_step_guards(self, guard, need_norms, loss, grads, old, new):
        """Traced tail of the step: non-finite detection (and, for a
        skip-mode NanGuard, the update revert via jnp.where — donation-safe
        because it selects between traced values, not buffers) plus the
        optional grad/param norms. `old`/`new` are (params, opt_state,
        model_state) triples; returns (new, aux). aux is {} when no
        instrumentation is armed, so the uninstrumented step is unchanged."""
        aux = {}
        gnorm = self._global_norm(grads) if need_norms else None
        if guard is not None:
            bad = ~jnp.isfinite(loss)
            if guard.check_grads:
                bad = bad | ~jnp.isfinite(gnorm)
            aux["nonfinite"] = bad.astype(jnp.int32)
            if guard.action == "skip":
                keep = lambda n, o: jnp.where(bad, o, n)
                # params and opt slots always share their old structure;
                # model state may be a partial update — revert per key
                new = (jax.tree_util.tree_map(keep, new[0], old[0]),
                       jax.tree_util.tree_map(keep, new[1], old[1]),
                       self._revert_partial_state(bad, new[2], old[2]))
        if need_norms:
            aux["grad_norm"] = gnorm
            aux["param_norm"] = self._global_norm(new[0])
        return new, aux

    @property
    def _n_compute_devices(self) -> int:
        """Devices the step's FLOP count is spread over (MFU denominator):
        1 for the local loop; the mesh size for DistriOptimizer."""
        return 1

    def _observe_sync(self, driver_state, loss_val, lr, throughput,
                      step_time_s, records, aux_pending):
        """Host side of a sync point: resolve the pending in-step aux
        scalars (ONE batched device_get), assemble the step record, run the
        health monitors, emit telemetry. No-op when neither is attached."""
        if self.telemetry is None and not self.health_monitors:
            return
        rec = {"step": driver_state["neval"],
               "epoch": driver_state["epoch"] + 1,
               "loss": loss_val, "lr": self._lr_scalar(lr),
               "throughput": throughput, "step_time_s": step_time_s,
               "records": records}
        info = getattr(getattr(self, "_step_fn", None), "last_info", None)
        if info is not None:
            # cost attribution off the compiled step executable
            # (observability/costs.py): the SPMD step's FLOP count covers
            # the global batch, so MFU divides by the whole-mesh peak —
            # null (never fabricated) on chips outside the registry
            from bigdl_tpu.observability import costs
            rec["flops_per_step"] = info.get("flops")
            rec["bytes_accessed"] = info.get("bytes_accessed")
            rec["mfu"] = costs.mfu(info.get("flops"), step_time_s,
                                   n_devices=self._n_compute_devices)
        if self._active_pipeline is not None:
            # input-pipeline health gauges (docs/observability.md):
            # instantaneous ready-batch depth, cumulative driver
            # fetch-wait, worker-pool busy fraction
            rec.update(self._active_pipeline.health())
        if aux_pending:
            vals = jax.device_get(list(aux_pending))
            aux_pending.clear()
            if "nonfinite" in vals[-1]:
                rec["nonfinite_steps"] = int(sum(int(v["nonfinite"])
                                                 for v in vals))
            if "grad_norm" in vals[-1]:
                rec["grad_norm"] = float(vals[-1]["grad_norm"])
                rec["param_norm"] = float(vals[-1]["param_norm"])
        for m in self.health_monitors:
            m.observe(rec, self.telemetry)
        if self.telemetry is not None:
            self.telemetry.step(**rec)

    def _telemetry_run_start(self, loop: str):
        if self.tracer is not None and hasattr(self.tracer, "begin_trace"):
            # root trace for the run: every loop span (data fetch, step
            # dispatch, loss sync, ...) becomes a child with this
            # trace_id, so one run filters cleanly out of a merged trace
            self.tracer.begin_trace(f"optimize/{loop}", cat="train",
                                    loop=loop)
        if self.telemetry is None:
            return
        self.telemetry.run_start(
            loop=loop, model=type(self.model).__name__,
            optim_method=type(self.optim_method).__name__,
            backend=jax.default_backend(), n_devices=jax.device_count(),
            sync_interval=max(1, int(getattr(self, "sync_interval", 1))))

    def _end_run_trace(self):
        if self.tracer is not None and hasattr(self.tracer, "end_trace"):
            self.tracer.end_trace()

    def _telemetry_run_end(self, driver_state):
        self._end_run_trace()
        if self.telemetry is None:
            return
        self.telemetry.run_end(step=driver_state["neval"],
                               epoch=driver_state["epoch"],
                               loss=driver_state.get("loss"),
                               metrics=self.metrics.as_dict())

    def _telemetry_run_abort(self, error):
        """Terminal marker for a run that dies mid-loop, so every
        run_start in the stream pairs with run_end, run_retry, or
        run_abort (a hard process kill can still truncate the stream)."""
        self._end_run_trace()
        if self.telemetry is not None:
            self.telemetry.event("run_abort", error=repr(error))

    # -- helpers --
    class _SyncWindow:
        """Throughput/compute-time bookkeeping over sync windows, shared
        by the local and distributed loops. A window spans device-drained
        point to device-drained point and counts ONLY the dispatch+device
        portion of each iteration: `restart()` is called at the END of
        the iteration body (after validation/checkpoint/summary/hooks),
        so that host-side tail work never inflates the next window's
        training throughput."""

        def __init__(self):
            self.records = 0
            self.iters = 0
            self.t0 = time.perf_counter()
            self.step_time_s = float("nan")

        def add(self, n: int):
            self.records += n
            self.iters += 1

        def throughput(self, metrics) -> float:
            """At a sync point: window throughput; records the
            per-iteration compute-time metric (also kept on
            `step_time_s` for the telemetry step record)."""
            dt = max(time.perf_counter() - self.t0, 1e-9)
            self.step_time_s = dt / max(self.iters, 1)
            metrics.add("computing time average", self.step_time_s * 1e9)
            return self.records / dt

        def restart(self):
            self.records, self.iters = 0, 0
            self.t0 = time.perf_counter()

    def _clip_grads_expr(self, grads):
        """Build the clipping expression (traced under jit). Parity:
        ParameterOperations.scala:71 (constant) and :89 (global L2 norm)."""
        if self.grad_clip_const is not None:
            lo, hi = self.grad_clip_const
            grads = jax.tree_util.tree_map(lambda g: jnp.clip(g, lo, hi), grads)
        if self.grad_clip_norm is not None:
            leaves = jax.tree_util.tree_leaves(grads)
            total = jnp.sqrt(sum(jnp.sum(l.astype(jnp.float32) ** 2) for l in leaves))
            scale = jnp.minimum(1.0, self.grad_clip_norm / (total + 1e-12))
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        return grads

    def _save_checkpoint(self, params, model_state, tag, opt_slots=None):
        if self.checkpoint_path is None:
            return
        if getattr(self, "checkpoint_sharded", False):
            from bigdl_tpu.serialization.sharded_checkpoint import (
                save_checkpoint_sharded)
            save_checkpoint_sharded(self.checkpoint_path, self.model,
                                    params, model_state, self.optim_method,
                                    opt_slots=opt_slots, tag=tag)
            keep = getattr(self, "checkpoint_keep_last_n", None)
            if keep is not None and jax.process_index() == 0:
                # retention applies to sharded checkpoints too; only the
                # lead process prunes (every host scans the same store)
                from bigdl_tpu.serialization.checkpoint import (
                    prune_checkpoints)
                prune_checkpoints(self.checkpoint_path, keep)
            return
        from bigdl_tpu.serialization.checkpoint import save_checkpoint
        save_checkpoint(self.checkpoint_path, self.model, params, model_state,
                        self.optim_method, opt_slots=opt_slots, tag=tag,
                        overwrite=self.overwrite_checkpoint,
                        keep_last_n=getattr(self, "checkpoint_keep_last_n",
                                            None),
                        cursor=self._data_cursor())

    def _data_cursor(self):
        """The dataset's iteration cursor for checkpointing, pointed at
        the last TRAINED batch (`_cursor_prev_pos` — one pull behind the
        loop's lookahead), or None when the dataset does not support one
        (custom AbstractDataSet), the stream position is currently not
        trustworthy (mid elastic replay, prefetch pipeline), or the
        capture fails — a checkpoint must never fail over its cursor."""
        cur = getattr(self.dataset, "cursor", None)
        if cur is None or not getattr(self, "_cursor_valid", True) \
                or self._active_pipeline is not None:
            return None
        try:
            return cur(position=getattr(self, "_cursor_prev_pos", None))
        except Exception as e:
            logger.warning("data cursor capture failed (%r); checkpoint "
                           "saved without one", e)
            return None

    def _init_cursor_positions(self):
        """Anchor the pull-position trackers at the stream's current
        (post-resume-skip) position; called right before the driver's
        first pull of a run."""
        self._cursor_valid = True
        pos = getattr(self.dataset, "position", None)
        if pos is None:
            self._cursor_prev_pos = self._cursor_last_pos = None
            return
        try:
            p = pos()
        except Exception:
            p = None
        self._cursor_prev_pos = self._cursor_last_pos = p

    def _note_pull(self):
        """Record the stream position after a successful live pull: the
        PREVIOUS sample then points at the last trained batch — exactly
        what a checkpoint's data cursor must reference (the newest pull
        is the loop's untrained lookahead). Re-validates the cursor after
        an elastic replay window drains (a real pull means everything
        buffered has been retrained)."""
        pos = getattr(self.dataset, "position", None)
        if pos is None:
            return
        try:
            p = pos()
        except Exception:
            return
        self._cursor_prev_pos = getattr(self, "_cursor_last_pos", None)
        self._cursor_last_pos = p
        self._cursor_valid = True

    def _validation_batches(self):
        """Yield MiniBatches whether the dataset holds Samples or batches."""
        from bigdl_tpu.dataset.sample import Sample
        from bigdl_tpu.dataset.transformer import SampleToMiniBatch
        it = iter(self.validation_dataset.data(train=False)
                  if hasattr(self.validation_dataset, "data")
                  else self.validation_dataset)
        first = next(it, None)
        if first is None:
            return
        import itertools
        chained = itertools.chain([first], it)
        if isinstance(first, Sample):
            bs = getattr(self, "validation_batch_size", 32)
            yield from SampleToMiniBatch(bs)(chained)
        else:
            yield from chained

    def _validate(self, params, model_state, driver_state):
        if not (self.validation_trigger and self.validation_dataset
                and self.validation_trigger(driver_state)):
            return None
        results = [None] * len(self.validation_methods)
        for batch in self._validation_batches():
            x = _to_device(batch.get_input())
            y = _to_device(batch.get_target())
            out, _ = functional_apply(self.model, params, x,
                                      state=model_state, training=False)
            for i, m in enumerate(self.validation_methods):
                r = m.apply(out, y)
                results[i] = r if results[i] is None else results[i] + r
        for m, r in zip(self.validation_methods, results):
            logger.info(f"{m!r} is {r!r}")
            if self.validation_summary is not None and r is not None:
                val, _ = r.result()
                self.validation_summary.add_scalar(
                    repr(m), val, driver_state["neval"])
        if results and results[0] is not None:
            driver_state["score"] = results[0].result()[0]
            # feed Plateau-style schedules
            sched = getattr(self.optim_method, "schedule", None)
            if sched is not None and hasattr(sched, "record"):
                sched.record(driver_state["score"], self.optim_method)
        return results

    # -- the train step: one loss closure, one body, one compile choice --
    def _loss_and_grads(self):
        """The loss closure every loop differentiates: `(params,
        model_state, x, y, rng) -> ((loss, new_ms), grads)`. The step body
        calls it once or once per micro-batch; the elastic loop jits it
        as its per-shard executable."""
        model, criterion = self.model, self.criterion
        precision_scope = self._precision_scope
        mixed = self._mixed_bf16
        cast = self._cast_floats

        def loss_and_grads(params, model_state, x, y, rng):
            def loss_fn(p):
                with precision_scope():
                    # mixed precision: bf16 compute, f32 masters — the cast
                    # sits INSIDE value_and_grad so its adjoint upcasts the
                    # gradients back to f32 before clip/update
                    xc = cast(x, jnp.bfloat16) if mixed else x
                    if mixed:
                        p = cast(p, jnp.bfloat16)
                    out, new_ms = functional_apply(model, p, xc,
                                                   state=model_state,
                                                   training=True, rng=rng)
                    if mixed:
                        out = cast(out, jnp.float32)
                    with jax.named_scope("loss"):
                        return criterion.apply(out, y), new_ms
            return jax.value_and_grad(loss_fn, has_aux=True)(params)

        return loss_and_grads

    def _step_body(self, constrain_state=None):
        """The train step, traced: `step(params, opt_state, model_state,
        x, y, lr, rng) -> (params, opt_state, model_state, loss, rng,
        aux)`. `constrain_state`, the one thing a loop adds, is applied to
        the new model state (the SPMD loop holds it replicated)."""
        loss_and_grads = self._loss_and_grads()
        optim = self.optim_method
        clip = self._clip_grads_expr
        accum = int(self.grad_accum_steps or 1)
        guard, need_norms = self._aux_flags()
        guards = self._apply_step_guards

        def step(params, opt_state, model_state, x, y, lr, rng):
            # rng chain lives ON DEVICE: split inside the jitted step and
            # return the successor, so the host never dispatches a separate
            # split per iteration
            rng, step_rng = jax.random.split(rng)
            if accum > 1:
                # gradient accumulation: split the batch into `accum`
                # micro-batches and lax.scan the grad computation, so peak
                # activation memory shrinks by ~accum while the weight
                # update sees the FULL batch gradient (mean over micros).
                def micro(xy):
                    return jnp.reshape(
                        xy, (accum, xy.shape[0] // accum) + xy.shape[1:])

                def body(carry, mb):
                    g_acc, l_acc, ms = carry
                    mx, my, mrng = mb
                    (l, new_ms), g = loss_and_grads(params, ms, mx, my,
                                                    mrng)
                    g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                    return (g_acc, l_acc + l, new_ms), None

                zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
                rngs = jax.random.split(step_rng, accum)
                (g_sum, l_sum, new_ms), _ = jax.lax.scan(
                    body, (zeros, 0.0, model_state),
                    (micro(x), micro(y), rngs))
                grads = jax.tree_util.tree_map(lambda g: g / accum, g_sum)
                loss = l_sum / accum
            else:
                (loss, new_ms), grads = loss_and_grads(params, model_state,
                                                       x, y, step_rng)
            # return the FULL merged state, not the partial update:
            # model_state is donated, so untouched old leaves must flow
            # through the step (aliased by XLA) rather than be re-read
            # from dead host references
            new_ms = merge_state(model_state, new_ms)
            with jax.named_scope("optimizer update"):
                grads = clip(grads)
                new_params, new_opt = optim.update_with_masters(
                    grads, opt_state, params, lr)
            with jax.named_scope("step guards"):
                (new_params, new_opt, new_ms), aux = guards(
                    guard, need_norms, loss, grads,
                    (params, opt_state, model_state),
                    (new_params, new_opt, new_ms))
            if constrain_state is not None:
                new_ms = constrain_state(new_ms)
            return new_params, new_opt, new_ms, loss, rng, aux

        return step

    def _compiled(self, fn, label: str, donate_argnums, sig_argnums=None,
                  **jit_kwargs):
        """`jax.jit(fn)`; with telemetry attached, routed through the
        compile-telemetry wrapper: one `compile` record per distinct
        signature (`sig_argnums`: the batch args only — param/opt trees
        keep constant avals within a run) and FLOPs/bytes off the
        executable for the step records' attribution fields. Without
        telemetry the plain jit path (and its C++ fast dispatch) is kept:
        attribution is observability, and an unobserved run must not pay
        for it."""
        jitted = jax.jit(fn, donate_argnums=donate_argnums, **jit_kwargs)
        if self.telemetry is None:
            return jitted
        from bigdl_tpu.observability.compilation import CompiledFunction
        return CompiledFunction(jitted=jitted, label=label,
                                telemetry=self.telemetry,
                                sig_argnums=sig_argnums)

    # -- the run: set-up, the iteration, the iteration's tail, the end --
    class _Run:
        """One optimize() call's variables: what the step carries from
        iteration to iteration, and what the iteration's tail and the
        run's tail read and write."""

        def __init__(self, opt, params, opt_state, model_state, data_iter):
            self.params, self.opt_state = params, opt_state
            self.model_state = model_state
            self.data_iter = data_iter
            self.state = opt.optim_method.state  # epoch/neval bookkeeping
            # a per-host shard feeds the loop; scale records by host count
            # so epoch triggers fire on global progress
            self.num_hosts = getattr(opt.dataset, "num_hosts", 1)
            self.epoch_size = getattr(opt.dataset, "global_size", None) \
                or opt.dataset.size() * self.num_hosts
            self.sync_every = max(1, int(opt.sync_interval))
            self.win = opt._SyncWindow()
            self.loss_val = float("nan")  # last synced loss
            self.loss = None  # device array of the most recent step's loss
            self.lr = None
            self.aux_pending: List = []  # per-dispatch aux scalars (tiny)
            self.preempted = False

    def _begin_run(self, loop: str, place=None) -> "_Run":
        """Set-up every loop shares. `place(params, model_state)` puts
        them where the loop's step wants them; the optimizer slots are
        created from the placed params, so `zeros_like` inherits each
        param's placement."""
        params = self.model.ensure_params()
        model_state = self.model._state
        # host snapshot BEFORE the first donated step kills these buffers:
        # a failed run (or retry attempt) restores it so the model
        # instance stays usable
        self._pristine_params = jax.device_get(params)
        self._pristine_state = jax.device_get(model_state)
        if place is not None:
            params, model_state = place(params, model_state)
        resume_slots = getattr(self, "_resume_slots", None)
        if resume_slots is not None:
            # checkpointed optimizer moments (Adam m/v, SGD velocity)
            # from resume_from_latest_checkpoint. COPY, never alias
            # (jnp.array, not asarray): the donated step would otherwise
            # delete the checkpoint loader's own arrays out from under
            # `_resume_slots`/retry handling when they are already
            # jax.Arrays (the orbax sharded format restores those)
            opt_state = jax.tree_util.tree_map(jnp.array, resume_slots)
            self._resume_slots = None
        else:
            opt_state = self.optim_method.init_state_with_masters(params)
        _, src = self._open_data_pipeline()
        data_iter = self._fast_forward_data(src, self.optim_method.state)
        self._init_cursor_positions()
        self._telemetry_run_start(loop)
        return self._Run(self, params, opt_state, model_state, data_iter)

    def _pull_batch(self, data_iter):
        """Next host batch, or None when a finite stream ran out. With
        set_prefetch armed, `next(data_iter)` is a queue pop off the
        background pipeline instead of inline transformer work."""
        with Timer(self.metrics, "data fetch time"), \
                self._span("data fetch"):
            batch = next(data_iter, None)
            if batch is None:
                logger.warning(
                    "training data stream exhausted before the end "
                    "trigger fired; stopping early (train=True datasets "
                    "normally loop forever)")
                return None
            self._note_pull()
        return batch

    def _place_batch(self, batch):
        """A host batch as the step's `(x, y)`: starts the async device
        transfer, which overlaps the dispatched step."""
        return _to_device(batch.get_input()), _to_device(batch.get_target())

    def _train(self, run: "_Run", step, suffix: str = "") -> Module:
        """The loop of the local and the SPMD optimizer: one dispatch of
        the compiled step per iteration and, but for the loss sync, no
        host sync and no further device dispatch."""
        self._step_fn = step
        state = run.state

        def fetch_and_place():
            # called right after the step is dispatched, so the numpy work
            # and the H2D DMA overlap the running step (the reference's
            # data-fetch Spark task overlapping the parameter-sync jobs,
            # DistriOptimizer.scala:330-339). The phase timers therefore
            # OVERLAP "computing time average" (dispatch -> loss sync);
            # the phase table is intentionally not additive
            batch = self._pull_batch(run.data_iter)
            return None if batch is None else (batch,
                                               *self._place_batch(batch))

        # device-resident rng chain, advanced inside the donated step; a
        # COPY so self.rng survives donation: a failed run leaves it where
        # it was, and a retry seeds a fresh chain from it
        rng = jnp.asarray(self.rng) + 0
        pending = fetch_and_place()
        while pending is not None and not self.end_trigger(state):
            batch, x, y = pending
            step_no = state["neval"] + 1
            with self._span("step prepare"):
                # chaos hook (resilience/faults.py): a no-op unless a
                # FaultInjector is installed — lets tests crash the loop
                # at an exact iteration
                faults.fire("train.step", step=step_no)
                run.lr = self.optim_method.current_lr()
            with self._span("step dispatch", step=step_no):
                (run.params, run.opt_state, run.model_state, run.loss, rng,
                 aux) = step(run.params, run.opt_state, run.model_state,
                             x, y, run.lr, rng)
            if aux:
                run.aux_pending.append(aux)
            # prefetch while the dispatched step runs on-device (deliberate
            # one-batch lookahead: the final prefetch of an optimize() call
            # is discarded — one batch of host work per run buys the
            # fetch/H2D overlap on every iteration)
            pending = fetch_and_place()
            do_sync = step_no % run.sync_every == 0
            if do_sync:
                # waits for the step; donation chains steps, so this means
                # every dispatched step up to here has completed
                with self._span("loss sync"):
                    run.loss_val = float(run.loss)
            self._finish_iteration(run, batch, do_sync, suffix)
            if run.preempted:
                break
        return self._finish_run(run, rng)

    def _finish_iteration(self, run: "_Run", batch, do_sync: bool,
                          suffix: str = "") -> bool:
        """The host's tail of an iteration, one span whether it synced or
        not: counters, the sync's records and log line, summaries, epoch
        roll-over, validation, checkpoint, hook, preemption poll (sets
        `run.preempted`). Returns whether an epoch boundary was crossed."""
        state = run.state
        with self._span("step bookkeeping"):
            n = batch.size() * run.num_hosts  # global records this step
            state["neval"] += 1
            state["recordsProcessedThisEpoch"] += n
            state["loss"] = run.loss_val
            run.win.add(n)
            if do_sync:
                # throughput + per-iteration compute time over the sync
                # window: exact wall time between device-drained points,
                # valid for any sync_interval. The window counts ONLY
                # dispatch+device time — it restarts after the
                # validation/checkpoint/hook tail below — and recording
                # the metric only at sync keeps "computing time average"
                # a true per-step figure (per-dispatch timing is
                # meaningless under async)
                throughput = run.win.throughput(self.metrics)
                self._observe_sync(state, run.loss_val, run.lr, throughput,
                                   run.win.step_time_s, n, run.aux_pending)
                logger.info(
                    f"[Epoch {state['epoch'] + 1} "
                    f"{state['recordsProcessedThisEpoch']}/"
                    f"{run.epoch_size}]"
                    f"[Iteration {state['neval']}] Training cost "
                    f"{run.loss_val}. Throughput is {throughput} "
                    f"records/second. {suffix}")
                if self.train_summary is not None:
                    it = state["neval"]
                    self.train_summary.add_scalar("Loss", run.loss_val, it)
                    self.train_summary.add_scalar("LearningRate",
                                                  self._lr_scalar(run.lr), it)
                    self.train_summary.add_scalar("Throughput", throughput, it)
                    # Parameters histograms only behind an explicit trigger —
                    # they pull every (sharded) weight to host
                    # (AbstractOptimizer.scala:47-92)
                    trig = getattr(self.train_summary, "get_summary_trigger",
                                   lambda _n: None)("Parameters")
                    if trig is not None and trig(state):
                        host = jax.device_get(run.params)
                        for path, leaf in \
                                jax.tree_util.tree_flatten_with_path(host)[0]:
                            tag = "/".join(
                                str(getattr(p, "key", getattr(p, "idx", p)))
                                for p in path)
                            self.train_summary.add_histogram(tag, leaf, it)

            boundary = state["recordsProcessedThisEpoch"] >= run.epoch_size
            if boundary:
                state["epoch"] += 1
                state["recordsProcessedThisEpoch"] = 0
                self._shuffle_dataset()

            with self._span("validation"):
                self._validate(run.params, run.model_state, state)
            if self.checkpoint_trigger and self.checkpoint_trigger(state):
                with Timer(self.metrics, "checkpoint time"), \
                        self._span("checkpoint"):
                    self._save_checkpoint(
                        run.params, run.model_state,
                        tag=f"iter{state['neval']}",
                        opt_slots=run.opt_state)
            if self.iteration_hook is not None:
                self.iteration_hook(state)
            run.preempted = self._check_preemption(
                run.params, run.model_state, run.opt_state, state, run.loss)
            if do_sync and not run.preempted:
                run.win.restart()  # exclude the tail from the next window
        return boundary

    def _finish_run(self, run: "_Run", rng) -> Module:
        """The run's tail: the true final loss when the loop ended between
        syncs, the partial aux window, `run_end`, the state handed back."""
        state = run.state
        if run.sync_every > 1 and run.loss is not None and \
                state["neval"] % run.sync_every != 0:
            state["loss"] = run.loss_val = float(run.loss)
        if run.aux_pending:
            # partial tail window (end trigger fired between syncs): the
            # guards/monitors must still see those steps' aux
            self._observe_sync(state, run.loss_val, run.lr, float("nan"),
                               float("nan"), 0, run.aux_pending)
        if not run.preempted:  # a preempted run already closed with run_abort
            self._telemetry_run_end(state)
        self._return_state(run.params, run.model_state, rng)
        return self.model

    def _restore_pristine(self):
        """Put the pre-run host snapshot back on the model after a failed
        donated run (the step aliased the model's old device buffers)."""
        if self._pristine_params is not None:
            self.model.set_params(self._pristine_params)
            self.model._state = self._pristine_state

    @contextlib.contextmanager
    def _run_scope(self):
        """Around one `optimize()`: no stale snapshot, the preemption
        handler armed and its previous disposition restored after."""
        # a snapshot left over from a PREVIOUS run is stale: a failure
        # early in this run (before _begin_run re-snapshots) must not
        # revert the model to pre-last-run weights
        self._pristine_params = self._pristine_state = None
        if self._preemption is not None:
            # a latch left set by a previous preempted run is stale: the
            # next optimize() (train-more / drill reuse) must train, not
            # instantly re-abort
            self._preemption.reset()
            self._preemption.install()
        try:
            yield
        finally:
            if self._preemption is not None:
                self._preemption.uninstall()

    def _return_state(self, params, model_state, rng):
        """Hand the trained state back: the advanced rng chain, so a
        subsequent optimize() call (resume / train-more) continues the
        dropout/noise stream instead of replaying it, and the model's
        parameters and state (as they are; DistriOptimizer gathers)."""
        self.rng = jax.device_get(rng)
        self.model.set_params(params)
        self.model._state = model_state


class LocalOptimizer(BaseOptimizer):
    """Train on the local device (one TPU chip / CPU)."""

    def __init__(self, model: Module, dataset, criterion: Criterion,
                 batch_size: int = 32):
        super().__init__(model, dataset, criterion)
        self.batch_size = batch_size

    def optimize(self) -> Module:
        with self._run_scope():
            try:
                return self._optimize_impl()
            except (KeyboardInterrupt, SystemExit):
                self._restore_pristine()
                raise
            except Exception as e:
                self._telemetry_run_abort(e)
                # the donated step killed the model's device arrays; put
                # the pre-run host snapshot back so the instance stays
                # usable (pre-donation behavior: params unchanged on
                # failure)
                self._restore_pristine()
                raise
            finally:
                # join prefetch workers whether the run finished or died —
                # repeated optimize() calls must never accumulate threads
                self._close_data_pipeline(self._active_pipeline)

    def _build_step(self):
        # donation: params, optimizer slots, model state and the rng chain
        # alias their output buffers (PERF.md measured a ~20x dispatch
        # penalty for non-donated same-shape probes). The guards'
        # skip-mode revert stays donation-safe: jnp.where selects between
        # traced values.
        return self._compiled(
            self._step_body(), f"local.step/{type(self.model).__name__}",
            donate_argnums=(0, 1, 2, 6), sig_argnums=(3, 4))

    def _optimize_impl(self) -> Module:
        self._maybe_optimize_graph()
        return self._train(self._begin_run("local"), self._build_step())
