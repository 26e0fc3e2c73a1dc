"""Dynamic micro-batching inference engine.

Parity: BigDL 2.0's Cluster Serving (arXiv 2204.01715 §4) grows a serving
layer over the training stack — requests stream into a queue, a dispatcher
coalesces them into batches sized by arrival rate, and the batched forward
amortizes per-call overhead. This is the TPU-native, in-process port:
concurrent clients `submit()` `Sample`s and get futures back; a dispatcher
thread drains the bounded queue into micro-batches under a
`(max_batch_size, max_wait_ms)` policy, pads each batch up to a small set
of power-of-two **shape buckets** so the jitted forward compiles once per
bucket, and dispatches ahead of the blocking device->host fetch through a
bounded in-flight window (the overlap `LocalPredictor.predict` uses).

Where the reference's Cluster Serving leaned on Redis + Flink for queueing
and backpressure, XLA's immutable compiled executables let the whole engine
live in one process: the queue is a `deque` under a condition variable, and
backpressure is the queue bound itself — `admission="block"` parks the
caller (up to its deadline), `admission="reject"` fails fast with
`QueueFullError` so an upstream load balancer can shed.

Bucket floor: the default buckets start at 2, not 1, because XLA lowers a
batch-1 matmul through a gemv path whose row results differ BITWISE from
the gemm path every other batch size takes — padding singles up to 2 keeps
serving outputs equal to offline `LocalPredictor.predict` batches to float32
rounding, and bit for bit at the same padded shape (asserted in
tests/test_serving.py). Pass `buckets=[1, ...]` explicitly to trade that
for the smaller padded forward.

Robustness contracts (all under test):
- a failed batch (bad feature shape, trace error) rejects only its OWN
  requests; the engine keeps serving,
- with `breaker=...` armed, a PERSISTENTLY failing batch domain (one
  shape bucket) trips a per-bucket circuit breaker
  (resilience/breaker.py): its requests then fast-fail with
  `ServingUnavailableError` instead of each paying a doomed forward,
  half-open probe batches recover it, transitions emit
  `circuit_open`/`circuit_close` telemetry, and `health()` reports the
  degraded domains,
- a request whose deadline lapses in the queue gets `ServingTimeoutError`
  while its batch neighbors complete normally,
- `close(drain=True)` stops admission, finishes every queued request, and
  joins the non-daemon dispatcher thread — a missed close is a VISIBLE
  leak under tests/conftest.py's thread-leak fixture, same policy as
  `dataset/prefetch.py`.

Telemetry: queue-wait / batch-size / end-to-end-latency histograms
(p50/p95/p99) plus queue-depth and bucket-hit-rate gauges flow through the
existing `observability.Telemetry` sinks as `serving_stats` records, and
every dispatch/fetch phase lands in an attached `SpanTracer`. Bucket
warmup/traffic compiles emit `compile` records (the predictor's jit runs
through the observability compile wrapper), and stats carry per-batch
FLOPs plus lifetime serving MFU (null off the chip registry).
"""

from __future__ import annotations

import itertools
import logging
import threading
import time
import weakref
from collections import deque
from concurrent.futures import Future, InvalidStateError
from concurrent.futures import TimeoutError as FuturesTimeoutError
from typing import Dict, List, Optional, Sequence

import numpy as np

from bigdl_tpu.dataset.sample import Sample
from bigdl_tpu.observability.spans import TraceContext
from bigdl_tpu.resilience import faults
from bigdl_tpu.resilience.breaker import (CLOSED, HALF_OPEN, OPEN,
                                          CircuitBreaker)
from bigdl_tpu.serving.stats import WindowedHistogram
from bigdl_tpu.utils.table import Table

logger = logging.getLogger("bigdl_tpu.serving")

# Engines still open at interpreter exit get a drain-less close so their
# non-daemon dispatcher cannot hang shutdown for callers that never call
# close() (the old PredictionService had no thread to leak). A REGULAR
# atexit hook runs only AFTER threading._shutdown has joined non-daemon
# threads — too late — so use threading._register_atexit (what
# concurrent.futures uses), falling back to atexit on Pythons without it.
_LIVE_ENGINES: "weakref.WeakSet" = weakref.WeakSet()


def _close_live_engines():
    for eng in list(_LIVE_ENGINES):
        try:
            eng.close(drain=False)
        except Exception:
            pass


try:
    threading._register_atexit(_close_live_engines)
except AttributeError:  # < 3.9: best effort only
    import atexit
    atexit.register(_close_live_engines)


class ServingError(RuntimeError):
    """Base class for engine-side request failures."""


class QueueFullError(ServingError):
    """Raised by `submit` under `admission="reject"` when the queue is at
    capacity — the fail-fast backpressure signal for an upstream shedder."""


class ServingTimeoutError(ServingError, TimeoutError):
    """A request's deadline lapsed before its batch dispatched (or before
    it was admitted, under blocking admission)."""


class EngineClosedError(ServingError):
    """The engine is shut down (or shutting down) and not accepting work."""


class ServingUnavailableError(ServingError):
    """Fast-fail shed: this request's shape bucket has its circuit
    breaker OPEN (too many consecutive batch failures) — the request was
    refused WITHOUT paying a forward. Retry after the breaker's reset
    timeout, or route elsewhere."""


def default_buckets(max_batch_size: int) -> List[int]:
    """Powers of two from 2 up to `max_batch_size` (which always caps the
    list, power of two or not): 32 -> [2, 4, 8, 16, 32], 24 -> [2, 4, 8,
    16, 24], 1 -> [1]. See the module docstring for why the floor is 2."""
    if max_batch_size < 1:
        raise ValueError(f"max_batch_size must be >= 1, got {max_batch_size}")
    if max_batch_size == 1:
        return [1]
    out, b = [], 2
    while b < max_batch_size:
        out.append(b)
        b *= 2
    out.append(max_batch_size)
    return out


class _Request:
    __slots__ = ("features", "future", "t_submit", "deadline", "ctx",
                 "seq", "t_gather", "session", "deadline_budget_ms")

    def __init__(self, features, deadline: Optional[float],
                 ctx: Optional[TraceContext] = None, seq: int = 0,
                 session=None,
                 deadline_budget_ms: Optional[float] = None):
        self.features = features
        self.future: Future = Future()
        self.t_submit = time.perf_counter()
        self.deadline = deadline  # absolute perf_counter seconds, or None
        self.ctx = ctx            # trace identity, carried across threads
        self.seq = seq
        self.t_gather: Optional[float] = None  # when its batch closed
        self.session = session    # echoed into the trace record
        self.deadline_budget_ms = deadline_budget_ms  # as GIVEN, not spent

    def signature(self):
        return tuple((f.shape, str(f.dtype)) for f in self.features)


def _resolve(future: Future, value=None, exc: Optional[BaseException] = None):
    """Set a future's outcome, ignoring client-side cancellation races."""
    try:
        if exc is not None:
            future.set_exception(exc)
        else:
            future.set_result(value)
    except InvalidStateError:
        pass  # client cancelled; outcome is moot


class InferenceEngine:
    """In-process serving engine: futures in, micro-batched forwards out.

    Example (single-threaded; real clients submit concurrently):
        >>> import numpy as np
        >>> import bigdl_tpu.nn as nn
        >>> from bigdl_tpu.dataset.sample import Sample
        >>> from bigdl_tpu.serving import InferenceEngine
        >>> m = nn.Sequential().add(nn.Linear(4, 2)).add(nn.LogSoftMax())
        >>> eng = InferenceEngine(m, max_batch_size=8, max_wait_ms=1.0)
        >>> out = eng.predict(Sample(np.ones(4, np.float32)))
        >>> out.shape
        (2,)
        >>> eng.close()

    Parameters
    ----------
    model : the trained module; converted for inference exactly like
        `LocalPredictor` (BN fold, noise elision) unless `convert=False`.
        Quantized modules (`nn/quantized.py`) serve with `convert=False`
        (they are already inference-form; the IR round-trip is for float
        training graphs).
    max_batch_size : dispatch cap; also the largest default bucket.
    max_wait_ms : how long the dispatcher holds an underfull batch open
        for more arrivals — the latency/throughput knob.
    queue_capacity : bound on queued (unbatched) requests.
    admission : "block" parks `submit` until space (or the request's
        deadline) — cooperative backpressure; "reject" raises
        `QueueFullError` immediately — load-shedding backpressure.
    buckets : ascending pad targets; `None` = `default_buckets(...)`.
        The largest bucket overrides `max_batch_size` as the dispatch cap.
    inflight : dispatched-but-unfetched batches kept in flight (the
        `LocalPredictor.predict` overlap window).
    telemetry : optional `observability.Telemetry`; the engine emits
        `serving_stats` records every `emit_every` batches and a final
        `serving_summary` on close.
    tracer : optional `observability.SpanTracer` for per-phase spans.
    breaker : optional dict of `resilience.CircuitBreaker` kwargs
        (`failure_threshold`, `reset_timeout_s`, `probe_successes`,
        `clock`) arming one circuit breaker per (feature-signature,
        bucket) batch domain. A bucket whose batches keep failing trips
        open: its requests then shed instantly with
        `ServingUnavailableError` instead of each paying a doomed
        forward (per-batch error isolation stops one bad batch killing
        its neighbors; the breaker stops a persistently bad bucket
        burning EVERY request routed at it). After `reset_timeout_s` one
        probe batch tests the water (half-open) and recovery closes the
        circuit. Transitions emit `circuit_open`/`circuit_half_open`/
        `circuit_close` telemetry events; `health()` reports per-bucket
        breaker state. None (default) disables the breaker.
    trace_sample : trace every Nth COMPLETED request; requests that
        fail/time out/shed always trace. 1 (default) traces everything —
        raise it to sample under heavy traffic (sampled-out requests pay
        NO tracing cost: neither the `trace` telemetry record nor the
        span tree is built). A traced request emits the critical-path
        `trace` record (telemetry attached) and lands as a span tree
        (submit->queue->dispatch->forward->fetch) on a per-request lane,
        flow-linked to its batch's dispatch span (tracer attached).
    replica_id : optional fleet identity (serving/fleet.py). When set,
        every `trace` record this engine emits carries a `replica_id`
        field, so a merged fleet stream attributes each request to the
        replica that served it.
    start : spawn the dispatcher immediately; `False` lets tests stage a
        full queue deterministically, then `start()`.
    """

    def __init__(self, model, max_batch_size: int = 32,
                 max_wait_ms: float = 2.0, queue_capacity: int = 256,
                 admission: str = "block",
                 buckets: Optional[Sequence[int]] = None,
                 inflight: int = 2, convert: bool = True,
                 telemetry=None, tracer=None, emit_every: int = 50,
                 hist_window: int = 8192,
                 breaker: Optional[Dict] = None, trace_sample: int = 1,
                 replica_id: Optional[str] = None, start: bool = True):
        if queue_capacity < 1:
            raise ValueError(
                f"queue_capacity must be >= 1, got {queue_capacity}")
        if admission not in ("block", "reject"):
            raise ValueError(
                f"admission must be 'block' or 'reject', got {admission!r}")
        if max_wait_ms < 0:
            raise ValueError(f"max_wait_ms must be >= 0, got {max_wait_ms}")
        if inflight < 1:
            raise ValueError(f"inflight must be >= 1, got {inflight}")
        if buckets is None:
            buckets = default_buckets(max_batch_size)
        else:
            buckets = sorted(int(b) for b in buckets)
            if not buckets or buckets[0] < 1:
                raise ValueError(f"buckets must be positive, got {buckets}")
            if len(set(buckets)) != len(buckets):
                raise ValueError(f"buckets must be distinct, got {buckets}")
        from bigdl_tpu.optim.predictor import LocalPredictor
        self._pred = LocalPredictor(model, batch_size=buckets[-1],
                                    convert=convert, instrument=True)
        self.model = self._pred.model  # the CONVERTED serving copy
        self._params = self.model.ensure_params()
        self._state = self.model._state
        self.buckets = buckets
        self.max_batch_size = buckets[-1]
        self.max_wait_s = max_wait_ms / 1e3
        self.queue_capacity = queue_capacity
        self.admission = admission
        self.inflight = inflight
        self.telemetry = telemetry
        self.tracer = tracer
        self.emit_every = max(1, int(emit_every))

        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)
        self._not_full = threading.Condition(self._lock)
        self._q: deque = deque()
        self._closing = False    # no new admissions
        self._drain = True       # finish queued work on close?
        self._joined = False
        self._thread: Optional[threading.Thread] = None

        # ---- stats (own lock: stats() must not contend with admission)
        self._slock = threading.Lock()
        self.queue_wait = WindowedHistogram(hist_window)   # seconds
        self.latency = WindowedHistogram(hist_window)      # seconds
        self.batch_sizes = WindowedHistogram(hist_window)  # requests/batch
        self._n = {"submitted": 0, "completed": 0, "failed": 0,
                   "timed_out": 0, "rejected": 0, "cancelled": 0,
                   "shed": 0, "batches": 0, "bucket_hits": 0, "rows": 0,
                   "padded_rows": 0}
        self._compiled = set()  # (signature, bucket) pairs seen/warmed
        # cost attribution (observability/costs.py): cumulative FLOPs /
        # bytes of dispatched batches, read off the compiled bucket
        # executables; the engine's MFU is averaged over its whole
        # serving lifetime (idle time included — that IS serving MFU)
        self._flops_total = 0.0
        self._bytes_total = 0.0
        self._t0_mono = time.monotonic()
        # perf_counter twin of _t0_mono: trace records stamp each
        # request's arrival_offset_ms against it, so a recorded stream
        # carries its own relative timeline (workload/record.py replays
        # it without wall-clock side channels)
        self._t0_perf = time.perf_counter()
        # route the predictor's compile telemetry into this engine's
        # stream under a serving label — bucket warmup cost and recompile
        # storms then show up as `compile` records
        jw = self._pred._jitted
        if hasattr(jw, "label"):
            jw.label = f"serving.forward/{type(self.model).__name__}"
            jw.telemetry = telemetry
        self._breaker_cfg = dict(breaker) if breaker is not None else None
        self._breakers: Dict[tuple, CircuitBreaker] = {}  # under _slock
        if trace_sample < 1:
            raise ValueError(
                f"trace_sample must be >= 1, got {trace_sample}")
        self.trace_sample = int(trace_sample)
        self.replica_id = replica_id
        self._req_seq = itertools.count()

        _LIVE_ENGINES.add(self)
        if start:
            self.start()

    # ------------------------------------------------------------ lifecycle
    def start(self):
        """Spawn the (non-daemon) dispatcher thread. Idempotent."""
        with self._lock:
            if self._closing:
                raise EngineClosedError("engine is closed")
            if self._thread is not None:
                return self
            t = self._thread = threading.Thread(
                target=self._run, name="bigdl-serving-dispatch",
                daemon=False)
        t.start()
        return self

    def close(self, drain: bool = True):
        """Stop admission, optionally finish queued work, join the
        dispatcher. `drain=True` (default) resolves every queued request
        before returning; `drain=False` fails queued requests with
        `EngineClosedError`. Idempotent."""
        with self._lock:
            self._closing = True
            self._drain = drain
            self._not_empty.notify_all()
            self._not_full.notify_all()
            t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join()
        with self._lock:
            if self._joined:
                return
            self._joined = True
        _LIVE_ENGINES.discard(self)
        # leftover requests (never-started engine, or drain=False)
        self._fail_queued(EngineClosedError("engine closed"))
        self._emit_safe({"type": "serving_summary", **self.stats()})

    def _fail_queued(self, exc: BaseException):
        with self._lock:
            left = list(self._q)
            self._q.clear()
            self._not_full.notify_all()
        with self._slock:
            self._n["cancelled"] += len(left)
        for r in left:
            _resolve(r.future, exc=exc)
        if left:
            # the SLO stream must see a shutdown that failed queued
            # work — every non-ok outcome traces (contract in the
            # trace_sample docs)
            self._finish_trace(left, None, time.perf_counter(),
                               status="cancelled", error=repr(exc))

    def _emit_safe(self, record: Dict):
        """Telemetry sinks must never take the dispatcher down (a full
        disk under a JsonlSink is an observability failure, not a serving
        failure) — log and keep serving."""
        if self.telemetry is None:
            return
        try:
            self.telemetry.emit(record)
        except Exception:
            logger.exception("serving telemetry sink failed; record dropped")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):  # backstop; callers close() explicitly
        try:
            self.close(drain=False)
        except Exception:
            pass

    # ------------------------------------------------------------ admission
    def submit(self, sample, deadline_ms: Optional[float] = None,
               session=None) -> Future:
        """Enqueue one request; returns a `concurrent.futures.Future`
        resolving to the per-sample output row (or raising
        `ServingTimeoutError` / `ServingError`). `sample` is a `Sample`
        or a raw feature array. `deadline_ms` bounds the request's whole
        queued life: admission (block mode) and batching both observe it.
        `session` is an opaque caller identity echoed into the request's
        trace record as `session_id` — the engine itself has no affinity
        (that is the fleet router's job); carrying it here keeps a
        single-engine trace stream replayable."""
        if isinstance(sample, Sample):
            feats = sample.features
        else:
            feats = [np.asarray(sample)]
        now = time.perf_counter()
        deadline = now + deadline_ms / 1e3 if deadline_ms is not None \
            else None
        # trace identity is minted at ADMISSION: whatever happens to the
        # request later (timeout, shed, error), its record carries one
        # trace_id covering its whole queued life
        ctx = TraceContext.new_trace() \
            if (self.telemetry is not None or self.tracer is not None) \
            else None
        req = _Request(feats, deadline, ctx=ctx, seq=next(self._req_seq),
                       session=session, deadline_budget_ms=deadline_ms)
        self._admit(req)
        return req.future

    def _admit(self, req):
        """Shared admission: bounded-queue backpressure (block-with-
        deadline or reject-on-full), closed-engine refusal, and the
        submitted counter. `req` only needs a `deadline` attribute — the
        generation subclass admits its own request type through the SAME
        queue/deadline machinery."""
        deadline = req.deadline
        with self._lock:
            if self._closing:
                raise EngineClosedError("engine is closed")
            if len(self._q) >= self.queue_capacity:
                if self.admission == "reject":
                    with self._slock:
                        self._n["rejected"] += 1
                    raise QueueFullError(
                        f"serving queue at capacity ({self.queue_capacity})")
                while len(self._q) >= self.queue_capacity \
                        and not self._closing:
                    timeout = None
                    if deadline is not None:
                        timeout = deadline - time.perf_counter()
                        if timeout <= 0:
                            with self._slock:
                                self._n["timed_out"] += 1
                            raise ServingTimeoutError(
                                "deadline lapsed waiting for queue space")
                    self._not_full.wait(timeout)
                if self._closing:
                    raise EngineClosedError("engine is closed")
            self._q.append(req)
            with self._slock:
                self._n["submitted"] += 1
            self._not_empty.notify()

    def predict(self, sample, timeout: Optional[float] = None,
                deadline_ms: Optional[float] = None) -> np.ndarray:
        """Blocking convenience: `submit` + wait. `timeout` (seconds)
        bounds the client-side wait; `deadline_ms` is the engine-side
        request deadline. A client-side timeout raises
        `ServingTimeoutError` (like an engine-side deadline lapse, so
        callers handle ONE exception family) and best-effort cancels the
        abandoned request."""
        fut = self.submit(sample, deadline_ms=deadline_ms)
        try:
            return fut.result(timeout)
        except FuturesTimeoutError:
            fut.cancel()  # if still queued, the dispatcher skips it
            raise ServingTimeoutError(
                f"result not ready within {timeout}s") from None

    # ------------------------------------------------------------ warmup
    def warmup(self, sample) -> int:
        """Precompile the jitted forward for EVERY bucket using `sample`'s
        feature signature (replicated), blocking until each executable is
        built — first-request latency then never pays a compile. Returns
        the jit-cache compile count. Call before serving traffic."""
        if isinstance(sample, Sample):
            feats = sample.features
        else:
            feats = [np.asarray(sample)]
        sig = tuple((f.shape, str(f.dtype)) for f in feats)
        for b in self.buckets:
            arrs = [np.stack([f] * b) for f in feats]
            y = self._forward_arrays(arrs)
            np.asarray(y)  # block: the compile must finish here
            with self._slock:
                self._compiled.add((sig, b))
        return self.compile_count()

    def compile_count(self) -> int:
        """Number of distinct XLA compilations of the serving forward, from
        the predictor's compile-telemetry wrapper (one entry per input
        signature — i.e. per bucket per feature signature). 0 before any
        forward."""
        return self._pred._jitted._cache_size()

    # ------------------------------------------------------------ dispatcher
    def _run(self):
        pending: deque = deque()  # (reqs, device_result) in flight
        try:
            while True:
                if pending:
                    # idle queue: fetch in-flight results instead of
                    # blocking for new work — without this, up to
                    # `inflight` batches would sit unfetched (and their
                    # clients unresolved) until the next arrival
                    with self._lock:
                        idle = not self._q and not self._closing
                    if idle:
                        self._complete(pending.popleft())
                        continue
                reqs = self._gather()
                if reqs is None:
                    break
                if not reqs:  # everything gathered had expired
                    continue
                for group in self._group(reqs):
                    batch = self._dispatch(group)
                    if batch is not None:
                        pending.append(batch)
                    while len(pending) > self.inflight:
                        self._complete(pending.popleft())
        finally:
            while pending:
                self._complete(pending.popleft())

    def _gather(self) -> Optional[List[_Request]]:
        """Pop one micro-batch worth of requests: wait for the first, hold
        the window open `max_wait_ms` for more (shutdown-drain skips the
        wait), then drop deadline-expired requests. None = shut down."""
        with self._lock:
            while not self._q and not self._closing:
                self._not_empty.wait()
            if not self._q:
                return None  # closing and nothing left
            if self._closing and not self._drain:
                return None  # leftover queue failed by close()
            reqs = [self._q.popleft()]
            window_end = time.perf_counter() + self.max_wait_s
            while len(reqs) < self.max_batch_size:
                while self._q and len(reqs) < self.max_batch_size:
                    reqs.append(self._q.popleft())
                if len(reqs) >= self.max_batch_size or self._closing:
                    break
                remaining = window_end - time.perf_counter()
                if remaining <= 0:
                    break
                self._not_empty.wait(remaining)
            self._not_full.notify_all()
        now = time.perf_counter()
        alive = []
        for r in reqs:
            if r.deadline is not None and now >= r.deadline:
                # count BEFORE resolving: a client that saw its future
                # settle must already see consistent stats()
                with self._slock:
                    self._n["timed_out"] += 1
                _resolve(r.future, exc=ServingTimeoutError(
                    "deadline lapsed in the serving queue "
                    f"({(now - r.t_submit) * 1e3:.1f} ms queued)"))
                self._finish_trace([r], None, now, status="timeout")
            else:
                r.t_gather = now
                self.queue_wait.record(now - r.t_submit)
                alive.append(r)
        return alive

    @staticmethod
    def _group(reqs: List[_Request]) -> List[List[_Request]]:
        """Split a gathered window by feature signature — each distinct
        shape/dtype set is its own batch (and its own failure domain)."""
        groups: Dict[tuple, List[_Request]] = {}
        for r in reqs:
            groups.setdefault(r.signature(), []).append(r)
        return list(groups.values())

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]  # unreachable: gather caps at buckets[-1]

    # ------------------------------------------------------------ breaker
    @staticmethod
    def _bucket_label(sig, bucket: int) -> str:
        """Human/JSON-friendly batch-domain label: bucket size plus the
        per-feature shape:dtype signature."""
        shapes = "|".join(
            "x".join(map(str, shape)) + f":{dtype}" for shape, dtype in sig)
        return f"b{bucket}[{shapes}]"

    def _breaker_for(self, sig, bucket: int) -> Optional[CircuitBreaker]:
        """The (lazily-created) circuit breaker guarding one
        (signature, bucket) batch domain; None when breakers are off."""
        if self._breaker_cfg is None:
            return None
        key = (sig, bucket)
        with self._slock:
            br = self._breakers.get(key)
            if br is None:
                br = CircuitBreaker(
                    name=self._bucket_label(sig, bucket),
                    on_transition=self._on_breaker_transition,
                    **self._breaker_cfg)
                self._breakers[key] = br
            return br

    def _on_breaker_transition(self, old: str, new: str,
                               br: CircuitBreaker):
        kind = {OPEN: "circuit_open", CLOSED: "circuit_close"}.get(
            new, "circuit_half_open")
        logger.warning("serving circuit %s: %s -> %s", br.name, old, new)
        self._emit_safe({"type": "event", "event": kind,
                         "bucket": br.name, "from": old, "to": new})

    def _forward_arrays(self, arrs: List[np.ndarray]):
        import jax.numpy as jnp
        x = Table(*[jnp.asarray(a) for a in arrs]) if len(arrs) > 1 \
            else jnp.asarray(arrs[0])
        y = self._pred._forward(self._params, self._state, x)
        if isinstance(y, Table):
            y = y[1]  # same convention as LocalPredictor.predict
        return y

    def _span(self, name, **args):
        import contextlib
        if self.tracer is None:
            return contextlib.nullcontext()
        return self.tracer.span(name, cat="serving", **args)

    def _dispatch(self, reqs: List[_Request]):
        """Pad a group up to its bucket and launch the (async) jitted
        forward. A failure here resolves ONLY this group's futures; with
        breakers armed, an OPEN bucket sheds its group instantly with
        `ServingUnavailableError` — no forward is paid."""
        n = len(reqs)
        bucket = self._bucket_for(n)
        sig = reqs[0].signature()
        br = self._breaker_for(sig, bucket)
        if br is not None and not br.allow():
            with self._slock:  # count before resolving (stats consistency)
                self._n["shed"] += n
            for r in reqs:
                _resolve(r.future, exc=ServingUnavailableError(
                    f"circuit open for batch domain {br.name}; request "
                    "shed without a forward"))
            self._finish_trace(reqs, {"bucket": bucket},
                               time.perf_counter(), status="shed")
            return None
        # a batch admitted while HALF_OPEN is THE probe; batches admitted
        # while closed carry probe=False so an outcome arriving after a
        # later trip (inflight pipelining) cannot masquerade as probe
        # evidence — only the dispatcher thread dispatches, so the state
        # read here is consistent with the allow() above
        probe = br is not None and br.state == HALF_OPEN
        meta = {"bucket": bucket, "n": n,
                "t_d0": time.perf_counter(),
                "disp_tid": threading.get_ident() % 2 ** 31}
        try:
            with self._span("serve dispatch", n=n, bucket=bucket):
                # chaos site: no-op unless a FaultInjector is installed —
                # plans target one bucket via the sig/bucket context
                faults.fire("serve.forward", bucket=bucket, n=n, sig=sig)
                cols = [np.stack(c) for c in
                        zip(*(r.features for r in reqs))]
                if bucket > n:
                    # pad with the last row (always in-domain for the
                    # model, unlike zeros), sliced off after the fetch
                    cols = [np.concatenate(
                        [a, np.repeat(a[-1:], bucket - n, axis=0)])
                        for a in cols]
                y = self._forward_arrays(cols)
        except Exception as e:
            with self._slock:  # count before resolving (stats consistency)
                self._n["failed"] += n
                self._n["batches"] += 1
            if br is not None:
                br.record_failure(probe=probe)
            for r in reqs:
                _resolve(r.future, exc=ServingError(
                    f"batch forward failed: {e!r}"))
            self._finish_trace(reqs, meta, time.perf_counter(),
                               status="error", error=repr(e))
            return None
        meta["t_d1"] = time.perf_counter()
        self.batch_sizes.record(n)
        info = getattr(self._pred._jitted, "last_info", None)
        with self._slock:
            hit = (sig, bucket) in self._compiled
            self._compiled.add((sig, bucket))
            self._n["batches"] += 1
            self._n["bucket_hits"] += int(hit)
            self._n["rows"] += bucket
            self._n["padded_rows"] += bucket - n
            if info is not None:
                self._flops_total += info.get("flops") or 0.0
                self._bytes_total += info.get("bytes_accessed") or 0.0
        return reqs, y, br, probe, meta

    def _complete(self, batch):
        """Blocking device->host fetch of the OLDEST in-flight batch; newer
        batches keep the device busy meanwhile. The batch's breaker (if
        armed) learns the final outcome here — a batch only counts as a
        success once its results actually reached the host, and only a
        half-open-admitted probe batch may close/re-trip the circuit."""
        reqs, y, br, probe, meta = batch
        meta["t_f0"] = time.perf_counter()
        try:
            with self._span("serve fetch", n=len(reqs)):
                arr = np.asarray(y)
        except Exception as e:
            with self._slock:  # count before resolving (stats consistency)
                self._n["failed"] += len(reqs)
            if br is not None:
                br.record_failure(probe=probe)
            for r in reqs:
                _resolve(r.future, exc=ServingError(
                    f"batch fetch failed: {e!r}"))
            self._finish_trace(reqs, meta, time.perf_counter(),
                               status="error", error=repr(e))
            return
        if br is not None:
            br.record_success(probe=probe)
        now = time.perf_counter()
        with self._slock:
            self._n["completed"] += len(reqs)
            batches = self._n["batches"]
        for i, r in enumerate(reqs):
            self.latency.record(now - r.t_submit)
            _resolve(r.future, value=arr[i])
        self._finish_trace(reqs, meta, now, status="ok")
        if batches % self.emit_every == 0:
            self._emit_safe({"type": "serving_stats", **self.stats()})

    # ------------------------------------------------------------ tracing
    def _finish_trace(self, reqs: List[_Request], meta: Optional[Dict],
                      t_done: float, status: str,
                      error: Optional[str] = None):
        """Close out each request's trace: reconstruct the critical-path
        phase breakdown (queue -> batch form -> dispatch -> forward ->
        fetch) from the lifecycle timestamps, emit one `trace` telemetry
        record per request, and — with a tracer attached — lay the span
        tree on a per-request lane, flow-linked to the batch's live
        dispatch span. Never raises: tracing failures must not take the
        dispatcher down."""
        if self.telemetry is None and self.tracer is None:
            return
        try:
            self._finish_trace_impl(reqs, meta or {}, t_done, status,
                                    error)
        except Exception:
            logger.exception("request trace emission failed; dropped")

    def _finish_trace_impl(self, reqs, meta, t_done, status, error):
        t_d0 = meta.get("t_d0")
        t_d1 = meta.get("t_d1")
        t_f0 = meta.get("t_f0")
        bucket = meta.get("bucket")
        tracer = self.tracer
        # one perf_counter->tracer-us offset per completion batch: the
        # engine times phases on perf_counter (stats math), the tracer on
        # its own epoch-anchored base
        off = tracer.now_us() - time.perf_counter() * 1e6 \
            if tracer is not None else 0.0

        def us(t):
            return t * 1e6 + off

        for r in reqs:
            if r.ctx is None:
                continue
            if status == "ok" and r.seq % self.trace_sample:
                continue  # sampled out — spans AND record both shed;
                # non-ok outcomes always emit
            phases = [("queue", r.t_submit,
                       r.t_gather if r.t_gather is not None else t_done)]
            if r.t_gather is not None and t_d0 is not None:
                phases.append(("batch form", r.t_gather, t_d0))
            if t_d0 is not None and t_d1 is not None:
                phases.append(("dispatch", t_d0, t_d1))
                if t_f0 is not None:
                    phases.append(("forward", t_d1, t_f0))
                    phases.append(("fetch", t_f0, t_done))
                else:
                    phases.append(("forward", t_d1, t_done))
            total_ms = (t_done - r.t_submit) * 1e3
            if tracer is not None:
                # bounded lane pool: a request's spans render on one of 16
                # virtual tracks (overlap beyond that only stacks
                # visually; identity stays exact via trace_id)
                tid = tracer.lane(f"request-{r.seq % 16}")
                tracer.add_span("request", us(r.t_submit),
                                (t_done - r.t_submit) * 1e6,
                                cat="serving", tid=tid, ctx=r.ctx,
                                status=status, bucket=bucket)
                for name, a, b in phases:
                    tracer.add_span(name, us(a), (b - a) * 1e6,
                                    cat="serving", tid=tid,
                                    ctx=r.ctx.child())
                if r.t_gather is not None and t_d0 is not None and \
                        "disp_tid" in meta:
                    # flow arrow: this request's lane -> the batch's live
                    # "serve dispatch" span on the dispatcher lane
                    tracer.add_flow(r.seq, "batched", us(r.t_gather),
                                    tid, us(t_d0), meta["disp_tid"])
            if self.telemetry is None:
                continue
            rec = {"type": "trace", "trace_id": r.ctx.trace_id,
                   "kind": "serving_request", "status": status,
                   "latency_ms": round(total_ms, 3),
                   "arrival_offset_ms":
                       round((r.t_submit - self._t0_perf) * 1e3, 3)}
            if r.session is not None:
                rec["session_id"] = str(r.session)
            if r.deadline_budget_ms is not None:
                rec["deadline_budget_ms"] = round(r.deadline_budget_ms, 3)
            if r.features:
                rec["shape"] = [int(d) for d in
                                np.asarray(r.features[0]).shape]
            if self.replica_id is not None:
                rec["replica_id"] = self.replica_id
            if status == "ok" and self.trace_sample > 1:
                # this record stands in for trace_sample completed
                # requests; SLO consumers weight it so sampling cannot
                # inflate the bad fraction (errors always emit at w=1)
                rec["sample_weight"] = self.trace_sample
            path = []
            for name, a, b in phases:
                ms = (b - a) * 1e3
                path.append({"name": name, "ms": round(ms, 3),
                             "frac": round(ms / total_ms, 4)
                             if total_ms > 0 else None})
            field = {"queue": "queue_wait_ms", "batch form":
                     "batch_form_ms", "dispatch": "dispatch_ms",
                     "forward": "forward_ms", "fetch": "fetch_ms"}
            for p in path:
                rec[field[p["name"]]] = p["ms"]
            rec["critical_path"] = path
            if bucket is not None:
                rec["bucket"] = int(bucket)
            if meta.get("n") is not None:
                rec["batch"] = int(meta["n"])
            if error is not None:
                rec["error"] = error
            self._emit_safe(rec)

    # ------------------------------------------------------------ stats
    def stats(self) -> Dict:
        """Flat JSON-safe snapshot: counters, queue-depth and
        bucket-hit-rate gauges, and ms-scaled p50/p95/p99 histograms for
        queue wait, end-to-end latency, and batch size (docs/serving.md
        documents every field)."""
        with self._lock:
            depth = len(self._q)
        with self._slock:
            n = dict(self._n)
            flops_total, bytes_total = self._flops_total, self._bytes_total
        out = {"queue_depth": depth, **n}
        out["bucket_hit_rate"] = round(n["bucket_hits"] / n["batches"], 4) \
            if n["batches"] else None
        out["pad_fraction"] = round(n["padded_rows"] / n["rows"], 4) \
            if n["rows"] else None
        # attribution: mean per-dispatched-batch cost plus lifetime MFU
        # (cumulative FLOPs over wall time vs single-chip registry peak;
        # null off the registry — CPU included)
        from bigdl_tpu.observability import costs
        batches = n["batches"]
        out["flops_per_step"] = round(flops_total / batches, 1) \
            if batches and flops_total else None
        out["bytes_accessed"] = round(bytes_total / batches, 1) \
            if batches and bytes_total else None
        m = costs.mfu(flops_total or None,
                      time.monotonic() - self._t0_mono)
        out["mfu"] = round(m, 6) if m is not None else None
        out.update(self.queue_wait.snapshot("queue_wait_ms", scale=1e3))
        out.update(self.latency.snapshot("latency_ms", scale=1e3))
        out.update(self.batch_sizes.snapshot("batch_size", digits=1))
        return out

    def health(self) -> Dict:
        """Liveness/readiness surface (the load-balancer probe):

        - `status`: "ok" (serving, all circuits closed), "degraded" (at
          least one batch domain's breaker is open/half-open — OTHER
          domains still serve), or "closed" (engine shut down).
        - `open_buckets`: the degraded batch-domain labels.
        - `breakers`: per-domain `CircuitBreaker.snapshot()` dicts
          (state, consecutive failures, times opened, shed count).
        - `queue_depth` / `queue_capacity`: admission headroom.
        """
        with self._lock:
            depth = len(self._q)
            closing = self._closing
        with self._slock:
            breakers = dict(self._breakers)
        snaps = {br.name: br.snapshot() for br in breakers.values()}
        open_buckets = sorted(name for name, s in snaps.items()
                              if s["state"] != CLOSED)
        status = "closed" if closing else \
            ("degraded" if open_buckets else "ok")
        return {"status": status, "open_buckets": open_buckets,
                "breakers": snaps, "queue_depth": depth,
                "queue_capacity": self.queue_capacity}
