"""Continuous-batching autoregressive serving: prefill buckets, an O(1)
per-slot KV decode cache, and streaming token futures.

The micro-batching engine (serving/engine.py) batches fixed-shape single
forwards; serving `models/transformer.py` GENERATION through it would pay
one full-sequence recompute per emitted token per request — O(L^2) work
per token and zero cross-request batching on the decode path. This module
is the autoregressive tier on two compiled paths:

- **Prefill** — a queued prompt is padded to a power-of-two sequence
  bucket and grouped with same-bucket neighbors into a power-of-two batch
  bucket (the engine's existing bucket discipline: one compile per
  (batch-bucket, seq-bucket), `warmup()` precompiles them all). The
  prefill executable runs ONE full-sequence causal forward and commits
  each prompt's per-layer K/V into that request's **slot** of a
  preallocated `[slots, heads, max_len, head_dim]` cache (per-row
  `lax.dynamic_update_slice` under donation), returning the first
  generated token.
- **Decode** — ONE fixed-shape jitted step over ALL slots
  (`TransformerLM.apply_step`): each active slot's last token goes in at
  its own position (causal-mask-correct for mixed slot ages), its K/V is
  written in place, and the next greedy token comes out. O(1) memory and
  step cost per token — never a per-token concat, never a retrace.
  Steady-state decode emits ZERO new `compile` records regardless of
  join/leave churn or token position (suite-asserted). The loop is a
  pipeline ONE step deep: a step's tokens stay on the device as the
  next step's input, so step n+1 is dispatched BEFORE the host fetches
  and delivers step n — the jit call, the fetch's wake-up and the
  delivery run while the chip decodes.

**Continuous batching**: requests join a free slot as soon as their
prefill lands and leave at EOS / max-tokens *between* decode steps — no
drain barrier; the decode batch composition changes while the loop runs.
Because every slot's math is row-independent, a request's token sequence
is bit-identical whatever its co-tenants are — continuous-batched greedy
decode produces EXACTLY the tokens of one-request-at-a-time
full-recompute decode (`greedy_decode_reference`), the parity contract
tests/test_generation.py pins at 8+ concurrent churning streams.

**Streaming token futures**: `generate()` returns a `TokenStream` the
caller consumes WHILE the engine decodes — iterate for tokens as they are
produced, `result()` for the full list, `cancel()` to free the slot at
the next step boundary.

Admission shares the engine machinery: the same bounded queue
(block-with-deadline / reject-on-full), per-request deadlines over the
queued life, the per-(seq-bucket, batch-bucket) circuit breaker on the
prefill path, `close(drain=...)` semantics, and the telemetry/trace
streams — plus `generation` records (tokens/sec, decode occupancy,
prefill/decode split, slot churn) and one `trace` record per request with
`kind="generate"` whose critical path is queue -> prefill -> decode
(`metrics_cli trace` renders it).

Failure containment: the KV cache is DONATED to both executables, so a
failed prefill/decode *execution* leaves its buffers unknown — the engine
then fails the affected streams, reallocates a fresh cache, and keeps
serving (a fault injected BEFORE dispatch — the `serve.forward` /
`serve.decode` sites — fails only its own group, cache intact).

Lineage: the portable constant-memory decode cache follows
"Compiler-First State Space Duality and Portable O(1) Autoregressive
Caching" (PAPERS.md, arXiv 2603.09555); the serving tier itself is the
generation workload BigDL 2.0's Cluster Serving (arXiv 2204.01715) grew
toward.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bigdl_tpu.nn import kv_cache
from bigdl_tpu.observability.compilation import CompiledFunction
from bigdl_tpu.observability.spans import TraceContext
from bigdl_tpu.resilience import faults
from bigdl_tpu.resilience.breaker import HALF_OPEN
from bigdl_tpu.serving.engine import (EngineClosedError, InferenceEngine,
                                      ServingError, ServingTimeoutError,
                                      ServingUnavailableError)
from bigdl_tpu.serving.stats import WindowedHistogram

logger = logging.getLogger("bigdl_tpu.serving")

#: Decode-step chaos site (the prefill path fires the engine's existing
#: `serve.forward` site with bucket context).
SITE_DECODE = faults.register_site("serve.decode")


def default_seq_buckets(max_len: int, floor: int = 8) -> List[int]:
    """Power-of-two prompt-length pad targets up to (and always
    including) `max_len`: 64 -> [8, 16, 32, 64], 48 -> [8, 16, 32, 48].
    One prefill compile per (batch-bucket, seq-bucket)."""
    if max_len < 1:
        raise ValueError(f"max_len must be >= 1, got {max_len}")
    out, b = [], min(floor, max_len)
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return out


def _next_token(logp):
    """Greedy 1-based ids [B] from log-probs [B, vocab], traced inside the
    decode and prefill programs under the model's `head` scope name."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("head"):
        return jnp.argmax(logp, axis=-1).astype(jnp.int32) + 1


class TokenStream:
    """Streaming token future for ONE generation request.

    The engine's decode loop appends tokens as it produces them; the
    caller consumes them concurrently:

    - iterate (`for tok in stream`) — blocks per token, raising the
      request's failure (`ServingTimeoutError`, `ServingError`, ...) at
      the point the stream died;
    - `result(timeout)` — block for completion, return the full list;
    - `get(i, timeout)` — token `i` (blocking), `None` once the stream
      finished OK with fewer tokens — the index-based surface the
      fleet's exactly-once re-route wrapper builds on;
    - `cancel()` — stop generation at the next step boundary (the slot
      frees; tokens already emitted stay readable);
    - `token_times()` — when the ENGINE delivered each token
      (`time.perf_counter()` seconds, one stamp a token): the
      dispatcher reads the clock once a decode step (or prefill group)
      and every token of that step carries the reading, so first-token
      time and inter-token gaps can be had without a reader thread
      waking once a token to stamp them.

    Thread-safe. `status` is None while streaming, then one of
    "ok"/"timeout"/"error"/"cancelled"/"shed". Token ids are 1-based
    (the model's label convention); an EOS token IS emitted before the
    stream finishes.
    """

    def __init__(self):
        self._cond = threading.Condition()
        self._tokens: List[int] = []
        # engine-side stamp of each token: the dispatcher sets `_t` to its
        # one clock reading for the step before it puts the step's token
        # (`_put` keeps its one argument); None: `_put` reads the clock
        self._t: Optional[float] = None
        self._times: List[float] = []
        self._status: Optional[str] = None
        self._exc: Optional[BaseException] = None
        self._cancelled = False

    # ---- producer side (engine internals)
    def _put(self, tok: int):
        with self._cond:
            self._tokens.append(int(tok))
            self._times.append(time.perf_counter() if self._t is None
                               else self._t)
            self._cond.notify_all()

    def _finish(self, status: str = "ok",
                exc: Optional[BaseException] = None):
        with self._cond:
            if self._status is None:
                self._status = status
                self._exc = exc
                self._cond.notify_all()

    # ---- consumer side
    def cancel(self):
        """Ask the engine to stop this request at the next step boundary
        (or skip it while still queued). Already-emitted tokens stay
        readable; the stream finishes with status "cancelled"."""
        with self._cond:
            self._cancelled = True

    @property
    def cancelled(self) -> bool:
        return self._cancelled

    @property
    def done(self) -> bool:
        with self._cond:
            return self._status is not None

    @property
    def status(self) -> Optional[str]:
        with self._cond:
            return self._status

    @property
    def error(self) -> Optional[BaseException]:
        """The stream's failure, once finished non-ok (None otherwise)."""
        with self._cond:
            return self._exc

    def token_count(self) -> int:
        with self._cond:
            return len(self._tokens)

    def token_times(self) -> List[float]:
        """The engine's delivery stamp of every token so far
        (`time.perf_counter()` seconds; `token_times()[i]` belongs to
        `get(i)`; non-decreasing). First-token time is
        `token_times()[0]` minus the submit time, inter-token gaps are
        `numpy.diff(token_times())`."""
        with self._cond:
            return list(self._times)

    def get(self, i: int, timeout: Optional[float] = None) -> Optional[int]:
        """Token `i` (blocking up to `timeout` seconds), or None when the
        stream finished OK with <= `i` tokens; raises the stream's
        failure once `i` is past the delivered prefix."""
        deadline = time.monotonic() + timeout if timeout is not None \
            else None
        with self._cond:
            while True:
                if len(self._tokens) > i:
                    return self._tokens[i]
                if self._status is not None:
                    if self._exc is not None:
                        raise self._exc
                    return None
                wait = None if deadline is None \
                    else deadline - time.monotonic()
                if wait is not None and wait <= 0:
                    raise ServingTimeoutError(
                        f"token {i} not ready within {timeout}s")
                self._cond.wait(wait)

    def __iter__(self):
        i = 0
        while True:
            tok = self.get(i)
            if tok is None:
                return
            yield tok
            i += 1

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block until the stream finishes; return ALL tokens (raises the
        stream's failure instead, or `ServingTimeoutError` on a
        client-side timeout)."""
        deadline = time.monotonic() + timeout if timeout is not None \
            else None
        with self._cond:
            while self._status is None:
                wait = None if deadline is None \
                    else deadline - time.monotonic()
                if wait is not None and wait <= 0:
                    raise ServingTimeoutError(
                        f"generation not finished within {timeout}s")
                self._cond.wait(wait)
            if self._exc is not None:
                raise self._exc
            return list(self._tokens)


class _GenRequest:
    __slots__ = ("prompt", "max_new_tokens", "eos_id", "stream", "deadline",
                 "ctx", "seq", "t_submit", "t_gather", "t_prefill1",
                 "tokens_out", "slot", "pos", "session",
                 "deadline_budget_ms")

    def __init__(self, prompt: np.ndarray, max_new_tokens: int,
                 eos_id: Optional[int], deadline: Optional[float],
                 ctx: Optional[TraceContext], seq: int,
                 session=None,
                 deadline_budget_ms: Optional[float] = None):
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        self.stream = TokenStream()
        self.deadline = deadline  # absolute perf_counter seconds, or None
        self.ctx = ctx
        self.seq = seq
        self.t_submit = time.perf_counter()
        self.t_gather: Optional[float] = None   # left the queue (prefill)
        self.t_prefill1: Optional[float] = None  # prefill landed
        self.tokens_out: List[int] = []
        self.slot: Optional[int] = None
        self.pos = 0  # next decode position (= prompt length after prefill;
        # advances when a step is dispatched, not when it is delivered)
        self.session = session    # echoed into the trace record
        self.deadline_budget_ms = deadline_budget_ms  # as GIVEN, not spent

    def asked(self) -> int:
        """Tokens the device has been asked for: the prefill's and one a
        dispatched decode step, delivered or still in flight."""
        return self.pos - self.prompt.size + 1


class GenerationEngine(InferenceEngine):
    """Continuous-batching autoregressive serving over a cache-aware
    model (`TransformerLM`-shaped: `init_cache` / `apply_prefill` /
    `apply_step`).

    Example (greedy decode, streaming consumption):
        >>> import jax, numpy as np
        >>> from bigdl_tpu.models.transformer import TransformerLM
        >>> from bigdl_tpu.serving import GenerationEngine
        >>> m = TransformerLM(32, embed_dim=16, n_layer=1, n_head=2,
        ...                   use_flash=False, max_len=16)
        >>> _ = m.ensure_params(jax.random.PRNGKey(0))
        >>> eng = GenerationEngine(m, slots=2, max_len=16,
        ...                        max_new_tokens=3)
        >>> toks = list(eng.stream(np.array([1, 2, 3], np.int32)))
        >>> len(toks)
        3
        >>> eng.close()

    Parameters (beyond the `InferenceEngine` ones it shares —
    `queue_capacity`, `admission`, `telemetry`, `tracer`, `breaker`,
    `trace_sample`, `replica_id`, `emit_every`, `start`):

    slots : decode batch width — concurrent streams decoded per step.
        Inactive slots ride along at fixed shape (the continuous-batching
        trade: wasted lanes, zero recompiles).
    max_len : KV cache depth per slot; every request must satisfy
        `len(prompt) + max_new_tokens <= max_len` at admission.
    max_new_tokens / eos_id : per-request defaults (`eos_id` compares
        against emitted 1-based ids; 0 disables since no 1-based token
        is 0).
    prefill_batch : largest prefill batch bucket (power-of-two buckets
        below it, the engine's `default_buckets`).
    seq_buckets : ascending prompt pad targets; None =
        `default_seq_buckets(max_len)`. `max_len` is always appended so
        any admissible prompt has a bucket.
    """

    def __init__(self, model, *, slots: int = 8, max_len: int = 256,
                 max_new_tokens: int = 64, eos_id: Optional[int] = None,
                 prefill_batch: int = 4,
                 seq_buckets: Optional[Sequence[int]] = None,
                 max_wait_ms: float = 0.0, queue_capacity: int = 256,
                 admission: str = "block", telemetry=None, tracer=None,
                 emit_every: int = 50, hist_window: int = 8192,
                 breaker: Optional[Dict] = None, trace_sample: int = 1,
                 replica_id: Optional[str] = None, start: bool = True):
        for attr in ("init_cache", "apply_prefill", "apply_step"):
            if not hasattr(model, attr):
                raise TypeError(
                    f"{type(model).__name__} has no {attr}(); "
                    "GenerationEngine needs a cache-aware autoregressive "
                    "model (models/transformer.py TransformerLM)")
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        super().__init__(model, max_batch_size=prefill_batch,
                         max_wait_ms=max_wait_ms,
                         queue_capacity=queue_capacity, admission=admission,
                         convert=False, inflight=1, telemetry=telemetry,
                         tracer=tracer, emit_every=emit_every,
                         hist_window=hist_window, breaker=breaker,
                         trace_sample=trace_sample, replica_id=replica_id,
                         start=False)
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.default_max_new_tokens = int(max_new_tokens)
        self.default_eos_id = eos_id
        if seq_buckets is None:
            seq_buckets = default_seq_buckets(self.max_len)
        else:
            seq_buckets = sorted(int(b) for b in seq_buckets)
            if not seq_buckets or seq_buckets[0] < 1 \
                    or len(set(seq_buckets)) != len(seq_buckets):
                raise ValueError(
                    f"seq_buckets must be distinct positive ints, got "
                    f"{seq_buckets}")
            if seq_buckets[-1] > self.max_len:
                raise ValueError(
                    f"seq_buckets cannot exceed max_len {self.max_len}, "
                    f"got {seq_buckets}")
            if seq_buckets[-1] < self.max_len:
                seq_buckets.append(self.max_len)
        self.seq_buckets = list(seq_buckets)
        self._cache = model.init_cache(self.slots, self.max_len)
        # the cache is donated to every step: whoever reads a leaf of it
        # off the dispatcher's thread (`generation_stats()`) holds this
        # lock, as the dispatcher does around each call that donates it
        self._cache_lock = threading.Lock()
        # callers of warmup() waiting for a turn with no slot active
        self._warm_waiters: List[Tuple[threading.Event, Dict]] = []
        # slot table: dispatcher-thread-owned; _active mirrors it under
        # _slock for stats()/generation_stats() readers
        self._slot_req: List[Optional[_GenRequest]] = [None] * self.slots
        self._active = 0
        # the decode step dispatched and not yet fetched: its [slots]
        # tokens on the device, and the requests it was dispatched for
        # (the slot table may have moved on by delivery)
        self._flying: Optional[Tuple] = None
        self._g = {"tokens": 0, "decode_steps": 0, "decode_slot_steps": 0,
                   # steps dispatched while the one before was not yet
                   # fetched; slot-steps computed for a request that had
                   # ended (EOS, cancel) by the time they were delivered
                   "decode_overlapped_steps": 0,
                   "decode_discarded_slot_steps": 0,
                   "prefill_requests": 0, "prefill_batches": 0,
                   "slot_joins": 0, "slot_leaves": 0,
                   "prefill_s": 0.0, "decode_s": 0.0,
                   # the host's parts of a decode step: the jit call,
                   # the blocking fetch (both inside decode_s) and the
                   # delivery of the step's tokens after it
                   "decode_dispatch_s": 0.0, "decode_fetch_s": 0.0,
                   "decode_deliver_s": 0.0}
        # how deep a decode step reads a full layer's cache: the model's
        # ladder (nn/kv_cache.py), or the whole depth where its step has
        # none; dispatched steps counted by the rung they read to
        depths = getattr(model, "decode_depths", None)
        self._depths = depths(self.max_len) if depths is not None \
            else (self.max_len,)
        self._steps_by_rung = [0] * len(self._depths)
        # engine-side token clock (seconds): first token after submit,
        # one a request; gap between two decode steps' deliveries, ONE a
        # step (every slot that decoded in both saw the same gap)
        self.ttft = WindowedHistogram(hist_window)
        self.token_gap = WindowedHistogram(hist_window)
        self._t_decoded: Optional[float] = None  # last decode delivery
        mname = type(self.model).__name__
        model_ref = self.model
        import jax.numpy as jnp
        # `prev` of a step with none before it (every slot is `fresh`
        # then): a device array, so it shares the one decode signature
        self._no_prev = jnp.ones((self.slots,), jnp.int32)

        def _decode_fn(params, cache, prev, tokens, fresh, positions):
            # a slot's input is the step before's output, still on the
            # device, unless the host holds its last token (`fresh`)
            tokens = jnp.where(fresh, tokens, prev)
            logp, cache = model_ref.apply_step(params, tokens, cache,
                                               positions)
            return _next_token(logp), cache

        def _prefill_fn(params, cache, tokens, slot_ids, lengths):
            logp, cache = model_ref.apply_prefill(params, tokens, cache,
                                                  slot_ids, lengths)
            return _next_token(logp), cache

        # the cache is DONATED: the per-token cost of the decode step is
        # one in-place slice update, never a buffer copy; signatures are
        # the token arrays alone (params/cache avals are fixed for life)
        self._decode = CompiledFunction(
            _decode_fn, label=f"serving.decode/{mname}",
            telemetry=telemetry, sig_argnums=(2, 3, 4, 5),
            donate_argnums=(1,))
        self._prefill = CompiledFunction(
            _prefill_fn, label=f"serving.prefill/{mname}",
            telemetry=telemetry, sig_argnums=(2,), donate_argnums=(1,))
        if start:
            self.start()

    # ------------------------------------------------------------ admission
    def generate(self, prompt, max_new_tokens: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 session=None) -> TokenStream:
        """Admit one greedy-decode request; returns its `TokenStream`.
        `prompt` is a 1-D array of 1-based token ids. `deadline_ms`
        bounds the request's QUEUED life (admission + waiting for a free
        slot); once its prefill lands, a request runs to completion.
        `session` is an opaque caller identity echoed into the trace
        record as `session_id` (replayable streams; the fleet router owns
        affinity). Raises `ValueError` for inadmissible requests
        (`len(prompt) + max_new_tokens > max_len`), plus the engine's
        usual admission errors."""
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        if prompt.min() < 1:
            raise ValueError("token ids are 1-based; got a value < 1")
        n_new = self.default_max_new_tokens if max_new_tokens is None \
            else int(max_new_tokens)
        if n_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {n_new}")
        if prompt.size + n_new > self.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({n_new}) "
                f"exceeds the cache depth max_len={self.max_len}")
        now = time.perf_counter()
        deadline = now + deadline_ms / 1e3 if deadline_ms is not None \
            else None
        ctx = TraceContext.new_trace() \
            if (self.telemetry is not None or self.tracer is not None) \
            else None
        req = _GenRequest(prompt, n_new,
                          self.default_eos_id if eos_id is None else eos_id,
                          deadline, ctx, next(self._req_seq),
                          session=session, deadline_budget_ms=deadline_ms)
        self._admit(req)
        return req.stream

    def stream(self, prompt, **kw):
        """Generator convenience: yields tokens as they are produced
        (same failure semantics as iterating `generate(...)`)."""
        yield from self.generate(prompt, **kw)

    def submit(self, sample, deadline_ms: Optional[float] = None,
               session=None):
        raise ServingError(
            "GenerationEngine serves generate()/stream(); use "
            "InferenceEngine for one-shot forwards")

    # ------------------------------------------------------------ warmup
    def warmup(self, sample=None) -> int:
        """Precompile EVERY prefill (batch-bucket, seq-bucket) executable
        plus the single decode executable, blocking until each is built
        and has run once, so first-request latency never pays a compile.
        They run against the LIVE cache (a second one does not fit beside
        a large model's weights), on the dispatcher's own thread in a
        turn in which no slot is active: a warm-up asked for under
        traffic holds admissions back until the slots have drained, then
        runs, then admissions go on. What it writes (one position of
        slot 0, position 0 of every slot) lies where a later prefill
        overwrites it or the mask hides it. `sample` is accepted for
        engine-protocol compatibility (the fleet re-warms rejoining
        replicas) and ignored: generation signatures are fully determined
        by the engine's own buckets. Returns the compile count."""
        with self._lock:
            started = self._thread is not None and not self._closing
            if started:
                done, box = threading.Event(), {}
                self._warm_waiters.append((done, box))
                self._not_empty.notify_all()
        if not started:
            self._warm_now()
        else:
            done.wait()
            if "error" in box:
                raise box["error"]
        return self.compile_count()

    def _warm_now(self):
        """Run every program once against the live cache; dispatcher
        thread (or the caller's, before the engine starts)."""
        with self._cache_lock:
            for t_pad in self.seq_buckets:
                for b in self.buckets:
                    tokens = np.ones((b, t_pad), np.int32)
                    ids = np.zeros((b,), np.int32)
                    lengths = np.ones((b,), np.int32)
                    tok, self._cache = self._prefill(
                        self._params, self._cache, tokens, ids, lengths)
                    np.asarray(tok)  # block: the compile must finish here
                    with self._slock:
                        self._compiled.add((self._gen_sig(t_pad), b))
            tok, self._cache = self._decode(
                self._params, self._cache, self._no_prev,
                np.ones((self.slots,), np.int32),
                np.ones((self.slots,), bool),
                np.zeros((self.slots,), np.int32))
            np.asarray(tok)

    def _serve_warm_waiters(self, error: Optional[BaseException] = None):
        with self._lock:
            waiters, self._warm_waiters = self._warm_waiters, []
        if not waiters:
            return
        if error is None:
            try:
                self._warm_now()
            except Exception as e:  # the donated cache is unknowable now
                error = e
                self._reset_cache(ServingError(f"warm-up failed: {e!r}"))
        for done, box in waiters:
            if error is not None:
                box["error"] = error
            done.set()

    def compile_count(self) -> int:
        """Distinct compiled signatures across the prefill buckets and
        the decode step (steady state: `len(buckets) * len(seq_buckets)
        + 1` after `warmup()`, and NEVER grows under traffic)."""
        return self._prefill._cache_size() + self._decode._cache_size()

    # ------------------------------------------------------------ loop
    @staticmethod
    def _gen_sig(t_pad: int):
        """Breaker/ledger signature for one padded prompt length (plays
        the role of the base engine's feature signature)."""
        return (((t_pad,), "int32"),)

    def _seq_bucket(self, n: int) -> int:
        for b in self.seq_buckets:
            if b >= n:
                return b
        return self.seq_buckets[-1]  # unreachable: admission caps at
        # max_len and the last bucket IS max_len

    def _run(self):
        try:
            while True:
                with self._lock:
                    if not self._q and self._active == 0 \
                            and not self._closing \
                            and not self._warm_waiters:
                        with self._span("await request"):
                            while not self._q and self._active == 0 \
                                    and not self._closing \
                                    and not self._warm_waiters:
                                self._not_empty.wait()
                    if self._closing:
                        if not self._drain:
                            break
                        if not self._q and self._active == 0:
                            break
                    warm = bool(self._warm_waiters)
                # lint: unguarded-ok(the dispatcher thread is the only _active writer; _slock exists for cross-thread stats readers, not this owner-thread read)
                if warm and self._active == 0 and self._flying is None:
                    self._serve_warm_waiters()
                    continue
                # lint: unguarded-ok(the dispatcher thread is the only _active writer; _slock exists for cross-thread stats readers, not this owner-thread read)
                with self._span("generate step", n_active=self._active):
                    if not warm:  # a waiting warm-up lets the slots drain
                        self._admit_into_slots()
                    self._decode_once()
        finally:
            self._serve_warm_waiters(EngineClosedError("engine closed"))
            self._abort_slots(EngineClosedError("engine closed"))
            self._emit_safe({"type": "generation",
                             **self.generation_stats()})

    def _admit_into_slots(self):
        """Move queued requests into free slots and prefill them —
        between decode steps, with no drain barrier: an empty slot fills
        the moment a prefill lands, however old its neighbors are."""
        free = [i for i, r in enumerate(self._slot_req) if r is None]
        if not free:
            return
        take: List[_GenRequest] = []
        dropped: List = []  # (req, status, exc) resolved OUTSIDE the lock
        now = time.perf_counter()
        with self._lock:
            while self._q and len(take) < len(free):
                r = self._q.popleft()
                if r.stream.cancelled:
                    with self._slock:
                        self._n["cancelled"] += 1
                    dropped.append((r, "cancelled", None))
                elif r.deadline is not None and now >= r.deadline:
                    with self._slock:
                        self._n["timed_out"] += 1
                    dropped.append((r, "timeout", ServingTimeoutError(
                        "deadline lapsed in the serving queue "
                        f"({(now - r.t_submit) * 1e3:.1f} ms queued)")))
                else:
                    take.append(r)
            self._not_full.notify_all()
        if not take and not dropped:
            return
        with self._span("admit requests", taken=len(take),
                        dropped=len(dropped)):
            for r, status, exc in dropped:
                r.stream._finish(status, exc)
                self._gen_trace(r, status)
            groups: Dict[int, List[_GenRequest]] = {}
            for r in take:
                groups.setdefault(self._seq_bucket(r.prompt.size),
                                  []).append(r)
            for t_pad, rs in groups.items():
                for i in range(0, len(rs), self.max_batch_size):
                    self._prefill_group(rs[i:i + self.max_batch_size],
                                        t_pad, free)

    def _prefill_group(self, rs: List[_GenRequest], t_pad: int,
                       free: List[int]):
        n = len(rs)
        bucket = self._bucket_for(n)
        sig = self._gen_sig(t_pad)
        br = self._breaker_for(sig, bucket)
        if br is not None and not br.allow():
            with self._slock:
                self._n["shed"] += n
            exc = ServingUnavailableError(
                f"circuit open for prefill domain {br.name}; request "
                "shed without a forward")
            for r in rs:
                r.stream._finish("shed", exc)
                self._gen_trace(r, "shed")
            return
        probe = br is not None and br.state == HALF_OPEN
        slots = [free.pop(0) for _ in rs]
        tokens = np.ones((bucket, t_pad), np.int32)
        slot_ids = np.zeros((bucket,), np.int32)
        lengths = np.ones((bucket,), np.int32)
        for j, r in enumerate(rs):
            tokens[j, :r.prompt.size] = r.prompt
            slot_ids[j] = slots[j]
            lengths[j] = r.prompt.size
        for j in range(n, bucket):
            # bucket padding replicates the LAST request — including its
            # slot id, so the padded row's commit rewrites identical K/V
            tokens[j] = tokens[n - 1]
            slot_ids[j] = slot_ids[n - 1]
            lengths[j] = lengths[n - 1]
        t0 = time.perf_counter()
        for r in rs:
            r.t_gather = t0
            self.queue_wait.record(t0 - r.t_submit)
        dispatched = False
        try:
            with self._span("generate prefill", n=n, bucket=bucket,
                            t_pad=t_pad):
                faults.fire("serve.forward", bucket=bucket, n=n, sig=sig)
                dispatched = True
                with self._cache_lock:
                    first, self._cache = self._prefill(
                        self._params, self._cache, tokens, slot_ids,
                        lengths)
                first = np.asarray(first)  # slot state must be real
                # before the next decode step reads it
        except Exception as e:
            self._prefill_failed(rs, slots, free, br, probe, dispatched, e)
            return
        t1 = time.perf_counter()
        if br is not None:
            br.record_success(probe=probe)
        info = self._prefill.last_info
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
            self._g["prefill_requests"] += n
            self._g["prefill_batches"] += 1
            self._g["prefill_s"] += t1 - t0
            self._g["slot_joins"] += n
            self._g["tokens"] += n
            self._active += n
        for j, r in enumerate(rs):
            r.slot = slots[j]
            r.t_prefill1 = t1
            r.pos = r.prompt.size  # the first decode writes HERE
            self._slot_req[r.slot] = r
            tok = int(first[j])
            r.tokens_out.append(tok)
            r.stream._t = t1  # the group's one reading stamps the token
            r.stream._put(tok)
            self.ttft.record(t1 - r.t_submit)
            if r.stream.cancelled:
                self._retire(r, "cancelled")
            elif tok == r.eos_id or r.max_new_tokens == 1:
                self._retire(r, "ok")

    def _prefill_failed(self, rs, slots, free, br, probe,
                        dispatched: bool, e: Exception):
        """A failed prefill rejects only its OWN group — but once the
        executable DISPATCHED, the donated cache is unknowable, so the
        engine reallocates it and fails the active streams too (they
        lost their history)."""
        free.extend(slots)
        with self._slock:
            self._n["failed"] += len(rs)
            self._n["batches"] += 1
        if br is not None:
            br.record_failure(probe=probe)
        exc = ServingError(f"prefill failed: {e!r}")
        for r in rs:
            r.stream._finish("error", exc)
            self._gen_trace(r, "error", error=repr(e))
        if dispatched:
            logger.warning("prefill execution failed (%r); reallocating "
                           "the donated KV cache and aborting active "
                           "streams", e)
            self._reset_cache(exc)

    def _decode_once(self):
        """One turn of the decode pipeline: dispatch step n+1, THEN fetch
        and deliver step n, so the host's share of a step (the jit call,
        the fetch's wake-up, delivery) runs while the device decodes.

        Step n+1 takes its tokens from step n's result on the device, so
        its roster is settled before step n is seen: every active request
        but those that step n completes by count. One that step n ends by
        EOS, or that was cancelled, rides along once more and is dropped
        at delivery; its stray K/V write lies beyond what a later
        occupant of the slot attends to before overwriting it. Each step
        is ONE fixed-shape program over all slots, inactive slots riding
        along (zero recompiles, whatever the churn)."""
        flying = self._flying
        riders = [r for r in self._slot_req
                  if r is not None and r.asked() < r.max_new_tokens]
        if not riders and flying is None:
            return
        if riders:
            with self._span("decode build"):
                tokens = np.ones((self.slots,), np.int32)
                fresh = np.ones((self.slots,), bool)
                positions = np.zeros((self.slots,), np.int32)
                for r in riders:
                    if r.asked() == len(r.tokens_out):  # all delivered
                        tokens[r.slot] = r.tokens_out[-1]
                    else:  # its last token is step n's, on the device
                        fresh[r.slot] = False
                    positions[r.slot] = r.pos
                rung = int(kv_cache.rung_index(self._depths, positions))
        self._flying = None  # step n is this turn's to fetch
        t0 = time.perf_counter()
        dispatch_s = fetch_s = 0.0
        try:
            with self._span("generate decode", n=len(riders)):
                if riders:
                    faults.fire(SITE_DECODE, n=len(riders))
                    t = time.perf_counter()
                    with self._span("decode dispatch"), self._cache_lock:
                        nxt, self._cache = self._decode(
                            self._params, self._cache,
                            self._no_prev if flying is None else flying[0],
                            tokens, fresh, positions)
                    dispatch_s = time.perf_counter() - t
                    self._flying = (nxt, riders)
                    for r in riders:
                        r.pos += 1
                if flying is not None:
                    t = time.perf_counter()
                    with self._span("decode fetch"):
                        got = np.asarray(flying[0])  # waits for step n
                    fetch_s = time.perf_counter() - t
        except Exception as e:
            # each active stream is counted "failed" ONCE, by _retire
            self._reset_cache(ServingError(f"decode step failed: {e!r}"))
            return
        # the turn's ONE delivery reading: the end of decode_s, the stamp
        # of every token step n emits, the start of `decode deliver`
        now = time.perf_counter()
        with self._slock:
            self._g["decode_s"] += now - t0
            self._g["decode_dispatch_s"] += dispatch_s
            self._g["decode_fetch_s"] += fetch_s
            if riders:
                self._steps_by_rung[rung] += 1
                if flying is not None:
                    self._g["decode_overlapped_steps"] += 1
        if flying is not None:
            self._deliver(got, flying[1], now)

    def _deliver(self, got: np.ndarray, roster: List[_GenRequest],
                 now: float):
        """Hand a fetched step's tokens to the requests it was dispatched
        for. One that has left its slot since (EOS or cancellation seen a
        step late; the slot may hold another request by now) gets
        nothing, and its slot-step is counted as discarded."""
        live = [r for r in roster if self._slot_req[r.slot] is r]
        with self._span("decode deliver", n=len(live)):
            self.batch_sizes.record(len(live))
            if self._t_decoded is not None:
                self.token_gap.record(now - self._t_decoded)
            self._t_decoded = now
            info = self._decode.last_info
            with self._slock:
                self._g["decode_steps"] += 1
                self._g["decode_slot_steps"] += len(live)
                self._g["decode_discarded_slot_steps"] += \
                    len(roster) - len(live)
                self._g["tokens"] += len(live)
                if info is not None:
                    self._flops_total += info.get("flops") or 0.0
                    self._bytes_total += info.get("bytes_accessed") or 0.0
                steps = self._g["decode_steps"]
            for r in live:
                tok = int(got[r.slot])
                r.tokens_out.append(tok)
                r.stream._t = now  # the step's one reading
                r.stream._put(tok)
                if r.stream.cancelled:
                    self._retire(r, "cancelled")
                elif tok == r.eos_id \
                        or len(r.tokens_out) >= r.max_new_tokens:
                    self._retire(r, "ok")
            flying = self._flying
            if flying is not None and not any(
                    self._slot_req[r.slot] is r for r in flying[1]):
                # all the step in flight was dispatched for have ended:
                # nobody waits for it, so it is not fetched
                with self._slock:
                    self._g["decode_discarded_slot_steps"] += len(flying[1])
                self._flying = None
            if steps % self.emit_every == 0 and self.telemetry is not None:
                self._emit_safe({"type": "generation",
                                 **self.generation_stats()})
            delivered = time.perf_counter() - now
            with self._slock:
                self._g["decode_deliver_s"] += delivered

    def _retire(self, r: _GenRequest, status: str,
                exc: Optional[BaseException] = None):
        """A request leaves its slot BETWEEN steps (EOS, token budget,
        cancellation, abort) — the slot frees for the next admission
        while its neighbors keep decoding."""
        self._slot_req[r.slot] = None
        with self._slock:
            self._active -= 1
            idle = self._active == 0
            self._g["slot_leaves"] += 1
            key = {"ok": "completed", "error": "failed",
                   "cancelled": "cancelled", "timeout": "timed_out"}
            self._n[key.get(status, "failed")] += 1
        if idle:
            # no slot is left to see the gap to the next decode step
            self._t_decoded = None
        if status == "ok":
            self.latency.record(time.perf_counter() - r.t_submit)
        r.stream._finish(status, exc)
        self._gen_trace(r, status,
                        error=repr(exc) if exc is not None else None)

    def _reset_cache(self, exc: BaseException):
        """The donated cache's buffers are unknown after a failed
        execution: fail every active stream (their KV history is gone),
        reallocate, and keep serving fresh requests."""
        with self._cache_lock:
            self._cache = self.model.init_cache(self.slots, self.max_len)
        self._flying = None
        for r in list(self._slot_req):
            if r is not None:
                self._retire(r, "error", exc)

    def _abort_slots(self, exc: BaseException):
        self._flying = None
        for r in list(self._slot_req):
            if r is not None:
                self._retire(r, "cancelled", exc)

    def _fail_queued(self, exc: BaseException):
        with self._lock:
            left = list(self._q)
            self._q.clear()
            self._not_full.notify_all()
        with self._slock:
            self._n["cancelled"] += len(left)
        for r in left:
            r.stream._finish("cancelled", exc)
            self._gen_trace(r, "cancelled", error=repr(exc))

    # ------------------------------------------------------------ telemetry
    def generation_stats(self) -> Dict:
        """The `generation` record body: token throughput, decode batch
        occupancy, prefill/decode split, and slot churn (documented in
        docs/observability.md)."""
        with self._slock:
            g = dict(self._g)
            by_rung = list(self._steps_by_rung)
            active = self._active
        with self._lock:
            depth = len(self._q)
        elapsed = time.monotonic() - self._t0_mono
        # what the model counts on the device, carried in the cache
        # pytree (no fetch a step): read here, in one fetch
        model_counters = {}
        if hasattr(self.model, "cache_stats"):
            try:
                with self._cache_lock:
                    model_counters = self.model.cache_stats(self._cache)
            except Exception:  # a cache lost to a failed step
                logger.exception("cache_stats failed; counters left out")
        occ = g["decode_slot_steps"] / (g["decode_steps"] * self.slots) \
            if g["decode_steps"] else None
        # over the steps dispatched: one in flight, or dropped unfetched
        # because all its requests had ended, is not yet in decode_steps
        read_depth = sum(d * n for d, n in zip(self._depths, by_rung))
        return {
            "slots": self.slots, "active_slots": active,
            "queue_depth": depth, "max_len": self.max_len,
            "tokens_total": g["tokens"],
            "tokens_per_sec": round(g["tokens"] / elapsed, 2)
            if elapsed > 0 and g["tokens"] else None,
            "decode_steps": g["decode_steps"],
            "decode_occupancy": round(occ, 4) if occ is not None else None,
            "decode_read_depth_total": read_depth,
            "decode_depth_share": round(
                read_depth / (sum(by_rung) * self.max_len), 4)
            if any(by_rung) else None,
            "decode_steps_by_depth": {str(d): n for d, n in
                                      zip(self._depths, by_rung)},
            "prefill_requests": g["prefill_requests"],
            "prefill_batches": g["prefill_batches"],
            "prefill_s_total": round(g["prefill_s"], 4),
            "decode_s_total": round(g["decode_s"], 4),
            "decode_dispatch_s_total": round(g["decode_dispatch_s"], 4),
            "decode_fetch_s_total": round(g["decode_fetch_s"], 4),
            "decode_deliver_s_total": round(g["decode_deliver_s"], 4),
            "decode_overlapped_steps": g["decode_overlapped_steps"],
            "decode_discarded_slot_steps": g["decode_discarded_slot_steps"],
            "slot_joins": g["slot_joins"],
            "slot_leaves": g["slot_leaves"],
            **self.ttft.snapshot("ttft_ms", scale=1e3),
            **self.token_gap.snapshot("itl_ms", scale=1e3),
            **model_counters,
        }

    def _gen_trace(self, r: _GenRequest, status: str,
                   error: Optional[str] = None):
        """One `trace` record per request, kind="generate": critical path
        queue -> prefill -> decode (plus the span tree on a request lane
        with a tracer attached). Never raises."""
        if self.telemetry is None and self.tracer is None:
            return
        try:
            self._gen_trace_impl(r, status, error)
        except Exception:
            logger.exception("generation trace emission failed; dropped")

    def _gen_trace_impl(self, r: _GenRequest, status: str,
                        error: Optional[str]):
        if r.ctx is None:
            return
        if status == "ok" and r.seq % self.trace_sample:
            return  # sampled out; non-ok outcomes always emit
        t_done = time.perf_counter()
        phases = [("queue", r.t_submit,
                   r.t_gather if r.t_gather is not None else t_done)]
        if r.t_gather is not None and r.t_prefill1 is not None:
            phases.append(("prefill", r.t_gather, r.t_prefill1))
            phases.append(("decode", r.t_prefill1, t_done))
        total_ms = (t_done - r.t_submit) * 1e3
        tracer = self.tracer
        if tracer is not None:
            off = tracer.now_us() - time.perf_counter() * 1e6
            tid = tracer.lane(f"request-{r.seq % 16}")
            tracer.add_span("generate", r.t_submit * 1e6 + off,
                            (t_done - r.t_submit) * 1e6, cat="serving",
                            tid=tid, ctx=r.ctx, status=status,
                            tokens=len(r.tokens_out))
            for name, a, b in phases:
                tracer.add_span(name, a * 1e6 + off, (b - a) * 1e6,
                                cat="serving", tid=tid, ctx=r.ctx.child())
        if self.telemetry is None:
            return
        rec = {"type": "trace", "trace_id": r.ctx.trace_id,
               "kind": "generate", "status": status,
               "latency_ms": round(total_ms, 3),
               "tokens": len(r.tokens_out),
               "prompt_tokens": int(r.prompt.size),
               "arrival_offset_ms":
                   round((r.t_submit - self._t0_perf) * 1e3, 3)}
        stamps = r.stream.token_times()
        if stamps:
            # the engine's own token clock (TokenStream.token_times)
            rec["ttft_ms"] = round((stamps[0] - r.t_submit) * 1e3, 3)
        if len(stamps) > 1:
            gaps = np.diff(stamps) * 1e3
            rec["itl_p50_ms"] = round(float(np.median(gaps)), 3)
            rec["itl_max_ms"] = round(float(gaps.max()), 3)
        if r.session is not None:
            rec["session_id"] = str(r.session)
        if r.deadline_budget_ms is not None:
            rec["deadline_budget_ms"] = round(r.deadline_budget_ms, 3)
        if self.replica_id is not None:
            rec["replica_id"] = self.replica_id
        if status == "ok" and self.trace_sample > 1:
            rec["sample_weight"] = self.trace_sample
        field = {"queue": "queue_wait_ms", "prefill": "prefill_ms",
                 "decode": "decode_ms"}
        path = []
        for name, a, b in phases:
            ms = (b - a) * 1e3
            path.append({"name": name, "ms": round(ms, 3),
                         "frac": round(ms / total_ms, 4)
                         if total_ms > 0 else None})
            rec[field[name]] = round(ms, 3)
        rec["critical_path"] = path
        if error is not None:
            rec["error"] = error
        self._emit_safe(rec)


def greedy_decode_reference(model, params, prompt, max_new_tokens: int,
                            eos_id: Optional[int] = None,
                            pad_to: Optional[int] = None, fwd=None):
    """One-request-at-a-time FULL-RECOMPUTE greedy decode — the O(L^2)
    serial baseline the continuous-batched engine must match
    token-for-token (the parity contract in tests/test_generation.py and
    `bench_cli --generate`).

    Recomputes the whole `[1, pad_to]` padded sequence through
    `model.apply` for every emitted token (one fixed-shape compile; pass
    a shared jitted `fwd(params, tokens)` to amortize it across calls).
    Returns the emitted 1-based token list (EOS included when hit)."""
    import jax
    import jax.numpy as jnp
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    total = int(pad_to or (prompt.size + max_new_tokens))
    if prompt.size + max_new_tokens > total:
        raise ValueError("pad_to must hold prompt + max_new_tokens")
    if fwd is None:
        fwd = jax.jit(lambda p, t: model.apply(p, t, None))
    toks = np.ones((1, total), np.int32)
    toks[0, :prompt.size] = prompt
    n = prompt.size
    out: List[int] = []
    for _ in range(max_new_tokens):
        logp = fwd(params, jnp.asarray(toks))
        nxt = int(np.asarray(jnp.argmax(logp[0, n - 1]))) + 1
        out.append(nxt)
        if n < total:
            toks[0, n] = nxt
        n += 1
        if eos_id is not None and nxt == eos_id:
            break
    return out
