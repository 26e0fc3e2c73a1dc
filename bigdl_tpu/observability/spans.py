"""Nested host-side trace spans, exportable as Chrome/Perfetto trace JSON.

Why host spans at all on a compiled runtime: the XLA device trace (xprof /
`jax.profiler.start_trace`) shows fused ops, not framework phases — "data
fetch", "step dispatch", "loss sync", "checkpoint" are host concepts the
compiler never sees. A `SpanTracer` records those phases with wall-clock
timestamps and exports the standard Chrome trace-event format, which
Perfetto (and TensorBoard's trace viewer) loads directly; opening the host
trace next to a device trace captured in the same run lines the two up on
absolute time.

Each span also enters a `jax.profiler.TraceAnnotation`, so when the XLA
profiler IS active the same phase names appear inside the device trace's
host rows — one naming scheme across both views.

Request-scoped tracing: a `TraceContext` gives a span distributed identity
(trace_id / span_id / parent_id). Open one with `tracer.trace(...)` (root)
or pass `ctx=` explicitly; spans opened inside an active context become its
children automatically (thread-local propagation), and the ids land in the
exported event `args` so one request's spans can be filtered out of a busy
trace by trace_id. Cross-thread hops (a request handed from the submitting
thread to a dispatcher) carry the context on the request object and link
the two lanes with Chrome flow events (`add_flow`).

Process lanes: every `SpanTracer` gets a distinct Perfetto pid derived
from its `process_name` registration (same name -> same lane, new name ->
new lane), so several tracers — one per worker of a `SimulatedCluster`,
or a serving tracer next to a training tracer — merge into ONE loadable
trace with `merge_traces` / `export_merged` without colliding lanes.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

# ---------------------------------------------------------------- identity
_pid_lock = threading.Lock()
_pids: Dict[str, int] = {}


def _pid_for(process_name: str) -> int:
    """Stable Perfetto pid for a process lane name: first registration
    allocates the next pid, re-registration returns the same one — two
    tracers exporting into one merged trace can never collide unless they
    deliberately share a name (in which case they SHARE the lane)."""
    with _pid_lock:
        pid = _pids.get(process_name)
        if pid is None:
            pid = len(_pids) + 1
            _pids[process_name] = pid
        return pid


def _new_id(nbytes: int) -> str:
    return os.urandom(nbytes).hex()


def _trace_annotation():
    """`jax.profiler.TraceAnnotation`, or False where JAX has none: a
    span looks it up once a tracer, not once a span."""
    try:
        import jax
        return jax.profiler.TraceAnnotation
    except Exception:
        return False


class TraceContext:
    """Distributed span identity: (trace_id, span_id, parent_id).

    One `trace_id` names a whole request/run; each span under it has its
    own `span_id` and points at its parent. `new_trace()` mints a root,
    `child()` derives the context for a sub-span. Immutable and cheap —
    safe to stash on queued request objects and hand across threads."""

    __slots__ = ("trace_id", "span_id", "parent_id")

    def __init__(self, trace_id: str, span_id: str,
                 parent_id: Optional[str] = None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id

    @classmethod
    def new_trace(cls) -> "TraceContext":
        return cls(_new_id(8), _new_id(4), None)

    def child(self) -> "TraceContext":
        return TraceContext(self.trace_id, _new_id(4), self.span_id)

    def ids(self) -> Dict[str, str]:
        out = {"trace_id": self.trace_id, "span_id": self.span_id}
        if self.parent_id is not None:
            out["parent_id"] = self.parent_id
        return out

    def __repr__(self):
        return (f"TraceContext({self.trace_id}/{self.span_id}"
                f"<-{self.parent_id})")


class SpanTracer:
    """Records nested `with tracer.span(name): ...` phases.

    Spans are complete events ("ph": "X") in the Chrome trace-event format:
    microsecond wall-clock timestamps (absolute epoch, so the trace can be
    overlaid on an xprof device trace from the same run), per-thread track
    ids, and arbitrary JSON-safe `args`. Thread-safe; each thread carries
    its own span stack and trace-context stack.

    `annotate=True` (default) additionally wraps every span in
    `jax.profiler.TraceAnnotation`, a no-op unless the XLA profiler is
    tracing.

    `max_events` bounds host memory for long runs (the loops record a
    handful of spans per iteration): once full, the OLDEST events are
    dropped — the export keeps the most recent window and reports the
    drop count in the process metadata (`dropped_events`)."""

    def __init__(self, process_name: str = "bigdl_tpu",
                 annotate: bool = True, max_events: int = 1_000_000):
        self.process_name = process_name
        self.pid = _pid_for(process_name)
        self.annotate = annotate
        self._events: deque = deque(maxlen=max_events)
        self.dropped_events = 0
        self._lock = threading.Lock()
        self._tls = threading.local()  # per-thread TraceContext stack
        self._lanes: Dict[int, str] = {}  # tid -> display name
        self._next_lane_tid = 1_000_000_000  # synthetic-lane tid range
        # jax.profiler.TraceAnnotation, resolved on the first span (not
        # here: building a tracer must not import jax); False = unusable
        self._annotation = None
        # monotonic offsets supply the durations (an NTP step mid-run can
        # never produce a negative span); the wall base, sampled once,
        # anchors them to absolute epoch time for cross-trace alignment
        self._wall0_us = time.time() * 1e6
        self._mono0 = time.monotonic()

    def _now_us(self) -> float:
        return self._wall0_us + (time.monotonic() - self._mono0) * 1e6

    def now_us(self) -> float:
        """This tracer's current timestamp (absolute epoch microseconds)
        — for callers synthesizing retroactive spans via `add_span`."""
        return self._now_us()

    # ------------------------------------------------------------ context
    def _ctx_stack(self) -> List[TraceContext]:
        stack = getattr(self._tls, "ctx", None)
        if stack is None:
            stack = self._tls.ctx = []
        return stack

    def current_context(self) -> Optional[TraceContext]:
        """The innermost active `TraceContext` on this thread, or None."""
        stack = self._ctx_stack()
        return stack[-1] if stack else None

    @contextlib.contextmanager
    def trace(self, name: str, cat: str = "host", **args):
        """Open a ROOT trace: mints a fresh trace_id and records `name` as
        its root span; spans opened inside become children automatically.
        Yields the root `TraceContext` (pass `.child()` across threads)."""
        ctx = TraceContext.new_trace()
        with self.span(name, cat=cat, ctx=ctx, **args):
            yield ctx

    def begin_trace(self, name: str, cat: str = "host",
                    **args) -> TraceContext:
        """Non-lexical root trace for driver loops that cannot wrap their
        whole body in a `with`: pushes a fresh root context for this
        thread and returns it. Close with `end_trace()` — the root span
        is recorded then, covering begin..end. A stale root a crashed
        run left open is superseded (its spans are discarded, the stack
        restored to its base), but an ENCLOSING user context — `with
        tracer.trace(...): opt.optimize()` — survives: begin/end only
        own the stack above the depth they found."""
        stack = self._ctx_stack()
        frame = getattr(self._tls, "open_roots", None)
        if frame is None:
            frame = self._tls.open_roots = []
        if frame:  # stale root from a crashed/retried run: unwind to it
            _, _, _, _, _, base = frame[0]
            del frame[:]
            del stack[base:]
        # inside an enclosing user trace the run joins it as a child;
        # otherwise it roots a fresh trace
        ctx = stack[-1].child() if stack else TraceContext.new_trace()
        frame.append((ctx, name, cat, self._now_us(), args, len(stack)))
        stack.append(ctx)
        return ctx

    def end_trace(self):
        """Record the span opened by `begin_trace`, popping the stack
        back to the depth `begin_trace` found (an enclosing user context
        is restored). Safe to call when no root is open (idempotent)."""
        frame = getattr(self._tls, "open_roots", None)
        if not frame:
            return
        ctx, name, cat, t0, args, base = frame.pop()
        stack = self._ctx_stack()
        del stack[base:]
        self.add_span(name, t0, self._now_us() - t0, cat=cat, ctx=ctx,
                      **args)

    # ------------------------------------------------------------ recording
    @contextlib.contextmanager
    def span(self, name: str, cat: str = "host",
             ctx: Optional[TraceContext] = None, **args):
        """Time a nested phase. `args` must be JSON-serializable; they land
        in the trace event's `args` field (visible in Perfetto's detail
        pane). `ctx` pins the span's trace identity explicitly; without
        it, an active context on this thread makes the span its child, and
        with no active context the span stays identity-free (zero-cost
        compatibility for plain phase timing)."""
        ann = None
        if self.annotate:
            cls = self._annotation
            if cls is None:
                cls = self._annotation = _trace_annotation()
            if cls:
                try:
                    ann = cls(name)
                    ann.__enter__()
                except Exception:
                    ann = None
        stack = self._ctx_stack()
        if ctx is None and stack:
            ctx = stack[-1].child()
        pushed = ctx is not None
        if pushed:
            stack.append(ctx)
        t0 = self._now_us()
        try:
            yield self
        finally:
            dur = self._now_us() - t0
            if pushed and stack and stack[-1] is ctx:
                stack.pop()
            if ann is not None:
                ann.__exit__(None, None, None)
            if ctx is not None:
                args = {**args, **ctx.ids()}
            tid = threading.get_ident() % 2 ** 31
            ev = {"name": name, "cat": cat, "ph": "X",
                  "ts": t0, "dur": dur, "pid": self.pid, "tid": tid}
            if args:
                ev["args"] = args
            with self._lock:
                if tid not in self._lanes:
                    self._lanes[tid] = threading.current_thread().name
                self._append(ev)

    def _append(self, ev):  # under self._lock
        if len(self._events) == self._events.maxlen:
            self.dropped_events += 1
        self._events.append(ev)

    def lane(self, name: str) -> int:
        """A synthetic track (tid) with a display name — for spans that
        belong to a logical flow (one serving request) rather than a real
        thread. Same name -> same tid."""
        with self._lock:
            for tid, lname in self._lanes.items():
                if lname == name and tid >= 1_000_000_000:
                    return tid
            tid = self._next_lane_tid
            self._next_lane_tid += 1
            self._lanes[tid] = name
            return tid

    def add_span(self, name: str, ts_us: float, dur_us: float,
                 cat: str = "host", tid: Optional[int] = None,
                 ctx: Optional[TraceContext] = None, **args):
        """Record a complete span with EXPLICIT timestamps — for producers
        that only know a phase's bounds after the fact (the serving engine
        reconstructs a request's queue/dispatch/fetch phases at completion
        time). `tid` defaults to the calling thread; use `lane(name)` for
        a synthetic track."""
        if ctx is not None:
            args = {**args, **ctx.ids()}
        if tid is None:
            tid = threading.get_ident() % 2 ** 31
            tname = threading.current_thread().name
        else:
            tname = None
        ev = {"name": name, "cat": cat, "ph": "X",
              "ts": ts_us, "dur": max(0.0, dur_us),
              "pid": self.pid, "tid": tid}
        if args:
            ev["args"] = args
        with self._lock:
            if tname is not None:
                self._lanes.setdefault(tid, tname)
            self._append(ev)

    def add_flow(self, flow_id, name: str, ts_from_us: float, tid_from: int,
                 ts_to_us: float, tid_to: int, cat: str = "flow"):
        """Link two tracks with a Chrome flow arrow (`ph:"s"` -> `ph:"f"`)
        — how a batch span points back at the member requests it served.
        `flow_id` must be unique per arrow within the trace."""
        s = {"name": name, "cat": cat, "ph": "s", "id": flow_id,
             "ts": ts_from_us, "pid": self.pid, "tid": tid_from}
        f = {"name": name, "cat": cat, "ph": "f", "bp": "e", "id": flow_id,
             "ts": max(ts_to_us, ts_from_us), "pid": self.pid,
             "tid": tid_to}
        with self._lock:
            self._append(s)
            self._append(f)

    @property
    def events(self) -> List[Dict]:
        """Snapshot of the recorded events (for tests/tools)."""
        with self._lock:
            return list(self._events)

    def reset(self):
        with self._lock:
            self._events.clear()
            self.dropped_events = 0

    # ------------------------------------------------------------ export
    def to_chrome_trace(self) -> Dict:
        """The trace as a Chrome trace-event JSON object (Perfetto-loadable:
        `{"traceEvents": [...], "displayTimeUnit": "ms"}` plus process/
        thread metadata events)."""
        with self._lock:
            events = list(self._events)
            dropped = self.dropped_events
            lanes = dict(self._lanes)
        proc_args = {"name": self.process_name}
        if dropped:
            proc_args["dropped_events"] = dropped
        meta = [{"name": "process_name", "ph": "M", "pid": self.pid,
                 "tid": 0, "args": proc_args}]
        for tid in sorted({e["tid"] for e in events}):
            meta.append({"name": "thread_name", "ph": "M", "pid": self.pid,
                         "tid": tid,
                         "args": {"name": lanes.get(tid, f"host-{tid}")}})
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}

    def export(self, path: str) -> str:
        """Write the Chrome trace JSON to `path` (chrome://tracing or
        https://ui.perfetto.dev open it directly). Returns `path`."""
        from bigdl_tpu.utils import filesystem as fsys
        with fsys.open_file(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path


def merge_traces(tracers: Sequence[SpanTracer]) -> Dict:
    """ONE Chrome trace document from several tracers — each keeps its own
    process lane (distinct pid per `process_name` registration), so a
    2-worker `SimulatedCluster` run, or serving + training tracers from
    the same process, load as one aligned Perfetto view. Timestamps are
    absolute epoch microseconds in every tracer, so no rebasing is
    needed."""
    events: List[Dict] = []
    for tr in tracers:
        events.extend(tr.to_chrome_trace()["traceEvents"])
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def export_merged(path: str, tracers: Sequence[SpanTracer]) -> str:
    """`merge_traces` straight to a file; returns `path`."""
    from bigdl_tpu.utils import filesystem as fsys
    with fsys.open_file(path, "w") as f:
        json.dump(merge_traces(tracers), f)
    return path
