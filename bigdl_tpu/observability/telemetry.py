"""Structured run metrics: per-step records fanned out to pluggable sinks.

A training run should leave a machine-readable record, not just log lines.
`Telemetry` turns the optimizer's per-sync figures (step, loss, lr,
throughput, step wall time, optional grad/param norms) plus host/device
resource stats into flat JSON-safe dicts and hands them to every attached
sink. Record types:

- `run_start`  — one per `optimize()` call: run config (devices, model).
- `step`       — one per sync point (= per iteration at sync_interval 1).
- `event`      — health-monitor findings (nan_guard, straggler, ...).
- `compile`    — one per distinct compiled signature (observability/
                 compilation.py): lower/compile seconds, FLOPs, cache hit.
- `run_end`    — final step count plus the `Metrics.as_dict()` phase table.

The serving engine adds `serving_stats`/`serving_summary` through the same
sinks (and the serving fleet adds `serving_fleet`). Every record type's field contract is declared in `RECORD_SCHEMAS`
(checked by `validate_record`, pinned by tests) and documented
field-by-field in docs/observability.md.

Every record carries `time` (epoch seconds — absolute, so streams overlay
on Perfetto device traces). Durations inside records (`step_time_s`,
`lower_s`, ...) are measured with monotonic clocks by their producers; an
NTP step skews `time`, never a duration.
"""

from __future__ import annotations

import json
import math
import os
import time
from typing import Dict, List, Optional

from bigdl_tpu.resilience import faults


def host_rss_mb() -> Optional[float]:
    """Current resident set size of this process in MB (from
    /proc/self/statm; None where procfs is unavailable)."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE") / 1e6
    except (OSError, ValueError, IndexError):
        return None


def device_memory_stats() -> List[Dict]:
    """Per-device memory stats from `jax.local_devices()` — bytes in use
    and peak, where the backend reports them (TPU does; CPU returns [])."""
    import jax
    out = []
    for d in jax.local_devices():
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        out.append({"device": str(d),
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return out


def sanitize_nonfinite(obj):
    """Strict-JSON view of a record: non-finite floats become `null`, and
    a dict field additionally gains a sibling `"<field>_nonfinite": true`
    marker so consumers can tell "loss was NaN" from "loss was absent".
    Recurses through nested dicts/lists; everything else passes through
    unchanged. (`json.dumps` default `allow_nan=True` emits bare `NaN`
    tokens, which strict parsers reject.)"""
    if isinstance(obj, dict):
        out = {}
        for k, v in obj.items():
            if isinstance(v, float) and not math.isfinite(v):
                out[k] = None
                out[k + "_nonfinite"] = True
            else:
                out[k] = sanitize_nonfinite(v)
        return out
    if isinstance(obj, (list, tuple)):
        return [None if isinstance(v, float) and not math.isfinite(v)
                else sanitize_nonfinite(v) for v in obj]
    return obj


class TelemetrySink:
    """A destination for telemetry records. Subclasses implement `emit`
    (one flat JSON-safe dict per call); `close` is optional."""

    def emit(self, record: Dict):
        raise NotImplementedError

    def close(self):
        pass


class JsonlSink(TelemetrySink):
    """Append records to a JSONL file, one JSON object per line, flushed
    per record so a crashed run still leaves its stream on disk.

    Every line is STRICT JSON: non-finite floats are encoded as `null`
    with a sibling `<field>_nonfinite: true` marker (see
    `sanitize_nonfinite`) — a NaN loss must not poison downstream strict
    parsers with a bare `NaN` token."""

    def __init__(self, path: str, append: bool = True):
        self.path = path
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        self._f = open(path, "a" if append else "w")

    def emit(self, record: Dict):
        self._f.write(json.dumps(sanitize_nonfinite(record),
                                 allow_nan=False) + "\n")
        self._f.flush()

    def close(self):
        self._f.close()


class InMemorySink(TelemetrySink):
    """Collects records in a list — the test/notebook sink."""

    def __init__(self):
        self.records: List[Dict] = []

    def emit(self, record: Dict):
        self.records.append(record)

    def steps(self) -> List[Dict]:
        """Just the per-step records, in order."""
        return [r for r in self.records if r.get("type") == "step"]


class SummarySink(TelemetrySink):
    """Bridge into the existing TensorBoard event writer: numeric fields of
    `step` records become `TrainSummary.add_scalar` calls under
    `telemetry/<field>` tags, so the telemetry stream shows up next to the
    classic Loss/Throughput curves."""

    _SKIP = ("step", "epoch", "time", "type")

    def __init__(self, summary):
        self.summary = summary

    def emit(self, record: Dict):
        if record.get("type") != "step" or "step" not in record:
            return
        it = int(record["step"])
        for key, val in record.items():
            if key in self._SKIP or not isinstance(val, (int, float)):
                continue
            self.summary.add_scalar(f"telemetry/{key}", float(val), it)

    def close(self):
        self.summary.close()


class CompositeSink(TelemetrySink):
    """Fan one stream out to several sinks."""

    def __init__(self, *sinks: TelemetrySink):
        self.sinks = list(sinks)

    def emit(self, record: Dict):
        for s in self.sinks:
            s.emit(record)

    def close(self):
        for s in self.sinks:
            s.close()


_NUM = (int, float)
_OPT_NUM = (int, float, type(None))
_OPT_STR = (str, type(None))

#: Declared field contract per record type — what sink consumers may rely
#: on. `required` fields are always present (with the given types),
#: `optional` fields are typed when present, and unless `open` is True any
#: OTHER field is a contract violation (`<field>_nonfinite` markers from
#: the strict-JSON encoding are always allowed). `event` is open: each
#: monitor/resilience event carries its own context fields.
RECORD_SCHEMAS: Dict[str, Dict] = {
    "run_start": {
        "required": {},
        "optional": {"loop": str, "model": str, "optim_method": str,
                     "backend": str, "n_devices": int, "sync_interval": int},
    },
    "step": {
        "required": {"step": int},
        "optional": {
            "epoch": int, "loss": _OPT_NUM, "lr": _NUM,
            "throughput": _NUM, "step_time_s": _NUM, "records": int,
            "grad_norm": _NUM, "param_norm": _NUM, "nonfinite_steps": int,
            "host_rss_mb": _NUM, "device_mem": list,
            "prefetch_queue_depth": int, "prefetch_fetch_wait_s": _NUM,
            "prefetch_worker_busy": _NUM,
            "flops_per_step": _OPT_NUM, "bytes_accessed": _OPT_NUM,
            "mfu": _OPT_NUM,
        },
    },
    "event": {
        "required": {"event": str},
        "optional": {},
        "open": True,
    },
    "compile": {
        "required": {"label": str, "signature": str, "lower_s": _NUM,
                     "compile_s": _NUM, "cache_hit": bool},
        "optional": {"jaxpr_eqns": _OPT_NUM, "flops": _OPT_NUM,
                     "bytes_accessed": _OPT_NUM},
    },
    "run_end": {
        "required": {},
        "optional": {"step": int, "epoch": int, "loss": _OPT_NUM,
                     "metrics": dict},
    },
    # one per completed serving request (serving/engine.py): the
    # critical-path phase breakdown under the request's trace identity.
    # kind="generate" requests (serving/generation.py) carry the
    # prefill/decode split and the emitted token count instead of the
    # batch-forward phases.
    "trace": {
        "required": {"trace_id": str, "kind": str, "status": str},
        "optional": {"latency_ms": _NUM, "queue_wait_ms": _NUM,
                     "batch_form_ms": _NUM, "dispatch_ms": _NUM,
                     "forward_ms": _NUM, "fetch_ms": _NUM,
                     "prefill_ms": _NUM, "decode_ms": _NUM, "tokens": int,
                     # kind="generate": the engine's own token clock
                     # (TokenStream.token_times): first token after
                     # submit, median and widest gap between tokens
                     "ttft_ms": _NUM, "itl_p50_ms": _NUM,
                     "itl_max_ms": _NUM,
                     "batch": int, "bucket": int,
                     "critical_path": list, "error": str,
                     "sample_weight": int, "replica_id": str,
                     # replayable-workload fields (workload/record.py):
                     # arrival offset relative to the emitter's start,
                     # session identity, the deadline BUDGET the caller
                     # gave (latency_ms is what happened; the budget is
                     # what was promised), and the request shape/prompt
                     # size needed to re-synthesize an equivalent request
                     "arrival_offset_ms": _NUM, "session_id": _OPT_STR,
                     "deadline_budget_ms": _OPT_NUM, "idempotent": bool,
                     "shape": list, "prompt_tokens": int},
    },
    # continuous-batching generation snapshot (serving/generation.py),
    # one every emit_every decode steps plus a final one at close;
    # PrometheusTextSink renders the newest as the serving_tokens_per_sec
    # / serving_decode_occupancy gauge family
    "generation": {
        "required": {"slots": int, "active_slots": int,
                     "tokens_total": int, "decode_steps": int,
                     "prefill_requests": int, "slot_joins": int,
                     "slot_leaves": int, "tokens_per_sec": _OPT_NUM,
                     "decode_occupancy": _OPT_NUM},
        "optional": {"queue_depth": int, "max_len": int,
                     "prefill_batches": int, "prefill_s_total": _NUM,
                     "decode_s_total": _NUM,
                     # the host's parts of the decode steps (dispatch +
                     # fetch lie inside decode_s_total, deliver after it)
                     "decode_dispatch_s_total": _NUM,
                     "decode_fetch_s_total": _NUM,
                     "decode_deliver_s_total": _NUM,
                     # the one-step decode pipeline: steps dispatched
                     # before the step before was fetched; slot-steps
                     # computed for a request that had already ended
                     "decode_overlapped_steps": int,
                     "decode_discarded_slot_steps": int,
                     # how deep the decode steps read the cache: the
                     # ladder's rung (nn/kv_cache.py) summed over the
                     # steps dispatched, that over those steps x max_len
                     # (1.0: the whole depth every step), steps by rung
                     "decode_read_depth_total": int,
                     "decode_depth_share": _OPT_NUM,
                     "decode_steps_by_depth": dict,
                     # engine-side token clock over the recent window
                     # (WindowedHistogram.snapshot: quantiles absent
                     # before the first observation)
                     "ttft_ms_p50": _NUM, "ttft_ms_p95": _NUM,
                     "ttft_ms_p99": _NUM, "ttft_ms_count": int,
                     "itl_ms_p50": _NUM, "itl_ms_p95": _NUM,
                     "itl_ms_p99": _NUM, "itl_ms_count": int,
                     # what a model counts on the device in its cache
                     # pytree (`cache_stats`; models/decoder.py): routed
                     # token-expert pairs, the busiest expert's load over
                     # the mean, distinct experts a decode step read,
                     # cache positions the window's ring did not read
                     # (each kind's only where the model has such layers)
                     "moe_pairs_routed": int,
                     "moe_expert_load_max_over_mean": _OPT_NUM,
                     "moe_experts_touched_per_step": _OPT_NUM,
                     "window_positions_skipped": _NUM,
                     # recurrent layers: bytes of the slots' states and
                     # tails, live slot-steps whose states a decode step
                     # replaced, chunks of real tokens prefills scanned,
                     # the largest |S| of a live slot after the last step
                     "recurrent_state_bytes": int,
                     "recurrent_slot_steps": int,
                     "recurrent_chunks_scanned": int,
                     "recurrent_state_absmax": _NUM,
                     # latent layers: bytes of the slots' latents and
                     # rotary keys; over the decode steps, the live
                     # slots' positions + 1 and slots x the depth read
                     "latent_cache_bytes": int,
                     "latent_positions_live": _NUM,
                     "latent_positions_read": _NUM,
                     # grouped-query attention layers: over the decode
                     # steps and those layers, the live slots'
                     # min(position + 1, depth) and the positions read
                     "kv_positions_live": _NUM,
                     "kv_positions_read": _NUM},
    },
    # fleet-level counters/gauges (serving/fleet.py), one per
    # membership change or maintain() tick; PrometheusTextSink renders
    # the newest as the serving_fleet_* gauge family
    "serving_fleet": {
        "required": {"replicas_alive": int, "replicas_total": int,
                     "replicas_draining": int, "reroutes_total": int},
        "optional": {"routed_total": int, "affinity_routes_total": int,
                     "reroute_failed_total": int, "drains_total": int,
                     "scale_ups_total": int, "scale_downs_total": int,
                     "generations_total": int,
                     "stream_reroutes_total": int,
                     "replica_queue_depth": dict},
    },
    # periodic per-objective evaluation (observability/slo.py)
    "slo_status": {
        "required": {"slo": str, "kind": str, "alerting": bool},
        "optional": {"objective": _NUM, "good": int, "bad": int,
                     "compliance": _OPT_NUM, "burn_rate": _OPT_NUM,
                     "error_budget_remaining": _OPT_NUM,
                     "window_s": _NUM, "alerts_fired": int},
    },
    # replay progress heartbeat (workload/replay.py), one every
    # progress_every replayed entries; every field is deterministic under
    # a fixed (workload, seed, target config) so two replays of the same
    # scenario emit IDENTICAL sequences — metrics_cli diff relies on it
    "workload_replay": {
        "required": {"workload": str, "entries_total": int,
                     "entries_done": int, "chaos_fired": int},
        "optional": {"seed": int, "speed": _NUM, "offset_ms": _NUM,
                     "ok": int, "errors": int, "timeouts": int,
                     "shed": int},
    },
    # one per completed replay (workload/replay.py): the outcome tallies
    # + config fingerprint that metrics_cli diff compares across runs.
    # `divergent` is set only when the replayer was handed a baseline
    # stream to compare against; PrometheusTextSink renders it as the
    # workload_replay_divergent gauge
    "replay_summary": {
        "required": {"workload": str, "entries_total": int,
                     "ok": int, "errors": int, "timeouts": int,
                     "shed": int, "chaos_fired": int},
        "optional": {"seed": int, "speed": _NUM, "replicas": int,
                     "workload_sha256": str, "duration_ms": _NUM,
                     "rerouted": int, "cancelled": int,
                     "divergent": bool, "divergence": _OPT_STR},
    },
    # a burn-rate breach transition (observability/slo.py); the flight
    # recorder treats this as a dump trigger
    "alert": {
        "required": {"slo": str, "message": str},
        "optional": {"kind": str, "severity": str,
                     "burn_rate_short": _NUM, "burn_rate_long": _NUM,
                     "short_window_s": _NUM, "long_window_s": _NUM,
                     "factor": _NUM},
    },
}

_SERVING_FIELDS = {
    "required": {"queue_depth": int, "submitted": int, "completed": int,
                 "failed": int, "timed_out": int, "rejected": int,
                 "cancelled": int, "shed": int, "batches": int,
                 "bucket_hits": int, "rows": int, "padded_rows": int,
                 "bucket_hit_rate": _OPT_NUM, "pad_fraction": _OPT_NUM,
                 "queue_wait_ms_count": int, "latency_ms_count": int,
                 "batch_size_count": int},
    "optional": {
        **{f"{pre}_p{q}": _NUM
           for pre in ("queue_wait_ms", "latency_ms", "batch_size")
           for q in (50, 95, 99)},
        "flops_per_step": _OPT_NUM, "bytes_accessed": _OPT_NUM,
        "mfu": _OPT_NUM,
    },
}
RECORD_SCHEMAS["serving_stats"] = _SERVING_FIELDS
RECORD_SCHEMAS["serving_summary"] = _SERVING_FIELDS


def validate_record(record: Dict):
    """Check one telemetry record against `RECORD_SCHEMAS`; raises
    `ValueError` naming the first violation (unknown type, missing/
    mistyped field, undeclared field on a closed record type). Used by the
    contract tests; cheap enough for a validating sink."""
    rtype = record.get("type")
    if rtype not in RECORD_SCHEMAS:
        raise ValueError(f"unknown record type {rtype!r}")
    if not isinstance(record.get("time"), (int, float)):
        raise ValueError(f"{rtype}: missing/mistyped 'time'")
    schema = RECORD_SCHEMAS[rtype]
    fields = {**schema["required"], **schema["optional"]}

    def check(name, types):
        val = record[name]
        ok = isinstance(val, types if isinstance(types, tuple)
                        else (types,))
        # bools are ints in python; don't let True satisfy an int field
        if ok and isinstance(val, bool) and bool not in (
                types if isinstance(types, tuple) else (types,)):
            ok = False
        if not ok:
            raise ValueError(
                f"{rtype}.{name}: {type(val).__name__} not in "
                f"{types}")

    for name, types in schema["required"].items():
        if name not in record:
            raise ValueError(f"{rtype}: missing required field {name!r}")
        check(name, types)
    for name in record:
        if name in ("type", "time") or name.endswith("_nonfinite"):
            continue
        if name in fields:
            check(name, fields[name])
        elif not schema.get("open"):
            raise ValueError(f"{rtype}: undeclared field {name!r}")


class Telemetry:
    """The optimizer-facing collector.

    `Telemetry(sink, ...)` attaches to an optimizer via `set_telemetry`;
    the train loop calls `step(...)` at every sync point and
    `run_start`/`run_end` around the run. Knobs:

    - `grad_norms=True` — have the optimizer compute the global gradient
      and parameter L2 norms INSIDE the jitted step (two tree reductions,
      fused by XLA) and report them per step.
    - `resources=True` — sample host RSS and device memory stats with
      every step record (procfs read + PJRT query, host-side only).
    - `flight` — the always-on crash flight recorder
      (observability/flight.py): every record also lands in a bounded
      ring, auto-dumped to disk on `run_abort` / `fault_injected` /
      NaN-guard `raise`. Pass a configured `FlightRecorder` to control
      capacity/dump dir, or `False` to disable.
    """

    def __init__(self, *sinks: TelemetrySink, grad_norms: bool = False,
                 resources: bool = True, flight=None):
        from bigdl_tpu.observability.flight import FlightRecorder
        self.sink = CompositeSink(*sinks)
        self.grad_norms = grad_norms
        self.resources = resources
        if flight is None:
            flight = FlightRecorder()
        self.flight = flight or None  # False/0 -> disabled

    def add_sink(self, sink: TelemetrySink) -> "Telemetry":
        self.sink.sinks.append(sink)
        return self

    def emit(self, record: Dict):
        if os.environ.get("BIGDL_TPU_STRICT_TELEMETRY") == "1":
            rtype = record.get("type")
            if rtype not in RECORD_SCHEMAS:
                raise ValueError(
                    f"unknown telemetry record type {rtype!r} under "
                    f"BIGDL_TPU_STRICT_TELEMETRY=1 — declare it in "
                    f"RECORD_SCHEMAS (known: {', '.join(sorted(RECORD_SCHEMAS))})")
        # chaos site: a FaultInjector plan can make the sink path flake
        # here, proving observability failures stay non-fatal to the
        # system being observed (the serving engine catches and keeps
        # serving — tests/test_resilience.py)
        faults.fire("telemetry.sink", record_type=record.get("type"))
        record.setdefault("time", time.time())
        if self.flight is not None:
            # ring first: a failing sink must not starve the crash record
            self.flight.emit(record)
        self.sink.emit(record)

    def run_start(self, **fields):
        self.emit({"type": "run_start", **fields})

    def step(self, **fields):
        rec = {"type": "step", **fields}
        if self.resources:
            rss = host_rss_mb()
            if rss is not None:
                rec["host_rss_mb"] = round(rss, 2)
            mem = device_memory_stats()
            if mem:
                rec["device_mem"] = mem
        self.emit(rec)

    def event(self, kind: str, **fields):
        self.emit({"type": "event", "event": kind, **fields})

    def run_end(self, **fields):
        self.emit({"type": "run_end", **fields})

    def close(self):
        self.sink.close()
