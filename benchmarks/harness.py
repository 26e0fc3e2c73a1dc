"""One run of one cell: find the pieces by name, look for the chip, let
the mix's driver set up and measure, read memory, compare with the plain
reference, reduce the trace, print the result line.

`run_cell` is what `python3 -m benchmarks.run` calls. Tests call it with
`require_chip=False` and a manifest of tiny sizes to drive everything
but the look for a chip.
"""

from __future__ import annotations

import glob
import os
import sys
import time
from typing import Any, Dict, Optional

from benchmarks import compare
from benchmarks.files import HERE, Manifest, load_json, load_py

#: events JAX's monitoring emits once for every program it lowers or
#: compiles; none may fire inside a measured window
_LOWERING_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                    "/jax/core/compile/backend_compile_duration")


class NoChip(SystemExit):
    """No accelerator the peaks table knows, or fewer chips than the
    cell asks for: exit non-zero, print no result."""


class Context:
    """What a driver, a reader and a check are handed."""

    def __init__(self, manifest: Manifest, cell: Dict[str, Any], seed: int,
                 seconds: float, trace: bool, devices, peaks, t_start: float,
                 trace_dir: str):
        self.manifest, self.cell = manifest, cell
        self.cfg = manifest.config(cell["config"])
        self.mix = manifest.traffic(cell["traffic"])
        self.limits = manifest.limits(cell["name"])
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.chips = int(cell["chips"])
        self.devices, self.peaks = devices, peaks
        self.t_start = t_start
        self.trace_dir = trace_dir
        self.reference = load_py("reference", self.cfg["reference"])
        self.adapter = load_py("models", self.cfg["model"]).Adapter(
            self.cfg, self.mix)
        self._lowerings = 0
        self._tracing = None  # None: not started, True: on, False: done
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, *_a, **_k):
        if name in _LOWERING_EVENTS:
            self._lowerings += 1

    def lowerings(self) -> int:
        return self._lowerings

    def trace_from(self, elapsed_s: float, start_s: float):
        """Call with the seconds since the window opened: turns the
        profiler on, once, when `start_s` has passed."""
        if self._tracing is None and elapsed_s >= start_s:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the program's spans, not frames
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._tracing = True

    def trace_stop(self):
        if self._tracing is True:
            import jax
            jax.profiler.stop_trace()
            self._tracing = False

    def trace_file(self) -> Optional[str]:
        found = sorted(glob.glob(os.path.join(
            self.trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


def find_devices(chips: int, peaks_doc: Dict[str, Any], require_chip: bool):
    import jax
    devs = jax.devices()
    kind = devs[0].device_kind
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"benchmarks: no TPU: JAX found platform "
                         f"{devs[0].platform!r} ({kind!r})")
        if kind not in peaks_doc["devices"]:
            raise NoChip(f"benchmarks: peaks.json does not know {kind!r}")
        if len(devs) < chips:
            raise NoChip(f"benchmarks: the cell asks for {chips} chips, "
                         f"JAX found {len(devs)}")
    peaks = peaks_doc["devices"].get(kind)
    return devs[:chips], peaks


def memory_peak_bytes(devices) -> int:
    """Peak on the fullest chip: what the allocator had in use plus what
    loaded programs reserved for their temporaries (the v5e reports them
    apart; a training step's gigabytes are in the second)."""
    peak = 0
    for d in devices:
        s = d.memory_stats() or {}
        peak = max(peak, int(s.get("peak_bytes_in_use", 0))
                   + int(s.get("peak_bytes_reserved", 0)))
    return peak


def _say(device: Dict[str, Any], msg: str):
    print(f"[{device['kind']} x{device['count']}] {msg}", file=sys.stderr,
          flush=True)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             manifest: Optional[Manifest] = None, require_chip: bool = True,
             t_start: Optional[float] = None,
             scratch: Optional[str] = None,
             keep: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Run one cell once; returns the result line as a dict. `keep`, if
    given, receives the context, the driver's output and the rows
    compared (benchmarks/readings.py reads limits' readings from them)."""
    t_start = time.perf_counter() if t_start is None else t_start
    manifest = manifest or Manifest()
    cell = manifest.cell(workload)
    peaks_doc = load_json(os.path.join(HERE, "peaks.json"))
    devices, peaks = find_devices(int(cell["chips"]), peaks_doc, require_chip)
    t_chip = time.perf_counter()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if require_chip:
        from bigdl_tpu.utils import compile_cache
        _say(device, f"compile cache: {compile_cache.configure()}")
    scratch = scratch or os.path.join(
        os.environ.get("TMPDIR") or os.path.join(HERE, ".cache"),
        "bigdl_tpu_bench")
    trace_dir = os.path.join(scratch, f"trace-{os.getpid()}")
    ctx = Context(manifest, cell, seed, seconds, trace, devices, peaks,
                  t_start, trace_dir)
    ctx.t_chip = t_chip  # set-up's phases are read from here on
    driver = load_py("drivers", ctx.mix["driver"])
    _say(device, f"{workload} seed={seed} seconds={seconds} trace={int(trace)}")

    out = driver.run(ctx)
    setup_s = out["t_window"] - t_start
    device["memory_peak_bytes"] = memory_peak_bytes(devices)
    _say(device, f"set-up {setup_s:.1f} s, window {out['window_s']:.1f} s: "
         + out["note"])

    t_check = time.perf_counter()
    rows = compare.judge(driver.check(ctx, out), ctx.limits)
    _say(device, f"reference and comparison "
         f"{time.perf_counter() - t_check:.1f} s")
    correct = all(r["ok"] for r in rows) and out["failed"] == 0
    if keep is not None:
        keep.update(ctx=ctx, out=out, rows=rows)

    e2e = dict(out["end_to_end"], setup_s=setup_s)
    reported = set(e2e)
    metrics: Dict[str, Any] = {}
    breakdown = None
    if trace:
        from benchmarks.trace import reduce as trace_reduce
        path = ctx.trace_file()
        if path is None:
            raise RuntimeError("the traced run wrote no .xplane.pb")
        reduced = trace_reduce.reduce_file(path, n_devices=len(devices))
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = reduced["breakdown"]
        for m in manifest.metrics("per_layer", workload, reported):
            spec = manifest.metric_file(m["name"])
            reader = load_py("readers", spec["reader"])
            value = reader.read(ctx, out, reduced, spec.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        for m in manifest.metrics("end_to_end", workload, reported):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    for r in rows:
        _say(device, "compared {name}: {value:.6g} (limit {limit}) {v}{at}"
             .format(v="ok" if r["ok"] else "NOT CORRECT",
                     at=f" worst leaf {r['leaf']}" if r.get("leaf") else "",
                     **r))
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if trace:
        result["end_to_end_seen"] = e2e
    result["compared"] = {r["name"]: {"value": r["value"],
                                      "limit": r["limit"]} for r in rows}
    return result
