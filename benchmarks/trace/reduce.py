"""From a profiler trace (`.xplane.pb`) to numbers, with
`jax.profiler.ProfileData` and nothing else.

What a v5e trace holds (looked at by hand, PR 26): one plane per chip,
`/device:TPU:<n>`, with the lines `XLA Modules` (one event per run of a
compiled program, named `jit_<fn>(<hash>)`), `XLA Ops` (one event per
HLO operation, named by its full HLO text, nested where an operation
holds others), `Async XLA Ops` (copies and collectives in flight) and
`Steps`; and one host plane, `/host:CPU`, with a line per thread, on
which `jax.profiler.TraceAnnotation`s (the program's `SpanTracer` spans)
appear by name. All start times are nanoseconds on one clock.

A Mosaic (Pallas) kernel is an `XLA Ops` event whose text is a
`custom-call(` that carries `kernel_metadata`; the program gives its
kernels no name of their own yet, so metrics tell them apart by the
shapes in that text.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SHORT_GAP_NS = 2000.0
SHORT_GAPS = "_between_operations__under_2_us_each_"
NO_SPAN = "_host_in_no_span_"

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "f32": 4, "s32": 4,
                "u32": 4, "f64": 8, "s64": 8, "u64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([\d,]*)\]")


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that no interval of merged `busy` covers."""
    out, at = [], lo
    for a, b in busy:
        if b <= lo:
            continue
        if a >= hi:
            break
        if a > at:
            out.append((at, min(a, hi)))
        at = max(at, b)
    if at < hi:
        out.append((at, hi))
    return out


def shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """Every array shape in an HLO operation's text, in order: results
    first, then operands."""
    return [(d, tuple(int(x) for x in dims.split(",") if x))
            for d, dims in _SHAPE.findall(text)]


def shape_bytes(shape: Tuple[str, Tuple[int, ...]]) -> int:
    n = 1
    for d in shape[1]:
        n *= d
    return n * _DTYPE_BYTES[shape[0]]


def op_name(text: str) -> str:
    """`%fusion.12 = ...` -> `fusion.12`, plus its result's type and
    shape so that a reader can tell equal names of two programs apart."""
    head = text.split(" = ", 1)
    name = head[0].lstrip("%")
    found = _SHAPE.search(head[1]) if len(head) > 1 else None
    if found:
        name += "_" + found.group(1) + "_" + found.group(2).replace(",", "_")
    return name


def is_kernel(text: str) -> bool:
    return "custom-call(" in text and "kernel_metadata" in text


def _attribute(idle: Sequence[Interval],
               spans: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Idle seconds by the innermost host span that covers them."""
    out: Dict[str, float] = {}
    spans = sorted(spans)
    starts = [s[0] for s in spans]
    import bisect
    for a, b in idle:
        if b - a < SHORT_GAP_NS:
            out[SHORT_GAPS] = out.get(SHORT_GAPS, 0.0) + (b - a)
            continue
        hi = bisect.bisect_left(starts, b)
        cover = [s for s in spans[max(0, hi - 64):hi] if s[1] > a]
        cuts = sorted({a, b, *(min(max(x, a), b) for s in cover
                               for x in s[:2])})
        for lo, up in zip(cuts, cuts[1:]):
            mid = (lo + up) / 2
            inner = [s for s in cover if s[0] <= mid < s[1]]
            name = max(inner)[2] if inner else NO_SPAN
            out[name] = out.get(name, 0.0) + (up - lo)
    return {k: v * 1e-9 for k, v in out.items()}


def reduce_planes(planes, n_devices: int = 1,
                  span_names: Optional[Iterable[str]] = None) -> Dict[str, Any]:
    """Reduce the planes of one trace.

    `span_names` are the host annotations to attribute idle gaps to
    (None: every host event whose name holds a space or starts with a
    lower-case letter and no `::`, which is how the program names its
    spans and the runtime does not)."""
    devices: Dict[int, Dict[str, list]] = {}
    host_events: List[Tuple[float, float, str]] = []
    lo_all, hi_all = float("inf"), float("-inf")
    wanted = set(span_names) if span_names is not None else None
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), {})
            for line in plane.lines:
                dev[line.name] = [(e.start_ns, e.start_ns + e.duration_ns,
                                   e.name) for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    name = e.name.split("#", 1)[0]
                    if wanted is not None:
                        keep = name in wanted
                    else:
                        keep = "::" not in name and not name.startswith("$") \
                            and (" " in name.strip()) and name[:1].islower()
                    if keep:
                        host_events.append((e.start_ns,
                                            e.start_ns + e.duration_ns, name))
        else:
            continue
        for line in plane.lines:
            for e in line.events:
                lo_all = min(lo_all, e.start_ns)
                hi_all = max(hi_all, e.start_ns + e.duration_ns)
    if not devices:
        raise ValueError("the trace holds no /device:TPU:<n> plane")
    ids = sorted(devices)[:n_devices]
    busy_s = 0.0
    per_device = {}
    for i in ids:
        ops = devices[i].get("XLA Ops", [])
        busy = merge((a, b) for a, b, _ in ops)
        per_device[i] = busy
        busy_s += total(busy) * 1e-9
    busy_s /= len(ids)
    if busy_s <= 0:
        raise ValueError("no operation ran on the device in the traced window")

    first = devices[ids[0]]
    ops: Dict[str, List[float]] = {}
    kernels: List[Tuple[str, float]] = []
    # nested operations (a while loop and its body) would count twice:
    # sum only the events that hold no other
    evs = sorted(first.get("XLA Ops", []), key=lambda e: (e[0], -e[1]))
    for k, (a, b, text) in enumerate(evs):
        if k + 1 < len(evs) and a <= evs[k + 1][0] and evs[k + 1][1] <= b \
                and evs[k + 1][1] - evs[k + 1][0] < b - a:
            continue
        rec = ops.setdefault(text, [0, 0.0])
        rec[0] += 1
        rec[1] += (b - a) * 1e-9
        if is_kernel(text):
            kernels.append((text, (b - a) * 1e-9))
    modules: Dict[str, List[float]] = {}
    for a, b, name in first.get("XLA Modules", []):
        rec = modules.setdefault(name.split("(", 1)[0], [0, 0.0])
        rec[0] += 1
        rec[1] += (b - a) * 1e-9
    spans: Dict[str, List[float]] = {}
    for a, b, name in host_events:
        spans.setdefault(name, []).append((b - a) * 1e-9)

    idle = gaps(per_device[ids[0]], lo_all, hi_all)
    by_span = _attribute(idle, host_events)
    top_ops = sorted(((op_name(t), s) for t, (_, s) in ops.items()),
                     key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi_all - lo_all) * 1e-9, "busy_s": busy_s,
        "ops": ops, "kernels": kernels, "modules": modules, "spans": spans,
        "breakdown": {"device_ops": [[n, s] for n, s in top_ops],
                      "idle_gaps": [[re.sub(r"[^A-Za-z0-9_.-]", "_", n), s]
                                    for n, s in top_gaps]},
    }


def reduce_file(path: str, n_devices: int = 1,
                span_names: Optional[Iterable[str]] = None) -> Dict[str, Any]:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, n_devices,
                         span_names)
