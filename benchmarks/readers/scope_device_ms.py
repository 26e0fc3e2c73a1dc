"""Device milliseconds that one run of a compiled program spends under
some of the program's named scopes, read from the trace's operations.

args: {"modules": [...], "scopes": [...], "without": [...]}.

* Runs: the first chip's `XLA Modules` events whose name, up to its
  `(<program id>)`, is one of `modules`, and that lie wholly inside the
  traced slice: some event of that chip's `XLA Modules` or `XLA Ops`
  lines ends before the run starts, and some starts after it ends (a run
  cut by the slice's edge has lost operations).
* Operations: the leaf `XLA Ops` events inside those runs, by the rule of
  `trace/reduce.py` (an event that holds the next, longer than it, is a
  while loop or a conditional, not counted with its body).
* Scope path: an operation's `tf_op` stat, the framework name path (the
  HLO instruction's `metadata.op_name`, `jit(f)/full attention/kv
  write/...`, then `:` and a type). A v5e trace carries it on the event's
  metadata, which `ProfileData` does not show: it is read from the file's
  `XPlane.event_metadata` here, by program id and operation text. Where
  no operation carries one, the `metadata.op_name` of the instruction in
  the program's HLO proto, which the `/host:metadata` plane holds. An
  operation with no path takes that of the innermost operation that
  holds it: the compiler expands a per-row cache write into a while loop
  whose body it leaves unnamed, and the loop keeps the scope. What is
  left with no path and waits for a copy or a slice the compiler started
  into fast memory (`copy-done`, `slice-done`) reads as `prefetch wait`:
  no program scope can name it. A fusion carries the path of the
  operation it is built around (a product or a convolution where it
  holds one, so a weight's update fused into its gradient's product
  reads as the product's layer), else of its root; a backward operation
  carries its forward one's inside `transpose(jvp(...))`.
* Sum: the operations with a path component equal to one of `scopes`,
  also wrapped in `jvp(...)`, `transpose(...)` or `vmap(...)`, and with
  none equal to one of `without`, over the number of runs.

None where no run is found, or no operation lies under the scopes (a
program that does not have them). The reduction of a trace file is kept
on the run's context, by path: the metrics of one run read one file.

Run as a script, `python3 benchmarks/readers/scope_device_ms.py TRACE
MODULE...` prints each program's device milliseconds a run by the chain
of documented scopes its operations lie under.
"""

from __future__ import annotations

import bisect
import re
import sys
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_WRAPPED = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
_PROGRAM = re.compile(r"^(.*?)\((\d+)\)$")
_PREFETCH = re.compile(r"^%?(?:copy|slice)-(?:start|done)\b")
PREFETCH_WAIT = ("prefetch wait",)

#: the program's named scopes (PERF.md section 3), outermost first where
#: they nest
VOCABULARY = (
    "embed", "head", "norm", "counters",
    "full attention", "window attention", "latent attention",
    "linear attention", "kv write", "kv commit",
    "mla project", "mla expand", "mla absorb", "mla attend",
    "gdn conv", "gdn chunk scan", "gdn step",
    "moe route", "moe experts", "moe gate up", "moe down",
    "shared expert", "dense ffn",
    "stem", "stage 1", "stage 2", "stage 3", "stage 4", "classifier",
    "loss", "optimizer update", "step guards",
    PREFETCH_WAIT[0])


def components(path: str) -> Tuple[str, ...]:
    """`jit(step)/transpose(jvp(stage 1))/conv:` -> ("jit(step)",
    "stage 1", "conv"): the type after the last `:` dropped, each
    component unwrapped of the transforms that keep its scope."""
    head, colon, tail = path.rpartition(":")
    if colon and "/" not in tail:
        path = head
    out = []
    for c in path.split("/"):
        m = _WRAPPED.match(c)
        while m:
            c = m.group(1)
            m = _WRAPPED.match(c)
        out.append(c)
    return tuple(out)


# --------------------------------------------------- the file's metadata
def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i=0, end=None):
    """(field, value) of one protobuf message in buf[i:end]; a
    length-delimited value as a memoryview, a varint as an int."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif kind == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif kind == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {kind} is not read here")
        yield key >> 3, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf):
    """XStat -> (metadata id, value): a string, bytes (a memoryview), a
    number, or ("ref", id) of the stat metadata whose name is the
    string."""
    sid, value = 0, None
    for f, v in _fields(buf):
        if f == 1:
            sid = v
        elif f == 5:
            value = _text(v)
        elif f == 7:
            value = ("ref", v)
        elif f in (2, 3, 4, 6):
            value = v
    return sid, value


def _plane_metadata(plane):
    """XPlane -> (name, {metadata id: (name, [(stat id, value)])},
    {stat id: stat name}); its lines are skipped, not read."""
    name, events, stat_names = "", {}, {}
    for f, v in _fields(plane):
        if f == 2:
            name = _text(v)
        elif f in (4, 5):
            value = None
            for g, w in _fields(v):
                if g == 2:
                    value = w
            if value is None:
                continue
            mid, mname, stats = 0, "", []
            for g, w in _fields(value):
                if g == 1:
                    mid = w
                elif g == 2:
                    mname = _text(w)
                elif g == 5 and f == 4:
                    stats.append(_stat(w))
            if f == 4:
                events[mid] = (mname, stats)
            else:
                stat_names[mid] = mname
    return name, events, stat_names


def _hlo_op_names(proto) -> Dict[str, str]:
    """HloProto -> {instruction name: metadata.op_name}."""
    out = {}
    for f, module in _fields(proto):
        if f != 1:
            continue
        for g, comp in _fields(module):
            if g != 3:
                continue
            for h, inst in _fields(comp):
                if h != 2:
                    continue
                iname, op = "", ""
                for k, w in _fields(inst):
                    if k == 1:
                        iname = _text(w)
                    elif k == 7:
                        for m, x in _fields(w):
                            if m == 2:
                                op = _text(x)
                if op:
                    out[iname] = op
    return out


def op_paths(raw: bytes, device: str):
    """The scope path of each operation of plane `device`, from a
    serialized XSpace: ({(program id, operation text): tf_op},
    {program id: {instruction name: op_name}} from the HLO protos, read
    only where no operation carries a `tf_op`)."""
    buf = memoryview(raw)
    tf_ops: Dict[Tuple[int, str], str] = {}
    protos = []
    for f, plane in _fields(buf):
        if f != 1:
            continue
        name, events, stat_names = _plane_metadata(plane)
        if name == device:
            for text, stats in events.values():
                named = {}
                for sid, value in stats:
                    if isinstance(value, tuple):
                        value = stat_names.get(value[1], "")
                    named[stat_names.get(sid)] = value
                path, pid = named.get("tf_op"), named.get("program_id")
                if isinstance(path, str):
                    tf_ops[(pid if isinstance(pid, int) else None,
                            text)] = path
        elif name == "/host:metadata":
            for mid, (_, stats) in events.items():
                for sid, value in stats:
                    if stat_names.get(sid) == "Hlo Proto":
                        protos.append((mid, value))
    hlo = {} if tf_ops else {pid: _hlo_op_names(proto)
                             for pid, proto in protos}
    return tf_ops, hlo


# ------------------------------------------------------------- the runs
def _instruction(text: str) -> str:
    """`%fusion.12 = ...` -> `fusion.12`."""
    return text.split(" = ", 1)[0].lstrip("%")


def runs_of(planes, tf_ops=None, hlo=None):
    """[(module name, [(seconds, path components)] of its leaf
    operations)] of the first chip's whole runs."""
    tf_ops, hlo = tf_ops or {}, hlo or {}
    chips = {}
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chips[int(m.group(1))] = plane
    if not chips:
        return []
    lines = {line.name: [(e.start_ns, e.start_ns + e.duration_ns, e)
                         for e in line.events]
             for line in chips[min(chips)].lines}
    mods = sorted((a, b, e.name) for a, b, e in lines.get("XLA Modules", []))
    ops = sorted(lines.get("XLA Ops", []), key=lambda x: (x[0], -x[1]))
    every = mods + [(a, b, None) for a, b, _ in ops]
    if not mods or not ops:
        return []
    first_end = min(b for _, b, _ in every)
    last_start = max(a for a, _, _ in every)
    runs = []
    for a, b, name in mods:
        m = _PROGRAM.match(name)
        base, pid = (m.group(1), int(m.group(2))) if m else (name, None)
        whole = first_end <= a and b <= last_start
        runs.append((a, b, base, pid, whole, []))
    starts = [r[0] for r in runs]
    memo: Dict[str, tuple] = {}

    def path(e, pid):
        p = tf_ops.get((pid, e.name)) or tf_ops.get((None, e.name))
        if p is None:
            for k, v in e.stats:
                if k == "tf_op":
                    p = v
                    break
        if p is None and pid in hlo:
            p = hlo[pid].get(_instruction(e.name))
        if not p:
            return None
        if p not in memo:
            memo[p] = components(p)
        return memo[p]

    held: List[Tuple[float, Optional[tuple]]] = []  # (end, path) around
    for k, (a, b, e) in enumerate(ops):
        r = bisect.bisect_right(starts, a) - 1
        run = runs[r] if r >= 0 and a <= runs[r][1] else None
        while held and held[-1][0] < b:
            held.pop()
        own = path(e, run[3] if run else None)
        if own is None and held:
            own = held[-1][1]
        if own is None and _PREFETCH.match(e.name):
            own = PREFETCH_WAIT
        held.append((b, own))
        if run is None or not run[4] or b > run[1]:
            continue
        if k + 1 < len(ops) and a <= ops[k + 1][0] and ops[k + 1][1] <= b \
                and ops[k + 1][1] - ops[k + 1][0] < b - a:
            continue  # holds the next: a loop or a conditional
        run[5].append(((b - a) * 1e-9, own))
    return [(base, leaves) for _, _, base, _, whole, leaves in runs
            if whole]


def runs_in_file(path: str):
    """`runs_of` the trace file at `path`."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    # `data` is held until the planes' events have been read
    data = ProfileData.from_serialized_xspace(raw)
    planes = list(data.planes)
    devices = sorted(int(m.group(1)) for m in
                     (DEVICE_PLANE.match(p.name) for p in planes) if m)
    if not devices:
        return []
    tf_ops, hlo = op_paths(raw, f"/device:TPU:{devices[0]}")
    return runs_of(planes, tf_ops, hlo)


def ms_per_run(runs, modules, scopes, without=()) -> Optional[float]:
    chosen = [leaves for base, leaves in runs if base in modules]
    if not chosen:
        return None
    scopes, without = set(scopes), set(without)
    total, found = 0.0, False
    for leaves in chosen:
        for seconds, comps in leaves:
            if comps and scopes.intersection(comps) \
                    and not without.intersection(comps):
                total += seconds
                found = True
    return 1e3 * total / len(chosen) if found else None


def read(ctx, out, reduced, args):
    path = ctx.trace_file()
    if path is None:
        return None
    kept = getattr(ctx, "scope_runs", None)
    if kept is None or kept[0] != path:
        kept = ctx.scope_runs = (path, runs_in_file(path))
    return ms_per_run(kept[1], set(args["modules"]), args["scopes"],
                      args.get("without", ()))


def split(runs, module) -> Dict[str, float]:
    """Device ms a run of `module` by the chain of documented scopes
    (`VOCABULARY`) its operations lie under, "" for none."""
    chosen = [leaves for base, leaves in runs if base == module]
    out: Dict[str, float] = {}
    for leaves in chosen:
        for seconds, comps in leaves:
            key = " > ".join(c for c in comps or () if c in VOCABULARY)
            out[key] = out.get(key, 0.0) + 1e3 * seconds / len(chosen)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


if __name__ == "__main__":
    runs = runs_in_file(sys.argv[1])
    for module in sys.argv[2:]:
        n = sum(base == module for base, _ in runs)
        print(f"{module}: {n} whole runs")
        for key, ms in split(runs, module).items():
            print(f"  {ms:10.4f} ms  {key or '(no documented scope)'}")
