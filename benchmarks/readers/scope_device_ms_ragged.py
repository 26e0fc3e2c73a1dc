"""`scope_device_ms`, with XLA's own grouped-product kernels read under
the program scope that calls them.

args: `scope_device_ms`'s, and {"ragged_dot_scope": "<scope>"}.

On a v5e XLA rewrites `lax.ragged_dot` into Mosaic kernels of its own
(`ragged-dot-none.*`, `ragged-dot-metadata.*`) and gives each its own
name as `op_name` in place of the program's path, so no named scope
holds them and `scope_device_ms` reads the routed experts' top-k, sort
and gathers without their products. The program calls `ragged_dot` in
one place, the routed experts' grouped products (`nn/experts.py`), so an
operation whose text starts `ragged-dot` takes the path
`<ragged_dot_scope>/ragged-dot` here, whatever it carried; every other
operation is read as `scope_device_ms` reads it. The runs of one trace
file are kept on the run's context, apart from `scope_device_ms`'s.

None where no run is found or no operation lies under the scopes.
"""

from __future__ import annotations

import re

from benchmarks.files import load_py

_scope = load_py("readers", "scope_device_ms")
RAGGED_DOT = re.compile(r"^%?ragged-dot")


def runs_in_file(path: str, ragged_dot_scope: str):
    """`scope_device_ms.runs_in_file`, each `ragged-dot` operation of the
    first chip under `ragged_dot_scope`."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    data = ProfileData.from_serialized_xspace(raw)
    planes = list(data.planes)
    chips = sorted((int(m.group(1)), p) for m, p in
                   ((_scope.DEVICE_PLANE.match(p.name), p) for p in planes)
                   if m)
    if not chips:
        return []
    first = chips[0][1]
    tf_ops, hlo = _scope.op_paths(raw, first.name)
    named = f"{ragged_dot_scope}/ragged-dot"
    for key in [k for k in tf_ops if RAGGED_DOT.match(k[1])]:
        tf_ops[key] = named
    for line in first.lines:
        if line.name == "XLA Ops":
            for e in line.events:
                if RAGGED_DOT.match(e.name):
                    tf_ops[(None, e.name)] = named
    return _scope.runs_of(planes, tf_ops, hlo)


def read(ctx, out, reduced, args):
    path = ctx.trace_file()
    if path is None:
        return None
    kept = getattr(ctx, "ragged_scope_runs", None)
    if kept is None or kept[0] != path:
        kept = ctx.ragged_scope_runs = (
            path, runs_in_file(path, args["ragged_dot_scope"]))
    return _scope.ms_per_run(kept[1], set(args["modules"]), args["scopes"],
                             args.get("without", ()))
