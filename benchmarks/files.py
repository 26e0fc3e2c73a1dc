"""Finding the benchmark's pieces by the names BENCHMARK.json gives.

No registry: a cell names a configuration and a traffic mix, a mix names
its driver, a configuration names its model adapter and reference, a
metric names its reader. Each is a file whose name is that name.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_py(kind: str, name: str, base: str = HERE):
    """Import `<base>/<kind>/<name>.py` as a module. Names hold `-` and
    `.`, so the file is loaded by path rather than by import statement."""
    path = os.path.join(base, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} named {name!r}: {path} is missing")
    mod_name = "benchmarks._by_name." + kind + "." + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    """BENCHMARK.json and the files it names, read from `root`.

    `base` is the directory that holds configs/, traffic/, limits/ and
    metrics/ (the benchmark's own, or a test's copy with tiny sizes);
    code (drivers, models, references, readers, counts) always comes
    from this package."""

    def __init__(self, path: Optional[str] = None, base: Optional[str] = None):
        self.path = path or os.path.join(ROOT, "BENCHMARK.json")
        self.base = base or HERE
        self.doc = load_json(self.path)

    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        known = ", ".join(w["name"] for w in self.doc["workloads"])
        raise SystemExit(f"benchmarks: no workload {name!r}; known: {known}")

    def config(self, name: str) -> Dict[str, Any]:
        for c in self.doc["configs"]:
            if c["name"] == name:
                root = os.path.dirname(self.path)
                return load_json(os.path.join(root, c["file"]))
        raise SystemExit(f"benchmarks: no configuration {name!r}")

    def traffic(self, name: str) -> Dict[str, Any]:
        return load_json(os.path.join(self.base, "traffic", name + ".json"))

    def limits(self, cell: str) -> Dict[str, float]:
        return load_json(os.path.join(self.base, "limits", cell + ".json"))

    def metrics(self, kind: str, cell: str, reported: set):
        """The `kind` ("end_to_end" or "per_layer") metrics this cell
        reports: those that list it, and those with no `workloads` key
        whose moved metric (or, end to end, the metric itself) the cell
        has."""
        out = []
        for m in self.doc[kind]:
            cells = m.get("workloads")
            if cells is not None:
                if cell in cells:
                    out.append(m)
            elif kind == "end_to_end" or m["moves"] in reported:
                out.append(m)
        return out

    def metric_file(self, name: str) -> Dict[str, Any]:
        return load_json(os.path.join(HERE, "metrics", name + ".json"))
