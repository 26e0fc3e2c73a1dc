"""Cost accounting: per-executable FLOPs, bytes accessed, and MFU.

The bench driver computed MFU offline (bench_cli lowers the step a second
time and divides by a hand-kept peak table); the telemetry stream itself
had no notion of FLOPs, so nobody could read model efficiency off a run's
records. This module makes cost a first-class telemetry input:

- `executable_costs(compiled)` reads XLA's own cost model off a
  `jax.stages.Compiled` (`flops`, `bytes accessed`).
- `jaxpr_flops(jaxpr)` is the fallback estimator for an executable whose
  analysis carries no usable count: a jaxpr walk counting matmul/conv
  FLOPs exactly and elementwise ops as one FLOP per output element,
  recursing through pjit/scan/while sub-jaxprs (scan bodies scale by
  trip count).
- `PEAK_BF16_FLOPS` / `peak_flops(device_kind)` is the small peak-FLOPs
  chip registry (dense bf16 per chip), matched on the exact
  `device_kind`. Unknown kinds — CPU included — return None, and every
  derived MFU is then None (null in JSONL), never a made-up number.
- `mfu(flops, step_time_s, ...)` folds the three together:
  achieved FLOP/s over the mesh peak.

Example:
    >>> from bigdl_tpu.observability.costs import peak_flops, mfu
    >>> peak_flops("TPU v5e")
    197000000000000.0
    >>> peak_flops("cpu") is None
    True
    >>> mfu(197e12, step_time_s=2.0, device_kind="TPU v5e")
    0.5
"""

from __future__ import annotations

import math
from typing import Dict, Optional

#: Dense bf16 peak FLOP/s per chip, keyed by the lower-cased
#: `device_kind` string JAX reports (both spellings where JAX has two).
#: Exact match only: a kind that is not here — a new chip, the CPU —
#: yields None, which the telemetry stream reports as a null MFU and the
#: chip entry points (chip_smoke.py, bench.py) refuse to run on.
PEAK_BF16_FLOPS = {
    "tpu v2": 45e12, "tpu v3": 123e12, "tpu v4": 275e12,
    "tpu v5 lite": 197e12, "tpu v5e": 197e12,
    "tpu v5": 459e12, "tpu v5p": 459e12,
    "tpu v6 lite": 918e12, "tpu v6e": 918e12,
}


def peak_flops(device_kind) -> Optional[float]:
    """Peak dense bf16 FLOP/s for a chip, from the registry; None for
    unknown kinds (CPU, new chips not yet registered). Accepts a kind
    string or a jax device object."""
    kind = device_kind if isinstance(device_kind, str) \
        else getattr(device_kind, "device_kind", "")
    return PEAK_BF16_FLOPS.get(kind.lower())


def default_device_kind() -> str:
    """The local backend's device kind (`jax.devices()[0].device_kind`),
    cached after the first call — the registry lookup runs per sync point."""
    global _DEVICE_KIND
    if _DEVICE_KIND is None:
        try:
            import jax
            _DEVICE_KIND = getattr(jax.devices()[0], "device_kind", "")
        except Exception:
            _DEVICE_KIND = ""
    return _DEVICE_KIND


_DEVICE_KIND: Optional[str] = None


def executable_costs(compiled) -> Dict[str, Optional[float]]:
    """`{"flops": ..., "bytes_accessed": ...}` from a
    `jax.stages.Compiled`'s `cost_analysis()` dict. A count the backend
    left out, or reported as zero or non-finite, is None; callers fall
    back to `jaxpr_flops`."""
    cost = compiled.cost_analysis()

    def positive(key):
        v = cost.get(key)
        return float(v) if v is not None and math.isfinite(v) and v > 0 \
            else None
    return {"flops": positive("flops"),
            "bytes_accessed": positive("bytes accessed")}


def _prod(xs) -> float:
    p = 1.0
    for x in xs:
        p *= x
    return p


def _dot_general_flops(eqn) -> float:
    """2*B*M*N*K for a dot_general: batch dims B, contracting dims K,
    remaining lhs dims M, remaining rhs dims N."""
    lhs, rhs = (v.aval.shape for v in eqn.invars[:2])
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    k = _prod(lhs[d] for d in lc)
    b = _prod(lhs[d] for d in lb)
    m = _prod(s for d, s in enumerate(lhs) if d not in set(lc) | set(lb))
    n = _prod(s for d, s in enumerate(rhs) if d not in set(rc) | set(rb))
    return 2.0 * b * m * n * k


def _conv_flops(eqn) -> float:
    """2 * output elements * kernel spatial size * in-channels /
    feature_group_count for conv_general_dilated."""
    rhs = eqn.invars[1].aval.shape
    out = eqn.outvars[0].aval.shape
    dn = eqn.params["dimension_numbers"]
    groups = eqn.params.get("feature_group_count", 1) or 1
    k_spatial = _prod(rhs[d] for d in dn.rhs_spec[2:])
    in_ch = rhs[dn.rhs_spec[1]]
    return 2.0 * _prod(out) * k_spatial * in_ch / groups


#: Memory-movement primitives counted as zero FLOPs in the fallback walk:
#: `get`/`swap` are Pallas/state ref loads/stores (they dominate a kernel
#: body's eqn list but do no arithmetic), `copy` is a device copy.
_MEMORY_PRIMITIVES = frozenset({"get", "swap", "copy"})


def _pallas_grid_size(eqn) -> float:
    """Number of grid cells a pallas_call's kernel body runs for (1 for
    a gridless call)."""
    gm = eqn.params.get("grid_mapping")
    grid = tuple(getattr(gm, "grid", ()) or ())
    # symbolic/dynamic grid axes fall back to 1 — a floor, never a crash
    return _prod(d for d in grid if isinstance(d, int)) or 1.0


def jaxpr_flops(jaxpr) -> float:
    """Estimated FLOPs of a (closed) jaxpr: exact matmul/conv counts plus
    one FLOP per output element for everything else, recursing through
    call/pjit/custom-derivative sub-jaxprs and scaling scan bodies by
    their trip count. A floor estimate — used only when the backend's
    own cost model reports nothing.

    Fused-kernel attribution: a `pallas_call` body counts once per GRID
    CELL (the body jaxpr sees one block; the walk used to count it once,
    under-reporting fused steps by the grid factor), with ref
    loads/stores (`get`/`swap`) excluded as memory movement — so a fused
    BN+ReLU / stem / flash step attributes ~the unfused equivalent's
    count (regression-pinned in tests/test_attribution.py).
    `custom_vjp_call*` descends through `fun_jaxpr`/`call_jaxpr` like the
    other call primitives."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    total = 0.0
    for eqn in inner.eqns:
        name = eqn.primitive.name
        try:
            if name == "dot_general":
                total += _dot_general_flops(eqn)
                continue
            if name == "conv_general_dilated":
                total += _conv_flops(eqn)
                continue
        except Exception:
            pass  # malformed params: fall through to the generic count
        if name == "pallas_call":
            try:
                total += jaxpr_flops(eqn.params["jaxpr"]) \
                    * _pallas_grid_size(eqn)
                continue
            except Exception:
                pass  # unexpected params shape: generic count below
        if name in _MEMORY_PRIMITIVES:
            continue
        sub = None
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            if key in eqn.params:
                sub = eqn.params[key]
                break
        if sub is not None:
            body = jaxpr_flops(sub)
            if name == "scan":
                body *= eqn.params.get("length", 1) or 1
            total += body
            continue
        if name == "while":
            # trip count is data-dependent: count one body iteration
            total += jaxpr_flops(eqn.params["body_jaxpr"])
            continue
        for out in eqn.outvars:
            shape = getattr(getattr(out, "aval", None), "shape", None)
            if shape is not None:
                total += _prod(shape)
    return total


def jaxpr_eqn_count(jaxpr) -> int:
    """Number of top-level equations in a (closed) jaxpr — the compile
    record's coarse "how big is this program" figure."""
    return len(getattr(jaxpr, "jaxpr", jaxpr).eqns)


def mfu(flops: Optional[float], step_time_s: Optional[float],
        device_kind: Optional[str] = None,
        n_devices: int = 1) -> Optional[float]:
    """Model FLOPs utilization: `flops / step_time_s` (achieved FLOP/s of
    the whole program — for an SPMD step that is already the global-batch
    count) over `n_devices * peak_flops(device_kind)`. None whenever any
    input is missing/non-finite or the chip is not in the registry —
    an unknown chip yields a null MFU, never a fabricated one."""
    if flops is None or step_time_s is None:
        return None
    if not (math.isfinite(flops) and math.isfinite(step_time_s)) \
            or flops <= 0 or step_time_s <= 0:
        return None
    peak = peak_flops(device_kind if device_kind is not None
                      else default_device_kind())
    if not peak:
        return None
    return flops / step_time_s / (peak * max(1, int(n_devices)))
