"""Compile telemetry: an AOT lowering/compile wrapper around `jax.jit`.

Recompile storms and warmup cost are invisible in a plain jitted loop —
the first call with a new input signature silently pays trace + lower +
XLA compile, and nothing in the telemetry stream says so. `CompiledFunction`
wraps a jitted callable and makes every compilation an explicit, observable
event:

- each call computes a cheap input *signature* (shape/dtype of the
  designated `sig_argnums` — e.g. just the batch arrays of a train step,
  so the per-call cost is a couple of tuples, not a walk of the parameter
  tree);
- a new signature goes through the staged AOT path
  (`jit.trace -> .lower() -> .compile()`), timing the lowering and the
  backend compile separately, reading FLOPs / bytes-accessed off the
  compiled executable's cost analysis (`observability.costs`, jaxpr-walk
  fallback), and emitting ONE `compile` telemetry record:
  `{type: "compile", label, signature, lower_s, compile_s, jaxpr_eqns,
  cache_hit, flops, bytes_accessed}`;
- subsequent calls with a known signature dispatch straight to the cached
  executable — zero events, near-zero overhead;
- a `(label, signature, eqn-count)` triple that some earlier wrapper in
  this process already compiled reports `cache_hit: true` (re-running the
  same shapes is cheap thanks to jax/XLA caching, and the stream says so).

Durations use `time.monotonic()` — an NTP step cannot produce a negative
`compile_s`.

A lowering or compile error (a Mosaic refusal, an out-of-memory plan)
propagates to the caller once, as itself: compiling the same program a
second time through plain `jit` would only fail again, later and under
another name. Only a failed AOT *dispatch* (aval drift in a non-signature
argument) flips the instance onto the plain jitted call.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

from bigdl_tpu.observability import costs

logger = logging.getLogger("bigdl_tpu.observability")

#: Process-level ledger of (label, signature, eqn_count) triples already
#: compiled by SOME CompiledFunction — a later wrapper hitting the same
#: triple reports its compile record with `cache_hit: true`.
_COMPILED_BEFORE: set = set()
_COMPILED_BEFORE_LOCK = threading.Lock()


def _leaf_sig(leaf) -> Tuple:
    shape = getattr(leaf, "shape", None)
    if shape is not None:
        return (tuple(shape), str(getattr(leaf, "dtype", type(leaf).__name__)))
    return ("py", type(leaf).__name__)


def arg_signature(args) -> Tuple:
    """Hashable shape/dtype signature of a tuple of pytree arguments."""
    import jax
    return tuple(
        tuple(_leaf_sig(l) for l in jax.tree_util.tree_leaves(a))
        for a in args)


def signature_str(sig: Tuple) -> str:
    """Compact human/JSON form of an `arg_signature`, e.g.
    `"32x28x28:float32|32:int32"`."""
    parts = []
    for arg in sig:
        for leaf in arg:
            if leaf[0] == "py":
                parts.append(f"py:{leaf[1]}")
            else:
                shape, dtype = leaf
                parts.append("x".join(map(str, shape)) + f":{dtype}"
                             if shape else f"scalar:{dtype}")
    return "|".join(parts)


class CompiledFunction:
    """Wrap a function (or an existing `jax.jit` object) with per-signature
    AOT compilation, compile telemetry, and cost bookkeeping.

    Parameters
    ----------
    fn : the python callable to jit (ignored when `jitted` is given).
    label : the compile record's `label` field — name the call site
        (`"local.step/LeNet5"`, `"serving.forward/Sequential"`).
    telemetry : optional `observability.Telemetry`; assignable after
        construction (`wrapper.telemetry = tel`) — the serving engine
        attaches its stream to the predictor's wrapper this way.
    sig_argnums : positional indices whose shapes/dtypes define the
        signature (default: all args). Non-signature args must keep
        constant avals over the wrapper's lifetime (the train loops and
        the predictor satisfy this: parameter trees don't change shape
        mid-run); a violation surfaces as a dispatch error and flips the
        wrapper onto the plain-jit fallback.
    donate_argnums : forwarded to `jax.jit`.

    After any call, `last_info` holds the dispatched signature's cost dict
    (`{"flops", "bytes_accessed", "jaxpr_eqns", "lower_s", "compile_s",
    "cache_hit", "signature"}`) — the optimizers and the serving engine
    read FLOPs for the step/stats records from it.
    """

    def __init__(self, fn: Optional[Callable] = None, *, label: str,
                 telemetry=None, sig_argnums: Optional[Sequence[int]] = None,
                 donate_argnums=(), jitted=None):
        import jax
        if jitted is None:
            if fn is None:
                raise ValueError("need fn or jitted")
            jitted = jax.jit(fn, donate_argnums=donate_argnums)
        self._jit = jitted
        self.label = label
        self.telemetry = telemetry
        self.sig_argnums = tuple(sig_argnums) if sig_argnums is not None \
            else None
        self._lock = threading.Lock()
        self._cache: Dict[Tuple, Tuple] = {}  # sig -> (compiled, info)
        self._aot_ok = True
        self._tls = threading.local()  # per-thread last dispatched info

    # ------------------------------------------------------------ internals
    def _signature(self, args) -> Tuple:
        if self.sig_argnums is None:
            return arg_signature(args)
        return arg_signature(tuple(args[i] for i in self.sig_argnums))

    @property
    def last_info(self) -> Optional[Dict]:
        """Cost dict of the signature THIS THREAD last dispatched (the
        serving dispatcher must not read the warmup thread's bucket), or
        None when the last call took the plain-jit fallback — absent
        attribution beats silently wrong attribution."""
        return getattr(self._tls, "info", None)

    def _cache_size(self) -> int:
        """Distinct signatures compiled through this wrapper — keeps the
        serving engine's jit-cache-based `compile_count()` working. Once
        the plain-jit fallback is engaged, later compiles land in the
        underlying jit cache instead, so count both (a signature that
        compiled on both sides before the flip counts twice — monitoring
        precision, not an invariant)."""
        with self._lock:
            n = len(self._cache)
        if not self._aot_ok:
            try:
                n += int(self._jit._cache_size())
            except Exception:
                pass
        return n

    def executables(self) -> list:
        """The compiled executables built so far (`jax.stages.Compiled`),
        one per signature — chip_smoke.py reads their HLO to check that
        the Mosaic kernels and the collectives it expects are in the step
        that actually ran."""
        with self._lock:
            return [compiled for compiled, _ in self._cache.values()]

    def _emit(self, record: Dict):
        if self.telemetry is None:
            return
        try:
            self.telemetry.emit(record)
        except Exception:
            logger.exception("compile telemetry emit failed; record dropped")

    def _compile(self, sig: Tuple, args):
        """Stage lower+compile for one signature, emit its compile record,
        cache the executable. Returns (compiled, info); lowering and
        compile errors propagate."""
        t0 = time.monotonic()
        traced = self._jit.trace(*args)
        eqns = costs.jaxpr_eqn_count(traced.jaxpr)
        lowered = traced.lower()
        lower_s = time.monotonic() - t0
        t1 = time.monotonic()
        compiled = lowered.compile()
        compile_s = time.monotonic() - t1
        cost = costs.executable_costs(compiled)
        if cost["flops"] is None:
            # the backend's count was zero or non-finite: jaxpr-walk floor
            cost["flops"] = costs.jaxpr_flops(traced.jaxpr) or None
        key = (self.label, sig, eqns)
        with _COMPILED_BEFORE_LOCK:
            cache_hit = key in _COMPILED_BEFORE
            _COMPILED_BEFORE.add(key)
        info = {"signature": signature_str(sig), "lower_s": round(lower_s, 6),
                "compile_s": round(compile_s, 6), "jaxpr_eqns": eqns,
                "cache_hit": cache_hit, "flops": cost["flops"],
                "bytes_accessed": cost["bytes_accessed"]}
        self._emit({"type": "compile", "label": self.label, **info})
        return compiled, info

    # ------------------------------------------------------------- dispatch
    def _fallback(self, args):
        """Plain-jit dispatch; clears this thread's last_info so readers
        see 'no attribution' rather than a stale signature's costs."""
        self._tls.info = None
        return self._jit(*args)

    def __call__(self, *args):
        if not self._aot_ok:
            return self._fallback(args)
        try:
            sig = self._signature(args)
        except Exception:
            self._aot_ok = False
            return self._fallback(args)
        with self._lock:
            entry = self._cache.get(sig)
        if entry is None:
            entry = self._compile(sig, args)
            with self._lock:
                self._cache.setdefault(sig, entry)
        compiled, info = entry
        try:
            out = compiled(*args)
        except Exception as e:
            # AOT dispatch rejected the arguments (aval drift in a
            # non-signature arg, backend quirk): permanent plain-jit
            # fallback — correctness over instrumentation
            logger.warning("AOT dispatch failed for %s (%r); falling back "
                           "to plain jit dispatch", self.label, e)
            self._aot_ok = False
            return self._fallback(args)
        self._tls.info = info
        return out

    def cost_info(self, *args) -> Optional[Dict]:
        """The cached cost dict for the signature `args` would dispatch
        under, without running anything; None if never compiled."""
        try:
            sig = self._signature(args)
        except Exception:
            return None
        with self._lock:
            entry = self._cache.get(sig)
        return entry[1] if entry else None
