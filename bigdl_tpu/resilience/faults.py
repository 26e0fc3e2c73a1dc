"""Deterministic, plan-driven fault injection.

The reference's robustness story — "retry the job and reload the newest
snapshot" (DL/optim/DistriOptimizer.scala:862-943) — was validated by
integration clusters that actually lost executors. This repo has no
cluster to kill, so faults become a first-class, *deterministic* input:
named sites threaded through the framework call `fire("site.name")`,
which is a single global load + `None` check when no injector is
installed, and raises a chosen exception at a chosen hit when one is.

Chaos tests then crash the system at any instrumented point — between two
checkpoint writes, inside a prefetch worker, on the Nth train step, in a
serving forward — and assert the recovery machinery (durable checkpoints,
retry policies, the serving circuit breaker) actually recovers.

Instrumented sites (see docs/resilience.md for the full contract):

    ckpt.write.params / ckpt.write.state / ckpt.write.optim /
    ckpt.write.manifest / ckpt.commit      serialization/checkpoint.py
    train.step                             both optimizers' driver loops
    mesh.device_loss / mesh.collective     DistriOptimizer elastic loop
    prefetch.worker                        dataset/prefetch.py workers
    serve.forward                          serving/engine.py dispatch
    serve.replica_crash / serve.route /
    serve.drain                            serving/fleet.py (registered
                                           via register_site on import)
    fs.remote_io                           utils/filesystem.py remote ops
    telemetry.sink                         observability Telemetry.emit

Example — crash the 3rd training step once, transiently:

    >>> from bigdl_tpu.resilience import FaultInjector, FaultSpec
    >>> plan = FaultInjector(FaultSpec("train.step", at_hit=3))
    >>> with plan:
    ...     pass  # optimizer.optimize() here would crash at step 3
"""

from __future__ import annotations

import logging
import random
import threading
from typing import Callable, Dict, List, Optional, Tuple

logger = logging.getLogger("bigdl_tpu.resilience")

#: Every site the framework instruments. Site names follow the
#: `<subsystem>.<event>` convention (docs/resilience.md): the prefix is
#: the owning subsystem (`ckpt`, `train`, `mesh`, `prefetch`, `serve`,
#: `fs`, `telemetry`), the suffix the instrumented moment. `FaultSpec`
#: VALIDATES against this registry — a typo'd site raises at plan-build
#: time instead of silently never firing. Out-of-tree code extends the
#: registry with `register_site()` before building its specs.
KNOWN_SITES = (
    "ckpt.write.params", "ckpt.write.state", "ckpt.write.optim",
    "ckpt.write.manifest", "ckpt.commit",
    "train.step", "mesh.device_loss", "mesh.collective",
    "prefetch.worker", "serve.forward",
    "fs.remote_io", "telemetry.sink",
)

_EXTRA_SITES: set = set()


def register_site(site: str) -> str:
    """Register an out-of-tree fault site so `FaultSpec(site)` accepts it.
    Returns the name. Use for application-level `fire()` points; the
    in-tree sites live in `KNOWN_SITES`."""
    if not site or "." not in site:
        raise ValueError(
            f"fault site {site!r} must follow '<subsystem>.<event>'")
    _EXTRA_SITES.add(site)
    return site


def known_sites() -> tuple:
    """Every currently-registered site (in-tree + `register_site` extras)."""
    return KNOWN_SITES + tuple(sorted(_EXTRA_SITES))


class InjectedFault(Exception):
    """Base class for injector-raised faults."""


class TransientInjectedFault(InjectedFault):
    """An injected fault classified TRANSIENT by `RetryPolicy` defaults —
    models a flaky network read, a preempted worker, a link blip."""


class PermanentInjectedFault(InjectedFault):
    """An injected fault classified PERMANENT by `RetryPolicy` defaults —
    models a shape error or a poisoned input that retrying cannot fix."""


class FaultSpec:
    """One entry of a fault plan: fire `exc` at site `site`.

    Parameters
    ----------
    site : the instrumented site name — must be in `known_sites()`
        (`KNOWN_SITES` plus `register_site` extras). An unknown name
        raises `ValueError` at spec-build time: a typo'd site would
        otherwise silently never fire and the chaos test would pass
        vacuously.
    at_hit : 1-based hit count at which the fault starts firing (hit =
        one `fire()` call at this site while the plan is installed).
    times : how many consecutive hits fire from `at_hit` on; `None`
        means every hit from `at_hit` onward (a persistent failure).
    p : per-hit probability instead of deterministic counting — drawn
        from the INJECTOR's seeded rng, so a given (plan, seed) replays
        bit-identically. `at_hit`/`times` still bound which hits are
        eligible.
    exc : the exception to raise — a class (instantiated with a
        descriptive message), an instance (raised as-is), or a callable
        `ctx -> BaseException`.
    when : optional predicate over the site's context dict (e.g.
        `lambda ctx: ctx.get("bucket") == 4`) for targeting one bucket /
        step / path; hits that fail the predicate are not counted.
    """

    __slots__ = ("site", "at_hit", "times", "p", "exc", "when")

    def __init__(self, site: str, at_hit: int = 1,
                 times: Optional[int] = 1, p: Optional[float] = None,
                 exc=TransientInjectedFault,
                 when: Optional[Callable[[Dict], bool]] = None):
        if at_hit < 1:
            raise ValueError(f"at_hit must be >= 1, got {at_hit}")
        if times is not None and times < 1:
            raise ValueError(f"times must be >= 1 or None, got {times}")
        if site not in KNOWN_SITES and site not in _EXTRA_SITES:
            raise ValueError(
                f"FaultSpec site {site!r} is not an instrumented site — it "
                f"would never fire. Known sites: {', '.join(known_sites())}. "
                f"Out-of-tree fire() points must call register_site() "
                f"first.")
        self.site = site
        self.at_hit = at_hit
        self.times = times
        self.p = p
        self.exc = exc
        self.when = when

    def _build_exc(self, ctx: Dict, hit: int) -> BaseException:
        if isinstance(self.exc, BaseException):
            return self.exc
        if isinstance(self.exc, type) and issubclass(self.exc,
                                                     BaseException):
            return self.exc(f"injected fault at {self.site} (hit {hit})")
        return self.exc(ctx)

    def __repr__(self):
        return (f"FaultSpec({self.site!r}, at_hit={self.at_hit}, "
                f"times={self.times}, p={self.p})")


class FaultInjector:
    """A seeded fault plan, installable as the process-wide injector.

    Use as a context manager (install on enter, uninstall on exit) or via
    `install()`/`uninstall()`. Thread-safe: sites fire from optimizer,
    prefetch-worker, and serving-dispatcher threads concurrently. Firing
    history is kept on `fired` (list of `(site, hit)` tuples) and per-site
    hit counts on `hits()`, so tests can assert exactly what happened.

    When `telemetry` is attached, every firing emits a `fault_injected`
    event BEFORE the exception is raised — the chaos stream then shows
    cause (fault_injected) and effect (retry / circuit_open /
    checkpoint_quarantined) in one place. A reentrancy guard keeps a
    `telemetry.sink` spec from recursing through that very emission.
    """

    def __init__(self, *specs: FaultSpec, seed: int = 0, telemetry=None):
        self.specs = list(specs)
        self.telemetry = telemetry
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self._hits: Dict[str, int] = {}        # per-site fire() calls
        self._spec_hits: Dict[int, int] = {}   # per-spec matching calls
        self.fired: List[Tuple[str, int]] = []
        self._local = threading.local()

    # ------------------------------------------------------------ plan API
    def add(self, spec: FaultSpec) -> "FaultInjector":
        """Append a spec to the plan (usable while installed)."""
        with self._lock:
            self.specs.append(spec)
        return self

    def hits(self, site: str) -> int:
        """How many `fire()` calls `site` made while this plan was
        installed (every call, faulted or not)."""
        with self._lock:
            return self._hits.get(site, 0)

    # ----------------------------------------------------------- lifecycle
    def install(self) -> "FaultInjector":
        """Make this plan the process-wide injector (replacing any other)."""
        global _ACTIVE
        _ACTIVE = self
        return self

    def uninstall(self):
        """Remove this plan if it is the installed one."""
        global _ACTIVE
        if _ACTIVE is self:
            _ACTIVE = None

    def __enter__(self) -> "FaultInjector":
        return self.install()

    def __exit__(self, *exc):
        self.uninstall()

    # -------------------------------------------------------------- firing
    def _fire(self, site: str, ctx: Dict):
        if getattr(self._local, "emitting", False):
            return  # a telemetry.sink spec must not recurse through its
            # own fault_injected emission
        raise_exc = None
        hit = 0
        with self._lock:
            hit = self._hits.get(site, 0) + 1
            self._hits[site] = hit
            for s in self.specs:
                if s.site != site:
                    continue
                if s.when is not None and not s.when(ctx):
                    continue
                # at_hit/times count the calls MATCHING this spec (site +
                # predicate), so "bucket 4's 3rd batch" targets cleanly
                shit = self._spec_hits.get(id(s), 0) + 1
                self._spec_hits[id(s)] = shit
                if shit < s.at_hit:
                    continue
                if s.times is not None and shit >= s.at_hit + s.times:
                    continue
                if s.p is not None and self._rng.random() >= s.p:
                    continue
                raise_exc = s._build_exc(ctx, shit)
                self.fired.append((site, hit))
                break
        if raise_exc is None:
            return
        logger.warning("fault injected at %s (hit %d): %r", site, hit,
                       raise_exc)
        if self.telemetry is not None:
            self._local.emitting = True
            try:
                self.telemetry.event("fault_injected", site=site, hit=hit,
                                     error=repr(raise_exc))
            except Exception:
                logger.exception("fault_injected telemetry emit failed")
            finally:
                self._local.emitting = False
        raise raise_exc


#: The installed injector, or None. Read on every `fire()` call — keeping
#: this a bare module global makes the disabled path one LOAD_GLOBAL plus
#: an `is None` test, cheap enough for per-item prefetch loops.
_ACTIVE: Optional[FaultInjector] = None


def fire(site: str, **ctx):
    """Framework-side fault point: a no-op unless a `FaultInjector` is
    installed, in which case the installed plan decides whether this hit
    at `site` raises. `ctx` keyword args (step, bucket, path, ...) are
    visible to `FaultSpec.when` predicates and exception factories."""
    inj = _ACTIVE
    if inj is not None:
        inj._fire(site, ctx)


def active_injector() -> Optional[FaultInjector]:
    """The installed injector, or None (for tests/diagnostics)."""
    return _ACTIVE
