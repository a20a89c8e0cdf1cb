"""Deterministic fault injection at named hook points.

The fault model: a backend wobbles transiently, dies outright
mid-transfer, or stalls — and serving replicas can drop mid-stream.  This module lets
tier-1 CPU tests replay those failures deterministically.

Hook points (``fault_point(site, **ctx)``) are compiled into the hot
paths but are a single attribute read + ``is None`` check when no
injector is active — and NOTHING can activate one unless the
``BIGDL_TPU_FAULTS`` env var is explicitly set, so production paths
never fire a fault by accident.

Spec grammar (``;``-separated specs)::

    BIGDL_TPU_FAULTS="site:kind[:key=val[,key=val...]][;spec...]"

    site   hook-point name: transfer.chunk | engine.init |
           serving.dispatch | serving.enqueue | serving.verify |
           serving.migrate | serving.cancel
           (more may be added freely; a transient at serving.verify
           demotes the speculating slots to plain decode instead of
           killing their streams — see lm_engine._step_spec; a
           transient at serving.migrate retries the KV-chain export
           via with_backoff, backend_lost makes the decode replica
           re-prefill the migrated prompt — zero accepted loss either
           way, see serving/disagg/coordinator.py; serving.cancel is
           the client-disconnect site — it is crossed once per live
           stream per scheduler round, and ANY injected fault there is
           converted into a cooperative ``stream.cancel()``, i.e. the
           client walked away mid-stream.  The stream finishes with a
           typed truncation, never an error: a disconnect storm must
           cost wasted decode, not correctness — see
           lm_engine._lifecycle_round)
    kind   transient     raise TransientBackendError
           backend_lost  raise BackendLostError
           die           alias of backend_lost (reads better for
                         replica-death specs)
           latency       sleep ms= milliseconds, then continue
    keys   p=0.25        firing probability (default 1.0; draws come
                         from one seeded stream, BIGDL_TPU_FAULTS_SEED)
           after=3       arm from the 3rd matching check on (1-based)
           count=2       fire at most twice, then go quiet
           name=r1       only match checks carrying ctx name == "r1"
           ms=50         latency kind: sleep duration

Examples::

    # a backend death mid-transfer: the third chunk kills it
    BIGDL_TPU_FAULTS="transfer.chunk:backend_lost:after=3"
    # a flaky link: 20% of chunk uploads wobble, forever
    BIGDL_TPU_FAULTS="transfer.chunk:transient:p=0.2"
    # serving replica r1 dies from its 4th dispatch on
    BIGDL_TPU_FAULTS="serving.dispatch:die:name=r1,after=4"
"""
from __future__ import annotations

import logging
import os
import random
import threading
import time
from typing import Optional

from bigdl_tpu.resilience.errors import BackendLostError, TransientBackendError

log = logging.getLogger("bigdl_tpu.resilience")

ENV_SPEC = "BIGDL_TPU_FAULTS"
ENV_SEED = "BIGDL_TPU_FAULTS_SEED"

_KINDS = ("transient", "backend_lost", "die", "latency")


class _FaultSpec:
    __slots__ = ("site", "kind", "p", "after", "count", "name", "ms",
                 "seen", "fired")

    def __init__(self, site: str, kind: str, *, p: float = 1.0,
                 after: int = 1, count: Optional[int] = None,
                 name: Optional[str] = None, ms: float = 0.0):
        if kind not in _KINDS:
            raise ValueError(f"unknown fault kind {kind!r} "
                             f"(expected one of {_KINDS})")
        self.site = site
        self.kind = "backend_lost" if kind == "die" else kind
        self.p = float(p)
        self.after = int(after)
        self.count = None if count is None else int(count)
        self.name = name
        self.ms = float(ms)
        self.seen = 0    # matching checks observed
        self.fired = 0   # faults actually injected

    def describe(self) -> str:
        extra = []
        if self.p < 1.0:
            extra.append(f"p={self.p}")
        if self.after > 1:
            extra.append(f"after={self.after}")
        if self.count is not None:
            extra.append(f"count={self.count}")
        if self.name is not None:
            extra.append(f"name={self.name}")
        if self.kind == "latency":
            extra.append(f"ms={self.ms}")
        tail = (":" + ",".join(extra)) if extra else ""
        return f"{self.site}:{self.kind}{tail}"


def parse_spec(text: str) -> list:
    """Parse the env grammar into specs; a malformed spec raises
    loudly — a typo'd chaos configuration silently injecting nothing
    would invalidate the whole fault run."""
    specs = []
    for raw in text.split(";"):
        raw = raw.strip()
        if not raw:
            continue
        fields = raw.split(":")
        if len(fields) < 2:
            raise ValueError(
                f"bad fault spec {raw!r}: expected site:kind[:k=v,...]")
        site, kind = fields[0].strip(), fields[1].strip()
        kwargs = {}
        if len(fields) > 2:
            for pair in ":".join(fields[2:]).split(","):
                pair = pair.strip()
                if not pair:
                    continue
                if "=" not in pair:
                    raise ValueError(
                        f"bad fault spec {raw!r}: option {pair!r} "
                        "is not key=value")
                k, v = pair.split("=", 1)
                k = k.strip()
                if k in ("p", "ms"):
                    kwargs[k] = float(v)
                elif k in ("after", "count"):
                    kwargs[k] = int(v)
                elif k == "name":
                    kwargs[k] = v.strip()
                else:
                    raise ValueError(
                        f"bad fault spec {raw!r}: unknown option {k!r}")
        specs.append(_FaultSpec(site, kind, **kwargs))
    if not specs:
        raise ValueError(f"fault spec {text!r} contains no specs")
    return specs


class FaultInjector:
    """Deterministic injector: seeded probability stream + per-spec
    check counters, so the same spec + seed + call sequence injects
    the same faults every run."""

    def __init__(self, specs, seed: int = 0):
        if isinstance(specs, str):
            specs = parse_spec(specs)
        self.specs = list(specs)
        self.seed = int(seed)
        self._rng = random.Random(self.seed)
        self._lock = threading.Lock()

    def check(self, site: str, **ctx) -> None:
        """Raise / sleep according to the first matching armed spec."""
        for spec in self.specs:
            if spec.site != site:
                continue
            with self._lock:
                if spec.name is not None and ctx.get("name") != spec.name:
                    continue
                spec.seen += 1
                if spec.seen < spec.after:
                    continue
                if spec.count is not None and spec.fired >= spec.count:
                    continue
                # p=1.0 specs never touch the rng, so fully
                # deterministic specs stay independent of any
                # probabilistic ones sharing the stream
                if spec.p < 1.0 and self._rng.random() >= spec.p:
                    continue
                spec.fired += 1
                fired = spec.fired
            self._record(site, spec)
            detail = (f"injected fault [{spec.describe()}] at {site} "
                      f"(check {spec.seen}, firing {fired}, ctx {ctx})")
            if spec.kind == "latency":
                time.sleep(spec.ms / 1000.0)
                return
            if spec.kind == "backend_lost":
                raise BackendLostError(detail)
            raise TransientBackendError(f"UNAVAILABLE: {detail}")

    @staticmethod
    def _record(site: str, spec: _FaultSpec) -> None:
        from bigdl_tpu.obs import get_registry
        get_registry().counter("resilience/faults_injected").add(1)
        log.info("fault injected: %s at %s", spec.describe(), site)
        # every fire is an incident candidate; the recorder's per-site
        # dedup window collapses a chaos sweep to one bundle per site
        try:
            from bigdl_tpu.obs import flight
            flight.get_flight_recorder().record(
                "fault_injected",
                {"site": site, "spec": spec.describe()}, key=site)
        except Exception:
            log.exception("fault flight-recorder dump failed")

    def stats(self) -> dict:
        # aggregate per describe(): a chaos schedule arms many
        # identical specs (one per event) — last-wins keying would
        # silently drop the fired counts of all but one
        with self._lock:
            out: dict = {}
            for s in self.specs:
                d = out.setdefault(s.describe(), {"seen": 0, "fired": 0})
                d["seen"] += s.seen
                d["fired"] += s.fired
            return out


_active: Optional[FaultInjector] = None


def active() -> Optional[FaultInjector]:
    return _active


def install(injector: Optional[FaultInjector]) -> None:
    """Activate an injector — REFUSED unless ``BIGDL_TPU_FAULTS`` is
    explicitly set, so no code path (test helper, misconfigured tool)
    can ever switch fault injection on in a production process by
    accident.  ``install(None)`` always deactivates."""
    global _active
    if injector is not None and not os.environ.get(ENV_SPEC):
        raise RuntimeError(
            f"refusing to activate FaultInjector: {ENV_SPEC} is not set "
            "(fault injection must be an explicit, visible choice)")
    _active = injector


def refresh_from_env() -> Optional[FaultInjector]:
    """(Re)build the active injector from ``BIGDL_TPU_FAULTS`` /
    ``BIGDL_TPU_FAULTS_SEED``; unset env deactivates.  Called once at
    import, and by tests around monkeypatched env."""
    global _active
    spec = os.environ.get(ENV_SPEC)
    if not spec:
        _active = None
        return None
    injector = FaultInjector(spec, seed=int(os.environ.get(ENV_SEED, "0")))
    log.warning("fault injection ACTIVE (%s=%r, seed=%d)",
                ENV_SPEC, spec, injector.seed)
    _active = injector
    return injector


def fault_point(site: str, **ctx) -> None:
    """Hook point: no-op (one global read) unless an injector is
    active.  Safe to call from any thread."""
    inj = _active
    if inj is not None:
        inj.check(site, **ctx)


refresh_from_env()
