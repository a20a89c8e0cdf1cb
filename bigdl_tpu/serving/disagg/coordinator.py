"""DisaggCoordinator: phase-dedicated replica pools with KV-chain
migration between them.

Prefill and decode have opposite hardware appetites — prefill is one
big compute-bound matmul per request, decode is a memory-bound gather
over the KV arena per token — yet a co-located engine interleaves them
on the same slots, so every large prompt stalls every in-flight decode
(``serving/lm/itl_prefill_gap`` counts those gaps).
Disaggregation (DistServe, OSDI'24; Splitwise, ISCA'24) runs the two
phases on *separate replicas* so the SLOs decouple: TTFT is the
prefill pool's problem, ITL the decode pool's.

The coordinator owns both pools and the hop between them:

- **prefill replicas** are plain :class:`LMServingEngine` instances
  constructed with ``migrate=<coordinator callback>``: they bucket-
  prefill, emit the FIRST token (TTFT is paid where the prompt is
  computed), then hand the request off instead of seating a decode
  slot.  They never compile or run the decode executable.
- **decode replicas** are untouched engines; they receive migrated
  requests via :meth:`LMServingEngine.adopt` and run the donated
  fixed-shape decode executable over chains they adopted rather than
  prefilled.
- **the hop** is :meth:`BlockPool.export_chain` on the prefill side →
  :meth:`BlockPool.adopt_chain` on the decode side, over
  ``chunked_device_put`` (the 32 MB rule).  Before exporting, the
  coordinator matches the DECODE replica's radix cache against the
  prompt (the trie is lock-guarded, so the cross-thread match from the
  prefill worker is safe): blocks the decode pool already holds do not
  travel — prefix sharing survives the hop — and only the unmatched
  tail is wired across.
- **faults**: the export runs under ``with_backoff`` around the
  ``serving.migrate`` fault site.  A transient retries; exhausted
  retries (``BackendLostError``) drop the payload and the decode
  replica RE-PREFILLS the prompt locally — deterministic prefill makes
  the recomputed KV bit-identical and the already-emitted first token
  is never re-picked, so the accepted stream completes exactly
  (counted in ``re_prefills``, never lost).
- **independent scaling**: :meth:`try_scale_up` adds a replica to ONE
  phase, gated on the :class:`PlacementPolicy`'s phase-tagged slots;
  :meth:`slo_controllers` wires two ladders — TTFT → prefill pool,
  ITL → decode pool — over the per-phase metrics the pools publish at
  ``serving/lm/prefill/*`` and ``serving/lm/decode/*``.

BigDL lineage: the original framework separated functional roles
across identical workers on one cluster (arXiv 1804.05839), and BigDL
2.0 ran heterogeneous pipelines side by side on shared infrastructure
(arXiv 2204.01715); phase-dedicated pools are that separation applied
to the two halves of autoregressive generation.
"""
from __future__ import annotations

import logging
import threading
from typing import Dict, List, Optional

from bigdl_tpu.obs import get_registry, get_tracer
from bigdl_tpu.resilience.errors import BackendLostError
from bigdl_tpu.resilience.faults import fault_point
from bigdl_tpu.resilience.retry import with_backoff
from bigdl_tpu.serving.lm_engine import (KVHandoff, LMMetrics,
                                         LMServingEngine, LMStream)

_tracer = get_tracer()
log = logging.getLogger("bigdl_tpu.serving")


class DisaggCoordinator:
    """Run prefill and decode on separate replica pools of one model.

    Args:
        model: a built ``TransformerLM`` — shared by every replica
            (params are read-only at serve time).
        prefill_replicas / decode_replicas: initial pool sizes.
        placement: optional
            :class:`~bigdl_tpu.serving.placement.PlacementPolicy`;
            when given, every replica acquires a phase-tagged mesh
            slot (``acquire(phase=...)``) and scale-up is refused once
            the device set is full.  Without it replicas share the
            default device (the CPU test posture).
        max_replicas_per_phase: scale-up ceiling per phase when no
            placement policy bounds it.
        migrate_retries / migrate_base_delay_s: ``with_backoff``
            parameters for the chain export at the ``serving.migrate``
            fault site.
        name: prefix for replica engine names
            (``<name>-prefill0``, ``<name>-decode0``, ...).
        spec: optional speculative-decoding config — applied to DECODE
            replicas only (a prefill replica never decodes).
        **engine_kwargs: forwarded to every
            :class:`LMServingEngine` (slots, cache_len, block_len,
            num_blocks, temperature, eos_id, ...).

    Each phase publishes ONE shared :class:`LMMetrics` (all replicas
    of a phase record into the same histograms) under
    ``serving/lm/prefill/`` and ``serving/lm/decode/`` — the two SLO
    ladders each watch their own phase's latency, which is the whole
    point of disaggregating.
    """

    def __init__(self, model, *,
                 prefill_replicas: int = 1,
                 decode_replicas: int = 1,
                 placement=None,
                 max_replicas_per_phase: int = 4,
                 migrate_retries: int = 2,
                 migrate_base_delay_s: float = 0.05,
                 name: str = "disagg",
                 spec=None,
                 **engine_kwargs):
        if prefill_replicas < 1 or decode_replicas < 1:
            raise ValueError("each phase needs at least one replica")
        self.model = model
        self.name = name
        self.placement = placement
        self.max_replicas_per_phase = int(max_replicas_per_phase)
        self.migrate_retries = int(migrate_retries)
        self.migrate_base_delay_s = float(migrate_base_delay_s)
        self._spec = spec
        self._kw = dict(engine_kwargs)
        slots = int(self._kw.get("slots", 8))
        self._prefill_metrics = LMMetrics(slots * prefill_replicas)
        self._decode_metrics = LMMetrics(slots * decode_replicas)
        self._lock = threading.Lock()
        self._slices: Dict[str, object] = {}   # engine name -> MeshSlice
        self._rr = 0                           # round-robin submit cursor
        self.migrations = 0
        self.migrated_blocks = 0
        self.lost_payloads = 0
        self._closing = False
        # decode pool first: the migrate callback needs a live target
        # before any prefill replica can finish its first request
        self.decode: List[LMServingEngine] = [
            self._make_engine("decode", i) for i in range(decode_replicas)]
        self.prefill: List[LMServingEngine] = [
            self._make_engine("prefill", i) for i in range(prefill_replicas)]

    # -- replica construction ------------------------------------------- #
    def _make_engine(self, phase: str, idx: int) -> LMServingEngine:
        ename = f"{self.name}-{phase}{idx}"
        slot = None
        if self.placement is not None:
            slot = self.placement.acquire(phase=phase)
            if slot is None:
                raise RuntimeError(
                    f"no free placement slot for {ename} "
                    f"({self.placement!r})")
        kw = dict(self._kw)
        if phase == "prefill":
            kw["migrate"] = self._migrate
            metrics, prefix = self._prefill_metrics, "serving/lm/prefill/"
        else:
            if self._spec is not None:
                kw["spec"] = self._spec
            metrics, prefix = self._decode_metrics, "serving/lm/decode/"
        try:
            eng = LMServingEngine(self.model, name=ename, placement=slot,
                                  metrics=metrics, metrics_prefix=prefix,
                                  **kw)
        except BaseException:
            if slot is not None:
                self.placement.release(slot)
            raise
        if slot is not None:
            self._slices[ename] = slot
        # a decode replica is indistinguishable from a co-located engine
        # from the inside (migrate=None); the pool it serves is not
        eng.phase = phase
        return eng

    # -- the migration hop ---------------------------------------------- #
    def _pick_decode(self) -> LMServingEngine:
        """Least-loaded decode replica (active + pending adoptions)."""
        return min(self.decode,
                   key=lambda e: (e._n_active + len(e._adopt_q)
                                  + len(e._prefilling)))

    def _migrate(self, h: KVHandoff, blocks, src_pool) -> None:
        """Prefill-engine callback (runs in ITS worker thread, with the
        chain's references still held by the caller): pick a decode
        replica, dedupe against its radix, wire the unmatched tail
        across, enqueue the adoption."""
        eng = self._pick_decode()
        t = int(h.prompt0.shape[0])
        n_prompt = src_pool.blocks_for(t)
        matched: List[int] = []
        if eng.radix is not None:
            # lock-guarded trie: safe from this (foreign) thread.
            # Matched blocks are retained in the DECODE pool for the
            # adoption — they are the part of the chain that does not
            # need to travel.
            matched = eng.radix.match(h.prompt0)
        tail = list(blocks[len(matched):n_prompt])

        def _export():
            fault_point("serving.migrate", rid=h.rid, src=h.src_name,
                        dst=eng.name, blocks=len(tail))
            return src_pool.export_chain(tail)

        try:
            h.payload = with_backoff(
                _export, retries=self.migrate_retries,
                base_delay_s=self.migrate_base_delay_s,
                label=f"{self.name}.migrate")
        except BackendLostError:
            # the wire is gone mid-hop; the chain still exists only on
            # the (about-to-release) prefill side, so the decode
            # replica recomputes it.  Deterministic prefill + the
            # carried first token keep the stream exact.
            log.warning("%s: migrate payload lost for %s; decode "
                        "replica %s will re-prefill", self.name, h.rid,
                        eng.name)
            h.payload = None
        h.matched = matched
        try:
            eng.adopt(h)
        except BaseException:
            if matched:
                eng.pool.release(matched)
            raise
        with self._lock:
            self.migrations += 1
            if h.payload is None:
                self.lost_payloads += 1
            else:
                self.migrated_blocks += int(h.payload["blocks"])

    # -- client API ------------------------------------------------------ #
    def submit(self, prompt_ids, *, max_new_tokens=None, temperature=None,
               eos_id=None, rng=None) -> LMStream:
        """Enqueue one prompt on a prefill replica (round-robin); the
        returned stream completes on whichever decode replica adopts
        the chain — the client never sees the hop."""
        with self._lock:
            if self._closing:
                from bigdl_tpu.serving.batcher import ServingClosed
                raise ServingClosed("DisaggCoordinator is closed")
            eng = self.prefill[self._rr % len(self.prefill)]
            self._rr += 1
        return eng.submit(prompt_ids, max_new_tokens=max_new_tokens,
                          temperature=temperature, eos_id=eos_id, rng=rng)

    def warmup(self) -> int:
        """AOT-compile every replica's executables — including the
        decode pools' adopt scatters for every power-of-two wire width
        a migration can arrive at, so the first hop never pays a
        mid-traffic compile.  Returns the executable count."""
        n = 0
        for eng in self.prefill + self.decode:
            n += eng.warmup()
        for eng in self.decode:
            widths, w = [], 1
            while w < eng.table_width:
                widths.append(w)
                w *= 2
            widths.append(w)
            n += eng.pool.warmup_adopt(widths)
        return n

    # -- independent phase scaling --------------------------------------- #
    def try_scale_up(self, phase: str) -> bool:
        """Add one replica to ``phase`` ("prefill" | "decode").  Returns
        False — without side effects — when the phase is at its ceiling
        or the placement policy has no free slot; truthiness is the
        :class:`SLOController` scale-actuator contract (falsy ⇒ the
        ladder falls through to admission control)."""
        if phase not in ("prefill", "decode"):
            raise ValueError(f"unknown phase {phase!r}")
        with self._lock:
            if self._closing:
                return False
            pool = self.prefill if phase == "prefill" else self.decode
            idx = len(pool)
            if idx >= self.max_replicas_per_phase:
                return False
            if self.placement is not None and self.placement.headroom() == 0:
                return False
            try:
                eng = self._make_engine(phase, idx)
            except RuntimeError:
                return False   # raced out of the last placement slot
            pool.append(eng)
        metrics = (self._prefill_metrics if phase == "prefill"
                   else self._decode_metrics)
        with metrics._lock:
            metrics.slots += eng.slots
        log.info("%s: scaled %s pool to %d replicas", self.name, phase,
                 idx + 1)
        _tracer.instant("disagg/scale_up", cat="serve", phase=phase,
                        replicas=idx + 1)
        return True

    def slo_controllers(self, *, ttft_target_s: float, itl_target_s: float,
                        **ctl_kwargs):
        """Two independent ladders over the per-phase histograms:
        windowed TTFT p99 grows the PREFILL pool, windowed decode-ITL
        p99 grows the DECODE pool.  Extra kwargs go to both
        :class:`~bigdl_tpu.traffic.slo.SLOController` constructors.
        Returned un-started; callers tick or ``start()`` them."""
        from bigdl_tpu.traffic.slo import SLOController
        ttft_ctl = SLOController(
            histogram=self._prefill_metrics.ttft,
            target_p99_s=ttft_target_s,
            scale_up=lambda: self.try_scale_up("prefill"),
            **ctl_kwargs)
        itl_ctl = SLOController(
            histogram=self._decode_metrics.itl_decode,
            target_p99_s=itl_target_s,
            scale_up=lambda: self.try_scale_up("decode"),
            **ctl_kwargs)
        return ttft_ctl, itl_ctl

    # -- observability ---------------------------------------------------- #
    @property
    def prefill_metrics(self) -> LMMetrics:
        return self._prefill_metrics

    @property
    def decode_metrics(self) -> LMMetrics:
        return self._decode_metrics

    @property
    def metrics(self) -> LMMetrics:
        """Engine-compat alias (callers written against one engine
        read ``eng.metrics``): the DECODE pool's metrics — the client-visible
        token cadence (ITL, tokens/sec, completions) lives where decode
        runs; TTFT is client-measured and ``prefill_metrics`` holds the
        server-side view."""
        return self._decode_metrics

    @property
    def decode_attn(self) -> str:
        return self.decode[0].decode_attn

    def stats(self) -> dict:
        with self._lock:
            out = {
                "name": self.name,
                "prefill_replicas": len(self.prefill),
                "decode_replicas": len(self.decode),
                "migrations": self.migrations,
                "migrated_blocks": self.migrated_blocks,
                "lost_payloads": self.lost_payloads,
            }
        out["re_prefills"] = sum(e.re_prefills for e in self.decode)
        out["adopted"] = sum(e.adopted for e in self.decode)
        out["phase_counts"] = (self.placement.phase_counts()
                               if self.placement is not None else None)
        out["prefill"] = self._prefill_metrics.snapshot()
        out["decode"] = self._decode_metrics.snapshot()
        out["engines"] = {e.name: e.stats()
                          for e in self.prefill + self.decode}
        return out

    # -- lifecycle -------------------------------------------------------- #
    def close(self, timeout: Optional[float] = 30.0) -> None:
        """Drain prefill replicas first (their last requests migrate
        out), then decode replicas, then release placement slots."""
        with self._lock:
            if self._closing:
                return
            self._closing = True
        for eng in self.prefill:
            eng.close(timeout)
        for eng in self.decode:
            eng.close(timeout)
        if self.placement is not None:
            for ename, slot in self._slices.items():
                try:
                    self.placement.release(slot)
                except Exception:
                    log.exception("releasing %s's slot failed", ename)
            self._slices.clear()

    def __enter__(self) -> "DisaggCoordinator":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
