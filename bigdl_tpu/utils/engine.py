"""Engine: topology discovery and runtime configuration.

TPU-native rebuild of the reference's ``utils/Engine.scala`` (84-445).  The
reference derives (nodeNumber, coresPerNode) from the Spark conf and runs
``coresPerNode`` thread-replicas per executor, each pinned to one MKL thread.
On TPU the mapping is:

    one Spark executor ("node")      -> one JAX process (host)
    one core-thread model replica    -> one TPU chip (one mesh slot)
    Engine.init / checkSingleton     -> jax.distributed.initialize + device
                                        enumeration (one process owns the
                                        host's chips)
    Engine.default / Engine.model    -> host thread pool for the input
                                        pipeline; on-device parallelism is
                                        XLA's job.

There are no thread-replica semantics to reproduce on device: XLA batches
natively, so ``core_number`` counts *local devices*, not threads.
"""
from __future__ import annotations

import os
import threading
from concurrent.futures import Future, ThreadPoolExecutor
from concurrent.futures import TimeoutError as FuturesTimeoutError
from typing import Callable, Optional, Sequence


class ThreadPool:
    """Host-side task pool (ref utils/ThreadPool.scala:92-168).

    Used by the data pipeline for threaded prefetch/decode, the role
    ``Engine.default`` played for the reference's coarse host tasks.  The
    straggler-timeout variant ``invoke_and_wait2`` is kept for API parity,
    though under SPMD lockstep on TPU it only gates *host* work.
    """

    def __init__(self, size: int):
        self._size = size
        self._pool = ThreadPoolExecutor(max_workers=size, thread_name_prefix="bigdl-tpu")

    @property
    def size(self) -> int:
        return self._size

    def invoke(self, tasks: Sequence[Callable]) -> list[Future]:
        return [self._pool.submit(t) for t in tasks]

    def invoke_and_wait(self, tasks: Sequence[Callable]) -> list:
        return [f.result() for f in self.invoke(tasks)]

    def invoke_and_wait2(self, tasks: Sequence[Callable], timeout: Optional[float] = None) -> list[Future]:
        """Submit all tasks, wait up to ``timeout`` seconds; returns futures
        (some possibly unfinished — the caller decides what to drop).

        Only *timeouts* are swallowed (that is the straggler-drop
        semantic); a task that raised re-raises here after every other
        task has been waited on — a worker dying with a real error is a
        bug, not a straggler (the reference distinguishes the two the
        same way: invokeAll returns, then Future.get rethrows)."""
        futures = self.invoke(tasks)
        first_error: Optional[Exception] = None
        for f in futures:
            try:
                f.result(timeout=timeout)
            except FuturesTimeoutError:
                pass  # straggler: caller inspects f.done() and drops it
            except Exception as e:  # task failure (KeyboardInterrupt et al.
                # propagate immediately — don't hold Ctrl-C hostage)
                if first_error is None:
                    first_error = e
        if first_error is not None:
            raise first_error
        return futures

    def sync(self, futures: Sequence[Future]) -> None:
        for f in futures:
            f.result()

    def shutdown(self) -> None:
        self._pool.shutdown(wait=False)


class _EngineState:
    def __init__(self):
        self.initialized = False
        self.node_number = 1
        self.core_number = 1
        self.default_pool: Optional[ThreadPool] = None
        self.model_pool: Optional[ThreadPool] = None
        self.lock = threading.Lock()
        self.singleton_claimed = False


_state = _EngineState()


def ensure_virtual_devices(n: int):
    """CPU rehearsal helper: return ``n`` VIRTUAL CPU devices (the analog
    of the reference's simulated-multinode trick: DistriOptimizerSpec
    runs 4 "nodes" as 4 partitions in one local[1] JVM,
    optim/DistriOptimizerSpec.scala:39-43).  It never hands back
    accelerator devices and never initialises an accelerator backend: a
    multi-chip path that wants real chips builds its mesh from
    ``jax.devices()`` and fails there if the host has too few.

    ``--xla_force_host_platform_device_count`` only takes effect if set
    before the first backend initialisation in the process, hence the env
    mutation before any ``jax.devices()`` call.  Used by the driver's
    ``dryrun_multichip`` and the perf scaling sweep."""
    import re

    want = max(8, n)
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None or int(m.group(1)) < want:
        if m is not None:
            flags = flags.replace(m.group(0), "")
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={want}").strip()
    import jax
    from jax._src import xla_bridge as _xb

    if (not _xb.backends_are_initialized()
            and str(jax.config.jax_platforms or "") != "cpu"):
        # First backend use in the process: select the cpu platform
        # outright so the rehearsal never claims (or waits for) a chip.
        # The pin is process-global; release_virtual_devices() undoes it
        # for callers that later want the real accelerator.
        global _pin_active, _pinned_prior_platforms
        _pin_active = True
        _pinned_prior_platforms = jax.config.jax_platforms
        jax.config.update("jax_platforms", "cpu")

    try:
        devices = list(jax.devices("cpu"))
    except RuntimeError as e:
        raise RuntimeError(
            f"need {n} virtual devices and the cpu backend is unavailable "
            f"— a jax backend was initialised before this call, so "
            f"XLA_FLAGS was set too late; restart and request the virtual "
            f"devices before any other jax use.") from e
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices; have {len(devices)} CPU virtual devices. "
            f"If a jax backend was initialised before this call, XLA_FLAGS "
            f"was set too late — restart and request the virtual devices "
            f"before any other jax use.")
    return devices[:n]


_pin_active = False
_pinned_prior_platforms = None


#: the checkout that holds this package: the compile cache's fixed home
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it, nothing is
    touched.  Unset: ``<checkout>/.jax_cache`` — a FIXED, git-ignored
    path (the directory is part of the cache key's lookup, so a path
    with a pid, a time or a temp dir in it never hits).  Every entry
    point that compiles calls this once (Engine.init, chip_smoke.py,
    the serving engines); it is idempotent."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def release_virtual_devices() -> None:
    """Undo ``ensure_virtual_devices``' process-global cpu-platform pin:
    restore the prior ``jax_platforms`` setting and clear the cached
    backend set, so the next ``jax.devices()`` re-reads it and real
    accelerators become visible again.  Arrays created on the virtual
    pool keep referencing their (now un-cached) cpu client and stay
    readable — the same contract the jax ``clear_backends`` API gives.
    No-op when nothing was pinned."""
    global _pin_active, _pinned_prior_platforms
    if not _pin_active:
        return
    import jax
    from jax.extend.backend import clear_backends

    jax.config.update("jax_platforms", _pinned_prior_platforms)
    _pin_active = False
    _pinned_prior_platforms = None
    clear_backends()


class Engine:
    """Singleton runtime facade (ref utils/Engine.scala:84-99,142-146)."""

    @staticmethod
    def init(node_number: Optional[int] = None,
             core_number: Optional[int] = None) -> None:
        """Discover topology.  With no args: local mode uses the current
        process's devices (ref Engine.init no-arg, utils/Engine.scala:84-99);
        in a multi-host job call ``jax.distributed.initialize`` first (the
        analog of launching on Spark) and Engine picks up process/device
        counts from JAX.

        The JAX platform is whatever ``JAX_PLATFORMS`` says (``cpu`` for
        tests and rehearsals; unset on a machine with a chip).
        """
        import jax

        configure_compile_cache()
        # resilience hook: simulate the classic failure where the
        # backend never answers the first jax.devices() touch
        from bigdl_tpu.resilience.faults import fault_point
        fault_point("engine.init")

        with _state.lock:
            if node_number is None:
                node_number = jax.process_count()
            if core_number is None:
                if os.environ.get("DL_CORE_NUMBER"):
                    core_number = int(os.environ["DL_CORE_NUMBER"])
                else:
                    core_number = jax.local_device_count()
            _state.node_number = node_number
            _state.core_number = core_number
            host_threads = int(os.environ.get("BIGDL_TPU_DEFAULT_POOL_SIZE", str(max(os.cpu_count() or 4, 4))))
            if _state.default_pool is None:
                _state.default_pool = ThreadPool(host_threads)
            if _state.model_pool is None:
                _state.model_pool = ThreadPool(core_number)
            _state.initialized = True

    @staticmethod
    def node_number() -> int:
        Engine._require_init()
        return _state.node_number

    @staticmethod
    def core_number() -> int:
        Engine._require_init()
        return _state.core_number

    @staticmethod
    def default() -> ThreadPool:
        Engine._require_init()
        return _state.default_pool  # type: ignore[return-value]

    @staticmethod
    def default_or_create(size: Optional[int] = None) -> ThreadPool:
        """The shared host pool, created lazily if Engine.init has not
        run yet.  Serving and other host-side consumers reuse ONE pool
        per process instead of each spinning a private executor; a
        later Engine.init adopts the same pool (init only fills the
        slot when empty)."""
        with _state.lock:
            if _state.default_pool is None:
                host_threads = size or int(os.environ.get(
                    "BIGDL_TPU_DEFAULT_POOL_SIZE",
                    str(max(os.cpu_count() or 4, 4))))
                _state.default_pool = ThreadPool(host_threads)
            return _state.default_pool

    @staticmethod
    def model() -> ThreadPool:
        Engine._require_init()
        return _state.model_pool  # type: ignore[return-value]

    @staticmethod
    def check_singleton() -> bool:
        """Atomic guard: only one Engine owner per process (ref
        utils/Engine.scala:164-174 — one BigDL task per executor JVM; here,
        one trainer per process, since the process owns the host's TPUs)."""
        if os.environ.get("BIGDL_TPU_CHECK_SINGLETON", "1") in ("0", "false"):
            return True
        with _state.lock:
            if _state.singleton_claimed:
                return False
            _state.singleton_claimed = True
            return True

    @staticmethod
    def diagnose_tpu() -> str:
        """Report processes that look like stale TPU holders — the wedge
        where a dead trainer keeps the chip claimed and every new backend
        init hangs or returns UNAVAILABLE until the holder is reaped
        (the single-chip analog of the reference's checkSingleton guard:
        utils/Engine.scala:164-174 prevents two tasks sharing an
        executor; here two processes sharing a chip).  Pure /proc scan —
        never touches the jax backend, so it is safe to call while the
        chip is wedged."""
        notes = []
        lockfile = "/tmp/libtpu_lockfile"
        if os.path.exists(lockfile):
            notes.append(f"{lockfile} exists")
        me = os.getpid()
        try:
            for pid in os.listdir("/proc"):
                if not pid.isdigit() or int(pid) == me:
                    continue
                try:
                    with open(f"/proc/{pid}/cmdline", "rb") as f:
                        cmd = f.read().replace(b"\0", b" ").decode(
                            errors="replace")
                    with open(f"/proc/{pid}/maps", "r",
                              errors="replace") as f:
                        maps = f.read()
                except OSError:
                    continue
                if cmd and ("libtpu" in maps or "accel" in maps):
                    notes.append(f"pid {pid} holds libtpu: {cmd[:120]}")
        except OSError:
            pass
        notes.extend(Engine._diagnose_memory())
        return "; ".join(notes) if notes else "no stale TPU holder found"

    @staticmethod
    def _diagnose_memory() -> list:
        """Memory-ledger capacity state for stall/flight dumps.  Reads
        only the ledger's host-side totals and its LAST reconcile
        verdict — never the jax backend (this report must stay safe to
        produce while the chip is wedged)."""
        try:
            from bigdl_tpu.obs.ledger import get_ledger
            s = get_ledger().summary()
        except Exception:
            return []
        if not s["entries"] and not s["executables"]:
            return []   # nothing registered: keep the report terse
        last = s.get("last_reconcile") or {}
        drift = last.get("drift_bytes")
        verdict = last.get("verdict", "never_run")
        return [f"memory: ledger={s['ledger_bytes']}B across "
                f"{s['subsystems']} subsystems, "
                f"{s['executables']} executables, "
                f"drift={drift if drift is not None else 'n/a'} "
                f"({verdict})"]

    @staticmethod
    def reset() -> None:
        """Test hook: clear init + singleton state."""
        with _state.lock:
            _state.initialized = False
            _state.singleton_claimed = False
            _state.node_number = 1
            _state.core_number = 1

    @staticmethod
    def is_initialized() -> bool:
        return _state.initialized

    @staticmethod
    def _require_init() -> None:
        if not _state.initialized:
            raise RuntimeError(
                "Engine.init() must be called before use. In a multi-host job, "
                "call jax.distributed.initialize() first."
            )
