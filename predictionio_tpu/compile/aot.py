"""AOT executable registry: compile at deploy time, dispatch without
tracing at serve time.

The SNIPPETS.md [1] ``Lowered`` -> ``.lower().compile()`` path, made a
subsystem. An **executable spec** is a builder that, given a bucket-dim
dict (``{"u": 1024, "i": 2048, "b": 16, "k": 16, "r": 10, "p": 1}``),
returns ``(jit_fn, example_args, static_kwargs)``; the registry lowers
and compiles it once per bucket and holds the resulting
``jax.Compiled``. Output avals are whatever the builder's program
emits — the readback plane (ISSUE 19) leans on this: packed buckets
(``p`` > 0) compile programs whose ONE output is the contiguous
ids+quantized-scores payload, so steady-state packing costs zero
serve-time compiles exactly like every other warmed bucket.
A warmed dispatch site then calls the held executable DIRECTLY — zero
Python re-trace, zero XLA compile, zero jit-cache probe on the request
path. Unwarmed buckets fall back to the plain jitted function (whose
compile the persistent cache answers across processes) and schedule a
background adoption so the next request hits.

The registry is also the process's **cached-jit surface**
(``shared_jit``): hot-path modules resolve their jitted helpers here
instead of module-local ``_jits`` dicts, which is the idiom the JAX003/
JAX005 lint rules recognize as compile-plane-routed.

Instrumentation (obs registry):

- a compile here runs under ``costmon.executable(label, bucket=...)``, so
  its trace, lowering, compile or cache-load seconds land in
  ``pio_compile_seconds_total{executable,stage}`` and its record in the
  set-up account with the bucket (obs/costmon);
- ``pio_aot_dispatch_hits_total{executable}`` /
  ``..._misses_total`` / ``..._fallbacks_total`` — warmed vs unwarmed
  vs aval-mismatch dispatches;
- ``pio_aot_executables_resident`` — held Compiled count.

``PIO_AOT=off`` turns every dispatch into the fallback call.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from predictionio_tpu.compile.buckets import (bucket_key, bucket_label,
                                              bucket_table_rows_sharded,
                                              should_promote_table)
from predictionio_tpu.obs.costmon import device_timed

logger = logging.getLogger(__name__)


def aot_enabled() -> bool:
    return os.environ.get("PIO_AOT", "").lower() not in (
        "off", "0", "false", "no")


class AOTRegistry:
    """Process-wide registry of AOT-compiled executables, keyed by
    (label, bucket). Thread-safe; compiles happen OUTSIDE the lock (an
    XLA compile may take minutes on TPU — holding the lock would stall
    every dispatch)."""

    def __init__(self, registry=None):
        self._lock = threading.RLock()
        self._builders: Dict[str, Callable] = {}
        self._compiled: Dict[Tuple[str, tuple], Any] = {}
        #: key -> Event set when that key's compile finishes (blocking
        #: callers racing a background compile wait on it)
        self._inflight: Dict[Tuple[str, tuple], threading.Event] = {}
        #: buckets whose compile failed — never retried this process
        #: (a reliably-failing spec would otherwise respawn a minutes-
        #: long XLA compile on every dispatch miss); the jit fallback
        #: keeps serving them. A deploy-time warm reports them as
        #: ``failed`` and the deploy fails (serving/server.py).
        self._failed: set = set()
        self._threads: set = set()
        self._jits: Dict[str, Any] = {}
        if registry is None:
            from predictionio_tpu.obs import get_registry
            registry = get_registry()
        self._c_hits = registry.counter(
            "pio_aot_dispatch_hits_total",
            "dispatches answered by a held AOT executable (no trace, "
            "no compile)", labelnames=("executable",))
        self._c_misses = registry.counter(
            "pio_aot_dispatch_misses_total",
            "dispatches for a bucket with no held executable (served "
            "by the jit fallback; background adoption scheduled)",
            labelnames=("executable",))
        self._c_fallbacks = registry.counter(
            "pio_aot_dispatch_fallbacks_total",
            "held-executable calls rejected on argument avals and "
            "re-served by the jit fallback", labelnames=("executable",))
        # NOTE: the resident-count gauge is registered by get_aot() for
        # the process singleton only — gauge_func is first-registration-
        # wins and a strong closure here would pin whichever instance
        # (a test's throwaway registry) registered first, plus every
        # device executable it holds (the flight-source/incident-
        # provider weakref lesson from ISSUE 6)

    # -- specs --------------------------------------------------------------
    def register(self, label: str, builder: Callable) -> None:
        """``builder(**dims) -> (jit_fn, example_args, static_kwargs)``.
        Re-registration replaces (module reload); held executables for
        the label are kept — they were built from the same source."""
        with self._lock:
            self._builders[label] = builder

    def has_spec(self, label: str) -> bool:
        with self._lock:
            return label in self._builders

    # -- compile ------------------------------------------------------------
    def ensure(self, label: str, dims: Dict[str, int],
               background: bool = False) -> Optional[Any]:
        """Compile (label, bucket) if absent. Blocking by default —
        deploy/swap warming wants the executable held before traffic.
        ``background=True`` returns immediately and adopts the
        executable when the daemon thread finishes."""
        if not aot_enabled():
            return None
        key = (label, bucket_key(dims))
        with self._lock:
            if key in self._compiled:
                return self._compiled[key]
            if key in self._failed:
                return None
            builder = self._builders.get(label)
            if builder is None:
                return None
            pending = self._inflight.get(key)
            if pending is None:
                self._inflight[key] = threading.Event()
        if pending is not None:
            # another thread (e.g. a background promotion) is already
            # compiling this bucket: a blocking caller — a deploy/swap
            # warm whose contract is executable-before-traffic — must
            # WAIT for it, not silently skip the bucket
            if not background:
                pending.wait(timeout=600.0)
                return self._compiled.get(key)
            return None
        if background:
            t = threading.Thread(
                target=self._compile_one, args=(label, dims, key),
                name=f"pio-aot-{label}", daemon=True)
            with self._lock:
                self._threads.add(t)
            t.start()
            return None
        return self._compile_one(label, dims, key)

    def _compile_one(self, label, dims, key):
        from predictionio_tpu.obs import costmon
        try:
            builder = self._builders[label]
            fn, args, statics = builder(**dims)
            # compile attribution: the AOT warm IS this executable's
            # compile — charge its label and bucket, and let the
            # persistent cache answer it when a previous process paid
            with costmon.executable(label, bucket=bucket_label(dims)):
                compiled = fn.lower(*args, **(statics or {})).compile()
            with self._lock:
                self._compiled[key] = compiled
            return compiled
        except Exception:
            with self._lock:
                self._failed.add(key)
            logger.warning("AOT compile of %s %s failed; bucket "
                           "memoized as failed — dispatches fall back "
                           "to jit for this process", label, dims,
                           exc_info=True)
            return None
        finally:
            with self._lock:
                ev = self._inflight.pop(key, None)
                self._threads.discard(threading.current_thread())
            if ev is not None:
                ev.set()

    # -- dispatch -----------------------------------------------------------
    def lookup(self, label: str, dims: Dict[str, int]) -> Optional[Any]:
        return self._compiled.get((label, bucket_key(dims)))

    def dispatch(self, label: str, dims: Dict[str, int],
                 fallback: Callable, *args):
        """Serve-path dispatch: the held executable when the bucket is
        warm (zero trace/compile), else the jit ``fallback`` — whose
        compile the persistent cache covers — plus a background
        adoption so the NEXT request in this bucket hits.

        Every dispatch — held executable and fallback alike — runs
        under ``costmon.device_timed`` (ISSUE 11): dispatch wall is
        counted per request and a 1-in-N sampled sync books true
        device seconds to ``pio_device_time_seconds_total{label}``."""
        if not aot_enabled():
            return self._fallback(label, fallback, args)
        compiled = self._compiled.get((label, bucket_key(dims)))
        if compiled is not None:
            try:
                out = device_timed(label, compiled, *args)
                self._c_hits.labels(executable=label).inc()
                return out
            except TypeError:
                # argument avals drifted off the bucket contract (a
                # caller bug or a dtype surprise): serve correctly via
                # the fallback and make the drift countable
                self._c_fallbacks.labels(executable=label).inc()
                logger.debug("AOT %s %s aval mismatch; fallback",
                             label, dims, exc_info=True)
        else:
            self._c_misses.labels(executable=label).inc()
            self.ensure(label, dims, background=True)
        return self._fallback(label, fallback, args)

    @staticmethod
    def _fallback(label: str, fallback: Callable, args):
        """The jit fallback, whose compile (a bucket not yet held) books
        under ``label`` unless a caller's scope already names it."""
        from predictionio_tpu.obs import costmon
        with costmon.executable(label, defer_to_outer=True):
            return device_timed(label, fallback, *args)

    # -- shared cached-jit surface ------------------------------------------
    def adopt(self, key: str, fn) -> Any:
        """Adopt an externally-built jitted callable into the shared-
        jit table (first adoption wins; later adopters get the resident
        instance) — the cached-jit idiom JAX003 recognizes."""
        with self._lock:
            return self._jits.setdefault(key, fn)

    def shared_jit(self, key: str, impl: Callable, **jit_kwargs):
        """Process-wide memoized ``jax.jit`` construction: hot-path
        modules resolve their jitted helpers from the compile plane
        instead of private ``_jits`` dicts, so the registry can report
        them and the lint rules can recognize the idiom. One jit per
        key for the process lifetime."""
        fn = self._jits.get(key)
        if fn is None:
            with self._lock:
                fn = self._jits.get(key)
                if fn is None:
                    import jax
                    fn = jax.jit(impl, **jit_kwargs)
                    self._jits[key] = fn
        return fn

    # -- warming ------------------------------------------------------------
    def warm(self, specs: Iterable[Tuple[str, Dict[str, int]]],
             background: bool = False) -> Dict[str, Any]:
        """Compile every (label, dims) in ``specs``; returns a summary
        the caller can log/record. Blocking unless ``background``."""
        t0 = time.perf_counter()
        compiled = skipped = failed = 0
        for label, dims in specs:
            if not self.has_spec(label):
                skipped += 1
                continue
            before = self.lookup(label, dims) is not None
            self.ensure(label, dims, background=background)
            if not before and self.lookup(label, dims) is not None:
                compiled += 1
            elif (label, bucket_key(dims)) in self._failed:
                failed += 1
        return {"compiled": compiled, "skipped": skipped,
                "failed": failed,
                "wallS": round(time.perf_counter() - t0, 4)}

    # -- introspection ------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        """Registry state for /stats.json and `pio status --telemetry`:
        executables resident, buckets compiled per label, jit handles,
        the compile requests and seconds of the labels it has specs for
        (from obs/costmon's family, every stage: jit fallbacks and
        promotions included), dispatch hit/miss counts since start."""
        from predictionio_tpu.obs import costmon

        def _vals(counter):
            return {labels["executable"]: v
                    for labels, v in counter.samples() if labels}

        with self._lock:
            by_label: Dict[str, List[str]] = {}
            for (label, key) in self._compiled:
                by_label.setdefault(label, []).append(
                    "-".join(f"{k}{v}" for k, v in key))
            out = {
                "enabled": aot_enabled(),
                "executablesResident": len(self._compiled),
                "bucketsCompiled": {k: sorted(v)
                                    for k, v in sorted(by_label.items())},
                "sharedJits": sorted(self._jits),
                "inflight": len(self._inflight),
                "failedBuckets": len(self._failed),
            }
        hits, misses = _vals(self._c_hits), _vals(self._c_misses)
        out["dispatchHits"] = hits
        out["dispatchMisses"] = misses
        out["dispatchFallbacks"] = _vals(self._c_fallbacks)
        total = sum(hits.values()) + sum(misses.values())
        out["hitRate"] = (round(sum(hits.values()) / total, 4)
                          if total else None)
        out["pcache"] = costmon.pcache_totals()
        labels = set(self._builders)
        requests = costmon.compile_requests()
        out["compileCount"] = int(sum(
            n for by in requests.values()
            for label, n in by.items() if label in labels))
        out["compileSeconds"] = round(sum(
            sum(st.values()) for label, st in
            costmon.compile_stage_seconds().items() if label in labels), 4)
        return out

    def clear(self) -> None:
        with self._lock:
            self._compiled.clear()
            self._jits.clear()
            self._failed.clear()

    def shutdown(self, join_timeout_s: float = 15.0) -> None:
        """Quiesce for interpreter exit: wait out in-flight background
        compiles (a daemon thread killed mid-XLA-compile aborts the
        process), then release the held executables (destructing them
        after the jax backend tears down segfaults)."""
        with self._lock:
            threads = list(self._threads)
        for t in threads:
            try:
                t.join(timeout=join_timeout_s)
            except Exception:
                pass
        self.clear()


_registry_lock = threading.Lock()
_registry: Optional[AOTRegistry] = None


def _drop_executables_at_exit():
    # held Compiled objects must be released (and in-flight background
    # compiles joined) BEFORE the jax backend tears down: a Compiled
    # destructed after the runtime is gone, or a daemon compile thread
    # killed mid-XLA, takes the process down at exit. atexit runs
    # pre-finalization, before jax's own handlers unwind.
    try:
        if _registry is not None:
            _registry.shutdown()
    except Exception:
        pass


def get_aot() -> AOTRegistry:
    global _registry
    if _registry is None:
        with _registry_lock:
            if _registry is None:
                _registry = AOTRegistry()
                try:
                    from predictionio_tpu.obs import get_registry
                    get_registry().gauge_func(
                        "pio_aot_executables_resident",
                        "AOT-compiled executables currently held by "
                        "the process registry",
                        lambda: float(len(_registry._compiled))
                        if _registry is not None else 0.0)
                except Exception:
                    logger.debug("aot gauge unavailable", exc_info=True)
                import atexit
                atexit.register(_drop_executables_at_exit)
    return _registry


def precompile_next_rung(label: str, dims: Dict[str, int], dim: str,
                         n: int) -> None:
    """Bucket promotion: a resident table of ``n`` live rows nearing
    its rung ``dims[dim]`` pre-compiles ``label``'s executable at the
    next rung in the background, BEFORE growth needs it (a sharded
    layout's ``s`` dim keeps the rung on a multiple of the shards)."""
    if should_promote_table(n, dims[dim]):
        nxt = bucket_table_rows_sharded(dims[dim] + 1, dims.get("s", 1))
        get_aot().ensure(label, dict(dims, **{dim: nxt}), background=True)


def shared_jit(key: str, impl: Callable, **jit_kwargs):
    """Module-level convenience for :meth:`AOTRegistry.shared_jit`."""
    return get_aot().shared_jit(key, impl, **jit_kwargs)


def sharded_aval(shape, dtype, *axes, mesh=None):
    """A ``ShapeDtypeStruct`` carrying a ``NamedSharding`` over the
    (current) mesh — the sharding-aware aval sharded spec builders
    lower with, so the bucket ladder and swap-time warmup cover the
    model-sharded serve executables exactly like the replicated ones
    (an aval without a sharding would lower a single-device program
    and the held executable would reject every sharded argument).
    ``axes`` is the per-dim mesh axis name (or None), e.g.
    ``sharded_aval((i, r), np.float32, "model", None)``."""
    import jax
    from predictionio_tpu.parallel.mesh import current_mesh
    ctx = mesh or current_mesh()
    return jax.ShapeDtypeStruct(
        shape, dtype,
        sharding=jax.sharding.NamedSharding(
            ctx.mesh, jax.sharding.PartitionSpec(*axes)))


def warm_enabled() -> bool:
    """Deploy/swap-time warming can be disabled separately from AOT
    dispatch (``PIO_AOT_WARM=off``): dispatch + background adoption
    keep working, but model changes stop pre-compiling the bucket
    ladder — the hermetic test suite uses this (dozens of server
    fixtures would each pay the ladder), production never should."""
    return os.environ.get("PIO_AOT_WARM", "").lower() not in (
        "off", "0", "false", "no")


def warm_models(algorithms, models, batch_hint: int = 16,
                background: bool = False) -> Dict[str, Any]:
    """Warm the serving executables for a (algorithms, models) pair —
    the deploy/hot-swap/canary hook. Each algorithm exposing
    ``aot_warm_specs(model, batch_hint)`` contributes (label, dims)
    rows. Nothing here raises (a warm failure must never block a swap
    — the fallback path still serves); what did not compile is counted
    in ``failed``, which the deploy-time load treats as fatal."""
    if not aot_enabled() or not warm_enabled():
        return {"compiled": 0, "skipped": 0, "failed": 0, "wallS": 0.0,
                "disabled": True}
    from predictionio_tpu.compile.cache import enable_persistent_cache
    enable_persistent_cache()
    specs: List[Tuple[str, Dict[str, int]]] = []
    hook_failures = 0
    for algo, model in zip(algorithms, models):
        hook = getattr(algo, "aot_warm_specs", None)
        if hook is None:
            continue
        try:
            specs.extend(hook(model, batch_hint))
        except Exception:
            hook_failures += 1
            logger.warning("aot_warm_specs failed for %s",
                           type(algo).__name__, exc_info=True)
    out = get_aot().warm(specs, background=background)
    out["failed"] += hook_failures
    out["specs"] = len(specs)
    return out
