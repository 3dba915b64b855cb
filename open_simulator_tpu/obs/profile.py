"""JAX dispatch / recompile / transfer / cost accounting.

"How many XLA recompiles did this sweep trigger" was previously
unanswerable: the module-level jits in ``scheduler/engine.py``,
``ops/scan.py``, and ``parallel/sweep.py`` compiled (or didn't)
invisibly. This module wraps them in ``InstrumentedJit``, which counts

- ``jax_dispatches_total`` (+ per-site ``jax_dispatches_<site>``):
  every call into a jitted entry point — one device dispatch each;
- ``jax_recompiles_total`` (+ per-site): calls that compiled a new
  executable for this shape/static combination — an ahead-of-time
  cache miss on the AOT path, a grown ``PjitFunction._cache_size`` on
  the fallback path;
- ``device_transfer_d2h_bytes_total`` / ``..._h2d_bytes_total``:
  bytes materialized from / shipped to the device at the few sites
  that do it (engine scan outputs, scenario batches).

Since the compiled-cost observatory (docs/OBSERVABILITY.md), each site
also compiles AHEAD OF TIME: the first call of a shape-signature runs
``jit(...).lower(args).compile()``, extracts ``cost_analysis()`` /
``memory_analysis()`` into the cost registry (obs/costs.py), and
REUSES the compiled artifact for this and every later same-signature
dispatch — cost capture adds zero extra compiles, and the executable
becomes a named object keyed by signature (the first step toward
ROADMAP item 4's persisted compile cache). Calls the AOT path cannot
serve — tracer arguments (this site traced inside an outer jit),
committed/sharded inputs (the multichip mesh path), keyword arguments,
signature-cache overflow, or ``SIMON_AOT=0`` — fall back to the plain
jitted call unchanged. Every dispatch additionally records its
latency into the per-site streaming histogram (obs/histo.py) and
polls the device-memory ledger (obs/ledger.py) so the HBM peak is
observed exactly where it moves.

Everything lands in the existing process-wide ``utils.trace.Counters``
registry, so ``simon serve``'s ``/metrics`` endpoint and the bench
harness report the same numbers with zero extra plumbing. The counters
are always on (one lock + dict-add per DISPATCH, which is rare —
dispatches are per scan round, not per pod), so there is no flag to
forget before asking "did this workload recompile".

The optional ``jax.profiler`` capture (``--profile-dir DIR``, or
SIMON_PROFILE_DIR) is one capture of the whole command, started and
stopped by the CLI (``cli._obs_begin``); every ``utils.trace.phase`` is
an annotation in it.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Optional

from ..runtime import inject as _inject
from ..utils.trace import COUNTERS
from . import spans as _spans
from .costs import COSTS, extract_record
from .histo import HISTOS
from .ledger import LEDGER, _span_boundary

log = logging.getLogger(__name__)

# the ledger's top-level-span watermark frames ride the span recorder's
# boundary hook; installed here (not in ledger.py) because this module
# is the first in the obs import order that may safely touch both
_spans.set_boundary_hook(_span_boundary)

_UNSET = object()


def _aot_enabled() -> bool:
    return os.environ.get("SIMON_AOT", "1") != "0"


def _artifact_store():
    """The armed persistent artifact store, or None. Lazy sibling
    import: the obs package must load without incremental/ (and the
    store is consulted only on the rare compile path)."""
    try:
        from ..incremental.store import current_store
    except ImportError:
        return None
    return current_store()


def _ledger_enabled() -> bool:
    return os.environ.get("SIMON_LEDGER", "1") != "0"


class InstrumentedJit:
    """Wraps a jitted callable with dispatch + compile counters, AOT
    cost capture, per-dispatch latency histograms and (when the span
    recorder is on) a per-dispatch span. Transparent to callers:
    ``__call__`` only."""

    # signature-cache bound: a workload churning through more distinct
    # shapes than this is not warm-cacheable anyway — AOT capture
    # retires for the site rather than growing without bound
    MAX_AOT_SIGNATURES = 128

    __slots__ = (
        "_fn", "name", "_static", "_aot", "_aot_on", "_lock",
        "_lead_argnum",
    )

    def __init__(self, fn, name: str, static_argnums=(), lead_argnum=None):
        self._fn = fn
        self.name = name
        self._static = frozenset(int(i) for i in static_argnums)
        self._lead_argnum = lead_argnum
        # signature -> (compiled, CostRecord), or None (signature
        # retired to the plain path)
        self._aot = {}
        self._aot_on = hasattr(fn, "lower")
        self._lock = threading.Lock()

    def _cache_size(self) -> Optional[int]:
        size = getattr(self._fn, "_cache_size", None)
        if size is None:
            return None
        try:
            return int(size())
        except (TypeError, ValueError):  # non-standard jit wrapper
            return None

    # -- AOT path -----------------------------------------------------------

    def _signature(self, args):
        """Hashable shape-signature of a call, or None when the call
        cannot ride the AOT path (tracers, committed shardings,
        unhashable static leaves)."""
        import jax

        try:
            leaves, treedef = jax.tree_util.tree_flatten(args)
        except Exception:  # noqa: BLE001 - unflattenable args: plain path, never an instrumentation failure
            return None
        sig = []
        for leaf in leaves:
            if isinstance(leaf, jax.core.Tracer):
                # this site is being traced inside an outer jit: the
                # dispatch belongs to the outer executable
                return None
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is not None and dtype is not None:
                if getattr(leaf, "_committed", False):
                    # explicitly placed/sharded input (the multichip
                    # mesh path): the signature would need the sharding
                    # too — stay on the plain jit, which handles it
                    return None
                sig.append(
                    (
                        tuple(shape),
                        str(dtype),
                        bool(getattr(leaf, "weak_type", False)),
                    )
                )
            else:
                sig.append(("static", leaf))
        key = (treedef, tuple(sig))
        try:
            hash(key)
        except TypeError:  # unhashable static leaf
            return None
        return key

    def _lead_dim(self, args) -> int:
        """Row count of the CHUNKED axis for this compile. Sites
        dispatched through guard.run_chunked declare which argument
        carries it (``lead_argnum``) — without that, a site whose
        non-batched arguments have node/pod-sized leading dimensions
        would record those instead, and the cost registry's per-row
        scaling would underestimate chunk workspace by orders of
        magnitude (a chunk of 8 scenarios over 10k nodes is NOT
        8/10000ths of the compiled workspace)."""
        import jax

        search = args
        if self._lead_argnum is not None and self._lead_argnum < len(args):
            search = (args[self._lead_argnum],)
        best = 0
        for leaf in jax.tree_util.tree_leaves(search):
            shape = getattr(leaf, "shape", None)
            if shape:
                best = max(best, int(shape[0]))
        return best

    def _dynamic_args(self, args):
        return [a for i, a in enumerate(args) if i not in self._static]

    def _aot_compile(self, key, args):
        """Lower + compile the signature once, extract its cost/memory
        analysis into the registry, and cache the artifact. Any
        failure retires the signature to the plain path (logged —
        never silent, never fatal). ``_lock`` owns the signature cache
        (`_aot`/`_aot_on`); ``_fn``/``name`` are immutable after
        construction and stay out of the locked region.

        When a persistent artifact store is armed (``--aot-store`` /
        SIMON_AOT_STORE, incremental/store.py), a verified store entry
        is loaded INSTEAD of compiling — the zero-compile cold start:
        the recompile counter does not move, the load is counted
        (``aot_store_hit_total``). Fresh compiles are serialized back
        (outside the lock: the save fsyncs). A rejected/corrupt entry
        was already counted and logged by the store; it lands here as
        a plain compile."""
        fn, name = self._fn, self.name
        with self._lock:
            entry = self._aot.get(key, _UNSET)
        if entry is not _UNSET:
            # raced: another thread already compiled/loaded/retired it —
            # skip the store probe (a second full deserialization would
            # also double-count the hit)
            return entry
        lead_dim = self._lead_dim(args)
        store = _artifact_store()
        loaded = store.load(name, key) if store is not None else None
        to_save = None
        with self._lock:
            entry = self._aot.get(key, _UNSET)
            if entry is not _UNSET:
                return entry  # raced: another thread compiled/retired it
            if len(self._aot) >= self.MAX_AOT_SIGNATURES:
                log.warning(
                    "jit site %s exceeded %d AOT signatures; cost capture "
                    "retired for this site (shape-churning workload)",
                    name, self.MAX_AOT_SIGNATURES,
                )
                self._aot_on = False
                return None
            if loaded is not None:
                compiled, rec = loaded
                COSTS.record(name, key, rec, loaded=True)
                entry = (compiled, rec)
                self._aot[key] = entry
                return entry
            try:
                compiled = fn.lower(*args).compile()
            except Exception as e:  # noqa: BLE001 - AOT is an optimization: any lowering/compile fault falls back to the plain jit call, which surfaces real errors itself
                log.debug(
                    "jit site %s: AOT lower/compile unavailable for this "
                    "signature (%s); falling back to the plain jit path",
                    name, str(e).split("\n", 1)[0][:120],
                )
                self._aot[key] = None
                return None
            COUNTERS.inc("jax_recompiles_total")
            COUNTERS.inc(f"jax_recompiles_{name}")
            rec = extract_record(name, compiled, lead_dim=lead_dim)
            COSTS.record(name, key, rec)
            entry = (compiled, rec)
            self._aot[key] = entry
            to_save = entry
        if store is not None and to_save is not None:
            store.save(name, key, to_save[0], to_save[1])
        return entry

    # -- dispatch -----------------------------------------------------------

    def _dispatch(self, args, kwargs):
        use_aot = False
        if not kwargs and _aot_enabled():
            with self._lock:
                use_aot = self._aot_on
        if use_aot:
            key = self._signature(args)
            if key is not None:
                with self._lock:
                    entry = self._aot.get(key, _UNSET)
                if entry is _UNSET:
                    entry = self._aot_compile(key, args)
                if entry is not None:
                    compiled, rec = entry
                    try:
                        out = compiled(*self._dynamic_args(args))
                    except TypeError as e:
                        # the signature missed a discriminant the
                        # executable is strict about (layout/sharding
                        # drift): retire it and re-dispatch plainly
                        log.warning(
                            "jit site %s: AOT artifact rejected its "
                            "signature (%s); retiring to the plain path",
                            self.name, str(e).split("\n", 1)[0][:120],
                        )
                        with self._lock:
                            self._aot[key] = None
                    else:
                        COSTS.on_dispatch(rec)
                        return out
        before = self._cache_size()
        out = self._fn(*args, **kwargs)
        after = self._cache_size()
        if before is not None and after is not None and after > before:
            COUNTERS.inc("jax_recompiles_total", after - before)
            COUNTERS.inc(f"jax_recompiles_{self.name}", after - before)
        return out

    def __call__(self, *args, **kwargs):
        COUNTERS.inc("jax_dispatches_total")
        COUNTERS.inc(f"jax_dispatches_{self.name}")
        # chaos seam: `jit.<site>` raises the configured device fault
        # at the Nth dispatch of this site — the raw RuntimeError
        # shapes the guard ladder classifies (runtime/inject.py)
        _inject.fire(f"jit.{self.name}")
        from .spans import RECORDER

        t0 = time.perf_counter()
        try:
            if RECORDER.enabled:
                with RECORDER.span(f"jit/{self.name}", site=self.name):
                    out = self._dispatch(args, kwargs)
            else:
                out = self._dispatch(args, kwargs)
        finally:
            HISTOS.observe(f"jit/{self.name}", time.perf_counter() - t0)
            if _ledger_enabled():
                LEDGER.poll()
        return out


def instrument_jit(
    fn, name: str, static_argnums=(), lead_argnum=None
) -> InstrumentedJit:
    """Wrap a jitted function for dispatch/recompile/cost accounting.
    ``static_argnums`` must mirror the wrapped jit's own (the AOT
    artifact is called with the dynamic arguments only).
    ``lead_argnum`` names the argument whose leading dimension is the
    chunked/batched-scenario axis — required for sites driven through
    ``guard.run_chunked`` so the cost registry's per-row estimates
    scale by the right axis. Safe to apply to anything callable; AOT
    capture and cache-miss detection degrade gracefully when the
    wrapper exposes no ``lower``/``_cache_size``."""
    return InstrumentedJit(
        fn, name, static_argnums=static_argnums, lead_argnum=lead_argnum
    )


# ------------------------------------------------------ transfer gauges


def record_d2h(nbytes: int) -> None:
    """Bytes materialized host-side from device outputs (np.asarray of
    placements and friends)."""
    COUNTERS.inc("device_transfer_d2h_bytes_total", int(nbytes))
    COUNTERS.gauge("device_transfer_d2h_last_bytes", float(nbytes))


def record_h2d(nbytes: int) -> None:
    """Bytes shipped device-wards (encoded batches, scenario masks)."""
    COUNTERS.inc("device_transfer_h2d_bytes_total", int(nbytes))
    COUNTERS.gauge("device_transfer_h2d_last_bytes", float(nbytes))


def nbytes_of(*arrays) -> int:
    """Total nbytes of numpy/jax arrays (anything exposing .nbytes);
    non-arrays count zero — callers pass whatever they just moved."""
    total = 0
    for a in arrays:
        nb = getattr(a, "nbytes", None)
        if isinstance(nb, int):
            total += nb
    return total


# ------------------------------------------------------ snapshot helpers


_KEYS = (
    "jax_dispatches_total",
    "jax_recompiles_total",
    "device_transfer_d2h_bytes_total",
    "device_transfer_h2d_bytes_total",
)


def snapshot() -> dict:
    """Current values of the headline profiling counters."""
    return {k: COUNTERS.get(k) for k in _KEYS}


def delta(since: dict) -> dict:
    """Counter movement since a previous ``snapshot()`` — the bench
    harness stamps each scenario's dispatch/recompile cost with this."""
    now = snapshot()
    return {k: now[k] - since.get(k, 0) for k in _KEYS}
