"""Unified degradation ladder for device work.

Every device call site — capacity probes, the batched sweep, chaos
scenario batches, defrag depth scans, the what-if multi-spec driver —
routes through this module instead of carrying its own retry logic
(the PR-1 halving machinery lived inside parallel/sweep.py; promoted
here so every path shares one audited ladder).

Engine ladder, in downgrade order (docs/ROBUSTNESS.md):

1. ``pallas`` — the fused single-kernel fast path (ops/pallas_scan.py)
2. ``pallas-stream`` — same kernel with HBM-streamed term state; the
   downgrade happens at plan-build time (build_plan auto-rewrites when
   the resident state exceeds the VMEM budget) and is trace-noted by
   fallback_reason()
3. ``xla-scan`` — the vmapped masked lax.scan
4. ``serial-oracle`` — the deterministic host oracle, always correct,
   never OOMs

Error-driven downgrades (run_laddered) and chunk-halving retries
(run_chunked) react to the classified taxonomy (runtime/errors.py):
``DeviceOOM`` halves the batch before falling to the next rung,
``CompileFailure`` / ``BackendUnavailable`` skip straight down (a
smaller batch would hit the same compiler/backend wall). Every
downgrade is trace-noted with its reason and logged — no silent paths.
Errors that classify to nothing propagate untouched: a shape bug must
stay loud.
"""

from __future__ import annotations

import logging
from typing import Callable, List, Optional, Sequence, Tuple

from .errors import (
    BackendUnavailable,
    CompileFailure,
    DeviceOOM,
    ExecutionHalted,
)

LADDER = ("pallas", "pallas-stream", "xla-scan", "serial-oracle")

# test hook: callable(chunk_len) invoked before each device chunk is
# evaluated; tests make it raise fake device errors to exercise the
# halving-retry / ladder-downgrade paths without real hardware faults
_OOM_INJECT = None

log = logging.getLogger(__name__)


def is_oom(e: BaseException) -> bool:
    """Device-memory exhaustion, as XLA reports it (XlaRuntimeError is
    a RuntimeError whose message carries the RESOURCE_EXHAUSTED status
    code; some backends phrase it as an allocation failure)."""
    if isinstance(e, (MemoryError, DeviceOOM)):
        return True
    msg = str(e)
    return "RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower()


def classify_device_error(e: BaseException):
    """Map a raw device-side exception onto the taxonomy. Returns the
    taxonomy CLASS (DeviceOOM / CompileFailure / BackendUnavailable)
    or None when the error is not a recognized device fault and must
    propagate unchanged."""
    if isinstance(e, (DeviceOOM, CompileFailure, BackendUnavailable)):
        return type(e)
    if isinstance(e, MemoryError):
        return DeviceOOM
    if not isinstance(e, (RuntimeError, OSError)):
        return None
    msg = str(e)
    if "RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower():
        return DeviceOOM
    low = msg.lower()
    if "mosaic" in low or "compilation" in low or "lowering" in low:
        return CompileFailure
    if (
        "UNAVAILABLE" in msg
        or "failed to initialize" in low
        or "backend" in low
        and "not found" in low
    ):
        return BackendUnavailable
    return None


def _reason(e: BaseException) -> str:
    return str(e).split("\n", 1)[0][:120]


def note_downgrade(label: str, frm: str, to: str, reason: str, trace=None):
    """Record one ladder downgrade: trace note + warning log. Callers
    downgrade THROUGH this so every degradation carries its reason."""
    from ..utils.trace import GLOBAL

    (trace or GLOBAL).append_note(
        f"{label}-downgrade", f"{frm} -> {to}: {reason}"
    )
    log.warning("%s: downgrading %s -> %s (%s)", label, frm, to, reason)


#: counters the ladder bumps when it degrades or predicts it must
GUARD_COUNTERS = (
    "guard_oom_reactive_total",
    "guard_oom_predicted_total",
    "guard_rung_predicted_skips_total",
)


def degradations(trace=None) -> dict:
    """Every degradation on record: the ``*-downgrade`` /
    ``*-serial-fallback`` notes of the trace (since its last reset) and
    the nonzero guard counters. Empty on a clean run — the chip smoke and the bench
    fail on anything here, so a kernel the chip refuses can never pass
    as a slow success."""
    from ..utils.trace import COUNTERS, GLOBAL

    notes = (trace or GLOBAL).notes
    found = {
        k: v for k, v in notes.items()
        if k.endswith("-downgrade") or k.endswith("-serial-fallback")
    }
    found.update(
        {k: COUNTERS.get(k) for k in GUARD_COUNTERS if COUNTERS.get(k)}
    )
    return found


def try_downgrade(e: BaseException, *, label: str, frm: str, to: str,
                  trace=None) -> bool:
    """One-rung downgrade for call sites that hold their own fallback
    path (defrag's XLA branch, the what-if driver's per-spec probe):
    when `e` classifies as a device fault, trace-note the downgrade and
    return True (caller switches rungs); else return False (caller
    re-raises — the error is a real bug, not a degradation)."""
    if classify_device_error(e) is None:
        return False
    note_downgrade(label, frm, to, _reason(e), trace)
    return True


def run_laddered(
    steps: Sequence[Tuple[str, Callable[[], object]]],
    *,
    label: str,
    trace=None,
    on_downgrade: Optional[Callable[[str, BaseException], None]] = None,
    predictor: Optional[Callable[[str], Optional[bool]]] = None,
):
    """Run the first rung; on a classified device error fall to the
    next, trace-noting the downgrade. ``steps`` is [(rung_name,
    thunk)] in ladder order; ``on_downgrade(rung, error)`` lets the
    caller retire state tied to the failed rung (e.g. drop a Pallas
    plan so later probes skip the dead rung; ``error`` is None for a
    predicted skip). Unclassified errors propagate; a classified error
    on the LAST rung is re-raised as its taxonomy type.

    ``predictor(rung)`` is the memory ledger's predictive gate
    (obs/ledger.py rung_predictor): False means the AOT memory
    analysis plus current live bytes say this rung cannot fit in
    device memory, so it is skipped WITHOUT dispatching the doomed
    executable — the observable difference from the reactive ladder,
    counted in ``guard_rung_predicted_skips_total``. True/None run the
    rung normally (reactive downgrade stays as the fallback), and the
    LAST rung always runs (the serial oracle never OOMs)."""
    if not steps:
        raise ValueError("run_laddered needs at least one rung")
    from ..utils.trace import COUNTERS

    descent: List[str] = []
    for i, (rung, thunk) in enumerate(steps):
        if (
            predictor is not None
            and i + 1 < len(steps)
            and predictor(rung) is False
        ):
            COUNTERS.inc("guard_rung_predicted_skips_total")
            note_downgrade(
                label, rung, steps[i + 1][0],
                "memory ledger predicts it will not fit", trace,
            )
            descent.append(f"{rung}: skipped on ledger verdict")
            if on_downgrade is not None:
                on_downgrade(rung, None)
            continue
        try:
            return thunk()
        except Exception as e:  # audited: classified, then re-raised or downgraded
            cls = classify_device_error(e)
            if cls is None:
                raise
            if cls is DeviceOOM:
                COUNTERS.inc("guard_oom_reactive_total")
            descent.append(f"{rung}: {cls.__name__}: {_reason(e)}")
            if i + 1 >= len(steps):
                # the LAST rung failed: the raw backend exception must
                # not escape — callers route taxonomy types to exit
                # codes, so re-raise typed, carrying the full descent
                # trace (every rung tried and why it fell)
                wrapped = cls(
                    f"{label}: ladder exhausted at {rung}: {_reason(e)} "
                    f"(descent: {' | '.join(descent)})"
                )
                wrapped.descent = tuple(descent)
                raise wrapped from e
            note_downgrade(label, rung, steps[i + 1][0], _reason(e), trace)
            if on_downgrade is not None:
                on_downgrade(rung, e)


def run_chunked(
    evaluate,
    n_items: int,
    *,
    label: str,
    serial_fallback=None,
    trace=None,
    budget=None,
    estimate=None,
    shards=1,
):
    """Evaluate items [0, n_items) in device batches with bounded
    halving-retry on device OOM (a 10k-scenario vmap that exhausts
    device memory must not kill the whole plan).

    ``evaluate(lo, hi)`` runs one contiguous chunk on the device and
    returns a list of per-item results. On ``DeviceOOM`` the chunk is
    split in half and each half retried, bottoming out at single-item
    chunks; a single item that still OOMs goes through
    ``serial_fallback(i)`` (the deterministic host-oracle rung). A
    ``CompileFailure`` / ``BackendUnavailable`` skips the halving — a
    smaller batch hits the same wall — and sends every remaining item
    of the chunk through ``serial_fallback`` directly (or re-raises
    typed when there is none). Every degradation is trace-noted with
    its reason and logged; errors that classify to nothing propagate.

    ``estimate(lo, hi)`` is the predictive half (obs/costs.py
    chunk_estimator): predicted device workspace bytes for dispatching
    that chunk, from the site's AOT ``memory_analysis``. When the
    memory ledger (obs/ledger.py) says the chunk will NOT fit next to
    what is live right now, the chunk is split WITHOUT dispatching the
    doomed executable (``guard_oom_predicted_total``) — the correct
    chunk size is chosen before the first RESOURCE_EXHAUSTED instead
    of after it. Prediction accuracy is counted
    (``ledger_predict_hit_total`` / ``ledger_predict_miss_total``) so
    CI can gate on the ledger staying honest; estimate=None (or an
    unknown budget) leaves the reactive behavior exactly as before.

    ``shards`` is the device count of a mesh-sharded dispatch
    (parallel/mesh.py) — an int, or a CALLABLE re-read per chunk so a
    mid-run mesh downgrade inside ``evaluate`` (classified fault ->
    unsharded) flips the later chunks' predictions back to full-size
    arithmetic. The estimate is then PER-DEVICE bytes (the shard-aware
    chunk estimator divides the batched-axis workspace by the shard
    count) and the ledger's fit verdict compares it against the
    TIGHTEST device's headroom — without this, a sharded dispatch
    would be predicted at full-replica size and spuriously
    chunk-split.

    ``budget.check`` runs between chunks (the executor's safe
    boundary); on expiry/interrupt the raised ``ExecutionHalted``
    carries ``partial_results`` (the per-item result list, None where
    incomplete) so callers can report the completed prefix."""
    from ..utils.trace import COUNTERS, GLOBAL

    tr = trace or GLOBAL
    out = [None] * n_items
    done = [False] * n_items
    pending: List[Tuple[int, int]] = [(0, n_items)] if n_items else []
    halvings = serial = 0

    def run_serial(lo, hi, reason, why):
        nonlocal serial
        for i in range(lo, hi):
            serial += 1
            tr.append_note(
                f"{label}-serial-fallback", f"item {i} via serial oracle after {reason}"
            )
            log.warning(
                "%s: item %d falling back to the serial oracle after %s (%s)",
                label, i, why, reason,
            )
            out[i] = serial_fallback(i)
            done[i] = True

    while pending:
        if budget is not None:
            try:
                budget.check(f"{label} chunk boundary")
            except ExecutionHalted as e:
                e.partial_results = [
                    r if ok else None for r, ok in zip(out, done)
                ]
                raise
        lo, hi = pending.pop()
        predicted_fit = None
        if estimate is not None:
            est = estimate(lo, hi)
            if est is not None:
                from ..obs.ledger import LEDGER

                cur_shards = shards() if callable(shards) else shards
                predicted_fit = LEDGER.predict_fit(
                    int(est), label=label, shards=cur_shards
                )
                if predicted_fit is False and hi - lo > 1:
                    COUNTERS.inc("guard_oom_predicted_total")
                    mid = (lo + hi) // 2
                    halvings += 1
                    tr.append_note(
                        f"{label}-chunk-predicted-split",
                        f"[{lo},{hi}) -> [{lo},{mid})+[{mid},{hi}): ledger "
                        f"predicts {est} workspace bytes will not fit",
                    )
                    log.info(
                        "%s: ledger predicts chunk [%d,%d) (%d workspace "
                        "bytes) will not fit; splitting before dispatch",
                        label, lo, hi, est,
                    )
                    pending.append((mid, hi))
                    pending.append((lo, mid))
                    continue
                if predicted_fit is False:
                    # single item predicted not to fit: route straight
                    # to the serial rung, zero doomed dispatches
                    if serial_fallback is not None:
                        COUNTERS.inc("guard_oom_predicted_total")
                        run_serial(
                            lo, hi,
                            f"ledger predicted {est} bytes will not fit",
                            "predicted OOM",
                        )
                        continue
                    predicted_fit = None  # nothing to degrade to: try it
        try:
            if _OOM_INJECT is not None:
                _OOM_INJECT(hi - lo)
            results = evaluate(lo, hi)
        except (
            RuntimeError,
            MemoryError,
            OSError,
            DeviceOOM,
            CompileFailure,
            BackendUnavailable,
        ) as e:
            # everything classify_device_error can recognize — raw XLA
            # RuntimeErrors, OSError-shaped backend faults, and already-
            # typed taxonomy errors from nested rungs
            cls = classify_device_error(e)
            if cls is None:
                raise
            reason = _reason(e)
            if cls is DeviceOOM:
                COUNTERS.inc("guard_oom_reactive_total")
                if predicted_fit is True:
                    # the ledger said this would fit and it did not:
                    # count the miss so accuracy is gateable, not lore
                    COUNTERS.inc("ledger_predict_miss_total")
            if cls is not DeviceOOM:
                # halving cannot fix a compiler/backend fault: the
                # whole remaining chunk drops to the serial rung
                if serial_fallback is None:
                    raise cls(f"{label}: {reason}") from e
                run_serial(lo, hi, reason, cls.__name__)
                continue
            if hi - lo == 1:
                if serial_fallback is None:
                    # no serial floor: the failure leaves here typed
                    # (never the raw XLA RuntimeError) so exit codes
                    # stay within the taxonomy
                    wrapped = DeviceOOM(
                        f"{label}: single-item chunk still exhausts "
                        f"device memory: {reason}"
                    )
                    raise wrapped from e
                run_serial(lo, hi, reason, "device OOM even alone")
                continue
            mid = (lo + hi) // 2
            halvings += 1
            tr.append_note(
                f"{label}-chunk-halving",
                f"[{lo},{hi}) -> [{lo},{mid})+[{mid},{hi}) after {reason}",
            )
            log.warning(
                "%s: chunk [%d,%d) exhausted device memory; retrying as "
                "two halves (%s)", label, lo, hi, reason
            )
            # LIFO: push the upper half first so the lower half runs next
            pending.append((mid, hi))
            pending.append((lo, mid))
            continue
        if predicted_fit is True:
            COUNTERS.inc("ledger_predict_hit_total")
        out[lo:hi] = results
        done[lo:hi] = [True] * (hi - lo)
    if halvings or serial:
        tr.note(
            f"{label}-degraded",
            f"{halvings} chunk-halving(s), {serial} serial fallback(s)",
        )
    return out
