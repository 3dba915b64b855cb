"""Persistent compile-artifact store: zero-compile cold starts.

PR 10 made every jit site an AOT-compiled named executable keyed by
shape-signature — but only in-process: a fresh ``simon serve`` re-pays
the full XLA compile bill before its first answer. This module
persists those executables across processes as a content-addressed
on-disk store:

- one file per (site, shape-signature) under ``--aot-store DIR`` (or
  ``SIMON_AOT_STORE``), named by a sha256 of the site, the rendered
  signature, and the TOOL DIGEST (jax/jaxlib versions, backend
  platform + version, device count, store schema) — an artifact
  compiled by a different toolchain can never be offered to this one;
- entries are written crash-safely (tmp + ``os.replace``, the PR-2
  journal discipline) with a JSON header carrying the payload sha256
  and the cost/memory analysis, so verification happens BEFORE any
  payload deserialization;
- stale / corrupt / digest-mismatched entries are refused LOUDLY
  (``aot_store_reject_total`` + a warning naming the file and why) and
  the site recompiles — a bad store can cost a compile, never an
  answer;
- serialization rides ``jax.experimental.serialize_executable``; on
  backends where executable export is unsupported the store degrades
  to enabling JAX's own persistent compilation cache in its one
  directory (utils/compile_cache.py), keyed by jax's hashes instead
  of ours — cold starts still skip XLA, only the loaded-cost
  bookkeeping is lost.

The load path is a guard seam (``aot.store_load`` injection point):
classified faults degrade to a counted miss + recompile, identical
results — the chaos matrix drives this (tests/test_chaos_matrix.py).

Counters (``/metrics`` as ``simon_aot_store_*``, bench obs blocks via
``aot_store_block``): ``aot_store_hit_total``, ``aot_store_miss_total``,
``aot_store_reject_total``, ``aot_store_save_total`` (+ per-site
variants for hits).
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import struct
import tempfile
import threading
from contextlib import suppress
from typing import Optional

from ..runtime import inject as _inject
from ..runtime.errors import (
    BackendUnavailable,
    CompileFailure,
    DeviceOOM,
    ExternalIOError,
)
from ..utils.trace import COUNTERS

log = logging.getLogger(__name__)

STORE_ENV = "SIMON_AOT_STORE"
#: force the persistent-compilation-cache fallback even where
#: executable serialization works (testing / debugging knob)
MODE_ENV = "SIMON_AOT_STORE_MODE"

#: bump when the entry layout changes — old entries then digest-miss
#: (they were keyed with the old schema string) instead of misparsing
_SCHEMA = "simon-aot-2"
_MAGIC = b"SIMONAOT\n"

#: faults at the load seam that degrade to a counted recompile; an
#: unclassified error or a ConformanceError stays loud
_DEGRADABLE = (
    DeviceOOM,
    CompileFailure,
    BackendUnavailable,
    ExternalIOError,
    OSError,
)


def _tool_digest() -> str:
    """Digest of everything that makes a serialized executable
    loadable HERE: jax + jaxlib versions, backend platform and its
    runtime version, device count (a 1-device artifact must not load
    into an 8-device mesh process), and the store schema."""
    import jax

    backend = jax.devices()[0]
    client = getattr(backend, "client", None)
    parts = (
        _SCHEMA,
        getattr(jax, "__version__", "?"),
        getattr(getattr(jax, "lib", None), "__version__", "?"),
        getattr(backend, "platform", "?"),
        str(getattr(client, "platform_version", "?")),
        str(jax.device_count()),
    )
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:24]


def render_signature(site: str, key) -> Optional[str]:
    """Deterministic cross-process text of an InstrumentedJit
    shape-signature ``(treedef, ((shape, dtype, weak) | ('static',
    leaf), ...))``. Static leaves render by repr — ScanFeatures /
    ScoreWeights NamedTuples, bools, ints and strings are all
    repr-stable. A leaf whose repr leaks an object identity (``0x``
    address) cannot key a cross-process store: return None and the
    signature stays in-process only (counted miss, never a wrong
    hit)."""
    try:
        treedef, sig = key
        rendered = f"{treedef}|{sig!r}"
    except (TypeError, ValueError):
        return None
    if " at 0x" in rendered or "object at" in rendered:
        return None
    return f"{site}|{rendered}"


class ArtifactStore:
    """One directory of compiled-executable entries. Thread-safe: the
    lock covers the fallback latch; file operations are atomic
    (tmp + rename) and idempotent per digest."""

    def __init__(self, root: str):
        self.root = root
        os.makedirs(root, exist_ok=True)
        self._lock = threading.Lock()
        # None = undecided (probe on first save), True = executable
        # serialization unsupported here -> jax persistent cache mode
        self._fallback: Optional[bool] = None
        if os.environ.get(MODE_ENV, "") == "cache":
            self._fallback = True
            self._enable_jax_cache()
        self.tool = _tool_digest()

    # -- keying ------------------------------------------------------------

    def entry_path(self, site: str, key) -> Optional[str]:
        rendered = render_signature(site, key)
        if rendered is None:
            return None
        digest = hashlib.sha256(
            f"{self.tool}|{rendered}".encode()
        ).hexdigest()[:32]
        safe_site = "".join(c if c.isalnum() or c in "-_" else "_" for c in site)
        return os.path.join(self.root, f"{safe_site}-{digest}.aotx")

    # -- load --------------------------------------------------------------

    def load(self, site: str, key):
        """Return ``(compiled, CostRecord)`` for a verified store entry,
        or None (counted miss/reject — the caller compiles). Never
        raises for a bad entry: a corrupt store costs a compile, not an
        answer. The ``aot.store_load`` chaos seam lives here; classified
        faults degrade to a reject + recompile."""
        path = self.entry_path(site, key)
        if path is None:
            COUNTERS.inc("aot_store_miss_total")
            return None
        try:
            _inject.fire("aot.store_load", jit_site=site)
            with self._lock:
                fallback = self._fallback
            if fallback:
                # jax's own cache does the persistence; our load is
                # always a miss (the compile below hits jax's cache)
                COUNTERS.inc("aot_store_miss_total")
                return None
            if not os.path.exists(path):
                COUNTERS.inc("aot_store_miss_total")
                COUNTERS.inc(f"aot_store_miss_{site}")
                return None
            with open(path, "rb") as f:
                blob = f.read()
            header, payload = self._parse(path, blob)
            if header is None:
                COUNTERS.inc("aot_store_reject_total")
                return None
            entry = self._deserialize(site, path, header, payload)
            if entry is None:
                COUNTERS.inc("aot_store_reject_total")
                return None
            COUNTERS.inc("aot_store_hit_total")
            COUNTERS.inc(f"aot_store_hit_{site}")
            from ..utils.trace import GLOBAL

            GLOBAL.note("aot-store-hit", site)
            return entry
        except _DEGRADABLE as e:
            # the degradation contract of the chaos matrix: a store
            # fault (injected or real I/O) is a loud reject + recompile
            log.warning(
                "aot store: load of %s degraded to recompile (%s: %s)",
                site, type(e).__name__, str(e).split("\n", 1)[0][:120],
            )
            COUNTERS.inc("aot_store_reject_total")
            from ..utils.trace import GLOBAL

            GLOBAL.note("aot-store-degraded", f"{site}: {type(e).__name__}")
            return None

    def _parse(self, path: str, blob: bytes):
        """Split + verify an entry file. Returns (header, payload) or
        (None, None) with the refusal logged — every branch names the
        file and the exact mismatch."""
        if not blob.startswith(_MAGIC):
            log.warning("aot store: %s: bad magic; refusing entry", path)
            return None, None
        off = len(_MAGIC)
        if len(blob) < off + 4:
            log.warning("aot store: %s: truncated header length", path)
            return None, None
        (hlen,) = struct.unpack(">I", blob[off:off + 4])
        off += 4
        if len(blob) < off + hlen:
            log.warning("aot store: %s: truncated header (torn write?)", path)
            return None, None
        try:
            header = json.loads(blob[off:off + hlen].decode("utf-8"))
        except (ValueError, UnicodeDecodeError):
            log.warning("aot store: %s: unparseable header", path)
            return None, None
        payload = blob[off + hlen:]
        if header.get("tool") != self.tool:
            log.warning(
                "aot store: %s: toolchain digest mismatch (entry %s, "
                "process %s); refusing and recompiling",
                path, header.get("tool"), self.tool,
            )
            return None, None
        sha = hashlib.sha256(payload).hexdigest()
        if header.get("payload_sha256") != sha:
            log.warning(
                "aot store: %s: payload sha256 mismatch (corrupt entry); "
                "refusing and recompiling", path,
            )
            return None, None
        return header, payload

    def _deserialize(self, site: str, path: str, header: dict, payload: bytes):
        """Rehydrate a verified payload into ``(compiled, CostRecord)``.
        The sha256 gate ran already, so unpickling is over bytes we
        wrote ourselves."""
        from ..obs.costs import CostRecord

        try:
            import jax
            from jax.experimental import serialize_executable

            ser, in_tree, out_tree = pickle.loads(payload)
            # load onto the devices the executable was compiled for:
            # left unset, JAX loads it for EVERY device of the backend
            by_id = {d.id: d for d in jax.devices()}
            compiled = serialize_executable.deserialize_and_load(
                ser, in_tree, out_tree,
                execution_devices=[by_id[i] for i in header["devices"]],
            )
        except Exception as e:  # noqa: BLE001 - any rehydration fault degrades to a counted reject + recompile; the compile path surfaces real errors
            log.warning(
                "aot store: %s: deserialization failed (%s); refusing and "
                "recompiling", path, str(e).split("\n", 1)[0][:120],
            )
            return None
        cost = header.get("cost") or {}
        rec = CostRecord(
            site=site,
            flops=float(cost.get("flops", 0.0)),
            bytes_accessed=float(cost.get("bytes_accessed", 0.0)),
            argument_bytes=int(cost.get("argument_bytes", 0)),
            output_bytes=int(cost.get("output_bytes", 0)),
            temp_bytes=int(cost.get("temp_bytes", 0)),
            generated_code_bytes=int(cost.get("generated_code_bytes", 0)),
            lead_dim=int(cost.get("lead_dim", 0)),
        )
        return compiled, rec

    # -- save --------------------------------------------------------------

    def save(self, site: str, key, compiled, rec) -> bool:
        """Serialize one freshly-compiled executable, crash-safely
        (tmp + rename). Serialization being unsupported on this
        backend latches the jax-persistent-cache fallback instead; any
        other failure is logged and skipped (the store is an
        optimization, never load-bearing)."""
        path = self.entry_path(site, key)
        with self._lock:
            fallback = self._fallback
        if path is None or fallback:
            return False
        try:
            from jax.experimental import serialize_executable

            payload = pickle.dumps(serialize_executable.serialize(compiled))
            devices = [
                d.id for d in compiled.runtime_executable().local_devices()
            ]
        except Exception as e:  # noqa: BLE001 - export support is backend-optional: probe result decides the fallback, never crashes the dispatch
            enable = False
            with self._lock:
                if self._fallback is None:
                    self._fallback = True
                    enable = True
            if enable:
                log.warning(
                    "aot store: executable serialization unavailable "
                    "on this backend (%s); falling back to the JAX "
                    "persistent compilation cache",
                    str(e).split("\n", 1)[0][:120],
                )
                self._enable_jax_cache()
            return False
        with self._lock:
            if self._fallback is None:
                self._fallback = False
        header = {
            "schema": _SCHEMA,
            "site": site,
            "tool": self.tool,
            "payload_sha256": hashlib.sha256(payload).hexdigest(),
            "devices": devices,
            "cost": dict(rec.as_dict(), site=site),
        }
        hbytes = json.dumps(header, sort_keys=True).encode()
        try:
            fd, tmp = tempfile.mkstemp(
                dir=self.root, prefix=os.path.basename(path) + ".tmp."
            )
            renamed = False
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(_MAGIC)
                    f.write(struct.pack(">I", len(hbytes)))
                    f.write(hbytes)
                    f.write(payload)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, path)
                renamed = True
            finally:
                if not renamed:
                    # the tmp file must not linger on ANY failure path
                    # (including an injected crash riding through);
                    # best-effort — the raising error is the real story
                    with suppress(OSError):
                        os.unlink(tmp)
        except OSError as e:
            log.warning(
                "aot store: save of %s failed (%s); entry skipped",
                site, str(e).split("\n", 1)[0][:120],
            )
            return False
        COUNTERS.inc("aot_store_save_total")
        return True

    # -- fallback ----------------------------------------------------------

    def _enable_jax_cache(self) -> None:
        """Best-effort enablement of JAX's persistent compilation cache
        in its one directory (utils/compile_cache.py; a directory
        already set stays) — the degraded mode for backends without
        executable export. Thresholds open wide so even sub-second
        compiles persist."""
        try:
            import jax

            from ..utils.compile_cache import configure_compile_cache

            cache_dir = configure_compile_cache()
            log.info("aot store: persistent compilation cache at %s", cache_dir)
            for knob, value in (
                ("jax_persistent_cache_min_compile_time_secs", 0),
                ("jax_persistent_cache_min_entry_size_bytes", -1),
            ):
                try:
                    jax.config.update(knob, value)
                except (AttributeError, ValueError):
                    # knob absent on this jax release: defaults apply
                    log.debug("aot store: jax knob %s unavailable", knob)
        except Exception as e:  # noqa: BLE001 - the fallback of the fallback is plain recompilation; log and move on
            log.warning(
                "aot store: persistent compilation cache unavailable "
                "(%s); artifacts will not persist",
                str(e).split("\n", 1)[0][:120],
            )

    def stats(self) -> dict:
        with self._lock:
            fallback = bool(self._fallback)
        return {
            "root": self.root,
            "tool": self.tool,
            "fallback": fallback,
            "entries": len(
                [n for n in os.listdir(self.root) if n.endswith(".aotx")]
            ),
        }


# ---------------------------------------------------------- process wiring

_STORE: Optional[ArtifactStore] = None
_STORE_LOCK = threading.Lock()
_ENV_CHECKED = False


def configure_store(path: Optional[str]) -> Optional[ArtifactStore]:
    """Arm (or disarm with None/'') the process-wide artifact store —
    the ``--aot-store DIR`` wiring. Returns the live store."""
    global _STORE, _ENV_CHECKED
    with _STORE_LOCK:
        _ENV_CHECKED = True
        if not path:
            _STORE = None
        else:
            _STORE = ArtifactStore(path)
        return _STORE


def current_store() -> Optional[ArtifactStore]:
    """The armed store, auto-configuring from ``SIMON_AOT_STORE`` on
    first consultation (subprocess surfaces need no flag plumbing)."""
    global _STORE, _ENV_CHECKED
    if _STORE is None and not _ENV_CHECKED:
        with _STORE_LOCK:
            if not _ENV_CHECKED:
                _ENV_CHECKED = True
                env = os.environ.get(STORE_ENV, "")
                if env:
                    _STORE = ArtifactStore(env)
    return _STORE


# ---------------------------------------------------------- obs blocks


def aot_store_block() -> dict:
    """Store counters for bench obs lines / trace artifacts / the
    doctor (hit_rate is the doctor-gated dimension)."""
    hits = COUNTERS.get("aot_store_hit_total")
    misses = COUNTERS.get("aot_store_miss_total")
    rejects = COUNTERS.get("aot_store_reject_total")
    saves = COUNTERS.get("aot_store_save_total")
    if not (hits or misses or rejects or saves):
        return {}
    return {
        "hits": hits,
        "misses": misses,
        "rejects": rejects,
        "saves": saves,
        "hit_rate": round(hits / max(1, hits + misses), 4),
    }


def incremental_block() -> dict:
    """Delta re-simulation counters (resim.py + the serve/twin/timeline
    wiring) for bench obs lines — suffix_fraction is the doctor-gated
    dimension: re-dispatched rows over rows the prefix reuse saved."""
    suffix = COUNTERS.get("incremental_suffix_pods_total")
    prefix = COUNTERS.get("incremental_prefix_reused_pods_total")
    if not (suffix or prefix):
        return {}
    return {
        "suffix_pods": suffix,
        "prefix_reused_pods": prefix,
        "suffix_fraction": round(suffix / max(1, suffix + prefix), 6),
        "resims": COUNTERS.get("incremental_resims_total"),
        "full_rebuilds": COUNTERS.get("incremental_full_rebuilds_total"),
        "fallbacks": COUNTERS.get("incremental_fallbacks_total"),
    }
