"""What every cell shares: the cell's entry in BENCHMARK.json, the device
check, the compile cache, the per-layer metric readers, and the result
line. Everything that belongs to one configuration, traffic mix or
metric lives in a file of its own, found here by name.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoDevice(SystemExit):
    """Exit non-zero, print no result."""


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json")


def end_to_end(bench: dict, name: str) -> list:
    return [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]


def per_layer(bench: dict, name: str) -> list:
    return [m for m in bench["per_layer"] if name in m.get("workloads", [name])]


def device(chips: int) -> dict:
    """The device as JAX reports it. No TPU, or fewer chips than the
    cell asks for: exit non-zero with no result, unless the CPU was
    chosen explicitly (JAX_PLATFORMS=cpu) for a rehearsal."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    chose_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if dev["platform"] != "tpu" and not chose_cpu:
        raise NoDevice(f"benchmark: no TPU (JAX sees {dev}); JAX_PLATFORMS=cpu rehearses")
    if dev["platform"] == "tpu" and dev["count"] < chips:
        raise NoDevice(f"benchmark: the cell needs {chips} chips, JAX sees {dev['count']}")
    return dev


def configure_cache() -> str:
    """JAX's persistent cache: JAX_COMPILATION_CACHE_DIR where set, else
    the checkout's fixed .jax_cache. Every program is cached, however
    fast it compiled, so a cell's second run compiles nothing."""
    import jax

    if not jax.config.jax_compilation_cache_dir:
        os.makedirs(CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


class CompileEvents:
    """XLA compiles and persistent-cache hits while it is open (JAX's
    own monitoring events): the window must compile nothing."""

    def __init__(self):
        from jax._src import monitoring

        self.compiles = 0
        self.cache_hits = 0
        self._m = monitoring

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        self._d, self._e = on_duration, on_event
        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def close(self):
        self._m.unregister_event_duration_listener(self._d)
        self._m.unregister_event_listener(self._e)


class GcPauses:
    """Collections of Python's cyclic GC while it is open, and the
    longest pause: a host stall the window should be able to name."""

    def __init__(self):
        import gc
        import time

        self.n = 0
        self.longest = 0.0
        self._t = 0.0

        def cb(phase, info):
            if phase == "start":
                self._t = time.perf_counter()
            else:
                self.n += 1
                self.longest = max(self.longest, time.perf_counter() - self._t)

        self._cb = cb
        gc.callbacks.append(cb)

    def close(self):
        import gc

        gc.callbacks.remove(self._cb)


def memory_peak_bytes() -> int:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metric(name: str, ctx: dict) -> Optional[float]:
    """A per-layer metric's reader is benchmark/metrics/<name>.py with
    ``read(ctx) -> float | None``; None leaves the metric out."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    return load_module(path, f"bench_metric_{name.replace('.', '_')}").read(ctx)


def driver(kind: str):
    """A traffic file's `kind` names its driver: drivers/<kind>.py."""
    return load_module(os.path.join(HERE, "drivers", f"{kind}.py"), f"bench_driver_{kind}")


def emit(result: dict, checks: dict) -> None:
    """Checks on stderr as the last lines, then the result line, the
    checks under the key that comes last."""
    for k, (value, limit) in checks.items():
        print(f"check {k}: {value} (limit {limit})", file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    print(json.dumps(result), flush=True)
