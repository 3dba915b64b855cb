"""Run one cell of the benchmark once, as the driver calls it:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (scenario from the seed, warm-up of every shape the cell uses),
then the measured window, then the check against the plain reference.
The last line of stdout is one JSON object: correct, attempted, failed,
metrics, device, with --trace 1 the breakdown, and last the numbers
compared with their limits. No TPU: exit 2, no result (JAX_PLATFORMS=cpu
rehearses on the CPU; --scale shrinks the counts there).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="shrink every count (CPU rehearsals only)")
    return ap.parse_args(argv)


def traced_phases(names: set):
    """While tracing, the program's phase() also opens a profiler
    annotation of the same name, so idle gaps can be labelled."""
    import contextlib

    import jax

    from open_simulator_tpu.utils import trace as trace_mod

    orig = trace_mod.phase

    @contextlib.contextmanager
    def phase(name, trace=None):
        names.add(name)
        with jax.profiler.TraceAnnotation(name), orig(name, trace):
            yield

    trace_mod.phase = phase
    return lambda: setattr(trace_mod, "phase", orig)


def main(argv=None) -> int:
    args = parse(argv)
    from benchmark import harness, loop, scenario

    bench = harness.load_benchmark()
    cell = harness.cell(bench, args.workload)
    dev = harness.device(int(cell["chips"]))
    if args.scale < 1 and dev["platform"] == "tpu":
        raise SystemExit("benchmark: --scale is for CPU rehearsals")
    harness.configure_cache()
    import jax

    from open_simulator_tpu.obs import profile  # noqa: F401 - registers the counters
    from open_simulator_tpu.utils.trace import COUNTERS

    scn = scenario.build(cell["config"], cell["traffic"], args.seed, args.scale)
    drv = harness.driver(scn.traffic["kind"]).Driver(scn, args.seed)
    drv.setup()
    import gc

    # set-up's objects (the cluster, the running pods) live through the
    # window: frozen, the cyclic GC's cost is what the operations allocate
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START

    names = {"bench/op"}
    undo = None
    if args.trace:
        shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
        undo = traced_phases(names)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1  # annotations only: level 2 is ~50 MB/s of runtime events
        jax.profiler.start_trace(harness.TRACE_DIR, profiler_options=opts)
    recompiles0 = COUNTERS.get("jax_recompiles_total")
    events = harness.CompileEvents()
    pauses = harness.GcPauses()
    with jax.profiler.TraceAnnotation("bench/window"):
        drv.window(args.seconds)
    pauses.close()
    events.close()
    recompiles = COUNTERS.get("jax_recompiles_total") - recompiles0
    if args.trace:
        jax.profiler.stop_trace()
        undo()
    peak = harness.memory_peak_bytes()
    attempted, failed = drv.attempted_failed()
    ctx = drv.context()
    drv.release()
    gc.unfreeze()
    gc.collect()
    t_check = time.perf_counter()
    checks = drv.check()
    check_s = time.perf_counter() - t_check
    correct = all(v <= lim for v, lim in checks.values())

    device = dict(dev, memory_peak_bytes=peak)
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.trace:
        from benchmark import tracing

        red = tracing.reduce(tracing.find_xplane(harness.TRACE_DIR), labels=names)
        ctx["trace"] = red
        top = sorted(red["module_seconds"].items(), key=lambda kv: -kv[1])[:8]
        print(f"device seconds by XLA module: {top}", file=sys.stderr, flush=True)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        metrics = {}
        for m in harness.per_layer(bench, args.workload):
            v = harness.read_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = red["breakdown"]
    else:
        values = dict(drv.metrics(), setup_s=setup_s)
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in harness.end_to_end(bench, args.workload)
        }
        result["device"] = device
    from open_simulator_tpu.utils.trace import GLOBAL

    ops = sorted(ctx.get("op_s", []))
    spread = f"op s min/median/max {ops[0]:.3f}/{ops[len(ops) // 2]:.3f}/{ops[-1]:.3f}; " if ops else ""
    print(f"window: {attempted} ops; {spread}jax_recompiles_total +{recompiles}; "
          f"XLA compiles {events.compiles}, persistent-cache hits {events.cache_hits}; "
          f"GC collections {pauses.n}, longest {pauses.longest:.3f}s; "
          f"setup {setup_s:.3f}s; check {check_s:.3f}s; last op's notes {GLOBAL.notes}",
          file=sys.stderr, flush=True)
    print(f"window: {loop.slowest(ctx)}", file=sys.stderr, flush=True)
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SystemExit as e:
        if isinstance(e.code, str):
            print(e.code, file=sys.stderr)
            sys.exit(2)
        raise
