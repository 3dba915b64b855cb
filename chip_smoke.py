"""Prove that the capacity planner runs on a TPU chip, end to end.

One process, through the entry points a user calls, at the flagship
size (100k pods over 10k nodes; bench.build_capacity_scenario, fixed
seed). Each phase prints one progress line; any failure exits non-zero
and prints no result.

    python chip_smoke.py               # one chip: device, flagship plan,
                                       # conformance fuzz, serve
    python chip_smoke.py --four-chips  # the mesh path only, on 4 chips

Phases (one chip):
1. device: JAX must report a TPU (no CPU fallback).
2. flagship: ``probe_plan`` on the fused Pallas kernel with no ladder
   degradation; its placements at the chosen count equal one XLA-scan
   pass at that count, elementwise.
3. conformance: ``bench.run_conformance_fuzz`` (mixed features, the
   streamed-terms layout, gpushare) — zero mismatches.
4. serve: an in-process ``ServeDaemon`` over the same 10k-node cluster
   answers a few ``/v1/simulate`` POSTs byte-identically to standalone
   simulates, with zero recompiles after the first request.

The last line of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys
import time
import urllib.request


class SmokeFailure(Exception):
    """A phase's check failed."""


def say(phase: str, msg: str) -> None:
    print(f"chip_smoke [{phase}] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def phase_device(min_count: int) -> dict:
    import jax

    devs = jax.devices()
    dev = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    check(dev["platform"] == "tpu", f"no TPU: JAX sees {dev}")
    check(
        dev["count"] >= min_count,
        f"needs {min_count} chips, JAX sees {dev['count']}",
    )
    say("device", f"{dev['kind']} x {dev['count']}")
    return dev


def _sweep_placements(cluster, apps, new_node, count, kernel: bool):
    """Placements[P] (sweep node indices) of one probe at ``count``,
    on the fused kernel or on the XLA scan, plus the sweep."""
    from open_simulator_tpu.apply.applier import MAX_NUM_NEW_NODE
    from open_simulator_tpu.models.workloads import reset_name_counter
    from open_simulator_tpu.ops import pallas_scan
    from open_simulator_tpu.parallel.sweep import CapacitySweep
    from open_simulator_tpu.runtime.guard import degradations
    from open_simulator_tpu.utils.trace import GLOBAL

    pallas_scan.FORCE_ENABLE = None if kernel else False
    try:
        reset_name_counter()
        sweep = CapacitySweep(cluster, apps, new_node, MAX_NUM_NEW_NODE)
        res = sweep.probe(count)
    finally:
        pallas_scan.FORCE_ENABLE = None
    ran = GLOBAL.notes.get("sweep-kernel", "")
    want = "pallas" if kernel else "xla-scan"
    check(ran.split(" ")[0] == want, f"probe ran on {ran!r}, not {want}")
    check(not degradations(), f"probe degraded: {degradations()}")
    return res.placements, sweep


def phase_flagship(cluster, apps, new_node) -> None:
    from collections import Counter

    import numpy as np

    from open_simulator_tpu.apply.applier import probe_plan
    from open_simulator_tpu.models.workloads import reset_name_counter
    from open_simulator_tpu.runtime.guard import degradations
    from open_simulator_tpu.utils.trace import GLOBAL

    reset_name_counter()
    GLOBAL.reset()
    t0 = time.perf_counter()
    res = probe_plan(cluster, apps, new_node)
    plan_s = time.perf_counter() - t0
    kernel = GLOBAL.notes.get("sweep-kernel")
    check(res.success, f"capacity plan failed: {res.message}")
    check(kernel == "pallas", f"plan ran on {kernel!r}, not the pallas kernel")
    check(not degradations(), f"plan degraded: {degradations()}")
    count = res.new_node_count
    # pod names repeat at this scale (5-hex suffixes), so the plan's
    # report is compared as a multiset of (node, pod name) pairs and
    # the kernel-vs-XLA check by pod index
    reported = Counter(
        (ns.node["metadata"]["name"], pod["metadata"]["name"])
        for ns in res.result.node_status
        for pod in ns.pods
    )
    del res

    got, sweep = _sweep_placements(cluster, apps, new_node, count, True)
    want, _ = _sweep_placements(cluster, apps, new_node, count, False)
    mism = int((np.asarray(got) != np.asarray(want)).sum())
    check(mism == 0, f"{mism} of {len(want)} kernel placements differ from XLA")
    names = [ns.name for ns in sweep.oracle.nodes]
    check(
        reported == Counter(
            (names[i], pod["metadata"]["name"])
            for i, pod in zip(got, sweep.pods)
        ),
        "the plan's report differs from the kernel's placements",
    )
    say(
        "flagship",
        f"{len(sweep.pods)} pods x {sweep.n_base} nodes: +{count} nodes on "
        f"pallas in {plan_s:.2f}s (cold), placements == xla-scan",
    )


def phase_conformance() -> None:
    import bench

    z = bench.run_conformance_fuzz()
    check(z["checked"] > 0, f"conformance fuzz did not run: {z}")
    check(z["mismatches"] == 0, f"conformance fuzz mismatches: {z}")
    say("conformance", f"{z['checked']} placements compared, 0 mismatches")


def _deployment(name: str, replicas: int, cpu: str) -> dict:
    return {
        "kind": "Deployment",
        "metadata": {"name": name, "namespace": "smoke", "labels": {"app": name}},
        "spec": {
            "replicas": replicas,
            "template": {
                "spec": {
                    "containers": [
                        {
                            "name": "c",
                            "image": f"img-{name}",
                            "resources": {
                                "requests": {"cpu": cpu, "memory": "1Gi"}
                            },
                        }
                    ]
                }
            },
        },
    }


def _recompiles(base: str) -> int:
    with urllib.request.urlopen(base + "/metrics", timeout=60) as resp:
        text = resp.read().decode()
    for line in text.splitlines():
        if line.startswith("simon_jax_recompiles_total "):
            return int(float(line.split()[1]))
    raise SmokeFailure("/metrics has no simon_jax_recompiles_total")


def phase_serve(cluster) -> None:
    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.models.workloads import reset_name_counter
    from open_simulator_tpu.scheduler.core import AppResource, simulate
    from open_simulator_tpu.serve.server import ServeDaemon
    from open_simulator_tpu.serve.session import Session, result_payload

    apps = [
        _deployment(f"whatif-{i}", 50, cpu)
        for i, cpu in enumerate(("500m", "250m", "1", "750m"))
    ]
    daemon = ServeDaemon(Session(cluster), port=0, max_batch=4)
    daemon.start()
    base = f"http://{daemon.host}:{daemon.port}"
    bodies = []
    try:
        for i, app in enumerate(apps):
            req = urllib.request.Request(
                base + "/v1/simulate",
                data=json.dumps(
                    {"apps": [{"name": app["metadata"]["name"],
                               "yaml": json.dumps(app)}]}
                ).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=600) as resp:
                check(resp.status == 200, f"POST {i} answered {resp.status}")
                bodies.append(resp.read())
            if i == 0:
                warm = _recompiles(base)
        warm_recompiles = _recompiles(base) - warm
    finally:
        daemon.shutdown()
    check(warm_recompiles == 0, f"{warm_recompiles} recompiles after the first request")
    # standalone simulates only now: their compiles share the counters
    for i, (app, body) in enumerate(zip(apps, bodies)):
        res = ResourceTypes()
        res.deployments = [app]
        reset_name_counter()
        want = result_payload(
            simulate(
                copy.deepcopy(cluster),
                [AppResource(app["metadata"]["name"], res)],
                engine="tpu",
            )
        )
        check(body == want, f"POST {i} body differs from a standalone simulate")
    say(
        "serve",
        f"{len(bodies)} /v1/simulate answers over {len(cluster.nodes)} nodes "
        "byte-identical to standalone, 0 warm recompiles",
    )


def phase_four_chips(cluster, apps, new_node) -> None:
    """The capacity sweep sharded over the scenario axis and one scan
    sharded over the node axis of a 4-device mesh, each against the
    same work unsharded on one device."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from open_simulator_tpu.apply.applier import MAX_NUM_NEW_NODE
    from open_simulator_tpu.models.workloads import reset_name_counter
    from open_simulator_tpu.ops import pallas_scan
    from open_simulator_tpu.parallel import mesh as mesh_mod
    from open_simulator_tpu.parallel.sweep import CapacitySweep
    from open_simulator_tpu.runtime.guard import degradations

    mesh = Mesh(np.array(jax.devices()[:4]), (mesh_mod.MESH_AXIS,))
    pallas_scan.FORCE_ENABLE = False  # the mesh path is the XLA scan
    try:
        reset_name_counter()
        sweep = CapacitySweep(cluster, apps, new_node, MAX_NUM_NEW_NODE)
        counts = list(range(0, 64, 8))
        t0 = time.perf_counter()
        sharded = sweep.probe_many(counts, mesh=mesh)
        t_sharded = time.perf_counter() - t0
        plain = sweep.probe_many(counts)
        check(
            np.array_equal(sharded.placements, plain.placements)
            and np.array_equal(sharded.unscheduled, plain.unscheduled),
            "scenario-sharded sweep differs from the unsharded one",
        )
        count = 32
        valid = sweep.node_valid(count)
        t0 = time.perf_counter()
        node_pl = mesh_mod.run_node_sharded(
            mesh, sweep.static, sweep.init, sweep.batch.class_of_pod,
            sweep.batch.pinned_node, valid, sweep.pod_active(valid),
            sweep.features,
        )[0]
        t_node = time.perf_counter() - t0
        ref = sweep._probe_xla(count, valid).placements
    finally:
        pallas_scan.FORCE_ENABLE = None
    mism = int((node_pl != ref).sum())
    check(mism == 0, f"node-sharded scan: {mism} placements differ")
    check(
        np.array_equal(ref, plain.placements[counts.index(count)]),
        "single probe differs from its sweep row",
    )
    check(not degradations(), f"mesh path degraded: {degradations()}")
    say(
        "four-chips",
        f"{len(sweep.pods)} pods x {sweep.n} nodes on a 4-chip mesh: "
        f"{len(counts)}-count sweep (scenario axis, {t_sharded:.2f}s cold) "
        f"and node-axis scan ({t_node:.2f}s cold) == unsharded",
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the 4-chip mesh phase and what it is compared with",
    )
    args = ap.parse_args(argv)
    try:
        dev = phase_device(4 if args.four_chips else 1)
        from open_simulator_tpu.utils.compile_cache import configure_compile_cache

        say("cache", configure_compile_cache())
        import bench

        scenario = bench.build_capacity_scenario()
        if args.four_chips:
            phase_four_chips(*scenario)
        else:
            phase_flagship(*scenario)
            phase_conformance()
            phase_serve(scenario[0])
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
