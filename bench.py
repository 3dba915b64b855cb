"""Benchmarks against the BASELINE.json north star (the reference
publishes no numbers of its own — BASELINE.json `published: {}`).

Scenarios (SIMON_BENCH env):
- `capacity` (headline, default): END-TO-END capacity plan at 100k pods
  x 10k nodes — workload expansion, host encode, resource lower bound,
  bisection probes (masked scans), host replay, and the report, wall
  clock. North star: <10 s on a single TPU chip (the reference's
  equivalent is the interactive per-guess re-simulation loop,
  pkg/apply/apply.go:186-239).
- `default`: raw scan throughput, 20k pods over 10k nodes.
- `affinity`: the 100-StatefulSet anti-affinity + topology-spread
  stress (term-table machinery).
- `affinity-25k`: the same stress at 25k nodes — past the resident
  VMEM cliff, auto-routed to the STREAMED terms kernel (r5).
- `mixed`: the default scenario with 1% hostPort and 1% extended-
  resource pods — proves mixed batches stay on the fused kernel.
- `gpushare`: per-device GPU-memory fragmentation scoring at 1k 8-GPU
  nodes (simon-gpushare-config.yaml at scale).
- `storage`: the open-local VG binpack + exclusive-device path at 10k
  2-VG nodes — on the fused kernel since r5 (host-f64 score tables).
- `sample`: select_host="sample" e2e (Go-RNG reservoir in the scan
  carry, r5) vs first-max on the same XLA path.
- `priority`: the default batch with a few high-priority pods — the
  priority-scan engine keeps the bulk on the fused scan.
- `priority-dense`: 75% of the 20k pods carry non-zero priorities over
  8 tiers (the round-3 serial cliff, VERDICT r3 weak #2) — the tiered
  priority-scan engine places it in one optimistic ordered scan per
  preemption escape, and the metric line carries the per-phase
  sort/encode/scan/replay wall-clock split.
- `tier-stress`: escape-heavy worst case — more preempting priority
  tiers than MAX_SCAN_ESCAPES on a packed cluster, so every escape,
  masked re-dispatch, and the serial-tail ladder is in the measured
  path (the ladder the unit tests only pin semantically).
- `storage-fallback`: open-local nodes with 6 VGs — past the fused
  kernel's storage scope cap (>4 VGs), so the batch rides the XLA
  fallback and its rate is a recorded number instead of an invisible
  regression surface.
- `twin-delta`: the live digital twin's substrate — cluster deltas/s
  applied to a warm 10k-node mirror through the incremental
  applicator (twin/deltas.py), with warm what-if queries answered
  against the drifting live state (p50/p95 recorded, zero warm
  recompiles asserted).
- `fuzz`: on-device Pallas-vs-XLA placement conformance over a
  mixed-feature scenario (terms+ports+scalars+pins+storage, plus a
  forced STREAMED-terms pass); `all` runs it first and aborts on any
  mismatch, so every recorded number is backed by a fresh hardware
  numerics check.
- `defrag`: pod-migration defragmentation sweep on a cluster snapshot.
- `whatif`: minimal-count capacity plan over 8 candidate newnode specs.
- `serve-qps`: the `simon serve` daemon under a concurrent client
  storm — qps, p50/p95 latency, mean coalesced batch fill, and device
  dispatches per request (<1 proves the micro-batching; r6).
- `shadow-replay`: the shadow divergence auditor replaying a recorded
  decision log of simon's own placements on the warm single-pod scan
  probe — steps/s, agreement rate (gated at 1.0), dispatches per step,
  zero warm jit-cache misses asserted (r7).
- `fleet-qps`: the `simon fleet` router over 1/2/4 serve replica
  subprocesses sharing one AOT store — aggregate req/s per fleet size
  plus the live kill -9 failover: rerouted first-200 and full
  journal-replay recovery, gated at zero new XLA compiles (r16).
- `all`: capacity headline with the others embedded in the metric
  string (one scenario per BASELINE.json config).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Timing protocol: each scenario runs once to compile (JAX traces are
cached in-process and in .jax_cache) and once for the measurement, so
numbers reflect steady-state operation, not XLA compile time. Host-side
work (expansion, encode, replay, report) is inside the timed region.

Every line names the device it ran on (``"device"``: platform, kind,
count). The bench runs on a TPU, or on the CPU only when
``JAX_PLATFORMS=cpu`` chooses it; finding no chip otherwise is an
error, never a silent CPU number. Scenarios whose ``serve`` children
need the chip (cold-start, fleet-qps) run before this process touches
the backend, and fleet-qps refuses a TPU host (one chip per replica
is ROADMAP B). A capacity plan that degraded off its kernel fails.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

N_NODES = 10_000
N_PODS = 20_000
CAP_NODES = 10_000
CAP_PODS = 100_000
NORTH_STAR_PODS_PER_SEC = 10_000.0
NORTH_STAR_PLAN_SECONDS = 10.0
TIMED_RUNS = 3


def _timed(fn, runs=TIMED_RUNS):
    """Median-of-N timing with recorded spread (VERDICT r3 weak #5:
    best-of-2 hid run-to-run variance). Returns (median_s, spread,
    result) where spread is
    {"min_s", "max_s", "runs"}; callers quote the MEDIAN."""
    times, result = [], None
    for _ in range(runs):
        t0 = time.perf_counter()
        result = fn()
        times.append(time.perf_counter() - t0)
    times.sort()
    spread = {
        "min_s": round(times[0], 4),
        "max_s": round(times[-1], 4),
        "runs": runs,
    }
    return times[len(times) // 2], spread, result


#: scenarios whose serve children need the chip: this process must
#: not hold it while they run
CHILD_SCENARIOS = ("cold-start", "fleet-qps")


def _device() -> dict:
    """The device this run measures, as JAX reports it. Exits non-zero
    when JAX finds no TPU unless JAX_PLATFORMS=cpu chose the CPU."""
    import jax

    devs = jax.devices()
    dev = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    chose_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if dev["platform"] != "tpu" and not chose_cpu:
        sys.exit(
            f"bench: no TPU found (JAX sees {dev}); set JAX_PLATFORMS=cpu "
            "to run on the CPU"
        )
    return dev


def _make_node(name: str, cpu: int, mem_gi: int, labels=None, taints=None) -> dict:
    node = {
        "kind": "Node",
        "metadata": {
            "name": name,
            "labels": {"kubernetes.io/hostname": name, **(labels or {})},
        },
        "status": {
            "allocatable": {"cpu": str(cpu), "memory": f"{mem_gi}Gi", "pods": "110"}
        },
    }
    if taints:
        node["spec"] = {"taints": taints}
    return node


def build_scenario(port_frac=0.0, scalar_frac=0.0):
    """Default 10k-node scan scenario. `port_frac`/`scalar_frac` taint a
    fraction of pods with hostPorts / extended-resource requests — the
    SIMON_BENCH=mixed variant proving mixed batches keep the fused
    kernel (round 2 sent any such batch to the ~12x slower XLA scan)."""
    import numpy as np

    rng = np.random.RandomState(0)
    nodes = []
    for i in range(N_NODES):
        cpu = int(rng.choice([16, 32, 64, 96]))
        taints = None
        if i % 11 == 0:
            taints = [{"key": "dedicated", "value": "infra", "effect": "NoSchedule"}]
        node = _make_node(f"node-{i:05d}", cpu, cpu * 4, {"zone": f"z{i % 16}"}, taints)
        if scalar_frac:
            node["status"]["allocatable"]["example.com/accel"] = "8"
        nodes.append(node)

    classes = [
        ("small", "250m", "512Mi", None, False),
        ("medium", "1", "2Gi", None, False),
        ("large", "4", "8Gi", None, False),
        ("zonal", "500m", "1Gi", {"zone": "z3"}, False),
        ("tolerant", "2", "4Gi", None, True),
    ]
    pods = []
    for p in range(N_PODS):
        name, cpu, mem, selector, tol = classes[p % len(classes)]
        spec = {
            "containers": [
                {
                    "name": "c",
                    "image": f"img-{name}",
                    "resources": {"requests": {"cpu": cpu, "memory": mem}},
                }
            ],
            "schedulerName": "default-scheduler",
        }
        if selector:
            spec["nodeSelector"] = selector
        if tol:
            spec["tolerations"] = [{"key": "dedicated", "operator": "Exists"}]
        if port_frac and p % max(int(1 / port_frac), 1) == 0:
            # vary the port across the port-bearing pods (p itself is a
            # multiple of the stride here, so `p % 4` would collapse to
            # one port) to exercise a multi-entry port vocab
            hp = 8000 + (p // 100) % 4
            spec["containers"][0]["ports"] = [
                {"containerPort": hp, "hostPort": hp, "protocol": "TCP"}
            ]
        if scalar_frac and p % max(int(1 / scalar_frac), 1) == 1:
            spec["containers"][0]["resources"]["requests"][
                "example.com/accel"
            ] = "1"
        pods.append(
            {
                "metadata": {
                    "name": f"pod-{p:06d}",
                    "namespace": "bench",
                    "labels": {"cls": name},
                    "annotations": {},
                },
                "spec": spec,
            }
        )
    return nodes, pods


def build_affinity_scenario(n_nodes=2000, replicas=20):
    """SIMON_BENCH=affinity: the 100-StatefulSet anti-affinity +
    topology-spread stress from BASELINE.md, expanded to pods. The
    `all` scenario also runs it at 10k nodes x 10k pods (replicas=100)
    to record the BASELINE "pods scheduled/sec at 10k nodes" figure on
    the term machinery."""
    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.scheduler.core import AppResource
    from open_simulator_tpu.scheduler.queues import expand_apps
    from open_simulator_tpu.testing import build_affinity_stress

    nodes, stss = build_affinity_stress(
        n_nodes=n_nodes, n_sts=100, replicas=replicas, zones=16
    )
    res = ResourceTypes()
    res.stateful_sets = stss
    pods = expand_apps([AppResource("stress", res)], nodes)[0]
    return nodes, pods


def build_gpushare_scenario(n_nodes=1000, n_pods=10000):
    """SIMON_BENCH=gpushare: the simon-gpushare-config.yaml concept at
    scale — per-device GPU-memory fragmentation scoring (tightest-fit
    single-GPU, two-pointer multi-GPU; open-gpu-share
    gpunodeinfo.go:232-291). V100-style nodes: 8 devices x 32Gi."""
    gi = 1 << 30
    nodes = []
    for i in range(n_nodes):
        nodes.append(
            {
                "kind": "Node",
                "metadata": {
                    "name": f"gpu-node-{i:04d}",
                    "labels": {"kubernetes.io/hostname": f"gpu-node-{i:04d}"},
                    "annotations": {},
                },
                # gpu-count/gpu-mem live in CAPACITY (the open-gpu-share
                # codec reads capacity; example gpushare nodes carry both)
                "status": {
                    "allocatable": {"cpu": "64", "memory": "256Gi", "pods": "110"},
                    "capacity": {
                        "cpu": "64",
                        "memory": "256Gi",
                        "pods": "110",
                        "alibabacloud.com/gpu-count": "8",
                        "alibabacloud.com/gpu-mem": str(8 * 32 * gi),
                    },
                },
            }
        )
    # fragmentation mix: 4/8/16/32 Gi single-GPU shares + 2-GPU jobs
    shapes = [
        (4 * gi, 1),
        (8 * gi, 1),
        (16 * gi, 1),
        (32 * gi, 1),
        (16 * gi, 2),
    ]
    pods = []
    for p in range(n_pods):
        mem, cnt = shapes[p % len(shapes)]
        pods.append(
            {
                "metadata": {
                    "name": f"gpu-pod-{p:05d}",
                    "namespace": "bench",
                    "labels": {},
                    "annotations": {
                        "alibabacloud.com/gpu-mem": str(mem),
                        "alibabacloud.com/gpu-count": str(cnt),
                    },
                },
                "spec": {
                    "containers": [
                        {
                            "name": "c",
                            "image": "img-gpu",
                            "resources": {"requests": {"cpu": "4", "memory": "16Gi"}},
                        }
                    ],
                    "schedulerName": "default-scheduler",
                },
            }
        )
    return nodes, pods


def run_defrag(n_nodes=1000, n_pods=6000) -> dict:
    """SIMON_BENCH=defrag: pod-migration defragmentation sweep on a
    cluster snapshot (BASELINE config #4) — rank under-utilized nodes,
    batch-evaluate all drain depths, replay the deepest feasible drain."""
    from open_simulator_tpu.parallel.defrag import plan_defrag
    from open_simulator_tpu.scheduler.core import NodeStatus, SimulateResult

    nodes = [
        _make_node(f"node-{i:05d}", 32, 128, {"zone": f"z{i % 16}"})
        for i in range(n_nodes)
    ]
    _, pods = build_scenario()
    pods = [p for p in pods if "nodeSelector" not in p["spec"]][:n_pods]
    # synthetic placed snapshot at ~20% fill over ALL nodes, so every
    # drained node forces real migrations
    statuses = [NodeStatus(node=n, pods=[]) for n in nodes]
    for i, pod in enumerate(pods[:n_pods]):
        ns = statuses[i % n_nodes]
        pod = dict(pod)
        pod["spec"] = dict(pod["spec"])
        pod["spec"]["nodeName"] = ns.node["metadata"]["name"]
        pod.setdefault("status", {})["phase"] = "Running"
        ns.pods.append(pod)
    snapshot = SimulateResult(unscheduled_pods=[], node_status=statuses)
    plan_defrag(snapshot, max_drain=16)  # warm/compile
    elapsed, spread, res = _timed(lambda: plan_defrag(snapshot, max_drain=16))
    return {
        "elapsed_s": elapsed,
        "spread": spread,
        "drained": res.chosen_depth,
        "moves": len(res.moves),
        "nodes": n_nodes,
        "pods": n_pods,
    }


def run_whatif(n_base=500, n_pods=5000) -> dict:
    """SIMON_BENCH=whatif: what-if capacity sweep over 8 candidate
    newnode specs (BASELINE config #5): per spec, find the minimal
    feasible new-node count; report total wall-clock for all 8."""
    from open_simulator_tpu.apply.applier import probe_plan, probe_plan_multi
    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.models.workloads import reset_name_counter
    from open_simulator_tpu.scheduler.core import AppResource
    from open_simulator_tpu.utils.trace import GLOBAL

    nodes = []
    for i in range(n_base):
        nodes.append(_make_node(f"node-{i:05d}", 16, 64, {"zone": f"z{i % 16}"}))
    rep = n_pods // 4

    def deploy(name, replicas, cpu, mem):
        return {
            "kind": "Deployment",
            "metadata": {"name": name, "namespace": "bench", "labels": {"app": name}},
            "spec": {
                "replicas": replicas,
                "template": {
                    "spec": {
                        "containers": [
                            {
                                "name": "c",
                                "image": f"img-{name}",
                                "resources": {"requests": {"cpu": cpu, "memory": mem}},
                            }
                        ]
                    }
                },
            },
        }

    resources = ResourceTypes()
    resources.deployments = [
        deploy("large", rep, "4", "8Gi"),
        deploy("medium", rep, "1", "2Gi"),
        deploy("small", rep, "500m", "1Gi"),
        deploy("mem", rep, "1", "8Gi"),
    ]
    cluster = ResourceTypes()
    cluster.nodes = nodes
    apps = [AppResource("bench", resources)]
    specs = [
        ("c16", 16, 64), ("c32", 32, 128), ("c48", 48, 192), ("c64", 64, 256),
        ("c96", 96, 384), ("m32", 32, 256), ("m64", 64, 512), ("c128", 128, 512),
    ]
    templates = [_make_node(f"tpl-{nm}", cpu, mem) for nm, cpu, mem in specs]
    # warm one spec (compiles the masked scan for this feature set; the
    # other specs reuse the same compiled shapes)
    reset_name_counter()
    probe_plan(cluster, apps, templates[0])

    def sweep():
        # all 8 specs in lockstep: each search round's probes dispatch
        # across specs in ONE device sync (probe_plan_multi; the r4
        # version paid ~23 sequential device round-trips)
        reset_name_counter()
        results = probe_plan_multi(cluster, apps, templates)
        return [r.new_node_count if r.success else -1 for r in results]

    elapsed, spread, counts = _timed(sweep)
    return {
        "elapsed_s": elapsed,
        "spread": spread,
        "specs": len(specs),
        "counts": counts,
        "pods": n_pods,
        "nodes": n_base,
        "rounds": GLOBAL.notes.get("whatif-rounds"),
        "syncs": GLOBAL.notes.get("whatif-syncs"),
    }


def run_serve_qps(n_clients=8, per_client=6, n_nodes=200) -> dict:
    """SIMON_BENCH=serve-qps: the `simon serve` daemon under concurrent
    what-if load (docs/SERVING.md). An in-process daemon (HTTP on an
    ephemeral port) takes a storm of N clients x M requests; concurrent
    requests coalesce onto batched scenario scans (up to --max-batch
    per device dispatch), so the recorded dispatches-per-request proves
    the micro-batching (<1 means coalescing happened; 1 would be the
    one-dispatch-per-request serial daemon). One warm storm first:
    each distinct in-flight batch size compiles its own scan shape, and
    the measured storm should see the jit cache, not the compiler."""
    import threading
    import urllib.request

    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.serve.server import ServeDaemon
    from open_simulator_tpu.serve.session import Session
    from open_simulator_tpu.utils.trace import COUNTERS

    nodes = [
        _make_node(f"serve-n-{i:04d}", 32, 128, {"zone": f"z{i % 8}"})
        for i in range(n_nodes)
    ]
    cluster = ResourceTypes()
    cluster.nodes = nodes
    session = Session(cluster)
    daemon = ServeDaemon(session, port=0, max_batch=8, queue_depth=256)
    daemon.start()
    base = f"http://{daemon.host}:{daemon.port}"
    app = {
        "kind": "Deployment",
        "metadata": {"name": "qps", "namespace": "bench", "labels": {"app": "qps"}},
        "spec": {
            "replicas": 50,
            "template": {
                "spec": {
                    "containers": [
                        {
                            "name": "c",
                            "image": "img-qps",
                            "resources": {
                                "requests": {"cpu": "500m", "memory": "1Gi"}
                            },
                        }
                    ]
                }
            },
        },
    }
    body = json.dumps(
        {"apps": [{"name": "qps", "yaml": json.dumps(app)}]}
    ).encode()

    def storm():
        errors = []

        def client():
            try:
                for _ in range(per_client):
                    req = urllib.request.Request(
                        base + "/v1/simulate",
                        data=body,
                        headers={"Content-Type": "application/json"},
                    )
                    with urllib.request.urlopen(req, timeout=600) as resp:
                        resp.read()
            except Exception as e:  # noqa: BLE001 - surfaced via the raise below
                errors.append(e)

        threads = [threading.Thread(target=client) for _ in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise RuntimeError(f"serve-qps client failed: {errors[0]}")

    try:
        storm()  # warm: compile the in-flight batch shapes
        COUNTERS.reset()  # measured storm owns the windows and totals
        t0 = time.perf_counter()
        storm()
        elapsed = time.perf_counter() - t0
        total = COUNTERS.get("serve_requests_total")
        dispatches = COUNTERS.get("serve_device_dispatches_total")
        return {
            "qps": round(total / elapsed, 2),
            "p50_ms": round(
                COUNTERS.percentile("serve_latency_seconds", 50) * 1000, 1
            ),
            "p95_ms": round(
                COUNTERS.percentile("serve_latency_seconds", 95) * 1000, 1
            ),
            "batch_fill_mean": round(COUNTERS.mean("serve_batch_fill"), 2),
            "dispatches_per_request": round(dispatches / max(total, 1), 3),
            "requests": total,
            "shed": COUNTERS.get("serve_shed_total"),
            "clients": n_clients,
            "nodes": n_nodes,
            "elapsed_s": round(elapsed, 3),
        }
    finally:
        # a failed storm must not leak the daemon (port, dispatcher
        # thread) into the rest of a SIMON_BENCH=all run
        daemon.shutdown()


def run_shadow_replay(n_nodes=200, n_pods=400) -> dict:
    """SIMON_BENCH=shadow-replay: the shadow divergence auditor
    (docs/OBSERVABILITY.md) replaying a recorded decision log of
    simon's own placements on the warm tpu probe — one single-pod
    masked scan per decision against the incrementally mirrored
    cluster. Measures replay steps/s, the agreement rate (must be 1.0:
    the log IS simon's decisions), and dispatches per step; the
    warm-path contract (zero jit-cache misses after the first step of
    each shape) is asserted, not assumed."""
    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.obs import profile as obs_profile
    from open_simulator_tpu.scheduler.core import AppResource
    from open_simulator_tpu.shadow.record import record_simulation
    from open_simulator_tpu.shadow.replay import ShadowReplayer

    nodes = [
        _make_node(f"shadow-n-{i:04d}", 32, 128, {"zone": f"z{i % 8}"})
        for i in range(n_nodes)
    ]
    cluster = ResourceTypes()
    cluster.nodes = nodes
    res = ResourceTypes()
    res.pods = [
        {
            "kind": "Pod",
            "metadata": {"name": f"shadow-p-{i:05d}", "namespace": "bench"},
            "spec": {
                "containers": [
                    {
                        "name": "c",
                        "image": "img-shadow",
                        "resources": {
                            "requests": {"cpu": "500m", "memory": "1Gi"}
                        },
                    }
                ]
            },
        }
        for i in range(n_pods)
    ]
    steps = record_simulation(cluster, [AppResource("shadow-app", res)])
    decisions = sum(1 for s in steps if s.kind == "decision")

    def once():
        replayer = ShadowReplayer(cluster, engine="tpu")
        report = replayer.run(steps)
        assert report.decisions == decisions
        assert report.agreement_rate == 1.0
        assert report.warm_recompiles == 0
        return report

    once()  # warm: compile the single-pod probe shape
    obs0 = obs_profile.snapshot()
    elapsed, spread, _report = _timed(once)
    prof = obs_profile.delta(obs0)
    return {
        "nodes": n_nodes,
        "decisions": decisions,
        "steps": len(steps),
        "steps_per_sec": round(decisions / elapsed, 1),
        "elapsed_s": round(elapsed, 3),
        "agreement_rate": 1.0,
        "dispatches_per_step": round(
            prof["jax_dispatches_total"] / (decisions * spread["runs"]), 3
        ),
        "spread": spread,
    }


def run_twin_delta(n_nodes=10_000, n_deltas=2000, query_every=100) -> dict:
    """SIMON_BENCH=twin-delta: the live digital twin's substrate under
    churn (docs/TWIN.md). A warm 10k-node mirror absorbs a
    deterministic stream of pod bind/evict deltas through the
    incremental applicator (twin/deltas.py — place/evict on
    copy-on-write NodeStates, never a reload), with a warm what-if
    query answered against LIVE state every `query_every` deltas (one
    masked-scan dispatch + scratch replay). Measures deltas/s applied
    and the query p50/p95 while the cluster drifts underneath; zero
    recompiles asserted across the measured churn — the query
    re-dispatches ONE compiled shape the whole time (the tentpole's
    warm-delta contract, measured at bench scale)."""
    import numpy as _np

    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.obs import profile as obs_profile
    from open_simulator_tpu.scheduler.core import AppResource
    from open_simulator_tpu.twin import queries as twin_queries
    from open_simulator_tpu.twin.deltas import (
        POD_BIND,
        POD_EVICT,
        ClusterDelta,
    )
    from open_simulator_tpu.twin.mirror import ClusterMirror, FeedSource

    nodes = [
        _make_node(f"twin-n-{i:05d}", 32, 128, {"zone": f"z{i % 8}"})
        for i in range(n_nodes)
    ]
    cluster = ResourceTypes()
    cluster.nodes = nodes
    mirror = ClusterMirror(cluster, FeedSource([], batch=1), engine="tpu")
    mirror.bootstrap()

    def churn_pod(i):
        return {
            "kind": "Pod",
            "metadata": {"name": f"tw-{i:06d}", "namespace": "bench"},
            "spec": {
                "containers": [
                    {
                        "name": "c",
                        "image": "img-twin",
                        "resources": {
                            "requests": {"cpu": "500m", "memory": "1Gi"}
                        },
                    }
                ]
            },
        }

    # deterministic churn: two binds then an evict of the older one —
    # the mirror's committed population grows while never leaking
    stream = []
    for i in range(n_deltas):
        if i % 3 == 2:
            j = i - 2
            stream.append(
                ClusterDelta(
                    kind=POD_EVICT,
                    namespace="bench",
                    name=f"tw-{j:06d}",
                    node_name=f"twin-n-{j % n_nodes:05d}",
                )
            )
        else:
            stream.append(
                ClusterDelta(
                    kind=POD_BIND,
                    pod=churn_pod(i),
                    node_name=f"twin-n-{i % n_nodes:05d}",
                )
            )

    def query_app():
        res = ResourceTypes()
        res.pods = [
            {
                "kind": "Pod",
                "metadata": {"name": "twin-query", "namespace": "bench"},
                "spec": {
                    "containers": [
                        {
                            "name": "c",
                            "image": "img-twin",
                            "resources": {
                                "requests": {"cpu": "2", "memory": "4Gi"}
                            },
                        }
                    ]
                },
            }
        ]
        return [AppResource("twin-query", res)]

    out = twin_queries.whatif(mirror, query_app())  # cold: compiles the shape
    assert out["success"]
    app = mirror.applicator
    obs0 = obs_profile.snapshot()
    q_times = []
    t0 = time.perf_counter()
    for i, d in enumerate(stream):
        app.apply(d)
        if i % query_every == query_every - 1:
            tq = time.perf_counter()
            ans = twin_queries.whatif(mirror, query_app())
            q_times.append(time.perf_counter() - tq)
            assert ans["success"]
    elapsed = time.perf_counter() - t0
    prof = obs_profile.delta(obs0)
    assert prof["jax_recompiles_total"] == 0, (
        f"warm deltas recompiled {prof['jax_recompiles_total']}x"
    )
    assert app.reloads == 0 and app.skips == 0
    q_arr = _np.asarray(q_times)
    return {
        "nodes": n_nodes,
        "deltas": n_deltas,
        "deltas_per_sec": round(n_deltas / (elapsed - float(q_arr.sum())), 1),
        "elapsed_s": round(elapsed, 3),
        "queries": len(q_times),
        "query_p50_ms": round(float(_np.percentile(q_arr, 50)) * 1000, 1),
        "query_p95_ms": round(float(_np.percentile(q_arr, 95)) * 1000, 1),
        "query_dispatches": prof["jax_dispatches_total"],
        "warm_recompiles": prof["jax_recompiles_total"],
        "committed_pods": len([p for ns in mirror.oracle.nodes for p in ns.pods]),
    }


def run_delta_resim(n_nodes=10_000, n_pods=20_000, delta_pods=16) -> dict:
    """SIMON_BENCH=delta-resim: delta re-simulation on the committed
    placement journal (docs/PERFORMANCE.md, ROADMAP item 3). A serve
    session commits an N-pod roster ONCE (the committed scan), then a
    K-pod delta stream (evicts near the journal tail + fresh arrivals)
    re-simulates only the affected suffix per delta — prefix placements
    replay host-side from the journal (PR-3 bulk scatter-add, no
    device work, no re-encode) and one suffix-sized scan re-decides the
    rest. Gated inline: the resimulated committed state is
    dict-identical to a from-scratch full re-scan, the suffix-pods
    counter stays ≪ the roster (the acceptance bound), and a warm
    what-if against the drifted state repeats at zero recompiles.
    Reports deltas/s and the measured speedup vs paying the full
    re-scan per delta."""
    import numpy as _np

    from open_simulator_tpu.incremental.resim import CommittedScan
    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.obs import profile as obs_profile
    from open_simulator_tpu.scheduler.core import AppResource
    from open_simulator_tpu.serve.session import Session, WhatIfRequest
    from open_simulator_tpu.twin.deltas import (
        POD_ARRIVE,
        POD_EVICT,
        ClusterDelta,
    )
    from open_simulator_tpu.utils.trace import COUNTERS

    def bare_pod(name):
        return {
            "kind": "Pod",
            "metadata": {"name": name, "namespace": "bench"},
            "spec": {
                "containers": [
                    {
                        "name": "c",
                        "image": "img-resim",
                        "resources": {
                            "requests": {"cpu": "500m", "memory": "1Gi"}
                        },
                    }
                ],
                "schedulerName": "default-scheduler",
            },
        }

    cluster = ResourceTypes()
    cluster.nodes = [
        _make_node(f"resim-n-{i:05d}", 64, 256, {"zone": f"z{i % 8}"})
        for i in range(n_nodes)
    ]
    cluster.pods = [bare_pod(f"resim-p-{i:05d}") for i in range(n_pods)]
    session = Session(cluster)
    committed = session._committed_scan()
    assert committed is not None, "delta-resim needs the incremental path"
    # full re-scan baseline: what every delta would cost without the
    # journal (also the conformance anchor's construction path)
    t0 = time.perf_counter()
    CommittedScan(cluster.nodes, session.cluster_pods)
    t_full = time.perf_counter() - t0

    suffix0 = COUNTERS.get("incremental_suffix_pods_total")
    prefix0 = COUNTERS.get("incremental_prefix_reused_pods_total")
    deltas = []
    for i in range(delta_pods // 2):
        deltas.append(
            ClusterDelta(
                kind=POD_EVICT, namespace="bench",
                name=f"resim-p-{n_pods - 2 - 3 * i:05d}",
            )
        )
        deltas.append(
            ClusterDelta(kind=POD_ARRIVE, pod=bare_pod(f"resim-new-{i:03d}"))
        )
    t0 = time.perf_counter()
    for delta in deltas:
        out = session.apply_delta(delta)
        assert out == "applied", f"delta not applied: {out}"
    t_deltas = time.perf_counter() - t0
    suffix_pods = COUNTERS.get("incremental_suffix_pods_total") - suffix0
    prefix_pods = COUNTERS.get("incremental_prefix_reused_pods_total") - prefix0
    total_rows = len(deltas) * len(session.cluster_pods)
    # acceptance gate: the journal re-dispatched a sliver of the rows
    # a per-delta full re-scan would have paid
    assert suffix_pods * 20 < total_rows, (
        f"suffix not incremental: {suffix_pods} of {total_rows} rows"
    )
    # conformance gate: resimulated committed state == full re-scan
    fresh = CommittedScan(cluster.nodes, session.cluster_pods)
    assert session._committed_scan().state_digest() == fresh.state_digest(), (
        "delta re-simulation diverged from the full re-scan"
    )
    # warm what-if against the drifted state: second query of the same
    # shape must be pure cache (the millisecond warm path)
    app = ResourceTypes()
    app.pods = [bare_pod("resim-query-pod")]
    req = WhatIfRequest(apps=[AppResource("resim-query", app)])
    session.evaluate_batch([req])  # shape compile
    prof0 = obs_profile.snapshot()
    q_times = []
    for _ in range(5):
        t0 = time.perf_counter()
        replies = session.evaluate_batch([req])
        q_times.append(time.perf_counter() - t0)
        assert replies[0].status == 200
    prof = obs_profile.delta(prof0)
    assert prof["jax_recompiles_total"] == 0, (
        f"warm what-if recompiled: {prof['jax_recompiles_total']}"
    )
    per_delta = t_deltas / len(deltas)
    return {
        "nodes": n_nodes,
        "pods": n_pods,
        "deltas": len(deltas),
        "deltas_per_sec": round(len(deltas) / t_deltas, 2),
        "per_delta_ms": round(per_delta * 1000, 1),
        "full_rescan_s": round(t_full, 3),
        "speedup_x": round(t_full / per_delta, 2),
        "suffix_pods": suffix_pods,
        "prefix_reused_pods": prefix_pods,
        "suffix_fraction": round(
            suffix_pods / max(1, suffix_pods + prefix_pods), 6
        ),
        "whatif_p50_ms": round(
            float(_np.percentile(_np.asarray(q_times), 50)) * 1000, 1
        ),
        "warm_recompiles": prof["jax_recompiles_total"],
    }


def run_cold_start(config="example/simon-config.yaml") -> dict:
    """SIMON_BENCH=cold-start: time-to-first-200 for a fresh `simon
    serve` process, cold vs warm artifact store (incremental/store.py).
    Two daemon subprocesses run against the SAME --aot-store directory:
    the first compiles and persists every shape it touches, the second
    loads them — gated inline at zero new XLA compiles before its
    first answer (the zero-compile cold start, CI-mirrored). Value is
    the warm-store time-to-first-200."""
    import shutil
    import subprocess
    import tempfile
    import urllib.request

    store = tempfile.mkdtemp(prefix="simon-aot-bench-")
    body = json.dumps(
        {
            "apps": [
                {
                    "name": "cold",
                    "yaml": json.dumps(
                        {
                            "kind": "Pod",
                            "metadata": {
                                "name": "cold-1", "namespace": "bench"
                            },
                            "spec": {
                                "containers": [
                                    {
                                        "name": "c",
                                        "image": "img-cold",
                                        "resources": {
                                            "requests": {
                                                "cpu": "100m",
                                                "memory": "128Mi",
                                            }
                                        },
                                    }
                                ]
                            },
                        }
                    ),
                }
            ]
        }
    ).encode()

    def one_process() -> dict:
        t0 = time.perf_counter()
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "open_simulator_tpu.cli", "serve",
                "-f", config, "--port", "0", "--aot-store", store,
            ],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        base = None
        try:
            deadline = time.monotonic() + 300
            while time.monotonic() < deadline:
                line = proc.stdout.readline()
                if not line:
                    raise RuntimeError("serve exited before listening")
                if "listening on http://" in line:
                    base = line.split("listening on ")[1].split()[0]
                    break
            assert base, "serve never reported its port"
            req = urllib.request.Request(
                base + "/v1/simulate", data=body,
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=300) as resp:
                assert resp.status == 200
                answer = resp.read()
            t_first = time.perf_counter() - t0
            with urllib.request.urlopen(base + "/metrics", timeout=60) as resp:
                metrics = resp.read().decode()
            counts = {}
            for key in (
                "simon_jax_recompiles_total",
                "simon_aot_store_hit_total",
                "simon_aot_store_save_total",
            ):
                for ln in metrics.splitlines():
                    if ln.startswith(key + " "):
                        counts[key] = int(float(ln.split()[1]))
            return {
                "t_first_s": t_first,
                "answer": answer,
                "recompiles": counts.get("simon_jax_recompiles_total", -1),
                "hits": counts.get("simon_aot_store_hit_total", 0),
                "saves": counts.get("simon_aot_store_save_total", 0),
            }
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=10)

    try:
        cold = one_process()
        assert cold["saves"] >= 1, "cold process persisted no artifacts"
        warm = one_process()
        # THE gate: a warm store means the second process's first
        # answer costs zero new XLA compiles
        assert warm["recompiles"] == 0, (
            f"warm cold-start recompiled {warm['recompiles']} times"
        )
        assert warm["hits"] >= 1, "warm process never hit the store"
        assert warm["answer"] == cold["answer"], "answers diverged"
    finally:
        shutil.rmtree(store, ignore_errors=True)
    return {
        "cold_first_200_s": round(cold["t_first_s"], 3),
        "warm_first_200_s": round(warm["t_first_s"], 3),
        "speedup_x": round(cold["t_first_s"] / warm["t_first_s"], 2),
        "warm_recompiles": warm["recompiles"],
        "warm_store_hits": warm["hits"],
        "cold_saves": cold["saves"],
    }


def run_fleet_qps(
    n_clients=8, per_client=4, cluster_dir="example/cluster/demo"
) -> dict:
    """SIMON_BENCH=fleet-qps: the `simon fleet` router in front of
    1/2/4 supervised serve replica subprocesses (docs/FLEET.md), all
    sharing one AOT artifact store. Per fleet size: a balanced-tenancy
    client storm through the router (one warm storm first; replicas
    are separate processes, so N replicas should buy roughly Nx
    aggregate throughput on N spare cores). On the 2-replica fleet the
    headline failover is measured live: kill -9 the replica that owns
    a tenant's warm session after it has journaled a cluster delta,
    then time both the rerouted first-200 (the zero-loss path — same
    request id, next ring slot) and the full recovery (supervision
    pass detects the death, respawns into the slot, replays the dead
    replica's snapshot journal) — gated inline at zero new XLA
    compiles and deltaSeq parity on the replacement."""
    import shutil
    import signal as _signal
    import tempfile
    import threading
    import urllib.request

    from open_simulator_tpu.fleet.audit import FailoverAudit
    from open_simulator_tpu.fleet.replica import ReplicaProcess, serve_argv
    from open_simulator_tpu.fleet.router import FleetRouter

    root = tempfile.mkdtemp(prefix="simon-fleet-bench-")
    store = os.path.join(root, "store")
    # replica children run with cwd=fleet_dir, so the config they load
    # must name its cluster dir absolutely
    cfg = os.path.join(root, "simon-config.yaml")
    with open(cfg, "w", encoding="utf-8") as f:
        f.write(
            "apiVersion: simon/v1alpha1\n"
            "kind: Config\n"
            "metadata:\n"
            "  name: fleet-bench\n"
            "spec:\n"
            "  cluster:\n"
            f"    customConfig: {os.path.abspath(cluster_dir)}\n"
        )
    app = {
        "kind": "Deployment",
        "metadata": {"name": "fq", "namespace": "bench", "labels": {"app": "fq"}},
        "spec": {
            "replicas": 50,
            "template": {
                "spec": {
                    "containers": [
                        {
                            "name": "c",
                            "image": "img-fq",
                            "resources": {
                                "requests": {"cpu": "500m", "memory": "1Gi"}
                            },
                        }
                    ]
                }
            },
        },
    }
    body = json.dumps(
        {"apps": [{"name": "fq", "yaml": json.dumps(app)}]}
    ).encode()

    def post(url, data=body, tenant=None, timeout=600):
        headers = {"Content-Type": "application/json"}
        if tenant:
            headers["X-Simon-Tenant"] = tenant
        req = urllib.request.Request(url, data=data, headers=headers)
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()

    def balanced_tenants(router, slots, count):
        # one tenant per client, ring owners round-robined over the
        # slots: the fleet measures capacity, not hash-placement luck
        out, j = [], 0
        for i in range(count):
            want = slots[i % len(slots)]
            while True:
                t = f"bench-tenant-{j}"
                j += 1
                if router.ring.route_order(t)[0] == want:
                    out.append(t)
                    break
        return out

    def storm(base, tenants):
        errors = []

        def client(tenant):
            try:
                for _ in range(per_client):
                    post(base + "/v1/simulate", tenant=tenant)
            except Exception as e:  # noqa: BLE001 - surfaced via the raise below
                errors.append(e)

        threads = [
            threading.Thread(target=client, args=(t,)) for t in tenants
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise RuntimeError(f"fleet-qps client failed: {errors[0]}")

    def measure_failover(router, base, victim):
        # a tenant whose warm session lives on the victim's slot
        tenant = next(
            t
            for t in (f"victim-tenant-{i}" for i in range(256))
            if router.ring.route_order(t)[0] == victim.slot
        )
        # journal a roster delta on the victim and warm the post-delta
        # shape into the shared store, so the replacement has a real
        # delta stream to replay and nothing left to compile
        delta = json.dumps(
            {"kind": "node_join", "node": _make_node("fq-joined", 8, 32)}
        ).encode()
        status, _ = post(base + "/v1/cluster-delta", data=delta, tenant=tenant)
        assert status == 200, "cluster delta refused"
        post(base + "/v1/simulate", tenant=tenant)

        t_kill = time.perf_counter()
        os.kill(victim.pid, _signal.SIGKILL)
        victim.proc.wait(timeout=30)
        # the zero-loss path: the orphaned tenant's next request
        # reroutes to the next ring slot and still answers 200
        status, _ = post(base + "/v1/simulate", tenant=tenant)
        assert status == 200, "rerouted request did not answer 200"
        rerouted_s = time.perf_counter() - t_kill
        # full recovery: one supervision pass detects the death and
        # respawns into the slot (journal replay + store-warm boot),
        # then the replacement answers its first direct request
        router.probe_once()
        assert victim.alive() and victim.restarts == 1, "respawn failed"
        status, _ = post(victim.url + "/v1/simulate", tenant=tenant)
        assert status == 200, "replacement did not answer 200"
        recovery_s = time.perf_counter() - t_kill
        # close the audit episode honestly: the first 2xx answered
        # THROUGH the router from the respawned slot is the timeline's
        # first_200 checkpoint (router._note_answer -> audit)
        status, _ = post(base + "/v1/simulate", tenant=tenant)
        assert status == 200, "router did not answer from respawned slot"
        phases = {}
        if router.audit is not None and router.audit.completed:
            from open_simulator_tpu.fleet.audit import validate_audit_log

            validate_audit_log(router.audit.path)
            summary = router.audit.completed[-1]
            phases = {
                k: round(float(v), 3) for k, v in summary["phases"].items()
            }

        recompiles = -1
        with urllib.request.urlopen(
            victim.url + "/metrics", timeout=60
        ) as resp:
            for ln in resp.read().decode().splitlines():
                if ln.startswith("simon_jax_recompiles_total "):
                    recompiles = int(float(ln.split()[1]))
        assert recompiles == 0, (
            f"replacement paid {recompiles} XLA compiles; the shared "
            "store must serve them all"
        )
        with urllib.request.urlopen(
            victim.url + "/v1/state-digest", timeout=60
        ) as resp:
            digest = json.loads(resp.read().decode())
        assert digest["deltaSeq"] == 1, "replacement replayed no deltas"
        return {
            "failover_first_200_s": round(rerouted_s, 3),
            "failover_seconds": round(recovery_s, 3),
            "failover_phases": phases,
            "replacement_recompiles": recompiles,
            "replayed_delta_seq": digest["deltaSeq"],
        }

    qps = {}
    failover = {}
    try:
        for n in (1, 2, 4):
            fleet_dir = os.path.join(root, f"fleet-{n}")
            os.makedirs(fleet_dir)
            reps = []
            for i in range(n):
                slot = f"r{i}"
                snap = os.path.join(fleet_dir, f"{slot}.snapshot.jsonl")
                reps.append(
                    ReplicaProcess(
                        slot,
                        serve_argv(
                            cfg,
                            aot_store=store,
                            snapshot_path=snap,
                            extra=["--drain-timeout", "10"],
                        ),
                        fleet_dir,
                    )
                )
            # audit timeline (fleet/audit.py): every supervision event
            # lands in a fsync'd JSONL so measure_failover can report
            # the per-phase breakdown simon doctor gates on
            audit = FailoverAudit(
                os.path.join(fleet_dir, "failover-audit.jsonl")
            )
            router = FleetRouter(
                reps, port=0, probe_interval_s=0, forward_timeout_s=600.0,
                audit=audit,
            )
            router.start()  # started first so the finally can drain
            try:
                for r in reps:
                    r.spawn()  # serial: the first run populates the store
                base = f"http://{router.host}:{router.port}"
                slots = sorted(s for s in router.replicas)
                tenants = balanced_tenants(router, slots, n_clients)
                storm(base, tenants)  # warm: compile once, store-hit after
                t0 = time.perf_counter()
                storm(base, tenants)
                elapsed = time.perf_counter() - t0
                qps[n] = round(n_clients * per_client / elapsed, 2)
                if n == 2:
                    failover = measure_failover(router, base, reps[0])
            finally:
                router.shutdown()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    # what replication bought: the best fleet vs the 1-replica fleet
    # (on a core-starved box the best fleet may be smaller than the
    # largest one — report what the hardware actually delivered)
    q1 = qps[1]
    n_best = max(qps, key=lambda k: qps[k])
    return {
        "qps_by_replicas": {str(k): v for k, v in sorted(qps.items())},
        "qps_1": q1,
        "qps_max": qps[n_best],
        "replicas_max": n_best,
        "qps_scaling": round(qps[n_best] / q1, 2),
        "requests_per_fleet": n_clients * per_client,
        **failover,
    }


def run_failover_aged(
    levels=(0, 10_000, 50_000), interval=1_500, keep=2,
    n_nodes=64, n_pods=32,
) -> dict:
    """SIMON_BENCH=failover-aged: bounded-recovery restore cost as a
    replica AGES (runtime/checkpoint.py, docs/ROBUSTNESS.md). A serve
    session absorbs 0/10k/50k journaled deltas, then a replacement
    replica bootstraps from the snapshot two ways — full journal
    replay (checkpointing off) vs checkpoint restore + suffix replay
    (--checkpoint-interval {interval}) — and the time to the first
    what-if 200 after the kill (the in-process failover_first_200_s
    analogue; the XLA shape is warmed once up front so the cells
    measure recovery, not compiles — cold-start owns the compile
    story). Gated inline: the checkpointed replica's replayed suffix
    stays under ONE checkpoint interval at every aging level
    (fleet_replay_deltas_total, the acceptance bound — full replay
    grows as O(age), checkpointed recovery does not), every replica's
    state-digest triple is identical to the live session it replaces,
    and the aged cells add zero XLA recompiles after the warmup."""
    import shutil
    import tempfile

    from open_simulator_tpu.fleet.replay import replay_into_session
    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.obs import profile as obs_profile
    from open_simulator_tpu.runtime.checkpoint import (
        CheckpointManager,
        checkpoint_dir,
    )
    from open_simulator_tpu.scheduler.core import AppResource
    from open_simulator_tpu.serve.session import (
        Session,
        WhatIfRequest,
        session_checkpoint_state,
        verify_payload_digest,
    )
    from open_simulator_tpu.serve.sessions import (
        SessionCache,
        open_snapshot,
        serve_keep_record,
    )
    from open_simulator_tpu.testing import make_fake_pod
    from open_simulator_tpu.twin.deltas import (
        POD_ARRIVE,
        POD_EVICT,
        ClusterDelta,
    )
    from open_simulator_tpu.utils.trace import COUNTERS

    def build_cluster():
        cluster = ResourceTypes()
        cluster.nodes = [
            _make_node(f"aged-n-{i:03d}", 64, 256, {"zone": f"z{i % 4}"})
            for i in range(n_nodes)
        ]
        cluster.pods = [
            make_fake_pod(f"aged-p{i:03d}", "default", "250m", "512Mi")
            for i in range(n_pods)
        ]
        return cluster

    app = ResourceTypes()
    app.pods = [make_fake_pod("aged-query", "default", "250m", "512Mi")]
    req = WhatIfRequest(apps=[AppResource("aged-query", app)])
    # warm the what-if shape on a throwaway session (NOT the live ones:
    # materializing a committed scan there would turn every journaled
    # delta into an incremental re-simulation and measure the wrong
    # thing — aging cost is journal arithmetic, not device work)
    Session(build_cluster()).evaluate_batch([req])
    prof0 = obs_profile.snapshot()

    cells = {}
    root = tempfile.mkdtemp(prefix="simon-aged-")
    try:
        for n_deltas in levels:
            cell = {}
            for arm in ("full_replay", "checkpoint"):
                session = Session(build_cluster())
                path = os.path.join(
                    root, f"aged-{n_deltas}-{arm}.snapshot.jsonl"
                )
                journal = open_snapshot(path)
                cache = SessionCache(capacity=2, snapshot=journal)
                mgr = None
                if arm == "checkpoint":
                    mgr = CheckpointManager(
                        checkpoint_dir(path),
                        interval=interval,
                        keep=keep,
                        capture=lambda s=session: session_checkpoint_state(s),
                        materialized_digest=(
                            lambda p, s=session: verify_payload_digest(s, p)
                        ),
                        journal=journal,
                        keep_record=serve_keep_record(session.fingerprint),
                        label="bench-aged",
                        synchronous=True,
                    )
                # age the replica: arrive/evict pairs, journaled with
                # their sequence numbers exactly as the serve delta
                # handler records them (roster returns to the base
                # shape, so every cell's first answer is shape-warm)
                for i in range(n_deltas // 2):
                    name = f"aged-churn-{i:05d}"
                    pod = make_fake_pod(name, "default", "250m", "512Mi")
                    for d in (
                        ClusterDelta(kind=POD_ARRIVE, pod=pod),
                        ClusterDelta(
                            kind=POD_EVICT, namespace="default", name=name
                        ),
                    ):
                        out, seq = session.apply_delta_seq(d)
                        assert out == "applied", f"delta not applied: {out}"
                        cache.record_delta(
                            session.fingerprint, d.as_record(), seq=seq
                        )
                        if mgr is not None:
                            mgr.note_delta(seq)
                if mgr is not None:
                    assert mgr.last_error is None, mgr.last_error
                journal.close()
                # the kill: a replacement replica bootstraps from the
                # snapshot and answers its first what-if
                ctr0 = COUNTERS.get("fleet_replay_deltas_total")
                t0 = time.perf_counter()
                replica = Session(build_cluster())
                summary = replay_into_session(
                    replica, path, use_checkpoints=(arm == "checkpoint")
                )
                restore_s = time.perf_counter() - t0
                replies = replica.evaluate_batch([req])
                first_200_s = time.perf_counter() - t0
                assert replies[0].status == 200, replies[0].status
                replayed = COUNTERS.get("fleet_replay_deltas_total") - ctr0
                # dict-identity gate: the replacement reports the same
                # state-digest triple the dead replica would have
                assert (
                    replica.fingerprint,
                    replica.delta_seq,
                    replica.state_digest(),
                ) == (
                    session.fingerprint,
                    session.delta_seq,
                    session.state_digest(),
                ), f"aged replica diverged at {n_deltas}/{arm}"
                if arm == "checkpoint":
                    # the acceptance bound: recovery replays at most one
                    # checkpoint interval of deltas, however old the
                    # replica — counter-gated, not summary-trusted
                    assert replayed <= interval, (
                        f"replayed {replayed} deltas > interval {interval}"
                    )
                cell[arm] = {
                    "restore_s": round(restore_s, 4),
                    "first_200_s": round(first_200_s, 4),
                    "replayed_deltas": replayed,
                    "skipped_prefix": summary["skippedPrefix"],
                    "restored_seq": (
                        summary["checkpoint"]["deltaSeq"]
                        if summary["checkpoint"]
                        else 0
                    ),
                }
            cells[str(n_deltas)] = cell
    finally:
        shutil.rmtree(root, ignore_errors=True)
    prof = obs_profile.delta(prof0)
    assert prof["jax_recompiles_total"] == 0, (
        f"aged failover recompiled: {prof['jax_recompiles_total']}"
    )
    worst = cells[str(max(levels))]
    return {
        "nodes": n_nodes,
        "pods": n_pods,
        "levels": list(levels),
        "interval": interval,
        "keep": keep,
        "cells": cells,
        "restore_seconds": worst["checkpoint"]["restore_s"],
        "first_200_s": worst["checkpoint"]["first_200_s"],
        "full_replay_first_200_s": worst["full_replay"]["first_200_s"],
        "replayed_deltas": worst["checkpoint"]["replayed_deltas"],
        "speedup_x": round(
            worst["full_replay"]["first_200_s"]
            / max(worst["checkpoint"]["first_200_s"], 1e-9),
            2,
        ),
        "warm_recompiles": prof["jax_recompiles_total"],
    }


def run_timeline(n_arrivals=1000, n_nodes=48) -> dict:
    """SIMON_BENCH=timeline: the discrete-event timeline
    (docs/TIMELINE.md) playing a 1000-arrival seeded synthetic trace
    (Poisson arrivals, exponential lifetimes, spot reclaims) through
    three autoscaler policies — static / threshold / capacity-probe —
    as batched scenario rows. Measures arrival steps/s end to end and
    the windowed-batching contract: device dispatches per window and
    per policy (the point of the stepper — a 1000-step trace must cost
    a handful of dispatches, not 1000 simulate() calls), with zero
    warm recompiles asserted, not assumed (the pinned-scenario jit is
    process-wide, parallel/sweep.py _scenario_rows_jit)."""
    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.obs import profile as obs_profile
    from open_simulator_tpu.timeline.autoscaler import parse_policies
    from open_simulator_tpu.timeline.compare import run_policies
    from open_simulator_tpu.timeline.events import (
        SyntheticSpec,
        generate_synthetic,
    )

    nodes = [
        _make_node(f"tl-n-{i:04d}", 16, 64, {"zone": f"z{i % 8}"})
        for i in range(n_nodes)
    ]
    cluster = ResourceTypes()
    cluster.nodes = nodes
    new_node = _make_node("tl-template", 32, 128)
    spec = SyntheticSpec(
        arrivals=n_arrivals,
        arrival_rate=1.0,
        mean_lifetime_s=300.0,
        long_running_frac=0.7,
        spot_frac=0.1,
        spot_hazard=1 / 2500.0,
        seed=11,
    )
    events = generate_synthetic(spec, [n["metadata"]["name"] for n in nodes])
    n_policies = 3

    def once():
        cmp_ = run_policies(
            cluster,
            events,
            parse_policies(["static:4", "threshold", "probe"]),
            new_node_spec=new_node,
            max_nodes=16,
            cadence_s=100.0,
            warmup_s=30.0,
            engine="tpu",
        )
        for tl in cmp_.policies:
            assert tl.final is not None and tl.final.pending == 0, (
                f"{tl.policy}: {tl.final.pending} pods still pending at the "
                "horizon — the bench workload must end drained"
            )
        return cmp_

    once()  # cold: compiles the window scan shapes
    obs0 = obs_profile.snapshot()
    elapsed, spread, cmp_ = _timed(once)
    prof = obs_profile.delta(obs0)
    assert prof["jax_recompiles_total"] == 0, (
        f"warm timeline runs recompiled {prof['jax_recompiles_total']}x"
    )
    runs = spread["runs"]
    per_policy = prof["jax_dispatches_total"] / runs / n_policies
    return {
        "nodes": n_nodes,
        "arrivals": n_arrivals,
        "events": cmp_.events,
        "windows": cmp_.windows,
        "policies": n_policies,
        "steps_per_sec": round(n_arrivals / elapsed, 1),
        "elapsed_s": round(elapsed, 3),
        "dispatches_per_window": round(
            prof["jax_dispatches_total"] / runs / max(cmp_.windows, 1), 2
        ),
        "dispatches_per_policy": round(per_policy, 1),
        "warm_recompiles": prof["jax_recompiles_total"],
        "spread": spread,
    }


def run_mesh_scan(n_scenarios=64, n_pods=48) -> dict:
    """SIMON_BENCH=mesh-scan: mesh-sharded scanning (ROADMAP item 1,
    parallel/mesh.py). A nodes x devices grid of chaos-substrate
    scenario batches (seeded node-outage masks through
    probe_scenarios): for each cell the batch dispatches with the
    scenario axis sharded over the first D devices, and the recorded
    number is rows/s plus the SPEEDUP RATIO of the full mesh vs the
    1-device dispatch of the same batch. Efficiency divides the ratio
    by the mesh's EFFECTIVE parallelism (device count on real
    accelerators; min(devices, host cores) on the forced host-platform
    CPU mesh, where virtual devices share cores) so the gate measures
    against what the hardware can physically deliver. SIMON_MESH_GATE
    (e.g. 0.7) makes the run FAIL when the largest grid's ratio falls
    under gate x effective parallelism — the CI contract for the
    >= 0.7*N scenario-axis scaling target. A node-axis-sharded probe
    is also conformance-checked elementwise against the unsharded scan
    (the 100k-node path's shape, at bench-tractable size)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.parallel import mesh as mesh_mod
    from open_simulator_tpu.parallel.sweep import CapacitySweep
    from open_simulator_tpu.scheduler.core import AppResource
    from open_simulator_tpu.testing import (
        make_fake_deployment,
        make_fake_node,
    )

    devices = jax.devices()
    ladder = [d for d in (1, 2, 4, 8) if d <= len(devices)]
    if len(devices) not in ladder:
        ladder.append(len(devices))
    rng = np.random.RandomState(7)

    def build(n_nodes):
        cluster = ResourceTypes()
        cluster.nodes = [
            make_fake_node(f"mesh-n-{i:05d}", "16", "64Gi")
            for i in range(n_nodes)
        ]
        res = ResourceTypes()
        res.deployments = [
            make_fake_deployment("web", "mesh", n_pods, "500m", "512Mi")
        ]
        return CapacitySweep(cluster, [AppResource("mesh", res)], None, 0)

    grid = []
    ratios = {}
    rows_headline = None
    eff = 1
    for n_nodes in (256, 2048):
        sweep = build(n_nodes)
        valids = np.ones((n_scenarios, sweep.n), bool)
        for s in range(n_scenarios):
            valids[s, rng.choice(sweep.n, size=8, replace=False)] = False
        actives = np.ones((n_scenarios, len(sweep.pods)), bool)
        pins = np.tile(
            np.asarray(sweep.batch.pinned_node), (n_scenarios, 1)
        )
        rates = {}
        for n_dev in ladder:
            sweep.mesh = (
                None if n_dev == 1
                else Mesh(np.array(devices[:n_dev]), (mesh_mod.MESH_AXIS,))
            )
            sweep.probe_scenarios(valids, actives, pins, site="bench")  # warm
            elapsed, spread, _ = _timed(
                lambda: sweep.probe_scenarios(
                    valids, actives, pins, site="bench"
                )
            )
            rates[n_dev] = round(n_scenarios / elapsed, 1)
            grid.append(
                {
                    "nodes": n_nodes,
                    "devices": n_dev,
                    "rows_per_sec": rates[n_dev],
                    "elapsed_s": round(elapsed, 3),
                    "spread": spread,
                }
            )
        max_dev = ladder[-1]
        ratio = round(rates[max_dev] / max(rates[1], 1e-9), 2)
        grid[-1]["speedup_x"] = ratio
        ratios[n_nodes] = ratio
        if n_nodes == 2048:
            rows_headline = rates[max_dev]
        eff = mesh_mod.effective_parallelism(sweep.mesh)
        # node-axis conformance at this grid size: the sharded scan is
        # only a scale claim if its placements are the unsharded ones
        if sweep.mesh is not None:
            valid0 = valids[0]
            active0 = sweep.pod_active(valid0)
            pl, _u, _c, _m, _v = mesh_mod.run_node_sharded(
                sweep.mesh, sweep.static, sweep.init,
                sweep.batch.class_of_pod, sweep.batch.pinned_node,
                valid0, active0, sweep.features,
            )
            ref = sweep._probe_xla(-1, valid0)
            assert (pl == ref.placements).all(), (
                f"node-sharded placements diverged at {n_nodes} nodes"
            )
    # the gate reads the grid's BEST speedup cell: on a real
    # multi-chip mesh every cell should clear 0.7*N (chips do not
    # share cores), but on the forced host-platform mesh only the
    # cells whose 1-device baseline is single-core-bound can exhibit
    # scaling at all — XLA:CPU's intra-op threading already spreads
    # the big-grid baseline over every core, so the marginal speedup
    # there measures the host, not the sharding
    gate = os.environ.get("SIMON_MESH_GATE")
    best_ratio = max(ratios.values())
    efficiency = round(best_ratio / max(eff, 1), 3)
    if gate:
        want = float(gate) * eff
        assert best_ratio >= want, (
            f"mesh-scan speedup {best_ratio}x (best grid cell; "
            f"{ratios}) under the gate {float(gate)} x {eff} effective "
            f"device(s) = {want}x"
        )
    return {
        "grid": grid,
        "scenarios": n_scenarios,
        "pods": n_pods,
        "devices": ladder[-1],
        "effective_parallelism": eff,
        "rows_per_sec": round(rows_headline, 1),
        "speedup_x": best_ratio,
        "speedup_by_nodes": ratios,
        "efficiency": efficiency,
        "node_axis_conformance": "ok",
    }


def run_sample() -> dict:
    """SIMON_BENCH=sample: select_host="sample" (reservoir sampling
    with the Go math/rand stream carried in the scan state, r5) vs the
    first-max default on the SAME XLA-scan path — sample mode is
    XLA-scan-only (the Pallas kernel rejects it), so the honest
    comparison holds the engine constant. e2e simulate() wall-clock on
    the default 20k-pod x 10k-node scenario."""
    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.models.workloads import reset_name_counter
    from open_simulator_tpu.ops import pallas_scan
    from open_simulator_tpu.scheduler.core import AppResource, simulate

    nodes, pods = build_scenario()
    cluster = ResourceTypes()
    cluster.nodes = nodes
    res = ResourceTypes()
    res.pods = pods
    apps = [AppResource("bench", res)]

    def run(select_host):
        reset_name_counter()
        return simulate(cluster, apps, engine="tpu", select_host=select_host)

    run("sample")  # compile/warm
    elapsed_s, spread_s, result = _timed(lambda: run("sample"))
    # first-max on the same XLA path (kernel disabled) for the ratio
    prev = pallas_scan.FORCE_ENABLE
    pallas_scan.FORCE_ENABLE = False
    try:
        run("first-max")
        elapsed_f, spread_f, _ = _timed(lambda: run("first-max"))
    finally:
        pallas_scan.FORCE_ENABLE = prev
    return {
        "elapsed_s": elapsed_s,
        "spread": spread_s,
        "pods_per_sec": len(pods) / elapsed_s,
        "firstmax_pods_per_sec": len(pods) / elapsed_f,
        "ratio": elapsed_s / elapsed_f,
        "scheduled": len(pods) - len(result.unscheduled_pods),
        "total": len(pods),
        "nodes": len(nodes),
    }


def run_conformance_fuzz(n_nodes=1000, n_pods=2000, seed=0) -> dict:
    """Hardware conformance check (the only real-TPU numerics check —
    unit tests run the kernel in interpret mode on CPU): build a
    feature-mixed scenario (affinity/spread terms + hostPorts + scalar
    resources + nodeName pins), run the COMPILED Pallas kernel and the
    XLA scan on identical inputs, and require placement-for-placement
    equality. Runs inside `all` so every recorded bench is backed by a
    fresh on-device conformance pass."""
    import numpy as np

    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.ops import pallas_scan
    from open_simulator_tpu.ops import scan as scan_ops
    from open_simulator_tpu.ops.encode import (
        encode_batch,
        encode_cluster,
        encode_dynamic,
        features_of_batch,
        to_scan_static,
        to_scan_state,
    )
    from open_simulator_tpu.scheduler.core import AppResource
    from open_simulator_tpu.scheduler.queues import expand_apps
    from open_simulator_tpu.scheduler.oracle import Oracle
    from open_simulator_tpu.testing import build_affinity_stress

    rng = np.random.RandomState(seed)
    nodes, stss = build_affinity_stress(
        n_nodes=n_nodes, n_sts=20, replicas=max(n_pods // 20, 1), zones=8
    )
    res = ResourceTypes()
    res.stateful_sets = stss
    pods = expand_apps([AppResource("fuzz", res)], nodes)[0]
    # mix in the non-term feature surface: ports, scalars, pins, and
    # open-local storage (r5: the storage block rides the kernel too)
    import json as _json

    for node in nodes[: n_nodes // 2]:
        node["status"]["allocatable"]["example.com/accel"] = "4"
    gi = 1 << 30
    for node in nodes[: n_nodes // 3]:
        node["metadata"].setdefault("annotations", {})[
            "simon/node-local-storage"
        ] = _json.dumps(
            {
                "vgs": [
                    {"name": "a", "capacity": str(100 * gi), "requested": "0"}
                ],
                "devices": [
                    {
                        "name": "/dev/vdb",
                        "capacity": str(120 * gi),
                        "mediaType": "ssd",
                        "isAllocated": "false",
                    }
                ],
            }
        )
    import copy

    for i, pod in enumerate(pods[:n_pods]):
        k = rng.randint(0, 40)
        if k > 3:
            continue
        # replica clones share nested spec objects (workloads.py
        # _expand_template, read-only-after-expansion contract): give
        # this pod its own deep copy before stamping per-pod features,
        # or the whole template's replicas would inherit them. Each
        # mutated pod mints a fresh pod class — deliberately pushing the
        # batch past 128 classes so the kernel's multi-row class-column
        # tables (col_u dynamic sublane reads) get a fresh hardware
        # check every run, while staying under the 512-class scope.
        pod["spec"] = spec = copy.deepcopy(pod["spec"])
        if k == 0:
            port = 9000 + int(rng.randint(0, 3))
            spec["containers"][0]["ports"] = [
                {"containerPort": port, "hostPort": port, "protocol": "TCP"}
            ]
        elif k == 1:
            spec["containers"][0]["resources"]["requests"][
                "example.com/accel"
            ] = str(1 + i % 4)
        elif k == 2:
            spec["nodeName"] = nodes[int(rng.randint(0, n_nodes))]["metadata"]["name"]
        else:
            vols = (
                [{"kind": "LVM", "size": str((1 + i % 8) * gi),
                  "scName": "open-local-lvm"}]
                if i % 3
                else [{"kind": "SSD", "size": str(60 * gi),
                       "scName": "open-local-device-ssd"}]
            )
            pod["metadata"] = meta = dict(pod["metadata"])
            meta["annotations"] = dict(meta.get("annotations") or {})
            meta["annotations"]["simon/pod-local-storage"] = _json.dumps(
                {"volumes": vols}
            )
    pods = pods[:n_pods]

    oracle = Oracle(nodes)
    cluster = encode_cluster(oracle)
    batch = encode_batch(oracle, cluster, pods)
    # the deliberate point of the mutation mix: cross the 128-class
    # boundary so the kernel's multi-row class-column tables get a
    # hardware check (content-keyed class dedup could silently collapse
    # this if the vocabularies shrink)
    assert batch.u > 128, f"fuzz scenario dedup'd to {batch.u} classes"
    dyn = encode_dynamic(oracle, cluster)
    features = features_of_batch(cluster, batch)
    ones_p = np.ones(len(pods), bool)
    ones_n = np.ones(cluster.n, bool)

    if not pallas_scan.should_use():
        return {"checked": 0, "mismatches": 0, "note": "no TPU backend"}
    plan = pallas_scan.build_plan(cluster, batch, dyn, features)
    if plan is None:
        # a TPU is present but the fuzz scenario fell out of kernel
        # scope — that is scenario drift, not an environment condition:
        # fail loudly rather than void the hardware check
        raise AssertionError(
            "conformance fuzz scenario no longer rides the kernel: "
            f"{pallas_scan.last_reject() or 'rejected'}"
        )
    place_k, _ = pallas_scan.run_scan_pallas(
        plan, batch.class_of_pod, ones_p, ones_n, pinned=batch.pinned_node
    )
    import jax.numpy as jnp

    static = to_scan_static(cluster, batch)
    init = to_scan_state(dyn, batch)
    place_x, _ = scan_ops.run_scan(
        static,
        init,
        jnp.asarray(batch.class_of_pod),
        jnp.asarray(batch.pinned_node),
        features=features,
    )
    place_k = np.asarray(place_k)
    place_x = np.asarray(place_x)
    # normalize the no-node encodings before comparing
    place_k = np.where(place_k < 0, -1, place_k)
    place_x = np.where(place_x < 0, -1, place_x)
    mism = int((place_k != place_x).sum())
    if mism:
        idx = np.nonzero(place_k != place_x)[0][:5]
        raise AssertionError(
            f"pallas/xla conformance fuzz FAILED: {mism} of {len(pods)} "
            f"placements differ (first at pods {idx.tolist()}: "
            f"kernel={place_k[idx].tolist()} xla={place_x[idx].tolist()})"
        )
    # third flavor: the STREAMED term layout (HBM state + per-pod row
    # gather — what the kernel auto-selects past the VMEM cliff),
    # force-built on the same scenario so the compiled DMA path gets
    # the same every-bench hardware check as the resident kernel
    prev_force = pallas_scan.STREAM_FORCE
    pallas_scan.STREAM_FORCE = True
    try:
        plan_s = pallas_scan.build_plan(cluster, batch, dyn, features)
        if plan_s is None or not plan_s.terms.cfg.stream:
            raise AssertionError(
                "conformance fuzz could not build the streamed plan: "
                f"{pallas_scan.last_reject() or 'rejected'}"
            )
        place_s, _ = pallas_scan.run_scan_pallas(
            plan_s, batch.class_of_pod, ones_p, ones_n,
            pinned=batch.pinned_node,
        )
    finally:
        pallas_scan.STREAM_FORCE = prev_force
    place_s = np.where(np.asarray(place_s) < 0, -1, place_s)
    mism_s = int((place_s != place_x).sum())
    if mism_s:
        idx = np.nonzero(place_s != place_x)[0][:5]
        raise AssertionError(
            f"streamed-terms conformance fuzz FAILED: {mism_s} of "
            f"{len(pods)} placements differ (first at pods {idx.tolist()}: "
            f"stream={place_s[idx].tolist()} xla={place_x[idx].tolist()})"
        )
    gpu = _gpu_conformance_fuzz(seed)
    return {"checked": 2 * len(pods) + gpu["checked"], "mismatches": 0}


def _gpu_conformance_fuzz(seed=0, n_nodes=500, n_pods=1500) -> dict:
    """Second fuzz flavor: gpu device packing + affinity terms together
    on the compiled kernel (no pins — gpu+pins is out of scope)."""
    import copy

    import jax.numpy as jnp
    import numpy as np

    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.ops import pallas_scan
    from open_simulator_tpu.ops import scan as scan_ops
    from open_simulator_tpu.ops.encode import (
        encode_batch,
        encode_cluster,
        encode_dynamic,
        features_of_batch,
        to_scan_static,
        to_scan_state,
    )
    from open_simulator_tpu.scheduler.core import AppResource
    from open_simulator_tpu.scheduler.queues import expand_apps
    from open_simulator_tpu.scheduler.oracle import Oracle
    from open_simulator_tpu.testing import build_affinity_stress, with_node_gpu

    rng = np.random.RandomState(seed + 1)
    nodes, stss = build_affinity_stress(
        n_nodes=n_nodes, n_sts=10, replicas=max(n_pods // 10, 1), zones=8
    )
    for node in nodes:
        with_node_gpu(4, "32")(node)
    res = ResourceTypes()
    res.stateful_sets = stss
    pods = expand_apps([AppResource("gfuzz", res)], nodes)[0]
    for i, pod in enumerate(pods[:n_pods]):
        if rng.randint(0, 5) != 0:
            continue
        pod["metadata"] = copy.deepcopy(pod["metadata"])
        mem = int(rng.choice([2, 4, 8, 17]))
        cnt = int(rng.choice([1, 1, 1, 2]))
        pod["metadata"].setdefault("annotations", {}).update(
            {
                "alibabacloud.com/gpu-mem": str(mem),
                "alibabacloud.com/gpu-count": str(cnt),
            }
        )
    pods = pods[:n_pods]
    oracle = Oracle(nodes)
    cluster = encode_cluster(oracle)
    batch = encode_batch(oracle, cluster, pods)
    dyn = encode_dynamic(oracle, cluster)
    features = features_of_batch(cluster, batch)
    assert features.gpu and features.terms
    plan = pallas_scan.build_plan(cluster, batch, dyn, features)
    if plan is None:
        raise AssertionError(
            "gpu conformance fuzz scenario no longer rides the kernel: "
            f"{pallas_scan.last_reject() or 'rejected'}"
        )
    ones_p = np.ones(len(pods), bool)
    ones_n = np.ones(cluster.n, bool)
    place_k, _ = pallas_scan.run_scan_pallas(
        plan, batch.class_of_pod, ones_p, ones_n, pinned=batch.pinned_node
    )
    static = to_scan_static(cluster, batch)
    init = to_scan_state(dyn, batch)
    place_x, _ = scan_ops.run_scan(
        static,
        init,
        jnp.asarray(batch.class_of_pod),
        jnp.asarray(batch.pinned_node),
        features=features,
    )
    place_k = np.where(np.asarray(place_k) < 0, -1, np.asarray(place_k))
    place_x = np.where(np.asarray(place_x) < 0, -1, np.asarray(place_x))
    mism = int((place_k != place_x).sum())
    if mism:
        raise AssertionError(
            f"gpu conformance fuzz FAILED: {mism} of {len(pods)} differ"
        )
    return {"checked": len(pods), "mismatches": 0}


def run_priority(n_priority=5) -> dict:
    """SIMON_BENCH=priority: the default 20k-pod x 10k-node batch with a
    few high-priority pods mixed in. Round 2 sent any such batch to the
    O(P*N) serial oracle (minutes, unmeasured — VERDICT r2 weak #4); the
    hybrid split now serial-schedules only the priority pods and keeps
    the zero-priority bulk on the fused scan. End-to-end through the
    Simulator: sort, split, serial head, scan, host replay."""
    import copy

    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.scheduler.core import AppResource, simulate

    nodes, pods = build_scenario()
    for i in range(n_priority):
        pods[i] = copy.deepcopy(pods[i])
        pods[i]["metadata"]["name"] = f"critical-{i}"
        pods[i]["spec"]["priority"] = 100000
    cluster = ResourceTypes()
    cluster.nodes = nodes
    res = ResourceTypes()
    res.pods = pods
    apps = [AppResource("bench", res)]
    simulate(cluster, apps, engine="tpu")  # warm/compile
    elapsed, spread, result = _timed(lambda: simulate(cluster, apps, engine="tpu"))
    return {
        "elapsed_s": elapsed,
        "spread": spread,
        "pods_per_sec": len(pods) / elapsed,
        "scheduled": len(pods) - len(result.unscheduled_pods),
        "total": len(pods),
        "priority_pods": n_priority,
        "nodes": len(nodes),
    }


def run_tier_stress(n_nodes=128, n_zero=1000) -> dict:
    """SIMON_BENCH=tier-stress: the escape-HEAVY worst case of the
    tiered priority engine — every node is packed with a bound
    zero-priority victim, and more preemptors than MAX_SCAN_ESCAPES
    arrive at distinct priorities (one tier each). Each preemptor
    fails the optimistic scan AND passes the serial PostFilter gates,
    so the engine pays one serial escape + one masked re-dispatch per
    preemptor (no re-encode: the batch encodes once,
    engine.begin_batch) until the escape cap trips and the remainder
    finishes on the serial oracle. Measures the cost of the
    MAX_SCAN_ESCAPES ladder itself — rounds, escapes, serial-tail
    size — which the unit tests only pin semantically
    (tests/test_preemption.py, tests/test_tiered_scan.py). A
    PodDisruptionBudget selects every victim: that puts them out of
    the device dry run's scope (ops/preempt.py), which would otherwise
    preempt for every preemptor inside the one scan."""
    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.scheduler.core import (
        MAX_SCAN_ESCAPES,
        AppResource,
        simulate,
    )
    from open_simulator_tpu.utils.trace import GLOBAL

    nodes = [_make_node(f"tier-node-{i:04d}", 1, 4) for i in range(n_nodes)]
    victims = []
    for i in range(n_nodes):
        victims.append(
            {
                "metadata": {
                    "name": f"victim-{i:04d}",
                    "namespace": "bench",
                    "labels": {"role": "victim"},
                },
                "spec": {
                    "nodeName": f"tier-node-{i:04d}",
                    "containers": [
                        {
                            "name": "c",
                            "image": "v",
                            "resources": {
                                "requests": {"cpu": "800m", "memory": "1Gi"}
                            },
                        }
                    ],
                    "schedulerName": "default-scheduler",
                },
            }
        )
    n_pre = MAX_SCAN_ESCAPES + 8
    pods = []
    for i in range(n_pre):
        pods.append(
            {
                "metadata": {
                    "name": f"pre-{i:03d}",
                    "namespace": "bench",
                    "labels": {},
                },
                "spec": {
                    "priority": 100000 - i,  # one tier per preemptor
                    "containers": [
                        {
                            "name": "c",
                            "image": "p",
                            "resources": {
                                "requests": {"cpu": "800m", "memory": "1Gi"}
                            },
                        }
                    ],
                    "schedulerName": "default-scheduler",
                },
            }
        )
    for i in range(n_zero):
        pods.append(
            {
                "metadata": {
                    "name": f"zero-{i:05d}",
                    "namespace": "bench",
                    "labels": {},
                },
                "spec": {
                    "containers": [
                        {
                            "name": "c",
                            "image": "z",
                            "resources": {
                                "requests": {"cpu": "50m", "memory": "8Mi"}
                            },
                        }
                    ],
                    "schedulerName": "default-scheduler",
                },
            }
        )
    cluster = ResourceTypes()
    cluster.nodes = nodes
    cluster.pods = victims
    cluster.pod_disruption_budgets = [
        {
            "kind": "PodDisruptionBudget",
            "metadata": {"name": "victims", "namespace": "bench"},
            "spec": {"selector": {"matchLabels": {"role": "victim"}}},
        }
    ]
    res = ResourceTypes()
    res.pods = pods
    apps = [AppResource("bench", res)]
    simulate(cluster, apps, engine="tpu")  # warm/compile
    GLOBAL.reset()
    elapsed, spread, result = _timed(lambda: simulate(cluster, apps, engine="tpu"))
    total = len(pods)
    return {
        "elapsed_s": elapsed,
        "spread": spread,
        "pods_per_sec": total / elapsed,
        "scheduled": total - len(result.unscheduled_pods),
        "total": total,
        "preemptors": n_pre,
        "nodes": n_nodes,
        "rounds": GLOBAL.notes.get("priority-scan-rounds"),
        "escapes": GLOBAL.notes.get("priority-scan-escapes"),
        "tiers": GLOBAL.notes.get("priority-scan-tiers"),
        "serial_tail": GLOBAL.notes.get("priority-scan-serial-tail"),
        "preemptions": len(result.preemptions),
    }


def _phase_breakdown(runs=TIMED_RUNS) -> str:
    """Per-run averages of the priority-path phases recorded since the
    last GLOBAL.reset() — the sort/encode/scan/replay split the tiered
    engine trace-notes (utils/trace.py phase_seconds)."""
    from open_simulator_tpu.utils.trace import GLOBAL

    def ms(name):
        return f"{GLOBAL.phase_seconds(name) / runs * 1000:.0f}"

    return (
        f"expand/sort/encode/scan/replay = {ms('host/expand')}/"
        f"{ms('priority/sort')}/{ms('engine/encode')}/{ms('engine/scan')}/"
        f"{ms('engine/replay')} ms"
    )


def run_priority_dense(frac=0.75) -> dict:
    """SIMON_BENCH=priority-dense: the round-3 cliff (VERDICT r3 weak
    #2) — 20k pods x 10k nodes where 75% of pods carry a non-zero
    priority across 8 distinct classes. Round 3 routed the whole
    non-zero segment to the pure-Python serial oracle ("serial
    (minutes, unmeasured)", docs/PERFORMANCE.md); the round-4
    priority-scan engine places it with one optimistic ordered scan
    per preemption escape (zero escapes here: the cluster fits), so
    dense-priority throughput should sit near the plain scan rate.
    End-to-end through the Simulator: sort, scan, serial escapes,
    host replay."""
    import copy

    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.scheduler.core import AppResource, simulate
    from open_simulator_tpu.utils.trace import GLOBAL

    nodes, pods = build_scenario()
    tiers = [100000, 10000, 5000, 1000, 500, 100, 50, 10]
    n_dense = int(len(pods) * frac)
    for i in range(n_dense):
        pods[i] = copy.deepcopy(pods[i])
        pods[i]["spec"]["priority"] = tiers[i % len(tiers)]
    cluster = ResourceTypes()
    cluster.nodes = nodes
    res = ResourceTypes()
    res.pods = pods
    apps = [AppResource("bench", res)]
    simulate(cluster, apps, engine="tpu")  # warm/compile
    GLOBAL.reset()
    elapsed, spread, result = _timed(lambda: simulate(cluster, apps, engine="tpu"))
    return {
        "elapsed_s": elapsed,
        "spread": spread,
        "pods_per_sec": len(pods) / elapsed,
        "scheduled": len(pods) - len(result.unscheduled_pods),
        "total": len(pods),
        "priority_pods": n_dense,
        "scan_rounds": GLOBAL.notes.get("priority-scan-rounds"),
        "escapes": GLOBAL.notes.get("priority-scan-escapes"),
        "tiers": GLOBAL.notes.get("priority-scan-tiers"),
        "phases": _phase_breakdown(),
        "nodes": len(nodes),
    }


def build_storage_scenario(n_nodes=10_000, n_pods=20_000, n_vgs=2):
    """SIMON_BENCH=storage: the open-local VG/device path at scale
    (VERDICT r3 weak #3 — previously unmeasured). Every node carries
    the simon/node-local-storage annotation with `n_vgs` LVM VGs and
    two exclusive devices; 90% of pods bin-pack 1-3 LVM volumes, 10%
    claim an exclusive SSD/HDD device. On the fused kernel since r5
    (host-precomputed f64 score tables) — EXCEPT shapes past the
    kernel's scope caps: `n_vgs > 4` rejects the plan
    (pallas_scan._build_storage) and the batch rides the XLA scan,
    which SIMON_BENCH=storage-fallback measures (VERDICT r5 missing
    #2: the fallback regression surface was invisible)."""
    import json as _json

    gi = 1 << 30
    nodes = []
    for i in range(n_nodes):
        storage = {
            "vgs": [
                {
                    "name": f"pool-{chr(ord('a') + v)}",
                    "capacity": str((100 + 100 * (v % 2)) * gi),
                    "requested": "0",
                }
                for v in range(n_vgs)
            ],
            "devices": [
                {
                    "name": "/dev/vdb",
                    "capacity": str(120 * gi),
                    "mediaType": "ssd",
                    "isAllocated": "false",
                },
                {
                    "name": "/dev/vdc",
                    "capacity": str(500 * gi),
                    "mediaType": "hdd",
                    "isAllocated": "false",
                },
            ],
        }
        nodes.append(
            {
                "kind": "Node",
                "metadata": {
                    "name": f"stor-node-{i:05d}",
                    "labels": {"kubernetes.io/hostname": f"stor-node-{i:05d}"},
                    "annotations": {
                        "simon/node-local-storage": _json.dumps(storage)
                    },
                },
                "status": {
                    "allocatable": {"cpu": "32", "memory": "128Gi", "pods": "110"},
                    "capacity": {"cpu": "32", "memory": "128Gi", "pods": "110"},
                },
            }
        )
    lvm_shapes = [
        [("LVM", 1 * gi)],
        [("LVM", 5 * gi)],
        [("LVM", 10 * gi), ("LVM", 2 * gi)],
        [("LVM", 8 * gi), ("LVM", 4 * gi), ("LVM", 1 * gi)],
    ]
    dev_shapes = [[("SSD", 100 * gi)], [("HDD", 400 * gi)]]
    pods = []
    for p in range(n_pods):
        if p % 10 == 9:
            vols = dev_shapes[(p // 10) % len(dev_shapes)]
        else:
            vols = lvm_shapes[p % len(lvm_shapes)]
        payload = {
            "volumes": [
                {"kind": k, "size": str(sz), "scName": f"open-local-{k.lower()}"}
                for k, sz in vols
            ]
        }
        pods.append(
            {
                "metadata": {
                    "name": f"stor-pod-{p:06d}",
                    "namespace": "bench",
                    "labels": {},
                    "annotations": {
                        "simon/pod-local-storage": _json.dumps(payload)
                    },
                },
                "spec": {
                    "containers": [
                        {
                            "name": "c",
                            "image": "kv",
                            "resources": {
                                "requests": {"cpu": "250m", "memory": "512Mi"}
                            },
                        }
                    ],
                    "schedulerName": "default-scheduler",
                },
            }
        )
    return nodes, pods


def build_capacity_scenario():
    """SIMON_BENCH=capacity: 10k base nodes deliberately short of the
    100k-pod workload, so the planner must find the minimal new-node
    count (the BASELINE.json north-star configuration)."""
    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.scheduler.core import AppResource

    nodes = []
    for i in range(CAP_NODES):
        taints = None
        if i % 23 == 0:
            taints = [{"key": "dedicated", "value": "infra", "effect": "NoSchedule"}]
        nodes.append(
            _make_node(f"node-{i:05d}", 16, 64, {"zone": f"z{i % 16}"}, taints)
        )

    def deploy(name, replicas, cpu, mem, selector=None, tolerant=False):
        spec = {
            "containers": [
                {
                    "name": "c",
                    "image": f"img-{name}",
                    "resources": {"requests": {"cpu": cpu, "memory": mem}},
                }
            ]
        }
        if selector:
            spec["nodeSelector"] = selector
        if tolerant:
            spec["tolerations"] = [{"key": "dedicated", "operator": "Exists"}]
        return {
            "kind": "Deployment",
            "metadata": {"name": name, "namespace": "bench", "labels": {"app": name}},
            "spec": {"replicas": replicas, "template": {"spec": spec}},
        }

    # 100k pods, ~160k cpu requested vs 160k allocatable — and 435 of
    # the base nodes are tainted, usable only by the tolerant class.
    # Spreading scores put only ~1/23 of the tolerant pods there, so
    # ~5k tainted cpu is stranded and the effective supply is ~155k:
    # the planner must bisect to tens of 96-cpu nodes. Class order
    # matters too: the toleration queue sort schedules `tolerant` first
    # and the rest in list order, so `small` (250m granule) lands last
    # and back-fills the cpu fragments the coarse classes strand — the
    # plan is driven by the aggregate shortfall, not by fragmentation
    # (which no node count under MaxNumNewNode could fix).
    rep = CAP_PODS // 5
    resources = ResourceTypes()
    resources.deployments = [
        deploy("memheavy", rep, "750m", "8Gi"),
        deploy("large", rep, "4", "8Gi"),
        deploy("medium", rep, "1", "2Gi"),
        deploy("small", rep, "250m", "512Mi"),
        deploy("tolerant", rep, "2", "4Gi", tolerant=True),
    ]
    cluster = ResourceTypes()
    cluster.nodes = nodes
    apps = [AppResource("bench", resources)]
    new_node = _make_node("template", 96, 384)
    return cluster, apps, new_node


def _scan_rate(nodes, pods, label: str) -> dict:
    """Compile once, then time one full scan incl. the forced
    device->host transfer (np.asarray of the output, so the timing
    covers execution, not the enqueue). Uses the same engine fast path
    production uses: the fused Pallas kernel when the batch is in
    scope, the XLA scan otherwise. The label records the backend and
    kernel the run executed on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    label = f"{label}@{jax.default_backend()}"

    from open_simulator_tpu.ops import pallas_scan
    from open_simulator_tpu.ops import scan as scan_ops
    from open_simulator_tpu.ops.encode import (
        encode_batch,
        encode_cluster,
        encode_dynamic,
        features_of_batch,
        to_scan_static,
        to_scan_state,
    )
    from open_simulator_tpu.scheduler.oracle import Oracle

    oracle = Oracle(nodes)
    cluster = encode_cluster(oracle)
    batch = encode_batch(oracle, cluster, pods)
    dyn = encode_dynamic(oracle, cluster)
    features = features_of_batch(cluster, batch)

    plan = (
        pallas_scan.build_plan(cluster, batch, dyn, features)
        if pallas_scan.should_use()
        else None
    )
    # median of three measured runs (see _timed)
    if plan is not None:
        ones_p = np.ones(len(pods), bool)
        ones_n = np.ones(cluster.n, bool)
        pallas_scan.run_scan_pallas(
            plan, batch.class_of_pod, ones_p, ones_n, pinned=batch.pinned_node
        )
        elapsed, spread, (placements_np, _) = _timed(
            lambda: pallas_scan.run_scan_pallas(
                plan, batch.class_of_pod, ones_p, ones_n,
                pinned=batch.pinned_node,
            )
        )
        label += "/" + pallas_scan.kernel_label(plan)
    else:
        static = to_scan_static(cluster, batch)
        init = to_scan_state(dyn, batch)
        class_arr = jnp.asarray(batch.class_of_pod)
        pinned_arr = jnp.asarray(batch.pinned_node)

        placements, _ = scan_ops.run_scan(
            static, init, class_arr, pinned_arr, features=features
        )
        np.asarray(placements)  # compile + warm

        def once():
            placements, _ = scan_ops.run_scan(
                static, init, class_arr, pinned_arr, features=features
            )
            return np.asarray(placements)

        elapsed, spread, placements_np = _timed(once)

    return {
        "label": label,
        "pods_per_sec": len(pods) / elapsed,
        "scheduled": int((placements_np >= 0).sum()),
        "total": len(pods),
        "nodes": len(nodes),
        "spread": spread,
    }


def run_capacity() -> dict:
    from open_simulator_tpu.apply.applier import probe_plan
    from open_simulator_tpu.models.workloads import reset_name_counter
    from open_simulator_tpu.utils.trace import GLOBAL

    cluster, apps, new_node = build_capacity_scenario()
    # warm: compiles the masked scan for this feature set
    reset_name_counter()
    warm = probe_plan(cluster, apps, new_node)
    # measured: full end-to-end plan (expansion, encode, lower bound,
    # probes, replay, report) with warm compile caches, median of
    # three runs with spread recorded (_timed)
    def once():
        reset_name_counter()
        GLOBAL.reset()
        result = probe_plan(cluster, apps, new_node)
        assert result.success and result.new_node_count == warm.new_node_count
        return result

    elapsed, spread, result = _timed(once)
    from open_simulator_tpu.runtime.guard import degradations

    degraded = degradations()
    if degraded:
        raise AssertionError(f"capacity plan degraded: {degraded}")
    return {
        "elapsed_s": elapsed,
        "protocol": f"median-of-{spread['runs']}",
        "spread": spread,
        "new_node_count": result.new_node_count,
        "pods": CAP_PODS,
        "nodes": CAP_NODES,
        "phases": GLOBAL.as_dict(),
    }


def _parse_args(argv=None):
    """Scenario selection stays on SIMON_BENCH (so every recorded
    ``cmd`` in BENCH_r*.json keeps working); flags are the regression
    doctor's diff mode — the library half of ``simon doctor``."""
    p = argparse.ArgumentParser(
        description="simon bench harness (scenario via SIMON_BENCH env)"
    )
    p.add_argument(
        "--against", metavar="BENCH_rXX.json",
        help="diff this run against a recorded bench file (raw line, "
        "JSONL, or BENCH_r*.json wrapper) and exit 1 past thresholds",
    )
    p.add_argument(
        "--time-tolerance", type=float, default=0.5,
        help="fractional slack on the headline value (default 0.5 = "
        "±50%%; wall-clock on shared runners is noisy)",
    )
    p.add_argument(
        "--dispatch-tolerance", type=int, default=0,
        help="absolute slack on device dispatches (default 0: dispatch "
        "counts are semantic on a fixed scenario)",
    )
    p.add_argument(
        "--recompile-tolerance", type=int, default=0,
        help="absolute slack on XLA recompiles (default 0)",
    )
    p.add_argument(
        "--hbm-tolerance", type=float, default=0.5,
        help="fractional slack on the ledger peak-HBM watermark",
    )
    p.add_argument(
        "--p95-tolerance", type=float, default=0.5,
        help="fractional slack on per-site latency p95s",
    )
    p.add_argument(
        "--suffix-tolerance", type=float, default=0.5,
        help="fractional slack on the incremental suffix fraction "
        "(regresses up)",
    )
    p.add_argument(
        "--store-tolerance", type=float, default=0.5,
        help="fractional slack on the artifact-store hit rate "
        "(regresses down)",
    )
    p.add_argument(
        "--store-reject-tolerance", type=int, default=0,
        help="absolute slack on artifact-store rejects (default 0)",
    )
    p.add_argument(
        "--fleet-tolerance", type=float, default=0.5,
        help="fractional slack on the fleet qps-scaling factor "
        "(regresses down) and failover seconds (regresses up)",
    )
    p.add_argument(
        "--ckpt-tolerance", type=float, default=0.5,
        help="fractional slack on the aged-failover checkpoint "
        "restore seconds (regresses up)",
    )
    return p.parse_args(argv)


def main():
    args = _parse_args()
    from open_simulator_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    scenario = os.environ.get("SIMON_BENCH", "all")

    # flight-recorder attribution for every recorded number: device
    # dispatches, XLA recompiles, transfer bytes (always-on counters,
    # obs/profile.py) and the top spans by EXCLUSIVE wall-clock (span
    # recorder at phase granularity — a handful of microseconds per
    # phase, far inside the run-to-run spread). Future perf PRs get
    # phase attribution out of every BENCH_*.json for free.
    from open_simulator_tpu.fleet.replica import replicas_on_chips
    from open_simulator_tpu.obs import profile as obs_profile
    from open_simulator_tpu.obs import spans as obs_spans

    # SIMON_BENCH_OBS=0 turns the span recorder off for strict
    # flags-off timing (the counters stay — they are always-on and
    # per-dispatch, not per-pod); measured spans-on overhead is ~1%
    # at phase granularity (docs/OBSERVABILITY.md)
    bench_obs = os.environ.get("SIMON_BENCH_OBS", "1") != "0"
    if bench_obs:
        obs_spans.RECORDER.enable()
    obs_before = obs_profile.snapshot()
    # the serve children of cold-start run while this process is still
    # off the backend; every other scenario names the device first
    cs = run_cold_start() if scenario in ("cold-start", "all") else None
    if scenario == "fleet-qps" and replicas_on_chips():
        sys.exit(
            "bench: fleet-qps needs one chip per serve replica, and the "
            "fleet cannot pin replicas to chips yet (ROADMAP B)"
        )
    device = _device() if scenario not in CHILD_SCENARIOS else None
    fq = None  # fleet stats ride out["obs"]["fleet"] when the fleet ran
    fa = None  # aged-failover stats ride out["obs"]["ckpt"] when run
    if scenario == "default":
        nodes, pods = build_scenario()
        r = _scan_rate(nodes, pods, "default")
        out = {
            "metric": f"pods scheduled/sec at {r['nodes']} nodes "
            f"(default scenario, {r['label']}, {r['scheduled']}/{r['total']} placed)",
            "value": round(r["pods_per_sec"], 1),
            "unit": "pods/s",
            "vs_baseline": round(r["pods_per_sec"] / NORTH_STAR_PODS_PER_SEC, 3),
        }
    elif scenario == "affinity":
        nodes, pods = build_affinity_scenario()
        r = _scan_rate(nodes, pods, "affinity")
        out = {
            "metric": f"pods scheduled/sec at {r['nodes']} nodes "
            f"(affinity-stress scenario, {r['label']}, {r['scheduled']}/{r['total']} placed)",
            "value": round(r["pods_per_sec"], 1),
            "unit": "pods/s",
            "vs_baseline": round(r["pods_per_sec"] / NORTH_STAR_PODS_PER_SEC, 3),
        }
    elif scenario == "affinity-25k":
        # past the ~12.3k-node resident VMEM cliff: auto-routes to the
        # streamed-terms kernel (HBM state + per-pod row gather)
        nodes, pods = build_affinity_scenario(n_nodes=25_000, replicas=100)
        r = _scan_rate(nodes, pods, "affinity-25k")
        out = {
            "metric": f"pods scheduled/sec at {r['nodes']} nodes "
            f"(affinity-stress past the VMEM cliff, {r['label']}, "
            f"{r['scheduled']}/{r['total']} placed)",
            "value": round(r["pods_per_sec"], 1),
            "unit": "pods/s",
            "vs_baseline": round(r["pods_per_sec"] / NORTH_STAR_PODS_PER_SEC, 3),
        }
    elif scenario == "mixed":
        nodes, pods = build_scenario(port_frac=0.01, scalar_frac=0.01)
        r = _scan_rate(nodes, pods, "mixed")
        out = {
            "metric": f"pods scheduled/sec at {r['nodes']} nodes "
            f"(default + 1% hostPort + 1% extended-resource pods, "
            f"{r['label']}, {r['scheduled']}/{r['total']} placed)",
            "value": round(r["pods_per_sec"], 1),
            "unit": "pods/s",
            "vs_baseline": round(r["pods_per_sec"] / NORTH_STAR_PODS_PER_SEC, 3),
        }
    elif scenario == "capacity":
        c = run_capacity()
        out = {
            "metric": f"capacity plan e2e wall-clock, {c['pods']} pods x "
            f"{c['nodes']} nodes (plan: +{c['new_node_count']} nodes; "
            f"incl. expansion+encode+probes+replay+report; median of "
            f"{c['spread']['runs']}, min {c['spread']['min_s']:.2f}s)",
            "value": round(c["elapsed_s"], 2),
            "unit": "s",
            "vs_baseline": round(NORTH_STAR_PLAN_SECONDS / c["elapsed_s"], 3),
        }
    elif scenario == "gpushare":
        nodes, pods = build_gpushare_scenario()
        r = _scan_rate(nodes, pods, "gpushare")
        out = {
            "metric": f"pods scheduled/sec at {r['nodes']} GPU nodes "
            f"(gpushare fragmentation, {r['label']}, {r['scheduled']}/{r['total']} placed)",
            "value": round(r["pods_per_sec"], 1),
            "unit": "pods/s",
            "vs_baseline": round(r["pods_per_sec"] / NORTH_STAR_PODS_PER_SEC, 3),
        }
    elif scenario == "storage":
        nodes, pods = build_storage_scenario()
        r = _scan_rate(nodes, pods, "storage")
        out = {
            "metric": f"pods scheduled/sec at {r['nodes']} open-local nodes "
            f"(2 VGs + SSD/HDD devices per node, 90% LVM / 10% device pods, "
            f"{r['label']}, {r['scheduled']}/{r['total']} placed; median of "
            f"{r['spread']['runs']})",
            "value": round(r["pods_per_sec"], 1),
            "unit": "pods/s",
            "vs_baseline": round(r["pods_per_sec"] / NORTH_STAR_PODS_PER_SEC, 3),
        }
    elif scenario == "sample":
        z = run_sample()
        out = {
            "metric": f"pods scheduled/sec at {z['nodes']} nodes, e2e "
            f"simulate with select_host=sample (Go-RNG reservoir in the "
            f"scan carry; first-max on the same XLA path: "
            f"{z['firstmax_pods_per_sec']:.0f} pods/s -> "
            f"{z['ratio']:.2f}x its wall-clock; "
            f"{z['scheduled']}/{z['total']} placed)",
            "value": round(z["pods_per_sec"], 1),
            "unit": "pods/s",
            "vs_baseline": round(z["pods_per_sec"] / NORTH_STAR_PODS_PER_SEC, 3),
        }
    elif scenario == "fuzz":
        z = run_conformance_fuzz()
        skipped = z["checked"] == 0
        out = {
            "metric": (
                "pallas/xla conformance fuzz SKIPPED (no TPU backend)"
                if skipped
                else f"pallas/xla on-device conformance fuzz "
                f"({z['checked']} mixed-feature placements compared)"
            ),
            "value": z["mismatches"],
            "unit": "mismatches",
            "vs_baseline": None if skipped else 1.0,
        }
    elif scenario == "priority":
        p = run_priority()
        out = {
            "metric": f"pods scheduled/sec at {p['nodes']} nodes, e2e simulate "
            f"({p['priority_pods']} priority pods, priority-scan engine; "
            f"{p['scheduled']}/{p['total']} placed; median of "
            f"{p['spread']['runs']}, min {p['spread']['min_s']:.2f}s)",
            "value": round(p["pods_per_sec"], 1),
            "unit": "pods/s",
            "vs_baseline": round(p["pods_per_sec"] / NORTH_STAR_PODS_PER_SEC, 3),
        }
    elif scenario == "priority-dense":
        p = run_priority_dense()
        out = {
            "metric": f"pods scheduled/sec at {p['nodes']} nodes, e2e simulate "
            f"({p['priority_pods']}/{p['total']} pods priority-bearing over "
            f"{p['tiers']} tiers, tiered priority-scan engine, "
            f"{p['scan_rounds']} scan rounds / {p['escapes']} serial escapes; "
            f"{p['scheduled']}/{p['total']} placed; per-run phases: "
            f"{p['phases']}; median of {p['spread']['runs']})",
            "value": round(p["pods_per_sec"], 1),
            "unit": "pods/s",
            "vs_baseline": round(p["pods_per_sec"] / NORTH_STAR_PODS_PER_SEC, 3),
        }
    elif scenario == "tier-stress":
        t = run_tier_stress()
        out = {
            "metric": f"pods scheduled/sec at {t['nodes']} packed nodes, e2e "
            f"simulate (escape-heavy tier stress: {t['preemptors']} preempting "
            f"tiers > MAX_SCAN_ESCAPES, {t['rounds']} rounds / {t['escapes']} "
            f"escapes / serial tail {t['serial_tail']}; {t['preemptions']} "
            f"preemptions, {t['scheduled']}/{t['total']} placed; median of "
            f"{t['spread']['runs']})",
            "value": round(t["pods_per_sec"], 1),
            "unit": "pods/s",
            "vs_baseline": round(t["pods_per_sec"] / NORTH_STAR_PODS_PER_SEC, 3),
        }
    elif scenario == "storage-fallback":
        # >4 VGs per node: outside the fused kernel's storage scope
        # (pallas_scan._build_storage caps) — records the XLA-fallback
        # rate a user hits on such shapes (VERDICT r5 missing #2)
        nodes, pods = build_storage_scenario(n_nodes=2000, n_pods=4000, n_vgs=6)
        r = _scan_rate(nodes, pods, "storage-fallback")
        out = {
            "metric": f"pods scheduled/sec at {r['nodes']} open-local nodes "
            f"(6 VGs per node — past the kernel scope cap, {r['label']}, "
            f"{r['scheduled']}/{r['total']} placed; median of "
            f"{r['spread']['runs']})",
            "value": round(r["pods_per_sec"], 1),
            "unit": "pods/s",
            "vs_baseline": round(r["pods_per_sec"] / NORTH_STAR_PODS_PER_SEC, 3),
        }
    elif scenario == "shadow-replay":
        sh = run_shadow_replay()
        out = {
            "metric": f"shadow replay steps/s, {sh['decisions']} recorded "
            f"decisions x {sh['nodes']} nodes on the warm tpu probe "
            f"(agreement {sh['agreement_rate']:.2f}, "
            f"{sh['dispatches_per_step']} dispatches/step, zero warm "
            f"recompiles; median of {sh['spread']['runs']})",
            "value": sh["steps_per_sec"],
            "unit": "steps/s",
            "vs_baseline": None,
            "steps_per_sec": sh["steps_per_sec"],
            "agreement_rate": sh["agreement_rate"],
            "dispatches_per_step": sh["dispatches_per_step"],
        }
    elif scenario == "twin-delta":
        td = run_twin_delta()
        out = {
            "metric": f"twin cluster-deltas/s applied to a warm "
            f"{td['nodes']}-node mirror ({td['deltas']} bind/evict deltas, "
            f"{td['committed_pods']} pods committed at close; "
            f"{td['queries']} live what-if queries interleaved, "
            f"p50 {td['query_p50_ms']}ms p95 {td['query_p95_ms']}ms, "
            f"zero warm recompiles)",
            "value": td["deltas_per_sec"],
            "unit": "deltas/s",
            "vs_baseline": None,
            "deltas_per_sec": td["deltas_per_sec"],
            "query_p50_ms": td["query_p50_ms"],
            "query_p95_ms": td["query_p95_ms"],
            "warm_recompiles": td["warm_recompiles"],
        }
    elif scenario == "delta-resim":
        dr = run_delta_resim()
        out = {
            "metric": f"committed-journal deltas/s on a {dr['nodes']}-node "
            f"cluster, {dr['pods']} committed pods x {dr['deltas']}-pod "
            f"delta stream (suffix fraction {dr['suffix_fraction']}, "
            f"{dr['per_delta_ms']}ms/delta vs {dr['full_rescan_s']}s full "
            f"re-scan = {dr['speedup_x']}x; committed state dict-identical "
            f"to full re-scan; warm what-if p50 {dr['whatif_p50_ms']}ms at "
            f"zero recompiles)",
            "value": dr["deltas_per_sec"],
            "unit": "deltas/s",
            "vs_baseline": None,
            "suffix_fraction": dr["suffix_fraction"],
            "speedup_x": dr["speedup_x"],
            "per_delta_ms": dr["per_delta_ms"],
            "whatif_p50_ms": dr["whatif_p50_ms"],
            "warm_recompiles": dr["warm_recompiles"],
        }
    elif scenario == "cold-start":
        out = {
            "metric": f"serve warm-store time-to-first-200 "
            f"({cs['warm_first_200_s']}s vs {cs['cold_first_200_s']}s cold "
            f"store = {cs['speedup_x']}x; {cs['warm_store_hits']} artifacts "
            f"loaded, ZERO new XLA compiles before the first answer; "
            f"{cs['cold_saves']} artifacts persisted by the cold run)",
            "value": cs["warm_first_200_s"],
            "unit": "s",
            "vs_baseline": None,
            "cold_first_200_s": cs["cold_first_200_s"],
            "warm_first_200_s": cs["warm_first_200_s"],
            "speedup_x": cs["speedup_x"],
            "warm_recompiles": cs["warm_recompiles"],
            "warm_store_hits": cs["warm_store_hits"],
        }
    elif scenario == "fleet-qps":
        fq = run_fleet_qps()
        out = {
            "metric": f"fleet router req/s over 1/2/4 serve replicas "
            f"({fq['qps_by_replicas']['1']}/{fq['qps_by_replicas']['2']}/"
            f"{fq['qps_by_replicas']['4']} req/s = {fq['qps_scaling']}x at "
            f"{fq['replicas_max']} replicas; kill -9 failover: rerouted "
            f"first-200 in {fq['failover_first_200_s']}s with the original "
            f"request id, replacement respawned + journal-replayed in "
            f"{fq['failover_seconds']}s at ZERO new XLA compiles)",
            "value": fq["qps_max"],
            "unit": "req/s",
            "vs_baseline": None,
            "qps_by_replicas": fq["qps_by_replicas"],
            "qps_scaling": fq["qps_scaling"],
            "failover_first_200_s": fq["failover_first_200_s"],
            "failover_seconds": fq["failover_seconds"],
            "replacement_recompiles": fq["replacement_recompiles"],
        }
    elif scenario == "failover-aged":
        fa = run_failover_aged()
        w0 = fa["cells"][str(fa["levels"][-1])]
        out = {
            "metric": f"aged failover first-200 after "
            f"{fa['levels'][-1]} absorbed deltas: {fa['first_200_s']}s "
            f"with checkpoints (--checkpoint-interval {fa['interval']}, "
            f"restore {fa['restore_seconds']}s, {fa['replayed_deltas']} "
            f"deltas replayed < one interval) vs "
            f"{fa['full_replay_first_200_s']}s full journal replay "
            f"({w0['full_replay']['replayed_deltas']} deltas) = "
            f"{fa['speedup_x']}x; state-digest triples identical, zero "
            f"warm recompiles; cells at {fa['levels']} deltas",
            "value": fa["first_200_s"],
            "unit": "s",
            "vs_baseline": None,
            "cells": fa["cells"],
            "interval": fa["interval"],
            "restore_seconds": fa["restore_seconds"],
            "full_replay_first_200_s": fa["full_replay_first_200_s"],
            "replayed_deltas": fa["replayed_deltas"],
            "speedup_x": fa["speedup_x"],
        }
    elif scenario == "timeline":
        tl = run_timeline()
        out = {
            "metric": f"timeline steps/s, {tl['arrivals']} arrivals / "
            f"{tl['events']} events x {tl['nodes']} nodes through "
            f"{tl['policies']} policies in {tl['windows']} windows "
            f"({tl['dispatches_per_policy']} dispatches/policy, "
            f"{tl['dispatches_per_window']} dispatches/window, zero warm "
            f"recompiles; median of {tl['spread']['runs']})",
            "value": tl["steps_per_sec"],
            "unit": "steps/s",
            "vs_baseline": None,
            "steps_per_sec": tl["steps_per_sec"],
            "windows": tl["windows"],
            "dispatches_per_policy": tl["dispatches_per_policy"],
            "dispatches_per_window": tl["dispatches_per_window"],
        }
    elif scenario == "mesh-scan":
        ms = run_mesh_scan()
        out = {
            "metric": f"mesh-scan scenario rows/s at 2048 nodes x "
            f"{ms['devices']} devices ({ms['scenarios']} outage scenarios, "
            f"best-cell speedup {ms['speedup_x']}x vs 1 device, efficiency "
            f"{ms['efficiency']} of {ms['effective_parallelism']} effective "
            f"device(s); node-axis conformance "
            f"{ms['node_axis_conformance']}; grid medians of {TIMED_RUNS})",
            "value": ms["rows_per_sec"],
            "unit": "rows/s",
            "vs_baseline": None,
            "rows_per_sec": ms["rows_per_sec"],
            "speedup_x": ms["speedup_x"],
            "efficiency": ms["efficiency"],
            "devices": ms["devices"],
            "effective_parallelism": ms["effective_parallelism"],
            "grid": ms["grid"],
        }
    elif scenario == "serve-qps":
        s = run_serve_qps()
        out = {
            "metric": f"simon serve qps, {s['clients']} concurrent clients x "
            f"{s['nodes']} nodes ({s['requests']} requests, p50 {s['p50_ms']}ms "
            f"p95 {s['p95_ms']}ms, mean batch fill {s['batch_fill_mean']}, "
            f"{s['dispatches_per_request']} device dispatches/request, "
            f"{s['shed']} shed)",
            "value": s["qps"],
            "unit": "req/s",
            "vs_baseline": None,
            "qps": s["qps"],
            "p50_ms": s["p50_ms"],
            "p95_ms": s["p95_ms"],
            "batch_fill_mean": s["batch_fill_mean"],
            "dispatches_per_request": s["dispatches_per_request"],
        }
    elif scenario == "defrag":
        d = run_defrag()
        out = {
            "metric": f"defrag sweep wall-clock, {d['pods']} pods x {d['nodes']} "
            f"nodes (drained {d['drained']} nodes, {d['moves']} migrations)",
            "value": round(d["elapsed_s"], 2),
            "unit": "s",
            "vs_baseline": round(NORTH_STAR_PLAN_SECONDS / d["elapsed_s"], 3),
        }
    elif scenario == "whatif":
        w = run_whatif()
        out = {
            "metric": f"what-if sweep over {w['specs']} newnode specs, "
            f"{w['pods']} pods x {w['nodes']} base nodes "
            f"(min counts per spec: {w['counts']})",
            "value": round(w["elapsed_s"], 2),
            "unit": "s",
            "vs_baseline": round(NORTH_STAR_PLAN_SECONDS / w["elapsed_s"], 3),
        }
    else:  # all: capacity headline + the other BASELINE configs embedded
        from open_simulator_tpu.utils.memo import clear_all_memos

        def isolated(fn, *args, **kw):
            # each scenario starts with empty identity memos, exactly
            # like its standalone run — the 100k-pod scenarios would
            # otherwise overflow the caps mid-measurement of the later
            # ones (wholesale clears inside their timed region)
            clear_all_memos()
            return fn(*args, **kw)

        z = isolated(run_conformance_fuzz)  # raises on any mismatch
        c = isolated(run_capacity)
        nodes, pods = build_scenario()
        rd = isolated(_scan_rate, nodes, pods, "default")
        nodes, pods = build_affinity_scenario()
        ra = isolated(_scan_rate, nodes, pods, "affinity")
        nodes, pods = build_affinity_scenario(n_nodes=10_000, replicas=100)
        ra10 = isolated(_scan_rate, nodes, pods, "affinity-10k")
        nodes, pods = build_affinity_scenario(n_nodes=25_000, replicas=100)
        ra25 = isolated(_scan_rate, nodes, pods, "affinity-25k")
        nodes, pods = build_scenario(port_frac=0.01, scalar_frac=0.01)
        rm = isolated(_scan_rate, nodes, pods, "mixed")
        nodes, pods = build_gpushare_scenario()
        rg = isolated(_scan_rate, nodes, pods, "gpushare")
        nodes, pods = build_storage_scenario()
        rs = isolated(_scan_rate, nodes, pods, "storage")
        nodes, pods = build_storage_scenario(n_nodes=2000, n_pods=4000, n_vgs=6)
        rsf = isolated(_scan_rate, nodes, pods, "storage-fallback")
        d = isolated(run_defrag)
        w = isolated(run_whatif)
        p = isolated(run_priority)
        pd = isolated(run_priority_dense)
        ts = isolated(run_tier_stress)
        sm = isolated(run_sample)
        sq = isolated(run_serve_qps)
        sh = isolated(run_shadow_replay)
        tl = isolated(run_timeline)
        td = isolated(run_twin_delta)
        ms = isolated(run_mesh_scan)
        dr = isolated(run_delta_resim)
        fq = None if replicas_on_chips() else isolated(run_fleet_qps)
        fa = isolated(run_failover_aged)
        out = {
            "metric": f"capacity plan e2e wall-clock, {c['pods']} pods x "
            f"{c['nodes']} nodes, north star <10s (plan: +{c['new_node_count']} nodes; "
            f"incl. expansion+encode+probes+replay+report; median of "
            f"{c['spread']['runs']} runs, min {c['spread']['min_s']:.2f}s "
            f"max {c['spread']['max_s']:.2f}s; "
            f"also: default scan {rd['pods_per_sec']:.0f} pods/s at 10k nodes ({rd['label']}) "
            f"({rm['pods_per_sec']:.0f} with 1% hostPort+extended-resource pods), "
            f"affinity-stress {ra['pods_per_sec']:.0f} pods/s at 2k nodes, "
            f"{ra10['pods_per_sec']:.0f} pods/s at 10k nodes "
            f"(min-max {ra10['spread']['min_s']:.2f}-{ra10['spread']['max_s']:.2f}s) "
            f"and {ra25['pods_per_sec']:.0f} pods/s at 25k nodes past the "
            f"VMEM cliff ({ra25['label']}), "
            f"gpushare {rg['pods_per_sec']:.0f} pods/s at {rg['nodes']} 8-GPU nodes, "
            f"open-local storage {rs['pods_per_sec']:.0f} pods/s at {rs['nodes']} "
            f"2-VG nodes ({rs['label']}), "
            f"storage-fallback {rsf['pods_per_sec']:.0f} pods/s at {rsf['nodes']} "
            f"6-VG nodes past the kernel scope cap ({rsf['label']}), "
            f"defrag sweep {d['elapsed_s']:.2f}s/{d['drained']} drained at {d['nodes']} nodes, "
            f"8-spec what-if {w['elapsed_s']:.2f}s, "
            f"priority-mixed e2e {p['pods_per_sec']:.0f} pods/s "
            f"({p['priority_pods']} priority pods), "
            f"priority-dense e2e {pd['pods_per_sec']:.0f} pods/s "
            f"({pd['priority_pods']}/{pd['total']} priority-bearing over "
            f"{pd['tiers']} tiers, {pd['scan_rounds']} rounds/{pd['escapes']} "
            f"escapes; {pd['phases']}), "
            f"tier-stress e2e {ts['pods_per_sec']:.0f} pods/s "
            f"({ts['escapes']} escapes, serial tail {ts['serial_tail']}), "
            f"sample-mode e2e {sm['pods_per_sec']:.0f} pods/s "
            f"({sm['ratio']:.2f}x first-max on the same XLA path), "
            f"serve-qps {sq['qps']:.1f} req/s over {sq['clients']} clients "
            f"(p50 {sq['p50_ms']}ms p95 {sq['p95_ms']}ms, batch fill "
            f"{sq['batch_fill_mean']}, {sq['dispatches_per_request']} "
            f"dispatches/request), "
            f"shadow-replay {sh['steps_per_sec']:.0f} steps/s over "
            f"{sh['decisions']} recorded decisions (agreement "
            f"{sh['agreement_rate']:.2f}, {sh['dispatches_per_step']} "
            f"dispatches/step), "
            f"timeline {tl['steps_per_sec']:.0f} steps/s over "
            f"{tl['arrivals']} arrivals x {tl['policies']} policies "
            f"({tl['windows']} windows, {tl['dispatches_per_policy']} "
            f"dispatches/policy, zero warm recompiles), "
            f"twin-delta {td['deltas_per_sec']:.0f} deltas/s onto a warm "
            f"{td['nodes']}-node mirror (live what-if p95 "
            f"{td['query_p95_ms']}ms, zero warm recompiles), "
            f"mesh-scan {ms['rows_per_sec']:.0f} scenario rows/s at 2048 "
            f"nodes x {ms['devices']} devices (best-cell {ms['speedup_x']}x vs 1 "
            f"device, efficiency {ms['efficiency']} of "
            f"{ms['effective_parallelism']} effective, node-axis "
            f"conformance {ms['node_axis_conformance']}), "
            f"delta-resim {dr['deltas_per_sec']:.1f} deltas/s onto a "
            f"{dr['pods']}-pod committed journal (suffix fraction "
            f"{dr['suffix_fraction']}, {dr['speedup_x']}x vs full re-scan, "
            f"dict-identical state), "
            f"cold-start warm-store first-200 {cs['warm_first_200_s']}s vs "
            f"{cs['cold_first_200_s']}s cold ({cs['speedup_x']}x, zero new "
            f"compiles), "
            + (
                "fleet-qps not run (one chip per replica is ROADMAP B), "
                if fq is None
                else f"fleet-qps {fq['qps_by_replicas']['1']}/"
                f"{fq['qps_by_replicas']['2']}/{fq['qps_by_replicas']['4']} "
                f"req/s at 1/2/4 replicas ({fq['qps_scaling']}x; kill -9 "
                f"failover rerouted first-200 {fq['failover_first_200_s']}s, "
                f"full recovery {fq['failover_seconds']}s, zero new "
                f"compiles), "
            )
            + f"failover-aged first-200 {fa['first_200_s']}s after "
            f"{fa['levels'][-1]} absorbed deltas with checkpoints "
            f"(restore {fa['restore_seconds']}s, {fa['replayed_deltas']} "
            f"deltas replayed < interval {fa['interval']}) vs "
            f"{fa['full_replay_first_200_s']}s full replay "
            f"({fa['speedup_x']}x, digest-identical); "
            f"all pods/s medians of {TIMED_RUNS}; "
            + (
                f"on-device conformance fuzz: {z['checked']} placements ok)"
                if z["checked"]
                else "conformance fuzz SKIPPED: no TPU)"
            ),
            "value": round(c["elapsed_s"], 2),
            "unit": "s",
            "vs_baseline": round(NORTH_STAR_PLAN_SECONDS / c["elapsed_s"], 3),
        }
    # the chip children have exited: the device is free to name now
    out["device"] = device or _device()
    recorded = obs_spans.RECORDER.snapshot() if bench_obs else []
    obs_spans.RECORDER.disable()
    prof = obs_profile.delta(obs_before)
    out["obs"] = {
        "jax_dispatches": prof["jax_dispatches_total"],
        "jax_recompiles": prof["jax_recompiles_total"],
        "transfer_d2h_bytes": prof["device_transfer_d2h_bytes_total"],
        "transfer_h2d_bytes": prof["device_transfer_h2d_bytes_total"],
        "top_spans_exclusive_ms": obs_spans.top_spans(recorded, 5),
    }
    # compiled-cost / memory-ledger / latency-histogram observatory
    # blocks (docs/OBSERVABILITY.md): what each executable costs, where
    # the HBM peak sat, and the per-site latency distributions — the
    # dimensions `bench.py --against` / `simon doctor` gate on
    out["obs"].update(obs_spans.observatory_block())
    # shadow auditor counters ride the same registry (shadow/replay.py);
    # present whenever the run replayed decisions
    from open_simulator_tpu.utils.trace import COUNTERS

    if COUNTERS.get("shadow_steps_total"):
        out["obs"]["shadow"] = {
            "steps": COUNTERS.get("shadow_steps_total"),
            "agree": COUNTERS.get("shadow_agree_total"),
            "divergences": COUNTERS.get("shadow_divergence_total"),
            "warm_recompiles": COUNTERS.get("shadow_warm_recompiles_total"),
        }
    # fleet block: the dimensions `simon doctor` gates on
    # (fleet.qps_scaling regresses down, fleet.failover_seconds up)
    if fq is not None:
        out["obs"]["fleet"] = {
            "qps_scaling": fq["qps_scaling"],
            "failover_seconds": fq["failover_seconds"],
            "failover_first_200_s": fq["failover_first_200_s"],
            "qps_by_replicas": fq["qps_by_replicas"],
            "replacement_recompiles": fq["replacement_recompiles"],
        }
        # audited per-phase breakdown (fleet/audit.py): lets the
        # doctor name the slow phase when failover_seconds regresses
        if fq.get("failover_phases"):
            out["obs"]["fleet"]["failover_phases"] = fq["failover_phases"]
    # checkpoint block: the aged-failover dimensions `simon doctor`
    # gates on (ckpt.restore_seconds regresses up — a slower restore
    # from the newest generation + suffix means bounded recovery is
    # no longer bounded)
    if fa is not None:
        out["obs"]["ckpt"] = {
            "restore_seconds": fa["restore_seconds"],
            "first_200_s": fa["first_200_s"],
            "full_replay_first_200_s": fa["full_replay_first_200_s"],
            "replayed_deltas": fa["replayed_deltas"],
            "interval": fa["interval"],
            "warm_recompiles": fa["warm_recompiles"],
        }
    print(json.dumps(out))
    if args.against:
        # the doctor's diff (obs/doctor.py): value + dispatches +
        # recompiles + peak HBM + per-site p95s vs the recorded run;
        # report on stderr so the JSON record line above stays parseable
        from open_simulator_tpu.obs import doctor

        base = doctor.load_bench_record(args.against)
        report = doctor.diff_records(
            base, out, doctor.Thresholds.from_args(args)
        )
        print(
            doctor.render_text(report, args.against, "this run"),
            file=sys.stderr,
        )
        if not report.ok:
            sys.exit(1)


if __name__ == "__main__":
    main()
