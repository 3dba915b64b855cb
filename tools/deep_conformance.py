"""Deep hardware conformance sweep: randomized mixed-feature scenarios
(gpu+terms and terms+ports+scalars+pins, with scenario masks), compiled
Pallas kernel vs XLA scan on the real TPU. Heavier than the bench fuzz
(SIMON_BENCH=fuzz); run after kernel changes:

    python tools/deep_conformance.py

Exits non-zero on the first placement mismatch, when no TPU backend is
present, or when every scenario skips. SIMON_BENCH=fuzz (bench.py) is
the lighter per-bench-run gate; keep kernel-scope changes reflected in
both. Last full run (end of r5, after storage-in-kernel + streamed
terms + packed plan transfer): 6448 placements over 12 scenarios —
4 gpu+terms, 4 terms+ports+scalars+pins+storage, 4 of those with the
STREAMED term layout forced — 0 mismatches, 0 skipped.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import copy

import numpy as np

import jax.numpy as jnp
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
from open_simulator_tpu.models.workloads import reset_name_counter
from open_simulator_tpu.testing import build_affinity_stress, with_node_gpu

if not pallas_scan.should_use():
    # without this guard run_scan_pallas silently interprets on CPU and
    # this tool would report hardware conformance it never ran
    print("ERROR: no TPU backend — this sweep validates the COMPILED kernel")
    sys.exit(2)

checked = 0
scenarios = 0
skipped = 0
for seed in range(12):
    rng = np.random.RandomState(1000 + seed)
    reset_name_counter()
    n_nodes = int(rng.choice([200, 500, 1000]))
    nodes, stss = build_affinity_stress(
        n_nodes=n_nodes,
        n_sts=int(rng.randint(5, 15)),
        replicas=int(rng.randint(20, 80)),
        zones=int(rng.choice([4, 8, 16])),
    )
    use_gpu = seed % 3 == 0
    # r5: a third of the non-gpu seeds force the STREAMED terms layout
    # (HBM state + per-pod row gather) and also mix open-local storage
    # into the batch, so both r5 kernel subsystems get the same
    # hardware sweep as the resident kernel
    use_stream = not use_gpu and seed % 3 == 1
    use_storage = not use_gpu
    if use_gpu:
        for node in nodes:
            with_node_gpu(int(rng.randint(1, 5)), "32")(node)
    else:
        for node in nodes[: n_nodes // 2]:
            node["status"]["allocatable"]["example.com/accel"] = "4"
    if use_storage:
        import json as _json

        gi = 1 << 30
        for node in nodes[: (2 * n_nodes) // 3]:
            node["metadata"].setdefault("annotations", {})[
                "simon/node-local-storage"
            ] = _json.dumps(
                {
                    "vgs": [
                        {
                            "name": "a",
                            "capacity": str(int(rng.choice([50, 100])) * gi),
                            "requested": str(int(rng.randint(0, 8)) * gi),
                        },
                        {
                            "name": "b",
                            "capacity": str(200 * gi),
                            "requested": "0",
                        },
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
    res = ResourceTypes()
    res.stateful_sets = stss
    pods = expand_apps([AppResource("d", res)], nodes)[0]
    for i, pod in enumerate(pods):
        k = rng.randint(0, 30)
        if use_gpu:
            if k <= 3:
                pod["metadata"] = copy.deepcopy(pod["metadata"])
                pod["metadata"].setdefault("annotations", {}).update(
                    {
                        "alibabacloud.com/gpu-mem": str(int(rng.choice([2, 4, 8, 17]))),
                        "alibabacloud.com/gpu-count": str(int(rng.choice([1, 1, 2]))),
                    }
                )
            continue
        if k > 3:
            continue
        pod["spec"] = spec = copy.deepcopy(pod["spec"])
        if k == 0:
            port = 9000 + int(rng.randint(0, 4))
            spec["containers"][0]["ports"] = [
                {"containerPort": port, "hostPort": port, "protocol": "TCP"}
            ]
        elif k == 1:
            spec["containers"][0]["resources"]["requests"]["example.com/accel"] = str(
                1 + i % 3
            )
        elif k == 2:
            spec["nodeName"] = nodes[int(rng.randint(0, n_nodes))]["metadata"]["name"]
        else:
            gi = 1 << 30
            vols = (
                [
                    {
                        "kind": "LVM",
                        "size": str(int(rng.choice([1, 5, 12])) * gi),
                        "scName": "open-local-lvm",
                    }
                ]
                if i % 3
                else [
                    {
                        "kind": "SSD",
                        "size": str(60 * gi),
                        "scName": "open-local-device-ssd",
                    }
                ]
            )
            pod["metadata"] = copy.deepcopy(pod["metadata"])
            pod["metadata"].setdefault("annotations", {})[
                "simon/pod-local-storage"
            ] = _json.dumps({"volumes": vols})
    oracle = Oracle(nodes)
    c = encode_cluster(oracle)
    b = encode_batch(oracle, c, pods)
    d = encode_dynamic(oracle, c)
    f = features_of_batch(c, b)
    pallas_scan.STREAM_FORCE = True if use_stream else None
    plan = pallas_scan.build_plan(c, b, d, f)
    pallas_scan.STREAM_FORCE = None
    if plan is None:
        skipped += 1
        print(f"seed {seed}: skipped ({pallas_scan.last_reject()})")
        continue
    if use_stream:
        assert plan.terms is not None and plan.terms.cfg.stream
    # scenario masks too: random node subset + inactive pods
    nv = np.ones(c.n, bool)
    nv[rng.rand(c.n) < 0.1] = False
    pa = np.ones(len(pods), bool)
    pa[rng.rand(len(pods)) < 0.05] = False
    static = to_scan_static(c, b)
    init = to_scan_state(d, b)
    ref, _ = scan_ops.run_scan_masked(
        static,
        init,
        jnp.asarray(b.class_of_pod),
        jnp.asarray(b.pinned_node),
        jnp.asarray(nv),
        jnp.asarray(pa),
        features=f,
    )
    got, _ = pallas_scan.run_scan_pallas(
        plan, b.class_of_pod, pa, nv, pinned=b.pinned_node
    )
    ref = np.asarray(ref)
    got = np.asarray(got)
    mism = int((got != ref).sum())
    tag = "gpu+terms" if use_gpu else "terms+ports+scalars+pins+storage"
    if use_stream:
        tag += "+STREAMED"
    print(f"seed {seed}: {len(pods)} pods, u={b.u}, {tag}: {mism} mismatches")
    if mism:
        idx = np.nonzero(got != ref)[0][:5]
        print("  first:", idx.tolist(), got[idx].tolist(), ref[idx].tolist())
        sys.exit(1)
    checked += len(pods)
    scenarios += 1
if scenarios == 0:
    # all seeds rejected = scenario drift, not a pass (the bench fuzz
    # raises in the same situation)
    print("ERROR: every scenario skipped — nothing was validated")
    sys.exit(3)
print(f"DEEP CONFORMANCE OK: {checked} placements over {scenarios} scenarios ({skipped} skipped)")
