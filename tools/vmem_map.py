"""Map the fused kernel's VMEM cliff (VERDICT r3 weak #4 / next #4).

The Pallas scan keeps every persistent (R, 128) node-state tile in
VMEM and rejects the plan when the tile budget exceeds ~13 MB
(pallas_scan.build_plan); past that point the batch drops to the XLA
scan (~10x). This tool bisects, per bench scenario flavor, the
maximum node count whose plan still fits, and prints the tile count
at the edge — the numbers quoted in docs/PERFORMANCE.md.

Plan building is host-only: no TPU needed, and SIMON_PALLAS_FORCE=1
makes should_use() irrelevant (build_plan is called directly).

Usage: python tools/vmem_map.py
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def build_at(n_nodes: int, flavor: str):
    import bench
    from open_simulator_tpu.ops import pallas_scan
    from open_simulator_tpu.ops.encode import (
        encode_batch,
        encode_cluster,
        encode_dynamic,
        features_of_batch,
    )
    from open_simulator_tpu.scheduler.oracle import Oracle

    if flavor == "default":
        nodes, pods = bench.build_scenario()
    elif flavor == "mixed":
        nodes, pods = bench.build_scenario(port_frac=0.01, scalar_frac=0.01)
    elif flavor == "affinity":
        nodes, pods = bench.build_affinity_scenario(n_nodes=2000, replicas=20)
    elif flavor == "gpushare":
        nodes, pods = bench.build_gpushare_scenario(n_nodes=1000, n_pods=2000)
    else:
        raise ValueError(flavor)
    # resize the node axis by cloning/truncating the built nodes
    base = nodes
    nodes = []
    i = 0
    while len(nodes) < n_nodes:
        src = base[i % len(base)]
        if i < len(base):
            nodes.append(src)
        else:
            clone = {
                "metadata": {
                    "name": f"x-{i:06d}",
                    "labels": dict((src.get("metadata") or {}).get("labels") or {}),
                },
                "spec": dict(src.get("spec") or {}),
                "status": src.get("status"),
            }
            nodes.append(clone)
        i += 1
    oracle = Oracle(nodes)
    cluster = encode_cluster(oracle)
    batch = encode_batch(oracle, cluster, pods[: min(len(pods), 2000)])
    dyn = encode_dynamic(oracle, cluster)
    features = features_of_batch(cluster, batch)
    plan = pallas_scan.build_plan(cluster, batch, dyn, features)
    return plan, pallas_scan.last_reject()


def max_nodes(flavor: str, lo: int = 1000, hi: int = 600_000) -> tuple:
    """Largest node count whose plan builds, by bisection."""
    plan, rej = build_at(lo, flavor)
    if plan is None:
        return 0, rej
    while hi - lo > max(lo // 50, 256):  # ~2% resolution
        mid = (lo + hi) // 2
        plan, rej = build_at(mid, flavor)
        if plan is None and rej and "VMEM" in rej:
            hi = mid
        elif plan is None:
            return lo, rej  # rejected for a non-VMEM reason: report it
        else:
            lo = mid
    return lo, None


def main() -> None:
    for flavor in ("default", "mixed", "gpushare", "affinity"):
        n, rej = max_nodes(flavor)
        note = f" (stopped: {rej})" if rej else ""
        print(f"{flavor:10s} max nodes on the fused kernel ~= {n:,}{note}")


if __name__ == "__main__":
    main()
