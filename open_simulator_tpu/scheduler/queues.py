"""Which pods share content, and in what order they queue.

expand_apps is the one path from a request's apps to a kernel batch:
simulate (scheduler/core.py schedule_app), the plan sweep
(parallel/sweep.py), serve (serve/session.py) and the twin
(twin/queries.py) all take their pods, content groups
(models/workloads.ExpandIndex) and queue order from it. The order
(queue_order) composes the reference's heuristics (pkg/algo):

- affinity / toleration order: pods with nodeSelector (resp.
  tolerations) first (pkg/algo/affinity.go, toleration.go). Stable
  sorts — the reference's comparators are not strict weak orders under
  Go's unstable sort.Sort, so we define the evident intent (documented
  deviation, scheduler/core.py).
- greed_sort: descending dominant-resource share against total cluster
  allocatable, pods with a nodeName first (pkg/algo/greed.go:45-91).
  Dead code in the reference at this revision (`--use-greed` is parsed
  but never forwarded, SURVEY.md §2.1); here the flag actually applies
  the ordering.
"""

from __future__ import annotations

from fractions import Fraction
from typing import List

import numpy as np

from ..models import requests as req
from ..models import workloads as wl


def _share(alloc: float, total: float) -> float:
    """algo.Share (greed.go:78-91)."""
    if total == 0:
        return 0.0 if alloc == 0 else 1.0
    return alloc / total


def greed_sort(nodes: List[dict], pods: List[dict]) -> List[dict]:
    return [pods[i] for i in _greed_order(nodes, pods)]


def _greed_order(nodes: List[dict], pods: List[dict]) -> List[int]:
    """GreedQueue ordering: dominant share of (cpu, memory) vs the
    cluster total, descending; pods with spec.nodeName first.

    Capacity totals exclude simon-fabricated new nodes so the ordering
    is independent of the capacity-planner's current new-node count —
    the serial escalation run and the batched sweep (which pads to the
    maximum count) must sort pods identically or the sweep's minimal
    count is not valid for the serial run that confirms it."""
    from ..models.workloads import LABEL_NEW_NODE

    total_cpu = 0.0
    total_mem = 0.0
    for node in nodes:
        if LABEL_NEW_NODE in ((node.get("metadata") or {}).get("labels") or {}):
            continue
        alloc = req.node_allocatable(node)
        total_cpu += float(alloc.get(req.CPU, Fraction(0)))
        total_mem += float(alloc.get(req.MEMORY, Fraction(0)))

    def dominant_share(pod: dict) -> float:
        requests = req.pod_requests(pod)
        if not requests:
            return 0.0
        cpu = float(requests.get(req.CPU, Fraction(0)))
        mem = float(requests.get(req.MEMORY, Fraction(0)))
        return max(_share(cpu, total_cpu), _share(mem, total_mem))

    return sorted(
        range(len(pods)),
        key=lambda i: (
            not (pods[i].get("spec") or {}).get("nodeName"),
            -dominant_share(pods[i]),
        ),
    )


def queue_order(
    pods, groups, resolver=None, saw_priority=False, greed_nodes=None, less=None
):
    """One app's expanded pods in queue order. Returns `(pods, groups,
    prios)`: the pods permuted, their (group_of, firsts) content-group
    index permuted with them, and each pod's effective priority.

    The order is the reference's pipeline — greed_sort when
    `greed_nodes` is given (--use-greed), then the affinity and toleration sorts, then
    PrioritySort (queuesort/priority_sort.go:41-45: priority desc, ties
    by queue arrival) with nodeName-bound pods committing first (their
    capacity is occupied regardless of queue order, and sorting a
    pending pod ahead of them would let it bind into capacity they
    already hold). Those stable sorts and the partition are ONE stable
    lexicographic sort by (bound-first, -priority | bound-const,
    tolerations-is-None, nodeSelector-is-None, arrival), and every key
    is a per-GROUP constant (ExpandIndex), so the order is a handful of
    per-group resolutions plus one np.lexsort. greed_sort's key is read
    per pod, only under --use-greed.

    PrioritySort applies only where a priority signal exists (a pod of
    the batch resolves non-zero, or `saw_priority`: the cluster's pods
    did), so the no-priority case keeps the reference's exact list
    order. Without a `resolver` the batch is ordered without it and
    every priority reads 0 (the twin's probes). A custom QueueSort
    comparator `less` replaces PrioritySort (the framework allows
    exactly one queue-sort plugin): bound pods first, then the pending
    ones by `less`, stable on ties."""
    from .preemption import batch_priorities

    group_of, firsts = groups
    ng = len(firsts)
    g_spec = [f.get("spec") or {} for f in firsts]
    g_aff = np.fromiter((s.get("nodeSelector") is None for s in g_spec), bool, ng)
    g_tol = np.fromiter((s.get("tolerations") is None for s in g_spec), bool, ng)
    g_bound = np.fromiter((bool(s.get("nodeName")) for s in g_spec), bool, ng)
    if resolver is None:
        g_prio = np.zeros(ng, dtype=np.int64)
    else:
        g_prio = batch_priorities(firsts, resolver)
    if greed_nodes is None:
        arrival = np.arange(len(pods), dtype=np.int64)
    else:
        arrival = np.asarray(_greed_order(greed_nodes, pods), dtype=np.int64)
    g = group_of[arrival]
    keys = (g_aff[g], g_tol[g])
    if less is None and (saw_priority or bool((g_prio != 0).any())):
        # bound pods share one priority-key constant: they keep their
        # (toleration, affinity, arrival) order among themselves
        not_bound = ~g_bound[g]
        keys += (np.where(not_bound, -g_prio[g], np.int64(0)), not_bound)
    perm = arrival[np.lexsort(keys)]
    if less is not None:
        import functools

        bound = g_bound[group_of[perm]]
        pending = sorted(
            perm[~bound].tolist(),
            key=functools.cmp_to_key(
                lambda a, b: -1 if less(pods[a], pods[b])
                else (1 if less(pods[b], pods[a]) else 0)
            ),
        )
        perm = np.concatenate([perm[bound], np.asarray(pending, dtype=np.int64)])
    g = group_of[perm]
    return [pods[i] for i in perm.tolist()], (g, firsts), g_prio[g]


def expand_apps(
    apps, nodes, resolver=None, saw_priority=False, use_greed=False, less=None
):
    """`apps` expanded in order (models/workloads.generate_valid_pods_from_app)
    into one batch, each app's pods in queue order (queue_order).
    Returns `(pods, groups, prios)`. The caller keeps the generated-name
    counter where it wants it (serve re-seats it per request)."""
    from ..utils.trace import phase

    pods, groups, prios = [], [], [np.zeros(0, dtype=np.int64)]
    for app in apps:
        with phase("host/expand"):
            index = wl.ExpandIndex()
            app_pods = wl.generate_valid_pods_from_app(
                app.name, app.resource, nodes, index
            )
        with phase("priority/sort"):
            p, g, pr = queue_order(
                app_pods, index.groups(), resolver, saw_priority,
                nodes if use_greed else None, less,
            )
        pods.extend(p)
        groups.append(g)
        prios.append(pr)
    return pods, wl.join_groups(*groups), np.concatenate(prios)
