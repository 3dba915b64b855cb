"""The plain reference for DefaultPreemption: one kube-scheduler v1.20
cycle per pod, in PrioritySort order, with the PostFilter's dry run as
the simulator documents it (scheduler/preemption.py), in plain numpy on
the host's CPU.

It imports nothing of the program. Its inputs are the tables that
``scenario.py`` builds; the filters and scores are ``reference.py``'s
(NodeResourcesFit and TaintToleration; least-allocated + balanced
allocation + twice the min-max Simon share, first maximum in node
order), its spread and anti-affinity terms aside: no template of a
preemption configuration carries them.

Each node holds its pods as slots in commit order. When a pod fits no
node, the dry run visits every node: out go its pods of lower priority;
the pod must then fit; each is put back in MoreImportantPod order
(priority descending, earlier commit first) while the pod still fits,
and the rest are the victims. Of the nodes with victims, the choice is
pickOneNodeForPreemption's (no PodDisruptionBudgets here): the lowest
highest victim priority, the lowest sum of victim priorities, the
fewest victims, the latest earliest commit among the highest-priority
victims, then the first node. The victims leave the node and join the
back of the queue in that order; the pod runs a fresh cycle.

``precision="low"`` runs the quantities in int32 and the fractions in
float32: the control that must fail (32Gi of memory wraps in int32).
"""

from __future__ import annotations

from collections import deque
from typing import List, Sequence

import numpy as np

from . import reference
from .scenario import PodClass, Tables

MAX_SCORE = reference.MAX_SCORE


class Result:
    """counts[N, C]: pods of each class on each node at the end; events:
    (node, number of victims) of every preemption in order; unscheduled:
    pods left without a node."""

    def __init__(self, counts, events, unscheduled):
        self.counts = counts
        self.events = events
        self.unscheduled = unscheduled


def schedule(tables: Tables, classes: List[PodClass], prios: Sequence[int], bound,
             seq: Sequence[int], taint_keys, precision: str = "high") -> Result:
    """Place the pods of `seq` (class ids, in queue order) after the
    `bound` (class, node) pods, in that commit order."""
    static, state, np_float = reference.build_inputs(
        tables, classes, bound, taint_keys, None, precision
    )
    np_int = static["alloc_cpu"].dtype
    acpu, amem, apods = static["alloc_cpu"], static["alloc_mem"], static["alloc_pods"]
    cpu, mem, simon, ok = static["cpu"], static["mem"], static["simon"], static["static_ok"]
    used_cpu, used_mem, used_pods = (np.array(a) for a in state[:3])
    prio = np.asarray(prios, np.int64)
    n = len(tables.names)
    width = max(int(apods.max(initial=1)), int(used_pods.max(initial=0)), 1)
    slot_cls = np.full((n, width), -1, np.int64)
    slot_seq = np.zeros((n, width), np.int64)
    rows = np.arange(n)
    clock = 0

    def commit(c, node):
        nonlocal clock
        k = int(used_pods[node])
        slot_cls[node, k] = c
        slot_seq[node, k] = clock
        clock += 1
        used_cpu[node] += cpu[c]
        used_mem[node] += mem[c]
        used_pods[node] += 1

    # build_inputs counted the bound pods into `used_*` already
    fill = np.zeros(n, np.int64)
    for c, node in bound:
        slot_cls[node, fill[node]] = c
        slot_seq[node, fill[node]] = clock
        fill[node] += 1
        clock += 1

    def fits(c, c_used, m_used, n_used):
        return (n_used + 1 <= apods) & (c_used + cpu[c] <= acpu) & (m_used + mem[c] <= amem)

    def pick(c, feas):
        req_cpu = used_cpu + cpu[c]
        req_mem = used_mem + mem[c]
        with np.errstate(divide="ignore", invalid="ignore"):
            least = (
                np.where(req_cpu > acpu, 0, (acpu - req_cpu) * MAX_SCORE // np.maximum(acpu, 1))
                + np.where(req_mem > amem, 0, (amem - req_mem) * MAX_SCORE // np.maximum(amem, 1))
            ) // 2
            cf = req_cpu.astype(np_float) / acpu.astype(np_float)
            mf = req_mem.astype(np_float) / amem.astype(np_float)
            bal = np.where(
                (cf >= 1) | (mf >= 1), 0,
                np.floor((1 - np.abs(cf - mf)) * MAX_SCORE).astype(np_int),
            )
        raw = simon[c]
        hi, lo = raw[feas].max(), raw[feas].min()
        share = (raw - lo) * MAX_SCORE // max(hi - lo, 1) if hi > lo else 0
        return int(np.argmax(np.where(feas, least + bal + 2 * share, -1)))

    def dry_run(c):
        """(node, victim slots in eviction order) or None."""
        w = max(int(used_pods.max(initial=0)), 1)
        cls = slot_cls[:, :w]
        valid = cls >= 0
        sp = np.where(valid, prio[np.maximum(cls, 0)], np.iinfo(np.int64).max)
        lower = valid & (sp < prio[c])
        if not lower.any():
            return None
        cc = np.where(lower, cpu[np.maximum(cls, 0)], 0).sum(1).astype(np_int)
        mm = np.where(lower, mem[np.maximum(cls, 0)], 0).sum(1).astype(np_int)
        nl = lower.sum(1)
        cur_c, cur_m, cur_n = used_cpu - cc, used_mem - mm, used_pods - nl
        cand = ok[c] & (nl > 0) & fits(c, cur_c, cur_m, cur_n)
        lower &= cand[:, None]
        order = np.lexsort((slot_seq[:, :w], np.where(lower, -sp, np.iinfo(np.int64).max)), axis=1)
        victim = np.zeros_like(lower)
        for j in range(w):
            k = order[:, j]
            is_low = lower[rows, k]
            kc = np.maximum(cls[rows, k], 0)
            back = is_low & fits(c, cur_c + cpu[kc], cur_m + mem[kc], cur_n + 1)
            cur_c = cur_c + np.where(back, cpu[kc], 0)
            cur_m = cur_m + np.where(back, mem[kc], 0)
            cur_n = cur_n + back
            victim[rows, k] |= is_low & ~back
        nv = victim.sum(1)
        pool = cand & (nv > 0)
        if not pool.any():
            return None
        top = np.where(victim, sp, np.iinfo(np.int64).min).max(1)
        psum = np.where(victim, sp, 0).sum(1)
        early = np.where(victim & (sp == top[:, None]), slot_seq[:, :w],
                         np.iinfo(np.int64).max).min(1)
        for x in (top, psum, nv):
            pool &= x == x[pool].min()
        pool &= early == early[pool].max()
        node = int(np.argmax(pool))
        slots = np.flatnonzero(victim[node])
        slots = slots[np.lexsort((slot_seq[node, slots], -sp[node, slots]))]
        return node, slots

    def evict(node, slots):
        out = []
        for s in slots:
            c = int(slot_cls[node, s])
            used_cpu[node] -= cpu[c]
            used_mem[node] -= mem[c]
            used_pods[node] -= 1
            out.append(c)
        keep = np.ones(width, bool)
        keep[slots] = False
        keep &= slot_cls[node] >= 0
        kept_cls, kept_seq = slot_cls[node, keep], slot_seq[node, keep]
        slot_cls[node] = -1
        slot_cls[node, :len(kept_cls)] = kept_cls
        slot_seq[node, :len(kept_seq)] = kept_seq
        return out

    queue = deque(int(c) for c in seq)
    events = []
    unscheduled = 0
    while queue:
        c = queue.popleft()
        feas = ok[c] & fits(c, used_cpu, used_mem, used_pods)
        if not feas.any():
            got = dry_run(c)
            if got is None:
                unscheduled += 1
                continue
            node, slots = got
            queue.extend(evict(node, slots))
            events.append((node, len(slots)))
            feas = ok[c] & fits(c, used_cpu, used_mem, used_pods)
            if not feas.any():
                unscheduled += 1
                continue
        commit(c, pick(c, feas))

    counts = np.zeros((n, len(classes)), np.int64)
    valid = slot_cls >= 0
    np.add.at(counts, (np.nonzero(valid)[0], slot_cls[valid]), 1)
    return Result(counts, events, unscheduled)
