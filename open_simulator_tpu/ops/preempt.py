"""DefaultPreemption's dry run on the device, inside the scan step.

A pod that fails every node and passes the PostFilter gates (the tier
escape mask plus its preemptionPolicy, preemption.tier_escape_mask) runs
the whole preemption cycle of scheduler/preemption.run_preemption
against the scan state, in the same step:

- selectVictimsOnNode for every node at once: remove every pod of lower
  priority, check the fit, then reprieve them one at a time in
  MoreImportantPod order (priority descending, earlier commit first);
- pickOneNodeForPreemption's criteria as a lexicographic choice over
  nodes (PDB violations are 0 in scope; then the lowest highest-priority
  victim, the lowest priority sum, the fewest victims, the latest
  earliest start among the highest-priority victims, the first node);
- the eviction, and the retry cycle. In scope, every node but the
  chosen one is as infeasible as before the eviction, and the chosen
  one now fits (the reprieve keeps the fit), so the retry cycle's
  filter leaves that node alone and its choice is that node: the
  caller places the pod there without scoring again.

The fused kernel runs the same dry run over int32 tiles (the
`dry_run` of pallas_scan._make_kernel) where its scope allows; this
module is the XLA scan's, and the host side of both.

The scan state carries a per-node table of committed pods (PreemptState):
slot k of node n is the k-th pod of the oracle's `ns.pods`, with its
priority, commit sequence and requests. Valid slots always form a
prefix: a commit goes to slot `pod_cnt[n]`, an eviction compacts the
node's column. So the victim slots of the chosen node are the indices
of the victims in `ns.pods`, which is what the host replay reads.

Scope (core.py decides it per batch; the scan refuses anything else at
trace time): victims may affect the preemptor only through
NodeResourcesFit and the pod count. A batch with host ports, scalar
resources, GPU share, open-local volumes, inter-pod affinity or spread
terms, a custom post_filter plugin or sample-mode selectHost keeps the
serial escape. So does a step where a potential victim on a node that
could fit the preemptor is out of scope itself (`hard`: matched by a
PodDisruptionBudget, holding GPU share or open-local volumes), or where
the table overflowed: the step then reports ESCAPE and changes nothing.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# pre_node codes beside a node index: nothing preempted, or the step
# needs the serial preemption cycle on the host
NONE = -1
ESCAPE = -2

_I64_MAX = np.iinfo(np.int64).max
_I64_MIN = np.iinfo(np.int64).min
# MoreImportantPod as one int64 key, smallest first: (2^31 - 1 - priority)
# in the high bits, the commit sequence in the low 31. Priorities are
# int32 and sequences below 2^31 (core.py checks both on the host)
_PRIO_TOP = (1 << 31) - 1
_SEQ_BITS = 31


class PreemptState(NamedTuple):
    """The committed pods of every node, [K, N] slots (see module
    docstring), plus the next commit sequence and a sticky overflow
    flag (a commit past slot K-1)."""

    valid: jnp.ndarray  # [K, N] bool
    prio: jnp.ndarray  # [K, N] i64
    seq: jnp.ndarray  # [K, N] i64
    mcpu: jnp.ndarray  # [K, N] i64
    mem: jnp.ndarray
    eph: jnp.ndarray
    nz_mcpu: jnp.ndarray
    nz_mem: jnp.ndarray
    hard: jnp.ndarray  # [K, N] bool: a victim the device may not evict
    seq_next: jnp.ndarray  # [] i64
    overflow: jnp.ndarray  # [] bool


class PreemptInput(NamedTuple):
    """Per-pod scan inputs of a batch that may preempt on the device."""

    prio: jnp.ndarray  # [P] i64 effective priority
    ok: jnp.ndarray  # [P] bool: a failure here runs the dry run
    hard: jnp.ndarray  # [P] bool: once committed, out of scope as a victim


_FIELDS = ("prio", "seq", "mcpu", "mem", "eph", "nz_mcpu", "nz_mem")


def _fits(static, u, c, m, e, cnt):
    """NodeResourcesFit and the pod count of class u on nodes holding
    (c, m, e) requested and `cnt` pods — the scan step's own fit."""
    fit_pods = cnt + 1 <= static.alloc_pods
    fit_res = (
        (static.alloc_mcpu >= static.req_mcpu[u] + c)
        & (static.alloc_mem >= static.req_mem[u] + m)
        & (static.alloc_eph >= static.req_eph[u] + e)
    )
    return fit_pods & (fit_res | ~static.has_request[u])


def _col_sum(x, mask):
    return jnp.sum(jnp.where(mask, x, 0), axis=0)


def dry_run(static, state, u, prio, node_valid):
    """selectVictimsOnNode on every node, pickOneNodeForPreemption, and
    the eviction. Returns (state after eviction, pre_node, victims[K]):
    pre_node is the chosen node, NONE when no node can help, or ESCAPE
    (state unchanged) when the step is out of device scope; victims
    marks the evicted slots of the chosen node, in `ns.pods` order."""
    t = state.preempt
    k = t.valid.shape[0]
    lower = t.valid & (t.prio < prio)  # [K, N]
    n_lower = jnp.sum(lower, axis=0)
    base_c = state.used_mcpu - _col_sum(t.mcpu, lower)
    base_m = state.used_mem - _col_sum(t.mem, lower)
    base_e = state.used_eph - _col_sum(t.eph, lower)
    base_n = state.pod_cnt - n_lower
    # nodesWherePreemptionMightHelp + the fit with every lower pod gone
    # (static filters reject a node for good; nothing else in scope
    # depends on the removed pods)
    cand = (
        static.static_feasible[u] & node_valid & (n_lower > 0)
        & _fits(static, u, base_c, base_m, base_e, base_n)
    )
    escape = t.overflow | jnp.any(lower & t.hard & cand[None, :])
    rem = lower & cand[None, :]
    key0 = jnp.where(rem, (_PRIO_TOP - t.prio) * (1 << _SEQ_BITS) + t.seq, _I64_MAX)
    slot = jnp.arange(k)[:, None]

    def reprieve(carry):
        j, key, victim, c, m, e, n = carry
        kmin = jnp.min(key, axis=0)
        has = kmin < _I64_MAX
        # argmin takes the first slot on equal keys: ns.pods order, as
        # the oracle's stable sort
        pick = (slot == jnp.argmin(key, axis=0)[None, :]) & has[None, :]
        pc = _col_sum(t.mcpu, pick)
        pm = _col_sum(t.mem, pick)
        pe = _col_sum(t.eph, pick)
        keep = has & _fits(static, u, c + pc, m + pm, e + pe, n + 1)
        c = c + jnp.where(keep, pc, 0)
        m = m + jnp.where(keep, pm, 0)
        e = e + jnp.where(keep, pe, 0)
        n = n + keep
        victim = victim | (pick & ~keep[None, :])
        key = jnp.where(pick, _I64_MAX, key)
        return j + 1, key, victim, c, m, e, n

    rounds = jnp.max(jnp.where(cand, n_lower, 0))
    _, _, victim, *_ = jax.lax.while_loop(
        lambda carry: carry[0] < rounds,
        reprieve,
        (jnp.zeros((), rounds.dtype), key0, jnp.zeros_like(lower),
         base_c, base_m, base_e, base_n),
    )

    # pickOneNodeForPreemption; a node whose pods were all reprieved is
    # no candidate (preemption.run_preemption drops it too)
    nv = jnp.sum(victim, axis=0)
    pool = cand & (nv > 0)
    top = jnp.max(jnp.where(victim, t.prio, _I64_MIN), axis=0)
    psum = _col_sum(t.prio, victim)
    early = jnp.min(jnp.where(victim & (t.prio == top[None, :]), t.seq, _I64_MAX), axis=0)
    for x in (top, psum, nv):
        pool = pool & (x == jnp.min(jnp.where(pool, x, _I64_MAX)))
    pool = pool & (early == jnp.max(jnp.where(pool, early, _I64_MIN)))
    found = jnp.any(pool) & ~escape
    chosen = jnp.argmax(pool)

    # the eviction: release the victims' requests and compact the
    # chosen node's column so valid slots stay a prefix
    vc = victim[:, chosen] & found
    gone = jnp.zeros_like(state.used_mcpu).at[chosen].set(1) * found

    def release(used, field):
        return used - gone * jnp.sum(jnp.where(vc, field[:, chosen], 0))

    keep = t.valid[:, chosen] & ~vc
    dest = jnp.where(keep, jnp.cumsum(keep) - 1, k)

    def compact(arr):
        col = jnp.zeros((k,), arr.dtype).at[dest].set(arr[:, chosen], mode="drop")
        return arr.at[:, chosen].set(col)

    table = t._replace(
        valid=compact(t.valid),
        hard=compact(t.hard),
        **{f: compact(getattr(t, f)) for f in _FIELDS},
    )
    new_state = state._replace(
        used_mcpu=release(state.used_mcpu, t.mcpu),
        used_mem=release(state.used_mem, t.mem),
        used_eph=release(state.used_eph, t.eph),
        nz_mcpu=release(state.nz_mcpu, t.nz_mcpu),
        nz_mem=release(state.nz_mem, t.nz_mem),
        pod_cnt=state.pod_cnt - gone * jnp.sum(vc),
        preempt=table,
    )
    pre_node = jnp.where(escape, ESCAPE, jnp.where(found, chosen, NONE))
    return new_state, pre_node.astype(jnp.int64), vc


def record_commit(static, state, u, placement, commit, prio, hard):
    """Append a committed pod of class u to its node's slots (slot
    `pod_cnt[node]` before the commit); a commit past slot K-1 sets the
    overflow flag instead, which turns every later dry run into ESCAPE."""
    t = state.preempt
    k = t.valid.shape[0]
    node = jnp.maximum(placement, 0)
    s = state.pod_cnt[node]
    ok = commit & (s < k)
    s = jnp.minimum(s, k - 1)

    def put(arr, v):
        return arr.at[s, node].set(jnp.where(ok, v, arr[s, node]))

    vals = {
        "prio": prio,
        "seq": t.seq_next,
        "mcpu": static.req_mcpu[u],
        "mem": static.req_mem[u],
        "eph": static.req_eph[u],
        "nz_mcpu": static.nz_mcpu[u],
        "nz_mem": static.nz_mem[u],
    }
    return t._replace(
        valid=put(t.valid, True),
        hard=put(t.hard, hard),
        seq_next=t.seq_next + commit,
        overflow=t.overflow | (commit & ~ok),
        **{f: put(getattr(t, f), v) for f, v in vals.items()},
    )


# ----------------------------------------------------------------- host side


def table_slots(oracle, cluster, batch, n_pinned: int = 0) -> int:
    """Slots per node: the most pods a node can hold during the batch.
    That is the pods it holds now, the batch's pinned pods (they bypass
    every fit), and the scheduled pods that can be there at once: no
    more than its pod count, and, where every class of the batch asks
    for cpu (memory), no more than its allocatable over the smallest
    such request, since each passed NodeResourcesFit on commit. Rounded
    up to a multiple of 8, so that small changes keep one compiled
    shape. A commit past the last slot sets the overflow flag (an
    escape), so the bound costs no exactness."""
    cur = np.fromiter((len(ns.pods) for ns in oracle.nodes), np.int64, len(oracle.nodes))
    new = np.asarray(cluster.alloc_pods, np.int64)
    loose = np.asarray(batch.class_of_pod)[np.asarray(batch.pinned_node) < 0]
    classes = np.unique(loose)
    for req, alloc in ((batch.req_mcpu, cluster.alloc_mcpu), (batch.req_mem, cluster.alloc_mem)):
        if not classes.size:
            new = np.zeros_like(new)
            break
        least = int(np.asarray(req)[classes].min())
        if least > 0:
            new = np.minimum(new, np.maximum(np.asarray(alloc, np.int64), 0) // least)
    most = int((cur + new).max(initial=0)) + n_pinned
    return -(-max(most, 1) // 8) * 8


def table_np(oracle, k: int, hard_of) -> dict:
    """The oracle's committed pods as [K, N] numpy slots: `valid` and
    `hard` (bool) and each of _FIELDS (int64). `hard_of(ns, pod)` says
    whether a pod is out of scope as a victim."""
    from ..models import requests as req

    n = len(oracle.nodes)
    valid = np.zeros((k, n), bool)
    hard = np.zeros((k, n), bool)
    vals = {f: np.zeros((k, n), np.int64) for f in _FIELDS}
    prio_of = oracle.pod_priority
    seq_of = oracle.commit_seq_of
    for n_i, ns in enumerate(oracle.nodes):
        for s_i, pod in enumerate(ns.pods):
            s = req.pod_request_summary(pod)
            valid[s_i, n_i] = True
            hard[s_i, n_i] = hard_of(ns, pod)
            vals["prio"][s_i, n_i] = prio_of(pod)
            vals["seq"][s_i, n_i] = seq_of(pod)
            vals["mcpu"][s_i, n_i] = s.mcpu
            vals["mem"][s_i, n_i] = s.mem
            vals["eph"][s_i, n_i] = s.eph
            vals["nz_mcpu"][s_i, n_i] = s.nz_mcpu
            vals["nz_mem"][s_i, n_i] = s.nz_mem
    return dict(vals, valid=valid, hard=hard)


def encode_table(table: dict, seq_next: int) -> PreemptState:
    """table_np's slots as the XLA scan's PreemptState."""
    return PreemptState(
        seq_next=jnp.asarray(seq_next, jnp.int64),
        overflow=jnp.zeros((), bool),
        **{f: jnp.asarray(table[f]) for f in ("valid", "hard") + _FIELDS},
    )
