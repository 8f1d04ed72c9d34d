"""Plain sequential reference for the benchmark's correctness check.

An event-batch discrete-event simulation of the SPARS scheduling and power
rules, written from the semantics alone and importing nothing of the
program under test: jobs and nodes are NumPy arrays, one batch is handled
at a time, in the order

    completions -> transitions -> scheduler pass (FCFS / EASY) -> starts
    -> idle-timeout switch-off (PSUS, PSAS; capped by queued demand under
       PSAS+IPM) -> proactive wake (PSAS+IPM)

and energy accrues per (node group, power state) between batches.

Scope: the schedulers ``FCFS``/``EASY`` x ``PSUS``/``PSAS``/``PSAS+IPM``,
any number of node groups with their own power, switch delays and speed,
node order ``id`` or ``cheap`` (active watts per unit of speed), the
scheduler window, and allocation ``any`` (a job takes the first nodes of
the allocation order, whatever their groups) or ``partition`` (a job runs
within one group: the group whose ``res``-th eligible node comes first in
the allocation order, and none where no group has that many; a job wider
than every group never starts). Time is whole seconds. ``energy_dtype``
sets the precision of the energy ledger: float64 is the reference; a lower
one (``bfloat16``) is the control that the check must refuse.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

# node power states (index into a group's power row)
SLEEP, SWITCHING_ON, IDLE, ACTIVE, SWITCHING_OFF = range(5)
# job states
WAITING, ALLOCATED, RUNNING, DONE = range(4)
INF = float(2**30)  # "never"

# (backfill, eager_ready, sleep_enabled, ipm_enabled) per scheduler label
_BASES = {"FCFS": False, "EASY": True}
_PSMS = {
    "PSUS": (True, True, False),
    "PSAS": (False, True, False),
    "PSAS+IPM": (False, True, True),
}


def policy_flags(label: str):
    base, psm = label.split()
    return (_BASES[base],) + _PSMS[psm]


class Platform:
    """Per-node tables built from the configuration's node groups."""

    def __init__(self, groups: Sequence[dict]):
        counts = [int(g["count"]) for g in groups]
        rep = lambda vals, dt: np.repeat(np.asarray(vals, dt), counts)  # noqa: E731
        self.n = sum(counts)
        self.n_groups = len(groups)
        self.names = tuple(g["name"] for g in groups)
        # watts per state, in the state order above
        self.group_power = np.asarray(
            [[g["power"][s] for s in ("sleep", "switching_on", "idle",
                                      "active", "switching_off")]
             for g in groups],
            np.float64,
        )
        self.gid = rep(range(len(groups)), np.int64)
        self.t_on = rep([g["t_switch_on"] for g in groups], np.float64)
        self.t_off = rep([g["t_switch_off"] for g in groups], np.float64)
        self.speed = rep([g["speed"] for g in groups], np.float32)
        # float32, as the cross-engine contract orders nodes
        self.cheap_key = (
            rep([g["power"]["active"] for g in groups], np.float32) / self.speed
        ).astype(np.float32)

    @classmethod
    def from_json(cls, doc: dict) -> "Platform":
        """From the platform JSON schema: ``node_groups`` (each with name,
        count, compute_speed and per-state ``power``/``transition_time``)
        or, for one homogeneous group, the same fields at the top level."""
        def group(g, name, count):
            st = g["states"]
            return {
                "name": name, "count": count,
                "power": {k: float(st[k]["power"]) for k in (
                    "sleep", "switching_on", "idle", "active", "switching_off")},
                "t_switch_on": int(st["switching_on"]["transition_time"]),
                "t_switch_off": int(st["switching_off"]["transition_time"]),
                "speed": float(g.get("compute_speed", 1.0)),
            }

        if doc.get("node_groups"):
            groups = [group(g, g["name"], int(g["count"]))
                      for g in doc["node_groups"]]
        else:
            groups = [group(doc, "default", int(doc["nb_nodes"]))]
        if sum(g["count"] for g in groups) != int(doc["nb_nodes"]):
            raise ValueError("node_groups do not add up to nb_nodes")
        return cls(groups)


class Result:
    def __init__(self, start, finish, terminated, energy, n_batches, names):
        self.start = start  # i64[J], -1 if never started
        self.finish = finish  # i64[J], -1 if not done
        self.terminated = terminated  # bool[J]
        self.energy = energy  # f64[G, 5] joules
        self.n_batches = n_batches
        self.group_names = names

    @property
    def total_energy_j(self) -> float:
        return float(self.energy.sum())

    @property
    def wasted_energy_j(self) -> float:
        e = self.energy.sum(axis=0)
        return float(e[IDLE] + e[SWITCHING_ON] + e[SWITCHING_OFF])

    def schedule(self) -> np.ndarray:
        """(J, 3) [start, finish, terminated] in submission order."""
        return np.stack(
            [self.start, self.finish, self.terminated.astype(np.int64)], axis=1
        )


def prepare_jobs(jobs: dict, n_nodes: int) -> dict:
    """The trace-to-simulation adaptation of an SWF replay: requests wider
    than the machine are clamped to it, submit times are rebased to 0, a
    requested time never lies below the runtime, and jobs are ordered by
    (submit time, job id)."""
    res = np.minimum(np.asarray(jobs["res"], np.int64), n_nodes)
    sub = np.asarray(jobs["subtime"], np.int64)
    run = np.maximum(np.asarray(jobs["runtime"], np.int64), 1)
    req = np.maximum(np.asarray(jobs["reqtime"], np.int64), run)
    jid = np.asarray(jobs["job_id"], np.int64)
    sub = sub - sub.min()
    order = np.lexsort((jid, sub))
    return {"job_id": jid[order], "res": res[order], "subtime": sub[order],
            "reqtime": req[order], "runtime": run[order]}


def simulate(
    platform: Platform,
    jobs: dict,
    label: str,
    timeout: Optional[int],
    *,
    node_order: str = "id",
    window: int = 32,
    allocation: str = "any",
    terminate_overrun: bool = False,
    energy_dtype=np.float64,
) -> Result:
    """Run ``jobs`` (as :func:`prepare_jobs` returns them) to completion."""
    if allocation not in ("any", "partition"):
        raise ValueError(f"allocation {allocation!r}: 'any' or 'partition'")
    partition = allocation == "partition"
    backfill, eager, sleep_on, ipm_on = policy_flags(label)
    p = platform
    N = p.n
    J = len(jobs["res"])
    W = max(1, min(window, J))
    res = jobs["res"]
    sub = jobs["subtime"].astype(np.float64)
    req = jobs["reqtime"].astype(np.float64)
    work = jobs["runtime"]
    order_key = p.cheap_key if node_order == "cheap" else np.zeros(N, np.float32)

    state = np.full(N, IDLE, np.int64)
    until = np.full(N, INF)
    njob = np.full(N, -1, np.int64)
    idle_since = np.zeros(N)
    status = np.full(J, WAITING, np.int64)
    start = np.full(J, -1.0)
    finish = np.full(J, INF)
    alloc_ready = np.full(J, INF)
    terminated = np.zeros(J, bool)
    edt = np.dtype(energy_dtype)
    energy = np.zeros((p.n_groups, 5), edt)
    power = p.group_power.astype(edt)
    t = 0.0

    def ready(now):
        if eager:
            return np.full(N, now)
        r = np.full(N, INF)
        r[state == IDLE] = now
        on = state == SWITCHING_ON
        r[on] = until[on]
        sl = state == SLEEP
        r[sl] = now + p.t_on[sl]
        off = state == SWITCHING_OFF
        r[off] = until[off] + p.t_on[off]
        return r

    def queued():
        return np.flatnonzero((status == WAITING) & (sub <= t))

    def scheduler_pass():
        q = queued()[:W]
        if q.size == 0:
            return
        free = njob < 0
        r = ready(t)
        elig = np.flatnonzero(free)
        # allocation order (ready, [cost key,] node id); allocating a job
        # takes the head of this order and changes no other node's key
        elig = elig[np.lexsort((elig, order_key[elig], r[elig]))]
        head = 0
        shadow = extra = None
        for j in q:
            k = int(res[j])
            if partition:
                rest = elig[head:].copy()
            if elig.size - head < k:
                ok = False
            elif partition and not group_first(elig[head:], k):
                ok = False
            else:
                chosen = elig[head:head + k]
                rdy = r[chosen[-1]]
                ok = shadow is None or (rdy + req[j] <= shadow or k <= extra)
            if partition and not ok:  # a pick the backfill test refused
                elig[head:] = rest
            if ok:
                head += k
                njob[chosen] = j
                wake = chosen[state[chosen] == SLEEP]
                state[wake] = SWITCHING_ON
                until[wake] = t + p.t_on[wake]
                status[j] = ALLOCATED
                alloc_ready[j] = rdy
                if shadow is not None:
                    extra = max(0, extra - k)
            elif shadow is None:
                if not backfill:
                    return
                shadow, extra = easy_shadow(k)

    def group_first(order, k):
        """The partition rule on ``order``, the eligible nodes in allocation
        order: of the groups with at least ``k`` nodes there, the one whose
        ``k``-th node comes first wins, and its first ``k`` nodes move to
        the front of ``order`` (in place), every other node keeping its
        place in the order behind them. False, and ``order`` untouched,
        where no group has ``k`` nodes."""
        best = None
        for g in range(p.n_groups):
            at = np.flatnonzero(p.gid[order] == g)
            if at.size >= k and (best is None or at[k - 1] < best[k - 1]):
                best = at
        if best is None:
            return False
        front = np.zeros(order.size, bool)
        front[best[:k]] = True
        order[:] = np.concatenate([order[front], order[~front]])
        return True

    def easy_shadow(k):
        rel = ready(t)
        held = njob >= 0
        hj = njob[held]
        rel[held] = np.where(
            status[hj] == RUNNING, start[hj] + req[hj],
            np.where(status[hj] == ALLOCATED, alloc_ready[hj] + req[hj], t),
        )
        rel = np.sort(rel)
        S = rel[min(k, N) - 1]
        return S, int(np.count_nonzero(rel <= S)) - k

    def start_jobs():
        on_idle = (njob >= 0) & (state == IDLE)
        cnt = np.bincount(njob[on_idle], minlength=J)
        go = np.flatnonzero((status == ALLOCATED) & (cnt == res))
        if go.size == 0:
            return
        smin = np.full(J, np.inf, np.float32)
        held = njob >= 0
        np.minimum.at(smin, njob[held], p.speed[held])
        realized = np.maximum(
            np.ceil(work[go].astype(np.float32) / smin[go]),
            1,
        ).astype(np.int64)
        if terminate_overrun:
            eff = np.minimum(realized, jobs["reqtime"][go])
            terminated[go] = realized > jobs["reqtime"][go]
        else:
            eff = realized
        status[go] = RUNNING
        start[go] = t
        finish[go] = t + eff
        mask = np.zeros(J, bool)
        mask[go] = True
        nodes = held & mask[np.maximum(njob, 0)]
        state[nodes] = ACTIVE
        until[nodes] = INF

    def demand():
        return int(res[queued()].sum())

    def available():
        return int(np.count_nonzero(
            (njob < 0) & ((state == IDLE) | (state == SWITCHING_ON))))

    def switch_off():
        if timeout is None:
            return
        c = np.flatnonzero(
            (njob < 0) & (state == IDLE) & (t - idle_since >= timeout))
        c = c[np.lexsort((c, idle_since[c]))]
        if ipm_on:
            c = c[: max(0, available() - demand())]
        state[c] = SWITCHING_OFF
        until[c] = t + p.t_off[c]

    def wake():
        deficit = demand() - available()
        if deficit <= 0:
            return
        c = np.flatnonzero((njob < 0) & (state == SLEEP))[:deficit]
        state[c] = SWITCHING_ON
        until[c] = t + p.t_on[c]

    def batch():
        done = np.flatnonzero((status == RUNNING) & (finish <= t))
        if done.size:
            status[done] = DONE
            mask = np.zeros(J, bool)
            mask[done] = True
            nodes = (njob >= 0) & mask[np.maximum(njob, 0)]
            njob[nodes] = -1
            state[nodes] = IDLE
            until[nodes] = INF
            idle_since[nodes] = t
        due = until <= t
        on = due & (state == SWITCHING_ON)
        off = due & (state == SWITCHING_OFF)
        state[on] = IDLE
        until[on] = INF
        idle_since[on] = t
        state[off] = SLEEP
        until[off] = INF
        rewake = off & (njob >= 0)  # reserved while shutting down
        state[rewake] = SWITCHING_ON
        until[rewake] = t + p.t_on[rewake]
        scheduler_pass()
        start_jobs()
        if sleep_on:
            switch_off()
        if ipm_on:
            wake()

    def next_time():
        cand = [sub[(status == WAITING) & (sub > t)],
                finish[status == RUNNING],
                until[(state == SWITCHING_ON) | (state == SWITCHING_OFF)]]
        if sleep_on and timeout is not None:
            cand.append(idle_since[(njob < 0) & (state == IDLE)] + timeout)
        c = np.concatenate(cand)
        c = c[c > t]
        return float(c.min()) if c.size else INF

    def accrue(t_next):
        dt = t_next - t
        if dt <= 0:
            return
        occ = np.zeros((p.n_groups, 5), np.int64)
        np.add.at(occ, (p.gid, state), 1)
        for g in range(p.n_groups):
            for s in range(5):
                if occ[g, s]:
                    draw = edt.type(occ[g, s]) * power[g, s] * edt.type(dt)
                    energy[g, s] = energy[g, s] + edt.type(draw)

    cap = 20 * J + 10_000
    n_batches = 0
    batch()
    while not np.all(status == DONE):
        nt = next_time()
        if nt >= INF or n_batches >= cap:
            break
        accrue(nt)
        t = nt
        batch()
        n_batches += 1
    fin = np.where(status == DONE, finish, -1).astype(np.int64)
    return Result(start.astype(np.int64), fin, terminated.copy(),
                  energy.astype(np.float64), n_batches, p.names)
