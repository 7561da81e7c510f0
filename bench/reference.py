"""Plain reference of the E2C event semantics, for deciding ``correct``.

An independent implementation in numpy (float64 by default) of what
one replica of the simulator computes: the same event phases in the
same order, the same tie-breaks (lowest task id, lowest machine id,
task-major for pair policies), the same bounded-window refill for
streams.  It imports nothing of the program under test; the benchmark's
tests check it against the program's own oracle on small instances.

``precision="bfloat16"`` rounds every input and every computed time,
cost and energy to bfloat16: the control that must come out as not
correct, since the configurations state float32.
"""
from __future__ import annotations

import math

import ml_dtypes
import numpy as np

NOT_ARRIVED, IN_BATCH, IN_MQ, RUNNING = 0, 1, 2, 3
COMPLETED, CANCELLED, MISSED_QUEUE, MISSED_RUNNING, PREEMPTED = 4, 5, 6, 7, 8

HEURISTICS = ("fcfs", "rr", "met", "mct", "ee_met", "ee_mct", "minmin",
              "maxmin", "edf_mct", "heft")


def _identity(x):
    return np.asarray(x, np.float64)


def _f32(x):
    return np.asarray(x, np.float64).astype(np.float32).astype(np.float64)


def _bf16(x):
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(
        np.float64)


class Replica:
    """One replica: inputs as float64 (or bfloat16-rounded) arrays."""

    def __init__(self, inp: dict, policy: str, *, window: int | None = None,
                 lcap: int = 4, precision: str = "float64"):
        if policy not in HEURISTICS:
            raise ValueError(f"reference has no policy {policy!r}")
        self.q = {"float64": _identity, "float32": _f32,
                  "bfloat16": _bf16}[precision]
        q = self.q
        self.arrival = q(inp["arrival"])
        self.type_id = np.asarray(inp["type_id"], np.int64)
        self.deadline = q(inp["deadline"])
        self.eet = q(inp["eet"])
        self.power = q(inp["power"])
        self.mtype = np.asarray(inp["mtype"], np.int64)
        self.noise = q(inp["noise"])
        self.speed = q(inp["speed"])
        self.power_scale = q(inp["power_scale"])
        self.down_start = q(inp["down_start"])
        self.down_end = q(inp["down_end"])
        self.kill = np.asarray(inp["kill"], bool)
        self.policy = policy
        self.lcap = lcap
        n, m = len(self.arrival), len(self.mtype)
        self.n, self.m = n, m
        # (N, M) expected and actual execution times, (M,) active power
        ex = self.eet[self.type_id][:, self.mtype]
        self.expected = q(ex / self.speed[None, :])
        self.exec_time = q(q(ex * self.noise[:, None]) / self.speed[None, :])
        self.p_active = q(self.power[self.mtype, 1] * self.power_scale)
        self.status = np.full(n, NOT_ARRIVED, np.int64)
        self.machine = np.full(n, -1, np.int64)
        self.seq = np.full(n, np.iinfo(np.int64).max, np.int64)
        self.t_start = np.full(n, -1.0)
        self.t_end = np.full(n, -1.0)
        self.n_preempts = np.zeros(n, np.int64)
        self.running = np.full(m, -1, np.int64)
        self.busy_until = np.zeros(m)
        self.energy = np.zeros(m)
        self.active_time = np.zeros(m)
        self.time = 0.0
        self.seq_counter = 0
        self.rr_ptr = 0
        self.window = window
        self.loaded = np.full(n, window is None, bool)
        self.retired = np.zeros(n, bool)
        self.next_load = n if window is None else 0
        trans = np.concatenate([self.down_start.ravel(),
                                self.down_end.ravel()])
        self.transitions = np.unique(trans[np.isfinite(trans)])

    # ---- helpers ---------------------------------------------------------
    def up(self) -> np.ndarray:
        down = (self.down_start <= self.time) & (self.time < self.down_end)
        return ~down.any(axis=1)

    def queued(self) -> np.ndarray:
        return self.status == IN_MQ

    def avail(self) -> np.ndarray:
        """(M,) time each machine frees up: its running task's end (or
        now), plus the expected time of its queued tasks in queue order."""
        q = self.q
        base = np.where(self.running >= 0,
                        np.maximum(self.time, self.busy_until), self.time)
        out = base.copy()
        ids = np.nonzero(self.queued())[0]
        for t in ids[np.argsort(self.seq[ids], kind="stable")]:
            m = self.machine[t]
            out[m] = q(out[m] + self.expected[t, m])
        return out

    def rooms(self) -> np.ndarray:
        counts = np.bincount(self.machine[self.queued()], minlength=self.m)
        return (counts < self.lcap) & self.up()

    # ---- streaming window ------------------------------------------------
    def stream_load(self):
        if self.window is None:
            return
        done = self.loaded & ~self.retired & (self.status >= COMPLETED)
        self.retired |= done
        occ = int((self.loaded & ~self.retired).sum())
        k = min(self.window - occ, self.n - self.next_load)
        if k > 0:
            self.loaded[self.next_load:self.next_load + k] = True
            self.next_load += k

    # ---- event phases ----------------------------------------------------
    def completions(self):
        q = self.q
        for m in np.nonzero((self.running >= 0)
                            & (self.busy_until <= self.time))[0]:
            t = self.running[m]
            dur = q(self.busy_until[m] - self.t_start[t])
            self.status[t] = COMPLETED
            self.t_end[t] = self.busy_until[m]
            self.energy[m] = q(self.energy[m] + q(self.p_active[m] * dur))
            self.active_time[m] = q(self.active_time[m] + dur)
            self.running[m] = -1

    def availability(self):
        q = self.q
        up = self.up()
        for m in np.nonzero(~up & (self.running >= 0))[0]:
            t = self.running[m]
            dur = q(self.time - self.t_start[t])
            self.energy[m] = q(self.energy[m] + q(self.p_active[m] * dur))
            self.active_time[m] = q(self.active_time[m] + dur)
            self.running[m] = -1
            self.n_preempts[t] += 1
            self._evict(t, m)
        for t in np.nonzero(self.queued())[0]:
            m = self.machine[t]
            if not up[m]:
                self.n_preempts[t] += 1
                self._evict(t, m)

    def _evict(self, t: int, m: int):
        if self.kill[m]:
            self.status[t] = PREEMPTED
            self.t_end[t] = self.time
        else:
            self.status[t] = IN_BATCH
            self.machine[t] = -1
            self.seq[t] = np.iinfo(np.int64).max
            self.t_start[t] = -1.0

    def arrivals(self):
        new = (self.status == NOT_ARRIVED) & self.loaded \
            & (self.arrival <= self.time)
        self.status[new] = IN_BATCH

    def deadline_drops(self):
        q = self.q
        drop = ((self.status == IN_BATCH) | (self.status == IN_MQ)) \
            & (self.deadline <= self.time)
        self.status[drop] = MISSED_QUEUE
        self.t_end[drop] = self.deadline[drop]
        for m in np.nonzero(self.running >= 0)[0]:
            t = self.running[m]
            if self.deadline[t] <= self.time:
                dur = q(self.deadline[t] - self.t_start[t])
                self.status[t] = MISSED_RUNNING
                self.t_end[t] = self.deadline[t]
                self.energy[m] = q(self.energy[m]
                                   + q(self.p_active[m] * dur))
                self.active_time[m] = q(self.active_time[m] + dur)
                self.running[m] = -1

    # ---- scheduler -------------------------------------------------------
    def decide(self, batch: np.ndarray, rooms: np.ndarray,
               avail: np.ndarray):
        """-> (task, machine) for the policy; mirrors its tie-breaks."""
        q = self.q
        head = batch[0]
        big = np.inf

        def ct(t):
            return np.where(rooms, q(avail + self.expected[t]), big)

        pol = self.policy
        if pol == "fcfs":
            return head, int(np.argmin(np.where(rooms, avail, big)))
        if pol == "rr":
            for k in range(self.m):
                m = (self.rr_ptr + k) % self.m
                if rooms[m]:
                    return head, m
        if pol == "met":
            return head, int(np.argmin(np.where(rooms, self.expected[head],
                                                big)))
        if pol in ("mct", "heft"):
            # heft without a DAG: every upward rank is 0, so the head
            return head, int(np.argmin(ct(head)))
        if pol == "ee_met":
            cost = q(self.expected[head] * self.p_active)
            return head, int(np.argmin(np.where(rooms, cost, big)))
        if pol == "ee_mct":
            c = ct(head)
            feas = rooms & (c <= self.deadline[head])
            if feas.any():
                cost = q(self.expected[head] * self.p_active)
                return head, int(np.argmin(np.where(feas, cost, big)))
            return head, int(np.argmin(c))
        if pol == "edf_mct":
            t = batch[int(np.argmin(self.deadline[batch]))]
            return t, int(np.argmin(ct(t)))
        c = np.where(rooms[None, :],
                     q(avail[None, :] + self.expected[batch]), big)
        if pol == "minmin":
            i, m = np.unravel_index(int(np.argmin(c)), c.shape)
            return batch[i], int(m)
        if pol == "maxmin":
            best = np.argmin(c, axis=1)
            i = int(np.argmax(c[np.arange(len(batch)), best]))
            return batch[i], int(best[i])
        raise ValueError(pol)

    def drain(self):
        while True:
            batch = np.nonzero(self.status == IN_BATCH)[0]
            rooms = self.rooms()
            if not len(batch) or not rooms.any():
                return
            avail = self.avail()
            t, m = self.decide(batch, rooms, avail)
            best = np.min(np.where(rooms, self.q(avail + self.expected[t]),
                                   np.inf))
            if best > self.deadline[t]:
                self.status[t] = CANCELLED
                self.t_end[t] = self.time
            else:
                self.status[t] = IN_MQ
                self.machine[t] = m
                self.seq[t] = self.seq_counter
                self.seq_counter += 1
                self.rr_ptr = (m + 1) % self.m

    def start_tasks(self):
        up = self.up()
        ids = np.nonzero(self.queued())[0]
        for m in range(self.m):
            if self.running[m] >= 0 or not up[m]:
                continue
            mine = ids[self.machine[ids] == m]
            if not len(mine):
                continue
            t = mine[np.argmin(self.seq[mine])]
            self.status[t] = RUNNING
            self.t_start[t] = self.time
            self.busy_until[m] = self.q(self.time + self.exec_time[t, m])
            self.running[m] = t

    def next_event(self) -> float:
        cands = [np.inf]
        waiting = (self.status == NOT_ARRIVED) & self.loaded
        if waiting.any():
            cands.append(self.arrival[waiting].min())
        if (self.running >= 0).any():
            cands.append(self.busy_until[self.running >= 0].min())
        live = (self.status >= IN_BATCH) & (self.status <= RUNNING)
        if live.any():
            cands.append(self.deadline[live].min())
        later = self.transitions[self.transitions > self.time]
        if len(later):
            cands.append(later[0])
        return min(cands)

    def run(self) -> "Replica":
        budget = 4 * self.n + 16 + 2 * self.down_start.size
        while budget > 0 and not (self.status >= COMPLETED).all():
            self.stream_load()
            t = self.next_event()
            if not math.isfinite(t):
                break
            self.time = max(t, self.time)
            self.completions()
            self.availability()
            self.arrivals()
            self.deadline_drops()
            self.drain()
            self.start_tasks()
            budget -= 1
        return self

    # ---- the replica's report row ----------------------------------------
    def downtime(self, span: float) -> np.ndarray:
        """(M,) time each machine spent down within [0, span]."""
        s = np.clip(self.down_start, 0.0, span)
        e = np.clip(self.down_end, 0.0, span)
        return np.maximum(e - s, 0.0).sum(axis=1)

    def summary(self) -> dict:
        """The columns the program reports per replica, in float64."""
        st = self.status
        completed = int((st == COMPLETED).sum())
        preempted = int((st == PREEMPTED).sum())
        span = max(float(self.t_end.max()), 0.0)
        down = self.downtime(span)
        idle_t = np.maximum(np.maximum(span - self.active_time, 0.0) - down,
                            0.0)
        idle_e = float((self.power[self.mtype, 0] * self.power_scale
                        * idle_t).sum())
        active_e = float(self.energy.sum())
        resp = np.where(st == COMPLETED, self.t_end - self.arrival, 0.0)
        return {
            "completed": completed,
            "missed": int(((st == MISSED_QUEUE)
                           | (st == MISSED_RUNNING)).sum()),
            "cancelled": int((st == CANCELLED).sum()),
            "preempted": preempted,
            "requeues": int(self.n_preempts.sum()) - preempted,
            "availability": float(np.mean(
                1.0 - self.downtime(max(span, 1e-9)) / max(span, 1e-9))),
            "completion_rate": completed / self.n,
            "makespan": span,
            "energy": active_e + idle_e,
            "active_energy": active_e,
            "idle_energy": idle_e,
            "mean_response": float(resp.sum()) / max(completed, 1),
        }


COUNT_COLUMNS = ("completed", "missed", "cancelled", "preempted",
                 "requeues")
VALUE_COLUMNS = ("availability", "completion_rate", "makespan", "energy",
                 "active_energy", "idle_energy", "mean_response")


def simulate(inp: dict, policy: str, window: int | None = None,
             precision: str = "float64") -> dict:
    """One replica's report row (the unit a worker process computes)."""
    return Replica(inp, policy, window=window, precision=precision
                   ).run().summary()
