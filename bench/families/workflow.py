"""Pegasus scientific workflows: DAGs of jobs whose edges carry files.

The program's workflow mode (``launch/experiment.py``,
``WorkloadAxis(shapes=...)``): every cell of the grid is one DAG of
``n_tasks`` jobs of a Pegasus workflow (Montage, CyberShake,
Epigenomics; Bharathi et al., WORKS 2008), its task types its job types
and its EET rows their runtime profiles (Juve et al., FGCS 29(3), 2013)
times the fleet's heterogeneity.  A child may run once every parent
completed and the parent's output file has been staged over the shared
storage: at ``max(arrival, max over in-edges of t_end[src] + delay)``.
A task whose parent failed is cancelled, and so on down the DAG.

Replicas come in *paired* cells: the ``n_p`` consecutive replicas of a
cell share one DAG, fleet and failure trace, one per policy (replica
``r`` runs policy ``r % n_p`` of cell ``r // n_p``); shape ``cell % n_s``,
fail rate ``(cell // n_s) % n_f``, DVFS state ``(cell // (n_s n_f)) %
n_d``.

This module draws every replica again from the seed in numpy, bitwise
as the program's normalize does, and simulates it with a plain
reference that extends ``reference.Replica`` by precedence, staging
delays, cascade cancels and HEFT's upward ranks.  It imports nothing of
the program (but ``make_spec``, which builds the program's spec).
"""
from __future__ import annotations

import numpy as np

from bench.inputs import DVFS_STATES, failure_trace
from bench.reference import (CANCELLED, COMPLETED, COUNT_COLUMNS,  # noqa
                             IN_BATCH, NOT_ARRIVED, RUNNING, VALUE_COLUMNS,
                             Replica)

INCONSISTENCY = 0.3
SLACK = 4.0

# job types: (name, mean runtime in s, MB handed to each child), the
# configuration's ``assumed`` values; a task's type is its job's index
JOBS = {
    "montage": (("mProjectPP", 1.73, 8.0), ("mDiffFit", 0.66, 0.01),
                ("mConcatFit", 143.26, 0.1), ("mBgModel", 384.49, 0.1),
                ("mBackground", 1.72, 8.0), ("mImgtbl", 2.78, 0.01),
                ("mAdd", 282.37, 170.0), ("mShrink", 66.10, 1.0),
                ("mJPEG", 0.64, 0.3)),
    "cybershake": (("ExtractSGT", 110.58, 150.0),
                   ("SeismogramSynthesis", 79.47, 0.02),
                   ("PeakValCalcOkaya", 0.55, 0.001),
                   ("ZipSeis", 265.73, 10.0), ("ZipPSA", 195.80, 0.5)),
    "epigenomics": (("fastQSplit", 34.34, 10.0), ("filterContams", 2.47, 10.0),
                    ("sol2sanger", 0.48, 10.0), ("fastq2bfq", 1.40, 3.0),
                    ("map", 201.78, 1.5), ("mapMerge", 11.00, 30.0),
                    ("maqIndex", 43.57, 100.0), ("pileup", 55.95, 80.0)),
}


# -- the three DAG structures ---------------------------------------------
def _montage(n: int, rng):
    """mProjectPP x P -> mDiffFit x D (pairs of near projections) ->
    mConcatFit -> mBgModel -> mBackground x P (mBgModel, own projection)
    -> mImgtbl -> mAdd -> mShrink -> mJPEG; 2P + D + 6 = n, D ≈ 3P."""
    p = max(2, round((n - 6) / 5))
    while n - 6 - 2 * p > p * (p - 1) // 2:
        p += 1
    d = n - 6 - 2 * p
    pairs = []
    k = 1
    while len(pairs) < d:
        pairs += [(i, i + k) for i in range(p - k)]
        k += 1
    pick = np.sort(rng.choice(len(pairs), d, replace=False))
    concat, bg0 = p + d, p + d + 2
    counts = [p, d, 1, 1, p, 1, 1, 1, 1]
    parents = [[] for _ in range(p)] + [list(pairs[j]) for j in pick]
    parents += [list(range(p, p + d)), [concat]]
    parents += [[i, concat + 1] for i in range(p)]
    parents += [list(range(bg0, bg0 + p)), [bg0 + p], [bg0 + p + 1],
                [bg0 + p + 2]]
    types = [0] * p + [1] * d + [2, 3] + [4] * p + [5, 6, 7, 8]
    return types, parents, counts


def _cybershake(n: int, rng):
    """ExtractSGT x S -> SeismogramSynthesis x K (one drawn extraction
    each) -> PeakValCalcOkaya x K; ZipSeis over the seismograms, ZipPSA
    over the peaks; S + 2K + 2 = n."""
    s = max(2, round(n / 250))
    s += (n - 2 - s) % 2
    k = (n - 2 - s) // 2
    sgt = np.sort(rng.integers(0, s, k))
    parents = [[] for _ in range(s)] + [[int(g)] for g in sgt]
    parents += [[s + j] for j in range(k)]
    parents += [list(range(s, s + k)), list(range(s + k, s + 2 * k))]
    types = [0] * s + [1] * k + [2] * k + [3, 4]
    return types, parents, [s, k, k, 1, 1]


def _epigenomics(n: int, rng):
    """fastQSplit x L (odd, about n / 250) -> per chunk, round-robin over
    the lanes, filterContams -> sol2sanger -> fastq2bfq -> map ->
    mapMerge of its lane -> maqIndex -> pileup; 2L + 4C + r + 2 = n with
    a last chunk of r < 4 stages."""
    lanes = max(1, n // 250) | 1
    c, r = divmod(n - 2 - 2 * lanes, 4)
    types = [0] * lanes
    parents = [[] for _ in range(lanes)]
    merge_in = [[] for _ in range(lanes)]
    for j, stages in enumerate([4] * c + ([r] if r else [])):
        prev = j % lanes
        for st in range(stages):
            types.append(1 + st)
            parents.append([prev])
            prev = len(parents) - 1
        merge_in[j % lanes].append(prev)
    merge0 = len(parents)
    types += [5] * lanes + [6, 7]
    parents += merge_in + [list(range(merge0, merge0 + lanes)),
                           [merge0 + lanes]]
    counts = [lanes] + [c + (r > j) for j in range(4)] + [lanes, 1, 1]
    return types, parents, counts


DAGS = {"montage": _montage, "cybershake": _cybershake,
        "epigenomics": _epigenomics}


def _counts(shape: str, n: int) -> np.ndarray:
    """Tasks of each job type; the structure's counts depend on n alone."""
    return np.asarray(DAGS[shape](n, np.random.default_rng(0))[2],
                      np.float64)


def seconds_per_unit(shape: str, n: int) -> float:
    """One simulated time unit: the shape's mean job runtime, seconds."""
    c = _counts(shape, n)
    return float(c @ np.asarray([j[1] for j in JOBS[shape]], np.float64)
                 / c.sum())


# -- the cell's draws ---------------------------------------------------
def grid_cell(axes: dict, cell: int) -> dict:
    n_s, n_f = len(axes["shapes"]), len(axes["fail_rates"])
    return {"shape": axes["shapes"][cell % n_s],
            "fail_rate": axes["fail_rates"][(cell // n_s) % n_f],
            "dvfs": axes["dvfs_states"][(cell // (n_s * n_f))
                                        % len(axes["dvfs_states"])]}


def synth_eet(costs, n_task_types: int, n_machine_types: int, seed: int):
    """Job-type cost x machine slowness x noise (the draw of a task cost
    is made and set aside, so the machine factors are Braun's)."""
    rng = np.random.default_rng(seed)
    rng.lognormal(0.0, 1.0, size=(n_task_types, 1))
    machine_slow = rng.lognormal(0.0, 0.5, size=(1, n_machine_types))
    noise = rng.lognormal(0.0, INCONSISTENCY,
                          size=(n_task_types, n_machine_types))
    task_cost = 1.0 * np.asarray(costs, np.float64).reshape(-1, 1)
    return (task_cost * machine_slow * noise).astype(np.float32)


def workflow(shape: str, n: int, mean_eet, bandwidth: float, seed: int):
    """-> (type_id, src, dst, delay f32, deadline f32) of one DAG."""
    rng = np.random.default_rng(seed)
    types, parents, _ = DAGS[shape](n, rng)
    type_id = np.asarray(types, np.int32)
    src = np.asarray([p for ps in parents for p in sorted(ps)], np.int32)
    dst = np.asarray([i for i, ps in enumerate(parents) for _ in ps],
                     np.int32)
    nbytes = np.asarray([j[2] for j in JOBS[shape]], np.float32)[type_id[src]]
    bw = float(bandwidth) * seconds_per_unit(shape, n)
    delay = (nbytes / np.float32(bw)).astype(np.float32)
    # deadline: slack x the critical path from the sources, staging in
    w = np.asarray(mean_eet, np.float32)[type_id].astype(np.float64)
    d64 = delay.astype(np.float64)
    cum = w.copy()
    best = np.full(n, -np.inf)
    for e in range(src.shape[0]):
        i = dst[e]
        best[i] = max(best[i], cum[src[e]] + d64[e])
        if e + 1 == src.shape[0] or dst[e + 1] != i:
            cum[i] = w[i] + best[i]
    deadline = (0.0 + SLACK * np.ones(n) * cum).astype(np.float32)
    return type_id, src, dst, delay, deadline


def upward_ranks(src, dst, w, delay) -> np.ndarray:
    """rank(i) = w[i] + max over child edges (delay + rank(child))."""
    rank = np.asarray(w, np.float64).copy()
    d = np.asarray(delay, np.float64)
    for e in range(len(src) - 1, -1, -1):
        p = src[e]
        rank[p] = max(rank[p], w[p] + (d[e] + rank[dst[e]]))
    return rank.astype(np.float32)


def draw(config: dict, axes: dict, seed: int, r: int) -> tuple[dict, str]:
    """-> (replica ``r``'s inputs as float32/int arrays, its policy)."""
    n, n_m = config["n_tasks"], config["n_machines"]
    n_tt, n_mt = config["n_task_types"], config["n_machine_types"]
    n_p = len(axes["policies"])
    cell = r // n_p
    g = grid_cell(axes, cell)
    crng = np.random.default_rng(seed + 104729 * cell)
    costs = np.ones(n_tt, np.float64)
    jobs = JOBS[g["shape"]]
    costs[:len(jobs)] = np.asarray([j[1] for j in jobs], np.float64) \
        / seconds_per_unit(g["shape"], n)
    eet = synth_eet(costs, n_tt, n_mt, seed + cell)
    power = np.stack([crng.uniform(20, 60, n_mt),
                      crng.uniform(80, 300, n_mt)], axis=1).astype(np.float32)
    mean_eet = eet.mean(1)
    type_id, src, dst, delay, deadline = workflow(
        g["shape"], n, mean_eet, axes["bandwidth"], seed + 7919 * cell)
    if g["fail_rate"] > 0.0:
        down_start, down_end = failure_trace(
            n_m, axes["n_intervals"], g["fail_rate"], axes["mttr"],
            seed + 31 * cell)
    else:
        down_start = np.full((n_m, axes["n_intervals"]), np.inf, np.float32)
        down_end = down_start.copy()
    kill = crng.random() < axes["spot_frac"]
    speed, power_scale = DVFS_STATES[g["dvfs"]]
    noise = crng.lognormal(0.0, 0.1, n).astype(np.float32)
    mtype = crng.integers(0, n_mt, n_m)
    rank = upward_ranks(src, dst, mean_eet.astype(np.float64)[type_id],
                        delay)
    return {"arrival": np.zeros(n, np.float32), "type_id": type_id,
            "deadline": deadline, "eet": eet, "power": power,
            "mtype": mtype, "noise": noise,
            "speed": np.full(n_m, speed, np.float32),
            "power_scale": np.full(n_m, power_scale, np.float32),
            "down_start": down_start, "down_end": down_end,
            "kill": np.full(n_m, kill, bool), "src": src, "dst": dst,
            "delay": delay, "rank": rank}, axes["policies"][r % n_p]


# -- the reference ------------------------------------------------------
class DagReplica(Replica):
    """``reference.Replica`` with precedence: a release phase after
    availability that cancels tasks with a failed parent (to a
    fixpoint), arrivals gated on every parent completed and every input
    staged, staged releases as events, and HEFT by upward rank."""

    def __init__(self, inp: dict, policy: str, **kw):
        super().__init__(inp, policy, **kw)
        q = self.q
        self.rank = q(inp["rank"])
        delay = q(inp["delay"])
        self.in_edges = [[] for _ in range(self.n)]
        for p, c, d in zip(inp["src"], inp["dst"], delay):
            self.in_edges[int(c)].append((int(p), float(d)))

    def _parents_done(self, t: int) -> tuple[bool, bool]:
        """(every parent terminal, some parent failed)."""
        st = [self.status[p] for p, _ in self.in_edges[t]]
        return (all(s >= COMPLETED for s in st),
                any(s > COMPLETED for s in st))

    def ready(self, t: int) -> float:
        return max((self.q(self.t_end[p] + d) for p, d in self.in_edges[t]),
                   default=-np.inf)

    def release(self):
        changed = True
        while changed:
            changed = False
            for t in np.nonzero(self.status == NOT_ARRIVED)[0]:
                done, failed = self._parents_done(t)
                if done and failed:
                    self.status[t] = CANCELLED
                    self.t_end[t] = self.time
                    changed = True

    def arrivals(self):
        for t in np.nonzero((self.status == NOT_ARRIVED)
                            & (self.arrival <= self.time))[0]:
            if self._parents_done(t)[0] and self.ready(t) <= self.time:
                self.status[t] = IN_BATCH

    def next_event(self) -> float:
        """As ``Replica.next_event``, but a task waits for its parents:
        a blocked task is no candidate (its release rides on a parent's
        end), a released one arrives once its inputs are staged, and one
        with a failed parent is cancelled now."""
        cands = [np.inf]
        for t in np.nonzero(self.status == NOT_ARRIVED)[0]:
            done, failed = self._parents_done(t)
            if done:
                cands.append(self.time if failed
                             else max(self.arrival[t], self.ready(t)))
        if (self.running >= 0).any():
            cands.append(self.busy_until[self.running >= 0].min())
        live = (self.status >= IN_BATCH) & (self.status <= RUNNING)
        if live.any():
            cands.append(self.deadline[live].min())
        later = self.transitions[self.transitions > self.time]
        if len(later):
            cands.append(later[0])
        return min(cands)

    def decide(self, batch, rooms, avail):
        if self.policy != "heft":
            return super().decide(batch, rooms, avail)
        t = batch[int(np.argmax(self.rank[batch]))]
        return t, int(np.argmin(np.where(
            rooms, self.q(avail + self.expected[t]), np.inf)))

    def run(self) -> "DagReplica":
        budget = 6 * self.n + 16 + 2 * self.down_start.size
        while budget > 0 and not (self.status >= COMPLETED).all():
            t = self.next_event()
            if not np.isfinite(t):
                break
            self.time = max(t, self.time)
            self.completions()
            self.availability()
            self.release()
            self.arrivals()
            self.deadline_drops()
            self.drain()
            self.start_tasks()
            budget -= 1
        return self


def simulate(inp: dict, policy: str, window: int | None = None,
             precision: str = "float64") -> dict:
    """One replica's report row (the unit a worker process computes)."""
    if window is not None:
        raise ValueError("the workflow family has no streaming window")
    return DagReplica(inp, policy, precision=precision).run().summary()


# -- the program's spec and layout ---------------------------------------
def axes(config: dict, traffic: dict) -> dict:
    """The cell's grid axes: shapes x fail x DVFS x policies."""
    scen = dict(config["scenario"], **traffic.get("scenario", {}))
    return {"shapes": list(traffic.get("shapes", config["shapes"])),
            "fail_rates": list(scen["fail_rates"]),
            "dvfs_states": list(scen["dvfs_states"]),
            "spot_frac": float(scen["spot_frac"]),
            "mttr": float(scen["mttr"]),
            "n_intervals": int(scen["n_intervals"]),
            "bandwidth": float(config["bandwidth_mb_s"]),
            "policies": list(traffic["policies"])}


def make_spec(config: dict, traffic: dict, seed: int):
    """The ``ExperimentSpec`` one call of the cell runs, for ``seed``."""
    from repro.launch import experiment as X
    ax = axes(config, traffic)
    return X.ExperimentSpec(
        n_replicas=traffic["replicas"],
        fleet=X.FleetAxis(config["n_machines"], config["n_machine_types"]),
        workload=X.WorkloadAxis(config["n_tasks"],
                                n_task_types=config["n_task_types"],
                                shapes=tuple(ax["shapes"]),
                                bandwidth=ax["bandwidth"]),
        scenario=X.ScenarioAxis(fail_rates=tuple(ax["fail_rates"]),
                                dvfs_states=tuple(ax["dvfs_states"]),
                                spot_frac=ax["spot_frac"],
                                mttr=ax["mttr"],
                                n_intervals=ax["n_intervals"]),
        policy=X.PolicyAxis(tuple(ax["policies"])),
        seed=seed)


def replica_policies(axes: dict, n_replicas: int) -> np.ndarray:
    """(R,) index into ``axes["policies"]``: the paired layout."""
    return np.arange(n_replicas) % len(axes["policies"])
