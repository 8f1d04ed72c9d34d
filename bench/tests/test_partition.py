"""Partition allocation: the reference, written from the semantics alone,
against the program through the benchmark's own entry and check, on a
small machine of three unequal node groups (unequal counts, speeds and
power; node order ``id`` and ``cheap``; dense and grouped tables), for
every scheduler of the grid at two timeouts. Schedules agree exactly, and
energy within the ``nasa_ipsc`` configuration's limit."""
import json
import os

import numpy as np
import pytest

import check
import harness
from cells import BENCH
from entries.experiments_run import Entry
from traffic import generate

LABELS = ["FCFS PSUS", "EASY PSUS", "FCFS PSAS", "EASY PSAS",
          "FCFS PSAS+IPM", "EASY PSAS+IPM"]
TIMEOUTS = [300, 1800]
LIMITS = json.load(open(os.path.join(BENCH, "configs", "nasa_ipsc.json")))["limits"]


def group(name, count, speed, watts, t_on, t_off):
    """A node group in the platform JSON schema; ``watts`` by state: sleep,
    switching on, idle, active, switching off."""
    sleep, on, idle, active, off = watts
    return {"name": name, "count": count, "compute_speed": speed, "states": {
        "sleep": {"power": sleep}, "idle": {"power": idle},
        "active": {"power": active},
        "switching_on": {"power": on, "transition_time": t_on},
        "switching_off": {"power": off, "transition_time": t_off}}}


# cheap order (active watts per unit of speed): hybrid 150, thin 200, fat 240
GROUPS = [group("thin", 7, 1.0, (10.0, 150.0, 100.0, 200.0, 20.0), 300, 200),
          group("fat", 4, 1.25, (12.0, 200.0, 150.0, 300.0, 25.0), 600, 300),
          group("hybrid", 3, 1.6, (8.0, 180.0, 90.0, 240.0, 15.0), 120, 180)]
N = sum(g["count"] for g in GROUPS)


def config(node_order, grouped, allocation="partition"):
    return {"platform": {"nb_nodes": N, "node_groups": GROUPS},
            "engine": {"node_order": node_order, "grouped_tables": grouped,
                       "window": 32, "allocation": allocation},
            "limits": LIMITS}


def jobs_of(rows):
    """A trace from (submit time, res, runtime, requested time) rows."""
    a = np.asarray(rows, np.int64)
    n = len(a)
    return {"job_id": np.arange(n, dtype=np.int64), "res": a[:, 1],
            "subtime": a[:, 0], "reqtime": a[:, 3], "runtime": a[:, 2],
            "user_id": np.zeros(n, np.int64)}


# job 0 fits the thin group alone (wider than fat and hybrid); job 1 then
# finds one thin node left: ``any`` takes it and two fat nodes, the
# partition rule the fat group, whose third node comes before hybrid's
WIDE = [(0, 6, 1000, 1500), (0, 3, 1000, 1500), (50, 2, 400, 900),
        (60, 5, 700, 2000), (900, 4, 300, 600), (1000, 1, 2000, 2500),
        (1200, 7, 500, 800), (1300, 3, 900, 1000), (4000, 2, 100, 300)]
# job 2 is wider than every group: it never starts, and EASY passes it
WIDER = WIDE[:2] + [(10, 12, 500, 600)] + WIDE[2:]
TRACES = {
    "generated": generate.generate(
        {"nb_res": N, "max_res": 7, "min_res": 1, "mean_interarrival": 150.0,
         "mean_runtime": 1200.0, "cv_runtime": 2.2, "overreq_factor": 4.0},
        40, 2**31 + 16),
    "wide": jobs_of(WIDE),
    "wider_than_every_group": jobs_of(WIDER),
}


def program(cfg, jobs, tmp):
    """Each lane's answer as the benchmark's entry gets it from the program."""
    entry = Entry(cfg, {"schedulers": LABELS, "timeouts": TIMEOUTS})
    swf = str(tmp / "trace.swf")
    generate.write_swf(jobs, swf, N)
    rec = harness.Recorder()
    rec.call = call = harness.Call(0, str(tmp / "out"))
    with harness.engine_spans(rec, entry):
        call.result = entry.call(swf, call.out_dir)
    return entry.scenarios, entry.outputs(call.result, call.out_dir,
                                          call.engine_out,
                                          range(len(entry.scenarios)))


@pytest.mark.parametrize("trace", sorted(TRACES))
@pytest.mark.parametrize("grouped", [False, True], ids=["dense", "grouped"])
@pytest.mark.parametrize("node_order", ["id", "cheap"])
def test_reference_agrees_with_the_program(tmp_path, node_order, grouped, trace):
    cfg = config(node_order, grouped)
    jobs = TRACES[trace]
    scenarios, got = program(cfg, jobs, tmp_path)
    for lane, (label, timeout) in enumerate(scenarios):
        ref = check.reference(cfg, jobs, label, timeout)
        mism, rel = check.compare(got[lane], ref)
        assert mism == 0, (label, timeout, got[lane].schedule, ref.schedule())
        assert rel <= LIMITS["energy_rel_err"], (label, timeout, rel)


def _run(node_order, allocation, jobs, label="EASY PSUS"):
    cfg = config(node_order, False, allocation)
    return check.reference(cfg, jobs, label, 300)


def test_partition_picks_the_group_whose_last_needed_node_comes_first():
    """Realized runtimes tell the groups apart: 1000 s of work takes
    1000 s on thin nodes, 800 s on fat ones and 625 s on hybrid ones."""
    jobs = TRACES["wide"]
    part, anyg = _run("id", "partition", jobs), _run("id", "any", jobs)
    took = lambda r: r.finish[:2] - r.start[:2]  # noqa: E731
    assert list(took(part)) == [1000, 800]  # thin alone, then fat alone
    assert list(took(anyg)) == [1000, 1000]  # thin, then thin + fat
    assert (part.schedule() != anyg.schedule()).any()
    # in cheap order hybrid comes first: ``any`` spans hybrid and thin
    # for job 0, the partition rule takes thin, the only group of 6
    cheap = _run("cheap", "partition", jobs)
    assert list(took(cheap)) == [1000, 625]


@pytest.mark.parametrize("label", ["FCFS PSUS", "EASY PSUS", "EASY PSAS+IPM"])
def test_a_job_wider_than_every_group_never_starts(label):
    jobs = TRACES["wider_than_every_group"]
    r = _run("id", "partition", jobs, label)
    assert r.start[2] == -1 and r.finish[2] == -1
    if label.startswith("FCFS"):
        assert (r.start[3:] == -1).all()  # blocked behind it
    else:
        assert r.start[3] == 50  # backfilled past it on arrival
    assert _run("id", "any", jobs, label).finish[2] >= 0


def test_unknown_allocation_is_refused():
    with pytest.raises(ValueError, match="allocation"):
        _run("id", "islands", TRACES["wide"])
