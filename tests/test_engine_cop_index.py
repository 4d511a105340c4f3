"""The engine's index of completed COPs (``Simulation._finish_cop``) gives
``used_cops`` and ``tasks_no_cop`` exactly as a scan of every completed COP
at each task start does.

The scan is kept here as the plain reference: at every task start it loops
over ``completed_cops``, marks each COP that copied one of the task's inputs
to its node as used, and counts the task as started without a COP when no
completed COP was made for it on that node.
"""
import pytest

from repro.sim import SimConfig, Simulation, TenantSpec, TrafficConfig
from repro.workloads import make_workflow


def _scan(sim, tid: int, node: int, used: set[int]) -> bool:
    """The reference: one task start's scan of every completed COP; adds
    the COPs it used to ``used`` and returns whether one was made for it."""
    task = sim.wf.tasks[tid]
    needed = False
    for cid, (plan, _) in sim.completed_cops.items():
        if plan.target != node:
            continue
        files = {t.file_id for t in plan.transfers}
        if files & set(task.inputs):
            used.add(cid)
        if plan.task_id == tid:
            needed = True
    return needed


def _run_checked(sim):
    """Run ``sim``, comparing the index with the scan at every task start;
    returns the result, the reference's used COPs and its "none" count."""
    used: set[int] = set()
    no_cop = [0]
    starts = [0]
    orig = sim._start_task

    def start(tid, node):
        if not _scan(sim, tid, node, used):
            no_cop[0] += 1
        orig(tid, node)
        starts[0] += 1
        assert sim.used_cops == used, (tid, node)
        assert sim.tasks_no_cop == no_cop[0], (tid, node)

    sim._start_task = start
    res = sim.run()
    assert starts[0] == sim.task_starts > 0
    return res, used, no_cop[0]


def _workflow(name, n_nodes, seed=0, scale=0.2, c_node=1, c_task=2):
    # c_node above 1 lets two COPs copy one file to one node
    return lambda: Simulation(make_workflow(name, scale=scale, seed=seed),
                              SimConfig(n_nodes=n_nodes, seed=seed,
                                        c_node=c_node, c_task=c_task), "wow")


def _failing(name, n_nodes, node, at, join=False):
    def build():
        sim = Simulation(make_workflow(name, scale=0.3),
                         SimConfig(n_nodes=n_nodes), "wow")
        sim.schedule_failure(at, node=node)
        if join:
            sim.schedule_join(at * 2, node_id=n_nodes)
        return sim
    return build


def _service(n_nodes):
    traffic = TrafficConfig(
        tenants=(TenantSpec("a", weight=2.0, workflows=("rnaseq", "sarek"),
                            scale=0.05),
                 TenantSpec("b", weight=1.0,
                            workflows=("chipseq", "rangeland"),
                            scale=0.05)),
        rate=0.05, n_arrivals=16, max_backlog=3, seed=5)
    return lambda: Simulation(None, SimConfig(n_nodes=n_nodes), "wow",
                              traffic=traffic)


RUNS = {
    "group-4": _workflow("group", 4, scale=0.6),
    "fork-8": _workflow("fork", 8, seed=3),
    "sarek-8": _workflow("sarek", 8, scale=0.1),
    "sarek-4-c_node2": _workflow("sarek", 4, seed=1, scale=0.1, c_node=2,
                                 c_task=3),
    "group_multiple-4-c_node3": _workflow("group_multiple", 4, seed=1,
                                          scale=0.5, c_node=3, c_task=3),
    "syn_blast-16": _workflow("syn_blast", 16, seed=1, scale=0.4),
    "fork-6-failure": _failing("fork", 6, node=3, at=40.0),
    "group-8-failure-join": _failing("group", 8, node=0, at=30.0,
                                     join=True),
    "service-4": _service(4),
}


@pytest.mark.parametrize("name", sorted(RUNS))
def test_index_matches_the_scan(name):
    sim = RUNS[name]()
    res, used, no_cop = _run_checked(sim)
    assert sim.completed_cops, "the run made no COP: nothing was compared"
    assert used and sim.used_cops == used
    # the Table II columns are the reference's
    assert res.tasks_no_cop == no_cop and res.cops_used == len(used)
    # every COP completed is indexed once; every index entry visited at a
    # task start is a COP that was used there
    assert sim.cops_indexed == len(sim.completed_cops)
    assert sim.cops_scanned >= len(used)
    if "failure" in name:
        assert sim.failed_nodes
