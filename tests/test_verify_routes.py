"""The verifier's ROUTE invariant: direct device->device copies verify
clean on a machine that declares a peer fabric between the two memories,
and are flagged on one that does not."""
import copy

import pytest

from repro.configs.dgx_a100 import dgx_a100
from repro.configs.paper_machine import CPU_CLASS, GPU_CLASS, paper_machine
from repro.core.machine import HOST_MEM, LinkModel, make_machine
from repro.core.simulator import Simulator
from repro.linalg.cholesky import cholesky_graph
from repro.sched import resolve
from repro.verify import errors, verify_audit


def _peer_machine():
    return make_machine(12, 4, CPU_CLASS, GPU_CLASS, fabric=LinkModel(50e9, 5e-6))


def _audited(machine, spec, nt=8, **kw):
    sim = Simulator(cholesky_graph(nt, 256, with_fns=False), machine, resolve(spec),
                    seed=0, noise=0.0, audit=True, **kw)
    res = sim.run()
    return sim, res


def _direct(log):
    return [land for land in log.landings
            if land.src is not None and HOST_MEM not in (land.src, land.mem)]


@pytest.mark.parametrize("spec", ["heft", "dada?alpha=0.5&use_cp=1"])
def test_peer_copies_verify_clean_on_a_declared_fabric(spec):
    sim, res = _audited(_peer_machine(), spec)
    assert res.routes["hops_peer"] == len(_direct(sim.audit)) > 0
    assert sim.audit.machine["fabric"] == [0, 1, 2, 3]
    assert errors(verify_audit(sim.audit)) == []


def test_churned_peer_machine_verifies_clean():
    sim, res = _audited(_peer_machine(), "dada?alpha=0.5&use_cp=1",
                        churn=150.0, fault_mode="drain")
    assert sim.faults.history and res.routes["hops_peer"] > 0
    assert errors(verify_audit(sim.audit)) == []


def test_direct_copy_without_a_fabric_is_flagged():
    sim, _ = _audited(_peer_machine(), "heft")
    log = copy.deepcopy(sim.audit)
    del log.machine["fabric"]
    assert "ROUTE" in {f.code for f in errors(verify_audit(log))}


def test_staged_copy_claimed_direct_is_flagged():
    # on the paper machine every device copy lands from the host; one
    # that claims a device as its source broke the staging
    sim, res = _audited(paper_machine(4), "heft")
    assert res.routes["hops_staged"] > 0 and not _direct(sim.audit)
    log = copy.deepcopy(sim.audit)
    land = next(x for x in log.landings if x.mem != HOST_MEM and x.src == HOST_MEM
                and any(y.mem == HOST_MEM and y.name == x.name and y.src != HOST_MEM
                        for y in log.landings))
    land.src = (land.mem + 1) % 4
    assert "ROUTE" in {f.code for f in errors(verify_audit(log))}


def test_the_deployment_verifies_clean_under_audit_on_the_normal_path():
    # the benchmark's deployment as a user runs it: the policy registry,
    # device scoring from 8 wide, the independent verifier on
    from repro.core import run_simulation
    from repro.sched.config import SchedConfig

    cfg = SchedConfig(backend="jax", jax_min=8, audit=True)
    strategy = resolve("dada?alpha=0.5&use_cp=1", backend="jax", config=cfg)
    res = run_simulation(cholesky_graph(32, 1024, itemsize=8, with_fns=False), dgx_a100(),
                         strategy, seed=2, config=cfg)
    assert len(res.intervals) == 5984 and res.routes["hops_peer"] > 0
    counts = strategy._scoring_backend().counts
    assert counts["cells_device"] > 0 and counts["cells_host"] > 0
