"""On a machine with a peer fabric the jax scoring backend prices copies by
route exactly as the numpy path does: placements bit for bit, in native
f64 and in the integer-exact f64 a TPU runs, for DADA and for HEFT."""
import pytest

from repro.configs.dgx_a100 import dgx_a100
from repro.configs.paper_machine import CPU_CLASS, GPU_CLASS
from repro.core import DADA, HEFT, run_simulation
from repro.core import backend as backend_mod
from repro.core import f64
from repro.core.machine import LinkModel, make_machine
from repro.linalg.cholesky import cholesky_graph
from repro.linalg.lu import lu_graph

jax = pytest.importorskip("jax")

STRATEGIES = {
    "heft": lambda b: HEFT(backend=b),
    "dada(0.5)+cp": lambda b: DADA(alpha=0.5, use_cp=True, backend=b),
}
MACHINES = {
    "dgx_a100": dgx_a100,
    # a fabric slower per copy than a host hop for small tiles: the route
    # is still the peer's, and both paths must price it alike
    "slow_fabric": lambda: make_machine(
        8, 4, CPU_CLASS, GPU_CLASS, fabric=LinkModel(12e9, 4e-5)),
}


@pytest.fixture(params=["native", "soft"])
def jax_backend(request, monkeypatch):
    monkeypatch.setenv("REPRO_SCHED_JAX_MIN", "1")
    if request.param == "soft":
        monkeypatch.setattr(backend_mod, "f64_for_platform", lambda p: f64.SOFT)
    backend_mod._reset_backend_cache()
    yield backend_mod.get_backend("jax")
    backend_mod._reset_backend_cache()


def _fingerprint(res):
    return (res.makespan, res.total_bytes, res.n_transfers, res.routes,
            tuple((iv.tid, iv.rid, iv.start, iv.end) for iv in res.intervals))


@pytest.mark.parametrize("machine", sorted(MACHINES))
@pytest.mark.parametrize("strat", sorted(STRATEGIES))
def test_jax_places_like_numpy_on_a_peer_machine(jax_backend, machine, strat):
    m = MACHINES[machine]()
    for graph in (cholesky_graph(6, 512, itemsize=8, with_fns=False),
                  lu_graph(4, 512, with_fns=False)):
        a = run_simulation(graph, m, STRATEGIES[strat]("numpy"), seed=5)
        b = run_simulation(graph, m, STRATEGIES[strat]("jax"), seed=5)
        assert _fingerprint(a) == _fingerprint(b)
        assert a.routes["hops_peer"] > 0
    c = jax_backend.counts
    assert c["device"] > 0 and c["outside"] == c["rejected"] == 0
    assert c["cells_device"] > 0 and c["cells_host"] == 0
