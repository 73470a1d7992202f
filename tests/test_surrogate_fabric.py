"""The surrogate episode engine stages device copies through the host, so
it refuses a machine with a peer fabric instead of mispricing it."""
import pytest

from repro.configs.dgx_a100 import dgx_a100
from repro.core import episode
from repro.linalg.cholesky import cholesky_graph


def test_surrogate_refuses_a_peer_machine():
    g = cholesky_graph(4, 256, with_fns=False)
    with pytest.raises(ValueError, match="peer fabric"):
        episode.build_plan(g, dgx_a100())
    with pytest.raises(ValueError, match="peer fabric"):
        episode.machine_axes(dgx_a100(), 128)


def test_run_batch_refuses_a_peer_machine():
    from repro.core.api import run_batch

    g = cholesky_graph(4, 256, with_fns=False)
    with pytest.raises(ValueError, match="peer fabric"):
        run_batch([{"graph": g, "machine": dgx_a100(), "strategy": "heft", "seed": 0}])
