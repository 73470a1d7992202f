"""``chip_smoke.py``'s phases at tiny sizes on the CPU.

The script itself refuses to run without a TPU (its ``main`` checks the
platform); its phase functions take their sizes as arguments, so the
checks they make — device-scored activations, placements equal to numpy,
the surrogate's ranking against the exact engine, and the f32 residual
bounds of the executed schedule — run here on small inputs.
"""
import importlib.util
from pathlib import Path

import pytest

from repro.configs.paper_machine import scaled_machine

pytest.importorskip("jax")

_ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_at_tiny_sizes(smoke):
    exact = smoke.phase_exact(4, 128, scaled_machine(n_gpus=6, n_cpus=2), jax_min=1)
    assert set(exact) == set(smoke.EXACT_SPECS)
    smoke.phase_surrogate(8, 256, n_gpus=2, n_seeds=4)
    out = smoke.phase_tiles(4, 128, exact[smoke.EXACT_SPECS[0]])
    assert out["schedule"]["res_max"] < 1e-5


def test_main_refuses_a_cpu(smoke, capsys):
    assert smoke.main() != 0
    captured = capsys.readouterr()
    assert "no TPU" in captured.err
    assert '"ok"' not in captured.out
