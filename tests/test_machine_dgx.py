"""The DGX A100 deployment's machine: 120 CPU workers and 8 GPUs, GPUs in
pairs on PCIe switches, all eight on one NVSwitch fabric, and kernel rates
that carry the paper machine's efficiencies to the new peaks."""
import pytest

from repro.configs import dgx_a100 as D
from repro.configs.paper_machine import CPU_CLASS, GPU_CLASS, paper_machine
from repro.core.perfmodel import TransferModel


def test_resources_and_link_groups():
    m = D.dgx_a100()
    assert len(m.resources) == 128 and len(m.cpus) == 120 and len(m.gpus) == 8
    assert [r.rid for r in m.resources] == list(range(128))
    assert {r.mem for r in m.cpus} == {-1}
    assert [r.mem for r in m.gpus] == list(range(8))
    assert m.link_groups == {s: [120 + 2 * s, 121 + 2 * s] for s in range(4)}
    assert m.link.bandwidth == 32e9 and m.link.latency == 15e-6


def test_fabric_joins_every_gpu_with_ports_of_their_own():
    m = D.dgx_a100()
    assert m.fabric.mems == tuple(range(8))
    assert m.fabric.link.bandwidth == 300e9
    assert sorted(m.fabric_ports) == list(range(8))
    ports = set(m.fabric_ports.values())
    assert len(ports) == 8 and not ports & set(m.link_groups)
    assert paper_machine(8).fabric is None and paper_machine(8).fabric_ports == {}


def test_rates_keep_the_paper_machines_efficiencies():
    m = D.dgx_a100()
    cpu, gpu = m.cpus[0].cls, m.gpus[0].cls
    for kind, rate in GPU_CLASS.rates.items():
        assert gpu.rate(kind) == pytest.approx(rate * 19.5e12 / 515e9)
    for kind, rate in CPU_CLASS.rates.items():
        assert cpu.rate(kind) == pytest.approx(rate * 36e9 / 10.64e9)
    assert gpu.rate("unknown") == pytest.approx(GPU_CLASS.default_rate * 19.5e12 / 515e9)
    # a tile gemm (tile 1024, f64) against one tile's copies
    gemm = 2 * 1024**3 / gpu.rate("gemm")
    tile = 1024 * 1024 * 8
    tm = TransferModel.of(m)
    assert 1.3 < tm.time(tile) / gemm < 1.6
    assert 0.2 < tm.peer_time(tile) / gemm < 0.25


def test_transfer_model_of_a_machine():
    tm = TransferModel.of(D.dgx_a100())
    assert tm.peer_mems == tuple(range(8)) and tm.peer_bandwidth == 300e9
    assert tm.peer_reach(-1) == 0 and tm.peer_reach(3) == 0b111111110
    plain = TransferModel.of(paper_machine(8))
    assert plain.peer_mems == () and plain.peer_reach(3) == 0
