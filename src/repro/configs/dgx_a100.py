"""An NVIDIA DGX A100 as a MachineModel.

NVIDIA's "DGX A100 System Architecture" document: eight A100-SXM4-40GB on
six NVSwitches (12 NVLink3 links per GPU, 300 GB/s each way per GPU, any
pair of GPUs one hop apart), the GPUs attached to the host in pairs through
PCIe Gen4 x16 switches, two AMD EPYC 7742 (128 cores, 2.25 GHz base). Each
GPU pins one core to run its worker, as the paper's model has it: 120 CPU
workers and 8 GPUs, 128 resources.

Kernel rates carry the paper machine's own efficiencies over to the new
peaks: ``rate = peak × paper_rate / paper_peak``, with an X5650 core at
10.64 GFLOP/s f64 (2.66 GHz × 4) against an EPYC 7742 core at 36 GFLOP/s
(2.25 GHz × 16), and a C2050 at 515 GFLOP/s f64 against the A100's f64
tensor-core 19.5 TFLOP/s. ``ASSUMED`` lists what is set here rather than
published.
"""
from __future__ import annotations

from repro.core.machine import LinkModel, MachineModel, ResourceClass, make_machine

from .paper_machine import CPU_CLASS, GPU_CLASS, PCIE_LATENCY

GF = 1e9
X5650_CORE_PEAK = 10.64 * GF
C2050_PEAK = 515.0 * GF
EPYC_7742_CORE_PEAK = 36.0 * GF
A100_F64_PEAK = 19.5e12
TOTAL_CORES = 128
N_GPUS = 8
PCIE_BANDWIDTH = 32e9  # PCIe Gen4 x16, per switch, shared by its two GPUs
NVSWITCH_BANDWIDTH = 300e9  # per GPU, each way
NVSWITCH_LATENCY = PCIE_LATENCY

ASSUMED = {
    "kernel efficiencies": "the paper machine's rate over peak, per kind and class, held at the new peaks",
    "pcie_bandwidth": "32 GB/s, the Gen4 x16 peak, not derated, as paper_machine's 8 GB/s is PCIe 2.0 x16's peak",
    "pcie_latency": "15 us per copy, paper_machine's",
    "nvswitch_latency": "15 us per copy, as PCIe: the copy's set-up, not the wire, sets it",
    "nvswitch_contention": "copies into one GPU share its 300 GB/s port; a source's outgoing port is not modelled",
    "gpu_memory": "40 GB per GPU, unbounded here: an 8 GiB matrix fits in one GPU",
}


def _scaled(cls: ResourceClass, new_peak: float, paper_peak: float) -> ResourceClass:
    return ResourceClass(
        name=cls.name,
        rates={k: new_peak * r / paper_peak for k, r in cls.rates.items()},
        default_rate=new_peak * cls.default_rate / paper_peak,
    )


CPU_CLASS_EPYC = _scaled(CPU_CLASS, EPYC_7742_CORE_PEAK, X5650_CORE_PEAK)
GPU_CLASS_A100 = _scaled(GPU_CLASS, A100_F64_PEAK, C2050_PEAK)


def dgx_a100() -> MachineModel:
    """120 EPYC cores and 8 A100s: two GPUs to a PCIe switch, all eight on
    one NVSwitch fabric."""
    return make_machine(
        n_cpus=TOTAL_CORES,
        n_gpus=N_GPUS,
        cpu_class=CPU_CLASS_EPYC,
        gpu_class=GPU_CLASS_A100,
        pcie_bandwidth=PCIE_BANDWIDTH,
        pcie_latency=PCIE_LATENCY,
        gpus_per_switch=2,
        gpu_pins_cpu=True,
        fabric=LinkModel(bandwidth=NVSWITCH_BANDWIDTH, latency=NVSWITCH_LATENCY),
    )
