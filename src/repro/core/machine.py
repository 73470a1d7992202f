"""Machine model: heterogeneous resources, memory spaces, links.

Faithful to the paper's platform abstraction:
  * ``m`` homogeneous CPUs sharing host memory (no transfer among them),
  * ``k`` homogeneous GPUs, each with a private memory, attached to the host
    through PCIe switches; two GPUs on one switch share the 16x bandwidth,
  * each *running* GPU monopolizes one CPU core to manage its worker
    (paper §4.1), so ``k`` GPUs leave ``total_cores - k`` compute CPUs.

Beyond the paper's box, a machine may declare a peer :class:`Fabric`
(NVLink/NVSwitch between GPUs, ICI between TPU chips): one direct hop
between any two of its device memories, bypassing the host. Without one,
a device→device copy is staged through host memory (two PCIe hops). How a
copy is routed and priced is decided in one place,
``repro.core.perfmodel.TransferModel.route``. Multi-hop fabrics (a torus
whose far chips are several links apart) are not modelled.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

HOST_MEM = -1  # memory-space id of host memory


@dataclass(frozen=True)
class ResourceClass:
    """A class of homogeneous processors with per-task-kind rates.

    ``rates`` maps task kind -> effective FLOP/s for that kind on this class.
    ``default_rate`` is used for unknown kinds.
    """

    name: str
    rates: Dict[str, float]
    default_rate: float

    def rate(self, kind: str) -> float:
        return self.rates.get(kind, self.default_rate)

    def exec_time(self, kind: str, flops: float) -> float:
        r = self.rate(kind)
        if flops <= 0.0:
            return 1e-7  # bookkeeping tasks are cheap but not free
        return flops / r


@dataclass(frozen=True)
class Resource:
    """One worker: a CPU core or a GPU (with its manager core)."""

    rid: int
    cls: ResourceClass
    mem: int  # memory space id: HOST_MEM for CPUs, >=0 for GPU memories
    link: Optional[int] = None  # PCIe switch / ICI link group id (None: none)

    @property
    def is_accelerator(self) -> bool:
        return self.mem != HOST_MEM

    def __repr__(self) -> str:
        return f"{self.cls.name}{self.rid}"


@dataclass
class LinkModel:
    """Asymptotic-bandwidth + latency transfer model (StarPU-like).

    ``bandwidth`` is per *switch group* (bytes/s); GPUs sharing a switch share
    it. ``latency`` is the fixed per-transfer cost.
    """

    bandwidth: float
    latency: float = 1e-5

    def time(self, nbytes: int, sharing: int = 1) -> float:
        if nbytes <= 0:
            return 0.0
        return self.latency + nbytes / (self.bandwidth / max(1, sharing))


@dataclass
class Fabric:
    """A peer fabric between device memories: one direct hop between any
    two of ``mems``, timed by ``link`` (bandwidth each way per device).
    Copies into one device serialize on that device's fabric port."""

    link: LinkModel
    mems: Tuple[int, ...]


@dataclass
class MachineModel:
    resources: List[Resource]
    link: LinkModel
    # link group id -> list of resource ids attached (for contention)
    link_groups: Dict[int, List[int]] = field(default_factory=dict)
    fabric: Optional[Fabric] = None

    def __post_init__(self) -> None:
        if not self.link_groups:
            groups: Dict[int, List[int]] = {}
            for r in self.resources:
                if r.link is not None:
                    groups.setdefault(r.link, []).append(r.rid)
            self.link_groups = groups
        # fabric port (contention group) of each fabric memory, numbered
        # after the host links so the two never share a queue
        self.fabric_ports: Dict[int, int] = {}
        if self.fabric is not None:
            base = 1 + max(self.link_groups, default=-1)
            self.fabric_ports = {m: base + i for i, m in enumerate(self.fabric.mems)}
        # cached partitions (resources never change after construction)
        self._cpus = [r for r in self.resources if not r.is_accelerator]
        self._gpus = [r for r in self.resources if r.is_accelerator]

    # ------------------------------------------------------------------
    @property
    def cpus(self) -> List[Resource]:
        return self._cpus

    @property
    def gpus(self) -> List[Resource]:
        return self._gpus

    def by_id(self, rid: int) -> Resource:
        return self.resources[rid]

    def classes(self) -> List[ResourceClass]:
        seen: Dict[str, ResourceClass] = {}
        for r in self.resources:
            seen.setdefault(r.cls.name, r.cls)
        return list(seen.values())

    def link_sharing(self, rid: int, active_per_group: Dict[int, int]) -> int:
        """How many *active* transfers share this resource's link group."""
        r = self.by_id(rid)
        if r.link is None:
            return 1
        return max(1, active_per_group.get(r.link, 1))


def make_machine(
    n_cpus: int,
    n_gpus: int,
    cpu_class: ResourceClass,
    gpu_class: ResourceClass,
    pcie_bandwidth: float = 8e9,
    pcie_latency: float = 1e-5,
    gpus_per_switch: int = 2,
    gpu_pins_cpu: bool = True,
    fabric: Optional[LinkModel] = None,
) -> MachineModel:
    """Build the paper-style machine.

    ``n_cpus`` is the number of *cores in the box*; if ``gpu_pins_cpu`` each
    GPU removes one compute core (paper: "Each running GPU monopolizes a CPU
    to manage its worker"). ``fabric``, where given, joins every GPU memory
    in one peer fabric of that link.
    """
    compute_cpus = n_cpus - n_gpus if gpu_pins_cpu else n_cpus
    if compute_cpus < 0:
        raise ValueError("more GPUs than cores to pin")
    resources: List[Resource] = []
    rid = 0
    for _ in range(compute_cpus):
        resources.append(Resource(rid, cpu_class, HOST_MEM, None))
        rid += 1
    for g in range(n_gpus):
        # Up to 4 switches; with <=4 GPUs each gets its own switch (paper:
        # "Experiments using up to 4 GPUs avoid this bandwidth constraint").
        switch = g % 4 if n_gpus <= 4 else g // gpus_per_switch
        resources.append(Resource(rid, gpu_class, mem=g, link=switch))
        rid += 1
    return MachineModel(
        resources=resources,
        link=LinkModel(bandwidth=pcie_bandwidth, latency=pcie_latency),
        fabric=None if fabric is None else Fabric(fabric, tuple(range(n_gpus))),
    )
