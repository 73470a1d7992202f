"""Performance models: history-based task timing + bandwidth transfer model.

Paper §2.3: "Our task prediction relies on an history-based model, and
transfer time estimation is based on asymptotic bandwidth". The runtime
observes real durations and corrects erroneous predictions online (StarPU
does the same). Here the *observed* durations come from the simulator's
ground-truth rates (with seeded noise), so the model genuinely calibrates
at runtime instead of being an oracle.

This module is array-native: ``Residency`` stores one bitmask per data
object (bit ``mem+1`` set ⇔ a valid copy lives in memory space ``mem``; the
host, ``HOST_MEM = -1``, is bit 0) and maintains an incremental
resident-bytes vector, so ``is_resident`` / ``transfer_hops`` are O(1) bit
tests and whole (tasks × resources) transfer/affinity matrices come out of
a handful of numpy ops over the CSR incidence of a
:class:`~repro.core.dag.GraphArrays`. The scalar reference implementations
live in ``repro.core._reference``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .dag import GraphArrays, Task
from .machine import HOST_MEM, MachineModel, Resource, ResourceClass

# Residency masks live in int64 arrays: bit 0 is the host, bit (mem+1) is
# device memory ``mem``; 62 device memories fit before the sign bit.
_MAX_MEM = 61


def _mem_bit(mem: int) -> int:
    if not -1 <= mem <= _MAX_MEM:
        raise ValueError(f"memory id {mem} outside supported range [-1, {_MAX_MEM}]")
    return 1 << (mem + 1)


@dataclass
class HistoryPerfModel:
    """Per (task kind, resource class) running mean of observed durations.

    Before any observation the model falls back to a static estimate
    ``flops / class_rate`` — the same bootstrap StarPU/XKaapi use before
    calibration kicks in.

    ``version`` increments on every ``observe`` so vectorized consumers
    (:class:`ClassPredictor`) know when their per-kind cache is stale.
    """

    _stats: Dict[Tuple[str, str], Tuple[int, float]] = field(default_factory=dict)
    version: int = 0

    def predict(self, task: Task, cls: ResourceClass) -> float:
        key = (task.kind, cls.name)
        st = self._stats.get(key)
        if st is not None and st[0] > 0:
            return st[1]
        return cls.exec_time(task.kind, task.flops)

    def observe(self, task: Task, cls: ResourceClass, duration: float) -> None:
        key = (task.kind, cls.name)
        n, mean = self._stats.get(key, (0, 0.0))
        n += 1
        mean += (duration - mean) / n
        self._stats[key] = (n, mean)
        self.version += 1

    def n_observations(self) -> int:
        return sum(n for n, _ in self._stats.values())

    def kind_table(
        self, cls: ResourceClass, kinds: Sequence[str]
    ) -> Tuple[List[float], List[bool]]:
        """(means, observed) per kind for resource class ``cls`` (plain
        lists: rebuilt on every observation, so no numpy allocation)."""
        means = []
        observed = []
        stats = self._stats
        name = cls.name
        for kind in kinds:
            st = stats.get((kind, name))
            if st is not None and st[0] > 0:
                means.append(st[1])
                observed.append(True)
            else:
                means.append(0.0)
                observed.append(False)
        return means, observed


class ClassPredictor:
    """Cached vectorized ``HistoryPerfModel.predict`` for one resource class.

    The static fallback ``flops / rate`` is a per-task constant, computed
    once per graph; the per-kind observed means are rebuilt lazily whenever
    the model's version moves (each rebuild is a loop over the handful of
    task kinds, not over tasks). ``times(tids)`` then reproduces
    ``predict`` elementwise: the observed running mean where one exists,
    the static estimate otherwise — the identical IEEE operations, just
    batched.
    """

    def __init__(self, model: HistoryPerfModel, cls: ResourceClass, arr: GraphArrays):
        self.model = model
        self.cls = cls
        self.arr = arr
        rates = np.array([cls.rate(k) for k in arr.kinds], dtype=np.float64)
        # exec_time: flops / rate, with the 1e-7 bookkeeping floor
        static = arr.flops / rates[arr.kind_codes]
        self.static_times = np.where(arr.flops <= 0.0, 1e-7, static)
        self.static_list = self.static_times.tolist()
        self._codes_list = arr.kind_codes.tolist()
        self._version = -1
        self._means_list: List[float] = []
        self._observed_list: List[bool] = []

    def _refresh(self) -> None:
        if self._version != self.model.version:
            self._means_list, self._observed_list = self.model.kind_table(
                self.cls, self.arr.kinds
            )
            self._version = self.model.version

    def times(self, tids: np.ndarray) -> np.ndarray:
        """Predicted durations for tasks ``tids`` (bit-equal to ``predict``)."""
        self._refresh()
        codes = self.arr.kind_codes[tids]
        means = np.asarray(self._means_list, dtype=np.float64)
        observed = np.asarray(self._observed_list, dtype=bool)
        return np.where(
            observed[codes], means[codes], self.static_times[tids]
        )

    def times_list(self, tids: Sequence[int]) -> List[float]:
        """Scalar fast path of :meth:`times` for narrow activations."""
        self._refresh()
        codes = self._codes_list
        means = self._means_list
        observed = self._observed_list
        static = self.static_list
        out = []
        for tid in tids:
            c = codes[tid]
            out.append(means[c] if observed[c] else static[tid])
        return out


# How a copy reaches its destination memory (``route``): it does not move
# (already there, or nowhere yet), one host link hop (host→device or
# device→host), one fabric hop between peers, or device→host→device.
ROUTE_NONE, ROUTE_HOST, ROUTE_PEER, ROUTE_STAGED = range(4)
ROUTE_HOPS = (0, 1, 1, 2)


def route(mask: int, dst_mem: int, peer_bits: int = 0) -> int:
    """The route of a copy to ``dst_mem`` of data whose valid copies are
    ``mask``; ``peer_bits`` are the memories one fabric hop from
    ``dst_mem`` (0 where it has no fabric). A peer on the fabric is
    preferred to the host, and staging through the host is the last
    resort: the paper-era PCIe path."""
    if mask == 0 or mask & (1 << (dst_mem + 1)):
        return ROUTE_NONE
    if mask & peer_bits:
        return ROUTE_PEER
    if dst_mem == HOST_MEM or mask & 1:
        return ROUTE_HOST
    return ROUTE_STAGED


@dataclass
class TransferModel:
    """Asymptotic-bandwidth estimator for host<->device transfers, and the
    one owner of how a copy is routed (:meth:`route`).

    ``predict`` ignores contention (a *prediction*, as in the paper — the
    simulator's ground truth does model switch contention, which is exactly
    the modeling error the paper discusses). ``peer_mems``, where the
    machine has a fabric, are the memories one fabric hop apart, with the
    fabric's own ``peer_bandwidth`` and ``peer_latency``. A copy costs one
    hop's time on its route, and a staged one two host hops (``2 × t``).
    """

    bandwidth: float
    latency: float = 1e-5
    peer_bandwidth: float = 0.0
    peer_latency: float = 0.0
    peer_mems: Tuple[int, ...] = ()

    def __post_init__(self) -> None:
        # memoized unique-memory decompositions, keyed by the mems tuple
        self._mem_plans: Dict[tuple, tuple] = {}
        # per destination memory: the residency bits one fabric hop away
        bits = 0
        for m in self.peer_mems:
            bits |= _mem_bit(m)
        self._peer_reach: Dict[int, int] = {m: bits for m in self.peer_mems}

    @classmethod
    def of(cls, machine: MachineModel) -> "TransferModel":
        """The prediction of ``machine``'s own links."""
        fab = machine.fabric
        if fab is None:
            return cls(bandwidth=machine.link.bandwidth, latency=machine.link.latency)
        return cls(bandwidth=machine.link.bandwidth, latency=machine.link.latency,
                   peer_bandwidth=fab.link.bandwidth, peer_latency=fab.link.latency,
                   peer_mems=tuple(fab.mems))

    def peer_reach(self, dst_mem: int) -> int:
        """Residency bits of the memories one fabric hop from ``dst_mem``."""
        return self._peer_reach.get(dst_mem, 0)

    def route(self, mask: int, dst_mem: int) -> int:
        """The route (``ROUTE_*``) of a copy to ``dst_mem`` on this machine."""
        return route(mask, dst_mem, self._peer_reach.get(dst_mem, 0))

    def time(self, nbytes: int) -> float:
        if nbytes <= 0:
            return 0.0
        return self.latency + nbytes / self.bandwidth

    def peer_time(self, nbytes: int) -> float:
        if nbytes <= 0:
            return 0.0
        return self.peer_latency + nbytes / self.peer_bandwidth

    def read_time(self, mask: int, dst_mem: int, nbytes: int) -> float:
        """Predicted time to bring one datum of ``nbytes`` to ``dst_mem``."""
        r = self.route(mask, dst_mem)
        if r == ROUTE_HOST:
            return self.time(nbytes)
        if r == ROUTE_STAGED:
            return 2 * self.time(nbytes)
        if r == ROUTE_PEER:
            return self.peer_time(nbytes)
        return 0.0

    def mem_plan(self, mems: tuple) -> tuple:
        """Decompose a resource→memory list into (unique mems, column-of,
        already-unique flag). Memoized; shared by the numpy matrix path and
        the jax scoring backend so both see the identical column layout."""
        cached = self._mem_plans.get(mems)
        if cached is None:
            uniq: List[int] = []
            col_of: List[int] = []
            seen: Dict[int, int] = {}
            for mem in mems:
                j = seen.get(mem)
                if j is None:
                    j = seen[mem] = len(uniq)
                    uniq.append(mem)
                col_of.append(j)
            cached = (uniq, col_of, len(uniq) == len(mems))
            self._mem_plans[mems] = cached
        return cached

    def task_input_transfer_time(
        self,
        task: Task,
        resource: Resource,
        residency: "Residency",
    ) -> float:
        """Predicted time to bring missing inputs of ``task`` to ``resource``."""
        total = 0.0
        for d in task.reads:
            if not residency.is_resident(d.name, resource.mem):
                total += self.read_time(residency.mask(d.name), resource.mem, d.size_bytes)
        return total

    # ------------------------------------------------------------------
    def task_input_transfer_rows(
        self,
        arr: GraphArrays,
        tids: Sequence[int],
        mems: Sequence[int],
        residency: "Residency",
    ) -> List[List[float]]:
        """(len(tids) × len(mems)) predicted input-transfer times, as rows.

        Same values as :meth:`task_input_transfer_matrix`; narrow
        activations (the common case — ``activate`` usually wakes 1-3
        tasks) take a scalar path over the per-task read lists and the
        residency bitmasks, wide ones take the batched numpy path. Both
        price each read by its :meth:`route` (``t``, ``2 * t`` or the
        fabric's own time) and sum in access order, so every entry is
        bit-equal to the scalar reference.
        """
        # resources sharing a memory space (all CPUs see host memory) share
        # a column: compute per unique memory, then expand
        uniq, col_of, full = self.mem_plan(tuple(mems))

        n = len(tids)
        if n >= 32:
            arr_tids = np.asarray(tids, dtype=np.int64)
            rows = self.task_input_transfer_matrix(
                arr, arr_tids, uniq, residency
            ).tolist()
        else:
            masks = residency._mask
            # per-task (read name, per-hop time, fabric-hop time) triples
            # are graph-static: precompute once per (model, graph) and only
            # refresh the residency masks per activation
            key = ("read_times", self.latency, self.bandwidth,
                   self.peer_latency, self.peer_bandwidth)
            prep = arr.cache.get(key)
            if prep is None:
                prep = [
                    [(name, self.time(size), self.peer_time(size)
                      if self.peer_mems else 0.0) for _, name, size in reads]
                    for reads in arr.task_reads
                ]
                arr.cache[key] = prep
            reach = self._peer_reach
            rows = []
            for tid in tids:
                reads = [(masks.get(name, 0), t, tp) for name, t, tp in prep[tid]]
                row = []
                for mem in uniq:
                    bit = 1 << (mem + 1)
                    peer = reach.get(mem, 0)
                    total = 0.0
                    for m, t, tp in reads:
                        if m & bit or m == 0:
                            continue
                        if m & peer:
                            total += tp
                        elif mem == HOST_MEM or m & 1:
                            total += t
                        else:
                            total += 2 * t
                    row.append(total)
                rows.append(row)
        if full:
            return rows
        return [[row[j] for j in col_of] for row in rows]

    def task_input_transfer_matrix(
        self,
        arr: GraphArrays,
        tids: np.ndarray,
        mems: Sequence[int],
        residency: "Residency",
    ) -> np.ndarray:
        """(len(tids) × len(mems)) predicted input-transfer times.

        Column ``j`` is ``task_input_transfer_time`` against memory space
        ``mems[j]``, computed from the read-CSR slice and the residency
        bitmasks. Per-read contributions are summed in access order, so
        each entry is bit-equal to the scalar loop.
        """
        indptr, ids, sizes = arr.gather_csr(
            tids, arr.read_indptr, arr.read_ids, arr.read_sizes
        )
        n, m = len(tids), len(mems)
        if len(ids) == 0:
            return np.zeros((n, m), dtype=np.float64)
        masks = residency.mask_of_ids(ids)
        # per-read transfer time (latency + size/bw; 0 for empty reads)
        per_read = np.where(sizes <= 0, 0.0, self.latency + sizes / self.bandwidth)
        if self.peer_mems:
            per_peer = np.where(
                sizes <= 0, 0.0, self.peer_latency + sizes / self.peer_bandwidth
            )
        on_host = (masks & 1) != 0
        nowhere = masks == 0
        out = np.empty((n, m), dtype=np.float64)
        # reduceat quirks: an empty segment yields the element at its start
        # (fixed up below), and a start index == len(contrib) is invalid
        # (avoided by the appended 0.0, which also absorbs harmlessly into
        # the sum of the final non-empty segment).
        empty_seg = indptr[:-1] == indptr[1:]
        fix_empty = bool(empty_seg.any())
        for j, mem in enumerate(mems):
            bit = _mem_bit(mem)
            resident = (masks & bit) != 0
            if mem == HOST_MEM:
                hops = np.where(resident | nowhere, 0.0, 1.0)
            else:
                hops = np.where(
                    resident | nowhere, 0.0, np.where(on_host, 1.0, 2.0)
                )
            contrib = hops * per_read
            peer = self._peer_reach.get(mem, 0)
            if peer:
                contrib = np.where(
                    ~(resident | nowhere) & ((masks & peer) != 0), per_peer, contrib
                )
            col = np.add.reduceat(np.append(contrib, 0.0), indptr[:-1])[:n]
            if fix_empty:
                col = np.where(empty_seg, 0.0, col)
            out[:, j] = col
        return out


class Residency:
    """Tracks which memory spaces hold a *valid* copy of each data object.

    Writes invalidate all other copies (MSI-like, matching a runtime that
    manages coherent transfers).

    Storage is one int bitmask per data object. Standalone instances keep a
    name-keyed dict; :meth:`attach` binds the tracker to a
    :class:`GraphArrays` id space, adding a dense ``int64`` mask array
    (``mask_arr``) for vectorized consumers and an incrementally maintained
    per-memory resident-bytes vector, so ``bytes_resident`` is O(1) instead
    of a sweep over every data object.
    """

    def __init__(self) -> None:
        self._mask: Dict[str, int] = {}
        # attached-mode state (set by attach())
        self._name_to_id: Optional[Dict[str, int]] = None
        self.mask_list: Optional[List[int]] = None
        self._sizes: Optional[List[int]] = None
        self._resident_bytes: List[int] = [0] * (_MAX_MEM + 2)
        # optional mask-change callback ``(did, name, old, new)`` —
        # installed by the capacity-bounded memory layer
        # (repro.runtime.memory) to mirror residency into its per-memory
        # LRU/accounting; None (the default) keeps the hot paths untouched
        self.observer = None

    # ------------------------------------------------------------------
    def attach(self, arr: GraphArrays) -> None:
        """Bind to a graph's data-id space (enables the array fast paths)."""
        self._name_to_id = arr.name_to_id
        self.mask_list = [0] * len(arr.data_names)
        self._sizes = arr.data_sizes.tolist()
        self._resident_bytes = [0] * (_MAX_MEM + 2)
        for name, did in arr.name_to_id.items():
            m = self._mask.get(name)
            if m:
                self.mask_list[did] = m
                for mem in self._decode(m):
                    self._resident_bytes[mem + 1] += self._sizes[did]

    @staticmethod
    def _decode(mask: int) -> List[int]:
        mems = []
        mem = -1
        while mask:
            if mask & 1:
                mems.append(mem)
            mask >>= 1
            mem += 1
        return mems

    def _set_mask(self, name: str, new: int) -> None:
        old = self._mask.get(name, 0)
        if old == new:
            return
        self._mask[name] = new
        if self._name_to_id is not None:
            did = self._name_to_id.get(name)
            if did is not None:
                self.mask_list[did] = new
                size = self._sizes[did]
                rb = self._resident_bytes
                changed = old ^ new
                while changed:
                    low = changed & -changed
                    idx = low.bit_length() - 1  # == mem + 1
                    if new & low:
                        rb[idx] += size
                    else:
                        rb[idx] -= size
                    changed ^= low
                if self.observer is not None:
                    self.observer(did, name, old, new)

    # ------------------------------------------------------------------
    def is_resident(self, name: str, mem: int) -> bool:
        if not -1 <= mem <= _MAX_MEM:
            raise ValueError(f"memory id {mem} outside supported range")
        return bool(self._mask.get(name, 0) & (1 << (mem + 1)))

    def mask(self, name: str) -> int:
        return self._mask.get(name, 0)

    def mask_of_ids(self, ids: np.ndarray) -> np.ndarray:
        """Bitmask vector for data ids (attached mode only)."""
        ml = self.mask_list
        return np.fromiter(map(ml.__getitem__, ids), dtype=np.int64, count=len(ids))

    def locations(self, name: str) -> set:
        return set(self._decode(self._mask.get(name, 0)))

    def has_any(self, name: str) -> bool:
        return self._mask.get(name, 0) != 0

    def transfer_hops(self, name: str, dst_mem: int) -> int:
        """Link hops of the copy's :func:`route` on a machine without a
        fabric: 1 if a copy is on host or dst is host; 2 for GPU->GPU
        (device -> host -> device, the paper-era PCIe path)."""
        _mem_bit(dst_mem)
        return ROUTE_HOPS[route(self._mask.get(name, 0), dst_mem)]

    def add_copy(self, name: str, mem: int) -> None:
        if not -1 <= mem <= _MAX_MEM:
            raise ValueError(f"memory id {mem} outside supported range")
        self._set_mask(name, self._mask.get(name, 0) | (1 << (mem + 1)))

    def write(self, name: str, mem: int) -> None:
        if not -1 <= mem <= _MAX_MEM:
            raise ValueError(f"memory id {mem} outside supported range")
        self._set_mask(name, 1 << (mem + 1))

    def write_id(self, did: int, name: str, new_mask: int) -> None:
        """Attached-mode fast write: caller supplies the data id and the
        (validated) single-bit mask. Semantically ``write(name, mem)``."""
        ml = self.mask_list
        old = ml[did]
        if old == new_mask:
            return
        self._mask[name] = new_mask
        ml[did] = new_mask
        size = self._sizes[did]
        rb = self._resident_bytes
        changed = old ^ new_mask
        while changed:
            low = changed & -changed
            idx = low.bit_length() - 1  # == mem + 1
            if new_mask & low:
                rb[idx] += size
            else:
                rb[idx] -= size
            changed ^= low
        if self.observer is not None:
            self.observer(did, name, old, new_mask)

    def drop_copy(self, name: str, mem: int) -> None:
        """Invalidate the copy of ``name`` at ``mem`` (eviction support).

        The inverse of :meth:`add_copy`: clears one residency bit, leaving
        any other valid copies untouched. A no-op when no copy is there.
        """
        if not -1 <= mem <= _MAX_MEM:
            raise ValueError(f"memory id {mem} outside supported range")
        self._set_mask(name, self._mask.get(name, 0) & ~(1 << (mem + 1)))

    def initialize(self, names: Iterable[str], mem: int) -> None:
        for n in names:
            self.write(n, mem)

    def bytes_resident(self, mem: int, sizes: Optional[Dict[str, int]] = None) -> int:
        """Bytes with a valid copy in ``mem``.

        With an explicit ``sizes`` dict this sums exactly those names (the
        original contract); attached instances answer the no-argument form
        from the incremental per-memory vector in O(1).
        """
        if sizes is not None:
            return sum(sz for n, sz in sizes.items() if self.is_resident(n, mem))
        if self._name_to_id is None:
            raise ValueError("bytes_resident() without sizes requires attach()")
        return self._resident_bytes[mem + 1]
