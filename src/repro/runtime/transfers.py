"""Transfer layer: link groups, the in-flight index, prefetch routing.

Lifted from the monolithic simulator's ``request_transfer`` / ``_one_hop``:

  * transfers serialize FIFO on their *link group* (GPUs sharing a PCIe
    switch share its bandwidth — ``link_free`` tracks when each group
    drains);
  * the in-flight index is kept per graph context and per data name
    (``ctx.inflight[name] -> {dst_mem: done_t}``), so duplicate requests
    dedup in O(1) and a write invalidates stale entries in O(copies);
  * each copy takes the route ``TransferModel.route`` picks: one host-link
    hop (host→device, device→host), one hop over the machine's peer
    fabric (its destination's fabric port is the contention group), or,
    where no fabric reaches, device→host→device (two hops, the paper-era
    PCIe path, reusing an already-in-flight host hop when one exists).
    ``Metrics`` counts the hops and bytes of each route.

Capacity-bounded memories (``repro.runtime.memory``) hook in at request
time: space at the destination is reserved *before* the hop is scheduled,
so any eviction write-back the reservation triggers serializes ahead of
the incoming copy on the same link — exactly how a coherent runtime
staging area behaves.

Transient link faults (opt-in via ``REPRO_SCHED_LINK_FLAKE``): each
demand hop fails with a seeded per-hop probability — the DMA ran, held
the link, and was dropped in flight. Failed hops retry with capped
exponential backoff (``REPRO_SCHED_BACKOFF_S`` base, doubling per
attempt, capped at 64×); when the ``REPRO_SCHED_RETRY_MAX`` budget is
exhausted the transfer *times out* and is re-sourced from another live
copy or host, modeled as one final reliable hop. Every attempt occupies
the link and is charged as real traffic (audited as ``retry`` /
``resource`` hops), so byte conservation holds attempt-for-attempt. The
flake generator lives on its own seeded stream: zero-flake runs consume
nothing and stay bit-for-bit identical.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from repro.core.machine import HOST_MEM, LinkModel, MachineModel
from repro.core.perfmodel import ROUTE_HOST, ROUTE_PEER

from .events import EventQueue
from .metrics import Metrics

# Dedicated flake stream key: keeps per-hop failure draws disjoint from
# the engine's noise stream and the churn stream for every engine seed.
_FLAKE_STREAM = 0xF1A4E


class TransferEngine:
    """Link timing + transfer routing for one engine."""

    __slots__ = (
        "machine", "model", "events", "metrics", "memory",
        "mem_link", "link_free", "_plain_link", "_link_lat", "_link_bw",
        "fabric_ports", "_fabric",
        "cancel_stale", "faults", "audit",
        "flake_rate", "retry_max", "backoff_s", "_flake_rng", "_flake_on",
    )

    def __init__(
        self,
        machine: MachineModel,
        transfer_model,
        events: EventQueue,
        metrics: Metrics,
    ) -> None:
        self.machine = machine
        self.model = transfer_model
        self.events = events
        self.metrics = metrics
        self.memory = None  # MemoryManager, wired by the engine
        self.faults = None  # FaultManager, wired by the engine
        self.audit = None  # repro.verify AuditLog, wired by the engine
        self.cancel_stale = False
        # transient link faults (inert until enable_flake)
        self.flake_rate = 0.0
        self.retry_max = 0
        self.backoff_s = 0.0
        self._flake_rng: Optional[np.random.Generator] = None
        self._flake_on = False
        self.link_free: Dict[int, float] = {}
        # accelerator memory -> link group (first resource on that memory)
        self.mem_link: Dict[int, Optional[int]] = {}
        for r in machine.resources:
            if r.is_accelerator:
                self.mem_link.setdefault(r.mem, r.link)
        # inlined link timing (hot path); only valid for a plain LinkModel
        self._plain_link = type(machine.link) is LinkModel
        self._link_lat = machine.link.latency
        self._link_bw = machine.link.bandwidth
        self.fabric_ports = machine.fabric_ports
        self._fabric = None if machine.fabric is None else machine.fabric.link

    # ------------------------------------------------------------------
    def one_hop(
        self, nbytes: int, group: Optional[int], t: float, kind: str = "copy",
        peer: bool = False,
    ) -> float:
        """Serialize the transfer on its link group (FIFO = shared bandwidth);
        ``peer`` times it on the fabric's link instead of the host's."""
        start = max(t, self.link_free.get(group, 0.0)) if group is not None else t
        if peer:
            dur = self._fabric.time(nbytes)
        elif self._plain_link:
            dur = 0.0 if nbytes <= 0 else self._link_lat + nbytes / self._link_bw
        else:
            dur = self.machine.link.time(nbytes)
        done = start + dur
        if group is not None:
            self.link_free[group] = done
        self.metrics.total_bytes += nbytes
        self.metrics.n_transfers += 1
        if self.audit is not None:
            self.audit.log_hop(kind, nbytes, group, t, done)
        return done

    # ------------------------------------------------------------------
    def enable_flake(
        self, rate: float, retry_max: int, backoff_s: float, seed: int
    ) -> None:
        """Arm the seeded per-hop failure model (the engine wires this
        when ``link_flake`` > 0; reliable engines never call it)."""
        if not (0.0 <= rate <= 1.0):
            raise ValueError(f"flake rate must be in [0, 1], got {rate}")
        if retry_max < 0:
            raise ValueError(f"retry_max must be >= 0, got {retry_max}")
        if backoff_s < 0:
            raise ValueError(f"backoff_s must be >= 0, got {backoff_s}")
        self.flake_rate = float(rate)
        self.retry_max = int(retry_max)
        self.backoff_s = float(backoff_s)
        self._flake_rng = np.random.default_rng(
            (int(seed) & 0xFFFFFFFF, _FLAKE_STREAM)
        )
        self._flake_on = self.flake_rate > 0.0

    def _flaky_hop(
        self,
        ctx,
        name: str,
        nbytes: int,
        group: Optional[int],
        t: float,
        dst_mem: int,
        peer: bool = False,
    ) -> float:
        """One demand hop under the flake model: retry with capped
        exponential backoff, re-source on timeout.

        Every attempt (the failed ones included) ran on the wire: it
        serializes on the link group and is charged as real traffic, so
        bytes are conserved attempt-for-attempt. The whole chain is
        priced synchronously — ``one_hop`` occupies links eagerly, and
        only the final landing is posted as an event — which keeps the
        event-loop structure (and the zero-flake path) untouched.
        """
        done = self.one_hop(nbytes, group, t, peer=peer)
        attempt = 0
        rng = self._flake_rng
        rate = self.flake_rate
        metrics = self.metrics
        while rng.random() < rate:
            if attempt >= self.retry_max:
                # retry budget exhausted: the transfer times out and is
                # re-sourced from another live copy or host — one final
                # reliable hop, so every transfer eventually lands
                metrics.n_timeouts += 1
                if self.audit is not None:
                    self.audit.log_timeout(
                        ctx.gid, name, dst_mem, done, attempt + 1, nbytes
                    )
                return self.one_hop(nbytes, group, done, kind="resource", peer=peer)
            attempt += 1
            delay = min(
                self.backoff_s * (2.0 ** (attempt - 1)),
                self.backoff_s * 64.0,
            )
            metrics.n_retries += 1
            metrics.retry_delay_s += delay
            if self.audit is not None:
                self.audit.log_retry(
                    ctx.gid, name, dst_mem, done, attempt, delay, nbytes
                )
            done = self.one_hop(nbytes, group, done + delay, kind="retry", peer=peer)
        return done

    # ------------------------------------------------------------------
    def request(
        self,
        ctx,
        name: str,
        size: int,
        dst_mem: int,
        now: float,
        protect=None,
    ) -> Optional[float]:
        """Ensure a valid copy of ``name`` will exist at ``dst_mem``.

        Returns the completion time, or None if already resident.
        ``protect`` (capacity-bounded mode) names data ids of ``ctx`` that
        the reservation's eviction pass must not victimize — the
        requesting task's own working set.
        """
        residency = ctx.residency
        mask = residency._mask.get(name, 0)
        if mask & (1 << (dst_mem + 1)):
            return None  # already resident
        inflight = ctx.inflight
        flights = inflight.get(name)
        if flights is not None:
            done = flights.get(dst_mem)
            if done is not None:
                return done
        if mask == 0:
            raise RuntimeError(f"no valid copy of {name} anywhere")
        memory = self.memory
        if memory is not None and memory.bounded and dst_mem != HOST_MEM:
            # reserve destination space first: eviction write-backs queue
            # on the link ahead of this copy
            memory.reserve(ctx, name, size, dst_mem, now, protect)
        ver = ctx.data_version.get(name, 0) if self.cancel_stale else 0
        # the destination memory's detach epoch (repro.runtime.faults):
        # a landing posted before a detach carries a stale epoch and is
        # dropped — the DMA died with the device. 0 whenever faults are
        # inactive (host memory never detaches, so host hops stay 0).
        faults = self.faults
        epoch = (
            faults.mem_epoch.get(dst_mem, 0)
            if faults is not None and faults.active
            else 0
        )
        mem_link = self.mem_link
        post = self.events.post
        flake = self._flake_on
        metrics = self.metrics
        audit = self.audit
        route = self.model.route(mask, dst_mem)
        if route == ROUTE_PEER:
            # a fabric peer holds a copy: one direct hop into dst's port
            peers = mask & self.model.peer_reach(dst_mem)
            src = (peers & -peers).bit_length() - 2
            port = self.fabric_ports[dst_mem]
            done = (
                self._flaky_hop(ctx, name, size, port, now, dst_mem, peer=True)
                if flake
                else self.one_hop(size, port, now, peer=True)
            )
            metrics.hops_peer += 1
            metrics.bytes_peer += size
        elif route == ROUTE_HOST and dst_mem != HOST_MEM:
            # a host copy exists: single host->device hop
            src = HOST_MEM
            done = (
                self._flaky_hop(
                    ctx, name, size, mem_link.get(dst_mem), now, dst_mem
                )
                if flake
                else self.one_hop(size, mem_link.get(dst_mem), now)
            )
            metrics.hops_host += 1
            metrics.bytes_host += size
        elif route == ROUTE_HOST:
            src = (mask & -mask).bit_length() - 2  # lowest-numbered location
            done = (
                self._flaky_hop(
                    ctx, name, size, mem_link.get(src), now, HOST_MEM
                )
                if flake
                else self.one_hop(size, mem_link.get(src), now)
            )
            metrics.hops_host += 1
            metrics.bytes_host += size
        else:
            # GPU -> host -> GPU (two hops, paper-era PCIe path)
            src = (mask & -mask).bit_length() - 2
            if flights is not None and HOST_MEM in flights:
                mid = flights[HOST_MEM]
            else:
                mid = (
                    self._flaky_hop(
                        ctx, name, size, mem_link.get(src), now, HOST_MEM
                    )
                    if flake
                    else self.one_hop(size, mem_link.get(src), now)
                )
                if flights is None:
                    flights = inflight[name] = {}
                flights[HOST_MEM] = mid
                post(mid, "xfer", (ctx, name, HOST_MEM, ver, 0))
                metrics.hops_staged += 1
                metrics.bytes_staged += size
                if audit is not None:
                    audit.note_request(ctx.gid, name, HOST_MEM, mid, now, src)
            src = HOST_MEM
            done = (
                self._flaky_hop(
                    ctx, name, size, mem_link.get(dst_mem), mid, dst_mem
                )
                if flake
                else self.one_hop(size, mem_link.get(dst_mem), mid)
            )
            metrics.hops_staged += 1
            metrics.bytes_staged += size
        if flights is None:
            flights = inflight[name] = {}
        flights[dst_mem] = done
        post(done, "xfer", (ctx, name, dst_mem, ver, epoch))
        if audit is not None:
            audit.note_request(ctx.gid, name, dst_mem, done, now, src)
        return done

    # ------------------------------------------------------------------
    def prefetch(self, ctx, task, mem: int, bit: int, now: float) -> None:
        """Start transfers for every non-resident input of ``task``."""
        mask_list = ctx.residency.mask_list
        inflight = ctx.inflight
        reads = ctx.arrays.task_reads[task.tid]
        protect = None
        for did, name, size in reads:
            if not mask_list[did] & bit:
                fl = inflight.get(name)
                if fl is None or mem not in fl:
                    if protect is None and self.memory is not None and self.memory.bounded:
                        protect = frozenset(d for d, _, _ in reads)
                    self.request(ctx, name, size, mem, now, protect)
