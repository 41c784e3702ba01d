"""Transport layer: who carries a byte between two devices (beyond paper §6).

The paper's stated limitation is that "two devices cannot communicate with
each other directly" — every exchange is host↔device, and §5.6 shows that
funnel losing on a Gbit link.  Its future work ("it may also be possible to
use MPI collective communications") is exactly what the OpenMP Cluster model
(arXiv:2207.05677) and HDArray (arXiv:1809.05657) build: a runtime that moves
data peer-to-peer behind the directive interface.  This module makes the
topology a first-class, swappable object:

* :class:`HostFunnelTransport` — paper-faithful: a device→device copy is a
  fetch to the host plus a re-send, every byte crossing the host NIC twice.
* :class:`PeerTransport` — devices exchange buffers with SEND/RECV commands
  that rendezvous across two device streams (:meth:`DevicePool.peer_copy`);
  bytes are accounted per directed link and timed on per-link lanes.

Collectives are built *on* the transport from the one primitive, so the same
ring all-reduce runs over either topology and the cost model shows the
difference instead of a ``record_adjustment`` pretending it:

* :meth:`Transport.ring_allreduce` — whole-buffer ring: D-1 rounds, each
  device forwards the buffer it received and accumulates into its own copy;
  per-link traffic is ``(D-1)·|buf|``, with the round's D messages
  concurrent on their per-link lanes in the modeled timeline.
* :meth:`Transport.gather` — leaf-wise gather of every device's buffer to a
  root's scratch slots.
* :meth:`Transport.broadcast` — ring-chain broadcast (root → root+1 → …),
  each hop stream-ordered after the previous hop's RECV.
* :meth:`Transport.allreduce_mean` — gather → reduce at the root in device
  order → scale by 1/D → broadcast.  The root reduction adds in ascending
  device order, matching the host-mediated ``sum(views)/D`` exactly, so
  direct parameter averaging is *bit-identical* to the funnel path.

**Hierarchical path** (rack-aware): installing a
:class:`~repro.core.topology.Topology` with more than one rack on the
transport makes every collective above dispatch to its hierarchical
counterpart — reduce-within-rack onto each rack leader, *chain* the partial
across the leaders in ascending order, then broadcast leader-to-leaders and
within each rack.  Cross-rack traffic drops from the flat ring's
``O(D·|buf|)`` to ``O(R·|buf|)`` (one partial up the chain, one result back
down), and because the chain adds in ascending device order the result is
**bitwise identical** to the serial left-associated sum — the same
association :meth:`allreduce_mean`'s flat reduction and the host-mediated
``sum(views)/D`` use, so flat, hierarchical and host-mediated paths all
agree bit for bit.  (The flat *ring* all-reduce associates per ring
position, so it agrees with the others only to float tolerance — the
hierarchical path is the more-exact one.)

All collectives operate on mediary handles already resident on the devices
and compose with the dependency-aware stream: SEND reads, RECV writes, the
on-device reduction EXECs read both operands and write back the accumulator,
so a collective interleaves safely with ``nowait`` regions sharing the same
buffers.
"""
from __future__ import annotations

import concurrent.futures as _cf
import threading as _threading
import time as _time
from typing import Any, Dict, List, Optional, Sequence

import jax
import numpy as _np

from .costmodel import LinkModel

#: Kernels the collectives EXEC on the devices; registered lazily into the
#: pool's own table so every pool (and its remote replicas, in the paper's
#: model) agrees on the wire index.
ADD_KERNEL = "__transport_add"
DIV_KERNEL = "__transport_div"
Q8_KERNEL = "__transport_q8"
ID_KERNEL = "__transport_id"


def _ensure_kernels(pool) -> None:
    table = pool.table
    if ADD_KERNEL not in table:
        table.register(ADD_KERNEL, lambda a, b: a + b)
    if DIV_KERNEL not in table:
        table.register(DIV_KERNEL, lambda a, s: a / s)
    if ID_KERNEL not in table:
        # device-local move of a finished scratch accumulator into a live
        # buffer (a stream writer, no wire traffic)
        table.register(ID_KERNEL, lambda a: a)
    if Q8_KERNEL not in table:
        from . import compression as comp

        def q8_roundtrip(a, block=256):
            # what the wire does to a message under block-int8 compression:
            # quantize, (send,) dequantize — the lossy round trip, on-device
            return comp.decompress(comp.compress(a, block), a.shape, a.dtype)

        table.register(Q8_KERNEL, q8_roundtrip)


class Transport:
    """How a buffer moves from one device's mediary slot to another's.

    Subclasses implement :meth:`sendrecv`; the collectives below are
    topology-agnostic and inherit whichever fabric the subclass provides.
    """

    kind = "abstract"

    #: Optional :class:`~repro.core.topology.Topology`.  When set (and it
    #: describes the pool with more than one rack) the collectives dispatch
    #: hierarchically and :meth:`edge_time`/:meth:`edge_route` price per
    #: device pair instead of uniformly.
    topology = None

    def _hier_ok(self, D: int) -> bool:
        """Whether the hierarchical collective path applies at size ``D``."""
        t = self.topology
        return t is not None and t.n_racks > 1 and t.n_devices == D

    def sendrecv(self, pool, src: int, src_handle: int,
                 dst: int, dst_handle: int, *,
                 nbytes: Optional[int] = None, tag: str = ""):
        """Copy ``(src, src_handle)`` into ``(dst, dst_handle)``.

        Returns the future of the destination write (a registered writer of
        ``dst_handle`` in ``dst``'s stream), or None for a transport whose
        writes are synchronous.
        """
        raise NotImplementedError

    def edge_time(self, cost, src: int, dst: int, nbytes: int) -> float:
        """Modeled seconds to carry one ``nbytes`` dependency edge src→dst.

        What a cost-driven placement policy charges for routing an edge over
        this fabric (``cost`` is the pool's :class:`~repro.core.costmodel.
        CostModel`).  The base transport is the host funnel: a device→device
        copy is a fetch plus a re-send, two messages on the host NIC.
        """
        return cost.link.time(nbytes, 1) * 2

    def edge_route(self, cost, src: int, dst: int,
                   nbytes: int) -> "tuple[float, str]":
        """``(seconds, wire)`` for one dependency edge over this fabric.

        ``wire`` is the route string a placement policy hands the runner:
        ``"peer"`` for a raw message, ``"peer+int8"`` where a topology-aware
        transport decides the block-int8 wire beats the raw bytes on this
        pair's link.  The base fabric has no per-pair knowledge: one raw
        message at :meth:`edge_time`'s price.
        """
        return self.edge_time(cost, src, dst, nbytes), "peer"

    # -- collectives -----------------------------------------------------------
    def ring_allreduce(self, pool, handles: Sequence[Sequence[int]],
                       specs: Sequence[jax.ShapeDtypeStruct], *,
                       wire_nbytes: Optional[Sequence[int]] = None,
                       tag: str = "ring") -> List[List[Any]]:
        """In-place sum across devices: ``handles[d][j] ← Σ_d handles[d][j]``.

        Whole-buffer ring: in round ``t`` device ``d`` forwards the buffer it
        received in round ``t-1`` (its own in round 0) to ``d+1`` and adds
        the buffer arriving from ``d-1`` into its accumulator.  After
        ``D-1`` rounds every device holds the full sum (per-device addition
        order follows the ring, so replicas agree to float tolerance, not
        bitwise).  Receive buffers ping-pong between two scratch slots: a
        round's SEND reads the *previous* round's slot while its RECV fills
        the other, so concurrent sends and receives of one round never
        touch the same handle.  SEND/RECV and writebacks issue
        asynchronously; the host loop does synchronize on each on-device
        ADD (``exec_kernel`` returns the value — the simulation's wall
        clock serializes there, the *modeled* timeline overlaps per lane).
        ``wire_nbytes[j]`` overrides leaf ``j``'s accounted message size
        (modeled wire compression).  Returns the per-device per-leaf futures
        of the final accumulator writes (stream ordering for entry updates).

        With a multi-rack :attr:`topology` installed this dispatches to
        :meth:`hier_allreduce` — same in-place sum, ``O(R)`` instead of
        ``O(D)`` cross-rack messages, and a *serial* (ascending) addition
        order where the ring's is per-position.
        """
        D, L = len(handles), len(specs)
        last: List[List[Any]] = [[None] * L for _ in range(D)]
        if D <= 1:
            return last
        if self._hier_ok(D):
            return self.hier_allreduce(pool, handles, specs,
                                       wire_nbytes=wire_nbytes, tag=tag)
        _ensure_kernels(pool)
        tmp = [[[pool.alloc(d, s.shape, s.dtype, tag=f"{tag}:tmp")
                 for s in specs] for d in range(D)] for _ in range(2)]
        try:
            for step in range(D - 1):
                cur, prev = tmp[step % 2], tmp[(step - 1) % 2]
                for d in range(D):
                    nxt = (d + 1) % D
                    for j in range(L):
                        src_h = handles[d][j] if step == 0 else prev[d][j]
                        self.sendrecv(pool, d, src_h, nxt, cur[nxt][j],
                                      nbytes=None if wire_nbytes is None
                                      else wire_nbytes[j],
                                      tag=f"{tag}:r{step}")
                for d in range(D):
                    for j in range(L):
                        out = pool.exec_kernel(
                            d, ADD_KERNEL,
                            buffers={"a": handles[d][j], "b": cur[d][j]},
                            tag=f"{tag}:add")
                        last[d][j] = pool.transfer_to_writeback(d, handles[d][j],
                                                                out)
        finally:
            # scratch is freed even on a failed round (FREE is a stream
            # writer: it runs after any in-flight SEND/RECV of the slot)
            for half in tmp:
                for d in range(D):
                    for j in range(L):
                        pool.free(d, half[d][j])
        return last

    def gather(self, pool, handles: Sequence[Sequence[int]],
               specs: Sequence[jax.ShapeDtypeStruct], *, root: int = 0,
               tag: str = "gather") -> Dict[int, List[int]]:
        """Copy every non-root device's buffer into fresh scratch slots on
        ``root``.  Returns ``{src_device: [scratch handles]}``; the caller
        owns (and frees) the scratch."""
        D = len(handles)
        scratch: Dict[int, List[int]] = {}
        for d in range(D):
            if d == root:
                continue
            scratch[d] = [pool.alloc(root, s.shape, s.dtype, tag=f"{tag}:buf")
                          for s in specs]
            for j, s in enumerate(specs):
                self.sendrecv(pool, d, handles[d][j], root, scratch[d][j],
                              tag=tag)
        return scratch

    def broadcast(self, pool, handles: Sequence[Sequence[int]],
                  specs: Sequence[jax.ShapeDtypeStruct], *, root: int = 0,
                  tag: str = "bcast") -> List[List[Any]]:
        """Ring-chain broadcast of ``root``'s buffer into every device's
        handles (root → root+1 → …).  Each hop's SEND reads the handle the
        previous hop's RECV wrote, so the chain pipelines per leaf.  Returns
        per-device per-leaf futures of the destination writes.  Dispatches
        to :meth:`hier_broadcast` under a multi-rack :attr:`topology`."""
        D, L = len(handles), len(specs)
        last: List[List[Any]] = [[None] * L for _ in range(D)]
        if self._hier_ok(D):
            return self.hier_broadcast(pool, handles, specs, root=root,
                                       tag=tag)
        chain = [(root + i) % D for i in range(D)]
        for prev, cur in zip(chain, chain[1:]):
            for j in range(L):
                last[cur][j] = self.sendrecv(pool, prev, handles[prev][j],
                                             cur, handles[cur][j], tag=tag)
        return last

    def allreduce_mean(self, pool, handles: Sequence[Sequence[int]],
                       specs: Sequence[jax.ShapeDtypeStruct], *,
                       root: int = 0, tag: str = "avg") -> List[List[Any]]:
        """Mean across devices, bit-identical to the host-mediated path.

        Gather to ``root``, reduce there in ascending device order (the same
        association as the host's ``sum(views) / D``), divide by ``D``, then
        ring-broadcast the mean back into every device's handles.

        With a multi-rack :attr:`topology` installed this dispatches to
        :meth:`hier_allreduce_mean`, whose leader-chain reduction carries
        the identical ascending association — still bit-identical to the
        host-mediated path, with ``O(R)`` cross-rack messages.
        """
        D, L = len(handles), len(specs)
        last: List[List[Any]] = [[None] * L for _ in range(D)]
        if D <= 1:
            return last
        if self._hier_ok(D):
            return self.hier_allreduce_mean(pool, handles, specs, root=root,
                                            tag=tag)
        _ensure_kernels(pool)
        scratch = self.gather(pool, handles, specs, root=root, tag=f"{tag}:gather")
        # accumulate in ASCENDING DEVICE order — device d's operand is its
        # gathered scratch copy, the root's its own buffer — so the
        # association matches the host's sum(views) for ANY root, not just
        # root 0.  Partial sums land only in scratch slots: the root's live
        # buffer is written exactly once, by the final divide, so a
        # mid-collective failure leaves every device's buffer intact (the
        # host-mediated path has the same all-or-nothing property).
        try:
            for j in range(L):
                acc = handles[root][j] if root == 0 else scratch[0][j]
                for d in range(1, D):
                    operand = handles[root][j] if d == root else scratch[d][j]
                    out = pool.exec_kernel(root, ADD_KERNEL,
                                           buffers={"a": acc, "b": operand},
                                           tag=f"{tag}:reduce")
                    if acc == handles[root][j]:  # first add when root == 0:
                        acc = operand            # park the sum in scratch
                    pool.transfer_to_writeback(root, acc, out)
                out = pool.exec_kernel(root, DIV_KERNEL, buffers={"a": acc},
                                       firstprivate={"s": float(D)},
                                       tag=f"{tag}:mean")
                last[root][j] = pool.transfer_to_writeback(root,
                                                           handles[root][j], out)
        finally:
            for hs in scratch.values():
                for h in hs:
                    pool.free(root, h)
        bcast = self.broadcast(pool, handles, specs, root=root, tag=f"{tag}:bcast")
        for d in range(D):
            if d != root:
                last[d] = bcast[d]
        return last

    # -- hierarchical collectives (rack-aware, beyond the flat ring) -----------
    def _hier_chain_reduce(self, pool, handles, specs, wire_nbytes, tag,
                           scratch):
        """Serial-association hierarchical SUM: returns ``(root, total)``.

        Per rack (contiguous ascending blocks — the Topology constructor
        guarantees it): every non-leader member SENDs its buffer to the rack
        leader (the intra-rack gathers of different racks run concurrently);
        each leader then folds ``incoming partial + own buffer + member
        copies`` left-to-right in ascending device order and SENDs the new
        partial to the next rack's leader.  The one cross-rack message per
        rack boundary is what replaces the flat ring's ``(D-1)`` crossings,
        and the fold order makes the total *bitwise* equal to the serial
        left-associated ascending sum ``((h_0 + h_1) + h_2) + …``.

        ``total`` are per-leaf scratch handles on ``root`` (the last rack's
        leader); live buffers are never written.  Every allocated slot is
        appended to ``scratch`` as ``(device, handle)`` — the caller frees.
        """
        L = len(specs)
        topo = self.topology
        wb = (lambda j: None) if wire_nbytes is None \
            else (lambda j: wire_nbytes[j])

        def _alloc(dev, j, kind):
            h = pool.alloc(dev, specs[j].shape, specs[j].dtype,
                           tag=f"{tag}:{kind}")
            scratch.append((dev, h))
            return h

        # 1) intra-rack gather onto each leader (all racks concurrent)
        gathered: Dict[int, List[int]] = {}     # member -> handles at leader
        for rack in topo.racks:
            lead = rack[0]
            for m in rack[1:]:
                gathered[m] = [_alloc(lead, j, "up") for j in range(L)]
                for j in range(L):
                    self.sendrecv(pool, m, handles[m][j],
                                  lead, gathered[m][j], nbytes=wb(j),
                                  tag=f"{tag}:up")
        # 2) fold + chain across leaders in ascending rack order
        carry_dev, carry = None, None
        for rack in topo.racks:
            lead = rack[0]
            incoming = None
            if carry is not None:
                incoming = [_alloc(lead, j, "chain") for j in range(L)]
                for j in range(L):
                    self.sendrecv(pool, carry_dev, carry[j],
                                  lead, incoming[j], nbytes=wb(j),
                                  tag=f"{tag}:chain")
            acc: List[Optional[int]] = [None] * L
            for j in range(L):
                ops = ([] if incoming is None else [incoming[j]])
                ops += [handles[m][j] if m == lead else gathered[m][j]
                        for m in rack]
                a = ops[0]
                for b in ops[1:]:
                    out = pool.exec_kernel(lead, ADD_KERNEL,
                                           buffers={"a": a, "b": b},
                                           tag=f"{tag}:add")
                    if acc[j] is None:
                        # partials park in scratch, never in a live buffer
                        acc[j] = _alloc(lead, j, "acc")
                    pool.transfer_to_writeback(lead, acc[j], out)
                    a = acc[j]
                if acc[j] is None:
                    acc[j] = a   # singleton first rack: its live buffer IS
                                 # the partial (read-only from here on)
            carry_dev, carry = lead, acc
        return carry_dev, carry

    def hier_allreduce(self, pool, handles: Sequence[Sequence[int]],
                       specs: Sequence[jax.ShapeDtypeStruct], *,
                       wire_nbytes: Optional[Sequence[int]] = None,
                       tag: str = "hier") -> List[List[Any]]:
        """Rack-aware in-place sum (the :meth:`ring_allreduce` contract).

        reduce-within-rack → chain-across-rack-leaders → move the total
        into the final leader's live buffer → :meth:`hier_broadcast` it
        back out.  Cross-rack messages: one partial per rack boundary up,
        one result per boundary down — ``2·(R-1)`` of size ``|buf|``
        against the flat ring's ``(D-1)`` per crossing link.  The chain's
        ascending fold makes every device's result bitwise equal to the
        serial ascending sum (see the module docstring).
        """
        D, L = len(handles), len(specs)
        last: List[List[Any]] = [[None] * L for _ in range(D)]
        if D <= 1:
            return last
        _ensure_kernels(pool)
        scratch: List[Any] = []
        try:
            root, total = self._hier_chain_reduce(pool, handles, specs,
                                                  wire_nbytes, tag, scratch)
            for j in range(L):
                out = pool.exec_kernel(root, ID_KERNEL,
                                       buffers={"a": total[j]},
                                       tag=f"{tag}:fin")
                last[root][j] = pool.transfer_to_writeback(
                    root, handles[root][j], out)
            down = self.hier_broadcast(pool, handles, specs, root=root,
                                       tag=f"{tag}:down",
                                       wire_nbytes=wire_nbytes)
            for d in range(D):
                if d != root:
                    last[d] = down[d]
        finally:
            for dev, h in scratch:
                pool.free(dev, h)
        return last

    def hier_allreduce_mean(self, pool, handles: Sequence[Sequence[int]],
                            specs: Sequence[jax.ShapeDtypeStruct], *,
                            root: int = 0,
                            tag: str = "havg") -> List[List[Any]]:
        """Rack-aware mean, bit-identical to flat :meth:`allreduce_mean`
        and to the host-mediated ``sum(views)/D``.

        Same leader chain as :meth:`hier_allreduce` (the identical serial
        association), then the final leader divides by ``D`` — its live
        buffer written exactly once, by that divide, preserving the flat
        path's all-or-nothing property — and :meth:`hier_broadcast`
        distributes the mean.  ``root`` does not change the values (every
        device receives identical bits), so the reduction is anchored at
        the last rack's leader regardless.
        """
        D, L = len(handles), len(specs)
        last: List[List[Any]] = [[None] * L for _ in range(D)]
        if D <= 1:
            return last
        _ensure_kernels(pool)
        scratch: List[Any] = []
        try:
            anchor, total = self._hier_chain_reduce(pool, handles, specs,
                                                    None, tag, scratch)
            for j in range(L):
                out = pool.exec_kernel(anchor, DIV_KERNEL,
                                       buffers={"a": total[j]},
                                       firstprivate={"s": float(D)},
                                       tag=f"{tag}:mean")
                last[anchor][j] = pool.transfer_to_writeback(
                    anchor, handles[anchor][j], out)
            down = self.hier_broadcast(pool, handles, specs, root=anchor,
                                       tag=f"{tag}:bcast")
            for d in range(D):
                if d != anchor:
                    last[d] = down[d]
        finally:
            for dev, h in scratch:
                pool.free(dev, h)
        return last

    def hier_broadcast(self, pool, handles: Sequence[Sequence[int]],
                       specs: Sequence[jax.ShapeDtypeStruct], *,
                       root: int = 0, tag: str = "hbcast",
                       wire_nbytes: Optional[Sequence[int]] = None
                       ) -> List[List[Any]]:
        """Rack-aware broadcast of ``root``'s buffer into every handle.

        The root's rack is served first; a leader chain carries the buffer
        across the other racks (one cross-rack message per boundary), and
        within each rack an intra-rack chain forwards it member to member —
        every hop stream-ordered after the previous hop's RECV, so the
        chains pipeline per leaf exactly like the flat ring broadcast.
        """
        D, L = len(handles), len(specs)
        last: List[List[Any]] = [[None] * L for _ in range(D)]
        topo = self.topology
        wb = (lambda j: None) if wire_nbytes is None \
            else (lambda j: wire_nbytes[j])
        r0 = topo.rack_of(root)
        order = [r0] + [r for r in range(topo.n_racks) if r != r0]
        entry = {r0: root}
        prev = root
        for r in order[1:]:
            lead = topo.leader(r)
            for j in range(L):
                last[lead][j] = self.sendrecv(pool, prev, handles[prev][j],
                                              lead, handles[lead][j],
                                              nbytes=wb(j), tag=f"{tag}:x")
            entry[r] = lead
            prev = lead
        for r, rack in enumerate(topo.racks):
            chain = [entry[r]] + [m for m in rack if m != entry[r]]
            for p, c in zip(chain, chain[1:]):
                for j in range(L):
                    last[c][j] = self.sendrecv(pool, p, handles[p][j],
                                               c, handles[c][j],
                                               nbytes=wb(j), tag=f"{tag}:in")
        return last

    def quantize_int8(self, pool, handles: Sequence[Sequence[int]],
                      specs: Sequence[jax.ShapeDtypeStruct], *,
                      block: int = 256, tag: str = "q8") -> List[int]:
        """Apply the wire's block-int8 round trip to every device's buffer
        in place and return the per-leaf compressed message sizes, for use
        as ``wire_nbytes`` in a following collective.

        The sizes are derived from :func:`~repro.core.compression.
        compressed_nbytes` of the actual compressed spec (via
        ``jax.eval_shape``), so they track ``block`` — a non-default block
        cannot silently mis-account the wire credits against the 256-value
        layout.
        """
        from . import compression as comp

        _ensure_kernels(pool)
        block = int(block)
        for d in range(len(handles)):
            for j in range(len(specs)):
                out = pool.exec_kernel(d, Q8_KERNEL,
                                       buffers={"a": handles[d][j]},
                                       firstprivate={"block": block},
                                       static_argnames=("block",),
                                       tag=f"{tag}:quantize")
                pool.transfer_to_writeback(d, handles[d][j], out)
        return [comp.compressed_nbytes(
            jax.eval_shape(lambda x: comp.compress(x, block), s))
            for s in specs]


class HostFunnelTransport(Transport):
    """Paper-faithful topology: the host is the only wire.

    A device→device copy is TRANSFER_FROM(src) + TRANSFER_TO(dst): the bytes
    cross the host NIC twice and are accounted (and timed) there — this is
    the measured source of degradation in the paper's §5.6 and the baseline
    the peer transport is judged against.
    """

    kind = "host-funnel"

    def sendrecv(self, pool, src: int, src_handle: int,
                 dst: int, dst_handle: int, *,
                 nbytes: Optional[int] = None, tag: str = ""):
        value = pool.transfer_from(src, src_handle, tag=tag)
        return pool.transfer_to(dst, dst_handle, value, tag=tag)


class PeerTransport(Transport):
    """Direct device↔device fabric over SEND/RECV stream commands.

    Byte accounting is always per directed link, never against the host
    funnel.  Message *timing* comes from the pool's ``cost.peer_link``
    (``RuntimeConfig.peer_link`` installs it at runtime construction; set
    it yourself on a bare pool) — a transfer never re-times a shared cost
    model as a side effect.  ``link`` documents the fabric this transport
    was built for; owners install it explicitly.

    ``retries > 0`` makes the fabric *fault tolerant*: each ``sendrecv``
    waits for its RECV, and an injected :class:`~repro.core.device.
    DeviceFailure` re-sends the message, falling back to the host funnel
    (fetch + re-send — always available) once the peer wire has failed
    ``retries`` times.  Re-sends are paced by exponential backoff with
    deterministic, seeded jitter (``backoff_base_s``·2^(attempt-1), capped
    at ``backoff_cap_s``, scaled by a seeded draw in [0.5, 1)) — the same
    (seed, failure schedule) replays the same delays bit-for-bit.

    ``op_timeout_s`` bounds how long a ``sendrecv`` waits for its RECV to
    settle: a blown timeout is classified as a straggler fault
    (:class:`~repro.core.device.StragglerTimeout`) and takes the same
    retry → backoff → funnel-fallback path as a loud failure, so a hung
    wire costs one timeout instead of the whole job.  The abandoned
    SEND/RECV pair settles whenever the worker unwedges; whatever it
    stashes is absorbed then.  The delivered value is identical regardless
    of the wire, so collectives stay bit-identical under injection.  The
    default (``retries=0``, no timeout) keeps the zero-overhead
    fire-and-forget behavior.
    """

    kind = "peer"

    def __init__(self, link: Optional[LinkModel] = None,
                 retries: int = 0, *, op_timeout_s: Optional[float] = None,
                 backoff_base_s: float = 1e-3, backoff_cap_s: float = 0.1,
                 seed: int = 0, topology=None) -> None:
        self.link = link
        # a Topology makes the fabric hierarchical: per-pair edge pricing
        # (intra vs inter rack), compression-aware edge routing, and
        # rack-aware collective dispatch (see Transport._hier_ok)
        self.topology = topology
        self.retries = retries
        self.op_timeout_s = op_timeout_s
        self.backoff_base_s = backoff_base_s
        self.backoff_cap_s = backoff_cap_s
        self._rng = _np.random.default_rng((seed, 0xB0FF))
        self._rng_lock = _threading.Lock()
        self.fallbacks = 0      # observability: edges rerouted to the funnel
        self.timeouts = 0       # ops that blew op_timeout_s (stragglers)
        self.backoffs = 0       # backoff sleeps taken
        self.backoff_s = 0.0    # total seconds spent backing off

    def _backoff(self, attempt: int) -> None:
        """Sleep the attempt's backoff: exponential, capped, seeded jitter."""
        with self._rng_lock:
            u = float(self._rng.random())
        delay = min(self.backoff_cap_s,
                    self.backoff_base_s * (2.0 ** (attempt - 1)))
        delay *= 0.5 + 0.5 * u
        self.backoffs += 1
        self.backoff_s += delay
        _time.sleep(delay)

    def sendrecv(self, pool, src: int, src_handle: int,
                 dst: int, dst_handle: int, *,
                 nbytes: Optional[int] = None, tag: str = ""):
        if self.retries <= 0 and self.op_timeout_s is None:
            return pool.peer_copy(src, src_handle, dst, dst_handle,
                                  nbytes=nbytes, tag=tag)
        from .device import DeviceFailure, StragglerTimeout
        attempt = 0
        while True:
            fut = pool.peer_copy(src, src_handle, dst, dst_handle,
                                 nbytes=nbytes, tag=tag)
            try:
                err = fut.exception(timeout=self.op_timeout_s)
            except _cf.TimeoutError:
                # straggler: the RECV has not settled within the op budget.
                # The pair keeps running on its workers; when it finally
                # settles, absorb whatever it stashed so no innocent sync op
                # inherits the abandoned copy's failure.
                self.timeouts += 1
                fut.add_done_callback(
                    lambda f: pool.absorb_failures()
                    if isinstance(f.exception(), DeviceFailure) else None)
                err = StragglerTimeout(
                    f"SEND/RECV {src}->{dst} exceeded the "
                    f"{self.op_timeout_s}s transport op timeout",
                    op="RECV", device=dst)
            if err is None:
                return fut
            if not isinstance(err, DeviceFailure):
                raise err
            # the SEND/RECV stashed async errors on both endpoints; this
            # failure is being handled here, so absorb them
            pool.absorb_failures()
            attempt += 1
            if attempt > self.retries:
                # peer wire is persistently down for this edge: reroute
                # through the host funnel (fetch + re-send), which delivers
                # the same bytes over the paper-faithful wire
                self.fallbacks += 1
                try:
                    value = pool.transfer_from(src, src_handle,
                                               tag=f"{tag}:fallback")
                except DeviceFailure:
                    # a timed-out SEND that fails when it finally runs
                    # stashes its failure on src, and the fetch queued
                    # behind it can surface that failure before the pair's
                    # absorb callback clears it; surfacing cleared it, so
                    # fetch again
                    value = pool.transfer_from(src, src_handle,
                                               tag=f"{tag}:fallback")
                return pool.transfer_to(dst, dst_handle, value,
                                        tag=f"{tag}:fallback")
            self._backoff(attempt)

    def edge_time(self, cost, src: int, dst: int, nbytes: int) -> float:
        """One message on the directed (src, dst) peer link — no funnel hop.

        With a :attr:`topology` covering both endpoints the price is
        per-pair (intra-rack vs spine, plus any per-pair override) and
        already reflects the cheaper of the raw and block-int8 wires —
        the same number :meth:`edge_route` routes by, so placement and
        routing can never disagree on what an edge costs.
        """
        if self.topology is not None and self.topology.covers(src, dst):
            return self.topology.edge_seconds(src, dst, nbytes)[0]
        plink = self.link or cost.peer_link or cost.link
        return plink.time(nbytes, 1)

    def edge_route(self, cost, src: int, dst: int, nbytes: int):
        """Per-pair price and wire choice: ``"peer+int8"`` where the link's
        bandwidth-delay arithmetic says compression wins (thin spine links,
        big messages), plain ``"peer"`` everywhere else."""
        if self.topology is not None and self.topology.covers(src, dst):
            seconds, compressed = self.topology.edge_seconds(src, dst, nbytes)
            return seconds, ("peer+int8" if compressed else "peer")
        return self.edge_time(cost, src, dst, nbytes), "peer"
