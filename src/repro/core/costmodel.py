"""Interconnect cost models and transfer accounting.

The paper evaluates on "a humble Gbit Ethernet network" and finds that the
compute/communication ratio decides whether offload pays.  We make that
tradeoff a first-class, queryable object: every host↔device transfer in the
offload runtime is logged against a :class:`LinkModel`, so benchmarks can
reproduce the paper's speedup curves (Figs 2–9) and the scheduler can make
comm-aware placement decisions; the same constants drive the roofline terms.

Two makespan models coexist:

* ``makespan(overlap=False)`` — paper-faithful: all communication serialized
  at the host NIC, then compute (the OpenMP host-funnel restriction).
* ``makespan(overlap=True)`` — an **event timeline**: recorded events are
  list-scheduled onto a host-TX lane, a host-RX lane (Gbit Ethernet is full
  duplex) and one compute lane per device, so host→device transfers for
  strip *k+1* genuinely overlap device *k*'s compute, exactly like the
  pipelined per-device command queues in :mod:`repro.core.device`.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple


@dataclass(frozen=True)
class LinkModel:
    """alpha-beta model: time(n bytes) = latency + n / bandwidth."""

    name: str
    bandwidth_Bps: float  # bytes per second
    latency_s: float

    def time(self, nbytes: int, n_messages: int = 1) -> float:
        return self.latency_s * n_messages + nbytes / self.bandwidth_Bps


# The paper's cluster: Gbit Ethernet (§5.2). ~125 MB/s peak, ~50us MPI latency.
PAPER_ETHERNET = LinkModel("gbit-ethernet", 125e6, 50e-6)

# The documented cold-start compute estimate: what a cost-driven policy
# charges for a kernel with no observations AND no calibration-profile seed
# (1 ms — the historical HeftPlacement default_task_s).  Every time the
# fallback ladder bottoms out here the model counts a cold prediction
# (``summary()["cold_predictions"]``) so a run placed blind is visible.
DEFAULT_KERNEL_TIME_S = 1e-3
# TPU v5e targets (system constants used throughout §Roofline).
TPU_ICI = LinkModel("tpu-v5e-ici", 50e9, 1e-6)        # ~50 GB/s per link
TPU_DCN = LinkModel("tpu-dcn", 25e9, 10e-6)           # cross-pod data-center network
TPU_PCIE_HOST = LinkModel("tpu-host-pcie", 16e9, 5e-6)

# Chip-level roofline constants (TPU v5e, per chip).
PEAK_FLOPS_BF16 = 197e12        # 197 TFLOP/s bf16
HBM_BW_Bps = 819e9              # 819 GB/s
ICI_BW_Bps = TPU_ICI.bandwidth_Bps


@dataclass
class TransferRecord:
    direction: str          # "to" | "from"
    device: int
    nbytes: int
    n_messages: int = 1
    tag: str = ""


@dataclass
class ComputeRecord:
    device: int
    seconds: float          # measured or modeled task compute time
    tag: str = ""
    kernel: str = ""        # registered kernel name (placement estimates)
    # an EXEC's parts, on the host clock (None where not measured):
    # ``seconds`` = dispatch_s (execute until the jitted call returns, on
    # the worker) + sync_s (then until its completion thread saw the
    # kernel finish); before it ran, gate_s (issue until its inputs
    # settled) and queue_s (then until the worker took it);
    # zero_fills: unwritten slots its argument resolution zero-filled;
    # work: what the task declared it computes (``TaskNode.work``), None
    # where it declared nothing; inflight: the device's earlier EXECs
    # dispatched but not completed when its dispatch began
    dispatch_s: Optional[float] = None
    sync_s: Optional[float] = None
    gate_s: Optional[float] = None
    queue_s: Optional[float] = None
    zero_fills: Optional[int] = None
    work: Optional[Dict[str, float]] = None
    inflight: Optional[int] = None


@dataclass
class PlacementRecord:
    """One placement decision a cost-driven policy predicted.

    ``predicted_s`` is the policy's earliest-finish-time estimate at decision
    time; :meth:`CostModel.placement_report` joins it with the compute
    records that later ran under ``task`` (the region tag), so benchmarks can
    quantify how well the model's timings anticipated reality.
    """

    task: str               # region tag the prediction was made for
    device: int
    predicted_s: float      # modeled finish time (policy clock)
    policy: str = ""


@dataclass
class PeerRecord:
    """One device↔device transfer on a peer link (never the host NIC)."""

    src: int
    dst: int
    nbytes: int
    n_messages: int = 1
    tag: str = ""


@dataclass
class Event:
    """One entry of the recorded event stream (issue order preserved)."""

    kind: str               # "xfer" | "compute" | "peer"
    device: int             # peer: the destination device
    tag: str = ""
    direction: str = ""     # xfer only: "to" | "from"
    nbytes: int = 0
    n_messages: int = 1
    seconds: float = 0.0    # compute only
    src: int = -1           # peer only: the source device


@dataclass
class TimelineSpan:
    """One scheduled event on the modeled timeline."""

    start: float
    end: float
    lane: str               # "tx" | "rx" | "dev<k>" | "p<src>>dst>"
    event: Event


def _tag_matches(tag: str, prefix: str) -> bool:
    return tag == prefix or tag.startswith(prefix + ":") or tag.startswith(prefix + "[")


class CostModel:
    """Accounts transfers/compute per device and models end-to-end makespan.

    ``makespan()`` reflects the paper's execution model: the host serializes
    its own sends/receives over a single NIC (the host funnel — the OpenMP
    restriction that all communication is host↔device), while device compute
    runs concurrently across devices.
    """

    def __init__(self, link: LinkModel = PAPER_ETHERNET,
                 peer_link: Optional[LinkModel] = None,
                 topology=None) -> None:
        self.link = link
        # the device↔device link (None = same fabric as the host link); the
        # transport layer records SEND/RECV traffic against this model so
        # peer collectives are *timed* on their own lanes, never credited
        # against the host NIC
        self.peer_link = peer_link
        # optional repro.core.topology.Topology: when set, each directed
        # peer pair is timed on ITS link (intra-rack vs spine, per-pair
        # overrides) instead of the one uniform peer_link, and cross-rack
        # traffic is accounted separately (bytes_peer_cross_rack)
        self.topology = topology
        # optional repro.core.calibrate.CalibrationProfile installed by
        # load_profile(): seeds kernel_time (until live observations land)
        # and replaced link/peer_link/topology-tier models with measured fits
        self.profile = None
        # how many kernel_time estimates bottomed out at the documented
        # default — no observation, no calibration seed (blind placements)
        self.cold_predictions = 0
        self.transfers: List[TransferRecord] = []
        self.compute: List[ComputeRecord] = []
        self.adjustments: List[TransferRecord] = []
        self.peers: List[PeerRecord] = []
        self.events: List[Event] = []
        self.placements: List[PlacementRecord] = []
        self._lock = threading.Lock()

    def reset(self) -> None:
        with self._lock:
            self.transfers.clear()
            self.compute.clear()
            self.adjustments.clear()
            self.peers.clear()
            self.events.clear()
            self.placements.clear()
            self.cold_predictions = 0   # the installed profile survives reset

    # -- accounting ---------------------------------------------------------
    def record_transfer(self, direction: str, device: int, nbytes: int,
                        n_messages: int = 1, tag: str = "") -> None:
        with self._lock:
            self.transfers.append(TransferRecord(direction, device, int(nbytes),
                                                 n_messages, tag))
            self.events.append(Event("xfer", device, tag=tag, direction=direction,
                                     nbytes=int(nbytes), n_messages=n_messages))

    def record_compute(self, device: int, seconds: float, tag: str = "",
                       kernel: str = "", *, dispatch_s: Optional[float] = None,
                       sync_s: Optional[float] = None,
                       gate_s: Optional[float] = None,
                       queue_s: Optional[float] = None,
                       zero_fills: Optional[int] = None,
                       work: Optional[Dict[str, float]] = None,
                       inflight: Optional[int] = None) -> None:
        with self._lock:
            self.compute.append(ComputeRecord(device, float(seconds), tag,
                                              kernel, dispatch_s, sync_s,
                                              gate_s, queue_s, zero_fills,
                                              work, inflight))
            self.events.append(Event("compute", device, tag=tag,
                                     seconds=float(seconds)))

    def record_placement(self, task: str, device: int, predicted_s: float,
                         policy: str = "") -> None:
        """Log a cost-driven placement decision (prediction side)."""
        with self._lock:
            self.placements.append(PlacementRecord(task, device,
                                                   float(predicted_s), policy))

    def kernel_time(self, kernel: str, *,
                    default: Optional[float] = None) -> float:
        """Estimated compute seconds for ``kernel`` — never ``None``.

        The fallback ladder (the estimate a cost-driven placement policy
        feeds its earliest-finish-time clock):

        1. mean of the live observations (sharpens as regions retire);
        2. the installed calibration profile's measured seed
           (:meth:`load_profile`);
        3. ``default`` if the caller passed one (a policy's own
           ``default_task_s``), else the documented
           :data:`DEFAULT_KERNEL_TIME_S`.

        Reaching rung 3 is a *cold prediction* — placement ran blind — and
        is counted in ``summary()["cold_predictions"]``.  (Historically this
        method returned ``None`` on zero observations, which silently
        degraded HEFT to insertion order.)
        """
        with self._lock:
            ts = [c.seconds for c in self.compute if c.kernel == kernel]
        if ts:
            return sum(ts) / len(ts)
        if self.profile is not None:
            seed = self.profile.kernel_seed(kernel)
            if seed is not None:
                return seed
        with self._lock:
            self.cold_predictions += 1
        return default if default is not None else DEFAULT_KERNEL_TIME_S

    def kernel_observations(self, kernel: str) -> int:
        """How many retired regions back the :meth:`kernel_time` estimate.

        Straggler detection gates on this: hedging off a one-sample estimate
        (often a JIT-compile spike) would duplicate healthy work.
        """
        with self._lock:
            return sum(1 for c in self.compute if c.kernel == kernel)

    def placement_report(self, *, roofline: bool = False):
        """Predicted-vs-observed accounting for cost-driven placements.

        Joins each :class:`PlacementRecord` with the compute records that ran
        under its region tag.  ``observed_s`` is that region's measured
        compute; ``predicted_s`` is the policy's modeled finish time (a clock
        value, not a duration — compare *orderings* and per-task compute, not
        absolute magnitudes).

        ``roofline=True`` returns ``{"placements": rows, "roofline":
        self.roofline_summary()}`` — the per-task join plus the per-kernel
        predicted-vs-observed roofline (``benchmarks/roofline.py`` renders
        it next to the dry-run table).
        """
        with self._lock:
            placements = list(self.placements)
            compute = list(self.compute)
        report = []
        for p in placements:
            obs = [c for c in compute if _tag_matches(c.tag, p.task)]
            report.append({
                "task": p.task, "policy": p.policy, "device": p.device,
                "predicted_s": p.predicted_s,
                "observed_s": sum(c.seconds for c in obs),
                "observed_device_ok": all(c.device == p.device for c in obs),
            })
        if roofline:
            return {"placements": report, "roofline": self.roofline_summary()}
        return report

    def roofline_summary(self) -> List[Dict[str, object]]:
        """Per-kernel predicted-vs-observed roofline rows.

        For every kernel with live observations and/or a calibration-profile
        entry: the calibrated seed vs the mean observed seconds
        (``model_ratio`` = observed/calibrated, 1.0 = the model nailed it),
        the dry-run FLOPs / bytes-accessed / arithmetic intensity, the
        achieved FLOP/s, and the chip roofline bound at that intensity
        (``min(peak, intensity × HBM bandwidth)`` with the §Roofline
        TPU-v5e-class constants — "memory"-bound left of the ridge point,
        "compute"-bound right of it).
        """
        with self._lock:
            compute = list(self.compute)
        prof_kernels = dict(getattr(self.profile, "kernels", None) or {})
        names = sorted({c.kernel for c in compute if c.kernel}
                       | set(prof_kernels))
        rows: List[Dict[str, object]] = []
        for name in names:
            ts = [c.seconds for c in compute if c.kernel == name]
            observed = sum(ts) / len(ts) if ts else None
            kp = prof_kernels.get(name)
            calibrated = kp.seconds if kp is not None else None
            flops = kp.flops if kp is not None else 0.0
            nbytes = kp.bytes_accessed if kp is not None else 0.0
            intensity = flops / nbytes if nbytes else 0.0
            roof = min(PEAK_FLOPS_BF16, intensity * HBM_BW_Bps) \
                if intensity else None
            achieved = flops / observed if (observed and flops) else None
            rows.append({
                "kernel": name, "observations": len(ts),
                "observed_s": observed, "calibrated_s": calibrated,
                "model_ratio": (observed / calibrated
                                if observed and calibrated else None),
                "flops": flops, "bytes_accessed": nbytes,
                "intensity": intensity,
                "achieved_flops_per_s": achieved,
                "roof_flops_per_s": roof,
                "roofline_fraction": (achieved / roof
                                      if achieved and roof else None),
                "bound": (("compute" if intensity >= PEAK_FLOPS_BF16
                           / HBM_BW_Bps else "memory")
                          if intensity else None),
            })
        return rows

    def load_profile(self, profile, *, n_devices: Optional[int] = None,
                     table_fingerprint: Optional[str] = None) -> None:
        """Seed the model from a measured per-host CalibrationProfile.

        After a staleness check (``profile.check`` — pool shape, topology
        racks, kernel-table fingerprint, schema version must match;
        :class:`~repro.core.calibrate.StaleProfileError` otherwise):

        * :meth:`kernel_time` falls back to the profile's measured kernel
          seconds until live observations land (rung 2 of the ladder);
        * ``link`` (the host funnel) and ``peer_link`` are replaced by the
          measured alpha-beta fits, so ``comm_time`` / ``edge_time`` /
          :meth:`peer_link_for` — and through them HEFT's peer-vs-funnel
          comparison and ``route_edge``'s ``"peer+int8"`` arithmetic — all
          price with observations instead of constants;
        * an installed :class:`~repro.core.topology.Topology` gets its
          intra/inter tier links replaced by the per-tier measurements.
        """
        profile.check(n_devices=n_devices, topology=self.topology,
                      table_fingerprint=table_fingerprint)
        self.profile = profile
        funnel = profile.link_model("funnel")
        if funnel is not None:
            self.link = funnel
        peer = profile.link_model("peer") or profile.link_model("peer:intra")
        if peer is not None:
            self.peer_link = peer
        if self.topology is not None:
            intra = profile.link_model("peer:intra")
            inter = profile.link_model("peer:inter")
            if intra is not None:
                self.topology.intra = intra
            if inter is not None:
                self.topology.inter = inter

    def record_peer(self, src: int, dst: int, nbytes: int,
                    n_messages: int = 1, tag: str = "") -> None:
        """One device→device transfer over the (src, dst) peer link.

        Peer traffic never touches the host NIC: it is excluded from
        ``bytes_moved``/``comm_time`` (the funnel accounting) and scheduled
        on its own per-directed-link lane in the overlap timeline.
        """
        with self._lock:
            self.peers.append(PeerRecord(src, dst, int(nbytes), n_messages, tag))
            self.events.append(Event("peer", dst, tag=tag, nbytes=int(nbytes),
                                     n_messages=n_messages, src=src))

    def record_adjustment(self, direction: str, device: int, nbytes: int,
                          tag: str = "") -> None:
        """Zero-latency byte-accounting correction (no wire messages).

        Used for modeled substitutions — e.g. compression replacing raw
        gradient bytes, or a collective replacing host-funnel fetches.  The
        delta (possibly negative) counts toward ``bytes_moved`` and adds pure
        bandwidth time to ``comm_time``, but never per-message latency and
        never an event on the timeline.
        """
        with self._lock:
            self.adjustments.append(TransferRecord(direction, device,
                                                   int(nbytes), 0, tag))

    def discard_tag(self, prefix: str) -> int:
        """Drop every record whose tag belongs to region ``prefix``.

        Used when a speculative re-dispatch loses: the duplicate's compute
        and transfers must not count toward the makespan.  Returns the number
        of records removed.
        """
        with self._lock:
            before = (len(self.transfers) + len(self.compute)
                      + len(self.adjustments) + len(self.peers)
                      + len(self.events) + len(self.placements))
            self.transfers = [t for t in self.transfers
                              if not _tag_matches(t.tag, prefix)]
            self.compute = [c for c in self.compute
                            if not _tag_matches(c.tag, prefix)]
            self.adjustments = [a for a in self.adjustments
                                if not _tag_matches(a.tag, prefix)]
            self.peers = [p for p in self.peers
                          if not _tag_matches(p.tag, prefix)]
            self.events = [e for e in self.events
                           if not _tag_matches(e.tag, prefix)]
            self.placements = [p for p in self.placements
                               if not _tag_matches(p.task, prefix)]
            return before - (len(self.transfers) + len(self.compute)
                             + len(self.adjustments) + len(self.peers)
                             + len(self.events) + len(self.placements))

    def rename_tag(self, prefix: str, new_prefix: str) -> int:
        """Rewrite every record in region ``prefix`` into ``new_prefix``.

        Used when a speculative re-dispatch WINS: the surviving copy's
        records are canonicalized onto the original task's tag, so the
        modeled work is identical no matter which physical copy raced to the
        result.  Returns the number of records renamed.
        """
        renamed = 0

        def swap(tag: str) -> str:
            nonlocal renamed
            if _tag_matches(tag, prefix):
                renamed += 1
                return new_prefix + tag[len(prefix):]
            return tag

        with self._lock:
            for rec in (*self.transfers, *self.compute, *self.adjustments,
                        *self.peers, *self.events):
                rec.tag = swap(rec.tag)
            for p in self.placements:
                p.task = swap(p.task)
        return renamed

    # -- summaries ------------------------------------------------------------
    def bytes_moved(self, direction: Optional[str] = None) -> int:
        return sum(t.nbytes for t in self.transfers + self.adjustments
                   if direction is None or t.direction == direction)

    def bytes_peer(self) -> int:
        """Bytes moved device→device — real messages, zero host-NIC load."""
        return sum(p.nbytes for p in self.peers)

    def bytes_peer_cross_rack(self) -> int:
        """Peer bytes whose (src, dst) pair crosses a rack boundary under
        the installed topology — the traffic the thin spine links carry,
        and exactly what the hierarchical collectives minimize.  0 when no
        topology is installed (a flat fabric has no boundaries)."""
        if self.topology is None:
            return 0
        return sum(p.nbytes for p in self.peers
                   if self.topology.covers(p.src, p.dst)
                   and self.topology.cross_rack(p.src, p.dst))

    def peer_link_for(self, src: int, dst: int) -> LinkModel:
        """The link model timing one directed (src, dst) peer message:
        the topology's per-pair link when one is installed, else the
        uniform ``peer_link`` (host link as the final fallback)."""
        if self.topology is not None and self.topology.covers(src, dst):
            return self.topology.link_between(src, dst)
        return self.peer_link or self.link

    def comm_time(self) -> float:
        """Total host-funnel communication time (serialized at the host NIC)."""
        wire = sum(self.link.time(t.nbytes, t.n_messages) for t in self.transfers)
        # adjustments are latency-free: pure bandwidth credits/debits
        wire += sum(a.nbytes / self.link.bandwidth_Bps for a in self.adjustments)
        return wire

    def peer_time(self) -> float:
        """Peer-fabric communication time: links carry traffic concurrently,
        each directed (src, dst) link serializes its own messages — the max
        per-link sum is the collective's modeled duration (a D-device ring
        takes one link's worth of time per round, not D).  Each directed
        pair is priced by :meth:`peer_link_for`, so under a topology an
        intra-rack and a spine message cost what *their* links charge."""
        per_link: Dict[Tuple[int, int], float] = {}
        for p in self.peers:
            k = (p.src, p.dst)
            per_link[k] = per_link.get(k, 0.0) \
                + self.peer_link_for(p.src, p.dst).time(p.nbytes,
                                                        p.n_messages)
        return max(per_link.values(), default=0.0)

    def compute_time(self) -> float:
        """Parallel compute time: max over devices of their summed task time."""
        per_dev: Dict[int, float] = {}
        for c in self.compute:
            per_dev[c.device] = per_dev.get(c.device, 0.0) + c.seconds
        return max(per_dev.values(), default=0.0)

    # -- event timeline (pipelined model) -------------------------------------
    def timeline(self) -> List[TimelineSpan]:
        """List-schedule the recorded events onto lanes.

        Lanes: ``tx`` (host→device sends), ``rx`` (device→host receives) —
        the NIC is full duplex — and one compute lane per device, plus one
        lane per *directed peer link* (``p<src>><dst>``).  A peer SEND/RECV
        occupies its link's lane, the source's per-device *send* side and
        the destination's per-device *receive* side: devices are full
        duplex (MPI_Sendrecv-style), so one ring round's D links all run
        concurrently and the round costs one link's time — timed, not
        adjusted onto the host NIC — while successive rounds serialize per
        link and per endpoint side.  A host transfer occupies its NIC lane
        *and* its device's compute lane (the device cannot compute while
        being written/read); compute occupies the device lane and starts
        only after the device's in-flight peer messages (their payloads
        feed it).  Per-lane order follows the recorded issue order, so the
        schedule is exactly what the per-device command queues execute.
        """
        with self._lock:
            events = list(self.events)
        tx_t, rx_t = 0.0, 0.0
        dev_t: Dict[int, float] = {}          # compute / host-xfer occupancy
        dev_tx: Dict[int, float] = {}         # peer send side, full duplex
        dev_rx: Dict[int, float] = {}         # peer receive side
        link_t: Dict[Tuple[int, int], float] = {}
        spans: List[TimelineSpan] = []
        for e in events:
            if e.kind == "xfer":
                nic_t = tx_t if e.direction == "to" else rx_t
                start = max(nic_t, dev_t.get(e.device, 0.0))
                dur = self.link.time(e.nbytes, e.n_messages)
                end = start + dur
                if e.direction == "to":
                    tx_t = end
                else:
                    rx_t = end
                dev_t[e.device] = end
                spans.append(TimelineSpan(start, end,
                                          "tx" if e.direction == "to" else "rx", e))
            elif e.kind == "peer":
                lk = (e.src, e.device)
                start = max(link_t.get(lk, 0.0),
                            dev_t.get(e.src, 0.0), dev_tx.get(e.src, 0.0),
                            dev_t.get(e.device, 0.0), dev_rx.get(e.device, 0.0))
                end = start + self.peer_link_for(e.src, e.device).time(
                    e.nbytes, e.n_messages)
                link_t[lk] = dev_tx[e.src] = dev_rx[e.device] = end
                spans.append(TimelineSpan(start, end, f"p{e.src}>{e.device}", e))
            elif e.kind == "compute":
                start = max(dev_t.get(e.device, 0.0), dev_tx.get(e.device, 0.0),
                            dev_rx.get(e.device, 0.0))
                end = start + e.seconds
                dev_t[e.device] = end
                spans.append(TimelineSpan(start, end, f"dev{e.device}", e))
        return spans

    def makespan(self, overlap: bool = False) -> float:
        """Modeled wall time.

        ``overlap=False`` is the paper-faithful model (comm then compute,
        host-serialized); ``overlap=True`` replays the recorded event stream
        on the lane timeline, so transfers pipelined behind other devices'
        compute are not double-charged.
        """
        if not overlap:
            return self.comm_time() + self.peer_time() + self.compute_time()
        spans = self.timeline()
        if not spans:
            return 0.0
        # adjustments (modeled substitutions: compression, collectives) move
        # bytes on/off the NIC without being schedulable events — apply their
        # net bandwidth time to the lane ends so credited-away transfers do
        # not stay on the critical path
        adj = {"to": 0.0, "from": 0.0}
        for a in self.adjustments:
            adj[a.direction] = adj.get(a.direction, 0.0) \
                + a.nbytes / self.link.bandwidth_Bps
        other_end = max((s.end for s in spans if s.lane not in ("tx", "rx")),
                        default=0.0)
        tx_end = max((s.end for s in spans if s.lane == "tx"), default=0.0)
        rx_end = max((s.end for s in spans if s.lane == "rx"), default=0.0)
        return max(other_end,
                   (tx_end + adj["to"]) if tx_end else 0.0,
                   (rx_end + adj["from"]) if rx_end else 0.0,
                   0.0)

    def summary(self) -> Dict[str, float]:
        return {
            "bytes_to": float(self.bytes_moved("to")),
            "bytes_from": float(self.bytes_moved("from")),
            "bytes_peer": float(self.bytes_peer()),
            "bytes_peer_cross_rack": float(self.bytes_peer_cross_rack()),
            "comm_s": self.comm_time(),
            "peer_s": self.peer_time(),
            "compute_s": self.compute_time(),
            "makespan_s": self.makespan(),
            "makespan_overlap_s": self.makespan(overlap=True),
            "cold_predictions": float(self.cold_predictions),
        }
