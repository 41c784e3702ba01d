"""ClusterRuntime: the paper's system as one deployable object.

Ties together the configuration file → :class:`DevicePool`, the kernel table,
the :class:`TargetExecutor`, and the cost model, and exposes the
data-parallel trainer *fabric* built from target regions:

* ``comm_mode="host-mediated"`` — paper-faithful.  Every gradient shard is
  transferred device → host, reduced on the host, and the update is
  re-broadcast host → device.  This is the only topology OpenMP allows
  ("Two devices cannot communicate with each other directly") and is the
  measured source of degradation in §5.6.
* ``comm_mode="direct"`` — beyond-paper.  Devices exchange gradients with a
  collective (`psum` in the pjit path; modeled ring all-reduce in the pool
  path), eliminating the host funnel — the paper's stated future work
  ("it may also be possible to use MPI collective communications").
* ``compress=True`` — int8 + error feedback on the host/DCN hop.

The pool path here RUNS on CPU (virtual devices) and is used by the BOTS
examples, the fault-tolerance tests and the Figs 2–9 reproductions; the pjit
path for pod-scale LM training lives in ``repro.train`` and shares the same
mode vocabulary so §Perf can compare like for like.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import compression as comp
from .costmodel import CostModel, LinkModel, PAPER_ETHERNET
from .device import DevicePool
from .kernel_table import GLOBAL_KERNEL_TABLE, KernelTable
from .target import MapSpec, Section, TargetExecutor
from .topology import Topology
from .transport import HostFunnelTransport, PeerTransport, Transport


@dataclass
class RuntimeConfig:
    nodes: Sequence[str] = ()                 # paper-style config lines
    n_virtual: Optional[int] = None           # or: N virtual devices
    link: LinkModel = PAPER_ETHERNET
    comm_mode: str = "host-mediated"          # "host-mediated" | "direct"
    # device↔device link for comm_mode="direct" (None: same fabric as `link`
    # — the paper's cluster is one Gbit Ethernet for every pair of nodes)
    peer_link: Optional[LinkModel] = None
    # hierarchical fabric shape (None: flat, every pair priced by peer_link).
    # A multi-rack Topology makes comm_mode="direct" rack-aware end to end:
    # per-pair edge pricing in the cost model and placement policies,
    # hierarchical collectives (reduce-within-rack → leader chain →
    # broadcast-within-rack, still bit-identical to host-mediated), and
    # compression-aware "peer+int8" edge routing where a link favors it.
    # Must describe exactly the pool's device count.
    topology: Optional[Topology] = None
    compress: bool = False
    max_host_threads: int = 16
    # resident-memory budget per device's present table, in bytes (None =
    # unbounded).  When set, making a buffer resident past the budget spills
    # the least-recently-used evictable entry (device-ahead content is
    # reconciled to the host first) and the next binding refetches it —
    # capacity changes traffic, never results.
    device_capacity_bytes: Optional[int] = None
    # comm_mode="direct" fault tolerance: >0 makes the peer transport wait
    # each sendrecv, retry injected SEND/RECV failures this many times, then
    # fall back to the host funnel — values are identical either way.  The
    # default keeps the fire-and-forget peer fabric (no per-message wait).
    transport_retries: int = 0
    # straggler protection (None = off, the zero-overhead default):
    # command_deadline_s bounds every value-producing device command (EXEC,
    # XFER_FROM) end to end — a blown deadline raises StragglerTimeout, a
    # recoverable DeviceFailure; transport_op_timeout_s bounds each peer
    # sendrecv the same way.  Retried sends pace themselves by exponential
    # backoff with deterministic, seeded jitter (base·2^(attempt-1), capped,
    # scaled by a draw in [0.5, 1) from transport_backoff_seed).
    command_deadline_s: Optional[float] = None
    transport_op_timeout_s: Optional[float] = None
    transport_backoff_base_s: float = 1e-3
    transport_backoff_seed: int = 0


class ClusterRuntime:
    def __init__(self, cfg: RuntimeConfig, table: Optional[KernelTable] = None,
                 *, pool: Optional[DevicePool] = None) -> None:
        """``pool`` runs the runtime on a pool built elsewhere — e.g.
        ``DevicePool.from_mesh_slices`` for one device per chip — in place
        of the one ``cfg.nodes`` / ``cfg.n_virtual`` describe.  The pool
        then brings its own kernel table, link, capacity and deadline."""
        if cfg.comm_mode not in ("host-mediated", "direct"):
            raise ValueError(f"unknown comm_mode {cfg.comm_mode!r}")
        self.cfg = cfg
        if pool is not None:
            if cfg.nodes or cfg.n_virtual is not None or table is not None:
                raise ValueError("pass either a pool or cfg.nodes / "
                                 "cfg.n_virtual / table, not both")
            self.pool = pool
        elif cfg.n_virtual is not None:
            self.pool = DevicePool.virtual(
                cfg.n_virtual, table=table, link=cfg.link,
                capacity_bytes=cfg.device_capacity_bytes,
                deadline_s=cfg.command_deadline_s)
        else:
            self.pool = DevicePool.from_config(
                cfg.nodes, table=table, link=cfg.link,
                capacity_bytes=cfg.device_capacity_bytes,
                deadline_s=cfg.command_deadline_s)
        self.ex = TargetExecutor(self.pool, max_host_threads=cfg.max_host_threads)
        if cfg.topology is not None \
                and cfg.topology.n_devices != len(self.pool):
            raise ValueError(
                f"topology describes {cfg.topology.n_devices} devices but "
                f"the pool has {len(self.pool)}")
        # the transport is what "direct" now *means*: a real peer fabric of
        # SEND/RECV stream commands, not a byte-accounting credit.  The
        # topology (when given) rides on both the cost model (per-pair
        # peer timing, cross-rack byte accounting) and the transport
        # (hierarchical collectives, compression-aware edge routing).
        self.pool.cost.peer_link = cfg.peer_link
        self.pool.cost.topology = cfg.topology
        self.transport: Transport = (
            PeerTransport(cfg.peer_link, retries=cfg.transport_retries,
                          op_timeout_s=cfg.transport_op_timeout_s,
                          backoff_base_s=cfg.transport_backoff_base_s,
                          seed=cfg.transport_backoff_seed,
                          topology=cfg.topology)
            if cfg.comm_mode == "direct" else HostFunnelTransport())
        self._ef_residual: Optional[Any] = None
        self._dps: Optional[Dict[str, Any]] = None   # data_parallel_step state

    # convenience passthroughs -------------------------------------------------
    @property
    def cost(self) -> CostModel:
        return self.pool.cost

    def target(self, *a, **kw):
        return self.ex.target(*a, **kw)

    def taskwait(self):
        return self.ex.taskwait()

    def wavefront_offload(self, tasks: Sequence[Any], **kw) -> Dict[str, Any]:
        """Run a task DAG on this runtime's executor (``policy=...`` picks
        placement).  ``peer=True`` uses this runtime's transport when it is
        a peer fabric (``comm_mode="direct"``, so its ``peer_link`` prices
        the edges); under a host-mediated runtime the scheduler's default
        :class:`~repro.core.transport.PeerTransport` carries the DAG edges —
        ``peer=True`` is an explicit request for the peer wire."""
        from .scheduler import wavefront_offload
        if (kw.get("peer") and "transport" not in kw
                and isinstance(self.transport, PeerTransport)):
            kw["transport"] = self.transport
        return wavefront_offload(self.ex, tasks, **kw)

    def calibrate(self, operands: Optional[Dict[str, Any]] = None, *,
                  reps: int = 5, warmup: int = 2,
                  sizes: Sequence[int] = (1 << 14, 1 << 20, 1 << 23),
                  save_dir: Optional[str] = None, load: bool = True):
        """Run the measured-cost calibration pass over this runtime's pool.

        Micro-benchmarks every registered kernel that has example operands
        (``operands[name]`` or a table ``example=``) plus the funnel and
        peer links per direction/tier, builds a per-host
        :class:`~repro.core.calibrate.CalibrationProfile`, optionally
        persists it (``save_dir``), and — unless ``load=False`` — installs
        it on the cost model so placement/routing price with the measured
        numbers.  Returns the profile.
        """
        from .calibrate import calibrate as _calibrate
        profile = _calibrate(self.pool, operands, reps=reps, warmup=warmup,
                             sizes=sizes, topology=self.cfg.topology,
                             save_dir=save_dir)
        if load:
            self.load_calibration(profile)
        return profile

    def load_calibration(self, profile):
        """Install a CalibrationProfile (object or JSON path) on the cost
        model, after validating it against this pool's shape, topology and
        kernel-table fingerprint (raises
        :class:`~repro.core.calibrate.StaleProfileError` on mismatch)."""
        from .calibrate import CalibrationProfile
        if isinstance(profile, (str, bytes, os.PathLike)):
            profile = CalibrationProfile.load(os.fspath(profile))
        self.cost.load_profile(profile, n_devices=len(self.pool),
                               table_fingerprint=self.pool.table.fingerprint())
        return profile

    def memory_report(self) -> Dict[int, Dict[str, int]]:
        """Per-device present-table memory accounting.

        One row per device: resident entry count and bytes against the
        capacity (``capacity_bytes`` is -1 when unbounded), plus the spill
        path's counters — evictions, transparent refetches, and the bytes
        reconciled (device-ahead content fetched at spill) / refetched.
        """
        return {d: self.pool.present[d].stats()
                for d in range(len(self.pool))}

    def shutdown(self) -> None:
        self.pool.stop_all()

    # -- data-parallel step fabric ----------------------------------------------
    def _ensure_dp_params(self, d: int, params: Any, tag: str) -> None:
        """Pin ``params`` resident under the runtime-namespaced entry name.

        The entry is ``_dpg_params`` (not ``"params"``): a user's own
        ``enter_data(d, params=...)`` environment must never be refreshed —
        or, on the shape-change path below, *freed* — by the trainer fabric.
        """
        try:
            self.ex.ensure_resident(d, f"{tag}:params", _dpg_params=params)
        except ValueError:
            # new model/shape under the same name on a long-lived runtime:
            # replace the resident environment (the exit must name the entry
            # that was entered — the kwarg name — not the transfer tag)
            self.ex.exit_data(d, "_dpg_params")
            self.ex.ensure_resident(d, f"{tag}:params", _dpg_params=params)

    def data_parallel_grads(self, kernel: str, params: Any, batches: Sequence[Any],
                            *, tag: str = "dp", resident: bool = True) -> Any:
        """One DP gradient exchange over the pool.

        ``kernel`` is a registered kernel ``(params, batch) -> grads`` pytree.
        Returns the mean gradient, moved according to ``comm_mode``:

        host-mediated: D× (params→dev, grads→host), host reduces — the
        faithful funnel; every gradient crosses one NIC.
        direct: gradients stay on-device (``device_out`` into a resident
        buffer) and the transport ring all-reduces them peer-to-peer
        (``(D-1)·|g|`` per link, concurrent links, SEND/RECV stream
        commands); the host fetches exactly ONE reduced copy.  With
        ``compress=True`` each device applies the wire's block-int8 round
        trip to its local gradients before the ring and the per-link bytes
        are the compressed message sizes (no error feedback on the peer
        fabric — that is a host-funnel feature).

        ``resident=True`` (default) keeps ``params`` in each device's data
        environment across calls: repeated steps over the same parameters
        (gradient accumulation, evaluation sweeps) move zero parameter bytes
        after the first, and an updated pytree re-sends only the leaves that
        changed.  NOTE this deliberately departs from the paper's per-region
        traffic model (∝ 2·D·|params| per step): pass ``resident=False`` for
        the seed-faithful ALLOC/XFER/FREE cycle — that is the baseline
        ``benchmarks/comm_modes.py``'s resident comparison measures against.
        """
        D = len(self.pool)
        assert len(batches) == D, f"need one batch per device, got {len(batches)}"
        if self.cfg.comm_mode == "direct":
            return self._dp_grads_direct(kernel, params, batches, tag=tag,
                                         resident=resident)
        gspec = jax.eval_shape(lambda p: p, params)
        futs = []
        for d in range(D):
            if resident:
                self._ensure_dp_params(d, params, tag)
                maps = MapSpec(to={"batch": batches[d]},
                               present={"params": "_dpg_params"},
                               from_={"grads": gspec})
            else:
                maps = MapSpec(to={"params": params, "batch": batches[d]},
                               from_={"grads": gspec})
            futs.append(self.ex.target(kernel, d, maps, nowait=True, tag=f"{tag}[{d}]"))
        grads = [r["grads"] for r in self.ex.drain(futs)]

        if self.cfg.compress:
            if self._ef_residual is None:
                self._ef_residual = [jax.tree.map(comp.ef_init, g) for g in grads]
            reconstructed = []
            for d, g in enumerate(grads):
                c, self._ef_residual[d] = comp.tree_ef_compress(g, self._ef_residual[d])
                nbytes = sum(comp.compressed_nbytes(x)
                             for x in jax.tree.leaves(
                                 c, is_leaf=lambda y: isinstance(y, comp.Compressed)))
                # compression replaces the raw from-transfer bytes: credit the
                # difference back as a zero-latency adjustment (the messages
                # already happened; only their size changes).  int64 product,
                # as in PresentEntry.nbytes — a >2³¹-element leaf must not
                # wrap the credit
                raw = sum(int(np.prod(l.shape, dtype=np.int64)) * l.dtype.itemsize
                          for l in jax.tree.leaves(g))
                self.cost.record_adjustment("from", d, int(nbytes - raw),
                                            tag=f"{tag}:compress-credit")
                reconstructed.append(comp.tree_decompress(c, g))
            grads = reconstructed

        # host reduce (already fetched above — the funnel is the fetch)
        return jax.tree.map(lambda *g: sum(g) / D, *grads)

    def _dp_grads_direct(self, kernel: str, params: Any, batches: Sequence[Any],
                         *, tag: str, resident: bool) -> Any:
        """The peer path: resident gradients, a real ring, one host copy."""
        D, pool, ex = len(self.pool), self.pool, self.ex
        gspec = jax.eval_shape(lambda p: p, params)
        gleaves = [(l.shape, jnp.dtype(l.dtype)) for l in jax.tree.leaves(
            gspec, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))]
        futs = []
        for d in range(D):
            if resident:
                self._ensure_dp_params(d, params, tag)
            ent = pool.present[d].get("_dpg_grads")
            if ent is not None and [(s.shape, jnp.dtype(s.dtype))
                                    for s in ent.specs] != gleaves:
                ex.exit_data(d, "_dpg_grads")    # param shapes changed
                ent = None
            if ent is None:
                ex.alloc_resident(d, "_dpg_grads", gspec, tag=f"{tag}:grads")
            maps = MapSpec(to={"batch": batches[d]} if resident
                           else {"params": params, "batch": batches[d]},
                           present={"params": "_dpg_params"} if resident else (),
                           device_out={"grads": "_dpg_grads"})
            futs.append(ex.target(kernel, d, maps, nowait=True, tag=f"{tag}[{d}]"))
        ex.drain(futs)
        handles = [pool.present[d].get("_dpg_grads").handles for d in range(D)]
        specs = pool.present[0].get("_dpg_grads").specs
        wire = None
        if self.cfg.compress:
            wire = self.transport.quantize_int8(pool, handles, specs,
                                                tag=f"{tag}:q8")
        wfuts = self.transport.ring_allreduce(pool, handles, specs,
                                              wire_nbytes=wire, tag=f"{tag}:ring")
        for d in range(D):
            with pool.env_locks[d]:
                ent = pool.present[d].get("_dpg_grads")
                if ent is not None:
                    ent.device_ahead = True
                    ent.version += 1
                    ent.write_futs = list(wfuts[d])
        total = ex.fetch_resident(0, "_dpg_grads")   # the one funnel copy
        mean = jax.tree.map(lambda s: s / D, total)
        if not resident:
            for d in range(D):
                ex.exit_data(d, "_dpg_grads")
        return mean

    # -- device-resident optimizer: local AdamW steps, periodic param sync ----
    def data_parallel_step(self, kernel: str, params: Any, batches: Sequence[Any],
                           *, opt_cfg: Optional[Any] = None, sync_every: int = 4,
                           tag: str = "dps") -> Any:
        """One local-update DP step with a device-resident optimizer.

        ``kernel`` is a registered ``(params, batch) -> {"grads": pytree}``
        kernel.  Unlike :meth:`data_parallel_grads` + a host-side update —
        which fetches every device's gradients every step (``D·|g|``
        from-bytes) and re-broadcasts updated parameters — each device here
        keeps ``params`` and the AdamW moments *resident* and applies the
        update on-device (``device_out`` maps: the fused grad+AdamW kernel's
        results are written back into the present entries, nothing crosses
        the wire).  Only every ``sync_every``-th step does the host fetch
        each device's parameters, average them, and push the average back —
        the local-SGD/model-averaging exchange.  Over S steps the funnel's
        from-traffic drops from ``S·D·|g|`` to ``(S/sync_every)·D·|p|``,
        ~``sync_every``× fewer bytes when ``|g| == |p|``.  Under
        ``comm_mode="direct"`` the sync itself leaves the funnel: devices
        average the resident parameters peer-to-peer (see
        :meth:`data_parallel_sync`) and only one copy of the mean reaches
        the host — ``(S/sync_every)·|p|`` from-bytes and zero sync
        to-bytes, with bit-identical parameters to the host-mediated path.

        Returns the host's current parameter view: the freshly averaged
        parameters on sync steps, the last synced value otherwise.  State
        (resident buffers, step counter) lives on the runtime; the first
        call initializes it from ``params`` and later calls ignore the
        argument.  Hyperparameters come from ``opt_cfg`` (an
        :class:`~repro.optim.adamw.AdamWConfig`, default settings if None)
        and travel as firstprivate scalars.
        """
        from ..optim.adamw import AdamWConfig, adamw_update

        D = len(self.pool)
        assert len(batches) == D, f"need one batch per device, got {len(batches)}"
        if sync_every < 1:
            raise ValueError(f"sync_every must be >= 1, got {sync_every}")
        st = self._dps
        if st is None or st["kernel"] != kernel:
            if st is not None:      # switching kernels: release the previous
                                    # resident state so nothing leaks and a
                                    # new param shape re-initializes cleanly
                for d in range(D):
                    self.ex.exit_data(d, "_dps_params", "_dps_mu",
                                      "_dps_nu", "_dps_count")
            cfg = opt_cfg or AdamWConfig()
            step_kernel = f"__dps_{kernel}"
            if step_kernel not in self.pool.table:
                gfn = self.pool.table.lookup(self.pool.table.index_of(kernel)).fn

                def fused(params, batch, mu, nu, count, lr, b1, b2, eps,
                          weight_decay, clip_norm):
                    grads = gfn(params, batch)["grads"]
                    return adamw_update(params, grads, mu, nu, count, lr=lr,
                                        b1=b1, b2=b2, eps=eps,
                                        weight_decay=weight_decay,
                                        clip_norm=clip_norm)

                self.pool.table.register(step_kernel, fused)
            moments = jax.tree.map(
                lambda p: jnp.zeros(jnp.shape(p), jnp.float32), params)
            # "_dps_"-namespaced entries (single underscore: a double-underscore
            # kwarg inside a class body would be name-mangled by Python): a user's own "params" data
            # environment (e.g. data_parallel_grads) must not collide with
            # the optimizer's resident state
            for d in range(D):
                self.ex.ensure_resident(d, f"{tag}:init", _dps_params=params,
                                        _dps_mu=moments, _dps_nu=moments,
                                        _dps_count=jnp.zeros((), jnp.float32))
            st = self._dps = {"kernel": kernel, "step_kernel": step_kernel,
                              "cfg": cfg, "step": 0, "host_params": params}
        if opt_cfg is not None:     # per-call hyperparameters are honored
            st["cfg"] = opt_cfg
        cfg = st["cfg"]
        st["step"] += 1
        lr = cfg.lr(st["step"]) if callable(cfg.lr) else cfg.lr
        fp = {"lr": float(lr), "b1": cfg.b1, "b2": cfg.b2, "eps": cfg.eps,
              "weight_decay": cfg.weight_decay, "clip_norm": cfg.clip_norm}
        alias = {"params": "_dps_params", "mu": "_dps_mu",
                 "nu": "_dps_nu", "count": "_dps_count"}
        futs = [self.ex.target(
            st["step_kernel"], d,
            MapSpec(to={"batch": batches[d]}, present=alias, device_out=alias,
                    firstprivate=fp),
            nowait=True, tag=f"{tag}[{d}]") for d in range(D)]
        try:
            self.ex.drain(futs)
        except BaseException:
            # a partial failure leaves devices at divergent step counts; a
            # later sync would silently average divergent parameters.  Poison
            # the state so the next call re-initializes (releasing the old
            # entries) from its ``params`` argument instead.
            st["kernel"] = None
            raise
        if st["step"] % sync_every == 0:
            self.data_parallel_sync(tag)
        return st["host_params"]

    def data_parallel_sync(self, tag: str = "dps") -> Any:
        """Force a parameter sync now; returns the averaged parameters.

        host-mediated: fetch every device's parameters (``D·|p|`` funnel
        from-bytes), average on the host, push the mean back (``D·|p|``
        to-bytes) — the paper's only legal topology.
        direct: the transport averages *in the stream* —
        gather → reduce-at-root → ring broadcast, all SEND/RECV peer
        messages — and the host fetches ONE copy of the mean for its own
        view (``|p|`` from-bytes, zero to-bytes).  The root reduces in
        ascending device order, the same association as the host's
        ``sum(views)/D``, so both modes produce bit-identical parameters.
        """
        st = self._dps
        if st is None:
            raise RuntimeError("data_parallel_step has not run yet")
        D, pool = len(self.pool), self.pool
        if self.cfg.comm_mode == "direct" and D > 1:
            handles = [pool.present[d].get("_dps_params").handles
                       for d in range(D)]
            specs = pool.present[0].get("_dps_params").specs
            wfuts = self.transport.allreduce_mean(pool, handles, specs, root=0,
                                                  tag=f"{tag}:sync")
            for d in range(D):
                with pool.env_locks[d]:
                    ent = pool.present[d].get("_dps_params")
                    if ent is not None:
                        ent.device_ahead = True
                        ent.version += 1
                        ent.write_futs = list(wfuts[d])
            mean = self.ex.fetch_resident(0, "_dps_params")
        else:
            views = [self.ex.fetch_resident(d, "_dps_params") for d in range(D)]
            mean = jax.tree.map(lambda *p: sum(p) / D, *views)
            for d in range(D):
                self.ex.ensure_resident(d, f"{tag}:sync", _dps_params=mean)
        st["host_params"] = mean
        return mean

    def speedup_report(self, serial_seconds: float) -> Dict[str, float]:
        """Paper-style speedup vs a single machine, under the link model."""
        s = self.cost.summary()
        return {
            **s,
            "serial_s": serial_seconds,
            "speedup": serial_seconds / s["makespan_s"] if s["makespan_s"] else float("inf"),
            "speedup_overlap": (serial_seconds / s["makespan_overlap_s"]
                                if s["makespan_overlap_s"] else float("inf")),
        }
