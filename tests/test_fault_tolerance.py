"""Fault-tolerant elastic TaskGraph execution — the seeded chaos suite.

Acceptance properties of the failure-aware scheduler:

* under seeded chaos (``FlakyDevice`` at p ∈ {0.05, 0.2}, every eligible
  op) random DAGs and the sparselu factorization finish BIT-identical to
  the fault-free run, for all three placement policies, host and peer
  modes alike — recovery moves work and bytes, never values;
* the health registry's blacklist never exceeds the injected failure
  count (no device is condemned without an observed fault);
* a persistently failed peer edge reroutes through the host funnel, both
  at the graph level (``run_graph`` recovery) and at the transport level
  (``PeerTransport(retries=...)`` fallback);
* elastic rescale mid-job: a shrink drains departing residency through
  the spill path (device-ahead updates survive, relocated to the
  least-loaded survivor), a grow is placeable at the next wave;
* ``with_retry`` dispatches through the ``nowait`` stream path and
  absorbs the failures it handles — they never resurface at an innocent
  region's sync point;
* a ``FlakyDevice(p=0.0)`` wrap is transparent: identical results,
  identical traffic, zero failures (the fault-free hot path is intact).
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))

from repro.core import (ClusterRuntime, DagTask, DevicePool, HealthRegistry,
                        KernelTable, MapSpec, PeerTransport, RuntimeConfig,
                        TargetExecutor, TaskGraph, TaskNode, run_graph)
from repro.ft import (FAULT_OPS, DeviceFailure, FlakyDevice, inject_flaky,
                      rescale_pool, with_retry)

POLICIES = ("round-robin", "locality", "heft")


# ---------------------------------------------------------------------------
# fixtures: a small diamond, random DAGs, sparselu
# ---------------------------------------------------------------------------
def _table():
    table = KernelTable()
    table.register("src", lambda s: {"out": s * jnp.ones((4, 4), jnp.float32)})
    table.register("combine", lambda x: {"out": x @ x * 1e-2 + 1.0})
    table.register("combine2", lambda x, y: {"out": x @ x * 1e-2 + y})
    return table


def _diamond(B=4):
    """a → {b, c} → d with deps used opaquely (host- and peer-routable)."""
    sds = jax.ShapeDtypeStruct((B, B), jnp.float32)
    return TaskGraph([
        TaskNode("a", "src", (),
                 lambda dv: MapSpec(to={"s": jnp.float32(3)}, from_={"out": sds})),
        TaskNode("b", "combine", ("a",),
                 lambda dv: MapSpec(to={"x": dv["a"]}, from_={"out": sds})),
        TaskNode("c", "combine", ("a",),
                 lambda dv: MapSpec(to={"x": dv["a"]}, from_={"out": sds})),
        TaskNode("d", "combine2", ("b", "c"),
                 lambda dv: MapSpec(to={"x": dv["b"], "y": dv["c"]},
                                    from_={"out": sds})),
    ])


def _random_tasks(seed, n_tasks, B=4):
    rng = np.random.default_rng(seed)
    sds = jax.ShapeDtypeStruct((B, B), jnp.float32)
    init = jnp.asarray(rng.standard_normal((B, B)), jnp.float32)
    tasks = []
    for i in range(n_tasks):
        n_deps = int(rng.integers(0, min(i, 2) + 1))
        deps = tuple(f"t{j}" for j in
                     rng.choice(i, size=n_deps, replace=False)) if i else ()
        tasks.append(DagTask(
            f"t{i}", "combine", deps,
            (lambda deps=deps, init=init: lambda dv: MapSpec(
                to=({"x": next(iter(dv.values()))} if dv else {"x": init}),
                from_={"out": sds}))()))
    return tasks


def _run_chaos(graph, table, *, policy, peer, p, seed, ops, n_dev=3,
               max_retries=30):
    """One chaos run: fresh pool, injected faults, results + fault counts.

    ``max_retries`` is per node and ALSO counts failed recovery sub-steps
    (a replay whose own fetch faults, a re-propagation whose send faults),
    so heavy chaos (p=0.2 over all five ops) needs more headroom than the
    runtime's default of 8.
    """
    pool = DevicePool.virtual(n_dev, table=table)
    ex = TargetExecutor(pool)
    if p > 0:
        inject_flaky(pool, p=p, seed=seed, ops=ops)
    res = run_graph(ex, graph, policy=policy, peer=peer,
                    max_retries=max_retries)
    injected = sum(getattr(d, "failures", 0) for d in pool.devices)
    return ({k: np.asarray(v) for k, v in res.items()}, injected,
            set(pool.health.blacklist), pool)


@pytest.fixture(scope="module")
def sparselu():
    from bots_sparselu import _build_dag, _make_table, _matrix
    K, B = 4, 32
    mat = _matrix(K, B)
    return _make_table(K), TaskGraph.from_tasks(_build_dag(mat, K, B))


# ---------------------------------------------------------------------------
# seeded chaos: bit-identical under injection (tentpole acceptance)
# ---------------------------------------------------------------------------
@settings(max_examples=3, deadline=None)
@given(st.integers(0, 10_000), st.integers(5, 9))
def test_chaos_random_dags_bit_identical(seed, n_tasks):
    """Random DAGs under EXEC+SEND+RECV chaos: every policy, both modes,
    p ∈ {0.05, 0.2} — bitwise equal to the fault-free reference, and the
    blacklist never exceeds the injected failure count."""
    table = _table()
    graph = TaskGraph.from_tasks(_random_tasks(seed, n_tasks))
    ref, _, _, _ = _run_chaos(graph, table, policy="round-robin", peer=False,
                              p=0.0, seed=0, ops=())
    for peer in (False, True):
        ops = ("EXEC", "SEND", "RECV") if peer else ("EXEC",)
        for policy in POLICIES:
            for p in (0.05, 0.2):
                vals, injected, blacklist, _ = _run_chaos(
                    graph, table, policy=policy, peer=peer,
                    p=p, seed=seed, ops=ops)
                for k in ref:
                    assert np.array_equal(ref[k], vals[k]), \
                        (policy, peer, p, k)
                assert len(blacklist) <= injected, (policy, peer, p)


def test_chaos_sparselu_bit_identical(sparselu):
    """The sparselu factorization under full five-op chaos at p=0.2:
    all three policies recover to the bitwise fault-free answer."""
    table, graph = sparselu
    ref, _, _, _ = _run_chaos(graph, table, policy="round-robin", peer=True,
                              p=0.0, seed=0, ops=(), n_dev=4)
    for policy in POLICIES:
        vals, injected, blacklist, _ = _run_chaos(
            graph, table, policy=policy, peer=True,
            p=0.2, seed=1234, ops=FAULT_OPS, n_dev=4)
        assert injected > 0           # p=0.2 over hundreds of commands
        assert len(blacklist) <= injected
        for k in ref:
            assert np.array_equal(ref[k], vals[k]), (policy, k)


def test_chaos_xfer_only_recovered():
    """Host-wire faults (XFER_TO/XFER_FROM) heal from host views in place."""
    table = _table()
    graph = _diamond()
    ref, _, _, _ = _run_chaos(graph, table, policy="round-robin", peer=False,
                              p=0.0, seed=0, ops=())
    for peer in (False, True):
        vals, _, _, _ = _run_chaos(graph, table, policy="locality", peer=peer,
                                   p=0.2, seed=77,
                                   ops=("XFER_TO", "XFER_FROM"))
        for k in ref:
            assert np.array_equal(ref[k], vals[k]), (peer, k)


def test_flaky_p0_is_transparent():
    """p=0.0 wrap: identical results AND identical traffic — the fault-free
    hot path does not pay for the recovery machinery."""
    table = _table()
    graph = _diamond()

    def run(p):
        pool = DevicePool.virtual(3, table=table)
        ex = TargetExecutor(pool)
        inject_flaky(pool, p=p, seed=9, ops=("EXEC", "SEND", "RECV"))
        res = run_graph(ex, graph, policy="heft", peer=True)
        stats = pool.cost.summary()
        return ({k: np.asarray(v) for k, v in res.items()}, stats,
                sum(d.failures for d in pool.devices), pool)

    ref, ref_stats, _, _ = run(0.0)
    vals, stats, failures, pool = run(0.0)
    assert failures == 0 and not pool.health.blacklist
    for k in ref:
        assert np.array_equal(ref[k], vals[k]), k
    for key in ("bytes_to", "bytes_from", "bytes_peer"):
        assert stats[key] == ref_stats[key], key


# ---------------------------------------------------------------------------
# failed peer edges fall back to the funnel (satellite 2)
# ---------------------------------------------------------------------------
def test_dead_peer_wire_reroutes_through_funnel():
    """SEND always fails: every cross-device edge reroutes through the host
    funnel — the graph still finishes bit-identical, with strictly more
    host-wire traffic than the healthy peer run."""
    table = _table()
    graph = _diamond()
    ref, _, _, healthy_pool = _run_chaos(graph, table, policy="round-robin",
                                         peer=True, p=0.0, seed=0, ops=())
    healthy_host = healthy_pool.cost.summary()["bytes_to"] \
        + healthy_pool.cost.summary()["bytes_from"]
    vals, injected, _, pool = _run_chaos(graph, table, policy="round-robin",
                                         peer=True, p=1.0, seed=3,
                                         ops=("SEND",))
    assert injected > 0
    for k in ref:
        assert np.array_equal(ref[k], vals[k]), k
    stats = pool.cost.summary()
    assert stats["bytes_to"] + stats["bytes_from"] > healthy_host


def test_peer_transport_retries_then_falls_back():
    """PeerTransport(retries=N) re-sends a failed message and reroutes via
    fetch+re-send once the wire has failed N+1 times — same delivered bytes."""
    table = _table()
    pool = DevicePool.virtual(2, table=table)
    inject_flaky(pool, p=1.0, seed=1, ops=("SEND",))
    tr = PeerTransport(retries=2)
    h0 = pool.alloc(0, (8,), jnp.float32, tag="src")
    pool.transfer_to(0, h0, jnp.arange(8, dtype=jnp.float32))
    h1 = pool.alloc(1, (8,), jnp.float32, tag="dst")
    pool.transfer_to(1, h1, jnp.zeros((8,), jnp.float32))
    fut = tr.sendrecv(pool, 0, h0, 1, h1, tag="edge")
    if fut is not None and hasattr(fut, "result"):
        fut.result()
    got = pool.transfer_from(1, h1, tag="chk")
    assert tr.fallbacks == 1
    assert pool.devices[0].failures == 3          # initial + 2 retries
    assert np.array_equal(np.asarray(got), np.arange(8, dtype=np.float32))


def test_runtime_config_wires_transport_retries():
    cfg = RuntimeConfig(n_virtual=2, comm_mode="direct", transport_retries=2)
    rt = ClusterRuntime(cfg, table=_table())
    try:
        assert isinstance(rt.transport, PeerTransport)
        assert rt.transport.retries == 2
    finally:
        rt.shutdown()


# ---------------------------------------------------------------------------
# elastic rescale (satellite 3)
# ---------------------------------------------------------------------------
def test_rescale_shrink_drains_device_ahead_updates():
    """A device-ahead resident update on a departing device survives the
    shrink: reconciled through the spill path, relocated to a survivor, and
    readable there — no lost updates."""
    table = _table()
    table.register("bump", lambda state, s: {"state": state + s})
    rt = ClusterRuntime(RuntimeConfig(n_virtual=3), table=table)
    try:
        for d in range(3):
            rt.ex.enter_data(d, **{f"state{d}":
                                   jnp.full((8,), float(d + 1), jnp.float32)})
        # an in-flight nowait region mutates the departing device's entry:
        # the rescale must join it, then drain the device-ahead result
        rt.ex.target("bump", 2,
                     MapSpec(present={"state": "state2"},
                             device_out={"state": "state2"},
                             to={"s": jnp.float32(10)}),
                     nowait=True, tag="bump")
        rep = rescale_pool(rt, 2)
        assert rep["from"] == 3 and rep["to"] == 2
        assert len(rt.pool) == 2
        moved = {m[0]: m for m in rep["moved"]}
        assert "state2" in moved, rep
        assert rep["reconciled_bytes"] >= 32, rep     # the +10 was drained
        tgt = moved["state2"][2]
        val = rt.ex.fetch_resident(tgt, "state2")
        assert np.array_equal(np.asarray(val),
                              np.full((8,), 13.0, np.float32))
    finally:
        rt.shutdown()


def test_rescale_shrink_mid_job_bit_identical():
    """Run a graph on 4 devices, shrink to 2, run again: the survivor pool
    produces the same bits (present tables, health, executor survive)."""
    table = _table()
    graph = _diamond()
    rt = ClusterRuntime(RuntimeConfig(n_virtual=4), table=table)
    try:
        ref = {k: np.asarray(v) for k, v in
               run_graph(rt.ex, graph, policy="locality", peer=True).items()}
        rep = rescale_pool(rt, 2)
        assert len(rt.pool) == 2 and rep["to"] == 2
        vals = run_graph(rt.ex, graph, policy="locality", peer=True)
        for k in ref:
            assert np.array_equal(ref[k], np.asarray(vals[k])), k
    finally:
        rt.shutdown()


def test_rescale_grow_joined_device_is_placed():
    """Grow 2→4: the joined devices are placeable — a round-robin graph run
    after the grow actually executes commands on them."""
    table = _table()
    graph = TaskGraph.from_tasks(_random_tasks(5, 9))
    rt = ClusterRuntime(RuntimeConfig(n_virtual=2), table=table)
    try:
        run_graph(rt.ex, graph, policy="round-robin")
        rep = rescale_pool(rt, 4)
        assert rep["from"] == 2 and rep["to"] == 4 and len(rt.pool) == 4
        before = [len(t) for t in rt.pool.stream_traces]
        vals = run_graph(rt.ex, graph, policy="round-robin")
        ref = run_graph(TargetExecutor(DevicePool.virtual(2, table=table)),
                        graph, policy="round-robin")
        for k in ref:
            assert np.array_equal(np.asarray(ref[k]), np.asarray(vals[k])), k
        grew = [len(t) - b for t, b in zip(rt.pool.stream_traces, before)]
        assert grew[2] > 0 and grew[3] > 0, grew
    finally:
        rt.shutdown()


def test_rescale_grow_mid_graph_next_wave_places_on_joined_device():
    """A device joining WHILE a graph runs is picked up at the next wave
    boundary (membership refresh) — no restart required."""
    table = _table()
    rt = ClusterRuntime(RuntimeConfig(n_virtual=2), table=table)
    try:
        sds = jax.ShapeDtypeStruct((4, 4), jnp.float32)
        state = {"grown": False}

        def growing_maps(dv):
            # make_maps runs on the host at wave-planning time: grow here,
            # mid-graph, exactly once
            if not state["grown"]:
                state["grown"] = True
                rescale_pool(rt, 3)
            return MapSpec(to={"x": next(iter(dv.values()))},
                           from_={"out": sds})

        tasks = _random_tasks(11, 4)
        tasks.append(DagTask("grow", "combine", ("t3",), growing_maps))
        # a wide final wave so round-robin must wrap onto device 2
        for i in range(4):
            tasks.append(DagTask(
                f"w{i}", "combine", ("grow",),
                lambda dv: MapSpec(to={"x": dv["grow"]}, from_={"out": sds})))
        graph = TaskGraph.from_tasks(tasks)
        vals = run_graph(rt.ex, graph, policy="round-robin")
        assert state["grown"] and len(rt.pool) == 3
        assert len(rt.pool.stream_traces[2]) > 0      # joined device worked
        ref = run_graph(TargetExecutor(DevicePool.virtual(2, table=table)),
                        graph, policy="round-robin")
        for k in ref:
            assert np.array_equal(np.asarray(ref[k]), np.asarray(vals[k])), k
    finally:
        rt.shutdown()


def test_rescale_rejects_zero():
    rt = ClusterRuntime(RuntimeConfig(n_virtual=2), table=_table())
    try:
        with pytest.raises(ValueError, match="rescale"):
            rescale_pool(rt, 0)
    finally:
        rt.shutdown()


# ---------------------------------------------------------------------------
# with_retry rides the nowait stream path (satellite 4)
# ---------------------------------------------------------------------------
def test_with_retry_composes_with_inflight_nowait_regions():
    """The retried region flows through the dependency-aware streams: it
    interleaves with a concurrent nowait region on the same pool, both
    finish, and the handled failure never resurfaces at the innocent
    region's sync point."""
    table = _table()
    pool = DevicePool.virtual(3, table=table)
    ex = TargetExecutor(pool)
    pool.devices[0] = FlakyDevice(pool.devices[0], p=1.0, seed=0)
    sds = jax.ShapeDtypeStruct((4, 4), jnp.float32)
    # an innocent region in flight on a healthy device
    innocent = ex.target("src", 1,
                         MapSpec(to={"s": jnp.float32(2)}, from_={"out": sds}),
                         nowait=True, tag="innocent")
    bl = set()
    out = with_retry(ex, "src", 0,
                     MapSpec(to={"s": jnp.float32(1)}, from_={"out": sds}),
                     blacklist=bl)
    assert np.array_equal(np.asarray(out["out"]), np.ones((4, 4), np.float32))
    assert 0 in bl and pool.devices[0].failures >= 1
    assert pool.health.failures(0) >= 1
    # the innocent region joins cleanly — no stashed DeviceFailure leaked
    got = ex.drain([innocent])[0]
    assert np.array_equal(np.asarray(got["out"]),
                          np.full((4, 4), 2.0, np.float32))
    # and the pool is clean: a fresh sync raises nothing
    for d in range(1, 3):
        pool.sync(d)


def test_with_retry_all_devices_failed_raises():
    table = _table()
    pool = DevicePool.virtual(2, table=table)
    ex = TargetExecutor(pool)
    inject_flaky(pool, p=1.0, seed=0)
    sds = jax.ShapeDtypeStruct((4, 4), jnp.float32)
    with pytest.raises(DeviceFailure):
        with_retry(ex, "src", 0,
                   MapSpec(to={"s": jnp.float32(1)}, from_={"out": sds}))


# ---------------------------------------------------------------------------
# injection + registry mechanics
# ---------------------------------------------------------------------------
def test_flaky_device_rejects_ineligible_ops():
    table = _table()
    pool = DevicePool.virtual(1, table=table)
    with pytest.raises(ValueError, match="ALLOC"):
        FlakyDevice(pool.devices[0], p=0.5, ops=("ALLOC",))
    pool.stop_all()


def test_flaky_failures_by_op_accounts_every_fault(sparselu):
    table, graph = sparselu
    _, injected, _, pool = _run_chaos(graph, table, policy="round-robin",
                                      peer=True, p=0.2, seed=42,
                                      ops=FAULT_OPS, n_dev=4)
    by_op = {}
    for d in pool.devices:
        for op, n in getattr(d, "failures_by_op", {}).items():
            by_op[op] = by_op.get(op, 0) + n
    assert set(by_op) <= set(FAULT_OPS)
    assert sum(by_op.values()) == injected > 0


def test_health_registry_threshold_and_fallback():
    reg = HealthRegistry(max_failures=2)
    reg.mark_failed(1)
    assert reg.is_healthy(1) and not reg.blacklist      # one strike forgiven
    reg.mark_failed(1)
    assert not reg.is_healthy(1) and reg.blacklist == {1}
    assert reg.healthy(3) == [0, 2]
    # blacklisting everyone must not leave the scheduler with nothing:
    # healthy() falls back to the full candidate set
    for d in (0, 2):
        reg.mark_failed(d)
        reg.mark_failed(d)
    assert reg.healthy(3) == [0, 1, 2]
    # a rejoined (or replaced) device gets a clean slate
    reg.mark_healthy(1)
    assert reg.failures(1) == 0 and 1 not in reg.blacklist


# ---------------------------------------------------------------------------
# gray failures: HANG and SLOW under deadlines + hedging (straggler tentpole)
# ---------------------------------------------------------------------------
from repro.core import StragglerTimeout
from repro.ft import FAULT_MODES, StragglerDetector


def _run_gray_chaos(graph, table, *, policy, peer, p, seed, mode,
                    ops=("EXEC",), n_dev=3, deadline_s=None, stragglers=None,
                    max_retries=60, hang_s=0.4, slow_s=0.3):
    """One gray-failure chaos run: fresh pool with a command deadline,
    seeded HANG/SLOW injection, optional hedging."""
    pool = DevicePool.virtual(n_dev, table=table, deadline_s=deadline_s)
    ex = TargetExecutor(pool)
    if p > 0:
        inject_flaky(pool, p=p, seed=seed, ops=ops, mode=mode,
                     hang_s=hang_s, slow_s=slow_s)
    res = run_graph(ex, graph, policy=policy, peer=peer,
                    max_retries=max_retries, stragglers=stragglers)
    return {k: np.asarray(v) for k, v in res.items()}, pool


def test_chaos_hang_bit_identical():
    """Seeded HANG injection with a command deadline: every policy, both
    edge routings, p ∈ {0.05, 0.2} — the hung commands blow the deadline,
    are classified as straggler faults, recovered through the same
    re-place/reroute/heal machinery, and the answer stays bitwise equal."""
    table = _table()
    graph = _diamond()
    ref, _, _, _ = _run_chaos(graph, table, policy="round-robin", peer=False,
                              p=0.0, seed=0, ops=())
    for peer in (False, True):
        for policy in POLICIES:
            for p in (0.05, 0.2):
                vals, pool = _run_gray_chaos(
                    graph, table, policy=policy, peer=peer, p=p,
                    seed=101, mode="hang", deadline_s=0.15, hang_s=0.4)
                for k in ref:
                    assert np.array_equal(ref[k], vals[k]), (policy, peer, p, k)


def test_hang_deadline_classified_as_straggler():
    """A hung EXEC surfaces as StragglerTimeout — a DeviceFailure subclass
    counted per-op in pool.straggler_timeouts — not as a stuck run."""
    table = _table()
    graph = _diamond()
    vals, pool = _run_gray_chaos(graph, table, policy="round-robin",
                                 peer=False, p=0.6, seed=3, mode="hang",
                                 deadline_s=0.1, hang_s=0.5)
    assert pool.straggler_timeouts.get("EXEC", 0) >= 1
    assert issubclass(StragglerTimeout, DeviceFailure)
    ref, _, _, _ = _run_chaos(graph, table, policy="round-robin", peer=False,
                              p=0.0, seed=0, ops=())
    for k in ref:
        assert np.array_equal(ref[k], vals[k]), k


def test_slow_mode_counts_stalls_not_failures():
    """SLOW is a straggler, not a fault: the command completes correctly,
    so it must not mark the device or enter the blacklist."""
    table = _table()
    graph = _diamond()
    vals, pool = _run_gray_chaos(graph, table, policy="locality", peer=False,
                                 p=1.0, seed=3, mode="slow", slow_s=0.05)
    assert sum(getattr(d, "stalls", 0) for d in pool.devices) > 0
    assert sum(getattr(d, "failures", 0) for d in pool.devices) == 0
    assert not pool.health.blacklist
    ref, _, _, _ = _run_chaos(graph, table, policy="round-robin", peer=False,
                              p=0.0, seed=0, ops=())
    for k in ref:
        assert np.array_equal(ref[k], vals[k]), k


def test_slow_device_hedged_duplicate_wins_bit_identical():
    """A persistently slow device's tasks are hedged onto a healthy peer;
    the duplicate wins, the loser's records are struck, and the answer is
    bitwise equal — in both edge routings."""
    table = _table()
    graph = _diamond()
    ref, _, _, _ = _run_chaos(graph, table, policy="round-robin", peer=False,
                              p=0.0, seed=0, ops=())
    for peer in (False, True):
        pool = DevicePool.virtual(3, table=table)
        ex = TargetExecutor(pool)
        pool.devices[0] = FlakyDevice(pool.devices[0], p=1.0, seed=11,
                                      ops=("EXEC",), mode="slow", slow_s=0.5)
        det = StragglerDetector(pool.cost, k=3.0, grace_s=0.05, poll_s=0.01,
                                baseline={k: 0.01 for k in
                                          ("src", "combine", "combine2")})
        res = run_graph(ex, graph, policy="round-robin", peer=peer,
                        stragglers=det)
        rep = det.report()
        assert rep["hedge_wins"] >= 1, rep
        assert rep["hedges_launched"] <= det.max_hedges
        for k in ref:
            assert np.array_equal(ref[k], np.asarray(res[k])), (peer, k)
        # loser accounting: each task's compute counted exactly once
        assert len(pool.cost.compute) == len(ref)


def test_no_hedges_and_no_overhead_at_p0():
    """With a detector attached but nothing slow, no hedges launch and the
    traffic is byte-identical to a detector-free run."""
    table = _table()
    graph = _diamond()

    def run(det):
        pool = DevicePool.virtual(3, table=table)
        ex = TargetExecutor(pool)
        res = run_graph(ex, graph, policy="heft", peer=True, stragglers=det)
        return ({k: np.asarray(v) for k, v in res.items()},
                pool.cost.summary())

    ref, ref_stats = run(None)
    det = StragglerDetector(DevicePool.virtual(1, table=table).cost,
                            k=3.0, grace_s=10.0)   # huge grace: never fires
    det.cost = None                                # must not even be read
    pool = DevicePool.virtual(3, table=table)
    det.cost = pool.cost
    ex = TargetExecutor(pool)
    res = run_graph(ex, graph, policy="heft", peer=True, stragglers=det)
    assert det.report()["hedges_launched"] == 0
    stats = pool.cost.summary()
    for k in ref:
        assert np.array_equal(ref[k], np.asarray(res[k])), k
    for key in ("bytes_to", "bytes_from", "bytes_peer"):
        assert stats[key] == ref_stats[key], key


def test_chaos_sparselu_slow_hedging_bounds_makespan(sparselu):
    """Acceptance: sparselu at D=4 with a persistently slow device — the
    hedged run's modeled makespan stays within 2× the fault-free run
    (the loser's stalled records are struck, so the model counts each
    task once, at its fast copy's cost)."""
    table, graph = sparselu
    pool0 = DevicePool.virtual(4, table=table)
    ref = run_graph(TargetExecutor(pool0), graph, policy="locality",
                    peer=True)
    ref_vals = {k: np.asarray(v) for k, v in ref.items()}
    ref_makespan = pool0.cost.makespan()
    baseline = {k: pool0.cost.kernel_time(k)
                for k in ("lu0", "fwd", "bdiv", "bmod")
                if pool0.cost.kernel_time(k)}

    pool = DevicePool.virtual(4, table=table)
    ex = TargetExecutor(pool)
    pool.devices[0] = FlakyDevice(pool.devices[0], p=1.0, seed=5,
                                  ops=("EXEC",), mode="slow", slow_s=0.3)
    det = StragglerDetector(pool.cost, k=4.0, grace_s=0.05, poll_s=0.01,
                            max_hedges=64, baseline=baseline)
    vals = run_graph(ex, graph, policy="locality", peer=True, stragglers=det)
    for k in ref_vals:
        assert np.array_equal(ref_vals[k], np.asarray(vals[k])), k
    rep = det.report()
    assert rep["hedge_wins"] >= 1, rep
    assert pool.cost.makespan() <= 2.0 * ref_makespan, \
        (pool.cost.makespan(), ref_makespan, rep)


# ---------------------------------------------------------------------------
# blacklist probation: rejoin after clean waves, capped (satellite)
# ---------------------------------------------------------------------------
def test_probation_rejoins_after_clean_waves_then_caps():
    reg = HealthRegistry(max_failures=2, probation_waves=2, max_rejoins=1)
    reg.mark_failed(0), reg.mark_failed(0)
    assert 0 in reg.blacklist
    assert reg.tick_wave() == []         # the faulting wave itself is dirty
    assert reg.tick_wave() == []         # 1 clean wave: still out
    assert reg.tick_wave() == [0]        # 2 clean waves: probationary rejoin
    assert 0 not in reg.blacklist
    reg.mark_failed(0)                   # one more strike re-blacklists:
    assert 0 in reg.blacklist            # rejoined at max_failures - 1
    for _ in range(10):                  # rejoin budget spent: stays out
        assert reg.tick_wave() == []
    assert 0 in reg.blacklist


def test_probation_dirty_wave_resets_the_clock():
    reg = HealthRegistry(max_failures=1, probation_waves=2)
    reg.mark_failed(0)
    assert reg.tick_wave() == []
    reg.mark_failed(0)                   # fault during probation wave 2
    assert reg.tick_wave() == []         # clock reset, not rejoined
    assert reg.tick_wave() == []
    assert reg.tick_wave() == [0]


def test_probation_default_off():
    reg = HealthRegistry(max_failures=1)
    reg.mark_failed(0)
    for _ in range(50):
        assert reg.tick_wave() == []
    assert 0 in reg.blacklist


def test_probation_rejoined_device_receives_work():
    """Integration: a blacklisted device rejoins at a wave boundary of a
    live run and the policy actually places tasks on it again."""
    table = _table()
    graph = TaskGraph.from_tasks(_random_tasks(17, 9))
    ref = run_graph(TargetExecutor(DevicePool.virtual(2, table=table)),
                    graph, policy="round-robin")
    pool = DevicePool.virtual(2, table=table)
    pool.health = HealthRegistry(max_failures=2, probation_waves=1)
    pool.health.mark_failed(0), pool.health.mark_failed(0)
    assert 0 in pool.health.blacklist
    ex = TargetExecutor(pool)
    vals = run_graph(ex, graph, policy="round-robin")
    assert 0 not in pool.health.blacklist          # rejoined mid-run
    assert sum(1 for c in pool.stream_traces[0] if c.op == "EXEC") > 0
    for k in ref:
        assert np.array_equal(np.asarray(ref[k]), np.asarray(vals[k])), k


# ---------------------------------------------------------------------------
# transport: deadlines + seeded exponential backoff (satellite)
# ---------------------------------------------------------------------------
def test_transport_op_timeout_falls_back_to_funnel():
    """retries=0 + op_timeout_s: a hung SEND times out, is counted, and the
    edge reroutes through the funnel; the orphaned command settles later
    without poisoning an innocent sync."""
    import time as _time
    table = _table()
    pool = DevicePool.virtual(2, table=table)
    pool.devices[0] = FlakyDevice(pool.devices[0], p=1.0, seed=5,
                                  ops=("SEND",), mode="hang", hang_s=0.5)
    tr = PeerTransport(retries=0, op_timeout_s=0.1)
    h0 = pool.alloc(0, (8,), jnp.float32, tag="src")
    pool.transfer_to(0, h0, jnp.arange(8, dtype=jnp.float32))
    h1 = pool.alloc(1, (8,), jnp.float32, tag="dst")
    pool.transfer_to(1, h1, jnp.zeros((8,), jnp.float32))
    fut = tr.sendrecv(pool, 0, h0, 1, h1, tag="edge")
    if fut is not None and hasattr(fut, "result"):
        fut.result()
    got = pool.transfer_from(1, h1, tag="chk")
    assert tr.timeouts >= 1 and tr.fallbacks == 1
    assert np.array_equal(np.asarray(got), np.arange(8, dtype=np.float32))
    _time.sleep(0.7)                     # orphan settles; callback absorbs
    pool.sync()                          # raises nothing


def test_transport_backoff_is_seeded_and_deterministic():
    """Retries back off exponentially with seeded jitter: two transports
    with the same seed accrue identical backoff, a different seed differs."""
    table = _table()

    def run(seed):
        pool = DevicePool.virtual(2, table=table)
        inject_flaky(pool, p=1.0, seed=1, ops=("SEND",))
        tr = PeerTransport(retries=3, backoff_base_s=1e-4, seed=seed)
        h0 = pool.alloc(0, (8,), jnp.float32, tag="src")
        pool.transfer_to(0, h0, jnp.arange(8, dtype=jnp.float32))
        h1 = pool.alloc(1, (8,), jnp.float32, tag="dst")
        pool.transfer_to(1, h1, jnp.zeros((8,), jnp.float32))
        fut = tr.sendrecv(pool, 0, h0, 1, h1, tag="edge")
        if fut is not None and hasattr(fut, "result"):
            fut.result()
        got = pool.transfer_from(1, h1, tag="chk")
        assert np.array_equal(np.asarray(got), np.arange(8, dtype=np.float32))
        return tr
    a, b, c = run(42), run(42), run(7)
    assert a.backoffs == b.backoffs == 3         # one per retry
    assert a.backoff_s > 0 and a.backoff_s == b.backoff_s
    assert c.backoff_s != a.backoff_s
    assert a.fallbacks == 1                      # still reroutes in the end


def test_runtime_config_wires_deadlines_and_backoff():
    cfg = RuntimeConfig(n_virtual=2, comm_mode="direct",
                        command_deadline_s=5.0, transport_retries=1,
                        transport_op_timeout_s=2.0,
                        transport_backoff_seed=9)
    rt = ClusterRuntime(cfg, table=_table())
    try:
        assert rt.pool.deadline_s == 5.0
        assert isinstance(rt.transport, PeerTransport)
        assert rt.transport.op_timeout_s == 2.0
        assert rt.transport.retries == 1
    finally:
        rt.shutdown()
