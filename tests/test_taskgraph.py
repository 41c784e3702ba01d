"""TaskGraph IR, placement policies, and capacity-bounded device memory.

The acceptance properties of the scheduling refactor:

* every placement policy produces BIT-identical results — placement moves
  bytes, never values (property-tested on random DAGs and the sparselu
  wavefront, in host and peer modes);
* a capacity cap small enough to force LRU eviction + transparent refetch
  mid-graph changes traffic only, never results;
* on the sparselu wavefront at D=4, locality/HEFT placement reduces the
  total moved bytes (funnel + peer) vs round-robin — ≥25% for HEFT in the
  comm-bound regime;
* a discarded region's records are struck from EVERY cost lane, including
  the peer SEND/RECV records of its edges (speculation-loser accounting).
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))

from repro.core import (ClusterRuntime, DagTask, DevicePool, HeftPlacement,
                        KernelTable, LinkModel, LocalityAffinity, MapSpec,
                        PeerRef, PeerTransport, PlacementPolicy, RoundRobin,
                        RuntimeConfig, TargetExecutor, TaskGraph, TaskNode,
                        offload_strips, recursive_offload, resolve_policy,
                        run_graph, sec, wavefront_offload)

POLICIES = ("round-robin", "locality", "heft")


def _table():
    table = KernelTable()
    table.register("combine", lambda x: {"out": x @ x * 1e-2 + 1.0})
    table.register("combine2", lambda x, y: {"out": x @ x * 1e-2 + y})
    return table


def _chain_tasks(B=8, length=5, seed=0):
    """A chain with a long-range edge: every step re-reads p0, so capacity
    eviction of p0 forces a transparent refetch mid-graph."""
    rng = np.random.default_rng(seed)
    init = jnp.asarray(rng.standard_normal((B, B)), jnp.float32)
    sds = jax.ShapeDtypeStruct((B, B), jnp.float32)
    tasks = [DagTask("p0", "combine", (),
                     lambda dv: MapSpec(to={"x": init}, from_={"out": sds}))]
    for w in range(1, length + 1):
        tasks.append(DagTask(
            f"p{w}", "combine2", (f"p{w-1}", "p0"),
            (lambda w=w: lambda dv: MapSpec(
                to={"x": dv[f"p{w-1}"], "y": dv["p0"]},
                from_={"out": sds}))()))
        tasks.append(DagTask(
            f"f{w}", "combine", (f"p{w-1}",),
            (lambda w=w: lambda dv: MapSpec(
                to={"x": dv[f"p{w-1}"]}, from_={"out": sds}))()))
    return tasks


def _fanout_tasks(B=8, fan=3, waves=3, seed=0):
    """Chained fan-outs (the sparselu pivot pattern, minus the LU algebra)."""
    rng = np.random.default_rng(seed)
    mat = jnp.asarray(rng.standard_normal((B, B)), jnp.float32)
    sds = jax.ShapeDtypeStruct((B, B), jnp.float32)
    tasks, prev = [], None
    for w in range(waves):
        p = f"p{w}"
        tasks.append(DagTask(
            p, "combine", tuple(d for d in (prev,) if d),
            (lambda prev=prev, mat=mat: lambda deps: MapSpec(
                to={"x": deps[prev] if prev else mat},
                from_={"out": sds}))()))
        for i in range(fan):
            tasks.append(DagTask(
                f"c{w}_{i}", "combine", (p,),
                (lambda p=p: lambda deps: MapSpec(
                    to={"x": deps[p]}, from_={"out": sds}))()))
        prev = p
    return tasks


# ---------------------------------------------------------------------------
# the IR itself
# ---------------------------------------------------------------------------
def test_taskgraph_waves_and_cycles():
    g = TaskGraph.from_tasks(_fanout_tasks(waves=2, fan=2))
    waves = g.waves()
    assert waves[0] == ["p0"]
    assert set(waves[1]) == {"c0_0", "c0_1", "p1"}
    assert len(g) == 6
    # defaults: reads mirror deps, writes the node's own name
    n = g.node("c0_0")
    assert n.reads == ("p0",) and n.writes == ("c0_0",)
    with pytest.raises(ValueError, match="duplicate"):
        g.add(TaskNode(name="p0", kernel="combine"))
    cyc = TaskGraph([TaskNode(name="a", kernel="k", deps=("b",)),
                     TaskNode(name="b", kernel="k", deps=("a",))])
    with pytest.raises(ValueError, match="cycle"):
        cyc.waves()


def test_resolve_policy_forms():
    assert isinstance(resolve_policy(None), RoundRobin)
    assert isinstance(resolve_policy("locality"), LocalityAffinity)
    assert isinstance(resolve_policy(HeftPlacement), HeftPlacement)
    p = HeftPlacement(default_task_s=1e-6)
    assert resolve_policy(p) is p
    with pytest.raises(ValueError, match="unknown placement policy"):
        resolve_policy("fifo")
    with pytest.raises(TypeError):
        resolve_policy(42)


# ---------------------------------------------------------------------------
# bit-identical results under every policy (satellite: property test)
# ---------------------------------------------------------------------------
def _run_tasks(tasks, *, policy, peer, cap=None, n_dev=3, table=None):
    rt = ClusterRuntime(RuntimeConfig(n_virtual=n_dev,
                                      device_capacity_bytes=cap),
                        table=table or _table())
    try:
        res = rt.wavefront_offload(list(tasks), nowait=True, peer=peer,
                                   policy=policy)
        stats = rt.cost.summary()
        mem = rt.memory_report()
        return ({k: np.asarray(v) for k, v in res.items()}, stats, mem)
    finally:
        rt.shutdown()


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10_000), st.integers(4, 9), st.integers(2, 4))
def test_policies_bit_identical_on_random_dags(seed, n_tasks, n_dev):
    """Random DAGs: all policies agree bitwise, host and peer modes alike."""
    rng = np.random.default_rng(seed)
    B = 4
    sds = jax.ShapeDtypeStruct((B, B), jnp.float32)
    init = jnp.asarray(rng.standard_normal((B, B)), jnp.float32)
    tasks = []
    for i in range(n_tasks):
        n_deps = int(rng.integers(0, min(i, 2) + 1))
        deps = tuple(f"t{j}" for j in
                     rng.choice(i, size=n_deps, replace=False)) if i else ()
        # deps are treated OPAQUELY (to= clause), so the same callback is
        # host- and peer-routable
        tasks.append(DagTask(
            f"t{i}", "combine", deps,
            (lambda deps=deps, init=init: lambda dv: MapSpec(
                to=({"x": next(iter(dv.values()))} if dv else {"x": init}),
                from_={"out": sds}))()))
    ref = None
    for peer in (False, True):
        for policy in POLICIES:
            vals, _, _ = _run_tasks(tasks, policy=policy, peer=peer,
                                    n_dev=n_dev)
            if ref is None:
                ref = vals
            for k in ref:
                assert np.array_equal(ref[k], vals[k]), (policy, peer, k)


def test_policies_bit_identical_under_capacity_pressure():
    """A cap small enough to force eviction+refetch mid-graph changes the
    traffic, never the result."""
    tasks = _chain_tasks(B=8, length=5)
    cap = 2 * 8 * 8 * 4                       # two 256-byte blocks/device
    ref, _, _ = _run_tasks(tasks, policy="round-robin", peer=True, n_dev=2)
    for policy in POLICIES:
        vals, _, mem = _run_tasks(tasks, policy=policy, peer=True, cap=cap,
                                  n_dev=2)
        evictions = sum(m["evictions"] for m in mem.values())
        refetches = sum(m["refetches"] for m in mem.values())
        assert evictions >= 1, (policy, mem)
        assert refetches >= 1, (policy, mem)
        for k in ref:
            assert np.array_equal(ref[k], vals[k]), (policy, k)


# ---------------------------------------------------------------------------
# sparselu at D=4: the acceptance numbers
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def sparselu():
    from bots_sparselu import _build_dag, _make_table, _matrix
    K, B = 4, 64
    mat = _matrix(K, B)
    return _make_table(K), _build_dag(mat, K, B), K, B


def test_sparselu_policies_bit_identical_and_fewer_bytes(sparselu):
    table, tasks, K, B = sparselu
    totals = {}
    ref = None
    # HEFT in the comm-bound regime (task estimate far below the modeled
    # edge time — §5.6's regime, where spreading is what loses): frozen
    # estimate so placement is deterministic under measured-timing noise
    heft = HeftPlacement(default_task_s=5e-6, use_observed=False)
    for name, policy in (("round-robin", "round-robin"),
                         ("locality", "locality"), ("heft", heft)):
        vals, stats, _ = _run_tasks(tasks, policy=policy, peer=True,
                                    n_dev=4, table=table)
        totals[name] = stats["bytes_to"] + stats["bytes_from"] \
            + stats["bytes_peer"]
        if ref is None:
            ref = vals
        for k in ref:
            assert np.array_equal(ref[k], vals[k]), (name, k)
    # cost-driven placement moves strictly fewer bytes than round-robin;
    # HEFT by >=25% (measured: ~44% — it retires every peer edge here)
    assert totals["locality"] < totals["round-robin"], totals
    assert totals["heft"] <= 0.75 * totals["round-robin"], totals
    # capacity cap forcing evictions mid-factorization: bit-for-bit again
    cap = 6 * B * B * 4
    vals, _, mem = _run_tasks(tasks, policy=heft, peer=True, cap=cap,
                              n_dev=4, table=table)
    assert sum(m["evictions"] for m in mem.values()) >= 1, mem
    for k in ref:
        assert np.array_equal(ref[k], vals[k]), ("capped", k)


# ---------------------------------------------------------------------------
# capacity-bounded present table: spill/refetch mechanics
# ---------------------------------------------------------------------------
def _cap_pool(n=1, cap=None):
    table = KernelTable()
    table.register("double", lambda x: {"out": x * 2.0})
    table.register("double_a", lambda a: {"out": a * 2.0})
    pool = DevicePool.virtual(n, table=table, capacity_bytes=cap)
    return pool, TargetExecutor(pool)


def test_lru_eviction_reconciles_device_ahead_and_refetches():
    blk = 16 * 4                               # 16 float32s per entry
    pool, ex = _cap_pool(cap=2 * blk)
    a, b, c = (jnp.arange(16.0) + i for i in range(3))
    ex.enter_data(0, "e", a=a)
    ex.enter_data(0, "e", b=b)
    # device-ahead: an on-device write nothing has fetched yet
    ex.target("double", 0, MapSpec(present={"x": "a"},
                                   device_out={"out": "a"}))
    assert pool.present[0].get("a").device_ahead
    # third entry exceeds the cap: LRU victim is "a" (b was entered later,
    # a's bind made it recently-used... touch order: a was used by the
    # region last, so the victim is "b")
    ex.enter_data(0, "e", c=c)
    table = pool.present[0]
    spilled = [n for n in table.names() if table.get(n).spilled]
    assert spilled == ["b"], spilled
    assert table.evictions == 1
    assert table.used_bytes() <= 2 * blk
    # the spilled entry's value survives: transparent on both read paths
    np.testing.assert_array_equal(ex.fetch_resident(0, "b"), np.asarray(b))
    # a device-ahead victim reconciles before its buffers are freed ("a" is
    # now the least-recently-used live entry)
    ex.enter_data(0, "e", d=jnp.zeros(16))     # evicts "a" (device-ahead)
    ent_a = table.get("a")
    assert ent_a.spilled and not ent_a.device_ahead
    assert table.bytes_reconciled >= blk
    np.testing.assert_array_equal(ex.fetch_resident(0, "a"),
                                  np.asarray(a) * 2.0)
    # a present binding REQUIRES residency: it refetches transparently
    out = ex.target("double", 0, MapSpec(
        present={"x": "a"},
        from_={"out": jax.ShapeDtypeStruct((16,), jnp.float32)}))
    np.testing.assert_array_equal(out["out"], np.asarray(a) * 4.0)
    assert not table.get("a").spilled
    assert table.refetches >= 1
    ex.exit_data(0, "a", "b", "c", "d")
    pool.stop_all()


def test_pinned_and_retained_entries_are_not_evicted():
    blk = 16 * 4
    pool, ex = _cap_pool(cap=2 * blk)
    ex.enter_data(0, "e", a=jnp.arange(16.0))
    ex.pin_resident(0, "a")
    ex.enter_data(0, "e", b=jnp.ones(16))
    pool.present[0].get("b").refcount += 1     # an in-flight region's hold
    try:
        # over budget with nothing evictable: soft cap — residency proceeds
        ex.enter_data(0, "e", c=jnp.zeros(16))
        table = pool.present[0]
        assert not table.get("a").spilled and not table.get("b").spilled
        assert table.used_bytes() == 3 * blk   # over cap, by design
        assert table.lru_victim() is table.get("c")
        # un-pinning re-admits the entry to the LRU scan
        ex.pin_resident(0, "a", pinned=False)
        assert table.lru_victim() is table.get("a")
    finally:
        pool.present[0].get("b").refcount -= 1
        ex.exit_data(0, "a", "b", "c")
        pool.stop_all()


def test_spilled_entry_refetches_on_next_match():
    blk = 16 * 4
    pool, ex = _cap_pool(cap=blk)
    a, b = jnp.arange(16.0), jnp.ones(16)
    ex.enter_data(0, "e", a=a)
    ex.enter_data(0, "e", b=b)                 # evicts "a"
    table = pool.present[0]
    assert table.get("a").spilled
    # a map naming the spilled value transparently refetches it (the ping
    # evicts "b" in turn — the cap holds one block) and the match hits
    out = ex.target("double_a", 0, MapSpec(to={"a": a},
                                           from_={"out": jax.ShapeDtypeStruct(
                                               (16,), jnp.float32)}))
    np.testing.assert_array_equal(out["out"], np.asarray(a) * 2)
    assert not table.get("a").spilled and table.get("b").spilled
    assert table.refetches >= 1
    assert table.used_bytes() <= blk
    # re-entering the spilled name revives it the same way
    ex.enter_data(0, "e", b=b)
    assert not table.get("b").spilled and table.get("a").spilled
    ex.exit_data(0, "a", "b", "b")             # two refs on b (entered twice)
    pool.stop_all()


def test_memory_report_shape():
    rt = ClusterRuntime(RuntimeConfig(n_virtual=2,
                                      device_capacity_bytes=1024))
    try:
        rep = rt.memory_report()
        assert set(rep) == {0, 1}
        for row in rep.values():
            for key in ("resident_bytes", "capacity_bytes", "evictions",
                        "refetches", "bytes_reconciled", "bytes_refetched"):
                assert key in row
            assert row["capacity_bytes"] == 1024
    finally:
        rt.shutdown()


# ---------------------------------------------------------------------------
# satellite: discard_tag strikes peer lanes (speculation losers)
# ---------------------------------------------------------------------------
def test_discard_tag_strikes_peer_records_and_events():
    from repro.core.costmodel import CostModel
    c = CostModel()
    c.record_peer(0, 1, 1000, tag="strips:spec[2]:edge")
    c.record_peer(1, 2, 500, tag="strips[0:4]")
    c.record_transfer("to", 0, 100, tag="strips:spec[2]:x")
    c.record_placement("strips:spec[2]", 1, 1e-3, policy="heft")
    # struck: peer record + transfer record + their 2 events + the placement
    assert c.discard_tag("strips:spec[2]") == 5
    assert c.bytes_peer() == 500                  # the winner's record stays
    assert c.bytes_moved() == 0
    assert not any(e.kind == "peer" and "spec" in e.tag for e in c.events)
    assert c.placements == []


def test_run_graph_tags_peer_edges_per_region_for_discard():
    """A region's peer propagation is tagged with ITS tag, so striking a
    (speculation-)losing region removes its peer records too."""
    tasks = _fanout_tasks(B=8, fan=2, waves=2)
    rt = ClusterRuntime(RuntimeConfig(n_virtual=2), table=_table())
    try:
        rt.wavefront_offload(list(tasks), nowait=True, peer=True,
                             policy="round-robin")
        cross = [p for p in rt.cost.peers]
        assert cross, "expected at least one peer edge"
        # every peer record's tag names the consumer region (dag:w<k>:<task>
        # :edge:<entry>) — not a shared run-wide tag
        assert all(p.tag.startswith("dag:w") and ":edge:" in p.tag
                   for p in cross), [p.tag for p in cross]
        victim_tag = cross[0].tag.split(":edge:", 1)[0]
        before = rt.cost.bytes_peer()
        rt.cost.discard_tag(victim_tag)
        assert rt.cost.bytes_peer() < before
        assert not any(p.tag.startswith(victim_tag) for p in rt.cost.peers)
    finally:
        rt.shutdown()


def test_offload_strips_speculation_strikes_loser_records():
    table = KernelTable()

    @table.kernel("square")
    def square(xs):
        return {"out": xs * xs}

    pool = DevicePool.virtual(3, table=table)
    ex = TargetExecutor(pool)
    data = jnp.arange(17.0)

    def make_maps(start, length):
        return MapSpec(to={"xs": sec(data, start, length)},
                       from_={"out": jax.ShapeDtypeStruct((length,),
                                                          data.dtype)})

    out = offload_strips(ex, "square", 17, make_maps, speculate=True)
    np.testing.assert_allclose(out, data * data)
    # for every strip, exactly ONE copy's compute survives in the model
    # (dispatched + respawned minus struck losers == number of strips)
    assert len(pool.cost.compute) == 3
    # serial dispatch wins over speculation (no straggler to race when
    # strips run one at a time): no duplicate compute, same result
    pool.cost.reset()
    out = offload_strips(ex, "square", 17, make_maps, speculate=True,
                         nowait=False)
    np.testing.assert_allclose(out, data * data)
    assert len(pool.cost.compute) == 3
    pool.stop_all()


# ---------------------------------------------------------------------------
# satellite: PeerRef resolution is placement-independent
# ---------------------------------------------------------------------------
def test_peerref_resolution_ignores_baked_device():
    """A callback may hand back a PeerRef with a stale/absent device field;
    the runner resolves through its live producer map."""
    B = 8
    sds = jax.ShapeDtypeStruct((B, B), jnp.float32)
    init = jnp.eye(B, dtype=jnp.float32)

    def consumer_maps(dv):
        (k, v), = dv.items()
        if isinstance(v, PeerRef):
            v = PeerRef(v.task, v.entry, device=999)   # deliberately wrong
        return MapSpec(to={"x": v}, from_={"out": sds})

    tasks = [DagTask("p", "combine", (),
                     lambda dv: MapSpec(to={"x": init}, from_={"out": sds})),
             DagTask("c", "combine", ("p",), consumer_maps)]

    class PinSecond(PlacementPolicy):
        name = "pin-second"

        def place(self, ctx, node, j, region_tag):
            return 0 if node.name == "p" else 1

    ref = _run_tasks(tasks, policy="round-robin", peer=False, n_dev=2)[0]
    for policy in ("round-robin", "locality", PinSecond()):
        vals, _, _ = _run_tasks(tasks, policy=policy, peer=True, n_dev=2)
        for k in ref:
            assert np.array_equal(ref[k], vals[k]), (policy, k)


# ---------------------------------------------------------------------------
# policies through the other two patterns (they lower into the same IR)
# ---------------------------------------------------------------------------
def test_offload_strips_and_recursive_accept_policies():
    table = KernelTable()

    @table.kernel("sq")
    def sq(xs):
        return {"out": xs * xs}

    @table.kernel("tri")
    def tri(n):
        return {"out": n * (n + 1) / 2}

    pool = DevicePool.virtual(3, table=table)
    ex = TargetExecutor(pool)
    data = jnp.arange(11.0)

    def make_maps(start, length):
        return MapSpec(to={"xs": sec(data, start, length)},
                       from_={"out": jax.ShapeDtypeStruct((length,),
                                                          data.dtype)})

    for policy in POLICIES:
        out = offload_strips(ex, "sq", 11, make_maps, policy=policy)
        np.testing.assert_allclose(out, data * data)

    def split(n):
        return [n - 1, n - 2] if n > 3 else None

    def combine(_n, kids):
        return kids[0] + kids[1]

    def rec_maps(n):
        return MapSpec(to={"n": jnp.asarray(float(n))},
                       from_={"out": jax.ShapeDtypeStruct((), jnp.float32)})

    vals = {policy: float(recursive_offload(ex, "tri", 6, split, combine,
                                            rec_maps, policy=policy))
            for policy in POLICIES}
    assert len(set(vals.values())) == 1, vals
    pool.stop_all()


# ---------------------------------------------------------------------------
# HEFT internals: edge routing + predicted-vs-observed accounting
# ---------------------------------------------------------------------------
def test_heft_routes_edges_to_funnel_when_peer_link_is_slow():
    tasks = _fanout_tasks(B=8, fan=2, waves=2)
    slow_peer = PeerTransport(LinkModel("modem", 1e3, 1.0))
    ref, _, _ = _run_tasks(tasks, policy="round-robin", peer=False, n_dev=2)
    rt = ClusterRuntime(RuntimeConfig(n_virtual=2), table=_table())
    try:
        heft = HeftPlacement(default_task_s=1e-3, use_observed=False)
        res = rt.wavefront_offload(
            list(tasks), nowait=True, peer=True, policy=heft,
            transport=slow_peer)
        # every cross-device edge was priced off the modem: zero peer bytes
        assert rt.cost.bytes_peer() == 0
        for k in ref:
            assert np.array_equal(ref[k], np.asarray(res[k])), k
    finally:
        rt.shutdown()
    # and the routing primitive itself answers "funnel" on that fabric
    from repro.core.taskgraph import PlacementContext
    rt2 = ClusterRuntime(RuntimeConfig(n_virtual=2), table=_table())
    try:
        ctx = PlacementContext(pool=rt2.pool, cost=rt2.cost, D=2, peer=True,
                               transport=slow_peer)
        assert HeftPlacement().route_edge(ctx, 0, 1, 1024) == "funnel"
        ctx_fast = PlacementContext(pool=rt2.pool, cost=rt2.cost, D=2,
                                    peer=True, transport=PeerTransport())
        assert HeftPlacement().route_edge(ctx_fast, 0, 1, 1024) == "peer"
    finally:
        rt2.shutdown()


def test_placement_report_predicted_vs_observed(sparselu):
    table, tasks, K, B = sparselu
    rt = ClusterRuntime(RuntimeConfig(n_virtual=2), table=table)
    try:
        rt.wavefront_offload(list(tasks), nowait=True, policy="heft")
        report = rt.cost.placement_report()
        assert len(report) == len(tasks)
        for row in report:
            assert row["policy"] == "heft"
            assert row["observed_s"] > 0.0          # the region really ran
            assert row["observed_device_ok"]        # where it was predicted
        # observed kernel timings exist for the estimator to sharpen on
        for kernel in ("lu0", "fwd", "bdiv", "bmod"):
            assert rt.cost.kernel_time(kernel) > 0.0
    finally:
        rt.shutdown()


def test_a_finished_run_leaves_no_cycle_holding_its_arrays():
    """``run_graph``'s helpers call each other; once it returns, its
    results and device arrays go by reference counting, not at the next
    cyclic collection (which would keep many runs' device memory alive)."""
    import gc
    table = KernelTable()
    table.register("inc", lambda x: {"out": x + 1.0})
    rt = ClusterRuntime(RuntimeConfig(n_virtual=2, comm_mode="direct"),
                        table=table)
    x = jnp.ones((8,), jnp.float32)
    sds = jax.ShapeDtypeStruct((8,), jnp.float32)
    tasks = [DagTask("a", "inc", (), lambda r: MapSpec(to={"x": x},
                                                       from_={"out": sds})),
             DagTask("b", "inc", ("a",), lambda r: MapSpec(
                 to={"x": r["a"]}, from_={"out": sds}))]
    for peer in (False, True):
        rt.wavefront_offload(tasks, nowait=True, peer=peer)   # warm
        gc.collect()
        gc.disable()
        try:
            res = rt.wavefront_offload(tasks, nowait=True, peer=peer)
            np.testing.assert_allclose(res["b"], np.full(8, 3.0))
            del res
            gc.set_debug(gc.DEBUG_SAVEALL)
            gc.collect()
            arrays = [o for o in gc.garbage if isinstance(o, jax.Array)]
        finally:
            gc.set_debug(0)
            gc.garbage.clear()
            gc.enable()
        assert arrays == []
    rt.shutdown()
