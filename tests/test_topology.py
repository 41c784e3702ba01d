"""Hierarchical topology (PR 9 tentpole): rack-aware collectives that are
bit-identical to the flat/serial association while moving O(R) instead of
O(D) cross-rack traffic, per-pair edge pricing, compression-aware edge
routing, and the funnel-fallback ladder under a dead rack-leader link."""
import numpy as np

from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp
import pytest

from repro.core import (ClusterRuntime, CostModel, DagTask, DevicePool,
                        HeftPlacement, INTRA_RACK, KernelTable, LinkModel,
                        MapSpec, PAPER_ETHERNET, PeerTransport,
                        PlacementContext, PlacementPolicy, RuntimeConfig,
                        Topology)
from repro.ft import inject_flaky


def _pool(n):
    table = KernelTable()
    table.register("combine", lambda x: {"out": x @ x * 1e-2 + 1.0})
    table.register("combine2", lambda x, y: {"out": x @ x * 1e-2 + y})
    return DevicePool.virtual(n, table=table)


def _install(pool, d, value):
    value = jnp.asarray(value)
    h = pool.alloc(d, value.shape, value.dtype)
    pool.transfer_to(d, h, value)
    return h


def _leaf_values(D, L=2, seed=0, shape=(5, 3)):
    rng = np.random.default_rng(seed)
    return [[jnp.asarray(rng.standard_normal(shape), jnp.float32)
             for _ in range(L)] for _ in range(D)]


def _setup_collective(D, values):
    pool = _pool(D)
    handles = [[_install(pool, d, v) for v in values[d]] for d in range(D)]
    specs = [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in values[0]]
    return pool, handles, specs


# ---------------------------------------------------------------------------
# the Topology object itself
# ---------------------------------------------------------------------------
def test_constructor_rejects_non_contiguous_racks():
    Topology([[0, 1], [2, 3]])                      # fine
    Topology([[0], [1, 2], [3]])                    # uneven is fine too
    for bad in ([[0, 1], [3, 4]],                   # gap
                [[1, 0], [2, 3]],                   # not ascending in-rack
                [[2, 3], [0, 1]],                   # racks out of order
                [[0, 1], []],                       # empty rack
                []):                                # no racks at all
        with pytest.raises(ValueError):
            Topology(bad)


def test_shape_constructors():
    t = Topology.two_tier(2, 4)
    assert t.racks == ((0, 1, 2, 3), (4, 5, 6, 7))
    assert (t.n_devices, t.n_racks) == (8, 2)
    p = Topology.partition(7, 3)
    assert p.racks == ((0, 1, 2), (3, 4, 5), (6,))  # remainder rack
    with pytest.raises(ValueError, match="per_rack"):
        Topology.partition(4, 0)
    f = Topology.flat(4)
    assert f.n_racks == 1 and f.intra is f.inter is PAPER_ETHERNET


def test_structure_queries():
    t = Topology.two_tier(2, 3)
    assert t.rack_of(0) == 0 and t.rack_of(5) == 1
    assert t.same_rack(0, 2) and not t.same_rack(2, 3)
    assert t.cross_rack(1, 4) and not t.cross_rack(4, 5)
    assert t.members(1) == (3, 4, 5)
    assert t.leaders() == [0, 3]
    assert t.leader_of(5) == 3 and t.leader(0) == 0
    assert t.covers(0, 5) and not t.covers(0, 6)


def test_link_between_and_overrides():
    t = Topology.two_tier(2, 2, inter_bw_ratio=0.1)
    assert t.link_between(0, 1) is t.intra
    assert t.link_between(1, 2) is t.inter
    assert t.inter.bandwidth_Bps == pytest.approx(t.intra.bandwidth_Bps * 0.1)
    # the default two-tier spine at ratio 0.1 IS the paper's Gbit Ethernet
    assert t.inter.bandwidth_Bps == pytest.approx(PAPER_ETHERNET.bandwidth_Bps)
    degraded = LinkModel("degraded", 1e6, 1e-3)
    t.set_link(0, 3, degraded)
    assert t.link_between(0, 3) is degraded
    assert t.link_between(3, 0) is degraded         # undirected by default
    t.set_link(1, 2, degraded, directed=True)
    assert t.link_between(1, 2) is degraded
    assert t.link_between(2, 1) is t.inter
    assert t.pair_time(0, 3, 1000) == pytest.approx(degraded.time(1000, 1))


def test_compression_decision_is_per_link():
    t = Topology.two_tier(2, 4, inter_bw_ratio=0.1)
    big = 1 << 20
    # fat intra-rack link: savings never beat the quantize cost
    assert not t.compression_wins(0, 1, big)
    # thin spine, big message: int8 wire wins and is strictly faster
    sec, comp = t.edge_seconds(0, 4, big)
    assert comp and sec < t.inter.time(big, 1)
    # tiny message: per-block scales make the wire LARGER -> never compress
    assert t.int8_wire_nbytes(16) > 16
    assert not t.compression_wins(0, 4, 16)
    # wire-size arithmetic: 300 f32 elements = 2 blocks of 256 + 2 scales
    assert t.int8_wire_nbytes(1200) == 2 * 256 + 2 * 4
    d = t.describe()
    assert d["racks"] == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert d["inter"]["bandwidth_Bps"] == pytest.approx(1.25e8)


# ---------------------------------------------------------------------------
# hierarchical collectives: fewer cross-rack bytes, identical bits
# ---------------------------------------------------------------------------
def test_hier_allreduce_moves_fewer_cross_rack_bytes():
    """2 racks x 4 devices: the flat ring crosses the spine 2(D-1) times,
    the hierarchical chain 2(R-1) times — an 85% cut, well past the 40%
    acceptance floor."""
    topo = Topology.two_tier(2, 4, inter_bw_ratio=0.1)
    n = 300
    values = [[jnp.full((n,), float(d + 1), jnp.float32)] for d in range(8)]

    def run(transport):
        pool, handles, specs = _setup_collective(8, values)
        pool.cost.topology = topo            # cross-rack accounting
        transport.ring_allreduce(pool, handles, specs)
        pool.sync()
        got = np.asarray(pool.transfer_from(0, handles[0][0]))
        cross = pool.cost.bytes_peer_cross_rack()
        assert pool.cost.summary()["bytes_peer_cross_rack"] == cross
        pool.stop_all()
        return got, cross

    flat_v, flat_x = run(PeerTransport())
    hier_v, hier_x = run(PeerTransport(topology=topo))
    buf = n * 4
    assert flat_x == 2 * 7 * buf                 # (D-1) crossings per link, x2
    assert hier_x == 2 * 1 * buf                 # leader chain + broadcast
    assert hier_x <= 0.6 * flat_x
    np.testing.assert_allclose(hier_v, flat_v, rtol=1e-6)


def test_hier_ring_allreduce_sums_bitwise_and_frees_scratch():
    topo = Topology.two_tier(2, 3)
    D = 6
    values = _leaf_values(D, seed=5)
    pool, handles, specs = _setup_collective(D, values)
    PeerTransport(topology=topo).ring_allreduce(pool, handles, specs)
    # the hierarchical sum carries the SERIAL ascending association
    want = [np.asarray(sum((values[d][j] for d in range(1, D)),
                           values[0][j])) for j in range(2)]
    pool.sync()
    for d in range(D):
        for j in range(2):
            got = np.asarray(pool.transfer_from(d, handles[d][j]))
            np.testing.assert_array_equal(got, want[j]), (d, j)
        assert len(pool.devices[d].store.live_handles()) == 2, d
    pool.stop_all()


@settings(max_examples=6, deadline=None)
@given(st.integers(2, 7), st.integers(1, 4), st.integers(0, 10_000))
def test_hier_mean_bit_identical_to_flat_and_serial(D, per_rack, seed):
    """Property: for ANY rack shape (odd D, remainder racks, singleton
    racks) the hierarchical mean equals the flat allreduce_mean equals the
    host-serial ``sum(views)/D`` — bitwise, on every device."""
    topo = Topology.partition(D, per_rack)
    values = _leaf_values(D, L=2, seed=seed, shape=(3, 2))
    serial = [np.asarray(sum(v[j] for v in values) / D) for j in range(2)]

    def run(transport):
        pool, handles, specs = _setup_collective(D, values)
        transport.allreduce_mean(pool, handles, specs)
        pool.sync()
        out = [[np.asarray(pool.transfer_from(d, handles[d][j]))
                for j in range(2)] for d in range(D)]
        for dev in pool.devices:             # no leaked collective scratch
            assert len(dev.store.live_handles()) == 2
        pool.stop_all()
        return out

    hier = run(PeerTransport(topology=topo))
    flat = run(PeerTransport())
    for d in range(D):
        for j in range(2):
            np.testing.assert_array_equal(hier[d][j], serial[j]), (d, j)
            np.testing.assert_array_equal(flat[d][j], serial[j]), (d, j)


def test_hier_broadcast_delivers_root_value_everywhere():
    topo = Topology.partition(5, 2)          # (0,1) (2,3) (4,)
    values = _leaf_values(5, seed=9)
    pool, handles, specs = _setup_collective(5, values)
    PeerTransport(topology=topo).broadcast(pool, handles, specs, root=3)
    pool.sync()
    for d in range(5):
        for j in range(2):
            np.testing.assert_array_equal(
                np.asarray(pool.transfer_from(d, handles[d][j])),
                np.asarray(values[3][j]))
    pool.stop_all()


def test_single_rack_topology_keeps_flat_collectives():
    """One rack never dispatches the hierarchical path (n_racks > 1 guard):
    flat topology is pricing-only."""
    tr = PeerTransport(topology=Topology.flat(3))
    assert not tr._hier_ok(3)
    assert PeerTransport(topology=Topology.two_tier(2, 2))._hier_ok(4)
    # size mismatch (subset pool) also falls back to the flat path
    assert not PeerTransport(topology=Topology.two_tier(2, 2))._hier_ok(3)


# ---------------------------------------------------------------------------
# chaos: a dead rack-leader link degrades through the fallback ladder
# ---------------------------------------------------------------------------
def test_hier_mean_survives_dead_rack_leader_link():
    """Every SEND/RECV on rack 1's leader fails hard: retries exhaust,
    the funnel fallback carries the leader's messages through the host —
    and the delivered bits are still the serial association."""
    topo = Topology.two_tier(2, 2)
    D = 4
    values = _leaf_values(D, seed=13)
    serial = [np.asarray(sum(v[j] for v in values) / D) for j in range(2)]
    pool, handles, specs = _setup_collective(D, values)
    leader = topo.leader(1)
    inject_flaky(pool, p=1.0, seed=1, devices=[leader],
                 ops=("SEND", "RECV"))
    tr = PeerTransport(retries=1, backoff_base_s=1e-5, topology=topo)
    tr.allreduce_mean(pool, handles, specs)
    pool.sync()
    assert tr.fallbacks > 0                  # the ladder actually engaged
    for d in range(D):
        for j in range(2):
            np.testing.assert_array_equal(
                np.asarray(pool.transfer_from(d, handles[d][j])),
                serial[j]), (d, j)
    pool.stop_all()


# ---------------------------------------------------------------------------
# per-pair edge pricing + compression-aware routing at the placement layer
# ---------------------------------------------------------------------------
def test_peer_edge_time_is_per_pair_under_topology():
    topo = Topology.two_tier(2, 2, inter_bw_ratio=0.1)
    tr = PeerTransport(topology=topo)
    cost = CostModel(PAPER_ETHERNET)
    n = 1 << 16
    intra = tr.edge_time(cost, 0, 1, n)
    inter = tr.edge_time(cost, 0, 2, n)
    assert intra < inter
    assert intra == pytest.approx(topo.intra.time(n, 1))
    # cross-rack price folds in the compression decision (int8 wire beats
    # raw on the spine at this size), so it undercuts the raw spine time
    assert inter == pytest.approx(topo.edge_seconds(0, 2, n)[0])
    assert inter < topo.inter.time(n, 1)
    # a pair the topology does not cover falls back to the flat peer link
    assert tr.edge_time(cost, 0, 7, n) == pytest.approx(
        PAPER_ETHERNET.time(n, 1))


def test_route_edge_compresses_only_where_the_link_is_thin():
    topo = Topology.two_tier(2, 2, inter_bw_ratio=0.1)
    tr = PeerTransport(topology=topo)
    cost = CostModel(PAPER_ETHERNET)
    ctx = PlacementContext(pool=None, cost=cost, D=4, peer=True,
                           transport=tr, topology=topo)
    policy = PlacementPolicy()
    big, tiny = 1 << 16, 16
    assert policy.route_edge(ctx, 0, 1, big) == "peer"        # fat intra
    assert policy.route_edge(ctx, 0, 2, big) == "peer+int8"   # thin spine
    assert policy.route_edge(ctx, 0, 2, tiny) == "peer"       # scale overhead
    heft = HeftPlacement(default_task_s=5e-6, use_observed=False)
    assert heft.route_edge(ctx, 0, 2, big) == "peer+int8"


def test_heft_packs_hot_edges_intra_rack():
    """Two consumers of one big producer output on a 2x2 topology with a
    punishing spine: EFT parks the second consumer on the producer's rack
    peer, never across the spine."""
    topo = Topology.two_tier(2, 2, inter_bw_ratio=0.01)
    tr = PeerTransport(topology=topo)
    cost = CostModel(PAPER_ETHERNET)
    nbytes = 1 << 20
    ctx = PlacementContext(pool=None, cost=cost, D=4, peer=True,
                           transport=tr, topology=topo,
                           home={"prod": 1}, out_bytes={"prod": nbytes})
    heft = HeftPlacement(default_task_s=5e-3, use_observed=False)
    heft.begin(ctx)
    from repro.core import TaskNode
    placed = [heft.place(ctx, TaskNode(f"c{i}", "combine", ("prod",), None),
                         i, "t") for i in range(2)]
    assert placed[0] == 1                    # free edge: producer's device
    assert placed[1] == 0                    # rack peer, NOT 2/3 over spine
    assert set(placed) <= set(topo.members(0))


# ---------------------------------------------------------------------------
# runtime integration: modeled wire compression keeps results bit-identical
# ---------------------------------------------------------------------------
def _chain_tasks(B=64, length=4, seed=0):
    """A pinned chain that zig-zags the 2x2 rack boundary: p0@0 -> p1@1
    (intra) -> p2@2 (spine) -> p3@3 (intra) -> p4@0 (spine)."""
    rng = np.random.default_rng(seed)
    init = jnp.asarray(rng.standard_normal((B, B)), jnp.float32)
    sds = jax.ShapeDtypeStruct((B, B), jnp.float32)
    tasks = [DagTask("p0", "combine", (),
                     lambda dv: MapSpec(to={"x": init}, from_={"out": sds}),
                     device=0)]
    for w in range(1, length + 1):
        tasks.append(DagTask(
            f"p{w}", "combine2", (f"p{w-1}", "p0"),
            (lambda w=w: lambda dv: MapSpec(
                to={"x": dv[f"p{w-1}"], "y": dv["p0"]},
                from_={"out": sds}))(),
            device=w % 4))
    return tasks


def _run_chain(topology):
    table = KernelTable()
    table.register("combine", lambda x: {"out": x @ x * 1e-2 + 1.0})
    table.register("combine2", lambda x, y: {"out": x @ x * 1e-2 + y})
    rt = ClusterRuntime(RuntimeConfig(n_virtual=4, topology=topology),
                        table=table)
    try:
        res = rt.wavefront_offload(_chain_tasks(), nowait=True, peer=True,
                                   policy="round-robin")
        return {k: np.asarray(v) for k, v in res.items()}, rt.cost.summary()
    finally:
        rt.shutdown()


def test_compressed_edge_routing_is_bit_identical_and_accounted():
    """Round-robin drives the chain across the spine; edges big enough for
    the int8 wire route as "peer+int8" — modeled bytes shrink, cross-rack
    traffic is itemized, and the VALUES are bitwise those of the raw run
    (wire compression is accounting-only on dependency edges)."""
    topo = Topology.two_tier(2, 2, inter_bw_ratio=0.1)
    raw_vals, raw_stats = _run_chain(None)
    top_vals, top_stats = _run_chain(topo)
    assert raw_vals.keys() == top_vals.keys()
    for k in raw_vals:
        np.testing.assert_array_equal(raw_vals[k], top_vals[k]), k
    assert raw_stats["bytes_peer_cross_rack"] == 0       # no topology: n/a
    assert top_stats["bytes_peer_cross_rack"] > 0
    # the compressed wire moved fewer modeled peer bytes than raw routing
    assert top_stats["bytes_peer"] < raw_stats["bytes_peer"]


def test_runtime_rejects_topology_size_mismatch():
    with pytest.raises(ValueError, match="topology"):
        ClusterRuntime(RuntimeConfig(n_virtual=3,
                                     topology=Topology.two_tier(2, 2)),
                       table=KernelTable())
