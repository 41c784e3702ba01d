"""Peer transport layer (PR 4 tentpole): SEND/RECV rendezvous in the
dependency-aware stream, topology-agnostic collectives, per-link peer lanes
in the cost model, and deadlock-freedom / serial-equivalence properties."""
import concurrent.futures as _cf
import threading
import time

from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CostModel, DevicePool, HostFunnelTransport,
                        KernelTable, LinkModel, PeerTransport)


def _pool(n):
    table = KernelTable()
    table.register("triple", lambda a: {"a": a * 3.0 + 1.0})
    return DevicePool.virtual(n, table=table)


def _install(pool, d, value):
    value = jnp.asarray(value)
    h = pool.alloc(d, value.shape, value.dtype)
    pool.transfer_to(d, h, value)
    return h


# ---------------------------------------------------------------------------
# the primitive: peer_copy / sendrecv
# ---------------------------------------------------------------------------
def test_peer_copy_moves_value_without_funnel_bytes():
    pool = _pool(2)
    v = jnp.arange(16.0, dtype=jnp.float32)
    hs = _install(pool, 0, v)
    hd = pool.alloc(1, v.shape, v.dtype)
    before = (pool.cost.bytes_moved("to"), pool.cost.bytes_moved("from"))
    pool.peer_copy(0, hs, 1, hd)
    got = pool.transfer_from(1, hd)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(v))
    # the copy itself crossed zero host-NIC bytes; it is peer traffic
    after_to = pool.cost.bytes_moved("to")
    assert after_to == before[0]
    assert pool.cost.bytes_peer() == v.size * 4
    # and it is a real pair of stream commands on both devices
    ops0 = [c.op for c in pool.stream_traces[0]]
    ops1 = [c.op for c in pool.stream_traces[1]]
    assert "SEND" in ops0 and "RECV" in ops1
    pool.stop_all()


def test_peer_copy_orders_like_a_stream_writer():
    """RECV is a writer of the destination handle: a consumer EXEC issued
    after the copy must see the received value, and a SEND issued after a
    producer XFER_TO must carry the produced value — even with the issue
    happening while the source worker is stalled."""
    pool = _pool(2)
    v0 = jnp.zeros(8, jnp.float32)
    hs = _install(pool, 0, v0)
    hd = _install(pool, 1, jnp.full(8, -1.0, jnp.float32))
    gate = threading.Event()
    pool._submit(0, gate.wait)               # stall device 0's stream
    pool.transfer_to(0, hs, jnp.full(8, 7.0, jnp.float32))   # producer
    pool.peer_copy(0, hs, 1, hd)                              # SEND after it
    threading.Timer(0.2, gate.set).start()   # release mid-exec-wait
    # exec_kernel blocks until the chain produce -> SEND -> RECV -> EXEC ran
    out = pool.exec_kernel(1, "triple", buffers={"a": hd})
    np.testing.assert_allclose(np.asarray(out["a"]), 7.0 * 3.0 + 1.0)
    pool.stop_all()


def test_peer_copy_recv_failure_surfaces_at_destination_sync():
    pool = _pool(2)
    hs = _install(pool, 0, jnp.ones(4))
    hd = pool.alloc(1, (4,), jnp.float32)
    pool.free(1, hd)                          # RECV will write a dead handle
    pool.peer_copy(0, hs, 1, hd)
    with pytest.raises(KeyError, match="not live"):
        pool.sync(1)
    # the stash is cleared and the source side was unaffected
    pool.sync()
    pool.stop_all()


def test_ring_rendezvous_is_deadlock_free():
    """A full ring of peer copies (0→1→…→D-1→0) issued while EVERY worker is
    stalled, in an adversarial issue order, completes once released: RECV is
    gated on its SEND through the dependency graph, so no worker ever parks
    inside a rendezvous."""
    D = 4
    pool = _pool(D)
    src = [_install(pool, d, jnp.full(8, float(d), jnp.float32))
           for d in range(D)]
    dst = [pool.alloc(d, (8,), jnp.float32) for d in range(D)]
    gates = [threading.Event() for _ in range(D)]
    for d in range(D):
        pool._submit(d, gates[d].wait)
    # adversarial order: issue the ring backwards
    for d in reversed(range(D)):
        pool.peer_copy(d, src[d], (d + 1) % D, dst[(d + 1) % D])
    for g in reversed(gates):
        g.set()
    deadline = time.monotonic() + 20
    for d in range(D):
        got = pool.transfer_from(d, dst[d])
        np.testing.assert_allclose(np.asarray(got), float((d - 1) % D))
        assert time.monotonic() < deadline
    pool.stop_all()


# ---------------------------------------------------------------------------
# collectives: same algorithm over either topology
# ---------------------------------------------------------------------------
def _leaf_values(D, L=2, seed=0):
    rng = np.random.default_rng(seed)
    return [[jnp.asarray(rng.standard_normal((5, 3)), jnp.float32)
             for _ in range(L)] for _ in range(D)]


def _setup_collective(D, values):
    pool = _pool(D)
    handles = [[_install(pool, d, v) for v in values[d]] for d in range(D)]
    specs = [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in values[0]]
    return pool, handles, specs


@pytest.mark.parametrize("transport_cls", [PeerTransport, HostFunnelTransport])
def test_ring_allreduce_sums_on_every_device(transport_cls):
    D = 3
    values = _leaf_values(D)
    pool, handles, specs = _setup_collective(D, values)
    transport_cls().ring_allreduce(pool, handles, specs)
    want = [sum(np.asarray(values[d][j]) for d in range(D)) for j in range(2)]
    for d in range(D):
        for j in range(2):
            got = np.asarray(pool.transfer_from(d, handles[d][j]))
            np.testing.assert_allclose(got, want[j], rtol=1e-5, atol=1e-6)
    # scratch freed: only the 2 leaves per device stay live
    pool.sync()
    for d in range(D):
        assert len(pool.devices[d].store.live_handles()) == 2, d
    pool.stop_all()


def test_ring_allreduce_topologies_account_differently():
    """The SAME ring over the two transports: peer moves its bytes on links,
    the funnel pays every hop twice through the host NIC."""
    D, n = 3, 64
    values = [[jnp.full((n,), float(d + 1), jnp.float32)] for d in range(D)]

    def run(transport):
        pool, handles, specs = _setup_collective(D, values)
        transport.ring_allreduce(pool, handles, specs)
        pool.sync()
        s = pool.cost.summary()
        pool.stop_all()
        return s

    base_pool, _, _ = _setup_collective(D, values)   # setup-only baseline
    base = base_pool.cost.summary()
    base_pool.stop_all()
    peer = run(PeerTransport())
    funnel = run(HostFunnelTransport())
    ring_bytes = D * (D - 1) * n * 4
    assert peer["bytes_peer"] == ring_bytes
    assert peer["bytes_from"] == base["bytes_from"]          # zero extra funnel
    assert funnel["bytes_peer"] == 0
    # every ring message = one fetch + one re-send through the host
    assert funnel["bytes_from"] - base["bytes_from"] == ring_bytes
    assert funnel["bytes_to"] - base["bytes_to"] == ring_bytes


def test_broadcast_and_gather():
    D = 4
    values = _leaf_values(D, seed=3)
    pool, handles, specs = _setup_collective(D, values)
    t = PeerTransport()
    scratch = t.gather(pool, handles, specs, root=2)
    for d, hs in scratch.items():
        for j, h in enumerate(hs):
            np.testing.assert_array_equal(
                np.asarray(pool.transfer_from(2, h)), np.asarray(values[d][j]))
        for h in hs:
            pool.free(2, h)
    t.broadcast(pool, handles, specs, root=2)
    for d in range(D):
        for j in range(2):
            np.testing.assert_array_equal(
                np.asarray(pool.transfer_from(d, handles[d][j])),
                np.asarray(values[2][j]))
    pool.stop_all()


@pytest.mark.parametrize("root", [0, 2])
def test_allreduce_mean_bit_identical_to_host_order(root):
    """The reduction accumulates in ascending DEVICE order — the exact
    association of the host-mediated ``sum(views) / D`` — for any root,
    not just root 0."""
    D = 4
    values = _leaf_values(D, seed=7)
    pool, handles, specs = _setup_collective(D, values)
    PeerTransport().allreduce_mean(pool, handles, specs, root=root)
    want = [np.asarray(sum(v[j] for v in values) / D) for j in range(2)]
    for d in range(D):
        for j in range(2):
            got = np.asarray(pool.transfer_from(d, handles[d][j]))
            np.testing.assert_array_equal(got, want[j])
    pool.sync()
    for d in range(D):                       # gather scratch freed
        assert len(pool.devices[d].store.live_handles()) == 2, d
    pool.stop_all()


@pytest.mark.parametrize("root", [0, 1])
def test_allreduce_mean_failure_leaves_live_buffers_intact(root):
    """A mid-collective failure must not corrupt any device's live buffer:
    partial sums land only in scratch, the root's buffer is written once by
    the final divide — all-or-nothing, like the host-mediated path."""
    from repro.core.transport import DIV_KERNEL

    D = 3
    values = _leaf_values(D, seed=11)
    pool, handles, specs = _setup_collective(D, values)
    # pre-register a failing divide: _ensure_kernels keeps it (same wire
    # name).  The divide runs AFTER every reduction add succeeded, so an
    # in-place reduction would already have overwritten the root's buffer
    # with the partial sum by the time this fires.
    pool.table.register(DIV_KERNEL, lambda a, s: (_ for _ in ()).throw(
        ValueError("injected reduce failure")))
    with pytest.raises(ValueError, match="injected reduce"):
        PeerTransport().allreduce_mean(pool, handles, specs, root=root)
    pool.sync()
    for d in range(D):
        for j in range(2):
            np.testing.assert_array_equal(
                np.asarray(pool.transfer_from(d, handles[d][j])),
                np.asarray(values[d][j])), (d, j)
        # the gather scratch was freed on the failure path too
        assert len(pool.devices[d].store.live_handles()) == 2, d
    pool.stop_all()


# ---------------------------------------------------------------------------
# cost model: peer lanes
# ---------------------------------------------------------------------------
def test_peer_lanes_timed_not_adjusted():
    link = LinkModel("unit", bandwidth_Bps=1e6, latency_s=0.0)
    cm = CostModel(link)
    MB = int(1e6)
    cm.record_compute(0, 1.0)                 # dev0 [0, 1]
    cm.record_peer(0, 1, MB)                  # p0>1 [1, 2] (after dev0 compute)
    cm.record_peer(1, 2, MB)                  # p1>2 [0, 1] (dev1 full duplex:
                                              #   sending ∥ receiving)
    cm.record_peer(0, 1, MB)                  # p0>1 [2, 3] (link + tx0 + rx1
                                              #   all busy till 2)
    cm.record_compute(1, 0.5)                 # dev1 [3, 3.5]: waits for its
                                              #   in-flight peer payloads
    assert cm.bytes_peer() == 3 * MB
    assert cm.bytes_moved() == 0              # nothing on the host NIC
    assert cm.comm_time() == 0.0
    # per-link serialization, links concurrent: p0>1 carries 2 MB
    assert cm.peer_time() == pytest.approx(2.0)
    spans = {(s.lane, s.start, s.end) for s in cm.timeline()}
    assert ("p0>1", 1.0, 2.0) in spans
    assert ("p1>2", 0.0, 1.0) in spans
    assert ("p0>1", 2.0, 3.0) in spans
    assert ("dev1", 3.0, 3.5) in spans
    assert cm.makespan(overlap=True) == pytest.approx(3.5)
    # paper-model serialization: max per-device compute + peer link time
    assert cm.makespan() == pytest.approx(1.0 + 2.0)


def test_ring_round_is_concurrent_across_links():
    """One ring round over D devices costs one link's time in the overlap
    timeline, not D: links are distinct lanes and endpoints are full
    duplex — the 'concurrent links' the peer_time() model promises, so
    makespan(overlap=True) never exceeds the serialized makespan()."""
    link = LinkModel("unit", bandwidth_Bps=1e6, latency_s=0.0)
    cm = CostModel(link)
    D, MB = 4, int(1e6)
    for d in range(D):                        # the round: 0>1, 1>2, 2>3, 3>0
        cm.record_peer(d, (d + 1) % D, MB)
    spans = cm.timeline()
    assert all(s.start == 0.0 and s.end == 1.0 for s in spans), spans
    assert cm.makespan(overlap=True) == pytest.approx(1.0)
    assert cm.peer_time() == pytest.approx(1.0)
    assert cm.makespan(overlap=True) <= cm.makespan()


def test_peer_link_model_override():
    fast = LinkModel("ici", bandwidth_Bps=1e9, latency_s=0.0)
    cm = CostModel(LinkModel("slow", bandwidth_Bps=1e6, latency_s=0.0),
                   peer_link=fast)
    cm.record_peer(0, 1, int(1e6))
    assert cm.peer_time() == pytest.approx(1e6 / 1e9)


# ---------------------------------------------------------------------------
# property: interleaved SEND/RECV x EXEC/XFER == serial dispatch
# ---------------------------------------------------------------------------
@settings(max_examples=15, deadline=None)
@given(st.lists(st.tuples(st.sampled_from(["xfer", "exec", "peer01", "peer10"]),
                          st.integers(0, 99)),
                min_size=1, max_size=12),
       st.integers(0, 3))
def test_random_interleavings_match_serial(ops, stall):
    """Random programs over two devices sharing one logical buffer pair:
    host writes, on-device EXECs, and peer copies in both directions.  The
    async dependency-aware dispatch (with a stalled worker forcing maximal
    issue-ahead) must leave both buffers bit-identical to a serial replay."""
    # serial reference on the host
    ref = {0: np.zeros(4, np.float32), 1: np.zeros(4, np.float32)}
    for kind, val in ops:
        if kind == "xfer":
            ref[val % 2] = np.full(4, float(val), np.float32)
        elif kind == "exec":
            ref[val % 2] = ref[val % 2] * 3.0 + 1.0
        elif kind == "peer01":
            ref[1] = ref[0].copy()
        else:
            ref[0] = ref[1].copy()

    pool = _pool(2)
    h = {d: _install(pool, d, jnp.zeros(4, jnp.float32)) for d in (0, 1)}
    gate = threading.Event()
    if stall < 2:                    # sometimes stall one worker during issue
        pool._submit(stall, gate.wait)
        # a synchronous EXEC on the stalled device must still make progress:
        # release the gate shortly, keeping issue-ahead pressure until then
        threading.Timer(0.1, gate.set).start()
    for kind, val in ops:
        if kind == "xfer":
            pool.transfer_to(val % 2, h[val % 2],
                             jnp.full(4, float(val), jnp.float32))
        elif kind == "exec":
            d = val % 2
            out = pool.exec_kernel(d, "triple", buffers={"a": h[d]})
            pool.transfer_to_writeback(d, h[d], out["a"])
        elif kind == "peer01":
            pool.peer_copy(0, h[0], 1, h[1])
        else:
            pool.peer_copy(1, h[1], 0, h[0])
    gate.set()
    pool.sync()
    for d in (0, 1):
        got = np.asarray(pool.transfer_from(d, h[d]))
        np.testing.assert_array_equal(got, ref[d]), (d, ops)
    pool.stop_all()
