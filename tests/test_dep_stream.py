"""Dependency-aware device streams (PR 3 tentpole): per-handle command
ordering, nowait x resident wavefronts, device-resident optimizer steps,
and the data-environment failure-path fixes."""
import concurrent.futures as _cf
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ClusterRuntime, DagTask, DevicePool, KernelTable,
                        MapSpec, RuntimeConfig, TargetExecutor,
                        wavefront_offload)
from repro.core.device import NodeDevice, StragglerTimeout
from repro.optim import AdamW, AdamWConfig


def _make_ex(n_dev=2):
    table = KernelTable()

    @table.kernel("axpb")
    def axpb(a, b):
        return {"out": a + b}

    @table.kernel("gen")
    def gen(x):
        return {"out": x @ x}

    @table.kernel("consume")
    def consume(lu, a):
        return {"out": lu + 2 * a}

    @table.kernel("boomk")
    def boomk(x):
        raise ValueError("injected kernel failure")

    @table.kernel("ident")
    def ident(x):
        return {"out": x}

    @table.kernel("bump")
    def bump(a):
        return {"a": a + 1}

    @table.kernel("use_global")
    def use_global(g, x):
        return {"out": g + x}

    pool = DevicePool.virtual(n_dev, table=table)
    return pool, TargetExecutor(pool)


def _fanout_dag(mat, ams):
    """One producer, N consumers of its output — sparselu's pivot fan-out."""
    sds = jax.ShapeDtypeStruct(mat.shape, mat.dtype)
    tasks = [DagTask("p", "gen", (),
                     lambda deps: MapSpec(to={"x": mat}, from_={"out": sds}))]
    for i, a in enumerate(ams):
        tasks.append(DagTask(
            f"c{i}", "consume", ("p",),
            (lambda a=a: lambda deps: MapSpec(
                to={"lu": deps["p"], "a": a}, from_={"out": sds}))()))
    return tasks


# ---------------------------------------------------------------------------
# nowait x resident: identical results, strictly fewer to-bytes
# ---------------------------------------------------------------------------
def _run_wavefront(nowait, resident, n_dev=2):
    rng = np.random.default_rng(0)
    mat = jnp.asarray(rng.standard_normal((24, 24)), jnp.float32)
    ams = [jnp.asarray(rng.standard_normal((24, 24)), jnp.float32)
           for _ in range(6)]
    pool, ex = _make_ex(n_dev)
    res = wavefront_offload(ex, _fanout_dag(mat, ams),
                            nowait=nowait, resident=resident)
    to_bytes = pool.cost.bytes_moved("to")
    # wave-resident exit path: every per-wave pin is released
    for d in range(n_dev):
        assert len(pool.present[d]) == 0, pool.present[d].names()
    pool.sync()
    for d in range(n_dev):
        assert pool.devices[d].store.live_handles() == [], d
        assert pool.mirrors[d].live_handles() == [], d
    return res, to_bytes


def test_nowait_resident_no_longer_raises_and_matches_serial():
    r_serial, _ = _run_wavefront(nowait=False, resident=False)
    r_conc, _ = _run_wavefront(nowait=True, resident=True)
    assert r_serial.keys() == r_conc.keys()
    for k in r_serial:
        np.testing.assert_allclose(r_conc[k], r_serial[k], rtol=1e-6)


def test_nowait_resident_moves_fewer_to_bytes():
    _, plain = _run_wavefront(nowait=True, resident=False)
    _, res = _run_wavefront(nowait=True, resident=True)
    assert res < plain, (res, plain)     # shared pivot crossed once per device


def test_mid_wave_failure_releases_every_pin():
    rng = np.random.default_rng(1)
    mat = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
    sds = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    tasks = _fanout_dag(mat, [mat + 1, mat + 2, mat + 3])
    tasks.append(DagTask("bad", "boomk", ("p",),
                         lambda deps: MapSpec(to={"x": deps["p"]},
                                              from_={"out": sds})))
    pool, ex = _make_ex(2)
    with pytest.raises(ValueError, match="injected"):
        wavefront_offload(ex, tasks, nowait=True, resident=True)
    for d in range(2):
        assert len(pool.present[d]) == 0
    pool.sync()
    for d in range(2):
        assert pool.devices[d].store.live_handles() == [], d


def test_mid_dispatch_failure_joins_launched_regions_and_releases_pins():
    """A later task's make_maps raising mid-wave must not leave the already
    launched regions running unjoined or their pins held."""
    rng = np.random.default_rng(2)
    mat = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
    sds = jax.ShapeDtypeStruct((8, 8), jnp.float32)

    def bad_maps(deps):
        raise RuntimeError("injected make_maps failure")

    tasks = [DagTask("ok", "gen", (),
                     lambda deps: MapSpec(to={"x": mat}, from_={"out": sds})),
             DagTask("bad", "gen", (), bad_maps)]
    pool, ex = _make_ex(2)
    with pytest.raises(RuntimeError, match="injected make_maps"):
        wavefront_offload(ex, tasks, nowait=True, resident=True)
    with ex._inflight_lock:
        assert ex._inflight == []        # the launched region was retired
    for d in range(2):
        assert len(pool.present[d]) == 0
    pool.sync()
    for d in range(2):
        assert pool.devices[d].store.live_handles() == [], d


def test_same_name_in_two_clauses_reuses_one_ticket():
    """present + tofrom naming the same resident buffer must not leak an
    open reader ticket (a leaked one wedges the writeback forever)."""
    pool, ex = _make_ex(1)
    v = jnp.full(4, 2.0, jnp.float32)
    ex.ensure_resident(0, a=v)
    out = ex.target("bump", 0, MapSpec(present=("a",), tofrom={"a": v}))
    np.testing.assert_allclose(out["a"], 3.0)
    pool.sync(0)
    # every registered reader settled: no open ticket survived the region
    assert all(f.done() for futs in pool._readers[0].values() for f in futs)
    ex.exit_data(0, "a")
    pool.sync()
    assert pool.devices[0].store.live_handles() == []


# ---------------------------------------------------------------------------
# producer/consumer ordering: two nowait regions share one resident name
# ---------------------------------------------------------------------------
def _wait_for_exec(pool, tag, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        with pool._trace_lock:
            if any(c.op == "EXEC" and c.tag == tag for c in pool.trace):
                return
        time.sleep(0.005)
    raise AssertionError(f"EXEC {tag!r} never issued")


def test_concurrent_regions_on_shared_resident_name_are_handle_ordered():
    """Region A matches version 1 of a resident buffer; a refresh to
    version 2 and region B are issued while A is still in flight.  The
    stream must order A's EXEC before the refresh XFER_TO before B's EXEC —
    per-handle producer/consumer ordering, not whole-queue serialization —
    and each region must compute with the version it matched."""
    pool, ex = _make_ex(1)
    v1 = jnp.full(8, 1.0, jnp.float32)
    ex.ensure_resident(0, a=v1)
    handle = pool.present[0].get("a").handles[0]
    gate = threading.Event()
    pool._submit(0, gate.wait)           # stall execution, not issue
    sds = jax.ShapeDtypeStruct((8,), jnp.float32)
    fut_a = ex.target("axpb", 0, MapSpec(to={"a": v1, "b": jnp.zeros(8)},
                                         from_={"out": sds}),
                      nowait=True, tag="regA")
    _wait_for_exec(pool, "regA")         # A matched v1 and issued its EXEC
    v2 = jnp.full(8, 5.0, jnp.float32)
    ex.ensure_resident(0, a=v2)          # refresh: a writer of A's handle
    fut_b = ex.target("axpb", 0, MapSpec(to={"a": v2, "b": jnp.zeros(8)},
                                         from_={"out": sds}),
                      nowait=True, tag="regB")
    _wait_for_exec(pool, "regB")
    gate.set()
    np.testing.assert_allclose(fut_a.result()["out"], 1.0)   # matched v1
    np.testing.assert_allclose(fut_b.result()["out"], 5.0)   # matched v2
    ex.exit_data(0, "a")
    pool.sync()
    stream = list(pool.stream_traces[0])
    exec_a = next(i for i, c in enumerate(stream)
                  if c.op == "EXEC" and c.tag == "regA")
    exec_b = next(i for i, c in enumerate(stream)
                  if c.op == "EXEC" and c.tag == "regB")
    refresh = [i for i, c in enumerate(stream)
               if c.op == "XFER_TO" and c.handle == handle
               and c.tag == "resident:a"]
    assert len(refresh) == 2             # initial enter + the v2 refresh
    assert exec_a < refresh[1] < exec_b, (exec_a, refresh, exec_b)
    assert handle in stream[exec_a].reads and handle in stream[exec_b].reads


# ---------------------------------------------------------------------------
# drain: an early failure must not retire still-running futures
# ---------------------------------------------------------------------------
def test_drain_waits_for_all_futures_to_settle():
    pool, ex = _make_ex(2)
    sds = jax.ShapeDtypeStruct((4,), jnp.float32)
    gate = threading.Event()
    pool._submit(1, gate.wait)           # hold device 1's stream
    slow = ex.target("ident", 1, MapSpec(to={"x": jnp.ones(4)},
                                         from_={"out": sds}), nowait=True)
    fast = ex.target("boomk", 0, MapSpec(to={"x": jnp.ones(4)},
                                         from_={"out": sds}), nowait=True)
    _cf.wait([fast._fut])                # the failure has settled
    seen = {}

    def run_drain():
        try:
            ex.drain([fast, slow])
        except ValueError as e:
            seen["error"] = e
            seen["slow_settled"] = slow.done()

    t = threading.Thread(target=run_drain)
    t.start()
    t.join(0.5)
    assert t.is_alive()                  # drain holds: slow has not settled
    gate.set()
    t.join(10)
    assert not t.is_alive()
    assert "injected" in str(seen["error"])
    assert seen["slow_settled"] is True  # retired only once everything settled
    with ex._inflight_lock:
        assert ex._inflight == []


# ---------------------------------------------------------------------------
# data-environment failure paths
# ---------------------------------------------------------------------------
def test_enter_data_partial_failure_frees_allocations():
    """A later leaf failing mid-enter must free the handles already made."""
    pool, ex = _make_ex(1)
    with pytest.raises(TypeError):
        ex.enter_data(0, a={"x": jnp.ones(4), "y": "not-an-array"})
    assert "a" not in pool.present[0]
    pool.sync(0)
    assert pool.devices[0].store.live_handles() == []
    assert pool.mirrors[0].live_handles() == []


def test_install_global_after_ensure_resident():
    """First-fit handles diverge across devices once a buffer is pinned on
    one of them; install_global must track per-device handles, not assert."""
    pool, ex = _make_ex(3)
    ex.ensure_resident(0, keep=jnp.ones(4))          # device 0's slot 0 taken
    pool.install_global("g", jnp.full(8, 2.0, jnp.float32))
    assert pool.globals["g"][0] != pool.globals["g"][1]
    sds = jax.ShapeDtypeStruct((8,), jnp.float32)
    for d in range(3):                               # lookup works everywhere
        out = ex.target("use_global", d, MapSpec(
            to={"x": jnp.ones(8)}, from_={"out": sds}, use_globals=("g",)))
        np.testing.assert_allclose(out["out"], 3.0)
    # re-install stays idempotent with divergent handles
    pool.install_global("g", jnp.full(8, 9.0, jnp.float32))
    out = ex.target("use_global", 1, MapSpec(
        to={"x": jnp.ones(8)}, from_={"out": sds}, use_globals=("g",)))
    np.testing.assert_allclose(out["out"], 10.0)
    ex.exit_data(0, "keep")
    pool.sync()
    for d in range(3):                               # mirror/store agreement
        assert (sorted(pool.mirrors[d].live_handles())
                == sorted(pool.devices[d].store.live_handles())), d


# ---------------------------------------------------------------------------
# present / device_out maps
# ---------------------------------------------------------------------------
def test_present_map_requires_residency():
    pool, ex = _make_ex(1)
    with pytest.raises(KeyError, match="not resident"):
        ex.target("bump", 0, MapSpec(present=("a",), device_out=("a",)))


def test_device_out_keeps_result_on_device():
    pool, ex = _make_ex(1)
    v0 = jnp.zeros(8, jnp.float32)
    ex.ensure_resident(0, a=v0)
    before = (pool.cost.bytes_moved("to"), pool.cost.bytes_moved("from"))
    for _ in range(3):
        ex.target("bump", 0, MapSpec(present=("a",), device_out=("a",)))
    # three on-device updates moved zero bytes either way
    assert (pool.cost.bytes_moved("to"), pool.cost.bytes_moved("from")) == before
    ent = pool.present[0].get("a")
    assert ent.device_ahead and ent.refcount == 1
    # a device-ahead entry must not serve a host-value match
    sds = jax.ShapeDtypeStruct((8,), jnp.float32)
    out = ex.target("axpb", 0, MapSpec(to={"a": v0, "b": jnp.zeros(8)},
                                       from_={"out": sds}))
    np.testing.assert_allclose(out["out"], 0.0)      # host value, not device's
    fetched = ex.fetch_resident(0, "a")
    np.testing.assert_allclose(fetched, 3.0)
    assert not pool.present[0].get("a").device_ahead
    ex.exit_data(0, "a")
    pool.sync()
    assert pool.devices[0].store.live_handles() == []


# ---------------------------------------------------------------------------
# device-resident optimizer: data_parallel_step
# ---------------------------------------------------------------------------
def _dp_table():
    table = KernelTable()

    @table.kernel("mse_grads")
    def mse_grads(params, batch):
        def loss(p):
            pred = batch["x"] @ p["w"] + p["b"]
            return jnp.mean((pred - batch["y"]) ** 2)
        return {"grads": jax.grad(loss)(params)}

    return table


def test_data_parallel_step_cuts_from_traffic_3x_with_same_numerics():
    """Acceptance: 8 local steps with sync_every=4 fetch parameters twice
    instead of gradients eight times (4x fewer from-bytes) and, with every
    device on the same batch, land on the same parameters as per-step
    data_parallel_grads + a host AdamW update."""
    d, steps, n_dev = 32, 8, 2
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.standard_normal((d, d)), jnp.float32),
              "b": jnp.zeros((d,), jnp.float32)}
    batch = {"x": jnp.asarray(rng.standard_normal((4, d)), jnp.float32),
             "y": jnp.asarray(rng.standard_normal((4, d)), jnp.float32)}

    rt = ClusterRuntime(RuntimeConfig(n_virtual=n_dev), table=_dp_table())
    dps_params = None
    for _ in range(steps):
        dps_params = rt.data_parallel_step("mse_grads", params,
                                           [batch] * n_dev, sync_every=4)
    dps_from = rt.cost.bytes_moved("from")
    rt.shutdown()

    rt2 = ClusterRuntime(RuntimeConfig(n_virtual=n_dev), table=_dp_table())
    opt = AdamW(AdamWConfig())
    state, host_params = opt.init(params), params
    for _ in range(steps):
        g = rt2.data_parallel_grads("mse_grads", host_params, [batch] * n_dev)
        host_params, state, _ = opt.update(g, state, host_params)
    base_from = rt2.cost.bytes_moved("from")
    rt2.shutdown()

    assert base_from >= 3 * dps_from, (base_from, dps_from)
    np.testing.assert_allclose(np.asarray(dps_params["w"]),
                               np.asarray(host_params["w"]),
                               rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dps_params["b"]),
                               np.asarray(host_params["b"]),
                               rtol=2e-4, atol=1e-5)


def test_data_parallel_step_and_grads_namespaces_do_not_collide():
    """The optimizer's resident state lives under _dps_-prefixed names, so
    interleaving data_parallel_grads (which pins its own "params") must
    neither clobber the device-advanced parameters nor free them."""
    d = 16
    rt = ClusterRuntime(RuntimeConfig(n_virtual=2), table=_dp_table())
    params = {"w": jnp.eye(d), "b": jnp.zeros((d,))}
    batches = [{"x": jnp.ones((2, d)), "y": jnp.zeros((2, d))}] * 2
    rt.data_parallel_step("mse_grads", params, batches, sync_every=2)
    rt.data_parallel_step("mse_grads", params, batches, sync_every=2)
    synced = rt._dps["host_params"]
    rt.data_parallel_grads("mse_grads", params, batches)   # pins "params"
    after = rt.data_parallel_step("mse_grads", params, batches, sync_every=2)
    # the interleaved grads call did not reset the optimizer's trajectory
    assert rt._dps["step"] == 3
    assert after is synced                     # no sync on step 3
    np.testing.assert_allclose(rt.ex.fetch_resident(0, "_dps_count"), 3.0)
    rt.shutdown()


def test_data_parallel_step_interleaves_with_handle_agreement():
    """Local steps + syncs leave mirror and store agreeing on every device."""
    rt = ClusterRuntime(RuntimeConfig(n_virtual=3), table=_dp_table())
    d = 16
    params = {"w": jnp.eye(d), "b": jnp.zeros((d,))}
    batches = [{"x": jnp.ones((2, d)), "y": jnp.full((2, d), float(i))}
               for i in range(3)]
    for _ in range(5):
        rt.data_parallel_step("mse_grads", params, batches, sync_every=2)
    rt.data_parallel_sync()
    rt.pool.sync()
    for dev in range(3):
        assert (sorted(rt.pool.mirrors[dev].live_handles())
                == sorted(rt.pool.devices[dev].store.live_handles())), dev
    rt.shutdown()


# ---------------------------------------------------------------------------
# pipelined EXECs: the worker dispatches, a completion thread waits
# ---------------------------------------------------------------------------
N_SPIN = 512


def _spin(x):
    """About a quarter of a second of CPU work for one small output."""
    return {"out": jax.lax.fori_loop(0, 100, lambda i, a: jnp.tanh(a @ a), x)}


def _spin_table():
    table = KernelTable()
    table.register("spin", _spin)
    table.register("mix", lambda a, b: {"out": a + b})
    return table


def _spin_input(v):
    return jnp.full((N_SPIN, N_SPIN), v / N_SPIN, jnp.float32)


def _wait_for(cond, timeout=30.0):
    end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < end, "timed out"
        time.sleep(0.005)


def _log_device(dev, log):
    """Append ("exec", tag) to ``log`` as ``dev`` starts each EXEC's
    dispatch and ("install", handle) as a write-back installs a result."""
    execute, install = dev.execute, dev.store.install

    def logged_execute(cmd, table, payload=None):
        if cmd.op == "EXEC":
            log.append(("exec", cmd.tag, time.perf_counter()))
        return execute(cmd, table, payload)

    def logged_install(handle, value):
        log.append(("install", handle, time.perf_counter()))
        return install(handle, value)

    dev.execute, dev.store.install = logged_execute, logged_install


def test_independent_execs_on_one_device_overlap():
    """The second of two queued EXECs is dispatched while the first's
    kernel runs, its record counts the first in flight, and the results
    are the serial path's."""
    pool = DevicePool.virtual(1, table=_spin_table())
    handles = []
    for v in (1.0, 2.0):
        h = pool.alloc(0, (N_SPIN, N_SPIN), jnp.float32)
        pool.transfer_to(0, h, _spin_input(v))
        handles.append(h)
    serial = [pool.exec_kernel(0, "spin", buffers={"x": h}, tag=f"s{i}")
              for i, h in enumerate(handles)]
    log = []
    _log_device(pool.devices[0], log)
    gate = threading.Event()
    pool._submit_async(0, gate.wait)             # holds the worker
    with _cf.ThreadPoolExecutor(2) as tp:
        futs = [tp.submit(pool.exec_kernel, 0, "spin", buffers={"x": h},
                          tag=f"k{i}") for i, h in enumerate(handles)]
        _wait_for(lambda: pool._queues[0].qsize() == 2)
        gate.set()
        outs = [f.result() for f in futs]
    rec = {r.tag: r for r in pool.cost.compute}
    assert rec["s0"].inflight == rec["s1"].inflight == 0
    (_, first, t_first), (_, second, t_second) = log
    assert rec[first].inflight == 0 and rec[second].inflight == 1
    # dispatched well before the first kernel completed
    assert t_second - t_first < rec[first].sync_s / 2
    for out, ref in zip(outs, serial):
        np.testing.assert_array_equal(out["out"], ref["out"])
    pool.stop_all()


def test_a_device_out_consumer_runs_after_its_producers_writeback():
    """Peer mode keeps each output on the device (a ``device_out`` map);
    the consumer's EXEC is dispatched only after both producers' results
    were written back, and computes on them."""
    rt = ClusterRuntime(RuntimeConfig(n_virtual=1, comm_mode="direct"),
                        table=_spin_table())
    x1, x2 = _spin_input(1.0), _spin_input(-0.5)
    sds = jax.ShapeDtypeStruct((N_SPIN, N_SPIN), jnp.float32)
    tasks = [
        DagTask("p1", "spin", (), lambda r: MapSpec(to={"x": x1},
                                                    from_={"out": sds})),
        DagTask("p2", "spin", (), lambda r: MapSpec(to={"x": x2},
                                                    from_={"out": sds})),
        DagTask("c", "mix", ("p1", "p2"),
                lambda r: MapSpec(to={"a": r["p1"], "b": r["p2"]},
                                  from_={"out": sds})),
    ]
    rt.wavefront_offload(tasks, nowait=True, peer=True, tag="warm")
    log = []
    _log_device(rt.pool.devices[0], log)
    res = rt.wavefront_offload(tasks, nowait=True, peer=True, tag="t")
    rt.shutdown()
    what = [(k, str(v).rsplit(":", 1)[-1]) for k, v, _ in log]
    c_at = what.index(("exec", "c"))
    last_p = max(what.index(("exec", "p1")), what.index(("exec", "p2")))
    assert sum(k == "install" for k, _ in what[last_p:c_at]) >= 2
    spin = jax.jit(_spin)
    ref = np.asarray(spin(x1)["out"]) + np.asarray(spin(x2)["out"])
    np.testing.assert_allclose(res["c"], ref, rtol=1e-6, atol=1e-6)


def test_a_kernel_outlasting_the_deadline_raises_though_its_dispatch_returned():
    """The pool deadline covers the kernel's run, not only its dispatch."""
    pool = DevicePool.virtual(1, table=_spin_table())
    h = pool.alloc(0, (N_SPIN, N_SPIN), jnp.float32)
    pool.transfer_to(0, h, _spin_input(1.0))
    for _ in range(2):                           # compile, then time
        pool.exec_kernel(0, "spin", buffers={"x": h})
    pool.deadline_s = pool.cost.compute[-1].seconds / 4
    with pytest.raises(StragglerTimeout):
        pool.exec_kernel(0, "spin", buffers={"x": h})
    done = pool._completions[0]
    assert done.dispatched == 3                  # the worker had let it go
    assert pool.straggler_timeouts == {"EXEC": 1}
    pool.sync()                                  # settles, harmlessly, later
    assert done.inflight == 0 and len(pool.cost.compute) == 2
    pool.stop_all()


class _FailsOnCompletion:
    """A kernel output whose run fails only when waited for."""

    def block_until_ready(self):
        raise RuntimeError("kernel failed on the chip")


class _FaultyChipDevice(NodeDevice):
    def execute(self, cmd, table, payload=None):
        out = super().execute(cmd, table, payload)
        if cmd.op == "EXEC" and cmd.tag == "bad":
            return {"out": _FailsOnCompletion()}
        return out


def test_a_failure_seen_on_completion_reaches_the_issuer():
    """A kernel that fails after its dispatch returned raises from the
    completion thread into its EXEC; the device serves on."""
    dev = _FaultyChipDevice(0, jax_device=jax.devices()[0])
    pool = DevicePool([dev], table=_spin_table())
    h = pool.alloc(0, (4, 4), jnp.float32)
    pool.transfer_to(0, h, np.ones((4, 4), np.float32))
    with pytest.raises(RuntimeError, match="failed on the chip"):
        pool.exec_kernel(0, "mix", buffers={"a": h, "b": h}, tag="bad")
    out = pool.exec_kernel(0, "mix", buffers={"a": h, "b": h})
    np.testing.assert_array_equal(out["out"], np.full((4, 4), 2.0))
    done = pool._completions[0]
    assert done.dispatched == done.completed == 2
    assert [r.tag for r in pool.cost.compute] == ["mix"]
    pool.stop_all()


def test_stop_all_waits_for_inflight_completions_and_ends_every_thread():
    pool = DevicePool.virtual(1, table=_spin_table())
    added = pool.add_device()
    handles = {}
    for d in (0, added):
        handles[d] = pool.alloc(d, (N_SPIN, N_SPIN), jnp.float32)
        pool.transfer_to(d, handles[d], _spin_input(1.0))
        pool.exec_kernel(d, "spin", buffers={"x": handles[d]})   # compiles
    with _cf.ThreadPoolExecutor(2) as tp:
        futs = [tp.submit(pool.exec_kernel, d, "spin",
                          buffers={"x": handles[d]}) for d in (0, added)]
        _wait_for(lambda: all(pool._completions[d].dispatched == 2
                              for d in (0, added)))
        pool.stop_all()
        assert all(f.done() for d in (0, added)
                   for f in pool._outstanding[d])
        outs = [f.result() for f in futs]
    np.testing.assert_array_equal(outs[0]["out"], outs[1]["out"])
    threads = pool._workers + [c.thread for c in pool._completions]
    assert len(threads) == 4 and not any(t.is_alive() for t in threads)
    with pytest.raises(RuntimeError, match="stopped"):
        pool.exec_kernel(0, "spin", buffers={"x": handles[0]})
