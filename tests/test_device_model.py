"""Unit + property tests for the paper's core mechanisms (§4):
kernel table, mediary addresses, map semantics, command protocol."""
import gc
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (DevicePool, HostMirror, KernelTable, MapSpec,
                        MediaryStore, TargetExecutor, sec)
from repro.core.device import Command
from repro.core.mediary import _Calloc


# ---------------------------------------------------------------------------
# kernel table (paper §4.1)
# ---------------------------------------------------------------------------
def test_kernel_table_stable_indices():
    """Same registration order → same indices + fingerprint on every 'node'."""
    def build():
        t = KernelTable()
        t.register("a", lambda x: x)
        t.register("b", lambda x: x + 1)
        t.register("c", lambda x: x * 2)
        return t

    host, dev = build(), build()
    for name in ("a", "b", "c"):
        assert host.index_of(name) == dev.index_of(name)
    assert host.fingerprint() == dev.fingerprint()
    # order change ⇒ fingerprint mismatch (the desync the paper must avoid)
    t2 = KernelTable()
    t2.register("b", lambda x: x)
    t2.register("a", lambda x: x)
    t2.register("c", lambda x: x)
    assert t2.fingerprint() != host.fingerprint()


def test_kernel_table_duplicate_rejected():
    t = KernelTable()
    t.register("k", lambda x: x)
    with pytest.raises(ValueError):
        t.register("k", lambda x: x)


def test_kernel_table_switch_dispatch():
    """lax.switch dispatch: the device-side command loop as traced control."""
    t = KernelTable()
    t.register("add1", lambda x: x + 1, signature="unary")
    t.register("dbl", lambda x: x * 2, signature="unary")
    t.register("other", lambda x, y: x + y, signature="binary")
    dispatch = t.switch_dispatch("unary")
    x = jnp.arange(4.0)
    np.testing.assert_allclose(
        jax.jit(dispatch)(t.class_index_of("add1"), x), x + 1)
    np.testing.assert_allclose(
        jax.jit(dispatch)(t.class_index_of("dbl"), x), x * 2)


# ---------------------------------------------------------------------------
# mediary addresses (paper §4.2)
# ---------------------------------------------------------------------------
def test_mediary_first_fit_reuse():
    store = MediaryStore()
    h0 = store.alloc((4,), jnp.float32)
    h1 = store.alloc((4,), jnp.float32)
    assert (h0, h1) == (0, 1)
    store.free(h0)
    assert store.alloc((2,), jnp.int32) == 0     # first-fit reuses slot 0
    with pytest.raises(KeyError):
        store.free(7)


def test_mediary_alloc_is_zeroed():
    """OMPi uses calloc(); ALLOC'd buffers must read as zeros."""
    store = MediaryStore()
    h = store.alloc((3, 2), jnp.float32)
    np.testing.assert_array_equal(store.read(h), np.zeros((3, 2)))


@settings(max_examples=50, deadline=None)
@given(st.lists(st.one_of(
    st.tuples(st.just("alloc"), st.integers(1, 8)),
    st.tuples(st.just("free"), st.integers(0, 30))), max_size=40))
def test_mirror_and_store_handles_always_agree(ops):
    """The paper's no-round-trip optimization: host mirror predicts the
    device's next handle for ANY alloc/free interleaving (property)."""
    mirror, store = HostMirror(), MediaryStore()
    live = []
    for op, arg in ops:
        if op == "alloc":
            hm = mirror.reserve((arg,), jnp.float32)
            hd = store.alloc((arg,), jnp.float32)
            assert hm == hd
            live.append(hm)
        elif live:
            h = live.pop(arg % len(live))
            mirror.free(h)
            store.free(h)
    assert sorted(mirror.live_handles()) == sorted(store.live_handles())


def test_host_mirror_holds_no_data():
    m = HostMirror()
    h = m.reserve((1024, 1024), jnp.float32)
    assert m.nbytes(h) == 1024 * 1024 * 4       # metadata only


# ---------------------------------------------------------------------------
# target regions + map semantics (paper §3)
# ---------------------------------------------------------------------------
@pytest.fixture()
def pool_ex():
    table = KernelTable()

    @table.kernel("saxpy")
    def saxpy(a, b, alpha):
        return {"out": alpha * a + b}

    @table.kernel("inc")
    def inc(buf):
        return {"buf": buf + 1}

    @table.kernel("use_global")
    def use_global(g, x):
        return {"out": g + x}

    pool = DevicePool.virtual(3, table=table)
    return pool, TargetExecutor(pool)


def test_map_to_from_with_firstprivate(pool_ex):
    pool, ex = pool_ex
    a, b = jnp.arange(4.0), jnp.ones(4)
    out = ex.target("saxpy", 0, MapSpec(
        to={"a": a, "b": b},
        from_={"out": jax.ShapeDtypeStruct((4,), jnp.float32)},
        firstprivate={"alpha": 3.0}))
    np.testing.assert_allclose(out["out"], 3.0 * a + b)
    # region teardown freed everything on device 0 and its mirror
    assert pool.devices[0].store.live_handles() == []
    assert pool.mirrors[0].live_handles() == []


def test_map_tofrom_roundtrip(pool_ex):
    pool, ex = pool_ex
    out = ex.target("inc", 1, MapSpec(tofrom={"buf": jnp.zeros(3)}))
    np.testing.assert_allclose(out["buf"], np.ones(3))


def test_array_sections_move_only_slices(pool_ex):
    """Paper Listing 2: only the required elements are copied per device."""
    pool, ex = pool_ex
    big = jnp.arange(100.0)
    before = pool.cost.bytes_moved("to")
    out = ex.target("saxpy", 2, MapSpec(
        to={"a": sec(big, 10, 5), "b": sec(big, 20, 5)},
        from_={"out": jax.ShapeDtypeStruct((5,), jnp.float32)},
        firstprivate={"alpha": 1.0}))
    moved = pool.cost.bytes_moved("to") - before
    assert moved == 2 * 5 * 4                    # two 5-element f32 sections
    np.testing.assert_allclose(out["out"], big[10:15] + big[20:25])


def test_declare_target_globals(pool_ex):
    """Globals installed once at the same handle on every device."""
    pool, ex = pool_ex
    g = jnp.full(8, 2.0)
    h = pool.install_global("g", g)
    assert all(pool.mirrors[d].live_handles() == [h] for d in range(len(pool)))
    out = ex.target("use_global", 1, MapSpec(
        to={"x": jnp.ones(8)},
        from_={"out": jax.ShapeDtypeStruct((8,), jnp.float32)},
        use_globals=("g",)))
    np.testing.assert_allclose(out["out"], 3.0)
    # global survives region teardown (device-lifetime, not region-lifetime)
    assert pool.mirrors[1].live_handles() == [h]


def test_nowait_and_taskwait(pool_ex):
    pool, ex = pool_ex
    futs = [ex.target("inc", d, MapSpec(tofrom={"buf": jnp.full(2, float(d))}),
                      nowait=True) for d in range(3)]
    results = ex.taskwait()
    for d, r in enumerate(results):
        np.testing.assert_allclose(r["buf"], d + 1.0)


def test_command_trace_and_stop(pool_ex):
    pool, ex = pool_ex
    ex.target("inc", 0, MapSpec(tofrom={"buf": jnp.zeros(2)}))
    ops = [c.op for c in pool.trace]
    assert ops == ["ALLOC", "XFER_TO", "EXEC", "XFER_FROM", "FREE"]
    pool.stop_all()
    with pytest.raises(RuntimeError):
        pool.devices[0].execute(Command("EXEC", 0, kernel_index=0), pool.table)


def test_kernel_must_return_mapped_outputs(pool_ex):
    pool, ex = pool_ex
    with pytest.raises(KeyError):
        ex.target("inc", 0, MapSpec(
            to={"buf": jnp.zeros(2)},
            from_={"missing": jax.ShapeDtypeStruct((2,), jnp.float32)}))


def test_config_file_multiplier():
    """Paper §4: 'node 2' in the config file starts 2 devices on that node."""
    pool = DevicePool.from_config(["node0 2", "node1", "# comment"])
    assert len(pool) == 3
    assert [d.hostname for d in pool.devices] == ["node0", "node0", "node1"]


# ---------------------------------------------------------------------------
# EXEC argument resolution: only the buffers a kernel takes are read
# ---------------------------------------------------------------------------
def _resolution_table(seen=None):
    t = KernelTable()
    t.register("ignores_out", lambda a: {"out": a + 1.0})
    t.register("reads_out", lambda a, out: {"out": a + out})

    def takes_all(**kw):
        if seen is not None:
            seen.append(sorted(kw))          # at trace time: the names given
        return {"out": kw["a"] * kw["alpha"] + kw["out"]}

    t.register("takes_all", takes_all)
    return t


def _exec_with_output_slot(pool, kernel, **kw):
    """EXEC ``kernel`` on device 0 with ``a`` written and ``out`` only
    ALLOC'd, as a ``from`` map leaves it; returns (result, out handle)."""
    a = pool.alloc(0, (4,), jnp.float32)
    pool.transfer_to(0, a, np.arange(4, dtype=np.float32))
    out = pool.alloc(0, (4,), jnp.float32)
    res = pool.exec_kernel(0, kernel, buffers={"a": a, "out": out}, **kw)
    pool.sync()
    return res, out


@pytest.mark.parametrize("kernel, fills, names, want", [
    # (a) the output slot is not taken: never read, never filled
    ("ignores_out", 0, None, np.arange(4.0) + 1.0),
    # (b) a kernel that takes its ALLOC'd output reads OMPi's calloc zeros
    ("reads_out", 1, None, np.arange(4.0)),
    # (c) a **kwargs kernel receives every mapped and firstprivate name
    ("takes_all", 1, ["a", "alpha", "out"], 2.0 * np.arange(4.0)),
])
def test_exec_resolves_only_the_buffers_its_kernel_takes(kernel, fills,
                                                         names, want):
    seen = []
    pool = DevicePool.virtual(1, table=_resolution_table(seen))
    store = pool.devices[0].store
    res, out = _exec_with_output_slot(pool, kernel,
                                      firstprivate={"alpha": 2.0})
    np.testing.assert_array_equal(res["out"], want)
    assert pool.cost.compute[-1].zero_fills == fills
    assert store.zero_fills == fills
    assert isinstance(store._slots[out], _Calloc) == (fills == 0)
    if names is not None:
        assert seen == [names]
    # the write-back installs the result without filling the slot first
    pool.transfer_to_writeback(0, out, res["out"]).result()
    np.testing.assert_array_equal(store.read(out), want)
    assert store.zero_fills == fills
    pool.stop_all()


@pytest.mark.parametrize("kernel", ["ignores_out", "reads_out"])
def test_exec_mapping_a_freed_handle_raises_whether_taken_or_not(kernel):
    """(d) a dead mapped handle still raises, even where the kernel does
    not take it and so nothing reads it."""
    pool = DevicePool.virtual(1, table=_resolution_table())
    a = pool.alloc(0, (4,), jnp.float32)
    pool.transfer_to(0, a, np.ones(4, np.float32))
    out = pool.alloc(0, (4,), jnp.float32)
    pool.free(0, out)
    with pytest.raises(KeyError, match="not live"):
        pool.exec_kernel(0, kernel, buffers={"a": a, "out": out})
    pool.stop_all()


def test_tables_reusing_a_name_keep_each_kernels_own_parameters():
    """(e) two tables, one after the other, register different functions
    under one name: each EXEC resolves by its own table's entry (a filter
    keyed on ``id(fn)`` would mix them up once an id is reused)."""
    for make, params, want, fills in (
            (lambda: lambda a: {"out": a + 1.0}, {"a"}, 1.0, 0),
            (lambda: lambda a, out: {"out": a + out}, {"a", "out"}, 0.0, 1),
            (lambda: lambda a: {"out": a + 1.0}, {"a"}, 1.0, 0)):
        t = KernelTable()
        t.register("k", make())
        assert t.lookup(0).params == frozenset(params)
        pool = DevicePool.virtual(1, table=t)
        res, _ = _exec_with_output_slot(pool, "k")
        np.testing.assert_array_equal(res["out"], np.arange(4.0) + want)
        assert pool.cost.compute[-1].zero_fills == fills
        pool.stop_all()
        del t, pool, res
        gc.collect()


def test_kernel_signature_is_read_at_registration_not_per_exec(monkeypatch):
    """(f) the parameter set is fixed when the kernel is registered; no
    EXEC reads a signature again."""
    t = _resolution_table()
    assert t.lookup(t.index_of("ignores_out")).params == frozenset({"a"})
    assert t.lookup(t.index_of("takes_all")).params is None
    pool = DevicePool.virtual(1, table=t)
    _exec_with_output_slot(pool, "ignores_out")       # builds the jit
    reads = []
    real = inspect.signature

    def counting(obj, *args, **kwargs):
        reads.append(obj)
        return real(obj, *args, **kwargs)

    monkeypatch.setattr(inspect, "signature", counting)
    for _ in range(3):
        res, _ = _exec_with_output_slot(pool, "ignores_out")
    np.testing.assert_array_equal(res["out"], np.arange(4.0) + 1.0)
    assert reads == []
    pool.stop_all()


MESH_SLICES = r"""
import sys
import jax, jax.numpy as jnp, numpy as np
sys.path.insert(0, "benchmarks")
import bots_alignment as ba
from repro.core import (ClusterRuntime, DevicePool, KernelTable, MapSpec,
                        RuntimeConfig, offload_strips)
from repro.core.device import Command
table = KernelTable()
table.register("ids", lambda start: {"out": start + jnp.arange(4)})
table.register("double", lambda x: {"out": 2 * x})
table.register("align_pairs", ba.align_pairs)
mesh = jax.make_mesh((4,), ("node",), devices=jax.devices()[:4])
pool = DevicePool.from_mesh_slices(mesh, "node", table=table)
chips = jax.devices()[:4]
for d, dev in enumerate(pool.devices):
    # the kernel's own output, before any write-back moves it
    out = dev.execute(Command("EXEC", d, kernel_index=table.index_of("ids")),
                      table, {"buffers": {}, "firstprivate": {"start": 1}})
    assert out["out"].devices() == {chips[d]}, (d, out["out"].devices())
    h = dev.store.alloc((3,), jnp.float32)
    dev.store.write(h, jnp.arange(3.0))
    out = dev.execute(Command("EXEC", d, kernel_index=table.index_of("double")),
                      table, {"buffers": {"x": h}})
    assert out["out"].devices() == {chips[d]}, (d, out["out"].devices())
    dev.store.free(h)
rt = ClusterRuntime(RuntimeConfig(), pool=pool)
img = offload_strips(rt.ex, "ids", 16, lambda start, length: MapSpec(
    firstprivate={"start": start},
    from_={"out": jax.ShapeDtypeStruct((length,), jnp.int32)}))
np.testing.assert_array_equal(np.asarray(img), np.arange(16))
assert img.devices() == {chips[0]}
bank = ba._data(10, 60, seed=5)
bank.install(rt.ex)
scores = np.asarray(ba.align_all_pairs(rt.ex, bank))
rt.shutdown()
rt = ClusterRuntime(RuntimeConfig(n_virtual=4), table=table)
bank.install(rt.ex)
np.testing.assert_array_equal(scores, np.asarray(ba.align_all_pairs(rt.ex, bank)))
rt.shutdown()
print("done")
"""


def test_mesh_slice_devices_run_their_kernels_on_their_own_chips():
    """One device per chip of a mesh: a kernel runs on its device's chip,
    whether its arguments are arrays or host scalars; strips computed on
    four chips are gathered on the first; all-pairs scores over four chips
    equal those over four virtual devices of one."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(repo, "src"))
    proc = subprocess.run([sys.executable, "-c", MESH_SLICES], cwd=repo,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.rstrip().endswith("done")
