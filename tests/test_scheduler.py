"""Task-restructuring patterns (paper §5) + fault tolerance on the pool."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (DagTask, DevicePool, KernelTable, MapSpec,
                        TargetExecutor, offload_strips, recursive_offload,
                        sec, strip_partition, wavefront_offload)
from repro.ft import DeviceFailure, FlakyDevice, inject_flaky
from repro.ft.failures import with_retry


# ---------------------------------------------------------------------------
# strip partitioning (alignment / mandelbrot pattern)
# ---------------------------------------------------------------------------
@settings(max_examples=100, deadline=None)
@given(st.integers(0, 500), st.integers(1, 32))
def test_strip_partition_properties(total, n):
    strips = strip_partition(total, n)
    assert sum(l for _, l in strips) == total
    if total:
        assert strips[0][0] == 0
        for (s0, l0), (s1, _) in zip(strips, strips[1:]):
            assert s1 == s0 + l0                     # contiguous
        lengths = [l for _, l in strips]
        assert max(lengths) - min(lengths) <= 1      # balanced ±1
        assert len(strips) == min(total, n)


def _make_square_ex(n_dev=3):
    table = KernelTable()

    @table.kernel("square")
    def square(xs):
        return {"out": xs * xs}

    pool = DevicePool.virtual(n_dev, table=table)
    return pool, TargetExecutor(pool)


@pytest.mark.parametrize("speculate", [False, True])
def test_offload_strips_square(speculate):
    pool, ex = _make_square_ex()
    data = jnp.arange(17.0)

    def make_maps(start, length):
        return MapSpec(to={"xs": sec(data, start, length)},
                       from_={"out": jax.ShapeDtypeStruct((length,), data.dtype)})

    out = offload_strips(ex, "square", 17, make_maps, speculate=speculate)
    np.testing.assert_allclose(out, data * data)


# ---------------------------------------------------------------------------
# recursive unroll-then-offload (fib pattern, paper §5.5)
# ---------------------------------------------------------------------------
def _fib(n):
    a, b = 0, 1
    for _ in range(n):
        a, b = b, a + b
    return a


def test_recursive_offload_fib():
    table = KernelTable()

    @table.kernel("fib_leaf")
    def fib_leaf(n):
        def step(_, ab):
            return ab[1], ab[0] + ab[1]
        a, b = jax.lax.fori_loop(
            0, n.astype(jnp.int32), step,
            (jnp.zeros((), jnp.int64), jnp.ones((), jnp.int64)))
        return {"out": a}

    pool = DevicePool.virtual(4, table=table)
    ex = TargetExecutor(pool)

    def split(n):
        return [n - 1, n - 2] if n > 10 else None

    def combine(_n, kids):
        return kids[0] + kids[1]

    def make_maps(n):
        return MapSpec(to={"n": jnp.asarray(n)},
                       from_={"out": jax.ShapeDtypeStruct((), jnp.int64)})

    result = recursive_offload(ex, "fib_leaf", 16, split, combine, make_maps)
    assert int(result) == _fib(16)
    # host expanded the recursion to ≥ one task per device before offloading
    execs = [c for c in pool.trace if c.op == "EXEC"]
    assert len(execs) >= len(pool)
    assert len({c.device for c in execs}) == len(pool)


# ---------------------------------------------------------------------------
# wavefront DAG (sparselu pattern, paper §5.6)
# ---------------------------------------------------------------------------
def test_wavefront_dag_order_and_host_mediation():
    table = KernelTable()

    @table.kernel("emit")
    def emit(x):
        return {"out": x + 1}

    pool = DevicePool.virtual(2, table=table)
    ex = TargetExecutor(pool)

    def maps_with(deps_wanted):
        def make(deps):
            base = sum(deps.values()) if deps else jnp.zeros(())
            return MapSpec(to={"x": base},
                           from_={"out": jax.ShapeDtypeStruct((), jnp.float32)})
        return make

    tasks = [
        DagTask("a", "emit", (), maps_with(())),
        DagTask("b", "emit", ("a",), maps_with(("a",))),
        DagTask("c", "emit", ("a",), maps_with(("a",))),
        DagTask("d", "emit", ("b", "c"), maps_with(("b", "c"))),
    ]
    res = wavefront_offload(ex, tasks)
    assert float(res["a"]) == 1.0
    assert float(res["b"]) == float(res["c"]) == 2.0
    assert float(res["d"]) == 5.0
    # every dependency round-trips via host: d's inputs were re-sent (XFER_TO)
    xfers_to = [c for c in pool.trace if c.op == "XFER_TO"]
    assert len(xfers_to) >= 4


def test_wavefront_cycle_detected():
    pool, ex = _make_square_ex(2)
    tasks = [DagTask("a", "square", ("b",), lambda d: MapSpec()),
             DagTask("b", "square", ("a",), lambda d: MapSpec())]
    with pytest.raises(ValueError, match="cycle"):
        wavefront_offload(ex, tasks)


# ---------------------------------------------------------------------------
# fault tolerance: injection, retry, blacklist (beyond-paper)
# ---------------------------------------------------------------------------
def test_flaky_device_injection_and_retry():
    table = KernelTable()

    @table.kernel("id")
    def ident(x):
        return {"out": x}

    pool = DevicePool.virtual(3, table=table)
    ex = TargetExecutor(pool)
    inject_flaky(pool, p=1.0, devices=[0])       # device 0 always fails

    maps = MapSpec(to={"x": jnp.ones(2)},
                   from_={"out": jax.ShapeDtypeStruct((2,), jnp.float32)})
    blacklist = set()
    out = with_retry(ex, "id", 0, maps, blacklist=blacklist)
    np.testing.assert_allclose(out["out"], 1.0)
    assert 0 in blacklist                        # failure recorded
    assert pool.devices[0].failures == 1

    # all devices dead ⇒ the error surfaces (no silent hang)
    inject_flaky(pool, p=1.0)
    with pytest.raises(DeviceFailure):
        with_retry(ex, "id", 1, maps, blacklist=set())


def test_elastic_pool_rescale():
    from repro.core import ClusterRuntime, RuntimeConfig
    from repro.ft import rescale_pool

    table = KernelTable()

    @table.kernel("sq2")
    def sq2(xs):
        return {"out": xs * xs}

    rt = ClusterRuntime(RuntimeConfig(n_virtual=2), table=table)
    data = jnp.arange(8.0)

    def make_maps(start, length):
        return MapSpec(to={"xs": sec(data, start, length)},
                       from_={"out": jax.ShapeDtypeStruct((length,), data.dtype)})

    out2 = offload_strips(rt.ex, "sq2", 8, make_maps)
    rescale_pool(rt, 4)                          # "grow the cluster"
    out4 = offload_strips(rt.ex, "sq2", 8, make_maps)
    np.testing.assert_allclose(out2, out4)
    assert len(rt.pool) == 4
