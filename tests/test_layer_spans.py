"""The runtime's layer spans and EXEC records: each EXEC's gate and queue
wait and its split into dispatch and sync, the worker's execution-order
log, kernels named by their table entry, and the ``omp.*`` spans of one
profiled DAG, nested as ``core/spans.py`` lays out."""
import concurrent.futures as cf
import glob
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ClusterRuntime, DagTask, DevicePool, KernelTable,
                        MapSpec, RuntimeConfig, TargetExecutor)
from repro.core import spans


def _table():
    t = KernelTable()
    t.register("double", lambda x: {"out": x * 2.0})
    return t


def _exec(pool, device=0, value=1.0, **kw):
    h = pool.alloc(device, (4,), jnp.float32)
    pool.transfer_to(device, h, np.full(4, value, np.float32))
    out = pool.exec_kernel(device, "double", buffers={"x": h}, **kw)
    pool.free(device, h)
    return out


def test_every_exec_leaves_one_record_with_nonnegative_waits():
    pool = DevicePool.virtual(2, table=_table())
    for d in (0, 1, 0):
        _exec(pool, d)
    pool.sync()
    recs = pool.cost.compute
    assert [r.device for r in recs] == [0, 1, 0]
    for rec in recs:
        assert rec.gate_s >= 0.0 and rec.queue_s >= 0.0
    execs = [sum(c.op == "EXEC" for c in t) for t in pool.stream_traces]
    assert execs == [2, 1]
    pool.stop_all()


def test_an_exec_gated_on_a_slow_producer_waits_in_gate_not_queue():
    pool = DevicePool.virtual(1, table=_table())
    _exec(pool)                                  # compile outside the test
    producer: cf.Future = cf.Future()
    threading.Timer(0.3, producer.set_result, (None,)).start()
    _exec(pool, extra_deps=(producer,))
    rec = pool.cost.compute[-1]
    assert rec.gate_s >= 0.25
    assert rec.queue_s < 0.1
    pool.stop_all()


def test_an_exec_behind_a_busy_worker_waits_in_queue_not_gate():
    pool = DevicePool.virtual(1, table=_table())
    _exec(pool)
    h = pool.alloc(0, (4,), jnp.float32)
    pool.transfer_to(0, h, np.ones(4, np.float32))
    pool.sync()
    pool._submit_async(0, lambda: time.sleep(0.3))   # occupies the worker
    pool.exec_kernel(0, "double", buffers={"x": h})
    rec = pool.cost.compute[-1]
    assert rec.queue_s >= 0.25
    assert rec.gate_s < 0.1
    pool.stop_all()


def test_the_worker_logs_commands_in_execution_order():
    """An EXEC gated on a slow producer runs, and is logged, after an
    ALLOC issued behind it."""
    pool = DevicePool.virtual(1, table=_table())
    h = pool.alloc(0, (4,), jnp.float32)
    pool.transfer_to(0, h, np.ones(4, np.float32))
    pool.sync()
    pool.stream_traces[0].clear()
    producer: cf.Future = cf.Future()
    done = cf.ThreadPoolExecutor(1).submit(
        pool.exec_kernel, 0, "double", buffers={"x": h},
        extra_deps=(producer,))
    time.sleep(0.1)                              # the EXEC is issued first
    pool.alloc(0, (4,), jnp.float32)
    time.sleep(0.1)                              # the ALLOC runs meanwhile
    producer.set_result(None)
    done.result()
    pool.sync()
    assert [c.op for c in pool.stream_traces[0]] == ["ALLOC", "EXEC"]
    assert [c.op for c in pool.trace[-2:]] == ["EXEC", "ALLOC"]   # issue order
    pool.stop_all()


def test_exec_records_split_into_dispatch_and_sync():
    pool = DevicePool.virtual(1, table=_table())
    for _ in range(3):
        _exec(pool)
    for rec in pool.cost.compute:
        assert rec.dispatch_s > 0 and rec.sync_s >= 0
        assert rec.dispatch_s + rec.sync_s == pytest.approx(rec.seconds)
        assert rec.gate_s >= 0 and rec.queue_s >= 0
        assert rec.inflight == 0                 # each ran alone
    pool.stop_all()


def test_exec_records_stay_whole_under_contention():
    """Many region threads issue EXECs at once: each leaves one record
    whose parts add up."""
    import sys
    pool = DevicePool.virtual(2, table=_table())
    ex = TargetExecutor(pool, max_host_threads=32)
    x = jnp.ones((4,), jnp.float32)
    maps = MapSpec(to={"x": x},
                   from_={"out": jax.ShapeDtypeStruct((4,), jnp.float32)})
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        futs = [ex.target("double", i % 2, maps, nowait=True)
                for i in range(200)]
        outs = ex.drain(futs)
    finally:
        sys.setswitchinterval(old)
    assert len(outs) == 200 and len(pool.cost.compute) == 200
    for rec in pool.cost.compute:
        assert rec.gate_s >= 0.0 and rec.queue_s >= 0.0
        assert rec.dispatch_s + rec.sync_s == pytest.approx(rec.seconds)
        assert rec.inflight >= 0
    pool.sync()
    assert sum(c.op == "EXEC" for t in pool.stream_traces for c in t) == 200
    # each device's worker and completion thread counted every EXEC once
    done = pool._completions
    assert [c.dispatched for c in done] == [c.completed for c in done]
    assert sum(c.dispatched for c in done) == 200
    pool.stop_all()


def test_a_lambda_kernel_lowers_to_a_module_named_after_its_entry():
    pool = DevicePool.virtual(1, table=_table())
    h = pool.alloc(0, (4,), jnp.float32)
    pool.transfer_to(0, h, np.ones(4, np.float32))
    out = pool.exec_kernel(0, "double", buffers={"x": h})
    np.testing.assert_array_equal(out["out"], np.full(4, 2.0, np.float32))
    index = pool.table.index_of("double")
    fn = pool.devices[0]._jit_cache[index]
    text = fn.lower(jnp.ones(4, jnp.float32)).as_text()
    assert text.startswith("module @jit_double ")
    assert "lambda" not in text.split("\n")[0]
    pool.stop_all()


# ---------------------------------------------------------------------------
# one profiled DAG: the spans and how they nest
# ---------------------------------------------------------------------------
def _events(path):
    """{thread line id: [(name, start, end, stats)]} of the host plane."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    plane = pd.find_plane_with_name("/host:CPU")
    lines = {}
    for i, line in enumerate(plane.lines):
        evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                dict(ev.stats)) for ev in line.events
               if ev.name.startswith(spans.PREFIX)]
        if evs:
            lines[i] = evs
    return lines


def _parent(ev, line):
    """The innermost ``omp.*`` span on the same line that contains ``ev``."""
    name, s, e, _ = ev
    best = None
    for other in line:
        if other is ev:
            continue
        if other[1] <= s and e <= other[2] and (other[2] - other[1]) > (e - s):
            if best is None or other[2] - other[1] < best[2] - best[1]:
                best = other
    return best[0] if best else None


@pytest.fixture(scope="module")
def dag_trace(tmp_path_factory):
    """Tasks a and b in the first wave, round-robin on devices 0 and 1; c
    (on device 0) reads both, so b's output goes 1 -> 0 as SEND/RECV."""
    table = KernelTable()
    table.register("src", lambda x: {"out": x + 1.0})
    table.register("mix", lambda a, b: {"out": a * b})
    rt = ClusterRuntime(RuntimeConfig(n_virtual=2, comm_mode="direct"),
                        table=table)
    x = jnp.arange(8, dtype=jnp.float32)
    sds = jax.ShapeDtypeStruct((8,), jnp.float32)
    tasks = [
        DagTask("a", "src", (), lambda r: MapSpec(to={"x": x},
                                                  from_={"out": sds})),
        DagTask("b", "src", (), lambda r: MapSpec(to={"x": x * 2.0},
                                                  from_={"out": sds})),
        DagTask("c", "mix", ("a", "b"),
                lambda r: MapSpec(to={"a": r["a"], "b": r["b"]},
                                  from_={"out": sds})),
    ]
    d = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        res = rt.wavefront_offload(tasks, nowait=True, peer=True,
                                   policy="round-robin", tag="t")
        jax.block_until_ready(res)
    finally:
        jax.profiler.stop_trace()
    rt.shutdown()
    np.testing.assert_allclose(res["c"],
                               (np.arange(8) + 1) * (2 * np.arange(8) + 1))
    path = sorted(glob.glob(f"{d}/**/*.xplane.pb", recursive=True))[-1]
    return _events(path)


EXPECTED_PARENT = {
    spans.WAVE: None,
    spans.WAVE_PLAN: spans.WAVE,
    spans.WAVE_ISSUE: spans.WAVE,
    spans.WAVE_JOIN: spans.WAVE,
    spans.REGION: None,
    spans.REGION_EXEC_WAIT: spans.REGION,
    spans.EXEC_DISPATCH: spans.CMD["EXEC"],
    spans.EXEC_SYNC: None,                # on the device's completion thread
    spans.JIT_BUILD: spans.EXEC_DISPATCH,
}


@pytest.mark.parametrize("name", sorted(EXPECTED_PARENT))
def test_every_span_is_present_and_nested_in_its_parent(dag_trace, name):
    found = [(ev, line) for line in dag_trace.values() for ev in line
             if ev[0] == name]
    assert found, f"no {name} span"
    for ev, line in found:
        assert _parent(ev, line) == EXPECTED_PARENT[name]


def test_waves_are_the_dags_depth_on_one_thread(dag_trace):
    waves = [(ev, i) for i, line in dag_trace.items() for ev in line
             if ev[0] == spans.WAVE]
    assert sorted(ev[3]["wave"] for ev, _ in waves) == [0, 1]
    assert len({i for _, i in waves}) == 1


@pytest.mark.parametrize("op", ["ALLOC", "XFER_TO", "EXEC", "WRITEBACK",
                                "SEND", "RECV", "XFER_FROM", "FREE"])
def test_commands_are_spanned_by_op_on_their_workers(dag_trace, op):
    evs = [(ev, i) for i, line in dag_trace.items() for ev in line
           if ev[0] == spans.CMD[op]]
    assert evs, f"no omp.cmd.{op} span"
    for ev, i in evs:
        assert _parent(ev, dag_trace[i]) is None   # outermost on its worker
        assert ev[3]["device"] in (0, 1)
    # each device's commands run on that device's worker alone
    lines_of = {}
    for i, line in dag_trace.items():
        for ev in line:
            if ev[0].startswith("omp.cmd."):
                lines_of.setdefault(ev[3]["device"], set()).add(i)
    assert len(lines_of) == 2
    assert all(len(v) == 1 for v in lines_of.values())
    assert lines_of[0] != lines_of[1]


def test_exec_syncs_run_on_a_completion_thread_per_device(dag_trace):
    """The wait for the chip is off the workers: each device's
    ``omp.exec.sync`` spans sit on one line that serves no command."""
    cmd_lines = {i for i, line in dag_trace.items() for ev in line
                 if ev[0].startswith("omp.cmd.")}
    sync_lines = {}
    for i, line in dag_trace.items():
        for ev in line:
            if ev[0] == spans.EXEC_SYNC:
                sync_lines.setdefault(ev[3]["device"], set()).add(i)
    assert sorted(sync_lines) == [0, 1]
    assert all(len(v) == 1 for v in sync_lines.values())
    assert not set().union(*sync_lines.values()) & cmd_lines


def test_exec_spans_name_their_kernel_in_the_tag(dag_trace):
    tags = {ev[3].get("tag") for line in dag_trace.values() for ev in line
            if ev[0] == spans.CMD["EXEC"]}
    assert {"t:w0:a", "t:w0:b", "t:w1:c"} <= tags
    builds = {ev[3]["kernel"] for line in dag_trace.values() for ev in line
              if ev[0] == spans.JIT_BUILD}
    assert builds == {"src", "mix"}
