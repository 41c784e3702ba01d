"""Names of the runtime's profiler spans, one per layer boundary.

Every span is a ``jax.profiler.TraceAnnotation``: it lands on the
``/host:CPU`` plane of a ``jax.profiler`` trace, on the same clock as the
chips' planes, and costs one enter/exit when no trace is being collected.
Keyword arguments (``device``, ``kernel``, ``tag``) become the event's
metadata and are encoded only while the profiler collects.

=======================  ===================================================
``omp.wave``             one wave of ``run_graph``, on the solving thread
``omp.wave.plan``        placement, ``make_maps``, peer rewrite, wave pins
``omp.wave.issue``       the wave's ``target`` calls
``omp.wave.join``        waiting for the wave's regions (and recovery)
``omp.region``           one target region, on the thread that runs it
``omp.region.exec_wait`` the issuing thread waiting for its EXEC's result
``omp.cmd.<OP>``         one stream item's service on its device worker
``omp.exec.dispatch``    an EXEC from ``execute`` until the jitted call
                         returns: lookup, argument filter, jit dispatch
``omp.exec.sync``        the device's completion thread waiting for an
                         EXEC's kernel (``block_until_ready``), outside
                         any ``omp.cmd.*`` span: the worker meanwhile
                         serves the next item
``omp.jit.build``        the first call of a new jit wrapper (a compile)
=======================  ===================================================

A span's self time is its duration less the time covered by ``omp.*``
spans nested in it on the same thread.
"""
from __future__ import annotations

import jax

span = jax.profiler.TraceAnnotation

PREFIX = "omp."
WAVE = "omp.wave"
WAVE_PLAN = "omp.wave.plan"
WAVE_ISSUE = "omp.wave.issue"
WAVE_JOIN = "omp.wave.join"
REGION = "omp.region"
REGION_EXEC_WAIT = "omp.region.exec_wait"
EXEC_DISPATCH = "omp.exec.dispatch"
EXEC_SYNC = "omp.exec.sync"
JIT_BUILD = "omp.jit.build"

#: what a device worker serves: the stream's commands, and the device-local
#: write-back of a kernel result (a stream item that is no ``Command``)
OPS = ("ALLOC", "FREE", "XFER_TO", "XFER_FROM", "EXEC", "SEND", "RECV",
       "STOP", "WRITEBACK")
CMD = {op: f"omp.cmd.{op}" for op in OPS}
