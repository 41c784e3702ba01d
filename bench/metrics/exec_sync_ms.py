"""Mean time a device worker waits in an EXEC's ``block_until_ready``, in
milliseconds: the kernel's run on the chip as the worker sees it.

Layer: device workers (``DevicePool.exec_kernel``).  Read from the window's
``CostModel.compute`` records (``sync_s``, the worker's clock; the trace
holds the same interval as the ``omp.exec.sync`` span).  Nothing to read
where the program records no such part.
"""


def read(ctx):
    parts = [r.sync_s for r in ctx.compute
             if getattr(r, "sync_s", None) is not None]
    if not parts:
        return None
    return 1e3 * sum(parts) / len(parts)
