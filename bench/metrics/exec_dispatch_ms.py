"""Mean dispatch time of an EXEC on its device worker, in milliseconds:
from ``execute`` until the jitted call returns (kernel lookup, argument
filter, buffer addresses, jit dispatch).

Layer: device workers (``DevicePool.exec_kernel``).  Read from the window's
``CostModel.compute`` records (``dispatch_s``, the worker's clock; the
trace holds the same interval as the ``omp.exec.dispatch`` span).  Nothing
to read where the program records no such part.
"""


def read(ctx):
    parts = [r.dispatch_s for r in ctx.compute
             if getattr(r, "dispatch_s", None) is not None]
    if not parts:
        return None
    return 1e3 * sum(parts) / len(parts)
