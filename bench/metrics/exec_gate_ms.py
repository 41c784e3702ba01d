"""Mean time an EXEC waited for its inputs, in milliseconds: from its issue
until the device stream handed it to the worker's queue (the last writer
of every handle it reads settled, and the producers it was ordered after).

Layer: target regions and command streams (``DevicePool._gate``).  Read
from the window's ``CostModel.compute`` records (``gate_s``, from the
EXEC's own work item).  Nothing to read where the program records no
such wait.
"""


def read(ctx):
    waits = [r.gate_s for r in ctx.compute
             if getattr(r, "gate_s", None) is not None]
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
