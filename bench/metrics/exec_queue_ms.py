"""Mean time a ready EXEC waited for its device worker, in milliseconds:
from the stream handing it to the worker's queue until the worker took it.
Large where one worker serves more than it can.

Layer: device workers (``DevicePool._worker``).  Read from the window's
``CostModel.compute`` records (``queue_s``, from the EXEC's own work
item).  Nothing to read where the program records no such wait.
"""


def read(ctx):
    waits = [r.queue_s for r in ctx.compute
             if getattr(r, "queue_s", None) is not None]
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
