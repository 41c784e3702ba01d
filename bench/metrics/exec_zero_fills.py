"""Mean number of unwritten buffer slots an EXEC's argument resolution
zero-filled on its device worker, in fills per EXEC.  A slot ALLOC
reserved reads as zeros, made when the slot is first read; an EXEC reads
only the buffers its kernel takes, so this counts the ALLOC'd outputs a
kernel takes as parameters.

Layer: device workers (``NodeDevice.execute``).  Read from the window's
``CostModel.compute`` records (``zero_fills``).  Nothing to read where the
program records no such count.
"""


def read(ctx):
    fills = [r.zero_fills for r in ctx.compute
             if getattr(r, "zero_fills", None) is not None]
    if not fills:
        return None
    return sum(fills) / len(fills)
