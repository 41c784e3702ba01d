"""Share of the window's EXECs dispatched while an earlier EXEC of the same
device was still running: how often the device worker handed the chip a
second kernel instead of waiting for the first.  0 where every EXEC ran
alone on its device.

Layer: device workers (``DevicePool._worker`` and the device's completion
thread).  Read from the window's ``CostModel.compute`` records
(``inflight``, the device's EXECs dispatched but not completed when this
one's dispatch began).  Nothing to read where the program records no such
count.
"""


def read(ctx):
    counts = [r.inflight for r in ctx.compute
              if getattr(r, "inflight", None) is not None]
    if not counts:
        return None
    return sum(1 for n in counts if n >= 1) / len(counts)
