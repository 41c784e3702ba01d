"""Giga cell updates per second of the alignment kernel: the DP cells at
the true lengths that the window's EXECs declared (``work["cells"]`` on
their ``CostModel.compute`` records, carried from ``TaskNode.work``), over
the chip seconds of the ``jit_align_pairs*`` modules, over 1e9.

The reduction lists only the longest modules, and a plan has more kernel
shapes than that, so the kernel's seconds are the chips' busy time less
the listed modules that are not the kernel.

Layer: kernels.  Nothing to read where no record declares cells or the
trace lists no such module.
"""


def read(ctx):
    cells = sum((getattr(r, "work", None) or {}).get("cells", 0)
                for r in ctx.compute)
    if not cells or ctx.trace is None:
        return None
    if not any(n.startswith("jit_align_pairs") for n, _ in ctx.trace.device_ops):
        return None
    seconds = sum(ctx.trace.busy_s) - sum(
        s for n, s in ctx.trace.device_ops if not n.startswith("jit_align_pairs"))
    if seconds <= 0:
        return None
    return cells / seconds / 1e9
