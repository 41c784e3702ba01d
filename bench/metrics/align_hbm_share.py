"""The alignment kernel's share of its roofline, by memory: the compulsory
HBM bytes of its operands and results that the window's EXECs declared
(``work["bytes"]`` on their ``CostModel.compute`` records), over the chip
seconds of the ``jit_align_pairs*`` modules, over the chip's HBM bandwidth
(``HBM_BW_Bps``).  The kernel's seconds are the chips' busy time less the
listed modules that are not the kernel, as in ``align_gcups``.

v5e publishes no peak for its vector unit, where this kernel's integer
work runs, so the compute roof waits for the peaks table keyed by
``device_kind``; until then this is the bytes side alone.

Layer: kernels.  Nothing to read where no record declares bytes or the
trace lists no such module.
"""


def read(ctx):
    from repro.core.costmodel import HBM_BW_Bps
    nbytes = sum((getattr(r, "work", None) or {}).get("bytes", 0)
                 for r in ctx.compute)
    if not nbytes or ctx.trace is None:
        return None
    if not any(n.startswith("jit_align_pairs") for n, _ in ctx.trace.device_ops):
        return None
    seconds = sum(ctx.trace.busy_s) - sum(
        s for n, s in ctx.trace.device_ops if not n.startswith("jit_align_pairs"))
    if seconds <= 0:
        return None
    return nbytes / seconds / HBM_BW_Bps
