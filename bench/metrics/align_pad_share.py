"""Share of the alignment kernel's work that is padding: 1 - the DP cells
at the true lengths over the cells the kernel computes, summed over the
window's EXECs (``work["cells"]`` and ``work["padded_cells"]`` on their
``CostModel.compute`` records).  The planner's length classes set it.

Layer: target regions and command streams.  Nothing to read where no
record declares its work.
"""


def read(ctx):
    works = [r.work for r in ctx.compute
             if getattr(r, "work", None) and r.work.get("padded_cells")]
    if not works:
        return None
    return 1.0 - (sum(w["cells"] for w in works)
                  / sum(w["padded_cells"] for w in works))
