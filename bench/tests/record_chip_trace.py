"""Record the chip trace that ``test_host_spans.py`` reads.

    python3 bench/tests/record_chip_trace.py [--out <file>]

Runs the ``sparselu-bots-b100.1chip`` cell at K=4 (B=100 as published)
through ``bench/run.py``'s own solve path on a TPU, with a traced window of
one solve after the warm-up, and writes the profiler's ``.xplane.pb``
gzipped to ``bench/tests/data/`` (under 1 MB).  Refuses to run without a
TPU.
"""
from __future__ import annotations

import copy
import gzip
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (ROOT, os.path.join(ROOT, "src"), os.path.join(ROOT, "benchmarks")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run, spec, trace_reduce  # noqa: E402

OUT = os.path.join(HERE, "data", "sparselu-k4-tpu-v5e.xplane.pb.gz")


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=OUT)
    args = ap.parse_args(argv)
    cell = copy.deepcopy(spec.load_cell(ROOT, "sparselu-bots-b100.1chip"))
    cell.config["blocks_per_side"] = 4
    run.require_chips(cell.chips)
    with tempfile.TemporaryDirectory() as d:
        # --seconds 0: the window ends with its first solve
        result, info = run.run_cell(cell, seed=2**33 + 4, seconds=0.0,
                                    trace=True, t_start=time.perf_counter(),
                                    trace_dir=d)
        print("\n".join(info))
        if not result["correct"]:
            print("the traced solve is not correct", file=sys.stderr)
            return 1
        with open(trace_reduce.find_xplane(d), "rb") as f:
            raw = f.read()
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with gzip.open(args.out, "wb", compresslevel=9) as f:
        f.write(raw)
    print(f"{args.out}: {os.path.getsize(args.out)} bytes "
          f"({len(raw)} before gzip)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
