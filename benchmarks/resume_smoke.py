"""Kill-and-resume smoke: checkpoint a sparselu run mid-graph, resume it in
a FRESH interpreter, assert the factorization is bit-identical.

This is the end-to-end drill for resumable runs.  The parent only
orchestrates and never imports JAX; three child processes run one after
another, so at most one process at a time holds the accelerator:

1. the uninterrupted reference run;
2. the "killed" run: the same DAG under ``GraphCheckpoint`` with
   ``halt_after`` at roughly half the waves (a job killed at a wave
   boundary);
3. the resumed run: the same DAG with ``resume_from`` pointing at the
   checkpoint directory.  It skips the completed prefix (asserted via its
   EXEC count), recomputes only the tail, and must produce the exact bytes
   of the reference run.

``--json PATH`` dumps {waves_total, waves_before_kill, execs_resumed,
identical} for the CI artifact.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

_CHILD = r"""
import json, sys
import numpy as np
sys.path.insert(0, {bench_dir!r})
from repro.core import (ClusterRuntime, GraphCheckpoint, GraphInterrupted,
                        RuntimeConfig, TaskGraph, load_graph_checkpoint)
from bots_sparselu import _build_dag, _make_table, _matrix

mode, K, B, D, ckdir = {mode!r}, {K}, {B}, {D}, {ckdir!r}
mat = _matrix(K, B)
n_waves = len(TaskGraph.from_tasks(_build_dag(mat, K, B)).waves())
kw = dict(nowait=True, peer=True, policy="locality", tag="sparselu")
if mode == "kill":
    kw["checkpoint"] = GraphCheckpoint(ckdir, every_waves=1, keep=2,
                                       halt_after=max(1, n_waves // 2))
elif mode == "resume":
    kw["resume_from"] = ckdir
rt = ClusterRuntime(RuntimeConfig(n_virtual=D), table=_make_table(K))
out = {{"waves": n_waves, "tasks": len(_build_dag(mat, K, B))}}
try:
    res = rt.wavefront_offload(_build_dag(mat, K, B), **kw)
    if mode == "kill":
        raise AssertionError("halt_after did not interrupt the run")
    out["execs"] = sum(1 for tr in rt.pool.stream_traces for c in tr
                       if c.op == "EXEC")
    out["results"] = {{name: np.asarray(v, np.float32).tobytes().hex()
                      for name, v in res.items()}}
except GraphInterrupted:
    _, extra = load_graph_checkpoint(ckdir)
    out["completed"] = len(set(extra["completed"]))
    out["wave"] = extra["wave"]
finally:
    rt.shutdown()
print(json.dumps(out))
"""


def _child(mode: str, K: int, B: int, D: int, ckdir: str) -> dict:
    """Run one stage in a fresh interpreter; return its JSON line."""
    bench_dir = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    src = os.path.abspath(os.path.join(bench_dir, "..", "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    code = _CHILD.format(bench_dir=bench_dir, mode=mode, K=K, B=B, D=D,
                         ckdir=ckdir)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=480)
    if proc.returncode != 0:
        raise RuntimeError(f"{mode} child failed:\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run(K: int = 4, B: int = 32, D: int = 4, ckdir: str | None = None):
    with tempfile.TemporaryDirectory(prefix="resume_smoke_") as tmp:
        ckdir = ckdir or os.path.join(tmp, "ck")
        ref = _child("reference", K, B, D, ckdir)
        killed = _child("kill", K, B, D, ckdir)
        resumed = _child("resume", K, B, D, ckdir)

    identical = resumed["results"] == ref["results"]
    assert identical, "resumed run diverged from the uninterrupted run"
    assert resumed["execs"] < ref["tasks"], \
        (resumed["execs"], ref["tasks"], "resume re-executed the whole graph")
    return {"K": K, "B": B, "devices": D, "tasks": ref["tasks"],
            "waves_total": ref["waves"], "waves_before_kill": killed["wave"] + 1,
            "tasks_completed_at_kill": killed["completed"],
            "execs_resumed": resumed["execs"], "identical": identical}


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="dump the resume row to PATH (CI artifact)")
    args = ap.parse_args()
    row = run()
    print(f"## kill-and-resume sparselu K={row['K']} B={row['B']} "
          f"D={row['devices']}: killed after wave "
          f"{row['waves_before_kill']}/{row['waves_total']} "
          f"({row['tasks_completed_at_kill']}/{row['tasks']} tasks done), "
          f"resumed with {row['execs_resumed']} EXECs in a fresh process — "
          f"bit-identical: {row['identical']}")
    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump({"benchmark": "resume_smoke", "row": row}, f,
                      indent=2, sort_keys=True)
        print(f"wrote {args.json}")
