"""The control of a cell: the plain reference with one guarantee broken,
put in the program's place and driven through the harness's own run and
check, which has to read ``correct: false``.

    python3 bench/control.py --workload rgg20.bfs --seeds 11 12 13 --seconds 51

Each seed is one ``run`` of ``bench/run.py`` at the cell's own size: the
graph, the warm-up, the roots, the window and the check are the run's,
and only the searches are answered by the control that the configuration
names (``control``, from ``bench/reference/bfs.py``) on a host copy of the
graph.  The result's ``correct`` and its numbers with their limits are
printed per seed; the exit code is 0 where every seed read not correct.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for _path in (str(ROOT / "src"), str(ROOT)):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from bench import run as run_mod  # noqa: E402
from bench.reference.bfs import CONTROLS  # noqa: E402


class ControlJobs:
    """Stands in for a job module's ``Jobs``: every search is answered by
    ``control`` on the host, with the program's wavefront."""

    def __init__(self, graph, scheduler: dict, control, seed: int):
        import numpy as np

        self.row_ptr = np.asarray(graph.row_ptr)
        self.col_idx = np.asarray(graph.col_idx)[:self.row_ptr[-1]]
        self.wavefront = scheduler["num_workers"]
        self.control, self.seed = control, seed

    def run(self, root: int, span) -> dict:
        with span("control"):
            dist = self.control(self.row_ptr, self.col_idx, root,
                                self.wavefront, self.seed)
        return {"root": root, "dist": dist, "rounds": 0, "pops": 0,
                "work": 0, "dropped": 0}


@contextlib.contextmanager
def in_place(cell, seed: int):
    """Within the block, runs of ``cell`` search with its control."""
    job_mod = run_mod._module("jobs", cell.traffic["job"])
    control = CONTROLS[cell.config["control"]]
    real = job_mod.Jobs
    job_mod.Jobs = lambda graph, scheduler: ControlJobs(
        graph, scheduler, control, seed)
    try:
        yield
    finally:
        job_mod.Jobs = real


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    every_seed_failed = True
    for seed in args.seeds:
        cell = run_mod.load_cell(args.workload)
        try:
            with in_place(cell, seed):
                result = run_mod.run(cell, seed, args.seconds, False)
        except run_mod.chip.NoChip as e:
            print(f"bench/control.py: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": cell.config["control"],
                          "searches": result["attempted"],
                          "correct": result["correct"],
                          "check": result["check"]}), flush=True)
        every_seed_failed &= result["correct"] is False
    print(json.dumps({"workload": args.workload,
                      "control_not_correct_on_every_seed": every_seed_failed}))
    return 0 if every_seed_failed else 1


if __name__ == "__main__":
    sys.exit(main())
