"""The control of the benchmark's comparison: the reference put in the
program's place with one stated guarantee broken, which the comparison
has to find not correct.

The configurations state an exact count of every embedding.  The control
leaves out every embedding through the highest-degree vertex, as a
program would that dropped the seeds of a hub whose wave overflowed
instead of splitting it; everything else is the reference as it is.

    python3 bench/control.py --workload graph500.q1 --seeds 1 2 3

prints, for each seed, the reference count, the control's, and the
checks the harness makes of them; it runs on the host alone and needs no
chip.  Exits non-zero if the comparison finds any control correct.
"""
from __future__ import annotations

import argparse
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def hub_dropped(pattern: str, n: int, edges: np.ndarray) -> int:
    """The reference count on the graph without the edges of its
    highest-degree vertex."""
    from bench.reference import adjacency, counter

    hub = int(np.argmax(np.diff(adjacency(n, edges).indptr)))
    keep = (edges[:, 0] != hub) & (edges[:, 1] != hub)
    return counter(pattern)(n, edges[keep])


def readings(cell, seed: int) -> tuple[int, int, dict]:
    """(reference, control, checks) of one seed at the cell's own size."""
    from bench import graphs, harness
    from bench.reference import counter

    n, edges = graphs.make_edges(cell.config["generator"], seed)
    pattern = cell.traffic["pattern"]
    ref = counter(pattern)(n, edges)
    ctl = hub_dropped(pattern, n, edges)
    checks = harness.check(ref, [harness.Query(seconds=0.0, count=ctl)])
    return ref, ctl, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    cell = harness.load_cell(args.workload)
    caught = True
    for seed in args.seeds:
        ref, ctl, checks = readings(cell, seed)
        gap = checks["count_gap"]
        caught &= gap["value"] > gap["limit"]
        print(f"{args.workload} seed {seed}: reference {ref} control {ctl} "
              f"count_gap {gap['value']} (limit {gap['limit']})", flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
