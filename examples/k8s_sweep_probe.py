"""The benchmark's cluster sweep (cell ``k8s-sweep``) on ONE device,
for the number to put beside the mesh's (PERF.md section 6): the same
cluster, images, warm-up, window and check as
``benchmark/run.py --workload k8s-sweep``, with the runner
``trivy_tpu.k8s.build_runner`` builds told ``mesh=None``, so every
sieve dispatch and interval wave runs on the first chip and the others
idle. Prints the run's result line, as ``run.py`` does.

    python3 examples/k8s_sweep_probe.py --seed N [--seconds 30]

On a host that shows one chip it runs there; the cell itself asks for
four.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run as bench_run  # noqa: E402
from modes import k8s_sweep  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    from trivy_tpu.runtime.device import resolve_device
    k8s_sweep.ONE_DEVICE = True
    cell = bench_run.load_cell("k8s-sweep")
    cell["chips"] = min(cell["chips"], resolve_device("tpu").devices)
    try:
        out = bench_run.Cell(cell, args.seed, args.seconds,
                             trace=False).run()
    except bench_run.BenchFailure as e:
        print(f"k8s_sweep_probe: cannot run: {e}", file=sys.stderr)
        return 3
    out["one_device"] = True
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
