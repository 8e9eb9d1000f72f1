"""What a file system call costs on this host, what the cost goes by,
and whether it overlaps across threads or processes (PERF.md section
6, PR 34).

Builds one tree of the configuration ``source-tree-1chip``
(``benchmark/gen_tree.py``, 40,377 files, 536 MB) under ``TMPDIR`` and
reads it file by file as ``artifact/walker.py`` did before PR 34
(``lstat``, ``os.open``, ``os.read`` to the end, ``os.close``), in runs
of 64 files, on 1, 2, 4, 8 and 16 threads, on 4 and 8 threads beside a
thread that never leaves the interpreter, and on 1 to 8 processes;
prints wall seconds and microseconds a call for each, each kind of
call clocked apart, and a path call by the path's depth and from the
directory's own descriptor. No JAX, no ``trivy_tpu``.

    python3 examples/fs_call_probe.py [--seed N] [--tree DIR]
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
RUN = 64


def list_tree(root: str) -> tuple:
    """The tree's files in the walker's order, and the listing's own
    seconds."""
    t0 = time.perf_counter()
    out, stack, n_dirs = [], [root], 0
    while stack:
        d = stack.pop()
        n_dirs += 1
        with os.scandir(d) as it:
            entries = list(it)
        out += sorted(e.path for e in entries
                      if e.is_file(follow_symlinks=False))
        stack += [e.path for e in entries
                  if e.is_dir(follow_symlinks=False)]
    return out, n_dirs, time.perf_counter() - t0


def read_run(paths: list) -> tuple:
    """One run: (calls, bytes, this task's own seconds)."""
    t0 = time.perf_counter()
    calls = nbytes = 0
    for full in paths:
        size = os.lstat(full).st_size
        fd = os.open(full, os.O_RDONLY)
        calls += 2
        try:
            want = size + 1
            while True:
                data = os.read(fd, want)
                calls += 1
                if not data:
                    break
                nbytes += len(data)
                want = max(want - len(data), 1 << 20)
        finally:
            os.close(fd)
            calls += 1
    return calls, nbytes, time.perf_counter() - t0


def by_call(paths: list) -> dict:
    """One thread, each kind of call clocked apart (the clock's own
    cost is in every figure alike)."""
    clock = time.perf_counter
    spent = {"lstat": 0.0, "open": 0.0, "read": 0.0, "read_eof": 0.0,
             "close": 0.0}
    for full in paths:
        t = clock()
        size = os.lstat(full).st_size
        spent["lstat"] += clock() - t
        t = clock()
        fd = os.open(full, os.O_RDONLY)
        spent["open"] += clock() - t
        want = size + 1
        while True:
            t = clock()
            data = os.read(fd, want)
            spent["read" if data else "read_eof"] += clock() - t
            if not data:
                break
            want = max(want - len(data), 1 << 20)
        t = clock()
        os.close(fd)
        spent["close"] += clock() - t
    return {k: round(v / len(paths) * 1e6, 2) for k, v in spent.items()}


def by_path(paths: list) -> None:
    """What a call that takes a path goes by: the same files by full
    path and from their directory's descriptor, and one file's
    ancestors by depth."""
    def per(fn, items):
        t = time.perf_counter()
        for it in items:
            fn(it)
        return round((time.perf_counter() - t) / len(items) * 1e6, 2)

    def open_fstat(p):
        fd = os.open(p, os.O_RDONLY)
        os.fstat(fd)
        os.close(fd)

    out = {"lstat_full": per(os.lstat, paths),
           "open_close_full": per(
               lambda p: os.close(os.open(p, os.O_RDONLY)), paths),
           "open_fstat_close_full": per(open_fstat, paths)}
    by_dir: dict = {}
    for p in paths:
        d, name = os.path.split(p)
        by_dir.setdefault(d, []).append(name)
    for key, fn in (
            ("lstat_dir_fd", lambda name, dfd: os.stat(
                name, dir_fd=dfd, follow_symlinks=False)),
            ("open_close_dir_fd", lambda name, dfd: os.close(
                os.open(name, os.O_RDONLY, dir_fd=dfd)))):
        t = time.perf_counter()
        for d, names in by_dir.items():
            dfd = os.open(d, os.O_RDONLY | os.O_DIRECTORY)
            for name in names:
                fn(name, dfd)
            os.close(dfd)
        out[key] = round((time.perf_counter() - t) / len(paths) * 1e6,
                         2)
    print(json.dumps({"probe": "by_path_us", **out}), flush=True)
    p, depth = paths[0], {}
    while p != os.path.dirname(p):
        depth[p.count(os.sep)] = per(os.lstat, [p] * 300)
        p = os.path.dirname(p)
    print(json.dumps({"probe": "lstat_by_depth_us",
                      **{str(k): depth[k] for k in sorted(depth)}}),
          flush=True)


def report(kind: str, n: int, wall: float, results: list,
           **more) -> None:
    calls = sum(r[0] for r in results)
    print(json.dumps({
        "probe": kind, "workers": n, "wall_s": round(wall, 3),
        "calls": calls, "bytes": sum(r[1] for r in results),
        # the tree's wall over its calls: what a file costs the walk
        "wall_us_per_call": round(wall / calls * 1e6, 2),
        # a call's own time inside its worker
        "worker_us_per_call": round(
            sum(r[2] for r in results) / calls * 1e6, 2), **more}),
        flush=True)


def spin(stop: threading.Event, count: list) -> None:
    """Pure Python on one thread: the walk's own interpreter work."""
    n = 0
    while not stop.is_set():
        for _ in range(1000):
            n += 1
    count.append(n)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147484999)
    ap.add_argument("--tree", default="",
                    help="read this directory instead of building one")
    args = ap.parse_args()
    work = ""
    if args.tree:
        root = args.tree
    else:
        sys.path.insert(0, os.path.join(HERE, "..", "benchmark"))
        import gen_tree
        with open(os.path.join(HERE, "..", "benchmark", "configs",
                               "source-tree-1chip.json")) as f:
            sizes = json.load(f)["sizes"]
        work = tempfile.mkdtemp(prefix="fs-call-probe-")
        t0 = time.perf_counter()
        (facts,) = gen_tree.build_trees(sizes, [0], work, args.seed)
        root = facts["path"]
        print(json.dumps({"probe": "built", "files": facts["files"],
                          "s": round(time.perf_counter() - t0, 1)}),
              flush=True)
    try:
        paths, n_dirs, listing_s = list_tree(root)
        runs = [paths[i:i + RUN] for i in range(0, len(paths), RUN)]
        print(json.dumps({
            "probe": "listing", "files": len(paths), "dirs": n_dirs,
            "s": round(listing_s, 3), "cpus": os.cpu_count(),
            "us_per_dir": round(listing_s / n_dirs * 1e6, 1)}),
            flush=True)
        print(json.dumps({"probe": "by_call_us", **by_call(paths)}),
              flush=True)
        by_path(paths[:8000])
        for n in (1, 2, 4, 8, 16):
            with ThreadPoolExecutor(n) as pool:
                t0 = time.perf_counter()
                results = list(pool.map(read_run, runs))
                wall = time.perf_counter() - t0
            report("threads", n, wall, results)
        # the same beside a thread that never lets go of the
        # interpreter but at the switch interval: the walk's Python
        # (a share of the runs: every call then waits out the 5 ms)
        runs_beside = runs[:len(runs) // 8 + 1]
        for n in (4, 8):
            stop, count = threading.Event(), []
            spinner = threading.Thread(target=spin, args=(stop, count))
            spinner.start()
            with ThreadPoolExecutor(n) as pool:
                t0 = time.perf_counter()
                results = list(pool.map(read_run, runs_beside))
                wall = time.perf_counter() - t0
            stop.set()
            spinner.join()
            report("threads_beside_python", n, wall, results,
                   spinner_loops_per_s=round(count[0] / wall))
        ctx = multiprocessing.get_context("spawn")
        for n in (1, 2, 4, 8):
            with ctx.Pool(n) as pool:
                pool.map(len, [[]] * n * 4)       # the workers are up
                t0 = time.perf_counter()
                results = pool.map(read_run, runs, chunksize=4)
                wall = time.perf_counter() - t0
            report("processes", n, wall, results)
    finally:
        if work:
            shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
