"""What the walk of ``trivy fs`` pays a file on this host, step by
step, with no device in it (PERF.md section 6, PR 36).

Builds one tree of the configuration ``source-tree-1chip``
(``benchmark/gen_tree.py``, 40,337 files, 536 MB) under ``TMPDIR``
and walks it on one thread as ``LocalFSArtifact`` does, four ways,
``--repeat`` times each, a seconds-a-tree line each:

``gates``       the analyzers' gates alone, asked as the walk asks
                them (``AnalyzerGroup.wanted`` without a size, then
                with it for whoever said maybe), over the tree's
                paths and sizes held in a list: no file is touched;
``walk_dict``   ``iter_fs`` and ``analyze_file`` whole, with a gate
                that answers both of the walk's questions from a
                dictionary made beforehand: the walk without what a
                gate costs;
``walk``        ``iter_fs`` and ``analyze_file`` whole, the group's
                own gate: what a tree costs ``ingest.tree_walk``
                before a part is cut;
``calls``       the walk's system calls alone: every directory
                opened and listed, every wanted file opened from its
                directory's descriptor, read and closed.

``walk`` less ``walk_dict`` is what an index of the gates can give
back at the most, and ``walk_dict`` less ``calls`` is the walk's
other steps a file. Where the group counts them
(``gate_files``, ``gate_probes``), the ``required`` calls a file are
printed too. No JAX; imports ``trivy_tpu.artifact`` from the checkout
it lies in.

    python3 examples/walk_gate_probe.py [--seed N] [--tree DIR]
                                        [--repeat N]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))


def fs_group():
    """The analyzers of ``trivy fs`` with its default checks."""
    from trivy_tpu.artifact.artifact import LocalFSArtifact
    from trivy_tpu.artifact.cache import MemoryCache
    return LocalFSArtifact(".", MemoryCache()).group


def gate_counts(group) -> dict:
    files = getattr(group, "gate_files", None)
    if not files:
        return {}
    return {"required_calls_per_file":
            round(group.gate_probes / files, 3)}


def list_tree(root: str) -> list:
    """(rel_path, size) of the tree's files, the walk's order."""
    from trivy_tpu.artifact.walker import iter_fs
    return [(rel, os.lstat(os.path.join(root, rel)).st_size)
            for rel, _, _, _ in iter_fs(root)]


def gates(listed: list) -> tuple:
    group = fs_group()
    wanted = group.wanted
    n = 0
    t0 = time.perf_counter()
    for rel, size in listed:
        maybe = wanted(rel, None)
        if maybe and wanted(rel, size, maybe):
            n += 1
    return time.perf_counter() - t0, n, gate_counts(group)


def walk(root: str, answers: dict = None) -> tuple:
    """The walk as ``LocalFSArtifact.inspect`` makes it; the secret
    candidates are let go as a part's cutter would take them."""
    from trivy_tpu.analyzer.analyzer import AnalysisResult
    from trivy_tpu.artifact.walker import iter_fs
    group = fs_group()
    gate = group.wanted
    if answers is not None:
        def gate(rel, size, among=None):
            return answers[rel][among is not None]
    result = AnalysisResult()
    n = 0
    t0 = time.perf_counter()
    for path, size, read, wanted in iter_fs(root, gate=gate):
        group.analyze_file(result, path, read, size, wanted)
        if result.secret_candidates:
            n += len(result.secret_candidates)
            result.secret_candidates = []
    return time.perf_counter() - t0, n, gate_counts(group)


def calls(root: str, answers: dict) -> tuple:
    """The system calls of ``iter_fs`` and no other step of it."""
    from trivy_tpu.artifact.walker import _read_fd
    n = 0
    t0 = time.perf_counter()
    stack = [(root, "")]
    while stack:
        dirpath, rel_dir = stack.pop()
        dfd = os.open(dirpath, os.O_RDONLY | os.O_DIRECTORY)
        try:
            with os.scandir(dfd) as it:
                entries = list(it)
            for e in entries:
                if e.is_dir(follow_symlinks=False):
                    stack.append((os.path.join(dirpath, e.name),
                                  rel_dir + e.name + "/"))
                elif answers[rel_dir + e.name][0]:
                    fd = os.open(e.name, os.O_RDONLY, dir_fd=dfd)
                    try:
                        size = os.fstat(fd).st_size
                        if answers[rel_dir + e.name][1]:
                            _read_fd(fd, size)
                            n += 1
                    finally:
                        os.close(fd)
        finally:
            os.close(dfd)
    return time.perf_counter() - t0, n, {}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147484999)
    ap.add_argument("--tree", default="",
                    help="walk this directory instead of building one")
    ap.add_argument("--repeat", type=int, default=3)
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
        work = tempfile.mkdtemp(prefix="walk-gate-probe-")
        t0 = time.perf_counter()
        (facts,) = gen_tree.build_trees(sizes, [0], work, args.seed)
        root = facts["path"]
        print(json.dumps({"probe": "built", "files": facts["files"],
                          "s": round(time.perf_counter() - t0, 1)}),
              flush=True)
    try:
        listed = list_tree(root)
        group = fs_group()
        answers = {}
        for rel, size in listed:
            maybe = group.wanted(rel, None)
            answers[rel] = (maybe, maybe and
                            group.wanted(rel, size, maybe))
        print(json.dumps({
            "probe": "listed", "files": len(listed),
            "wanted_at_some_size": sum(
                bool(a[0]) for a in answers.values()),
            "wanted": sum(bool(a[1]) for a in answers.values())}),
            flush=True)
        steps = (("gates", lambda: gates(listed)),
                 ("walk_dict", lambda: walk(root, answers)),
                 ("walk", lambda: walk(root)),
                 ("calls", lambda: calls(root, answers)))
        for _ in range(args.repeat):
            for name, step in steps:
                s, n, more = step()
                print(json.dumps({
                    "probe": name, "s_per_tree": round(s, 3),
                    "us_per_file": round(s / len(listed) * 1e6, 2),
                    "read": n, **more}), flush=True)
    finally:
        if work:
            shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
