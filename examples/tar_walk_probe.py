"""What walking an image's layer tars costs on this host, one pool image
of ``image-fleet-1chip`` at a time (PERF.md section 6).

Builds one image with ``benchmark/gen.build_image`` at the
configuration's sizes (3 layers, 565 text files, the package database
and the requirements), reads its layers into memory as the docker-save
opener does, and times, per image, on one thread and with no JAX:

(a) ``tarfile``: ``tarfile.open`` over a ``BytesIO``,
    ``collect_layer_tar`` with a budget, every regular file read
    through its ``read_fn``: the walk every layer took before the
    index, and the one a layer the index refuses still takes;
(b) ``index``: the same through ``guard.safetar.LayerTar``
    (``index_tar``'s one pass, a slice a file);
(c) each of the two inside the walk: ``AnalyzerGroup.analyze_file`` on
    every file as ``ImageArtifact._analyze_layers`` calls it, and the
    analyzers alone over the same files already read, so that the walk
    inside the walk is the difference (Python between calls costs more
    there than in a loop of its own: PERF.md section 6).

It checks that (a) and (b) give the same files, bytes, opaque
directories and whiteouts, and prints one JSON line.

    python3 examples/tar_walk_probe.py [--repeat 30] [--seed N]

Copied into a checkout without ``LayerTar`` it times the ``tarfile``
half of (a) and (c) only.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import statistics
import sys
import tarfile
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]

import gen  # noqa: E402
from trivy_tpu.analyzer import AnalyzerGroup  # noqa: E402
from trivy_tpu.analyzer.analyzer import AnalysisResult  # noqa: E402
from trivy_tpu.artifact.artifact import (ArtifactOption,  # noqa: E402
                                         _effective_disabled)
from trivy_tpu.artifact.walker import collect_layer_tar  # noqa: E402
from trivy_tpu.guard.budget import make_budget  # noqa: E402

try:
    from trivy_tpu.guard.safetar import LayerTar
except ImportError:              # a checkout from before the index
    LayerTar = None


def layers_of(seed: int) -> list:
    """The uncompressed layer tars of one pool image."""
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "image-fleet-1chip.json")))
    blocks = gen.text_blocks(np.random.default_rng([seed, 1]), 256)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "img.tar")
        gen.build_image(cfg["sizes"], 0, path, seed, blocks)
        with tarfile.open(path) as outer:
            names = json.load(outer.extractfile("manifest.json"))[0][
                "Layers"]
            return [outer.extractfile(n).read() for n in names]


if LayerTar is not None:
    class TarfileLayer(LayerTar):
        """A layer the index is not asked of: ``tarfile`` walks it."""
        __slots__ = ()

        def index(self, limit=None):
            return None


def open_tarfile(data: bytes):
    if LayerTar is None:
        return tarfile.open(fileobj=io.BytesIO(data))
    return TarfileLayer(data)


def walk(layers: list, opener) -> list:
    """Every layer walked and every file read: [(files, opq, wh)]."""
    out = []
    budget = make_budget(None, name="probe")
    for data in layers:
        with opener(data) as tf:
            files, opq, wh = collect_layer_tar(tf, budget=budget)
            out.append(([(p, s, read()) for p, s, read in files],
                        opq, wh))
    return out


def analyze(layers: list, opener, group) -> None:
    budget = make_budget(None, name="probe")
    for data in layers:
        result = AnalysisResult()
        with opener(data) as tf:
            files, _opq, _wh = collect_layer_tar(tf, budget=budget)
            for path, size, read in files:
                group.analyze_file(result, path, read, size)


def analyze_read(read_files: list, group) -> None:
    for files in read_files:
        result = AnalysisResult()
        for path, size, content in files:
            group.analyze_file(result, path, lambda c=content: c, size)


def clock(fn, repeat: int) -> dict:
    fn()                                     # warm
    times = []
    for _ in range(repeat):
        t = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t) * 1e3)
    return {"min_ms": round(min(times), 3),
            "median_ms": round(statistics.median(times), 3)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--repeat", type=int, default=30)
    ap.add_argument("--seed", type=int, default=2147483647)
    args = ap.parse_args()
    layers = layers_of(args.seed)
    group = AnalyzerGroup(disabled=_effective_disabled(ArtifactOption()),
                          file_patterns={})
    openers = {"tarfile": open_tarfile}
    if LayerTar is not None:
        openers["index"] = LayerTar
    walked = {k: walk(layers, o) for k, o in openers.items()}
    out = {"layers": len(layers),
           "layer_bytes": sum(len(d) for d in layers),
           "files": sum(len(f) for f, _o, _w in walked["tarfile"]),
           "file_bytes": sum(len(c) for f, _o, _w in walked["tarfile"]
                             for _p, _s, c in f)}
    if "index" in walked:
        out["same"] = walked["index"] == walked["tarfile"]
    read_files = [f for f, _o, _w in walked["tarfile"]]
    out["analyzers_alone"] = clock(
        lambda: analyze_read(read_files, group), args.repeat)
    for k, o in openers.items():
        out[f"walk.{k}"] = clock(lambda o=o: walk(layers, o),
                                 args.repeat)
        out[f"in_walk.{k}"] = clock(lambda o=o: analyze(layers, o, group),
                                    args.repeat)
    print(json.dumps(out))
    return 0 if out.get("same", True) else 1


if __name__ == "__main__":
    sys.exit(main())
