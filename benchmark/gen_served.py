"""Data for the served deployment (``served-1chip``): the builds an
organisation's CI runners push to a central server, made from a seed
with ``gen``'s helpers and none of the program. ``gen_shared`` cannot
give these: its layers are text files by the measured size
distribution, and a served request carries no file (the client
analyzes; the server sees package lists).

A base image is two layers that hold the release file and the package
database alone (400 apk or dpkg packages, every other one
advisory-bearing, as ``gen_shared.build_base`` draws them), shared
byte for byte by every build on it, so that their diff IDs and cache
keys are the same. A build is a base and one layer of its own, which
nobody has seen: a ``requirements.txt`` of 120 pins (as
``gen_shared.build_tag`` draws them).

Which base the k-th build sits on is no draw: a stride schedule over
Zipf weights (``gen_shared.stride_schedule``), the same on every seed,
so every run meets the bases' first sights at the same builds. The
seed makes the packages, the pins and the arrival times: gamma
inter-arrival times of the traffic file's shape (0.5: a coefficient
of variation of 1.41, burstier than a Poisson process) about its mean
rate.
"""

from __future__ import annotations

import hashlib

import numpy as np

from gen import DISTROS, _layer_tar, lib_version, os_version
from gen_shared import stride_schedule, write_tag_tar, zipf_weights


def plan(traffic: dict, builds: int, seed: int) -> dict:
    """``base_of[k]`` and ``at[k]`` (seconds from the window's
    start) for builds 0 to ``builds`` - 1, and ``first[k]``: whether
    build k is the first on its base."""
    base_of = stride_schedule(
        zipf_weights(traffic["bases"], traffic["base_zipf_s"]),
        builds)
    shape = traffic["arrival_gamma_shape"]
    rng = np.random.default_rng([seed, 9])
    gaps = rng.gamma(shape, 1.0 / (shape * traffic["rate_per_s"]),
                     builds)
    seen, first = set(), []
    for b in base_of:
        first.append(b not in seen)
        seen.add(b)
    return {"base_of": base_of,
            "at": [float(t) for t in np.cumsum(gaps)],
            "first": first}


def build_base(sz: dict, b: int, seed: int) -> dict:
    """Base ``b``: its layer tars (the release file in the first,
    the package database in the last), their diff IDs and its
    advisory-bearing packages."""
    rng = np.random.default_rng([seed, 5, b])
    d = b % 2
    family, _bucket, relfile, relver, dbpath = DISTROS[d]
    pkgs, os_pkgs = [], []
    ids = rng.choice(sz["os_universe"], sz["os_pkgs"] // 2 + 1,
                     replace=False)
    for k in range(sz["os_pkgs"]):
        bb, c, r = (int(x) for x in rng.integers(0, (10, 10, 4)))
        if k % 2 == 0:
            i = int(ids[k // 2])
            name = f"{family}-pkg-{i}"
            os_pkgs.append((i, bb, c, r))
        else:
            name = f"{family}-other-b{b}-{k}"
        pkgs.append((name, os_version(family, bb, c, r)))
    if family == "alpine":
        pkgdb = "".join(f"P:{p}\nV:{v}\no:{p}\nL:MIT\n\n"
                        for p, v in pkgs)
    else:
        pkgdb = "".join(
            f"Package: {p}\nStatus: install ok installed\n"
            f"Version: {v}\nArchitecture: amd64\n\n"
            for p, v in pkgs)
    layers = [{} for _ in range(sz["base_layers"])]
    layers[0][relfile] = relver.encode()
    layers[-1][dbpath] = pkgdb.encode()
    blobs = [_layer_tar(f) for f in layers]
    return {"blobs": blobs, "distro": d, "os_pkgs": os_pkgs,
            "diff_ids": ["sha256:" + hashlib.sha256(x).hexdigest()
                         for x in blobs]}


def build_top(sz: dict, k: int, b: int, base: dict, path: str,
              seed: int) -> dict:
    """Build ``k`` on base ``b``: its own layer and the tar. Returns
    the facts the reference needs."""
    rng = np.random.default_rng([seed, 6, k])
    pip = {}
    for j in range(sz["pip_pkgs"]):
        i = int(rng.integers(0, sz["ghsa_pkgs"])) if j % 2 == 0 \
            else sz["ghsa_pkgs"] + int(rng.integers(0, 10 ** 6))
        pip[f"pip-lib-{i}=={lib_version(i, j % 3)}"] = \
            (i, lib_version(i, j % 3))
    reqs = sorted(pip)
    layer = {"srv/app/requirements.txt":
             ("\n".join(reqs) + "\n").encode()}
    write_tag_tar(path, base, _layer_tar(layer),
                  f"registry/base{b}:build{k}")
    return {"path": path, "build": k, "base": b,
            "distro": base["distro"], "os_pkgs": base["os_pkgs"],
            "pip_pkgs": [pip[r] for r in reqs], "secrets": []}
