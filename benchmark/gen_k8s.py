"""Data for the cluster deployment (``k8s-cluster-4chip``): a cluster's
workload manifests, as ``kubectl get -o yaml`` exports them, and one
docker-save tar an image they name, under the name
``trivy-tpu k8s --images-dir`` resolves a reference to (the reference
with ``/``, ``:`` and ``@`` as ``_``, plus ``.tar``). Made from a seed
with ``gen``'s helpers and none of the program.

Which image a workload runs is no draw: the configuration's
``cluster`` says how many namespaces of which workload kinds, which
workloads carry the shared sidecar and which one of the shared init
images, and how many app images there are. Each app image is named
by one workload first, and the workloads left draw theirs by a
stride schedule over Zipf weights (``gen_shared``), the same on
every seed. The seed makes each image's bytes, packages and secrets
(``gen.build_image``, an image's own ``n``).

Run as a command it prints the plan of a configuration:
``python3 benchmark/gen_k8s.py benchmark/configs/k8s-cluster-4chip.json``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import yaml

import gen
from gen_shared import stride_schedule, zipf_weights

GEN_PROCS = 6
REGISTRY = "registry.local"


def image_file(ref: str) -> str:
    """The tar's name for ``ref`` (``k8s.resolve_image_ref``'s
    contract, written down here again)."""
    return "".join("_" if ch in "/:@" else ch for ch in ref) + ".tar"


def plan(cluster: dict) -> dict:
    """``{"workloads": [...], "refs": [...]}``: every workload as
    ``{namespace, kind, name, init, containers}`` (image refs), and
    the distinct refs, an image's ``n`` its index there."""
    apps = [f"{REGISTRY}/apps/app-{i:03d}:1.{i % 7}"
            for i in range(cluster["app_images"])]
    sidecar = f"{REGISTRY}/mesh/proxy:1.27"
    inits = [f"{REGISTRY}/platform/init-{j}:2.0"
             for j in range(cluster["init_images"])]
    kinds = [k for k, n in cluster["kinds"] for _ in range(n)]
    n_app = cluster["namespaces"] * len(kinds)
    first = list(range(len(apps)))
    app_of = first + stride_schedule(
        zipf_weights(len(apps), cluster["zipf_s"]),
        n_app - len(first))
    workloads = []
    for w in range(n_app):
        ns, kind = divmod(w, len(kinds))
        kind = kinds[kind]
        containers = [apps[app_of[w]]]
        if w % cluster["sidecar_every"] == 0:
            containers.append(sidecar)
        init = []
        if w % cluster["init_every"] == 1:
            init = [inits[(w // cluster["init_every"]) % len(inits)]]
        workloads.append({
            "namespace": f"tenant-{ns:02d}", "kind": kind,
            "name": f"{kind.lower()}-{w % len(kinds):02d}",
            "init": init, "containers": containers})
    daemons = []
    for name in cluster["daemonsets"]:
        ref = f"{REGISTRY}/kube-system/{name}:1.0"
        daemons.append(ref)
        workloads.append({"namespace": "kube-system",
                          "kind": "DaemonSet", "name": name,
                          "init": [], "containers": [ref]})
    refs = apps + [sidecar] + inits + daemons
    return {"workloads": workloads, "refs": refs}


def manifest(w: dict) -> dict:
    """One workload object as the API server exports it."""
    pod = {"containers": [
        {"name": f"c{i}", "image": ref,
         "resources": {"requests": {"cpu": "100m"}}}
        for i, ref in enumerate(w["containers"])]}
    if w["init"]:
        pod["initContainers"] = [{"name": f"init{i}", "image": ref}
                                 for i, ref in enumerate(w["init"])]
    labels = {"app": w["name"]}
    template = {"metadata": {"labels": labels}, "spec": pod}
    if w["kind"] == "Pod":
        spec = pod
    elif w["kind"] == "CronJob":
        spec = {"schedule": "0 3 * * *", "jobTemplate": {
            "spec": {"template": dict(template, spec=dict(
                pod, restartPolicy="OnFailure"))}}}
    elif w["kind"] == "Job":
        spec = {"template": dict(template, spec=dict(
            pod, restartPolicy="Never"))}
    else:
        spec = {"selector": {"matchLabels": labels},
                "template": template}
        if w["kind"] in ("Deployment", "StatefulSet"):
            spec["replicas"] = 2
    group = {"Pod": "v1", "Job": "batch/v1", "CronJob": "batch/v1"}
    return {"apiVersion": group.get(w["kind"], "apps/v1"),
            "kind": w["kind"],
            "metadata": {"name": w["name"],
                         "namespace": w["namespace"],
                         "labels": labels},
            "spec": spec}


def write_manifests(workloads: list, directory: str) -> None:
    """One multi-document file a namespace."""
    os.makedirs(directory, exist_ok=True)
    by_ns: dict = {}
    for w in workloads:
        by_ns.setdefault(w["namespace"], []).append(manifest(w))
    for ns, docs in by_ns.items():
        with open(os.path.join(directory, f"{ns}.yaml"), "w",
                  encoding="utf-8") as f:
            yaml.safe_dump_all(docs, f, sort_keys=False)


def write_images(sizes: dict, refs: list, first_n: int,
                 directory: str, seed: int) -> dict:
    """``refs[k]`` as ``gen.build_image`` image ``first_n + k`` under
    its resolved name; a few generator processes (``gen.py`` run as
    a command: numpy, never jax) where there are many. Returns
    ``{ref: facts}``."""
    ns = list(range(first_n, first_n + len(refs)))
    procs = min(GEN_PROCS, os.cpu_count() or 1, len(ns) // 16)
    if procs < 2:
        items = gen.build_images(sizes, ns, directory, seed)
    else:
        jobs = [subprocess.Popen(
            [sys.executable, gen.__file__, json.dumps(
                {"sizes": sizes, "ns": ns[k::procs],
                 "directory": directory, "seed": seed})],
            stdout=subprocess.PIPE) for k in range(procs)]
        items = []
        for job in jobs:
            out, _ = job.communicate()
            if job.returncode != 0:
                raise RuntimeError(f"gen.py exit {job.returncode}")
            items += json.loads(out)
        items.sort(key=lambda it: it["path"])
    facts = {}
    for ref, item in zip(refs, items):
        path = os.path.join(directory, image_file(ref))
        os.rename(item["path"], path)
        facts[ref] = dict(item, path=path)
    return facts


def make(sizes: dict, cluster: dict, warm_images: int, work: str,
         seed: int) -> dict:
    """The cluster's manifests, a warm-up cluster of ``warm_images``
    workloads in one namespace with images of their own, and every
    image; returns the directories and ``{ref: facts}``."""
    p = plan(cluster)
    images = os.path.join(work, "images")
    os.makedirs(images, exist_ok=True)
    warm = [{"namespace": "warmup", "kind": "Deployment",
             "name": f"warm-{k}", "init": [],
             "containers": [f"{REGISTRY}/warmup/app-{k}:1.0"]}
            for k in range(warm_images)]
    warm_refs = [w["containers"][0] for w in warm]
    facts = write_images(sizes, p["refs"] + warm_refs, 0, images,
                         seed)
    write_manifests(p["workloads"], os.path.join(work, "cluster"))
    write_manifests(warm, os.path.join(work, "warmup"))
    return {"cluster": os.path.join(work, "cluster"),
            "warmup": os.path.join(work, "warmup"),
            "images": images, "refs": p["refs"], "facts": facts,
            "workloads": len(p["workloads"]),
            "image_refs": sum(len(w["init"]) + len(w["containers"])
                              for w in p["workloads"])}


if __name__ == "__main__":
    with open(sys.argv[1], encoding="utf-8") as f:
        cfg = json.load(f)
    got = plan(cfg["cluster"])
    refs = [r for w in got["workloads"]
            for r in w["init"] + w["containers"]]
    print(json.dumps({"workloads": len(got["workloads"]),
                      "image_refs": len(refs),
                      "images_distinct": len(set(refs)),
                      "refs": len(got["refs"])}))
