"""The plain reference for a cluster sweep (``k8s-sweep``): what
``trivy k8s --report summary cluster`` reports of the cluster
``gen_k8s`` wrote, worked out from the manifests and ``gen``'s facts
with nothing of the program (no import of ``trivy_tpu``).

The manifests are read with a YAML parser of their own: every object
whose kind carries a pod spec (a bare Pod's ``spec``, a CronJob's
``spec.jobTemplate.spec.template.spec``, every other kind's
``spec.template.spec``) is a workload, and its image refs are those
of its ``initContainers`` and ``containers``. Each distinct ref gets
its image's findings from ``reference.image_findings``:
vulnerabilities as (package, installed, id, fixed, severity), secrets
as (target, rule, line), exactly the six planted in the image. A
resource, (namespace, kind, name), holds the union over its images,
how many image refs it names, and the summary's counts by severity,
which add each of its images' vulnerabilities up (Trivy's summary
counts a resource's results, an image's result each).
"""

from __future__ import annotations

import os

import yaml

import reference

SEVS = ("CRITICAL", "HIGH", "MEDIUM", "LOW", "UNKNOWN")


def _pod(doc: dict) -> dict:
    spec = doc.get("spec") or {}
    if doc["kind"] == "Pod":
        return spec
    if doc["kind"] == "CronJob":
        spec = (spec.get("jobTemplate") or {}).get("spec") or {}
    return (spec.get("template") or {}).get("spec") or {}


def workloads(directory: str) -> dict:
    """``{(namespace, kind, name): [image ref, ...]}`` of every
    object with containers under ``directory``."""
    out = {}
    for name in sorted(os.listdir(directory)):
        with open(os.path.join(directory, name),
                  encoding="utf-8") as f:
            for doc in yaml.safe_load_all(f):
                pod = _pod(doc)
                refs = [c["image"] for key in ("initContainers",
                                               "containers")
                        for c in pod.get(key) or []]
                if refs:
                    meta = doc["metadata"]
                    out[(meta.get("namespace", ""), doc["kind"],
                         meta["name"])] = refs
    return out


def cluster_findings(table, directory: str, facts: dict, checks,
                     control: bool = False) -> dict:
    """``{resource: {"refs", "vulns", "secrets", "counts"}}`` for the
    cluster under ``directory``, ``facts`` the images' by ref."""
    per_image = {}
    out = {}
    for key, refs in workloads(directory).items():
        vulns, secrets = set(), set()
        counts = dict.fromkeys(SEVS, 0)
        for ref in refs:
            if ref not in per_image:
                per_image[ref] = reference.image_findings(
                    table, facts[ref], checks, control)
            got = per_image[ref]
            vulns |= got["vulns"]
            secrets |= got["secrets"]
            for v in got["vulns"]:
                counts[v[4]] += 1
        out[key] = {"refs": len(refs), "vulns": vulns,
                    "secrets": secrets, "counts": counts}
    return out


def cluster_jobs(table, facts: dict, refs) -> set:
    """The interval jobs a sweep of the images ``refs`` cannot do
    without."""
    jobs = set()
    for ref in refs:
        jobs |= reference.image_jobs(table, facts[ref])
    return jobs
