"""Kubernetes scanning (reference: pkg/k8s/{commands,scanner,report}
+ the external trivy-kubernetes enumerator).

The reference enumerates cluster artifacts (workload images + raw
manifests) through the Kubernetes API and loops them SEQUENTIALLY
through the artifact runner (scanner.go:58-78). Here the enumerator
is a seam: ``ManifestClient`` walks exported/declared manifests (this
environment has no cluster API; a live client plugs into the same
``artifacts()`` contract), and the scan fans the cluster's distinct
images through the command's fleet runner (:func:`build_runner`: the
scheduler, and a mesh over every visible chip) — SURVEY §2.6's fleet
case — and maps each image's findings back to every workload that
names it.
"""

from __future__ import annotations

import os
import re
from dataclasses import dataclass, field
from typing import Optional

from ..obs.trace import phase_span
from ..runtime import BatchScanRunner
from ..utils import get_logger
from .metrics import K8S_METRICS

log = get_logger("k8s")

try:
    import yaml as yaml_mod
except ImportError:              # pragma: no cover
    yaml_mod = None

# workload kinds that carry pod specs (trivy-kubernetes artifacts.go)
WORKLOAD_KINDS = ("Pod", "Deployment", "StatefulSet", "DaemonSet",
                  "ReplicaSet", "ReplicationController", "Job",
                  "CronJob")


@dataclass
class Artifact:
    """One cluster object (ref trivy-kubernetes artifacts.Artifact)."""

    kind: str = ""
    name: str = ""
    namespace: str = ""
    images: list = field(default_factory=list)
    raw: dict = field(default_factory=dict)


@dataclass
class Resource:
    """Per-object findings (ref pkg/k8s/report/report.go:58-69)."""

    namespace: str = ""
    kind: str = ""
    name: str = ""
    results: list = field(default_factory=list)
    error: str = ""

    def to_dict(self) -> dict:
        d = {"Kind": self.kind, "Name": self.name}
        if self.namespace:
            d["Namespace"] = self.namespace
        if self.results:
            d["Results"] = [r.to_dict() for r in self.results]
        if self.error:
            d["Error"] = self.error
        return d


@dataclass
class K8sReport:
    """ref report.go:42-48."""

    cluster_name: str = ""
    vulnerabilities: list = field(default_factory=list)
    misconfigurations: list = field(default_factory=list)

    def to_dict(self) -> dict:
        d = {"ClusterName": self.cluster_name}
        if self.vulnerabilities:
            d["Vulnerabilities"] = [r.to_dict()
                                    for r in self.vulnerabilities]
        if self.misconfigurations:
            d["Misconfigurations"] = [
                r.to_dict() for r in self.misconfigurations]
        return d


def _pod_spec(doc: dict) -> dict:
    spec = doc.get("spec") or {}
    if doc.get("kind") == "CronJob":
        spec = ((spec.get("jobTemplate") or {}).get("spec") or {})
    return ((spec.get("template") or {}).get("spec")) or spec


def _images(doc: dict) -> list:
    pod = _pod_spec(doc)
    return [c.get("image", "")
            for key in ("initContainers", "containers")
            for c in pod.get(key) or [] if c.get("image")]


def images_from_object(doc: dict) -> list:
    """Pod-spec image references of one workload object — bare Pods
    and every templated WORKLOAD_KIND alike. Shared by the cluster
    scanner and the admission webhook (watch/admission.py), so both
    agree on what "the images of this object" means."""
    if not isinstance(doc, dict):
        return []
    return _images(doc)


class ManifestClient:
    """Artifact enumerator over manifest files — the stand-in for the
    live-cluster client (same ``artifacts()`` contract)."""

    def __init__(self, path: str):
        self.path = path
        self.cluster_name = os.path.basename(
            path.rstrip("/")) or path

    def _files(self):
        if os.path.isfile(self.path):
            yield self.path
            return
        for dirpath, _, names in os.walk(self.path):
            for name in sorted(names):
                if name.endswith((".yaml", ".yml", ".json")):
                    yield os.path.join(dirpath, name)

    def artifacts(self) -> list:
        out = []
        for fp in self._files():
            try:
                with open(fp, "rb") as f:
                    docs = list(yaml_mod.safe_load_all(
                        f.read().decode("utf-8", "replace")))
            except (OSError, yaml_mod.YAMLError) as e:
                log.warning("skipping %s: %s", fp, e)
                continue
            for doc in docs:
                if not isinstance(doc, dict) or "kind" not in doc:
                    continue
                meta = doc.get("metadata") or {}
                out.append(Artifact(
                    kind=doc.get("kind", ""),
                    name=meta.get("name", ""),
                    namespace=meta.get("namespace", ""),
                    images=_images(doc)
                    if doc.get("kind") in WORKLOAD_KINDS else [],
                    raw=doc))
        return out


def _sanitize_ref(ref: str) -> str:
    return re.sub(r"[/:@]", "_", ref)


def resolve_image_ref(images_dir: str, ref: str) -> Optional[str]:
    """image ref → local tarball named ``<ref with /:@ as _>.tar``
    (the zero-egress stand-in for a registry pull). ONE copy of the
    naming contract — the cluster scanner and the watch/admission
    resolvers (watch/source.dir_resolver) both call it."""
    if not images_dir:
        return None
    for cand in (f"{_sanitize_ref(ref)}.tar",
                 f"{_sanitize_ref(ref.split('/')[-1])}.tar"):
        path = os.path.join(images_dir, cand)
        if os.path.exists(path):
            return path
    return None


def build_runner(store=None, *, backend: str = "tpu", sched="on",
                 sched_config=None, mesh="visible") -> BatchScanRunner:
    """The runner ``trivy-tpu k8s`` sweeps a cluster's images with,
    built in one place so that the command and the benchmark's
    cluster cell sweep alike: the fleet path (``sched``: ``"on"``, a
    ``SchedConfig``, or ``"off"`` for the direct single batch) and,
    where jax sees more than one device, a mesh over all of them
    (``runtime.device.visible_mesh``) on which every sieve dispatch
    and interval wave is sharded. ``mesh=None`` keeps one device.
    The caller closes the runner."""
    if isinstance(mesh, str):
        from ..runtime.device import visible_mesh
        mesh = visible_mesh(backend)
    return BatchScanRunner(store=store, backend=backend, mesh=mesh,
                           sched=sched, sched_config=sched_config)


class K8sScanner:
    """ref pkg/k8s/scanner/scanner.go:30-78, with the sequential
    artifact loop replaced by one fleet scan of the cluster's
    distinct images. ``runner`` (:func:`build_runner`'s) is the
    caller's, who closes it; without one a sweep builds a
    one-device runner of its own and closes it at its end."""

    def __init__(self, store=None, backend: str = "tpu",
                 images_dir: str = "", security_checks=None,
                 runner: Optional[BatchScanRunner] = None):
        self.store = store
        self.backend = backend
        self.images_dir = images_dir
        self.security_checks = security_checks or ["vuln", "config"]
        self.runner = runner

    def scan(self, client) -> K8sReport:
        images = "vuln" in self.security_checks or \
            "secret" in self.security_checks
        with phase_span("enumerate", pipeline="k8s"):
            artifacts = client.artifacts()
            # (artifact, ref, path|None) for every container image
            owners = [(a, ref, self._resolve(ref))
                      for a in artifacts for ref in a.images] \
                if images else []
        report = K8sReport(cluster_name=client.cluster_name)

        if "config" in self.security_checks or \
                "rbac" in self.security_checks:
            report.misconfigurations = [
                self._scan_misconfig(a) for a in artifacts]

        if images:
            report.vulnerabilities = self._scan_images(artifacts,
                                                       owners)
        return report

    # -- misconfigs: the manifests themselves --

    def _scan_misconfig(self, artifact: Artifact) -> Resource:
        from ..misconf import scan_config_files
        from ..scan.local import _to_detected_misconf
        from ..types import ConfigFile, Result
        from ..types.report import ResultClass

        raw = yaml_mod.safe_dump(artifact.raw).encode()
        results = []
        for mc in scan_config_files([ConfigFile(
                type="yaml",
                file_path=f"{artifact.namespace or 'default'}/"
                          f"{artifact.kind}/{artifact.name}",
                content=raw)]):
            detected = [
                _to_detected_misconf(f, "CRITICAL", "FAIL", mc.layer)
                for f in mc.failures]
            detected += [
                _to_detected_misconf(s, "UNKNOWN", "PASS", mc.layer)
                for s in mc.successes]
            results.append(Result(
                target=mc.file_path, class_=ResultClass.CONFIG,
                type=mc.file_type, misconfigurations=detected))
        return Resource(namespace=artifact.namespace,
                        kind=artifact.kind, name=artifact.name,
                        results=results)

    # -- vulns: every distinct image in the cluster, one fleet scan --

    def _scan_images(self, artifacts: list, owners: list) -> list:
        # shared images scan once, in the order first named
        paths = list(dict.fromkeys(p for _, _, p in owners if p))
        K8S_METRICS.note_sweep(
            workloads=sum(1 for a in artifacts if a.images),
            image_refs=len(owners), images_distinct=len(paths),
            images_unresolved=sum(1 for o in owners if o[2] is None))
        by_path: dict = {}
        if paths:
            from ..types import ScanOptions
            options = ScanOptions(
                security_checks=[c for c in self.security_checks
                                 if c in ("vuln", "secret")],
                backend=self.backend)
            runner = self.runner
            if runner is None:
                runner = build_runner(self.store,
                                      backend=self.backend, mesh=None)
            try:
                if runner.sched == "on":
                    runner.scheduler.k8s_metrics = K8S_METRICS
                by_path = dict(zip(paths, runner.scan_paths(
                    paths, options)))
            finally:
                if runner is not self.runner:
                    runner.close()

        with phase_span("report", pipeline="k8s"):
            out = []
            for a, ref, path in owners:
                res = by_path.get(path)
                if res is None:
                    error, results = f"image not resolvable: {ref}", []
                else:
                    error = res.error
                    results = [] if res.error else res.report.results
                out.append(Resource(
                    namespace=a.namespace, kind=a.kind, name=a.name,
                    results=results, error=error))
        return out

    def _resolve(self, ref: str) -> Optional[str]:
        return resolve_image_ref(self.images_dir, ref)
