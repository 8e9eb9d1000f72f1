"""Image and filesystem artifacts (reference:
pkg/fanal/artifact/image/image.go + artifact/local/fs.go).

Inspect flow (image.go:75-257): compute content-addressed cache keys
per layer → ask the cache which are missing → analyze only those →
PutBlob. The reference analyzes layers in parallel goroutines with a
per-file semaphore; here every missing layer's files are analyzed on
the host (parsers are irregular), while ALL layers' secret candidates
go to the TPU in one batched sieve dispatch — the batch dimension
replaces the goroutine pool.
"""

from __future__ import annotations

import hashlib
import json
from dataclasses import dataclass, field
from typing import Optional

from ..analyzer import AnalyzerGroup
from ..analyzer.analyzer import AnalysisResult
from ..handler import handler_versions, post_handle
from ..types import (ArtifactInfo, ArtifactReference, BlobInfo,
                     ImageMetadata, Secret)
from ..utils import get_logger
from .cache import calc_key
from .image import ImageSource, guess_base_layers
from .metrics import INGEST_METRICS
from .walker import collect_layer_tar, iter_fs

log = get_logger("artifact")


@dataclass
class ArtifactOption:
    disabled_analyzers: list = field(default_factory=list)
    skip_dirs: list = field(default_factory=list)
    skip_files: list = field(default_factory=list)
    file_patterns: dict = field(default_factory=dict)
    no_progress: bool = True
    insecure: bool = False
    secret_scanner: object = None      # BatchSecretScanner (shared)
    scan_secrets: bool = True
    scan_misconfig: bool = False       # IaC config collection
    scan_licenses: bool = False        # license classification
    # ingest guards (trivy_tpu/guard, docs/robustness.md): ON by
    # default with DEFAULT_LIMITS; --no-ingest-guards turns them off
    # (the differential baseline). ``ingest_limits`` overrides the
    # limits; the per-target ResourceBudget itself is created fresh
    # per scan (never shared across targets).
    ingest_guards: bool = True
    ingest_limits: object = None       # ResourceLimits or None
    # secret rule-set fingerprint (secret.batch.rules_fingerprint):
    # cached blob CONTENT includes secret findings, so two rule
    # configurations must never share blob cache keys. Empty =
    # derive from ``secret_scanner`` (builtin when None).
    secret_rules_fp: str = ""


def _secret_scanner(opt: ArtifactOption):
    if opt.secret_scanner is None:
        from ..secret.batch import BatchSecretScanner
        opt.secret_scanner = BatchSecretScanner()
    return opt.secret_scanner


def _effective_disabled(opt: ArtifactOption) -> list:
    """Config collectors only run when misconfig scanning is on
    (the reference registers them behind the misconf option)."""
    disabled = list(opt.disabled_analyzers)
    if not opt.scan_misconfig:
        from ..analyzer.config import CONFIG_ANALYZER_TYPES
        disabled.extend(CONFIG_ANALYZER_TYPES)
    if not opt.scan_licenses:
        from ..analyzer.licensing import LICENSE_ANALYZER_TYPES
        disabled.extend(LICENSE_ANALYZER_TYPES)
    return disabled


class ImageArtifact:
    def __init__(self, image: ImageSource, cache,
                 option: Optional[ArtifactOption] = None,
                 budget=None):
        self.image = image
        self.cache = cache
        self.opt = option or ArtifactOption()
        # one ResourceBudget per target: prefer an explicit one, then
        # the budget the image was loaded under (so layer reads and
        # the walk charge the SAME counters), else a fresh one when
        # guards are on
        if budget is None:
            budget = getattr(image, "ingest_budget", None)
        if budget is None and self.opt.ingest_guards:
            from ..guard.budget import make_budget
            budget = make_budget(self.opt.ingest_limits,
                                 name=getattr(image, "name", ""))
        self.budget = budget
        image.ingest_budget = budget
        arch = getattr(image, "archive", None)
        if arch is not None and budget is not None and \
                arch.budget is None:
            # the image was loaded unguarded: retrofit the budget
            # onto the shared archive handle so layer blob reads and
            # gzip decompression charge it too
            arch.budget = budget
        self.group = AnalyzerGroup(
            disabled=_effective_disabled(self.opt),
            file_patterns=self.opt.file_patterns)

    def cache_keys(self) -> tuple:
        """``(artifact_id, blob_ids, base)`` — the content-addressed
        cache keys :meth:`inspect` scans under. Needs only the image
        *metadata* (id, config, diff_ids), never a layer byte, so the
        streaming warm-layer probe can ask "which layers are already
        cached?" before any blob GET is issued."""
        img = self.image
        import os as _os
        opts_key = {"skip_dirs": self.opt.skip_dirs,
                    "skip_files": self.opt.skip_files,
                    "patterns": sorted(self.opt.file_patterns),
                    # guards change which entries of a HOSTILE layer
                    # survive the walk, so guarded and unguarded
                    # blobs must never share cache keys (clean
                    # layers produce identical content either way)
                    "ingest_guards": self.budget is not None,
                    "secrets": self.opt.scan_secrets,
                    # the rule set decides which secret findings a
                    # blob carries — a trivy-secret.yaml custom set
                    # must never share cached blobs with the builtin
                    # corpus (and the findings memo keys on the same
                    # fingerprint, docs/performance.md)
                    "secret_rules": self._rules_fp()
                    if self.opt.scan_secrets else "",
                    "misconfig": self.opt.scan_misconfig,
                    "licenses": self.opt.scan_licenses,
                    # the rekor URL changes analyzer/handler output
                    # (different servers hold different
                    # attestations), so it keys cached blobs
                    "rekor": _os.environ.get(
                        "TRIVY_REKOR_URL", ""),
                    # likewise the APK index URL decides what
                    # history_packages the artifact record holds
                    "apk_index": _os.environ.get(
                        "TRIVY_APK_INDEX_ARCHIVE_URL",
                        _os.environ.get(
                            "FANAL_APK_INDEX_ARCHIVE_URL", ""))}
        versions = dict(self.group.versions())
        versions.update({f"handler/{k}": v
                         for k, v in handler_versions().items()})
        # base-image layers skip secret scanning (image.go:215-218),
        # so a layer's blob CONTENT depends on whether this image
        # treats it as base — the flag must be in the key, or a
        # shared cache would serve base-stripped secrets to an image
        # that owns the layer (and vice versa). The reference keys
        # all layers alike (image.go:152-169) and accepts that
        # asymmetry; our keys never interoperate with its anyway.
        base = set(guess_base_layers(img.diff_ids, img.config)) \
            if self.opt.scan_secrets else set()
        blob_ids = [
            calc_key(d, versions,
                     options=dict(opts_key, base_layer=True)
                     if d in base else opts_key)
            for d in img.diff_ids]
        artifact_id = calc_key(img.id, versions, options=opts_key)
        return artifact_id, blob_ids, base

    def inspect(self) -> ArtifactReference:
        img = self.image
        artifact_id, blob_ids, base = self.cache_keys()
        self._bytes_analyzed = 0

        try:
            missing_artifact, missing = self.cache.missing_blobs(
                artifact_id, blob_ids)

            todo = [i for i, b in enumerate(blob_ids)
                    if b in missing]
            # tracing: the analyze span (active on this thread when
            # the runner/scheduler traces the request) records how
            # much of the image was a cache hit
            from ..obs.trace import add_event
            add_event("inspect", layers=len(blob_ids),
                      missing=len(todo))
            if todo:
                # streaming sources pipeline fetch+inflate in the
                # background: (re)start exactly the missing layers
                # and bind this thread's analyze span so the
                # in-flight fetch/decompress stage spans land in the
                # request's trace (idempotent; absent on
                # materialized sources)
                prefetch = getattr(img, "prefetch", None)
                if prefetch is not None:
                    prefetch(todo)
                self._inspect_layers(todo, blob_ids, base)
            # the cache's outcome for this image, where the
            # benchmark's snapshots read it (artifact/metrics.py)
            INGEST_METRICS.note_inspect(
                len(blob_ids), len(todo), self._bytes_analyzed,
                len(base), self.group.take_gate_counts())
            if missing_artifact and \
                    getattr(self, "_os_found", None) is None:
                # OS layer may be a cache hit while the artifact
                # record is being (re)built — read it from the
                # cached blobs so the history analyzer still knows
                # the distro/version
                for b in blob_ids:
                    blob = self.cache.get_blob(b)
                    if blob is not None and blob.os is not None:
                        self._os_found = blob.os
                        break
        finally:
            # layer reads are done — release the shared archive
            # handle now rather than at GC (a 512-image fleet would
            # otherwise hold 512 open fds), including on the
            # fully-cached path where nothing was read
            img.close()
        if missing_artifact:
            self.cache.put_artifact(artifact_id,
                                    self._artifact_info())

        return ArtifactReference(
            name=img.name,
            type="container_image",
            id=artifact_id,
            blob_ids=blob_ids,
            image_metadata=ImageMetadata(
                id=img.id,
                diff_ids=img.diff_ids,
                repo_tags=img.repo_tags,
                repo_digests=img.repo_digests,
                image_config=img.config,
            ),
        )

    def _rules_fp(self) -> str:
        """Secret rule-set fingerprint for the blob cache key: an
        explicit fingerprint wins (the batch runner stamps its
        shared sieve's), else the option's scanner, else builtin."""
        if self.opt.secret_rules_fp:
            return self.opt.secret_rules_fp
        from ..secret.batch import rules_fingerprint
        return rules_fingerprint(self.opt.secret_scanner)

    # --- analysis ---

    def _inspect_layers(self, todo: list, blob_ids: list,
                        base: set) -> None:
        # secret scanning is skipped on base-image layers — their
        # "secrets" belong to the base image's publisher, not this
        # image (ref image.go:215-218); `base` also marked these
        # layers' cache keys in inspect()
        import contextlib
        layer_results = []
        all_candidates = []        # (layer_idx, path, content)
        budget = self.budget
        ctx = budget.activate() if budget is not None \
            else contextlib.nullcontext()
        with ctx:
            self._analyze_layers(todo, layer_results, all_candidates,
                                 base)
        if budget is not None:
            budget.flush_metrics()

        secrets_by_layer = self._batch_secrets(all_candidates)

        for i, result, opq_dirs, wh_files in layer_results:
            result.secrets = secrets_by_layer.get(i, [])
            blob = result.to_blob_info(diff_id=self.image.diff_ids[i])
            blob.opaque_dirs = opq_dirs
            blob.whiteout_files = wh_files
            post_handle(blob)
            self.cache.put_blob(blob_ids[i], blob)

    def _analyze_layers(self, todo: list, layer_results: list,
                        all_candidates: list, base: set) -> None:
        from ..obs.trace import add_event, phase_span
        # the skip lists are asked of a file only where one is set
        skipped = self._skipped if self.opt.skip_dirs \
            or self.opt.skip_files else None
        for i in todo:
            layer = self.image.layers[i]
            result = AnalysisResult()
            # layer.open() blocks until the layer's bytes are ready;
            # on a streaming source that wait is covered by the
            # layer's own fetch/decompress spans (excluded by the
            # timeline when they overlap device compute — pipelined
            # staging), so the layer_analyze stage span deliberately
            # starts AFTER the open and covers only walk + analyzers
            with layer.open() as tf:
                with phase_span("layer_analyze", pipeline="ingest",
                                layer=i):
                    files, opq_dirs, wh_files = collect_layer_tar(
                        tf, budget=self.budget)
                    for path, size, read in files:
                        if skipped is not None and skipped(path):
                            continue
                        self._bytes_analyzed += size
                        self.group.analyze_file(result, path, read,
                                                size)
            add_event("layer_analyzed", layer=i,
                      files=len(files))
            layer_results.append((i, result, opq_dirs, wh_files))
            if result.os is not None:
                # feeds the image-config history analyzer, like the
                # reference's osFound (image.go:206-250)
                self._os_found = result.os
            if self.image.diff_ids[i] in base:
                continue
            for path, content in result.secret_candidates:
                all_candidates.append((i, path, content))

    def _batch_secrets(self, candidates: list) -> dict:
        """ONE kernel dispatch across every missing layer's files.
        Image paths get a leading '/' (secret.go:97-101). The same
        path can exist in several layers with different contents —
        results map back by the entry INDEX scan_files returns,
        never by path."""
        if not candidates or not self.opt.scan_secrets:
            return {}
        scanner = _secret_scanner(self.opt)
        files = [("/" + path, content)
                 for _, path, content in candidates]
        out: dict = {}
        for idx, s in scanner.scan_files(files):
            out.setdefault(candidates[idx][0], []).append(s)
        return out

    def _skipped(self, path: str) -> bool:
        for d in self.opt.skip_dirs:
            d = d.strip("/")
            if path == d or path.startswith(d + "/"):
                return True
        return ("/" + path if not path.startswith("/") else path)\
            in self.opt.skip_files or path in self.opt.skip_files

    def _artifact_info(self) -> ArtifactInfo:
        """inspectConfig analog (ref image.go:349-376): image
        metadata plus packages reconstructed from RUN history for
        --removed-pkgs scanning."""
        from ..analyzer.imgconf import analyze_image_config
        cfg = self.image.config
        os_found = getattr(self, "_os_found", None)
        return ArtifactInfo(
            architecture=cfg.get("architecture", ""),
            created=cfg.get("created", ""),
            docker_version=cfg.get("docker_version", ""),
            os=cfg.get("os", ""),
            history_packages=analyze_image_config(
                os_found.family if os_found else "",
                os_found.name if os_found else "", cfg),
        )


class LocalFSArtifact:
    """Directory tree → ONE blob (reference: artifact/local/fs.go)."""

    def _stream_secrets(self, result: AnalysisResult, files,
                        stream) -> list:
        """The walk with its secret candidates cut into parts
        (``stream.cutter``, a ``secret.batch.PartCutter``) and each
        part handed on as it closes: ``stream.emit`` waits while as
        many parts are in flight as the stream allows, so the tree's
        bytes held are those parts' and no more; ``stream.finish``
        waits for the last and returns the Secrets in the files'
        order. ``ingest.tree_walk`` books the walk's own seconds,
        reading and analyzing up to a part's close, and nothing of
        a wait in ``emit``."""
        from ..obs.trace import phase_span
        cutter = stream.cutter
        n_files = n_candidates = n_bytes = 0
        walking = True
        while walking:
            parts = []
            with phase_span("tree_walk", pipeline="ingest"):
                for path, size, read, wanted in files:
                    n_files += 1
                    self.group.analyze_file(result, path, read, size,
                                            wanted)
                    if not result.secret_candidates:
                        continue
                    for p, content in result.secret_candidates:
                        n_candidates += 1
                        n_bytes += len(content)
                        parts.append(cutter.add(p, content))
                    result.secret_candidates = []
                    if any(parts):
                        break
                else:
                    walking = False
                    parts.append(cutter.flush())
            # popped, and never bound to a name here, so that nothing
            # in the walk keeps a part's bytes once the stream has
            # let them go
            while parts:
                if parts[0]:
                    stream.emit(parts.pop(0))
                else:
                    parts.pop(0)
        INGEST_METRICS.note_tree(n_files, n_candidates, n_bytes,
                                 self.group.take_gate_counts())
        return stream.finish()

    def __init__(self, root: str, cache,
                 option: Optional[ArtifactOption] = None):
        self.root = root
        self.cache = cache
        self.opt = option or ArtifactOption()
        self.group = AnalyzerGroup(
            disabled=_effective_disabled(self.opt),
            file_patterns=self.opt.file_patterns)

    def inspect(self, stream=None) -> ArtifactReference:
        """``stream`` (the batch runner's, for a tree that rides the
        scheduler): where the secret candidates go, a part at a
        time and while the walk goes on, instead of all of them to
        one ``scan_files`` call at its end. The other analyzers run
        in the walk either way, and the blob is the same."""
        result = AnalysisResult()
        files = iter_fs(self.root, skip_dirs=self.opt.skip_dirs,
                        skip_files=self.opt.skip_files,
                        gate=self.group.wanted)
        try:
            if stream is not None and self.opt.scan_secrets:
                result.secrets = self._stream_secrets(result, files,
                                                      stream)
            else:
                for path, size, read, wanted in files:
                    self.group.analyze_file(result, path, read, size,
                                            wanted)
        finally:
            # an error or a cancelled tree leaves no file open
            files.close()
        if stream is None and result.secret_candidates \
                and self.opt.scan_secrets:
            scanner = _secret_scanner(self.opt)
            result.secrets = [s for _, s in scanner.scan_files(
                [(p, c) for p, c in result.secret_candidates])]

        blob = result.to_blob_info()
        post_handle(blob)
        # NOTE: blob.diff_id stays empty — filesystem scans report
        # Layer: {} (reference: local artifacts have no layers); the
        # content hash is only the cache key.
        raw = json.dumps(blob.to_dict(), sort_keys=True).encode()
        blob_id = "sha256:" + hashlib.sha256(raw).hexdigest()
        self.cache.put_blob(blob_id, blob)
        return ArtifactReference(
            name=self.root, type="filesystem", id=blob_id,
            blob_ids=[blob_id])
