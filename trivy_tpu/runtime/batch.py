"""Multi-image batch scanner.

Pipeline per batch of images:

  1. host: load each image, compute cache keys, walk MISSING layers
     through the non-secret analyzers; secret candidates accumulate
     across all images tagged (image, layer);
  2. TPU dispatch #1: one literal-sieve pass over every candidate
     byte of every image (trivy_tpu.secret.batch);
  3. host: PutBlob per layer, ApplyLayers per image, advisory name
     join per package across all images;
  4. TPU dispatch #2: one interval-membership pass over every
     (package, advisory) pair of every image (trivy_tpu.detect.batch);
  5. host: per-image result assembly, enrichment.

Cached images skip 1-2 entirely (content-addressed MissingBlobs —
the reference's resume mechanism, SURVEY.md §5). Two kernel dispatches
per BATCH — not per image — amortize dispatch latency across the
whole fleet (the reference's k8s scanner loops artifacts sequentially,
SURVEY.md §2.6).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from ..artifact.artifact import (ArtifactOption, ImageArtifact,
                                 LocalFSArtifact)
from ..artifact.cache import MemoryCache
from ..artifact.image import load_image
from ..db import AdvisoryStore
from ..detect.batch import dispatch_jobs
from ..scan.local import LocalScanner, ScanTarget
from ..types import Metadata, Report, ScanOptions
from ..utils import get_logger

log = get_logger("runtime.batch")


@dataclass
class BatchScanResult:
    name: str
    report: Optional[Report] = None
    error: str = ""
    # degraded-mode status (docs/robustness.md): ok | degraded |
    # failed, with machine-readable FailureCause records. A slot
    # with ``error`` set is failed; a slot that completed through a
    # fault (device quarantine → host fallback) is degraded.
    status: str = "ok"
    causes: list = field(default_factory=list)

    def apply_degraded(self, causes: list) -> None:
        from ..types.report import FailureCause
        fc = [FailureCause.coerce(c) for c in causes]
        self.causes.extend(fc)
        if self.status != "failed":
            self.status = "degraded"
        if self.report is not None:
            self.report.mark_degraded(fc)

    def mark_failed(self, stage: str, kind: str,
                    message: str) -> "BatchScanResult":
        from ..types.report import FailureCause
        self.status = "failed"
        self.causes.append(FailureCause(stage=stage, kind=kind,
                                        message=message))
        if self.report is not None:
            self.report.mark_degraded(self.causes[-1:],
                                      status="failed")
        return self


class BatchScanRunner:
    def __init__(self, store: Optional[AdvisoryStore] = None,
                 cache=None, backend: str = "tpu", mesh=None,
                 secret_scanner=None, sched="off",
                 sched_config=None, artifact_option=None,
                 fault_injector=None, tracer=None, memo=None,
                 dispatch_depth: int = 0, warm: bool = True):
        from ..obs.trace import get_tracer
        from .ring import resolve_dispatch_depth
        self.store = store or AdvisoryStore()
        self.cache = cache if cache is not None else MemoryCache()
        # dispatch_depth: bound on in-flight interval waves on the
        # direct (sched=off) path — the double-buffered slot runtime
        # (docs/performance.md §8). 0 = TRIVY_TPU_DISPATCH_DEPTH or
        # the default 2; 1 restores the synchronous ladder
        self.dispatch_depth = resolve_dispatch_depth(dispatch_depth)
        # memo: trivy_tpu.memo.FindingsMemo (or None) — per-layer
        # detection-verdict memoization threaded into every
        # LocalScanner this runner constructs, on both execution
        # paths (docs/performance.md "Findings memoization")
        self.memo = memo
        self.backend = backend
        self.mesh = mesh
        # tracer: trivy_tpu.obs.Tracer — per-request span trees on
        # both execution paths (docs/observability.md); an untraced
        # run passes Tracer(enabled=False)
        self.tracer = tracer if tracer is not None else get_tracer()
        if secret_scanner is None:
            from ..secret.batch import BatchSecretScanner
            secret_scanner = BatchSecretScanner(
                backend="cpu-ref" if backend == "cpu-ref" else "tpu",
                mesh=mesh)
        self.secret_scanner = secret_scanner
        self.artifact_option = artifact_option
        # fault_injector: trivy_tpu.faults.FaultInjector (or None) —
        # threads into the scheduler's device dispatch and this
        # runner's host phases (--fault-spec / pytest -m faults)
        self.fault_injector = fault_injector
        # sched: "off" = the direct single-batch ladder below;
        # "on"/SchedConfig/ScanScheduler = continuous batching with
        # pipelined host/device overlap (trivy_tpu.sched)
        self.sched_config = sched_config
        # warm: run the pad ladders' programs before the first
        # request (runtime/aot.warm_ladders). A process that scans
        # one target and exits (``cli fs``) says False and compiles
        # only the rungs its target meets, when it meets them
        self.warm = warm
        self._scheduler = None
        self._owns_scheduler = False
        if hasattr(sched, "submit"):       # a ScanScheduler
            self._scheduler = sched        # shared — caller closes
            self.sched = "on"
        elif sched not in (None, "off", False):
            self.sched = "on"
            from ..sched import SchedConfig
            if isinstance(sched, SchedConfig):
                self.sched_config = sched
        else:
            self.sched = "off"
        self.last_stats: dict = {}   # phase timings of the last batch

    # --- scheduler plumbing ---

    @property
    def scheduler(self):
        if self._scheduler is None:
            from ..sched import ScanScheduler, SchedConfig
            cfg = self.sched_config
            if cfg is None:
                # propagate the runner's slot depth so --sched on
                # and off honor the same --dispatch-depth knob
                cfg = SchedConfig(
                    dispatch_depth=self.dispatch_depth)
            self._scheduler = ScanScheduler(
                config=cfg, backend=self.backend,
                mesh=self.mesh, secret_scanner=self.secret_scanner,
                tracer=self.tracer)
            self._scheduler.fault_injector = self.fault_injector
            self._owns_scheduler = True
            from .aot import warm_ladders
            from .device import on_accelerator
            if self.warm and self.backend != "cpu-ref" \
                    and self.mesh is None and on_accelerator():
                # every shape a batch can take compiles now, or
                # comes from the persistent cache, and not in the
                # middle of the fleet
                warm_ladders(self.secret_scanner, self.store, cfg)
        return self._scheduler

    def close(self) -> None:
        # only tear down a scheduler this runner constructed — an
        # externally provided one may serve other request sources
        if self._scheduler is not None and self._owns_scheduler:
            self._scheduler.close()
            self._scheduler = None

    def _store_view(self) -> tuple:
        """``(db, release|None)``: a SwappableStore holder (the
        server's hot-swap contract, now honored by embedders — the
        watch runtime and the admission webhook front long-lived
        runners whose advisory DB updates underneath them) is
        acquired per scan so a ``db update`` swap waits for
        in-flight work; plain stores pass through untouched."""
        s = self.store
        if hasattr(s, "acquire") and hasattr(s, "release"):
            return s.acquire(), s.release
        return s, None

    def scan_paths(self, paths: list,
                   options: Optional[ScanOptions] = None) -> list:
        if self.sched == "on":
            # lazy image load inside analyze() — tar walking is host
            # work that should overlap device execution too
            return self._scan_scheduled(
                [(p, None) for p in paths], options)
        import tarfile as _tarfile
        images, failures = [], {}
        for i, p in enumerate(paths):
            try:
                if self.fault_injector is not None:
                    self.fault_injector.on_image_load(p)
                images.append((i, load_image(
                    p, budget=self._ingest_budget(p))))
            except (OSError, ValueError, _tarfile.TarError) as e:
                failures[i] = _failed_slot(p, e)
        results = self.scan_images([img for _, img in images],
                                   options)
        out = dict(failures)
        for (i, _), res in zip(images, results):
            out[i] = res
        return [out[i] for i in range(len(paths))]

    def scan_images(self, images: list,
                    options: Optional[ScanOptions] = None) -> list:
        if self.sched == "on":
            return self._scan_scheduled(
                [(getattr(img, "name", ""), img) for img in images],
                options)
        from ..utils import defer_gc
        with defer_gc():
            return self._scan_images(images, options)

    def blob_keyer(self, scan_secrets: bool = True):
        """Warm-layer probe keyer for ``artifact.stream.stream_image``:
        computes the SAME ``(artifact_id, blob_ids, base)`` this
        runner's inspect will scan under — same artifact option, same
        secret-rules fingerprint — from image *metadata* alone, so the
        streaming path can skip the blob GET for every layer the
        cache already holds. A mismatched keyer would skip layers
        inspect then reports missing (a failed scan), which is why
        this lives on the runner instead of the stream module."""
        opt = self._image_opt(scan_secrets)

        def keyer(img):
            a = ImageArtifact(img, self.cache, opt,
                              budget=getattr(img, "ingest_budget",
                                             None))
            return a.cache_keys()

        return keyer

    def scan_registry_refs(self, refs: list, client=None,
                           options: Optional[ScanOptions] = None,
                           streaming: bool = True) -> list:
        """Scan images straight from a registry — the cold-wall path
        (docs/performance.md §9). With ``streaming`` (the default)
        each ref becomes a :class:`~..artifact.stream.\
StreamingImageSource`: layer blobs decompress into the scan as they
        arrive, warm layers skip their GET entirely, and the per-layer
        pipeline overlaps the fleet's device work on both execution
        paths. ``streaming=False`` is the materialize-first baseline
        (``DistributionClient.pull``) ``pytest -m stream`` compares
        against."""
        from ..artifact.registry import DistributionClient
        from ..artifact.stream import stream_image
        if client is None:
            client = DistributionClient()
        # the registry stream is a failure domain of its own
        # (registry-flaky scenario): thread the runner's injector
        # into the blob fetch engine
        client.fault_injector = self.fault_injector
        options = options or ScanOptions(backend=self.backend)
        scan_secrets = "secret" in options.security_checks
        keyer = self.blob_keyer(scan_secrets)

        def load(ref, budget):
            if not streaming:
                return client.pull(ref, budget=budget)
            return stream_image(client, ref, cache=self.cache,
                                keyer=keyer, budget=budget)

        if self.sched == "on":
            return self._scan_scheduled([(r, None) for r in refs],
                                        options, loader=load)
        sources, failures = [], {}
        for i, ref in enumerate(refs):
            try:
                if self.fault_injector is not None:
                    self.fault_injector.on_image_load(ref)
                sources.append((i, load(
                    ref, self._ingest_budget(ref))))
            except (OSError, ValueError) as e:
                # RegistryError is a ValueError; GuardError keeps its
                # typed ingest stage/kind through _failed_slot
                failures[i] = _failed_slot(ref, e)
        try:
            results = self.scan_images(
                [src for _, src in sources], options)
        finally:
            for _, src in sources:
                try:
                    src.close()
                except Exception:   # noqa: BLE001 — cleanup only
                    log.debug("source close failed",
                              exc_info=True)
        out = dict(failures)
        for (i, _), res in zip(sources, results):
            out[i] = res
        return [out[i] for i in range(len(refs))]

    def _ingest_budget(self, name: str):
        """Fresh per-target ResourceBudget (docs/robustness.md), or
        None when the runner's artifact option disabled the guards
        (``--no-ingest-guards``)."""
        from ..guard.budget import make_budget
        opt = self.artifact_option
        enabled = opt.ingest_guards if opt is not None else True
        return make_budget(
            getattr(opt, "ingest_limits", None) if opt else None,
            enabled=enabled, name=name)

    def _image_opt(self, scan_secrets: bool) -> ArtifactOption:
        """Per-scan artifact option: the runner-level template (CLI
        skip dirs / file patterns) with secret scanning routed to the
        batch sieve instead of a per-artifact scanner."""
        from ..secret.batch import rules_fingerprint
        if self.artifact_option is None:
            return ArtifactOption(
                scan_secrets=scan_secrets,
                secret_rules_fp=rules_fingerprint(
                    self.secret_scanner))
        import copy
        opt = copy.copy(self.artifact_option)
        opt.scan_secrets = scan_secrets and \
            self.artifact_option.scan_secrets
        opt.secret_scanner = None
        # blob keys must reflect the sieve that ACTUALLY produces
        # this runner's secret findings (the shared batch scanner,
        # not the per-option default)
        if not opt.secret_rules_fp:
            opt.secret_rules_fp = rules_fingerprint(
                self.secret_scanner)
        return opt

    # --- the scheduled (continuous-batching) route ---

    def _scan_scheduled(self, items: list,
                        options: Optional[ScanOptions] = None,
                        loader=None) -> list:
        """``items``: [(name, image-or-None)] — None loads the path
        (or, with ``loader``, the registry ref) lazily inside
        analyze(). Submits one request per image to the scheduler and
        gathers results in input order; per-request failures (load
        errors, deadline expiry) fail their own slot, never the
        fleet."""
        import time as _time

        from ..sched import RateLimitedError

        options = options or ScanOptions(backend=self.backend)
        sched = self.scheduler
        reqs = []
        for name, img in items:
            req = self._image_request(sched, name, img, options,
                                      loader=loader)
            while True:
                try:
                    reqs.append(sched.submit(req, block=True))
                    break
                except RateLimitedError as e:
                    # closed-loop fleet semantics: block=True means
                    # "wait for capacity", and a tenant rate limit
                    # is capacity too — sleep the shed hint and
                    # retry instead of killing the whole fleet.
                    # Serving callers (submit_path) still surface
                    # the 429 to the client.
                    _time.sleep(min(max(e.retry_after_s, 0.01),
                                    5.0))
        out = []
        for (name, _), req in zip(items, reqs):
            try:
                out.append(req.result())
            except Exception as e:       # noqa: BLE001 — one slot's
                # failure (typed or not) must never crash the fleet
                # gather; the cause lands in the slot's report
                out.append(_failed_slot(name, e,
                                        trace_id=req.trace_id,
                                        tracer=self.tracer))
        self.last_stats = {"images": len(items),
                           "sched": sched.stats()}
        for k, v in self.last_stats["sched"].items():
            if k.endswith("_s") or k == "overlap_ratio":
                self.last_stats[k] = v
        return out

    def submit_path(self, path: str,
                    options: Optional[ScanOptions] = None,
                    tenant: str = "", priority: int = 0,
                    trace_id: str = "", parent_span_id: str = ""):
        """Serving-mode entry: enqueue ONE image scan through the
        scheduler and return its ScanRequest future (``.result()``
        blocks; raises QueueFullError on backpressure, or
        RateLimitedError when the ``tenant`` is over its quota or
        rate limit — docs/serving.md "Multi-tenant QoS"). The batch
        composition is the scheduler's business — concurrent
        submitters share device dispatches across tenants."""
        options = options or ScanOptions(backend=self.backend)
        sched = self.scheduler
        return sched.submit(
            self._image_request(sched, path, None, options,
                                tenant=tenant, priority=priority,
                                trace_id=trace_id,
                                parent_span_id=parent_span_id))

    # --- a directory tree: one request that streams ---

    def scan_trees(self, paths: list,
                   options: Optional[ScanOptions] = None) -> list:
        """Scan directory trees (``fs``, ``rootfs``), one report
        each, as ``scan_paths`` scans images. A tree always rides
        the scheduler (:meth:`submit_tree`); a tree that cannot be
        scanned fails its own slot."""
        options = options or ScanOptions(backend=self.backend)
        reqs = [self.submit_tree(p, options, block=True)
                for p in paths]
        out, stats = [], []
        for path, req in zip(paths, reqs):
            try:
                out.append(req.result())
            except Exception as e:       # noqa: BLE001 (the slot's)
                out.append(_failed_slot(path, e,
                                        trace_id=req.trace_id,
                                        tracer=self.tracer))
            stats.append(req.tree_stats)
        self.last_stats = {"trees": stats,
                           "sched": self.scheduler.stats()}
        return out

    def submit_tree(self, root: str,
                    options: Optional[ScanOptions] = None,
                    tenant: str = "", priority: int = 0,
                    block: bool = False):
        """Enqueue ONE directory tree and return its ScanRequest
        future, as :meth:`submit_path` does for an image. The
        request walks the tree once, on a host worker, and yields
        its device work in parts while it walks: the secret
        candidates are cut, whole files, into sieve batches of one
        warmed rung (``secret.batch.PartCutter``), each launched
        through the scheduler's ring as it closes
        (``ScanScheduler.submit_part``), so the walk and pack of
        part k+1 overlap the sieve of part k and the verify of part
        k-1, and the tree's bytes held on the host are those of the
        parts in flight: one slot of the ring each, one more
        waiting for a slot, and the one the walk is filling. The
        other analyzers run in the walk; the tree's interval jobs
        ride the scheduler's waves once the blob is written, as an
        image's do; the report is built once, from one blob."""
        options = options or ScanOptions(backend=self.backend)
        sched = self.scheduler
        return sched.submit(
            self._tree_request(sched, root, options, tenant=tenant,
                               priority=priority), block=block)

    def _tree_request(self, sched, root: str, options,
                      tenant: str = "", priority: int = 0):
        from ..sched import AnalyzedWork, ScanRequest

        scan_secrets = "secret" in options.security_checks

        def analyze(req):
            db = self._hold_store(req)
            opt = self._image_opt(scan_secrets)
            stream = _TreeStream(sched, req, self.secret_scanner) \
                if opt.scan_secrets else None
            req.tree_stats = stream.stats if stream else {}
            ref = LocalFSArtifact(root, self.cache, opt).inspect(
                stream)
            scanner = LocalScanner(self.cache, db, memo=self.memo)
            prepared = scanner.prepare(
                ScanTarget(name=ref.name, artifact_id=ref.id,
                           blob_ids=ref.blob_ids), options)

            def finish(found, detected):
                results, os_found = scanner.finish(prepared,
                                                   detected)
                return BatchScanResult(
                    name=root,
                    report=Report(artifact_name=root,
                                  artifact_type="filesystem",
                                  metadata=Metadata(os=os_found),
                                  results=results))

            return AnalyzedWork(jobs=prepared.jobs, finish=finish)

        req = ScanRequest(name=root, analyze=analyze,
                          deadline_s=getattr(options, "deadline_s",
                                             0.0) or 0.0,
                          tenant=tenant, priority=priority)
        req.tree_stats = {}
        return req

    def _hold_store(self, req):
        """The advisory store for one request's analyze. A
        SwappableStore's reader is held from here to the request's
        resolution, so a DB hot swap waits for this scan (the
        server's acquire/release contract): chained AFTER any
        caller-provided on_done, released exactly once at whatever
        resolution path fires first."""
        db, release = self._store_view()
        if release is not None:
            prev = req.on_done

            def _done(r, _prev=prev, _rel=release):
                try:
                    if _prev is not None:
                        _prev(r)
                finally:
                    _rel()
            req.on_done = _done
        return db

    def _image_request(self, sched, name: str, image, options,
                       tenant: str = "", priority: int = 0,
                       trace_id: str = "", parent_span_id: str = "",
                       loader=None):
        from ..sched import AnalyzedWork, ScanRequest

        scan_secrets = "secret" in options.security_checks

        def analyze(req):
            inj = self.fault_injector
            if inj is not None:
                # host failure domains: corrupt layer tar fails this
                # slot only; a slow-host stall eats into the deadline
                inj.on_host_analyze(name)
                inj.on_image_load(name)
            db = self._hold_store(req)
            budget = self._ingest_budget(name)
            # loader: registry seam (scan_registry_refs) — builds a
            # StreamingImageSource (or a pulled one) instead of
            # opening a local tar; either way the image is loaded
            # INSIDE analyze so manifest/config fetches overlap
            # device execution like tar walking does
            if image is not None:
                img = image
            elif loader is not None:
                img = loader(name, budget)
            else:
                img = load_image(name, budget=budget)
            owns_img = image is None
            opt = self._image_opt(scan_secrets)
            a = _SchedImageArtifact(img, self.cache, opt,
                                    budget=budget)
            # register pending blob writes BEFORE the analyzed blobs
            # land in the cache (the _batch_secrets hook fires between
            # analysis and put_blob), so a concurrent request can
            # never observe an unpatched blob without also seeing the
            # dependency that guards it
            a._sched = sched
            a._sched_req = req
            try:
                ref = a.inspect()
            finally:
                if owns_img:
                    # after inspect every analyzed byte lives in the
                    # cache; release the source now (a streaming
                    # source's layer spool can be whole decompressed
                    # layers on disk, and a fleet of leaked spools
                    # outlives the scan)
                    try:
                        img.close()
                    except Exception:   # noqa: BLE001 — cleanup
                        log.debug("source close failed for %r",
                                  name, exc_info=True)
            a.reference = ref
            if a.budget is not None:
                # survivable hostile input (e.g. a corrupt rpmdb):
                # the slot completes but reports status=degraded
                # with ingest-stage causes
                for kind, msg in a.budget.soft_faults:
                    req.record_fault("ingest", kind, msg)
            scanner = LocalScanner(self.cache, db,
                                   memo=self.memo)
            prepared = scanner.prepare(
                ScanTarget(name=ref.name, artifact_id=ref.id,
                           blob_ids=ref.blob_ids), options)
            candidates = []
            patch = None
            deps = []
            if scan_secrets:
                # collected paths already carry the image '/' prefix
                candidates = [(path, content)
                              for _, path, content in a.collected]
                deps = sched.blob_deps(ref.blob_ids, req)
                if a.collected:
                    patch = _make_patch(self.cache, a)

            def finish(found, detected):
                if scan_secrets:
                    from ..applier import merge_layer_secrets
                    blobs = [self.cache.get_blob(b)
                             for b in ref.blob_ids]
                    prepared.detail.secrets = \
                        merge_layer_secrets(blobs)
                results, os_found = scanner.finish(prepared,
                                                   detected)
                return BatchScanResult(
                    name=ref.name,
                    report=Report(
                        artifact_name=ref.name,
                        artifact_type="container_image",
                        metadata=Metadata(
                            os=os_found,
                            image_id=ref.image_metadata.id,
                            diff_ids=ref.image_metadata.diff_ids,
                            repo_tags=ref.image_metadata.repo_tags,
                            image_config=ref.image_metadata
                            .image_config,
                        ),
                        results=results,
                    ))

            return AnalyzedWork(candidates=candidates,
                                jobs=prepared.jobs, patch=patch,
                                finish=finish, deps=deps)

        if not trace_id and not parent_span_id:
            # ambient fleet context (obs/propagate.py): a scan
            # submitted under an active span — the simhost root, a
            # watch event's propagated context — joins that trace
            # instead of starting an unlinked one
            from ..obs.propagate import current_context
            ctx = current_context()
            if ctx is not None:
                trace_id = ctx.trace_id
                parent_span_id = ctx.parent_span_id
        return ScanRequest(name=name or getattr(image, "name", ""),
                           analyze=analyze,
                           deadline_s=getattr(options, "deadline_s",
                                              0.0) or 0.0,
                           tenant=tenant, priority=priority,
                           trace_id=trace_id[:64],
                           parent_span_id=parent_span_id[:64])

    def _scan_images(self, images: list,
                     options: Optional[ScanOptions] = None) -> list:
        db, release = self._store_view()
        try:
            return self._scan_images_db(db, images, options)
        finally:
            if release is not None:
                release()

    def _scan_images_db(self, db, images: list,
                        options: Optional[ScanOptions] = None) \
            -> list:
        import time as _time
        options = options or ScanOptions(backend=self.backend)
        scan_secrets = "secret" in options.security_checks

        # ---- phase 1: analyze missing layers, collect candidates,
        # squash + join PER IMAGE ----
        # tracing (docs/observability.md): the direct path has no
        # queue, so each image's span tree is analyze → device (the
        # fleet-shared dispatch window) → report
        tracer = self.tracer
        from ..obs.trace import activate_or_null, phase_span
        # ambient fleet context (obs/propagate.py): scans launched
        # under an active span (the simhost root, a propagated watch
        # submission) join that trace — per-image roots become its
        # remote-style children; with no ambient span the behavior
        # is byte-identical to the single-process path
        from ..obs.propagate import current_context
        amb = current_context()
        slots, failures = [], {}     # [(input idx, artifact)]
        roots: dict = {}             # input idx -> root span
        opt = self._image_opt(scan_secrets)
        scanner = LocalScanner(self.cache, db, memo=self.memo)
        prepared = []                # aligned with slots
        analyze_s = join_s = 0.0
        for idx, img in enumerate(images):
            name = getattr(img, "name", "")
            root = tracer.start_request(
                name,
                trace_id=amb.trace_id if amb else "",
                parent_span_id=amb.parent_span_id if amb else "")
            roots[idx] = root
            a = _CollectingImageArtifact(img, self.cache, opt)
            sp = tracer.child(root, "analyze")
            t1 = _time.perf_counter()
            try:
                with sp.activate():
                    a.reference = a.inspect()
            except Exception as e:   # noqa: BLE001 — a hostile or
                # broken artifact fails ITS slot with a typed cause;
                # the fleet keeps scanning (same isolation the
                # scheduled path gets from per-request analyze)
                sp.end("error")
                root.set("error", repr(e))
                root.end("failed")
                failures[idx] = _failed_slot(
                    name, e, trace_id=root.trace_id, tracer=tracer)
                continue
            analyze_s += _time.perf_counter() - t1
            # squash + advisory join for THIS image immediately,
            # instead of a fleet-wide barrier after every analyze:
            # with streaming sources, later images' layer fetches
            # are still in flight on the hostpool while this join
            # runs — the ISSUE's fetch/join overlap. The join span
            # keeps the phase visible to idle attribution
            # (host_pack_bound).
            ref = a.reference
            with sp.activate():
                with phase_span("join", pipeline="detect",
                                blobs=len(ref.blob_ids)) as jsp:
                    prepared.append(scanner.join(
                        ScanTarget(name=ref.name,
                                   artifact_id=ref.id,
                                   blob_ids=ref.blob_ids),
                        options))
            join_s += jsp.duration_s
            sp.end()
            slots.append((idx, a))
        artifacts = [a for _, a in slots]
        # one shared device window per surviving image: the sieve
        # and interval dispatches below serve the whole fleet, so
        # every slot's "device" span brackets the same wall interval
        dev_spans = {idx: tracer.child(roots[idx], "device",
                                       shared=True)
                     for idx, _ in slots}

        # ---- phase 2a: ENQUEUE the sieve dispatch (async) ----
        # the packing + enqueue runs on the host pool so the interval
        # enqueue below overlaps the SEGMENT PACKING too, not just
        # the device execution behind it; results are collected in
        # 2b — apply_layers' secret merge is re-derived afterwards
        # via applier.merge_layer_secrets, which is exactly the
        # secret part of the squash
        from .hostpool import get_host_pool
        t0 = _time.perf_counter()
        collected = [c for a in artifacts for c in a.collected]
        sec_stats: dict = {}       # only this batch's, never stale
        sieve_handle = sieve_future = None
        # pack/h2d_upload/db_upload phase spans attach under the
        # fleet's first shared device span (they bracket work done
        # once for the whole batch)
        sp0 = next(iter(dev_spans.values()), None)

        def _enqueue_sieve(files):
            if sp0 is None:
                return self.secret_scanner.dispatch_files(files)
            with sp0.activate():
                return self.secret_scanner.dispatch_files(files)

        if scan_secrets and collected:
            pool = get_host_pool()
            files = [(p, c) for _, p, c in collected]
            if pool is not None:
                sieve_future = pool.submit(_enqueue_sieve, files)
            else:
                sieve_handle = _enqueue_sieve(files)
        secret_s = _time.perf_counter() - t0

        # (the old phase-3 fleet-wide squash/join barrier now runs
        # per image inside phase 1, overlapping in-flight fetches)

        # ---- phase 4a: ENQUEUE the interval waves (async) ----
        # the slot runtime (docs/performance.md §8): dedup + wave
        # packing + donated-buffer uploads run here, every wave is
        # enqueued non-blocking into a bounded dispatch ring, and
        # the ring's drain thread materializes wave N while wave N+1
        # packs — so the device computes THROUGH the sieve collect
        # below instead of serializing after it. Joined AFTER the
        # sieve enqueue so device work stays enqueue-ordered on this
        # thread (the sched executor invariant).
        from ..detect.batch import collect_dispatch, \
            dispatch_jobs_async
        from .ring import (RING_METRICS, DispatchRing, RingMetrics,
                           TeeRingMetrics)
        if sieve_future is not None:
            t0 = _time.perf_counter()
            sieve_handle = sieve_future.result()
            secret_s += _time.perf_counter() - t0
        all_jobs = []
        for idx, p in enumerate(prepared):
            for job in p.jobs:
                job.payload = (idx, job.payload)
                all_jobs.append(job)
        detected_by_image: dict = {}
        kstats: dict = {}          # this batch's dispatch counters
        ring = None
        # per-scan books: the ring reports into its own RingMetrics
        # (exact for THIS scan even when concurrent scans run their
        # own rings in-process) AND the process-wide RING_METRICS
        # the /metrics endpoint serves
        scan_rm = RingMetrics()
        if all_jobs and options.backend != "cpu-ref" \
                and self.dispatch_depth > 1:
            ring = DispatchRing(depth=self.dispatch_depth,
                                name="interval",
                                metrics=TeeRingMetrics(
                                    scan_rm, RING_METRICS))
        try:
            with activate_or_null(sp0):
                ih = dispatch_jobs_async(all_jobs,
                                         backend=options.backend,
                                         mesh=self.mesh,
                                         stats=kstats, ring=ring)

            # ---- phase 2b: sieve collect + late secret merge ----
            # overlaps the interval waves still computing/draining
            t0 = _time.perf_counter()
            if sieve_handle is not None:
                from ..applier import merge_layer_secrets
                with activate_or_null(sp0):
                    # collect emits its own dfa_scan(fetch)/decode/
                    # verify phase spans; the blob patch + re-merge
                    # is collect-side host work too
                    found = self.secret_scanner.collect(sieve_handle)
                    with phase_span("decode", pipeline="secret",
                                    stage="patch"):
                        _patch_blobs(self.cache, artifacts, found)
                        sec_stats = dict(getattr(self.secret_scanner,
                                                 "stats", {}))
                        # re-merge EVERY artifact: a patched blob may
                        # be shared with artifacts whose own
                        # `collected` is empty (fleets share layers —
                        # the cached-layer case), and their prepare()
                        # ran before the patch landed. Nothing found
                        # → nothing patched → prepare()'s merge
                        # already stands.
                        if found:
                            for a, p in zip(artifacts, prepared):
                                blobs = [self.cache.get_blob(b)
                                         for b in
                                         a.reference.blob_ids]
                                p.detail.secrets = \
                                    merge_layer_secrets(blobs)
            secret_s += _time.perf_counter() - t0

            # ---- phase 4b: collect the interval waves ----
            with activate_or_null(sp0):
                detected_pairs = collect_dispatch(ih)
            for idx, payload in detected_pairs:
                detected_by_image.setdefault(idx, []).append(payload)
        finally:
            if ring is not None:
                ring.close()
        for sp in dev_spans.values():
            sp.end()

        ring1 = scan_rm.snapshot()
        ring_busy = ring1["slot_busy_s"]
        ring_overlap = ring1["slot_overlap_s"]
        jobs_in = kstats.get("jobs_in", len(all_jobs))
        self.last_stats = {
            "images": len(images),
            "analyze_s": round(analyze_s, 4),
            "secret_batch_s": round(secret_s, 4),
            "squash_join_s": round(join_s, 4),
            # the calling thread's seconds in the interval phases:
            # pack, h2d_upload, and the wait for the ring's waves
            "interval_dispatch_s": round(
                kstats.get("dispatch_s", 0.0), 4),
            "interval_device_s": round(
                kstats.get("device_s", 0.0), 4),
            "interval_jobs": len(all_jobs),
            "interval_jobs_unique": kstats.get("jobs_unique", 0),
            "interval_dedup_ratio": round(
                1.0 - kstats.get("jobs_unique", 0) / jobs_in, 4)
            if jobs_in else 0.0,
            # slot-runtime accounting for THIS scan (deltas of the
            # process-wide ring books): how much of the in-flight
            # wall ran >= 2 waves deep
            "dispatch_depth": self.dispatch_depth,
            "interval_waves": ih.waves,
            "dispatch_overlap_ratio": round(
                ring_overlap / ring_busy, 4) if ring_busy > 0
            else 0.0,
            "secret": sec_stats,
        }

        # ---- phase 5: assemble per image ----
        out = dict(failures)
        for local, ((idx, a), p) in enumerate(zip(slots, prepared)):
            sp = tracer.child(roots[idx], "report")
            with sp.activate():
                results, os_found = scanner.finish(
                    p, detected_by_image.get(local, []))
                ref = a.reference
                res = BatchScanResult(
                    name=ref.name,
                    report=Report(
                        artifact_name=ref.name,
                        artifact_type="container_image",
                        metadata=Metadata(
                            os=os_found,
                            image_id=ref.image_metadata.id,
                            diff_ids=ref.image_metadata.diff_ids,
                            repo_tags=ref.image_metadata.repo_tags,
                            image_config=ref.image_metadata
                            .image_config,
                        ),
                        results=results,
                    ))
            sp.end()
            root = roots[idx]
            b = getattr(a, "budget", None)
            degraded = b is not None and b.soft_faults
            if degraded:
                causes = [{"stage": "ingest", "kind": k,
                           "message": m} for k, m in b.soft_faults]
                if not root.noop:
                    from ..obs.trace import trace_cause
                    causes.append(trace_cause(tracer,
                                              root.trace_id))
                res.apply_degraded(causes)
            root.end("degraded" if degraded else "ok")
            out[idx] = res
        return [out[i] for i in range(len(images))]


    def scan_boms(self, boms: list,
                  options: Optional[ScanOptions] = None) -> list:
        """Batch-scan SBOM documents: ``boms`` is a list of
        (name, raw-bytes). BASELINE config #4's shape (no tar
        walking, no analyzers), and what ``cli sbom`` rides when it
        is given several documents: decode → name-join → one dedup
        of the whole call's jobs, whose distinct rows go to the
        resident advisory tables in waves of one warmed shape
        (``detect/batch._resident_waves``: a call of any size above
        one wave compiles nothing the last call did not)."""
        if self.sched == "on":
            return self._scan_boms_scheduled(boms, options)
        from ..utils import defer_gc
        with defer_gc():
            return self._scan_boms(boms, options)

    def _scan_boms_scheduled(self, boms: list,
                             options: Optional[ScanOptions] = None)\
            -> list:
        options = options or ScanOptions(
            backend=self.backend, security_checks=["vuln"])
        sched = self.scheduler
        reqs = [sched.submit(self._bom_request(name, data, options),
                             block=True)
                for name, data in boms]
        out = []
        for (name, _), req in zip(boms, reqs):
            try:
                out.append(req.result())
            except Exception as e:       # noqa: BLE001
                out.append(_failed_slot(name, e))
        self.last_stats = {"sboms": len(boms),
                           "sched": sched.stats()}
        return out

    def _bom_request(self, name: str, data: bytes, options):
        from ..sched import AnalyzedWork, ScanRequest

        def analyze(req):
            from ..artifact.sbom import decode_to_blob
            db = self._hold_store(req)
            # a malformed document fails its own slot, never the
            # fleet (ValueError resolves this request only)
            atype, decoded, blob, blob_id = decode_to_blob(data)
            self.cache.put_blob(blob_id, blob)
            scanner = LocalScanner(self.cache, db,
                                   memo=self.memo)
            prepared = scanner.prepare(
                ScanTarget(name=name, artifact_id=blob_id,
                           blob_ids=[blob_id]), options)

            def finish(found, detected):
                results, os_found = scanner.finish(prepared,
                                                   detected)
                return BatchScanResult(
                    name=name,
                    report=Report(artifact_name=name,
                                  artifact_type=atype,
                                  metadata=Metadata(os=os_found),
                                  results=results,
                                  cyclonedx=decoded.cyclonedx))

            return AnalyzedWork(jobs=prepared.jobs, finish=finish)

        return ScanRequest(name=name, analyze=analyze,
                           deadline_s=getattr(options, "deadline_s",
                                              0.0) or 0.0)

    def _scan_boms(self, boms: list,
                   options: Optional[ScanOptions] = None) -> list:
        db, release = self._store_view()
        try:
            return self._scan_boms_db(db, boms, options)
        finally:
            if release is not None:
                release()

    def _scan_boms_db(self, db, boms: list,
                      options: Optional[ScanOptions] = None) -> list:
        from ..artifact.sbom import decode_to_blob
        from ..detect.metrics import DETECT_METRICS
        from ..obs.trace import phase_span

        options = options or ScanOptions(
            backend=self.backend, security_checks=["vuln"])

        # The phases below (decode, join, the interval pack /
        # h2d_upload / device_compute inside dispatch_jobs, finish,
        # and defer_gc's gc round this call) run one after the other
        # on the calling thread, so their seconds partition a pass's
        # wall time; the stats keys are sums of their durations.

        # ---- phase 1: decode + blob (host, this thread) ----
        # decode is the dominant host phase at fleet scale (PERF.md
        # §5, sbom-batch): json parse + purl decode per component,
        # all of it under the interpreter lock, so it runs on the
        # calling thread: on the host pool it was a sixth slower and
        # unsteady (docs/performance.md "SBOM decode and the lock
        # convoy"). A malformed document still fails only its own
        # slot. The decode_task span books cpu_s over busy_s: how
        # much of the decode's wall this thread really ran.
        scanner = LocalScanner(self.cache, db, memo=self.memo)

        def decode_one(item):
            name, data = item
            try:
                return decode_to_blob(data)
            except ValueError as e:
                return e

        with phase_span("decode", pipeline="detect",
                        docs=len(boms)) as dsp:
            with phase_span("decode_task", pipeline="detect"):
                decodes = [decode_one(item) for item in boms]
        prepared, metas, failures = [], [], {}
        all_jobs = []
        components = 0
        with phase_span("join", pipeline="detect",
                        docs=len(boms)) as jsp:
            for i, ((name, _data), dec) in enumerate(zip(boms,
                                                         decodes)):
                if isinstance(dec, ValueError):
                    failures[i] = _failed_slot(name, dec)
                    continue
                atype, decoded, blob, blob_id = dec
                self.cache.put_blob(blob_id, blob)
                prepared.append((i, scanner.join(
                    ScanTarget(name=name, artifact_id=blob_id,
                               blob_ids=[blob_id]), options)))
                metas.append((i, name, atype, decoded))
                components += sum(
                    len(a.libraries) for a in blob.applications) \
                    + sum(len(pi.packages)
                          for pi in blob.package_infos)
            # the jobs leave the join tagged with their document
            for idx, (_, p) in enumerate(prepared):
                for job in p.jobs:
                    job.payload = (idx, job.payload)
                    all_jobs.append(job)

        DETECT_METRICS.inc("sbom_docs", len(prepared))
        DETECT_METRICS.inc("sbom_components", components)

        # ---- phase 2: one dedup and its waves over all SBOMs ----
        kstats: dict = {}
        hits = dispatch_jobs(all_jobs, backend=options.backend,
                             mesh=self.mesh, stats=kstats)

        # ---- phase 3: assemble ----
        with phase_span("finish", pipeline="detect",
                        docs=len(prepared)):
            detected: dict = {}
            for idx, payload in hits:
                detected.setdefault(idx, []).append(payload)
            out = dict(failures)
            for idx, ((i, p), (_, name, atype, decoded)) in \
                    enumerate(zip(prepared, metas)):
                results, os_found = scanner.finish(
                    p, detected.get(idx, []))
                out[i] = BatchScanResult(
                    name=name,
                    report=Report(artifact_name=name,
                                  artifact_type=atype,
                                  metadata=Metadata(os=os_found),
                                  results=results,
                                  cyclonedx=decoded.cyclonedx))
            ordered = [out[i] for i in range(len(boms))]
        jobs_in = kstats.get("jobs_in", len(all_jobs))
        self.last_stats = {
            "sboms": len(boms),
            # decode and the name join, as ever
            "decode_s": round(dsp.duration_s + jsp.duration_s, 4),
            # the calling thread's seconds in the interval phases
            "interval_dispatch_s": round(
                kstats.get("dispatch_s", 0.0), 4),
            "interval_device_s": round(
                kstats.get("device_s", 0.0), 4),
            "interval_jobs": len(all_jobs),
            "interval_jobs_unique": kstats.get("jobs_unique", 0),
            "interval_dedup_ratio": round(
                1.0 - kstats.get("jobs_unique", 0) / jobs_in, 4)
            if jobs_in else 0.0,
        }
        return ordered


class _TreeStream:
    """One tree's secret candidates on their way through the
    scheduler in parts: what ``LocalFSArtifact.inspect`` streams
    into. ``emit`` hands a closed part to
    ``ScanScheduler.submit_part`` and returns at once unless as many
    parts are unresolved as the ring has slots and one more (the
    one waiting for a slot); with the part the walk is filling,
    those are all of the tree's bytes the host holds. A part's
    bytes are let go when its findings are delivered. A part that
    fails fails the tree: the error is raised where the walk next
    meets the stream."""

    def __init__(self, sched, req, scanner):
        import threading

        from ..secret.batch import PartCutter
        self.sched, self.req = sched, req
        self.cutter = PartCutter(scanner)
        self.in_flight = max(1, sched.config.dispatch_depth) + 1
        self._free = threading.Semaphore(self.in_flight)
        self._lock = threading.Lock()
        self._found: list = []       # (seq, Secret)
        self._error: Optional[BaseException] = None
        self._held = 0               # bytes of unresolved parts
        # peak_held_bytes: the most candidate bytes the tree held
        # at once, unresolved parts and the open one together
        self.stats = {"parts": 0, "files": 0, "bytes": 0,
                      "peak_held_bytes": 0}

    def _take(self) -> None:
        """One unresolved part's place, waited for; a tree whose
        deadline passes or that is cancelled stops waiting."""
        from ..sched import DeadlineExceeded, RequestCancelled
        while not self._free.acquire(timeout=0.5):
            if self.req.cancelled:
                raise RequestCancelled(
                    f"scan {self.req.name!r}: cancelled")
            if self.req.expired():
                raise DeadlineExceeded(
                    f"scan {self.req.name!r}: deadline exceeded")
        if self._error is not None:
            raise self._error

    def emit(self, part: list) -> None:
        seqs = [seq for seq, _, _ in part]
        files = [(path, content) for _, path, content in part]
        del part
        nbytes = sum(len(c) for _, c in files)
        with self._lock:
            st = self.stats
            st["parts"] += 1
            st["files"] += len(files)
            st["bytes"] += nbytes
            st["peak_held_bytes"] = max(
                st["peak_held_bytes"],
                self._held + nbytes + self.cutter.open_bytes)
        self._take()
        with self._lock:
            self._held += nbytes

        def deliver(found: list) -> None:
            self._found.extend((seqs[j], secret)
                               for j, secret in found)
            del files[:]             # the part's bytes go here

        def on_done(part_req) -> None:
            try:
                part_req.result(timeout=0)
            except Exception as e:   # noqa: BLE001 (the tree's now)
                if self._error is None:
                    self._error = e
            with self._lock:
                self._held -= nbytes
            self._free.release()

        self.sched.submit_part(self.req, files, deliver, on_done)

    def finish(self) -> list:
        """Every part back: the Secrets, in the files' order."""
        for _ in range(self.in_flight):
            self._take()
        self._found.sort(key=lambda found: found[0])
        return [secret for _, secret in self._found]


class _CollectingImageArtifact(ImageArtifact):
    """ImageArtifact that defers secret scanning to the batch: its
    _batch_secrets records (layer, path, content) and returns nothing;
    the runner patches blobs once the global dispatch resolves."""

    def inspect(self):
        self.collected = []        # per-instance, even when cached
        return super().inspect()

    def _batch_secrets(self, candidates: list) -> dict:
        self.collected = [(li, "/" + path, content)
                          for li, path, content in candidates]
        return {}


class _SchedImageArtifact(_CollectingImageArtifact):
    """Collecting artifact that additionally announces which cache
    blobs this request will patch — registered with the scheduler
    BEFORE put_blob runs, so concurrent requests sharing a layer
    always see either the patched blob or the pending-write event."""

    _sched = None
    _sched_req = None

    def _inspect_layers(self, todo, blob_ids, base):
        self._sched_blob_ids = blob_ids
        return super()._inspect_layers(todo, blob_ids, base)

    def _batch_secrets(self, candidates: list) -> dict:
        if candidates and self._sched is not None and \
                self.opt.scan_secrets:
            ids = sorted({self._sched_blob_ids[li]
                          for li, _, _ in candidates})
            self._sched.register_blob_writes(ids, self._sched_req)
        return super()._batch_secrets(candidates)


def _failed_slot(name: str, err: BaseException, trace_id: str = "",
                 tracer=None) -> BatchScanResult:
    """One failed fleet slot with a machine-readable cause: the
    typed scheduler errors map to distinct kinds so a caller can
    tell backpressure (retryable) from deadline (not) from a broken
    image. When the slot was traced, a trailing ``obs/trace`` cause
    references the flight-recorder dump (the primary cause stays
    first — callers key off ``causes[0]``)."""
    import tarfile as _tarfile

    from ..guard.budget import GuardError
    from ..ops.program import DeviceProgramError
    from ..sched import (DeadlineExceeded, QueueFullError,
                         SchedulerClosed)
    if isinstance(err, DeviceProgramError):
        # a kernel that does not compile: the package's fault, not
        # the image's — the CLI exits non-zero on it
        stage, kind = "device", "program_fault"
    elif isinstance(err, GuardError):
        # ingest-guard trip (docs/robustness.md): resource-budget
        # (bombs, floods, deadlines) or malformed-archive
        # (traversal, truncation, undecodable names)
        stage, kind = err.stage, err.kind
    elif isinstance(err, DeadlineExceeded):
        stage, kind = "sched", "deadline_exceeded"
    elif isinstance(err, QueueFullError):
        stage, kind = "sched", "queue_full"
    elif isinstance(err, SchedulerClosed):
        stage, kind = "sched", "shutdown"
    elif isinstance(err, (OSError, ValueError,
                          _tarfile.TarError)):
        stage, kind = "host", "load_failed"
    else:
        stage, kind = "sched", "error"
    res = BatchScanResult(name=name, error=str(err)).mark_failed(
        stage, kind, str(err))
    if trace_id and tracer is not None:
        from ..obs.trace import trace_cause
        from ..types.report import FailureCause
        res.causes.append(
            FailureCause.coerce(trace_cause(tracer, trace_id)))
    return res


def _make_patch(cache, artifact):
    """Per-request secret patch: map batch sieve results back to this
    artifact's layers by the LOCAL candidate index and rewrite the
    affected cached blobs (the one-artifact slice of _patch_blobs)."""

    def patch(found: list) -> None:
        by_layer: dict = {}
        for idx, s in found:
            li = artifact.collected[idx][0]
            by_layer.setdefault(li, []).append(s)
        for li, secrets in by_layer.items():
            blob_id = artifact.reference.blob_ids[li]
            blob = cache.get_blob(blob_id)
            if blob is not None:
                secrets.sort(key=lambda s: s.file_path)
                for s in secrets:
                    s.findings.sort(key=lambda f: (f.rule_id,
                                                   f.start_line))
                blob.secrets = secrets
                cache.put_blob(blob_id, blob)

    return patch


def _patch_blobs(cache, artifacts, found) -> None:
    """Map batch results back to (artifact, layer) by the entry index
    scan_files returns and rewrite the affected cached blobs. Path
    strings are never consulted: fleets share file trees, so identical
    paths across images/layers are the common case, not the exception."""
    owners = []
    for a in artifacts:
        for li, _path, _ in a.collected:
            owners.append((a, li))
    by_blob: dict = {}
    for idx, s in found:
        a, li = owners[idx]
        by_blob.setdefault((a, li), []).append(s)
    for (a, li), secrets in by_blob.items():
        blob_id = a.reference.blob_ids[li]
        blob = cache.get_blob(blob_id)
        if blob is not None:
            secrets.sort(key=lambda s: s.file_path)
            for s in secrets:
                s.findings.sort(key=lambda f: (f.rule_id,
                                               f.start_line))
            blob.secrets = secrets
            cache.put_blob(blob_id, blob)
