"""Local scan driver (reference: pkg/scanner/local/scan.go:78-175).

ApplyLayers → OS + language vuln detection (the batched interval
kernel) → secrets/misconf results → FillInfo enrichment.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from ..applier import apply_layers
from ..db import AdvisoryStore
from ..db.compiled import CompiledDB
from ..detect.batch import (PairJob, ResidentPairJob, detect_pairs,
                            dispatch_jobs)
from ..detect.enrich import fill_info
from ..detect.library import _TYPES as LIB_TYPES
from ..detect.library import _fixed_versions, normalize_pkg_name
from ..detect.metrics import DETECT_METRICS
from ..detect.ospkg.drivers import DRIVERS, format_src_version
from ..types import (OS, DetectedVulnerability, Result, ResultClass,
                     Vulnerability)
from ..types.common import SEVERITIES
from ..utils import get_logger

log = get_logger("scan.local")

# pre-defined targets for aggregated package types (scan.go pkgTargets)
_PKG_TARGETS = {
    "python-pkg": "Python",
    "node-pkg": "Node.js",
    "gemspec": "Ruby",
    "jar": "Java",
}


@dataclass
class ScanTarget:
    name: str
    artifact_id: str
    blob_ids: list


@dataclass
class PreparedScan:
    """Phase-1 output: everything needed to finish once the batched
    interval kernel returns."""

    target: ScanTarget
    options: object
    detail: object
    jobs: list
    eosl: bool
    pkg_results: list
    # findings-memo state (trivy_tpu.memo): the per-package queries
    # _vuln_jobs recorded, and the hit/miss partition plan finish()
    # resolves — both None when no memo is attached
    queries: Optional[list] = None
    memo_plan: object = None
    # requester identity (server tenant scope) — the impact index
    # records it per image so hot-swap push re-scans stay
    # tenant-scoped
    tenant: str = ""


class LocalScanner:
    def __init__(self, cache, store: Optional[AdvisoryStore] = None,
                 memo=None, tenant: str = ""):
        self.cache = cache
        self.store = store or AdvisoryStore()
        self.compiled: Optional[CompiledDB] = \
            store if isinstance(store, CompiledDB) else None
        # memo: trivy_tpu.memo.FindingsMemo — per-layer detection
        # verdicts served without device dispatch when the exact
        # question was answered before (docs/performance.md)
        self.memo = memo
        self.tenant = tenant

    def scan(self, target: ScanTarget, options: ScanOptions) -> tuple:
        """Returns (results, os) — single-target convenience around
        prepare + one kernel dispatch + finish."""
        prepared = self.prepare(target, options)
        detected = dispatch_jobs(prepared.jobs,
                                 backend=options.backend)
        return self.finish(prepared, detected)

    def prepare(self, target: ScanTarget,
                options: ScanOptions) -> PreparedScan:
        """ApplyLayers + advisory name-join → pair jobs. No kernel
        work happens here, so a batch runner can merge many targets'
        jobs into one dispatch — and with streaming ingest the
        runner calls prepare per image as soon as ITS layers have
        analyzed, overlapping the join with later images' in-flight
        fetches. The ``join`` phase span lives here, not in the
        callers, so idle attribution (host_pack_bound) sees the
        squash/name-join identically on the direct and scheduled
        paths."""
        from ..obs.trace import phase_span
        with phase_span("join", pipeline="detect",
                        blobs=len(target.blob_ids)):
            return self.join(target, options)

    def join(self, target: ScanTarget,
             options: ScanOptions) -> PreparedScan:
        """:meth:`prepare` without a phase of its own, for a caller
        that brackets one ``join`` phase round a whole batch
        (``scan_boms``: thousands of one-blob documents a call,
        where a span a document would be a span inside the loop)."""
        blobs = [self.cache.get_blob(b) for b in target.blob_ids]
        detail = apply_layers(blobs)

        if options.scan_removed_packages:
            # packages installed-then-deleted in the Dockerfile:
            # reconstructed from RUN history at inspect time, merged
            # with installed packages taking priority by name
            # (ref local/scan.go:181-182,523-536 mergePkgs)
            info = self.cache.get_artifact(target.artifact_id)
            history = getattr(info, "history_packages", None) or []
            present = {p.name for p in detail.packages}
            detail.packages.extend(
                p for p in history if p.name not in present)

        # repository fallback BEFORE the "none" default — a
        # distroless alpine has packages and an apk repositories
        # stream but no release file (ref local/scan.go:82-97,
        # where the Repository assignment overwrites the "none"
        # default unconditionally)
        if detail.os is None and detail.repository is not None:
            detail.os = OS(family=detail.repository.family,
                           name=detail.repository.release)
        if detail.os is None and detail.packages:
            detail.os = OS(family="none")

        pkg_results: list = []
        if options.list_all_packages:
            r = self._os_pkgs_result(target.name, detail)
            if r is not None:
                pkg_results.append(r)
            pkg_results.extend(self._lang_pkgs_results(detail))

        jobs, eosl = ([], False)
        queries = [] if self.memo is not None else None
        if "vuln" in options.security_checks:
            jobs, eosl = self._vuln_jobs(detail, options,
                                         queries=queries)
        prepared = PreparedScan(target=target, options=options,
                                detail=detail, jobs=jobs, eosl=eosl,
                                pkg_results=pkg_results,
                                queries=queries,
                                tenant=self.tenant)
        if self.memo is not None and jobs:
            # hit/miss partition: verdicts answered before are
            # served at finish; only novel queries keep their jobs
            # for the device dispatch (docs/performance.md)
            prepared.memo_plan = self.memo.partition(
                prepared, blobs, detail, options, db=self.store)
            plan = prepared.memo_plan
            if plan is not None:
                # cost attribution (obs/cost.py): memo hits are
                # device work this tenant did NOT pay for — the
                # invoice shows them next to the device-seconds
                # the misses went on to cost
                from ..obs.cost import COST_LEDGER
                COST_LEDGER.charge(
                    self.tenant,
                    memo_hits=int(getattr(plan, "queries_hit", 0)
                                  or 0),
                    memo_misses=int(getattr(plan, "queries_miss",
                                            0) or 0))
        return prepared

    def finish(self, prepared: PreparedScan,
               detected: list) -> tuple:
        """Assemble results from the detected pair payloads."""
        options = prepared.options
        detail = prepared.detail
        results: list = []

        if prepared.memo_plan is not None:
            # record the novel queries' verdicts, append the served
            # hits — the hit payloads are THIS scan's own job
            # payloads, so results are byte-identical to a cold run
            detected = self.memo.resolve(prepared.memo_plan,
                                         detected)
            prepared.memo_plan = None

        if "vuln" in options.security_checks:
            if detail.os is not None:
                detail.os.eosl = prepared.eosl
            vuln_results = self._vuln_results(
                prepared.target.name, detail, detected,
                options.vuln_type)
            results.extend(self._fill_pkgs(prepared.pkg_results,
                                           vuln_results))
        else:
            results.extend(prepared.pkg_results)

        if "config" in options.security_checks:
            results.extend(self._misconf_results(detail))

        if "secret" in options.security_checks:
            results.extend(self._secret_results(detail))

        if "license" in options.security_checks:
            results.extend(self._license_results(
                detail, getattr(options, "license_categories", None)))

        # module-collected custom resources ride a Result of class
        # "custom" so post-scanners can read them (ref
        # local/scan.go:154-163)
        if detail.custom_resources:
            results.append(Result(
                target="", class_=ResultClass.CUSTOM,
                custom_resources=list(detail.custom_resources)))

        for r in results:
            fill_info(self.store, r.vulnerabilities)

        # post-scan hook chain (ref local/scan.go:170-174 post.Scan)
        from .post import post_scan
        results = post_scan(results)

        return results, detail.os

    # --- vulnerabilities ---

    def _vuln_jobs(self, detail, options,
                   queries: Optional[list] = None) -> tuple:
        jobs: list = []
        eosl = False
        if queries is not None:
            from ..memo.findings import MemoQuery

        cdb = self.compiled
        decoded = 0     # rows read from the compiled table
        if "os" in options.vuln_type and detail.os is not None \
                and detail.packages:
            driver = DRIVERS.get(detail.os.family)
            if driver is not None:
                eosl = not driver.is_supported(detail.os.name)
                bucket = driver.bucket(detail.os.name,
                                       detail.repository)
                for pkg in detail.packages:
                    installed = driver.installed(pkg)
                    qstart = len(jobs)
                    if cdb is not None:
                        rows = cdb.candidate_rows(
                            bucket, driver.src_name(pkg))
                        decoded += len(rows)
                        for row in rows:
                            adv = cdb.rows_meta[row][2]
                            if not driver.adv_match(
                                    detail.os.name, pkg, adv):
                                continue
                            jobs.append(ResidentPairJob(
                                cdb=cdb, row=row,
                                grammar=driver.grammar,
                                pkg_version=installed,
                                report_unfixed=driver.report_unfixed,
                                payload=("os", None, self._ospkg_vuln(
                                    driver, pkg, installed, adv))))
                    else:
                        for adv in self.store.get(
                                bucket, driver.src_name(pkg)):
                            if not driver.adv_match(detail.os.name,
                                                    pkg, adv):
                                continue
                            jobs.append(self._ospkg_job(
                                driver, pkg, installed, adv))
                    if queries is not None and len(jobs) > qstart:
                        queries.append(MemoQuery(
                            kind="os", bucket=bucket,
                            name=driver.src_name(pkg),
                            grammar=driver.grammar,
                            installed=installed,
                            report_unfixed=driver.report_unfixed,
                            pkg=pkg, start=qstart, end=len(jobs),
                            os_name=detail.os.name,
                            family=detail.os.family))
            elif detail.os.family not in ("none", ""):
                log.warning("unsupported os: %s", detail.os.family)

        if "library" in options.vuln_type:
            for app in detail.applications:
                if app.type not in LIB_TYPES:
                    continue
                eco, grammar = LIB_TYPES[app.type]
                for lib in app.libraries:
                    name = normalize_pkg_name(eco, lib.name)
                    qstart = len(jobs)
                    if cdb is not None:
                        rows = cdb.candidate_rows_prefix(
                            f"{eco}::", name)
                        decoded += len(rows)
                        for row in rows:
                            adv = cdb.rows_meta[row][2]
                            jobs.append(ResidentPairJob(
                                cdb=cdb, row=row, grammar=grammar,
                                pkg_version=lib.version,
                                payload=("lib",
                                         (app.type, app.file_path),
                                         self._lib_vuln(lib, adv))))
                    else:
                        for adv in self.store.get_advisories(
                                f"{eco}::", name):
                            jobs.append(self._lib_job(
                                app, grammar, lib, adv))
                    if queries is not None and len(jobs) > qstart:
                        queries.append(MemoQuery(
                            kind="lib", bucket=f"{eco}::",
                            name=name, grammar=grammar,
                            installed=lib.version,
                            report_unfixed=True, pkg=lib,
                            start=qstart, end=len(jobs)))
        if decoded:
            DETECT_METRICS.inc("table_rows_decoded", decoded)
        return jobs, eosl

    def _vuln_results(self, target: str, detail,
                      detected: list, vuln_type: list) -> list:
        os_vulns: list = []
        app_vulns: dict = {}
        for payload in detected:
            kind, key, vuln = payload
            if kind == "os":
                os_vulns.append(vuln)
            else:
                app_vulns.setdefault(key, []).append(vuln)

        results = []
        # the os-pkgs result is emitted whenever a known distro was
        # detected, even with zero findings (ref scan.go:243-271
        # scanOSPkgs returns a Result unless the OS is unknown or
        # unsupported; empty results are never filtered out)
        # gated on the os vuln type, like scanVulnerabilities
        # dispatch — `--vuln-type library` must not emit the husk
        has_driver = ("os" in vuln_type
                      and detail.os is not None
                      and DRIVERS.get(detail.os.family) is not None)
        if os_vulns or has_driver:
            target_name = target
            if detail.os is not None and detail.os.family and \
                    detail.os.family != "none":
                target_name = (f"{target} ({detail.os.family} "
                               f"{detail.os.name})")
            results.append(Result(
                target=target_name,
                class_=ResultClass.OSPKG,
                type=detail.os.family if detail.os else "",
                vulnerabilities=sorted(
                    os_vulns, key=lambda v: (v.pkg_name,
                                             v.vulnerability_id)),
            ))
        for app in detail.applications:
            key = (app.type, app.file_path)
            vulns = app_vulns.get(key)
            if not vulns:
                continue
            target_name = app.file_path or \
                _PKG_TARGETS.get(app.type, "")
            results.append(Result(
                target=target_name,
                class_=ResultClass.LANGPKG,
                type=app.type,
                vulnerabilities=sorted(
                    vulns, key=lambda v: (v.pkg_name,
                                          v.vulnerability_id)),
            ))
        return results

    def _ospkg_vuln(self, driver, pkg, installed,
                    adv) -> DetectedVulnerability:
        v = DetectedVulnerability(
            vulnerability_id=adv.vulnerability_id,
            vendor_ids=adv.vendor_ids,
            pkg_id=pkg.id,
            pkg_name=pkg.name,
            installed_version=installed,
            fixed_version=driver.fixed_version(adv),
            layer=pkg.layer,
            ref=pkg.ref,
            data_source=adv.data_source,
        )
        if driver.severity_source and adv.severity:
            v.severity_source = driver.severity_source
            v.vulnerability = Vulnerability(
                severity=str(SEVERITIES[adv.severity])
                if 0 <= adv.severity < 5 else "UNKNOWN")
        return v

    def _ospkg_job(self, driver, pkg, installed, adv) -> PairJob:
        v = self._ospkg_vuln(driver, pkg, installed, adv)
        return PairJob(
            grammar=driver.grammar,
            pkg_version=installed,
            fixed_version=adv.fixed_version,
            affected_version=adv.affected_version,
            report_unfixed=driver.report_unfixed,
            kind="ospkg",
            payload=("os", None, v),
        )

    def _lib_vuln(self, lib, adv) -> DetectedVulnerability:
        return DetectedVulnerability(
            vulnerability_id=adv.vulnerability_id,
            pkg_id=lib.id,
            pkg_name=lib.name,
            pkg_path=lib.file_path,
            installed_version=lib.version,
            fixed_version=_fixed_versions(adv),
            layer=lib.layer,
            ref=lib.ref,
            data_source=adv.data_source,
        )

    def _lib_job(self, app, grammar, lib, adv) -> PairJob:
        v = self._lib_vuln(lib, adv)
        return PairJob(
            grammar=grammar,
            pkg_version=lib.version,
            vulnerable=adv.vulnerable_versions,
            patched=adv.patched_versions,
            unaffected=adv.unaffected_versions,
            payload=("lib", (app.type, app.file_path), v),
        )

    # --- other result classes ---

    def _os_pkgs_result(self, target, detail) -> Optional[Result]:
        if not detail.packages or detail.os is None:
            return None
        pkgs = sorted(detail.packages, key=lambda p: p.name)
        return Result(
            target=f"{target} ({detail.os.family} {detail.os.name})",
            class_=ResultClass.OSPKG,
            type=detail.os.family,
            packages=pkgs,
        )

    def _lang_pkgs_results(self, detail) -> list:
        out = []
        for app in detail.applications:
            if not app.libraries:
                continue
            target = app.file_path or _PKG_TARGETS.get(app.type, "")
            out.append(Result(target=target,
                              class_=ResultClass.LANGPKG,
                              type=app.type,
                              packages=app.libraries))
        return out

    def _fill_pkgs(self, pkg_results, vuln_results) -> list:
        """Merge package listings into matching vuln results
        (scan.go fillPkgsInVulns)."""
        if not pkg_results:
            return vuln_results
        out = []
        used = set()
        for vr in vuln_results:
            for i, pr in enumerate(pkg_results):
                if (pr.class_, pr.target) == (vr.class_, vr.target):
                    vr.packages = pr.packages
                    used.add(i)
                    break
            out.append(vr)
        for i, pr in enumerate(pkg_results):
            if i not in used:
                out.append(pr)
        return out

    def _secret_results(self, detail) -> list:
        out = []
        for secret in detail.secrets:
            out.append(Result(
                target=secret.file_path,
                class_=ResultClass.SECRET,
                secrets=secret.findings,
            ))
        return out

    def _misconf_results(self, detail) -> list:
        """misconfsToResults (ref local/scan.go:337-371): flatten each
        file's failures/warnings/successes into status-tagged
        DetectedMisconfigurations."""
        out = []
        for mc in detail.misconfigurations:
            detected = []
            for f in mc.failures:
                detected.append(_to_detected_misconf(
                    f, "CRITICAL", "FAIL", mc.layer,
                    traces=mc.traces))
            for w in mc.warnings:
                detected.append(_to_detected_misconf(
                    w, "MEDIUM", "FAIL", mc.layer,
                    traces=mc.traces))
            # the per-file trace rides every failure; an all-pass
            # file carries it once on its first success — exactly
            # the case where "clean" must be distinguishable from
            # "couldn't evaluate" — instead of duplicating the
            # whole list onto every PASS row
            file_traces = mc.traces if not (
                mc.failures or mc.warnings) else []
            for s in mc.successes:
                detected.append(_to_detected_misconf(
                    s, "UNKNOWN", "PASS", mc.layer,
                    traces=file_traces))
                file_traces = []
            for e in mc.exceptions:
                detected.append(_to_detected_misconf(
                    e, "UNKNOWN", "EXCEPTION", mc.layer,
                    traces=mc.traces))
            out.append(Result(
                target=mc.file_path,
                class_=ResultClass.CONFIG,
                type=mc.file_type,
                misconfigurations=detected,
            ))
        out.sort(key=lambda r: r.target)
        return out


    def _license_results(self, detail, categories) -> list:
        """scanLicenses (ref local/scan.go:372-396 + 145-149): OS
        package licenses, per-application licenses, and loose-file
        classifier findings, each category-mapped to a severity."""
        from ..licensing import LicenseScanner
        from ..types.report import DetectedLicense

        scanner = LicenseScanner(categories or None)
        results = []

        os_licenses = []
        for pkg in detail.packages:
            for lic in pkg.licenses:
                category, severity = scanner.scan(lic)
                os_licenses.append(DetectedLicense(
                    severity=severity, category=category,
                    pkg_name=pkg.name, name=lic, confidence=1.0))
        results.append(Result(
            target="OS Packages", class_=ResultClass.LICENSE,
            licenses=os_licenses))

        for app in detail.applications:
            app_licenses = []
            for lib in app.libraries:
                for lic in lib.licenses:
                    category, severity = scanner.scan(lic)
                    app_licenses.append(DetectedLicense(
                        severity=severity, category=category,
                        pkg_name=lib.name, name=lic,
                        confidence=1.0))
            target = app.file_path or _PKG_TARGETS.get(app.type, "")
            results.append(Result(
                target=target, class_=ResultClass.LICENSE,
                licenses=app_licenses))

        file_licenses = []
        for lf in detail.licenses:
            for finding in lf.findings:
                category, severity = scanner.scan(finding.name)
                file_licenses.append(DetectedLicense(
                    severity=severity, category=category,
                    file_path=lf.file_path, name=finding.name,
                    confidence=finding.confidence,
                    link=finding.link))
        results.append(Result(
            target="Loose File License(s)",
            class_=ResultClass.LICENSE_FILE,
            licenses=file_licenses))
        return results


def _to_detected_misconf(res, default_severity: str, status: str,
                         layer, traces=None):
    """toDetectedMisconfiguration (ref local/scan.go:398-452)."""
    from ..types.report import DetectedMisconfiguration

    severity = res.severity or default_severity
    msg = (res.message or "").strip() or "No issues found"
    references = list(res.references)
    primary_url = ""
    if not res.namespace or res.namespace.startswith("builtin."):
        primary_url = ("https://avd.aquasec.com/misconfig/"
                       f"{res.id.lower()}")
        if primary_url not in references:
            references.append(primary_url)
    if not primary_url and references:
        primary_url = references[0]
    return DetectedMisconfiguration(
        type=res.type, id=res.id, avd_id=res.avd_id,
        title=res.title, description=res.description,
        message=msg, namespace=res.namespace, query=res.query,
        resolution=res.recommended_actions,
        severity=severity, primary_url=primary_url,
        references=references, status=status, layer=layer,
        cause_metadata=res.cause_metadata,
        traces=list(traces or []))
