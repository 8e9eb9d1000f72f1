"""Advisory store + detectors + batch interval kernel tests."""

import random

import pytest

from trivy_tpu.db import AdvisoryStore, load_fixtures
from trivy_tpu.detect import new_library_driver, ospkg_detect
from trivy_tpu.detect.batch import PairJob, dispatch_jobs
from trivy_tpu.detect.enrich import fill_info
from trivy_tpu.types import Package


@pytest.fixture()
def store(tmp_path):
    fixture = tmp_path / "db.yaml"
    fixture.write_text("""
- bucket: alpine 3.9
  pairs:
    - bucket: openssl
      pairs:
        - key: CVE-2019-1549
          value: {FixedVersion: 1.1.1d-r0}
        - key: CVE-2019-1551
          value: {FixedVersion: 1.1.1d-r2}
    - bucket: musl
      pairs:
        - key: CVE-2019-14697
          value: {FixedVersion: 1.1.20-r5}
- bucket: debian 9
  pairs:
    - bucket: bash
      pairs:
        - key: CVE-2016-9401
          value: {FixedVersion: "4.4-5", Severity: 1}
        - key: CVE-2019-18276
          value: {Severity: 2}
- bucket: "pip::GitHub Security Advisory Pip"
  pairs:
    - bucket: django
      pairs:
        - key: CVE-2021-44420
          value:
            PatchedVersions: ["2.2.25", "3.1.14", "3.2.10"]
            VulnerableVersions: ["<2.2.25", ">=3.0a1, <3.1.14",
                                 ">=3.2a1, <3.2.10"]
- bucket: "npm::GitHub Security Advisory Npm"
  pairs:
    - bucket: jquery
      pairs:
        - key: CVE-2020-11022
          value:
            PatchedVersions: [">=3.5.0"]
            VulnerableVersions: [">=1.2.0 <3.5.0"]
- bucket: vulnerability
  pairs:
    - key: CVE-2016-9401
      value:
        Title: "bash: popd controlled free"
        Severity: LOW
        VendorSeverity: {nvd: 1, redhat: 1}
        References: ["https://www.debian.org/security/x"]
    - key: CVE-2021-44420
      value:
        Severity: HIGH
        VendorSeverity: {nvd: 3, "ghsa": 3}
""")
    return load_fixtures([str(fixture)])


class TestStore:
    def test_get(self, store):
        advs = store.get("alpine 3.9", "openssl")
        assert {a.vulnerability_id for a in advs} == \
            {"CVE-2019-1549", "CVE-2019-1551"}

    def test_prefix_scan(self, store):
        advs = store.get_advisories("pip::", "django")
        assert len(advs) == 1
        assert advs[0].patched_versions == ["2.2.25", "3.1.14",
                                            "3.2.10"]

    def test_vulnerability_detail(self, store):
        d = store.get_vulnerability("CVE-2016-9401")
        assert d.severity == "LOW"
        assert d.vendor_severity["redhat"] == 1


class TestOspkg:
    def test_alpine(self, store):
        pkgs = [Package(name="openssl", src_name="openssl",
                        version="1.1.1c", src_version="1.1.1c",
                        release="r0", src_release="r0"),
                Package(name="musl", src_name="musl",
                        version="1.1.21", src_version="1.1.21",
                        release="r0", src_release="r0")]
        vulns, eosl = ospkg_detect("alpine", "3.9.4", None, pkgs,
                                   store)
        ids = {(v.pkg_name, v.vulnerability_id) for v in vulns}
        assert ("openssl", "CVE-2019-1549") in ids
        assert ("openssl", "CVE-2019-1551") in ids
        # musl 1.1.21-r0 > fixed 1.1.20-r5 → not vulnerable
        assert not any(p == "musl" for p, _ in ids)
        assert eosl is True      # 3.9 EOL was 2020-11-01

    def test_debian_unfixed_and_severity(self, store):
        pkgs = [Package(name="bash", src_name="bash",
                        version="4.4-4", src_version="4.4-4")]
        vulns, _ = ospkg_detect("debian", "9.13", None, pkgs, store)
        by_id = {v.vulnerability_id: v for v in vulns}
        assert "CVE-2016-9401" in by_id        # 4.4-4 < 4.4-5
        assert "CVE-2019-18276" in by_id       # unfixed → reported
        v = by_id["CVE-2016-9401"]
        assert v.severity_source == "debian"
        assert v.vulnerability.severity == "LOW"

    def test_fixed_not_vulnerable(self, store):
        pkgs = [Package(name="bash", src_name="bash",
                        version="5.0-1", src_version="5.0-1")]
        vulns, _ = ospkg_detect("debian", "9", None, pkgs, store)
        assert {v.vulnerability_id for v in vulns} == \
            {"CVE-2019-18276"}


class TestLibrary:
    def test_pip_ranges(self, store):
        d = new_library_driver("pip")
        vulns = d.detect(store, "", "Django", "3.1.13")
        assert [v.vulnerability_id for v in vulns] == \
            ["CVE-2021-44420"]
        assert vulns[0].fixed_version == "2.2.25, 3.1.14, 3.2.10"
        assert d.detect(store, "", "Django", "3.1.14") == []
        assert d.detect(store, "", "django", "2.2.24") != []

    def test_npm(self, store):
        d = new_library_driver("npm")
        assert d.detect(store, "", "jquery", "3.4.1") != []
        assert d.detect(store, "", "jquery", "3.5.0") == []


class TestEnrich:
    def test_severity_precedence(self, store):
        d = new_library_driver("pip")
        vulns = d.detect(store, "", "django", "2.2.0")
        fill_info(store, vulns)
        v = vulns[0]
        # datasource id absent in VendorSeverity → NVD fallback
        assert v.vulnerability.severity == "HIGH"
        assert v.severity_source == "nvd"
        assert v.primary_url == \
            "https://avd.aquasec.com/nvd/cve-2021-44420"

    def test_package_specific_severity_wins(self, store):
        pkgs = [Package(name="bash", src_name="bash",
                        version="4.4-4", src_version="4.4-4")]
        vulns, _ = ospkg_detect("debian", "9", None, pkgs, store)
        fill_info(store, vulns)
        v = next(x for x in vulns
                 if x.vulnerability_id == "CVE-2016-9401")
        assert v.vulnerability.severity == "LOW"
        assert v.severity_source == "debian"
        assert v.vulnerability.title == "bash: popd controlled free"


class TestBatchKernel:
    GRAMMARS = ["semver", "pep440", "npm", "rubygems", "maven"]

    def _random_constraint(self, rng):
        v = f"{rng.randrange(4)}.{rng.randrange(10)}.{rng.randrange(10)}"
        op = rng.choice(["<", "<=", ">", ">=", "=", ""])
        return f"{op}{v}"

    def test_differential_vs_host(self):
        from trivy_tpu.vercmp import get_comparer
        from trivy_tpu.vercmp.base import is_vulnerable

        rng = random.Random(11)
        jobs = []
        expect = []
        for i in range(400):
            grammar = rng.choice(self.GRAMMARS)
            ver = f"{rng.randrange(4)}.{rng.randrange(10)}" \
                  f".{rng.randrange(10)}"
            vulnerable = [self._random_constraint(rng)
                          for _ in range(rng.randrange(0, 3))]
            patched = [self._random_constraint(rng)
                       for _ in range(rng.randrange(0, 2))]
            unaffected = [self._random_constraint(rng)
                          for _ in range(rng.randrange(0, 2))]
            jobs.append(PairJob(grammar=grammar, pkg_version=ver,
                                vulnerable=vulnerable,
                                patched=patched,
                                unaffected=unaffected, payload=i))
            want = is_vulnerable(get_comparer(grammar), ver,
                                 vulnerable, patched, unaffected)
            if want:
                expect.append(i)

        got = sorted(dispatch_jobs(jobs, backend="cpu-ref"))
        assert got == expect
        got_tpu = sorted(dispatch_jobs(jobs))
        assert got_tpu == expect
        assert expect, "differential corpus must have positives"

    def test_ospkg_pairs(self):
        jobs = [
            PairJob(grammar="apk", pkg_version="1.1.1c-r0",
                    fixed_version="1.1.1d-r0", kind="ospkg",
                    payload="hit"),
            PairJob(grammar="apk", pkg_version="1.1.1d-r0",
                    fixed_version="1.1.1d-r0", kind="ospkg",
                    payload="miss"),
            PairJob(grammar="apk", pkg_version="1.0.0-r0",
                    fixed_version="", kind="ospkg",
                    report_unfixed=True, payload="unfixed"),
            PairJob(grammar="apk", pkg_version="1.0.0-r0",
                    fixed_version="", kind="ospkg",
                    report_unfixed=False, payload="skipped"),
            PairJob(grammar="apk", pkg_version="1.0.0-r0",
                    fixed_version="2.0-r0",
                    affected_version="1.5-r0", kind="ospkg",
                    payload="too-old"),
        ]
        got = set(dispatch_jobs(jobs, backend="cpu-ref"))
        assert got == {"hit", "unfixed"}

    def test_empty_string_forces(self):
        jobs = [PairJob(grammar="semver", pkg_version="9.9.9",
                        vulnerable=[""], patched=[], unaffected=[],
                        payload="forced")]
        assert dispatch_jobs(jobs, backend="cpu-ref") == ["forced"]


class TestRedHatContentSets:
    """Content-set narrowing (ref redhat.go:27-44,129-138): an
    advisory listing content sets only matches packages whose
    buildinfo sets (or NVR, or the per-major defaults) intersect."""

    def _store(self):
        from trivy_tpu.db.store import AdvisoryStore
        s = AdvisoryStore()
        s.put_advisory("Red Hat", "openssl", "CVE-2099-0001", {
            "FixedVersion": "1:1.1.1k-7.el8_6",
            "ContentSets": ["rhel-8-for-x86_64-baseos-rpms"]})
        s.put_advisory("Red Hat", "openssl", "CVE-2099-0002", {
            "FixedVersion": "1:1.1.1k-8.el8_6",
            "ContentSets": ["rhel-8-for-s390x-baseos-rpms"]})
        s.put_advisory("Red Hat", "openssl", "CVE-2099-0003", {
            "FixedVersion": "1:1.1.1k-9.el8_6"})   # no sets: global
        return s

    def _pkg(self, build_info=None):
        return Package(name="openssl", version="1.1.1k", release="6.el8",
                       epoch=1, arch="x86_64", src_name="openssl",
                       src_version="1.1.1k", src_release="6.el8",
                       src_epoch=1, build_info=build_info)

    def _ids(self, pkg, os_ver="8.6"):
        vulns, _ = ospkg_detect("redhat", os_ver, None, [pkg],
                                self._store())
        return {v.vulnerability_id for v in vulns}

    def test_buildinfo_narrows(self):
        pkg = self._pkg({"ContentSets":
                         ["rhel-8-for-x86_64-baseos-rpms"]})
        # the s390x-only advisory is suppressed
        assert self._ids(pkg) == {"CVE-2099-0001", "CVE-2099-0003"}

    def test_out_of_set_all_suppressed(self):
        pkg = self._pkg({"ContentSets":
                         ["rhel-8-for-aarch64-baseos-rpms"]})
        assert self._ids(pkg) == {"CVE-2099-0003"}

    def test_default_content_sets_fallback(self):
        # no buildinfo (plain RHEL host) -> defaults for major 8
        assert self._ids(self._pkg()) == \
            {"CVE-2099-0001", "CVE-2099-0003"}

    def test_nvr_match(self):
        s = self._store()
        s.put_advisory("Red Hat", "openssl", "CVE-2099-0004", {
            "FixedVersion": "1:1.1.1k-10.el8_6",
            "ContentSets": ["ubi8-container-8.6-100-x86_64"]})
        pkg = self._pkg({"ContentSets": [],
                         "Nvr": "ubi8-container-8.6-100",
                         "Arch": "x86_64"})
        vulns, _ = ospkg_detect("redhat", "8.6", None, [pkg], s)
        ids = {v.vulnerability_id for v in vulns}
        assert "CVE-2099-0004" in ids
        assert "CVE-2099-0001" not in ids


class TestRedHatSameCVEMerge:
    """Several RHSAs can fix one CVE (redhat-oval emits one advisory
    per (entry, CVE)); the uniqueness pass must MERGE them — newest
    FixedVersion per the rpm comparer, union of vendor ids — instead
    of keeping whichever entry it saw first (ref redhat.go
    uniqVulns)."""

    def _detect(self, order):
        from trivy_tpu.db.store import AdvisoryStore
        from trivy_tpu.scan.filter import filter_results
        from trivy_tpu.types import Result, Severity
        s = AdvisoryStore()
        entries = {
            "RHSA-2099:0001": "1:1.1.1k-7.el8_6",
            "RHSA-2099:0002": "1:1.1.1k-9.el8_6",
        }
        for key in order:
            s.put_advisory("Red Hat", "openssl", key, {
                "Entries": [{
                    "FixedVersion": entries[key],
                    "Cves": [{"ID": "CVE-2099-1000",
                              "Severity": 3}],
                }]})
        vulns, _ = ospkg_detect("redhat", "8.6", None,
                                [Package(name="openssl",
                                         version="1.1.1k",
                                         release="6.el8", epoch=1,
                                         arch="x86_64")], s)
        assert len(vulns) == 2      # both advisories matched
        result = Result(target="t", vulnerabilities=vulns)
        filter_results([result], [Severity.parse(sv) for sv in
                                  ("UNKNOWN", "LOW", "MEDIUM",
                                   "HIGH", "CRITICAL")])
        return result.vulnerabilities

    def test_merges_newest_fix_and_unions_vendor_ids(self):
        merged = self._detect(["RHSA-2099:0001", "RHSA-2099:0002"])
        assert len(merged) == 1
        assert merged[0].fixed_version == "1:1.1.1k-9.el8_6"
        assert merged[0].vendor_ids == ["RHSA-2099:0001",
                                        "RHSA-2099:0002"]

    def test_merge_is_order_independent(self):
        a = self._detect(["RHSA-2099:0001", "RHSA-2099:0002"])
        b = self._detect(["RHSA-2099:0002", "RHSA-2099:0001"])
        assert a[0].fixed_version == b[0].fixed_version
        assert a[0].vendor_ids == b[0].vendor_ids

    def test_non_redhat_keeps_first_with_fix(self):
        from trivy_tpu.scan.filter import filter_results
        from trivy_tpu.types import (DetectedVulnerability, Result,
                                     Severity)
        unfixed = DetectedVulnerability(
            vulnerability_id="CVE-1", pkg_name="p",
            installed_version="1")
        fixed = DetectedVulnerability(
            vulnerability_id="CVE-1", pkg_name="p",
            installed_version="1", fixed_version="2")
        result = Result(target="t",
                        vulnerabilities=[unfixed, fixed])
        filter_results([result], [Severity.parse("UNKNOWN")])
        assert [v.fixed_version
                for v in result.vulnerabilities] == ["2"]


class TestBuildInfoPipeline:
    def test_content_manifest_analyzer(self):
        import json
        from trivy_tpu.analyzer.buildinfo import \
            ContentManifestAnalyzer
        a = ContentManifestAnalyzer()
        path = "root/buildinfo/content_manifests/ubi8.json"
        assert a.required(path)
        assert not a.required("etc/content_manifests/x.json")
        res = a.analyze(path, json.dumps(
            {"content_sets": ["rhel-8-for-x86_64-baseos-rpms"]}
        ).encode())
        assert res.build_info == {
            "ContentSets": ["rhel-8-for-x86_64-baseos-rpms"]}

    def test_dockerfile_analyzer(self):
        from trivy_tpu.analyzer.buildinfo import \
            BuildInfoDockerfileAnalyzer
        a = BuildInfoDockerfileAnalyzer()
        path = "root/buildinfo/Dockerfile-ubi8-8.6-100"
        assert a.required(path)
        content = (b'FROM scratch\n'
                   b'ENV COMP=ubi8-container\n'
                   b'LABEL com.redhat.component="$COMP" '
                   b'architecture="x86_64"\n')
        res = a.analyze(path, content)
        assert res.build_info == {"Nvr": "ubi8-container-8.6-100",
                                  "Arch": "x86_64"}

    def test_applier_shares_buildinfo(self):
        from trivy_tpu.applier import apply_layers
        from trivy_tpu.types import BlobInfo, PackageInfo
        base = BlobInfo(
            diff_id="sha256:base",
            package_infos=[PackageInfo(
                file_path="var/lib/rpm/Packages",
                packages=[Package(name="openssl",
                                  version="1.1.1k")])])
        redhat_layer = BlobInfo(
            diff_id="sha256:rh",
            build_info={"ContentSets": ["rhel-8-for-x86_64-baseos-rpms"]})
        customer = BlobInfo(
            diff_id="sha256:cust",
            package_infos=[PackageInfo(
                file_path="var/lib/rpm/Packages",
                packages=[Package(name="openssl",
                                  version="1.1.1k"),
                          Package(name="curl",
                                  version="7.61.1")])])
        detail = apply_layers([base, redhat_layer, customer])
        by_name = {p.name: p for p in detail.packages}
        # base layer shares layer 1's record; the customer layer
        # (no record of its own) inherits the nearest Red Hat layer
        assert by_name["openssl"].build_info == {
            "ContentSets": ["rhel-8-for-x86_64-baseos-rpms"]}
        assert by_name["curl"].build_info == {
            "ContentSets": ["rhel-8-for-x86_64-baseos-rpms"]}
