"""Compiled (TPU-resident) advisory tables: parity, persistence,
scale, hot swap."""

import glob
import os
import random
import time

import pytest

from trivy_tpu.db import AdvisoryStore, CompiledDB, SwappableStore
from trivy_tpu.db.fixtures import load_fixtures
from trivy_tpu.detect.batch import (ResidentPairJob,
                                    detect_pairs_resident)
from trivy_tpu.vercmp import get_comparer
from trivy_tpu.vercmp.base import is_vulnerable

REF_DB = "/root/reference/integration/testdata/fixtures/db"


@pytest.fixture(scope="module")
def fixture_store():
    if not os.path.isdir(REF_DB):
        pytest.skip("reference fixtures not mounted")
    return load_fixtures(sorted(glob.glob(f"{REF_DB}/*.yaml")))


@pytest.fixture(scope="module")
def fixture_cdb(fixture_store):
    return CompiledDB.compile(fixture_store)


def _jobs_for(cdb, prefix, pkg, version, grammar):
    return [ResidentPairJob(cdb=cdb, row=row, grammar=grammar,
                            pkg_version=version,
                            payload=(row, version))
            for row in cdb.candidate_rows_prefix(prefix, pkg)]


def test_compiled_matches_host_on_fixtures(fixture_store,
                                           fixture_cdb):
    """Every (bucket pkg, probe version) decision must equal the
    exact host evaluation."""
    cdb = fixture_cdb
    cases = 0
    for bucket, pkgs in fixture_store.buckets.items():
        from trivy_tpu.db.compiled import bucket_grammar
        grammar = bucket_grammar(bucket)
        if grammar is None:
            continue
        comparer = get_comparer(grammar)
        for pkg in pkgs:
            for adv in fixture_store.get(bucket, pkg):
                probes = set()
                if adv.fixed_version:
                    probes.add(adv.fixed_version)
                for c in (list(adv.vulnerable_versions) +
                          list(adv.patched_versions)):
                    for tok in c.replace(",", " ").split():
                        v = tok.lstrip("<>=!~^[(").rstrip(")]")
                        if v and v[0].isdigit():
                            probes.add(v)
                for version in probes:
                    try:
                        comparer.parse(version)
                    except ValueError:
                        continue
                    want = is_vulnerable(
                        comparer, version, adv.vulnerable_versions,
                        adv.patched_versions, adv.unaffected_versions)\
                        if (adv.vulnerable_versions or
                            adv.patched_versions or
                            adv.unaffected_versions) else None
                    if want is None:   # ospkg advisory
                        if adv.fixed_version:
                            want = comparer.compare(
                                version, adv.fixed_version) < 0
                        else:
                            want = True
                    # one vuln id can appear in several advisories
                    # of the same package (redhat-oval entries with
                    # different fixed versions) — match the exact
                    # advisory, not just the id
                    rows = [i for i in
                            cdb.candidate_rows(bucket, pkg)
                            if cdb.rows_meta[i][2] is adv
                            or (cdb.rows_meta[i][2]
                                .vulnerability_id ==
                                adv.vulnerability_id
                                and cdb.rows_meta[i][2]
                                .fixed_version ==
                                adv.fixed_version)]
                    assert rows
                    jobs = [ResidentPairJob(
                        cdb=cdb, row=rows[0], grammar=grammar,
                        pkg_version=version, payload=1)]
                    got = bool(detect_pairs_resident(jobs,
                                                     backend="cpu-ref"))
                    assert got == want, (bucket, pkg,
                                         adv.vulnerability_id,
                                         version)
                    cases += 1
    assert cases > 50


def test_fuzz_resident_vs_host():
    """Random semver advisories: resident path == exact host path."""
    rng = random.Random(7)
    store = AdvisoryStore()
    n_adv = 300
    for i in range(n_adv):
        lo = f"{rng.randrange(4)}.{rng.randrange(10)}.{rng.randrange(10)}"
        hi = f"{rng.randrange(4, 8)}.{rng.randrange(10)}.{rng.randrange(10)}"
        fixed = f"{rng.randrange(8)}.{rng.randrange(10)}.{rng.randrange(10)}"
        store.put_advisory(
            "cargo::Fuzz", f"pkg{i % 40}", f"CVE-FUZZ-{i}",
            {"VulnerableVersions": [f">= {lo}, < {hi}"],
             "PatchedVersions": [fixed]})
    cdb = CompiledDB.compile(store)
    comparer = get_comparer("semver")
    checked = 0
    for i in range(400):
        pkg = f"pkg{rng.randrange(40)}"
        ver = f"{rng.randrange(8)}.{rng.randrange(10)}.{rng.randrange(10)}"
        rows = cdb.candidate_rows("cargo::Fuzz", pkg)
        jobs = [ResidentPairJob(cdb=cdb, row=r, grammar="semver",
                                pkg_version=ver, payload=r)
                for r in rows]
        got = sorted(detect_pairs_resident(jobs, backend="cpu-ref"))
        want = sorted(
            r for r in rows
            if is_vulnerable(comparer, ver,
                             cdb.rows_meta[r][2].vulnerable_versions,
                             cdb.rows_meta[r][2].patched_versions,
                             cdb.rows_meta[r][2].unaffected_versions))
        assert got == want
        checked += len(rows)
    assert checked > 1000


def test_save_load_roundtrip(fixture_cdb, tmp_path):
    path = str(tmp_path / "db")
    fixture_cdb.save(path)
    loaded = CompiledDB.load(path)
    assert loaded.stats == fixture_cdb.stats
    assert (loaded.flags == fixture_cdb.flags).all()
    # a detection through the loaded store matches
    jobs = _jobs_for(loaded, "pip::", "werkzeug", "0.11", "pep440")
    got = detect_pairs_resident(jobs, backend="cpu-ref")
    assert len(got) == 2


def test_scale_100k_advisories_dispatch_is_o_packages():
    """Compile 100k synthetic advisories once; per-dispatch host work
    must not scale with the advisory universe."""
    rng = random.Random(3)
    store = AdvisoryStore()
    N = 100_000
    n_pkgs = 5_000
    for i in range(N):
        lo = f"{rng.randrange(5)}.{rng.randrange(20)}.0"
        hi = f"{rng.randrange(5, 9)}.{rng.randrange(20)}.0"
        store.put_advisory(
            "npm::Scale", f"lib{i % n_pkgs}", f"CVE-S-{i}",
            {"VulnerableVersions": [f">={lo} <{hi}"]})
    t0 = time.monotonic()
    cdb = CompiledDB.compile(store)
    compile_s = time.monotonic() - t0
    assert cdb.stats["rows"] == N

    # dispatch against 50 packages — host time must be tiny compared
    # to compile time (rank lookups + dict joins only)
    jobs = []
    for i in range(50):
        pkg = f"lib{rng.randrange(n_pkgs)}"
        ver = f"{rng.randrange(9)}.{rng.randrange(20)}.0"
        jobs.extend(_jobs_for(cdb, "npm::", pkg, ver, "npm"))
    t0 = time.monotonic()
    hits = detect_pairs_resident(jobs, backend="cpu-ref")
    dispatch_s = time.monotonic() - t0
    assert jobs and hits is not None
    # O(packages) check: a full-universe rebuild costs ~compile_s per
    # dispatch; the resident path must be far below that
    assert dispatch_s < max(0.25, compile_s / 20), \
        (dispatch_s, compile_s)
    # fallback-rate telemetry exists
    assert "host_fallback_rate" in cdb.stats


def test_hot_swap_blocks_until_readers_drain(fixture_cdb):
    import threading
    sw = SwappableStore(fixture_cdb)
    db1 = sw.acquire()
    new_db = CompiledDB()
    done = threading.Event()

    def swapper():
        sw.swap(new_db, stage=False)
        done.set()

    t = threading.Thread(target=swapper)
    t.start()
    time.sleep(0.05)
    assert not done.is_set(), "swap must wait for readers"
    assert sw.current() is db1 or sw.current() is fixture_cdb
    sw.release()
    t.join(timeout=5)
    assert done.is_set()
    assert sw.current() is new_db


def test_save_is_atomic_single_file(fixture_cdb, tmp_path):
    """Round 4 (ADVICE): persistence is ONE data-only npz written via
    temp+rename — no pickle sidecar, no partial pair to observe."""
    import os
    path = str(tmp_path / "db")
    fixture_cdb.save(path)
    assert os.path.exists(path + ".npz")
    assert not os.path.exists(path + ".pkl")
    assert not os.path.exists(path + ".npz.tmp")
    # file must be loadable by a plain JSON/npz reader (data-only):
    import json as _json
    import numpy as _np
    arrs = _np.load(path + ".npz")
    meta = _json.loads(arrs["meta"].tobytes().decode())
    assert "universe" in meta and "grammars" in meta
    # no record a row in meta: the rows are array members, their
    # strings one JSON list
    assert "rows_meta" not in meta and "index" not in meta
    n = len(fixture_cdb.rows_meta)
    assert arrs["rows"].shape == (n, 6)
    assert arrs["row_grammar"].shape == (n,)
    assert arrs["forms"].shape[1] == 10
    assert arrs["items"].ndim == 1
    strings = _json.loads(arrs["strings"].tobytes().decode())
    assert all(isinstance(v, str) for v in strings)


def test_load_restores_key_types(fixture_cdb, tmp_path):
    """bisect at scan time compares fresh parse keys against loaded
    ones — types must round-trip exactly for every grammar."""
    path = str(tmp_path / "db")
    fixture_cdb.save(path)
    loaded = CompiledDB.load(path)
    for g, (keys, base) in fixture_cdb.universe.items():
        k2, b2 = loaded.universe[g]
        assert b2 == base and k2 == keys
        for a, b in zip(keys, k2):
            assert type(a) is type(b), (g, type(a), type(b))


def test_truncated_db_does_not_kill_watcher(fixture_cdb, tmp_path):
    """A garbage file at the watched path must log and keep the old
    tables (ADVICE: the old except clause let zip errors kill the
    watcher thread permanently)."""
    from trivy_tpu.db.compiled import SwappableStore
    from trivy_tpu.rpc.server import DBWorker
    path = str(tmp_path / "db")
    fixture_cdb.save(path)
    store = SwappableStore(fixture_cdb)
    w = DBWorker(store, path, interval_s=3600)
    with open(path + ".npz", "wb") as f:
        f.write(b"PK\x03\x04 definitely not a real zip")
    assert w.check_once() is False
    assert store.current() is fixture_cdb      # old tables intact
    fixture_cdb.save(path)                      # recovery still works
    assert w.check_once() is True


def test_date_only_values_round_trip(tmp_path):
    """yaml parses unquoted day-only values into datetime.date —
    save must tag them, load must restore the exact type."""
    import datetime
    from trivy_tpu.db import AdvisoryStore
    s = AdvisoryStore()
    s.put_advisory("alpine 3.16", "p", "CVE-9",
                   {"FixedVersion": "1.0.0-r0"})
    s.put_vulnerability("CVE-9", {
        "Severity": "LOW",
        "PublishedDate": datetime.date(2020, 2, 1),
        "LastModifiedDate": datetime.datetime(
            2020, 9, 14, 18, 32,
            tzinfo=datetime.timezone.utc)})
    cdb = CompiledDB.compile(s)
    path = str(tmp_path / "db")
    cdb.save(path)
    v = CompiledDB.load(path).vulnerabilities["CVE-9"]
    assert v["PublishedDate"] == datetime.date(2020, 2, 1)
    assert type(v["PublishedDate"]) is datetime.date
    assert v["LastModifiedDate"].tzinfo is not None


# ---- the table as columns (no Python object a row) -------------------

def _every_field_store(n_pkgs: int = 12) -> "AdvisoryStore":
    """A generated table that uses every Advisory field: apk rows
    with an affected version, unfixed deb rows, Red Hat rows with
    arches, vendor ids, a data source and content sets, library rows
    with one to three constraint lists, rows on the host path (more
    alternatives than the interval table holds, a constraint that
    does not parse) and a bucket with no grammar. ``n_pkgs`` scales
    the rows and nothing else: versions, sources and list lengths
    come from small fixed sets."""
    store = AdvisoryStore()
    src = {"ID": "redhat-oval", "Name": "Red Hat OVAL v2",
           "URL": "https://www.redhat.com/security/data/oval/v2/"}
    for i in range(n_pkgs):
        store.put_advisory(
            "alpine 3.16", f"apk-{i}", f"CVE-A-{i}",
            {"FixedVersion": f"1.{i % 5}.0-r1",
             "AffectedVersion": f"1.{i % 3}.0-r0"})
        store.put_advisory(
            "debian 11", f"deb-{i}", f"CVE-D-{i}",
            {"FixedVersion": "" if i % 2 else f"2.{i % 4}-1",
             "Severity": i % 4})
        store.put_advisory(
            "Red Hat", f"rpm-{i}", f"CVE-R-{i}",
            {"FixedVersion": f"0:3.{i % 4}-1.el8",
             "Arches": ["x86_64", "aarch64"][:1 + i % 2],
             "VendorIDs": [f"RHSA-2026:{i % 7:04d}"],
             "Severity": 3, "DataSource": src,
             "ContentSets": ["rhel-8-for-x86_64-baseos-rpms",
                             "rhel-8-for-x86_64-appstream-rpms"]})
        store.put_advisory(
            "npm::GitHub Security Advisory Npm", f"lib-{i}",
            f"GHSA-N-{i}",
            {"VulnerableVersions": [f"<1.{i % 6}.0",
                                    f">=2.0.0, <2.{i % 6}.1"],
             "PatchedVersions": [f">=1.{i % 6}.0, <2.0.0",
                                 f">=2.{i % 6}.1"],
             "UnaffectedVersions": ["<0.1.0"][:i % 2],
             "DataSource": {"ID": "ghsa", "Name": "GitHub",
                            "URL": "https://github.com/advisories"}})
        store.put_advisory(
            "pip::GitHub Security Advisory Pip", f"lib-{i}",
            f"GHSA-P-{i}", {"VulnerableVersions": [f"<3.{i % 6}"]})
        # host path: six alternatives where the table holds four
        store.put_advisory(
            "npm::GitHub Security Advisory Npm", f"lib-{i}",
            f"GHSA-H-{i}",
            {"VulnerableVersions": [
                " || ".join(f"={k}.{i % 6}.0" for k in range(6))]})
        # host path: a constraint that does not parse
        store.put_advisory(
            "cargo::GitHub Security Advisory Rust", f"crate-{i}",
            f"RUSTSEC-{i}", {"VulnerableVersions": [">>nope"],
                             "PatchedVersions": [f">=1.{i % 6}.0"]})
        # no grammar for this bucket
        store.put_advisory("plan9 4", f"p9-{i}", f"CVE-9-{i}",
                           {"FixedVersion": "1.0"})
    store.put_vulnerability("CVE-A-0", {"Title": "t",
                                        "Severity": "HIGH"})
    return store


def _expected_rows(store) -> list:
    """The rows as the table before the columns kept them: one
    ``(bucket, pkg, Advisory)`` tuple a row, in compile order."""
    return [(b, p, adv) for b in sorted(store.buckets)
            for p in sorted(store.buckets[b])
            for adv in store.get(b, p)]


def _host_eval_ref(bucket, adv, version):
    """``CompiledDB.host_eval`` from a row tuple: the exact host
    decision for one (advisory, installed version)."""
    from trivy_tpu.db.compiled import bucket_grammar
    comparer = get_comparer(bucket_grammar(bucket) or "semver")
    if adv.vulnerable_versions or adv.patched_versions or \
            adv.unaffected_versions:
        return is_vulnerable(comparer, version,
                             adv.vulnerable_versions,
                             adv.patched_versions,
                             adv.unaffected_versions)
    try:
        if adv.affected_version and \
                comparer.parse(adv.affected_version) > \
                comparer.parse(version):
            return False
        return adv.fixed_version == "" or \
            comparer.compare(version, adv.fixed_version) < 0
    except ValueError:
        return False


@pytest.fixture(scope="module")
def generated_store():
    return _every_field_store()


@pytest.fixture(params=["fixture", "generated"])
def table_store(request):
    return request.getfixturevalue(request.param + "_store")


@pytest.mark.parametrize("via", ["compile", "load"])
def test_rows_equal_the_row_tuples(table_store, via, tmp_path):
    """Every row read from the columns equals the tuple the table
    used to keep, after compile and after save and load; so do the
    name join's candidate rows and the host evaluation."""
    store = table_store
    cdb = CompiledDB.compile(store)
    if via == "load":
        cdb.save(str(tmp_path / "db"))
        cdb = CompiledDB.load(str(tmp_path / "db"))
    want = _expected_rows(store)
    assert len(cdb.rows_meta) == len(want) == cdb.stats["rows"]
    assert [cdb.rows_meta[i] for i in range(len(want))] == want
    assert list(cdb.rows_meta) == want
    # a fresh Advisory a read: mutating one changes nothing
    first = cdb.rows_meta[0][2]
    first.vulnerable_versions.append("mutated")
    first.vulnerability_id = "mutated"
    assert cdb.rows_meta[0] == want[0]
    assert cdb.rows_meta[-1] == want[-1]
    with pytest.raises(IndexError):
        cdb.rows_meta[len(want)]

    index: dict = {}
    for i, (b, p, _adv) in enumerate(want):
        index.setdefault(b, {}).setdefault(p, []).append(i)
    for b, pkgs in index.items():
        for p, rows in pkgs.items():
            assert list(cdb.candidate_rows(b, p)) == rows
        assert list(cdb.candidate_rows(b, "no-such-package")) == []
    assert list(cdb.candidate_rows("no such bucket", "p")) == []
    assert {b: {p: list(r) for p, r in pkgs.items()}
            for b, pkgs in cdb.index.items()} == index
    for pre in {b.split("::", 1)[0] + "::" for b in index
                if "::" in b} | {"alpine", "nothing::"}:
        names = {p for b, pkgs in index.items()
                 if b.startswith(pre) for p in pkgs}
        for p in sorted(names) + ["no-such-package"]:
            assert cdb.candidate_rows_prefix(pre, p) == [
                i for b in index if b.startswith(pre)
                for i in index[b].get(p, [])]

    probes = ["0.0.1", "1.0.0", "1.2.0-r0", "1.3.0", "2.0.0", "2.2-1",
              "2.3.1", "3.2", "0:3.1-1.el8", "5.0.0", "not a version"]
    checked = 0
    for i, (b, _p, adv) in enumerate(want):
        for version in probes:
            assert cdb.host_eval(i, version) == \
                _host_eval_ref(b, adv, version), (i, version)
            checked += 1
    assert checked >= 11 * len(want)


def _tracked_objects_added(make) -> int:
    """Collector-tracked objects alive after ``make()`` returned its
    table, over those alive before: what the table holds."""
    import gc
    gc.collect()
    before = len(gc.get_objects())
    cdb = make()
    gc.collect()
    after = len(gc.get_objects())
    assert len(cdb.rows_meta)
    return after - before


@pytest.mark.parametrize("via", ["compile", "load"])
def test_tracked_objects_do_not_grow_with_rows(via, tmp_path):
    """A table of four times the rows holds the collector-tracked
    objects of the small one, within a small constant: a full
    collection does not walk the rows. As row tuples the tables
    differed by eight objects a row (a tuple, an Advisory and its
    six lists) and by a list a package in the index."""
    def make(n_pkgs):
        if via == "compile":
            return lambda: CompiledDB.compile(
                _every_field_store(n_pkgs))
        path = str(tmp_path / f"db{n_pkgs}")
        CompiledDB.compile(_every_field_store(n_pkgs)).save(path)
        return lambda: CompiledDB.load(path)

    _tracked_objects_added(make(8))        # imports, caches, logging
    small = _tracked_objects_added(make(100))
    large = _tracked_objects_added(make(400))
    rows = 8 * (400 - 100)
    assert len(CompiledDB.compile(_every_field_store(400)).rows_meta) \
        - len(CompiledDB.compile(_every_field_store(100)).rows_meta) \
        == rows
    assert abs(large - small) < 64, (small, large)


def _save_as_row_records(cdb, want: list, path: str) -> None:
    """``save`` as it was before the columns: one JSON record a row,
    the index as lists and the grammar a row, all in ``meta``."""
    import json

    import numpy as np

    from trivy_tpu.db.compiled import _adv_enc, _enc_key, _json_default
    index: dict = {}
    for i, (b, p, _adv) in enumerate(want):
        index.setdefault(b, {}).setdefault(p, []).append(i)
    meta = {
        "rows_meta": [(b, p, _adv_enc(a)) for b, p, a in want],
        "row_grammar": list(cdb.row_grammar),
        "index": index,
        "universe": {g: [[_enc_key(k) for k in keys], base]
                     for g, (keys, base) in cdb.universe.items()},
        "vulnerabilities": cdb.vulnerabilities,
        "data_sources": cdb.data_sources,
        "stats": cdb.stats,
    }
    blob = np.frombuffer(
        json.dumps(meta, default=_json_default).encode(), np.uint8)
    with open(path + ".npz", "wb") as f:
        np.savez_compressed(f, v_lo=cdb.v_lo, v_hi=cdb.v_hi,
                            s_lo=cdb.s_lo, s_hi=cdb.s_hi,
                            flags=cdb.flags, meta=blob)


def test_file_in_the_row_record_format_still_loads(generated_store,
                                                   tmp_path, caplog):
    """A compiled.npz written before the columns (a user's file, a
    stale data cache) loads to the same table, and says it is old."""
    import logging
    cdb = CompiledDB.compile(generated_store)
    want = _expected_rows(generated_store)
    path = str(tmp_path / "old")
    _save_as_row_records(cdb, want, path)
    root = logging.getLogger("trivy_tpu")   # does not propagate
    root.addHandler(caplog.handler)
    try:
        old = CompiledDB.load(path)
    finally:
        root.removeHandler(caplog.handler)
    assert "old compiled-db format" in caplog.text
    assert list(old.rows_meta) == want
    assert old.row_grammar == cdb.row_grammar
    assert old.index == cdb.index
    assert old.universe == cdb.universe
    assert old.stats == cdb.stats
    assert old.vulnerabilities == cdb.vulnerabilities
    assert (old.flags == cdb.flags).all() and \
        (old.v_lo == cdb.v_lo).all()
    assert old.content_fingerprint() == cdb.content_fingerprint()
    # and what it saves is the new format
    old.save(str(tmp_path / "new"))
    new = CompiledDB.load(str(tmp_path / "new"))
    assert list(new.rows_meta) == want
    assert new.content_fingerprint() == cdb.content_fingerprint()


def test_out_of_range_columns_are_refused(generated_store, tmp_path):
    """The columns index one another; a file whose indices point
    outside (truncated, hand-edited) is refused at load, not at the
    first row a scan reads."""
    import numpy as np
    path = str(tmp_path / "db")
    CompiledDB.compile(generated_store).save(path)
    members = dict(np.load(path + ".npz"))
    for name, bad in (("rows", 10 ** 6), ("rows", -1),
                      ("items", 10 ** 6), ("forms", 10 ** 6)):
        arrs = dict(members)
        arrs[name] = arrs[name].copy()
        arrs[name].flat[arrs[name].size - 1] = bad
        with open(path + "-bad.npz", "wb") as f:
            np.savez_compressed(f, **arrs)
        with pytest.raises(ValueError):
            CompiledDB.load(path + "-bad")
    arrs = dict(members)
    arrs["items"] = arrs["items"][:-1]
    with open(path + "-bad.npz", "wb") as f:
        np.savez_compressed(f, **arrs)
    with pytest.raises(ValueError):
        CompiledDB.load(path + "-bad")


# content_fingerprint() of CompiledDB.compile(_every_field_store())
# at the commit before the columns (4161d83): a findings memo kept on
# disk is keyed on it and has to survive the upgrade
_GOLDEN_FINGERPRINT = "7b3104e921250978f7322a420af6aeb5"


@pytest.mark.parametrize("via", ["compile", "load"])
def test_content_fingerprint_is_the_row_tuples(generated_store, via,
                                               tmp_path):
    cdb = CompiledDB.compile(generated_store)
    if via == "load":
        cdb.save(str(tmp_path / "db"))
        cdb = CompiledDB.load(str(tmp_path / "db"))
    assert cdb.content_fingerprint() == _GOLDEN_FINGERPRINT
    assert CompiledDB().content_fingerprint() == \
        "4f53cda18c2baa0c0354bb5f9a3ecbe5"


def test_table_rows_decoded_counts_the_join(generated_store):
    """``table_rows_decoded`` is the rows the name join built from
    the columns: every candidate row of every package asked for,
    added once a join, and exported on /metrics."""
    import json

    from trivy_tpu.detect.metrics import DETECT_METRICS
    from trivy_tpu.obs.prom import render_prometheus
    from trivy_tpu.runtime import BatchScanRunner
    from trivy_tpu.sched.metrics import SchedMetrics
    cdb = CompiledDB.compile(generated_store)
    comps = [("npm", "lib-0", "1.0.0"), ("npm", "lib-1", "2.0.0"),
             ("pypi", "lib-2", "1.0"), ("npm", "absent", "1.0.0")]
    bom = {"bomFormat": "CycloneDX", "specVersion": "1.4",
           "serialNumber": "urn:uuid:rows-decoded", "version": 1,
           "metadata": {"component": {
               "bom-ref": "root", "type": "container", "name": "c"}},
           "components": [
               {"bom-ref": f"ref-{k}", "type": "library", "name": n,
                "version": v, "purl": f"pkg:{t}/{n}@{v}"}
               for k, (t, n, v) in enumerate(comps)]}
    want = sum(len(cdb.candidate_rows_prefix(pre, n)) for pre, n in
               (("npm::", "lib-0"), ("npm::", "lib-1"),
                ("pip::", "lib-2"), ("npm::", "absent")))
    assert want == 5
    before = DETECT_METRICS.snapshot()["table_rows_decoded"]
    results = BatchScanRunner(store=cdb, backend="cpu").scan_boms(
        [("a.cdx.json", json.dumps(bom).encode())] * 3)
    assert all(r.status == "ok" for r in results)
    after = DETECT_METRICS.snapshot()["table_rows_decoded"]
    assert after - before == 3 * want
    text = render_prometheus(SchedMetrics().snapshot())
    assert 'event="table_rows_decoded"' in text
