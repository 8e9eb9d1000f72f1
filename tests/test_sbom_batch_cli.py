"""``trivy-tpu sbom`` over several documents, and what it rides:
``BatchScanRunner.scan_boms`` under repeats (PR 33, ``sbom-zipf-2m``).

The documents are drawn by popularity from a seed (Zipf, exponent 1,
3,000 names an ecosystem, a tenth of them advisory-bearing, a purl
twice in some documents and in many documents at once) and the table
is a few thousand rows. What every path has to give, document for
document, is worked out here by a plain matcher of Trivy's rule (a
version is vulnerable when it satisfies any of VulnerableVersions and
none of PatchedVersions) that imports nothing of ``detect/`` or
``db/compiled.py``.
"""

import contextlib
import io
import json
import os
import random

import numpy as np
import pytest

ECOSYSTEMS = (
    # name, bucket, purl prefix, advisory-name template
    ("npm", "npm::Node.js", "pkg:npm/", "{n}"),
    ("pip", "pip::Python", "pkg:pypi/", "{n}"),
    ("maven", "maven::Maven", "pkg:maven/smoke/", "smoke:{n}"),
    ("go", "go::Go", "pkg:golang/smoke/", "smoke/{n}"),
)
NAMES = 3000            # an ecosystem
BEARING = 300           # indices under it bear three advisories
DOCS, COMPS = 300, 12
SEED = 20260933


# ---------------------------------------------------------------------
# the data, from a seed
# ---------------------------------------------------------------------

def version(i: int, pick: int) -> str:
    return f"{(i * 7 + pick) % 4}.{(i * 13 + pick) % 10}." \
           f"{(i * 3 + pick) % 10}"


def advisories(seed: int) -> dict:
    """{(ecosystem, index): [(vuln id, advisory)] * 3}: an upper
    bound, a bounded range, or two alternatives."""
    rnd = random.Random(seed)
    out = {}
    for e, (eco, *_rest) in enumerate(ECOSYSTEMS):
        for i in range(BEARING):
            advs = []
            for a in range(3):
                fixed = f"{rnd.randint(1, 3)}.{rnd.randint(0, 9)}." \
                        f"{rnd.randint(1, 9)}"
                roll = rnd.random()
                if roll < 0.6:
                    adv = {"VulnerableVersions": [f"<{fixed}"],
                           "PatchedVersions": [f">={fixed}"]}
                elif roll < 0.85:
                    lo = f"{rnd.randint(0, 2)}.{rnd.randint(0, 9)}.0"
                    adv = {"VulnerableVersions": [f">={lo}, <{fixed}"],
                           "PatchedVersions": [f">={fixed}"]}
                else:
                    alt = f"{rnd.randint(2, 4)}.{rnd.randint(0, 9)}." \
                          f"{rnd.randint(1, 9)}"
                    adv = {"VulnerableVersions": [
                               f"<{fixed}", f">=2.0.0, <{alt}"],
                           "PatchedVersions": [f">={fixed}",
                                               f">={alt}"]}
                advs.append((f"GHSA-{eco}-{i:04d}-{a}", adv))
            out[(e, i)] = advs
    return out


def documents(seed: int) -> list:
    """``DOCS`` documents of ``COMPS`` components as ``[(name, bytes,
    [(ecosystem, index, version)])]``. Package indices by Zipf rank
    through a fixed permutation; every fifth document holds its
    first purl twice."""
    rng = np.random.default_rng(seed)
    comps = COMPS
    cdf = np.cumsum(1.0 / np.arange(1, NAMES + 1))
    cdf /= cdf[-1]
    order = np.random.default_rng(SEED).permutation(NAMES)
    out = []
    for n in range(DOCS):
        eco = rng.integers(0, len(ECOSYSTEMS), comps).tolist()
        idx = order[np.minimum(np.searchsorted(cdf, rng.random(comps)),
                               NAMES - 1)].tolist()
        pick = rng.integers(0, 3, comps).tolist()
        if n % 5 == 0:
            eco[1], idx[1], pick[1] = eco[0], idx[0], pick[0]
        facts = [(e, i, version(i, p))
                 for e, i, p in zip(eco, idx, pick)]
        components = []
        for k, (e, i, ver) in enumerate(facts):
            name = f"{ECOSYSTEMS[e][0]}-lib-{i}"
            purl = f"{ECOSYSTEMS[e][2]}{name}@{ver}"
            components.append({"bom-ref": f"{purl}-{n}-{k}",
                               "type": "library", "name": name,
                               "version": ver, "purl": purl})
        doc = {"bomFormat": "CycloneDX", "specVersion": "1.4",
               "serialNumber": f"urn:uuid:doc-{n}", "version": 1,
               "metadata": {"component": {
                   "bom-ref": "root", "type": "container",
                   "name": f"doc-{n}"}},
               "components": components}
        out.append((f"doc{n:04d}.cdx.json", json.dumps(doc).encode(),
                    facts))
    return out


# ---------------------------------------------------------------------
# the plain matcher
# ---------------------------------------------------------------------

def _v(text: str) -> tuple:
    return tuple(int(x) for x in text.split("."))


def _holds(v: tuple, entry: str) -> bool:
    for bound in (b.strip() for b in entry.split(",")):
        if bound.startswith(">="):
            if not v >= _v(bound[2:]):
                return False
        elif bound.startswith("<"):
            if not v < _v(bound[1:]):
                return False
        else:
            raise ValueError(bound)
    return True


def expected(table: dict, facts: list) -> set:
    """{(package name, version, vulnerability id)} of one document."""
    out = set()
    for e, i, ver in facts:
        eco, _bucket, _purl, tpl = ECOSYSTEMS[e]
        for vid, adv in table.get((e, i), ()):
            v = _v(ver)
            if any(_holds(v, x) for x in adv["VulnerableVersions"]) \
                    and not any(_holds(v, x)
                                for x in adv["PatchedVersions"]):
                out.add((tpl.format(n=f"{eco}-lib-{i}"), ver, vid))
    return out


def reported(doc: dict) -> set:
    return {(v["PkgName"], v["InstalledVersion"], v["VulnerabilityID"])
            for r in doc.get("Results") or []
            for v in r.get("Vulnerabilities") or []}


def found(report) -> set:
    """The same off a report that has not been rendered."""
    return {(v.pkg_name, v.installed_version, v.vulnerability_id)
            for r in report.results for v in r.vulnerabilities}


# ---------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def table():
    return advisories(SEED)


@pytest.fixture(scope="module")
def store(table):
    from trivy_tpu.db import AdvisoryStore
    s = AdvisoryStore()
    for (e, i), advs in table.items():
        eco, bucket, _purl, tpl = ECOSYSTEMS[e]
        for vid, adv in advs:
            s.put_advisory(bucket, tpl.format(n=f"{eco}-lib-{i}"),
                           vid, adv)
    return s


@pytest.fixture(scope="module")
def cdb(store):
    from trivy_tpu.db import CompiledDB
    return CompiledDB.compile(store)


@pytest.fixture(scope="module")
def cdb_path(cdb, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("cdb") / "cdb")
    cdb.save(path)
    return path


@pytest.fixture(scope="module")
def docs():
    return documents(SEED)


@pytest.fixture(scope="module")
def doc_dir(docs, tmp_path_factory):
    d = tmp_path_factory.mktemp("boms")
    for name, data, _ in docs:
        (d / name).write_bytes(data)
    (d / "notes.txt").write_text("not a document: no .json\n")
    return str(d)


def cli(*argv) -> tuple:
    from trivy_tpu.cli import main
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), \
            contextlib.redirect_stderr(err):
        code = main(list(argv))
    return code, out.getvalue(), err.getvalue()


def scan_flags(cdb_path, tmp_path) -> list:
    return ["--compiled-db", cdb_path, "--backend", "cpu",
            "--no-cache", "--cache-dir", str(tmp_path / "cache"),
            "--severity", "UNKNOWN,LOW,MEDIUM,HIGH,CRITICAL"]


# ---------------------------------------------------------------------
# (a) every path gives the plain matcher's findings
# ---------------------------------------------------------------------

def test_the_documents_repeat_purls(table, docs):
    """What the other tests stand on: purls repeated inside a
    document and across documents, and findings to compare."""
    purls = [[(e, i, v) for e, i, v in facts] for _, _, facts in docs]
    assert sum(len(set(p)) < len(p) for p in purls) >= DOCS // 5
    flat = [x for p in purls for x in p]
    assert len(set(flat)) < 0.7 * len(flat)
    found = [expected(table, facts) for _, _, facts in docs]
    assert sum(bool(f) for f in found) > DOCS // 4
    assert len(set().union(*found)) > 100


@pytest.mark.parametrize("store_kind", ["compiled", "plain"])
def test_scan_boms_gives_the_plain_matchers_findings(
        store_kind, store, cdb, table, docs):
    from trivy_tpu.runtime import BatchScanRunner
    runner = BatchScanRunner(
        store=cdb if store_kind == "compiled" else store,
        backend="cpu")
    results = runner.scan_boms([(n, d) for n, d, _ in docs])
    assert len(results) == len(docs)
    for (name, _, facts), res in zip(docs, results):
        assert res.status == "ok" and not res.error, name
        assert res.name == name
        assert found(res.report) == \
            expected(table, facts), name


@pytest.mark.parametrize("form", ["targets", "directory", "mixed"])
def test_command_gives_each_document_its_findings(
        form, table, docs, doc_dir, cdb_path, tmp_path):
    paths = [os.path.join(doc_dir, n) for n, _, _ in docs]
    targets = {"targets": paths[:40], "directory": [doc_dir],
               "mixed": paths[:3] + [doc_dir]}[form]
    code, out, err = cli("sbom", *targets, "--format", "json",
                         *scan_flags(cdb_path, tmp_path))
    assert code == 0, err
    reports = json.loads(out)
    want = {"targets": docs[:40], "directory": docs,
            "mixed": docs[:3] + docs}[form]
    assert [r["ArtifactName"] for r in reports] == \
        [os.path.join(doc_dir, n) for n, _, _ in want]
    for report, (name, _, facts) in zip(reports, want):
        assert report["ArtifactType"] == "cyclonedx"
        assert reported(report) == expected(table, facts), name


def test_command_gives_the_single_document_commands_report(
        docs, doc_dir, cdb_path, tmp_path):
    """Document for document the batch's report is the report the
    single-document command gives: the whole JSON, not the findings
    alone."""
    some = [os.path.join(doc_dir, docs[k][0]) for k in (0, 5, 17, 42)]
    flags = scan_flags(cdb_path, tmp_path)
    code, out, err = cli("sbom", *some, "--format", "json", *flags)
    assert code == 0, err
    many = json.loads(out)
    assert any(reported(r) for r in many)
    for path, report in zip(some, many):
        code, out, err = cli("sbom", path, "--format", "json", *flags)
        assert code == 0, err
        assert json.loads(out) == report, path


def test_one_target_keeps_the_single_document_path(
        docs, doc_dir, cdb_path, tmp_path, monkeypatch):
    from trivy_tpu import cli as cli_mod
    from trivy_tpu.runtime import BatchScanRunner

    def never(*a, **kw):
        raise AssertionError("one document took the batch path")

    monkeypatch.setattr(cli_mod, "_run_sbom_batch", never)
    monkeypatch.setattr(BatchScanRunner, "scan_boms", never)
    path = os.path.join(doc_dir, docs[5][0])
    code, out, err = cli("sbom", path, "--format", "json",
                         *scan_flags(cdb_path, tmp_path))
    assert code == 0, err
    report = json.loads(out)
    assert isinstance(report, dict)         # one object, not a list
    assert report["ArtifactName"] == path
    code, table_out, _ = cli("sbom", path,
                             *scan_flags(cdb_path, tmp_path))
    assert code == 0 and "Total: " in table_out


def test_command_rides_scan_boms_in_calls_of_bounded_size(
        docs, doc_dir, cdb_path, tmp_path, monkeypatch):
    """Calls of at most ``SBOM_CALL_DOCS`` documents, each call's
    files read when its turn comes: never more documents' bytes held
    than one call's."""
    from trivy_tpu import cli as cli_mod
    from trivy_tpu.detect.metrics import DETECT_METRICS
    from trivy_tpu.obs.trace import phase_rows
    from trivy_tpu.runtime import BatchScanRunner
    monkeypatch.setattr(cli_mod, "SBOM_CALL_DOCS", 128)
    real = BatchScanRunner.scan_boms
    calls = []

    def counted(self, boms, options=None):
        calls.append(len(boms))
        return real(self, boms, options)

    monkeypatch.setattr(BatchScanRunner, "scan_boms", counted)
    before = DETECT_METRICS.snapshot()
    reads = phase_rows("sbom").get("read", {}).get("n", 0)
    code, out, err = cli("sbom", doc_dir, "--format", "json",
                         *scan_flags(cdb_path, tmp_path))
    assert code == 0, err
    assert calls == [128, 128, DOCS - 256]
    assert len(json.loads(out)) == DOCS
    after = DETECT_METRICS.snapshot()
    assert after["sbom_docs"] - before["sbom_docs"] == DOCS
    assert after["sbom_components"] - before["sbom_components"] == \
        DOCS * COMPS
    assert after["jobs_unique"] - before["jobs_unique"] < \
        after["jobs_in"] - before["jobs_in"]
    assert phase_rows("sbom")["read"]["n"] - reads == 3


@pytest.mark.parametrize("fmt", ["table", "template"])
def test_command_writes_a_report_a_document(fmt, docs, doc_dir,
                                            cdb_path, tmp_path):
    paths = [os.path.join(doc_dir, docs[k][0]) for k in range(6)]
    extra = ["--template", "{{ range . }}{{ .Target }}\n{{ end }}"] \
        if fmt == "template" else []
    code, out, err = cli("sbom", *paths, "--format", fmt, *extra,
                         *scan_flags(cdb_path, tmp_path))
    assert code == 0, err
    # a table a document that has findings, one after the other
    assert out.count("Total: " if fmt == "table" else "\n") >= 2


@pytest.mark.parametrize("argv,code,said", [
    (["--format", "cyclonedx"], 2, "table/json/template"),
    (["--format", "sarif"], 2, "table/json/template"),
    (["--server", "http://127.0.0.1:1"], 2, "local-only"),
])
def test_command_refuses_what_would_not_work(argv, code, said, docs,
                                             doc_dir, cdb_path,
                                             tmp_path):
    paths = [os.path.join(doc_dir, docs[k][0]) for k in range(2)]
    got, _out, err = cli("sbom", *paths, *argv,
                         *scan_flags(cdb_path, tmp_path))
    assert got == code and said in err


def test_command_names_a_target_that_is_not_there(doc_dir, cdb_path,
                                                  tmp_path):
    empty = tmp_path / "empty"
    empty.mkdir()
    flags = scan_flags(cdb_path, tmp_path)
    code, _, err = cli("sbom", doc_dir, str(tmp_path / "nope.json"),
                       *flags)
    assert code == 1 and "nope.json" in err
    code, _, err = cli("sbom", str(empty), *flags)
    assert code == 1 and "no *.json" in err


# ---------------------------------------------------------------------
# (b) no compile when a pass's rows cross a rung's edge
# ---------------------------------------------------------------------

def _pass_of(rows_wanted: int, first: int) -> list:
    """Documents of the advisory-bearing names alone, enough of them
    for about ``rows_wanted`` distinct (row, version) jobs."""
    rng = np.random.default_rng([SEED, first])
    out, n = [], first
    per_doc = 40
    for _ in range(rows_wanted // (3 * per_doc) + 1):
        components = []
        for k in range(per_doc):
            e = int(rng.integers(0, len(ECOSYSTEMS)))
            i = int(rng.integers(0, BEARING))
            ver = f"{int(rng.integers(0, 40))}.{k}.{n % 97}"
            name = f"{ECOSYSTEMS[e][0]}-lib-{i}"
            purl = f"{ECOSYSTEMS[e][2]}{name}@{ver}"
            components.append({"bom-ref": f"{purl}-{k}",
                               "type": "library", "name": name,
                               "version": ver, "purl": purl})
        out.append((f"pass{n}.cdx.json", json.dumps({
            "bomFormat": "CycloneDX", "specVersion": "1.4",
            "version": 1, "components": components}).encode()))
        n += 1
    return out


@pytest.mark.parametrize("first_rows,second_rows", [
    (6000, 10000),      # 8,192: the old ladder's first step up
    (15000, 18000),     # 16,384
    (10000, 6000),      # and down again
])
def test_no_new_program_when_rows_cross_a_rungs_edge(
        first_rows, second_rows, cdb):
    from trivy_tpu.detect.batch import _WAVE_ROWS, _job_bucket
    from trivy_tpu.detect.metrics import DETECT_METRICS
    from trivy_tpu.ops.program import compiled_programs
    from trivy_tpu.runtime import BatchScanRunner
    runner = BatchScanRunner(store=cdb, backend="cpu")
    rows = []
    for wanted, first in ((first_rows, 0), (second_rows, 5000)):
        before = DETECT_METRICS.snapshot()
        results = runner.scan_boms(_pass_of(wanted, first))
        assert all(r.status == "ok" for r in results)
        after = DETECT_METRICS.snapshot()
        sent = after["device_rows"] - before["device_rows"]
        waves = after["device_waves"] - before["device_waves"]
        assert sent == after["jobs_unique"] - before["jobs_unique"]
        assert waves == -(-sent // _WAVE_ROWS) > 1
        rows.append(sent)
        if len(rows) == 1:
            programs = set(compiled_programs())
    # the two passes would have taken two rungs of the old ladder
    assert _job_bucket(rows[0]) != _job_bucket(rows[1]), rows
    assert set(compiled_programs()) == programs
    assert any(f"int32[{_WAVE_ROWS}]" in p and
               p.startswith("interval_hits_resident") for p in programs)


@pytest.mark.parametrize("rows", [1, 63, 64, 65, 4095, 4096, 4097,
                                  8192, 8193, 57000])
def test_resident_waves_cover_every_row_once(rows):
    from trivy_tpu.detect.batch import (_WAVE_ROWS, _job_bucket,
                                        _resident_waves)
    waves = _resident_waves(rows)
    assert [a for a, _, _ in waves] == \
        [k * _WAVE_ROWS for k in range(len(waves))]
    assert sum(n for _, n, _ in waves) == rows
    assert all(0 < n <= padded for _, n, padded in waves)
    if rows <= _WAVE_ROWS:
        assert waves == [(0, rows, _job_bucket(rows))]
    else:
        assert {padded for _, _, padded in waves} == {_WAVE_ROWS}


# ---------------------------------------------------------------------
# (c) a malformed document fails its own slot
# ---------------------------------------------------------------------

@pytest.mark.parametrize("bad", [
    b"not a document at all",
    b'{"bomFormat": "CycloneDX", "components": "not a list"}',
    b"",
])
def test_a_malformed_document_fails_its_own_slot(bad, table, docs, cdb):
    from trivy_tpu.runtime import BatchScanRunner
    some = [(n, d) for n, d, _ in docs[:30]]
    boms = some[:10] + [("bad.cdx.json", bad)] + some[10:]
    results = BatchScanRunner(store=cdb, backend="cpu").scan_boms(boms)
    assert [r.name for r in results] == [n for n, _ in boms]
    assert results[10].status == "failed" and results[10].error
    good = results[:10] + results[11:]
    for (name, _, facts), res in zip(docs[:30], good):
        assert res.status == "ok", name
        assert found(res.report) == \
            expected(table, facts), name


def test_scan_boms_decodes_on_the_calling_thread(docs, cdb):
    """One ``decode_task`` a call, on the caller's thread, and no
    task handed to the host pool, however many the documents."""
    import threading
    from trivy_tpu.artifact import sbom as artifact_sbom
    from trivy_tpu.detect.metrics import DETECT_METRICS
    from trivy_tpu.obs.trace import phase_rows
    from trivy_tpu.runtime import BatchScanRunner
    runner = BatchScanRunner(store=cdb, backend="cpu")
    seen = set()
    real = artifact_sbom.decode_to_blob

    def watched(data):
        seen.add(threading.current_thread().name)
        return real(data)

    artifact_sbom.decode_to_blob = watched
    try:
        tasks = DETECT_METRICS.snapshot()["pack_tasks"]
        spans = phase_rows("detect").get("decode_task", {}).get("n", 0)
        results = runner.scan_boms([(n, d) for n, d, _ in docs])
    finally:
        artifact_sbom.decode_to_blob = real
    assert len(docs) > 64 and all(r.status == "ok" for r in results)
    assert seen == {threading.current_thread().name}
    assert DETECT_METRICS.snapshot()["pack_tasks"] == tasks
    assert phase_rows("detect")["decode_task"]["n"] == spans + 1


def test_command_reports_the_rest_and_exits_1(table, docs, doc_dir,
                                              cdb_path, tmp_path):
    broken = tmp_path / "broken.cdx.json"
    broken.write_text("{ not json")
    gone = tmp_path / "unreadable.cdx.json"
    gone.mkdir()                    # open() fails: a slot of its own
    paths = [os.path.join(doc_dir, docs[k][0]) for k in range(4)]
    code, out, err = cli("sbom", paths[0], paths[1], str(broken),
                         str(gone) + os.sep + "..", paths[2],
                         "--format", "json",
                         *scan_flags(cdb_path, tmp_path))
    # the directory form of the fourth target walks tmp_path: the
    # broken document again, and nothing else ends in .json there
    assert code == 1
    assert err.count("broken.cdx.json") == 2
    reports = json.loads(out)
    assert [r["ArtifactName"] for r in reports] == paths[:3]
    for report, (_, _, facts) in zip(reports, docs[:3]):
        assert reported(report) == expected(table, facts)


# ---------------------------------------------------------------------
# (d) a memo asked more keys than it holds
# ---------------------------------------------------------------------

@pytest.mark.parametrize("maxsize,keys,rounds", [
    (8, 100, 3), (64, 1000, 2), (1024, 3000, 2), (64, 40, 5),
    (64, 30, 5)])
def test_keyed_memo_past_its_size_answers_right_and_counts_turns(
        maxsize, keys, rounds):
    from trivy_tpu.detect.ccache import KeyedMemo
    from trivy_tpu.detect.metrics import DETECT_METRICS
    memo = KeyedMemo(maxsize, "purl_cache_hits", "purl_cache_misses",
                     "purl_cache_turns")
    before = DETECT_METRICS.snapshot()
    stores = 0

    def factory(key):
        nonlocal stores
        stores += 1
        return ("parsed", key)

    for _ in range(rounds):
        for k in range(keys):
            assert memo.lookup(f"pkg:npm/lib-{k}@1.0.0", factory) == \
                ("parsed", f"pkg:npm/lib-{k}@1.0.0")
            assert len(memo) <= maxsize
    after = DETECT_METRICS.snapshot()
    turns = after["purl_cache_turns"] - before["purl_cache_turns"]
    misses = after["purl_cache_misses"] - before["purl_cache_misses"]
    hits = after["purl_cache_hits"] - before["purl_cache_hits"]
    assert misses == stores and hits + misses == rounds * keys
    if keys > maxsize:
        # every key is gone again before it is asked again
        assert hits == 0
        assert turns == (rounds * keys) // (maxsize // 2)
    elif keys > maxsize // 2:
        # a generation takes misses and carried hits alike, so what
        # is sure to stay is half the size: some keys come back as
        # hits, some were let go in a turn
        assert hits > 0 and misses > keys and turns >= rounds
    else:
        assert misses == keys and turns == 0
    assert after["constraint_cache_turns"] == \
        before["constraint_cache_turns"]


def test_purl_memo_and_constraint_memo_name_their_turn_counters():
    from trivy_tpu import purl
    from trivy_tpu.detect.ccache import INTERVAL_CACHE
    from trivy_tpu.detect.metrics import DETECT_METRICS
    assert purl._parse_cache()._turn == "purl_cache_turns"
    assert INTERVAL_CACHE._turn == "constraint_cache_turns"
    snap = DETECT_METRICS.snapshot()
    for key in ("purl_cache_turns", "constraint_cache_turns",
                "sbom_docs", "sbom_components"):
        assert key in snap
