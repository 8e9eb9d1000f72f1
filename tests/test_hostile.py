"""Hostile-artifact suite (docs/robustness.md "Untrusted input &
resource budgets"; ``pytest -m hostile``).

Scans the adversarial corpus (trivy_tpu/faults/hostile.py) through
both runner paths and asserts the guard contract: every artifact
completes — no crash, no hang past its ingest deadline — in exactly
one of ok/degraded/failed with a machine-readable ``ingest``-stage
FailureCause, while clean images stay byte-identical to a guardless
run. Plus unit coverage for the budget/safetar primitives, the
walker's path hygiene, the registry retry policy, the atomic DB
install, and the server admission caps — and a seeded property test
that random malformed tars never raise past the artifact boundary.
"""

import dataclasses
import io
import json
import random
import tarfile
import threading
import time
import tracemalloc
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from tests.test_sched import _norm, make_fleet, make_store
from trivy_tpu.artifact.artifact import ArtifactOption
from trivy_tpu.artifact.walker import collect_layer_tar
from trivy_tpu.faults.hostile import (EXPECTED_STATUS, build_corpus,
                                      corrupt_boltdb_layout,
                                      hostile_limits)
from trivy_tpu.guard import (GUARD_METRICS, IngestDeadlineExceeded,
                             MalformedArchiveError, ResourceBudget,
                             ResourceBudgetExceeded, ResourceLimits,
                             decompress_bounded, make_budget,
                             open_layer_bytes)
from trivy_tpu.guard.safetar import LayerTar
from trivy_tpu.runtime import BatchScanRunner
from trivy_tpu.types import ScanOptions

pytestmark = pytest.mark.hostile

SCALE = 0.05


def _scan(paths, limits, sched="off", guards=True):
    opt = ArtifactOption(ingest_guards=guards, ingest_limits=limits)
    runner = BatchScanRunner(store=make_store(), backend="cpu-ref",
                             sched=sched, artifact_option=opt)
    try:
        return runner.scan_paths(
            list(paths), ScanOptions(backend="cpu-ref"))
    finally:
        runner.close()


# ---------------------------------------------------------------
# the corpus end-to-end: every artifact quarantined per-target
# ---------------------------------------------------------------

class TestCorpusQuarantine:
    @pytest.mark.parametrize("sched", ["off", "on"])
    def test_every_artifact_ends_typed(self, hostile_corpus,
                                       tmp_path, sched):
        corpus, limits = hostile_corpus(scale=SCALE)
        limits = dataclasses.replace(limits, ingest_deadline_s=30.0)
        clean = make_fleet(tmp_path, 2)
        t0 = time.monotonic()
        results = _scan(clean + [p for _, p in corpus], limits,
                        sched=sched)
        wall = time.monotonic() - t0
        assert wall < 120, f"corpus scan took {wall:.0f}s"

        clean_res, hostile_res = results[:2], results[2:]
        for r in clean_res:
            assert r.status == "ok" and not r.error
        for (name, _), r in zip(corpus, hostile_res):
            assert r.status == EXPECTED_STATUS[name], \
                f"{name}: {r.status} ({r.error})"
            stages = {c.stage for c in r.causes}
            assert "ingest" in stages, f"{name}: causes {r.causes}"
            kinds = {c.kind for c in r.causes}
            assert kinds & {"resource-budget", "malformed-archive"}

    def test_clean_slots_byte_identical_with_guards(
            self, hostile_corpus, tmp_path):
        corpus, limits = hostile_corpus(scale=SCALE)
        clean = make_fleet(tmp_path, 4)
        guarded = _scan(clean, limits, guards=True)
        unguarded = _scan(clean, limits, guards=False)
        assert _norm(guarded) == _norm(unguarded)
        mixed = _scan(clean + [p for _, p in corpus], limits)
        assert _norm(mixed[:4]) == _norm(unguarded)

    def test_degraded_slot_report_carries_status(
            self, hostile_corpus):
        corpus, limits = hostile_corpus(scale=SCALE,
                                        only=["corrupt-rpmdb"])
        (res,) = _scan([corpus[0][1]], limits)
        assert res.status == "degraded"
        assert res.report is not None
        doc = res.report.to_dict()
        assert doc["Status"] == "degraded"
        assert doc["FailureCauses"][0]["Stage"] == "ingest"

    def test_unknown_builder_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="unknown hostile"):
            build_corpus(str(tmp_path), only=["no-such-attack"])

    def test_corpus_deterministic_per_seed(self, tmp_path):
        a = build_corpus(str(tmp_path / "a"), seed=11, scale=0.02)
        b = build_corpus(str(tmp_path / "b"), seed=11, scale=0.02)
        for (_, pa), (_, pb) in zip(a, b):
            assert open(pa, "rb").read() == open(pb, "rb").read()


# ---------------------------------------------------------------
# property test: random mutations never escape the artifact boundary
# ---------------------------------------------------------------

class TestMalformedNeverEscapes:
    def test_random_mutants_end_typed(self, tmp_path):
        base = open(make_fleet(tmp_path, 1)[0],
                    "rb").read()
        rng = random.Random(20260804)
        limits = hostile_limits(SCALE)
        paths = []
        for i in range(24):
            data = bytearray(base)
            op = rng.randrange(3)
            if op == 0:                      # truncate
                data = data[:rng.randrange(1, len(data))]
            elif op == 1:                    # flip a byte run
                off = rng.randrange(len(data))
                n = min(len(data) - off, rng.randrange(1, 512))
                for j in range(off, off + n):
                    data[j] ^= 0xFF
            else:                            # splice garbage
                off = rng.randrange(len(data))
                data[off:off] = rng.randbytes(rng.randrange(1, 2048))
            p = tmp_path / f"mutant{i}.tar"
            p.write_bytes(bytes(data))
            paths.append(str(p))
        # must return one result per slot — never raise
        results = _scan(paths, limits)
        assert len(results) == len(paths)
        for r in results:
            assert r.status in ("ok", "degraded", "failed")
            if r.status == "failed":
                assert r.causes, f"untyped failure: {r.error}"


# ---------------------------------------------------------------
# OCI digest strings must never become path escapes, and the
# resolve chain must carry the budget (review findings)
# ---------------------------------------------------------------

def _oci_dir(tmp_path, digest_override=None, layer_bytes=None):
    import gzip
    import hashlib
    import os
    root = str(tmp_path / "layout")
    os.makedirs(os.path.join(root, "blobs", "sha256"))

    def put(data):
        h = hashlib.sha256(data).hexdigest()
        open(os.path.join(root, "blobs", "sha256", h),
             "wb").write(data)
        return "sha256:" + h

    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tf:
        ti = tarfile.TarInfo("etc/alpine-release")
        ti.size = 7
        tf.addfile(ti, io.BytesIO(b"3.16.2\n"))
    layer = layer_bytes if layer_bytes is not None \
        else gzip.compress(buf.getvalue(), mtime=0)
    diff = "sha256:" + hashlib.sha256(buf.getvalue()).hexdigest()
    ldig = put(layer)
    cfg = json.dumps({"architecture": "amd64", "os": "linux",
                      "rootfs": {"type": "layers",
                                 "diff_ids": [diff]},
                      "config": {}}).encode()
    cdig = digest_override or put(cfg)
    man = json.dumps({"schemaVersion": 2,
                      "config": {"digest": cdig},
                      "layers": [{"digest": ldig}]}).encode()
    mdig = put(man)
    json.dump({"schemaVersion": 2, "manifests": [{"digest": mdig}]},
              open(str(tmp_path / "layout" / "index.json"), "w"))
    return root


class TestDigestHygiene:
    def test_traversal_digest_never_reads_outside_layout(
            self, tmp_path):
        from trivy_tpu.artifact.image import load_image
        (tmp_path / "secret.json").write_text(
            '{"stolen": true, "rootfs": {"diff_ids": []}}')
        root = _oci_dir(
            tmp_path,
            digest_override="sha256:../../secret.json")
        for budget in (ResourceBudget(), None):
            with pytest.raises(ValueError, match="digest"):
                load_image(root, budget=budget)

    def test_db_layout_traversal_digest_rejected(self, tmp_path):
        from trivy_tpu.db.lifecycle import read_oci_layout
        layout = str(tmp_path / "db-layout")
        import os
        os.makedirs(layout)
        json.dump({"schemaVersion": 2, "manifests": [
            {"digest": "sha256:../../../../etc/passwd"}]},
            open(os.path.join(layout, "index.json"), "w"))
        with pytest.raises(ValueError, match="digest"):
            read_oci_layout(layout)

    def test_resolve_path_carries_budget(self, tmp_path):
        import gzip
        from trivy_tpu.artifact.resolve import resolve_image
        # a 2 MB bomb layer in an OCI dir loaded through the
        # RESOLVE chain (not --input) must still trip the budget
        root = _oci_dir(
            tmp_path,
            layer_bytes=gzip.compress(b"\0" * (2 << 20), mtime=0))
        lim = ResourceLimits(max_decompressed_bytes=128 << 10,
                             max_compression_ratio=1e9)
        src = resolve_image(root, budget=ResourceBudget(lim))
        with pytest.raises(ResourceBudgetExceeded):
            src.layers[0].open()


# ---------------------------------------------------------------
# walker path hygiene (satellite: artifact/walker.py)
# ---------------------------------------------------------------

def _walk(names, budget=None, sizes=None):
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tf:
        for i, name in enumerate(names):
            ti = tarfile.TarInfo(name)
            ti.size = (sizes or {}).get(name, 0)
            tf.addfile(ti, io.BytesIO(b"x" * ti.size))
    with LayerTar(buf.getvalue()) as tf:
        return collect_layer_tar(tf, budget=budget)


class TestWalkerPaths:
    def test_benign_names_normalized(self):
        files, opq, wh = _walk(["./.env", "./app/x", "/abs/file",
                                "plain.txt", "a/./b"])
        paths = [p for p, _, _ in files]
        assert paths == [".env", "app/x", "abs/file", "plain.txt",
                         "a/b"]

    def test_traversal_skipped_unguarded(self):
        before = GUARD_METRICS.snapshot()["traversal_rejected"]
        files, _, _ = _walk(["../../etc/passwd", "a/../../b",
                             "ok.txt"])
        assert [p for p, _, _ in files] == ["ok.txt"]
        assert GUARD_METRICS.snapshot()["traversal_rejected"] \
            >= before + 2

    def test_traversal_trips_guarded(self):
        with pytest.raises(MalformedArchiveError, match="traversal"):
            _walk(["../../etc/passwd"], budget=ResourceBudget())

    def test_inner_dotdot_normalizes_in_bounds(self):
        # a/b/../c cleans to a/c — in bounds, kept
        files, _, _ = _walk(["a/b/../c"])
        assert [p for p, _, _ in files] == ["a/c"]

    def test_whiteout_traversal_rejected(self):
        files, opq, wh = _walk(["app/.wh.x", "app/.wh...",
                                "dir/.wh..wh..opq"])
        assert wh == ["app/x"]
        assert opq == ["dir"]
        with pytest.raises(MalformedArchiveError, match="whiteout"):
            _walk(["app/.wh..."], budget=ResourceBudget())

    def test_one_char_component_depth_attack_still_trips(self):
        # 1-char dirs defeat any "deep paths are long" shortcut —
        # the length gate must be conservative enough to catch them
        lim = ResourceLimits(max_depth=16)
        deep = "/".join("a" * 1 for _ in range(40)) + "/f"
        with pytest.raises(ResourceBudgetExceeded, match="deeper"):
            _walk([deep], budget=ResourceBudget(lim))

    def test_entry_flood_trips_in_batches(self):
        lim = ResourceLimits(max_files=100)
        with pytest.raises(ResourceBudgetExceeded,
                           match="entry count"):
            _walk([f"f{i}" for i in range(200)],
                  budget=ResourceBudget(lim))

    def test_header_flood_is_parsed_no_further_than_the_budget(self):
        # twenty times max_files empty members in one layer: the
        # index reads one member past the entries the budget has
        # left and hands the layer to tarfile, which trips where it
        # always tripped; the walk's peak stays a fraction of the
        # layer, where one parse of every header would cost more
        # than the layer itself
        import trivy_tpu.obs.trace  # noqa: F401 — the trip imports it
        lim = ResourceLimits(max_files=500)
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w") as tf:
            tf.addfile(tarfile.TarInfo("f"))
        flood = buf.getvalue()[:512] * (20 * lim.max_files) + \
            bytes(1024)
        budget = ResourceBudget(lim)
        tracemalloc.start()
        try:
            with pytest.raises(ResourceBudgetExceeded,
                               match="entry count"):
                with open_layer_bytes(flood, budget) as tf:
                    try:
                        collect_layer_tar(tf, budget=budget)
                    finally:
                        assert not tf.indexed
            _, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        assert budget.entries <= lim.max_files + 32
        assert peak < len(flood) // 4

    def test_oversize_member_trips(self):
        lim = ResourceLimits(max_file_bytes=10)
        with pytest.raises(ResourceBudgetExceeded,
                           match="per-file"):
            _walk(["big.bin"], budget=ResourceBudget(lim),
                  sizes={"big.bin": 100})


# ---------------------------------------------------------------
# budget / safetar primitives
# ---------------------------------------------------------------

class TestBudgetPrimitives:
    def test_ratio_tripwire_before_absolute_cap(self):
        import gzip
        lim = ResourceLimits(max_decompressed_bytes=1 << 30,
                             max_compression_ratio=100.0,
                             ratio_min_bytes=1 << 16)
        bomb = gzip.compress(b"\0" * (8 << 20))
        b = ResourceBudget(lim)
        with pytest.raises(ResourceBudgetExceeded, match="ratio"):
            decompress_bounded(bomb, b)
        assert b.decompressed < (8 << 20)    # never materialized

    def test_absolute_byte_cap(self):
        lim = ResourceLimits(max_decompressed_bytes=1000,
                             max_compression_ratio=1e9)
        with pytest.raises(ResourceBudgetExceeded, match="budget"):
            open_layer_bytes(b"A" * 2000, ResourceBudget(lim))

    def test_truncated_gzip_is_malformed(self):
        import gzip
        whole = gzip.compress(b"payload" * 1000)
        with pytest.raises(MalformedArchiveError):
            decompress_bounded(whole[:len(whole) // 2],
                               ResourceBudget())

    def test_garbage_layer_is_malformed(self):
        with pytest.raises(MalformedArchiveError):
            open_layer_bytes(b"not a tar at all" * 100,
                             ResourceBudget())

    def test_deadline_trips(self):
        lim = ResourceLimits(ingest_deadline_s=0.001)
        b = ResourceBudget(lim)
        time.sleep(0.01)
        with pytest.raises(IngestDeadlineExceeded):
            b.check_deadline()
        # IngestDeadlineExceeded is a resource-budget trip
        assert issubclass(IngestDeadlineExceeded,
                          ResourceBudgetExceeded)

    def test_make_budget_disabled(self):
        assert make_budget(None, enabled=False) is None
        assert make_budget(None, enabled=True) is not None

    def test_guard_metrics_in_scheduler_stats(self):
        from trivy_tpu.sched import ScanScheduler
        s = ScanScheduler()
        try:
            snap = s.stats()
        finally:
            s.close()
        assert "budget_trips" in snap["guard"]

    def test_trips_are_value_errors(self):
        # every trip must be catchable by the existing per-slot
        # (OSError, ValueError) load-error handling
        assert issubclass(MalformedArchiveError, ValueError)
        assert issubclass(ResourceBudgetExceeded, ValueError)


# ---------------------------------------------------------------
# corrupt rpmdb: soft fault, and hardened openers never loop/crash
# ---------------------------------------------------------------

class TestRpmdbHardening:
    def test_cyclic_bdb_overflow_chain_raises(self):
        import struct
        from trivy_tpu.rpmdb import list_packages
        data = bytearray(3 * 4096)
        struct.pack_into("<I", data, 12, 0x061561)
        struct.pack_into("<I", data, 20, 4096)       # page size
        struct.pack_into("<I", data, 32, 2)          # last_pgno
        # page 1: hash page with one H_OFFPAGE entry → page 2
        off = 4096
        data[off + 25] = 2                           # hash page
        struct.pack_into("<H", data, off + 20, 2)    # entries
        struct.pack_into("<H", data, off + 26, 100)  # key offset
        struct.pack_into("<H", data, off + 28, 60)   # val offset
        data[off + 100] = 1                          # key: inline
        data[off + 60] = 3                           # val: offpage
        struct.pack_into("<I", data, off + 64, 2)    # → page 2
        struct.pack_into("<I", data, off + 68, 4096) # total len
        # page 2: overflow pointing at ITSELF (the cycle)
        off = 2 * 4096
        data[off + 25] = 7
        struct.pack_into("<I", data, off + 16, 2)    # next = self
        struct.pack_into("<H", data, off + 22, 16)
        t0 = time.monotonic()
        with pytest.raises(ValueError):
            list_packages(bytes(data))
        assert time.monotonic() - t0 < 5.0           # no spin

    def test_corrupt_rpmdb_soft_fault_degrades(self, hostile_corpus):
        corpus, limits = hostile_corpus(scale=SCALE,
                                        only=["corrupt-rpmdb"])
        (res,) = _scan([corpus[0][1]], limits)
        assert res.status == "degraded"
        assert any(c.kind == "malformed-archive" for c in res.causes)


# ---------------------------------------------------------------
# atomic DB install (satellite: db/lifecycle.py)
# ---------------------------------------------------------------

class TestAtomicDBInstall:
    def _good_layout(self, tmp_path):
        import datetime
        from trivy_tpu.db.boltwriter import write_trivy_db
        from trivy_tpu.db.lifecycle import (Metadata, SCHEMA_VERSION,
                                            pack_db_archive,
                                            write_oci_layout)
        bolt = str(tmp_path / "src.db")
        write_trivy_db(bolt, {"alpine 3.16": {"musl": {
            "CVE-1": {"FixedVersion": "1.2.3-r1"}}}},
            {"CVE-1": {"Severity": "HIGH"}})
        meta = Metadata(
            version=SCHEMA_VERSION,
            next_update=datetime.datetime(
                2030, 1, 1, tzinfo=datetime.timezone.utc))
        layout = str(tmp_path / "good-layout")
        write_oci_layout(layout,
                         pack_db_archive(open(bolt, "rb").read(),
                                         meta))
        return layout

    def test_corrupt_download_rolls_back(self, tmp_path):
        import os
        from trivy_tpu.db.lifecycle import (db_dir, load_metadata,
                                            update_from_oci_layout)
        cache = str(tmp_path / "cache")
        update_from_oci_layout(self._good_layout(tmp_path), cache)
        before_db = open(os.path.join(db_dir(cache), "trivy.db"),
                         "rb").read()
        before_meta = load_metadata(cache)

        bad = corrupt_boltdb_layout(str(tmp_path / "bad-layout"))
        with pytest.raises(ValueError):
            update_from_oci_layout(bad, cache)

        # previous install still serving, byte-identical
        after_db = open(os.path.join(db_dir(cache), "trivy.db"),
                        "rb").read()
        assert after_db == before_db
        after_meta = load_metadata(cache)
        assert after_meta.next_update == before_meta.next_update
        from trivy_tpu.db.boltdb import load_trivy_db
        _, n, _ = load_trivy_db(
            os.path.join(db_dir(cache), "trivy.db"))
        assert n == 1
        # and no half-written temp dirs left behind
        assert not [d for d in os.listdir(cache)
                    if d.startswith(".db-install-")]

    def test_tampered_layer_digest_rejected(self, tmp_path):
        import os
        from trivy_tpu.db.lifecycle import (read_oci_layout,
                                            update_from_oci_layout)
        layout = self._good_layout(tmp_path)
        idx = json.load(open(os.path.join(layout, "index.json")))
        mdigest = idx["manifests"][0]["digest"].split(":")[1]
        manifest = json.load(open(os.path.join(
            layout, "blobs", "sha256", mdigest)))
        layer_hex = manifest["layers"][0]["digest"].split(":")[1]
        blob_path = os.path.join(layout, "blobs", "sha256",
                                 layer_hex)
        blob = bytearray(open(blob_path, "rb").read())
        blob[len(blob) // 2] ^= 0xFF
        open(blob_path, "wb").write(bytes(blob))
        with pytest.raises(ValueError, match="digest mismatch"):
            read_oci_layout(layout)
        with pytest.raises(ValueError, match="digest mismatch"):
            update_from_oci_layout(layout,
                                   str(tmp_path / "cache2"))


# ---------------------------------------------------------------
# server admission caps
# ---------------------------------------------------------------

class TestServerAdmission:
    def test_oversized_scan_blob_list_answers_413(self):
        from trivy_tpu.rpc.server import RequestTooLarge, ScanServer
        server = ScanServer(max_scan_blobs=4)
        with pytest.raises(RequestTooLarge):
            server.scan({"target": "t", "artifact_id": "a",
                         "blob_ids": [f"sha256:{i}" for i in
                                      range(10)]})

    def test_oversized_body_answers_413_before_read(self):
        import urllib.request
        from trivy_tpu.rpc.server import ScanServer, serve
        server = ScanServer(max_body_bytes=1024)
        httpd, _ = serve(port=0, server=server)
        try:
            port = httpd.server_address[1]
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/twirp/"
                f"trivy.scanner.v1.Scanner/Scan",
                data=b"x" * 4096,
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(req, timeout=10)
            assert exc.value.code == 413
            body = json.loads(exc.value.read())
            assert body["code"] == "payload_too_large"
        finally:
            httpd.shutdown()

    def test_metrics_report_guard_and_admission(self):
        from trivy_tpu.rpc.server import ScanServer
        out = ScanServer().metrics()
        assert "budget_trips" in out["guard"]
        assert out["admission"]["max_body_bytes"] > 0


# ---------------------------------------------------------------
# registry retry policy (satellite: artifact/registry.py)
# ---------------------------------------------------------------

class _FlakyServer:
    """Answers N transient errors (with Retry-After) then 200."""

    def __init__(self, fail_times: int, status: int = 503):
        self.requests = []
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                outer.requests.append(self.path)
                if len(outer.requests) <= fail_times:
                    self.send_response(status)
                    self.send_header("Retry-After", "0")
                    self.send_header("Content-Length", "0")
                    self.end_headers()
                    return
                body = b'{"ok": true}'
                self.send_response(200)
                self.send_header("Content-Length",
                                 str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()
        self.registry = f"127.0.0.1:{self.httpd.server_address[1]}"

    def close(self):
        self.httpd.shutdown()


class TestRegistryRetries:
    def test_transient_5xx_retried_until_success(self):
        from trivy_tpu.artifact.registry import DistributionClient
        srv = _FlakyServer(fail_times=2)
        try:
            client = DistributionClient(retries=3, backoff_s=0.01)
            _, body = client._get(srv.registry, "/v2/r/manifests/t",
                                  accept="*/*")
            assert json.loads(body)["ok"] is True
            assert len(srv.requests) == 3
        finally:
            srv.close()

    def test_retries_exhausted_fails_typed(self):
        from trivy_tpu.artifact.registry import (DistributionClient,
                                                 RegistryError)
        srv = _FlakyServer(fail_times=99)
        try:
            client = DistributionClient(retries=2, backoff_s=0.01)
            with pytest.raises(RegistryError, match="503"):
                client._get(srv.registry, "/v2/r/manifests/t")
            assert len(srv.requests) == 3     # 1 try + 2 retries
        finally:
            srv.close()

    def test_authoritative_4xx_fails_fast(self):
        from trivy_tpu.artifact.registry import (DistributionClient,
                                                 RegistryError)
        srv = _FlakyServer(fail_times=99, status=404)
        try:
            client = DistributionClient(retries=3, backoff_s=0.01)
            with pytest.raises(RegistryError, match="404"):
                client._get(srv.registry, "/v2/r/manifests/t")
            assert len(srv.requests) == 1     # no retry on 404
        finally:
            srv.close()
