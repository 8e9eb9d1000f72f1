"""Perf smoke set (``pytest -m perf``, tier-1): fast CPU-sim checks
that the hot dispatch path keeps its shape — job dedup folds
duplicates, bulk segment packing is byte-identical to the naive
packer, pair rows pad to the bucket ladder, the constraint/purl
caches hit, and the balanced shard layout stays sound. A regression
here fails tier-1 at once, before any chip run could show it
(docs/performance.md)."""

import numpy as np
import pytest

pytestmark = pytest.mark.perf


def _mk_jobs(n_dups: int = 5):
    from trivy_tpu.detect.batch import PairJob
    jobs = []
    for i in range(4):
        for d in range(n_dups):
            jobs.append(PairJob(
                grammar="semver", pkg_version=f"1.{i}.0",
                vulnerable=["<1.2.0"], patched=[">=1.2.0"],
                payload=("p", i, d)))
    return jobs


def test_dedup_folds_duplicates_and_fans_out():
    from trivy_tpu.detect.batch import dispatch_jobs
    jobs = _mk_jobs(n_dups=5)
    stats: dict = {}
    hits = dispatch_jobs(jobs, backend="cpu-ref", stats=stats)
    assert stats["jobs_in"] == 20
    assert stats["jobs_unique"] == 4          # 4 distinct versions
    # versions 1.0.0 and 1.1.0 are < 1.2.0 → every duplicate's
    # payload comes back; 1.2.0/1.3.0 are patched
    want = {("p", i, d) for i in (0, 1) for d in range(5)}
    assert set(hits) == want


def test_dedup_matches_naive_host_eval():
    """Seeded random job mix: deduped dispatch == per-job host
    truth, payload multiplicity preserved."""
    from trivy_tpu.detect.batch import (PairJob, _host_eval,
                                        dispatch_jobs)
    rng = np.random.default_rng(20260804)
    jobs = []
    for k in range(200):
        v = (f"{int(rng.integers(0, 3))}."
             f"{int(rng.integers(0, 4))}.{int(rng.integers(0, 4))}")
        fixed = (f"{int(rng.integers(1, 3))}."
                 f"{int(rng.integers(0, 4))}.1")
        jobs.append(PairJob(
            grammar="semver", pkg_version=v,
            vulnerable=[f"<{fixed}"], patched=[f">={fixed}"],
            payload=k))
    got = sorted(dispatch_jobs(jobs, backend="cpu-ref", stats={}))
    want = sorted(k for k, j in enumerate(jobs) if _host_eval(j))
    assert got == want


def test_resident_dedup_matches_full_eval(tmp_path):
    from trivy_tpu.db import AdvisoryStore
    from trivy_tpu.db.compiled import CompiledDB
    from trivy_tpu.detect.batch import ResidentPairJob, dispatch_jobs
    store = AdvisoryStore()
    for i in range(6):
        store.put_advisory("npm::Node.js", f"lib{i}",
                           f"CVE-{i}", {
                               "VulnerableVersions": [f"<1.{i}.0"],
                               "PatchedVersions": [f">=1.{i}.0"]})
    cdb = CompiledDB.compile(store)
    jobs = []
    for rep in range(7):
        for row in range(len(cdb.rows_meta)):
            jobs.append(ResidentPairJob(
                cdb=cdb, row=row, grammar=cdb.row_grammar[row],
                pkg_version="1.2.5", payload=(row, rep)))
    stats: dict = {}
    got = dispatch_jobs(jobs, backend="cpu-ref", stats=stats)
    assert stats["jobs_unique"] == len(cdb.rows_meta)
    assert stats["jobs_in"] == 7 * len(cdb.rows_meta)
    # truth: 1.2.5 < 1.i.0 only for i in {3, 4, 5}
    vuln_rows = {row for row in range(len(cdb.rows_meta))
                 if cdb.host_eval(row, "1.2.5")}
    want = sorted((row, rep) for row in vuln_rows
                  for rep in range(7))
    assert sorted(got) == want


def test_resident_mixed_stores_evaluate_per_store():
    """A job list spanning two CompiledDBs must evaluate each job
    against ITS OWN store — row N means different advisories per
    generation: the dispatcher runs one segment per store."""
    from trivy_tpu.db import AdvisoryStore
    from trivy_tpu.db.compiled import CompiledDB
    from trivy_tpu.detect.batch import ResidentPairJob, dispatch_jobs

    def mk(fixed: str):
        store = AdvisoryStore()
        store.put_advisory("npm::Node.js", "lib", "CVE-X",
                           {"VulnerableVersions": [f"<{fixed}"],
                            "PatchedVersions": [f">={fixed}"]})
        return CompiledDB.compile(store)

    a, b = mk("1.0.0"), mk("9.0.0")      # same row 0, different fix
    jobs = [ResidentPairJob(cdb=db, row=0,
                            grammar=db.row_grammar[0],
                            pkg_version="2.0.0", payload=name)
            for db, name in ((a, "a"), (b, "b"))]
    # 2.0.0: patched in a (<1.0.0 misses), vulnerable in b (<9.0.0)
    assert dispatch_jobs(jobs, backend="cpu-ref", stats={}) == ["b"]
    assert jobs[0].dedup_key() != jobs[1].dedup_key()


def test_job_bucket_ladder():
    from trivy_tpu.detect.batch import _job_bucket
    assert _job_bucket(1) == 64
    assert _job_bucket(64) == 64
    assert _job_bucket(65) == 128
    assert _job_bucket(8192) == 8192
    assert _job_bucket(8193) == 16384
    assert _job_bucket(20000) == 24576


# the pair-row ladder and its warm-up, pinned (PR 38): a finer
# segment ladder, when one comes, is to leave these as they are
JOB_BUCKETS = [(1, 64), (63, 64), (64, 64), (65, 128), (128, 128),
               (129, 256), (1000, 1024), (4096, 4096), (4097, 8192),
               (8191, 8192), (8192, 8192), (8193, 16384),
               (9000, 16384), (10240, 16384), (12288, 16384),
               (16384, 16384), (16385, 24576), (20000, 24576),
               (24576, 24576), (24577, 32768), (40000, 40960),
               (100000, 106496)]


@pytest.mark.parametrize("n,rung", JOB_BUCKETS)
def test_job_bucket_keeps_its_coarse_steps(n, rung):
    """The interval kernel costs a fraction of a millisecond a rung:
    powers of two from 64 to 8,192, multiples of 8,192 above."""
    from trivy_tpu.detect.batch import _job_bucket
    assert _job_bucket(n) == rung


@pytest.mark.parametrize("jobs,rungs", [
    (32768, (64, 128, 256, 512, 1024, 2048, 4096)),
    (100000, (64, 128, 256, 512, 1024, 2048, 4096)),
    (1000, (64, 128, 256, 512, 1024)),
    (64, (64,)),
])
def test_interval_rungs_are_what_they_were(jobs, rungs):
    from trivy_tpu.runtime.aot import interval_rungs
    from trivy_tpu.sched import SchedConfig
    assert SchedConfig().max_batch_jobs == 32768
    assert interval_rungs(jobs) == rungs


def _naive_segment(scanner, files):
    """The pre-bulk packer, kept as the reference implementation."""
    seg_file, seg_pos, chunks = [], [], []
    step = scanner.seg_len - scanner.overlap
    for idx, content in files:
        n = len(content)
        if n == 0:
            continue
        pos = 0
        while True:
            chunks.append(content[pos:pos + scanner.seg_len])
            seg_file.append(idx)
            seg_pos.append(pos)
            if pos + scanner.seg_len >= n:
                break
            pos += step
    buf = np.zeros((len(chunks), scanner.seg_len), np.uint8)
    for i, c in enumerate(chunks):
        buf[i, :len(c)] = np.frombuffer(c, np.uint8)
    return buf, seg_file, seg_pos


def test_bulk_segment_packing_matches_naive():
    from trivy_tpu.secret.batch import BatchSecretScanner, _FileEntry
    s = BatchSecretScanner(backend="cpu-ref")
    rng = np.random.default_rng(7)
    sizes = [0, 1, 100, s.seg_len - 1, s.seg_len, s.seg_len + 1,
             3 * s.seg_len + 17, 10 * s.seg_len]
    files = [(i, rng.integers(32, 127, n).astype(np.uint8)
              .tobytes()) for i, n in enumerate(sizes)]
    entries = [_FileEntry(path=f"f{i}", content=c, index=i)
               for i, c in files]
    buf, seg_file, seg_pos, occ = s._segment(entries)
    nbuf, nseg_file, nseg_pos = _naive_segment(s, files)
    assert seg_file == nseg_file and seg_pos == nseg_pos
    np.testing.assert_array_equal(buf, nbuf)
    assert occ == []                    # no mesh → no shard layout


def test_balanced_shard_layout_sound(mesh8):
    """Mesh layout: every file's segments land contiguously inside
    one shard block, pad rows are marked -1 and zero-filled, and
    the per-shard occupancy reflects the LPT balance. The sieve
    shards over every device of the mesh, flat (the DFA table is
    replicated per chip, so the data axis gets all the parallelism)
    — PROVIDED the batch is big enough to give each shard a full
    ≥64-row block; the corpus below is."""
    from trivy_tpu.secret.batch import BatchSecretScanner, _FileEntry
    s = BatchSecretScanner(backend="cpu-ref", mesh=mesh8)
    d = int(mesh8.devices.size)
    rng = np.random.default_rng(11)
    # one fat file + many mid-size ones — the case contiguous layout
    # serializes (≈ 40 + 30×10 segments → 8 shards of ≥ 64 rows)
    sizes = [40 * s.seg_len] + [10 * s.seg_len] * 30
    entries = [_FileEntry(path=f"f{i}",
                          content=rng.integers(
                              32, 127, n).astype(np.uint8).tobytes(),
                          index=i)
               for i, n in enumerate(sizes)]
    buf, seg_file, seg_pos, occ = s._segment(entries)
    assert buf.shape[0] % d == 0
    assert len(occ) == d and max(occ) == 1.0
    rows_per_shard = buf.shape[0] // d
    step = s.seg_len - s.overlap
    # reconstruct every file byte-exactly from its segments
    for e in entries:
        rows = [r for r in range(buf.shape[0])
                if seg_file[r] == e.index]
        assert rows == list(range(rows[0], rows[0] + len(rows)))
        shard = rows[0] // rows_per_shard
        assert (rows[-1]) // rows_per_shard == shard, \
            "file split across shards"
        got = bytearray()
        for k, r in enumerate(rows):
            assert seg_pos[r] == k * step
            take = s.seg_len if k == 0 else s.seg_len - s.overlap
            seg = buf[r].tobytes()
            got += seg[s.overlap:] if k else seg
        assert bytes(got[:len(e.content)]) == e.content
    # pad rows zero-filled and marked
    for r in range(buf.shape[0]):
        if seg_file[r] == -1:
            assert not buf[r].any()


def test_balance_lpt_properties():
    from trivy_tpu.parallel.balance import (balance_by_volume,
                                            shard_occupancy)
    vols = [100, 1, 1, 1, 1, 1, 1, 1]
    assign = balance_by_volume(vols, 4)
    # the fat item sits alone; the small ones spread over the rest
    fat_shard = assign[0]
    assert all(a != fat_shard for a in assign[1:])
    occ = shard_occupancy(vols, assign, 4)
    assert len(occ) == 4 and occ[fat_shard] == 1.0
    # uniform volumes → perfect balance
    occ = shard_occupancy([5] * 8, balance_by_volume([5] * 8, 4), 4)
    assert occ == [1.0] * 4


def test_constraint_interval_cache_hits():
    from trivy_tpu.detect.ccache import ConstraintIntervalCache
    from trivy_tpu.detect.metrics import DETECT_METRICS
    from trivy_tpu.vercmp import get_comparer
    cache = ConstraintIntervalCache(maxsize=4)
    cmp_ = get_comparer("semver")
    before = DETECT_METRICS.snapshot()
    a = cache.intervals("semver", cmp_, "<1.2.0")
    b = cache.intervals("semver", cmp_, "<1.2.0")
    assert a is b and len(a) == 1
    after = DETECT_METRICS.snapshot()
    assert after["interval_cache_hits"] >= \
        before["interval_cache_hits"] + 1
    # errors are cached and re-raised fresh
    with pytest.raises(ValueError):
        cache.intervals("semver", cmp_, ">>nope")
    with pytest.raises(ValueError):
        cache.intervals("semver", cmp_, ">>nope")
    # LRU bound holds
    for i in range(10):
        cache.intervals("semver", cmp_, f"<9.{i}.0")
    assert len(cache) <= 4


def test_purl_cache_isolation():
    """Cache hits must hand out fresh objects — decode mutates the
    result (bom-ref, qualifiers)."""
    from trivy_tpu import purl
    from trivy_tpu.detect.metrics import DETECT_METRICS
    s = "pkg:npm/%40scoped/pkg@1.0.0?arch=amd64"
    before = DETECT_METRICS.snapshot()
    p1 = purl.from_string(s)
    p2 = purl.from_string(s)
    after = DETECT_METRICS.snapshot()
    assert after["purl_cache_hits"] >= before["purl_cache_hits"] + 1
    assert p1 is not p2
    assert p1.to_string() == p2.to_string()
    p1.qualifiers.append(("x", "y"))
    p1.file_path = "mutated"
    p3 = purl.from_string(s)
    assert p3.qualifiers == p2.qualifiers
    assert p3.file_path == ""
    with pytest.raises(ValueError):
        purl.from_string("not-a-purl")
    with pytest.raises(ValueError):          # cached error path
        purl.from_string("not-a-purl")


def test_host_pool_is_one_named_pool_or_none(monkeypatch):
    """``get_host_pool`` builds one pool a process, its threads
    named ``trivy-hostpool`` (the guard ``hostpool-blocking`` and the
    lock witness key on the name), and none where it is switched
    off: the direct path's sieve enqueue then runs inline."""
    import threading

    import trivy_tpu.runtime.hostpool as hp
    monkeypatch.setattr(hp, "_POOL", None)
    monkeypatch.setenv("TRIVY_TPU_HOST_POOL", "0")
    assert hp.get_host_pool() is None
    monkeypatch.setenv("TRIVY_TPU_HOST_POOL", "1")
    pool = hp.get_host_pool()
    try:
        assert pool is not None and hp.get_host_pool() is pool
        name = pool.submit(
            lambda: threading.current_thread().name).result(timeout=30)
        assert name.startswith("trivy-hostpool")
    finally:
        monkeypatch.setattr(hp, "_POOL", None)
        pool.shutdown(wait=False)


def test_mesh_segment_layout_matches_shape_bucket(mesh8):
    """Shard count derives from the batch's PADDED size: the total
    padded rows must equal the 1-device pad bucket at EVERY device
    count (so adding virtual devices can never inflate sieve
    compute — the 2× regression the first sharded-async cut hit),
    shards are ≥64-row blocks, and a small batch simply uses fewer
    shards instead of shattering into padded slivers."""
    from trivy_tpu.ops.keywords import _bucket
    from trivy_tpu.secret.batch import BatchSecretScanner, _FileEntry
    s = BatchSecretScanner(backend="cpu-ref", mesh=mesh8)
    rng = np.random.default_rng(13)

    def layout_for(n_files):
        entries = [_FileEntry(path=f"f{i}",
                              content=rng.integers(
                                  32, 127, 5 * s.seg_len)
                              .astype(np.uint8).tobytes(),
                              index=i)
                   for i in range(n_files)]
        return s._layout(s._metas(entries))

    # small batch (9 files ≈ 54 segs): bucket 256 → 4 shards of 64,
    # NOT 8 shards of padded slivers
    lay = layout_for(9)
    total = sum(1 for f in lay["seg_file"] if f >= 0)
    assert lay["B"] == _bucket(total)
    assert lay["n_shards"] == 4
    assert lay["rows_per_shard"] == 64

    # big batch (60 files ≈ 360 segs): bucket 512 → all 8 shards
    lay = layout_for(60)
    assert lay["n_shards"] == 8
    assert lay["B"] == lay["n_shards"] * lay["rows_per_shard"]
    assert lay["rows_per_shard"] % 64 == 0
    # every file sits inside one shard block
    rps = lay["rows_per_shard"]
    seg_file = lay["seg_file"]
    for idx in set(f for f in seg_file if f >= 0):
        rows = [r for r in range(lay["B"]) if seg_file[r] == idx]
        assert rows == list(range(rows[0], rows[0] + len(rows)))
        assert rows[0] // rps == rows[-1] // rps


@pytest.mark.parametrize("shards", [2, 4, 8])
@pytest.mark.parametrize("total,rung", [
    (4132, 8192), (5121, 8192), (8192, 8192), (8193, 12288),
    (12289, 16384)])
def test_mesh_layout_over_the_cap(shards, total, rung):
    """Every rung above the cap is a multiple of it: a power-of-two
    shard count carves it into whole 64-row blocks, and the padded
    total is the one-device rung (an image of 4,132 rows pads to
    8,192 on a mesh as on one device)."""
    from trivy_tpu.ops.keywords import _bucket
    from trivy_tpu.parallel.mesh import make_mesh
    from trivy_tpu.secret.batch import BatchSecretScanner, _FileEntry
    s = BatchSecretScanner(backend="cpu-ref", mesh=make_mesh(shards))
    step = s.seg_len - s.overlap
    # files of four rows and one that brings the total to the row
    sizes = [s.seg_len + 3 * step] * (total // 4)
    if total % 4:
        sizes.append(s.seg_len + (total % 4 - 1) * step)
    entries = [_FileEntry(path=f"f{i}", content=bytes(n), index=i)
               for i, n in enumerate(sizes)]
    metas = s._metas(entries)
    assert sum(m[2] for m in metas) == total
    lay = s._layout(metas)
    assert _bucket(total) == rung
    assert lay["n_shards"] == shards
    assert lay["B"] == rung == shards * lay["rows_per_shard"]
    assert lay["rows_per_shard"] % 64 == 0
    assert sum(1 for f in lay["seg_file"] if f >= 0) == total


def test_detect_metrics_on_metrics_surface():
    """/metrics carries the dedup + cache counters in both the JSON
    snapshot and the Prometheus text rendering."""
    from trivy_tpu.obs.prom import render_prometheus
    from trivy_tpu.sched.metrics import SchedMetrics
    snap = SchedMetrics().snapshot()
    assert "detect" in snap
    for key in ("jobs_in", "jobs_unique", "dedup_ratio",
                "interval_cache_hit_rate", "purl_cache_hit_rate",
                "db_uploads", "upload_amortization"):
        assert key in snap["detect"], key
    text = render_prometheus(snap)
    assert "trivy_tpu_detect_events_total" in text
    assert "trivy_tpu_detect_dedup_ratio" in text
    assert "trivy_tpu_detect_interval_cache_hit_rate" in text


def test_db_generation_and_invalidation():
    from trivy_tpu.db import AdvisoryStore
    from trivy_tpu.db.compiled import CompiledDB, SwappableStore
    store = AdvisoryStore()
    store.put_advisory("npm::Node.js", "lib", "CVE-1",
                       {"VulnerableVersions": ["<1.0.0"],
                        "PatchedVersions": [">=1.0.0"]})
    a = CompiledDB.compile(store)
    b = CompiledDB.compile(store)
    assert b.generation > a.generation
    a.device_tables()
    a.device_tables()
    st = a.device_stats()
    assert st["uploads"] == 1 and st["dispatches"] == 2
    assert st["amortization"] == 2.0
    holder = SwappableStore(a)
    holder.swap(b)
    assert holder.current() is b
    assert a.device_stats()["invalidations"] == 1
    assert not a._device                 # buffers dropped
    # re-upload after invalidation works (new generation of the
    # same db object is a fresh upload)
    a.device_tables()
    assert a.device_stats()["uploads"] == 2


def test_sched_off_stats_carry_dedup(tmp_path):
    """The direct image path reports per-batch dedup numbers in
    ``last_stats``."""
    from trivy_tpu.runtime import BatchScanRunner
    from trivy_tpu.utils.synth import tiny_fleet
    paths, store = tiny_fleet(str(tmp_path), n_images=2)
    runner = BatchScanRunner(store=store, backend="cpu-ref")
    runner.scan_paths(paths)
    stats = runner.last_stats
    assert "interval_dedup_ratio" in stats
    assert stats["interval_jobs_unique"] <= stats["interval_jobs"]
