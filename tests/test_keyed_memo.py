"""The keyed memo (detect/ccache.KeyedMemo) and the counters it books
(detect/metrics.DetectMetrics): no lock on a lookup or a count,
bounded whatever the interleaving, counted exactly from eight
threads, and the same answers as the factory's. Then the SBOM decode
through the host pool, document for document the one-thread loop's
(docs/performance.md "SBOM decode and the lock convoy")."""

import json
import random
import sys
import threading
from concurrent.futures import ThreadPoolExecutor

import pytest

from trivy_tpu import purl
from trivy_tpu.detect import ccache
from trivy_tpu.detect.ccache import KeyedMemo
from trivy_tpu.detect.metrics import DETECT_METRICS, DetectMetrics

THREADS = 8
LOOKUPS = 50_000
MAXSIZE = 1024
KEYS = 4 * MAXSIZE


def _factory(key: str):
    return ("parsed", key)


def _in_threads(work, n: int = THREADS) -> None:
    """``work(t)`` on ``n`` threads started together, with the
    interpreter switching threads every few bytecodes, so that an
    interleaving a lock would have forbidden does happen."""
    gate = threading.Barrier(n)
    errors: list = []

    def run(t):
        try:
            gate.wait(timeout=30)
            work(t)
        except BaseException as e:     # handed to the test below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(t,))
               for t in range(n)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    assert errors == []


@pytest.fixture(scope="module")
def stress():
    """Eight threads, 50,000 lookups between them over four times
    more keys than the memo may hold, a quarter of the draws from 64
    hot keys (so hits, carries and ageing all happen)."""
    memo = KeyedMemo(MAXSIZE, "purl_cache_hits", "purl_cache_misses")
    sizes = [0] * THREADS
    wrong = [0] * THREADS
    before = DETECT_METRICS.snapshot()

    def work(t):
        rng = random.Random(t)
        for _ in range(LOOKUPS // THREADS):
            k = rng.randrange(64 if rng.random() < 0.25 else KEYS)
            key = f"pkg:npm/lib-{k}@1.0.{k % 7}"
            if memo.lookup(key, _factory) != _factory(key):
                wrong[t] += 1
            sizes[t] = max(sizes[t], len(memo))

    _in_threads(work)
    after = DETECT_METRICS.snapshot()
    return {"largest": max(sizes), "wrong": sum(wrong),
            "final": len(memo),
            **{k: after[k] - before[k]
               for k in ("purl_cache_hits", "purl_cache_misses",
                         "purl_cache_lookups")}}


def test_threaded_memo_never_exceeds_maxsize(stress):
    assert 0 < stress["final"] <= MAXSIZE
    assert stress["largest"] <= MAXSIZE


def test_threaded_hits_plus_misses_is_lookups_exactly(stress):
    assert stress["purl_cache_hits"] + stress["purl_cache_misses"] \
        == LOOKUPS == stress["purl_cache_lookups"]
    assert stress["purl_cache_hits"] > 0
    assert stress["purl_cache_misses"] >= KEYS // 2


def test_threaded_values_equal_the_factorys(stress):
    assert stress["wrong"] == 0


def test_counters_lose_no_add_from_eight_threads():
    m = DetectMetrics()

    def work(t):
        for i in range(5_000):
            m.inc("purl_cache_hits" if i % 2 else "purl_cache_misses")
            m.note_wave(3)

    _in_threads(work)
    snap = m.snapshot()
    assert snap["purl_cache_hits"] == snap["purl_cache_misses"] \
        == THREADS * 2_500
    assert snap["purl_cache_lookups"] == THREADS * 5_000
    assert snap["purl_cache_hit_rate"] == 0.5
    assert snap["device_waves"] == THREADS * 5_000
    assert snap["device_rows"] == THREADS * 15_000
    m.reset()
    assert m.snapshot()["device_rows"] == 0


def test_lookup_counts_surface_on_metrics():
    from trivy_tpu.obs.prom import render_prometheus
    from trivy_tpu.sched.metrics import SchedMetrics
    purl.from_string("pkg:npm/on-the-surface@1.0.0")
    snap = SchedMetrics().snapshot()
    for memo in ("purl_cache", "interval_cache"):
        d = snap["detect"]
        assert d[f"{memo}_lookups"] == \
            d[f"{memo}_hits"] + d[f"{memo}_misses"]
    assert snap["detect"]["purl_cache_lookups"] >= 1
    text = render_prometheus(snap)
    assert 'event="purl_cache_lookups"' in text
    assert 'event="interval_cache_lookups"' in text


class _CountingLock:
    """Stands in for ``threading.Lock`` and ``RLock``: a real lock
    that counts every one made and every acquisition."""

    made = 0
    acquired = 0
    _real = threading.Lock

    def __init__(self):
        type(self).made += 1
        self._lock = self._real()

    def acquire(self, *args, **kwargs):
        type(self).acquired += 1
        return self._lock.acquire(*args, **kwargs)

    def release(self):
        self._lock.release()

    def __enter__(self):
        return self.acquire()

    def __exit__(self, *exc):
        self.release()


def test_lookup_and_its_counting_acquire_no_lock(monkeypatch):
    """Deterministic, no clock: every lock the memo or its counters
    could make is a counting stand-in, and a miss, a hit, a hit
    carried forward, a remembered failure and an ageing between them
    acquire none. (The LRU this replaced took its own lock twice and
    the counters' once on every miss.)"""
    monkeypatch.setattr(_CountingLock, "made", 0)
    monkeypatch.setattr(_CountingLock, "acquired", 0)
    monkeypatch.setattr(threading, "Lock", _CountingLock)
    monkeypatch.setattr(threading, "RLock", _CountingLock)
    metrics = DetectMetrics()
    monkeypatch.setattr(ccache, "DETECT_METRICS", metrics)
    memo = KeyedMemo(8, "purl_cache_hits", "purl_cache_misses")

    def factory(key):
        if key == "bad":
            raise ValueError("no")
        return _factory(key)

    for key in ("a", "a", "b", "c", "d", "a", "e", "f", "g", "a"):
        assert memo.lookup(key, factory) == _factory(key)
    for _ in range(2):
        with pytest.raises(ValueError):
            memo.lookup("bad", factory)
    snap = metrics.snapshot()
    assert (snap["purl_cache_hits"], snap["purl_cache_misses"]) \
        == (4, 8)
    assert _CountingLock.acquired == 0
    assert _CountingLock.made == 0


def test_failure_is_remembered_and_raised_fresh():
    memo = KeyedMemo(8, "purl_cache_hits", "purl_cache_misses")
    calls = []

    def factory(key):
        calls.append(key)
        raise ValueError(f"cannot parse {key}")

    raised = []
    for _ in range(3):
        with pytest.raises(ValueError, match="cannot parse x") as e:
            memo.lookup("x", factory)
        raised.append(e.value)
    assert calls == ["x"]
    assert len({id(e) for e in raised}) == 3
    assert raised[1].__traceback__ is not raised[2].__traceback__


def test_from_string_hands_out_a_fresh_object_each_call():
    s = "pkg:maven/org.example/memo-test@2.1.0?type=jar&classifier=x"
    first = purl.from_string(s)
    want = (first.to_string(), list(first.qualifiers))
    first.qualifiers.append(("mutated", "yes"))
    first.file_path = "mutated"
    first.name = "mutated"
    for _ in range(2):
        again = purl.from_string(s)
        assert again is not first
        assert (again.to_string(), again.qualifiers) == want
        assert again.file_path == ""
        again.qualifiers.clear()


def test_used_key_survives_an_ageing_and_an_idle_one_is_gone():
    memo = KeyedMemo(8, "interval_cache_hits", "interval_cache_misses")
    calls = []

    def factory(key):
        calls.append(key)
        return _factory(key)

    for key in ("k0", "k1", "k2", "k3"):      # the fourth store ages
        memo.lookup(key, factory)
    memo.lookup("k0", factory)                # used: carried forward
    for key in ("k4", "k5", "k6"):            # ages a second time
        memo.lookup(key, factory)
    assert len(memo) <= 8
    calls.clear()
    assert memo.lookup("k0", factory) == _factory("k0")
    assert calls == []                        # survived one ageing
    memo.lookup("k1", factory)
    assert calls == ["k1"]                    # idle for two: gone


def test_memo_refuses_a_size_it_cannot_keep():
    with pytest.raises(ValueError):
        KeyedMemo(1, "purl_cache_hits", "purl_cache_misses")


def _cyclonedx(n: int, rng: random.Random) -> bytes:
    ecosystems = ("pkg:npm/", "pkg:pypi/", "pkg:maven/org.example/",
                  "pkg:golang/github.com/example/")
    comps = []
    for c in range(20):
        k = rng.randrange(300)
        ref = f"{ecosystems[k % 4]}lib-{k}@1.{k % 5}.0"
        if k % 37 == 0:
            ref = f"not-a-purl-{k}"          # skipped, and remembered
        if k % 41 == 0:
            ref += "?arch=amd64&distro=x%20y"
        comps.append({"bom-ref": f"{ref}-{n}-{c}", "type": "library",
                      "name": f"lib-{k}", "version": f"1.{k % 5}.0",
                      "purl": ref})
    return json.dumps({
        "bomFormat": "CycloneDX", "specVersion": "1.4", "version": 1,
        "serialNumber": f"urn:uuid:memo-{n}",
        "metadata": {"component": {"bom-ref": "root",
                                   "type": "container",
                                   "name": f"memo-{n}"}},
        "components": comps}).encode()


def test_pooled_decode_equals_the_one_thread_loop():
    """``decode_to_blob`` over 200 documents on eight threads that
    share the purl memo: blobs and blob ids equal the plain loop's,
    document for document."""
    from trivy_tpu.artifact.sbom import decode_to_blob
    rng = random.Random(28)
    docs = [_cyclonedx(n, rng) for n in range(200)]

    def flat(dec):
        atype, _decoded, blob, blob_id = dec
        return atype, blob_id, blob.to_dict()

    purl._parse_cache().clear()
    alone = [flat(decode_to_blob(d)) for d in docs]
    purl._parse_cache().clear()
    pool = ThreadPoolExecutor(max_workers=THREADS,
                              thread_name_prefix="trivy-hostpool")
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        pooled = list(pool.map(decode_to_blob, docs))
    finally:
        sys.setswitchinterval(interval)
        pool.shutdown(wait=True)
    assert len(pooled) == len(alone) == 200
    for n, (dec, want) in enumerate(zip(pooled, alone)):
        assert flat(dec) == want, n
    assert sum(len(w[2]["Applications"]) for w in alone) > 0
