"""Keyed memo caches for the dispatch hot path.

:class:`KeyedMemo` is the shared machinery: a bounded memo of a
factory per key that takes no lock on a lookup, hit or miss, caches
``ValueError`` failures as a sentinel (re-raised fresh on every hit —
a malformed input repeated across 10k SBOMs should cost one parse
attempt, not 10k), and books hit/miss totals into ``DETECT_METRICS``
under caller-named counters (which take no lock either). No lock,
because the SBOM decode asks it once a component from eight pool
threads, 80,000 times a pass, and one lock there convoys the pass
(docs/performance.md "SBOM decode and the lock convoy").

:data:`INTERVAL_CACHE` memoizes constraint→interval compilation,
which is PURE per (grammar, constraint string) — the resulting
``Interval`` objects carry parsed version keys that every consumer
treats as read-only (rank encoding and bound interning only read
them) — so one process-wide instance serves every dispatcher and
every DB compile. ``purl.from_string`` rides the same class for its
parse memo (that cache copies values out, because decode mutates
its results). Hit rates and lookup counts surface on ``/metrics``
(docs/performance.md).
"""

from __future__ import annotations

import itertools

from .metrics import DETECT_METRICS


class _CachedError:
    __slots__ = ("message",)

    def __init__(self, message: str):
        self.message = message


class KeyedMemo:
    """Bounded, lock-free memo over a per-call factory.

    ``lookup(key, factory)`` returns the cached value (the SAME
    object every hit — callers that mutate results must copy out) or
    runs ``factory(key)`` and caches it. A factory raising
    ``ValueError`` caches the message and every later hit re-raises
    a fresh ``ValueError``.

    Entries age out in two generations of plain dicts: stores go to
    the current one, a hit in the previous one is carried forward,
    and when the current one has taken ``maxsize // 2`` stores it
    becomes the previous one and the old previous one is dropped. So
    a key used since the last ageing survives the next, a key left
    alone for two is gone, and exact LRU order is not kept.

    No lock: it rests on the interpreter lock making each of
    ``dict.get``, ``d[k] = v`` (keys are ``str`` or tuples of
    ``str``: their hash and equality run no bytecode),
    ``next(itertools.count())`` and an attribute store atomic. The
    generations and the current one's ticket counter are read and
    replaced as one tuple; a store first draws a ticket and writes
    only with one below ``maxsize // 2``, so no dict ever takes more
    stores than that however threads interleave, and ``len`` never
    passes ``maxsize``. What a race can cost: two threads that miss
    one key both run the factory (each gets its own value), and a
    store made while the generations turn may be forgotten. Each
    turn is counted under ``turn_counter`` where one is named."""

    def __init__(self, maxsize: int, hit_counter: str,
                 miss_counter: str, turn_counter: str = ""):
        if maxsize < 2:
            raise ValueError("a two-generation memo needs maxsize "
                             f">= 2, not {maxsize}")
        self.maxsize = maxsize
        self._half = maxsize // 2
        self._hit = hit_counter
        self._miss = miss_counter
        self._turn = turn_counter
        self.clear()

    def lookup(self, key, factory):
        cur, _tickets, prev = self._gens
        hit = cur.get(key)
        if hit is None:
            hit = prev.get(key)
            if hit is not None:
                self._put(key, hit)
        if hit is not None:
            DETECT_METRICS.inc(self._hit)
            if isinstance(hit, _CachedError):
                raise ValueError(hit.message)
            return hit
        DETECT_METRICS.inc(self._miss)
        try:
            value = factory(key)
        except ValueError as e:
            self._put(key, _CachedError(str(e)))
            raise
        self._put(key, value)
        return value

    def _put(self, key, value) -> None:
        gens = self._gens
        cur, tickets, _prev = gens
        ticket = next(tickets)
        if ticket < self._half:
            cur[key] = value
        # the last ticket's holder turns the generations; a later
        # one does if it finds them unturned (two may: both make
        # ``cur`` the previous one, and one's fresh dict is dropped)
        if ticket >= self._half - 1 and self._gens is gens:
            self._gens = ({}, itertools.count(), cur)
            # a memo that turns several times inside one pass keeps
            # only what is asked again within ``maxsize // 2`` stores
            # (PERF.md section 4, ``sbom-zipf-2m``); two threads that
            # turn at once count two
            if self._turn:
                DETECT_METRICS.inc(self._turn)

    def __len__(self) -> int:
        cur, _tickets, prev = self._gens
        return len(cur) + len(prev)

    def clear(self) -> None:
        self._gens = ({}, itertools.count(), {})


class ConstraintIntervalCache(KeyedMemo):
    """Memo over ``comparer.constraint_intervals`` keyed by
    (grammar, constraint string)."""

    def __init__(self, maxsize: int = 65536):
        super().__init__(maxsize, "interval_cache_hits",
                         "interval_cache_misses",
                         "constraint_cache_turns")

    def intervals(self, grammar: str, comparer,
                  constraint: str) -> tuple:
        """Compiled intervals for one ``||``-free constraint, shared
        across callers (read-only by contract). Raises ValueError on
        a (cached) parse failure, like ``constraint_intervals``."""
        return self.lookup(
            (grammar, constraint),
            lambda _k: tuple(
                comparer.constraint_intervals(constraint)))


INTERVAL_CACHE = ConstraintIntervalCache()
