"""Secret-sieve metrics: selectivity, verify tail, DFA table upload
amortization (docs/performance.md "the DFA engine").

Process-wide by design, mirroring ``detect.metrics.DETECT_METRICS``:
the DFA table is a process singleton per rule-set hash, uploads
happen once per (generation, placement), and the numbers an operator
watches on ``/metrics`` are cumulative totals. Counter updates take
one short lock per BATCH (the batch scanner flushes a whole sieve's
numbers in one call) — nothing here sits on a per-byte hot path.
"""

from __future__ import annotations

import threading


class SecretMetrics:
    """Cumulative counters for the secret-sieve hot path."""

    _KEYS = (
        # sieve funnel: files in, files that needed ANY host verify,
        # files fully cleared on device, files with findings
        "files_total", "files_gated", "files_device_cleared",
        "files_with_findings",
        # per-rule verify split (windowed-exact vs whole-file) and
        # rules the on-device DFA chain gate dropped before any host
        # regex ran; verify_bytes: the bytes handed to an exact
        # regex, a region's length or the file's for a whole-file rule
        "rules_verified", "rules_windowed", "rules_wholefile",
        "rules_chain_gated", "verify_bytes",
        # file bytes whose sieve ran on the device (fused or
        # sharded dispatch) — cpu-ref batches add nothing here
        "device_bytes",
        # a fused dispatch's real segment rows, and the rows of the
        # buffer it uploaded (its ``_bucket`` rung): what the pad
        # ladder costs the device, which sieves every row
        "sieve_rows", "sieve_rows_padded",
        # wall-time accumulators (seconds, float)
        "sieve_s", "verify_s",
        # DFA table residency (ops/dfa.py DfaTable hooks)
        "dfa_uploads", "dfa_upload_bytes", "dfa_dispatches",
        "dfa_invalidations",
        # a mesh dispatch's non-empty layout shards; decode_tasks
        # stays 0 (the decode runs on the collecting thread)
        "shards_dispatched", "decode_tasks",
        # a sharded dispatch's shards, their real segment rows, and
        # the real rows of its fullest shard: max x shards / rows is
        # 1.0 where the LPT layout (parallel/balance.py) is even
        "sieve_shards", "sieve_shard_rows", "sieve_shard_rows_max",
        # dispatches whose hit rows passed SIEVE_CAP: the whole mask
        # fetched by a second dispatch (secret/batch._decode)
        "sieve_full_fetches",
        # a streamed tree (runtime/batch.submit_tree): the parts its
        # candidates were cut into, and the files with more rows
        # than a part, each of which rode alone
        "tree_parts", "tree_oversize_files",
    )

    def __init__(self):
        self._lock = threading.Lock()
        self._c = {k: 0 for k in self._KEYS}

    def inc(self, name: str, n=1) -> None:
        with self._lock:
            # lint: disable=unbounded-label-cardinality -- counter
            # names are code-literal call sites, never
            # request-derived strings
            self._c[name] = self._c.get(name, 0) + n

    def note_batch(self, stats: dict) -> None:
        """Flush one sieve batch's stats dict (BatchSecretScanner
        ``collect``) into the cumulative counters."""
        with self._lock:
            c = self._c
            c["files_total"] += stats.get("files_total", 0)
            c["files_gated"] += stats.get("files_gated", 0)
            c["files_device_cleared"] += (
                stats.get("files_total", 0)
                - stats.get("files_gated", 0))
            c["files_with_findings"] += stats.get(
                "files_with_findings", 0)
            c["rules_verified"] += stats.get("rules_verified", 0)
            c["rules_windowed"] += stats.get("rules_windowed", 0)
            c["rules_wholefile"] += stats.get("rules_wholefile", 0)
            c["rules_chain_gated"] += stats.get(
                "rules_chain_gated", 0)
            c["verify_bytes"] += stats.get("verify_bytes", 0)
            if stats.get("mode") in ("fused", "sharded"):
                c["device_bytes"] += stats.get("bytes_total", 0)
            rows = stats.get("shard_rows")
            if stats.get("mode") == "sharded" and rows:
                c["sieve_shards"] += len(rows)
                c["sieve_shard_rows"] += sum(rows)
                c["sieve_shard_rows_max"] += max(rows)
            if stats.get("mode") == "fused":
                c["sieve_rows"] += stats.get("sieve_rows", 0)
                c["sieve_rows_padded"] += stats.get(
                    "sieve_rows_padded", 0)
            c["sieve_s"] += stats.get("sieve_s", 0.0)
            c["verify_s"] += stats.get("verify_s", 0.0)

    def note_dfa_upload(self, nbytes: int) -> None:
        with self._lock:
            self._c["dfa_uploads"] += 1
            self._c["dfa_upload_bytes"] += nbytes

    def reset(self) -> None:
        """Test hook — production code never calls this."""
        with self._lock:
            for k in self._c:
                self._c[k] = 0

    def snapshot(self) -> dict:
        with self._lock:
            out = dict(self._c)
        out["sieve_s"] = round(out["sieve_s"], 4)
        out["verify_s"] = round(out["verify_s"], 4)
        ft = out["files_total"]
        out["sieve_selectivity"] = round(
            out["files_gated"] / ft, 4) if ft else 0.0
        out["dfa_upload_amortization"] = round(
            out["dfa_dispatches"] / out["dfa_uploads"], 2) \
            if out["dfa_uploads"] else 0.0
        # the sieve's rows of the phase clock (obs/trace.phase_span:
        # pack, h2d_upload, dfa_scan, decode, verify), cumulative
        from ..obs.trace import phase_rows
        out["phase"] = phase_rows("secret")
        return out


SECRET_METRICS = SecretMetrics()
