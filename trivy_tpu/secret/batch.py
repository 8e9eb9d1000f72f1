"""Batched secret scanning: on-device multi-pattern DFA sieve +
windowed host verify.

Pipeline (the TPU re-design of the reference's per-file scan loop,
pkg/fanal/secret/scanner.go:341):

  1. files → fixed-size overlapping segments in one [B, L] uint8 buffer
     (the "sequence dimension" of this domain — SURVEY.md §5);
  2. ONE kernel dispatch scans every segment against the compiled
     multi-pattern table (trivy_tpu.ops.dfa): full-length gate
     keywords, anchor literals, and each rule's mandatory fixed
     byte-class chain — per-(segment, pattern) position bitmasks out
     of a banded transition table resident in HBM. Class-run gates
     (trivy_tpu.ops.runs) ride the same dispatch;
  3. host decodes hits: a rule is *gated in* for a file iff one of its
     keywords hit (reference MatchKeywords semantics) AND its compiled
     chain hit (a chain miss is a PROOF the regex cannot match — the
     rule resolves fully on-device); for rules whose regex is provably
     anchor-bounded (rx.anchor), a preliminary regex over small
     windows around anchor hits decides whether the rule can match;
     a rule whose only unbounded parts are whitespace runs
     (rx.anchor.space_elastic) gets the regions round its chain's
     and keyword's hits, where its exact verify then runs;
  4. files with surviving rules get a CPU-exact scan restricted to
     those rules — byte-identical findings, because every rule that
     could contribute findings (or censoring) survives the sieve.

With a mesh, the sieve is submitted SHARDED AND ASYNC
(parallel/secret_shard.py): per-shard segment packing fans over the
host pool concurrently, ONE non-blocking shard_map dispatch splits
the rows across every chip (so the sieve computes while the caller
squashes layers, preps interval jobs, and packs the next batch), and
per-shard result decode fans back over the pool — the host thread
never serializes the whole sieve, which is what used to make
``secret_batch_s`` GROW with device count.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Optional

import numpy as np

from ..ops.keywords import (MAX_CODE_LEN, N_BLOCKS, PART_ROWS,
                            _bucket, pad_batch)
from ..utils import get_logger
from .plan import ScanPlan, build_scan_plan
from .rx.anchor import SPACE_1TO1
from .scanner import Scanner

log = get_logger("secret.batch")

SEG_LEN = 2048       # segment length in bytes
OVERLAP = 16         # floor; raised to the plan's min_overlap

_BUILTIN_RULES_FP = [None]


def rules_fingerprint(scanner=None) -> str:
    """Content hash of a secret rule SET — a blob-cache and
    findings-memo key component (docs/performance.md): two rule
    configurations (builtin vs a trivy-secret.yaml custom set) must
    never share cached secret findings. ``scanner`` is a
    BatchSecretScanner, a bare Scanner, or None (the builtin
    corpus, hashed once per process)."""
    import hashlib
    inner = getattr(scanner, "scanner", scanner)
    rules = getattr(inner, "rules", None)
    if rules is None:
        if _BUILTIN_RULES_FP[0] is None:
            from .scanner import new_scanner
            _BUILTIN_RULES_FP[0] = rules_fingerprint(new_scanner())
        return _BUILTIN_RULES_FP[0]
    cached = getattr(inner, "_rules_fp", None)
    if cached is not None:
        return cached
    h = hashlib.sha256()
    for r in rules:
        h.update(repr((
            r.id, r.category, r.severity,
            r.regex.pattern if r.regex is not None else "",
            tuple(r.keywords),
            r.path.pattern if r.path is not None else "",
            tuple((a.id, a.regex.pattern if a.regex is not None
                   else "", a.path.pattern if a.path is not None
                   else "") for a in r.allow_rules),
            r.secret_group_name)).encode())
    # global allow rules / exclude blocks change findings too
    for a in getattr(inner, "allow_rules", ()):
        h.update(repr((a.id,
                       a.regex.pattern if a.regex is not None
                       else "",
                       a.path.pattern if a.path is not None
                       else "")).encode())
    fp = h.hexdigest()[:16]
    try:
        inner._rules_fp = fp     # rule sets are static after build
    except AttributeError:
        pass
    return fp


def _runs_by_file(hits: np.ndarray, seg_file: list) -> dict:
    """file index → set of run-spec indices from ``hits`` [rows,
    specs] bool; rows of no file (``seg_file`` -1, a shard's
    padding) are dropped. One numpy call, then plain integers: on
    the drain thread every further call into numpy gives the
    interpreter away for a switch interval (PERF.md section 6,
    PR 38), and a loop over numpy's scalars costs three times one
    over a list's."""
    rows, specs = np.nonzero(hits)
    out: dict = {}
    for si, sp in zip(rows.tolist(), specs.tolist()):
        if seg_file[si] >= 0:
            out.setdefault(seg_file[si], set()).add(sp)
    return out


class PartCutter:
    """Cuts a stream of candidate files, whole, into sieve batches
    of at most ``PART_ROWS`` segment rows (``ops.keywords``: the
    ladder's top warmed rung): ``add`` returns the part a file
    closed, or None. A file with more rows than a part is a part of
    its own, at the ladder's steps above the rung, and is counted.
    A file is ``(seq, path, content)``: parts come back in the order
    they closed, which an oversize file makes another order than the
    files', and ``seq`` brings the findings home."""

    def __init__(self, scanner: "BatchSecretScanner"):
        self.scanner = scanner
        self.part_rows = PART_ROWS
        self._files: list = []
        self._rows = 0
        self._seq = 0
        self.open_bytes = 0          # of the part still open

    def add(self, path: str, content: bytes) -> Optional[list]:
        from .metrics import SECRET_METRICS
        item = (self._seq, path, content)
        self._seq += 1
        rows = self.scanner._n_segs(len(content))
        if rows > self.part_rows:
            SECRET_METRICS.inc("tree_oversize_files")
            SECRET_METRICS.inc("tree_parts")
            return [item]
        closed = None
        if self._rows + rows > self.part_rows:
            closed = self.flush()
        self._files.append(item)
        self._rows += rows
        self.open_bytes += len(content)
        return closed

    def flush(self) -> Optional[list]:
        """The part still open, or None."""
        from .metrics import SECRET_METRICS
        if not self._files:
            return None
        closed, self._files, self._rows = self._files, [], 0
        self.open_bytes = 0
        SECRET_METRICS.inc("tree_parts")
        return closed


@dataclass
class _FileEntry:
    path: str
    content: bytes
    index: int


class BatchSecretScanner:
    """Scans many files per kernel dispatch. API mirrors Scanner.scan
    but over a batch; results are CPU-engine-identical."""

    def __init__(self, scanner: Optional[Scanner] = None,
                 seg_len: int = SEG_LEN, backend: str = "tpu",
                 mesh=None):
        if scanner is None:
            from .scanner import new_scanner
            scanner = new_scanner()
        self.scanner = scanner
        self.backend = backend
        self.mesh = mesh
        self.plan: ScanPlan = build_scan_plan(self.scanner.rules)
        self.table = self.plan.table
        # overlap ≥ the longest compiled pattern (full-length
        # keywords, chains, class runs) so nothing straddles an
        # uncovered segment boundary — plan.validate_overlap makes a
        # violation a loud build error, not a silent false negative
        self.overlap = max(OVERLAP, MAX_CODE_LEN,
                           self.plan.min_overlap)
        # kernels need L % 128 == 0 (lane width / block reduction)
        self.seg_len = max(seg_len, 4 * self.overlap, 128)
        self.seg_len = ((self.seg_len + 127) // 128) * 128
        self.plan.validate_overlap(self.overlap)
        # table column -> the plan's rules (by position) it gates, and
        # the rules no keyword gates (``_rules_gated_in``)
        self._gated_by: dict = {}
        for ri, rp in enumerate(self.plan.rules):
            for col in rp.gate:
                self._gated_by.setdefault(col, []).append(ri)
        self._gateless = [ri for ri, rp in enumerate(self.plan.rules)
                          if not rp.gate]
        self.stats: dict = {}

    # --- segmenting ---

    def _n_segs(self, n: int) -> int:
        """Segment count for an ``n``-byte file: positions advance by
        ``seg_len - overlap`` until one window reaches the end."""
        L, step = self.seg_len, self.seg_len - self.overlap
        if n <= L:
            return 1
        return 1 + -(-(n - L) // step)

    def _shard_count(self) -> int:
        """Data shards for the sieve: every device of the mesh, flat
        — the DFA table is KBs, so rules-axis sharding buys nothing;
        each chip holds the full table and takes a slice of files."""
        if self.mesh is None:
            return 1
        return int(self.mesh.devices.size)

    def _fill_rows(self, buf: np.ndarray, row0: int, content: bytes,
                   n_segs: int) -> None:
        """Pack one file's overlapping segments into ``buf`` rows
        [row0, row0+n_segs) with one bulk strided copy. The mesh
        path packs with it, a shard a pool task
        (parallel/secret_shard.py); the one-device path packs a
        whole batch in ``_pack`` instead, and the tests hold that to
        the rows this writes."""
        L, step = self.seg_len, self.seg_len - self.overlap
        n = len(content)
        arr = np.frombuffer(content, np.uint8)
        if n_segs == 1:
            buf[row0, :n] = arr
            return
        total = (n_segs - 1) * step + L
        tmp = np.zeros(total, np.uint8)
        tmp[:n] = arr
        # zero-copy sliding view over the padded file image; the
        # single assignment below is the only copy that happens
        view = np.lib.stride_tricks.as_strided(
            tmp, (n_segs, L), (step, 1))
        buf[row0:row0 + n_segs] = view

    def _layout(self, metas: list) -> dict:
        """Row layout for a batch — the device assignment. With a
        mesh, files are placed into per-shard row blocks balanced by
        byte volume (parallel.balance, LPT) so one fat image cannot
        serialize the data axis; each block pads to the widest shard
        (rows of ``seg_file == -1`` are inert — all-zero segments
        match no pattern and the decoders skip them). Returns
        {B, layout: [(row0, meta idx)], seg_file, seg_pos,
        occupancy, n_shards, rows_per_shard}."""
        step = self.seg_len - self.overlap
        n_shards = self._shard_count()
        occupancy: list = []
        total = sum(m[2] for m in metas)
        # shard count derives from the batch's PADDED size, not the
        # device count alone: the jit pad ladder (_bucket) fixes the
        # total padded rows, and shards are carved out of that same
        # total in ≥ MIN_SHARD_ROWS blocks — so a small batch on 8
        # devices uses fewer shards instead of padding every tiny
        # shard up to a full block (which pads a scheduler batch of
        # a few hundred segments with rows the sieve scans for nothing)
        MIN_SHARD_ROWS = 64          # = the pallas tile (TILE_B)
        if n_shards > 1 and len(metas) > 1:
            Bp = _bucket(total, base=4 * MIN_SHARD_ROWS)
            pow2 = 1
            while pow2 * 2 <= n_shards:
                pow2 *= 2
            n_shards = max(1, min(pow2, Bp // MIN_SHARD_ROWS))
        else:
            n_shards = 1         # a single file cannot shard
        if n_shards > 1:
            from ..parallel.balance import (balance_by_volume,
                                            shard_occupancy)
            volumes = [n for _, n, _ in metas]
            assign = balance_by_volume(volumes, n_shards)
            occupancy = shard_occupancy(volumes, assign, n_shards)
            by_shard: list = [[] for _ in range(n_shards)]
            for mi, s in enumerate(assign):
                by_shard[s].append(mi)
            # every ladder value divides evenly by a pow2 shard
            # count ≤ Bp/MIN_SHARD_ROWS, so the total padded rows
            # are IDENTICAL at every device count; only a fat file
            # overflowing its LPT block (occupancy shows it) can
            # force a wider shard
            rows_per_shard = Bp // n_shards
            nat = max(sum(metas[mi][2] for mi in block) or 1
                      for block in by_shard)
            if nat > rows_per_shard:
                rows_per_shard = -(-nat // MIN_SHARD_ROWS) * \
                    MIN_SHARD_ROWS
            B = n_shards * rows_per_shard
            layout = []          # (row0, meta index)
            for s, block in enumerate(by_shard):
                row = s * rows_per_shard
                for mi in block:
                    layout.append((row, mi))
                    row += metas[mi][2]
        else:
            B = total
            layout, row = [], 0
            for mi, m in enumerate(metas):
                layout.append((row, mi))
                row += m[2]
            n_shards, rows_per_shard = 1, B

        seg_file = [-1] * B
        seg_pos = [0] * B
        for row0, mi in layout:
            fe, _n, n_segs = metas[mi]
            seg_file[row0:row0 + n_segs] = [fe.index] * n_segs
            seg_pos[row0:row0 + n_segs] = range(0, n_segs * step,
                                                step)
        return {"B": B, "layout": layout, "seg_file": seg_file,
                "seg_pos": seg_pos, "occupancy": occupancy,
                "n_shards": n_shards,
                "rows_per_shard": rows_per_shard}

    def _metas(self, files: list) -> list:
        return [(fe, len(fe.content), self._n_segs(len(fe.content)))
                for fe in files if len(fe.content) > 0]

    def _pack(self, files: list) -> tuple:
        """Flatten files into [_bucket(B), L] uint8 with per-file
        overlap chaining. Returns (padded buffer, seg_file, seg_pos,
        shard_occupancy); the real rows are the first
        ``len(seg_file)``, the rest are zero, so ``pad_batch`` has
        nothing to add before the upload.

        The whole pack runs on the calling thread and never lets go
        of the interpreter: one zeroed allocation, then one
        buffer-protocol slice copy a row, no numpy call and no pool
        task. On the scheduler's launch thread every call that drops
        the lock (a pool future, a numpy copy of over ~500 elements)
        is a wait of up to the switch interval behind the analyze
        workers, and that wait, not the copy, was the pack's cost.
        (The sharded-async path packs a shard a pool task instead:
        parallel.secret_shard.)"""
        L, step = self.seg_len, self.seg_len - self.overlap
        metas = self._metas(files)
        if not metas:
            return np.zeros((0, L), np.uint8), [], [], []
        lay = self._layout(metas)
        raw = bytearray(_bucket(lay["B"]) * L)
        out = memoryview(raw)
        for row0, mi in lay["layout"]:
            fe, n, n_segs = metas[mi]
            src = memoryview(fe.content)
            at = row0 * L
            last = (n_segs - 1) * step
            for off in range(0, last, step):
                out[at:at + L] = src[off:off + L]
                at += L
            out[at:at + n - last] = src[last:]
        return (np.frombuffer(raw, np.uint8).reshape(-1, L),
                lay["seg_file"], lay["seg_pos"], lay["occupancy"])

    def _segment(self, files: list) -> tuple:
        """``_pack`` without the pad rows: (buffer [B, L], seg_file,
        seg_pos, shard_occupancy)."""
        padded, seg_file, seg_pos, occupancy = self._pack(files)
        return (padded[:len(seg_file)], seg_file, seg_pos,
                occupancy)

    # --- the public API ---

    def scan_files(self, files: Iterable) -> list:
        """``files``: iterable of (path, content-bytes).
        Returns list of ``(entry_index, types.Secret)`` pairs, only for
        entries with findings. Callers MUST map results back by the
        returned index, never by path: the same path routinely appears
        in several entries (every alpine image shares a file tree) and
        path-based attribution misassigns findings across them.

        ``self.stats`` afterwards holds the sieve selectivity and the
        host/device time split for this call (stats + tracing)."""
        return self.collect(self.dispatch_files(files))

    def dispatch_files(self, files: Iterable):
        """Async half of scan_files: build the segment buffer and
        ENQUEUE the sieve dispatch without fetching results. The
        device computes while the caller does host work (squash,
        interval job prep); ``collect`` fetches + verifies.

        On the cpu-ref backend the dispatch runs eagerly; with a
        mesh, per-shard packing fans over the host pool and one
        non-blocking shard_map dispatch covers every chip."""
        entries = [
            _FileEntry(path=p, content=c, index=i)
            for i, (p, c) in enumerate(files)
        ]
        return self._dispatch(entries)

    def collect(self, handle) -> list:
        """Blocking half of scan_files: fetch sieve outputs, decode
        candidates, run the windowed/whole-file exact verify."""
        from .metrics import SECRET_METRICS
        from ..obs.trace import phase_span
        entries = handle["entries"]
        candidates = self._decode(handle)

        results = []
        rules_verified = windowed = wholefile = verify_bytes = 0
        # the verify tail is a collect-side host phase: the timeline
        # attributes device idle under it to collect_bound
        with phase_span("verify", pipeline="secret",
                        files=len(entries)) as vsp:
            for fe in entries:
                chosen = candidates.get(fe.index)
                if not chosen:
                    continue
                rules_verified += len(chosen)
                idxs = sorted(chosen)
                rules = [self.scanner.rules[i] for i in idxs]
                regions = [chosen[i] for i in idxs]
                sub = Scanner(rules, self.scanner.allow_rules,
                              self.scanner.exclude_block)
                secret = sub.scan(fe.path, fe.content,
                                  regions=regions)
                # count AFTER the scan: multibyte files silently
                # fall back whole-file inside Scanner.scan
                if not getattr(sub, "used_regions", False):
                    regions = [None] * len(regions)
                for spans in regions:
                    if spans is None:
                        wholefile += 1
                        verify_bytes += len(fe.content)
                    else:
                        windowed += 1
                        verify_bytes += sum(b - a for a, b in spans)
                if secret.findings:
                    results.append((fe.index, secret))

        # every second here is a phase's own (obs/trace.phase_span):
        # sieve_s is pack + upload + dfa_scan + decode, device_s the
        # upload and dfa_scan part of it (the enqueue, and the fetch
        # where the device's wall passes on the host)
        self.stats = {
            "files_total": len(entries),
            "bytes_total": sum(len(fe.content) for fe in entries),
            # real segment rows, and the rows of the rung a fused
            # dispatch uploaded (the sharded path pads a shard at a
            # time and says nothing here)
            "sieve_rows": len(handle.get("seg_file", ())),
            "sieve_rows_padded": handle.get("padded_rows", 0),
            "files_gated": len(candidates),
            "rules_verified": rules_verified,
            "rules_windowed": windowed,
            "rules_wholefile": wholefile,
            "verify_bytes": verify_bytes,
            "rules_chain_gated": handle.get("chain_gated", 0),
            "files_with_findings": len(results),
            "sieve_s": round(handle.get("pack_s", 0.0)
                             + handle["device_s"]
                             + handle.get("decode_s", 0.0), 4),
            "pack_s": round(handle.get("pack_s", 0.0), 4),
            "device_s": round(handle["device_s"], 4),
            "verify_s": round(vsp.duration_s, 4),
            "shard_occupancy": handle.get("shard_occupancy", []),
            "mode": handle.get("mode", ""),
        }
        SECRET_METRICS.note_batch(self.stats)
        return results

    # --- sieve stages ---

    def _dispatch(self, entries: list) -> dict:
        """Segment + enqueue the sieve. Returns the handle `_decode`
        consumes; on the fused and sharded paths the jax arrays
        inside are NOT yet materialized — the device(s) compute in
        the background."""
        from ..obs.trace import phase_span
        handle = {"entries": entries, "device_s": 0.0}
        if self.mesh is not None and self.backend != "cpu-ref":
            # sharded async submission: concurrent per-shard packs
            # on the host pool, one non-blocking mesh dispatch,
            # decode fanned back over the pool at collect time
            from ..parallel.secret_shard import ShardedSieve
            metas = self._metas(entries)
            if not metas:
                handle["mode"] = "empty"
                return handle
            # start() is host work: shard layout + pool-parallel
            # segment fills, then a NON-blocking mesh enqueue — so
            # it brackets as pack, not device-busy; the dfa_scan
            # busy span lives at ShardedSieve.decode()'s join,
            # where the device wall actually passes
            with phase_span("pack", pipeline="secret",
                            files=len(entries),
                            shards=self._shard_count()) as sp:
                sharded = ShardedSieve(self, metas)
                sharded.start()
            handle.update(mode="sharded", sharded=sharded,
                          pack_s=sp.duration_s,
                          shard_occupancy=sharded.occupancy)
            return handle

        with phase_span("pack", pipeline="secret",
                        files=len(entries)) as sp:
            padded, seg_file, seg_pos, occupancy = \
                self._pack(entries)
            buf = padded[:len(seg_file)]
            sp.set("segments", int(buf.shape[0]))
        handle.update(buf=buf, padded=padded, seg_file=seg_file,
                      seg_pos=seg_pos, pack_s=sp.duration_s,
                      shard_occupancy=occupancy)
        if buf.shape[0] == 0:
            handle["mode"] = "empty"
            return handle
        if self.backend == "cpu-ref":
            from ..ops.dfa import dfa_masks_host
            # the host kernel IS the sieve compute on this path —
            # bracketed as dfa_scan so the timeline counts it busy
            # (the fused path's span lives at its fetch instead,
            # where the async dispatch's wall actually passes)
            with phase_span("dfa_scan", pipeline="secret",
                            segments=int(buf.shape[0]),
                            patterns=self.table.n_patterns,
                            host=True) as sp:
                handle["masks"] = dfa_masks_host(buf, self.table)
            handle["mode"] = "host"
            handle["device_s"] += sp.duration_s
            return handle
        # fused path: the segment buffer is uploaded ONCE,
        # pattern blockmasks + run hits come out of a single dispatch
        # against the resident band table, and the mask fetch is
        # compacted to the hit rows (selectivity makes this ~1% of
        # the full [B, K] array; the >CAP fallback fetches all)
        import jax
        platform = jax.default_backend()
        specs = tuple(self.plan.run_specs)
        tbl = self.table.device_tables()
        fn = self.table.fused_sieve(specs, platform)
        with phase_span("h2d_upload", pipeline="secret",
                        bytes=int(buf.nbytes)) as usp:
            dev = jax.device_put(pad_batch(padded))
        padded_rows = int(dev.shape[0])
        with phase_span("dfa_scan", pipeline="secret",
                        segments=int(buf.shape[0]),
                        patterns=self.table.n_patterns) as sp:
            # the segment buffer is donated to the kernel — ``dev``
            # is dead after this call (the >CAP fallback re-uploads)
            nhit, idx, cm, h = fn(dev, *tbl)
        handle.update(mode="fused", platform=platform,
                      padded_rows=padded_rows,
                      tbl=tbl, nhit=nhit, idx=idx, cm=cm, h=h)
        handle["device_s"] += usp.duration_s + sp.duration_s
        return handle

    def _decode(self, handle: dict) -> dict:
        """file index → {rule index: verify spans or None}.

        A rule maps to merged byte spans when its window proof is
        extraction-exact (the host then regexes only those spans); to
        None when it needs the reference's whole-file scan. A rule
        that is space-elastic (rx.anchor.space_elastic) maps to the
        regions round its chain's and keyword's hits."""
        from ..obs.trace import phase_span
        if handle["mode"] == "empty":
            return {}
        entries = handle["entries"]

        if handle["mode"] == "sharded":
            # the join (dfa_scan, fetch=True) comes first, the
            # pool-fanned block decode and the rule choice after it
            masks, runs = handle["sharded"].fetch()
            handle["device_s"] += handle["sharded"].device_s
            with phase_span("decode", pipeline="secret",
                            mode="sharded") as sp:
                file_codes, runs_map = handle["sharded"].decode(
                    masks, runs)

                def file_runs(fidx) -> set:
                    return runs_map.get(fidx, set())

                out = self._choose(handle, entries, file_codes,
                                   file_runs)
            handle["decode_s"] = sp.duration_s
            return out

        buf = handle["buf"]
        seg_file = handle["seg_file"]
        seg_pos = handle["seg_pos"]
        run_fetch = None
        if handle["mode"] == "host":
            # the host kernel already ran (and was bracketed) at
            # dispatch; this nonzero walk is plain decode work and
            # must NOT count as device-busy
            masks = handle["masks"]
            seg_nz, code_nz = np.nonzero(masks)
            hit_vals = masks[seg_nz, code_nz]
        else:
            # the result fetch is where the async dispatch's device
            # wall actually passes (materializing the jax arrays
            # blocks on the computation) — bracketed as dfa_scan so
            # the timeline counts it as device-busy, not collect work
            with phase_span("dfa_scan", pipeline="secret",
                            fetch=True) as fsp:
                B = buf.shape[0]
                K = self.table.n_patterns
                nhit = int(handle["nhit"])
                cm = handle["cm"]
                h = handle["h"]
                if nhit > min(cm.shape[0], handle["padded_rows"]):
                    from .metrics import SECRET_METRICS
                    SECRET_METRICS.inc("sieve_full_fetches")
                    # fetch the full mask array; run hits (h) were
                    # already computed by the fused dispatch. The
                    # fused dispatch DONATED its segment buffer
                    # (ops/dfa.py), so this rare overflow path
                    # re-uploads rather than reuse freed HBM
                    import jax as _jax
                    full = self.table.full_sieve(
                        (), handle["platform"])
                    m, _ = full(
                        _jax.device_put(pad_batch(handle["padded"])),
                        *handle["tbl"])
                    masks = np.asarray(m)[:B, :K]
                    seg_nz, code_nz = np.nonzero(masks)
                    hit_vals = masks[seg_nz, code_nz]
                else:
                    rows = np.asarray(cm)[:nhit, :K]
                    ridx = np.asarray(handle["idx"])[:nhit]
                    rnz, code_nz = np.nonzero(rows)
                    # padded rows (index ≥ B) never hit: zero
                    # segments
                    seg_nz = ridx[rnz]
                    hit_vals = rows[rnz, code_nz]
                run_fetch = np.asarray(h)[:B]
            handle["device_s"] += fsp.duration_s

        # run-hits decode is lazy: it happens at most once per batch,
        # and only when a run-gated rule survives its keyword gate
        runs_cache: dict = {}
        runs_ready = [False]

        def file_runs(fidx) -> set:
            if not runs_ready[0]:
                runs_cache.update(
                    _runs_by_file(run_fetch, seg_file)
                    if run_fetch is not None
                    else self._file_runs(buf, seg_file))
                runs_ready[0] = True
            return runs_cache.get(fidx, set())

        # per file: pattern column → merged list of
        # (segment file-offset, bitmask)
        with phase_span("decode", pipeline="secret",
                        mode=handle["mode"]) as sp:
            file_codes: dict = {}
            for si, ci, mv in zip(seg_nz.tolist(),
                                  code_nz.tolist(),
                                  hit_vals.tolist()):
                if seg_file[si] < 0:
                    continue              # shard-padding row
                fc = file_codes.setdefault(seg_file[si], {})
                fc.setdefault(ci, []).append((seg_pos[si],
                                              int(mv)))

            out = self._choose(handle, entries, file_codes,
                               file_runs)
        handle["decode_s"] = sp.duration_s
        return out

    def _choose(self, handle: dict, entries: list, file_codes: dict,
                file_runs) -> dict:
        """Rule selection over decoded pattern hits: keyword gate ∧
        chain gate ∧ run gate ∧ (for anchored rules) anchor windows.
        A chain miss resolves the rule on-device — no host regex."""
        by_index = {fe.index: fe for fe in entries}
        blk = self.seg_len // N_BLOCKS
        out: dict = {}
        chain_gated = 0

        def runs_pass(rp, fidx) -> bool:
            return not rp.run_gate or \
                set(rp.run_gate) <= file_runs(fidx)

        def verify_on(chosen, fe, rp, codes) -> None:
            # the whole file (None), or a space-elastic rule's
            # regions; no region at all clears the rule
            spans = self._regions(fe, rp, codes, blk)
            if spans is None or spans:
                chosen[rp.rule_index] = spans

        # rules with no keyword gate and no anchor run everywhere
        # (reference: empty keyword list passes MatchKeywords),
        # unless their DFA chain or a mandatory class-run is
        # provably absent
        always = [rp for rp in self.plan.rules
                  if not rp.gate and not rp.anchored]
        if always:
            for fe in entries:
                codes = file_codes.get(fe.index, {})
                sel = {}
                for rp in always:
                    if rp.chain is not None and rp.chain not in codes:
                        chain_gated += 1
                        continue
                    if runs_pass(rp, fe.index):
                        verify_on(sel, fe, rp, codes)
                if sel:
                    out[fe.index] = sel

        for fidx, codes in file_codes.items():
            fe = by_index[fidx]
            hit = set(codes)
            chosen = dict(out.get(fidx, ()))
            for rp in self._rules_gated_in(hit):
                if rp.chain is not None and rp.chain not in hit:
                    if rp.gate:
                        chain_gated += 1
                    continue
                if not rp.anchored:
                    if rp.gate and runs_pass(rp, fidx):
                        verify_on(chosen, fe, rp, codes)
                    continue
                anchor_hits = [h for a in rp.anchors
                               for h in codes.get(a, ())]
                if not anchor_hits:
                    continue
                spans = self._windows(fe, rp, anchor_hits, blk)
                if rp.exact:
                    # extraction-exact: verify scans only these spans;
                    # no prelim pass needed (verify IS the prelim)
                    chosen[rp.rule_index] = spans
                elif self._prelim(fe, rp, spans):
                    verify_on(chosen, fe, rp, codes)
            if chosen:
                out[fidx] = chosen
        handle["chain_gated"] = chain_gated
        return out

    def _rules_gated_in(self, hit: set) -> list:
        """The plan's rules whose keyword gate a file's hit columns
        pass, and those nothing gates, in the plan's order: read off
        the few columns a file hits, where a loop over the plan
        asked each of 83 rules about each of 384 files a batch."""
        cand = set(self._gateless)
        for col in hit:
            cand.update(self._gated_by.get(col, ()))
        rules = self.plan.rules
        return [rules[ri] for ri in sorted(cand)]

    def _file_runs(self, buf: np.ndarray, seg_file: list) -> dict:
        """file index → set of run-spec indices present somewhere in
        the file, on the host kernel's path (the fused dispatch
        brings its run hits along). One elementwise pass over the
        same segment buffer the sieve used; overlap ≥ max runlen
        keeps it sound."""
        specs = tuple(self.plan.run_specs)
        if not specs:
            return {}
        from ..ops.runs import run_hits_host
        return _runs_by_file(run_hits_host(buf, specs), seg_file)

    def _windows(self, fe: _FileEntry, rp, anchor_hits: list,
                 blk: int) -> list:
        """Merged byte spans around anchor hit blocks: every possible
        match of the rule lies entirely inside one span, with ≥8 bytes
        of slack past any match edge (window = max match len, plus
        MAX_CODE_LEN for the anchor literal body crossing a block
        edge — anchors are ≤ MAX_CODE_LEN by rx construction)."""
        w = rp.window + MAX_CODE_LEN
        spans = []
        for pos, mask in anchor_hits:
            m = mask
            while m:
                lsb = m & -m
                j = lsb.bit_length() - 1
                m ^= lsb
                a = pos + j * blk - w
                b = pos + (j + 1) * blk + w
                spans.append((max(0, a), min(len(fe.content), b)))
        return _merged(spans)

    def _regions(self, fe: _FileEntry, rp, codes: dict,
                 blk: int) -> Optional[list]:
        """Merged byte regions for the exact verify of a rule with no
        extraction-exact window: None, the whole file, unless the
        rule is space-elastic (``rp.elastic``: rx.anchor.space_elastic
        holds the proof that ``finditer`` over these regions gives the
        whole file's matches). Round every hit block of a piece every
        match contains, widened by the piece's length (a piece that
        starts in the block may end past it, and one that crosses a
        segment's end is reported by the next segment), the walk of
        the rule's reach in the file's own bytes: at most so many
        bytes, then to the end of the whitespace run the walk stands
        in or next to, as often as the rule has runs on that side;
        two bytes more on the right; what touches merged. Two pieces'
        regions are intersected: a match holds both. ``[]`` says a
        piece has no hit, so the rule cannot fire. Where the regions
        are most of the file (a megabyte of base64 is one chain hit
        after another, and has no keyword to narrow it) the file goes
        to the regex whole: the merged region is the file anyway."""
        if not rp.elastic:
            return None
        content = fe.content
        n = len(content)
        out = None
        for col, reach in rp.elastic:
            spans = []
            for pos, mask in codes.get(col, ()):
                m = mask
                while m:
                    lsb = m & -m
                    at = pos + (lsb.bit_length() - 1) * blk
                    m ^= lsb
                    spans.append((at - reach.length,
                                  at + blk + reach.length))
            if not spans:
                return []
            walked = _merged([
                (_walk(content, max(a, 0), reach.left, -1),
                 min(n, _walk(content, min(b, n), reach.right, 1) + 2))
                for a, b in _merged(spans)])
            if 2 * sum(b - a for a, b in walked) > n:
                continue
            out = walked if out is None else _intersect(out, walked)
        if out is None or 2 * sum(b - a for a, b in out) > n:
            return None
        return out

    def _prelim(self, fe: _FileEntry, rp, merged: list) -> bool:
        """Windowed existence check for rules whose window proof is
        sound for detection but not extraction (elastic edges, ^/$):
        a hit here still requires the reference whole-file scan."""
        rule = self.scanner.rules[rp.rule_index]
        for a, b in merged:
            # decode mirrors Scanner.scan; edge-partial codepoints sit
            # in the ≥8-byte margin outside any possible match span
            window = fe.content[a:b].decode("utf-8", "surrogateescape")
            if rule.regex.search(window):
                return True
        return False


def _merged(spans: list) -> list:
    """Sorted, and what overlaps or touches made one."""
    merged: list = []
    for a, b in sorted(spans):
        if merged and a <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], b))
        else:
            merged.append((a, b))
    return merged


def _intersect(xs: list, ys: list) -> list:
    """The bytes in both of two merged span lists."""
    out = []
    i = j = 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def _walk(content: bytes, x: int, steps: tuple, d: int) -> int:
    """From ``x`` outwards (``d`` -1 left, +1 right) by a rule's
    reach (rx.anchor.ElasticReach): at most ``steps[0]`` bytes, and
    before each further reach to the end of the whitespace run the
    walk stands in or next to."""
    n = len(content)
    for i, reach in enumerate(steps):
        if i and d < 0:
            while x > 0 and content[x - 1] in SPACE_1TO1:
                x -= 1
        elif i:
            while x < n and content[x] in SPACE_1TO1:
                x += 1
        x += d * reach
        if x <= 0 or x >= n:
            return max(0, min(x, n))
    return x
