"""Mesh-sharded secret sieve: async per-shard submission.

The round-5 sieve built ONE global segment buffer on the host thread,
dispatched one mesh-wide ``shard_map`` kernel, and decoded the whole
mask array serially — so ``secret_batch_s`` was host-bound and GREW
with device count (every added shard added padding, packing and
decode to the same host thread).

This module replaces that with an async sharded submission:

  1. files are LPT-assigned to per-shard row blocks of one buffer
     (parallel.balance — layout unchanged, still the device
     assignment);
  2. every shard's rows PACK as independent host-pool tasks running
     CONCURRENTLY (the old path packed serially on one thread);
  3. one shard_map dispatch splits the rows across every chip and
     returns BEFORE the chips finish — so the caller's host work
     (squash, interval prep, and the scheduler's NEXT batch, whose
     packing this overlaps) proceeds while the sieve computes;
  4. at collect time, per-shard mask decode (nonzero + dict build)
     fans back over the host pool and partial results merge.

The "pack batch N+1 while batch N computes" overlap therefore comes
from the async dispatch + the scheduler's batch pipelining, not from
interleaving shards within one batch — a per-shard dispatch loop was
tried first and measured ~1.3 s of jit compile per (device, shape)
pair, dwarfing what it overlapped (see ShardedSieve below).

The DFA band table is tiny (KBs), so every device holds the FULL
table — replicated once per (rule-set generation, device) through
the same ResidentTables machinery as the advisory DB — and the data
axis gets ALL the parallelism; no collective is needed, each shard's
masks come home independently. The hostpool contract holds: pack and
decode tasks block only on jax device results, never on other pool
tasks or scheduler events (runtime/hostpool.py).

The reference analog is the client/server work split (SURVEY.md
§2.6): N thin clients → 1 stateful server over Twirp becomes
N data shards → per-chip resident rule tables over ICI.

``sharded_blockmask`` (the round-5 shard_map literal kernel) is kept
for the ops-level tests and the legacy ``run_blockmask`` path.
"""

from __future__ import annotations

import functools
import threading

import numpy as np

from ..ops.keywords import CODE_CHUNK, _bucket, code_blockmask_impl
from .mesh import (DATA_AXIS, RULES_AXIS, mesh_axis_sizes,
                   pad_to_multiple)


def sieve_rows(rows: int, n_devices: int) -> int:
    """The rows of the buffer a sharded dispatch uploads for
    ``rows`` segment rows: their ``ops.keywords._bucket`` rung, a
    multiple of ``n_devices`` pallas tiles (TILE_B 32 rows)."""
    return pad_to_multiple(_bucket(rows), n_devices * 32)


class ShardedSieve:
    """One batch's async sharded sieve submission. Built by
    BatchSecretScanner._dispatch (mesh path): per-shard segment
    packing fans over the host pool, ONE shard_map dispatch splits
    the rows across every chip (the DFA band arrays are replicated
    per mesh through ResidentTables — masks are row-elementwise, so
    no collective), and per-shard result decode fans back over the
    pool at collect time. The single dispatch is deliberate: a
    per-device dispatch loop costs one jit compile per (device,
    shape) — measured ~1.3 s each on the CPU sim — where shard_map
    compiles once per shape and still executes per-chip in parallel.
    Single-producer, single-consumer."""

    def __init__(self, scanner, metas: list):
        self.scanner = scanner
        self.metas = metas
        self.lay = scanner._layout(metas)
        self.occupancy = self.lay["occupancy"]
        # each shard's real segment rows (SECRET_METRICS' sieve_shard*)
        self.shard_rows: list = []
        self.device_s = 0.0       # the join's dfa_scan span
        self._out = None

    def _fill_shard(self, items: list, buf) -> None:
        for row0, mi in items:
            fe, _n, n_segs = self.metas[mi]
            self.scanner._fill_rows(buf, row0, fe.content, n_segs)

    def start(self) -> "ShardedSieve":
        import jax

        from ..runtime.hostpool import get_host_pool
        from ..secret.metrics import SECRET_METRICS
        sc = self.scanner
        lay = self.lay
        n_shards, rps = lay["n_shards"], lay["rows_per_shard"]
        self.n_valid = lay["B"]
        n_flat = int(sc.mesh.devices.size)
        # the shard_map splits the leading dim over every chip, and
        # the pallas kernel tiles each chip's block by TILE_B rows;
        # the rows take a rung of the segment ladder, as the one-chip
        # path's do, so that runtime/aot.warm_ladders can warm every
        # shape a batch gives
        B = sieve_rows(lay["B"], n_flat)
        self.buf = buf = np.zeros((B, sc.seg_len), np.uint8)
        self.seg_file = lay["seg_file"]
        self.seg_pos = lay["seg_pos"]
        self.rps = rps if n_shards > 1 else B

        by_shard: list = [[] for _ in range(n_shards)]
        for row0, mi in lay["layout"]:
            by_shard[row0 // rps].append((row0, mi))
        by_shard = [blk for blk in by_shard if blk]
        self.shard_rows = [sum(self.metas[mi][2] for _, mi in blk)
                           for blk in by_shard]

        pool = get_host_pool()
        on_pool = threading.current_thread().name.startswith(
            "trivy-hostpool")
        # the caller's pack phase brackets start(): WALL time across
        # the parallel fills, whose per-task durations overlap
        if pool is not None and not on_pool and len(by_shard) > 1:
            fills = [pool.submit(self._fill_shard, blk, buf)
                     for blk in by_shard]
            for f in fills:
                f.result()
        else:
            for blk in by_shard:
                self._fill_shard(blk, buf)

        table = sc.table
        platform = jax.default_backend()
        fn = table.mesh_sieve(sc.mesh, tuple(sc.plan.run_specs),
                              platform)
        tbl = table.device_tables(sc.mesh)
        # async: returns before the chips finish; the caller's host
        # work (squash, interval prep, the NEXT batch's packing)
        # overlaps the sieve compute
        self._out = fn(buf, *tbl)
        SECRET_METRICS.inc("shards_dispatched", len(by_shard))
        return self

    def fetch(self) -> tuple:
        """Join the mesh result: ``(masks, runs)`` on the host."""
        from ..obs.trace import phase_span
        K = self.scanner.table.n_patterns
        # the async dispatch's device wall passes HERE — the
        # np.asarray join blocks on the mesh sieve — so this is the
        # dfa_scan busy span the idle-attribution timeline counts
        # (mirrors the fused path's dfa_scan(fetch=True))
        with phase_span("dfa_scan", pipeline="secret", fetch=True,
                        segments=int(self.n_valid)) as sp:
            masks = np.asarray(self._out[0])[:self.n_valid, :K]
            runs = np.asarray(self._out[1])[:self.n_valid]
        self.device_s += sp.duration_s
        return masks, runs

    def decode(self, masks, runs) -> tuple:
        """Decode the joined result in parallel: returns
        (file_codes, runs_map) merged across shard blocks —
        ``file_codes``: file index → {pattern col: [(seg offset,
        blockmask)]}; ``runs_map``: file index → {run-spec idx}."""
        from ..runtime.hostpool import map_in_pool
        from ..secret.metrics import SECRET_METRICS
        seg_file, seg_pos = self.seg_file, self.seg_pos
        blocks = [(r0, min(r0 + self.rps, self.n_valid))
                  for r0 in range(0, self.n_valid, self.rps)]

        def decode_block(span):
            row0, row1 = span
            codes: dict = {}
            m = masks[row0:row1]
            for si, ci in zip(*np.nonzero(m)):
                fidx = seg_file[row0 + int(si)]
                if fidx < 0:
                    continue              # shard-padding row
                codes.setdefault(fidx, {}).setdefault(
                    int(ci), []).append(
                        (seg_pos[row0 + int(si)],
                         int(m[si, ci])))
            rmap: dict = {}
            for si, sp in zip(*np.nonzero(runs[row0:row1])):
                fidx = seg_file[row0 + int(si)]
                if fidx < 0:
                    continue
                rmap.setdefault(fidx, set()).add(int(sp))
            return codes, rmap

        SECRET_METRICS.inc("decode_tasks", len(blocks))
        file_codes: dict = {}
        runs_map: dict = {}
        for codes, rmap in map_in_pool(decode_block, blocks):
            # a file lives wholly inside one shard block, so
            # per-file entries never interleave across partials
            file_codes.update(codes)
            for fidx, s in rmap.items():
                runs_map.setdefault(fidx, set()).update(s)
        return file_codes, runs_map


# ---------------------------------------------------------------------
# round-5 shard_map literal kernel (kept for ops-level parity tests)
# ---------------------------------------------------------------------

@functools.lru_cache(maxsize=8)
def _build_blockmask(mesh, L: int):
    import jax
    from jax.sharding import PartitionSpec as P

    def local(segments, lo_c, hi_c, lo_m, hi_m):
        masks = code_blockmask_impl(segments, lo_c, hi_c, lo_m, hi_m)
        return jax.lax.all_gather(masks, RULES_AXIS, axis=1, tiled=True)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(RULES_AXIS), P(RULES_AXIS),
                  P(RULES_AXIS), P(RULES_AXIS)),
        out_specs=P(DATA_AXIS, None),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_blockmask(mesh, segments: np.ndarray, codes: tuple)\
        -> np.ndarray:
    """[B, L] segments × padded code arrays → [B, Kp] uint32 masks.

    Codes are padded so each rules-shard holds a CODE_CHUNK multiple;
    pad codes never match real text (zero code + full mask)."""
    d, r = mesh_axis_sizes(mesh)
    B, L = segments.shape
    K = codes[0].shape[0]
    Bp = pad_to_multiple(B, d)
    Kp = pad_to_multiple(K, r * CODE_CHUNK)

    if Bp != B:
        segments = np.concatenate(
            [segments, np.zeros((Bp - B, L), segments.dtype)])
    padded = []
    for i, a in enumerate(codes):
        if Kp != K:
            pad = np.zeros(Kp - K, a.dtype)
            if i >= 2:
                pad = pad + np.uint32(0xFFFFFFFF)
            a = np.concatenate([np.asarray(a), pad])
        padded.append(np.asarray(a))

    fn = _build_blockmask(mesh, L)
    masks = np.asarray(fn(segments, *padded))
    return masks[:B, :K]
