"""Bounded tar/gzip primitives for hostile archives.

Everything here assumes the input is attacker-controlled and trades
a little ceremony for three invariants:

1. **no unbounded materialization** — gzip output is produced in
   64 KiB chunks and every chunk is charged against the budget
   before the next is read, so a decompression bomb trips the byte
   budget (usually the ratio tripwire) instead of OOMing the host;
2. **no path escapes** — entry names are normalized with posix
   ``normpath`` and anything that still reaches outside the archive
   root (``..`` segments) or cannot be represented (undecodable
   bytes, absurd length/depth) is rejected;
3. **typed failure** — every malformed/truncated stream surfaces as
   :class:`MalformedArchiveError` (a ValueError), never a raw
   ``tarfile``/``gzip``/``struct`` exception, so the per-slot
   degraded-mode handling stays uniform.

With ``budget=None`` (``--no-ingest-guards``) the helpers fall back
to the historical unbounded behavior — the differential baseline.

A layer the caller holds whole is a :class:`LayerTar`: the walker
indexes its bytes in one pass (:func:`index_tar`) and opens
``tarfile`` only for a layer with a header the index does not take,
so a hostile layer meets ``tarfile``'s own checks, as it always did.
"""

from __future__ import annotations

import gzip
import io
import os
import posixpath
import re
import tarfile
import zlib
from functools import partial
from typing import Callable, Optional

import numpy as np

from .budget import (GUARD_METRICS, MalformedArchiveError,
                     ResourceBudget)

GZIP_MAGIC = b"\x1f\x8b"
_CHUNK = 1 << 16

# OCI digest shape: algorithm + hex. Digests name blob FILES in a
# layout ("blobs/<algo>/<hex>"), so anything looser is a path — a
# manifest carrying "sha256:../../../etc/secret" must die here, not
# become an arbitrary host-file read
_DIGEST_RE = re.compile(r"^[a-z0-9]+:[0-9a-fA-F]{32,128}$")


def validate_digest(digest: str) -> str:
    """Reject OCI digest strings that could not be a plain
    ``algo:hex`` pair (traversal, separators, empty)."""
    if not _DIGEST_RE.match(digest or ""):
        raise MalformedArchiveError(
            f"invalid OCI digest {digest!r}")
    return digest

# exception classes that mean "the archive bytes are broken", to be
# re-raised as MalformedArchiveError with context
_ARCHIVE_ERRORS = (tarfile.TarError, gzip.BadGzipFile, zlib.error,
                   EOFError)


def is_gzip(data: bytes) -> bool:
    return data[:2] == GZIP_MAGIC


def decompress_bounded(data: bytes,
                       budget: Optional[ResourceBudget]) -> bytes:
    """Gzip-decompress ``data`` chunk-wise, charging the budget per
    chunk (ratio tripwire armed with the compressed size). Truncated
    or corrupt streams raise MalformedArchiveError."""
    if budget is None:
        try:
            return gzip.decompress(data)
        except _ARCHIVE_ERRORS as e:
            raise MalformedArchiveError(
                f"corrupt gzip stream: {e}") from e
    out = io.BytesIO()
    try:
        with gzip.GzipFile(fileobj=io.BytesIO(data)) as gz:
            while True:
                budget.check_deadline()
                chunk = gz.read(_CHUNK)
                if not chunk:
                    break
                budget.charge_decompressed(
                    len(chunk), compressed_total=len(data))
                out.write(chunk)
    except _ARCHIVE_ERRORS as e:
        budget.malformed(f"truncated or corrupt gzip stream: {e}")
    return out.getvalue()


def _open_tar(data: bytes, budget: Optional[ResourceBudget] = None) \
        -> tarfile.TarFile:
    """``tarfile``'s view of a layer's bytes; bytes it refuses are a
    typed malformed-archive trip."""
    try:
        return tarfile.open(fileobj=io.BytesIO(data))
    except _ARCHIVE_ERRORS as e:
        if budget is not None:
            budget.malformed(f"unreadable layer tar: {e}")
        raise MalformedArchiveError(
            f"unreadable layer tar: {e}") from e


def open_layer_tar(data: bytes,
                   budget: Optional[ResourceBudget] = None) \
        -> "LayerTar":
    """An uncompressed layer the caller holds whole → ``LayerTar``,
    whose bytes ``tarfile`` refuses raise ``MalformedArchiveError``
    (``unreadable layer tar``) here."""
    return LayerTar(data, partial(_open_tar, budget=budget))


def open_layer_bytes(data: bytes,
                     budget: Optional[ResourceBudget] = None) \
        -> "LayerTar":
    """Layer blob bytes (tar or tar.gz) → ``LayerTar``, bounded. A
    plain (uncompressed) tar is charged at face value; a gzip member
    is streamed through :func:`decompress_bounded`."""
    if is_gzip(data):
        data = decompress_bounded(data, budget)
    elif budget is not None:
        budget.charge_decompressed(len(data))
    return open_layer_tar(data, budget)


# --- a layer's tar, indexed in one pass over its bytes ---------------

BLOCK = tarfile.BLOCKSIZE
# types whose data blocks follow the header (tarfile.REGULAR_TYPES
# less sparse), and the other types the index takes: tarfile skips no
# data after those whatever their size field says
_REGULAR = (tarfile.REGTYPE, tarfile.AREGTYPE, tarfile.CONTTYPE)
_PLAIN = _REGULAR + (tarfile.LNKTYPE, tarfile.SYMTYPE, tarfile.CHRTYPE,
                     tarfile.BLKTYPE, tarfile.DIRTYPE, tarfile.FIFOTYPE)
# extension headers the index takes: each holds a field of the member
# whose header follows it
_EXTENSIONS = (tarfile.XHDTYPE, tarfile.GNUTYPE_LONGNAME,
               tarfile.GNUTYPE_LONGLINK)
_MAX_EXTENSIONS = 8          # a longer chain is tarfile's to judge
# a number field as tarfile's nti reads it from octal digits: up to
# its first NUL, spaces round the digits; anything else (base-256, a
# sign, other whitespace) is left to tarfile
_OCTAL = re.compile(rb" *([0-7]*) *(?:\x00|\Z)")
_PAX_LENGTH = re.compile(rb"([0-9]{1,20}) ")
_ENC = tarfile.ENCODING


def _octals(raw: bytes, width: int) -> bool:
    return all(_OCTAL.match(raw, i, i + width) is not None
               for i in range(0, len(raw), width))


def _nts(data: bytes, a: int, b: int) -> str:
    """tarfile's ``nts``: up to the first NUL, decoded as a TarFile
    decodes names."""
    end = data.find(0, a, b)
    return data[a:b if end < 0 else end].decode(_ENC, "surrogateescape")


def _block(n: int) -> int:
    return -(-n // BLOCK) * BLOCK


def _pax_field(raw: bytes, encoding: str) -> str:
    # tarfile's _decode_pax_field: strict UTF-8 first
    try:
        return raw.decode("utf-8")
    except UnicodeDecodeError:
        return raw.decode(encoding, "surrogateescape")


def _pax(buf: bytes) -> Optional[dict]:
    """A PAX ``x`` header's records framed as ``TarInfo._proc_pax``
    frames them → {"path", "linkpath", "size"}: whichever it sets,
    the last record of a keyword winning. None where the framing
    fails, or a record is one the index does not apply: sparse maps
    (``GNU.sparse.*``) and ``hdrcharset``."""
    out = {}
    pos = 0
    n = len(buf)
    while n > pos and buf[pos] != 0:
        m = _PAX_LENGTH.match(buf, pos)
        if m is None:
            return None
        length = int(m.group(1))
        if length < 5 or pos + length > n:
            return None
        end = pos + length - 1
        key, eq, value = buf[m.end(1) + 1:end].partition(b"=")
        if not key or eq != b"=" or buf[end] != 0x0A:
            return None
        if key == b"path" or key == b"linkpath":
            out[key.decode()] = _pax_field(value, _ENC)
        elif key == b"size":
            try:
                out["size"] = int(_pax_field(value, "utf-8"))
            except ValueError:
                out["size"] = 0        # as _apply_pax_info reads it
        elif key == b"hdrcharset" or key.startswith(b"GNU.sparse."):
            return None
        pos += length
    return out


def _member(data: bytes, off: int, heads: list, sums: list,
            memo: dict) -> Optional[tuple]:
    """The member whose headers start at ``off`` as
    ``TarInfo.fromtarfile`` builds it → ``(name, type, size,
    linkname, data_offset, next_offset)``, its checksums left to
    :func:`_checksums_ok`; None where ``tarfile`` has to judge it."""
    n = len(data)
    exts = []
    while True:
        # every number field TarInfo.frombuf parses must read as
        # octal digits; the ones that repeat from member to member
        # (mode, uid and gid; mtime; the device numbers) are checked
        # once a layer per value
        size = _OCTAL.match(data, off + 124, off + 136)
        chksum = _OCTAL.match(data, off + 148, off + 156)
        if size is None or chksum is None:
            return None
        same = (data[off + 100:off + 124], data[off + 136:off + 148],
                data[off + 329:off + 345])
        ok = memo.get(same)
        if ok is None:
            ok = memo[same] = _octals(same[0], 8) and \
                _octals(same[1], 12) and _octals(same[2], 8)
        if not ok:
            return None
        size = int(size.group(1) or b"0", 8)
        heads.append(off)
        sums.append(int(chksum.group(1) or b"0", 8))
        typ = data[off + 156:off + 157]
        if typ not in _EXTENSIONS:
            break
        end = off + BLOCK + _block(size)
        if end + BLOCK > n or len(exts) == _MAX_EXTENSIONS:
            return None
        exts.append((typ, data[off + BLOCK:end]))
        off = end
    if typ not in _PLAIN:
        return None                 # sparse, global, Solaris, unknown
    # TarInfo.frombuf, then _proc_builtin
    name = _nts(data, off, off + 100)
    if typ == tarfile.AREGTYPE and name.endswith("/"):
        typ = tarfile.DIRTYPE       # a V7 directory
    isdir = typ == tarfile.DIRTYPE
    if isdir:
        name = name.rstrip("/")
    if data[off + 345]:             # the ustar prefix
        name = _nts(data, off + 345, off + 500) + "/" + name
        if isdir:
            name = name.rstrip("/")
    link = typ == tarfile.SYMTYPE or typ == tarfile.LNKTYPE
    linkname = _nts(data, off + 157, off + 257) if link else ""
    data_off = off + BLOCK
    regular = typ in _REGULAR
    nxt = data_off + _block(size) if regular else data_off
    # the extension headers, the one nearest the member first, as
    # the nested _proc_gnulong / _proc_pax calls apply them
    for ext, buf in reversed(exts):
        if ext == tarfile.XHDTYPE:
            pax = _pax(buf)
            if pax is None:
                return None
            if "path" in pax:
                name = pax["path"].rstrip("/")
            if "linkpath" in pax and link:
                linkname = pax["linkpath"]
            if "size" in pax:
                size = pax["size"]
                nxt = data_off + _block(size) if regular else data_off
        else:
            value = _nts(buf, 0, len(buf))
            if ext == tarfile.GNUTYPE_LONGNAME:
                name = value
            elif link:
                linkname = value
            if isdir:
                name = name.removesuffix("/")
    if size < 0:
        return None                 # it would walk back over headers
    return name, typ, size, linkname, data_off, nxt


def _checksums_ok(data: bytes, heads: list, sums: list) -> bool:
    """Each header's checksum field against both sums
    ``tarfile.calc_chksums`` accepts, unsigned and signed, over a
    batch of headers at once."""
    if not heads:
        return True
    blocks = np.frombuffer(data, np.uint8, len(data) // BLOCK * BLOCK)
    rows = blocks.reshape(-1, BLOCK)[np.asarray(heads) // BLOCK]
    rows[:, 148:156] = 0x20          # the field, read as spaces
    unsigned = rows.sum(axis=1, dtype=np.int64)
    signed = unsigned - 256 * (rows >= 0x80).sum(axis=1)
    want = np.asarray(sums, dtype=np.int64)
    return bool(((want == unsigned) | (want == signed)).all())


# headers whose checksums are summed together: what a batch copies
# (512 bytes a header) stays the same however many members a layer has
_CHECK_BATCH = 1024


def index_tar(data: bytes, limit: Optional[int] = None) \
        -> Optional[list]:
    """A layer's members in one pass over its bytes, 512-byte header
    by header → ``[(name, type, size, linkname, data_offset)]``, the
    fields as ``tarfile`` gives them (its name decoding, V7
    directories, the ustar prefix, PAX ``path``/``linkpath``/``size``
    records, GNU long names and links; a link name for a link only),
    one entry for each member iterating a ``TarFile`` yields. It stops
    after ``limit`` members, and gives those.

    None where ``tarfile`` has to judge the layer: a checksum that
    fails, a number field that is not plain octal (base-256), a
    sparse member, a global or unknown header type, a header or a
    member that runs past the end, bytes that are no tar."""
    n = len(data)
    out = []
    heads: list = []
    sums: list = []
    memo: dict = {}
    off = 0
    while limit is None or len(out) < limit:
        if off + BLOCK > n:
            if off and off == n:
                break               # tarfile reads an empty header
            return None
        if data[off] == 0 and data.count(0, off, off + BLOCK) == BLOCK:
            break                   # the end-of-archive block
        m = _member(data, off, heads, sums, memo)
        if m is None:
            return None
        out.append(m[:5])
        off = m[5]
        if len(heads) >= _CHECK_BATCH:
            if not _checksums_ok(data, heads, sums):
                return None
            heads.clear()
            sums.clear()
    return out if _checksums_ok(data, heads, sums) else None


def _plain_tar(data: bytes) -> tarfile.TarFile:
    return tarfile.open(fileobj=io.BytesIO(data))


class LayerTar:
    """A layer's uncompressed tar as the openers hold it: its bytes
    (``data``), which the walker indexes (:meth:`index`), and
    ``tarfile``'s view of them (``tar``), opened only where the walk
    falls back to it. ``open_tar(data)`` opens it as the opener always
    has; it is called at once where the index cannot read the first
    member, so that bytes ``tarfile`` refuses raise what they raised,
    where they raised it. A context manager, as the ``TarFile`` was."""

    __slots__ = ("data", "indexed", "_open", "_tf")

    def __init__(self, data: bytes, open_tar: Callable = _plain_tar):
        self.data = data
        self.indexed = False
        self._open = open_tar
        self._tf = None
        if index_tar(data, 1) is None:
            self._tf = open_tar(data)

    @property
    def tar(self) -> tarfile.TarFile:
        if self._tf is None:
            self._tf = self._open(self.data)
        return self._tf

    def index(self, limit: Optional[int] = None) -> Optional[list]:
        """:func:`index_tar` of the layer, or None where ``tarfile``
        walks it: a header the index does not take, or more than
        ``limit`` members (the entries a budget has left, so that a
        flood of headers is parsed no further than ``tarfile`` parses
        it before the budget trips); ``indexed`` says which."""
        members = None
        if self._tf is None:
            members = index_tar(self.data,
                                None if limit is None else limit + 1)
            if members is not None and limit is not None and \
                    len(members) > limit:
                members = None
        self.indexed = members is not None
        return members

    def close(self) -> None:
        if self._tf is not None:
            self._tf.close()

    def __enter__(self) -> "LayerTar":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def has_traversal(path: str) -> bool:
    """True when the already-normpath'd path still escapes the
    archive root."""
    return path == ".." or path.startswith("../") or \
        "/../" in path or path.endswith("/..")


def link_escapes(name: str, linkname: str, hard: bool) -> bool:
    """True when a symlink (or, ``hard``, a hardlink) member named
    ``name`` points outside the archive root. Absolute *symlink*
    targets are normal in real images (``/usr/bin/sh →
    /bin/busybox``) and are resolved in-archive by readers, so only
    relative ``..`` escapes and absolute *hardlink* targets count."""
    target = linkname or ""
    if hard and target.startswith("/"):
        return True
    if target.startswith("/"):
        return False
    base = posixpath.dirname(posixpath.normpath(name).lstrip("/"))
    joined = posixpath.normpath(posixpath.join(base, target))
    return has_traversal(joined)


def read_member(tf: tarfile.TarFile, member: tarfile.TarInfo,
                budget: Optional[ResourceBudget] = None,
                checked: bool = True) -> bytes:
    """Read one member's payload; truncated data raises
    MalformedArchiveError instead of a raw tarfile error. Pass
    ``checked=False`` when the caller has NOT already size-checked
    the member (the walker checks at collect time)."""
    if budget is not None and not checked:
        budget.check_deadline()
        budget.check_file_size(member.size, member.name)
    try:
        f = tf.extractfile(member)
        data = f.read() if f is not None else b""
    except _ARCHIVE_ERRORS + (OSError,) as e:
        raise MalformedArchiveError(
            f"truncated archive reading {member.name!r}: {e}") from e
    if len(data) != member.size:
        raise MalformedArchiveError(
            f"truncated member {member.name!r}: "
            f"{len(data)} of {member.size} bytes")
    return data


def safe_extract_db_archive(blob: bytes, dest_dir: str,
                            budget: Optional[ResourceBudget] = None,
                            wanted: tuple = ("trivy.db",
                                             "metadata.json")) -> list:
    """Extract the advisory-DB tgz into ``dest_dir``: only regular
    files whose *basename* is in ``wanted`` (flattened — traversal
    is impossible by construction), link members rejected, reads
    bounded. Returns the basenames written."""
    raw = decompress_bounded(blob, budget)
    try:
        tf = tarfile.open(fileobj=io.BytesIO(raw))
    except _ARCHIVE_ERRORS as e:
        raise MalformedArchiveError(
            f"unreadable DB archive: {e}") from e
    written = []
    with tf:
        for member in tf:
            if budget is not None:
                budget.charge_entry()
            name = os.path.basename(member.name)
            if name not in wanted:
                continue
            if member.issym() or member.islnk():
                raise MalformedArchiveError(
                    f"DB archive member {member.name!r} is a link")
            if not member.isfile():
                continue
            data = read_member(tf, member, budget, checked=False)
            with open(os.path.join(dest_dir, name), "wb") as out:
                out.write(data)
            written.append(name)
    return written
