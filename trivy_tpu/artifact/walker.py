"""Layer-tar and filesystem walkers (reference: pkg/fanal/walker).

Tar walker semantics (tar.go:33-125): iterate entries, collect
whiteout files (``.wh.<name>``) and opaque dirs (``.wh..wh..opq``),
skip non-regular files; paths are cleaned, no leading slash.

How a layer is walked: the layer openers hold a layer's bytes whole
and return them as a ``guard.safetar.LayerTar``; ``collect_layer_tar``
takes its members from ``index_tar``, one pass over those bytes, and
slices a regular file's bytes out of them. A layer with a header the
index does not take (a bad checksum, a base-256 number, a sparse
member, a global or unknown header type, a header or member past the
end, no tar at all) is walked by ``tarfile`` as before, whole; the
bytes decide, a layer at a time, and the rules below are the same
either way. So does a layer with more members than the budget has
entries left: the index stops one past them, and ``tarfile`` trips on
the entry count where it always did.

Hostile-input posture (docs/robustness.md): entry names whose
normpath still contains ``..`` segments are never kept — without a
budget they are skipped (and counted), with a budget the archive is
quarantined via :class:`MalformedArchiveError`; entry counts,
per-file sizes and the ingest deadline are charged against the
per-scan :class:`ResourceBudget` when one is threaded in.
"""

from __future__ import annotations

import os
import posixpath
import tarfile
from functools import partial
from typing import Callable, Optional

from ..guard.budget import (GUARD_METRICS, MalformedArchiveError,
                            ResourceBudget)
from ..guard.safetar import (LayerTar, has_traversal, link_escapes,
                             read_member)

WH_PREFIX = ".wh."
OPQ = ".wh..wh..opq"

SKIP_SYSTEM_DIRS = ["proc", "sys", "dev"]


def collect_layer_tar(tf: LayerTar,
                      budget: Optional[ResourceBudget] = None) -> tuple:
    """Eagerly walk a layer tar: ([(path, size, read_fn)], opq_dirs,
    wh_files).

    ``tf`` is the ``LayerTar`` a layer opener returns. Its members
    come from ``index_tar``, one pass over the bytes the opener holds,
    and a regular file's ``read_fn`` slices them; no ``TarInfo`` or
    file object is built. Where a header is one the index does not
    take (a bad checksum, a base-256 number, a sparse member, a global
    or unknown header type, a header or member that runs past the
    end, bytes that are no tar), or the layer has more members than
    the budget's entries left, the whole layer is walked by
    ``tarfile`` as before (``LayerTar.indexed`` stays False), so that
    a hostile layer trips what it tripped, where it tripped it. The
    rules below run on (name, type, size, linkname) either way, and
    the budget is charged one entry a member."""
    recs = tf.index(budget.remaining_entries()
                    if budget is not None else None)
    if recs is not None:
        data = tf.data

        def read_fn(off, size):
            return partial(data.__getitem__, slice(off, off + size))
    else:
        tar = tf.tar
        recs = _tar_members(tar, budget)

        def read_fn(member, size):
            return _tar_reader(tar, member, budget)
    files = []
    opq_dirs: list = []
    wh_files: list = []
    # hot-loop setup: hoist the limits and keep the per-entry guard
    # cost to an increment plus gated (mostly-false) cheap checks
    # (pytest -m hostile holds clean slots byte-identical either way)
    lim = budget.limits if budget is not None else None
    max_file = lim.max_file_bytes if lim is not None else 0
    # every path component costs ≥2 name bytes ("a/"), so a name
    # shorter than 2·max_depth cannot exceed the depth limit —
    # count("/") only runs on names long enough to matter
    depth_gate = 2 * lim.max_depth if lim is not None else 0
    seen = 0
    for nm, typ, size, linkname, ref in recs:
        # strip the leading "./" / "/" PREFIX only — lstrip would eat
        # the dot of dotfiles (./.env → env) and break .wh. detection
        path = posixpath.normpath(nm)
        if path.startswith("/"):
            path = path.lstrip("/")
        if budget is not None:
            seen += 1
            if not (seen & 31):
                budget.charge_entries(32)
        if not path or path == ".":
            continue
        if ".." in path and has_traversal(path):
            GUARD_METRICS.inc("traversal_rejected")
            if budget is not None:
                budget.malformed(f"path traversal in entry {nm!r}")
            continue                 # unguarded: reject, keep walking
        if lim is not None:
            if len(nm) > lim.max_name_bytes:
                budget.malformed(
                    f"entry name longer than "
                    f"{lim.max_name_bytes} bytes")
            if not nm.isascii():
                try:
                    nm.encode("utf-8")
                except UnicodeEncodeError:
                    # tarfile decodes undecodable bytes with
                    # surrogateescape; such names cannot round-trip
                    # into reports — structurally hostile
                    budget.malformed(
                        f"undecodable (non-UTF-8) entry name {nm!r}")
            if len(nm) > depth_gate and \
                    path.count("/") + 1 > lim.max_depth:
                budget.exceeded(
                    f"entry {nm!r} deeper than "
                    f"{lim.max_depth} components")
        file_dir, file_name = posixpath.split(path)
        if file_name == OPQ:
            opq_dirs.append(file_dir)
            continue
        if file_name.startswith(WH_PREFIX):
            target = posixpath.normpath(posixpath.join(
                file_dir, file_name[len(WH_PREFIX):]))
            if target == "." or \
                    (".." in target and has_traversal(target)):
                # a whiteout that "deletes" a path outside the
                # archive root is as hostile as a traversal entry
                GUARD_METRICS.inc("traversal_rejected")
                if budget is not None:
                    budget.malformed(
                        f"path traversal in whiteout {path!r}")
                continue
            wh_files.append(target)
            continue
        if typ in tarfile.REGULAR_TYPES:
            if _skip_system(path):
                continue
            if budget is not None and \
                    (size < 0 or size > max_file):
                budget.check_file_size(size, path)
            files.append((path, size, read_fn(ref, size)))
            continue
        if typ == tarfile.SYMTYPE or typ == tarfile.LNKTYPE:
            if link_escapes(nm, linkname, typ == tarfile.LNKTYPE):
                # never followed (only regular files are read), but
                # worth surfacing: count, and report the slot
                # degraded when a budget is watching
                GUARD_METRICS.inc("link_escapes")
                if budget is not None:
                    budget.note(
                        "malformed-archive",
                        f"link member {path!r} escapes the "
                        f"archive root ({linkname!r})")
    if budget is not None:
        budget.charge_entries(seen & 31)
    return files, opq_dirs, wh_files


def _tar_members(tf: tarfile.TarFile,
                 budget: Optional[ResourceBudget] = None):
    """``tarfile``'s members as ``index_tar`` gives them, (name,
    type, size, linkname, TarInfo), one at a time: a layer that
    breaks mid-iteration trips where it breaks."""
    members = iter(tf)
    while True:
        try:
            member = next(members)
        except StopIteration:
            return
        except tarfile.TarError as e:
            # truncated/corrupt layer surfacing mid-iteration: a
            # typed malformed-archive trip, never a raw tarfile
            # error past the artifact boundary
            if budget is not None:
                budget.malformed(
                    f"truncated or corrupt layer tar: {e}")
            raise MalformedArchiveError(
                f"truncated or corrupt layer tar: {e}") from e
        yield (member.name, member.type, member.size,
               member.linkname, member)


def _tar_reader(tf: tarfile.TarFile, member,
                budget: Optional[ResourceBudget] = None) -> Callable:
    def read() -> bytes:
        if budget is not None:
            return read_member(tf, member, budget)
        f = tf.extractfile(member)
        return f.read() if f is not None else b""
    return read


def _skip_system(path: str) -> bool:
    top = path.split("/", 1)[0]
    return top in SKIP_SYSTEM_DIRS


def _clean_skip(paths) -> set:
    """walk.go:27-38: skip paths are cleaned and matched with the
    leading '/' trimmed — against the path as WALKED (root-joined for
    fs scans), not the root-relative analysis path."""
    out = set()
    for p in paths:
        p = posixpath.normpath(p.replace(os.sep, "/")).lstrip("/")
        out.add(p)
    return out


def iter_fs(root: str, skip_dirs: list = (),
            skip_files: list = (),
            budget: Optional[ResourceBudget] = None,
            gate: Optional[Callable] = None):
    """Directory walk → (rel_path, size, read_fn, wanted), one at a
    time, so that a caller that streams (``LocalFSArtifact``) has its
    first files before the tree is walked to its end (reference:
    walker/fs.go; shared skip logic walk.go:47-62). Skip lists match
    both the cwd-relative walked path (reference behavior for
    relative scan roots) and the root-relative path (convenience).
    Symlinks are never followed, to a directory or to a file, so a
    link farm cannot pull the walk outside ``root``; a budget
    additionally bounds file count, wall clock (both asked before a
    file is touched) and the size of every file that is opened.
    The order is ``os.walk``'s (a directory's files, names sorted,
    then its directories as the file system lists them).

    ``gate(rel_path, size, among=None)`` says who wants a file,
    without its content (``AnalyzerGroup.wanted``; a ``size`` of
    None asks who would at some size, and those are asked again,
    ``among``, once the size is known): its answer comes back as
    ``wanted``. Without a gate every file is wanted at any size and
    ``wanted`` is None.

    Which system calls a file costs, and who makes them: all are
    made here, on the thread that iterates, and the ones that take a
    path take a short one (what a path costs by its length, and why
    nothing is read ahead of the walk: PERF.md section 6, PR 34). A
    directory is opened once, by path, listed from its descriptor
    (the listing says what is a regular file), and closed before the
    walk goes down: one directory and one file are open at a time
    whatever the depth. A file that nobody wants at any size costs
    no call (its ``size`` is None: nobody asked, so the budget's
    bound on a file's size is not asked of it either; it is never
    read). Any other file is opened from the directory's descriptor,
    a path of one component, and its size is the open file's
    (``fstat``); a file that its size rules out is closed unread; a
    wanted file is read from that descriptor by its ``read_fn`` (the
    reads to its end, ``close``; a ``read_fn`` called again, or
    after the walk has moved on, reads by path). A file that cannot
    be opened is asked ``lstat``: gone, and the walk raises here;
    there and wanted, and its ``read_fn`` raises what ``open``
    raised.

    What a file costs besides: one relative path, built from its
    directory's; the skip lists are not asked where they are empty,
    and a file's path from the root is built only when the file has
    to be read by it."""
    skip_dirs = _clean_skip(skip_dirs)
    skip_files = _clean_skip(skip_files)
    root_prefix = posixpath.normpath(
        root.replace(os.sep, "/")).lstrip("/")

    def skipped(rel: str, skips: set) -> bool:
        return rel in skips or \
            posixpath.join(root_prefix, rel) in skips

    def walk(dirpath: str, rel_dir: str):
        # the directory's own descriptor: a path from it is one
        # component, and a path's price goes by its components
        # (os.fwalk's way; here the files' order is the walk's)
        dfd = None
        try:
            dfd = os.open(dirpath, os.O_RDONLY | os.O_DIRECTORY)
            with os.scandir(dfd) as it:
                entries = list(it)
        except OSError:
            if dfd is not None:
                os.close(dfd)
            return      # as os.walk: what cannot be listed is passed
        try:
            files = [e for e in entries
                     if e.is_file(follow_symlinks=False)]
            prefix = rel_dir + "/" if rel_dir else ""
            for e in sorted(files, key=lambda e: e.name):
                name = e.name
                rel = prefix + name
                if skip_files and skipped(rel, skip_files):
                    continue
                if budget is not None:   # before the file is touched
                    budget.check_deadline()
                    budget.charge_entry()
                wanted = gate(rel, None) if gate is not None else None
                opened = None
                if gate is not None and not wanted:
                    size, read = None, partial(_read_path, dirpath,
                                               name, 0)
                else:
                    try:
                        read = opened = _OpenFile(name, dfd, dirpath)
                        size = opened.size
                    except OSError as err:
                        size = e.stat(follow_symlinks=False).st_size
                        read = _raiser(err)
                    if gate is not None:
                        wanted = gate(rel, size, wanted)
                try:
                    if budget is not None and size is not None:
                        budget.check_file_size(size, rel)
                    if gate is not None and not wanted \
                            and opened is not None:
                        opened.close()      # its size rules it out
                    yield rel, size, read, wanted
                finally:
                    if opened is not None:
                        opened.close()
        finally:
            os.close(dfd)
        # by path and with no descriptor held: a tree's depth costs
        # the walk none
        for e in entries:
            if e.is_dir(follow_symlinks=False):
                rel = posixpath.join(rel_dir, e.name)
                if not (skip_dirs and skipped(rel, skip_dirs)):
                    yield from walk(os.path.join(dirpath, e.name),
                                    rel)

    yield from walk(root, "")


def _read_fd(fd: int, size: int) -> bytes:
    """The rest of an open file by ``os.read``: the first read asks
    for a byte more than ``size``, so a file that has not grown is
    read in one call and the empty read that says it ended. A read
    may come back short before the end (one call gives 0x7ffff000
    bytes at the most; a network or FUSE file system gives what it
    has), so only an empty read ends the loop."""
    chunks = []
    want = size + 1
    while True:
        data = os.read(fd, want)
        if not data:
            break
        chunks.append(data)
        want = max(want - len(data), 1 << 20)
    return b"".join(chunks)


def _read_path(dirpath: str, name: str, size: int) -> bytes:
    """The file's bytes by path: ``open``, the reads to its end and
    ``close`` (four system calls where a buffered ``open().read()``
    makes six)."""
    fd = os.open(os.path.join(dirpath, name), os.O_RDONLY)
    try:
        return _read_fd(fd, size)
    finally:
        os.close(fd)


class _OpenFile:
    """``read_fn`` of a file the walk has opened: its bytes from the
    descriptor, which is closed with the read or when the walk moves
    on; after that, by path like any other file's."""

    __slots__ = ("fd", "dirpath", "name", "size")

    def __init__(self, name: str, dir_fd: int, dirpath: str):
        self.dirpath = dirpath
        self.name = name
        self.fd = os.open(name, os.O_RDONLY, dir_fd=dir_fd)
        try:
            self.size = os.fstat(self.fd).st_size
        except OSError:
            self.close()
            raise

    def __call__(self) -> bytes:
        if self.fd is None:
            return _read_path(self.dirpath, self.name, self.size)
        try:
            return _read_fd(self.fd, self.size)
        finally:
            self.close()

    def close(self) -> None:
        if self.fd is not None:
            fd, self.fd = self.fd, None
            os.close(fd)


def _raiser(err: OSError) -> Callable:
    """``read_fn`` of a file that is there and cannot be opened."""
    def read() -> bytes:
        raise err
    return read
