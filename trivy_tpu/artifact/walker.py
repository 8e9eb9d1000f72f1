"""Layer-tar and filesystem walkers (reference: pkg/fanal/walker).

Tar walker semantics (tar.go:33-125): iterate entries, collect
whiteout files (``.wh.<name>``) and opaque dirs (``.wh..wh..opq``),
skip non-regular files; paths are cleaned, no leading slash.

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
from typing import Callable, Optional

from ..guard.budget import GUARD_METRICS, ResourceBudget
from ..guard.safetar import has_traversal, link_escapes, read_member

WH_PREFIX = ".wh."
OPQ = ".wh..wh..opq"

SKIP_SYSTEM_DIRS = ["proc", "sys", "dev"]


def collect_layer_tar(tf: tarfile.TarFile,
                      budget: Optional[ResourceBudget] = None) \
        -> tuple:
    """Eagerly walk a layer tar: ([(path, size, read_fn)], opq_dirs,
    wh_files)."""
    from ..guard.budget import MalformedArchiveError
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
    members = iter(tf)
    while True:
        try:
            member = next(members)
        except StopIteration:
            break
        except tarfile.TarError as e:
            # truncated/corrupt layer surfacing mid-iteration: a
            # typed malformed-archive trip, never a raw tarfile
            # error past the artifact boundary
            if budget is not None:
                budget.malformed(
                    f"truncated or corrupt layer tar: {e}")
            raise MalformedArchiveError(
                f"truncated or corrupt layer tar: {e}") from e
        nm = member.name
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
        if member.isreg():
            if _skip_system(path):
                continue
            size = member.size
            if budget is not None and \
                    (size < 0 or size > max_file):
                budget.check_file_size(size, path)
            files.append((path, size,
                          _tar_reader(tf, member, budget)))
            continue
        if member.issym() or member.islnk():
            if link_escapes(member):
                # never followed (only regular files are read), but
                # worth surfacing: count, and report the slot
                # degraded when a budget is watching
                GUARD_METRICS.inc("link_escapes")
                if budget is not None:
                    budget.note(
                        "malformed-archive",
                        f"link member {path!r} escapes the "
                        f"archive root ({member.linkname!r})")
    if budget is not None:
        budget.charge_entries(seen & 31)
    return files, opq_dirs, wh_files


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
            budget: Optional[ResourceBudget] = None):
    """Directory walk → (rel_path, size, read_fn), one at a time, so
    that a caller that streams (``LocalFSArtifact``) has its first
    files before the tree is walked to its end (reference:
    walker/fs.go; shared skip logic walk.go:47-62). Skip lists match
    both the cwd-relative walked path (reference behavior for
    relative scan roots) and the root-relative path (convenience).
    Symlinks are never followed, to a directory or to a file, so a
    link farm cannot pull the walk outside ``root``; a budget
    additionally bounds file count, per-file size, and wall clock.
    The order is ``os.walk``'s (a directory's files, names sorted,
    then its directories as the file system lists them); the
    directory's own listing says what is a regular file, so a file
    costs one ``lstat`` for its size where ``os.walk`` and
    ``os.path`` cost three (a tree of 40,000 files on a slow file
    system is walked in seconds of system calls)."""
    skip_dirs = _clean_skip(skip_dirs)
    skip_files = _clean_skip(skip_files)
    root_prefix = posixpath.normpath(
        root.replace(os.sep, "/")).lstrip("/")

    def skipped(rel: str, skips: set) -> bool:
        return rel in skips or \
            posixpath.join(root_prefix, rel) in skips

    def walk(dirpath: str, rel_dir: str):
        try:
            with os.scandir(dirpath) as it:
                entries = list(it)
        except OSError:
            return      # as os.walk: what cannot be listed is passed
        files = [e for e in entries
                 if e.is_file(follow_symlinks=False)]
        for e in sorted(files, key=lambda e: e.name):
            rel = posixpath.join(rel_dir, e.name)
            if skipped(rel, skip_files):
                continue
            size = e.stat(follow_symlinks=False).st_size
            if budget is not None:
                budget.check_deadline()
                budget.charge_entry()
                budget.check_file_size(size, rel)
            yield rel, size, _file_reader(e.path, size)
        for e in entries:
            if e.is_dir(follow_symlinks=False):
                rel = posixpath.join(rel_dir, e.name)
                if not skipped(rel, skip_dirs):
                    yield from walk(e.path, rel)

    yield from walk(root, "")


def _file_reader(full: str, size: int) -> Callable:
    """The file's bytes by ``os.read`` to the end of the file: the
    first read asks for a byte more than the listing's ``size``, so
    a file that has not grown is read in one call and the empty read
    that says it ended (four system calls where a buffered
    ``open().read()`` makes six). A read may come back short before
    the end (one call gives 0x7ffff000 bytes at the most; a network
    or FUSE file system gives what it has), so only an empty read
    ends the loop."""
    def read() -> bytes:
        fd = os.open(full, os.O_RDONLY)
        try:
            chunks = []
            want = size + 1
            while True:
                data = os.read(fd, want)
                if not data:
                    break
                chunks.append(data)
                want = max(want - len(data), 1 << 20)
            return b"".join(chunks)
        finally:
            os.close(fd)
    return read
