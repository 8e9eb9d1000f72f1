"""Registry + group + result merging."""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Callable, Optional

from ..types import OS, BlobInfo, Repository

_REGISTRY: list = []


@dataclass(frozen=True)
class GateKey:
    """The cheap part of an analyzer's gate, declared so that
    :class:`AnalyzerGroup` can index it once instead of asking the
    analyzer every file: what a path must have for ``required`` to
    say yes at any size. Either any of

    * ``basenames``: the file's base name is one of these;
    * ``suffixes``: the path ends with one of these (``.jar``,
      ``.deps.json``, ``gradle.lockfile``, ``.dist-info/METADATA``);
    * ``dirs``: the path starts with one of these directory prefixes,
      each ending in ``/`` (``var/lib/dpkg/``);
    * ``dotless_or_exe``: a base name without a dot, or ``.exe``;

    or, with ``everything``, every file but those under a directory
    named in ``but_dirs`` (``.git``, ``usr/share/doc``: matched on
    whole path components), those called one of ``but_basenames``
    and those whose extension, as ``posixpath.splitext`` gives it and
    lowercased, is in ``but_exts``.

    ``sizes`` is ``(least, most)``, ``most`` None for no limit: it
    says the gate's whole size rule is ``least <= size <= most``, so
    that an analyzer that said yes without a size is not asked again
    with it. Left None, it is asked again."""

    basenames: frozenset = frozenset()
    suffixes: tuple = ()
    dirs: tuple = ()
    dotless_or_exe: bool = False
    everything: bool = False
    but_dirs: tuple = ()
    but_basenames: frozenset = frozenset()
    but_exts: frozenset = frozenset()
    sizes: Optional[tuple] = None

    def __post_init__(self):
        if any(not d.endswith("/") for d in self.dirs):
            raise ValueError("a directory prefix ends in '/'")
        # "/name/" is in "/dir/" where name is a component of dir
        object.__setattr__(self, "_but_dirs", tuple(
            "/" + d.strip("/") + "/" for d in self.but_dirs))

    def matches(self, path: str) -> bool:
        """The key asked of one path by itself (what ``required``
        derives from; the group's index gives the same answer or a
        wider one)."""
        dir_, _, base = path.rpartition("/")
        if not self.everything:
            return (base in self.basenames
                    or path.endswith(self.suffixes)
                    or path.startswith(self.dirs)
                    or (self.dotless_or_exe and
                        ("." not in base or base.endswith(".exe"))))
        if base in self.but_basenames:
            return False
        # splitext's rule: dots that lead a name start no extension
        i = base.rfind(".")
        if i > 0 and base[i:].lower() in self.but_exts \
                and base[:i].lstrip("."):
            return False
        return not self.dir_ruled_out(dir_)

    def dir_ruled_out(self, dir_: str) -> bool:
        """No file of this directory passes (``everything`` only)."""
        padded = "/" + dir_ + "/"
        for d in self._but_dirs:
            if d in padded:
                return True
        return False

    def in_size(self, size: int) -> bool:
        least, most = self.sizes
        return size >= least and (most is None or size <= most)

    def index_names(self) -> Optional[tuple]:
        """``(base names, extensions)`` under which a path that ends
        in one of ``suffixes`` is found: the suffix's own base name
        where it holds a ``/``, else its last extension, lowercased.
        None where a suffix has neither (no index serves it)."""
        names, exts = set(), set()
        for s in self.suffixes:
            tail = s.rpartition("/")
            if tail[1]:
                names.add(tail[2])
            elif "." in s:
                exts.add(s[s.rfind("."):].lower())
            else:
                return None
        return names, exts


class Analyzer:
    """Base analyzer. Subclasses set ``type``/``version`` and implement
    ``required(path, size)`` + ``analyze(path, content)``.

    How an analyzer says which files it reads, cheapest first for a
    scan (docs/performance.md §10, "The analyzers' gates"):

    * ``exact_paths`` / ``basenames``: the gate is a fixed path or
      base-name set and nothing else. ``required`` is derived; the
      group answers from two tables and puts these analyzers ahead
      of the others in a file's order.
    * ``key``: a :class:`GateKey`, the cheap necessary part of the
      gate. Where that is the whole gate (with its ``sizes``),
      ``required`` is derived from it too: a single source of truth.
      Where the gate has more to it (a regex, an environment
      variable), the analyzer implements ``required`` as well and
      the key only decides whether it is asked at all: ``required``
      may say no where the key matched, never yes where it did not.
    * neither: ``required`` is opaque and is asked of every file, as
      a module's own gate is."""

    type: str = ""
    version: int = 1
    exact_paths: frozenset = frozenset()
    basenames: frozenset = frozenset()
    key: Optional[GateKey] = None

    def required(self, path: str, size: Optional[int] = None) -> bool:
        if self.exact_paths or self.basenames:
            return path in self.exact_paths or \
                path.rpartition("/")[2] in self.basenames
        key = self.key
        if key is not None:
            if size is not None and key.sizes is not None \
                    and not key.in_size(size):
                return False
            return key.matches(path)
        raise NotImplementedError

    def analyze(self, path: str, content: bytes)\
            -> "AnalysisResult":
        raise NotImplementedError

    def analyze_into(self, result: "AnalysisResult", path: str,
                     content: bytes) -> None:
        """What ``analyze`` finds, added to ``result``
        (``AnalyzerGroup.analyze_file`` calls this; an analyzer that
        finds one thing a file may add it without a result of its
        own)."""
        result.merge(self.analyze(path, content))


def register_analyzer(a) -> "Analyzer":
    """Usable as ``@register_analyzer`` on a class (instantiates it)
    or called with an instance."""
    _REGISTRY.append(a() if isinstance(a, type) else a)
    return a


def registered_analyzers() -> list:
    return list(_REGISTRY)


@dataclass
class AnalysisResult:
    """Mergeable fragment (reference: analyzer.go AnalysisResult)."""

    os: Optional[OS] = None
    repository: Optional[Repository] = None
    package_infos: list = field(default_factory=list)
    applications: list = field(default_factory=list)
    config_files: list = field(default_factory=list)
    secrets: list = field(default_factory=list)
    licenses: list = field(default_factory=list)
    system_files: list = field(default_factory=list)
    custom_resources: list = field(default_factory=list)
    secret_candidates: list = field(default_factory=list)  # (path, data)
    build_info: Optional[dict] = None      # Red Hat only

    def merge(self, other: "AnalysisResult") -> None:
        if other is None:
            return
        if other.os is not None:
            self.os = _merge_os(self.os, other.os)
        if other.build_info:
            # content-manifest and buildinfo-Dockerfile analyzers
            # contribute different keys of the same record
            self.build_info = {**(self.build_info or {}),
                               **other.build_info}
        if other.repository is not None:
            self.repository = other.repository
        self.package_infos.extend(other.package_infos)
        self.applications.extend(other.applications)
        self.config_files.extend(other.config_files)
        self.secrets.extend(other.secrets)
        self.licenses.extend(other.licenses)
        self.system_files.extend(other.system_files)
        self.custom_resources.extend(other.custom_resources)
        self.secret_candidates.extend(other.secret_candidates)

    def sort(self) -> None:
        """Reference AnalysisResult.Sort (analyzer.go:175-230):
        deterministic ordering before the blob is written."""
        self.package_infos.sort(key=lambda p: p.file_path)
        for pi in self.package_infos:
            pi.packages.sort(key=lambda p: p.name)
        self.applications.sort(key=lambda a: a.file_path)
        for app in self.applications:
            app.libraries.sort(key=lambda p: (p.name, p.version))
        self.custom_resources.sort(key=lambda c: c.file_path)
        self.secrets.sort(key=lambda s: s.file_path)
        for sec in self.secrets:
            sec.findings.sort(key=lambda f: (f.rule_id, f.start_line))
        self.licenses.sort(
            key=lambda lf: (lf.type, lf.file_path))

    def to_blob_info(self, diff_id: str = "", digest: str = "")\
            -> BlobInfo:
        self.sort()
        return BlobInfo(
            diff_id=diff_id,
            digest=digest,
            os=self.os,
            repository=self.repository,
            package_infos=self.package_infos,
            applications=self.applications,
            config_files=self.config_files,
            secrets=self.secrets,
            licenses=self.licenses,
            system_files=self.system_files,
            custom_resources=self.custom_resources,
            build_info=self.build_info,
        )


def _merge_os(old: Optional[OS], new: OS) -> OS:
    """OS.Merge semantics (fanal types): later analyzers fill gaps;
    the 'release' file never overrides a specific family; ubuntu wins
    over debian (ubuntu ships /etc/debian_version too)."""
    if old is None:
        return new
    if old.family and new.family and old.family != new.family:
        # specific families beat the generic os-release fallback;
        # the version must come from the WINNING family's analyzer
        # (ubuntu 22.04 + debian bookworm/sid must not mix)
        if new.family == "ubuntu" or (old.family == "debian"
                                      and new.family != "debian"):
            family, name = new.family, (new.name or old.name)
        else:
            family, name = old.family, (old.name or new.name)
        return OS(family=family, name=name, eosl=old.eosl or new.eosl)
    return OS(family=new.family or old.family,
              name=new.name or old.name,
              eosl=old.eosl or new.eosl,
              extended=old.extended or new.extended)


class AnalyzerGroup:
    """Fans a file out to all matching analyzers
    (analyzer.go:393-447; the goroutine pool becomes a plain loop —
    parallelism lives in the batched kernels, not host threads).

    The gate is answered from an index built here, once, from what
    the analyzers declare (:class:`Analyzer`): two tables for the
    declared paths and base names, and for the analyzers that
    declare a :class:`GateKey` their base names, extensions and
    directories, so that a file costs one ``rpartition``, a few
    dictionary lookups and the ``required`` of those analyzers its
    name, extension or directory point at. What depends on the
    directory alone is decided once a directory and remembered for
    the next file of it (a walk hands a directory's files in
    together, a layer's tar nearly so; paths in any order get the
    same answers). An analyzer that declares nothing, and one that
    ``--file-patterns`` names, is asked every file.

    ``gate_files`` and ``gate_probes`` count the files the gate was
    asked about and the ``required`` calls that took: plain integers
    of this group's one thread, handed to ``INGEST_METRICS`` by
    whoever owns the group (:meth:`take_gate_counts`)."""

    def __init__(self, disabled: Optional[list] = None,
                 file_patterns: Optional[dict] = None):
        self.disabled = set(disabled or [])
        # --file-patterns TYPE:regex overrides (analyzer.go:464)
        self.patterns = {t: re.compile(p)
                         for t, p in (file_patterns or {}).items()}
        self.analyzers = [a for a in registered_analyzers()
                          if a.type not in self.disabled]
        self.gate_files = 0
        self.gate_probes = 0
        # the two tables of declared-gate analyzers (the by-path one
        # split by directory); anything with a --file-patterns
        # override stays in the probe list so the override can force
        # it on arbitrary paths
        self._paths_in: dict = {}     # dir -> {base name: [analyzer]}
        self._by_base: dict = {}      # base name -> [analyzer]
        self._probe: list = []        # the others, in their order
        for a in self.analyzers:
            declared = a.exact_paths or a.basenames
            if not declared or a.type in self.patterns:
                self._probe.append(a)
                continue
            for p in a.exact_paths:
                dir_, _, base = p.rpartition("/")
                # the root's by their whole path: "/x" is not "x"
                self._paths_in.setdefault(dir_, {}) \
                    .setdefault(base if dir_ else p, []).append(a)
            for b in a.basenames:
                self._by_base.setdefault(b, []).append(a)
        self._index_keys()
        # the last directory asked about and its verdicts, one tuple
        # so that a reader never sees one directory's name beside
        # another's verdicts
        self._dir = self._enter("")

    def _index_keys(self) -> None:
        """The probe list's declared keys, by what one ``rpartition``
        of a path gives. An entry is ``(adds, drops)``: ranks in the
        probe list to ask, and ranks of ``everything`` analyzers not
        to ask."""
        self._keys: dict = {}         # rank -> GateKey, where indexed
        self._sizes: dict = {}        # analyzer -> key, not asked again
        self._key_base: dict = {}
        self._key_ext: dict = {}      # ".ext", lowercased
        dotless: list = []
        for i, a in enumerate(self._probe):
            key = a.key
            if key is None or a.type in self.patterns \
                    or a.exact_paths or a.basenames:
                continue
            names = key.index_names()
            if names is None:
                continue              # a suffix no index serves
            self._keys[i] = key
            if key.sizes is not None:
                self._sizes[a] = key    # its whole size rule
            elif type(a).required is Analyzer.required:
                self._sizes[a] = None   # derived: it has none
            for b in key.basenames | names[0]:
                self._key_base.setdefault(b, ([], []))[0].append(i)
            for e in names[1]:
                self._key_ext.setdefault(e, ([], []))[0].append(i)
            if key.dotless_or_exe:
                dotless.append(i)
                self._key_ext.setdefault(".exe", ([], []))[0].append(i)
            for b in key.but_basenames:
                self._key_base.setdefault(b, ([], []))[1].append(i)
            for e in key.but_exts:
                self._key_ext.setdefault(e, ([], []))[1].append(i)
        self._dotless = (dotless, []) if dotless else None
        # whom a directory may tell to ask of its every file: those
        # with no key, and those whose key looks at the directory
        self._by_dir = [
            (a, self._keys.get(i))
            for i, a in enumerate(self._probe)
            if i not in self._keys or self._keys[i].everything
            or self._keys[i].dirs]
        # asked again once a file's size is known
        self._again = frozenset(
            a for a in self._probe if a not in self._sizes)
        self._plans: dict = {}

    def versions(self) -> dict:
        return {a.type: a.version for a in self.analyzers}

    def take_gate_counts(self) -> tuple:
        """``(gate_files, gate_probes)`` since the last taking."""
        out = self.gate_files, self.gate_probes
        self.gate_files = self.gate_probes = 0
        return out

    def _enter(self, dir_: str) -> tuple:
        """A directory's verdicts: ``(dir, asks, paths)``, the probe
        list's analyzers to ask of every file in it (those that
        declare no key, the ``everything`` ones it does not rule
        out, those whose directory prefix it starts with) and the
        declared paths that lie in it."""
        prefix = dir_ + "/" if dir_ else ""
        asks = tuple(
            a for a, key in self._by_dir
            if key is None or (
                not key.dir_ruled_out(dir_) if key.everything
                else prefix.startswith(key.dirs)))
        return dir_, asks, self._paths_in.get(dir_)

    def _plan(self, asks: tuple, base, ext, drop_ext: bool) -> tuple:
        """The probe list's analyzers to ask of a file whose base
        name or extension the index knows (``base``, ``ext``: None
        where it does not): the directory's ``asks`` with the
        entries' adds and less their drops (an extension's only with
        ``drop_ext``), in the probe list's order. Remembered by its
        inputs, which are as many as the declarations make them."""
        at = (asks, base, ext, drop_ext)
        plan = self._plans.get(at)
        if plan is None:
            named = self._key_base.get(base, ((), ()))
            by_ext = self._dotless if ext == "" \
                else self._key_ext.get(ext, ((), ()))
            ranks = {i for i, a in enumerate(self._probe)
                     if a in asks}
            ranks.update(named[0], by_ext[0])
            ranks.difference_update(named[1])
            if drop_ext:
                ranks.difference_update(by_ext[1])
            plan = self._plans[at] = tuple(
                self._probe[r] for r in sorted(ranks))
        return plan

    def wanted(self, path: str, size: Optional[int],
               among: Optional[list] = None) -> list:
        """The gate without the content: the analyzers that ask for
        ``path`` of ``size``, in the order they run (the two
        dispatch tables, then the probe list's ``required`` and
        ``--file-patterns``). Empty for a file nobody reads. A
        ``size`` of None asks who would at some size (``required``
        leaves its size rules out then): where that is nobody, a
        walk need not so much as ask the file's size, and where it
        is somebody, the answer goes back in as ``among`` with the
        size and only those are asked again, or held to the
        ``sizes`` their key declares
        (``artifact/walker.iter_fs``)."""
        forced = self._forced if self.patterns else None
        if among is not None:
            matched = []
            for a in among:
                # the tables and --file-patterns never look at a size
                if a in self._again:
                    if not (forced and forced(a, path)):
                        self.gate_probes += 1
                        if not a.required(path, size):
                            continue
                else:
                    key = self._sizes.get(a)
                    if key is not None and not key.in_size(size):
                        continue
                matched.append(a)
            return matched
        self.gate_files += 1
        dir_, _, base = path.rpartition("/")
        memo = self._dir
        if memo[0] != dir_:
            memo = self._dir = self._enter(dir_)
        asks = memo[1]
        matched = []
        if memo[2] is not None:
            matched.extend(memo[2].get(base if dir_ else path, ()))
        tabled = self._by_base.get(base)
        if tabled is not None:
            for a in tabled:
                if a not in matched:   # declared in both tables
                    matched.append(a)
        i = base.rfind(".")
        ext = base[i:].lower() if i >= 0 else ""
        by_ext = self._key_ext.get(ext) if i >= 0 else self._dotless
        named = base in self._key_base
        if named or by_ext is not None:
            # splitext's rule: dots that lead a name start no
            # extension, and nobody skips a file for those
            asks = self._plan(
                asks, base if named else None,
                ext if by_ext is not None else None,
                bool(by_ext and by_ext[1]) and i > 0
                and bool(base[:i].lstrip(".")))
        if forced is None:
            self.gate_probes += len(asks)
            for a in asks:
                if a.required(path, size):
                    matched.append(a)
            return matched
        for a in asks:
            if forced(a, path):
                matched.append(a)
                continue
            self.gate_probes += 1
            if a.required(path, size):
                matched.append(a)
        return matched

    def _forced(self, a, path: str) -> bool:
        """A match of ``--file-patterns`` forces an analyzer on."""
        pat = self.patterns.get(a.type)
        return pat is not None and pat.search(path) is not None

    def analyze_file(self, result: AnalysisResult, path: str,
                     content_fn: Callable, size: int,
                     wanted: Optional[list] = None) -> None:
        """``wanted``: what :meth:`wanted` said of this file, where
        the caller has asked already (the gates run once a file)."""
        if wanted is None:
            wanted = self.wanted(path, size)
        if not wanted:
            return
        content = content_fn()  # read once, shared by all analyzers
        for a in wanted:
            a.analyze_into(result, path, content)
