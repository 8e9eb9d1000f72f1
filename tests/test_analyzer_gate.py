"""``AnalyzerGroup.wanted``: the analyzers' gates without a file's
content, answered from the index the group builds of what the
analyzers declare, and what a walk may conclude from them before it
knows a file's size (``artifact/walker.iter_fs``). The oracle asks
every analyzer its own ``required``: nothing of the index."""

import posixpath
import random

import pytest

from trivy_tpu.analyzer import analyzer as analyzer_mod
from trivy_tpu.analyzer.analyzer import (AnalysisResult, Analyzer,
                                         AnalyzerGroup, GateKey,
                                         registered_analyzers)

PATHS = [
    "Dockerfile", "deploy/Dockerfile.prod", "app/build.dockerfile",
    "k8s/deploy.yaml", "k8s/values.yml", "cfg/settings.json",
    "infra/main.tf", "requirements.txt", "svc/requirements.txt",
    "package-lock.json", "web/yarn.lock", "go.sum", "go.mod",
    "Cargo.lock", "pom.xml", "lib/app.jar", "lib/app.war",
    "gradle.lockfile", "sub/x.gradle.lockfile", "Gemfile.lock",
    "composer.lock", "Pipfile.lock", "poetry.lock",
    "bin/tool", "bin/tool.exe", "usr/local/bin/server",
    "src/main.py", "src/app.js", "src/.env", "README.md",
    "node_modules/dep/index.js", ".git/config", "assets/logo.png",
    "build/obj.pyc", "dist/bundle.tar.gz", "docs/manual.pdf",
    "etc/os-release", "etc/alpine-release", "lib/apk/db/installed",
    "var/lib/dpkg/status", "var/lib/dpkg/info/bash.list",
    "var/lib/rpm/Packages", "root/buildinfo/Dockerfile-x",
    "root/buildinfo/content_manifests/x.json", "LICENSE",
    "usr/share/doc/pkg/copyright", "trivy-secret.yaml", "Customfile",
]
SIZES = [0, 5, 9, 10, 63, 64, 4096, 10 ** 7, 10 ** 10]

# What the gates of the commit before the index answered (every
# analyzer on, no --file-patterns) without a size, at 9 bytes and at
# 4,096: the details they took from posixpath. ``splitext(".pyc")``
# has no extension, ``.PNG`` is skipped, ``a.tar.gz`` by ``.gz``, a
# file called ``node_modules`` or ``.git`` is in no skipped
# directory, a trailing dot is an empty extension, ``.jar`` as a
# whole name ends with ``.jar``, ``/etc/os-release`` is not
# ``etc/os-release``.
TRAPS = {
    ".pyc": (["secret"], [], ["secret"]),
    "build/.pyc": (["secret"], [], ["secret"]),
    "build/..pyc": (["secret"], [], ["secret"]),
    "build/a..pyc": ([], [], []),
    "assets/IMG.PNG": ([], [], []),
    "dist/a.tar.gz": ([], [], []),
    "src/name.": (["secret"], [], ["secret"]),
    "src/node_modules": (
        ["secret", "gobinary", "rustbinary"],
        [],
        ["secret", "gobinary", "rustbinary"]),
    "node_modules/dep/index.js": ([], [], []),
    "a/node_modules/x.py": ([], [], []),
    "src/.git": (["secret"], [], ["secret"]),
    ".git/config": (
        ["gobinary", "rustbinary"],
        [],
        ["gobinary", "rustbinary"]),
    "x/specifications/y.gemspec": (
        ["secret", "gemspec"],
        ["gemspec"],
        ["secret", "gemspec"]),
    "src/y.gemspec": (["secret"], [], ["secret"]),
    "src/pkg.egg-info": (
        ["secret", "python-pkg"],
        ["python-pkg"],
        ["secret", "python-pkg"]),
    "site/pkg.egg-info/PKG-INFO": (
        ["secret", "python-pkg", "gobinary", "rustbinary"],
        ["python-pkg"],
        ["secret", "python-pkg", "gobinary", "rustbinary"]),
    "site/pkg.dist-info/METADATA": (
        ["secret", "python-pkg", "gobinary", "rustbinary"],
        ["python-pkg"],
        ["secret", "python-pkg", "gobinary", "rustbinary"]),
    "site/METADATA": (
        ["secret", "gobinary", "rustbinary"],
        [],
        ["secret", "gobinary", "rustbinary"]),
    "var/lib/dpkg/status.d/base": (
        ["dpkg", "secret", "gobinary", "rustbinary"],
        ["dpkg"],
        ["dpkg", "secret", "gobinary", "rustbinary"]),
    "var/lib/dpkg/info/bash.list": (
        ["dpkg", "secret"],
        ["dpkg"],
        ["dpkg", "secret"]),
    "var/lib/dpkg/info/bash.md5sums": (["secret"], [], ["secret"]),
    "var/lib/dpkgx/status": (
        ["secret", "gobinary", "rustbinary"],
        [],
        ["secret", "gobinary", "rustbinary"]),
    "root/buildinfo/content_manifests/x.json": (
        ["secret", "json", "redhat content manifest"],
        ["json", "redhat content manifest"],
        ["secret", "json", "redhat content manifest"]),
    "root/buildinfo/Dockerfile-x": (
        ["secret", "gobinary", "rustbinary", "redhat dockerfile"],
        ["redhat dockerfile"],
        ["secret", "gobinary", "rustbinary", "redhat dockerfile"]),
    "root/buildinfox/Dockerfile-x": (
        ["secret", "gobinary", "rustbinary"],
        [],
        ["secret", "gobinary", "rustbinary"]),
    "src/.jar": (["secret", "jar"], ["jar"], ["secret", "jar"]),
    "src/x.JAR": (["secret"], [], ["secret"]),
    "bin/tool.EXE": (["secret"], [], ["secret"]),
    "bin/tool.exe": (
        ["secret", "gobinary", "rustbinary"],
        [],
        ["secret", "gobinary", "rustbinary"]),
    "bin/tool": (
        ["secret", "gobinary", "rustbinary"],
        [],
        ["secret", "gobinary", "rustbinary"]),
    "go.mod": (["gomod"], ["gomod"], ["gomod"]),
    "svc/packages.lock.json": (
        ["secret", "nuget", "json"],
        ["nuget", "json"],
        ["secret", "nuget", "json"]),
    "sub/x.gradle.lockfile": (
        ["secret", "gradle"],
        ["gradle"],
        ["secret", "gradle"]),
    "app/x.deps.json": (
        ["secret", "dotnet-core", "json"],
        ["dotnet-core", "json"],
        ["secret", "dotnet-core", "json"]),
    "usr/share/doc/pkg/copyright": (
        ["secret", "dpkg-license", "gobinary", "rustbinary"],
        ["dpkg-license"],
        ["secret", "dpkg-license", "gobinary", "rustbinary"]),
    "usr/lib/x.py": (["secret"], [], ["secret"]),
    "usr/libexec/x.py": (
        ["secret", "license-file"],
        ["license-file"],
        ["secret", "license-file"]),
    "LICENSE": (
        ["secret", "license-file", "gobinary", "rustbinary"],
        ["license-file"],
        ["secret", "license-file", "gobinary", "rustbinary"]),
    "docs/License.md": (
        ["secret", "license-file"],
        ["license-file"],
        ["secret", "license-file"]),
    "/etc/os-release": (
        ["secret", "gobinary", "rustbinary"],
        [],
        ["secret", "gobinary", "rustbinary"]),
    "etc/os-release": (
        ["os-release", "secret", "gobinary", "rustbinary"],
        ["os-release"],
        ["os-release", "secret", "gobinary", "rustbinary"]),
    "usr/lib/os-release": (
        ["os-release", "secret", "gobinary", "rustbinary"],
        ["os-release"],
        ["os-release", "secret", "gobinary", "rustbinary"]),
}


class _OpaqueModule:
    """A module whose own gate nobody can index (and which, like a
    WASM module's, is never handed a size of None)."""
    name = "opaque"

    def required(self, path, size):
        return size % 2 == 0 and "e" in path

    def analyze(self, path, content):
        return None


@pytest.fixture
def group_of(monkeypatch):
    """``group_of(which)``: the group under test. The registry and
    the secret analyzer's ``config_path`` are the process's, so what
    a case adds to them is taken back after it."""
    def build(which):
        if which == "module":
            from trivy_tpu.module import _ModuleAnalyzer
            monkeypatch.setattr(
                analyzer_mod, "_REGISTRY",
                analyzer_mod._REGISTRY
                + [_ModuleAnalyzer(_OpaqueModule())])
        group = AnalyzerGroup(**GROUPS.get(which, {}))
        if which == "secret-config":
            # set once the group is built, as the command sets it:
            # the gate is held to the value at the time of asking
            (secret,) = [a for a in registered_analyzers()
                         if a.type == "secret"]
            monkeypatch.setattr(secret, "config_path",
                                "conf/trivy-secret.yaml")
        return group
    return build


GROUPS = {
    "default": {},
    "file-patterns": {"file_patterns": {
        "dockerfile": "Customfile", "yaml": r"\.conf$",
        "secret": r"logo\.png$", "npm": "lock"}},
    "disabled": {"disabled": ["secret", "yaml", "gobinary"]},
}
WHICH = sorted(GROUPS) + ["module", "secret-config"]


def composed(seed: int = 20261004, n: int = 3000) -> list:
    """``PATHS``, the traps, and ``n`` paths put together from every
    name, suffix and directory an analyzer declares (and the case
    and the dots of each varied), seeded."""
    rng = random.Random(seed)
    names = {"main.py", "notes", "x.conf", "", ".", "logo.png"}
    dirs = {"", "src", "src/pkg", "a//b", "/", "/etc"}
    for a in registered_analyzers():
        key = a.key or GateKey()
        for p in a.exact_paths:
            dirs.add(posixpath.dirname(p))
            names.add(posixpath.basename(p))
        names |= set(a.basenames) | key.basenames | key.but_basenames
        for s in key.suffixes:
            head, _, tail = s.rpartition("/")
            names |= {tail, "x" + tail, "x" + tail.upper(),
                      "." + tail.lstrip(".")}
            if head:
                dirs |= {"x" + head, "site/y" + head}
        for e in key.but_exts:
            names |= {e, "f" + e, "F" + e.upper(), "." + e, "g." + e,
                      "h" + e + ".txt"}
        if key.dotless_or_exe:
            names |= {"tool", "tool.exe", "tool.EXE", ".exe"}
        for d in key.dirs:
            d = d.rstrip("/")
            dirs |= {d, d + "/sub", d + "x", "x/" + d,
                     posixpath.dirname(d)}
        for d in key.but_dirs:
            names.add(posixpath.basename(d))
            dirs |= {d, "a/" + d + "/b", d + ".bak", "a/x" + d}
    names, dirs = sorted(names), sorted(dirs)
    out = PATHS + sorted(TRAPS)
    for _ in range(n):
        d, name = rng.choice(dirs), rng.choice(names)
        out.append(d + "/" + name if d else name)
    return out


def brute_force(group, path, size) -> list:
    """Every analyzer of the group asked its own ``required``, and
    ``--file-patterns``, directly; in the order a file's analyzers
    run: those that declare ``exact_paths`` or ``basenames`` (and
    that no pattern names) by path, then by base name, then the
    others as they were registered."""
    tabled = [a for a in group.analyzers
              if (a.exact_paths or a.basenames)
              and a.type not in group.patterns]
    matched = [a for a in tabled if path in a.exact_paths]
    matched += [a for a in tabled if a not in matched
                and path.rpartition("/")[2] in a.basenames]
    for a in group.analyzers:
        if a in tabled:
            continue
        pat = group.patterns.get(a.type)
        if (pat is not None and pat.search(path)) \
                or a.required(path, size):
            matched.append(a)
    return matched


def types(analyzers) -> list:
    return [a.type for a in analyzers]


@pytest.mark.parametrize("order", ["composed", "shuffled"])
@pytest.mark.parametrize("size", [None] + SIZES)
@pytest.mark.parametrize("which", WHICH)
def test_wanted_is_every_analyzer_asked_directly(group_of, which,
                                                 size, order):
    """The index answers as the analyzers do, analyzer for analyzer
    and in their order, whatever the order the paths come in (the
    remembered directory is the last one asked about)."""
    group = group_of(which)
    paths = composed()
    if order == "shuffled":
        random.Random(size).shuffle(paths)
    somebody = 0
    for path in paths:
        want = brute_force(group, path, size)
        assert types(group.wanted(path, size)) == types(want), path
        somebody += bool(want)
        if size is None:
            continue
        # who would at some size: never fewer than at this one, and
        # asking only those again is asking everybody
        maybe = group.wanted(path, None)
        assert set(map(id, want)) <= set(map(id, maybe)), path
        assert group.wanted(path, size, maybe) == want, path
    assert somebody >= (500 if size is None or size >= 10 else 100)


@pytest.mark.parametrize("path", sorted(TRAPS))
def test_the_traps_are_answered_as_before_the_index(path):
    group = AnalyzerGroup()
    for size, want in zip((None, 9, 4096), TRAPS[path]):
        assert types(group.wanted(path, size)) == want
        assert types(brute_force(group, path, size)) == want


@pytest.mark.parametrize("which", WHICH)
def test_the_gate_counts_its_files_and_its_required_calls(
        group_of, monkeypatch, which):
    """``gate_files`` is the files asked about (a second question
    about a file, with its size, is the same file) and
    ``gate_probes`` every ``required`` call the group made."""
    group = group_of(which)
    calls = []
    for a in group.analyzers:
        monkeypatch.setattr(
            a, "required",
            lambda path, size=None, a=a, real=a.required:
            (calls.append(a.type), real(path, size))[1])
    paths = composed(n=500)
    for path in paths:
        maybe = group.wanted(path, None)
        if maybe:
            group.wanted(path, 4096, maybe)
    assert (group.gate_files, group.gate_probes) == \
        (len(paths), len(calls))
    assert group.take_gate_counts() == (len(paths), len(calls))
    assert group.take_gate_counts() == (0, 0)
    if which == "default":
        # the index, on paths made of the declared names: a fifth
        # of asking every analyzer that is in no table
        probe = [a for a in group.analyzers
                 if not (a.exact_paths or a.basenames)]
        assert len(probe) >= 20
        assert len(calls) < len(paths) * len(probe) / 5


def test_a_key_is_necessary_for_its_analyzers_yes():
    """What the index rests on: ``required`` never says yes where
    the declared key does not match; and a derived ``required`` is
    the key with its ``sizes``."""
    for a in registered_analyzers():
        if a.key is None:
            continue
        derived = type(a).required is Analyzer.required
        for path in composed():
            for size in (None, 9, 4096):
                yes = a.required(path, size)
                assert not yes or a.key.matches(path), (a.type, path)
                if derived:
                    assert yes == (a.key.matches(path) and (
                        size is None or a.key.sizes is None
                        or a.key.in_size(size))), (a.type, path)


def test_an_analyzer_that_declares_nothing_is_asked_every_file(
        group_of):
    group = group_of("module")
    (module,) = [a for a in group.analyzers
                 if a.type == "module:opaque"]
    asked = []
    module.mod.required = lambda path, size: \
        asked.append((path, size)) or True
    paths = ["assets/logo.png", "node_modules/x/e.js", "src/main.py"]
    for path in paths:
        maybe = group.wanted(path, None)
        assert module in maybe
        assert module in group.wanted(path, 7, maybe)
    assert asked == [(path, 7) for path in paths]


def test_a_directory_prefix_ends_in_a_slash():
    with pytest.raises(ValueError):
        GateKey(dirs=("root/buildinfo",))


def test_somebody_is_ruled_out_by_size_alone():
    """What the walk opens and closes unread: wanted at some size,
    not at this one."""
    from trivy_tpu.artifact.artifact import LocalFSArtifact
    from trivy_tpu.artifact.cache import MemoryCache
    group = LocalFSArtifact(".", MemoryCache()).group  # fs defaults
    assert group.wanted("src/main.py", None) and \
        not group.wanted("src/main.py", 9)
    assert len(group.wanted("bin/tool", 64)) > 1 and \
        [a.type for a in group.wanted("bin/tool", 63)] == ["secret"]
    assert group.wanted("assets/logo.png", None) == []
    assert group.wanted("node_modules/dep/index.js", None) == []


@pytest.mark.parametrize("which", sorted(GROUPS))
def test_analyze_file_with_the_answer_handed_in(which):
    """The same analyzers see the same content once, whether
    ``analyze_file`` asks the gates itself or is handed what
    ``wanted`` said; a file nobody wants is not read."""
    group = AnalyzerGroup(**GROUPS[which])
    seen = []
    for a in group.analyzers:
        a_type = a.type

        def analyze_into(result, path, content, a_type=a_type):
            seen.append((a_type, path, content))

        a.analyze_into = analyze_into
    try:
        for path in PATHS:
            body = path.encode() * 20
            reads = []

            def read(body=body):
                reads.append(1)
                return body

            size = len(body)
            del seen[:]
            group.analyze_file(AnalysisResult(), path, read, size)
            asked_itself = list(seen)
            del seen[:]
            group.analyze_file(AnalysisResult(), path, read, size,
                               group.wanted(path, size))
            assert seen == asked_itself
            assert len(reads) == (2 if seen else 0)
    finally:
        for a in group.analyzers:
            del a.analyze_into


def test_a_modules_gate_is_never_asked_without_a_size():
    from trivy_tpu.module import _ModuleAnalyzer

    class Mod:
        name = "sized"
        asked = []

        def required(self, path, size):
            self.asked.append(size)
            return size > 100          # would raise on None

    a = _ModuleAnalyzer(Mod())
    assert a.required("x.txt", None) is True
    assert a.required("x.txt", 50) is False
    assert a.required("x.txt", 500) is True
    assert Mod.asked == [50, 500]
    assert isinstance(a, Analyzer)
