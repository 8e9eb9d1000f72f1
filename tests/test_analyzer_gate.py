"""``AnalyzerGroup.wanted``: the analyzers' gates without a file's
content, asked once a file, and what a walk may conclude from them
before it knows a file's size (``artifact/walker.iter_fs``)."""

import pytest

from trivy_tpu.analyzer.analyzer import (AnalysisResult, Analyzer,
                                         AnalyzerGroup)

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
GROUPS = {
    "default": {},
    "file-patterns": {"file_patterns": {
        "dockerfile": "Customfile", "yaml": r"\.conf$",
        "secret": r"logo\.png$"}},
    "disabled": {"disabled": ["secret", "yaml", "gobinary"]},
}


def _as_analyze_file_asked(group, path, size) -> list:
    """The gates as ``analyze_file`` asked them before ``wanted``
    was there."""
    matched = list(group._by_path.get(path, ()))
    for a in group._by_base.get(path.rpartition("/")[2], ()):
        if a not in matched:
            matched.append(a)
    for a in group._probe:
        pat = group.patterns.get(a.type)
        if pat is not None and pat.search(path):
            matched.append(a)
        elif a.required(path, size):
            matched.append(a)
    return matched


@pytest.mark.parametrize("size", SIZES)
@pytest.mark.parametrize("which", sorted(GROUPS))
def test_wanted_is_what_analyze_file_asked(which, size):
    group = AnalyzerGroup(**GROUPS[which])
    somebody = 0
    for path in PATHS:
        want = _as_analyze_file_asked(group, path, size)
        assert group.wanted(path, size) == want
        somebody += bool(want)
        # who would at some size: never fewer than at this one, and
        # asking only those again is asking everybody
        maybe = group.wanted(path, None)
        assert set(map(id, want)) <= set(map(id, maybe))
        assert group.wanted(path, size, maybe) == want
    assert somebody >= (10 if size else 3)


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

        def analyze(path, content, a_type=a_type):
            seen.append((a_type, path, content))
            return None

        a.analyze = analyze
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
            del a.analyze


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
