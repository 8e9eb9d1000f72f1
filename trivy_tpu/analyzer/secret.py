"""Secret-candidate collector (reference:
pkg/fanal/analyzer/secret/secret.go).

Gating mirrors Required (secret.go:112-141: size ≥ 10, skip .git /
node_modules dirs, lockfiles, binary-ish extensions) and Analyze's
binary sniff (utils.IsBinary). Unlike the reference — which regexes
each file inline — this analyzer only COLLECTS candidates; the
artifact layer scans the whole collection in one TPU batch
(trivy_tpu.secret.batch), with identical findings.
"""

from __future__ import annotations

import posixpath

from .analyzer import (AnalysisResult, Analyzer, GateKey,
                       register_analyzer)

SKIP_FILES = {"go.mod", "go.sum", "package-lock.json", "yarn.lock",
              "pnpm-lock.yaml", "Pipfile.lock", "Gemfile.lock"}
SKIP_DIRS = {".git", "node_modules"}
SKIP_EXTS = {".jpg", ".png", ".gif", ".doc", ".pdf", ".bin", ".svg",
             ".socket", ".deb", ".rpm", ".zip", ".gz", ".gzip",
             ".tar", ".pyc"}


def is_binary(content: bytes) -> bool:
    """utils.IsBinary approximation: NUL byte in the head chunk."""
    return b"\x00" in content[:8000]


@register_analyzer
class SecretCandidateAnalyzer(Analyzer):
    type = "secret"
    version = 1
    config_path = ""      # set from --secret-config (secret.go:135)
    key = GateKey(everything=True, but_dirs=tuple(sorted(SKIP_DIRS)),
                  but_basenames=frozenset(SKIP_FILES),
                  but_exts=frozenset(SKIP_EXTS), sizes=(10, None))

    def required(self, path, size=None):
        # the secret-rule config itself is never scanned; the
        # reference compares basename(configPath) against the walked
        # path (secret.go:135) — a deliberate quirk we replicate
        # exactly (a top-level file merely SHARING the config's name
        # is skipped there too). Asked each time, so a config_path
        # set after a group was built (cli.py) is honoured.
        if self.config_path and \
                posixpath.basename(self.config_path) == path:
            return False
        return Analyzer.required(self, path, size)

    def analyze(self, path, content):
        result = AnalysisResult()
        self.analyze_into(result, path, content)
        return result

    def analyze_into(self, result, path, content):
        # most files of a scan come here: no result of its own
        if not is_binary(content):
            result.secret_candidates.append((path, content))
