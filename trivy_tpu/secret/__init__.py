"""Secret detection engine.

CPU path: exact reference semantics (pkg/fanal/secret/scanner.go).
TPU path: multi-pattern DFA sieve (trivy_tpu.ops.dfa — full-length
keywords, anchors, and per-rule fixed chains in one banded table) +
class-run gates (trivy_tpu.ops.runs) + sparse host verification,
orchestrated by trivy_tpu.secret.batch (the rows sharded over a mesh:
trivy_tpu.parallel.secret_shard).
"""

from .model import (
    Rule,
    AllowRule,
    ExcludeBlock,
    Location,
    SecretConfig,
    load_config,
)
from .scanner import Scanner, new_scanner
from .builtin_rules import BUILTIN_RULES, BUILTIN_ALLOW_RULES

__all__ = [
    "Rule", "AllowRule", "ExcludeBlock", "Location", "SecretConfig",
    "load_config", "Scanner", "new_scanner", "BUILTIN_RULES",
    "BUILTIN_ALLOW_RULES",
]
