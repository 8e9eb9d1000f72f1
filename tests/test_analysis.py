"""Static-analysis engine tests (``pytest -m lint``,
docs/static-analysis.md).

Covers: the suppression grammar (property-tested on seeded random
comments; reason-less and unused suppressions FAIL), one minimal
violating + one minimal clean fixture per rule, the PR-4 and PR-5
regression fixtures that deliberately reintroduce the historical
bug shapes, the tree-wide zero-unsuppressed-findings acceptance
gate, and the stable-sorted ``--json`` CLI contract.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from trivy_tpu.analysis import (analyze_source, analyze_tree,
                                parse_suppressions)
from trivy_tpu.analysis.engine import (BAD_SUPPRESSION,
                                       UNUSED_SUPPRESSION)

pytestmark = pytest.mark.lint


def _findings(src, rule=None, extra=None):
    rep = analyze_source(src, extra=extra)
    out = [f for f in rep.findings
           if f.rule not in (BAD_SUPPRESSION, UNUSED_SUPPRESSION)]
    if rule is not None:
        out = [f for f in out if f.rule == rule]
    return out


# ---------------------------------------------------------------
# suppression grammar
# ---------------------------------------------------------------

class TestSuppressionParser:
    def test_basic_forms(self):
        sups = parse_suppressions([
            "x = 1  # lint: disable=monotonic-clock -- wall is a "
            "label here",
            "# lint: disable=lock-discipline,donation-safety -- "
            "leaf lock",
            "y = 2  # ordinary comment",
            "# lint: disable=bare-except-at-seam --",
            "# lint: disable=bare-except-at-seam",
        ])
        assert set(sups) == {1, 2, 4, 5}
        assert sups[1].rules == ("monotonic-clock",)
        assert sups[1].reason.startswith("wall is a label")
        assert sups[2].rules == ("lock-discipline",
                                 "donation-safety")
        assert sups[2].valid
        # reason-less forms parse but are INVALID (fail closed)
        assert not sups[4].valid
        assert not sups[5].valid

    def test_property_random_comments(self):
        """Seeded random comment lines: every generated suppression
        round-trips (rules + reason), garbage never parses as a
        valid suppression."""
        rng = np.random.default_rng(20260804)
        rules = ["monotonic-clock", "lock-discipline",
                 "hostpool-blocking", "donation-safety",
                 "bare-except-at-seam",
                 "unbounded-label-cardinality"]
        words = ["leaf", "lock", "capped", "upstream", "wall",
                 "label", "bounded", "fold"]
        for _ in range(200):
            n = int(rng.integers(1, 4))
            chosen = sorted({rules[int(i)] for i in
                             rng.integers(0, len(rules), n)})
            with_reason = bool(rng.integers(0, 2))
            reason = " ".join(
                words[int(i)]
                for i in rng.integers(0, len(words), 3)) \
                if with_reason else ""
            prefix = "x = 1  " if rng.integers(0, 2) else ""
            line = (f"{prefix}# lint: disable="
                    + ",".join(chosen)
                    + (f" -- {reason}" if with_reason else ""))
            sups = parse_suppressions([line])
            assert 1 in sups, line
            assert sups[1].rules == tuple(chosen)
            assert sups[1].valid == with_reason
            assert sups[1].reason == reason
        for garbage in ("# lint disable=foo", "# disable=foo",
                        "# lint: enable=foo -- r", "x = 1", ""):
            assert parse_suppressions([garbage]) == {}

    def test_reasonless_suppression_is_a_finding(self):
        src = ("try:\n    pass\n"
               "# lint: disable=bare-except-at-seam\n"
               "except:\n    pass\n")
        rep = analyze_source(src)
        rules = {f.rule for f in rep.findings}
        assert BAD_SUPPRESSION in rules
        # and it suppressed NOTHING: the bare-except still fires
        assert "bare-except-at-seam" in rules

    def test_unknown_rule_is_a_finding(self):
        rep = analyze_source(
            "# lint: disable=no-such-rule -- because\nx = 1\n")
        assert any(f.rule == BAD_SUPPRESSION and
                   "no-such-rule" in f.message
                   for f in rep.findings)

    def test_unused_suppression_is_a_finding(self):
        rep = analyze_source(
            "# lint: disable=monotonic-clock -- stale\nx = 1\n")
        assert any(f.rule == UNUSED_SUPPRESSION
                   for f in rep.findings)

    def test_valid_suppression_suppresses(self):
        src = ("import time\n"
               "# lint: disable=monotonic-clock -- test fixture\n"
               "d = time.time() - 0\n")
        rep = analyze_source(src)
        assert rep.findings == []
        assert len(rep.suppressed) == 1
        assert rep.suppressed[0].reason == "test fixture"

    def test_comment_block_above_reaches_finding(self):
        """A suppression may sit at the top of the contiguous
        comment block directly above the flagged line (multi-line
        reasons wrap in a 72-column tree)."""
        src = ("import time\n"
               "# lint: disable=monotonic-clock -- the reason\n"
               "# continues in prose on following comment lines\n"
               "d = time.time() - 0\n")
        rep = analyze_source(src)
        assert rep.findings == []
        assert len(rep.suppressed) == 1

    def test_trailing_comment_does_not_leak_downward(self):
        src = ("import time\n"
               "x = 1  # lint: disable=monotonic-clock -- mine\n"
               "d = time.time() - 0\n")
        rep = analyze_source(src)
        assert any(f.rule == "monotonic-clock"
                   for f in rep.findings)


# ---------------------------------------------------------------
# per-rule fixtures: minimal violating + minimal clean
# ---------------------------------------------------------------

class TestMonotonicClock:
    def test_subtraction_flagged(self):
        fs = _findings("import time\nt0 = 0\n"
                       "d = time.time() - t0\n",
                       rule="monotonic-clock")
        assert len(fs) == 1 and fs[0].line == 3

    def test_augassign_flagged(self):
        fs = _findings("import time\nx = 0.0\nx += time.time()\n",
                       rule="monotonic-clock")
        assert len(fs) == 1

    def test_label_storage_clean(self):
        assert _findings(
            "import time\nlabel = time.time()\n"
            "d = {'wall': time.time()}\n",
            rule="monotonic-clock") == []

    def test_monotonic_arithmetic_clean(self):
        assert _findings(
            "import time\nd = time.monotonic() - 0.5\n",
            rule="monotonic-clock") == []


PR4_GAUGE_UNDER_LOCK = """
import threading

class Metrics:
    def __init__(self):
        self._lock = threading.Lock()
        self._depth_fn = None

    def snapshot(self):
        with self._lock:
            depth = self._depth_fn() if self._depth_fn else 0
        return {"queue_depth": depth}
"""

PR4_FIXED = """
import threading

class Metrics:
    def __init__(self):
        self._lock = threading.Lock()
        self._depth_fn = None

    def snapshot(self):
        depth_fn = self._depth_fn
        depth = depth_fn() if depth_fn else 0
        with self._lock:
            out = {"queue_depth": depth}
        return out
"""


class TestLockDiscipline:
    def test_pr4_gauge_under_lock_regression(self):
        """The exact PR-4 bug shape: SchedMetrics.snapshot calling
        the live depth gauge under its own lock."""
        fs = _findings(PR4_GAUGE_UNDER_LOCK,
                       rule="lock-discipline")
        assert len(fs) == 1
        assert "_depth_fn" in fs[0].message
        assert "PR-4" in fs[0].message

    def test_pr4_fixed_shape_clean(self):
        assert _findings(PR4_FIXED, rule="lock-discipline") == []

    def test_metric_call_under_lock_flagged(self):
        src = ("import threading\n"
               "class Ring:\n"
               "    def __init__(self, metrics):\n"
               "        self._cv = threading.Condition()\n"
               "        self.metrics = metrics\n"
               "    def end(self):\n"
               "        with self._cv:\n"
               "            self.metrics.slot_end()\n")
        fs = _findings(src, rule="lock-discipline")
        assert len(fs) == 1 and "metric call" in fs[0].message

    def test_cross_module_locking_entry_flagged(self):
        a = ("import threading\n"
             "from other import locked_entry\n"
             "LOCK_A = threading.Lock()\n"
             "def caller():\n"
             "    with LOCK_A:\n"
             "        locked_entry()\n")
        b = ("import threading\n"
             "LOCK_B = threading.Lock()\n"
             "def locked_entry():\n"
             "    with LOCK_B:\n"
             "        return 1\n")
        fs = _findings(a, rule="lock-discipline",
                       extra={"other.py": b})
        assert any("locking entry point" in f.message
                   for f in fs)

    def test_package_init_relative_import_resolves(self):
        """A level-1 relative import inside a package __init__
        resolves against the package itself, not a phantom leaf —
        locking entry points imported there must not become silent
        false negatives (review regression)."""
        init = ("import threading\n"
                "from .queue import locked_entry\n"
                "INIT_LOCK = threading.Lock()\n"
                "def facade():\n"
                "    with INIT_LOCK:\n"
                "        locked_entry()\n")
        queue = ("import threading\n"
                 "QLOCK = threading.Lock()\n"
                 "def locked_entry():\n"
                 "    with QLOCK:\n"
                 "        return 1\n")
        rep = analyze_source(
            init, rel="trivy_tpu/fakepkg/__init__.py",
            extra={"trivy_tpu/fakepkg/queue.py": queue})
        assert any(f.rule == "lock-discipline" and
                   "locking entry point" in f.message
                   for f in rep.findings), rep.findings

    def test_lock_order_cycle_flagged(self):
        src = ("import threading\n"
               "A = threading.Lock()\n"
               "B = threading.Lock()\n"
               "def one():\n"
               "    with A:\n"
               "        with B:\n"
               "            pass\n"
               "def two():\n"
               "    with B:\n"
               "        with A:\n"
               "            pass\n")
        fs = _findings(src, rule="lock-discipline")
        assert any("lock-order cycle" in f.message for f in fs)

    def test_consistent_nesting_clean(self):
        src = ("import threading\n"
               "A = threading.Lock()\n"
               "B = threading.Lock()\n"
               "def one():\n"
               "    with A:\n"
               "        with B:\n"
               "            pass\n"
               "def two():\n"
               "    with A:\n"
               "        with B:\n"
               "            pass\n")
        assert _findings(src, rule="lock-discipline") == []


PR5_POOL_SELF_JOIN = """
from trivy_tpu.runtime.hostpool import get_host_pool

def pack_segment(seg):
    pool = get_host_pool()
    return list(pool.map(str, seg))

def sieve_enqueue(items):
    return [get_host_pool().submit(pack_segment, s) for s in items]
"""

PR5_GUARDED = """
import threading
from trivy_tpu.runtime.hostpool import get_host_pool

def pack_segment(seg):
    if threading.current_thread().name.startswith(
            "trivy-hostpool"):
        return [str(s) for s in seg]
    pool = get_host_pool()
    return list(pool.map(str, seg))

def sieve_enqueue(items):
    return [get_host_pool().submit(pack_segment, s) for s in items]
"""


class TestHostpoolBlocking:
    def test_pr5_pool_self_join_regression(self):
        """The exact PR-5 bug shape: a task handed to the host
        pool that blocks on ``pool.map`` of the same pool."""
        fs = _findings(PR5_POOL_SELF_JOIN,
                       rule="hostpool-blocking")
        assert len(fs) == 1
        assert "pack_segment" in fs[0].message
        assert "PR-5" in fs[0].message

    def test_thread_name_guard_clean(self):
        assert _findings(PR5_GUARDED,
                         rule="hostpool-blocking") == []

    def test_same_named_nested_defs_both_indexed(self):
        """Two parents each defining a local ``job`` must not
        shadow each other in the index — the second job's blocking
        facts were silently dropped before (review regression)."""
        src = ("from trivy_tpu.runtime.hostpool import "
               "get_host_pool\n"
               "def parent_a(items):\n"
               "    def job(x):\n"
               "        return x\n"
               "    return get_host_pool().submit(job, items)\n"
               "def parent_b(items):\n"
               "    def job(x):\n"
               "        pool = get_host_pool()\n"
               "        return pool.submit(str, x).result()\n"
               "    return get_host_pool().submit(job, items)\n")
        fs = _findings(src, rule="hostpool-blocking")
        assert len(fs) >= 1
        assert any(f.line == 9 for f in fs), fs

    def test_transitive_reach_flagged(self):
        src = ("from trivy_tpu.runtime.hostpool import "
               "get_host_pool\n"
               "def leaf(x):\n"
               "    pool = get_host_pool()\n"
               "    return pool.submit(str, x).result()\n"
               "def middle(x):\n"
               "    return leaf(x)\n"
               "def outer(items):\n"
               "    return get_host_pool().submit(middle, items)\n")
        fs = _findings(src, rule="hostpool-blocking")
        assert len(fs) == 1 and "leaf" in fs[0].message


class TestDonationSafety:
    def test_read_after_donate_flagged(self):
        src = ("import jax\n"
               "def impl(a, b):\n"
               "    return a\n"
               "donated = jax.jit(impl, donate_argnums=(0,))\n"
               "def run(x, y):\n"
               "    out = donated(x, y)\n"
               "    return out + x.sum()\n")
        fs = _findings(src, rule="donation-safety")
        assert len(fs) == 1 and "'x'" in fs[0].message

    def test_undonated_arg_clean(self):
        src = ("import jax\n"
               "def impl(a, b):\n"
               "    return a\n"
               "donated = jax.jit(impl, donate_argnums=(0,))\n"
               "def run(x, y):\n"
               "    out = donated(x, y)\n"
               "    return out + y.sum()\n")
        assert _findings(src, rule="donation-safety") == []

    def test_rebinding_clears_the_taint(self):
        src = ("import jax\n"
               "def impl(a):\n"
               "    return a\n"
               "donated = jax.jit(impl, donate_argnums=(0,))\n"
               "def run(x):\n"
               "    x = donated(x)\n"
               "    return x.sum()\n")
        assert _findings(src, rule="donation-safety") == []

    def test_multiline_call_args_not_flagged(self):
        """Loads on the donation call's own wrapped argument list
        are the handoff, not a use-after-donate (the
        detect/batch.py false-positive shape)."""
        src = ("import jax\n"
               "def impl(a, b):\n"
               "    return a\n"
               "donated = jax.jit(impl, donate_argnums=(0, 1))\n"
               "def run(dr, di):\n"
               "    hits = donated(\n"
               "        dr, di)\n"
               "    return hits\n")
        assert _findings(src, rule="donation-safety") == []


class TestBareExcept:
    def test_bare_except_flagged(self):
        fs = _findings("try:\n    pass\nexcept:\n    pass\n",
                       rule="bare-except-at-seam")
        assert len(fs) == 1

    def test_silent_swallow_flagged(self):
        fs = _findings(
            "try:\n    pass\nexcept Exception:\n    pass\n",
            rule="bare-except-at-seam")
        assert len(fs) == 1

    def test_logged_handler_clean(self):
        src = ("import logging\n"
               "try:\n    pass\n"
               "except Exception as e:\n"
               "    logging.warning('boom %r', e)\n")
        assert _findings(src, rule="bare-except-at-seam") == []

    def test_narrow_handler_clean(self):
        assert _findings(
            "try:\n    pass\nexcept ValueError:\n    pass\n",
            rule="bare-except-at-seam") == []


class TestLabelCardinality:
    OPEN = ("import threading\n"
            "class FooMetrics:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._c = {}\n"
            "    def inc(self, name):\n"
            "        with self._lock:\n"
            "            self._c[name] = self._c.get(name, 0) + 1\n"
            "    def snapshot(self):\n"
            "        return dict(self._c)\n")

    def test_open_insert_flagged(self):
        fs = _findings(self.OPEN,
                       rule="unbounded-label-cardinality")
        assert len(fs) == 1 and "FooMetrics" in fs[0].message

    def test_overflow_fold_clean(self):
        capped = self.OPEN.replace(
            "            self._c[name] = "
            "self._c.get(name, 0) + 1\n",
            "            if name not in self._c and "
            "len(self._c) >= 64:\n"
            "                name = '<overflow>'\n"
            "            self._c[name] = "
            "self._c.get(name, 0) + 1\n")
        assert _findings(capped,
                         rule="unbounded-label-cardinality") == []

    def test_augassign_on_preset_keys_clean(self):
        src = ("class BarMetrics:\n"
               "    def __init__(self):\n"
               "        self._c = {'a': 0, 'b': 0}\n"
               "    def inc(self, name):\n"
               "        self._c[name] += 1\n"
               "    def snapshot(self):\n"
               "        return dict(self._c)\n")
        assert _findings(src,
                         rule="unbounded-label-cardinality") == []

    def test_non_metrics_class_ignored(self):
        src = ("class Plain:\n"
               "    def __init__(self):\n"
               "        self._d = {}\n"
               "    def put(self, key, v):\n"
               "        self._d[key] = v\n")
        assert _findings(src,
                         rule="unbounded-label-cardinality") == []


# ---------------------------------------------------------------
# the tree-wide acceptance gate
# ---------------------------------------------------------------

class TestTreeClean:
    def test_whole_tree_zero_unsuppressed_findings(self):
        """THE gate: ``python -m trivy_tpu.analysis`` ships clean —
        zero unsuppressed findings over the whole package, and
        every suppression carries a reason (reason-less or stale
        ones are findings themselves, so ``rep.ok`` covers them)."""
        rep = analyze_tree()
        assert rep.files > 150
        assert rep.ok, "\n" + rep.text()
        for f in rep.suppressed:
            assert f.reason.strip(), f

    def test_grep_lint_successor_covers_old_scope_and_more(self):
        """The AST ``monotonic-clock`` rule subsumes the deleted
        PR-8 grep test (tests/test_obs_timeline.py): obs/ stays
        wall-arithmetic-free, and the discipline now also covers
        sched/, watch/, memo/ — dirs the grep never swept."""
        rep = analyze_tree()
        offenders = [f for f in rep.findings + rep.suppressed
                     if f.rule == "monotonic-clock"]
        assert offenders == []


# ---------------------------------------------------------------
# CLI: exit codes, --json stability
# ---------------------------------------------------------------

class TestCli:
    def _main(self, argv, capsys):
        from trivy_tpu.analysis.__main__ import main
        rc = main(argv)
        return rc, capsys.readouterr().out

    def test_violation_exits_1_with_location(self, tmp_path,
                                             capsys):
        bad = tmp_path / "bad.py"
        bad.write_text("import time\nd = time.time() - 1\n")
        rc, out = self._main([str(bad)], capsys)
        assert rc == 1
        assert "bad.py:2: monotonic-clock:" in out

    def test_clean_exits_0(self, tmp_path, capsys):
        ok = tmp_path / "ok.py"
        ok.write_text("x = 1\n")
        rc, out = self._main([str(ok)], capsys)
        assert rc == 0
        assert "0 findings" in out

    def test_json_stable_sorted(self, tmp_path, capsys):
        """Byte-identical --json across runs, findings ordered by
        (path, line, rule) — CI artifact diffs stay reviewable."""
        for name, src in (
                ("b.py", "import time\nd = time.time() - 1\n"
                         "e = time.time() - 2\n"),
                ("a.py", "try:\n    pass\nexcept:\n    pass\n")):
            (tmp_path / name).write_text(src)
        rc1, out1 = self._main([str(tmp_path), "--json"], capsys)
        rc2, out2 = self._main([str(tmp_path), "--json"], capsys)
        assert rc1 == rc2 == 1
        assert out1 == out2
        doc = json.loads(out1)
        keys = [(f["path"], f["line"], f["rule"])
                for f in doc["findings"]]
        assert keys == sorted(keys)
        assert doc["counts"]["monotonic-clock"] == 2

    def test_rule_subset_and_catalog(self, tmp_path, capsys):
        bad = tmp_path / "bad.py"
        bad.write_text("import time\nd = time.time() - 1\n")
        rc, _ = self._main(
            [str(bad), "--rules", "bare-except-at-seam"], capsys)
        assert rc == 0        # clock rule not selected
        rc, out = self._main(["--list-rules"], capsys)
        assert rc == 0
        for rule in ("monotonic-clock", "lock-discipline",
                     "hostpool-blocking", "donation-safety",
                     "bare-except-at-seam",
                     "unbounded-label-cardinality"):
            assert rule in out
        rc, _ = self._main([str(bad), "--rules", "nope"], capsys)
        assert rc == 2

    def test_module_invocation_end_to_end(self, tmp_path):
        """The documented entry point: ``python -m
        trivy_tpu.analysis <file>`` in a real subprocess."""
        import subprocess
        import sys
        bad = tmp_path / "bad.py"
        bad.write_text("try:\n    pass\nexcept:\n    pass\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        p = subprocess.run(
            [sys.executable, "-m", "trivy_tpu.analysis",
             str(bad)],
            capture_output=True, text=True, env=env,
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
        assert p.returncode == 1, p.stdout + p.stderr
        assert "bare-except-at-seam" in p.stdout
