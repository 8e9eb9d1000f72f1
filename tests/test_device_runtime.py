"""Device selection, the compile/run boundary and the one compile
cache (ISSUE 21 B–D): nothing on the main path may hide the device.

* ``--backend tpu`` off a TPU is an error unless ``JAX_PLATFORMS``
  asks for the CPU explicitly (this suite's setting);
* a device program that does not compile fails the batch — no
  bisect, no quarantine, no host fallback — and the process exits
  non-zero; run-time failures keep the ladder (tests/test_faults.py);
* the cache helper honours ``JAX_COMPILATION_CACHE_DIR`` and sets no
  directory then, otherwise uses the fixed in-checkout path;
* a ``--server`` client never initialises a backend.
"""

import json
import os
import subprocess
import sys
import threading

import pytest

from trivy_tpu.ops.program import DeviceProgram, DeviceProgramError
from trivy_tpu.utils.synth import tiny_fleet

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def fleet(tmp_path):
    from trivy_tpu.db import CompiledDB
    paths, store = tiny_fleet(str(tmp_path), n_images=3,
                              n_advisories=24)
    cdb = CompiledDB.compile(store)
    cdb.save(str(tmp_path / "cdb"))
    return paths, cdb, str(tmp_path / "cdb")


def _cli(argv: list) -> int:
    from trivy_tpu import cli
    return cli.main(argv)


# ---------------------------------------------------------------
# B. fail-not-fallback device selection
# ---------------------------------------------------------------

class TestResolveDevice:
    def test_explicit_cpu_request_is_not_a_fallback(self):
        from trivy_tpu.runtime.device import (device_identity,
                                              resolve_device)
        info = resolve_device("tpu")        # JAX_PLATFORMS=cpu here
        assert info.platform == "cpu" and info.devices >= 1
        assert device_identity() == {
            "platform": "cpu", "device_kind": info.device_kind,
            "devices": info.devices}

    @pytest.mark.parametrize("env", [None, "", "tpu,cpu"])
    def test_tpu_backend_off_tpu_raises(self, monkeypatch, env):
        from trivy_tpu.runtime.device import (DeviceUnavailable,
                                              resolve_device)
        if env is None:
            monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        else:
            monkeypatch.setenv("JAX_PLATFORMS", env)
        with pytest.raises(DeviceUnavailable, match="no fallback"):
            resolve_device("tpu")

    def test_cpu_ref_touches_nothing(self, monkeypatch):
        import jax
        from trivy_tpu.runtime.device import resolve_device
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(jax, "devices", lambda *a: 1 / 0)
        assert resolve_device("cpu-ref").platform == ""

    def test_failed_backend_init_raises(self, monkeypatch):
        import jax
        from trivy_tpu.runtime.device import (DeviceUnavailable,
                                              resolve_device)

        def boom(*a):
            raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.setattr(jax, "devices", boom)
        with pytest.raises(DeviceUnavailable, match="another "
                                                    "process"):
            resolve_device("tpu")

    @pytest.mark.parametrize("sched", ["on", "off"])
    def test_cli_exits_nonzero_with_message(self, monkeypatch,
                                            capsys, fleet, tmp_path,
                                            sched):
        paths, _cdb, prefix = fleet
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        rc = _cli(["image"] + paths + [
            "--compiled-db", prefix, "--backend", "tpu",
            "--sched", sched, "--format", "json",
            "--cache-dir", str(tmp_path / "cache")])
        assert rc == 1
        err = capsys.readouterr().err
        assert "needs the tpu platform" in err
        assert "Traceback" not in err

    def test_host_only_commands_need_no_device(self, monkeypatch,
                                               tmp_path):
        # misconfiguration scans dispatch no kernel: no TPU, no error
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        (tmp_path / "Dockerfile").write_text("FROM alpine\n")
        assert _cli(["config", str(tmp_path), "--format", "json",
                     "--output", str(tmp_path / "o.json")]) == 0


class TestBuildInfo:
    def test_build_info_names_the_real_device(self):
        from trivy_tpu.rpc.server import ScanServer
        from trivy_tpu.runtime.device import resolve_device
        info = resolve_device("tpu")
        srv = ScanServer()
        try:
            build = srv.health()["build"]
            assert build["platform"] == info.platform == "cpu"
            assert build["device_kind"] == info.device_kind
            assert build["devices"] == info.devices
            assert "backend" not in build
            line = [ln for ln in srv.metrics_text().splitlines()
                    if ln.startswith("trivy_tpu_build_info{")]
            assert len(line) == 1
            assert 'platform="cpu"' in line[0]
            assert f'devices="{info.devices}"' in line[0]
        finally:
            srv.close()

    def test_sim_replica_mirrors_the_labels(self):
        from trivy_tpu.router.sim import SimReplica
        from trivy_tpu.sched.metrics import build_info
        sim = SimReplica(name="s")
        assert set(sim.build_info()) == set(build_info())


# ---------------------------------------------------------------
# B. a compile failure is a program fault
# ---------------------------------------------------------------

class _Unlowerable:
    """Stands in for a jitted kernel the backend refuses."""

    def lower(self, *args):
        raise RuntimeError("INTERNAL: Mosaic failed to compile "
                           "TPU kernel (injected)")

    def __call__(self, *args):          # pragma: no cover
        raise AssertionError("a refused program must never run")


def _refuse_sieve(monkeypatch) -> None:
    from trivy_tpu.ops import dfa
    broken = DeviceProgram(_Unlowerable(), "dfa_fused_sieve")
    monkeypatch.setattr(dfa.DfaTable, "fused_sieve",
                        lambda self, specs, platform: broken)


class TestProgramFault:
    def test_device_program_separates_compile_from_run(self):
        import jax
        import numpy as np
        events = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, secs, **kw: events.append(name))
        prog = DeviceProgram(jax.jit(lambda x: x * 3 + 1), "triple")
        x = np.arange(12, dtype=np.int32).reshape(3, 4)
        events.clear()
        assert np.array_equal(np.asarray(prog(x)), x * 3 + 1)
        compiles = [e for e in events if e.endswith(
            "backend_compile_duration")]
        # lower().compile() then the jit call: ONE backend compile
        assert len(compiles) == 1
        events.clear()
        prog(x)
        assert not [e for e in events if "compile" in e
                    and "trace" not in e]
        with pytest.raises(DeviceProgramError, match="triple"):
            DeviceProgram(jax.jit(lambda x: x @ x), "triple")(
                np.zeros((2, 3), np.float32))

    def test_scheduler_fails_the_batch_no_ladder(self, monkeypatch,
                                                 fleet):
        from trivy_tpu.runtime import BatchScanRunner
        paths, cdb, _ = fleet
        _refuse_sieve(monkeypatch)
        runner = BatchScanRunner(store=cdb, sched="on")
        try:
            results = runner.scan_paths(paths)
            stats = runner.scheduler.stats()["counters"]
            fault = runner.scheduler.program_fault
        finally:
            runner.close()
        assert [r.status for r in results] == ["failed"] * 3
        for r in results:
            assert (r.causes[0].stage, r.causes[0].kind) == \
                ("device", "program_fault")
            assert "Mosaic failed to compile" in r.error
        assert isinstance(fault, DeviceProgramError)
        assert stats["program_faults"] >= 1
        for k in ("batch_bisects", "quarantined", "host_fallbacks"):
            assert stats.get(k, 0) == 0, k

    def test_runtime_failure_keeps_the_ladder(self, fleet,
                                              make_faults):
        # the control: a device failure at RUN time is still
        # bisected and finished on the host, slot degraded
        from trivy_tpu.runtime import BatchScanRunner
        paths, cdb, _ = fleet
        runner = BatchScanRunner(
            store=cdb, sched="on",
            fault_injector=make_faults(
                f"poison-image:poison={os.path.basename(paths[1])}"))
        try:
            results = runner.scan_paths(paths)
            stats = runner.scheduler.stats()["counters"]
            fault = runner.scheduler.program_fault
        finally:
            runner.close()
        assert fault is None
        assert [r.status for r in results] == \
            ["ok", "degraded", "ok"]
        assert stats["quarantined"] == 1
        assert stats["host_fallbacks"] == 1

    @pytest.mark.parametrize("sched", ["on", "off"])
    def test_cli_exits_nonzero(self, monkeypatch, capsys, fleet,
                               tmp_path, sched):
        paths, _cdb, prefix = fleet
        _refuse_sieve(monkeypatch)
        rc = _cli(["image"] + paths + [
            "--compiled-db", prefix, "--sched", sched,
            "--format", "json",
            "--output", str(tmp_path / "out.json"),
            "--cache-dir", str(tmp_path / "cache")])
        assert rc == 1
        err = capsys.readouterr().err
        assert "failed to compile" in err
        assert "degraded" not in err

    def test_warm_ladders_lets_compile_errors_through(
            self, monkeypatch, tmp_path, fleet):
        from trivy_tpu.runtime import aot
        _paths, cdb, _prefix = fleet

        def refuse(*a, **kw):
            raise DeviceProgramError("interval_hits refused")

        monkeypatch.setattr(aot, "precompile_interval_shapes",
                            refuse)
        with pytest.raises(DeviceProgramError):
            aot.warm_ladders(store=cdb,
                             cache_dir=str(tmp_path / "c"))

    def test_server_exits_nonzero_on_a_latched_fault(self):
        from trivy_tpu.rpc.server import ScanServer, serve_forever
        srv = ScanServer(sched="on")
        srv.scheduler.program_fault = DeviceProgramError("refused")
        rc = []
        t = threading.Thread(
            target=lambda: rc.append(serve_forever(
                "127.0.0.1", 0, srv, drain_timeout_s=2.0)))
        t.start()
        t.join(timeout=15)
        assert not t.is_alive()
        assert rc == [1]


class TestProfileTrace:
    def _refuse_trace(self, monkeypatch, platform):
        import jax

        def boom(*a, **kw):
            raise RuntimeError("profiler plugin missing")

        monkeypatch.setattr(jax.profiler, "trace", boom)
        monkeypatch.setattr(jax, "default_backend",
                            lambda: platform)

    def test_fails_on_tpu_when_the_trace_cannot_start(
            self, monkeypatch, tmp_path):
        from trivy_tpu.obs.profiler import device_trace
        self._refuse_trace(monkeypatch, "tpu")
        with pytest.raises(RuntimeError, match="device trace"):
            with device_trace(str(tmp_path / "p")):
                pass

    def test_cpu_platform_keeps_the_host_profile(
            self, monkeypatch, tmp_path):
        from trivy_tpu.obs.profiler import device_trace
        self._refuse_trace(monkeypatch, "cpu")
        with device_trace(str(tmp_path / "p")):
            pass
        assert (tmp_path / "p" / "host_profile.folded").exists()

    def test_deviceless_process_starts_no_jax_trace(
            self, monkeypatch, tmp_path):
        from trivy_tpu.obs.profiler import device_trace
        self._refuse_trace(monkeypatch, "tpu")
        with device_trace(str(tmp_path / "p"), device=False):
            pass
        assert (tmp_path / "p" / "host_profile.folded").exists()


# ---------------------------------------------------------------
# B. the --server client is thin
# ---------------------------------------------------------------

_CLIENT = """
import sys
from trivy_tpu import cli
rc = cli.main(sys.argv[1:])
import jax._src.xla_bridge as xb
print("BACKENDS_INITIALIZED", xb.backends_are_initialized())
sys.exit(rc)
"""


def test_server_client_never_initialises_a_backend(fleet, tmp_path):
    from trivy_tpu.rpc.server import ScanServer, serve
    paths, cdb, _ = fleet
    srv = ScanServer(store=cdb, sched="on", token="t")
    httpd, _ = serve(port=0, server=srv)
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    out = tmp_path / "client.json"
    try:
        # no JAX_PLATFORMS at all: a client that touched jax's
        # backends would initialise whatever the machine has
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_PLATFORMS"}
        p = subprocess.run(
            [sys.executable, "-c", _CLIENT, "image", "--input",
             paths[0], "--server", url, "--token", "t",
             "--format", "json", "--output", str(out),
             "--profile-out", str(tmp_path / "prof")],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=120)
    finally:
        httpd.shutdown()
        srv.close()
    assert p.returncode == 0, p.stderr[-2000:]
    assert "BACKENDS_INITIALIZED False" in p.stdout
    assert "device: platform=" not in p.stderr
    doc = json.loads(out.read_text())
    classes = {r["Class"] for r in doc["Results"]}
    assert {"os-pkgs", "secret"} <= classes   # host sieve found it


# ---------------------------------------------------------------
# C. the dry run uses the devices the process has
# ---------------------------------------------------------------

def test_dryrun_multichip_fails_clearly_without_devices():
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__ as entry
    finally:
        sys.path.remove(REPO)
    with pytest.raises(RuntimeError, match="needs 64 devices"):
        entry.dryrun_multichip(64)


# ---------------------------------------------------------------
# D. one compile cache, placeable from outside
# ---------------------------------------------------------------

class TestCompileCache:
    @pytest.fixture
    def updates(self, monkeypatch):
        """Record jax.config.update calls without applying them —
        the suite's own jax keeps its configuration."""
        import jax
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: calls.append((k, v)))
        return calls

    def test_env_var_wins_and_no_directory_is_set(
            self, monkeypatch, tmp_path, updates):
        from trivy_tpu.runtime.aot import configure_compile_cache
        env_dir = str(tmp_path / "from-env")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        got = configure_compile_cache(str(tmp_path / "from-flag"))
        assert got == env_dir and os.path.isdir(env_dir)
        assert "jax_compilation_cache_dir" not in dict(updates)
        assert not (tmp_path / "from-flag").exists()

    def test_unset_uses_the_fixed_in_checkout_path(
            self, monkeypatch, updates):
        from trivy_tpu.runtime import aot
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR",
                           raising=False)
        assert aot.DEFAULT_CACHE_DIR == \
            os.path.join(REPO, ".jax_cache")
        assert aot.configure_compile_cache() == \
            aot.DEFAULT_CACHE_DIR
        assert dict(updates)["jax_compilation_cache_dir"] == \
            aot.DEFAULT_CACHE_DIR
        # twice the same path: never a temp name, pid or timestamp
        assert aot.configure_compile_cache() == \
            aot.DEFAULT_CACHE_DIR

    def test_compile_cache_flag_places_it_when_env_is_unset(
            self, monkeypatch, tmp_path, updates):
        from trivy_tpu.runtime.aot import configure_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR",
                           raising=False)
        flag = str(tmp_path / "from-flag")
        assert configure_compile_cache(flag) == flag
        assert dict(updates)["jax_compilation_cache_dir"] == flag

    def test_gitignore_lists_the_cache_and_smoke_output(self):
        with open(os.path.join(REPO, ".gitignore")) as f:
            ignored = f.read().split()
        assert ".jax_cache/" in ignored
        assert "chip_smoke_out/" in ignored
        assert "chiprun_out/" in ignored
