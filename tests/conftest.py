"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's localhost-server trick for multi-node testing
(SURVEY.md §4): a CPU backend with 8 fake devices stands in for a v5e-8
TPU mesh so sharding/collective code paths compile and run in CI.

``JAX_PLATFORMS=cpu`` here is an EXPLICIT request for the CPU platform:
``runtime.device.resolve_device`` accepts it for ``--backend tpu`` (an
explicit request is not a fallback), so the suite drives the device
path without a chip.
"""

import os

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402

# opt-in runtime lock-order witness for the WHOLE run
# (docs/static-analysis.md): TRIVY_TPU_LOCK_WITNESS=1 wraps every
# lock trivy_tpu constructs from here on and raises on an
# acquisition-order cycle or a host-pool self-join
from trivy_tpu.analysis.witness import \
    maybe_install_from_env  # noqa: E402

maybe_install_from_env()


@pytest.fixture
def lock_witness():
    """Install the runtime lock-order witness for one test — the
    seeded race storms run under it, so the PR-4 (lock-order
    cycle) and PR-5 (pool self-join) deadlock classes raise
    loudly inside the storm instead of silently returning. If the
    session-level env witness is already active, it is reused and
    left installed."""
    from trivy_tpu.analysis import witness as w

    pre = w.active_witness()
    wit = w.install_witness()
    try:
        yield wit
    finally:
        if pre is None:
            w.uninstall_witness()


@pytest.fixture(scope="session")
def mesh8():
    from trivy_tpu.parallel.mesh import make_mesh
    assert len(jax.devices()) >= 8
    return make_mesh(8)


@pytest.fixture
def hostile_corpus(tmp_path):
    """Materialize the adversarial ingest corpus
    (trivy_tpu/faults/hostile.py) at a test-friendly scale:
    ``hostile_corpus()`` → ([(builder name, image path)], limits)
    where ``limits`` are the matching scaled ResourceLimits."""
    from trivy_tpu.faults.hostile import build_corpus, hostile_limits

    def make(scale: float = 0.05, only=None, seed: int = 20260804):
        corpus = build_corpus(str(tmp_path / "hostile"), seed=seed,
                              only=only, scale=scale)
        return corpus, hostile_limits(scale)

    return make


@pytest.fixture
def make_faults():
    """Build a deterministic FaultInjector from a --fault-spec
    string, e.g. ``make_faults("poison-image:poison=img3.tar")``
    (docs/robustness.md has the scenario list)."""
    from trivy_tpu.faults import FaultInjector, parse_fault_spec

    def make(spec: str):
        return FaultInjector(parse_fault_spec(spec))

    return make
