"""Device selection — one function for every entry point.

``--backend tpu`` means the kernels run on a TPU. A process that
asked for the TPU and landed on another platform — libtpu failed to
initialise, another process holds the chip, no chip at all — would
otherwise run the ``jnp`` path under the name "tpu" and exit 0.
:func:`resolve_device` makes that an error, with one exception: an
environment whose ``JAX_PLATFORMS`` puts ``cpu`` first asked for the
CPU explicitly (the test suite's setting), and an explicit request
is not a fallback.

Every entry point that dispatches device work (CLI scan commands,
``server``, ``watch``, ``benchmark/run.py``, ``chip_smoke.py``
stages) calls :func:`resolve_device` first thing: it places the
persistent compile cache (``runtime.aot.configure_compile_cache``),
initialises the backend, checks it, and logs platform, device kind
and count once. ``build_info()`` / ``/healthz`` report what it
found (:func:`device_identity`), not the flag.
"""

from __future__ import annotations

import os
from dataclasses import asdict, dataclass
from typing import Optional

from ..utils import get_logger

log = get_logger("runtime.device")


class DeviceUnavailable(RuntimeError):
    """The requested backend's platform is absent, busy or failed
    to initialise."""


@dataclass(frozen=True)
class DeviceInfo:
    platform: str = ""        # jax.devices()[0].platform
    device_kind: str = ""     # jax.devices()[0].device_kind
    devices: int = 0          # len(jax.devices())


# what resolve_device found for this process (jax backends are
# process-global, so is this); None until a device backend resolved
_resolved: Optional[DeviceInfo] = None


def device_identity() -> dict:
    """``{platform, device_kind, devices}`` of the resolved device,
    empty values in a process that never resolved one (thin clients,
    the router front, cpu-ref runs). Never initialises a backend."""
    return asdict(_resolved or DeviceInfo())


def on_accelerator() -> bool:
    """True in a process whose :func:`resolve_device` found another
    platform than the CPU: where a new shape's compile costs seconds
    (5.7 to 6.0 s a sieve rung on a TPU v5e, PERF.md) and not the
    tens of milliseconds XLA:CPU takes. Never initialises a
    backend."""
    return (_resolved or DeviceInfo()).platform not in ("", "cpu")


def _cpu_requested() -> bool:
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    return first.strip().lower() == "cpu"


def resolve_device(backend: str = "tpu") -> DeviceInfo:
    """Resolve ``backend`` (``tpu`` | ``cpu`` | ``cpu-ref``) to the
    device this process runs on, or raise :class:`DeviceUnavailable`.
    ``cpu-ref`` is the host reference engine: no device, jax is not
    touched."""
    global _resolved
    if backend == "cpu-ref":
        return DeviceInfo()
    import jax

    from ..obs.trace import set_annotator
    from .aot import configure_compile_cache
    # from here on every phase_span, and each DeviceProgram's
    # compile and dispatch, is also a span on the profiler's clock
    # (``--profile-out DIR`` shows them above the device rows);
    # outside a profiler session an annotation costs a flag test
    set_annotator(jax.profiler.TraceAnnotation)
    try:
        configure_compile_cache()
    except OSError as e:
        log.warning("persistent compile cache unavailable: %r", e)
    if backend == "cpu":
        # an explicit request for the CPU platform; a no-op once a
        # backend is up, which the platform check below then reports
        jax.config.update("jax_platforms", "cpu")
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise DeviceUnavailable(
            f"--backend {backend}: jax could not initialise a "
            f"device (absent, or held by another process): {e}") \
            from e
    info = DeviceInfo(platform=devices[0].platform,
                      device_kind=devices[0].device_kind,
                      devices=len(devices))
    want = "cpu" if backend == "cpu" else "tpu"
    if info.platform != want and not (
            want == "tpu" and info.platform == "cpu"
            and _cpu_requested()):
        raise DeviceUnavailable(
            f"--backend {backend} needs the {want} platform but jax "
            f"is on {info.platform!r} ({info.device_kind}); no "
            "fallback. Set JAX_PLATFORMS=cpu (or --backend cpu) to "
            "run the device path on the CPU on purpose.")
    if info != _resolved:
        _resolved = info
        log.info("device: platform=%s device_kind=%s devices=%d",
                 info.platform, info.device_kind, info.devices)
    return info
