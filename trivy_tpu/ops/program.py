"""The compile/run boundary of a device program.

``jax.jit`` traces, lowers and compiles inside the first call for
each argument signature, so a kernel the backend refuses (a Mosaic
lowering gap, VMEM or tiling limits) and a run-time failure of a
program that did compile surface from the same call site. The two
are different faults (docs/robustness.md): a refused program is a
bug in this package and fails the batch and the process; a run-time
failure is data- or device-dependent and keeps the bisect →
quarantine → host-fallback ladder.

:class:`DeviceProgram` makes the boundary explicit: each new
signature is lowered and compiled BEFORE the call, and any failure
there raises :class:`DeviceProgramError`. jit keeps the lowering and
the executable it built, so the call that follows reuses them
(``tests/test_device_runtime.py`` pins that no second compile
happens).
"""

from __future__ import annotations

import weakref

# every live DeviceProgram, for compiled_programs()
_PROGRAMS: "weakref.WeakSet" = weakref.WeakSet()


def compiled_programs() -> list:
    """Sorted ``name(dtype[shape], …)`` of every signature this
    process compiled through a DeviceProgram — what a warm start
    should find in the persistent compile cache."""
    return sorted({f"{p.name}({sig})" for p in list(_PROGRAMS)
                   for sig in list(p._compiled.values())})


class DeviceProgramError(RuntimeError):
    """A device program failed to trace, lower or compile — a
    program fault, never retried, bisected or finished on the
    host."""


class DeviceProgram:
    """A jitted callable with an explicit compile step per
    signature. Attribute access falls through to the jitted
    function (``lower``, ``clear_cache`` …)."""

    def __init__(self, jitted, name: str):
        self._jitted = jitted
        self.name = name
        self._dispatch_span = f"trivy.dispatch.{name}"
        self._compiled: dict = {}     # signature -> readable shapes
        _PROGRAMS.add(self)

    def __call__(self, *args):
        from ..obs.trace import annotation
        sig = tuple((getattr(a, "shape", None),
                     str(getattr(a, "dtype", "")),
                     getattr(a, "sharding", None)) for a in args)
        if sig not in self._compiled:
            shapes = ", ".join(f"{s[1]}{list(s[0] or ())}"
                               for s in sig)
            try:
                with annotation(f"trivy.compile.{self.name}"):
                    self._jitted.lower(*args).compile()
            except Exception as e:
                raise DeviceProgramError(
                    f"device program {self.name!r} failed to "
                    f"compile for ({shapes}): "
                    f"{type(e).__name__}: {e}") from e
            # a dict store is atomic under the GIL; two threads
            # racing a new signature at worst both compile
            self._compiled[sig] = shapes
        # the enqueue: jit returns before the device has finished
        with annotation(self._dispatch_span):
            return self._jitted(*args)

    def __getattr__(self, attr: str):
        return getattr(self._jitted, attr)
