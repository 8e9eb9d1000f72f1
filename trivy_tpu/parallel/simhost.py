"""Multi-process simulation worker for the multi-host contract
(docs/performance.md §8 "Multi-host mesh").

``python -m trivy_tpu.parallel.simhost <spec.json> <out.json>``
runs as ONE simulated host: it reads the shared fleet spec, derives
the global LPT shard layout exactly like a real pod process would
(:func:`trivy_tpu.parallel.multihost.host_shard_layout` — a pure
function of the fleet, so no coordination traffic), scans only the
slice it owns on a process-local CPU mesh, and writes its layout +
normalized reports. The parent (``pytest -m async_rt``, ``pytest -m
fleetobs``) spawns P of these with ``TRIVY_TPU_PROCESS_ID=0..P-1``
and gates two invariants the real pod depends on:

* **layout parity** — every process reports the identical global
  assignment;
* **findings byte-identity** — the union of per-host reports equals
  a single-host scan of the whole fleet.

Spec JSON: ``{"paths": [tar, ...], "devices": N (per host),
"db_fixture": {bucket: {pkg: {cve: advisory}}},
"vulns": {cve: {...}}, "dispatch_depth": D}``. Resident advisory
tables are compiled per process — each host stages its own copy
through the ResidentTables generation machinery, which is exactly
the per-host replication contract of the real pod.

Fleet observability (docs/observability.md "Fleet plane"): an
optional spec ``"traceparent"`` roots this process's span tree under
the parent's span — the parent's flight recorder then names every
host in ONE cross-process trace. ``"clock_port_file"`` starts a
monotonic ClockServer and writes its port, so the parent can
estimate this process's clock offset pairwise; the output gains
``"trace"`` (ids for child-link assertions) and ``"timeline"`` (the
serialized span export + epoch that MergedTimeline aligns).
"""

from __future__ import annotations

import json
import sys


def _normalized(results) -> list:
    out = []
    for r in results:
        if r.error:
            out.append([r.name, "error", r.error])
        else:
            out.append([r.name, json.dumps(r.report.to_dict(),
                                           sort_keys=True)])
    return out


def run_simhost(spec: dict, topo=None) -> dict:
    """One simulated host's scan: returns {assign, indices,
    reports}. Importable (the async_rt tests call it in-process for
    the single-host reference arm)."""
    import os

    from . import make_mesh
    from .multihost import (host_shard_layout, local_indices,
                            topology_from_env)
    from ..db import AdvisoryStore, CompiledDB
    from ..runtime import BatchScanRunner

    topo = topology_from_env() if topo is None else topo
    paths = list(spec["paths"])
    volumes = [os.path.getsize(p) for p in paths]
    assign = host_shard_layout(volumes, topo.num_processes)
    mine = local_indices(volumes, topo)

    store = AdvisoryStore()
    for bucket, pkgs in (spec.get("db_fixture") or {}).items():
        for pkg, advs in pkgs.items():
            for cve, adv in advs.items():
                store.put_advisory(bucket, pkg, cve, adv)
    for cve, vuln in (spec.get("vulns") or {}).items():
        store.put_vulnerability(cve, vuln)
    cdb = CompiledDB.compile(store)

    mesh = make_mesh(min(int(spec.get("devices") or 1),
                         _device_count()))
    runner = BatchScanRunner(
        store=cdb, backend="tpu", mesh=mesh,
        dispatch_depth=int(spec.get("dispatch_depth") or 2))

    from ..obs.propagate import (EMPTY_CONTEXT, ClockServer,
                                 parse_traceparent)
    from ..obs.timeline import export_tracer
    from ..obs.trace import get_tracer

    clock = None
    port_file = str(spec.get("clock_port_file") or "")
    if port_file:
        clock = ClockServer()
        clock.write_port_file(port_file)

    tracer = get_tracer()
    process = f"host{topo.process_id}"
    ctx = parse_traceparent(
        str(spec.get("traceparent") or "")) or EMPTY_CONTEXT
    # the simhost root: a LOCAL root span (it completes this
    # process's bucket) carrying the parent process's span as its
    # remote parent, so the merged trace links across the seam
    root = tracer.start_span(
        "simhost", trace_id=ctx.trace_id,
        remote_parent=ctx.parent_span_id,
        attrs={"process": process})
    try:
        with root.activate():
            results = runner.scan_paths([paths[i] for i in mine])
        root.end()
    except BaseException:
        root.end(status="failed")
        raise
    finally:
        if clock is not None:
            clock.close()
    return {
        "process_id": topo.process_id,
        "num_processes": topo.num_processes,
        "assign": assign,
        "indices": mine,
        "reports": _normalized(results),
        "trace": {
            "trace_id": root.trace_id,
            "root_span_id": root.span_id,
            "remote_parent": ctx.parent_span_id,
        },
        "timeline": export_tracer(tracer, process=process),
    }


def _device_count() -> int:
    import jax
    return len(jax.devices())


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 2:
        print("usage: python -m trivy_tpu.parallel.simhost "
              "<spec.json> <out.json>", file=sys.stderr)
        return 2
    with open(argv[0], encoding="utf-8") as f:
        spec = json.load(f)
    out = run_simhost(spec)
    with open(argv[1], "w", encoding="utf-8") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
