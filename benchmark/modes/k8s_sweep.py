"""Traffic mode ``k8s_sweep``: closed sweeps of a whole cluster, back
to back, each one ``K8sScanner.scan(ManifestClient(dir))`` on the
runner that ``trivy-tpu k8s`` builds (``trivy_tpu.k8s.build_runner``:
the scheduler, and a mesh over every chip the process sees), and the
summary the command prints. Parameters in the traffic file: ``warmup``
(the images of the warm-up's own cluster), ``sched``,
``security_checks``; the cluster is the configuration's ``cluster``
(``gen_k8s.plan``).

Set-up: the manifests and every image (``gen_k8s.make``). The runner
``run.py`` built is replaced by the command's, as ``open_loop_served``
seats its own, so the counters the metrics read are those of the
scheduler the sweeps ride. The warm-up sweeps the warm-up cluster.
Every sweep starts with the blob cache empty (``fresh_cache``: a
nightly scan job whose cache is a pod's ``emptyDir``). The window ends
with the sweep that crosses ``--seconds``; a unit is a distinct image
of a completed sweep.

After the window every resource of every completed sweep is held to
``reference_k8s``: one entry an image ref it names, none with an
error, and the reference's counts in the summary the window printed.
The last sweep's report is rendered by the program's own
``write_k8s_report(fmt="json")`` and each resource's entries, rendered
as one report, must give the reference's findings (rendering every
sweep would cost about 20 s a sweep after the window). A program
without ``build_runner`` cannot run the cell and says so at once.
"""

from __future__ import annotations

import io
import json
import time

from jax.profiler import TraceAnnotation

try:
    from trivy_tpu.k8s import K8sScanner, ManifestClient, build_runner
except ImportError as e:            # run.py: "cannot run", exit 3
    raise ImportError(
        "this program has no trivy_tpu.k8s.build_runner, the function "
        f"the k8s command builds its runner with ({e})") from e

# the one-device sweep beside the mesh's (examples/k8s_sweep_probe.py)
ONE_DEVICE = False
# the tests plant faults here: a function of a sweep's report and of
# the data
PLANT = None


def make_data(cell, work: str) -> dict:
    import gen_k8s
    return gen_k8s.make(cell.sizes, cell.cell["config"]["cluster"],
                        cell.traffic["warmup"], work, cell.seed)


def sweep(cell, data: dict, directory: str):
    """One sweep as the command makes it: the cluster's report and
    its summary."""
    from trivy_tpu.k8s.report import write_k8s_report
    report = data["scanner"].scan(ManifestClient(directory))
    summary = io.StringIO()
    write_k8s_report(report, fmt="table", mode="summary",
                     output=summary)
    return report, summary.getvalue()


def warm_up(cell, data: dict) -> list:
    """The command's runner in the harness's, and the warm-up
    cluster swept on it."""
    t = cell.traffic
    store = cell.runner.store
    cell.runner.close()
    cell.runner = build_runner(store, backend="tpu", sched=t["sched"],
                               mesh=None if ONE_DEVICE else "visible")
    data["scanner"] = K8sScanner(
        store=store, backend="tpu", images_dir=data["images"],
        security_checks=list(t["security_checks"]),
        runner=cell.runner)
    mesh = cell.runner.mesh
    cell.say(f"k8s: {data['workloads']} workloads, "
             f"{data['image_refs']} image refs, {len(data['refs'])} "
             f"distinct images; mesh "
             f"{dict(mesh.shape) if mesh is not None else None}")
    report, _ = sweep(cell, data, data["warmup"])
    return [f"{r.namespace}/{r.kind}/{r.name}: {r.error}"
            for r in report.vulnerabilities if r.error]


def drive(cell, data: dict, seconds: float) -> dict:
    sweeps, took = [], []
    t0 = time.monotonic()
    with TraceAnnotation("bench.window"):
        while True:
            cell.fresh_cache()
            s0 = time.monotonic()
            with TraceAnnotation("bench.pass"):
                sweeps.append(sweep(cell, data, data["cluster"]))
            t1 = time.monotonic()
            took.append(round(t1 - s0, 3))
            if t1 - t0 >= seconds:
                break
    return {"finished": [], "in_flight": [], "sweeps": sweeps,
            "units": len(sweeps) * len(data["refs"]),
            "window_s": t1 - t0, "wraps": 0,
            "lines": [f"sweeps (s): {took}"]}


class Answer:
    """What ``check.compare`` reads of a slot."""

    def __init__(self, report):
        self.status, self.error, self.report = "ok", "", report


def summary_counts(text: str) -> dict:
    """``{(namespace, kind, name): [C, H, M, L, U]}`` of a printed
    summary's vulnerability column."""
    out = {}
    for line in text.splitlines():
        cells = [c.strip() for c in line.strip("|").split("|")]
        if len(cells) != 4 or "/" not in cells[1] \
                or cells[0] == "Namespace":
            continue
        kind, name = cells[1].split("/", 1)
        out[(cells[0], kind, name)] = [int(x) for x in
                                       cells[2].split("/")]
    return out


def rendered(report) -> dict:
    """``{resource: [entry, ...]}`` of the report as the command
    writes it (the severity filter first, then ``write_k8s_report``'s
    json)."""
    from trivy_tpu.cli import DEFAULT_SEVERITIES, _severities
    from trivy_tpu.k8s.report import write_k8s_report
    from trivy_tpu.scan import filter_results
    sev = _severities(DEFAULT_SEVERITIES)
    for res in report.vulnerabilities:
        filter_results(res.results, sev)
    buf = io.StringIO()
    write_k8s_report(report, fmt="json", output=buf)
    out: dict = {}
    for e in json.loads(buf.getvalue()).get("Vulnerabilities") or []:
        out.setdefault((e.get("Namespace", ""), e["Kind"], e["Name"]),
                       []).append(e)
    return out


def grouped(report) -> dict:
    """``{resource: [entry, ...]}`` of the report as the scan left
    it, an entry holding its error alone."""
    out: dict = {}
    for r in report.vulnerabilities:
        out.setdefault((r.namespace, r.kind, r.name), []).append(
            {"Error": r.error})
    return out


def answers(cell, rec: dict, data: dict) -> dict:
    """Every resource of every completed sweep against the reference:
    its entries (one an image ref, none with an error) and its row
    of the summary; of the last sweep, its entries rendered as one
    report whose findings are compared."""
    import reference_k8s
    from trivy_tpu.types import Report
    from trivy_tpu.types.convert import result_from_dict
    checks = cell.traffic["security_checks"]
    want = reference_k8s.cluster_findings(
        cell.table, data["cluster"], data["facts"], checks)
    ctrl = reference_k8s.cluster_findings(
        cell.table, data["cluster"], data["facts"], checks,
        control=True) if cell.control else {}
    out = []
    last = len(rec["sweeps"]) - 1
    for s, (report, summary) in enumerate(rec["sweeps"]):
        if PLANT is not None:
            PLANT(report, data)
        rows = summary_counts(summary)
        got = rendered(report) if s == last else grouped(report)
        for key in sorted(set(want) | set(got)):
            name = f"sweep{s}:{'/'.join(key)}"
            w, entries = want.get(key), got.get(key, [])
            bad = [e["Error"] for e in entries if e.get("Error")]
            if w is None:
                bad.append("a resource the cluster does not hold")
            elif len(entries) != w["refs"]:
                bad.append(f"{len(entries)} image entries, the "
                           f"workload names {w['refs']} images")
            elif rows.get(key) != [w["counts"][v]
                                   for v in reference_k8s.SEVS]:
                bad.append(f"summary {rows.get(key)}, the "
                           f"reference's {w['counts']}")
            if bad:
                out.append((name, RuntimeError(bad[0]), None, None))
                continue
            if s != last:
                continue
            results = [result_from_dict(r) for e in entries
                       for r in e.get("Results") or []]
            c = ctrl.get(key)
            out.append((name, Answer(Report(artifact_name=name,
                                            results=results)),
                        {"vulns": w["vulns"], "secrets": w["secrets"]},
                        {"vulns": c["vulns"], "secrets": c["secrets"]}
                        if c else None))
    jobs = reference_k8s.cluster_jobs(cell.table, data["facts"],
                                      data["refs"]) \
        if rec["sweeps"] else set()
    return {"answers": out, "never": 0, "expected_rows": len(jobs)}
