"""What decides ``correct``: every report the window produced (or a
sample of them drawn from the seed), rendered by the program's own
json writer as the CLI does, against ``reference``'s answer for the
same image or document, and the counters that show a slot finished on
the host under the device's name. Every comparison is exact, so every
limit is 0; the device's work in the window has a floor instead, the
interval jobs that the reference expects of what the window finished.
"""

from __future__ import annotations

import io
import json

# counters of ScanScheduler.stats()["counters"] that mean a slot left
# the device path (the configuration's guarantee: all zero)
FALLBACK_COUNTERS = ("batch_bisects", "quarantined", "host_fallbacks",
                     "program_faults", "failed", "timed_out",
                     "cancelled")


def render(report) -> dict:
    """One report as the CLI's json writer emits it (the multi-image
    path of ``cli._finish_many``; copied from chip_smoke._render)."""
    from trivy_tpu import __version__
    from trivy_tpu.cli import DEFAULT_SEVERITIES, _severities
    from trivy_tpu.report import write_report
    from trivy_tpu.scan import filter_results
    sev = _severities(DEFAULT_SEVERITIES)
    report.results = filter_results(report.results, sev)
    buf = io.StringIO()
    write_report(report, fmt="json", output=buf,
                 severities=[str(s) for s in sev],
                 app_version=__version__)
    return json.loads(buf.getvalue())


def findings(doc: dict) -> dict:
    """The findings of a rendered report, in the reference's form."""
    vulns, secrets = set(), set()
    for res in doc.get("Results") or []:
        for v in res.get("Vulnerabilities") or []:
            vulns.add((v["PkgName"], v["InstalledVersion"],
                       v["VulnerabilityID"], v.get("FixedVersion", ""),
                       v.get("Severity", "UNKNOWN")))
        for s in res.get("Secrets") or []:
            secrets.add((res["Target"], s["RuleID"], s["StartLine"]))
    return {"vulns": vulns, "secrets": secrets}


def compare(answers: list, never_answered: int) -> dict:
    """``answers``: ``(name, result-or-exception, want, control)``
    with ``want`` the reference's findings and ``control`` None, or
    the control's findings to put in the program's place. Returns
    the numbers compared, each ``[value, limit, "max"|"min"]``, and a
    few examples of what differed for the lines above the result."""
    not_ok = mismatched = n_vulns = n_secrets = 0
    examples = []
    for name, res, want, control in answers:
        if isinstance(res, BaseException) or res.status != "ok" \
                or res.error or res.report is None:
            not_ok += 1
            examples.append(f"{name}: slot not ok: "
                            f"{getattr(res, 'status', res)!r} "
                            f"{getattr(res, 'error', '')!r}"[:300])
            continue
        doc = render(res.report)
        got = findings(doc) if control is None else control
        if doc.get("Status", "") != "":
            not_ok += 1
            examples.append(f"{name}: Status {doc.get('Status')!r}")
        n_vulns += len(want["vulns"])
        n_secrets += len(want["secrets"])
        if got != want:
            mismatched += 1
            diff = sorted(got["vulns"] ^ want["vulns"]) + \
                sorted(got["secrets"] ^ want["secrets"])
            examples.append(f"{name}: {len(diff)} findings differ, "
                            f"first {diff[:2]}")
    numbers = {
        "reports_compared": [len(answers), 1, "min"],
        "reports_mismatched": [mismatched, 0, "max"],
        "slots_not_ok": [not_ok, 0, "max"],
        "never_answered": [never_answered, 0, "max"],
    }
    return {"numbers": numbers, "examples": examples[:8],
            "reference_vulns": n_vulns, "reference_secrets": n_secrets}


def guarantees(window: dict, since_warmup: dict, table: dict,
               expected_rows: int, checks) -> dict:
    """The configuration's guarantees, as far as counters show them.
    ``window`` is the delta of the program's counters over the window
    alone (a run whose window did no device work must not pass on
    what the warm-up did), ``since_warmup`` the delta up to the last
    answer waited for, ``table`` the compiled table's own stats.

    * ``device_rows``: interval jobs sent to the device in the
      window, at least the distinct jobs the reference expects of
      what the window finished;
    * ``host_fallback_pairs``: distinct jobs less those sent to the
      device (detect/batch.py evaluates the rest on the host), 0;
    * ``table_host_fallback_rows``: rows of the table compiled for
      the host path, 0 (the configuration's 0.00%);
    * ``off_device_events``: the scheduler's counters that mean a
      slot left the device path, 0; read only where the runner has a
      scheduler;
    * ``sieve_dispatches``: at least 1 in the window, where the cell
      scans for secrets."""
    det = since_warmup["detect"]
    numbers = {
        "device_rows": [window["detect"]["device_rows"],
                        max(1, expected_rows), "min"],
        "host_fallback_pairs": [
            det["jobs_unique"] - det["device_rows"], 0, "max"],
        "table_host_fallback_rows": [
            int(table["host_fallback_rows"]), 0, "max"],
    }
    if "counters" in since_warmup:
        numbers["off_device_events"] = [
            sum(int(since_warmup["counters"].get(k, 0))
                for k in FALLBACK_COUNTERS), 0, "max"]
    if "secret" in checks:
        numbers["sieve_dispatches"] = [
            window["secret"]["dfa_dispatches"], 1, "min"]
    return numbers


def verdict(numbers: dict) -> bool:
    return all(v <= lim if kind == "max" else v >= lim
               for v, lim, kind in numbers.values())
