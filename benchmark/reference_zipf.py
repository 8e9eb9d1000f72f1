"""The Zipf deployment's copy of the plain reference: what a correct
scan reports for a document that ``gen_zipf`` made. It is
``reference.sbom_findings`` on the document's facts, and one thing
more is held, which the facts make worth holding: the reference
answers a purl from the purl alone, so two documents of a pass that
hold the same purl get the same findings for it, however the program
parsed it (a memo's hit, or its miss after the memo turned), joined
it (once a document) and matched it (one row for every document that
asked). :func:`repeats` reads that off the compared reports by
itself, so a run's lines say whether a fault lies where documents
share work (they disagree) or under it (they agree and are wrong).
Imports nothing of the program.

The control is ``reference.py``'s: versions compared without their
last field. Its answers must fail the check.
"""

from __future__ import annotations

from reference import sbom_findings  # noqa: F401  (the answers)


def repeats(compared: list) -> dict:
    """``compared``: ``(components, findings)`` a document, the
    components as ``gen.sbom_components`` gives them, the findings as
    ``check.findings`` does. Returns how many (package, version)
    pairs two or more of the documents hold (``shared``), and those
    of them that the documents do not all report alike
    (``disagree``, as names and versions)."""
    from gen import ECOSYSTEMS
    seen: dict = {}             # (name, version) -> [its findings]
    for comps, found in compared:
        by_pkg: dict = {}
        for name, ver, vid, _fixed, _sev in found["vulns"]:
            by_pkg.setdefault((name, ver), set()).add(vid)
        for e, i, ver in set(comps):
            eco, _bucket, _purl, tpl = ECOSYSTEMS[e]
            key = (tpl.format(n=f"{eco}-lib-{i}"), ver)
            seen.setdefault(key, []).append(
                frozenset(by_pkg.get(key, ())))
    shared = {k: v for k, v in seen.items() if len(v) > 1}
    return {"shared": len(shared),
            "disagree": sorted(k for k, v in shared.items()
                               if len(set(v)) > 1)}
