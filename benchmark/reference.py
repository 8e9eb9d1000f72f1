"""The plain reference: what a correct scan reports for an image or a
document that ``gen`` made, worked out from the facts ``gen`` recorded
and Trivy's published matching rules, with nothing of the program: no
import of ``trivy_tpu``, no compiled table, no rule file.

* OS packages (apk, dpkg): an advisory carries one FixedVersion and a
  package is vulnerable when its installed version sorts below it.
  Every version ``gen`` makes is ``1.<b>.<c>-r<r>`` or ``1.<b>.<c>-<r>``
  with one digit a field, for which apk's and dpkg's orders are both
  the order of the tuple ``(b, c, r)``.
* Libraries (GHSA): vulnerable when the version satisfies any of
  VulnerableVersions and none of PatchedVersions; an entry is a
  comma-joined conjunction of ``<``, ``>=`` bounds
  (aquasecurity/trivy ``pkg/detector/library/compare``). Installed
  versions are plain ``x.y.z``; a bound may carry ``-beta.1``, and a
  prerelease sorts below its release in semver, PEP 440 and Maven
  alike, so ``v < x.y.z-beta.1`` is ``v < x.y.z`` for a release ``v``.
* Secrets: exactly the ones ``gen`` planted, by target, rule and line.
  The corpus is words and templates chosen to match no builtin rule.

``control=True`` computes the same under the two short cuts a later PR
would be tempted by: versions compared without their last field (the
revision of an OS package, the patch of a library: a narrower rank),
and no overlap between sieve segments (a match that straddles a
segment's end is lost). The control's answers must fail the check.
"""

from __future__ import annotations

from gen import (DISTROS, ECOSYSTEMS, SEVERITIES, GhsaTable,
                 ghsa_vuln_id, os_fixed, os_version, os_vuln_id)


def _ver(text: str, control: bool) -> tuple:
    release = text.split("-")[0]
    v = tuple(int(x) for x in release.split("."))
    return v[:2] if control else v


def _holds(v: tuple, entry: str, control: bool) -> bool:
    for bound in entry.split(","):
        bound = bound.strip()
        if bound.startswith(">="):
            ok = v >= _ver(bound[2:], control)
        elif bound.startswith("<"):
            ok = v < _ver(bound[1:], control)
        else:
            raise ValueError(f"bound {bound!r}")
        if not ok:
            return False
    return True


def ghsa_vulnerable(version: str, advisory: dict,
                    control: bool = False) -> bool:
    v = _ver(version, control)
    if not any(_holds(v, e, control)
               for e in advisory["VulnerableVersions"]):
        return False
    return not any(_holds(v, e, control)
                   for e in advisory["PatchedVersions"])


def _severity(i: int, a: int, detailed: bool) -> str:
    return SEVERITIES[(i + a) % 4] if detailed else "UNKNOWN"


def library_findings(tab: GhsaTable, comps, control=False) -> set:
    """``comps``: (ecosystem index, package index, version). Returns
    {(package name, version, vuln id, fixed version, severity)}."""
    out = set()
    for e, i, ver in comps:
        if i >= tab.ghsa_pkgs:
            continue
        eco, _bucket, _purl, tpl = ECOSYSTEMS[e]
        name = tpl.format(n=f"{eco}-lib-{i}")
        for a in range(3):
            adv = tab.advisory(e, i, a)
            if ghsa_vulnerable(ver, adv, control):
                fixed = ", ".join(adv["PatchedVersions"])
                out.add((name, ver, ghsa_vuln_id(eco, i, a), fixed,
                         _severity(i, a, i % 10 == 0)))
    return out


def os_findings(distro: int, os_pkgs, control=False) -> set:
    family = DISTROS[distro][0]
    out = set()
    for i, b, c, r in os_pkgs:
        for a in range(3):
            fb, fc, fr = os_fixed(i, a)
            have, fixed = (b, c, r), (fb, fc, fr)
            if control:
                have, fixed = have[:2], fixed[:2]
            if have < fixed:
                out.add((f"{family}-pkg-{i}",
                         os_version(family, b, c, r),
                         os_vuln_id(family, i, a),
                         os_version(family, fb, fc, fr),
                         _severity(i, a, True)))
    return out


def image_findings(tab: GhsaTable, facts: dict, checks,
                   control=False) -> dict:
    """What a scan of one ``gen.build_image`` image reports."""
    pip = [(1, i, ver) for i, ver in facts["pip_pkgs"]]
    want = {"vulns": os_findings(facts["distro"], facts["os_pkgs"],
                                 control)
            | library_findings(tab, pip, control),
            "secrets": set()}
    if "secret" in checks:
        secrets = facts["secrets"]
        if control:
            # the two planted across a segment's end are the first two
            secrets = secrets[2:]
        want["secrets"] = {tuple(s) for s in secrets}
    return want


def sbom_findings(tab: GhsaTable, facts, control=False) -> dict:
    return {"vulns": library_findings(tab, facts, control),
            "secrets": set()}


# ---------------------------------------------------------------------
# the interval jobs a scan cannot do without
# ---------------------------------------------------------------------

def library_jobs(tab: GhsaTable, comps) -> set:
    """One job an advisory of every distinct (package, version) that
    the table knows: three advisories a package."""
    return {("lib", e, i, ver, a) for e, i, ver in comps
            if i < tab.ghsa_pkgs for a in range(3)}


def image_jobs(tab: GhsaTable, facts: dict) -> set:
    """The same for an image: its advisory-bearing OS packages and
    its pip requirements."""
    d = facts["distro"]
    return {("os", d, i, b, c, r, a)
            for i, b, c, r in facts["os_pkgs"] for a in range(3)} \
        | library_jobs(tab, [(1, i, ver)
                             for i, ver in facts["pip_pkgs"]])
