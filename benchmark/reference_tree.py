"""The plain reference for ``source-tree-1chip``: what a correct
``trivy fs`` reports for a tree that ``gen_tree`` made, from the facts
``gen_tree`` recorded. Nothing of the program.

* Secrets: exactly the ones planted, as (path, rule, line); never a
  trap (a token under ``node_modules``, in a ``.png``, behind a NUL,
  in a lockfile: upstream's ``Required`` leaves those files out).
* Libraries: ``reference.library_findings`` over the pins of every
  ``requirements.txt``; the report is compared as a set, so a pin
  that two services share counts once.

``control=True`` is the tree scanned under the short cuts a streamed
scan would be tempted by: the skips not applied (every trap
reported), and the files on both sides of a part's cut lost (the
secrets planted there dropped), besides ``reference``'s narrower
version compare.
"""

from __future__ import annotations

import reference


def _pins(facts: dict) -> list:
    return [(1, i, ver) for i, ver in facts["pip_pkgs"]]


def tree_findings(tab, facts: dict, checks, control=False) -> dict:
    want = {"vulns": reference.library_findings(tab, _pins(facts),
                                                control),
            "secrets": set()}
    if "secret" in checks:
        secrets = facts["secrets"]
        if control:
            lost = set(facts["boundary"])
            secrets = [s for k, s in enumerate(secrets)
                       if k not in lost] + facts["traps"]
        want["secrets"] = {tuple(s) for s in secrets}
    return want


def tree_jobs(tab, facts: dict) -> set:
    """The interval jobs a scan of the tree cannot do without."""
    return reference.library_jobs(tab, _pins(facts))
