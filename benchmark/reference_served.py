"""The served deployment's copy of the plain reference: what a correct
Scan answers for a build that ``gen_served`` made. It is
``reference.image_findings`` on the build's facts (its base's
packages, its own pins; ``vuln`` only, so no secrets), whatever the
server had cached or memoized when the build came: the answer for the
hundredth build on a base holds the same findings for the base's
packages as the first. Imports nothing of the program.

``control=True`` computes the same under the short cut this
deployment tempts: a findings memo (or a layer cache) keyed too
coarsely, so that a build's own layer is answered from the first
build seen on its base: that build's pins in this one's place. The
control's answers must fail the check.

``build_jobs`` is the floor under the device's work: the interval
jobs of a build, of which the window cannot have done without the
base's at the base's first sight and the build's own at every build
(the memo may answer the rest); the mode unites them over what
finished.
"""

from __future__ import annotations

from typing import Optional

from reference import image_findings as plain_findings
from reference import image_jobs as build_jobs  # noqa: F401


def build_findings(tab, facts: dict, checks, control: bool = False,
                   first: Optional[dict] = None) -> dict:
    """``first``: the facts of the first build the schedule sends on
    this build's base; read by the control alone."""
    if not control:
        return plain_findings(tab, facts, checks)
    return plain_findings(
        tab, dict(facts, pip_pkgs=first["pip_pkgs"]), checks)
