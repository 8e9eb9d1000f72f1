"""The shared-base deployment's copy of the plain reference: what a
correct scan reports for a tag that ``gen_shared`` made. It is
``reference.image_findings`` on the tag's facts (the base's packages,
the tag's requirements, the secrets planted in the tag's own layer),
with Trivy's rule that secrets in the base image's layers are not
reported (``pkg/fanal/artifact/image/image.go`` ``guessBaseLayers``):
the two ``gen_shared`` planted in every base stay out. A cached layer
and a memoized verdict change nothing of it: the answer for the
hundredth request of a tag is the answer for its first. Imports
nothing of the program.

``control=True`` computes the same under the two short cuts this
deployment tempts: everything sieved and nothing skipped (the base's
planted secrets are reported), and a cache key that is too coarse (a
tag's own layer answered from the first tag seen on its base: that
tag's requirements and secrets in this one's place). The control's
answers must fail the check.
"""

from __future__ import annotations

from typing import Optional

from reference import image_findings as plain_findings
from reference import image_jobs  # noqa: F401  (the mode's floor)


def image_findings(tab, facts: dict, checks, control: bool = False,
                   first: Optional[dict] = None) -> dict:
    """``first``: the facts of the first tag the schedule asks for
    on this tag's base; read by the control alone."""
    if not control:
        return plain_findings(tab, facts, checks)
    coarse = dict(facts, pip_pkgs=first["pip_pkgs"],
                  secrets=first["secrets"] + facts["base_secrets"])
    return plain_findings(tab, coarse, checks)
