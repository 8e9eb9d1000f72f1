"""CycloneDX documents whose packages are drawn by popularity, for
``sbom-batch-2m-1chip``: a store of build SBOMs in which a few
hundred packages are in most documents and most packages are in
almost none. ``gen.build_sboms`` with one thing changed, the draw of
a component's package; the documents, the facts and everything that
reads them (``gen.sbom_components``, ``reference.py``) are ``gen``'s.
Imports nothing of the program.

A component: its ecosystem drawn evenly over the four; its
popularity rank ``r`` in 1..``name_universe`` with P(r) proportional
to ``r ** -zipf_s`` (the inverse of the cumulative weights at an even
draw); its package index ``perm_e[r - 1]``, ``perm_e`` a permutation
of 0..``name_universe`` - 1 fixed by ``db_seed`` and the ecosystem
alone, so the same packages are popular on every ``--seed``, as the
advisory table is one for every seed. Indices under ``ghsa_pkgs``
are the advisory-bearing names (a tenth where ``name_universe`` is
ten times ``ghsa_pkgs``): the permutation spreads them over every
rank. The release is one of three, drawn evenly. Two components of
one purl in a document stay, under ``bom-ref``s of their own, as two
resolutions of one package in a lock file do.
"""

from __future__ import annotations

import numpy as np

from gen import ECOSYSTEMS, lib_version


def rank_cdf(names: int, s: float):
    """The cumulative popularity of ranks 1..``names``."""
    cdf = np.cumsum(np.arange(1, names + 1, dtype=np.float64) ** -s)
    return cdf / cdf[-1]


def popularity_order(names: int, db_seed: int):
    """``[ecosystems, names]``: the package index at each rank."""
    return np.stack([np.random.default_rng([db_seed, 11, e])
                     .permutation(names)
                     for e in range(len(ECOSYSTEMS))])


def draw(sz: dict, count: int, seed: int, tag: str) -> dict:
    """The facts of ``count`` documents: three ``[count, comps]``
    arrays, ``gen.build_sboms``' ``facts``."""
    rng = np.random.default_rng([seed, 4, sum(tag.encode())])
    shape = (count, sz["comps"])
    names = sz["name_universe"]
    eco = rng.integers(0, len(ECOSYSTEMS), shape)
    rank = np.searchsorted(rank_cdf(names, sz["zipf_s"]),
                           rng.random(shape))
    order = popularity_order(names, sz["db_seed"])
    return {"eco": eco,
            "idx": order[eco, np.minimum(rank, names - 1)],
            "pick": rng.integers(0, 3, shape)}


def build_sboms(sz: dict, count: int, seed: int, tag: str) -> tuple:
    """``(docs, facts)`` as ``gen.build_sboms`` returns them: the
    documents as ``(name, raw bytes)`` and the draws behind them. A
    document is written by hand, key for key what ``gen`` hands to
    ``json.dumps`` (no name here needs an escape): 60,000 documents
    of 40 components are 2.4 million components a run."""
    facts = draw(sz, count, seed, tag)
    comp = ('{{"bom-ref": "{p}@{v}-{n}-{k}", "type": "library", '
            '"name": "{name}", "version": "{v}", "purl": "{p}@{v}"}}')
    head = ('{{"bomFormat": "CycloneDX", "specVersion": "1.4", '
            '"serialNumber": "urn:uuid:{tag}-{n}", "version": 1, '
            '"metadata": {{"component": {{"bom-ref": "root", '
            '"type": "container", "name": "{tag}-{n}"}}}}, '
            '"components": [')
    ecos = [(name, purl_ns) for name, _, purl_ns, _ in ECOSYSTEMS]
    docs = []
    for n in range(count):
        comps = []
        for k, (e, i, pick) in enumerate(zip(
                facts["eco"][n].tolist(), facts["idx"][n].tolist(),
                facts["pick"][n].tolist())):
            eco, purl_ns = ecos[e]
            name = f"{eco}-lib-{i}"
            comps.append(comp.format(p=purl_ns + name, name=name,
                                     v=lib_version(i, pick), n=n, k=k))
        docs.append((f"{tag}{n}.cdx.json",
                     (head.format(tag=tag, n=n) + ", ".join(comps)
                      + "]}").encode()))
    return docs, facts
