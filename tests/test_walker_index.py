"""The tar index against ``tarfile``: a layer walked from
``guard.safetar.index_tar``'s one pass over its bytes gives what the
walk over a ``tarfile.TarFile`` gives (files, their bytes, opaque
directories, whiteouts, guard counters, budget charges, the exception
and its text), and a layer with a header the index does not take is
walked by ``tarfile`` itself."""

import bz2
import gzip
import io
import json
import os
import sys
import tarfile
from unittest import mock

import numpy as np
import pytest

from trivy_tpu.artifact.walker import collect_layer_tar
from trivy_tpu.faults.hostile import BUILDERS, build_corpus, hostile_limits
from trivy_tpu.guard.budget import (DEFAULT_LIMITS, GUARD_METRICS,
                                    GuardMetrics, ResourceBudget,
                                    ResourceLimits)
from trivy_tpu.guard import safetar
from trivy_tpu.guard.safetar import (LayerTar, index_tar, is_gzip,
                                     open_layer_bytes)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

SCALE = 0.05
SEED = 2147483911


# ---------------------------------------------------------------
# the two walks, from a layer blob as an image holds it
# ---------------------------------------------------------------

def _walk(blob: bytes, limits, index: bool) -> dict:
    """Open, walk and read one layer blob, by the index (``index``)
    or by ``tarfile`` as every layer was walked before it; what it
    gave, what it raised and where, and what it charged."""
    metrics = GuardMetrics()
    budget = ResourceBudget(limits, metrics=metrics) \
        if limits is not None else None
    before = GUARD_METRICS.snapshot()
    out = {"indexed": None}
    stage = "open"
    try:
        if index:
            tf = open_layer_bytes(blob, budget)
        else:
            # an index that takes no layer: tarfile opens each at once,
            # as every opener did before the index, and walks it
            with mock.patch.object(safetar, "index_tar",
                                   return_value=None):
                tf = open_layer_bytes(blob, budget)
        with tf:
            stage = "walk"
            files, opq, wh = collect_layer_tar(tf, budget=budget)
            out.update(opq=opq, wh=wh)
            stage = "read"
            out["files"] = [(p, s, read()) for p, s, read in files]
            if index:
                out["indexed"] = tf.indexed
    except Exception as e:  # noqa: BLE001 — compared, not handled
        out["error"] = (stage, type(e).__name__, str(e))
    after = GUARD_METRICS.snapshot()
    out["guard"] = {k: after[k] - before[k] for k in after}
    if budget is not None:
        budget.flush_metrics()
        out["budget"] = (budget.entries, budget.decompressed,
                         list(budget.soft_faults), metrics.snapshot())
    return out


def _differential(blob: bytes, limits) -> dict:
    got = _walk(blob, limits, index=True)
    want = _walk(blob, limits, index=False)
    indexed = got.pop("indexed")
    assert want.pop("indexed") is None
    assert got == want
    return dict(got, indexed=indexed)


# ---------------------------------------------------------------
# layers to walk
# ---------------------------------------------------------------

def _ti(name, data=b"", type=tarfile.REGTYPE, linkname="", **attrs):
    ti = tarfile.TarInfo(name)
    ti.type = type
    ti.linkname = linkname
    ti.size = len(data) if ti.isreg() else 0
    for k, v in attrs.items():
        setattr(ti, k, v)
    return ti, data


def _tar(members, format=tarfile.PAX_FORMAT, **kw) -> bytes:
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w", format=format,
                      **kw) as tf:
        for ti, data in members:
            tf.addfile(ti, io.BytesIO(data) if ti.isreg() else None)
    return buf.getvalue()


def _block(n: int) -> int:
    return -(-n // 512) * 512


def _groups(data: bytes) -> list:
    """A tar cut into its members' block groups (extension headers
    with the member they belong to), the end blocks left out."""
    out, off, start = [], 0, 0
    while off + 512 <= len(data) and any(data[off:off + 512]):
        typ = data[off + 156:off + 157]
        size = tarfile.nti(data[off + 124:off + 136])
        off += 512
        if typ in (b"x", b"g", b"L", b"K"):
            off += _block(size)
            continue
        if typ in tarfile.REGULAR_TYPES:
            off += _block(size)
        out.append(data[start:off])
        start = off
    return out


def _rechecksum(b: bytearray, off: int, signed: bool = False) -> None:
    b[off + 148:off + 156] = b" " * 8
    total = sum(b[off:off + 512])
    if signed:          # as Sun and NeXT tars summed: bytes as int8
        total -= 256 * sum(1 for x in b[off:off + 512] if x >= 0x80)
    b[off + 148:off + 156] = b"%06o\0 " % total


def _patch(data: bytes, off: int, at: int, value: bytes,
           fix: bool = True) -> bytes:
    """The header at ``off`` with ``value`` written at ``at``, its
    checksum made good again unless ``fix`` is False."""
    b = bytearray(data)
    b[off + at:off + at + len(value)] = value
    if fix:
        _rechecksum(b, off)
    return bytes(b)


def _second_header(data: bytes) -> int:
    return len(_groups(data)[0])


def _with_pax(member: tuple, headers: dict) -> tuple:
    member[0].pax_headers = headers
    return member


LONG = "usr/share/" + "d" * 70 + "/" + "f" * 70 + ".txt"
FILES = [_ti("etc/os-release", b"ID=alpine\n"),
         _ti("app/main.py", b"print('hi')\n" * 40),
         _ti("app/empty")]


def _pax_size() -> bytes:
    # the member's header says 5 bytes, its PAX record 12: tarfile
    # reads 12
    tar = _tar([_with_pax(_ti("app/sized.txt", b"hello world\n"),
                          {"size": "12"})] + FILES[:1])
    off = 512 + _block(tarfile.nti(tar[124:136]))
    return _patch(tar, off, 124, b"00000000005\0")


def _x_then_l() -> bytes:
    (gl,) = _groups(_tar([_ti(LONG + ".gnu", b"g")],
                         format=tarfile.GNU_FORMAT))
    (px,) = _groups(_tar([_ti(LONG + ".pax", b"p")]))
    lpart = gl[:len(gl) - 1024]          # L header and its blocks
    xpart = px[:len(px) - 1024]          # x header and its blocks
    member = px[len(px) - 1024:]         # the member's own blocks
    return xpart + lpart + member + bytes(1024)


def _l_then_x() -> bytes:
    (gl,) = _groups(_tar([_ti(LONG + ".gnu", b"g")],
                         format=tarfile.GNU_FORMAT))
    (px,) = _groups(_tar([_ti(LONG + ".pax", b"p")]))
    return gl[:len(gl) - 1024] + px + bytes(1024)


def _long_chain() -> bytes:
    (gl,) = _groups(_tar([_ti(LONG, b"z")], format=tarfile.GNU_FORMAT))
    lpart = gl[:len(gl) - 1024]
    return lpart * 9 + gl[len(gl) - 1024:] + bytes(1024)


def _global_mid() -> bytes:
    first = _groups(_tar(FILES[:1]))[0]
    return first + _tar(FILES[1:], pax_headers={"comment": "mid"})


def _signed_checksum() -> bytes:
    tar = bytearray(_tar([_ti(b"caf\xe9/\xff.txt".decode(
        "utf-8", "surrogateescape"), b"s")], format=tarfile.GNU_FORMAT))
    _rechecksum(tar, 0, signed=True)
    return bytes(tar)


def _prefix_only_dir() -> bytes:
    # a directory whose name field is empty and whose ustar prefix
    # ends in "/": tarfile strips the slashes after joining the two
    tar = _tar([_ti("x", type=tarfile.DIRTYPE), FILES[0]],
               format=tarfile.USTAR_FORMAT)
    tar = _patch(tar, 0, 0, bytes(100))
    return _patch(tar, 0, 345, b"usr/d/\0")


def _no_end_blocks() -> bytes:
    return b"".join(_groups(_tar(FILES)))


def _base256(at: int) -> bytes:
    tar = _tar(FILES)
    off = _second_header(tar)
    n = 0 if at == 108 else tarfile.nti(tar[off + 124:off + 136])
    width = 8 if at == 108 else 12
    return _patch(tar, off, at,
                  b"\x80" + n.to_bytes(width - 1, "big"))


def _image_layers(path: str) -> list:
    with tarfile.open(path) as outer:
        names = json.load(outer.extractfile("manifest.json"))[0][
            "Layers"]
        return [outer.extractfile(n).read() for n in names]


@pytest.fixture(scope="module")
def sources(tmp_path_factory):
    """Layers no case builds for itself: the hostile corpus's, by
    builder, and one ``image-fleet-1chip`` image's at its sizes."""
    import gen
    root = tmp_path_factory.mktemp("walker-index")
    out = {f"hostile-{name}": _image_layers(path)
           for name, path in build_corpus(str(root / "hostile"),
                                          seed=SEED, scale=SCALE)}
    cfg = json.load(open(os.path.join(
        BENCH, "configs", "image-fleet-1chip.json")))
    blocks = gen.text_blocks(np.random.default_rng([SEED, 1]), 256)
    path = str(root / "fleet.tar")
    gen.build_image(cfg["sizes"], 3, path, SEED, blocks)
    out["fleet-image"] = _image_layers(path)
    return out


TAR = _tar(FILES)
# (layers, indexed: True / False / None where either is right)
CLEAN = {
    "fleet-image": (None, True),
    "ustar-prefix": (lambda: [_tar([_ti(LONG, b"deep\n"),
                                    _ti(LONG[:-4], type=tarfile.DIRTYPE)],
                                   format=tarfile.USTAR_FORMAT)], True),
    "pax-path-linkpath": (lambda: [_tar([
        _ti(LONG, b"long\n"),
        _ti("café/ü.txt", b"utf8\n"),
        _ti(LONG + ".d/", type=tarfile.DIRTYPE),
        _ti("app/link", type=tarfile.SYMTYPE, linkname="../" + LONG),
        _ti("app/hard", type=tarfile.LNKTYPE, linkname=LONG)])], True),
    "pax-size": (lambda: [_pax_size()], True),
    "gnu-long-name-link": (lambda: [_tar([
        _ti(LONG, b"gnu\n"),
        _ti(LONG + ".d", type=tarfile.DIRTYPE),
        _ti("app/l", type=tarfile.SYMTYPE, linkname="/" + LONG)],
        format=tarfile.GNU_FORMAT)], True),
    "x-then-L": (lambda: [_x_then_l()], True),
    "L-then-x": (lambda: [_l_then_x()], True),
    "v7-directory": (lambda: [_tar([
        _ti("olddir/", type=tarfile.AREGTYPE),
        _ti("olddir/f", b"in it\n", type=tarfile.AREGTYPE)],
        format=tarfile.USTAR_FORMAT)], True),
    "non-utf8-names": (lambda: [_tar([_ti(
        b"srv/caf\xe9/\xff.txt".decode("utf-8", "surrogateescape"),
        b"hi")], format=fmt) for fmt in (tarfile.GNU_FORMAT,
                                         tarfile.USTAR_FORMAT)], True),
    "links-escape": (lambda: [_tar([
        _ti("a/f", b"x"),
        _ti("a/h", type=tarfile.LNKTYPE, linkname="/etc/passwd"),
        _ti("a/s", type=tarfile.SYMTYPE, linkname="../../etc/shadow"),
        _ti("a/ok", type=tarfile.SYMTYPE, linkname="/bin/busybox"),
        _ti("b", type=tarfile.LNKTYPE, linkname="a/f")])], True),
    "whiteouts-opaque": (lambda: [_tar([
        _ti("app/.wh.x"), _ti("dir/.wh..wh..opq"),
        _ti(".wh..wh..opq"), _ti("app/keep", b"k")]),
        _tar([_ti("app/.wh..."), _ti("app/y", b"y")])], True),
    "traversal-names": (lambda: [_tar([
        _ti("./.env", b"e"), _ti("/abs/f", b"a"), _ti("a/b/../c", b"c"),
        _ti("../../etc/cron", b"boom"), _ti("a/../../b", b"b")])],
        True),
    "special-members": (lambda: [_tar([
        _ti("d", type=tarfile.DIRTYPE), _ti("p", type=tarfile.FIFOTYPE),
        _ti("c", type=tarfile.CHRTYPE, devmajor=1, devminor=3),
        _ti("k", type=tarfile.BLKTYPE, devmajor=8, devminor=0),
        _ti("cont", b"contiguous\n", type=tarfile.CONTTYPE),
        _ti("proc/x", b"p"), _ti("sys/y", b"s"),
        _ti("dev/null", b"n")])], True),
    "deep-and-long-names": (lambda: [_tar([
        _ti("/".join(["d"] * 300) + "/f", b"deep"),
        _ti("n" * 5000, b"long")])], True),
    "signed-checksum": (lambda: [_signed_checksum()], True),
    "ustar-prefix-only-dir": (lambda: [_prefix_only_dir()], True),
    "empty-archive": (lambda: [bytes(1024)], True),
    "no-end-blocks": (lambda: [_no_end_blocks()], True),
    "trailing-garbage": (lambda: [TAR + b"junk" * 300], True),
    "gzip-layer": (lambda: [gzip.compress(TAR, mtime=0)], True),
}
FALLBACK = {
    "bad-checksum-first": lambda: [_patch(TAR, 0, 0, b"X", fix=False)],
    "bad-checksum-mid": lambda: [_patch(TAR, _second_header(TAR), 0,
                                        b"X", fix=False)],
    "truncated-member": lambda: [TAR[:_second_header(TAR) + 700]],
    "truncated-header": lambda: [TAR[:_second_header(TAR) + 300]],
    "truncated-padding": lambda: [_groups(TAR)[0][:512 + 10]],
    "base256-size": lambda: [_base256(124)],
    "base256-uid": lambda: [_base256(108)],
    "gnu-sparse-member": lambda: [_patch(TAR, _second_header(TAR), 156,
                                         b"S")],
    "pax-sparse-record": lambda: [_tar([_with_pax(
        _ti("app/sparse", b"s"), {"GNU.sparse.name": "real.txt"})]
        + FILES)],
    "pax-hdrcharset": lambda: [_tar([_ti(
        b"caf\xe9".decode("utf-8", "surrogateescape"), b"b")])],
    "pax-size-not-a-number": lambda: [_tar([_with_pax(
        _ti("app/n", b"twelve bytes"), {"size": "twelve"})] + FILES)],
    "pax-size-negative": lambda: [_tar([_with_pax(
        _ti("app/m", b"minus"), {"size": "-5"})] + FILES)],
    "global-header-first": lambda: [_tar(FILES,
                                         pax_headers={"comment": "g"})],
    "global-header-mid": lambda: [_global_mid()],
    "unknown-type": lambda: [_patch(TAR, _second_header(TAR), 156,
                                    b"V")],
    "extension-chain": lambda: [_long_chain()],
    "bz2-layer": lambda: [bz2.compress(TAR)],
    "not-a-tar": lambda: [b"not a tar at all\n" * 200],
    "empty-bytes": lambda: [b""],
}

CASES = [pytest.param(f"hostile-{n}", id=f"hostile-{n}")
         for n in BUILDERS] + \
    [pytest.param(n, id=n) for n in CLEAN] + \
    [pytest.param(n, id=f"fallback-{n}") for n in FALLBACK]


def _case(name: str, sources: dict) -> tuple:
    if name.startswith("hostile-"):
        return sources[name], hostile_limits(SCALE), None
    if name in CLEAN:
        build, indexed = CLEAN[name]
        layers = sources[name] if build is None else build()
        return layers, DEFAULT_LIMITS, indexed
    return FALLBACK[name](), DEFAULT_LIMITS, False


@pytest.mark.parametrize("guarded", [True, False],
                         ids=["guarded", "unguarded"])
@pytest.mark.parametrize("case", CASES)
def test_index_walks_as_tarfile_does(case, guarded, sources):
    layers, limits, indexed = _case(case, sources)
    for blob in layers:
        got = _differential(blob, limits if guarded else None)
        if indexed is not None and "error" not in got:
            assert got["indexed"] is indexed


@pytest.mark.parametrize("case", [n for n in CLEAN
                                  if CLEAN[n][0] is not None])
def test_index_reads_the_fields_tarfile_reads(case):
    """The records themselves, beside ``TarInfo``'s fields (a link
    name only where the member is a link)."""
    for data in CLEAN[case][0]():
        if is_gzip(data):
            data = gzip.decompress(data)
        with tarfile.open(fileobj=io.BytesIO(data)) as tf:
            want = [(m.name, m.type, m.size,
                     m.linkname if m.issym() or m.islnk() else "",
                     m.offset_data) for m in tf]
        assert index_tar(data) == want


def test_extension_headers_apply_as_tarfile_nests_them():
    assert index_tar(_x_then_l())[0][0] == LONG + ".pax"
    assert index_tar(_l_then_x())[0][0] == LONG + ".gnu"
    assert index_tar(_pax_size())[0][2] == 12


def test_fleet_layers_are_indexed_whole(sources):
    """The generator's layers take the index, member for member."""
    for data in sources["fleet-image"]:
        members = index_tar(data)
        with tarfile.open(fileobj=io.BytesIO(data)) as tf:
            assert len(members) == len(tf.getmembers()) > 0


def test_refused_first_member_opens_tarfile_at_once():
    layer = LayerTar(_tar(FILES, pax_headers={"comment": "g"}))
    assert layer._tf is not None and layer.index() is None
    assert not layer.indexed
    with pytest.raises(tarfile.ReadError):
        LayerTar(b"")
    lazy = LayerTar(TAR)
    assert lazy._tf is None
    assert lazy.tar.getnames() == [ti.name for ti, _ in FILES]


def test_index_stops_at_its_limit():
    """``index_tar`` gives the members up to its limit; a layer with
    more members than a budget's entries left is ``tarfile``'s."""
    whole = index_tar(TAR)
    assert index_tar(TAR, 2) == whole[:2]
    assert index_tar(TAR, len(FILES) + 5) == whole
    layer = LayerTar(TAR)
    assert layer.index(len(FILES) - 1) is None and not layer.indexed
    assert layer.index(len(FILES)) == whole and layer.indexed
    assert layer.index(None) == whole


def test_checksums_are_summed_a_batch_at_a_time():
    """A bad checksum after the first batch of headers, and in the
    last, partial one, still sends the layer to ``tarfile``."""
    many = _tar([_ti(f"f{i}") for i in range(2 * safetar._CHECK_BATCH
                                             + 3)])
    assert len(index_tar(many)) == 2 * safetar._CHECK_BATCH + 3
    for k in (safetar._CHECK_BATCH + 7, 2 * safetar._CHECK_BATCH + 1):
        assert index_tar(_patch(many, 512 * k, 0, b"X",
                                fix=False)) is None


def test_layers_indexed_counts_indexed_layers_only(tmp_path):
    """``layers_indexed`` books a layer the index walked and not one
    that fell back to ``tarfile``, beside ``layers_analyzed``."""
    from trivy_tpu.artifact import ArtifactOption, ImageArtifact
    from trivy_tpu.artifact.cache import MemoryCache
    from trivy_tpu.artifact.image import load_image
    from trivy_tpu.artifact.metrics import INGEST_METRICS
    from trivy_tpu.faults.hostile import _image_tar
    clean = _tar(FILES)
    fallback = _global_mid()
    path = _image_tar(str(tmp_path / "img.tar"), [clean, fallback])
    before = INGEST_METRICS.snapshot()
    ImageArtifact(load_image(path), MemoryCache(),
                  ArtifactOption(scan_secrets=False)).inspect()
    after = INGEST_METRICS.snapshot()
    assert after["layers_analyzed"] - before["layers_analyzed"] == 2
    assert after["layers_indexed"] - before["layers_indexed"] == 1


def test_guard_limits_hold_on_the_index():
    """A budget's entry, size and name limits trip on indexed layers
    with the texts the ``tarfile`` walk gives (the differential above
    compares them; here they are seen to trip at all)."""
    lim = ResourceLimits(max_files=2, max_file_bytes=4,
                         max_name_bytes=16)
    got = _differential(_tar([_ti(f"f{i}") for i in range(40)]), lim)
    assert got["error"][1] == "ResourceBudgetExceeded"
    got = _differential(_tar([_ti("big", b"x" * 10)]), lim)
    assert "per-file" in got["error"][2]
    got = _differential(_tar([_ti("n" * 40, b"x")]), lim)
    assert "longer than" in got["error"][2]
