"""Regressions for review findings on the artifact/detect stack."""

import io
import tarfile

from trivy_tpu.vercmp import get_comparer


def test_deb_missing_revision_equals_zero():
    c = get_comparer("deb")
    assert c.compare("1.0", "1.0-0") == 0
    assert c.compare("1.2.3", "1.2.3-0") == 0
    assert c.compare("1.0-1", "1.0") == 1


def test_tar_walker_keeps_dotfiles_and_whiteouts():
    from trivy_tpu.artifact.walker import collect_layer_tar
    from trivy_tpu.guard.safetar import LayerTar
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tf:
        for name, data in [("./.env", b"secret"),
                           ("./app/.wh..env", b""),
                           ("/abs/file", b"x"),
                           ("./dir/.wh..wh..opq", b"")]:
            ti = tarfile.TarInfo(name)
            ti.size = len(data)
            tf.addfile(ti, io.BytesIO(data))
    with LayerTar(buf.getvalue()) as tf:
        files, opq, wh = collect_layer_tar(tf)
        assert tf.indexed
    paths = [p for p, _, _ in files]
    assert ".env" in paths           # dotfile survives with its dot
    assert "abs/file" in paths
    assert wh == ["app/.env"]        # whiteout detected + decoded
    assert opq == ["dir"]


def test_merge_os_keeps_winning_family_version():
    from trivy_tpu.analyzer.analyzer import _merge_os
    from trivy_tpu.types import OS
    # lsb-release (ubuntu) seen first, debian_version second
    merged = _merge_os(OS(family="ubuntu", name="22.04"),
                       OS(family="debian", name="bookworm/sid"))
    assert (merged.family, merged.name) == ("ubuntu", "22.04")
    # and in the opposite walk order
    merged = _merge_os(OS(family="debian", name="bookworm/sid"),
                       OS(family="ubuntu", name="22.04"))
    assert (merged.family, merged.name) == ("ubuntu", "22.04")


def test_batch_secrets_layer_attribution(tmp_path):
    """Same path in two layers, secret only in the lower one."""
    from tests.test_e2e_image import make_image_tar, run_cli
    import json
    tar = make_image_tar(tmp_path, [
        {"app/.env": b"GITHUB_TOKEN=ghp_" + b"A" * 36 + b"\n"},
        {"app/.env": b"clean now\n"},
    ])
    out = tmp_path / "r.json"
    code, _ = run_cli([
        "image", "--input", tar, "--format", "json",
        "--output", str(out), "--security-checks", "secret",
        "--backend", "cpu-ref", "--no-cache"])
    assert code == 0
    report = json.loads(out.read_text())
    secrets = [r for r in report.get("Results") or []
               if r["Class"] == "secret"]
    assert secrets, "layer-1 finding must be preserved"
    finding = secrets[0]["Secrets"][0]
    # attribution must be the exact layer that contained the secret
    diff_ids = report["Metadata"]["DiffIDs"]
    assert finding["Layer"]["DiffID"] == diff_ids[0]


def test_batch_secrets_clean_layer_first(tmp_path):
    """Same path in two layers, clean version FIRST: the cursor-based
    mapping used to attach the finding to the clean lower layer."""
    from tests.test_e2e_image import make_image_tar, run_cli
    import json
    tar = make_image_tar(tmp_path, [
        {"app/.env": b"nothing to see\n"},
        {"app/.env": b"GITHUB_TOKEN=ghp_" + b"B" * 36 + b"\n"},
    ])
    out = tmp_path / "r.json"
    code, _ = run_cli([
        "image", "--input", tar, "--format", "json",
        "--output", str(out), "--security-checks", "secret",
        "--backend", "cpu-ref", "--no-cache"])
    assert code == 0
    report = json.loads(out.read_text())
    secrets = [r for r in report.get("Results") or []
               if r["Class"] == "secret"]
    assert secrets
    finding = secrets[0]["Secrets"][0]
    diff_ids = report["Metadata"]["DiffIDs"]
    assert finding["Layer"]["DiffID"] == diff_ids[1]


def test_batch_two_images_same_path_attribution(tmp_path):
    """Two images sharing a path; secret only in the SECOND image.
    The finding must land on image 2 and image 1 must come back clean
    (VERDICT r1 weak #1: path-cursor misattribution across images)."""
    from tests.test_e2e_image import make_image_tar
    from trivy_tpu.runtime.batch import BatchScanRunner
    from trivy_tpu.types import ScanOptions

    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    tar1 = make_image_tar(tmp_path / "a", [
        {"srv/cfg.conf": b"plain config, nothing secret\n"}])
    tar2 = make_image_tar(tmp_path / "b", [
        {"srv/cfg.conf": b"token=ghp_" + b"C" * 36 + b"\n"}])

    runner = BatchScanRunner(backend="cpu-ref")
    res = runner.scan_paths(
        [tar1, tar2],
        ScanOptions(backend="cpu-ref", security_checks=["secret"]))
    assert res[0].error == "" and res[1].error == ""

    def secret_count(r):
        return sum(len(x.secrets) for x in r.report.results)

    assert secret_count(res[0]) == 0, "clean image must stay clean"
    assert secret_count(res[1]) == 1, "finding must follow its image"


def test_batch_per_image_counts_match_solo_scans(tmp_path):
    """Batch scanning a small fleet must reproduce per-image secret
    counts of individual scans (same-path files planted everywhere)."""
    from tests.test_e2e_image import make_image_tar
    from trivy_tpu.runtime.batch import BatchScanRunner
    from trivy_tpu.types import ScanOptions

    layers = []
    for i in range(4):
        files = {"etc/app.conf": b"shared body %d\n" % i}
        if i % 2 == 1:
            files["etc/app.conf"] += (
                b"aws=AKIAIOSFODNN7EXAMPL%d\n" % i)
        layers.append([files])
    for i in range(4):
        (tmp_path / str(i)).mkdir()
    tars = [make_image_tar(tmp_path / str(i), lys)
            for i, lys in enumerate(layers)]

    opts = ScanOptions(backend="cpu-ref", security_checks=["secret"])
    batch = BatchScanRunner(backend="cpu-ref").scan_paths(tars, opts)
    solo = [BatchScanRunner(backend="cpu-ref").scan_paths([t], opts)[0]
            for t in tars]

    def counts(r):
        return sum(len(x.secrets) for x in r.report.results)

    assert [counts(r) for r in batch] == [counts(r) for r in solo]


def test_redhat_family_supported():
    from trivy_tpu.db import AdvisoryStore
    from trivy_tpu.detect import ospkg_detect
    from trivy_tpu.types import Package
    store = AdvisoryStore()
    store.put_advisory("Red Hat", "openssl-libs", "CVE-2020-1971",
                       {"FixedVersion": "1:1.1.1g-12.el8_3",
                        "Severity": 2})
    # advisories key by BINARY name + binary EVR (redhat.go:127,143)
    pkgs = [Package(name="openssl-libs", version="1.1.1c",
                    release="2.el8", epoch=1, src_name="openssl",
                    src_version="1.1.1c", src_release="2.el8",
                    src_epoch=1)]
    vulns, _ = ospkg_detect("redhat", "8.3", None, pkgs, store)
    assert [v.vulnerability_id for v in vulns] == ["CVE-2020-1971"]
    vulns, _ = ospkg_detect("centos", "8", None, pkgs, store)
    assert len(vulns) == 1


def test_batch_shared_layer_secret_lands_on_both_images(tmp_path):
    """Two images sharing the SAME layer (identical bytes → one
    cached blob) with a secret in it: the deferred sieve collect
    must re-merge secrets for the image whose analysis saw the blob
    as already-cached and collected nothing itself (review r5)."""
    from tests.test_e2e_image import make_image_tar
    from trivy_tpu.runtime.batch import BatchScanRunner
    from trivy_tpu.types import ScanOptions

    shared = {"srv/cfg.conf": b"token=ghp_" + b"C" * 36 + b"\n"}
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    tar1 = make_image_tar(tmp_path / "a", [shared])
    tar2 = make_image_tar(
        tmp_path / "b",
        [shared, {"etc/extra.txt": b"nothing here\n"}])

    runner = BatchScanRunner(backend="cpu-ref")
    res = runner.scan_paths(
        [tar1, tar2],
        ScanOptions(backend="cpu-ref", security_checks=["secret"]))
    assert res[0].error == "" and res[1].error == ""

    def secret_count(r):
        return sum(len(x.secrets) for x in r.report.results)

    assert secret_count(res[0]) == 1
    assert secret_count(res[1]) == 1, \
        "shared cached layer must surface the secret on BOTH images"
