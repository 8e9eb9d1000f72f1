"""CPU rehearsal of ``chip_smoke.py`` (tier-1).

The smoke's stage functions run here at a tiny size on the CPU mesh
under the suite's explicit ``JAX_PLATFORMS=cpu`` — the same
assertions as on the chip (every slot ok, zero bisects / quarantines
/ host fallbacks, device-work counters > 0, planted secrets and
expected CVE counts found, cpu-ref byte parity, a warm child that
compiles nothing known) minus the platform check. This is the
rehearsal to run before spending chip time; a smoke run itself never
passes off the chip, which the last tests pin.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
try:
    import chip_smoke
finally:
    sys.path.remove(REPO)

SZ = dict(chip_smoke.TINY, seed=20260926)


@pytest.fixture(scope="module")
def work(tmp_path_factory):
    work = str(tmp_path_factory.mktemp("smoke") / "work")
    os.makedirs(work)
    counts = chip_smoke.make_data(SZ, work, SZ["seed"])
    assert counts["db_rows"] == 3 * (2 * SZ["os_universe"]
                                     + 4 * SZ["ghsa_pkgs"])
    assert counts["images"] == SZ["images"]
    assert counts["min_image_text_bytes"] >= SZ["image_bytes"]
    assert counts["rules"] == 83 and counts["patterns"] == 160
    yield work
    chip_smoke.kill_children()


@pytest.fixture(scope="module")
def fleet(work):
    return {sched: chip_smoke.stage_fleet(SZ, work, sched)
            for sched in ("on", "off")}


@pytest.fixture(scope="module")
def sbom(work):
    line = chip_smoke.stage_sbom(SZ, work)
    line["counts"].update(chip_smoke.stage_sbom_cli(SZ, work))
    return line


@pytest.fixture(scope="module")
def server(work):
    return chip_smoke.stage_server(SZ, work)


def test_full_size_is_what_the_issue_names():
    full = chip_smoke.FULL
    assert 3 * (2 * full["os_universe"]
                + 4 * full["ghsa_pkgs"]) >= 1_000_000
    assert full["images"] == 32 and full["files"] >= 1000
    assert full["image_bytes"] >= 8 << 20
    assert full["os_pkgs"] >= 200
    assert (full["sboms"], full["comps"]) == (2000, 40)
    assert full["kernel_rows"] == 4096 and full["clients"] == 8
    assert (full["parity_images"], full["parity_sboms"]) == (4, 200)
    assert chip_smoke.REDUCED == []


def test_data_is_seeded(work, tmp_path):
    again = str(tmp_path / "work")
    os.makedirs(again)
    chip_smoke.make_data(SZ, again, SZ["seed"])
    for rel in ("images/img00.tar", "sboms.jsonl"):
        with open(os.path.join(work, rel), "rb") as a, \
                open(os.path.join(again, rel), "rb") as b:
            # sboms.jsonl names files under its own work dir
            assert a.read().replace(work.encode(), b"W") == \
                b.read().replace(again.encode(), b"W"), rel


def test_kernels_agree_with_the_host_references(work):
    line = chip_smoke.stage_kernels(SZ, work)
    c = line["counts"]
    assert line["platform"] == "cpu"
    assert c["sieve_shape"] == [SZ["kernel_rows"], 2048]
    assert c["patterns"] == 160 and c["hit_cells"] > 0
    assert c["custom_chains"] > 16      # the custom table differs
    assert c["resident_table_rows"] == 3 * (
        2 * SZ["os_universe"] + 4 * SZ["ghsa_pkgs"])


@pytest.mark.parametrize("sched", ["on", "off"])
def test_fleet_scan_does_device_work(fleet, sched):
    line = fleet[sched]
    c = line["counts"]
    assert line["platform"] == "cpu" and line["devices"] >= 1
    assert c["images"] == SZ["images"]
    assert c["dfa_dispatches"] > 0 and c["interval_waves"] > 0
    assert c["device_bytes"] >= SZ["images"] * SZ["image_bytes"]
    assert c["os_vulns"] > 0 and c["lang_vulns"] > 0
    # 6 planted per image: 2 straddling tokens, aws, slack, pem,
    # and the token in the non-UTF-8 file
    assert c["secrets"] >= 6 * SZ["images"]


def test_both_execution_paths_report_the_same(fleet, work):
    out = os.path.join(work, "out")
    assert chip_smoke._load_docs(
        os.path.join(out, "fleet-on.json")) == chip_smoke._load_docs(
        os.path.join(out, "fleet-off.json"))


def test_a_degraded_slot_does_not_pass(fleet, work):
    exp = chip_smoke._expect(work)
    docs = chip_smoke._load_docs(
        os.path.join(work, "out", "fleet-on.json"))
    bad = json.loads(docs[0])
    bad["Status"] = "degraded"
    with pytest.raises(chip_smoke.SmokeFailure, match="status"):
        chip_smoke._check_findings(
            [json.dumps(bad)] + docs[1:], exp["images"])
    with pytest.raises(chip_smoke.SmokeFailure, match="planted"):
        miss = json.loads(docs[0])
        miss["Results"] = [r for r in miss["Results"]
                           if r.get("Class") != "secret"]
        chip_smoke._check_findings(
            [json.dumps(miss)] + docs[1:], exp["images"])


def test_a_run_without_device_work_does_not_pass(work):
    exp = chip_smoke._expect(work)
    dump = chip_smoke._stats_dump(
        chip_smoke._log(work, "fleet-on"))
    for section, key in (("secret", "dfa_dispatches"),
                         ("secret", "device_bytes"),
                         ("detect", "device_waves")):
        idle = json.loads(json.dumps(dump))
        idle[section][key] = 0
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke._check_device_work(idle, exp["images"])
    for key in ("batch_bisects", "quarantined", "host_fallbacks"):
        hid = json.loads(json.dumps(dump))
        hid["counters"][key] = 1
        with pytest.raises(chip_smoke.SmokeFailure, match=key):
            chip_smoke._check_device_work(hid, exp["images"])


def test_sbom_batch(sbom):
    c = sbom["counts"]
    assert c["sboms"] == SZ["sboms"] and c["vulns"] > 0
    assert c["interval_waves"] > 0 and c["cli_sbom_equal"]
    assert c["host_fallback_rate"] == 0.0


def test_server_owns_the_device_and_clients_are_thin(server):
    c = server["counts"]
    assert server["platform"] == "cpu"      # from /healthz
    assert c["clients"] == SZ["clients"]
    assert c["interval_waves"] > 0 and c["secrets"] > 0


def test_cpu_ref_parity_is_byte_identical(work, fleet, sbom, server):
    line = chip_smoke.stage_parity(
        SZ, work, chip_smoke.start_parity(SZ, work))
    same = line["counts"]["byte_identical"]
    assert same == {"fleet-on": SZ["parity_images"],
                    "fleet-off": SZ["parity_images"],
                    "clients": min(SZ["clients"],
                                   SZ["parity_images"]),
                    "sboms": SZ["parity_sboms"]}
    # and a single differing byte fails it
    path = os.path.join(work, "out", "ref-fleet.json")
    shutil.copy(path, path + ".bak")
    with open(path, encoding="utf-8") as f:
        docs = json.load(f)
    docs[0]["ArtifactName"] += "x"
    with open(path, "w", encoding="utf-8") as f:
        json.dump(docs, f)
    try:
        with pytest.raises(chip_smoke.SmokeFailure,
                           match="differs from cpu-ref"):
            chip_smoke.stage_parity(SZ, work, [])
    finally:
        shutil.move(path + ".bak", path)


def test_warm_child_compiles_nothing_known(work, fleet, sbom):
    line = chip_smoke.stage_cache(SZ, work, fleet["on"])
    c = line["counts"]
    assert c["known_programs"] >= 2         # sieve + interval
    assert c["persistent_cache_hits"] >= c["known_programs"]
    assert c["fresh_compiles_of_known_programs"] == 0


def test_mesh_stage_spreads_rows_over_four_devices(work):
    line = chip_smoke.stage_mesh(SZ, work, n_devices=4)
    c = line["counts"]
    assert c["mesh_shape"] == [2, 2]
    assert len(c["sieve_rows_per_device"]) == 4
    assert len(c["interval_rows_per_device"]) == 4
    assert len(c["shard_occupancy"]) == 4
    assert c["sbom_vulns"] > 0


# ---------------------------------------------------------------
# the smoke itself never passes off the chip
# ---------------------------------------------------------------

def test_smoke_fails_at_stage_one_without_a_tpu(tmp_path, capsys):
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.run(SZ, str(tmp_path), stages=("device",))
    cap = capsys.readouterr()
    assert cap.out == ""            # a failed run prints no result
    line = json.loads(cap.err.strip().splitlines()[-1])
    assert (line["stage"], line["ok"]) == ("device", False)


def test_result_line_has_the_contract_keys_and_no_others():
    summary = {"stage": "summary",
               "device": {"platform": "tpu", "kind": "TPU v5 lite",
                          "count": 1},
               "stages": {"device": "passed"}, "reduced": [],
               "wall_s": 1.0, "claim": None}
    assert json.loads(chip_smoke.result_line(summary)) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite",
                   "count": 1}}


def test_passing_main_ends_with_summary_then_result(
        tmp_path, capsys, monkeypatch):
    summary = {"stage": "summary",
               "device": {"platform": "tpu", "kind": "TPU v5 lite",
                          "count": 1},
               "stages": {s: "passed" for s in chip_smoke.STAGES},
               "reduced": [], "wall_s": 1.0, "claim": None}
    monkeypatch.setattr(chip_smoke, "run",
                        lambda sz, out, stages: dict(summary))
    assert chip_smoke.main(["--out", str(tmp_path)]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert list(json.loads(out[-2]))[-1] == "claim"
    last = json.loads(out[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    # a partial run gets the summary, no result line, exit != 0
    assert chip_smoke.main(["--out", str(tmp_path),
                            "--stages", "device,data"]) == 4
    out = capsys.readouterr().out.strip().splitlines()
    assert "ok" not in json.loads(out[-1])


def test_smoke_alone_in_a_directory_fails_and_prints_nothing(
        tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    p = subprocess.run([sys.executable, "chip_smoke.py"],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=60)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no trivy_tpu package" in p.stderr


def test_parent_never_imports_jax():
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; "
         "sys.exit(any(m == 'jax' or m.startswith('jax.') "
         "for m in sys.modules))"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr
