"""The two bytes functions on hand-worked shapes."""

import work

CONFIG = {"sizes": {"seg_len": 2048}, "patterns": 160}


def test_sieve_bytes_one_row():
    # 2048 bytes read, 160 bits = 10 words of 16 bits = 20 bytes written
    assert work.sieve_row_bytes(2048, 160) == 2068
    assert work.sieve_row_bytes(2048, 161) == 2070


def test_sieve_bytes_of_a_dispatch():
    # 8192 rows of 2048 bytes were sent: 16,777,216 bytes
    stats = {"secret": {"device_bytes": 8192 * 2048}}
    assert work.sieve_bytes(stats, CONFIG) == 8192 * 2068


def test_interval_bytes_of_a_wave():
    # a job: 2 x 4 bytes read, 17 int32 gathered, 1 byte written
    assert work.INTERVAL_JOB_BYTES == 8 + 17 * 4 + 1 == 77
    stats = {"detect": {"device_rows": 4096}}
    assert work.interval_bytes(stats, CONFIG) == 4096 * 77


def test_resident_row_is_68_bytes():
    # 69,360,000 resident bytes over 1,020,000 rows (chip_smoke, PR 21)
    assert 69_360_000 / 1_020_000 == 68


def test_generator_processes_write_the_same_images(tmp_path):
    """``gen.py`` as a command (one generator process of a closed-loop
    pool) writes what ``build_images`` writes in process."""
    import hashlib
    import json
    import subprocess
    import sys

    import gen
    from conftest import BENCH
    sizes = json.load(open(f"{BENCH}/configs/image-fleet-1chip.json"))["sizes"]
    sizes.update(json.load(open(f"{BENCH}/tests/tiny.json"))["sizes"])
    facts = {}
    for how in ("inline", "command"):
        d = tmp_path / how
        d.mkdir()
        if how == "inline":
            facts[how] = gen.build_images(sizes, [0, 3], str(d), 77)
        else:
            out = subprocess.run(
                [sys.executable, gen.__file__, json.dumps(
                    {"sizes": sizes, "ns": [0, 3], "directory": str(d),
                     "seed": 77})], capture_output=True, check=True)
            facts[how] = json.loads(out.stdout)
    digest = {how: [hashlib.sha256(open(f["path"], "rb").read()).hexdigest()
                    for f in facts[how]] for how in facts}
    assert digest["inline"] == digest["command"]
    for a, b in zip(facts["inline"], facts["command"]):
        a, b = dict(a, path=""), dict(b, path="")
        assert json.loads(json.dumps(a)) == b
