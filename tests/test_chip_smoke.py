"""chip_smoke.py's manners towards whoever runs it: the one line it may
write to standard output has exactly the contract's keys, and where no
chip initialises it fails before loading data and prints no result.  What
the script proves about the program only a chip run can show."""

import json
import os
import subprocess
import sys

from tests.conftest import REPO_ROOT

import chip_smoke


def test_result_line_has_exactly_the_contracts_keys():
    assert chip_smoke.RESULT_KEYS == ("ok", "device")
    assert chip_smoke.DEVICE_KEYS == ("platform", "kind", "count")
    line = chip_smoke.result_line("tpu", "TPU v5 lite", 1)
    assert "\n" not in line
    obj = json.loads(line)
    assert tuple(obj) == chip_smoke.RESULT_KEYS
    assert tuple(obj["device"]) == chip_smoke.DEVICE_KEYS
    assert obj == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_no_chip_fails_before_loading_data_and_prints_no_result(tmp_path):
    """JAX_PLATFORMS=cpu in the caller's environment and no flag: the
    server child is still started with JAX_PLATFORMS=tpu, finds no chip at
    its first codec selection, and the run ends in the preflight."""
    work = tmp_path / "work"
    r = subprocess.run(
        [sys.executable, str(REPO_ROOT / "chip_smoke.py"),
         "--workdir", str(work)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=tmp_path,
        capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "Unable to initialize backend" in r.stderr, r.stderr[-2000:]
    report = json.loads((work / "report.json").read_text())
    assert report["ok"] is False and report["device"] is None
    assert list(report["phases"]) == ["start"]  # preflight never passed
    # the one-needle probe volume is all the data there is
    dats = sorted(p.name for p in (work / "data").glob("*.dat"))
    assert dats == ["smokeprobe_1.dat"], dats
