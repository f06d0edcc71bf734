"""chip_smoke.py, the chip host's one command, in its explicit CPU mode
at a tiny size — and refusing to run without a chip when that mode is
not asked for (tests pin JAX_PLATFORMS=cpu, so this box has none)."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def run_smoke(tmp_path, *extra):
    return subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path)] + list(extra),
        cwd=ROOT, capture_output=True, text=True, timeout=600)


def test_cpu_mode_summary(tmp_path):
    proc = run_smoke(tmp_path, "--cpu", "--hosts", "2000", "--shards", "2")
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    # the last line is the result, with exactly these keys
    result = json.loads(lines[-1])
    assert list(result) == ["ok", "device"] and result["ok"] is True
    assert sorted(result["device"]) == ["count", "kind", "platform"]
    assert result["device"]["platform"] == "cpu"
    assert isinstance(result["device"]["kind"], str)
    assert isinstance(result["device"]["count"], int)
    assert result["device"]["count"] >= 1
    summary = json.loads(lines[-2])
    assert summary["device"] == result["device"]
    assert summary["device_work_on_served_path"] == "none"
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    assert summary["host"]["cpus_allowed"] >= 1
    assert summary["host"]["shards_auto_would_be"] >= 1
    serve = summary["serve"]
    assert serve["names"] == 2000 and serve["shards"] == 2
    assert len(set(serve["worker_pids"])) == 2
    assert serve["worker_store"] == "ReplicaStore"
    assert serve["mutation_read_back_from"] == serve["worker_pids"]
    assert serve["checks_passed"]["udp_a"] >= 64
    assert serve["checks_passed"]["srv_udp_truncated"] == 1
    assert serve["dnsblast"]["errors"] == 0
    assert serve["orphan_pids"] == []
    for worker in serve["native_lane"]:
        assert worker["zone_entries"] > 0 and worker["native_serves"] > 0
    assert "names" in summary["assumed"]
    with open(tmp_path / "summary.json") as f:
        assert json.load(f) == summary


def test_without_a_chip_it_fails_and_says_so(tmp_path):
    proc = run_smoke(tmp_path)
    assert proc.returncode != 0
    assert "need 'tpu'" in proc.stderr and "no accelerator" in proc.stderr
    # no result: nothing on stdout parses as the summary
    assert '"ok"' not in proc.stdout
