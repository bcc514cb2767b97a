"""chip_smoke.py's contract, as far as a machine without a chip can
show it: it refuses to pass where the serving process is not on a TPU,
its explicit dry run rehearses every phase on the CPU, and its parent
process never loads jax (one process per chip)."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path, timeout):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # The environment places the compile cache: the server children
    # inherit it and chip_smoke must report this directory, not set one.
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla-cache")
    return subprocess.run(
        [sys.executable, SMOKE, *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout,
    )


def test_no_chip_means_failure_naming_the_platform(tmp_path):
    proc = _run([], tmp_path, timeout=300)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert "not on a TPU" in proc.stderr
    # No result is printed: nothing on stdout parses as the JSON line.
    assert '"ok"' not in proc.stdout


def test_dry_run_passes_every_phase_at_a_tiny_size(tmp_path):
    evidence = os.path.join(REPO, "chiprun_out", "chip_smoke.json")
    before = os.path.getmtime(evidence) if os.path.exists(evidence) else None
    proc = _run(["--dry-run"], tmp_path, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    # A dry run writes beside a chip run's evidence, never over it.
    after = os.path.getmtime(evidence) if os.path.exists(evidence) else None
    assert after == before
    with open(os.path.join(REPO, "chiprun_out", "chip_smoke_dry_run.json")) as f:
        assert json.loads(f.read())["dry_run"]
    assert "proves nothing about the chip" in proc.stdout
    counts_line, verdict_line = proc.stdout.strip().splitlines()[-2:]
    # The last line is the verdict and holds exactly these keys.
    verdict = json.loads(verdict_line)
    assert verdict == {"ok": True, "device": verdict["device"]}
    assert sorted(verdict["device"]) == ["count", "kind", "platform"]
    assert verdict["device"]["platform"] == "cpu"  # never mistaken for a chip run
    assert isinstance(verdict["device"]["kind"], str)
    assert type(verdict["device"]["count"]) is int
    result = json.loads(counts_line)
    assert result["ok"] is True and result["mismatches"] == 0
    assert result["device"] == verdict["device"]
    assert "dry_run" in result and result["reduced"]
    s1, s2 = result["start1"], result["start2"]
    assert s1["keys_loaded"] == 3000
    assert s1["live_keys"]["lane0of1"] >= 3000
    assert s1["banks"]["lane0of1"]["slot_table"] == "native"
    assert result["native_slot_table"]["built_here"].endswith("_libslottable.so")
    # Cells came back at a fractional emission interval and were granted.
    assert s1["gcra_refills_granted"] > 0
    assert s1["max_launch_lanes"] >= 1024
    assert not any(s1["faults"].values()) and not any(s2["faults"].values())
    # Start 2 warms all 50 serving shapes through start 1's cache.
    assert s2["shapes_compiled"] == 50
    cache = result["compile_cache"]
    assert cache["dir"] == str(tmp_path / "xla-cache")
    assert 0 < cache["entries_after_start1"] <= cache["entries_after_start2"]


def test_parent_never_loads_jax():
    """Importing everything the chip_smoke parent imports leaves jax
    unloaded — a parent that touched jax would hold the chip its server
    child needs."""
    code = (
        "import sys; sys.argv = ['chip_smoke.py']; import chip_smoke; "
        "sys.exit(1 if 'jax' in sys.modules else 0)"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True, text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
