"""chip_smoke.py's contract, as far as a CPU can check it.

The script itself is for the chip (and says no to anything else); what is
tested here is that refusal, and — slow — the three phases themselves at
``tiny_synthetic`` on a fake CPU mesh with interpret-mode kernels, so the
script is debugged on the CPU and only measured on the chip.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra)
    return env


def test_refuses_a_cpu_before_building_anything(tmp_path):
    # The cache directory is the witness: a program that compiled would
    # have left an entry (the smoke caches everything it builds), and the
    # directory is not even created.
    cache = tmp_path / "cache"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=_env(JAX_COMPILATION_CACHE_DIR=str(cache)), cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode not in (0, None), proc.stdout
    assert "platform=cpu" in proc.stdout
    assert '"ok"' not in proc.stdout  # no result line
    assert "not a TPU" in proc.stderr
    assert not cache.exists()


def test_fails_alone_without_the_program(tmp_path):
    # In a directory that holds chip_smoke.py and nothing else of the
    # repo there is no system to prove: non-zero, no result line.
    alone = tmp_path / "chip_smoke.py"
    alone.write_bytes(open(os.path.join(REPO, "chip_smoke.py"), "rb").read())
    env = _env()
    env.pop("PYTHONPATH")
    proc = subprocess.run(
        [sys.executable, str(alone)], env=env, cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode not in (0, None)
    assert '"ok"' not in proc.stdout


def test_result_line_holds_exactly_ok_and_device():
    # The reader of the last line accepts these keys and no others; the
    # full account (phases, versions) is the line before it.
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    line = chip_smoke.result_line(
        {"platform": "tpu", "device_kind": "TPU v5 lite", "n_devices": 4}
    )
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4},
    }


@pytest.mark.slow
def test_three_phases_on_a_fake_two_device_mesh(tmp_path):
    # Two fake devices, not conftest's eight: every replica of the serve
    # phase compiles its own programs, and two already prove the mesh,
    # the shard_map'd kernel and replica-per-device placement.
    code = (
        "import json, chip_smoke;"
        "from mx_rcnn_tpu.utils.compile_cache import configure_cache;"
        "configure_cache();"
        f"p = chip_smoke.run_phases('tiny_synthetic', {str(tmp_path)!r});"
        "print('PHASES ' + json.dumps(p))"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env=_env(
            XLA_FLAGS="--xla_force_host_platform_device_count=2",
            MX_RCNN_PALLAS_INTERPRET="1",
        ),
        cwd=REPO, capture_output=True, text=True, timeout=3000,
    )
    assert proc.returncode == 0, proc.stderr[-6000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("PHASES ")][-1]
    phases = json.loads(line[len("PHASES "):])
    assert set(phases) == {"train", "eval", "serve"}
    assert phases["train"]["pool_impl"] == "pallas-shardmap"
    assert phases["train"]["placement"]["param_copies"] == 2
    assert phases["serve"]["answered_by"] == [0, 1]
    for p in phases.values():
        assert p["built_after_first_call"] == []
