"""Opt-in on-TPU probe of every Pallas kernel (tests/_kernels_tpu_worker.py).

Mosaic's verdict on a kernel, MXU bf16 truncation and VMEM limits are
invisible to the interpret-mode CPU tests, so the real kernels run on the
real chip against their XLA oracles at ``r50_fpn_coco`` recipe shapes.
The tolerances and their reasons live next to each check in the worker.

Same opt-in pattern as tests/test_overfit_tpu.py: the in-process suite is
pinned to the fake CPU mesh (and so never holds the chip), and the chip
work runs in ONE subprocess without the platform pin, gated behind
RUN_KERNELS_TPU=1.  Through the chip tool:

    chiprun -- env RUN_KERNELS_TPU=1 python -m pytest tests/test_kernels_tpu.py -q
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

pytestmark = [
    pytest.mark.slow,
    pytest.mark.skipif(
        not os.environ.get("RUN_KERNELS_TPU"),
        reason="set RUN_KERNELS_TPU=1 (needs the TPU; ~3-5 min)",
    ),
]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

def test_every_pallas_kernel_compiles_and_matches_on_tpu():
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("MX_RCNN_POOL_BWD", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "_kernels_tpu_worker.py")],
        env=env, capture_output=True, text=True, timeout=3000,
    )
    lines = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert lines, (proc.stdout[-2000:], proc.stderr[-4000:])
    out = json.loads(lines[-1][len("RESULT "):])
    assert out["platform"] == "tpu", out
    # All six pallas_call sites compiled and matched on the installed
    # libtpu: ROIAlign forward and backward in PR 21, the KDA scan's
    # chunk-local pair (ops/pallas/kda.py) in PR 31, causal attention's pair
    # (ops/pallas/attention.py) in PR 33 (PERF.md); a refusal here is a
    # regression.
    failed = {
        name: res for name, res in out["probes"].items() if not res["ok"]
    }
    assert not failed, json.dumps(failed, indent=1)
    assert proc.returncode == 0, proc.stderr[-4000:]
