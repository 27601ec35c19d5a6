"""Test config: force JAX onto CPU with 8 fake devices.

This is the standard JAX analog of a fake-NCCL backend (SURVEY.md section 5):
multi-chip sharding logic is exercised on an 8-device CPU mesh with no TPU
attached.  Must run before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402  (safe: importing jax does not init backends)

# Something may have imported jax before this file ran, in which case
# jax.config captured JAX_PLATFORMS from the outer environment — override
# through the config API, not the env var.
jax.config.update("jax_platforms", "cpu")

# Persistent compile cache: the integration tests jit full ResNet train
# steps; caching makes re-runs of the suite seconds instead of minutes.
# One rule for every entry point (utils/compile_cache.py): on the CPU
# backend that is tests/.jax_cache/<cpu fingerprint>, or wherever
# JAX_COMPILATION_CACHE_DIR points.
from mx_rcnn_tpu.utils.compile_cache import configure_cache  # noqa: E402

configure_cache()

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.RandomState(0)
