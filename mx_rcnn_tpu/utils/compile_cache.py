"""Where the persistent compile cache lives — one rule for every entry point.

:func:`configure_cache` is the only place in the repo that points jax's
persistent compilation cache anywhere, and every entry point that compiles
calls it once, after its platform is decided:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax reads the variable itself, at
  import.  The directory is used exactly as given — no subdirectory is
  added, nothing in it is pruned, nothing is written anywhere else.  This
  is how a caller that starts a fresh machine per run (the chip tool)
  hands the program a cache that outlives the run.
- unset, accelerator backend: ``<checkout>/.jax_cache``.  No device
  fingerprint in the path: jax's own cache key already carries the
  backend, the device kind and the compiler version.
- unset, CPU backend (the test suite's fake mesh and the CPU-only
  harnesses): ``<checkout>/tests/.jax_cache/<cpu fingerprint>``.  The
  fingerprint stays because XLA:CPU executables are codegen'd for the
  COMPILING machine — loading another machine's blobs risks SIGILL and
  silently changes numerics (a recorded golden once reproduced only
  because the cache replayed the recording machine's executables) — and
  jax's key does not separate two x86 hosts.

Both default paths are fixed: nothing in them comes from a temporary
name, a pid, the clock or a boot id, because jax only hits entries it
finds under the same directory on the next run.

Import note: this module's own imports are stdlib, but importing it pulls
in the ``mx_rcnn_tpu`` package, which imports jax.  That initialises no
backend, but platform variables (``JAX_PLATFORMS``, ``XLA_FLAGS``) must
be pinned BEFORE the import.
"""

from __future__ import annotations

import hashlib
import os
import re

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEVICE_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")
CPU_CACHE_ROOT = os.path.join(REPO_ROOT, "tests", ".jax_cache")

# Comma-joined run of LLVM ±feature tokens, e.g.
# "+64bit,+avx2,...,+prefer-no-scatter,+prefer-no-gather,-amx-fp16,..."
_FEATURE_RUN = re.compile(rb"[+-][a-z0-9_.\-]+(?:,[+-][a-z0-9_.\-]+){8,}")


def _features_from_blob(blob: bytes) -> str:
    """Cache-key material from a serialized AOT probe executable.

    Preferred: the longest ``+feat,-feat,...`` run — the human-auditable
    LLVM target-feature string itself.  The installed jaxlib (0.9.0) does
    not embed a recognizable run, so the WHOLE blob is hashed instead: the
    codegen'd bytes differ wherever the target features differ, so the key
    discriminates exactly the failure mode that a /proc/cpuinfo proxy was
    seen to miss (two hosts with identical kernel-reported flags and
    different LLVM preference features).
    """
    runs = [m.group(0) for m in _FEATURE_RUN.finditer(blob)]
    if runs:
        return max(runs, key=len).decode()
    return "blob:" + hashlib.sha1(blob).hexdigest()


def llvm_target_features() -> str | None:
    """Fingerprint of the code XLA:CPU generates on this host.

    Serializes a trivial AOT-compiled executable and reduces it with
    :func:`_features_from_blob`.  Requires the CPU backend (returns None
    on any other — the caller then keys on /proc/cpuinfo alone).
    """
    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "cpu":
        return None
    probe = (
        jax.jit(lambda x: x @ x)
        .lower(jnp.zeros((4, 4), jnp.float32))
        .compile()
    )
    return _features_from_blob(probe.runtime_executable().serialize())


def cpu_fingerprint() -> str:
    """Hash of this host's CPU identity and the compiler stack.

    Mixes every distinct ``flags``/``Features`` and CPUID identity line of
    ``/proc/cpuinfo`` (sorted union — heterogeneous cores report differing
    lines in unstable order; ``platform.uname()`` when unreadable),
    :func:`llvm_target_features` — the primary key, because the cpuinfo
    lines alone were seen to collide across hosts whose codegen differed —
    and the jaxlib version (blob layout and codegen move with it).
    """
    import jaxlib

    fields = (
        # x86 feature + identity lines.
        "flags", "vendor_id", "cpu family", "model", "stepping", "model name",
        # ARM equivalents.
        "Features", "CPU implementer", "CPU part", "CPU variant",
        "CPU architecture", "CPU revision",
    )
    key = ""
    try:
        with open("/proc/cpuinfo") as f:
            lines = {
                line.strip()
                for line in f
                if line.split(":")[0].strip() in fields
            }
        key = "\n".join(sorted(lines))
    except OSError:
        pass
    if not key:
        import platform

        key = repr(platform.uname())
    feats = llvm_target_features()
    key += "\nllvm_target_features=" + (feats if feats is not None else "?")
    key += "\njaxlib=" + jaxlib.version.__version__
    return hashlib.sha1(key.encode()).hexdigest()[:8]


def configure_cache() -> str:
    """Apply the module's rule; returns the directory in use.

    Initialises the backend (``jax.default_backend()``), so call it once
    the platform is decided and before the first compile.  A directory
    that is already configured — by ``JAX_COMPILATION_CACHE_DIR`` or by an
    earlier call in this process — is left exactly as it is.
    """
    import jax

    on_cpu = jax.default_backend() == "cpu"
    # CPU: only programs worth the disk (the suite compiles thousands of
    # tiny ones, and the chip tool copies tests/.jax_cache with the tree).
    # Accelerator: everything — a cold start there is a few hundred small
    # programs around each large one, and each costs a compile.
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", 5.0 if on_cpu else 0.0
    )
    placed = jax.config.jax_compilation_cache_dir
    if placed:
        return placed
    cache_dir = (
        os.path.join(CPU_CACHE_ROOT, cpu_fingerprint())
        if on_cpu
        else DEVICE_CACHE_DIR
    )
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
