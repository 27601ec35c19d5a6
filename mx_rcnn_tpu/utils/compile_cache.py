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

:func:`configure_cache` also installs the program's one compile listener
(:func:`install_compile_listener`): every trace, lowering and compile (or
load) of the process is laid on the span timeline, and its first call
records how long the process took to get there (``setup.import``,
``setup.backend``).

Import note: this module's own imports are stdlib, but importing it pulls
in the ``mx_rcnn_tpu`` package, which imports jax.  That initialises no
backend, but platform variables (``JAX_PLATFORMS``, ``XLA_FLAGS``) must
be pinned BEFORE the import.
"""

from __future__ import annotations

import hashlib
import os
import re
import threading
import time

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


# jax.monitoring's names (jax/_src/dispatch.py, compiler.py).  A backend
# compile event closes every program the process obtains an executable for,
# its seconds including the persistent cache's retrieval on a hit; a hit
# fires ``cache_hits`` first, on the same thread.  Tracing a function to a
# jaxpr and lowering the jaxpr to a module report their seconds the same
# way, and a trace also announces its start (a scalar under the same name):
# an inner jit's trace runs inside the outer's.
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_SPAN_OF = {
    _TRACE: "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    _BACKEND_COMPILE: "jit.compile",
}
# An eager op's trace is tens of microseconds and a process makes
# thousands (4,395 outermost in one benchmark run, 0.6 s together against
# 3.0 s in the four over a millisecond): shorter ones get no span.
_MIN_TRACE_S = 1e-3
_listening = False
_start_recorded = False
# This thread's compile in flight was a cache hit (``hit``); traces it is
# inside (``depth``).
_local = threading.local()


_COUNTERS = {
    "jit_programs_built_total":
        "programs compiled or loaded from the persistent cache",
    "jit_backend_compile_seconds_total":
        "seconds inside backend compiles (cache retrieval included)",
}


def _counter(name: str):
    from mx_rcnn_tpu import obs

    return obs.counter(name, _COUNTERS[name])


def compile_totals() -> tuple[int, float]:
    """(programs built, seconds inside their backend compiles) so far in
    this process, as the listener counted them."""
    return (
        int(_counter("jit_programs_built_total").value()),
        _counter("jit_backend_compile_seconds_total").value(),
    )


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_HIT:
        _local.hit = True


def _on_scalar(event: str, value, **kw) -> None:
    if event == _TRACE:
        _local.depth = getattr(_local, "depth", 0) + 1


def _on_duration(event: str, seconds: float, **kw) -> None:
    name = _SPAN_OF.get(event)
    if name is None:
        return
    from mx_rcnn_tpu import obs

    attrs = {"fun_name": str(kw.get("fun_name", "?"))}
    if event == _TRACE:
        _local.depth = depth = max(getattr(_local, "depth", 1) - 1, 0)
        if depth or seconds < _MIN_TRACE_S:  # inside the outermost's span
            return
    elif event == _BACKEND_COMPILE:
        attrs["cache_hit"] = getattr(_local, "hit", False)
        _local.hit = False
        _counter("jit_programs_built_total").inc()
        _counter("jit_backend_compile_seconds_total").inc(seconds)
    dur_ns = int(seconds * 1e9)
    obs.tracer().record(
        name, time.monotonic_ns() - dur_ns, dur_ns, subsystem="jit",
        attrs=attrs,
    )


def install_compile_listener() -> None:
    """Lay every program this process builds on the span timeline
    (subsystem ``jit``, each span ending when the listener is told):
    ``jit.trace`` (``fun_name``; the outermost function traced to a jaxpr,
    the jits inside it not again, nor one under a millisecond),
    ``jit.lower`` (the jaxpr lowered to a module) and ``jit.compile``
    (``fun_name``, ``cache_hit``: an executable compiled or loaded from the
    persistent cache) — the last also counted in
    ``jit_programs_built_total`` and ``jit_backend_compile_seconds_total``
    (no labels), which :func:`compile_totals` reads.  Idempotent: jax keeps
    listeners for the life of the process."""
    global _listening
    if _listening:
        return
    _listening = True
    import jax

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_scalar_listener(_on_scalar)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def _process_start_ns() -> int | None:
    """``time.monotonic_ns()`` at which this process started, from the
    kernel's record (``/proc/self/stat``, in clock ticks since boot); None
    where that cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age_ns = time.clock_gettime_ns(time.CLOCK_BOOTTIME) - int(
            ticks * 1e9 / os.sysconf("SC_CLK_TCK")
        )
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.monotonic_ns() - age_ns


def _record_start(t0: int, t1: int) -> None:
    """Once a process (subsystem ``process``): ``setup.import``, process
    start to the first :func:`configure_cache` (the interpreter, ``import
    jax``, whatever the entry point imported and did first);
    ``setup.backend``, the backend's start inside it, ``t0``..``t1`` (next
    to nothing where the caller had touched the backend already)."""
    global _start_recorded
    if _start_recorded:
        return
    _start_recorded = True
    from mx_rcnn_tpu import obs

    born = _process_start_ns()
    if born is not None:
        obs.tracer().record("setup.import", born, t0 - born, subsystem="process")
    obs.tracer().record("setup.backend", t0, t1 - t0, subsystem="process")


def configure_cache() -> str:
    """Apply the module's rule; returns the directory in use.

    Initialises the backend (``jax.default_backend()``), so call it once
    the platform is decided and before the first compile.  A directory
    that is already configured — by ``JAX_COMPILATION_CACHE_DIR`` or by an
    earlier call in this process — is left exactly as it is.
    """
    import jax

    install_compile_listener()
    t0 = time.monotonic_ns()
    on_cpu = jax.default_backend() == "cpu"
    _record_start(t0, time.monotonic_ns())
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
