"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py        (no arguments, no environment)

Drives the main path once, through the entry points a user calls, at the
full width of ``r50_fpn_coco`` (ResNet-50-FPN, 81 classes, 800x1344,
bf16 backbone, preset defaults) with synthetic pixels and weights made
from the preset's seed:

- **train**  ``mx_rcnn_tpu.cli.train_cli.main`` for a few steps, with a
  log point and a checkpoint inside them;
- **eval**   ``mx_rcnn_tpu.cli.eval_cli.run_eval`` on the trained state for
  two full batches per chip;
- **serve**  ``mx_rcnn_tpu.serve.build_fleet`` with one replica per chip,
  a few requests of two image sizes through ``submit()``.

ONE process holds every chip of the host: the entry points are called
in-process (a chip belongs to one process at a time, so a parent that had
touched jax could start no child that needs it), and it uses whatever
``jax.devices()`` gives — one chip or four, same file.

It fails — non-zero exit, no result line — unless jax's first device is a
TPU, before any model is built: it never runs a smaller version of itself
on a CPU.  Any exception or failed check in any phase ends the run
non-zero; nothing downgrades a phase to a warning.  What it prints per
phase is set-up fact, not speed: wall time, time spent compiling (or
loading from the persistent cache), and device memory.  The last two
lines of stdout are JSON: first the full account (``phases`` with the
device and the jax/libtpu versions), then — last, and only when every
phase passed — the result line, which holds exactly
``{"ok": true, "device": {"platform", "kind", "count"}}`` with the device
as jax reports it.

``run_phases`` is also what tests/test_chip_smoke.py calls at
``tiny_synthetic`` on a fake CPU mesh with interpret-mode kernels, so the
script is debugged here and only measured there.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class _CompileLog:
    """Every program jax builds in this process, as it happens.

    jax reports one ``backend_compile_duration`` per program — a real
    compile or a load from the persistent cache, told apart by the cache's
    own hit/miss events — with the jitted function's name.
    """

    def __init__(self) -> None:
        import jax

        self.programs: list[tuple[str, float]] = []  # (fun_name, seconds)
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **kw) -> None:
        if event == _BACKEND_COMPILE:
            self.programs.append((str(kw.get("fun_name", "?")), seconds))

    def _event(self, event: str, **kw) -> None:
        if event == _CACHE_HIT:
            self.hits += 1
        elif event == _CACHE_MISS:
            self.misses += 1

    def mark(self) -> tuple[int, int, int]:
        return len(self.programs), self.hits, self.misses

    def since(self, mark: tuple[int, int, int], first_call: str | None) -> dict:
        """Set-up account of the programs built after ``mark``.

        ``first_call`` names the phase's main jitted program: whatever is
        built AFTER that program's own build came after the phase's first
        call (its second step, its second batch) and must be nothing.
        ``None``: the caller warmed everything before ``mark``, so every
        program since is one too many.
        """
        progs = self.programs[mark[0]:]
        names = [n for n, _ in progs]
        if first_call is None:
            late = names
        else:
            if first_call not in names:
                raise AssertionError(
                    f"no program named {first_call!r} was built in this "
                    f"phase (built: {sorted(set(names))})"
                )
            late = names[names.index(first_call) + 1:]
        return {
            "programs_built": len(progs),
            "compile_or_load_s": round(sum(s for _, s in progs), 2),
            "cache_hits": self.hits - mark[1],
            "cache_misses": self.misses - mark[2],
            "built_after_first_call": late,
        }


def _memory() -> dict:
    """Device memory as the backend reports it.  Both peaks are PROCESS
    high-water marks (they never reset), so a later phase shows a new
    value only if it needed more than every phase before it.  On the TPU
    runtime ``peak_bytes_in_use`` counts live arrays only; a running
    program's temporaries are accounted as reserved
    (``peak_bytes_reserved``), and a cell is sized from the sum."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()]
    return {
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
        "peak_bytes_reserved": [s.get("peak_bytes_reserved") for s in stats],
        "bytes_in_use": [s.get("bytes_in_use") for s in stats],
    }


def _check(cond: bool, what: str) -> None:
    # Not `assert`: the checks must hold under `python -O` too.
    if not cond:
        raise AssertionError(what)


def _finite(tree: dict, what: str) -> None:
    bad = {k: v for k, v in tree.items() if not math.isfinite(float(v))}
    _check(not bad, f"{what}: non-finite values {bad}")


def _placement(cfg, state, n_dev: int) -> dict:
    """With several chips: the batch really splits one share per chip and
    the state is a full copy on each — read from ``addressable_shards`` of
    arrays placed by the same plan and ``shard_batch`` the train loop uses."""
    import jax

    from mx_rcnn_tpu.data import DetectionLoader, build_dataset, filter_roidb
    from mx_rcnn_tpu.parallel import make_mesh, shard_batch
    from mx_rcnn_tpu.train.loop import build_plan

    mesh = make_mesh()
    plan = build_plan(cfg, mesh)
    global_batch = cfg.train.per_device_batch * n_dev
    loader = DetectionLoader(
        filter_roidb(build_dataset(cfg.data, train=True).roidb()),
        cfg.data, batch_size=global_batch, train=True, seed=cfg.train.seed,
        prefetch=False,
    )
    batch = shard_batch(next(loader.iter_from(0)), mesh)
    shards = batch.images.addressable_shards
    _check(len({s.device for s in shards}) == n_dev,
           f"batch lives on {len({s.device for s in shards})} devices, "
           f"not {n_dev}")
    per_chip = global_batch // n_dev
    _check(all(s.data.shape[0] == per_chip for s in shards),
           f"batch shards hold {[s.data.shape[0] for s in shards]} images, "
           f"want {per_chip} each")
    rows = sorted(s.index[0].start or 0 for s in shards)
    _check(rows == [i * per_chip for i in range(n_dev)],
           f"batch shards start at rows {rows}: not a {n_dev}-way split")
    placed = plan.shard_state(state)
    leaf = max(jax.tree_util.tree_leaves(placed.params), key=lambda x: x.size)
    _check(len({s.device for s in leaf.addressable_shards}) == n_dev
           and all(s.data.shape == leaf.shape
                   for s in leaf.addressable_shards),
           "params are not a full copy on every device")
    return {
        "mesh": dict(mesh.shape),
        "global_batch": global_batch,
        "batch_shard_shapes": [list(s.data.shape) for s in shards],
        "param_copies": len(leaf.addressable_shards),
    }


def run_phases(config: str, workdir: str, train_steps: int = 6) -> dict:
    """Train, evaluate and serve ``config`` once; returns the per-phase
    account.  Raises on the first thing that is not right."""
    import jax
    import numpy as np

    from mx_rcnn_tpu.cli import train_cli
    from mx_rcnn_tpu.cli.common import config_from_args
    from mx_rcnn_tpu.cli.eval_cli import _restored_state, run_eval
    from mx_rcnn_tpu.detection import graph
    from mx_rcnn_tpu.parallel.step import eval_variables
    from mx_rcnn_tpu.serve import build_fleet
    from mx_rcnn_tpu.train.checkpoint import latest_step

    n_dev = jax.device_count()
    want_pool = "pallas" if n_dev == 1 else "pallas-shardmap"
    log = _CompileLog()
    phases: dict[str, dict] = {}

    # -- train -----------------------------------------------------------
    argv = [
        "--config", config, "--workdir", workdir, "--no-eval",
        "--steps", str(train_steps),
        "--set", "data.dataset=synthetic",
        "--set", "train.log_every=2",
        "--set", "train.checkpoint_every=3",
    ]
    cfg = config_from_args(train_cli.parse_args(argv))
    mark, t0 = log.mark(), time.perf_counter()
    graph.LAST_POOL_IMPL = None
    out = train_cli.main(argv)
    wall = time.perf_counter() - t0
    _check(out["final_step"] == train_steps,
           f"final_step {out['final_step']} != {train_steps}")
    run_dir = os.path.join(workdir, cfg.name)
    with open(os.path.join(run_dir, "metrics.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    # The loop logs its first step, then every train.log_every-th.
    _check([r["step"] for r in rows] == [1, *range(2, train_steps + 1, 2)],
           f"logged steps {[r['step'] for r in rows]}")
    for r in rows:
        _finite({"loss": r["loss"]}, f"train step {r['step']}")
    ckpt_dir = os.path.join(run_dir, "ckpt")
    _check(latest_step(ckpt_dir) == train_steps,
           f"no checkpoint for step {train_steps} under {ckpt_dir}")
    _check(graph.LAST_POOL_IMPL == want_pool,
           f"ROIAlign took {graph.LAST_POOL_IMPL!r}, want {want_pool!r}")
    phases["train"] = {
        "wall_s": round(wall, 1),
        "steps": train_steps,
        "global_batch": cfg.train.per_device_batch * n_dev,
        "losses": [round(r["loss"], 4) for r in rows],
        "pool_impl": graph.LAST_POOL_IMPL,
        **log.since(mark, first_call="jit(step)"),
        **_memory(),
    }
    _check(not phases["train"]["built_after_first_call"],
           "train built programs after its first step: "
           f"{phases['train']['built_after_first_call']}")

    state = _restored_state(cfg, ckpt_dir, None)
    if n_dev > 1:
        phases["train"]["placement"] = _placement(cfg, state, n_dev)

    # -- eval ------------------------------------------------------------
    per_step = max(cfg.model.test.per_device_batch, 1) * n_dev
    mark, t0 = log.mark(), time.perf_counter()
    graph.LAST_POOL_IMPL = None
    metrics = run_eval(cfg, state=state, limit=2 * per_step)
    wall = time.perf_counter() - t0
    _check(bool(metrics), "eval returned no metrics")
    _finite(metrics, "eval metrics")
    _check(graph.LAST_POOL_IMPL == want_pool,
           f"eval ROIAlign took {graph.LAST_POOL_IMPL!r}, want {want_pool!r}")
    phases["eval"] = {
        "wall_s": round(wall, 1),
        "images": 2 * per_step,
        "images_per_step": per_step,
        "metrics": {k: round(float(v), 4) for k, v in metrics.items()},
        "pool_impl": graph.LAST_POOL_IMPL,
        **log.since(mark, first_call="jit(step)"),
        **_memory(),
    }
    _check(not phases["eval"]["built_after_first_call"],
           "eval built programs after its first batch: "
           f"{phases['eval']['built_after_first_call']}")

    # -- serve -----------------------------------------------------------
    variables = eval_variables(state)
    mark, t0 = log.mark(), time.perf_counter()
    fleet = build_fleet(cfg, variables, n_replicas=n_dev)
    fleet.start()  # warms every program of every replica
    try:
        warm = log.since(mark, first_call=None)
        warm_wall = time.perf_counter() - t0
        placed = {
            r.rid: {
                d for leaf in jax.tree_util.tree_leaves(
                    r.engine.runner._active[0]
                ) for d in leaf.devices()
            }
            for r in fleet._reps()
        }
        _check(all(len(d) == 1 for d in placed.values()),
               f"a replica's params span several devices: {placed}")
        homes = [next(iter(d)) for d in placed.values()]
        _check(len(set(homes)) == n_dev,
               f"{n_dev} replicas sit on {len(set(homes))} devices: {homes}")
        rng = np.random.default_rng(0)
        sizes = [(480, 640), (720, 1280)]
        if max(cfg.data.image_size) < 480:  # the tiny CPU-test canvas
            sizes = [(96, 128), (128, 96)]
        n_req = max(4, 2 * n_dev)
        images = [
            rng.integers(0, 256, (*sizes[i % 2], 3), dtype=np.uint8)
            for i in range(n_req)
        ]
        mark_req, t1 = log.mark(), time.perf_counter()
        pending = [fleet.submit(img) for img in images]
        results = [p.result(timeout=600) for p in pending]
        serve_wall = time.perf_counter() - t1
        req = log.since(mark_req, first_call=None)
    finally:
        fleet.stop()
    levels = [r["level"] for r in results]
    _check(all(lv == "full" for lv in levels),
           f"responses degraded below 'full': {levels}")
    answered = sorted({r["replica_id"] for r in results})
    _check(answered == list(range(n_dev)),
           f"replicas that answered: {answered}, want all of 0..{n_dev - 1}")
    for r in results:
        _check(np.isfinite(r["boxes"]).all() and np.isfinite(r["scores"]).all()
               and r["boxes"].shape == (len(r["scores"]), 4),
               "a response is not finite (n, 4) boxes with n scores")
    _check(not req["built_after_first_call"],
           f"serving built programs on the request path: "
           f"{req['built_after_first_call']}")
    phases["serve"] = {
        "wall_s": round(warm_wall + serve_wall, 1),
        "warmup_wall_s": round(warm_wall, 1),
        "replicas": n_dev,
        "replica_devices": [str(d) for d in homes],
        "requests": n_req,
        "request_sizes": sizes,
        "levels": sorted(set(levels)),
        "answered_by": answered,
        **warm,  # every program is built in the warm-up...
        "built_after_first_call": req["built_after_first_call"],  # ...none after
        **_memory(),
    }
    return phases


def result_line(dev: dict) -> str:
    """The last line of stdout: exactly ``ok`` and ``device``, the device
    as jax reports it (``dev`` is ``utils.runtime.device_record()``).
    Whoever runs the smoke reads this line and nothing else, so it takes
    no other key — the full account is the line before it."""
    return json.dumps({
        "ok": True,
        "device": {
            "platform": dev["platform"],
            "kind": dev["device_kind"],
            "count": dev["n_devices"],
        },
    })


def main() -> int:
    from mx_rcnn_tpu.utils.runtime import device_record, runtime_versions

    versions, dev = runtime_versions(), device_record()
    print(f"jax {versions['jax']}  jaxlib {versions['jaxlib']}  "
          f"libtpu {versions['libtpu']}")
    print(f"platform={dev['platform']}  device_kind={dev['device_kind']}  "
          f"n_devices={dev['n_devices']}", flush=True)
    if dev["platform"] != "tpu":
        print("chip_smoke: jax's first device is not a TPU — refusing to "
              "run (this script has no CPU version of itself)",
              file=sys.stderr)
        return 1

    from mx_rcnn_tpu.utils.compile_cache import configure_cache

    print(f"compile cache: {configure_cache()}", flush=True)
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    t0 = time.perf_counter()
    try:
        phases = run_phases("r50_fpn_coco", workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    for name, p in phases.items():
        peaks = [b for b in p["peak_bytes_in_use"] if b is not None]
        _check(bool(peaks), "the backend reports no peak_bytes_in_use")
        reserved = [b or 0 for b in p["peak_bytes_reserved"]]
        print(
            f"{name}: wall {p['wall_s']} s, of which building programs "
            f"{p['compile_or_load_s']} s (set-up, not speed: "
            f"{p['programs_built']} programs, {p['cache_misses']} compiled, "
            f"{p['cache_hits']} from the cache); process peak device memory "
            f"{max(peaks) / 2**30:.2f} GiB in use + "
            f"{max(reserved) / 2**30:.2f} GiB reserved"
        )
    print(f"total wall {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({**dev, **versions, "phases": phases}))
    print(result_line(dev), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
