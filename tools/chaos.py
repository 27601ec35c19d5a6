"""CPU-only chaos harness: fault-inject real train/serve subprocesses, prove
recovery.  (It runs several processes at once; a chip belongs to one.)

The robustness claims in docs/robustness.md are cheap to assert and easy
to regress silently — so this harness drives the REAL CLI (`train_cli`)
as a subprocess on hermetic CPU (tiny_synthetic preset) and injects the
faults the runtime is supposed to survive:

  baseline  uninterrupted run; its final checkpoint is the bitwise oracle
            for every recovery scenario below.
  sigkill   SIGKILL (no grace, mid-flight) once a mid-run checkpoint
            lands; resume with --resume; final params must be
            BIT-IDENTICAL to baseline's.
  sigterm   SIGTERM mid-run; the child must drain the in-flight step,
            write the emergency checkpoint and exit RESUMABLE_EXIT_CODE;
            resume; bit-identical final params.
  nan       arm the loader's NaN hook (MX_RCNN_CHAOS_NAN_STEPS) for one
            batch; the guardian must roll back, skip the window and
            finish with every logged metric finite.
  truncate  SIGKILL mid-run, then truncate the newest checkpoint's files
            (simulating a kill inside the write); the resumed child must
            fall back to the previous step and STILL converge to
            baseline's exact params.

Data-path scenarios (data/service.py, data/cache.py) — every train child
above already runs the production input path (process decode workers +
checksummed tensor cache via ``--set data.num_workers/cache_dir``), so
baseline vs sigkill/sigterm doubles as the cache-hit-vs-miss bitwise
proof; these four inject data-specific faults on top:

  data_worker_kill   a decode worker SIGKILLs itself mid-epoch (armed via
                     MX_RCNN_CHAOS_DATA_SUICIDE); its in-flight batches
                     are reassigned deterministically and the final
                     params are BIT-IDENTICAL to baseline's.
  data_worker_wedge  a worker wedges (no heartbeat); the watchdog reaps
                     + respawns it, the run completes bit-identical, and
                     the per-interval data_stall_ms stays bounded (the
                     wedge never leaks into the wait).
  cache_corrupt      flip bytes inside a cached tensor blob; the next run
                     detects the bad checksum, quarantines + rebuilds the
                     blob, completes, and stays bit-identical — corrupt
                     bytes are never served.
  data_service_dead  every worker dies until the respawn budget is
                     exhausted (suicide "always"); the service degrades
                     to in-process synchronous assembly and the run
                     STILL completes bit-identical.

Inference scenarios (docs/serving.md) — same real-subprocess discipline:

  eval_sigkill  SIGKILL a --resumable eval once shard checkpoints are on
                disk; re-run with --resume; the final detections JSON
                must be BYTE-IDENTICAL to an uninterrupted eval's.
  eval_corrupt  poison images via MX_RCNN_CHAOS_BAD_IMAGES; eval must
                finish cleanly, quarantine the ids, and still dump every
                scheduled image.
  overload      flood a real engine past its bounded queue; at least one
                request must be shed (typed Overloaded) and every
                admitted request must complete — no deadlock.
  hang          serve through a runner whose device call never returns;
                the watchdog must declare the engine dead and fail the
                waiter with a typed error instead of hanging the client.

Fleet scenarios (serve/fleet.py) — real fleets on 4 fake CPU devices
(``--xla_force_host_platform_device_count``, one replica per device):

  replica_kill    kill 1 of 4 replicas mid-load: every ACCEPTED request
                  still completes (failover retry), the replica is
                  quarantined, rebuilt and reinstated.
  replica_wedge   one replica's device calls hang: hedged retries keep
                  latency bounded, the watchdog + supervisor quarantine
                  the wedge, the rebuild reinstates it.
  swap_under_load zero-downtime weight swap mid-traffic: every response
                  bitwise-matches the old-weights or new-weights oracle
                  for its generation — no request ever sees a
                  half-swapped tree.
  fleet_drain     SIGTERM during load: the fleet stops admitting,
                  every accepted request completes, and the process
                  exits RESUMABLE_EXIT_CODE (75) — the trainer's
                  preemption contract, applied to serving.
  fleet_scale     autoscaler closed loop (ctrl/autoscale.py): a queue
                  spike forces a scale-up onto a spare device, idleness
                  dwells into a scale-down drain, zero accepted
                  requests lost — and the full resize story replays
                  from the obs journal.

Cross-host fabric scenarios (serve/rpc.py, serve/gossip.py,
serve/gateway.py) — REAL multi-process fleets: each host is a
tools/serve_host.py subprocess (its own interpreter, devices and RPC
port); the chaos child drives them through a real GatewayRouter:

  host_kill        SIGKILL one of two host processes mid-load through
                   the gateway: zero accepted-request loss (cross-host
                   retry), gossip flags the host dead, the gateway
                   quarantines it and rebalances onto the survivor —
                   which then drains on SIGTERM and exits 75.
  host_partition   SIGSTOP a host (alive but silent — a network
                   partition, not a crash): gossip walks it through
                   suspect -> dead, the gateway fences it, traffic
                   keeps completing on the peer; SIGCONT heals the
                   partition and the probe loop reinstates the host.
  cross_host_swap  pod-wide generation-tagged weight roll under load:
                   every response from EITHER host bitwise-matches the
                   oracle for the generation it reports — proving hosts
                   serve identical weights per generation and no
                   response ever mixes generations.

Bit-identity holds because recovery re-runs the same compiled program
over the same data schedule from the same restored state — it is the
strongest possible "nothing was lost, nothing was double-applied" check
and it needs no tolerance tuning.

Usage:
  python tools/chaos.py [--scenario all|baseline|sigkill|sigterm|nan|truncate
                                    |data_worker_kill|data_worker_wedge
                                    |cache_corrupt|data_service_dead
                                    |eval_sigkill|eval_corrupt|overload|hang
                                    |replica_kill|replica_wedge
                                    |swap_under_load|fleet_drain|fleet_scale
                                    |host_kill|host_partition
                                    |cross_host_swap]
                        [--steps 12] [--workdir DIR] [--keep] [--timeout 900]
                        [--scenario-timeout SECONDS] [--lockcheck auto|on|off]

``--scenario`` also takes a comma-separated list (e.g.
``--scenario data_worker_kill,cache_corrupt``) — scenarios share the
workdir, so baseline runs once and is reused.

Every scenario runs under a per-scenario wall-clock budget
(``--scenario-timeout``, default 1.5x ``--timeout``); on expiry the
orphan reaper SIGKILLs every live child so one wedged scenario cannot
hang the harness past its budget.

The fleet/fabric scenarios additionally run their children under the
runtime lock-order sanitizer (``--lockcheck auto``, the default, sets
``MX_RCNN_LOCKCHECK=1`` — see mx_rcnn_tpu/analysis/lockcheck.py): a
lock-order inversion or a blocking call under a held lock raises in the
child AND lands in the obs journal, and either fails the scenario.

Prints one JSON summary line on stdout; exits non-zero if any scenario
fails.  (`--child*` / `--compare` are internal subprocess entry modes.)
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "tiny_synthetic"
CKPT_EVERY = 3
LOG_EVERY = 2
EVAL_LIMIT = 16  # images per chaos eval (shard_size=1 -> one shard each)


def _hermetic_cpu() -> None:
    """CPU-only jax in THIS interpreter.  The harness starts several
    processes at once, and a chip belongs to one process at a time."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, REPO_ROOT)
    import jax

    jax.config.update("jax_platforms", "cpu")
    from mx_rcnn_tpu.utils.compile_cache import configure_cache

    configure_cache()


def _fleet_cpu(n_devices: int = 4) -> None:
    """Hermetic CPU with ``n_devices`` fake devices (one per replica).
    Must run before the first jax import — the XLA flag is read at
    backend init."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    _hermetic_cpu()


def _init_variables(cfg, seed: int):
    import jax
    from mx_rcnn_tpu.detection import TwoStageDetector, init_detector

    return init_detector(
        TwoStageDetector(cfg=cfg.model), jax.random.PRNGKey(seed),
        cfg.data.image_size,
    )


# -- internal subprocess modes ------------------------------------------------


def child_main(argv: list[str]) -> int:
    """Run the real train CLI hermetically (the orchestrator's workload)."""
    _hermetic_cpu()
    from mx_rcnn_tpu.cli import train_cli

    return train_cli.cli(argv)


def child_eval_main(argv: list[str]) -> int:
    """Run the real eval CLI hermetically (resumable-eval scenarios)."""
    _hermetic_cpu()
    from mx_rcnn_tpu.cli import eval_cli

    return eval_cli.cli(argv)


def child_overload_main() -> int:
    """Flood a REAL engine (tiny model, random params) past its queue.

    Prints one JSON line: submitted/shed/served counts and engine stats.
    Exits 0 only if >=1 request was shed AND every admitted request
    completed — returning at all is the no-deadlock proof."""
    _hermetic_cpu()
    import numpy as np

    import jax
    from mx_rcnn_tpu.config import get_config
    from mx_rcnn_tpu.detection import TwoStageDetector, init_detector
    from mx_rcnn_tpu.serve import Overloaded, build_engine

    cfg = get_config(CONFIG)
    variables = init_detector(
        TwoStageDetector(cfg=cfg.model), jax.random.PRNGKey(0),
        cfg.data.image_size,
    )
    img = np.random.default_rng(0).uniform(
        0, 255, (100, 100, 3)
    ).astype(np.float32)
    submitted = 12
    shed = 0
    reqs = []
    with build_engine(cfg, variables, max_queue=2) as engine:
        # The burst is orders of magnitude faster than one device call, so
        # the 2-deep queue must overflow deterministically.
        for _ in range(submitted):
            try:
                reqs.append(engine.submit(img))
            except Overloaded:
                shed += 1
        served = sum(1 for r in reqs if r.result(timeout=300))
        stats = engine.stats()
    print(json.dumps({
        "submitted": submitted, "shed": shed, "served": served,
        "stats_shed": stats["shed"], "state": stats["state"],
    }))
    assert shed >= 1, "queue never overflowed — admission control untested"
    assert served == submitted - shed, "admitted request lost (deadlock?)"
    assert stats["shed"] == shed
    return 0


def child_hang_main() -> int:
    """Serve through a runner whose device call never returns; the
    watchdog must fail the waiter and declare the engine dead."""
    _hermetic_cpu()
    import threading

    import numpy as np
    from mx_rcnn_tpu.serve import EngineUnavailable, InferenceEngine

    class HangingRunner:
        """Runner-protocol stub wedged like a hung device stream."""

        buckets = [(64, 64)]
        batch_size = 1

        def levels(self):
            return ("full", "reduced")

        def pick_bucket(self, h, w):
            return (64, 64)

        def smaller_bucket(self, bucket):
            return None

        def warmup(self):
            return 1

        def run(self, mode, bucket, images):
            threading.Event().wait()  # never returns

    engine = InferenceEngine(
        HangingRunner(), hang_timeout=1.0, watchdog_poll=0.1
    ).start()
    req = engine.submit(np.zeros((32, 32, 3), np.float32))
    try:
        req.result(timeout=60)
        print(json.dumps({"ok": False, "why": "hung request returned"}))
        return 1
    except EngineUnavailable:
        pass
    stats = engine.stats()
    print(json.dumps({"hung": stats["hung"], "state": stats["state"]}))
    assert stats["hung"] == 1, stats
    assert stats["state"] == "dead", stats
    # No engine.stop(): the worker daemon thread is wedged by design and
    # must not block process exit.
    return 0


def child_replica_kill_main() -> int:
    """Kill 1 of 4 replicas mid-load: zero failed ACCEPTED requests.

    The killed replica's queued/in-flight work fails over via the fleet's
    retry; the supervisor quarantines, rebuilds and reinstates it."""
    _fleet_cpu(4)
    import numpy as np
    from mx_rcnn_tpu import obs
    from mx_rcnn_tpu.config import get_config
    from mx_rcnn_tpu.serve import build_fleet

    obs_dir = os.environ.get("MX_RCNN_OBS_DIR")
    if obs_dir:
        # Durable observability plane: the parent scenario asserts the
        # journal + flight-recorder artifacts reconstruct the incident.
        obs.configure(obs_dir)
        obs.install_crash_handler()

    cfg = get_config(CONFIG)
    variables = _init_variables(cfg, seed=0)
    img = np.random.default_rng(0).uniform(
        0, 255, (100, 100, 3)
    ).astype(np.float32)
    fleet = build_fleet(
        cfg, variables, n_replicas=4,
        engine_kwargs={"hang_timeout": 300.0},
        supervisor_poll=0.1,
    )
    with fleet:
        accepted = [fleet.submit(img, timeout=300) for _ in range(6)]
        wait_for(lambda: any(r.done() for r in accepted), 300)
        fleet.kill_replica(2, "chaos: replica kill mid-load")
        accepted += [fleet.submit(img, timeout=300) for _ in range(8)]
        results = [r.result(timeout=300) for r in accepted]
        reinstated = wait_for(
            lambda: fleet.stats()["reinstatements"] >= 1, 300
        )
        s = fleet.stats()
    print(json.dumps({
        "accepted": len(accepted), "completed": len(results),
        "failed": s["failed"], "retries": s["retries"],
        "quarantines": s["quarantines"],
        "reinstatements": s["reinstatements"],
        "replicas_used": sorted({r["replica_id"] for r in results}),
    }))
    assert len(results) == len(accepted), "an accepted request was lost"
    assert s["failed"] == 0, f"accepted requests failed: {s}"
    assert s["quarantines"] >= 1, s
    assert reinstated, "killed replica was never reinstated"
    if obs_dir:
        obs.close()
    return 0


def child_replica_wedge_main() -> int:
    """One replica's device calls hang forever: hedging keeps latency
    bounded, the watchdog + supervisor quarantine the wedge, and the
    background rebuild reinstates the replica."""
    _fleet_cpu(4)
    import numpy as np

    import jax
    from mx_rcnn_tpu.config import get_config
    from mx_rcnn_tpu.serve import FleetRouter, InferenceEngine
    from mx_rcnn_tpu.serve.engine import DetectorRunner

    cfg = get_config(CONFIG)
    variables = _init_variables(cfg, seed=0)
    release = threading.Event()
    builds = {"n": 0}

    class WedgedRunner:
        """Delegates to a real runner, but every device call hangs until
        released — a wedged device stream."""

        def __init__(self, inner) -> None:
            self._inner = inner

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def run(self, mode, bucket, images):
            release.wait()
            return self._inner.run(mode, bucket, images)

    devices = jax.devices()

    def factory(rid: int) -> InferenceEngine:
        runner = DetectorRunner(
            cfg, variables, device=devices[rid % len(devices)]
        )
        builds["n"] += 1
        if rid == 0 and builds["n"] == 1:
            runner = WedgedRunner(runner)  # only the FIRST build wedges
        return InferenceEngine(
            runner, replica_id=rid, hang_timeout=3.0, watchdog_poll=0.1
        )

    fleet = FleetRouter(
        factory, 2, hedge_after=1.0, supervisor_poll=0.1
    )
    lat = []
    with fleet:
        t0 = time.monotonic()
        reqs = [fleet.submit(img, timeout=120) for img in [
            np.random.default_rng(i).uniform(
                0, 255, (100, 100, 3)
            ).astype(np.float32) for i in range(8)
        ]]
        for r in reqs:
            r.result(timeout=240)
            lat.append(time.monotonic() - t0)
        quarantined = wait_for(
            lambda: fleet.stats()["quarantines"] >= 1, 120
        )
        release.set()  # un-wedge so the stuck worker thread can exit
        reinstated = wait_for(
            lambda: fleet.stats()["reinstatements"] >= 1, 300
        )
        s = fleet.stats()
    lat.sort()
    p99 = lat[min(len(lat) - 1, int(round(0.99 * (len(lat) - 1))))]
    print(json.dumps({
        "completed": len(lat), "failed": s["failed"],
        "hedges": s["hedges"], "hedge_wins": s["hedge_wins"],
        "quarantines": s["quarantines"],
        "reinstatements": s["reinstatements"],
        "p99_s": round(p99, 3),
    }))
    assert s["failed"] == 0, s
    assert s["hedges"] >= 1, f"wedge never triggered a hedge: {s}"
    assert quarantined, "wedged replica was never quarantined"
    assert reinstated, "wedged replica was never reinstated"
    assert p99 < 60.0, (
        f"p99 {p99:.1f}s unbounded — hedging failed to contain the wedge"
    )
    return 0


def child_tenant_starvation_main() -> int:
    """Noisy-neighbor isolation on a real fleet: a flooder saturating its
    quota must not move the victims' completion rate or latency.

    Phase A runs the victims alone (flooder-free baseline p99); Phase B
    replays the identical victim load while the flooder fires point-blank
    bursts between every victim submit.  The flooder's excess bounces off
    its token bucket as QuotaExceeded at the fleet front door; the
    victims complete 100% with zero shed/quota and a p99 within a gated
    factor of the baseline."""
    _fleet_cpu(2)
    import numpy as np
    from mx_rcnn_tpu import obs
    from mx_rcnn_tpu.config import get_config
    from mx_rcnn_tpu.serve import (
        Overloaded, QuotaExceeded, TenancyPolicy, build_fleet,
    )
    from mx_rcnn_tpu.serve.tenancy import parse_table

    obs_dir = os.environ.get("MX_RCNN_OBS_DIR")
    if obs_dir:
        # Journaled: the parent scenario re-derives the per-tenant story
        # (quota rejections, outcome counts) from the obs artifacts.
        obs.configure(obs_dir)

    cfg = get_config(CONFIG)
    variables = _init_variables(cfg, seed=0)
    rng = np.random.default_rng(0)

    def fresh_img():
        # Distinct per request so the result cache can't serve hits and
        # flatten the latency comparison between phases.
        return rng.uniform(0, 255, (100, 100, 3)).astype(np.float32)

    # Victims are unlimited (rate<=0); the flooder is rate-capped so its
    # bursts die at the quota gate instead of filling the queues.
    policy = TenancyPolicy(parse_table(
        "victim:weight=4;bursty:weight=2;flood:rate=2,burst=2,priority=2"
    ))
    N_VICTIM, N_BURSTY, FLOOD_BURST = 10, 5, 8
    VICTIMS = ("victim", "bursty")

    fleet = build_fleet(cfg, variables, n_replicas=2, tenancy=policy,
                        engine_kwargs={"hang_timeout": 300.0})

    def run_mix(flood: bool) -> dict:
        per = {t: {"submitted": 0, "completed": 0, "shed": 0, "quota": 0,
                   "lat": []} for t in ("victim", "bursty", "flood")}
        pending = []

        def sub(tenant):
            per[tenant]["submitted"] += 1
            try:
                req = fleet.submit(fresh_img(), timeout=300, tenant=tenant)
            except QuotaExceeded:
                per[tenant]["quota"] += 1
                return
            except Overloaded:
                per[tenant]["shed"] += 1
                return
            pending.append((tenant, time.monotonic(), req))

        for i in range(N_VICTIM):
            sub("victim")
            if i % 2 == 0 and per["bursty"]["submitted"] < N_BURSTY:
                sub("bursty")
            if flood:
                for _ in range(FLOOD_BURST):
                    sub("flood")
            time.sleep(0.05)
        for tenant, t0, req in pending:
            req.result(timeout=300)
            per[tenant]["completed"] += 1
            per[tenant]["lat"].append(time.monotonic() - t0)
        for t, d in per.items():
            lat = sorted(d.pop("lat"))
            d["p99_s"] = round(
                lat[min(len(lat) - 1, int(round(0.99 * (len(lat) - 1))))], 4
            ) if lat else None
        return per

    with fleet:
        base = run_mix(flood=False)
        mix = run_mix(flood=True)
        s = fleet.stats()

    baseline_p99 = max(b["p99_s"] for t, b in base.items() if t in VICTIMS)
    mix_p99 = max(m["p99_s"] for t, m in mix.items() if t in VICTIMS)
    print(json.dumps({
        "baseline_p99_s": baseline_p99, "mix_p99_s": mix_p99,
        "victims": {t: mix[t] for t in VICTIMS},
        "flooder": mix["flood"],
        "fleet": {"shed": s["shed"], "quota": s["quota"],
                  "failed": s["failed"]},
    }))
    for t in VICTIMS:
        for phase in (base, mix):
            v = phase[t]
            assert v["completed"] == v["submitted"], (t, phase)
            assert v["quota"] == 0 and v["shed"] == 0, (t, phase)
    assert mix["flood"]["quota"] >= FLOOD_BURST, (
        f"flooder was never quota-capped: {mix['flood']}"
    )
    assert s["shed"] == 0 and s["failed"] == 0, s
    # 0.25s floor: at CPU-scale latencies, scheduler noise would flap a
    # pure ratio gate long before real starvation shows.
    assert mix_p99 <= 3.0 * max(baseline_p99, 0.25), (
        f"victims starved: mix p99 {mix_p99}s vs baseline {baseline_p99}s"
    )
    if obs_dir:
        obs.close()
    return 0


def child_swap_main() -> int:
    """Zero-downtime weight swap under load: every response must
    bitwise-match the old-weights or new-weights oracle for the
    generation it reports — a half-swapped tree would match neither."""
    _fleet_cpu(4)
    import numpy as np
    from mx_rcnn_tpu import obs
    from mx_rcnn_tpu.config import get_config
    from mx_rcnn_tpu.serve import build_fleet

    obs_dir = os.environ.get("MX_RCNN_OBS_DIR")
    if obs_dir:
        # Journaled so the parent's lock-sanitizer sweep sees swap-path
        # violations even from threads that swallow exceptions.
        obs.configure(obs_dir)

    cfg = get_config(CONFIG)
    v0 = _init_variables(cfg, seed=0)
    v1 = _init_variables(cfg, seed=1)
    probe = np.random.default_rng(7).uniform(
        0, 255, (96, 128, 3)
    ).astype(np.float32)
    KEYS = ("boxes", "scores", "classes")

    def sig(res):
        return {k: np.asarray(res[k]) for k in KEYS}

    def matches(res, oracle) -> bool:
        return all(
            np.array_equal(np.asarray(res[k]), oracle[k]) for k in KEYS
        )

    fleet = build_fleet(
        cfg, v0, n_replicas=2,
        engine_kwargs={"hang_timeout": 300.0},
        supervisor_poll=0.1,
    )
    results: list[dict] = []
    errors: list[str] = []
    stop = threading.Event()

    def pump() -> None:
        while not stop.is_set():
            try:
                results.append(fleet.infer(probe, timeout=300))
            except Exception as e:  # noqa: BLE001 - report, don't die
                errors.append(f"{type(e).__name__}: {e}")
                return

    with fleet:
        oracle = {0: sig(fleet.infer(probe, timeout=300))}
        pumps = [
            threading.Thread(target=pump, daemon=True) for _ in range(2)
        ]
        for t in pumps:
            t.start()
        wait_for(lambda: len(results) >= 2, 300)
        gen = fleet.swap_weights(v1)  # mid-load, rolled replica by replica
        wait_for(
            lambda: any(
                r.get("generation") == gen for r in list(results)
            ),
            300,
        )
        stop.set()
        for t in pumps:
            t.join(300)
        oracle[gen] = sig(fleet.infer(probe, timeout=300))
    gens = sorted({r["generation"] for r in results})
    mismatched = [
        i for i, r in enumerate(results)
        if r["generation"] not in oracle
        or not matches(r, oracle[r["generation"]])
    ]
    print(json.dumps({
        "responses": len(results), "generations_seen": gens,
        "mismatched": mismatched, "errors": errors,
        "swap_generation": gen,
    }))
    assert not errors, f"requests failed during the swap: {errors}"
    assert gens == [0, gen], (
        f"expected traffic on both sides of the swap, saw {gens}"
    )
    assert not mismatched, (
        f"{len(mismatched)} responses matched NEITHER weight version — "
        "a request saw a half-swapped tree"
    )
    return 0


def child_fleet_drain_main() -> int:
    """SIGTERM during load: stop admitting, complete every accepted
    request, exit RESUMABLE_EXIT_CODE — the trainer's preemption
    contract (train/preemption.py), applied to the serving fleet."""
    _fleet_cpu(4)
    import numpy as np
    from mx_rcnn_tpu.config import get_config
    from mx_rcnn_tpu.serve import Overloaded, build_fleet
    from mx_rcnn_tpu.train.preemption import (
        RESUMABLE_EXIT_CODE,
        PreemptionGuard,
    )

    cfg = get_config(CONFIG)
    variables = _init_variables(cfg, seed=0)
    img = np.random.default_rng(0).uniform(
        0, 255, (100, 100, 3)
    ).astype(np.float32)
    fleet = build_fleet(
        cfg, variables, n_replicas=2,
        engine_kwargs={"hang_timeout": 300.0},
        supervisor_poll=0.1,
    )
    accepted = []
    with PreemptionGuard() as guard:
        fleet.start()
        print("FLEET_READY", flush=True)
        while not guard.triggered and len(accepted) < 500:
            try:
                accepted.append(fleet.submit(img, timeout=300))
            except Overloaded:
                time.sleep(0.2)
                continue
            time.sleep(0.05)
        clean = fleet.drain(timeout=240)
    failed = 0
    for r in accepted:
        try:
            r.result(timeout=1)
        except Exception:  # noqa: BLE001 - counted, asserted below
            failed += 1
    print(json.dumps({
        "accepted": len(accepted), "failed": failed,
        "drained_clean": bool(clean),
        "signal": guard.signum,
    }), flush=True)
    assert guard.triggered, "drain ran without a signal — test is vacuous"
    assert clean, "drain left pending requests behind"
    assert failed == 0, f"{failed} accepted requests failed during drain"
    return RESUMABLE_EXIT_CODE


def child_fleet_scale_main() -> int:
    """Autoscaler closed loop on a real fleet: a queue spike forces a
    scale-up (background build joins the rotation), idleness then walks
    the dwell counter to a scale-down (drain + slot release) — with
    zero accepted requests lost across both resizes."""
    _fleet_cpu(4)
    import numpy as np
    from mx_rcnn_tpu import obs
    from mx_rcnn_tpu.config import get_config
    from mx_rcnn_tpu.ctrl import Autoscaler, ScalePolicy
    from mx_rcnn_tpu.serve import build_fleet

    obs_dir = os.environ.get("MX_RCNN_OBS_DIR")
    if obs_dir:
        obs.configure(obs_dir)

    cfg = get_config(CONFIG)
    variables = _init_variables(cfg, seed=0)
    img = np.random.default_rng(0).uniform(
        0, 255, (100, 100, 3)
    ).astype(np.float32)
    fleet = build_fleet(
        cfg, variables, n_replicas=2,
        engine_kwargs={"hang_timeout": 300.0, "max_queue": 64},
        supervisor_poll=0.1,
    )
    # Tight thresholds so a 12-request burst is unambiguous pressure
    # and an idle fleet is unambiguous comfort; no cooldowns, so the
    # test drives the dwell logic alone.
    scaler = Autoscaler(fleet, ScalePolicy(
        min_replicas=2, max_replicas=3,
        load_high=1.0, load_low=0.5,
        down_dwell=2, up_cooldown_s=0.0, down_cooldown_s=0.0,
    ))
    with fleet:
        accepted = [fleet.submit(img, timeout=300) for _ in range(12)]
        rec_up = scaler.step()
        assert rec_up["action"] == "up", rec_up
        new_rid = rec_up["replica"]
        # The new replica builds in the background (warmup compiles)
        # while the burst keeps serving; wait until it joins rotation.
        wait_for(
            lambda: any(
                rep["rid"] == new_rid
                and rep["state"] in ("ready", "degraded")
                for rep in fleet.stats()["replica"]
            ),
            300,
        )
        # Traffic lands on the grown fleet too.
        accepted += [fleet.submit(img, timeout=300) for _ in range(4)]
        results = [r.result(timeout=300) for r in accepted]
        # Idle now: the dwell counter must walk to a scale-down.
        rec_down = None
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            rec = scaler.step()
            if rec["action"] == "down":
                rec_down = rec
                break
            time.sleep(0.2)
        s = fleet.stats()
    assert rec_down is not None, "autoscaler never scaled down"
    assert rec_down["replica"] == new_rid, rec_down
    assert rec_down.get("clean", False), f"retire drain unclean: {rec_down}"
    print(json.dumps({
        "accepted": len(accepted), "completed": len(results),
        "failed": s["failed"], "added": s["added"],
        "retired": s["retired"], "replicas_final": s["replicas"],
        "scaled_up_rid": new_rid,
        "up_reason": rec_up["reason"], "down_reason": rec_down["reason"],
        "decisions": len(scaler.resize_timeline()),
    }))
    assert len(results) == len(accepted), "an accepted request was lost"
    assert s["failed"] == 0, f"accepted requests failed: {s}"
    assert s["added"] >= 1 and s["retired"] >= 1, s
    assert s["replicas"] == 2, s
    if obs_dir:
        obs.close()
    return 0


# -- cross-host fabric children ----------------------------------------------


SERVE_HOST = os.path.join(REPO_ROOT, "tools", "serve_host.py")


class _FabricHost:
    """One tools/serve_host.py subprocess — a REAL host: its own
    interpreter, fake devices, fleet, RPC port and gossip node.
    Readiness (and the ephemeral port) is parsed from its log."""

    def __init__(self, workdir: str, host_id: str, *, replicas: int = 2,
                 seed: int = 0, peers: str = "") -> None:
        os.makedirs(workdir, exist_ok=True)
        self.host_id = host_id
        self.log_path = os.path.join(workdir, f"{host_id}.log")
        self._log = open(self.log_path, "a")
        argv = [
            sys.executable, SERVE_HOST, "--host-id", host_id,
            "--config", CONFIG, "--replicas", str(replicas),
            "--seed", str(seed), "--port", "0",
        ]
        if peers:
            argv += ["--peers", peers]
        self.proc = subprocess.Popen(
            argv, stdout=self._log, stderr=subprocess.STDOUT, cwd=REPO_ROOT,
        )
        self.port: Optional[int] = None
        self.addr: Optional[str] = None

    def wait_ready(self, timeout: float) -> str:
        def ready_line():
            if self.proc.poll() is not None:
                raise AssertionError(
                    f"{self.host_id} died (rc={self.proc.returncode}) "
                    f"before HOST_READY (log: {self.log_path})\n"
                    f"{self.log_tail()}"
                )
            try:
                with open(self.log_path) as f:
                    for ln in f:
                        if ln.startswith("HOST_READY"):
                            return ln.strip()
            except OSError:
                pass
            return None

        line = wait_for(ready_line, timeout, poll=0.5)
        assert line, (
            f"{self.host_id} not ready within {timeout}s "
            f"(log: {self.log_path})\n{self.log_tail()}"
        )
        for tok in line.split():
            if tok.startswith("port="):
                self.port = int(tok.partition("=")[2])
        assert self.port, f"no port on READY line: {line!r}"
        self.addr = f"127.0.0.1:{self.port}"
        return self.addr

    def log_tail(self, n: int = 30) -> str:
        try:
            with open(self.log_path) as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""

    def kill(self) -> None:
        try:
            if self.proc.poll() is None:
                self.proc.kill()
                self.proc.wait(10)
        except Exception:  # noqa: BLE001 - teardown best effort
            pass
        self._log.close()


def _fabric_workdir() -> str:
    return os.environ.get("MX_RCNN_FABRIC_WD") or tempfile.mkdtemp(
        prefix="mx_rcnn_fabric_"
    )


def _collect_results(accepted: list) -> tuple[list, list]:
    results, errors = [], []
    for r in accepted:
        try:
            results.append(r.result(timeout=300))
        except Exception as e:  # noqa: BLE001 - counted, asserted by caller
            errors.append(f"{type(e).__name__}: {e}")
    return results, errors


def child_host_kill_main() -> int:
    """SIGKILL one of two REAL host processes mid-load through the
    gateway: zero accepted-request loss, gossip flags the host dead,
    the gateway quarantines it and rebalances onto the survivor — and
    the survivor then honors the preemption contract (SIGTERM -> drain
    -> exit 75)."""
    _fleet_cpu(2)
    import numpy as np
    from mx_rcnn_tpu.serve import GatewayRouter, GossipNode
    from mx_rcnn_tpu.serve.gossip import DEAD as GOSSIP_DEAD

    wd = _fabric_workdir()
    RESUMABLE_EXIT_CODE = 75  # pinned, mirrors train/preemption.py
    hosts: list[_FabricHost] = []
    try:
        a = _FabricHost(wd, "hostA", replicas=2, seed=0)
        hosts.append(a)
        a.wait_ready(600)
        b = _FabricHost(wd, "hostB", replicas=2, seed=0,
                        peers=f"hostA={a.addr}")
        hosts.append(b)
        b.wait_ready(600)

        # Observer gossip node: proves the mesh (not just the gateway's
        # own request failures) detects the death.
        observer = GossipNode(
            "chaos-observer", "", lambda: {"draining": True},
            peers={"hostA": a.addr, "hostB": b.addr},
            period_s=0.25, suspect_after_s=1.0, dead_after_s=3.0,
        ).start()
        gw = GatewayRouter(
            [a.addr, b.addr], probe_interval_s=0.25, gossip=observer,
        ).start()
        assert wait_for(lambda: gw.stats()["replicas"] == 2, 120), (
            f"gateway never saw both hosts routable: {gw.stats()}"
        )
        img = np.random.default_rng(0).uniform(
            0, 255, (100, 100, 3)
        ).astype(np.float32)
        accepted = [gw.submit(img, timeout=120) for _ in range(6)]
        wait_for(lambda: any(r.done() for r in accepted), 300)
        a.proc.kill()  # a whole failure domain vanishes mid-load
        accepted += [gw.submit(img, timeout=120) for _ in range(8)]
        results, errors = _collect_results(accepted)
        gossip_dead = wait_for(
            lambda: (
                observer.peers().get("hostA") is not None
                and observer.peers()["hostA"].status == GOSSIP_DEAD
            ),
            60,
        )
        quarantined = wait_for(lambda: gw.stats()["quarantines"] >= 1, 60)
        post_kill_hosts = sorted(
            {r["host_id"] for r in results[-8:]}
        ) if len(results) >= 8 else []
        s = gw.stats()
        # Gateway metrics must scrape clean after the failover: the
        # request counter and the gossip peer gauge both rendered, with
        # traffic actually recorded (the CI fabric_smoke gate).
        from mx_rcnn_tpu import obs
        metrics_text = obs.render_metrics()
        metrics_clean = (
            "gateway_requests_total" in metrics_text
            and "gossip_peers" in metrics_text
            and 'outcome="ok"' in metrics_text
        )
        # Survivor honors the serving preemption contract.
        b.proc.send_signal(signal.SIGTERM)
        rc_b = b.proc.wait(240)
        gw.stop()
        observer.close()
    finally:
        for h in hosts:
            h.kill()
    print(json.dumps({
        "accepted": len(accepted), "completed": len(results),
        "errors": errors, "failed": s["failed"],
        "retries": s["retries"], "quarantines": s["quarantines"],
        "gossip_dead": bool(gossip_dead),
        "post_kill_hosts": post_kill_hosts,
        "survivor_exit": rc_b,
        "metrics_clean": metrics_clean,
    }))
    assert not errors, f"accepted requests lost: {errors}"
    assert len(results) == len(accepted)
    assert s["failed"] == 0, s
    assert quarantined, "gateway never quarantined the killed host"
    assert gossip_dead, "gossip never flagged the killed host dead"
    assert post_kill_hosts == ["hostB"], (
        f"post-kill traffic not rebalanced onto the survivor: "
        f"{post_kill_hosts}"
    )
    assert rc_b == RESUMABLE_EXIT_CODE, (
        f"survivor drain exit {rc_b} != {RESUMABLE_EXIT_CODE}"
    )
    assert metrics_clean, "gateway metrics did not scrape clean"
    return 0


def child_host_partition_main() -> int:
    """SIGSTOP a host (alive but silent — a partition, not a crash):
    gossip ages it suspect -> dead, the gateway fences it, traffic
    completes on the peer; SIGCONT heals and the probe loop reinstates."""
    _fleet_cpu(2)
    import numpy as np
    from mx_rcnn_tpu.serve import GatewayRouter, GossipNode
    from mx_rcnn_tpu.serve.gossip import ALIVE as G_ALIVE
    from mx_rcnn_tpu.serve.gossip import DEAD as G_DEAD

    wd = _fabric_workdir()
    hosts: list[_FabricHost] = []
    try:
        a = _FabricHost(wd, "hostA", replicas=2, seed=0)
        hosts.append(a)
        a.wait_ready(600)
        b = _FabricHost(wd, "hostB", replicas=2, seed=0,
                        peers=f"hostA={a.addr}")
        hosts.append(b)
        b.wait_ready(600)

        observer = GossipNode(
            "chaos-observer", "", lambda: {"draining": True},
            peers={"hostA": a.addr, "hostB": b.addr},
            period_s=0.25, suspect_after_s=1.0, dead_after_s=3.0,
        ).start()
        gw = GatewayRouter(
            [a.addr, b.addr], probe_interval_s=0.25, gossip=observer,
        ).start()
        assert wait_for(lambda: gw.stats()["replicas"] == 2, 120), (
            f"gateway never saw both hosts routable: {gw.stats()}"
        )
        os.kill(a.proc.pid, signal.SIGSTOP)  # silent, not dead
        partition_detected = wait_for(
            lambda: (
                observer.peers().get("hostA") is not None
                and observer.peers()["hostA"].status == G_DEAD
            ),
            60,
        )
        fenced = wait_for(
            lambda: gw.stats()["hosts"]
            .get("hostA", {}).get("state") != "ready",
            60,
        )
        img = np.random.default_rng(0).uniform(
            0, 255, (100, 100, 3)
        ).astype(np.float32)
        accepted = [gw.submit(img, timeout=120) for _ in range(6)]
        results, errors = _collect_results(accepted)
        during = sorted({r["host_id"] for r in results})
        os.kill(a.proc.pid, signal.SIGCONT)  # partition heals
        healed = wait_for(
            lambda: (
                observer.peers().get("hostA") is not None
                and observer.peers()["hostA"].status == G_ALIVE
            ),
            120,
        )
        reinstated = wait_for(
            lambda: gw.stats()["hosts"]
            .get("hostA", {}).get("state") == "ready",
            120,
        )
        s = gw.stats()
        gw.stop()
        observer.close()
    finally:
        for h in hosts:
            try:
                os.kill(h.proc.pid, signal.SIGCONT)  # un-freeze first
            except OSError:
                pass
            h.kill()
    print(json.dumps({
        "accepted": len(accepted), "completed": len(results),
        "errors": errors, "failed": s["failed"],
        "partition_detected": bool(partition_detected),
        "fenced": bool(fenced), "hosts_during_partition": during,
        "healed": bool(healed), "reinstated": bool(reinstated),
        "quarantines": s["quarantines"],
        "reinstatements": s["reinstatements"],
        "routable_final": s["replicas"],
    }))
    assert partition_detected, "gossip never aged the stopped host to dead"
    assert fenced, "gateway kept routing to the partitioned host"
    assert not errors and len(results) == len(accepted), (
        f"requests lost during the partition: {errors}"
    )
    assert during == ["hostB"], (
        f"partitioned host served traffic while fenced: {during}"
    )
    assert healed, "gossip never saw the host come back alive"
    assert reinstated, "probe loop never reinstated the healed host"
    assert s["replicas"] == 2, s
    assert s["failed"] == 0, s
    return 0


def child_cross_host_swap_main() -> int:
    """Pod-wide generation-tagged weight roll across two REAL host
    processes under load: every response from either host must
    bitwise-match the oracle for the generation it reports."""
    _fleet_cpu(2)
    import numpy as np
    from mx_rcnn_tpu.config import get_config
    from mx_rcnn_tpu.serve import GatewayRouter

    cfg = get_config(CONFIG)
    v1 = _init_variables(cfg, seed=1)  # the roll target
    wd = _fabric_workdir()
    hosts: list[_FabricHost] = []
    KEYS = ("boxes", "scores", "classes")

    def sig(res):
        return {k: np.asarray(res[k]) for k in KEYS}

    def matches(res, oracle) -> bool:
        return all(
            np.array_equal(np.asarray(res[k]), oracle[k]) for k in KEYS
        )

    try:
        a = _FabricHost(wd, "hostA", replicas=2, seed=0)
        hosts.append(a)
        a.wait_ready(600)
        b = _FabricHost(wd, "hostB", replicas=2, seed=0,
                        peers=f"hostA={a.addr}")
        hosts.append(b)
        b.wait_ready(600)
        gw = GatewayRouter([a.addr, b.addr], probe_interval_s=0.25).start()
        assert wait_for(lambda: gw.stats()["replicas"] == 2, 120), (
            f"gateway never saw both hosts routable: {gw.stats()}"
        )
        probe = np.random.default_rng(7).uniform(
            0, 255, (96, 128, 3)
        ).astype(np.float32)
        # Generation-0 oracle — computed on whichever host the gateway
        # picks; every gen-0 response from EITHER host must match it
        # bitwise (hosts share seed, config and compiled program).
        oracle = {0: sig(gw.infer(probe, timeout=300))}
        results: list[dict] = []
        errors: list[str] = []
        stop = threading.Event()

        def pump() -> None:
            while not stop.is_set():
                try:
                    results.append(gw.infer(probe, timeout=300))
                except Exception as e:  # noqa: BLE001 - report, don't die
                    errors.append(f"{type(e).__name__}: {e}")
                    return

        pumps = [
            threading.Thread(target=pump, daemon=True) for _ in range(2)
        ]
        for t in pumps:
            t.start()
        wait_for(lambda: len(results) >= 2, 300)
        gen = gw.swap_weights(v1)  # hosts rolled ONE AT A TIME
        wait_for(
            lambda: any(
                r.get("generation") == gen for r in list(results)
            ),
            300,
        )
        stop.set()
        for t in pumps:
            t.join(300)
        oracle[gen] = sig(gw.infer(probe, timeout=300))
        s = gw.stats()
        gw.stop()
    finally:
        for h in hosts:
            h.kill()
    gens = sorted({r["generation"] for r in results})
    hosts_used = sorted({r["host_id"] for r in results})
    mismatched = [
        i for i, r in enumerate(results)
        if r["generation"] not in oracle
        or not matches(r, oracle[r["generation"]])
    ]
    print(json.dumps({
        "responses": len(results), "generations_seen": gens,
        "hosts_used": hosts_used, "mismatched": mismatched,
        "errors": errors, "swap_generation": gen,
        "host_generations": {
            h: d["generation"] for h, d in s["hosts"].items()
        },
    }))
    assert not errors, f"requests failed during the roll: {errors}"
    assert gens == [0, gen], (
        f"expected traffic on both sides of the roll, saw {gens}"
    )
    assert hosts_used == ["hostA", "hostB"], (
        f"oracle only exercised one host: {hosts_used}"
    )
    assert not mismatched, (
        f"{len(mismatched)} responses matched NEITHER generation oracle "
        "— a host served mixed or stale weights"
    )
    return 0


# -- continuous-deployment scenarios (ctrl/deploy.py) -------------------------


def _deploy_runner_cls():
    """Weight-sensitive runner-protocol fake for the deploy children
    (mirrors tools/soak.py::_SoakRunner — kept separate so the tool
    never imports the test suite).  Every detection carries a signature
    derived from the currently-loaded tree, so bitwise response parity
    across engines holds if and only if their weights are bitwise
    equal."""
    import numpy as np

    class _WeightRunner:
        def __init__(self, variables, delay: float = 0.002):
            self.buckets = [(64, 64)]
            self.batch_size = 1
            self.delay = delay
            self.generation = 0
            self.swapped: list = []
            self._warmed = set()
            self._sig = self._sig_of(variables)

        @staticmethod
        def _sig_of(tree) -> float:
            leaves: list = []

            def walk(x):
                if isinstance(x, dict):
                    for k in sorted(x):
                        walk(x[k])
                else:
                    leaves.append(np.asarray(x))

            walk(tree)
            return float(np.ravel(leaves[0])[0]) if leaves else 0.0

        def levels(self):
            return ("full", "reduced", "proposals")

        def pick_bucket(self, h, w):
            return self.buckets[0]

        def smaller_bucket(self, bucket):
            return None

        def warmup(self):
            for b in self.buckets:
                for mode in self.levels():
                    self._warmed.add((mode, b))
            return len(self._warmed)

        def swap_weights(self, variables, generation=None):
            gen = (self.generation + 1 if generation is None
                   else int(generation))
            if gen <= self.generation:
                raise ValueError("generation must be monotonic")
            self.generation = gen
            self._sig = self._sig_of(variables)
            self.swapped.append((gen, variables))
            return gen

        def run(self, mode, bucket, images):
            assert (mode, tuple(bucket)) in self._warmed, (
                f"RECOMPILATION on serving path: {(mode, bucket)}"
            )
            if self.delay:
                time.sleep(self.delay)
            s = self._sig
            return [
                {
                    "boxes": np.array(
                        [[0.0, 0.0, 1.0 + s, 1.0 + s]], np.float32
                    ),
                    "scores": np.array([0.9], np.float32),
                    "classes": np.zeros(1, np.int32),
                    "generation": self.generation,
                }
                for _ in images
            ]

    return _WeightRunner


def _deploy_fleet(live_tree, delay: float = 0.002):
    """(fleet, live-runner dict) over weight-sensitive fakes.  The
    returned dict holds ONLY the in-rotation replicas — the Deployer's
    spare canary engine reuses the same factory under a later rid, and
    its swaps must never count as fleet rolls."""
    from mx_rcnn_tpu.serve import FleetRouter, InferenceEngine

    WeightRunner = _deploy_runner_cls()
    n = 2
    runners: dict = {}

    def factory(rid: int) -> InferenceEngine:
        r = WeightRunner(live_tree, delay=delay)
        runners[rid] = r
        return InferenceEngine(r, replica_id=rid, hang_timeout=60.0)

    fleet = FleetRouter(
        factory, n, supervisor_poll=0.1, initial_weights=live_tree,
    )
    return fleet, runners, n


def child_deploy_reject_main() -> int:
    """Two poisoned candidates land under live traffic: a corrupt
    checkpoint (bit-flipped after its manifest was written) and a
    healthy-on-disk tree whose detections regress on the golden set.
    Both must be rejected — and no served response may EVER carry a
    candidate generation tag (rejected generations are burned)."""
    _hermetic_cpu()
    import numpy as np
    from mx_rcnn_tpu import obs
    from mx_rcnn_tpu.ctrl import Deployer
    from mx_rcnn_tpu.train import checkpoint

    obs_dir = os.environ.get("MX_RCNN_OBS_DIR")
    if obs_dir:
        obs.configure(obs_dir)

    live_tree = {"w": np.full((8,), 3.0, np.float32)}
    bad_tree = {"w": np.full((8,), 40.0, np.float32)}
    fleet, runners, n_live = _deploy_fleet(live_tree)

    ckpt_dir = tempfile.mkdtemp(prefix="mx_rcnn_deploy_reject_")
    # Step 1: a clean save, then one flipped byte in the landed files —
    # the manifest checksum must refuse it BEFORE deserialization.
    checkpoint.save_checkpoint(
        ckpt_dir, {"step": 1, "variables": bad_tree}, manifest=True
    )
    manifest = checkpoint.read_manifest(ckpt_dir, 1)
    rel = max(manifest["files"], key=lambda r: manifest["files"][r]["bytes"])
    blob = os.path.join(checkpoint._step_dir(ckpt_dir, 1), rel)
    with open(blob, "r+b") as f:
        raw = bytearray(f.read())
        raw[len(raw) // 2] ^= 0xFF
        f.seek(0)
        f.write(bytes(raw))
    # Step 2: restores fine, but every detection moves away from the
    # live tree's golden ground truth (parity fails AND mAP regresses).
    checkpoint.save_checkpoint(
        ckpt_dir, {"step": 2, "variables": bad_tree}, manifest=True
    )

    live_sig = 3.0
    golden = {
        "images": [np.zeros((32, 32, 3), np.float32)],
        "gt": {0: {"0": {
            "boxes": np.array(
                [[0.0, 0.0, 1.0 + live_sig, 1.0 + live_sig]], np.float32
            ),
            "difficult": np.zeros(1, bool),
        }}},
    }

    served: list = []
    errors: list = []
    stop = threading.Event()

    def pump() -> None:
        i = 0
        while not stop.is_set():
            img = np.full((32, 32, 3), float(i % 13), np.float32)
            try:
                served.append(fleet.infer(img, timeout=60))
            except Exception as e:  # noqa: BLE001 - report, don't die
                errors.append(f"{type(e).__name__}: {e}")
                return
            i += 1
            time.sleep(0.004)

    with fleet:
        dep = Deployer(
            fleet, ckpt_dir,
            mirror_rate=1.0, min_mirrored=5, shadow_window_s=30.0,
            mirror_timeout_s=15.0, slo_fast_s=2.0, slo_slow_s=6.0,
            watch_window_s=30.0, golden=golden,
        )
        pumps = [
            threading.Thread(target=pump, daemon=True) for _ in range(2)
        ]
        for t in pumps:
            t.start()
        wait_for(lambda: len(served) >= 5, 120)
        decisions = dep.step_once()
        stop.set()
        for t in pumps:
            t.join(60)

    burned = sorted(
        h["generation"] for h in dep.history
        if h["kind"] == "deploy_shadow_start"
    )
    gens_served = sorted({r["generation"] for r in served})
    leaked = [g for g in gens_served if g in burned]
    print(json.dumps({
        "decisions": [
            {"step": d["step"], "outcome": d["outcome"],
             "reason": d.get("reason")}
            for d in decisions
        ],
        "responses": len(served),
        "generations_served": gens_served,
        "candidate_generations": burned,
        "leaked_generations": leaked,
        "fleet_generation": fleet.generation,
        "live_swaps": sum(
            len(runners[rid].swapped) for rid in range(n_live)
        ),
        "errors": errors,
    }))
    assert not errors, f"live requests failed during rejection: {errors}"
    assert len(decisions) == 2, decisions
    assert decisions[0]["outcome"] == "invalid", decisions[0]
    assert decisions[0]["reason"].startswith("file_checksum_mismatch"), \
        decisions[0]
    assert decisions[1]["outcome"] == "rejected", decisions[1]
    assert decisions[1]["reason"] == "parity", decisions[1]
    assert fleet.generation == 0, fleet.generation
    assert all(not runners[rid].swapped for rid in range(n_live)), (
        "a live replica was swapped despite both candidates failing the gate"
    )
    assert served and gens_served == [0], gens_served
    assert not leaked, (
        f"rejected candidate generation(s) {leaked} appeared in served "
        "responses"
    )
    return 0


def child_deploy_rollback_main() -> int:
    """Promote a parity-clean candidate, then inject latency so the
    LIVE SLO burns inside the post-promote watch window: the Deployer
    must automatically re-publish the previous generation's retained
    tree — bitwise — under a NEW, HIGHER generation number, landing the
    whole fleet back on a single generation."""
    _hermetic_cpu()
    import numpy as np
    from mx_rcnn_tpu import obs
    from mx_rcnn_tpu.config import CtrlConfig
    from mx_rcnn_tpu.ctrl import Deployer, SLOEngine, default_slos
    from mx_rcnn_tpu.train import checkpoint

    obs_dir = os.environ.get("MX_RCNN_OBS_DIR")
    if obs_dir:
        obs.configure(obs_dir)

    live_tree = {"w": np.full((8,), 3.0, np.float32)}
    # Bitwise-equal weights under a fresh step: parity passes, the
    # regression is an SLO burn AFTER promotion, not an accuracy drop.
    cand_tree = {"w": np.full((8,), 3.0, np.float32)}
    fleet, runners, n_live = _deploy_fleet(live_tree)

    ckpt_dir = tempfile.mkdtemp(prefix="mx_rcnn_deploy_rollback_")
    checkpoint.save_checkpoint(
        ckpt_dir, {"step": 1, "variables": cand_tree}, manifest=True
    )

    ctrl = CtrlConfig(latency_target=0.9, latency_threshold_s=0.05)
    live_slo = SLOEngine(
        default_slos(ctrl), fast_s=2.0, slow_s=6.0, burn_factor=2.0,
    ).start(0.2)

    served: list = []
    errors: list = []
    stop = threading.Event()

    def pump() -> None:
        i = 0
        while not stop.is_set():
            img = np.full((32, 32, 3), float(i % 13), np.float32)
            try:
                served.append(fleet.infer(img, timeout=60))
            except Exception as e:  # noqa: BLE001 - report, don't die
                errors.append(f"{type(e).__name__}: {e}")
                return
            i += 1
            time.sleep(0.004)

    rollback = None
    try:
        with fleet:
            # Shadow-scoped availability is relaxed: the spare engine's
            # bounded queue can shed a burst under the 1.0 mirror rate,
            # and a single shed in ~15 samples would fail a 0.95 target
            # — this scenario's regression is the post-promote LIVE
            # latency burn, not shadow capacity.
            dep = Deployer(
                fleet, ckpt_dir,
                mirror_rate=1.0, min_mirrored=5, shadow_window_s=30.0,
                mirror_timeout_s=15.0, slo_fast_s=2.0, slo_slow_s=6.0,
                watch_window_s=120.0, live_slo=live_slo,
                availability_target=0.5,
            )
            pumps = [
                threading.Thread(target=pump, daemon=True)
                for _ in range(2)
            ]
            for t in pumps:
                t.start()
            wait_for(lambda: len(served) >= 5, 120)
            decisions = dep.step_once()
            assert decisions and decisions[-1]["outcome"] == "promoted", \
                decisions
            promoted_gen = decisions[-1]["generation"]
            wait_for(
                lambda: any(
                    r["generation"] == promoted_gen for r in list(served)
                ),
                120,
            )
            # The new generation misbehaves in production: every live
            # request now lands far above the latency SLO threshold.
            for rid in range(n_live):
                runners[rid].delay = 0.3
            deadline = time.monotonic() + 90
            while rollback is None and time.monotonic() < deadline:
                for d in dep.step_once():
                    if d["outcome"] == "rolled_back":
                        rollback = d
                time.sleep(0.2)
            for rid in range(n_live):
                runners[rid].delay = 0.002  # lift so the drain is quick
            stop.set()
            for t in pumps:
                t.join(60)
            wait_for(
                lambda: rollback is not None and any(
                    r[0] == rollback["to_generation"]
                    for rid in range(n_live)
                    for r in runners[rid].swapped
                ),
                60,
            )
    finally:
        live_slo.stop()

    assert rollback is not None, (
        "live SLO burn inside the watch window never triggered rollback"
    )
    restored = [runners[rid].swapped[-1] for rid in range(n_live)]
    bitwise = all(
        gen == rollback["to_generation"]
        and sorted(tree) == sorted(live_tree)
        and all(np.array_equal(tree[k], live_tree[k]) for k in tree)
        for gen, tree in restored
    )
    pod_gens = sorted({runners[rid].generation for rid in range(n_live)})
    gens_served = sorted({r["generation"] for r in served})
    print(json.dumps({
        "promoted_generation": promoted_gen,
        "from_generation": rollback["from_generation"],
        "to_generation": rollback["to_generation"],
        "restored_generation": rollback["restored_generation"],
        "burn_slo": rollback["slo"],
        "bitwise_restore": bitwise,
        "pod_generations": pod_gens,
        "generations_served": gens_served,
        "responses": len(served),
        "errors": errors,
    }))
    assert not errors, f"live requests failed during the roll: {errors}"
    assert rollback["from_generation"] == promoted_gen, rollback
    assert rollback["to_generation"] > promoted_gen, (
        "rollback rewound the generation number: "
        f"{rollback['to_generation']} <= {promoted_gen}"
    )
    assert fleet.generation == rollback["to_generation"], fleet.generation
    assert bitwise, (
        "rollback did not restore the previous generation's tree bitwise"
    )
    assert pod_gens == [rollback["to_generation"]], (
        f"pod split across generations after rollback: {pod_gens}"
    )
    assert set(gens_served) <= {0, promoted_gen,
                                rollback["to_generation"]}, gens_served
    return 0


def compare_main(dir_a: str, dir_b: str) -> int:
    """Bitwise-compare the newest checkpoints of two run dirs."""
    _hermetic_cpu()
    import numpy as np

    import jax
    from mx_rcnn_tpu.train.checkpoint import restore_raw

    a, b = restore_raw(dir_a), restore_raw(dir_b)
    fa, ta = jax.tree_util.tree_flatten(a)
    fb, tb = jax.tree_util.tree_flatten(b)
    if ta != tb:
        print(json.dumps({"equal": False, "why": "tree structure differs"}))
        return 1
    diffs = [
        i for i, (x, y) in enumerate(zip(fa, fb))
        if not np.array_equal(np.asarray(x), np.asarray(y))
    ]
    print(json.dumps({"equal": not diffs, "leaves": len(fa), "diffs": diffs}))
    return 1 if diffs else 0


# -- orchestrator -------------------------------------------------------------


def train_argv(workdir: str, steps: int, resume: bool = False,
               cache_dir: str | None = None, service_workers: int = 2,
               respawns: int = 2,
               extra_sets: tuple[str, ...] = ()) -> list[str]:
    # Every train child runs the PRODUCTION input path: process decode
    # workers + the checksummed tensor cache.  The cache root is shared
    # across sibling scenarios by default (one level above the per-
    # scenario workdir): baseline populates it cold, sigkill/sigterm/
    # truncate resume against it warm — so the standing bit-identity
    # comparisons double as the cache-hit-vs-miss bitwise proof.
    if cache_dir is None:
        cache_dir = os.path.join(
            os.path.dirname(os.path.abspath(workdir)), "tensor_cache"
        )
    argv = [
        sys.executable, os.path.abspath(__file__), "--child", "--",
        "--config", CONFIG, "--workdir", workdir,
        "--steps", str(steps), "--no-eval",
        "--set", f"train.checkpoint_every={CKPT_EVERY}",
        "--set", f"train.log_every={LOG_EVERY}",
        "--set", f"data.num_workers={service_workers}",
        "--set", f"data.worker_respawns={respawns}",
        "--set", f"data.cache_dir={cache_dir}",
    ]
    for item in extra_sets:
        argv += ["--set", item]
    if resume:
        argv.append("--resume")
    return argv


def eval_argv(workdir: str, ckpt: str, resume: bool = False) -> list[str]:
    argv = [
        sys.executable, os.path.abspath(__file__), "--child-eval", "--",
        "--config", CONFIG, "--workdir", workdir, "--ckpt", ckpt,
        "--resumable", "--shard-size", "1", "--limit", str(EVAL_LIMIT),
        "--dump", os.path.join(workdir, "detections.json"),
    ]
    if resume:
        argv.append("--resume")
    return argv


def ckpt_dir(workdir: str) -> str:
    return os.path.join(workdir, CONFIG, "ckpt")


def finalized_steps(workdir: str) -> list[int]:
    """Finalized orbax step dirs (bare ints; tmp dirs have suffixes)."""
    d = ckpt_dir(workdir)
    if not os.path.isdir(d):
        return []
    return sorted(
        int(n) for n in os.listdir(d)
        if n.isdigit() and os.path.isdir(os.path.join(d, n))
    )


def metrics_rows(workdir: str) -> list[dict]:
    path = os.path.join(workdir, CONFIG, "metrics.jsonl")
    if not os.path.exists(path):
        return []
    rows = []
    with open(path) as f:
        for line in f:
            try:
                rows.append(json.loads(line))
            except ValueError:
                pass
    return rows


# Every live chaos subprocess, so a scenario-timeout (or harness exit)
# can SIGKILL the lot instead of leaving orphans holding the CI budget.
_LIVE_PROCS: set = set()


def reap_orphans() -> int:
    """SIGKILL every still-live chaos child; returns how many."""
    reaped = 0
    for proc in list(_LIVE_PROCS):
        if proc.poll() is None:
            try:
                proc.kill()
                proc.wait(5)
                reaped += 1
            except Exception:  # noqa: BLE001 - best effort by design
                pass
        _LIVE_PROCS.discard(proc)
    return reaped


class Child:
    def __init__(self, workdir: str, argv: list[str],
                 log_name: str = "child-first",
                 env: dict | None = None) -> None:
        self.log_path = os.path.join(workdir, f"{log_name}.log")
        os.makedirs(workdir, exist_ok=True)
        self._log = open(self.log_path, "a")
        self.proc = subprocess.Popen(
            argv,
            stdout=self._log, stderr=subprocess.STDOUT,
            env={**os.environ, **(env or {})}, cwd=REPO_ROOT,
        )
        _LIVE_PROCS.add(self.proc)

    def wait(self, timeout: float) -> int:
        try:
            return self.proc.wait(timeout)
        finally:
            self._log.close()
            _LIVE_PROCS.discard(self.proc)

    def signal(self, sig: int) -> None:
        self.proc.send_signal(sig)

    def log_tail(self, n: int = 30) -> str:
        with open(self.log_path) as f:
            return "".join(f.readlines()[-n:])

    def log_contains(self, needle: str) -> bool:
        try:
            with open(self.log_path) as f:
                return needle in f.read()
        except OSError:
            return False


def wait_for(predicate, timeout: float, poll: float = 0.25):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = predicate()
        if v:
            return v
        time.sleep(poll)
    return None


def run_argv_to_completion(workdir: str, argv: list[str], timeout: float,
                           log_name: str, env: dict | None = None) -> int:
    child = Child(workdir, argv, log_name=log_name, env=env)
    rc = child.wait(timeout)
    if rc not in (0,):
        raise AssertionError(
            f"child exited {rc} (log: {child.log_path})\n{child.log_tail()}"
        )
    return rc


def run_to_completion(workdir: str, steps: int, timeout: float,
                      resume: bool = False, env: dict | None = None,
                      **argv_kw) -> int:
    return run_argv_to_completion(
        workdir, train_argv(workdir, steps, resume, **argv_kw), timeout,
        log_name=f"child-{'resume' if resume else 'first'}", env=env,
    )


def bitwise_equal(workdir_a: str, workdir_b: str, timeout: float) -> bool:
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--compare",
         ckpt_dir(workdir_a), ckpt_dir(workdir_b)],
        capture_output=True, text=True, timeout=timeout, cwd=REPO_ROOT,
    )
    sys.stderr.write(out.stdout + out.stderr)
    return out.returncode == 0


def interrupt_at_checkpoint(workdir: str, steps: int, sig: int,
                            min_step: int, timeout: float) -> int:
    """Start a run, deliver ``sig`` once a checkpoint >= min_step is
    finalized, return the exit code."""
    child = Child(workdir, train_argv(workdir, steps))
    hit = wait_for(
        lambda: [s for s in finalized_steps(workdir) if s >= min_step],
        timeout,
    )
    if not hit:
        child.signal(signal.SIGKILL)
        child.wait(timeout)
        raise AssertionError(
            f"no checkpoint >= {min_step} appeared within {timeout}s "
            f"(log: {child.log_path})\n{child.log_tail()}"
        )
    child.signal(sig)
    return child.wait(timeout)


def scenario_baseline(root: str, steps: int, timeout: float) -> dict:
    wd = os.path.join(root, "baseline")
    done = finalized_steps(wd)
    if done and done[-1] == steps:  # idempotent across partial reruns
        return {"final_step": steps, "reused": True}
    run_to_completion(wd, steps, timeout)
    final = finalized_steps(wd)
    assert final and final[-1] == steps, f"final checkpoints: {final}"
    return {"final_step": final[-1]}


def scenario_sigkill(root: str, steps: int, timeout: float) -> dict:
    wd = os.path.join(root, "sigkill")
    rc = interrupt_at_checkpoint(
        wd, steps, signal.SIGKILL, min_step=CKPT_EVERY, timeout=timeout
    )
    assert rc == -signal.SIGKILL, f"expected SIGKILL death, got rc={rc}"
    interrupted_at = finalized_steps(wd)[-1]
    assert interrupted_at < steps, "child finished before the kill landed"
    run_to_completion(wd, steps, timeout, resume=True)
    assert finalized_steps(wd)[-1] == steps
    assert bitwise_equal(os.path.join(root, "baseline"), wd, timeout), (
        "resumed-after-SIGKILL params differ from the uninterrupted run"
    )
    return {"killed_after_step": interrupted_at, "bit_identical": True}


def scenario_sigterm(root: str, steps: int, timeout: float) -> dict:
    # Pinned contract (EX_TEMPFAIL) — mirrored from train/preemption.py so
    # the orchestrator stays import-free; test_robustness pins the value.
    RESUMABLE_EXIT_CODE = 75

    wd = os.path.join(root, "sigterm")
    rc = interrupt_at_checkpoint(
        wd, steps, signal.SIGTERM, min_step=CKPT_EVERY, timeout=timeout
    )
    assert rc == RESUMABLE_EXIT_CODE, (
        f"expected resumable exit {RESUMABLE_EXIT_CODE}, got {rc}"
    )
    emergency = finalized_steps(wd)[-1]
    assert emergency < steps
    run_to_completion(wd, steps, timeout, resume=True)
    assert finalized_steps(wd)[-1] == steps
    assert bitwise_equal(os.path.join(root, "baseline"), wd, timeout), (
        "resumed-after-SIGTERM params differ from the uninterrupted run"
    )
    return {"emergency_step": emergency, "bit_identical": True}


def scenario_nan(root: str, steps: int, timeout: float) -> dict:
    wd = os.path.join(root, "nan")
    poison = CKPT_EVERY + 2  # inside the second checkpoint interval
    run_to_completion(
        wd, steps, timeout, env={"MX_RCNN_CHAOS_NAN_STEPS": str(poison)}
    )
    assert finalized_steps(wd)[-1] == steps
    rows = metrics_rows(wd)
    assert rows and rows[-1]["step"] == steps, f"metrics rows: {rows}"
    bad = [
        (r["step"], k) for r in rows for k, v in r.items()
        if isinstance(v, float) and v != v  # NaN
    ]
    assert not bad, f"non-finite metrics survived the rollback: {bad}"
    return {"poisoned_batch": poison, "metric_rows": len(rows)}


def scenario_truncate(root: str, steps: int, timeout: float) -> dict:
    wd = os.path.join(root, "truncate")
    rc = interrupt_at_checkpoint(
        wd, steps, signal.SIGKILL, min_step=2 * CKPT_EVERY, timeout=timeout
    )
    assert rc == -signal.SIGKILL
    latest = finalized_steps(wd)[-1]
    # Truncate every file of the newest checkpoint — a kill mid-write.
    clipped = 0
    for dirpath, _, files in os.walk(os.path.join(ckpt_dir(wd), str(latest))):
        for name in files:
            path = os.path.join(dirpath, name)
            size = os.path.getsize(path)
            with open(path, "r+b") as f:
                f.truncate(size // 2)
            clipped += 1
    assert clipped, f"checkpoint step {latest} has no files to truncate"
    run_to_completion(wd, steps, timeout, resume=True)
    assert finalized_steps(wd)[-1] == steps
    assert bitwise_equal(os.path.join(root, "baseline"), wd, timeout), (
        "recovery past the truncated checkpoint lost bit-identity"
    )
    return {"truncated_step": latest, "files_clipped": clipped,
            "bit_identical": True}


# -- data-path scenarios ------------------------------------------------------


def _child_log(workdir: str, name: str = "child-first") -> str:
    try:
        with open(os.path.join(workdir, f"{name}.log")) as f:
            return f.read()
    except OSError:
        return ""


def scenario_data_worker_kill(root: str, steps: int, timeout: float) -> dict:
    """SIGKILL one decode worker mid-epoch (the worker self-kills on a
    claimed batch index); its in-flight batches are reassigned and the
    final params must be bitwise-identical to the uninterrupted run."""
    wd = os.path.join(root, "data_worker_kill")
    os.makedirs(wd, exist_ok=True)
    sentinel = os.path.join(wd, "suicide.sentinel")
    obs_dir = os.path.join(wd, "obs")
    kill_idx = CKPT_EVERY + 1  # mid-epoch, past the first checkpoint
    run_to_completion(
        wd, steps, timeout,
        env={"MX_RCNN_CHAOS_DATA_SUICIDE": f"{kill_idx}:{sentinel}"},
        extra_sets=("obs.enabled=true", f"obs.dir={obs_dir}"),
    )
    assert finalized_steps(wd)[-1] == steps
    assert os.path.exists(sentinel), (
        "no worker ever claimed the suicide fault — the service path "
        "did not run"
    )
    logtxt = _child_log(wd)
    assert "chaos: self-SIGKILL" in logtxt, "worker never self-killed"
    assert "respawning" in logtxt, (
        "dead worker was never respawned (watchdog missed the death)"
    )
    # The grep strings above are derived from the typed journal — the
    # same death must be queryable as a worker_death event with payload.
    sys.path.insert(0, REPO_ROOT)
    try:
        from mx_rcnn_tpu.obs import read_journal
    finally:
        sys.path.pop(0)

    journal = read_journal(os.path.join(obs_dir, "journal.jsonl"))
    deaths = [r for r in journal if r.get("kind") == "worker_death"]
    assert deaths, "journal recorded no worker_death event"
    assert any(
        r.get("kind") == "checkpoint_saved" for r in journal
    ), "journal recorded no checkpoint_saved event"
    assert bitwise_equal(os.path.join(root, "baseline"), wd, timeout), (
        "params diverged after a decode-worker SIGKILL — reassignment "
        "is not schedule-deterministic"
    )
    return {"killed_batch": kill_idx, "bit_identical": True,
            "journal_events": len(journal)}


def scenario_data_worker_wedge(root: str, steps: int, timeout: float) -> dict:
    """One worker wedges (sleeps without heartbeating); the tightened
    watchdog must reap + respawn it, the run completes bit-identical, and
    per-interval data_stall_ms stays bounded by the watchdog — the wedge
    sleep itself (3600s) must never leak into the consumer wait."""
    wd = os.path.join(root, "data_worker_wedge")
    os.makedirs(wd, exist_ok=True)
    sentinel = os.path.join(wd, "wedge.sentinel")
    wedge_idx = CKPT_EVERY + 1
    watchdog_s = 4.0
    run_to_completion(
        wd, steps, timeout,
        env={
            "MX_RCNN_CHAOS_DATA_WEDGE": f"{wedge_idx}:{sentinel}",
            "MX_RCNN_DATA_WATCHDOG_S": str(watchdog_s),
        },
    )
    assert finalized_steps(wd)[-1] == steps
    assert os.path.exists(sentinel), "no worker ever claimed the wedge"
    logtxt = _child_log(wd)
    assert "wedged" in logtxt, "watchdog never reaped the wedged worker"
    assert "respawning" in logtxt
    stalls = [
        r["data_stall_ms"] for r in metrics_rows(wd)
        if "data_stall_ms" in r
    ]
    assert stalls, "no data_stall_ms rows — stall metering is dark"
    bound_ms = 30_000.0  # generous: watchdog 4s + respawn + CPU decode
    assert max(stalls) < bound_ms, (
        f"data_stall_ms peaked at {max(stalls):.0f}ms — the wedge leaked "
        f"past the {watchdog_s:.0f}s watchdog"
    )
    assert bitwise_equal(os.path.join(root, "baseline"), wd, timeout), (
        "params diverged after a wedged decode worker"
    )
    return {"wedged_batch": wedge_idx, "max_stall_ms": round(max(stalls), 1),
            "bit_identical": True}


def _blob_valid(path: str) -> bool:
    """Inline tensor-blob integrity check (mirrors data/cache.py's layout:
    magic, u32 header len, JSON header with crc32/nbytes, payload) — the
    orchestrator stays package-import-free."""
    import struct
    import zlib

    with open(path, "rb") as f:
        blob = f.read()
    magic = b"MXTC1\n"
    if not blob.startswith(magic) or len(blob) < len(magic) + 4:
        return False
    (hlen,) = struct.unpack_from("<I", blob, len(magic))
    try:
        header = json.loads(blob[len(magic) + 4 : len(magic) + 4 + hlen])
    except ValueError:
        return False
    payload = blob[len(magic) + 4 + hlen :]
    return (
        len(payload) == header["nbytes"]
        and zlib.crc32(payload) == header["crc32"]
    )


def scenario_cache_corrupt(root: str, steps: int, timeout: float) -> dict:
    """Bit-rot a cached tensor blob between two runs sharing the cache:
    the second run must detect the checksum mismatch, quarantine + rebuild
    the blob, complete, and stay bitwise-identical to baseline — corrupt
    cache bytes are never served."""
    import glob as _glob

    wd = os.path.join(root, "cache_corrupt")
    cache = os.path.join(wd, "tensor_cache")  # private: we poison it
    wd_a = os.path.join(wd, "populate")
    run_to_completion(wd_a, steps, timeout, cache_dir=cache)
    assert finalized_steps(wd_a)[-1] == steps
    blobs = sorted(_glob.glob(os.path.join(cache, "tensors", "*", "*.blob")))
    assert blobs, f"populate run wrote no tensor blobs under {cache}"
    victim = blobs[0]
    with open(victim, "r+b") as f:
        f.seek(-8, os.SEEK_END)
        tail = f.read(8)
        f.seek(-8, os.SEEK_END)
        f.write(bytes(b ^ 0xFF for b in tail))  # flip payload bytes
    assert not _blob_valid(victim), "corruption did not take"
    wd_b = os.path.join(wd, "repair")
    run_to_completion(wd_b, steps, timeout, cache_dir=cache)
    assert finalized_steps(wd_b)[-1] == steps
    qpath = os.path.join(wd_b, CONFIG, "quarantine.jsonl")
    assert os.path.exists(qpath), "corrupt blob was never quarantined"
    reasons = set()
    with open(qpath) as f:
        for line in f:
            try:
                reasons.add(json.loads(line).get("reason"))
            except ValueError:
                pass
    assert "cache_checksum" in reasons, (
        f"expected a cache_checksum quarantine record, got {sorted(reasons)}"
    )
    assert _blob_valid(victim), (
        "corrupt blob was not rebuilt in place (repair run left it rotten)"
    )
    assert bitwise_equal(os.path.join(root, "baseline"), wd_b, timeout), (
        "params diverged after cache corruption — corrupt bytes reached "
        "training"
    )
    return {"corrupted_blob": os.path.basename(victim),
            "quarantine_reasons": sorted(r for r in reasons if r),
            "bit_identical": True}


def scenario_data_service_dead(root: str, steps: int, timeout: float) -> dict:
    """Every worker dies on its first task ("always" suicide) until the
    respawn budget is exhausted; the service must degrade to in-process
    synchronous assembly and the run must STILL complete bit-identical."""
    wd = os.path.join(root, "data_service_dead")
    run_to_completion(
        wd, steps, timeout, respawns=1,
        env={"MX_RCNN_CHAOS_DATA_SUICIDE": "always"},
    )
    assert finalized_steps(wd)[-1] == steps
    logtxt = _child_log(wd)
    assert "respawn budget exhausted" in logtxt, (
        "service never exhausted its respawn budget"
    )
    assert "falling back to in-process synchronous assembly" in logtxt, (
        "service died without the logged degradation transition"
    )
    assert bitwise_equal(os.path.join(root, "baseline"), wd, timeout), (
        "sync-fallback params differ from the uninterrupted run"
    )
    return {"fallback": "sync", "bit_identical": True}


# -- inference scenarios ------------------------------------------------------


def shard_files(workdir: str) -> list[str]:
    d = os.path.join(workdir, CONFIG, "eval_shards")
    if not os.path.isdir(d):
        return []
    return sorted(
        n for n in os.listdir(d)
        if n.startswith("shard-") and n.endswith(".json")
    )


def _baseline_ckpt(root: str) -> str:
    d = ckpt_dir(os.path.join(root, "baseline"))
    assert os.path.isdir(d), "baseline scenario must run first"
    return d


def scenario_eval_sigkill(root: str, steps: int, timeout: float) -> dict:
    ckpt = _baseline_ckpt(root)
    ref = os.path.join(root, "eval_ref")
    run_argv_to_completion(
        ref, eval_argv(ref, ckpt), timeout, log_name="eval-ref"
    )
    with open(os.path.join(ref, "detections.json"), "rb") as f:
        ref_bytes = f.read()

    wd = os.path.join(root, "eval_sigkill")
    child = Child(wd, eval_argv(wd, ckpt), log_name="eval-first")
    hit = wait_for(lambda: shard_files(wd), timeout, poll=0.05)
    if not hit:
        child.signal(signal.SIGKILL)
        child.wait(timeout)
        raise AssertionError(
            f"no shard checkpoint appeared within {timeout}s "
            f"(log: {child.log_path})\n{child.log_tail()}"
        )
    child.signal(signal.SIGKILL)
    rc = child.wait(timeout)
    assert rc == -signal.SIGKILL, f"expected SIGKILL death, got rc={rc}"
    partial = len(shard_files(wd))
    assert 0 < partial < EVAL_LIMIT, (
        f"kill left {partial}/{EVAL_LIMIT} shards — nothing to resume"
    )
    run_argv_to_completion(
        wd, eval_argv(wd, ckpt, resume=True), timeout, log_name="eval-resume"
    )
    assert len(shard_files(wd)) == EVAL_LIMIT
    with open(os.path.join(wd, "detections.json"), "rb") as f:
        got = f.read()
    assert got == ref_bytes, (
        "resumed eval detections differ from the uninterrupted run"
    )
    return {"killed_after_shards": partial, "total_shards": EVAL_LIMIT,
            "byte_identical": True}


def scenario_eval_corrupt(root: str, steps: int, timeout: float) -> dict:
    ckpt = _baseline_ckpt(root)
    wd = os.path.join(root, "eval_corrupt")
    bad = ["3", "7"]  # inside the --limit window of the synthetic split
    run_argv_to_completion(
        wd, eval_argv(wd, ckpt), timeout, log_name="eval-corrupt",
        env={"MX_RCNN_CHAOS_BAD_IMAGES": ",".join(bad)},
    )
    qpath = os.path.join(wd, CONFIG, "quarantine.jsonl")
    assert os.path.exists(qpath), "corrupt images were not quarantined"
    with open(qpath) as f:
        rows = [json.loads(line) for line in f]
    quarantined = {str(r["image_id"]) for r in rows}
    assert set(bad) <= quarantined, (
        f"expected {bad} quarantined, got {sorted(quarantined)}"
    )
    with open(os.path.join(wd, "detections.json")) as f:
        dump = json.load(f)
    assert len(dump) == EVAL_LIMIT, (
        f"dump holds {len(dump)}/{EVAL_LIMIT} images — corrupt inputs must "
        "blank-substitute, not drop"
    )
    return {"quarantined": sorted(quarantined), "dump_images": len(dump)}


# Journal kinds written by the runtime lock sanitizer
# (mx_rcnn_tpu/analysis/lockcheck.py).  The in-process raise is the
# primary signal — a child that trips dies nonzero — but a violation on
# a thread whose exceptions get swallowed (supervisor loops, probe
# loops) still reaches the journal, and the scenario must fail on it.
SANITIZER_KINDS = {"lock_order_violation", "held_lock_blocked_call"}


def _assert_no_sanitizer_reports(wd: str) -> None:
    """Fail if any journal under this scenario's workdir carries a
    lockcheck report.  No-op when the sanitizer was not enabled."""
    if os.environ.get("MX_RCNN_LOCKCHECK") != "1":
        return
    for path in glob.glob(
        os.path.join(wd, "**", "journal.jsonl"), recursive=True
    ):
        with open(path) as f:
            for ln in f:
                try:
                    rec = json.loads(ln)
                except ValueError:
                    continue
                assert rec.get("kind") not in SANITIZER_KINDS, (
                    f"lock sanitizer report in {path}: {rec}"
                )


def _json_child(root: str, name: str, flag: str, timeout: float,
                env: Optional[dict] = None) -> dict:
    """Run a self-asserting child mode; return its JSON stdout line."""
    wd = os.path.join(root, name)
    os.makedirs(wd, exist_ok=True)
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), flag],
        capture_output=True, text=True, timeout=timeout, cwd=REPO_ROOT,
        env={**os.environ, **env} if env else None,
    )
    with open(os.path.join(wd, "child.log"), "w") as f:
        f.write(out.stdout + out.stderr)
    assert out.returncode == 0, (
        f"{name} child exited {out.returncode}:\n{out.stdout}\n{out.stderr}"
    )
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    assert lines, f"{name} child printed no JSON:\n{out.stdout}"
    _assert_no_sanitizer_reports(wd)
    return json.loads(lines[-1])


def scenario_overload(root: str, steps: int, timeout: float) -> dict:
    r = _json_child(root, "overload", "--child-overload", timeout)
    # The child already asserted shed >= 1 and served == submitted - shed;
    # re-assert here so the summary line can't paper over a child bug.
    assert r["shed"] >= 1 and r["served"] == r["submitted"] - r["shed"], r
    return r


def scenario_hang(root: str, steps: int, timeout: float) -> dict:
    r = _json_child(root, "hang", "--child-hang", timeout)
    assert r.get("hung") == 1 and r.get("state") == "dead", r
    return r


# -- fleet scenarios ----------------------------------------------------------


def scenario_replica_kill(root: str, steps: int, timeout: float) -> dict:
    # Journal enabled: on top of the child's own zero-loss assertions,
    # the scenario proves the incident is reconstructable from the obs
    # artifacts alone (docs/observability.md).
    obs_dir = os.path.join(root, "replica_kill", "obs")
    r = _json_child(root, "replica_kill", "--child-replica-kill", timeout,
                    env={"MX_RCNN_OBS_DIR": obs_dir})
    assert r["failed"] == 0 and r["completed"] == r["accepted"], r
    assert r["quarantines"] >= 1 and r["reinstatements"] >= 1, r

    # The flight recorder fired on the kill and captured the killing
    # event in its postmortem ring.
    dumps = sorted(glob.glob(os.path.join(obs_dir, "flight_*.json")))
    assert dumps, f"no flight-recorder dump under {obs_dir}"
    dump_kinds: set = set()
    for path in dumps:
        with open(path) as f:
            dump_kinds.update(
                e.get("kind") for e in json.load(f)["entries"]
                if isinstance(e, dict)
            )
    assert "engine_killed" in dump_kinds, sorted(
        k for k in dump_kinds if k
    )

    # The journal alone reconstructs kill -> quarantine -> reinstate.
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    report, _ = obs_report.build_report(obs_dir)
    tl = [e["kind"] for e in report["incident_timeline"]]
    for kind in ("engine_killed", "fleet_quarantine", "fleet_reinstate"):
        assert kind in tl, tl
    assert max(
        tl.index("engine_killed"), tl.index("fleet_quarantine")
    ) < tl.index("fleet_reinstate"), tl
    r["obs_events"] = report["journal_records"]
    r["flight_dumps"] = len(dumps)
    return r


def scenario_replica_wedge(root: str, steps: int, timeout: float) -> dict:
    r = _json_child(root, "replica_wedge", "--child-replica-wedge", timeout)
    assert r["failed"] == 0 and r["hedges"] >= 1, r
    assert r["quarantines"] >= 1 and r["p99_s"] < 60.0, r
    return r


def scenario_tenant_starvation(root: str, steps: int, timeout: float) -> dict:
    # Journal enabled: beyond the child's own isolation assertions, the
    # per-tenant story (quota rejections on the flooder, clean outcomes
    # for the victims) must be reconstructable from the obs artifacts
    # alone via tools/obs_report.py.
    obs_dir = os.path.join(root, "tenant_starvation", "obs")
    r = _json_child(root, "tenant_starvation", "--child-tenant-starvation",
                    timeout, env={"MX_RCNN_OBS_DIR": obs_dir})
    for t, v in r["victims"].items():
        assert v["completed"] == v["submitted"], (t, r)
        assert v["quota"] == 0 and v["shed"] == 0, (t, r)
    assert r["flooder"]["quota"] >= 1, r
    assert r["fleet"]["shed"] == 0 and r["fleet"]["failed"] == 0, r
    assert r["mix_p99_s"] <= 3.0 * max(r["baseline_p99_s"], 0.25), r

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    report, _ = obs_report.build_report(obs_dir)
    tenants = report["tenants"]
    assert set(tenants) >= {"victim", "bursty", "flood"}, sorted(tenants)
    assert tenants["flood"]["quota_rejections"] >= r["flooder"]["quota"], (
        tenants["flood"]
    )
    for t in ("victim", "bursty"):
        assert tenants[t]["quota_rejections"] == 0, tenants[t]
        assert tenants[t]["requests"].get("shed", 0) == 0, tenants[t]
        assert tenants[t]["requests"].get("completed", 0) >= 1, tenants[t]
    r["report_tenants"] = {
        t: {"requests": v["requests"],
            "quota_rejections": v["quota_rejections"]}
        for t, v in tenants.items()
    }
    return r


def scenario_swap_under_load(root: str, steps: int, timeout: float) -> dict:
    obs_dir = os.path.join(root, "swap_under_load", "obs")
    r = _json_child(root, "swap_under_load", "--child-swap", timeout,
                    env={"MX_RCNN_OBS_DIR": obs_dir})
    assert not r["mismatched"] and not r["errors"], r
    assert r["generations_seen"] == [0, r["swap_generation"]], r
    return r


def scenario_fleet_drain(root: str, steps: int, timeout: float) -> dict:
    """SIGTERM a real serving child mid-load; it must drain and exit 75."""
    RESUMABLE_EXIT_CODE = 75  # pinned, mirrors train/preemption.py

    wd = os.path.join(root, "fleet_drain")
    child = Child(
        wd, [sys.executable, os.path.abspath(__file__),
             "--child-fleet-drain"],
        log_name="fleet-drain",
    )
    if not wait_for(lambda: child.log_contains("FLEET_READY"), timeout):
        child.signal(signal.SIGKILL)
        child.wait(timeout)
        raise AssertionError(
            f"fleet never came up within {timeout}s "
            f"(log: {child.log_path})\n{child.log_tail()}"
        )
    time.sleep(2.0)  # let accepted load pile up mid-flight
    child.signal(signal.SIGTERM)
    rc = child.wait(timeout)
    assert rc == RESUMABLE_EXIT_CODE, (
        f"expected resumable exit {RESUMABLE_EXIT_CODE}, got {rc} "
        f"(log: {child.log_path})\n{child.log_tail()}"
    )
    with open(child.log_path) as f:
        lines = [ln for ln in f if ln.startswith("{")]
    assert lines, f"drain child printed no JSON\n{child.log_tail()}"
    r = json.loads(lines[-1])
    assert r["accepted"] > 0 and r["failed"] == 0 and r["drained_clean"], r
    _assert_no_sanitizer_reports(wd)
    return r


def scenario_fleet_scale(root: str, steps: int, timeout: float) -> dict:
    # Journal enabled: beyond the child's zero-loss assertions, the
    # scenario proves the whole resize story — decision, build, join,
    # dwell, retire — reconstructs from the obs artifacts alone.
    obs_dir = os.path.join(root, "fleet_scale", "obs")
    r = _json_child(root, "fleet_scale", "--child-fleet-scale", timeout,
                    env={"MX_RCNN_OBS_DIR": obs_dir})
    assert r["failed"] == 0 and r["completed"] == r["accepted"], r
    assert r["added"] >= 1 and r["retired"] >= 1, r

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    report, _ = obs_report.build_report(obs_dir)
    tl = [e["kind"] for e in report["incident_timeline"]]
    for kind in ("fleet_scale_up", "fleet_replica_added",
                 "fleet_scale_down", "fleet_replica_retired"):
        assert kind in tl, tl
    assert tl.index("fleet_scale_up") < tl.index("fleet_scale_down"), tl
    assert tl.index("fleet_replica_added") < tl.index(
        "fleet_replica_retired"
    ), tl
    r["obs_events"] = report["journal_records"]
    return r


# -- cross-host fabric scenarios ---------------------------------------------


def scenario_host_kill(root: str, steps: int, timeout: float) -> dict:
    wd = os.path.join(root, "host_kill")
    r = _json_child(root, "host_kill", "--child-host-kill", timeout,
                    env={"MX_RCNN_FABRIC_WD": wd})
    assert not r["errors"] and r["completed"] == r["accepted"], r
    assert r["failed"] == 0 and r["quarantines"] >= 1, r
    assert r["gossip_dead"] and r["post_kill_hosts"] == ["hostB"], r
    assert r["survivor_exit"] == 75, r
    assert r["metrics_clean"], r
    return r


def scenario_host_partition(root: str, steps: int, timeout: float) -> dict:
    wd = os.path.join(root, "host_partition")
    r = _json_child(root, "host_partition", "--child-host-partition",
                    timeout, env={"MX_RCNN_FABRIC_WD": wd})
    assert r["partition_detected"] and r["fenced"], r
    assert not r["errors"] and r["failed"] == 0, r
    assert r["hosts_during_partition"] == ["hostB"], r
    assert r["healed"] and r["reinstated"] and r["routable_final"] == 2, r
    return r


def scenario_cross_host_swap(root: str, steps: int, timeout: float) -> dict:
    wd = os.path.join(root, "cross_host_swap")
    r = _json_child(root, "cross_host_swap", "--child-cross-host-swap",
                    timeout, env={"MX_RCNN_FABRIC_WD": wd})
    assert not r["errors"] and not r["mismatched"], r
    assert r["generations_seen"] == [0, r["swap_generation"]], r
    assert r["hosts_used"] == ["hostA", "hostB"], r
    return r


# -- continuous-deployment scenarios ------------------------------------------


def _deploy_timeline(obs_dir: str) -> list:
    """Incident-timeline kinds reconstructed from the journal ALONE —
    the acceptance bar for the deploy scenarios is that the whole
    deployment story replays from the obs artifacts."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    report, _ = obs_report.build_report(obs_dir)
    return [e["kind"] for e in report["incident_timeline"]]


def scenario_deploy_reject(root: str, steps: int, timeout: float) -> dict:
    obs_dir = os.path.join(root, "deploy_reject", "obs")
    r = _json_child(root, "deploy_reject", "--child-deploy-reject", timeout,
                    env={"MX_RCNN_OBS_DIR": obs_dir})
    assert not r["errors"] and not r["leaked_generations"], r
    assert r["fleet_generation"] == 0 and r["live_swaps"] == 0, r
    assert [d["outcome"] for d in r["decisions"]] == \
        ["invalid", "rejected"], r

    tl = _deploy_timeline(obs_dir)
    assert tl.count("deploy_candidate") == 2, tl
    assert tl.count("deploy_reject") == 2, tl
    assert "deploy_promote" not in tl, tl
    # The corrupt candidate died at the manifest (no shadow); the
    # regressed one went through a full shadow verdict first.
    assert tl.count("deploy_shadow_start") == 1, tl
    assert tl.index("deploy_shadow_verdict") < \
        len(tl) - tl[::-1].index("deploy_reject"), tl
    r["timeline"] = tl
    return r


def scenario_deploy_rollback(root: str, steps: int, timeout: float) -> dict:
    obs_dir = os.path.join(root, "deploy_rollback", "obs")
    r = _json_child(root, "deploy_rollback", "--child-deploy-rollback",
                    timeout, env={"MX_RCNN_OBS_DIR": obs_dir})
    assert not r["errors"] and r["bitwise_restore"], r
    assert r["to_generation"] > r["promoted_generation"], r
    assert r["pod_generations"] == [r["to_generation"]], r

    tl = _deploy_timeline(obs_dir)
    for kind in ("deploy_candidate", "deploy_shadow_start",
                 "deploy_shadow_verdict", "deploy_promote",
                 "slo_burn_start", "deploy_rollback"):
        assert kind in tl, (kind, tl)
    assert tl.index("deploy_promote") < tl.index("slo_burn_start"), tl
    assert tl.index("slo_burn_start") < tl.index("deploy_rollback"), tl
    r["timeline"] = tl
    return r


SCENARIOS = {
    "baseline": scenario_baseline,
    "sigkill": scenario_sigkill,
    "sigterm": scenario_sigterm,
    "nan": scenario_nan,
    "truncate": scenario_truncate,
    "data_worker_kill": scenario_data_worker_kill,
    "data_worker_wedge": scenario_data_worker_wedge,
    "cache_corrupt": scenario_cache_corrupt,
    "data_service_dead": scenario_data_service_dead,
    "eval_sigkill": scenario_eval_sigkill,
    "eval_corrupt": scenario_eval_corrupt,
    "overload": scenario_overload,
    "hang": scenario_hang,
    "replica_kill": scenario_replica_kill,
    "replica_wedge": scenario_replica_wedge,
    "tenant_starvation": scenario_tenant_starvation,
    "swap_under_load": scenario_swap_under_load,
    "fleet_drain": scenario_fleet_drain,
    "fleet_scale": scenario_fleet_scale,
    "host_kill": scenario_host_kill,
    "host_partition": scenario_host_partition,
    "cross_host_swap": scenario_cross_host_swap,
    "deploy_reject": scenario_deploy_reject,
    "deploy_rollback": scenario_deploy_rollback,
}

# Scenarios that restore/compare against baseline's checkpoint.
NEEDS_BASELINE = {
    "sigkill", "sigterm", "truncate", "eval_sigkill", "eval_corrupt",
    "data_worker_kill", "data_worker_wedge", "cache_corrupt",
    "data_service_dead",
}

# Scenarios that exercise the threaded serving plane: `--lockcheck auto`
# (the default) runs these with MX_RCNN_LOCKCHECK=1 so every child —
# including the fabric's per-host subprocesses, which inherit the
# environment — gets instrumented locks.  The sanitizer is deliberately
# NOT defaulted on for the training scenarios: their children assert
# bitwise-exact resume, and instrumentation has no business there.
LOCKCHECK_SCENARIOS = {
    "overload", "hang", "replica_kill", "replica_wedge",
    "tenant_starvation", "swap_under_load", "fleet_drain", "fleet_scale",
    "host_kill", "host_partition", "cross_host_swap",
    "deploy_reject", "deploy_rollback",
}


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "--child":
        rest = argv[2:] if argv[1:2] == ["--"] else argv[1:]
        return child_main(rest)
    if argv and argv[0] == "--child-eval":
        rest = argv[2:] if argv[1:2] == ["--"] else argv[1:]
        return child_eval_main(rest)
    if argv and argv[0] == "--child-overload":
        return child_overload_main()
    if argv and argv[0] == "--child-hang":
        return child_hang_main()
    if argv and argv[0] == "--child-replica-kill":
        return child_replica_kill_main()
    if argv and argv[0] == "--child-replica-wedge":
        return child_replica_wedge_main()
    if argv and argv[0] == "--child-tenant-starvation":
        return child_tenant_starvation_main()
    if argv and argv[0] == "--child-swap":
        return child_swap_main()
    if argv and argv[0] == "--child-fleet-drain":
        return child_fleet_drain_main()
    if argv and argv[0] == "--child-fleet-scale":
        return child_fleet_scale_main()
    if argv and argv[0] == "--child-host-kill":
        return child_host_kill_main()
    if argv and argv[0] == "--child-host-partition":
        return child_host_partition_main()
    if argv and argv[0] == "--child-cross-host-swap":
        return child_cross_host_swap_main()
    if argv and argv[0] == "--child-deploy-reject":
        return child_deploy_reject_main()
    if argv and argv[0] == "--child-deploy-rollback":
        return child_deploy_rollback_main()
    if argv and argv[0] == "--compare":
        return compare_main(argv[1], argv[2])

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--scenario", default="all",
                   help="'all', one scenario name, or a comma-separated "
                        "list (baseline is prepended automatically when a "
                        "listed scenario needs it)")
    p.add_argument("--steps", type=int, default=12)
    p.add_argument("--workdir", default=None,
                   help="scratch root (default: a fresh temp dir)")
    p.add_argument("--keep", action="store_true",
                   help="keep the scratch root for inspection")
    p.add_argument("--timeout", type=float, default=900.0,
                   help="per-child wall clock budget (seconds)")
    p.add_argument("--scenario-timeout", type=float, default=None,
                   help="hard per-scenario budget; on expiry every live "
                        "child is SIGKILLed and the scenario is marked "
                        "failed (default: 1.5 x --timeout)")
    p.add_argument("--lockcheck", choices=("auto", "on", "off"),
                   default="auto",
                   help="run children under the runtime lock-order "
                        "sanitizer (MX_RCNN_LOCKCHECK=1): 'auto' enables "
                        "it for the fleet/fabric scenarios, 'on'/'off' "
                        "force it everywhere/nowhere")
    args = p.parse_args(argv)
    scenario_timeout = args.scenario_timeout or 1.5 * args.timeout

    root = args.workdir or tempfile.mkdtemp(prefix="mx_rcnn_chaos_")
    if args.scenario == "all":
        names = list(SCENARIOS)
    else:
        names = [n.strip() for n in args.scenario.split(",") if n.strip()]
        unknown = [n for n in names if n not in SCENARIOS]
        if unknown:
            p.error(f"unknown scenario(s) {unknown}; "
                    f"known: {', '.join(SCENARIOS)}")
    # Recovery scenarios restore/compare baseline's checkpoint; pure
    # engine scenarios (overload/hang) don't pay for a training run.
    if "baseline" not in names and NEEDS_BASELINE & set(names):
        names.insert(0, "baseline")

    results: dict[str, dict] = {}
    failed = []
    for name in names:
        t0 = time.monotonic()
        # Env (not argv) so it reaches every process a scenario spawns,
        # transitively: _json_child children, Child-managed servers, and
        # the fabric hosts they fork in turn.
        lockcheck_on = args.lockcheck == "on" or (
            args.lockcheck == "auto" and name in LOCKCHECK_SCENARIOS
        )
        if lockcheck_on:
            os.environ["MX_RCNN_LOCKCHECK"] = "1"
        else:
            os.environ.pop("MX_RCNN_LOCKCHECK", None)
        # Hard backstop above the per-child timeout: a scenario whose
        # orchestration half wedges (not just the child) gets its entire
        # process tree reaped rather than hanging the suite.
        timed_out = threading.Event()
        timer = threading.Timer(
            scenario_timeout,
            lambda: (timed_out.set(), reap_orphans()),
        )
        timer.daemon = True
        timer.start()
        try:
            r = SCENARIOS[name](root, args.steps, args.timeout)
            r["ok"] = True
        except (AssertionError, Exception) as e:  # noqa: BLE001 - report all
            err = f"{type(e).__name__}: {e}"
            if timed_out.is_set():
                err = (f"scenario timed out after {scenario_timeout:.0f}s "
                       f"(children reaped); {err}")
            r = {"ok": False, "error": err}
            failed.append(name)
        finally:
            timer.cancel()
            leaked = reap_orphans()
            if leaked:
                print(f"[chaos] {name}: reaped {leaked} leftover "
                      f"subprocess(es)", file=sys.stderr)
        r["seconds"] = round(time.monotonic() - t0, 1)
        results[name] = r
        print(f"[chaos] {name}: {r}", file=sys.stderr)
        if name == "baseline" and not r["ok"]:
            break  # nothing to compare against
    os.environ.pop("MX_RCNN_LOCKCHECK", None)
    print(json.dumps({"root": root, "steps": args.steps, "results": results}))
    if not args.keep and not failed:
        shutil.rmtree(root, ignore_errors=True)
    elif failed:
        print(f"[chaos] artifacts kept at {root}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
