"""Open-loop load generator for the serving fleet (BENCH_serving).

Drives a real :class:`~mx_rcnn_tpu.serve.fleet.FleetRouter` (tiny model,
random params, hermetic CPU with one fake device per replica) for a
fixed duration and reports the latency distribution over *completed*
requests plus the fleet's own counters.  Open-loop means arrivals are
scheduled on the wall clock, not gated on responses — a slow fleet falls
behind and the backlog shows up as shed requests and a fat tail, exactly
like production.

The arrival rate follows a ``--profile`` (shared with tools/soak.py via
:func:`make_profile`):

* ``constant`` — ``--qps`` throughout (the default; unchanged behavior).
* ``sine`` — a compressed diurnal curve: ``qps * (1 + amplitude *
  sin(2*pi*t/period))``, so the fleet sees a trough and a peak every
  ``--period`` seconds.
* ``spike`` — ``--qps`` baseline with a burst of ``qps *
  spike-factor`` for the first ``--duty`` fraction of every
  ``--period`` seconds: the autoscaler-rehearsal shape.

Optionally (``--kill-one``) a replica is killed at the midpoint, which
exercises quarantine -> rebuild -> reinstatement *under load*: the bench
passes only if accepted requests keep completing and p99 stays under the
``--assert-p99`` bound while a replica is out.

``--clients N`` switches to a CLOSED-loop shape instead: N concurrent
small clients each submit one request, wait for its response, and
immediately submit the next — the many-small-callers traffic that
cross-request packing (``--batch-size > 1``) exists for.  The
BENCH_serving line always reports batch occupancy (mean + p50 over
device calls) and ``sustained_qps_per_replica``; ``--assert-occupancy``
gates on the mean.

``--tenants 'victim:weight=4,qps=5;flood:rate=6,burst=4,qps=30,role=flooder'``
runs one open-loop schedule PER TENANT: each entry names a tenant, its
offered ``qps`` (plus an optional per-tenant ``profile``), its policy
knobs (``weight/rate/burst/priority`` — forwarded into
``serve.tenancy.table`` when driving a local fleet), and an optional
``role=flooder`` marker.  Every request carries its tenant token;
``QuotaExceeded`` rejections are counted per tenant as ``quota``
(distinct from ``shed``), and the BENCH line gains a per-tenant table.
``--assert-tenant-isolation FACTOR`` runs a flooder-free baseline phase
first and exits nonzero unless every non-flooder tenant's p99 in the
full mix stays within FACTOR of its solo baseline (noisy-neighbor
isolation, docs/serving.md).

``--targets hostA:port,hostB:port`` swaps the local fleet for an
in-process :class:`~mx_rcnn_tpu.serve.gateway.GatewayRouter` over REAL
host processes (tools/serve_host.py), and ``--gateway URL`` drives a
remote fabric endpoint over RPC — same schedule, same BENCH line, with
``hosts`` listing every host that served traffic (``["local"]`` for the
single-process default).

The driven router carries a content-addressed result cache by default
(``--result-cache N`` capacity, 0 disables; see serve/result_cache.py):
duplicate images are answered without a device call and identical
in-flight requests coalesce onto one.  ``--dup-frac F`` makes that
fraction of arrivals re-send one hot image to rehearse duplicate-heavy
traffic; the BENCH line reports ``cache_hits`` and ``coalesced``.

Prints diagnostics to stderr and exactly one ``BENCH_serving`` JSON line
as the LAST line on stdout:

    {"bench": "serving", "platform": "cpu", "device_kind": "cpu",
     "n_devices": 2, "replicas": 2, "hosts": ["local"],
     "qps": 6.0, "duration_s": 15.0,
     "submitted": 90, "completed": 88, "shed": 2, "failed": 0,
     "p50_s": 0.21, "p99_s": 0.57, "max_s": 0.61,
     "killed_rid": 0, "quarantines": 1, "reinstatements": 1,
     "hedges": 0, "retries": 1, "generation": 0}

Usage:
    XLA_FLAGS=--xla_force_host_platform_device_count=2 \\
    JAX_PLATFORMS=cpu python tools/loadgen.py \\
        --replicas 2 --qps 6 --duration 15 --kill-one --assert-p99 60
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import threading
import time
from typing import Callable

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PROFILES = ("constant", "sine", "spike")


def make_profile(
    name: str,
    qps: float,
    *,
    amplitude: float = 0.5,
    period_s: float = 60.0,
    spike_factor: float = 4.0,
    duty: float = 0.15,
) -> Callable[[float], float]:
    """Arrival-rate schedule ``rate(t_elapsed) -> req/s``.

    Shared by the loadgen CLI and the soak harness so both rehearse the
    same traffic shapes.  Rates are floored at a small positive value —
    an open loop with rate exactly 0 would never schedule the next
    arrival and the clock math below divides by it.
    """
    if qps <= 0:
        raise ValueError("qps must be > 0")
    if name == "constant":
        return lambda t: qps
    if name == "sine":
        if not 0.0 <= amplitude <= 1.0:
            raise ValueError("amplitude must be in [0, 1]")
        return lambda t: max(
            0.05, qps * (1.0 + amplitude * math.sin(2 * math.pi * t / period_s))
        )
    if name == "spike":
        if not 0.0 < duty < 1.0:
            raise ValueError("duty must be in (0, 1)")
        return lambda t: (
            qps * spike_factor if (t % period_s) < duty * period_s else qps
        )
    raise ValueError(f"unknown profile {name!r} (want one of {PROFILES})")


_TENANT_POLICY_KEYS = ("weight", "rate", "burst", "priority")


def parse_tenant_load_spec(spec: str) -> list[dict]:
    """``--tenants`` entries: ``name:k=v,...;name2:...`` where the keys
    are the ``serve.tenancy`` policy knobs plus the load-side ``qps``,
    ``profile`` and ``role`` (``role=flooder`` marks the adversary the
    isolation gate excludes from its baseline).  Shared with
    tools/soak.py so both rehearse the same tenant mixes.
    """
    out: list[dict] = []
    for part in spec.split(";"):
        part = part.strip()
        if not part:
            continue
        name, _, kvs = part.partition(":")
        name = name.strip()
        if not name:
            raise ValueError(f"tenant entry missing a name: {part!r}")
        ent = {"name": name, "qps": None, "profile": "constant",
               "role": "normal", "policy": {}}
        for kv in kvs.split(","):
            kv = kv.strip()
            if not kv:
                continue
            key, sep, val = kv.partition("=")
            key, val = key.strip(), val.strip()
            if not sep:
                raise ValueError(f"tenant {name!r}: malformed knob {kv!r}")
            if key == "qps":
                ent["qps"] = float(val)
            elif key == "profile":
                if val not in PROFILES:
                    raise ValueError(
                        f"tenant {name!r}: unknown profile {val!r}"
                    )
                ent["profile"] = val
            elif key == "role":
                ent["role"] = val
            elif key in _TENANT_POLICY_KEYS:
                ent["policy"][key] = val
            else:
                raise ValueError(
                    f"tenant {name!r}: unknown knob {key!r} (expected "
                    f"qps/profile/role or one of {_TENANT_POLICY_KEYS})"
                )
        out.append(ent)
    if not out:
        raise ValueError("empty --tenants spec")
    if len({e["name"] for e in out}) != len(out):
        raise ValueError("duplicate tenant name in --tenants spec")
    return out


def tenant_table_string(specs: list[dict]) -> str:
    """Rebuild the ``serve.tenancy.table`` string from parsed entries
    (policy knobs only — qps/profile/role are load-side)."""
    return ";".join(
        e["name"] + ":" + ",".join(
            f"{k}={v}" for k, v in e["policy"].items()
        )
        for e in specs
    )


def _fake_cpu_devices(n_devices: int) -> None:
    """One fake device per replica — only on a CPU the caller NAMED
    (``JAX_PLATFORMS=cpu``: tests and CI).  With nothing said the fleet
    runs on what jax finds, one replica per chip, and the BENCH line says
    which (``platform`` / ``device_kind`` / ``n_devices``)."""
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n_devices}"
        ).strip()


def _percentile(sorted_vals: list[float], q: float) -> float:
    """Nearest-rank percentile over an already-sorted list."""
    if not sorted_vals:
        return float("nan")
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


class _RemoteFuture:
    """FleetRequest-shaped handle over one remote RPC inference."""

    def __init__(self) -> None:
        self._event = threading.Event()
        self._result = None
        self._error: BaseException | None = None

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout=None):
        if not self._event.wait(timeout):
            raise TimeoutError("remote request not complete")
        if self._error is not None:
            raise self._error
        return self._result


class _RemoteGateway:
    """FleetRouter-shaped driver for a REMOTE fabric endpoint
    (``--gateway URL``): submit/stats/stop over serve/rpc.py's client,
    each submit running its blocking RPC on a daemon thread."""

    def __init__(self, url: str) -> None:
        from mx_rcnn_tpu.serve import RpcClient

        self.client = RpcClient(url)

    def submit(self, image, timeout=None, trace_id=None,
               tenant=None) -> _RemoteFuture:
        fut = _RemoteFuture()

        def run() -> None:
            try:
                fut._result = self.client.infer(
                    image, deadline_s=timeout, trace_id=trace_id,
                    tenant=tenant,
                )
            except BaseException as e:  # noqa: BLE001 - carried to result()
                fut._error = e
            finally:
                fut._event.set()

        threading.Thread(target=run, daemon=True).start()
        return fut

    def stats(self) -> dict:
        return self.client.stats()["fleet"]

    def stop(self, timeout=None) -> None:
        del timeout


def _build_driver(args, cfg):
    """(fleet-shaped driver, hosts list) for the three serving surfaces:
    a local FleetRouter (default), an in-process GatewayRouter over
    ``--targets``, or a remote fabric endpoint via ``--gateway URL``."""
    if args.gateway:
        drv = _RemoteGateway(args.gateway)
        stats = drv.stats()  # fail fast when the endpoint is down
        hosts = sorted(stats.get("hosts", {})) or [args.gateway]
        print(f"[loadgen] driving remote gateway {args.gateway} "
              f"(hosts: {', '.join(hosts)})", file=sys.stderr)
        return drv, hosts
    if args.targets:
        from mx_rcnn_tpu.serve import GatewayRouter, ResultCache

        targets = [t.strip() for t in args.targets.split(",") if t.strip()]
        gw = GatewayRouter(
            targets, hedge_after=None, probe_interval_s=0.25,
            result_cache=(
                ResultCache(capacity=args.result_cache)
                if args.result_cache > 0 else None
            ),
        ).start()
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            if gw.stats()["replicas"] >= len(targets):
                break
            time.sleep(0.1)
        stats = gw.stats()
        if stats["replicas"] == 0:
            raise RuntimeError(f"no routable host among {targets}")
        hosts = sorted(stats["hosts"])
        print(f"[loadgen] gateway over {len(hosts)} host(s): "
              f"{', '.join(hosts)} ({stats['replicas']} routable)",
              file=sys.stderr)
        return gw, hosts
    return None, ["local"]


def run_bench(args: argparse.Namespace) -> dict:
    import numpy as np

    import jax
    from mx_rcnn_tpu.config import apply_overrides, get_config
    from mx_rcnn_tpu.detection import TwoStageDetector, init_detector
    from mx_rcnn_tpu.serve import (
        Overloaded, QuotaExceeded, ServeError, build_fleet,
    )

    from mx_rcnn_tpu import obs

    obs_on = bool(args.obs_dir)
    if obs_on:
        # Durable plane: journal + per-request spans under --obs-dir and
        # (optionally) a live /metrics endpoint to scrape mid-run.
        obs.configure(
            args.obs_dir, metrics_port=args.metrics_port, flush_s=5.0
        )
        print(f"[loadgen] obs: run_id={obs.run_id()} dir={obs.out_dir()} "
              f"metrics_port={obs.metrics_port()}", file=sys.stderr)

    cfg = get_config(args.config)
    tenant_specs = getattr(args, "_tenant_specs", None)
    if tenant_specs:
        # A local fleet enforces the tenant table itself; fabric modes
        # only carry the tokens (the remote hosts own their policy).
        cfg = apply_overrides(cfg, [
            "serve.tenancy.enabled=true",
            f"serve.tenancy.table={tenant_table_string(tenant_specs)}",
        ])
    fleet, hosts = _build_driver(args, cfg)
    # Remote surfaces are tools/serve_host.py processes: CPU-only.
    args._device = {"platform": "cpu", "device_kind": "cpu", "n_devices": None}
    if fleet is None:
        from mx_rcnn_tpu.utils.compile_cache import configure_cache
        from mx_rcnn_tpu.utils.runtime import device_record

        configure_cache()
        args._device = device_record()
        variables = init_detector(
            TwoStageDetector(cfg=cfg.model), jax.random.PRNGKey(0),
            cfg.data.image_size,
        )
        from mx_rcnn_tpu.serve import ResultCache

        fleet = build_fleet(
            cfg, variables, args.replicas,
            batch_size=args.batch_size,
            engine_kwargs={
                "hang_timeout": 300.0, "max_queue": args.max_queue,
                "pack": not args.no_pack, "pack_window_s": args.pack_window,
            },
            supervisor_poll=0.1,
            hedge_after="auto",
            result_cache=(
                ResultCache(capacity=args.result_cache)
                if args.result_cache > 0 else None
            ),
        )
        print(f"[loadgen] starting {args.replicas} replica(s) "
              f"(warmup compiles)...", file=sys.stderr)
        fleet.start()
        print("[loadgen] fleet ready", file=sys.stderr)
    args._hosts = hosts
    if obs_on:
        obs.register_status("fleet", fleet.stats)

    rng = np.random.default_rng(0)
    h, w = cfg.data.image_size
    images = [rng.integers(0, 255, (h, w, 3), dtype=np.uint8)
              for _ in range(4)]

    def pick_image(i: int, base: int):
        # --dup-frac: that fraction of arrivals re-send one hot image
        # (duplicate-heavy traffic: retry storms, hot thumbnails), evenly
        # interleaved so dups overlap in flight; the rest cycle the
        # distinct pool as before.
        f = args.dup_frac
        if f > 0.0 and math.floor((i + 1) * f) > math.floor(i * f):
            return images[0]
        return images[base % len(images)]

    lock = threading.Lock()
    latencies: list[float] = []
    submitted = shed = quota = failed = 0
    pending: list = []
    tstats: dict[str, dict] = {
        e["name"]: {"submitted": 0, "shed": 0, "quota": 0, "failed": 0,
                    "lat": []}
        for e in (tenant_specs or [])
    }

    def collect(freq, t_submit: float, tenant: str | None = None) -> None:
        nonlocal shed, quota, failed
        ts = tstats.get(tenant)
        try:
            freq.result(timeout=args.deadline + 60.0)
        except QuotaExceeded:
            # The tenant's own budget, not fleet pressure — kept apart
            # from shed on both the global and per-tenant rows.
            with lock:
                quota += 1
                if ts is not None:
                    ts["quota"] += 1
            return
        except Overloaded:
            # Fabric modes surface admission-control shedding at result
            # time (the remote 429 comes back on the response path).
            with lock:
                shed += 1
                if ts is not None:
                    ts["shed"] += 1
            return
        except ServeError:
            with lock:
                failed += 1
                if ts is not None:
                    ts["failed"] += 1
            return
        lat = time.monotonic() - t_submit
        with lock:
            latencies.append(lat)
            if ts is not None:
                ts["lat"].append(lat)

    killed_rid = None
    if args.clients > 0:
        # Closed loop: N concurrent small clients, each waiting for its
        # response before submitting again — per-caller concurrency is 1,
        # so only CROSS-request packing can fill a micro-batch.
        t0 = time.monotonic()
        deadline_wall = t0 + args.duration
        kill_lock = threading.Lock()

        def client(ci: int) -> None:
            nonlocal submitted, shed, failed, killed_rid
            sent = 0
            while True:
                now = time.monotonic()
                if now >= deadline_wall:
                    return
                if args.kill_one and now - t0 >= args.duration / 2.0:
                    with kill_lock:
                        if killed_rid is None:
                            killed_rid = 0
                            fleet.kill_replica(0, "loadgen --kill-one")
                            print(f"[loadgen] killed replica 0 at "
                                  f"t={now - t0:.1f}s", file=sys.stderr)
                trace_id = obs.new_trace_id() if obs_on else None
                sent += 1
                try:
                    freq = fleet.submit(
                        pick_image(sent, ci),
                        timeout=args.deadline, trace_id=trace_id,
                    )
                except Overloaded:
                    with lock:
                        submitted += 1
                        shed += 1
                    time.sleep(0.01)
                    continue
                except ServeError as e:
                    with lock:
                        submitted += 1
                        failed += 1
                    print(f"[loadgen] submit failed: {e}", file=sys.stderr)
                    time.sleep(0.05)
                    continue
                with lock:
                    submitted += 1
                try:
                    freq.result(timeout=args.deadline + 60.0)
                except Overloaded:
                    with lock:
                        shed += 1
                    continue
                except ServeError:
                    with lock:
                        failed += 1
                    continue
                with lock:
                    latencies.append(time.monotonic() - now)

        clients = [
            threading.Thread(target=client, args=(ci,), daemon=True)
            for ci in range(args.clients)
        ]
        for t in clients:
            t.start()
        for t in clients:
            t.join(timeout=args.duration + args.deadline + 120.0)
        return _finish(args, fleet, latencies, submitted, shed, failed,
                       killed_rid, obs_on)
    if tenant_specs:
        # One open-loop schedule per tenant: each tenant's arrivals are
        # clocked independently at its own qps/profile, so the flooder
        # falling behind (or bouncing off its quota) never slows the
        # victims' offered load.
        t0 = time.monotonic()
        deadline_wall = t0 + args.duration
        n_tenants = len(tenant_specs)

        def tenant_loop(ent: dict) -> None:
            nonlocal submitted, shed, quota, failed
            name = ent["name"]
            ts = tstats[name]
            rate = make_profile(
                ent["profile"],
                ent["qps"] if ent["qps"] else max(args.qps / n_tenants, 0.1),
                amplitude=args.amplitude, period_s=args.period,
                spike_factor=args.spike_factor, duty=args.duty,
            )
            next_at = t0
            sent = 0
            while True:
                now = time.monotonic()
                if now >= deadline_wall:
                    return
                if now < next_at:
                    time.sleep(min(next_at - now, 0.05))
                    continue
                next_at += 1.0 / rate(now - t0)
                trace_id = obs.new_trace_id() if obs_on else None
                sent += 1
                try:
                    freq = fleet.submit(
                        pick_image(sent, sent), timeout=args.deadline,
                        trace_id=trace_id, tenant=name,
                    )
                except QuotaExceeded:
                    with lock:
                        submitted += 1
                        quota += 1
                        ts["submitted"] += 1
                        ts["quota"] += 1
                    continue
                except Overloaded:
                    with lock:
                        submitted += 1
                        shed += 1
                        ts["submitted"] += 1
                        ts["shed"] += 1
                    continue
                except ServeError as e:
                    with lock:
                        submitted += 1
                        failed += 1
                        ts["submitted"] += 1
                        ts["failed"] += 1
                    print(f"[loadgen] {name}: submit failed: {e}",
                          file=sys.stderr)
                    continue
                with lock:
                    submitted += 1
                    ts["submitted"] += 1
                t = threading.Thread(
                    target=collect, args=(freq, now, name), daemon=True
                )
                t.start()
                pending.append(t)

        loops = [
            threading.Thread(target=tenant_loop, args=(e,), daemon=True)
            for e in tenant_specs
        ]
        for t in loops:
            t.start()
        for t in loops:
            t.join(timeout=args.duration + 120.0)
        for t in list(pending):
            t.join(timeout=args.deadline + 120.0)
        return _finish(args, fleet, latencies, submitted, shed, failed,
                       killed_rid, obs_on, quota=quota, tstats=tstats,
                       tenant_specs=tenant_specs)
    rate = make_profile(
        args.profile, args.qps,
        amplitude=args.amplitude, period_s=args.period,
        spike_factor=args.spike_factor, duty=args.duty,
    )
    t0 = time.monotonic()
    next_at = t0
    deadline_wall = t0 + args.duration
    while True:
        now = time.monotonic()
        if now >= deadline_wall:
            break
        if now < next_at:
            time.sleep(min(next_at - now, 0.05))
            continue
        # Open loop: the schedule advances whether or not this arrival
        # is admitted, so a slow fleet accumulates lateness (and sheds)
        # instead of quietly throttling the offered load.  The interval
        # is re-derived from the profile each arrival, so sine/spike
        # shapes modulate inter-arrival gaps, not batch sizes.
        next_at += 1.0 / rate(now - t0)
        if args.kill_one and killed_rid is None and \
                now - t0 >= args.duration / 2.0:
            killed_rid = 0
            fleet.kill_replica(0, "loadgen --kill-one")
            print(f"[loadgen] killed replica 0 at "
                  f"t={now - t0:.1f}s", file=sys.stderr)
        # Every synthetic request carries its own trace id; with --obs-dir
        # the whole span tree (request -> attempt -> engine queue/device)
        # lands in <obs-dir>/spans.jsonl keyed by it.
        trace_id = obs.new_trace_id() if obs_on else None
        try:
            freq = fleet.submit(pick_image(submitted, submitted),
                                timeout=args.deadline, trace_id=trace_id)
        except QuotaExceeded:
            with lock:
                submitted += 1
                quota += 1
            continue
        except Overloaded:
            with lock:
                submitted += 1
                shed += 1
            continue
        except ServeError as e:
            with lock:
                submitted += 1
                failed += 1
            print(f"[loadgen] submit failed: {e}", file=sys.stderr)
            continue
        with lock:
            submitted += 1
        t = threading.Thread(target=collect, args=(freq, now), daemon=True)
        t.start()
        pending.append(t)

    for t in pending:
        t.join(timeout=args.deadline + 120.0)
    return _finish(args, fleet, latencies, submitted, shed, failed,
                   killed_rid, obs_on, quota=quota)


def _occupancy_summary() -> dict:
    """Aggregate the ``serve_batch_occupancy`` histogram across every
    replica/level series: device-call count, mean fill, p50 fill."""
    from mx_rcnn_tpu import obs
    from mx_rcnn_tpu.obs import metrics as metrics_mod

    snap = obs.histogram(
        "serve_batch_occupancy",
        "request slots filled / slots total per device call",
    ).snapshot()
    series = [s for s in snap.values() if s.get("count")]
    calls = sum(s["count"] for s in series)
    if not calls:
        return {"device_calls": 0, "mean": None, "p50": None}
    le = series[0]["le"]
    merged = [0] * len(le)
    for s in series:
        for i, c in enumerate(s["buckets"]):
            merged[i] += c
    return {
        "device_calls": calls,
        "mean": round(sum(s["sum"] for s in series) / calls, 4),
        "p50": round(
            metrics_mod.percentile_from_counts(le, merged, 0.50), 4
        ),
    }


def _finish(args, fleet, latencies, submitted, shed, failed, killed_rid,
            obs_on, quota=0, tstats=None, tenant_specs=None) -> dict:
    from mx_rcnn_tpu import obs

    stats = fleet.stats()
    # Generous stop budget: --kill-one leaves a background rebuild whose
    # warmup compile cannot be interrupted; stop() waits it out.
    fleet.stop(timeout=240.0)

    latencies.sort()
    host_detail = stats.get("hosts")
    hosts = (
        sorted(host_detail) if isinstance(host_detail, dict) and host_detail
        else list(getattr(args, "_hosts", ["local"]))
    )
    rec = {
        "bench": "serving",
        # Where the model ran: a record from a CPU is a count of requests,
        # never a latency of the chip.
        **args._device,
        "replicas": args.replicas,
        "hosts": hosts,
        "qps": args.qps,
        "profile": args.profile,
        "clients": args.clients,
        "batch_size": args.batch_size,
        "pack": not args.no_pack,
        "duration_s": args.duration,
        "submitted": submitted,
        "completed": len(latencies),
        "shed": shed,
        "quota": quota,
        "failed": failed,
        "sustained_qps_per_replica": round(
            len(latencies) / args.duration / max(args.replicas, 1), 3
        ),
        "p50_s": round(_percentile(latencies, 0.50), 4),
        "p99_s": round(_percentile(latencies, 0.99), 4),
        "max_s": round(max(latencies), 4) if latencies else float("nan"),
        "occupancy": _occupancy_summary(),
        "cache_hits": (stats.get("cache") or {}).get("hits", 0),
        "coalesced": (stats.get("cache") or {}).get("coalesced", 0),
        "killed_rid": killed_rid,
        "quarantines": stats["quarantines"],
        "reinstatements": stats["reinstatements"],
        "hedges": stats["hedges"],
        "retries": stats["retries"],
        "generation": stats["generation"],
    }
    if tstats is not None and tenant_specs is not None:
        roles = {e["name"]: e["role"] for e in tenant_specs}
        tenants = {}
        for name, ts in tstats.items():
            lat = sorted(ts["lat"])
            tenants[name] = {
                "role": roles.get(name, "normal"),
                "submitted": ts["submitted"],
                "completed": len(lat),
                "shed": ts["shed"],
                "quota": ts["quota"],
                "failed": ts["failed"],
                "p50_s": round(_percentile(lat, 0.50), 4),
                "p99_s": round(_percentile(lat, 0.99), 4),
            }
        rec["tenants"] = tenants
        if isinstance(stats.get("tenancy"), dict):
            rec["tenancy"] = stats["tenancy"]
    if obs_on:
        port = obs.metrics_port()
        if port is not None:
            # Self-scrape: prove the endpoint serves non-empty metrics
            # for the run we just generated.
            import urllib.request

            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5
            ).read().decode()
            n_series = sum(
                1 for ln in body.splitlines()
                if ln and not ln.startswith("#")
            )
            print(f"[loadgen] /metrics scrape: {n_series} series",
                  file=sys.stderr)
            rec["metrics_series"] = n_series
        rec["obs"] = {
            "run_id": obs.run_id(),
            "dir": obs.out_dir(),
            "journal": os.path.join(obs.out_dir(), "journal.jsonl"),
            "spans": os.path.join(obs.out_dir(), "spans.jsonl"),
        }
        obs.close()
    return rec


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--replicas", type=int, default=2)
    p.add_argument("--qps", type=float, default=6.0,
                   help="open-loop arrival rate (requests/second); the "
                        "baseline rate for non-constant profiles")
    p.add_argument("--profile", choices=PROFILES, default="constant",
                   help="traffic shape over the window (see module doc)")
    p.add_argument("--amplitude", type=float, default=0.5,
                   help="sine profile: fractional swing around --qps")
    p.add_argument("--period", type=float, default=60.0,
                   help="sine/spike profile: cycle length in seconds")
    p.add_argument("--spike-factor", type=float, default=4.0,
                   help="spike profile: burst rate as a multiple of --qps")
    p.add_argument("--duty", type=float, default=0.15,
                   help="spike profile: fraction of each period spent "
                        "bursting")
    p.add_argument("--duration", type=float, default=15.0,
                   help="load window in seconds")
    p.add_argument("--deadline", type=float, default=120.0,
                   help="per-request deadline in seconds")
    p.add_argument("--max-queue", type=int, default=64,
                   help="per-replica admission queue bound")
    p.add_argument("--clients", type=int, default=0,
                   help="closed-loop mode: this many concurrent "
                        "one-request-at-a-time clients instead of the "
                        "open-loop --qps schedule (0 = open loop)")
    p.add_argument("--batch-size", type=int, default=None,
                   help="per-replica micro-batch slots (device call "
                        "width); default follows cfg.serve.batch_size")
    p.add_argument("--no-pack", action="store_true",
                   help="disable continuous batching (one caller's "
                        "same-plan run per device call, as before)")
    p.add_argument("--pack-window", type=float, default=0.0,
                   help="seconds the worker lingers for stragglers to "
                        "top off a partial batch")
    p.add_argument("--config", default="tiny_synthetic")
    p.add_argument("--targets", default="",
                   help="drive an IN-PROCESS gateway over these "
                        "comma-separated host addrs (tools/serve_host.py "
                        "fleets) instead of a local fleet")
    p.add_argument("--gateway", default="",
                   help="drive a REMOTE fabric endpoint (gateway or "
                        "single host) at this base URL / addr")
    p.add_argument("--kill-one", action="store_true",
                   help="kill replica 0 at the midpoint of the window")
    p.add_argument("--result-cache", type=int, default=256,
                   help="content-addressed result cache capacity on the "
                        "driven router (0 disables; see docs/serving.md)")
    p.add_argument("--dup-frac", type=float, default=0.0,
                   help="fraction of arrivals that re-send one hot image "
                        "(duplicate-heavy traffic for the result cache)")
    p.add_argument("--tenants", default="",
                   help="per-tenant open-loop mix: 'name:qps=5,weight=4;"
                        "flood:qps=30,rate=6,role=flooder' — policy "
                        "knobs feed serve.tenancy.table on a local "
                        "fleet; see docs/serving.md")
    p.add_argument("--assert-tenant-isolation", type=float, default=None,
                   help="with --tenants: run a flooder-free baseline "
                        "first and exit nonzero unless every non-flooder "
                        "tenant's p99 in the full mix is within this "
                        "factor of its solo baseline")
    p.add_argument("--assert-p50", type=float, default=None,
                   help="exit nonzero unless p50 latency (s) is under "
                        "this bound")
    p.add_argument("--assert-p99", type=float, default=None,
                   help="exit nonzero unless p99 latency (s) is under "
                        "this bound and no accepted request failed")
    p.add_argument("--assert-occupancy", type=float, default=None,
                   help="exit nonzero unless mean batch occupancy "
                        "(slots filled / slots total per device call) "
                        "is at least this bound")
    p.add_argument("--obs-dir", default=None,
                   help="write the obs journal, per-request span files "
                        "and flight dumps under this directory")
    p.add_argument("--metrics-port", type=int, default=0,
                   help="with --obs-dir: bind /metrics here (0 = "
                        "ephemeral, shown on stderr)")
    args = p.parse_args(argv)
    if args.targets and args.gateway:
        p.error("--targets and --gateway are mutually exclusive")
    if args.kill_one and (args.targets or args.gateway):
        p.error("--kill-one drives a LOCAL fleet; use tools/chaos.py "
                "host_kill for fabric-level failure injection")
    tenant_specs = None
    if args.tenants:
        if args.clients > 0 or args.kill_one:
            p.error("--tenants is an open-loop multi-tenant mix; it "
                    "composes with neither --clients nor --kill-one")
        try:
            tenant_specs = parse_tenant_load_spec(args.tenants)
        except ValueError as e:
            p.error(str(e))
        args._tenant_specs = tenant_specs
    if args.assert_tenant_isolation is not None:
        if not tenant_specs:
            p.error("--assert-tenant-isolation requires --tenants")
        if all(e["role"] != "flooder" for e in tenant_specs):
            p.error("--assert-tenant-isolation needs a role=flooder "
                    "tenant to remove in the baseline phase")
    _fake_cpu_devices(args.replicas)

    baseline = None
    if args.assert_tenant_isolation is not None:
        # Phase A: the same victims at the same rates, flooder removed
        # (and no obs plane — one journal per process).  Its record goes
        # to stderr only; the BENCH contract stays one-stdout-line.
        import copy

        base_args = copy.copy(args)
        base_args._tenant_specs = [
            e for e in tenant_specs if e["role"] != "flooder"
        ]
        base_args.obs_dir = None
        print("[loadgen] isolation baseline: flooder-free phase...",
              file=sys.stderr)
        baseline = run_bench(base_args)
        print(f"[loadgen] baseline record: {json.dumps(baseline)}",
              file=sys.stderr)

    rec = run_bench(args)
    if baseline is not None:
        rec["isolation"] = {
            "factor": args.assert_tenant_isolation,
            "baseline_p99_s": {
                name: t["p99_s"]
                for name, t in baseline["tenants"].items()
            },
        }
    print(json.dumps(rec))

    ok = True
    if rec["completed"] == 0:
        print("[loadgen] FAIL: no request completed", file=sys.stderr)
        ok = False
    if rec["failed"] != 0:
        print(f"[loadgen] FAIL: {rec['failed']} accepted request(s) "
              f"failed", file=sys.stderr)
        ok = False
    if args.kill_one and rec["quarantines"] < 1:
        print("[loadgen] FAIL: --kill-one but no quarantine observed",
              file=sys.stderr)
        ok = False
    if args.assert_p50 is not None and not rec["p50_s"] < args.assert_p50:
        print(f"[loadgen] FAIL: p50 {rec['p50_s']}s >= bound "
              f"{args.assert_p50}s", file=sys.stderr)
        ok = False
    if args.assert_p99 is not None and not rec["p99_s"] < args.assert_p99:
        print(f"[loadgen] FAIL: p99 {rec['p99_s']}s >= bound "
              f"{args.assert_p99}s", file=sys.stderr)
        ok = False
    if args.assert_occupancy is not None:
        mean_occ = rec["occupancy"]["mean"]
        if mean_occ is None or mean_occ < args.assert_occupancy:
            print(f"[loadgen] FAIL: mean batch occupancy {mean_occ} < "
                  f"bound {args.assert_occupancy}", file=sys.stderr)
            ok = False
    if args.assert_tenant_isolation is not None:
        factor = args.assert_tenant_isolation
        for name, t in rec["tenants"].items():
            if t["role"] == "flooder":
                continue
            if t["completed"] == 0:
                print(f"[loadgen] FAIL: tenant {name} completed nothing "
                      f"in the mix phase", file=sys.stderr)
                ok = False
                continue
            solo = rec["isolation"]["baseline_p99_s"].get(name)
            mix = t["p99_s"]
            # The 50 ms floor keeps sub-tick solo baselines from turning
            # scheduler noise into a flaky gate.
            if solo is None or not mix <= factor * max(solo, 0.05):
                print(f"[loadgen] FAIL: tenant {name} p99 {mix}s vs "
                      f"flooder-free baseline {solo}s exceeds factor "
                      f"{factor}", file=sys.stderr)
                ok = False
        if ok:
            print(f"[loadgen] tenant isolation HELD (factor {factor})",
                  file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
