"""CPU-only: one serving-fabric process — a host (fleet + RPC + gossip) or
the pod gateway — of a harness that runs several at once, and a chip
belongs to one process at a time.

Host mode builds a real FleetRouter (tiny model, random params, hermetic
CPU with fake devices), exports it over the stdlib RPC surface
(serve/rpc.py), and joins the health gossip mesh.  Gateway mode runs a
GatewayRouter over ``--targets`` and exports the SAME RPC surface, so
callers (tools/loadgen.py --gateway, the chaos harness) speak one
protocol to a host or to the whole pod.

Readiness is announced on stdout (parents parse these lines):

    HOST_READY host_id=hostA port=41327 pid=12345
    GATEWAY_READY port=41901 pid=12346

Shutdown: SIGTERM (or POST /rpc/drain) drains the local fleet — stop
admitting, finish accepted work — then exits
``RESUMABLE_EXIT_CODE`` (75), the train/preemption.py convention, so a
supervisor restarts the host and gossip's incarnation numbers retire
the old identity.  While draining, ``/readyz`` answers 503 so balancers
stop sending work before the process goes away.

Usage (2-host fleet + gateway on one machine, all ephemeral ports):

    python tools/serve_host.py --host-id hostA --devices 2 --replicas 2
    python tools/serve_host.py --host-id hostB --devices 2 --replicas 2 \\
        --peers hostA=127.0.0.1:<portA>
    python tools/serve_host.py --gateway \\
        --targets 127.0.0.1:<portA>,127.0.0.1:<portB>
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import signal
import sys
import threading

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

log = logging.getLogger("serve_host")


def _hermetic_cpu(n_devices: int) -> None:
    """CPU-only jax with ``n_devices`` fake devices.  Must run before the
    first jax import (the XLA flag is read at backend init)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    from mx_rcnn_tpu.utils.compile_cache import configure_cache

    configure_cache()


def _parse_peers(spec: str) -> dict:
    """``hostA=127.0.0.1:1234,hostB=...`` -> {host_id: addr}."""
    peers = {}
    for item in (spec or "").split(","):
        item = item.strip()
        if not item:
            continue
        host_id, _, addr = item.partition("=")
        if not addr:
            raise ValueError(f"--peers wants host=addr, got {item!r}")
        peers[host_id] = addr
    return peers


def run_host(args: argparse.Namespace) -> int:
    _hermetic_cpu(args.devices)
    from mx_rcnn_tpu import obs
    from mx_rcnn_tpu.config import get_config
    from mx_rcnn_tpu.serve import GossipNode, HostRpcServer, build_fleet
    from mx_rcnn_tpu.train.preemption import RESUMABLE_EXIT_CODE

    cfg = get_config(args.config)
    fab = cfg.fabric
    if args.obs_dir:
        obs.configure(args.obs_dir, metrics_port=args.metrics_port)
        obs.install_crash_handler()

    import jax
    from mx_rcnn_tpu.detection import TwoStageDetector, init_detector

    variables = init_detector(
        TwoStageDetector(cfg=cfg.model), jax.random.PRNGKey(args.seed),
        cfg.data.image_size,
    )
    fleet = build_fleet(
        cfg, variables, args.replicas,
        engine_kwargs={"hang_timeout": 300.0, "max_queue": args.max_queue},
        supervisor_poll=0.1,
    )
    print(f"[{args.host_id}] warming {args.replicas} replica(s)...",
          file=sys.stderr, flush=True)
    fleet.start()

    done = threading.Event()
    drain_ok = {"ok": True}

    def on_drain(ok: bool) -> None:
        drain_ok["ok"] = ok
        done.set()

    server = HostRpcServer(
        fleet, args.host_id, port=args.port,
        weights_template=variables, on_drain=on_drain,
    )

    def snapshot() -> dict:
        s = fleet.stats()
        reps = max(1, int(s.get("replicas", 1)))
        return {
            "generation": s.get("generation", 0),
            "load": float(s.get("pending", 0)) / reps,
            "routable": reps,
            "draining": bool(s.get("draining")),
        }

    gossip = GossipNode(
        args.host_id, server.addr, snapshot,
        peers=_parse_peers(args.peers),
        period_s=fab.gossip_period_s,
        suspect_after_s=fab.suspect_after_s,
        dead_after_s=fab.dead_after_s,
    )
    server.gossip = gossip
    server.incarnation = gossip.incarnation
    server.start()
    gossip.start()
    obs.register_status("fleet", fleet.stats)
    obs.register_status("gossip", gossip.snapshot)

    scaler = None
    if args.autoscale:
        from mx_rcnn_tpu.config import CtrlConfig
        from mx_rcnn_tpu.ctrl.autoscale import Autoscaler, ScalePolicy

        # Pod-aggregated signals: this host scales on gossip's view of
        # the whole pod, not just its own queue.
        scaler = Autoscaler(
            fleet, ScalePolicy.from_config(CtrlConfig()),
            pod_view=gossip.aggregate,
        ).start(period_s=1.0)

    def on_sigterm(signum, frame) -> None:
        del signum, frame
        threading.Thread(
            target=lambda: on_drain(fleet.drain(args.drain_timeout)),
            daemon=True,
        ).start()

    signal.signal(signal.SIGTERM, on_sigterm)
    print(
        f"HOST_READY host_id={args.host_id} port={server.port} "
        f"pid={os.getpid()}",
        flush=True,
    )
    done.wait()
    if scaler is not None:
        scaler.stop()
    gossip.close()
    server.close()
    fleet.stop(timeout=60.0)
    print(json.dumps({
        "host_id": args.host_id, "drained": drain_ok["ok"],
        "stats": {
            k: v for k, v in fleet.stats().items() if k != "replica"
        },
    }), flush=True)
    if args.obs_dir:
        obs.close()
    return RESUMABLE_EXIT_CODE


def run_gateway(args: argparse.Namespace) -> int:
    # The gateway holds no model and runs no device code, but jax may be
    # imported transitively — keep it hermetic and CPU-only anyway.
    _hermetic_cpu(1)
    from mx_rcnn_tpu import obs
    from mx_rcnn_tpu.config import get_config
    from mx_rcnn_tpu.serve import GatewayRouter, GossipNode, HostRpcServer

    cfg = get_config(args.config)
    fab = cfg.fabric
    if args.obs_dir:
        obs.configure(args.obs_dir, metrics_port=args.metrics_port)
        obs.install_crash_handler()

    targets = [t.strip() for t in args.targets.split(",") if t.strip()]
    gossip = GossipNode(
        "gateway", "", lambda: {"draining": True},
        peers={addr: addr for addr in targets},
        period_s=fab.gossip_period_s,
        suspect_after_s=fab.suspect_after_s,
        dead_after_s=fab.dead_after_s,
    )
    gateway = GatewayRouter(
        targets,
        hedge_after=(
            args.hedge_after if args.hedge_after and args.hedge_after > 0
            else None
        ),
        max_attempts=fab.max_attempts,
        quarantine_failures=fab.quarantine_failures,
        probe_interval_s=fab.probe_interval_s,
        gossip=gossip,
    )
    gateway.start()
    gossip.start()
    server = HostRpcServer(gateway, "gateway", port=args.port,
                           gossip=gossip)
    server.start()
    obs.register_status("gateway", gateway.stats)
    obs.register_status("gossip", gossip.snapshot)

    done = threading.Event()

    def on_sigterm(signum, frame) -> None:
        del signum, frame
        threading.Thread(
            target=lambda: (gateway.drain(args.drain_timeout), done.set()),
            daemon=True,
        ).start()

    signal.signal(signal.SIGTERM, on_sigterm)
    print(f"GATEWAY_READY port={server.port} pid={os.getpid()}",
          flush=True)
    done.wait()
    gossip.close()
    server.close()
    gateway.stop()
    print(json.dumps({"gateway": gateway.stats()}), flush=True)
    if args.obs_dir:
        obs.close()
    return 0


def main(argv=None) -> int:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--gateway", action="store_true",
                   help="run the pod gateway instead of a host fleet")
    p.add_argument("--host-id", default="host0")
    p.add_argument("--config", default="tiny_synthetic")
    p.add_argument("--seed", type=int, default=0,
                   help="weight init seed (hosts in one pod MUST share "
                        "it, or responses differ by host)")
    p.add_argument("--replicas", type=int, default=2)
    p.add_argument("--devices", type=int, default=None,
                   help="fake CPU devices (default: --replicas)")
    p.add_argument("--port", type=int, default=0,
                   help="RPC bind port (0 = ephemeral, announced on "
                        "the READY line)")
    p.add_argument("--peers", default="",
                   help="host mode: hostA=addr,hostB=addr gossip seeds")
    p.add_argument("--targets", default="",
                   help="gateway mode: comma-separated host addrs")
    p.add_argument("--hedge-after", type=float, default=0.0,
                   help="gateway: seconds before a cross-host hedge "
                        "(0 = no hedging)")
    p.add_argument("--max-queue", type=int, default=64)
    p.add_argument("--autoscale", action="store_true",
                   help="host mode: run the autoscaler with "
                        "pod-aggregated gossip signals")
    p.add_argument("--drain-timeout", type=float, default=30.0)
    p.add_argument("--obs-dir", default=None)
    p.add_argument("--metrics-port", type=int, default=0)
    args = p.parse_args(argv)
    if args.devices is None:
        args.devices = max(args.replicas, 1)
    if args.gateway:
        if not args.targets:
            p.error("--gateway requires --targets")
        return run_gateway(args)
    return run_host(args)


if __name__ == "__main__":
    sys.exit(main())
