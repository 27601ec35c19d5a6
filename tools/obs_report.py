"""Merge one run's observability artifacts into a single report.

Inputs (all optional — the report carries whatever exists):

* ``<obs-dir>/journal.jsonl``   — typed event journal (mx_rcnn_tpu.obs)
* ``<obs-dir>/spans.jsonl``     — finished spans, one Chrome-trace event
  per line
* ``<obs-dir>/flight_*.json``   — flight-recorder postmortem dumps
* ``--stage-log`` file(s)       — bench/chaos stdout with ``{"metric":
  ...}`` JSON lines (train_stage_ms breakdowns, BENCH headlines)
* ``--profile-dir``             — what ``train.py --profile DIR`` wrote: a
  ``jax.profiler`` XPlane and, beside it, ``host_spans.json`` (the
  program's spans of the profiled window, ``utils/profiling.py``)

Outputs:

* ``artifacts/obs_report.json`` (``--out``) — counts per event kind, the
  reconstructed **incident timeline** (kill -> detect -> quarantine/reap
  -> rebuild/respawn -> recover, in journal order), an **slo** section
  (error-budget timeline from the ``slo_error_budget_remaining`` gauge
  in ``metrics_flush`` snapshots, burn alerts, and the autoscaler's
  resize decisions with their input signals — why the fleet changed
  size, from the journal alone), flight-dump summaries and any
  stage/headline lines.
* ``<obs-dir>/trace.json`` (``--trace-out``) — the span lines wrapped in
  a Chrome-trace ``{"traceEvents": [...]}`` array, loadable in Perfetto.
  With ``--profile-dir`` the profiled window's host spans and the
  XPlane's device events (program runs and ops, per chip) are laid on ONE
  axis in it — microseconds since the profiler session began: a span's
  monotonic time plus the window's wall offset less the XPlane's own
  ``profile_start_time``.  The two clocks meet to a few milliseconds
  (obs/tracing.py): enough to see which step a host span belongs to.
* the report's ``spans.by_name`` — per span name (``setup.*``,
  ``train_step``, ``data``, ``step``, ``feed.wait``, ``feed.put``,
  ``drain``, ``checkpoint``, ``jit.trace``, ``jit.lower``,
  ``jit.compile``, the serving spans): count,
  total, median and longest, in milliseconds.
* the report's ``device_parts`` (with ``--profile-dir``, where the XPlane
  holds the program's ``Hlo Proto``) — the profiled steps' device time by
  part, pass and owner, from the benchmark's own classification
  (``perfbench/step_parts.py``): ms a step by kind x part x pass
  (``["kda", "glue", "bwd", 41.2]``), the same by layer, XLA's own copies
  by the layer that owns them, and the totals the per-layer metrics
  ``mixer_proj_ms``, ``mixer_glue_ms``, ``remat_ms``, ``xla_copy_ms`` and
  ``unowned_share`` read from the same rows (docs/observability.md).

Usage:
    python tools/obs_report.py --obs-dir /tmp/run/obs \\
        --stage-log /tmp/run/bench.log --out artifacts/obs_report.json
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mx_rcnn_tpu.obs.metrics import parse_labels  # noqa: E402

# Event kinds that mark state changes in an incident, in no particular
# order — the TIMELINE order comes from the journal, these only filter
# routine chatter (metrics_flush, shed) out of it.
INCIDENT_KINDS = frozenset({
    "worker_death", "worker_retired", "worker_wedged", "service_fallback",
    "cache_quarantine", "shm_quarantine", "cache_evict",
    "guardian_rollback", "rollback_restored", "guardian_loss_spike",
    "training_diverged", "preempt_drain", "recompiled",
    "checkpoint_saved", "checkpoint_restored",
    "engine_dead", "engine_killed",
    "fleet_quarantine", "fleet_reinstate", "fleet_retire", "weight_swap",
    "breaker_transition", "ladder_transition",
    "slo_burn_start", "slo_burn_stop",
    "fleet_scale_up", "fleet_scale_down",
    "fleet_replica_added", "fleet_replica_retired",
    "gateway_weight_roll",
    "deploy_candidate", "deploy_shadow_start", "deploy_shadow_verdict",
    "deploy_promote", "deploy_reject", "deploy_rollback", "deploy_resume",
    "tenant_quota_tightened", "tenant_quota_restored",
})


def _slo_section(journal: list[dict], t0: float) -> dict:
    """Control-plane story from the journal alone: the error-budget
    trajectory (every ``metrics_flush`` snapshot carries the
    ``slo_error_budget_remaining{slo=...}`` gauge), burn-alert
    transitions, and the fleet-resize decisions with the signals the
    autoscaler acted on."""
    budget_timeline: list[dict] = []
    burn_alerts: list[dict] = []
    resize_decisions: list[dict] = []
    for rec in journal:
        kind = rec.get("kind")
        payload = rec.get("payload") or {}
        t_s = round(rec.get("ts", t0) - t0, 3)
        if kind == "metrics_flush":
            series = (payload.get("snapshot") or {}).get(
                "slo_error_budget_remaining"
            )
            if isinstance(series, dict) and series:
                point = {"t_s": t_s}
                for label, v in series.items():
                    # label is 'slo="availability"' — keep just the value.
                    name = label.split('"')[1] if '"' in label else label
                    point[name] = round(v, 6) if isinstance(v, float) else v
                budget_timeline.append(point)
        elif kind in ("slo_burn_start", "slo_burn_stop"):
            burn_alerts.append({
                "t_s": t_s, "event": kind.rsplit("_", 1)[-1],
                **{k: v for k, v in payload.items()},
            })
        elif kind in ("fleet_scale_up", "fleet_scale_down"):
            resize_decisions.append({
                "t_s": t_s,
                "direction": kind.rsplit("_", 1)[-1],
                **{k: v for k, v in payload.items()},
            })
    return {
        "budget_timeline": budget_timeline,
        "burn_alerts": burn_alerts,
        "resize_decisions": resize_decisions,
    }


def _pack_section(journal: list[dict]) -> dict:
    """Packing / zero-copy efficiency from the last ``metrics_flush``
    snapshot: the ``serve_batch_occupancy`` histogram collapsed to a
    device-call count + mean fill, and the shm ring counters — so a
    report always answers "were the device calls full and did the data
    plane copy" without re-scraping /metrics."""
    snap: dict = {}
    for rec in journal:
        if rec.get("kind") == "metrics_flush":
            s = (rec.get("payload") or {}).get("snapshot") or {}
            if s:
                snap = s  # keep the LAST flush (cumulative series)
    out: dict = {}
    occ = snap.get("serve_batch_occupancy")
    if isinstance(occ, dict) and occ:
        calls = sum(
            v.get("count", 0) for v in occ.values() if isinstance(v, dict)
        )
        filled = sum(
            v.get("sum", 0.0) for v in occ.values() if isinstance(v, dict)
        )
        out["batch_occupancy"] = {
            "device_calls": calls,
            "mean": round(filled / calls, 4) if calls else None,
        }
    for name in ("data_shm_bytes_total", "data_shm_ring_stalls_total",
                 "data_shm_quarantines_total",
                 "serve_cache_hits_total", "serve_cache_coalesced_total",
                 "serve_cache_evictions_total"):
        series = snap.get(name)
        if isinstance(series, dict) and series:
            out[name] = round(sum(
                v for v in series.values() if isinstance(v, (int, float))
            ), 2)
    size = snap.get("serve_cache_size")
    if isinstance(size, dict) and size:
        # Gauge: last value wins per series; one shared cache per router.
        vals = [v for v in size.values() if isinstance(v, (int, float))]
        if vals:
            out["serve_cache_size"] = vals[-1]
    return out


def _tenant_section(journal: list[dict], t0: float) -> dict:
    """Per-tenant story when multi-tenancy ran (docs/serving.md): request
    outcomes from the ``tenant``-labelled ``fleet_requests_total`` rows of
    the last ``metrics_flush`` snapshot, quota rejections from
    ``serve_quota_exceeded_total``, and the per-tenant burn/governor
    timeline (burn transitions on tenant-scoped SLOs plus quota
    tighten/restore actions).  Empty when the run had no tenancy — the
    metrics carry no ``tenant`` label then, by design."""
    snap: dict = {}
    for rec in journal:
        if rec.get("kind") == "metrics_flush":
            s = (rec.get("payload") or {}).get("snapshot") or {}
            if s:
                snap = s  # cumulative series: the LAST flush wins
    tenants: dict[str, dict] = {}

    def ent(name: str) -> dict:
        return tenants.setdefault(name, {
            "requests": {}, "quota_rejections": 0, "timeline": [],
        })

    series = snap.get("fleet_requests_total")
    if isinstance(series, dict):
        for key, v in series.items():
            lbl = parse_labels(key)
            name = lbl.get("tenant")
            if not name or not isinstance(v, (int, float)):
                continue
            outcomes = ent(name)["requests"]
            outcome = lbl.get("outcome", "?")
            outcomes[outcome] = outcomes.get(outcome, 0) + int(round(v))
    series = snap.get("serve_quota_exceeded_total")
    if isinstance(series, dict):
        for key, v in series.items():
            name = parse_labels(key).get("tenant")
            if name and isinstance(v, (int, float)):
                ent(name)["quota_rejections"] += int(round(v))
    for rec in journal:
        kind = rec.get("kind")
        if kind not in ("slo_burn_start", "slo_burn_stop",
                        "tenant_quota_tightened", "tenant_quota_restored"):
            continue
        payload = rec.get("payload") or {}
        name = payload.get("tenant")
        if not name:
            continue  # fleet-wide burn: not one tenant's story
        ent(name)["timeline"].append({
            "t_s": round(rec.get("ts", t0) - t0, 3), "kind": kind,
            **{k: v for k, v in payload.items() if k != "tenant"},
        })
    return tenants


def _read_jsonl(path: str) -> list[dict]:
    if not os.path.exists(path):
        return []
    out = []
    with open(path, "rb") as f:
        for line in f:
            try:
                rec = json.loads(line.decode("utf-8", "replace"))
            except ValueError:
                continue  # torn/corrupt line — same tolerance as obs.journal
            if isinstance(rec, dict):
                out.append(rec)
    return out


def _order_key(rec: dict):
    # Wall clock first (cross-process), monotonic as the tiebreaker
    # (same-process events can share a rounded wall timestamp).
    return (rec.get("ts", 0.0), rec.get("ts_mono_ns", 0))


def span_table(spans: list[dict]) -> dict:
    """{span name: {count, total_ms, p50_ms, max_ms}} over Chrome-trace
    span events (``dur`` in microseconds)."""
    durs: dict[str, list[float]] = {}
    for s in spans:
        durs.setdefault(s.get("name", "?"), []).append(s.get("dur", 0.0) / 1e3)
    table = {}
    for name, d in sorted(durs.items()):
        d.sort()
        table[name] = {
            "count": len(d),
            "total_ms": round(sum(d), 3),
            "p50_ms": round(d[(len(d) - 1) // 2], 3),
            "max_ms": round(d[-1], 3),
        }
    return table


def merge_profile(profile_dir: str) -> list[dict]:
    """Chrome-trace events of the newest profiled window under
    ``profile_dir``: its host spans and the XPlane's device events on one
    axis (microseconds since the profiler session began).  [] when the
    directory holds no ``host_spans.json``."""
    found = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "host_spans.json"
    ))) or sorted(glob.glob(os.path.join(profile_dir, "host_spans.json")))
    if not found:
        return []
    with open(found[-1]) as f:
        doc = json.load(f)
    planes = sorted(glob.glob(
        os.path.join(os.path.dirname(found[-1]), "*.xplane.pb")
    ))
    events, start_ns = [], None
    if planes:
        from jax.profiler import ProfileData

        data = ProfileData.from_file(planes[-1])
        for plane in data.planes:
            if plane.name == "Task Environment":
                start_ns = dict(plane.stats).get("profile_start_time")
            if not plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                if line.name not in ("XLA Modules", "XLA Ops"):
                    continue
                for e in line.events:
                    events.append({
                        "ph": "X", "name": e.name.split(" ", 1)[0].lstrip("%"),
                        "cat": line.name, "pid": plane.name, "tid": line.name,
                        "ts": e.start_ns / 1e3, "dur": e.duration_ns / 1e3,
                    })
    spans = doc.get("spans", [])
    if start_ns is None:
        # No XPlane to date the axis: the window's own start stands in.
        shift_us = -doc.get("window_start_mono_ns", 0) / 1e3
    else:
        shift_us = (doc.get("wall_offset_ns", 0) - int(start_ns)) / 1e3
    for s in spans:
        events.append(dict(s, ts=s["ts"] + shift_us, pid="host"))
    return events


def device_parts(profile_dir: str):
    """The newest XPlane under ``profile_dir`` by part, pass and owner
    (``perfbench/step_parts.py``, the benchmark's own reduction): ms a step
    by kind x part x pass, the same by layer, XLA's copies by owner, and the
    totals the benchmark's per-layer metrics read from the same rows.
    None where the directory holds no XPlane or the XPlane no ``Hlo Proto``."""
    planes = sorted(
        glob.glob(os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb"))
    ) or sorted(glob.glob(os.path.join(profile_dir, "*.xplane.pb")))
    if not planes:
        return None
    from perfbench import step_parts

    reading = step_parts.reading_of_xplane(planes[-1])
    table = step_parts.table(reading)       # None: a CPU's profile, no Hlo Proto
    if table is None:
        return None
    return dict(table, xplane=os.path.abspath(planes[-1]),
                program=reading["program_name"])


def build_report(
    obs_dir: str, stage_logs: tuple[str, ...] = ()
) -> tuple[dict, list[dict]]:
    """(report dict, chrome-trace span events) for one obs directory."""
    journal = sorted(
        _read_jsonl(os.path.join(obs_dir, "journal.jsonl")), key=_order_key
    )
    spans = _read_jsonl(os.path.join(obs_dir, "spans.jsonl"))
    flights = []
    for path in sorted(glob.glob(os.path.join(obs_dir, "flight_*.json"))):
        try:
            with open(path) as f:
                dump = json.load(f)
        except (OSError, ValueError):
            continue
        flights.append({
            "path": path,
            "trigger": dump.get("trigger"),
            "run_id": dump.get("run_id"),
            "entries": len(dump.get("entries", [])),
            "kinds": sorted({
                e.get("kind") for e in dump.get("entries", [])
                if isinstance(e, dict) and e.get("kind")
            }),
        })

    t0 = journal[0]["ts"] if journal else 0.0
    events_by_kind: dict[str, int] = {}
    timeline = []
    for rec in journal:
        kind = rec.get("kind", "?")
        events_by_kind[kind] = events_by_kind.get(kind, 0) + 1
        if kind in INCIDENT_KINDS:
            timeline.append({
                "t_s": round(rec.get("ts", t0) - t0, 3),
                "subsystem": rec.get("subsystem"),
                "kind": kind,
                "pid": rec.get("pid"),
                "payload": rec.get("payload", {}),
            })

    stage_lines = []
    for log_path in stage_logs:
        if not os.path.exists(log_path):
            continue
        with open(log_path, "rb") as f:
            for line in f:
                try:
                    rec = json.loads(line.decode("utf-8", "replace"))
                except ValueError:
                    continue
                if isinstance(rec, dict) and ("metric" in rec or "bench" in rec):
                    stage_lines.append(rec)

    traces: dict[str, int] = {}
    for s in spans:
        tid = (s.get("args") or {}).get("trace_id")
        if tid:
            traces[tid] = traces.get(tid, 0) + 1

    report = {
        "obs_dir": os.path.abspath(obs_dir),
        "run_ids": sorted({r.get("run_id", "-") for r in journal}),
        "journal_records": len(journal),
        "events_by_kind": dict(sorted(events_by_kind.items())),
        "incident_timeline": timeline,
        "slo": _slo_section(journal, t0),
        "tenants": _tenant_section(journal, t0),
        "data_plane": _pack_section(journal),
        "spans": {
            "count": len(spans),
            "traces": len(traces),
            "max_spans_per_trace": max(traces.values(), default=0),
            "by_name": span_table(spans),
        },
        "flight_dumps": flights,
        "stage_lines": stage_lines,
    }
    return report, spans


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--obs-dir", required=True,
                   help="directory obs.configure() wrote into")
    p.add_argument("--stage-log", action="append", default=[],
                   help="bench/chaos log with JSON metric lines "
                        "(repeatable)")
    p.add_argument("--profile-dir", default=None,
                   help="the directory train.py --profile DIR wrote: its "
                        "host spans and device events are merged into "
                        "the trace file on one axis")
    p.add_argument("--out", default="artifacts/obs_report.json")
    p.add_argument("--trace-out", default=None,
                   help="Chrome-trace wrap of spans.jsonl (default: "
                        "<obs-dir>/trace.json; 'none' to skip)")
    args = p.parse_args(argv)

    report, spans = build_report(args.obs_dir, tuple(args.stage_log))

    trace_out = args.trace_out
    if trace_out is None:
        trace_out = os.path.join(args.obs_dir, "trace.json")
    if args.profile_dir:
        merged = merge_profile(args.profile_dir)
        report["profile"] = {
            "dir": os.path.abspath(args.profile_dir),
            "events": len(merged),
            "host_spans": span_table(
                [e for e in merged if e.get("pid") == "host"]
            ),
        }
        # The merged window replaces the raw span lines in the trace file:
        # they sit on another axis (the process's monotonic clock).
        spans = merged or spans
        parts = device_parts(args.profile_dir)
        if parts is None:
            # The report stays byte for byte what it was without the section.
            print("[obs_report] device_parts: none (no XPlane with an Hlo "
                  "Proto under --profile-dir)", file=sys.stderr)
        else:
            report["device_parts"] = parts
    if trace_out != "none" and spans:
        with open(trace_out, "w") as f:
            json.dump({"traceEvents": spans}, f)
        report["trace_file"] = os.path.abspath(trace_out)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(f"[obs_report] {report['journal_records']} journal record(s), "
          f"{report['spans']['count']} span(s), "
          f"{len(report['flight_dumps'])} flight dump(s) -> {args.out}",
          file=sys.stderr)
    print(json.dumps({
        "metric": "obs_report",
        "value": {
            "events": report["journal_records"],
            "incidents": len(report["incident_timeline"]),
            "spans": report["spans"]["count"],
            "flight_dumps": len(report["flight_dumps"]),
        },
        "path": os.path.abspath(args.out),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
