"""The program's own timeline (PR 25): the span buffer and its clock, the
compile listener, the feed's spans, the training loop's spans and journal
events, the names the compiled step's ops carry, and the profiler window.
All on the CPU at ``tiny`` size; a time taken here is a count of work."""

from __future__ import annotations

import dataclasses
import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu import obs
from mx_rcnn_tpu.obs import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_plane():
    obs.reset()
    yield
    obs.reset()


# -- A. the span record -------------------------------------------------------


class TestSpanBuffer:
    def test_buffer_is_bounded_and_keeps_the_newest(self):
        tr = tracing.Tracer(capacity=4)
        for i in range(6):
            tr.span("s", attrs={"i": i}).end()
        kept = tr.recent()
        assert [s.attrs["i"] for s in kept] == [2, 3, 4, 5]

    def test_default_buffer_holds_setup_and_a_window(self):
        # a cold start's ~340 programs, lowered and compiled, ~60 long traces,
        # 6 set-up spans and 75 steps x 5 spans, twice over
        assert tracing.SPAN_BUFFER >= 2 * (2 * 340 + 60 + 6 + 75 * 5)

    def test_recent_since_and_subsystem(self):
        tr = tracing.Tracer()
        tr.span("a", subsystem="train").end()
        mark = time.monotonic_ns()
        tr.span("b", subsystem="train").end()
        tr.span("c", subsystem="jit").end()
        assert [s.name for s in tr.recent(since_ns=mark)] == ["b", "c"]
        assert [s.name for s in tr.recent(subsystem="train")] == ["a", "b"]
        assert [s.name for s in tr.recent(mark, "jit")] == ["c"]

    def test_ids_are_unique_and_need_no_uuid4(self, monkeypatch):
        import uuid

        def boom():
            raise AssertionError("uuid4 on the span path")

        monkeypatch.setattr(uuid, "uuid4", boom)
        tr = tracing.Tracer()
        spans = [tr.span("s") for _ in range(2000)]
        ids = {s.span_id for s in spans} | {tracing.new_trace_id() for _ in range(100)}
        assert len(ids) == 2100
        assert all(re.fullmatch(r"[0-9a-f]{16}", i) for i in ids)
        child = spans[0].child("c")
        assert child.trace_id == spans[0].trace_id
        assert child.parent_id == spans[0].span_id

    def test_no_wall_clock_read_per_span_and_ts_wall_derived(self, monkeypatch):
        class Clock:
            monotonic_ns = staticmethod(time.monotonic_ns)

            def time(self):
                raise AssertionError("wall clock read on the span path")

            time_ns = time

        now = time.time()
        monkeypatch.setattr(tracing, "time", Clock())
        with tracing.Tracer().span("s") as s:
            pass
        assert abs(s.to_chrome()["args"]["ts_wall"] - now) < 5.0

    def test_monotonic_and_perf_counter_are_one_clock(self):
        # perfbench/program_spans.py sets the program's spans (monotonic_ns)
        # by the offset fitted for the harness's (perf_counter_ns).
        gaps = []
        for _ in range(50):
            a = time.monotonic_ns()
            b = time.perf_counter_ns()
            c = time.monotonic_ns()
            assert a <= b <= c
            gaps.append(c - a)
        assert min(gaps) < 1_000_000

    def test_ring_copy_is_rendered_on_read(self):
        with obs.span("outer", subsystem="test", attrs={"k": 1}):
            pass
        entry = [e for e in obs.flight().entries() if e.get("type") == "span"][-1]
        assert entry["name"] == "outer" and entry["args"]["k"] == 1
        assert obs.tracer().recent(subsystem="test")[-1].name == "outer"

    def test_spans_do_not_push_events_out_of_the_flight_ring(self):
        obs.emit("train", "checkpoint_saved", {"step": 1})
        for i in range(2000):  # a few hundred steps' worth
            obs.tracer().record("step", i, 1, subsystem="train")
        entries = obs.flight().entries()
        assert any(e.get("kind") == "checkpoint_saved" for e in entries)
        spans = [e for e in entries if e.get("type") == "span"]
        assert len(spans) == 512 and spans[-1]["ts"] == pytest.approx(1.999)
        # one list in order of time: an event emitted now comes last
        obs.emit("train", "recompiled", {"step": 2, "fun_name": "f", "seconds": 0.1})
        with obs.span("after", subsystem="test"):
            pass
        kinds = [e.get("kind") or e.get("name") for e in obs.flight().entries()]
        assert kinds[-2:] == ["recompiled", "after"]
        assert kinds.index("checkpoint_saved") > kinds.index("step")

    def test_wall_offset_is_wall_minus_monotonic(self):
        off = tracing.wall_offset_ns()
        assert abs(off - (time.time_ns() - time.monotonic_ns())) < 50_000_000
        assert abs(tracing.wall_of(time.monotonic_ns()) - time.time()) < 1.0


# -- B. compiles, the feed -----------------------------------------------------


class TestCompileListener:
    def test_counts_a_fresh_jit_and_a_cache_hit(self):
        from mx_rcnn_tpu.utils import compile_cache as cc

        cc.install_compile_listener()
        cc.install_compile_listener()  # idempotent: one count per program
        x = jnp.ones(3)  # its own little program, before the count
        n0, s0 = cc.compile_totals()

        @jax.jit
        def fresh_program_for_the_listener(x):
            return x * 3 + 1

        fresh_program_for_the_listener(x).block_until_ready()
        n1, s1 = cc.compile_totals()
        assert n1 == n0 + 1 and s1 > s0
        span = obs.tracer().recent(subsystem="jit")[-1]
        assert span.name == "jit.compile"
        assert "fresh_program_for_the_listener" in span.attrs["fun_name"]
        assert span.attrs["cache_hit"] is False
        # a hit, as jax reports one: cache_hits, the retrieval, then the
        # backend compile event that closes the program
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        jax.monitoring.record_event_duration_secs(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.25
        )
        jax.monitoring.record_event_duration_secs(
            "/jax/core/compile/backend_compile_duration", 0.3, fun_name="jit(cached)"
        )
        hit = obs.tracer().recent(subsystem="jit")[-1]
        assert hit.attrs == {"fun_name": "jit(cached)", "cache_hit": True}
        assert hit.dur_ns == pytest.approx(0.3e9)
        assert cc.compile_totals()[0] == n1 + 1

    def test_a_program_is_traced_lowered_and_compiled_in_that_order(self, monkeypatch):
        from mx_rcnn_tpu.utils import compile_cache as cc

        cc.install_compile_listener()
        x = jnp.ones(5)
        # an eager op's trace, as jax reports one: under a millisecond, no span
        for seconds in (0.0004, 0.002):
            jax.monitoring.record_scalar(cc._TRACE, time.time(), fun_name="add")
            jax.monitoring.record_event_duration_secs(cc._TRACE, seconds, fun_name="add")
        # (by name: on a loaded machine the test before this one leaves a trace span of its own)
        told = [s.dur_ns for s in obs.tracer().recent(subsystem="jit")
                if s.name == "jit.trace" and s.attrs["fun_name"] == "add"]
        assert told == [2_000_000]
        monkeypatch.setattr(cc, "_MIN_TRACE_S", 0.0)
        since = time.monotonic_ns()

        @jax.jit
        def outer_program_for_the_listener(x):
            return jax.jit(lambda y: y * 2)(x) + 1  # an inner jit: no span of its own

        outer_program_for_the_listener(x).block_until_ready()
        mine = [
            s for s in obs.tracer().recent(since_ns=since, subsystem="jit")
            if "outer_program_for_the_listener" in s.attrs["fun_name"]
        ]
        assert [s.name for s in mine] == ["jit.trace", "jit.lower", "jit.compile"]
        assert all(a.end_ns <= b.start_ns + 1_000_000 for a, b in zip(mine, mine[1:]))
        traces = [
            s for s in obs.tracer().recent(since_ns=since, subsystem="jit")
            if s.name == "jit.trace"
        ]
        assert len(traces) == 1  # the inner jit's trace lies inside it

    def test_the_way_to_the_first_configure_cache_is_recorded_once(self):
        from mx_rcnn_tpu.utils import compile_cache as cc

        cc._start_recorded = False
        now = time.monotonic_ns()
        cc.configure_cache()
        cc.configure_cache()
        got = obs.tracer().recent(subsystem="process")
        assert [s.name for s in got] == ["setup.import", "setup.backend"]
        born, backend = got
        # this process is older than its test and younger than a day
        assert 0 < now - born.start_ns < 86_400e9
        assert born.end_ns == backend.start_ns and backend.end_ns >= now


class TestFeedSpans:
    def test_slow_source_is_wait_and_slow_put_is_put(self, monkeypatch):
        from mx_rcnn_tpu.parallel import prefetch

        real_put = jax.device_put

        def slow_put(x, *a, **k):
            time.sleep(0.03)
            return real_put(x, *a, **k)

        monkeypatch.setattr(prefetch.jax, "device_put", slow_put)

        def slow_source():
            for i in range(4):
                time.sleep(0.05)
                yield np.full((2,), i, np.float32)

        stats = prefetch.PrefetchStats()
        out = list(prefetch.device_prefetch(slow_source(), None, depth=1, stats=stats))
        assert [int(o[0]) for o in out] == [0, 1, 2, 3]
        stall_s, batches = stats.take()
        put_s = stats.take_put()
        assert batches == 5  # four batches and the pull that found the end
        assert stall_s >= 4 * 0.05 - 4 * 0.03 - 0.02  # the source's time past the puts
        assert 4 * 0.03 <= put_s < 4 * 0.03 + 0.1
        spans = obs.tracer().recent(subsystem="train")
        puts = [s for s in spans if s.name == "feed.put"]
        waits = [s for s in spans if s.name == "feed.wait"]
        assert [s.attrs["seq"] for s in puts] == [0, 1, 2, 3]
        assert [s.attrs["seq"] for s in waits] == sorted(s.attrs["seq"] for s in waits)
        assert set(s.attrs["seq"] for s in waits) <= {0, 1, 2, 3, 4}
        assert sum(s.dur_ns for s in puts) / 1e9 == pytest.approx(put_s)
        assert sum(s.dur_ns for s in waits) / 1e9 == pytest.approx(stall_s)
        assert all(s.dur_ns >= 0.03e9 for s in puts) and waits

    def test_spans_hang_under_the_consumers_span(self):
        from mx_rcnn_tpu.parallel import prefetch

        stats = prefetch.PrefetchStats()
        it = prefetch.device_prefetch(
            iter([np.zeros(2), np.ones(2)]), None, depth=1, host_depth=0, stats=stats
        )
        with obs.span("data", subsystem="train") as parent:
            stats.parent = parent
            next(it)
        it.close()
        kids = [s for s in obs.tracer().recent(subsystem="train") if s.name.startswith("feed.")]
        assert kids and all(s.parent_id == parent.span_id for s in kids)
        assert all(s.trace_id == parent.trace_id for s in kids)


# -- B. the training loop, one run ------------------------------------------------


class _ShapeShift:
    """The tiny loader, its canvas 32 px taller from the ``at``-th batch on:
    the step has to be compiled again there."""

    def __init__(self, loader, at):
        self.loader, self.at = loader, at

    def iter_from(self, skip_batches=0):
        for i, b in enumerate(self.loader.iter_from(skip_batches=skip_batches)):
            if i + skip_batches >= self.at:
                b = b._replace(images=np.pad(b.images, ((0, 0), (0, 32), (0, 0), (0, 0))))
            yield b


@pytest.fixture(scope="module")
def loop_run(tmp_path_factory):
    """One ``train()`` of six tiny steps whose fourth batch changes shape.
    -> (spans, journal events, metric rows)."""
    from mx_rcnn_tpu.config import get_config
    from mx_rcnn_tpu.data import DetectionLoader, build_dataset, filter_roidb
    from mx_rcnn_tpu.train.loop import train
    from mx_rcnn_tpu.utils.compile_cache import install_compile_listener

    obs.reset()
    install_compile_listener()
    # The set-up spans count the programs built inside them: start from no
    # in-process executables, whatever ran before on this worker.
    jax.clear_caches()
    work = tmp_path_factory.mktemp("timeline")
    cfg = get_config("tiny_synthetic", workdir=str(work))
    cfg = dataclasses.replace(
        cfg,
        train=dataclasses.replace(cfg.train, log_every=2, checkpoint_every=4),
        obs=dataclasses.replace(cfg.obs, enabled=True, dir=str(work / "obs")),
    )
    roidb = filter_roidb(build_dataset(cfg.data, train=True).roidb())
    loader = DetectionLoader(
        roidb, cfg.data, train=True,
        batch_size=cfg.train.per_device_batch * jax.device_count(),  # train()'s own mesh
        seed=cfg.train.seed, num_proposals=cfg.model.rpn.train_post_nms_top_n,
    )
    train(cfg, mesh=None, total_steps=6, workdir=str(work), loader=_ShapeShift(loader, 3))
    spans = obs.tracer().recent()
    obs.close()
    with open(work / "obs" / "journal.jsonl") as f:
        events = [json.loads(line) for line in f]
    with open(work / cfg.name / "metrics.jsonl") as f:
        rows = [json.loads(line) for line in f]
    with open(work / "obs" / "spans.jsonl") as f:
        durable = [json.loads(line) for line in f]
    obs.reset()
    return spans, events, rows, durable


class TestTrainLoopTimeline:
    def test_setup_phases_in_order_and_apart(self, loop_run):
        spans = loop_run[0]
        setup = [s for s in spans if s.name.startswith("setup.")]
        assert [s.name for s in setup] == [
            "setup.init_state", "setup.optimizer", "setup.plan", "setup.step",
        ]
        for a, b in zip(setup, setup[1:]):
            assert a.end_ns <= b.start_ns
        assert all(s.subsystem == "train" for s in setup)
        assert all({"programs", "compile_s"} <= set(s.attrs) for s in setup)
        assert setup[0].attrs["programs"] > 0  # the eager init's small programs

    def test_every_step_has_its_spans(self, loop_run):
        spans = [s for s in loop_run[0] if s.subsystem == "train"]
        steps = [s for s in spans if s.name == "train_step"]
        assert [s.attrs["step"] for s in steps] == list(range(6))
        for st in steps:
            kids = [s for s in spans if s.parent_id == st.span_id]
            assert [k.name for k in kids] == ["data", "step"]
            assert all(k.attrs["step"] == st.attrs["step"] for k in kids)
            assert all(st.start_ns <= k.start_ns and k.end_ns <= st.end_ns for k in kids)
        data_ids = {s.span_id for s in spans if s.name == "data"}
        puts = [s for s in spans if s.name == "feed.put"]
        assert len(puts) >= 6 and all(p.parent_id in data_ids for p in puts)
        assert [p.attrs["seq"] for p in puts] == list(range(len(puts)))

    def test_drains_and_checkpoints_are_spans(self, loop_run):
        spans = loop_run[0]
        drains = [s.attrs["step"] for s in spans if s.name == "drain"]
        saves = [s.attrs["step"] for s in spans if s.name == "checkpoint"]
        assert drains == [1, 2, 4, 6]
        assert saves == [0, 4, 6]  # the safety net, the cadence, the last

    def test_recompile_after_the_first_drain_is_journalled(self, loop_run):
        events = [e for e in loop_run[1] if e["kind"] == "recompiled"]
        assert events, "the shape change at the fourth batch went unreported"
        steps = {e["payload"]["step"] for e in events}
        assert steps == {3}
        assert any("step" in e["payload"]["fun_name"] for e in events)
        assert all(e["payload"]["seconds"] > 0 for e in events)
        assert all(e["subsystem"] == "train" for e in events)

    def test_log_line_gains_data_put_ms(self, loop_run):
        rows = loop_run[2]
        assert rows and all("data_put_ms" in r and "data_stall_ms" in r for r in rows)
        assert all(r["data_put_ms"] >= 0 for r in rows)

    def test_durable_mode_writes_what_the_buffer_holds(self, loop_run):
        spans, _, _, durable = loop_run
        names = {d["name"] for d in durable}
        assert {"setup.init_state", "train_step", "data", "step", "feed.put", "drain",
                "checkpoint", "jit.compile"} <= names
        assert len(durable) == len(spans)


# -- C. the device side: names ------------------------------------------------------


def _tiny_vgg_step():
    sys.path.insert(0, os.path.join(REPO, "tests", "perfbench"))
    from _benchmark_tiny import TINY_OVERRIDES

    from mx_rcnn_tpu.config import apply_overrides, get_config
    from mx_rcnn_tpu.detection import Batch
    from mx_rcnn_tpu.train.loop import build_all

    cfg = apply_overrides(
        get_config("vgg16_voc07"), TINY_OVERRIDES["vgg16_voc07"] + ["train.per_device_batch=2"]
    )
    _, _, state, step_fn, gb = build_all(cfg, None)
    h, w = cfg.data.image_size
    g = cfg.data.max_gt_boxes
    batch = Batch(
        images=jnp.zeros((gb, h, w, 3), jnp.uint8), image_hw=jnp.full((gb, 2), float(h)),
        gt_boxes=jnp.zeros((gb, g, 4)), gt_classes=jnp.zeros((gb, g), jnp.int32),
        gt_valid=jnp.zeros((gb, g), bool),
    )
    return step_fn.lower(state, batch).compile().as_text()


@pytest.fixture(scope="module")
def step_hlo():
    return _tiny_vgg_step()


class TestStepOpNames:
    def test_proposals_is_split_and_the_sweep_is_named(self, step_hlo):
        names = set(re.findall(r'op_name="([^"]*)"', step_hlo))
        for inner in ("topk", "decode", "nms"):
            rx = re.compile(r"[/(]proposals[/)].*[/(]" + inner + r"[/)]")
            assert any(rx.search(n) for n in names), inner
        sweep = [n for n in names if "nms_sweep" in n]
        assert sweep and all("while/body" in n for n in sweep if n.startswith("jit("))
        assert any(re.search(r"[/(]nms[/)].*while/body/nms_sweep", n) for n in sweep)

    def test_no_op_of_the_step_is_left_without_a_name(self, step_hlo):
        """Extends tpulint TPU005 (static, MXU calls only) to what the
        compiler was actually handed: every op that came from the program
        (its path starts at ``jit(step)``) sits under a scope, a flax module
        or a named function - the MXU ops among them."""
        from perfbench.hlo_module import has_scope

        rows = re.findall(r'^\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(.*?op_name="([^"]*)"',
                          step_hlo, re.M)
        mxu = [n for op, n in rows if op in ("convolution", "dot")]
        assert mxu and all(has_scope(n) for n in mxu)
        bare = sorted({n for _, n in rows if n.startswith("jit(step)") and not has_scope(n)})
        assert bare == []

    def test_fpn_proposals_carry_the_same_scopes(self):
        from mx_rcnn_tpu.ops.proposals import generate_fpn_proposals

        a = {2: 64, 3: 16}
        scores = {l: jnp.linspace(0.1, 0.9, n) for l, n in a.items()}
        deltas = {l: jnp.zeros((n, 4)) for l, n in a.items()}
        anchors = {
            l: jnp.stack([jnp.arange(n, dtype=jnp.float32)] * 2
                         + [jnp.arange(n, dtype=jnp.float32) + 8.0] * 2, axis=1)
            for l, n in a.items()
        }
        jp = jax.make_jaxpr(
            lambda s, d: generate_fpn_proposals(s, d, anchors, 64.0, 64.0, 16, 8)
        )(scores, deltas)
        stacks = {str(e.source_info.name_stack) for e in jp.eqns}
        for scope in ("topk", "decode", "nms"):
            assert any(scope in s.split("/") for s in stacks), (scope, stacks)


def _pallas_names(fn, *args):
    found = []

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                found.append(e.params["name"])
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", None)
                    if inner is not None and hasattr(inner, "eqns"):
                        walk(inner)
                    elif hasattr(sub, "eqns"):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


class TestKernelNames:
    def _roi_inputs(self):
        feats = {2: jnp.ones((1, 16, 16, 128), jnp.float32), 3: jnp.ones((1, 8, 8, 128), jnp.float32)}
        rois = jnp.asarray([[[4.0, 4.0, 40.0, 40.0], [0.0, 0.0, 20.0, 20.0]]])
        return feats, rois

    def test_roi_align_forward_and_backward(self):
        from mx_rcnn_tpu.ops.pallas.roi_align import multilevel_roi_align_fast

        feats, rois = self._roi_inputs()

        def fwd(f):
            return multilevel_roi_align_fast(f, rois, 7, 2, 16, True, "pallas")

        assert _pallas_names(fwd, feats) == ["roi_align_fwd"]
        both = _pallas_names(jax.grad(lambda f: fwd(f).sum()), feats)
        assert sorted(set(both)) == ["roi_align_bwd", "roi_align_fwd"]
        # the accepted roofline readers' patterns still tell them apart
        fwd_rx, bwd_rx = re.compile(r"roi_align"), re.compile(r"roi_align.*bwd")
        picks_fwd = [n for n in both if fwd_rx.search(n) and not re.search(r"bwd", n)]
        picks_bwd = [n for n in both if bwd_rx.search(n)]
        assert set(picks_fwd) == {"roi_align_fwd"} and set(picks_bwd) == {"roi_align_bwd"}


# -- D. the program's own profiler window ---------------------------------------------


class TestProfileWindow:
    def test_host_tracer_off_and_spans_beside_the_trace(self, tmp_path, monkeypatch):
        from mx_rcnn_tpu.utils import profiling

        calls = {}

        def start(logdir, **kw):
            calls["options"] = kw.get("profiler_options")
            os.makedirs(os.path.join(logdir, "plugins", "profile", "run1"))

        monkeypatch.setattr(profiling.jax.profiler, "start_trace", start)
        monkeypatch.setattr(profiling.jax.profiler, "stop_trace", lambda: calls.setdefault("stopped", True))
        obs.span("before", subsystem="train").end()
        win = profiling.ProfileWindow(str(tmp_path), 2, 4)
        for i in range(6):
            win.step(i)
            obs.span("train_step", subsystem="train", attrs={"step": i}).end()
        win.close()
        assert calls["options"].host_tracer_level == 0
        assert calls["options"].python_tracer_level == 0
        assert calls["stopped"]
        with open(tmp_path / "plugins" / "profile" / "run1" / profiling.HOST_SPANS_FILE) as f:
            doc = json.load(f)
        assert [s["args"]["step"] for s in doc["spans"]] == [2, 3]
        assert abs(doc["wall_offset_ns"] - (time.time_ns() - time.monotonic_ns())) < 50_000_000

    def test_trace_context_manager_uses_the_same_start(self, tmp_path, monkeypatch):
        from mx_rcnn_tpu.utils import profiling

        seen = []
        monkeypatch.setattr(
            profiling.jax.profiler, "start_trace",
            lambda logdir, **kw: seen.append(kw["profiler_options"].host_tracer_level),
        )
        monkeypatch.setattr(profiling.jax.profiler, "stop_trace", lambda: None)
        with profiling.trace(str(tmp_path)):
            obs.span("inside", subsystem="train").end()
        assert seen == [0]
        with open(tmp_path / profiling.HOST_SPANS_FILE) as f:
            assert [s["name"] for s in json.load(f)["spans"]] == ["inside"]


# -- the operator's reader --------------------------------------------------------


class TestObsReport:
    def _tool(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import obs_report
        finally:
            sys.path.pop(0)
        return obs_report

    def test_per_name_table_of_the_training_spans(self, loop_run, tmp_path):
        tool = self._tool()
        with open(tmp_path / "spans.jsonl", "w") as f:
            for d in loop_run[3]:
                f.write(json.dumps(d) + "\n")
        report, spans = tool.build_report(str(tmp_path))
        table = report["spans"]["by_name"]
        assert table["train_step"]["count"] == 6 and table["drain"]["count"] == 4
        for name in ("setup.init_state", "feed.put", "checkpoint", "data", "step"):
            row = table[name]
            assert row["count"] >= 1 and 0 <= row["p50_ms"] <= row["max_ms"] <= row["total_ms"]
        assert table["feed.put"]["total_ms"] == pytest.approx(
            sum(s["dur"] for s in spans if s["name"] == "feed.put") / 1e3, abs=1e-2
        )

    def test_profile_window_is_merged_onto_one_axis(self, tmp_path):
        tool = self._tool()
        run = tmp_path / "plugins" / "profile" / "run1"
        run.mkdir(parents=True)
        with open(run / "host_spans.json", "w") as f:
            json.dump({"wall_offset_ns": 1_000_000, "window_start_mono_ns": 5_000_000, "spans": [
                {"ph": "X", "name": "feed.put", "cat": "train", "ts": 6000.0, "dur": 250.0,
                 "pid": 1, "tid": 1, "args": {"seq": 3}},
            ]}, f)
        events = tool.merge_profile(str(tmp_path))
        # no XPlane beside it here: the window's own start dates the axis
        assert [(e["name"], e["ts"], e["pid"]) for e in events] == [("feed.put", 1000.0, "host")]
        assert tool.merge_profile(str(tmp_path / "nothing")) == []
