"""The reduction from a trace to numbers, on a hand-made fragment: three
steps of 10 ms on one chip, each a conv, an op under the ``proposals`` scope,
a ROIAlign kernel forward and backward and an all-reduce that overlaps the
last 1 ms of compute, with a 2 ms gap before each step."""

import importlib.util
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import readers  # noqa: E402
from perfbench import trace_reduce as tr  # noqa: E402

MS = 1e6


def fragment():
    ops, modules, host = [], [], []
    for i in range(3):
        t = i * 12 * MS + 2 * MS
        modules.append((f"jit_step({i})", t, 10 * MS, ""))
        ops += [
            ("convolution.1", t, 4 * MS, "jit(step)/jit(main)/jvp(backbone)/conv"),
            ("while.3", t + 4 * MS, 2 * MS, "jit(step)/jit(main)/proposals/while"),
            ("fusion.7", t + 4.5 * MS, 1 * MS, "jit(step)/jit(main)/proposals/while/body/nms"),
            ("multilevel_roi_align_pallas.1", t + 6 * MS, 1 * MS, ""),
            ("custom-call.5", t + 7 * MS, 2 * MS,
             "jit(step)/jit(main)/transpose(jvp(roi_align))/pallas_call"),  # found by its scope
            ("all-reduce.1", t + 8 * MS, 2 * MS, ""),
        ]
        host.append(("next_batch", t - 2 * MS, 1.5 * MS))
        host.append(("dispatch", t - 0.5 * MS, 0.4 * MS))
    # the loop's barriers: one before the first step, one after the last
    host.append(("sync", -1 * MS, 1 * MS))
    host.append(("sync", 30 * MS, 6 * MS))
    return ops, modules, host


CLOCK = 7e12  # the harness's clock runs this far ahead of the trace's


def test_union_counts_overlap_once():
    assert tr.union_ns([(0, 10), (5, 10), (30, 5)]) == 20


def test_busy_and_idle_share():
    ops, _, _ = fragment()
    assert tr.busy_ns(ops) == pytest.approx(30 * MS)
    gaps = tr.gaps(ops, 0, 36 * MS)
    assert sum(d for _, d in gaps) == pytest.approx(6 * MS)
    assert len(gaps) == 3


def test_step_interval_percentile():
    _, modules, _ = fragment()
    iv = tr.step_intervals_ns(modules, "jit_step")
    assert iv == pytest.approx([12 * MS, 12 * MS])
    assert tr.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 95) == 10
    assert tr.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 50) == 5
    assert tr.percentile(list(range(1, 101)), 95) == 95


def test_exposed_collective_is_what_no_compute_covers():
    ops, _, _ = fragment()
    # each 2 ms all-reduce overlaps the ROIAlign backward's last 1 ms
    assert tr.exposed_collective_ns(ops) == pytest.approx(3 * 1 * MS)


def test_gap_attribution_names_the_host_span():
    ops, _, host = fragment()
    rows = dict(tr.attribute_gaps(tr.gaps(ops, 0, 36 * MS), host))
    assert rows["next_batch"] == pytest.approx(6e-3)


def reading():
    ops, modules, host = fragment()
    r = {
        # the stretch in which the profiler started comes first: each of the two
        # barriers closes three steps, which sets the two clocks against each other
        "trace": {"devices": {0: {
            "XLA Ops": ops,
            "XLA Modules": [(f"jit_step({i})", i * 12 * MS + MS, 10 * MS, "")
                            for i in (-3, -2, -1)] + modules,
        }}},
        "host_spans": [(n, s + CLOCK, d) for n, s, d in host], "sync_every": 3,
        "program_name": "jit_step", "chips": 1,
        "counters": {"steps": 3, "global_batch": 8, "data_stall_s": 0.006, "sync_every": 8},
        "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        "step_flops": lambda: 197e12 * 0.012 * 0.25,  # a quarter of the peak over a 12 ms step
        "config": {"reference": {
            "canvas": [800, 1344], "roi_levels": [2, 3, 4, 5], "feature_channels": 256,
            "rcnn": {"roi_batch_size": 512, "pooled_size": 7, "sampling_ratio": 2},
            "rpn": {"test_post_nms_top_n": 1000},
        }},
    }
    readers.prepare(r)
    return r


def metric(name):
    path = os.path.join(REPO, "perfbench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_prepare_window_and_steps():
    r = reading()
    assert r["steps_traced"] == 3
    assert r["window_s"] == pytest.approx(0.036)
    assert r["busy_s"] == pytest.approx(0.030)


@pytest.mark.parametrize("name,want", [
    ("device_idle_share.train", 100.0 * 6 / 36),
    ("proposals_ms.train", 2.0),              # the while op and the op inside it count once
    ("step_interval_p95_ms.train", 12.0),
    ("collective_exposed_ms.train", 1.0),
    ("data_stall_ms.train", 2.0),
    ("step_mfu.train", 25.0),
])
def test_readers_on_the_fragment(name, want):
    assert metric(name)(reading()) == pytest.approx(want)


def test_roofline_readers_split_forward_and_backward():
    r = reading()
    fwd = metric("roi_align_fwd_roofline.train")(r)
    bwd = metric("roi_align_bwd_roofline.train")(r)
    from perfbench.flops import least_seconds
    from perfbench.roi_need import need_of

    least_f, bound_f = least_seconds(need_of(r, backward=False), r["peak"])
    least_b, _ = least_seconds(need_of(r, backward=True), r["peak"])
    assert fwd == pytest.approx(100 * least_f / 1e-3)
    assert bwd == pytest.approx(100 * least_b / 2e-3)
    assert bound_f == "bytes" and 0 < fwd < 100 and 0 < bwd < 100


def test_a_reader_with_nothing_to_read_returns_nothing():
    r = reading()
    r["ops"] = [
        o for o in r["ops"]
        if "roi_align" not in o[3] and "roi_align" not in o[0] and "all-reduce" not in o[0]
    ]
    assert metric("roi_align_fwd_roofline.train")(r) is None
    assert metric("collective_exposed_ms.train")(r) is None


def test_names_and_scopes_from_the_compiled_program():
    hlo = (
        'ENTRY %main {\n'
        '  %fusion.31 = f32[8,4]{1,0} fusion(f32[8]{0} %p), kind=kLoop, '
        'metadata={op_name="jit(step)/jit(main)/jvp(proposals)/vmap()/mul" source_file="x.py"}\n'
        '  ROOT %while.3 = (s32[]) while(%t), metadata={op_name="jit(step)/jit(main)/'
        'transpose(jvp(TwoStageDetector.features))/backbone/layer1_block0/conv1"}\n}'
    )
    scopes = tr.scopes_from_hlo(hlo)
    assert scopes["fusion.31"].endswith("jvp(proposals)/vmap()/mul")
    assert tr.scope_group(scopes["fusion.31"]) == "proposals"
    assert tr.scope_group(scopes["while.3"]) == "backbone.bwd"
    assert tr.scope_group("jit(step)/jit(main)/optimizer/mul") == "optimizer"
    assert tr.scope_group("") == ""
    assert tr.short_name("%fusion.31 = f32[8,4]{1,0:T(8,128)} fusion(f32[8]{0} %p)") == "fusion.31"


def test_window_runs_from_barrier_to_barrier():
    r = reading()
    assert (r["lo"], r["hi"]) == (0.0, 36 * MS)
    groups = dict(r["breakdown"]["device_ops"])
    assert groups["backbone"] == pytest.approx(0.012)
    assert groups["proposals"] == pytest.approx(0.006)  # the loop and the op inside it, once
    assert "roi_align.bwd" in groups


def test_clocks_are_set_by_the_barriers():
    ends = [10.0, 20.0, 30.0, 40.0]
    # each barrier returns 2 or 3 after its stretch's last step: the least, so
    # that no barrier comes to lie before its step
    assert tr.clock_offset_ns([1022.0, 1043.0], ends, 2) == 1002.0
    # counted from the end: the profiler may miss steps of its first stretch
    assert tr.clock_offset_ns([1022.0, 1043.0], ends[1:], 2) == 1002.0
    assert tr.clock_offset_ns([], ends, 2) is None
    # no barrier at all: no offset, and the window falls back to the ops' own span
    r = {**reading(), "host_spans": []}
    readers.prepare(r)
    assert (r["lo"], r["hi"]) == (2 * MS, 36 * MS) and r["breakdown"]["idle_gaps"][0][0] == "unattributed"


def test_no_device_plane_is_an_error():
    with pytest.raises(RuntimeError):
        readers.prepare({"trace": {"devices": {}, "host": {}}})
