"""The readers PR 25 adds, on hand-made fragments: the program's spans set on
the device's clock and clipped, the set-up and compile sums, and - from a
hand-encoded ``.xplane.pb`` holding a tiny program - a fusion named through
the computation it calls, the share of busy time without a name, the NMS's
time and its sweeps counted by the runs of a ``while`` body."""

import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import hlo_module, program_spans, readers  # noqa: E402

MS = 1e6
CLOCK = 5e12  # the host's clock runs this far ahead of the trace's


# -- a protobuf writer, for the fragment only ------------------------------------


def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(no, value):
    """One field: ints as varints, bytes/str length-delimited."""
    if isinstance(value, int):
        return _varint(no << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(no << 3 | 2) + _varint(len(value)) + value


def _instr(name, opcode, op_name="", calls=(), packed=False):
    body = _f(1, name) + _f(2, opcode)
    if op_name:
        body += _f(7, _f(2, op_name))
    if packed and calls:
        body += _f(38, b"".join(_varint(c) for c in calls))
    else:
        body += b"".join(_f(38, c) for c in calls)
    return body


def _module(name, computations):
    """computations: [(id, name, [instruction bytes])]"""
    body = _f(1, name)
    for cid, cname, instrs in computations:
        body += _f(3, _f(1, cname) + b"".join(_f(2, i) for i in instrs) + _f(5, cid))
    return _f(1, body)  # HloProto.hlo_module


P = "jit(step)/jit(main)/"
STEP = _module("jit_step", [
    (1, "fused_bwd", [
        _instr("param_0", "parameter"),
        _instr("convert.1", "convert", P + "transpose(jvp(roi_align))/vmap(jit(roi_align))/convert_element_type"),
        _instr("scatter.1", "scatter", P + "transpose(jvp(roi_align))/vmap(jit(roi_align))/scatter-add"),
        _instr("add.9", "add", P + "jvp(rcnn_loss)/add"),
    ]),
    (2, "fused_copy", [_instr("param_1", "parameter"), _instr("copy.3", "copy")]),
    (3, "nms_cond", [_instr("compare.1", "compare", P + "jvp(proposals)/vmap(nms)/jit(nms_indices)/while/cond/ne")]),
    (4, "nms_body", [
        _instr("and_reduce_fusion", "fusion",
               P + "jvp(proposals)/vmap(nms)/jit(nms_indices)/while/body/nms_sweep/reduce_or"),
        _instr("fusion.60", "fusion", P + "jvp(proposals)/vmap(nms)/jit(nms_indices)/while/body/nms_sweep/and"),
    ]),
    (5, "main", [
        _instr("convolution.1", "convolution", P + "jvp(TwoStageDetector.features)/backbone/conv"),
        _instr("fusion.40", "fusion", "", calls=[1]),                 # XLA made it: no metadata
        _instr("fusion.41", "fusion", "", calls=[2], packed=True),   # nothing inside has a name either
        _instr("while.2", "while", P + "jvp(proposals)/vmap(nms)/jit(nms_indices)/while", calls=[4, 3]),
        _instr("fusion.7", "fusion", P + "jvp(proposals)/vmap(nms)/jit(nms_indices)/sort"),
        _instr("fusion.8", "fusion", P + "jvp(proposals)/vmap(topk)/jit(_take)/gather"),
        _instr("fusion.1021", "fusion", P + "jvp(proposals)/vmap(nms)/jit(nms_indices)/while/body/nms_sweep/reduce_or"),
        _instr("multiply.5", "multiply", P + "mul"),                  # the program left it bare
    ]),
])


def _xplane(tmp, module_event="jit_step(7)", start_ns=1790000000000000000):
    """A trace file holding only what hlo_module reads: the metadata plane
    with the program, and the Task Environment plane with the start."""
    meta = _f(2, "/host:metadata") + _f(4, _f(1, 1) + _f(2, _f(1, 1) + _f(2, module_event) + _f(5, _f(1, 1) + _f(6, STEP))))
    env = (_f(2, "Task Environment") + _f(5, _f(1, 9) + _f(2, _f(1, 9) + _f(2, "profile_start_time")))
           + _f(6, _f(1, 9) + _f(3, start_ns)))
    d = os.path.join(tmp, "cell", "plugins", "profile", "run")
    os.makedirs(d)
    path = os.path.join(d, "host.xplane.pb")
    with open(path, "wb") as f:
        f.write(_f(1, meta) + _f(1, env))
    return path


def _reading(tmp, sweeps=(3, 5)):
    """Two steps of 20 ms; the NMS loop runs its body ``sweeps`` times."""
    ops, modules, host = [], [], []
    for i, n in enumerate(sweeps):
        t = i * 22 * MS + 2 * MS
        modules.append(("jit_step(7)", t, 20 * MS, ""))
        ops += [
            ("convolution.1", t, 6 * MS, ""),
            ("fusion.8", t + 6 * MS, 1 * MS, ""),
            ("fusion.1021", t + 7 * MS, 0.1 * MS, ""),      # peeled out of the loop: no sweep
            ("while.2", t + 7.1 * MS, n * 0.5 * MS, ""),
            ("fusion.7", t + 11 * MS, 1 * MS, ""),
            ("fusion.40", t + 12 * MS, 4 * MS, ""),
            ("fusion.41", t + 16 * MS, 1 * MS, ""),
            ("multiply.5", t + 17 * MS, 1 * MS, ""),
            ("convolution.1", t + 18 * MS, 2 * MS, ""),
        ]
        for k in range(n):
            ops.append(("and_reduce_fusion", t + 7.1 * MS + k * 0.5 * MS, 0.3 * MS, ""))
            ops.append(("fusion.60", t + 7.4 * MS + k * 0.5 * MS, 0.2 * MS, ""))
        host.append(("next_batch", t - 2 * MS, 1.5 * MS))
        host.append(("dispatch", t - 0.5 * MS, 0.4 * MS))
    host.append(("sync", -1 * MS, 1 * MS))
    host.append(("sync", 42 * MS, 2 * MS))
    r = {
        "trace": {"devices": {0: {
            "XLA Ops": ops,
            "XLA Modules": [("jit_step(7)", i * 22 * MS + 2 * MS, 20 * MS, "") for i in (-2, -1)] + modules,
        }}},
        "host_spans": [(n, s + CLOCK, d) for n, s, d in host], "sync_every": 2,
        "program_name": "jit_step", "chips": 1, "scopes": {}, "trace_root": tmp,
        "counters": {"steps": 2, "global_batch": 8, "data_stall_s": 0.0, "sync_every": 2},
    }
    readers.prepare(r)
    return r


def metric(name):
    path = os.path.join(REPO, "perfbench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("m25_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture
def tracer():
    from mx_rcnn_tpu import obs

    obs.reset()
    yield obs.tracer()
    obs.reset()


# -- the program read from the trace ------------------------------------------------


def test_program_and_start_time_come_out_of_the_trace_file(tmp_path):
    path = _xplane(str(tmp_path))
    mods = hlo_module.modules_of_xplane(path)
    assert list(mods) == ["jit_step(7)"]
    main = {i["name"]: i for i in mods["jit_step(7)"]["computations"]["main"]}
    assert main["while.2"]["calls"] == ["nms_body", "nms_cond"]
    assert main["fusion.41"]["calls"] == ["fused_copy"]          # the packed spelling
    assert main["convolution.1"]["opcode"] == "convolution"
    assert hlo_module.loop_bodies(mods["jit_step(7)"]) == {"nms_body"}
    assert hlo_module.profile_start_ns(path) == 1790000000000000000


def test_a_fusion_without_metadata_takes_the_name_of_what_it_holds(tmp_path):
    mod = hlo_module.modules_of_xplane(_xplane(str(tmp_path)))["jit_step(7)"]
    names = hlo_module.resolve(mod)
    # two of its three named instructions are ROIAlign's backward
    assert "transpose(jvp(roi_align))" in names["fusion.40"]
    assert names["fusion.41"] == "" and names["multiply.5"].endswith("/mul")
    assert names["convolution.1"].endswith("backbone/conv")     # its own name is kept


@pytest.mark.parametrize("path,scoped", [
    ("jit(step)/jit(main)/mul", False),
    ("jit(step)/jvp()/slice", False),
    ("reduce_sum", False),
    ("", False),
    ("jit(step)/rng/jit(_threefry_fold_in)", True),
    ("jit(step)/jvp(rcnn_loss)", True),
    ("jit(step)/optimizer/mul", True),
    ("jit(step)/transpose(jvp(TwoStageDetector.features))/backbone/conv", True),
    ("jit(step)/jvp(proposals)/vmap(jit(nms_indices))/while", True),
])
def test_what_counts_as_a_name(path, scoped):
    assert hlo_module.has_scope(path) is scoped


# -- the device-trace readers -----------------------------------------------------


def test_unscoped_share_is_what_no_named_op_covers(tmp_path):
    _xplane(str(tmp_path))
    r = _reading(str(tmp_path))
    # busy 17.6 + 18.6 ms; without a name after resolution: fusion.41 and multiply.5
    assert metric("unscoped_share.train")(r) == pytest.approx(100.0 * (2 + 2) / 36.2)
    assert r["hlo_module"] is not None


def test_unscoped_share_falls_back_on_the_harness_scopes(tmp_path):
    r = _reading(str(tmp_path))                                   # no trace file to find
    r["scopes"] = {"convolution.1": "jit(step)/backbone/conv"}
    assert metric("unscoped_share.train")(r) == pytest.approx(100.0 * (36.2 - 16) / 36.2)
    assert r["hlo_module"] is None


def test_nms_ms_is_the_nms_inside_proposals(tmp_path):
    _xplane(str(tmp_path))
    r = _reading(str(tmp_path), sweeps=(3, 5))
    # fusion.1021 0.1 + the loop (1.5, 2.5) + the sort 1.0; the top-k is not NMS
    assert metric("nms_ms.train")(r) == pytest.approx((0.1 + 1.5 + 1.0 + 0.1 + 2.5 + 1.0) / 2)


def test_a_body_run_3_and_5_times_reads_4_sweeps_a_step(tmp_path):
    _xplane(str(tmp_path))
    assert metric("nms_sweeps.train")(_reading(str(tmp_path), sweeps=(3, 5))) == pytest.approx(4.0)


def test_sweeps_count_under_the_names_of_an_older_executable(tmp_path):
    r = _reading(str(tmp_path), sweeps=(3, 5))                    # no trace file: one body
    old = "jit(step)/jvp(proposals)/vmap(jit(nms_indices))/while/body/reduce_or"
    r["scopes"] = {"and_reduce_fusion": old, "while.2": "jit(step)/jvp(proposals)/vmap(jit(nms_indices))/while"}
    assert metric("nms_sweeps.train")(r) == pytest.approx(4.0)
    assert metric("nms_ms.train")(r) == pytest.approx((1.5 + 2.5) / 2)


# -- the program-span readers -------------------------------------------------------


def test_a_put_half_outside_the_window_is_clipped(tmp_path, tracer):
    r = _reading(str(tmp_path))
    lo, hi = r["lo"], r["hi"]
    assert (lo, hi) == (pytest.approx(0.0), pytest.approx(44 * MS))
    rows = [
        ("feed.put", CLOCK + lo - 1 * MS, 2 * MS, {"seq": 0}),    # half before the window
        ("feed.put", CLOCK + 10 * MS, 3 * MS, {"seq": 1}),
        ("feed.put", CLOCK + hi + 5 * MS, 1 * MS, {"seq": 2}),    # after it
    ]
    inside = program_spans.in_window(r, rows)
    assert [(s, d) for _, s, d, _ in inside] == [
        (pytest.approx(lo), pytest.approx(1 * MS)), (pytest.approx(10 * MS), pytest.approx(3 * MS)),
    ]
    for name, s, d, attrs in rows:
        tracer.record(name, int(s), int(d), subsystem="train", attrs=attrs)
    assert metric("feed_put_ms.train")(r) == pytest.approx((1 + 3) / 2)


def test_setup_readers_count_what_ended_before_the_window(tmp_path, tracer):
    r = _reading(str(tmp_path))
    start = program_spans.traced_from_ns(r)
    assert start == pytest.approx(CLOCK - 1 * MS)
    s = 1e9
    for name, t, d in [("setup.init_state", -30 * s, 2 * s), ("setup.optimizer", -28 * s, 0.5 * s),
                       ("setup.plan", -27 * s, 0.1 * s), ("setup.step", -26 * s, 0.4 * s),
                       ("setup.init_state", +9 * s, 7 * s)]:            # a later build: not set-up
        tracer.record(name, int(start + t), int(d), subsystem="train")
    # one inside the first phase (counted there), two outside, the reference's after the window
    for t, d in [(-29.5 * s, 0.3 * s), (-20 * s, 1.2 * s), (-10 * s, 0.3 * s), (+3 * s, 60 * s)]:
        tracer.record("jit.compile", int(start + t), int(d), subsystem="jit",
                      attrs={"fun_name": "f", "cache_hit": False})
    assert metric("setup_init_s.train")(r) == pytest.approx(3.0)
    assert metric("setup_compile_s.train")(r) == pytest.approx(1.5)
    # the step traced for 6 s, its lowering (which traces 1 s more inside) for 2 s; a trace
    # inside a phase is the phase's; the reference's after the window is nobody's
    for name, t, d in [("jit.trace", -25 * s, 6 * s), ("jit.lower", -19 * s, 2 * s),
                       ("jit.trace", -18.5 * s, 1 * s), ("jit.trace", -29.9 * s, 0.2 * s),
                       ("jit.trace", +4 * s, 9 * s)]:
        tracer.record(name, int(start + t), int(d), subsystem="jit", attrs={"fun_name": "step"})
    assert metric("setup_lower_s.train")(r) == pytest.approx(8.0)
    assert metric("setup_start_s.train")(r) is None                # no such span: nothing read
    tracer.record("setup.import", int(start - 45 * s), int(11 * s), subsystem="process")
    tracer.record("setup.backend", int(start - 34 * s), int(0.5 * s), subsystem="process")
    assert metric("setup_start_s.train")(r) == pytest.approx(11.5)
    assert metric("setup_init_s.train")(r) == pytest.approx(3.0)   # the process's are not build_all's


def test_a_full_buffer_is_not_read(tmp_path, tracer, capsys):
    from mx_rcnn_tpu.obs.tracing import SPAN_BUFFER

    r = _reading(str(tmp_path))
    start = program_spans.traced_from_ns(r)
    tracer.record("setup.init_state", int(start - 30e9), int(2e9), subsystem="train")
    assert metric("setup_init_s.train")(r) == pytest.approx(2.0)
    for i in range(SPAN_BUFFER):  # the set-up's span has fallen out: a sum of the rest would read low
        tracer.record("jit.trace", int(start - 20e9) + i, 1, subsystem="jit")
    assert program_spans.spans(subsystem="jit") is None
    assert metric("setup_lower_s.train")(r) is None and metric("setup_init_s.train")(r) is None
    assert "full" in capsys.readouterr().err


def test_a_program_without_the_buffer_reads_nothing(tmp_path, monkeypatch):
    from mx_rcnn_tpu import obs

    monkeypatch.setattr(obs, "tracer", lambda: object())           # the parent: no recent()
    r = _reading(str(tmp_path))
    assert program_spans.spans(subsystem="train") is None
    for name in ("setup_init_s.train", "setup_compile_s.train", "feed_put_ms.train",
                 "setup_lower_s.train", "setup_start_s.train"):
        assert metric(name)(r) is None


def test_new_metrics_are_listed_with_their_cell_and_a_reader():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = {m["name"]: m for m in bench["per_layer"]}
    for name, source, moves in [
        ("setup_init_s.train", "program_counter", "setup_s"),
        ("setup_compile_s.train", "program_counter", "setup_s"),
        ("feed_put_ms.train", "program_counter", "train_img_s_chip"),
        ("unscoped_share.train", "device_trace", "train_img_s_chip"),
        ("nms_ms.train", "device_trace", "train_img_s_chip"),
        ("nms_sweeps.train", "device_trace", "train_img_s_chip"),
        ("setup_lower_s.train", "program_span", "setup_s"),
        ("setup_start_s.train", "program_span", "setup_s"),
    ]:
        assert new[name]["source"] == source and new[name]["moves"] == moves
        assert new[name]["workloads"] == ["vgg16_voc07.train_b16"]
        assert callable(metric(name))
