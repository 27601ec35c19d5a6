"""``perfbench/step_parts.py`` on hand-made fragments: an ``Hlo Proto`` with
operand ids (adoption from the producer, from the consumer, along a chain,
across a tuple, and an op nobody owns), a ``while`` dropped for its body, a
fusion that holds a ``dot`` against one that does not, the pass grammar, the
six readers over the rows and the operator's table over the same file."""

import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _hlo_fragment import instr, module, xplane  # noqa: E402

from perfbench import readers, step_parts  # noqa: E402
from perfbench import trace_reduce as tr  # noqa: E402

MS = 1e6
P = "jit(step)/"
FWD = P + "jvp(TwoStageDetector.features)/backbone/"
# what the chip's step carries (PR 36's traces): the forward's path once more inside the transposed one
BWD = (P + "transpose(jvp(TwoStageDetector.features))/backbone/"
       "jvp(TwoStageDetector.features)/backbone/checkpoint/")
REMAT = BWD + "rematted_computation/"

STEP = module("jit_step", [
    (1, "fused_dot", [instr("p.1", "parameter", 1), instr("dot.1", "dot", 2, [1])]),
    (2, "fused_mul", [instr("p.2", "parameter", 1), instr("multiply.1", "multiply", 2, [1])]),
    (3, "fused_copy", [instr("p.3", "parameter", 1), instr("copy.9", "copy", 2, [1])]),
    (4, "body", [
        instr("p.4", "parameter", 1),
        instr("gte.4", "get-tuple-element", 2, [1]),
        instr("fusion.b1", "fusion", 3, [2], FWD + "l6/kda/scan/inter/while/body/mul", calls=[2]),
        instr("copy.b2", "copy", 4, [2]),                 # its producer is the loop's tuple element
        instr("tuple.4", "tuple", 5, [3, 4]),
    ]),
    (5, "cond", [instr("p.5", "parameter", 1), instr("compare.5", "compare", 2, [1])]),
    (6, "main", [
        instr("p0", "parameter", 1),
        instr("fusion.10", "fusion", 2, [1], FWD + "l6/kda/proj/dense/dot_general", calls=[1]),
        instr("fusion.11", "fusion", 3, [2], FWD + "l6/kda/proj/mul", calls=[2]),
        instr("copy.1", "copy", 4, [3]),                  # producer: fusion.11
        instr("copy.2", "copy", 5, [1]),                  # producer a parameter -> consumer fusion.12
        instr("fusion.12", "fusion", 6, [5], FWD + "l11/mla/attn/dot_general", calls=[1]),
        instr("copy-start.1", "copy-start", 7, [6]),
        instr("copy-done.1", "copy-done", 8, [7]),        # a chain of two nameless ops -> fusion.12
        # CSE hands every reader of a zero ONE constant, under whichever name came first: no owner's
        instr("constant.1", "constant", 9, op_name=P + "jvp(TwoStageDetector.rpn)/rpn/zeros_like"),
        instr("broadcast.9", "broadcast", 10, [9]),       # that constant behind it, nobody reads it
        instr("tuple.2", "tuple", 11, [3]),
        instr("while.1", "while", 12, [11], FWD + "l6/kda/scan/inter/while", calls=[4, 5]),
        instr("custom-call.1", "custom-call", 13, [3],
              REMAT + "l6/kda/scan/intra/jit(_call)/kda_intra_fwd/pallas_call",
              target="tpu_custom_call"),
        instr("custom-call.2", "custom-call", 14, [3], P + "jvp(proposals)/vmap(topk)/jit(_take)/gather",
              target="GatherOnTpu"),
        instr("fusion.20", "fusion", 15, [13], REMAT + "l6/kda/proj/dense/dot_general", calls=[1]),
        instr("fusion.21", "fusion", 16, [15], BWD + "l6/kda/proj/dense/transpose(dot_general)", calls=[1]),
        instr("fusion.22", "fusion", 17, [16],
              BWD + "l6/kda/proj/checkpoint/rematted_computation/mul", calls=[2]),
        instr("fusion.23", "fusion", 18, [16], calls=[3]),     # XLA's: a copy in a fusion, no name inside
        instr("fusion.30", "fusion", 19, [17], P + "optimizer/mul", calls=[2]),
        instr("tuple.3", "tuple", 20, [1, 19]),
        instr("gte.1", "get-tuple-element", 21, [20], index=1),
        instr("copy.5", "copy", 22, [21]),                # across the tuple: fusion.30
        instr("convolution.1", "convolution", 23, [1], FWD + "patchify/conv/conv_general_dilated"),
        instr("fusion.40", "fusion", 24, [23], FWD + "l0/ffn/dense/dot_general", calls=[1]),
    ]),
])

# (op, offset ms, duration ms) of one 100 ms step
EVENTS = [
    ("convolution.1", 0, 2), ("fusion.10", 2, 6), ("fusion.11", 8, 3), ("copy.1", 11, 1),
    ("copy.2", 12, 1), ("fusion.12", 13, 4), ("copy-start.1", 17, 0.01), ("copy-done.1", 17.01, 0.99),
    ("broadcast.9", 18, 0.5),
    ("while.1", 19, 6), ("fusion.b1", 19.5, 2), ("copy.b2", 21.5, 0.5), ("fusion.b1", 22, 2),
    ("copy.b2", 24, 0.5),                                   # the loop leaves 1 ms uncovered
    ("fusion.40", 25, 5), ("custom-call.2", 30, 1),
    ("custom-call.1", 40, 3), ("fusion.20", 43, 5), ("fusion.21", 48, 8), ("fusion.22", 56, 4),
    ("fusion.23", 60, 2), ("fusion.30", 70, 10), ("copy.5", 80, 1),
]
STEPS = 2


def conf(name="ling3_flash_vl_det"):
    with open(os.path.join(REPO, "perfbench", "configs", f"{name}.json")) as f:
        return json.load(f)


def reading(tmp, hlo=STEP, config=None):
    ops, modules = [], []
    for i in range(STEPS):
        t = i * 100 * MS
        modules.append(("jit_step(7)", t, 100 * MS, ""))
        ops += [(nm, t + at * MS, d * MS, "") for nm, at, d in EVENTS]
    xplane(str(tmp), hlo)
    r = {
        "trace": {"devices": {0: {"XLA Ops": ops, "XLA Modules": modules}}},
        "host_spans": [("sync", 5e12 - 1 * MS, 1 * MS), ("sync", 5e12 + 199 * MS, 1 * MS)],
        "sync_every": 2, "program_name": "jit_step", "chips": 1, "scopes": {},
        "trace_root": str(tmp), "counters": {"steps": 2, "global_batch": 2, "sync_every": 2},
        "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        "config": config or conf(),
    }
    readers.prepare(r)
    return r


def metric(name):
    path = os.path.join(REPO, "perfbench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("m36_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture
def rows(tmp_path):
    r = reading(tmp_path)
    return {row.name: row for row in step_parts.classify(r) if row.start < 100 * MS}


# -- owner ------------------------------------------------------------------------------


@pytest.mark.parametrize("op, kind, layer, how", [
    ("copy.1", "kda", "l6", "producer"),          # a copy takes its producer's name
    ("copy.2", "mla", "l11", "consumer"),         # its producer is a parameter: the consumer's
    ("copy-done.1", "mla", "l11", "producer"),    # through the nameless copy-start behind it
    ("copy.5", "optimizer", "", "producer"),      # through a get-tuple-element and its tuple
    ("fusion.23", "kda", "l6", "producer"),       # a fusion XLA made of a copy
])
def test_an_op_without_a_name_is_adopted(rows, op, kind, layer, how):
    row = rows[op]
    assert (row.kind, row.layer, row.adopted, row.part) == (kind, layer, how, "copy")


@pytest.mark.parametrize("op", [
    "broadcast.9",      # a constant behind it (a named one, even), nothing reads it
    "copy.b2",          # a loop's tuple element behind it, the body's result tuple ahead
])
def test_an_op_nobody_produced_or_reads_stays_unowned(rows, op):
    row = rows[op]
    assert (row.owner, row.kind, row.layer, row.adopted, row.part) == ("", "", "", "", "copy")


def test_an_op_with_its_own_name_keeps_it(rows):
    row = rows["fusion.11"]
    assert row.owner == FWD + "l6/kda/proj/mul" and row.adopted == ""


# -- leaves only -------------------------------------------------------------------------


def test_a_loop_is_dropped_for_its_body_and_keeps_what_the_body_leaves(tmp_path):
    r = reading(tmp_path)
    got = [row for row in step_parts.classify(r) if row.start < 100 * MS]
    loop = [row for row in got if row.name == "while.1"]
    # 6 ms of loop, 5 ms of body ops: 0.5 ms before the first, 0.5 after the last
    assert sorted((row.start / MS, row.dur / MS) for row in loop) == [(19.0, 0.5), (24.5, 0.5)]
    assert {(row.kind, row.part, row.pass_) for row in loop} == {("kda", "core", "fwd")}
    assert sum(row.name == "fusion.b1" for row in got) == 2
    info = r["step_parts"]
    assert info["loop_overhead_ns"] == pytest.approx(STEPS * 1 * MS)
    assert info["overlap_ns"] == pytest.approx(0)
    assert info["busy_ns"] == pytest.approx(r["busiest_busy_s"] * 1e9)


@pytest.mark.parametrize("key", ["pass_", "part", "kind", "layer", "adopted"])
def test_the_rows_partition_the_busy_time_by_any_key(tmp_path, key):
    r = reading(tmp_path)
    groups = {}
    for row in step_parts.classify(r):
        groups.setdefault(getattr(row, key), []).append((row.start, row.dur))
    assert sum(tr.union_ns(iv) for iv in groups.values()) == pytest.approx(
        r["busiest_busy_s"] * 1e9, rel=1e-9)


# -- part --------------------------------------------------------------------------------


@pytest.mark.parametrize("op, part", [
    ("fusion.10", "dense"),          # the computation it calls holds a dot
    ("fusion.11", "glue"),           # the one it calls holds a multiply
    ("convolution.1", "dense"),      # the op itself
    ("fusion.12", "core"),           # under attn, whatever it holds
    ("fusion.b1", "core"),           # under scan
    ("custom-call.1", "kernel"),     # Mosaic's custom call
    ("custom-call.2", "glue"),       # a custom call XLA made of a gather is no kernel
    ("fusion.23", "copy"),           # no name of its own after resolve
    ("fusion.30", "glue"),
])
def test_the_part_of_an_op(rows, op, part):
    assert rows[op].part == part


def test_experts_are_core_and_the_router_is_not():
    graph = {"nodes": {}, "_dots": {}}
    node = {"opcode": "fusion", "target": "", "calls": []}
    assert step_parts.part_of(graph, node, FWD + "l6/moe/experts/mul") == "core"
    assert step_parts.part_of(graph, node, FWD + "l6/moe/router/mul") == "glue"


# -- pass --------------------------------------------------------------------------------

CPU = "jit(step)/"


@pytest.mark.parametrize("path, want", [
    # the four forms jax gives a block under jax.checkpoint that holds a glue function under its own
    (CPU + "jvp(backbone)/l0/kda/proj/dense/dot_general", "fwd"),
    (CPU + "transpose(jvp(backbone))/jvp(backbone)/checkpoint/rematted_computation/l0/kda/proj/dense/dot_general",
     "remat"),
    (CPU + "transpose(jvp(backbone))/jvp(backbone)/checkpoint/l0/kda/proj/dense/transpose(dot_general)", "bwd"),
    (CPU + "transpose(jvp(backbone))/jvp(backbone)/checkpoint/l0/kda/proj/checkpoint/rematted_computation/mul",
     "bwd"),
    # a Pallas kernel's pair under custom_vjp: the forward kernel in all three passes' places, the backward one
    (FWD + "l16/mamba/scan/jit(_call)/selective_scan_fwd/pallas_call", "fwd"),
    (REMAT + "l16/mamba/scan/jit(_call)/selective_scan_fwd/pallas_call", "remat"),
    (BWD + "l16/mamba/scan/jit(_call)/selective_scan_bwd/pallas_call", "bwd"),
    # outside the layers a recomputation is no block's
    (P + "transpose(jvp(roi_align))/checkpoint/rematted_computation/mul", "bwd"),
    (P + "optimizer/mul", "fwd"),
    # XLA joins the paths of ops it merged: the first stands
    (FWD + "l0/ffn/mul;" + BWD + "l0/ffn/mul", "fwd"),
    ("", "fwd"),
])
def test_the_pass_grammar(path, want):
    assert step_parts.pass_of(path) == want


@pytest.mark.parametrize("path, want", [
    (FWD + "l6/kda/proj/dense/dot_general", ("l6", "kda")),
    (REMAT + "l11/moe/experts/mul", ("l11", "moe")),
    (FWD + "l6/add", ("l6", "block")),
    (FWD + "patchify/conv/conv_general_dilated", ("", "patchify")),
    (BWD.replace("checkpoint/", "") + "neck/conv/transpose(conv_general_dilated)", ("", "neck")),
    (FWD + "mul", ("", "backbone")),
    (P + "jvp(proposals)/vmap(nms)/jit(nms_indices)/while/body/and", ("", "proposals")),
    (P + "jvp(TwoStageDetector.rpn)/rpn/rpn._heads/conv/conv_general_dilated", ("", "rpn")),
    (P + "jvp(TwoStageDetector.box)/box_head/reshape;" + P + "jvp(roi_align)/mul", ("", "box_head")),
    ("", ("", "")),
])
def test_the_layer_and_kind_of_a_path(path, want):
    assert step_parts.place(path) == want


# -- the six readers ---------------------------------------------------------------------

KDA_DENSE = 6 + 5 + 8          # fusion.10, its recomputation, its backward
KDA_GLUE = 3 + 4               # fusion.11, the backward's fusion.22
WANT = {
    "mixer_proj_ms.train": KDA_DENSE + KDA_GLUE,
    "mixer_glue_ms.train": KDA_GLUE,
    "remat_ms.train": 3 + 5,                                   # the kernel and fusion.20
    "xla_copy_ms.train": 1 + 1 + 1 + 0.5 + 2 * 0.5 + 2 + 1,    # copies, the pair (start + done), broadcast, fusion.23
    "unowned_share.train": 100.0 * (0.5 + 2 * 0.5) / 63.5,     # broadcast.9 and the body's copies of 63.5 ms busy
}
SIX = sorted(WANT) + ["mixer_proj_roofline.train"]


@pytest.mark.parametrize("name", sorted(WANT))
def test_readers_on_the_fragment(tmp_path, name):
    assert metric(name)(reading(tmp_path)) == pytest.approx(WANT[name], rel=1e-6)


def test_the_roofline_is_the_need_over_the_projections_time(tmp_path):
    from perfbench.flops import least_seconds
    from perfbench.mixer_need import mixer_proj_need

    r = reading(tmp_path)
    least, bound = least_seconds(mixer_proj_need(r["config"]["reference"], 2), r["peak"])
    assert bound == "flops"
    want = 100.0 * least / (WANT["mixer_proj_ms.train"] / 1e3)
    assert metric("mixer_proj_roofline.train")(r) == pytest.approx(want, rel=1e-6)


@pytest.mark.parametrize("name", SIX)
def test_a_reader_reads_nothing_on_the_first_cell(tmp_path, name):
    r = reading(tmp_path, config=conf("vgg16_voc07"))
    assert metric(name)(r) is None


@pytest.mark.parametrize("name", SIX)
def test_a_reader_reads_nothing_from_a_trace_without_an_hlo_proto(tmp_path, name):
    r = reading(tmp_path, hlo=None)
    assert metric(name)(r) is None
    assert r["hlo_module"] is None and r["step_parts"]["rows"] is None


@pytest.mark.parametrize("hlo, why", [
    # an operand id no instruction of the computation has
    (module("jit_step", [(1, "main", [instr("fusion.10", "fusion", 2, [77])])]), "operand id 77"),
    # an event the program does not name
    (module("jit_step", [(1, "main", [instr("fusion.10", "fusion", 2)])]), "is named"),
    # bytes that are no protobuf
    (b"\x0a\x03\x1f\xff\xff", "wire type"),
])
def test_a_reader_reads_nothing_from_a_program_it_cannot_follow(tmp_path, hlo, why):
    r = reading(tmp_path, hlo=hlo)
    for name in SIX:
        assert metric(name)(r) is None
    assert why in r["step_parts"]["error"]


def test_classify_leaves_the_reading_as_the_accepted_readers_know_it(tmp_path):
    r = reading(tmp_path)
    unscoped = metric("unscoped_share.train")(r)
    before = {k: (list(v) if isinstance(v, list) else dict(v) if isinstance(v, dict) else v)
              for k, v in r.items()}
    assert step_parts.classify(r) is step_parts.classify(r)        # cached
    assert set(r) - set(before) == {"step_parts"}
    for key in ("ops", "op_names", "hlo_module", "breakdown", "modules"):
        assert r[key] == before[key]
    assert metric("unscoped_share.train")(r) == unscoped


def test_new_metrics_are_listed_with_the_three_decoder_cells_and_a_reader():
    from perfbench.spec import Spec

    spec = Spec(REPO)
    decoder_cells = ["ling3_flash_vl_det.train_coco", "nemotron_twotower_det.train_coco",
                     "phi4_mini_flash_det.train_coco"]
    listed = {m["name"]: m for m in spec.bench["per_layer"]}
    assert [m["name"] for m in spec.bench["per_layer"][-6:]] == [
        "mixer_proj_ms.train", "mixer_glue_ms.train", "mixer_proj_roofline.train",
        "remat_ms.train", "xla_copy_ms.train", "unowned_share.train"]
    for name in SIX:
        m = listed[name]
        assert m["workloads"] == decoder_cells and m["moves"] == "train_img_s_chip"
        assert m["source"] == "device_trace" and callable(spec.reader(name))
        assert name not in [x["name"] for x in spec.metrics_of("vgg16_voc07.train_b16", "per_layer")]


# -- the operator's table ----------------------------------------------------------------


def _tool():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    return obs_report


def _profile(tmp, hlo):
    """A profile directory as ``train.py --profile`` leaves one, its XPlane
    holding a chip's plane with the fragment's two steps."""
    ops, modules = [], []
    for i in range(STEPS):
        t = 1000 + i * 100 * MS
        modules.append(("jit_step(7)", t, 100 * MS))
        ops += [(nm, t + at * MS, d * MS) for nm, at, d in EVENTS]
    # a step the window cut in two: its ops are in the file, its program's run is not
    ops.append(("fusion.30", 1000 + 200 * MS, 10 * MS))
    xplane(str(tmp), hlo, modules=modules, ops=ops, sub=("plugins", "profile", "run1"))
    return str(tmp)


def test_the_operator_s_table_reads_what_the_readers_read(tmp_path):
    tool = _tool()
    parts = tool.device_parts(_profile(tmp_path, STEP))
    assert parts["steps"] == STEPS and parts["program"] == "jit_step"
    assert parts["busy_ms"] == pytest.approx(63.5)
    for name in WANT:
        assert parts["totals"][name.replace(".train", "")] == pytest.approx(WANT[name], rel=1e-6)
    assert sum(parts["by_pass"].values()) == pytest.approx(parts["busy_ms"])
    assert sum(parts["by_part"].values()) == pytest.approx(parts["busy_ms"])
    assert ["l6/kda", "dense", "bwd", 8.0] in parts["by_layer_part_pass"]
    assert ["kda", "glue", "bwd", 4.0] in parts["by_kind_part_pass"]
    assert ["l6/kda", "producer", "bwd", 2.0] in parts["copy_by_owner"]      # fusion.23
    assert ["(nobody)", "none", "fwd", 1.5] in parts["copy_by_owner"]


def test_a_profile_without_an_hlo_proto_leaves_the_report_as_it_was(tmp_path, capsys):
    tool = _tool()
    obs = tmp_path / "obs"
    obs.mkdir()
    out = {}
    for name, args in (("before", []), ("after", ["--profile-dir", _profile(tmp_path / "p", None)])):
        out[name] = str(tmp_path / f"{name}.json")
        assert tool.main(["--obs-dir", str(obs), "--out", out[name], *args]) == 0
    with open(out["before"]) as f:
        before = json.load(f)
    with open(out["after"]) as f:
        after = json.load(f)
    assert "device_parts" not in after
    after.pop("profile")        # the section --profile-dir has always added
    assert after == before
    assert "device_parts: none" in capsys.readouterr().err
    assert tool.device_parts(str(tmp_path / "nothing")) is None


def test_the_report_gains_the_section_where_the_profile_has_the_program(tmp_path):
    tool = _tool()
    obs = tmp_path / "obs"
    obs.mkdir()
    out = str(tmp_path / "report.json")
    assert tool.main(["--obs-dir", str(obs), "--out", out, "--profile-dir",
                      _profile(tmp_path / "p", STEP)]) == 0
    with open(out) as f:
        parts = json.load(f)["device_parts"]
    assert parts["totals"]["remat_ms"] == pytest.approx(WANT["remat_ms.train"])
