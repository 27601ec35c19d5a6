"""``ling3_flash_vl_det`` at tiny widths on the CPU: the program's backbone
against the plain reference (forward and every leaf's gradient, seeded), the
reference's own share test, and the tiny configuration through the harness's
run with the control and the faults planted, which have to come out NOT
correct.

Tolerances (float32 on both sides; the program chunks the recurrence, blocks
the attention and sorts its dispatch, so only the order of summation
differs): features 1e-4 of their scale (measured 3e-6), a leaf's gradient
1e-3 of its own or the median leaf's norm (measured 2e-5); through the
harness the limits of ``test_benchmark_reference.py``."""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _ling_tiny import CELL, make_root, small_program_choices, tiny_config  # noqa: E402

from perfbench import weights as W  # noqa: E402
from perfbench.reference import backbone_ling3_flash_vl as B  # noqa: E402

LIMITS = {"loss2": 1e-3, "loss3": 1e-3, "grad1": 1e-3, "change": 1e-2, "grad1_layer": 1e-3,
          "change_layer": 1e-2, "dir1": 1e-3, "dirc": 1e-3}


@pytest.fixture(scope="module", autouse=True)
def _seams_at_tiny_size():
    with small_program_choices():
        yield


def _program_backbone(conf):
    from mx_rcnn_tpu.config import apply_overrides, get_config
    from mx_rcnn_tpu.models.build import build_backbone

    cfg = apply_overrides(get_config(conf["preset"]), conf["overrides"])
    return build_backbone(cfg.model.backbone, out_levels=(4,), dtype=jnp.float32)


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_program_backbone_is_the_reference_forward_and_gradients(seed):
    conf = tiny_config()
    ref = conf["reference"]
    w = W.make_weights(seed, B.specs(ref))
    x = jax.random.normal(W.seed_key(seed, 5), (2, 128, 128, 3))
    cot = jax.random.normal(W.seed_key(seed, 6), (2, 8, 8, ref["feature_channels"]))
    backbone = _program_backbone(conf)

    def program(w):
        variables = {"params": W.nest(w, "params")["backbone"],
                     "constants": W.nest(w, "constants")["backbone"]}
        feats, sown = backbone.apply(variables, x, mutable=["counters"])
        return feats[4], sown["counters"]

    def plain(w):
        return jnp.concatenate([B.features(ref, w, x[i:i + 1])[4] for i in range(2)])

    got, counters = program(w)
    want = plain(w)
    assert got.shape == want.shape == (2, 8, 8, 32)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, atol=1e-4 * scale)
    assert float(counters["moe_dropped_slots"][0]) == 0.0

    trainable = {p: v for p, v in w.items() if p.startswith("params/")}
    rest = {p: v for p, v in w.items() if p not in trainable}
    g_prog = jax.grad(lambda t: jnp.sum(program({**t, **rest})[0] * cot))(trainable)
    g_ref = jax.grad(lambda t: jnp.sum(plain({**t, **rest}) * cot))(trainable)
    norms = {p: float(jnp.linalg.norm(g)) for p, g in g_ref.items()}
    median = float(np.median(list(norms.values())))
    assert min(norms.values()) > 0.0, "a leaf the reference's features never read"
    for p, g in g_ref.items():
        gap = float(jnp.linalg.norm(g_prog[p] - g)) / max(norms[p], median)
        assert gap < 1e-3, (p, gap)


def test_the_reference_s_shares_add_up_to_its_uncut_layer():
    """The routed parts of every share plus the shared expert counted once are
    the uncut layer, in the reference's own arithmetic (the program's op has
    the same test in tests/test_ops_decoder.py)."""
    conf = tiny_config()
    dc = conf["reference"]["decoder"]
    whole = dict(dc, experts_first=0, num_experts=dc["num_experts_published"])
    ref = dict(conf["reference"], decoder=whole)
    w = W.make_weights(11, B.specs(ref))
    p = "params/backbone/l4/moe"
    x = jax.random.normal(jax.random.PRNGKey(2), (50, dc["hidden_size"]))
    want, slots = B.experts_here(whole, w, p, x, None)
    assert float(slots) == 50 * dc["num_experts_per_tok"]   # every pick is held by the whole
    shared = B._swiglu(w, f"{p}/shared", x, None)
    held = dc["num_experts"]
    total = shared
    for first in range(0, dc["num_experts_published"], held):
        share = dict(dc, experts_first=first, num_experts=held)
        total = total + B.experts_here(share, w, p, x, None)[0] - shared
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert float(jnp.abs(want - shared).max()) > 1e-2   # the routed part is not nothing


# -- through the harness -------------------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("ling")), limits=LIMITS)


def run(root, seed=2**31 + 11):
    from perfbench.run import run_cell

    return run_cell(CELL, seed, 1.0, 0, root=root, require_chip=False)


def test_the_tiny_configuration_runs_correct_through_the_harness(root):
    out = run(root)
    assert out["correct"], out["compared"]
    assert set(out["metrics"]) == {"train_img_s_chip", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["compared"]["built_in_window"] == [0, 0]
    counters = out["run"]["counters"]
    assert counters["moe_dropped_slots"] == 0.0 and counters["moe_slots_here"] > 0
    assert counters["moe_load_max_over_mean"] >= 1.0
    assert 0.0 <= counters["moe_tokens_without_held_expert"] < 1.0


def test_a_step_that_returns_its_state_unchanged_is_not_correct(root, monkeypatch):
    from perfbench import program

    real = program.build_train

    def build(*args, **kw):
        state, step_fn, plan, gb = real(*args, **kw)

        def step(state, batch):
            keep = jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), state)
            _, metrics = step_fn(state, batch)
            return keep, metrics

        return state, step, plan, gb

    monkeypatch.setattr(program, "build_train", build)
    out = run(root)
    assert not out["correct"]
    assert out["compared"]["change"][0] == pytest.approx(1.0, abs=1e-3)


def test_held_experts_left_out_of_the_program_is_not_correct(root, monkeypatch):
    """The expert layer without its routed part: the held experts' leaves get
    no gradient (``grad1`` reads 1 on them) and do not move."""
    from mx_rcnn_tpu.models import decoder

    real = decoder.held_experts

    def nothing(x, *args, **kw):
        y, counters = real(x, *args, **kw)
        return jnp.zeros_like(y), counters

    monkeypatch.setattr(decoder, "held_experts", nothing)
    out = run(root)
    assert not out["correct"]
    assert out["compared"]["grad1"][0] == pytest.approx(1.0, abs=1e-3)
    assert "/moe/experts/" in out["run"]["extra"]["grad1_leaf"]


def _sides(layers=6, experts=8):
    """Leaf norms of two sides that agree: every expert's three matrices in
    every layer, expert 0 the favourite, and a few other leaves."""
    norms = {f"params/backbone/l{l}/moe/experts/e{e}/{m}/kernel": 4.0 if e == 0 else 0.5
             for l in range(6, 6 + layers) for e in range(experts) for m in ("gate", "up", "down")}
    norms.update({f"params/backbone/l{l}/kda/q/kernel": 1.0 for l in range(6, 6 + layers)})
    side = {"steps": [], "grad1": norms, "change": dict(norms)}
    return side, {**side, "grad1": dict(norms), "change": dict(norms)}


@pytest.mark.parametrize("fault,judged_by", [
    # one layer's favourite expert computes nothing: its layer reads over the
    # limit, all 48 as one leaf do not (what the second number is for)
    ({"l7": ["e0"]}, {"grad1_layer"}),
    # a little-used expert alone: under both (the price of the merge; a sound
    # run reads up to 0.81 on such a leaf)
    ({"l7": ["e5"]}, set()),
    # every held expert left out
    ({f"l{l}": [f"e{e}" for e in range(8)] for l in range(6, 12)}, {"grad1", "grad1_layer"}),
])
def test_a_layer_s_experts_are_judged_together(fault, judged_by):
    from perfbench.entries.train_lean import numbers_of

    prog, ref = _sides()
    for layer, experts in fault.items():
        for path in prog["grad1"]:
            if f"/{layer}/moe/experts/" in path and path.split("/")[5] in experts:
                prog["grad1"][path] = 0.0
    numbers = numbers_of(prog, ref)
    limits = {"grad1": 0.45, "grad1_layer": 0.6}   # the cell's
    assert {k for k, v in limits.items() if numbers[k] > v} == judged_by, numbers
    assert numbers["grad1_per_expert"] == (1.0 if fault else 0.0)


@pytest.fixture(scope="module")
def side_cell(root):
    """One built cell, its first batches and their float32 reference, shared
    by the readings below."""
    from perfbench.entries.train_lean import LeanTrainCell
    from perfbench.run import Context
    from perfbench.spec import Spec

    ctx = Context(Spec(root), CELL, 5, 1.0, 0, time.perf_counter())
    cell = LeanTrainCell(ctx)
    try:
        for _ in range(cell.follow_steps):
            next(cell.feed)  # fills cell.followed through the tap
        yield cell, cell.reference()
    finally:
        cell.close()


@pytest.mark.parametrize("kind,number", [
    ("fp8", "dir1"), ("half_batch", "dir1"), ("unchanged", "change"), ("no_experts", "grad1"),
])
def test_the_control_and_the_faults_read_over_the_limits(side_cell, kind, number):
    """The reference in the program's place - in eight bits, on half the
    batch, never moving, or without its held experts - against the float32
    reference on the same batches."""
    from perfbench import compare
    from perfbench.entries.train_lean import side_reading

    cell, ref_res = side_cell
    numbers = side_reading(cell, kind, ref_res)
    correct, rows = compare.judge(numbers, LIMITS)
    assert not correct
    assert numbers[number] > 3 * LIMITS[number], rows


def test_the_readings_script_writes_one_line_a_side(root, monkeypatch, capsys):
    """``train_lean.py`` as the script the cell's limits are read with."""
    import json

    from perfbench.entries import train_lean

    monkeypatch.setattr(train_lean, "REPO_ROOT", root)
    assert train_lean.main(["--workload", CELL, "--seeds", "7", "--sides", "unchanged",
                            "--seconds", "0.5", "--no-chip"]) == 0
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert [r["kind"] for r in rows] == ["program", "unchanged"]
    assert rows[0]["built_in_window"] == 0 and rows[0]["numbers"]["grad1"] < LIMITS["grad1"]
    assert rows[0]["correct"] and rows[0]["compared"]["built_in_window"] == [0, 0]
    assert rows[1]["numbers"]["change"] == pytest.approx(1.0, abs=1e-3)
    assert not rows[1]["correct"] and rows[1]["compared"]["change"][1] == LIMITS["change"]
    with open(os.path.join(root, "chiprun_out", f"readings_{CELL}.jsonl")) as f:
        assert len(f.readlines()) == 2
