"""``phi4_mini_flash_det`` at tiny widths on the CPU: the program's backbone
against the plain reference (forward and every leaf's gradient, seeded), and
the tiny configuration through the harness's run with the control and the
faults planted, which have to come out NOT correct.

Tolerances (float32 on both sides; the program chunks the recurrence and
blocks the attention, so only the order of summation differs): features 1e-4
of their scale, a leaf's gradient 1e-3 of its own or the median leaf's norm;
through the harness the limits of ``test_ling_reference.py``."""

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

from _sambay_tiny import CELL, make_root, small_program_choices, tiny_config  # noqa: E402

from perfbench import weights as W  # noqa: E402
from perfbench.entries import train_lean_sambay as E  # noqa: E402
from perfbench.reference import backbone_phi4_mini_flash as B  # noqa: E402

LIMITS = {"loss2": 1e-3, "loss3": 1e-3, "grad1": 1e-3, "change": 1e-2, "dir1": 1e-3, "dirc": 1e-3}


@pytest.fixture(scope="module", autouse=True)
def _seams_at_tiny_size():
    """Both sides compute in float32 here, where a remainder of a hundredth of
    its terms is still six digits: every ``lambda`` leaf is judged (the rule's
    share is the bfloat16 cell's, and has tests of its own below)."""
    with small_program_choices(), pytest.MonkeyPatch.context() as mp:
        mp.setattr(E, "KEPT_MIN", 0.0)
        yield


def _weights(seed, ref):
    """As the entry makes them: drawn, then the scan's leaves and lambda mapped."""
    host = E.sambay_ranges(ref["decoder"], jax.device_get(W.make_weights(seed, B.specs(ref))))
    return {p: jnp.asarray(v) for p, v in host.items()}


def _program_backbone(conf):
    from mx_rcnn_tpu.config import apply_overrides, get_config
    from mx_rcnn_tpu.models.build import build_backbone

    cfg = apply_overrides(get_config(conf["preset"]), conf["overrides"])
    return build_backbone(cfg.model.backbone, out_levels=(4,), dtype=jnp.float32)


def test_the_entry_maps_the_draws_onto_the_family_s_ranges():
    dc = tiny_config()["reference"]["decoder"]
    w = _weights(2**31 + 5, {"decoder": dict(dc, hidden_size=256), "feature_channels": 32})
    a = np.exp(np.asarray(w["params/backbone/l0/mamba/A_log"]))
    dt = np.log1p(np.exp(np.asarray(w["params/backbone/l0/mamba/dt_bias"], np.float64)))
    assert a.shape == (512, 4) and 1.0 <= a.min() < 1.5 and 15.0 < a.max() <= 16.0001
    assert 1e-3 * 0.999 <= dt.min() < 1.3e-3 and 0.08 < dt.max() <= 0.1001
    assert abs(np.median(np.log(dt)) - np.log(1e-2)) < 0.5           # log-uniform
    d = np.asarray(w["params/backbone/l0/mamba/D"])
    assert 0.7 <= d.min() and d.max() <= 1.0                         # left as drawn
    lam = np.concatenate([np.asarray(w[f"params/backbone/l{l}/{k}/lambda"]).ravel()
                          for l, k in ((1, "swa"), (3, "swa"), (5, "full"), (7, "xattn"))])
    assert lam.std() == pytest.approx(0.1, rel=0.25)
    for leaf in ("A_log", "dt_bias", "D", "lambda", "norm1/scale", "Wqkv/bias"):
        assert not E.decayed("a/" + leaf), leaf
    for leaf in ("conv/kernel", "dt_proj/kernel", "x_proj/kernel", "Wqkv/kernel"):
        assert E.decayed("a/" + leaf), leaf


def test_the_family_s_names_are_lent_while_the_entry_runs_and_no_longer():
    from perfbench.reference import detector

    before = (detector.decayed, E.L.LeanTrainCell, E.L.side_reading, E.L.numbers_of,
              E.L.W.make_weights)
    assert detector.decayed("a/lambda")
    with E._as_this_family():
        assert not detector.decayed("a/lambda") and not detector.decayed("a/bias")
        assert E.L.LeanTrainCell is E.SambayTrainCell and E.L.side_reading is E.side_reading
        assert E.L.numbers_of is E.numbers_of
    assert (detector.decayed, E.L.LeanTrainCell, E.L.side_reading, E.L.numbers_of,
            E.L.W.make_weights) == before


@pytest.mark.parametrize("kept,judged", [(0.2, True), (0.05, True), (0.049, False), (0.002, False),
                                         (None, True)])
def test_a_lambda_leaf_is_judged_where_enough_of_its_sum_is_left(kept, judged, monkeypatch):
    """``grad1`` and ``change`` leave a ``lambda`` leaf out where the reference
    finds that under ``KEPT_MIN`` of the sum behind its gradient survives the
    cancellation of its terms in this run - a rule on that measure, not on the
    leaf's name: above it, or where nothing was measured, the leaf is judged
    like any other; the same two numbers with every leaf in ride beside."""
    monkeypatch.setattr(E, "KEPT_MIN", 0.05)
    lam = "params/backbone/l1/swa/lambda"
    steps = [{"loss": 1.0, "rpn": 0.5, "rcnn": 0.5}]
    leaves = {lam: 0.02, "params/backbone/l1/swa/Wqkv/kernel": 2.0,
              "params/backbone/l1/swa/subln/scale": 0.05, "params/rpn/conv/kernel": 1.0}
    ref = {"steps": steps, "grad1": dict(leaves), "change": dict(leaves)}
    if kept is not None:
        ref["lambda_kept"] = {lam: {"kept": kept, "kept_by_position": 4 * kept, "sum": 1.0}}
    off = dict(leaves, **{lam: 0.08})                                   # four times the reference's
    n = E.numbers_of({"steps": steps, "grad1": off, "change": off}, ref)
    gap = pytest.approx(0.06 / 0.525)
    assert n["grad1_with_lambda"] == gap and n["grad1_leaf_with_lambda"] == lam
    assert n["change_with_lambda"] == gap
    if judged:
        assert n["grad1"] == gap and n["grad1_leaf"] == lam and n["change"] == gap
        assert n["lambda_left_out"] == []
    else:
        assert n["grad1"] == 0.0 and n["change"] == 0.0 and n["lambda_left_out"] == [lam]
        assert n["lambda_kept"] == {lam: [kept, 4 * kept]}
    # a fault in any other leaf of the layer shows either way (over the median leaf of those judged)
    bad = dict(leaves, **{"params/backbone/l1/swa/subln/scale": 0.5})
    found = E.numbers_of({"steps": steps, "grad1": bad, "change": bad}, ref)
    assert found["grad1"] == pytest.approx(0.45 / (0.525 if judged else 1.0))
    assert found["grad1_leaf"].endswith("/subln/scale")


def test_the_reference_s_layer_rule_is_the_program_s():
    from mx_rcnn_tpu.config import PHI4_MINI_FLASH
    from mx_rcnn_tpu.models.decoder import lambda_init, layer_kinds

    dc = {"num_hidden_layers_published": 32, "mb_per_layer": 2}
    for layer in range(32):
        assert layer_kinds(PHI4_MINI_FLASH, layer) == (B.kind(dc, layer), "ffn")
        assert lambda_init(layer) == B.lambda_init(layer)


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_program_backbone_is_the_reference_forward_and_gradients(seed):
    conf = tiny_config()
    ref = conf["reference"]
    w = _weights(seed, ref)
    x = jax.random.normal(W.seed_key(seed, 5), (2, 128, 128, 3))
    cot = jax.random.normal(W.seed_key(seed, 6), (2, 8, 8, ref["feature_channels"]))
    backbone = _program_backbone(conf)

    def program(w):
        return backbone.apply({"params": W.nest(w, "params")["backbone"]}, x)[4]

    def plain(w):
        return jnp.concatenate([B.features(ref, w, x[i:i + 1])[4] for i in range(2)])

    got, want = program(w), plain(w)
    assert got.shape == want.shape == (2, 8, 8, 32)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, atol=1e-4 * scale)

    g_prog = jax.grad(lambda t: jnp.sum(program(t) * cot))(w)
    g_ref = jax.grad(lambda t: jnp.sum(plain(t) * cot))(w)
    norms = {p: float(jnp.linalg.norm(g)) for p, g in g_ref.items()}
    median = float(np.median(list(norms.values())))
    assert min(norms.values()) > 0.0, "a leaf the reference's features never read"
    for p, g in g_ref.items():
        gap = float(jnp.linalg.norm(g_prog[p] - g)) / max(norms[p], median)
        assert gap < 1e-3, (p, gap)


# -- through the harness -------------------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("sambay")), limits=LIMITS)


def run(root, seed=2**31 + 11):
    from perfbench.run import run_cell

    return run_cell(CELL, seed, 1.0, 0, root=root, require_chip=False)


def test_the_tiny_configuration_runs_correct_through_the_harness(root):
    out = run(root)
    assert out["correct"], out["compared"]
    assert set(out["metrics"]) == {"train_img_s_chip", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["compared"]["built_in_window"] == [0, 0]
    assert "moe_slots_here" not in out["run"]["counters"]       # nothing is routed


def test_under_the_cell_s_own_share_the_run_says_which_leaves_it_left_out(root, monkeypatch):
    """With ``KEPT_MIN`` as the bfloat16 cell has it, the tiny run's result line
    carries the measure of all four ``lambda`` leaves and names those it left out."""
    monkeypatch.setattr(E, "KEPT_MIN", 0.05)
    extra = run(root, seed=5)["run"]["extra"]
    assert len(extra["lambda_kept"]) == 4
    below = sorted(leaf for leaf, (kept, _) in extra["lambda_kept"].items() if kept < 0.05)
    assert extra["lambda_left_out"] == below and below


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


def test_the_window_left_out_of_the_program_is_not_correct(root, monkeypatch):
    """The program's window layers seeing the whole prefix (what a kernel that
    forgets the band's leading edge would compute)."""
    from mx_rcnn_tpu.models import decoder

    real = decoder.causal_attention
    monkeypatch.setattr(decoder, "causal_attention",
                        lambda *a, window=None, **kw: real(*a, **kw))
    out = run(root)
    assert not out["correct"]
    assert out["compared"]["dir1"][0] > 3 * LIMITS["dir1"]


def test_the_carry_left_out_of_the_program_is_not_correct(root, monkeypatch):
    """The program's scan with every chunk started from a zero state: the tiny
    image's 64 positions are four chunks of 16."""
    from mx_rcnn_tpu.models import decoder

    real = decoder.selective_scan_chunked

    def no_carry(x, dt, a, b, c, d, **kw):
        chunk, t = 16, x.shape[1]
        parts = [real(x[:, lo:lo + chunk], dt[:, lo:lo + chunk], a, b[:, lo:lo + chunk],
                      c[:, lo:lo + chunk], d, **kw) for lo in range(0, t, chunk)]
        return jnp.concatenate(parts, axis=1)

    monkeypatch.setattr(decoder, "selective_scan_chunked", no_carry)
    out = run(root)
    assert not out["correct"]
    assert out["compared"]["dir1"][0] > 3 * LIMITS["dir1"]


@pytest.fixture(scope="module")
def side_cell(root):
    """One built cell, its first batches and their float32 reference, shared
    by the readings below."""
    from perfbench.run import Context
    from perfbench.spec import Spec

    ctx = Context(Spec(root), CELL, 5, 1.0, 0, time.perf_counter())
    with E._as_this_family():
        cell = E.SambayTrainCell(ctx)
        try:
            for _ in range(cell.follow_steps):
                next(cell.feed)  # fills cell.followed through the tap
            yield cell, cell.reference()
        finally:
            cell.close()


@pytest.mark.parametrize("kind,number", [
    ("fp8", "dir1"), ("half_batch", "dir1"), ("unchanged", "change"), ("no_carry", "dir1"),
    ("no_window", "dir1"), ("no_diff", "dir1"), ("stale_share", "dir1"),
])
def test_the_control_and_the_faults_read_over_the_limits(side_cell, kind, number):
    """The reference in the program's place - in eight bits, on half the batch,
    never moving, with its recurrence started from zero at every chunk, its
    window layers seeing everything, lambda a_2 left out, or its Gated Memory
    Unit and cross layer reading zeros - against the float32 reference on the
    same batches."""
    from perfbench import compare

    cell, ref_res = side_cell
    numbers = E.side_reading(cell, kind, ref_res)
    correct, rows = compare.judge(numbers, LIMITS)
    assert not correct
    assert numbers[number] > 3 * LIMITS[number], rows


def test_the_conditioning_s_terms_add_up_to_the_leaf_s_own_gradient(side_cell):
    """``SambayReference`` takes d loss / d lambda term by term (a field of
    zeros added to lambda, among the weights of its one gradient program): the
    terms' sum times the leaf's fixed vectors, after the clip, is the norm the
    reference's own gradient has for that leaf; what is left of the terms'
    sizes is a share, smaller still element by element than position by
    position; and the field is gone again before the optimizer: the
    reference's leaves are the program's."""
    cell, ref_res = side_cell
    kept = ref_res["lambda_kept"]
    assert sorted(kept) == sorted(p for p in ref_res["grad1"] if p.endswith("/lambda")) and len(kept) == 4
    assert not any(E.FIELD in p for p in list(ref_res["grad1"]) + list(ref_res["change"]))
    clip = min(1.0, cell.ref["optimizer"]["grad_clip"] / ref_res["steps"][0]["grad_norm"])
    for leaf, k in kept.items():
        lam = np.asarray(cell.w0[leaf], np.float64)
        e1, e2 = np.exp(lam[0] @ lam[1]), np.exp(lam[2] @ lam[3])
        fixed = np.sqrt(e1 ** 2 * (lam[0] @ lam[0] + lam[1] @ lam[1])
                        + e2 ** 2 * (lam[2] @ lam[2] + lam[3] @ lam[3]))
        assert abs(k["sum"]) * fixed * clip == pytest.approx(ref_res["grad1"][leaf], rel=1e-4)
        assert 0.0 < k["kept"] <= k["kept_by_position"] <= 1.0


def test_a_control_or_a_fault_is_judged_under_the_reference_s_measure(side_cell, monkeypatch):
    """The measure that decides which leaves count is the float32 reference's
    on the followed batches, for the program and for a side in its place alike
    (with lambda a_2 left out the side's own field is never read: its terms
    are nought, and nothing asks)."""
    cell, ref_res = side_cell
    monkeypatch.setattr(E, "KEPT_MIN", 1.0)       # every lambda leaf left out, by the reference's measure
    numbers = E.side_reading(cell, "no_diff", ref_res)
    assert numbers["lambda_kept"] == {p: [k["kept"], k["kept_by_position"]]
                                      for p, k in ref_res["lambda_kept"].items()}
    assert len(numbers["lambda_left_out"]) == 4 and not numbers["grad1_leaf"].endswith("/lambda")
    assert numbers["dir1"] > 3 * LIMITS["dir1"]     # the fault itself stayed under the field's patch


def test_the_faults_leave_the_reference_as_it_was(side_cell):
    cell, ref_res = side_cell
    again = E.L.numbers_of(cell.reference(), ref_res)
    assert again["grad1"] == 0.0 and again["dir1"] < 1e-6


def test_the_readings_script_writes_one_line_a_side(root, monkeypatch, capsys):
    """``train_lean_sambay.py`` as the script the cell's limits are read with."""
    import json

    monkeypatch.setattr(E.L, "REPO_ROOT", root)
    assert E.main(["--workload", CELL, "--seeds", "7", "--sides", "unchanged,stale_share",
                   "--seconds", "0.5", "--no-chip"]) == 0
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert [r["kind"] for r in rows] == ["program", "unchanged", "stale_share"]
    assert rows[0]["built_in_window"] == 0 and rows[0]["numbers"]["grad1"] < LIMITS["grad1"]
    assert rows[0]["correct"] and rows[0]["compared"]["built_in_window"] == [0, 0]
    assert rows[1]["numbers"]["change"] == pytest.approx(1.0, abs=1e-3)
    assert not rows[1]["correct"] and not rows[2]["correct"]
    with open(os.path.join(root, "chiprun_out", f"readings_{CELL}.jsonl")) as f:
        assert len(f.readlines()) == 3
