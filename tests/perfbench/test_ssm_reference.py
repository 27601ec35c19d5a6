"""``nemotron_twotower_det`` at tiny widths on the CPU: the program's backbone
against the plain reference (forward and every leaf's gradient, seeded), the
reference's own share test, and the tiny configuration through the harness's
run with the control and the faults planted, which have to come out NOT
correct.

Tolerances (float32 on both sides; the program chunks the recurrence, blocks
the attention and sorts its dispatch, so only the order of summation
differs): features 1e-4 of their scale (measured 1e-6), a leaf's gradient
1e-3 of its own or the median leaf's norm; through the harness the limits of
``test_ling_reference.py``."""

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

from _ssm_tiny import CELL, make_root, small_program_choices, tiny_config  # noqa: E402

from perfbench import weights as W  # noqa: E402
from perfbench.entries import train_lean_ssm as E  # noqa: E402
from perfbench.reference import backbone_nemotron_twotower as B  # noqa: E402

LIMITS = {"loss2": 1e-3, "loss3": 1e-3, "grad1": 1e-3, "change": 1e-2, "grad1_layer": 1e-3,
          "change_layer": 1e-2, "dir1": 1e-3, "dirc": 1e-3}


@pytest.fixture(scope="module", autouse=True)
def _seams_at_tiny_size():
    with small_program_choices():
        yield


def _weights(seed, ref):
    """As the entry makes them: drawn, then the scan's leaves mapped."""
    host = E.ssm_ranges(ref["decoder"], jax.device_get(W.make_weights(seed, B.specs(ref))))
    return {p: jnp.asarray(v) for p, v in host.items()}


def _program_backbone(conf):
    from mx_rcnn_tpu.config import apply_overrides, get_config
    from mx_rcnn_tpu.models.build import build_backbone

    cfg = apply_overrides(get_config(conf["preset"]), conf["overrides"])
    return build_backbone(cfg.model.backbone, out_levels=(4,), dtype=jnp.float32)


def test_the_entry_maps_the_scan_s_draws_onto_the_family_s_ranges():
    dc = tiny_config()["reference"]["decoder"]
    w = _weights(2**31 + 5, {"decoder": dict(dc, mamba_num_heads=512), "feature_channels": 32})
    a = np.exp(np.asarray(w["params/backbone/l0/ssm/A_log"]))
    dt = np.log1p(np.exp(np.asarray(w["params/backbone/l0/ssm/dt_bias"], np.float64)))
    assert 1.0 <= a.min() < 1.5 and 15.0 < a.max() <= 16.0001
    assert 1e-3 * 0.999 <= dt.min() < 1.3e-3 and 0.08 < dt.max() <= 0.1001
    assert abs(np.median(np.log(dt)) - np.log(1e-2)) < 0.5           # log-uniform
    d = np.asarray(w["params/backbone/l0/ssm/D"])
    assert 0.7 <= d.min() and d.max() <= 1.0                         # left as drawn
    assert not E.decayed("params/backbone/l0/ssm/A_log") and not E.decayed("a/dt_bias")
    assert not E.decayed("a/D") and not E.decayed("a/norm/scale") and E.decayed("a/conv/kernel")


def test_the_family_s_names_are_lent_while_the_entry_runs_and_no_longer():
    """The accepted entry and reference carry this family's class, faults and
    no-decay rule inside ``_as_this_family`` alone: another entry imported in
    the same process finds them as they were."""
    from perfbench.reference import detector

    before = detector.decayed, E.L.LeanTrainCell, E.L.side_reading, E.L.W.make_weights
    assert detector.decayed("a/A_log")
    with E._as_this_family():
        assert not detector.decayed("a/A_log") and not detector.decayed("a/bias")
        assert E.L.LeanTrainCell is E.SsmTrainCell and E.L.side_reading is E.side_reading
    assert (detector.decayed, E.L.LeanTrainCell, E.L.side_reading, E.L.W.make_weights) == before


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_program_backbone_is_the_reference_forward_and_gradients(seed):
    conf = tiny_config()
    ref = conf["reference"]
    w = _weights(seed, ref)
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
    slots = sum(float(B.slots_here(ref, w, x[i:i + 1])) for i in range(2))
    assert float(counters["moe_slots_here"][0]) == slots > 0

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
    """The routed parts of the 16 / 4 shares plus the shared expert counted
    once are the uncut layer, in the reference's own arithmetic (the program's
    op has the same test in tests/test_ops_decoder.py, both forms)."""
    conf = tiny_config()
    dc = conf["reference"]["decoder"]
    whole = dict(dc, experts_first=0, n_routed_experts=dc["n_routed_experts_published"])
    ref = dict(conf["reference"], decoder=whole)
    w = W.make_weights(11, B.specs(ref))
    p = "params/backbone/l1/moe"
    x = jax.random.normal(jax.random.PRNGKey(2), (50, dc["hidden_size"]))
    want, slots = B.experts_here(whole, w, p, x, None)
    assert float(slots) == 50 * dc["num_experts_per_tok"]   # every pick is held by the whole
    shared = B._relu2_mlp(w, f"{p}/shared", x, None)
    held = dc["n_routed_experts"]
    total = shared
    for first in range(0, dc["n_routed_experts_published"], held):
        share = dict(dc, experts_first=first, n_routed_experts=held)
        total = total + B.experts_here(share, w, p, x, None)[0] - shared
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert float(jnp.abs(want - shared).max()) > 1e-2   # the routed part is not nothing


# -- through the harness -------------------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("ssm")), limits=LIMITS)


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
    program_slots, reference_slots = out["run"]["extra"]["moe_slots1"]
    assert program_slots == reference_slots > 0      # float32 on both sides: no pick flips


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


def test_the_carry_left_out_of_the_program_is_not_correct(root, monkeypatch):
    """The program's scan with every chunk started from a zero state (what a
    kernel that forgets the carry would compute): the tiny image's 64
    positions are four chunks of 16."""
    from mx_rcnn_tpu.models import decoder

    real = decoder.ssd_chunked

    def no_carry(x, dt, a, b, c, d, **kw):
        chunk, t = 16, x.shape[1]      # the tiny configuration's (small_program_choices)
        parts = [real(x[:, lo:lo + chunk], dt[:, lo:lo + chunk], a, b[:, lo:lo + chunk],
                      c[:, lo:lo + chunk], d, **kw) for lo in range(0, t, chunk)]
        return jnp.concatenate(parts, axis=1)

    monkeypatch.setattr(decoder, "ssd_chunked", no_carry)
    out = run(root)
    assert not out["correct"]
    assert out["compared"]["dir1"][0] > 3 * LIMITS["dir1"]


def test_held_experts_left_out_of_the_program_is_not_correct(root, monkeypatch):
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


@pytest.fixture(scope="module")
def side_cell(root):
    """One built cell, its first batches and their float32 reference, shared
    by the readings below."""
    from perfbench.run import Context
    from perfbench.spec import Spec

    ctx = Context(Spec(root), CELL, 5, 1.0, 0, time.perf_counter())
    with E._as_this_family():
        cell = E.SsmTrainCell(ctx)
        try:
            for _ in range(cell.follow_steps):
                next(cell.feed)  # fills cell.followed through the tap
            yield cell, cell.reference()
        finally:
            cell.close()


@pytest.mark.parametrize("kind,number", [
    ("fp8", "dir1"), ("half_batch", "dir1"), ("unchanged", "change"), ("no_experts", "grad1"),
    ("no_carry", "dir1"),
])
def test_the_control_and_the_faults_read_over_the_limits(side_cell, kind, number):
    """The reference in the program's place - in eight bits, on half the
    batch, never moving, without its held experts, or with its recurrence
    started from zero at every chunk - against the float32 reference on the
    same batches."""
    from perfbench import compare

    cell, ref_res = side_cell
    numbers = E.side_reading(cell, kind, ref_res)
    correct, rows = compare.judge(numbers, LIMITS)
    assert not correct
    assert numbers[number] > 3 * LIMITS[number], rows


def test_the_faults_leave_the_reference_as_it_was(side_cell):
    cell, ref_res = side_cell
    again = E.L.numbers_of(cell.reference(), ref_res)
    assert again["grad1"] == 0.0 and again["dir1"] < 1e-6


def test_the_readings_script_writes_one_line_a_side(root, monkeypatch, capsys):
    """``train_lean_ssm.py`` as the script the cell's limits are read with."""
    import json

    monkeypatch.setattr(E.L, "REPO_ROOT", root)
    assert E.main(["--workload", CELL, "--seeds", "7", "--sides", "unchanged,no_carry",
                   "--seconds", "0.5", "--no-chip"]) == 0
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert [r["kind"] for r in rows] == ["program", "unchanged", "no_carry"]
    assert rows[0]["built_in_window"] == 0 and rows[0]["numbers"]["grad1"] < LIMITS["grad1"]
    assert rows[0]["correct"] and rows[0]["compared"]["built_in_window"] == [0, 0]
    assert rows[1]["numbers"]["change"] == pytest.approx(1.0, abs=1e-3)
    assert not rows[1]["correct"] and not rows[2]["correct"]
    with open(os.path.join(root, "chiprun_out", f"readings_{CELL}.jsonl")) as f:
        assert len(f.readlines()) == 3
