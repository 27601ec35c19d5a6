"""``granite4_h_micro_det`` at tiny widths on the CPU: the program's backbone
against the plain reference (forward and every leaf's gradient, seeded), each
departure from the published equations failing that comparison, and the tiny
configuration through the harness's run with the control and the faults
planted, which have to come out NOT correct.

Tolerances (float32 on both sides; the program chunks the recurrence and
blocks the attention, so only the order of summation differs): features 1e-4
of their scale (measured 2e-7), a leaf's gradient 1e-3 of its own or the
median leaf's norm; through the harness the limits of
``test_ssm_reference.py``."""

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

from _granite_tiny import CELL, make_root, small_program_choices, tiny_config  # noqa: E402

from perfbench import weights as W  # noqa: E402
from perfbench.entries import train_lean_granite as E  # noqa: E402
from perfbench.reference import backbone_granite4_h_micro as B  # noqa: E402

LIMITS = {"loss2": 1e-3, "loss3": 1e-3, "grad1": 1e-3, "change": 1e-2, "dir1": 1e-3,
          "dirc": 1e-3}


@pytest.fixture(scope="module", autouse=True)
def _seams_at_tiny_size():
    with small_program_choices():
        yield


def _weights(seed, ref):
    """As the entry makes them: drawn, then the scan's leaves and the patchify mapped."""
    host = E.granite_ranges(ref["decoder"], jax.device_get(W.make_weights(seed, B.specs(ref))))
    return {p: jnp.asarray(v) for p, v in host.items()}


def _program_backbone(conf, **fields):
    from mx_rcnn_tpu.config import apply_overrides, get_config
    from mx_rcnn_tpu.models.build import build_backbone

    sets = [f"model.backbone.decoder.{k}={v}" for k, v in fields.items()]
    cfg = apply_overrides(get_config(conf["preset"]), conf["overrides"] + sets)
    return build_backbone(cfg.model.backbone, out_levels=(4,), dtype=jnp.float32)


def _features(conf, w, x, **fields):
    backbone = _program_backbone(conf, **fields)
    variables = {"params": W.nest(w, "params")["backbone"]}
    return backbone.apply(variables, x, mutable=["counters"])[0][4]


def _plain(ref, w, x):
    return jnp.concatenate([B.features(ref, w, x[i:i + 1])[4] for i in range(x.shape[0])])


def test_the_entry_maps_the_draws_onto_the_family_s_ranges():
    ref = tiny_config()["reference"]
    dc = dict(ref["decoder"], mamba_n_heads=512, mamba_d_head=1)
    drawn = jax.device_get(W.make_weights(2**31 + 5, B.specs(dict(ref, decoder=dc))))
    w = E.granite_ranges(dc, drawn)
    a = np.exp(np.asarray(w["params/backbone/l3/ssm/A_log"]))
    dt = np.log1p(np.exp(np.asarray(w["params/backbone/l3/ssm/dt_bias"], np.float64)))
    assert 1.0 <= a.min() < 1.5 and 15.0 < a.max() <= 16.0001
    assert 1e-3 * 0.999 <= dt.min() < 1.3e-3 and 0.08 < dt.max() <= 0.1001
    k = "params/backbone/patchify/kernel"
    np.testing.assert_allclose(w[k], 0.1 * np.asarray(drawn[k]), rtol=1e-6)   # initializer_range
    for p in ("params/backbone/l3/ssm/D", "params/backbone/l3/ffn/up/kernel",
              "params/backbone/l5/gqa/q/kernel"):
        np.testing.assert_array_equal(w[p], drawn[p])                        # left as drawn
    assert not E.decayed("params/backbone/l3/ssm/A_log") and not E.decayed("a/dt_bias")
    assert not E.decayed("a/D") and not E.decayed("a/norm1/scale") and E.decayed("a/ffn/up/kernel")


def test_the_family_s_names_are_lent_while_the_entry_runs_and_no_longer():
    from perfbench.reference import detector

    names = lambda: (detector.decayed, E.L.LeanTrainCell, E.L.side_reading, E.L.LeanReference,
                     E.L.W.make_weights)
    before = names()
    assert detector.decayed("a/A_log")
    with E._as_this_family():
        assert not detector.decayed("a/A_log") and not detector.decayed("a/bias")
        assert E.L.LeanTrainCell is E.GraniteTrainCell and E.L.side_reading is E.side_reading
        assert E.L.LeanReference is E.HostSumReference
    assert names() == before


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_program_backbone_is_the_reference_forward_and_gradients(seed):
    conf = tiny_config()
    ref = conf["reference"]
    w = _weights(seed, ref)
    x = jax.random.normal(W.seed_key(seed, 5), (2, 128, 128, 3))
    cot = jax.random.normal(W.seed_key(seed, 6), (2, 8, 8, ref["feature_channels"]))
    backbone = _program_backbone(conf)

    def program(w):
        return backbone.apply({"params": W.nest(w, "params")["backbone"]}, x,
                              mutable=["counters"])[0][4]

    got, want = program(w), _plain(ref, w, x)
    assert got.shape == want.shape == (2, 8, 8, 32)
    np.testing.assert_allclose(got, want, atol=1e-4 * float(jnp.abs(want).max()))

    g_prog = jax.grad(lambda t: jnp.sum(program(t) * cot))(w)
    g_ref = jax.grad(lambda t: jnp.sum(_plain(ref, t, x) * cot))(w)
    norms = {p: float(jnp.linalg.norm(g)) for p, g in g_ref.items()}
    median = float(np.median(list(norms.values())))
    assert min(norms.values()) > 0.0, "a leaf the reference's features never read"
    for p, g in g_ref.items():
        gap = float(jnp.linalg.norm(g_prog[p] - g)) / max(norms[p], median)
        assert gap < 1e-3, (p, gap)


@pytest.mark.parametrize("field,value", [
    ("residual_multiplier", 1.0),       # every branch added whole
    ("attention_multiplier", 0.0),      # the scores at head_dim ** -0.5, not 1/64
    ("embedding_multiplier", 1.0),      # the patch tokens left at their own scale
])
def test_a_departure_from_the_published_equations_fails_the_comparison(field, value):
    """The program with one muP multiplier at its default (what the other
    families run), against the reference as published: at least 100 x the
    tolerance the sound program meets."""
    conf = tiny_config()
    ref = conf["reference"]
    w = _weights(3, ref)
    x = jax.random.normal(W.seed_key(3, 5), (2, 128, 128, 3))
    want = _plain(ref, w, x)
    gap = float(jnp.abs(_features(conf, w, x, **{field: value}) - want).max())
    assert gap > 100 * 1e-4 * float(jnp.abs(want).max()), gap


# -- through the harness -------------------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("granite")), limits=LIMITS)


def run(root, seed=2**31 + 11):
    from perfbench.run import run_cell

    return run_cell(CELL, seed, 1.0, 0, root=root, require_chip=False)


def test_the_tiny_configuration_runs_correct_through_the_harness(root):
    out = run(root)
    assert out["correct"], out["compared"]
    assert set(out["metrics"]) == {"train_img_s_chip", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["compared"]["built_in_window"] == [0, 0]
    assert "moe_slots1" not in out["run"]["extra"]      # nothing is routed


def test_the_carry_left_out_of_the_program_is_not_correct(root, monkeypatch):
    """The program's scan with every chunk started from a zero state: the tiny
    image's 64 positions are four chunks of 16."""
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


def test_the_residual_multiplier_left_out_of_the_program_is_not_correct(root, monkeypatch):
    from mx_rcnn_tpu.config import apply_overrides
    from perfbench import program

    real = program.load_config
    monkeypatch.setattr(program, "load_config", lambda conf, cell: apply_overrides(
        real(conf, cell), ["model.backbone.decoder.residual_multiplier=1.0"]))
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
        cell = E.GraniteTrainCell(ctx)
        try:
            for _ in range(cell.follow_steps):
                next(cell.feed)  # fills cell.followed through the tap
            yield cell, cell.reference()
        finally:
            cell.close()


@pytest.mark.parametrize("kind,number", [
    ("fp8", "dir1"), ("half_batch", "dir1"), ("unchanged", "change"), ("no_carry", "dir1"),
    ("no_residual_multiplier", "dir1"),
])
def test_the_control_and_the_faults_read_over_the_limits(side_cell, kind, number):
    """The reference in the program's place - in eight bits, on half of each
    batch, never moving, with its recurrence started from zero at every chunk,
    or with every branch added at 1.0 - against the float32 reference on the
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
    """``train_lean_granite.py`` as the script the cell's limits are read with."""
    import json

    monkeypatch.setattr(E.L, "REPO_ROOT", root)
    assert E.main(["--workload", CELL, "--seeds", "7", "--sides", "unchanged",
                   "--seconds", "0.5", "--no-chip"]) == 0
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert [r["kind"] for r in rows] == ["program", "unchanged"]
    assert rows[0]["built_in_window"] == 0 and rows[0]["numbers"]["grad1"] < LIMITS["grad1"]
    assert rows[0]["correct"] and rows[0]["compared"]["built_in_window"] == [0, 0]
    assert rows[1]["numbers"]["change"] == pytest.approx(1.0, abs=1e-3)
    assert not rows[1]["correct"]
    with open(os.path.join(root, "chiprun_out", f"readings_{CELL}.jsonl")) as f:
        assert len(f.readlines()) == 2
