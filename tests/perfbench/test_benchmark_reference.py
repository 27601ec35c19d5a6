"""The plain reference against the program at ``tiny`` size on the CPU, for
both backbones, through the harness's own run (everything but the look for a
chip); the control and the planted faults have to come out NOT correct.

Tolerances (float32 on both sides, so only the order of summation differs):
each step's loss 1e-3 relative (measured 3e-7), the first gradient's worst
leaf 1e-3 (measured 2e-7), the change's worst leaf 1e-2 (measured 3e-5: three
steps of momentum amplify the first step's rounding), the RPN head's
direction gaps 1e-3 (measured 1e-7 and 3e-5)."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _benchmark_tiny import make_root  # noqa: E402

LIMITS = {"loss2": 1e-3, "loss3": 1e-3, "grad1": 1e-3, "change": 1e-2, "dir1": 1e-3, "dirc": 1e-3}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("bench")), limits=LIMITS)


def run(root, config, seed=2**31 + 11):
    from perfbench.run import run_cell

    return run_cell(f"tiny_{config}.train_b2", seed, 1.0, 0, root=root, require_chip=False)


@pytest.mark.parametrize("config", ["r50_fpn_coco", "vgg16_voc07"])
def test_program_agrees_with_the_plain_reference(root, config):
    out = run(root, config)
    assert out["correct"], out["compared"]
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(out)[-1] == "compared"
    assert set(out["metrics"]) == {"train_img_s_chip", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["compared"]["built_in_window"] == [0, 0]
    for name, (value, limit) in out["compared"].items():
        assert value <= limit, (name, value, limit)


def _wrap_step(monkeypatch, wrap):
    from perfbench import program

    real = program.build_train

    def build(*args, **kw):
        state, step_fn, plan, gb = real(*args, **kw)
        return state, wrap(step_fn), plan, gb

    monkeypatch.setattr(program, "build_train", build)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(root, monkeypatch):
    import jax
    import jax.numpy as jnp

    def wrap(step_fn):
        def step(state, batch):
            keep = jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), state)
            _, metrics = step_fn(state, batch)
            return keep, metrics

        return step

    _wrap_step(monkeypatch, wrap)
    out = run(root, "r50_fpn_coco")
    assert not out["correct"]
    # nothing moved: the change reads 1 by the worst leaf's measure
    assert out["compared"]["change"][0] == pytest.approx(1.0, abs=1e-3)


def test_half_of_the_batch_left_out_is_not_correct(root, monkeypatch):
    import jax

    def wrap(step_fn):
        def step(state, batch):
            with jax.transfer_guard("allow"):  # the fault's own slicing, not the step
                half = jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], batch)
            return step_fn(state, half)

        return step

    _wrap_step(monkeypatch, wrap)
    out = run(root, "r50_fpn_coco")
    assert not out["correct"]
    assert out["compared"]["grad1"][0] > 10 * LIMITS["grad1"]
    assert out["compared"]["dir1"][0] > 10 * LIMITS["dir1"]


@pytest.fixture(scope="module")
def side_cell(root):
    """One built cell, its first batches and their float32 reference, shared
    by the readings below (a build and a compile each would cost 15 s a case)."""
    import time

    from perfbench.entries.train import TrainCell
    from perfbench.run import Context
    from perfbench.spec import Spec

    ctx = Context(Spec(root), "tiny_r50_fpn_coco.train_b2", 5, 1.0, 0, time.perf_counter())
    cell = TrainCell(ctx)
    try:
        for _ in range(cell.follow_steps):
            next(cell.feed)  # fills cell.followed through the tap
        yield cell, cell.reference(), {}
    finally:
        cell.close()


@pytest.mark.parametrize("kind,number", [
    ("int8", "dir1"), ("fp8", "dir1"), ("half_batch", "dir1"), ("unchanged", "loss2"),
])
def test_the_control_and_the_fault_read_over_the_limits(side_cell, kind, number):
    """The reference in the program's place - in eight bits, on half the
    batch, or never moving - against the float32 reference on the same batches."""
    from perfbench import compare, readings

    cell, ref_res, runners = side_cell
    numbers, _ = readings.side_reading(cell, kind, ref_res, runners)
    correct, rows = compare.judge(numbers, LIMITS)
    assert not correct
    assert numbers[number] > 3 * LIMITS[number], rows
