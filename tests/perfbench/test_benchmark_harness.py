"""The harness is driven by data: a configuration, a cell, a traffic mix and a
per-layer metric are found by name from files added beside the others; a
measured run fails off the chip; BENCHMARK.json keeps to its contract."""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _benchmark_tiny import make_root  # noqa: E402
from perfbench import compare, traffic  # noqa: E402
from perfbench.spec import Spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_added_files_are_found_by_name(tmp_path):
    spec = Spec(make_root(str(tmp_path)))
    cell = spec.cell("tiny_r50_fpn_coco.train_b2")
    assert cell["entry"] == "train" and cell["config"] == "tiny_r50_fpn_coco"
    assert spec.config(cell["config"])["reference"]["canvas"] == [128, 128]
    assert spec.traffic(cell["traffic"])["pool"] == 8
    names = [m["name"] for m in spec.metrics_of(cell["name"], "per_layer")]
    assert "steps_per_sync.train" in names
    assert spec.reader("steps_per_sync.train")({"counters": {"sync_every": 2}}) == 2.0
    # ... and the cells that were there do not see the newcomer's metric
    other = [m["name"] for m in spec.metrics_of("vgg16_voc07.train_b16", "per_layer")]
    assert "steps_per_sync.train" not in other


def test_every_cell_has_its_files(bench):
    spec = Spec(REPO)
    for w in bench["workloads"]:
        cell = spec.cell(w["name"])
        assert set(cell["limits"]) >= {"grad1", "change", "dir1"} and cell["steady"]
        spec.config(cell["config"])
        spec.traffic(cell["traffic"])
        for m in spec.metrics_of(w["name"], "per_layer"):
            assert callable(spec.reader(m["name"]))
        e2e = [m["name"] for m in spec.metrics_of(w["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2


def test_benchmark_json_keeps_to_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in bench[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, len(cells) // 4)
    for m in bench["end_to_end"]:
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and set(m.get("workloads", [])) <= cells
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
    rooflines = [m for m in bench["per_layer"] if m["name"].split(".")[0].endswith("_roofline")]
    mfus = {m["moves"] for m in bench["per_layer"] if "mfu" in m["name"]}
    assert all(m["unit"] == "%" and m["moves"] in mfus for m in rooflines)


def test_a_measured_run_fails_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = json.load(open(os.path.join(REPO, "BENCHMARK.json")))["workloads"][0]["name"]
    out = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode not in (0, None)
    assert out.stdout.strip() == ""            # nothing that could pass for a result
    assert "no TPU" in out.stderr


def test_traffic_is_the_same_work_for_every_seed():
    mix = Spec(REPO).traffic("train_coco")
    a = traffic.make_images(mix, 81, 1)
    b = traffic.make_images(mix, 81, 2**31 + 7)
    again = traffic.make_images(mix, 81, 1)
    sizes = lambda t: sorted(im.shape for im in t[0])
    assert sizes(a) == sizes(b) and len(a[0]) == mix["pool"]
    assert sorted(len(x) for x in a[1]) == sorted(len(x) for x in b[1])
    assert all((x == y).all() for x, y in zip(a[0], again[0]))
    assert any(x.shape != y.shape or (x != y).any() for x, y in zip(a[0], b[0]))
    for im, bx in zip(a[0], a[1]):
        assert im.dtype.name == "uint8" and im.shape[0] <= im.shape[1]
        assert (bx[:, 2] > bx[:, 0]).all() and (bx[:, 2] < im.shape[1]).all()


def test_worst_leaf_measures_the_gap_of_norms():
    ref = {"a": 1.0, "b": 100.0, "c": 1e-6}
    assert compare.worst_leaf(dict(ref), ref)[0] == 0.0
    gap, leaf = compare.worst_leaf({"a": 1.5, "b": 100.0, "c": 1e-6}, ref)
    assert leaf == "a" and gap == pytest.approx(0.5)
    # an all-but-zero leaf is held against the median leaf, not itself
    gap, _ = compare.worst_leaf({"a": 1.0, "b": 100.0, "c": 3e-6}, ref)
    assert gap == pytest.approx(2e-6)
    with pytest.raises(ValueError):
        compare.worst_leaf({"a": 1.0}, ref)


def test_judge_holds_every_limit():
    ok, rows = compare.judge({"x": 0.01, "y": 0.5}, {"x": 0.02, "y": 0.4})
    assert not ok and rows == {"x": [0.01, 0.02], "y": [0.5, 0.4]}
    assert compare.judge({"x": 0.01}, {"x": 0.02})[0]
    assert not compare.judge({}, {"x": 0.02})[0]
    assert not compare.judge({"x": float("nan")}, {"x": 0.02})[0]


def test_direction_gap_is_free_of_scale_and_sees_a_turned_leaf():
    import numpy as np

    rng = np.random.default_rng(0)
    ref = {"a": rng.normal(size=(64, 8)), "b": rng.normal(size=(8,)), "c": 1e-9 * rng.normal(size=4)}
    # every leaf scaled alike (the optimizer's clip): no gap
    assert compare.direction_gap({k: 0.3 * v for k, v in ref.items()}, ref)[0] < 1e-12
    # one leaf turned by noise a tenth of its size: that leaf, about a tenth
    turned = dict(ref, b=ref["b"] + 0.1 * np.linalg.norm(ref["b"]) / np.sqrt(8) * rng.normal(size=8))
    gap, leaf = compare.direction_gap(turned, ref)
    assert leaf == "b" and 0.03 < gap < 0.3
    # an all-but-zero leaf is held against the median leaf's share, not its own
    assert compare.direction_gap(dict(ref, c=3 * ref["c"]), ref)[0] < 1e-6
    # nothing moved on the program's side: 1
    assert compare.direction_gap({k: 0 * v for k, v in ref.items()}, ref)[0] == pytest.approx(1.0)
    with pytest.raises(ValueError):
        compare.direction_gap({"a": ref["a"]}, ref)
