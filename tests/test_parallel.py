"""Multi-device sharding tests on the 8-device fake CPU mesh.

The SURVEY §5(d) strategy: data-parallel logic is validated without TPU
hardware via ``xla_force_host_platform_device_count=8`` (set in conftest).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.config import get_config
from mx_rcnn_tpu.data import DetectionLoader, SyntheticDataset
from mx_rcnn_tpu.detection import TwoStageDetector
from mx_rcnn_tpu.parallel import (
    batch_sharding,
    make_mesh,
    make_train_step,
    replicated,
    shard_batch,
)
from mx_rcnn_tpu.train import create_train_state, make_optimizer

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs the 8-device fake mesh"
)


class TestMesh:
    def test_pure_dp_mesh(self):
        mesh = make_mesh()
        assert mesh.shape["data"] == 8
        assert mesh.shape["model"] == 1

    def test_2d_mesh(self):
        mesh = make_mesh(model_parallel=2)
        assert mesh.shape["data"] == 4
        assert mesh.shape["model"] == 2

    def test_indivisible_raises(self):
        with pytest.raises(ValueError):
            make_mesh(model_parallel=3)

    def test_shard_batch_layout(self):
        mesh = make_mesh()
        x = np.arange(8 * 4, dtype=np.float32).reshape(8, 4)
        y = shard_batch(x, mesh)
        assert y.sharding.is_equivalent_to(batch_sharding(mesh), y.ndim)
        np.testing.assert_allclose(np.asarray(y), x)
        # Each device holds exactly one row.
        assert all(s.data.shape == (1, 4) for s in y.addressable_shards)


class TestShardedTrainStep:
    @pytest.fixture(scope="class")
    def setup(self):
        cfg = get_config("tiny_synthetic")
        model = TwoStageDetector(cfg=cfg.model)
        mesh = make_mesh()
        rng = jax.random.PRNGKey(0)
        tx, schedule = make_optimizer(cfg.train, None)
        # params unknown before init → build tx after state init instead.
        state = create_train_state(
            model,
            tx,
            rng,
            cfg.data.image_size,
            batch=1,
        )
        roidb = SyntheticDataset(num_images=8, image_hw=cfg.data.image_size).roidb()
        loader = DetectionLoader(roidb, cfg.data, batch_size=8, prefetch=False)
        return cfg, model, mesh, tx, schedule, state, loader

    def test_one_sharded_step(self, setup):
        cfg, model, mesh, tx, schedule, state, loader = setup
        step_fn = make_train_step(model, tx, schedule, mesh=mesh)
        state = jax.device_put(state, replicated(mesh))
        batch = shard_batch(next(iter(loader)), mesh)
        w_before = np.asarray(
            jax.device_get(jax.tree_util.tree_leaves(state.params)[0])
        )
        state, metrics = step_fn(state, batch)
        metrics = jax.device_get(metrics)
        for k, v in metrics.items():
            assert np.isfinite(v), f"{k} not finite"
        assert int(state.step) == 1
        w_after = np.asarray(
            jax.device_get(jax.tree_util.tree_leaves(state.params)[0])
        )
        assert not np.allclose(w_before, w_after)

    def test_frozen_params_bitexact_with_stopgrad_mask(self, setup):
        """build_all-style freezing: the stop-gradient trainable_mask plus
        the masked optimizer must leave frozen leaves BIT-identical through
        a real sharded step while trainable leaves move."""
        from mx_rcnn_tpu.train.optim import frozen_mask

        cfg, model, mesh, _, schedule, _state, loader = setup
        # The sibling test donated its device_put view of the fixture state
        # (scalar leaves alias under identical sharding and get deleted) —
        # build a fresh state instead of touching the fixture's.
        probe_tx, _ = make_optimizer(cfg.train, None)
        state = create_train_state(
            model, probe_tx, jax.random.PRNGKey(3), cfg.data.image_size, batch=1
        )
        freeze = ("backbone/conv1", "backbone/bn1", "backbone/layer1")
        tx, schedule = make_optimizer(
            cfg.train, state.params, freeze_prefixes=freeze
        )
        state = state.replace(opt_state=tx.init(state.params))
        mask = frozen_mask(state.params, freeze)
        step_fn = make_train_step(
            model, tx, schedule, mesh=mesh, trainable_mask=mask
        )
        state = jax.device_put(state, replicated(mesh))
        batch = shard_batch(next(iter(loader)), mesh)
        before = jax.device_get(state.params)
        state, _ = step_fn(state, batch)
        after = jax.device_get(state.params)
        flat_b = jax.tree_util.tree_flatten_with_path(before)[0]
        flat_a = dict(jax.tree_util.tree_flatten_with_path(after)[0])
        flat_m = dict(jax.tree_util.tree_flatten_with_path(mask)[0])
        moved = 0
        for path, b in flat_b:
            a = flat_a[path]
            if flat_m[path]:
                moved += int(not np.allclose(b, a))
            else:
                np.testing.assert_array_equal(
                    b, a, err_msg=f"frozen {jax.tree_util.keystr(path)} moved"
                )
        assert moved > 0  # trainable params did update


class TestShardedEval:
    def test_multichip_eval_matches_single(self, tmp_path):
        """run_eval over the 8-device mesh == single-device metrics."""
        import jax

        from mx_rcnn_tpu.cli.eval_cli import run_eval
        from mx_rcnn_tpu.config import get_config
        from mx_rcnn_tpu.train.loop import build_all

        cfg = get_config("tiny_synthetic", workdir=str(tmp_path))
        _, _, state, _, _ = build_all(cfg, mesh=None)

        multi = run_eval(cfg, state=state)

        # Force the single-device path by hiding the mesh.
        orig = jax.device_count
        try:
            jax.device_count = lambda *a, **k: 1
            single = run_eval(cfg, state=state)
        finally:
            jax.device_count = orig
        for k, v in single.items():
            assert np.isclose(multi[k], v, atol=1e-5), (k, multi[k], v)


class TestShardedPallasRoiAlign:
    """VERDICT r2 #2: the Pallas ROIAlign rides shard_map on >1-chip data
    meshes (interpret mode on the fake CPU mesh runs the real grid/DMA
    logic); numerics must match the XLA path it replaced."""

    def test_sharded_helper_matches_vmapped_xla(self, rng):
        from mx_rcnn_tpu.ops.pallas.roi_align import sharded_multilevel_roi_align
        from mx_rcnn_tpu.ops.roi_align import multilevel_roi_align
        from mx_rcnn_tpu.parallel.mesh import DATA_AXIS

        mesh = make_mesh()
        b, r = 8, 16
        pyr = {
            l: jnp.asarray(
                rng.rand(b, 64 >> (l - 2), 88 >> (l - 2), 128), jnp.float32
            )
            for l in (2, 3, 4, 5)
        }
        rois = np.asarray(rng.rand(b, r, 4) * 50, np.float32)
        rois[..., 2:] = rois[..., :2] + 10 + rng.rand(b, r, 2) * 40
        rois = jnp.asarray(rois)
        out = jax.jit(
            lambda p, rr: sharded_multilevel_roi_align(
                p, rr, 7, 2, mesh, DATA_AXIS, interpret=True
            )
        )(pyr, rois)
        ref = jax.vmap(
            lambda p, rr: multilevel_roi_align(
                p, rr, output_size=7, sampling_ratio=2
            )
        )(pyr, rois)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-4
        )

    def test_sharded_kernel_lowers_for_the_tpu_platform(self, rng):
        """What interpret mode cannot see: jax refuses to lower a Mosaic
        kernel under a shard_map that leaves ANY mesh axis to GSPMD, even
        one of size 1.  Lowering for the TPU platform needs no TPU, so the
        real (non-interpret) kernel call is lowered here, forward and
        backward, on the (data, model) mesh the train step uses."""
        from mx_rcnn_tpu.ops.pallas.roi_align import sharded_multilevel_roi_align
        from mx_rcnn_tpu.parallel.mesh import DATA_AXIS

        mesh = make_mesh()
        assert len(mesh.axis_names) == 2  # the axis that must be manual too
        b = mesh.shape[DATA_AXIS]
        pyr = {
            l: jnp.asarray(
                rng.rand(b, 64 >> (l - 2), 88 >> (l - 2), 128), jnp.bfloat16
            )
            for l in (2, 3, 4, 5)
        }
        rois = jnp.asarray([[[4.0, 4.0, 60.0, 50.0]] * 8] * b, jnp.float32)

        def loss(p, rr):
            out = sharded_multilevel_roi_align(p, rr, 7, 2, mesh, DATA_AXIS)
            return jnp.sum(out.astype(jnp.float32))

        text = (
            jax.jit(jax.value_and_grad(loss)).trace(pyr, rois)
            .lower(lowering_platforms=("tpu",)).as_text()
        )
        assert text.count("tpu_custom_call") == 2  # forward + backward

    def test_sharded_train_step_pallas_matches_xla(self, monkeypatch):
        """Full sharded train step, pallas-shardmap vs xla backend: same
        seed, same batch, (near-)identical metrics — and the trace must
        actually take the shard_map path, not silently fall back."""
        import dataclasses

        from mx_rcnn_tpu.detection import graph
        from mx_rcnn_tpu.train.loop import build_all

        mesh = make_mesh()
        roidb = SyntheticDataset(num_images=8, image_hw=(128, 128)).roidb()

        def one_step(impl):
            cfg = get_config("tiny_synthetic")
            cfg = dataclasses.replace(
                cfg,
                model=dataclasses.replace(
                    cfg.model,
                    rcnn=dataclasses.replace(
                        cfg.model.rcnn, roi_align_impl=impl
                    ),
                ),
            )
            model, tx, state, step_fn, gb = build_all(cfg, mesh)
            loader = DetectionLoader(
                roidb, cfg.data, batch_size=gb, train=True, seed=0,
                prefetch=False, num_workers=0,
            )
            state = jax.device_put(state, replicated(mesh))
            batch = shard_batch(next(iter(loader)), mesh)
            state, metrics = step_fn(state, batch)
            return {k: float(v) for k, v in jax.device_get(metrics).items()}

        monkeypatch.setenv("MX_RCNN_PALLAS_INTERPRET", "1")
        graph.LAST_POOL_IMPL = None
        pallas_metrics = one_step("pallas")
        assert graph.LAST_POOL_IMPL == "pallas-shardmap"
        xla_metrics = one_step("xla")
        assert graph.LAST_POOL_IMPL == "xla"
        for k in xla_metrics:
            assert np.isclose(pallas_metrics[k], xla_metrics[k], atol=1e-4), (
                k, pallas_metrics[k], xla_metrics[k],
            )


class TestSpatialPartition:
    """Spatial (height-axis) partitioning — the CNN analog of sequence
    parallelism: convs sharded over chips with XLA halo exchange."""

    def test_matches_pure_dp_numerics(self):
        import dataclasses

        import jax

        from mx_rcnn_tpu.config import get_config
        from mx_rcnn_tpu.data import DetectionLoader, SyntheticDataset
        from mx_rcnn_tpu.parallel import make_mesh, replicated, shard_batch
        from mx_rcnn_tpu.train.loop import build_all

        cfg = get_config("tiny_synthetic")
        cfg_sp = dataclasses.replace(
            cfg, train=dataclasses.replace(cfg.train, spatial_partition=4)
        )

        roidb = SyntheticDataset(num_images=4, image_hw=cfg.data.image_size).roidb()

        def one_step(c, mesh):
            model, tx, state, step_fn, gb = build_all(c, mesh)
            loader = DetectionLoader(
                roidb, c.data, batch_size=gb, train=True, seed=0,
                prefetch=False, num_workers=0,
            )
            batch = next(iter(loader))
            if mesh is not None:
                state = jax.device_put(state, replicated(mesh))
                batch = shard_batch(
                    batch, mesh, spatial=c.train.spatial_partition > 1
                )
            state, metrics = step_fn(state, batch)
            return {k: float(v) for k, v in jax.device_get(metrics).items()}, gb

        # 8 devices: (8 data, 1 model) vs (2 data, 4 model-spatial).
        m_dp = make_mesh(jax.devices()[:2])  # 2-way DP baseline, batch 2
        m_sp = make_mesh(jax.devices(), model_parallel=4)  # batch 2, sp=4
        dp_metrics, gb_dp = one_step(cfg, m_dp)
        sp_metrics, gb_sp = one_step(cfg_sp, m_sp)
        assert gb_dp == gb_sp == 2  # same global batch -> comparable
        for k in dp_metrics:
            assert np.isclose(sp_metrics[k], dp_metrics[k], atol=2e-2), (
                k, sp_metrics[k], dp_metrics[k],
            )

    def test_global_batch_accounting(self):
        import dataclasses

        import jax

        from mx_rcnn_tpu.config import get_config
        from mx_rcnn_tpu.parallel import make_mesh
        from mx_rcnn_tpu.train.loop import build_all

        cfg = get_config("tiny_synthetic")
        cfg = dataclasses.replace(
            cfg, train=dataclasses.replace(cfg.train, spatial_partition=2)
        )
        mesh = make_mesh(jax.devices(), model_parallel=2)
        *_, gb = build_all(cfg, mesh)
        assert gb == 4  # 8 devices / sp 2


class TestHostPrefetcher:
    """The r6 host-side double buffer (parallel/prefetch.py): batch order
    is the determinism contract (quarantine substitution, chaos bit-exact
    resume all key off it), exceptions belong to the stream position they
    occurred at, and close() must actually stop the thread."""

    def test_order_preserved(self):
        from mx_rcnn_tpu.parallel.prefetch import _HostPrefetcher

        p = _HostPrefetcher(iter(range(200)), depth=4)
        assert list(p) == list(range(200))

    def test_exception_relayed_after_preceding_items(self):
        from mx_rcnn_tpu.parallel.prefetch import _HostPrefetcher

        def src():
            yield 0
            yield 1
            raise ValueError("loader died")

        p = _HostPrefetcher(src(), depth=2)
        assert next(p) == 0
        assert next(p) == 1
        with pytest.raises(ValueError, match="loader died"):
            next(p)
        # A failed stream stays terminated.
        with pytest.raises(StopIteration):
            next(p)

    def test_close_stops_thread_while_producer_blocked(self):
        import itertools

        from mx_rcnn_tpu.parallel.prefetch import _HostPrefetcher

        p = _HostPrefetcher(itertools.count(), depth=1)
        assert next(p) == 0
        p.close()  # producer is blocked on a full queue right now
        assert not p._thread.is_alive()

    def test_device_prefetch_generator_close_joins_thread(self):
        import itertools
        import threading

        from mx_rcnn_tpu.parallel.prefetch import device_prefetch

        def alive():
            return [
                t for t in threading.enumerate()
                if t.name == "host-prefetch" and t.is_alive()
            ]

        before = len(alive())
        gen = device_prefetch(
            iter(np.arange(64).reshape(8, 8)), mesh=None, depth=2
        )
        assert np.asarray(next(gen)).shape == (8,)
        assert len(alive()) == before + 1
        gen.close()
        assert len(alive()) == before

    def test_host_depth_zero_is_synchronous_fallback(self):
        import threading

        from mx_rcnn_tpu.parallel.prefetch import device_prefetch

        n_before = len(
            [t for t in threading.enumerate() if t.name == "host-prefetch"]
        )
        out = list(
            device_prefetch(iter(range(10)), mesh=None, depth=2, host_depth=0)
        )
        assert [int(np.asarray(x)) for x in out] == list(range(10))
        n_after = len(
            [t for t in threading.enumerate() if t.name == "host-prefetch"]
        )
        assert n_after == n_before
