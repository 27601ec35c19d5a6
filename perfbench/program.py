"""The one file of the benchmark that touches the program (``mx_rcnn_tpu``):
it builds the system under test from a configuration file and a cell, and
hands it the benchmark's weights.  Everything measured or compared lives in
the other files and imports nothing from here down.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.weights import flatten, nest


def load_config(config: dict, cell: dict):
    """The program's Config of a configuration file under a cell: the named
    preset, the file's overrides, then the cell's (``--set`` syntax)."""
    from mx_rcnn_tpu.config import apply_overrides, get_config

    cfg = get_config(config["preset"])
    return apply_overrides(cfg, list(config.get("overrides", [])) + list(cell.get("overrides", [])))


def make_mesh(chips: int):
    """None on one chip (the loop's own single-device path), else the loop's
    ``{data: chips, model: 1}`` mesh over the first ``chips`` devices."""
    if chips == 1:
        return None
    from mx_rcnn_tpu.parallel import make_mesh as _make

    return _make(model_parallel=1)


def _place_weights(tree, flat, prefix):
    """The program's tree of one collection, filled with the benchmark's
    leaves; any leaf the two sides do not share by name and shape is an error."""
    want = flatten(jax.tree_util.tree_map(lambda x: x, tree), prefix)
    have = {p: v for p, v in flat.items() if p.startswith(prefix + "/")}
    if set(want) != set(have):
        raise ValueError(
            f"{prefix}: leaves differ: program-only {sorted(set(want) - set(have))[:4]}, "
            f"benchmark-only {sorted(set(have) - set(want))[:4]}"
        )
    for p in want:
        if tuple(want[p].shape) != tuple(have[p].shape):
            raise ValueError(f"{p}: program {want[p].shape} vs benchmark {have[p].shape}")
    return nest(have, prefix)


def _unfreeze(tree):
    if hasattr(tree, "unfreeze"):
        tree = tree.unfreeze()
    if isinstance(tree, dict):
        return {k: _unfreeze(v) for k, v in tree.items()}
    return tree


def build_train(cfg, mesh, weights: dict, rng):
    """``train/loop.py::build_all``'s state and compiled step, the state's
    parameters, constants and key replaced by the benchmark's.
    -> (state, step_fn, plan, global_batch)."""
    from mx_rcnn_tpu.train.loop import build_all, build_plan

    model, tx, state, step_fn, global_batch = build_all(cfg, mesh)
    params = _place_weights(_unfreeze(state.params), weights, "params")
    model_state = {
        k: _place_weights(_unfreeze(v), weights, k) for k, v in _unfreeze(state.model_state).items()
    }
    # Copies: the step donates its state, the reference needs the originals.
    copy = lambda t: jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), t)
    state = state.replace(params=copy(params), model_state=copy(model_state), rng=copy(rng))
    plan = build_plan(cfg, mesh, model=model)
    return plan.shard_state(state), step_fn, plan, global_batch


def train_feed(cfg, plan, mesh, roidb, global_batch, seed, stats, tap=None):
    """The loop's input path over the benchmark's records:
    ``DetectionLoader`` -> ``device_prefetch`` (depth 2, host depth 1).
    ``tap(host_batch)`` sees each host batch before it is put on the device."""
    from mx_rcnn_tpu.data import DetectionLoader
    from mx_rcnn_tpu.parallel import device_prefetch

    loader = DetectionLoader(
        roidb, cfg.data, batch_size=global_batch, train=True, seed=seed,
        with_masks=cfg.model.mask.enabled,
        num_proposals=cfg.model.rpn.train_post_nms_top_n,
    )
    host_it = loader.iter_from(skip_batches=0)

    def tapped():
        try:
            for b in host_it:
                if tap is not None:
                    tap(b)
                yield b
        finally:
            close = getattr(host_it, "close", None)
            if close is not None:
                close()

    return device_prefetch(
        tapped(), mesh, depth=2, spatial=False, stacked=plan.stacked,
        host_depth=1, stats=stats,
    )


def prefetch_stats():
    from mx_rcnn_tpu.parallel import PrefetchStats

    return PrefetchStats()


def records(images, boxes, classes):
    """The program's roidb records over the benchmark's pixels and boxes."""
    from mx_rcnn_tpu.data.roidb import RoiRecord

    return [
        RoiRecord(
            image_id=str(i), image_path="", height=int(im.shape[0]), width=int(im.shape[1]),
            boxes=np.asarray(b, np.float32), gt_classes=np.asarray(c, np.int32),
            masks=None, image_array=im,
        )
        for i, (im, b, c) in enumerate(zip(images, boxes, classes))
    ]


def host_batch_dict(batch) -> dict:
    """A host Batch as the plain dict the reference takes."""
    return {
        "images": np.asarray(batch.images), "image_hw": np.asarray(batch.image_hw),
        "gt_boxes": np.asarray(batch.gt_boxes), "gt_classes": np.asarray(batch.gt_classes),
        "gt_valid": np.asarray(batch.gt_valid),
    }


def configure_cache():
    from mx_rcnn_tpu.utils.compile_cache import configure_cache as _cc

    return _cc()


def momentum_trace(opt_state):
    """The SGD momentum buffers of the loop's optimizer state, flat by leaf
    name (``params/...``); frozen leaves have none."""
    import optax

    found = []

    def visit(node):
        if isinstance(node, optax.TraceState):
            found.append(node.trace)
            return
        if isinstance(node, (tuple, list)):
            for x in node:
                visit(x)
        elif isinstance(node, dict):
            for x in node.values():
                visit(x)
        elif hasattr(node, "inner_state"):
            visit(node.inner_state)
        elif hasattr(node, "inner_states"):
            visit(node.inner_states)

    visit(opt_state)
    if len(found) != 1:
        raise ValueError(f"expected one momentum trace in the optimizer state, found {len(found)}")
    out = {}
    for path, leaf in flatten(_unfreeze_masked(found[0]), "params").items():
        if leaf is not None and hasattr(leaf, "shape"):
            out[path] = leaf
    return out


def _unfreeze_masked(tree):
    """optax's masked trees keep ``MaskedNode`` at frozen leaves: drop them."""
    import optax

    if isinstance(tree, optax.MaskedNode):
        return None
    tree = _unfreeze(tree) if not isinstance(tree, dict) else tree
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            v = _unfreeze_masked(v)
            if v is not None:
                out[k] = v
        return out
    return tree


def reset_state(state, plan, weights: dict, rng):
    """``state`` as a fresh build from other weights holds it: step 0, zero
    optimizer state, the given parameters, constants and key."""
    copy = lambda t: jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), t)
    params = _place_weights(_unfreeze(state.params), weights, "params")
    model_state = {
        k: _place_weights(_unfreeze(v), weights, k)
        for k, v in _unfreeze(state.model_state).items()
    }
    fresh = state.replace(
        step=jnp.zeros_like(state.step), params=copy(params), model_state=copy(model_state),
        opt_state=jax.tree_util.tree_map(jnp.zeros_like, state.opt_state), rng=copy(rng),
    )
    return plan.shard_state(fresh)


def pool_impl():
    """Which ROIAlign the last traced program took (``pallas``,
    ``pallas-shardmap`` or ``xla``), as the graph recorded it at trace time."""
    from mx_rcnn_tpu.detection import graph

    return graph.LAST_POOL_IMPL


def op_scopes(step_fn, *args) -> dict:
    """{HLO instruction name: named-scope path} of the compiled step, read
    from its own metadata (the compile is a cache hit; traced runs only)."""
    from perfbench.trace_reduce import scopes_from_hlo

    return scopes_from_hlo(step_fn.lower(*args).compile().as_text())
