"""The three ops the decoder backbone brought (ops/kda.py, ops/attention.py,
ops/moe.py) against their plain forms, float32 on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.ops import kda
from mx_rcnn_tpu.ops.attention import causal_attention, causal_attention_dense
from mx_rcnn_tpu.ops.kda import kda_chunked, kda_recurrent, short_conv
from mx_rcnn_tpu.ops.moe import held_experts, route, segment_rows


@pytest.fixture(params=["xla", "kernel"])
def width(request, monkeypatch):
    """The head width at which the chunk-local part runs as plain XLA (16), and
    the one at which it runs as the Pallas kernel pair (128, chunk 64) once
    ``kda_chunked`` finds a TPU: here the kernels are interpreted."""
    if request.param == "xla":
        return 16
    monkeypatch.setattr(kda, "_takes_kernel", kda.kda_kernel.supported)
    return 128


def _kda_inputs(seed, b, t, h=2, d=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, t, h, d))) * d**-0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, d)))
    v = jax.random.normal(ks[2], (b, t, h, d))
    g = -5.0 * jax.nn.sigmoid(2.3 * jax.random.normal(ks[3], (b, t, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta


# 64 and 128: whole chunks; 37, 100, 130: a ragged last chunk; 16: one chunk
@pytest.mark.parametrize("length", [16, 37, 64, 100, 128, 130])
def test_chunked_kda_is_the_recurrence(length):
    args = _kda_inputs(length, 2, length)
    want = kda_recurrent(*args)
    got = kda_chunked(*args, chunk=16 if length == 16 else 64, dtype=jnp.float32)
    assert float(jnp.abs(got - want).max()) < 1e-5 * max(1.0, float(jnp.abs(want).max()))


@pytest.mark.parametrize("length", [48, 100])
def test_chunked_kda_gradients_are_the_recurrence_s(length):
    args = _kda_inputs(7 + length, 1, length)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))
    want = jax.grad(loss(kda_recurrent), argnums=(0, 1, 2, 3, 4))(*args)
    got = jax.grad(
        loss(lambda *a: kda_chunked(*a, chunk=32, dtype=jnp.float32)), argnums=(0, 1, 2, 3, 4)
    )(*args)
    for w, g in zip(want, got):
        assert float(jnp.abs(g - w).max()) < 1e-4 * float(jnp.abs(w).max())


@pytest.mark.parametrize("gate", [-5.0, -1e-3])
def test_chunked_kda_holds_at_the_gate_s_bounds(gate, width):
    """Every channel at the safe gate's bound for a whole chunk (and hardly
    decaying at all): the result AND the gradients are the recurrence's.  With
    the decay measured from a sub-chunk's start, e^-80 times a cotangent fell
    under float32's range and the decay's gradient came out 76 times off."""
    q, k, v, g, beta = _kda_inputs(3, 1, 128, d=width)
    g = jnp.full_like(g, gate)
    want = kda_recurrent(q, k, v, g, beta)
    got = kda_chunked(q, k, v, g, beta, dtype=jnp.float32)
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(jnp.abs(want).max())
    cot = jax.random.normal(jax.random.PRNGKey(0), v.shape)
    loss = lambda fn: lambda *a: jnp.sum(fn(*a) * cot)
    gw = jax.grad(loss(kda_recurrent), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    gg = jax.grad(loss(lambda *a: kda_chunked(*a, dtype=jnp.float32)),
                  argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    for name, w_, g_ in zip("qkvgb", gw, gg):
        # the decay's own gradient is a small difference of large terms where it decays hard
        tol = 1e-3 if name == "g" else 1e-5
        assert float(jnp.linalg.norm(g_ - w_)) < tol * float(jnp.linalg.norm(w_)), name


def test_no_state_leaks_from_one_image_into_the_next():
    a = _kda_inputs(11, 1, 70)
    b = _kda_inputs(12, 1, 70)
    both = tuple(jnp.concatenate([x, y]) for x, y in zip(a, b))
    out = kda_chunked(*both, chunk=32, dtype=jnp.float32)
    for i, alone in enumerate((a, b)):
        want = kda_chunked(*alone, chunk=32, dtype=jnp.float32)
        np.testing.assert_allclose(out[i:i + 1], want, atol=1e-6)
    # ... and the second image's result is not what a state carried over gives
    joined = tuple(jnp.concatenate([x, y], axis=1) for x, y in zip(a, b))
    carried = kda_recurrent(*joined)[:, 70:]
    assert float(jnp.abs(carried - out[1:]).max()) > 1e-3


def test_short_conv_is_causal_and_starts_from_zeros():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 3))
    y = short_conv(x, w)
    want = sum(w[j] * jnp.pad(x, ((0, 0), (3, 0), (0, 0)))[:, j:j + 9] for j in range(4))
    np.testing.assert_allclose(y, want, atol=1e-6)
    np.testing.assert_allclose(y[:, 0], x[:, 0] * w[3], atol=1e-6)
    moved = short_conv(x.at[:, 5].add(1.0), w)
    np.testing.assert_allclose(moved[:, :5], y[:, :5], atol=0)


# 70 positions: blocks that divide them, that leave a ragged last one, one block
@pytest.mark.parametrize("block", [7, 16, 35, 70, 512])
def test_blocked_attention_is_the_dense_one(block):
    ks = jax.random.split(jax.random.PRNGKey(block), 3)
    q = jax.random.normal(ks[0], (2, 70, 3, 12))
    k = jax.random.normal(ks[1], (2, 70, 3, 12))
    v = jax.random.normal(ks[2], (2, 70, 3, 8))
    want = causal_attention_dense(q, k, v, 0.3)
    got = causal_attention(q, k, v, 0.3, block=block, dtype=jnp.float32)
    np.testing.assert_allclose(got, want, atol=2e-6)
    gw = jax.grad(lambda q: jnp.sum(jnp.sin(causal_attention_dense(q, k, v, 0.3))))(q)
    gg = jax.grad(
        lambda q: jnp.sum(jnp.sin(causal_attention(q, k, v, 0.3, block=block, dtype=jnp.float32)))
    )(q)
    np.testing.assert_allclose(gg, gw, atol=1e-5)


def _layer(seed=0, t=50, d=16, e=32, f=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (t, d))
    router = 0.3 * jax.random.normal(ks[1], (d, e))
    bias = 0.02 * jax.random.normal(ks[2], (e,))
    mats = [0.3 * jax.random.normal(k, s) for k, s in
            zip(ks[3:], [(e, d, f), (e, d, f), (e, f, d)])]
    return x, router, bias, mats


def _dense_layer(x, experts, weights, mats):
    gate, up, down = mats
    y = 0.0
    for e in range(gate.shape[0]):
        w_e = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=1)
        y = y + w_e[:, None] * ((jax.nn.silu(x @ gate[e]) * (x @ up[e])) @ down[e])
    return y


def test_router_keeps_the_best_groups_and_normalises_over_all_picked():
    x, router, bias, _ = _layer()
    experts, weights = route(x, router, bias, n_group=4, topk_group=2, k=4, scale=2.5)
    s = jax.nn.sigmoid(x @ router)
    sel = np.asarray(s + bias)
    for t in range(x.shape[0]):
        groups = sel[t].reshape(4, 8)
        kept = np.argsort(-np.sort(groups, axis=1)[:, -2:].sum(axis=1), kind="stable")[:2]
        allowed = [g * 8 + i for g in kept for i in range(8)]
        want = sorted(allowed, key=lambda i: -sel[t, i])[:4]
        assert sorted(int(i) for i in experts[t]) == sorted(want)
    np.testing.assert_allclose(weights.sum(axis=1), 2.5, rtol=1e-6)
    np.testing.assert_allclose(
        weights, 2.5 * jnp.take_along_axis(s, experts, 1)
        / jnp.take_along_axis(s, experts, 1).sum(1, keepdims=True), rtol=1e-6)


@pytest.mark.parametrize("held", [4, 8, 32])
def test_the_shares_parts_add_up_to_the_uncut_layer(held):
    x, router, bias, mats = _layer()
    experts, weights = route(x, router, bias, 4, 2, 4, 2.5)
    want = _dense_layer(x, experts, weights, mats)
    total = 0.0
    for first in range(0, 32, held):
        part, c = held_experts(
            x, experts, weights, *(m[first:first + held] for m in mats), first,
            x.shape[0] * min(4, held), dtype=jnp.float32,   # one segment of every slot
        )
        assert float(c["moe_dropped_slots"]) == 0.0
        total = total + part
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_no_slot_is_dropped_when_the_routing_piles_onto_one_expert():
    x, _, _, mats = _layer()
    t = x.shape[0]
    experts = jnp.tile(jnp.asarray([[2, 9, 17, 30]], jnp.int32), (t, 1))  # every token: expert 2
    weights = jnp.full((t, 4), 0.625)
    part, c = held_experts(x, experts, weights, *(m[:4] for m in mats), 0,
                           4 * t, dtype=jnp.float32)   # one segment of every slot
    assert float(c["moe_dropped_slots"]) == 0.0 and float(c["moe_slots_here"]) == t
    assert float(c["moe_load_max_over_mean"]) == pytest.approx(4.0)
    assert float(c["moe_tokens_without_held_expert"]) == 0.0
    np.testing.assert_allclose(part, _dense_layer(x, experts, weights, [m[:4] for m in mats]),
                               atol=2e-5)


@pytest.mark.parametrize("segment", [16, 24, 64, 200])
def test_the_result_does_not_depend_on_the_segment(segment):
    """Segments smaller than the routed slots (several run, a group is cut
    across them), larger (the rest are skipped), and not a divisor of the
    worst case: the same sum, nothing dropped."""
    x, router, bias, mats = _layer()
    experts, weights = route(x, router, bias, 4, 2, 4, 2.5)
    sub = [m[:4] for m in mats]
    part, c = held_experts(x, experts, weights, *sub, 0, segment, dtype=jnp.float32)
    np.testing.assert_allclose(part, _dense_layer(x, experts, weights, sub), atol=2e-5)
    assert float(c["moe_slots_here"]) == int(jnp.sum(experts < 4))
    assert float(c["moe_dropped_slots"]) == 0.0


def test_a_segment_is_sized_from_the_uniform_share():
    assert segment_rows(8400, 8, 8, 512) == 4224       # the cell's: 4 x 1,050 slots a layer
    assert segment_rows(8400, 8, 64, 512) == 33664     # 128-row multiples
    assert segment_rows(50, 4, 4, 8) == 200            # never more than every slot


def test_held_experts_gradients_reach_weights_and_tokens():
    x, router, bias, mats = _layer()
    experts, weights = route(x, router, bias, 4, 2, 4, 2.5)
    sub = [m[:8] for m in mats]

    def loss(fn):
        return lambda x, gate: jnp.sum(jnp.sin(fn(x, gate)))

    ours = lambda x, gate: held_experts(x, experts, weights, gate, sub[1], sub[2], 0, 64,
                                        dtype=jnp.float32)[0]
    plain = lambda x, gate: _dense_layer(x, experts, weights, [gate, sub[1], sub[2]])
    got = jax.grad(loss(ours), argnums=(0, 1))(x, sub[0])
    want = jax.grad(loss(plain), argnums=(0, 1))(x, sub[0])
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5)


@pytest.mark.parametrize("chunk,noise", [(16, 0.0), (64, 0.0), (64, 0.05)])
def test_chunked_kda_holds_where_tokens_look_alike(chunk, noise, width):
    """Keys that are (nearly) one vector, beta near 1 and hardly any decay: I + A
    is a constant times the all-ones triangle, the case in which the
    multiplied-out series for its inverse cancels to noise (flat image
    background does this to every chunk)."""
    b, t, h, d = 1, 128, 2, width
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    near = lambda k, shape: jax.random.normal(k, (b, 1) + shape) + noise * jax.random.normal(
        jax.random.fold_in(k, 1), (b, t) + shape)
    q, k, v = unit(near(ks[0], (h, d))) * d**-0.5, unit(near(ks[1], (h, d))), near(ks[2], (h, d))
    g = jnp.full((b, t, h, d), -1e-3)
    beta = jnp.full((b, t, h), 0.97)
    loss = lambda fn: lambda q, k, v, g, beta: jnp.sum(jnp.sin(fn(q, k, v, g, beta)))
    chunked = lambda *a: kda_chunked(*a, chunk=chunk, dtype=jnp.float32)
    want = kda_recurrent(q, k, v, g, beta)
    assert float(jnp.abs(chunked(q, k, v, g, beta) - want).max()) < 1e-5 * float(jnp.abs(want).max())
    gw = jax.grad(loss(kda_recurrent), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    gg = jax.grad(loss(chunked), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    # 128 wide the recurrence's own float32 sums are eight times as long: the
    # XLA form reads 1.7e-4 on beta's gradient there, the kernels 1.6e-4
    tol = 1e-4 if width == 16 else 2.5e-4
    for w_, g_ in zip(gw, gg):
        assert float(jnp.linalg.norm(g_ - w_)) < tol * float(jnp.linalg.norm(w_))
