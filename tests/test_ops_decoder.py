"""The ops the decoder backbones brought (ops/kda.py, ops/attention.py,
ops/moe.py, ops/ssd.py) against their plain forms, float32 on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.ops import kda
from mx_rcnn_tpu.ops.attention import causal_attention, causal_attention_dense
from mx_rcnn_tpu.ops.kda import kda_chunked, kda_recurrent, short_conv
from mx_rcnn_tpu.ops.moe import held_experts, route
from mx_rcnn_tpu.ops.ssd import ssd_chunked, ssd_recurrent


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
    """A loop over the experts: gated (gate, up, down) or, gate None, relu^2."""
    gate, up, down = mats
    y = 0.0
    for e in range(up.shape[0]):
        w_e = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=1)
        hidden = jnp.square(jax.nn.relu(x @ up[e])) if gate is None \
            else jax.nn.silu(x @ gate[e]) * (x @ up[e])
        y = y + w_e[:, None] * (hidden @ down[e])
    return y


def _cut(mats, lo, hi):
    return [None if m is None else m[lo:hi] for m in mats]


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


def test_one_group_routes_over_every_expert():
    """``n_group`` 1: no group stage, the top k of score + bias over them all."""
    x, router, bias, _ = _layer()
    experts, weights = route(x, router, bias, n_group=1, topk_group=1, k=6, scale=2.5)
    s = jax.nn.sigmoid(x @ router)
    want = jnp.argsort(-(s + bias), axis=1)[:, :6]
    np.testing.assert_array_equal(jnp.sort(experts, axis=1), jnp.sort(want, axis=1))
    np.testing.assert_allclose(weights.sum(axis=1), 2.5, rtol=1e-6)


FORMS = ["swiglu", "relu2"]


def _form(mats, form):
    """The gated three-matrix expert, or the two-matrix relu^2 one (no gate)."""
    return [None if form == "relu2" else mats[0], mats[1], mats[2]]


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("held", [4, 8, 32])
def test_the_shares_parts_add_up_to_the_uncut_layer(held, form):
    """The 32 / held shares' routed parts are the uncut layer's (the shared
    expert is added outside, once), for the gated three-matrix expert and the
    two-matrix relu^2 one."""
    x, router, bias, mats = _layer()
    mats = _form(mats, form)
    experts, weights = route(x, router, bias, 4, 2, 4, 2.5)
    want = _dense_layer(x, experts, weights, mats)
    total, slots = 0.0, 0.0
    for first in range(0, 32, held):
        part, c = held_experts(x, experts, weights, *_cut(mats, first, first + held), first,
                               dtype=jnp.float32)
        assert float(c["moe_dropped_slots"]) == 0.0
        total, slots = total + part, slots + float(c["moe_slots_here"])
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert slots == experts.size            # every pick is some share's


@pytest.mark.parametrize("form", FORMS)
def test_no_slot_is_dropped_when_the_routing_piles_onto_one_expert(form):
    x, _, _, mats = _layer()
    sub = _cut(_form(mats, form), 0, 4)
    t = x.shape[0]
    experts = jnp.tile(jnp.asarray([[2, 9, 17, 30]], jnp.int32), (t, 1))  # every token: expert 2
    weights = jnp.full((t, 4), 0.625)
    part, c = held_experts(x, experts, weights, *sub, 0, dtype=jnp.float32)
    assert float(c["moe_dropped_slots"]) == 0.0 and float(c["moe_slots_here"]) == t
    assert float(c["moe_load_max_over_mean"]) == pytest.approx(4.0)
    assert float(c["moe_tokens_without_held_expert"]) == 0.0
    np.testing.assert_allclose(part, _dense_layer(x, experts, weights, sub), atol=2e-5)


@pytest.mark.parametrize("form", FORMS)
def test_held_experts_are_the_loop_values_gradients_and_counters(form):
    """Every held expert over every token, weighted by the routing weight:
    values, gradients to tokens, weights and the routing weights, counters."""
    x, router, bias, mats = _layer()
    experts, weights = route(x, router, bias, 1, 1, 6, 2.5)
    gate, up, down = _cut(_form(mats, form), 0, 8)
    ours = lambda x, w, up, down: held_experts(x, experts, w, gate, up, down, 0, dtype=jnp.float32)
    plain = lambda x, w, up, down: _dense_layer(x, experts, w, [gate, up, down])
    part, c = ours(x, weights, up, down)
    np.testing.assert_allclose(part, plain(x, weights, up, down), atol=2e-5)
    held = experts < 8
    assert float(c["moe_slots_here"]) == int(jnp.sum(held)) > 0
    assert float(c["moe_dropped_slots"]) == 0.0
    load = jnp.sum(experts[:, :, None] == jnp.arange(8), axis=(0, 1))
    assert float(c["moe_load_max_over_mean"]) == pytest.approx(float(load.max() * 8 / load.sum()))
    assert float(c["moe_tokens_without_held_expert"]) == pytest.approx(
        1.0 - float(jnp.mean(jnp.any(held, axis=1))))
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))
    got = jax.grad(loss(lambda *a: ours(*a)[0]), argnums=(0, 1, 2, 3))(x, weights, up, down)
    want = jax.grad(loss(plain), argnums=(0, 1, 2, 3))(x, weights, up, down)
    for g, w in zip(got, want):
        assert float(jnp.abs(w).max()) > 1e-3
        np.testing.assert_allclose(g, w, atol=5e-5)


def test_the_gate_s_gradient_is_the_loop_s():
    x, router, bias, mats = _layer()
    experts, weights = route(x, router, bias, 4, 2, 4, 2.5)
    sub = _cut(mats, 0, 8)
    loss = lambda fn: lambda gate: jnp.sum(jnp.sin(fn(gate)))
    ours = lambda gate: held_experts(x, experts, weights, gate, sub[1], sub[2], 0,
                                     dtype=jnp.float32)[0]
    plain = lambda gate: _dense_layer(x, experts, weights, [gate, sub[1], sub[2]])
    np.testing.assert_allclose(jax.grad(loss(ours))(sub[0]), jax.grad(loss(plain))(sub[0]),
                               atol=2e-5)


@pytest.mark.parametrize("form", FORMS)
def test_a_token_that_picked_no_held_expert_gets_nothing_and_sends_no_gradient(form):
    """The held experts run over every token; where a token picked none of
    them its row of the result is exactly 0, and so is its gradient."""
    x, router, bias, mats = _layer()
    experts, weights = route(x, router, bias, 1, 1, 6, 2.5)
    sub = _cut(_form(mats, form), 0, 4)
    outside = ~jnp.any(experts < 4, axis=1)
    assert 0 < int(outside.sum()) < x.shape[0]
    fn = lambda x: held_experts(x, experts, weights, *sub, 0, dtype=jnp.float32)[0]
    assert float(jnp.abs(fn(x)[outside]).max()) == 0.0
    assert float(jnp.abs(jax.grad(lambda x: jnp.sum(jnp.sin(fn(x))))(x)[outside]).max()) == 0.0


@pytest.mark.parametrize("form", FORMS)
def test_the_order_of_a_token_s_picks_does_not_matter(form):
    x, router, bias, mats = _layer()
    experts, weights = route(x, router, bias, 1, 1, 6, 2.5)
    sub = _cut(_form(mats, form), 0, 8)
    part, c = held_experts(x, experts, weights, *sub, 0, dtype=jnp.float32)
    turned, c2 = held_experts(x, experts[:, ::-1], weights[:, ::-1], *sub, 0, dtype=jnp.float32)
    np.testing.assert_allclose(turned, part, atol=1e-6)
    assert {k: float(v) for k, v in c.items()} == {k: float(v) for k, v in c2.items()}


@pytest.mark.parametrize("form", FORMS)
def test_bfloat16_operands_stay_near_the_float32_layer(form):
    x, router, bias, mats = _layer()
    experts, weights = route(x, router, bias, 1, 1, 6, 2.5)
    sub = _cut(_form(mats, form), 0, 8)
    want = _dense_layer(x, experts, weights, sub)
    got, _ = held_experts(x, experts, weights, *sub, 0)
    assert got.dtype == jnp.float32
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert 1e-4 < rel < 2e-2, rel


# -- the state-space scan (ops/ssd.py) ----------------------------------------


def _ssd_inputs(seed, b, t, h=4, p=8, g=2, n=16, dt_range=(1e-3, 0.1), alike=0.0):
    """x, dt, A, B, C, D as the mixer hands them over: dt log-uniform in
    ``dt_range``, A = -(1..16); ``alike`` > 0 makes every position the first
    one plus that much noise (a flat image's patch tokens)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def draw(key, shape):
        v = jax.random.normal(key, shape)
        return v[:, :1] + alike * v if alike else v

    x, bm, cm = draw(ks[0], (b, t, h, p)), draw(ks[1], (b, t, g, n)), draw(ks[2], (b, t, g, n))
    lo, hi = np.log(dt_range[0]), np.log(dt_range[1])
    dt = jnp.exp(lo + (hi - lo) * jax.random.uniform(ks[3], (b, t, h)))
    a = -jax.random.uniform(ks[4], (h,), minval=1.0, maxval=16.0)
    return x, dt, a, bm, cm, jax.random.uniform(ks[5], (h,), minval=0.7, maxval=1.0)


# 64 and 128: whole chunks; 37, 100, 130: a ragged last chunk; 16: one chunk
@pytest.mark.parametrize("length", [16, 37, 64, 100, 128, 130])
def test_chunked_ssd_is_the_recurrence(length):
    args = _ssd_inputs(length, 2, length)
    want = ssd_recurrent(*args)
    got = ssd_chunked(*args, chunk=32, dtype=jnp.float32)
    np.testing.assert_allclose(got, want, atol=1e-5 * float(jnp.abs(want).max()))


# dt at the published floor (a chunk keeps its state: the carry is everything),
# at a value where a head forgets within a chunk (exp(-16 x 2 x 32) underflows:
# the masked difference must give 0, not inf or nan; the cumulative sum reaches
# 1,000 there and its differences keep 1e-4, where the published range keeps
# 1e-5), and tokens that look alike
@pytest.mark.parametrize("dt_range,alike,tol", [
    ((1e-3, 1e-3), 0.0, 1e-4), ((0.1, 0.1), 0.0, 1e-4), ((2.0, 2.0), 0.0, 1e-3),
    ((1e-3, 0.1), 0.05, 1e-4),
])
def test_chunked_ssd_gradients_are_the_recurrence_s(dt_range, alike, tol):
    args = _ssd_inputs(7, 2, 100, dt_range=dt_range, alike=alike)
    cot = jax.random.normal(jax.random.PRNGKey(1), args[0].shape)
    loss = lambda fn: lambda *a: jnp.sum(fn(*a) * cot)
    chunked = lambda *a: ssd_chunked(*a, chunk=32, dtype=jnp.float32)
    np.testing.assert_allclose(chunked(*args), ssd_recurrent(*args), atol=2e-5)
    got = jax.grad(loss(chunked), argnums=range(6))(*args)
    want = jax.grad(loss(ssd_recurrent), argnums=range(6))(*args)
    for name, g_, w_ in zip(("x", "dt", "a", "b", "c", "d"), got, want):
        assert bool(jnp.isfinite(g_).all()), name
        assert float(jnp.linalg.norm(g_ - w_)) <= tol * float(jnp.linalg.norm(w_)), name


def test_no_ssd_state_leaks_from_one_image_into_the_next():
    a = _ssd_inputs(11, 1, 70)
    b = _ssd_inputs(12, 1, 70)
    both = tuple(jnp.concatenate([x, y]) if x.ndim > 1 else x for x, y in zip(a, b))
    out = ssd_chunked(*both, chunk=32, dtype=jnp.float32)
    shared = lambda args: args[:2] + (a[2],) + args[3:5] + (a[5],)   # one layer's A and D
    for i, alone in enumerate((a, shared(b))):
        want = ssd_chunked(*alone, chunk=32, dtype=jnp.float32)
        np.testing.assert_allclose(out[i:i + 1], want, atol=1e-6)
    # ... and the second image's result is not what a state carried over gives
    joined = tuple(jnp.concatenate([x, y], axis=1) if x.ndim > 1 else x for x, y in zip(a, b))
    carried = ssd_recurrent(*joined)[:, 70:]
    assert float(jnp.abs(carried - out[1:]).max()) > 1e-3


def test_the_carry_between_chunks_is_not_nothing():
    """At the published dt range the state that crosses a chunk's seam carries
    a good part of the result: leaving it out must show."""
    args = _ssd_inputs(5, 1, 96)
    whole = ssd_chunked(*args, chunk=32, dtype=jnp.float32)
    cut = lambda lo: tuple(m[:, lo:lo + 32] if m.ndim > 1 else m for m in args)
    no_carry = jnp.concatenate([ssd_recurrent(*cut(lo)) for lo in (0, 32, 64)], axis=1)
    np.testing.assert_allclose(no_carry[:, :32], whole[:, :32], atol=1e-5)
    gap = jnp.linalg.norm(no_carry[:, 32:] - whole[:, 32:]) / jnp.linalg.norm(whole[:, 32:])
    assert float(gap) > 0.1


# -- grouped-query attention ---------------------------------------------------


@pytest.mark.parametrize("block", [16, 35, 512])
@pytest.mark.parametrize("kv", [1, 2])
def test_grouped_attention_is_the_dense_one(kv, block):
    """Fewer key heads than query heads: a key head serves consecutive query
    heads; the oracle repeats K and V, the blocked form does not."""
    ks = jax.random.split(jax.random.PRNGKey(block + kv), 3)
    q = jax.random.normal(ks[0], (2, 70, 4, 12))
    k = jax.random.normal(ks[1], (2, 70, kv, 12))
    v = jax.random.normal(ks[2], (2, 70, kv, 8))
    want = causal_attention_dense(q, k, v, 0.3)
    by_hand = causal_attention_dense(
        q, jnp.repeat(k, 4 // kv, axis=2), jnp.repeat(v, 4 // kv, axis=2), 0.3)
    np.testing.assert_allclose(want, by_hand, atol=0)
    blocked = lambda q, k, v: causal_attention(q, k, v, 0.3, block=block, dtype=jnp.float32)
    got = blocked(q, k, v)
    assert got.shape == (2, 70, 4, 8)
    np.testing.assert_allclose(got, want, atol=2e-6)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))
    gg = jax.grad(loss(blocked), argnums=(0, 1, 2))(q, k, v)
    gw = jax.grad(loss(lambda *a: causal_attention_dense(*a, 0.3)), argnums=(0, 1, 2))(q, k, v)
    for g_, w_ in zip(gg, gw):
        np.testing.assert_allclose(g_, w_, atol=2e-5)


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
