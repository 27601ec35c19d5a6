"""The two ops the SambaY decoder adds, at tiny sizes on the CPU in float32:
the chunked selective scan against the token-by-token recurrence (values and
gradients; chunk lengths that do not divide T; decays from forget-in-a-token
to carry-across-chunks), and causal attention under a window in its three
forms - the dense oracle against a loop over queries, the blocked XLA form and
the Pallas kernel pair (interpreted) against the oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.ops import attention
from mx_rcnn_tpu.ops.attention import causal_attention, causal_attention_dense
from mx_rcnn_tpu.ops.pallas import attention as kernel
from mx_rcnn_tpu.ops.selective_scan import (
    CHUNK, _scan_in_blocks, selective_scan_chunked, selective_scan_recurrent)


def _scan_inputs(seed, b, t, ch=24, n=4, dt_range=(1e-3, 0.1), a_range=(1.0, 16.0)):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (b, t, ch))
    bs, cs = jax.random.normal(ks[1], (b, t, n)), jax.random.normal(ks[2], (b, t, n))
    lo, hi = np.log(dt_range[0]), np.log(dt_range[1])
    dt = jnp.exp(jax.random.uniform(ks[3], (b, t, ch), minval=lo, maxval=hi))
    a = -jax.random.uniform(ks[4], (ch, n), minval=a_range[0], maxval=a_range[1])
    return (x, dt, a, bs, cs, jax.random.normal(ks[5], (ch,))), jax.random.normal(ks[6], (b, t, ch))


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


# chunk 16: T a multiple (64), not one (37, 100), one chunk and a part (17), a single short chunk (5)
@pytest.mark.parametrize("length", [5, 16, 17, 37, 64, 100])
def test_the_chunked_scan_is_the_recurrence(length):
    args, _ = _scan_inputs(length, 2, length)
    want = selective_scan_recurrent(*args)
    got = selective_scan_chunked(*args, chunk=16)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert _rel(got, want) < 1e-6


@pytest.mark.parametrize("chunk", [5, 12, 16, 64, CHUNK])
def test_the_chunk_moves_nothing_but_the_order_of_sums(chunk):
    args, _ = _scan_inputs(1, 1, 50)
    assert _rel(selective_scan_chunked(*args, chunk=chunk), selective_scan_recurrent(*args)) < 1e-6


# dt A from -1e-5 (a state carried across every chunk) to -16 and beyond (forgotten
# within a token: exp(-16) = 1e-7), and both kinds of channel side by side
@pytest.mark.parametrize("dt_range,a_range", [
    ((1e-3, 0.1), (1.0, 16.0)), ((1e-5, 1e-4), (1.0, 2.0)), ((0.5, 8.0), (1.0, 16.0)),
    ((1e-4, 8.0), (1.0, 16.0)),
], ids=["as_drawn", "carried_across_chunks", "forgotten_in_a_token", "both"])
@pytest.mark.parametrize("length,chunk", [(48, 16), (50, 12)])
def test_the_chunked_scan_s_gradients_are_the_recurrence_s(length, chunk, dt_range, a_range):
    args, cot = _scan_inputs(3, 2, length, dt_range=dt_range, a_range=a_range)
    loss = lambda fn: lambda *a: jnp.sum(fn(*a) * cot)
    want = jax.grad(loss(selective_scan_recurrent), argnums=range(6))(*args)
    got = jax.grad(loss(lambda *a: selective_scan_chunked(*a, chunk=chunk)), argnums=range(6))(*args)
    assert _rel(selective_scan_chunked(*args, chunk=chunk), selective_scan_recurrent(*args)) < 1e-6
    for name, g, w in zip(("x", "dt", "a", "b", "c", "d"), got, want):
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert _rel(g, w) < 2e-5, name


def test_no_exponent_is_positive_where_a_token_forgets_everything():
    """dt A = -8 a token over a chunk of 128 is a cumulative product of e^-1024: a
    form that divides by it is inf or nan; this one takes exp of sums <= 0 alone."""
    args, cot = _scan_inputs(5, 1, 300, dt_range=(7.9, 8.1), a_range=(0.99, 1.01))
    got, vjp = jax.vjp(lambda *a: selective_scan_chunked(*a), *args)
    assert _rel(got, selective_scan_recurrent(*args)) < 1e-6
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in vjp(cot))


def test_no_scan_state_leaks_from_one_image_into_the_next():
    args, _ = _scan_inputs(7, 2, 40)
    both = selective_scan_chunked(*args, chunk=16)
    alone = selective_scan_chunked(*(m[1:] if m.ndim == 3 else m for m in args), chunk=16)
    np.testing.assert_allclose(both[1:], alone, rtol=1e-6, atol=1e-6)


def test_the_carry_between_chunks_is_not_nothing():
    """With slow decays a chunk's result depends on the chunks before it: the
    scan started afresh at a chunk's first position reads differently there."""
    args, _ = _scan_inputs(9, 1, 64, dt_range=(1e-3, 1e-2))
    args = args[:5] + (jnp.zeros_like(args[5]),)         # without the skip, which no state feeds
    whole = selective_scan_chunked(*args, chunk=16)
    afresh = selective_scan_chunked(*(m[:, 32:] if m.ndim == 3 else m for m in args), chunk=16)
    assert _rel(whole[:, 32:], afresh) > 0.1


def test_bfloat16_inputs_are_computed_in_float32():
    args, _ = _scan_inputs(11, 1, 40)
    low = tuple(m.astype(jnp.bfloat16) if i in (0, 3, 4) else m for i, m in enumerate(args))
    got = selective_scan_chunked(*low, chunk=16)
    want = selective_scan_recurrent(*(m.astype(jnp.float32) for m in low))
    assert got.dtype == jnp.float32 and _rel(got, want) < 1e-6


@pytest.mark.parametrize("length", [1, 7, 16, 128])
def test_a_scan_in_blocks_is_the_scan(length):
    step = lambda h, xs: (0.9 * h + xs[0], h * xs[0])
    xs = (jnp.arange(1.0, length + 1.0),)
    want = jax.lax.scan(step, jnp.float32(0.5), xs)
    got = _scan_in_blocks(step, jnp.float32(0.5), xs)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-6)


# -- attention under a window ---------------------------------------------------

TILE = 128
DIFF = (4, 2, 64, 128)   # query heads on key heads, key width, value width: two query pairs a key pair


@pytest.fixture
def tile(monkeypatch):
    return lambda rows: monkeypatch.setattr(kernel, "TILE", rows)


def _qkv(seed, b, t, heads):
    h, hkv, dk, dv = heads
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, t, h, dk)), jax.random.normal(ks[1], (b, t, hkv, dk)),
            jax.random.normal(ks[2], (b, t, hkv, dv)))


def _with_gradients(fn, args):
    loss = lambda *a: jnp.sum(jnp.sin(fn(*a)))
    return (fn(*args),) + jax.grad(loss, argnums=(0, 1, 2))(*args)


@pytest.mark.parametrize("window", [1, 3, 9, 50])
def test_the_dense_oracle_s_window_is_a_loop_over_queries(window):
    """A query sees itself and the ``window - 1`` positions before it."""
    q, k, v = _qkv(window, 1, 20, (2, 1, 8, 4))
    got = causal_attention_dense(q, k, v, 0.3, window=window)
    for t in range(20):
        lo = max(0, t - window + 1)
        for h in range(2):
            p = jax.nn.softmax(jnp.einsum("d,kd->k", q[0, t, h], k[0, lo:t + 1, 0]) * 0.3)
            np.testing.assert_allclose(got[0, t, h], p @ v[0, lo:t + 1, 0], rtol=1e-5, atol=1e-6)


# T = 300 on tiles of 128 (the last one ragged) and blocks of 64; windows: a tile, less
# than one, between one and two, two tiles and one position more, a single other key, all
# but one position; 640 / 128: whole tiles alone; 384 / 130: the edge meets two tiles
@pytest.mark.parametrize("length,window", [
    (300, 128), (300, 100), (300, 200), (300, 256), (300, 257), (300, 2), (300, 299),
    (640, 128), (384, 130), (520, 129),
])
def test_under_a_window_both_forms_are_the_dense_oracle(length, window, tile):
    tile(TILE)
    args = _qkv(length + window, 2, length, DIFF)
    want = _with_gradients(lambda *a: causal_attention_dense(*a, 0.125, window=window), args)
    xla = _with_gradients(
        lambda *a: causal_attention(*a, 0.125, block=64, dtype=jnp.float32, window=window), args)
    pair = _with_gradients(
        lambda *a: kernel.flash_attention(*a, 0.125, dtype=jnp.float32, window=window), args)
    for name, w, x, p in zip(("o", "dq", "dk", "dv"), want, xla, pair):
        assert x.shape == p.shape == w.shape, name
        assert _rel(x, w) < 2e-6 and _rel(p, w) < 2e-6, name


def test_the_kernel_path_takes_the_window(tile, monkeypatch):
    """``causal_attention`` hands its window to the kernel pair where the shapes
    are the kernel's (20 query pairs on 10 key pairs at the cell's widths here)."""
    tile(TILE)
    monkeypatch.setattr(attention, "_takes_kernel", kernel.supported)
    args = _qkv(4, 1, 200, (20, 10, 64, 128))
    got = causal_attention(*args, 0.125, dtype=jnp.float32, window=70)
    assert _rel(got, causal_attention_dense(*args, 0.125, window=70)) < 2e-6
    assert _rel(got, causal_attention_dense(*args, 0.125)) > 0.05     # and it is not nothing


@pytest.mark.parametrize("window", [300, 301, 5000])
def test_a_window_that_holds_the_sequence_is_no_window(window, tile, monkeypatch):
    tile(TILE)
    args = _qkv(8, 1, 300, DIFF)
    np.testing.assert_array_equal(
        causal_attention(*args, 0.125, block=64, dtype=jnp.float32, window=window),
        causal_attention(*args, 0.125, block=64, dtype=jnp.float32))
    monkeypatch.setattr(attention, "_takes_kernel", kernel.supported)
    np.testing.assert_array_equal(
        causal_attention(*args, 0.125, dtype=jnp.float32, window=window),
        causal_attention(*args, 0.125, dtype=jnp.float32))
    np.testing.assert_array_equal(causal_attention_dense(*args, 0.125, window=window),
                                  causal_attention_dense(*args, 0.125))


def test_bfloat16_under_a_window_is_as_near_the_oracle_as_the_xla_form(tile):
    tile(TILE)
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(12, 1, 300, DIFF))
    want = _with_gradients(
        lambda *a: causal_attention_dense(*(x.astype(jnp.float32) for x in a), 0.125, window=128),
        (q, k, v))
    xla = _with_gradients(lambda *a: causal_attention(*a, 0.125, block=64, window=128), (q, k, v))
    got = _with_gradients(lambda *a: kernel.flash_attention(*a, 0.125, window=128), (q, k, v))
    for name, x, y, z in zip(("o", "dq", "dk", "dv"), got, xla, want):
        assert x.dtype == y.dtype, name
        assert _rel(x, z) < 1.5 * _rel(y, z) + 1e-3, name


@pytest.mark.parametrize("window,tiles,want", [
    (None, 5, (5, 5)), (512, 5, (0, 1)), (1024, 5, (1, 2)), (700, 5, (0, 2)), (513, 5, (0, 1)), (514, 5, (0, 2)),
    (1, 5, (0, 0)), (2, 5, (0, 1)), (512, 0, (0, 0)), (5000, 3, (3, 3)),
])
def test_which_tiles_meet_the_band(window, tiles, want):
    """(tiles before the diagonal's wholly inside the band, tiles that meet it)."""
    assert kernel._band(window, 512, tiles) == want
