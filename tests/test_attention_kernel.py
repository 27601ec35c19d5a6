"""Causal attention as the Pallas kernel pair (ops/pallas/attention.py),
interpreted on the CPU at both decoder cells' head shapes (32 query heads on 2
key heads of 128; 32 on 32 with 192-wide keys and 128-wide values) and at
lengths that are no multiple of a tile: against the dense oracle in float32
and against the blocked XLA form it replaces on the TPU
(ops/attention.py::causal_attention), which stays the path of every other
platform and shape.  What Mosaic makes of the kernels is compiled here for a
described chip and run in tests/_kernels_tpu_worker.py on a real one."""

import jax
import jax.numpy as jnp
import pytest

from mx_rcnn_tpu.ops import attention
from mx_rcnn_tpu.ops.pallas import attention as kernel

GQA = (32, 2, 128, 128)      # nemotron_twotower_det: heads, key heads, Dk, Dv
MLA = (32, 32, 192, 128)     # ling3_flash_vl_det
DIFF = (20, 10, 64, 128)     # phi4_mini_flash_det: one map of the differential attention
TILE = 128                   # the least the kernels take: several tiles in 300 positions


@pytest.fixture
def tile(monkeypatch):
    """Sets the kernels' tile, a program constant, for a test: the one hook
    (``supported`` reads the same constant)."""
    return lambda rows: monkeypatch.setattr(kernel, "TILE", rows)


@pytest.fixture
def on_the_kernel(monkeypatch):
    """``causal_attention`` takes the kernel wherever the shapes are the
    kernel's, as it does on the TPU; off the TPU the kernel runs interpreted."""
    monkeypatch.setattr(attention, "_takes_kernel", kernel.supported)


def _inputs(seed, b, t, heads, operands=jnp.float32):
    h, hkv, dk, dv = heads
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, t, h, dk))
    k = jax.random.normal(ks[1], (b, t, hkv, dk))
    v = jax.random.normal(ks[2], (b, t, hkv, dv))
    return tuple(x.astype(operands) for x in (q, k, v)), dk ** -0.5


def _rel(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _with_gradients(fn, args):
    loss = lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32)))
    return (fn(*args),) + jax.grad(loss, argnums=(0, 1, 2))(*args)


# 200: two tiles, the second ragged; 128: one whole tile
@pytest.mark.parametrize("length", [200, 128])
@pytest.mark.parametrize("heads", [GQA, MLA], ids=["32_on_2x128", "32_on_32x192_128"])
def test_in_float32_the_kernel_pair_is_the_dense_oracle(heads, length, tile):
    tile(TILE)
    args, scale = _inputs(length, 1, length, heads)
    got = _with_gradients(
        lambda *a: kernel.flash_attention(*a, scale, dtype=jnp.float32), args)
    want = _with_gradients(lambda *a: attention.causal_attention_dense(*a, scale), args)
    for name, x, y in zip(("o", "dq", "dk", "dv"), got, want):
        assert x.shape == y.shape and x.dtype == jnp.float32, name
        assert _rel(x, y) < 2e-6, name


@pytest.mark.parametrize("heads", [GQA, MLA], ids=["32_on_2x128", "32_on_32x192_128"])
def test_in_bfloat16_the_kernel_pair_is_as_near_the_oracle_as_the_xla_form(heads, tile):
    tile(TILE)
    (q, k, v), scale = _inputs(7, 1, 200, heads, operands=jnp.bfloat16)
    want = _with_gradients(
        lambda *a: attention.causal_attention_dense(*(x.astype(jnp.float32) for x in a), scale),
        (q, k, v))
    xla = _with_gradients(lambda *a: attention.causal_attention(*a, scale, block=64), (q, k, v))
    got = _with_gradients(lambda *a: kernel.flash_attention(*a, scale), (q, k, v))
    for name, x, y, z in zip(("o", "dq", "dk", "dv"), got, xla, want):
        assert x.shape == y.shape and x.dtype == y.dtype, name
        # the same roundings in another order: 2^-8 a value, less in the norm
        assert _rel(x, z) < max(1.2 * _rel(y, z), 1e-3) and _rel(x, z) < 1e-2, name
        assert _rel(x, y) < 1e-2, name


@pytest.mark.parametrize("noise", [0.05, 0.3])
def test_where_tokens_look_alike_dq_is_what_is_left_after_the_softmax_s_sum_cancels(noise, tile):
    """A flat image's patch tokens: every key and value of a head is one vector
    but for ``noise``.  The scores' cotangents then sum to nothing over a
    row's keys and dq is the small remainder, 1e-4 of dv.  It survives only if
    sum(o * do) is the mean of dp as the kernel's own matmuls see both: o
    normalised by the sum of the probabilities as rounded for the matmul, do
    rounded as in dp.  (With either left in float32 dq read 10 and 0.7 times
    its own size off on these inputs, the XLA form 0.05: PERF.md section 6.)"""
    tile(256)
    b, t, h, hkv, d = 1, 700, 2, 1, 128
    ks = jax.random.split(jax.random.PRNGKey(1), 8)
    alike = lambda i, heads: (jax.random.normal(ks[i], (b, 1, heads, d))
                              + noise * jax.random.normal(ks[i + 1], (b, t, heads, d)))
    args = tuple(x.astype(jnp.bfloat16) for x in (alike(0, h), alike(2, hkv), alike(4, hkv)))
    cot, scale = alike(6, h), d ** -0.5
    grads = lambda fn: jax.grad(lambda *a: jnp.sum(fn(*a) * cot), argnums=(0, 1, 2))(*args)
    want = grads(lambda *a: attention.causal_attention_dense(
        *(x.astype(jnp.float32) for x in a), scale))
    xla = grads(lambda *a: attention.causal_attention(*a, scale, block=256))
    got = grads(lambda *a: kernel.flash_attention(*a, scale))
    assert float(jnp.linalg.norm(want[0]) / jnp.linalg.norm(want[2])) < 1e-2
    for name, x, y, z in zip(("dq", "dk", "dv"), got, xla, want):
        assert _rel(x, z) < max(1.5 * _rel(y, z), 5e-3), name


# several whole tiles before a ragged one: both kernels' loops over whole tiles run
@pytest.mark.parametrize("batch,length,heads", [(2, 600, (4, 2, 128, 128)), (1, 520, (2, 2, 64, 256))])
def test_the_kernel_path_is_the_dense_oracle(on_the_kernel, batch, length, heads, tile):
    tile(TILE)
    args, scale = _inputs(length, batch, length, heads)
    fn = lambda *a: attention.causal_attention(*a, scale, dtype=jnp.float32)
    assert "pallas_call" in str(jax.make_jaxpr(fn)(*args))
    got = _with_gradients(fn, args)
    want = _with_gradients(lambda *a: attention.causal_attention_dense(*a, scale), args)
    for name, x, y in zip(("o", "dq", "dk", "dv"), got, want):
        assert _rel(x, y) < 2e-6, name


# Every way a sequence can end against the tiles: a last tile that is narrower
# and ends at T exactly (384 on 256, 640 and 1152 on 512: a 768 x 768 canvas's
# 2,304 on 512 is of this kind), narrower and overhanging (600 on 512), whole
# and overhanging (1000 on 512), whole (512, 1024); with one whole tile before
# it or two, so the backward's loop over whole query tiles runs or is empty.
@pytest.mark.parametrize("length,rows", [(384, 256), (640, 512), (1152, 512), (600, 512),
                                         (1000, 512), (512, 256), (1024, 512), (896, 256)])
def test_however_the_sequence_ends_against_the_tiles(on_the_kernel, length, rows, tile):
    tile(rows)
    heads = (2, 1, 128, 128)
    assert kernel.supported(length, *heads, jnp.float32)
    args, scale = _inputs(length, 1, length, heads)
    fn = lambda *a: attention.causal_attention(*a, scale, dtype=jnp.float32)
    assert "pallas_call" in str(jax.make_jaxpr(fn)(*args))
    got = _with_gradients(fn, args)
    want = _with_gradients(lambda *a: attention.causal_attention_dense(*a, scale), args)
    for name, x, y in zip(("o", "dq", "dk", "dv"), got, want):
        assert bool(jnp.all(jnp.isfinite(x))) and _rel(x, y) < 2e-6, name


@pytest.mark.parametrize("length", [100, 130])
def test_what_a_block_holds_past_the_sequence_s_end_reads_as_zeros(length, tile):
    """The kernels are handed q, k, v as they are: the last tile's rows past T
    are in no array (the interpreter fills them with NaN, the chip with what
    the buffer held), and results and gradients are those of the inputs padded
    with zeros to whole tiles."""
    tile(TILE)
    args, scale = _inputs(length, 2, length, (2, 1, 128, 128))
    padded = tuple(jnp.pad(x, ((0, 0), (0, -length % TILE), (0, 0), (0, 0))) for x in args)
    fn = lambda *a: kernel.flash_attention(*a, scale, dtype=jnp.float32)
    cut = lambda *a: fn(*a)[:, :length]
    for x, y in zip(_with_gradients(fn, args), _with_gradients(cut, padded)):
        assert bool(jnp.all(jnp.isfinite(x))) and _rel(x, y[:, :length]) < 1e-6


def test_no_image_sees_another_on_the_kernel_path(on_the_kernel):
    (a, _), (b, scale) = _inputs(11, 1, 150, (2, 1, 128, 128)), _inputs(12, 1, 150, (2, 1, 128, 128))
    both = tuple(jnp.concatenate([x, y]) for x, y in zip(a, b))
    fn = lambda *x: attention.causal_attention(*x, scale, dtype=jnp.float32)
    out = fn(*both)
    for i, alone in enumerate((a, b)):
        assert float(jnp.abs(out[i:i + 1] - fn(*alone)).max()) < 1e-6


@pytest.mark.parametrize("heads,takes", [
    ((3, 3, 12, 8), False),         # tests/test_ops_decoder.py's: no whole vreg of values
    ((4, 2, 128, 96), False),
    ((4, 3, 128, 128), False),      # key heads that do not divide the query heads' 4
    ((4, 2, 128, 128), True),
    ((2, 2, 192, 128), True),       # the key width is padded, the value width is not
])
def test_off_the_tpu_and_at_other_shapes_the_xla_form_answers(heads, takes, monkeypatch):
    """The kernel is taken by platform and shape alone: never on the CPU, and
    on a TPU only where ``supported`` says so."""
    assert kernel.supported(70, *heads, jnp.float32) == takes
    if heads[0] % heads[1]:
        return      # no form takes key heads that do not divide the query heads
    (q, k, v), scale = _inputs(3, 1, 70, heads)
    traced = lambda: str(jax.make_jaxpr(
        lambda *a: attention.causal_attention(*a, scale, block=24, dtype=jnp.float32))(q, k, v))
    assert jax.default_backend() == "cpu" and "pallas_call" not in traced()
    monkeypatch.setattr(attention, "_takes_kernel", kernel.supported)      # as if on a TPU
    assert ("pallas_call" in traced()) == takes
    got = attention.causal_attention(q, k, v, scale, block=24, dtype=jnp.float32)
    assert _rel(got, attention.causal_attention_dense(q, k, v, scale)) < 2e-6


def test_a_sequence_too_long_for_vmem_and_other_dtypes_go_to_the_xla_form():
    assert kernel.supported(4200, *GQA, jnp.bfloat16) and kernel.supported(4200, *MLA, jnp.bfloat16)
    assert kernel.supported(4200, *MLA, jnp.float32)
    assert not kernel.supported(65536, *GQA, jnp.bfloat16)
    assert not kernel.supported(4200, *GQA, jnp.float16)


def test_a_tile_is_whole_vregs_and_the_last_is_what_is_left():
    assert kernel._tiles(4200, 512) == [(lo, 512) for lo in range(0, 4096, 512)] + [(4096, 128)]
    assert kernel._tiles(1000, 512) == [(0, 512), (512, 512)]
    assert kernel._tiles(2304, 512) == [(lo, 512) for lo in range(0, 2048, 512)] + [(2048, 256)]
    assert kernel._tiles(70, 512) == [(0, 128)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("heads,t,dtype,window", [
    (GQA, 4200, jnp.bfloat16, None), (MLA, 4200, jnp.bfloat16, None),
    ((4, 2, 128, 128), 1100, jnp.float32, None), ((4, 2, 128, 128), 2304, jnp.bfloat16, None),
    (DIFF, 4200, jnp.bfloat16, None), (DIFF, 4200, jnp.bfloat16, 512),
    ((4, 2, 128, 128), 2304, jnp.bfloat16, 700),
], ids=["nemotron_twotower_det", "ling3_flash_vl_det", "float32", "768x768_last_tile_narrow",
        "phi4_mini_flash_det_full", "phi4_mini_flash_det_window", "window_of_no_whole_tiles"])
def test_mosaic_compiles_both_kernels_at_the_decoder_cells_shapes(one_chip, heads, t, dtype, window):
    """q ``[2, 4200, 32 * 128]`` on k ``[2, 4200, 2 * 128]``, and q, k
    ``[2, 4200, 32 * 256]`` (192 padded) on v ``[2, 4200, 32 * 128]``, at the
    program's tile; float32 operands (six passes a matmul) at a shorter
    sequence; a 768 x 768 canvas's 2,304 positions, whose last tile is narrower
    and ends at T exactly; one map of the differential attention (20 query
    pairs on 10 key pairs, keys 64 padded to 128, values 128) over the whole
    prefix and under the window of 512; a window that is no multiple of a
    tile.  Compiled for a described v5e, run nowhere."""
    b = 2
    h, hkv, dk, dv = heads
    dk = -(-dk // kernel.LANES) * kernel.LANES
    spec = lambda width: jax.ShapeDtypeStruct((b, t, width), dtype, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(kernel._attention(q, k, v, (h, hkv), dk ** -0.5, kernel.TILE, False, window))

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        spec(h * dk), spec(hkv * dk), spec(hkv * dv)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2   # forward + backward
