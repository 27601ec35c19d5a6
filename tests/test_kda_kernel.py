"""The chunk-local part of the KDA scan as the Pallas kernel pair
(ops/pallas/kda.py), interpreted on the CPU at the kernel's own shapes (chunk
64, Dk = Dv = 128): against the recurrence token by token and against the XLA
form it replaces on the TPU (ops/kda.py::_intra), which stays the path of
every other platform and shape.  What Mosaic makes of the kernels is compiled
here for a described chip and run in tests/_kernels_tpu_worker.py on a real one."""

import jax
import jax.numpy as jnp
import pytest

from mx_rcnn_tpu.ops import kda
from mx_rcnn_tpu.ops.pallas import kda as kda_kernel

D = kda_kernel.LANES
FAR = 41.0


@pytest.fixture
def on_the_kernel(monkeypatch):
    """``kda_chunked`` takes the kernel wherever the shapes are the kernel's,
    as it does on the TPU; off the TPU the kernel runs interpreted."""
    monkeypatch.setattr(kda, "_takes_kernel", kda_kernel.supported)


def _inputs(seed, b, t, h=2, operands=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, t, h, D))) * D**-0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, D)))
    v = jax.random.normal(ks[2], (b, t, h, D))
    g = -5.0 * jax.nn.sigmoid(2.3 * jax.random.normal(ks[3], (b, t, h, D)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q.astype(operands), k.astype(operands), v.astype(operands), g, beta


def _rel(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    # (e^{G_C} of a whole random chunk underflows to zeros on both sides)
    return float(jnp.linalg.norm(got - want) / jnp.maximum(jnp.linalg.norm(want), 1e-30))


def _with_gradients(fn, args):
    loss = lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32)))
    return (fn(*args),) + jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)


def _xla_intra(q, k, v, g, beta, dtype):
    """ops/kda.py::_intra on the kernel's arguments, chunk-major as the kernel's."""
    b, t, _, _ = k.shape
    assert t % kda.CHUNK == 0
    ch = lambda x, kind: jnp.moveaxis(
        x.astype(kind).reshape((b, t // kda.CHUNK, kda.CHUNK) + x.shape[2:]), 3, 1)
    xs = kda._intra(ch(q, dtype), ch(k, dtype), ch(v, dtype), ch(g, jnp.float32),
                    ch(beta, jnp.float32)[..., None], kda_kernel.SUB, FAR, dtype)
    return tuple(jnp.moveaxis(x, 2, 0) for x in xs)


# one chunk; three in one grid step; nine in three steps of three
@pytest.mark.parametrize("chunks,batch", [(1, 1), (3, 2), (9, 1)])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 4e-3)])
def test_the_kernel_s_six_results_are_the_xla_form_s(chunks, batch, dtype, tol):
    args = _inputs(chunks, batch, chunks * kda.CHUNK, operands=dtype)
    got = kda_kernel.kda_intra(*args, dtype, FAR)
    want = _xla_intra(*args, dtype)
    for name, x, y in zip(("w", "u0", "qe", "p", "ke", "eg"), got, want):
        assert x.shape == y.shape and x.dtype == y.dtype, name
        assert _rel(x, y) < tol, name


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 6e-3)])
def test_the_kernel_s_gradients_are_autodiff_s_of_the_xla_form(dtype, tol):
    """Each of the six cotangents reaches each of the five inputs."""
    args = _inputs(5, 2, 2 * kda.CHUNK, operands=dtype)
    loss = lambda fn: lambda *a: sum(
        jnp.sum(x.astype(jnp.float32) * jnp.cos(0.1 * i + x.astype(jnp.float32)))
        for i, x in enumerate(fn(*a)))
    got = jax.grad(loss(lambda *a: kda_kernel.kda_intra(*a, dtype, FAR)), argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(loss(lambda *a: _xla_intra(*a, dtype)), argnums=(0, 1, 2, 3, 4))(*args)
    for name, x, y in zip("qkvgb", got, want):
        assert x.shape == y.shape and x.dtype == y.dtype, name
        assert _rel(x, y) < tol, name


@pytest.mark.parametrize("length", [100, 130])
def test_what_a_block_holds_past_the_sequence_s_end_reads_as_zeros(length):
    """The kernels are handed q, k, v, g as they are: the last chunk's rows
    past T are in no array (the interpreter fills them with NaN, the chip
    with what the buffer held), and results and gradients are those of the
    inputs padded with zeros to whole chunks."""
    args = _inputs(length, 2, length)
    pad = -length % kda.CHUNK
    padded = tuple(jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) for x in args)
    loss = lambda *a: sum(jnp.sum(jnp.sin(x)) for x in kda_kernel.kda_intra(*a, jnp.float32, FAR))
    for x, y in zip(kda_kernel.kda_intra(*args, jnp.float32, FAR),
                    kda_kernel.kda_intra(*padded, jnp.float32, FAR)):
        assert bool(jnp.all(x == y))
    for x, y in zip(jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args),
                    jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*padded)):
        assert bool(jnp.all(jnp.isfinite(x))) and _rel(x, y[:, :length]) < 1e-6


# 64: one chunk; 100, 200: a ragged last chunk; 2 x 100: two sequences
@pytest.mark.parametrize("batch,length", [(1, 64), (2, 100), (1, 200)])
def test_the_kernel_path_is_the_recurrence(on_the_kernel, batch, length):
    args = _inputs(length, batch, length)
    chunked = lambda *a: kda.kda_chunked(*a, dtype=jnp.float32)
    assert "pallas_call" in str(jax.make_jaxpr(chunked)(*args))
    got = _with_gradients(chunked, args)
    want = _with_gradients(kda.kda_recurrent, args)
    for name, x, y in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert _rel(x, y) < 1e-5, name


@pytest.mark.parametrize("batch,length", [(2, 100), (1, 192)])
def test_in_bfloat16_the_kernel_path_is_as_near_the_recurrence_as_the_xla_form(batch, length,
                                                                             monkeypatch):
    args = _inputs(length, batch, length, operands=jnp.bfloat16)
    want = _with_gradients(kda.kda_recurrent, args)
    chunked = lambda *a: kda.kda_chunked(*a, dtype=jnp.bfloat16)
    xla = _with_gradients(chunked, args)
    monkeypatch.setattr(kda, "_takes_kernel", kda_kernel.supported)
    got = _with_gradients(chunked, args)
    for name, x, y, z in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, xla, want):
        assert x.dtype == y.dtype, name
        # one bfloat16 rounding of the scan's operands: 2^-8 a value, less in the norm
        assert _rel(x, z) < max(1.2 * _rel(y, z), 1e-3) and _rel(x, z) < 1e-2, name


def test_no_state_leaks_from_one_image_into_the_next_on_the_kernel_path(on_the_kernel):
    a, b = _inputs(11, 1, 70), _inputs(12, 1, 70)
    both = tuple(jnp.concatenate([x, y]) for x, y in zip(a, b))
    out = kda.kda_chunked(*both, dtype=jnp.float32)
    for i, alone in enumerate((a, b)):
        want = kda.kda_chunked(*alone, dtype=jnp.float32)
        assert float(jnp.abs(out[i:i + 1] - want).max()) < 1e-6


@pytest.mark.parametrize("shape,chunk", [((1, 128, 2, 16), 64), ((1, 128, 2, D), 32),
                                         ((1, 128, 2, D), 64)])
def test_off_the_tpu_and_at_other_shapes_the_xla_form_runs(shape, chunk, monkeypatch):
    """The kernel is taken by platform and shape alone: never on the CPU, and
    on a TPU only at chunk 64 with Dk = Dv = 128."""
    b, t, h, d = shape
    x = jnp.zeros(shape)
    traced = lambda: str(jax.make_jaxpr(
        lambda *a: kda.kda_chunked(*a, chunk=chunk, dtype=jnp.float32)
    )(x, x, x, x, jnp.zeros((b, t, h))))
    assert jax.default_backend() == "cpu" and "pallas_call" not in traced()
    monkeypatch.setattr(kda, "_takes_kernel", kda_kernel.supported)   # as if on a TPU
    assert ("pallas_call" in traced()) == (d == D and chunk == 64)


def test_a_grid_step_takes_whole_chunks_that_divide_the_sequence():
    assert kda_kernel._chunks_per_step(66) == 6      # the decoder cell: 4,200 positions
    assert kda_kernel._chunks_per_step(1) == 1 and kda_kernel._chunks_per_step(67) == 1
    assert kda_kernel._chunks_per_step(64) == 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_mosaic_compiles_both_kernels_at_the_decoder_cell_s_shape(one_chip, dtype):
    """q ``[2, 4224, 32 * 128]``: 66 chunks after padding, six a grid step.
    Compiled for a described v5e, run nowhere."""
    b, t, h = 2, 4224, 32
    steps = kda_kernel._chunks_per_step(t // kda.CHUNK)
    spec = lambda shape, kind: jax.ShapeDtypeStruct(shape, kind, sharding=one_chip)
    args = [spec((b, t, h * D), dtype)] * 3 + [
        spec((b, t, h * D), jnp.float32),
        spec((b, h, t // kda.CHUNK // steps, steps, kda.CHUNK), jnp.float32)]

    def loss(*a):
        out = kda_kernel._intra(*a, dtype, FAR, False)
        return sum(jnp.sum(x.astype(jnp.float32)) for x in out), out

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)).lower(
        *args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2   # forward + backward
