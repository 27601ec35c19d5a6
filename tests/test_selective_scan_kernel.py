"""The Mamba-1 selective scan as the Pallas kernel pair
(ops/pallas/selective_scan.py), interpreted on the CPU at the kernel's own
shapes (chunks of 128 positions, blocks of 1,024 channels, 8 or 16 states):
result and all six gradients against the recurrence token by token
(ops/selective_scan.py::selective_scan_recurrent), which is also the oracle of
the chunked XLA form that stays the path of every other platform and shape.
What Mosaic makes of the kernels is compiled here for a described chip and run
in tests/_kernels_tpu_worker.py on a real one."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.ops import selective_scan as scan
from mx_rcnn_tpu.ops.pallas import selective_scan as kernel
from mx_rcnn_tpu.ops.selective_scan import selective_scan_chunked, selective_scan_recurrent

NAMES = ("y", "dx", "ddt", "da", "db", "dc", "dd")


@pytest.fixture
def on_the_kernel(monkeypatch):
    """``selective_scan_chunked`` takes the kernel wherever the shapes are the
    kernel's, as it does on the TPU; off the TPU the kernel runs interpreted."""
    monkeypatch.setattr(scan, "_takes_kernel", kernel.supported)


def _inputs(seed, b, t, ch=kernel.BLOCK, n=8, dt_range=(1e-3, 0.5)):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (b, t, ch))
    bs, cs = jax.random.normal(ks[1], (b, t, n)), jax.random.normal(ks[2], (b, t, n))
    lo, hi = np.log(dt_range[0]), np.log(dt_range[1])
    dt = jnp.exp(jax.random.uniform(ks[3], (b, t, ch), minval=lo, maxval=hi))
    a = -jax.random.uniform(ks[4], (ch, n), minval=1.0, maxval=16.0)
    d = jax.random.uniform(ks[5], (ch,), minval=0.7, maxval=1.0)
    return (x, dt, a, bs, cs, d), jax.random.normal(ks[6], (b, t, ch))


def _rel(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _with_gradients(fn, args, cot):
    loss = lambda *m: jnp.sum(fn(*m) * cot)
    return (fn(*args),) + jax.grad(loss, argnums=range(6))(*args)


# one whole chunk; a part of one; two sequences, a chunk and a part; three
# chunks, the last ragged, 16 states over two blocks of channels
@pytest.mark.parametrize("batch,length,channels,n", [
    (1, 128, 1024, 8), (2, 50, 1024, 8), (2, 200, 1024, 16), (1, 300, 2048, 16)])
def test_the_kernel_pair_is_the_recurrence(on_the_kernel, batch, length, channels, n):
    args, cot = _inputs(length, batch, length, channels, n)
    assert "pallas_call" in str(jax.make_jaxpr(selective_scan_chunked)(*args))
    got = _with_gradients(selective_scan_chunked, args, cot)
    want = _with_gradients(selective_scan_recurrent, args, cot)
    assert got[0].dtype == jnp.float32
    for name, x, y in zip(NAMES, got, want):
        assert x.shape == y.shape and x.dtype == y.dtype, name
        assert _rel(x, y) < 1e-5, name


def test_a_bfloat16_x_is_widened_in_the_kernel_and_its_gradient_comes_back_bfloat16(on_the_kernel):
    args, cot = _inputs(7, 2, 150)
    narrow = (args[0].astype(jnp.bfloat16),) + args[1:]
    got = _with_gradients(selective_scan_chunked, narrow, cot)
    widened = (narrow[0].astype(jnp.float32),) + args[1:]
    want = _with_gradients(selective_scan_recurrent, widened, cot)
    assert got[0].dtype == jnp.float32 and got[1].dtype == jnp.bfloat16
    for name, x, y in zip(NAMES, got, want):
        # dx is rounded once on its way out; everything else is float32 throughout
        assert _rel(x, y) < (4e-3 if name == "dx" else 1e-5), name


@pytest.mark.parametrize(
    "case", ["carried_across_chunks", "forgotten_in_a_token", "a_flat_sequence"])
def test_at_the_ends_of_the_ranges_the_kernel_pair_is_the_recurrence(on_the_kernel, case):
    """The probe's extreme channels, here the whole block: ``dt`` 1e-3 with A 1
    (a chunk keeps 88 % of its state: the carry is everything), ``dt`` 8 with
    A 16 (exponents of -128: forgotten within a token), tokens that are one
    value but for 5 %."""
    (x, dt, a, bs, cs, d), cot = _inputs(11, 2, 260)
    if case == "carried_across_chunks":
        dt, a = jnp.full_like(dt, 1e-3), jnp.full_like(a, -1.0)
    elif case == "forgotten_in_a_token":
        dt, a = jnp.full_like(dt, 8.0), jnp.full_like(a, -16.0)
    else:
        x = 1.0 + 0.05 * x
    args = (x, dt, a, bs, cs, d)
    got = _with_gradients(selective_scan_chunked, args, cot)
    want = _with_gradients(selective_scan_recurrent, args, cot)
    for name, u, v in zip(NAMES, got, want):
        assert bool(jnp.isfinite(u).all()), name
        if case == "forgotten_in_a_token" and name == "da":
            # every term carries exp(-128): zero on both sides
            assert float(jnp.abs(u).max()) < 1e-30 and float(jnp.abs(v).max()) < 1e-30
        else:
            assert _rel(u, v) < 1e-5, name


@pytest.mark.parametrize("length", [100, 130])
def test_rows_past_the_sequence_s_end_are_read_as_neutral_and_leave_zeros(length):
    """The kernels are handed x, dt and dy as they are: the last chunk's rows
    past T are in no array (the interpreter fills them with NaN, the chip with
    what the buffer held).  Every result is finite, the sums over channels
    that the backward writes for those rows are zero, and results and
    gradients are those of the inputs padded to whole chunks with neutral
    rows (``dt`` = 0)."""
    args, cot = _inputs(length, 2, length)
    pad = -length % kernel.CHUNK
    rows = lambda m: jnp.pad(m, ((0, 0), (0, pad), (0, 0))) if m.ndim == 3 else m
    got = _with_gradients(kernel.selective_scan, args, cot)
    whole = _with_gradients(kernel.selective_scan, tuple(map(rows, args)), rows(cot))
    for name, u, v in zip(NAMES, got, whole):
        assert bool(jnp.isfinite(u).all()), name
        assert _rel(u, v[:, :length] if v.ndim == 3 else v) < 1e-6, name
    # the kernels themselves, on the layouts ``selective_scan`` hands them
    b, t, _ = args[0].shape
    n, chunks = args[2].shape[1], -(-t // kernel.CHUNK)
    operands = kernel._operands(*args)
    y, kept = kernel._call(False, True, *operands)
    dx, ddt, da, dd, dbp, dcp = kernel._call(
        True, True, *operands, cot.reshape(operands[0].shape), kept)
    for m in (y, kept, dx, ddt, da, dd, dbp, dcp):
        assert bool(jnp.isfinite(m).all())
    assert dbp.shape == (b, chunks * kernel.CHUNK, n, 128)
    assert float(jnp.abs(dbp[:, t:]).max()) == 0.0 and float(jnp.abs(dcp[:, t:]).max()) == 0.0


def test_no_state_leaks_from_one_image_into_the_next_on_the_kernel_path(on_the_kernel):
    (a1, _), (a2, _) = _inputs(21, 1, 140), _inputs(22, 1, 140)
    shared = a1[2], a1[5]
    both = tuple(jnp.concatenate([u, v]) for u, v in zip(a1, a2))
    out = selective_scan_chunked(both[0], both[1], shared[0], both[3], both[4], shared[1])
    for i, alone in enumerate((a1, a2)):
        want = selective_scan_chunked(alone[0], alone[1], shared[0], alone[3], alone[4], shared[1])
        assert float(jnp.abs(out[i:i + 1] - want).max()) < 1e-6


# What the chunked XLA form lowered to at the parent of the PR that brought the
# kernel pair (PR 35), value and six gradients, by the digest of the StableHLO
# text: (B, T, C, N, chunk) -> sha256[:16].  A PR that means to change the XLA
# form prints the new ones with the expression in the test.
PARENTS_LOWERING = {
    (2, 300, 1024, 16, 128): "b069aaede04dfe20",      # a shape the kernel takes on a TPU
    (1, 50, 24, 4, 16): "7ccee7488068e1e9",           # one it does not take anywhere
}


@pytest.mark.parametrize("shape", list(PARENTS_LOWERING))
def test_off_the_tpu_the_chunked_form_lowers_to_what_it_lowered_to_before(shape):
    b, t, ch, n, chunk = shape
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    args = (jax.ShapeDtypeStruct((b, t, ch), jnp.bfloat16), f32(b, t, ch), f32(ch, n),
            f32(b, t, n), f32(b, t, n), f32(ch))
    loss = lambda *m: jnp.sum(selective_scan_chunked(*m, chunk=chunk))
    assert jax.default_backend() == "cpu"
    text = jax.jit(jax.value_and_grad(loss, argnums=range(6))).lower(*args).as_text()
    assert "pallas" not in text and "tpu_custom_call" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENTS_LOWERING[shape]


@pytest.mark.parametrize("t,channels,n,chunk,taken", [
    (4200, 5120, 16, 128, True),        # the SambaY cell's
    (1, 1024, 8, 128, True),
    (4200, 5120, 16, 64, False),        # another chunk is the XLA form's to choose
    (4200, 5000, 16, 128, False),       # not whole blocks of channels
    (4200, 5120, 4, 128, False),        # states not in groups of eight
    (4200, 5120, 32, 128, False),       # a chunk's states past the VMEM kept for them
    (50, 24, 4, 16, False),             # the tiny configurations of the CPU tests
])
def test_the_kernel_is_taken_by_platform_and_shape_alone(t, channels, n, chunk, taken, monkeypatch):
    assert kernel.supported(t, channels, n, chunk) is taken
    assert not scan._takes_kernel(t, channels, n, chunk)          # never on the CPU
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    short = min(t, 130)
    args = (f32(1, short, channels), f32(1, short, channels), f32(channels, n), f32(1, short, n),
            f32(1, short, n), f32(channels))
    traced = lambda: str(jax.make_jaxpr(lambda *m: selective_scan_chunked(*m, chunk=chunk))(*args))
    assert "pallas_call" not in traced()
    monkeypatch.setattr(scan, "_takes_kernel", kernel.supported)   # as if on a TPU
    assert ("pallas_call" in traced()) is taken


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
def test_mosaic_compiles_both_kernels_at_the_sambay_cell_s_shape(one_chip, dtype):
    """x ``[2, 4200, 5120]``: 33 chunks, the last of 104 positions, five blocks
    of channels.  Compiled for a described v5e, run nowhere."""
    b, t, ch, n = 2, 4200, 5120, 16
    chunks, blocks = -(-t // kernel.CHUNK), ch // kernel.BLOCK
    spec = lambda shape, kind=jnp.float32: jax.ShapeDtypeStruct(shape, kind, sharding=one_chip)
    args = [spec((b, t, ch), dtype), spec((b, t, ch)),
            spec((blocks, n, 8, 128)), spec((blocks, 8, 128)),
            spec((b, chunks, 1, kernel.CHUNK * n)), spec((b, chunks, 1, kernel.CHUNK * n))]

    def loss(*m):
        y = kernel._scan(*m, False)
        return jnp.sum(y), y

    compiled = jax.jit(jax.value_and_grad(loss, argnums=range(6), has_aux=True)).lower(
        *args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2   # forward + backward
