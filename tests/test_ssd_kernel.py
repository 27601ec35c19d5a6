"""The Mamba-2 chunked scan as the Pallas kernel pair (ops/pallas/ssd.py),
interpreted on the CPU at the kernel's own widths (heads of 64, states of 128,
chunks of 128, one or two groups of eight heads, one group of 64 heads as the
granite cell has it): result and all six
gradients against the recurrence token by token (ops/ssd.py::ssd_recurrent),
which is also the oracle of the chunked XLA form that stays the path of every
other platform and shape.  What Mosaic makes of the kernels is compiled here
for a described chip and run in tests/_kernels_tpu_worker.py on a real one."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.ops import ssd as scan
from mx_rcnn_tpu.ops.pallas import ssd as kernel
from mx_rcnn_tpu.ops.ssd import ssd_chunked, ssd_recurrent

NAMES = ("y", "dx", "ddt", "da", "db", "dc", "dd")
P, N = kernel.HEAD_DIM, kernel.LANES


@pytest.fixture
def on_the_kernel(monkeypatch):
    """``ssd_chunked`` takes the kernel wherever the shapes are the kernel's,
    as it does on the TPU; off the TPU the kernel runs interpreted."""
    monkeypatch.setattr(scan, "_takes_kernel", kernel.supported)


def _inputs(seed, b, t, h=8, g=1, dt_range=(1e-3, 0.1)):
    """The published ranges: ``dt`` log-uniform in time_step_min..max, A in 1..16."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (b, t, h, P))
    bs, cs = jax.random.normal(ks[1], (b, t, g, N)), jax.random.normal(ks[2], (b, t, g, N))
    lo, hi = np.log(dt_range[0]), np.log(dt_range[1])
    dt = jnp.exp(jax.random.uniform(ks[3], (b, t, h), minval=lo, maxval=hi))
    a = -jax.random.uniform(ks[4], (h,), minval=1.0, maxval=16.0)
    d = jax.random.uniform(ks[5], (h,), minval=0.7, maxval=1.0)
    return (x, dt, a, bs, cs, d), jax.random.normal(ks[6], (b, t, h, P))


def _rel(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _with_gradients(fn, args, cot):
    loss = lambda *m: jnp.sum(fn(*m) * cot)
    return (fn(*args),) + jax.grad(loss, argnums=range(6))(*args)


def _f32(*m):
    return ssd_chunked(*m, dtype=jnp.float32)


def _xla_form(dtype):
    """The chunked XLA form whatever ``_takes_kernel`` says."""
    def form(*m):
        takes, scan._takes_kernel = scan._takes_kernel, lambda *_: False
        try:
            return ssd_chunked(*m, dtype=dtype)
        finally:
            scan._takes_kernel = takes
    return form


# one whole chunk; a part of one; two sequences, a chunk and a part, two
# groups; three chunks, the last ragged; one group of 64 heads (B and C shared
# by all of them), two chunks, the last ragged
@pytest.mark.parametrize("batch,length,heads,groups", [
    (1, 128, 8, 1), (2, 100, 8, 1), (2, 200, 16, 2), (1, 300, 16, 2), (1, 200, 64, 1)])
def test_the_kernel_pair_is_the_recurrence(on_the_kernel, batch, length, heads, groups):
    args, cot = _inputs(length, batch, length, heads, groups)
    assert "pallas_call" in str(jax.make_jaxpr(_f32)(*args))
    got = _with_gradients(_f32, args, cot)
    want = _with_gradients(ssd_recurrent, args, cot)
    assert got[0].dtype == jnp.float32
    for name, x, y in zip(NAMES, got, want):
        assert x.shape == y.shape and x.dtype == y.dtype, name
        assert _rel(x, y) < 1e-5, name


def test_bfloat16_operands_read_no_farther_from_the_recurrence_than_the_xla_form(on_the_kernel):
    """The cell's arithmetic: x, B and C handed over in bfloat16, every matmul's
    operands bfloat16.  Each of the seven readings is within 1.25 x the XLA
    form's, and dx, dB, dC come back in their arguments' type."""
    (x, dt, a, bs, cs, d), cot = _inputs(5, 2, 300, 16, 2)
    narrow = (x.astype(jnp.bfloat16), dt, a, bs.astype(jnp.bfloat16), cs.astype(jnp.bfloat16), d)
    widened = tuple(m.astype(jnp.float32) for m in narrow)
    want = _with_gradients(ssd_recurrent, widened, cot)
    got = _with_gradients(lambda *m: ssd_chunked(*m, dtype=jnp.bfloat16), narrow, cot)
    xla = _with_gradients(_xla_form(jnp.bfloat16), narrow, cot)
    assert [m.dtype for m in got] == [m.dtype for m in xla]
    for name, u, v, w in zip(NAMES, got, xla, want):
        assert _rel(u, w) <= 1.25 * _rel(v, w) + 1e-6, name


@pytest.mark.parametrize(
    "case", ["carried_across_chunks", "forgotten_in_a_few_tokens", "tokens_alike"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_at_the_ends_of_the_ranges_the_kernel_pair_is_the_recurrence(on_the_kernel, case, dtype):
    """The probe's extreme heads, here every head: ``dt`` 1e-3 with A 1 (a chunk
    keeps 88 % of its state: the carry is everything), ``dt`` 0.1 with A 16
    (forgets within a few tokens), tokens that are one vector but for 5 % (a
    flat image's: what cancelled under autodiff of the XLA form must still
    cancel in the hand-written backward, PERF.md 7 w).  Each reading within
    1.25 x the XLA form's distance to the recurrence, and in float32 (sums in
    another order) within 2e-5 at the least: where tokens look alike dA is
    what is left of a cancellation and reads 1.1e-5 here, the XLA form 3.8e-6;
    with bfloat16 operands 3.0e-2 against 2.8e-2."""
    (x, dt, a, bs, cs, d), cot = _inputs(11, 2, 260, 8, 1)
    if case == "carried_across_chunks":
        dt, a = jnp.full_like(dt, 1e-3), jnp.full_like(a, -1.0)
    elif case == "forgotten_in_a_few_tokens":
        dt, a = jnp.full_like(dt, 0.1), jnp.full_like(a, -16.0)
    else:
        noise = jax.random.normal(jax.random.PRNGKey(12), x.shape)
        x = jnp.broadcast_to(x[:, :1], x.shape) + 0.05 * noise
    args = (x, dt, a, bs, cs, d)
    want = _with_gradients(ssd_recurrent, args, cot)
    got = _with_gradients(lambda *m: ssd_chunked(*m, dtype=dtype), args, cot)
    xla = _with_gradients(_xla_form(dtype), args, cot)
    floor = 2e-5 if dtype == jnp.float32 else 1e-6
    for name, u, v, w in zip(NAMES, got, xla, want):
        assert bool(jnp.isfinite(u).all()), name
        assert _rel(u, w) <= max(floor, 1.25 * _rel(v, w)), name


@pytest.mark.parametrize("length", [1, 100, 127, 128, 129, 256, 257])
def test_however_the_sequence_ends_against_the_chunks(length):
    """The kernels are handed x, B, C, dt and dy as they are: the last chunk's
    rows past T are in no array (the interpreter fills them with NaN, the chip
    with what the buffer held).  Every result is finite, and results and
    gradients are those of the inputs padded to whole chunks with neutral rows
    (``dt`` = 0, the rest zeros)."""
    args, cot = _inputs(length, 2, length, 8, 1)
    pad = -length % kernel.CHUNK
    rows = lambda m: jnp.pad(m, ((0, 0), (0, pad)) + ((0, 0),) * (m.ndim - 2)) if m.ndim > 1 else m
    fn = lambda *m: kernel.ssd(*m, dtype=jnp.float32)
    got = _with_gradients(fn, args, cot)
    whole = _with_gradients(fn, tuple(map(rows, args)), rows(cot))
    for name, u, v in zip(NAMES, got, whole):
        v = v[:, :length] if v.ndim > 1 else v
        assert bool(jnp.isfinite(u).all()), name
        # (at one position dA is nothing on both sides)
        assert float(jnp.linalg.norm(u - v)) <= 1e-6 * float(jnp.linalg.norm(v)), name


def test_no_state_leaks_from_one_image_into_the_next_on_the_kernel_path(on_the_kernel):
    (a1, c1), (a2, c2) = _inputs(21, 1, 140), _inputs(22, 1, 140)
    shared = a1[2], a1[5]
    both = tuple(jnp.concatenate([u, v]) for u, v in zip(a1, a2))
    pick = lambda m: (m[0], m[1], shared[0], m[3], m[4], shared[1])
    out = _with_gradients(_f32, pick(both), jnp.concatenate([c1, c2]))
    for i, (alone, cot) in enumerate(((a1, c1), (a2, c2))):
        want = _with_gradients(_f32, pick(alone), cot)
        for name, u, v in zip(NAMES, out, want):
            if name in ("da", "dd"):        # summed over the images
                continue
            assert float(jnp.abs(u[i:i + 1] - v).max()) < 1e-5, name


# What the chunked XLA form lowered to at the parent of the PR that brought the
# kernel pair (PR 37), value and six gradients, by the digest of the StableHLO
# text: (B, T, H, P, G, N, chunk, dtype) -> sha256[:16].  A PR that means to
# change the XLA form prints the new ones with the expression in the test.
PARENTS_LOWERING = {
    (2, 300, 16, 64, 2, 128, 128, "bfloat16"): "a2068d6c8e9ef20d",   # a shape the kernel takes on a TPU
    (1, 50, 4, 8, 2, 16, 16, "float32"): "565a35da7f50b77a",        # one it does not take anywhere
}


@pytest.mark.parametrize("shape", list(PARENTS_LOWERING))
def test_off_the_tpu_the_chunked_form_lowers_to_what_it_lowered_to_before(shape):
    b, t, h, p, g, n, chunk, dtype = shape
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    bf16 = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
    args = (bf16(b, t, h, p), f32(b, t, h), f32(h), bf16(b, t, g, n), bf16(b, t, g, n), f32(h))
    loss = lambda *m: jnp.sum(ssd_chunked(*m, chunk=chunk, dtype=jnp.dtype(dtype)))
    assert jax.default_backend() == "cpu"
    text = jax.jit(jax.value_and_grad(loss, argnums=range(6))).lower(*args).as_text()
    assert "pallas" not in text and "tpu_custom_call" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENTS_LOWERING[shape]


@pytest.mark.parametrize("t,heads,head_dim,groups,state,chunk,taken", [
    (4200, 64, 64, 8, 128, 128, True),      # the state-space cell's
    (4200, 64, 64, 1, 128, 128, True),      # the granite cell's: one group of 64 heads
    (1, 8, 64, 1, 128, 128, True),
    (4200, 64, 64, 8, 128, 64, False),      # another chunk is the XLA form's to choose
    (4200, 64, 128, 8, 128, 128, False),    # heads of another width
    (4200, 64, 64, 8, 64, 128, False),      # states of another width
    (4200, 48, 64, 8, 128, 128, False),     # groups of six heads
    (4200, 64, 64, 16, 128, 128, False),    # groups of four heads
    (50, 4, 8, 2, 16, 16, False),           # the tiny configurations of the CPU tests
])
def test_the_kernel_is_taken_by_platform_and_shape_alone(
        t, heads, head_dim, groups, state, chunk, taken, monkeypatch):
    assert kernel.supported(t, heads, head_dim, groups, state, chunk) is taken
    assert not scan._takes_kernel(t, heads, head_dim, groups, state, chunk)   # never on the CPU
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    short = min(t, 130)
    args = (f32(1, short, heads, head_dim), f32(1, short, heads), f32(heads),
            f32(1, short, groups, state), f32(1, short, groups, state), f32(heads))
    traced = lambda: str(jax.make_jaxpr(lambda *m: ssd_chunked(*m, chunk=chunk))(*args))
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


def _compiles_both_kernels(one_chip, dtype, g):
    b, t, h = 2, 4200, 64
    spec = lambda shape, kind=jnp.float32: jax.ShapeDtypeStruct(shape, kind, sharding=one_chip)
    args = [spec((b, t, h * P), dtype), spec((b, t, h)), spec((b, t, g * N), dtype),
            spec((b, t, g * N), dtype), spec((1, h)), spec((h,))]

    def loss(*m):
        y = kernel._ssd(*m, jnp.dtype(dtype), False)
        return jnp.sum(y), y

    compiled = jax.jit(jax.value_and_grad(loss, argnums=range(6), has_aux=True)).lower(
        *args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2   # forward + backward


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_mosaic_compiles_both_kernels_at_the_state_space_cell_s_shape(one_chip, dtype):
    """x ``[2, 4200, 64 x 64]``, B and C ``[2, 4200, 8 x 128]``: 33 chunks, the
    last of 104 positions, eight groups of eight heads.  Compiled for a
    described v5e, run nowhere."""
    _compiles_both_kernels(one_chip, dtype, 8)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_mosaic_compiles_both_kernels_at_one_group_of_64_heads(one_chip, dtype):
    """The granite cell's shape: B and C ``[2, 4200, 1 x 128]`` read by all 64
    heads, the gated norm's one group of 4,096 outside the kernel."""
    _compiles_both_kernels(one_chip, dtype, 1)


# the step's paths as the chip's trace carries them (tests/perfbench/test_step_parts.py's FWD, BWD, REMAT)
_STEP = "jit(step)/"
_FWD = _STEP + "jvp(TwoStageDetector.features)/backbone/"
_BWD = (_STEP + "transpose(jvp(TwoStageDetector.features))/backbone/"
        "jvp(TwoStageDetector.features)/backbone/checkpoint/")
_REMAT = _BWD + "rematted_computation/"


@pytest.mark.parametrize("path, pass_", [
    (_FWD + "l0/ssm/scan/jit(_call)/ssd_fwd/pallas_call", "fwd"),
    (_REMAT + "l2/ssm/scan/jit(_call)/ssd_fwd/pallas_call", "remat"),
    (_BWD + "l2/ssm/scan/jit(_call)/ssd_bwd/pallas_call", "bwd"),
])
def test_the_trace_reader_puts_the_kernels_in_their_pass_and_layer(path, pass_):
    """perfbench/step_parts.py reads the pair as the state-space layer's, in
    all three passes, with no reader change."""
    from perfbench import step_parts
    assert step_parts.pass_of(path) == pass_
    assert step_parts.place(path) == (path.split("/ssm/")[0].rsplit("/", 1)[1], "ssm")
