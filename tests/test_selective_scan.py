"""The selective scan (ops/selective_scan.py): the plain chunked path
against the recurrence walked a position at a time, forward and in
every gradient; the Pallas kernels (ops/pallas/selective_scan.py)
against the plain path in interpret mode, ``A``'s, ``B``'s and ``C``'s
sums across grid steps among the gradients; a decay that underflows;
the shapes that take the plain path; and what neither path may hold: a
clip, a floor, a division by a decay."""

import inspect
import re

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.ops import selective_scan as entry
from dlrover_tpu.ops.pallas import selective_scan as kernels
from dlrover_tpu.telemetry.registry import counter

NAMES = ("x", "Delta", "B", "C", "A", "D")


def recurrence(x, delta, B, C, A, D):
    """``h_t = exp(Delta_t A) h_{t-1} + Delta_t x_t B_t``, ``o_t = h_t
    C_t + D x_t``, a position at a time, every state kept: x, delta
    [b, s, d]; B, C [b, s, n]; A [d, n]; D [d]."""
    def step(h, at):
        x_t, delta_t, b_t, c_t = at
        h = jnp.exp(delta_t[..., None] * A) * h + (
            delta_t * x_t)[..., None] * b_t[:, None]
        return h, jnp.einsum("bdn,bn->bd", h, c_t) + D * x_t

    _, o = jax.lax.scan(
        step, jnp.zeros((x.shape[0], *A.shape)),
        tuple(jnp.moveaxis(a, 1, 0) for a in (x, delta, B, C)))
    return jnp.moveaxis(o, 0, 1)


def operands(seed=1, b=2, s=128, d=256, n=16, rate=1.0, dtype=jnp.float32):
    """Operands of their own. ``rate`` scales the log decay ``Delta
    A`` (about -1 a step at 1)."""
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (b, s, d)).astype(dtype)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (b, s, d))) * rate
    B = (jax.random.normal(ks[2], (b, s, n)) * n ** -0.5).astype(dtype)
    C = jax.random.normal(ks[3], (b, s, n)).astype(dtype)
    A = -jnp.exp(jax.random.uniform(ks[4], (d, n), minval=-1.0, maxval=2.7))
    D = jax.random.normal(ks[5], (d,))
    return x, delta, B, C, A, D


def close(got, want, rel, what):
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= rel * max(scale, 1e-6), (
        what, float(jnp.abs(got - want).max()), scale)


def gradients(f, ops, weights):
    return jax.grad(
        lambda *o: jnp.sum(f(*o).astype(jnp.float32) * weights),
        argnums=range(6))(*ops)


@pytest.mark.parametrize("seq,chunk", [(96, 32), (100, 32), (40, 64)])
def test_plain_path_is_the_recurrence(seq, chunk):
    """Whole chunks, a padded last chunk, a sequence under one."""
    ops = operands(s=seq, d=24, n=8)
    want = recurrence(*ops)
    got = entry.selective_scan_plain(*ops, chunk=chunk)
    close(got, want, 1e-5, "o")
    weights = jax.random.normal(jax.random.key(5), want.shape)
    for name, g, w in zip(NAMES, gradients(
            lambda *o: entry.selective_scan_plain(*o, chunk=chunk),
            ops, weights), gradients(recurrence, ops, weights)):
        close(g, w, 2e-5, name)


@pytest.mark.parametrize("d,rate,seq", [
    (256, 1.0, 128), (640, 0.05, 128), (1024, 30.0, 128),
    (2048, 0.3, 192),
])
def test_kernels_are_the_plain_path(d, rate, seq):
    """Interpret mode: a tile of 256 lanes, five of 128 (what 5,120
    channels are to 1,024), one of 1,024 and two of 1,024; two chunks a
    sequence and three, two sequences; a slow, a usual and a fast
    decay. Forward, the forward that keeps the entry states, and every
    gradient: ``B``'s and ``C``'s are sums over a chunk's channel tiles
    in the backward kernel's scratch, then over the lanes once a
    chunk."""
    ops = operands(d=d, rate=rate, s=seq)
    assert kernels.tiles_the_kernel(ops[0].shape, ops[2].shape)
    want = entry.selective_scan_plain(*ops)
    close(kernels.selective_scan_tpu(*ops), want, 1e-5, "o")
    o, states = kernels.selective_scan(*ops, keep_states=True)
    close(o, want, 1e-5, "o beside the states")
    assert states.shape == (2, seq // entry.CHUNK, 16, d)
    assert not states[:, 0].any() and states[:, 1].any()
    weights = jax.random.normal(jax.random.key(5), want.shape)
    for name, g, w in zip(NAMES, gradients(
            kernels.selective_scan_tpu, ops, weights), gradients(
                entry.selective_scan_plain, ops, weights)):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        close(g, w, 2e-5, name)


def test_kernels_take_bfloat16_rows_and_round_once():
    ops = operands(dtype=jnp.bfloat16)
    got = kernels.selective_scan_tpu(*ops)
    assert got.dtype == jnp.bfloat16
    want = entry.selective_scan_plain(*ops)
    assert float(jnp.abs(
        got.astype(jnp.float32) - want.astype(jnp.float32)
    ).max()) <= 2 ** -7 * float(jnp.abs(want.astype(jnp.float32)).max())
    weights = jax.random.normal(jax.random.key(5), want.shape)
    for name, g, w in zip(NAMES, gradients(
            kernels.selective_scan_tpu, ops, weights), gradients(
                entry.selective_scan_plain, ops, weights)):
        assert g.dtype == w.dtype, name
        close(g.astype(jnp.float32), w.astype(jnp.float32), 2e-2, name)


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_a_decay_that_underflows_is_exact(path):
    """``Delta A`` under -104: ``exp`` gives zero, the state forgets
    and keeps the position's own write, ``o_t = Delta_t x_t (B_t . C_t)
    + D x_t``; every gradient is finite and ``A``'s is zero."""
    x, delta, B, C, A, D = operands()
    A = jnp.full_like(A, -400.0)
    delta = jnp.maximum(delta, 0.5)
    f = (entry.selective_scan_plain if path == "plain"
         else kernels.selective_scan_tpu)
    got = f(x, delta, B, C, A, D)
    want = delta * x * jnp.sum(B * C, axis=-1, keepdims=True) + D * x
    close(got, want, 1e-6, "o")
    grads = gradients(f, (x, delta, B, C, A, D), jnp.ones_like(got))
    for name, g in zip(NAMES, grads):
        assert bool(jnp.isfinite(g).all()), name
    assert not grads[4].any()


def test_the_entry_counts_its_path_and_checks_its_shapes():
    plain = counter("selective_scan_plain_calls", "")
    kernel = counter("selective_scan_kernel_calls", "")
    before = plain.value, kernel.value
    ops = operands(s=32, d=24, n=8)
    close(entry.selective_scan(*ops), recurrence(*ops), 1e-5, "o")
    assert (plain.value, kernel.value) == (before[0] + 1, before[1])
    x, delta, B, C, A, D = ops
    for bad in ((x, delta[:, :16], B, C, A, D), (x, delta, B, C[..., :4], A, D),
                (x, delta, B, C, A.T, D), (x, delta, B, C, A, D[:8])):
        with pytest.raises(ValueError, match="selective_scan"):
            entry.selective_scan(*bad)


def test_on_the_tpu_the_entry_takes_the_kernels(monkeypatch):
    monkeypatch.setattr(entry, "_use_pallas", lambda x, B: True)
    kernel = counter("selective_scan_kernel_calls", "")
    before = kernel.value
    ops = operands()
    close(entry.selective_scan(*ops), entry.selective_scan_plain(*ops),
          1e-5, "o")
    assert kernel.value == before + 1


@pytest.mark.parametrize("x_shape,b_shape,tiles", [
    ((1, 8192, 5120), (1, 8192, 16), True),   # the cell's
    ((2, 128, 128), (2, 128, 8), True),
    ((1, 8192, 5000), (1, 8192, 16), False),  # channels off the lanes
    ((1, 8192, 5120), (1, 8192, 12), False),  # states off the sublanes
    ((1, 8160, 5120), (1, 8160, 16), False),  # no whole chunks
    ((2, 128, 192), (2, 128, 16), False),     # tiny-jamba's
])
def test_the_shapes_alone_decide_the_path(x_shape, b_shape, tiles):
    assert kernels.tiles_the_kernel(x_shape, b_shape) is tiles
    assert list(inspect.signature(kernels.tiles_the_kernel).parameters) == [
        "x_shape", "b_shape"]
    assert kernels._lanes(5120) == 1024


def test_neither_path_clips_floors_or_divides():
    for module in (entry, kernels):
        code = re.sub(r'""".*?"""', "", inspect.getsource(module), flags=re.S)
        code = re.sub(r"#.*", "", code)
        assert not re.search(
            r"clip|minimum|maximum|reciprocal|log\(| / ", code
        ), module.__name__
    assert "os.environ" not in inspect.getsource(entry)
    assert "os.environ" not in inspect.getsource(kernels)
