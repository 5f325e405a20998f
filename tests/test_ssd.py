"""The state-space scan (ops/ssd.py): the chunked dual against the
recurrence walked a position at a time, forward and in every gradient,
at the decays ISSUE 54 names; the Pallas kernels (ops/pallas/ssd.py)
against the plain path in interpret mode; and what neither path may
hold: a clip, a floor, a factored exponential."""

import inspect
import re

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.ops import ssd
from dlrover_tpu.ops.pallas import ssd as kernels
from dlrover_tpu.telemetry.registry import counter, gauge

NAMES = ("x", "B", "C", "Delta", "A", "D")


def recurrence(x, B, C, dt, A, D):
    """``S_t = a_t S_{t-1} + Delta_t x_t B_t^T``, ``o_t = S_t C_t + D
    x_t``, a position at a time: x [b, s, heads, p]; B, C [b, s,
    groups, n]; dt [b, s, heads]; A, D [heads]."""
    b, s, heads, p = x.shape
    per = heads // B.shape[2]
    Bh, Ch = (jnp.repeat(a, per, axis=2) for a in (B, C))

    def step(state, at):
        x_t, b_t, c_t, dt_t = at
        state = jnp.exp(A * dt_t)[..., None, None] * state + jnp.einsum(
            "bh,bhp,bhn->bhpn", dt_t, x_t, b_t)
        return state, jnp.einsum(
            "bhpn,bhn->bhp", state, c_t) + D[:, None] * x_t

    _, o = jax.lax.scan(
        step, jnp.zeros((b, heads, p, B.shape[-1])),
        tuple(jnp.moveaxis(a, 1, 0) for a in (x, Bh, Ch, dt)))
    return jnp.moveaxis(o, 0, 1)


def operands(seed=1, b=2, s=256, heads=4, groups=2, p=64, n=128, rate=1.0,
             step=None):
    """Operands of their own. ``rate`` scales the log decay ``A
    Delta`` (about -1 a step at 1); ``step`` makes it exactly that
    for every head and position."""
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (b, s, heads, p))
    B = jax.random.normal(ks[1], (b, s, groups, n)) * n ** -0.5
    C = jax.random.normal(ks[2], (b, s, groups, n))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, s, heads))) * rate
    A = -jnp.exp(jax.random.uniform(ks[4], (heads,), minval=0.0, maxval=2.7))
    D = jax.random.normal(ks[5], (heads,))
    if step is not None:
        dt, A = jnp.full_like(dt, 1.0), jnp.full_like(A, step)
    return x, B, C, dt, A, D


def rows(a):
    return a.reshape(*a.shape[:2], -1)


def through_kernels(x, B, C, dt, A, D):
    return kernels.ssd_tpu(
        rows(x), rows(B), rows(C), dt, A, D, B.shape[2]).reshape(x.shape)


def close(got, want, rel, what):
    """Within ``rel`` of the largest entry (of 1e-6 where the entries
    are smaller: at ``a = e^-30`` a step A's gradient is 1e-11)."""
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= rel * max(scale, 1e-6), (
        what, float(jnp.abs(got - want).max()), scale)


def gradients(f, ops, weights):
    return jax.grad(
        lambda *o: jnp.sum(f(*o) * weights), argnums=range(6))(*ops)


#: (operands' arguments, the chunk): slow and fast decays, a = e^-30 a
#: step, a chunk whose summed log decay passes -200, a sequence that
#: is no whole number of chunks, one head a group
CASES = {
    "slow": (dict(rate=0.05), 128),
    "a step about 1/e": (dict(), 128),
    "fast": (dict(rate=10.0), 128),
    "a = e^-30 a step": (dict(step=-30.0), 64),
    "a chunk's sum past -200": (dict(step=-2.0, s=384), 128),
    "no whole number of chunks": (dict(s=200), 64),
    "a group of one head": (dict(heads=2, groups=2), 32),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_chunked_dual_is_the_recurrence(case):
    """Forward and every gradient of ``ssd_plain`` against the
    recurrence: float32 noise, at any decay."""
    kw, chunk = CASES[case]
    ops = operands(**kw)
    if "past -200" in case:
        cum = jnp.cumsum((ops[3] * ops[4]).reshape(2, -1, 128, 4), axis=2)
        assert float(cum.min()) < -200
    want = recurrence(*ops)
    got = ssd.ssd_plain(*ops, chunk)
    assert bool(jnp.isfinite(got).all())
    close(got, want, 2e-5, "o")
    weights = jax.random.normal(jax.random.key(9), want.shape)
    for name, g, w in zip(
            NAMES, gradients(lambda *o: ssd.ssd_plain(*o, chunk), ops,
                             weights),
            gradients(recurrence, ops, weights)):
        assert bool(jnp.isfinite(g).all()), name
        close(g, w, 1e-3, name)


@pytest.mark.parametrize("case", [
    "a step about 1/e", "fast", "a = e^-30 a step",
    "a chunk's sum past -200"])
def test_the_kernels_are_the_plain_path(case):
    """The Pallas kernels in interpret mode, float32 operands, forward
    and in every gradient (the backward kernel over the entry states
    the forward kept), two heads to a lane tile."""
    kw, _ = CASES[case]
    ops = operands(**{"b": 1, **kw})
    want = ssd.ssd_plain(*ops)
    got = through_kernels(*ops)
    assert got.dtype == want.dtype
    close(got, want, 2e-5, "o")
    weights = jax.random.normal(jax.random.key(9), want.shape)
    for name, g, w in zip(
            NAMES, gradients(through_kernels, ops, weights),
            gradients(ssd.ssd_plain, ops, weights)):
        assert bool(jnp.isfinite(g).all()), name
        close(g, w, 1e-3, name)


@pytest.mark.parametrize("p", [128, 32])
def test_the_kernels_take_one_and_four_heads_a_lane_tile(p):
    ops = operands(heads=8, groups=2, p=p, s=128, b=1)
    close(through_kernels(*ops), ssd.ssd_plain(*ops), 2e-5, "o")
    weights = jax.random.normal(jax.random.key(9), ops[0].shape)
    for name, g, w in zip(
            NAMES, gradients(through_kernels, ops, weights),
            gradients(ssd.ssd_plain, ops, weights)):
        close(g, w, 1e-3, name)


def test_the_kernels_take_a_lightning_layers_regime():
    """One head a group, 128 values and 128 states a head, a step of
    one, a constant rate a head and no skip: a lightning layer's call
    (``models/llama.py``), through the kernels against the recurrence
    walked a position at a time, forward and in q's, k's and v's
    gradients."""
    heads = 4
    assert kernels.tiles_the_kernel(
        (1, 16384, 32 * 128), (1, 16384, 32 * 128), 32, 32)
    x, B, C, _, _, _ = operands(heads=heads, groups=heads, p=128, b=1)
    dt = jnp.ones(x.shape[:3])
    A = -jnp.exp2(-8.0 * jnp.arange(1, heads + 1) / heads)
    ops = (x, B, C * 128 ** -0.5, dt, A, jnp.zeros((heads,)))
    want = recurrence(*ops)
    close(through_kernels(*ops), want, 2e-5, "o")
    weights = jax.random.normal(jax.random.key(9), x.shape)
    for name, g, w in list(zip(
            NAMES, gradients(through_kernels, ops, weights),
            gradients(recurrence, ops, weights)))[:3]:
        close(g, w, 1e-3, name)
    assert gauge("ssd_heads_per_step", "").value == 1
    assert gauge("ssd_state_bytes", "").value == 128 * 128 * 4


def test_the_kernels_in_bfloat16_round_once():
    """bfloat16 rows in, bfloat16 rows out, float32 inside: within
    bfloat16's own resolution of the float32 result."""
    x, B, C, dt, A, D = operands()
    low = tuple(a.astype(jnp.bfloat16) for a in (x, B, C))
    want = ssd.ssd_plain(*(a.astype(jnp.float32) for a in low), dt, A, D)
    got = through_kernels(*low, dt, A, D)
    assert got.dtype == jnp.bfloat16
    close(got.astype(jnp.float32), want, 2e-2, "o")
    dx, db, dc, ddt, da, dd = gradients(
        lambda *o: through_kernels(*o).astype(jnp.float32),
        (*low, dt, A, D), jnp.ones(x.shape))
    assert (dx.dtype, db.dtype, dc.dtype) == (jnp.bfloat16,) * 3
    assert (ddt.dtype, da.dtype, dd.dtype) == (jnp.float32,) * 3


def test_the_entry_takes_rows_and_counts_its_path():
    x, B, C, dt, A, D = operands(s=128)
    calls = [counter(f"ssd_{path}_calls", "") for path in ("plain", "kernel")]
    before = [c.value for c in calls]
    got = ssd.ssd_scan(rows(x), rows(B), rows(C), dt, A, D, 4, 2)
    assert [c.value - was for c, was in zip(calls, before)] == [1, 0]
    close(got.reshape(x.shape), recurrence(x, B, C, dt, A, D), 2e-5, "o")
    # the chunk changes nothing
    other = ssd.ssd_scan(rows(x), rows(B), rows(C), dt, A, D, 4, 2, chunk=32)
    close(other, got, 2e-5, "chunk")
    with pytest.raises(ValueError, match="ssd_scan"):
        ssd.ssd_scan(rows(x), rows(B), rows(C), dt[..., :3], A, D, 4, 2)
    with pytest.raises(ValueError, match="ssd_scan"):
        ssd.ssd_scan(rows(x), rows(B), rows(C), dt, A, D, 4, 3)


def test_which_shapes_the_kernels_take_and_what_they_record():
    cell = ((1, 8192, 8192), (1, 8192, 1024), 128, 8)
    assert kernels.tiles_the_kernel(*cell)
    assert kernels.heads_a_step(128, 8) == 16
    # half a lane tile a group, a sequence that is no whole chunks,
    # states that are no whole tile
    assert not kernels.tiles_the_kernel((1, 256, 64), (1, 256, 128), 1, 1)
    assert not kernels.tiles_the_kernel((1, 200, 256), (1, 200, 256), 4, 2)
    assert not kernels.tiles_the_kernel((1, 256, 256), (1, 256, 32), 4, 2)
    through_kernels(*operands(s=128, b=1))
    assert gauge("ssd_chunk", "").value == ssd.CHUNK == 128
    assert gauge("ssd_heads_per_step", "").value == 2
    assert gauge("ssd_state_bytes", "").value == 2 * 64 * 128 * 4
    assert gauge("ssd_backward_kernels", "").value == 1
    # the cell's: sixteen heads' [64, 128] float32 states a grid step
    kernels._record(128, 8, 64, 128)
    assert gauge("ssd_state_bytes", "").value == 512 * 1024
    assert gauge("ssd_heads_per_step", "").value == 16


def test_neither_path_clips_floors_or_factors_a_decay():
    """A decay between two positions is ``exp`` of a masked
    difference: no ``clip``, ``minimum``, ``maximum`` or floor in
    either file, and every ``exp`` of the kernels is of a difference,
    of a sum to its own position or of the chunk's last."""
    for module in (ssd, kernels):
        body = inspect.getsource(module).split('"""', 2)[2]
        code = "\n".join(
            line.split("#")[0] for line in body.splitlines())
        for word in ("clip", "minimum(", "maximum(", "FLOOR", "CLIP"):
            assert word not in code, (module.__name__, word)
    taken = re.findall(r"jnp\.exp\(([^\n]*)", inspect.getsource(kernels))
    allowed = ("jnp.where(", "last - cum)", 't["cum"])', 't["last"])')
    assert taken and all(t.startswith(allowed) for t in taken), taken
    assert "-jnp.inf" in inspect.getsource(kernels._decay)
    assert "-jnp.inf" in inspect.getsource(ssd.ssd_plain)
