"""Pallas TPU kernels of a Mamba-2 layer's state-space scan
(ops/ssd.py has the recurrence and its chunked form).

A grid step is one chunk of ``CHUNK`` positions of one group of one
sequence: the group's heads side by side on the lanes of ``x`` (16
heads of 64 are 1,024 lanes), the one ``B`` and ``C`` they share, and
``C B^T`` made once for all of them. The chunk axis is sequential and
the group's states stay resident in VMEM as ``[n states, heads x p]``
float32 (512 KB at 16 heads of 64 with 128 states): the lanes of the
state are the lanes of ``x``, so a lane tile of 128 holds whole heads
(two of 64) and every product of a tile is one ``[128, 128]``-faced
matmul for all its heads at once. Only the decay mask ``L`` is a
head's own: a tile's heads each take their ``(C B^T * L_h) (Delta x)``
against the tile's 128 lanes and keep their own lanes of it.

The forward kernel goes up the sequence. Differentiated, it also
writes each chunk's entry states (``[batch, groups, chunks, n, heads
x p]`` float32: 268 MB a layer at 8,192 positions of 128 heads, alive
for that layer's backward pass only), and the backward kernel goes
down the sequence over them with the states' cotangent resident: one
backward kernel, not a second forward walk inside the backward.

The decay. ``exp(cum_t - cum_s)`` is taken pair by pair on the
difference, masked to ``s <= t`` before it is exponentiated; the other
factors are ``exp(cum_t)`` and ``exp(cum_last - cum_t)``. Every
exponent is at most zero: nothing is clipped or floored. The sums
``cum`` themselves (a chunk's log decay ``A Delta`` cumulated from its
start) are made outside the kernels, in float32 by XLA, and handed in
twice, positions down the sublanes and positions along the lanes, so
that a head's column and its row are both slices and no kernel
transposes; their gradient leaves the same way and JAX differentiates
the cumulation, ``A Delta`` and ``D``'s spread to the lanes.

Both calls are made inside one jitted function, ``ssd``: a device
trace names a Pallas call after the innermost jitted function that
holds it, and the benchmark's ``ssd_ms`` tells the kernels by that
name.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.ssd import CHUNK

#: lanes of a tile: whole heads, and a group's states' width
LANE = 128
#: kernels the backward pass runs (beside the forward that keeps the
#: chunks' entry states)
BACKWARD_KERNELS = 1

F32 = jnp.float32
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _sizes(x_shape, b_shape, heads, groups):
    """``(a head's width p, a group's states n, heads a group)``."""
    return x_shape[2] // heads, b_shape[2] // groups, heads // groups


def tiles_the_kernel(x_shape, b_shape, heads, groups) -> bool:
    """Whether the kernels take rows ``[batch, seq, heads x p]`` and
    ``[batch, seq, groups x n]``: whole heads to a lane tile, a
    group's heads in whole tiles, the states in whole tiles, the
    sequence in whole chunks."""
    p, n, per = _sizes(x_shape, b_shape, heads, groups)
    return (
        heads % groups == 0 and LANE % p == 0 and (per * p) % LANE == 0
        and n % LANE == 0 and x_shape[1] % CHUNK == 0
    )


def heads_a_step(heads, groups) -> int:
    """Heads of one grid step: a group's, which share ``B`` and ``C``."""
    return heads // groups


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _dot(a, b, dims, dtype):
    """A product on the MXU with a float32 result: the operands in
    ``dtype``, float32 ones at the highest precision."""
    a, b = a.astype(dtype), b.astype(dtype)
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=F32,
        precision=jax.lax.Precision.HIGHEST if dtype == F32 else None,
    )


def _places(shape):
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _spread(columns, p):
    """``[rows, 1]`` columns, one a head of a lane tile, as ``[rows,
    LANE]``: each over its head's ``p`` lanes."""
    out = jnp.broadcast_to(columns[0], (columns[0].shape[0], LANE))
    head = _places(out.shape)[1] // p
    for i, column in enumerate(columns[1:], 1):
        out = jnp.where(head == i, column, out)
    return out


def _own_lanes(parts, p):
    """``[rows, LANE]`` arrays, one a head of a lane tile: each
    head's own ``p`` lanes of its array."""
    out = parts[0]
    head = _places(out.shape)[1] // p
    for i, part in enumerate(parts[1:], 1):
        out = jnp.where(head == i, part, out)
    return out


def _head_sums(values, p):
    """The sums over each head's ``p`` lanes of ``values`` [rows,
    LANE]: a list of ``[rows, 1]``."""
    head = _places(values.shape)[1] // p
    return [
        jnp.sum(jnp.where(head == i, values, 0.0), axis=1, keepdims=True)
        for i in range(LANE // p)
    ]


def _columns(block, columns):
    """``columns`` ([rows, 1] each) side by side as ``block``'s
    shape ``[rows, heads]``."""
    out = jnp.zeros(block, F32)
    lane = _places(block)[1]
    for h, column in enumerate(columns):
        out = jnp.where(lane == h, column, out)
    return out


def _decay(cum, cum_t, h, mask):
    """``L_h`` [t, s] = ``exp(cum_t - cum_s)`` for ``s <= t``, zero
    above: the difference masked, then exponentiated."""
    return jnp.exp(jnp.where(
        mask, cum[:, h:h + 1] - cum_t[h:h + 1, :], -jnp.inf
    ))


def _tile(refs, j, p):
    """What both kernels make of lane tile ``j`` of a chunk: its
    heads' numbers, ``x`` in float32 and the lane-spread steps and
    sums."""
    x_ref, dt_ref, cum_ref = refs
    lanes = slice(j * LANE, (j + 1) * LANE)
    heads = range(j * (LANE // p), (j + 1) * (LANE // p))
    x = x_ref[:, lanes].astype(F32)
    dt = _spread([dt_ref[:, h:h + 1] for h in heads], p)
    cum = _spread([cum_ref[:, h:h + 1] for h in heads], p)
    # the last row as a masked sum over the sublanes: where a tile is
    # one head (a lightning layer's 128-wide heads) ``cum`` is one
    # column spread over the lanes and its last row, sliced, one
    # number, which the chip's compiler does not spread over sublanes
    # and lanes at once
    at_last = _places(cum.shape)[0] == CHUNK - 1
    last = jnp.sum(jnp.where(at_last, cum, 0.0), axis=0, keepdims=True)
    return dict(
        lanes=lanes, heads=heads, x=x, dt=dt, cum=cum, u=dt * x,
        last=last, to_end=jnp.exp(last - cum),
    )


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, cum_t_ref, dl_ref,
                o_ref, *rest, p, dtype):
    states_ref, state = rest if len(rest) == 2 else (None, *rest)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    if states_ref is not None:
        states_ref[...] = state[...]
    b, c = b_ref[...], c_ref[...]
    cum, cum_t = cum_ref[...], cum_t_ref[...]
    row, col = _places((CHUNK, CHUNK))
    mask = row >= col
    scores = _dot(c, b, _NT, dtype)  # C B^T, once for the group
    for j in range(x_ref.shape[1] // LANE):
        t = _tile((x_ref, dt_ref, cum_ref), j, p)
        lanes = t["lanes"]
        entry = state[:, lanes]
        within = _own_lanes([
            _dot(scores * _decay(cum, cum_t, h, mask), t["u"], _NN, dtype)
            for h in t["heads"]
        ], p)
        across = _dot(c, entry, _NN, dtype) * jnp.exp(t["cum"])
        o_ref[:, lanes] = (
            within + across + dl_ref[:, lanes] * t["x"]
        ).astype(o_ref.dtype)
        state[:, lanes] = jnp.exp(t["last"]) * entry + _dot(
            b, t["u"] * t["to_end"], _TN, dtype)


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, cum_t_ref, dl_ref,
                states_ref, do_ref, dx_ref, db_ref, dc_ref, ddt_ref,
                dcum_ref, dcum_t_ref, ddl_ref, dstate, *, p, dtype):
    first = pl.program_id(2) == 0

    @pl.when(first)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        ddl_ref[...] = jnp.zeros_like(ddl_ref)

    b, c = b_ref[...], c_ref[...]
    cum, cum_t = cum_ref[...], cum_t_ref[...]
    row, col = _places((CHUNK, CHUNK))
    mask = row >= col
    at_last = _places((CHUNK, 1))[0] == CHUNK - 1
    scores = _dot(c, b, _NT, dtype)
    d_scores = jnp.zeros((CHUNK, CHUNK), F32)
    d_b = jnp.zeros(b.shape, F32)
    d_c = jnp.zeros(c.shape, F32)
    ddt, dcum = [], []
    for j in range(x_ref.shape[1] // LANE):
        t = _tile((x_ref, dt_ref, cum_ref), j, p)
        lanes, x, u = t["lanes"], t["x"], t["u"]
        do = do_ref[:, lanes].astype(F32)
        entry, after = states_ref[:, lanes], dstate[:, lanes]
        head = _places(do.shape)[1] // p
        grown = jnp.exp(t["cum"])
        do_grown = do * grown
        kept = jnp.exp(t["last"])
        # the state's path: what leaves the chunk, and what it read
        du = t["to_end"] * _dot(b, after, _NN, dtype)
        d_b = d_b + _dot(u * t["to_end"], after, _NT, dtype)
        d_c = d_c + _dot(do_grown, entry, _NT, dtype)
        dstate[:, lanes] = kept * after + _dot(c, do_grown, _TN, dtype)
        # a position's sum is in its own result's ``exp(cum)`` and,
        # against the last's, in what it hands the state
        read = do_grown * _dot(c, entry, _NN, dtype)
        handed = du * u
        to_last = [
            jnp.sum(a, axis=0, keepdims=True) + b_ for a, b_ in zip(
                _head_sums(handed, p),
                _head_sums(jnp.sum(
                    after * kept * entry, axis=0, keepdims=True), p))
        ]
        along = [
            a + jnp.where(at_last, b_, 0.0)
            for a, b_ in zip(_head_sums(read - handed, p), to_last)
        ]
        inside = []
        for i, h in enumerate(t["heads"]):
            decay = _decay(cum, cum_t, h, mask)
            weights = scores * decay
            d_weights = _dot(jnp.where(head == i, do, 0.0), u, _NT, dtype)
            d_scores = d_scores + d_weights * decay
            # the exponent's cotangent: down a row it is the row's
            # sum's, up a column the column's, negated
            spent = d_weights * weights
            along[i] = along[i] + jnp.sum(spent, axis=1, keepdims=True)
            dcum_t_ref[h:h + 1, :] = -jnp.sum(spent, axis=0, keepdims=True)
            inside.append(_dot(weights, do, _TN, dtype))
        du = du + _own_lanes(inside, p)
        dx_ref[:, lanes] = (
            t["dt"] * du + dl_ref[:, lanes] * do).astype(dx_ref.dtype)
        ddl_ref[:, lanes] += jnp.sum(do * x, axis=0, keepdims=True)
        ddt += _head_sums(du * x, p)
        dcum += along
    db_ref[...] = (d_b + _dot(d_scores, c, _TN, dtype)).astype(db_ref.dtype)
    dc_ref[...] = (d_c + _dot(d_scores, b, _NN, dtype)).astype(dc_ref.dtype)
    ddt_ref[...] = _columns(ddt_ref.shape, ddt)
    dcum_ref[...] = _columns(dcum_ref.shape, dcum)


def _specs(chunks, wide, n, per, reverse):
    """Block specs for the grid ``(batch, group, chunk)``: a group's
    lanes of ``x``; its ``B`` or ``C``; a head's numbers, positions
    down the sublanes ``[batch, groups, seq, heads a group]`` or along
    the lanes ``[batch, groups, heads a group, seq]``; ``D`` on its
    head's lanes; the group's states ``[batch, groups, chunks, n,
    wide]``. ``reverse`` walks the chunks from the last."""
    def at(i):
        return chunks - 1 - i if reverse else i

    return dict(
        x=pl.BlockSpec((None, CHUNK, wide), lambda b, g, i: (b, at(i), g)),
        bc=pl.BlockSpec((None, CHUNK, n), lambda b, g, i: (b, at(i), g)),
        down=pl.BlockSpec(
            (None, None, CHUNK, per), lambda b, g, i: (b, g, at(i), 0)),
        along=pl.BlockSpec(
            (None, None, per, CHUNK), lambda b, g, i: (b, g, 0, at(i))),
        lanes=pl.BlockSpec((1, wide), lambda b, g, i: (0, g)),
        sums=pl.BlockSpec((None, 1, wide), lambda b, g, i: (b, 0, g)),
        states=pl.BlockSpec(
            (None, None, None, n, wide),
            lambda b, g, i: (b, g, at(i), 0, 0)),
    )


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )


def _forward(x, B, C, dt, cum, cum_t, dl, keep_states):
    batch, seq, _ = x.shape
    groups, per = dt.shape[1], dt.shape[3]
    wide, n = x.shape[2] // groups, B.shape[2] // groups
    chunks = seq // CHUNK
    s = _specs(chunks, wide, n, per, False)
    out_specs = [s["x"]]
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    if keep_states:
        out_specs.append(s["states"])
        out_shape.append(jax.ShapeDtypeStruct(
            (batch, groups, chunks, n, wide), F32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, p=wide // per, dtype=x.dtype),
        grid=(batch, groups, chunks),
        in_specs=[s["x"], s["bc"], s["bc"], s["down"], s["down"],
                  s["along"], s["lanes"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, wide), F32)],
        compiler_params=_params(), interpret=_interpret(),
    )(x, B, C, dt, cum, cum_t, dl)
    return out if keep_states else out[0]


def _backward(x, B, C, dt, cum, cum_t, dl, states, do):
    batch, seq, _ = x.shape
    groups, per = dt.shape[1], dt.shape[3]
    wide, n = x.shape[2] // groups, B.shape[2] // groups
    s = _specs(seq // CHUNK, wide, n, per, True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, p=wide // per, dtype=x.dtype),
        grid=(batch, groups, seq // CHUNK),
        in_specs=[s["x"], s["bc"], s["bc"], s["down"], s["down"],
                  s["along"], s["lanes"], s["states"], s["x"]],
        out_specs=[s["x"], s["bc"], s["bc"], s["down"], s["down"],
                   s["along"], s["sums"]],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(B.shape, B.dtype),
            jax.ShapeDtypeStruct(C.shape, C.dtype),
            jax.ShapeDtypeStruct(dt.shape, F32),
            jax.ShapeDtypeStruct(cum.shape, F32),
            jax.ShapeDtypeStruct(cum_t.shape, F32),
            # a batch row's sums over its positions, a lane
            jax.ShapeDtypeStruct((batch, 1, x.shape[2]), F32),
        ],
        scratch_shapes=[pltpu.VMEM((n, wide), F32)],
        compiler_params=_params(), interpret=_interpret(),
    )(x, B, C, dt, cum, cum_t, dl, states, do)


@functools.partial(jax.jit, static_argnames=("keep_states",))
def ssd(x, B, C, dt, cum, dl, states=None, do=None, keep_states=False):
    """On rows ``x`` [batch, seq, heads x p] and ``B``, ``C`` [batch,
    seq, groups x n], with a head's step ``dt`` and summed log decay
    ``cum`` as ``[batch, groups, seq, heads a group]`` float32 and
    ``dl`` [1, heads x p], ``D`` on its head's lanes: the forward
    kernel's ``o`` (with ``keep_states`` also the chunks' entry
    states), or with the states and the result's cotangent ``do`` the
    backward kernel's six gradients, each in its operand's shape. One
    jitted name for both, which is what a device trace calls them."""
    cum_t = jnp.swapaxes(cum, 2, 3)
    if do is None:
        out = _forward(x, B, C, dt, cum, cum_t, dl, keep_states)
        return tuple(out) if keep_states else out
    dx, db, dc, ddt, dcum, dcum_t, ddl = _backward(
        x, B, C, dt, cum, cum_t, dl, states, do)
    return (dx, db, dc, ddt, dcum + jnp.swapaxes(dcum_t, 2, 3),
            jnp.sum(ddl, axis=0))


def _record(heads, groups, p, n):
    """Say what was built, at trace time: the gauges of
    docs/TELEMETRY.md."""
    from dlrover_tpu.telemetry.registry import gauge

    together = heads_a_step(heads, groups)
    gauge(
        "ssd_chunk", "positions of one chunk of the state-space scan",
    ).set(CHUNK)
    gauge(
        "ssd_heads_per_step",
        "heads, a group's, whose chunk one grid step of the state-space "
        "scan's kernels takes",
    ).set(together)
    gauge(
        "ssd_state_bytes",
        "bytes of the states, a grid step's heads', resident in VMEM "
        "through the state-space scan",
    ).set(together * p * n * 4)
    gauge(
        "ssd_backward_kernels",
        "Pallas kernels of the state-space scan's backward pass, beside "
        "the forward that keeps the chunks' entry states",
    ).set(BACKWARD_KERNELS)


@jax.custom_vjp
def _scan(x, B, C, dt, cum, dl):
    return ssd(x, B, C, dt, cum, dl)


def _vjp_fwd(x, B, C, dt, cum, dl):
    o, states = ssd(x, B, C, dt, cum, dl, keep_states=True)
    return o, (x, B, C, dt, cum, dl, states)


def _vjp_bwd(saved, do):
    *operands, states = saved
    return ssd(*operands, states=states, do=do)


_scan.defvjp(_vjp_fwd, _vjp_bwd)


def ssd_tpu(x, B, C, dt, A, D, groups):
    """``ops/ssd.py ssd_scan`` through the kernels. What a kernel is
    handed beside the rows is made here, by XLA and under JAX's own
    differentiation: a head's numbers by group, ``[batch, groups, seq,
    heads a group]``; the log decay ``A dt`` summed from each chunk's
    start; ``D`` on its head's lanes."""
    batch, seq, heads = dt.shape
    per = heads // groups
    p, n, _ = _sizes(x.shape, B.shape, heads, groups)
    _record(heads, groups, p, n)

    def by_group(a):
        return jnp.swapaxes(a.reshape(batch, seq, groups, per), 1, 2)

    dt = dt.astype(F32)
    cum = jnp.cumsum(
        (dt * A.astype(F32)).reshape(batch, seq // CHUNK, CHUNK, heads),
        axis=2,
    ).reshape(batch, seq, heads)
    dl = jnp.repeat(D.astype(F32), p)[None]
    return _scan(x, B, C, by_group(dt), by_group(cum), dl)
