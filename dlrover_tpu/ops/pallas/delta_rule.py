"""Pallas TPU kernels of the gated delta rule with per-channel decay
(ops/delta_rule.py has the recurrence and its chunked form).

A grid step is one chunk of ``CHUNK`` positions of one head of one
sequence; a head's chunks are walked in order, with its [values,
keys] float32 state resident in VMEM (64 KB). The forward kernel goes
up the sequence. Differentiated, it also writes each chunk's entry
state, and the backward kernel goes down the sequence over them with
the state's cotangent resident, making a chunk's ``A``, ``B``, ``W``
again from its operands: one forward kernel that keeps ``[seq / 64,
heads, 128, 128]`` float32 (256 MB a layer at 8,192 positions of 64
heads, alive for that layer's backward pass only) and one backward
kernel, not a second forward walk inside the backward.

Within a chunk the rank-one updates are folded: ``(I + Diag(beta)
A)^-1`` of the strictly lower ``N = Diag(beta) A`` is a product of
``I + (-M) ** (2 ** j)`` for a nilpotent ``M``, ten float32 products
on the MXU (``_inverse``: the diagonal's blocks of 16 first, then the
blocks below them).

The decay. ``exp(G_t - G_i)`` for ``i <= t`` is at most one, but it
does not factor into a row's and a column's part without exponents of
both signs, and a channel that forgets fast overflows ``exp(-G_i)``
within a chunk. So a chunk's rows are taken ``SUB`` at a time, each
block against every column up to its own last, with the exponents
relative to the middle ``rho`` of the block's own range of ``G``: a
row's factor is ``exp(G_t - rho)`` and a column's ``exp(rho - G_i)``,
which is at most one for every column before the block and within
``exp(+-half the block's range)`` inside it. That range is bounded
by the entry: ``ops/delta_rule.py gated_delta_rule`` takes no log
decay under ``G_FLOOR`` (-10 a step), so a block's fifteen steps span
at most 150 and no factor of a pair the result keeps passes
``exp(75)``: the factored products are exact on everything the entry
hands over. ``delta_rule_tpu`` called by itself on a faster decay is
outside that: ``CLIP`` then keeps the factors finite and the block
and channel are wrong (tests/test_delta_rule.py reads by how much).
What ``CLIP`` does bind on inside the range is columns after a block's
own, whose ``exp(rho - G_i)`` would overflow and whose products are
masked, by a select, after they are made.

Operands, residuals and results are rows, ``[batch, seq, heads x
128]`` (``beta`` ``[batch, seq, heads]``): what a projection writes,
and what a grid step's block, a head's lane tile of 64 rows, is cut
from. On the chip ``[seq, heads, 128]`` is other bytes than ``[seq,
heads x 128]`` (tiles of (heads, 128), not of (8 rows, 128)), so a
caller that holds heads pays a pass over each operand to get here:
ops/delta_rule.py's 4-D entry is the one place that does, and the
record says so.

Both calls are made inside one jitted function, ``delta_rule``: a
device trace names a Pallas call after the innermost jitted function
that holds it, and the benchmark's ``delta_rule_ms`` tells the
kernels by that name.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.delta_rule import CHUNK, G_FLOOR

#: rows of a chunk that share a reference for their decay's exponents
SUB = 16
#: the largest exponent, of either sign, that a factor is given: over
#: half of what a block spans at the entry's floor, under float32's 88
CLIP = 80.0
assert -G_FLOOR * (SUB - 1) / 2 <= CLIP
#: a head's keys and values: one lane tile
HEAD = 128
#: kernels the backward pass runs (beside the forward that keeps the
#: chunks' entry states)
BACKWARD_KERNELS = 1

F32 = jnp.float32
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def tiles_the_kernel(shape, heads) -> bool:
    """Whether the kernels take rows ``[batch, seq, heads x d]``: a
    head one lane tile wide, the sequence whole chunks."""
    return shape[2] == heads * HEAD and shape[1] % CHUNK == 0


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _dot(a, b, dims, dtype=F32):
    """A product on the MXU with a float32 result: the operands in
    ``dtype``, float32 ones at the highest precision."""
    a, b = a.astype(dtype), b.astype(dtype)
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=F32,
        precision=jax.lax.Precision.HIGHEST if dtype == F32 else None,
    )


def _triangle(strict, upper=False):
    row = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
    if upper:
        row, col = col, row
    return row > col if strict else row >= col


def _ones(mask):
    return jnp.where(mask, 1.0, 0.0).astype(F32)


def _factors(gc):
    """``[(rows, exp(G_t - rho) [SUB, d], exp(rho - G_i) [CHUNK, d])]``
    for each block of ``SUB`` rows of the chunk's cumulated log decay
    ``gc``."""
    out = []
    for at in range(0, CHUNK, SUB):
        rows = slice(at, at + SUB)
        block = gc[rows]
        rho = 0.5 * (block[:1] + block[SUB - 1:])
        out.append((
            rows, jnp.exp(jnp.clip(block - rho, -CLIP, CLIP)),
            jnp.exp(jnp.minimum(rho - gc, CLIP)),
        ))
    return out


def _inverse(n):
    """``(I + n)^-1`` of a strictly lower-triangular ``n`` [CHUNK,
    CHUNK], in float32 products. A nilpotent ``m`` has ``(I + m)^-1 =
    (I - m)(I + m^2)(I + m^4)...``; taken on all of ``n`` at once the
    high powers of keys that resemble each other grow large before
    they cancel. So first the blocks of ``SUB`` on the diagonal, whose
    sixteenth power is zero (``near``), then what is left of ``I +
    n`` once they are divided out: ``I + near n_off``, whose blocks
    lie strictly below the diagonal and whose ``CHUNK / SUB``-th
    power is zero."""
    row = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
    eye = _ones(row == col)
    diagonal = row // SUB == col // SUB

    def inverse(m, order):
        inv, power = eye - m, m
        for _ in range(order.bit_length() - 2):
            power = _dot(power, power, _NN)
            inv = _dot(inv, eye + power, _NN)
        return inv

    near = inverse(jnp.where(diagonal, n, 0.0), SUB)
    far = _dot(near, jnp.where(diagonal, 0.0, n), _NN)
    return _dot(inverse(far, CHUNK // SUB), near, _NN)


def _chunk(q, k, v, g, beta, state, dtype):
    """What both kernels make of a chunk's operands (float32) and its
    entry state [values, keys]: a dict of the chunked form's parts."""
    gc = _dot(_ones(_triangle(False)), g, _NN)  # cumulated down the rows
    factors = _factors(gc)
    a_rows, b_rows = [], []
    for rows, e_row, e_col in factors:
        both = _dot(
            jnp.concatenate([k[rows] * e_row, q[rows] * e_row], axis=0),
            k * e_col, _NT, dtype,
        )
        a_rows.append(both[:SUB])
        b_rows.append(both[SUB:])
    a = jnp.where(_triangle(True), jnp.concatenate(a_rows, axis=0), 0.0)
    b = jnp.where(_triangle(False), jnp.concatenate(b_rows, axis=0), 0.0)
    inv = _inverse(beta * a)
    gamma = jnp.exp(gc)
    kg, qg = k * gamma, q * gamma
    held = _dot(kg, state, _NT, dtype)  # what the state holds along k
    w = _dot(inv, beta * (v - held), _NN)
    last = gc[CHUNK - 1:]
    return dict(
        gc=gc, factors=factors, a=a, b=b, inv=inv, gamma=gamma, kg=kg,
        qg=qg, held=held, w=w, last=last, kd=k * jnp.exp(last - gc),
    )


def _next_state(c, state, dtype):
    return state * jnp.exp(c["last"]) + _dot(c["w"], c["kd"], _TN, dtype)


def _load(refs):
    return [ref[...].astype(F32) for ref in refs]


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest,
                scale, dtype):
    states_ref, state = (rest if len(rest) == 2 else (None, *rest))

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    q, k, v, g, beta = _load((q_ref, k_ref, v_ref, g_ref, beta_ref))
    entry = state[...]
    if states_ref is not None:
        states_ref[...] = entry
    c = _chunk(q, k, v, g, beta, entry, dtype)
    o_ref[...] = (scale * (
        _dot(c["qg"], entry, _NT, dtype) + _dot(c["b"], c["w"], _NN, dtype)
    )).astype(o_ref.dtype)
    state[...] = _next_state(c, entry, dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate, *,
                scale, dtype):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    q, k, v, g, beta = _load((q_ref, k_ref, v_ref, g_ref, beta_ref))
    entry, after = states_ref[...], dstate[...]
    do = scale * do_ref[...].astype(F32)
    c = _chunk(q, k, v, g, beta, entry, dtype)
    w, kd, kg, qg, gamma = c["w"], c["kd"], c["kg"], c["qg"], c["gamma"]

    dw = _dot(c["b"], do, _TN, dtype) + _dot(kd, after, _NT, dtype)
    db = jnp.where(_triangle(False), _dot(do, w, _NT, dtype), 0.0)
    dqg = _dot(do, entry, _NN, dtype)
    dkd = _dot(w, after, _NN, dtype)
    dr = _dot(c["inv"], dw, _TN)
    dn = jnp.where(_triangle(True), -_dot(dr, w, _NT), 0.0)
    da = beta * dn
    dbeta_ref[...] = (
        jnp.sum(dn * c["a"], axis=1, keepdims=True)
        + jnp.sum(dr * (v - c["held"]), axis=1, keepdims=True)
    )
    dv = beta * dr
    dv_ref[...] = dv.astype(dv_ref.dtype)
    dkg = -_dot(dv, entry, _NN, dtype)
    dstate[...] = (
        _dot(do, qg, _TN, dtype) + after * jnp.exp(c["last"])
        - _dot(dv, kg, _TN, dtype)
    )
    dq = dqg * gamma
    dk = dkg * gamma + dkd * jnp.exp(c["last"] - c["gc"])
    dgc = dqg * qg + dkg * kg - dkd * kd
    dlast = (
        jnp.sum(entry * after, axis=0, keepdims=True) * jnp.exp(c["last"])
        + jnp.sum(dkd * kd, axis=0, keepdims=True)
    )
    dq_rows, dk_rows, dgc_rows = [], [], []
    for rows, e_row, e_col in c["factors"]:
        grads = jnp.concatenate([da[rows], db[rows]], axis=0)
        along = _dot(grads, k * e_col, _NN, dtype)
        to_k, to_q = along[:SUB] * e_row, along[SUB:] * e_row
        dk_rows.append(to_k)
        dq_rows.append(to_q)
        dgc_rows.append(k[rows] * to_k + q[rows] * to_q)
        down = e_col * _dot(
            grads,
            jnp.concatenate([k[rows] * e_row, q[rows] * e_row], axis=0),
            _TN, dtype,
        )
        dk = dk + down
        dgc = dgc - k * down
    dq_ref[...] = (dq + jnp.concatenate(dq_rows, axis=0)).astype(dq_ref.dtype)
    dk_ref[...] = (dk + jnp.concatenate(dk_rows, axis=0)).astype(dk_ref.dtype)
    dgc = dgc + jnp.concatenate(dgc_rows, axis=0)
    # a position's log decay is in every later row's cumulated one,
    # and the chunk's last row's in the state that leaves it
    dg_ref[...] = (
        _dot(_ones(_triangle(False, upper=True)), dgc, _NN) + dlast
    ).astype(dg_ref.dtype)


def _specs(chunks, reverse):
    """Block specs of a ``[batch, seq, heads x d]`` operand, of
    ``beta`` as ``[batch, heads, seq, 1]`` and of the entry states
    ``[batch, heads, chunks, d, d]``, for the grid ``(batch, head,
    chunk)``; ``reverse`` walks the chunks from the last."""
    def at(n):
        return chunks - 1 - n if reverse else n

    wide = pl.BlockSpec((None, CHUNK, HEAD), lambda b, h, n: (b, at(n), h))
    beta = pl.BlockSpec(
        (None, None, CHUNK, 1), lambda b, h, n: (b, h, at(n), 0))
    states = pl.BlockSpec(
        (None, None, None, HEAD, HEAD), lambda b, h, n: (b, h, at(n), 0, 0))
    return wide, beta, states


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )


def _forward(q, k, v, g, beta, heads, keep_states):
    batch, seq, _ = q.shape
    chunks = seq // CHUNK
    wide, beta_spec, states_spec = _specs(chunks, False)
    out_specs = [wide]
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)]
    if keep_states:
        out_specs.append(states_spec)
        out_shape.append(jax.ShapeDtypeStruct(
            (batch, heads, chunks, HEAD, HEAD), F32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=HEAD ** -0.5, dtype=q.dtype),
        grid=(batch, heads, chunks),
        in_specs=[wide, wide, wide, wide, beta_spec],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((HEAD, HEAD), F32)],
        compiler_params=_params(), interpret=_interpret(),
    )(q, k, v, g, beta)
    return out if keep_states else out[0]


def _backward(q, k, v, g, beta, states, do, heads):
    batch, seq, _ = q.shape
    chunks = seq // CHUNK
    wide, beta_spec, states_spec = _specs(chunks, True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=HEAD ** -0.5, dtype=q.dtype),
        grid=(batch, heads, chunks),
        in_specs=[wide, wide, wide, wide, beta_spec, states_spec, wide],
        out_specs=[wide, wide, wide, wide, beta_spec],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(g.shape, g.dtype),
            jax.ShapeDtypeStruct(beta.shape, beta.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((HEAD, HEAD), F32)],
        compiler_params=_params(), interpret=_interpret(),
    )(q, k, v, g, beta, states, do)


def _beta_by_head(beta):
    """``[batch, seq, heads]`` as ``[batch, heads, seq, 1]``: a chunk's
    step sizes down the sublanes."""
    return jnp.swapaxes(beta, 1, 2)[..., None]


@functools.partial(jax.jit, static_argnames=("keep_states",))
def delta_rule(q, k, v, g, beta, states=None, do=None, keep_states=False):
    """On rows ``[batch, seq, heads x 128]`` and ``beta`` ``[batch,
    seq, heads]``: the forward kernel's ``o`` (with ``keep_states``
    also the chunks' entry states), or with the states and the
    result's cotangent ``do`` the backward kernel's five gradients,
    each in its operand's shape. One jitted name for both, which is
    what a device trace calls them."""
    heads = q.shape[2] // HEAD
    wide = (q, k, v, g.astype(F32), _beta_by_head(beta.astype(F32)))
    if do is None:
        out = _forward(*wide, heads, keep_states)
        return tuple(out) if keep_states else out
    dq, dk, dv, dg, dbeta = _backward(*wide, states, do, heads)
    return (
        dq, dk, dv, dg.astype(g.dtype),
        jnp.swapaxes(dbeta[..., 0], 1, 2).astype(beta.dtype),
    )


def _record(folded):
    """Say what was built, at trace time: the gauges and counters of
    docs/TELEMETRY.md. Every Pallas call a step holds is counted once,
    by whether its caller held rows or heads that were ``folded``."""
    from dlrover_tpu.telemetry.registry import counter, gauge

    gauge(
        "delta_rule_chunk",
        "positions of one chunk of the gated delta rule's scan",
    ).set(CHUNK)
    gauge(
        "delta_rule_state_bytes",
        "bytes of a head's state resident in VMEM through the gated "
        "delta rule's scan",
    ).set(HEAD * HEAD * 4)
    gauge(
        "delta_rule_backward_kernels",
        "Pallas kernels of the gated delta rule's backward pass, beside "
        "the forward that keeps the chunks' entry states",
    ).set(BACKWARD_KERNELS)
    if folded:
        counter(
            "delta_rule_folded_calls",
            "Pallas calls of the gated delta rule traced on operands "
            "that came as [batch, seq, heads, d] and were folded to "
            "rows: a relayout of each on the chip",
        ).inc()
    else:
        counter(
            "delta_rule_rows_calls",
            "Pallas calls of the gated delta rule traced on operands "
            "that came as rows [batch, seq, heads x d], as the kernels "
            "read them",
        ).inc()


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def delta_rule_tpu(q, k, v, g, beta, folded=False):
    """``delta_rule`` with its differentiation rule; ``folded`` says,
    for the record alone, that the caller held heads."""
    _record(folded)
    return delta_rule(q, k, v, g, beta)


def _vjp_fwd(q, k, v, g, beta, folded):
    _record(folded)
    o, states = delta_rule(q, k, v, g, beta, keep_states=True)
    return o, (q, k, v, g, beta, states)


def _vjp_bwd(folded, saved, do):
    _record(folded)
    *operands, states = saved
    return delta_rule(*operands, states=states, do=do)


delta_rule_tpu.defvjp(_vjp_fwd, _vjp_bwd)
