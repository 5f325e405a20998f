"""Pallas TPU kernels of the gated delta rule, with a decay a key
channel or one a head (ops/delta_rule.py has the recurrence, its two
forms of decay and its chunked form).

A grid step is one chunk of ``CHUNK`` positions of several adjacent
heads of one sequence (``heads_a_step``: the most of ``HEADS_A_STEP``
that divides the operands' heads, of ``FORWARD_HEADS_A_STEP`` for the
forward kernels, one head where none does); the
heads' chunks are walked in order, with each head's [values, keys]
float32 state resident in VMEM (64 KB a head). The forward kernel goes
up the sequence. Differentiated, it also writes, for each chunk, the
head's entry state and what it solved there: the pair's inverse and
the head's ``w``. The backward kernel goes down the sequence over
them with the state's cotangent resident. It makes a chunk's ``A``,
``B`` and decay factors again from its operands, and solves nothing:
the inverse is the longest chain of either kernel, and the float32
one it reads is the one it would make, so its gradients are the same
bits. One forward kernel that keeps ``[heads, seq / 64, 128, 128]``
float32 entry states, ``[heads / 2, seq / 64, 128, 128]`` inverses and
``[seq, heads x 128]`` of ``w`` (64 + 32 + 32 KB a head's chunk: 537,
268 and 268 MB a layer at 8,192 positions of 64 heads, alive for that
layer's backward pass only) and one backward kernel, not a second
forward walk inside the backward.

Why several heads. A head's chunk is a chain of small products, each
waiting for the one before (the inverse below alone is seven deep, on
[64, 64] operands), and one head offers the compiler nothing to put
in the waits: alone, a head's chunk takes 2.3 us forward and 3.0
backward, 1.5 us of either the inverse (PERF.md, PR 46). The heads
are independent and adjacent lanes of the same rows, so a grid step
takes a block of rows ``[CHUNK, heads_a_step x 128]`` and makes the
heads' chunks side by side. The arithmetic of a head is what it is
alone, product for product and bit for bit; what changes is the order
of the kernel's text, which is the order the compiler issues from:
the heads' parts are generators taken in turn (``_in_turn``), so that
the same product of every head stands together, and the inverse is
taken on two heads at once as one block-diagonal [128, 128] matrix,
which fills the MXU's face where one head's [64, 64] fills a quarter.
The stages between two turns are jitted (``_stage``), so that a kernel
is traced in the time of one head's, however many it holds.

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
and what a grid step's block, its heads' lane tiles of 64 rows, is
cut from. On the chip ``[seq, heads, 128]`` is other bytes than ``[seq,
heads x 128]`` (tiles of (heads, 128), not of (8 rows, 128)), so a
caller that holds heads pays a pass over each operand to get here:
ops/delta_rule.py's 4-D entry is the one place that does, and the
record says so.

One decay a head (``g`` in ``beta``'s shape). The same two kernels,
stage for stage, with three differences. The decay of a pair is one
``[CHUNK, CHUNK]`` mask a head, ``exp`` of the sum of the steps
between the two positions (``_mask``: one float32 product with a
triangle of ones, no exponent positive, nothing factored): no ``SUB``,
no ``CLIP`` and no floor, exact at any decay. ``g`` comes and ``dg``
goes as ``beta`` does, ``[batch, heads, seq, 1]``, a number a position
down the sublanes, and is spread over a head's lanes in VMEM only. And
a head is ``dk`` keys by ``dv`` values of any two widths: the state is
``[values, keys]``, the chunk's system and its inverse ``[CHUNK,
CHUNK]`` whatever ``dv``, the scale ``dk ** -0.5``. A width that is
no lane tile is padded with zero columns inside ``delta_rule``, keys
to ``HEAD`` and values to whole lane tiles (96 to 128, 192 to 256): a
zero key column adds nothing to any product over keys and its column
of the state stays zero, and value columns never meet (every product
over values is over positions or keys), so the padded columns of ``o``
are zero and are cut off again; the results are the unpadded
equations' to the bit. What it costs: a pass over q, k, v and ``o``
each way (and over ``do``, ``dq``, ``dk``, ``dv``), the entry states
kept at ``[256, 128]`` where ``[192, 96]`` would do (128 KB a head's
chunk for 72), and nothing on the MXU or in the vector unit, whose
tiles are 128 lanes wide whatever a head's width. No weight is padded:
the model's leaves keep their shapes.

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

from dlrover_tpu.ops.delta_rule import CHUNK, G_FLOOR, one_decay_a_head

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
#: inverses a chunk's backward makes: it reads the one the forward kept
#: (tests/test_delta_rule.py counts them in the kernel's trace)
BACKWARD_INVERSES = 0
#: heads that may share a grid step, the most first. Eight read 6%
#: under four in the kernels and 0.6% in the step, for 2.5 s more of
#: every start spent tracing them (PERF.md, PR 46)
HEADS_A_STEP = (4, 2)
#: and of the forward kernels, which take a third pair where the head
#: count has one: at 30 heads of 96 x 192 six a step read 15.0 ms a
#: call for two's 20.0, while the backward, with twice the values
#: alive, read 38.4 for 20.2 (PERF.md, PR 70). Every entry is even
#: where ``HEADS_A_STEP``'s choice is, so the pairs whose inverses the
#: forward keeps are the pairs the backward reads
FORWARD_HEADS_A_STEP = (6,) + HEADS_A_STEP

F32 = jnp.float32
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


#: the widest head of values, padded, whose state a grid step holds
MOST_VALUES = 256


def tiles_the_kernel(shape, heads, v_shape=None) -> bool:
    """Whether the kernels take rows ``[batch, seq, heads x d]``: a
    head one lane tile wide, the sequence whole chunks. With
    ``v_shape``, one decay a head: keys of at most a lane tile and
    values of at most ``MOST_VALUES`` a head (``delta_rule`` pads both
    to whole tiles)."""
    if shape[1] % CHUNK:
        return False
    if v_shape is None:
        return shape[2] == heads * HEAD
    return (shape[2] <= heads * HEAD
            and v_shape[2] <= heads * MOST_VALUES)


def heads_a_step(heads, forward=False) -> int:
    """Heads of one grid step: the most of ``HEADS_A_STEP`` (of
    ``FORWARD_HEADS_A_STEP`` for the ``forward`` kernels) that divides
    the operands' ``heads``, one where none does."""
    rule = FORWARD_HEADS_A_STEP if forward else HEADS_A_STEP
    return next((h for h in rule if heads % h == 0), 1)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _in_turn(bodies):
    """Run generators a step each in turn until each has returned,
    and give what they returned. A head's part of a grid step is
    written as a generator that yields where its next line waits for
    products: taken in turn, the heads' products of one stage stand
    next to each other in the kernel's text, which is the order the
    compiler issues them in (it moves little of one head's chain up
    beside another's by itself: PERF.md, PR 46). One body is run
    straight through."""
    bodies = list(bodies)
    results = [None] * len(bodies)
    live = dict(enumerate(bodies))
    while live:
        for j, body in list(live.items()):
            try:
                next(body)
            except StopIteration as stop:
                results[j] = stop.value
                del live[j]
    return results


#: a stage of a head's part, traced once for all the heads of a grid
#: step and all three kernels (the trace of a kernel is otherwise as
#: many times a head's as the step has heads: PERF.md, PR 46); the
#: kernel's text holds every call's operations in place
_stage = functools.partial(jax.jit, static_argnames=("dtype",))


def _dot(a, b, dims, dtype=F32):
    """A product on the MXU with a float32 result: the operands in
    ``dtype``, float32 ones at the highest precision."""
    a, b = a.astype(dtype), b.astype(dtype)
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=F32,
        precision=jax.lax.Precision.HIGHEST if dtype == F32 else None,
    )


def _places(shape):
    """Every entry's row and its column."""
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _triangle(strict, upper=False):
    row, col = _places((CHUNK, CHUNK))
    if upper:
        row, col = col, row
    return row > col if strict else row >= col


def _ones(mask):
    return jnp.where(mask, 1.0, 0.0).astype(F32)


def _eye(shape):
    row, col = _places(shape)
    return _ones(row == col)


def _rows(block):
    """The rows of a chunk's ``block``-th block of ``SUB``."""
    return slice(block * SUB, (block + 1) * SUB)


@jax.jit
def _cumulated(g):
    """A chunk's log decay cumulated down its rows."""
    return _dot(_ones(_triangle(False)), g, _NN)


@jax.jit
def _factors(gc):
    """``[(exp(G_t - rho) [SUB, d], exp(rho - G_i) [CHUNK, d])]`` for
    each block of ``SUB`` rows of the chunk's cumulated log decay
    ``gc``."""
    out = []
    for at in range(CHUNK // SUB):
        block = gc[_rows(at)]
        rho = 0.5 * (block[:1] + block[SUB - 1:])
        out.append((
            jnp.exp(jnp.clip(block - rho, -CLIP, CLIP)),
            jnp.exp(jnp.minimum(rho - gc, CLIP)),
        ))
    return out


@_stage
def _decayed(q, k, factors, dtype):
    """``A`` (strictly lower) and ``B`` (lower) of a chunk, a block
    of rows at a time."""
    a_rows, b_rows = [], []
    for at, (e_row, e_col) in enumerate(factors):
        rows = _rows(at)
        both = _dot(
            jnp.concatenate([k[rows] * e_row, q[rows] * e_row], axis=0),
            k * e_col, _NT, dtype,
        )
        a_rows.append(both[:SUB])
        b_rows.append(both[SUB:])
    return (
        jnp.where(_triangle(True), jnp.concatenate(a_rows, axis=0), 0.0),
        jnp.where(_triangle(False), jnp.concatenate(b_rows, axis=0), 0.0),
    )


@_stage
def _against_state(q, k, gc, state, dtype):
    gamma = jnp.exp(gc)
    kg, qg = k * gamma, q * gamma
    last = gc[CHUNK - 1:]
    return dict(
        gamma=gamma, kg=kg, qg=qg, last=last, kd=k * jnp.exp(last - gc),
        held=_dot(kg, state, _NT, dtype),  # what the state holds along k
    )


def _before(q, k, v, g, beta, state, dtype):
    """What both kernels make of a head's chunk, its operands in
    float32 and its entry state [values, keys], ahead of the inverse:
    a dict of the chunked form's parts; a generator (``_in_turn``)."""
    gc = _cumulated(g)
    yield
    factors = _factors(gc)
    yield
    a, b = _decayed(q, k, factors, dtype)
    yield
    return dict(
        q=q, k=k, v=v, beta=beta, state=state, gc=gc, factors=factors,
        a=a, b=b, **_against_state(q, k, gc, state, dtype),
    )


@jax.jit
def _mask(g):
    """One decay a head: ``exp(G_t - G_i)`` [CHUNK, CHUNK] of a
    chunk's log decay ``g`` [CHUNK, 1], zero above the diagonal. The
    exponent is the sum of the steps ``i < j <= t``, one float32
    product of a triangle of ones with ``g`` spread under the
    diagonal: a sum of terms of one sign, never a difference of two
    cumulated sums."""
    under = jnp.where(
        _triangle(True), jnp.broadcast_to(g, (CHUNK, CHUNK)), 0.0)
    between = _dot(_ones(_triangle(False)), under, _NN)
    return jnp.where(_triangle(False), jnp.exp(between), 0.0)


@_stage
def _masked(q, k, mask, dtype):
    """``A`` (strictly lower) and ``B`` (lower) of a chunk under one
    decay a head: ``K K^T`` and ``Q K^T`` in one product, times the
    pairs' decay."""
    both = _dot(jnp.concatenate([k, q], axis=0), k, _NT, dtype)
    return (
        jnp.where(_triangle(True), both[:CHUNK] * mask, 0.0),
        both[CHUNK:] * mask,
    )


def _before_a_head(q, k, v, g, beta, state, dtype):
    """``_before`` with one decay a head, ``g`` [CHUNK, 1]: the
    cumulated log decay spread over a head's lanes, so that
    ``_against_state`` and every later stage read what they read of a
    decay a channel, and the pairs' mask in the factors' place."""
    gc = _cumulated(jnp.broadcast_to(g, q.shape))
    yield
    mask = _mask(g)
    yield
    a, b = _masked(q, k, mask, dtype)
    yield
    return dict(
        q=q, k=k, v=v, beta=beta, state=state, gc=gc, mask=mask,
        a=a, b=b, **_against_state(q, k, gc, state, dtype),
    )


@jax.jit
def _split(n):
    """A strictly lower ``n`` as ``I`` less its blocks of ``SUB`` on
    the diagonal, those blocks, and the rest."""
    row, col = _places(n.shape)
    diagonal = row // SUB == col // SUB
    near = jnp.where(diagonal, n, 0.0)
    return _eye(n.shape) - near, near, jnp.where(diagonal, 0.0, n)


@jax.jit
def _times(a, b):
    return _dot(a, b, _NN)


@jax.jit
def _times_one_plus(a, b):
    """``a (I + b)``."""
    return _dot(a, _eye(b.shape) + b, _NN)


@jax.jit
def _one_minus(m):
    return _eye(m.shape) - m


def _inverse(n):
    """``(I + n)^-1`` of a strictly lower-triangular ``n``, in float32
    products; a generator (``_in_turn``). ``n`` is one head's [CHUNK,
    CHUNK] or two heads' on the diagonal of one matrix, whose inverse
    has the two heads' on its diagonal: a product of such matrices is
    such a matrix, and the zero blocks add exact zeros, so a head's
    numbers are what they are by itself. A nilpotent ``m`` has ``(I +
    m)^-1 = (I - m)(I + m^2)(I + m^4)...``; taken on all of ``n`` at
    once the high powers of keys that resemble each other grow large
    before they cancel. So first the blocks of ``SUB`` on the diagonal,
    whose sixteenth power is zero (``near``), then what is left of ``I
    + n`` once they are divided out: ``I + near n_off``, whose blocks
    lie strictly below the diagonal and whose ``CHUNK / SUB``-th
    power is zero."""
    def inverse(inv, power, order):
        for _ in range(order.bit_length() - 2):
            power = _times(power, power)
            yield
            inv = _times_one_plus(inv, power)
            yield
        return inv

    inv, on_diagonal, off_diagonal = _split(n)
    near = yield from inverse(inv, on_diagonal, SUB)
    far = _times(near, off_diagonal)
    yield
    inv = yield from inverse(_one_minus(far), far, CHUNK // SUB)
    return _times(inv, near)


def _diagonal(blocks):
    """One or two square blocks as the blocks on the diagonal of one
    matrix, zeros beside them."""
    if len(blocks) == 1:
        return blocks[0]
    one, two = blocks
    zero = jnp.zeros_like(one)
    return jnp.concatenate([
        jnp.concatenate([one, zero], axis=1),
        jnp.concatenate([zero, two], axis=1),
    ], axis=0)


def _paired(heads) -> int:
    """Heads whose inverse is taken as one matrix, of a grid step's
    ``heads``: two; one where they are an odd number."""
    return 1 if heads % 2 else 2


def _pairs(xs):
    """``xs``, a grid step's heads', ``_paired`` at a time."""
    size = _paired(len(xs))
    return [xs[at:at + size] for at in range(0, len(xs), size)]


def _across(invs, rights, dims):
    """The float32 product of each pair's matrix of ``invs`` with its
    heads' ``rights`` [CHUNK, d], one under the other; a list as
    ``rights`` is."""
    out = []
    for inv, pair in zip(invs, _pairs(rights)):
        both = _dot(inv, jnp.concatenate(pair, axis=0), dims)
        out += [both[at:at + CHUNK] for at in range(0, both.shape[0], CHUNK)]
    return out


def _lanes(j, width=HEAD):
    """Head ``j``'s columns of a grid step's block of rows, a head
    ``width`` of them."""
    return slice(j * width, (j + 1) * width)


def _values(ref, heads):
    """A head's columns of a block of ``heads`` heads' values."""
    return ref.shape[1] // heads


def _parts(refs, beta_ref, states, dtype):
    """For each head of a grid step the chunked form's parts ahead of
    the inverse. ``refs`` are the blocks of ``q, k, v, g``, a head a
    lane tile of each (of ``v`` whole tiles), and ``states`` holds the
    heads' entry states; ``g`` in ``beta``'s shape is one decay a
    head."""
    q_ref, k_ref, v_ref, g_ref = refs
    heads = states.shape[0]
    wide = _values(v_ref, heads)
    if g_ref.shape == beta_ref.shape:
        return _in_turn(
            _before_a_head(
                q_ref[:, _lanes(j)].astype(F32),
                k_ref[:, _lanes(j)].astype(F32),
                v_ref[:, _lanes(j, wide)].astype(F32),
                g_ref[j], beta_ref[j], states[j], dtype)
            for j in range(heads)
        )
    return _in_turn(
        _before(*(ref[:, _lanes(j)].astype(F32) for ref in refs),
                beta_ref[j], states[j], dtype)
        for j in range(heads)
    )


def _solved(cs):
    """Each pair of heads' inverse, and into each head's parts its
    ``w``: what the forward solves of a chunk, and keeps for the
    backward."""
    invs = _in_turn(
        _inverse(_diagonal([c["beta"] * c["a"] for c in pair]))
        for pair in _pairs(cs)
    )
    ws = _across(
        invs, [c["beta"] * (c["v"] - c["held"]) for c in cs], _NN)
    for c, w in zip(cs, ws):
        c["w"] = w
    return invs


def _kept(cs, inv_ref, w_ref):
    """``_solved`` as the backward has it: read from what the forward
    kept."""
    wide = _values(w_ref, len(cs))
    for j, c in enumerate(cs):
        c["w"] = w_ref[:, _lanes(j, wide)]
    return [inv_ref[pair] for pair in range(inv_ref.shape[0])]


@_stage
def _result(c, dtype):
    """A chunk's ``o`` ahead of its scale, and the state it leaves."""
    entry = c["state"]
    return (
        _dot(c["qg"], entry, _NT, dtype) + _dot(c["b"], c["w"], _NN, dtype),
        entry * jnp.exp(c["last"]) + _dot(c["w"], c["kd"], _TN, dtype),
    )


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest,
                scale, dtype):
    *kept, state = rest  # nothing, or the states', inverses' and w's

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    cs = _parts((q_ref, k_ref, v_ref, g_ref), beta_ref, state, dtype)
    invs = _solved(cs)
    if kept:
        states_ref, inv_ref, w_ref = kept
        for pair, inv in enumerate(invs):
            inv_ref[pair] = inv
    wide = _values(o_ref, len(cs))
    for j, c in enumerate(cs):
        if kept:
            states_ref[j] = c["state"]
            w_ref[:, _lanes(j, wide)] = c["w"]
        o, state[j] = _result(c, dtype)
        o_ref[:, _lanes(j, wide)] = (scale * o).astype(o_ref.dtype)


@_stage
def _cotangents(c, do, after, dtype):
    """What ``do`` and the cotangent ``after`` of the state a chunk
    leaves hand to ``w`` and to the parts that ``w`` does not pass
    through."""
    return dict(
        dw=_dot(c["b"], do, _TN, dtype) + _dot(c["kd"], after, _NT, dtype),
        db=jnp.where(
            _triangle(False), _dot(do, c["w"], _NT, dtype), 0.0),
        dqg=_dot(do, c["state"], _NN, dtype),
        dkd=_dot(c["w"], after, _NN, dtype),
    )


@_stage
def _through_the_inverse(c, dr, dtype):
    """From ``dr``, the cotangent of the solved system's right side:
    ``dn`` of ``Diag(beta) A``, ``dbeta``, ``dv`` and ``dkg``."""
    dn = jnp.where(_triangle(True), -_dot(dr, c["w"], _NT), 0.0)
    dv = c["beta"] * dr
    return dict(
        dn=dn, dv=dv, dkg=-_dot(dv, c["state"], _NN, dtype),
        dbeta=jnp.sum(dn * c["a"], axis=1, keepdims=True)
        + jnp.sum(dr * (c["v"] - c["held"]), axis=1, keepdims=True),
    )


@_stage
def _before_state(c, d, do, after, dtype):
    """The cotangent of a chunk's entry state."""
    return (
        _dot(do, c["qg"], _TN, dtype) + after * jnp.exp(c["last"])
        - _dot(d["dv"], c["kg"], _TN, dtype)
    )


@_stage
def _to_operands(c, d, after, dtype):
    """``dq``, ``dk`` and ``dg`` of a chunk from the cotangents of its
    parts."""
    q, k, kd = c["q"], c["k"], c["kd"]
    da, db = c["beta"] * d["dn"], d["db"]
    dqg, dkg, dkd = d["dqg"], d["dkg"], d["dkd"]
    dq = dqg * c["gamma"]
    dk = dkg * c["gamma"] + dkd * jnp.exp(c["last"] - c["gc"])
    dgc = dqg * c["qg"] + dkg * c["kg"] - dkd * kd
    dlast = (
        jnp.sum(c["state"] * after, axis=0, keepdims=True)
        * jnp.exp(c["last"])
        + jnp.sum(dkd * kd, axis=0, keepdims=True)
    )
    dq_rows, dk_rows, dgc_rows = [], [], []
    for at, (e_row, e_col) in enumerate(c["factors"]):
        rows = _rows(at)
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
    dgc = dgc + jnp.concatenate(dgc_rows, axis=0)
    # a position's log decay is in every later row's cumulated one,
    # and the chunk's last row's in the state that leaves it
    return (
        dq + jnp.concatenate(dq_rows, axis=0),
        dk + jnp.concatenate(dk_rows, axis=0),
        _dot(_ones(_triangle(False, upper=True)), dgc, _NN) + dlast,
    )


@_stage
def _to_operands_a_head(c, d, after, dtype):
    """``_to_operands`` with one decay a head: ``dq``, ``dk`` and
    ``dg`` [CHUNK, 1]. The pairs' decay is the mask, so ``A``'s and
    ``B``'s cotangents go to ``K K^T`` and ``Q K^T`` through it, and to
    the log decay as each pair's ``dA A + dB B``: to the row's
    cumulated sum with one sign, to the column's with the other."""
    q, k, kd, mask = c["q"], c["k"], c["kd"], c["mask"]
    da, db = c["beta"] * d["dn"], d["db"]
    dqg, dkg, dkd = d["dqg"], d["dkg"], d["dkd"]
    to_kk, to_qk = da * mask, db * mask
    dq = dqg * c["gamma"] + _dot(to_qk, k, _NN, dtype)
    dk = (
        dkg * c["gamma"] + dkd * jnp.exp(c["last"] - c["gc"])
        + _dot(to_kk, k, _NN, dtype) + _dot(to_kk, k, _TN, dtype)
        + _dot(to_qk, q, _TN, dtype)
    )
    pairs = da * c["a"] + db * c["b"]
    dgc = (
        jnp.sum(dqg * c["qg"] + dkg * c["kg"] - dkd * kd,
                axis=1, keepdims=True)
        + jnp.sum(pairs, axis=1, keepdims=True)
        - _dot(pairs, jnp.ones((CHUNK, HEAD), F32), _TN)[:, :1]
    )
    last = c["last"][:, :1]
    dlast = (
        jnp.sum(jnp.sum(c["state"] * after, axis=1, keepdims=True),
                axis=0, keepdims=True) * jnp.exp(last)
        + jnp.sum(jnp.sum(dkd * kd, axis=1, keepdims=True),
                  axis=0, keepdims=True)
    )
    # as ``_to_operands``' last line, on one column spread over a tile
    dg = _dot(
        _ones(_triangle(False, upper=True)),
        jnp.broadcast_to(dgc, (CHUNK, HEAD)), _NN)[:, :1] + dlast
    return dq, dk, dg


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, inv_ref,
                w_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                dstate, *, scale, dtype):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    heads = range(dstate.shape[0])
    wide = _values(do_ref, len(heads))
    a_head = g_ref.shape == beta_ref.shape
    cs = _parts((q_ref, k_ref, v_ref, g_ref), beta_ref, states_ref, dtype)
    invs = _kept(cs, inv_ref, w_ref)
    dos = [scale * do_ref[:, _lanes(j, wide)].astype(F32) for j in heads]
    afters = [dstate[j] for j in heads]
    ds = [_cotangents(c, do, after, dtype)
          for c, do, after in zip(cs, dos, afters)]
    drs = _across(invs, [d["dw"] for d in ds], _TN)

    def rest_of(j, c, d, do, after, dr):
        d.update(_through_the_inverse(c, dr, dtype))
        yield
        dbeta_ref[j] = d["dbeta"]
        dv_ref[:, _lanes(j, wide)] = d["dv"].astype(dv_ref.dtype)
        dstate[j] = _before_state(c, d, do, after, dtype)
        yield
        dq, dk, dg = (
            _to_operands_a_head if a_head else _to_operands
        )(c, d, after, dtype)
        dq_ref[:, _lanes(j)] = dq.astype(dq_ref.dtype)
        dk_ref[:, _lanes(j)] = dk.astype(dk_ref.dtype)
        if a_head:
            dg_ref[j] = dg
        else:
            dg_ref[:, _lanes(j)] = dg.astype(dg_ref.dtype)

    _in_turn(rest_of(*of) for of in zip(heads, cs, ds, dos, afters, drs))


def _specs(chunks, together, reverse, values=HEAD):
    """Block specs of a ``[batch, seq, heads x d]`` operand of keys
    and of one of ``values`` columns a head, of ``beta`` as ``[batch,
    heads, seq, 1]``, of the entry states ``[batch, heads, chunks,
    values, d]`` and of the pairs' inverses (``_kept_shapes``), for
    the grid ``(batch, heads / together, chunk)``: a block is
    ``together`` adjacent heads' part of each; ``reverse`` walks the
    chunks from the last."""
    def at(n):
        return chunks - 1 - n if reverse else n

    paired = _paired(together)
    wide, wide_values = (pl.BlockSpec(
        (None, CHUNK, together * d), lambda b, h, n: (b, at(n), h)
    ) for d in (HEAD, values))
    beta = pl.BlockSpec(
        (None, together, CHUNK, 1), lambda b, h, n: (b, h, at(n), 0))
    states = pl.BlockSpec(
        (None, together, None, values, HEAD),
        lambda b, h, n: (b, h, at(n), 0, 0))
    invs = pl.BlockSpec(
        (None, together // paired, None, paired * CHUNK, paired * CHUNK),
        lambda b, h, n: (b, h, at(n), 0, 0))
    return wide, wide_values, beta, states, invs


def _kept_shapes(batch, seq, heads, values=HEAD):
    """What the forward keeps of every chunk for the backward, beside
    ``o``: the heads' entry states ``[values, keys]``, each pair's
    inverse as ``_inverse`` returns it (two heads' on the diagonal of
    one matrix; one head's where a grid step takes one), and the
    heads' ``w`` as rows. All float32: the backward's products see the
    bits they would make."""
    chunks = seq // CHUNK
    paired = _paired(heads_a_step(heads))
    return [
        jax.ShapeDtypeStruct((batch, heads, chunks, values, HEAD), F32),
        jax.ShapeDtypeStruct(
            (batch, heads // paired, chunks, paired * CHUNK, paired * CHUNK),
            F32),
        jax.ShapeDtypeStruct((batch, seq, heads * values), F32),
    ]


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )


def _forward(q, k, v, g, beta, heads, keep_states, scale=HEAD ** -0.5):
    """``g`` as rows, a decay a channel, or as ``beta`` is, one a
    head; ``v`` a head of whole lane tiles."""
    batch, seq, _ = q.shape
    chunks = seq // CHUNK
    together = heads_a_step(heads, forward=True)
    values = v.shape[2] // heads
    wide, wide_values, beta_spec, states_spec, invs_spec = _specs(
        chunks, together, False, values)
    g_spec = beta_spec if g.shape == beta.shape else wide
    out_specs = [wide_values]
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)]
    if keep_states:
        out_specs += [states_spec, invs_spec, wide_values]
        out_shape += _kept_shapes(batch, seq, heads, values)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, dtype=q.dtype),
        grid=(batch, heads // together, chunks),
        in_specs=[wide, wide, wide_values, g_spec, beta_spec],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((together, values, HEAD), F32)],
        compiler_params=_params(), interpret=_interpret(),
    )(q, k, v, g, beta)
    return out if keep_states else out[0]


def _backward(q, k, v, g, beta, kept, do, heads, scale=HEAD ** -0.5):
    batch, seq, _ = q.shape
    chunks = seq // CHUNK
    together = heads_a_step(heads)
    values = v.shape[2] // heads
    wide, wide_values, beta_spec, states_spec, invs_spec = _specs(
        chunks, together, True, values)
    g_spec = beta_spec if g.shape == beta.shape else wide
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, dtype=q.dtype),
        grid=(batch, heads // together, chunks),
        in_specs=[wide, wide, wide_values, g_spec, beta_spec,
                  states_spec, invs_spec, wide_values, wide_values],
        out_specs=[wide, wide, wide_values, g_spec, beta_spec],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(g.shape, g.dtype),
            jax.ShapeDtypeStruct(beta.shape, beta.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((together, values, HEAD), F32)],
        compiler_params=_params(), interpret=_interpret(),
    )(q, k, v, g, beta, *kept, do)


def _beta_by_head(beta):
    """``[batch, seq, heads]`` as ``[batch, heads, seq, 1]``: a chunk's
    step sizes down the sublanes."""
    return jnp.swapaxes(beta, 1, 2)[..., None]


def _padded(x, heads, to):
    """Rows ``[batch, seq, heads x d]`` with every head's columns
    padded with zeros to ``to``."""
    batch, seq, width = x.shape
    d = width // heads
    if d == to:
        return x
    return jnp.pad(
        x.reshape(batch, seq, heads, d), ((0, 0),) * 3 + ((0, to - d),)
    ).reshape(batch, seq, heads * to)


def _cut(x, heads, d):
    """``_padded``'s inverse: every head's first ``d`` columns."""
    batch, seq, width = x.shape
    if width == heads * d:
        return x
    return x.reshape(batch, seq, heads, -1)[..., :d].reshape(
        batch, seq, heads * d)


def padded_values(dv):
    """A head's ``dv`` values in whole lane tiles."""
    return -(-dv // HEAD) * HEAD


def _a_head(q, k, v, g, beta, kept, do, keep_states):
    """``delta_rule`` with one decay a head, ``g`` [batch, seq,
    heads], on heads of ``dk`` keys by ``dv`` values: the same kernels
    on heads padded to whole lane tiles (the module's docstring says
    why that is exact and what it costs)."""
    heads = beta.shape[2]
    dk, dv = q.shape[2] // heads, v.shape[2] // heads
    wide = (
        _padded(q, heads, HEAD), _padded(k, heads, HEAD),
        _padded(v, heads, padded_values(dv)),
        _beta_by_head(g.astype(F32)), _beta_by_head(beta.astype(F32)),
    )
    scale = dk ** -0.5
    if do is None:
        out = _forward(*wide, heads, keep_states, scale)
        if keep_states:
            return _cut(out[0], heads, dv), tuple(out[1:])
        return _cut(out, heads, dv)
    grads = _backward(
        *wide, kept, _padded(do, heads, padded_values(dv)), heads, scale)

    def by_position(x, like):
        return jnp.swapaxes(x[..., 0], 1, 2).astype(like.dtype)

    return (
        *(_cut(x, heads, d) for x, d in zip(grads, (dk, dk, dv))),
        by_position(grads[3], g), by_position(grads[4], beta),
    )


@functools.partial(jax.jit, static_argnames=("keep_states",))
def delta_rule(q, k, v, g, beta, kept=None, do=None, keep_states=False):
    """On rows ``[batch, seq, heads x 128]`` and ``beta`` ``[batch,
    seq, heads]``: the forward kernel's ``o`` (with ``keep_states``
    also what the backward reads: the chunks' entry states, inverses
    and ``w``, ``_kept_shapes``), or with those three as ``kept`` and
    the result's cotangent ``do`` the backward kernel's five gradients,
    each in its operand's shape. With ``g`` in ``beta``'s shape, one
    decay a head, on rows of ``heads x dk`` keys and ``heads x dv``
    values (``_a_head``). One jitted name for both calls and both
    forms, which is what a device trace calls them."""
    if one_decay_a_head(k, g, beta):
        return _a_head(q, k, v, g, beta, kept, do, keep_states)
    heads = q.shape[2] // HEAD
    wide = (q, k, v, g.astype(F32), _beta_by_head(beta.astype(F32)))
    if do is None:
        out = _forward(*wide, heads, keep_states)
        return (out[0], tuple(out[1:])) if keep_states else out
    dq, dk, dv, dg, dbeta = _backward(*wide, kept, do, heads)
    return (
        dq, dk, dv, dg.astype(g.dtype),
        jnp.swapaxes(dbeta[..., 0], 1, 2).astype(beta.dtype),
    )


#: the labels of the two counters of calls: the form of decay
#: (``channel`` or ``head``) and a head's ``<keys>x<values>`` as the
#: caller handed them, ahead of any padding
CALL_LABELS = ("decay", "head")


def _record(folded, k, v, g, beta):
    """Say what was built, at trace time, for these operands: the
    gauges and counters of docs/TELEMETRY.md. Every Pallas call a step
    holds is counted once, by whether its caller held rows or heads
    that were ``folded``, under the form of decay and the head's two
    widths as labels."""
    from dlrover_tpu.telemetry.registry import counter, gauge

    heads = beta.shape[2]
    a_head = one_decay_a_head(k, g, beta)
    dk, dv = k.shape[2] // heads, v.shape[2] // heads
    values = padded_values(dv) if a_head else HEAD
    labels = dict(
        decay="head" if a_head else "channel", head=f"{dk}x{dv}")
    together = heads_a_step(heads)
    gauge(
        "delta_rule_chunk",
        "positions of one chunk of the gated delta rule's scan",
    ).set(CHUNK)
    gauge(
        "delta_rule_heads_per_step",
        "adjacent heads whose chunk one grid step of the gated delta "
        "rule's backward kernel takes (the forward's may take more)",
    ).set(together)
    gauge(
        "delta_rule_state_bytes",
        "bytes of the states, a grid step's heads', resident in VMEM "
        "through the gated delta rule's scan",
    ).set(together * values * HEAD * 4)
    gauge(
        "delta_rule_backward_kernels",
        "Pallas kernels of the gated delta rule's backward pass, beside "
        "the forward that keeps the chunks' entry states",
    ).set(BACKWARD_KERNELS)
    gauge(
        "delta_rule_backward_inverses",
        "inverses of a chunk's (I + Diag(beta) A) that the gated delta "
        "rule's backward kernel makes: 0, it reads the forward's",
    ).set(BACKWARD_INVERSES)
    gauge(
        "delta_rule_kept_bytes",
        "bytes a head's chunk of the gated delta rule keeps from the "
        "forward for its backward: the entry state, its share of the "
        "inverse and w, float32",
    ).set(sum(
        array.size * array.dtype.itemsize
        for array in _kept_shapes(1, CHUNK, together, values)) // together)
    if folded:
        counter(
            "delta_rule_folded_calls",
            "Pallas calls of the gated delta rule traced on operands "
            "that came as [batch, seq, heads, d] and were folded to "
            "rows: a relayout of each on the chip",
            CALL_LABELS,
        ).labels(**labels).inc()
    else:
        counter(
            "delta_rule_rows_calls",
            "Pallas calls of the gated delta rule traced on operands "
            "that came as rows [batch, seq, heads x d], as the kernels "
            "read them",
            CALL_LABELS,
        ).labels(**labels).inc()


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def delta_rule_tpu(q, k, v, g, beta, folded=False):
    """``delta_rule`` with its differentiation rule; ``folded`` says,
    for the record alone, that the caller held heads."""
    _record(folded, k, v, g, beta)
    return delta_rule(q, k, v, g, beta)


def _vjp_fwd(q, k, v, g, beta, folded):
    _record(folded, k, v, g, beta)
    o, kept = delta_rule(q, k, v, g, beta, keep_states=True)
    return o, (q, k, v, g, beta, kept)


def _vjp_bwd(folded, saved, do):
    *operands, kept = saved
    _record(folded, *operands[1:])
    return delta_rule(*operands, kept=kept, do=do)


delta_rule_tpu.defvjp(_vjp_fwd, _vjp_bwd)
