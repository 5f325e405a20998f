"""The state-space scan of a Mamba-2 layer (SSD: a selective state
space with one scalar decay a head). Head ``h`` of ``heads`` reads the
``B`` and ``C`` of its group ``h // (heads / groups)``; its state
``S`` [p values, n states] starts at zero and::

    a_t = exp(A_h Delta_t)                      # A_h < 0: a_t in (0, 1]
    S_t = a_t S_{t-1} + Delta_t x_t B_t^T
    o_t = S_t C_t + D_h x_t

No erase term and no inverse: the write is the plain outer product,
which is what tells it from the delta rule (ops/delta_rule.py), and
what makes a chunk four products and one mask. With ``cum_t`` the log
decay ``A_h Delta`` summed from the chunk's start to t (inclusive)::

    L[t, s] = exp(cum_t - cum_s)   for s <= t, 0 above
    Y = ((C B^T) * L) (Delta x) + exp(cum) * (C S_in^T) + D x
    S_out = exp(cum_last) S_in + ((Delta x) * exp(cum_last - cum))^T B

A decay between two positions is the exponential of a difference of
cumulative sums, masked before it is exponentiated, never a product of
two exponentials: every exponent that is taken is at most zero, so
nothing is clipped and there is no floor, at any decay.

The entry is ``ssd_scan``, on rows: ``x`` [batch, seq, heads x p] as
the convolution wrote it, ``B`` and ``C`` [batch, seq, groups x n],
``Delta`` [batch, seq, heads] and ``A``, ``D`` [heads] in float32. On
the TPU, where the shapes tile, the Pallas kernels of
ops/pallas/ssd.py (forward; backward over the chunks' entry states the
forward keeps when it is differentiated). Elsewhere ``ssd_plain``: the
equations above under a ``lax.scan`` over chunks, differentiated by
JAX. float32 inside both, whatever the operands' dtype. A sequence is
a row of the batch: the state starts at zero at its first position.

Linear attention with a fixed decay a head (a lightning layer of
``models/llama.py``: ``S_t = exp(-m_h) S_{t-1} + k_t v_t^T``, ``o_t =
S_t^T q_t / sqrt(d)``) is this scan in a regime of its own, and is
called, not copied: ``x = v``, ``B = k``, ``C = q / sqrt(d)``,
``Delta = 1``, ``A_h = -m_h``, ``D = 0``, one head a group (``heads =
groups``), ``p = n = d``. The kernels take it where ``d`` is a lane
tile's 128 (``tiles_the_kernel``; a grid step is then one head, its
state [128, 128]); the constant step and the zero skip are read and
multiplied like any other, which a leaner entry could spare (PERF.md
section 7).
"""

import jax
import jax.numpy as jnp

#: positions of a chunk, in the plain path and in the kernels
CHUNK = 128


def _use_pallas(x: jax.Array, B: jax.Array, heads: int, groups: int) -> bool:
    if jax.default_backend() != "tpu":
        return False
    from dlrover_tpu.ops.pallas.ssd import tiles_the_kernel

    return tiles_the_kernel(x.shape, B.shape, heads, groups)


def ssd_plain(x, B, C, dt, A, D, chunk: int = CHUNK):
    """The chunked equations as they stand, in float32, rounded once:
    ``x`` [b, s, heads, p], ``B``, ``C`` [b, s, groups, n], ``dt`` [b,
    s, heads], ``A``, ``D`` [heads]. A sequence that is no whole number
    of chunks is padded with positions that leave the state as it is
    (``dt`` 0)."""
    b, s, heads, p = x.shape
    groups, n = B.shape[2:]
    c = min(chunk, s)
    pad = -s % c
    f32 = jnp.float32

    def chunks(a, to_heads=False):
        a = a.astype(f32)
        if to_heads:  # a head reads its group's
            a = jnp.repeat(a, heads // groups, axis=2)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        # [chunks, batch, c, ...]
        return jnp.moveaxis(a.reshape(b, -1, c, *a.shape[2:]), 1, 0)

    lower = jnp.tril(jnp.ones((c, c), bool))[None, :, :, None]
    A, D = A.astype(f32), D.astype(f32)

    @jax.checkpoint
    def step(state, operands):  # state [b, heads, p, n]
        x, B, C, dt = operands
        cum = jnp.cumsum(dt * A, axis=1)  # [b, c, heads], at most 0
        # exp(cum_t - cum_s) [b, t, s, heads]: masked, then taken
        decay = jnp.exp(jnp.where(
            lower, cum[:, :, None] - cum[:, None, :], -jnp.inf
        ))
        u = dt[..., None] * x
        y = (
            jnp.einsum("bthn,bshn,btsh,bshp->bthp", C, B, decay, u)
            + jnp.exp(cum)[..., None]
            * jnp.einsum("bthn,bhpn->bthp", C, state)
            + D[:, None] * x
        )
        last = cum[:, -1:]  # [b, 1, heads]
        state = (
            jnp.exp(last[:, 0])[..., None, None] * state
            + jnp.einsum(
                "bthp,bthn->bhpn", u * jnp.exp(last - cum)[..., None], B)
        )
        return state, y

    xs = (chunks(x), chunks(B, True), chunks(C, True), chunks(dt))
    _, y = jax.lax.scan(step, jnp.zeros((b, heads, p, n), f32), xs)
    # [chunks, b, c, heads, p] -> [b, s, heads, p]
    y = jnp.moveaxis(y, 0, 1).reshape(b, s + pad, heads, p)
    return y[:, :s].astype(x.dtype)


def _count(path: str):
    """Say, at trace time, which path a call of the entry took: the
    counters of docs/TELEMETRY.md."""
    from dlrover_tpu.telemetry.registry import counter

    counter(
        f"ssd_{path}_calls",
        f"calls of the state-space scan traced on the {path} path",
    ).inc()


def ssd_scan(x, B, C, dt, A, D, heads: int, groups: int,
             chunk: int = CHUNK):
    """The entry, on rows: ``x`` [batch, seq, heads x p], ``B`` and
    ``C`` [batch, seq, groups x n], ``dt`` [batch, seq, heads] (the
    step ``Delta``, past its softplus) and ``A`` (negative), ``D``
    [heads] in float32, to ``o`` in ``x``'s shape and dtype.
    Differentiable in all six. ``chunk``: the positions of a chunk
    (the result does not depend on it); the kernels' is ``CHUNK``,
    and another takes the plain path."""
    if not (x.ndim == 3 and B.shape == C.shape
            and B.shape[:2] == x.shape[:2]
            and dt.shape == (*x.shape[:2], heads)
            and A.shape == D.shape == (heads,)
            and heads % groups == 0
            and x.shape[2] % heads == B.shape[2] % groups == 0):
        raise ValueError(
            f"ssd_scan: x {x.shape}, B {B.shape}, C {C.shape}, dt "
            f"{dt.shape}, A {A.shape}, D {D.shape} in rows of {heads} "
            f"heads in {groups} groups"
        )
    if chunk == CHUNK and _use_pallas(x, B, heads, groups):
        from dlrover_tpu.ops.pallas.ssd import ssd_tpu

        _count("kernel")
        return ssd_tpu(x, B, C, dt, A, D, groups)
    _count("plain")

    def apart(a, by):
        return a.reshape(*a.shape[:2], by, -1)

    return ssd_plain(
        apart(x, heads), apart(B, groups), apart(C, groups), dt, A, D,
        chunk,
    ).reshape(x.shape)
