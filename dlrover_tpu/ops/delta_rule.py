"""The gated delta rule: a linear-attention layer's recurrence, a
head at a time, in two forms of decay. With a decay of its own for
every key channel (Kimi Delta Attention), ``alpha_t = exp(g_t)`` in
(0, 1]^d and a step size ``beta_t``, the state ``S`` [d keys, d
values] starts at zero and::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(d)

The state forgets channel by channel, then the rank-one update takes
out what it holds along ``k_t`` and writes ``v_t`` there. With one
decay a head (Gated DeltaNet), ``alpha_t = exp(g_t)`` a number, the
same lines with ``alpha_t I`` for ``Diag(alpha_t)``, and the state
may be ``[dk keys, dv values]`` of two widths (``o_t = S_t^T q_t /
sqrt(dk)``). ``g``'s shape says which: ``k``'s, or ``beta``'s.

Computed in chunks of ``CHUNK`` positions. With ``G_t`` the log decay
cumulated from the chunk's start and ``w_t = beta_t (v_t - S_{t-1}^T
(alpha_t * k_t))`` what position t really writes::

    A[t, i] = sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c])      i < t
    B[t, i] = sum_c q_t[c] k_i[c] exp(G_t[c] - G_i[c])      i <= t
    (I + Diag(beta) A) W = beta * (V - (K * exp(G)) S_0)
    O = ((Q * exp(G)) S_0 + B W) / sqrt(d)
    S_C = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T W

so the sequential part is one unit lower-triangular system a chunk
and a [d, d] state handed from chunk to chunk. With one decay a head
``exp(G_t - G_i)`` leaves the sums over ``c``: one ``[CHUNK, CHUNK]``
mask a head on ``K K^T`` and ``Q K^T``, whatever the two widths (the
system and its inverse are ``[CHUNK, CHUNK]`` whatever ``dv``).

The entry is ``gated_delta_rule_rows``, on ``[batch, seq, heads x
d]``; ``gated_delta_rule`` folds ``[batch, seq, heads, d]`` to it.

On the TPU, where the shapes tile, the Pallas kernels of
ops/pallas/delta_rule.py (forward; backward over the chunks' entry
states that the forward keeps when it is differentiated), which are
exact while no step of a decay a channel forgets faster than
``G_FLOOR``: the entry sees to that. One decay a head has no floor on
either path: a pair's exponent is a sum of the steps between the two
positions, never positive and never factored. Elsewhere
``gated_delta_rule_plain``: the equations above under a ``lax.scan``
over chunks, every ``exp(G_t - G_i)`` taken pair by pair (exact at any
decay: no exponent is positive), the system solved by substitution,
differentiated by JAX. float32 inside both, whatever the operands'
dtype.
"""

import jax
import jax.numpy as jnp

#: positions of a chunk, in the plain path and in the kernels
CHUNK = 64
#: the least log decay a step that ``gated_delta_rule`` takes: a faster
#: one is taken as this, ``alpha`` of 4.5e-5 where it was smaller yet.
#: It bounds what a block of sixteen positions spans, which is what
#: lets the kernels factor a pair's decay into a row's and a column's
#: part exactly (ops/pallas/delta_rule.py)
G_FLOOR = -10.0


def one_decay_a_head(k, g, beta) -> bool:
    """Which form of decay ``g``'s shape says: ``beta``'s, one a head;
    ``k``'s, one a channel (a head one column wide is both, and is
    taken as a channel's)."""
    return g.shape == beta.shape and g.shape != k.shape


def _use_pallas(q: jax.Array, heads: int) -> bool:
    if jax.default_backend() != "tpu":
        return False
    from dlrover_tpu.ops.pallas.delta_rule import tiles_the_kernel

    return tiles_the_kernel(q.shape, heads)


def _use_pallas_a_head(q: jax.Array, v: jax.Array, heads: int) -> bool:
    """``_use_pallas`` for one decay a head, whose heads have two
    widths."""
    if jax.default_backend() != "tpu":
        return False
    from dlrover_tpu.ops.pallas.delta_rule import tiles_the_kernel

    return tiles_the_kernel(q.shape, heads, v.shape)


def _steps_between(g, lower, strictly):
    """``sum of g_j over i < j <= t`` [..., t, i] of a chunk's log
    decay a head ``g`` [..., c] where ``i <= t`` (0 elsewhere): a
    pair's exponent as the sum of the steps between the two, not the
    difference of two cumulated sums, which at a decay of 30 a step
    would round what a slow step after a fast one keeps."""
    between = (lower[:, None, :] & strictly.T[None, :, :]).astype(g.dtype)
    return jnp.einsum(
        "...j,tij->...ti", g, between,
        precision=jax.lax.Precision.HIGHEST,
    )


def gated_delta_rule_plain(q, k, v, g, beta, chunk: int = CHUNK):
    """The chunked equations as they stand, in float32, rounded once.
    ``g`` [b, s, h, d] a decay a channel, or [b, s, h] one a head,
    with ``v`` then [b, s, h, dv] of any width. A sequence that is no
    whole number of chunks is padded with positions that leave the
    state as it is (``k`` 0, ``g`` 0, ``beta`` 0)."""
    b, s, h, d = q.shape
    a_head = g.ndim == 3
    c = min(chunk, s)
    pad = -s % c
    f32 = jnp.float32

    def chunks(x):
        x = x.astype(f32)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        # [chunks, batch, heads, c, ...]
        x = x.reshape(b, -1, c, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

    lower = jnp.tril(jnp.ones((c, c), bool))
    strictly = jnp.tril(jnp.ones((c, c), bool), -1)
    eye = jnp.eye(c, dtype=f32)

    @jax.checkpoint
    def step(state, x):  # state [b, h, keys, values]
        q, k, v, g, beta = x
        beta = beta[..., None]
        if a_head:
            # one [t, i] mask a head: exp of the steps between
            decay = jnp.exp(_steps_between(g, lower, strictly))
            a = jnp.where(strictly, jnp.einsum(
                "bhtc,bhic->bhti", k, k) * decay, 0.0)
            bb = jnp.where(lower, jnp.einsum(
                "bhtc,bhic->bhti", q, k) * decay, 0.0)
            gc = jnp.cumsum(g, axis=-1)[..., None]  # [b, h, t, 1]
            to_end = decay[..., -1, :, None]  # the steps after t
        else:
            gc = jnp.cumsum(g, axis=-2)
            # exp(G_t - G_i) [b, h, t, i, c]: no positive exponent
            # where i <= t, and none taken where i > t (masked below)
            decay = jnp.exp(jnp.where(
                lower[:, :, None],
                gc[..., :, None, :] - gc[..., None, :, :], 0.0,
            ))
            a = jnp.where(strictly, jnp.einsum(
                "bhtc,bhic,bhtic->bhti", k, k, decay), 0.0)
            bb = jnp.where(lower, jnp.einsum(
                "bhtc,bhic,bhtic->bhti", q, k, decay), 0.0)
            to_end = jnp.exp(gc[..., -1:, :] - gc)
        gamma = jnp.exp(gc)
        rhs = beta * (v - jnp.einsum("bhtc,bhcv->bhtv", k * gamma, state))
        w = jax.scipy.linalg.solve_triangular(
            eye + beta * a, rhs, lower=True, unit_diagonal=True
        )
        o = (jnp.einsum("bhtc,bhcv->bhtv", q * gamma, state)
             + jnp.einsum("bhti,bhiv->bhtv", bb, w))
        last = gc[..., -1:, :]
        state = (
            jnp.swapaxes(jnp.exp(last), -1, -2) * state
            + jnp.einsum("bhtc,bhtv->bhcv", k * to_end, w)
        )
        return state, o

    xs = tuple(chunks(x) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, d, v.shape[-1]), f32), xs)
    # [chunks, b, h, c, values] -> [b, s, h, values]
    o = jnp.moveaxis(jnp.moveaxis(o, 3, 2), 0, 1).reshape(b, s + pad, h, -1)
    return (o[:, :s] * d ** -0.5).astype(v.dtype)


def gated_delta_rule_rows(q, k, v, g, beta, heads: int, folded=False):
    """The entry, on rows: ``q, k`` [batch, seq, heads x dk], ``v``
    [batch, seq, heads x dv], ``g`` the log of the decay, float32, at
    most 0: the same shape as ``k``, a decay a channel, or as
    ``beta``, one a head; ``beta`` [batch, seq, heads]; to ``o`` in
    ``v``'s shape and dtype. A head is its columns of a row, side by
    side, as a projection writes them and the kernels read them.
    Differentiable in all five. A sequence is a row of the batch: the
    state starts at zero at its first position. A decay a channel has
    ``dv = dk`` and a floor: a log decay under ``G_FLOOR`` is taken as
    ``G_FLOOR``, on either path: what a channel keeps of its state
    over such a step is then 4.5e-5 and not less, and ``g`` there gets
    no gradient. One decay a head takes any two widths and has no
    floor. ``folded`` is ``gated_delta_rule``'s to say, for the
    kernels' record."""
    a_head = one_decay_a_head(k, g, beta)
    if not (q.ndim == 3 and q.shape == k.shape
            and (a_head or g.shape == k.shape == v.shape)
            and v.shape[:2] == q.shape[:2]
            and beta.shape == (*q.shape[:2], heads)
            and q.shape[2] % heads == v.shape[2] % heads == 0):
        raise ValueError(
            f"gated_delta_rule: q {q.shape}, k {k.shape}, v {v.shape}, "
            f"g {g.shape}, beta {beta.shape} in rows of {heads} heads"
        )
    if not a_head:
        g = jnp.maximum(g, G_FLOOR)
    if (_use_pallas_a_head(q, v, heads) if a_head
            else _use_pallas(q, heads)):
        from dlrover_tpu.ops.pallas.delta_rule import delta_rule_tpu

        return delta_rule_tpu(q, k, v, g, beta, folded)

    def apart(x):
        return x.reshape(*x.shape[:2], heads, -1)

    return gated_delta_rule_plain(
        apart(q), apart(k), apart(v), g if a_head else apart(g), beta
    ).reshape(v.shape)


def gated_delta_rule(q, k, v, g, beta):
    """``gated_delta_rule_rows`` for a caller that holds heads: ``q,
    k`` [batch, seq, heads, dk], ``v`` [batch, seq, heads, dv], ``g``
    as ``k`` (a decay a channel) or as ``beta`` [batch, seq, heads]
    (one a head) to ``o`` [batch, seq, heads, dv] in ``v``'s dtype.
    Folded to rows here and nowhere else: on the chip that is a pass
    over each operand and over ``o``, which the model does not pay (it
    holds rows)."""
    if not (q.ndim == 4 and q.shape == k.shape
            and g.shape in (k.shape, beta.shape)
            and v.shape[:3] == q.shape[:3]):
        raise ValueError(
            f"gated_delta_rule: q {q.shape}, k {k.shape}, v {v.shape}, "
            f"g {g.shape}, beta {beta.shape}"
        )

    def rows(x):
        return x.reshape(*x.shape[:2], -1)

    return gated_delta_rule_rows(
        rows(q), rows(k), rows(v), rows(g) if g.ndim == 4 else g, beta,
        q.shape[2], folded=True,
    ).reshape(v.shape)
