"""The selective scan of a Mamba-1 mixer: a state-space recurrence
whose decay is a number for every channel **and** state. Channel ``d``
of ``channels`` keeps ``n`` states ``h[d, :]``, from zero at a
sequence's start; every channel reads the one ``B_t`` and ``C_t`` of
its position::

    a_t[d, n] = exp(Delta_t[d] A[d, n])          # A < 0: a_t in (0, 1]
    h_t[d, n] = a_t[d, n] h_{t-1}[d, n] + Delta_t[d] x_t[d] B_t[n]
    o_t[d]    = sum_n h_t[d, n] C_t[n] + D[d] x_t[d]

What tells it from ops/ssd.py's scan (Mamba-2): there a head has one
scalar decay, so a chunk is four matrix products and one ``[128,
128]`` mask of decays; here ``a_t`` differs by channel and by state
(with one channel a head it would still be ``n`` decays a head), no
product on the MXU expresses a chunk, and the recurrence is walked
position by position, elementwise over ``[channels, n]``.

The decay is the exponential of ``Delta A`` itself, at most zero: no
clip, no floor, no division by a decay anywhere, forward or backward.
Below about -87.3 the float32 ``exp`` gives a denormal that the TPU
flushes, below -103.97 zero everywhere: ``a_t = 0`` then, the state
forgets what it held and keeps the position's own write, which is the
recurrence's limit and exact; its gradient through ``a_t`` is zero
alike.

The entry is ``selective_scan``, on rows: ``x`` and ``Delta`` [batch,
seq, channels] (``Delta`` past its softplus), ``B`` and ``C`` [batch,
seq, n], ``A`` [channels, n] (negative) and ``D`` [channels] to ``o``
in ``x``'s shape and dtype. The skip ``D x`` rides in the operator
(the kernels hold ``x`` anyway); the gate ``silu(z)`` of the mixer does
not: it is the caller's, one fused pass of XLA's ahead of the output
projection. On the TPU, where the shapes tile, the Pallas kernels of
ops/pallas/selective_scan.py (a forward that can keep the chunks'
entry states, one backward over them). Elsewhere ``selective_scan_plain``:
the recurrence walked position by position inside chunks under a
``lax.scan``, each chunk made again for its backward, so that no array
of ``[seq, channels, n]`` exists; differentiated by JAX. float32 inside
both, whatever the operands' dtype. A sequence is a row of the batch.
"""

import jax
import jax.numpy as jnp

#: positions of a chunk, in the plain path and in the kernels: what is
#: kept of a differentiated forward is a chunk's entry state
CHUNK = 64


def _use_pallas(x: jax.Array, B: jax.Array) -> bool:
    if jax.default_backend() != "tpu":
        return False
    from dlrover_tpu.ops.pallas.selective_scan import tiles_the_kernel

    return tiles_the_kernel(x.shape, B.shape)


def selective_scan_plain(x, delta, B, C, A, D, chunk: int = CHUNK):
    """The recurrence as it stands, in float32, rounded once. A
    sequence that is no whole number of chunks is padded with positions
    that leave the state as it is (``Delta`` 0). ``chunk`` is the
    tests' seam (a short sequence in several chunks); the program
    passes none."""
    b, s, d = x.shape
    n = B.shape[2]
    c = min(chunk, s)
    pad = -s % c
    f32 = jnp.float32
    A, D = A.astype(f32), D.astype(f32)

    def chunks(a):  # [chunks, c, batch, ...]: positions lead
        a = a.astype(f32)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(a.reshape(b, -1, c, a.shape[2]), 0, 2)

    def position(h, operands):  # h [b, d, n]
        x, delta, B, C = operands
        h = (jnp.exp(delta[..., None] * A) * h
             + (delta * x)[..., None] * B[:, None])
        return h, jnp.einsum("bdn,bn->bd", h, C) + D * x

    @jax.checkpoint
    def step(h, operands):
        return jax.lax.scan(position, h, operands)

    _, o = jax.lax.scan(
        step, jnp.zeros((b, d, n), f32),
        (chunks(x), chunks(delta), chunks(B), chunks(C)),
    )
    # [chunks, c, b, d] -> [b, s, d]
    o = jnp.moveaxis(o.reshape(-1, b, d), 0, 1)
    return o[:, :s].astype(x.dtype)


def _count(path: str):
    """Say, at trace time, which path a call of the entry took: the
    counters of docs/TELEMETRY.md."""
    from dlrover_tpu.telemetry.registry import counter

    counter(
        f"selective_scan_{path}_calls",
        f"calls of the selective scan traced on the {path} path",
    ).inc()


def selective_scan(x, delta, B, C, A, D):
    """The entry, on rows: ``x``, ``delta`` [batch, seq, channels],
    ``B``, ``C`` [batch, seq, n], ``A`` [channels, n], ``D``
    [channels] to ``o`` in ``x``'s shape and dtype. Differentiable in
    all six."""
    if not (x.ndim == 3 and delta.shape == x.shape and B.ndim == 3
            and B.shape == C.shape and B.shape[:2] == x.shape[:2]
            and A.shape == (x.shape[2], B.shape[2])
            and D.shape == (x.shape[2],)):
        raise ValueError(
            f"selective_scan: x {x.shape}, delta {delta.shape}, B "
            f"{B.shape}, C {C.shape}, A {A.shape}, D {D.shape}"
        )
    if _use_pallas(x, B):
        from dlrover_tpu.ops.pallas.selective_scan import (
            selective_scan_tpu,
        )

        _count("kernel")
        return selective_scan_tpu(x, delta, B, C, A, D)
    _count("plain")
    return selective_scan_plain(x, delta, B, C, A, D)
