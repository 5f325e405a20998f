"""Operations and bytes, computed from shapes. The yardstick's own:
a later PR that claims a gain cannot change what a token costs.

Every function takes a configuration as its file holds it (the
source's own key names) and never imports the program. What differs
from family to family is in ``families/<family>.py``, found by the
configuration's ``family``; nothing here names one.

Conventions, stated once:

- A multiply-add is 2 operations. The backward pass of a matrix
  multiplication costs twice its forward pass, so a training step is
  3 x the forward operations. Recomputed operations (remat) are not
  counted: they are not required by the mathematics.
- Attention is counted as causal: position i attends to i + 1 keys,
  so a score matrix costs s * s * d operations (half of 2 * s * s * d).
  The conventional count (PaLM, and ``models/*.flops_per_token``)
  takes the full square; the causal count is what the algorithm needs
  and gives the lower utilisation.
- The embedding lookup is a gather, not a matrix multiplication: 0.
  The output head is one, tied or not.
"""

import functools

from yardstick import cells


def _family_first(dense):
    """``dense``, unless the configuration's family file defines a
    function of the same name: a family whose layers are windowed,
    latent or linear counts them itself."""

    @functools.wraps(dense)
    def count(config, *args):
        return getattr(
            cells.family_module(config), dense.__name__, dense
        )(config, *args)

    return count


def shape(config):
    """The sizes the count needs, under one set of names for every
    family: ``hidden``, ``ffn``, ``layers``, ``heads``, ``kv_heads``,
    ``head_dim``, ``vocab``."""
    return cells.family_module(config).shape(config)


def matmul_params(config):
    """Weights that a token is multiplied by in one forward pass:
    the family's own count (``dense_matmul_params`` of its shape for
    a dense block; the router and the experts a token is routed to,
    not all of them, for a sparse one)."""
    return cells.family_module(config).matmul_params(config)


def dense_matmul_params(s):
    """A dense decoder's ``matmul_params`` from its ``shape``, which
    then also says how many matrices its feed-forward has
    (``ffn_matrices``)."""
    h, d = s["hidden"], s["head_dim"]
    per_layer = (
        h * s["heads"] * d          # q
        + 2 * h * s["kv_heads"] * d  # k, v
        + s["heads"] * d * h        # o
        + s["ffn_matrices"] * h * s["ffn"]
    )
    return s["layers"] * per_layer + h * s["vocab"]


@_family_first
def attention_forward_flops_per_token(config, seq):
    """Scores and weighted values, causal, all layers: each of the
    two products costs ``seq * head_dim`` operations a token and
    head (2 * seq * head_dim for the full square, half of it seen)."""
    s = shape(config)
    return 2.0 * s["layers"] * s["heads"] * s["head_dim"] * seq


@_family_first
def train_flops_per_token(config, seq):
    """Forward and backward, no recomputation."""
    forward = (
        2.0 * matmul_params(config)
        + attention_forward_flops_per_token(config, seq)
    )
    return 3.0 * forward


@_family_first
def attention_kernel_step(config, sequences, seq):
    """What the attention kernels of one training step must do for
    ``sequences`` sequences on one chip, all layers: ``(flops,
    bytes)``.

    Operations: forward has 2 products (q k^T, p v); backward needs 5
    (recompute q k^T, then dv, dp, dq, dk): 7 causal products of
    ``seq * seq * head_dim`` operations a head. A backward split into
    a dq and a dkv kernel recomputes more than that; the surplus is
    the implementation's, not the algorithm's.

    Bytes: every operand read once and every result written once, in
    bf16: forward reads q, k, v and writes o; backward reads q, k, v,
    o, do and writes dq, dk, dv. The log-sum-exp rows are left out
    (1/head_dim of q).
    """
    s = shape(config)
    d = s["head_dim"]
    flops = 7.0 * s["layers"] * sequences * s["heads"] * seq * seq * d
    q_like = sequences * seq * s["heads"] * d * 2
    kv_like = sequences * seq * s["kv_heads"] * d * 2
    forward = 2 * q_like + 2 * kv_like
    backward = 4 * q_like + 4 * kv_like
    return flops, float(s["layers"] * (forward + backward))


def roofline_seconds(flops, nbytes, peak):
    """The least time one chip could take, and which bound sets it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    if t_flops >= t_bytes:
        return t_flops, "compute"
    return t_bytes, "memory"
