"""Mixture-of-Experts with expert parallelism, TPU-first.

Parity reference: atorch/atorch/modules/moe/ — ``MOELayer`` with explicit
``_AllToAll`` autograd dispatch (moe_layer.py:87,161), expert process
groups (:29), top-k and switch gating (topk_gating.py, switch_gating.py),
and the MoE-aware DDP that excludes expert params from the global
allreduce (ddp.py:26).

TPU-native redesign: dispatch/combine are capacity-bucketed EINSUMS over a
one-hot routing tensor; sharding expert weights on the "expert" mesh axis
and tokens on the data axes makes GSPMD insert the all-to-alls the
reference wrote by hand — and the expert/non-expert gradient split falls
out of the sharding rules (expert params simply aren't replicated), no
special DDP needed. Gating runs in fp32; an auxiliary load-balance loss
(Switch-style) and router z-loss are returned for the trainer to add.

Two paths, one routing rule. Where every expert lives on the device
(no ``expert`` mesh axis), ``dropless_moe_mlp``: the ``tokens x k``
assignments are sorted by expert, gathered into expert order, run
through one grouped matmul a projection (ops/grouped_matmul.py) and
gathered back. No ``[N, E, C]`` tensor, no capacity: every assignment
is computed. Where experts are sharded over an ``expert`` axis,
``moe_mlp``: the capacity-bucketed einsums above, which drop what
overflows. The model file chooses between them by the mesh.

A share of the experts. The dropless path can be told which experts
this device holds (``first_held``, and as many as the expert matrices
it is given): the router keeps its width, top-k is over all of them,
and the layer returns the part of the sum that the held experts give,
every assignment to one of them computed whatever the imbalance. What
the absent experts would have added is left out; nothing stands in for
their devices or for the exchange with them. A share walks the sorted
assignments in chunks of ``CHUNK_ROWS`` and does a chunk's work only
where the chunk starts before the held experts' rows end
(``_walk_held_rows``): its time and its memory follow the rows that
are here, not the ``tokens x k`` that may be.
"""

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

# loss coefficients owned HERE (callers add aux unscaled): Switch-style
# balance loss at 1e-2, router z-loss at 1e-3
BALANCE_LOSS_COEF = 1e-2
Z_LOSS_COEF = 1e-3


def expert_counts(experts: jax.Array, e: int) -> jax.Array:
    """How many of the assignments ``experts`` (int, any shape) each of
    the ``e`` experts received: int32 [e], sums to ``experts.size``."""
    return jnp.sum(
        jax.nn.one_hot(experts.reshape(-1), e, dtype=jnp.int32), axis=0
    )


def balance_loss(probs: jax.Array, experts: jax.Array) -> jax.Array:
    """Load-balance loss (Switch eq. 4 over all k choices, as
    ``OlmoeForCausalLM``'s ``load_balancing_loss_func`` counts them):
    ``E * sum_e f_e * p_e``, ``f_e`` the share of the ``N x k``
    assignments that expert e received, ``p_e`` its mean router
    probability. ``probs`` [N, E] float32, ``experts`` int [N, k]."""
    e = probs.shape[-1]
    f = expert_counts(experts, e) / experts.size
    return e * jnp.sum(f * jnp.mean(probs, axis=0))


def topk_gating(
    logits: jax.Array,  # [tokens, experts] fp32
    k: int,
    capacity: int,
    norm_topk_prob: bool = True,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing with per-expert capacity.

    Returns (dispatch [N, E, C] bool-ish fp32, combine [N, E, C] fp32,
    aux_loss scalar). Tokens overflowing an expert's capacity are dropped
    (standard Switch/GShard semantics).
    """
    n, e = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    aux_loss = balance_loss(probs, jax.lax.top_k(probs, k)[1])

    dispatch = jnp.zeros((n, e, capacity), jnp.float32)
    combine = jnp.zeros((n, e, capacity), jnp.float32)
    # iterate the k choices (k is small and static); queue positions carry
    # a running per-expert offset so later rounds don't collide with slots
    # already filled by earlier rounds
    counts = jnp.zeros((e,), jnp.float32)
    masked_probs = probs
    for _ in range(k):
        choice = jnp.argmax(masked_probs, axis=-1)  # [N]
        gate = jnp.take_along_axis(
            masked_probs, choice[:, None], axis=-1
        )[:, 0]
        onehot = jax.nn.one_hot(choice, e, dtype=jnp.float32)  # [N, E]
        # position of each token within its chosen expert's queue
        pos = (
            (jnp.cumsum(onehot, axis=0) - 1.0) + counts[None, :]
        ) * onehot  # [N, E]
        in_cap = (pos < capacity) & (onehot > 0)
        counts = counts + jnp.sum(onehot, axis=0)
        pos_cap = jnp.clip(pos.astype(jnp.int32), 0, capacity - 1)
        slot = jax.nn.one_hot(pos_cap, capacity, dtype=jnp.float32)
        contrib = (
            onehot * in_cap.astype(jnp.float32)
        )[..., None] * slot  # [N, E, C]
        dispatch = dispatch + contrib
        combine = combine + contrib * gate[:, None, None]
        masked_probs = masked_probs * (1.0 - onehot)  # exclude chosen

    if k > 1 and norm_topk_prob:
        # renormalize combine weights over the selected experts
        denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
        combine = combine / jnp.where(denom == 0.0, 1.0, denom)
    # k == 1 keeps the RAW gate probability (Switch semantics):
    # renormalizing would pin every weight to 1.0 and zero the router's
    # gradient through the LM loss
    return dispatch, combine, aux_loss


def moe_mlp(
    x: jax.Array,  # [batch, seq, hidden]
    gate_w: jax.Array,  # [hidden, experts]
    w_gate: jax.Array,  # [experts, hidden, mlp]  (SwiGLU gate proj)
    w_up: jax.Array,  # [experts, hidden, mlp]
    w_down: jax.Array,  # [experts, mlp, hidden]
    k: int = 2,
    capacity_factor: float = 1.25,
    norm_topk_prob: bool = True,
    balance_coef: float = BALANCE_LOSS_COEF,
    z_coef: float = Z_LOSS_COEF,
) -> Tuple[jax.Array, jax.Array]:
    """MoE SwiGLU block: route -> expert compute -> combine, with a
    capacity an expert (overflow is dropped): the path for experts
    sharded over an ``expert`` mesh axis.

    Returns (out [batch, seq, hidden], aux_loss). ``aux_loss`` is FULLY
    scaled (balance + z-loss coefficients applied here) — callers add it
    to the main loss as-is. Expert dims shard over
    the "expert" mesh axis via the models' logical-axes rules; the
    dispatch/combine einsums become all-to-alls under GSPMD.
    """
    b, s, h = x.shape
    e = gate_w.shape[-1]
    n = b * s
    capacity = max(1, int(capacity_factor * n * k / e))
    flat = x.reshape(n, h)

    router_logits = (flat.astype(jnp.float32)
                     @ gate_w.astype(jnp.float32))  # [N, E]
    # router z-loss keeps logits small (stability on bf16)
    z_loss = z_coef * jnp.mean(
        jax.nn.logsumexp(router_logits, axis=-1) ** 2
    )
    dispatch, combine, balance = topk_gating(
        router_logits, k, capacity, norm_topk_prob
    )
    aux = balance_coef * balance + z_loss

    xe = jnp.einsum(
        "nec,nd->ecd", dispatch.astype(x.dtype), flat
    )  # [E, C, H]
    gate_act = jax.nn.silu(jnp.einsum("ecd,edm->ecm", xe, w_gate))
    up = jnp.einsum("ecd,edm->ecm", xe, w_up)
    ye = jnp.einsum("ecm,emd->ecd", gate_act * up, w_down)  # [E, C, H]
    out = jnp.einsum(
        "nec,ecd->nd", combine.astype(x.dtype), ye
    ).reshape(b, s, h)
    return out, aux


# -- the dropless path ------------------------------------------------------

@jax.custom_vjp
def _to_expert_order(flat, order, inverse):
    """Row i of the result is the token of assignment ``order[i]``:
    ``flat[order // k]``, [N * k, H]. ``order`` is a permutation of the
    ``N x k`` assignments (token-major) and ``inverse`` its inverse, so
    the transpose is a gather too (each token's k rows, summed) and
    not the scatter-add autodiff would write."""
    return flat[order // (order.shape[0] // flat.shape[0])]


def _to_expert_order_fwd(flat, order, inverse):
    k = order.shape[0] // flat.shape[0]
    return flat[order // k], (inverse, flat.shape[0])


def _to_expert_order_bwd(res, g):
    inverse, n = res
    back = g[inverse].reshape(n, -1, g.shape[-1])
    return (
        jnp.sum(back.astype(jnp.float32), axis=1).astype(g.dtype),
        None, None,
    )


_to_expert_order.defvjp(_to_expert_order_fwd, _to_expert_order_bwd)


@jax.custom_vjp
def _to_token_order(rows, order, inverse):
    """``rows[inverse]``: the expert-ordered rows back in assignment
    order (token-major). A permutation, so its transpose is the gather
    by ``order``."""
    return rows[inverse]


def _to_token_order_fwd(rows, order, inverse):
    return rows[inverse], order


def _to_token_order_bwd(order, g):
    return g[order], None, None


_to_token_order.defvjp(_to_token_order_fwd, _to_token_order_bwd)


def router_logits(x: jax.Array, gate_w: jax.Array) -> jax.Array:
    """``x [..., H] @ gate_w [H, E]`` in float32."""
    return x.astype(jnp.float32) @ gate_w.astype(jnp.float32)


#: the router's gate, by the model file's name for it: what turns an
#: expert's logit into its score
GATES = {
    "softmax": lambda logits: jax.nn.softmax(logits, axis=-1),
    "sigmoid": jax.nn.sigmoid,
}


def _scores_at(probs: jax.Array, experts: jax.Array) -> jax.Array:
    """``probs[n, experts[n, j]]``, [N, k], read by comparing each
    chosen expert with the expert index and summing over the experts:
    a sum of one score and zeros is that score, and ``experts``' k a
    token are distinct, so the gradient's sum over k has one term a
    place too. The same bits as ``jnp.take_along_axis`` and its
    scatter-add, which the chip runs as a scalar gather at 10 ns an
    element and, backward, a sort and a scatter of the ``N x k``
    assignments (PERF.md, PR 55)."""
    hit = experts[..., None] == jnp.arange(
        probs.shape[-1], dtype=experts.dtype
    )
    return jnp.sum(jnp.where(hit, probs[..., None, :], 0.0), axis=-1)


def route(
    flat: jax.Array,  # [N, H]
    gate_w: jax.Array,  # [H, E]
    k: int,
    norm_topk_prob: bool,
    balance_coef: float = BALANCE_LOSS_COEF,
    z_coef: float = Z_LOSS_COEF,
    **routing,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``route_logits`` of the router's own product with ``flat``."""
    return route_logits(
        router_logits(flat, gate_w), k, norm_topk_prob, balance_coef,
        z_coef, **routing,
    )


def route_logits(
    logits: jax.Array,  # [N, E] float32: ``router_logits``
    k: int,
    norm_topk_prob: bool,
    balance_coef: float = BALANCE_LOSS_COEF,
    z_coef: float = Z_LOSS_COEF,
    gate: str = "softmax",
    bias: jax.Array = None,  # [E] float32
    norm_eps: float = None,
    scaling: float = 1.0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Router of the dropless path: ``(weights [N, k] float32,
    experts int32 [N, k], aux)``. The weights are the float32
    gate's own top-k scores (ties to the lower index, as
    ``jax.lax.top_k`` gives them), renormalised over the k only where
    the configuration says so (which makes a softmax gate's the
    softmax over the k chosen logits); at k = 1 they stay raw in
    either case, or the router would get no gradient through the LM
    loss. ``aux`` is scaled: the balance loss over all k choices plus
    the z-loss.

    ``gate`` "sigmoid" scores each expert by itself (a sum over the k
    then takes ``Lfm2MoeSparseMoeBlock``'s 1e-6 beside it, or the
    ``norm_eps`` given in its place: ``DeepseekV3TopkRouter``'s is
    1e-20). ``scaling``: a factor on the weights, after the sum
    (``routed_scaling_factor``). ``bias``:
    the k experts are the top-k of score plus bias and the weights
    the scores there, without it; the bias is a buffer that no
    gradient reaches (a balance kept by moving it is the trainer's
    to keep, by ``moved_bias`` on the step's ``expert_counts``). The
    balance term reads the
    scores normalised to sum to one over the experts, which a
    softmax's are."""
    probs = GATES[gate](logits)
    if bias is None:
        weights, experts = jax.lax.top_k(probs, k)
    else:
        _, experts = jax.lax.top_k(
            probs + jax.lax.stop_gradient(bias), k
        )
        weights = _scores_at(probs, experts)
    shares = probs
    if gate != "softmax":
        shares = probs / jnp.sum(probs, axis=-1, keepdims=True)
    aux = balance_coef * balance_loss(shares, experts) + z_coef * (
        jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    )
    if norm_topk_prob and k > 1:
        total = jnp.sum(weights, axis=-1, keepdims=True)
        if norm_eps is None and gate == "sigmoid":
            norm_eps = 1e-6
        if norm_eps:
            total = total + norm_eps
        weights = weights / total
    if scaling != 1.0:
        weights = weights * scaling
    return weights, experts, aux


def moved_bias(bias: jax.Array, counts: jax.Array,
               rate: float) -> jax.Array:
    """The selection bias after one step of the rule that balances
    without an auxiliary loss (arXiv:2408.15664, as torchtitan's
    ``MoEArgs.load_balance_coeff`` applies it ahead of an optimizer
    step): ``bias + delta``, ``delta = rate x sign(mean(c) - c)``
    less its own mean, ``c`` the assignments each expert received in
    the step. ``bias`` float32 [..., E], ``counts`` int [..., E]: a
    layer a row. An expert under the mean load is raised by ``rate``,
    one over it lowered, and the bias keeps its mean."""
    c = counts.astype(jnp.float32)
    delta = rate * jnp.sign(jnp.mean(c, axis=-1, keepdims=True) - c)
    return bias + (delta - jnp.mean(delta, axis=-1, keepdims=True))


#: the activation in an expert, by the model file's name for it: the
#: gate's, or in an expert without a gate matrix the one product's
ACTIVATIONS = {
    "silu": jax.nn.silu, "relu": jax.nn.relu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}

#: rows of one chunk of a share's walk at rows ``CHUNK_WIDTH`` wide: a
#: multiple of 512, so that ``grouped_matmul.tiles`` keeps its row
#: tile. The chip's verdict at [16384, 2560], 16 of 64 experts held,
#: top-6 (PERF.md, PR 35; ``benchmarks/profile_moe_share.py``): a live
#: chunk costs 3.8 ms at 4,096 rows, 5.1 at 8,192 and 7.2 at 12,288,
#: forward and backward under a remat policy that runs the forward
#: twice. Smaller chunks follow the held share more closely and pay a
#: turn's fixed costs (the tokens' float32 sum rewritten, and the
#: float32 gradient sums of the experts the chunk holds rows of read
#: and written, once a turn) more often, larger ones round a layer's
#: share up further: in the cell 4,096 read 4.4% fewer tokens/s than
#: this and 12,288 0.3% more for a second of set-up (when a turn still
#: moved every held expert's three sums: before PR 39)
CHUNK_ROWS = 8192
#: the width those rows were timed at. A chunk is sized by the bytes
#: of its gathered rows, so narrower rows make a longer chunk: 10,240
#: at 2048. The chip's verdict there, [32768, 2048], 8 of 32 experts
#: of 1792 held, top-4 (PERF.md, PR 36; forward plus the gradients'
#: program, ms a layer, at held shares of 0.249 / 0.251): 8,192 rows
#: 40.8 / 46.1 (the even share, a row a token, ends on the fourth
#: chunk's edge at every batch of 8,192-token sequences, and a fifth
#: live chunk costs 5.3), 10,240 rows 42.3 / 42.5, 12,288 rows 39.4
#: / 39.6 (three live chunks). So bytes do not pick the fastest chunk
#: at that width; they pick one whose cost is level across the even
#: share, and leave the 2560-wide walk as it was timed.
CHUNK_WIDTH = 2560


def walk_chunks(assignments: int, width: int) -> Tuple[int, int]:
    """``(rows of a chunk, chunks)`` of a share's walk over
    ``assignments`` sorted rows ``width`` wide: ``CHUNK_ROWS`` at
    ``CHUNK_WIDTH``, as many more as the rows are narrower, in whole
    tiles of 512; or all the assignments where they are fewer. The
    last chunk may reach past the end."""
    rows = CHUNK_ROWS * CHUNK_WIDTH // width
    if rows > 512:
        rows -= rows % 512
    rows = min(rows, assignments)
    return rows, -(-assignments // rows)


def _chunk(order, group_sizes, start, rows, weights):
    """Of the ``rows`` sorted assignments from ``start``: which they
    are, their tokens (the last token for an id past ``N x k``), and
    how many of them each held expert has (the groups' row ranges
    clipped to the chunk's: they sum to the held rows inside it, and
    rows past that sum come out of the grouped matmuls as zeros)."""
    n, k = weights.shape
    with jax.named_scope("moe.dispatch"):
        chosen = jax.lax.dynamic_slice(order, (start,), (rows,))
        ends = jnp.cumsum(group_sizes)
        sizes = (jnp.clip(ends, start, start + rows)
                 - jnp.clip(ends - group_sizes, start, start + rows))
        return chosen, jnp.minimum(chosen // k, n - 1), sizes


def _products(rows, matrices, sizes):
    """``rows`` [C, .] of a chunk against each of the experts'
    ``matrices``, by the chunk's group ``sizes``. The scope is opened
    in here, under any transform of the caller's: a device trace
    names a kernel after the innermost name, and the grouped
    matmuls' readers know ``gmm``."""
    from dlrover_tpu.ops.grouped_matmul import grouped_matmul

    with jax.named_scope("moe.experts"):
        return tuple(
            grouped_matmul(rows, w, sizes, filled=False)
            for w in matrices
        )


def _first_matrices(w_gate, w_up):
    """The matrices an expert's input meets: the gate's and ``w_up``,
    or ``w_up`` alone in an expert without a gate (``w_gate`` None)."""
    return (w_up,) if w_gate is None else (w_gate, w_up)


def _hidden(products, act):
    """An expert's hidden rows from its first products: ``act(gate) *
    up`` of ``(gate, up)``, ``act(up)`` of ``(up,)`` alone."""
    *gate, up = products
    return ACTIVATIONS[act](gate[0]) * up if gate else ACTIVATIONS[act](up)


def _gated(products, scale, act):
    """``_hidden``, each row times its routing weight ``scale`` [C]
    float32, in the products' dtype: the down projection's input."""
    with jax.named_scope("moe.experts"):
        return (
            _hidden(products, act).astype(jnp.float32) * scale[:, None]
        ).astype(products[0].dtype)


def _over_live_chunks(live, carry, order, group_sizes, rows):
    """``carry`` after ``live(carry, start)`` for each chunk of
    ``rows`` of ``order`` that starts before the groups' rows end, in
    turn; a chunk past them costs a branch not taken."""
    held_rows = jnp.sum(group_sizes)

    def step(carry, start):
        return jax.lax.cond(
            start < held_rows, live, lambda carry, _: carry, carry, start
        ), None

    return jax.lax.scan(
        step, carry, jnp.arange(0, order.shape[0], rows, dtype=jnp.int32)
    )[0]


def _scales(weights, order):
    """The routing weights by assignment id, a zero for each id of
    ``order`` past ``N x k``."""
    return jnp.pad(
        weights.reshape(-1), (0, order.shape[0] - weights.size)
    )


def _walk(act, flat, weights, w_gate, w_up, w_down, order, group_sizes):
    """The held experts' part of the layer's sum, [N, H] in ``flat``'s
    dtype. ``order`` [chunks x C]: the assignments (token-major ids
    into ``weights`` [N, k]) sorted by expert, the held experts' first
    and in ``group_sizes``' order, then the ids ``N x k ...`` that no
    token has. A chunk that starts at or past the held rows' end takes
    the empty branch; a live one gathers its C token rows, runs the
    three grouped matmuls on its own group sizes and adds its C
    results to their tokens in float32 (a token may occur more than
    once in a chunk). With every assignment held every chunk is live.
    ``w_gate`` None: experts without a gate matrix, two products.

    Differentiated as ``_walk_held_rows``, whose backward pass is
    the same walk written out (``_walk_bwd``)."""
    from dlrover_tpu.ops.grouped_matmul import add_rows

    rows, _ = walk_chunks(weights.size, flat.shape[1])
    scales = _scales(weights, order)

    def live(out, start):
        chosen, tokens, sizes = _chunk(
            order, group_sizes, start, rows, weights
        )
        with jax.named_scope("moe.dispatch"):
            mine = flat[tokens]
        first = _products(mine, _first_matrices(w_gate, w_up), sizes)
        (mine,) = _products(
            _gated(first, scales[chosen], act), (w_down,), sizes
        )
        with jax.named_scope("moe.combine"):
            return add_rows(out, tokens, mine)

    return _over_live_chunks(
        live, jnp.zeros(flat.shape, jnp.float32), order, group_sizes, rows
    ).astype(flat.dtype)


#: ``_walk`` under reverse mode, its backward pass written out
#: (``_walk_bwd``). Left to autodiff, the loop's transpose hands
#: every chunk, live or not, a cotangent the size of the experts'
#: matrices and of ``flat`` to add, keeps a turn's rows for every
#: chunk or makes them again, and sums the matrices' gradients in
#: their own dtype, rounded once a chunk: at the shapes above 67-71
#: ms a layer where this reads 16, and a step that plans 17.4G of the
#: chip's 15.75G (PERF.md, PR 35; ``profile_moe_share.py --autodiff``)
_walk_held_rows = jax.custom_vjp(_walk, nondiff_argnums=(0,))


def _walk_fwd(act, *args):
    return _walk(act, *args), args


def _walk_bwd(act, args, g):
    """The same walk for the cotangent ``g`` [N, H] of the result:
    a live chunk makes its two first products again, takes ``g``'s
    rows of its tokens back through the three stages (each stage
    differentiated by JAX, the chain between them written out so that
    the matrices' gradients can be added where they are kept), and
    adds what comes out for its rows to their tokens. The matrices'
    gradients are summed in float32 over the chunks (an expert's rows
    may lie in several) and rounded to the matrices' dtype once, as
    the one pass rounds them."""
    from dlrover_tpu.ops.grouped_matmul import add_rhs_gradient, add_rows

    flat, weights, w_gate, w_up, w_down, order, group_sizes = args
    rows, _ = walk_chunks(weights.size, flat.shape[1])
    scales = _scales(weights, order)
    firsts = _first_matrices(w_gate, w_up)
    matrices = (*firsts, w_down)

    def live(grads, start):
        d_flat, d_scales, *d_firsts, d_w_down = grads
        chosen, tokens, sizes = _chunk(
            order, group_sizes, start, rows, weights
        )
        with jax.named_scope("moe.dispatch"):
            mine = flat[tokens]
        first, to_rows = jax.vjp(
            lambda r: _products(r, firsts, sizes), mine
        )
        # ``g``'s rows are gathered once both products of ``mine`` are
        # made: the two gathers depend on nothing of each other, and the
        # chip's scheduler, left the choice, has put ``g``'s between
        # the two products, a chunk's rows more alive at the step's
        # planned peak (PERF.md section 6, PR 37)
        g_then, *first = jax.lax.optimization_barrier((g, *first))
        with jax.named_scope("moe.combine"):
            cotangent = g_then[tokens]
        hidden, to_products = jax.vjp(
            functools.partial(_gated, act=act), tuple(first),
            scales[chosen],
        )
        _, to_hidden = jax.vjp(
            lambda h: _products(h, (w_down,), sizes), hidden
        )
        d_first, d_scale = to_products(*to_hidden((cotangent,)))
        (d_mine,) = to_rows(d_first)
        with jax.named_scope("moe.experts"):
            d_firsts = [
                add_rhs_gradient(d_w, mine, d, sizes)
                for d_w, d in zip(d_firsts, d_first)
            ]
            d_w_down = add_rhs_gradient(
                d_w_down, hidden, cotangent, sizes
            )
        with jax.named_scope("moe.dispatch"):
            return (
                add_rows(d_flat, tokens, d_mine),
                d_scales.at[chosen].set(d_scale, unique_indices=True),
                *d_firsts, d_w_down,
            )

    d_flat, d_scales, *d_matrices = _over_live_chunks(
        live,
        (jnp.zeros(flat.shape, jnp.float32), jnp.zeros_like(scales),
         *(jnp.zeros(w.shape, jnp.float32) for w in matrices)),
        order, group_sizes, rows,
    )
    d_matrices = [d.astype(w.dtype) for d, w in zip(d_matrices, matrices)]
    if w_gate is None:
        d_matrices = [None] + d_matrices
    return (
        d_flat.astype(flat.dtype),
        d_scales[:weights.size].reshape(weights.shape),
        *d_matrices, None, None,
    )


_walk_held_rows.defvjp(_walk_fwd, _walk_bwd)


def _share(flat, weights, experts, w_gate, w_up, w_down, act, first_held):
    """``dropless_moe_mlp``'s result [N, H] where the device holds
    the ``w_gate.shape[0]`` experts from ``first_held`` of the more
    that ``experts`` [N, k] chooses among."""
    held, nk = w_up.shape[0], experts.size
    rows, chunks = walk_chunks(nk, flat.shape[1])
    with jax.named_scope("moe.dispatch"):
        # held experts by their place here, every absent one last
        assigned = experts.reshape(nk) - first_held
        assigned = jnp.where(
            (assigned >= 0) & (assigned < held), assigned, held
        )
        # the last chunk's ids past N x k: each its own, behind all
        order = jnp.concatenate([
            jnp.argsort(assigned, stable=True).astype(jnp.int32),
            jnp.arange(nk, rows * chunks, dtype=jnp.int32),
        ])
        group_sizes = expert_counts(assigned, held + 1)[:held]
    return _walk_held_rows(
        act, flat, weights, w_gate, w_up, w_down, order, group_sizes
    )


def dropless_moe_mlp(
    x: jax.Array,  # [batch, seq, hidden]
    gate_w: jax.Array,  # [hidden, experts]
    w_gate: jax.Array,  # [held, hidden, mlp]
    w_up: jax.Array,  # [held, hidden, mlp]
    w_down: jax.Array,  # [held, mlp, hidden]
    k: int = 2,
    norm_topk_prob: bool = True,
    balance_coef: float = BALANCE_LOSS_COEF,
    z_coef: float = Z_LOSS_COEF,
    logits: jax.Array = None,  # [batch, seq, experts] float32
    act: str = "silu",
    first_held: int = 0,
    shared: Tuple[jax.Array, jax.Array, jax.Array] = None,
    count: bool = False,
    latent: Tuple[jax.Array, jax.Array] = None,
    **routing,  # ``route_logits``' gate, bias, norm_eps and scaling
) -> Tuple[jax.Array, jax.Array]:
    """MoE gated block (``act(gate) * up``, then down) in which every
    one of the ``N x k`` assignments to an expert on this device is
    computed: ``(out [batch, seq, hidden], aux)``, ``aux`` scaled as
    ``moe_mlp``'s. ``w_gate`` None: experts without a gate matrix,
    ``act(up)`` then down (``act`` "relu2": the relu's square).
    ``logits``: the router's, where the model computes
    them elsewhere than from ``x`` (``router_logits``); else from
    ``x`` here. ``shared``: the gate (or None, as ``w_gate``), up and
    down matrices ([hidden,
    mlp'], [hidden, mlp'], [mlp', hidden]) of an expert that every
    token takes, unweighted and whole on every device, added to
    ``out`` (scope ``moe.shared``): of the devices that share a layer
    each computes it for its own tokens, so over the shares it counts
    once. ``latent``: ``(down [hidden, latent], up [latent,
    hidden])``, whole on every device: the routed experts read and
    write rows ``latent`` wide, ``x`` through ``down`` (scope
    ``moe.latent_down``), and their weighted sum goes through ``up``
    (``moe.latent_up``; linear, so the shares' partial sums add up
    past it as before it); router and shared expert read ``x``
    itself. ``count``: ``(out, aux, counts)``, with the assignments
    each of the router's experts received, held here or not, int32
    [experts] (``expert_counts`` of the selection): what a rule that
    moves the selection bias reads (``moved_bias``).

    The four scopes name every device op's ``op_name``: ``moe.route``
    (router, softmax, top-k, aux losses), ``moe.dispatch`` (stable
    sort of the assignments by expert, gather into expert order),
    ``moe.experts`` (the grouped matmuls; their two results are named
    ``moe_gate`` and ``moe_up`` for a remat policy to keep),
    ``moe.combine`` (the rows back to their tokens, summed over a
    token's k; the weights were applied inside the experts).

    The device holds the ``held`` experts ``first_held ...`` whose
    matrices it is given: all of the router's, or a share. With
    every expert here the layer is one pass over the ``N x k`` rows.
    With a share, routing, weights and ``aux`` are over all experts
    as before and ``out`` is the held experts' part of the sum: the
    assignments to absent experts sort behind the held ones', and
    the sorted rows are walked in chunks of ``CHUNK_ROWS``
    (``_walk_held_rows``) of which only those that start before the
    held rows' end are gathered, multiplied and added to their
    tokens. A token's k experts may all be held, and then every
    chunk is live; what a share costs is the sort of all ``N x k``
    keys, then a row gather, the three products and the rows' sum
    into their tokens (``ops/grouped_matmul.py add_rows``) for the
    held rows rounded up to a chunk, with no ``N x k``-row buffer at
    any routing. The backward pass is the same walk, and
    makes a chunk's two first products again whatever the remat
    policy keeps."""
    from jax.ad_checkpoint import checkpoint_name

    from dlrover_tpu.ops.grouped_matmul import grouped_matmul

    b, s, h = x.shape
    e, held = gate_w.shape[-1], w_up.shape[0]
    if not 0 <= first_held <= e - held:
        raise ValueError(
            f"experts {first_held}..{first_held + held - 1} of {e}"
        )
    n = b * s
    flat = x.reshape(n, h)
    with jax.named_scope("moe.route"):
        if logits is None:
            logits = router_logits(flat, gate_w)
        weights, experts, aux = route_logits(
            logits.reshape(n, e), k, norm_topk_prob, balance_coef,
            z_coef, **routing,
        )
        counts = (expert_counts(experts, e),) if count else ()

    def result(out):
        """``out`` [N, .] through the latent's way up and with the
        shared expert's term, ``aux`` and, asked for, the counts."""
        if latent is not None:
            with jax.named_scope("moe.latent_up"):
                out = out @ latent[1]
        out = out.reshape(b, s, h)
        if shared is not None:
            with jax.named_scope("moe.shared"):
                *ws_first, ws_down = shared
                out = out + _hidden(tuple(
                    x @ w for w in _first_matrices(*ws_first)
                ), act) @ ws_down
        return (out, aux, *counts)

    if latent is not None:
        with jax.named_scope("moe.latent_down"):
            flat = flat @ latent[0]
    if held < e:
        return result(_share(
            flat, weights, experts, w_gate, w_up, w_down, act, first_held
        ))
    with jax.named_scope("moe.dispatch"):
        assigned = experts.reshape(n * k)
        order = jnp.argsort(assigned, stable=True).astype(jnp.int32)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(n * k, dtype=jnp.int32)
        )
        group_sizes = expert_counts(assigned, e)
        rows = _to_expert_order(flat, order, inverse)
    with jax.named_scope("moe.experts"):
        grouped = functools.partial(
            grouped_matmul, group_sizes=group_sizes
        )
        first = () if w_gate is None else (
            checkpoint_name(grouped(rows, w_gate), "moe_gate"),)
        first += (checkpoint_name(grouped(rows, w_up), "moe_up"),)
        # the routing weight goes onto the down product's input: the
        # product is linear in it, and the weight's gradient then
        # needs that input (made again from the two kept products)
        # and not the down product's result, which nothing keeps
        hidden = (
            _hidden(first, act).astype(jnp.float32)
            * weights.reshape(n * k)[order][:, None]
        ).astype(x.dtype)
        rows = grouped(hidden, w_down)
    with jax.named_scope("moe.combine"):
        mine = _to_token_order(rows, order, inverse).reshape(
            n, k, rows.shape[-1])
        out = jnp.sum(mine.astype(jnp.float32), axis=1).astype(x.dtype)
    return result(out)


def tokens_per_expert(
    x: jax.Array, gate_w: jax.Array, k: int
) -> jax.Array:
    """How many of ``x``'s tokens the router sends to each expert,
    int32 [experts]: sums to ``tokens x k``."""
    return logits_per_expert(router_logits(x, gate_w), k)


def logits_per_expert(
    logits: jax.Array, k: int, **routing
) -> jax.Array:
    """``tokens_per_expert`` from the router's logits [..., experts],
    chosen as ``route_logits`` chooses (``routing``: its gate and
    bias)."""
    e = logits.shape[-1]
    _, experts, _ = route_logits(
        logits.reshape(-1, e), k, norm_topk_prob=False, **routing
    )
    return expert_counts(experts, e)


def bias_changed(
    logits: jax.Array, k: int, bias: jax.Array, gate: str = "softmax"
) -> jax.Array:
    """How many of the ``tokens x k`` assignments the selection bias
    changed: the experts among a token's top-k of score plus bias
    that are not among its top-k of the score alone. int32 scalar."""
    flat = logits.reshape(-1, logits.shape[-1])
    _, biased, _ = route_logits(flat, k, False, gate=gate, bias=bias)
    _, plain, _ = route_logits(flat, k, False, gate=gate)
    kept = jnp.any(biased[:, :, None] == plain[:, None, :], axis=-1)
    return jnp.sum(~kept, dtype=jnp.int32)


def set_expert_load_gauges(counts) -> Tuple[float, float]:
    """From tokens per expert and layer ([layers, experts], what
    ``models.llama.routing_stats`` returns) set the gauges
    ``moe_expert_load_max_over_mean`` and
    ``moe_expert_load_min_over_mean`` (``GET /metrics``): the busiest
    and the idlest expert of any layer against its layer's mean. On one
    device a dropless step's time does not depend on them; sharded
    over an ``expert`` axis the busiest expert's device sets it."""
    import numpy as np

    from dlrover_tpu.telemetry.registry import gauge

    load = np.asarray(counts, dtype=np.float64)
    ratio = load / np.maximum(load.mean(axis=-1, keepdims=True), 1e-9)
    most, least = float(ratio.max()), float(ratio.min())
    gauge(
        "moe_expert_load_max_over_mean",
        "tokens of the busiest expert of any layer over its layer's "
        "mean, at the last evaluation",
    ).set(most)
    gauge(
        "moe_expert_load_min_over_mean",
        "tokens of the idlest expert of any layer over its layer's "
        "mean, at the last evaluation",
    ).set(least)
    return most, least


def set_rows_held_gauge(counts, first_held: int, held: int) -> float:
    """From the same counts set the gauge ``moe_rows_held_share``:
    assignments to the experts this device holds
    (``first_held ... first_held + held - 1``) over all ``N x k``, all
    layers together. 1 where every expert is here; ``held / experts``
    under even routing. It is the share of the dropless buffers' rows
    that the grouped matmuls compute."""
    import numpy as np

    from dlrover_tpu.telemetry.registry import gauge

    load = np.asarray(counts, dtype=np.float64)
    share = float(
        load[..., first_held:first_held + held].sum()
        / max(load.sum(), 1e-9)
    )
    gauge(
        "moe_rows_held_share",
        "assignments to experts this device holds over all tokens x "
        "k, at the last evaluation",
    ).set(share)
    return share


def _walk_of(counts, width: int):
    """``(counts as int64 [layers, experts], rows of a chunk,
    chunks)`` of the walk a layer with these counts makes."""
    import numpy as np

    load = np.asarray(counts, dtype=np.int64)
    load = load.reshape(-1, load.shape[-1])
    return (load, *walk_chunks(int(load[0].sum()), width))


def set_chunks_walked_gauge(counts, first_held: int, held: int,
                            width: int) -> float:
    """From the same counts set the gauge ``moe_chunks_walked_share``
    {``chunk_rows``}: the chunks of a share's walk that are live (a
    layer's held rows rounded up to whole chunks, ``walk_chunks``)
    over all chunks, all layers together. 1 where every expert is
    here (one pass, no walk). Over ``moe_rows_held_share`` it is what
    the chunk's rounding costs. ``width``: the layer's rows' (the
    model's hidden size), which sizes a chunk."""
    from dlrover_tpu.telemetry.registry import gauge

    load, rows, chunks = _walk_of(counts, width)
    share = 1.0
    if held < load.shape[-1]:
        here = load[:, first_held:first_held + held].sum(axis=-1)
        share = float((-(-here // rows)).sum() / (chunks * len(load)))
    gauge(
        "moe_chunks_walked_share",
        "live chunks of the walk over a share's sorted assignments "
        "over all chunks, at the last evaluation",
        ("chunk_rows",),
    ).labels(chunk_rows=str(rows)).set(share)
    return share


def set_sums_visited_gauge(counts, first_held: int, held: int,
                           width: int) -> float:
    """From the same counts set the gauge ``moe_sums_visited_share``
    {``chunk_rows``}: the held experts with a row in a live chunk,
    summed over the live chunks of a share's walk, over held experts
    x live chunks, all layers together. It is the share of the
    experts' float32 gradient sums that the backward walk's in-place
    products read and write (``ops/grouped_matmul.py
    add_rhs_gradient`` visits only the groups that have rows in the
    piece); the rows are sorted by expert, so under even routing a
    chunk holds rows of ``rows / (rows an expert) + 1`` experts or
    so. 1 where every expert is here (one pass, every sum written
    once); 0 where no chunk is live. ``width`` as above."""
    import numpy as np

    from dlrover_tpu.telemetry.registry import gauge

    load, rows, _ = _walk_of(counts, width)
    share = 1.0
    if held < load.shape[-1]:
        here = load[:, first_held:first_held + held]
        ends = np.cumsum(here, axis=-1)
        # the chunks an expert's rows lie in: its first row's to its
        # last row's
        visits = np.where(
            here > 0, (ends - 1) // rows - (ends - here) // rows + 1, 0
        ).sum()
        live = (-(-ends[:, -1] // rows)).sum()
        share = float(visits / max(held * live, 1))
    gauge(
        "moe_sums_visited_share",
        "held experts with a row in a live chunk of a share's walk "
        "over held experts x live chunks, at the last evaluation",
        ("chunk_rows",),
    ).labels(chunk_rows=str(rows)).set(share)
    return share


def set_bias_changed_gauge(changed, assignments: int) -> float:
    """From the assignments a selection bias changed, a layer
    (``models.llama.bias_changed_stats``), of ``assignments`` a layer
    set the gauge ``moe_bias_changed_share``: all layers together. 0
    where the bias is flat; what a router's balance owes to its
    bias."""
    import numpy as np

    from dlrover_tpu.telemetry.registry import gauge

    changed = np.asarray(changed, dtype=np.float64)
    share = float(changed.sum() / max(changed.size * assignments, 1))
    gauge(
        "moe_bias_changed_share",
        "assignments the router's selection bias changed over all "
        "tokens x k, at the last evaluation",
    ).set(share)
    return share


def set_bias_abs_max_gauge(magnitudes) -> float:
    """From the largest magnitude of each expert layer's selection
    bias (``models.llama.expert_bias_abs_max``) set the gauge
    ``moe_bias_abs_max``: the most over the layers. 0 while the bias
    is the zeros it starts at; how far a rule that moves it
    (``moved_bias``) has taken it, against scores that lie in (0, 1)
    under a sigmoid gate."""
    import numpy as np

    from dlrover_tpu.telemetry.registry import gauge

    most = float(np.max(np.asarray(magnitudes, dtype=np.float64)))
    gauge(
        "moe_bias_abs_max",
        "largest magnitude of the router's selection bias over the "
        "expert layers, at the last evaluation",
    ).set(most)
    return most
