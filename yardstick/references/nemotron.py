"""The ``nemotron`` family's plain forward loss: Nemotron-3 Super
(``model_type`` ``nemotron_h``), a stack of blocks of ONE branch as
the builder knows the family's modelling code and reports. Every norm
is an RMSNorm with a scale at ``layer_norm_epsilon``; nothing is
rotated and no projection has a bias. Layer ``l``, of the kind
``hybrid_override_pattern[l]`` names::

    x = x + branch_l(RMSNorm(x; norm_l))

``M``, a Mamba-2 mixer (``mamba_num_heads`` heads of
``mamba_head_dim`` in ``n_groups`` groups of ``ssm_state_size``
states), with ``y`` the normed stream::

    [z | xBC | dt] = y W_in                    # inner | inner + 2 g n | heads
    xBC = silu(conv(xBC) + b_conv)             # causal, depthwise,
                                               # conv_kernel taps a channel
    [x | B | C] = xBC                          # head h: group h // (heads / g)
    Delta_t = softplus(dt_t + dt_bias)         # a head, float32
    a_t = exp(-exp(A_log_h) Delta_t)
    S_t = a_t S_{t-1} + Delta_t x_t B_t^T      # S_0 = 0, [head_dim, states]
    o_t = S_t C_t + D_h x_t
    o = o * silu(z)                            # the gate BEFORE the norm
    o = o * rsqrt(mean over a group's columns of o^2 + eps) * w_norm
    out = o W_out

The recurrence is walked position by position with the heads' states
``[heads, head_dim, states]``: no chunk, no decay mask, no dual form.

``*``, attention (``num_attention_heads`` query heads on
``num_key_value_heads`` of ``head_dim``)::

    q, k, v = y Wq, y Wk, y Wv                 # no bias, no position
    out = softmax(q k^T / sqrt(d)) v W_o       # causal, every earlier key

``E``, experts in a latent, routed in float32 (``n_group`` 1 and
``topk_group`` 1: the group step chooses the one group there is, and
is left out)::

    s = sigmoid(y W_r)                  # over all the router's experts
    e_1..e_k = top-k of s + b           # b: expert_bias, a buffer
    w_j = s[e_j] / (sum_j s[e_j] + 1e-20) * routed_scaling_factor
    u = y W_dn                          # hidden -> moe_latent_size
    r = sum_j w_j W2_{e_j} relu(W1_{e_j} u)^2     # no gate matrix
    out = r W_up + Ws2 relu(Ws1 y)^2    # the shared expert on the stream

Past the last layer, with ``h`` the stream before the final norm, one
multi-token-prediction module (``num_nextn_predict_layers`` 1)::

    h'_i = W_eh [RMSNorm(Emb(t_{i+1}); embed_norm) ; RMSNorm(h_i; hidden_norm)]
    h'' = the sublayers mtp_hybrid_override_pattern names, on h'
    L_mtp = CE(head(RMSNorm(h''; the module's final_norm)), t_{i+2})

with the model's own embedding and head. The last position has no
``t_{i+1}``: it is handed the sequence's first token (a roll) and its
target masks it out, as the one before it, which has no ``t_{i+2}``.
The objective is ``L_main + w L_mtp + a L_LB``: an expert layer,
``L_LB = E sum_e f_e p_e`` (``f_e`` the share of the ``N x k``
assignments that expert e received, held or not, ``p_e`` the mean of
its score normalised to sum to one over the experts), summed over the
expert layers, the module's among them; ``w`` and ``a`` are the
configuration's ``assumed``.

Attention walks the query rows in blocks against an explicit mask
over all keys; the convolution is an explicit sum over taps of shifted
copies; the routing is a dense mask over all of the router's experts
and a Python loop over the ones held here, each run on every token and
kept where the mask has it.

The share. This chip holds ``n_routed_experts`` experts of each
expert layer (``share.first_expert_held`` is the first) of the
``share.router_width`` the router ranks, the latent projections, the
router and the shared expert whole, and a slice of the vocabulary.
What the absent experts would have added is left out (the way up is
linear, so the shares' partial latent sums add up past it as before
it), and that partial sum goes on to the next layer; logits and cross
entropy are over the slice.

The parameters are the program's tree: in ``period`` a stack
``[periods, ...]`` for each position of the scanned period, so that
layer ``l`` is position ``l % period`` of period ``l // period``; the
module in ``mtp[0]``, its sublayers one by one in ``block``.

Departures from the source as the builder knows it, each stated
(``assumed`` in the configuration's file has each one's origin).
``config.json`` names sizes only: the order ``z | x | B | C | dt`` of
``W_in``'s columns, the gate ahead of the grouped norm, attention
without positions, the router's form with its bias held at zero, the
latent projections without norm or bias, the module's form and
weight, and the balance term are ``assumed``."""

import functools

import jax
import jax.numpy as jnp

from yardstick.reference import (
    F32, HIGHEST, embed, final_rms, layer, mean_nll, rms_norm,
)

EXPERTS = ("w_up", "w_down")
#: query rows whose scores against every key are held at once
ROWS = 256


def attention(q, k, v, rows=ROWS):
    """q [b, s, heads, d]; k, v [b, s, kv_heads, d]; causal. Query
    head i reads kv head ``i // group``. ``rows`` query positions at
    a time."""
    b, s, heads, d = q.shape
    kv_heads = k.shape[2]
    rows = min(rows, s)
    if s % rows:
        raise ValueError(f"{s} positions in blocks of {rows}")
    j = jnp.arange(s)

    def block(args):
        r0, qr = args  # qr [b, rows, kv_heads, group, d]
        keep = j[None, :] <= (r0 + jnp.arange(rows))[:, None]
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qr, k)
        scores = jnp.where(keep, scores / jnp.sqrt(F32(d)), -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, v)

    blocks = q.reshape(b, s // rows, rows, kv_heads, heads // kv_heads, d)
    out = jax.lax.map(
        block, (jnp.arange(0, s, rows), jnp.moveaxis(blocks, 1, 0))
    )
    return jnp.moveaxis(out, 0, 1).reshape(b, s, heads * d)


def full_attention(y, p, heads, kv_heads):
    b, s, _ = y.shape
    q = (y @ p["wq"]).reshape(b, s, heads, -1)
    k = (y @ p["wk"]).reshape(b, s, kv_heads, -1)
    v = (y @ p["wv"]).reshape(b, s, kv_heads, -1)
    return attention(q, k, v) @ p["wo"]


def conv_silu(x, w, bias):
    """x [b, s, channels]; w [channels, taps], oldest tap first; bias
    [channels]."""
    s, taps = x.shape[1], w.shape[1]
    c = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j
        earlier = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :s]
        c = c + w[:, j] * earlier
    return jax.nn.silu(c + bias)


def recurrence(x, B, C, dt, A, D):
    """The state-space recurrence, a position at a time. x [b, s,
    heads, d]; B, C [b, s, heads, n] (a head its group's); dt [b, s,
    heads]; A (negative), D [heads]. Returns ``o`` [b, s, heads, d]."""
    b, s, heads, d = x.shape

    def step(state, at):  # state [b, heads, d, n]
        x_t, b_t, c_t, dt_t = at
        state = jnp.exp(A * dt_t)[..., None, None] * state + jnp.einsum(
            "bh,bhd,bhn->bhdn", dt_t, x_t, b_t)
        return state, jnp.einsum("bhdn,bhn->bhd", state, c_t) + (
            D[:, None] * x_t)

    _, o = jax.lax.scan(
        step, jnp.zeros((b, heads, d, B.shape[-1]), F32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (x, B, C, dt)),
    )
    return jnp.moveaxis(o, 0, 1)


def mamba(y, p, heads, groups, states, eps):
    b, s, _ = y.shape
    inner = p["ssm_out"].shape[0]
    proj = y @ p["ssm_in"]
    z = proj[..., :inner]
    xbc = proj[..., inner:-heads]
    dt = proj[..., -heads:]
    xbc = conv_silu(xbc, p["ssm_conv_w"], p["ssm_conv_b"])
    x = xbc[..., :inner].reshape(b, s, heads, -1)
    per = heads // groups

    def to_heads(a):  # [b, s, groups x n] -> a head its group's
        return jnp.repeat(a.reshape(b, s, groups, states), per, axis=2)

    B = to_heads(xbc[..., inner:inner + groups * states])
    C = to_heads(xbc[..., inner + groups * states:])
    dt = jax.nn.softplus(dt + p["dt_bias"])
    o = recurrence(x, B, C, dt, -jnp.exp(p["A_log"]), p["D"])
    o = o.reshape(b, s, inner) * jax.nn.silu(z)
    by_group = o.reshape(b, s, groups, -1)
    by_group = by_group * jax.lax.rsqrt(
        jnp.mean(by_group * by_group, axis=-1, keepdims=True) + eps)
    return (by_group.reshape(b, s, inner) * p["ssm_norm"]) @ p["ssm_out"]


def _expert(blocks, name, i, e):
    """Held expert ``e`` of layer ``i`` of the stack, in float32: the
    only float32 copy of an expert's matrix that lives at a time."""
    one_layer = jax.lax.dynamic_index_in_dim(
        blocks[name], i, axis=0, keepdims=False
    )
    return one_layer[e].astype(F32)


def ungated(y, w_up, w_down):
    return jnp.square(jax.nn.relu(y @ w_up)) @ w_down


def experts(y, blocks, p, i, per_token, first_held, norm_topk, eps,
            scaling):
    """``(the held experts' part of the routed sum through the way up
    and the shared expert's term, L_LB)``."""
    b, s, _ = y.shape
    logits = y @ p["router"]  # [b, s, width]
    width = logits.shape[-1]
    score = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(score + p["expert_bias"], per_token)
    picked = jnp.take_along_axis(score, chosen, axis=-1)
    if norm_topk:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + eps)
    picked = picked * scaling
    hot = jax.nn.one_hot(chosen, width, dtype=F32)  # [b, s, k, width]
    weights = jnp.einsum("bsk,bske->bse", picked, hot)
    u = y @ p["w_latent_down"]
    total = jnp.zeros_like(u)
    for e in range(blocks["w_up"].shape[1]):  # the experts held here
        out = ungated(u, *(_expert(blocks, name, i, e) for name in EXPERTS))
        total = total + weights[..., first_held + e, None] * out
    total = total @ p["w_latent_up"] + ungated(y, p["ws_up"], p["ws_down"])
    shares = score / jnp.sum(score, axis=-1, keepdims=True)
    load = jnp.sum(hot, axis=(0, 1, 2)) / (b * s * per_token)
    return total, width * jnp.sum(load * jnp.mean(shares, axis=(0, 1)))


@functools.partial(jax.jit, static_argnames=(
    "branch", "heads", "kv_heads", "ssm_heads", "groups", "states", "eps",
    "per_token", "first_held", "norm_topk", "topk_eps", "scaling"))
def _block(x, blocks, i, *, branch, heads, kv_heads, ssm_heads, groups,
           states, eps, per_token, first_held, norm_topk, topk_eps,
           scaling):
    """``(x, L_LB)`` of layer ``i`` of the stack ``blocks``, a block
    of the one ``branch``."""
    with HIGHEST():
        p = layer(
            {k: v for k, v in blocks.items() if k not in EXPERTS}, i
        )
        if branch == "M":
            y = rms_norm(x, p["attn_norm"], eps)
            return x + mamba(y, p, ssm_heads, groups, states, eps), F32(0.0)
        if branch == "*":
            y = rms_norm(x, p["attn_norm"], eps)
            return x + full_attention(y, p, heads, kv_heads), F32(0.0)
        y = rms_norm(x, p["mlp_norm"], eps)
        out, balance = experts(
            y, blocks, p, i, per_token, first_held, norm_topk, topk_eps,
            scaling,
        )
        return x + out, balance


@jax.jit
def _merge(e, h, eh_proj):
    with HIGHEST():
        return jnp.concatenate([e, h], axis=-1) @ eh_proj.astype(F32)


def loss(config, params, tokens, targets):
    if tokens.shape[1] > config["max_position_embeddings"]:
        raise ValueError(
            f"sequence {tokens.shape[1]} is longer than the "
            f"{config['max_position_embeddings']} positions the "
            "source declares"
        )
    assumed = config["assumed"]
    eps = float(config["layer_norm_epsilon"])
    block = functools.partial(
        _block,
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        ssm_heads=config["mamba_num_heads"], groups=config["n_groups"],
        states=config["ssm_state_size"], eps=eps,
        per_token=config["num_experts_per_tok"],
        first_held=config["share"]["first_expert_held"],
        norm_topk=bool(config["norm_topk_prob"]),
        topk_eps=float(assumed["topk_norm_eps"]),
        scaling=float(config["routed_scaling_factor"]),
    )
    period = len(params["period"])
    x = embed(params["embed"], tokens)
    balance = 0.0
    for l, branch in enumerate(config["hybrid_override_pattern"]):
        x, layer_balance = block(
            x, params["period"][l % period], l // period, branch=branch
        )
        balance = balance + layer_balance
    head = params["lm_head"]
    main = mean_nll(final_rms(x, params["final_norm"], eps), head, targets)

    (module,) = params["mtp"]
    ahead = jnp.roll(tokens, -1, axis=1)  # t_{i+1}; the last is masked
    y = _merge(
        final_rms(embed(params["embed"], ahead), module["embed_norm"], eps),
        final_rms(x, module["hidden_norm"], eps),
        module["eh_proj"],
    )
    for branch, sublayer in zip(
            config["mtp_hybrid_override_pattern"], module["block"]):
        y, layer_balance = block(
            y, jax.tree.map(lambda a: a[None], sublayer), 0, branch=branch
        )
        balance = balance + layer_balance
    further = jnp.concatenate(
        [targets[:, 1:], jnp.full_like(targets[:, :1], -1)], axis=1
    )  # t_{i+2}
    mtp = mean_nll(final_rms(y, module["final_norm"], eps), head, further)
    return (
        main + assumed["mtp_loss_weight"] * mtp
        + assumed["router_aux_loss_coef"] * balance
    )
