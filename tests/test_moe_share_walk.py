"""A share of the experts on the dropless path (parallel/moe.py
``dropless_moe_mlp`` with fewer matrices than the router is wide): the
walk of the sorted assignments in chunks, compared with the one pass
over every expert in which the absent experts' matrices are zero; the
matrices' gradients summed over chunks; the gauge of the chunks
walked. The two sums a chunk adds to (ops/grouped_matmul.py
``add_rows``, ``add_rhs_gradient``): test_grouped_matmul.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.parallel import moe

M, E = 32, 8
TOKENS, TOP, FIRST, HELD = 32, 3, 2, 4


def _objective(fn, k, norm):
    def objective(args):
        out, aux = fn(*args, k, norm)
        return jnp.sum(jnp.sin(out)) + 7.0 * aux

    return objective


def _chunks_of(monkeypatch, rows, width=TOKENS + 8):
    """``rows`` a chunk at rows ``width`` wide (``_share_layer``'s
    are 40): the two constants ``moe.walk_chunks`` sizes a chunk
    from."""
    monkeypatch.setattr(moe, "CHUNK_ROWS", rows)
    monkeypatch.setattr(moe, "CHUNK_WIDTH", width)


def test_a_chunk_is_sized_by_its_rows_bytes():
    """``CHUNK_ROWS`` at the width they were timed at, as many more
    as the rows are narrower, in whole tiles of 512; all the
    assignments where they are fewer."""
    rows, width = moe.CHUNK_ROWS, moe.CHUNK_WIDTH
    assert moe.walk_chunks(12 * rows, width) == (rows, 12)
    assert moe.walk_chunks(16 * rows, width * 4 // 5) == (rows * 5 // 4, 13)
    assert moe.walk_chunks(16 * rows, 2 * width) == (rows // 2, 32)
    assert moe.walk_chunks(131072, 3000) == (6656, 20)  # 6,990 in tiles
    assert moe.walk_chunks(96, 64) == (96, 1)


def _share_layer(held_rows, seed=11):
    """32 tokens whose top-3 of 8 experts are set by hand, so that
    exactly ``held_rows`` of the 96 assignments fall on experts
    2..5: feature t of token t is 1 and the router's row t holds that
    token's logits; eight more features carry noise, so ``x`` and the
    router both get gradient through logits the test did not fix.
    Returns the layer's arguments and the [32, 3] choices."""
    ks = jax.random.split(jax.random.key(seed), 6)
    here, away = [2, 3, 4, 5], [0, 1, 6, 7]
    chosen, left = [], held_rows
    for t in range(TOKENS):
        mine = min(TOP, left, -(-left // (TOKENS - t)))
        left -= mine
        chosen.append(
            # uneven groups: expert 5 gets few rows, so a chunk holds
            # it whole beside a neighbour's rows of the same tokens
            [here[(i if t < 24 else t + i) % 4] for i in range(mine)]
            + [away[(t + i) % 4] for i in range(TOP - mine)])
    assert left == 0
    table = np.full((TOKENS, E), -6.0, np.float32)
    for t, mine in enumerate(chosen):
        table[t, mine] = [3.0, 2.0, 1.0]
    h = TOKENS + 8
    x = jnp.concatenate([
        jnp.eye(TOKENS), 0.3 * jax.random.normal(ks[0], (TOKENS, 8)),
    ], axis=1).reshape(2, TOKENS // 2, h)
    router = jnp.concatenate([
        jnp.asarray(table), 0.05 * jax.random.normal(ks[1], (8, E)),
    ])
    w_gate = jax.random.normal(ks[2], (E, h, M)) * h ** -0.5
    w_up = jax.random.normal(ks[3], (E, h, M)) * h ** -0.5
    w_down = jax.random.normal(ks[4], (E, M, h)) * M ** -0.5
    return (x, router, w_gate, w_up, w_down), np.asarray(chosen)


def _walked(x, router, w_gate, w_up, w_down, k, norm):
    cut = slice(FIRST, FIRST + HELD)
    return moe.dropless_moe_mlp(
        x, router, w_gate[cut], w_up[cut], w_down[cut], k, norm,
        first_held=FIRST)


def _one_pass(x, router, w_gate, w_up, w_down, k, norm):
    """Every expert here, the absent ones' matrices zero: what they
    add to a token is ``act(0) * 0`` through a zero matrix."""
    keep = jnp.zeros((E, 1, 1)).at[FIRST:FIRST + HELD].set(1.0)
    return moe.dropless_moe_mlp(
        x, router, w_gate * keep, w_up * keep, w_down * keep, k, norm)


@pytest.mark.parametrize("chunk", [16, 20])
@pytest.mark.parametrize("held_rows,what", [
    (0, "no chunk is live"),
    (24, "an even quarter"),
    (96, "every assignment is held: every chunk is live"),
    (32, "the held rows end on a chunk's edge"),
    (31, "one under the edge"),
    (33, "one over the edge"),
])
def test_a_shares_walk_equals_the_one_pass(monkeypatch, held_rows, what,
                                           chunk):
    """``out``, ``aux`` and the gradients of ``x``, the router and the
    held experts' three matrices, in chunks of 16 (six of them) and of
    20 (five, the last padded past the 96 assignments)."""
    _chunks_of(monkeypatch, chunk)
    args, chosen = _share_layer(held_rows)
    held = (chosen >= FIRST) & (chosen < FIRST + HELD)
    assert held.sum() == held_rows
    with jax.default_matmul_precision("highest"):
        out, aux = _walked(*args, TOP, True)
        ref_out, ref_aux = _one_pass(*args, TOP, True)
        grads = jax.grad(_objective(_walked, TOP, True))(args)
        ref_grads = jax.grad(_objective(_one_pass, TOP, True))(args)
        aux_grads = jax.grad(
            lambda args: 7.0 * _walked(*args, TOP, True)[1])(args)
    np.testing.assert_allclose(out, ref_out, atol=2e-5)
    np.testing.assert_allclose(aux, ref_aux, rtol=1e-6)
    for name, g, r in zip(("x", "router", "w_gate", "w_up", "w_down"),
                          grads, ref_grads):
        np.testing.assert_allclose(g, r, atol=3e-5, err_msg=name)
        assert float(jnp.abs(r).sum()) > 0 or held_rows == 0, name
    absent = np.r_[0:FIRST, FIRST + HELD:E]
    for g in grads[2:]:
        assert float(jnp.abs(g[absent]).sum()) == 0
    if held_rows == 0:
        # nothing of the layer is here: the router learns from the
        # balance and z terms alone
        assert float(jnp.abs(out).max()) == 0
        for g in grads[2:]:
            assert float(jnp.abs(g).sum()) == 0
        for g, a in zip(grads[:2], aux_grads[:2]):
            np.testing.assert_array_equal(g, a)
    if held_rows == 96:
        # the sorted rows as the layer orders them: some token is in
        # one chunk twice, under two of its experts
        tokens = np.argsort(chosen.reshape(-1), kind="stable") // TOP
        assert any(
            len(set(tokens[at:at + chunk])) < len(tokens[at:at + chunk])
            for at in range(0, 96, chunk))


def test_every_expert_here_is_one_pass_and_a_share_a_loop(monkeypatch):
    """What the shapes say decides: a call with all of the router's
    experts has no loop in its program and does not read
    ``CHUNK_ROWS``; a share's is a ``while`` around a ``cond``."""
    args, _ = _share_layer(24)

    def text(fn):
        return str(jax.make_jaxpr(jax.grad(
            _objective(fn, TOP, True)))(args))

    whole = text(moe.dropless_moe_mlp)
    assert "cond[" not in whole and "scan[" not in whole
    share = text(_walked)
    assert share.count("scan[") == 2 and "cond[" in share
    _chunks_of(monkeypatch, 16)
    assert text(moe.dropless_moe_mlp) == whole
    assert text(_walked) != share


def test_chunks_walked_gauge():
    """Live chunks over all chunks, every layer: the held rows of a
    layer rounded up to chunks of ``CHUNK_ROWS``."""
    rows = moe.CHUNK_ROWS
    counts = np.zeros((3, E), np.int64)
    counts[:, 0] = 4 * rows  # absent
    counts[0, FIRST] = 0  # no chunk
    counts[1, FIRST:FIRST + 2] = rows // 2  # one chunk to its edge
    counts[2, FIRST + 3] = rows + 1  # one row into a second
    counts[:, 7] = 2 * rows - counts[:, FIRST:FIRST + HELD].sum(-1)
    assert (counts.sum(-1) == 6 * rows).all()
    share = moe.set_chunks_walked_gauge(
        counts, FIRST, HELD, moe.CHUNK_WIDTH)
    assert share == pytest.approx((0 + 1 + 2) / 18)
    from dlrover_tpu.telemetry.registry import default_registry

    assert (f'moe_chunks_walked_share{{chunk_rows="{rows}"}} {share}'
            in default_registry().to_prometheus_text())
    assert moe.set_chunks_walked_gauge(
        counts, 0, E, moe.CHUNK_WIDTH) == 1.0
    assert share >= moe.set_rows_held_gauge(counts, FIRST, HELD)


@pytest.mark.parametrize("tokens,top,experts,held,width,visits,of", [
    (32768, 4, 32, 8, 2048, 10, 32),  # lfm2: 3, 3, 3, 1 of 8
    (16384, 6, 64, 16, 2560, 18, 48),  # smallthinker: 6, 6, 6 of 16
], ids=["lfm2", "smallthinker"])
def test_sums_visited_gauge_under_even_routing(
    tokens, top, experts, held, width, visits, of
):
    """At both cells' shapes with every expert given the same rows:
    the held experts with a row in each live chunk (the rows are
    sorted by expert, so an expert's rows lie in one chunk or in two
    neighbours) over held experts x live chunks, two layers alike."""
    counts = np.full((2, experts), tokens * top // experts, np.int64)
    rows, _ = moe.walk_chunks(tokens * top, width)
    share = moe.set_sums_visited_gauge(counts, 0, held, width)
    assert share == pytest.approx(visits / of)
    from dlrover_tpu.telemetry.registry import default_registry

    assert (f'moe_sums_visited_share{{chunk_rows="{rows}"}} {share}'
            in default_registry().to_prometheus_text())
    # every expert held: one pass, every sum written once
    assert moe.set_sums_visited_gauge(counts, 0, experts, width) == 1.0


def test_sums_visited_gauge_counts_the_chunks_an_expert_lies_in():
    """Hand-made counts, ``CHUNK_ROWS`` rows a chunk: a layer whose
    held rows are one expert's over three chunks (3 visits of 4 x 3),
    a layer with a row each in a first chunk (4 of 4 x 1), and a layer
    that holds nothing (no live chunk, no visit)."""
    rows = moe.CHUNK_ROWS
    counts = np.zeros((3, E), np.int64)
    counts[0, FIRST + 1] = 2 * rows + 5
    counts[1, FIRST:FIRST + HELD] = 1
    counts[:, 7] = 6 * rows - counts[:, FIRST:FIRST + HELD].sum(-1)
    share = moe.set_sums_visited_gauge(
        counts, FIRST, HELD, moe.CHUNK_WIDTH)
    assert share == pytest.approx((3 + HELD) / (HELD * (3 + 1)))
    nothing = np.zeros((1, E), np.int64)
    nothing[0, 7] = 6 * rows
    assert moe.set_sums_visited_gauge(
        nothing, FIRST, HELD, moe.CHUNK_WIDTH) == 0.0


@pytest.mark.parametrize("chunk", [8, 24])
def test_an_experts_gradient_over_many_chunks_is_rounded_once(
    monkeypatch, chunk
):
    """bfloat16, the cells' dtype: one held expert takes all 96 rows,
    so its rows straddle every chunk edge (11 of them at 8 rows a
    chunk, 3 at 24), beside an absent one. The three matrices'
    gradients are those of the same rows in one chunk to the last bit
    almost everywhere, and nowhere further than one step of bfloat16:
    summed in float32 over the chunks and rounded once. Sums kept in
    bfloat16 from chunk to chunk are several steps off."""
    ks = jax.random.split(jax.random.key(chunk), 5)
    n, h, m = 96, 64, 32
    x = jax.random.normal(ks[0], (1, n, h)).astype(jnp.bfloat16)
    logits = jnp.tile(jnp.array([4.0, -4.0]), (1, n, 1))
    matrices = tuple(
        (jax.random.normal(k, (2, *shape)) * shape[0] ** -0.5)
        .astype(jnp.bfloat16)
        for k, shape in zip(ks[1:], ((h, m), (h, m), (m, h)))
    )
    router = jnp.zeros((h, 2), jnp.bfloat16)  # the logits are given

    def objective(matrices):
        out, _ = moe.dropless_moe_mlp(
            x, router, *(w[:1] for w in matrices), 1, True, logits=logits)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    _chunks_of(monkeypatch, n, h)
    whole = jax.grad(objective)(matrices)
    _chunks_of(monkeypatch, chunk, h)
    in_chunks = jax.grad(objective)(matrices)
    for name, got, want in zip(("w_gate", "w_up", "w_down"), in_chunks,
                               whole):
        assert got.dtype == jnp.bfloat16, name
        got, want = (np.asarray(g[0], np.float32) for g in (got, want))
        assert np.abs(want).max() > 0.1, name
        # neighbours in bfloat16 lie 2**-8 to 2**-7 of their size apart
        assert (np.abs(got - want) <= 2.0 ** -7 * np.abs(want)).all(), name
        assert (got == want).mean() > 0.97, name
