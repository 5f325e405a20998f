"""The window of the flash-attention kernels (interpret mode on the
CPU) against a dense mask: forward, gradients, the census and its
gauges. A module of its own beside ``test_flash_attention.py``, so that
the two run on two workers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.attention import mha_reference
from dlrover_tpu.ops.pallas import flash_attention
from dlrover_tpu.ops.pallas.flash_attention import flash_attention_tpu

from .test_flash_attention import _rand_qkv


# (seq, block_q, block_k, g, window, tile): the window below, equal to
# and above the block; one that both edges cross in one block; a
# window of one key; groups 1, 4 and 7 (7: the folded rows are no
# power of two); unequal blocks, so that the clamps at both ends of a
# row's and of a column's live blocks are exercised. ``tile`` is the
# edge of the column tiles of a block that an edge of the band
# crosses, in the forward and in the backward kernels
# (``flash_attention._window_tile``; None: as the file has it, which
# at these blocks is the whole block but in the last case's backward)
WINDOWED = [
    (256, 64, 64, 1, 32, None),
    (256, 64, 64, 1, 64, None),
    (256, 64, 64, 1, 100, None),
    (256, 64, 64, 4, 64, None),
    (256, 64, 128, 7, 80, None),
    (512, 64, 256, 7, 128, None),
    (256, 128, 64, 1, 50, None),
    (128, 64, 64, 1, 1, None),
    (256, 64, 64, 1, 255, None),
    # the cells' geometry in small: block_q an eighth and a quarter of
    # block_k, groups of 8 and 7, a window of two and of four key
    # blocks (trinity-mini's and smallthinker's)
    (256, 16, 64, 8, 128, 16),
    (256, 16, 32, 7, 128, 8),
    # a window that is no multiple of the tile or of block_q, one
    # smaller than a tile, one that both edges cross inside one tile
    (256, 32, 64, 8, 100, 16),
    (256, 32, 128, 4, 20, 32),
    (256, 32, 128, 1, 100, 64),
    # fewer blocks in the sequence than the window and a block's
    # edges would span (two, where 255 / 128 + 2 is three)
    (256, 32, 128, 1, 255, 32),
    # block_q above block_k, tiled
    (256, 128, 64, 1, 50, 16),
    # the file's own tiles, at a block they tile
    (1024, 128, 1024, 2, 300, None),
]


@pytest.fixture
def tiled(monkeypatch):
    """Sets the edge of a windowed kernel's column tiles."""

    def set_tile(tile):
        if tile is not None:
            monkeypatch.setattr(
                flash_attention, "_window_tile",
                lambda kernel, block_k: tile if block_k > tile else block_k,
            )

    return set_tile


def _keep(seq, window):
    i = np.arange(seq)
    return (i[None, :] <= i[:, None]) & (i[:, None] - i[None, :] < window)


def _windowed(seq, bq, bk, g, window, d=32):
    q, k, v = _rand_qkv(jax.random.key(seq + window), 1, seq, g, 1, d)

    def kernel(q, k, v):
        return flash_attention_tpu(
            q, k, v, causal=True, block_q=bq, block_k=bk, window=window)

    def dense(q, k, v):
        return mha_reference(
            q, k, v, causal=False, mask=jnp.asarray(_keep(seq, window)))

    return (q, k, v), kernel, dense


def _assert_gradients(kernel, dense, qkv):
    grads = [
        jax.grad(lambda *a: jnp.sum(f(*a) ** 2), argnums=(0, 1, 2))(*qkv)
        for f in (kernel, dense)
    ]
    for got, want, name in zip(*grads, "qkv"):
        np.testing.assert_allclose(
            got, want, rtol=5e-3, atol=5e-3, err_msg=f"d{name}")


@pytest.mark.parametrize("what", ["forward", "gradients", "census"])
@pytest.mark.parametrize("seq,bq,bk,g,window,tile", WINDOWED)
def test_window_against_a_dense_mask(seq, bq, bk, g, window, tile, what,
                                     tiled):
    tiled(tile)
    qkv, kernel, dense = _windowed(seq, bq, bk, g, window)
    if what == "forward":
        np.testing.assert_allclose(
            kernel(*qkv), dense(*qkv), rtol=2e-3, atol=2e-3)
        # the reference's own window is the same band
        np.testing.assert_allclose(
            mha_reference(*qkv, causal=True, window=window), dense(*qkv),
            rtol=1e-6, atol=1e-6)
    elif what == "gradients":
        _assert_gradients(kernel, dense, qkv)
    else:
        for kernel in ("fwd", "bwd"):
            _assert_census(kernel, seq, bq, bk, window)


def _assert_census(kernel, seq, bq, bk, window):
    """The census, the band's grid and the runs of tiles that
    ``kernel`` holds a body of, against the dense mask."""
    t = _keep(seq, window).reshape(seq // bq, bq, seq // bk, bk)
    some, every = t.any(axis=(1, 3)), t.all(axis=(1, 3))
    assert flash_attention.causal_tile_census(
        seq, bq, bk, bq, bk, window
    ) == (int(some.sum()), int(some.sum()), int((some & ~every).sum()))
    band = flash_attention._band_of(kernel, seq, bq, bk, window)
    tile = band.tile
    assert bk % tile == 0
    tiles = t.reshape(seq // bq, bq, seq // bk, bk // tile, tile)
    seen, all_seen = tiles.any(axis=(1, 4)), tiles.all(axis=(1, 4))
    assert flash_attention.causal_tile_census(
        seq, bq, bk, bq, tile, window
    ) == (int(some.sum()) * (bk // tile), int(seen.sum()),
          int((seen & ~all_seen).sum()))
    # a row's (a column's) live blocks are one run from its first; the
    # grid's minor dimension is the longest
    for i, row in enumerate(some):
        (live,) = np.nonzero(row)
        assert (live[0], live[-1]) == (
            band.first_key_block(i), band.last_key_block(i))
        assert len(live) == live[-1] - live[0] + 1
    for j, column in enumerate(some.T):
        (live,) = np.nonzero(column)
        assert (live[0], live[-1]) == (
            band.first_query_block(j), band.last_query_block(j))
    assert band.key_steps() == some.sum(axis=1).max()
    assert band.query_steps() == some.sum(axis=0).max()
    # a crossed block's live tiles are one run, which is what a step
    # computes, and the kernels hold a body for exactly those runs
    runs = set()
    for i, j in zip(*np.nonzero(some & ~every)):
        (live,) = np.nonzero(seen[i, j])
        assert len(live) == live[-1] - live[0] + 1
        run = (int(live[0]), int(live[-1]) + 1)
        assert band.tiles(i * bq, j * bk) == run
        runs.add(run)
    assert band.crossed_tiles() == sorted(runs)


#: one case of each orientation of the blocks and of each kind of
#: group, with tiles
PAIRED = [WINDOWED[9], WINDOWED[10], WINDOWED[13], WINDOWED[15]]


@pytest.mark.parametrize("seq,bq,bk,g,window,tile", PAIRED)
def test_the_pairs_kernels_with_a_window(seq, bq, bk, g, window, tile,
                                         tiled, monkeypatch):
    """The dq and the dk/dv kernel apart (what a head too long for a
    resident gradient keeps; no cell runs them with a window): the
    grid by key blocks counts a key block's band of query blocks."""
    tiled(tile)
    monkeypatch.setattr(
        flash_attention, "_one_backward_kernel", lambda g, seq, d: False)
    qkv, kernel, dense = _windowed(seq, bq, bk, g, window)
    _assert_gradients(kernel, dense, qkv)


@pytest.mark.parametrize("g", [1, 4, 7])
def test_window_that_reaches_every_key_is_plain_causal(g):
    """To the last bit, forward and gradients: such a call builds the
    kernels of a call without a window."""
    (q, k, v), _, _ = _windowed(256, 64, 64, g, 256)

    def run(window):
        f = lambda q, k, v: flash_attention_tpu(  # noqa: E731
            q, k, v, causal=True, block_q=64, block_k=64, window=window)
        return f(q, k, v), jax.grad(
            lambda *a: jnp.sum(f(*a) ** 2), argnums=(0, 1, 2))(q, k, v)

    for got, want in zip(jax.tree.leaves(run(256)),
                         jax.tree.leaves(run(None))):
        np.testing.assert_array_equal(got, want)
    assert jax.make_jaxpr(lambda *a: run(4096)[0])(q, k, v).pretty_print() \
        == jax.make_jaxpr(lambda *a: run(None)[0])(q, k, v).pretty_print()


#: the two windowed cells' attention at 16,384 positions in the rule's
#: (128, 1024) blocks, a kv head: (window; live grid blocks, those an
#: edge crosses; the grid's key steps), and for the forward and the
#: backward kernels (the tile's edge; tiles the live blocks cover,
#: those computed, those masked)
AT_16K = {
    "trinity-mini": (2048, 360, 240, 3,
                     {"fwd": (1024, 360, 360, 240),
                      "bwd": (512, 720, 600, 240)}),
    "smallthinker": (4096, 560, 224, 5,
                     {"fwd": (1024, 560, 560, 224),
                      "bwd": (512, 1120, 1008, 224)}),
}
#: the runs of tiles that a kernel holds a masked body of: the whole
#: block; at two tiles a block the leading one and both (the
#: diagonal), the trailing one (the window's edge)
RUNS = {1024: [(0, 1)], 512: [(0, 1), (0, 2), (1, 2)]}


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
@pytest.mark.parametrize("cell", AT_16K)
def test_windowed_census_and_grid_at_16k(cell, kernel):
    """Pure arithmetic, no kernel: ISSUE 52's tables. Of 2,048 grid
    steps a kv head 360 (560) were live; the band's grid has 384 (640)
    steps, and the backward kernel computes an edge block's live
    (128, 512) tiles: 0.83 (0.90) of the columns of the blocks."""
    seq, bq, bk = 16384, 128, 1024
    window, live, crossed, steps, tiled_as = AT_16K[cell]
    tile, covered, computed, masked = tiled_as[kernel]
    assert flash_attention.causal_tile_census(
        seq, bq, bk, bq, bk) == (1088, 1088, 128)
    assert flash_attention.causal_tile_census(
        seq, bq, bk, bq, bk, window) == (live, live, crossed)
    band = flash_attention._band_of(kernel, seq, bq, bk, window)
    assert band.tile == tile
    assert (band.key_steps(), band.query_steps()) == (
        steps, (window + bk - 2) // bq + 1)
    assert flash_attention._grid(seq, bq, bk, band) == (128, steps)
    assert flash_attention._grid(seq, bq, bk, None) == (128, 16)
    assert flash_attention.causal_tile_census(
        seq, bq, bk, bq, tile, window) == (covered, computed, masked)
    assert band.crossed_tiles() == RUNS[tile]


def _gauge(name, **labels):
    from dlrover_tpu.telemetry.registry import default_registry

    text = default_registry().to_prometheus_text()
    key = name + "{" + ",".join(
        f'{k}="{v}"' for k, v in labels.items()) + "} "
    line = next(ln for ln in text.splitlines() if ln.startswith(key))
    return float(line.split()[1])


@pytest.mark.parametrize("cell", AT_16K)
def test_census_gauges_at_16k(cell):
    """What building a cell's windowed backward kernel sets, without
    building one: the share of the covered tiles computed (0.83 and
    0.90; 1.0 with whole blocks), and the share of the grid's steps
    that are live (0.94 and 0.875 where 0.18 and 0.27 were)."""
    seq, bq, bk = 16384, 128, 1024
    window, live, _, steps, tiled_as = AT_16K[cell]
    tile, covered, computed, masked = tiled_as["bwd"]
    flash_attention._set_census_gauges(
        "dq_dkv", seq, bq, bk, bq, tile, window, 128 * steps)
    labels = dict(kernel="dq_dkv", window=window)
    assert _gauge("attn_tiles_computed_share", **labels) \
        == computed / covered
    assert _gauge("attn_tiles_masked_share", **labels) == masked / covered
    assert _gauge("attn_grid_steps_live_share", **labels) \
        == live / (128 * steps)
    assert live / 2048 < 0.28 and live / (128 * steps) > 0.87
    assert {"trinity-mini": 5 / 6, "smallthinker": 0.9}[cell] \
        == computed / covered


def test_building_a_windowed_kernel_sets_the_gauges(tiled):
    tiled(16)
    (q, k, v), kernel, _ = _windowed(256, 16, 64, 8, 128)
    kernel(q, k, v)
    labels = dict(kernel="fwd", window=128)
    # a query block of 16 meets three key blocks of 64 (the first 8
    # rows fewer): twelve tiles of 16, of which the band's 128 + 15
    # keys touch nine or ten
    covered, computed, masked = flash_attention.causal_tile_census(
        256, 16, 64, 16, 16, 128)
    assert _gauge("attn_tiles_computed_share", **labels) \
        == computed / covered
    assert 0.7 < computed / covered < 0.85
    assert _gauge("attn_tiles_masked_share", **labels) == masked / covered
    # 16 query blocks by 3 steps, of which the first rows' are short
    assert _gauge("attn_grid_steps_live_share", **labels) \
        == (covered // 4) / 48
    (q, k, v), kernel, _ = _windowed(256, 64, 64, 1, 64)
    kernel(q, k, v)
    # whole blocks (64 is under the tile): 7 live of 16, 4 on the
    # diagonal, 3 that the window's edge crosses (by one pair each),
    # in a grid of 4 x 2
    labels = dict(kernel="fwd", window=64)
    assert _gauge("attn_tiles_masked_share", **labels) == 1.0
    assert _gauge("attn_tiles_computed_share", **labels) == 1.0
    assert _gauge("attn_grid_steps_live_share", **labels) == 7 / 8


def test_window_needs_causal():
    (q, k, v), _, _ = _windowed(128, 64, 64, 1, 32)
    with pytest.raises(ValueError, match="causal band"):
        flash_attention_tpu(q, k, v, causal=False, window=32)
    with pytest.raises(ValueError, match="causal band"):
        mha_reference(q, k, v, causal=False, window=32)
