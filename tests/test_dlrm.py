"""Sparse-embedding recommender family (BASELINE config #4, VERDICT r3
Missing #1): vocab-parallel lookup exactness (fwd + grad), rowwise
training over the 8-device mesh, padding-mask semantics, and the
capacity argument — a table bigger than one chip's HBM plans onto the
mesh via the ordinary vocab-axis rules."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import dlrm, model_module_for
from dlrover_tpu.parallel.embedding import vocab_parallel_lookup
from dlrover_tpu.parallel.mesh import create_mesh


def _mesh():
    return create_mesh([("data", 2), ("fsdp", 4)])


def test_lookup_matches_dense_gather_forward_and_grad():
    mesh = _mesh()
    V, D, B, F = 64, 8, 16, 5
    table = jax.random.normal(jax.random.key(0), (V, D))
    ids = jax.random.randint(jax.random.key(1), (B, F), 0, V)

    got = jax.jit(
        lambda t, i: vocab_parallel_lookup(t, i, mesh)
    )(table, ids)
    np.testing.assert_allclose(got, table[ids], rtol=1e-6)

    g_sharded = jax.jit(jax.grad(
        lambda t: jnp.sum(vocab_parallel_lookup(t, ids, mesh) ** 2)
    ))(table)
    g_dense = jax.grad(lambda t: jnp.sum(t[ids] ** 2))(table)
    np.testing.assert_allclose(g_sharded, g_dense, rtol=1e-6)


def test_lookup_rejects_batch_on_table_axis():
    mesh = _mesh()
    table = jnp.zeros((64, 8))
    ids = jnp.zeros((4, 2), jnp.int32)
    with pytest.raises(ValueError, match="must not include"):
        vocab_parallel_lookup(
            table, ids, mesh, batch_axes=("data", "fsdp")
        )


def test_contract_and_dispatch():
    cfg = dlrm.criteo_wide_deep()
    assert model_module_for(cfg) is dlrm
    assert cfg.total_vocab == 733578  # sum of the CRITEO vocab stats
    assert cfg.padded_vocab % 1024 == 0
    params = dlrm.init_params(jax.random.key(0), cfg)
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert n == dlrm.param_count(cfg)
    assert dlrm.flops_per_token(cfg) > 0
    assert dlrm.table_bytes(cfg) > 4 * cfg.total_vocab * cfg.embed_dim


def test_dot_interaction_shape_guard():
    with pytest.raises(ValueError, match="bottom_mlp"):
        dlrm.DLRMConfig(embed_dim=16, bottom_mlp=(64, 8))


def test_padding_rows_carry_no_gradient():
    """Label -1 rows (elastic tail-shard padding) must not contribute
    to the loss or to table gradients."""
    cfg = dlrm.criteo_wide_deep(
        vocab_sizes=(50,) * 4, row_align=8
    )
    params = dlrm.init_params(jax.random.key(0), cfg)
    rng = np.random.RandomState(0)
    dense = rng.randn(6, cfg.dense_dim).astype(np.float32)
    cat = rng.randint(0, 50, (6, 4)).astype(np.int32)
    labels = np.array([1, 0, 1, 0, 1, 1], np.int32)

    loss_plain = dlrm.loss(
        params, (dense[:4], cat[:4], labels[:4]), cfg
    )
    padded_labels = labels.copy()
    padded_labels[4:] = -1
    loss_padded = dlrm.loss(params, (dense, cat, padded_labels), cfg)
    np.testing.assert_allclose(
        float(loss_plain), float(loss_padded), rtol=1e-6
    )
    g = jax.grad(
        lambda p: dlrm.loss(p, (dense, cat, padded_labels), cfg)
    )(params)
    # rows referenced ONLY by padded examples get zero grad
    only_padded = set(np.unique(cat[4:])) - set(np.unique(cat[:4]))
    if only_padded:
        row = sorted(only_padded)[0]
        assert float(jnp.sum(jnp.abs(g["table"][row]))) == 0.0


def test_rowwise_training_learns_on_mesh():
    """e2e on the 8-device mesh: table sharded over fsdp, batch over
    data; the planted click rule is learned (loss drops, acc beats
    the base rate). Compact vocab: this verifies the SHARDED math, and
    a CRITEO-size table's per-device dense update starves the XLA CPU
    collective watchdog when 8 device threads share one host core
    (the launcher e2e runs the full CRITEO config single-device)."""
    import os
    import sys

    examples = os.path.join(
        os.path.dirname(__file__), "..", "examples"
    )
    sys.path.insert(0, examples)
    try:
        from dlrm_train import make_clicks
    finally:
        sys.path.remove(examples)

    cfg = dlrm.criteo_wide_deep(
        vocab_sizes=(64, 40, 96, 8, 200, 33, 4, 120), row_align=8
    )
    mesh = _mesh()
    trainer = dlrm.make_trainer(cfg, mesh)
    params, opt_state = trainer.init(jax.random.key(0))
    assert "fsdp" in str(params["table"].sharding.spec)

    dense, cat, labels = make_clicks(512, cfg)
    first = None
    for i in range(80):
        lo = (i * 128) % 512
        batch = trainer.shard_batch((
            dense[None, lo:lo + 128], cat[None, lo:lo + 128],
            labels[None, lo:lo + 128],
        ))
        params, opt_state, loss = trainer.train_step(
            params, opt_state, batch
        )
        # one step in flight: with the next ones dispatched behind it
        # the eight device threads of a loaded host can each wait in a
        # different step's collective, and XLA CPU aborts at 40 s
        loss.block_until_ready()
        if first is None:
            first = float(loss)
    assert float(loss) < 0.8 * first, (first, float(loss))
    # probe under jit: EAGER shard_map collectives dispatch per-op and
    # can trip XLA CPU's stuck-rendezvous watchdog on a loaded host
    logits = jax.jit(
        lambda p, d, c: dlrm.forward(p, d, c, cfg, mesh=mesh)
    )(params, jnp.asarray(dense), jnp.asarray(cat))
    acc = float(jnp.mean(
        (logits > 0).astype(np.int32) == jnp.asarray(labels)
    ))
    base = max(labels.mean(), 1 - labels.mean())
    assert acc > base, (acc, base)


def test_large_table_exceeds_chip_but_plans_onto_mesh():
    """The capacity argument the PS served in the reference: a 26.4 GB
    stacked table (incl. the wide column) cannot live on one 15.75 GB
    chip; the planner's vocab-axis rule shards it over fsdp and the
    per-chip state fits."""
    from dlrover_tpu.auto.planner import plan_rules

    hbm = 15.75e9
    cfg = dlrm.dlrm_large(total_vocab=200_000_000, embed_dim=32)
    assert dlrm.table_bytes(cfg) > hbm  # one chip cannot hold it

    abs_params = jax.eval_shape(
        lambda k: dlrm.init_params(k, cfg), jax.random.key(0)
    )
    plan = plan_rules(
        abs_params, dlrm.param_axes(cfg), {"fsdp": 8}, hbm,
        tokens_per_step=8192, hidden_size=cfg.embed_dim,
        num_layers=cfg.num_layers, batch_axes=("data",),
        # f32 params + adagrad accumulator + grads ~ 3x in-dtype bytes
        state_bytes_multiplier=3.0,
    )
    assert plan.rules.get("vocab") == "fsdp"
    assert plan.memory_bytes < hbm
    assert plan.memory_bytes * 8 >= dlrm.table_bytes(cfg) * 3 * 0.9


def test_out_of_range_ids_clip_within_own_feature():
    """Review fix: an id >= its feature's vocab clips to the feature's
    LAST row rather than silently reading a neighboring feature."""
    cfg = dlrm.criteo_wide_deep(vocab_sizes=(4, 4), row_align=8)
    params = dlrm.init_params(jax.random.key(0), cfg)
    dense = np.zeros((1, cfg.dense_dim), np.float32)
    bad = np.array([[9, 0]], np.int32)      # feature-0 id out of range
    clipped = np.array([[3, 0]], np.int32)  # feature 0's last valid row
    out_bad = dlrm.forward(params, dense, bad, cfg)
    out_clip = dlrm.forward(params, dense, clipped, cfg)
    np.testing.assert_allclose(
        np.asarray(out_bad), np.asarray(out_clip), rtol=1e-6
    )


def test_auto_accelerate_dispatches_dlrm():
    """The auto layer runs the recommender family end to end: rowwise
    candidates are enumerated, configs without a remat field survive
    strategy application, and the dryrun times real (dense, cat,
    labels) batches."""
    from dlrover_tpu.auto.accelerate import auto_accelerate

    cfg = dlrm.criteo_wide_deep(
        vocab_sizes=(64, 40, 96, 8), row_align=8
    )
    result = auto_accelerate(
        cfg, global_batch=64, seq_len=1,
        devices=jax.devices()[:8], dryrun_top_k=2,
    )
    shardings = {r.strategy.sharding for r in result.reports}
    assert "rowwise" in shardings
    # the winner actually trains on the family's batch structure
    trainer = result.trainer
    params, opt_state = trainer.init(jax.random.key(0))
    import os
    import sys

    examples = os.path.join(
        os.path.dirname(__file__), "..", "examples"
    )
    sys.path.insert(0, examples)
    try:
        from dlrm_train import make_clicks
    finally:
        sys.path.remove(examples)

    dense, cat, labels = make_clicks(64, cfg)
    batch = trainer.shard_batch(trainer.microbatch(
        (dense, cat, labels)
    ))
    _, _, loss = trainer.train_step(params, opt_state, batch)
    assert np.isfinite(float(loss))
