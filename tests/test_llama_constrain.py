"""The activation-layout constraints of the Llama block (models/llama.py
``constrain``; built by ``make_trainer_for_llama`` on a mesh of more
than one device): they say where a value lives, never what it is, and
on one device they are not there at all.

On the virtual CPU mesh; what the constraints do to the collectives of
the compiled four-chip step is tests/test_chip_compile.py's.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.ops.attention import make_sharded_attention
from dlrover_tpu.parallel import sharding as shd
from dlrover_tpu.parallel.context_parallel import (
    make_context_parallel_attn,
)
from dlrover_tpu.parallel.mesh import create_mesh
from dlrover_tpu.trainer.sharded import (
    ShardedTrainer, make_trainer_for_llama,
)

BATCH, SEQ = 4, 32


def _step_text(trainer):
    tok = jax.ShapeDtypeStruct(
        (1, BATCH, SEQ), jnp.int32,
        sharding=trainer.microbatch_sharding,
    )
    return trainer.train_step.lower(
        *trainer.abstract_state(), (tok, tok)
    ).as_text()


@pytest.mark.parametrize("remat", ["dots_attn_out", "dots"])
def test_one_device_step_is_the_unconstrained_program(remat):
    """``mesh.size == 1``: no callable is built, so the step lowers to
    the text of a trainer built by hand over ``next_token_loss`` alone
    (the same compile-cache key as before the constraints existed)."""
    cfg = llama.llama_tiny(remat=remat)
    mesh = create_mesh(
        [("data", 1), ("fsdp", 1)], devices=jax.devices()[:1]
    )

    def by_hand(constrain=None):
        return ShardedTrainer(
            lambda params, batch: llama.next_token_loss(
                params, batch, cfg, constrain=constrain
            ),
            lambda rng: llama.init_params(rng, cfg),
            llama.param_axes(cfg), mesh, strategy="fsdp",
            optimizer=optax.adamw(1e-4),
        )

    made = make_trainer_for_llama(
        cfg, mesh, strategy="fsdp", optimizer=optax.adamw(1e-4)
    )
    text = _step_text(made)
    assert text == _step_text(by_hand())
    # and a callable would have shown: a constraint is an op of its
    # own in the lowered text, even over one device
    rules = shd.get_rules("fsdp")
    pinned = _step_text(by_hand(
        lambda x, axes: shd.constrain(x, mesh, rules, axes)
    ))
    assert pinned != text
    assert len(pinned.splitlines()) > len(text.splitlines())


MESHES = {
    "fsdp": [("data", 1), ("fsdp", 4)],
    "tp_fsdp": [("fsdp", 2), ("tensor", 2)],
    "ddp": [("data", 4)],
    "sequence": [("data", 2), ("seq", 2)],
}


@pytest.mark.parametrize("strategy", sorted(MESHES))
def test_constraints_change_no_value(strategy):
    """Loss and every gradient leaf, with and without the constraints,
    over four devices: equal to float32 rounding (a layout decides in
    which order partial sums meet, nothing else)."""
    cfg = llama.llama_tiny(dtype=jnp.float32, remat="dots_attn_out")
    mesh = create_mesh(MESHES[strategy], devices=jax.devices()[:4])
    rules = shd.get_rules(strategy)
    if strategy == "sequence":
        attn_fn = make_context_parallel_attn(mesh, kind="ring")
    else:
        attn_fn = make_sharded_attention(
            mesh,
            q_spec=shd.spec_for_axes(
                ("batch", None, "heads", None), rules, mesh
            ),
            kv_spec=shd.spec_for_axes(
                ("batch", None, "kv_heads", None), rules, mesh
            ),
        )
    trainer = make_trainer_for_llama(
        cfg, mesh, strategy=strategy, attn_fn=attn_fn
    )
    free = partial(llama.next_token_loss, cfg=cfg, attn_fn=attn_fn)
    params, _ = trainer.init(jax.random.key(0))
    tokens = np.asarray(jax.random.randint(
        jax.random.key(1), (BATCH, SEQ), 0, cfg.vocab_size
    ))
    batch = jax.device_put((tokens, tokens), trainer.batch_sharding)

    # no mesh context: the constraints carry their own mesh
    pinned_text = jax.jit(trainer._loss_fn).lower(params, batch).as_text()
    free_text = jax.jit(free).lower(params, batch).as_text()
    assert pinned_text != free_text  # the callable was built and used

    loss, grads = jax.jit(jax.value_and_grad(trainer._loss_fn))(
        params, batch
    )
    loss_free, grads_free = jax.jit(jax.value_and_grad(free))(
        params, batch
    )
    np.testing.assert_allclose(
        float(loss), float(loss_free), rtol=1e-6
    )
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    flat_free = jax.tree.leaves(grads_free)
    assert len(flat) == len(flat_free)
    for (path, g), g_free in zip(flat, flat_free):
        # read: at most 5e-7 of the leaf's largest entry
        g_free = np.asarray(g_free)
        np.testing.assert_allclose(
            np.asarray(g), g_free, rtol=0,
            atol=4e-6 * np.abs(g_free).max(),
            err_msg=jax.tree_util.keystr(path),
        )


@pytest.mark.parametrize("strategy", ["fsdp", "zero1", "ddp"])
def test_init_lays_the_state_out_as_abstract_state_does(strategy):
    """One layout for a fresh state and a restored one. Left to
    propagation, Adam's moments came out of ``init`` replicated under
    ``fsdp`` (zeros propagate nothing): 15 GB a chip at Mistral-7B's
    16 layers."""
    mesh = create_mesh([("data", 1), ("fsdp", 4)], jax.devices()[:4])
    trainer = make_trainer_for_llama(
        llama.llama_tiny(), mesh, strategy=strategy
    )
    params, opt_state = state = trainer.init(jax.random.key(0))
    for got, want in zip(
        jax.tree.leaves(state), jax.tree.leaves(trainer.abstract_state())
    ):
        assert got.shape == want.shape and got.dtype == want.dtype
        assert got.sharding.is_equivalent_to(
            want.sharding, got.ndim
        ), (got.sharding, want.sharding)
    if strategy == "fsdp":  # a moment is sharded as its parameter is
        for p, mu in zip(
            jax.tree.leaves(params), jax.tree.leaves(opt_state[0].mu)
        ):
            assert mu.sharding.is_equivalent_to(p.sharding, p.ndim)
    wq = opt_state[0].mu["blocks"]["wq"]
    assert (wq.sharding.shard_shape(wq.shape) == wq.shape) == (
        strategy == "ddp"
    )
