"""Deadlines for the gradients' reductions where the step gathers its
weights (``models/llama.py _tie``, engaged by
``trainer.sharded.make_trainer_for_llama`` from its mesh and rule
table): which meshes engage them, that a mesh which gathers nothing
traces the program it always traced, and that a tied step computes
what an untied one does."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.parallel import sharding as shd
from dlrover_tpu.parallel.mesh import create_mesh
from dlrover_tpu.telemetry.registry import default_registry
from dlrover_tpu.trainer.sharded import make_trainer_for_llama

GAUGE = "dlrover_trainer_gradient_deadlines"


def _dense(**kw):
    return llama.llama_tiny(**{**dict(
        num_layers=3, dtype=jnp.float32), **kw})


def _by_position(**kw):
    """A stack kept by position: three periods of an attention layer
    and a gated short convolution."""
    return _dense(**{**dict(
        num_layers=6, layer_types=("full_attention", "conv") * 3), **kw})


def _experts(**kw):
    return llama.llama_moe_tiny(**{**dict(dtype=jnp.float32), **kw})


def _trainer(cfg, strategy, axes):
    mesh = create_mesh(axes, devices=jax.devices()[
        :int(np.prod([n for _, n in axes]))])
    return make_trainer_for_llama(cfg, mesh, strategy=strategy)


def _barriers(jaxpr):
    """How many ``optimization_barrier``s ``jaxpr`` holds, those
    inside other equations' jaxprs too."""
    count = 0
    for eqn in jaxpr.eqns:
        count += eqn.primitive.name == "optimization_barrier"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    count += _barriers(sub)
    return count


def _grad_jaxpr(trainer, batch=4, seq=32):
    params, _ = trainer.abstract_state()
    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    return jax.make_jaxpr(jax.value_and_grad(trainer._loss_fn))(
        params, (tok, tok))


FSDP4 = [("data", 1), ("fsdp", 4)]


@pytest.mark.parametrize("case,strategy,axes,engaged", [
    # what gathers nothing traces what it traced: one device, and
    # weights that are whole on every device
    ("one device", "fsdp", [("data", 1), ("fsdp", 1)], False),
    ("ddp", "ddp", FSDP4, False),
    ("zero1", "zero1", FSDP4, False),
    # weights split over an axis of their own: the matmul is split
    # with them
    ("tp", "tp", [("data", 1), ("tensor", 4)], False),
    # ZeRO-3: the batch rides the axis the weights are split over
    ("fsdp", "fsdp", FSDP4, True),
    ("fsdp beside data", "fsdp", [("data", 2), ("fsdp", 2)], True),
    ("tp_fsdp", "tp_fsdp", [("fsdp", 2), ("tensor", 2)], True),
])
def test_the_mesh_and_the_rule_table_decide(case, strategy, axes, engaged):
    cfg = _dense()
    trainer = _trainer(cfg, strategy, axes)
    assert default_registry().get(GAUGE).value == int(engaged)
    assert shd.gathers_params(
        llama.param_axes(cfg)["blocks"], trainer.mesh, trainer.rules
    ) == engaged
    # a tied layer holds two barriers, and the scan's body one layer
    assert _barriers(_grad_jaxpr(trainer).jaxpr) == (2 if engaged else 0)


@pytest.mark.parametrize("remat", ["off", "dots", "dots_attn_out", "minimal"])
def test_without_gathered_weights_the_jaxpr_is_the_one_without_the_argument(
    remat,
):
    cfg = _dense(remat=remat)
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cfg))
    tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)

    def text(**kw):
        return str(jax.make_jaxpr(jax.value_and_grad(
            lambda p, b: llama.next_token_loss(p, b, cfg, **kw)
        ))(params, (tok, tok)))

    assert text() == text(gathered_weights=False)
    assert "optimization_barrier" not in text()
    assert "optimization_barrier" in text(gathered_weights=True)


@pytest.mark.parametrize("make", [
    _dense, lambda: _dense(remat="dots_attn_out"), _by_position, _experts,
    lambda: llama.llama_loop_tiny(dtype=jnp.float32),
], ids=["dense", "dense, dots_attn_out", "kept by position", "experts",
        "a looped stack"])
def test_a_tied_step_computes_what_an_untied_one_does(make, monkeypatch):
    """Loss and every gradient leaf under ``fsdp`` over four of the
    suite's eight CPU devices, with the ties and without (the same
    mesh, constraints and attention: only the rule's answer differs),
    in float32. A tie is the identity forward and backward, so the
    mathematics does not change; the compiler may fuse what stands on
    the two sides of a barrier otherwise, so the tolerance is
    float32's rounding and not zero."""
    cfg = make()
    tied = _trainer(cfg, "fsdp", FSDP4)
    assert default_registry().get(GAUGE).value == 1
    monkeypatch.setattr(shd, "gathers_params", lambda *a: False)
    untied = _trainer(cfg, "fsdp", FSDP4)
    assert default_registry().get(GAUGE).value == 0
    params, _ = tied.init(jax.random.key(0))
    tokens = jax.random.randint(
        jax.random.key(1), (4, 32), 0, cfg.vocab_size)
    batch = jax.tree.map(
        lambda x: jax.device_put(x, tied.batch_sharding),
        (tokens, jnp.roll(tokens, -1, axis=1)))
    results = []
    for trainer in (untied, tied):
        with trainer.mesh:
            results.append(jax.jit(jax.value_and_grad(trainer._loss_fn))(
                params, batch))
    (loss_0, grads_0), (loss_1, grads_1) = results
    np.testing.assert_allclose(loss_0, loss_1, rtol=1e-6)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(grads_0),
        jax.tree.leaves(grads_1),
    ):
        np.testing.assert_allclose(
            a, b, rtol=2e-5, atol=1e-5, err_msg=str(path))
    moved = [float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(grads_1)]
    assert sum(moved) >= len(moved) - 2, moved  # a frozen bias or two


def test_a_tie_holds_the_weights_gradient_before_the_cotangent_goes_on():
    """By hand: forward nothing; backward one barrier around the
    held value's cotangent and the weights' gradients, each laid out
    by ``constrain`` with its own axes."""
    seen = []

    def constrain(x, axes):
        seen.append(axes)
        return x

    def f(x, w):
        x, w = llama._tie(
            constrain, x, w, {"a": ("embed", "mlp"), "b": ("norm",)})
        return jnp.sum(x @ w["a"] * w["b"])

    x = jnp.ones((2, 3))
    w = {"a": jnp.ones((3, 4)), "b": jnp.full((4,), 2.0)}
    assert "optimization_barrier" not in str(jax.make_jaxpr(f)(x, w))
    grads = jax.make_jaxpr(jax.grad(f, argnums=(0, 1)))(x, w)
    assert _barriers(grads.jaxpr) == 1
    assert sorted(seen) == [("embed", "mlp"), ("norm",)]
    dx, dw = jax.grad(f, argnums=(0, 1))(x, w)
    np.testing.assert_array_equal(dx, jnp.full((2, 3), 8.0))
    np.testing.assert_array_equal(dw["a"], jnp.full((3, 4), 4.0))
    np.testing.assert_array_equal(dw["b"], jnp.full((4,), 6.0))
