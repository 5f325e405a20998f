"""The plain reference against the program's two model files, at
tiny sizes on the CPU, from the same seeded parameters."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import model_module_for
from yardstick import cells, reference, worker

SEQ = 64


def _case(name, dtype):
    with open(os.path.join(cells.HERE, "configs", name + ".json")) as f:
        config = json.load(f)
    config["dtype"] = dtype
    traffic = {"seq": SEQ, "remat": "off", "loss_chunk": 0}
    cfg = worker.program_config(config, traffic)
    model = model_module_for(cfg)
    params = model.init_params(jax.random.key(7), cfg)
    if config["family"] == "gpt":
        # zero-initialised biases would hide a dropped bias
        params["blocks"] = {
            k: (v + 0.05 if k.startswith("b") or k.endswith("bias")
                else v)
            for k, v in params["blocks"].items()
        }
    tokens, targets = worker.SeededTokens(
        5, SEQ, config["vocab_size"])(0, 3)
    program = float(model.next_token_loss(
        params, (jnp.asarray(tokens), jnp.asarray(targets)), cfg))
    return config, params, tokens, targets, program


@pytest.mark.parametrize("name", ["tiny-llama", "tiny-gpt"])
def test_float32_program_agrees_with_the_reference(name):
    config, params, tokens, targets, program = _case(name, "float32")
    ref = float(reference.loss(config, params, tokens, targets))
    assert abs(program - ref) < 2e-5, (program, ref)


@pytest.mark.parametrize("name", ["tiny-llama", "tiny-gpt"])
def test_bf16_program_is_inside_the_chip_tolerance(name):
    config, params, tokens, targets, program = _case(name, "bfloat16")
    ref = float(reference.loss(config, params, tokens, targets))
    assert abs(program - ref) < worker.REFERENCE_TOLERANCE


@pytest.mark.parametrize("name,key,value", [
    ("tiny-llama", "rope_theta", 500.0),
    ("tiny-llama", "rms_norm_eps", 1e-2),
    ("tiny-gpt", "layer_norm_epsilon", 1e-2),
])
def test_a_changed_term_shows(name, key, value):
    """The comparison sees a term of the block that is off."""
    config, params, tokens, targets, program = _case(name, "float32")
    ref = float(reference.loss(
        {**config, key: value}, params, tokens, targets))
    assert abs(program - ref) > 1e-4


def test_the_last_position_is_masked():
    tokens, targets = worker.SeededTokens(1, SEQ, 256)(10, 12)
    assert (targets[:, -1] == -1).all()
    np.testing.assert_array_equal(targets[:, :-1], tokens[:, 1:])


def test_longer_than_the_window_is_refused():
    config, params, tokens, targets, _ = _case("tiny-llama", "float32")
    with pytest.raises(ValueError):
        reference.loss({**config, "sliding_window": SEQ // 2}, params,
                       tokens, targets)
