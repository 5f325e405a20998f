"""How much of a tiny configuration's bf16 reading is flipped expert
choices: the bf16 program's loss against the float32 reference's, as
``tests/yardstick/test_yardstick_<family>.py``'s
``test_bf16_program_is_inside_the_chip_tolerance`` compares them, once
with the router's own top-k and once with every layer's choice pinned
to the reference's (``jax.lax.top_k`` replaced while the program
traces: the weights stay the program's own scores at the pinned
experts). A sandbox tool for the CPU, not a cell: run it from the root
of the tree to be read, the parent's copy too,

    JAX_PLATFORMS=cpu python benchmarks/pinned_routing.py kimi 1-12

One line a seed: the free difference, the pinned one, and the share of
the reference's choices that the eager bf16 program does not make.
"""

import importlib
import os
import sys
from unittest import mock

sys.path[:0] = [os.getcwd(), os.path.join(os.getcwd(), "tests", "yardstick")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

TOP_K = jax.lax.top_k


def recorded(run):
    """``run()`` without jit and the choices of each ``top_k`` call in
    the order the layers make them."""
    seen = []

    def top_k(x, k):
        values, chosen = TOP_K(x, k)
        seen.append(np.asarray(chosen))
        return values, chosen

    with mock.patch.object(jax.lax, "top_k", top_k), jax.disable_jit():
        return run(), seen


def pinned(run, choices):
    """``run()`` with each ``top_k`` call answering the next of
    ``choices``: every call site has to be traced once."""
    left = list(choices)

    def top_k(x, k):
        chosen = jnp.asarray(left.pop(0)).reshape(x.shape[:-1] + (k,))
        return jnp.take_along_axis(x, chosen, axis=-1), chosen

    with mock.patch.object(jax.lax, "top_k", top_k):
        value = run()
    assert not left, f"{len(left)} recorded choices were not asked for"
    return value


def flipped(ours, theirs) -> float:
    """The share of ``theirs``' choices that are not among ``ours``."""
    missed = total = 0
    for a, b in zip(ours, theirs):
        a = a.reshape(-1, a.shape[-1])
        b = b.reshape(a.shape)
        missed += int((~(b[:, :, None] == a[:, None, :]).any(-1)).sum())
        total += b.size
    return missed / total


def main():
    family, seeds = sys.argv[1], sys.argv[2]
    first, _, last = seeds.partition("-")
    case = importlib.import_module(f"test_yardstick_{family}")
    for seed in range(int(first), int(last or first) + 1):
        cfg_file, cfg, params, batch = case._case(
            "bfloat16", False, 8, seed=seed)

        def program():
            return float(jax.jit(lambda p, b: case.llama.next_token_loss(
                p, b, cfg))(params, batch))

        ref, choices = recorded(lambda: float(
            case.reference.loss(cfg_file, params, *batch)))
        _, ours = recorded(lambda: float(
            case.llama.next_token_loss(params, batch, cfg)))
        print(
            f"seed {seed}: free {abs(program() - ref):.6f} pinned "
            f"{abs(pinned(program, choices) - ref):.6f} flipped "
            f"{flipped(ours, choices):.4%} of {sum(c.size for c in choices)}",
            flush=True)


if __name__ == "__main__":
    main()
