"""Time the gated delta rule's kernels, and hold them to the plain
chunked path's values (dev tool).

``ops/delta_rule.py gated_delta_rule`` runs, on the TPU, the Pallas
kernels of ``ops/pallas/delta_rule.py``; elsewhere the chunked
equations under a ``lax.scan``. This script times the kernels at
``solar-open2-250b-ep32.steady``'s shape (64 heads of 128 at 8,192
positions in bf16, the log decay in float32): the forward, the
forward that keeps the chunks' entry states with the backward over
them, with the least time the memory allows beside each
(``yardstick/families/solar.py delta_rule_step``'s bytes at 819
GB/s). Each on rows ``[1, 8192, 8192]``, which is what the step
calls (``gated_delta_rule_rows``), and beside it through the 4-D
entry on ``[1, 8192, 64, 128]``, whose fold to rows is a pass over
every operand and result on the chip; and compares ``o`` and the
five gradients with the plain path's on ``--check-heads`` of the
heads (the plain path holds ``[heads, 64, 64, 128]`` float32 a
chunk), at the decays ``--decay`` lists (``g`` uniform in ``-decay x
[0.2, 1]``). The entry ``gated_delta_rule`` takes no step under
``G_FLOOR`` (-10): a decay of 20 is compared through the entry, which
is what the kernels' exactness rests on there.

``--heads-per-step 1 2 4 8`` times the three kernels alone at each of
those numbers of heads a grid step, whatever the kernels' own rule
(``heads_a_step``) takes for the shape, prints the microseconds a
chunk of one head costs beside the milliseconds a call, and holds each
one's ``o`` and gradients to the first's bit for bit. Beside each it
times the backward as it was before it read what the forward solved
(``solving_again``: it makes a chunk's inverse and ``w`` from its
operands, the blocks of the kept ones fetched and not read) and holds
the kernels' gradients to that one's, bit for bit. ``--floors`` adds
two readings at each, and alone takes the rule's own heads a step: a
grid step whose body is empty (the blocks' DMAs and the step's own
cost) and one whose ``_inverse`` makes no product (the forward's body
without its longest chain; the backward makes no inverse, so its
reading there is its own).

``--a-head`` takes the other form of decay, one a head (``g`` in
``beta``'s shape), on heads of ``--key-dim`` keys by ``--value-dim``
values: ``olmo-hybrid-7b-vp8.steady``'s shape is ``--a-head --heads 30
--seq 16384 --key-dim 96 --value-dim 192`` (``delta_rule`` pads a head
to 128 x 256 inside, so beside the calls as the step makes them it
times the kernels alone on operands that come padded: the difference
is the pads' and the cuts'); the least time is
``yardstick/families/olmo_hybrid.py delta_rule_step``'s bytes, and the
decays compared may pass ``G_FLOOR``, which this form does not have.

On no cell's path. Only a TPU run says anything:
``chiprun -- python3 benchmarks/profile_delta_rule.py``.
"""

import argparse
import contextlib
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dlrover_tpu.ops.delta_rule import (  # noqa: E402
    gated_delta_rule, gated_delta_rule_plain, gated_delta_rule_rows,
)
from dlrover_tpu.ops.pallas import delta_rule as kernels  # noqa: E402

HBM_BYTES_PER_S = 819e9  # yardstick/peaks.json, "TPU v5 lite"
NAMES = ("q", "k", "v", "g", "beta")
RULE, FORWARD_RULE = kernels.HEADS_A_STEP, kernels.FORWARD_HEADS_A_STEP


def timed(fn, *args, n=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def operands(batch, seq, heads, decay, dtype, seed=0, dk=kernels.HEAD,
             dv=kernels.HEAD, a_head=False):
    """``(q, k, v, g, beta), do`` on heads of ``dk`` keys by ``dv``
    values; ``g`` a number a channel, or with ``a_head`` one a head."""
    keys = jax.random.split(jax.random.key(seed), 6)
    shape, wide = (batch, seq, heads, dk), (batch, seq, heads, dv)
    # keys that resemble each other, as silu's leave them
    q, k = (jax.nn.silu(jax.random.normal(key, shape)) for key in keys[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.nn.silu(jax.random.normal(keys[2], wide))
    g = -decay * jax.random.uniform(
        keys[3], shape[:3] if a_head else shape, minval=0.2)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3]))
    do = jax.random.normal(keys[5], wide).astype(dtype)
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta), do


def as_rows(ops):
    """The operands as the rows the step hands over: a head's columns
    side by side, ``g`` a head's number as it is."""
    return (*(x.reshape(*x.shape[:2], -1) for x in ops[:3]),
            ops[3].reshape(*ops[3].shape[:2], -1) if ops[3].ndim == 4
            else ops[3], ops[4])


def at_heads_a_step(together):
    """Have the kernels built from here on take ``together`` heads a
    grid step (``None``: what their rule takes), whatever was traced
    before."""
    kernels.HEADS_A_STEP = RULE if together is None else (together,)
    kernels.FORWARD_HEADS_A_STEP = (
        FORWARD_RULE if together is None else (together,))
    jax.clear_caches()


def _empty_forward(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest,
                   **_):
    o_ref[...] = v_ref[...]


def _empty_backward(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref,
                    inv_ref, w_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                    dbeta_ref, dstate, **_):
    dq_ref[...] = q_ref[...]
    dk_ref[...] = k_ref[...]
    dv_ref[...] = do_ref[...]
    dg_ref[...] = g_ref[...]
    dbeta_ref[...] = beta_ref[...]


def _no_inverse(n):
    return n
    yield


def _solved_again(cs, inv_ref, w_ref):
    return kernels._solved(cs)


#: the backward before PR 61: a chunk's inverse and ``w`` made again
#: from its operands, as the forward makes them
SOLVING_AGAIN = dict(_kept=_solved_again)
#: what a grid step costs with less in it: the blocks' DMAs and the
#: step's own cost alone, and the body without the inverse's chain of
#: ten float32 products (the results are then no delta rule's)
FLOORS = {
    "empty_body": dict(_fwd_kernel=_empty_forward,
                       _bwd_kernel=_empty_backward),
    "no_inverse": dict(_inverse=_no_inverse),
}


@contextlib.contextmanager
def built_with(stubs, together):
    """The kernels built inside with ``stubs`` for their parts, at
    ``together`` heads a grid step."""
    real = {name: getattr(kernels, name) for name in stubs}
    for name, stub in stubs.items():
        setattr(kernels, name, stub)
    at_heads_a_step(together)
    try:
        yield
    finally:
        for name, fn in real.items():
            setattr(kernels, name, fn)
        at_heads_a_step(together)


def kernel_times(flat, do, n):
    """Milliseconds a call of the forward kernel, of the forward that
    keeps what the backward reads and of the backward kernel over
    that, as built now."""
    keep = jax.jit(functools.partial(kernels.delta_rule, keep_states=True))
    _, kept = keep(*flat)
    return {
        "forward_ms": 1e3 * timed(jax.jit(kernels.delta_rule), *flat, n=n),
        "forward_keeping_states_ms": 1e3 * timed(keep, *flat, n=n),
        "backward_ms": 1e3 * timed(
            jax.jit(lambda *a: kernels.delta_rule(
                *a[:5], kept=a[5], do=a[6])), *flat, kept, do, n=n),
    }


def a_heads_chunk(times, shape, heads):
    """``times`` with, beside each call's milliseconds, the
    microseconds it takes a chunk of one head."""
    batch, seq, _ = shape
    chunks = batch * heads * (seq // kernels.CHUNK)
    out = {}
    for name, ms in times.items():
        out[name] = round(ms, 4)
        out[name.replace("_ms", "_us_a_heads_chunk")] = round(
            1e3 * ms / chunks, 4)
    return out


def gradients_of(fn):
    def gradients(args, do):
        _, back = jax.vjp(fn, *args)
        return back(do)

    return jax.jit(gradients)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--check-heads", type=int, default=4)
    ap.add_argument("--check-seq", type=int, default=2048)
    ap.add_argument("--decay", type=float, nargs="+",
                    default=[0.3, 5.0, 20.0])
    ap.add_argument("--n", type=int, default=10,
                    help="calls timed; 0 skips the timing")
    ap.add_argument("--heads-per-step", type=int, nargs="+", default=[],
                    help="time the kernels alone at each of these heads "
                         "a grid step (each divides --heads), whatever "
                         "their rule takes, and hold each one's results "
                         "to the first's, bit for bit")
    ap.add_argument("--floors", action="store_true",
                    help="at each of --heads-per-step (without it, at "
                         "the heads a step the kernels' rule takes) also "
                         "time a grid step with an empty body and one "
                         "whose _inverse makes no product")
    ap.add_argument("--a-head", action="store_true",
                    help="one decay a head, on heads of --key-dim keys "
                         "by --value-dim values")
    ap.add_argument("--key-dim", type=int, default=kernels.HEAD)
    ap.add_argument("--value-dim", type=int, default=kernels.HEAD)
    ap.add_argument("--out", default="chiprun_out/delta_rule.jsonl")
    args = ap.parse_args(argv)
    if not args.a_head and (args.key_dim, args.value_dim) != (
            kernels.HEAD, kernels.HEAD):
        ap.error("a decay a channel is on heads of 128 x 128")
    form = dict(dk=args.key_dim, dv=args.value_dim, a_head=args.a_head)
    if jax.default_backend() != "tpu":
        print("not a TPU: a CPU run times nothing", file=sys.stderr)
        return 1
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    positions = args.batch * args.seq * args.heads
    column, betas = positions * kernels.HEAD, 4 * positions
    least = {
        "forward_ms": 1e3 * ((8 + 4) * column + betas) / HBM_BYTES_PER_S,
        "gradients_ms": 1e3 * (
            (8 + 4) * column + betas + (6 + 4) * column + betas
        ) / HBM_BYTES_PER_S,
    }
    if args.a_head:
        # q, k, v and o in bf16, g and beta a float32 a head; then the
        # five operands and do read and the five gradients written
        keys, values = (2 * positions * d
                        for d in (args.key_dim, args.value_dim))
        forward = 2 * keys + 2 * values + 2 * betas
        least = {
            "forward_ms": 1e3 * forward / HBM_BYTES_PER_S,
            "gradients_ms": 1e3 * (
                forward + 2 * keys + values + 2 * betas
            ) / HBM_BYTES_PER_S,
        }
    rows = []
    ops, do = operands(
        args.batch, args.seq, args.heads, 0.3, jnp.bfloat16, **form)
    flat = as_rows(ops)
    flat_do = do.reshape(flat[2].shape)
    first = None
    if args.floors and not args.heads_per_step:
        args.heads_per_step = [kernels.heads_a_step(args.heads)]
    for together in args.heads_per_step if args.n else []:
        at_heads_a_step(together)
        try:
            times = kernel_times(flat, flat_do, args.n)
        except jax.errors.JaxRuntimeError as e:
            # more heads a step than VMEM holds: said, and on
            rows.append({"what": "kernels alone", "heads_a_step": together,
                         "refused": str(e)[:200]})
            print(json.dumps(rows[-1]), flush=True)
            continue
        row = {"what": "kernels alone", "shape": list(flat[0].shape),
               "heads_a_step": kernels.heads_a_step(args.heads),
               **a_heads_chunk(times, flat[0].shape, args.heads)}
        got = (kernels.delta_rule(*flat),
               *gradients_of(kernels.delta_rule_tpu)(flat, flat_do))
        first = first or got
        row["same_bits_as_first"] = all(
            bool((a == b).all()) for a, b in zip(got, first))
        with built_with(SOLVING_AGAIN, together):
            row.update(a_heads_chunk({
                "backward_solving_again_ms": kernel_times(
                    flat, flat_do, args.n)["backward_ms"]}, flat[0].shape,
                args.heads))
            row["same_bits_as_solving_again"] = all(
                bool((a == b).all()) for a, b in zip(
                    got[1:],
                    gradients_of(kernels.delta_rule_tpu)(flat, flat_do)))
        rows.append(row)
        print(json.dumps(row), flush=True)
        for name, stubs in FLOORS.items() if args.floors else ():
            with built_with(stubs, together):
                rows.append({
                    "what": name, "heads_a_step": together,
                    **a_heads_chunk(kernel_times(flat, flat_do, args.n),
                                    flat[0].shape, args.heads)})
            print(json.dumps(rows[-1]), flush=True)
    at_heads_a_step(None)
    if args.a_head and args.n:
        # the kernels alone, on operands that come padded to whole
        # lane tiles: what the calls below pay beside them is the
        # pads' and the cuts'
        wide = kernels.padded_values(args.value_dim)
        padded = (
            kernels._padded(flat[0], args.heads, kernels.HEAD),
            kernels._padded(flat[1], args.heads, kernels.HEAD),
            kernels._padded(flat[2], args.heads, wide), *flat[3:])
        rows.append({
            "what": "kernels alone, operands padded beforehand",
            "shape": [list(x.shape) for x in padded[:3]],
            "heads_a_step": kernels.heads_a_step(args.heads),
            **a_heads_chunk(
                kernel_times(padded, kernels._padded(
                    flat_do, args.heads, wide), args.n),
                flat[0].shape, args.heads)})
        print(json.dumps(rows[-1]), flush=True)
    row = {"what": "kernels timed", "shape": list(ops[0].shape),
           "values": args.value_dim,
           "decay": "a head" if args.a_head else "a channel",
           "chunk": kernels.CHUNK, "sub": kernels.SUB,
           "heads_a_step": kernels.heads_a_step(args.heads),
           **{"least_" + k: round(v, 4) for k, v in least.items()}}
    if args.n:
        on_rows = functools.partial(gated_delta_rule_rows, heads=args.heads)
        row["forward_ms"] = 1e3 * timed(jax.jit(on_rows), *flat, n=args.n)
        row["forward_keeping_states_ms"] = 1e3 * timed(jax.jit(
            functools.partial(kernels.delta_rule, keep_states=True)),
            *flat, n=args.n)
        row["forward_and_gradients_ms"] = 1e3 * timed(
            gradients_of(on_rows), flat, flat_do, n=args.n)
        row["forward_from_heads_ms"] = 1e3 * timed(
            jax.jit(gated_delta_rule), *ops, n=args.n)
        row["forward_and_gradients_from_heads_ms"] = 1e3 * timed(
            gradients_of(gated_delta_rule), ops, do, n=args.n)
        rows.append(row)
        print(json.dumps(row), flush=True)
    for dtype in (jnp.float32, jnp.bfloat16):
        for decay in args.decay:
            ops, do = operands(
                args.batch, args.check_seq, args.check_heads, decay, dtype,
                seed=1, **form)
            # the plain path's float32 products at the highest
            # precision: the chip's default rounds them to bfloat16
            with jax.default_matmul_precision("highest"):
                want_o = gated_delta_rule_plain(
                    *(x.astype(jnp.float32) for x in ops))
                want = gradients_of(gated_delta_rule_plain)(
                    tuple(x.astype(jnp.float32) for x in ops),
                    do.astype(jnp.float32))
            got_o = gated_delta_rule(*ops)
            got = gradients_of(gated_delta_rule)(ops, do)
            row = {"what": "kernels against the plain path",
                   "dtype": jnp.dtype(dtype).name, "decay": decay,
                   "least_g": float(ops[3].min()),
                   "shape": list(ops[0].shape)}

            def off(a, b):
                scale = float(jnp.abs(b).max())
                return float(jnp.abs(
                    a.astype(jnp.float32) - b).max()) / scale

            row["o_off"] = off(got_o, want_o)
            for name, a, b in zip(NAMES, got, want):
                row[f"d{name}_off"] = off(a, b)
            row["finite"] = all(
                bool(jnp.isfinite(x.astype(jnp.float32)).all())
                for x in (got_o, *got))
            rows.append(row)
            print(json.dumps(row), flush=True)
    with open(args.out, "a") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
