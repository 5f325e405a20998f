"""The in-place grouped sum against megablox's, on the chip (dev tool).

``ops/pallas/grouped_sum.py add_grouped_product`` is megablox's
``tgmm(existing_out=...)`` without a grid step for a group that has no
row in the piece. What interpret mode cannot show is that the chip
leaves an aliased result's unvisited blocks alone. This script holds
it, at the shapes of a live chunk of the two cells that walk in
chunks (``--cell lfm2``: 10,240 rows against 8 experts of 2048 x 1792;
``--cell smallthinker``: 8,192 rows against 16 of 2560 x 768; the
gate/up and the down face of each, and ``add_rows``' sum of a chunk's
rows into blocks of 512 tokens), for pieces that hold rows of a few
consecutive experts, of one, of none, of the first and the last only,
and of all: the result equals megablox's bit for bit, and every group
without a row comes back as it went in. It then times both kernels,
``--n`` calls chained on one sum inside one program so that the sum
stays in place as it does in the walk (ms a call), and compares the
two sums after those calls too.

A row of JSON a case; the last line says ``"ok"``. Exit 1 where a
case differs. On no cell's path. Only a TPU run says anything:
``chiprun -- python3 benchmarks/check_grouped_sum.py``.
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.ops import grouped_matmul as gm
from dlrover_tpu.ops.pallas.grouped_sum import add_grouped_product

#: rows of a chunk, experts held, hidden and expert width, tokens
CELLS = {"lfm2": (10240, 8, 2048, 1792, 32768),
         "smallthinker": (8192, 16, 2560, 768, 16384)}


def pieces(rows, groups):
    """Group sizes of the pieces to try, by name: what a chunk of a
    walk over rows sorted by expert holds."""
    even = rows // groups
    few = np.zeros(groups, np.int64)
    few[1:4] = (rows // 4 + 37, rows // 4, rows // 4 - 200)
    one = np.zeros(groups, np.int64)
    one[groups // 2] = rows // 3 + 5
    ends = np.zeros(groups, np.int64)
    ends[[0, -1]] = (700, 1301)
    tail = np.zeros(groups, np.int64)
    tail[-1] = 130  # a layer's last, nearly empty chunk
    return {
        "few": few, "one": one, "none": np.zeros(groups, np.int64),
        "ends": ends, "tail": tail,
        "all": np.full(groups, even) - np.arange(groups),
    }


def megablox(into, lhs, rhs, sizes, tiling):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    return tgmm(
        lhs.swapaxes(0, 1), rhs, sizes, preferred_element_type=into.dtype,
        tiling=tiling, existing_out=into, interpret=gm._interpret(),
    )


def chained(kernel, n):
    """``n`` calls of ``kernel`` on one sum, in one program."""
    def run(into, lhs, rhs, sizes):
        return jax.lax.fori_loop(
            0, n, lambda _, acc: kernel(acc, lhs, rhs, sizes), into
        )
    return jax.jit(run, donate_argnums=0)


def ms_a_call(program, n, into, *operands):
    into = jax.block_until_ready(program(into + 0, *operands))
    t0 = time.perf_counter()
    into = jax.block_until_ready(program(into, *operands))
    return 1e3 * (time.perf_counter() - t0) / n, into


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cell", default="lfm2,smallthinker")
    ap.add_argument("--n", type=int, default=50)
    ap.add_argument("--out", default="chiprun_out/grouped_sum.jsonl")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("not a TPU: a CPU run shows nothing here", file=sys.stderr)
        return 1
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    ok = True
    for cell in args.cell.split(","):
        rows, held, hidden, width, tokens = CELLS[cell]
        for face, groups, (k, n), most in (
            ("gate_up", held, (hidden, width), gm.IN_PLACE_TILE),
            ("down", held, (width, hidden), gm.IN_PLACE_TILE),
            ("tokens", tokens // gm.ROW_BLOCK, (gm.ROW_BLOCK, hidden), None),
        ):
            tiling = gm.tiles(rows, k, n, most=most)
            keys = jax.random.split(jax.random.key(k), 3)
            lhs = jax.random.normal(keys[0], (rows, k), jnp.bfloat16)
            rhs = jax.random.normal(keys[1], (rows, n), jnp.bfloat16)
            into = jax.random.normal(keys[2], (groups, k, n), jnp.float32)

            ours = functools.partial(
                add_grouped_product, tiling=tiling,
                interpret=gm._interpret())
            theirs = functools.partial(megablox, tiling=tiling)
            once_ours, once_theirs = jax.jit(ours), jax.jit(theirs)
            often = {"ms": chained(ours, args.n),
                     "megablox_ms": chained(theirs, args.n)}
            for name, sizes in pieces(rows, groups).items():
                sizes = jnp.asarray(sizes, jnp.int32)
                got = once_ours(into, lhs, rhs, sizes)
                want = once_theirs(into, lhs, rhs, sizes)
                empty = np.asarray(sizes) == 0
                row = {
                    "cell": cell, "face": face, "piece": name,
                    "tiles": tiling, "groups_with_rows": int((~empty).sum()),
                    "equal": bool(jnp.array_equal(got, want)),
                    "empty_kept": bool(
                        jnp.array_equal(got[empty], into[empty])),
                    "changed": bool(
                        empty.all() or not jnp.array_equal(
                            got[~empty], into[~empty])),
                }
                sums = {}
                for label, program in often.items():
                    row[label], sums[label] = ms_a_call(
                        program, args.n, into, lhs, rhs, sizes)
                row["chained_equal"] = bool(jnp.array_equal(*sums.values()))
                ok = ok and all(row[key] for key in (
                    "equal", "empty_kept", "changed", "chained_equal"))
                print(json.dumps(row), flush=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
    print(json.dumps({"ok": ok, "device": jax.devices()[0].device_kind}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
