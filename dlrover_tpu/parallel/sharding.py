"""Sharding strategies as logical-axis rules (the GSPMD opt_lib).

Parity reference: atorch's entire optimization library collapses here —
 - DDP / parallel_mode (auto/opt_lib/parallel_mode_optimization.py:25)
 - ZeRO-1/2/FSDP (auto/opt_lib/zero_optimization.py:22,126)
 - Megatron TP row/col/vocab layers
   (modules/distributed_modules/layers.py:227,380,540) and the FX-graph
   TP compiler (compilers/tp_compiler.py)
 - mixed parallel (auto/opt_lib/mixed_parallel_optimization.py:33)

TPU-native redesign: one model definition + one mesh + a RULE TABLE mapping
*logical* array axes ("embed", "mlp", "heads", "vocab", "batch", ...) to
mesh axes. ``jit`` with these shardings makes XLA insert the all-gathers /
reduce-scatters the reference implemented as autograd-wrapped collectives
(modules/distributed_modules/mappings.py:23-424). A "strategy" is just a
named rule table; switching DP -> FSDP -> TP+FSDP changes no model code.

Logical axis conventions used by dlrover_tpu.models:
  batch      — per-example dim of activations/batches
  seq        — sequence dim of activations (context parallelism)
  embed      — transformer residual/hidden dim
  mlp        — MLP intermediate dim
  heads      — attention heads dim
  kv_heads   — KV heads dim (GQA)
  head_dim   — per-head dim (never sharded)
  vocab      — vocabulary dim
  expert     — MoE expert dim
  layers     — scan-stacked layer dim (pipeline stages)
  norm       — 1-D norm/bias scales
"""

import math
from typing import Any, Dict, Mapping, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.parallel.mesh import (
    DATA_AXIS, EXPERT_AXIS, FSDP_AXIS, PIPE_AXIS, SEQ_AXIS, TENSOR_AXIS,
    axis_size,
)

# a rule maps logical axis name -> mesh axis (str), tuple of mesh axes,
# or None (replicated)
Rules = Dict[str, Union[str, Tuple[str, ...], None]]

# ---------------------------------------------------------------------------
# strategy rule tables

def ddp_rules() -> Rules:
    """Pure data parallelism: params replicated, batch sharded."""
    return {"batch": (DATA_AXIS, FSDP_AXIS)}


def fsdp_rules() -> Rules:
    """ZeRO-3: every param split over fsdp on its first dim that is not
    ``layers`` (for a matmul's weight the forward contraction dim);
    batch over data+fsdp. The all-gather of a layer's weights before use
    and the reduce-scatter of their gradients (printed by the TPU
    compiler as an ``all-reduce-scatter`` fusion) are the torch FSDP
    wrap (zero_optimization.py:126) done by the compiler, provided the
    model pins its activations to ``batch`` (``constrain``, as
    ``trainer.sharded.make_trainer_for_llama`` does on a mesh of more
    than one device): a partitioner left free moves the smaller operand
    of ``x[batch/n, seq, embed] @ w[embed/n, mlp]``, which at a few
    sequences a chip is ``x``."""
    return {
        "batch": (DATA_AXIS, FSDP_AXIS),
        "embed": FSDP_AXIS,
        "vocab": FSDP_AXIS,
        "mlp": FSDP_AXIS,
        "heads": FSDP_AXIS,
        "kv_heads": FSDP_AXIS,
        "expert": FSDP_AXIS,
    }


def tp_rules() -> Rules:
    """Megatron TP: column-parallel on mlp/heads, row-parallel comes out of
    the matching contraction; vocab-parallel embedding."""
    return {
        "batch": (DATA_AXIS, FSDP_AXIS),
        "mlp": TENSOR_AXIS,
        "heads": TENSOR_AXIS,
        "kv_heads": TENSOR_AXIS,
        "vocab": TENSOR_AXIS,
    }


def tp_fsdp_rules() -> Rules:
    """3D: fsdp shards the embed dim, tensor shards mlp/heads/vocab."""
    return {
        "batch": (DATA_AXIS, FSDP_AXIS),
        "embed": FSDP_AXIS,
        "mlp": TENSOR_AXIS,
        "heads": TENSOR_AXIS,
        "kv_heads": TENSOR_AXIS,
        "vocab": TENSOR_AXIS,
        "expert": EXPERT_AXIS,
    }


def sequence_rules() -> Rules:
    """Long-context: activations' seq dim over the seq axis (ring/blockwise
    attention handles the cross-shard scores — see ops.ring_attention)."""
    r = tp_fsdp_rules()
    r["seq"] = SEQ_AXIS
    return r


def pipeline_rules() -> Rules:
    """GSPMD pipelining: the scan-stacked layer dim over the pipe axis."""
    r = tp_fsdp_rules()
    r["layers"] = PIPE_AXIS
    return r


def zero1_rules() -> Rules:
    """ZeRO-1 (parity: auto/opt_lib/zero_optimization.py:22): params and
    grads replicated like DDP, but the OPTIMIZER STATE is sharded over
    fsdp — see ``opt_state_rules``. The jitted step then reduce-scatters
    grads into the sharded Adam update and all-gathers the delta, cutting
    the dominant Adam m+v footprint by the fsdp factor while keeping
    DDP's simple layout. Use when params fit in HBM but Adam state
    doesn't."""
    return {"batch": (DATA_AXIS, FSDP_AXIS)}


def zero2_rules() -> Rules:
    """ZeRO-2 (parity: zero_optimization.py:53): ZeRO-1 plus sharded
    gradient accumulation — the grad buffer (and scan carry, under
    accumulation) is constrained to the fsdp layout, so grads are
    reduce-scattered once instead of held replicated."""
    return {"batch": (DATA_AXIS, FSDP_AXIS)}


def rowwise_rules() -> Rules:
    """Sparse-embedding (DLRM-class) layout: table rows over fsdp,
    batch over data ONLY — the vocab-parallel lookup psums over the
    table axis, so every table shard must see the same batch slice
    (parallel/embedding.py). Dense MLPs stay replicated (tiny); their
    grads all-reduce over data as in DDP."""
    return {
        "batch": DATA_AXIS,
        "vocab": FSDP_AXIS,
    }


STRATEGIES = {
    "ddp": ddp_rules,
    "zero1": zero1_rules,
    "zero2": zero2_rules,
    "fsdp": fsdp_rules,
    "tp": tp_rules,
    "tp_fsdp": tp_fsdp_rules,
    "sequence": sequence_rules,
    "pipeline": pipeline_rules,
    "rowwise": rowwise_rules,
}

# strategies whose optimizer state is sharded differently from params.
# The rule table shards every param logical axis over fsdp — applied to
# the param-shaped subtrees of the optax state (opt_state_shardings).
_ZERO_OPT_RULES = {
    "embed": FSDP_AXIS,
    "vocab": FSDP_AXIS,
    "mlp": FSDP_AXIS,
    "heads": FSDP_AXIS,
    "kv_heads": FSDP_AXIS,
    "expert": FSDP_AXIS,
}


def opt_state_rules(strategy: str) -> Optional[Rules]:
    """Rule table for optimizer-state sharding when it differs from the
    param layout (ZeRO-1/2); None means "mirror the params"."""
    if strategy in ("zero1", "zero2"):
        return dict(_ZERO_OPT_RULES)
    return None


def grad_rules(strategy: str) -> Optional[Rules]:
    """Rule table constraining gradient layout (ZeRO-2); None leaves
    the layout to XLA."""
    if strategy == "zero2":
        return dict(_ZERO_OPT_RULES)
    return None


def get_rules(strategy: str) -> Rules:
    if strategy not in STRATEGIES:
        raise ValueError(
            f"Unknown strategy {strategy!r}; one of {sorted(STRATEGIES)}"
        )
    return STRATEGIES[strategy]()


# ---------------------------------------------------------------------------
# applying rules

def spec_for_axes(
    logical_axes: Tuple[Optional[str], ...],
    rules: Rules,
    mesh: Optional[Mesh] = None,
) -> P:
    """Turn a tuple of logical axis names into a PartitionSpec.

    Mesh axes not present in the mesh (or of size 1) degrade to
    replication, so one rule table serves every mesh shape. A mesh axis is
    used at most once per spec (XLA requirement) — first logical axis wins.
    """
    used = set()
    parts = []
    for ax in logical_axes:
        rule = rules.get(ax) if ax is not None else None
        if rule is None:
            parts.append(None)
            continue
        mesh_axes = (rule,) if isinstance(rule, str) else tuple(rule)
        if mesh is not None:
            mesh_axes = tuple(
                m for m in mesh_axes
                if m in mesh.axis_names and axis_size(mesh, m) > 1
            )
        mesh_axes = tuple(m for m in mesh_axes if m not in used)
        used.update(mesh_axes)
        if not mesh_axes:
            parts.append(None)
        elif len(mesh_axes) == 1:
            parts.append(mesh_axes[0])
        else:
            parts.append(mesh_axes)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def tree_shardings(
    axes_tree: Any, mesh: Mesh, rules: Rules
) -> Any:
    """Map a pytree of logical-axes tuples to NamedShardings.

    ``axes_tree`` mirrors the param tree, with each leaf a tuple like
    ``("embed", "mlp")``. Leaves that are None are fully replicated.
    """

    def leaf(axes):
        if axes is None:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, spec_for_axes(tuple(axes), rules, mesh))

    return jax.tree.map(
        leaf, axes_tree,
        is_leaf=lambda x: x is None or (
            isinstance(x, tuple)
            and all(a is None or isinstance(a, str) for a in x)
        ),
    )


def gathers_params(axes_tree: Any, mesh: Mesh, rules: Rules) -> bool:
    """Whether a step under ``rules`` on ``mesh`` has to gather the
    parameters of ``axes_tree`` before it uses them, and reduce-scatter
    their gradients (ZeRO-3): some leaf is split over a mesh axis that
    the batch is split over too, so a device holds a part of the
    weight and rows that need all of it. False on one device, with
    replicated parameters (``ddp``, ``zero1``), and where the weights
    are split over axes of their own (``tp``: the matmul is split with
    them and nothing is gathered)."""
    batch_axes = set(
        jax.tree.leaves(tuple(spec_for_axes(("batch",), rules, mesh)))
    )
    return any(
        batch_axes.intersection(jax.tree.leaves(tuple(sharding.spec)))
        for sharding in jax.tree.leaves(
            tree_shardings(axes_tree, mesh, rules)
        )
    )


def opt_state_shardings(
    abs_opt_state: Any,
    abs_params: Any,
    param_shardings: Any,
    mesh: Mesh,
) -> Any:
    """Shardings for an optax state whose param-shaped subtrees should
    follow ``param_shardings`` (computed under e.g. the ZeRO opt rules)
    and whose other leaves (step counts, scalars) are replicated.

    Optax states embed zero or more subtrees with exactly the params'
    treedef (adam: mu and nu); we match on treedef rather than leaf
    shapes so wrapped/chained transforms keep working.
    """
    pdef = jax.tree.structure(abs_params)
    replicated = NamedSharding(mesh, P())

    def is_param_subtree(sub) -> bool:
        try:
            return jax.tree.structure(sub) == pdef
        except Exception:
            return False

    return jax.tree.map(
        lambda sub: param_shardings if is_param_subtree(sub)
        else replicated,
        abs_opt_state,
        is_leaf=is_param_subtree,
    )


def batch_sharding(mesh: Mesh, rules: Rules,
                   extra_axes: Tuple[Optional[str], ...] = ()) -> (
        NamedSharding):
    """Sharding for a [batch, ...] array (e.g. token ids [batch, seq])."""
    return NamedSharding(
        mesh, spec_for_axes(("batch",) + tuple(extra_axes), rules, mesh)
    )


def fit_spec(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """``spec`` with every dim of ``shape`` that its mesh axes do not
    divide left whole instead (kv heads fewer than the tensor axis, a
    batch smaller than the mesh)."""
    parts = []
    for dim, axes in zip(shape, tuple(spec) + (None,) * len(shape)):
        names = (axes,) if isinstance(axes, str) else (axes or ())
        n = math.prod(mesh.shape[a] for a in names)
        parts.append(axes if dim % n == 0 else None)
    return P(*parts)


def constrain(x, mesh: Mesh, rules: Rules,
              logical_axes: Tuple[Optional[str], ...]):
    """In-model sharding hint (replaces the reference's explicit collective
    mappings): ``constrain(h, mesh, rules, ("batch", "seq", "embed"))``.
    A NamedSharding over ``mesh``, so it needs no mesh context."""
    spec = fit_spec(spec_for_axes(logical_axes, rules, mesh), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
