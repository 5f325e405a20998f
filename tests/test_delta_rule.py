"""The gated delta rule (ops/delta_rule.py): the plain chunked path
and the Pallas kernels in interpret mode against the recurrence walked
token by token, ``o`` and all five gradients; the entry on rows
against the entry on heads."""

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.ops import delta_rule
from dlrover_tpu.ops.pallas import delta_rule as kernels

D = kernels.HEAD


def recurrence(q, k, v, g, beta):
    """``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t
    k_t v_t^T``, ``o_t = S_t^T q_t / sqrt(d)``, a position at a time,
    float32."""
    b, s, h, d = q.shape

    def step(state, x):  # [b, h, keys, values]
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[..., None]
        held = jnp.einsum("bhc,bhcv->bhv", k_t, state)
        state = state + (
            (beta_t[..., None] * k_t)[..., None]
            * (v_t - held)[..., None, :]
        )
        return state, jnp.einsum("bhc,bhcv->bhv", q_t, state)

    xs = tuple(
        jnp.moveaxis(x.astype(jnp.float32), 1, 0)
        for x in (q, k, v, g, beta)
    )
    _, o = jax.lax.scan(
        step, jnp.zeros((b, h, d, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1) * d ** -0.5


def operands(seed, batch, seq, heads, decay, beta=None, dtype=jnp.float32,
             d=D):
    """q and k of unit length, v normal, ``g`` uniform in ``-decay x
    [0.2, 1]`` (or ``-decay`` at every position and channel, where
    ``decay`` is a tuple of one), ``beta`` 2 sigmoid(normal) or
    ``beta`` everywhere."""
    keys = jax.random.split(jax.random.key(seed), 5)
    shape = (batch, seq, heads, d)
    q, k, v = (jax.random.normal(key, shape) for key in keys[:3])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    if isinstance(decay, tuple):
        g = jnp.full(shape, -decay[0], jnp.float32)
    else:
        g = -decay * jax.random.uniform(keys[3], shape, minval=0.2)
    if beta is None:
        step = 2 * jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3]))
    else:
        step = jnp.full(shape[:3], beta, jnp.float32)
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, step)


def rows(x):
    """``[batch, seq, heads, d]`` as the rows the kernels take."""
    return x.reshape(*x.shape[:2], -1)


def on_heads(fn):
    """``fn`` of rows, as a function of ``[batch, seq, heads, d]``
    operands, which is what ``recurrence`` and the plain path take."""
    def folded(q, k, v, g, beta):
        return fn(rows(q), rows(k), rows(v), rows(g), beta).reshape(v.shape)

    return folded


PATHS = {
    "plain": delta_rule.gated_delta_rule_plain,
    "kernels": on_heads(kernels.delta_rule_tpu),
}
NAMES = ("q", "k", "v", "g", "beta")


def with_gradients(fn, args, cotangent):
    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * cotangent),
        argnums=(0, 1, 2, 3, 4),
    ))(*args)


def relative(got, want):
    scale = float(jnp.abs(want).max())
    return float(jnp.abs(got.astype(jnp.float32) - want).max()) / scale


#: (sequence, decay, beta): a chunk and a bit, many chunks, one chunk;
#: a decay of 5 a step at every channel and position, and up to 5; a
#: step size near 0 and near 2
CASES = {
    "many chunks": (320, 0.3, None),
    "one chunk": (64, 0.3, None),
    "g of -5 a step": (192, (5.0,), None),
    "g down to -5": (192, 5.0, None),
    "beta near 0": (128, 0.3, 0.02),
    "beta near 2": (128, 0.3, 1.98),
    "no decay": (128, (0.0,), None),
}


#: heads of the operands -> heads that a grid step of the kernels takes
#: of them: every number the kernels' rule can choose, and one head
#: where nothing else divides the count
HEADS_A_STEP = {3: 1, 2: 2, 4: 4, 6: 2}
#: (path, heads of the operands): the kernels at each number of heads a
#: grid step, which only the head count decides
PATHS_AND_HEADS = [("plain", 2)] + [("kernels", h) for h in (3, 2, 4)]


def test_heads_a_grid_step_come_from_the_head_count():
    assert set(HEADS_A_STEP.values()) == {1, *kernels.HEADS_A_STEP}
    for heads, together in HEADS_A_STEP.items():
        assert kernels.heads_a_step(heads) == together
    assert kernels.heads_a_step(64) == max(kernels.HEADS_A_STEP)
    assert kernels.heads_a_step(1) == 1


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("path,heads", PATHS_AND_HEADS)
def test_path_agrees_with_the_recurrence(path, heads, case):
    """float32: ``o`` and the five gradients to 1e-4 of each one's
    largest entry, no NaN, no overflow; the kernels with one, two and
    four heads' chunks a grid step."""
    seq, decay, beta = CASES[case]
    args = operands(3, 2, seq, heads, decay, beta)
    want_o = recurrence(*args)
    cotangent = jax.random.normal(jax.random.key(9), want_o.shape)
    got_o = PATHS[path](*args)
    assert bool(jnp.isfinite(got_o).all())
    assert relative(got_o, want_o) < 1e-4
    _, want = with_gradients(recurrence, args, cotangent)
    _, got = with_gradients(PATHS[path], args, cotangent)
    for name, a, b in zip(NAMES, got, want):
        assert bool(jnp.isfinite(a).all()), name
        assert relative(a, b) < 1e-4, (name, relative(a, b))


@pytest.mark.parametrize("path", list(PATHS))
def test_a_chunks_edge_is_no_edge(path):
    """Positions 63 and 64 (a chunk's last, the next one's first) and
    a sub-block's edge read as the recurrence does; what a later
    position holds moves no earlier result, and its cotangent reaches
    every earlier operand."""
    args = operands(5, 1, 192, 1, 1.0)
    want = recurrence(*args)
    got = PATHS[path](*args)
    for at in (15, 16, 63, 64, 127, 128):
        assert relative(got[:, at], want[:, at]) < 1e-4, at
    later = tuple(x.at[:, 100:].set(x[:, 100:] * 0.5) for x in args)
    assert relative(PATHS[path](*later)[:, :100], got[:, :100]) < 1e-5
    args = operands(5, 1, 192, 1, 0.01)  # what forgets little
    only_last = jnp.zeros_like(want).at[:, -1].set(1.0)
    _, grads = with_gradients(PATHS[path], args, only_last)
    _, wanted = with_gradients(recurrence, args, only_last)
    for name, a, b in zip(NAMES, grads, wanted):
        # the first position's q meets only its own result
        assert name == "q" or float(jnp.abs(a[:, 0]).max()) > 0, name
        assert relative(a, b) < 1e-4, name


@pytest.mark.parametrize("path,heads", PATHS_AND_HEADS + [("kernels", 6)])
def test_batch_rows_and_heads_are_independent(path, heads):
    """A row of the batch alone and the heads in another order read
    the same. In the kernels, where heads share a grid step, a head's
    ``o`` and gradients are the same to the last bit whoever its
    neighbours are: by itself (a grid step of one head), and beside
    heads whose operands were changed."""
    args = operands(7, 3, 128, heads, 0.5)
    whole = PATHS[path](*args)
    for row in range(3):
        alone = PATHS[path](*(x[row:row + 1] for x in args))
        assert relative(alone, whole[row:row + 1]) < 1e-5
    swapped = PATHS[path](*(x[:, :, ::-1] for x in args))
    assert relative(swapped[:, :, ::-1], whole) < 1e-5
    if path != "kernels":
        return
    assert kernels.heads_a_step(heads) == HEADS_A_STEP[heads]
    args = tuple(x[:1] for x in args)
    cotangent = jax.random.normal(jax.random.key(9), args[2].shape)
    last = heads - 1  # the last head: the others share its grid step
    others = tuple(
        x.at[:, :, :last].set(x[:, :, :last] * 0.5) for x in args)
    alone = tuple(x[:, :, last:] for x in args)
    _, grads = with_gradients(PATHS[path], args, cotangent)
    _, beside_others = with_gradients(PATHS[path], others, cotangent)
    _, by_itself = with_gradients(
        PATHS[path], alone, cotangent[:, :, last:])
    for name, a, b, c in zip(
        ("o", *NAMES),
        (whole[:1], *grads),
        (PATHS[path](*others), *beside_others),
        (PATHS[path](*alone), *by_itself),
    ):
        assert bool((a[:, :, last:] == b[:, :, last:]).all()), name
        assert bool((a[:, :, last:] == c).all()), name


def solved_again(cs, inv_ref, w_ref):
    """What the backward kernel made of a grid step's chunks before it
    read what the forward kept (``_chunks`` as it was, kept here as
    the reference): each pair's inverse and each head's ``w`` again
    from the operands, the kept blocks unread."""
    invs = kernels._in_turn(
        kernels._inverse(
            kernels._diagonal([c["beta"] * c["a"] for c in pair]))
        for pair in kernels._pairs(cs)
    )
    ws = kernels._across(
        invs, [c["beta"] * (c["v"] - c["held"]) for c in cs], kernels._NN)
    for c, w in zip(cs, ws):
        c["w"] = w
    return invs


#: (sequence, decay, dtype): float32 over a chunk and a bit; every
#: channel at the entry's floor at every position; the cells' bfloat16
KEPT_CASES = {
    "many chunks": (320, 0.3, jnp.float32),
    "g at the floor": (128, (-delta_rule.G_FLOOR,), jnp.float32),
    "bfloat16": (192, 1.0, jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(KEPT_CASES))
@pytest.mark.parametrize("heads", [3, 2, 4])
def test_backward_reads_what_the_forward_solved(heads, case, monkeypatch):
    """The forward that keeps the chunks' entry states keeps each
    pair's inverse and each head's ``w`` beside them, float32; the
    backward kernel over them makes no inverse, and its five gradients
    are, bit for bit, those of a backward that solves each chunk
    again: at one, two and four heads a grid step."""
    seq, decay, dtype = KEPT_CASES[case]
    args = operands(31, 2, seq, heads, decay, dtype=dtype)
    flat = (*(rows(x) for x in args[:4]), args[4])
    do = rows(jax.random.normal(
        jax.random.key(9), args[2].shape)).astype(dtype)
    o, kept = kernels.delta_rule(*flat, keep_states=True)
    assert bool((o == kernels.delta_rule(*flat)).all())
    paired = min(HEADS_A_STEP[heads], 2)  # heads whose inverse is one matrix
    chunks = seq // kernels.CHUNK
    assert [(x.shape, x.dtype) for x in kept] == [
        ((2, heads, chunks, D, D), jnp.float32),
        ((2, heads // paired, chunks, paired * 64, paired * 64),
         jnp.float32),
        ((2, seq, heads * D), jnp.float32),
    ]
    made = []

    def inverse(n):
        made.append(n.shape)
        return (yield from kernels_inverse(n))

    kernels_inverse = kernels._inverse
    monkeypatch.setattr(kernels, "_inverse", inverse)

    def backward():
        # a trace of its own: the kernel's body is looked up as it is now
        return jax.jit(lambda *a: kernels.delta_rule.__wrapped__(
            *a[:5], kept=a[5:8], do=a[8]))(*flat, *kept, do)

    got = backward()
    assert len(made) == kernels.BACKWARD_INVERSES == 0
    monkeypatch.setattr(kernels, "_kept", solved_again)
    want = backward()
    assert made == [(paired * 64, paired * 64)] * (
        HEADS_A_STEP[heads] // paired)
    for name, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert bool(jnp.isfinite(a.astype(jnp.float32)).all()), name
        assert bool((a == b).all()), name


def test_kernels_in_bfloat16_are_within_a_step_of_bfloat16():
    """bfloat16 operands, the products' operands rounded to bfloat16
    inside: ``o`` and the gradients within 2 ** -7 of each one's
    largest entry (a bfloat16's last place at that size) of the
    float32 recurrence on the same rounded operands."""
    args = operands(11, 1, 256, 2, 1.0, dtype=jnp.bfloat16)
    want_o = recurrence(*args)
    cotangent = jax.random.normal(jax.random.key(9), want_o.shape)
    got_o = PATHS["kernels"](*args)
    assert got_o.dtype == jnp.bfloat16
    assert relative(got_o, want_o) < 2 ** -7
    _, want = with_gradients(recurrence, args, cotangent)
    _, got = with_gradients(PATHS["kernels"], args, cotangent)
    for name, a, b in zip(NAMES, got, want):
        assert a.dtype == (jnp.bfloat16 if name in "qkv" else jnp.float32)
        assert relative(a, b) < 2 ** -7, (name, relative(a, b))


def test_plain_path_takes_any_length_and_width():
    """A sequence that is no whole number of chunks and heads that are
    no lane tile: padded positions leave the state as it is."""
    args = operands(13, 2, 100, 3, 0.5, d=16)
    want = recurrence(*args)
    assert relative(delta_rule.gated_delta_rule_plain(*args), want) < 1e-4
    assert relative(
        delta_rule.gated_delta_rule_plain(*args, chunk=32), want) < 1e-4
    # the entry's dispatch off the TPU is the plain path
    assert relative(delta_rule.gated_delta_rule(*args), want) < 1e-4
    assert not kernels.tiles_the_kernel(rows(args[0]).shape, 3)
    assert kernels.tiles_the_kernel((1, 8192, 8192), 64)
    assert not kernels.tiles_the_kernel((1, 8192, 8192), 32)
    assert not kernels.tiles_the_kernel((1, 8200, 8192), 64)


def _one_fast_step(args, at=(70, 75)):
    """``args`` with a log decay of -100 at two positions of one
    sub-block, in every channel: each step is far inside what an
    ``exp`` holds, the block's range of 200 is past what ``CLIP``
    holds."""
    q, k, v, g, beta = args
    return q, k, v, g.at[:, jnp.array(at)].set(-100.0), beta


def _fast_channels(args, step=-12.0):
    """``args`` with eight channels of every head forgetting by
    ``step`` at every position: 192 over a block of sixteen."""
    q, k, v, g, beta = args
    return q, k, v, g.at[..., :8].set(step), beta


PAST = {
    "12 a step in eight channels": lambda: _fast_channels(
        operands(17, 1, 128, 2, 0.3)),
    "two fast steps in a block": lambda: _one_fast_step(
        operands(19, 1, 128, 2, 0.3)),
}


@pytest.fixture
def kernels_at_the_entry(monkeypatch):
    """The dispatch a TPU process takes, the kernels in interpret
    mode."""
    monkeypatch.setattr(delta_rule, "_use_pallas", lambda q, heads: True)


@pytest.mark.parametrize("case", list(PAST))
@pytest.mark.parametrize("path", list(PATHS))
def test_the_entry_holds_a_fast_decay_to_its_floor(
    path, case, kernels_at_the_entry, monkeypatch
):
    """A channel that forgets by more than 150 within a block of
    ``SUB`` rows is past what the kernels' factored exponents hold
    exactly (ops/pallas/delta_rule.py). The entry takes no step under
    ``G_FLOOR``, on either path, and what that costs is under the
    tests' own tolerance: ``o`` and the five gradients are the
    recurrence's on the operands as they came, ``alpha`` of
    ``exp(-100)`` and all."""
    if path == "plain":
        monkeypatch.setattr(
            delta_rule, "_use_pallas", lambda q, heads: False)
    args = PAST[case]()
    assert float(args[3].min()) < delta_rule.G_FLOOR
    want_o = recurrence(*args)
    cotangent = jax.random.normal(jax.random.key(9), want_o.shape)
    assert relative(delta_rule.gated_delta_rule(*args), want_o) < 1e-4
    _, want = with_gradients(recurrence, args, cotangent)
    _, got = with_gradients(delta_rule.gated_delta_rule, args, cotangent)
    for name, a, b in zip(NAMES, got, want):
        assert bool(jnp.isfinite(a).all()), name
        assert relative(a, b) < 1e-4, (name, relative(a, b))
    # under the floor ``g`` decides nothing, and is told so
    assert float(jnp.abs(
        jnp.where(args[3] < delta_rule.G_FLOOR, got[3], 0.0)).max()) == 0


def test_what_the_kernels_alone_read_past_the_floor():
    """Why the floor is there: ``delta_rule_tpu`` by itself on two
    steps of -100 in one block is finite and wrong by more than any
    rounding, where the same operands with one such step (a block's
    range of 100, inside what ``CLIP`` holds) read as the recurrence;
    at the floor itself, at every position and channel, it is exact."""
    alone = on_heads(kernels.delta_rule)
    inside = _one_fast_step(operands(19, 1, 128, 2, 0.3), at=(70,))
    assert relative(alone(*inside), recurrence(*inside)) < 1e-4
    past = _one_fast_step(operands(19, 1, 128, 2, 0.3))
    got = alone(*past)
    assert bool(jnp.isfinite(got).all())
    assert relative(got, recurrence(*past)) > 1e-2
    floor = operands(23, 1, 128, 2, (-delta_rule.G_FLOOR,))
    assert relative(alone(*floor), recurrence(*floor)) < 1e-4
    assert -delta_rule.G_FLOOR * (kernels.SUB - 1) / 2 <= kernels.CLIP


#: (dtype, what ``o`` and the gradients may differ by, of each one's
#: largest entry): the two entries run the same program on the same
#: numbers, so float32 is held to its rounding and bfloat16 to the
#: limit the kernels have against the recurrence
ENTRY_LIMITS = {"float32": 1e-5, "bfloat16": 2 ** -7}


@pytest.mark.parametrize("dtype", list(ENTRY_LIMITS))
@pytest.mark.parametrize("path", list(PATHS))
def test_the_rows_entry_is_the_heads_entry(path, dtype, monkeypatch):
    """``gated_delta_rule_rows`` on ``[batch, seq, heads x d]`` against
    ``gated_delta_rule`` on the same numbers as ``[batch, seq, heads,
    d]``: ``o`` and the five gradients, each in its operand's shape,
    on the plain path and on the kernels."""
    monkeypatch.setattr(
        delta_rule, "_use_pallas", lambda q, heads: path == "kernels")
    args = operands(29, 2, 128, 3, 1.0, dtype=jnp.dtype(dtype),
                    d=D if path == "kernels" else 16)
    flat = (*(rows(x) for x in args[:4]), args[4])
    want_o = delta_rule.gated_delta_rule(*args)
    got_o = delta_rule.gated_delta_rule_rows(*flat, heads=3)
    assert got_o.shape == flat[2].shape and got_o.dtype == args[2].dtype
    limit = ENTRY_LIMITS[dtype]
    assert relative(got_o.reshape(want_o.shape),
                    want_o.astype(jnp.float32)) < limit
    cotangent = jax.random.normal(jax.random.key(9), want_o.shape)
    _, want = with_gradients(delta_rule.gated_delta_rule, args, cotangent)
    _, got = with_gradients(
        lambda *a: delta_rule.gated_delta_rule_rows(*a, heads=3), flat,
        rows(cotangent))
    for name, a, b, operand in zip(NAMES, got, want, flat):
        assert a.shape == operand.shape and a.dtype == b.dtype, name
        assert relative(a.reshape(b.shape), b.astype(jnp.float32)) < limit, (
            name, relative(a.reshape(b.shape), b.astype(jnp.float32)))


def test_entry_refuses_mismatched_operands():
    q, k, v, g, beta = operands(1, 1, 64, 2, 0.5)
    with pytest.raises(ValueError):
        delta_rule.gated_delta_rule(q, k, v, g[:, :32], beta)
    with pytest.raises(ValueError):
        delta_rule.gated_delta_rule(q, k, v, g, beta[..., None])
    # heads that fold to rows of one width are still other heads
    with pytest.raises(ValueError):
        delta_rule.gated_delta_rule(
            q, k.reshape(1, 64, 4, D // 2), v, g, beta)


@pytest.mark.parametrize("wrong", ["heads", "beta", "g", "4-D"])
def test_rows_entry_refuses_mismatched_operands(wrong):
    q, k, v, g, beta = operands(1, 1, 64, 2, 0.5)
    flat = [rows(q), rows(k), rows(v), rows(g), beta]
    heads = 2
    if wrong == "heads":
        heads = 3  # beta has two, and 3 does not divide a row
    elif wrong == "beta":
        flat[4] = beta[..., None]
    elif wrong == "g":
        flat[3] = flat[3][:, :32]
    else:
        flat[0] = q
    with pytest.raises(ValueError):
        delta_rule.gated_delta_rule_rows(*flat, heads=heads)


def _calls(decay="channel", head=f"{D}x{D}"):
    """The two counters of calls, under the labels of a form of decay
    and a head's two widths."""
    from dlrover_tpu.telemetry.registry import counter

    return tuple(
        counter(f"delta_rule_{handed}_calls", "", kernels.CALL_LABELS)
        .labels(decay=decay, head=head).value
        for handed in ("rows", "folded"))


def test_dispatch_says_what_it_built(kernels_at_the_entry):
    """The gauges, set where the kernels are built, and the counters
    of the calls built on rows and on heads that were folded: one for
    a forward, two more for its gradients (the forward that keeps the
    entry states and what it solved, the backward that reads them)."""
    from dlrover_tpu.telemetry.registry import gauge

    for name in ("delta_rule_chunk", "delta_rule_state_bytes",
                 "delta_rule_backward_kernels", "delta_rule_heads_per_step",
                 "delta_rule_kept_bytes"):
        gauge(name, "").set(0)
    gauge("delta_rule_backward_inverses", "").set(1)
    args = operands(1, 1, 64, 1, 0.5)
    flat = (*(rows(x) for x in args[:4]), args[4])
    before = _calls()
    jax.jit(kernels.delta_rule_tpu).lower(*flat)  # traced, not run
    assert gauge("delta_rule_chunk", "").value == 64
    assert gauge("delta_rule_heads_per_step", "").value == 1
    assert gauge("delta_rule_state_bytes", "").value == 128 * 128 * 4
    assert gauge("delta_rule_backward_kernels", "").value == 1
    assert gauge("delta_rule_backward_inverses", "").value == 0
    # a head by itself: its state, its [64, 64] inverse and its w
    assert gauge("delta_rule_kept_bytes", "").value == 4 * (
        128 * 128 + 64 * 64 + 64 * 128)
    assert _calls() == (before[0] + 1, before[1])
    jax.jit(lambda *a: delta_rule.gated_delta_rule_rows(
        *a, heads=1)).lower(*flat)
    assert _calls() == (before[0] + 2, before[1])
    # the 4-D entry folds, and says so
    jax.jit(delta_rule.gated_delta_rule).lower(*args)
    assert _calls() == (before[0] + 2, before[1] + 1)
    jax.jit(jax.grad(
        lambda *a: delta_rule.gated_delta_rule(*a).sum(), argnums=(0, 3)
    )).lower(*args)
    assert _calls() == (before[0] + 2, before[1] + 3)
    # as many heads' states resident as a grid step takes heads
    for heads, together in HEADS_A_STEP.items():
        args = operands(1, 1, 64, heads, 0.5)
        jax.jit(delta_rule.gated_delta_rule).lower(*args)
        assert gauge("delta_rule_heads_per_step", "").value == together
        assert gauge(
            "delta_rule_state_bytes", "").value == together * 128 * 128 * 4
        # a pair's inverse is one [128, 128] matrix, half of it a head's
        assert gauge("delta_rule_kept_bytes", "").value == 4 * (
            128 * 128 + (64 * 64 if together == 1 else 128 * 64) + 64 * 128)


# ---------------------------------------------------------------------------
# one decay a head, on heads of two widths

def recurrence_a_head(q, k, v, g, beta):
    """``S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t
    v_t^T`` with ``g_t`` a number a head, ``S`` [dk, dv], ``o_t = S_t^T
    q_t / sqrt(dk)``, a position at a time, float32."""
    b, s, h, dk = q.shape

    def step(state, x):  # [b, h, keys, values]
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[..., None, None]
        held = jnp.einsum("bhc,bhcv->bhv", k_t, state)
        state = state + jnp.einsum(
            "bhc,bhv->bhcv", beta_t[..., None] * k_t, v_t - held)
        return state, jnp.einsum("bhc,bhcv->bhv", q_t, state)

    xs = tuple(
        jnp.moveaxis(x.astype(jnp.float32), 1, 0)
        for x in (q, k, v, g, beta)
    )
    _, o = jax.lax.scan(
        step, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1) * dk ** -0.5


def operands_a_head(seed, batch, seq, heads, decay, beta=None,
                    dtype=jnp.float32, dk=24, dv=40):
    """``operands`` with ``v`` of its own width and ``g`` a number a
    head and position."""
    q, k, _, g, step = operands(seed, batch, seq, heads, decay, beta, d=dk)
    v = jax.random.normal(
        jax.random.fold_in(jax.random.key(seed), 7), (batch, seq, heads, dv))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype),
            g[..., 0], step)


def on_heads_a_head(fn):
    def folded(q, k, v, g, beta):
        return fn(rows(q), rows(k), rows(v), g, beta).reshape(v.shape)

    return folded


PATHS_A_HEAD = {
    "plain": delta_rule.gated_delta_rule_plain,
    "kernels": on_heads_a_head(kernels.delta_rule_tpu),
}
#: (sequence, decay, beta) as ``CASES``, and a decay of 30 a step at
#: every position, which a floor at -10 would change and none touches
CASES_A_HEAD = {
    **CASES,
    "g of -30 a step": (128, (30.0,), None),
    "g down to -30": (192, 30.0, None),
    "beta at 2": (128, 0.3, 2.0),
}
#: (dk, dv): unequal and neither a power of two; the cell's; square
WIDTHS = [(24, 40), (96, 192), (128, 128)]


#: the plain path at the cases its one line of decay can differ by
#: (its jitted walk is the slow side here), the kernels at every one
PATHS_AND_CASES_A_HEAD = [
    ("plain", case) for case in (
        "many chunks", "g of -30 a step", "g down to -30", "beta at 2")
] + [("kernels", case) for case in CASES_A_HEAD]


@pytest.mark.parametrize("path,case", PATHS_AND_CASES_A_HEAD)
def test_one_decay_a_head_is_the_recurrence(path, case):
    """``o`` and the five gradients, ``g``'s and ``beta``'s a number a
    head, against the walk position by position at ``dk != dv``: the
    plain path and the kernels in interpret mode, three heads (a grid
    step of one) on the plain path's side and two on the kernels'."""
    seq, decay, beta = CASES_A_HEAD[case]
    args = operands_a_head(11, 2, seq, 2, decay, beta)
    cotangent = jax.random.normal(jax.random.key(5), args[2].shape)
    want_o, want = with_gradients(recurrence_a_head, args, cotangent)
    got_o, got = with_gradients(PATHS_A_HEAD[path], args, cotangent)
    assert abs(float(got_o - want_o)) < 1e-4 * (1 + abs(float(want_o)))
    for name, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape, name
        # at -30 a step ``g``'s whole gradient is 4e-5 of q's or
        # less, and float32's rounding of the sums it is a difference
        # of shows: held to q's gradient's scale there
        if name == "g" and "30" in case:
            assert float(jnp.abs(a - b).max()) < 2e-5 * float(
                jnp.abs(want[0]).max()), name
            continue
        assert relative(a, b) < 2e-5, (name, relative(a, b))


@pytest.mark.parametrize("widths", WIDTHS)
def test_the_kernels_take_a_head_of_two_widths(widths):
    """A head of ``dk`` keys by ``dv`` values padded to whole lane
    tiles inside ``delta_rule``: the result and every gradient are the
    plain path's on the unpadded operands, each in its operand's
    shape, at three heads (a grid step of one, an inverse a head)."""
    dk, dv = widths
    args = operands_a_head(3, 1, 128, 3, 2.0, dk=dk, dv=dv)
    cotangent = jax.random.normal(jax.random.key(6), args[2].shape)
    want_o, want = with_gradients(
        delta_rule.gated_delta_rule_plain, args, cotangent)
    got_o, got = with_gradients(PATHS_A_HEAD["kernels"], args, cotangent)
    assert abs(float(got_o - want_o)) < 1e-4 * (1 + abs(float(want_o)))
    for name, a, b, operand in zip(NAMES, got, want, args):
        assert a.shape == operand.shape and a.dtype == operand.dtype, name
        assert relative(a, b) < 2e-5, (name, relative(a, b))


def test_a_slow_step_after_fast_ones_keeps_what_it_should():
    """Thirty-one steps of -30 and then steps of -0.01: a pair's
    exponent is the sum of the steps between the two positions, not
    the difference of two cumulated sums near -930, whose float32
    rounding (6e-5) would be the slow steps' whole decay."""
    q, k, v, g, beta = operands_a_head(4, 1, 64, 2, (0.01,))
    g = g.at[:, :31].set(-30.0)
    want = recurrence_a_head(q, k, v, g, beta)
    for path in PATHS_A_HEAD.values():
        assert relative(path(q, k, v, g, beta), want) < 2e-6


def test_bfloat16_operands_on_a_head_of_two_widths():
    args = operands_a_head(8, 1, 128, 2, 1.0, dtype=jnp.bfloat16,
                           dk=96, dv=192)
    want = recurrence_a_head(*args)
    for path in PATHS_A_HEAD.values():
        got = path(*args)
        assert got.dtype == jnp.bfloat16
        assert relative(got, want) < 2 ** -7


def test_the_shapes_alone_decide_the_scalar_forms_path(monkeypatch):
    """``tiles_the_kernel`` with ``v``'s shape: whole chunks, keys of
    at most a lane tile, values of at most ``MOST_VALUES``; what does
    not tile takes the plain path, on the TPU's dispatch too."""
    assert kernels.tiles_the_kernel((1, 16384, 2880), 30, (1, 16384, 5760))
    assert kernels.tiles_the_kernel((2, 64, 48), 2, (2, 64, 80))
    assert not kernels.tiles_the_kernel((2, 96, 48), 2, (2, 96, 80))
    assert not kernels.tiles_the_kernel((1, 64, 2 * 160), 2, (1, 64, 80))
    assert not kernels.tiles_the_kernel((1, 64, 48), 2, (1, 64, 2 * 320))
    # a decay a channel: its own rule, as it was
    assert kernels.tiles_the_kernel((1, 64, 2 * D), 2)
    assert not kernels.tiles_the_kernel((1, 64, 2 * 96), 2)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jnp.zeros((1, 96, 48))
    assert not delta_rule._use_pallas_a_head(q, jnp.zeros((1, 96, 80)), 2)
    assert delta_rule._use_pallas_a_head(
        q[:, :64], jnp.zeros((1, 64, 80)), 2)
    args = operands_a_head(2, 1, 96, 2, 1.0)  # no whole chunks
    flat = (*(rows(x) for x in args[:3]), args[3], args[4])
    before = _calls("head", "24x40")
    got = delta_rule.gated_delta_rule_rows(*flat, heads=2)
    assert _calls("head", "24x40") == before  # no kernel was built
    assert relative(got.reshape(args[2].shape),
                    recurrence_a_head(*args)) < 1e-5


def test_the_rows_entry_takes_one_decay_a_head(monkeypatch):
    """``gated_delta_rule_rows`` with ``g`` in ``beta``'s shape
    against the entry on heads, on the kernels; the counters tell the
    form and the two widths by their labels, and the state's gauge is
    the padded ``[values, keys]``."""
    from dlrover_tpu.telemetry.registry import gauge

    monkeypatch.setattr(
        delta_rule, "_use_pallas_a_head", lambda q, v, heads: True)
    args = operands_a_head(29, 2, 128, 2, 1.0, dk=96, dv=192)
    flat = (*(rows(x) for x in args[:3]), args[3], args[4])
    before = _calls("head", "96x192"), _calls()
    want = delta_rule.gated_delta_rule(*args)
    got = delta_rule.gated_delta_rule_rows(*flat, heads=2)
    assert got.shape == flat[2].shape
    assert relative(got.reshape(want.shape), want) < 1e-6
    assert relative(want, recurrence_a_head(*args)) < 1e-5
    assert (_calls("head", "96x192"), _calls()) == (
        (before[0][0] + 1, before[0][1] + 1), before[1])
    assert gauge("delta_rule_heads_per_step", "").value == 2
    assert gauge("delta_rule_state_bytes", "").value == 2 * 256 * 128 * 4
    assert gauge("delta_rule_kept_bytes", "").value == 4 * (
        256 * 128 + 128 * 64 + 64 * 256)


def test_one_decay_a_head_has_no_floor(monkeypatch):
    """The entry leaves a decay a head as it came: at -6 to -30 a
    step the state keeps ``exp(g)`` of itself, not ``exp(G_FLOOR)``
    where that is more, and ``g`` gets its gradient there, on both
    paths."""
    args = operands_a_head(6, 1, 64, 2, 30.0)
    flat = (*(rows(x) for x in args[:3]), args[3], args[4])
    cotangent = jax.random.normal(jax.random.key(2), args[2].shape)
    _, want = with_gradients(recurrence_a_head, args, cotangent)
    assert float(jnp.abs(want[3]).max()) > 0
    for on_kernels in (False, True):
        monkeypatch.setattr(
            delta_rule, "_use_pallas_a_head",
            lambda q, v, heads: on_kernels)
        _, got = with_gradients(
            lambda *a: delta_rule.gated_delta_rule_rows(
                *a, heads=2).reshape(args[2].shape), flat, cotangent)
        assert relative(got[3], want[3]) < 1e-4
        floored = flat[:3] + (jnp.maximum(flat[3], delta_rule.G_FLOOR),
                              flat[4])
        assert relative(
            delta_rule.gated_delta_rule_rows(*floored, heads=2),
            delta_rule.gated_delta_rule_rows(*flat, heads=2)) > 1e-6


@pytest.mark.parametrize("wrong", ["g a column", "v's positions",
                                   "k's width", "heads"])
def test_rows_entry_refuses_mismatched_scalar_operands(wrong):
    q, k, v, g, beta = operands_a_head(1, 1, 64, 2, 0.5)
    flat = [rows(q), rows(k), rows(v), g, beta]
    heads = 2
    if wrong == "g a column":
        flat[3] = g[..., None]
    elif wrong == "v's positions":
        flat[2] = flat[2][:, :32]
    elif wrong == "k's width":
        flat[1] = flat[1][..., :24]
    else:
        heads = 4  # beta has two
    with pytest.raises(ValueError):
        delta_rule.gated_delta_rule_rows(*flat, heads=heads)


def test_the_forward_takes_a_third_pair_where_the_head_count_has_one():
    """The forward kernels' own rule: six heads a grid step where six
    divide the count (the cell's 30), else what the backward takes;
    every choice even where the backward's is, so the pairs whose
    inverses the forward keeps are the pairs the backward reads: at six
    heads (forward 6 a step, backward 2) the result and the gradients
    are the plain path's."""
    assert kernels.heads_a_step(30, forward=True) == 6
    assert kernels.heads_a_step(30) == 2
    for heads in (64, 32, 4, 2, 3, 1):
        assert kernels.heads_a_step(heads, forward=True) == (
            kernels.heads_a_step(heads))
    assert all(h % 2 == 0 for h in kernels.FORWARD_HEADS_A_STEP)
    args = operands_a_head(13, 1, 128, 6, 2.0)
    cotangent = jax.random.normal(jax.random.key(6), args[2].shape)
    want_o, want = with_gradients(PATHS_A_HEAD["plain"], args, cotangent)
    got_o, got = with_gradients(PATHS_A_HEAD["kernels"], args, cotangent)
    assert abs(float(got_o - want_o)) < 1e-4 * (1 + abs(float(want_o)))
    for name, a, b in zip(NAMES, got, want):
        assert relative(a, b) < 2e-5, (name, relative(a, b))
