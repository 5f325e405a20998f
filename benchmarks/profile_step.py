"""Decompose the single-chip train step into timed components (dev tool)."""

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dlrover_tpu.models import llama
from dlrover_tpu.ops.attention import flash_attention, mha_reference


def timeit(fn, *args, n=10, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main():
    cfg = llama.llama_1b(remat="dots")
    batch, seq = 4, 2048
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32))
    params = jax.jit(lambda r: llama.init_params(r, cfg))(jax.random.key(0))

    # 1. full loss fwd
    loss_fn = jax.jit(
        lambda p, t: llama.next_token_loss(p, (t, t), cfg))
    t = timeit(loss_fn, params, tokens)
    print(f"loss fwd only:            {t*1e3:8.1f} ms")

    # 2. full fwd+bwd (no optimizer)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t: llama.next_token_loss(p, (t, t), cfg)))
    t_fb = timeit(grad_fn, params, tokens)
    print(f"loss fwd+bwd:             {t_fb*1e3:8.1f} ms")

    # 3. trunk only fwd+bwd (mean of hidden states as dummy loss)
    trunk = jax.jit(jax.value_and_grad(
        lambda p, t: llama.hidden_states(p, t, cfg)[0]
        .astype(jnp.float32).mean()))
    t_tr = timeit(trunk, params, tokens)
    print(f"trunk fwd+bwd:            {t_tr*1e3:8.1f} ms")

    # 4. head+CE fwd+bwd given hidden states
    x = jax.jit(lambda p, t: llama.hidden_states(p, t, cfg)[0])(
        params, tokens)

    def head_loss(lm_head, x, t):
        logits = (x @ lm_head).astype(jnp.float32)
        s, c = llama._masked_nll(logits, t)
        return s / c

    head = jax.jit(jax.value_and_grad(head_loss))
    t_h = timeit(head, params["lm_head"], x, tokens)
    print(f"head+CE fwd+bwd:          {t_h*1e3:8.1f} ms")

    # 4b. embed bwd (scatter-add) isolated
    def embed_loss(embed, t):
        return embed[t].astype(jnp.float32).mean()

    emb = jax.jit(jax.value_and_grad(embed_loss))
    t_e = timeit(emb, params["embed"], tokens)
    print(f"embed fwd+bwd (scatter):  {t_e*1e3:8.1f} ms")

    # 5. optimizer update alone
    opt = optax.adamw(1e-4, b1=0.9, b2=0.95)
    opt_state = jax.jit(opt.init)(params)
    grads = jax.tree.map(jnp.ones_like, params)

    @jax.jit
    def do_update(g, s, p):
        u, s2 = opt.update(g, s, p)
        return optax.apply_updates(p, u), s2

    t_o = timeit(do_update, grads, opt_state, params)
    print(f"adamw update:             {t_o*1e3:8.1f} ms")

    # 6. attention kernel alone, model shapes: 22 layers x [4,2048,32,64]
    q = jnp.asarray(rng.standard_normal((batch, seq, 32, 64)),
                    jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((batch, seq, 4, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((batch, seq, 4, 64)), jnp.bfloat16)
    for bq, bk in [(256, 256), (512, 512), (1024, 1024), (2048, 512),
                   (512, 1024)]:
        f = jax.jit(jax.value_and_grad(
            lambda q: flash_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk)
            .astype(jnp.float32).mean()))
        t_a = timeit(f, q)
        print(f"flash fwd+bwd bq={bq:4d} bk={bk:4d}: {t_a*1e3:8.2f} ms "
              f"(x22 = {t_a*22*1e3:6.1f})")
    f = jax.jit(jax.value_and_grad(
        lambda q: mha_reference(q, k, v, causal=True)
        .astype(jnp.float32).mean()))
    t_a = timeit(f, q)
    print(f"mha_reference fwd+bwd:    {t_a*1e3:8.2f} ms (x22 = "
          f"{t_a*22*1e3:6.1f})")


if __name__ == "__main__":
    main()
