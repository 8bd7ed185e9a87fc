"""Batched LM serving driver: prefill + KV-cache decode with a simple
continuous-batching scheduler.

A small request pool arrives with different prompt lengths; the server
prefills each prompt into a padded cache slot, then decodes the whole batch
in lockstep (one token/step for every live slot). Finished slots (EOS or
max-new-tokens) are immediately refilled from the queue — the "continuous
batching" serving pattern, scaled down to a CPU demo.

Demo simplification: the cache ``length`` is shared across slots (the max
over live requests), so a freshly-admitted short prompt also attends over
zero-padded cache positions. Production serving keeps a per-slot length
vector; see ``repro.models.attention.decode_attention`` which already masks
per-position when given one.

    PYTHONPATH=src python examples/serve_lm.py --requests 6 --max-new 16
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import ModelConfig, decode_step, init_params, prefill

CFG = ModelConfig(
    name="demo-serve", family="dense", n_layers=4, d_model=256, n_heads=4,
    n_kv=2, d_head=64, d_ff=1024, vocab=8192, act="swiglu", qk_norm=True,
    tie_embeddings=True, attn_q_chunk=64, attn_kv_chunk=64)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch", type=int, default=2, help="decode slots")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=160)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    queue = [rng.integers(1, CFG.vocab, size=rng.integers(8, 64)).tolist()
             for _ in range(args.requests)]
    print(f"serving {len(queue)} requests, {args.batch} decode slots, "
          f"params={CFG.n_params / 1e6:.1f}M")

    params = init_params(jax.random.PRNGKey(0), CFG)
    prefill_1 = jax.jit(
        lambda p, t: prefill(p, CFG, t, args.max_len)[:2])
    decode = jax.jit(lambda p, t, c: decode_step(p, CFG, t, c))

    # slot state: per-slot caches are stacked into one batched cache tree
    def stack(trees):
        return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=-4)
                            if xs[0].ndim >= 4 else xs[0], *trees)

    completions = {}
    t0 = time.perf_counter()
    slots = []      # (req_id, generated tokens list)
    caches = None
    live_tok = jnp.zeros((args.batch, 1), jnp.int32)
    next_id = 0

    def admit(slot_idx):
        """Prefill the next queued request into a slot."""
        nonlocal caches, live_tok, next_id
        prompt = queue.pop(0)
        logits, c1 = prefill_1(params, jnp.asarray([prompt], jnp.int32))
        first = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        if caches is None:
            caches = jax.tree.map(
                lambda x: jnp.repeat(x, args.batch, axis=-4)
                if x.ndim >= 4 else x, c1)
        else:
            # splice this request's cache into the slot (cache layout:
            # (..., B, S, heads, d) with B at axis -4 for k/v leaves)
            caches = jax.tree.map(
                lambda full, one: jax.lax.dynamic_update_slice_in_dim(
                    full, one, slot_idx, axis=-4)
                if full.ndim >= 4 else jnp.maximum(full, one), caches, c1)
        live_tok = live_tok.at[slot_idx, 0].set(first[0])
        slots[slot_idx] = (next_id, [int(first[0])])
        next_id += 1

    for i in range(min(args.batch, len(queue) + 0)):
        slots.append(None)
        admit(i)
    while len(slots) < args.batch:
        slots.append(None)

    steps = 0
    while any(s is not None for s in slots):
        logits, caches = decode(params, live_tok, caches)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        live_tok = nxt[:, None]
        steps += 1
        for i, s in enumerate(slots):
            if s is None:
                continue
            rid, toks = s
            toks.append(int(nxt[i]))
            if len(toks) >= args.max_new:
                completions[rid] = toks
                slots[i] = None
                if queue:
                    admit(i)
    dt = time.perf_counter() - t0

    for rid in sorted(completions):
        print(f"  req {rid}: {len(completions[rid])} tokens "
              f"{completions[rid][:8]}...")
    tput = sum(len(v) for v in completions.values()) / dt
    print(f"{len(completions)} completions in {dt:.2f}s "
          f"({steps} decode steps, {tput:.1f} tok/s on this host)")
    assert len(completions) == args.requests


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
