"""GQA attention: chunked (flash-style) training/prefill + context-parallel
decode over a sequence-sharded KV cache.

Training/prefill uses an online-softmax kv-chunk scan per q-chunk (bounded
score memory at any sequence length).  Decode computes plain softmax over the
cache with the cache's *sequence* dim sharded over the `model` mesh axis
('kv_seq' logical axis): GSPMD turns the softmax/contraction over the sharded
axis into local partials + tiny all-reduces — the log-sum-exp combine of
flash-decoding, expressed declaratively.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, rms_norm, rope_table, _normal
from repro.parallel import logical_shard

NEG_INF = -1e30


def init_attention(key, d_model: int, n_heads: int, n_kv: int, d_head: int,
                   qk_norm: bool, dtype) -> dict:
    ks = jax.random.split(key, 4)
    p = {
        "wq": _normal(ks[0], (d_model, n_heads * d_head), dtype,
                      d_model ** -0.5),
        "wk": _normal(ks[1], (d_model, n_kv * d_head), dtype,
                      d_model ** -0.5),
        "wv": _normal(ks[2], (d_model, n_kv * d_head), dtype,
                      d_model ** -0.5),
        "wo": _normal(ks[3], (n_heads * d_head, d_model), dtype,
                      (n_heads * d_head) ** -0.5),
    }
    if qk_norm:
        p["q_norm"] = jnp.ones((d_head,), dtype)
        p["k_norm"] = jnp.ones((d_head,), dtype)
    return p


def attention_axes(qk_norm: bool) -> dict:
    p = {"wq": ("wt_fsdp", "heads"), "wk": ("wt_fsdp", "kv_heads"),
         "wv": ("wt_fsdp", "kv_heads"), "wo": ("heads", "wt_fsdp")}
    if qk_norm:
        p["q_norm"] = (None,)
        p["k_norm"] = (None,)
    return p


def _project_qkv(x, p, cfg, positions):
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(
        B, S, cfg.n_heads, cfg.d_head)
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"]).reshape(
        B, S, cfg.n_kv, cfg.d_head)
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"]).reshape(
        B, S, cfg.n_kv, cfg.d_head)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    cos, sin = rope_table(positions, cfg.d_head, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    q = logical_shard(q, "batch", "seq", "heads", None)
    k = logical_shard(k, "batch", "seq", "kv_heads", None)
    v = logical_shard(v, "batch", "seq", "kv_heads", None)
    return q, k, v


def _mask_for(qpos, kpos, causal, window, skv_valid):
    mask = qpos[:, None] >= -1   # all-true of the right shape
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window is not None:
        mask &= qpos[:, None] - kpos[None, :] < window
    if skv_valid is not None:
        mask &= (kpos < skv_valid)[None, :]
    return mask


def _flash_fwd(q, k, v, causal, q_offset, q_chunk, kv_chunk, window, skv):
    """Scan over q chunks; online-softmax scan over kv chunks inside.
    q (B, nq, Cq, H, D) flat-headed; k/v (B, nk, Ck, H, D) (pre-repeated to
    H = n_q_heads so the 'heads' axis shards cleanly).
    Returns o (B,nq,Cq,H,D) and lse (B,nq,Cq,H)."""
    B, nq, Cq, H, D = q.shape
    nk, Ck = k.shape[1], k.shape[2]
    scale = D ** -0.5

    def one_q(_, inp):
        qc, qi = inp
        qc = logical_shard(qc, "batch", None, "heads", None)
        qpos = q_offset + qi * Cq + jnp.arange(Cq)

        def kv_step(carry, kinp):
            m, l, acc = carry
            kc, vc, kj = kinp
            kpos = kj * Ck + jnp.arange(Ck)
            s = jnp.einsum("bqhd,bkhd->bhqk", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            s = logical_shard(s, "batch", "heads", None, None)
            s = jnp.where(_mask_for(qpos, kpos, causal, window, skv),
                          s, NEG_INF)
            m2 = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m2[..., None])
            corr = jnp.exp(m - m2)
            l2 = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vc.dtype), vc,
                            preferred_element_type=jnp.float32)
            acc2 = acc * corr[..., None] + pv
            return (m2, l2, acc2), None

        m0 = jnp.full((B, H, Cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, Cq), jnp.float32)
        a0 = jnp.zeros((B, H, Cq, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (k.swapaxes(0, 1), v.swapaxes(0, 1), jnp.arange(nk)))
        o_c = (acc / jnp.maximum(l[..., None], 1e-30)).transpose(0, 2, 1, 3)
        lse_c = (m + jnp.log(jnp.maximum(l, 1e-30))).transpose(0, 2, 1)
        return None, (o_c.astype(q.dtype), lse_c)

    _, (o, lse) = jax.lax.scan(one_q, None,
                               (q.swapaxes(0, 1), jnp.arange(nq)))
    return o.swapaxes(0, 1), lse.swapaxes(0, 1)


def _flash_bwd_body(q, k, v, o, lse, do, causal, q_offset, window, skv):
    """Flash backward: recompute p per (q,kv) chunk pair; O(Cq*Ck) live."""
    B, nq, Cq, H, D = q.shape
    nk, Ck = k.shape[1], k.shape[2]
    scale = D ** -0.5
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                      # (B,nq,Cq,H)

    def one_q(carry, inp):
        dk_acc, dv_acc = carry                    # (B,nk,Ck,H,D) f32
        qc, oc, lsec, doc, dltc, qi = inp
        qpos = q_offset + qi * Cq + jnp.arange(Cq)

        def kv_step(inner, kinp):
            dq_c, dk_acc, dv_acc = inner
            kc, vc, kj = kinp
            kpos = kj * Ck + jnp.arange(Ck)
            s = jnp.einsum("bqhd,bkhd->bhqk", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(_mask_for(qpos, kpos, causal, window, skv),
                          s, NEG_INF)
            p = jnp.exp(s - lsec.transpose(0, 2, 1)[..., None])  # (B,H,q,k)
            p = logical_shard(p, "batch", "heads", None, None)
            dv_c = jnp.einsum("bhqk,bqhd->bkhd", p,
                              doc.astype(jnp.float32),
                              preferred_element_type=jnp.float32)
            dp = jnp.einsum("bqhd,bkhd->bhqk", doc, vc,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - dltc.transpose(0, 2, 1)[..., None])
            dq_c = dq_c + jnp.einsum("bhqk,bkhd->bqhd", ds, kc,
                                     preferred_element_type=jnp.float32
                                     ) * scale
            dk_c = jnp.einsum("bhqk,bqhd->bkhd", ds,
                              qc.astype(jnp.float32),
                              preferred_element_type=jnp.float32) * scale
            dk_acc = dk_acc.at[:, kj].add(dk_c)
            dv_acc = dv_acc.at[:, kj].add(dv_c)
            return (dq_c, dk_acc, dv_acc), None

        dq0 = jnp.zeros((B, Cq, H, D), jnp.float32)
        (dq_c, dk_acc, dv_acc), _ = jax.lax.scan(
            kv_step, (dq0, dk_acc, dv_acc),
            (k.swapaxes(0, 1), v.swapaxes(0, 1), jnp.arange(nk)))
        return (dk_acc, dv_acc), dq_c

    dk0 = jnp.zeros((B, nk, Ck, H, D), jnp.float32)
    dv0 = jnp.zeros((B, nk, Ck, H, D), jnp.float32)
    (dk, dv), dq = jax.lax.scan(
        one_q, (dk0, dv0),
        (q.swapaxes(0, 1), o.swapaxes(0, 1), lse.swapaxes(0, 1),
         do.swapaxes(0, 1), delta.swapaxes(0, 1), jnp.arange(nq)))
    return dq.swapaxes(0, 1).astype(q.dtype), dk.astype(k.dtype), \
        dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, q_offset, window, skv):
    o, _ = _flash_fwd(q, k, v, causal, q_offset, q.shape[2], k.shape[2],
                      window, skv)
    return o


def _flash_f(q, k, v, causal, q_offset, window, skv):
    o, lse = _flash_fwd(q, k, v, causal, q_offset, q.shape[2], k.shape[2],
                        window, skv)
    return o, (q, k, v, o, lse)


def _flash_b(causal, q_offset, window, skv, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _flash_bwd_body(q, k, v, o, lse, do, causal, q_offset,
                                 window, skv)
    return dq, dk, dv


_flash.defvjp(_flash_f, _flash_b)


def chunked_attention(q, k, v, *, causal: bool, q_offset: int = 0,
                      q_chunk: int = 512, kv_chunk: int = 1024,
                      window: Optional[int] = None):
    """Flash attention (online softmax fwd, recompute bwd — custom VJP).

    q (B,Sq,Hq,D); k,v (B,Skv,Hkv,D). GQA is handled by repeating K/V chunks
    to flat Hq heads (cheap: one chunk at a time) so the 'heads' axis shards
    cleanly on the TP mesh axis. Score memory is O(q_chunk × kv_chunk); the
    backward recomputes p instead of saving per-chunk residuals — without
    this, differentiating a kv-chunk scan materializes the full (nq, nk)
    score matrix into while-loop buffers (the paper's lesson, inverted:
    trade recompute for on-chip working set).
    """
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    nq, nk = -(-Sq // q_chunk), -(-Skv // kv_chunk)
    pad_q, pad_k = nq * q_chunk - Sq, nk * kv_chunk - Skv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    qs = q.reshape(B, nq, q_chunk, Hq, D)
    ks = k.reshape(B, nk, kv_chunk, Hq, D)
    vs = v.reshape(B, nk, kv_chunk, Hq, D)
    skv = Skv if pad_k else None
    out = _flash(qs, ks, vs, causal, q_offset, window, skv)
    out = out.reshape(B, nq * q_chunk, Hq, D)
    if pad_q:
        out = out[:, :Sq]
    return out.astype(q.dtype)


def _flash_stub_host(q, k, v):
    import numpy as np
    from repro.kernels.flash_attention import ref_attention
    return np.asarray(ref_attention(jnp.asarray(q), jnp.asarray(k),
                                    jnp.asarray(v), causal=True))


def _flash_stub_bwd_host(q, k, v, do):
    import numpy as np

    def f(q, k, v):
        from repro.kernels.flash_attention import ref_attention
        return ref_attention(q, k, v, causal=True)

    _, vjp = jax.vjp(f, jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    dq, dk, dv = vjp(jnp.asarray(do))
    return np.asarray(dq), np.asarray(dk), np.asarray(dv)


@jax.custom_vjp
def _flash_stub(q, k, v):
    """Custom-call stand-in for the Pallas flash kernel (dry-run billing).

    Lowers to one opaque custom-call with operands (q, k, v) and result o —
    exactly the kernel's HBM DMA footprint (K/V fit VMEM at per-device
    shapes, so each is read once). The HLO analyzer bills callback
    custom-calls operands+result and assigns MXU FLOPs analytically
    (hlo_analysis.attention_stub_flops). Executable too (numpy oracle) so
    smoke tests can run the stub path."""
    return jax.pure_callback(
        _flash_stub_host, jax.ShapeDtypeStruct(q.shape, q.dtype), q, k, v,
        vmap_method="sequential")


def _fs_fwd(q, k, v):
    return _flash_stub(q, k, v), (q, k, v)


def _fs_bwd(res, do):
    q, k, v = res
    return jax.pure_callback(
        _flash_stub_bwd_host,
        (jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)), q, k, v, do,
        vmap_method="sequential")


_flash_stub.defvjp(_fs_fwd, _fs_bwd)


def _flash_stub_sharded(q, k, v):
    """shard_map wrapper: a bare custom-call is opaque to GSPMD, which would
    replicate q/k/v across the mesh (measured: 8x collective blow-up).
    Mapping it over the ambient mesh keeps operands sharded — each shard's
    custom-call is billed at per-device shapes, which is what the Pallas
    kernel sees on real hardware."""
    from repro.parallel.sharding import current_rules, resolve_spec
    mesh, rules = current_rules()
    if mesh is None or rules is None:
        return _flash_stub(q, k, v)
    qs = resolve_spec(q.shape, ("batch", "seq", "heads", None), mesh, rules)
    ks = resolve_spec(k.shape, ("batch", "seq", "kv_heads", None), mesh,
                      rules)
    fn = jax.shard_map(_flash_stub, mesh=mesh, in_specs=(qs, ks, ks),
                          out_specs=qs, check_vma=False)
    return fn(q, k, v)


def self_attention(x, p, cfg, positions, *, causal: bool = True,
                   return_kv: bool = False):
    """Train/prefill self-attention block core (no residual/norm)."""
    q, k, v = _project_qkv(x, p, cfg, positions)
    if cfg.attn_impl == "pallas" and causal:
        from repro.kernels.flash_attention import flash_attention
        out = flash_attention(q, k, v, True, cfg.attn_q_chunk,
                              cfg.attn_kv_chunk,
                              jax.default_backend() != "tpu")
    elif cfg.attn_impl == "stub" and causal:
        out = _flash_stub_sharded(q, k, v)
    else:
        out = chunked_attention(q, k, v, causal=causal,
                                q_chunk=cfg.attn_q_chunk,
                                kv_chunk=cfg.attn_kv_chunk)
    out = logical_shard(out, "batch", "seq", "heads", None)
    B, S = x.shape[:2]
    out = out.reshape(B, S, cfg.n_heads * cfg.d_head)
    out = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    if return_kv:
        return out, (k, v)
    return out


def cross_attention(x, memory, p, cfg):
    """Decoder->encoder cross attention (no RoPE on memory side)."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(
        B, S, cfg.n_heads, cfg.d_head)
    k = jnp.einsum("bsd,dh->bsh", memory, p["wk"]).reshape(
        B, memory.shape[1], cfg.n_kv, cfg.d_head)
    v = jnp.einsum("bsd,dh->bsh", memory, p["wv"]).reshape(
        B, memory.shape[1], cfg.n_kv, cfg.d_head)
    out = chunked_attention(q, k, v, causal=False,
                            q_chunk=cfg.attn_q_chunk,
                            kv_chunk=cfg.attn_kv_chunk)
    out = out.reshape(B, S, cfg.n_heads * cfg.d_head)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"])


class KVCache(NamedTuple):
    k: jnp.ndarray        # (B, S_max, Hkv, D) — 'kv_seq' sharded
    v: jnp.ndarray
    length: jnp.ndarray   # () int32 — tokens already cached


def decode_attention(x, p, cfg, cache: KVCache):
    """One-token decode: attention over the sequence-sharded cache.

    Returns (out (B,1,d_model), new (k,v) for this position).  The softmax
    over the sharded cache axis lowers to local partial max/sum + small
    all-reduces — context-parallel flash-decoding via GSPMD.
    """
    B = x.shape[0]
    pos = cache.length[None].astype(jnp.int32)          # (1,)
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(
        B, 1, cfg.n_heads, cfg.d_head)
    k_new = jnp.einsum("bsd,dh->bsh", x, p["wk"]).reshape(
        B, 1, cfg.n_kv, cfg.d_head)
    v_new = jnp.einsum("bsd,dh->bsh", x, p["wv"]).reshape(
        B, 1, cfg.n_kv, cfg.d_head)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k_new = rms_norm(k_new, p["k_norm"], cfg.norm_eps)
    cos, sin = rope_table(pos, cfg.d_head, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k_new = apply_rope(k_new, cos, sin)

    Skv = cache.k.shape[1]
    G = cfg.n_heads // cfg.n_kv
    qg = q.reshape(B, cfg.n_kv, G, cfg.d_head)
    scale = cfg.d_head ** -0.5
    # scores over the sharded cache + the fresh position appended logically
    s_cache = jnp.einsum("bhgd,bshd->bhgs", qg, cache.k,
                         preferred_element_type=jnp.float32) * scale
    s_cache = logical_shard(s_cache, "batch", "kv_heads", None, "kv_seq")
    valid = jnp.arange(Skv) < cache.length
    s_cache = jnp.where(valid[None, None, None, :], s_cache, NEG_INF)
    s_new = jnp.einsum("bhgd,bshd->bhgs", qg, k_new,
                       preferred_element_type=jnp.float32) * scale
    m = jnp.maximum(s_cache.max(axis=-1), s_new[..., 0])
    p_cache = jnp.exp(s_cache - m[..., None])
    p_new = jnp.exp(s_new[..., 0] - m)
    denom = p_cache.sum(axis=-1) + p_new
    o = jnp.einsum("bhgs,bshd->bhgd", p_cache.astype(cache.v.dtype), cache.v,
                   preferred_element_type=jnp.float32)
    o = (o + p_new[..., None] * v_new[:, 0, :, None, :]) / denom[..., None]
    o = o.reshape(B, 1, cfg.n_heads * cfg.d_head).astype(x.dtype)
    out = jnp.einsum("bsh,hd->bsd", o, p["wo"])
    return out, (k_new, v_new)


def update_cache(cache: KVCache, k_new, v_new) -> KVCache:
    """Write this step's K/V at position ``length`` (sharded-dim DUS)."""
    k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_new, cache.length, 1)
    v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_new, cache.length, 1)
    k = logical_shard(k, "batch", "kv_seq", "kv_heads", None)
    v = logical_shard(v, "batch", "kv_seq", "kv_heads", None)
    return KVCache(k, v, cache.length + 1)


def init_cache(cfg, batch: int, max_len: int, n_layers: int, dtype):
    shape = (n_layers, batch, max_len, cfg.n_kv, cfg.d_head)
    k = jnp.zeros(shape, dtype)
    v = jnp.zeros(shape, dtype)
    return KVCache(k, v, jnp.zeros((), jnp.int32))
