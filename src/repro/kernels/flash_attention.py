"""Pallas TPU flash attention (fwd + bwd) — the LM-side hot-spot kernel.

This is the stencil paper's insight applied to attention: the (Sq, Skv)
score matrix is the "grid", and materializing it to HBM is what kills the
memory roofline term (measured: ~4 TB/device/step of score traffic on
granite-3-8b train_4k — EXPERIMENTS.md §Perf). The kernel tiles Q into
VMEM blocks (spatial blocking), streams KV tiles through a running online
softmax (the rolling-window/temporal dimension), and writes only the
(Sq, D) output — one HBM round-trip for the whole operator:

    HBM traffic: read Q + K + V (+dO, O, lse for bwd), write O (dQ,dK,dV)
    vs XLA chunked attention: s/p tiles cross HBM once per chunk pair.

Layout/tiling choices (TPU-native, not a GPU port):
  * block_q x d_head tiles sit in VMEM as (block_q, d_head) f32; MXU dims
    are d_head = 128-multiples; block_kv is a lane-aligned 128-multiple.
  * grid = (batch*heads, Sq/block_q); the kv loop is a fori_loop *inside*
    the kernel with `pl.when` causal skipping (block-level the same trick
    as the paper's "compute halos redundantly, mask only writes").
  * GQA: K/V are indexed by head-group via the BlockSpec index_map — no
    repeated K/V materialization (XLA path pays a G-times K/V blow-up).
  * backward recomputes s/p per tile pair (flash-2 style: no (Sq,Skv)
    residual; only O, lse, and the row-sum delta are read back).

Validated in interpret mode against ``ref_attention`` (tests/test_flash.py)
over shape/dtype/causal/GQA sweeps.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_KV = 512


def ref_attention(q, k, v, *, causal: bool = True):
    """Pure-jnp oracle: q (B,Sq,H,D); k,v (B,Skv,Hkv,D), GQA-aware."""
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    kr = jnp.repeat(k, G, axis=2)
    vr = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * (D ** -0.5)
    if causal:
        Skv = k.shape[1]
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(Skv)[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, vr.astype(jnp.float32))
    return o.astype(q.dtype)


# --- forward kernel ----------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                causal: bool, block_kv: int, skv: int, scale: float):
    """One (batch*head, q-block) program: stream kv blocks, online softmax.

    q_ref (Bq, D); k_ref/v_ref (Skv, D) in ANY/VMEM; o_ref (Bq, D);
    lse_ref (Bq, 1).
    """
    Bq, D = q_ref.shape
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale
    nkv = skv // block_kv

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = qi * Bq + jax.lax.broadcasted_iota(jnp.int32, (Bq, block_kv), 0)
            kpos = j * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (Bq, block_kv), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m2 = jnp.maximum(m, s.max(axis=1, keepdims=True))
        corr = jnp.exp(m - m2)
        p = jnp.exp(s - m2)
        l2 = l * corr + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc2 = acc * corr + pv
        return m2, l2, acc2

    m0 = jnp.full((Bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Bq, 1), jnp.float32)
    a0 = jnp.zeros((Bq, D), jnp.float32)
    if causal:
        # block-level early exit: kv blocks fully above the diagonal of this
        # q block contribute nothing (paper's "control only the writes",
        # lifted to control flow since whole blocks are skippable)
        last = (qi + 1) * Bq  # first kv index NOT needed
        nkv_eff = jnp.minimum(nkv, pl.cdiv(last, block_kv))
    else:
        nkv_eff = nkv
    m, l, acc = jax.lax.fori_loop(0, nkv_eff, body, (m0, l0, a0))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_ref[...] = lse.astype(lse_ref.dtype)


def _flash_fwd_pallas(q, k, v, causal: bool, block_q: int, block_kv: int,
                      interpret: bool):
    """q (B,Sq,H,D); k/v (B,Skv,Hkv,D) -> (o, lse)."""
    B, Sq, H, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = H // Hkv
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0 and Skv % block_kv == 0
    scale = D ** -0.5

    # (B,S,H,D) -> (B*H, S, D) program-major layout
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)

    grid = (B * H, Sq // block_q)
    kernel = functools.partial(_fwd_kernel, causal=causal,
                               block_kv=block_kv, skv=Skv, scale=scale)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda h, i: (h, i, 0)),
            pl.BlockSpec((None, Skv, D), lambda h, i, G=G: (h // G, 0, 0)),
            pl.BlockSpec((None, Skv, D), lambda h, i, G=G: (h // G, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, D), lambda h, i: (h, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda h, i: (h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, Sq, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(qt, kt, vt)
    o = o.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    lse = lse.reshape(B, H, Sq)
    return o, lse


# --- backward kernels --------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dq_ref, *,
                   causal: bool, block_kv: int, skv: int, scale: float):
    Bq, D = q_ref.shape
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    lse = lse_ref[...].astype(jnp.float32)
    dlt = dlt_ref[...].astype(jnp.float32)
    nkv = skv // block_kv

    def body(j, dq):
        k = k_ref[pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * Bq + jax.lax.broadcasted_iota(jnp.int32, (Bq, block_kv), 0)
            kpos = j * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (Bq, block_kv), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dlt)
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    if causal:
        nkv_eff = jnp.minimum(nkv, pl.cdiv((qi + 1) * Bq, block_kv))
    else:
        nkv_eff = nkv
    dq = jax.lax.fori_loop(0, nkv_eff, body,
                           jnp.zeros((Bq, D), jnp.float32))
    dq_ref[...] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
                    dk_ref, dv_ref, *, causal: bool, block_q: int, sq: int,
                    scale: float):
    Bk, D = k_ref.shape
    ki = pl.program_id(1)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    nq = sq // block_q

    def body(i, carry):
        dk, dv = carry
        q = q_ref[pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        dlt = dlt_ref[pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, Bk), 0)
            kpos = ki * Bk + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, Bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dv2 = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dlt)
        dk2 = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
        return dk2, dv2

    if causal:
        # q blocks strictly above this kv block's diagonal see none of it
        first = (ki * Bk) // block_q
    else:
        first = 0
    dk0 = jnp.zeros((Bk, D), jnp.float32)
    dv0 = jnp.zeros((Bk, D), jnp.float32)
    dk, dv = jax.lax.fori_loop(first, nq, body, (dk0, dv0))
    dk_ref[...] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, causal: bool, block_q: int,
                      block_kv: int, interpret: bool):
    B, Sq, H, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = H // Hkv
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    scale = D ** -0.5

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                               # (B,Sq,H)
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    dot = do.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    lset = lse.reshape(B * H, Sq, 1)
    dltt = delta.transpose(0, 2, 1).reshape(B * H, Sq, 1)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, block_kv=block_kv,
                          skv=Skv, scale=scale),
        grid=(B * H, Sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda h, i: (h, i, 0)),
            pl.BlockSpec((None, Skv, D), lambda h, i, G=G: (h // G, 0, 0)),
            pl.BlockSpec((None, Skv, D), lambda h, i, G=G: (h // G, 0, 0)),
            pl.BlockSpec((None, block_q, D), lambda h, i: (h, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda h, i: (h, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda h, i: (h, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(qt, kt, vt, dot, lset, dltt)

    # dk/dv per q-head, then sum over the G query heads of each kv head
    dkh, dvh = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, block_q=block_q,
                          sq=Sq, scale=scale),
        grid=(B * H, Skv // block_kv),
        in_specs=[
            pl.BlockSpec((None, Sq, D), lambda h, j: (h, 0, 0)),
            pl.BlockSpec((None, block_kv, D), lambda h, j, G=G: (h // G, j, 0)),
            pl.BlockSpec((None, block_kv, D), lambda h, j, G=G: (h // G, j, 0)),
            pl.BlockSpec((None, Sq, D), lambda h, j: (h, 0, 0)),
            pl.BlockSpec((None, Sq, 1), lambda h, j: (h, 0, 0)),
            pl.BlockSpec((None, Sq, 1), lambda h, j: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_kv, D), lambda h, j: (h, j, 0)),
            pl.BlockSpec((None, block_kv, D), lambda h, j: (h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Skv, D), jnp.float32),
            jax.ShapeDtypeStruct((B * H, Skv, D), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(qt, kt, vt, dot, lset, dltt)

    dq = dq.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    dkh = dkh.reshape(B, Hkv, G, Skv, D).sum(axis=2)
    dvh = dvh.reshape(B, Hkv, G, Skv, D).sum(axis=2)
    dk = dkh.transpose(0, 2, 1, 3).astype(k.dtype)
    dv = dvh.transpose(0, 2, 1, 3).astype(v.dtype)
    return dq, dk, dv


# --- custom-vjp wrapper ------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_kv: int = DEFAULT_BLOCK_KV,
                    interpret: bool = True):
    """Flash attention via Pallas. q (B,Sq,H,D); k/v (B,Skv,Hkv,D)."""
    o, _ = _flash_fwd_pallas(q, k, v, causal, block_q, block_kv, interpret)
    return o


def _fa_fwd(q, k, v, causal, block_q, block_kv, interpret):
    o, lse = _flash_fwd_pallas(q, k, v, causal, block_q, block_kv, interpret)
    return o, (q, k, v, o, lse)


def _fa_bwd(causal, block_q, block_kv, interpret, res, do):
    q, k, v, o, lse = res
    return _flash_bwd_pallas(q, k, v, o, lse, do, causal, block_q, block_kv,
                             interpret)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def flash_traffic_bytes(B: int, Sq: int, Skv: int, H: int, Hkv: int, D: int,
                        bytes_el: int = 2, train: bool = True) -> int:
    """Exact HBM traffic of the kernel's DMA schedule (cf. dma_traffic_bytes
    for the stencil kernels): fwd reads Q + K,V per q-block pass (K/V are
    re-streamed from HBM once per q-block row when they exceed VMEM; for
    per-device shapes here K/V fit VMEM, so one read), writes O + lse; bwd
    reads Q,K,V,O,dO,lse and writes dQ,dK,dV."""
    qb = B * Sq * H * D * bytes_el
    kvb = 2 * B * Skv * Hkv * D * bytes_el
    ob = qb
    lseb = B * Sq * H * 4
    fwd = qb + kvb + ob + lseb
    if not train:
        return fwd
    bwd = (qb + kvb + ob + qb + lseb + lseb) + (qb + kvb)
    return fwd + bwd


def flash_flops(B: int, Sq: int, Skv: int, H: int, D: int,
                causal: bool = True, train: bool = True) -> float:
    """MXU FLOPs of the kernel: 2 dots fwd (4·S²·D per head), 5 dots bwd."""
    pairs = Sq * Skv * (0.5 if causal else 1.0)
    fwd = 2 * 2 * B * H * pairs * D
    if not train:
        return fwd
    return fwd + 5 * 2 * B * H * pairs * D
