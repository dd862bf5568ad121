"""Pallas TPU kernel: FlashAttention-style blocked attention with online
softmax, causal and sliding-window masking, and GQA head grouping.

TPU mapping:
  * grid = (B*Hq, Sq/bq, Sk/bk); the key axis is innermost/"arbitrary" so
    the f32 running (m, l, acc) state lives in VMEM scratch across its
    steps. Query/output tiles are (bq, D) — MXU-aligned for D in
    {64, 128, 256}.
  * GQA: the kv BlockSpec index map folds the query head onto its kv
    group — no materialized head repeat (the jnp path repeats).
  * Block-level skipping: key blocks entirely outside the causal /
    sliding window band are skipped with @pl.when — the kernel does no
    work for them (this is the structural win over masked dense attention
    that makes sliding-window decode O(window), used by the hymba and
    mixtral configs).

The backward pass is delegated to the jnp reference via custom_vjp in
ops.py: the kernel targets the serving/prefill hot path; training uses
XLA's fused attention from the reference path (see DESIGN.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30


def _make_kernel(block_q: int, block_k: int, seq_q: int, seq_k: int,
                 causal: bool, window: int, scale: float,
                 offset: int | None = None):
    num_k = seq_k // block_k
    if offset is None:
        offset = seq_k - seq_q   # query i sits at absolute position i + offset

    def kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref):
        iq, ik = pl.program_id(1), pl.program_id(2)

        @pl.when(ik == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        # ---- block-level skip test (static bounds per (iq, ik)) ----
        q_lo = iq * block_q + offset
        q_hi = q_lo + block_q - 1
        k_lo = ik * block_k
        k_hi = k_lo + block_k - 1
        live = jnp.bool_(True)
        if causal:
            live = live & (k_lo <= q_hi)
        if window > 0:
            live = live & (k_hi > q_lo - window)

        @pl.when(live)
        def _body():
            q = q_ref[0].astype(jnp.float32)              # (bq, D)
            k = k_ref[0].astype(jnp.float32)              # (bk, D)
            v = v_ref[0].astype(jnp.float32)              # (bk, D)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (bq, bk)

            qpos = q_lo + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = k_lo + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = jnp.ones((block_q, block_k), dtype=bool)
            if causal:
                mask = mask & (kpos <= qpos)
            if window > 0:
                mask = mask & (kpos > qpos - window)
            s = jnp.where(mask, s, _NEG_INF)

            m_prev = m_ref[...]                            # (bq, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
            acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[...] = m_new

        @pl.when(ik == num_k - 1)
        def _flush():
            l = jnp.maximum(l_ref[...], 1e-30)
            o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)

    return kernel


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           scale: float | None = None,
                           block_q: int = 128, block_k: int = 128,
                           offset: int | None = None,
                           interpret: bool = False):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D). Shapes must divide the
    blocks (ops.py pads and passes the *unpadded* position ``offset`` so
    padding never shifts the causal/window band). Returns (B, Hq, Sq, D)."""
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    if Hq % Hkv != 0:
        raise ValueError(f"query heads {Hq} not a multiple of kv heads {Hkv}")
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)

    qf = q.reshape(B * Hq, Sq, D)
    kf = k.reshape(B * Hkv, Sk, D)
    vf = v.reshape(B * Hkv, Sk, D)

    def kv_index(bh, iq, ik):
        # fold query head bh = b*Hq + h onto kv head b*Hkv + h//group.
        return (bh // Hq) * Hkv + (bh % Hq) // group, ik, 0

    kernel = _make_kernel(block_q, block_k, Sq, Sk, causal, window, scale,
                          offset)
    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, Sq // block_q, Sk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, D),
                               lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, Hq, Sq, D)
