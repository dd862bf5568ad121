"""Shared model layers: norms, rotary embeddings, GQA attention (full /
sliding-window, train & cached-decode paths), SwiGLU MLP, and the
capacity-based MoE block with expert-parallel sharding.

Functional style: ``init_*`` returns a param dict; ``apply``-style
functions are pure. All matmuls run in the config dtype (bf16) with f32
for norms, softmax, router logits and attention accumulation.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.flash_attention.ops import flash_attention


def maybe_shard(x, spec: P):
    """with_sharding_constraint that degrades to identity when no mesh (or
    a mesh lacking the named axes) is in context — so model code runs
    unchanged on a single CPU device and under the production mesh."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty:
            return x
        names = set()
        for part in spec:
            if part is None:
                continue
            names.update((part,) if isinstance(part, str) else part)
        if not names.issubset(set(mesh.axis_names)):
            return x
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x


# ---------------------------------------------------------------------------
# Norms & activations
# ---------------------------------------------------------------------------

def init_norm(d: int, dtype) -> Dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * params["scale"].astype(jnp.float32)).astype(x.dtype)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "relu": jax.nn.relu}[name]


def _dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0]
    std = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (B, H, S, D); positions: (S,) or (B, S) absolute positions."""
    D = x.shape[-1]
    freqs = rope_frequencies(D, theta)                  # (D/2,)
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]
        ang = ang[None, None]                           # (1, 1, S, D/2)
    else:
        ang = positions[:, None, :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., ::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, qkv_bias: bool, dtype) -> Dict:
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (d_model, n_heads * head_dim), dtype),
        "wk": _dense_init(ks[1], (d_model, n_kv_heads * head_dim), dtype),
        "wv": _dense_init(ks[2], (d_model, n_kv_heads * head_dim), dtype),
        "wo": _dense_init(ks[3], (n_heads * head_dim, d_model), dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
    return p


def _project_qkv(params, x, n_heads, n_kv_heads, head_dim):
    B, S, _ = x.shape
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(B, S, n_heads, head_dim).transpose(0, 2, 1, 3)
    k = k.reshape(B, S, n_kv_heads, head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(B, S, n_kv_heads, head_dim).transpose(0, 2, 1, 3)
    return q, k, v


def attention_train(params, x, *, n_heads, n_kv_heads, head_dim,
                    rope_theta, window: int = 0, causal: bool = True,
                    positions=None, use_pallas: bool = False,
                    kv_override=None):
    """Full-sequence attention (training / prefill). Returns (out, (k, v))
    so prefill can seed the decode cache. ``kv_override`` supplies
    externally computed (k, v) — used by cross-attention."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
    if kv_override is not None:
        k, v = kv_override
    elif rope_theta > 0:
        if positions is None:
            positions = jnp.arange(S)
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    elif positions is None:
        pass
    o = flash_attention(q, k, v, causal=causal, window=window,
                        use_pallas=use_pallas)
    o = o.transpose(0, 2, 1, 3).reshape(B, S, n_heads * head_dim)
    return o @ params["wo"], (k, v)


# Perf-iteration flag (EXPERIMENTS.md §Perf): the baseline decode
# materializes the GQA head repeat of the cache (matching the reference);
# the grouped path contracts (Hkv, g) without the repeat — on the sharded
# split-KV cache the repeat forces an involuntary full rematerialization
# in GSPMD (observed in the dry-run logs).
DECODE_GROUPED_GQA = False


def attention_decode(params, x, cache_k, cache_v, pos, *, n_heads,
                     n_kv_heads, head_dim, rope_theta, window: int = 0):
    """Single-token decode against a KV cache.

    x: (B, 1, D); cache_k/v: (B, Hkv, S, D); pos: scalar — the position of
    the new token (cache entries [0, pos) are valid; the new KV is written
    at index pos, or at pos % window for sliding-window ring caches).
    Returns (out, new_cache_k, new_cache_v).
    """
    B = x.shape[0]
    S = cache_k.shape[2]
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
    if rope_theta > 0:
        posv = jnp.asarray(pos)[None]
        q = apply_rope(q, posv, rope_theta)
        k = apply_rope(k, posv, rope_theta)
    slot = pos % S if window > 0 else jnp.minimum(pos, S - 1)
    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k.astype(cache_k.dtype), (0, 0, slot, 0))
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v.astype(cache_v.dtype), (0, 0, slot, 0))

    group = n_heads // n_kv_heads
    kpos = jnp.arange(S)
    valid = kpos <= pos if window <= 0 else \
        (kpos <= pos) | (pos >= S)       # ring cache: all slots live once full
    if DECODE_GROUPED_GQA:
        qg = q.reshape(B, n_kv_heads, group, head_dim).astype(jnp.float32)
        kf = cache_k.astype(jnp.float32)
        vf = cache_v.astype(jnp.float32)
        scores = jnp.einsum("bhgd,bhkd->bhgk", qg, kf) / (head_dim ** 0.5)
        scores = jnp.where(valid[None, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bhgk,bhkd->bhgd", probs, vf).astype(x.dtype)
        o = o.reshape(B, 1, n_heads * head_dim)
        return o @ params["wo"], cache_k, cache_v
    qf = q.astype(jnp.float32)
    kf = jnp.repeat(cache_k.astype(jnp.float32), group, axis=1)
    vf = jnp.repeat(cache_v.astype(jnp.float32), group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) / (head_dim ** 0.5)
    scores = jnp.where(valid[None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", probs, vf).astype(x.dtype)
    o = o.transpose(0, 2, 1, 3).reshape(B, 1, n_heads * head_dim)
    return o @ params["wo"], cache_k, cache_v


# ---------------------------------------------------------------------------
# MLP (SwiGLU)
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, dtype,
             mlp_type: str = "swiglu") -> Dict:
    ks = jax.random.split(key, 3)
    p = {
        "w_up": _dense_init(ks[1], (d_model, d_ff), dtype),
        "w_down": _dense_init(ks[2], (d_ff, d_model), dtype),
    }
    if mlp_type == "swiglu":
        p["w_gate"] = _dense_init(ks[0], (d_model, d_ff), dtype)
    return p


def mlp(params, x, act: str = "silu"):
    if "w_gate" in params:          # gated (SwiGLU-style)
        h = act_fn(act)(x @ params["w_gate"]) * (x @ params["w_up"])
    else:                           # plain 2-matrix MLP (whisper)
        h = act_fn(act)(x @ params["w_up"])
    return h @ params["w_down"]


# ---------------------------------------------------------------------------
# MoE (capacity-based dispatch, expert-parallel)
# ---------------------------------------------------------------------------

# Perf-iteration flag (EXPERIMENTS.md §Perf): shard the dispatch buffers'
# capacity dim over BOTH data and model axes (256-way instead of 16-way)
# when EP is unavailable — hypothesis: smaller resident buffers and less
# resharding traffic around the expert GEMMs.
MOE_BUF_2D = False


def _moe_buffer_spec(n_experts: int, ep_axis: Optional[str]):
    """Sharding for the (E, C, D) dispatch buffers: experts over the axis
    when divisible (EP), else capacity over the axis (keeps the all-to-all
    local while expert-TP splits the FFN dims)."""
    if ep_axis is None:
        return None
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty or ep_axis not in mesh.axis_names:
            return None
        size = mesh.shape[ep_axis]
    except Exception:
        return None
    if n_experts % size == 0:
        return P(ep_axis, None, None)
    if MOE_BUF_2D and "data" in mesh.axis_names:
        return P(None, ("data", ep_axis), None)
    return P(None, ep_axis, None)


def init_moe(key, d_model: int, d_ff: int, n_experts: int, dtype) -> Dict:
    ks = jax.random.split(key, 4)
    shape = (n_experts, d_model, d_ff)

    def einit(k, s):
        return (jax.random.normal(k, s, jnp.float32)
                * (s[1] ** -0.5)).astype(dtype)

    return {
        "router": _dense_init(ks[0], (d_model, n_experts), jnp.float32),
        "w_gate": einit(ks[1], shape),
        "w_up": einit(ks[2], shape),
        "w_down": einit(ks[3], (n_experts, d_ff, d_model)),
    }


def _moe_tokens(params, xf, *, n_experts: int, top_k: int,
                capacity_factor: float, act: str,
                ep_axis: Optional[str]):
    """Capacity-based top-k MoE over a flat token block (T, D)."""
    T, D = xf.shape
    K = top_k

    logits = (xf.astype(jnp.float32) @ params["router"])      # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, tope = jax.lax.top_k(probs, K)                      # (T, K)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch): E * sum_e f_e * p_e
    me = probs.mean(axis=0)
    ce = jnp.zeros((n_experts,), jnp.float32).at[tope.reshape(-1)].add(
        1.0 / (T * K))
    aux = n_experts * jnp.sum(me * ce)

    C = max(int(T * K / n_experts * capacity_factor), 4)
    e_flat = tope.reshape(T * K)
    onehot = jax.nn.one_hot(e_flat, n_experts, dtype=jnp.int32)
    # position-in-expert via log-depth associative scan (a plain cumsum
    # lowers to reduce-window, which XLA's cost model charges O(n^2)).
    cum = jax.lax.associative_scan(jnp.add, onehot, axis=0)
    pos_flat = (cum - 1)[jnp.arange(T * K), e_flat]           # (T*K,)
    keep = (pos_flat < C)
    pos_c = jnp.where(keep, pos_flat, 0)

    x_rep = jnp.repeat(xf, K, axis=0)                         # (T*K, D)
    buf = jnp.zeros((n_experts, C, D), xf.dtype)
    buf = buf.at[e_flat, pos_c].add(
        jnp.where(keep[:, None], x_rep, 0).astype(xf.dtype))
    # EP when experts divide the axis, otherwise shard token capacity
    # (expert-TP handles the FFN dims through the weight shardings).
    ep_spec = _moe_buffer_spec(n_experts, ep_axis)
    if ep_spec is not None:
        buf = maybe_shard(buf, ep_spec)

    h = act_fn(act)(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    if ep_spec is not None:
        out_buf = maybe_shard(out_buf, ep_spec)

    gathered = out_buf[e_flat, pos_c]                         # (T*K, D)
    gathered = jnp.where(keep[:, None], gathered, 0)
    w_flat = topw.reshape(T * K, 1).astype(gathered.dtype)
    out = (gathered * w_flat).reshape(T, K, D).sum(axis=1)
    return out, aux


# token blocks larger than this are processed by a scan over chunks —
# bounds the dispatch buffers (x_rep, (E, C, D)) at chunk granularity.
MOE_CHUNK_TOKENS = 1 << 17


def moe(params, x, *, n_experts: int, top_k: int,
        capacity_factor: float = 1.25, act: str = "silu",
        ep_axis: Optional[str] = "model",
        chunk_tokens: Optional[int] = None):
    """GShard-style capacity-based top-k MoE (see _moe_tokens).

    Token blocks beyond ``chunk_tokens`` are processed chunkwise (capacity
    applies per chunk — slightly different drop behaviour, recorded in
    DESIGN.md). Returns (out, aux_loss).
    """
    B, S, D = x.shape
    T = B * S
    xf = x.reshape(T, D)
    if chunk_tokens is None:
        chunk_tokens = MOE_CHUNK_TOKENS   # read at call time (perf knob)
    kwargs = dict(n_experts=n_experts, top_k=top_k,
                  capacity_factor=capacity_factor, act=act,
                  ep_axis=ep_axis)
    if chunk_tokens and T > chunk_tokens and T % chunk_tokens == 0:
        nch = T // chunk_tokens

        def body(_, xc):
            out, aux = _moe_tokens(params, xc, **kwargs)
            return None, (out, aux)

        from repro.kernels.flash_attention import ops as _fops
        _, (outs, auxs) = jax.lax.scan(
            body, None, xf.reshape(nch, chunk_tokens, D),
            unroll=nch if _fops._COST_EXACT else 1)
        return outs.reshape(B, S, D), jnp.mean(auxs)
    out, aux = _moe_tokens(params, xf, **kwargs)
    return out.reshape(B, S, D), aux
