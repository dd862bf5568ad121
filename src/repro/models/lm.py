"""Decoder LM (+ optional encoder for enc-dec archs) covering every
assigned architecture through the per-layer block pattern:

    attn_mlp  — GQA attention + SwiGLU MLP            (dense family)
    swa_mlp   — sliding-window attention + MLP
    moe       — GQA/SWA attention + top-k MoE FFN      (mixtral, granite)
    mamba_mlp — SSM heads + MLP
    hybrid    — parallel attention ∥ SSM heads + MLP   (hymba)
    mlstm     — xLSTM matrix-memory block (no separate MLP)
    slstm     — xLSTM scalar-memory block (sequential scan)

Layers are scanned over the block-pattern period (homogeneous stacks keep
the HLO small for the 512-device dry-run lowering); per-slot params are
stacked along a leading group axis. Three entry points:

    train_loss(params, arch, batch)   -> scalar loss
    prefill(params, arch, tokens,...) -> (logits_last, cache)
    decode_step(params, arch, batch)  -> (logits, new_cache)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import recurrent as R


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_block(key, arch: ArchConfig, kind: str) -> Dict:
    D, F = arch.d_model, arch.d_ff
    dt = arch.jnp_dtype
    Hd = arch.head_dim_
    ks = jax.random.split(key, 6)
    p: Dict = {"norm1": L.init_norm(D, dt)}
    if kind in ("attn_mlp", "swa_mlp", "moe", "hybrid"):
        p["attn"] = L.init_attention(ks[0], D, arch.n_heads, arch.n_kv_heads,
                                     Hd, arch.qkv_bias, dt)
    if kind in ("mamba_mlp", "hybrid"):
        p["ssm"] = R.init_ssm_heads(ks[1], D, arch.ssm_heads or arch.n_heads,
                                    arch.ssm_state, dt)
    if kind == "mlstm":
        p["mlstm"] = R.init_mlstm(ks[2], D, arch.n_heads, dt)
    elif kind == "slstm":
        p["slstm"] = R.init_slstm(ks[3], D, arch.n_heads, dt)
    else:
        p["norm2"] = L.init_norm(D, dt)
        if kind == "moe":
            p["moe"] = L.init_moe(ks[4], D, F, arch.n_experts, dt)
        else:
            p["mlp"] = L.init_mlp(ks[5], D, F, dt, arch.mlp_type)
    if arch.is_encdec:
        p["norm_x"] = L.init_norm(D, dt)
        p["xattn"] = L.init_attention(ks[0] if kind != "attn_mlp" else ks[1],
                                      D, arch.n_heads, arch.n_kv_heads, Hd,
                                      False, dt)
    return p


def init_params(arch: ArchConfig, key) -> Dict:
    dt = arch.jnp_dtype
    D, V = arch.d_model, arch.vocab_size
    keys = jax.random.split(key, arch.n_layers + 8)
    period = len(arch.block_pattern)
    if arch.n_layers % period != 0:
        raise ValueError(
            f"{arch.name}: n_layers={arch.n_layers} not a multiple of "
            f"the block pattern period {period}")
    groups = arch.n_layers // period

    # stack each pattern slot's params over the groups.
    layer_params = {}
    for slot, kind in enumerate(arch.block_pattern):
        per_group = [_init_block(keys[g * period + slot], arch, kind)
                     for g in range(groups)]
        layer_params[f"slot{slot}_{kind}"] = jax.tree.map(
            lambda *xs: jnp.stack(xs), *per_group)

    params = {
        "embed": (jax.random.normal(keys[-1], (V, D), jnp.float32)
                  * 0.02).astype(dt),
        "final_norm": L.init_norm(D, dt),
        "layers": layer_params,
    }
    if not arch.tie_embeddings:
        params["unembed"] = (jax.random.normal(keys[-2], (D, V), jnp.float32)
                             * D ** -0.5).astype(dt)
    if arch.meta_tokens:
        params["meta"] = (jax.random.normal(
            keys[-3], (arch.meta_tokens, D), jnp.float32) * 0.02).astype(dt)
    if arch.is_encdec:
        enc_layers = [_init_block(keys[-4 - i], ArchConfig(
            **{**dataclasses.asdict(arch), "encoder_layers": 0,
               "block_pattern": ("attn_mlp",)}), "attn_mlp")
            for i in range(arch.encoder_layers)]
        params["encoder"] = {
            "layers": jax.tree.map(lambda *xs: jnp.stack(xs), *enc_layers),
            "final_norm": L.init_norm(D, dt),
            "pos_embed": (jax.random.normal(
                keys[-5], (arch.encoder_seq, D), jnp.float32) * 0.02
            ).astype(dt),
        }
    return params


def param_specs(arch: ArchConfig):
    """ShapeDtypeStruct tree of the params — zero allocation (dry-run)."""
    return jax.eval_shape(lambda: init_params(arch, jax.random.key(0)))


def param_count(arch: ArchConfig, include_embed: bool = True) -> int:
    import math
    specs = param_specs(arch)
    if not include_embed:
        specs = dict(specs)
        specs.pop("embed", None)
        specs.pop("unembed", None)
    return sum(math.prod(l.shape) for l in jax.tree.leaves(specs))


# ---------------------------------------------------------------------------
# Forward (full-sequence: train / prefill)
# ---------------------------------------------------------------------------

def _block_forward(p, x, arch: ArchConfig, kind: str, *,
                   enc_out=None, use_pallas: bool = False):
    """One block, full sequence. Returns (x, cache_entries)."""
    window = arch.window if kind in ("swa_mlp", "moe", "hybrid") else 0
    cache = {}
    h = L.rmsnorm(p["norm1"], x)
    if kind in ("attn_mlp", "swa_mlp", "moe"):
        a, (ck, cv) = L.attention_train(
            p["attn"], h, n_heads=arch.n_heads, n_kv_heads=arch.n_kv_heads,
            head_dim=arch.head_dim_, rope_theta=arch.rope_theta,
            window=window, use_pallas=use_pallas)
        cache["k"], cache["v"] = ck, cv
        x = x + a
    elif kind == "mamba_mlp":
        a, state = R.ssm_heads_train(p["ssm"], h,
                                     n_heads=arch.ssm_heads or arch.n_heads,
                                     dk=arch.ssm_state)
        cache["ssm_state"] = state
        x = x + a
    elif kind == "hybrid":
        a, (ck, cv) = L.attention_train(
            p["attn"], h, n_heads=arch.n_heads, n_kv_heads=arch.n_kv_heads,
            head_dim=arch.head_dim_, rope_theta=arch.rope_theta,
            window=window, use_pallas=use_pallas)
        s, state = R.ssm_heads_train(p["ssm"], h,
                                     n_heads=arch.ssm_heads or arch.n_heads,
                                     dk=arch.ssm_state)
        cache["k"], cache["v"], cache["ssm_state"] = ck, cv, state
        x = x + 0.5 * (a + s)
    elif kind == "mlstm":
        a, (state, norm) = R.mlstm_train(p["mlstm"], h, n_heads=arch.n_heads)
        cache["mlstm_state"], cache["mlstm_norm"] = state, norm
        return x + a, cache
    elif kind == "slstm":
        a, state = R.slstm_train(p["slstm"], h, n_heads=arch.n_heads)
        cache["slstm_state"] = state
        return x + a, cache

    if arch.is_encdec and enc_out is not None:
        hx = L.rmsnorm(p["norm_x"], x)
        cx, _ = L.attention_train(
            p["xattn"], hx, n_heads=arch.n_heads,
            n_kv_heads=arch.n_kv_heads, head_dim=arch.head_dim_,
            rope_theta=0.0, causal=False,
            kv_override=_cross_kv(p["xattn"], enc_out, arch))
        x = x + cx

    h2 = L.rmsnorm(p["norm2"], x)
    if kind == "moe":
        f, aux = L.moe(p["moe"], h2, n_experts=arch.n_experts,
                       top_k=arch.top_k,
                       capacity_factor=arch.capacity_factor, act=arch.act)
        cache["moe_aux"] = aux
    else:
        f = L.mlp(p["mlp"], h2, act=arch.act)
    return x + f, cache


def _cross_kv(xattn_params, enc_out, arch: ArchConfig):
    """Project encoder output to cross-attention K/V (no rope)."""
    B, Se, _ = enc_out.shape
    k = (enc_out @ xattn_params["wk"]).reshape(
        B, Se, arch.n_kv_heads, arch.head_dim_).transpose(0, 2, 1, 3)
    v = (enc_out @ xattn_params["wv"]).reshape(
        B, Se, arch.n_kv_heads, arch.head_dim_).transpose(0, 2, 1, 3)
    return k, v


def _scan_layers(params, x, arch: ArchConfig, fn, remat: str = "none",
                 shard_acts: bool = False, unroll_layers: int = 0):
    """Scan ``fn(slot_params, x, kind) -> (x, per_layer_out)`` over the
    layer groups; the pattern period is unrolled inside the body.

    remat: "none" | "full" | "dots" — activation checkpointing policy for
    the block body. shard_acts: apply the sequence-parallel layer-boundary
    sharding constraint. unroll_layers > 0 replaces the scan with a python
    loop over that many groups (roofline cost extraction — see
    repro.roofline: XLA's cost_analysis counts a scan body once).
    """
    slots = [f"slot{i}_{k}" for i, k in enumerate(arch.block_pattern)]

    def body(x, group_params):
        outs = {}
        for slot, kind in zip(slots, arch.block_pattern):
            x, out = fn(group_params[slot], x, kind)
            outs[slot] = out
        if shard_acts:
            from repro.parallel.sharding import activation_spec
            mesh = jax.sharding.get_abstract_mesh()
            if not mesh.empty:
                x = L.maybe_shard(x, activation_spec(mesh.axis_names))
        return x, outs

    if remat == "full":
        body = jax.checkpoint(body)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots)

    if unroll_layers:
        outs = []
        for g in range(unroll_layers):
            gp = jax.tree.map(lambda a: a[g], params["layers"])
            x, out = body(x, gp)
            outs.append(out)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        return x, stacked
    return jax.lax.scan(body, x, params["layers"])


def _sinusoid(positions, d: int):
    half = d // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _embed(params, arch: ArchConfig, tokens, extras: Dict, pos0=0):
    x = params["embed"][tokens].astype(arch.jnp_dtype)
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty:
        from repro.parallel.sharding import activation_spec
        x = L.maybe_shard(x, activation_spec(mesh.axis_names))
    if arch.pos_embed == "sinusoidal":
        positions = pos0 + jnp.arange(tokens.shape[1])
        x = x + _sinusoid(positions, arch.d_model)[None].astype(x.dtype)
    if arch.frontend == "vision_stub" and "patches" in extras:
        x = jnp.concatenate([extras["patches"].astype(x.dtype), x], axis=1)
    if arch.meta_tokens:
        B = x.shape[0]
        meta = jnp.broadcast_to(params["meta"][None],
                                (B, arch.meta_tokens, arch.d_model))
        x = jnp.concatenate([meta.astype(x.dtype), x], axis=1)
    return x


def _encoder_forward(params, arch: ArchConfig, frames, use_pallas=False,
                     remat: str = "none"):
    """Whisper-style encoder over (stub) frame embeddings."""
    enc = params["encoder"]
    x = frames.astype(arch.jnp_dtype) + enc["pos_embed"][None]

    def body(x, lp):
        h = L.rmsnorm(lp["norm1"], x)
        a, _ = L.attention_train(
            lp["attn"], h, n_heads=arch.n_heads, n_kv_heads=arch.n_kv_heads,
            head_dim=arch.head_dim_, rope_theta=0.0, causal=False,
            use_pallas=use_pallas)
        x = x + a
        h2 = L.rmsnorm(lp["norm2"], x)
        return x + L.mlp(lp["mlp"], h2, act=arch.act), None

    if remat == "full":
        body = jax.checkpoint(body)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots)
    x, _ = jax.lax.scan(body, x, enc["layers"])
    return L.rmsnorm(enc["final_norm"], x)


def forward(params, arch: ArchConfig, tokens, extras: Optional[Dict] = None,
            use_pallas: bool = False, return_cache: bool = False,
            remat: str = "none", shard_acts: bool = False,
            unroll_layers: int = 0):
    """Full-sequence forward. Returns (logits, aux, cache)."""
    extras = extras or {}
    enc_out = None
    if arch.is_encdec:
        enc_out = _encoder_forward(params, arch, extras["frames"],
                                   use_pallas, remat=remat)
    x = _embed(params, arch, tokens, extras)

    def fn(slot_params, x, kind):
        return _block_forward(slot_params, x, arch, kind, enc_out=enc_out,
                              use_pallas=use_pallas)

    x, caches = _scan_layers(params, x, arch, fn, remat=remat,
                             shard_acts=shard_acts,
                             unroll_layers=unroll_layers)
    x = L.rmsnorm(params["final_norm"], x)
    unembed = params.get("unembed")
    if unembed is None:
        unembed = params["embed"].T
    logits = x @ unembed
    aux = jnp.float32(0)
    for slot_out in caches.values():
        if "moe_aux" in slot_out:
            aux = aux + jnp.sum(slot_out["moe_aux"])
    return logits, aux, (caches if return_cache else None)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def train_loss(params, arch: ArchConfig, batch: Dict,
               use_pallas: bool = False, aux_weight: float = 0.01,
               remat: str = "none", shard_acts: bool = False,
               unroll_layers: int = 0):
    tokens, targets = batch["tokens"], batch["targets"]
    extras = {k: v for k, v in batch.items()
              if k not in ("tokens", "targets")}
    logits, aux, _ = forward(params, arch, tokens, extras,
                             use_pallas=use_pallas, remat=remat,
                             shard_acts=shard_acts,
                             unroll_layers=unroll_layers)
    # prefix tokens (patches / meta) carry no loss.
    n_prefix = logits.shape[1] - targets.shape[1]
    if n_prefix:
        logits = logits[:, n_prefix:]
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    # vocab-parallel gold-logit extraction (Megatron-style): a masked
    # reduction over the sharded vocab dim instead of take_along_axis —
    # the gather would force an all-gather of the V-sharded logits.
    v_iota = jnp.arange(logits.shape[-1])
    gold = jnp.sum(jnp.where(v_iota[None, None, :] == targets[..., None],
                             logits, 0.0), axis=-1)
    loss = jnp.mean(logz - gold)
    return loss + aux_weight * aux


# ---------------------------------------------------------------------------
# KV / state cache: specs, prefill, decode
# ---------------------------------------------------------------------------

def _cache_len(arch: ArchConfig, kind: str, seq_len: int) -> int:
    if kind in ("swa_mlp", "hybrid") and arch.window > 0:
        return min(seq_len, arch.window)
    if kind == "moe" and arch.window > 0:
        return min(seq_len, arch.window)
    return seq_len


def cache_specs(arch: ArchConfig, batch: int, seq_len: int):
    """ShapeDtypeStruct tree of the decode cache (leading group axis
    matches the layer scan)."""
    dt = arch.jnp_dtype
    f32 = jnp.float32
    period = len(arch.block_pattern)
    G = arch.n_layers // period
    Hd = arch.head_dim_
    Hkv = arch.n_kv_heads
    H = arch.n_heads
    dh = arch.d_model // H
    Hs = arch.ssm_heads or arch.n_heads
    dv_ssm = arch.d_model // Hs

    def sd(shape, dtype=dt):
        return jax.ShapeDtypeStruct((G,) + shape, dtype)

    out = {}
    for slot, kind in enumerate(arch.block_pattern):
        entry = {}
        if kind in ("attn_mlp", "swa_mlp", "moe", "hybrid"):
            Sc = _cache_len(arch, kind, seq_len)
            entry["k"] = sd((batch, Hkv, Sc, Hd))
            entry["v"] = sd((batch, Hkv, Sc, Hd))
        if kind in ("mamba_mlp", "hybrid"):
            entry["ssm_state"] = sd((batch, Hs, arch.ssm_state, dv_ssm), f32)
        if kind == "mlstm":
            entry["mlstm_state"] = sd((batch, H, Hd, dh), f32)
            entry["mlstm_norm"] = sd((batch, H, Hd), f32)
        if kind == "slstm":
            for s in ("c", "n", "h", "m"):
                entry[f"slstm_{s}"] = sd((batch, H, dh), f32)
        out[f"slot{slot}_{kind}"] = entry
    if arch.is_encdec:
        out["cross"] = {"k": sd((batch, Hkv, arch.encoder_seq, Hd)),
                        "v": sd((batch, Hkv, arch.encoder_seq, Hd))}
    return out


def _block_decode(p, x, arch: ArchConfig, kind: str, cache: Dict, pos,
                  cross_kv=None):
    window = arch.window if kind in ("swa_mlp", "moe", "hybrid") else 0
    new_cache = {}
    h = L.rmsnorm(p["norm1"], x)
    if kind in ("attn_mlp", "swa_mlp", "moe", "hybrid"):
        a, ck, cv = L.attention_decode(
            p["attn"], h, cache["k"], cache["v"], pos,
            n_heads=arch.n_heads, n_kv_heads=arch.n_kv_heads,
            head_dim=arch.head_dim_, rope_theta=arch.rope_theta,
            window=window)
        new_cache["k"], new_cache["v"] = ck, cv
        if kind == "hybrid":
            s, state = R.ssm_heads_step(
                p["ssm"], h, cache["ssm_state"],
                n_heads=arch.ssm_heads or arch.n_heads, dk=arch.ssm_state)
            new_cache["ssm_state"] = state
            a = 0.5 * (a + s)
        x = x + a
    elif kind == "mamba_mlp":
        a, state = R.ssm_heads_step(
            p["ssm"], h, cache["ssm_state"],
            n_heads=arch.ssm_heads or arch.n_heads, dk=arch.ssm_state)
        new_cache["ssm_state"] = state
        x = x + a
    elif kind == "mlstm":
        a, (state, norm) = R.mlstm_step(
            p["mlstm"], h, cache["mlstm_state"], cache["mlstm_norm"],
            n_heads=arch.n_heads)
        return x + a, {"mlstm_state": state, "mlstm_norm": norm}
    elif kind == "slstm":
        st = tuple(cache[f"slstm_{s}"] for s in ("c", "n", "h", "m"))
        a, st = R.slstm_step(p["slstm"], h, st, n_heads=arch.n_heads)
        return x + a, {f"slstm_{s}": v for s, v in zip("cnhm", st)}

    if arch.is_encdec and cross_kv is not None:
        hx = L.rmsnorm(p["norm_x"], x)
        B = hx.shape[0]
        q = (hx @ p["xattn"]["wq"]).reshape(
            B, 1, arch.n_heads, arch.head_dim_).transpose(0, 2, 1, 3)
        ck, cv = cross_kv
        group = arch.n_heads // arch.n_kv_heads
        scores = jnp.einsum(
            "bhqd,bhkd->bhqk", q.astype(jnp.float32),
            jnp.repeat(ck.astype(jnp.float32), group, axis=1)) \
            / (arch.head_dim_ ** 0.5)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", probs,
                       jnp.repeat(cv.astype(jnp.float32), group, axis=1))
        o = o.astype(x.dtype).transpose(0, 2, 1, 3).reshape(
            B, 1, arch.n_heads * arch.head_dim_)
        x = x + o @ p["xattn"]["wo"]

    h2 = L.rmsnorm(p["norm2"], x)
    if kind == "moe":
        f, _ = L.moe(p["moe"], h2, n_experts=arch.n_experts,
                     top_k=arch.top_k,
                     capacity_factor=arch.capacity_factor, act=arch.act)
    else:
        f = L.mlp(p["mlp"], h2, act=arch.act)
    return x + f, new_cache


def decode_step(params, arch: ArchConfig, batch: Dict,
                use_pallas: bool = False, unroll_layers: int = 0):
    """One decode step: batch = {tokens (B,1), cache, pos [, frames]}.
    Returns (logits (B, 1, V), new_cache). ``unroll_layers`` mirrors
    _scan_layers (roofline cost extraction)."""
    tokens, cache, pos = batch["tokens"], batch["cache"], batch["pos"]
    x = params["embed"][tokens].astype(arch.jnp_dtype)
    if arch.pos_embed == "sinusoidal":
        x = x + _sinusoid(jnp.asarray(pos)[None],
                          arch.d_model)[None].astype(x.dtype)
    slots = [f"slot{i}_{k}" for i, k in enumerate(arch.block_pattern)]
    layer_cache = {k: v for k, v in cache.items() if k != "cross"}

    if arch.is_encdec:
        # per-layer cross K/V rides the scan (each decoder layer projects
        # the encoder output with its own weights).
        def body(x, group):
            group_params, group_cache, cross = group
            new = {}
            for slot, kind in zip(slots, arch.block_pattern):
                x, nc = _block_decode(group_params[slot], x, arch, kind,
                                      group_cache[slot], pos,
                                      (cross["k"], cross["v"]))
                new[slot] = nc
            return x, new

        xs = (params["layers"], layer_cache, cache["cross"])
    else:
        def body(x, group):
            group_params, group_cache = group
            new = {}
            for slot, kind in zip(slots, arch.block_pattern):
                x, nc = _block_decode(group_params[slot], x, arch, kind,
                                      group_cache[slot], pos, None)
                new[slot] = nc
            return x, new

        xs = (params["layers"], layer_cache)

    if unroll_layers:
        news = []
        for g in range(unroll_layers):
            xs_g = jax.tree.map(lambda a: a[g], xs)
            x, new = body(x, xs_g)
            news.append(new)
        new_cache = jax.tree.map(lambda *vs: jnp.stack(vs), *news)
    else:
        x, new_cache = jax.lax.scan(body, x, xs)
    x = L.rmsnorm(params["final_norm"], x)
    unembed = params.get("unembed")
    if unembed is None:
        unembed = params["embed"].T
    logits = x @ unembed
    if arch.is_encdec:
        new_cache["cross"] = cache["cross"]
    return logits, new_cache


def prefill(params, arch: ArchConfig, tokens,
            extras: Optional[Dict] = None, use_pallas: bool = False):
    """Prefill: forward over the prompt, returning last-position logits and
    a seeded cache is intentionally NOT materialized here — prefill lowers
    the forward pass (the dry-run measures it); serving then re-runs
    decode_step against cache_specs-shaped buffers."""
    logits, aux, _ = forward(params, arch, tokens, extras,
                             use_pallas=use_pallas)
    return logits[:, -1:]
