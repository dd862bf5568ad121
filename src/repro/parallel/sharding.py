"""Sharding rules for the model zoo over the production mesh.

Mesh axes: ('data', 'model') single-pod, ('pod', 'data', 'model')
multi-pod. Strategy (DESIGN.md):

* TP  — attention heads / FFN hidden / vocab over 'model' (Megatron-style:
  column-parallel in-projections, row-parallel out-projections).
* FSDP — the remaining weight dim over 'data' (XLA all-gathers per layer).
  Replicated across pods: intra-pod FSDP + cross-pod gradient reduction is
  the hierarchical schedule (cross-pod traffic = one gradient allreduce).
* EP  — MoE expert dim over 'model' (detected by the 'moe' path segment).
* DP  — batch over ('pod', 'data').
* SP  — layer-boundary activations sharded over 'model' on the sequence
  dim (sequence parallelism), bounding saved-activation memory.
* decode — KV cache sequence dim over 'model' (split-KV / flash-decode
  style partial attention; XLA inserts the softmax reduction).

Rules are path+shape based so they apply uniformly across the zoo,
including scan-stacked params (leading group axes get None). Every spec is
SANITIZED against the actual mesh: any named axis that does not evenly
divide its dim falls back to replication for that dim (e.g. odd vocab
sizes like 49155, batch=1 decode, 25-head hymba projections).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


_COL = ("wq", "wk", "wv", "w_gate", "w_up", "w_z", "w_i", "w_f", "w_o")
_ROW = ("wo", "w_down")
_REPL = ("scale", "b_decay", "b_f", "router", "w_decay",
         "r_z", "r_i", "r_f", "r_o", "meta", "pos_embed")


def _rule_for(name: str, shape: Tuple[int, ...], in_moe: bool,
              fsdp: str, tp: str, tp_size: int = 0) -> P:
    nd = len(shape)

    def pad(spec_tail):
        return P(*([None] * (nd - len(spec_tail))), *spec_tail)

    if name == "embed":
        return P(tp, fsdp)                     # (V, D): vocab-parallel
    if name == "unembed":
        return P(fsdp, tp)                     # (D, V)
    if name in _REPL:
        return P(*([None] * nd))
    if name in ("bq", "bk", "bv"):
        return pad((tp,))
    if in_moe and nd >= 3:
        n_experts = shape[nd - 3]
        ep_ok = tp_size > 0 and n_experts % tp_size == 0
        if name in ("w_gate", "w_up"):
            # EP when the expert count divides the TP axis (granite 32e);
            # otherwise expert-TP: split each expert's FFN over 'model'
            # (mixtral 8e on a 16-wide axis).
            return pad((tp, fsdp, None)) if ep_ok else pad((None, fsdp, tp))
        if name == "w_down":
            return pad((tp, None, fsdp)) if ep_ok else pad((None, tp, fsdp))
    if name in _COL and nd >= 2:
        return pad((fsdp, tp))                 # (D_in, D_out) column-par
    if name in _ROW and nd >= 2:
        return pad((tp, fsdp))                 # row-parallel
    if name.startswith("w_") and nd >= 2:      # misc projections
        return pad((fsdp, tp))
    return P(*([None] * nd))


def sanitize_spec(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop named axes that don't exist on the mesh or don't divide the
    dim; jit requires exact divisibility for explicit in_shardings."""
    parts = []
    for dim, part in zip(shape, tuple(spec) + (None,) * (len(shape)
                                                         - len(spec))):
        if part is None:
            parts.append(None)
            continue
        names = (part,) if isinstance(part, str) else tuple(part)
        names = tuple(n for n in names if n in mesh.axis_names)
        size = int(np.prod([mesh.shape[n] for n in names])) if names else 1
        if not names or size == 0 or dim % size != 0:
            parts.append(None)
        else:
            parts.append(names if len(names) > 1 else names[0])
    return P(*parts)


def param_partition_specs(param_tree, mesh: Optional[Mesh] = None,
                          fsdp: str = "data", tp: str = "model"):
    """PartitionSpec tree matching ``param_tree`` (arrays or SDS)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(param_tree)
    specs = []
    tp_size = int(mesh.shape[tp]) if mesh is not None \
        and tp in mesh.axis_names else 0
    for path, leaf in flat:
        keys = [str(e.key) for e in path
                if isinstance(e, jax.tree_util.DictKey)]
        name = keys[-1] if keys else ""
        in_moe = "moe" in keys[:-1]
        spec = _rule_for(name, leaf.shape, in_moe, fsdp, tp, tp_size)
        if mesh is not None:
            spec = sanitize_spec(spec, leaf.shape, mesh)
        specs.append(spec)
    return jax.tree_util.tree_unflatten(treedef, specs)


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The data-parallel axes present on this mesh ('pod' first)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_partition_specs(batch_tree, mesh: Mesh, kind: str = "train"):
    """Input sharding: batch dim over the DP axes; decode caches shard the
    KV sequence dim over 'model' (split-KV)."""
    dp = dp_axes(mesh)
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)

    def spec_for(path, leaf):
        nd = len(leaf.shape)
        keys = [str(e.key) for e in path
                if isinstance(e, jax.tree_util.DictKey)]
        name = keys[-1] if keys else ""
        if nd == 0:
            return P()
        if name in ("k", "v") and nd == 5:
            # stacked KV cache (G, B, Hkv, S, D): batch over DP, cache
            # sequence over 'model' (split-KV decode).
            spec = P(None, dp_spec, None, "model", None)
        elif name.startswith(("ssm_", "mlstm_", "slstm_")):
            spec = P(None, dp_spec, *([None] * (nd - 2)))
        else:
            # tokens/targets/frames/patches: batch first.
            spec = P(dp_spec, *([None] * (nd - 1)))
        return sanitize_spec(spec, leaf.shape, mesh)

    flat, treedef = jax.tree_util.tree_flatten_with_path(batch_tree)
    return jax.tree_util.tree_unflatten(
        treedef, [spec_for(p, l) for p, l in flat])


def named_shardings(tree, specs, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def activation_spec(mesh_axis_names) -> P:
    """Layer-boundary residual sharding: batch over DP, sequence over
    'model' (sequence parallelism)."""
    dp = tuple(a for a in ("pod", "data") if a in mesh_axis_names)
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    return P(dp_spec, "model", None)
