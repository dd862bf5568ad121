"""Replication-divergence taint analysis over shard_map jaxprs.

The sharded solvers run with ``check_vma=False`` (jax's own check
rejects the kernel-SVM and logistic-regression programs, whose scan
carries start replicated and become shard-varying), which means NOTHING
in jax verifies the replication contract the drivers rely on: every
output ``solve_sharded`` declares replicated (``out_specs=P()``)
— the objective, the row-partitioned families' full x, the replicated
state leaves the elastic runtime checkpoints — must compute the SAME
value on every shard. A shard-divergent "replicated" output is the
worst kind of bug: single-device tests pass, multi-device runs silently
diverge per shard and the fault-tolerant re-shard path restores garbage.

This pass recovers the guarantee statically. Each value in the
shard_map body carries a taint: the set of mesh axes its value may vary
over. The rules:

  * inputs taint with the axes their ``in_specs`` shard them over;
  * ``axis_index(a)`` is the canonical divergence source — taint {a};
  * ``psum`` over axes A *removes* A from the operand taint (summing
    across an axis makes the result invariant along it) — this is the
    ONLY way a partition-tainted value becomes replicated;
  * everything else unions its operand taints;
  * scan/while carries iterate to a fixpoint; a while whose predicate
    is tainted poisons every carry (shards may run different trip
    counts); a cond joins branch outputs and a tainted predicate
    poisons the join (shards may take different branches).

An output declared replicated whose taint still contains a mesh axis is
an error naming the axis and the output (``state.gram`` etc.). The
analysis is purely symbolic — it runs on the 1-device trace, no devices
or compilation involved.
"""
from __future__ import annotations

from typing import Dict, FrozenSet, List, Optional, Tuple

import jax

from repro.analysis.common import (Diagnostic, bench_shape, family_variants,
                                   variant_config)
from repro.core.types import ProblemFamily

Taint = FrozenSet[str]
EMPTY: Taint = frozenset()

# Primitives whose params hold the sub-jaxpr(s) we recurse into with a
# plain invar->outvar mapping (no carry fixpoint needed).
_CALL_PARAMS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def _as_open(j):
    return getattr(j, "jaxpr", j)


def _read(env: Dict, v) -> Taint:
    from jax._src.core import Literal
    if isinstance(v, Literal):
        return EMPTY
    return env.get(v, EMPTY)


def taint_jaxpr(jaxpr, in_taints: List[Taint]) -> List[Taint]:
    """Propagate taints through one (open) jaxpr; returns the outvar
    taints. Conservative: any primitive it does not model forwards the
    union of its operand taints to every output."""
    env: Dict = {}
    open_j = _as_open(jaxpr)
    if len(open_j.invars) != len(in_taints):
        raise ValueError(
            f"taint_jaxpr: {len(open_j.invars)} invars but "
            f"{len(in_taints)} input taints")
    for var, t in zip(open_j.invars, in_taints):
        env[var] = t
    for var in open_j.constvars:
        env[var] = EMPTY

    for eqn in open_j.eqns:
        name = eqn.primitive.name
        ins = [_read(env, v) for v in eqn.invars]
        union: Taint = frozenset().union(*ins) if ins else EMPTY

        if name == "psum":
            axes = frozenset(eqn.params.get("axes", ()))
            outs = [t - axes for t in ins]
        elif name == "axis_index":
            outs = [frozenset({eqn.params["axis_name"]})]
        elif name in ("all_gather", "pgather"):
            # gathers materialize every shard on every shard: the
            # result no longer varies over the gathered axis.
            axes = eqn.params.get("axis_name", ())
            axes = frozenset((axes,) if isinstance(axes, str) else axes)
            outs = [union - axes for _ in eqn.outvars]
        elif name == "scan":
            nc = eqn.params["num_consts"]
            ncarry = eqn.params["num_carry"]
            body = eqn.params["jaxpr"]
            consts, carry = ins[:nc], ins[nc:nc + ncarry]
            xs = ins[nc + ncarry:]
            carry = _fixpoint(
                body, lambda c: consts + c + xs,
                lambda o: o[:ncarry], carry)
            body_out = taint_jaxpr(body, consts + carry + xs)
            outs = list(carry) + body_out[ncarry:]
        elif name == "while":
            cn = eqn.params["cond_nconsts"]
            bn = eqn.params["body_nconsts"]
            cond_j, body_j = eqn.params["cond_jaxpr"], eqn.params["body_jaxpr"]
            cond_c, body_c = ins[:cn], ins[cn:cn + bn]
            carry = ins[cn + bn:]
            carry = _fixpoint(
                body_j, lambda c: body_c + c, lambda o: o, carry)
            pred = taint_jaxpr(cond_j, cond_c + carry)[0]
            if pred:
                # shards may disagree on when to stop: every carry
                # inherits the predicate's divergence.
                carry = [t | pred for t in carry]
            outs = list(carry)
        elif name == "cond":
            pred, operands = ins[0], ins[1:]
            branch_outs = [taint_jaxpr(b, list(operands))
                           for b in eqn.params["branches"]]
            outs = [frozenset().union(*(bo[i] for bo in branch_outs)) | pred
                    for i in range(len(eqn.outvars))]
        elif any(p in eqn.params for p in _CALL_PARAMS):
            sub = next(eqn.params[p] for p in _CALL_PARAMS
                       if p in eqn.params)
            outs = taint_jaxpr(sub, ins)
        else:
            outs = [union for _ in eqn.outvars]

        for var, t in zip(eqn.outvars, outs):
            from jax._src.core import DropVar
            if not isinstance(var, DropVar):
                env[var] = t
    return [_read(env, v) for v in open_j.outvars]


def _fixpoint(body, make_ins, take_carry, carry: List[Taint],
              max_iters: int = 32) -> List[Taint]:
    """Iterate carry taints through a loop body until stable. Taints
    only grow (sets under union), so this terminates in at most
    |axes| x |carry| rounds; max_iters is a safety valve."""
    for _ in range(max_iters):
        new = [a | b for a, b in
               zip(carry, take_carry(taint_jaxpr(body, make_ins(carry))))]
        if new == carry:
            return carry
        carry = new
    return carry


def _find_shard_map(jaxpr):
    open_j = _as_open(jaxpr)
    for eqn in open_j.eqns:
        if eqn.primitive.name == "shard_map":
            return eqn
        for key in _CALL_PARAMS:
            if key in eqn.params:
                found = _find_shard_map(eqn.params[key])
                if found is not None:
                    return found
    return None


def _spec_taint(spec) -> Taint:
    """The mesh axes a PartitionSpec shards over (an entry is None, one
    axis name, or a tuple of them)."""
    out: FrozenSet[str] = frozenset()
    for entry in spec:
        if entry is not None:
            out |= frozenset((entry,) if isinstance(entry, str) else entry)
    return out


def shard_map_out_taints(jaxpr) -> Tuple[List[Taint], List[Taint]]:
    """Locate the shard_map eqn inside a traced jaxpr and run the taint
    analysis over its body. Returns (out_taints, declared_out_taints):
    the inferred per-output taints and what ``out_specs`` declares
    (empty set = declared fully replicated)."""
    eqn = _find_shard_map(jaxpr)
    if eqn is None:
        raise ValueError("no shard_map equation found in jaxpr")
    in_taints = [_spec_taint(p) for p in eqn.params["in_specs"]]
    out_taints = taint_jaxpr(eqn.params["jaxpr"], in_taints)
    declared = [_spec_taint(p) for p in eqn.params["out_specs"]]
    return out_taints, declared


def check_replication(fam: ProblemFamily,
                      variants: Optional[Tuple[str, ...]] = None,
                      iterations: int = 16
                      ) -> Tuple[List[Diagnostic], List[str]]:
    """Verify, for every registered variant of ``fam``, that each
    output the sharded solve declares replicated is provably
    shard-invariant under the taint rules."""
    from repro.core import api
    diags: List[Diagnostic] = []
    checked: List[str] = []
    axis = fam.default_axes if isinstance(fam.default_axes, str) \
        else fam.default_axes[0]
    mesh = jax.make_mesh((1,), (axis,))
    m, n = bench_shape(fam)
    for variant in variants or family_variants(fam):
        where = f"{fam.name}:{variant}"
        checked.append(where)
        cfg = variant_config(fam, variant, iterations=iterations)
        traced = api.trace_sharded(fam, cfg, mesh, m=m, n=n)
        out_taints, declared = shard_map_out_taints(traced.jaxpr)
        names = [name for name, _ in traced.out_layout]
        if len(out_taints) != len(names):
            raise ValueError(
                f"{where}: traced {len(out_taints)} outputs but layout "
                f"declares {len(names)} — trace_sharded out of sync")
        for name, taint, decl in zip(names, out_taints, declared):
            leaked = taint - decl
            if leaked:
                kind = "replicated" if not decl else \
                    f"sharded only over {sorted(decl)}"
                diags.append(Diagnostic(
                    "replication", "error", where,
                    f"output {name!r} is declared {kind} but its value "
                    f"may vary over mesh axis(es) {sorted(leaked)}: it "
                    f"derives from shard-local data never psum'd over "
                    f"that axis, so shards will silently disagree"))
    return diags, checked
