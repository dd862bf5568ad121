"""Structural verification of the SA claim — now via ``repro.analysis``.

Two complementary views, for EVERY registered problem family (the list
comes from the registry, so a newly registered family is verified here
with zero benchmark edits):

  * **static (in-process)** — ``repro.analysis.solver_collective_budget``
    walks the traced jaxpr and reports, per family x s: the in-loop
    collective counts by type, the all-reduce payload bytes per OUTER
    iteration, and runtime messages per solve (= in-loop all-reduces x
    outer trips). This is the dry-run analogue of the paper's latency
    measurements and needs no devices at all.
  * **compiled (subprocess, 8 placeholder devices)** — the post-SPMD
    HLO of the same lowering, parsed with
    ``repro.roofline.analysis.collective_stats_from_hlo``, cross-checks
    that XLA kept exactly the collectives the jaxpr promised (the bench
    process keeps 1 device; forcing devices needs XLA_FLAGS before jax
    imports, hence the subprocess).
"""
import os
import re
import subprocess
import sys

from benchmarks.common import emit

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

H = 64
S_VALUES = (1, 4, 16)

CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.core import api
from repro.core.types import FAMILIES, SolverConfig
from repro.roofline.analysis import collective_stats_from_hlo

H = 64
SHAPES = {"row": (512, 128), "col": (256, 512)}
meshes = {}
for name in sorted(FAMILIES):
    fam = FAMILIES[name]
    axis = fam.default_axes if isinstance(fam.default_axes, str) \
        else fam.default_axes[0]
    if axis not in meshes:
        meshes[axis] = jax.make_mesh((8,), (axis,))
    m, n = SHAPES[fam.partition]
    for s in (1, 4, 16):
        cfg = SolverConfig(block_size=fam.bench_block_size, iterations=H,
                           s=s, track_objective=False)
        txt = api.lower_solve(name, cfg, meshes[axis], m=m, n=n,
                              axes=axis).compile().as_text()
        stats = collective_stats_from_hlo(txt)
        others = stats.total_count - stats.counts["all-reduce"]
        print(f"{name.upper()} s={s} "
              f"compiled_allreduce={stats.counts['all-reduce']} "
              f"compiled_other={others}")
"""


def static_rows():
    """The jaxpr-level budget rows, in-process (1 device is enough: the
    trace is symbolic) — assembled by the analyzer's shared
    ``budget_rows`` helper, not re-derived here."""
    sys.path.insert(0, SRC)
    from repro.analysis import budget_rows
    return budget_rows(s_values=S_VALUES, iterations=H)


def main():
    rows = static_rows()
    kinds = sorted({name for name, _ in rows})
    for (name, s), row in sorted(rows.items()):
        emit(f"collective_count/{name}/s{s}", 0.0,
             f"static={row.allreduces_in_loop};"
             f"other_collectives={row.other_collectives};"
             f"trips={row.trips};runtime_msgs={row.runtime_messages};"
             f"bytes_per_outer={row.bytes_per_outer:.0f}")
    for name in kinds:
        red = rows[(name, 1)].runtime_messages \
            / max(rows[(name, 16)].runtime_messages, 1)
        emit(f"collective_count/{name}/reduction_s16", 0.0,
             f"latency_reduction={red:.1f}x(expected~16x)")
    # the SA claim, structurally: ONE in-loop Allreduce per outer
    # iteration and zero other collectives, for every registered family.
    worst = max(r.allreduces_in_loop for r in rows.values())
    extra = max(r.other_collectives for r in rows.values())
    emit("collective_count/one_allreduce_per_outer", 0.0,
         f"max_static={worst};max_other={extra};families={len(kinds)};"
         f"ok={worst == 1 and extra == 0}")

    # cross-check against the compiled 8-device artifacts.
    # forced host devices: the child never claims an accelerator that
    # this process may hold.
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", CODE], env=env,
                         capture_output=True, text=True, timeout=1800)
    if out.returncode != 0:
        emit("collective_count/compiled/ERROR", 0.0,
             out.stderr[-300:].replace("\n", " ")[:200])
        return
    agree = True
    for line in out.stdout.splitlines():
        m = re.match(r"([A-Z]+) s=(\d+) compiled_allreduce=(\d+) "
                     r"compiled_other=(\d+)", line)
        if m:
            kind, s, ar, other = m.groups()
            want = sum(rows[(kind.lower(), int(s))].budget.total.values())
            agree &= int(ar) + int(other) == want
            emit(f"collective_count/{kind.lower()}/s{s}/compiled", 0.0,
                 f"allreduce={ar};other={other};jaxpr_total={want}")
    emit("collective_count/compiled_matches_jaxpr", 0.0, f"ok={agree}")


if __name__ == "__main__":
    main()
