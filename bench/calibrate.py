#!/usr/bin/env python3
"""The readings a cell's correctness limits are set from.

    python3 bench/calibrate.py --workload NAME --seeds 1,2,... \
        [--control-seeds 1,2,3]

In one process: the cell's program is built once, then for each seed
the problem is drawn, solved by the program and by the float32
reference, and compared as a benchmark run compares them. For each
control seed the reference computed in bfloat16, the precision below
the configuration's float32, is put in the program's place and compared
the same way. One JSON line per reading. The largest program reading is
a limit's lower end and the smallest control reading its upper end.
Needs the chips the cell asks for; the benchmark's own runs never run
this.
"""
import argparse
import json
import sys
import time

import run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]

    import jax
    import jax.numpy as jnp
    from repro.api import resolve_family
    jax.config.update("jax_compilation_cache_dir", run.CACHE_DIR)
    cell = run.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"calibrate: {cell.name} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2
    cfg, _ = run.solver_config(cell.traffic)
    family = resolve_family(family=cell.config.FAMILY)
    mesh, shardings = run.make_mesh(cell, family)
    compiled = None
    worst = {"program": {}, "control": {}}
    for seed in sorted(set(seeds) | set(control)):
        t0 = time.perf_counter()
        data = cell.config.make(seed, shardings)
        problem = cell.config.problem(data)
        if compiled is None:
            compiled, _ = run.build_program(problem, cfg, mesh)
        out = jax.block_until_ready(compiled(problem.A, problem.b))
        del problem
        ref = run.reference(cell, data, cfg)
        sides = []
        if seed in seeds:
            sides.append(("program", [out]))
        if seed in control:
            sides.append(("control", [run.reference(cell, data, cfg,
                                                    jnp.bfloat16)]))
        for side, outs in sides:
            got = run.readings(outs, ref)
            print(json.dumps({"side": side, "seed": seed, **got,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
            for k, v in got.items():
                agg = max if side == "program" else min
                worst[side][k] = agg(worst[side].get(k, v), v)
    print(json.dumps({"workload": cell.name, "program_max": worst["program"],
                      "control_min": worst["control"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
