#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

A cell names a configuration (``bench/configs/<config>.py``: the data
generator, its published shape and the reference it is judged by) and a
traffic mix (``bench/traffic/<traffic>.json``: the ``SolverConfig``
fields it sets, the chips and the partition of A). Per-layer metrics are
readers in ``bench/metrics/<name>.py``; the limits of the correctness
comparison are in ``bench/limits/<workload>.json``. Nothing here names a
cell, so a new one is new files and a new entry in ``BENCHMARK.json``.

Set-up draws the problem on the device from ``--seed``, builds ONE
program (``jax.jit`` around ``repro.api.solve`` with A and b as its
arguments), compiles it through the persistent cache in ``.jax_cache/``
at the checkout's root, and runs one warm-up solve. The window then
calls that program back to back, one caller, each solve ending in
``block_until_ready``. After the window every solve's ``x`` and
objective trajectory are compared with the plain reference.

With ``--trace 1`` the window (at most ``TRACE_SECONDS``) runs under
the profiler and the line carries the per-layer metrics instead of the
end-to-end ones. The last line of standard output is one JSON object;
the numbers compared, each beside its limit, come last in it (under
``checks``) and as the last lines of standard error. With no TPU, or
fewer chips than the cell asks for, the run prints no result and exits 2.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
TRACE_SECONDS = 2.0

# libtpu writes its logs under /tmp unless told otherwise.
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))   # the program under test


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def load_cell(workload: str) -> types.SimpleNamespace:
    """Everything one cell needs, found by the names in BENCHMARK.json."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    config = next(c for c in spec["configs"] if c["name"] == w["config"])
    with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if traffic["chips"] != w["chips"]:
        raise SystemExit(f"{workload}: traffic {w['traffic']!r} is for "
                         f"{traffic['chips']} chips, the cell asks {w['chips']}")
    with open(os.path.join(BENCH, "limits", workload + ".json")) as f:
        limits = json.load(f)
    return types.SimpleNamespace(
        name=workload, chips=w["chips"], traffic=traffic, limits=limits,
        config=load_module(os.path.join(ROOT, config["file"]),
                           "bench_config_" + config["name"].replace("-", "_")),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)])


def solver_config(traffic: dict):
    """``SolverConfig`` from the fields the traffic names that it still
    has; the others are returned as dropped.

    The solver's own seed comes from the traffic and not from
    ``--seed``: it is a constant of the compiled program, so a seed
    that changed with every run would compile every run anew."""
    from repro.api import SolverConfig
    have = {f.name for f in dataclasses.fields(SolverConfig)}
    fields = dict(traffic["solver"])
    dropped = sorted(k for k in fields if k not in have)
    kept = {k: v for k, v in fields.items() if k in have}
    return SolverConfig(**kept), dropped


def make_mesh(cell, family):
    """The mesh and the layout of A and b for a sharded cell, or Nones."""
    import jax
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    part = cell.traffic.get("partition")
    if part is None:
        return None, None
    if part != family.partition:
        raise SystemExit(f"{cell.name}: the {family.name} family partitions "
                         f"A by {family.partition}, the traffic asks {part}")
    axis = family.default_axes
    mesh = jax.make_mesh((cell.chips,), (axis,),
                         axis_types=(AxisType.Auto,))
    if part == "row":
        specs = {"A": P(axis, None), "b": P(axis)}
    else:
        specs = {"A": P(None, axis), "b": P()}
    return mesh, {k: NamedSharding(mesh, v) for k, v in specs.items()}


def build_program(problem, cfg, mesh):
    """ONE compiled program: ``repro.api.solve`` on A and b. Returns
    (compiled, implementation labels of the solver's seams)."""
    import jax
    from repro import api
    labels = {}
    kw = {} if mesh is None else {"backend": "sharded", "mesh": mesh}

    def run(A, b):
        res = api.solve(dataclasses.replace(problem, A=A, b=b), cfg, **kw)
        labels.update({k[:-len("_impl")]: v for k, v in res.aux.items()
                       if k.endswith("_impl") and isinstance(v, str)})
        return res.x, res.objective

    compiled = jax.jit(run).lower(problem.A, problem.b).compile()
    if getattr(cfg, "use_pallas", False) and cfg.s > 1 \
            and not hasattr(problem.A, "row_cols"):
        labels.setdefault("gram", "pallas")   # the dense fused Gram GEMM
    return compiled, labels


def window(compiled, args, seconds: float, annotate):
    """Solves back to back until ``seconds`` have passed; returns
    (per-solve seconds, per-solve dispatch seconds, outputs, window
    seconds)."""
    import jax
    times, dispatch, outs = [], [], []
    start = time.perf_counter()
    end = start + seconds
    with annotate("bench.window"):
        while True:
            t0 = time.perf_counter()
            with annotate("bench.dispatch"):
                out = compiled(*args)
            td = time.perf_counter()
            with annotate("bench.block_until_ready"):
                jax.block_until_ready(out)
            t1 = time.perf_counter()
            times.append(t1 - t0)
            dispatch.append(td - t0)
            outs.append(out)
            if t1 >= end:
                break
    return times, dispatch, outs, t1 - start


def readings(outs, ref):
    """The numbers compared, each the worst over the window's solves:
    the solution's relative error and the objective trajectory's
    largest gap, over the reference's largest objective."""
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    xr, orf = (jax.device_put(v, dev).astype(jnp.float32) for v in ref)
    xs = jnp.stack([jax.device_put(o[0], dev).astype(jnp.float32)
                    for o in outs])
    objs = jnp.stack([jax.device_put(o[1], dev).astype(jnp.float32)
                      for o in outs])

    @jax.jit
    def worst(xs, objs, xr, orf):
        x_err = jnp.linalg.norm(xs - xr, axis=1) / jnp.maximum(
            jnp.linalg.norm(xr), 1e-30)
        o_dev = jnp.max(jnp.abs(objs - orf), axis=1) / jnp.maximum(
            jnp.max(jnp.abs(orf)), 1e-30)
        return jnp.max(x_err), jnp.max(o_dev), jnp.sum(
            ~(jnp.isfinite(x_err) & jnp.isfinite(o_dev)))

    with jax.default_matmul_precision("highest"):
        x_err, o_dev, bad = worst(xs, objs, xr, orf)
    return {"x_rel_err": float(x_err), "obj_rel_dev": float(o_dev),
            "nonfinite_solves": int(bad)}


def reference(cell, data, cfg, dtype=None):
    import jax.numpy as jnp
    ref = load_module(os.path.join(BENCH, "reference",
                                   cell.config.REFERENCE + ".py"),
                      "bench_reference_" + cell.config.REFERENCE)
    fields = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return ref.solve(data, fields, cfg.seed,
                     dtype=dtype or jnp.dtype(cell.config.DTYPE))


def peak_memory(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


def per_layer(cell, red, hlo, cfg, m, solves, devices):
    """The cell's per-layer metrics, each from its own reader; a reader
    that finds nothing returns None and its metric is left out."""
    ctx = types.SimpleNamespace(
        trace=red, hlo=hlo, cfg=cfg, solves=solves, chips=len(devices),
        outer=solves * cfg.outer_iterations,
        m_loc=m // len(devices),
        device_kind=devices[0].device_kind)
    out = {}
    for m in cell.per_layer:
        reader = load_module(os.path.join(BENCH, "metrics",
                                          m["name"] + ".py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the profile in .bench_trace/ for reading "
                         "by hand")
    return ap.parse_args(argv)


def main(argv=None, *, require_tpu=True, sizes=None, solver=None):
    """One run of one cell. ``require_tpu=False``, ``sizes`` (keyword
    arguments of the configuration's generator) and ``solver`` (fields
    laid over the traffic's) are for the benchmark's own tests, which
    drive a run at a tiny size on the CPU."""
    args = parse_args(argv)
    cell = load_cell(args.workload)
    cell.traffic["solver"].update(solver or {})

    import contextlib
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and (platform != "tpu" or len(devices) < cell.chips):
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {platform} device(s). Nothing run.",
              file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    marks = {"devices": time.perf_counter()}

    from repro.api import resolve_family
    cfg, dropped = solver_config(cell.traffic)
    family = resolve_family(family=cell.config.FAMILY)
    mesh, shardings = make_mesh(cell, family)
    data = cell.config.make(args.seed, shardings, **(sizes or {}))
    problem = jax.block_until_ready(cell.config.problem(data))
    marks["data"] = time.perf_counter()
    compiled, labels = build_program(problem, cfg, mesh)
    marks["program"] = time.perf_counter()
    hlo = compiled.as_text()
    a_args = (problem.A, problem.b)
    rows = problem.A.shape[0]
    jax.block_until_ready(compiled(*a_args))              # warm-up solve
    # Set-up's objects move out of the collector's way: a full
    # collection that scans them all stalls a solve by tenths of seconds.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T0
    marks["warm_up"] = T0 + setup_s

    import hlo as hlo_places
    kernel_in_hlo = "tpu_custom_call" in hlo
    info = {"workload": cell.name, "solver_config": cell.traffic["solver"],
            "dropped_fields": dropped, "impl": labels,
            "tpu_custom_call": kernel_in_hlo, "compile_cache": CACHE_DIR,
            "setup_phases_s": dict(zip(marks, (
                b - a for a, b in zip([T0, *marks.values()],
                                      marks.values()))))}
    if cell.chips > 1:
        info["allreduces_per_outer"] = hlo_places.allreduces_per_outer(hlo)
    print(json.dumps(info), flush=True)

    if args.trace:
        trace_dir = os.path.join(TRACE_DIR, cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        try:
            times, dispatch, outs, window_s = window(
                compiled, a_args, min(args.seconds, TRACE_SECONDS),
                jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()
    else:
        times, dispatch, outs, window_s = window(
            compiled, a_args, args.seconds,
            lambda name: contextlib.nullcontext())
    gc.unfreeze()
    memory_peak = peak_memory(devices)

    # The program's state is freed before the reference runs.
    del compiled, problem, a_args
    t_ref = time.perf_counter()
    ref = reference(cell, data, cfg)
    got = readings(outs, ref)
    med = sorted(times)[len(times) // 2]
    print(json.dumps({"reference_s": time.perf_counter() - t_ref,
                      "solves": len(times), "window_s": window_s,
                      "solve_median_s": med,
                      # [index, seconds, of which dispatch] of slow solves
                      "solves_over_2x_median": [
                          [i, t, d] for i, (t, d) in enumerate(
                              zip(times, dispatch)) if t > 2 * med]}),
          flush=True)
    checks = {k: {"value": got[k], "limit": cell.limits[k]}
              for k in ("x_rel_err", "obj_rel_dev")}
    checks["nonfinite_solves"] = {"value": got["nonfinite_solves"],
                                  "limit": 0}
    pallas = sorted(k for k, v in labels.items() if "pallas" in v)
    if platform == "tpu":
        checks["pallas_seams_without_kernel"] = {
            "value": 0 if kernel_in_hlo else len(pallas), "limit": 0}
    # NaN compares false, so a NaN reading fails.
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(times),
              "failed": 0 if correct else len(times)}
    if args.trace:
        import reduce_trace
        red = reduce_trace.reduce_dir(trace_dir)
        result["metrics"] = per_layer(cell, red, hlo, cfg, rows,
                                      len(times), devices)
        device.update(busy_s=red.busy_s(), window_s=red.window_s)
        result["breakdown"] = {"device_ops": red.top_ops(10),
                               "idle_gaps": red.idle_gaps(10)}
        if args.keep_trace:
            with open(os.path.join(trace_dir, "program.hlo.txt"), "w") as f:
                f.write(hlo)
        else:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        import numpy as np
        values = {"setup_s": setup_s, "solve_s": window_s / len(times),
                  "solve_p95_s": float(np.percentile(times, 95))}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
