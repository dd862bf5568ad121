"""§Perf hillclimb harness: re-lower the three chosen cells with each
candidate change toggled, and report the roofline-term deltas. Runs in a
subprocess per configuration (512 placeholder devices + clean flag
state). Results feed EXPERIMENTS.md §Perf.

    PYTHONPATH=src python -m benchmarks.perf_iterations [--cell mixtral]
"""
import argparse
import json
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "results", "perf")

CELL_CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json, sys
flags = json.loads(sys.argv[1])
import repro.models.layers as L
import repro.kernels.flash_attention.ops as fops
L.DECODE_GROUPED_GQA = flags.get("grouped_gqa", False)
L.MOE_BUF_2D = flags.get("moe_buf_2d", False)
fops.CHUNKED_BF16_PROBS = flags.get("bf16_probs", False)
if "moe_chunk" in flags:
    L.MOE_CHUNK_TOKENS = flags["moe_chunk"]
if "q_chunk" in flags:
    import repro.kernels.flash_attention.ops as _f
    _orig = _f.attention_chunked
    qc = flags["q_chunk"]
    def patched(q, k, v, **kw):
        kw["q_chunk"] = qc
        return _orig(q, k, v, **kw)
    _f.attention_chunked = patched
    # rebind in flash_attention's module namespace
from repro.launch import dryrun
opts = dryrun.DryrunOptions(remat=flags.get("remat", "full"))
r = dryrun.run_cell(flags["arch"], flags["shape"],
                    multi_pod=flags.get("multi_pod", False),
                    opts=opts, verbose=False)
keep = {k: r.get(k) for k in ("status", "memory", "roofline",
                              "per_device", "useful_ratio",
                              "useful_ratio_attn", "collective_counts",
                              "error")}
print("RESULT " + json.dumps(keep))
"""

SOLVER_CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json, sys, re, jax
flags = json.loads(sys.argv[1])
from repro.core.distributed import lower_lasso_step
from repro.core.types import SolverConfig
from repro.launch.mesh import make_production_mesh
from repro.roofline.analysis import collective_bytes_from_hlo
mesh = make_production_mesh(multi_pod=flags.get("multi_pod", True))
axes = ("pod", "data") if flags.get("multi_pod", True) else "data"
H, s, mu = 64, flags.get("s", 16), flags.get("mu", 8)
cfg = SolverConfig(block_size=mu, iterations=H, s=s,
                   track_objective=False,
                   symmetric_gram=flags.get("sym_gram", False))
lowered = lower_lasso_step(cfg, mesh, m=131072, n=8192, axes=axes)
c = lowered.compile()
txt = c.as_text()
coll = collective_bytes_from_hlo(txt)
static = len(re.findall(r"= \S+ all-reduce\(", txt))
ca = c.cost_analysis()
out = {"s": s, "static_allreduce": static, "trips": H // s,
       "runtime_msgs": static * (H // s),
       "coll_bytes_per_outer": coll["total"],
       "flops": ca.get("flops"), "bytes": ca.get("bytes accessed")}
print("RESULT " + json.dumps(out))
"""


SVM_SOLVER_CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json, sys, re, jax
flags = json.loads(sys.argv[1])
from repro.core.distributed import lower_svm_step
from repro.core.types import SolverConfig
from repro.roofline.analysis import collective_bytes_from_hlo
mesh = jax.make_mesh((512,), ("model",))
H, s, mu = 64, flags.get("s", 16), flags.get("mu", 8)
kernel = flags.get("kernel", "linear")
params = {"gamma": 0.1} if kernel == "rbf" else None
cfg = SolverConfig(block_size=mu, iterations=H, s=s,
                   track_objective=False)
lowered = lower_svm_step(cfg, mesh, m=8192, n=131072, axes="model",
                         kernel=kernel, kernel_params=params)
c = lowered.compile()
txt = c.as_text()
coll = collective_bytes_from_hlo(txt)
static = len(re.findall(r"= \S+ all-reduce\(", txt))
ca = c.cost_analysis()
out = {"s": s, "mu": mu, "kernel": kernel, "static_allreduce": static,
       "trips": H // s, "runtime_msgs": static * (H // s),
       "coll_bytes_per_outer": coll["total"],
       "flops": ca.get("flops"), "bytes": ca.get("bytes accessed")}
print("RESULT " + json.dumps(out))
"""


def run_config(code: str, flags: dict, timeout=1500):
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code, json.dumps(flags)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    return {"status": "error", "error": out.stderr[-500:]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    os.makedirs(OUT_DIR, exist_ok=True)

    experiments = {
        # Cell A: worst useful ratio / collective-heavy MoE training.
        "mixtral_train": [
            ("baseline", CELL_CODE,
             {"arch": "mixtral-8x7b", "shape": "train_4k"}),
            ("moe_buf_2d", CELL_CODE,
             {"arch": "mixtral-8x7b", "shape": "train_4k",
              "moe_buf_2d": True}),
            ("moe_chunk_64k", CELL_CODE,
             {"arch": "mixtral-8x7b", "shape": "train_4k",
              "moe_chunk": 1 << 16}),
            ("moe_chunk_256k", CELL_CODE,
             {"arch": "mixtral-8x7b", "shape": "train_4k",
              "moe_chunk": 1 << 18}),
        ],
        # Cell B: collective-bound decode at 32k (split-KV resharding).
        "llama3_decode": [
            ("baseline", CELL_CODE,
             {"arch": "llama3-8b", "shape": "decode_32k",
              "multi_pod": True}),
            ("grouped_gqa", CELL_CODE,
             {"arch": "llama3-8b", "shape": "decode_32k",
              "multi_pod": True, "grouped_gqa": True}),
        ],
        # Cell C (paper-representative): the distributed SA solver itself.
        "sa_lasso": [
            ("s1_classical", SOLVER_CODE, {"s": 1, "multi_pod": True}),
            ("s16_paper", SOLVER_CODE, {"s": 16, "multi_pod": True}),
            ("s16_sym_gram", SOLVER_CODE,
             {"s": 16, "sym_gram": True, "multi_pod": True}),
            ("s64_paper", SOLVER_CODE, {"s": 64, "multi_pod": True}),
            ("s64_sym_gram", SOLVER_CODE,
             {"s": 64, "sym_gram": True, "multi_pod": True}),
        ],
        # Cell C2: the (kernel-)SVM SA solver — the kernel rows move the
        # (m, s*mu) cross block instead of the reduced Gram; ONE
        # all-reduce per outer iteration either way.
        "sa_svm": [
            ("s1_classical", SVM_SOLVER_CODE, {"s": 1}),
            ("s16_paper", SVM_SOLVER_CODE, {"s": 16}),
            ("s64_paper", SVM_SOLVER_CODE, {"s": 64}),
            ("s16_rbf", SVM_SOLVER_CODE, {"s": 16, "kernel": "rbf"}),
            ("s64_rbf", SVM_SOLVER_CODE, {"s": 64, "kernel": "rbf"}),
        ],
        # Memory-bound prefill: attention chunk size + bf16 probs.
        "tinyllama_prefill": [
            ("baseline", CELL_CODE,
             {"arch": "tinyllama-1.1b", "shape": "prefill_32k"}),
            ("bf16_probs", CELL_CODE,
             {"arch": "tinyllama-1.1b", "shape": "prefill_32k",
              "bf16_probs": True}),
        ],
    }

    names = args.only.split(",") if args.only else list(experiments)
    for name in names:
        results = {}
        for tag, code, flags in experiments[name]:
            print(f"[perf] {name}/{tag} ...", flush=True)
            r = run_config(code, flags)
            results[tag] = r
            if "roofline" in (r or {}):
                t = r["roofline"]
                print(f"    C={t['compute_s'] * 1e3:9.1f}ms "
                      f"M={t['memory_s'] * 1e3:9.1f}ms "
                      f"N={t['collective_s'] * 1e3:9.1f}ms "
                      f"mem={r['memory']['total_bytes'] / 1e9:6.2f}GB "
                      f"u={r.get('useful_ratio', 0):.3f}", flush=True)
            else:
                print(f"    {r}", flush=True)
        with open(os.path.join(OUT_DIR, f"{name}.json"), "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
