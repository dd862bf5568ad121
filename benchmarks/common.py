"""Shared benchmark plumbing: CSV emission + dataset cache."""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

_rows = []


def emit(name: str, us_per_call: float, derived: str):
    row = f"{name},{us_per_call:.2f},{derived}"
    _rows.append(row)
    print(row, flush=True)


def header():
    print("name,us_per_call,derived", flush=True)


def timeit(fn, *args, repeats: int = 3):
    import jax
    jax.block_until_ready(fn(*args))    # warmup / compile
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e6, out
