"""Fault-tolerant training driver.

Responsibilities:
  * build the jitted train step (grad accumulation with ONE deferred
    reduction per step — the trainer-side analogue of the paper's SA
    batching; remat & sequence-parallel options)
  * periodic async checkpoints (params, optimizer state, data-pipeline
    state, RNG)
  * failure handling: on a (simulated or real) host failure, rebuild the
    mesh from the surviving devices, restore the latest checkpoint onto
    the NEW topology (cross-topology restore), rewind the data pipeline,
    recompile, continue — no human in the loop
  * straggler policy: rebalance shares or evict via the same elastic path

The driver is topology-agnostic: meshes are built from whatever device
list is alive, and checkpoints re-shard because PartitionSpecs are
logical (see repro.checkpoint).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs.base import ArchConfig
from repro.data.tokens import TokenPipeline
from repro.models import lm
from repro.optim.adamw import AdamW
from repro.parallel.sharding import (batch_partition_specs, dp_axes,
                                     param_partition_specs)
from repro.runtime.failures import FailureInjector
from repro.runtime.stragglers import StragglerMonitor


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_every: int = 20
    ckpt_keep: int = 3
    microbatches: int = 1
    remat: str = "none"
    shard_acts: bool = False
    model_axis: int = 1            # TP degree
    seed: int = 0
    log_every: int = 10


def build_mesh(devices: List, model_axis: int) -> Mesh:
    n = len(devices)
    if n % model_axis != 0:
        raise ValueError(
            f"{n} devices do not divide into model_axis={model_axis}")
    devs = np.array(devices).reshape(n // model_axis, model_axis)
    return Mesh(devs, ("data", "model"))


def make_train_step(arch: ArchConfig, optimizer: AdamW, mesh: Mesh,
                    cfg: TrainerConfig):
    """jit'd (params, opt_state, batch) -> (params, opt_state, loss).

    With cfg.microbatches > 1 the batch is split and gradients accumulate
    locally across microbatches inside ONE jitted step — XLA emits a
    single gradient reduction per step instead of one per microbatch
    (deferred-allreduce; verified structurally by
    benchmarks/collective_count.py)."""
    pspecs = param_partition_specs(lm.param_specs(arch), mesh)
    sspecs = optimizer.state_specs(pspecs)

    def loss_fn(params, batch):
        return lm.train_loss(params, arch, batch, remat=cfg.remat,
                             shard_acts=cfg.shard_acts)

    def step_fn(params, opt_state, batch):
        k = cfg.microbatches
        if k == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        else:
            micro = jax.tree.map(
                lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]),
                batch)

            def acc(carry, mb):
                tot_loss, tot_grads = carry
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                return (tot_loss + l,
                        jax.tree.map(jnp.add, tot_grads, g)), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(
                acc, (jnp.float32(0), zeros), micro)
            loss = loss / k
            grads = jax.tree.map(lambda g: g / k, grads)
        params, opt_state = optimizer.update(grads, opt_state, params)
        return params, opt_state, loss

    ns = lambda spec: jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec,
        is_leaf=lambda x: isinstance(x, P))
    return jax.jit(step_fn,
                   in_shardings=(ns(pspecs), ns(sspecs), None),
                   out_shardings=(ns(pspecs), ns(sspecs), None),
                   donate_argnums=(0, 1))


class Trainer:
    def __init__(self, arch: ArchConfig, optimizer: AdamW,
                 pipeline: TokenPipeline, cfg: TrainerConfig,
                 devices: Optional[List] = None,
                 failure_injector: Optional[FailureInjector] = None,
                 straggler_monitor: Optional[StragglerMonitor] = None,
                 host_of_device: Optional[Callable[[int], int]] = None):
        self.arch = arch
        self.optimizer = optimizer
        self.pipeline = pipeline
        self.cfg = cfg
        self.devices = list(devices if devices is not None
                            else jax.devices())
        self.injector = failure_injector
        self.stragglers = straggler_monitor
        # mapping device index -> host id (for failure simulation).
        self.host_of_device = host_of_device or (lambda i: i)
        self.ckpt = CheckpointManager(cfg.ckpt_dir, keep=cfg.ckpt_keep)
        self.losses: List[float] = []
        self.events: List[str] = []
        self._setup(fresh=True)

    # -- topology / (re)compilation ------------------------------------

    def _usable_devices(self) -> List:
        """Largest prefix of live devices compatible with model_axis and
        the global batch divisibility."""
        n = len(self.devices)
        ma = self.cfg.model_axis
        while n > 0:
            if n % ma == 0 and self.pipeline.global_batch % (n // ma) == 0 \
                    and self.pipeline.global_batch % max(
                        (n // ma) * self.cfg.microbatches, 1) == 0:
                return self.devices[:n]
            n -= 1
        raise RuntimeError("no usable device configuration")

    def _setup(self, fresh: bool):
        devs = self._usable_devices()
        self.mesh = build_mesh(devs, self.cfg.model_axis)
        self.step_fn = make_train_step(self.arch, self.optimizer,
                                       self.mesh, self.cfg)
        self.pspecs = param_partition_specs(lm.param_specs(self.arch),
                                            self.mesh)
        self.sspecs = self.optimizer.state_specs(self.pspecs)
        if fresh:
            with jax.set_mesh(self.mesh):
                params = lm.init_params(self.arch,
                                        jax.random.key(self.cfg.seed))
                params = jax.device_put(params, self._ns(self.pspecs))
                opt_state = self.optimizer.init(params)
                opt_state = jax.device_put(opt_state, self._ns(self.sspecs))
            self.params, self.opt_state = params, opt_state
            self.step = 0

    def _ns(self, specs):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))

    # -- checkpoint / restore -------------------------------------------

    def _save(self):
        state = {"params": self.params, "opt": self.opt_state}
        specs = {"params": self.pspecs, "opt": self.sspecs}
        self.ckpt.save(self.step, state, specs,
                       extra={"pipeline": self.pipeline.checkpoint(),
                              "step": self.step})

    def _restore(self):
        like = {"params": jax.tree.map(lambda x: x, self.params),
                "opt": self.opt_state}
        state, extra = self.ckpt.restore_latest(like, self.mesh)
        self.params = state["params"]
        self.opt_state = state["opt"]
        self.step = extra["step"]
        self.pipeline.state.step = extra["pipeline"]["step"]

    # -- failure path -----------------------------------------------------

    def _handle_failure(self, dead_hosts: List[int]):
        self.events.append(f"step {self.step}: hosts {dead_hosts} failed")
        self.ckpt.wait()
        self.devices = [d for i, d in enumerate(self.devices)
                        if self.host_of_device(i) not in dead_hosts]
        if not self.devices:
            raise RuntimeError("all devices lost")
        # rebuild topology, restore latest checkpoint onto it, rewind data.
        self._setup(fresh=True)      # fresh init to get placement...
        self._restore()              # ...then overwrite from checkpoint
        self.events.append(
            f"re-meshed to {len(self.devices)} devices "
            f"({self.mesh.shape}), resumed at step {self.step}")

    # -- main loop ---------------------------------------------------------

    def run(self) -> Dict:
        while self.step < self.cfg.steps:
            if self.injector:
                dead = self.injector.check(self.step)
                if dead:
                    self._handle_failure(dead)
                    continue
            tokens, targets = self.pipeline.batch_at(self.step)
            batch = {"tokens": tokens, "targets": targets}
            bspecs = batch_partition_specs(batch, self.mesh)
            batch = jax.device_put(batch, self._ns(bspecs))
            t0 = time.perf_counter()
            self.params, self.opt_state, loss = self.step_fn(
                self.params, self.opt_state, batch)
            loss = float(loss)
            dt = time.perf_counter() - t0
            self.losses.append(loss)
            if self.stragglers:
                n_hosts = len({self.host_of_device(i)
                               for i in range(len(self.devices))})
                actions = self.stragglers.record(
                    {h: dt for h in range(n_hosts)})
                for h, act in actions.items():
                    if act == "evict":
                        self._handle_failure([h])
                        break
            self.step += 1
            if self.step % self.cfg.ckpt_every == 0 \
                    or self.step == self.cfg.steps:
                self._save()
        self.ckpt.wait()
        return {"losses": self.losses, "events": self.events,
                "final_step": self.step}
