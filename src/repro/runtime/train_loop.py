"""Training step factory + driver loop.

Two cross-pod gradient-exchange modes:

- ``baseline``: one jit'd SPMD program; the data-parallel gradient
  reduction (including cross-pod) is the all-reduce XLA inserts.
- ``pla`` (paper scenario 1): ``shard_map`` manual over the ``pod``
  axis ("data"/"model" stay auto): each pod computes its local gradient,
  PLA-compresses it with error feedback, and only the fixed-budget records
  cross the pod boundary (repro.compression.grad).

The driver wires in: deterministic resumable data, async checkpoints,
telemetry compression (scenario 1 again), straggler/failure hooks, and
SIGTERM-safe shutdown.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import sharding as compat_sharding
from repro.compression.grad import (GradCompressionConfig,
                                    init_error_feedback, pod_compressed_mean)
from repro.compression.telemetry import TelemetryCompressor
from repro.models.zoo import ModelAPI
from repro.optimizer import AdamWConfig, adamw_init, adamw_update, \
    warmup_cosine
from repro.runtime.checkpoint import CheckpointManager


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    steps: int = 100
    peak_lr: float = 3e-4
    warmup_steps: int = 10
    grad_accum: int = 1
    log_every: int = 10
    ckpt_every: int = 0                 # 0 = off
    grad_mode: str = "baseline"         # baseline | pla
    adamw: AdamWConfig = AdamWConfig()
    pla: GradCompressionConfig = GradCompressionConfig()
    # Cast f32 master weights to the compute dtype ONCE per step, outside
    # the microbatch loop: XLA then hoists the ZeRO all-gather out of the
    # accumulation scan (otherwise params re-gather — in f32! — on every
    # microbatch; measured 8x param bytes on the data axis, §Perf P10).
    # Default OFF: on multi-pod meshes the cast graph trips an XLA SPMD
    # partitioner CHECK (same family as the chunked-CE bug; pending
    # Shardy).  Single-pod perf runs enable it explicitly.
    cast_params_once: bool = False


def _accum_grads(loss_fn, params, batch, accum: int):
    """Microbatched value_and_grad with lax.scan accumulation."""
    if accum <= 1:
        return jax.value_and_grad(loss_fn)(params, batch)

    def split(x):
        return x.reshape(accum, x.shape[0] // accum, *x.shape[1:])

    mb = jax.tree.map(split, batch)

    def body(carry, mbatch):
        tot_l, tot_g = carry
        l, g = jax.value_and_grad(loss_fn)(params, mbatch)
        return (tot_l + l, jax.tree.map(jnp.add, tot_g, g)), None

    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (tot_l, tot_g), _ = jax.lax.scan(body, (jnp.zeros(()), zeros), mb)
    scale = 1.0 / accum
    return tot_l * scale, jax.tree.map(lambda g: g * scale, tot_g)


def make_train_step(api: ModelAPI, tcfg: TrainConfig,
                    mesh: Optional[jax.sharding.Mesh] = None
                    ) -> Callable:
    """Returns jit-able ``step(params, opt, ef, batch, step_idx) ->
    (params, opt, ef, metrics)``."""

    def loss_fn(p, b):
        if tcfg.cast_params_once:
            cdt = api.cfg.adtype
            p = jax.tree.map(
                lambda x: x.astype(cdt)
                if x.dtype == jnp.float32 and x.ndim >= 2 else x, p)
        return api.loss(p, b)

    def lr_at(step_idx):
        return warmup_cosine(step_idx, peak_lr=tcfg.peak_lr,
                             warmup_steps=tcfg.warmup_steps,
                             total_steps=max(tcfg.steps, 2))

    if tcfg.grad_mode == "baseline":
        def step(params, opt, ef, batch, step_idx):
            loss, grads = _accum_grads(loss_fn, params, batch,
                                       tcfg.grad_accum)
            params, opt, st = adamw_update(grads, opt, params,
                                           lr_at(step_idx), tcfg.adamw)
            metrics = {"loss": loss, "grad_norm": st["grad_norm"],
                       "wire_bytes": jnp.zeros(())}
            return params, opt, ef, metrics
        return step

    assert tcfg.grad_mode == "pla"
    assert mesh is not None and "pod" in mesh.axis_names, \
        "pla grad mode needs a mesh with a 'pod' axis"

    # Manual over 'pod' only; the other axes stay automatically sharded.
    def pod_local(params, opt, ef, batch, step_idx):
        loss, grads = _accum_grads(loss_fn, params, batch, tcfg.grad_accum)
        mean_g, new_ef, stats = pod_compressed_mean(grads, ef, tcfg.pla,
                                                    axis_name="pod")
        params, opt, st = adamw_update(mean_g, opt, params,
                                       lr_at(step_idx), tcfg.adamw)
        metrics = {"loss": jax.lax.pmean(loss, "pod"),
                   "grad_norm": st["grad_norm"],
                   "wire_bytes": stats["wire_bytes"]}
        return params, opt, ef_like(new_ef, ef), metrics

    def ef_like(new_ef, ef):
        return jax.tree.map(lambda n, o: n.astype(o.dtype), new_ef, ef)

    replicated = lambda tree: jax.tree.map(lambda _: P(), tree)

    def step(params, opt, ef, batch, step_idx):
        batch_specs = jax.tree.map(
            lambda x: P(*(("pod",) + (None,) * (x.ndim - 1))), batch)
        fn = compat_sharding.shard_map(
            pod_local, mesh=mesh,
            in_specs=(replicated(params), replicated(opt), replicated(ef),
                      batch_specs, P()),
            out_specs=(replicated(params), replicated(opt), replicated(ef),
                       {"loss": P(), "grad_norm": P(), "wire_bytes": P()}),
            axis_names={"pod"}, check=False)
        return fn(params, opt, ef, batch, step_idx)

    return step


def run_train(api: ModelAPI, tcfg: TrainConfig, pipeline,
              ckpt: Optional[CheckpointManager] = None,
              telemetry: Optional[TelemetryCompressor] = None,
              mesh: Optional[jax.sharding.Mesh] = None,
              resume: bool = True,
              key: Optional[jax.Array] = None) -> Dict[str, Any]:
    """CPU-runnable training driver (also the shape of the fleet driver)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    params = api.init(key)
    opt = adamw_init(params, tcfg.adamw)
    ef = init_error_feedback(params) if tcfg.grad_mode == "pla" else \
        jnp.zeros(())
    start_step = 0
    if ckpt is not None and resume:
        latest = ckpt.latest_step()
        if latest is not None:
            trees = ckpt.restore(latest, {"params": params, "opt": opt})
            params, opt = trees["params"], trees["opt"]
            start_step = latest + 1

    step_fn = jax.jit(make_train_step(api, tcfg, mesh),
                      donate_argnums=(0, 1, 2))
    history = []
    t0 = time.time()
    for step in range(start_step, tcfg.steps):
        batch = pipeline.batch_at(step)
        params, opt, ef, metrics = step_fn(params, opt, ef, batch,
                                           jnp.asarray(step))
        if telemetry is not None:
            telemetry.append(step, {k: float(v) for k, v in metrics.items()})
        if step % tcfg.log_every == 0 or step == tcfg.steps - 1:
            history.append({"step": step,
                            **{k: float(v) for k, v in metrics.items()}})
        if ckpt is not None and tcfg.ckpt_every and \
                step % tcfg.ckpt_every == tcfg.ckpt_every - 1:
            ckpt.save(step, {"params": params, "opt": opt})
    if ckpt is not None:
        ckpt.wait()
    return {"params": params, "opt": opt, "history": history,
            "seconds": time.time() - t0}
