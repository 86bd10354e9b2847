"""Training launcher: ``python -m repro.launch.train --arch <id> [--smoke]``.

On this CPU container it runs reduced (smoke) configs end-to-end with the
full production plumbing: sharded params (host mesh), microbatched train
step, deterministic data pipeline, async checkpointing, supervisor-driven
restart, straggler monitor.  On a TPU pod the same script runs the full
config on ``make_production_mesh()`` (``--mesh prod``).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import Prefetcher, SyntheticLMDataset
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.params import count_params, init_params, param_pspecs
from repro.models.partitioning import make_rules, spec_tree_to_shardings
from repro.models.registry import get_config, get_smoke_config
from repro.optim.adamw import adamw_init, opt_state_pspecs
from repro.runtime.heartbeat import StepMonitor
from repro.runtime.supervisor import Supervisor
from repro.train.step import TrainHParams, make_train_step


def build_trainer(
    cfg, mesh, *, batch: int, seq: int, hp: TrainHParams, seed: int = 0
):
    rules = make_rules(
        mesh, fsdp=cfg.fsdp, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads
    )
    params = init_params(cfg, jax.random.PRNGKey(seed))
    opt = adamw_init(params)
    p_specs = param_pspecs(cfg, rules)
    o_specs = opt_state_pspecs(
        p_specs, params, dict(mesh.shape).get("data", 1)
    )
    p_sh = spec_tree_to_shardings(mesh, p_specs)
    o_sh = spec_tree_to_shardings(mesh, o_specs)
    params = jax.tree.map(jax.device_put, params, p_sh)
    opt = jax.tree.map(jax.device_put, opt, o_sh)
    step = jax.jit(
        make_train_step(cfg, rules, hp),
        in_shardings=(p_sh, o_sh, None),
        out_shardings=(p_sh, o_sh, None),
        donate_argnums=(0, 1),
    )
    return params, opt, step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-gpt2-124m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", choices=["host", "prod"], default="host")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = (
        make_production_mesh() if args.mesh == "prod" else make_host_mesh()
    )
    hp = TrainHParams(
        base_lr=args.lr,
        warmup_steps=max(args.steps // 10, 1),
        total_steps=args.steps,
        num_microbatches=args.microbatches,
    )
    print(f"arch={cfg.name} params={count_params(cfg):,} mesh={dict(mesh.shape)}")
    params, opt, step_fn = build_trainer(
        cfg, mesh, batch=args.batch, seq=args.seq, hp=hp
    )

    data = SyntheticLMDataset(cfg.vocab, args.seq, args.batch)
    ckpt = CheckpointManager(args.ckpt_dir, keep_n=2)
    monitor = StepMonitor()
    sup = Supervisor(ckpt, ckpt_every=args.ckpt_every)

    # NOTE: batches are fetched by step index (not an iterator) so restarts
    # replay the exact stream; Prefetcher covers the steady-state throughput
    # path and is exercised by examples/train_lm.py and the tests.
    state = {"params": params, "opt": opt}

    def one_step(state, step):
        t0 = time.perf_counter()
        batch = {
            k: jnp.asarray(v) for k, v in data.batch_at(step).items()
        }
        if cfg.vision_prefix:
            batch["vision_embeds"] = jnp.zeros(
                (args.batch, cfg.vision_prefix, cfg.d_model),
                jnp.dtype(cfg.dtype),
            )
        if cfg.encoder_decoder:
            batch["encoder_frames"] = jnp.zeros(
                (args.batch, cfg.encoder_seq, cfg.d_model),
                jnp.dtype(cfg.dtype),
            )
        params, opt, metrics = step_fn(state["params"], state["opt"], batch)
        loss = float(metrics["loss"])
        monitor.record(0, step, time.perf_counter() - t0)
        if step % args.log_every == 0:
            print(f"step {step:5d}  loss {loss:.4f}  "
                  f"lr {float(metrics['lr']):.2e}  "
                  f"({time.perf_counter() - t0:.2f}s)")
        return {"params": params, "opt": opt}

    t0 = time.perf_counter()
    state = sup.run(state, one_step, num_steps=args.steps)
    ckpt.wait()
    print(
        f"done: {sup.stats.steps_run} steps in {time.perf_counter()-t0:.1f}s;"
        f" failures={sup.stats.failures} restores={sup.stats.restores};"
        f" stragglers={monitor.stragglers()}"
    )


if __name__ == "__main__":
    main()
