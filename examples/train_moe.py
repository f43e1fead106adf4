"""Train a ~100M-class MoE with exoshuffle sort-dispatch for a few hundred
steps — the paper's technique inside a real training loop.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/train_moe.py --steps 300

Uses a scaled qwen2-moe family config (8 experts, top-2, sort dispatch over
the model axis of a 2x4 mesh) with the exoshuffle epoch-shuffled data
pipeline, checkpointing every 100 steps.
"""
import argparse
import dataclasses
import os
import time

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch import sharding as shd
from repro.launch.dryrun import block_specs_of
from repro.models import api as mapi
from repro.train import checkpoint as ckpt
from repro.train.optimizer import OptConfig
from repro.train.train_step import TrainConfig, init_train_state, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/exoshuffle_moe_ckpt")
    args = ap.parse_args()

    from repro.core.compat import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    # ~100M-class MoE of the qwen2-moe family, exoshuffle sort dispatch
    cfg = dataclasses.replace(
        get("qwen2-moe-a2.7b"),
        n_layers=4, d_model=256, n_heads=8, n_kv_heads=8, d_head=32,
        vocab=8192, n_experts=8, top_k=2, d_ff_expert=512, shared_d_ff=512,
        dispatch_impl="sort", moe_capacity_factor=2.0, dtype="float32",
        remat=False, attn_chunk=64, train_microbatches=1,
    )
    model0 = mapi.build(cfg, mesh=mesh, dp_axes=("data",))
    p_specs = shd.param_pspecs(cfg, model0.abstract_params(), mesh)
    model = mapi.build(cfg, mesh=mesh, dp_axes=("data",),
                       block_specs=block_specs_of(cfg, p_specs))

    tcfg = TrainConfig(opt=OptConfig(peak_lr=1e-3, warmup_steps=20,
                                     total_steps=args.steps))
    state = init_train_state(model, jax.random.PRNGKey(0), tcfg)
    state_specs = {"params": p_specs,
                   "opt": {"mu": p_specs, "nu": p_specs, "step": P()}}
    sh = jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs,
                      is_leaf=lambda x: isinstance(x, P))
    state = {k: jax.device_put(state[k], sh[k]) for k in ("params", "opt")}

    step_fn = jax.jit(make_train_step(model, tcfg, mesh=mesh), donate_argnums=0)
    data = TokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                    global_batch=args.batch,
                                    num_samples=args.batch * 64))
    t0 = time.time()
    for step in range(args.steps):
        with mesh:
            state, m = step_fn(state, data.batch_at(step))
        if step % 25 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss {float(m['loss']):.4f} "
                  f"({args.batch*args.seq*(step+1)/(time.time()-t0):,.0f} tok/s)")
        if (step + 1) % 100 == 0:
            ckpt.save(state, args.ckpt_dir, step + 1)
            print(f"checkpointed at {step + 1}")
    n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
    print(f"done: {n_params/1e6:.1f}M params, final loss {float(m['loss']):.4f}")


if __name__ == "__main__":
    from repro.core.compat import enable_compile_cache

    enable_compile_cache()
    main()
