"""Training driver: config -> mesh -> jit(train_step) -> supervised loop.

    PYTHONPATH=src python -m repro.launch.train --arch starcoder2-3b \
        --steps 200 --smoke --ckpt-dir /tmp/ckpt

--smoke runs the reduced config on the host devices (the CPU-runnable path:
examples/train_lm.py drives ~100M-class models through exactly this code).
On hardware the same driver runs the full config against the production mesh.
The loop is wrapped in the fault-tolerance supervisor: checkpoint/restart,
straggler flagging, async checkpointing; the data pipeline is cursor-seekable
so restarts resume mid-stream deterministically.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.data.synthetic import SyntheticTokens
from repro.launch.mesh import make_ctx, make_production_mesh
from repro.models.params import init_params
from repro.models.steps import make_train_step
from repro.optim import make_optimizer
from repro.optim.schedule import cosine_schedule
from repro.parallel.ctx import ParallelCtx
from repro.runtime.ft import TrainSupervisor
from repro.parallel.compat import make_mesh


def host_mesh_ctx(cfg):
    """Mesh over whatever devices exist (tests/CPU): (data, model=1)."""
    n = len(jax.devices())
    mesh = make_mesh((n, 1), ("data", "model"))
    return ParallelCtx(mesh=mesh, dp_axes=("data",), tp_axis="model",
                       shard_heads=cfg.heads_shardable(1))


def train(cfg, *, steps: int, batch: int, seq: int, ckpt_dir: str | None,
          lr: float = 3e-4, save_every: int = 50, ctx=None, seed: int = 0,
          log_every: int = 10, on_metrics=None):
    ctx = ctx or host_mesh_ctx(cfg)
    data = SyntheticTokens(vocab=cfg.vocab, seq_len=seq, global_batch=batch,
                           seed=seed)
    opt = make_optimizer(cfg.optimizer)
    params = init_params(cfg, jax.random.key(seed))
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(
        cfg, ctx, opt, cosine_schedule(lr, max(steps // 20, 1), steps)),
        donate_argnums=(0, 1))

    history = []

    def one_step(step, state):
        params, opt_state = state
        tokens, labels = data.batch(step)
        import jax.numpy as jnp
        b = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}
        if cfg.family == "encdec":
            rng = np.random.default_rng(step)
            b["enc"] = jnp.asarray(
                rng.standard_normal((batch, cfg.enc_ctx, cfg.d_model)),
                jnp.bfloat16)
        if cfg.embed_inputs:
            rng = np.random.default_rng(step)
            b["embeds"] = jnp.asarray(
                rng.standard_normal((batch, seq, cfg.d_model)), jnp.bfloat16)
        params, opt_state, metrics = step_fn(params, opt_state, b)
        return (params, opt_state), metrics

    def metrics_cb(step, metrics, slow):
        loss = float(metrics["loss"])
        history.append(loss)
        if on_metrics:
            on_metrics(step, metrics, slow)
        elif step % log_every == 0:
            print(f"step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f}"
                  f"{' [straggler]' if slow else ''}", flush=True)

    if ckpt_dir:
        sup = TrainSupervisor(ckpt_dir, save_every=save_every)
        state = sup.run((params, opt_state), steps, one_step,
                        on_metrics=metrics_cb)
    else:
        state = (params, opt_state)
        for s in range(steps):
            state, m = one_step(s, state)
            metrics_cb(s, m, False)
    return state, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    ctx = None
    if args.production_mesh:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        ctx = make_ctx(cfg, mesh, multi_pod=args.multi_pod)
    t0 = time.time()
    _, history = train(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                       ckpt_dir=args.ckpt_dir, lr=args.lr, ctx=ctx)
    print(f"done: {args.steps} steps in {time.time()-t0:.1f}s; "
          f"loss {history[0]:.3f} -> {history[-1]:.3f}")


if __name__ == "__main__":
    main()
