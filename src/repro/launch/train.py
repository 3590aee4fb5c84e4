"""Training driver: data pipeline → jitted step → checkpoints → metrics.

Runs real steps on whatever mesh fits the local devices (CPU tests/examples
use reduced configs; the production meshes are exercised by the dry-run).
Fault-tolerance wiring:

* checkpoint every ``checkpoint_every`` steps — async, atomic, integrity-
  checked, writer elected through the paper's ALock (``repro.coord``);
* restart: ``--resume`` restores the newest verified checkpoint and the data
  pipeline continues at the restored step (stateless batch addressing);
* straggler/elastic behaviour is exercised in tests/test_elastic.py via
  re-meshing a saved checkpoint onto a different device count.
"""

from __future__ import annotations

import argparse
import functools
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import CheckpointManager, load_checkpoint
from ..configs import RunConfig, SHAPES, ShapeConfig, get_config
from ..coord import CoordinationService
from ..data import SyntheticLMDataset, make_batch_iterator
from ..models import Model
from .compile_cache import use_compilation_cache
from .mesh import make_mesh
from .steps import build_train_step, init_train_state


def train(
    arch: str,
    *,
    smoke: bool = True,
    steps: int = 50,
    shape: Optional[ShapeConfig] = None,
    mesh_shape=(1, 1),
    mesh_axes=("data", "model"),
    run: Optional[RunConfig] = None,
    resume: bool = False,
    log_every: int = 10,
    num_hosts: int = 1,
) -> Dict:
    cfg = get_config(arch, smoke=smoke)
    run = run or RunConfig(total_steps=steps, checkpoint_every=max(1, steps // 2))
    shape = shape or ShapeConfig("e2e", seq_len=128, global_batch=8, kind="train")
    mesh = make_mesh(mesh_shape, mesh_axes)
    model = Model(cfg)
    npods = dict(zip(mesh_axes, mesh_shape)).get("pod", 1)

    svc = CoordinationService(num_hosts=max(num_hosts, 1))
    ckpt = CheckpointManager(
        run.checkpoint_dir, every=run.checkpoint_every, svc=svc, host=0
    )

    with jax.set_mesh(mesh):
        step_fn, state_shapes, state_sh, batch_sh = build_train_step(
            model, run, mesh, shape
        )
        start_step = 0
        state = None
        if resume:
            try:
                state, start_step, _ = load_checkpoint(
                    run.checkpoint_dir, state_shapes, shardings=state_sh
                )
                print(f"[train] resumed from step {start_step}")
            except FileNotFoundError:
                pass
        if state is None:
            # Created already sharded: no device ever holds an unsharded
            # copy of the parameters or the optimizer state.
            init = functools.partial(init_train_state, model, run, npods=npods)
            state = jax.jit(init, out_shardings=state_sh)(
                jax.random.PRNGKey(run.seed))

        data = SyntheticLMDataset(cfg, shape, seed=run.seed)
        it = make_batch_iterator(data, start_step=start_step)
        history = []
        t0 = time.time()
        for i in range(start_step, steps):
            batch = jax.device_put(next(it), batch_sh)
            state, metrics = step_fn(state, batch)
            if (i + 1) % log_every == 0 or i == steps - 1:
                m = {k: float(v) for k, v in metrics.items()}
                m["step"] = i + 1
                history.append(m)
                print(
                    f"[train] step {i + 1}/{steps} loss={m['loss']:.4f} "
                    f"grad_norm={m.get('grad_norm', float('nan')):.3f} "
                    f"({(time.time() - t0) / (i - start_step + 1):.2f}s/step)"
                )
            ckpt.maybe_save(i + 1, state, extra={"arch": arch})
        ckpt.wait()
        it.close()
    return {"history": history, "final_state": state, "config": cfg}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--sync-mode", default="flat")
    args = ap.parse_args()
    use_compilation_cache()
    run = RunConfig(
        total_steps=args.steps,
        checkpoint_dir=args.ckpt_dir,
        checkpoint_every=max(1, args.steps // 2),
        sync_mode=args.sync_mode,
    )
    shape = ShapeConfig("cli", seq_len=args.seq_len, global_batch=args.batch,
                        kind="train")
    out = train(args.arch, smoke=args.smoke, steps=args.steps, shape=shape,
                run=run, resume=args.resume)
    losses = [h["loss"] for h in out["history"]]
    print(f"[train] done; first logged loss {losses[0]:.4f} → last {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
