"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b-smoke \
      --steps 50 --batch 8 --seq 128

Works on CPU for smoke-size configs (the production path is the same code
under a real TPU mesh): builds the mesh from available devices, shards the
TrainState with the model's logical axes, runs the supervised train loop
with checkpoint/restart, straggler monitoring and (optional) int8 gradient
compression.
"""

from __future__ import annotations

import argparse
import logging
import time

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.configs.base import ShapeSpec
from repro.checkpoint import Checkpointer
from repro.data import Batcher, token_stream
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_host_mesh
from repro.models.api import get_model
from repro.runtime import TrainingSupervisor

log = logging.getLogger(__name__)


def make_lm_data(cfg, n_tokens: int, batch: int, seq: int, mesh):
    stream = token_stream(n_tokens + 1, cfg.vocab_size, seed=0)
    n_seqs = n_tokens // seq
    toks = stream[: n_seqs * seq].reshape(n_seqs, seq)
    labels = stream[1 : n_seqs * seq + 1].reshape(n_seqs, seq)
    data = {"tokens": toks, "labels": labels}
    return Batcher(data, batch_size=batch, mesh=mesh)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config for this arch")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", choices=["none", "int8"],
                    default="none")
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=25)
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO)
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family in ("vlm", "encdec"):
        raise SystemExit(
            "train.py drives token-LM archs; use examples/ for vlm/encdec")

    mesh = make_host_mesh()
    model = get_model(cfg)
    shape = ShapeSpec("cli", args.seq, args.batch, "train")
    settings = steps_lib.TrainSettings(
        learning_rate=args.lr, microbatches=args.microbatches,
        grad_compression=args.grad_compression)

    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        step_fn, st_sh, b_sh, _ = steps_lib.build_train_step(
            model, mesh, shape, settings)
        state = steps_lib.init_train_state(model, settings,
                                           jax.random.PRNGKey(0))
        state = jax.device_put(state, st_sh)

        batches = make_lm_data(cfg, args.batch * args.seq * (args.steps + 4),
                               args.batch, args.seq, mesh)
        sup = TrainingSupervisor(
            Checkpointer(args.checkpoint_dir),
            checkpoint_every=args.checkpoint_every)

        def wrapped(state, batch):
            state, metrics = step_fn(state, batch)
            return state, {k: float(v) for k, v in metrics.items()}

        t0 = time.time()
        state, history = sup.run(state, wrapped, batches, args.steps,
                                 restore_shardings=st_sh)
        dt = time.time() - t0

    losses = [h["loss"] for h in history]
    print(f"\n{cfg.name}: {len(history)} steps in {dt:.1f}s "
          f"({dt / max(1, len(history)):.3f}s/step)")
    print(f"loss: first={losses[0]:.4f} last={losses[-1]:.4f} "
          f"min={min(losses):.4f}")
    print(f"stragglers observed: {len(sup.straggler.straggler_steps)}")
    if losses[-1] >= losses[0]:
        raise SystemExit("loss did not decrease")


if __name__ == "__main__":
    main()
