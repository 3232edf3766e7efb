"""Train a small decoder LM with the full stack: synthetic packed data
pipeline -> model zoo -> Adam train step, on CPU.

    PYTHONPATH=src python examples/train_small.py --steps 60
"""
import argparse
import sys
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.cache import enable_compile_cache
from repro.models import build_model
from repro.training.data import DataConfig, PackedStream
from repro.training.optimizer import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch).reduced()
    model = build_model(cfg)
    init_state, train_step = make_train_step(model, "adam")
    state = init_state(jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M")

    data = PackedStream(DataConfig(cfg.vocab_size, args.seq, args.batch))
    step_fn = jax.jit(train_step, donate_argnums=(0,))

    losses = []
    t0 = time.time()
    for step in range(args.steps):
        batch = data.batch(step)
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {losses[-1]:.4f}  "
                  f"({(time.time()-t0)/(step+1):.2f}s/step)")
    first, last = np.mean(losses[:5]), np.mean(losses[-5:])
    print(f"\nloss {first:.3f} -> {last:.3f} "
          f"({'OK — decreasing' if last < first else 'NOT decreasing'})")
    return 0 if last < first else 1


if __name__ == "__main__":
    sys.exit(main())
