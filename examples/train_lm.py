"""End-to-end LM training driver on the framework's substrate.

Trains a reduced-config model from the assigned pool for a few hundred
steps on the synthetic pipeline, with checkpointing and the restart
supervisor enabled — the same code path as ``python -m repro.launch.train``.

    PYTHONPATH=src python examples/train_lm.py --arch smollm-135m --steps 200
"""

import argparse

from repro.launch.train import train_loop
from repro.util import init_compile_cache


def main():
    init_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--full-size", action="store_true",
                    help="use the full (not reduced) config — needs real HW")
    ap.add_argument("--checkpoint-dir", default="ckpt_example")
    args = ap.parse_args()

    metrics = train_loop(
        arch=args.arch,
        reduced=not args.full_size,
        steps=args.steps,
        global_batch=args.global_batch,
        seq_len=args.seq_len,
        lr=1e-3,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=50,
        log_every=20,
    )
    first = sum(m["loss"] for m in metrics[:10]) / 10
    last = sum(m["loss"] for m in metrics[-10:]) / 10
    print(f"mean loss: first 10 steps {first:.4f} -> last 10 steps {last:.4f}")


if __name__ == "__main__":
    main()
