"""Train a reduced-config LM for a few hundred steps on CPU.

Exercises the full training substrate: token pipeline → sharded train step
(AdamW, clipping, z-loss) → async checkpoints → resume.

Run:  PYTHONPATH=src python examples/train_lm.py [--arch xlstm-125m]
"""
import argparse
import shutil

from repro.launch.train import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--steps", type=int, default=200)
    args = ap.parse_args()
    ckpt = "/tmp/repro_example_ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)
    losses = train(args.arch, smoke=True, steps=args.steps, batch=8,
                   seq=128, ckpt_dir=ckpt, ckpt_every=50)
    print(f"loss: {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"({'improved' if losses[-1] < losses[0] else 'NO IMPROVEMENT'})")
    # Resume from checkpoint for a handful more steps (restart path).
    more = train(args.arch, smoke=True, steps=args.steps + 10, batch=8,
                 seq=128, ckpt_dir=ckpt, ckpt_every=0)
    print(f"resumed and ran {len(more)} more steps; final {more[-1]:.3f}")


if __name__ == "__main__":
    from repro.compile_cache import init_compile_cache
    init_compile_cache()
    main()
