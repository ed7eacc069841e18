"""LM training driver example: train a ~100M-param llama-family model with the
full substrate (synthetic pipeline, AdamW + clip + warmup-cosine, checkpointing).

On this CPU container the default runs a reduced model for a quick demo; pass
``--preset 100m --steps 300`` for the full exercise (slow on CPU, the intended
target is the TPU mesh via launch/train.py).

    PYTHONPATH=src python examples/train_lm.py [--steps 30]
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.launch import train as train_mod  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--preset", default=None, choices=[None, "100m"])
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_example")
    args = ap.parse_args()

    sys.argv = ["train", "lm", "--arch", args.arch, "--reduced",
                "--steps", str(args.steps), "--batch", "4", "--seq", "256",
                "--ckpt-dir", args.ckpt_dir, "--ckpt-every", "10",
                "--log-every", "5"]
    if args.preset:
        sys.argv += ["--preset", args.preset]
    train_mod.main()


if __name__ == "__main__":
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    main()
