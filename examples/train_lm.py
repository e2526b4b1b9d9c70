"""End-to-end LM training driver: train a ~100M-class model for a few
hundred steps on the synthetic stream, with checkpointing and the fault
supervisor — the same step builders the 512-chip dry-run lowers.

  PYTHONPATH=src python examples/train_lm.py            # ~100M model, 300 steps
  PYTHONPATH=src python examples/train_lm.py --tiny     # smoke (seconds)
"""
import argparse
import sys

from repro.launch import train
from repro.runtime.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true", help="reduced config smoke run")
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/sparseknn_train_lm")
    args = ap.parse_args()

    if args.tiny:
        argv = [
            "--arch", "qwen3-0.6b", "--smoke",
            "--steps", str(args.steps or 30),
            "--global-batch", "8", "--seq-len", "64",
            "--ckpt-dir", args.ckpt_dir, "--ckpt-every", "10",
            "--resume", "auto", "--log-every", "5",
        ]
    else:
        # qwen1.5-0.5b full config is ~460M; with seq 256 and batch 8 this
        # trains for real on CPU in tens of minutes — the 100M-class loop.
        argv = [
            "--arch", "qwen1.5-0.5b", "--smoke",
            "--steps", str(args.steps or 300),
            "--global-batch", "16", "--seq-len", "128",
            "--ckpt-dir", args.ckpt_dir, "--ckpt-every", "50",
            "--resume", "auto", "--log-every", "10",
        ]
    return train.main(argv)


if __name__ == "__main__":
    sys.exit(main())
