"""End-to-end driver: train a ~100M-param llama-family model for a few
hundred steps on the synthetic pipeline, with checkpointing + resume.

    PYTHONPATH=src python examples/train_100m.py [--steps 300]

(Thin wrapper over ``repro.launch.train`` — the same driver a pod
deployment uses; on one CPU this takes a while at full size, so CI-style
runs can pass ``--width 256 --depth 4 --steps 60`` for a ~10M variant.)
"""

import sys

from repro.launch import train

if __name__ == "__main__":
    argv = [
        "--arch", "llama3.2-1b",
        "--width", "640", "--depth", "8", "--vocab", "8192",
        "--batch", "8", "--seq", "256",
        "--steps", "300", "--lr", "1e-3",
        "--ckpt-dir", "results/100m_ckpt", "--ckpt-every", "100",
        "--metrics-out", "results/100m_metrics.json",
    ] + sys.argv[1:]
    sys.argv = [sys.argv[0]] + argv
    train.main()
