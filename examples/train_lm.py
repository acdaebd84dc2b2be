"""End-to-end training driver: train a ~20M-param minitron-family model for
a few hundred steps on the synthetic pipeline, with checkpoint + resume.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""

import argparse
import os
import subprocess
import sys

from repro.compile_cache import CACHE_ENV, cache_dir

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=300)
args = ap.parse_args()

subprocess.run(
    [sys.executable, "-m", "repro.launch.train",
     "--arch", "minitron-4b", "--reduced",
     "--steps", str(args.steps), "--batch", "8", "--seq", "128",
     "--ckpt-every", "100"],
    check=True,
    env=dict(os.environ, **{CACHE_ENV: cache_dir()}),
)
