"""Batched serving example: prefill a batch of prompts, greedy-decode
continuations with ring-buffer KV caches (gemma3 family: 5:1 local:global
sliding-window attention, so the local caches stay window-sized).

    PYTHONPATH=src python examples/serve_lm.py
"""

import os
import subprocess
import sys

from repro.compile_cache import CACHE_ENV, cache_dir

subprocess.run(
    [sys.executable, "-m", "repro.launch.serve",
     "--arch", "gemma3-12b", "--reduced",
     "--batch", "4", "--prompt-len", "48", "--gen", "24"],
    check=True,
    env=dict(os.environ, **{CACHE_ENV: cache_dir()}),
)
