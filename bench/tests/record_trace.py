"""Record the small CPU traces that ``test_devtrace.py`` reduces.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m bench.tests.record_trace [threads|workers]

runs ``g18-ooc.pagerank`` at scale 9 on the CPU with ``trace`` on and
keeps the profiler's ``.xplane.pb`` as ``bench/tests/data/cpu_trace.xplane.pb``;
then runs ``g20-procs4.pagerank`` (four worker processes) at scale 8 the
same way and keeps each worker's trace as
``bench/tests/data/workers/shard-<w>/trace.xplane.pb``, with the window it was
reduced over (wall-clock seconds) in ``window.json``. An argument
records only the one named.
"""

import json
import os
import shutil
import sys
import tempfile

from bench import devtrace, roofline
from bench.tests import tiny

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE = os.path.join(DATA, "cpu_trace.xplane.pb")
WORKERS = os.path.join(DATA, "workers")


def worker_dirs() -> list[str]:
    """One directory a worker, as the probe writes them."""
    return [os.path.join(WORKERS, f"shard-{w}") for w in range(4)]


def main(which=("threads", "workers")) -> None:
    summarize = devtrace.summarize_dir
    summarize_workers = devtrace.summarize_workers

    def keep(log_dir):
        shutil.copy(devtrace.find_xplane(log_dir), TRACE)
        return summarize(log_dir)

    def keep_workers(log_dirs, wall_lo, wall_hi, top=10):
        for d, keep_dir in zip(log_dirs, worker_dirs()):
            os.makedirs(keep_dir, exist_ok=True)
            shutil.copy(devtrace.find_xplane(d),
                        os.path.join(keep_dir, "trace.xplane.pb"))
        with open(os.path.join(WORKERS, "window.json"), "w") as f:
            json.dump(dict(wall_lo=wall_lo, wall_hi=wall_hi), f)
        return summarize_workers(log_dirs, wall_lo, wall_hi, top)

    devtrace.summarize_dir = keep
    devtrace.summarize_workers = keep_workers
    v5e = roofline.peaks("TPU v5 lite")
    roofline.peaks = lambda kind, path=None: v5e
    with tempfile.TemporaryDirectory() as d:
        if "threads" in which:
            result, _ = tiny.run("g18-ooc.pagerank", d, scale=9,
                                 seconds=0.1, trace=True)
            print(result["device"], os.path.getsize(TRACE))
        if "workers" in which:
            result, _ = tiny.run("g20-procs4.pagerank", d, scale=8,
                                 seconds=0.1, trace=True)
            print(result["device"], [os.path.getsize(devtrace.find_xplane(
                d)) for d in worker_dirs()])


if __name__ == "__main__":
    main(sys.argv[1:] or ("threads", "workers"))
