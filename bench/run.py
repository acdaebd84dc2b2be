#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell is an entry of ``workloads`` in
``BENCHMARK.json``; everything else it needs is found by name under
``bench/`` (see ``bench/__init__.py``). The run fails before any work,
exit code 1 and no result, when JAX finds no TPU or fewer chips than the
cell asks for: it never falls back to the CPU.

The last line of standard output is one JSON object: ``correct``,
``attempted`` and ``failed`` (supersteps; for a traffic that halts,
searches), ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number compared with the reference beside its limit. The same numbers
are the last lines of standard error.

``--control 1`` runs the configuration's lower-precision path instead of
the configured plan: the control that the check must call not correct.
The benchmark's own runs never pass it. A traffic with no such path (its
``no_control``) exits with code 2 and a line saying so.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # before JAX loads, so that the workers of launch="processes" inherit
    # them: one compile cache at a fixed path inside the checkout, and
    # every program cached, however quickly it compiled
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    from bench import harness

    t_start = harness.process_start()
    try:
        cell = harness.resolve(harness.load_spec(ROOT), args.workload, ROOT)
        if args.control:
            harness.check_control(cell)
        harness.check_chips(cell)
    except harness.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    try:
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"bench: the system under test is not here: {e}",
              file=sys.stderr)
        return 2
    try:
        result, checks = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace),
            control=bool(args.control), t_start=t_start)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    except Exception:
        traceback.print_exc()
        return 3
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
