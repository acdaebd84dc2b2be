"""Benchmark harness: one module per paper table. Prints CSV
``name,us_per_call,derived`` (benchmarks/common.emit) and consolidates
everything into one ``BENCH_PR5.json`` artifact — the perf trajectory's
seed record: per-bench wall-clock, the RAM model, the full-duplex overlap
milliseconds, and the payload-codec bytes-on-wire.

``--tiny`` runs the seconds-scale subset (the CI smoke job); ``--chaos``
runs ONLY the fixed-seed chaos-soak matrix (bench_chaos: coordinator
kill -9, peer reset, ENOSPC, bit-flip — the CI chaos-soak job) and gates
on every fault class recovering bit-identically; ``--out``
writes the consolidated JSON; ``--check`` fails the run when a required
section is missing or empty, when the receiver overlap is not positive,
when the lossless payload channel is under 1.5x, when the
``launch="processes"`` per-process RAM model grows with the process count,
when the semi-external hot cache fails to cut disk block reads below
pure streaming while staying inside the planner's ``hot_cache`` model,
or when the socket transport's measured link throughput does not beat the
file-exchange baseline (or its run left shared-filesystem exchange dirs
behind) — the acceptance gates, enforced where the numbers are produced.
"""

from __future__ import annotations

import argparse
import json
import sys
import traceback

from benchmarks import common
from repro.compile_cache import use_compile_cache
from benchmarks.common import OVERLAP_MIN_CPUS, PAYLOAD_LOSSLESS_FLOOR

#: required BENCH_PR5.json sections; --check fails on a missing/empty one
REQUIRED_SECTIONS = ("wall_clock", "ram_model", "overlap", "bytes_on_wire",
                     "process_launch", "semi_external", "net")

#: the chaos-soak matrix (bench_chaos.CASES); --chaos --check fails unless
#: every class ran and recovered bit-identically
CHAOS_CASES = ("coord_kill", "peer_reset", "enospc_ckpt", "bitflip_log")


def _module_plan(tiny: bool, chaos: bool = False):
    if chaos:
        from benchmarks import bench_chaos

        # the soak is its own CI job: the perf sections stay out of it so
        # a chaos failure is unambiguously a recovery bug, not a perf gate
        return [("chaos", bench_chaos, [])]

    from benchmarks import (
        bench_hashmin, bench_kernels, bench_memory, bench_messages,
        bench_pagerank, bench_sssp,
    )

    if tiny:
        # bench_memory carries every PR-5 section and finishes in seconds;
        # the full-size table benches (scale 13-15 graphs) stay out of the
        # smoke budget
        return [("memory", bench_memory, ["--tiny"])]
    return [
        ("pagerank", bench_pagerank, []),
        ("messages", bench_messages, []),
        ("hashmin", bench_hashmin, []),
        ("sssp", bench_sssp, []),
        ("memory", bench_memory, []),
        ("kernels", bench_kernels, []),
    ]


def consolidate(records_by_bench: dict[str, list[dict]], tiny: bool,
                chaos: bool = False) -> dict:
    """Shape the per-bench emit() records into the BENCH_PR5 sections."""
    all_recs = [r for recs in records_by_bench.values() for r in recs]

    def values_of(name: str) -> dict:
        for r in all_recs:
            if r["name"] == name and "values" in r:
                return r["values"]
        return {}

    if chaos:
        # --chaos report: one section, one entry per fault class
        cases = {
            r["name"].split("/", 1)[1]: r.get("values", {})
            for r in all_recs
            if r["name"].startswith("chaos/") and r["name"] != "chaos/reference"
        }
        return dict(
            meta=dict(tiny=tiny, chaos=True,
                      benches=sorted(records_by_bench)),
            sections=dict(chaos=cases),
            records=records_by_bench,
        )

    wall_clock = [
        dict(name=r["name"], us=r["us"])
        for r in all_recs
        if r["us"] > 0 and ("superstep" in r["name"] or "/m_" in r["name"])
    ]
    ram_model = [
        dict(name=r["name"], derived=r["derived"])
        for r in all_recs
        if "ram" in r["name"] or "resident" in r["name"]
        or "model" in r["name"] or "planned_vs_measured" in r["name"]
    ]
    overlap = values_of("memory/pipeline_overlap")
    process_launch = values_of("memory/process_launch")
    semi_external = values_of("memory/semi_external")
    net = values_of("memory/net")
    wire = values_of("memory/payload_wire_lossless")
    bytes_on_wire = dict(
        lossless=wire,
        bf16=values_of("memory/payload_wire_bf16"),
    )
    return dict(
        meta=dict(tiny=tiny, benches=sorted(records_by_bench)),
        sections=dict(
            wall_clock=wall_clock,
            ram_model=ram_model,
            overlap=overlap,
            bytes_on_wire=bytes_on_wire if wire else {},
            process_launch=process_launch,
            semi_external=semi_external,
            net=net,
        ),
        records=records_by_bench,
    )


def check_chaos(report: dict) -> list[str]:
    """The chaos-soak acceptance gates: every fault class in the matrix
    ran, the drill really fired (respawn/recovery counts match), and the
    recovered run is bit-identical — no surviving silent-corruption path."""
    problems = []
    cases = (report.get("sections", {}) or {}).get("chaos") or {}
    for name in CHAOS_CASES:
        vals = cases.get(name)
        if not vals:
            problems.append(f"chaos case {name!r} missing from the soak")
            continue
        if not vals.get("identical"):
            problems.append(
                f"chaos case {name!r} diverged from the undisturbed "
                "reference — recovery is not bit-identical"
            )
        if vals.get("coord_restarts") != vals.get("expected_restarts"):
            problems.append(
                f"chaos case {name!r}: coordinator respawns "
                f"{vals.get('coord_restarts')!r} != expected "
                f"{vals.get('expected_restarts')!r} (drill misfired)"
            )
        if vals.get("recoveries") != vals.get("expected_recoveries"):
            problems.append(
                f"chaos case {name!r}: worker recoveries "
                f"{vals.get('recoveries')!r} != expected "
                f"{vals.get('expected_recoveries')!r} (drill misfired)"
            )
        if not vals.get("quarantined", True):
            problems.append(
                f"chaos case {name!r}: corrupt store was not quarantined"
            )
    return problems


def check(report: dict) -> list[str]:
    """The smoke-job acceptance gates; returns the list of violations."""
    if (report.get("meta") or {}).get("chaos"):
        return check_chaos(report)
    problems = []
    sections = report.get("sections", {})
    for name in REQUIRED_SECTIONS:
        if not sections.get(name):
            problems.append(f"BENCH_PR5 section {name!r} missing or empty")
    overlap = sections.get("overlap") or {}
    if overlap.get("recv_ms", 0) <= 0 or overlap.get("send_ms", 0) <= 0:
        problems.append(
            "both channel directions must have done work "
            f"(send_ms={overlap.get('send_ms')!r}, "
            f"recv_ms={overlap.get('recv_ms')!r})"
        )
    if overlap.get("cpus", 1) >= OVERLAP_MIN_CPUS:
        # overlap positivity is only a meaningful gate where the background
        # threads had a core to run on (mirrors bench_memory's own assert)
        if overlap.get("receiver_overlap_ms", 0) <= 0:
            problems.append(
                f"receiver overlap must be > 0 ms, got "
                f"{overlap.get('receiver_overlap_ms')!r}"
            )
        if overlap.get("sender_overlap_ms", 0) <= 0:
            problems.append(
                f"sender overlap must be > 0 ms, got "
                f"{overlap.get('sender_overlap_ms')!r}"
            )
    procs = sections.get("process_launch") or {}
    rams = procs.get("per_process_ram") or []
    if len(rams) < 2:
        problems.append(
            "process_launch must model >= 2 process counts, got "
            f"{procs.get('ns')!r}"
        )
    elif any(b > a for a, b in zip(rams, rams[1:])):
        problems.append(
            "per-process RAM must not grow with the process count: "
            f"ns={procs.get('ns')!r} ram={rams!r}"
        )
    semi = sections.get("semi_external") or {}
    if semi:
        if semi.get("semi_blocks", 0) >= semi.get("streamed_blocks", 0):
            problems.append(
                "semi-external must read strictly fewer edge blocks than "
                f"pure streaming: semi={semi.get('semi_blocks')!r} "
                f"streamed={semi.get('streamed_blocks')!r}"
            )
        if semi.get("late_semi", 0) >= semi.get("late_streamed", 0):
            problems.append(
                "semi-external must beat pure streaming on the sparse late "
                f"rounds: late_semi={semi.get('late_semi')!r} "
                f"late_streamed={semi.get('late_streamed')!r}"
            )
        cache_cap = semi.get("n_shards", 0) * semi.get("hot_cache_model", 0)
        if not 0 < semi.get("cached_bytes", 0) <= cache_cap:
            problems.append(
                "resident cache bytes must be positive and within the "
                f"planner's hot_cache model: "
                f"cached={semi.get('cached_bytes')!r} cap={cache_cap!r}"
            )
    net = sections.get("net") or {}
    if net:
        if net.get("link_bytes_per_s", 0) <= net.get("file_bytes_per_s", 0):
            problems.append(
                "measured socket link throughput must beat the "
                "file-exchange baseline: "
                f"link={net.get('link_bytes_per_s')!r} B/s "
                f"file={net.get('file_bytes_per_s')!r} B/s"
            )
        if not net.get("no_fs_exchange"):
            problems.append(
                "socket-transport run must not write shared-filesystem "
                "exchange dirs (announce markers found)"
            )
        if net.get("wire_bytes", 0) <= 0 or net.get("frames", 0) <= 0:
            problems.append(
                "socket transport moved no frames: "
                f"wire_bytes={net.get('wire_bytes')!r} "
                f"frames={net.get('frames')!r}"
            )
        if net.get("cpus", 1) >= OVERLAP_MIN_CPUS:
            if net.get("sender_overlap_ms", 0) <= 0:
                problems.append(
                    "socket-run sender overlap must be > 0 ms, got "
                    f"{net.get('sender_overlap_ms')!r}"
                )
            if net.get("receiver_overlap_ms", 0) <= 0:
                problems.append(
                    "socket-run receiver overlap must be > 0 ms, got "
                    f"{net.get('receiver_overlap_ms')!r}"
                )
    wire = (sections.get("bytes_on_wire") or {}).get("lossless") or {}
    if wire.get("ratio", 0) < PAYLOAD_LOSSLESS_FLOOR:
        problems.append(
            f"lossless payload channel must be >= "
            f"{PAYLOAD_LOSSLESS_FLOOR}x smaller, got "
            f"{wire.get('ratio')!r}"
        )
    return problems


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="seconds-scale subset (CI smoke)")
    ap.add_argument("--chaos", action="store_true",
                    help="run ONLY the fixed-seed chaos-soak fault matrix "
                         "(coordinator kill, peer reset, ENOSPC, bit-flip)")
    ap.add_argument("--out", metavar="PATH", default=None,
                    help="write the consolidated BENCH_PR5.json here")
    ap.add_argument("--check", action="store_true",
                    help="fail unless every required section is present and "
                         "the overlap/wire acceptance gates hold (--chaos: "
                         "every fault class recovered bit-identically)")
    args = ap.parse_args()
    use_compile_cache()

    print("name,us_per_call,derived")
    failed = []
    records_by_bench: dict[str, list[dict]] = {}
    for name, mod, mod_args in _module_plan(args.tiny, args.chaos):
        mark = len(common.all_records())
        argv = sys.argv
        try:
            sys.argv = [argv[0]] + mod_args  # argparse-driven mains
            mod.main()
        except Exception:
            failed.append(mod.__name__)
            traceback.print_exc()
        finally:
            sys.argv = argv
        records_by_bench[name] = common.records_since(mark)

    report = consolidate(records_by_bench, args.tiny, args.chaos)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
        print(f"wrote {args.out}", file=sys.stderr)
    if args.check:
        for problem in check(report):
            failed.append(problem)
            print(f"CHECK FAILED: {problem}", file=sys.stderr)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    main()
