#!/usr/bin/env python3
"""Smoke run of the GraphD system on a TPU: the quickest proof that the main
path still starts on the chip and gives right answers.

    python chip_smoke.py [--seed N]            # one chip, four phases
    python chip_smoke.py --chips 4 [--seed N]  # four chips, multi-process only

The graph is a Graph500/Kronecker graph generated from ``--seed`` by
``rmat_graph`` at the size of LDBC Graphalytics' ``graph500-22`` dataset
(scale 22, edge factor 16, A/B/C = 0.57/0.19/0.19). Departure from the
dataset: ``rmat_graph`` drops self-loops and duplicate edges. Phase A runs
at that scale; the other phases cut the scale to fit the run's time, say
why below (``*_SCALE``), and print the cut.

One chip, one process, every phase through the public API:

  A  in-memory ``recoded`` PageRank (10 supersteps) planned by ``GraphDJob``;
  B  out-of-core ``streamed`` BFS with the full-duplex pipeline, planned by
     ``GraphDJob`` under a RAM budget that rules the edge groups out of RAM;
  C  combiner-less OMS path: ``SecondMinLabel`` streamed through ``GraphDJob``;
  D  ``GraphDEngine`` with ``backend="pallas"``: PageRank through the
     compiled Mosaic kernels, whose step must hold a ``tpu_custom_call``.

``--chips 4`` runs only the path that exists across chips: a streamed
PageRank plan under ``launch="processes"`` with four workers, one chip each,
against a one-process ``launch="threads"`` run of the same plan (in a child
process that exits before the workers start) and the numpy reference.

Every answer is checked against a plain numpy reference written below, which
imports nothing from the code under test. Times printed here are smoke
numbers, not a benchmark. The last line of standard output is one JSON
object naming the device; any failed phase exits non-zero without it.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

# graph500-22 (LDBC Graphalytics): Kronecker scale 22, edge factor 16
SCALE, EDGE_FACTOR, KRONECKER = 22, 16, (0.57, 0.19, 0.19)
N_SHARDS = 8  # phases A-C: 8 emulated machines on one chip
PR_STEPS = 10
# PageRank runs in float32 and sums up to max-in-degree terms per vertex in
# an order the device chooses; the reference runs in float64. 1e-3 relative
# is ~1.6e4 float32 ulps: far above summation-order noise, far below any
# real error (a lost or doubled message moves a rank by >= 1e-2 relative).
PR_RTOL = 1e-3
ROOT = 0  # BFS root (original id): the Kronecker generator's densest vertex
# Phases B and C: the streamed supersteps are host-driven (every staged
# chunk of 8 x 512 edges is copied to the chip and synced on), and a
# near-dense one took ~105 s at scale 22 on one v5e host; B and C at scale
# 22 would leave too little of the run's time limit. They share one graph.
STREAM_SCALE = 20
# Phase D: the kernel layout cuts each (dst window, src window) cell of a
# group into its own 512-edge blocks; a cell holds ~2**(22 - scale) edges,
# so at scale 22 the blocks would be ~0.2% full and the layout ~500x the
# edges. Scale 16 fills them ~12%.
PALLAS_SCALE = 16
PROC_CHIPS = 4  # --chips 4: one worker per chip
# --chips 4: the threads reference folds every edge through one chip,
# 10 near-dense streamed supersteps; at scale 22 that is ~1000 s alone.
PROC_SCALE = 18


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


# --------------------------------------------------------------------------
# numpy references (independent of the code under test)
# --------------------------------------------------------------------------

def ref_pagerank(n, src, dst, steps, damping=0.85):
    deg = np.bincount(src, minlength=n).astype(np.float64)
    inv = 1.0 / np.maximum(deg, 1.0)
    v = np.full(n, 1.0 / n)
    for _ in range(steps):
        acc = np.bincount(dst, weights=v[src] * inv[src], minlength=n)
        v = 0.15 / n + damping * acc
    return v


def ref_bfs(n, src, dst, root):
    level = np.full(n, np.inf)
    level[root] = 0.0
    frontier = np.zeros(n, bool)
    frontier[root] = True
    d = 0
    while frontier.any():
        d += 1
        reached = dst[frontier[src]]
        reached = reached[np.isinf(level[reached])]
        frontier = np.zeros(n, bool)
        frontier[reached] = True
        level[reached] = d
    return level


def ref_second_min(n, src, dst, label, sentinel):
    """Second-smallest distinct label among each vertex's in-neighbours."""
    key = np.unique(dst.astype(np.int64) << 32 | label[src].astype(np.int64))
    d, lab = key >> 32, key & 0xFFFFFFFF
    first = np.flatnonzero(np.r_[True, d[1:] != d[:-1]])
    count = np.diff(np.r_[first, d.size])
    out = np.full(n, sentinel, np.int64)
    two = first[count >= 2]
    out[d[two]] = lab[two + 1]
    return out


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def as_array(values: dict, n: int, dtype) -> np.ndarray:
    """JobResult.values ({original id: value}) as a dense array by id."""
    ids = np.fromiter(values.keys(), np.int64, len(values))
    out = np.zeros(n, dtype)
    out[ids] = np.fromiter(values.values(), dtype, len(values))
    if len(values) != n:
        fail(f"{len(values)} vertex values for {n} vertices")
    return out


def check_close(name, got, ref, rtol):
    err = np.abs(got - ref) / np.abs(ref)
    worst = float(err.max())
    if not np.all(np.isfinite(got)) or worst > rtol:
        fail(f"{name}: max relative error {worst:.3g} > {rtol:g}")
    return worst


def check_equal(name, got, ref):
    bad = np.flatnonzero(got != ref)
    if bad.size:
        i = int(bad[0])
        fail(f"{name}: {bad.size} vertices differ (vertex {i}: "
             f"{got[i]} vs reference {ref[i]})")


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"


def step_times(history) -> str:
    secs = [r.seconds for r in history]
    rest = secs[1:]
    med = f"{np.median(rest):.3f} s" if rest else "n/a"
    return (f"first superstep (incl. compile) {secs[0]:.3f} s, "
            f"superstep median {med} over {len(rest)} "
            "[smoke numbers, not a benchmark]")


class CacheCounter:
    """Persistent compile-cache hits and misses (writes), counted from
    JAX's monitoring events from construction on."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def budget_for(program, graph, n, rule_out, want):
    """A RAM budget per shard under which the planner picks a plan that
    ``want`` accepts: start one byte under candidate ``rule_out``'s floor
    (``plan.explain()`` lists the candidates) and walk down in 5% steps,
    so the plan keeps the largest knobs that fit."""
    from repro.core import MemoryBudget, plan
    from repro.core.plan import PlanInfeasible

    try:
        plan(program, graph, MemoryBudget(ram_per_shard=1, n_shards=n))
    except PlanInfeasible as e:
        floors = {c["name"]: c["ram_total"]
                  for c in e.breakdown["candidates"]}
    ram = floors[rule_out] - 1
    while True:
        budget = MemoryBudget(ram_per_shard=ram, n_shards=n)
        if want(plan(program, graph, budget)):
            return budget
        ram = int(ram * 0.95)


def generate(scale, seed):
    from repro.graph import rmat_graph

    t0 = time.perf_counter()
    a, b, c = KRONECKER
    g = rmat_graph(scale=scale, edge_factor=EDGE_FACTOR, a=a, b=b, c=c,
                   seed=seed)
    dt = time.perf_counter() - t0
    log(f"graph: Kronecker scale {scale}, edge factor {EDGE_FACTOR}, "
        f"A/B/C {a}/{b}/{c}, seed {seed}: |V|={g.vertex_ids.size} "
        f"|E|={g.src.size} after dropping self-loops and duplicates; "
        f"generate {dt:.3f} s")
    return g


# --------------------------------------------------------------------------
# one chip: phases A-D
# --------------------------------------------------------------------------

def phase_a(graph, device):
    from repro.core import GraphDJob, MemoryBudget, PageRank

    n = graph.vertex_ids.size
    t0 = time.perf_counter()
    with GraphDJob(PageRank(supersteps=PR_STEPS), graph,
                   budget=MemoryBudget(n_shards=N_SHARDS),
                   workdir=tempfile.mkdtemp(prefix="chip-smoke-a-")) as job:
        setup = time.perf_counter() - t0
        if job.plan.mode != "recoded":
            fail(f"phase A planned {job.plan.mode!r}, expected 'recoded'")
        res = job.run()
    shutil.rmtree(res.workdir, ignore_errors=True)
    got = as_array(res.values, n, np.float64)
    err = check_close("phase A PageRank", got,
                      ref_pagerank(n, graph.src, graph.dst, PR_STEPS),
                      PR_RTOL)
    log(f"[A] in-memory PageRank x{PR_STEPS}: plan={res.plan.mode} "
        f"n_shards={res.plan.n_shards}; set-up (partition) {setup:.3f} s; "
        f"{step_times(res.history)}; peak device memory so far "
        f"{peak_bytes(device)}; max relative error {err:.3g} "
        f"(tolerance {PR_RTOL:g}) OK")


def full_duplex(p) -> bool:
    return (p.mode == "streamed" and p.pipeline
            and p.config.channel.full_duplex)


def phase_b(graph, device):
    from repro.core import BFS, GraphDJob
    from repro.graph import recode_ids

    n = graph.vertex_ids.size
    root_new = int(recode_ids(graph.vertex_ids, N_SHARDS).to_new(
        np.array([ROOT]))[0])
    prog = BFS(root_new)
    # below the unpipelined fold's floor (n accumulators): the pipelined
    # channel keeps one
    budget = budget_for(prog, graph, N_SHARDS, "streamed", want=full_duplex)
    t0 = time.perf_counter()
    with GraphDJob(prog, graph, budget=budget,
                   workdir=tempfile.mkdtemp(prefix="chip-smoke-b-")) as job:
        setup = time.perf_counter() - t0
        p = job.plan
        if not full_duplex(p):
            fail(f"phase B planned {p.mode} pipeline={p.pipeline} "
                 f"full_duplex={p.config.channel.full_duplex}")
        if int(job.rmap.to_new(np.array([ROOT]))[0]) != root_new:
            fail("phase B: the job recoded the BFS root differently")
        res = job.run()
    shutil.rmtree(res.workdir, ignore_errors=True)
    got = as_array(res.values, n, np.float64)
    ref = ref_bfs(n, graph.src, graph.dst, ROOT)
    check_equal("phase B BFS levels", got, ref)
    reached = int(np.isfinite(ref).sum())
    log(f"[B] out-of-core BFS from vertex {ROOT} at scale {STREAM_SCALE} "
        f"(cut from {SCALE}: host-driven streamed supersteps): plan={p.mode} "
        f"pipeline={p.pipeline} full_duplex={p.config.channel.full_duplex} "
        f"ram_per_shard={budget.ram_per_shard} B; set-up (partition + "
        f"spill) {setup:.3f} s; {len(res.history)} supersteps, "
        f"{step_times(res.history)}; peak device memory so far "
        f"{peak_bytes(device)}; {reached} vertices reached, levels "
        f"0..{int(ref[np.isfinite(ref)].max())} exact OK")


def phase_c(graph, device):
    from repro.core import GraphDJob, SecondMinLabel

    n = graph.vertex_ids.size
    prog = SecondMinLabel()
    # below the in-memory message-list mode: the edge groups go to disk
    budget = budget_for(prog, graph, N_SHARDS, "basic",
                        want=lambda p: p.mode == "streamed")
    t0 = time.perf_counter()
    with GraphDJob(prog, graph, budget=budget,
                   workdir=tempfile.mkdtemp(prefix="chip-smoke-c-")) as job:
        setup = time.perf_counter() - t0
        if job.plan.mode != "streamed":
            fail(f"phase C planned {job.plan.mode!r}, expected 'streamed'")
        label = job.rmap.to_new(graph.vertex_ids)
        if np.unique(label).size != n:
            fail("phase C: the recoding is not one-to-one")
        res = job.run()
    shutil.rmtree(res.workdir, ignore_errors=True)
    got = as_array(res.values, n, np.int64)
    ids = np.empty(n, np.int64)
    ids[graph.vertex_ids] = label
    ref = ref_second_min(n, graph.src, graph.dst, ids, prog.SENTINEL)
    check_equal("phase C second-min labels", got, ref)
    log(f"[C] combiner-less SecondMinLabel at scale {STREAM_SCALE} (cut "
        f"from {SCALE}: host-driven streamed supersteps): "
        f"plan={res.plan.mode} "
        f"pipeline={res.plan.pipeline}; set-up (partition + spill) "
        f"{setup:.3f} s; {step_times(res.history)}; peak device memory so "
        f"far {peak_bytes(device)}; "
        f"{int((ref != prog.SENTINEL).sum())} labelled vertices exact OK")


def phase_d(seed, device):
    from repro.core import EngineConfig, GraphDEngine, PageRank
    from repro.graph import partition_graph

    graph = generate(PALLAS_SCALE, seed)
    n = graph.vertex_ids.size
    t0 = time.perf_counter()
    pg, _ = partition_graph(graph, N_SHARDS, vertex_pad=512)
    runs = {}
    for backend in ("pallas", "jnp"):
        eng = GraphDEngine(pg, PageRank(supersteps=PR_STEPS),
                           config=EngineConfig(mode="recoded",
                                               backend=backend))
        if backend == "pallas":
            setup = time.perf_counter() - t0
            v0, a0 = eng.init()
            hlo = eng.lower_step(v0, a0).compile().as_text()
            if "tpu_custom_call" not in hlo:
                fail("phase D: the Pallas step holds no tpu_custom_call "
                     "(the kernel was interpreted, not compiled)")
        (vals, _), hist = eng.run()
        runs[backend] = (as_array(eng.gather_values(vals), n, np.float64),
                         hist)
    ref = ref_pagerank(n, graph.src, graph.dst, PR_STEPS)
    got, hist = runs["pallas"]
    err = check_close("phase D Pallas PageRank", got, ref, PR_RTOL)
    err_jnp = check_close("phase D Pallas vs jnp backend", got,
                          runs["jnp"][0], PR_RTOL)
    log(f"[D] Pallas-backend PageRank x{PR_STEPS} at scale {PALLAS_SCALE} "
        f"(cut from {SCALE}: kernel-layout fill): mode=recoded "
        f"backend=pallas, tpu_custom_call present; set-up (partition + "
        f"kernel layout) {setup:.3f} s; {step_times(hist)}; peak device "
        f"memory so far {peak_bytes(device)}; max relative error "
        f"{err:.3g} vs numpy, {err_jnp:.3g} vs the jnp backend "
        f"(tolerance {PR_RTOL:g}) OK")


def one_chip(args):
    import jax

    from repro.compile_cache import use_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {dev.platform!r} devices")
    cache = use_compile_cache()
    counter = CacheCounter()
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {cache}")
    phase_a(generate(SCALE, args.seed), dev)
    graph = generate(STREAM_SCALE, args.seed)
    phase_b(graph, dev)
    phase_c(graph, dev)
    phase_d(args.seed, dev)
    log(f"compile cache {cache}: {counter.hits} hits, {counter.misses} "
        f"misses this run ({'hit' if counter.hits else 'cold'})")
    return dict(platform=dev.platform, kind=dev.device_kind,
                count=len(devices))


# --------------------------------------------------------------------------
# four chips: launch="processes", one worker per chip
# --------------------------------------------------------------------------

def threads_child(args):
    """The one-process reference run of the four-chip plan; it holds the
    chips only while it runs, and exits before the workers start."""
    import jax

    from repro.compile_cache import use_compile_cache
    from repro.core import GraphDJob, PageRank
    from repro.core.plan import ExecutionPlan
    from repro.graph import Graph

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {dev.platform!r} devices")
    use_compile_cache()
    d = args.threads_child
    z = np.load(os.path.join(d, "graph.npz"))
    graph = Graph(src=z["src"], dst=z["dst"], weight=z["weight"],
                  vertex_ids=z["ids"])
    with open(os.path.join(d, "plan.json")) as f:
        p = ExecutionPlan.from_json(f.read())
    with GraphDJob(PageRank(supersteps=PR_STEPS), graph, plan=p,
                   workdir=os.path.join(d, "threads")) as job:
        res = job.run()
    np.save(os.path.join(d, "threads.npy"),
            as_array(res.values, graph.vertex_ids.size, np.float32))
    with open(os.path.join(d, "threads.json"), "w") as f:
        json.dump(dict(device=res.devices[0], mode=res.plan.mode,
                       seconds=[r.seconds for r in res.history]), f)
    return None


def four_chips(args):
    from repro.core import GraphDJob, MemoryBudget, PageRank, plan
    from repro.core.coordinator import WorkerFailed
    from repro.launch.placement import count_chips

    chips = count_chips()
    if chips < PROC_CHIPS:
        fail(f"--chips {PROC_CHIPS} needs {PROC_CHIPS} TPU chips on this "
             f"host; found {chips}")
    graph = generate(PROC_SCALE, args.seed)
    n = graph.vertex_ids.size
    prog = PageRank(supersteps=PR_STEPS)
    p = plan(prog, graph, MemoryBudget(n_shards=PROC_CHIPS),
             launch="processes")
    if not (p.mode == "streamed" and p.pipeline):
        fail(f"four-chip plan is {p.mode} pipeline={p.pipeline}")
    with tempfile.TemporaryDirectory(prefix="chip-smoke-4-") as d:
        np.savez(os.path.join(d, "graph.npz"), src=graph.src, dst=graph.dst,
                 weight=graph.weight, ids=graph.vertex_ids)
        with open(os.path.join(d, "plan.json"), "w") as f:
            f.write(p.to_json())
        t0 = time.perf_counter()
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--threads-child", d, "--seed", str(args.seed)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        if child.returncode != 0:
            print(child.stdout[-4000:], file=sys.stderr)
            fail(f"threads reference run exited {child.returncode}")
        threads_s = time.perf_counter() - t0
        ref_threads = np.load(os.path.join(d, "threads.npy"))
        with open(os.path.join(d, "threads.json")) as f:
            tinfo = json.load(f)
        log(f"[4] threads reference (one process, {tinfo['device']['kind']}"
            f"): plan={tinfo['mode']} n_shards={PROC_CHIPS}; whole child "
            f"{threads_s:.3f} s; first superstep (incl. compile) "
            f"{tinfo['seconds'][0]:.3f} s, superstep median "
            f"{np.median(tinfo['seconds'][1:]):.3f} s [smoke numbers, not a "
            "benchmark]")
        t0 = time.perf_counter()
        with GraphDJob(prog, graph, plan=p, launch="processes",
                       workdir=os.path.join(d, "procs")) as job:
            setup = time.perf_counter() - t0
            try:
                res = job.run()
            except WorkerFailed as e:
                for w in range(PROC_CHIPS):  # before the workdir goes
                    path = os.path.join(d, "procs", "procs", f"shard-{w}",
                                        "worker.log")
                    if os.path.exists(path):
                        with open(path, errors="replace") as f:
                            tail = f.read()[-3000:]
                        print(f"--- worker {w} log ---\n{tail}",
                              file=sys.stderr)
                fail(f"launch='processes' failed: {e}")
    got = as_array(res.values, n, np.float32)
    for w in res.devices:
        log(f"[4] worker {w['shard']}: {w['platform']} {w['kind']}")
    if len(res.devices) != PROC_CHIPS or any(
            w["platform"] != "tpu" for w in res.devices):
        fail(f"workers ran on {res.devices}, expected {PROC_CHIPS} TPU chips")
    check_equal("four-chip processes vs threads", got, ref_threads)
    err = check_close("four-chip PageRank", got.astype(np.float64),
                      ref_pagerank(n, graph.src, graph.dst, PR_STEPS),
                      PR_RTOL)
    log(f"[4] launch=processes x{PROC_CHIPS} streamed PageRank x{PR_STEPS} "
        f"at scale {PROC_SCALE} (cut from {SCALE}: the one-chip threads "
        "reference run): "
        f"plan={res.plan.mode} pipeline={res.plan.pipeline}; set-up "
        f"(partition + spill) {setup:.3f} s; {step_times(res.history)}; "
        f"bit-identical to the threads run; max relative error {err:.3g} "
        f"vs numpy (tolerance {PR_RTOL:g}) OK")
    kinds = {w["kind"] for w in res.devices}
    return dict(platform="tpu", kind=kinds.pop(), count=len(res.devices))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, PROC_CHIPS), default=1,
                    help="4: run only the four-chip multi-process path")
    ap.add_argument("--threads-child", metavar="DIR",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"no repro package under {SRC}: run from a checkout")
    sys.path.insert(0, SRC)
    if args.threads_child:
        threads_child(args)
        return 0
    device = one_chip(args) if args.chips == 1 else four_chips(args)
    print(json.dumps(dict(ok=True, device=device)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
