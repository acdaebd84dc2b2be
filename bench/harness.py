"""One run of one benchmark cell: set-up, a timed window, the check.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix; :func:`resolve` finds their files and the
readers of the cell's metrics by name (see ``bench/__init__.py``).
:func:`run_cell` then

1. loads the configuration's graph (``graphgen``: made once in a child
   process and cached under ``bench/.cache/graphs``) and labels its
   vertices from the seed: the benchmark's data, not set-up;
2. opens the chip, builds the traffic's program and the configuration's
   budget, and constructs the ``GraphDJob`` (plan, partition, spill),
   refusing a plan other than the configuration's;
3. runs one warm-up superstep through ``GraphDJob.run``, which compiles or
   loads every program from the persistent cache, and sizes the timed
   call's superstep count ``K`` so that the window lasts about
   ``seconds``;
4. times one ``GraphDJob.run`` call of ``K`` supersteps, with ``trace``
   under the profiler;
5. after the window, reads the device's memory peak, releases the job,
   and compares the values with the traffic's numpy reference run for as
   many supersteps on the same graph.

Set-up is everything from process start to the window's start less the
seconds of step 1; the window runs from the timed call's start to its
last superstep's end.

A configuration whose ``launch.mode`` is ``"processes"`` runs one worker
process per chip (``repro.launch.procs``), and this process never opens a
chip. ``GraphDJob.run`` spawns its workers anew at every call, so such a
cell differs in three places: the warm-up runs two supersteps, and the
second one's seconds give ``K``; the timed call runs ``1 + K``
supersteps, and its first, which carries the workers' spawn, their JAX
start and their program loads, belongs to set-up, so that the window runs
from that superstep's end to the last one's; and what only the process
that holds a chip can read (its memory peak, its trace, its compiles and
its barrier waits) comes from the probe, ``bench/probe/sitecustomize.py``,
which the harness loads into each worker of the timed call.

A traffic that says ``"halts": true`` is a search that runs to its
fixpoint, such as a BFS. Its unit of work is one whole search, as
Graphalytics times it, so such a cell differs in other places: the
warm-up is one whole search, whose seconds give ``K``, the number of
searches the window runs; each search starts again from superstep 0
(``GraphDEngine.run`` with no state) and runs until the program halts,
under a cap of ``|V|`` supersteps; the window runs from the first timed
search's start to the last one's last superstep; and every timed search
is compared with the reference, which also gives the supersteps the
search takes to its fixpoint. A program argument ``{"$source": {"seed":
s}}`` is the search's source: a vertex drawn from the traffic's seed
``s`` (:func:`resolve_sources`), the same vertex of the structure in
every run, whose index the reference is handed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time
from dataclasses import dataclass

import numpy as np

from bench import devtrace, graphgen

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class SpecError(ValueError):
    """``BENCHMARK.json`` or a file it names is missing or malformed."""


class NoChip(RuntimeError):
    """The accelerator the cell asks for is not there."""


# ---------------------------------------------------------------------------
# finding a cell's files by name
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    traffic: dict
    reference: object  # module: bench/reference/<traffic>.py
    end_to_end: list  # [(metric entry, reader module)]
    per_layer: list

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])



def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_spec(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def resolve(spec: dict, name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``spec`` with its files loaded from ``root``."""
    by_name = {w["name"]: w for w in spec.get("workloads", [])}
    if name not in by_name:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json (have "
                        f"{sorted(by_name)})")
    w = by_name[name]
    bench = os.path.join(root, "bench")
    config = load_json(os.path.join(bench, "configs", f"{w['config']}.json"))
    traffic = load_json(os.path.join(bench, "traffic",
                                     f"{w['traffic']}.json"))
    reference = load_module(os.path.join(bench, "reference",
                                         f"{w['traffic']}.py"), w["traffic"])

    def readers(kind):
        out = []
        for m in spec.get(kind, []):
            if name in m.get("workloads", [name]):
                out.append((m, load_module(
                    os.path.join(bench, "metrics", f"{m['name']}.py"),
                    m["name"])))
        return out

    return Cell(name=name, workload=w, config=config, traffic=traffic,
                reference=reference, end_to_end=readers("end_to_end"),
                per_layer=readers("per_layer"))


# ---------------------------------------------------------------------------
# what a run leaves for the metric readers
# ---------------------------------------------------------------------------

@dataclass
class RunRecord:
    """Everything a metric reader (``bench/metrics/<name>.py``, a function
    ``read(run) -> float | None``) may read."""

    cell: Cell
    n_vertices: int
    setup_s: float
    partition_s: float
    first_step_s: float
    window_s: float
    window: list  # SuperstepRecord of each superstep in the window
    window_edges: int  # edges out of active vertices, summed over them
    plan_mode: str
    channel_stats: dict | None = None  # GraphDEngine.channel_stats, if any
    trace: devtrace.TraceSummary | None = None
    device_kind: str = ""
    # launch="processes" only: the timed call's start to its first
    # superstep's end, less the window's median superstep
    spawn_s: float | None = None
    # launch="processes" only: each worker's seconds in each barrier wait
    # of the window's supersteps
    barrier_waits: list | None = None


# ---------------------------------------------------------------------------
# the chip
# ---------------------------------------------------------------------------

def count_chips(dev_root: str = "/dev") -> int:
    """TPU chips on this host from its device nodes, without opening one."""
    import glob

    accel = glob.glob(os.path.join(dev_root, "accel[0-9]*"))
    if accel:
        return len(accel)
    return len(glob.glob(os.path.join(dev_root, "vfio", "[0-9]*")))


def check_chips(cell: Cell) -> None:
    """Fail before any work unless the host has the cell's TPU chips,
    counted from its device nodes: nothing opens a chip here, so that the
    graph can be made in a child process that holds it for a while."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        raise NoChip("JAX_PLATFORMS=cpu: no TPU")
    have = count_chips()
    if have < cell.chips:
        raise NoChip(f"{cell.name} needs {cell.chips} TPU chips; this host "
                     f"has {have}")


def open_device(cell: Cell) -> dict:
    """Open the chips of a threads cell; fail unless JAX finds them as
    TPUs (it never falls back to the CPU)."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform!r} devices")
    if len(devices) < cell.chips:
        raise NoChip(f"{cell.name} needs {cell.chips} TPU chips; JAX found "
                     f"{len(devices)}")
    return dict(platform=devices[0].platform, kind=devices[0].device_kind,
                count=len(devices))


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or loading from
    the persistent cache), how many programs it built that way, and how
    many of them the persistent cache held, from its monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
    HITS = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **_kw) -> None:
        # compiles on other threads overlap the main thread's work
        if (event in self.EVENTS
                and threading.get_ident() == threading.main_thread().ident):
            self.seconds += duration
            self.compiles += event == self.EVENTS[-1]

    def _on_event(self, event: str, **_kw) -> None:
        self.requests += event == self.REQUESTS
        self.hits += event == self.HITS

    @property
    def misses(self) -> int:
        """Programs compiled anew: asked of the persistent cache, not in it."""
        return self.requests - self.hits


# ---------------------------------------------------------------------------
# the job
# ---------------------------------------------------------------------------

def draw_source(src, dst, n: int, seed: int) -> int:
    """A search key by Graph500's rule: a vertex index drawn uniformly from
    ``seed`` among the vertices with an edge, drawn again while the search
    from it reaches fewer than half of the ``n`` vertices."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import breadth_first_order

    has_edge = np.flatnonzero(np.bincount(src, minlength=n))
    adj = csr_matrix((np.ones(src.size, np.int8), (src, dst)), shape=(n, n))
    rng = np.random.default_rng(int(seed))
    for _ in range(100):
        v = int(rng.choice(has_edge))
        reached = breadth_first_order(adj, v, return_predecessors=False)
        if 2 * reached.size >= n:
            return v
    raise SpecError(f"no vertex drawn from seed {seed} reaches half of the "
                    "graph")


def resolve_sources(traffic: dict, src, dst, labels) -> dict:
    """``traffic`` with its argument ``{"$source": {"seed": s}}``, if it has
    one, resolved: the program gets ``{"$recoded_id": id}``, the id in this
    run of the vertex :func:`draw_source` draws from ``s``, and the traffic
    gets ``source_index``, that vertex's index, for the reference. The draw
    reads the structure alone, so every run searches from the same vertex
    of it."""
    args = traffic["program"].get("args", {})
    keys = [k for k, v in args.items()
            if isinstance(v, dict) and "$source" in v]
    if not keys:
        return traffic
    if len(keys) > 1:
        raise SpecError(f"more than one $source argument: {keys}")
    out = json.loads(json.dumps(traffic))
    index = draw_source(src, dst, labels.size,
                        args[keys[0]]["$source"]["seed"])
    out["program"]["args"][keys[0]] = {"$recoded_id": int(labels[index])}
    out["source_index"] = index
    return out


def check_control(cell: Cell) -> None:
    """Fail unless the cell has a lower-precision path to run as the
    control: a traffic whose answers no narrower type can change says so
    under ``no_control``."""
    if "no_control" in cell.traffic:
        raise SpecError(f"{cell.name}: traffic {cell.workload['traffic']!r} "
                        "has no precision control: "
                        f"{cell.traffic['no_control']}")


def make_program(traffic: dict, vertex_ids, n_shards: int):
    """The traffic's ``program``: a class of ``repro.core`` and its
    arguments; an argument ``{"$recoded_id": v}`` is vertex id ``v``'s
    recoded id under ``n_shards`` (how GraphD's programs name a vertex)."""
    import repro.core as core
    from repro.graph import recode_ids

    spec = traffic["program"]
    args = {}
    for k, v in spec.get("args", {}).items():
        if isinstance(v, dict) and "$recoded_id" in v:
            v = int(recode_ids(vertex_ids, n_shards).to_new(
                np.array([v["$recoded_id"]]))[0])
        args[k] = v
    return getattr(core, spec["class"])(**args)


def budget_for(program, metas, n, rule_out, want, launch="threads"):
    """A RAM budget per shard under which the planner, planning for
    ``launch``, picks a plan that ``want`` accepts for each of ``metas``:
    one byte under candidate ``rule_out``'s floor (the candidates of
    ``PlanInfeasible.breakdown``) for the first, walking down in 5 % steps,
    so the plan keeps the largest knobs that fit."""
    from repro.core import MemoryBudget, plan
    from repro.core.plan import PlanInfeasible

    try:
        plan(program, metas[0], MemoryBudget(ram_per_shard=1, n_shards=n),
             launch=launch)
    except PlanInfeasible as e:
        floors = {c["name"]: c["ram_total"]
                  for c in e.breakdown["candidates"]}
    ram = floors[rule_out] - 1
    while ram > 0:
        budget = MemoryBudget(ram_per_shard=ram, n_shards=n)
        try:
            if all(want(plan(program, m, budget, launch=launch))
                   for m in metas):
                return budget
        except PlanInfeasible:
            pass
        ram = int(ram * 0.95)
    raise SpecError(f"no budget under the {rule_out!r} floor gives the plan")


def plan_matches(p, want: dict) -> bool:
    got = dict(mode=p.mode, pipeline=p.pipeline,
               full_duplex=p.config.channel.full_duplex)
    return all(got[k] == v for k, v in want.items())


def realized_meta(graph, n: int, vertex_pad: int = 8):
    """The graph's ``GraphMeta`` with the shard size its hash partition
    will realize (``vertex_pad`` is ``GraphDJob``'s default)."""
    from repro.core.plan import GraphMeta
    from repro.graph import recode_ids

    pos = recode_ids(graph.vertex_ids, n).max_positions
    return GraphMeta(n_vertices=graph.n_vertices, n_edges=graph.n_edges,
                     max_shard_vertices=-(-pos // vertex_pad) * vertex_pad,
                     for_n_shards=n)


def launch_of(config: dict) -> tuple[str, dict]:
    """The configuration's ``GraphDJob`` launch and its options
    (``launch``: ``{"mode", "opts"}``; threads when it has none)."""
    spec = config.get("launch") or {}
    return spec.get("mode", "threads"), dict(spec.get("opts") or {})


def make_budget(config: dict, program, graph):
    from repro.core import MemoryBudget

    n = int(config["n_shards"])
    rule = config.get("ram_per_shard")
    if rule is None:
        return MemoryBudget(n_shards=n)
    # the job plans for the graph, then again for the shard size its
    # partition realized: both plans must be the configuration's
    from repro.core.plan import GraphMeta

    want = config["plan"]
    return budget_for(program, [GraphMeta.of(graph),
                                realized_meta(graph, n)], n,
                      rule["below_floor_of"],
                      lambda p: plan_matches(p, want),
                      launch=launch_of(config)[0])


def control_plan(program, graph, budget, config: dict):
    """The plan with the configuration's lower-precision path switched on
    (the ``channel`` knobs of its ``control`` entry): the control that the
    check must fail."""
    from repro.core import plan

    p = plan(program, graph, budget, launch=launch_of(config)[0])
    cfg = dataclasses.replace(p.config, channel=dataclasses.replace(
        p.config.channel, **config["control"]["channel"]))
    cfg.finalize()
    return dataclasses.replace(p, config=cfg)


def values_array(values: dict, labels: np.ndarray) -> tuple[np.ndarray, int]:
    """``JobResult.values`` (by vertex id) as a float64 array in the order
    of ``labels``, the ids of the graph's vertices, and how many of them
    it lacks (those read NaN)."""
    n = labels.size
    order = np.argsort(labels)
    ids = np.fromiter(values.keys(), np.int64, len(values))
    vals = np.fromiter(values.values(), np.float64, len(values))
    j = np.minimum(np.searchsorted(labels[order], ids), n - 1)
    ok = labels[order[j]] == ids
    out = np.full(n, np.nan)
    out[order[j[ok]]] = vals[ok]
    return out, n - int(np.unique(j[ok]).size)


# ---------------------------------------------------------------------------
# the workers of launch="processes"
# ---------------------------------------------------------------------------

#: put first on the ``PYTHONPATH`` of the timed call's workers, so that each
#: imports the probe's ``sitecustomize``
PROBE = os.path.join(BENCH, "probe")
_PROBE_ENV = ("PYTHONPATH", "GRAPHD_PROBE_DIR", "GRAPHD_PROBE_TRACE")


class WorkerProbe:
    """The probe in every worker that ``GraphDJob.run`` spawns inside the
    ``with`` block (the workers inherit this process's environment), and
    what it wrote: :meth:`records`, one dict a worker, after the call."""

    def __init__(self, out_dir: str, trace: bool):
        self.out_dir = out_dir
        self.trace = trace

    def __enter__(self) -> "WorkerProbe":
        self._saved = {k: os.environ.get(k) for k in _PROBE_ENV}
        path = os.environ.get("PYTHONPATH")
        os.environ["PYTHONPATH"] = os.pathsep.join([PROBE] + ([path] if path
                                                              else []))
        os.environ["GRAPHD_PROBE_DIR"] = self.out_dir
        os.environ["GRAPHD_PROBE_TRACE"] = "1" if self.trace else "0"
        return self

    def __exit__(self, *exc) -> None:
        for k, v in self._saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    def records(self, n: int) -> list[dict]:
        out = []
        for w in range(n):
            path = os.path.join(self.out_dir, f"shard-{w}.json")
            if not os.path.isfile(path):
                raise RuntimeError(f"worker {w} left no probe record "
                                   f"({path})")
            with open(path) as f:
                out.append(json.load(f))
        return out


def worker_device(cell: Cell, devices: list) -> dict:
    """The chips of a processes cell as its workers report them
    (``JobResult.devices``); fail unless each is a TPU (no worker falls
    back to the CPU) and there are as many as the cell asks for."""
    platforms = sorted({d["platform"] for d in devices})
    if platforms != ["tpu"]:
        raise NoChip(f"no TPU: the workers found {platforms} devices")
    if len(devices) < cell.chips:
        raise NoChip(f"{cell.name} needs {cell.chips} TPU chips; "
                     f"{len(devices)} workers reported one")
    return dict(platform="tpu", kind=devices[0]["kind"], count=len(devices))


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def process_start() -> float:
    """This process's start, on the wall clock."""
    import psutil

    return psutil.Process().create_time()


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             control: bool = False, device: dict | None = None,
             t_start: float | None = None, cache: str | None = None):
    """Run ``cell`` once; returns ``(result, checks)``: the result line's
    object (without ``checks``) and the numbers compared with their
    limits. ``device`` stands in for the chip's (the tests run on the
    CPU); without it the run opens its TPU once the graph is made, or,
    for a processes cell, takes its workers' chips."""
    import jax

    from repro.core import GraphDJob
    from repro.graph.csr import Graph

    t_start = process_start() if t_start is None else t_start
    cfg, traffic, ref = cell.config, cell.traffic, cell.reference
    launch, launch_opts = launch_of(cfg)
    procs = launch == "processes"
    halts = bool(traffic.get("halts"))
    if halts and procs:
        raise SpecError(f"{cell.name}: a traffic that halts runs in one "
                        "process only: launch=\"processes\" spawns its "
                        "workers anew at every search")
    if control:
        check_control(cell)
    cache = cache or os.path.join(BENCH, ".cache")
    # the benchmark's data: made in a child process, which frees the chip
    # and its host memory before this process opens the chip
    t0 = time.perf_counter()
    ids, src, dst, made = graphgen.load_or_generate(
        cfg["graph"], os.path.join(cache, "graphs"),
        platform=device["platform"] if device else "tpu", log=log)
    labels = graphgen.labels(cfg["graph"], ids, seed)
    graph = Graph(src=labels[src], dst=labels[dst], weight=None,
                  vertex_ids=labels)
    traffic = resolve_sources(traffic, src, dst, labels)
    data_s = time.perf_counter() - t0
    log(f"{cell.name}: graph {made['cache']} {made['seconds']:.3f} s, "
        f"labelled from the seed, {data_s:.3f} s in all (not set-up)")
    if device is None and not procs:
        device = open_device(cell)
    n = labels.size
    program = make_program(traffic, graph.vertex_ids, int(cfg["n_shards"]))
    budget = make_budget(cfg, program, graph)
    work_root = os.path.join(cache, "work")
    os.makedirs(work_root, exist_ok=True)
    workdir = tempfile.mkdtemp(prefix=f"{cell.name}-", dir=work_root)
    clock = CompileClock()
    # a processes cell's probe writes its records (and traces) here
    trace_dir = (tempfile.mkdtemp(
        prefix=f"{'probe' if procs else 'trace'}-{cell.name}-",
        dir=work_root) if trace or procs else None)
    try:
        t0 = time.perf_counter()
        ctl = cfg["control"] if control else {}
        if "channel" in ctl:
            job = GraphDJob(program, graph, workdir=workdir,
                            plan=control_plan(program, graph, budget, cfg),
                            launch=launch, launch_opts=launch_opts)
        else:
            job = GraphDJob(program, graph, budget=budget, workdir=workdir,
                            launch=launch, launch_opts=launch_opts)
        partition_s = time.perf_counter() - t0
        p = job.plan
        log(f"{cell.name}: plan mode={p.mode} pipeline={p.pipeline} "
            f"full_duplex={p.config.channel.full_duplex} "
            f"compress_payload={p.config.channel.compress_payload} "
            f"n_shards={p.n_shards} ram_per_shard={budget.ram_per_shard} "
            f"launch={launch}; partition {partition_s:.3f} s; edge store "
            f"on disk "
            f"{job.store.disk_bytes() if job.store is not None else 0} B")
        if not ctl and not plan_matches(p, cfg["plan"]):
            raise SpecError(f"{cell.name}: the planner chose mode={p.mode} "
                            f"pipeline={p.pipeline} full_duplex="
                            f"{p.config.channel.full_duplex}, the config "
                            f"asks for {cfg['plan']}")
        # warm-up: every program compiles or loads from the cache here
        compiled0 = clock.seconds
        if halts:
            cap = n  # supersteps: no search goes deeper than |V| levels
            # one whole search: every superstep the timed searches run
            t0 = time.perf_counter()
            _, warm_history = job.engine.run(max_supersteps=cap)
            warm_s = time.perf_counter() - t0
            # the search less its compiles, floored as for a superstep
            step_est = max(warm_s - (clock.seconds - compiled0),
                           0.25 * warm_s)
            k = max(1, round(seconds / step_est))
            unit = "search"
        else:
            warm_steps = 2 if procs else 1
            warm = job.run(max_supersteps=warm_steps)
            warm_history = warm.history
            if procs:
                # the first superstep spawned the workers: the second is a
                # superstep alone
                step_est = warm_history[1].seconds
                if device is None:
                    device = worker_device(cell, warm.devices)
            else:
                # the warm-up superstep less its compiles; the floor keeps K
                # from growing past a few times the window where they
                # overlap
                step_est = max(warm_history[0].seconds
                               - (clock.seconds - compiled0),
                               0.25 * warm_history[0].seconds)
            k = max(2, round(seconds / step_est))
            unit = "superstep"
        first_step_s = warm_history[0].seconds
        log(f"{cell.name}: set-up built {clock.compiles} program(s), "
            f"{clock.misses} compiled anew "
            f"({'a cold run' if clock.misses else 'all from the cache'}), "
            f"{clock.seconds:.3f} s of tracing, lowering and compiling; "
            f"warm-up supersteps "
            f"{[round(r.seconds, 3) for r in warm_history]} s, {unit} "
            f"estimate {step_est:.3f} s -> K={k}")

        stamps = []  # (perf_counter, wall clock, record) per superstep

        def on_step(rec, _state):
            with jax.profiler.TraceAnnotation(devtrace.STEP_SPAN):
                stamps.append((time.perf_counter(), time.time(), rec))

        probe = WorkerProbe(trace_dir, trace) if procs else None
        if trace and not procs:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        compiles0 = clock.compiles
        searches = []  # halts: (values, history, channel stats) a search
        t_call, wall_call = time.perf_counter(), time.time()
        try:
            with (probe or contextlib.nullcontext()), \
                    jax.profiler.TraceAnnotation(devtrace.RUN_SPAN):
                if halts:
                    for _ in range(k):
                        (values, _), history = job.engine.run(
                            max_supersteps=cap, on_step=on_step)
                        stats = getattr(job.engine, "channel_stats", None)
                        searches.append((values, history, dataclasses.asdict(
                            stats) if stats else None))
                else:
                    # a processes call runs one more superstep: its first,
                    # the spawn
                    target = warm_steps + int(procs) + k
                    res = job.run(max_supersteps=target, on_step=on_step)
        finally:
            if trace and not procs:
                jax.profiler.stop_trace()
        if procs:
            (t0, wall0, _), timed = stamps[0], stamps[1:]
        else:
            t0, wall0, timed = t_call, wall_call, stamps
        window = [r for _, _, r in timed]
        window_s = stamps[-1][0] - t0
        setup_s = wall0 - t_start - data_s
        if clock.compiles > compiles0:
            log(f"{cell.name}: WARNING {clock.compiles - compiles0} "
                "compile(s) inside the window")
        spawn_s = barrier_waits = workers = None
        if procs:
            ends = [t for t, _, _ in stamps]
            spawn_s = (ends[0] - t_call) - statistics.median(
                b - a for a, b in zip(ends, ends[1:]))
            workers = probe.records(p.n_shards)
            peaks = [w["peak_bytes_in_use"] for w in workers]
            device = dict(device, memory_peak_bytes=max(
                (b for b in peaks if b is not None), default=None),
                memory_peak_bytes_per_chip=peaks)
            in_window = {r.step for r in window}
            for w in workers:
                timed_steps = {step for step, _, _ in w["barrier"]}
                if not in_window <= timed_steps:
                    raise RuntimeError(
                        f"worker {w['shard']} timed no barrier wait in "
                        f"supersteps {sorted(in_window - timed_steps)}: the "
                        "probe found no FileCoordinator.wait_commit to time")
            barrier_waits = [sec for w in workers
                             for step, _, sec in w["barrier"]
                             if step in in_window]
            built = sum(len(w["compiles"]) for w in workers)
            late = sum(t > wall0 for w in workers for t, _ in w["compiles"])
            misses = sum(len(w["cache_misses"]) for w in workers)
            log(f"{cell.name}: the timed call's workers built {built} "
                f"program(s), {misses} compiled anew; spawn and first "
                f"superstep {ends[0] - t_call:.3f} s")
            if trace:
                log(f"{cell.name}: the workers wrote their traces in "
                    f"{[w['trace_stop_s'] for w in workers]} s")
            if late:
                log(f"{cell.name}: WARNING {late} compile(s) inside the "
                    "window, in the workers")
            channel = None
        else:
            stats = jax.devices()[0].memory_stats() or {}
            device = dict(device, memory_peak_bytes=stats.get(
                "peak_bytes_in_use"))
            if halts:
                # each search's run starts the channel's stats afresh
                per_search = [c for _, _, c in searches if c is not None]
                channel = ({key: sum(c[key] for c in per_search)
                            for key, v in per_search[0].items()
                            if isinstance(v, (int, float))}
                           if per_search else None)
            else:
                stats = getattr(job.engine, "channel_stats", None)
                channel = dataclasses.asdict(stats) if stats else None
        if halts:
            steps = len(warm_history) + len(window)
            answers = [values_array(job.engine.gather_values(values),
                                    labels) + (len(history),)
                       for values, history, _ in searches]
            del searches
        else:
            steps = len(warm_history) + len(res.history)
            got, missing = values_array(res.values, labels)
            del res
        job.close(delete=True)
        del job
        summary = None
        if trace and procs:
            t0 = time.perf_counter()
            summary = devtrace.summarize_workers(
                [w["trace_dir"] for w in workers], wall0, stamps[-1][1])
            log(f"{cell.name}: read the workers' traces in "
                f"{time.perf_counter() - t0:.3f} s")
        elif trace:
            summary = devtrace.summarize_dir(trace_dir)
    finally:
        for d in (workdir, trace_dir):
            if d:
                shutil.rmtree(d, ignore_errors=True)

    # the check, after the window and with the job released
    t0 = time.perf_counter()
    limits = dict(traffic["limits"], missing_vertices=0,
                  missing_supersteps=0)
    if halts:
        # every timed search, against the search to its fixpoint
        ref_steps = ref.supersteps(src, dst, n, traffic)
        want = ref.run(src, dst, n, traffic, ref_steps)
        readings = [dict(ref.compare(got, want, traffic),
                         missing_vertices=missing,
                         missing_supersteps=abs(ref_steps - taken))
                    for got, missing, taken in answers]
        attempted = len(readings)
    else:
        ref_steps = steps
        want = ref.run(src, dst, n, traffic, steps)
        if "reference" in ctl:
            # the reference one precision lower, in the program's place
            got = ref.run(src, dst, n, traffic, steps,
                          precision=ctl["reference"])
        readings = [dict(ref.compare(got, want, traffic),
                         missing_vertices=missing,
                         missing_supersteps=target - steps)]
        attempted = steps
    checks = {key: dict(value=max(r[key] for r in readings), limit=lim)
              for key, lim in limits.items()}
    bad = sum(any(not r[key] <= lim for key, lim in limits.items())
              for r in readings)
    correct = bad == 0
    failed = (bad if halts else attempted) if bad else 0
    log(f"{cell.name}: reference {ref_steps} supersteps in "
        f"{time.perf_counter() - t0:.3f} s")

    per_step = ref.active_edges(src, dst, n, traffic, ref_steps)
    run = RunRecord(
        cell=cell, n_vertices=n, setup_s=setup_s,
        partition_s=partition_s, first_step_s=first_step_s,
        window_s=window_s, window=window,
        window_edges=int(sum(per_step[r.step] for r in window
                             if r.step < len(per_step))),
        plan_mode=p.mode, channel_stats=channel, trace=summary,
        device_kind=device["kind"], spawn_s=spawn_s,
        barrier_waits=barrier_waits)
    metrics = {}
    for entry, reader in (cell.per_layer if trace else cell.end_to_end):
        v = reader.read(run)
        if v is not None:
            metrics[entry["name"]] = dict(value=v, unit=entry["unit"])
    result = dict(correct=bool(correct), attempted=attempted, failed=failed,
                  metrics=metrics, device=device)
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = dict(device_ops=summary.device_ops,
                                   idle_gaps=summary.idle_gaps)
    log(f"{cell.name}: K={k} window {window_s:.3f} s, set-up "
        f"{setup_s:.3f} s")
    return result, checks
