"""The benchmark's probe inside each ``launch="processes"`` worker.

Only the process that holds a chip can read its memory or trace it, and a
worker of ``launch="processes"`` holds its chip alone. So for its timed
call the harness puts this directory first on the workers' ``PYTHONPATH``
and names an output directory in ``GRAPHD_PROBE_DIR``; Python imports this
file at each worker's start. In a worker (``python -m repro.launch.procs
worker <spec_dir> <shard>``) it starts one thread that waits, polling,
until the worker has opened its device, and then

* records the worker's compiles (JAX's monitoring events, wall-clock
  stamped), so that the harness can tell whether one fell in the window;
* times each barrier wait, ``FileCoordinator.wait_commit`` (from the
  worker's ``arrive`` to the commit that releases it), on the host clock,
  under a trace span of its own: the span that marks the worker's main
  thread in the trace;
* with ``GRAPHD_PROBE_TRACE=1``, traces the chip (python tracer off) into
  ``<dir>/shard-<w>``, and stops the trace when the commit that halts the
  job releases the worker: before it writes its result, since the launcher
  kills a worker that takes more than 30 s to exit after the results are
  in, and writing a trace of a long call takes a while;
* at exit, before JAX's own exit handlers, reads the chip's
  ``peak_bytes_in_use`` and writes ``<dir>/shard-<w>.json``.

It does nothing in any other process, and changes no result of the worker.
A ``sitecustomize`` that this file hides on the path runs after it.

The probe stands in for records the program does not keep yet: it replaces
the worker's ``FileCoordinator.wait_commit`` and finds the worker by its
command line. Where either is gone, the worker leaves no barrier wait, and
the harness stops the run rather than report without one.
"""

import os
import sys

BARRIER_SPAN = "bench.barrier_wait"
_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


def _worker_shard():
    argv = getattr(sys, "orig_argv", [])
    try:
        i = argv.index("repro.launch.procs")
    except ValueError:
        return None
    rest = argv[i + 1:]
    if argv[i - 1] != "-m" or len(rest) < 3 or rest[0] != "worker":
        return None
    return int(rest[2])


class _Probe:
    def __init__(self, out_dir: str, shard: int, trace: bool):
        self.out_dir = out_dir
        self.shard = shard
        self.trace_dir = (os.path.join(out_dir, f"shard-{shard}")
                          if trace else None)
        self.compiles = []  # [wall time, seconds] of each backend compile
        self.cache_misses = []  # wall time of each persistent-cache miss
        self.barrier = []  # [step, wall start, seconds] of each wait
        self.tracing = False
        self.trace_stop_s = None  # seconds that writing the trace took

    def watch(self) -> None:
        import time

        while True:
            # a backend opens only once jax has finished importing
            ready = getattr(sys.modules.get("jax._src.xla_bridge"),
                            "backends_are_initialized", None)
            if ready is not None and ready():
                break
            time.sleep(0.02)
        import atexit

        import jax
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        self._time_barrier()
        if self.trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.tracing = True
        # registered after JAX's own exit handlers, so it runs before them
        atexit.register(self.finish)

    def _on_duration(self, event, duration, **_kw):
        if event in _COMPILE_EVENTS:
            import time

            self.compiles.append([time.time(), duration])

    def _on_event(self, event, **_kw):
        if event == _CACHE_MISS:
            import time

            self.cache_misses.append(time.time())

    def _time_barrier(self) -> None:
        import time

        from jax.profiler import TraceAnnotation
        from repro.core.coordinator import FileCoordinator

        wait = FileCoordinator.wait_commit
        probe = self

        def timed(coord, step, shard):
            wall, t0 = time.time(), time.perf_counter()
            with TraceAnnotation(BARRIER_SPAN):
                out = wait(coord, step, shard)
            probe.barrier.append([int(step), wall, time.perf_counter() - t0])
            if out.get("halt"):
                probe.stop_trace()
            return out

        FileCoordinator.wait_commit = timed

    def stop_trace(self) -> None:
        if not self.tracing:
            return
        import time

        import jax

        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.tracing = False
        self.trace_stop_s = time.perf_counter() - t0

    def finish(self) -> None:
        import json

        import jax

        d = jax.devices()[0]
        stats = d.memory_stats() or {}
        self.stop_trace()  # a job that never halted
        rec = dict(shard=self.shard, pid=os.getpid(), platform=d.platform,
                   kind=d.device_kind,
                   peak_bytes_in_use=stats.get("peak_bytes_in_use"),
                   compiles=self.compiles, cache_misses=self.cache_misses,
                   barrier=self.barrier, trace_dir=self.trace_dir,
                   trace_stop_s=self.trace_stop_s)
        path = os.path.join(self.out_dir, f"shard-{self.shard}.json")
        with open(path + ".tmp", "w") as f:
            json.dump(rec, f)
        os.replace(path + ".tmp", path)


def _start() -> None:
    out_dir = os.environ.get("GRAPHD_PROBE_DIR")
    shard = _worker_shard() if out_dir else None
    if shard is None:
        return
    import threading

    probe = _Probe(out_dir, shard, os.environ.get("GRAPHD_PROBE_TRACE") == "1")
    threading.Thread(target=probe.watch, name="bench-probe",
                     daemon=True).start()


def _chain() -> None:
    """Run the next ``sitecustomize`` on the path after the one that Python
    imported (this file, or a file that ran it)."""
    import importlib.machinery
    import runpy

    spec = getattr(sys.modules.get("sitecustomize"), "__spec__", None)
    if spec is None or not spec.origin:
        return
    first = os.path.dirname(os.path.abspath(spec.origin))
    entries = [os.path.abspath(p or os.curdir) for p in sys.path]
    if first not in entries:
        return
    i = entries.index(first)
    rest = [p for p, e in zip(sys.path[i + 1:], entries[i + 1:])
            if e != first]
    nxt = importlib.machinery.PathFinder.find_spec("sitecustomize", rest)
    if nxt is not None and nxt.origin and os.path.abspath(
            nxt.origin) != os.path.abspath(__file__):
        runpy.run_path(nxt.origin, run_name="sitecustomize")


_start()
_chain()
