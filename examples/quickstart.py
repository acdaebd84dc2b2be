"""Quickstart: PageRank with the declarative job API.

One call owns the whole lifecycle — the planner picks the execution mode
(in-memory recoded vs out-of-core streamed vs §4 pipelined) and sizes every
staging/window knob from the memory budget; the job partitions (spilling
edge streams to disk when the plan says so), runs, and hands back a
structured result.

    PYTHONPATH=src python examples/quickstart.py
"""

from repro.compile_cache import use_compile_cache
from repro.core import GraphDJob, MemoryBudget, PageRank, plan
from repro.graph import rmat_graph

use_compile_cache()

# 1. load a graph (here: generated; loaders accept any edge list with
#    arbitrary 64-bit vertex ids — the recoding pass densifies them)
graph = rmat_graph(scale=12, edge_factor=16, seed=0, sparse_ids=True)
print(f"graph: |V|={graph.n_vertices:,} |E|={graph.n_edges:,}")

# 2. describe the machines, not the physical plan: 8 "machines", 256 KiB of
#    RAM each. The planner chooses the mode and derives the knobs — ask it
#    to explain itself before committing anything to disk.
budget = MemoryBudget(ram_per_shard=256 << 10, n_shards=8)
print(plan(PageRank(supersteps=10), graph, budget).explain(), "\n")

# 3. run the job (partition -> spill if needed -> engine -> supersteps)
with GraphDJob(PageRank(supersteps=10), graph, budget=budget) as job:
    result = job.run(verbose=True)

# 4. results, keyed by the original vertex ids, plus the audit trail
ranks = result.values
top = sorted(ranks.items(), key=lambda kv: -kv[1])[:5]
print("top-5 vertices by PageRank:")
for vid, r in top:
    print(f"  vertex {vid}: {r:.6f}")
print(f"rank mass: {sum(ranks.values()):.4f}")
s = result.summary()  # JSON-able: what was planned, what actually ran
print(f"mode={s['mode']} planned_ram={s['planned']['ram']}B "
      f"realized_ram={s['realized']['ram']}B "
      f"({s['n_supersteps']} supersteps)")
