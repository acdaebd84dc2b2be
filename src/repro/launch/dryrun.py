import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run (deliverable e).

Lowers + compiles every (architecture × input shape) cell on the single-pod
(16, 16) and multi-pod (2, 16, 16) production meshes with pure
ShapeDtypeStruct inputs (zero allocation), then records:

* memory_analysis()  — proves the cell fits per-chip HBM,
* cost_analysis()    — per-chip HLO FLOPs / bytes for §Roofline,
* collective op bytes parsed from the post-SPMD HLO (launch/roofline.py).

The 11th config is the paper's own system: a 256-shard GraphD PageRank
superstep over a ClueWeb-scale abstract graph.

Usage:
  python -m repro.launch.dryrun --arch minitron-4b --shape train_4k
  python -m repro.launch.dryrun --all [--multipod] [--out results.json]
  python -m repro.launch.dryrun --graphd [--multipod]
"""

import argparse
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, cell_supported, get_config
from repro.data.tokens import batch_specs
from repro.launch.mesh import (
    batch_specs_tree, cache_specs_tree, dp_axes, make_production_mesh,
    param_specs, to_shardings,
)
from repro.launch.roofline import collective_bytes_from_text, roofline_terms
from repro.models.transformer import abstract_params
from repro.serving.cache import abstract_caches
from repro.serving.engine import decode_step, prefill
from repro.training.optimizer import AdamWConfig
from repro.training.train import make_train_step

WHISPER_SELF_LEN = 448  # decoder context; cross-KV covers `seq_len` frames


def _opt_state_abstract(params_abs, grad_compress: bool):
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
    opt = dict(
        mu=jax.tree.map(f32, params_abs),
        nu=jax.tree.map(f32, params_abs),
        step=jax.ShapeDtypeStruct((), jnp.int32),
    )
    if grad_compress:
        opt["err"] = jax.tree.map(f32, params_abs)
    return opt


def _media_spec(cfg, B, seq_len):
    n_media = cfg.n_media_tokens
    if cfg.family == "audio":
        n_media = seq_len  # encoder frames = the shape's sequence length
    return jax.ShapeDtypeStruct((B, n_media, cfg.d_model), cfg.dtype), n_media


def lower_cell(arch: str, shape: str, mesh, cfg=None, opt_cfg=None,
               param_mode: str = "train"):
    """Build (fn, arg_specs, in_shardings, out_shardings) and lower+compile.

    param_mode="serve" switches to weight-stationary TP specs (§Perf)."""
    cfg = cfg or get_config(arch)
    info = SHAPES[shape]
    S, B, kind = info["seq_len"], info["global_batch"], info["kind"]

    params_abs = abstract_params(cfg)
    pspecs = param_specs(params_abs, mesh, mode=param_mode)

    if kind == "train":
        step_fn = make_train_step(cfg, opt_cfg or AdamWConfig())
        opt_abs = _opt_state_abstract(params_abs, cfg.grad_compress)
        ospecs = dict(
            mu=param_specs(params_abs, mesh, mode=param_mode),
            nu=param_specs(params_abs, mesh, mode=param_mode),
            step=P(),
        )
        if cfg.grad_compress:
            ospecs["err"] = param_specs(params_abs, mesh, mode=param_mode)
        batch_abs = batch_specs(cfg, S, B)
        if cfg.family == "audio":
            media, _ = _media_spec(cfg, B, S)
            batch_abs["media"] = media
        bspecs = batch_specs_tree(batch_abs, mesh)
        in_shard = to_shardings((pspecs, ospecs, bspecs), mesh)
        out_shard = to_shardings(
            (pspecs, ospecs, jax.tree.map(lambda _: P(), dict(
                loss=0, aux=0, grad_norm=0, lr=0))), mesh
        )
        fn = jax.jit(step_fn, in_shardings=in_shard,
                     out_shardings=out_shard)
        args = (params_abs, opt_abs, batch_abs)

    elif kind == "prefill":
        tok_len = WHISPER_SELF_LEN if cfg.family == "audio" else S
        caches_abs = abstract_caches(
            cfg, B, max_len=tok_len,
            n_media=S if cfg.family == "audio" else None,
        )
        cspecs = cache_specs_tree(caches_abs, mesh)
        toks = jax.ShapeDtypeStruct((B, tok_len), jnp.int32)
        tspec = batch_specs_tree(toks, mesh)
        args_list = [params_abs, toks, caches_abs]
        in_list = [pspecs, tspec, cspecs]
        if cfg.family in ("audio", "vlm"):
            media, _ = _media_spec(cfg, B, S)
            args_list.append(media)
            in_list.append(batch_specs_tree(media, mesh))
        fn = jax.jit(
            functools.partial(prefill, cfg),
            in_shardings=to_shardings(tuple(in_list), mesh),
            out_shardings=to_shardings(
                (batch_specs_tree(
                    jax.ShapeDtypeStruct((B, cfg.vocab), jnp.float32), mesh
                ), cspecs), mesh),
        )
        args = tuple(args_list)

    else:  # decode
        self_len = WHISPER_SELF_LEN if cfg.family == "audio" else S
        caches_abs = abstract_caches(
            cfg, B, max_len=self_len,
            n_media=S if cfg.family == "audio" else None,
        )
        cspecs = cache_specs_tree(caches_abs, mesh)
        tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        fn = jax.jit(
            functools.partial(decode_step, cfg),
            in_shardings=to_shardings(
                (pspecs, cspecs, batch_specs_tree(tok, mesh), P()), mesh
            ),
            out_shardings=to_shardings(
                (batch_specs_tree(
                    jax.ShapeDtypeStruct((B, cfg.vocab), jnp.float32), mesh
                ), cspecs), mesh),
        )
        args = (params_abs, caches_abs, tok, pos)

    from repro.models.sharding import rules

    dp = dp_axes(mesh)
    seq = "model" if cfg.seq_shard else None
    with rules(batch=dp if len(dp) > 1 else dp[0], model="model", seq=seq,
               mesh=mesh):
        t0 = time.perf_counter()
        lowered = fn.lower(*args)
        t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    return lowered, compiled, dict(
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1)
    )


def _cost_of(compiled):
    cost = compiled.cost_analysis()
    coll = collective_bytes_from_text(compiled.as_text())
    return dict(
        flops=cost.get("flops", 0.0),
        bytes=cost.get("bytes accessed", 0.0),
        coll=coll["total"],
        coll_by_op=coll["by_op"],
    )


def _extrapolate(c1, c2, G: int):
    """Depth-linear extrapolation from unrolled 1- and 2-group compiles:
    total(G) = base + G * per_group with base = 2*c1 - c2."""
    out = {}
    for k in ("flops", "bytes", "coll"):
        per_group = max(c2[k] - c1[k], 0.0)
        base = max(c1[k] - per_group, 0.0)
        out[k] = base + G * per_group
    out["coll_by_op"] = {
        op: max(c1["coll_by_op"].get(op, 0)
                + (G - 1) * max(c2["coll_by_op"].get(op, 0)
                                - c1["coll_by_op"].get(op, 0), 0), 0)
        for op in set(c1["coll_by_op"]) | set(c2["coll_by_op"])
    }
    return out


def analyze(arch, shape, mesh_name, mesh, compiled, cfg, times,
            param_mode="train"):
    """Full-model compile proves the cell; 1- and 2-group unrolled compiles
    recover exact depth-linear cost terms (scan bodies are counted once by
    XLA's cost analysis — verified empirically)."""
    n_chips = 512 if mesh_name == "multipod" else 256
    info = SHAPES[shape]
    mem = compiled.memory_analysis()

    G = cfg.n_pattern_groups
    _, comp1, _ = lower_cell(arch, shape, mesh, cfg=cfg.with_groups(1),
                             param_mode=param_mode)
    _, comp2, _ = lower_cell(arch, shape, mesh, cfg=cfg.with_groups(2),
                             param_mode=param_mode)
    cost = _extrapolate(_cost_of(comp1), _cost_of(comp2), G)

    terms = roofline_terms(
        cfg, info, flops=cost["flops"], bytes_accessed=cost["bytes"],
        collective_bytes=cost["coll"], n_chips=n_chips,
    )
    arg_bytes = getattr(mem, "argument_size_in_bytes", 0)
    rec = dict(
        arch=arch, shape=shape, mesh=mesh_name, ok=True,
        flops_per_chip=cost["flops"],
        bytes_per_chip=cost["bytes"],
        collective_bytes_per_chip=cost["coll"],
        collective_breakdown=cost["coll_by_op"],
        argument_bytes=arg_bytes,
        output_bytes=getattr(mem, "output_size_in_bytes", 0),
        cpu_temp_bytes=getattr(mem, "temp_size_in_bytes", 0),
        # modeled TPU-resident bytes: sharded args (exact) + remat
        # checkpoints + one layer's working set (documented in EXPERIMENTS)
        peak_bytes_model=arg_bytes + _activation_model_bytes(cfg, info,
                                                             n_chips),
        **times,
        **terms,
    )
    return rec


def _activation_model_bytes(cfg, info, n_chips: int) -> int:
    """Remat activation model: G checkpointed layer inputs + ~4 working
    buffers of one pattern group, batch/seq sharded across the mesh."""
    S, B, kind = info["seq_len"], info["global_batch"], info["kind"]
    if kind != "train":
        S_act = 1 if kind == "decode" else S
    else:
        S_act = S
    tokens_per_chip = max(B * S_act // n_chips, 1)
    a = tokens_per_chip * cfg.d_model * 2  # bf16 layer input
    G = cfg.n_pattern_groups
    work = 4 * a * len(cfg.pattern) + tokens_per_chip * max(
        cfg.d_ff, cfg.moe_dff, cfg.d_ssm_inner if cfg.ssm_state else 0, 1
    ) * 2
    logits = tokens_per_chip * cfg.vocab * 4 // 16  # vocab TP-sharded
    return int(G * a + work + logits)


def run_cell(arch: str, shape: str, multi_pod: bool, cfg=None,
             param_mode: str = "train", variant: str = ""):
    mesh_name = "multipod" if multi_pod else "singlepod"
    ok, why = cell_supported(arch, shape)
    if not ok:
        return dict(arch=arch, shape=shape, mesh=mesh_name, ok=False,
                    skipped=True, reason=why)
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = cfg or get_config(arch)
    with mesh:
        lowered, compiled, times = lower_cell(arch, shape, mesh, cfg=cfg,
                                              param_mode=param_mode)
        rec = analyze(arch, shape, mesh_name, mesh, compiled, cfg, times,
                      param_mode=param_mode)
    if variant:
        rec["variant"] = variant
    return rec


# ---------------------------------------------------------------------------
# GraphD (the paper's system) as the 11th dry-run config
# ---------------------------------------------------------------------------

def run_graphd_cell(multi_pod: bool, scale: str = "clueweb",
                    mode: str = "recoded", edge_block: int = 4096,
                    variant: str = ""):
    """One PageRank superstep on a web-scale abstract graph, sharded over
    all chips (the pod is a flat ring of 'machines'). ``mode`` selects the
    exchange (recoded ring / recoded_compact all_to_all / basic)."""
    from repro.core.algorithms import PageRank
    from repro.core.engine import superstep_spmd
    from repro.graph.partition import abstract_partitioned_graph

    sizes = dict(
        clueweb=(978_408_098, 42_574_107_469),  # Table 1
        webuk=(133_633_040, 5_507_679_822),
    )
    import numpy as np

    V, E = sizes[scale]
    n = 512 if multi_pod else 256
    # the paper's |W| machines form a flat ring: no 2-D structure
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:n]), ("machines",))

    pg = abstract_partitioned_graph(n, V, E, edge_block=edge_block,
                                    vertex_pad=512)
    prog = PageRank(supersteps=10)
    axis = "machines"

    def step(pg_, v, a, s):
        sq = lambda t: jax.tree.map(lambda x: x[0], t)
        nv, na, st = superstep_spmd(
            prog, sq(pg_), sq(v), sq(a), s, axis=axis, mode=mode
        )
        return nv[None], na[None], st

    spec = P(axis)
    fn = jax.shard_map(
        step, mesh=mesh,
        in_specs=(spec, spec, spec, P()),
        out_specs=(spec, spec, P()),
    )
    vals = jax.ShapeDtypeStruct((n, pg.P), jnp.float32)
    act = jax.ShapeDtypeStruct((n, pg.P), jnp.bool_)
    stp = jax.ShapeDtypeStruct((), jnp.int32)
    shard = NamedSharding(mesh, P(axis))
    rep = NamedSharding(mesh, P())
    jfn = jax.jit(
        fn,
        in_shardings=(jax.tree.map(lambda _: shard, pg), shard, shard, rep),
    )
    t0 = time.perf_counter()
    lowered = jfn.lower(pg, vals, act, stp)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    coll = collective_bytes_from_text(compiled.as_text())
    terms = roofline_terms(
        None, dict(kind="graphd", seq_len=0, global_batch=0),
        flops=cost.get("flops", 0.0),
        bytes_accessed=cost.get("bytes accessed", 0.0),
        collective_bytes=coll["total"], n_chips=n,
        graphd=dict(V=V, E=E, n=n),
    )
    return dict(
        arch=f"graphd-pagerank-{scale}", shape="superstep",
        variant=variant, mode=mode, edge_block=edge_block,
        mesh="multipod" if multi_pod else "singlepod", ok=True,
        flops_per_chip=cost.get("flops", 0.0),
        bytes_per_chip=cost.get("bytes accessed", 0.0),
        collective_bytes_per_chip=coll["total"],
        collective_breakdown=coll["by_op"],
        argument_bytes=getattr(mem, "argument_size_in_bytes", 0),
        temp_bytes=getattr(mem, "temp_size_in_bytes", 0),
        peak_bytes=(
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0)
        ),
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        **terms,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--graphd", action="store_true")
    ap.add_argument("--out", default="dryrun_results.json")
    args = ap.parse_args()

    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results}

    def record(rec):
        results[:] = [
            r for r in results
            if (r["arch"], r["shape"], r["mesh"])
            != (rec["arch"], rec["shape"], rec["mesh"])
        ]
        results.append(rec)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)

    def one(arch, shape, multi):
        mesh_name = "multipod" if multi else "singlepod"
        key = (arch, shape, mesh_name)
        if key in done:
            print(f"[skip] {key} already done")
            return
        print(f"[dryrun] {arch} x {shape} on {mesh_name} ...", flush=True)
        try:
            rec = run_cell(arch, shape, multi)
        except Exception as e:
            traceback.print_exc()
            rec = dict(arch=arch, shape=shape, mesh=mesh_name, ok=False,
                       error=f"{type(e).__name__}: {e}")
        record(rec)
        status = "OK" if rec.get("ok") else (
            "SKIP" if rec.get("skipped") else "FAIL")
        print(f"  -> {status} "
              f"(compile {rec.get('compile_s', '-')}s, "
              f"peak {rec.get('peak_bytes', 0)/2**30:.2f} GiB/chip)",
              flush=True)

    if args.graphd:
        rec = run_graphd_cell(args.multipod)
        record(rec)
        print(json.dumps(rec, indent=1))
        return
    if args.all:
        for arch in ARCHS:
            for shape in SHAPES:
                one(arch, shape, args.multipod)
        return
    assert args.arch and args.shape, "--arch/--shape or --all"
    one(args.arch, args.shape, args.multipod)


if __name__ == "__main__":
    main()
