"""MoE routing demo: the GraphD message-combining pattern applied to tokens
(DESIGN.md §Arch-applicability). Shows expert load distribution, capacity
drops, and the load-balance aux loss on a reduced qwen3-moe config.

    PYTHONPATH=src python examples/moe_expert_stats.py
"""

import jax
import jax.numpy as jnp

from repro.compile_cache import use_compile_cache
from repro.configs import get_config
from repro.data.tokens import synthetic_batch
from repro.models.moe import moe_ffn
from repro.models.transformer import init_params

use_compile_cache()
cfg = get_config("qwen3-moe-235b-a22b").reduced()
params = init_params(cfg, jax.random.key(0))
moe_params = jax.tree.map(lambda p: p[0], params["groups"][0]["ffn"])

batch = synthetic_batch(cfg, 0, seq_len=64, global_batch=4)
x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(cfg.dtype)

y, (aux, dropped) = moe_ffn(
    moe_params, x, n_experts=cfg.n_experts, topk=cfg.topk,
    capacity_factor=cfg.capacity_factor, n_shared=cfg.n_shared_experts,
)
print(f"moe: {cfg.n_experts} experts, top-{cfg.topk}")
print(f"  output shape      : {y.shape}")
print(f"  load-balance aux  : {float(aux):.4f} (1.0 = perfectly balanced)")
print(f"  capacity drops    : {float(dropped)*100:.2f}%")

logits = jnp.einsum("td,de->te",
                    x.reshape(-1, cfg.d_model).astype(jnp.float32),
                    moe_params["router"].astype(jnp.float32))
_, eidx = jax.lax.top_k(jax.nn.softmax(logits), cfg.topk)
load = jnp.bincount(eidx.reshape(-1), length=cfg.n_experts)
print(f"  expert load       : min={int(load.min())} max={int(load.max())} "
      f"mean={float(load.mean()):.1f}")
print("  (tokens = messages, experts = vertices, top-k routing = message "
      "sending, weighted sum = the SUM combiner)")
