"""DLRM (MLPerf config) — arXiv:1906.00091.

The hot path is the sparse embedding lookup.  JAX has no EmbeddingBag, so
it is built here from ``jnp.take`` + ``jax.ops.segment_sum`` (the brief's
required construction).  Large tables are *row-sharded* over the mesh
``model`` axis and looked up with the S2-style demand-driven pattern
(DESIGN.md §5): every shard answers for the rows it owns (masked local
take), answers are psum-combined — a single collective per bag instead of
gathering tables.  Small tables are replicated per
``planner.embedding_placement`` (the paper's replicate-vs-shard rule).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as shd
from repro.core.planner import embedding_placement
from repro.training import optimizer as opt_lib

# Criteo-1TB per-field vocabulary sizes (MLPerc DLRM reference).
CRITEO_TABLE_SIZES = [
    39884406, 39043, 17289, 7420, 20263, 3, 7120, 1543, 63, 38532951,
    2953546, 403346, 10, 2208, 11938, 155, 4, 976, 14, 39979771,
    25641295, 39664984, 585935, 12972, 108, 36,
]


@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    name: str = "dlrm-mlperf"
    n_dense: int = 13
    n_sparse: int = 26
    embed_dim: int = 128
    bot_mlp: tuple[int, ...] = (512, 256, 128)
    top_mlp: tuple[int, ...] = (1024, 1024, 512, 256, 1)
    table_sizes: tuple[int, ...] = tuple(CRITEO_TABLE_SIZES)
    multi_hot: int = 1  # lookups per field (bag size)
    optimizer: str = "adamw"
    dtype: Any = jnp.float32
    # §Perf iteration 2: bf16 embedding tables halve the table-gradient
    # all-reduce (the dominant collective) and table HBM; AdamW moments
    # stay f32 (master precision in the optimizer state).
    table_dtype: Any = jnp.bfloat16

    @property
    def padded_table_sizes(self) -> tuple[int, ...]:
        """Row counts padded to 512 so row-sharding divides any mesh axis
        (padding rows are never indexed: data ids stay < true size)."""
        return tuple(-(-r // 512) * 512 if r > 512 else r for r in self.table_sizes)

    def table_modes(self, n_devices: int, batch: int) -> list[str]:
        """Per-table replicate/shard decision via the paper's rule."""
        return [
            embedding_placement(rows, self.embed_dim, batch * self.multi_hot, n_devices).mode
            for rows in self.table_sizes
        ]


def _mlp_init(key, sizes, dtype):
    layers = []
    for a, b in zip(sizes[:-1], sizes[1:]):
        key, k = jax.random.split(key)
        layers.append(
            {
                "w": (jax.random.normal(k, (a, b)) / math.sqrt(a)).astype(dtype),
                "b": jnp.zeros((b,), dtype),
            }
        )
    return layers


def _mlp_apply(layers, x, final_act=None):
    for i, l in enumerate(layers):
        x = x @ l["w"] + l["b"]
        if i + 1 < len(layers):
            x = jax.nn.relu(x)
    if final_act is not None:
        x = final_act(x)
    return x


def init_params(cfg: DLRMConfig, key) -> dict:
    kb, kt, ke = jax.random.split(key, 3)
    tables = {}
    for i, rows in enumerate(cfg.padded_table_sizes):
        ke, k = jax.random.split(ke)
        tables[f"t{i}"] = (
            jax.random.normal(k, (rows, cfg.embed_dim)) / math.sqrt(cfg.embed_dim)
        ).astype(cfg.table_dtype)
    n_int = (cfg.n_sparse + 1) * cfg.n_sparse // 2  # upper-triangle pairs incl. dense
    top_in = n_int + cfg.bot_mlp[-1]
    return {
        "bot": _mlp_init(kb, (cfg.n_dense,) + cfg.bot_mlp, cfg.dtype),
        "top": _mlp_init(kt, (top_in,) + cfg.top_mlp, cfg.dtype),
        "tables": tables,
    }


def param_specs(cfg: DLRMConfig, rules: shd.Rules) -> dict:
    mesh = shd.get_mesh()
    n_dev = 1
    if mesh is not None:
        n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    modes = cfg.table_modes(n_dev, 65536)
    tables = {
        f"t{i}": (rules.p_table_rows() if modes[i] == "shard" else P(None, None))
        for i in range(cfg.n_sparse)
    }
    mlp_spec = [{"w": P(None, None), "b": P(None)}]
    return {
        "bot": mlp_spec * len(cfg.bot_mlp),
        "top": mlp_spec * len(cfg.top_mlp),
        "tables": tables,
    }


# ---------------------------------------------------------------------------
# EmbeddingBag: take + segment_sum, demand-driven over row shards
# ---------------------------------------------------------------------------


def embedding_bag_local(table, idx, bag_ids, n_bags):
    """Reference EmbeddingBag (sum mode): rows = take(table, idx);
    bags = segment_sum(rows, bag_ids)."""
    rows = jnp.take(table, idx, axis=0)
    return jax.ops.segment_sum(rows, bag_ids, num_segments=n_bags)


def embedding_bag_sharded(table, idx, rules: shd.Rules):
    """2D-parallel row-sharded EmbeddingBag.

    ``idx`` (B, hot) stays sharded over the batch (data) axes; table rows
    shard over the model axis.  Each (data, model) device answers for the
    rows it owns over *its* batch slice (masked local take) and one psum
    over the model axis combines — the demand-driven S2 pattern with one
    collective per bag batch.  Output: (B, D) sharded over the batch axes.
    """
    mesh = shd.get_mesh()
    B, hot = idx.shape
    if mesh is None or rules.model_axis is None:
        bag_ids = jnp.repeat(jnp.arange(B), hot)
        return embedding_bag_local(table, idx.reshape(-1), bag_ids, B)
    M = rules.model_size
    rows_total = table.shape[0]
    rows_local = -(-rows_total // M)

    def local(table_shard, idx_loc):
        b_loc, h = idx_loc.shape
        flat = idx_loc.reshape(-1)
        mi = jax.lax.axis_index(rules.model_axis)
        lo = mi * rows_local
        in_range = jnp.logical_and(flat >= lo, flat < lo + table_shard.shape[0])
        local_idx = jnp.where(in_range, flat - lo, 0)
        rows = jnp.take(table_shard, local_idx, axis=0)
        rows = jnp.where(in_range[:, None], rows, 0)
        bag_ids = jnp.repeat(jnp.arange(b_loc), h)
        out = jax.ops.segment_sum(rows, bag_ids, num_segments=b_loc)
        return jax.lax.psum(out, rules.model_axis)

    pad = rows_local * M - rows_total
    if pad:
        table = jnp.pad(table, ((0, pad), (0, 0)))
    idx_spec = rules.fit(P(rules.batch, None), idx.shape)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(rules.model_axis, None), idx_spec),
        out_specs=P(tuple(idx_spec)[0], None),
        check_vma=False,
    )(table, idx)


# ---------------------------------------------------------------------------
# Forward / loss / steps
# ---------------------------------------------------------------------------


def forward(cfg: DLRMConfig, rules: shd.Rules, params, batch) -> jnp.ndarray:
    """batch: dense (B, 13) float; sparse (B, 26, multi_hot) int32."""
    dense, sparse = batch["dense"], batch["sparse"]
    B = dense.shape[0]
    x_dense = _mlp_apply(params["bot"], dense)  # (B, 128)

    mesh = shd.get_mesh()
    n_dev = 1
    if mesh is not None:
        n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    modes = cfg.table_modes(n_dev, B)

    embs = []
    bag_ids = jnp.repeat(jnp.arange(B), cfg.multi_hot)
    for i in range(cfg.n_sparse):
        table = params["tables"][f"t{i}"]
        if modes[i] == "shard":
            e = embedding_bag_sharded(table, sparse[:, i, :], rules)
        else:
            e = embedding_bag_local(table, sparse[:, i, :].reshape(-1), bag_ids, B)
        embs.append(e)

    # dot-interaction over [bottom-mlp output] + 26 embeddings
    embs = [e.astype(jnp.float32) for e in embs]
    feats = jnp.stack([x_dense] + embs, axis=1)  # (B, 27, D)
    inter = jnp.einsum("bnd,bmd->bnm", feats, feats)
    iu = jnp.triu_indices(cfg.n_sparse + 1, k=1)
    inter_flat = inter[:, iu[0], iu[1]]  # (B, 351)
    top_in = jnp.concatenate([x_dense, inter_flat], axis=-1)
    logit = _mlp_apply(params["top"], top_in)[:, 0]
    return logit


def loss_fn(cfg: DLRMConfig, rules: shd.Rules, params, batch) -> jnp.ndarray:
    logit = forward(cfg, rules, params, batch)
    y = batch["labels"].astype(jnp.float32)
    return jnp.mean(
        jnp.maximum(logit, 0) - logit * y + jnp.log1p(jnp.exp(-jnp.abs(logit)))
    )


def make_train_step(cfg: DLRMConfig, rules: shd.Rules):
    optimizer = opt_lib.get(cfg.optimizer)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(lambda p: loss_fn(cfg, rules, p, batch))(params)
        new_params, new_opt = optimizer.update(params, grads, opt_state)
        return new_params, new_opt, loss

    return train_step


def make_serve_step(cfg: DLRMConfig, rules: shd.Rules):
    def serve_step(params, batch):
        return jax.nn.sigmoid(forward(cfg, rules, params, batch))

    return serve_step


def make_retrieval_step(cfg: DLRMConfig, rules: shd.Rules):
    """retrieval_cand shape: one query (dense+sparse) scored against 1M
    candidate item embeddings — a batched dot, not a loop."""

    def retrieval_step(params, batch):
        dense, sparse, cand = batch["dense"], batch["sparse"], batch["candidates"]
        q = _mlp_apply(params["bot"], dense)  # (1, D)
        bag_ids = jnp.zeros((cfg.multi_hot,), jnp.int32)
        mesh = shd.get_mesh()
        n_dev = 1
        if mesh is not None:
            n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        modes = cfg.table_modes(n_dev, 1)
        embs = [q[0]]
        for i in range(cfg.n_sparse):
            table = params["tables"][f"t{i}"]
            if modes[i] == "shard":
                embs.append(embedding_bag_sharded(table, sparse[:, i, :], rules)[0])
            else:
                embs.append(
                    embedding_bag_local(table, sparse[0, i, :].reshape(-1), bag_ids, 1)[0]
                )
        user = jnp.mean(jnp.stack(embs, 0), 0)  # (D,)
        cand = shd.constrain(cand, P(tuple(rules.batch_axes) + ((rules.model_axis,) if rules.model_axis else ()), None))
        scores = cand @ user  # (n_candidates,)
        return jax.lax.top_k(scores, 64)

    return retrieval_step
