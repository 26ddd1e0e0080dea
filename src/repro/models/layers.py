"""Transformer building blocks: RMSNorm, RoPE, GQA attention (qk-norm
optional), chunked flash-style attention, SwiGLU FFN, and a MoE layer with
sort-based expert-parallel dispatch over the mesh ``model`` axis.

Everything is pure-functional: ``init_*`` build param pytrees,
``apply_*`` consume them.  Sharding intent is expressed through
:class:`repro.dist.sharding.Rules` constraints; the same code runs
unconstrained on one CPU device for smoke tests.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as shd

# ---------------------------------------------------------------------------
# Norms / RoPE / misc
# ---------------------------------------------------------------------------


def rmsnorm(x, scale, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def rope(x, positions, theta: float = 1e6):
    """x: (..., S, H, Dh); positions: (..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def silu(x):
    return x * jax.nn.sigmoid(x)


def cross_entropy(logits, labels, rules: shd.Rules, n_valid: int | None = None):
    """Token-mean CE; vocab dim may be sharded (logsumexp psums under GSPMD).
    ``n_valid`` masks the vocab-padding columns added for even sharding."""
    logits = shd.constrain(logits, rules.logits()).astype(jnp.float32)
    V = logits.shape[-1]
    if n_valid is not None and n_valid < V:
        pad_mask = jnp.arange(V) >= n_valid
        logits = jnp.where(pad_mask, -1e30, logits)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def _shard_chunks(v_shard: int, target: int = 1024) -> int:
    """Largest power-of-two chunk count <= 16 that divides v_shard."""
    for n2 in (16, 8, 4, 2):
        if v_shard % n2 == 0 and v_shard // n2 >= 128:
            return n2
    return 1


def chunked_cross_entropy(x, lm_head, labels, rules: shd.Rules, n_valid: int):
    """Token-mean CE computed in vocab chunks: the (B, S, V) logits tensor
    is never materialized (§Perf iteration 3).

    Chunking is *layout-aligned*: the head is viewed as (D, M, n2, vc2)
    where M is the model-axis shard count and chunks split the columns
    WITHIN each shard, so every chunk matmul is shard-local (a naive
    (D, n_chunks, v_chunk) reshape straddles shard boundaries and
    all-gathers the head — measured +3.3 GiB/step).  Two chunk passes
    (max, then exp-sum + masked gold extraction) with jax.checkpoint'd
    chunk bodies; running stats are (B, S) f32.
    """
    B, S, D = x.shape
    V = lm_head.shape[1]
    M = max(rules.model_size, 1)
    assert V % M == 0, (V, M)
    v_shard = V // M
    n2 = _shard_chunks(v_shard)
    vc2 = v_shard // n2
    heads = lm_head.reshape(D, M, n2, vc2)
    heads = shd.constrain(heads, P(None, rules.model_axis, None, None))

    # global column id of (m, ci, c2) is m*v_shard + ci*vc2 + c2
    m_ids = jnp.arange(M)[:, None] * v_shard
    c2_ids = jnp.arange(vc2)[None, :]

    def logits_chunk(ci):
        w = jax.lax.dynamic_index_in_dim(heads, ci, axis=2, keepdims=False)
        lg = jnp.einsum("bsd,dmv->bsmv", x, w).astype(jnp.float32)
        col = m_ids + ci * vc2 + c2_ids  # (M, vc2)
        return jnp.where(col[None, None] < n_valid, lg, -1e30), col

    ck_logits = jax.checkpoint(logits_chunk)

    def max_body(m, ci):
        lg, _ = ck_logits(ci)
        return jnp.maximum(m, lg.max((-1, -2))), None

    m, _ = jax.lax.scan(
        max_body, jnp.full((B, S), -jnp.inf, jnp.float32), jnp.arange(n2)
    )
    m = jax.lax.stop_gradient(m)

    def chunk_contrib(ci):
        lg, col = ck_logits(ci)
        se = jnp.exp(lg - m[..., None, None]).sum((-1, -2))
        gold_mask = col[None, None] == labels[..., None, None]
        gold = jnp.where(gold_mask, lg, 0.0).sum((-1, -2))
        return se, gold

    ck_contrib = jax.checkpoint(chunk_contrib)

    def sum_body(carry, ci):
        se_acc, gold_acc = carry
        se, gold = ck_contrib(ci)
        return (se_acc + se, gold_acc + gold), None

    (se, gold), _ = jax.lax.scan(
        sum_body,
        (jnp.zeros((B, S), jnp.float32), jnp.zeros((B, S), jnp.float32)),
        jnp.arange(n2),
    )
    lse = m + jnp.log(se)
    return jnp.mean(lse - gold)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def chunked_attention(
    q, k, v, *, causal: bool, q_chunk: int = 512, kv_chunk: int = 1024, q_offset=0
):
    """Flash-style chunked attention in pure JAX (the ref for the Pallas
    decode kernel).  q: (B, Sq, H, Dh); k/v: (B, Skv, G, Dh) with H = G·r
    (GQA).  Online softmax over KV chunks keeps the peak score buffer at
    (B, H, q_chunk, kv_chunk) instead of (B, H, Sq, Skv)."""
    B, Sq, H, Dh = q.shape
    _, Skv, G, _ = k.shape
    r = H // G
    scale = 1.0 / math.sqrt(Dh)
    q = q.reshape(B, Sq, G, r, Dh)

    n_q = -(-Sq // q_chunk)
    n_kv = -(-Skv // kv_chunk)
    q_pad = n_q * q_chunk - Sq
    kv_pad = n_kv * kv_chunk - Skv
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0), (0, 0)))
    if kv_pad:
        k = jnp.pad(k, ((0, 0), (0, kv_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, kv_pad), (0, 0), (0, 0)))

    kc = k.reshape(B, n_kv, kv_chunk, G, Dh)
    vc = v.reshape(B, n_kv, kv_chunk, G, Dh)
    qc = q.reshape(B, n_q, q_chunk, G, r, Dh)

    kv_valid = (jnp.arange(n_kv * kv_chunk) < Skv).reshape(n_kv, kv_chunk)

    def q_step(_, qi):
        qblk = qc[:, qi]  # (B, qc, G, r, Dh)
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, ki):
            m, l, acc = carry
            kblk, vblk = kc[:, ki], vc[:, ki]  # (B, kc, G, Dh)
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qblk, kblk).astype(jnp.float32) * scale
            kv_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            mask = kv_valid[ki][None, :]
            if causal:
                mask = jnp.logical_and(mask, q_pos[:, None] >= kv_pos[None, :])
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bgrqk,bkgd->bgrqd", p.astype(vblk.dtype), vblk
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, G, r, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, G, r, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, G, r, q_chunk, Dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(n_kv))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.astype(q.dtype)  # (B, G, r, qc, Dh)

    _, outs = jax.lax.scan(q_step, None, jnp.arange(n_q))  # (n_q, B, G, r, qc, Dh)
    out = jnp.moveaxis(outs, 0, 1)  # (B, n_q, G, r, qc, Dh)
    out = jnp.moveaxis(out, 4, 2)  # (B, n_q, qc, G, r, Dh)
    out = out.reshape(B, n_q * q_chunk, G, r, Dh)[:, :Sq]
    return out.reshape(B, Sq, H, Dh)


def decode_attention(q, k, v, kv_len):
    """Single-position attention against a (possibly sequence-sharded) KV
    cache.  q: (B, 1, H, Dh); k/v: (B, S, G, Dh); kv_len: valid prefix.
    The full score tensor is tiny (q_len = 1), so a plain softmax is used
    and GSPMD turns the S-reduction into a psum across KV shards."""
    B, _, H, Dh = q.shape
    _, S, G, _ = k.shape
    r = H // G
    scale = 1.0 / math.sqrt(Dh)
    qr = q.reshape(B, G, r, Dh)
    s = jnp.einsum("bgrd,bsgd->bgrs", qr, k).astype(jnp.float32) * scale
    mask = jnp.arange(S)[None, None, None, :] < kv_len
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrs,bsgd->bgrd", p.astype(v.dtype), v)
    return out.reshape(B, 1, H, Dh)


# ---------------------------------------------------------------------------
# Attention block (projections + norms + rope)
# ---------------------------------------------------------------------------


def init_attention(key, d_model: int, n_q: int, n_kv: int, d_head: int, qk_norm: bool, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    sd = 1.0 / math.sqrt(d_model)
    p = {
        "wq": (jax.random.normal(k1, (d_model, n_q * d_head)) * sd).astype(dtype),
        "wk": (jax.random.normal(k2, (d_model, n_kv * d_head)) * sd).astype(dtype),
        "wv": (jax.random.normal(k3, (d_model, n_kv * d_head)) * sd).astype(dtype),
        "wo": (jax.random.normal(k4, (n_q * d_head, d_model)) * sd).astype(dtype),
    }
    if qk_norm:
        p["q_norm"] = jnp.ones((d_head,), jnp.float32)
        p["k_norm"] = jnp.ones((d_head,), jnp.float32)
    return p


def apply_attention_proj(p, x, n_q, n_kv, d_head, positions, rules: shd.Rules, rope_theta=1e6):
    """QKV projection + qk-norm + rope.  Returns (q, k, v)."""
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, n_q, d_head)
    k = (x @ p["wk"]).reshape(B, S, n_kv, d_head)
    v = (x @ p["wv"]).reshape(B, S, n_kv, d_head)
    if "q_norm" in p:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    q = rope(q, positions, rope_theta)
    k = rope(k, positions, rope_theta)
    q = shd.constrain(q, rules.act_bthd())
    k = shd.constrain(k, P(rules.batch, None, None, None))
    v = shd.constrain(v, P(rules.batch, None, None, None))
    return q, k, v


# ---------------------------------------------------------------------------
# FFN (dense SwiGLU)
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    si, so = 1.0 / math.sqrt(d_model), 1.0 / math.sqrt(d_ff)
    return {
        "w_gate": (jax.random.normal(k1, (d_model, d_ff)) * si).astype(dtype),
        "w_up": (jax.random.normal(k2, (d_model, d_ff)) * si).astype(dtype),
        "w_down": (jax.random.normal(k3, (d_ff, d_model)) * so).astype(dtype),
    }


def apply_mlp(p, x, rules: shd.Rules):
    h = silu(x @ p["w_gate"]) * (x @ p["w_up"])
    h = shd.constrain(h, rules.act_ffn())
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# MoE with expert parallelism (sort-based dispatch + all_to_all)
# ---------------------------------------------------------------------------


def init_moe(key, d_model: int, d_ff: int, n_experts: int, dtype):
    k0, k1, k2, k3 = jax.random.split(key, 4)
    si, so = 1.0 / math.sqrt(d_model), 1.0 / math.sqrt(d_ff)
    return {
        "router": (jax.random.normal(k0, (d_model, n_experts)) * si).astype(jnp.float32),
        "w_gate": (jax.random.normal(k1, (n_experts, d_model, d_ff)) * si).astype(dtype),
        "w_up": (jax.random.normal(k2, (n_experts, d_model, d_ff)) * si).astype(dtype),
        "w_down": (jax.random.normal(k3, (n_experts, d_ff, d_model)) * so).astype(dtype),
    }


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def apply_moe(
    p,
    x,
    *,
    n_experts: int,
    top_k: int,
    rules: shd.Rules,
    capacity_factor: float = 1.25,
    fsdp: bool = False,
):
    """Expert-parallel MoE layer.

    Experts are sharded over the mesh ``model`` axis (EP group); tokens are
    sharded over (batch_axes, model).  Dispatch is sort-based with a static
    per-destination capacity (tokens beyond capacity are dropped, standard
    GShard semantics), routed with two ``all_to_all``s.  With a 1-device
    mesh the same code degenerates to a local grouped matmul.
    """
    mesh = shd.get_mesh()
    B, S, D = x.shape

    if mesh is None or rules.model_axis is None:
        return _moe_local(p, x, n_experts=n_experts, top_k=top_k)

    M = rules.model_size
    e_loc = n_experts // M
    assert e_loc * M == n_experts, (n_experts, M)

    spec_x = rules.fit(P(rules.batch, rules.model_axis, None), x.shape)
    t_loc = (B // rules.spec_divisor(spec_x, 0)) * (S // rules.spec_divisor(spec_x, 1))
    cap_send = _round_up(int(t_loc * top_k / M * capacity_factor) + 1, 8)
    cap_exp = _round_up(int(M * cap_send / e_loc * capacity_factor) + 1, 8)

    def local(x, router, w_gate, w_up, w_down):
        # x: (B_loc, S_loc, D); experts local: (e_loc, D, F)
        if fsdp and rules.batch_axes:
            # FSDP: expert weights rest sharded on d_ff over the data axes;
            # gather just-in-time for this layer (trillion-param MoE).
            for ax in rules.batch_axes:
                w_gate = jax.lax.all_gather(w_gate, ax, axis=2, tiled=True)
                w_up = jax.lax.all_gather(w_up, ax, axis=2, tiled=True)
                w_down = jax.lax.all_gather(w_down, ax, axis=1, tiled=True)
        bl, sl, _ = x.shape
        xt = x.reshape(bl * sl, D)
        T = bl * sl
        logits = xt.astype(jnp.float32) @ router  # (T, E)
        gate_vals, gate_idx = jax.lax.top_k(logits, top_k)  # (T, k)
        weights = jax.nn.softmax(gate_vals, axis=-1)

        a_tok = jnp.repeat(jnp.arange(T), top_k)  # (T*k,)
        a_exp = gate_idx.reshape(-1)
        a_w = weights.reshape(-1)
        dest = a_exp // e_loc  # target model shard

        order = jnp.argsort(dest, stable=True)
        dest_s, tok_s, exp_s, w_s = dest[order], a_tok[order], a_exp[order], a_w[order]
        group_start = jnp.searchsorted(dest_s, jnp.arange(M), side="left")
        rank = jnp.arange(T * top_k) - group_start[dest_s]
        slot = jnp.where(rank < cap_send, rank, cap_send)  # cap_send = drop slot

        send_x = jnp.zeros((M, cap_send + 1, D), x.dtype).at[dest_s, slot].set(xt[tok_s])
        send_le = jnp.full((M, cap_send + 1), e_loc, jnp.int32).at[dest_s, slot].set(
            (exp_s % e_loc).astype(jnp.int32)
        )
        send_x, send_le = send_x[:, :cap_send], send_le[:, :cap_send]

        recv_x = jax.lax.all_to_all(send_x, rules.model_axis, 0, 0, tiled=True).reshape(
            M, cap_send, D
        )
        recv_le = jax.lax.all_to_all(send_le, rules.model_axis, 0, 0, tiled=True).reshape(
            M, cap_send
        )

        # ---- second-stage dispatch: group received tokens by local expert
        rx = recv_x.reshape(M * cap_send, D)
        rle = recv_le.reshape(M * cap_send)
        order2 = jnp.argsort(rle, stable=True)
        rle_s = rle[order2]
        estart = jnp.searchsorted(rle_s, jnp.arange(e_loc), side="left")
        rank2 = jnp.arange(M * cap_send) - estart[jnp.minimum(rle_s, e_loc - 1)]
        valid2 = jnp.logical_and(rle_s < e_loc, rank2 < cap_exp)
        slot2 = jnp.where(valid2, rank2, cap_exp)
        buf = jnp.zeros((e_loc, cap_exp + 1, D), x.dtype).at[
            jnp.minimum(rle_s, e_loc - 1), slot2
        ].set(rx[order2])
        buf = buf[:, :cap_exp]

        # ---- expert computation (batched matmul over local experts) ------
        h = jnp.einsum("ecd,edf->ecf", buf, w_gate)
        u = jnp.einsum("ecd,edf->ecf", buf, w_up)
        y = jnp.einsum("ecf,efd->ecd", silu(h) * u, w_down)  # (e_loc, cap_exp, D)

        # ---- inverse of stage 2: back to recv-slot order ------------------
        y_sorted = y[jnp.minimum(rle_s, e_loc - 1), jnp.minimum(rank2, cap_exp - 1)]
        y_sorted = jnp.where(valid2[:, None], y_sorted, 0.0)
        inv2 = jnp.argsort(order2, stable=True)
        y_recv = y_sorted[inv2].reshape(M, cap_send, D)

        # ---- return trip + weighted combine -------------------------------
        y_back = jax.lax.all_to_all(y_recv, rules.model_axis, 0, 0, tiled=True).reshape(
            M, cap_send, D
        )
        kept = rank < cap_send
        y_slots = y_back[dest_s, jnp.minimum(rank, cap_send - 1)]
        y_slots = jnp.where(kept[:, None], y_slots, 0.0)
        out = jnp.zeros((T, D), jnp.float32).at[tok_s].add(
            y_slots.astype(jnp.float32) * w_s[:, None]
        )
        return out.reshape(bl, sl, D).astype(x.dtype)

    if fsdp and rules.batch_axes:
        spec_in = P(rules.model_axis, None, rules.batch_axes)
        spec_out = P(rules.model_axis, rules.batch_axes, None)
    else:
        spec_in = spec_out = P(rules.model_axis, None, None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec_x, P(None, None), spec_in, spec_in, spec_out),
        out_specs=spec_x,
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def _moe_local(p, x, *, n_experts: int, top_k: int):
    """Reference MoE (no mesh): dense per-expert compute with gather-combine.
    Used by smoke tests and as the oracle for the EP path."""
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    logits = xt.astype(jnp.float32) @ p["router"]
    gate_vals, gate_idx = jax.lax.top_k(logits, top_k)
    weights = jax.nn.softmax(gate_vals, axis=-1)
    h = jnp.einsum("td,edf->tef", xt, p["w_gate"])
    u = jnp.einsum("td,edf->tef", xt, p["w_up"])
    y = jnp.einsum("tef,efd->ted", silu(h) * u, p["w_down"])  # (T, E, D)
    sel = jnp.take_along_axis(y, gate_idx[:, :, None], axis=1)  # (T, k, D)
    out = (sel * weights[:, :, None]).sum(axis=1)
    return out.reshape(B, S, D).astype(x.dtype)
