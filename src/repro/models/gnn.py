"""GNN architectures: GCN, SchNet, NequIP, EquiformerV2-style eSCN.

Message passing is built on ``jax.ops.segment_sum`` over an edge-index →
node scatter (the brief's required JAX-native sparse path).  Distribution
follows the paper's setting: edges are *arbitrarily partitioned* across
devices (every mesh axis, flattened), node state is replicated, and each
step's scatter is combined with a ``psum`` — exactly the S2 'unicast
responses OR-combined over sites' pattern of the RPQ engine, applied to
feature aggregation (DESIGN.md §5).

Equivariant models:

* NequIP (l_max=2) uses *Cartesian irreps* — scalars (C,), vectors (C,3),
  traceless-symmetric tensors (C,3,3) — whose products implement the real
  Clebsch–Gordan paths for l ≤ 2 exactly (cross/outer/trace algebra).
* EquiformerV2 (l_max=6, m_max=2) uses eSCN SO(2) convolutions: per-edge
  rotation of spherical-tensor features into the edge-aligned frame, a
  per-|m| block-linear mix (m ≤ m_max), and rotation back.  Wigner-D
  matrices are built in-graph by the sample-point regression
  D = Y(R·P)·Y(P)⁺ (exact up to numerics; see DESIGN.md §2 hardware
  notes for the trade-off vs host-precomputed Wigner matrices).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as shd
from repro.training import optimizer as opt_lib

Array = jax.Array


# ---------------------------------------------------------------------------
# Distributed scatter: edges sharded over the mesh, nodes replicated
# ---------------------------------------------------------------------------


def scatter_sum(messages: Array, dst: Array, n_nodes: int, rules: shd.Rules) -> Array:
    """segment-sum messages (E, ...) into (n_nodes, ...), psum over edge
    shards when a mesh is active.  Call *inside* the shard_map region."""
    out = jax.ops.segment_sum(messages, dst, num_segments=n_nodes)
    mesh = shd.get_mesh()
    if mesh is not None:
        axes = tuple(rules.batch_axes) + (
            (rules.model_axis,) if rules.model_axis else ()
        )
        for ax in axes:
            out = jax.lax.psum(out, ax)
    return out


def edge_shard_map(fn, rules: shd.Rules, n_edge_arrays: int, n_rep_arrays: int):
    """Wrap ``fn(edge_arrays..., rep_arrays...)`` so edge arrays are sharded
    over every mesh axis and the rest (node state, params) replicated.
    Output must be replicated (fn psums via scatter_sum)."""
    mesh = shd.get_mesh()
    if mesh is None:
        return fn
    axes = tuple(rules.batch_axes) + ((rules.model_axis,) if rules.model_axis else ())
    espec = P(axes)
    in_specs = tuple([espec] * n_edge_arrays + [P()] * n_rep_arrays)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False
    )


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------


def _mlp_init(key, sizes, dtype=jnp.float32):
    layers = []
    for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:])):
        key, k = jax.random.split(key)
        layers.append(
            {
                "w": (jax.random.normal(k, (a, b)) / math.sqrt(a)).astype(dtype),
                "b": jnp.zeros((b,), dtype),
            }
        )
    return layers


def _mlp_apply(layers, x, act=jax.nn.silu):
    for i, l in enumerate(layers):
        x = x @ l["w"] + l["b"]
        if i + 1 < len(layers):
            x = act(x)
    return x


def gaussian_rbf(d: Array, n_rbf: int, cutoff: float) -> Array:
    centers = jnp.linspace(0.0, cutoff, n_rbf)
    gamma = n_rbf / cutoff
    out = jnp.exp(-gamma * jnp.square(d[..., None] - centers))
    env = 0.5 * (jnp.cos(jnp.pi * jnp.clip(d / cutoff, 0, 1)) + 1.0)  # cosine cutoff
    return out * env[..., None]


# ===========================================================================
# GCN (Kipf & Welling) — arXiv:1609.02907
# ===========================================================================


@dataclasses.dataclass(frozen=True)
class GCNConfig:
    name: str = "gcn-cora"
    n_layers: int = 2
    d_hidden: int = 16
    d_feat: int = 1433
    n_classes: int = 7
    optimizer: str = "adamw"


def gcn_init(cfg: GCNConfig, key) -> dict:
    sizes = [cfg.d_feat] + [cfg.d_hidden] * (cfg.n_layers - 1) + [cfg.n_classes]
    return {"layers": _mlp_init(key, sizes)}


def gcn_forward(cfg: GCNConfig, rules: shd.Rules, params, batch) -> Array:
    x = batch["node_feat"]
    n = x.shape[0]
    src, dst, emask = batch["edge_src"], batch["edge_dst"], batch["edge_mask"]

    # symmetric normalization with self-loops (computed from the edge list)
    def degs(src, dst, emask):
        ones = emask.astype(jnp.float32)
        din = scatter_sum(ones, dst, n, rules) + 1.0
        dout = scatter_sum(ones, src, n, rules) + 1.0
        return din, dout

    din, dout = edge_shard_map(degs, rules, 3, 0)(src, dst, emask)

    for i, layer in enumerate(params["layers"]):
        h = x @ layer["w"] + layer["b"]

        def prop(src, dst, emask, h, dout, din):
            coef = emask.astype(jnp.float32) * jax.lax.rsqrt(dout[src] * din[dst])
            agg = scatter_sum(h[src] * coef[:, None], dst, n, rules)
            return agg

        agg = edge_shard_map(prop, rules, 3, 3)(src, dst, emask, h, dout, din)
        x = agg + h * jax.lax.rsqrt(din * dout)[:, None]  # self loop
        if i + 1 < len(params["layers"]):
            x = jax.nn.relu(x)
    return x  # logits (N, n_classes)


def gcn_loss(cfg: GCNConfig, rules: shd.Rules, params, batch) -> Array:
    logits = gcn_forward(cfg, rules, params, batch)
    labels = batch["labels"]
    mask = batch["train_mask"].astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum((lse - gold) * mask) / jnp.maximum(mask.sum(), 1.0)


# ===========================================================================
# SchNet — arXiv:1706.08566
# ===========================================================================


@dataclasses.dataclass(frozen=True)
class SchNetConfig:
    name: str = "schnet"
    n_interactions: int = 3
    d_hidden: int = 64
    n_rbf: int = 300
    cutoff: float = 10.0
    n_species: int = 32
    optimizer: str = "adamw"


def schnet_init(cfg: SchNetConfig, key) -> dict:
    keys = jax.random.split(key, 2 + cfg.n_interactions)
    inter = []
    for i in range(cfg.n_interactions):
        k1, k2, k3 = jax.random.split(keys[i], 3)
        inter.append(
            {
                "filter": _mlp_init(k1, [cfg.n_rbf, cfg.d_hidden, cfg.d_hidden]),
                "in_proj": _mlp_init(k2, [cfg.d_hidden, cfg.d_hidden]),
                "out": _mlp_init(k3, [cfg.d_hidden, cfg.d_hidden, cfg.d_hidden]),
            }
        )
    return {
        "embed": jax.random.normal(keys[-2], (cfg.n_species, cfg.d_hidden)) * 0.1,
        "inter": inter,
        "readout": _mlp_init(keys[-1], [cfg.d_hidden, cfg.d_hidden // 2, 1]),
    }


def schnet_energy(cfg: SchNetConfig, rules: shd.Rules, params, batch) -> Array:
    species, pos = batch["species"], batch["positions"]
    src, dst, emask = batch["edge_src"], batch["edge_dst"], batch["edge_mask"]
    n = species.shape[0]
    h = params["embed"][species]

    for blk in params["inter"]:

        def interact(src, dst, emask, h, pos, f0w, f0b, f1w, f1b, ipw, ipb):
            rel = pos[src] - pos[dst]
            d = jnp.sqrt(jnp.sum(rel * rel, -1) + 1e-12)
            rbf = gaussian_rbf(d, cfg.n_rbf, cfg.cutoff)
            filt = jax.nn.silu(rbf @ f0w + f0b) @ f1w + f1b  # (E, D)
            hj = h[src] @ ipw + ipb
            msg = hj * filt * emask[:, None].astype(h.dtype)
            return scatter_sum(msg, dst, n, rules)

        agg = edge_shard_map(interact, rules, 3, 8)(
            src, dst, emask, h, pos,
            blk["filter"][0]["w"], blk["filter"][0]["b"],
            blk["filter"][1]["w"], blk["filter"][1]["b"],
            blk["in_proj"][0]["w"], blk["in_proj"][0]["b"],
        )
        h = h + _mlp_apply(blk["out"], agg)

    atom_e = _mlp_apply(params["readout"], h)[:, 0] * batch["node_mask"].astype(h.dtype)
    if "graph_ids" in batch:
        # per-graph readout; segment count comes from the target's static shape
        return jax.ops.segment_sum(atom_e, batch["graph_ids"], batch["energy"].shape[0])
    return atom_e.sum()[None]


def schnet_loss(cfg: SchNetConfig, rules: shd.Rules, params, batch) -> Array:
    e = schnet_energy(cfg, rules, params, batch)
    return jnp.mean(jnp.square(e - batch["energy"]))


# ===========================================================================
# NequIP (l_max = 2, Cartesian irreps) — arXiv:2101.03164
# ===========================================================================


@dataclasses.dataclass(frozen=True)
class NequIPConfig:
    name: str = "nequip"
    n_layers: int = 5
    channels: int = 32
    l_max: int = 2  # fixed by the Cartesian implementation
    n_rbf: int = 8
    cutoff: float = 5.0
    n_species: int = 32
    optimizer: str = "adamw"


_N_PATHS = 10  # radial-weighted tensor-product paths (see nequip_layer)


def nequip_init(cfg: NequIPConfig, key) -> dict:
    C = cfg.channels
    keys = jax.random.split(key, 2 + cfg.n_layers)
    layers = []
    for i in range(cfg.n_layers):
        k1, k2, k3, k4, k5 = jax.random.split(keys[i], 5)
        layers.append(
            {
                "radial": _mlp_init(k1, [cfg.n_rbf, 32, _N_PATHS * C]),
                "mix_s": jax.random.normal(k2, (C, C)) / math.sqrt(C),
                "mix_v": jax.random.normal(k3, (C, C)) / math.sqrt(C),
                "mix_t": jax.random.normal(k4, (C, C)) / math.sqrt(C),
                "gate": _mlp_init(k5, [C, 2 * C]),
            }
        )
    return {
        "embed": jax.random.normal(keys[-2], (cfg.n_species, C)) * 0.5,
        "layers": layers,
        "readout": _mlp_init(keys[-1], [C, C, 1]),
    }


def _traceless(outer):  # (..., 3, 3) -> traceless symmetric part
    sym = 0.5 * (outer + jnp.swapaxes(outer, -1, -2))
    tr = jnp.trace(sym, axis1=-2, axis2=-1)[..., None, None]
    return sym - tr * jnp.eye(3) / 3.0


def nequip_energy(cfg: NequIPConfig, rules: shd.Rules, params, batch) -> Array:
    species, pos = batch["species"], batch["positions"]
    src, dst, emask = batch["edge_src"], batch["edge_dst"], batch["edge_mask"]
    n = species.shape[0]
    C = cfg.channels
    s = params["embed"][species]  # (N, C) scalars
    v = jnp.zeros((n, C, 3))
    t = jnp.zeros((n, C, 3, 3))

    for blk in params["layers"]:

        def message(src, dst, emask, s, v, t, pos, r0w, r0b, r1w, r1b):
            rel = pos[src] - pos[dst]  # (E, 3)
            d = jnp.sqrt(jnp.sum(rel * rel, -1) + 1e-12)
            rhat = rel / d[:, None]
            T_edge = _traceless(rhat[:, :, None] * rhat[:, None, :])  # (E,3,3)
            rbf = gaussian_rbf(d, cfg.n_rbf, cfg.cutoff)
            w = (jax.nn.silu(rbf @ r0w + r0b) @ r1w + r1b).reshape(-1, _N_PATHS, C)
            w = w * emask[:, None, None].astype(w.dtype)
            sj, vj, tj = s[src], v[src], t[src]  # (E,C) (E,C,3) (E,C,3,3)
            rh = rhat[:, None, :]  # (E,1,3)
            # --- the 10 CG paths for l<=2 in Cartesian form ---------------
            m_s = (
                w[:, 0] * sj  # s⊗Y0→s
                + w[:, 1] * jnp.einsum("ecx,ex->ec", vj, rhat)  # v⊗Y1→s
                + w[:, 2] * jnp.einsum("ecxy,exy->ec", tj, T_edge)  # t⊗Y2→s
            )
            m_v = (
                w[:, 3, :, None] * sj[:, :, None] * rh  # s⊗Y1→v
                + w[:, 4, :, None] * vj  # v⊗Y0→v
                + w[:, 5, :, None] * jnp.cross(vj, jnp.broadcast_to(rh, vj.shape))  # v⊗Y1→v
                + w[:, 6, :, None] * jnp.einsum("ecxy,ey->ecx", tj, rhat)  # t⊗Y1→v
            )
            m_t = (
                w[:, 7, :, None, None] * sj[:, :, None, None] * T_edge[:, None]  # s⊗Y2→t
                + w[:, 8, :, None, None] * _traceless(vj[:, :, :, None] * rh[:, :, None, :])  # v⊗Y1→t
                + w[:, 9, :, None, None] * tj  # t⊗Y0→t
            )
            return (
                scatter_sum(m_s, dst, n, rules),
                scatter_sum(m_v, dst, n, rules),
                scatter_sum(m_t, dst, n, rules),
            )

        ms, mv, mt = edge_shard_map(message, rules, 3, 8)(
            src, dst, emask, s, v, t, pos,
            blk["radial"][0]["w"], blk["radial"][0]["b"],
            blk["radial"][1]["w"], blk["radial"][1]["b"],
        )
        # node update: channel mixing per irrep + gated nonlinearity
        s_new = ms @ blk["mix_s"]
        v_new = jnp.einsum("ncx,cd->ndx", mv, blk["mix_v"])
        t_new = jnp.einsum("ncxy,cd->ndxy", mt, blk["mix_t"])
        gates = _mlp_apply(blk["gate"], s_new)
        gv, gt = jax.nn.sigmoid(gates[:, :C]), jax.nn.sigmoid(gates[:, C:])
        s = s + jax.nn.silu(s_new)
        v = v + v_new * gv[:, :, None]
        t = t + t_new * gt[:, :, None, None]

    atom_e = _mlp_apply(params["readout"], s)[:, 0] * batch["node_mask"].astype(s.dtype)
    if "graph_ids" in batch:
        # per-graph readout; segment count comes from the target's static shape
        return jax.ops.segment_sum(atom_e, batch["graph_ids"], batch["energy"].shape[0])
    return atom_e.sum()[None]


def nequip_loss(cfg: NequIPConfig, rules: shd.Rules, params, batch) -> Array:
    e = nequip_energy(cfg, rules, params, batch)
    return jnp.mean(jnp.square(e - batch["energy"]))


# ===========================================================================
# EquiformerV2-style eSCN — arXiv:2306.12059
# ===========================================================================


@dataclasses.dataclass(frozen=True)
class EquiformerConfig:
    name: str = "equiformer-v2"
    n_layers: int = 12
    channels: int = 128
    l_max: int = 6
    m_max: int = 2
    n_heads: int = 8
    n_rbf: int = 32
    cutoff: float = 8.0
    n_species: int = 32
    optimizer: str = "adamw"

    @property
    def n_coef(self) -> int:
        return (self.l_max + 1) ** 2


# ---- real spherical harmonics up to l_max (recurrence-based) --------------


def real_sph_harm(vec: Array, l_max: int, xp=jnp) -> Array:
    """Real, orthonormal spherical harmonics Y_{lm}(v̂) for unit vectors.

    vec: (..., 3) -> (..., (l_max+1)^2), ordering l-major, m from -l..l.
    Associated Legendre via the standard stable recurrences; azimuthal
    factors via Chebyshev recursion on (cosφ, sinφ).  ``xp`` selects the
    array namespace (numpy for the host-side Wigner basis)."""
    x, y, z = vec[..., 0], vec[..., 1], vec[..., 2]
    rho = xp.sqrt(x * x + y * y + 1e-20)
    ct = z  # cos θ (unit vectors)
    st = rho
    cphi, sphi = x / rho, y / rho

    # P_l^m(ct) for 0<=m<=l<=l_max (unnormalized, Condon–Shortley OMITTED)
    Pmm = {0: xp.ones_like(ct)}
    for m in range(1, l_max + 1):
        Pmm[m] = Pmm[m - 1] * (2 * m - 1) * st
    Plm = {}
    for m in range(0, l_max + 1):
        Plm[(m, m)] = Pmm[m]
        if m < l_max:
            Plm[(m + 1, m)] = ct * (2 * m + 1) * Pmm[m]
        for l in range(m + 2, l_max + 1):
            Plm[(l, m)] = (
                (2 * l - 1) * ct * Plm[(l - 1, m)] - (l + m - 1) * Plm[(l - 2, m)]
            ) / (l - m)

    cos_m = {0: xp.ones_like(cphi), 1: cphi}
    sin_m = {0: xp.zeros_like(sphi), 1: sphi}
    for m in range(2, l_max + 1):
        cos_m[m] = 2 * cphi * cos_m[m - 1] - cos_m[m - 2]
        sin_m[m] = 2 * cphi * sin_m[m - 1] - sin_m[m - 2]

    comps = []
    for l in range(l_max + 1):
        for m in range(-l, l + 1):
            am = abs(m)
            norm = math.sqrt(
                (2 * l + 1) / (4 * math.pi) * math.factorial(l - am) / math.factorial(l + am)
            )
            if m == 0:
                comps.append(norm * Plm[(l, 0)])
            elif m > 0:
                comps.append(math.sqrt(2) * norm * Plm[(l, m)] * cos_m[m])
            else:
                comps.append(math.sqrt(2) * norm * Plm[(l, am)] * sin_m[am])
    return xp.stack(comps, axis=-1)


def _fibonacci_points(n: int) -> np.ndarray:
    i = np.arange(n) + 0.5
    phi = np.arccos(1 - 2 * i / n)
    theta = np.pi * (1 + 5**0.5) * i
    return np.stack(
        [np.sin(phi) * np.cos(theta), np.sin(phi) * np.sin(theta), np.cos(phi)], -1
    )


_WIGNER_NPTS = 80


from functools import lru_cache


@lru_cache(maxsize=8)
def _wigner_basis_np(l_max: int):
    """Host-side (pure numpy, safe under jit tracing): sample points P and
    pinv(Y(P)) for the per-edge D-regression."""
    pts = _fibonacci_points(_WIGNER_NPTS)
    Y = real_sph_harm(pts, l_max, xp=np)  # (npts, ncoef)
    return pts.astype(np.float32), np.linalg.pinv(Y).astype(np.float32)


def _wigner_basis(l_max: int):
    pts, pinv = _wigner_basis_np(l_max)
    return jnp.asarray(pts), jnp.asarray(pinv)


def edge_rotation(rhat: Array) -> Array:
    """Rotation matrix R_e with R_e @ rhat = ẑ (Rodrigues)."""
    z = jnp.array([0.0, 1e-9, 1.0])
    z = z / jnp.linalg.norm(z)
    v = jnp.cross(rhat, z)
    c = rhat @ z
    s2 = jnp.sum(v * v, -1)
    vx = jnp.zeros(rhat.shape[:-1] + (3, 3))
    vx = vx.at[..., 0, 1].set(-v[..., 2]).at[..., 0, 2].set(v[..., 1])
    vx = vx.at[..., 1, 0].set(v[..., 2]).at[..., 1, 2].set(-v[..., 0])
    vx = vx.at[..., 2, 0].set(-v[..., 1]).at[..., 2, 1].set(v[..., 0])
    eye = jnp.broadcast_to(jnp.eye(3), vx.shape)
    factor = jnp.where(s2 > 1e-12, (1 - c) / jnp.maximum(s2, 1e-12), 0.5)
    return eye + vx + (vx @ vx) * factor[..., None, None]


def wigner_d(rot: Array, l_max: int, pts: Array, pinv_y: Array) -> Array:
    """D(R) (ncoef, ncoef) per edge via Y(R·P) = D·Y(P) regression."""
    rp = jnp.einsum("...ij,pj->...pi", rot, pts)  # rotated sample points
    y_rot = real_sph_harm(rp, l_max)  # (..., npts, ncoef)
    # D = Y(RP)^T · pinv(Y(P))^T : solve D Y(P)ᵀ = Y(RP)ᵀ
    return jnp.einsum("...pc,pk->...ck", y_rot, pinv_y.T)


def _m_indices(l_max: int, m_max: int):
    """Coefficient indices for each |m| <= m_max: (pos list, neg list, l list)."""
    idx = {}
    for m in range(0, m_max + 1):
        pos, neg = [], []
        for l in range(m, l_max + 1):
            base = l * l + l  # m=0 position of degree l
            pos.append(base + m)
            neg.append(base - m)
        idx[m] = (np.array(pos), np.array(neg))
    return idx


def equiformer_init(cfg: EquiformerConfig, key) -> dict:
    C = cfg.channels
    n_l = cfg.l_max + 1
    keys = jax.random.split(key, 2 + cfg.n_layers)
    layers = []
    for i in range(cfg.n_layers):
        ks = jax.random.split(keys[i], 6)
        n_lm = {m: cfg.l_max + 1 - m for m in range(cfg.m_max + 1)}
        so2 = {
            f"w{m}": jax.random.normal(ks[0], (2, n_lm[m] * C, n_lm[m] * C))
            / math.sqrt(n_lm[m] * C)
            for m in range(cfg.m_max + 1)
        }
        layers.append(
            {
                "so2": so2,
                "radial": _mlp_init(ks[1], [cfg.n_rbf, 64, (cfg.m_max + 1) * C]),
                "attn": _mlp_init(ks[2], [C, 32, cfg.n_heads]),
                "mix": jax.random.normal(ks[3], (n_l, C, C)) / math.sqrt(C),
                "gate": _mlp_init(ks[4], [C, n_l * C]),
            }
        )
    return {
        "embed": jax.random.normal(keys[-2], (cfg.n_species, C)) * 0.5,
        "layers": layers,
        "readout": _mlp_init(keys[-1], [C, C, 1]),
    }


_BIG_GRAPH_NODES = 150_000
_BIG_CHUNK = 32_768


def equiformer_energy_big(cfg: EquiformerConfig, rules: shd.Rules, params, batch) -> Array:
    """Large-graph eSCN path (ogb_products / minibatch_lg scale).

    The (N, C, (l_max+1)²) node irreps do not fit replicated (61 GB at
    2.45M nodes).  Layout:

      * node state is sharded over the model axis (rows), replicated over
        data; edges shard over the data axes only, so every model shard of
        a data column sees the same edges — required for the masked-psum
        gather of arbitrary source rows,
      * per-edge work runs in 32k chunks under jax.checkpoint, with
        *online segment-softmax* (flash-style running max/denominator per
        destination row) so the graph attention stays exact across chunks,
      * cross-data softmax state merges with the standard flash combine
        (pmax on m; psum of exp-rescaled l and acc).

    The per-chunk psum gather over the model axis is the price of
    arbitrary (non-localized) node placement — exactly the paper's
    localized-vs-non-localized trade-off applied to feature retrieval
    (DESIGN.md §5); locality-aware placement would remove it.
    """
    mesh = shd.get_mesh()
    species, pos = batch["species"], batch["positions"]
    src, dst, emask = batch["edge_src"], batch["edge_dst"], batch["edge_mask"]
    n = species.shape[0]
    C, ncoef, heads = cfg.channels, cfg.n_coef, cfg.n_heads
    pts, pinv_y = _wigner_basis(cfg.l_max)
    midx = _m_indices(cfg.l_max, cfg.m_max)
    M = rules.model_size
    data_axes = rules.batch_axes
    assert n % M == 0, (n, M)
    n_m = n // M  # rows per model block

    flat_params, treedef = jax.tree_util.tree_flatten(params)

    def local(species_loc, pos_loc, nmask_loc, src, dst, emask, *flat):
        p = jax.tree_util.tree_unflatten(treedef, flat)
        mi = jax.lax.axis_index(rules.model_axis)
        lo = mi * n_m

        def gather(arr_m, idx):
            """Rows of a model-sharded (n_m, ...) array at edge indices:
            masked local take + psum over the model axis."""
            inr = jnp.logical_and(idx >= lo, idx < lo + n_m)
            rows = jnp.take(arr_m, jnp.where(inr, idx - lo, 0), axis=0)
            rows = jnp.where(inr.reshape(inr.shape + (1,) * (rows.ndim - 1)), rows, 0)
            return jax.lax.psum(rows, rules.model_axis)

        D = 1
        for ax in data_axes:
            D *= mesh.shape[ax]
        n_rest = n_m // D

        def gather_rest(h_rest):
            h = h_rest
            for ax in reversed(data_axes):
                h = jax.lax.all_gather(h, ax, axis=0, tiled=True)
            return h

        def scatter_rest(h_full):
            di = jnp.int32(0)
            for ax in data_axes:
                di = di * mesh.shape[ax] + jax.lax.axis_index(ax)
            return jax.lax.dynamic_slice_in_dim(h_full, di * n_rest, n_rest, axis=0)

        scatter_rest_1d = scatter_rest

        # node state and edge accumulators run in bf16 (f32 master math in
        # the per-chunk message computation; the +acc accumulation is the
        # only bf16 reduction — ~60 terms, well within bf16 integer range)
        h0 = (
            jnp.zeros((n_m, C, ncoef), jnp.bfloat16)
            .at[:, :, 0].set(p["embed"][species_loc].astype(jnp.bfloat16))
        )
        # node state *rests* sharded over (model × data) rows; each layer
        # all-gathers its model block over data (FSDP-style activations) so
        # layer checkpoints are n_m/D rows, not n_m
        h_rest = scatter_rest(h0)

        e_loc = src.shape[0]
        n_chunks = max(e_loc // _BIG_CHUNK, 1)
        chunk = e_loc // n_chunks
        src_c = src.reshape(n_chunks, chunk)
        dst_c = dst.reshape(n_chunks, chunk)
        em_c = emask.reshape(n_chunks, chunk)

        def layer_fn(h_rest, blk):
            h_m = gather_rest(h_rest)
            h_scal = h_m[:, :, 0].astype(jnp.float32)  # scalars drive attention

            def edge_logits(s_idx, d_idx, em):
                """Attention logits from the scalar pathway only (as in
                EquiformerV2's separate alpha projection) — keeps pass 1
                cheap and pass 2's accumulator linear in the carry."""
                hj_s = gather(h_scal, s_idx)  # (chunk, C)
                logits = (
                    jax.nn.silu(hj_s @ blk["attn"][0]["w"] + blk["attn"][0]["b"])
                    @ blk["attn"][1]["w"] + blk["attn"][1]["b"]
                )
                return jnp.where(em[:, None], logits, -1e30)

            def edge_messages(s_idx, d_idx):
                pj = gather(pos_loc, s_idx)
                pi = gather(pos_loc, d_idx)
                hj = gather(h_m, s_idx).astype(jnp.float32)
                rel = pj - pi
                dd = jnp.sqrt(jnp.sum(rel * rel, -1) + 1e-12)
                rhat = rel / dd[:, None]
                rot = edge_rotation(rhat)
                Dw = wigner_d(rot, cfg.l_max, pts, pinv_y)
                rbf = gaussian_rbf(dd, cfg.n_rbf, cfg.cutoff)
                rw = (
                    jax.nn.silu(rbf @ blk["radial"][0]["w"] + blk["radial"][0]["b"])
                    @ blk["radial"][1]["w"] + blk["radial"][1]["b"]
                ).reshape(-1, cfg.m_max + 1, C)
                g = jnp.einsum("eck,eqk->ecq", hj, Dw)
                out = jnp.zeros_like(g)
                for m in range(cfg.m_max + 1):
                    pos_i, neg_i = midx[m]
                    gp = g[:, :, pos_i] * rw[:, m][:, :, None]
                    w1, w2 = blk["so2"][f"w{m}"][0], blk["so2"][f"w{m}"][1]
                    if m == 0:
                        yp = jnp.einsum("eu,uv->ev", gp.reshape(gp.shape[0], -1), w1)
                        out = out.at[:, :, pos_i].set(yp.reshape(gp.shape))
                    else:
                        gn = g[:, :, neg_i] * rw[:, m][:, :, None]
                        fp, fn = gp.reshape(gp.shape[0], -1), gn.reshape(gn.shape[0], -1)
                        yp = jnp.einsum("eu,uv->ev", fp, w1) - jnp.einsum("eu,uv->ev", fn, w2)
                        yn = jnp.einsum("eu,uv->ev", fp, w2) + jnp.einsum("eu,uv->ev", fn, w1)
                        out = out.at[:, :, pos_i].set(yp.reshape(gp.shape))
                        out = out.at[:, :, neg_i].set(yn.reshape(gn.shape))
                return jnp.einsum("ecq,eqk->eck", out, Dw)

            def local_dst(d_idx):
                inr = jnp.logical_and(d_idx >= lo, d_idx < lo + n_m)
                return inr, jnp.where(inr, d_idx - lo, n_m)  # row n_m = drop

            # ---- pass 1: softmax statistics (small carry) -----------------
            ckpt_logits = jax.checkpoint(edge_logits)

            def stats_body(carry, xs):
                m_run, l_run = carry
                s_idx, d_idx, em = xs
                logits = ckpt_logits(s_idx, d_idx, em)
                inr, d_local = local_dst(d_idx)
                m_chunk = (
                    jnp.full((n_m + 1, heads), -1e30)
                    .at[d_local].max(jax.lax.stop_gradient(logits))[: n_m]
                )
                m_new = jnp.maximum(m_run, m_chunk)
                w_edge = jnp.exp(logits - m_new[jnp.minimum(d_local, n_m - 1)])
                w_edge = jnp.where(inr[:, None], w_edge, 0.0) * em[:, None]
                l_chunk = jnp.zeros((n_m + 1, heads)).at[d_local].add(w_edge)[: n_m]
                return (m_new, l_run * jnp.exp(m_run - m_new) + l_chunk), None

            carry0 = (jnp.full((n_m, heads), -1e30), jnp.zeros((n_m, heads)))
            (m_run, l_run), _ = jax.lax.scan(stats_body, carry0, (src_c, dst_c, em_c))
            # flash combine across the data axes (each saw different edges)
            m_g = m_run
            for ax in data_axes:
                m_g = jax.lax.pmax(m_g, ax)
            m_g = jax.lax.stop_gradient(m_g)
            l_g = l_run * jnp.exp(m_run - m_g)
            for ax in data_axes:
                l_g = jax.lax.psum(l_g, ax)
            l_g = jnp.maximum(l_g, 1e-20)

            # ---- pass 2: normalized aggregation.  The carry update is a
            # pure add (linear), so its value is never a backward residual;
            # only the *chunk contribution* is checkpointed (recompute) ----
            def chunk_contrib(s_idx, d_idx, em):
                logits = edge_logits(s_idx, d_idx, em)
                inr, d_local = local_dst(d_idx)
                alpha = jnp.exp(logits - m_g[jnp.minimum(d_local, n_m - 1)])
                alpha = alpha / l_g[jnp.minimum(d_local, n_m - 1)]
                alpha = jnp.where(inr[:, None], alpha, 0.0) * em[:, None]
                msg = edge_messages(s_idx, d_idx)
                w_c = jnp.repeat(alpha, C // heads, axis=-1)
                return (
                    jnp.zeros((n_m + 1, C, ncoef), jnp.bfloat16)
                    .at[d_local].add((msg * w_c[:, :, None]).astype(jnp.bfloat16))[: n_m]
                )

            ckpt_contrib = jax.checkpoint(chunk_contrib)

            def agg_body(acc, xs):
                s_idx, d_idx, em = xs
                return acc + ckpt_contrib(s_idx, d_idx, em), None

            acc, _ = jax.lax.scan(
                agg_body, jnp.zeros((n_m, C, ncoef), jnp.bfloat16), (src_c, dst_c, em_c)
            )
            # combine across data *and* drop to rest-sharded rows in one
            # collective; all update math then runs at n_m/D row count
            agg = acc
            for ax in data_axes:
                agg = jax.lax.psum_scatter(agg, ax, scatter_dimension=0, tiled=True)

            nr = agg.shape[0]
            agg = agg.astype(jnp.float32)
            upd = []
            for l in range(cfg.l_max + 1):
                sl = slice(l * l, (l + 1) * (l + 1))
                upd.append(jnp.einsum("nck,cd->ndk", agg[:, :, sl], blk["mix"][l]))
            upd = jnp.concatenate(upd, axis=-1)
            gates = _mlp_apply(blk["gate"], upd[:, :, 0]).reshape(nr, C, cfg.l_max + 1)
            gate_full = jnp.repeat(
                jax.nn.sigmoid(gates),
                np.array([2 * l + 1 for l in range(cfg.l_max + 1)]),
                axis=-1,
                total_repeat_length=ncoef,
            )
            return h_rest + (upd * gate_full).astype(jnp.bfloat16)

        # scan over stacked layer params: one reused buffer set per layer
        blk_stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *p["layers"])

        def scan_layer(h_rest, blk):
            return jax.checkpoint(layer_fn)(h_rest, blk), None

        h_rest, _ = jax.lax.scan(scan_layer, h_rest, blk_stacked)

        nmask_rest = scatter_rest_1d(nmask_loc)
        atom_e = (
            _mlp_apply(p["readout"], h_rest[:, :, 0].astype(jnp.float32))[:, 0]
            * nmask_rest.astype(jnp.float32)
        )
        e = atom_e.sum()
        e = jax.lax.psum(e, rules.model_axis)
        for ax in data_axes:
            e = jax.lax.psum(e, ax)
        return e[None]

    nspec = P(rules.model_axis)
    espec = P(data_axes if data_axes else None)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(nspec, P(rules.model_axis, None), nspec, espec, espec, espec)
        + tuple(P() for _ in flat_params),
        out_specs=P(),
        check_vma=False,
    )
    return fn(species, pos, batch["node_mask"], src, dst, emask, *flat_params)


def equiformer_energy(cfg: EquiformerConfig, rules: shd.Rules, params, batch) -> Array:
    species, pos = batch["species"], batch["positions"]
    if (
        species.shape[0] >= _BIG_GRAPH_NODES
        and shd.get_mesh() is not None
        and rules.model_axis is not None
        and species.shape[0] % rules.model_size == 0
    ):
        return equiformer_energy_big(cfg, rules, params, batch)
    src, dst, emask = batch["edge_src"], batch["edge_dst"], batch["edge_mask"]
    n = species.shape[0]
    C, ncoef = cfg.channels, cfg.n_coef
    pts, pinv_y = _wigner_basis(cfg.l_max)
    midx = _m_indices(cfg.l_max, cfg.m_max)

    h = jnp.zeros((n, C, ncoef)).at[:, :, 0].set(params["embed"][species])

    for blk in params["layers"]:

        def message(src, dst, emask, h, pos, *flat_params):
            it = iter(flat_params)
            so2 = {f"w{m}": next(it) for m in range(cfg.m_max + 1)}
            r0w, r0b, r1w, r1b = next(it), next(it), next(it), next(it)
            a0w, a0b, a1w, a1b = next(it), next(it), next(it), next(it)

            rel = pos[src] - pos[dst]
            d = jnp.sqrt(jnp.sum(rel * rel, -1) + 1e-12)
            rhat = rel / d[:, None]
            rot = edge_rotation(rhat)  # (E,3,3)
            D = wigner_d(rot, cfg.l_max, pts, pinv_y)  # (E,ncoef,ncoef)
            rbf = gaussian_rbf(d, cfg.n_rbf, cfg.cutoff)
            rw = (jax.nn.silu(rbf @ r0w + r0b) @ r1w + r1b).reshape(
                -1, cfg.m_max + 1, C
            )

            hj = h[src]  # (E, C, ncoef)
            g = jnp.einsum("eck,eqk->ecq", hj, D)  # rotate into edge frame

            out = jnp.zeros_like(g)
            for m in range(cfg.m_max + 1):
                pos_i, neg_i = midx[m]
                gp = g[:, :, pos_i] * rw[:, m][:, :, None]  # (E, C, n_lm)
                w1, w2 = so2[f"w{m}"][0], so2[f"w{m}"][1]
                if m == 0:
                    yp = jnp.einsum("eu,uv->ev", gp.reshape(gp.shape[0], -1), w1)
                    out = out.at[:, :, pos_i].set(yp.reshape(gp.shape))
                else:
                    gn = g[:, :, neg_i] * rw[:, m][:, :, None]
                    fp, fn = gp.reshape(gp.shape[0], -1), gn.reshape(gn.shape[0], -1)
                    yp = jnp.einsum("eu,uv->ev", fp, w1) - jnp.einsum("eu,uv->ev", fn, w2)
                    yn = jnp.einsum("eu,uv->ev", fp, w2) + jnp.einsum("eu,uv->ev", fn, w1)
                    out = out.at[:, :, pos_i].set(yp.reshape(gp.shape))
                    out = out.at[:, :, neg_i].set(yn.reshape(gn.shape))

            msg = jnp.einsum("ecq,eqk->eck", out, D)  # rotate back (Dᵀ = D⁻¹)

            # graph attention on the scalar channel (segment softmax)
            scal = msg[:, :, 0]  # (E, C)
            logits = jax.nn.silu(scal @ a0w + a0b) @ a1w + a1b  # (E, heads)
            logits = jnp.where(emask[:, None], logits, -1e30)
            # max-subtraction is for numerical stability only: cut the
            # gradient so pmax/segment_max need no transpose rule
            zmax = jax.ops.segment_max(jax.lax.stop_gradient(logits), dst, num_segments=n)
            mesh = shd.get_mesh()
            if mesh is not None:
                for ax in tuple(rules.batch_axes) + (
                    (rules.model_axis,) if rules.model_axis else ()
                ):
                    zmax = jax.lax.pmax(zmax, ax)
            zmax = jax.lax.stop_gradient(zmax)
            ex = jnp.exp(logits - zmax[dst]) * emask[:, None]
            denom = scatter_sum(ex, dst, n, rules)
            alpha = ex / jnp.maximum(denom[dst], 1e-20)  # (E, heads)
            alpha_c = jnp.repeat(alpha, C // cfg.n_heads, axis=-1)  # (E, C)
            msg = msg * alpha_c[:, :, None] * emask[:, None, None]
            return scatter_sum(msg, dst, n, rules)

        flat = [blk["so2"][f"w{m}"] for m in range(cfg.m_max + 1)] + [
            blk["radial"][0]["w"], blk["radial"][0]["b"],
            blk["radial"][1]["w"], blk["radial"][1]["b"],
            blk["attn"][0]["w"], blk["attn"][0]["b"],
            blk["attn"][1]["w"], blk["attn"][1]["b"],
        ]
        agg = edge_shard_map(message, rules, 3, 2 + len(flat))(
            src, dst, emask, h, pos, *flat
        )

        # per-degree channel mixing + gated nonlinearity
        upd = []
        for l in range(cfg.l_max + 1):
            sl = slice(l * l, (l + 1) * (l + 1))
            upd.append(jnp.einsum("nck,cd->ndk", agg[:, :, sl], blk["mix"][l]))
        upd = jnp.concatenate(upd, axis=-1)
        gates = _mlp_apply(blk["gate"], upd[:, :, 0]).reshape(n, C, cfg.l_max + 1)
        gate_full = jnp.repeat(
            jax.nn.sigmoid(gates),
            np.array([2 * l + 1 for l in range(cfg.l_max + 1)]),
            axis=-1,
            total_repeat_length=ncoef,
        )
        h = h + upd * gate_full

    atom_e = _mlp_apply(params["readout"], h[:, :, 0])[:, 0]
    atom_e = atom_e * batch["node_mask"].astype(atom_e.dtype)
    if "graph_ids" in batch:
        # per-graph readout; segment count comes from the target's static shape
        return jax.ops.segment_sum(atom_e, batch["graph_ids"], batch["energy"].shape[0])
    return atom_e.sum()[None]


def equiformer_loss(cfg: EquiformerConfig, rules: shd.Rules, params, batch) -> Array:
    e = equiformer_energy(cfg, rules, params, batch)
    return jnp.mean(jnp.square(e - batch["energy"]))


# ===========================================================================
# Common train-step factory
# ===========================================================================

LOSS_FNS = {
    "gcn-cora": gcn_loss,
    "schnet": schnet_loss,
    "nequip": nequip_loss,
    "equiformer-v2": equiformer_loss,
}
INIT_FNS = {
    "gcn-cora": gcn_init,
    "schnet": schnet_init,
    "nequip": nequip_init,
    "equiformer-v2": equiformer_init,
}
FWD_FNS = {
    "gcn-cora": gcn_forward,
    "schnet": schnet_energy,
    "nequip": nequip_energy,
    "equiformer-v2": equiformer_energy,
}


def make_gnn_train_step(cfg, rules: shd.Rules):
    loss_fn = LOSS_FNS[cfg.name]
    optimizer = opt_lib.get(cfg.optimizer)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(lambda p: loss_fn(cfg, rules, p, batch))(params)
        new_params, new_opt = optimizer.update(params, grads, opt_state)
        return new_params, new_opt, loss

    return train_step


def make_gnn_serve_step(cfg, rules: shd.Rules):
    fwd = FWD_FNS[cfg.name]

    def serve_step(params, batch):
        return fwd(cfg, rules, params, batch)

    return serve_step
