"""Declarative sharding rules + the active-mesh context.

Three pieces, used by every model, launch cell, and strategy executor:

* :class:`Rules` — a declarative table of ``name pattern → PartitionSpec``
  sharding rules (fnmatch wildcards, first match wins, ``"*"`` fallback =
  replicated), derived from a mesh's axis names.  Named accessors
  (``act_btd()``, ``p_attn_in()``, ``kv_cache()``, ...) are thin lookups
  into that table, so a config can override placement for any tensor by
  name without touching model code.
* :func:`get_mesh` / :func:`use_mesh` — the context-managed active mesh.
  Model code never takes a mesh parameter; it asks for the ambient one.
* :func:`constrain` — ``with_sharding_constraint`` that fits the spec to
  the value's shape and is a **no-op off-mesh**, so the same model code
  runs unconstrained on one CPU device for smoke tests.

Axis convention (DESIGN.md §6): ``pod``/``data`` carry batch / site /
ZeRO sharding ("sites" in the paper's sense are the ``data`` axis);
``model`` carries tensor/expert/KV-sequence parallelism.
"""

from __future__ import annotations

import contextlib
import dataclasses
import fnmatch
import threading
from typing import Mapping

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_BATCH_AXIS_NAMES = ("pod", "data")
_MODEL_AXIS_NAME = "model"

# --------------------------------------------------------------------------
# Active mesh context
# --------------------------------------------------------------------------

_STATE = threading.local()


def get_mesh() -> Mesh | None:
    """The active mesh set by :func:`use_mesh`, or None (single-device)."""
    stack = getattr(_STATE, "stack", None)
    return stack[-1] if stack else None


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None):
    """Context manager installing ``mesh`` as the ambient mesh."""
    stack = getattr(_STATE, "stack", None)
    if stack is None:
        stack = _STATE.stack = []
    stack.append(mesh)
    try:
        yield mesh
    finally:
        stack.pop()


# --------------------------------------------------------------------------
# Spec fitting
# --------------------------------------------------------------------------


def _entry_names(entry) -> tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def _fit_entry(axis_sizes: Mapping[str, int], entry, dim: int):
    """Fit one spec entry to one dimension: drop axes the mesh does not
    have, then degrade (innermost-first) until the shard count divides the
    dimension; fully non-divisible entries degrade to replicated."""
    names = [n for n in _entry_names(entry) if n in axis_sizes]
    while names:
        size = 1
        for n in names:
            size *= axis_sizes[n]
        if size <= max(dim, 0) and dim % size == 0:
            break
        names.pop()
    if not names:
        return None
    return names[0] if len(names) == 1 else tuple(names)


def _fit(axis_sizes: Mapping[str, int], spec, shape) -> P:
    entries = list(tuple(spec)) if spec is not None else []
    entries = entries[: len(shape)] + [None] * (len(shape) - len(entries))
    return P(*(_fit_entry(axis_sizes, e, d) for e, d in zip(entries, shape)))


def fit_spec(mesh: Mesh | None, spec, shape) -> P:
    """Fit ``spec`` to a concrete ``shape`` on ``mesh``: pad/truncate to the
    rank and degrade non-divisible dims to replicated (e.g. granite's
    vocab 49155 on a 16-way model axis)."""
    if mesh is None:
        return P(*([None] * len(shape)))
    sizes = {n: int(mesh.shape[n]) for n in mesh.axis_names}
    return _fit(sizes, spec, shape)


def constrain(x, rule):
    """Apply a sharding constraint; identity when no mesh is active.

    ``rule`` is a PartitionSpec (or None, or a rule *name* resolved through
    the active mesh's default :class:`Rules` table).  The spec is fitted to
    ``x.shape`` first, so callers never have to special-case non-divisible
    or lower-rank tensors.
    """
    mesh = get_mesh()
    if mesh is None or rule is None:
        return x
    if isinstance(rule, str):
        rule = Rules.from_mesh(mesh).spec(rule)
    fitted = fit_spec(mesh, rule, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, fitted))


# --------------------------------------------------------------------------
# Rules
# --------------------------------------------------------------------------


def _default_table(batch, model, flat) -> tuple[tuple[str, P], ...]:
    """The built-in name→spec rule table.

    ``batch`` is the batch entry (axis name, tuple of names, or None),
    ``model`` the tensor-parallel axis (or None), ``flat`` every mesh axis
    flattened (edge/site sharding for the RPQ and GNN executors).
    First match wins; ``"*"`` is the replicated fallback.
    """
    return (
        # -- activations ----------------------------------------------------
        ("act/btd", P(batch, None, None)),
        ("act/bthd", P(batch, None, model, None)),
        ("act/ffn", P(batch, None, model)),
        ("act/logits", P(batch, None, model)),
        # -- stacked per-layer LM params (leading layer dim) ----------------
        ("params/*/attn/w[qkv]", P(None, None, model)),
        ("params/*/attn/wo", P(None, model, None)),
        ("params/*/mlp/w_gate", P(None, None, model)),
        ("params/*/mlp/w_up", P(None, None, model)),
        ("params/*/mlp/w_down", P(None, model, None)),
        ("params/*/moe/router", P(None, None, None)),
        ("params/*/moe/w*", P(None, model, None, None)),
        ("params/embed", P(model, None)),
        ("params/lm_head", P(None, model)),
        # -- embedding tables (DLRM row sharding) ---------------------------
        ("params/table_rows", P(model, None)),
        # -- KV cache (leading layer dim) -----------------------------------
        ("cache/kv", P(None, batch, None, None, None)),
        ("cache/kv_seq", P(None, batch, model, None, None)),
        # -- graph edges: sites = every axis, flattened ---------------------
        ("edges", P(flat)),
        # -- fallback -------------------------------------------------------
        ("*", P()),
    )


@dataclasses.dataclass(frozen=True)
class Rules:
    """Sharding rules for one mesh shape.

    ``batch_axes`` are the data-parallel axes (``pod``/``data`` — the
    paper's *sites*); ``model_axis`` is the tensor/expert-parallel axis.
    ``table`` maps name patterns to PartitionSpecs; :meth:`spec` resolves a
    name through it with wildcard matching and the ``"*"`` fallback.
    """

    batch_axes: tuple[str, ...]
    model_axis: str | None
    axis_sizes: Mapping[str, int]
    table: tuple[tuple[str, P], ...]

    @classmethod
    def from_mesh(cls, mesh: Mesh | None, overrides: Mapping[str, P] | None = None) -> "Rules":
        """Derive rules from a mesh's axis names (None → all-replicated).

        ``overrides`` prepends extra ``pattern → spec`` rules that win over
        the built-in table.
        """
        if mesh is None:
            batch_axes: tuple[str, ...] = ()
            model_axis = None
            axis_sizes: dict[str, int] = {}
        else:
            names = tuple(mesh.axis_names)
            batch_axes = tuple(n for n in names if n in _BATCH_AXIS_NAMES)
            model_axis = _MODEL_AXIS_NAME if _MODEL_AXIS_NAME in names else None
            axis_sizes = {n: int(mesh.shape[n]) for n in names}
        batch = _batch_entry(batch_axes)
        flat = tuple(batch_axes) + ((model_axis,) if model_axis else ())
        table = _default_table(batch, model_axis, flat or None)
        if overrides:
            table = tuple(overrides.items()) + table
        return cls(batch_axes, model_axis, axis_sizes, table)

    # -- core lookup -------------------------------------------------------

    def spec(self, name: str, shape=None) -> P:
        """Resolve ``name`` through the rule table (first fnmatch wins);
        with ``shape``, fit the result to it."""
        for pattern, spec in self.table:
            if fnmatch.fnmatchcase(name, pattern):
                return self.fit(spec, shape) if shape is not None else spec
        return P()

    def fit(self, spec, shape) -> P:
        """Fit a spec to a shape (degrade non-divisible dims; pad rank)."""
        return _fit(self.axis_sizes, spec, shape)

    def spec_divisor(self, spec, dim: int) -> int:
        """Shard count of dimension ``dim`` under ``spec`` (1 if unsharded)."""
        entries = tuple(spec)
        entry = entries[dim] if dim < len(entries) else None
        size = 1
        for n in _entry_names(entry):
            size *= self.axis_sizes.get(n, 1)
        return size

    # -- derived axis facts --------------------------------------------------

    @property
    def batch(self):
        """The batch-dim spec entry: one axis name, a tuple, or None."""
        return _batch_entry(self.batch_axes)

    @property
    def model_size(self) -> int:
        """Shard count of the model axis (0 when no mesh / no model axis)."""
        if self.model_axis is None:
            return 0
        return self.axis_sizes.get(self.model_axis, 0)

    # -- named accessors (thin table lookups) --------------------------------

    def act_btd(self) -> P:
        return self.spec("act/btd")

    def act_bthd(self) -> P:
        return self.spec("act/bthd")

    def act_ffn(self) -> P:
        return self.spec("act/ffn")

    def logits(self) -> P:
        return self.spec("act/logits")

    def p_attn_in(self) -> P:
        return self.spec("params/layers/attn/wq")

    def p_attn_out(self) -> P:
        return self.spec("params/layers/attn/wo")

    def p_mlp_in(self) -> P:
        return self.spec("params/layers/mlp/w_gate")

    def p_mlp_out(self) -> P:
        return self.spec("params/layers/mlp/w_down")

    def p_moe_experts(self) -> P:
        return self.spec("params/layers/moe/w_gate")

    def p_router(self) -> P:
        return self.spec("params/layers/moe/router")

    def p_embed(self) -> P:
        return self.spec("params/embed")

    def p_lm_head(self) -> P:
        return self.spec("params/lm_head")

    def p_table_rows(self) -> P:
        return self.spec("params/table_rows")

    def kv_cache(self) -> P:
        return self.spec("cache/kv")

    def kv_cache_seq_sharded(self) -> P:
        return self.spec("cache/kv_seq")

    def edges(self) -> P:
        return self.spec("edges")


def _batch_entry(batch_axes: tuple[str, ...]):
    if not batch_axes:
        return None
    if len(batch_axes) == 1:
        return batch_axes[0]
    return tuple(batch_axes)
