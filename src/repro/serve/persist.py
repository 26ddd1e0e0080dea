"""Stage-A plan-cache persistence — warm restarts for the serving layer.

Two-stage compilation (PR 5, :mod:`repro.core.plans`) made the expensive,
graph-dependent half of an executor build — tile packing into staged
block-sparse tensors — a cache entry.  That cache dies with the process,
so every restart of a serving site pays the full cold Stage-A build
before its first query.  This module serializes the *packed* Stage-A
artifacts (the products of ``pack_blocks``: the global staged tile
tensor and the per-site staged slabs) plus enough metadata to validate
them, and restores them into a fresh :class:`~repro.core.plans.GraphPlanStore`
on startup.

What makes a snapshot valid for a placement is *content*, not object
identity: the store keys by ``id(placement)``, so a snapshot carries a
SHA-256 **fingerprint** of the placement's full content (node count,
label vocabulary, edge triples, per-site edge ids) and the loader
re-keys entries against the new process's placement object only when
the fingerprints match.  Any mismatch — different graph, different
partition, different format version, truncated file — falls back to a
cold build by returning ``False``; a warm restore must never serve
answers for a graph it was not built from.

Derived Stage-A artifacts (device-granular merges, shape buckets, padded
site arrays, degree vectors) are *not* serialized: they rebuild from the
restored slabs without any tile packing (asserted via ``BUILD_COUNTERS``
in ``tests/test_serve_aio.py``), and keeping the snapshot to the packing
products keeps it small and format-stable.

The on-disk format is a pickle (stdlib, no new deps) of numpy payloads —
treat snapshot files like any other local cache: they are not an
interchange format and should not be loaded from untrusted sources.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import tempfile
from typing import Any

import numpy as np

import jax.numpy as jnp

from repro.core.plans import GraphPlanStore
from repro.graph.partition import Placement
from repro.graph.structure import LabeledGraph
from repro.kernels.frontier import ops as fops
from repro.kernels.frontier.ref import tile_shape

# 2: the uint32 store's tiles are (ceil(B/32), B) lane-dense bit-plane
# rows (src bits, dst lanes); a version-1 snapshot holds (B, ceil(B/32))
# dst-bit tiles, which would read as a different graph, so it rebuilds
FORMAT_VERSION = 2

# the pack_blocks products; everything else in the store derives from
# these (or from the raw placement) without packing a single tile
PERSISTED_KINDS = ("staged_graph", "staged_sharded")


# ---------------------------------------------------------------------------
# content fingerprints
# ---------------------------------------------------------------------------


def graph_fingerprint(graph: LabeledGraph) -> str:
    """SHA-256 of the graph's full content (nodes, vocabulary, edges)."""
    h = hashlib.sha256()
    h.update(np.int64(graph.n_nodes).tobytes())
    h.update("\x00".join(graph.labels).encode())
    for arr in (graph.src, graph.lbl, graph.dst):
        h.update(np.ascontiguousarray(arr, np.int64).tobytes())
    return h.hexdigest()


def placement_fingerprint(placement: Placement) -> str:
    """SHA-256 of the placement's content: the graph plus the per-site
    edge-id partition (replication included) — everything Stage A reads."""
    h = hashlib.sha256()
    h.update(graph_fingerprint(placement.graph).encode())
    h.update(np.int64(placement.n_sites).tobytes())
    for eids in placement.site_edges:
        h.update(np.ascontiguousarray(eids, np.int64).tobytes())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# artifact <-> payload codecs (numpy-only payloads; device arrays rehydrate)
# ---------------------------------------------------------------------------


def _encode_offsets(offsets: dict) -> dict:
    return {
        key: (int(base), np.asarray(rows), np.asarray(cols))
        for key, (base, rows, cols) in offsets.items()
    }


def _encode(kind: str, artifact: Any) -> dict:
    if kind == "staged_graph":
        sg: fops.StagedGraph = artifact
        return {
            "n_nodes": sg.n_nodes, "v_pad": sg.v_pad, "block_size": sg.block_size,
            "tiles": np.asarray(sg.tiles), "offsets": _encode_offsets(sg.offsets),
            "tile_dtype": sg.tile_dtype,
        }
    if kind == "staged_sharded":
        ss: fops.StagedShardedGraph = artifact
        return {
            "n_sites": ss.n_sites, "n_nodes": ss.n_nodes, "v_pad": ss.v_pad,
            "block_size": ss.block_size,
            "site_tiles": [np.asarray(t) for t in ss.site_tiles],
            "site_offsets": [_encode_offsets(o) for o in ss.site_offsets],
            "tile_dtype": ss.tile_dtype,
        }
    raise ValueError(f"unpersistable Stage-A kind {kind!r}")


def _check_tiles(tiles: np.ndarray, block_size: int, tile_dtype: str) -> np.ndarray:
    """A payload's tiles, refused (``ValueError``) unless each tile has
    the shape of ``tile_dtype``'s layout at ``block_size``."""
    tiles = np.asarray(tiles)
    if tiles.ndim != 3 or tiles.shape[1:] != tile_shape(block_size, tile_dtype):
        raise ValueError(f"tiles {tiles.shape} are not the {tile_dtype} layout at B={block_size}")
    return tiles


def _decode(kind: str, payload: dict) -> Any:
    dtype = payload["tile_dtype"]
    if kind == "staged_graph":
        return fops.StagedGraph(
            n_nodes=payload["n_nodes"], v_pad=payload["v_pad"],
            block_size=payload["block_size"],
            tiles=jnp.asarray(_check_tiles(payload["tiles"], payload["block_size"], dtype)),
            offsets=dict(payload["offsets"]),
            tile_dtype=dtype,
        )
    if kind == "staged_sharded":
        return fops.StagedShardedGraph(
            n_sites=payload["n_sites"], n_nodes=payload["n_nodes"],
            v_pad=payload["v_pad"], block_size=payload["block_size"],
            site_tiles=tuple(
                _check_tiles(t, payload["block_size"], dtype) for t in payload["site_tiles"]
            ),
            site_offsets=tuple(dict(o) for o in payload["site_offsets"]),
            tile_dtype=dtype,
        )
    raise ValueError(f"unpersistable Stage-A kind {kind!r}")


# ---------------------------------------------------------------------------
# save / load
# ---------------------------------------------------------------------------


def save_stage_a(
    store: GraphPlanStore, placement: Placement, path: str, stats_epoch: int = 0
) -> dict:
    """Snapshot every persistable Stage-A entry anchored to ``placement``
    (or its graph) to ``path``.  Returns a small manifest
    (``{"n_entries", "fingerprint", "stats_epoch"}``).  The write is
    atomic (tmp file + rename) so a crash mid-save never leaves a
    truncated snapshot for the next restart to trip over."""
    entries = []
    for anchor_name, anchor in (("placement", placement), ("graph", placement.graph)):
        for portable_key, artifact, _epoch in store.export_entries(anchor):
            if portable_key[0] not in PERSISTED_KINDS:
                continue
            entries.append(
                (anchor_name, portable_key, _encode(portable_key[0], artifact))
            )
    blob = {
        "format_version": FORMAT_VERSION,
        "fingerprint": placement_fingerprint(placement),
        "stats_epoch": int(stats_epoch),
        "entries": entries,
    }
    d = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            pickle.dump(blob, f, protocol=pickle.HIGHEST_PROTOCOL)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise
    return {
        "n_entries": len(entries),
        "fingerprint": blob["fingerprint"],
        "stats_epoch": blob["stats_epoch"],
    }


def load_stage_a(
    store: GraphPlanStore, placement: Placement, path: str, stats_epoch: int = 0
) -> bool:
    """Warm-restore a Stage-A snapshot into ``store``, re-keyed to
    ``placement`` at the caller's current ``stats_epoch``.

    Returns ``True`` only when the snapshot exists, parses, carries the
    current format version, and its content fingerprint matches this
    placement exactly; every other outcome returns ``False`` and leaves
    the store untouched, so the caller's cold-build path runs as if no
    snapshot existed."""
    try:
        with open(path, "rb") as f:
            blob = pickle.load(f)
    except (OSError, pickle.UnpicklingError, EOFError, AttributeError):
        return False
    if not isinstance(blob, dict) or blob.get("format_version") != FORMAT_VERSION:
        return False
    if blob.get("fingerprint") != placement_fingerprint(placement):
        return False
    try:
        decoded = [
            (anchor_name, portable_key, _decode(portable_key[0], payload))
            for anchor_name, portable_key, payload in blob["entries"]
        ]
    except (KeyError, ValueError, TypeError):
        return False
    for anchor_name, portable_key, artifact in decoded:
        anchor = placement if anchor_name == "placement" else placement.graph
        store.install_entry(portable_key, anchor, stats_epoch, artifact)
    return True
