"""The LiDAR map's cell at its CPU cut: the route that ``auto`` gives, and a
leaf distance whose cancellation at metre coordinates the comparison has
to catch."""
from __future__ import annotations

import json

import jax.numpy as jnp
import numpy as np

from bench import run
from bench.tests.conftest import REPO
from repro.api import QueryEngine
from repro.core import neighbor

CELL = "rtnn-cloud-1m.knn"


def _expanded_leaf_dist_sq(p, pts):
    """``max(|q|^2 - 2 q.c + |c|^2, 0)``: the expanded form that the tree's
    leaf computed before the difference form."""
    q2 = jnp.sum(p * p, axis=-1)
    qc = jnp.sum(p[..., None, :] * pts, axis=-1)
    return jnp.maximum(q2[..., None] - 2.0 * qc
                       + jnp.sum(pts * pts, axis=-1), 0.0)


def test_cut_keeps_the_tree_route(tiny_root):
    cell = run.Cell(tiny_root, CELL)
    config = cell.config
    points = config["sweeps"] * config["rings"] * config["azimuths"]
    assert points >= QueryEngine.AUTO_TREE_MIN_POINTS
    dep = cell.kind.build(config, cell.mix, run.seed_keys(1)[0])
    assert dep.engine.cloud.size == points
    assert dep.engine.resolve_neighbor_backend(
        "nearest", config["metric"], k=config["k"]) == "tree_wavefront"
    real = json.loads((REPO / "bench" / "configs"
                       / "rtnn-cloud-1m.json").read_text())
    assert real["sweeps"] * real["rings"] * real["azimuths"] == 1 << 20


def test_map_is_in_metres_in_one_world_frame(tiny_root):
    cell = run.Cell(tiny_root, CELL)
    data = cell.kind.make_data(cell.config, cell.mix["sets"])
    m = np.asarray(data.map)
    assert np.isfinite(m).all()
    assert np.abs(m[:, :2]).max() > 20.0  # metres, not normalised
    assert m[:, 2].min() > -0.2  # nothing far below the ground
    scans = np.asarray(data.scans)
    assert scans.shape == (cell.mix["sets"], cell.mix["rows_per_call"], 3)
    again = cell.kind.make_data(cell.config, cell.mix["sets"])
    np.testing.assert_array_equal(np.asarray(again.map), m)


def test_expanded_leaf_form_comes_out_incorrect(tiny_root, cpu_chip,
                                                monkeypatch):
    monkeypatch.setattr(neighbor, "leaf_dist_sq", _expanded_leaf_dist_sq)
    out = run.run(tiny_root, CELL, 3, 1e-9, False)
    assert out["correct"] is False
    limits = run.Cell(tiny_root, CELL).config["limits"]
    assert out["checks"]["score_err"]["value"] > limits["score_err"]
