"""The numbers that decide ``correct``: what the timed path produced
against the plain reference.

Models are plain trees: ``{"cnn": {"stages": ({"w", "b"}, ...)}, "beta"}``
with a leading member dim for members. Each function returns one number;
``judge`` holds every number to its limit.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def _leaves(tree) -> List[np.ndarray]:
    if isinstance(tree, dict):
        return [x for k in sorted(tree) for x in _leaves(tree[k])]
    if isinstance(tree, (list, tuple)):
        return [x for t in tree for x in _leaves(t)]
    return [np.asarray(tree, np.float64)]


def _models(run: dict) -> List[Tuple[list, np.ndarray]]:
    """(CNN leaves, β) of every member and of the averaged model."""
    m = run["members"]
    k = m["beta"].shape[0]
    out = [([leaf[i] for leaf in _leaves(m["cnn"])], np.asarray(
        m["beta"][i], np.float64)) for i in range(k)]
    out.append((_leaves(run["averaged"]["cnn"]),
                np.asarray(run["averaged"]["beta"], np.float64)))
    return out


def beta_max_err(got: dict, want: dict) -> float:
    """Worst, over every member and the averaged model, of
    max |β_got − β_ref| / max |β_ref|."""
    return max(float(np.max(np.abs(g - w)) / max(np.max(np.abs(w)), 1e-30))
               for (_, g), (_, w) in zip(_models(got), _models(want)))


def param_max_err(got: dict, want: dict) -> float:
    """Worst, over every leaf of every member and the averaged model, of
    max |θ_got − θ_ref| / max |θ_ref|."""
    worst = 0.0
    for (g, _), (w, _) in zip(_models(got), _models(want)):
        for a, b in zip(g, w):
            worst = max(worst, float(np.max(np.abs(a - b))
                                     / max(np.max(np.abs(b)), 1e-30)))
    return worst


def score_gap(got: np.ndarray, want: np.ndarray) -> float:
    """max |served − reference| over the sampled (k, n, C) member scores,
    over the largest |reference score|."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def label_gap(labels: np.ndarray, want: np.ndarray) -> float:
    """The widest gap, over the sampled requests, by which the reference's
    combined score of the served label lies below its best, over the
    largest |reference combined score|: 0 where every served label is the
    reference's, and small where a near tie flips."""
    want = np.asarray(want, np.float64)
    served = want[np.arange(len(labels)), np.asarray(labels)]
    return float(np.max(want.max(-1) - served)
                 / max(np.max(np.abs(want)), 1e-30))


NUMBERS = {"beta_max_err": beta_max_err, "param_max_err": param_max_err}


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, dict]]:
    """Every number with its limit; correct when each is a finite number
    at or under its limit."""
    shown, ok = {}, True
    for name, limit in limits.items():
        v = values.get(name)
        good = v is not None and np.isfinite(v) and v <= limit
        ok = ok and bool(good)
        shown[name] = {"value": None if v is None else float(v),
                       "limit": float(limit)}
    return ok, shown
