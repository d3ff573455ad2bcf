"""The one generator of traffic: the roots of a closed loop of searches.

A traffic file (``bench/traffic/<name>.json``) names the job, the warm-up
root and a root rule; this module turns it and the seed into the sequence
of roots the window runs, one search at a time, in order and cycling.

Rules (``roots.rule``):

* ``uniform`` -- Graph500's rule: ``count`` vertices of degree 1 or more
  (all of them, where fewer)
  drawn uniformly from the seed, without replacement, among those the
  warm-up search reached (a root outside the giant component would search
  a handful of vertices, yet count the whole graph's ``|V| + |E|``);
* ``near_points`` -- for each listed point of the unit square, the reached
  vertex nearest to it (graphs whose family gives ``points``), in an
  order drawn from the seed.

The warm-up root (``warmup_root``) is ``max_degree``: the vertex of
highest degree, lowest id first.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def warmup_root(traffic: dict, degrees: np.ndarray) -> int:
    rule = traffic["warmup_root"]
    if rule != "max_degree":
        raise ValueError(f"unknown warm-up root rule {rule!r}")
    return int(np.argmax(degrees))


def roots(traffic: dict, seed: int, reached: np.ndarray,
          degrees: np.ndarray, points=None) -> list:
    """The roots of one run; ``reached`` is the warm-up search's boolean
    reach over the vertices, ``points`` the family's ``(x, y)`` if any."""
    spec = traffic["roots"]
    component = np.flatnonzero(reached & (degrees >= 1))
    rng = np.random.default_rng(seed)
    if spec["rule"] == "uniform":
        out = rng.choice(component, min(spec["count"], component.size),
                         replace=False)
    elif spec["rule"] == "near_points":
        if points is None:
            raise ValueError("near_points needs a graph family with points")
        x, y = (np.asarray(a, np.float64)[component] for a in points)
        out = [component[np.argmin((x - px) ** 2 + (y - py) ** 2)]
               for px, py in spec["points"]]
    else:
        raise ValueError(f"unknown root rule {spec['rule']!r}")
    return [int(out[i]) for i in rng.permutation(len(out))]

