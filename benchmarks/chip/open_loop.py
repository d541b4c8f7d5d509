"""The one generator of serving traffic: an open-loop pose trace read from a
mix's data file (``traffic/<mix>.json``).

A mix names an arrival process and a pose process, each with its
parameters, and how many served requests the check samples::

    {"arrivals": {"kind": "paced", "rate_rps": 0.88},
     "poses": {"kind": "orbit", "sessions": 16, ...},
     "fixed_seed": 20261017, "check_requests": 8}

Each kind is a file of its own, found by name, so a new process is a new
file and a new mix of known kinds is data alone:

- ``traffic/arrivals/<kind>.py``: ``times(params, seconds, rng)`` -> sorted
  arrival seconds in [0, seconds);
- ``traffic/poses/<kind>.py``: ``make(params, n, fixed, rng, center,
  rig_radius)`` -> ([(view, far)] for the n requests, [(view, far)] poses
  that set-up requests once to prime the cache).

Two streams of randomness: ``fixed``, from the mix's ``fixed_seed``, is the
same for every run seed and draws the shape of the work (arrival times,
which viewer sends each request, dwells, which viewers are far); ``rng``,
from ``--seed``, draws the poses themselves.  So every seed offers the same
number of requests at the same times with the same pattern of new poses,
repeats and LOD rungs, and only where the cameras look changes.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path

import numpy as np

from harness import load_module

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Request:
    arrival_s: float
    view: np.ndarray        # (4, 4) float32 world -> camera
    far: bool


def kind(group: str, name: str, here: Path = HERE):
    """The module of an arrival or pose process, by name."""
    path = here / "traffic" / group / f"{name}.py"
    if not path.exists():
        raise SystemExit(f"no {group} process {path}")
    return load_module(path)


def make(traffic: dict, *, center, rig_radius: float, seconds: float,
         seed: int, here: Path = HERE):
    """-> (requests sorted by arrival, prime poses [(view, far)])."""
    fixed = np.random.default_rng([int(traffic["fixed_seed"]), 5])
    rng = np.random.default_rng([seed, 7])
    arr, pos = traffic["arrivals"], traffic["poses"]
    times = np.asarray(kind("arrivals", arr["kind"], here).times(
        arr, seconds, fixed), np.float64)
    views, prime = kind("poses", pos["kind"], here).make(
        pos, len(times), fixed, rng, center, rig_radius)
    reqs = [Request(float(t), v, f) for t, (v, f) in zip(times, views)]
    return reqs, prime
