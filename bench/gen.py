"""Traffic generator: one general generator that every traffic mix file
under ``bench/traffic/`` parameterises.

A mix is a request workload. Every ToR hosts ``streams`` independent
request streams, each a renewal process whose gaps follow the mix's
``interarrival_us`` distribution; each request is one flow from its
stream's ToR whose bytes are the sum of the mix's ``flow_bytes`` parts
(for the KV-store mix: key plus value). The number of streams per ToR is
set so that the mean offered bytes are ``load`` x the ToR's circuit
capacity. Flows are chopped into ``cell_bytes`` cells paced at one
circuit's worth of cells per slice, and kept in arrival order up to the
deployment's fixed population ``packets``.

The population is the same for every seed: arrival times, sizes and each
flow's stream are one draw from the mix's own ``size_seed``, cut at
``packets`` cells, so every seed offers the same bytes in every slice and
the fabric program sees the same shapes and the same flow count (a static
argument of its jitted scan). The seed of :func:`workload` relabels the
ToRs (a permutation, so every ToR keeps one stream group's load) and draws
every flow's destination.

A run's workloads (:func:`pool`) are the same for every run seed up to
the names of the ToRs: they are drawn from the mix's ``size_seed``, and
the run seed turns each by a cyclic shift of the ToR labels. A
round-robin schedule maps onto itself under such a shift, so every seed
gives the fabric the same work, packet for packet, and the window's rate
does not depend on the seed the run drew.

Distributions, each a dict with one of (MATLAB's parameterisation, as
the source papers fit them):

* ``{"gev": [mu, sigma, k]}``: generalized extreme value,
  ``F(x) = exp(-(1 + k (x - mu) / sigma) ** (-1 / k))``;
* ``{"gpareto": [theta, sigma, k]}``: generalized Pareto,
  ``F(x) = 1 - (1 + k (x - theta) / sigma) ** (-1 / k)``,

and optionally ``"clip": [lo, hi]``.

The result is a dict of numpy arrays in the fabric's structure-of-arrays
layout: ``src dst size t_inject flow seq`` (int32) and ``is_eleph`` (bool).
"""
from __future__ import annotations

import math

import numpy as np

FIELDS = ("src", "dst", "size", "t_inject", "flow", "seq", "is_eleph")


def draw(rng: np.random.Generator, dist: dict, shape) -> np.ndarray:
    """Values of ``dist`` (see the module doc), by inversion of its CDF."""
    if "gev" in dist:
        mu, sigma, k = dist["gev"]
        x = mu + sigma * ((-np.log(rng.random(shape))) ** (-k) - 1.0) / k
    elif "gpareto" in dist:
        theta, sigma, k = dist["gpareto"]
        x = theta + sigma * ((1.0 - rng.random(shape)) ** (-k) - 1.0) / k
    else:
        raise ValueError(f"unknown distribution {dist}")
    if "clip" in dist:
        x = np.clip(x, *dist["clip"])
    return x


def mean(dist: dict) -> float:
    """The distribution's mean (before any clip)."""
    if "gev" in dist:
        mu, sigma, k = dist["gev"]
        return mu + sigma * (math.gamma(1.0 - k) - 1.0) / k
    if "gpareto" in dist:
        theta, sigma, k = dist["gpareto"]
        return theta + sigma / (1.0 - k)
    raise ValueError(f"unknown distribution {dist}")


def streams_per_tor(deployment: dict, mix: dict) -> int:
    """Request streams per ToR that offer ``load`` x its circuit capacity."""
    capacity = (deployment["uplinks"] * deployment["slice_bytes"]
                / deployment["slice_us"])                      # bytes per us
    per_stream = (sum(mean(d) for d in mix["flow_bytes"])
                  / mean(mix["interarrival_us"]))
    return max(1, round(mix["load"] * capacity / per_stream))


def population(deployment: dict, mix: dict):
    """The seed-independent part: per-flow (bytes, cells, start slice, ToR
    before relabelling, elephant flag), in arrival order, cut so that the
    cells number exactly ``packets``."""
    n_nodes = deployment["tors"]
    P = deployment["packets"]
    cell = mix["cell_bytes"]
    S = streams_per_tor(deployment, mix)
    rng = np.random.default_rng(mix["size_seed"])
    gap = mean(mix["interarrival_us"])
    # every flow has a cell at least, so P flows' time suffices; draw each
    # stream's gaps past that horizon
    horizon = P * gap / (n_nodes * S)
    n = int(horizon / gap * 1.5) + 16
    t = np.cumsum(draw(rng, mix["interarrival_us"], (n_nodes * S, n)), axis=1)
    if (t[:, -1] < horizon).any():
        raise ValueError("a stream's draws end before the horizon")
    stream, k = np.nonzero(t < horizon)
    at = t[stream, k]
    order = np.argsort(at, kind="stable")
    at, tor = at[order], stream[order] // S
    size = np.rint(sum(draw(rng, d, at.size) for d in mix["flow_bytes"]))
    size = np.maximum(size, 1).astype(np.int64)
    cells = -(-size // cell)
    end = np.cumsum(cells)
    if end[-1] < P:
        raise ValueError(f"the mix draws {end[-1]} cells, fewer than the "
                         f"population of {P}")
    F = int(np.searchsorted(end, P)) + 1           # flows touched by the cut
    kept = cells[:F].copy()
    kept[-1] -= end[F - 1] - P
    return dict(size=size[:F], cells=kept, tor=tor[:F],
                t_start=(at[:F] // deployment["slice_us"]).astype(np.int64),
                is_eleph=size[:F] >= mix["elephant_bytes"])


def workload(deployment: dict, mix: dict, seed, pop=None) -> dict:
    """One workload of the fixed population for ``seed`` (whatever
    ``np.random.default_rng`` takes)."""
    pop = population(deployment, mix) if pop is None else pop
    n_nodes = deployment["tors"]
    cell = mix["cell_bytes"]
    per_slice = max(1, deployment["slice_bytes"] // cell)
    rng = np.random.default_rng(seed)
    F = pop["size"].size
    src = rng.permutation(n_nodes)[pop["tor"]]
    dst = rng.integers(0, n_nodes - 1, size=F)
    dst = dst + (dst >= src)                        # any ToR but the source
    cells = pop["cells"]
    flow = np.repeat(np.arange(F), cells)
    first = np.cumsum(cells) - cells
    seq = np.arange(flow.size) - np.repeat(first, cells)
    size = np.minimum(pop["size"][flow] - seq * cell, cell)
    i32 = lambda a: np.ascontiguousarray(a, dtype=np.int32)
    return dict(src=i32(src[flow]), dst=i32(dst[flow]), size=i32(size),
                t_inject=i32(pop["t_start"][flow] + seq // per_slice),
                flow=i32(flow), seq=i32(seq),
                is_eleph=np.ascontiguousarray(pop["is_eleph"][flow]))


def pool(deployment: dict, mix: dict, seed: int, size: int,
         pop=None) -> list[dict]:
    """A run's ``size`` workloads for ``seed``: workload k is drawn from
    ``(size_seed, k)``, the same for every seed, and its ToR labels are
    turned by a shift drawn from ``seed`` (node i becomes i + c mod N)."""
    pop = population(deployment, mix) if pop is None else pop
    n_nodes = deployment["tors"]
    shifts = np.random.default_rng(seed).integers(0, n_nodes, size)
    out = []
    for k, c in enumerate(shifts):
        wl = workload(deployment, mix, [mix["size_seed"], k], pop)
        for f in ("src", "dst"):
            wl[f] = ((wl[f] + c) % n_nodes).astype(np.int32)
        out.append(wl)
    return out
