"""Build a configuration's deployment through the program's own API.

The sensor positions come from the configuration's ``placement_seed``, so
every run of a configuration has the same topology, the same padded
shapes and the same compiled programs; ``--seed`` draws the field values,
the traffic and the sample that is checked.  The field values are made on
the host: at most B x n floats (2736 x 54 or 64 x 2000).

The configuration's ``placement`` says how the ``sensors`` positions are
drawn on the square ``domain`` ([lo, hi] on each of ``dim`` axes):

  ``"uniform"``  uniform on the domain.
  ``{"kind": "clustered", "centres": C, "zipf_s": s, "sigma": sigma,
  "background": beta}``  a Thomas (Neyman-Scott) cluster process, as
      sensors placed where people live:
      ``centres``     C >= 1 cluster centres, uniform on the domain;
      ``zipf_s``      s >= 0: the centre of rank r (1..C, in the order they
                      are drawn) takes a share of the clustered sensors
                      proportional to 1 / r**s (Zipf's law of city sizes);
      ``sigma``       sigma > 0: a clustered sensor lies at its centre plus
                      a Gaussian offset of this standard deviation per axis;
      ``background``  0 <= beta <= 1: each sensor is uniform on the domain
                      with this probability, else clustered.
      A clustered draw that falls outside the domain is drawn again around
      the same centre, so there are exactly ``sensors`` positions and the
      background share is binomial.
Any other value fails at set-up, naming the key.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


def radius(cfg: dict) -> float:
    return cfg["radius_scale"] * math.sqrt(100.0 / cfg["sensors"])


CLUSTERED = ("kind", "centres", "zipf_s", "sigma", "background")


def layout(cfg: dict) -> tuple:
    """(positions (n, d) float32, centre (n,) int): the centre each sensor
    was drawn around, -1 for a uniform (background) sensor."""
    lo, hi = cfg["domain"]
    n, d = cfg["sensors"], cfg["dim"]
    rng = np.random.default_rng(cfg["placement_seed"])
    place = cfg["placement"]
    if place == "uniform":
        pos = rng.uniform(lo, hi, size=(n, d)).astype(np.float32)
        return pos, np.full(n, -1)
    if not isinstance(place, dict) or place.get("kind") != "clustered":
        raise ValueError(f"placement: unknown placement {place!r}; "
                         "'uniform' or {'kind': 'clustered', ...}")
    odd = sorted(set(place) ^ set(CLUSTERED))
    if odd:
        raise ValueError(f"placement: keys {odd} missing or unknown; a clustered "
                         f"placement has exactly {list(CLUSTERED)}")
    c, s = int(place["centres"]), float(place["zipf_s"])
    sigma, beta = float(place["sigma"]), float(place["background"])
    for key, bad in (("centres", c < 1 or c != place["centres"]), ("zipf_s", s < 0),
                     ("sigma", not sigma > 0), ("background", not 0 <= beta <= 1)):
        if bad:
            raise ValueError(f"placement.{key}: {place[key]!r} is out of range")
    centres = rng.uniform(lo, hi, size=(c, d))
    weight = 1.0 / np.arange(1, c + 1) ** s
    centre = np.where(rng.random(n) < beta, -1, rng.choice(c, size=n, p=weight / weight.sum()))
    pos = rng.uniform(lo, hi, size=(n, d))
    todo = np.nonzero(centre >= 0)[0]
    while len(todo):
        pos[todo] = centres[centre[todo]] + sigma * rng.normal(size=(len(todo), d))
        todo = todo[np.any((pos[todo] < lo) | (pos[todo] > hi), axis=1)]
    return pos.astype(np.float32), centre


def positions(cfg: dict) -> np.ndarray:
    return layout(cfg)[0]


def streams(seed: int, n: int) -> list:
    """``n`` independent generators from one (possibly > 64-bit) seed."""
    return [np.random.default_rng(s) for s in np.random.SeedSequence(seed).spawn(n)]


class Fields:
    """B smooth random fields: a * sin(pi fx x0 + px) * cos(pi fy x1 + py)."""

    def __init__(self, b: int, rng: np.random.Generator, noise: float):
        self.amp = rng.uniform(0.5, 1.5, b)
        self.fx = rng.uniform(0.5, 2.0, b)
        self.fy = rng.uniform(0.5, 2.0, b)
        self.px = rng.uniform(0.0, 2 * np.pi, b)
        self.py = rng.uniform(0.0, 2 * np.pi, b)
        self.noise = noise

    def value(self, b, x) -> np.ndarray:
        """Noise-free value of fields ``b`` (m,) at points ``x`` (m, d)."""
        x = np.asarray(x, np.float64)
        return self.amp[b] * np.sin(np.pi * self.fx[b] * x[:, 0] + self.px[b]) * np.cos(
            np.pi * self.fy[b] * x[:, 1] + self.py[b]
        )

    def readings(self, pos: np.ndarray, rng: np.random.Generator) -> np.ndarray:
        """(B, n) noisy readings of every field at every sensor."""
        b = len(self.amp)
        bb = np.repeat(np.arange(b), len(pos))
        xx = np.tile(pos, (b, 1))
        clean = self.value(bb, xx).reshape(b, len(pos))
        return (clean + self.noise * rng.normal(size=clean.shape)).astype(np.float32)


@dataclasses.dataclass
class Network:
    """The seed-independent part: positions, radius and padded topology."""

    cfg: dict
    pos: np.ndarray
    radius: float
    d_max: int
    topology: object

    @property
    def n(self) -> int:
        return int(self.pos.shape[0])

    @property
    def fields(self) -> int:
        return int(self.cfg["fields"])


def network(cfg: dict) -> Network:
    """Host build of the topology (the program's O(n^2) builders)."""
    from repro.core import build_topology
    from repro.core.topology import geometric_adjacency

    pos = positions(cfg)
    r = radius(cfg)
    deg_max = int(geometric_adjacency(pos, r).sum(1).max())
    d_max = deg_max + int(cfg["stream_lanes"])
    topo = build_topology(pos, r, d_max=d_max, n_max=cfg["sensors"] + cfg["spares"])
    return Network(cfg=cfg, pos=pos, radius=r, d_max=d_max, topology=topo)


def problem(net: Network, ys: np.ndarray):
    """The batched SN-Train problem for readings ``ys`` (B, n)."""
    import jax
    import jax.numpy as jnp

    from repro.core import Kernel, make_batch_problem

    cfg = net.cfg
    prob = make_batch_problem(
        net.topology, Kernel(cfg["kernel"], gamma=cfg["gamma"]), ys,
        jnp.full((net.n,), cfg["lambda"], jnp.float32),
    )
    jax.block_until_ready(prob)
    return prob


def with_readings(prob, ys: np.ndarray):
    """The same problem with other readings (only ``y`` depends on them)."""
    import jax.numpy as jnp

    pad = prob.n - ys.shape[1]
    y = np.concatenate([ys, np.zeros((ys.shape[0], pad), np.float32)], axis=1)
    return dataclasses.replace(prob, y=jnp.asarray(y))


def query_box(net: Network) -> tuple:
    """Queries stay inside the sensors' bounding box, which the serving
    plan's exactness contract covers, inset by 1%."""
    lo = net.pos.min(0)
    hi = net.pos.max(0)
    pad = 0.01 * (hi - lo)
    return lo + pad, hi - pad
