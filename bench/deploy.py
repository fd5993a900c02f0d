"""Build a configuration's deployment through the program's own API.

The sensor positions come from the configuration's ``placement_seed``, so
every run of a configuration has the same topology, the same padded
shapes and the same compiled programs; ``--seed`` draws the field values,
the traffic and the sample that is checked.  The field values are made on
the host: at most B x n floats (2736 x 54 or 64 x 2000).
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


def radius(cfg: dict) -> float:
    return cfg["radius_scale"] * math.sqrt(100.0 / cfg["sensors"])


def positions(cfg: dict) -> np.ndarray:
    lo, hi = cfg["domain"]
    rng = np.random.default_rng(cfg["placement_seed"])
    return rng.uniform(lo, hi, size=(cfg["sensors"], cfg["dim"])).astype(np.float32)


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
