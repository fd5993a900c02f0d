"""The one traffic generator: reads a mix's parameters and draws its
schedule from the seed.

A mix is a data file ``traffic/<name>.json``.  Its ``loop`` says how the
harness drives it:

  ``open``    independent users: query requests on a fixed schedule at
              ``rate_per_s`` (open loop), plus measurement arrivals from
              every sensor once per reporting interval;
  ``closed``  ``clients`` callers that each wait for an answer before
              sending the next request;
  ``solve``   repeated solves of all fields from the initial state.

A request kind (``points`` or ``tile``) draws its points uniform on the
query box, or, with ``"near": "sensors"`` and ``"jitter": j``, around the
sensors, as people ask where they and the sensors are: each point (a
tile's centre) is a sensor drawn from the request generator plus Gaussian
noise of ``j`` x the box's side per axis, clipped into the box (a tile is
shifted to lie inside it).  The box is ``deploy.query_box``, the serving
plan's exactness contract.

Every seed gets the same work: the number of requests in a window is
fixed by the rate and the window, the request sizes are a fixed multiset,
and one fixed generator (``SCHEDULE_SEED``) draws their order and the due
times of an open mix's requests and arrivals (uniform order statistics: a
Poisson process given its count).  So every seed offers the same sizes at
the same times, and ``--seed`` draws where the queries fall, which sensors
report and what they read.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Requests:
    due: np.ndarray  # (N,) seconds after the window opens, sorted
    queries: list  # N arrays (rows, d) of query points
    kinds: list  # N request-kind names


@dataclasses.dataclass
class Arrivals:
    due: np.ndarray  # (A,) seconds after the window opens, sorted
    fields: np.ndarray  # (A,) int
    sensors: np.ndarray  # (A,) int
    xs: np.ndarray  # (A, d)
    ys: np.ndarray  # (A,)


def _sizes(kinds: list, count: int) -> list:
    """A fixed multiset of request kinds with the mix's shares."""
    out = []
    for kind in kinds:
        out += [kind] * int(round(kind["share"] * count))
    out = (out + [kinds[0]] * count)[:count]
    return out


def _near(kind: dict, rng, box, pos, count: int) -> np.ndarray:
    """``count`` points around sensors drawn from ``rng``, inside ``box``."""
    if kind["near"] != "sensors":
        raise ValueError(f"request kind {kind['kind']!r}: near {kind['near']!r}; "
                         "only 'sensors' is known")
    if pos is None:
        raise ValueError("requests near sensors need the sensors' positions")
    lo, hi = box
    x = pos[rng.integers(0, len(pos), size=count)]
    x = x + float(kind["jitter"]) * (hi - lo) * rng.normal(size=x.shape)
    return np.clip(x, lo, hi)


def _points(kind: dict, rng, box, rows: int | None = None, pos=None) -> np.ndarray:
    lo, hi = box
    d = len(lo)
    if kind["kind"] == "points":
        if rows is None:
            rows = int(rng.integers(kind["rows_min"], kind["rows_max"] + 1))
        if "near" in kind:
            return _near(kind, rng, box, pos, rows).astype(np.float32)
        return rng.uniform(lo, hi, size=(rows, d)).astype(np.float32)
    if kind["kind"] == "tile":
        g = int(kind["grid"])
        side = float(kind["tile_side"]) * (hi - lo)
        if "near" in kind:
            corner = np.clip(_near(kind, rng, box, pos, 1)[0] - side / 2, lo, hi - side)
        else:
            corner = rng.uniform(lo, hi - side)
        ax = [corner[i] + side[i] * (np.arange(g) + 0.5) / g for i in range(d)]
        mesh = np.stack(np.meshgrid(*ax, indexing="ij"), -1).reshape(-1, d)
        return mesh.astype(np.float32)
    raise ValueError(f"unknown request kind {kind['kind']!r}")


SCHEDULE_SEED = 1  # the open mixes' timing, the same for every --seed


def _schedules() -> tuple:
    """Generators of the requests' and the arrivals' timing."""
    seq = np.random.SeedSequence(SCHEDULE_SEED)
    return tuple(np.random.default_rng(s) for s in seq.spawn(2))


def open_requests(mix: dict, seconds: float, rng, box, pos=None) -> Requests:
    count = int(round(mix["rate_per_s"] * seconds))
    kinds = _sizes(mix["requests"], count)
    sched = _schedules()[0]
    order = sched.permutation(count)
    kinds = [kinds[i] for i in order]
    due = np.sort(sched.uniform(0.0, seconds, size=count))
    # point requests cycle through every size from rows_min to rows_max,
    # so the rows of a window are the same for every seed
    cycle = {}
    queries = []
    for k in kinds:
        rows = None
        if k["kind"] == "points":
            i = cycle.get(id(k), 0)
            cycle[id(k)] = i + 1
            rows = k["rows_min"] + i % (k["rows_max"] - k["rows_min"] + 1)
        queries.append(_points(k, rng, box, rows, pos))
    return Requests(due=due, queries=queries, kinds=[k["kind"] for k in kinds])


def reports(cfg: dict, net_pos: np.ndarray, fields, seconds: float, rng) -> Arrivals:
    """Every sensor reports once per ``report_interval_s`` at a random
    phase: one reading of each quantity for the newest interval's field.

    The window sees ``sensors * seconds / interval`` reports, from
    distinct sensors while the window is shorter than the interval, so
    no (field, sensor) pair repeats within a run.  The due times come from
    ``SCHEDULE_SEED``, all else from ``rng``.
    """
    n = net_pos.shape[0]
    interval = float(cfg["report_interval_s"])
    if seconds > interval:
        raise ValueError("a window longer than the reporting interval repeats sensors")
    reporters = int(round(n * seconds / interval))
    sensors = rng.choice(n, size=reporters, replace=False)
    due = np.sort(_schedules()[1].uniform(0.0, seconds, size=reporters))
    q = len(cfg["quantities"])
    newest = [i * cfg["intervals"] + cfg["intervals"] - 1 for i in range(q)]
    f = np.tile(np.asarray(newest), reporters)
    s = np.repeat(sensors, q)
    x = net_pos[s]
    y = fields.value(f, x) + fields.noise * rng.normal(size=len(f))
    return Arrivals(
        due=np.repeat(due, q), fields=f.astype(np.int32), sensors=s.astype(np.int32),
        xs=x.astype(np.float32), ys=y.astype(np.float32),
    )


def closed_request(mix: dict, rng, box, pos=None) -> np.ndarray:
    """One closed-loop request: ``rows_min``..``rows_max`` points, near the
    sensors where the mix says so."""
    keys = ("rows_min", "rows_max", "near", "jitter")
    kind = {"kind": "points", **{k: mix[k] for k in keys if k in mix}}
    return _points(kind, rng, box, pos=pos)
