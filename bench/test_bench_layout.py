"""A configuration states a clustered layout and where its queries land,
and the reference, held in blocks of fields, checks it (CPU, tiny sizes)."""

import time

import numpy as np
import pytest

import deploy
import harness
import traffic
from reference import Reference, adjacency
from tinycfg import cut

CLUSTERED = {"kind": "clustered", "centres": 12, "zipf_s": 1.0, "sigma": 0.15,
             "background": 0.3}


def clustered(sensors=2000, **place):
    """city-aq-2k with a clustered placement."""
    cfg = harness.Spec().config("city-aq-2k")
    cfg.update(name="city-aq-clustered", sensors=sensors,
               placement={**CLUSTERED, **place})
    return cfg


def test_clustered_placement_is_the_stated_process():
    cfg = clustered()
    pos, centre = deploy.layout(cfg)
    again, _ = deploy.layout(cfg)
    assert np.array_equal(pos, again)  # from placement_seed alone
    assert pos.shape == (2000, 2) and pos.dtype == np.float32
    lo, hi = cfg["domain"]
    assert pos.min() >= lo and pos.max() <= hi
    n, beta = len(pos), CLUSTERED["background"]
    bg = np.sum(centre < 0)
    assert abs(bg - n * beta) <= 3 * np.sqrt(n * beta * (1 - beta)), bg
    counts = np.bincount(centre[centre >= 0], minlength=12)
    # the Zipf weights 1, 1/2, 1/3, ... order the largest clusters
    assert counts.argmax() == 0 and counts[0] > counts[1] > counts[2] > counts[5:].max()
    # clustered is denser than uniform: a larger largest degree
    r = deploy.radius(cfg)
    uniform = deploy.positions(harness.Spec().config("city-aq-2k"))
    assert adjacency(pos, r).sum(1).max() > 2 * adjacency(uniform, r).sum(1).max()


@pytest.mark.parametrize("place,key", [
    ("grid", "'grid'"),
    ({**CLUSTERED, "kind": "poisson"}, "poisson"),
    ({**CLUSTERED, "spread": 1.0}, "spread"),
    ({k: v for k, v in CLUSTERED.items() if k != "sigma"}, "sigma"),
    ({**CLUSTERED, "background": 1.5}, "placement.background"),
])
def test_a_placement_it_does_not_know_fails_naming_it(place, key):
    cfg = clustered()
    cfg["placement"] = place
    with pytest.raises(ValueError, match=key):
        deploy.positions(cfg)


def _mix(near: bool, jitter=0.01) -> dict:
    kinds = [{"share": 0.8, "kind": "points", "rows_min": 1, "rows_max": 8},
             {"share": 0.2, "kind": "tile", "grid": 16, "tile_side": 0.125}]
    if near:
        kinds = [{**k, "near": "sensors", "jitter": jitter} for k in kinds]
    return {"rate_per_s": 40.0, "requests": kinds}


def _nearest(pos, xq):
    return np.sqrt(((xq[:, None, :] - pos[None, :, :]) ** 2).sum(-1)).min(1)


def test_requests_near_sensors_stay_in_the_box_and_land_by_sensors():
    cfg = clustered(sensors=400)
    pos = deploy.positions(cfg)
    net = deploy.Network(cfg=cfg, pos=pos, radius=0.0, d_max=0, topology=None)
    lo, hi = box = deploy.query_box(net)
    got = {}
    for near in (False, True):
        rng = deploy.streams(2**40 + 3, 1)[0]
        req = traffic.open_requests(_mix(near), 10.0, rng, box, pos)
        xq = np.concatenate(req.queries)
        assert np.all(xq >= lo - 1e-6) and np.all(xq <= hi + 1e-6)
        tiles = [q for q, k in zip(req.queries, req.kinds) if k == "tile"]
        side = 0.125 * (hi - lo)
        spans = np.array([q.max(0) - q.min(0) for q in tiles])
        assert np.allclose(spans, side * 15 / 16, rtol=1e-5)  # whole tiles
        points = np.concatenate([q for q, k in zip(req.queries, req.kinds) if k == "points"])
        got[near] = np.median(_nearest(pos, points))
    assert got[True] < 0.5 * got[False], got
    # the closed loop takes the same keys
    rng = deploy.streams(5, 1)[0]
    mix = {"rows_min": 64, "rows_max": 64, "near": "sensors", "jitter": 0.0}
    xq = traffic.closed_request(mix, rng, box, pos)
    assert _nearest(pos, xq).max() <= 0.03  # a sensor, clipped into the 1%-inset box
    with pytest.raises(ValueError, match="near 'roads'"):
        traffic.closed_request({**mix, "near": "roads"}, rng, box, pos)


def test_blocks_of_fields_agree_with_one_block():
    cfg = cut(clustered(centres=3))
    pos = deploy.positions(cfg)
    rng_fields, rng_arr, rng_q = deploy.streams(2**35 + 11, 3)
    fields = deploy.Fields(cfg["fields"], rng_fields, cfg["noise"])
    ys = fields.readings(pos, rng_fields)
    arr = traffic.reports(cfg, pos, fields, 30.0, rng_arr)
    xq = rng_q.uniform(-0.9, 0.9, size=(200, 2)).astype(np.float32)
    outs = []
    for fields_per_block in (None, 3):
        ref = Reference(pos, deploy.radius(cfg), cfg["gamma"], cfg["lambda"], ys, lanes=2)
        if fields_per_block:
            ref = Reference(pos, deploy.radius(cfg), cfg["gamma"], cfg["lambda"], ys,
                            lanes=2, budget=fields_per_block * ref.field_bytes)
        assert [b.hi - b.lo for b in ref.blocks] == ([8] if not fields_per_block
                                                      else [3, 3, 2])
        ref.sweeps(10)
        half = len(arr.due) // 2
        ref.absorb(arr.fields[:half], arr.sensors[:half], arr.xs[:half], arr.ys[:half])
        ref.sweeps(5)
        ref.absorb(arr.fields, arr.sensors, arr.xs, arr.ys)
        ref.sweeps(5)
        ans, tie = ref.answer(xq, cfg["k"])
        outs.append((ref.messages(), ref.slots(), ref.coefficients(), ans, tie))
    for one, blocked in zip(*outs):
        assert one.shape == blocked.shape
        scale = max(np.max(np.abs(one)), 1e-30)
        assert np.max(np.abs(one.astype(np.float64) - blocked)) <= 1e-6 * scale


class _NearSpec(harness.Spec):
    """The cells' own traffic, with every request kind near the sensors."""

    def traffic(self, name):
        mix = super().traffic(name)
        if "requests" in mix:
            mix["requests"] = [{**k, "near": "sensors", "jitter": 0.02}
                               for k in mix["requests"]]
        else:
            mix.update(near="sensors", jitter=0.02)
        return mix


@pytest.mark.parametrize("near", [False, True])
@pytest.mark.parametrize("cell", ["city2k-daemon", "lab54-history"])
def test_tiny_clustered_deployment_runs_correct(cell, near):
    spec = _NearSpec() if near else harness.Spec()
    cfg = cut(clustered(centres=3))
    out = harness.run(cell, 2**35 + 21, 1.0, False, time.perf_counter(), spec=spec,
                      cfg=cfg)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
