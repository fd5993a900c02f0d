"""The existing cells draw what they drew before the configuration could
state a clustered layout and requests could land near sensors: SHA-256 of
their positions, requests, arrivals and reference messages, recorded on
the code before that change."""

import hashlib
import types

import numpy as np
import pytest

import deploy
import harness
import traffic
from reference import Reference
from tinycfg import tiny

DIGESTS = {
    ("positions", "intel-lab-54"):
        "4b7143c20bf4a4e9beaa3affceb1bbbf4a2ede7a5995ef69518f9ec7e6a4ca45",
    ("positions", "city-aq-2k"):
        "f09cf41ae642b6f18387df56960ced8f96416741fb468f5fbb6feae62d0dea40",
    ("open_requests", 1): "fe5dd3ddfed05894e2a8cbe59200a4d4d46a5610001b9f7f5cafbd478bc20409",
    ("open_requests", 2): "8ecd927952401f2d262f61e28fe3e035022f0cff371f6569d4581f08b8e1b7c3",
    ("arrivals", 1): "d47f3c41625283e55005e8224cc22c706b651d77e478bbb767fa8037b28f4d72",
    ("arrivals", 2): "8e4885c5aeab1f6f2dda86b206348c2eff7fa8fe2f93e3a2d951fbedcd24f74e",
    ("closed_requests", 1): "d436d1935b0a3737df508ed64b3176da3dd976c31889d9f4381a4e2ef1456b4c",
    ("closed_requests", 2): "7215faa62b0ad947d7bfe018f2aac230005a278ae65aa074eb202e06df04dd66",
    ("reference_messages", "city2k-daemon"):
        "2db181547dc2553df20290589fb9ec8a121baaa45a5ee58a8fa0d071df2e2321",
    ("reference_messages", "lab54-history"):
        "a44a42a2e947fa309db80f651b6a446ed377e17fa466d4f9c01fdcd232cf6b23",
    ("reference_absorb_answer", "city2k-daemon"):
        "53e3b0ac1d0b9058a31ef8026d4b1ea188afbf6b615c03dd67612773f51a8de9",
}


def digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _box(cfg):
    return deploy.query_box(types.SimpleNamespace(pos=deploy.positions(cfg)))


def positions(name):
    return digest(deploy.positions(harness.Spec().config(name)))


def _open(seed):
    """The first 200 requests and every arrival of ``daemon_open_loop`` over
    30 s, drawn as ``drivers.open_loop`` draws them."""
    spec = harness.Spec()
    cfg = spec.config("city-aq-2k")
    pos = deploy.positions(cfg)
    rng_fields, rng_req, rng_arr, _ = deploy.streams(seed, 4)
    fields = deploy.Fields(cfg["fields"], rng_fields, cfg["noise"])
    fields.readings(pos, rng_fields)
    req = traffic.open_requests(spec.traffic("daemon_open_loop"), 30.0, rng_req, _box(cfg))
    arr = traffic.reports(cfg, pos, fields, 30.0, rng_arr)
    return req, arr


def open_requests(seed):
    req, _ = _open(seed)
    return digest(req.due, np.array(req.kinds[:200]), *req.queries[:200])


def arrivals(seed):
    _, arr = _open(seed)
    return digest(arr.due, arr.fields, arr.sensors, arr.xs, arr.ys)


def closed_requests(seed):
    spec = harness.Spec()
    box = _box(spec.config("intel-lab-54"))
    rng_req = deploy.streams(seed, 3)[1]
    mix = spec.traffic("history_closed_loop")
    return digest(*[traffic.closed_request(mix, rng_req, box) for _ in range(64)])


def _reference(cell, seed, lanes=0):
    _, cfg = tiny(cell)
    pos = deploy.positions(cfg)
    rng = deploy.streams(seed, 3)
    fields = deploy.Fields(cfg["fields"], rng[0], cfg["noise"])
    ys = fields.readings(pos, rng[0])
    ref = Reference(pos, deploy.radius(cfg), cfg["gamma"], cfg["lambda"], ys, lanes=lanes)
    return ref, cfg, pos, fields, rng


def reference_messages(cell):
    ref = _reference(cell, 7)[0]
    ref.sweeps(20)
    return digest(ref.messages())


def reference_absorb_answer(cell):
    """Two absorbs (the second repeats the first's pairs: lane 1), sweeps
    between them, then every read-out and 300 answers."""
    ref, cfg, pos, fields, (_, rng_arr, rng_q) = _reference(cell, 9, lanes=2)
    arr = traffic.reports(cfg, pos, fields, 30.0, rng_arr)
    ref.sweeps(10)
    half = len(arr.due) // 2
    ref.absorb(arr.fields[:half], arr.sensors[:half], arr.xs[:half], arr.ys[:half])
    ref.sweeps(5)
    ref.absorb(arr.fields, arr.sensors, arr.xs, arr.ys)
    ref.sweeps(5)
    xq = rng_q.uniform(-0.9, 0.9, size=(300, 2)).astype(np.float32)
    ans, tie = ref.answer(xq, cfg["k"])
    return digest(ref.messages(), ref.slots(), ref.coefficients(), ans, tie)


@pytest.mark.parametrize("what,arg", sorted(DIGESTS, key=str))
def test_existing_cells_draw_what_they_drew(what, arg):
    assert globals()[what](arg) == DIGESTS[what, arg]
