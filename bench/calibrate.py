#!/usr/bin/env python3
"""Readings that the limits in ``limits/<cell>.json`` are set from.

For each seed, in one process (the network is built once: its positions
do not depend on the seed), this reads what the comparison of a cell
gives for

  * the program: the cell's own timed path at the cell's size, with a
    short window at the cell's load, compared with the reference;
  * the control (``--control-seeds``): for the serving cells, the
    program with its own lower-precision path switched on (bfloat16
    anchor tables, ``serve_dtype="bf16"``); for the converge cells, the
    reference itself computed with three-pass (``--controls bf16x3``) or
    one-pass (``bf16``) bfloat16 matrix products in the program's place,
    at the sweep count the program's solve took; for the daemon's sweeps
    (open loop), the reference at each of ``--controls`` put in the
    program's place beside the program's own reading of a control seed:
    the same set-up sweeps, absorbs and tick sweeps, its messages against
    the reference's at the snapshot the check compares;
  * a planted fault (``--fault early_stop --fault-seeds 1-3``): the
    program with a fault of ``faults.py`` under it, read last.

    python3 bench/calibrate.py --workload city2k-converge --seeds 1-12 \
        --control-seeds 1-3 --controls bf16x3,bf16 --seconds 4

Prints one JSON line per reading.  It runs on the chip only.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


class _Patch:
    """``monkeypatch.setattr`` for a planted fault that lasts the process."""

    @staticmethod
    def setattr(target, name, value):
        setattr(target, name, value)


def solve_readings(net, cfg: dict, seed: int, controls=()) -> dict:
    """Readings of one solve of the seed's first batch: the program's, and
    each control's (the reference in the program's place at a lower
    precision, ``"bf16x3"`` or ``"bf16"``, at the program's sweep count)."""
    import jax
    import numpy as np

    import deploy
    import drivers
    from repro.core import init_state, monitor

    rng_fields, _ = deploy.streams(seed, 2)
    fields = deploy.Fields(cfg["fields"], rng_fields, cfg["noise"])
    ys = fields.readings(net.pos, rng_fields)
    prob = deploy.problem(net, ys)
    _, st, rc = monitor.watch_sweeps(prob, init_state(prob),
                                     config=drivers._watch_config(cfg))
    jax.block_until_ready(st)
    z, c = np.asarray(st.z), np.asarray(st.coef)
    del prob, st
    sweeps = int(rc.sweeps)
    msg, coef, stop = drivers.replay_solve(net, cfg, ys, sweeps)
    n = net.n

    def numbers(zz, cc):
        return {"message_gap": drivers._gap(zz[:, :n], msg),
                "coef_gap": drivers._gap(cc[:, :n, : coef.shape[2]], coef),
                "stop_residual": stop}

    out = {"sweeps": sweeps, "converged": bool(np.all(rc.converged)),
           "program": numbers(z, c)}
    for precision in controls:
        cm, cc, _ = drivers.replay_solve(net, cfg, ys, sweeps, precision=precision)
        out["control_" + precision] = numbers(cm, cc)
    return out


def shadowed(precisions) -> type:
    """``reference.Reference`` with a copy at each of ``precisions`` fed
    the same sweeps and absorbs; at each ``messages()`` call (the check
    calls it for the snapshot it compares) ``gaps`` keeps each copy's
    message gap against it: the control of the daemon's sweeps."""
    import drivers
    import reference

    class Shadowed(reference.Reference):
        gaps: dict = {}

        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.shadows = {p: reference.Reference(*a, **{**k, "precision": p})
                            for p in precisions}

        def sweeps(self, count):
            super().sweeps(count)
            for s in self.shadows.values():
                s.sweeps(count)

        def absorb(self, *a):
            for s in self.shadows.values():
                s.absorb(*a)
            return super().absorb(*a)

        def messages(self):
            m = super().messages()
            Shadowed.gaps = {p: drivers._gap(s.messages(), m)
                             for p, s in self.shadows.items()}
            return m

    return Shadowed


def run_readings(cell: str, net, seed: int, seconds: float, daemon: dict,
                 mix: dict | None = None, controls=()) -> dict:
    """The comparison's numbers for one short run of a serving cell, and
    with ``controls`` those of the reference at each precision in the
    place of the daemon's sweeps (``control_<precision>``)."""
    import drivers
    import harness

    spec = harness.Spec(ROOT)
    w = spec.cell(cell)
    cfg = spec.config(w["config"])
    ctx = drivers.Context(cfg=cfg, mix={**spec.traffic(w["traffic"]), **(mix or {})},
                          seed=seed,
                          seconds=seconds, t_start=time.perf_counter(), net=net,
                          daemon=daemon)
    rec, check = drivers.DRIVERS[ctx.mix["loop"]](ctx)
    plain = drivers.Reference
    if controls:
        drivers.Reference = shadowed(controls)
    try:
        out = check()
    finally:
        gaps = getattr(drivers.Reference, "gaps", {})
        drivers.Reference = plain
    r = {"numbers": out, "attempted": rec.attempted, "failed": rec.failed,
         "notes": rec.notes}
    for p, gap in gaps.items():
        r["control_" + p] = {"message_gap": gap}
    return r


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="1-3")
    ap.add_argument("--controls", default="bf16x3",
                    help="converge and open-loop cells: control precisions of the "
                    "sweeps, comma-separated")
    ap.add_argument("--fault", default="", help="a fault of faults.py to plant "
                    "after the program and control readings")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--mix", default="{}", help="traffic mix overrides (JSON)")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / "bench_out" / "tpu_logs"))
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import jax

    import deploy
    import harness

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    spec = harness.Spec(ROOT)
    w = spec.cell(args.workload)
    cfg = spec.config(w["config"])
    loop = spec.traffic(w["traffic"])["loop"]
    net = deploy.network(cfg)
    control_seeds = set(_seeds(args.control_seeds))
    controls = [c for c in args.controls.split(",") if c]

    def reading(seed, kind, overrides):
        t = time.perf_counter()
        if loop == "solve":
            r = solve_readings(net, cfg, seed,
                               controls=controls if kind == "program" and seed in control_seeds
                               else ())
        else:
            r = run_readings(args.workload, net, seed, args.seconds, overrides,
                             json.loads(args.mix),
                             controls=controls if kind == "program" and loop == "open"
                             and seed in control_seeds else ())
        r.update(seed=seed, kind=kind, seconds=time.perf_counter() - t)
        print(json.dumps(r), flush=True)

    for seed in _seeds(args.seeds):
        reading(seed, "program", {})
    if loop != "solve":
        for seed in _seeds(args.control_seeds):
            reading(seed, "control", {"serve_dtype": "bf16"})
    if args.fault:
        import faults

        faults.FAULTS[args.fault](_Patch, cfg["fields"])
        for seed in _seeds(args.fault_seeds):
            reading(seed, "fault_" + args.fault, {})
    return 0


if __name__ == "__main__":
    sys.exit(main())
