"""Tiny configurations for the CPU tests: the cells' own files, cut to a
size a test run holds."""

from __future__ import annotations

import harness

SENSORS, FIELDS, ARRIVAL_ROWS = 40, 8, 8  # the cut of any other configuration


def cut(cfg: dict) -> dict:
    """``cfg`` cut in place to a size a test run holds: the two named cuts,
    else at most ``SENSORS`` sensors and ``FIELDS`` fields (whole
    intervals of every quantity) and ``ARRIVAL_ROWS``-row absorb windows."""
    if cfg["name"] == "city-aq-2k":
        cfg.update(sensors=40, fields=8, intervals=2)
        cfg["daemon"]["arrival_rows"] = 8
    elif cfg["name"] == "intel-lab-54":
        cfg.update(fields=12, intervals=4)  # all 54 motes
    else:
        q = len(cfg["quantities"])
        intervals = max(1, min(cfg["intervals"], FIELDS // q))
        cfg.update(sensors=min(cfg["sensors"], SENSORS), fields=q * intervals,
                   intervals=intervals)
        cfg["daemon"]["arrival_rows"] = ARRIVAL_ROWS
    return cfg


def tiny(cell: str) -> tuple:
    """(spec, cfg) for ``cell`` with a few sensors and fields."""
    spec = harness.Spec()
    return spec, cut(spec.config(spec.cell(cell)["config"]))
