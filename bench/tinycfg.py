"""Tiny configurations for the CPU tests: the cells' own files, cut to a
size a test run holds."""

from __future__ import annotations

import harness


def tiny(cell: str) -> tuple:
    """(spec, cfg) for ``cell`` with a few sensors and fields."""
    spec = harness.Spec()
    cfg = spec.config(spec.cell(cell)["config"])
    if cfg["name"] == "city-aq-2k":
        cfg.update(sensors=40, fields=8, intervals=2)
        cfg["daemon"]["arrival_rows"] = 8
    else:
        cfg.update(fields=12, intervals=4)  # all 54 motes
    return spec, cfg
