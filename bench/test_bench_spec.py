"""BENCHMARK.json and unproven.json merge soundly, and every cell of
BENCHMARK.json finds the files the harness looks up by its names."""

import pytest

import harness

KEYS = ("configs", "workloads", "end_to_end", "per_layer")
FILES = {"BENCHMARK.json": harness.ROOT / "BENCHMARK.json",
         "unproven.json": harness.BENCH / "unproven.json"}


@pytest.mark.parametrize("key", KEYS)
def test_no_entry_is_in_both_files(key):
    bench, unproven = (harness.load(p) for p in FILES.values())
    both = {e["name"] for e in bench[key]} & {e["name"] for e in unproven[key]}
    assert not both, f"{key} named in both files: {sorted(both)}"


@pytest.mark.parametrize("key", ["end_to_end", "per_layer"])
def test_merged_metric_lists_each_cell_once(key):
    for m in harness.Spec().raw[key]:
        cells = m.get("workloads", [])
        assert len(cells) == len(set(cells)), m


def test_every_cell_has_its_files():
    spec = harness.Spec()
    bench = harness.load(FILES["BENCHMARK.json"])
    for cell in bench["workloads"]:
        assert (harness.ROOT / next(c["file"] for c in bench["configs"]
                                    if c["name"] == cell["config"])).exists(), cell
        assert spec.traffic(cell["traffic"])["loop"] in ("open", "closed", "solve")
        assert spec.limits(cell["name"]), cell
        for trace in (False, True):
            for m in spec.metrics(cell, trace):
                assert callable(harness.reader(m["name"])), m


@pytest.mark.parametrize("name", FILES)
def test_per_layer_moves_an_end_to_end_metric_of_its_file(name):
    raw = harness.load(FILES[name])
    e2e = {m["name"]: m for m in raw["end_to_end"]}
    for m in raw["per_layer"]:
        assert m["moves"] in e2e, m
        # each cell the metric lists reports the metric it moves
        moved = e2e[m["moves"]].get("workloads")
        if moved is not None:
            assert set(m.get("workloads", [])) <= set(moved), m
