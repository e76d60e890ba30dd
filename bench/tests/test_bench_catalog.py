"""The harness finds every part by the name ``BENCHMARK.json`` gives it, so
a new cell, mix, metric or configuration is new files and an entry."""
import json
import re

import pytest

from bench.catalog import Catalog, peaks_for

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def catalog():
    return Catalog()


def test_every_cell_resolves(catalog):
    for w in catalog.spec["workloads"]:
        cell = catalog.cell(w["name"])
        assert cell.chips == w["chips"] == 1
        catalog.generator(cell.traffic["kind"]).drive
        catalog.rows(cell.config["rows"]["generator"]).Rows
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(catalog.reader(m["name"]).read)


def test_benchmark_json_keeps_its_shape(catalog):
    spec = catalog.spec
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    reported = {}
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
        for cell in m.get("workloads", [w["name"] for w in spec["workloads"]]):
            reported.setdefault(cell, set()).add(m["name"])
    for m in spec["per_layer"]:
        assert NAME.match(m["name"]) and m["layer"]
        for cell in m["workloads"]:
            assert m["moves"] in reported[cell], (m["name"], cell)
    for c in spec["configs"]:
        assert json.load(open(catalog.dirs[-1].parent / c["file"]))["reduced"] == c["reduced"]


def test_test_only_workload_is_found_by_name(tiny_catalog):
    """A cell, configuration and mix that exist only as the tests' files."""
    catalog = tiny_catalog
    cell = catalog.cell("tiny.open")
    assert cell.config["n_trees"] == 8 and cell.traffic["rate_rps"] == 40
    assert [m["name"] for m in cell.per_layer] == ["queue_ms", "batch_rows"]
    closed = catalog.cell("tiny.closed")
    assert [m["name"] for m in closed.per_layer] == ["batch_rows"]
    with pytest.raises(KeyError):
        catalog.cell("rf-esa.steady")


def test_peaks_are_keyed_by_device_kind():
    peaks = peaks_for("TPU v5 lite")
    assert peaks["int8_ops_per_s"] == 393e12 and peaks["hbm_bytes_per_s"] == 819e9
    assert "source" in peaks
    with pytest.raises(KeyError):
        peaks_for("cpu")
