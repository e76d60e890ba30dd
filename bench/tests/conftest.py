"""The benchmark's tests run on the host CPU (the Pallas kernels
interpreted); ``tiny_catalog`` resolves the tests' own cells, configuration
and mixes under ``data/`` before the benchmark's."""
import os
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="session")
def tiny_catalog():
    from bench.catalog import Catalog

    return Catalog(DATA / "BENCHMARK.json", extra_dir=DATA)
