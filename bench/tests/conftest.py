"""Set-up of the benchmark's CPU tests: the repository's ``src`` on the
path, JAX's compile-cache settings put back after each test, and cells
kept out of ``BENCHMARK.json`` added for a test."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(autouse=True)
def _restore_jax_cache_config():
    import jax
    keys = ("jax_persistent_cache_min_compile_time_secs",
            "jax_compilation_cache_dir")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


@pytest.fixture
def add_cell(monkeypatch):
    """Adds a cell that ``BENCHMARK.json`` does not hold, as a later change
    would bring it: the entries of ``bench/tests/data/<cell>.json``
    appended to the lists of the same names."""
    import json
    from bench import harness
    orig, added = harness.benchmark, []

    def bench():
        b = orig()
        for name in added:
            extra = json.loads((DATA / f"{name}.json").read_text())
            for key, entries in extra.items():
                b[key] = b[key] + entries
        return b
    monkeypatch.setattr(harness, "benchmark", bench)
    return added.append
