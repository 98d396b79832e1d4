"""Tiny versions of the cells, for driving whole runs on the CPU."""
import json

import pytest

from bench import run

#: The advisor cell as a later benchmark PR enters it in BENCHMARK.json
#: (its files are here; it has no chip measurements yet, so it is not in
#: the benchmark).
ADVISOR = {"name": "advisor_open_uniform", "config": "advisor_service",
           "traffic": "open_uniform", "chips": 1}


_load_benchmark_cell = run.load_cell


def load_cell(name: str) -> dict:
    if name != ADVISOR["name"]:
        return _load_benchmark_cell(name)
    return {
        "cell": ADVISOR,
        "config": json.loads((run.BENCH / "configs" /
                              f"{ADVISOR['config']}.json").read_text()),
        "traffic": json.loads((run.BENCH / "traffic" /
                               f"{ADVISOR['traffic']}.json").read_text()),
        "end_to_end": [{"name": "query_p99_ms", "unit": "ms"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [],
    }


def shrink(spec: dict) -> dict:
    """The cell at a size a CPU test can hold: same code paths, smaller
    grids, lower rate, smaller batches."""
    t, c = spec["traffic"], spec["config"]
    if t["kind"] == "sweep":
        t["draw"] = {"n_ga": 3, "n_elems_log2": 2, "delta": 2}
        t["chunk"] = 4096
    else:
        t["rate_per_s"] = 200
        c["ranges"]["n_ga"] = [1, 8]
        c["server"]["max_batch"] = 8
        c["server"]["max_wait_ms"] = 5.0
    return spec


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setattr(run, "load_cell", lambda name: shrink(load_cell(name)))

    def go(name: str, seed: int = 12345, seconds: float = 0.5) -> dict:
        return run.run_cell(name, seed=seed, seconds=seconds, trace=False)

    return go


@pytest.fixture
def fresh_programs(monkeypatch):
    """Drop the program's compiled-function caches, so a patched function
    is traced anew (and the patch is undone with the caches)."""
    from repro import api
    from repro.core import device_stream

    monkeypatch.setattr(device_stream, "_STEP_CACHE", {})
    monkeypatch.setattr(api, "_JAX_FN", None)
