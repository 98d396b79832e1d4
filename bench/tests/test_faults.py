"""Whole runs at a tiny size on the CPU (the harness's look for a chip is
skipped): sound runs are correct, and each fault a cell can have, planted
in the timed path, makes ``correct`` false.  The four-chip cell's faults
are in ``test_faults_4dev.py``."""
import dataclasses

import numpy as np
import pytest

SWEEP_CELLS = ["explore_10m", "explore_1m_within_s10"]
ALL_CELLS = SWEEP_CELLS + ["advisor_open_uniform"]


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_sound_run_is_correct(tiny, fresh_programs, cell):
    res = tiny(cell)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "check"


# -- the fused device step (explore_10m) ------------------------------------

def test_fused_step_state_unchanged(tiny, fresh_programs, monkeypatch):
    from repro.core import device_stream as ds

    monkeypatch.setattr(ds, "_get_step", lambda chunk, sig:
                        (lambda carry, tables, start: carry))
    res = tiny("explore_10m")
    assert not res["correct"]


def _wrap_score_chunk(monkeypatch, change):
    from repro.core import device_stream as ds

    orig = ds._score_chunk

    def wrapped(tables, start, chunk):
        return change(*orig(tables, start, chunk), chunk)

    monkeypatch.setattr(ds, "_score_chunk", wrapped)


def test_fused_step_half_batch(tiny, fresh_programs, monkeypatch):
    import jax.numpy as jnp

    def half(cols, valid, mask, chunk):
        keep = valid // 2
        return cols, keep, mask & (jnp.arange(chunk) < keep)

    _wrap_score_chunk(monkeypatch, half)
    assert not tiny("explore_10m")["correct"]


def test_fused_step_answer_altered(tiny, fresh_programs, monkeypatch):
    import jax.numpy as jnp

    def alter(cols, valid, mask, chunk):
        # the chunk's fastest design, which the top-k answers with
        t = cols["t_exe"]
        best = jnp.argmin(jnp.where(mask, t, jnp.inf))
        return {**cols, "t_exe": t.at[best].multiply(0.5)}, valid, mask

    _wrap_score_chunk(monkeypatch, alter)
    assert not tiny("explore_10m")["correct"]


# -- the host stream (explore_1m_within_s10) ---------------------------------

def test_host_stream_state_unchanged(tiny, fresh_programs, monkeypatch):
    from repro.core import stream

    for cls in (stream.StatsReducer, stream.TopKReducer,
                stream.ParetoReducer):
        monkeypatch.setattr(cls, "update", lambda self, cols: None)
    assert not tiny("explore_1m_within_s10")["correct"]


def test_host_stream_half_batch(tiny, fresh_programs, monkeypatch):
    from repro.core import stream

    orig = stream.SweepPlan.evaluator

    def evaluator(self, stage_times=None):
        f = orig(self, stage_times)

        def half(ids):
            cols = f(ids)
            m = len(cols["id"]) // 2
            return {k: np.asarray(v)[:m] for k, v in cols.items()}
        return half

    monkeypatch.setattr(stream.SweepPlan, "evaluator", evaluator)
    assert not tiny("explore_1m_within_s10")["correct"]


def test_host_stream_answer_altered(tiny, fresh_programs, monkeypatch):
    from repro import api

    orig = api._jax_estimate_batch

    def altered(batch, sharding=None, stage_times=None):
        est = orig(batch, sharding=sharding, stage_times=stage_times)
        t = np.array(est.t_exe)
        t[np.argmin(t)] *= 0.5          # the chunk's fastest design
        return dataclasses.replace(est, t_exe=t)

    monkeypatch.setattr(api, "_jax_estimate_batch", altered)
    assert not tiny("explore_1m_within_s10")["correct"]


# -- the serving batcher (advisor_open_uniform) ------------------------------

def test_server_half_batch(tiny, fresh_programs, monkeypatch):
    from repro.core.serving import Server

    orig = Server._score

    def half(self, designs):
        res = orig(self, list(designs)[:max(1, len(designs) // 2)])
        return [dataclasses.replace(res[i % len(res)], design=d)
                for i, d in enumerate(designs)]

    monkeypatch.setattr(Server, "_score", half)
    assert not tiny("advisor_open_uniform", seconds=1.0)["correct"]


def test_server_answer_altered(tiny, fresh_programs, monkeypatch):
    from repro.core.serving import Server

    orig = Server._score

    def altered(self, designs):
        res = orig(self, designs)
        res[0] = dataclasses.replace(res[0], t_exe=res[0].t_exe * 1.5)
        return res

    monkeypatch.setattr(Server, "_score", altered)
    assert not tiny("advisor_open_uniform")["correct"]
