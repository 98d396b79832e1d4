"""Back-to-back design-space sweeps through ``Session.sweep``.

The window runs whole sweeps until ``seconds`` have passed; the sweep still
running then finishes and counts.  The rate is every grid point of every
sweep (pruned candidates included) over the wall time from the first
sweep's start to the last sweep's end.
"""
from __future__ import annotations

import time

from bench import compare, generator, reference


def _program_space(config: dict, lists: dict):
    from repro import Space
    from repro.core import LsuType
    from repro.core.fpga import BspParams, DramParams

    drams = [DramParams(**d) for d in config["drams"]]
    bsps = [BspParams(**b) for b in config["bsps"]]
    return Space.grid(
        lsu_type=[LsuType(t) for t in lists["lsu_type"]],
        n_ga=lists["n_ga"], simd=lists["simd"], n_elems=lists["n_elems"],
        delta=lists["delta"], elem_bytes=lists["elem_bytes"],
        include_write=lists["include_write"],
        val_constant=lists["val_constant"],
        dram=[drams[i] for i in lists["dram"]],
        bsp=[bsps[i] for i in lists["bsp"]])


def _constraints(config: dict, traffic: dict):
    name = traffic.get("envelope")
    if name is None:
        return ()
    from repro.search import ResourceEnvelope, within

    return (within(ResourceEnvelope(**config["envelopes"][name])),)


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro import Session
        from repro.core.stream import default_reducers

        self.config, self.traffic, self.seed = config, traffic, seed
        self.session = Session(backend="jax-jit")
        self.reducers = default_reducers(traffic["top_k"])
        self.constraints = _constraints(config, traffic)
        self.lists: list[dict] = []
        self.reports: list = []

    def _sweep(self, lists: dict, profile: bool = False):
        return self.session.sweep(
            _program_space(self.config, lists),
            chunk_size=self.traffic["chunk"], reducers=self.reducers,
            constraints=self.constraints, profile=profile)

    def build(self, seconds: float) -> None:
        """Nothing to build ahead: sweep ``i`` draws its axes from
        ``(seed, i)`` in microseconds when it starts."""

    def warm_up(self) -> None:
        """Compile the chunk program: a one-point grid pads to the full
        chunk shape and its axis tables to the same buckets as the cell's
        grids, so it runs the very programs the window runs."""
        lists = generator.sweep_lists(self.config, self.traffic, self.seed,
                                      0)
        tiny = {k: v[:1] for k, v in lists.items()}
        self._sweep(tiny)

    def window(self, seconds: float, profile: bool, annotate) -> dict:
        spans, points, prof = [], 0, {}
        t_end = time.perf_counter() + seconds
        i = 0
        while True:
            lists = generator.sweep_lists(self.config, self.traffic,
                                          self.seed, i)
            t0 = time.perf_counter()
            with annotate("bench.sweep"):
                rep = self._sweep(lists, profile=profile)
            t1 = time.perf_counter()
            spans.append((t0, t1))
            points += generator.grid_size(lists)
            self.lists.append(lists)
            self.reports.append(rep)
            for k, v in (rep.profile or {}).items():
                prof[k] = prof.get(k, 0.0) + v if k.endswith("_s") else v
            i += 1
            if t1 >= t_end:
                break
        wall = spans[-1][1] - spans[0][0]
        return {
            "attempted": len(spans), "failed": 0,
            "metrics": {"sweep_points_per_s": points / wall},
            "layer": {"kind": "sweep", "points": points, "profile": prof},
            "log": {"sweeps": len(spans), "points": points, "wall_s": wall,
                    **{k: prof[k] for k in ("path", "devices", "host_reason")
                       if k in prof}},
        }

    def close(self) -> None:
        pass

    def check(self) -> dict:
        """One sweep of the window, drawn from the seed, against the plain
        reference on the same grid."""
        r = generator.rng(self.seed, 2)
        i = int(r.integers(len(self.reports)))
        lists, rep = self.lists[i], self.reports[i]
        env = self.traffic.get("envelope")
        ref = reference.sweep(
            lists, self.config["drams"], self.config["bsps"],
            envelope=(self.config["envelopes"][env] if env else None),
            k=self.traffic["top_k"])
        return compare.sweep_numbers(rep, ref)
