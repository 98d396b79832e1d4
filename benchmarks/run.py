"""Benchmark driver — one function per paper table/figure plus the TPU
roofline harness and the design-space sweep engine.  Prints
``name,us_per_call,derived`` CSV summary rows (the harness contract)
followed by the detailed per-table CSVs.

Usage:
    python -m benchmarks.run [--details] [--roofline-only] [--hw <name>]
    python -m benchmarks.run --smoke --out json         # fast subset

``--smoke`` runs only the fast, simulator-free subset (paper Table IV,
Fig. 5 stride, a reduced design-space sweep, the 1M-point streaming
sweep with per-backend points/sec + peak RSS, the 10M-point
device-vs-host streaming sweep (jax-jit pipeline against the numpy-batch
fold, with an agreement flag), the distributed-sweep scaling bench at
1/2/4 process workers, the 32-client serving-latency bench (p50/p99),
and the whole-model ``model_e2e`` bench — transformer train + decode
steps composed through ``Session.estimate_model`` on two hardware
presets, with an agreement flag) and, with ``--out``, writes the full
results as JSON.  ``--out json`` resolves to ``BENCH_smoke.json`` at the
repository root (git-ignored; host figures, not the on-chip yardstick of
``bench/run.py``).  ``--hw <name>`` re-runs everything against a
``repro.hw`` registry spec (e.g. ``stratix10_ddr4_2666``, ``tpu_v5e``).
"""
from __future__ import annotations

import argparse
import csv
import io
import json
import pathlib
import sys
import time

# `pip install -e .` or the root conftest.py make `repro` importable; the
# per-entry-file src/ bootstrap this file used to carry is gone.  Run from
# an installed checkout or with PYTHONPATH=src.


def _csv(rows: list[dict]) -> str:
    if not rows:
        return ""
    buf = io.StringIO()
    w = csv.DictWriter(buf, fieldnames=list(rows[0].keys()))
    w.writeheader()
    for r in rows:
        w.writerow(r)
    return buf.getvalue()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--details", action="store_true",
                    help="print full per-table CSVs")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--roofline-only", action="store_true")
    mode.add_argument("--smoke", action="store_true",
                      help="fast subset: model-only tables + reduced sweep")
    ap.add_argument("--out", type=str, default=None,
                    help="write results as JSON to this path; the literal "
                         "value 'json' resolves to BENCH_smoke.json (or "
                         "BENCH_full.json) at the repository root")
    ap.add_argument("--hw", type=str, default=None, metavar="NAME",
                    help="evaluate against a repro.hw registry spec "
                         "(e.g. stratix10_ddr4_2666, tpu_v5e)")
    args = ap.parse_args()

    from benchmarks import paper_tables as PT
    from benchmarks import sweep_bench as SB

    session = None
    if args.hw:
        import repro.hw as hwreg
        from repro import Session

        session = Session().with_hardware(hwreg.get(args.hw))
        PT.set_session(session)

    summary: list[tuple[str, float, str]] = []
    details: dict[str, list[dict]] = {}

    if args.smoke:
        tables = {k: PT.ALL[k] for k in ("table4_applications", "fig5_stride")
                  if k in PT.ALL}
        sweep_fn = lambda: SB.sweep_speedup(SB.SMOKE_AXES,  # noqa: E731
                                            session=session)
    else:
        tables = {} if args.roofline_only else dict(PT.ALL)
        sweep_fn = lambda: SB.sweep_speedup(session=session)  # noqa: E731

    if not args.roofline_only:
        # The streaming benchmarks run their backends in subprocesses; they
        # go first, before this process touches jax, because an
        # accelerator serves one process at a time.

        # 1M-point streaming sweep: points/sec + peak RSS per backend vs the
        # materialize-everything baseline.
        rows, us = PT.timed(lambda: SB.stream_bench(session=session))
        details["stream_1m"] = rows
        summary.append(("stream_1m", us, _derive("stream_1m", rows)))

        # 10M-point streaming sweep: the device-resident jax-jit pipeline
        # vs the numpy-batch host fold at a scale too large to materialize
        # (device==host agreement + per-backend points/sec).
        rows, us = PT.timed(lambda: SB.stream10_bench(session=session))
        details["stream_10m"] = rows
        summary.append(("stream_10m", us, _derive("stream_10m", rows)))

        # distributed streaming sweep: the same 1M-point grid through the
        # coordinator/worker process pool at 1/2/4 workers (points/sec +
        # agreement with the single-process fold).
        rows, us = PT.timed(lambda: SB.stream_dist(session=session))
        details["stream_dist"] = rows
        summary.append(("stream_dist", us, _derive("stream_dist", rows)))

    for name, fn in tables.items():
        rows, us = PT.timed(fn)
        details[name] = rows
        summary.append((name, us, _derive(name, rows)))

    if not args.roofline_only:
        rows, us = PT.timed(sweep_fn)
        details["sweep"] = rows
        summary.append(("sweep", us, _derive("sweep", rows)))

        # gradient-based search vs the exhaustive grid: Session.optimize
        # must bit-match the 1M-point optimum and recover the Pareto front
        # while evaluating <1% of the points.
        rows, us = PT.timed(lambda: SB.optimize_1m(session=session))
        details["optimize_1m"] = rows
        summary.append(("optimize_1m", us, _derive("optimize_1m", rows)))

        # serving layer: 32 concurrent clients against Session.serve() —
        # hot (cache-warm interactive) p50/p99 latency vs the single-request
        # baseline, plus cold micro-batched throughput.
        from benchmarks import serve_bench as SVB
        rows, us = PT.timed(lambda: SVB.serve_bench(session=session))
        details["serve_smoke"] = rows
        summary.append(("serve_smoke", us, _derive("serve_smoke", rows)))

        # whole-model estimation: transformer train + decode steps composed
        # through Session.estimate_model on two hardware presets; the
        # composed-total == summed-parts agreement and the wall time.
        from benchmarks import model_bench as MB
        rows, us = PT.timed(lambda: MB.model_e2e(session=session))
        details["model_e2e"] = rows
        summary.append(("model_e2e", us, _derive("model_e2e", rows)))

    if not args.smoke:
        # roofline (reads dry-run artifacts if present)
        try:
            from benchmarks import roofline as RL
            t0 = time.perf_counter()
            cells = RL.load_cells(hw=session.hw if session else None)
            us = (time.perf_counter() - t0) / max(1, len(cells)) * 1e6
            if cells:
                import statistics
                ufl = [c.useful_flops_ratio for c in cells
                       if c.shape == "train_4k" and c.mesh == "16x16"]
                coll = sum(1 for c in cells if c.dominant == "collective")
                derived = (f"cells={len(cells)} "
                           f"train_useful_flops_median={statistics.median(ufl):.2f} "
                           f"collective_dominant={coll}")
            else:
                derived = "no dry-run artifacts yet"
            summary.append(("roofline", us, derived))
            details["roofline"] = [c.as_row() for c in cells]
        except Exception as e:  # noqa: BLE001
            summary.append(("roofline", 0.0, f"error: {e}"))

    print("name,us_per_call,derived")
    for name, us, derived in summary:
        print(f"{name},{us:.1f},{derived}")

    if args.details:
        for name, rows in details.items():
            print(f"\n== {name} ==")
            sys.stdout.write(_csv(rows))

    if args.out:
        payload = {
            "hw": args.hw or "default",
            "summary": [{"name": n, "us_per_call": round(u, 1), "derived": d}
                        for n, u, d in summary],
            "details": details,
        }
        if args.out == "json":
            # canonical perf-trajectory artifact at the repository root
            root = pathlib.Path(__file__).resolve().parents[1]
            out = root / ("BENCH_smoke.json" if args.smoke
                          else "BENCH_full.json")
        else:
            out = pathlib.Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(payload, indent=2, default=str))
        print(f"wrote {out}")


def _derive(name: str, rows: list[dict]) -> str:
    if name == "table4_applications":
        errs = [r["err_pct"] for r in rows]
        return (f"max_err={max(errs):.1f}% mean_err={sum(errs)/len(errs):.1f}% "
                f"(paper: 9.2%/7.6%)")
    if name == "table5_comparison":
        ours = max(r["err_ours_pct"] for r in rows)
        wang = max(r["err_wang_pct"] for r in rows)
        hls = max(r["err_hlscope_pct"] for r in rows)
        return f"max_err ours={ours}% wang={wang}% hlscope={hls}%"
    if name == "fig4_lsu_microbench":
        errs = [r["err_vs_sim_pct"] for r in rows if r["memory_bound"]]
        return f"mean_err_vs_sim={sum(errs)/max(1,len(errs)):.1f}% (mem-bound only)"
    if name == "fig5_stride":
        bca = {r["delta"]: r["t_norm"] for r in rows if r["lsu"] == "bca"}
        return f"bca_linear_delta4={bca.get(4)} (expect ~4.0)"
    if name == "fig3_membound":
        mb = sum(1 for r in rows if r["memory_bound"])
        return f"membound_points={mb}/{len(rows)}"
    if name == "sweep":
        r = rows[0]
        return (f"points={r['n_points']} speedup={r['speedup']}x "
                f"agree={r['agree_rtol_1e6']} pareto={r['pareto_points']}")
    if name == "stream_1m":
        parts = [f"{r['backend']}={r['points_per_sec']:,.0f}pps/"
                 f"{r['peak_rss_mb']:.0f}MB" for r in rows]
        agree = all(r["agree_1e6"] for r in rows)
        return f"points={rows[0]['n_points']} {' '.join(parts)} agree={agree}"
    if name == "stream_10m":
        parts = [f"{r['backend']}={r['points_per_sec']:,.0f}pps/"
                 f"{r['peak_rss_mb']:.0f}MB" for r in rows]
        agree = all(r["agree_device_host"] for r in rows)
        dev = next((r for r in rows if r["backend"] == "jax-jit"), None)
        su = f" device_speedup={dev['speedup_vs_host']}x" if dev else ""
        return (f"points={rows[0]['n_points']} {' '.join(parts)}"
                f"{su} agree_device_host={agree}")
    if name == "stream_dist":
        parts = [f"w{r['workers']}={r['points_per_sec']:,.0f}pps"
                 f"(x{r['speedup_vs_1worker']})" for r in rows]
        agree = all(r["agree"] for r in rows)
        return (f"points={rows[0]['n_points']} {' '.join(parts)} "
                f"agree={agree} cpus={rows[0]['cpus']}")
    if name == "optimize_1m":
        r = rows[0]
        return (f"points={r['n_points']} evals={r['n_evals']} "
                f"({100 * r['evals_fraction']:.2f}%) "
                f"matched_optimum={r['matched_optimum']} "
                f"front_recall={r['front_recall']} "
                f"speedup_vs_full_grid={r['speedup_vs_full_grid']}x")
    if name == "serve_smoke":
        by = {r["scenario"]: r for r in rows}
        single, hot, cold = by["single"], by["serve_hot"], by["serve_cold"]
        return (f"clients={hot['clients']} "
                f"hot_p50={hot['p50_us']:.0f}us "
                f"hot_p99={hot['p99_us']:.0f}us "
                f"({hot['x_single']:.2f}x single {single['p50_us']:.0f}us, "
                f"budget {hot['p99_budget']:.0f}x) "
                f"hot={hot['qps']:,.0f}qps hit={hot['cache_hit_rate']:.2f} "
                f"cold={cold['qps']:,.0f}qps "
                f"mean_batch={cold['mean_batch']:.1f}")
    if name == "model_e2e":
        total = next(r for r in rows if r["hardware"] == "total")
        parts = [f"{r['hardware']}/{r['phase']}={r['t_total_ms']}ms"
                 for r in rows if r["hardware"] != "total"]
        return (f"agree={total['agree']} wall={total['wall_s']}s "
                f"{' '.join(parts)}")
    if name == "table6_kernel_validation":
        errs = [r["err_pct"] for r in rows if isinstance(r["err_pct"], float)]
        fails = len(rows) - len(errs)
        return (f"kernels={len(errs)} max_err={max(errs, default=0):.1f}% "
                f"failures={fails} (measured vs Eqs. 1-10, calibrated)")
    return f"rows={len(rows)}"


if __name__ == "__main__":
    main()
