"""model_e2e smoke bench: whole-model estimation on two hardware presets.

Walks the shipped transformer config (reduced to a 2-layer smoke shape so
CPU lowering stays fast), composes train-step and decode-step estimates
through ``Session.estimate_model`` on two ``repro.hw`` presets, and
re-sums the per-op estimates through individual ``Session.estimate``
calls — the ``agree`` column is the composed-total == summed-parts
invariant.  ``wall_s`` on the ``total`` row is lower + compile + walk +
compose for everything.
"""
from __future__ import annotations

import time

#: Two presets with genuinely different memory systems: the paper's FPGA
#: board and the TPU transplant.
HARDWARE = ("stratix10_ddr4_1866", "tpu_v5e")
PHASES = ("train", "decode")


def model_e2e(session=None) -> list[dict]:
    import repro
    from repro import hw as hwreg
    from repro.configs import ARCHS, reduced_config
    from repro.workload import steps

    cfg = reduced_config(ARCHS[sorted(ARCHS)[0]], layers_scale=2)
    t0 = time.perf_counter()
    # Lower + walk once; the per-preset sessions re-score the same records.
    texts = {p: steps.phase_hlo(cfg, p, batch=2, seq_len=64)
             for p in PHASES}

    rows: list[dict] = []
    for hw_name in HARDWARE:
        sess = (session or repro.Session()).with_hardware(
            hwreg.get(hw_name))
        rep = sess.estimate_model(texts, name=cfg.name)
        for phase in rep.phases:
            parts = sum(sess.estimate(op.design).t_exe
                        for op in phase.ops)
            agree = abs(phase.t_total - parts) <= 1e-6 * max(parts, 1e-30)
            rows.append({
                "hardware": hw_name,
                "phase": phase.name,
                "model": cfg.name,
                "t_total_ms": round(phase.t_total * 1e3, 6),
                "n_ops": phase.n_ops,
                "n_scored": len(phase.ops),
                "bytes_mb": round(phase.total_bytes / 1e6, 3),
                "flops_m": round(phase.flops / 1e6, 3),
                "bottleneck": phase.bottleneck,
                "memory_bound_share": round(
                    sum(op.t_exe for op in phase.ops
                        if op.estimate.memory_bound)
                    / phase.t_total if phase.t_total else 0.0, 3),
                "agree": bool(agree),
            })
    rows.append({
        "hardware": "total", "phase": "all", "model": cfg.name,
        "wall_s": round(time.perf_counter() - t0, 3),
        "agree": all(r["agree"] for r in rows),
    })
    return rows
