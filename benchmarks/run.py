"""Benchmark harness: one function per paper table/figure + framework perf.

Prints CSV sections:
  * paper figures: model-vs-paper success-rate deltas (the reproduction
    scorecard; closed-form calibrated model + Monte-Carlo spot checks),
  * trial-batched vs per-trial Monte-Carlo characterization speedup
    (the PR-over-PR perf trajectory headline),
  * program-level Monte-Carlo (XOR / MAJ3 / ripple adder through the
    unified trial-batched executor) per-trial vs batched,
  * resident-register vs host-staged program execution (RowClone-chained
    intermediates: host-write bus-byte reduction at matched success),
  * scheduled vs greedy resident execution (compile-time polarity
    scheduling: polarity-spill reduction at matched success),
  * resident v2 (duplication-not-spill + pinned inputs): zero add4
    polarity spills at the native row geometry and strictly fewer
    chained host-write bytes than the PR-4 sessions,
  * multi-bank scaling (BankArray): Monte-Carlo trial groups sharded
    round-robin over N independent per-bank chips — modeled DRAM-time
    (makespan) throughput at 16 banks vs 1, single-bank bit parity with
    the plain BankSim path, and a cross-bank popcount reduction tree,
  * fused multi-bank MC: the bank axis stacked onto the trial axis —
    wall-clock throughput of the fused path vs the per-bank loop at 4
    and 16 banks (bit-identical results, exact parity gate), plus the
    occupancy-aware group dealer's makespan on uneven loads,
  * in-DRAM vs CPU cost model (the paper's motivation, Table-style),
  * kernel micro-benchmarks (packed-op throughput on this host),
  * PuD-engine offload accounting on LM workloads,
  * static analysis: plan-verifier (symbolic replay) overhead over the
    program zoo and DDR4 timing lint of the engine command logs
    (violations gated to 0; by-design PuD gaps and the independent-bank
    makespan's tRRD/tFAW optimism quantified), plus the rank-legal
    schedule of the same logs (post-schedule violations gated to 0),
  * roofline: APA command throughput vs the DDR4 command-bus ceiling
    across 1-16 banks — the optimistic independent-bank model scales
    linearly while the rank-legal schedule flattens at the 4-ACT/tFAW
    rate limit (every scheduled stream must re-lint to 0 violations).

Run: PYTHONPATH=src python -m benchmarks.run [--fast] [--json [PATH]]
                                             [--only SECTION]...

``--json`` additionally writes machine-readable timings + success-rate
deltas (default path BENCH_pr9.json) so CI can archive the trajectory;
``benchmarks.diff_bench`` compares snapshots across PRs/nightlies.
``--only`` (repeatable) runs just the named sections — see
``_sections`` for the keys (e.g. ``--only fused --only bankarray``).
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

#: machine-readable results accumulated by the sections (--json output)
RESULTS: dict = {"sections": {}}


def _p(*args):
    print(*args, flush=True)


def _csv(name, rows, header):
    _p(f"\n== {name} ==")
    _p(header)
    for r in rows:
        _p(",".join(str(x) for x in r))
    RESULTS["sections"][name] = {"header": header,
                                 "rows": [list(r) for r in rows]}


def fig5_coverage():
    from repro.core import charz
    d = charz.fig5_activation_coverage()
    rows = [(k, round(100 * d["model"].get(k, 0.0), 3),
             round(100 * v, 3),
             round(100 * (d["model"].get(k, 0.0) - v), 3))
            for k, v in d["paper"].items()]
    _csv("Fig5 activation-type coverage (%)", rows,
         "type,model,paper,delta")


def fig7_not(mc=False):
    from repro.core import charz
    d = charz.fig7_not_vs_dst_rows(mc=mc, trials=60)
    rows = []
    for k, v in d.items():
        if k == "paper":
            continue
        paper = d["paper"].get(k, "")
        rows.append((k, round(100 * v["closed_form"], 2),
                     round(100 * v.get("monte_carlo", float("nan")), 2)
                     if mc else "",
                     round(100 * paper, 2) if paper else ""))
    _csv("Fig7 NOT success vs #destination rows (%)", rows,
         "n_dst,closed_form,monte_carlo,paper")


def fig8_patterns():
    from repro.core import charz
    d = charz.fig8_not_activation_patterns()
    rows = [(k, round(100 * v, 2)) for k, v in d.items()
            if ":" in str(k)]
    rows.append(("n2n_advantage", round(100 * d["n2n_advantage"], 2)))
    rows.append(("paper_n2n_advantage",
                 round(100 * d["paper_n2n_advantage"], 2)))
    _csv("Fig8 NOT success by activation type (%)", rows, "type,success")


def fig9_distance():
    from repro.core import charz
    d = charz.fig9_not_distance_heatmap()
    rows = [(k, round(100 * v, 2)) for k, v in d.items()]
    _csv("Fig9 NOT success by (src,dst) distance region (%)", rows,
         "src-dst,success")


def fig10_12_not_modifiers():
    from repro.core import charz
    d = charz.fig10_not_temperature()
    rows = [(n, *[round(100 * d[n][t], 2) for t in (50, 60, 70, 80, 95)])
            for n in d]
    _csv("Fig10 NOT success vs temperature (%)", rows,
         "n_dst,50C,60C,70C,80C,95C")
    d = charz.fig11_not_speed()
    rows = [(n, *[round(100 * d[n][s], 2) for s in (2133, 2400, 2666)])
            for n in d]
    _csv("Fig11 NOT success vs speed grade (%)", rows,
         "n_dst,2133,2400,2666")
    d = charz.fig12_not_die_revision()
    _csv("Fig12 NOT success by module (%)",
         [(k, round(100 * v, 2)) for k, v in d.items()], "module,success")


def fig15_ops(mc=False):
    from repro.core import charz
    d = charz.fig15_ops_vs_inputs(mc=mc, trials=40)
    rows = []
    for op in ("and", "nand", "or", "nor"):
        for n in (2, 4, 8, 16):
            cell = d[op][n]
            paper = d["paper_16"][op] if n == 16 else ""
            rows.append((op, n, round(100 * cell["closed_form"], 2),
                         round(100 * cell.get("monte_carlo", float("nan")),
                               2) if mc else "",
                         round(100 * paper, 2) if paper else ""))
    _csv("Fig15 op success vs #inputs (%)", rows,
         "op,n,closed_form,monte_carlo,paper16")


def fig16_kdep():
    from repro.core import charz
    d = charz.fig16_k_dependence()
    rows = [(k, *[round(100 * x, 1) for x in v]) for k, v in d.items()]
    _csv("Fig16 success vs #logic-1 operands (%)", rows, "op,k=0..n")


def fig17_21_op_modifiers():
    from repro.core import charz
    d = charz.fig17_ops_distance_heatmap()
    rows = []
    for op in ("and", "nand", "or", "nor"):
        rows.append((op, round(100 * d[op]["spread"], 2),
                     round(100 * d["paper_spread"][op], 2)))
    _csv("Fig17 op distance-spread (max-min, %)", rows,
         "op,model,paper")
    d = charz.fig18_data_pattern()
    rows = [(op, round(100 * d[op]["avg_delta"], 2),
             round(100 * d["paper_avg_delta"][op], 2))
            for op in ("and", "nand", "or", "nor")]
    _csv("Fig18 data-pattern delta all01-random (%)", rows,
         "op,model,paper")
    d = charz.fig19_ops_temperature()
    rows = [(op, round(100 * d[op]["max_delta"], 2),
             round(100 * d["paper_max_delta"][op], 2))
            for op in ("and", "nand", "or", "nor")]
    _csv("Fig19 op max temperature delta 50->95C (%)", rows,
         "op,model,paper")
    d = charz.fig20_ops_speed()
    nand4 = d["nand"][4]
    rows = [("nand4_2133_minus_2400",
             round(100 * (nand4[2133] - nand4[2400]), 2),
             round(100 * d["paper_nand4_2133_2400"], 2))]
    _csv("Fig20 op speed effect (%)", rows, "metric,model,paper")
    d = charz.fig21_ops_die_revision()
    rows = [(mod, round(100 * d[mod]["and"][2], 2)) for mod in d]
    _csv("Fig21 2-input AND by die (%)", rows, "module,success")


def charz_batched_speedup(fast=False):
    """Trial-batched vs per-trial Monte-Carlo wall clock at equal trial
    counts — the acceptance benchmark for the batched simulator core.

    The per-trial column runs the seed's one-episode-per-trial loop
    (``batched=False``); the batched column runs the same trial count as
    one vectorized episode per stratified activation pair.
    """
    from repro.core import charz

    # enough trials that the batched path's fixed per-episode costs are
    # amortized (tg = trials/9 per stratified pair); still ~4s in fast mode
    trials = 324 if fast else 648
    points = [
        ("and2", lambda b: charz.mc_boolean_success("and", 2, trials=trials,
                                                    batched=b)),
        ("or4", lambda b: charz.mc_boolean_success("or", 4, trials=trials,
                                                   batched=b)),
        ("and16", lambda b: charz.mc_boolean_success("and", 16, trials=trials,
                                                     batched=b)),
        ("nand16", lambda b: charz.mc_boolean_success("nand", 16,
                                                      trials=trials,
                                                      batched=b)),
        ("not1", lambda b: charz.mc_not_success(1, trials=trials, batched=b)),
        ("not8", lambda b: charz.mc_not_success(8, trials=trials, batched=b)),
        ("cellmap_and4", lambda b: float(np.mean(charz.measure_cell_map(
            "and", 4, trials=trials, batched=b)))),
    ]
    points[0][1](True)   # warm the pair inventory / caches
    rows = []
    tot_pt = tot_b = 0.0
    detail = {}
    for name, fn in points:
        t0 = time.perf_counter()
        v_pt = float(fn(False))
        t_pt = time.perf_counter() - t0
        t0 = time.perf_counter()
        v_b = float(fn(True))
        t_b = time.perf_counter() - t0
        tot_pt += t_pt
        tot_b += t_b
        rows.append((name, trials, round(t_pt, 3), round(t_b, 3),
                     round(t_pt / t_b, 1), round(100 * v_pt, 2),
                     round(100 * v_b, 2), round(100 * (v_b - v_pt), 2)))
        detail[name] = {"trials": trials, "per_trial_s": t_pt,
                        "batched_s": t_b, "speedup": t_pt / t_b,
                        "per_trial_success": v_pt, "batched_success": v_b}
    speedup = tot_pt / tot_b
    rows.append(("TOTAL", trials, round(tot_pt, 3), round(tot_b, 3),
                 round(speedup, 1), "", "", ""))
    _csv("Characterization MC: per-trial vs trial-batched (equal trials)",
         rows,
         "point,trials,per_trial_s,batched_s,speedup,"
         "per_trial_succ,batched_succ,delta")
    _p(f"characterization batched speedup: {speedup:.1f}x "
       f"(target >= 10x)")
    RESULTS["charz_speedup"] = speedup
    RESULTS["charz_speedup_detail"] = detail
    return speedup


def program_mc_speedup(fast=False):
    """Program-level Monte-Carlo through the unified executor: whole
    compiled Boolean programs (XOR / MAJ3 / 4-bit ripple adder) on the
    noisy simulator, per-trial reference vs trial-batched ``run_sim`` at
    equal trial counts (acceptance target: >= 5x)."""
    from repro.core import charz

    cfgs = [
        ("xor", 216 if fast else 432),
        ("maj3", 216 if fast else 432),
        ("add4", 54 if fast else 108),
    ]
    # warm pair-inventory/caches at the benchmark seed
    charz.mc_program_success("xor", trials=9, row_bits=2048, seed=0)
    rows = []
    tot_pt = tot_b = 0.0
    detail = {}
    for name, trials in cfgs:
        prog = charz.get_program(name)
        n_ops = sum(1 for i in prog.instrs
                    if i.op not in ("input", "const"))
        t0 = time.perf_counter()
        v_pt = float(charz.mc_program_success(name, trials=trials,
                                              batched=False))
        t_pt = time.perf_counter() - t0
        t0 = time.perf_counter()
        v_b = float(charz.mc_program_success(name, trials=trials))
        t_b = time.perf_counter() - t0
        est = float(charz.program_success_estimate(name))
        tot_pt += t_pt
        tot_b += t_b
        rows.append((name, n_ops, trials, round(t_pt, 3), round(t_b, 3),
                     round(t_pt / t_b, 1), round(100 * v_pt, 2),
                     round(100 * v_b, 2), round(100 * est, 2)))
        detail[name] = {"native_ops": n_ops, "trials": trials,
                        "per_trial_s": t_pt, "batched_s": t_b,
                        "speedup": t_pt / t_b,
                        "per_trial_success": v_pt, "batched_success": v_b,
                        "independent_op_estimate": est}
    speedup = tot_pt / tot_b
    rows.append(("TOTAL", "", "", round(tot_pt, 3), round(tot_b, 3),
                 round(speedup, 1), "", "", ""))
    _csv("Program execution MC: per-trial vs trial-batched (equal trials)",
         rows,
         "program,native_ops,trials,per_trial_s,batched_s,speedup,"
         "per_trial_succ,batched_succ,indep_op_est")
    _p(f"program execution batched speedup: {speedup:.1f}x "
       f"(target >= 5x)")
    RESULTS["program_speedup"] = speedup
    RESULTS["program_speedup_detail"] = detail
    return speedup


def resident_vs_staged(fast=False):
    """Resident-register vs host-staged program execution on the DRAM
    simulator: same compiled programs, same seeds — the resident executor
    chains intermediates in-bank via RowClone, so host-write bus traffic
    collapses (acceptance target: >= 50% fewer host-write bytes on the
    4-bit adder) at matched Monte-Carlo success.
    """
    import numpy as np
    from repro.core import charz
    from repro.core import compiler as CC
    from repro.core.isa import PudIsa
    from repro.core.simulator import BankSim

    trials = {"xor": 216, "maj3": 216, "add4": 54 if fast else 108}
    rows = []
    detail = {}
    for name, tr in trials.items():
        prog = charz.get_program(name)
        names = sorted({i.name for i in prog.instrs if i.op == "input"})
        # success at equal seeds / trial counts
        t0 = time.perf_counter()
        s_stg = float(charz.mc_program_success(name, trials=tr, seed=0))
        t_stg = time.perf_counter() - t0
        t0 = time.perf_counter()
        s_res = float(charz.mc_program_success(
            name, trials=tr, seed=0,
            resident=CC.ResidentPolicy.SCHEDULED))
        t_res = time.perf_counter() - t0
        # command-stream traffic of one trial-batched run per mode
        traffic = {}
        for resident in (False, True):
            sim = BankSim(row_bits=2048, seed=0, error_model="analog",
                          trials=12, track_unshared=False)
            isa = PudIsa(sim)
            rng = np.random.default_rng(1)
            ins = {n: rng.integers(0, 2, (12, isa.width)).astype(np.uint8)
                   for n in names}
            CC.run_sim(prog, ins, isa,
                       resident=(CC.ResidentPolicy.SCHEDULED if resident
                                 else CC.ResidentPolicy.HOST))
            row_bytes = sim.geom.row_bits // 8
            traffic[resident] = {
                "wr_bytes": sim.log.counts.get("WR", 0) * row_bytes,
                "rd_bytes": sim.log.counts.get("RD", 0) * row_bytes,
                "rowclones": sim.log.counts.get("RC", 0),
                "apas": sim.log.counts.get("APA", 0),
                "energy_pj": sim.log.energy_pj,
            }
        red = 1.0 - traffic[True]["wr_bytes"] / traffic[False]["wr_bytes"]
        rows.append((name, tr, round(100 * s_stg, 2), round(100 * s_res, 2),
                     traffic[False]["wr_bytes"], traffic[True]["wr_bytes"],
                     round(100 * red, 1), traffic[True]["rowclones"],
                     round(t_stg, 3), round(t_res, 3)))
        detail[name] = {
            "trials": tr,
            "staged_success": s_stg, "resident_success": s_res,
            "staged_wr_bytes": traffic[False]["wr_bytes"],
            "resident_wr_bytes": traffic[True]["wr_bytes"],
            "staged_rd_bytes": traffic[False]["rd_bytes"],
            "resident_rd_bytes": traffic[True]["rd_bytes"],
            "wr_byte_reduction": red,
            "rowclones": traffic[True]["rowclones"],
            "staged_s": t_stg, "resident_s": t_res,
        }
    _csv("Resident vs host-staged program execution (DRAM backend)",
         rows,
         "program,trials,staged_succ,resident_succ,staged_wr_B,"
         "resident_wr_B,wr_reduction_pct,rowclones,staged_s,resident_s")
    red4 = detail["add4"]["wr_byte_reduction"]
    _p(f"add4 resident host-write byte reduction: {100 * red4:.1f}% "
       f"(target >= 50%)")
    RESULTS["resident_detail"] = detail
    RESULTS["resident_wr_reduction_add4"] = red4
    return red4


def scheduled_vs_greedy(fast=False):
    """Scheduled vs greedy resident execution: the compile-time
    polarity/residency scheduler (consumer-polarity De Morgan forms,
    pressure ordering, Belady rows) against the PR-3 greedy policy —
    same programs, same seeds.  Acceptance target: >= 30% fewer polarity
    spills on the 4-bit adder at matched Monte-Carlo success; the static
    plan's command counts ARE the measured stream (test-enforced), so the
    spill/traffic columns double as the cost-model table.
    """
    from repro.core import charz
    from repro.core import compiler as CC
    from repro.core.isa import PudIsa
    from repro.core.simulator import BankSim

    trials = {"xor": 216, "maj3": 216, "add4": 54 if fast else 108}
    rows = []
    detail = {}
    for name, tr in trials.items():
        prog = charz.get_program(name)
        plans = {}
        for policy in ("greedy", "scheduled"):
            isa = PudIsa(BankSim(row_bits=2048, seed=0,
                                 error_model="analog", trials=12,
                                 track_unshared=False))
            plans[policy] = CC.schedule_resident(prog, isa, policy=policy)
        g, s = plans["greedy"], plans["scheduled"]
        t0 = time.perf_counter()
        succ = float(charz.mc_program_success(
            name, trials=tr, seed=0,
            resident=CC.ResidentPolicy.SCHEDULED))
        t_mc = time.perf_counter() - t0
        red = (1.0 - s.polarity_spills / g.polarity_spills
               if g.polarity_spills else 0.0)
        rows.append((name, tr, g.polarity_spills, s.polarity_spills,
                     round(100 * red, 1), g.writes, s.writes,
                     g.rowclones, s.rowclones, round(100 * succ, 2),
                     round(t_mc, 3)))
        detail[name] = {
            "trials": tr,
            "greedy_spills": g.polarity_spills,
            "scheduled_spills": s.polarity_spills,
            "spill_reduction": red,
            "greedy_wr": g.writes, "scheduled_wr": s.writes,
            "greedy_rowclones": g.rowclones,
            "scheduled_rowclones": s.rowclones,
            "scheduled_success": succ,
        }
    _csv("Scheduled vs greedy resident execution (polarity scheduling)",
         rows,
         "program,trials,greedy_spills,sched_spills,spill_reduction_pct,"
         "greedy_wr,sched_wr,greedy_rc,sched_rc,sched_succ,sched_mc_s")
    red4 = detail["add4"]["spill_reduction"]
    _p(f"add4 scheduled polarity-spill reduction: {100 * red4:.1f}% "
       f"(target >= 30%)")
    RESULTS["scheduled_detail"] = detail
    RESULTS["sched_spill_reduction_add4"] = red4
    return red4


def resident_v2(fast=False):
    """Resident compilation v2: duplication-not-spill + pinned inputs.

    Three measurements per program, all PR-5 acceptance quantities:

    * **plan @ native geometry** — the scheduled plan at the module's
      real row width (the geometry the engine runs): polarity spills
      must hit 0 on add4 with the conflicts converted to dual-form
      producer duplications, at lower CostModel energy than both the
      greedy plan and the spill alternative (the cost-gate contract),
    * **chained multi-block engine run** — host-write bytes of the new
      default (`PudEngine("dram")` = scheduled resident, sessions with
      pinned inputs) vs the PR-4 behavior (scheduled sessions without
      duplication/pinning) on the same planes: strictly fewer bytes,
    * **Monte-Carlo success** — `resident="scheduled"` at the PR-4
      benchmark config (matched-success evidence for the diff gate).
    """
    import jax.numpy as jnp
    from repro.core import charz
    from repro.core import compiler as CC
    from repro.core.isa import PudIsa
    from repro.core.simulator import BankSim
    from repro.pud.engine import PudEngine

    trials = {"xor": 216, "maj3": 216, "add4": 54 if fast else 108}
    rows = []
    detail = {}
    rng = np.random.default_rng(17)
    for name, tr in trials.items():
        prog = charz.get_program(name)
        # --- scheduled plan at the native row geometry ---
        plans = {}
        for policy in ("greedy", "scheduled"):
            isa = PudIsa(BankSim(error_model="ideal", seed=0))
            plans[policy] = CC.schedule_resident(prog, isa, policy=policy)
        g, s = plans["greedy"], plans["scheduled"]
        isa = PudIsa(BankSim(error_model="ideal", seed=0))
        spill_alt = CC.schedule_resident(
            prog, isa, policy="scheduled",
            _fixed=(s.order, s.demorgan, {}, False))
        # --- chained multi-block engine run: v2 vs PR-4 behavior ---
        names = sorted({i.name for i in prog.instrs if i.op == "input"})
        planes = {n: jnp.asarray(rng.integers(0, 2 ** 32, (2, 600),
                                              dtype=np.uint32))
                  for n in names}            # 38400 bits -> 10 chunks
        eng = PudEngine("dram", noisy=False)          # v2 default
        out_v2 = eng.run_program(prog, dict(planes))
        staged_v2 = eng.report.staged_bytes
        # PR-4 behavior: scheduled sessions without duplication/pinning,
        # on the exact chunk-block partition the engine used (reuse the
        # engine's own chunking so a DRAM_CHUNK_BATCH/DRAM_MIN_PAIR_SWEEP
        # change cannot silently desynchronize the comparison)
        from repro.kernels import ops as kops
        w = eng._isa.width
        bits = {n: PudEngine._to_chunks(
            np.asarray(kops.ref.unpack_bits(p)).reshape(-1), w)
            for n, p in planes.items()}
        n_chunks = bits[names[0]].shape[0]
        blk_sz = eng._block_size(n_chunks)
        staged_pr4 = 0
        sess4: dict[int, CC.ResidentSession] = {}
        for lo in range(0, n_chunks, blk_sz):
            blk = {n: b[lo:lo + blk_sz] for n, b in bits.items()}
            t = blk[names[0]].shape[0]
            if t not in sess4:
                sim = BankSim(error_model="ideal", seed=0,
                              trials=t if t > 1 else None,
                              track_unshared=False)
                sess4[t] = CC.ResidentSession(
                    prog, PudIsa(sim), policy="scheduled",
                    pin_inputs=False, duplicate=False)
            sim = sess4[t].isa.sim
            wr0 = sim.log.counts.get("WR", 0)
            sess4[t].run({k: v[0] for k, v in blk.items()} if t == 1
                         else blk)
            staged_pr4 += (sim.log.counts.get("WR", 0) - wr0) \
                * (sim.geom.row_bits // 8)
        # --- MC success at the PR-4 benchmark config ---
        succ = float(charz.mc_program_success(
            name, trials=tr, seed=0,
            resident=CC.ResidentPolicy.SCHEDULED))
        rows.append((name, g.polarity_spills, s.polarity_spills,
                     s.duplications, round(s.cost().energy_pj / 1e3, 1),
                     round(spill_alt.cost().energy_pj / 1e3, 1),
                     staged_v2, staged_pr4, round(100 * succ, 2)))
        detail[name] = {
            "greedy_spills": g.polarity_spills,
            "scheduled_spills": s.polarity_spills,
            "duplications": s.duplications,
            "plan_energy_nJ": s.cost().energy_pj / 1e3,
            "spill_alt_energy_nJ": spill_alt.cost().energy_pj / 1e3,
            "chained_staged_bytes": staged_v2,
            "pr4_staged_bytes_3blocks": staged_pr4,
            "scheduled_success": succ,
        }
        out_ref = PudEngine("jnp").run_program(prog, dict(planes))
        for k in prog.outputs:
            assert (np.asarray(out_v2[k]) == np.asarray(out_ref[k])).all()
    _csv("Resident v2: duplication-not-spill + pinned inputs "
         "(native geometry)",
         rows,
         "program,greedy_spills,sched_spills,duplications,plan_nJ,"
         "spill_alt_nJ,chained_staged_B,pr4_staged_B,sched_succ")
    add4 = detail["add4"]
    _p(f"add4 scheduled spills at native geometry: "
       f"{add4['scheduled_spills']} (target 0, "
       f"{add4['duplications']} duplications); chained staged bytes "
       f"{add4['chained_staged_bytes']} vs PR-4 "
       f"{add4['pr4_staged_bytes_3blocks']}")
    RESULTS["resident_v2_detail"] = detail
    RESULTS["resident_v2_add4_spills"] = add4["scheduled_spills"]
    return add4["scheduled_spills"]


def multi_bank_scaling(fast=False):
    """Multi-bank sharded Monte-Carlo + cross-bank reduction (BankArray).

    Banks are independent chips operating concurrently in real DRAM, so
    the scaling quantity is *modeled DRAM time*: the array finishes with
    its slowest bank (makespan = max over per-bank command-log time).
    On this 1-CPU host the banks still simulate sequentially, so
    wall-clock does not scale — the honest wall columns show that.

    Three measurements:

    * **MC throughput scaling** — ``charz.mc_program_success(banks=N)``
      shards the trial groups round-robin over N per-bank chips;
      acceptance target: >= 10x trials/makespan at 16 banks vs 1
      (>= 60% parallel efficiency), plus the scheduled-resident variant
      (bank 0 runs the planner search, siblings replay its decisions),
    * **single-bank parity** — ``BankArray(banks=1)`` executes the
      program zoo bit-for-bit identically to a plain ``BankSim`` (exact
      diff gate: ``parity_mismatch_bits`` must stay 0),
    * **cross-bank reduction** — per-bank popcounts combined through the
      host-mediated binary adder tree (``BankArray.popcount``), checked
      against ideal arithmetic (``reduce_mismatch_lanes`` must stay 0).
    """
    from repro.core import charz
    from repro.core import compiler as CC
    from repro.core.bankarray import BankArray
    from repro.core.isa import PudIsa
    from repro.core.policy import ResidentPolicy
    from repro.core.simulator import BankSim

    groups = 48                      # divisible by 16 and by 1
    trials = 96 if fast else 192
    rows = []
    detail = {}
    for name in ("xor", "maj3"):
        per = {}
        for banks in (1, 16):
            st: dict = {}
            t0 = time.perf_counter()
            succ = float(charz.mc_program_success(
                name, trials=trials, seed=0, groups=groups, banks=banks,
                stats=st))
            per[banks] = {"success": succ,
                          "makespan_ns": st["makespan_ns"],
                          "total_time_ns": st["total_time_ns"],
                          "wall_s": time.perf_counter() - t0}
        speedup = per[1]["makespan_ns"] / per[16]["makespan_ns"]
        eff = speedup / 16
        rows.append((name, trials, groups,
                     round(100 * per[1]["success"], 2),
                     round(100 * per[16]["success"], 2),
                     round(per[1]["makespan_ns"] / 1e3, 1),
                     round(per[16]["makespan_ns"] / 1e3, 1),
                     round(speedup, 2), round(100 * eff, 1),
                     round(per[1]["wall_s"], 3),
                     round(per[16]["wall_s"], 3)))
        detail[name] = {
            "trials": trials, "groups": groups,
            "success_b1": per[1]["success"],
            "success_b16": per[16]["success"],
            "makespan_b1_ns": per[1]["makespan_ns"],
            "makespan_b16_ns": per[16]["makespan_ns"],
            "speedup_16": speedup, "efficiency_16": eff,
        }
    _csv("Multi-bank MC scaling (modeled DRAM time; banks concurrent)",
         rows,
         "program,trials,groups,succ_b1,succ_b16,makespan_b1_us,"
         "makespan_b16_us,speedup,efficiency_pct,wall_b1_s,wall_b16_s")
    sp = min(d["speedup_16"] for d in detail.values())
    ef = min(d["efficiency_16"] for d in detail.values())
    _p(f"16-bank modeled speedup: {sp:.2f}x (target >= 10x), "
       f"efficiency {100 * ef:.1f}% (target >= 60%)")

    # scheduled resident at 16 banks: search on bank 0, replay elsewhere
    st = {}
    t0 = time.perf_counter()
    succ = float(charz.mc_program_success(
        "xor", trials=trials, seed=0, groups=groups, banks=16,
        resident=ResidentPolicy.SCHEDULED, stats=st))
    detail["xor_scheduled_b16"] = {
        "success_b16": succ, "makespan_ns": st["makespan_ns"],
        "wall_s": time.perf_counter() - t0}
    _p(f"xor scheduled@16 banks: success {100 * succ:.2f}%, "
       f"makespan {st['makespan_ns'] / 1e3:.1f}us")

    # single-bank parity: BankArray(banks=1) vs plain BankSim, program zoo
    mism = 0
    rng = np.random.default_rng(11)
    for name in ("xor", "maj3", "add4"):
        prog = charz.get_program(name)
        in_names = sorted({i.name for i in prog.instrs if i.op == "input"})
        arr = BankArray(row_bits=1024, seed=5, error_model="analog",
                        trials=8, track_unshared=False)
        sim = BankSim(row_bits=1024, seed=5, error_model="analog",
                      trials=8, track_unshared=False)
        w = arr.isa(0).width
        ins = {n: rng.integers(0, 2, (8, w)).astype(np.uint8)
               for n in in_names}
        out_a = CC.run_sim(prog, ins, arr.isa(0))
        out_b = CC.run_sim(prog, ins, PudIsa(sim))
        mism += int(sum((out_a[k] != out_b[k]).sum()
                        for k in prog.outputs))
    detail["parity_mismatch_bits"] = mism
    _p(f"BankArray(banks=1) vs BankSim parity mismatches: {mism} "
       f"(target 0)")

    # cross-bank reduction: per-bank popcounts -> host-mediated add tree
    arr = BankArray(banks=4, row_bits=256, error_model="ideal", seed=0)
    w = arr.isa(0).width
    planes = [rng.integers(0, 2, (3, w)).astype(np.uint8)
              for _ in range(4)]
    counts, _bank = arr.popcount(planes)
    want = sum(p.sum(axis=0, dtype=int) for p in planes)
    got = sum(counts[i].astype(int) << i for i in range(counts.shape[0]))
    bad = int((got != want).sum())
    detail["reduce_mismatch_lanes"] = bad
    detail["reduce_makespan_ns"] = arr.makespan_ns()
    detail["reduce_total_time_ns"] = arr.total_time_ns()
    _p(f"cross-bank popcount reduction: {bad} wrong lanes (target 0); "
       f"makespan {arr.makespan_ns() / 1e3:.1f}us vs single-bank "
       f"{arr.total_time_ns() / 1e3:.1f}us")
    RESULTS["bankarray_detail"] = detail
    RESULTS["bankarray_speedup_16"] = sp
    return sp


def fused_multibank(fast=False):
    """Fused multi-bank MC: the bank axis stacked onto the trial axis.

    An N-bank, T-trial sweep runs as one ``(N*tg, rows, bits)`` array
    pass per round instead of N per-bank episodes
    (``repro.core.fused``), paying the per-command host overhead once.
    Three measurements:

    * **wall-clock throughput vs banks** — the same MC estimate (raw op,
      NOT protocol, compiled program) through the loop reference
      (``fused=False``) and the fused path at 4 and 16 banks;
      acceptance target: >= 6x wall-clock at 16 banks on the raw-op
      characterization sweep (the headline ``fused_speedup_16``; the
      small NOT/program points are setup-dominated at benchmark sizes
      and reported informationally), with every success rate *exactly*
      equal to the loop path's (the fused path is bit-identical per
      bank, so the deltas must be +0.00),
    * **fused parity** — loop-vs-fused engine runs (nary / NOT /
      compiled program on the dram backend, numpy resolve) compared
      bit-for-bit; ``fused_parity_mismatch_bits`` must stay 0,
    * **occupancy dealer** — a mixed-fan-in, uneven group load dealt
      ``round_robin`` vs ``occupancy`` (greedy least-loaded on live
      ``bank_time_ns``): the occupancy makespan must not exceed
      round-robin's (``occupancy_regression_ns`` gated at 0).
    """
    import jax.numpy as jnp
    from repro.core import charz
    from repro.core.bankarray import BankArray
    from repro.core.policy import EngineConfig
    from repro.pud.engine import PudEngine

    trials = 192 if fast else 384
    groups = 48                      # divisible by 4 and by 16
    points = [
        ("and16", lambda b, f, st: charz.mc_boolean_success(
            "and", 16, trials=trials, groups=groups, banks=b, fused=f,
            stats=st)),
        ("not4", lambda b, f, st: charz.mc_not_success(
            4, trials=trials, groups=groups, banks=b, fused=f, stats=st)),
        ("xor", lambda b, f, st: charz.mc_program_success(
            "xor", trials=trials, groups=groups, banks=b, fused=f,
            stats=st)),
    ]
    # warm pair inventories / program caches so neither path pays
    # first-call costs inside a timed region
    points[0][1](4, True, None)
    points[2][1](4, True, None)
    rows = []
    detail = {}
    max_delta = 0.0
    speedup = 0.0
    for banks in (4, 16):
        for name, fn in points:
            t0 = time.perf_counter()
            v_loop = float(fn(banks, False, None))
            t_loop = time.perf_counter() - t0
            t0 = time.perf_counter()
            v_fused = float(fn(banks, True, None))
            t_fused = time.perf_counter() - t0
            sp = t_loop / t_fused
            if banks == 16 and name == "and16":
                speedup = sp
            max_delta = max(max_delta, abs(v_fused - v_loop))
            rows.append((name, banks, trials, round(t_loop, 3),
                         round(t_fused, 3), round(sp, 1),
                         round(100 * v_loop, 2), round(100 * v_fused, 2),
                         round(100 * (v_fused - v_loop), 2)))
            detail[f"{name}_b{banks}"] = {
                "banks": banks, "trials": trials, "groups": groups,
                "loop_s": t_loop, "fused_s": t_fused, "speedup": sp,
                "loop_success": v_loop, "fused_success": v_fused,
            }
    _csv("Fused multi-bank MC: loop vs bank-stacked episodes "
         "(equal trials)",
         rows,
         "point,banks,trials,loop_s,fused_s,speedup,"
         "loop_succ,fused_succ,delta")
    _p(f"fused 16-bank wall-clock speedup (raw-op sweep): {speedup:.1f}x "
       f"(target >= 6x); max success delta {100 * max_delta:.2f} pts "
       f"(target 0.00)")

    # fused parity through the engine stack: nary / NOT / program
    rng = np.random.default_rng(9)

    def mk(r, c):
        return jnp.asarray(rng.integers(0, 2 ** 32, (r, c),
                                        dtype=np.uint32))

    def xor_bits(a, b):
        x = np.bitwise_xor(np.asarray(a), np.asarray(b))
        return int(np.unpackbits(x.view(np.uint8)).sum())

    el = PudEngine(EngineConfig(backend="dram", banks=4, noisy=True,
                                fused=False))
    ef = PudEngine(EngineConfig(backend="dram", banks=4, noisy=True,
                                fused=True))
    x, y = mk(6, 9), mk(6, 9)
    mism = xor_bits(el.nary(jnp.stack([x, y]), "nand"),
                    ef.nary(jnp.stack([x, y]), "nand"))
    mism += xor_bits(el.not_(x), ef.not_(x))
    prog = charz.get_program("xor")
    ol = el.run_program(prog, {"a": x, "b": y})
    of = ef.run_program(prog, {"a": x, "b": y})
    mism += sum(xor_bits(ol[k], of[k]) for k in prog.outputs)
    detail["fused_parity_mismatch_bits"] = mism
    detail["success_delta_pts"] = 100 * max_delta
    _p(f"fused engine parity mismatches: {mism} bits (target 0)")

    # occupancy dealer: mixed fan-ins, groups not divisible by banks
    works = [("and", 16)] * 3 + [("and", 2)] * 7
    weights = [float(n) for _op, n in works]
    span = {}
    for dealer in ("round_robin", "occupancy"):
        arr = BankArray(banks=4, row_bits=512, seed=2,
                        error_model="analog", trials=8,
                        track_unshared=False)
        wrng = np.random.default_rng(3)
        deal = charz._deal_groups(
            arr, len(works), dealer,
            weights if dealer == "occupancy" else None)
        for g, b in enumerate(deal):
            isa = arr.isa(b)
            isa.sim.recycle_rows()
            op, n = works[g]
            ops = charz._random_bits(wrng, (8, n, isa.width))
            isa.nary_op(op, ops.swapaxes(0, 1))
        span[dealer] = arr.makespan_ns()
    impr = 1.0 - span["occupancy"] / span["round_robin"]
    detail["occupancy"] = {
        "round_robin_makespan_ns": span["round_robin"],
        "occupancy_makespan_ns": span["occupancy"],
        "improvement": impr,
    }
    detail["occupancy_regression_ns"] = max(
        0.0, span["occupancy"] - span["round_robin"])
    _p(f"occupancy dealer makespan: {span['occupancy'] / 1e3:.1f}us vs "
       f"round-robin {span['round_robin'] / 1e3:.1f}us "
       f"({100 * impr:.1f}% better)")
    RESULTS["fused_detail"] = detail
    RESULTS["fused_speedup_16"] = speedup
    return speedup


def calibration_scorecard():
    from repro.core import analog as A
    from repro.core import calibrate as C
    res = C.residuals(A.DEFAULT_PARAMS)
    rows = [(k, p, round(m, 2), round(d, 2))
            for k, (p, m, d) in sorted(res.items())]
    _csv("Calibration scorecard (every quantified paper claim)", rows,
         "claim,paper,model,delta")
    worst = max(abs(d) for _p_, _m, d in res.values())
    n_tight = sum(1 for _p_, _m, d in res.values() if abs(d) <= 1.5)
    _p(f"claims={len(res)} within1.5pts={n_tight} worst_delta={worst:.2f}")
    RESULTS["calibration"] = {"claims": len(res), "within_1p5": n_tight,
                              "worst_delta": worst}


def cost_model_table():
    """The paper's motivation: in-DRAM bulk ops vs processor-centric."""
    from repro.core.isa import CostModel
    cm = CostModel()
    rows = []
    for n in (2, 4, 8, 16):
        d = cm.boolean(n)
        c = cm.cpu_baseline(n)
        rows.append((n, round(d.time_ns, 1), round(c.time_ns, 1),
                     round(d.energy_pj / 1e3, 2), round(c.energy_pj / 1e3, 2),
                     round(c.energy_pj / d.energy_pj, 1),
                     d.bus_bytes, c.bus_bytes))
    _csv("In-DRAM vs CPU per-row bulk op (8KB row)", rows,
         "n_inputs,dram_ns,cpu_ns,dram_nJ,cpu_nJ,energy_ratio,"
         "dram_bus_B,cpu_bus_B")


def reliability_planning():
    from repro.core import reliability as R
    rows = []
    for op, n in (("and", 2), ("and", 16), ("nand", 16), ("or", 16)):
        pl = R.plan(op, n, 0.999999)
        rows.append((op, n, pl.replicas, round(100 * pl.p_raw, 2),
                     f"{pl.p_final:.8f}", pl.ops_total))
    _csv("Redundancy planning to 1e-6 error (best placement)", rows,
         "op,n,replicas,p_raw,p_final,native_ops")


def kernel_microbench(fast=False):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    reps = 3 if fast else 10
    rows = []

    def bench(name, fn, *args, bits):
        fn(*args)  # warm
        t0 = time.time()
        for _ in range(reps):
            jax.block_until_ready(fn(*args))
        dt = (time.time() - t0) / reps
        rows.append((name, round(dt * 1e3, 3),
                     round(bits / dt / 1e9, 2)))

    p16 = jnp.asarray(rng.integers(0, 2 ** 32, (16, 64, 512),
                                   dtype=np.uint32))
    bench("nary_and_16x64x512", lambda x: ops.nary_bitwise(x, "and"), p16,
          bits=16 * 64 * 512 * 32)
    a = jnp.asarray(rng.integers(0, 2 ** 32, (8, 64, 512), dtype=np.uint32))
    bench("adder_8plane", lambda x: ops.add_planes(x, x), a,
          bits=8 * 64 * 512 * 32)
    x = jnp.asarray(rng.integers(0, 2 ** 32, (256, 64), dtype=np.uint32))
    w = jnp.asarray(rng.integers(0, 2 ** 32, (256, 64), dtype=np.uint32))
    bench("popcount_gemm_256x256x2048",
          lambda a_, b_: ops.popcount_gemm(a_, b_, kind="xnor"), x, w,
          bits=256 * 256 * 2048 * 2)
    _csv("Kernel micro-bench (interpret-mode on CPU; TPU is the target)",
         rows, "kernel,ms_per_call,Gbit/s")


def pud_offload_lm():
    """PuD-engine offload accounting on LM mask/dedup workloads."""
    import jax.numpy as jnp
    from repro.pud.engine import PudEngine
    from repro.pud import masks as M
    eng = PudEngine("jnp")
    M.compose_attention_mask(eng, 4096, window=1024,
                             doc_ids=jnp.zeros(4096, jnp.int32))
    gate = jnp.asarray(np.random.default_rng(0).integers(0, 60, (8192, 4)))
    M.route_mask_planes(eng, gate, 60)
    rep = eng.report.summary()
    rows = [(k, round(v, 4) if isinstance(v, float) else v)
            for k, v in rep.items()]
    _csv("PuD offload report (mask composition + MoE routing)", rows,
         "metric,value")


def static_analysis(fast=False):
    """Static analysis: plan-verifier overhead + DDR4 timing lint.

    * **verifier overhead** — wall time of the symbolic plan replay
      (``analysis.verify_plan``) per zoo program/policy, next to the
      planning time it rides on; findings must be 0 everywhere (the
      ``static.verify_findings`` counter is gated exactly),
    * **timing lint** — the loop-path and fused-path engine command
      logs expand to primitive ACT/PRE timelines and lint against the
      JEDEC rule set; per-bank ``violations`` must be 0 (exact gate)
      while the deliberate PuD gaps land in ``by_design``, and the
      rank-level tRRD/tFAW merge quantifies the independent-bank
      makespan's optimism (``min_legal_makespan_ns`` lower bound),
    * **rank schedule** — the same logs run through the event-driven
      scheduler (``analysis.schedule_bank_array``): the legal makespan
      with its refresh/rank stall split, and the proof obligation that
      the scheduled stream re-lints to 0 violations (exact gate on
      ``static.sched_violations_{loop,fused}``).
    """
    import jax.numpy as jnp

    from repro import analysis
    from repro.core import charz
    from repro.core import compiler as CC
    from repro.core.isa import PudIsa
    from repro.core.policy import ResidentPolicy
    from repro.core.simulator import BankSim
    from repro.pud.engine import PudEngine

    detail: dict = {}
    rows = []
    n_findings = 0
    verify_ms = plan_ms = 0.0
    for name in charz.PROGRAMS:
        prog = charz.get_program(name)
        for pol in ("greedy", "scheduled"):
            isa = PudIsa(BankSim(row_bits=128, error_model="ideal",
                                 seed=11))
            t0 = time.time()
            plan = CC.schedule_resident(prog, isa, policy=pol,
                                        verify=False)
            t1 = time.time()
            findings = analysis.verify_plan(prog, plan)
            t2 = time.time()
            n_findings += len(findings)
            verify_ms += (t2 - t1) * 1e3
            plan_ms += (t1 - t0) * 1e3
            rows.append((name, pol, len(plan.steps), len(findings),
                         round((t1 - t0) * 1e3, 2),
                         round((t2 - t1) * 1e3, 2)))
    _csv("Plan verifier (symbolic replay) over the program zoo", rows,
         "program,policy,steps,findings,plan_ms,verify_ms")
    detail["verify_findings"] = n_findings
    detail["verify_ms_total"] = round(verify_ms, 2)
    detail["verify_overhead_pct"] = round(
        100.0 * verify_ms / plan_ms, 2) if plan_ms else 0.0

    rows = []
    rng = np.random.default_rng(7)
    prog = charz.get_program("xor")
    for fused in (False, True):
        eng = PudEngine("dram", banks=2, fused=fused,
                        resident=(ResidentPolicy.HOST if fused
                                  else ResidentPolicy.SCHEDULED),
                        verify=False)
        ins = {k: jnp.asarray(np.asarray(rng.integers(
            0, 2**32, (4, 4), dtype=np.uint32))) for k in ("a", "b")}
        eng.run_program(prog, ins)
        rep = analysis.lint_bank_array(eng._array)
        tl = eng.schedule_timing()
        label = "fused" if fused else "loop"
        by_design = sum(sum(r.by_design.values()) for r in rep.per_bank)
        deficit_ns = sum(r.deficit_ns for r in rep.per_bank)
        rows.append((label, rep.violations, by_design,
                     round(deficit_ns, 1), rep.trrd_conflicts,
                     rep.tfaw_conflicts, round(rep.makespan_ns, 1),
                     round(rep.min_legal_makespan_ns, 1),
                     round(rep.optimism_pct, 2),
                     round(tl.legal_makespan_ns, 1),
                     tl.relint_violations))
        detail[f"timing_violations_{label}"] = rep.violations
        detail[f"timing_by_design_{label}"] = by_design
        detail[f"makespan_ns_{label}"] = round(rep.makespan_ns, 1)
        detail[f"min_legal_makespan_ns_{label}"] = round(
            rep.min_legal_makespan_ns, 1)
        detail[f"legal_makespan_ns_{label}"] = round(
            tl.legal_makespan_ns, 1)
        detail[f"refresh_stall_ns_{label}"] = round(
            tl.refresh_stall_ns, 1)
        detail[f"rank_stall_ns_{label}"] = round(tl.rank_stall_ns, 1)
        detail[f"sched_violations_{label}"] = tl.relint_violations
    _csv("DDR4 timing lint of engine command logs (2-bank loop vs fused)",
         rows, "path,violations,by_design,deficit_ns,trrd_conflicts,"
               "tfaw_conflicts,makespan_ns,min_legal_makespan_ns,"
               "optimism_pct,legal_makespan_ns,sched_violations")
    sv = (detail["sched_violations_loop"]
          + detail["sched_violations_fused"])
    _p(f"post-schedule lint violations: {sv} (target 0); legal makespan "
       f"loop {detail['legal_makespan_ns_loop']}ns vs optimistic "
       f"{detail['makespan_ns_loop']}ns")
    RESULTS["static_detail"] = detail


def roofline(fast=False):
    """APA throughput vs DDR4 command bandwidth across bank counts.

    Each bank runs the same APA-heavy characterization workload (rounds
    of 4-input NAND at 8 trials), so the optimistic independent-bank
    model predicts a flat makespan — N banks finish N times the work in
    the time of one.  The rank-legal schedule instead serializes ACTs
    under tRRD and the 4-per-tFAW window: per-bank throughput flattens
    once the rank ACT rate hits the ``4 / tFAW`` command-bus ceiling,
    which is the paper's Section-6 scaling argument in roofline form.

    Gates: every scheduled stream re-lints to 0 violations
    (``roofline.sched_violations_b{N}``, exact) and
    ``legal >= max(optimistic, min_legal)`` at every point; ACT counts
    are deterministic counters, throughputs tolerance-gated floats.
    """
    from repro import analysis
    from repro.core import charz
    from repro.core.bankarray import BankArray
    from repro.core.device import timings_for

    rounds = 4 if fast else 8
    rows = []
    detail: dict = {"rounds": rounds}
    bad = 0
    for banks in (1, 2, 4, 8, 16):
        arr = BankArray(banks=banks, row_bits=512, seed=3,
                        error_model="analog", trials=8,
                        track_unshared=False)
        rng = np.random.default_rng(13)
        for b in range(banks):
            isa = arr.isa(b)
            for _ in range(rounds):
                isa.sim.recycle_rows()
                ops = charz._random_bits(rng, (8, 4, isa.width))
                isa.nary_op("nand", ops.swapaxes(0, 1))
        t = timings_for(arr.module)
        tl = analysis.schedule_bank_array(arr)
        opt = float(arr.makespan_ns())
        legal = tl.legal_makespan_ns
        n_ops = banks * rounds
        ceiling = 4.0 / t.tFAW * 1e3            # ACTs per us, rank-wide
        acts_us = tl.n_acts / (legal / 1e3)
        ok = (tl.relint_violations == 0
              and legal >= max(opt, tl.min_legal_makespan_ns) - 1e-6)
        bad += 0 if ok else 1
        rows.append((banks, n_ops, tl.n_acts, round(opt, 1),
                     round(legal, 1),
                     round(n_ops / (opt / 1e3), 2),
                     round(n_ops / (legal / 1e3), 2),
                     round(acts_us, 1), round(ceiling, 1),
                     round(tl.refresh_stall_ns, 1),
                     round(tl.rank_stall_ns, 1),
                     tl.relint_violations))
        detail[f"acts_b{banks}"] = tl.n_acts
        detail[f"sched_violations_b{banks}"] = tl.relint_violations
        detail[f"makespan_ns_b{banks}"] = round(opt, 1)
        detail[f"legal_makespan_ns_b{banks}"] = round(legal, 1)
        detail[f"min_legal_makespan_ns_b{banks}"] = round(
            tl.min_legal_makespan_ns, 1)
        detail[f"refresh_stall_ns_b{banks}"] = round(
            tl.refresh_stall_ns, 1)
        detail[f"rank_stall_ns_b{banks}"] = round(tl.rank_stall_ns, 1)
        detail[f"ops_per_us_optimistic_b{banks}"] = n_ops / (opt / 1e3)
        detail[f"ops_per_us_legal_b{banks}"] = n_ops / (legal / 1e3)
        detail[f"acts_per_us_legal_b{banks}"] = acts_us
    detail["acts_per_us_ceiling"] = round(ceiling, 2)
    detail["gate_failures"] = bad
    _csv("Roofline: APA throughput vs DDR4 command bandwidth (1-16 banks)",
         rows,
         "banks,ops,acts,makespan_ns,legal_makespan_ns,"
         "ops_per_us_opt,ops_per_us_legal,acts_per_us,act_ceiling_per_us,"
         "refresh_stall_ns,rank_stall_ns,sched_violations")
    flat = (detail["ops_per_us_legal_b16"]
            / detail["ops_per_us_optimistic_b16"])
    _p(f"roofline gate failures: {bad} (target 0); 16-bank legal "
       f"throughput is {100 * flat:.1f}% of the optimistic model "
       f"(ACT rate {detail['acts_per_us_legal_b16']:.1f}/us vs ceiling "
       f"{detail['acts_per_us_ceiling']}/us)")
    RESULTS["roofline_detail"] = detail
    RESULTS["roofline_gate_failures"] = bad
    return bad


def workloads_bench(fast=False):
    """Real workloads on the substrate: bloom dedup + bit-serial dot.

    Four measurements, the PR-10 acceptance quantities:

    * **bloom insert bytes** — a batch-streamed bloom insert on the
      2-bank dram engine: in-DRAM host bytes moved under the scheduled
      resident policy must undercut both the host-staged reference
      policy and the processor-centric CPU baseline,
    * **golden parity** — dram bloom plane/probe bit-identical to jnp,
      dram bit-serial dot equal to the popcount-GEMM kernel (exact
      counters, 0 in the baseline),
    * **accuracy vs success rate** — the noisy bit-serial dot across
      temperatures: whole-program MC success and exact-lane workload
      accuracy next to the composed per-op estimate (the
      ``reliability.plan`` contract as a curve),
    * **fan-in sweep** — bloom probe/insert program success vs fan-in
      (paper SS5's many-input AND/OR at workload fan-ins).
    """
    from repro.core import charz
    from repro.core import compiler as CC
    from repro.core.isa import PudIsa
    from repro.core.policy import ResidentPolicy
    from repro.core.simulator import BankSim
    from repro.kernels import ops as kops
    from repro.pud import workloads as W
    from repro.pud.bloom import PudBloomFilter
    from repro.pud.engine import PudEngine

    detail = {}
    bad = 0
    rng = np.random.default_rng(10)

    # --- bloom insert: bytes moved + plane/probe parity ---
    keys = rng.integers(0, 2 ** 60, 512).astype(np.uint64)
    probe = np.arange(1024, dtype=np.uint64)
    filters = {}
    for label, pol in (("scheduled", None),
                       ("host_staged", ResidentPolicy.HOST)):
        eng = PudEngine("dram", noisy=False, banks=2, resident=pol)
        bf = PudBloomFilter(m_bits=1 << 15, n_hashes=4, engine=eng)
        for lo in range(0, 512, 128):       # 4 streamed insert batches
            bf.insert(keys[lo:lo + 128])
        filters[label] = bf
    bf_j = PudBloomFilter(m_bits=1 << 15, n_hashes=4)
    for lo in range(0, 512, 128):
        bf_j.insert(keys[lo:lo + 128])
    bf_d = filters["scheduled"]
    plane_mismatch = int((np.asarray(kops.unpack_bits(bf_d.plane))
                          != np.asarray(kops.unpack_bits(bf_j.plane))).sum())
    probe_mismatch = int((bf_d.probe(probe) != bf_j.probe(probe)).sum())
    sched_b = filters["scheduled"].engine.report.host_bytes_moved
    host_b = filters["host_staged"].engine.report.host_bytes_moved
    cpu_b = filters["scheduled"].engine.report.cpu.bus_bytes
    detail["bloom_insert"] = {
        "host_bytes_scheduled": sched_b,
        "host_bytes_host_staged": host_b,
        "cpu_baseline_bytes": cpu_b,
        "parity_mismatch_bits": plane_mismatch,
        "probe_mismatch_keys": probe_mismatch,
    }
    if not (sched_b < host_b and sched_b < cpu_b):
        bad += 1
    bad += int(plane_mismatch > 0) + int(probe_mismatch > 0)

    # --- bit-serial dot: golden parity on the dram engine ---
    x = rng.integers(0, 2, (8, 8), dtype=np.uint8)
    w = rng.integers(0, 2, (8, 8), dtype=np.uint8)
    eng = PudEngine("dram", noisy=False, banks=2)
    got = W.dot_bitserial(x, w, eng)
    ref = np.asarray(kops.popcount_gemm_bits(x, w))
    tree, _arr = W.dot_bitserial_tree(x, w, banks=2, row_bits=2048)
    detail["dot_parity"] = {
        "mismatch_lanes": int((got != ref).sum()),
        "tree_mismatch_lanes": int((tree != ref).sum()),
        "host_bytes_moved": eng.report.host_bytes_moved,
        "cpu_baseline_bytes": eng.report.cpu.bus_bytes,
    }
    bad += int((got != ref).any()) + int((tree != ref).any())

    # --- accuracy vs success rate: noisy dot across temperatures ---
    prog = charz.get_program("dot_bitserial8")
    a, b = W.dot_lane_planes(x, w)
    k, lanes = a.shape
    ref_flat = ref.reshape(-1)
    tr = 24 if fast else 48
    rows = []
    for temp in ((50.0, 85.0) if fast else (50.0, 70.0, 85.0)):
        est = float(charz.program_success_estimate("dot_bitserial8",
                                                   temp_c=temp))
        mc = float(charz.mc_program_success(
            prog, trials=tr, temp_c=temp, seed=0,
            resident=ResidentPolicy.SCHEDULED))
        # workload accuracy: exact-count lanes of the real x/w planes
        t_acc = 16
        isa = PudIsa(BankSim(row_bits=2048, error_model="analog",
                             temp_c=temp, seed=1, trials=t_acc,
                             track_unshared=False))
        pad = isa.width - lanes
        ins = {}
        for i in range(k):
            ins[f"a{i}"] = np.tile(np.pad(a[i], (0, pad)), (t_acc, 1))
            ins[f"b{i}"] = np.tile(np.pad(b[i], (0, pad)), (t_acc, 1))
        out = CC.run_sim(prog, ins, isa, trials=t_acc,
                         resident=ResidentPolicy.SCHEDULED)
        cnt = sum(np.asarray(out[f"c{i}"], dtype=np.int64)[:, :lanes] << i
                  for i in range(len(out)))
        acc = float((cnt == ref_flat[None, :]).mean())
        detail[f"dot_t{int(temp)}"] = {
            "per_op_estimate": est, "mc_success": mc,
            "lane_accuracy": acc,
        }
        if mc < est - 0.05:     # composition contract (+ MC margin)
            bad += 1
        rows.append((f"{temp:.0f}C", f"{est:.2e}", round(mc, 4),
                     round(acc, 4)))
    _csv("Bit-serial dot: accuracy vs success rate (noisy analog model)",
         rows, "temp,per_op_estimate,mc_success,lane_accuracy")

    # --- bloom probe/insert fan-in sweep (SS5 many-input AND/OR) ---
    sweep = charz.workload_fanin_sweep(
        fanins=(2, 8) if fast else (2, 4, 8, 16),
        trials=48 if fast else 96, seed=0)
    rows = []
    for name, d in sweep.items():
        detail[name] = d
        rows.append((name, round(d["estimate"], 4),
                     round(d["mc_success"], 4)))
    _csv("Bloom probe/insert program success vs fan-in",
         rows, "program,estimate,mc_success")

    _p(f"bloom insert host bytes: scheduled {sched_b} vs host-staged "
       f"{host_b} vs CPU baseline {cpu_b} "
       f"({100 * (1 - sched_b / host_b):.1f}% below host-staged)")
    _p(f"workloads gate failures: {bad}")
    RESULTS["workloads_detail"] = detail
    RESULTS["workloads_gate_failures"] = bad
    RESULTS["workloads_bloom_bytes_ratio"] = sched_b / host_b
    return bad


def _json_path(argv) -> str | None:
    if "--json" not in argv:
        return None
    i = argv.index("--json")
    if i + 1 < len(argv) and not argv[i + 1].startswith("-"):
        return argv[i + 1]
    return "BENCH_pr10.json"


def _sections(fast: bool, mc: bool):
    """Ordered (name, runner) section registry — the ``--only`` keys."""
    return [
        ("fig5", fig5_coverage),
        ("fig7", lambda: fig7_not(mc=mc)),
        ("fig8", fig8_patterns),
        ("fig9", fig9_distance),
        ("fig10_12", fig10_12_not_modifiers),
        ("fig15", lambda: fig15_ops(mc=mc)),
        ("fig16", fig16_kdep),
        ("fig17_21", fig17_21_op_modifiers),
        ("charz_speedup", lambda: charz_batched_speedup(fast=fast)),
        ("program_speedup", lambda: program_mc_speedup(fast=fast)),
        ("resident", lambda: resident_vs_staged(fast=fast)),
        ("scheduled", lambda: scheduled_vs_greedy(fast=fast)),
        ("resident_v2", lambda: resident_v2(fast=fast)),
        ("bankarray", lambda: multi_bank_scaling(fast=fast)),
        ("fused", lambda: fused_multibank(fast=fast)),
        ("calibration", calibration_scorecard),
        ("cost_model", cost_model_table),
        ("reliability", reliability_planning),
        ("kernels", lambda: kernel_microbench(fast=fast)),
        ("pud_offload", pud_offload_lm),
        ("static", lambda: static_analysis(fast=fast)),
        ("roofline", lambda: roofline(fast=fast)),
        ("workloads", lambda: workloads_bench(fast=fast)),
    ]


def _only_filter(argv) -> list[str]:
    """Section names selected by ``--only NAME`` (repeatable)."""
    names = []
    for i, a in enumerate(argv):
        if a == "--only":
            if i + 1 >= len(argv) or argv[i + 1].startswith("-"):
                raise SystemExit("--only needs a section name")
            names.append(argv[i + 1])
    return names


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    fast = "--fast" in sys.argv
    json_path = _json_path(sys.argv)
    only = _only_filter(sys.argv)
    mc = True          # MC columns are cheap now that the MC is batched
    sections = _sections(fast, mc)
    known = [n for n, _fn in sections]
    for n in only:
        if n not in known:
            raise SystemExit(f"--only {n}: unknown section "
                             f"(one of {', '.join(known)})")
    t0 = time.time()
    _p("# FCDRAM-JAX benchmark suite (one section per paper figure)")
    RESULTS["fast"] = fast
    if only:
        RESULTS["only"] = only
    for name, fn in sections:
        if only and name not in only:
            continue
        fn()
    total = time.time() - t0
    _p(f"\ntotal {total:.1f}s")
    if json_path:
        RESULTS["total_s"] = total
        with open(json_path, "w") as f:
            json.dump(RESULTS, f, indent=1, default=float)
        _p(f"wrote {json_path}")


if __name__ == "__main__":
    main()
