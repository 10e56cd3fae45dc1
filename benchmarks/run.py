"""Benchmark harness -- one benchmark per paper table/figure, plus kernel
micro-benchmarks and the TPU-scale derived benchmarks.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--fast]

Prints ``name,us_per_call,derived`` CSV (one line per benchmark) and writes
per-benchmark artifacts (full tables) under ``experiments/bench/``.

Paper mapping:
  table1_resnet18 / table1_resnet50   -> Table I (FPS, FPS/TOPS)
  fig5a_layer_latency                 -> Fig. 5(a) per-layer latencies
  fig5bc_scheduler_ratios             -> Fig. 5(b,c) time/memory ratios
  efficiency_98pct                    -> SS V "up to 98% performance efficiency"
  wrb_out_of_order                    -> SS II-A WRB claim
  aimc_noise_snr                      -> SS VI AIMC emulation
Beyond-paper (TPU adaptation):
  kernel_int8_gemm / kernel_im2col    -> Pallas kernels vs oracles (wall time)
  scheduler_capacity_sweep            -> two-phase gain vs memory pressure
  streaming_plan_lm                   -> scheduler applied to assigned LMs
  plan / stream                       -> repro.plan perf trajectory
                                         (BENCH_plan.json) and the executed
                                         stage pipeline vs its analytic
                                         model (BENCH_stream.json)
  serve                               -> device-resident decode loop vs the
                                         legacy host-loop engine: decode
                                         tok/s, retraces under mixed-length
                                         traffic, greedy bit-identity,
                                         prefill latency per bucket, Poisson
                                         TTFT percentiles (BENCH_serve.json)
  train_smoke / serve_smoke           -> end-to-end throughput (reduced configs)
  roofline_summary                    -> reads experiments/dryrun artifacts
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = ROOT / "experiments" / "bench"


def timed(fn, *args, repeats=3, **kw):
    fn(*args, **kw)  # warmup / compile
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / repeats
    return out, dt * 1e6  # us


def emit(name: str, us: float, derived: str, artifact: dict | None = None):
    print(f"{name},{us:.1f},{derived}")
    if artifact is not None:
        BENCH_DIR.mkdir(parents=True, exist_ok=True)
        (BENCH_DIR / f"{name}.json").write_text(json.dumps(artifact, indent=1))


# ------------------------------------------------------------- Table I ----


def bench_table1(variant: int):
    from repro.core.pu import PU_1X, PU_2X
    from repro.core import simulator as sim

    paper = {18: (1237.7, 268.6), 50: (584.9, 126.9)}[variant]
    layers = sim.resnet_gemm_layers(variant)

    def run():
        s1 = sim.simulate_model(PU_1X, layers)
        s2 = sim.simulate_model(PU_2X, layers)
        return sim.FleetSim(sims=[("pu1x", s1, 5), ("pu2x", s2, 5)])

    fleet, us = timed(run, repeats=1)
    fps, fpt = fleet.fps, fleet.fps_per_tops
    emit(
        f"table1_resnet{variant}",
        us,
        f"fps={fps:.1f}(paper {paper[0]});fps_per_tops={fpt:.1f}(paper {paper[1]});"
        f"rel_err={abs(fps - paper[0]) / paper[0]:.3f}",
        {
            "fps": fps, "fps_per_tops": fpt, "tops": fleet.tops,
            "paper_fps": paper[0], "paper_fps_per_tops": paper[1],
            "per_pu": {
                name: {
                    "fps": s.fps_scheduled,
                    "latency_ms": s.frame_s_scheduled * 1e3,
                    "efficiency": s.efficiency,
                }
                for name, s, _ in fleet.sims
            },
        },
    )


def bench_fig5a():
    from repro.core.pu import PU_1X, PU_2X
    from repro.core import simulator as sim

    layers = sim.resnet_gemm_layers(50)
    rows = []

    def run():
        rows.clear()
        for pu in (PU_1X, PU_2X):
            for ls in [sim.simulate_layer(pu, l) for l in layers]:
                rows.append(
                    {
                        "pu": pu.name,
                        "layer": ls.layer.name,
                        "latency_us": ls.latency_s * 1e6,
                        "compute_us": ls.compute_s * 1e6,
                        "act_in_us": ls.act_in_s * 1e6,
                        "wrb_ok": ls.wrb_rate_ok,
                    }
                )
        return rows

    _, us = timed(run, repeats=1)
    tot1 = sum(r["latency_us"] for r in rows if r["pu"] == "pu1x") / 1e3
    tot2 = sum(r["latency_us"] for r in rows if r["pu"] == "pu2x") / 1e3
    emit(
        "fig5a_layer_latency",
        us,
        f"resnet50_pu1x_ms={tot1:.1f}(paper 25.3);pu2x_ms={tot2:.1f}(paper 12.9);layers={len(layers)}",
        {"rows": rows},
    )


def bench_fig5bc():
    from repro.core.pu import PU_2X
    from repro.core import simulator as sim
    from repro.core import scheduler as sched

    layers = sim.resnet_gemm_layers(18)
    tiles = sim.model_tiles(PU_2X, layers)

    def run():
        return sched.two_phase(tiles, capacity=PU_2X.fast_mem_bytes)

    res, us = timed(run, repeats=1)
    tr = res.time_ratios()
    mr = res.memory_ratios()
    n_stall_base = sum(1 for t in res.baseline.tiles if t.stall > 1e-12)
    n_stall_adpt = sum(1 for t in res.adaptive.tiles if t.stall > 1e-12)
    emit(
        "fig5bc_scheduler_ratios",
        us,
        f"tiles={len(tiles)};stalled_base={n_stall_base};stalled_adaptive={n_stall_adpt};"
        f"stall_reduction={res.stall_reduction:.3f};mem_ratio_max={max(mr):.3f}",
        {
            "time_ratios": tr,
            "memory_ratios": mr,
            "baseline_stall_s": res.baseline.total_stall,
            "adaptive_stall_s": res.adaptive.total_stall,
            "relocations": [
                {"tile": t.index, "from": bt.window, "to": t.window}
                for bt, t in zip(res.baseline.tiles, res.adaptive.tiles)
                if bt.window != t.window
            ],
        },
    )


def bench_efficiency():
    from repro.core.pu import PU_1X, PU_2X
    from repro.core import simulator as sim

    out = {}
    def run():
        for variant in (18, 50):
            layers = sim.resnet_gemm_layers(variant)
            for pu in (PU_1X, PU_2X):
                out[f"r{variant}_{pu.name}"] = sim.simulate_model(pu, layers).efficiency
        return out

    _, us = timed(run, repeats=1)
    emit(
        "efficiency_98pct",
        us,
        ";".join(f"{k}={v:.3f}" for k, v in out.items()) + ";paper=0.98",
        out,
    )


def bench_wrb():
    from repro.core import wrb

    cfg = wrb.WRBConfig()

    def run():
        return {
            str(iv): wrb.ooo_benefit(cfg, n_waves=256, wave_interval=iv)
            for iv in (2, 4, 8)
        }

    res, us = timed(run, repeats=1)
    derived = ";".join(
        f"iv{iv}:in={io.efficiency:.3f},ooo={oo.efficiency:.3f}"
        for iv, (io, oo) in res.items()
    )
    emit("wrb_out_of_order", us, derived,
         {iv: {"in_order": io.efficiency, "ooo": oo.efficiency}
          for iv, (io, oo) in res.items()})


def bench_aimc():
    import jax
    import jax.numpy as jnp
    from repro.core.aimc import AIMCNoiseModel, NoiseInjectionUnit, snr_db
    from repro.core.quant import quantize

    w = jax.random.normal(jax.random.PRNGKey(0), (256, 256))
    niu = NoiseInjectionUnit({"w": quantize(w)}, AIMCNoiseModel())
    counter = [0]

    def run():
        counter[0] += 1
        noisy = niu.refresh(jax.random.PRNGKey(counter[0]))
        return float(snr_db(w, noisy["w"].dequantize()))

    snr, us = timed(run, repeats=3)
    emit("aimc_noise_snr", us, f"snr_db={snr:.1f};model=pcm_default",
         {"snr_db": snr})


# ----------------------------------------------------------- kernels -----


def bench_kernel_gemm(fast: bool):
    import jax.numpy as jnp
    from repro.kernels import int8_gemm, ref

    rng = np.random.default_rng(0)
    n, m, p = (128, 256, 128) if fast else (256, 512, 256)
    w = jnp.asarray(rng.integers(-127, 128, (n, m), dtype=np.int8))
    x = jnp.asarray(rng.integers(-127, 128, (m, p), dtype=np.int8))

    y, us_pallas = timed(
        lambda: int8_gemm(w, x, shift=7).block_until_ready(), repeats=2
    )
    yr, us_ref = timed(
        lambda: ref.int8_gemm_ref(w, x, shift=7).block_until_ready(), repeats=2
    )
    ok = bool((np.asarray(y) == np.asarray(yr)).all())
    emit(
        "kernel_int8_gemm",
        us_pallas,
        f"shape={n}x{m}x{p};interpret_vs_ref_ok={ok};ref_us={us_ref:.1f};"
        f"note=interpret-mode(CPU oracle check; perf target is TPU)",
    )


def bench_kernel_im2col(fast: bool):
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    h = 32 if fast else 64
    img = jnp.asarray(rng.integers(-127, 128, (h, h, 16), dtype=np.int8))

    y, us = timed(lambda: ops.im2col(img, 3, 1, 1).block_until_ready(), repeats=2)
    yr, us_ref = timed(
        lambda: ref.im2col_ref(img, 3, 1, 1).block_until_ready(), repeats=2
    )
    ok = bool((np.asarray(y) == np.asarray(yr)).all())
    emit(
        "kernel_im2col",
        us,
        f"img={h}x{h}x16;k3s1p1;ok={ok};ref_us={us_ref:.1f}",
    )


# ----------------------------------------------- scheduler at scale -------


def bench_scheduler_sweep():
    from repro.core.pu import PU_2X
    from repro.core import simulator as sim
    from repro.core import scheduler as sched

    layers = sim.resnet_gemm_layers(50)
    tiles = sim.model_tiles(PU_2X, layers)
    full_cap = PU_2X.fast_mem_bytes
    rows = []

    def run():
        rows.clear()
        for frac in (0.1, 0.2, 0.4, 0.6, 0.8, 1.0):
            cap = int(full_cap * frac)
            # bounded window scan: stress capacities leave many stalls
            # memory-blocked; scanning every window is O(n^2) simulates
            res = sched.two_phase(tiles, capacity=cap, max_window_scan=32)
            rows.append(
                {
                    "capacity_frac": frac,
                    "feasible": res.baseline.feasible,
                    "baseline_stall_ms": res.baseline.total_stall * 1e3,
                    "adaptive_stall_ms": res.adaptive.total_stall * 1e3,
                    "reduction": res.stall_reduction,
                    "baseline_util": res.baseline.utilization,
                    "adaptive_util": res.adaptive.utilization,
                }
            )
        return rows

    _, us = timed(run, repeats=1)
    feasible = [r for r in rows if r["feasible"]]
    mean_red = np.mean([r["reduction"] for r in feasible]) if feasible else 0
    emit(
        "scheduler_capacity_sweep",
        us,
        f"points={len(rows)};mean_stall_reduction={mean_red:.3f};"
        f"min_cap_frac_feasible={min((r['capacity_frac'] for r in feasible), default=None)}",
        {"rows": rows},
    )


def bench_streaming_lm():
    """Host->HBM weight streaming viability per arch: utilization vs tokens
    per round (the l/e ratio analysis of SS III applied to LM serving).
    Decode rounds (small P) are load-bound -- streaming only pays off past
    the arithmetic-intensity breakeven, which we report per arch."""
    from repro.configs import ARCH_IDS, get_config
    from repro.core.pu import host_offload_config
    from repro.runtime.serving import plan_model_streaming

    pu = host_offload_config()
    sweep = (64, 1024, 16384, 131072)
    rows = []

    def run():
        rows.clear()
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            utils = {}
            for p in sweep:
                plan = plan_model_streaming(cfg, pu, batch_tokens=p)
                utils[p] = float(plan.summary()["adaptive_util"])
            breakeven = next(
                (p for p in sweep if utils[p] > 0.5), None
            )
            rows.append({"arch": arch, "util_by_tokens": utils,
                         "breakeven_tokens_50pct": breakeven})
        return rows

    _, us = timed(run, repeats=1)
    at_max = np.mean([r["util_by_tokens"][sweep[-1]] for r in rows])
    n_be = sum(1 for r in rows if r["breakeven_tokens_50pct"] is not None)
    emit(
        "streaming_plan_lm",
        us,
        f"archs={len(rows)};mean_util@{sweep[-1]}tok={at_max:.3f};"
        f"archs_reaching_50pct={n_be};note=decode(P=64)_is_load-bound_by_design",
        {"rows": rows},
    )


# --------------------------------------------------- plan subsystem -------


def bench_plan_suite(fast: bool):
    """repro.plan perf trajectory: adaptive-phase wall time, stall
    reduction, incremental-vs-reference speedup, schedule-search gains
    (beam/anneal vs the paper heuristic), load-bound early exit, multi-PU
    partitioning and plan-cache behaviour.  Emits BENCH_plan.json at the
    repo root so future PRs can diff."""
    import time as _time

    from repro.configs import get_config
    from repro.core.pu import PU_1X, PU_2X, host_offload_config
    from repro.core import scheduler as sched
    from repro.core import simulator as sim
    from repro.plan import PlanCache, SearchConfig, plan
    from repro.runtime.serving import model_gemms

    records = {}

    # --fast trims the anneal ladder: same record shape, quick signal
    # (the CI gate only runs against full-mode output)
    anneal_steps = 300 if fast else 1500

    def search_record(tiles, cap, heuristic):
        """Beam + anneal vs the heuristic on one workload."""
        out = {
            "heuristic_stall_reduction": heuristic.stall_reduction,
            "heuristic_wall_s": heuristic.plan_wall_s,
        }
        for strat, cfg in (
            ("beam", SearchConfig(strategy="beam")),
            ("anneal", SearchConfig(
                strategy="anneal", seed=0, anneal_steps=anneal_steps)),
        ):
            t0 = _time.perf_counter()
            sp = plan(tiles, cap, search=cfg)
            out[strat] = {
                "stall_reduction": sp.stall_reduction,
                "wall_s": _time.perf_counter() - t0,
                "relocations": len(sp.relocations()),
                "gain_vs_heuristic": (
                    sp.stall_reduction / heuristic.stall_reduction
                    if heuristic.stall_reduction > 0
                    else float("inf")
                ),
                "search": sp.search,
            }
        out["stall_reduction"] = out["anneal"]["stall_reduction"]
        out["search_gain"] = out["anneal"]["gain_vs_heuristic"]
        return out

    def run():
        records.clear()
        # ResNet workloads under memory pressure (adaptive phase active)
        r50_tiles = None
        for variant in (18, 50):
            layers = sim.resnet_gemm_layers(variant)
            tiles = sim.model_tiles(PU_2X, layers)
            cap = int(PU_2X.fast_mem_bytes * 0.25)
            t0 = _time.perf_counter()
            new = plan(tiles, cap)
            t_new = _time.perf_counter() - t0
            rec = {
                "tiles": len(tiles),
                "capacity_frac": 0.25,
                "adaptive_wall_s": t_new,
                "baseline_stall_s": new.baseline_stall,
                "adaptive_stall_s": new.total_stall,
                "stall_reduction": new.stall_reduction,
                "relocations": len(new.relocations()),
            }
            if not fast:
                # bit-identity vs the reference planner: full-scan on the
                # smaller net, bounded-scan on ResNet-50 (the full-scan
                # reference costs ~20 s there; the bound exercises the
                # same code paths)
                scan = None if variant == 18 else 6
                t0 = _time.perf_counter()
                ref = sched.reference_two_phase(
                    tiles, cap, max_window_scan=scan
                )
                rec["reference_wall_s"] = _time.perf_counter() - t0
                if scan is None:
                    rec["speedup"] = rec["reference_wall_s"] / t_new
                    got = new
                else:
                    rec["reference_window_scan"] = scan
                    t0 = _time.perf_counter()
                    got = plan(tiles, cap, max_window_scan=scan)
                    rec["speedup"] = rec["reference_wall_s"] / (
                        _time.perf_counter() - t0
                    )
                rec["bit_identical"] = (
                    [t.window for t in ref.adaptive.tiles] == list(got.windows)
                    and ref.adaptive.total_stall == got.total_stall
                )
            records[f"resnet{variant}"] = rec
            if variant == 50:
                r50_tiles = tiles
                records["search_resnet50"] = search_record(tiles, cap, new)

        # second search workload: ResNet-50 under tighter memory, where
        # annealing finds relocations the one-shot heuristic cannot
        cap_tight = int(PU_2X.fast_mem_bytes * 0.2)
        heur_tight = plan(r50_tiles, cap_tight)
        rec = search_record(r50_tiles, cap_tight, heur_tight)
        rec["capacity_frac"] = 0.2
        records["search_resnet50_tight"] = rec

        # one LM config: host->HBM streaming plan of a decode round --
        # load-bound by design, so the adaptive phase must detect it and
        # exit without burning wall time on a scan that can't help
        cfg = get_config("olmo-1b")
        gemms = model_gemms(cfg, batch_tokens=16)
        pu = host_offload_config()
        tiles = []
        for _, n, m, p in gemms:
            tiles.extend(pu.gemm_tiles(n, m, p))
        t0 = _time.perf_counter()
        lm_plan = plan(tiles, pu.fast_mem_bytes)
        records["olmo_1b_decode"] = {
            "tiles": len(tiles),
            "adaptive_wall_s": _time.perf_counter() - t0,
            "baseline_stall_s": lm_plan.baseline_stall,
            "adaptive_stall_s": lm_plan.total_stall,
            "stall_reduction": lm_plan.stall_reduction,
            "skipped_load_bound": lm_plan.skipped_load_bound,
        }

        # multi-PU partitioning: K=2 pipeline vs the best single PU
        layers = sim.resnet_gemm_layers(50)
        part = sim.simulate_partitioned([PU_1X, PU_2X], layers)
        single = max(
            sim.simulate_model(PU_1X, layers).fps_scheduled,
            sim.simulate_model(PU_2X, layers).fps_scheduled,
        )
        records["partition_resnet50_k2"] = {
            "fps": part.fps,
            "best_single_pu_fps": single,
            "pipeline_gain": part.fps / single,
            "stages": part.summary()["stages"],
        }

        # cache effectiveness: replanning an identical workload is free
        # (fresh cache so the cold path is exercised exactly once)
        tiles = sim.model_tiles(PU_2X, sim.resnet_gemm_layers(18))
        cache = PlanCache()
        t0 = _time.perf_counter()
        cache.get_or_plan(tiles, PU_2X.fast_mem_bytes)
        t_cold = _time.perf_counter() - t0
        t0 = _time.perf_counter()
        cache.get_or_plan(tiles, PU_2X.fast_mem_bytes)
        records["plan_cache"] = {
            "cold_plan_s": t_cold,
            "warm_plan_s": _time.perf_counter() - t0,
            "hits_gained": cache.stats()["hits"],
        }
        return records

    # no timed() here: its warmup pass would double the suite's wall time
    # and pre-warm the cache record
    t0 = _time.perf_counter()
    run()
    us = (_time.perf_counter() - t0) * 1e6
    r18 = records["resnet18"]
    r50 = records["resnet50"]
    part = records["partition_resnet50_k2"]
    s50 = records["search_resnet50"]
    derived = (
        f"r50_adaptive_s={r50['adaptive_wall_s']:.3f};"
        f"r18_stall_red={r18['stall_reduction']:.3f};"
        + (f"r18_speedup={r18['speedup']:.1f}x;" if "speedup" in r18 else "")
        + f"search_gain={s50['search_gain']:.2f}x;"
        f"olmo_skipped={records['olmo_1b_decode']['skipped_load_bound']};"
        f"k2_gain={part['pipeline_gain']:.2f}x;"
        f"cache_hits={records['plan_cache']['hits_gained']}"
    )
    emit("plan", us, derived, records)
    (ROOT / "BENCH_plan.json").write_text(json.dumps(records, indent=1))


def bench_stream_suite(fast: bool):
    """Stage-parallel streaming runtime vs the analytic pipeline model:
    execute ResNet-50 partitioned plans for K in {1, 2} through
    runtime.pipeline_exec and record measured throughput, the
    measured-vs-predicted bubble fraction, and the K=2 gain over the
    best single-PU executor; then auto-tune microbatch depth against a
    10% target bubble from the executed measurement and compare with the
    fixed M=8 baseline.  Emits BENCH_stream.json at the repo root; CI
    gates on gain >= 1.2x, bubble within 2x of prediction, and the
    auto-tuner hitting its band at no throughput cost."""
    import time as _time

    from repro.core.pu import PU_1X, PU_2X
    from repro.core import simulator as sim
    from repro.runtime.autotune import AutotuneConfig, tune_pipeline
    from repro.runtime.pipeline_exec import execute_partitioned_plan

    layers = sim.resnet_gemm_layers(50)
    # the >=1.2x CI gate is calibrated at M=8 (fewer microbatches grow
    # the fill bubble: gain at M=4 is ~1.19x); the whole suite runs in
    # well under a second, so smoke mode keeps the same M
    M = 8
    records = {"microbatches": M}

    def record(tag, pus):
        pplan = sim.simulate_partitioned(pus, layers)
        rep = execute_partitioned_plan(pplan, n_microbatches=M)
        records[tag] = {
            "pus": [pu.name for pu in pus],
            "stages": [
                {
                    "pu": t.pu,
                    "busy_s": t.busy_s,
                    "stall_s": t.stall_s,
                    "starve_s": t.starve_s,
                    "fetches": t.fetches,
                    "peak_resident_bytes": t.peak_resident_bytes,
                }
                for t in rep.stages
            ],
            "measured_fps": rep.measured_fps,
            "predicted_fps": rep.predicted_fps,
            "steady_fps": rep.steady_fps,
            "analytic_fps": pplan.fps,
            "bubble_measured": rep.bubble_measured,
            "bubble_predicted": rep.bubble_predicted,
            "makespan_s": rep.makespan_s,
            "wall_s": rep.wall_s,
            "max_concurrent_stages": rep.max_concurrent_stages,
        }
        return records[tag]

    def run():
        r1a = record("k1_pu1x", [PU_1X])
        r1b = record("k1_pu2x", [PU_2X])
        r2 = record("k2", [PU_1X, PU_2X])
        best = max(r1a["measured_fps"], r1b["measured_fps"])
        records["best_single_pu_fps"] = best
        records["k2_gain_measured"] = r2["measured_fps"] / best
        records["k2_bubble_vs_predicted"] = (
            r2["bubble_measured"] / max(r2["bubble_predicted"], 1e-12)
        )
        # auto-tuned microbatch depth on the K=2 partition: the tuner
        # must land the executed bubble within 10% of the 0.10 target
        # and lose no throughput against the fixed M=8 baseline
        pplan = sim.simulate_partitioned([PU_1X, PU_2X], layers)
        tuned = tune_pipeline(pplan, AutotuneConfig(target_bubble=0.10))
        records["autotune_k2"] = {
            "target_bubble": tuned.target_bubble,
            "tuned_m": tuned.n_microbatches,
            "analytic_m": tuned.analytic_m,
            "tuned_queue_depth": tuned.queue_depth,
            "bubble_measured": tuned.bubble_measured,
            "within_tolerance": tuned.within_tolerance,
            "measured_fps": tuned.measured_fps,
            "fixed_m8_fps": r2["measured_fps"],
            "fps_vs_fixed_m8": tuned.measured_fps / r2["measured_fps"],
            "trials": tuned.trials,
            "depth_trials": tuned.depth_trials,
        }
        return records

    # no timed(): its warmup pass would run the three pipelines twice
    t0 = _time.perf_counter()
    run()
    us = (_time.perf_counter() - t0) * 1e6
    r2 = records["k2"]
    at = records["autotune_k2"]
    derived = (
        f"M={M};k2_measured_fps={r2['measured_fps']:.1f};"
        f"k2_gain={records['k2_gain_measured']:.2f}x;"
        f"bubble={r2['bubble_measured']:.3f}"
        f"(pred {r2['bubble_predicted']:.3f});"
        f"autoM={at['tuned_m']}@bubble={at['bubble_measured']:.3f};"
        f"wall_s={r2['wall_s']:.2f}"
    )
    emit("stream", us, derived, records)
    (ROOT / "BENCH_stream.json").write_text(json.dumps(records, indent=1))


def bench_serve_suite(fast: bool):
    """Device-resident decode loop vs the legacy host-loop engine
    (DESIGN.md SS7): identical mixed-length traffic through both engines
    per model config, recording decode throughput, the jit trace deltas
    after warmup, greedy stream bit-identity, per-bucket prefill latency,
    and TTFT/TPOT percentiles under a Poisson arrival trace (fused and
    staged); plus the ``pipeline_decode`` record -- a K=2 --multi-pu
    engine serving the same traffic through the overlapped staged
    decode loop (end-to-end medians over paired trials + steady-state
    decode-phase rates), gated on greedy bit-identity with the
    single-PU device loop, on the executor's virtual clock reproducing
    the plan recurrence, and on the >=1.0x steady-state decode
    throughput floor vs the fused loop; a lane-group sweep
    (M in {1,2,4,auto} x K in {2,3}) records bubble fraction and
    tokens/s per point; plus the ``decode_kernels`` record -- the fused
    Pallas decode kernels A/B'd against the composed-XLA decode, per-op
    (decode-attention, MLP, QKV at the smoke configs' shapes) and
    end-to-end, gated on argmax-identical greedy streams and zero
    retraces (the speedup floor applies to compiled runs only; CPU runs
    the kernels interpreted).  Emits BENCH_serve.json at the repo root; CI
    gates on the >=1.5x speedup floor, a zero-retrace ceiling after
    warmup, and bit-identity on the dense configs (MoE capacity
    coupling legitimately perturbs logits under admission regrouping,
    so mixtral's stream equality is recorded but not gated)."""
    import time as _time

    import jax
    from repro.configs import get_config, smoke_variant
    from repro.models import api as model_api
    from repro.runtime.serving import ServeConfig, ServingEngine

    archs = ("olmo-1b", "mixtral-8x7b") if fast else (
        "olmo-1b", "mixtral-8x7b", "gemma3-12b"
    )
    bit_gated = {"olmo-1b", "gemma3-12b"}
    n_req = 8 if fast else 16
    max_new = 12 if fast else 16
    records = {"n_requests": n_req, "max_new_tokens": max_new, "configs": {}}

    def mk_engine(cfg, params, host, decode_kernels=False):
        return ServingEngine(
            cfg, params,
            ServeConfig(
                max_batch=4, max_len=96, max_new_tokens=max_new,
                host_sampling=host, decode_kernels=decode_kernels,
            ),
        )

    def traffic(cfg, seed=2):
        rng = np.random.default_rng(seed)
        return [
            rng.integers(0, cfg.vocab, int(l)).astype(np.int32)
            for l in rng.integers(6, 40, n_req)
        ]

    def run_one(eng, prompts):
        eng.warmup()
        traces0 = dict(eng.trace_counts)
        n0 = len(eng.completed)       # run_until_drained returns the
        t0 = _time.perf_counter()     # engine-lifetime completed list;
        for p in prompts:             # scope this trial's tokens/streams
            eng.submit(p.copy())      # so engines can be re-trialed
        eng.run_until_drained()
        wall = _time.perf_counter() - t0
        done = eng.completed[n0:]
        toks = sum(len(r.out_tokens) for r in done)
        # key by arrival order within the trial (uids are lifetime
        # counters and would shift between trials)
        streams = {
            i: list(r.out_tokens)
            for i, r in enumerate(sorted(done, key=lambda r: r.uid))
        }
        retraces = {
            k: eng.trace_counts[k] - traces0[k] for k in traces0
        }
        return toks / wall, wall, streams, retraces

    def decode_phase_rate(
        cfg, params, host, stream_pus=None, m=0, decode_kernels=False
    ):
        """Steady-state decode rate with prefill out of the timed window:
        admit a full batch, then time the pure decode drain.  Median over
        trials (single-run walls are jittery at smoke scale).  With
        ``stream_pus`` the engine decodes through the overlapped staged
        loop (m=0 auto-tunes the lane-group depth)."""
        trials = 3 if fast else 5
        decode_new = 48 if fast else 64
        rng = np.random.default_rng(9)
        rates = []
        for _ in range(trials):
            eng = ServingEngine(
                cfg, params,
                ServeConfig(
                    max_batch=4, max_len=decode_new + 40,
                    max_new_tokens=decode_new, host_sampling=host,
                    stream_pus=stream_pus, decode_microbatches=m,
                    decode_kernels=decode_kernels,
                ),
            )
            eng.warmup()
            for _ in range(4):
                eng.submit(
                    rng.integers(0, cfg.vocab, 24).astype(np.int32)
                )
            if host:
                while eng.pending:
                    slot = next(
                        i for i, s in enumerate(eng._slots) if s is None
                    )
                    eng._admit_host(slot, eng._queue.popleft())
            else:
                eng._admit_device()
            # tokens already emitted at admission (host keeps them in
            # req.out_tokens; the device engine holds them in out_buf and
            # mirrors the count in _slot_emitted)
            pre = sum(len(r.out_tokens) for r in eng.completed)
            for i, r in enumerate(eng._slots):
                if r is not None:
                    pre += (
                        len(r.out_tokens) if host
                        else int(eng._slot_emitted[i])
                    )
            t0 = _time.perf_counter()
            done = eng.run_until_drained()
            wall = _time.perf_counter() - t0
            toks = sum(len(r.out_tokens) for r in done) - pre
            rates.append(toks / wall)
        return float(np.median(rates))

    def run():
        records["configs"].clear()
        olmo_device = None             # (params, streams, tps) for the
        for arch in archs:             # pipeline_decode comparison below
            cfg = smoke_variant(get_config(arch))
            api = model_api.get_api(cfg)
            params = api.init_params(cfg, jax.random.PRNGKey(0))
            prompts = traffic(cfg)
            host = mk_engine(cfg, params, host=True)
            host_tps, host_wall, host_streams, _ = run_one(host, prompts)
            dev = mk_engine(cfg, params, host=False)
            dev_tps, dev_wall, dev_streams, retr = run_one(dev, prompts)
            if arch == "olmo-1b":
                olmo_device = (params, dev_streams, dev_tps)
            host_dec = decode_phase_rate(cfg, params, host=True)
            dev_dec = decode_phase_rate(cfg, params, host=False)
            rec = {
                "family": cfg.family,
                "host_tokens_per_s": host_tps,
                "device_tokens_per_s": dev_tps,
                "speedup": dev_tps / host_tps,
                "host_decode_tokens_per_s": host_dec,
                "device_decode_tokens_per_s": dev_dec,
                "decode_speedup": dev_dec / host_dec,
                "host_wall_s": host_wall,
                "device_wall_s": dev_wall,
                "retraces_after_warmup": sum(retr.values()),
                "retraces_by_kind": retr,
                "greedy_bit_identical": host_streams == dev_streams,
                "bit_gated": arch in bit_gated,
                "prefill_s_by_bucket": {
                    str(b): s for b, s in dev.prefill_s_by_bucket().items()
                },
                "decode_traces_total": dev.trace_counts["decode"],
                "prefill_traces_total": dev.trace_counts["prefill"],
            }
            records["configs"][arch] = rec

        # true per-stage decode (--multi-pu): the overlapped staged loop
        # serves the same traffic as the single-PU device engine.  The
        # headline K=2 auto-tuned record is the median over paired
        # in-process trials (single-run walls are jittery at smoke
        # scale) and is CI-gated on greedy bit-identity, the virtual
        # clock reproducing the plan recurrence, zero retraces after
        # warmup, and the >=1.0x throughput floor vs the fused loop.
        # The lane-group sweep (M x K) below is informational.
        import dataclasses

        from repro.core.pu import host_offload_config, tpu_v5e_config

        def stage_pus(k):
            return [
                host_offload_config() if i % 2 == 0 else tpu_v5e_config()
                for i in range(k)
            ]

        def staged_engine(cfg, params, k, m):
            return ServingEngine(
                cfg, params,
                ServeConfig(
                    max_batch=4, max_len=96, max_new_tokens=max_new,
                    stream_pus=stage_pus(k), decode_microbatches=m,
                ),
            )

        cfg = smoke_variant(get_config("olmo-1b"))
        assert olmo_device is not None, "olmo-1b left the arch list"
        params, dev_streams, _ = olmo_device
        prompts = traffic(cfg)
        trials = 3 if fast else 5
        staged = staged_engine(cfg, params, 2, 0)
        base = mk_engine(cfg, params, host=False)
        ratios, st_rates, dev_rates, walls = [], [], [], []
        bit, retr_total = True, 0
        for _ in range(trials):
            st_tps, st_wall, st_streams, st_retr = run_one(staged, prompts)
            dev_tps, _, base_streams, _ = run_one(base, prompts)
            ratios.append(st_tps / dev_tps)
            st_rates.append(st_tps)
            dev_rates.append(dev_tps)
            walls.append(st_wall)
            bit = bit and st_streams == dev_streams == base_streams
            retr_total += sum(st_retr.values())
        st = staged.stats()

        # the gated ratio is the steady-state decode phase (prefill and
        # admission barriers out of the timed window, same methodology
        # as the per-config decode_speedup gate): this is the loop the
        # overlap optimizes, and end-to-end walls at smoke scale are
        # admission-jitter-bound (the e2e ratio stays recorded below)
        st_dec = decode_phase_rate(
            cfg, params, host=False, stream_pus=stage_pus(2)
        )
        dev_dec = decode_phase_rate(cfg, params, host=False)

        # lane-group sweep: M in {1 (serial reference), 2, 4, auto} x
        # K in {2, 3} stages; K=3 needs one model layer per stage, so
        # it runs on a 4-layer variant with its own fused baseline
        sweep = []
        for k in (2, 3):
            if k == 2:
                s_cfg, s_params = cfg, params
                s_base = float(np.median(dev_rates))
                ref_streams = dev_streams
            else:
                s_cfg = dataclasses.replace(cfg, n_layers=4)
                s_api = model_api.get_api(s_cfg)
                s_params = s_api.init_params(s_cfg, jax.random.PRNGKey(0))
                ref_eng = mk_engine(s_cfg, s_params, host=False)
                s_base, _, ref_streams, _ = run_one(
                    ref_eng, traffic(s_cfg)
                )
            for m in (1, 2, 4, 0):
                eng = staged_engine(s_cfg, s_params, k, m)
                tps, _, streams, retr = run_one(eng, traffic(s_cfg))
                es = eng.stats()
                sweep.append({
                    "k": k,
                    "m_requested": m,
                    "m": int(es["stage_decode_microbatches"]),
                    "tokens_per_s": tps,
                    "e2e_vs_single_pu": tps / s_base,
                    "bubble": float(es["stage_decode_bubble"]),
                    "clock_ok": bool(es["stage_decode_clock_ok"]),
                    "greedy_bit_identical": streams == ref_streams,
                    "retraces_after_warmup": sum(retr.values()),
                })

        records["pipeline_decode"] = {
            "arch": "olmo-1b",
            "stages": int(st["partition_stages"]),
            "stage_decode_rounds": st["stage_decode_rounds"],
            "stage_layers": [
                int(st[k]) for k in sorted(st) if k.endswith("_decode_layers")
            ],
            "clock_ok": bool(st["stage_decode_clock_ok"]),
            "greedy_bit_identical": bit,
            "microbatches": int(st["stage_decode_microbatches"]),
            "queue_depth": int(st["stage_decode_queue_depth"]),
            "coalesced": bool(st["stage_decode_coalesced"]),
            "bubble": float(st["stage_decode_bubble"]),
            "trials": trials,
            "decode_tokens_per_s": st_dec,
            "single_pu_decode_tokens_per_s": dev_dec,
            "vs_single_pu": st_dec / dev_dec,
            "tokens_per_s": float(np.median(st_rates)),
            "single_pu_tokens_per_s": float(np.median(dev_rates)),
            "e2e_vs_single_pu": float(np.median(ratios)),
            "retraces_after_warmup": retr_total,
            "wall_s": float(np.median(walls)),
            "sweep": sweep,
        }

        # fused Pallas decode kernels (--decode-kernels): per-op
        # microbenchmark (fused kernel vs the same math composed from
        # jitted XLA primitives, at the smoke configs' decode shapes)
        # plus an end-to-end engine A/B on identical traffic.  On CPU
        # the kernels run through the Pallas interpreter
        # (interpreted=true) so the timing ratios are recorded for
        # attribution but the speedup floor only gates compiled (TPU)
        # runs; argmax-identity and the zero-retrace ceiling gate
        # everywhere (benchmarks/check_regression.py).
        import functools

        import jax.numpy as jnp

        from repro.kernels import (
            decode_attention_ref,
            default_interpret,
            fused_decode_attention,
            fused_mlp,
            fused_mlp_ref,
            fused_qkv,
            fused_qkv_ref,
        )
        from repro.kernels import dispatch as kdispatch

        krec = {
            "interpreted": bool(default_interpret()),
            "per_op": {},
            "configs": {},
        }
        kop_archs = ("olmo-1b",) if fast else ("olmo-1b", "gemma3-12b")
        kb, ksk = 4, 96
        rngk = np.random.default_rng(7)

        def _tol_ok(a, b, atol=5e-2):
            return bool(
                np.allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    atol=atol,
                )
            )

        for arch in kop_archs:
            kcfg = smoke_variant(get_config(arch))
            d, f = kcfg.d_model, kcfg.d_ff
            hq, hkv, hd = kcfg.n_heads, kcfg.n_kv_heads, kcfg.head_dim
            blocks = kdispatch.kernel_blocks(kcfg, sk=ksk)
            mk = lambda *s: jnp.asarray(
                rngk.normal(size=s) * 0.05, jnp.bfloat16
            )
            x = mk(kb, d)
            qb, kbuf, vbuf = mk(kb, hq, hd), mk(kb, ksk, hkv, hd), mk(kb, ksk, hkv, hd)
            wo = mk(hq * hd, d)
            wq_, wk_, wv_ = mk(d, hq * hd), mk(d, hkv * hd), mk(d, hkv * hd)
            wu, wd_ = mk(d, f), mk(f, d)
            wg = mk(d, f) if kcfg.mlp == "swiglu" else None
            pos = jnp.asarray(rngk.integers(0, ksk, kb), jnp.int32)
            vlen = jnp.asarray(rngk.integers(1, ksk + 1, kb), jnp.int32)

            qkv_kw = dict(
                n_heads=hq, n_kv_heads=hkv, head_dim=hd, rope=True,
                theta=float(kcfg.rope_theta),
            )
            xla_qkv = jax.jit(functools.partial(fused_qkv_ref, **qkv_kw))
            ops_tbl = {
                "decode_attention": (
                    lambda: fused_decode_attention(
                        qb, kbuf, vbuf, wo, q_positions=pos,
                        kv_valid_len=vlen, block_s=blocks["block_s"],
                    ),
                    jax.jit(
                        lambda: decode_attention_ref(
                            qb, kbuf, vbuf, wo, q_positions=pos,
                            kv_valid_len=vlen,
                        )
                    ),
                ),
                "mlp": (
                    lambda: fused_mlp(
                        x, wu, wg, None, wd_, None, act=kcfg.mlp,
                        block_f=blocks["block_f"],
                    ),
                    jax.jit(
                        lambda: fused_mlp_ref(
                            x, wu, wg, None, wd_, None, act=kcfg.mlp
                        )
                    ),
                ),
                "qkv": (
                    lambda: fused_qkv(
                        x, wq_, wk_, wv_, None, None, None, pos,
                        block_m=blocks["block_m"], **qkv_kw,
                    ),
                    lambda: xla_qkv(x, wq_, wk_, wv_, None, None, None, pos),
                ),
            }
            for op, (kfn, xfn) in ops_tbl.items():
                yk, us_k = timed(
                    lambda: jax.block_until_ready(kfn()), repeats=3
                )
                yx, us_x = timed(
                    lambda: jax.block_until_ready(xfn()), repeats=3
                )
                ya = jax.tree.leaves(yk)
                yb = jax.tree.leaves(yx)
                krec["per_op"][f"{arch}/{op}"] = {
                    "kernel_us": us_k,
                    "xla_us": us_x,
                    "speedup": us_x / us_k,
                    "ok": all(_tol_ok(a, b) for a, b in zip(ya, yb)),
                }

        for arch in kop_archs:
            kcfg = smoke_variant(get_config(arch))
            kapi = model_api.get_api(kcfg)
            kparams = kapi.init_params(kcfg, jax.random.PRNGKey(0))
            kprompts = traffic(kcfg)
            xeng = mk_engine(kcfg, kparams, host=False)
            x_tps, _, x_streams, _ = run_one(xeng, kprompts)
            keng = mk_engine(kcfg, kparams, host=False, decode_kernels=True)
            k_tps, _, k_streams, kretr = run_one(keng, kprompts)
            k_dec = decode_phase_rate(
                kcfg, kparams, host=False, decode_kernels=True
            )
            x_dec = decode_phase_rate(kcfg, kparams, host=False)
            krec["configs"][arch] = {
                "kernel_tokens_per_s": k_tps,
                "xla_tokens_per_s": x_tps,
                "e2e_speedup": k_tps / x_tps,
                "kernel_decode_tokens_per_s": k_dec,
                "xla_decode_tokens_per_s": x_dec,
                "decode_speedup": k_dec / x_dec,
                "argmax_identical": k_streams == x_streams,
                "retraces_after_warmup": sum(kretr.values()),
            }
        records["decode_kernels"] = krec

        # TTFT / TPOT under a Poisson arrival trace (olmo): requests
        # arrive on the open-loop clock; the engine keeps fusing decode
        # blocks between admissions.  Both the fused device loop and the
        # K=2 overlapped staged loop serve the same trace.
        n_arr = 6 if fast else 12

        def poisson_trace(eng):
            eng.warmup()
            rng = np.random.default_rng(5)
            gaps = rng.exponential(0.08, n_arr)
            arrivals = np.cumsum(gaps)
            ps = traffic(cfg, seed=6)
            t0 = _time.perf_counter()
            i = 0
            while i < n_arr or eng.pending or eng.active:
                now = _time.perf_counter() - t0
                while i < n_arr and arrivals[i] <= now:
                    eng.submit(ps[i % len(ps)].copy())
                    i += 1
                if eng.pending or eng.active:
                    eng.step()
                elif i < n_arr:
                    _time.sleep(min(0.005, arrivals[i] - now))
            ttfts = sorted(
                r.ttft_s for r in eng.completed if r.ttft_s is not None
            )
            tpots = sorted(
                r.tpot_s for r in eng.completed if r.tpot_s is not None
            )
            return ttfts, tpots

        cfg = smoke_variant(get_config("olmo-1b"))
        api = model_api.get_api(cfg)
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        ttfts, tpots = poisson_trace(mk_engine(cfg, params, host=False))
        st_ttfts, st_tpots = poisson_trace(
            staged_engine(cfg, params, 2, 0)
        )
        records["ttft_poisson"] = {
            "arrival_rate_hz": 1.0 / 0.08,
            "requests": n_arr,
            "p50_s": float(np.percentile(ttfts, 50)),
            "p95_s": float(np.percentile(ttfts, 95)),
            "max_s": float(ttfts[-1]),
            "tpot_p50_s": float(np.percentile(tpots, 50)),
            "tpot_p95_s": float(np.percentile(tpots, 95)),
            "staged": {
                "ttft_p50_s": float(np.percentile(st_ttfts, 50)),
                "ttft_p95_s": float(np.percentile(st_ttfts, 95)),
                "tpot_p50_s": float(np.percentile(st_tpots, 50)),
                "tpot_p95_s": float(np.percentile(st_tpots, 95)),
            },
        }
        return records

    t0 = time.perf_counter()
    run()
    us = (time.perf_counter() - t0) * 1e6
    parts = []
    for arch, rec in records["configs"].items():
        parts.append(
            f"{arch}:x{rec['speedup']:.1f}/dec x{rec['decode_speedup']:.2f}"
            f"(retr={rec['retraces_after_warmup']}"
            f",bit={int(rec['greedy_bit_identical'])})"
        )
    tt = records["ttft_poisson"]
    pd = records["pipeline_decode"]
    derived = (
        ";".join(parts)
        + f";ttft_p50={tt['p50_s']:.3f}s;ttft_p95={tt['p95_s']:.3f}s"
        + f";tpot_p50={tt['tpot_p50_s']:.4f}s"
        + f";staged_k2:x{pd['vs_single_pu']:.2f}"
        f"(m={pd['microbatches']},bub={pd['bubble']:.2f})"
    )
    dk = records["decode_kernels"]
    dko = dk["configs"]["olmo-1b"]
    derived += (
        f";dk:x{dko['decode_speedup']:.2f}"
        f"(bit={int(dko['argmax_identical'])}"
        f",retr={dko['retraces_after_warmup']}"
        f",interp={int(dk['interpreted'])})"
    )
    emit("serve", us, derived, records)
    (ROOT / "BENCH_serve.json").write_text(json.dumps(records, indent=1))


# -------------------------------------------------------- end-to-end ------


def bench_train_smoke():
    from repro.configs import get_config, smoke_variant
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import single_device_mesh
    from repro.optim import AdamWConfig
    from repro.parallel.sharding import RULES_FSDP_TP
    from repro.runtime.train_loop import TrainLoop, TrainLoopConfig
    import tempfile

    cfg = smoke_variant(get_config("olmo-1b"))
    shape = ShapeConfig("bench", seq_len=128, global_batch=8, kind="train")
    with tempfile.TemporaryDirectory() as d:
        loop = TrainLoop(
            cfg, shape, single_device_mesh(), RULES_FSDP_TP,
            TrainLoopConfig(steps=8, ckpt_every=100, ckpt_dir=d, log_every=0),
            opt_cfg=AdamWConfig(lr=1e-3),
        )
        t0 = time.perf_counter()
        out = loop.run()
        dt = time.perf_counter() - t0
    steps_done = len(loop.records)
    wall = [r.wall_s for r in loop.records[2:]]
    us = float(np.mean(wall)) * 1e6 if wall else dt / max(steps_done, 1) * 1e6
    tokens_s = shape.seq_len * shape.global_batch / (us / 1e6)
    emit(
        "train_smoke",
        us,
        f"steps={steps_done};tokens_per_s={tokens_s:.0f};final_loss={out['final_loss']:.3f}",
    )


def bench_serve_smoke():
    import jax
    from repro.configs import get_config, smoke_variant
    from repro.models import api as model_api
    from repro.runtime.serving import ServeConfig, ServingEngine

    cfg = smoke_variant(get_config("olmo-1b"))
    api = model_api.get_api(cfg)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(
        cfg, params, ServeConfig(max_batch=4, max_len=96, max_new_tokens=16)
    )
    rng = np.random.default_rng(0)
    for _ in range(8):
        eng.submit(rng.integers(0, cfg.vocab, 16).astype(np.int32))
    t0 = time.perf_counter()
    eng.run_until_drained()
    dt = time.perf_counter() - t0
    s = eng.stats()
    emit(
        "serve_smoke",
        dt / max(s["rounds"], 1) * 1e6,
        f"tokens={s['tokens']:.0f};tokens_per_s={s['tokens']/dt:.1f};"
        f"mean_ttft_s={s['mean_ttft_s']:.2f}",
    )


# ----------------------------------------------------------- roofline -----


def bench_roofline_summary():
    dr = ROOT / "experiments" / "dryrun"
    rows = []
    if dr.exists():
        for f in sorted(dr.glob("*.json")):
            rec = json.loads(f.read_text())
            if rec.get("status") != "ok":
                continue
            r = rec["roofline"]
            rows.append(
                {
                    "cell": f"{rec['arch']}/{rec['shape']}/{rec['mesh']}"
                    + (f"/{rec['rules']}" if rec.get("rules", "fsdp_tp") != "fsdp_tp" else ""),
                    "dominant": r["dominant"],
                    "bound_ms": r["bound_s"] * 1e3,
                    "fraction": r["roofline_fraction"],
                }
            )
    if not rows:
        emit("roofline_summary", 0.0, "no dryrun artifacts yet")
        return
    worst = min(rows, key=lambda r: r["fraction"])
    best = max(rows, key=lambda r: r["fraction"])
    emit(
        "roofline_summary",
        0.0,
        f"cells={len(rows)};best={best['cell']}@{best['fraction']:.2f};"
        f"worst={worst['cell']}@{worst['fraction']:.2f}",
        {"rows": rows},
    )


BENCHES = {
    "table1_resnet18": lambda fast: bench_table1(18),
    "table1_resnet50": lambda fast: bench_table1(50),
    "fig5a_layer_latency": lambda fast: bench_fig5a(),
    "fig5bc_scheduler_ratios": lambda fast: bench_fig5bc(),
    "efficiency_98pct": lambda fast: bench_efficiency(),
    "wrb_out_of_order": lambda fast: bench_wrb(),
    "aimc_noise_snr": lambda fast: bench_aimc(),
    "kernel_int8_gemm": bench_kernel_gemm,
    "kernel_im2col": bench_kernel_im2col,
    "scheduler_capacity_sweep": lambda fast: bench_scheduler_sweep(),
    "streaming_plan_lm": lambda fast: bench_streaming_lm(),
    "plan": bench_plan_suite,
    "stream": bench_stream_suite,
    "serve": bench_serve_suite,
    "train_smoke": lambda fast: bench_train_smoke(),
    "serve_smoke": lambda fast: bench_serve_smoke(),
    "roofline_summary": lambda fast: bench_roofline_summary(),
}


def main(argv=None) -> int:
    """Run the selected benches; every bench runs even after one fails,
    and the exit code is 1 when any of them raised."""
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    print("name,us_per_call,derived")
    failed = []
    for name, fn in BENCHES.items():
        if args.only and args.only != name:
            continue
        try:
            fn(args.fast)
        except Exception as e:  # record, run the rest, fail at the end
            emit(name, -1.0, f"ERROR:{type(e).__name__}:{e}")
            failed.append(name)
    if failed:
        print(f"FAILED: {','.join(failed)}")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
