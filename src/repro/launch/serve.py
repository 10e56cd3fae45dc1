"""Serving launcher: batched requests against any assigned architecture.

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m --smoke \
        --requests 16 --max-new 24 [--stream] [--multi-pu K] [--aimc]

The decode loop is device-resident by default (fused sample-append
blocks, bucketed batched prefill -- DESIGN.md SS7); ``--host-sampling``
falls back to the legacy host loop (per-token decode jit + numpy
sampling), ``--prefill-buckets 16,32,...`` overrides the power-of-two
prompt-length ladder, and ``--decode-block`` caps the fused rounds per
host sync.  ``--stream`` plans host->HBM weight streaming with the
paper's two-phase scheduler and prints the plan summary (stall
reduction, utilization);
``--multi-pu K`` partitions the model's GEMM sequence across K PU
profiles (repro.plan.partition) and, after the decode loop drains,
*executes* the partition through the stage-parallel streaming runtime
(runtime.pipeline_exec) -- the printed stats carry both the analytic
pipeline numbers and the measured (executed) throughput and bubble.
The executed microbatch depth (and handoff queue depth) is auto-tuned
against ``--target-bubble`` from the measured bubble by default; pass
an explicit ``--microbatches M`` to pin it.  ``--plan-search
beam|anneal`` upgrades the streaming/partition planners' adaptive
phase to schedule search (deterministic via ``--plan-search-seed``).
``--decode-kernels`` swaps the per-token hot ops for the fused Pallas
decode kernels (kernels/decode.py) while keeping the composed-XLA loop
as the A/B reference.  ``--aimc`` enables the SS VI noise-injection
emulation, refreshing weights with fresh PCM-style noise every round.
"""
from __future__ import annotations

import argparse
import functools
import json

import numpy as np

import jax

from repro.analysis import sanitize
from repro.configs import ARCH_IDS, get_config, smoke_variant
from repro.core.aimc import AIMCNoiseModel
from repro.core.pu import host_offload_config, tpu_v5e_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api as model_api
from repro.plan import SearchConfig
from repro.runtime.serving import ServeConfig, ServingEngine


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--host-sampling", action="store_true",
                    help="legacy host-loop rounds (per-token decode jit, "
                         "numpy sampling, lane-isolated prefill) instead "
                         "of the device-resident decode loop")
    ap.add_argument("--prefill-buckets", default=None, metavar="N,N,...",
                    help="comma-separated prompt-length buckets for "
                         "batched prefill (default: power-of-two ladder "
                         "16,32,... capped at max_len)")
    ap.add_argument("--decode-block", type=int, default=32, metavar="R",
                    help="max fused decode rounds per host sync "
                         "(power-of-two blocks up to R; default 32)")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip pre-compiling the prefill-bucket/decode-"
                         "block trace grid at startup")
    ap.add_argument("--stream", action="store_true",
                    help="plan weight streaming (two-phase scheduler)")
    ap.add_argument("--multi-pu", type=int, default=0, metavar="K",
                    help="partition the model across K PU profiles "
                         "(alternating host-offload / v5e) and run true "
                         "per-stage decode: every serving round streams "
                         "each stage's model-layer slice through the "
                         "stage pipeline; K=1 falls back to the "
                         "single-PU streaming path")
    ap.add_argument("--no-stage-decode", action="store_true",
                    help="with --multi-pu, keep the fused single-PU "
                         "decode loop and only attach the partition "
                         "analytically (parity-debugging escape hatch)")
    ap.add_argument("--microbatches", type=int, default=0, metavar="M",
                    help="lane-group / microbatch depth M with "
                         "--multi-pu: splits the decode slot batch into "
                         "M lane groups for the overlapped staged loop "
                         "and sets the executed tile pipeline's depth; "
                         "1 = serial reference, 0 (default) auto-tunes "
                         "M and the handoff queue depth against "
                         "--target-bubble using the executed bubble "
                         "measurement")
    ap.add_argument("--target-bubble", type=float, default=0.10,
                    help="target fill/drain bubble fraction for the "
                         "microbatch auto-tuner (default 0.10)")
    ap.add_argument("--plan-search", default="heuristic",
                    choices=["heuristic", "beam", "anneal"],
                    help="schedule-search strategy for the streaming/"
                         "partition planners (beam/anneal spend the "
                         "vectorized planner's budget on stall search)")
    ap.add_argument("--plan-search-seed", type=int, default=0,
                    help="deterministic seed for --plan-search anneal")
    ap.add_argument("--decode-kernels", action="store_true",
                    help="fused Pallas decode kernels (QKV+RoPE, GQA "
                         "attention + out-projection, gated MLP) on the "
                         "per-token hot path; default keeps the "
                         "composed-XLA decode as the A/B reference")
    ap.add_argument("--aimc", action="store_true",
                    help="AIMC noise emulation (SS VI NIU)")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def run(argv=None) -> ServingEngine:
    """Build the engine for ``argv``, warm it, serve the seeded traffic
    until drained, and return the drained engine."""
    args = build_parser().parse_args(argv)
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)

    api = model_api.get_api(cfg)
    params = jax.jit(functools.partial(api.init_params, cfg))(
        jax.random.PRNGKey(args.seed)
    )

    serve_cfg = ServeConfig(
        max_batch=args.max_batch,
        max_len=args.prompt_len + args.max_new + 8,
        max_new_tokens=args.max_new,
        temperature=args.temperature,
        seed=args.seed,
        host_sampling=args.host_sampling,
        prefill_buckets=(
            tuple(int(b) for b in args.prefill_buckets.split(","))
            if args.prefill_buckets
            else None
        ),
        max_decode_block=args.decode_block,
        stream_pu=host_offload_config() if args.stream else None,
        stream_pus=(
            [
                host_offload_config() if k % 2 == 0 else tpu_v5e_config()
                for k in range(args.multi_pu)
            ]
            if args.multi_pu
            else None
        ),
        stage_decode=not args.no_stage_decode,
        decode_microbatches=args.microbatches,
        decode_kernels=args.decode_kernels,
        aimc=AIMCNoiseModel() if args.aimc else None,
        plan_search=(
            SearchConfig(
                strategy=args.plan_search, seed=args.plan_search_seed
            )
            if args.plan_search != "heuristic"
            else None
        ),
        target_bubble=args.target_bubble,
    )
    engine = ServingEngine(cfg, params, serve_cfg)
    if not args.no_warmup:
        engine.warmup()

    rng = np.random.default_rng(args.seed)
    for _ in range(args.requests):
        prompt = rng.integers(0, cfg.vocab, size=args.prompt_len).astype(np.int32)
        engine.submit(prompt)

    engine.run_until_drained()
    if engine.partitioned_plan is not None:
        # --multi-pu decode executes the partition for real: the
        # stage-parallel runtime streams every stage's tiles in plan
        # issue order and measures pipeline throughput + fill bubble.
        # M=0 auto-tunes depth (and handoff queue depth) against the
        # requested bubble target from the executed measurement.
        engine.execute_partition(
            n_microbatches=args.microbatches or None
        )
    return engine


def main(argv=None) -> int:
    enable_compile_cache()
    engine = run(argv)
    print(json.dumps(engine.stats(), indent=1, default=float))
    if sanitize.enabled():
        violations = sanitize.lock_violations()
        for v in violations:
            print(f"sanitize: {v.kind} violation {v.first}->{v.second or '?'} at {v.site}")
        if violations:
            return 2
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
