"""Smoke run of the main paths on TPU v5e, at olmo-1b's full width.

    python3 chip_smoke.py [--seed N]     # one chip: the serving path
    python3 chip_smoke.py --chips 4      # four chips: the sharded trainer

One chip, four phases, in order:

1. device -- the backend is a TPU (no CPU fallback), Pallas kernels are
   compiled (not interpreted) and ``REPRO_DECODE_KERNELS`` leaves the
   decode kernels on;
2. serve, XLA decode -- ``repro.launch.serve`` in this process with
   warmup and the runtime sanitizers armed: 8 requests of 128 prompt
   tokens and 32 new tokens on 8 decode slots; zero retraces after
   warmup, every request complete;
3. serve, ``--decode-kernels`` -- the same traffic and seed; the compiled
   decode block must hold Mosaic kernels (``tpu_custom_call``) and every
   first token must equal phase 2's;
4. teacher-forced comparison -- the same params and prompts through the
   model API: greedy XLA decode with its tokens fed to the kernel decode
   at each of the 31 steps, the last lane admitted ``LATE_STEP`` steps
   late; the forced run's first tokens must equal the served ones, and
   at every step the kernel logits must agree with XLA's within
   ``LOGIT_TOL`` of their largest magnitude.

``--chips 4`` runs only the sharded path: ``TrainLoop`` on a
(data=2, model=2) mesh, seq 2048, global batch 8, 3 steps, into a fresh
checkpoint directory that is removed afterwards; its step-0 loss must
match a one-chip forward of the same seeded parameters on the same
batch within ``LOSS_TOL``, the parameters must be spread over all four
chips, and the final state must be checkpointed at step 3.

Every time printed is smoke wall time (one run, compilation included
where named), not a benchmark measurement.  The last stdout line is
``{"ok": true, "device": {...}}`` and is printed only when every phase
passed; any failure exits non-zero.  One process holds the chip(s)
throughout; it starts no other.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent

# max|kernel - xla| / max|xla| of one decode step's logits, at every
# step of the teacher-forced run.  The paths round to bf16 (2^-8
# relative) at different points in every layer (streaming softmax, f32
# activation), and a one-ulp flip in the hidden state moves a 2048-wide
# logit dot by about u*sqrt(d).  At olmo-1b's width with 2, 4 and 8
# layers the interpreter measured 0.012, 0.017 and 0.020 on the first
# step; 16 layers on the chip gave 0.030.  A wrong mask, lane scalar or
# head mapping is O(1).
LOGIT_TOL = 0.05
# decode step at which the last lane of the forced run is admitted
LATE_STEP = 8
# sharded vs one-chip step-0 loss: the bf16 reduction-order tolerance of
# tests/test_multidevice.py::test_sharded_equals_single_device
LOSS_TOL = 0.03

ARCH = "olmo-1b"
SERVE_ARGS = [
    "--arch", ARCH, "--requests", "8", "--max-batch", "8",
    "--prompt-len", "128", "--max-new", "32", "--prefill-buckets", "128",
]


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + json.dumps(fields, default=float), flush=True)


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


# ------------------------------------------------------------------ device --


def phase_device(want_chips: int):
    import jax

    from repro.configs import get_config
    from repro.kernels import dispatch
    from repro.kernels.common import default_interpret
    from repro.launch.compile_cache import enable_compile_cache

    devs = jax.devices()
    d0 = devs[0]
    say(
        "device", jax=jax.__version__, platform=d0.platform,
        kind=d0.device_kind, count=len(devs),
    )
    check(d0.platform == "tpu", f"no TPU: jax found {d0.platform!r}")
    check(
        len(devs) >= want_chips,
        f"{want_chips} chips requested, {len(devs)} found",
    )
    check(not default_interpret(), "Pallas kernels would run interpreted")
    check(
        dispatch.enabled(dataclasses.replace(get_config(ARCH), decode_kernels=True)),
        "REPRO_DECODE_KERNELS disables the decode kernels",
    )
    say("device", compile_cache=enable_compile_cache())
    return devs


# ------------------------------------------------------------------- serve --


def teacher_forced(cfg, params, prompts, max_len: int, n_steps: int, late_step: int):
    """Greedy decode on the XLA path, its token fed to the kernel path at
    every step, both from the same prefilled cache, through the model API
    the engine serves with.  The last lane is admitted ``late_step`` steps
    after the others, so the lanes hold different lengths.

    Returns the XLA path's greedy tokens per lane, both paths' logits
    ``(n_steps, B, V)`` and the ``(n_steps, B)`` mask of admitted lanes.
    """
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import api as model_api
    from repro.runtime.serving import scatter_cache_lanes

    api = model_api.get_api(cfg)
    b, s = prompts.shape
    late = b - 1
    # slot b is out of bounds: scatter_cache_lanes drops that row
    early_slots = jnp.arange(b).at[late].set(b)
    late_slots = jnp.full((b,), b).at[late].set(late)

    @jax.jit
    def prefill(params, prompts):
        logits, one = api.prefill(
            cfg, params, {"tokens": prompts, "lengths": jnp.full((b,), s, jnp.int32)}
        )
        cache = scatter_cache_lanes(api.init_cache(cfg, b, max_len), one, early_slots)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache, one

    admit = jax.jit(lambda cache, one: scatter_cache_lanes(cache, one, late_slots))

    def stepper(c):
        @functools.partial(jax.jit, donate_argnums=(1,))
        def step(params, cache, tok, pos):
            logits, cache = api.decode_step(c, params, cache, tok[:, None], pos)
            return logits.astype(jnp.float32), cache

        return step

    step_x = stepper(cfg)
    step_k = stepper(dataclasses.replace(cfg, decode_kernels=True))

    tok0, cache_x, one = prefill(params, jnp.asarray(prompts))
    cache_k = jax.tree.map(jnp.copy, cache_x)
    tok0 = np.asarray(tok0)
    tokens = [[int(t)] for t in tok0]
    live = np.ones(b, bool)
    live[late] = False
    tok = np.where(live, tok0, 0).astype(np.int32)
    pos = np.where(live, s, 0).astype(np.int32)
    lx_all, lk_all, live_all = [], [], []
    for t in range(n_steps):
        if t == late_step:
            cache_x, cache_k = admit(cache_x, one), admit(cache_k, one)
            live[late] = True
            tok[late], pos[late] = tok0[late], s
        lx, cache_x = step_x(params, cache_x, jnp.asarray(tok), jnp.asarray(pos))
        lk, cache_k = step_k(params, cache_k, jnp.asarray(tok), jnp.asarray(pos))
        lx, lk = np.asarray(lx), np.asarray(lk)
        lx_all.append(lx)
        lk_all.append(lk)
        live_all.append(live.copy())
        nxt = lx.argmax(-1).astype(np.int32)
        for i in np.flatnonzero(live):
            tokens[i].append(int(nxt[i]))
        tok = np.where(live, nxt, 0).astype(np.int32)
        pos = np.where(live, pos + 1, 0).astype(np.int32)
    return tokens, np.stack(lx_all), np.stack(lk_all), np.stack(live_all)


def compare_forced(lx, lk, live):
    """Per-step logit difference of the kernel path against XLA (relative
    to the step's largest XLA logit, admitted lanes only), and per lane
    the first step whose argmax differs, with XLA's top-2 margin there."""
    import numpy as np

    rel = []
    for t in range(lx.shape[0]):
        x, k = lx[t][live[t]], lk[t][live[t]]
        rel.append(float(np.max(np.abs(k - x)) / np.max(np.abs(x))))
    agree = lx.argmax(-1) == lk.argmax(-1)
    first_div = {}
    for i in range(lx.shape[1]):
        bad = np.flatnonzero(live[:, i] & ~agree[:, i])
        if bad.size:
            t = int(bad[0])
            top2 = np.sort(lx[t, i])[-2:]
            first_div[i] = {
                "step": t,
                "xla_top2_margin": float(top2[1] - top2[0]),
                "lane_max_abs_diff": float(np.max(np.abs(lk[t, i] - lx[t, i]))),
            }
    return rel, float(agree[live].mean()), first_div


def phase_serve(name: str, argv, dev, kernels: bool):
    """One launcher run; returns what the comparison needs, so the
    engine (cache, compiled programs) is freed on return."""
    import numpy as np

    from repro.analysis import sanitize
    from repro.launch import serve

    t0 = time.perf_counter()
    engine = serve.run(argv)
    wall = time.perf_counter() - t0
    stats = engine.stats()
    say(name, stats=stats)
    say(
        name, smoke_wall_time_s=wall, warmup_compile_s=stats["warmup_s"],
        retraces_after_warmup=stats["retraces_after_warmup"],
        peak_bytes_in_use=peak_bytes(dev),
    )
    want = engine.serve_cfg.max_new_tokens
    reqs = sorted(engine.completed, key=lambda r: r.uid)
    check(len(reqs) == 8, f"{name}: {len(reqs)} of 8 requests completed")
    check(
        all(len(r.out_tokens) == want for r in reqs),
        f"{name}: a request stopped short of {want} tokens",
    )
    check(
        stats["retraces_after_warmup"] == 0,
        f"{name}: {stats['retraces_after_warmup']} retraces after warmup",
    )
    violations = sanitize.lock_violations()
    check(not violations, f"{name}: sanitizer violations {violations}")
    if kernels:
        hlo = engine.compiled_decode_block(1).as_text()
        check(
            "tpu_custom_call" in hlo,
            f"{name}: no tpu_custom_call in the compiled decode block",
        )
        say(name, tpu_custom_calls=hlo.count("tpu_custom_call"))
    out = np.asarray([r.out_tokens for r in reqs], np.int32)
    prompts = np.stack([r.prompt for r in reqs])
    cfg, params, max_len = engine.cfg, engine.params, engine.serve_cfg.max_len
    del engine, reqs
    gc.collect()
    return out, prompts, cfg, params, max_len


def run_one_chip(seed: int):
    # sanitizers armed for both serve phases
    os.environ["REPRO_SANITIZE"] = "1"
    devs = phase_device(1)
    import numpy as np

    argv = SERVE_ARGS + ["--seed", str(seed)]
    # keep only the tokens: phase 3 rebuilds the same seeded params
    xla_tok, prompts = phase_serve("serve_xla", argv, devs[0], kernels=False)[:2]
    k_tok, _, cfg, params, max_len = phase_serve(
        "serve_kernels", argv + ["--decode-kernels"], devs[0], kernels=True
    )
    say(
        "compare_served",
        first_tokens_identical=bool((k_tok[:, 0] == xla_tok[:, 0]).all()),
        greedy_token_agreement=float(np.mean(k_tok == xla_tok)),
    )
    check(
        (k_tok[:, 0] == xla_tok[:, 0]).all(),
        "first tokens differ between the kernel and XLA phases",
    )

    # the same seeded params and prompts, XLA's tokens forced through both
    n_steps = xla_tok.shape[1] - 1
    t0 = time.perf_counter()
    forced, lx, lk, live = teacher_forced(
        dataclasses.replace(cfg, decode_kernels=False), params, prompts,
        max_len, n_steps, LATE_STEP,
    )
    del params
    gc.collect()
    rel, argmax_agree, first_div = compare_forced(lx, lk, live)
    late = len(forced) - 1
    served = [list(r) for r in xla_tok]
    served[late] = served[late][: len(forced[late])]
    say(
        "compare_forced", smoke_wall_time_s=time.perf_counter() - t0,
        peak_bytes_in_use=peak_bytes(devs[0]),
        first_step_rel_diff=rel[0], max_rel_diff=max(rel),
        worst_step=int(np.argmax(rel)), logit_tol=LOGIT_TOL,
        rel_diff_per_step=rel, argmax_agreement=argmax_agree,
        first_divergence=first_div, late_lane=late, late_step=LATE_STEP,
        xla_vs_served_token_agreement=float(
            np.mean(np.concatenate(forced) == np.concatenate(served))
        ),
    )
    check(
        [t[0] for t in forced] == list(xla_tok[:, 0]),
        "the forced run's first tokens differ from the served ones",
    )
    check(
        max(rel) <= LOGIT_TOL,
        f"kernel logits differ from XLA by {max(rel)} > {LOGIT_TOL} "
        f"at step {int(np.argmax(rel))}",
    )


# ----------------------------------------------------------------- 4 chips --


def run_four_chips(seed: int):
    devs = phase_device(4)
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.models import api as model_api

    devs = devs[:4]
    cfg = get_config(ARCH)
    shape = ShapeConfig("chip_smoke", seq_len=2048, global_batch=8, kind="train")
    api = model_api.get_api(cfg)
    ckpt = REPO / "experiments" / "chip_smoke_ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)   # nothing may resume
    try:
        _train_four_chips(cfg, shape, api, devs, ckpt, seed)
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)


def _train_four_chips(cfg, shape, api, devs, ckpt: Path, seed: int):
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.launch.mesh import make_mesh
    from repro.models import api as model_api
    from repro.parallel.sharding import RULES_FSDP_TP
    from repro.runtime.train_loop import TrainLoop, TrainLoopConfig

    t0 = time.perf_counter()
    loop = TrainLoop(
        cfg, shape, make_mesh((2, 2), ("data", "model")), RULES_FSDP_TP,
        # the interval exceeds the run: only the final state is saved
        TrainLoopConfig(
            steps=3, ckpt_every=4, ckpt_dir=str(ckpt), log_every=1, seed=seed
        ),
    )

    # the reference: a one-chip forward of the same seeded params on the
    # loop's step-0 batch, freed before the sharded run starts
    one = SingleDeviceSharding(devs[0])
    struct = model_api.batch_struct(cfg, shape)
    batch_np = loop.dataset.batch(0)
    batch = {
        k: jax.device_put(jnp.asarray(batch_np[k]).astype(struct[k].dtype), one)
        for k in struct
    }
    params = jax.jit(
        functools.partial(api.init_params, cfg), out_shardings=one
    )(jax.random.PRNGKey(seed))
    ref_loss = float(
        jax.jit(functools.partial(api.train_loss, cfg))(params, batch)
    )
    del params, batch
    gc.collect()
    say("train_ref", one_chip_loss=ref_loss, peak_bytes_in_use=peak_bytes(devs[0]))

    out = loop.run()
    wall = time.perf_counter() - t0
    losses = [r.loss for r in loop.records]
    per_dev = {d.id: 0 for d in devs}
    for leaf in jax.tree.leaves(out["params"]):
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] += sh.data.nbytes
    say(
        "train_sharded", smoke_wall_time_s=wall, losses=losses,
        step_wall_s=[r.wall_s for r in loop.records],
        param_bytes_per_device=per_dev,
        peak_bytes_in_use={d.id: peak_bytes(d) for d in devs},
    )
    check(len(losses) == 3, f"{len(losses)} of 3 steps ran")
    saved = loop.ckpt.latest_step()
    say(
        "train_sharded", checkpoint_step=saved,
        checkpoint_bytes=sum(f.stat().st_size for f in ckpt.rglob("*") if f.is_file()),
    )
    check(saved == 3, f"final checkpoint at step {saved}, not 3")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(
        abs(losses[0] - ref_loss) < LOSS_TOL,
        f"step-0 loss {losses[0]} vs one-chip {ref_loss}",
    )
    full = cfg.param_count() * 4
    check(
        min(per_dev.values()) > 0 and max(per_dev.values()) < full,
        f"params not spread over 4 chips: {per_dev} (full copy {full} B)",
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (REPO / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {REPO / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(REPO / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # read nothing from a persisted streaming-plan cache (none is planned)
    os.environ["REPRO_PLAN_CACHE_DIR"] = "0"

    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips(args.seed)
        else:
            run_one_chip(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    say("done", smoke_wall_time_s=time.perf_counter() - t0)

    import jax

    d0 = jax.devices()[0]
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": d0.platform, "kind": d0.device_kind,
            "count": len(jax.devices()),
        },
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
