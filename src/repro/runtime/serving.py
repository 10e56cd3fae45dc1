"""Batched serving engine with the paper's adaptive weight streaming.

The paper's scenario: model weights live off-chip (HBM@FPGA); a scheduler
streams weight tiles into fast memory (URAM) while inference runs, hiding
load latency behind compute (SS III).  The TPU serving analogue implemented
here has two streaming levels:

- **HBM -> VMEM** (per-layer weight residency inside a step) is Pallas's
  block pipeline -- the int8 GEMM kernel already double-buffers tiles.
- **host -> HBM** (whole-model residency across steps) is where the paper's
  scheduler runs at serving scale: when a model's weights exceed device
  HBM, layer-group tiles are prefetched from host memory under the
  two-phase schedule; `ServingEngine` plans this with the same
  `core.scheduler` used for the FPGA reproduction (PUConfig =
  `host_offload_config()`).

The engine also carries the paper's SS VI AIMC emulation: an optional
NoiseInjectionUnit refreshes weights with fresh device-noise instances
every inference round, exactly the NIU read-modify-write loop.

Request flow (continuous batching, decode-centric):

    submit(prompt tokens) -> queue
    engine round: admit waiting requests into free slots (bucketed batched
                  prefill, one call per length bucket), then run a fused
                  block of decode rounds entirely on device.

The decode hot path is **device-resident** (DESIGN.md SS7): sampling,
append, per-slot position/remaining bookkeeping and termination flags all
live inside one jitted ``lax.scan`` block; between host syncs the engine
only moves a handful of scalars per slot.  ``ServeConfig.host_sampling``
keeps the legacy host-loop round (one decode jit per token, numpy
sampling) as an escape hatch and as the reference for the greedy
bit-identity property tests.

With ``stream_pus`` (K >= 2) the engine runs **true per-stage decode**
(DESIGN.md SS8): each round's hidden state flows through the stage
pipeline, every stage executing its model-layer slice against its own
KV-cache slice (``runtime.stage_decode``), with greedy streams
bit-identical to the fused single-PU loop.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.analysis import sanitize
from repro.configs.base import ModelConfig
from repro.core.aimc import AIMCNoiseModel, NoiseInjectionUnit
from repro.core.pu import PUConfig, host_offload_config
from repro.core.streaming import StreamingPlan, WeightTile, plan_streaming
from repro.kernels.common import require_compiled_kernels
from repro.models import api as model_api
from repro.plan import (
    PartitionedPlan,
    SearchConfig,
    partition_gemms,
    snap_boundaries_nonempty,
)


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8             # decode slots
    max_len: int = 512             # KV capacity per slot
    max_new_tokens: int = 32
    eos_token: int = -1            # -1: never stop on a token
    temperature: float = 0.0       # 0 => greedy
    seed: int = 0
    # --- device-resident round knobs ---------------------------------------
    # escape hatch: legacy host-loop round (per-token decode jit, numpy
    # sampling, lane-isolated eager prefill) -- the pre-device-resident
    # engine, kept for A/B benchmarking and bit-identity tests
    host_sampling: bool = False
    # prompt length buckets for batched prefill; None -> power-of-two
    # ladder 16, 32, ... capped at max_len.  Prompts are right-padded to
    # the smallest bucket >= their length so warm traffic reuses a
    # handful of compiled traces.
    prefill_buckets: Optional[Sequence[int]] = None
    # max fused decode rounds per host sync (block sizes are the powers
    # of two <= this, so traces stay bounded); 1 degenerates to one
    # round per sync
    max_decode_block: int = 32
    pad_token: int = 0             # token fed to inactive/padded lanes
    # weight streaming (host->HBM level); None disables planning
    stream_pu: Optional[PUConfig] = None
    # multi-PU partitioned streaming: the model's layer sequence is split
    # across these profiles (contiguous ranges balanced on exec time, one
    # two-phase schedule per PU -- repro.plan.partition); overrides the
    # single-PU plan when set
    stream_pus: Optional[List[PUConfig]] = None
    # schedule-search strategy for the streaming/partition planners
    # (None/heuristic = the paper's one-shot heuristic; beam/anneal run
    # the richer search funded by the event-indexed engine)
    plan_search: Optional[SearchConfig] = None
    # multi-PU decode rounds run each stage's *model layer slice* through
    # the stage pipeline (true per-stage decode: real activations in the
    # handoff queues, per-stage KV cache slices); False falls back to the
    # fused single-PU decode loop with the partition kept analytic-only
    stage_decode: bool = True
    # lane-group microbatches for the *overlapped* staged decode
    # schedule: each decode round is split into M groups along the slot
    # batch so stages and rounds overlap (runtime.stage_decode).
    # 0 auto-tunes M (and the handoff queue depth) against the executed
    # bubble at engine construction (runtime.autotune.tune_staged_decode);
    # 1 pins the serial reference schedule (the A/B bit-identity path);
    # >1 pins M, clamped to the largest divisor of max_batch <= the
    # request (lane groups must tile the slot batch)
    decode_microbatches: int = 0
    # handoff queue depth for the staged-decode pipeline when M is
    # pinned (auto-tune picks its own depth)
    stage_queue_depth: int = 2
    # target fill/drain bubble fraction for the auto-tuned microbatch
    # depth when execute_partition() is called without an explicit M
    target_bubble: float = 0.10
    # AIMC emulation
    aimc: Optional[AIMCNoiseModel] = None
    aimc_refresh_every: int = 1    # refresh noise every N engine rounds
    # Fused Pallas decode kernels (kernels/decode.py) on the per-token hot
    # path.  Threaded into ModelConfig.decode_kernels at engine
    # construction so the fused _decode_block scan, the per-stage loops,
    # and the coalesced staged path all pick them up through the model
    # forward; the stock-XLA path (False) stays the A/B reference.
    decode_kernels: bool = False


@dataclasses.dataclass(frozen=True)
class Admission:
    """One batched prefill call of the device path: the requests of one
    length bucket admitted together (the args of its ``serve.admit``
    span).  Each request it admitted keeps it as ``Request.admission``."""
    bucket: int        # prompt positions computed per row
    n: int             # requests admitted
    n_pad: int         # rows computed: n padded to a power of two
    tokens: int        # real prompt tokens

    @property
    def computed(self) -> int:
        """Prompt positions the prefill program computed, padding included."""
        return self.bucket * self.n_pad


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                 # (S,) int32
    max_new_tokens: int
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    submitted_at: float = 0.0
    first_token_at: Optional[float] = None
    done_at: Optional[float] = None
    admission: Optional[Admission] = None

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at

    @property
    def tpot_s(self) -> Optional[float]:
        """Time per output token after the first (decode steady state)."""
        if self.first_token_at is None or self.done_at is None:
            return None
        if len(self.out_tokens) < 2:
            return None
        return (self.done_at - self.first_token_at) / (
            len(self.out_tokens) - 1
        )


def _pow2_ceil(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def default_prefill_buckets(max_len: int) -> Tuple[int, ...]:
    """Power-of-two ladder 16, 32, ... capped at ``max_len``."""
    out, b = [], 16
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(sorted(set(out)))


class ServingEngine:
    """Continuous-batching LM server over the uniform model API."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        serve_cfg: ServeConfig,
        mesh=None,
        rules=None,
    ):
        require_compiled_kernels()
        if serve_cfg.decode_kernels and not cfg.decode_kernels:
            cfg = dataclasses.replace(cfg, decode_kernels=True)
        self.cfg = cfg
        self.api = model_api.get_api(cfg)
        self.serve_cfg = serve_cfg
        self.mesh = mesh
        self.rules = rules
        self._pristine = params
        self.params = params
        self._rng = np.random.default_rng(serve_cfg.seed)
        self._key = jax.random.PRNGKey(serve_cfg.seed)

        # request/slot state (host bookkeeping)
        self._queue: deque[Request] = deque()
        self._uid = 0
        self._slots: List[Optional[Request]] = [None] * serve_cfg.max_batch
        self._slot_pos = np.zeros(serve_cfg.max_batch, np.int32)
        self._slot_remaining = np.zeros(serve_cfg.max_batch, np.int32)
        self._slot_emitted = np.zeros(serve_cfg.max_batch, np.int32)
        self.completed: List[Request] = []
        self.rounds = 0
        self.warmup_s: Optional[float] = None
        self._warm_traces: Optional[int] = None

        # batched KV cache for all slots
        self._cache = self.api.init_cache(
            cfg, serve_cfg.max_batch, serve_cfg.max_len
        )

        # trace bookkeeping (repro.analysis.sanitize.TraceCounter): each
        # counter increments only while jit is *tracing* the wrapped
        # function, so steady-state traffic that reuses compiled buckets
        # leaves them flat.  trace_counts aliases the live counter dict
        # for stats() and the benchmarks.
        self.tracing = sanitize.TraceCounter(("decode", "prefill"))
        self.trace_counts: Dict[str, int] = self.tracing.counts
        # admitted prefill calls and their wall-clock seconds (dispatch to
        # host sync), keyed by bucket length
        self.prefill_calls: Dict[int, int] = {}
        self.prefill_seconds: Dict[int, float] = {}
        # device-path counters: real prompt tokens prefilled, the prompt
        # positions computed for them (bucket x padded rows), decode
        # blocks run, and active lanes x rounds over those blocks
        self.prefill_tokens = 0
        self.prefill_tokens_computed = 0
        self.decode_blocks = 0
        self.busy_lane_rounds = 0

        # ring caches re-layout the whole sequence at prefill time, which
        # does not compose with per-lane padded lengths; recurrent
        # families must see exact-length prompts (api flag)
        ring = bool(cfg.kv_ring and cfg.window and not cfg.global_every)
        self.bucketed_prefill = self.api.supports_bucketed_prefill and not ring
        ladder = [
            b for b in (
                serve_cfg.prefill_buckets
                or default_prefill_buckets(serve_cfg.max_len)
            )
            if b <= serve_cfg.max_len
        ]
        # max_len always terminates the ladder so every admissible prompt
        # (truncated to < max_len) has a bucket
        self._buckets = tuple(sorted(set(ladder + [serve_cfg.max_len])))

        # legacy host-loop decode step (also the host_sampling path)
        def _decode_step(p, c, t, pos):
            return self.api.decode_step(cfg, p, c, t, pos)

        self._decode = self.tracing.jit(_decode_step, kind="decode")

        # device-resident decode state: everything the steady-state loop
        # needs lives here between host syncs
        B = serve_cfg.max_batch
        self._state: Dict[str, jax.Array] = {
            "tokens": jnp.zeros((B, 1), jnp.int32),
            "pos": jnp.zeros((B,), jnp.int32),
            "remaining": jnp.zeros((B,), jnp.int32),
            "active": jnp.zeros((B,), jnp.bool_),
            "out_buf": jnp.zeros((B, serve_cfg.max_len), jnp.int32),
            "out_len": jnp.zeros((B,), jnp.int32),
            "key": jax.random.PRNGKey(serve_cfg.seed),
        }

        # cache and decode state are donated: the KV cache never crosses
        # the jit boundary by copy, it lives in the same device buffers
        # round after round (the "device-resident" in the name)
        self._decode_block = self.tracing.jit(
            self._decode_block_impl, kind="decode",
            static_argnums=3, donate_argnums=(1, 2),
        )

        self._admit_block = self.tracing.jit(
            self._admit_impl, kind="prefill", donate_argnums=(1, 2)
        )

        # per-round state transition for the staged (multi-PU) decode
        # path: exactly the fused block's post-decode update, jitted
        # standalone so the pipeline's logits feed the same bookkeeping
        self._staged_update = self.tracing.jit(
            self._postdecode_update, kind="decode"
        )

        # --- paper machinery ------------------------------------------------
        self.streaming_plan: Optional[StreamingPlan] = None
        self.partitioned_plan: Optional[PartitionedPlan] = None
        self.stage_meshes = None
        self.stage_meshes_shared = False
        self.last_pipeline_report = None
        self.last_autotune = None
        if serve_cfg.stream_pus and len(serve_cfg.stream_pus) == 1:
            # K=1 degenerates to the single-PU path: one "partition
            # stage" would only re-wrap the plain streaming plan.
            self.streaming_plan = plan_model_streaming(
                cfg, serve_cfg.stream_pus[0],
                batch_tokens=serve_cfg.max_batch,
                search=serve_cfg.plan_search,
            )
        elif serve_cfg.stream_pus:
            self.partitioned_plan = plan_partitioned_streaming(
                cfg, serve_cfg.stream_pus,
                batch_tokens=serve_cfg.max_batch,
                search=serve_cfg.plan_search,
            )
            if mesh is not None:
                from repro.launch.mesh import stage_submeshes

                self.stage_meshes, self.stage_meshes_shared = stage_submeshes(
                    mesh, len(self.partitioned_plan.stages)
                )
        # true per-stage decode: multi-PU device-path rounds run each
        # stage's model-layer slice through the stage pipeline, with
        # per-stage KV cache slices and real activation handoffs
        self._staged = None
        self._staged_live = False
        # M > 1 keeps the decode state split into per-lane-group dicts
        # between barriers (slicing/merging the full state every block
        # costs more than the decode itself on small models); _state is
        # stale while _staged_groups is set, except "key"
        self._staged_groups: Optional[List[Dict[str, jax.Array]]] = None
        self._staged_merged_key: Optional[jax.Array] = None
        # jitted lane-group state split/merge (built on first use)
        self._staged_split = None
        self._staged_merge = None
        self.staged_tune = None
        if (
            self.partitioned_plan is not None
            and serve_cfg.stage_decode
            and not serve_cfg.host_sampling
        ):
            from repro.runtime.stage_decode import StagedDecodeRunner

            # stages on one physical device (the single-host sim, or
            # shared submeshes) cannot overlap real compute -- one
            # execution stream serializes every stage.  Keep the
            # overlapped schedule but execute each block as a single
            # scan (StagedDecodeRunner.coalesce); distinct per-stage
            # device sets run the threaded executor for real overlap
            same_device = self.stage_meshes is None or self.stage_meshes_shared
            self._staged = StagedDecodeRunner(
                cfg, self.api, params, self.partitioned_plan,
                stage_meshes=None if same_device else self.stage_meshes,
                on_trace=self.tracing.bump,
                # fused into the last stage's cell: overlapped frames
                # carry their own sample-append transition, so the
                # coordinator thread does pure queue work
                postdecode=self._postdecode_update,
                coalesce=same_device,
            )
            # close the M loop: lane-group count and handoff queue depth
            # for the overlapped schedule come from the *executed* bubble
            # of a functional probe block (0 = auto), or are pinned by
            # the config (1 = the serial bit-identity reference)
            m_req = serve_cfg.decode_microbatches
            if m_req == 0:
                from repro.runtime.autotune import (
                    AutotuneConfig,
                    tune_staged_decode,
                )

                self.staged_tune = tune_staged_decode(
                    self.partitioned_plan, serve_cfg.max_batch,
                    AutotuneConfig(target_bubble=serve_cfg.target_bubble),
                )
                self._staged.configure(
                    n_groups=self.staged_tune.n_groups,
                    queue_depth=self.staged_tune.queue_depth,
                )
            else:
                m = max(
                    d for d in range(1, serve_cfg.max_batch + 1)
                    if serve_cfg.max_batch % d == 0 and d <= m_req
                )
                self._staged.configure(
                    n_groups=m, queue_depth=serve_cfg.stage_queue_depth
                )
        if serve_cfg.stream_pu is not None and not serve_cfg.stream_pus:
            self.streaming_plan = plan_model_streaming(
                cfg, serve_cfg.stream_pu,
                batch_tokens=serve_cfg.max_batch,
                search=serve_cfg.plan_search,
            )
        self.niu: Optional[NoiseInjectionUnit] = None
        if serve_cfg.aimc is not None and serve_cfg.aimc.enabled():
            self.niu = NoiseInjectionUnit(params, serve_cfg.aimc)

    # -- client API --------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: Optional[int] = None) -> int:
        # a request can never generate past the cache: clamp the budget to
        # max_len - 2 so at least two prompt tokens survive truncation
        # (keep = max_len - budget, see _truncated_prompt) and the
        # pos >= max_len - 1 stop can never cut a clamped budget short
        budget = max_new_tokens or self.serve_cfg.max_new_tokens
        req = Request(
            uid=self._uid,
            prompt=np.asarray(prompt, np.int32),
            max_new_tokens=max(1, min(budget, self.serve_cfg.max_len - 2)),
            submitted_at=time.perf_counter(),
        )
        self._uid += 1
        self._queue.append(req)
        return req.uid

    @property
    def active(self) -> int:
        return sum(s is not None for s in self._slots)

    @property
    def pending(self) -> int:
        return len(self._queue)

    def run_until_drained(self, max_rounds: int = 10_000) -> List[Request]:
        while (self.pending or self.active) and self.rounds < max_rounds:
            self.step()
        return self.completed

    def warmup(self):
        """Pre-compile the bounded trace grid so live traffic never
        retraces: every (prompt bucket x pow2 admit width) prefill shape
        and every pow2 decode-block length.  Warmup admission rows
        scatter out of bounds and no slot is active, so the served state
        is untouched -- except the sampling PRNG stream, which each
        warmup call advances exactly like a live call when
        ``temperature > 0`` (the engine stays deterministic for a fixed
        warmup + traffic sequence).

        Records ``warmup_s`` (wall time, compilation included, until the
        device is idle) and the trace count it leaves, so :meth:`stats`
        reports the traces taken after warmup."""
        t0 = time.perf_counter()
        self._warmup()
        jax.block_until_ready((self._cache, self._state))
        self.warmup_s = time.perf_counter() - t0
        self._warm_traces = self.tracing.total()

    def compiled_decode_block(self, n_rounds: int = 1):
        """The compiled fused decode block of ``n_rounds`` rounds -- the
        program the device path runs per block, for inspecting its HLO."""
        return self._decode_block.lower(
            self.params, self._cache, self._state, n_rounds
        ).compile()

    def _warmup(self):
        sc = self.serve_cfg
        if sc.host_sampling:
            tokens = jnp.zeros((sc.max_batch, 1), jnp.int32)
            self._decode(
                self.params, self._cache, tokens,
                jnp.zeros((sc.max_batch,), jnp.int32),
            )
            return
        if self.bucketed_prefill:
            nbs, nb = [], 1
            while nb < _pow2_ceil(sc.max_batch):
                nbs.append(nb)
                nb *= 2
            nbs.append(_pow2_ceil(sc.max_batch))
            for S in self._buckets:
                for nb in nbs:
                    # cache/state are donated into the call: reassign
                    self._cache, self._state, _, _ = self._admit_block(
                        self.params, self._cache, self._state,
                        jnp.full((nb, S), sc.pad_token, jnp.int32),
                        jnp.ones((nb,), jnp.int32),
                        jnp.full((nb,), sc.max_batch, jnp.int32),  # dropped
                        jnp.ones((nb,), jnp.int32),
                    )
        if self._staged is not None:
            # warm the per-stage cells and the state update at the live
            # schedule's lane-group width on throwaway cache slices,
            # then drop them.  The state is *kept* -- no lane is active,
            # so the transition is the identity except for the PRNG key,
            # which advances exactly like a live round (the warmup
            # contract above).  The first block is forced through the
            # threaded executor: its per-frame virtual clock is
            # cross-checked against the overlapped recurrence
            # (clock_ok), which the coalesced fast path then inherits
            self._staged_decode_block(2, force_threaded=True)
            if self._staged.coalesce and self._staged.n_groups > 1:
                # the coalesced path compiles one scan per pow2 block
                # length, like the fused single-PU ladder
                R = 1
                while R <= sc.max_decode_block:
                    self._staged_decode_block(R)
                    R *= 2
            self._staged_sync_state()
            # compile the barrier transform (slices -> master cache)
            # too: the first admission would otherwise pay it live
            self._staged.export_cache()
            self._staged.flush()
            self._staged.stage_caches = None
            self._staged_live = False
            self._staged.rounds_executed = 0
            self._staged.virtual_busy_s = 0.0
            self._staged.virtual_span_s = 0.0
            self._staged.last_report = None
            return
        R = 1
        while R <= sc.max_decode_block:
            self._cache, self._state = self._decode_block(
                self.params, self._cache, self._state, R
            )
            R *= 2

    # -- engine round -------------------------------------------------------
    def step(self):
        """One engine round (host path) or one fused block (device path)."""
        sc = self.serve_cfg
        if self.niu is not None and self.rounds % sc.aimc_refresh_every == 0:
            self._key, sub = jax.random.split(self._key)
            self.params = self.niu.refresh(sub)
        if sc.host_sampling:
            self._step_host()
        else:
            with TraceAnnotation("serve.step", queue=len(self._queue), lanes=self.active):
                self._step_device()

    # ======================================================================
    # device-resident path
    # ======================================================================

    def _sample_device(self, key, logits):
        """On-device sampling shared by admission and decode: greedy
        argmax, or temperature categorical consuming the threaded key."""
        sc = self.serve_cfg
        if sc.temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(
                sub, logits.astype(jnp.float32) / sc.temperature, axis=-1
            ).astype(jnp.int32)
        else:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return key, tok

    def _apply_eos(self, done, tok):
        """Fold eos termination into ``done``.  The single definition of
        "eos is enabled" for every path (host scalars and device
        vectors): any non-negative ``eos_token`` -- including 0 -- is a
        real stop token; only negative values disable the check."""
        if self.serve_cfg.eos_token >= 0:
            return done | (tok == self.serve_cfg.eos_token)
        return done

    def _postdecode_update(self, state, logits):
        """Sample-append bookkeeping after one decode round's logits:
        the single state transition shared by the fused device block and
        the staged per-round loop, so both paths terminate, append, and
        thread the PRNG identically.  Width-polymorphic: the lane count
        comes from the state, so the same transition serves the full
        slot batch and a 1/M lane-group slice (every operation is
        per-lane, which is why lane-group splitting preserves greedy
        bit-identity)."""
        sc = self.serve_cfg
        lane = jnp.arange(state["active"].shape[0])
        key, tok = self._sample_device(state["key"], logits)
        act = state["active"]
        acti = act.astype(jnp.int32)
        tok = jnp.where(act, tok, sc.pad_token)
        # inactive lanes write at an out-of-bounds column -> dropped
        col = jnp.where(act, state["out_len"], sc.max_len)
        out_buf = state["out_buf"].at[lane, col].set(tok, mode="drop")
        out_len = state["out_len"] + acti
        pos = state["pos"] + acti
        rem = state["remaining"] - acti
        done = (rem <= 0) | (pos >= sc.max_len - 1)
        done = self._apply_eos(done, tok)
        return {
            "tokens": tok[:, None],
            "pos": pos,
            "remaining": rem,
            "active": act & ~done,
            "out_buf": out_buf,
            "out_len": out_len,
            "key": key,
        }

    def _prefill_batch(self, tokens, lengths=None):
        """Model-API prefill batch for ``tokens``, with the stub modality
        inputs each family expects (shared by both admission paths)."""
        dt = jnp.bfloat16 if self.cfg.dtype == "bfloat16" else jnp.float32
        batch: Dict[str, jax.Array] = {"tokens": tokens}
        if lengths is not None:
            batch["lengths"] = lengths
        if self.cfg.family == "vlm":
            batch["patch_embeds"] = jnp.zeros(
                (tokens.shape[0], self.cfg.vision_patches, self.cfg.d_model),
                dt,
            )
        if self.cfg.family == "encdec":
            batch["frames"] = jnp.zeros(
                (tokens.shape[0], self.cfg.encoder_frames, self.cfg.d_model),
                dt,
            )
        return batch

    def _decode_block_impl(self, params, cache, state, n_rounds: int):
        """``n_rounds`` fused decode rounds: sample-append, per-slot
        position/remaining bookkeeping and done flags all stay on device;
        generated tokens land in the device-side ``out_buf`` ring so the
        host only reads them at request completion."""

        def one(carry, _):
            cache, st = carry
            logits, cache = self.api.decode_step(
                self.cfg, params, cache, st["tokens"], st["pos"]
            )
            return (cache, self._postdecode_update(st, logits)), None

        (cache, state), _ = jax.lax.scan(
            one, (cache, state), None, length=n_rounds
        )
        return cache, state

    def _staged_decode_block(self, n_rounds: int, force_threaded: bool = False):
        """``n_rounds`` true per-stage decode rounds: hidden states flow
        through the stage pipeline (every stage running its model-layer
        slice against its own KV cache slice on its submesh), then the
        shared ``_postdecode_update`` transition applies -- so greedy
        streams are bit-identical to the fused single-PU block.

        With ``n_groups == 1`` each round is one full-batch frame (the
        serial A/B reference).  With M > 1 the decode state is split
        into M lane-group slices and the rounds run *overlapped*
        (``StagedDecodeRunner.decode_block``): stage s computes group g
        while stage s-1 computes g+1, and round r+1 of a group enters
        the pipeline the moment round r of that group drains.  Every
        state operation is per-lane (sampling is per-lane argmax under
        greedy), so the merged stream is unchanged; the PRNG key is the
        only cross-lane state and is chained per group, which greedy
        never consumes -- temperature sampling stays deterministic but
        draws a different (per-group) stream than the fused loop."""
        runner = self._staged
        if runner.bound_params is not self.params:
            runner.rebind(self.params)       # e.g. after an NIU refresh
        if not self._staged_live:
            runner.load_cache(self._cache)
            self._staged_live = True
        M = runner.n_groups
        if M == 1:
            for _ in range(n_rounds):
                logits = runner.decode_round(
                    self._state["tokens"], self._state["pos"]
                )
                self._state = self._staged_update(self._state, logits)
            return
        if self._staged_groups is None:
            if self._staged_split is None:
                sc = self.serve_cfg
                temp = sc.temperature > 0
                gsz = sc.max_batch // M

                # jitted (like the merge below): eager slices would
                # re-specialize against the donated block outputs'
                # layouts at every barrier, costing fresh compiles
                def _split(state):
                    if temp:
                        keys = jax.random.split(state["key"], M + 1)
                        new_key, gkeys = keys[0], list(keys[1:])
                    else:
                        # greedy never consumes the key, but the staged
                        # cells donate their group's state -- each group
                        # needs its own buffer, not M references to the
                        # master key
                        new_key = state["key"]
                        gkeys = [state["key"] + 0 for _ in range(M)]
                    groups = []
                    for i in range(M):
                        gs = {
                            k: v[i * gsz:(i + 1) * gsz]
                            for k, v in state.items() if k != "key"
                        }
                        gs["key"] = gkeys[i]
                        groups.append(gs)
                    return new_key, groups

                self._staged_split = jax.jit(_split)
            new_key, groups = self._staged_split(self._state)
            self._staged_groups = groups
            self._staged_merged_key = new_key
        runner.decode_block(
            self._staged_groups, n_rounds, force_threaded=force_threaded
        )

    def _staged_sync_state(self):
        """Merge the per-lane-group decode states back into the master
        ``_state`` (lane groups rejoin on axis 0 in group order) -- the
        state half of the round-boundary barrier.  The merged PRNG key
        is the head of the split that seeded the groups, so a fixed
        warmup + traffic sequence stays deterministic."""
        groups = self._staged_groups
        if groups is None:
            return
        if self._staged_merge is None:

            def _merge(groups):
                return {
                    k: jnp.concatenate([gr[k] for gr in groups], axis=0)
                    for k in groups[0] if k != "key"
                }

            self._staged_merge = jax.jit(_merge)
        merged = self._staged_merge(groups)
        merged["key"] = self._staged_merged_key
        self._state = merged
        self._staged_groups = None
        self._staged_merged_key = None

    def _admit_impl(self, params, cache, state, tokens, lengths, slots, max_new):
        """Batched prefill of one length bucket + on-device admission:
        sample each prompt's first token, scatter the prefilled KV lanes
        and the per-slot decode state in one jitted update.  Dummy rows
        (bucket padding) carry ``slots == max_batch`` and are dropped by
        the out-of-bounds scatter mode."""
        batch = self._prefill_batch(
            tokens, lengths if self.bucketed_prefill else None
        )
        logits, one_cache = self.api.prefill(self.cfg, params, batch)
        key, tok = self._sample_device(state["key"], logits)

        cache = scatter_cache_lanes(cache, one_cache, slots)
        # a request whose budget is one token (or whose first token is
        # eos) completes at admission: it never occupies a decode slot
        done0 = self._apply_eos(max_new <= 1, tok)
        state = {
            "tokens": state["tokens"].at[slots, 0].set(tok, mode="drop"),
            "pos": state["pos"].at[slots].set(lengths, mode="drop"),
            "remaining": state["remaining"].at[slots].set(
                max_new - 1, mode="drop"
            ),
            "active": state["active"].at[slots].set(~done0, mode="drop"),
            "out_buf": state["out_buf"].at[slots, 0].set(tok, mode="drop"),
            "out_len": state["out_len"].at[slots].set(1, mode="drop"),
            "key": key,
        }
        return cache, state, tok, done0

    def _bucket_for(self, n: int) -> int:
        for b in self._buckets:
            if b >= n:
                return b
        return self._buckets[-1]

    def _truncated_prompt(self, req: Request) -> np.ndarray:
        """Tail of the prompt that fits the KV budget alongside the
        request's generation budget.

        A prompt of length ``keep`` prefills slots [0, keep); decode
        round r writes KV at ``keep + r`` and the engine stops after
        ``max_new - 1`` rounds (the first token is sampled at admission)
        or when ``pos`` reaches ``max_len - 1`` -- so the last KV write
        lands at ``keep + max_new - 2`` and ``keep = max_len - max_new``
        is exactly the largest safe context.  (The previous ``- 1``
        reserved a slot no path ever wrote, silently dropping one prompt
        token at the boundary -- caught by the length ``max_len - 1``
        boundary test.)"""
        sc = self.serve_cfg
        keep = max(1, sc.max_len - req.max_new_tokens)
        return req.prompt[-keep:]

    def _admit_device(self):
        """Admit every waiting request a free slot can take.  Requests in
        the same round whose prompts fall in the same length bucket share
        a single prefill call."""
        sc = self.serve_cfg
        free = [i for i, s in enumerate(self._slots) if s is None]
        admits: List[Tuple[int, Request, np.ndarray]] = []
        while free and self._queue:
            req = self._queue.popleft()
            admits.append((free.pop(0), req, None))
        if not admits:
            return
        if self._staged is not None and self._staged_live:
            # the round-boundary barrier: admission mutates slot
            # membership, so fold the per-lane-group decode states and
            # the staged runner's per-stage cache slices back into the
            # master layout first (export_cache also flushes the
            # overlapped session; everything is re-sliced lazily at the
            # next staged block, which re-pays the fill bubble there)
            self._staged_sync_state()
            self._cache = self._staged.export_cache()
            self._staged_live = False
        groups: Dict[int, List[Tuple[int, Request, np.ndarray]]] = {}
        for slot, req, _ in admits:
            prompt = self._truncated_prompt(req)
            S = (
                self._bucket_for(len(prompt))
                if self.bucketed_prefill
                else len(prompt)
            )
            groups.setdefault(S, []).append((slot, req, prompt))

        for S, group in sorted(groups.items()):
            nb = len(group)
            # pad the admit batch to a power of two so the (bucket, nb)
            # trace set stays bounded; dummy rows scatter out of bounds
            nb_pad = _pow2_ceil(nb) if self.bucketed_prefill else nb
            adm = Admission(S, nb, nb_pad, sum(len(p) for _, _, p in group))
            with TraceAnnotation("serve.admit", bucket=S, n=nb, n_pad=nb_pad,
                                 tokens=adm.tokens):
                tokens = np.full((nb_pad, S), sc.pad_token, np.int32)
                lengths = np.ones((nb_pad,), np.int32)
                slots = np.full((nb_pad,), sc.max_batch, np.int32)
                max_new = np.ones((nb_pad,), np.int32)
                for j, (slot, req, prompt) in enumerate(group):
                    tokens[j, : len(prompt)] = prompt
                    lengths[j] = len(prompt)
                    slots[j] = slot
                    max_new[j] = req.max_new_tokens
                t0 = time.perf_counter()
                self._cache, self._state, tok, done0 = self._admit_block(
                    self.params, self._cache, self._state,
                    jnp.asarray(tokens), jnp.asarray(lengths),
                    jnp.asarray(slots), jnp.asarray(max_new),
                )
            with TraceAnnotation("serve.admit_sync"):
                # lint: disable=RPL002 -- designed admission-boundary sync: the admit block must land before slots update
                done0_np = np.asarray(done0)
                self._time_prefill(S, time.perf_counter() - t0)
                now = time.perf_counter()
                # lint: disable=RPL002 -- designed admission-boundary sync: first tokens of already-done admits drain here
                tok_np = np.asarray(tok) if done0_np[:nb].any() else None
            self.prefill_tokens += adm.tokens
            self.prefill_tokens_computed += adm.computed
            for j, (slot, req, prompt) in enumerate(group):
                req.admission = adm
                req.first_token_at = now
                if done0_np[j]:
                    # lint: disable=RPL002 -- host-side numpy scalar; the batch already drained above
                    req.out_tokens = [int(tok_np[j])]
                    req.done_at = now
                    self.completed.append(req)
                else:
                    self._slots[slot] = req
                    self._slot_emitted[slot] = 1
                    self._slot_pos[slot] = len(prompt)

    def _step_device(self):
        """One fused block: admit (bucketed batched prefill), then run
        the largest power-of-two decode block that no active request can
        out-finish, then sync the per-slot scalars."""
        sc = self.serve_cfg
        self._admit_device()
        if not any(s is not None for s in self._slots):
            self.rounds += 1
            return
        # lint: disable=RPL002 -- _slot_emitted is a host numpy array; no device pull
        remaining = [
            max(1, req.max_new_tokens - int(self._slot_emitted[i]))
            for i, req in enumerate(self._slots)
            if req is not None
        ]
        cap = sc.max_decode_block
        if self.niu is not None:
            # AIMC refresh happens between host rounds; keep per-round
            # granularity so every round sees a fresh noise instance
            cap = 1
        # queue-aware block sizing: with admissions waiting, sync when
        # the earliest slot frees; with an empty queue a finished lane
        # just goes inactive inside the block (the batched step computes
        # every lane regardless), so run until the *last* slot could
        # finish and save the host syncs
        r = min(remaining) if self._queue else max(remaining)
        r = max(1, min(r, cap))
        R = 1 << (r.bit_length() - 1)          # largest power of two <= r
        lanes = len(remaining)
        # the decode block itself must never pull device data to the
        # host: under REPRO_SANITIZE=1 an implicit device->host transfer
        # inside it raises instead of silently serializing the rounds
        with sanitize.transfer_guard():
            if self._staged is not None:
                self._staged_decode_block(R)
            else:
                with TraceAnnotation("serve.decode_block", rounds=R, lanes=lanes):
                    self._cache, self._state = self._decode_block(
                        self.params, self._cache, self._state, R
                    )
        self.rounds += R
        self.decode_blocks += 1
        self.busy_lane_rounds += lanes * R

        groups = self._staged_groups
        with TraceAnnotation("serve.block_sync"):
            if groups is not None:
                # per-group scalar sync: the decode state stays split
                # between barriers, so read the per-lane flags group-wise
                # instead of merging the whole state every block
                gsize = sc.max_batch // len(groups)
                # lint: disable=RPL002 -- the designed block-boundary sync: per-slot flags after R fused rounds
                active = np.concatenate(
                    [np.asarray(gr["active"]) for gr in groups]
                )
                # lint: disable=RPL002 -- designed block-boundary sync (see above)
                out_len = np.concatenate(
                    [np.asarray(gr["out_len"]) for gr in groups]
                )
            else:
                # lint: disable=RPL002 -- the designed block-boundary sync: per-slot flags after R fused rounds
                active = np.asarray(self._state["active"])
                # lint: disable=RPL002 -- designed block-boundary sync (see above)
                out_len = np.asarray(self._state["out_len"])
        now = time.perf_counter()
        for i, req in enumerate(self._slots):
            if req is None:
                continue
            # lint: disable=RPL002 -- out_len already synced to host numpy above
            self._slot_emitted[i] = int(out_len[i])
            if not active[i]:
                # lint: disable=RPL002 -- out_len already synced to host numpy above
                n = int(out_len[i])
                with TraceAnnotation("serve.drain", tokens=n):
                    if groups is not None:
                        gi, row = divmod(i, gsize)
                        buf = groups[gi]["out_buf"][row, :n]
                    else:
                        buf = self._state["out_buf"][i, :n]
                    # lint: disable=RPL002 -- designed drain of a finished request's tokens at the block boundary
                    req.out_tokens = [int(t) for t in np.asarray(buf)]
                req.done_at = now
                self.completed.append(req)
                self._slots[i] = None

    # ======================================================================
    # legacy host-loop path (ServeConfig.host_sampling escape hatch)
    # ======================================================================

    def _step_host(self):
        """One engine round: admit+prefill -> batched decode, with
        sampling and request bookkeeping on the host (the pre-device-
        resident engine, kept as the A/B reference)."""
        sc = self.serve_cfg
        # admit
        for i in range(sc.max_batch):
            if self._slots[i] is None and self._queue:
                req = self._queue.popleft()
                self._admit_host(i, req)

        if not self.active:
            self.rounds += 1
            return

        # batched decode for all active slots (inactive slots decode a pad
        # token into their own cache lane; results discarded)
        tokens = np.zeros((sc.max_batch, 1), np.int32)
        for i, req in enumerate(self._slots):
            if req is not None:
                last = (
                    req.out_tokens[-1]
                    if req.out_tokens
                    else int(req.prompt[-1])
                )
                tokens[i, 0] = last
        # per-slot position vector: each lane writes its KV at its own
        # position, so staggered admissions never clobber a neighbour's
        # cache (the old engine passed the max over slots -- a later
        # admit wrote its KV at an earlier slot's position)
        logits, self._cache = self._decode(
            self.params, self._cache, jnp.asarray(tokens),
            jnp.asarray(self._slot_pos),
        )
        logits = np.asarray(logits, np.float32)

        now = time.perf_counter()
        for i, req in enumerate(self._slots):
            if req is None:
                continue
            tok = self._sample(logits[i])
            req.out_tokens.append(tok)
            if req.first_token_at is None:
                req.first_token_at = now
            self._slot_pos[i] += 1
            self._slot_remaining[i] -= 1
            if self._apply_eos(
                self._slot_remaining[i] <= 0
                or self._slot_pos[i] >= sc.max_len - 1,
                tok,
            ):
                req.done_at = now
                self.completed.append(req)
                self._slots[i] = None
        self.rounds += 1

    def _admit_host(self, slot: int, req: Request):
        """Prefill a request into one cache lane (lane-isolated)."""
        prompt = self._truncated_prompt(req)
        t0 = time.perf_counter()
        batch = self._prefill_batch(jnp.asarray(prompt[None, :], jnp.int32))
        logits, cache = self.api.prefill(self.cfg, self.params, batch)
        self._cache = scatter_cache(self._cache, cache, slot, len(prompt))
        tok = self._sample(np.asarray(logits, np.float32)[0])
        self._time_prefill(len(prompt), time.perf_counter() - t0)
        req.out_tokens.append(tok)
        req.first_token_at = time.perf_counter()
        # a single-token budget (or an eos first token) completes at
        # admission instead of occupying a slot for a wasted decode round
        if self._apply_eos(req.max_new_tokens <= 1, tok):
            req.done_at = req.first_token_at
            self.completed.append(req)
            return
        self._slots[slot] = req
        self._slot_pos[slot] = len(prompt)
        self._slot_remaining[slot] = req.max_new_tokens - 1

    def _time_prefill(self, bucket: int, seconds: float) -> None:
        self.prefill_calls[bucket] = self.prefill_calls.get(bucket, 0) + 1
        self.prefill_seconds[bucket] = self.prefill_seconds.get(bucket, 0.0) + seconds

    def prefill_s_by_bucket(self) -> Dict[int, float]:
        """Mean wall-clock seconds of an admitted prefill call, per bucket."""
        return {b: self.prefill_seconds[b] / n for b, n in sorted(self.prefill_calls.items())}

    def _sample(self, logits: np.ndarray) -> int:
        if self.serve_cfg.temperature <= 0:
            return int(np.argmax(logits))
        p = logits / self.serve_cfg.temperature
        p = np.exp(p - p.max())
        p /= p.sum()
        return int(self._rng.choice(len(p), p=p))

    # -- executed partition (stage-parallel streaming runtime) ---------------
    def execute_partition(self, n_microbatches: Optional[int] = None):
        """Run the partitioned plan through the real stage-parallel
        executor (``runtime.pipeline_exec``): K stage threads, per-stage
        prefetch workers honoring issue order, double-buffered handoffs.

        ``n_microbatches=None`` (the default) auto-tunes the microbatch
        depth and handoff queue depth against
        ``ServeConfig.target_bubble`` using the *executed* bubble
        measurement (``runtime.autotune``); an explicit integer pins M
        (the legacy fixed-depth behaviour).

        Validates the partition as a *runnable* artifact -- measured
        pipeline throughput and fill bubble land in :meth:`stats`
        alongside the analytic numbers so regressions between the cost
        model and the runtime are visible.  This is the functional-tile
        *bench* mode (microbatch dynamics at depth M); the serving
        rounds themselves run true per-stage decode through the same
        executor (``runtime.stage_decode``) whenever the engine has a
        partitioned plan on the device path.
        """
        if self.partitioned_plan is None:
            raise ValueError("engine has no partitioned plan "
                             "(ServeConfig.stream_pus not set or K=1)")
        if n_microbatches is None:
            from repro.runtime.autotune import AutotuneConfig, tune_pipeline

            result = tune_pipeline(
                self.partitioned_plan,
                AutotuneConfig(target_bubble=self.serve_cfg.target_bubble),
            )
            self.last_autotune = result
            self.last_pipeline_report = result.report
            return result.report
        from repro.runtime.pipeline_exec import execute_partitioned_plan

        report = execute_partitioned_plan(
            self.partitioned_plan, n_microbatches=n_microbatches
        )
        self.last_autotune = None     # pinned M supersedes any prior tune
        self.last_pipeline_report = report
        return report

    # -- metrics --------------------------------------------------------------
    def stats(self) -> Dict[str, float]:
        done = self.completed
        toks = sum(len(r.out_tokens) for r in done)
        ttfts = [r.ttft_s for r in done if r.ttft_s is not None]
        total = (
            max(r.done_at for r in done) - min(r.submitted_at for r in done)
            if done
            else 0.0
        )
        out = {
            "completed": float(len(done)),
            "tokens": float(toks),
            "rounds": float(self.rounds),
            "tokens_per_s": toks / total if total > 0 else 0.0,
            "mean_ttft_s": float(np.mean(ttfts)) if ttfts else 0.0,
            "device_resident": 0.0 if self.serve_cfg.host_sampling else 1.0,
            "decode_traces": float(self.trace_counts["decode"]),
            "prefill_traces": float(self.trace_counts["prefill"]),
        }
        if self._warm_traces is not None:
            out["warmup_s"] = self.warmup_s
            out["retraces_after_warmup"] = float(
                self.tracing.total() - self._warm_traces
            )
        for b, s in self.prefill_s_by_bucket().items():
            out[f"prefill_s_bucket{b}"] = s
        out.update(
            prefill_tokens=self.prefill_tokens,
            prefill_tokens_computed=self.prefill_tokens_computed,
            decode_blocks=self.decode_blocks,
            busy_lane_rounds=self.busy_lane_rounds,
        )
        if self.streaming_plan is not None:
            out.update(
                {f"stream_{k}": v for k, v in self.streaming_plan.summary().items()}
            )
        if self.partitioned_plan is not None:
            p = self.partitioned_plan
            out.update(
                {
                    "partition_stages": float(len(p.stages)),
                    "partition_fps": p.fps,
                    "partition_latency_s": p.latency_s,
                    "partition_bottleneck_s": p.bottleneck_s,
                    "partition_stall_s": sum(
                        s.plan.total_stall for s in p.stages
                    ),
                }
            )
            if self.last_pipeline_report is not None:
                r = self.last_pipeline_report
                out.update(
                    {
                        "partition_executed_fps": r.measured_fps,
                        # vs the steady-state analytic fps (like
                        # FleetSim.execute_pipelines): < 1 by the fill
                        # bubble, so the stat can actually move
                        "partition_executed_vs_analytic": (
                            r.measured_fps / r.steady_fps
                            if r.steady_fps > 0
                            else 0.0
                        ),
                        "partition_bubble_measured": r.bubble_measured,
                        "partition_bubble_predicted": r.bubble_predicted,
                        "partition_executed_wall_s": r.wall_s,
                        "partition_microbatches": float(r.n_microbatches),
                    }
                )
            if self.last_autotune is not None:
                a = self.last_autotune
                out.update(
                    {
                        "partition_autotuned_m": float(a.n_microbatches),
                        "partition_autotuned_queue_depth": float(
                            a.queue_depth
                        ),
                        "partition_autotune_target_bubble": a.target_bubble,
                        "partition_autotune_within_tolerance": float(
                            a.within_tolerance
                        ),
                        "partition_autotune_trials": float(len(a.trials)),
                    }
                )
            if self.stage_meshes is not None:
                out["partition_stage_devices"] = float(
                    sum(len(m.devices.ravel()) for m in self.stage_meshes)
                    if not self.stage_meshes_shared
                    else len(self.mesh.devices.ravel())
                )
            if self._staged is not None:
                # fold any open overlapped session into the virtual
                # account so the reported bubble covers every block
                self._staged.flush()
                out["stage_decode"] = 1.0
                out["stage_decode_rounds"] = float(
                    self._staged.rounds_executed
                )
                out["stage_decode_clock_ok"] = float(self._staged.clock_ok)
                out["stage_decode_coalesced"] = float(self._staged.coalesce)
                out["stage_decode_microbatches"] = float(
                    self._staged.n_groups
                )
                out["stage_decode_queue_depth"] = float(
                    self._staged.queue_depth
                )
                out["stage_decode_bubble"] = self._staged.bubble_fraction
                for k, (a, b) in enumerate(self._staged.ranges):
                    out[f"stage{k}_decode_layers"] = float(b - a)
                if self.staged_tune is not None:
                    t = self.staged_tune
                    out["stage_decode_autotuned"] = 1.0
                    out["stage_decode_autotune_target_bubble"] = (
                        t.target_bubble
                    )
                    out["stage_decode_autotune_within_tolerance"] = float(
                        t.within_tolerance
                    )
                    out["stage_decode_autotune_trials"] = float(
                        len(t.trials)
                    )
        return out


# -------------------------------------------------------------------------
# cache scatter + streaming-plan construction
# -------------------------------------------------------------------------


def scatter_cache_lanes(batched_cache, group_cache, slots: jax.Array):
    """Write a batch of prefilled sequences into cache lanes ``slots``.

    Works over arbitrary cache pytrees: any array leaf whose second axis
    is the batch axis (layers-leading layout (L, B, ...)) gets lanes
    ``slots`` overwritten with the corresponding rows of ``group_cache``
    (zero-padded to the full lane, so stale state beyond the prefill
    never survives).  Rows whose slot index is out of bounds (the
    bucket-padding dummies) are dropped by the scatter.
    """

    def upd(full, one):
        if not hasattr(full, "ndim") or full.ndim < 2:
            return full
        one = one.astype(full.dtype)
        pad_shape = (full.shape[0], one.shape[1]) + full.shape[2:]
        slicer = tuple(
            slice(0, min(o, f)) for o, f in zip(one.shape, pad_shape)
        )
        patch = jnp.zeros(pad_shape, full.dtype).at[slicer].set(one[slicer])
        return full.at[:, slots].set(patch, mode="drop")

    return jax.tree.map(upd, batched_cache, group_cache)


def scatter_cache(batched_cache, one_cache, slot: int, length: int):
    """Write a single-sequence prefill cache into lane ``slot`` (the
    lane-isolated special case of :func:`scatter_cache_lanes`)."""
    del length  # the full lane is overwritten; garbage can't survive
    return scatter_cache_lanes(
        batched_cache, one_cache, jnp.asarray([slot], jnp.int32)
    )


def model_gemms(cfg: ModelConfig, batch_tokens: int) -> List[Tuple[str, int, int, int]]:
    """(name, N, M, P) for every weight GEMM of one decode round, in
    inference order -- the schedulable tile sequence of the paper (SS III)
    applied to an LM.  P = tokens per round (the decode batch).
    """
    d, f = cfg.d_model, cfg.d_ff
    hd = cfg.head_dim
    gemms: List[Tuple[str, int, int, int]] = []
    p = batch_tokens
    for layer in range(cfg.n_layers):
        pre = f"L{layer}"
        if cfg.family not in ("ssm",):
            gemms.append((f"{pre}/q", cfg.n_heads * hd, d, p))
            gemms.append((f"{pre}/k", cfg.n_kv_heads * hd, d, p))
            gemms.append((f"{pre}/v", cfg.n_kv_heads * hd, d, p))
            gemms.append((f"{pre}/o", d, cfg.n_heads * hd, p))
        if cfg.family in ("ssm", "hybrid"):
            din = cfg.d_inner
            ns, nh = cfg.ssm_state, cfg.ssm_heads
            gemms.append((f"{pre}/ssm_in", 2 * din + 2 * ns + nh, d, p))
            gemms.append((f"{pre}/ssm_out", d, din, p))
        if cfg.is_moe:
            # only routed-to experts need residency: top_k of n_experts
            for e in range(cfg.top_k):
                gemms.append((f"{pre}/expert{e}/up", f, d, p))
                gemms.append((f"{pre}/expert{e}/gate", f, d, p))
                gemms.append((f"{pre}/expert{e}/down", d, f, p))
        elif cfg.d_ff > 0 and cfg.family != "ssm":
            n_mats = 3 if cfg.mlp == "swiglu" else 2
            gemms.append((f"{pre}/mlp_up", f * (n_mats - 1), d, p))
            gemms.append((f"{pre}/mlp_down", d, f, p))
    gemms.append(("unembed", cfg.vocab, d, p))
    return gemms


def plan_model_streaming(
    cfg: ModelConfig,
    pu: Optional[PUConfig] = None,
    batch_tokens: int = 8,
    search: Optional[SearchConfig] = None,
) -> StreamingPlan:
    """Two-phase streaming plan for one decode round of ``cfg``.

    Layer-level granularity (not R_SA rows): at TPU scale a schedulable
    tile is one weight matrix; the scheduler math is identical.
    """
    pu = pu or host_offload_config()
    tiles = [
        WeightTile(name=name, layer_index=i, n=n, m=m, p=p)
        for i, (name, n, m, p) in enumerate(model_gemms(cfg, batch_tokens))
    ]
    return plan_streaming(tiles, pu, search=search)


def _gemm_layer(name: str, n_layers: int) -> int:
    """Model-layer index of a ``model_gemms`` entry (``L{i}/...``);
    layer-less tails (unembed) count as past the last layer."""
    if name.startswith("L"):
        head = name.split("/", 1)[0]
        try:
            return int(head[1:])
        except ValueError:
            pass
    return n_layers


def attach_decode_ranges(
    cfg: ModelConfig,
    gemms: Sequence[Tuple[str, int, int, int]],
    pplan: PartitionedPlan,
) -> PartitionedPlan:
    """Derive each stage's *model-layer* decode range from its GEMM range.

    A model layer belongs to the stage that owns its first GEMM; the
    resulting boundaries are snapped to the family's allowed slice
    points (``ModelAPI.decode_slice_points`` -- e.g. hybrid boundaries
    must be group-aligned) and kept monotone, so the ranges tile
    ``[0, n_layers)`` exactly.  Snapping is non-empty-preserving
    (:func:`repro.plan.partition.snap_boundaries_nonempty`): whenever
    the slice grid has at least K-1 interior points, every stage owns
    >= 1 layer -- the unembed-heavy tail of the GEMM sequence would
    otherwise pull the last boundary onto ``n_layers`` and leave a
    degenerate empty stage idling through every decode round.  Only
    when K exceeds what the grid can host does a stage go empty and
    pass hidden states through untouched."""
    api = model_api.get_api(cfg)
    pts = sorted(api.decode_slice_points(cfg))
    L = cfg.n_layers
    first_gemm: Dict[int, int] = {}
    for gi, (name, *_rest) in enumerate(gemms):
        first_gemm.setdefault(_gemm_layer(name, L), gi)
    bounds = [0]
    for st in pplan.stages[1:]:
        gs = st.layer_start            # gemm-sequence index
        bounds.append(
            sum(1 for l in range(L) if first_gemm.get(l, 1 << 60) < gs)
        )
    bounds.append(L)
    snapped = [0] + snap_boundaries_nonempty(bounds[1:-1], pts, L) + [L]
    stages = tuple(
        dataclasses.replace(
            s,
            decode_layer_start=snapped[k],
            decode_layer_stop=snapped[k + 1],
        )
        for k, s in enumerate(pplan.stages)
    )
    return PartitionedPlan(stages=stages)


def plan_partitioned_streaming(
    cfg: ModelConfig,
    pus: Sequence[PUConfig],
    batch_tokens: int = 8,
    search: Optional[SearchConfig] = None,
) -> PartitionedPlan:
    """Split one decode round's GEMM sequence across several PU profiles.

    Contiguous GEMM ranges are balanced on each profile's exec-time model
    and each stage gets its own two-phase schedule (capacity + load
    channel per PU) -- the served model streams across the whole fleet
    instead of replicating frames.  ``search`` selects each stage's
    schedule-search strategy.  Each stage also carries the model-layer
    decode range its layer slicers consume (:func:`attach_decode_ranges`),
    making the plan runnable by ``runtime.stage_decode``.
    """
    gemms = model_gemms(cfg, batch_tokens)
    pplan = partition_gemms(gemms, list(pus), search=search)
    return attach_decode_ranges(cfg, gemms, pplan)
