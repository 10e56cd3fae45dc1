"""Single-token decode updates the stacked KV cache in place.

``transformer.decode_stage`` carries the whole ``(L, B, S, ...)`` cache
through its layer loop and writes one row per lane and layer.  Two
guards: the compiled serving decode block holds no ``copy`` of a
whole-cache buffer (a layer loop that slices each layer out and
re-stacks it into a new buffer costs two or three per round), and the
in-place step computes what the full-sequence forward pass does."""
import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, smoke_variant
from repro.models import api as model_api
from repro.models import transformer
from repro.runtime.serving import ServeConfig, ServingEngine

_HLO_DTYPE = {"bfloat16": "bf16", "float32": "f32", "int8": "s8"}
_COPY = re.compile(r"= (\w+\[[\d,]*\])(?:\{[^}]*\})? copy(?:-start)?\(")


def _cache_cfg(kind):
    if kind == "kv_ring":
        # pure sliding window (64 at smoke size), dense so the ring is the
        # only thing that differs from the plain cache
        base = smoke_variant(get_config("mixtral-8x7b"))
        return dataclasses.replace(base, n_experts=0, top_k=0, kv_ring=True)
    cfg = smoke_variant(get_config("olmo-1b"))
    return dataclasses.replace(cfg, kv_quant=True) if kind == "kv_quant" else cfg


@pytest.mark.parametrize("kind", ["plain", "kv_quant", "kv_ring"])
def test_decode_block_does_not_copy_the_cache(kind):
    cfg = _cache_cfg(kind)
    api = model_api.get_api(cfg)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(
        cfg, params, ServeConfig(max_batch=2, max_len=128, max_new_tokens=4)
    )
    cache_shapes = {
        f"{_HLO_DTYPE[str(c.dtype)]}[{','.join(map(str, c.shape))}]"
        for c in engine._cache
    }
    if kind == "kv_ring":
        assert all(f",{cfg.window}," in s for s in cache_shapes), cache_shapes
    hlo = engine._decode_block.lower(
        engine.params, engine._cache, engine._state, 2
    ).compile().as_text()
    copies = [s for s in _COPY.findall(hlo) if s in cache_shapes]
    assert copies == [], f"whole-cache copies in the decode block: {copies}"


def _greedy_decode(cfg, api, params, prompts, n_steps, per_lane):
    """Bucketed prefill of ``prompts`` then ``n_steps`` greedy decode
    steps -> (tokens (B, n_steps + 1), the cache before the first step and
    after each, the cache buffer length)."""
    b = len(prompts)
    lengths = np.array([len(p) for p in prompts], np.int32)
    bucket = int(lengths.max())
    toks = np.zeros((b, bucket), np.int32)
    for i, p in enumerate(prompts):
        toks[i, : len(p)] = p
    batch = {"tokens": jnp.asarray(toks)}
    if per_lane:
        batch["lengths"] = jnp.asarray(lengths)
    logits, pre = api.prefill(cfg, params, batch)
    max_len = bucket + n_steps + 4
    cache = tuple(
        jax.lax.dynamic_update_slice(f, c.astype(f.dtype), (0,) * f.ndim)
        for f, c in zip(api.init_cache(cfg, b, max_len), pre)
    )
    caches = [cache]
    step = jax.jit(lambda p, c, t, i: api.decode_step(cfg, p, c, t, i))
    out = [np.argmax(np.asarray(logits), -1)]
    for s in range(n_steps):
        pos = jnp.asarray(lengths + s) if per_lane else jnp.int32(bucket + s)
        logits, cache = step(
            params, cache, jnp.asarray(out[-1][:, None], jnp.int32), pos
        )
        out.append(np.argmax(np.asarray(logits), -1))
        caches.append(cache)
    return np.stack(out, 1), caches, max_len


@pytest.mark.parametrize("cache_kind", ["plain", "kv_quant"])
@pytest.mark.parametrize("pos_kind", ["aligned", "per_lane"])
def test_inplace_decode_matches_forward(pos_kind, cache_kind):
    """N greedy decode steps after a prefill pick the tokens the
    full-sequence forward pass picks; each step changes only its own row
    per lane and layer, and the rows hold the forward pass's K/V."""
    cfg = dataclasses.replace(
        smoke_variant(get_config("olmo-1b")),
        dtype="float32", kv_quant=cache_kind == "kv_quant",
    )
    api = model_api.get_api(cfg)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    per_lane = pos_kind == "per_lane"
    lens = (5, 9) if per_lane else (7, 7)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lens]
    n_steps = 6
    gen, caches, max_len = _greedy_decode(
        cfg, api, params, prompts, n_steps, per_lane
    )

    # step s writes row len + s of every lane and layer, and nothing else
    for s in range(n_steps):
        for before, after in zip(caches[s], caches[s + 1]):
            before, after = np.array(before), np.array(after)
            for lane, prompt in enumerate(prompts):
                row = (len(prompt) if per_lane else max(lens)) + s
                before[:, lane, row] = after[:, lane, row]
            np.testing.assert_array_equal(after, before)

    cache = caches[-1]
    for lane, prompt in enumerate(prompts):
        seq = np.concatenate([prompt, gen[lane, :-1]])[None]
        hidden, _, ref = transformer.forward_hidden(
            cfg, params, jnp.asarray(seq), return_cache=True
        )
        logits = np.asarray(hidden[0] @ transformer._unembed_matrix(cfg, params))
        np.testing.assert_array_equal(
            np.argmax(logits[len(prompt) - 1 :], -1), gen[lane]
        )
        n = seq.shape[1]
        if cfg.kv_quant:
            # past layer 0 the decode attends over dequantized K/V and the
            # forward pass over float K/V, so only layer 0's rows match;
            # each side lies within half a step of its own power-of-two
            # grid of the same float row
            for i in (0, 1):
                got, e_got = cache[i][0, lane, :n], cache[i + 2][0, lane, :n]
                want, e_want = ref[i][0, 0], ref[i + 2][0, 0]
                err = np.abs(
                    np.asarray(transformer.kv_dequantize(got, e_got, jnp.float32))
                    - np.asarray(transformer.kv_dequantize(want, e_want, jnp.float32))
                )
                tol = 0.5 * (
                    np.exp2(np.asarray(e_got, np.float32))
                    + np.exp2(np.asarray(e_want, np.float32))
                )[..., None]
                assert np.all(err <= tol + 1e-6), float(np.max(err - tol))
        else:
            for i in (0, 1):
                np.testing.assert_allclose(
                    np.asarray(cache[i][:, lane, :n]), np.asarray(ref[i][:, 0]),
                    rtol=1e-5, atol=1e-5,
                )
