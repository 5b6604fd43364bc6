"""Autoregressive generation with a KV cache for models.gpt.GPT.

Reference-era Paddle served decoding through fluid inference programs
(beam_search/while ops); the TPU-native design is a PURE-JAX decode pair
— `prefill` (one full forward that also returns per-layer K/V) and
`decode_step` (single-token forward against the cache, updated with
`lax.dynamic_update_slice`) — scanned under jit with STATIC shapes:
the cache is an L-tuple of (k, v) [B, H, max_seq, D] buffers from the
start (per-layer leaves so updates alias in place — see `prefill`),
positions past `cur_len` masked, so one compilation serves every
prompt/output length.

The decode math mirrors GPT.forward exactly (pre-LN blocks, tanh-gelu
MLP, 1/sqrt(D) attention scale, tied layout conventions); parity with
the Layer forward is asserted in tests/test_generation.py, so the two
implementations cannot drift silently.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..ops.pallas import ragged_paged_attention as _ragged
from .spec import ModelSpec

__all__ = ["extract_params", "prefill", "decode_step", "generate",
           "beam_search_generate", "serving_spec"]


def extract_params(model) -> dict:
    """GPT Layer → flat {name: jnp array} pytree for the decode fns."""
    return {k: p._value for k, p in model.named_parameters()}


def _ln(x, w, b, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _gelu(x):
    # constants pinned to x.dtype: a bare numpy float64 scalar would
    # promote everything under this package's x64 default
    c0 = jnp.asarray(np.sqrt(2.0 / np.pi), x.dtype)
    c1 = jnp.asarray(0.044715, x.dtype)
    half = jnp.asarray(0.5, x.dtype)
    one = jnp.asarray(1.0, x.dtype)
    return half * x * (one + jnp.tanh(c0 * (x + c1 * x ** 3)))


def _qkv_proj(p, i, x, geom):
    """ln1 + fused qkv projection → [3, B, H, t, D] (computed ONCE per
    layer per step; both the cache write and the attention consume it)."""
    _, H, D, _ = geom
    pre = f"blocks.{i}."
    h = _ln(x, p[pre + "ln1.weight"], p[pre + "ln1.bias"])
    qkv = h @ p[pre + "attn.qkv.weight"] + p[pre + "attn.qkv.bias"]
    B, t = x.shape[0], x.shape[1]
    return qkv.reshape(B, t, 3, H, D).transpose(2, 0, 3, 1, 4)


def _block(p, i, x, q, k_cache, v_cache, pos_mask, geom):
    """One pre-LN block over x [B, t, H*D]: attention of the precomputed
    q [B, H, t, D] against the cache, then the MLP.
    k_cache/v_cache: [B, H, S, D]; pos_mask True=attend — [t, S] shared
    across the batch (dense decode) or [B, 1, t, S] per-sequence (the
    ragged paged-attention path, `serving_spec`)."""
    _, H, D, _ = geom
    pre = f"blocks.{i}."
    B, t = x.shape[0], x.shape[1]
    scores = jnp.einsum("bhtd,bhsd->bhts", q, k_cache) \
        * jnp.asarray(1.0 / np.sqrt(D), q.dtype)
    mask = pos_mask if pos_mask.ndim == 4 else pos_mask[None, None]
    scores = jnp.where(mask, scores,
                       jnp.asarray(-1e30, scores.dtype))
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("bhts,bhsd->bhtd", probs, v_cache)
    return _attn_merge(p, i, x, att, geom)


def _attn_merge(p, i, x, att, geom):
    """Post-attention half of _block: heads-major att [B, H, t, D] →
    out-projection residual, then the MLP. Split out so attention-kernel
    substitutes (the TPU ragged paged-attention kernel,
    ops/pallas/ragged_paged_attention.py) can replace only the
    score/softmax math and reuse this half verbatim; _block calling
    through it traces to the identical jaxpr as the inline form."""
    _, H, D, _ = geom
    pre = f"blocks.{i}."
    B, t = x.shape[0], x.shape[1]
    att = att.transpose(0, 2, 1, 3).reshape(B, t, H * D)
    x = x + att @ p[pre + "attn.out.weight"] + p[pre + "attn.out.bias"]
    h = _ln(x, p[pre + "ln2.weight"], p[pre + "ln2.bias"])
    h = _gelu(h @ p[pre + "mlp.up.weight"] + p[pre + "mlp.up.bias"])
    x = x + h @ p[pre + "mlp.down.weight"] + p[pre + "mlp.down.bias"]
    return x


def _embed(p, ids, pos0):
    tok = p["wte.weight"][ids]                        # [B, t, H]
    t = ids.shape[1]
    pos = p["wpe.weight"][pos0 + jnp.arange(t)]       # [t, H]
    return tok + pos[None]


@functools.partial(jax.jit, static_argnums=(2,))
def prefill(params, input_ids, geom):
    """Full forward over the prompt; returns (last-position logits,
    cache: L-tuple of (k [B, H, max_seq, D], v)). geom: hashable static
    geometry (num_layers, num_heads, head_dim, max_seq_len).

    The cache is a PER-LAYER pytree, not one [L, 2, B, H, S, D] array:
    with a monolithic buffer every layer's `.at[i].set` in decode_step
    rewrote the whole cache — L full-cache copies per token, measured as
    flat ~1.7k tok/s decode from bs=32 to bs=64 (batch-independent =
    bandwidth burned on copies). Leaf-wise, each layer touches only its
    own k/v buffers and the scan carry aliases in place."""
    L, H, D, S = geom
    B, T = input_ids.shape
    x = _embed(params, input_ids, 0)
    causal = (jnp.arange(T)[:, None] >= jnp.arange(S)[None, :]) & \
        (jnp.arange(S)[None, :] < T)
    cache = []
    for i in range(L):
        # one ln1+qkv projection per layer: the cache write AND the
        # attention both consume it
        qkv = _qkv_proj(params, i, x, geom)
        kc = jnp.zeros((B, H, S, D), x.dtype).at[:, :, :T].set(qkv[1])
        vc = jnp.zeros((B, H, S, D), x.dtype).at[:, :, :T].set(qkv[2])
        cache.append((kc, vc))
        x = _block(params, i, x, qkv[0], kc, vc, causal, geom)
    x = _ln(x, params["ln_f.weight"], params["ln_f.bias"])
    logits = x[:, -1] @ params["lm_head.weight"]
    return logits, tuple(cache)


# --------------------------------------------------------------------------
# The decode step is DECOMPOSED into top-level jitted sub-programs shared
# with the paged serving path (`serving_spec` below): embed,
# per-layer qkv, per-layer attention+MLP, final head. Two monolithic jits
# (dense decode_step vs paged decode) fuse differently and drift by ~1e-7
# per step (measured on the CPU backend); routing BOTH paths through the
# SAME compiled executables makes paged decode bitwise-identical to the
# dense path by construction — positions beyond a sequence's length are
# masked to -1e30 before softmax, so cache garbage is erased exactly.
# Under an enclosing jit (the generate()/beam rollout scans, jax.export)
# these sub-jits inline and fuse into one program, exactly as before.

@jax.jit
def _token_embed(params, tokens, positions):
    """Per-row embedding: tokens [B] at per-sequence positions [B] ->
    [B, 1, C]. Same gather+add as _embed at a shared scalar position."""
    return params["wte.weight"][tokens[:, None]] \
        + params["wpe.weight"][positions][:, None]


@functools.partial(jax.jit, static_argnums=(1, 3))
def _decode_qkv(params, i, x, geom):
    return _qkv_proj(params, i, x, geom)


# ptlint: disable=PT-T009  agrees with the committed plan entry
# decode.cache_write (donate=[0, 1]); the jaxplan donation gate pins it
@functools.partial(jax.jit, donate_argnums=(0, 1))
def _cache_write(kc, vc, k_new, v_new, pos):
    """Write the new token's K/V [B, H, 1, D] at position pos (scalar)
    of the dense [B, H, S, D] cache.

    kc/vc are DONATED: every caller rebinds its cache to the returned
    pair (decode_step's per-layer loop, DecoderPredictor), so XLA can
    update the [B, H, S, D] buffers in place instead of double-residing
    old+new cache per layer per token. Under an enclosing jit (the
    generate()/beam rollout scans) donation of this inner program is
    ignored and the scan carry aliasing takes over — same effect."""
    z = jnp.asarray(0, pos.dtype)
    return (jax.lax.dynamic_update_slice(kc, k_new, (z, z, pos, z)),
            jax.lax.dynamic_update_slice(vc, v_new, (z, z, pos, z)))


@functools.partial(jax.jit, static_argnums=(1, 7))
def _decode_attn(params, i, x, q, kc, vc, positions, geom):
    """One block over the (dense-layout) context [B, H, S, D], attending
    row b to positions <= positions[b]."""
    S = kc.shape[2]
    attend = (jnp.arange(S)[None, :]
              <= positions[:, None])[:, None, None, :]  # [B, 1, 1, S]
    return _block(params, i, x, q, kc, vc, attend, geom)


@jax.jit
def _decode_head(params, x):
    x = _ln(x, params["ln_f.weight"], params["ln_f.bias"])
    return x[:, 0] @ params["lm_head.weight"]


def decode_step(params, cache, token, pos, geom):
    """One cached decode step. cache: the per-layer pytree from
    `prefill`; token [B], pos scalar (int32). Returns (logits [B, V],
    updated cache). Composed of the shared jitted sub-programs above;
    call it under jax.jit (as the generate()/beam scans do) to fuse the
    whole step into one program."""
    token = jnp.asarray(token, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    positions = jnp.broadcast_to(pos, token.shape)
    x = _token_embed(params, token, positions)        # [B, 1, H]
    new_cache = []
    for i, (kc, vc) in enumerate(cache):
        qkv = _decode_qkv(params, i, x, geom)         # once per layer
        kc, vc = _cache_write(kc, vc, qkv[1], qkv[2], pos)
        new_cache.append((kc, vc))
        x = _decode_attn(params, i, x, qkv[0], kc, vc, positions, geom)
    return _decode_head(params, x), tuple(new_cache)


@functools.lru_cache(maxsize=None)
def serving_spec(geom) -> ModelSpec:
    """This family as the `ModelSpec` `LLMEngine` serves it through, the
    first one: geom is the static geometry (num_layers, num_heads,
    head_dim, max_seq_len), and the (L, H, D, S) tuple names this spec
    wherever the serving layer takes a spec (`serving.attention.as_spec`).

    Parity contract: the math is NOT re-implemented. Embedding, per-layer
    qkv, the attention block and the LM head are the SAME top-level jitted
    sub-programs `decode_step` is composed of (_token_embed, _decode_qkv,
    _decode_attn, _decode_head); between them a layer writes the new
    token's K/V into the pool and reads the context back through the block
    table (`paged_cache.write_rows` / `gather_rows`). When
    max_blocks_per_seq * block_size == max_seq_len the gathered context,
    transposed to heads-major, has the exact dense cache layout (position
    p = block p // bs, slot p % bs) and the same shape, so XLA reuses the
    identical compiled executables for both paths; since out-of-length
    positions are masked to -1e30 before softmax (erasing pool garbage
    exactly: masked probs are exact zeros), the logits are
    bitwise-identical to `decode_step` (tests/test_serving.py pins this).
    Padded rows write out of bounds (dropped) and attend only to
    block-table padding that their mask erases; their logits are garbage
    and the engine ignores them. Under `ragged` on a backend that has the
    kernel (ops/pallas/ragged_paged_attention.route_gate) the pools are
    read through the block table inside the kernel and no context is
    gathered; off-TPU, and on a pool stored as one flat row a position
    (4 heads x 128, 2 x 256: `paged_cache.physical_shape`), both modes
    lower to the gather + composed attention.
    tests/test_serving_spec.py holds the three programs' names and
    argument counts."""
    # at call time: the serving package imports this module
    from ..inference.serving.paged_cache import gather_rows, write_rows
    num_layers, num_heads, head_dim, max_seq = geom

    def decode_layer(params, i, x, pool, slot_blocks, slot_offsets, tables,
                     positions, att_lens, live, ragged, state_slots=None,
                     att_starts=None, table_starts=None):
        kp, vp = pool
        qkv = _decode_qkv(params, i, x, geom)     # [3, N, H, 1, D]
        kp = write_rows(kp, qkv[1][:, :, 0], slot_blocks, slot_offsets)
        vp = write_rows(vp, qkv[2][:, :, 0], slot_blocks, slot_offsets)
        # the kernel's tile is a stored block [bs, G, L]; a pool stored as
        # ONE row of H * D lanes a position (`physical_shape`: at most 4
        # heads of whole lane rows) has no such tile and takes the gather
        if ragged and kp.ndim == 4 \
                and _ragged.route_gate(head_dim, num_heads, kp.shape[1:]):
            att = _ragged.ragged_decode_attention(
                qkv[0][:, :, 0, :], kp, vp, tables, att_lens)
            x = _attn_merge(params, i, x, att[:, :, None, :], geom)
        else:
            # heads-major [N, H, S, D]: the dense cache _decode_attn takes
            kc, vc = (gather_rows(p, tables, (num_heads, head_dim))
                      .transpose(0, 2, 1, 3) for p in (kp, vp))
            x = _decode_attn(params, i, x, qkv[0], kc, vc, positions, geom)
        return x, (kp, vp), None

    return ModelSpec(
        family="gpt2", num_layers=num_layers, max_seq_len=max_seq,
        cache_layout="heads", cache_shape=(num_heads, head_dim),
        cache_dtype="float32", embed=_token_embed,
        decode_layer=decode_layer, head=_decode_head,
        prefill=lambda params, ids: prefill(params, ids, geom) + (None,),
        config=geom)


@functools.lru_cache(maxsize=32)
def _sampling_rollout(geom, max_new: int, temperature: float, top_k: int,
                      top_p: float = 1.0, eos: int = -1):
    """One jitted (prefill + decode scan) program per static config.

    generate() used to run its lax.scan eagerly with per-call closures;
    each call re-traced, re-lowered and re-compiled the whole 12-layer
    rollout (~8.5 s host time per WARM call on the bench box, vs 0.15 ms
    for a cached decode_step — measured before this factory existed).
    Caching the jitted program by its static knobs makes warm generate
    calls pure device time.

    top_p >= 1.0 compiles the EXACT plain-temperature program (the
    nucleus mask is dropped at trace time), so top_p=1.0 is bitwise
    identical to not passing it. eos >= 0 adds a per-row finished flag
    to the scan carry: finished rows emit eos forever; shapes stay
    static, the scan still runs all max_new steps."""

    def run(params, ids, key):
        T = ids.shape[1]
        B = ids.shape[0]
        logits, cache = prefill(params, ids, geom)

        def sample(logits, key):
            if temperature <= 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            lg = logits.astype(jnp.float32) / temperature
            if top_k:
                kth = jnp.sort(lg, axis=-1)[:, -top_k][:, None]
                lg = jnp.where(lg < kth, -1e30, lg)
            if 0.0 < top_p < 1.0:
                # nucleus: keep the smallest rank-prefix whose mass
                # reaches top_p (rank 0 always kept — exclusive cumsum)
                srt = jnp.sort(lg, axis=-1)[:, ::-1]
                probs = jax.nn.softmax(srt, axis=-1)
                excl = jnp.cumsum(probs, axis=-1) - probs
                n_keep = jnp.sum(excl < top_p, axis=-1)
                kth = jnp.take_along_axis(srt, (n_keep - 1)[:, None],
                                          axis=-1)
                lg = jnp.where(lg < kth, -1e30, lg)
            return jax.random.categorical(key, lg, axis=-1).astype(
                jnp.int32)

        def body(carry, _):
            logits, cache, pos, key, finished = carry
            key, sub = jax.random.split(key)
            tok = sample(logits, sub)
            if eos >= 0:
                tok = jnp.where(finished, jnp.asarray(eos, tok.dtype),
                                tok)
                finished = finished | (tok == eos)
            logits, cache = decode_step(params, cache, tok, pos, geom)
            return (logits, cache, pos + 1, key, finished), tok

        carry0 = (logits, cache, jnp.asarray(T, jnp.int32), key,
                  jnp.zeros((B,), bool))
        _, toks = jax.lax.scan(body, carry0, None, length=max_new)
        return toks

    return jax.jit(run)


def generate(model, input_ids, max_new_tokens: int,
             temperature: float = 0.0, top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             eos_token_id: Optional[int] = None, seed: int = 0):
    """Autoregressive sampling: greedy at temperature 0, else
    temperature(+top-k/top-p) sampling. eos_token_id stops finished rows
    early: once a row samples eos, every later position is frozen to eos
    (masked inside the jitted scan — shapes stay static). input_ids:
    [B, T] array-like; returns np.ndarray [B, T + max_new_tokens]."""
    from ..core.tensor import Tensor
    cfg = model.cfg
    geom = (cfg.num_layers, cfg.num_heads,
            cfg.hidden_size // cfg.num_heads, cfg.max_seq_len)
    params = extract_params(model)
    ids = np.asarray(input_ids.numpy() if isinstance(input_ids, Tensor)
                     else input_ids)
    B, T = ids.shape
    if T + max_new_tokens > cfg.max_seq_len:
        raise ValueError(
            f"prompt {T} + new {max_new_tokens} exceeds max_seq_len "
            f"{cfg.max_seq_len}")
    fn = _sampling_rollout(geom, int(max_new_tokens), float(temperature),
                           int(top_k) if top_k else 0,
                           float(top_p) if top_p is not None else 1.0,
                           -1 if eos_token_id is None else int(eos_token_id))
    toks = fn(params, jnp.asarray(ids, jnp.int32),
              jax.random.PRNGKey(seed))
    return np.concatenate([ids, np.asarray(toks).T], axis=1)


@functools.lru_cache(maxsize=32)
def _beam_rollout(geom, max_new: int, K: int, V: int, eos: int):
    """Jitted beam-search rollout per static (geometry, beam, vocab,
    eos) config — same per-call retrace fix as _sampling_rollout."""

    def run(params, expanded_ids):
        BK, T = expanded_ids.shape
        B = BK // K
        logits, cache = prefill(params, expanded_ids, geom)
        # only beam 0 is live at step 0 (all beams hold the same prompt)
        scores0 = jnp.tile(jnp.asarray([0.0] + [-1e30] * (K - 1),
                                       jnp.float32)[None], (B, 1))

        def body(carry, _):
            logits, cache, scores, finished, lengths, pos = carry
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            logp = logp.reshape(B, K, V)
            if eos >= 0:
                # finished beams may only emit eos, at zero marginal cost
                only_eos = jnp.full((V,), -jnp.inf).at[eos].set(0.0)
                logp = jnp.where(finished[..., None],
                                 only_eos[None, None], logp)
            total = scores[..., None] + logp          # [B, K, V]
            flat = total.reshape(B, K * V)
            top_scores, top_idx = jax.lax.top_k(flat, K)   # [B, K]
            parent = top_idx // V
            token = (top_idx % V).astype(jnp.int32)
            brow = jnp.arange(B)[:, None]
            was_finished = finished[brow, parent]
            new_lengths = lengths[brow, parent] + (~was_finished).astype(
                lengths.dtype)  # frozen beams stop accruing length
            new_finished = was_finished
            if eos >= 0:
                new_finished = new_finished | (token == eos)
            # re-gather beams: cache batch dim is B*K, parents per-batch
            gidx = (brow * K + parent).reshape(-1)
            cache = jax.tree_util.tree_map(lambda a: a[gidx], cache)
            logits, cache = decode_step(params, cache, token.reshape(-1),
                                        pos, geom)
            return ((logits, cache, top_scores, new_finished,
                     new_lengths, pos + 1), (parent, token))

        finished0 = jnp.zeros((B, K), bool)
        lengths0 = jnp.full((B, K), T, jnp.float32)
        carry0 = (logits, cache, scores0, finished0, lengths0,
                  jnp.asarray(T, jnp.int32))
        (_, _, scores, _, lengths, _), (parents, tokens) = jax.lax.scan(
            body, carry0, None, length=max_new)
        return scores, lengths, parents, tokens

    return jax.jit(run)


def beam_search_generate(model, input_ids, beam_size: int,
                         max_new_tokens: int, length_penalty: float = 0.0,
                         eos_token_id: Optional[int] = None):
    """Beam search over the KV cache (reference: beam_search_op.cc +
    beam_search_decode_op.cc — the fluid decoding workhorse; here the
    beams live as an expanded batch dim, the cache is re-gathered to the
    surviving parents each step, and the token history is backtracked
    through the recorded (parent, token) lattice like the reference's
    sentence-ids/sentence-scores reconstruction).

    Returns (sequences [B, T + max_new_tokens], scores [B]) for the best
    beam per batch row; finished beams (eos emitted) freeze their score.
    """
    from ..core.tensor import Tensor
    cfg = model.cfg
    geom = (cfg.num_layers, cfg.num_heads,
            cfg.hidden_size // cfg.num_heads, cfg.max_seq_len)
    params = extract_params(model)
    ids = np.asarray(input_ids.numpy() if isinstance(input_ids, Tensor)
                     else input_ids)
    B, T = ids.shape
    K, V = int(beam_size), cfg.vocab_size
    if T + max_new_tokens > cfg.max_seq_len:
        raise ValueError("beam search exceeds max_seq_len")

    expanded = np.repeat(ids, K, axis=0)              # [B*K, T]
    eos = -1 if eos_token_id is None else int(eos_token_id)
    fn = _beam_rollout(geom, int(max_new_tokens), K, V, eos)
    scores, lengths, parents, tokens = (
        np.asarray(a) for a in fn(params,
                                  jnp.asarray(expanded, jnp.int32)))
    # parents/tokens: [steps, B, K]; scores/lengths: [B, K]

    if length_penalty:
        # per-HYPOTHESIS length normalization (reference beam_search_op):
        # beams that emitted eos early divide by their own shorter length
        scores = scores / (lengths ** length_penalty)
    best = scores.argmax(axis=1)                      # [B]
    # backtrack the (parent, token) lattice from the best leaf
    out = np.zeros((B, max_new_tokens), np.int64)
    for b in range(B):
        k = best[b]
        for s in range(max_new_tokens - 1, -1, -1):
            out[b, s] = tokens[s, b, k]
            k = parents[s, b, k]
    return np.concatenate([ids, out], axis=1), scores[np.arange(B), best]


def export_decoder(model, path_prefix: str):
    """Serialize the decode pair as StableHLO (jax.export) so a server
    can run autoregressive generation WITHOUT the model class or Python
    graph rebuild — the LLM-serving analogue of save_inference_model.
    Writes <prefix>.prefill.pdmodel, <prefix>.decode.pdmodel and
    <prefix>.pdmeta (geometry + param tree layout; parameters are baked
    into the artifacts as constants)."""
    import json
    import os
    from jax import export as jexport
    cfg = model.cfg
    geom = (cfg.num_layers, cfg.num_heads,
            cfg.hidden_size // cfg.num_heads, cfg.max_seq_len)
    L, H, D, S = geom
    params = extract_params(model)

    def prefill_fn(ids):
        return prefill(params, ids, geom)

    def decode_fn(cache, token, pos):
        return decode_step(params, cache, token, pos, geom)

    # symbolic batch, static seq buckets: export one prompt length (S//2
    # by convention) for prefill; decode is length-independent
    Tp = S // 2
    b = jexport.symbolic_shape("b")[0]
    ids_spec = jax.ShapeDtypeStruct((b, Tp), jnp.int32)
    # ptlint: disable=PT-T004  (export path: jit built once per
    # export_decoder() call, traced on specs, never dispatched)
    ex_prefill = jexport.export(jax.jit(prefill_fn))(ids_spec)
    leaf = jax.ShapeDtypeStruct((b, H, S, D), jnp.float32)
    cache_spec = tuple((leaf, leaf) for _ in range(L))
    tok_spec = jax.ShapeDtypeStruct((b,), jnp.int32)
    pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
    # ptlint: disable=PT-T004  (same export-only jit as above)
    ex_decode = jexport.export(jax.jit(decode_fn))(cache_spec, tok_spec,
                                                   pos_spec)
    d = os.path.dirname(path_prefix)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path_prefix + ".prefill.pdmodel", "wb") as f:
        f.write(ex_prefill.serialize())
    with open(path_prefix + ".decode.pdmodel", "wb") as f:
        f.write(ex_decode.serialize())
    with open(path_prefix + ".pdmeta", "w") as f:
        # JSON, not pickle: serving artifacts may come from third parties
        # and must not be able to execute code at load (same rule as the
        # p2p raw-buffer framing)
        json.dump({"geom": list(geom), "prefill_len": Tp,
                   "vocab_size": cfg.vocab_size}, f)


class DecoderPredictor:
    """Serves an export_decoder artifact: greedy generation from
    serialized StableHLO only (no model class). The rollout is
    device-resident: a jitted lax.scan feeds each argmax token straight
    back into the exported decode program, so the whole generation is
    ONE dispatch + ONE host fetch regardless of max_new_tokens (the
    exported artifact composes under tracing — exported.call is itself
    traceable)."""

    def __init__(self, path_prefix: str):
        import json
        from jax import export as jexport
        with open(path_prefix + ".prefill.pdmodel", "rb") as f:
            self._prefill = jexport.deserialize(f.read())
        with open(path_prefix + ".decode.pdmodel", "rb") as f:
            self._decode = jexport.deserialize(f.read())
        with open(path_prefix + ".pdmeta") as f:
            meta = json.load(f)  # JSON: no code execution at load
        self.geom = tuple(meta["geom"])
        self.prefill_len = int(meta["prefill_len"])
        self.vocab_size = int(meta["vocab_size"])
        self._rollouts = {}                  # max_new -> jitted scan

    def _rollout(self, max_new: int):
        """One jitted greedy rollout per max_new (memoized — same
        build-once discipline as _sampling_rollout's lru_cache, keyed
        per instance because the scan closes over this artifact's
        decode program)."""
        fn = self._rollouts.get(max_new)
        if fn is None:
            decode = self._decode

            def run(logits, cache, pos0):
                def body(carry, _):
                    logits, cache, pos = carry
                    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    logits, cache = decode.call(cache, tok, pos)
                    return (logits, cache, pos + 1), tok

                _, toks = jax.lax.scan(body, (logits, cache, pos0),
                                       None, length=max_new)
                return toks                  # [max_new, B]

            # ptlint: disable=PT-T004  (memoized above: built once per
            # (artifact, max_new), never per generate() call)
            fn = jax.jit(run)
            self._rollouts[max_new] = fn
        return fn

    def generate(self, input_ids, max_new_tokens: int):
        """Greedy decode. Prompts must be EXACTLY the exported prefill
        length: the fixed-shape prefill has no pad masking, so a shorter
        prompt would silently attend pad tokens at shifted positions and
        diverge from generate() — a loud error beats silent divergence.
        (Serve multiple buckets by exporting one artifact per length.)"""
        ids = np.asarray(input_ids)
        B, T = ids.shape
        Tp = self.prefill_len
        if T != Tp:
            raise ValueError(
                f"prompt length {T} != exported prefill length {Tp}; the "
                "fixed-shape prefill has no pad masking — export an "
                "artifact per prompt-length bucket")
        S = self.geom[3]
        if Tp + max_new_tokens > S:
            raise ValueError("generation exceeds max_seq_len")
        logits, cache = self._prefill.call(jnp.asarray(ids, jnp.int32))
        toks = self._rollout(max_new_tokens)(
            logits, cache, jnp.asarray(Tp, jnp.int32))
        # one fetch for the whole generation (the pre-device-resident
        # loop synced once per token — ptlint PT-T007's defect class)
        return np.concatenate([ids, np.asarray(toks).T], axis=1)
