"""The decode programs every served family shares: the scan, the packed
upload, the sampler.

A family is a `models.spec.ModelSpec` (its layers and the layout of what
it caches); the pools' format is `paged_cache`'s. This module owns what
is the same for all of them:

- `paged_decode_step`: one decode step for N sequences at DIFFERENT
  positions against the block pools, composed of the spec's functions.
- `fused_decode_chunk`: k such steps as ONE `lax.scan` on the device
  with sampling and termination in the carry. The host uploads one packed
  int32 array (layout at `PACK_COLS`) and fetches one int32 result.
- `_sample_rows`: the per-row sampler inside the scan. It does only what
  a chunk's rows ask for: the argmax when no row samples, the scaled
  categorical draw when none truncates, one sort when one does.

Batch shape: everything here is shape-polymorphic only in
(N, max_blocks_per_seq, num_blocks). Under the default ragged kernel
the engine pads N to the FIXED max_num_seqs — dead rows cost zero
kernel work (per-row lengths gate every block), so ONE compilation
covers every batch mix and there is no bucket axis at all. The
`kernel="bucketed"` fallback keeps the old power-of-two bucketing
(one compile per bucket) as the parity oracle.

Chunked prefill: prompt tokens ride the same fused scan as decode —
each scan trip feeds a prefilling row one prompt token (KV write, no
sample), and the trip that consumes the last prompt token samples the
request's first output in-scan. Long prompts therefore never
monopolise a step: they are split into k-token chunks admitted
alongside decode slots (scheduler.prefill_chunk_threshold).

No model module is imported here: the (L, H, D, S) tuple of
models/generation.py still names that family's spec (`as_spec`), looked
up when a tuple is given.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ...core.anomaly import rows_not_finite
from ...models.spec import ModelSpec, merge_counts
from .paged_cache import gather_rows, pool_geometry

__all__ = ["gather_block_kv", "paged_decode_step", "fused_decode_chunk",
           "PACK_COLS", "pack_f32", "as_spec"]


def gather_block_kv(pool, block_tables):
    """[num_blocks, bs, H, D] pool (one stored in its logical shape) +
    [N, MB] tables -> [N, H, MB*bs, D] contiguous per-sequence context,
    positions in block-table order: the dense cache's heads-major
    layout."""
    return gather_rows(pool, block_tables).transpose(0, 2, 1, 3)


def as_spec(geom) -> ModelSpec:
    """A ModelSpec as it is; the (L, H, D, S) tuple of models/generation.py
    names that family's spec, so every caller that holds a GPT geometry
    keeps working."""
    if isinstance(geom, ModelSpec):
        return geom
    from ...models.generation import serving_spec
    return serving_spec(tuple(geom))


def _pool_of(spec, pools, kind):
    """One block pool of the first layer of `kind` ("rows", "window"): its
    shape[:2] is the group's (num_blocks, block_size)."""
    layer = pools[spec.layer_caches.index(kind)]
    return jax.tree_util.tree_leaves(layer)[0]


def _window_slots(spec, pools, window_tables, window_firsts, pos, run):
    """What a window layer's call takes in the place of the block table
    and its write slot, for rows whose token is at `pos` [N] (`run` [N]: the
    others' writes are dropped): (slot_blocks in the window group, the
    window tables, att_starts, table_starts)."""
    num_wblocks, block_size = _pool_of(spec, pools, "window").shape[:2]
    entry = jnp.clip(pos // block_size - window_firsts, 0,
                     window_tables.shape[1] - 1)
    slot_blocks = jnp.where(
        run, jnp.take_along_axis(window_tables, entry[:, None], axis=1)[:, 0],
        num_wblocks)
    att_starts = jnp.maximum(pos + 1 - spec.window, 0).astype(jnp.int32)
    return slot_blocks, window_tables, att_starts, window_firsts * block_size


def _layer(spec, params, i, x, pool, slot_blocks, slot_offsets, tables,
           in_window, positions, att_lens, live, ragged, state_slots):
    """Layer i through the spec: a window layer with its own group's write
    slot, table and the two arguments that say where its attention starts
    (`in_window`, from `_window_slots`), every other layer as ever."""
    if spec.window and spec.layer_caches[i] == "window":
        slot_blocks, tables, att_starts, table_starts = in_window
        return spec.decode_layer(
            params, i, x, pool, slot_blocks, slot_offsets, tables,
            positions, att_lens, live, ragged, state_slots, att_starts,
            table_starts)
    return spec.decode_layer(
        params, i, x, pool, slot_blocks, slot_offsets, tables, positions,
        att_lens, live, ragged, state_slots)


def paged_decode_step(params, pools, tokens, positions, block_tables,
                      slot_blocks, slot_offsets, geom, state_slots=None,
                      window_tables=None, window_firsts=None):
    """One ragged decode step over the block pool.

    params: the models.generation.extract_params dict.
    pools: L-tuple of the spec's per-layer leaf: (k_pool, v_pool)
        [num_blocks, bs, H, D], or one latent pool [num_blocks, bs, W].
    tokens [N] int32 — last sampled token per sequence.
    positions [N] int32 — cached length per sequence (the new token's
        position); padded rows use 0.
    block_tables [N, MB] int32 — block ids padded with 0.
    slot_blocks/slot_offsets [N] int32 — write slot for the new token's
        cache row; padded rows point slot_blocks out of bounds
        (num_blocks) so the scatter drops them.
    geom: static ModelSpec, or the (num_layers, num_heads, head_dim,
        max_seq_len) tuple of models.generation (`as_spec`).
    state_slots [N] int32 — where the spec has state layers
        (`ModelSpec.layer_caches`), each sequence's slot in their
        `SeqState` leaves (`PagedKVCache.state_slot`); else None.
    window_tables [N, WB] int32, window_firsts [N] int32 — where the spec
        has window layers, each sequence's window blocks padded with 0 and
        the logical index of the first (`PagedKVCache.window_table`); the
        write slot in the window group is derived from `positions`.

    Returns (logits [N, V], updated pools). Composed of the spec's
    functions — for GPT-2 the shared jitted sub-programs of
    generation.decode_step around the pool's write and gather, see the
    parity contract at `models.generation.serving_spec`.
    """
    spec = as_spec(geom)
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    x = spec.embed(params, tokens, positions)     # [N, 1, C]
    live = jnp.ones(tokens.shape, bool)
    in_window = _window_slots(
        spec, pools, jnp.asarray(window_tables, jnp.int32),
        jnp.asarray(window_firsts, jnp.int32), positions, live) \
        if spec.window else None
    new_pools = []
    for i, pool in enumerate(pools):
        x, pool, _ = _layer(
            spec, params, i, x, pool, slot_blocks, slot_offsets,
            block_tables, in_window, positions, positions + 1, live, False,
            state_slots)
        new_pools.append(pool)
    return spec.head(params, x), tuple(new_pools)


# ----------------------------------- fused k-token decode + prefill chunks
# Packed per-sequence control state, one int32 [N, PACK_COLS + k + MB]
# upload per chunk (column layout below; float fields travel as raw f32
# bits so the whole transfer stays a single dtype-homogeneous array):
#   0 tok        last sampled token (the next step's input)
#   1 pos        next KV write position (== cached length)
#   2 active     1 for live rows, 0 for padding
#   3 out_cnt    tokens generated so far (threads the PRNG fold_in)
#   4 max_out    SamplingParams.max_tokens
#   5 eos        eos_token_id, -1 when unset
#   6 temp       temperature as float32 bits
#   7 top_k      0 = disabled
#   8 top_p      top_p as float32 bits (>=1.0 = disabled)
#   9 seed       per-request PRNG seed (masked to 31 bits)
#   10 pf_feed   prompt tokens to consume this chunk (0 = pure decode row)
#   11 pf_more   1 if prompt remains after this chunk (pf_more=1 implies
#                pf_feed == k: the engine never leaves a mid-chunk gap
#                between the last fed prompt token and the first sample)
#   12..12+k-1   the pf_feed prompt tokens for this chunk (0-padded)
#   12+k..       the block table row [MB]
#   12+k+MB..    only where the spec has window layers: the row's window
#                table [WB] (the blocks that hold its last `window`
#                positions) and ONE column, the logical index of the
#                table's first block
#   last         the row's state slot: ONE more column, and only where the
#                spec has state layers (MB = max_seq_len // block_size)
PACK_COLS = 12


def pack_f32(x) -> int:
    """Host-side helper: float -> raw float32 bits as a python int, for
    the packed control columns above."""
    import numpy as np
    return int(np.float32(x).view(np.int32))


def _truncate(lg, top_ks, top_ps):
    """Mask [N, V] temperature-scaled logits by each row's top-k
    (kth-largest threshold, ties kept, like the host sampler's
    kth = sort(lg)[-top_k]) and nucleus top-p (smallest prefix of the
    descending distribution with cumulative mass >= top_p; the kept set
    is computed with an EXCLUSIVE cumsum so the crossing token stays).
    ONE sort: top-k's mask is monotone, so the descending sort of the
    masked logits is the mask of the descending sort, value for value."""
    vocab = lg.shape[-1]
    use_k = (top_ks > 0)[:, None]
    srt = jnp.sort(lg, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(
        srt, jnp.clip(top_ks - 1, 0, vocab - 1)[:, None], axis=1)
    lg = jnp.where(use_k & (lg < kth), -1e30, lg)
    srt = jnp.where(use_k & (srt < kth), -1e30, srt)
    # top-p: exclusive cumulative mass < top_p keeps the crossing token.
    probs = jax.nn.softmax(srt, axis=-1)
    excl = jnp.cumsum(probs, axis=-1) - probs
    n_keep = jnp.sum(excl < top_ps[:, None], axis=-1)
    pth = jnp.take_along_axis(
        srt, jnp.clip(n_keep - 1, 0, vocab - 1)[:, None], axis=1)
    use_p = (top_ps > 0.0) & (top_ps < 1.0)
    return jnp.where(use_p[:, None] & (lg < pth), -1e30, lg)


def _sample_rows(logits, base_keys, out_cnt, temps, top_ks, top_ps,
                 any_sampled, any_truncated):
    """Per-row sampling over [N, V] logits — the device twin of
    LLMEngine._sample / generation._sampling_rollout: greedy when
    temp<=0, else a categorical draw from the temperature softmax
    restricted by top-k and top-p (`_truncate`), keyed by
    fold_in(seed_key, out_cnt).

    It does only what the rows ask for, under two scalar predicates the
    caller takes from the chunk's control columns: no row samples
    (`any_sampled` false) -> the argmax and nothing else; rows sample
    but none truncates (`any_truncated` false) -> the scaled draw with
    no sort, softmax or cumsum. Inside a branch every row runs the same
    dataflow and jnp.where selects, so a row's token does not depend on
    which branch its neighbours put it in."""
    def greedy(logits):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled(logits):
        keys = jax.vmap(jax.random.fold_in)(base_keys, out_cnt)
        lg = logits.astype(jnp.float32) \
            / jnp.where(temps > 0, temps, 1.0)[:, None]

        def draw(lg):
            return jax.vmap(jax.random.categorical)(keys, lg)

        drawn = lax.cond(any_truncated,
                         lambda lg: draw(_truncate(lg, top_ks, top_ps)),
                         draw, lg).astype(jnp.int32)
        return jnp.where(temps <= 0.0, greedy(logits), drawn)

    return lax.cond(any_sampled, sampled, greedy, logits)


# ptlint: disable=PT-T009  agrees with the committed plan entry
# serving.decode_chunk (donate=[1]); the jaxplan donation gate pins it
@functools.partial(jax.jit, static_argnums=(3, 4, 5), donate_argnums=(1,))
def fused_decode_chunk(params, pools, packed, geom, k, kernel="ragged"):
    """k decode steps for N sequences entirely on device: one lax.scan
    whose body is the paged decode step above plus on-device sampling
    and termination tracking. The host uploads ONE packed int32 array
    (layout at PACK_COLS) and fetches ONE int32 [k+2, N] result:

        rows 0..k-1   sampled token per scan step, -1 where the row was
                      frozen (inactive / already finished / flagged bad)
                      or silently consuming a prompt token (prefill trip)
        row  k        finished mask after the chunk (EOS or max_tokens)
        row  k+1      per-row not-finite flag, latched at the FIRST bad
                      step — the engine's anomaly attribution, computed
                      in-scan so quarantine needs no extra fetch
        rows k+2..    one per name in the spec's `counters` (none for
                      GPT-2): the count, repeated across the row. For the
                      expert family: (token, held expert) pairs multiplied
                      in the chunk, held experts hit summed over trips and
                      layers, the largest load of one expert in one trip

    `geom` (static) is the family's ModelSpec, or the (L, H, D, S) tuple
    of models/generation.py, which names the GPT-2 spec: embedding, each
    layer (cache write, attention through the block table, MLP) and the
    head are the spec's; the carry, prompt feed, sampling, termination,
    upload and fetch are this function's and the same for every family.

    Chunked prefill: rows with pf_feed > 0 spend their first pf_feed
    trips consuming prompt tokens from the feed columns — KV is written
    at the row's position exactly like a decode trip, but no token is
    sampled or emitted. The trip that consumes the LAST prompt token
    (pf_left==1 and pf_more==0) samples the request's first output from
    its logits with fold_in(seed, 0), then the row decodes normally for
    the rest of the chunk. Prefill and decode rows therefore share one
    program and one dispatch — a long prompt never stalls the batch.

    Frozen rows still flow through the fixed-shape body but scatter to
    slot_block=num_blocks (dropped) and keep their carry unchanged, so
    a chunk is bitwise-equivalent to running its live prefix as smaller
    chunks: sampling keys derive from fold_in(seed_key, out_cnt) — a
    function of per-request progress, NOT of chunk geometry — which
    makes token streams invariant under chunk size and under
    preemption/recovery replay (tests pin k-step vs k x 1-step).

    kernel (static): "ragged" (default) routes per-layer attention to
    the pallas ragged paged-attention kernel when the backend supports
    it (ops/pallas/ragged_paged_attention.route_gate) — the pools are
    read through the block table inside the kernel, dead rows cost zero
    work, and the batch is padded to ONE fixed width so a single
    compilation covers every mix. Off-TPU (CPU tier-1) both modes lower
    to the same gather + composed attention built from the shared
    jitted sub-programs, preserving the bitwise-parity contract;
    "bucketed" keeps the power-of-two padded path as the oracle.

    pools (arg 1) is DONATED: the KV carry is updated in place across
    the scan and the input buffers alias the output on TPU, so the k
    cache writes cost no extra copies of the pool. A state layer's
    `SeqState` leaves are pools like the others: in the carry, donated,
    aliased.

    Returns (out [k+2+len(counters), N] int32, updated pools).
    """
    spec = as_spec(geom)
    feed = packed[:, PACK_COLS:PACK_COLS + k].T      # [k, N] prompt feed
    if spec.window:
        # two groups of pools: the table, the write slot and the id that
        # drops a frozen row's write are each group's own
        num_blocks, block_size = _pool_of(spec, pools, "rows").shape[:2]
        mb = spec.max_seq_len // block_size
        at = PACK_COLS + k + mb
        tables = packed[:, PACK_COLS + k:at]
        end = packed.shape[1] - bool(spec.state_layers)
        window_tables, window_firsts = packed[:, at:end - 1], \
            packed[:, end - 1]
        state_slots = packed[:, end] if spec.state_layers else None
    else:
        num_blocks, block_size = pool_geometry(pools)
        if spec.state_layers:
            mb = spec.max_seq_len // block_size
            tables = packed[:, PACK_COLS + k:PACK_COLS + k + mb]
            state_slots = packed[:, PACK_COLS + k + mb]
        else:
            tables, state_slots = packed[:, PACK_COLS + k:], None
    n = packed.shape[0]
    active = packed[:, 2] > 0
    max_out = packed[:, 4]
    eos = packed[:, 5]
    temps = lax.bitcast_convert_type(packed[:, 6], jnp.float32)
    top_ks = packed[:, 7]
    top_ps = lax.bitcast_convert_type(packed[:, 8], jnp.float32)
    base_keys = jax.vmap(jax.random.PRNGKey)(packed[:, 9])
    pf_more = packed[:, 11] > 0
    ragged = kernel == "ragged"
    # what the chunk's rows ask of the sampler: chunk-invariant scalars
    samples = active & (temps > 0)
    any_sampled = jnp.any(samples)
    any_truncated = jnp.any(
        samples & ((top_ks > 0) | ((top_ps > 0) & (top_ps < 1))))

    def body(carry, feed_j):
        pools, tok, pos, out_cnt, finished, bad, pf_left, counts = carry
        run = active & ~finished & ~bad
        prefilling = run & (pf_left > 0)
        last_pf = prefilling & (pf_left == 1) & ~pf_more
        sampling = (run & ~prefilling) | last_pf
        tok_in = jnp.where(prefilling, feed_j, tok)
        blk_idx = jnp.where(run, pos // block_size, 0)
        slot_blocks = jnp.where(
            run,
            jnp.take_along_axis(tables, blk_idx[:, None], axis=1)[:, 0],
            num_blocks)                      # frozen rows: scatter drops
        slot_offsets = pos % block_size
        x = spec.embed(params, tok_in, pos)
        att_lens = jnp.where(run, pos + 1, 0).astype(jnp.int32)
        # slot j of a chunk is at position + j in BOTH tables
        in_window = _window_slots(spec, pools, window_tables, window_firsts,
                                  pos, run) if spec.window else None
        new_pools = []
        for i, pool in enumerate(pools):
            x, pool, layer_counts = _layer(
                spec, params, i, x, pool, slot_blocks, slot_offsets, tables,
                in_window, pos, att_lens, run, ragged, state_slots)
            new_pools.append(pool)
            if spec.counters:
                counts = merge_counts(counts, layer_counts)
        logits = spec.head(params, x)
        row_bad = rows_not_finite(logits) & run
        bad = bad | row_bad
        tok_new = _sample_rows(logits, base_keys, out_cnt, temps, top_ks,
                               top_ps, any_sampled, any_truncated)
        ok = run & ~row_bad
        step_ok = ok & sampling
        emit = jnp.where(step_ok, tok_new, -1)
        finished = finished | (step_ok & ((tok_new == eos)
                                          | (out_cnt + 1 >= max_out)))
        tok = jnp.where(step_ok, tok_new, tok)
        pos = jnp.where(ok, pos + 1, pos)
        out_cnt = jnp.where(step_ok, out_cnt + 1, out_cnt)
        pf_left = jnp.where(ok & prefilling, pf_left - 1, pf_left)
        return (tuple(new_pools), tok, pos, out_cnt, finished, bad,
                pf_left, counts), emit

    carry0 = (pools, packed[:, 0], packed[:, 1], packed[:, 3],
              jnp.zeros((n,), bool), jnp.zeros((n,), bool),
              packed[:, 10],
              jnp.zeros((len(spec.counters),), jnp.int32)
              if spec.counters else ())
    (pools, _, _, _, finished, bad, _, counts), toks = lax.scan(
        body, carry0, feed, length=k)
    out = jnp.concatenate(
        [toks.astype(jnp.int32),
         finished[None].astype(jnp.int32),
         bad[None].astype(jnp.int32)]
        + [jnp.broadcast_to(counts[j], (1, n))
           for j in range(len(spec.counters))], axis=0)
    return out, pools
