"""The shape a KV pool is STORED in (`paged_cache.physical_shape`): the rule,
and that nothing outside the format's owner can tell. Rows go in and
contexts come out in the logical per-position shape, bit for bit, on a
pool that packs two heads a lane row, on one padded to whole lane rows and
on one stored as it is; the ragged kernel reads the packed pool in place;
the host-side block operations carry whatever trails the block axis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.serving import (EngineConfig, LLMEngine,
                                          SamplingParams)
from paddle_tpu.inference.serving.paged_cache import (PagedKVCache,
                                                      gather_rows,
                                                      physical_shape,
                                                      write_rows)
from paddle_tpu.ops.pallas import ragged_paged_attention as rpa


@pytest.mark.parametrize("cache_shape, dtype, stored", [
    ((16, 64), jnp.float32, (8, 128)),     # GPT-2 medium: two heads a row
    ((16, 64), jnp.bfloat16, (8, 128)),
    ((8, 128), jnp.float32, (8, 128)),     # already whole tiles
    ((12, 64), jnp.float32, (6, 128)),     # 6 lane rows: two heads a row
    ((6, 128), jnp.float32, (6, 128)),
    ((2, 16), jnp.float32, (2, 16)),       # the toy sizes of the CPU tests
    ((2, 256), jnp.bfloat16, (512,)),      # 2 KV heads of 256: one row of
    ((2, 256), jnp.float32, (512,)),       # 512 lanes, no sublane padding
    ((4, 128), jnp.bfloat16, (512,)),
    ((576,), jnp.bfloat16, (640,)),        # the latent row, to lane rows
    ((512,), jnp.bfloat16, (512,)),
    ((24,), jnp.float32, (24,)),           # under one lane row: as it is
])
def test_the_stored_shape_is_decided_from_the_logical_one(cache_shape, dtype,
                                                          stored):
    assert physical_shape(cache_shape) == stored
    pc = PagedKVCache(2, cache_shape, num_blocks=3, block_size=8,
                      dtype=dtype)
    assert pc.cache_shape == cache_shape and pc.stored_shape == stored
    leaves = jax.tree_util.tree_leaves(pc.pools)
    assert len(leaves) == 2 * (2 if len(cache_shape) == 2 else 1)
    assert all(a.shape == (3, 8) + stored and a.dtype == dtype
               for a in leaves)


_SHAPES = [(16, 64), (576,), (130,), (2, 16), (12,), (2, 256)]
_IDS = ["packed", "padded-576", "padded-130", "heads-logical",
        "latent-logical", "heads-flat"]


@pytest.mark.parametrize("cache_shape", _SHAPES, ids=_IDS)
def test_rows_written_are_the_rows_gathered(cache_shape):
    """`write_rows` then `gather_rows`: logical rows in, logical contexts
    out, equal to a numpy loop over a logical pool to the bit; the row
    whose block id is out of range is dropped; a latent pool's padding
    stays zero."""
    rng = np.random.default_rng(sum(cache_shape))
    nb, bs, n = 6, 4, 3
    stored = physical_shape(cache_shape)
    pool = jnp.zeros((nb, bs) + stored, jnp.float32)
    rows = rng.normal(size=(n,) + cache_shape).astype(np.float32)
    slot_blocks = np.asarray([5, nb, 2], np.int32)      # row 1: dropped
    slot_offsets = np.asarray([3, 1, 0], np.int32)
    tables = np.asarray([[5, 0], [1, 1], [2, 4]], np.int32)
    want = np.zeros((nb, bs) + cache_shape, np.float32)
    for r in (0, 2):
        want[slot_blocks[r], slot_offsets[r]] = rows[r]
    got = write_rows(pool, jnp.asarray(rows), slot_blocks, slot_offsets)
    assert got.shape == pool.shape
    ctx = np.asarray(gather_rows(got, jnp.asarray(tables), cache_shape))
    assert ctx.shape == (n, 2 * bs) + cache_shape
    np.testing.assert_array_equal(
        ctx, np.stack([np.concatenate([want[b] for b in tables[r]])
                       for r in range(n)]))
    if len(cache_shape) == 1:
        assert not np.asarray(got)[..., cache_shape[0]:].any()


@pytest.mark.parametrize("cache_shape", _SHAPES, ids=_IDS)
def test_a_prefill_scattered_is_the_prefill_gathered(cache_shape):
    """`write_prefill` then `gather_rows` returns the dense prefill rows
    bit for bit, whatever shape the pool is stored in, and touches no block
    of another sequence."""
    rng = np.random.default_rng(sum(cache_shape) + 1)
    layers, bs, seq, length = 2, 4, 24, 9
    heads = len(cache_shape) == 2

    def dense():
        shape = (2, cache_shape[0], seq, cache_shape[1]) if heads \
            else (2, seq) + cache_shape
        a = rng.normal(size=shape).astype(np.float32)
        a[..., length:, :] = 0.0
        return jnp.asarray(a)
    cache = tuple((dense(), dense()) if heads else dense()
                  for _ in range(layers))
    pc = PagedKVCache(layers, cache_shape, num_blocks=8, block_size=bs)
    pc.allocate("other", 2 * bs)
    pc.pools = jax.tree_util.tree_map(lambda p: p + 1.0, pc.pools)
    pc.allocate("s", length)
    pc.write_prefill("s", cache, length, batch_index=1)
    table = jnp.asarray([pc.block_table("s")], jnp.int32)
    for pool, d in zip(jax.tree_util.tree_leaves(pc.pools),
                       jax.tree_util.tree_leaves(cache)):
        got = np.asarray(gather_rows(pool, table, cache_shape))[0]
        row = np.asarray(d[1])
        want = row.transpose(1, 0, 2) if heads else row
        np.testing.assert_array_equal(got, want[:got.shape[0]])
        other = np.asarray(pool)[np.asarray(pc.block_table("other"))]
        assert (other[..., :physical_shape(cache_shape)[-1]] == 1.0).all()


def _packed_case(sentinel, dtype=np.float32):
    """Pools of 16 heads x 64 stored as [8, 128] beside the same values in
    the logical shape: a dead row, a one-token row, rows that end mid-block,
    one that fills its table; table entries past a row's blocks hold
    `sentinel`."""
    rng = np.random.RandomState(3)
    n, nb, bs, h, d, mb = 5, 24, 8, 16, 64, 4
    k = rng.randn(nb, bs, h, d).astype(dtype)
    v = rng.randn(nb, bs, h, d).astype(dtype)
    q = rng.randn(n, h, d).astype(np.float32)
    lengths = np.array([0, 1, bs * mb, 7, bs * 2 + 3], np.int32)
    perm = rng.permutation(nb)
    tables = np.full((n, mb), nb if sentinel == "num_blocks" else -1,
                     np.int32)
    used = 0
    for i in range(n):
        need = -(-int(lengths[i]) // bs)
        tables[i, :need] = perm[used:used + need]
        used += need
    stored = (nb, bs) + physical_shape((h, d))
    return (q, k, v, jnp.asarray(k).reshape(stored),
            jnp.asarray(v).reshape(stored), tables, lengths)


@pytest.mark.parametrize("sentinel", ["num_blocks", "minus-one"])
def test_the_kernel_reads_the_packed_pool_in_place(sentinel):
    """`ragged_decode_attention` on the pool as stored ([.., 8, 128]: two
    heads a lane row), interpret mode, against `ragged_attention_reference`
    on the same stored pool and on the logical one: the reference reads
    both alike to the bit, and the kernel's per-head lane sums stay inside
    float32 rounding of it. The dead row returns exact zeros."""
    q, k, v, kp, vp, tables, lengths = _packed_case(sentinel)
    assert kp.shape == (24, 8, 8, 128)
    got = np.asarray(rpa.ragged_decode_attention(q, kp, vp, tables, lengths,
                                                 interpret=True))
    ref = np.asarray(rpa.ragged_attention_reference(q, kp, vp, tables,
                                                    lengths))
    np.testing.assert_array_equal(
        ref, np.asarray(rpa.ragged_attention_reference(q, k, v, tables,
                                                       lengths)))
    assert got.shape == q.shape
    np.testing.assert_allclose(got, ref, rtol=2e-6, atol=2e-6)
    assert np.all(got[0] == 0.0)
    # and against a dense softmax over each row's live positions
    for i, ln in enumerate(lengths):
        if not ln:
            continue
        kc = np.concatenate([k[b] for b in tables[i, :-(-ln // 8)]])[:ln]
        vc = np.concatenate([v[b] for b in tables[i, :-(-ln // 8)]])[:ln]
        s = np.einsum("hd,shd->hs", q[i], kc) / 8.0
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(got[i], np.einsum("hs,shd->hd", p, vc),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("block, dtype, table, group", [
    ((32, 8, 128), jnp.float32, 32, 4),    # the GPT-2 cells: 128 KiB a block
    ((32, 6, 128), jnp.float32, 32, 4),    # 6 rows fill a tile of 8
    ((32, 8, 128), jnp.bfloat16, 32, 4),   # half the bytes, a tile of 16 rows
    ((32, 12, 64), jnp.float32, 32, 2),    # [12, 64] fills [16, 128]
    ((8, 8, 128), jnp.float32, 32, 16),
    ((8, 8, 128), jnp.float32, 5, 5),      # never more than a row's table
    ((64, 32, 128), jnp.float32, 32, 1),   # a block past the budget: one
    ((4, 4, 8), jnp.float32, 5, 5),        # the toy sizes of the CPU tests
])
def test_the_blocks_of_a_dma_group_are_decided_from_the_stored_block(
        block, dtype, table, group):
    """`blocks_per_group`: as many stored blocks as half a MiB of VMEM
    holds, the block's two minor dimensions padded to the dtype's tile; no
    option, no field of `EngineConfig`."""
    assert rpa.blocks_per_group(block, dtype, table) == group


def test_a_pool_that_holds_no_such_heads_is_refused():
    q = jnp.zeros((2, 16, 64), jnp.float32)
    pool = jnp.zeros((4, 8, 12, 64), jnp.float32)
    with pytest.raises(ValueError, match="holds no"):
        rpa.ragged_decode_attention(q, pool, pool, jnp.zeros((2, 2), int),
                                    jnp.zeros((2,), int), interpret=True)


@pytest.mark.parametrize("layers, cache_shape, dtype, logical, physical", [
    (24, (16, 64), jnp.float32, 196_608, 196_608),   # the GPT-2 cells
    (5, (576,), jnp.bfloat16, 5_760, 6_400),         # the expert cell
])
def test_the_bytes_a_position_holds_logical_and_as_stored(
        layers, cache_shape, dtype, logical, physical):
    import math
    pc = PagedKVCache(layers, cache_shape, num_blocks=1, block_size=8,
                      dtype=dtype)
    assert pc.physical_bytes_per_token == physical
    pools_a_layer = 2 if len(cache_shape) == 2 else 1
    assert layers * pools_a_layer * math.prod(cache_shape) \
        * jnp.dtype(dtype).itemsize == logical


@pytest.mark.parametrize("heads, head_dim, stored", [
    (16, 64, (8, 128)),            # the smallest GPT whose pools pack
    (4, 128, (512,)),              # one flat row a position: on the chip
    (2, 256, (512,)),              # it must not reach the ragged kernel
], ids=["packed", "flat-4x128", "flat-2x256"])
def test_an_engine_serves_generate_s_tokens_on_pools_as_stored(
        heads, head_dim, stored):
    """Through `LLMEngine` with the default kernel ("ragged": on the chip,
    PADDLE_TPU_TEST_REAL_TPU=1, the kernel's gate is open): the pools are
    stored packed or flat, both gauges say what a position costs (the
    logical figure the spec's, the stored one the cache's, equal here:
    neither pads), and the tokens are `generate()`'s, from a dense prefill
    and from a chunked one."""
    from paddle_tpu.models.generation import generate
    from paddle_tpu.models.gpt import GPT, GPTConfig
    model = GPT(GPTConfig(vocab_size=96, hidden_size=heads * head_dim,
                          num_layers=1, num_heads=heads, max_seq_len=64))
    eng = LLMEngine.from_model(model, EngineConfig(
        block_size=8, num_blocks=24, max_num_seqs=4,
        prefill_chunk_threshold=12))
    assert eng.config.kernel == "ragged"
    assert [p.shape for p in eng.cache.pools[0]] == [(24, 8) + stored] * 2
    assert eng.spec.cache_shape == (heads, head_dim)
    assert eng.stats.cache_bytes_per_token == 2 * heads * head_dim * 4 \
        == eng.spec.cache_bytes_per_token \
        == eng.stats.cache_physical_bytes_per_token
    prompts = [np.arange(3, 10, dtype=np.int32),
               np.arange(20, 37, dtype=np.int32)]      # dense, chunked
    rids = [eng.add_request(p, SamplingParams(max_tokens=6))
            for p in prompts]
    out = eng.run(max_steps=200)
    for rid, p in zip(rids, prompts):
        want = np.asarray(generate(model, p[None], 6))[0, len(p):]
        np.testing.assert_array_equal(np.asarray(out[rid]), want)
    assert not any(eng.cache.check_integrity().values())


@pytest.mark.parametrize("heads, head_dim, stored, through_kernel", [
    (4, 128, (512,), False),       # one flat row a position: no kernel tile
    (2, 256, (512,), False),
    (8, 128, (8, 128), True),      # stored as it is: the kernel's tile
    (16, 64, (8, 128), True),      # packed: the kernel's tile
    (12, 64, (6, 128), True),      # packed, six lane rows (GPT-2 small)
])
def test_the_decode_layer_under_the_ragged_route_reads_every_stored_shape(
        monkeypatch, heads, head_dim, stored, through_kernel):
    """GPT-2's decode layer as the chip runs it (`ragged` on, the kernel's
    gate open; off the chip the kernel in interpret mode, on it
    (PADDLE_TPU_TEST_REAL_TPU=1) the kernel itself) on the pools as
    `PagedKVCache` stores them: a four-dimensional pool goes through the
    kernel, a flat row of H * D lanes takes the gather, and both give what
    the gather path gives."""
    from paddle_tpu.models import generation as gen
    from paddle_tpu.models.gpt import GPT, GPTConfig
    model = GPT(GPTConfig(vocab_size=32, hidden_size=heads * head_dim,
                          num_layers=1, num_heads=heads, max_seq_len=32))
    params = gen.extract_params(model)
    spec = gen.serving_spec((1, heads, head_dim, 32))
    rng = np.random.default_rng(heads)
    pc = PagedKVCache(1, (heads, head_dim), num_blocks=8, block_size=8)
    assert [p.shape[2:] for p in pc.pools[0]] == [stored] * 2
    pool = tuple(jnp.asarray(0.1 * rng.normal(size=p.shape), p.dtype)
                 for p in pc.pools[0])
    positions = np.asarray([0, 11, 23], np.int32)       # 1, 2 and 3 blocks
    tables = np.asarray([[5, 8, 8, 8], [1, 6, 8, 8], [0, 3, 7, 8]], np.int32)
    calls = []
    kernel, on_chip = rpa.ragged_decode_attention, \
        jax.default_backend() == "tpu"

    def counted(*args):
        calls.append(args[1].shape)
        return kernel(*args, interpret=not on_chip)

    monkeypatch.setattr(rpa, "supported", lambda *geometry: True)
    monkeypatch.setattr(rpa, "ragged_decode_attention", counted)
    x = spec.embed(params, jnp.asarray([3, 7, 9], jnp.int32),
                   jnp.asarray(positions))
    got, want = (spec.decode_layer(
        params, 0, x, pool, tables[np.arange(3), positions // 8],
        positions % 8, jnp.asarray(tables), jnp.asarray(positions),
        jnp.asarray(positions + 1), jnp.ones((3,), bool), ragged)
        for ragged in (True, False))
    assert calls == ([(8, 8) + stored] if through_kernel else [])
    tol = 2e-3 if on_chip else 1e-5
    np.testing.assert_allclose(got[0], want[0], rtol=tol, atol=tol)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _filled_packed_cache(seed):
    rng = np.random.default_rng(seed)
    pc = PagedKVCache(2, (16, 64), num_blocks=6, block_size=4)
    pc.pools = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.normal(size=p.shape), p.dtype), pc.pools)
    return pc


def test_exported_blocks_import_into_a_packed_pool_as_they_were():
    """export -> import between two packed pools: whole blocks on axis 0,
    whatever trails; the destination gathers the source's logical rows."""
    src, dst = _filled_packed_cache(0), _filled_packed_cache(1)
    src.allocate("s", 10)
    payload, n = src.export_blocks("s")
    assert n == 10 and payload[0][0].shape == (3, 4, 8, 128)
    dst.allocate("busy", 4)
    dst.import_blocks("s", payload, n)
    ts, td = (jnp.asarray([pc.block_table("s")], jnp.int32)
              for pc in (src, dst))
    for a, b in zip(jax.tree_util.tree_leaves(src.pools),
                    jax.tree_util.tree_leaves(dst.pools)):
        np.testing.assert_array_equal(
            np.asarray(gather_rows(a, ts, (16, 64))),
            np.asarray(gather_rows(b, td, (16, 64))))
    assert not any(dst.check_integrity().values())


def test_scrubbed_blocks_of_a_packed_pool_read_zero_and_no_other_does():
    pc = _filled_packed_cache(2)
    before = [np.asarray(p) for p in jax.tree_util.tree_leaves(pc.pools)]
    pc.scrub_blocks([1, 4])
    for old, new in zip(before, jax.tree_util.tree_leaves(pc.pools)):
        new = np.asarray(new)
        assert not new[[1, 4]].any()
        np.testing.assert_array_equal(new[[0, 2, 3, 5]], old[[0, 2, 3, 5]])


def test_int8_pools_hand_out_the_stored_shape_and_spill_per_head():
    """int8 mode on a geometry that packs: `pools` is the dequantized view
    in the STORED shape, like the float32 pools (the codes and their
    per-(block, head) scales stay logical inside); a block spilled to the
    host tier as codes + scales promotes back into the packed pool within
    twice the codec's committed bound."""
    from paddle_tpu.analysis.jaxnum import committed_codec_bound
    bound = committed_codec_bound()
    rng = np.random.RandomState(5)
    c = PagedKVCache(1, (16, 64), 4, 2, kv_cache_dtype="int8",
                     enable_prefix_cache=True, host_tier_blocks=4)
    assert c.stored_shape == (8, 128)
    assert c.physical_bytes_per_token == 2 * 16 * 64     # one byte a code
    toks = list(range(1, 9))
    table = c.allocate("a", 8)
    want = tuple(jnp.asarray(rng.randn(4, 2, 8, 128), jnp.float32)
                 for _ in range(2))
    c.pools = (want,)
    before = c.pools[0]

    def heads(a):
        return np.asarray(a).reshape(4, 2, 16, 64)
    for got, w in zip(before, want):
        assert got.shape == (4, 2, 8, 128)
        err = np.abs(heads(got) - heads(w)).max(axis=(1, 3)) \
            / np.abs(heads(w)).max(axis=(1, 3))
        assert err.max() <= bound
    c.free("a", cache_tokens=toks)
    ids = c._take_blocks("hog", 4)
    assert c.tier_demotions == 4
    for b in ids:                       # hand the blocks back
        del c._refcount[b]
        c._free.append(b)
        c.blocks_freed += 1
    assert c.host_tier.get(0)["payload"][0][0].shape == (2, 16, 64)
    assert c.ensure_promoted(toks + [99])["outcomes"] == ["hit"] * 4
    path, _ = c.prefix_index.match(toks, touch=False)
    for a, b in zip(c.pools[0], before):
        for node, old in zip(path, table):
            assert float(jnp.max(jnp.abs(a[node.block] - b[old]))
                         / jnp.max(jnp.abs(b[old]))) <= 2 * bound
    c.check_integrity()
