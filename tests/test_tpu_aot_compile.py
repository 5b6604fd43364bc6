"""The chip's compiler, asked without the chip.

The kernels of the two main paths, compiled at the real widths of the
flagship GPT for a described (not attached) v5e:2x2: the flash forward
and backward of the 6-head flagship, the packed-pair kernels of the
12-head one at both backward branches, the ragged decode kernel at three
head geometries (two of them packed two heads a lane row, as the cache
stores them) and at the largest blocks its gate admits, the flash kernel per shard under a 2x2 mesh, and the serving
cells' decode chunk and dense-admission scatter with their pools donated
and no pool copied. Interpret
mode accepts what Mosaic refuses (an unaligned slice, a batched dot with no
free lhs dim, too much VMEM); these compiles do not. Nothing runs, so they
say nothing about results or times.

One file on purpose: only one process at a time may load the TPU's
library, so the topology is described inside a module-scoped fixture, by
the one xdist worker that is handed this file, and every compile happens
in the test's own process.
"""
import functools
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

import paddle_tpu  # noqa: F401  (x64 on, as every kernel caller has it)


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _as_the_program_compiles():
    """Two settings of the test harness that the program does not run
    under. A compile for a described chip writes cache entries that a
    chipless process cannot read back, so the persistent cache is off
    around it. And conftest.py pins matmul precision to "highest" for its
    f64 oracles, under which upstream's flash kernel asks Mosaic for an
    fp32 contraction of bf16 tiles and is refused; the program runs at
    the default."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    cache = jax.config.jax_enable_compilation_cache
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", cache)
    jax.config.update("jax_default_matmul_precision", precision)
    cc.reset_cache()


def _kernel_calls(fn, *args) -> int:
    """Compile for the described chip (raises what its compiler would
    raise) and count the Mosaic kernels left in the program."""
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


def _flash_loss(q, k, v):
    from paddle_tpu.ops.pallas.flash_attention import _fa_core
    return _fa_core(q, k, v, True, 1.0 / float(np.sqrt(q.shape[-1]))) \
        .astype(jnp.float32).sum()


# [B, H, T, D] bf16 causal: the flagship's attention, and one long shape
@pytest.mark.parametrize("shape", [(32, 6, 1024, 128), (4, 6, 8192, 128)],
                         ids=["flagship", "long_8k"])
def test_flash_fwd_bwd_compiles(one_chip, shape):
    from paddle_tpu.ops.pallas.flash_attention import applied_patch
    assert applied_patch() == "lmdi_width1"   # the patched text is compiled
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    grad = jax.value_and_grad(_flash_loss, argnums=(0, 1, 2))
    assert _kernel_calls(grad, x, x, x) == 3      # fwd, dq, dkv


def test_flash_primal_compiles(one_chip):
    """The no-grad forward (eval, generate's prefill) skips the residuals
    and is a program of its own."""
    x = jax.ShapeDtypeStruct((32, 6, 1024, 128), jnp.bfloat16,
                             sharding=one_chip)
    assert _kernel_calls(_flash_loss, x, x, x) == 1


# 12 heads of 64 packed in pairs -> [B, 6, T, 128]; T=2048 takes the
# FA2-style backward (packed_flash.BWD_SINGLE_MAX)
@pytest.mark.parametrize("shape,calls", [((32, 6, 1024, 128), 2),
                                         ((16, 6, 2048, 128), 3)],
                         ids=["bwd_single", "bwd_fa2"])
def test_packed_flash_fwd_bwd_compiles(one_chip, shape, calls):
    from paddle_tpu.ops.pallas.packed_flash import (BWD_SINGLE_MAX,
                                                    packed_flash_attention)
    assert (shape[2] <= BWD_SINGLE_MAX) == (calls == 2)

    def loss(q, k, v):
        return packed_flash_attention(q, k, v, True, 0.125) \
            .astype(jnp.float32).sum()
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    grad = jax.value_and_grad(loss, argnums=(0, 1, 2))
    assert _kernel_calls(grad, x, x, x) == calls


def _kernel_names(fn, *args) -> list:
    """HLO names of the Mosaic kernels in the program compiled for the
    described chip: what the device trace will call them."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return sorted(re.match(r"\s*(?:ROOT )?%([\w\-]+?)(?:\.\d+)? = ", line)
                  .group(1) for line in text.splitlines()
                  if "tpu_custom_call" in line and " custom-call(" in line)


# the names are the contract with the benchmark's kernel metrics
# (benchmarks/lib/spans.py kernel_ops): JAX wraps a name given under jvp
# or transpose in the transform's, `jvp(packed_flash_fwd)`, and XLA spells
# that `jvp_packed_flash_fwd_`; a kernel without a name is `jvp__`
@pytest.mark.parametrize("shape,names", [
    ((32, 6, 1024, 128), ["jvp_packed_flash_fwd_",
                          "transpose_jvp_packed_flash_bwd__"]),
    ((16, 6, 2048, 128), ["jvp_packed_flash_fwd_",
                          "transpose_jvp_packed_flash_bwd_dkv__",
                          "transpose_jvp_packed_flash_bwd_dq__"])],
    ids=["bwd_single", "bwd_fa2"])
def test_packed_flash_kernels_carry_their_names(one_chip, shape, names):
    from paddle_tpu.ops.pallas.packed_flash import packed_flash_attention

    def loss(q, k, v):
        return packed_flash_attention(q, k, v, True, 0.125) \
            .astype(jnp.float32).sum()
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    grad = jax.value_and_grad(loss, argnums=(0, 1, 2))
    assert _kernel_names(grad, x, x, x) == names


def test_packed_flash_forward_alone_and_ragged_decode_are_named(one_chip):
    from paddle_tpu.ops.pallas.packed_flash import packed_flash_attention
    from paddle_tpu.ops.pallas.ragged_paged_attention import \
        ragged_decode_attention

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    x = sds((8, 6, 1024, 128), jnp.bfloat16)
    assert _kernel_names(
        lambda q, k, v: packed_flash_attention(q, k, v, True, 0.125),
        x, x, x) == ["packed_flash_fwd"]
    # the pool as the cache stores 16 heads x 64: two heads a lane row
    pool = sds((512, 32, 8, 128), jnp.float32)
    assert _kernel_names(
        ragged_decode_attention, sds((16, 16, 64), jnp.float32), pool, pool,
        sds((16, 32), jnp.int32), sds((16,), jnp.int32)) == \
        ["ragged_decode_attention"]


# the serving shape: 8 rows, float32 pools of 512 blocks x 32 tokens,
# 32 blocks per sequence (max_seq_len 1024), each pool in the shape the
# cache stores it in: (16, 64) packed two heads a lane row, the others as
# they are
@pytest.mark.parametrize("heads,head_dim", [(6, 128), (12, 64), (16, 64)])
def test_ragged_decode_compiles(one_chip, heads, head_dim):
    from paddle_tpu.inference.serving.paged_cache import physical_shape
    from paddle_tpu.ops.pallas.ragged_paged_attention import \
        ragged_decode_attention

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    pool = sds((512, 32) + physical_shape((heads, head_dim)), jnp.float32)
    assert _kernel_calls(
        ragged_decode_attention, sds((8, heads, head_dim), jnp.float32),
        pool, pool, sds((8, 32), jnp.int32), sds((8,), jnp.int32)) == 1


# the serving tile, and the largest stored blocks the kernel's gate admits
# (1 MiB of VMEM as float32: tall, wide, and packed two heads a lane row)
@pytest.mark.parametrize("block_size,heads,head_dim,group", [
    (32, 16, 64, 4), (64, 32, 128, 1), (256, 8, 128, 1), (256, 16, 64, 1)])
def test_ragged_decode_scratch_stays_inside_scoped_vmem(
        one_chip, monkeypatch, block_size, heads, head_dim, group):
    """What the kernel holds in VMEM for its copies (k and v, two slots, a
    group of blocks each) is at most a quarter of the v5e's default scoped
    limit of 16 MiB at every block the gate admits, the group is what the
    stored block's bytes say, and the chip's compiler takes the kernel with
    its block-sized temporaries under that default (no `vmem_limit_bytes`
    is set). One block-size step past the largest, the gate says no."""
    from paddle_tpu.inference.serving.paged_cache import physical_shape
    from paddle_tpu.ops.pallas import ragged_paged_attention as rpa

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    block = (block_size,) + physical_shape((heads, head_dim))
    assert rpa.blocks_per_group(block, jnp.float32, 32) == group
    assert 4 * group * rpa._block_vmem_bytes(block, jnp.float32) \
        <= 16 * 2 ** 20 // 4
    pool = sds((64,) + block, jnp.float32)
    assert _kernel_calls(
        rpa.ragged_decode_attention, sds((8, heads, head_dim), jnp.float32),
        pool, pool, sds((8, 32), jnp.int32), sds((8,), jnp.int32)) == 1
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert rpa.supported(head_dim, heads, block)
    if group == 1:
        assert not rpa.supported(head_dim, heads,
                                 (block_size + 8,) + block[1:])
    # a lane row cut in two has no copy out of HBM, whatever its size
    assert not rpa.supported(64, 12, (32, 12, 64))


def _pool_copies(text: str, num_blocks: int) -> list:
    """The compiled program's `copy` operations whose result is pool-shaped
    (`[num_blocks, block_size, ...]`): the relayouts a v5e makes around a
    program that indexes by block a pool it keeps block-id-minor."""
    return [line.strip()[:120] for line in text.splitlines()
            if re.search(rf"= \w+\[{num_blocks},\d+,[\d,]+\]\S* copy\(",
                         line)]


def _scatter_in_place(one_chip, layers, num_blocks, cache_shape, dtype,
                      dense_shape):
    """`write_prefill_scatter` on pools of `cache_shape` as the cache stores
    them: one XLA module under its own name, every pool aliased to its
    output (the donation took), NO pool-shaped copy and under 5 % of one
    pool of temporaries: the scatter writes the sequence's blocks and
    nothing else."""
    from paddle_tpu.inference.serving.paged_cache import (
        physical_shape, write_prefill_scatter)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    pool = sds((num_blocks, 32) + physical_shape(cache_shape), dtype)
    leaf = (pool, pool) if len(cache_shape) == 2 else pool
    dense = sds(dense_shape, dtype)
    dense_leaf = (dense, dense) if len(cache_shape) == 2 else dense
    compiled = write_prefill_scatter.lower(
        (leaf,) * layers, (dense_leaf,) * layers,
        sds((dense_shape[-2] // 32,), jnp.int32),
        sds((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "HloModule jit_write_prefill_scatter" in text
    assert _pool_copies(text, num_blocks) == []
    pool_bytes = int(np.prod(pool.shape)) * jnp.dtype(dtype).itemsize
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= len(
        jax.tree_util.tree_leaves(leaf)) * layers * pool_bytes
    assert mem.temp_size_in_bytes < 0.05 * pool_bytes


def test_write_prefill_scatter_updates_the_pools_in_place(one_chip):
    """The dense-admission scatter at the serving cell's geometry (two
    layers of it, 16 heads x 64 stored as [8, 128])."""
    _scatter_in_place(one_chip, 2, 512, (16, 64), jnp.float32,
                      (1, 16, 1024, 64))


@functools.lru_cache(maxsize=None)
def _decode_chunk_compiled(one_chip):
    """`jit_fused_decode_chunk` at the GPT-2 serving cells' geometry (two
    layers of GPT-2 medium's 16 heads x 64, 16 rows, 512 blocks x 32, a
    small vocabulary), the ragged kernel routed as on the chip."""
    from paddle_tpu.inference.serving.attention import (PACK_COLS,
                                                        fused_decode_chunk)
    from paddle_tpu.inference.serving.paged_cache import physical_shape
    from paddle_tpu.models import generation as gen
    from paddle_tpu.models.gpt import GPT, GPTConfig

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    layers, heads, head_dim, seq = geom = (2, 16, 64, 1024)
    model = GPT(GPTConfig(vocab_size=512, hidden_size=heads * head_dim,
                          num_layers=layers, num_heads=heads,
                          max_seq_len=seq))
    params = {k: sds(v.shape, v.dtype)
              for k, v in gen.extract_params(model).items()}
    pool = sds((512, 32) + physical_shape((heads, head_dim)), jnp.float32)
    assert pool.shape == (512, 32, 8, 128)
    backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        return fused_decode_chunk.lower(
            params, ((pool, pool),) * layers,
            sds((16, PACK_COLS + 8 + seq // 32), jnp.int32), geom, 8,
            "ragged").compile()
    finally:
        jax.default_backend = backend


def test_the_decode_chunk_copies_no_pool_at_the_serving_geometry(one_chip):
    """The pools stay block-major (`[512,32,8,128]`, row-major tiled), so
    no pool is copied in or out, every pool is aliased to its output, and
    the kernel is called once a layer under its name, on the pool as it is
    stored."""
    layers = 2
    compiled = _decode_chunk_compiled(one_chip)
    text = compiled.as_text()
    assert _pool_copies(text, 512) == []
    assert "[512,32,8,128]{3,2,1,0:T(8,128)}" in text
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(kernels) == layers
    assert all(re.match(r"\s*%ragged_decode_attention[.\d]* = f32\[16,8,128\]",
                        k) and "f32[512,32,8,128]" in k for k in kernels)
    pool_bytes = 512 * 32 * 8 * 128 * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * layers * pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes


def _computation(text: str, name: str) -> str:
    """The HLO computation `name` of a compiled program's text with every
    computation it calls (fusions, reducers, branches), as one string."""
    bodies = dict(re.findall(r"^(%[\w.-]+) \(.*?\{\n(.*?)^\}", text,
                             flags=re.M | re.S))
    seen, todo = [], [name]
    while todo:
        n = todo.pop()
        if n in seen:
            continue
        seen.append(n)
        todo += [c for c in re.findall(r"%[\w.-]+", bodies[n])
                 if c in bodies]
    return "\n".join(bodies[n] for n in seen)


def test_the_decode_chunk_sorts_only_where_a_row_samples(one_chip):
    """The same program: the sampler is a `conditional` on whether any
    row of the chunk samples. Its greedy computation is the argmax alone
    (no sort, softmax, cumulative sum or random bits), and the whole program
    sorts `[16, V]` once, in the branch of the rows that truncate (the
    branchless sampler sorted twice, every trip)."""
    text = _decode_chunk_compiled(one_chip).as_text()
    conds = re.findall(
        r"= \(s32\[16\]\S*\) conditional\(.*?"
        r"branch_computations=\{(%[\w.-]+), (%[\w.-]+)\}", text)
    assert len(conds) == 1
    greedy, sampled = (_computation(text, c) for c in conds[0])
    # the sort, the softmax, the cumsum, the key schedule, the inner cond
    costly = (" sort(", " exponential(", " reduce-window(", " xor(",
              " conditional(")
    for op in costly:
        assert op not in greedy and op in sampled, op
    assert " reduce(" in greedy and " iota(" in greedy      # the argmax
    sorts = [line for line in text.splitlines() if " sort(" in line]
    assert len(sorts) == 1 and "f32[16,512]" in sorts[0]


def test_flash_compiles_per_shard_under_a_mesh(topo):
    """GSPMD refuses to partition a Mosaic kernel; under a multi-device
    mesh the flash kernel runs per shard (flash_attention._fa_sharded).
    GPT-medium's attention on sharding=2 x tp=2, chip_smoke's four-chip
    layout: batch over sharding, heads over tp."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.parallel import build_mesh, get_global_mesh, \
        set_global_mesh
    mesh = build_mesh(sharding=2, tp=2, devices=topo.devices)
    before = get_global_mesh()
    set_global_mesh(mesh)
    try:
        x = jax.ShapeDtypeStruct(
            (8, 16, 1024, 64), jnp.bfloat16,
            sharding=NamedSharding(mesh, P(("dp", "sharding"), "tp")))

        def loss(q, k, v):
            return fa._fa_sharded(q, k, v, True, 0.125) \
                .astype(jnp.float32).sum()
        grad = jax.value_and_grad(loss, argnums=(0, 1, 2))
        assert _kernel_calls(grad, x, x, x) == 3
    finally:
        set_global_mesh(before)


#: (hidden, width, experts scored, held, top-k) of the expert cells
EXPERT_WIDTHS = {"latent": (7680, 2048, 256, 16, 8),    # cell 3, 16 of 256
                 "swa": (2304, 896, 64, 64, 8)}         # cell 7, all 64


@functools.lru_cache(maxsize=None)
def _held_experts_compiled(one_chip, rows, widths="latent"):
    """The dropless expert layer at a cell's published widths, compiled for
    `rows` tokens."""
    from paddle_tpu.distributed.moe import held_experts_mlp
    hidden, width, scored, held, top_k = EXPERT_WIDTHS[widths]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(x, router, wg, wu, wd):
        return held_experts_mlp(x, router, wg, wu, wd, (0, held), top_k, 2.5)
    return jax.jit(layer).lower(
        sds((rows, hidden), jnp.bfloat16), sds((hidden, scored), jnp.float32),
        sds((held, hidden, width), jnp.bfloat16),
        sds((held, hidden, width), jnp.bfloat16),
        sds((held, width, hidden), jnp.bfloat16)).compile()


def _branches(text: str) -> list:
    """The text of each branch computation of the program's one
    `conditional`, with what it calls, in branch order."""
    found = re.findall(r" conditional\(.*branch_computations=\{([^}]*)\}",
                       text)
    assert len(found) == 1
    return [_computation(text, name.strip())
            for name in found[0].split(",")]


def _batched_products(text: str) -> int:
    """Batched matmuls over the experts (`eck,ekn->ecn`), which the chip's
    compiler writes as convolutions."""
    return len(re.findall(r" convolution\(.*eck,ekn->ecn", text))


def test_the_held_experts_layer_is_a_grouped_matmul_kernel(one_chip):
    """At 128 decode rows `jax.lax.ragged_dot` becomes Mosaic grouped
    matmuls (three products and their group metadata), not a dense product
    over every (token, expert), and the pair buffer is the only large
    temporary. (PR 36 changed this test on purpose: the batched form is a
    third branch, and it copies no expert weight either.)"""
    compiled = _held_experts_compiled(one_chip, 128)
    text = compiled.as_text()
    assert text.count("ragged-dot") >= 3
    assert text.count("tpu_custom_call") >= 3
    # 1,024 pair rows of 7,680 float32 are 31 MB; a dense [16, 128, ...]
    # expansion of the weights or the rows, or a copy of one product's
    # weights (503 MB), would be hundreds
    assert compiled.memory_analysis().temp_size_in_bytes < 200e6


@pytest.mark.parametrize("rows, compact_tile", [(128, "128,512,512"),
                                                (1024, "512,512,512")])
def test_the_pair_buffer_has_a_compact_and_a_full_branch(one_chip, rows,
                                                         compact_tile):
    """The expert layer at the cell's decode shape (128 rows: 1,024 pairs,
    a compact buffer of 128) and at its longest prompt (1,024 rows: 8,192
    pairs against 1,024): one `conditional` with the three products in each
    branch. The kernel's row tile is min(buffer rows, 512), so the compact
    branch of a decode trip multiplies tiles of 128 rows where the full one
    multiplies 512. (PR 36 changed this test on purpose: the batched form
    is the first of now three branches, at a capacity of 16 and 128 rows an
    expert; PR 30's two stay as they were.)"""
    compiled = _held_experts_compiled(one_chip, rows)
    text = compiled.as_text()
    batched, compact, full = _branches(text)
    assert _batched_products(batched) == 3 and "ragged-dot" not in batched
    assert f"f32[16,{rows // 8},2048]" in batched
    for branch, tile in ((compact, compact_tile), (full, "512,512,512")):
        assert re.findall(r'ragged_dot_tiling="([0-9,]+)"', branch) \
            == [tile] * 3
        assert _batched_products(branch) == 0
    # the full branch's 8 x rows pair rows of 7,680 float32, twice
    assert compiled.memory_analysis().temp_size_in_bytes \
        < (200e6 if rows == 128 else 600e6)


def test_the_expert_layer_multiplies_batched_where_the_loads_fit(one_chip):
    """The layer at the sliding-window cell's decode shape ([64, 2304], 64
    experts of 2,304 x 896, top-8: 512 pairs, 8 an expert): ONE
    `conditional`; in one branch the three products batched over the
    experts at the capacity of 32 rows, in the other three `ragged-dot`
    kernels, the dropless fallback (no compact branch: R >= the pairs). No
    branch copies an expert weight: the temporaries are the [64, 32, .]
    buffers, a few MB, where one product's weights are 264 MB."""
    compiled = _held_experts_compiled(one_chip, 64, "swa")
    text = compiled.as_text()
    batched, grouped = _branches(text)
    assert _batched_products(batched) == 3 and "ragged-dot" not in batched
    assert "f32[64,32,896]" in batched and "f32[64,32,2304]" in batched
    # 512 divides neither 2,304 nor 896: the grouped kernel's small tiles
    assert sorted(re.findall(r'ragged_dot_tiling="([0-9,]+)"', grouped)) \
        == ["512,128,256", "512,256,128", "512,256,128"]
    assert grouped.count("tpu_custom_call") >= 3
    assert _batched_products(grouped) == 0
    assert not re.search(r"bf16\[64,(?:2304,896|896,2304)\]\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 16e6


def test_a_prompts_block_of_tokens_keeps_the_grouped_kernel(one_chip):
    """The same layer at a prompt's block of 1,024 tokens (8,192 pairs, 128
    an expert): four times that passes the chip's ridge, so the batched
    form is not in the program: no `conditional`, three `ragged-dot`
    kernels, as before PR 36."""
    compiled = _held_experts_compiled(one_chip, 1024, "swa")
    text = compiled.as_text()
    assert not re.findall(r" conditional\(", text)
    assert len(re.findall(r"ragged_dot_tiling=", text)) == 3
    assert _batched_products(text) == 0
    assert compiled.memory_analysis().temp_size_in_bytes < 100e6


def test_the_latent_pool_is_scattered_in_place_at_the_cell_size(one_chip):
    """`write_prefill_scatter` on the latent layout of the expert cell (5
    layers of bf16 rows 576 wide, stored 640 wide: a whole number of lane
    rows, which a v5e keeps block-major; 2,048 dense positions)."""
    _scatter_in_place(one_chip, 5, 8192, (576,), jnp.bfloat16,
                      (1, 2048, 576))


def test_the_kda_step_updates_the_slots_in_place_at_the_cell_size(one_chip):
    """The KDA decode kernel at the Ling cell's widths (128 rows, 32 heads
    of 128, 128 slots): Mosaic takes it (the (8, 128) transpose, the
    history's bfloat16 lane rows), the state and history slot arrays are
    aliased to the results, and nothing of the state's size is a
    temporary: the update is in place."""
    from paddle_tpu.ops.pallas import kda_step as kk

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    n, h, d, taps = 128, 32, 128, 4
    # the slot arrays are donated as the decode chunk's carry is
    step = jax.jit(functools.partial(kk.kda_step, scale=1 / np.sqrt(d)),
                   donate_argnums=(4, 5))
    compiled = step.lower(
        sds((n, h, 3 * d), jnp.bfloat16), sds((n, h, d), jnp.float32),
        sds((n, h), jnp.float32), sds((taps, h, 3 * d), jnp.bfloat16),
        sds((n, h, d, d), jnp.float32),
        sds((n, h, (taps - 1) * 3 * d), jnp.bfloat16), sds((n,), jnp.int32),
        sds((n,), jnp.bool_), sds((n,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%kda_step[.\d]* = ", text)) == 1
    mem = compiled.memory_analysis()
    state = n * h * d * d * 4
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < state / 100
