"""Compile the main path's kernels and steps, at real widths, for a TPU
that is described and not attached (``v5e:2x2``). Nothing runs; what the
chip's compiler would refuse — a misaligned tile, too much VMEM, a kernel
inside a program XLA must partition — is refused here, at no chip time.
Interpret mode, which every other test uses, reaches none of that.

A compile that passes is not a chip run: ``chip_smoke.py`` is."""

import os
import re

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, SingleDeviceSharding

import chip_smoke
from fluxmpi_tpu import ParallelConfig, config
from fluxmpi_tpu.ops import flash_attention
from fluxmpi_tpu.parallel import TrainState, make_train_step


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # no libtpu here, or its lock is held
        pytest.skip(f"cannot describe a v5e:2x2 topology: {exc!r}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep it out.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture()
def as_on_tpu(monkeypatch):
    """Code that asks ``jax.default_backend()`` still sees the CPU here;
    steer the kernels' ``interpret=None`` to its on-chip branch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(shape, dtype, device):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=SingleDeviceSharding(device)
    )


def _layout_copies(text, b, h, s, d):
    """``copy`` instructions of a compiled program that produce a bfloat16
    array of a head fold's shape (the dimensions ``b, h, s, d`` in any
    order and any layout, dimensions of one dropped, batch and heads
    perhaps one dimension): what XLA inserts where a producer cannot
    write, or a consumer read, the layout a Mosaic kernel's operand is
    fixed to (docs/gotchas.md, "Counting layout copies")."""
    folds = {tuple(sorted(x for x in dims if x != 1))
             for dims in ((b, h, s, d), (b * h, s, d))}
    shapes = re.findall(r"%copy[.\d]* = bf16\[([\d,]+)\]\{", text)
    return sum(
        tuple(sorted(int(x) for x in shape.split(",") if x != "1")) in folds
        for shape in shapes)


# Programs more than one test reads, compiled once a session (every test
# of this file runs in one process).
_COMPILED = {}


def _once(name, build):
    if name not in _COMPILED:
        _COMPILED[name] = build()
    return _COMPILED[name]


# (b, s, h, d) = (8, 1024, 12, 64): GPT-2 small's attention at the
# smoke's batch. name -> (h_kv, flash_attention keyword arguments).
_VARIANTS = {
    "causal": (12, {"causal": True}),
    "segments": (12, {"causal": True, "segments": True}),
    "window": (12, {"causal": True, "window": 256}),
    "gqa": (4, {"causal": True}),
    "kernel_dropout": (12, {"causal": True, "dropout_rate": 0.1}),
}


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("variant", sorted(_VARIANTS))
def test_flash_kernels_compile_for_v5e(topo, variant, grad):
    h_kv, kwargs = _VARIANTS[variant]
    kwargs = dict(kwargs)
    segments = kwargs.pop("segments", False)
    dev = topo.devices[0]
    b, s, h, d = 8, 1024, 12, 64

    def attend(q, k, v, seg, seed):
        return flash_attention(
            q, k, v, interpret=False,
            segment_ids=seg if segments else None,
            dropout_seed=seed if "dropout_rate" in kwargs else None,
            **kwargs,
        )

    def loss(q, k, v, seg, seed):
        return jnp.sum(attend(q, k, v, seg, seed).astype(jnp.float32))

    fn = jax.grad(loss, (0, 1, 2)) if grad else attend
    text = jax.jit(fn).lower(
        _sds((b, s, h, d), jnp.bfloat16, dev),
        _sds((b, s, h_kv, d), jnp.bfloat16, dev),
        _sds((b, s, h_kv, d), jnp.bfloat16, dev),
        _sds((b, s), jnp.int32, dev),
        _sds((), jnp.uint32, dev),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == (3 if grad else 1)


@pytest.mark.parametrize(
    "name, q_shape, kv_shape, grad",
    [
        # Paged decode: one query row against a 1,024-key cache, masked
        # by a (q, kv) segment pair — the engine's decode attend.
        ("decode_sq1", (8, 1, 12, 64), (8, 1024, 12, 64), False),
        # A wider, longer model: head_dim 128 at sequence 2,048.
        ("s2048_d128", (4, 2048, 16, 128), (4, 2048, 16, 128), True),
    ],
)
def test_flash_shapes_compile_for_v5e(topo, name, q_shape, kv_shape, grad):
    dev = topo.devices[0]
    decode = q_shape[1] == 1

    def attend(q, k, v, qseg, kseg):
        return flash_attention(
            q, k, v, interpret=False, causal=not decode,
            segment_ids=(qseg, kseg) if decode else None,
        )

    def loss(*args):
        return jnp.sum(attend(*args).astype(jnp.float32))

    fn = jax.grad(loss, (0, 1, 2)) if grad else attend
    text = jax.jit(fn).lower(
        _sds(q_shape, jnp.bfloat16, dev),
        _sds(kv_shape, jnp.bfloat16, dev),
        _sds(kv_shape, jnp.bfloat16, dev),
        _sds(q_shape[:2], jnp.int32, dev),
        _sds(kv_shape[:2], jnp.int32, dev),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == (3 if grad else 1)


# trinity-mini-serve's prefill (32 query over 4 K/V heads of 128, window
# and full layers) at the tile rule's tiles: 512 queries a step against
# the head's whole K/V in 512-sub-tiles under a loop (2 ... 16 of them;
# 8,192 tokens are the 2 MiB a K/V block may hold), and 576, which no
# multiple of 128 divides and the rule of before could not compile.
@pytest.mark.parametrize("window", [None, 2048], ids=["full", "window"])
@pytest.mark.parametrize("s", [576, 1024, 2560, 8192])
def test_flash_prefill_tiles_compile_for_v5e(topo, s, window):
    dev = topo.devices[0]

    def attend(q, k, v):
        return flash_attention(q, k, v, interpret=False, causal=True,
                               window=window)

    text = jax.jit(attend).lower(
        _sds((1, s, 32, 128), jnp.bfloat16, dev),
        _sds((1, s, 4, 128), jnp.bfloat16, dev),
        _sds((1, s, 4, 128), jnp.bfloat16, dev),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 1


# The staircase a diagonal sub-tile is worked as under the causal mask
# alone (``_strips``: four strips of 128 at the rule's 512-sub-tiles), in
# all three kernels: gpt2m-train's shape; head_dim 128 with grouped heads
# (trinity-mini-serve's full layer, two query blocks a grid row; nemotron's
# 16 query heads a K/V head); and gpt2m-serve's whole tiles of 640 and 768,
# whose strips would be no whole lane tiles and which keep the generic
# masked body. name -> (q shape, kv heads, the forward's strips).
_STAIRS = {
    "gpt2m_train": ((8, 1024, 16, 64), 16, 4),
    "trinity_full_2048": ((1, 2048, 32, 128), 4, 4),
    "nemotron_1024": ((1, 1024, 32, 128), 2, 4),
    "whole_640": ((1, 640, 16, 64), 16, 0),
    "whole_768": ((1, 768, 16, 64), 16, 0),
}


@pytest.mark.parametrize("name", sorted(_STAIRS))
def test_flash_staircase_compiles_for_v5e(topo, name):
    import importlib

    fa = importlib.import_module("fluxmpi_tpu.ops.flash_attention")
    (b, s, h, d), h_kv, strips = _STAIRS[name]
    dev = topo.devices[0]
    tiles = fa._tile_rule("fwd", s, s, d, jnp.bfloat16)
    assert fa._strips(tiles, True, None, False) == strips

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, interpret=False, causal=True).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        _sds((b, s, h, d), jnp.bfloat16, dev),
        _sds((b, s, h_kv, d), jnp.bfloat16, dev),
        _sds((b, s, h_kv, d), jnp.bfloat16, dev),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 3


# name -> (slots, heads, head_dim, layers, pool blocks, block_size,
# table width, dtype): the benchmark's serve cell (GPT-2 medium, 32 slots
# of 8 blocks of 128), the smoke's engine (GPT-2 small at the engine's
# default 16-token blocks), a 128-wide head, a float32 pool.
_PAGED = {
    "gpt2m_cell": (32, 16, 64, 24, 257, 128, 8, jnp.bfloat16),
    "gpt2s_smoke": (8, 12, 64, 12, 513, 16, 64, jnp.bfloat16),
    "d128": (8, 8, 128, 2, 33, 32, 4, jnp.bfloat16),
    "f32": (8, 16, 64, 2, 33, 128, 4, jnp.float32),
}


@pytest.mark.parametrize("name", sorted(_PAGED))
def test_paged_decode_kernel_compiles_for_v5e(topo, name):
    from fluxmpi_tpu.ops.paged_attention import paged_decode_attention

    slots, heads, d, layers, blocks, bs, mb, dtype = _PAGED[name]
    dev = topo.devices[0]
    pool = _sds((layers, blocks, bs, heads * d), dtype, dev)

    def attend(q, k_pool, v_pool, tables, lengths):
        return paged_decode_attention(
            q, k_pool, v_pool, tables, lengths, layer=layers - 1,
            interpret=False,
        )

    compiled = jax.jit(attend).lower(
        _sds((slots, heads, d), dtype, dev), pool, pool,
        _sds((slots, mb), jnp.int32, dev), _sds((slots,), jnp.int32, dev),
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    # The pools are read where they lie: nothing pool-sized is made.
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


# The serve cells' decode attention: slots, table entries, block size,
# query heads, then K/V heads, head_dim and a window (trinity-mini's full
# layer and a window layer's ring), or the latent rank, rotary lanes and
# the pool's padded width.
_SERVE_CELLS = {
    "gpt2m": (32, 8, 128, 16, 16, 64, None),
    "trinity": (64, 17, 512, 32, 4, 128, None),
    "trinity_ring": (64, 5, 512, 32, 4, 128, 2048),
    "sarvam": (48, 17, 1024, 64, 512, 64, 640),
    "granite": (128, 10, 256, 32, 8, 128, None),
    "nemotron": (192, 24, 256, 32, 2, 128, None),
    "falcon": (96, 6, 512, 20, 4, 128, None),
}
# What the benchmark's readers find the two kernels by: the instruction
# names of their calls, and of nothing else in a program.
_PAGED_CALLS = r"%(paged_decode_attention|paged_latent_decode)[\w.\-]* = "


@pytest.mark.parametrize("cell", sorted(_SERVE_CELLS))
def test_paged_kernels_walk_compiles_at_the_serve_cells_shapes(topo, cell):
    """Both decode kernels at the six serve cells' shapes: the walk's
    lists (``slots x entries`` int32, scalar prefetched), the dynamic
    grid bound and the whole-output block are taken by the chip's
    compiler, the call keeps the name the readers match, and the walk's
    list-making carries neither name."""
    from fluxmpi_tpu.ops.paged_attention import (
        paged_decode_attention,
        paged_latent_decode_attention,
    )

    slots, entries, block, heads, *rest = _SERVE_CELLS[cell]
    dev = topo.devices[0]
    blocks = 1 + slots * entries
    tables = _sds((slots, entries), jnp.int32, dev)
    lengths = _sds((slots,), jnp.int32, dev)
    if cell == "sarvam":
        rank, rope, width = rest

        def attend(q_abs, q_rope, pool, tables, lengths):
            return paged_latent_decode_attention(
                q_abs, q_rope, pool, tables, lengths, layer=1,
                interpret=False)

        args = (_sds((slots, heads, rank), jnp.bfloat16, dev),
                _sds((slots, heads, rope), jnp.bfloat16, dev),
                _sds((2, blocks, block, width), jnp.bfloat16, dev))
        name = "paged_latent_decode"
    else:
        kv_heads, head_dim, window = rest

        def attend(q, k_pool, v_pool, tables, lengths):
            return paged_decode_attention(
                q, k_pool, v_pool, tables, lengths, layer=1, window=window,
                interpret=False)

        pool = _sds((2, blocks, block, kv_heads * head_dim), jnp.bfloat16,
                    dev)
        args = (_sds((slots, heads, head_dim), jnp.bfloat16, dev), pool, pool)
        name = "paged_decode_attention"
    compiled = jax.jit(attend).lower(*args, tables, lengths).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert re.findall(_PAGED_CALLS, text) == [name]
    assert compiled.memory_analysis().temp_size_in_bytes < 2**23


# trinity-mini-serve's expert layer: 128 experts of [2048, 1024] up and
# [1024, 2048] down; 64 slots x top-8 rows in the decode tick, a prompt
# bucket's tokens x 8 in a prefill (the shortest, one past the window,
# the longest).
@pytest.mark.parametrize("side", ["up", "down"])
@pytest.mark.parametrize("tokens", [64, 512, 2560, 8704])
def test_grouped_matmul_kernel_compiles_for_v5e(topo, as_on_tpu, tokens,
                                                side):
    from fluxmpi_tpu.ops.grouped_matmul import grouped_matmul, row_tile

    dev = topo.devices[0]
    rows = tokens * 8
    k, n = (2048, 1024) if side == "up" else (1024, 2048)
    assert row_tile(rows, k, n, jnp.bfloat16) == 512
    compiled = jax.jit(grouped_matmul).lower(
        _sds((rows, k), jnp.bfloat16, dev),
        _sds((128, k, n), jnp.bfloat16, dev), _sds((128,), jnp.int32, dev),
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert len(re.findall(r"%ragged-dot-gmm[.\d]* = ", text)) == 1
    # The weights are read where they lie; no pass over the output
    # zeroes the rows past the last group.
    assert compiled.memory_analysis().temp_size_in_bytes < 2**21


# The combine of a layer that holds a share of its experts: a decode
# tick's rows (granite's 128 slots x top-10, sarvam's 48 x top-8), the
# longest prefill bucket and slab (2,048 tokens x 10; 4,096 x 8).
@pytest.mark.parametrize("tokens,top_k,tiles", [
    (128, 10, (256, 4096)), (48, 8, (128, 4096)),
    (2048, 10, (512, 512)), (4096, 8, (512, 256)),
])
def test_expert_combine_kernel_compiles_for_v5e(topo, as_on_tpu, tokens,
                                                top_k, tiles):
    from fluxmpi_tpu.ops import grouped_matmul as gm

    dev = topo.devices[0]
    rows, n = tokens * top_k, 4096
    assert gm._combine_tiles(rows, n, tokens) == tiles
    compiled = jax.jit(
        lambda y, token, scale, live: gm.combine(y, token, scale, live,
                                                 tokens)
    ).lower(
        _sds((rows, n), jnp.float32, dev), _sds((rows,), jnp.int32, dev),
        _sds((rows,), jnp.float32, dev), _sds((), jnp.int32, dev),
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert len(re.findall(r"%expert-combine[.\d]* = ", text)) == 1
    # No pass over the rows outside the kernel: nothing gathered whole.
    assert compiled.memory_analysis().temp_size_in_bytes < 2**21


_GPT2_MEDIUM = {**chip_smoke.GPT2_SMALL, "num_layers": 24, "d_model": 1024,
                "num_heads": 16, "d_ff": 4096}


def _gpt2_medium_serving_programs(topo):
    """``(decode, prefill)`` of the ``gpt2m-serve`` cell's engine, the
    prefill at its 256-token bucket."""
    from fluxmpi_tpu.serving import InferenceEngine

    cfg = _GPT2_MEDIUM
    dev = topo.devices[0]
    model = chip_smoke._lm(cfg)
    params = jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, dev),
        jax.eval_shape(
            lambda key: model.init(
                key, jnp.zeros((1, 8), jnp.int32), train=False
            ),
            jax.random.PRNGKey(0),
        ),
    )
    engine = InferenceEngine(model, params, attention="flash", slots=32,
                             block_size=128, check_memory=False)
    try:
        pool = _sds(engine.cache.pool_shapes[0], jnp.bfloat16, dev)
        mb = engine.max_blocks_per_seq
        decode = engine._decode_step.lower(
            params, (pool,), (pool,), (_sds((32, mb), jnp.int32, dev),),
            _sds((32,), jnp.int32, dev), _sds((32,), jnp.int32, dev),
            _sds((32,), jnp.int32, dev), _sds((32,), jnp.bool_, dev),
        ).compile()
        prefill = engine._prefill_step(256).lower(
            params, (pool,), (pool,), _sds((256,), jnp.int32, dev),
            _sds((), jnp.int32, dev), (_sds((mb,), jnp.int32, dev),),
        ).compile()
    finally:
        engine.close()
    return decode, prefill


def test_gpt2_medium_serving_programs_compile_for_v5e(topo, as_on_tpu):
    """The serve cell's decode program and a prefill bucket — GPT-2
    medium, 32 slots x 1,024 positions in 128-token blocks, bf16 pools
    of 1.6 GB each — hold one paged kernel a layer and no copy of a pool
    or of a slot's reserved cache (the programs they replace held 15.3 GB
    and 3.3 GB of temporaries)."""
    cfg = _GPT2_MEDIUM
    decode, prefill = _once(
        "gpt2m-serve", lambda: _gpt2_medium_serving_programs(topo))
    assert decode.as_text().count("tpu_custom_call") == cfg["num_layers"]
    # One call a layer under the name the readers match, and the walk's
    # list (made once a tick) under neither name.
    assert re.findall(_PAGED_CALLS, decode.as_text()) == [
        "paged_decode_attention"] * cfg["num_layers"]
    # Each weight is prefetched whole (the compiler's default cuts it in
    # four): a tick launches 1,352 operations where it launched 2,198.
    assert "slice-start" not in decode.as_text()
    for program in (decode, prefill):
        memory = program.memory_analysis()
        assert memory.temp_size_in_bytes < 2**29
        assert memory.alias_size_in_bytes >= 2 * 1.6e9  # pools in place


def _trinity_mini_serving_programs(topo):
    """``(decode, prefill, pool bytes)`` of the ``trinity-mini-serve``
    cell's engine, the prefill at its 2,560-token bucket."""
    import importlib.util
    import json

    from fluxmpi_tpu.serving import InferenceEngine

    configs = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs")

    def load(name):
        spec = importlib.util.spec_from_file_location(
            name.replace(".", "_"), os.path.join(configs, name))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    with open(os.path.join(configs, "trinity-mini.json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    prog, ref = load("trinity.program.py"), load("trinity.reference.py")
    dev = topo.devices[0]
    params = jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, dev),
        jax.eval_shape(
            lambda key: prog.to_program(ref.make_weights(cfg, key), cfg)[0],
            jax.random.PRNGKey(0),
        ),
    )
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert 8.4e9 < weights < 8.6e9
    engine = InferenceEngine(
        prog.build_model(cfg, "naive"), params, attention="flash", slots=64,
        block_size=512, max_len=8704, check_memory=False,
    )
    try:
        cache = engine.cache
        assert cache.pool_shapes == [(1, 1089, 512, 512), (4, 321, 512, 512)]
        pools = tuple(_sds(s, jnp.bfloat16, dev) for s in cache.pool_shapes)
        decode = engine._decode_step.lower(
            params, pools, pools,
            tuple(_sds((64, k.entries), jnp.int32, dev) for k in cache.kinds),
            _sds((64,), jnp.int32, dev), _sds((64,), jnp.int32, dev),
            # prev: 64 tokens, then 4 expert layers' counts of 128.
            _sds((64 + 4 * 128,), jnp.int32, dev),
            _sds((64,), jnp.bool_, dev),
        ).compile()
        prefill = engine._prefill_step(2560).lower(
            params, pools, pools, _sds((2560,), jnp.int32, dev),
            _sds((), jnp.int32, dev),
            tuple(_sds((k.entries,), jnp.int32, dev) for k in cache.kinds),
        ).compile()
    finally:
        engine.close()
    return decode, prefill, cache.pool_bytes


def test_trinity_mini_serving_programs_compile_for_v5e(topo, as_on_tpu):
    """The ``trinity-mini-serve`` cell's decode program and one prefill
    bucket at the published widths (32 query over 4 K/V heads of 128, 128
    experts top-8, vocabulary 200,192; 64 slots x 8,704 positions in
    512-token blocks): one paged kernel a layer, grouped and windowed,
    the grouped matmul's kernel (``ops/grouped_matmul.py``) three times an
    expert layer, the ring and the full pool updated in place, and
    bfloat16 weights of 8.5 GB beside them within one chip."""
    decode, prefill, pool_bytes = _once(
        "trinity-mini-serve", lambda: _trinity_mini_serving_programs(topo))
    # 5 paged kernels; 4 expert layers x 3 grouped matmuls, each named
    # as the benchmark's readers find XLA's own (``^ragged-dot``).
    text = decode.as_text()
    assert text.count("tpu_custom_call") == 5 + 4 * 3
    # The full layer's walk and the ring's are made once a tick each.
    assert re.findall(_PAGED_CALLS, text) == ["paged_decode_attention"] * 5
    assert "slice-start" not in text  # operands prefetched whole
    assert len(re.findall(r"%ragged-dot-gmm[.\d]* = ", text)) == 4 * 3
    assert len(re.findall(
        r"%ragged-dot-gmm[.\d]* = ", prefill.as_text())) == 4 * 3
    for program, temporaries in ((decode, 2**27), (prefill, 2**30)):
        memory = program.memory_analysis()
        assert memory.temp_size_in_bytes < temporaries
        assert memory.alias_size_in_bytes >= pool_bytes  # in place
        assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
                < 14e9)


@pytest.mark.parametrize("cell", ["gpt2m-serve", "trinity-mini-serve"])
def test_serve_prefill_folds_no_query_for_v5e(topo, as_on_tpu, cell):
    """A prefill hands the flash forward its queries as the projection's
    matmul writes them: no ``copy`` makes a head-shaped array of them,
    whatever the heads (16 of 64; 32 query over 4 K/V heads of 128 with a
    window, after the rotation), and at most one a layer of the keys,
    which the kernel reads row-major (GPT-2's keys have the queries'
    shape: one a layer for both)."""
    if cell == "gpt2m-serve":
        prefill = _once(
            cell, lambda: _gpt2_medium_serving_programs(topo))[1]
        layers, heads, kv_heads, s, d = 24, 16, 16, 256, 64
    else:
        prefill = _once(
            cell, lambda: _trinity_mini_serving_programs(topo))[1]
        layers, heads, kv_heads, s, d = 5, 32, 4, 2560, 128
    text = prefill.as_text()
    assert text.count("tpu_custom_call") >= layers  # the flash forward
    if heads != kv_heads:
        assert _layout_copies(text, 1, heads, s, d) == 0
    assert _layout_copies(text, 1, kv_heads, s, d) <= layers


def _load_config_module(name):
    import importlib.util

    configs = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs")
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), os.path.join(configs, name))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module, configs


# sarvam-105b-serve's kernels alone: the absorbed decode against the
# cell's pool of latent rows (576 padded to 640 lanes), and the
# un-absorbed prefill's flash forward with keys of 192 and values of 128
# (one block, some, the longest prompt).
def test_paged_latent_decode_kernel_compiles_for_v5e(topo):
    from fluxmpi_tpu.ops.paged_attention import paged_latent_decode_attention

    dev = topo.devices[0]
    pool = _sds((5, 817, 1024, 640), jnp.bfloat16, dev)

    def attend(q_abs, q_rope, pool, tables, lengths):
        return paged_latent_decode_attention(
            q_abs, q_rope, pool, tables, lengths, layer=4, interpret=False)

    compiled = jax.jit(attend).lower(
        _sds((48, 64, 512), jnp.bfloat16, dev),
        _sds((48, 64, 64), jnp.bfloat16, dev), pool,
        _sds((48, 17), jnp.int32, dev), _sds((48,), jnp.int32, dev),
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    # One findable name, the jitted wrapper's and the kernel's.
    assert len(re.findall(r"%paged_latent_decode[.\d]* = ", text)) == 1
    # The pool is read where it lies: nothing pool-sized is made (a pool
    # of 576-lane rows is copied whole: 7.1 GB at 1,089 blocks; the
    # queries, prefetched while the walk's list is made, are 1.9 MB).
    assert compiled.memory_analysis().temp_size_in_bytes < 2**22


@pytest.mark.parametrize("s", [1024, 5120, 16384])
def test_flash_latent_prefill_compiles_for_v5e(topo, s):
    dev = topo.devices[0]

    def attend(q, k, v):
        return flash_attention(q, k, v, interpret=False, causal=True)

    compiled = jax.jit(attend).lower(
        _sds((1, s, 64, 192), jnp.bfloat16, dev),
        _sds((1, s, 64, 192), jnp.bfloat16, dev),
        _sds((1, s, 64, 128), jnp.bfloat16, dev),
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.output_shardings is not None


def test_sarvam_105b_serving_programs_compile_for_v5e(topo, as_on_tpu):
    """The ``sarvam-105b-serve`` cell's decode program and its LONGEST
    prefill at the published widths (64 heads, latent 512 + rotary 64,
    16 of 128 experts of [4096, 2048] held, 32,768 rows of the
    vocabulary; 48 slots x 17,408 positions in 1,024-blocks): one latent
    kernel a layer, the grouped matmul's kernel three times an expert
    layer (its column-split tiles: a [4096, 2048] matrix is four weight
    blocks), the one pool of rows updated in place, and everything under
    14.5 GB beside 5.31 GB of bfloat16 weights. (64 slots: 16.1 GB.)"""
    import json

    from fluxmpi_tpu.serving import InferenceEngine

    prog, configs = _load_config_module("sarvam.program.py")
    ref, _ = _load_config_module("sarvam.reference.py")
    with open(os.path.join(configs, "sarvam-105b.json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    with open(os.path.join(os.path.dirname(configs), "workloads",
                           "sarvam-105b-serve.json"), encoding="utf-8") as f:
        geometry = json.load(f)["engine"]
    dev = topo.devices[0]
    params = jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, dev),
        jax.eval_shape(
            lambda key: prog.to_program(ref.make_weights(cfg, key), cfg)[0],
            jax.random.PRNGKey(0),
        ),
    )
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert 5.3e9 < weights < 5.33e9
    slots, bucket = geometry["slots"], 16384
    engine = InferenceEngine(
        prog.build_model(cfg, "naive"), params, attention="flash",
        slots=slots, block_size=geometry["block_size"],
        max_len=geometry["max_len"], check_memory=False,
    )
    try:
        cache = engine.cache
        assert cache.pool_shapes == [(5, 1 + slots * 17, 1024, 640)]
        assert cache.pool_bytes == 5 * (1 + slots * 17) * 1024 * 640 * 2
        pools = tuple(_sds(s, jnp.bfloat16, dev) for s in cache.pool_shapes)
        decode = engine._decode_step.lower(
            params, pools, (None,),
            tuple(_sds((slots, k.entries), jnp.int32, dev)
                  for k in cache.kinds),
            _sds((slots,), jnp.int32, dev), _sds((slots,), jnp.int32, dev),
            # prev: the tokens, then 4 expert layers' counts of 16 held.
            _sds((slots + 4 * 16,), jnp.int32, dev),
            _sds((slots,), jnp.bool_, dev),
        ).compile()
        prefill = engine._prefill_step(bucket).lower(
            params, pools, (None,), _sds((bucket,), jnp.int32, dev),
            _sds((), jnp.int32, dev),
            tuple(_sds((k.entries,), jnp.int32, dev) for k in cache.kinds),
        ).compile()
    finally:
        engine.close()
    text = decode.as_text()
    assert text.count("tpu_custom_call") == 5 + 4 * 4
    assert "slice-start" not in text  # operands prefetched whole
    assert len(re.findall(r"%paged_latent_decode[.\d]* = ", text)) == 5
    assert re.findall(_PAGED_CALLS, text) == ["paged_latent_decode"] * 5
    assert len(re.findall(r"%ragged-dot-gmm[.\d]* = ", text)) == 4 * 3
    # 16 of the router's 128 experts held: the combine reads live tiles.
    assert len(re.findall(r"%expert-combine[.\d]* = ", text)) == 4
    # The prefill: one flash forward a layer, no latent decode kernel.
    text = prefill.as_text()
    assert text.count("tpu_custom_call") == 5 + 4 * 4
    assert not re.findall(r"%paged_latent_decode[.\d]* = ", text)
    for program, temporaries in ((decode, 2**28), (prefill, 4 * 2**30)):
        memory = program.memory_analysis()
        assert memory.temp_size_in_bytes < temporaries
        assert memory.alias_size_in_bytes >= cache.pool_bytes  # in place
        assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
                < 14.5e9)


def _pool_sized(text, *shapes):
    """The instructions of a compiled program's text, by opcode, whose
    RESULT is a whole pool of one of ``shapes``: ``{opcode: count}``."""
    found = {}
    for shape in shapes:
        dims = ",".join(str(n) for n in shape)
        for opcode in re.findall(
                r"= \w+\[%s\]\S* ([\w\-]+)\(" % re.escape(dims), text):
            found[opcode] = found.get(opcode, 0) + 1
    return found


def test_ssm_state_update_kernel_compiles_for_v5e(topo):
    """The decode tick's state update at the published widths (128 heads
    of 64 over a state of 128; 9 layers x 129 entries of float32 = 4.87
    GB, and beside them the tails, 198 lane tiles of bfloat16 an entry):
    one kernel, both pools aliased in and out, no copy of either and no
    scatter."""
    from fluxmpi_tpu.ops.ssm import ssm_state_update

    dev = topo.devices[0]
    slots, layers, heads, head_dim, d_state = 128, 9, 128, 64, 128
    pool = (layers, slots + 1, d_state, heads * head_dim)
    tails = (layers, slots + 1, 198, 128)

    def update(pool, tail_pool, entries, tail, x, step, decay, b, c):
        return ssm_state_update(pool, tail_pool, entries, tail, x, step,
                                decay, b, c, layer=3, interpret=False)

    compiled = jax.jit(update, donate_argnums=(0, 1)).lower(
        _sds(pool, jnp.float32, dev), _sds(tails, jnp.bfloat16, dev),
        _sds((slots,), jnp.int32, dev),
        _sds((slots, 3, 8448), jnp.bfloat16, dev),
        _sds((slots, heads, head_dim), jnp.float32, dev),
        _sds((slots, heads), jnp.float32, dev),
        _sds((slots, heads), jnp.float32, dev),
        _sds((slots, d_state), jnp.float32, dev),
        _sds((slots, d_state), jnp.float32, dev),
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert len(re.findall(r"%ssm_state_update[.\d]* = ", text)) == 1
    # Whole pools come out of the kernel and nothing else: no scatter
    # into one, no copy of one.
    assert set(_pool_sized(text, pool, tails)) <= {
        "custom-call", "parameter", "get-tuple-element"}
    memory = compiled.memory_analysis()
    pool_bytes = layers * (slots + 1) * (
        heads * head_dim * d_state * 4 + 198 * 128 * 2)
    assert memory.alias_size_in_bytes >= pool_bytes  # in place
    assert memory.temp_size_in_bytes < 2**24


def test_granite_4_0_h_small_serving_programs_compile_for_v5e(topo, as_on_tpu):
    """The ``granite-4.0-h-small-serve`` cell's decode program and its
    LONGEST prefill at the published widths (9 Mamba-2 layers of 128 heads
    of 64 over a state of 128 around one attention layer of 32 over 8
    heads of 128, 18 of 72 experts of [4096, 768] held, 25,088 rows of
    the tied vocabulary; 128 slots x 2,560 positions in 256-blocks): one
    state-update kernel a Mamba layer and one paged decode kernel, the
    grouped matmul's kernel three times a layer, the state pool, the
    tails and the K/V updated in place (the tails by the state-update
    kernel: the tick holds no scatter into their pool and no copy of a
    whole pool), and everything under 14.5 GB beside 5.91 GB of bfloat16
    weights. (160 slots: 13.74 GB of arguments + 1.28 GB of the
    prefill's temporaries = 15.0 GB.)"""
    import json

    from fluxmpi_tpu.serving import InferenceEngine

    prog, configs = _load_config_module("granite.program.py")
    ref, _ = _load_config_module("granite.reference.py")
    with open(os.path.join(configs, "granite-4.0-h-small.json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    with open(os.path.join(os.path.dirname(configs), "workloads",
                           "granite-4.0-h-small-serve.json"),
              encoding="utf-8") as f:
        geometry = json.load(f)["engine"]
    dev = topo.devices[0]
    params = jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, dev),
        jax.eval_shape(
            lambda key: prog.to_program(ref.make_weights(cfg, key), cfg)[0],
            jax.random.PRNGKey(0),
        ),
    )
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert 5.90e9 < weights < 5.92e9
    slots, bucket = geometry["slots"], 2048
    engine = InferenceEngine(
        prog.build_model(cfg, "naive"), params, attention="flash",
        slots=slots, block_size=geometry["block_size"],
        max_len=geometry["max_len"], check_memory=False,
    )
    try:
        cache = engine.cache
        assert cache.pool_shapes == [(1, 1 + slots * 10, 256, 1024),
                                     (9, 1 + slots, 128, 8192)]
        state, tail = 128 * 64 * 128 * 4, 3 * 8448 * 2
        assert cache.pool_bytes == (
            2 * (1 + slots * 10) * 256 * 1024 * 2
            + (1 + slots) * 9 * (state + tail))
        k_pools = (_sds(cache.pool_shapes[0], jnp.bfloat16, dev),
                   _sds(cache.pool_shapes[1], jnp.float32, dev))
        tails = (9, 1 + slots, cache.tail_tiles, 128)
        assert cache.tail_tiles * 128 == 3 * 8448  # whole tiles, no padding
        v_pools = (_sds(cache.pool_shapes[0], jnp.bfloat16, dev),
                   _sds(tails, jnp.bfloat16, dev))
        decode = engine._decode_step.lower(
            params, k_pools, v_pools,
            tuple(_sds((slots, k.entries), jnp.int32, dev)
                  for k in cache.kinds),
            _sds((slots,), jnp.int32, dev), _sds((slots,), jnp.int32, dev),
            # prev: the tokens, then 10 expert layers' counts of 18 held.
            _sds((slots + 10 * 18,), jnp.int32, dev),
            _sds((slots,), jnp.bool_, dev),
        ).compile()
        prefill = engine._prefill_step(bucket).lower(
            params, k_pools, v_pools, _sds((bucket,), jnp.int32, dev),
            _sds((), jnp.int32, dev),
            tuple(_sds((k.entries,), jnp.int32, dev) for k in cache.kinds),
        ).compile()
    finally:
        engine.close()
    text = decode.as_text()
    assert text.count("tpu_custom_call") == 9 + 1 + 10 * 4
    assert re.findall(_PAGED_CALLS, text) == ["paged_decode_attention"]
    assert "slice-start" not in text  # operands prefetched whole
    assert len(re.findall(r"%ssm_state_update[.\d]* = ", text)) == 9
    assert len(re.findall(r"%ragged-dot-gmm[.\d]* = ", text)) == 10 * 3
    assert len(re.findall(r"%expert-combine[.\d]* = ", text)) == 10
    # A whole state or tail pool is the result of the nine kernels and of
    # nothing else (no scatter of tails, no copy between tilings), and
    # no pool of any kind is copied.
    assert set(_pool_sized(text, cache.pool_shapes[1], tails)) <= {
        "custom-call", "parameter", "get-tuple-element"}
    assert "copy" not in _pool_sized(text, *cache.pool_shapes, tails)
    # The prefill: one flash forward (the attention layer), the chunked
    # scan in plain XLA, no state-update kernel.
    text = prefill.as_text()
    assert text.count("tpu_custom_call") == 1 + 10 * 4
    assert not re.findall(r"%ssm_state_update[.\d]* = ", text)
    for program, temporaries in ((decode, 2**28), (prefill, 3 * 2**29)):
        memory = program.memory_analysis()
        assert memory.temp_size_in_bytes < temporaries
        assert memory.alias_size_in_bytes >= cache.pool_bytes  # in place
        assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
                < 14.5e9)


# nemotron-3-nano-serve's expert layer: 64 held experts of [1856, 2688]
# both ways (the up projection transposed: 1,856 is 14.5 lane tiles); 192
# slots x top-6 rows in the decode tick, the shortest and the longest
# prompt bucket x 6 in a prefill.
@pytest.mark.parametrize("tokens", [192, 256, 2048])
def test_relu2_expert_kernels_compile_for_v5e(topo, as_on_tpu, tokens):
    from fluxmpi_tpu.ops import grouped_matmul as gm

    dev = topo.devices[0]
    rows, d, width, held = tokens * 6, 2688, 1856, 64
    tile = gm.live_row_tile(rows)
    assert gm._tile_rule(rows, d, width, 2) == (tile, 128, 640)
    assert gm._tile_rule(rows, width, d, 2) == (tile, 128, 896)
    assert gm.row_tile(rows, d, width, jnp.bfloat16, transposed=True) == tile
    assert gm.row_tile(rows, d, width, jnp.bfloat16) is None  # 1,856 lanes

    def experts(u, w_up, w_down, sizes, token, scale, live):
        up = gm.grouped_matmul(u, w_up, sizes, transposed=True)
        y = gm.grouped_matmul(
            jnp.square(jax.nn.relu(up)).astype(jnp.bfloat16), w_down, sizes)
        return gm.combine(y, token, scale, live, tokens)

    weights = _sds((held, width, d), jnp.bfloat16, dev)
    compiled = jax.jit(experts).lower(
        _sds((rows, d), jnp.bfloat16, dev), weights, weights,
        _sds((held,), jnp.int32, dev), _sds((rows,), jnp.int32, dev),
        _sds((rows,), jnp.float32, dev), _sds((), jnp.int32, dev),
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    assert len(re.findall(r"%ragged-dot-gmm[.\d]* = ", text)) == 2
    assert len(re.findall(r"%expert-combine[.\d]* = ", text)) == 1
    # Both matrices are read where they lie (the hidden size on their
    # lanes): no copy of 638 MB of weights into a padded layout.
    assert "copy" not in _pool_sized(text, (held, width, d))
    assert compiled.memory_analysis().temp_size_in_bytes < tokens * 2**17


def test_ssm_state_update_kernel_with_groups_compiles_for_v5e(topo):
    """The state update at nemotron-3-nano's widths: 64 heads of 64 over
    a state of 128, EIGHT groups of B and C (a group 512 lanes, four
    tiles of the walk), 4 layers x 193 entries, tails of 144 lane
    tiles."""
    from fluxmpi_tpu.ops.ssm import ssm_state_update

    dev = topo.devices[0]
    slots, layers, heads, head_dim, d_state, groups = 192, 4, 64, 64, 128, 8
    pool = (layers, slots + 1, d_state, heads * head_dim)
    tails = (layers, slots + 1, 144, 128)

    def update(pool, tail_pool, entries, tail, x, step, decay, b, c):
        return ssm_state_update(pool, tail_pool, entries, tail, x, step,
                                decay, b, c, layer=2, interpret=False)

    compiled = jax.jit(update, donate_argnums=(0, 1)).lower(
        _sds(pool, jnp.float32, dev), _sds(tails, jnp.bfloat16, dev),
        _sds((slots,), jnp.int32, dev),
        _sds((slots, 3, 6144), jnp.bfloat16, dev),
        _sds((slots, heads, head_dim), jnp.float32, dev),
        _sds((slots, heads), jnp.float32, dev),
        _sds((slots, heads), jnp.float32, dev),
        _sds((slots, groups, d_state), jnp.float32, dev),
        _sds((slots, groups, d_state), jnp.float32, dev),
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert len(re.findall(r"%ssm_state_update[.\d]* = ", text)) == 1
    # The state pool is moved where it lies. (The tails, 28 MB here, this
    # free-standing program prefetches whole into fast memory and writes
    # back, one ``copy-start`` each way: the compiler's placement, no
    # re-tiling; the cell's decode program is held to none below.)
    assert set(_pool_sized(text, pool)) <= {
        "custom-call", "parameter", "get-tuple-element"}
    assert "copy" not in _pool_sized(text, pool, tails)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= layers * (slots + 1) * (
        heads * head_dim * d_state * 4 + 144 * 128 * 2)  # in place
    assert memory.temp_size_in_bytes < 2**24


def test_nemotron_3_nano_serving_programs_compile_for_v5e(topo, as_on_tpu):
    """The ``nemotron-3-nano-serve`` cell's decode program and its LONGEST
    prefill at the published widths (layers of one sublayer: 4 Mamba-2 of
    64 heads of 64 over a state of 128 in 8 groups, 4 expert layers
    holding 64 of 128 un-gated experts of [1856, 2688], one attention
    layer of 32 over 2 heads of 128; 65,536 rows of the vocabulary; the
    cell's slots x 6,144 positions in 256-blocks): one state-update
    kernel a Mamba layer, one paged decode kernel, the grouped matmul's
    kernel TWICE an expert layer and its combine once, every pool
    updated in place, no copy of a pool or of an expert matrix, and
    everything under 14.5 GB beside 6.33 GB of bfloat16 weights."""
    import json

    from fluxmpi_tpu.serving import InferenceEngine

    prog, configs = _load_config_module("nemotron.program.py")
    ref, _ = _load_config_module("nemotron.reference.py")
    with open(os.path.join(configs, "nemotron-3-nano-30b-a3b.json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    with open(os.path.join(os.path.dirname(configs), "workloads",
                           "nemotron-3-nano-serve.json"),
              encoding="utf-8") as f:
        geometry = json.load(f)["engine"]
    dev = topo.devices[0]
    params = jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, dev),
        jax.eval_shape(
            lambda key: prog.to_program(ref.make_weights(cfg, key), cfg)[0],
            jax.random.PRNGKey(0),
        ),
    )
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert 6.32e9 < weights < 6.34e9
    slots, bucket = geometry["slots"], 2048
    engine = InferenceEngine(
        prog.build_model(cfg, "naive"), params, attention="flash",
        slots=slots, block_size=geometry["block_size"],
        max_len=geometry["max_len"], check_memory=False,
    )
    try:
        cache = engine.cache
        # Nine layers, five of them keep something: the expert layers
        # have no pool, no table and no entry.
        assert cache.num_layers == 5
        assert cache.pool_shapes == [(1, 1 + slots * 24, 256, 256),
                                     (4, 1 + slots, 128, 4096)]
        state, tail = 64 * 64 * 128 * 4, 3 * 6144 * 2
        assert cache.pool_bytes == (
            2 * (1 + slots * 24) * 256 * 256 * 2
            + (1 + slots) * 4 * (state + tail))
        k_pools = (_sds(cache.pool_shapes[0], jnp.bfloat16, dev),
                   _sds(cache.pool_shapes[1], jnp.float32, dev))
        tails = (4, 1 + slots, cache.tail_tiles, 128)
        assert cache.tail_tiles * 128 == 3 * 6144  # whole tiles, no padding
        v_pools = (_sds(cache.pool_shapes[0], jnp.bfloat16, dev),
                   _sds(tails, jnp.bfloat16, dev))
        decode = engine._decode_step.lower(
            params, k_pools, v_pools,
            tuple(_sds((slots, k.entries), jnp.int32, dev)
                  for k in cache.kinds),
            _sds((slots,), jnp.int32, dev), _sds((slots,), jnp.int32, dev),
            # prev: the tokens, then 4 expert layers' counts of 64 held.
            _sds((slots + 4 * 64,), jnp.int32, dev),
            _sds((slots,), jnp.bool_, dev),
        ).compile()
        prefill = engine._prefill_step(bucket).lower(
            params, k_pools, v_pools, _sds((bucket,), jnp.int32, dev),
            _sds((), jnp.int32, dev),
            tuple(_sds((k.entries,), jnp.int32, dev) for k in cache.kinds),
        ).compile()
    finally:
        engine.close()
    text = decode.as_text()
    assert text.count("tpu_custom_call") == 4 + 1 + 4 * 3
    assert re.findall(_PAGED_CALLS, text) == ["paged_decode_attention"]
    assert "slice-start" not in text  # operands prefetched whole
    assert len(re.findall(r"%ssm_state_update[.\d]* = ", text)) == 4
    assert len(re.findall(r"%ragged-dot-gmm[.\d]* = ", text)) == 4 * 2
    assert len(re.findall(r"%expert-combine[.\d]* = ", text)) == 4
    # The state pool is the result of the four kernels and of nothing
    # else. The tails (28 MB) the compiler prefetches whole into fast
    # memory around each kernel (``copy-start`` / ``copy-done``, the same
    # tiling both ways; granite's 58 MB it leaves where they lie):
    # PERF.md §7.
    assert set(_pool_sized(text, cache.pool_shapes[1])) <= {
        "custom-call", "parameter", "get-tuple-element"}
    assert set(_pool_sized(text, tails)) <= {
        "custom-call", "parameter", "get-tuple-element", "copy-done"}
    for program in (decode, prefill):
        assert "copy" not in _pool_sized(
            program.as_text(), *cache.pool_shapes, tails, (64, 1856, 2688))
    # The prefill: one flash forward (the attention layer), the chunked
    # scan in plain XLA, no state-update kernel.
    text = prefill.as_text()
    assert text.count("tpu_custom_call") == 1 + 4 * 3
    assert not re.findall(r"%ssm_state_update[.\d]* = ", text)
    for program, temporaries in ((decode, 2**28), (prefill, 3 * 2**29)):
        memory = program.memory_analysis()
        assert memory.temp_size_in_bytes < temporaries
        assert memory.alias_size_in_bytes >= cache.pool_bytes  # in place
        assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
                < 14.5e9)


def test_ssm_state_update_kernel_with_a_state_of_256_compiles_for_v5e(topo):
    """The decode tick's state update at ``falcon-h1-34b``'s widths: 32
    heads of 128 over a state of 256 (``d_state`` twice a lane tile on the
    sublanes, 4,096 lanes: 2 groups of 16 tiles), 4 sublayers x 97 entries
    of float32, the tails 120 whole lane tiles an entry."""
    from fluxmpi_tpu.ops.ssm import ssm_state_update

    dev = topo.devices[0]
    slots, layers, heads, head_dim, d_state, groups = 96, 4, 32, 128, 256, 2
    pool = (layers, slots + 1, d_state, heads * head_dim)
    tails = (layers, slots + 1, 120, 128)

    def update(pool, tail_pool, entries, tail, x, step, decay, b, c):
        return ssm_state_update(pool, tail_pool, entries, tail, x, step,
                                decay, b, c, layer=2, interpret=False)

    compiled = jax.jit(update, donate_argnums=(0, 1)).lower(
        _sds(pool, jnp.float32, dev), _sds(tails, jnp.bfloat16, dev),
        _sds((slots,), jnp.int32, dev),
        _sds((slots, 3, 5120), jnp.bfloat16, dev),
        _sds((slots, heads, head_dim), jnp.float32, dev),
        _sds((slots, heads), jnp.float32, dev),
        _sds((slots, heads), jnp.float32, dev),
        _sds((slots, groups, d_state), jnp.float32, dev),
        _sds((slots, groups, d_state), jnp.float32, dev),
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert len(re.findall(r"%ssm_state_update[.\d]* = ", text)) == 1
    in_place = {"custom-call", "parameter", "get-tuple-element"}
    assert set(_pool_sized(text, pool)) <= in_place
    # The tail pool is small enough here (11.9 MB) that the compiler
    # prefetches it whole into fast memory and writes it back (an
    # asynchronous copy each way, 15 us at the chip's bandwidth); no
    # synchronous copy, no change of tiling.
    assert set(_pool_sized(text, tails)) <= in_place | {"copy-done"}
    memory = compiled.memory_analysis()
    pool_bytes = layers * (slots + 1) * (
        heads * head_dim * d_state * 4 + 120 * 128 * 2)
    assert memory.alias_size_in_bytes >= pool_bytes  # in place
    assert memory.temp_size_in_bytes < 2**24


def test_falcon_h1_34b_serving_programs_compile_for_v5e(topo, as_on_tpu):
    """The ``falcon-h1-34b-serve`` cell's decode program and its LONGEST
    prefill at the published widths (4 layers, each a Mamba-2 mixer of 32
    heads of 128 over a state of 256 in 2 groups BESIDE attention of 20
    over 4 heads of 128, an MLP of 21,504, an untied head of 261,120; the
    cell's slots x 3,072 positions in 512-blocks): a layer's TWO keeping
    sublayers are one state-update kernel and one paged decode kernel a
    tick, both pools of both kinds updated in place, and everything under
    14.5 GB beside 8.79 GB of bfloat16 weights."""
    import json

    from fluxmpi_tpu.serving import InferenceEngine

    prog, configs = _load_config_module("falcon.program.py")
    ref, _ = _load_config_module("falcon.reference.py")
    with open(os.path.join(configs, "falcon-h1-34b.json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    with open(os.path.join(os.path.dirname(configs), "workloads",
                           "falcon-h1-34b-serve.json"),
              encoding="utf-8") as f:
        geometry = json.load(f)["engine"]
    dev = topo.devices[0]
    params = jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, dev),
        jax.eval_shape(
            lambda key: prog.to_program(ref.make_weights(cfg, key), cfg)[0],
            jax.random.PRNGKey(0),
        ),
    )
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert 8.78e9 < weights < 8.80e9
    slots, bucket = geometry["slots"], 2048
    engine = InferenceEngine(
        prog.build_model(cfg, "naive"), params, attention="flash",
        slots=slots, block_size=geometry["block_size"],
        max_len=geometry["max_len"], check_memory=False,
    )
    try:
        cache = engine.cache
        assert cache.num_layers == 8  # the keeping sublayers
        assert cache.pool_shapes == [(4, 1 + slots * 6, 512, 512),
                                     (4, 1 + slots, 256, 4096)]
        state, tail = 32 * 128 * 256 * 4, 3 * 5120 * 2
        assert cache.pool_bytes == (
            2 * 4 * (1 + slots * 6) * 512 * 512 * 2
            + (1 + slots) * 4 * (state + tail))
        k_pools = (_sds(cache.pool_shapes[0], jnp.bfloat16, dev),
                   _sds(cache.pool_shapes[1], jnp.float32, dev))
        tails = (4, 1 + slots, cache.tail_tiles, 128)
        assert cache.tail_tiles == 120  # whole tiles, no padding
        v_pools = (_sds(cache.pool_shapes[0], jnp.bfloat16, dev),
                   _sds(tails, jnp.bfloat16, dev))
        decode = engine._decode_step.lower(
            params, k_pools, v_pools,
            tuple(_sds((slots, k.entries), jnp.int32, dev)
                  for k in cache.kinds),
            _sds((slots,), jnp.int32, dev), _sds((slots,), jnp.int32, dev),
            _sds((slots,), jnp.int32, dev), _sds((slots,), jnp.bool_, dev),
        ).compile()
        prefill = engine._prefill_step(bucket).lower(
            params, k_pools, v_pools, _sds((bucket,), jnp.int32, dev),
            _sds((), jnp.int32, dev),
            tuple(_sds((k.entries,), jnp.int32, dev) for k in cache.kinds),
        ).compile()
    finally:
        engine.close()
    text = decode.as_text()
    assert text.count("tpu_custom_call") == 4 + 4
    assert re.findall(_PAGED_CALLS, text) == ["paged_decode_attention"] * 4
    assert "slice-start" not in text  # operands prefetched whole
    assert len(re.findall(r"%ssm_state_update[.\d]* = ", text)) == 4
    in_place = {"custom-call", "parameter", "get-tuple-element"}
    assert set(_pool_sized(text, cache.pool_shapes[1])) <= in_place
    # The tails (11.9 MB in all) are prefetched whole into fast memory
    # and written back, asynchronously: see the kernel's own test.
    assert set(_pool_sized(text, tails)) <= in_place | {"copy-done"}
    assert "copy" not in _pool_sized(text, *cache.pool_shapes, tails)
    # The prefill: one flash forward a layer, the chunked scan in plain
    # XLA, no state-update kernel.
    text = prefill.as_text()
    assert text.count("tpu_custom_call") == 4
    assert not re.findall(r"%ssm_state_update[.\d]* = ", text)
    for program, temporaries in ((decode, 2**28), (prefill, 3 * 2**29)):
        memory = program.memory_analysis()
        assert memory.temp_size_in_bytes < temporaries
        assert memory.alias_size_in_bytes >= cache.pool_bytes  # in place
        assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
                < 14.5e9)


def _lm_state(cfg, optimizer):
    model = chip_smoke._lm(cfg)
    params = jax.eval_shape(
        lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32), train=False),
        jax.random.PRNGKey(0),
    )
    return jax.eval_shape(lambda p: TrainState.create(p, optimizer), params)


def _lm_batch(cfg, batch):
    tokens = jax.ShapeDtypeStruct((batch, cfg["max_len"]), jnp.int32)
    return tokens, tokens


def test_gpt2_small_train_step_compiles_for_v5e(topo, as_on_tpu):
    """The smoke's own step — GPT-2 small, bf16, flash attention, fused
    CE head, AdamW, 8 x 1,024 tokens — through make_train_step."""
    cfg = chip_smoke.GPT2_SMALL
    flash_loss, _ = chip_smoke._lm_losses(cfg)
    optimizer = optax.adamw(1e-3)
    mesh = Mesh(np.asarray(topo.devices[:1]), (config.DP_AXIS_NAME,))
    step = make_train_step(flash_loss, optimizer, mesh=mesh)
    compiled = step.lower(
        _lm_state(cfg, optimizer), _lm_batch(cfg, 8)
    ).compile()
    # Forward, dq and dkv for each of the 12 layers.
    assert compiled.as_text().count("tpu_custom_call") == 36
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2**30


def test_gpt2_medium_train_step_folds_heads_without_copies_for_v5e(
        topo, as_on_tpu):
    """The ``gpt2m-train`` cell's own step (GPT-2 medium through
    ``benchmarks/configs/gpt2.program.py``, AdamW, 8 x 1,024 tokens):
    three kernels a layer, and of the seven ``copy`` instructions a layer
    that folded Q, K, V, dO, dq, dk and dv row-major for them (5.7% of
    the step, PERF.md §6, PR 44) at most two: K for the forward, V for
    the backward."""
    import json

    prog, configs = _load_config_module("gpt2.program.py")
    with open(os.path.join(configs, "gpt2-medium.json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    model = prog.build_model(cfg)
    optimizer = prog.make_optimizer({"learning_rate": 1e-4})
    params = jax.eval_shape(
        lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32), train=False),
        jax.random.PRNGKey(0),
    )
    state = jax.eval_shape(lambda p: TrainState.create(p, optimizer), params)
    tokens = jax.ShapeDtypeStruct((8, cfg["n_positions"]), jnp.int32)
    mesh = Mesh(np.asarray(topo.devices[:1]), (config.DP_AXIS_NAME,))
    step = make_train_step(prog.make_loss(model), optimizer, mesh=mesh)
    text = step.lower(state, (tokens, tokens)).compile().as_text()
    layers, heads = cfg["n_layer"], cfg["n_head"]
    assert text.count("tpu_custom_call") == 3 * layers
    copies = _layout_copies(
        text, 8, heads, cfg["n_positions"], cfg["n_embd"] // heads)
    assert copies <= 2 * layers


def test_dp4_step_compiles_for_v5e(topo, as_on_tpu):
    """Four chips, data parallel, two layers: the kernels sit inside a
    program XLA partitions, which it cannot do to a Mosaic kernel —
    make_train_step has them run per device, and the gradients meet in
    an all-reduce."""
    cfg = {**chip_smoke.GPT2_SMALL, "num_layers": 2}
    flash_loss, _ = chip_smoke._lm_losses(cfg)
    optimizer = optax.adamw(1e-3)
    plan = ParallelConfig(dp=4).resolve(topo.devices)
    step = make_train_step(flash_loss, optimizer, parallel=plan)
    text = step.lower(
        _lm_state(cfg, optimizer), _lm_batch(cfg, 8)
    ).compile().as_text()
    assert "all-reduce" in text
    assert text.count("tpu_custom_call") == 6
