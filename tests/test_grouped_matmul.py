"""The routed experts' grouped matmul (``ops/grouped_matmul.py``): the
Pallas kernel in interpret mode at small aligned shapes against
``jax.lax.ragged_dot`` in float32, the walk it prefetches, the count of
its weight visits, and the rule that chooses between the kernel and
``ragged_dot``. What the chip's compiler says of the kernel at the real
widths is ``tests/test_tpu_compile.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _oracles import poisoned_past_the_groups
from fluxmpi_tpu.ops import grouped_matmul as gm

# (rows, k, n), tiles (tile_rows, sub_rows, tile_n), group sizes.
_CASES = {
    "uniform": ((64, 128, 256), (32, 16, 128), [16, 16, 16, 16]),
    "first_group_empty": ((64, 128, 256), (32, 16, 128), [0, 40, 8, 16]),
    "last_group_empty": ((64, 128, 256), (32, 16, 128), [30, 20, 14, 0]),
    "runs_of_empty_groups": ((64, 128, 128), (32, 16, 128),
                             [0, 0, 24, 0, 0, 0, 40, 0]),
    "one_group_holds_every_row": ((64, 128, 256), (32, 16, 256),
                                  [0, 0, 64, 0]),
    "tail_tile_never_visited": ((64, 128, 256), (32, 16, 128), [3, 5, 0, 7]),
    "no_row_at_all": ((64, 128, 256), (32, 16, 128), [0, 0, 0, 0]),
    "group_straddles_row_tiles": ((96, 128, 128), (32, 16, 128),
                                  [20, 50, 26]),
    "group_straddles_sub_tiles": ((64, 128, 128), (64, 16, 128),
                                  [7, 20, 9, 28]),
    "three_rows_a_group": ((64, 256, 128), (64, 16, 128),
                           [3, 2, 4, 3, 0, 3, 5, 1, 3, 3, 4, 2, 3, 3, 2, 3]),
    "one_row_tile": ((32, 128, 384), (32, 32, 128), [10, 0, 22]),
    # Widths that are no whole number of column blocks or lane tiles (the
    # un-gated experts of 1,856 = 14.5 tiles between a hidden size of
    # 2,688 = 21): a last column block past the width, a contraction of
    # one and a half tiles, and weights held TRANSPOSED ([groups, n, k]),
    # their narrow width on the sublanes.
    "last_column_block_partial": ((64, 128, 384), (32, 16, 256),
                                  [20, 0, 30, 14]),
    "contraction_of_one_and_a_half_tiles": ((64, 192, 384), (32, 16, 128),
                                            [0, 40, 8, 16]),
    "transposed_weights": ((64, 384, 256), (32, 16, 128), [16, 0, 30, 18],
                           True),
    "transposed_one_and_a_half_tiles_wide": ((64, 384, 192), (32, 16, 128),
                                             [3, 50, 0, 11], True),
    "transposed_three_blocks_of_uneven_width": ((96, 256, 320), (32, 16, 128),
                                                [20, 50, 26], True),
}


def _operands(shape, sizes, seed=0, transposed=False):
    rows, k, n = shape
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (rows, k), jnp.bfloat16)
    w = jax.random.normal(
        kw, (len(sizes), n, k) if transposed else (len(sizes), k, n),
        jnp.bfloat16)
    return x, w, jnp.asarray(sizes, jnp.int32)


def _reference(x, w, sizes, transposed=False):
    out = jax.lax.ragged_dot(
        x.astype(jnp.float32),
        (jnp.swapaxes(w, 1, 2) if transposed else w).astype(jnp.float32),
        sizes, precision=jax.lax.Precision.HIGHEST,
    )
    return _live(out, sizes)


def _live(out, sizes):
    """What a caller may read: the groups' rows (the rest made zero)."""
    live = jnp.arange(out.shape[0])[:, None] < jnp.sum(sizes)
    return jnp.where(live, out, 0.0)


@pytest.mark.parametrize("name", sorted(_CASES))
def test_kernel_matches_ragged_dot(name):
    shape, tiles, sizes, *transposed = _CASES[name]
    transposed = bool(transposed)
    x, w, sizes = _operands(shape, sizes, transposed=transposed)
    got = gm._gmm(x, w, sizes, tiles=tiles, transposed=transposed,
                  interpret=True)
    assert got.dtype == jnp.float32 and got.shape == (shape[0], shape[2])
    np.testing.assert_allclose(
        _live(got, sizes), _reference(x, w, sizes, transposed),
        rtol=1e-5, atol=1e-4
    )
    # A row tile that holds a row of the groups is zeroed past them; one
    # that holds none was never written (the interpreter leaves NaN).
    total, tile_rows = int(np.sum(np.asarray(sizes))), tiles[0]
    worked = -(-total // tile_rows) * tile_rows
    assert not np.any(np.asarray(got[total:worked]))
    assert np.all(np.isnan(np.asarray(got[max(worked, tile_rows):])))


@pytest.mark.parametrize("name", sorted(_CASES))
def test_walk_visits_each_touched_group_once_a_row_tile(name):
    shape, tiles, sizes, *_ = _CASES[name]
    rows, tile_rows, groups = shape[0], tiles[0], len(sizes)
    tile, weight, lo, hi, fresh = (
        np.asarray(a) for a in gm._visits(
            jnp.asarray(sizes, jnp.int32), rows, tile_rows)
    )
    assert len(tile) == rows // tile_rows + groups
    ends = np.cumsum(sizes)
    # (row tile, group, the tile's rows [lo, hi) that are the group's).
    want = [(t, g,
             max(ends[g] - sizes[g] - t * tile_rows, 0),
             min(ends[g] - t * tile_rows, tile_rows))
            for g in range(groups) if sizes[g]
            for t in range((ends[g] - sizes[g]) // tile_rows,
                           (ends[g] - 1) // tile_rows + 1)]
    real = hi > lo
    assert list(zip(tile[real], weight[real], lo[real], hi[real])) == want
    assert gm.weight_visits(sizes, tile_rows) == len(want)
    # Real visits come first, and fetch as many weight blocks as
    # ``weight_visits`` counts: the block changes at a real visit only.
    assert real[: len(want)].all() and not real[len(want):].any()
    fetched = 1 + np.count_nonzero(np.diff(weight)) if want else 0
    assert fetched <= len(want)
    assert len({(t, g) for t, g, _, _ in want}) == len(want)
    # The row tiles that hold a row of the groups are visited in order
    # and zeroed once, at their first visit; no tile past them is: the
    # steps past the walk stay on the last visited tile with the last
    # real visit's weight block, and multiply and zero nothing.
    worked = -(-int(ends[-1]) // tile_rows)
    assert sorted(set(tile.tolist())) == list(range(max(worked, 1)))
    assert np.all(np.diff(tile) >= 0)
    walked = len(want)
    assert fresh[:walked].tolist() == (
        [1, *(np.diff(tile[:walked]) != 0).astype(int)] if want else [])
    assert not fresh[walked:].any()
    assert np.all(lo[~real] == 0) and np.all(hi[~real] == 0)
    if want:
        assert np.all(weight[~real] == want[-1][1])
        assert np.all(tile[~real] == want[-1][0])


def test_weight_visits_counts_calls_over_leading_dimensions():
    by_layer = np.array([[3, 0, 5, 0], [0, 0, 0, 8], [2, 2, 2, 2]])
    assert gm.weight_visits(by_layer, 8) == 2 + 1 + 4
    # A group across a tile boundary is fetched once a tile.
    assert gm.weight_visits(by_layer, 4) == 3 + 2 + 4
    assert gm.weight_visits(np.zeros((2, 4), np.int32), 8) == 0


@pytest.mark.parametrize("rows", [64, 72, 200])
def test_jitted_kernel_pads_rows_the_row_tile_does_not_divide(
        rows, monkeypatch):
    monkeypatch.setattr(gm, "_SUB_ROWS", 16)
    monkeypatch.setattr(gm, "_TILE_ROWS", 64)
    sizes = [rows // 4, 0, rows // 2, 3]
    x, w, sizes = _operands((rows, 128, 128), sizes, seed=rows)
    fn = gm._jitted.__wrapped__(True)
    got = fn(x, w, sizes)
    assert got.shape == (rows, 128)
    np.testing.assert_allclose(
        _live(got, sizes), _reference(x, w, sizes), rtol=1e-5, atol=1e-4
    )


def test_kernel_under_an_outer_jit_lowers_once_a_shape():
    shape, tiles, sizes, *_ = _CASES["uniform"]
    x, w, sizes = _operands(shape, sizes)
    fn = gm._jitted(True)
    assert fn is gm._jitted(True)

    @jax.jit
    def three(x, w, sizes):
        return fn(x, w, sizes) + fn(x, w, sizes) + fn(x, w, sizes)

    text = three.lower(x, w, sizes).as_text()
    assert text.count('func.func private @"ragged-dot-gmm"(') == 1
    assert text.count('call @"ragged-dot-gmm"(') == 3
    assert text.count("func.func private @_visits(") == 1
    np.testing.assert_allclose(
        _live(three(x, w, sizes), sizes), 3 * _reference(x, w, sizes),
        rtol=1e-5, atol=3e-4,
    )


# ---------------------------------------------------------------------------
# The rule: the kernel or ``ragged_dot``, from backend, dtype and shapes
# ---------------------------------------------------------------------------

_FALLBACKS = {
    "float32_operands": ((64, 128, 128), jnp.float32),
    "narrow_k": ((64, 64, 128), jnp.bfloat16),
    "narrow_n": ((64, 128, 96), jnp.bfloat16),
    "aligned_bfloat16_on_a_cpu": ((64, 128, 128), jnp.bfloat16),
}


@pytest.mark.parametrize("name,as_tpu", [
    (name, as_tpu) for name in sorted(_FALLBACKS) for as_tpu in (False, True)
    # On a TPU these shapes are the kernel's.
    if not (as_tpu and name == "aligned_bfloat16_on_a_cpu")
])
def test_fallback_is_ragged_dot_bit_for_bit(name, as_tpu, monkeypatch):
    shape, dtype = _FALLBACKS[name]
    if as_tpu:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x, w, sizes = _operands(shape, [10, 0, 30, 16])
    x, w = x.astype(dtype), w.astype(dtype)
    assert gm.row_tile(shape[0], shape[1], shape[2], dtype) is None
    got = gm.grouped_matmul(x, w, sizes)
    want = jax.lax.ragged_dot(
        x, w, sizes, preferred_element_type=jnp.float32
    )
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("sizes", [[10, 0, 30, 16], [0, 0, 0, 0]])
def test_transposed_weights_on_a_cpu_are_ragged_dot_of_their_transpose(sizes):
    x, w, sizes = _operands((64, 128, 96), sizes, transposed=True)
    assert w.shape == (4, 96, 128)
    got = gm.grouped_matmul(x, w, sizes, transposed=True)
    want = jax.lax.ragged_dot(x, jnp.swapaxes(w, 1, 2), sizes,
                              preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_rule_takes_the_kernel_on_a_tpu_for_aligned_bfloat16(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # The weights' lanes are whole tiles: 1,856 columns are not, 1,856
    # rows of transposed weights (or of a contraction) are.
    assert gm.row_tile(1152, 2688, 1856, jnp.bfloat16) is None
    assert gm.row_tile(1152, 2688, 1856, jnp.bfloat16, transposed=True) == 128
    assert gm.row_tile(1152, 1856, 2688, jnp.bfloat16) == 128
    assert gm.row_tile(1152, 1856, 2688, jnp.bfloat16, transposed=True) is None
    # Every slot's pairs in one row tile: a touched expert is read once.
    assert gm.row_tile(512, 2048, 1024, jnp.bfloat16) == 512
    assert gm.row_tile(512, 1024, 2048, jnp.bfloat16) == 512
    assert gm.row_tile(512, 1024, 2048, jnp.bfloat16, jnp.float32) is None


@pytest.mark.parametrize("shape,want", [
    ((512, 2048, 1024), (512, 1024)),
    ((512, 1024, 2048), (512, 2048)),
    ((4096, 2048, 1024), (512, 1024)),
    ((384, 256, 128), (128, 128)),
    ((256, 8192, 1024), (256, 256)),
    ((256, 32768, 128), None),
    # The blocks of the cells the benchmark has: sarvam's up and down,
    # granite's.
    ((384, 4096, 2048), (128, 512)),
    ((384, 2048, 4096), (128, 1024)),
    ((1280, 4096, 768), (256, 384)),
    ((1280, 768, 4096), (256, 2048)),
    # Nemotron's: 1,856 columns in blocks of 640 (the last 576 wide),
    # 2,688 = 21 tiles in three of 896.
    ((1152, 2688, 1856), (128, 640)),
    ((1152, 1856, 2688), (128, 896)),
])
def test_tile_rule_reads_shapes_only(shape, want):
    rows, k, n = shape
    tiles = gm._tile_rule(rows, k, n, 2)
    if want is None:
        assert tiles is None
        return
    tile_rows, sub_rows, tile_n = tiles
    assert (tile_rows, tile_n) == want
    assert rows % tile_rows == 0 and tile_rows % sub_rows == 0
    # Whole lane tiles; only a last block may reach past the width.
    assert tile_n % 128 == 0 and (-(-n // tile_n) - 1) * tile_n < n
    assert n % tile_n == 0 or n % 128
    assert k * tile_n * 2 <= gm._WEIGHT_BLOCK_BYTES


def test_expert_mlp_on_a_cpu_is_ragged_dot_unchanged():
    """The layer's call site went from ``jax.lax.ragged_dot`` to
    ``grouped_matmul``: on a CPU the same values bit for bit."""
    from fluxmpi_tpu.models.decoder import ExpertMLP

    layer = ExpertMLP(num_experts=8, top_k=2, width=32, shared_width=32,
                      dtype=jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 16))
    params = layer.init(jax.random.PRNGKey(2), u)
    mask = jnp.arange(12)[None] < jnp.array([[12], [7]])
    out, state = layer.apply(params, u, mask, mutable=["intermediates"])
    counts = state["intermediates"]["expert_tokens"][0]
    assert int(counts.sum()) == (12 + 7) * 2

    p = params["params"]
    experts, weights = layer.route(u.reshape(-1, 16), p["router"], p["bias"])
    dense = jnp.zeros((24, 16))
    for slot in range(2):
        e = experts[:, slot]
        h = jax.nn.silu(jnp.einsum("td,tdf->tf", u.reshape(-1, 16),
                                   p["w1"][e]))
        h = h * jnp.einsum("td,tdf->tf", u.reshape(-1, 16), p["w3"][e])
        dense = dense + weights[:, slot, None] * jnp.einsum(
            "tf,tfd->td", h, p["w2"][e])
    shared = p["shared"]
    g = jax.nn.silu(u.reshape(-1, 16) @ shared["w1"]) * (
        u.reshape(-1, 16) @ shared["w3"])
    dense = dense * mask.reshape(-1, 1) + g @ shared["w2"]
    np.testing.assert_allclose(
        out.reshape(-1, 16), dense, rtol=2e-4, atol=2e-5
    )


# ---------------------------------------------------------------------------
# The engine's count of weight visits: only where a model has expert
# layers AND their grouped matmul is the kernel; traced or not
# ---------------------------------------------------------------------------


def _tiny_decoder():
    from fluxmpi_tpu.models import DecoderConfig, DecoderLM

    config = DecoderConfig.from_hf({
        "model_type": "afmoe", "vocab_size": 64, "hidden_size": 32,
        "intermediate_size": 64, "moe_intermediate_size": 16,
        "num_hidden_layers": 3, "num_dense_layers": 1,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
        "num_experts": 8, "num_experts_per_tok": 2, "num_shared_experts": 1,
        "layer_types": ["sliding_attention", "sliding_attention",
                        "full_attention"],
        "sliding_window": 16, "max_position_embeddings": 64,
        "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
        "route_norm": True, "route_scale": 1.0, "mup_enabled": False,
    })
    model = DecoderLM(config)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    return model, variables


def _serve(model, variables, traced, **engine):
    from fluxmpi_tpu.serving import InferenceEngine
    from fluxmpi_tpu.telemetry import tracing

    if traced:
        tracing.configure(True)
    try:
        eng = InferenceEngine(model, variables, slots=2, check_memory=False,
                              **engine)
        try:
            rng = np.random.default_rng(0)
            reqs = [eng.submit(rng.integers(0, 32, 6).astype(np.int32), 5)
                    for _ in range(3)]
            eng.run()
            assert [r.status for r in reqs] == ["finished"] * 3
            stats = eng.stats()
        finally:
            eng.close()
        delivered = [
            args for _, name, _, _, _, args
            in list(tracing.get_tracer()._events)
            if name == "serve.decode.deliver"
        ]
    finally:
        if traced:
            tracing.configure(False)
            tracing.reset()
    return stats, delivered


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("tile", [None, 2, 4], ids=["ragged_dot", "tile2",
                                                   "tile4"])
def test_engine_counts_weight_visits_where_the_kernel_runs(
        tile, traced, monkeypatch):
    from fluxmpi_tpu.models import DecoderLM

    model, variables = _tiny_decoder()
    assert model.expert_row_tile(2) is None  # a CPU: ragged_dot
    monkeypatch.setattr(DecoderLM, "expert_row_tile",
                        lambda self, tokens: tile)
    stats, delivered = _serve(model, variables, traced, block_size=8,
                              max_len=32)
    assert stats["experts_touched"] > 0
    assert len(delivered) == (stats["decode_steps"] if traced else 0)
    ratios = [a["expert_weight_visits_per_touched"] for a in delivered
              if "expert_weight_visits_per_touched" in a]
    if tile is None:
        assert stats["expert_weight_visits"] == 0 and not ratios
        return
    # 2 slots x top-2 = 4 rows a call: one row tile of 4 holds them all.
    visits, touched = stats["expert_weight_visits"], stats["experts_touched"]
    assert visits == touched if tile == 4 else touched <= visits <= 2 * touched
    if traced:
        assert len(ratios) == len(delivered)
        assert all(1.0 <= r <= 2.0 for r in ratios)
        assert all("experts_touched_pct" in a for a in delivered)


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
def test_model_without_expert_layers_never_reaches_the_expert_branch(traced):
    from fluxmpi_tpu.models import TransformerLM

    lm = TransformerLM(vocab_size=32, max_len=32, num_layers=1, d_model=16,
                       num_heads=2, d_ff=32)
    variables = lm.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                        train=False)
    stats, delivered = _serve(lm, variables, traced, block_size=8)
    assert stats["expert_weight_visits"] == 0 and stats["expert_slots"] == 0
    assert len(delivered) == (stats["decode_steps"] if traced else 0)
    for args in delivered:
        assert not [k for k in args if k.startswith("expert")]


def test_decoder_lm_row_tile_follows_the_rule(monkeypatch):
    import dataclasses

    model, _ = _tiny_decoder()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert model.expert_row_tile(64) is None  # float32, narrow
    wide = dataclasses.replace(
        model.config, hidden_size=256, moe_intermediate_size=128)
    bf16 = type(model)(wide, dtype=jnp.bfloat16)
    assert bf16.expert_row_tile(64) == 128  # 128 rows: one tile
    assert bf16.expert_row_tile(256) == 512
    dense = dataclasses.replace(wide, num_dense_layers=wide.num_layers)
    assert type(model)(dense, dtype=jnp.bfloat16).expert_row_tile(64) is None


# ---------------------------------------------------------------------------
# A layer that holds a share of its router's experts works on its own
# pairs only: rows past them are unspecified and never read
# ---------------------------------------------------------------------------

_SHARE = dict(num_experts=8, top_k=2, width=128, include_shared=False)


def _share_layers(dtype, monkeypatch, kernel):
    """The uncut layer's parameters, a function that applies the share
    ``(lo, hi)`` of them, and tokens whose router is steered: the first
    half choose among experts 0-3 only, the rest among all eight."""
    from fluxmpi_tpu.models.decoder import ExpertMLP

    monkeypatch.setattr(gm, "_SUB_ROWS", 16)
    monkeypatch.setattr(gm, "_TILE_ROWS", 64)
    if kernel:
        fn = gm._jitted.__wrapped__(True)
        monkeypatch.setattr(
            gm, "combine", lambda y, token, scale, live, tokens: gm._combine(
                y, token, scale, live, tokens=tokens, interpret=True,
                tiles=gm._combine_tiles(y.shape[0], y.shape[1], tokens)))
    else:
        def fn(x, w, sizes):
            return jax.lax.ragged_dot(
                x, w, sizes, preferred_element_type=jnp.float32)
    monkeypatch.setattr(gm, "grouped_matmul", poisoned_past_the_groups(fn))
    whole = ExpertMLP(dtype=dtype, **_SHARE)
    u = jax.random.normal(jax.random.PRNGKey(3), (1, 128, 128), dtype)
    params = whole.init(jax.random.PRNGKey(4), u)["params"]

    def share(lo, hi, u, token_mask=None, params=params):
        layer = ExpertMLP(dtype=dtype, expert_range=(lo, hi), **_SHARE)
        cut = {k: (v[lo:hi] if k in ("w1", "w2", "w3") else v)
               for k, v in params.items()}
        out, state = layer.apply({"params": cut}, u, token_mask,
                                 mutable=["intermediates"])
        return out, state["intermediates"]["expert_tokens"][0]

    return whole, params, share, u


@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("kernel", [False, True],
                         ids=["ragged_dot_float32", "kernel_bfloat16"])
def test_shares_add_up_though_rows_past_the_groups_are_poison(
        kernel, masked, monkeypatch):
    dtype = jnp.bfloat16 if kernel else jnp.float32
    whole, params, share, u = _share_layers(dtype, monkeypatch, kernel)
    # Every token to experts 0-3: the share (0, 4) receives EVERY pair
    # (all its row tiles are worked), the share (4, 8) none.
    steered = dict(params, bias=jnp.where(jnp.arange(8) < 4, 100.0, 0.0))
    mask = (jnp.arange(128) % 3 != 0)[None] if masked else None
    tokens = int(mask.sum()) if masked else 128
    uncut = whole.apply({"params": steered}, u, mask)
    assert bool(jnp.all(jnp.isfinite(uncut.astype(jnp.float32))))
    every, got_every = share(0, 4, u, mask, steered)
    none, got_none = share(4, 8, u, mask, steered)
    assert int(got_every.sum()) == tokens * 2 and int(got_none.sum()) == 0
    assert not np.any(np.asarray(none, np.float32))
    tol = dict(rtol=2e-2, atol=2e-3) if kernel else dict(rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(every, np.float32), np.asarray(uncut, np.float32), **tol)
    # Unsteered: three unequal shares, each a part of the pairs.
    uncut = whole.apply({"params": params}, u, mask)
    parts = [share(lo, hi, u, mask) for lo, hi in ((0, 1), (1, 5), (5, 8))]
    assert sum(int(got.sum()) for _, got in parts) == tokens * 2
    assert all(0 < int(got.sum()) < tokens * 2 for _, got in parts)
    total = sum(np.asarray(out, np.float32) for out, _ in parts)
    assert np.all(np.isfinite(total))
    np.testing.assert_allclose(total, np.asarray(uncut, np.float32), **tol)


def test_grad_through_a_share_matches_dense_over_the_held_experts(
        monkeypatch):
    whole, params, share, u = _share_layers(jnp.float32, monkeypatch, False)
    lo, hi = 2, 5
    target = jax.random.normal(jax.random.PRNGKey(5), u.shape)

    def dense(params, u):
        flat = u.reshape(-1, u.shape[-1])
        experts, weights = whole.route(flat, params["router"], params["bias"])
        out = jnp.zeros_like(flat)
        for e in range(lo, hi):
            h = jax.nn.silu(flat @ params["w1"][e]) * (flat @ params["w3"][e])
            weight = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
            out = out + weight[:, None] * (h @ params["w2"][e])
        return jnp.sum(out.reshape(u.shape) * target)

    def routed(params, u):
        return jnp.sum(share(lo, hi, u, params=params)[0] * target)

    want = jax.grad(dense, argnums=(0, 1))(params, u)
    got = jax.grad(routed, argnums=(0, 1))(params, u)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6)
    # Experts the share does not hold get no gradient from it.
    for name in ("w1", "w2", "w3"):
        assert not np.any(np.asarray(got[0][name][:lo]))
        assert not np.any(np.asarray(got[0][name][hi:]))


def _static_routed(layer, params, u):
    """``ExpertMLP`` with every expert held and no mask as it stood before
    a share's work was bounded (PR 39: every pass over all the rows at
    once, no loop, no branch, no mask), for the program text."""
    n, k, d = layer.num_experts, layer.top_k, u.shape[-1]
    u = u.reshape(-1, d)
    tokens = u.shape[0]
    experts, weights = layer.route(u, params["router"], params["bias"])
    flat = experts.reshape(-1)
    rank = (flat - 0) % n
    counts = jnp.zeros((n,), jnp.int32).at[flat].add(1, mode="drop")
    order = jnp.argsort(rank, stable=True)
    sizes = counts[0:n]
    rows = u.astype(layer.dtype)[order // k]

    def grouped(x, w):
        return gm.grouped_matmul(
            x.astype(layer.dtype), w.astype(layer.dtype), sizes)

    gate, up = grouped(rows, params["w1"]), grouped(rows, params["w3"])
    y = grouped(jax.nn.silu(gate) * up, params["w2"])
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    y = y[back].reshape(tokens, k, d)
    return jnp.sum(y * weights[..., None], axis=1).astype(layer.dtype)


@pytest.mark.parametrize("held", [None, (0, 8)], ids=["default", "range"])
def test_every_expert_held_lowers_to_the_static_program(held):
    from fluxmpi_tpu.models.decoder import ExpertMLP

    layer = ExpertMLP(dtype=jnp.float32, expert_range=held, **_SHARE)
    u = jax.random.normal(jax.random.PRNGKey(3), (64, 128))
    params = layer.init(jax.random.PRNGKey(4), u)["params"]
    got = jax.jit(
        lambda p, u: layer.apply({"params": p}, u)).lower(params, u).as_text()
    want = jax.jit(
        lambda p, u: _static_routed(layer, p, u)).lower(params, u).as_text()
    assert "stablehlo.while" not in got and "stablehlo.case" not in got
    assert got == want
    # A share of the experts differs in its combine only (on a CPU a
    # ``segment_sum``: a scatter-add where the gather by token stood).
    cut = ExpertMLP(dtype=jnp.float32, expert_range=(0, 4), **_SHARE)
    params = cut.init(jax.random.PRNGKey(4), u)["params"]
    text = jax.jit(
        lambda p, u: cut.apply({"params": p}, u)).lower(params, u).as_text()
    assert "stablehlo.while" not in text and "stablehlo.case" not in text
    assert text.count("stablehlo.scatter") == got.count(
        "stablehlo.scatter")  # the counts, the combine; no ``back``


# ---------------------------------------------------------------------------
# The combine: the live rows added into their tokens' rows
# ---------------------------------------------------------------------------


def _combine_operands(rows, n, tokens, live, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    y = jax.random.normal(keys[0], (rows, n), jnp.float32)
    token = jax.random.randint(keys[1], (rows,), 0, tokens)
    scale = jax.random.uniform(keys[2], (rows,))
    # Rows past the live ones hold what the contract allows: NaN.
    y = jnp.where((jnp.arange(rows) < live)[:, None], y, jnp.nan)
    return y, token, scale


def _combine_by_hand(y, token, scale, live, tokens):
    out = np.zeros((tokens, y.shape[1]), np.float64)
    for r in range(live):
        out[int(token[r])] += float(scale[r]) * np.asarray(y[r], np.float64)
    return out


# Columns in two blocks of 128, and 384 in blocks of 256: the last block
# half past the width.
@pytest.mark.parametrize("n,tile_n", [(256, 128), (384, 256)])
@pytest.mark.parametrize("live", [0, 1, 63, 64, 65, 200, 256])
@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_combine_adds_live_rows_only(kernel, live, n, tile_n):
    rows, tokens = 256, 40
    y, token, scale = _combine_operands(rows, n, tokens, live, seed=live)
    if kernel:
        got = gm._combine(y, token, scale, jnp.int32(live), tokens=tokens,
                          tiles=(64, tile_n), interpret=True)
    else:
        got = gm.combine(y, token, scale, jnp.int32(live), tokens)
    assert got.shape == (tokens, n) and got.dtype == jnp.float32
    np.testing.assert_allclose(
        got, _combine_by_hand(y, token, scale, live, tokens),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape,want", [
    ((1280, 4096, 128), (256, 4096)),   # a tick: 128 slots x top-10
    ((20480, 4096, 2048), (512, 512)),  # a 2,048-token prompt
    ((32768, 4096, 4096), (512, 256)),  # a slab of 4,096 tokens x top-8
    ((384, 4096, 48), (128, 4096)),
    ((128, 96, 16), None),              # narrow columns
    ((128, 128, 16384), None),          # no column block fits
    ((1152, 2688, 192), (128, 2688)),   # a tick: 192 slots x top-6
    ((12288, 2688, 2048), (512, 512)),  # 21 lane tiles: five blocks and one
])
def test_combine_tiles_read_shapes_only(shape, want):
    rows, n, tokens = shape
    assert gm._combine_tiles(rows, n, tokens) == want
    if want is not None:
        assert tokens * want[1] * 4 <= gm._COMBINE_BLOCK_BYTES
        assert want[1] % 128 == 0 and rows % want[0] == 0
        assert (-(-n // want[1]) - 1) * want[1] < n


def test_combine_on_a_cpu_is_the_masked_segment_sum(monkeypatch):
    y, token, scale = _combine_operands(128, 128, 16, 50)
    want = jax.ops.segment_sum(
        jnp.where((jnp.arange(128) < 50)[:, None], y, 0.0) * scale[:, None],
        token, num_segments=16)
    np.testing.assert_array_equal(
        np.asarray(gm.combine(y, token, scale, jnp.int32(50), 16)),
        np.asarray(want))
    # Float32 rows of whole lanes on a TPU: the kernel's.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    called = []
    monkeypatch.setattr(gm, "_jitted_combine",
                        lambda interpret, tokens: called.append(tokens) or (
                            lambda *a: "kernel"))
    assert gm.combine(y, token, scale, jnp.int32(50), 16) == "kernel"
    assert called == [16]


@pytest.mark.parametrize("rows,tokens", [(256, 40), (200, 40), (72, 13)])
def test_jitted_combine_pads_rows_and_tokens_to_whole_tiles(
        rows, tokens, monkeypatch):
    monkeypatch.setattr(gm, "_SUB_ROWS", 16)
    monkeypatch.setattr(gm, "_TILE_ROWS", 64)
    live = rows - 9
    y, token, scale = _combine_operands(rows, 128, tokens, live, seed=rows)
    got = gm._jitted_combine.__wrapped__(True, tokens)(
        y, token, scale, jnp.int32(live))
    assert got.shape == (tokens, 128)
    np.testing.assert_allclose(
        got, _combine_by_hand(y, token, scale, live, tokens),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("live", [0, 70, 200])
def test_combine_kernel_differentiates_as_the_masked_segment_sum(
        live, monkeypatch):
    monkeypatch.setattr(gm, "_SUB_ROWS", 16)
    monkeypatch.setattr(gm, "_TILE_ROWS", 64)
    rows, tokens = 200, 40
    y, token, scale = _combine_operands(rows, 128, tokens, live, seed=live)
    target = jax.random.normal(jax.random.PRNGKey(9), (tokens, 128))
    kernel = gm._jitted_combine.__wrapped__(True, tokens)

    def loss(fn):
        return lambda y, scale: jnp.sum(
            fn(y, token, scale, jnp.int32(live)) * target)

    got = jax.grad(loss(kernel), argnums=(0, 1))(y, scale)
    want = jax.grad(
        loss(lambda *a: gm.combine(*a, tokens)), argnums=(0, 1))(y, scale)
    for a, b in zip(got, want):
        # Rows past the live ones (NaN operands) get an exact zero.
        assert np.all(np.isfinite(np.asarray(a)))
        assert not np.any(np.asarray(a[live:]))
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
