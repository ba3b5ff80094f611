"""The flash kernels' sub-tile walk and tile rule (interpret mode, CPU):
operands in the caller's dtype, the loops between the window's far edge
and the causal frontier, the mask on cut sub-tiles only, a diagonal
sub-tile as a staircase of strips under the causal mask alone, against a
dense reference; the rule's tiles for every prefill bucket the benchmark's
serve cells compile; the kernel's traced size, which must not grow with
the sequence (every start of a program pays it: PERF.md §6, PR 29)."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

# ``fluxmpi_tpu.ops.flash_attention`` the attribute is the function.
fa = importlib.import_module("fluxmpi_tpu.ops.flash_attention")


def _dense(q, k, v, *, causal, window, qseg, kseg, dropout, seed):
    """Plain attention in float32 with the kernels' conventions: id-0 keys
    are padding, rows with no key give zeros and ``lse = -1e30``, dropout
    drops what the kernels' position hash drops."""
    b, sq, h, d = q.shape
    sk, group = k.shape[1], h // k.shape[2]
    qf = q.astype(jnp.float32)
    kf = jnp.repeat(k.astype(jnp.float32), group, axis=2)
    vf = jnp.repeat(v.astype(jnp.float32), group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) / np.sqrt(d)
    diff = jnp.arange(sq)[:, None] - jnp.arange(sk)[None, :]
    keep = jnp.ones((sq, sk), bool)
    if causal:
        keep &= diff >= 0
    if window is not None:
        keep &= diff < window
    keep = jnp.broadcast_to(keep[None, None], s.shape)
    if qseg is not None:
        keep &= ((qseg[:, None, :, None] == kseg[:, None, None, :])
                 & (kseg[:, None, None, :] != 0))
    s = jnp.where(keep, s, -1e30)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.where(keep, jnp.exp(s - lse[..., None]), 0.0)
    lse = jnp.where(jnp.any(keep, axis=-1), lse, -1e30)
    if dropout:
        kp = 1.0 - dropout
        q_pos = jnp.broadcast_to(jnp.arange(sq)[:, None], (sq, sk))
        k_pos = jnp.broadcast_to(jnp.arange(sk)[None, :], (sq, sk))
        kept = jax.vmap(
            lambda bh: fa._dropout_keep(jnp.uint32(seed), bh, q_pos, k_pos, kp)
        )(jnp.arange(b * h, dtype=jnp.uint32)).reshape(b, h, sq, sk)
        p = jnp.where(kept, p / kp, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vf), lse


def _segments(s, b=1):
    """Three documents and a padded tail, none on a sub-tile's edge; each
    further batch row's edges 17 positions later."""
    ids = np.zeros((b, s), np.int32)
    for i in range(b):
        ids[i, : s // 3 + 5 + 17 * i] = 1
        ids[i, s // 3 + 5 + 17 * i: s // 2 + 9 + 17 * i] = 2
        ids[i, s // 2 + 9 + 17 * i: s - 11] = 3
    return jnp.asarray(ids)


# name -> (length, heads, kv heads, keyword arguments, tiles or None).
# ``tiles`` = (block_q, block_k, sub_q, sub_k) for all three kernels,
# through the private entry; None goes through the public one and takes
# the rule's. Lengths the frontier cuts unevenly; windows narrower and
# wider than a sub-tile. ``d`` is the head_dim (32 unless given);
# ``stair`` says, by dtype, whether the forward's cut sub-tiles are worked
# as a staircase (``_strips``) or keep the generic masked body: the
# ``stair_*`` cases are the benchmark cells' shapes through the rule
# (bfloat16: 512-sub-tiles; float32: the 512 x 1,024 pair, square where
# 1,024 does not divide the length), the ``generic_*`` ones 512-sub-tiles
# that a window, segments, dropout or an unequal pair keeps off it. The
# ``layout_*`` cases have a batch (``b``) over one: Q, dO and the three
# gradients cross HBM as ``[rows, d, s]`` and a gradient's rows lie heads
# outermost (``_grad_row``), which a batch of one cannot tell from the
# operands' batch-outermost order; head widths 64 and 128, grouped heads,
# a window, segment ids (a batch row's own) and kernel dropout (the hash
# is keyed by the folded QUERY row, which the dkv kernel rebuilds).
_CASES = {
    "layout_b2_d64": (512, 4, 4, dict(causal=True, d=64, b=2), None),
    "layout_b3_8_over_2_d128": (512, 8, 2, dict(causal=True, d=128, b=3),
                                None),
    "layout_b2_window_d64": (768, 4, 2, dict(causal=True, window=200, d=64,
                                             b=2), (384, 768, 384, 256)),
    "layout_b3_segments_d64": (768, 4, 2, dict(causal=True, segments=True,
                                               d=64, b=3),
                               (768, 384, 384, 128)),
    "layout_b2_dropout_d128": (512, 4, 2, dict(causal=True, dropout=0.1,
                                               d=128, b=2),
                               (256, 512, 256, 256)),
    "layout_b2_full_d64": (512, 2, 1, dict(causal=False, d=64, b=2),
                           (256, 256, 256, 128)),
    "stair_512": (512, 2, 2, dict(causal=True, stair=(True, True)), None),
    "stair_1536_d64": (1536, 2, 2, dict(causal=True, d=64,
                                        stair=(True, True)), None),
    "stair_1024_16_over_2_d128": (1024, 16, 2, dict(
        causal=True, d=128, stair=(False, True)), None),
    "stair_512_32_over_4_d128": (512, 32, 4, dict(
        causal=True, d=128, stair=(True, True)), None),
    # 5 query heads a K/V head: the first prefill bucket of a model of 20
    # over 4 heads of 128.
    "stair_512_20_over_4_d128": (512, 20, 4, dict(
        causal=True, d=128, stair=(True, True)), None),
    "whole_640_d64": (640, 2, 2, dict(causal=True, d=64,
                                      stair=(False, False)), None),
    "whole_768_d64": (768, 2, 2, dict(causal=True, d=64,
                                      stair=(False, False)), None),
    "generic_window_1024": (1024, 2, 1, dict(
        causal=True, window=600, stair=(False, False)),
        (512, 1024, 512, 512)),
    "generic_segments_1024": (1024, 2, 1, dict(
        causal=True, segments=True, stair=(False, False)),
        (512, 1024, 512, 512)),
    "generic_dropout_1024": (1024, 2, 2, dict(
        causal=True, dropout=0.25, stair=(False, False)),
        (512, 1024, 512, 512)),
    "generic_unequal_1024": (1024, 2, 1, dict(
        causal=True, stair=(False, False)), (512, 1024, 512, 256)),
    "stair_blocks_1024": (1024, 2, 1, dict(
        causal=True, stair=(True, True)), (1024, 1024, 512, 512)),
    "causal_768": (768, 2, 2, dict(causal=True), (768, 768, 384, 256)),
    "window_narrow_768": (768, 2, 1, dict(causal=True, window=96),
                          (768, 768, 384, 256)),
    "window_wide_1536": (1536, 2, 1, dict(causal=True, window=300),
                         (512, 512, 256, 256)),
    "grouped_1536": (1536, 4, 2, dict(causal=True), (1536, 512, 512, 256)),
    "segments_768": (768, 2, 2, dict(causal=True, segments=True),
                     (768, 768, 384, 256)),
    "segments_window_768": (768, 2, 1, dict(causal=True, window=200,
                                            segments=True),
                            (384, 768, 384, 256)),
    "dropout_768": (768, 2, 2, dict(causal=True, dropout=0.25),
                    (768, 768, 384, 256)),
    "band_768": (768, 2, 2, dict(causal=False, window=40),
                 (256, 768, 256, 256)),
    "full_768": (768, 2, 1, dict(causal=False), (768, 768, 384, 384)),
    "rule_2560_window": (2560, 1, 1, dict(causal=True, window=1000), None),
    "rule_1024": (1024, 2, 1, dict(causal=True, stair=(False, True)), None),
    "rule_2048_window": (2048, 1, 1, dict(causal=True, window=700), None),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(_CASES))
def test_sub_tiled_kernels_match_dense(case, dtype):
    s, h, h_kv, kwargs, tiles = _CASES[case]
    kwargs = dict(kwargs)
    causal = kwargs.pop("causal")
    window = kwargs.pop("window", None)
    dropout = kwargs.pop("dropout", 0.0)
    b = kwargs.pop("b", 1)
    seg = _segments(s, b) if kwargs.pop("segments", False) else None
    d, seed = kwargs.pop("d", 32), 77
    stair = kwargs.pop("stair", None)
    if stair is not None:
        fwd = tiles or fa._tile_rule("fwd", s, s, d, dtype)
        strips = fa._strips(fwd, causal, window, seg is not None
                            or bool(dropout))
        assert strips == (
            fa._STRIPS if stair[dtype == jnp.bfloat16] else 0)
    keys = jax.random.split(jax.random.PRNGKey(s + h), 4)
    q = jax.random.normal(keys[0], (b, s, h, d), jnp.float32).astype(dtype)
    k = jax.random.normal(keys[1], (b, s, h_kv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(keys[2], (b, s, h_kv, d), jnp.float32).astype(dtype)
    w = jax.random.normal(keys[3], (b, s, h, d), jnp.float32)

    def flash(q, k, v):
        if tiles is None:
            return fa.flash_attention_with_lse(
                q, k, v, causal=causal, window=window, segment_ids=seg,
                dropout_rate=dropout,
                dropout_seed=seed if dropout else None)
        return fa._flash(
            q, k, v, seg, seg, jnp.uint32(seed) if dropout else None,
            causal, window, (tiles,) * 3, True, dropout)

    def dense(q, k, v):
        return _dense(q, k, v, causal=causal, window=window, qseg=seg,
                      kseg=seg, dropout=dropout, seed=seed)

    def loss(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v)
            live = lse > -1e20
            return (jnp.sum(out.astype(jnp.float32) * w)
                    + 0.1 * jnp.sum(jnp.where(live, lse, 0.0))), (out, lse)
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    (_, (out, lse)), grads = loss(flash)(q, k, v)
    (_, (out_d, lse_d)), grads_d = loss(dense)(q, k, v)
    assert out.dtype == dtype and lse.dtype == jnp.float32
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(out_d), atol=tol)
    # The log-sum-exp is float32 statistics over float32 products: tight
    # in both dtypes; rows with no key read the -1e30 convention exactly.
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(lse_d), atol=tol, rtol=1e-6)
    for name, g, g_d in zip("qkv", grads, grads_d):
        assert g.dtype == dtype
        scale = float(jnp.max(jnp.abs(g_d))) + 1e-6
        np.testing.assert_allclose(
            np.asarray(g, np.float32) / scale,
            np.asarray(g_d, np.float32) / scale, atol=tol,
            err_msg=f"d{name}")


# The forward with keys and values of their own widths (latent attention's
# un-absorbed prefill: keys of 192, values of 128), forward only: name ->
# (batch, length, heads, kv heads, window, tiles or None).
_WIDTHS = {
    "rule_1024": (1, 1024, 2, 2, None, None),
    "b2_grouped_512": (2, 512, 4, 2, None, None),
    "b2_window_768": (2, 768, 2, 1, 300, (384, 768, 384, 256)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(_WIDTHS))
def test_forward_with_keys_of_192_and_values_of_128_matches_dense(case, dtype):
    b, s, h, h_kv, window, tiles = _WIDTHS[case]
    keys = jax.random.split(jax.random.PRNGKey(s + b), 3)
    q = jax.random.normal(keys[0], (b, s, h, 192), jnp.float32).astype(dtype)
    k = jax.random.normal(keys[1], (b, s, h_kv, 192),
                          jnp.float32).astype(dtype)
    v = jax.random.normal(keys[2], (b, s, h_kv, 128),
                          jnp.float32).astype(dtype)
    if tiles is None:
        out, lse = fa.flash_attention_with_lse(q, k, v, causal=True,
                                               window=window)
    else:
        out, lse = fa._flash(q, k, v, None, None, None, True, window,
                             (tiles,) * 3, True, 0.0)
    out_d, lse_d = _dense(q, k, v, causal=True, window=window, qseg=None,
                          kseg=None, dropout=0.0, seed=0)
    assert out.shape == (b, s, h, 128) and out.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(out_d), atol=tol)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(lse_d), atol=tol, rtol=1e-6)


@pytest.mark.parametrize("tiles", [(256, 256, 256, 256), (512, 512, 256, 256),
                                   (256, 512, 128, 256)])
def test_rows_with_no_key_keep_the_lse_convention(tiles):
    """Ring attention merges blocks by the returned log-sum-exp: a row
    with no attendable key reads -1e30 and a zero output, whatever the
    sub-tiles: padding rows (id 0), and a band no pair of the block is
    in."""
    s, h, d = 512, 2, 32
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(x, (1, s, h, d), jnp.float32) for x in keys)
    seg = jnp.asarray(np.r_[np.ones(300), np.zeros(212)][None], jnp.int32)
    out, lse = fa._flash(q, k, v, seg, seg, None, True, None, (tiles,) * 3,
                         True, 0.0)
    assert np.all(np.asarray(lse)[:, :, 300:] == np.float32(-1e30))
    assert np.all(np.asarray(out)[:, 300:] == 0.0)
    assert np.all(np.asarray(lse)[:, :, :300] > -1e20)
    # Band-only, window <= -s: every key is too far behind.
    out, lse = fa._flash(q, k, v, None, None, None, False, -s, (tiles,) * 3,
                         True, 0.0)
    assert np.all(np.asarray(lse) == np.float32(-1e30))
    assert np.all(np.asarray(out) == 0.0)


def _before(s: int) -> tuple[int, int]:
    """The pair the caps of before PR 29 (512, 1,024) gave a length."""
    return fa._auto_block(s, 512), fa._auto_block(s, 1024)


# Every prefill bucket the benchmark's serve cells compile: gpt2m-serve's
# six (head_dim 64), trinity-mini-serve's 512 ... 8,192 (head_dim 128).
_BUCKETS = [(64, s) for s in (128, 256, 384, 512, 640, 768)] + [
    (128, s) for s in range(512, 8193, 512)]


@pytest.mark.parametrize("d, s", _BUCKETS)
def test_tile_rule_is_legal_at_every_serve_bucket(d, s):
    for kernel in ("fwd", "dq", "dkv"):
        bq, bk, sub_q, sub_k = fa._tile_rule(kernel, s, s, d, jnp.bfloat16)
        assert s % bq == 0 and s % bk == 0
        assert bq % sub_q == 0 and bk % sub_k == 0
        # A sub-tile smaller than its block starts on a lane boundary.
        assert sub_q == bq or sub_q % 128 == 0
        assert sub_k == bk or sub_k % 128 == 0
        # Float32 operands keep the blocks of before, each worked whole.
        assert fa._tile_rule(kernel, s, s, d, jnp.float32) == (
            *_before(s), *_before(s))
    fwd = fa._tile_rule("fwd", s, s, d, jnp.bfloat16)
    if s % 512 == 0:
        # One sub-tile of queries against the head's whole K/V, which at
        # head_dim 128 and 8,192 tokens is what a step may hold.
        assert fwd == (512, s, 512, 512)
    else:
        # gpt2m-serve's 128, 256, 384 (the pair of before) and 640, 768
        # (the pair of before cut them in 128- and 256-row blocks).
        assert fwd == (s, s, s, s)
    # The backward kernels, which no serve cell runs: 1,024-blocks in
    # 512-sub-tiles where they divide the length, else the pair of before.
    for kernel in ("dq", "dkv"):
        tiles = fa._tile_rule(kernel, s, s, d, jnp.bfloat16)
        if s % 1024 == 0:
            assert tiles == (1024, 1024, 512, 512)
        else:
            assert tiles == (*_before(s), *_before(s))


# (cell, head_dim, length, window, kernels) -> pairs worked at 1 (the
# generic masked body), 2 and 4 strips a diagonal sub-tile: every
# training and prefill shape of the benchmark's cells.
_PAIRS = [
    ("gpt2m-train", 64, 1024, None, ("fwd", "dq", "dkv"),
     (786432, 655360, 589824)),
    ("gpt2m-serve", 64, 128, None, ("fwd",), (16384, 16384, 16384)),
    ("gpt2m-serve", 64, 256, None, ("fwd",), (65536, 49152, 65536)),
    ("gpt2m-serve", 64, 384, None, ("fwd",), (147456, 147456, 147456)),
    ("gpt2m-serve", 64, 512, None, ("fwd",), (262144, 196608, 163840)),
    ("gpt2m-serve", 64, 640, None, ("fwd",), (409600, 409600, 409600)),
    ("gpt2m-serve", 64, 768, None, ("fwd",), (589824, 442368, 589824)),
    ("trinity-mini-serve", 128, 512, None, ("fwd",),
     (262144, 196608, 163840)),
    ("trinity-mini-serve", 128, 2048, None, ("fwd",),
     (2621440, 2359296, 2228224)),
    ("trinity-mini-serve", 128, 8192, None, ("fwd",),
     (35651584, 34603008, 34078720)),
    ("trinity-mini-serve", 128, 2560, 2048, ("fwd",), (3932160,) * 3),
    ("trinity-mini-serve", 128, 8192, 2048, ("fwd",), (18350080,) * 3),
    ("sarvam-105b-serve", 192, 1024, None, ("fwd",), (1048576,) * 3),
    ("sarvam-105b-serve", 192, 16384, None, ("fwd",), (142606336,) * 3),
    ("granite-4.0-h-small-serve", 128, 256, None, ("fwd",),
     (65536, 49152, 65536)),
    ("nemotron-3-nano-serve", 128, 1024, None, ("fwd",),
     (786432, 655360, 589824)),
    ("granite-4.0-h-small-serve", 128, 2048, None, ("fwd",),
     (2621440, 2359296, 2228224)),
]


@pytest.mark.parametrize(
    "cell, d, s, window, kernels, worked", _PAIRS,
    ids=[f"{c}-{s}-{w}" for c, _, s, w, _, _ in _PAIRS])
def test_visited_pairs_at_the_cells_shapes(monkeypatch, cell, d, s, window,
                                           kernels, worked):
    """The engagement counter: pairs the mask keeps over pairs the walk
    multiplies, a pure function of the static shapes. A window, keys of
    192 (the 512 x 1,024 pair) and a whole tile whose strips would be no
    whole lane tiles keep the generic body's count at any strip count."""
    q = np.arange(s)
    kept = int((q + 1 - (0 if window is None
                         else np.maximum(q - window + 1, 0))).sum())
    for kernel in kernels:
        tiles = fa._tile_rule(kernel, s, s, d, jnp.bfloat16)
        for strips, pairs in zip((1, 2, 4), worked):
            monkeypatch.setattr(fa, "_STRIPS", strips)
            assert fa._visited_pairs(tiles, s, s, True, window) == (
                kept, pairs)
        # Segments or dropout mask every sub-tile whole.
        assert fa._visited_pairs(tiles, s, s, True, window, True) == (
            kept, worked[0])


def test_the_staircase_works_the_share_of_the_pairs_the_table_says():
    """gpt2m-train's shape: 66.7% of the pairs the walk multiplied were
    kept before the staircase, 89.0% at the table's four strips."""
    assert fa._STRIPS == 4
    tiles = fa._tile_rule("dkv", 1024, 1024, 64, jnp.bfloat16)
    kept, worked = fa._visited_pairs(tiles, 1024, 1024, True, None)
    assert (kept, worked) == (524800, 589824)
    assert round(100 * kept / worked, 1) == 89.0
    # Not causal: every pair kept, every sub-tile whole.
    assert fa._visited_pairs(tiles, 1024, 1024, False, None) == (
        1024 * 1024,) * 2
    # A band of 1 keeps a query's own key and all ahead: the sub-tile
    # wholly behind its queries is never visited.
    kept, worked = fa._visited_pairs(tiles, 1024, 1024, False, 1)
    assert kept == sum(1024 - q for q in range(1024))
    assert worked == 1024 * 1024 - 512 * 512


def test_tile_rule_bounds_the_resident_keys():
    """The forward holds K and V^T of a head twice over: 2 MiB a block."""
    assert fa._tile_rule("fwd", 512, 16384, 128, jnp.bfloat16) == (
        512, 8192, 512, 512)
    assert fa._tile_rule("fwd", 1024, 32768, 64, jnp.bfloat16) == (
        512, 16384, 512, 512)
    # 9 sub-tiles of keys: the largest divisor under the bound is all 9.
    assert fa._tile_rule("fwd", 512, 4608, 128, jnp.bfloat16)[1] == 4608
    # 17 sub-tiles at head_dim 128: a prime over the bound falls to one.
    assert fa._tile_rule("fwd", 512, 8704, 128, jnp.bfloat16)[1] == 512


def test_tile_rule_keeps_odd_lengths_compilable():
    """A length no multiple of 128 divides is one whole block (a 64-wide
    query block's log-sum-exp row is refused by the chip's compiler)."""
    for s in (576, 704, 96):
        for kernel in ("fwd", "dq", "dkv"):
            assert fa._tile_rule(kernel, s, s, 64, jnp.bfloat16)[:2] == (s, s)
    # Past 1,024 a length 512 does not divide keeps the pair of before.
    assert fa._tile_rule("fwd", 1280, 1280, 64, jnp.bfloat16) == (
        256, 256, 256, 256)


def test_callers_blocks_still_win():
    q = jax.ShapeDtypeStruct((1, 2048, 2, 64), jnp.bfloat16)
    tiles, _ = fa._prepare(q, q, q, 256, None, True)
    assert [t[0] for t in tiles] == [256] * 3
    assert [t[2] for t in tiles] == [256] * 3  # worked whole
    assert tiles[0][1] == 2048 and tiles[1][1] == 1024
    tiles, _ = fa._prepare(q, q, q, None, 128, True)
    assert [t[1::2] for t in tiles] == [(128, 128)] * 3
    with pytest.raises(ValueError, match="divisible"):
        fa._prepare(q, q, q, 384, None, True)


def _kernel_equations(jaxpr) -> int:
    """Equations of a jaxpr and of every jaxpr inside it."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    n += _kernel_equations(inner)
    return n


def _forward_equations(s, window, dtype=jnp.bfloat16):
    q = jax.ShapeDtypeStruct((1, s, 32, 128), dtype)
    kv = jax.ShapeDtypeStruct((1, s, 4, 128), dtype)
    jaxpr = jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, window=window, interpret=True))(q, kv, kv)
    return _kernel_equations(jaxpr.jaxpr)


@pytest.mark.parametrize("window", [None, 2048])
def test_forward_kernel_does_not_grow_with_the_sequence(window):
    """The guard for set-up: a start traces and lowers every kernel
    before its compile-cache key exists, so what a kernel traces to is
    paid at every start of a program that holds it. The forward's
    sub-tiles run under ``fori_loop``s with a masked and an unmasked body:
    8,192 tokens trace to exactly what 1,024 do."""
    at_1024 = _forward_equations(1024, window)
    for s in (1536, 2560, 8192):
        assert _forward_equations(s, window) == at_1024
    # ... and to less than twice the one-body kernel of float32 operands,
    # whose blocks are worked whole.
    assert at_1024 < 2 * _forward_equations(1024, window, jnp.float32)


def test_the_sweep_script_says_what_layout_it_timed(tmp_path):
    """``scripts/flash_sweep.py`` on the CPU (a rehearsal, never a time):
    every row names the layout its kernel's operands cross HBM in, and
    times the call between a projection-shaped producer and consumer
    too, where the layout copies its wrapper implies show."""
    import json
    import os
    import sys

    scripts = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts")
    sys.path.insert(0, scripts)
    try:
        sweep = importlib.import_module("flash_sweep")
    finally:
        sys.path.remove(scripts)
    out = tmp_path / "sweep.json"
    assert sweep.main(["--shapes", "tiny-causal", "--reps", "1",
                       "--out", str(out)]) == 0
    rows = json.loads(out.read_text())["rows"]
    assert [row["kernel"] for row in rows] == ["fwd", "dq", "dkv"]
    for row in rows:
        assert "error" not in row, row
        assert row["layout"] == fa._LAYOUTS[row["kernel"]]
        assert row["ms"] > 0 and "ms_in_program" in row
