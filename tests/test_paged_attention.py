"""The paged decode attention kernel (interpret mode here) against its
plain ``jax.numpy`` reference: lengths, table order, trash and garbage."""

import numpy as np
import pytest

import jax.numpy as jnp

from fluxmpi_tpu.ops.paged_attention import (
    paged_decode_attention,
    paged_decode_reference,
)
from fluxmpi_tpu.serving.cache import TRASH_BLOCK

BLOCK = 8
MAX_BLOCKS = 4
HEADS = 3
LAYERS = 2
GARBAGE = 1e4

# The slot under test: idle, one position, a block's edge, one past it,
# mid-block, the whole table.
LENGTHS = {"idle": 0, "one": 1, "edge": BLOCK, "past_edge": BLOCK + 1,
           "mid_block": 2 * BLOCK + 3, "full_table": MAX_BLOCKS * BLOCK}


def _case(length, head_dim, dtype, seed):
    """Five slots over a pool full of garbage: the slot under test, an
    idle slot (length 0, all-trash table) and three of other lengths;
    blocks handed out in scrambled order; the tables' tails padded with
    the trash block; garbage in the trash block, in every block no table
    names and in the rows past each length."""
    rng = np.random.default_rng(seed)
    lengths = np.array([length, 0, 5, 2 * BLOCK, 3 * BLOCK + 1], np.int32)
    width = HEADS * head_dim
    num_blocks = 1 + len(lengths) * MAX_BLOCKS + 3
    pools = np.full((2, LAYERS, num_blocks, BLOCK, width), GARBAGE,
                    np.float32)
    order = iter(rng.permutation(np.arange(1, num_blocks)))
    tables = np.full((len(lengths), MAX_BLOCKS), TRASH_BLOCK, np.int32)
    for slot, n in enumerate(lengths):
        for j in range(-(-int(n) // BLOCK)):
            block = tables[slot, j] = next(order)
            rows = min(BLOCK, int(n) - j * BLOCK)
            pools[:, :, block, :rows] = rng.normal(
                size=(2, LAYERS, rows, width)
            )
    q = rng.normal(size=(len(lengths), HEADS, head_dim))
    return (jnp.asarray(q, dtype), jnp.asarray(pools[0], dtype),
            jnp.asarray(pools[1], dtype), jnp.asarray(tables),
            jnp.asarray(lengths))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("length", sorted(LENGTHS), ids=sorted(LENGTHS))
def test_paged_decode_attention_matches_reference(length, head_dim, dtype):
    args = _case(LENGTHS[length], head_dim, dtype, seed=len(length))
    layer = 1
    got = paged_decode_attention(*args, layer=layer).astype(jnp.float32)
    want = paged_decode_reference(*args, layer=layer).astype(jnp.float32)
    assert got.shape == (5, HEADS, head_dim)
    # No garbage got through: outputs are averages of unit normals.
    assert float(jnp.max(jnp.abs(got))) < 10.0
    # bf16: both round one float32 result to the output's 8 bits.
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    # Idle slots (length 0) output zeros; so does the slot under test
    # when it is one.
    lengths = np.asarray(args[4])
    np.testing.assert_array_equal(
        np.asarray(got)[lengths == 0], 0.0
    )
    # The other layer's rows are not read: an independent dense softmax
    # over slot 0's own positions of THIS layer gives the same answer.
    n = int(lengths[0])
    if n:
        q, k_pool, v_pool, tables = (np.asarray(a, np.float32)
                                     for a in args[:4])
        blocks = np.asarray(args[3])[0, : -(-n // BLOCK)]
        keys = k_pool[layer, blocks].reshape(-1, HEADS, head_dim)[:n]
        values = v_pool[layer, blocks].reshape(-1, HEADS, head_dim)[:n]
        scores = np.einsum("hd,thd->ht", q[0], keys) / np.sqrt(head_dim)
        probs = np.exp(scores - scores.max(axis=1, keepdims=True))
        probs /= probs.sum(axis=1, keepdims=True)
        np.testing.assert_allclose(
            np.asarray(got)[0], np.einsum("ht,thd->hd", probs, values),
            rtol=5 * tol, atol=5 * tol,
        )


def test_paged_decode_attention_rejects_mismatched_shapes():
    q, k_pool, v_pool, tables, lengths = _case(3, 64, jnp.float32, seed=0)
    with pytest.raises(ValueError, match="heads \\* head_dim"):
        paged_decode_attention(q[:, :2], k_pool, v_pool, tables, lengths)
    with pytest.raises(ValueError, match="tables must be"):
        paged_decode_reference(q, k_pool, v_pool, tables[:2], lengths)
    with pytest.raises(ValueError, match="layer 2 outside"):
        paged_decode_attention(q, k_pool, v_pool, tables, lengths, layer=2)


# ---------------------------------------------------------------------------
# Grouped heads and a window over a ring of blocks
# ---------------------------------------------------------------------------

WINDOW = 20
RING = -(-(WINDOW + BLOCK) // BLOCK)  # 4 blocks hold window + one block
Q_HEADS, KV_HEADS = 16, 2  # 8 query heads a K/V head

# Below the window, at it, one past it, a block's edge past it, and far
# enough past it that the ring has wrapped several times.
RING_LENGTHS = {"idle": 0, "one": 1, "below": WINDOW - 3, "at": WINDOW,
                "past": WINDOW + 1, "edge": 4 * BLOCK, "past_edge": 4 * BLOCK + 1,
                "wrapped": 9 * BLOCK + 5}


def _ring_case(length, head_dim, window, seed, q_heads=Q_HEADS):
    """Four slots whose sequences were written position by position into
    their tables (a ring of RING blocks with ``window``, else a plain
    table), over a pool of garbage. Returns the kernel's arguments and
    each slot's own keys and values, position by position."""
    rng = np.random.default_rng(seed)
    lengths = np.array([length, 0, 7, 6 * BLOCK + 2], np.int32)
    width = KV_HEADS * head_dim
    entries = RING if window else -(-int(lengths.max()) // BLOCK)
    num_blocks = 1 + len(lengths) * entries + 2
    pools = np.full((2, LAYERS, num_blocks, BLOCK, width), GARBAGE, np.float32)
    order = iter(rng.permutation(np.arange(1, num_blocks)))
    tables = np.full((len(lengths), entries), TRASH_BLOCK, np.int32)
    history = []
    for slot, n in enumerate(lengths):
        rows = rng.normal(size=(2, LAYERS, int(n), width))
        history.append(rows)
        for p in range(int(n)):
            entry = (p // BLOCK) % entries
            if tables[slot, entry] == TRASH_BLOCK:
                tables[slot, entry] = next(order)
            pools[:, :, tables[slot, entry], p % BLOCK] = rows[:, :, p]
    q = rng.normal(size=(len(lengths), q_heads, head_dim))
    args = (jnp.asarray(q, jnp.float32), jnp.asarray(pools[0]),
            jnp.asarray(pools[1]), jnp.asarray(tables), jnp.asarray(lengths))
    return args, history


# 8 query heads a K/V head, and 16 (32 over 2: a pool 256 lanes wide at
# head_dim 128), and 5 (10 over 2: query rows that fill no whole sublane
# tile and are padded to one).
@pytest.mark.parametrize("q_heads", [Q_HEADS, 32, 10],
                         ids=["8_to_1", "16_to_1", "5_to_1"])
@pytest.mark.parametrize("window", [WINDOW, None], ids=["window", "full"])
@pytest.mark.parametrize("head_dim", [16, 128])
@pytest.mark.parametrize("length", sorted(RING_LENGTHS), ids=sorted(RING_LENGTHS))
def test_paged_decode_attention_grouped_heads_and_window(length, head_dim,
                                                         window, q_heads):
    """Grouped query heads, with and without a window: the kernel
    (interpret mode), its reference, and a dense softmax over each slot's
    own last ``window`` positions agree; what the ring has overwritten
    and the garbage around it never reach the output."""
    args, history = _ring_case(RING_LENGTHS[length], head_dim, window,
                               seed=len(length), q_heads=q_heads)
    layer = 1
    got = np.asarray(paged_decode_attention(*args, layer=layer, window=window))
    want = np.asarray(paged_decode_reference(*args, layer=layer, window=window))
    assert got.shape == (4, q_heads, head_dim)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    q = np.asarray(args[0])
    for slot, n in enumerate(np.asarray(args[4])):
        if n == 0:
            np.testing.assert_array_equal(got[slot], 0.0)
            continue
        lo = max(0, n - window) if window else 0
        keys, values = (history[slot][i][layer, lo:n].reshape(
            -1, KV_HEADS, head_dim) for i in (0, 1))
        group = q_heads // KV_HEADS
        keys, values = (np.repeat(a, group, axis=1) for a in (keys, values))
        scores = np.einsum("hd,thd->ht", q[slot], keys) / np.sqrt(head_dim)
        probs = np.exp(scores - scores.max(axis=1, keepdims=True))
        probs /= probs.sum(axis=1, keepdims=True)
        np.testing.assert_allclose(
            got[slot], np.einsum("ht,thd->hd", probs, values),
            rtol=1e-4, atol=1e-4,
        )


def test_paged_decode_attention_rejects_a_ring_too_short_for_its_window():
    (q, k_pool, v_pool, tables, lengths), _ = _ring_case(5, 16, WINDOW, seed=0)
    with pytest.raises(ValueError, match="cannot hold a window"):
        paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                               window=RING * BLOCK)


# ---------------------------------------------------------------------------
# A cache of latent rows: one pool, each row key and value
# ---------------------------------------------------------------------------

RANK, ROPE = 32, 16


def _latent_case(length, width, dtype, seed):
    """As :func:`_case`, over ONE pool of latent rows ``[c; k_r]`` padded
    with zeros to ``width`` lanes (what the cache writes), garbage
    everywhere no table and no length reaches."""
    from fluxmpi_tpu.ops.paged_attention import (  # noqa: F401
        paged_latent_decode_attention,
    )

    rng = np.random.default_rng(seed)
    lengths = np.array([length, 0, 5, 2 * BLOCK, 3 * BLOCK + 1], np.int32)
    num_blocks = 1 + len(lengths) * MAX_BLOCKS + 3
    pool = np.full((LAYERS, num_blocks, BLOCK, width), GARBAGE, np.float32)
    pool[..., RANK + ROPE:] = 0.0
    order = iter(rng.permutation(np.arange(1, num_blocks)))
    tables = np.full((len(lengths), MAX_BLOCKS), TRASH_BLOCK, np.int32)
    for slot, n in enumerate(lengths):
        for j in range(-(-int(n) // BLOCK)):
            block = tables[slot, j] = next(order)
            rows = min(BLOCK, int(n) - j * BLOCK)
            pool[:, block, :rows, :RANK + ROPE] = rng.normal(
                size=(LAYERS, rows, RANK + ROPE)
            )
    q_abs = 0.3 * rng.normal(size=(len(lengths), HEADS, RANK))
    q_rope = 0.3 * rng.normal(size=(len(lengths), HEADS, ROPE))
    return (jnp.asarray(q_abs, dtype), jnp.asarray(q_rope, dtype),
            jnp.asarray(pool, dtype), jnp.asarray(tables),
            jnp.asarray(lengths))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("width", [RANK + ROPE, 128],
                         ids=["exact", "lane_padded"])
@pytest.mark.parametrize("length", sorted(LENGTHS), ids=sorted(LENGTHS))
def test_paged_latent_decode_matches_reference(length, width, dtype):
    from fluxmpi_tpu.ops.paged_attention import (
        paged_latent_decode_attention,
        paged_latent_decode_reference,
    )

    args = _latent_case(LENGTHS[length], width, dtype, seed=len(length))
    layer = 1
    got = paged_latent_decode_attention(*args, layer=layer).astype(jnp.float32)
    want = paged_latent_decode_reference(*args, layer=layer).astype(
        jnp.float32)
    assert got.shape == (5, HEADS, RANK)
    # No garbage got through: outputs are averages of unit normals.
    assert float(jnp.max(jnp.abs(got))) < 10.0
    # bf16: the kernel narrows p to the pool's dtype for its product with
    # the values (8 bits), the reference keeps float32 there.
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    lengths = np.asarray(args[4])
    np.testing.assert_array_equal(np.asarray(got)[lengths == 0], 0.0)
    # An independent dense softmax over slot 0's own rows of THIS layer:
    # the row is the key (all of it) and the value (its first RANK lanes).
    n = int(lengths[0])
    if n:
        q_abs, q_rope, pool = (np.asarray(a, np.float32) for a in args[:3])
        tables = np.asarray(args[3])
        rows = np.concatenate(
            [pool[layer, tables[0, j]] for j in range(MAX_BLOCKS)]
        )[:n, :RANK + ROPE]
        q = np.concatenate([q_abs[0], q_rope[0]], axis=-1)  # [heads, 48]
        s = q @ rows.T
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        dense = (p / p.sum(axis=-1, keepdims=True)) @ rows[:, :RANK]
        np.testing.assert_allclose(np.asarray(got)[0], dense, rtol=tol,
                                   atol=tol)


def test_paged_latent_decode_one_block_tables_and_many_heads():
    """A table of ONE block (the grid's second axis has one step) and a
    head count that is not a multiple of 8 sublanes."""
    from fluxmpi_tpu.ops.paged_attention import (
        paged_latent_decode_attention,
        paged_latent_decode_reference,
    )

    rng = np.random.default_rng(3)
    pool = jnp.asarray(rng.normal(size=(1, 4, BLOCK, RANK + ROPE)),
                       jnp.float32)
    q_abs = jnp.asarray(0.3 * rng.normal(size=(3, 11, RANK)), jnp.float32)
    q_rope = jnp.asarray(0.3 * rng.normal(size=(3, 11, ROPE)), jnp.float32)
    tables = jnp.asarray([[2], [TRASH_BLOCK], [3]], jnp.int32)
    lengths = jnp.asarray([BLOCK, 0, 3], jnp.int32)
    got = paged_latent_decode_attention(q_abs, q_rope, pool, tables, lengths)
    want = paged_latent_decode_reference(q_abs, q_rope, pool, tables, lengths)
    assert got.shape == (3, 11, RANK)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_paged_latent_decode_rejects_mismatched_shapes():
    from fluxmpi_tpu.ops.paged_attention import paged_latent_decode_attention

    q_abs, q_rope, pool, tables, lengths = _latent_case(
        5, RANK + ROPE, jnp.float32, seed=0)
    with pytest.raises(ValueError, match="rank \\+ rope"):
        paged_latent_decode_attention(q_abs, q_rope, pool[..., :RANK],
                                      tables, lengths)
    with pytest.raises(ValueError, match="tables must be"):
        paged_latent_decode_attention(q_abs, q_rope, pool, tables[:3],
                                      lengths)
    with pytest.raises(ValueError, match="outside the pool"):
        paged_latent_decode_attention(q_abs, q_rope, pool, tables, lengths,
                                      layer=LAYERS)


# ---------------------------------------------------------------------------
# The walk: the kernels visit the live slots' live blocks and nothing else
# ---------------------------------------------------------------------------

WALK_BLOCK = 8
WALK_ENTRIES = 5  # a table of 5 blocks: 40 positions, or a ring for 32

# What a walk can get wrong, as the slots' lengths.
WALKS = {
    "nothing_live": [0] * 6,
    "one_live_of_48": [0] * 29 + [19] + [0] * 18,
    "all_full_tables": [WALK_BLOCK * WALK_ENTRIES] * 6,
    "one_token_last_blocks": [1, WALK_BLOCK + 1, 2 * WALK_BLOCK + 1, 0,
                              4 * WALK_BLOCK + 1, 3 * WALK_BLOCK + 1],
    "dead_between_live": [9, 0, 0, 17, 0, 40, 0],
    "first_and_last_dead": [0, 0, 12, 8, 0],
}
# The shapes the walk serves: GPT-2's (a K/V head a query head), 5 query
# rows a K/V head padded to 8 (falcon-h1: 20 over 4 of 128), 8 a K/V
# head under a window on a ring (trinity-mini: 32 over 4 of 128; the
# ring holds the window and one block), and the latent pool at its served
# lanes (576 of 640 live, 512 of them the value).
WALK_KERNELS = {
    "one_to_one": dict(heads=4, kv_heads=4, head_dim=64),
    "five_to_one": dict(heads=20, kv_heads=4, head_dim=128),
    "ring": dict(heads=32, kv_heads=4, head_dim=128,
                 window=(WALK_ENTRIES - 1) * WALK_BLOCK),
    "latent": dict(heads=16, rank=512, rope=64, width=640),
}


def _walk_case(lengths, shape, seed):
    """Sequences written position by position into scrambled blocks (a
    ring under a window: lengths past the table are stretched so that it
    wraps), garbage wherever no live position lies. Returns the kernel,
    its reference, their arguments and the keyword arguments."""
    from fluxmpi_tpu.ops.paged_attention import (
        paged_latent_decode_attention,
        paged_latent_decode_reference,
    )

    rng = np.random.default_rng(seed)
    window = shape.get("window")
    lengths = np.asarray(lengths, np.int32)
    if window:
        # Every third block of length becomes two turns of the ring more.
        lengths = np.where(lengths > 2 * WALK_BLOCK,
                           lengths + 2 * WALK_ENTRIES * WALK_BLOCK, lengths)
    slots = len(lengths)
    latent = "rank" in shape
    width = shape["width"] if latent else shape["kv_heads"] * shape["head_dim"]
    live_lanes = shape["rank"] + shape["rope"] if latent else width
    num_blocks = 1 + slots * WALK_ENTRIES + 2
    pools = np.full((1 if latent else 2, LAYERS, num_blocks, WALK_BLOCK,
                     width), GARBAGE, np.float32)
    pools[..., live_lanes:] = 0.0
    order = iter(rng.permutation(np.arange(1, num_blocks)))
    tables = np.full((slots, WALK_ENTRIES), TRASH_BLOCK, np.int32)
    for slot, n in enumerate(lengths):
        for p in range(int(n)):
            entry = (p // WALK_BLOCK) % WALK_ENTRIES
            if tables[slot, entry] == TRASH_BLOCK:
                tables[slot, entry] = next(order)
            pools[:, :, tables[slot, entry], p % WALK_BLOCK, :live_lanes] = (
                rng.normal(size=(len(pools), LAYERS, live_lanes)))
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)
    if latent:
        q = [0.3 * rng.normal(size=(slots, shape["heads"], shape[k]))
             for k in ("rank", "rope")]
        args = (*(jnp.asarray(a, jnp.float32) for a in q),
                jnp.asarray(pools[0]), tables, lengths)
        return (paged_latent_decode_attention, paged_latent_decode_reference,
                args, {})
    q = rng.normal(size=(slots, shape["heads"], shape["head_dim"]))
    args = (jnp.asarray(q, jnp.float32), jnp.asarray(pools[0]),
            jnp.asarray(pools[1]), tables, lengths)
    return (paged_decode_attention, paged_decode_reference, args,
            {"window": window})


@pytest.mark.parametrize("kernel", sorted(WALK_KERNELS))
@pytest.mark.parametrize("walk", sorted(WALKS))
def test_paged_kernels_walk_the_live_blocks_only(walk, kernel):
    """Both kernels (interpret mode) against their references over the
    tables a walk can get wrong; a dead slot, wherever it lies, returns
    zeros, and no garbage reaches a live one. The list a caller hands in
    (one a tick for all layers) serves as the kernel's own does."""
    from fluxmpi_tpu.ops.paged_attention import live_block_walk

    attend, reference, args, kw = _walk_case(
        WALKS[walk], WALK_KERNELS[kernel], seed=len(walk))
    tables, lengths = args[-2:]
    got = np.asarray(attend(*args, layer=1, **kw))
    want = np.asarray(reference(*args, layer=1, **kw))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert float(np.max(np.abs(got))) < 10.0
    np.testing.assert_array_equal(got[np.asarray(lengths) == 0], 0.0)
    handed = live_block_walk(tables, lengths, block_size=WALK_BLOCK, **kw)
    np.testing.assert_array_equal(
        np.asarray(attend(*args, layer=1, walk=handed, **kw)), got)


@pytest.mark.parametrize("window", [None, (WALK_ENTRIES - 1) * WALK_BLOCK],
                         ids=["full", "ring"])
@pytest.mark.parametrize("walk", sorted(WALKS))
def test_live_block_walk_lists_each_live_block_once(walk, window):
    """The list against a loop over the slots: every live slot's blocks
    (those that meet the window, found on the ring), slot by slot, first
    block first; ``count`` of them; the last one held past the count."""
    from fluxmpi_tpu.ops.paged_attention import live_block_walk

    *_, args, kw = _walk_case(
        WALKS[walk], {**WALK_KERNELS["one_to_one"], "window": window}, seed=1)
    tables, lengths = (np.asarray(a) for a in args[-2:])
    slot, block, index, count = (np.asarray(a) for a in live_block_walk(
        *args[-2:], block_size=WALK_BLOCK, **kw))
    want = [
        (s, tables[s, j % WALK_ENTRIES], j)
        for s, n in enumerate(lengths)
        for j in range(max(n - window, 0) // WALK_BLOCK if window else 0,
                       -(-n // WALK_BLOCK))
    ]
    assert count.tolist() == [len(want)]
    assert slot.shape == (tables.size,)
    got = list(zip(slot.tolist(), block.tolist(), index.tolist()))
    assert got[:len(want)] == want
    assert set(got[len(want):]) <= {want[-1] if want else (0, TRASH_BLOCK, 0)}
    assert TRASH_BLOCK not in block[:len(want)]
