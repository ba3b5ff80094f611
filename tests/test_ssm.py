"""The state-space kernels (``fluxmpi_tpu/ops/ssm.py``) at small sizes on
the CPU: the decode tick's in-place state update as the Pallas kernel in
interpret mode against its plain reference, and the prefill's chunked
scan against the recurrence taken one token at a time.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fluxmpi_tpu.ops import ssm

LAYERS, ENTRIES, HEADS, HEAD_DIM, D_STATE = 2, 6, 8, 16, 128
SLOTS = 4
# A tail of three columns of 160 = 480 numbers: three whole 128-lane tiles
# and 96 lanes of a fourth, padded with zeros in the pool.
TAIL = (3, HEADS * HEAD_DIM + 2 * 16)
TILES = 4


def _operands(seed=0, dtype=jnp.float32, groups=None):
    """``(state pool, tail pool), (tail, x, step, decay, b, c)``: pools
    full of noise, so that an entry written by mistake shows. With
    ``groups``, B and C carry a group axis and a head is 64 wide: 512
    lanes, a group 256 or 128 of them (whole tiles of the kernel's
    walk). ``groups`` ``(groups, d_state)``: a state of ``d_state`` (256:
    two lane tiles on the sublanes) under heads of 128, a group four
    tiles of the walk."""
    d_state = D_STATE
    head_dim = HEAD_DIM if groups is None else 64
    if isinstance(groups, tuple):
        (groups, d_state), head_dim = groups, 128
    grouped = (SLOTS, d_state) if groups is None else (SLOTS, groups, d_state)
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    pool = jax.random.normal(
        keys[0], (LAYERS, ENTRIES, d_state, HEADS * head_dim)).astype(dtype)
    tail_pool = jax.random.normal(
        keys[5], (LAYERS, ENTRIES, TILES, 128)).astype(jnp.bfloat16)
    tail = jax.random.normal(keys[6], (SLOTS, *TAIL))
    x = jax.random.normal(keys[1], (SLOTS, HEADS, head_dim))
    step = jax.nn.softplus(jax.random.normal(keys[2], (SLOTS, HEADS)) - 2.0)
    decay = jnp.exp(-step * jnp.linspace(1.0, 16.0, HEADS))
    b = jax.random.normal(keys[3], grouped)
    c = jax.random.normal(keys[4], grouped)
    return (pool, tail_pool), (tail, x, step, decay, b, c)


def _update(kernel, pools, entries, operands, layer=0):
    """The kernel in interpret mode, or its plain reference."""
    if kernel:
        return ssm.ssm_state_update(*pools, entries, *operands, layer=layer,
                                    interpret=True)
    return ssm.ssm_state_update_reference(*pools, entries, *operands,
                                          layer=layer)


def _by_hand(pool, entry, slot, operands, layer):
    """One slot's update in numpy float64: ``(y, new state)``."""
    x, step, decay, b, c = (np.asarray(v, np.float64)[slot]
                            for v in operands[1:])
    state = np.asarray(ssm.from_pool_layout(pool[layer, entry], HEADS),
                       np.float64)
    d_state = pool.shape[2]
    # Head h reads its group's B and C: group h // (heads / groups).
    b, c = (np.repeat(v.reshape(-1, d_state), HEADS // v.reshape(
        -1, d_state).shape[0], axis=0) for v in (b, c))
    moved = (decay[:, None, None] * state
             + (step[:, None] * x)[:, :, None] * b[:, None, :])
    return (np.einsum("hpn,hn->hp", moved, c),
            np.asarray(ssm.to_pool_layout(jnp.asarray(moved))))


# Idle slots point at the trash entry (0): none live, one, some, all, and
# live slots whose entries run against the slot order.
LIVE = {"none_live": (0, 0, 0, 0), "one_live": (0, 0, 3, 0),
        "two_live": (5, 0, 0, 2), "all_live": (4, 2, 3, 1),
        "against_slot_order": (5, 4, 0, 1)}


# One group for all heads (no group axis), two groups of four heads, four
# of two; two groups of four heads of 128 over a state of 256.
@pytest.mark.parametrize("groups", [None, 2, 4, (2, 256)],
                         ids=["one_group", "two_groups", "four_groups",
                              "two_groups_state_256"])
@pytest.mark.parametrize("entries", LIVE.values(), ids=LIVE.keys())
@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "reference"])
def test_state_update_moves_live_states_and_leaves_the_rest(entries, kernel,
                                                            groups):
    layer = 1
    (pool, tail_pool), operands = _operands(groups=groups)
    entries = jnp.asarray(entries, jnp.int32)
    y, out, tails = _update(kernel, (pool, tail_pool), entries, operands,
                            layer)
    assert y.shape == operands[1].shape and y.dtype == jnp.float32
    assert out.shape == pool.shape and out.dtype == pool.dtype
    assert tails.shape == tail_pool.shape and tails.dtype == tail_pool.dtype
    live = {int(e): slot for slot, e in enumerate(entries) if int(e)}
    # Bit for bit, states and tails: the other layer, the trash entry,
    # every entry no live slot names (an idle slot's entry is neither
    # read nor written).
    np.testing.assert_array_equal(out[0], pool[0])
    np.testing.assert_array_equal(tails[0], tail_pool[0])
    for entry in range(ENTRIES):
        if entry not in live:
            np.testing.assert_array_equal(out[layer, entry],
                                          pool[layer, entry])
            np.testing.assert_array_equal(tails[layer, entry],
                                          tail_pool[layer, entry])
    want_tails = ssm.tail_to_pool_layout(operands[0]).astype(jnp.bfloat16)
    for slot, entry in enumerate(entries):
        if not int(entry):
            np.testing.assert_array_equal(y[slot], 0.0)
            continue
        want_y, want_state = _by_hand(pool, int(entry), slot, operands, layer)
        # float32 sums of 128 products of O(1) numbers.
        np.testing.assert_allclose(y[slot], want_y, rtol=0, atol=2e-4)
        np.testing.assert_allclose(out[layer, entry], want_state, rtol=0,
                                   atol=1e-5)
        # The slot's new tail, rounded once to the pool's dtype, whole.
        np.testing.assert_array_equal(tails[layer, entry], want_tails[slot])


@pytest.mark.parametrize("groups", [None, 4, (2, 256)],
                         ids=["one_group", "four_groups",
                              "two_groups_state_256"])
@pytest.mark.parametrize("entries", LIVE.values(), ids=LIVE.keys())
def test_kernel_and_reference_write_the_same_pools(entries, groups):
    """One contract: what the chip runs and what runs anywhere else leave
    the same tail pool bit for bit, and the same states to the last place
    of a float32 sum."""
    pools, operands = _operands(seed=4, groups=groups)
    entries = jnp.asarray(entries, jnp.int32)
    y_k, out_k, tails_k = _update(True, pools, entries, operands)
    y_r, out_r, tails_r = _update(False, pools, entries, operands)
    np.testing.assert_array_equal(tails_k, tails_r)
    np.testing.assert_allclose(out_k, out_r, rtol=0, atol=1e-5)
    np.testing.assert_allclose(y_k, y_r, rtol=0, atol=2e-4)
    # Where nothing moved, nothing was rounded either.
    idle = np.ones((ENTRIES,), bool)
    idle[[e for e in entries.tolist() if e]] = False
    np.testing.assert_array_equal(out_k[:, idle], out_r[:, idle])


def test_kernel_equals_reference_on_a_bfloat16_pool():
    """The pool's dtype is the state's: ``y`` comes from the moved state
    before it is rounded, the pool holds it rounded, on both paths."""
    pools, operands = _operands(seed=1, dtype=jnp.bfloat16)
    entries = jnp.asarray((2, 0, 5, 1), jnp.int32)
    y_k, out_k, tails_k = _update(True, pools, entries, operands)
    y_r, out_r, tails_r = _update(False, pools, entries, operands)
    assert out_k.dtype == out_r.dtype == jnp.bfloat16
    np.testing.assert_allclose(y_k, y_r, rtol=0, atol=2e-4)
    # One rounding to bfloat16 of the same float32 sums (a fused
    # multiply-add on one path may tip a tie: one unit in the last place).
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32), rtol=2 ** -7)
    np.testing.assert_array_equal(tails_k, tails_r)


def test_live_entries_compacts_in_slot_order_and_holds_the_last():
    ids, rows, count = ssm.live_entries(jnp.asarray((0, 7, 0, 3, 9, 0)))
    assert int(count[0]) == 3
    np.testing.assert_array_equal(rows, (1, 3, 4, 4, 4, 4))
    np.testing.assert_array_equal(ids, (7, 3, 9, 9, 9, 9))
    ids, rows, count = ssm.live_entries(jnp.zeros((4,), jnp.int32))
    assert int(count[0]) == 0
    np.testing.assert_array_equal(rows, 0)
    np.testing.assert_array_equal(ids, 0)


def test_without_a_tpu_the_public_call_is_the_reference():
    pools, operands = _operands(seed=2)
    entries = jnp.asarray((1, 0, 2, 0), jnp.int32)
    got = ssm.ssm_state_update(*pools, entries, *operands)
    want = ssm.ssm_state_update_reference(*pools, entries, *operands)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_state_update_refuses_shapes_that_do_not_fit():
    (pool, tail_pool), (tail, x, step, decay, b, c) = _operands()
    entries = jnp.zeros((SLOTS,), jnp.int32)
    rest = (x, step, decay, b, c)
    with pytest.raises(ValueError, match="expected"):
        ssm.ssm_state_update(pool, tail_pool, entries, tail, x[:, :4], step,
                             decay, b, c)
    with pytest.raises(ValueError, match="layer 2 outside"):
        ssm.ssm_state_update(pool, tail_pool, entries, tail, *rest, layer=2)
    with pytest.raises(ValueError, match="a state pool is"):
        ssm.ssm_state_update(pool[0], tail_pool, entries, tail, *rest)
    # A tail pool of another width, of other entries, tails of other slots.
    for pool_, tail_ in ((tail_pool[:, :, :3], tail), (tail_pool[:, :5], tail),
                         (tail_pool, tail[:3])):
        with pytest.raises(ValueError, match="expected tails"):
            ssm.ssm_state_update(pool, pool_, entries, tail_, *rest)


def test_pool_layout_is_the_states_transposed_side_by_side():
    state = jnp.arange(2 * 3 * 4 * 5, dtype=jnp.float32).reshape(2, 3, 4, 5)
    held = ssm.to_pool_layout(state)  # [.., d_state, heads * head_dim]
    assert held.shape == (2, 5, 12)
    assert float(held[1, 4, 2 * 4 + 3]) == float(state[1, 2, 3, 4])
    np.testing.assert_array_equal(ssm.from_pool_layout(held, 3), state)


# The cell's tail (25,344 = 198 whole tiles), the rehearsal's (480: 96
# lanes into a fourth tile), one a lane wider than a tile, one narrower.
@pytest.mark.parametrize("shape, tiles", [((3, 8448), 198), ((3, 160), 4),
                                          ((1, 129), 2), ((1, 5), 1)])
def test_tail_layout_is_the_columns_end_to_end_in_whole_tiles(shape, tiles):
    taps, width = shape
    tail = jnp.arange(2 * taps * width, dtype=jnp.float32).reshape(
        2, taps, width) + 1.0
    held = ssm.tail_to_pool_layout(tail)
    assert held.shape == (2, tiles, 128)
    flat = np.asarray(held).reshape(2, -1)
    np.testing.assert_array_equal(flat[:, :taps * width],
                                  np.asarray(tail).reshape(2, -1))
    np.testing.assert_array_equal(flat[:, taps * width:], 0.0)
    np.testing.assert_array_equal(ssm.tail_from_pool_layout(held, shape),
                                  tail)


def _recurrence(x, step, a_rate, b, c, initial=None):
    """``H_t = a_t H_{t-1} + D_t x_t B_t^T``, ``y_t = H_t C_t``, a token
    at a time."""
    batch, _, heads, head_dim = x.shape
    if b.ndim == 4:  # a group axis: head h reads group h // (heads / groups)
        b, c = (jnp.repeat(v, heads // v.shape[2], axis=2) for v in (b, c))
    else:
        b, c = (jnp.repeat(v[:, :, None], heads, axis=2) for v in (b, c))

    def token(state, at):
        x_t, step_t, b_t, c_t = at
        state = (state * jnp.exp(step_t * a_rate)[:, :, None, None]
                 + (step_t[..., None] * x_t)[..., None]
                 * b_t[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    if initial is None:
        initial = jnp.zeros((batch, heads, head_dim, b.shape[-1]))
    state, y = jax.lax.scan(token, initial, tuple(
        jnp.swapaxes(v, 0, 1) for v in (x, step, b, c)))
    return jnp.swapaxes(y, 0, 1), state


def _sequence(seq, seed=0, batch=2, heads=4, head_dim=8, d_state=16,
              groups=None):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(keys[0], (batch, seq, heads, head_dim))
    step = jax.nn.softplus(jax.random.normal(keys[1], (batch, seq, heads)) - 2)
    a_rate = -jnp.exp(jnp.linspace(0.0, 2.7, heads))
    grouped = (batch, seq, d_state) if groups is None else (
        batch, seq, groups, d_state)
    b = jax.random.normal(keys[2], grouped)
    c = jax.random.normal(keys[3], grouped)
    initial = jax.random.normal(keys[4], (batch, heads, head_dim, d_state))
    return x, step, a_rate, b, c, initial


# Inside the first chunk, on its edge, one past it, several
# chunks and a ragged tail.
@pytest.mark.parametrize("groups", [None, 1, 2, 4],
                         ids=["no_group_axis", "one", "two", "a_head_each"])
@pytest.mark.parametrize("seq", [1, 8, 9, 37])
@pytest.mark.parametrize("start", ["zero", "given"])
def test_chunked_scan_is_the_recurrence(seq, start, groups):
    x, step, a_rate, b, c, initial = _sequence(seq, seed=seq, groups=groups)
    initial = initial if start == "given" else None
    y, state = ssm.ssd_chunk_scan(x, step, a_rate, b, c, chunk=8,
                                  initial_state=initial)
    want_y, want_state = _recurrence(x, step, a_rate, b, c, initial)
    assert y.shape == x.shape and y.dtype == jnp.float32
    # float32 throughout: the two orders of the same sums.
    np.testing.assert_allclose(y, want_y, rtol=0, atol=5e-5)
    np.testing.assert_allclose(state, want_state, rtol=0, atol=5e-5)


def test_a_step_of_zero_is_a_position_that_is_not_there():
    """Padding: positions whose step is 0 leave the state where the last
    real token left it and add nothing to it."""
    real, padded = 11, 24
    x, step, a_rate, b, c, _ = _sequence(padded, seed=3)
    step = step.at[:, real:].set(0.0)
    _, state = ssm.ssd_chunk_scan(x, step, a_rate, b, c, chunk=8)
    _, want = ssm.ssd_chunk_scan(x[:, :real], step[:, :real], a_rate,
                                 b[:, :real], c[:, :real], chunk=8)
    np.testing.assert_allclose(state, want, rtol=0, atol=1e-6)


def test_chunked_scan_in_bfloat16_stays_near_the_recurrence():
    """Operands of the matmuls in bfloat16, decays, sums and the state
    float32: bfloat16's rounding of the operands (2 ** -9 each) and no
    more; a float32 state carried over three chunks."""
    x, step, a_rate, b, c, _ = _sequence(24, seed=5)
    y, state = ssm.ssd_chunk_scan(x.astype(jnp.bfloat16), step, a_rate,
                                  b, c, chunk=8)
    want_y, want_state = _recurrence(x, step, a_rate, b, c)
    assert state.dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(want_y)))
    assert float(jnp.max(jnp.abs(y - want_y))) < 0.02 * scale
    assert float(jnp.max(jnp.abs(state - want_state))) < 0.02 * float(
        jnp.max(jnp.abs(want_state)))
