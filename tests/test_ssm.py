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


def _operands(seed=0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    pool = jax.random.normal(
        keys[0], (LAYERS, ENTRIES, D_STATE, HEADS * HEAD_DIM)).astype(dtype)
    x = jax.random.normal(keys[1], (SLOTS, HEADS, HEAD_DIM))
    step = jax.nn.softplus(jax.random.normal(keys[2], (SLOTS, HEADS)) - 2.0)
    decay = jnp.exp(-step * jnp.linspace(1.0, 16.0, HEADS))
    b = jax.random.normal(keys[3], (SLOTS, D_STATE))
    c = jax.random.normal(keys[4], (SLOTS, D_STATE))
    return pool, (x, step, decay, b, c)


def _by_hand(pool, entry, slot, operands, layer):
    """One slot's update in numpy float64: ``(y, new state)``."""
    x, step, decay, b, c = (np.asarray(v, np.float64)[slot] for v in operands)
    state = np.asarray(ssm.from_pool_layout(pool[layer, entry], HEADS),
                       np.float64)
    moved = (decay[:, None, None] * state
             + (step[:, None] * x)[:, :, None] * b[None, None, :])
    return moved @ c, np.asarray(ssm.to_pool_layout(jnp.asarray(moved)))


# Idle slots point at the trash entry (0): none live, one, some, all.
@pytest.mark.parametrize("entries", [
    (0, 0, 0, 0), (0, 0, 3, 0), (5, 0, 0, 2), (4, 2, 3, 1),
], ids=["none_live", "one_live", "two_live", "all_live"])
@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "reference"])
def test_state_update_moves_live_states_and_leaves_the_rest(entries, kernel):
    layer = 1
    pool, operands = _operands()
    entries = jnp.asarray(entries, jnp.int32)
    if kernel:
        y, out = ssm.ssm_state_update(pool, entries, *operands, layer=layer,
                                      interpret=True)
    else:
        y, out = ssm.ssm_state_update_reference(pool, entries, *operands,
                                                layer=layer)
    assert y.shape == (SLOTS, HEADS, HEAD_DIM) and y.dtype == jnp.float32
    assert out.shape == pool.shape and out.dtype == pool.dtype
    live = {int(e): slot for slot, e in enumerate(entries) if int(e)}
    # Bit for bit: the other layer, the trash entry, every entry no live
    # slot names (an idle slot's state is neither read nor written).
    np.testing.assert_array_equal(out[0], pool[0])
    for entry in range(ENTRIES):
        if entry not in live:
            np.testing.assert_array_equal(out[layer, entry],
                                          pool[layer, entry])
    for slot, entry in enumerate(entries):
        if not int(entry):
            np.testing.assert_array_equal(y[slot], 0.0)
            continue
        want_y, want_state = _by_hand(pool, int(entry), slot, operands, layer)
        # float32 sums of 128 products of O(1) numbers.
        np.testing.assert_allclose(y[slot], want_y, rtol=0, atol=2e-4)
        np.testing.assert_allclose(out[layer, entry], want_state, rtol=0,
                                   atol=1e-5)


def test_kernel_equals_reference_on_a_bfloat16_pool():
    """The pool's dtype is the state's: ``y`` comes from the moved state
    before it is rounded, the pool holds it rounded, on both paths."""
    pool, operands = _operands(seed=1, dtype=jnp.bfloat16)
    entries = jnp.asarray((2, 0, 5, 1), jnp.int32)
    y_k, out_k = ssm.ssm_state_update(pool, entries, *operands,
                                      interpret=True)
    y_r, out_r = ssm.ssm_state_update_reference(pool, entries, *operands)
    assert out_k.dtype == out_r.dtype == jnp.bfloat16
    np.testing.assert_allclose(y_k, y_r, rtol=0, atol=2e-4)
    # One rounding to bfloat16 of the same float32 sums (a fused
    # multiply-add on one path may tip a tie: one unit in the last place).
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32), rtol=2 ** -7)


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
    pool, operands = _operands(seed=2)
    entries = jnp.asarray((1, 0, 2, 0), jnp.int32)
    y, out = ssm.ssm_state_update(pool, entries, *operands)
    y_r, out_r = ssm.ssm_state_update_reference(pool, entries, *operands)
    np.testing.assert_array_equal(y, y_r)
    np.testing.assert_array_equal(out, out_r)


def test_state_update_refuses_shapes_that_do_not_fit():
    pool, (x, step, decay, b, c) = _operands()
    entries = jnp.zeros((SLOTS,), jnp.int32)
    with pytest.raises(ValueError, match="expected"):
        ssm.ssm_state_update(pool, entries, x[:, :4], step, decay, b, c)
    with pytest.raises(ValueError, match="layer 2 outside"):
        ssm.ssm_state_update(pool, entries, x, step, decay, b, c, layer=2)
    with pytest.raises(ValueError, match="a state pool is"):
        ssm.ssm_state_update(pool[0], entries, x, step, decay, b, c)


def test_pool_layout_is_the_states_transposed_side_by_side():
    state = jnp.arange(2 * 3 * 4 * 5, dtype=jnp.float32).reshape(2, 3, 4, 5)
    held = ssm.to_pool_layout(state)  # [.., d_state, heads * head_dim]
    assert held.shape == (2, 5, 12)
    assert float(held[1, 4, 2 * 4 + 3]) == float(state[1, 2, 3, 4])
    np.testing.assert_array_equal(ssm.from_pool_layout(held, 3), state)


def _recurrence(x, step, a_rate, b, c, initial=None):
    """``H_t = a_t H_{t-1} + D_t x_t B_t^T``, ``y_t = H_t C_t``, a token
    at a time."""
    batch, _, heads, head_dim = x.shape

    def token(state, at):
        x_t, step_t, b_t, c_t = at
        state = (state * jnp.exp(step_t * a_rate)[:, :, None, None]
                 + (step_t[..., None] * x_t)[..., None]
                 * b_t[:, None, None, :])
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t)

    if initial is None:
        initial = jnp.zeros((batch, heads, head_dim, b.shape[-1]))
    state, y = jax.lax.scan(token, initial, tuple(
        jnp.swapaxes(v, 0, 1) for v in (x, step, b, c)))
    return jnp.swapaxes(y, 0, 1), state


def _sequence(seq, seed=0, batch=2, heads=4, head_dim=8, d_state=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(keys[0], (batch, seq, heads, head_dim))
    step = jax.nn.softplus(jax.random.normal(keys[1], (batch, seq, heads)) - 2)
    a_rate = -jnp.exp(jnp.linspace(0.0, 2.7, heads))
    b = jax.random.normal(keys[2], (batch, seq, d_state))
    c = jax.random.normal(keys[3], (batch, seq, d_state))
    initial = jax.random.normal(keys[4], (batch, heads, head_dim, d_state))
    return x, step, a_rate, b, c, initial


# Inside the first chunk, on its edge, one past it, several
# chunks and a ragged tail.
@pytest.mark.parametrize("seq", [1, 8, 9, 37])
@pytest.mark.parametrize("start", ["zero", "given"])
def test_chunked_scan_is_the_recurrence(seq, start):
    x, step, a_rate, b, c, initial = _sequence(seq, seed=seq)
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
