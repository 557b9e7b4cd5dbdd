"""The state-space recurrence of ``horovod_tpu/ops/ssm.py``: the chunked
scan against the token-by-token recurrence written out in numpy, and the
one-token update's two bodies (the Pallas kernel, interpreted, and
``jax.numpy``) against each other and against one step of the same
recurrence, over a slot-state array whose rows hold more than the
states."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import pallas, ssm

HEADS, P, GROUPS, N = 4, 8, 2, 16

# float32 throughout; what differs is the order of summation (a chunk's
# products against a running state).  Measured: 2.1e-6 on y of deviation
# 4, 4.8e-7 on the state.
TOL = 2e-5


def _inputs(b, t, seed=0):
    rng = np.random.RandomState(seed)
    return dict(
        x=rng.randn(b, t, HEADS, P).astype(np.float32),
        dt=np.log1p(np.exp(rng.randn(b, t, HEADS))).astype(np.float32),
        A=-np.exp(rng.rand(HEADS)).astype(np.float32),
        B=rng.randn(b, t, GROUPS, N).astype(np.float32),
        C=rng.randn(b, t, GROUPS, N).astype(np.float32),
        D=rng.randn(HEADS).astype(np.float32))


def _token_by_token(x, dt, A, B, C, D, h0=None):
    """``H_t = exp(dt_t A) H_(t-1) + dt_t B_t (outer) x_t``, ``y_t = C_t
    H_t + D x_t``, ``H`` ``[b, heads, n, p]``, in float64."""
    b, t = x.shape[:2]
    h = np.zeros((b, HEADS, N, P)) if h0 is None else h0.astype(np.float64)
    ys = []
    for i in range(t):
        bh = np.repeat(B[:, i], HEADS // GROUPS, axis=1)
        ch = np.repeat(C[:, i], HEADS // GROUPS, axis=1)
        h = np.exp(dt[:, i] * A)[..., None, None] * h \
            + dt[:, i][..., None, None] * bh[..., None] * x[:, i][:, :, None]
        ys.append(np.einsum("bhnp,bhn->bhp", h, ch) + D[:, None] * x[:, i])
    return np.stack(ys, axis=1), h


# Whole chunks, a prompt that is no multiple of the chunk, one shorter
# than a chunk, one token, and a chunk of the whole prompt.
@pytest.mark.parametrize("t,chunk", [(12, 4), (11, 4), (3, 4), (1, 4),
                                     (9, 16), (10, 1)])
def test_the_chunked_scan_is_the_token_by_token_recurrence(t, chunk):
    z = _inputs(2, t, seed=t)
    want_y, want_h = _token_by_token(**z)
    y, h = ssm.ssm_scan(*(jnp.asarray(z[k]) for k in "x dt A B C D".split()),
                        chunk=chunk)
    assert y.shape == (2, t, HEADS, P) and h.shape == (2, HEADS, N, P)
    assert y.dtype == h.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=0, atol=TOL)
    np.testing.assert_allclose(np.asarray(h), want_h, rtol=0, atol=TOL)


def test_the_scan_goes_on_from_a_state_it_is_given():
    """Tokens 0-6, then 7-12 from the state the first seven left: what
    the thirteen give in one scan."""
    z = _inputs(1, 13, seed=3)
    args = [jnp.asarray(z[k]) for k in "x dt A B C D".split()]
    whole_y, whole_h = ssm.ssm_scan(*args, chunk=4)

    def part(lo, hi, h0):
        x, dt, A, B, C, D = args
        return ssm.ssm_scan(x[:, lo:hi], dt[:, lo:hi], A, B[:, lo:hi],
                            C[:, lo:hi], D, h0=h0, chunk=4)

    y0, h0 = part(0, 7, None)
    y1, h1 = part(7, 13, h0)
    np.testing.assert_allclose(np.concatenate([y0, y1], axis=1), whole_y,
                               rtol=0, atol=TOL)
    np.testing.assert_allclose(h1, whole_h, rtol=0, atol=TOL)


def _round(slots, seed=0):
    rng = np.random.RandomState(seed)
    return dict(
        x=rng.randn(slots, HEADS, P).astype(np.float32),
        dt=np.log1p(np.exp(rng.randn(slots, HEADS))).astype(np.float32),
        A=-np.exp(rng.rand(HEADS)).astype(np.float32),
        B=rng.randn(slots, GROUPS, N).astype(np.float32),
        C=rng.randn(slots, GROUPS, N).astype(np.float32),
        D=rng.randn(HEADS).astype(np.float32))


def _update(state, z, live, plane, how, monkeypatch):
    monkeypatch.setenv("HOROVOD_PALLAS", "1" if how == "kernel" else "0")
    fn = jax.jit(lambda st, pl_: ssm.ssm_decode_update(
        st, *(jnp.asarray(z[k]) for k in "x dt A B C D".split()),
        jnp.asarray(live), plane=pl_), donate_argnums=(0,))
    if how == "kernel":
        assert "hvd_ssm_decode" in str(jax.make_jaxpr(fn)(
            jnp.asarray(state), jnp.int32(plane)))
    given = jnp.asarray(state)
    new, y = fn(given, jnp.int32(plane))
    assert given.is_deleted()
    return np.asarray(new), np.asarray(y)


# 16 slots: two groups of eight, one of them with no live slot (its
# blocks are not visited); 3 slots: one block of three sublanes; no live
# slot at all; every slot live.
@pytest.mark.parametrize("slots,live", [
    (16, [1, 2, 6]), (16, [3, 9, 15]), (3, [0, 2]), (16, []),
    (8, list(range(8))), (24, [17])])
def test_the_update_s_two_bodies_agree_and_leave_idle_rows_alone(
        slots, live, monkeypatch):
    hw = HEADS * N * P
    rng = np.random.RandomState(len(live))
    # Three planes of rows that hold 24 values more than the states.
    state = rng.randn(3, slots, hw + 24).astype(np.float32)
    z = _round(slots, seed=slots)
    on = np.zeros((slots,), bool)
    on[live] = True
    got = {how: _update(state, z, on, 1, how, monkeypatch)
           for how in ("kernel", "jnp")}
    np.testing.assert_allclose(got["kernel"][0], got["jnp"][0], rtol=0,
                               atol=1e-6)
    np.testing.assert_allclose(got["kernel"][1], got["jnp"][1], rtol=0,
                               atol=1e-5)
    # One step of the recurrence, for the live slots.
    h0 = state[1][:, :hw].reshape(slots, HEADS, N, P)
    want_y, want_h = _token_by_token(
        z["x"][:, None], z["dt"][:, None], z["A"], z["B"][:, None],
        z["C"][:, None], z["D"], h0=h0)
    for new, y in got.values():
        np.testing.assert_allclose(new[1][on][:, :hw],
                                   want_h.reshape(slots, hw)[on], rtol=0,
                                   atol=TOL)
        np.testing.assert_allclose(y[on], want_y[:, 0][on], rtol=0,
                                   atol=TOL)
        # Idle rows, the other planes and what rides behind the states:
        # bit for bit what they were; an idle slot reads out zero.
        np.testing.assert_array_equal(new[1][~on], state[1][~on])
        np.testing.assert_array_equal(new[[0, 2]], state[[0, 2]])
        np.testing.assert_array_equal(new[1][:, hw:], state[1][:, hw:])
        assert not np.any(y[~on])


def test_the_update_refuses_a_state_that_is_not_float32():
    z = _round(4)
    with pytest.raises(ValueError, match="float32"):
        ssm.ssm_decode_update(
            jnp.zeros((1, 4, HEADS * N * P), jnp.bfloat16),
            *(jnp.asarray(z[k]) for k in "x dt A B C D".split()),
            jnp.ones((4,), bool))
    with pytest.raises(ValueError, match="rows of"):
        ssm.ssm_decode_update(
            jnp.zeros((1, 4, HEADS * N * P - 1), jnp.float32),
            *(jnp.asarray(z[k]) for k in "x dt A B C D".split()),
            jnp.ones((4,), bool))


def test_the_kernel_follows_the_package_s_switch(monkeypatch):
    assert "ssm_decode" in pallas.registered_kernels()
    monkeypatch.delenv("HOROVOD_PALLAS", raising=False)
    monkeypatch.delenv("HOROVOD_PALLAS_DECODE", raising=False)
    assert not pallas.pallas_enabled("ssm_decode")      # auto, off the TPU
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    assert pallas.pallas_enabled("ssm_decode")
    monkeypatch.setenv("HOROVOD_PALLAS_DECODE", "0")
    assert not pallas.pallas_enabled("ssm_decode")


def test_the_kernel_lowers_for_the_chip_in_place_over_live_groups(
        monkeypatch):
    """At the published widths (32 heads of 128, a state of 256, 2
    groups; 80 slots, six planes of 1,063,936-value rows): one Mosaic
    call a plane, the state aliased input to output, ten groups of eight
    slots by sixteen blocks of two heads."""
    from serving_families import lowered_for_tpu
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    monkeypatch.setattr(pallas, "interpret_mode", lambda: False)
    S, f32 = jax.ShapeDtypeStruct, jnp.float32
    text = lowered_for_tpu(
        lambda st, x, dt, A, B, C, D, live: ssm.ssm_decode_update(
            st, x, dt, A, B, C, D, live, plane=3),
        S((6, 80, 1063936), f32), S((80, 32, 128), f32), S((80, 32), f32),
        S((32,), f32), S((80, 2, 256), f32), S((80, 2, 256), f32),
        S((32,), f32), S((80,), jnp.bool_))
    assert text.count('kernel_name = "hvd_ssm_decode"') == 1
    assert "iteration_bounds = array<i64: 10, 16>" in text
    assert ("output_operand_alias<output_tuple_indices = [0], "
            "operand_index = 7") in text
