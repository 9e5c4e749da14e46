"""Mamba-2's SSD recurrence (`ops/ssd.py`): the chunked prompt pass and the
one-token step are ONE recurrence, in both executions (the XLA chunks and
the two Pallas kernels, here in interpret mode), and the router that scores
by a softmax over the chosen logits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import moe, ssd
from ray_tpu.ops.pallas import ssd_scan as scan_kernel
from ray_tpu.ops.pallas import ssd_step as step_kernel

B, S, H, P, N = 2, 37, 4, 8, 16       # 37: no multiple of any chunk below


def _inputs(seed=0, s=S):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(ks[0], (B, s, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, s, H)) - 1.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.7))
    Bm, Cm = jax.random.normal(ks[3], (B, s, N)), jax.random.normal(ks[4], (B, s, N))
    D = 1.0 + 0.1 * jax.random.normal(ks[5], (H,))
    S0 = jax.random.normal(ks[6], (B, N, H * P))
    return x, dt, A, Bm, Cm, D, S0


def _xbc(x, Bm, Cm):
    return jnp.concatenate([x.reshape(x.shape[:2] + (-1,)), Bm, Cm], axis=-1)


def literal(x, dt, A, Bm, Cm, D, S0):
    """The recurrence written out, one position at a time, in the layout of
    the equations (S [b, H, P, N]); nothing of `ops/ssd.py`."""
    b, s = x.shape[:2]
    St = jnp.moveaxis(S0.reshape(b, N, H, P), 1, 3)                  # [b, H, P, N]
    ys = []
    for t in range(s):
        a = jnp.exp(dt[:, t] * A)                                     # [b, H]
        St = a[:, :, None, None] * St + (dt[:, t, :, None] * x[:, t])[..., None] \
            * Bm[:, t, None, None, :]
        ys.append(jnp.einsum("bhpn,bn->bhp", St, Cm[:, t]) + D[:, None] * x[:, t])
    return (jnp.stack(ys, 1).reshape(b, s, H * P),
            jnp.moveaxis(St, 3, 1).reshape(b, N, H * P))


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_scan_is_the_literal_recurrence(chunk):
    """Chunks of 8 and 16 (37 positions: the last chunk is padded) and the
    whole length as one chunk, from a carried-in state."""
    x, dt, A, Bm, Cm, D, S0 = _inputs()
    want_y, want_S = literal(x, dt, A, Bm, Cm, D, S0)
    y, St = ssd.ssd_scan(_xbc(x, Bm, Cm), dt, A, D, N, S0, None, chunk)
    np.testing.assert_allclose(y, want_y, atol=5e-5)
    np.testing.assert_allclose(St, want_S, atol=1e-5)


@pytest.mark.parametrize("chunk", [8, 16, 40])
def test_the_scan_kernel_is_the_literal_recurrence(chunk):
    """The Pallas kernel (interpret mode) over whole chunks: pairs of heads
    share a block, dt weighs the decay matrix's columns, D x is added inside."""
    x, dt, A, Bm, Cm, D, S0 = _inputs(1)
    want_y, want_S = literal(x, dt, A, Bm, Cm, D, S0)
    pad = -S % chunk
    xbc, dtp = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (_xbc(x, Bm, Cm), dt))
    cum = jnp.cumsum((dtp * A).reshape(B, -1, chunk, H), axis=2).reshape(B, -1, H)
    y, St = scan_kernel.ssd_scan_pallas(
        xbc, jnp.moveaxis(dtp, 1, 2), jnp.moveaxis(cum, 1, 2),
        jnp.repeat(D, P)[None], S0, chunk, P)
    np.testing.assert_allclose(y[:, :S], want_y, atol=5e-5)
    np.testing.assert_allclose(St, want_S, atol=1e-5)


@pytest.mark.parametrize("chunk,split", [(8, 20), (16, 29), (64, 5)])
def test_scan_then_steps_equals_steps(chunk, split):
    """A prompt through `ssd_scan`, continued by `ssd_step`, is the same
    tokens run by steps alone, whatever the chunk and wherever the prompt
    ends."""
    x, dt, A, Bm, Cm, D, _ = _inputs(2)
    S0 = jnp.zeros((B, N, H * P))
    St, ys = S0, []
    for t in range(S):
        St, y = ssd.ssd_step(St, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D)
        ys.append(y)
    y_scan, S_mid = ssd.ssd_scan(_xbc(x, Bm, Cm)[:, :split], dt[:, :split], A, D, N,
                                 None, None, chunk)
    np.testing.assert_allclose(y_scan, jnp.stack(ys[:split], 1), atol=5e-5)
    for t in range(split, S):
        S_mid, y = ssd.ssd_step(S_mid, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D)
        np.testing.assert_allclose(y, ys[t], atol=5e-5)
    np.testing.assert_allclose(S_mid, St, atol=1e-5)


def test_positions_past_the_true_length_leave_the_state_alone():
    """A right-padded bucket: whatever stands behind a row's true length, the
    state is the one after its last true position."""
    x, dt, A, Bm, Cm, D, S0 = _inputs(3)
    true_len = jnp.asarray([11, 30])
    valid = jnp.arange(S)[None, :] < true_len[:, None]
    _, St = ssd.ssd_scan(_xbc(x, Bm, Cm), dt, A, D, N, S0, valid, 16)
    for b, n in enumerate([11, 30]):
        _, want = literal(x[b:b + 1, :n], dt[b:b + 1, :n], A, Bm[b:b + 1, :n],
                          Cm[b:b + 1, :n], D, S0[b:b + 1])
        np.testing.assert_allclose(St[b], want[0], atol=1e-5)


def test_the_step_kernel_walks_the_busy_slots_only():
    """One layer of a stacked slot cache, in place: busy slots advance as
    `ssd_step` advances them; idle slots' state and every other layer are
    untouched, bit for bit; an idle slot's y is 0."""
    x, dt, A, Bm, Cm, D, _ = _inputs(4)
    slots_n, layers = 6, 3
    state = jax.random.normal(jax.random.PRNGKey(9), (layers, slots_n, N, H * P))
    lengths = jnp.asarray([0, 5, 0, 3, 9, 0])
    busy = np.asarray(lengths > 0)
    xs, dts, Bs, Cs = x[0, :slots_n], dt[0, :slots_n], Bm[0, :slots_n], Cm[0, :slots_n]
    want_S, want_y = ssd.ssd_step(state[1], xs, dts, A, Bs, Cs, D)
    got, y = ssd.ssd_step_slots(state, jnp.asarray(1), step_kernel.live_slots(lengths),
                                lengths > 0, xs, dts, A, Bs, Cs, D)
    np.testing.assert_allclose(got[1][busy], want_S[busy], atol=1e-6)
    np.testing.assert_allclose(y[busy], want_y[busy], atol=1e-5)
    assert np.array_equal(got[1][~busy], state[1][~busy])
    assert np.array_equal(got[0], state[0]) and np.array_equal(got[2], state[2])
    assert not np.any(np.asarray(y)[~busy])


def test_the_xla_step_over_slots_is_the_step():
    x, dt, A, Bm, Cm, D, _ = _inputs(5)
    state = jax.random.normal(jax.random.PRNGKey(3), (2, B, N, H * P))
    want_S, want_y = ssd.ssd_step(state[1], x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D)
    got, y = ssd.ssd_step_slots(state, jnp.asarray(1), None, jnp.ones((B,), bool),
                                x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D)
    np.testing.assert_allclose(got[1], want_S, atol=1e-6)
    np.testing.assert_allclose(y, want_y, atol=1e-6)
    assert np.array_equal(got[0], state[0])


def test_softmax_routing_weighs_the_chosen_logits_only():
    """The k largest logits, a softmax over THOSE k (not a softmax over all,
    gathered), in float32 whatever the activations' type."""
    key = jax.random.PRNGKey(0)
    h = jax.random.normal(key, (64, 32)).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1), (32, 12)).astype(jnp.bfloat16)
    idx, weights = moe.route_softmax_top_k(h, w, 3)
    logits = np.asarray(h, np.float32) @ np.asarray(w, np.float32)
    order = np.argsort(-logits, axis=-1)[:, :3]
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(order, -1))
    chosen = np.take_along_axis(logits, np.asarray(idx), -1)
    want = np.exp(chosen - chosen.max(-1, keepdims=True))
    want /= want.sum(-1, keepdims=True)
    assert weights.dtype == jnp.float32
    np.testing.assert_allclose(weights, want, rtol=1e-5)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    over_all = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    assert np.abs(np.take_along_axis(over_all, np.asarray(idx), -1) - want).max() > 0.05
