"""`ops/mamba.py`: the chunked selective scan (both executions: XLA's
associative scan per chunk, and the Pallas kernel in interpret mode) against
the token-by-token recurrence, all float32 on the CPU.

Tolerance: the three differ only in the order of float32 products and sums
(a chunk's running decay is a product of up to `chunk` factors where the
recurrence multiplies one at a time): 2e-6 absolute was read on values of
order 1; 2e-5 leaves room."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda, mamba
from ray_tpu.ops.pallas import selective_scan as kernel

TOL = 2e-5


def _inputs(b, s, di, n=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        u=jax.random.normal(ks[0], (b, s, di)),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (b, s, di)) - 2.0),
        A=-jnp.exp(jax.random.uniform(ks[2], (n, di), minval=0.0, maxval=2.77)),
        B=jax.random.normal(ks[3], (b, s, n)), C=jax.random.normal(ks[4], (b, s, n)),
        D=1.0 + 0.1 * jax.random.normal(ks[5], (di,)),
        h0=jax.random.normal(ks[6], (b, n, di)))


def _recurrence(u, dt, A, B, C, D, h0):
    """`selective_step` applied one position at a time."""
    def step(h, t):
        return mamba.selective_step(h, t[0], t[1], A, t[2], t[3], D)
    h, y = jax.lax.scan(step, h0, tuple(jnp.moveaxis(a, 1, 0) for a in (u, dt, B, C)))
    return jnp.moveaxis(y, 0, 1), h


def _close(a, b):
    return float(jnp.max(jnp.abs(a - b))) < TOL


@pytest.mark.parametrize("s,chunk", [(45, 16), (64, 16), (7, 128), (130, 64), (16, 16)])
def test_chunked_scan_is_the_sequential_recurrence(s, chunk):
    x = _inputs(2, s, 128)
    want_y, want_h = _recurrence(**x)
    y, h = mamba.selective_scan(x["u"], x["dt"], x["A"], x["B"], x["C"], x["D"],
                                x["h0"], None, chunk)
    assert y.shape == (2, s, 128) and h.shape == (2, 16, 128)
    assert _close(y, want_y) and _close(h, want_h)


@pytest.mark.parametrize("s,T,di", [(48, 16, 128), (64, 64, 256), (24, 8, 384)])
def test_the_pallas_kernel_is_the_sequential_recurrence(s, T, di):
    """The kernel itself (interpret mode here; the chip's compiler takes it
    at the published widths in `tests/test_chip_compile.py`)."""
    x = _inputs(2, s, di, seed=3)
    want_y, want_h = _recurrence(**x)
    y, h = kernel.selective_scan_pallas(x["u"], x["dt"], x["A"], x["B"], x["C"],
                                        x["h0"], T)
    assert _close(y + x["D"] * x["u"], want_y) and _close(h, want_h)


def test_padding_leaves_the_state_untouched():
    """Positions past `true_len` neither decay nor write: the state after a
    right-padded row is the state after its true positions."""
    x = _inputs(3, 40, 128, seed=1)
    true_len = jnp.asarray([40, 17, 1])
    valid = jnp.arange(40)[None, :] < true_len[:, None]
    y, h = mamba.selective_scan(x["u"], x["dt"], x["A"], x["B"], x["C"], x["D"],
                                x["h0"], valid, 16)
    for j, n in enumerate([40, 17, 1]):
        cut = {k: (v[j:j + 1, :n] if v.ndim == 3 and k != "h0" else v)
               for k, v in x.items()}
        cut["h0"] = x["h0"][j:j + 1]
        want_y, want_h = _recurrence(**cut)
        assert _close(h[j:j + 1], want_h) and _close(y[j:j + 1, :n], want_y)


def test_a_step_continues_a_scan_exactly():
    """Scan over the first 29 positions, then `selective_step` over the
    rest from the state it left = the scan over all of them."""
    x = _inputs(2, 37, 128, seed=2)
    whole_y, whole_h = mamba.selective_scan(x["u"], x["dt"], x["A"], x["B"], x["C"],
                                            x["D"], None, None, 16)
    head = {k: (v[:, :29] if v.ndim == 3 and k != "h0" else v) for k, v in x.items()}
    _, h = mamba.selective_scan(head["u"], head["dt"], x["A"], head["B"], head["C"],
                                x["D"], None, None, 16)
    for t in range(29, 37):
        h, y = mamba.selective_step(h, x["u"][:, t], x["dt"][:, t], x["A"],
                                    x["B"][:, t], x["C"][:, t], x["D"])
        assert _close(y, whole_y[:, t])
    assert _close(h, whole_h)


def test_the_convolution_tail_is_untouched_by_padding_and_feeds_the_step():
    """Mamba's convolution is `ops/kda.py`'s: the tail of a right-padded row
    is its last K-1 TRUE inputs, and the step form continues from it."""
    K, ch = 4, 24
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 20, ch))
    w = jax.random.normal(jax.random.PRNGKey(6), (K, ch))
    tail = kda.conv_tail(x, jnp.asarray([20, 11]), K)
    np.testing.assert_array_equal(np.asarray(tail[0]), np.asarray(x[0, 17:20]))
    np.testing.assert_array_equal(np.asarray(tail[1]), np.asarray(x[1, 8:11]))
    whole = kda.short_conv(x, w)
    y, _ = kda.short_conv_step(x[1:2, 11], tail[1:2], w)
    assert _close(y[0], whole[1, 11])


def test_uses_scan_kernel_follows_platform_and_shape(monkeypatch):
    from ray_tpu.ops.pallas import _util
    u = jnp.zeros((1, 8, 5120))
    assert not mamba.uses_scan_kernel(u)               # the CPU
    monkeypatch.setattr(_util, "on_tpu", lambda: True)
    assert mamba.uses_scan_kernel(u) and kernel.block_channels(5120) == 1024
    assert not mamba.uses_scan_kernel(jnp.zeros((1, 8, 100)))


@pytest.mark.parametrize("lengths", [[5, 0, 9, 0, 0, 3], [0, 0, 0, 0, 0, 0],
                                     [1, 2, 3, 4, 5, 6]])
def test_the_step_kernel_advances_the_busy_slots_only(lengths):
    """`selective_step_pallas` (interpret mode) over layer 1 of a stacked
    state of three: a busy slot's state and y are `selective_step`'s; an idle
    slot's state is untouched, and so is every other layer. (With nothing
    busy the first slot of the walk is advanced all the same: its block is
    the one the pipeline writes back.)"""
    from ray_tpu.ops.pallas import selective_step as step_kernel

    S, n, di = 6, 16, 256
    x = _inputs(S, 1, di, seed=4)
    state = jax.random.normal(jax.random.PRNGKey(8), (3, S, n, di))
    lengths = jnp.asarray(lengths, jnp.int32)
    u, dt, B, C = x["u"][:, 0], x["dt"][:, 0], x["B"][:, 0], x["C"][:, 0]
    want_h, want_y = mamba.selective_step(state[1], u, dt, x["A"], B, C,
                                          jnp.zeros((di,)))
    got, y = step_kernel.selective_step_pallas(
        state, jnp.asarray(1), step_kernel.live_slots(lengths), u, dt, x["A"], B, C)
    busy = np.asarray(lengths) > 0
    worked = busy.copy()
    if not busy.any():
        worked[0] = True
    assert _close(got[0], state[0]) and _close(got[2], state[2])
    assert _close(got[1][worked], want_h[worked])
    np.testing.assert_array_equal(np.asarray(got[1][~worked]),
                                  np.asarray(state[1][~worked]))
    assert not busy.any() or _close(y[busy], want_y[busy])
