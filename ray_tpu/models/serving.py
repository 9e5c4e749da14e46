"""Continuous-batching LLM engine: slot-based KV cache, join/leave per step.

The serving-side scheduler the reference lacks natively (it serves torch
models behind Serve replicas): requests occupy fixed cache *slots* so the
decode step is one compiled function over static shapes — sequences join
(prefill writes their KV rows into a free slot) and retire (EOS/length)
between steps without recompiling, the continuous-batching idea of Orca /
vLLM re-built TPU-first (static shapes for XLA, per-row positions instead
of dynamic batch).

Engine = pure-JAX step functions + a host-side slot manager. The decode
loop is built to run at device speed:

  * `decode_step_fused` donates the K/V/length buffers (no per-step
    reallocation of [L, slots, kvh, max_len, hd]) and fuses greedy sampling
    on-device, so only a [slots] int32 token array ever crosses to the
    host. Donation aliases the buffers; it does not say what the step does
    in between. A row write through a ONE-row window made XLA:TPU relayout
    the whole cache before and after it (`copy.58/61/64/65`, two per buffer
    per step, 12.9 GB of HBM traffic a step at 24 x 32 x 8 x 1024 x 128
    bf16), so `ops.cache.write_rows` writes tile-aligned blocks of rows, which keep
    the default layout: on the TPU one Pallas call a cache, aliased to its
    output, that moves the busy slots' blocks and no idle slot's (up to
    PR 32 a loop of 64 dependent updates over every slot, 1.2 ms a step).
    `tests/test_chip_compile.py` asks the chip's compiler; buffer pointers
    on the CPU alias either way;
  * attention reads a power-of-2 *bucket* of the cache (compiled once per
    bucket) instead of all max_len rows, and on the TPU, within the bucket,
    only each slot's blocks of rows that hold tokens
    (`ops.pallas.decode_attention`; an idle slot has length 0 and costs
    nothing), so a step pays for the cache in use;
  * `step()` runs one step of *lookahead*: it dispatches step N+1 before
    syncing step N's tokens, so host bookkeeping (EOS/finish/admit, slot
    accounting) overlaps device compute — at the cost of one junk slot-step
    per retiring request (its slot computes garbage once before the host
    notices the EOS);
  * admission is batched: all same-bucket waiting requests prefill in ONE
    `prefill_slots` call and their prefix KV is scattered straight into the
    donated slot cache (`_write_slots`), first tokens sampled on device.

Device waits happen OUTSIDE the bookkeeping lock: `submit()`, `progress()`
and `result()` stay responsive while a step is in flight (`_step_lock`
serializes steppers; `_lock` only guards host-side state).

The engine records what it does in the program's own timeline
(`util/tracing.py`, category `engine`, read back by `ray_tpu.timeline()`):
per request `engine.queue` -> `engine.prefill` -> `engine.decode`,
contiguous, emitted when the request finishes, under the trace context its
`submit()` ran in (a Serve replica's request thread has one; standalone use
has none and the spans carry no ids), and, from the thread that consumes a
driven `generate_stream`, ONE `engine.stream` when it ends (tokens, wakes,
how long a reaped token waited for its thread: never a span per token);
per step `engine.step` with children `engine.prefill_dispatch` and
`engine.wait_device`, so a step's host time is its duration less its device
waits, and of that `lock_wait_us` went to entering `_lock` and
`bookkeep_us` to handing tokens out under it; between two steps of the
driver thread `engine.between_steps`, so the two tile that thread's life.

Serve wires it through `LLMDeployment` (serve replicas each host an
engine; the replica lifecycle hooks `__serve_start__`/`__serve_stop__`
start and stop a background driver thread so the engine steps itself and
callers just wait on their request).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import programs as programs_ahead
from ray_tpu.models.inference import _gqa_decode_attention
from ray_tpu.models.transformer import (ModelConfig, _deq_tree,
                                        _embed_lookup, _mlp, lm_head_weights)
from ray_tpu.ops.cache import write_rows as _write_rows
from ray_tpu.ops.layers import apply_rotary, rms_norm, rotary_embedding
from ray_tpu.ops.pallas import decode_attention
from ray_tpu.util import tracing

logger = logging.getLogger(__name__)

_QUANT_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")

# attention never reads fewer cache rows than this — keeps the number of
# compiled bucket variants small (64, 128, 256, ... max_len)
_ATTN_BUCKET_MIN = 64


def quantize_model_params(params: Dict, cfg: ModelConfig) -> Dict:
    """w8a16 load-time quantization (the serving-engine consumer of
    `ops.pallas.quant.quantize_int8`): every projection matrix, the
    embedding table, and the lm head become `{"int8", "scale"}` leaves with
    per-row absmax scales — ~2x less weight HBM and 2x less weight traffic
    per decode step (decode is HBM-bound). Norm vectors stay in bf16: they
    are 0.01% of the bytes and norm math is fp32 anyway. The model's
    forward paths dequantize on read inside the layer scan."""
    from ray_tpu.ops.pallas.quant import quantize_int8

    def q(w):
        values, scales = quantize_int8(w)
        return {"int8": values, "scale": scales}

    out = dict(params)
    out["layers"] = {
        k: (q(v) if k in _QUANT_LEAVES else v)
        for k, v in params["layers"].items()
    }
    out["embed"] = q(params["embed"])
    if "lm_head" in params:
        out["lm_head"] = q(params["lm_head"])
    return out


@functools.partial(jax.jit, static_argnames=("cfg", "max_len"))
def prefill_kv(params: Dict, tokens: jax.Array, true_len: jax.Array,
               cfg: ModelConfig, max_len: int):
    """Prompt pass for ONE right-padded request [1, s_bucket]: returns
    (logits at true_len-1 [vocab], k [L, kvh, max_len, hd], v likewise).

    Prompts are padded to bucket lengths before this call so XLA compiles
    once per bucket, not once per prompt length; the causal mask makes
    positions < true_len independent of the padding. The engine's admission
    path uses the batched `prefill_slots` instead; this stays as the
    single-request entry point."""
    from ray_tpu.models.inference import prefill

    logits, cache = prefill(params, tokens, cfg, max_len,
                            logits_index=true_len[None] - 1)
    return logits[0], cache["k"][:, 0], cache["v"][:, 0]


@functools.partial(jax.jit, static_argnames=("cfg", "max_len"))
def prefill_slots(params: Dict, tokens: jax.Array, true_len: jax.Array,
                  cfg: ModelConfig, max_len: int):
    """Batched prompt pass over one admission bucket [nb, s_bucket]: every
    same-bucket waiting request prefills in a single compiled call. Returns
    (first greedy tokens [nb] — sampled ON DEVICE, no logits cross to the
    host — and the prefix caches k/v [L, nb, kvh, max_len, hd])."""
    from ray_tpu.models.inference import prefill

    logits, cache = prefill(params, tokens, cfg, max_len,
                            logits_index=true_len - 1)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return first, cache["k"], cache["v"]


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _write_slots(k_all: jax.Array, v_all: jax.Array, lengths: jax.Array,
                 tokens: jax.Array, slots: jax.Array, k_rows: jax.Array,
                 v_rows: jax.Array, true_len: jax.Array, first: jax.Array):
    """Admission scatter: write a prefill bucket's KV rows straight into
    the DONATED slot cache (in-place update — the cache is never cloned to
    admit). `slots` entries equal to num_slots are batch padding and are
    dropped by the out-of-bounds scatter mode. `tokens` is deliberately NOT
    donated: the in-flight decode step still reads the previous buffer."""
    k_all = k_all.at[:, slots].set(k_rows, mode="drop")
    v_all = v_all.at[:, slots].set(v_rows, mode="drop")
    lengths = lengths.at[slots].set(true_len, mode="drop")
    tokens = tokens.at[slots].set(first, mode="drop")
    return k_all, v_all, lengths, tokens


def _zero_lengths(lengths: jax.Array, retired: jax.Array) -> jax.Array:
    """lengths [B] with the `retired` [B] (bool) slots' set to 0: idle."""
    return jnp.where(retired, 0, lengths)


def _bucket_len(n: int, max_len: int) -> int:
    b = 8
    while b < n:
        b *= 2
    return min(b, max_len - 1)


def _attn_bucket(pos: int, max_len: int) -> int:
    """Power-of-2 attention window >= the deepest active position (strict
    mask: position pos attends cache rows [0, pos))."""
    b = min(_ATTN_BUCKET_MIN, max_len)
    while b < pos:
        b *= 2
    return min(b, max_len)


def _one_row_qkv(cfg: ModelConfig, p, x, cos, sin):
    """The decode step's projections: x [B, 1, d] -> q [B, kvh, rep, hd] (the
    grouped layout both attention paths take), k, v [B, kvh, hd], q and k
    rotated. The mathematics is `transformer._project_qkv`'s; the spelling is
    the decode step's own because at ONE row a slot a product is bound by
    its weight's bytes, so the weight's layout decides. Left to fuse the
    rotation's float32 convert and head split into the product, XLA:TPU lays
    the product's output heads-major and then wants the layer's `wq` / `wk`
    transposed: sliced out of the stack, written down and copied, every
    layer of every step (`tests/test_chip_compile.py` names the ops). Behind
    the barrier the products stay flat bf16 [B, heads * hd] and read their
    weight in place, out of the stack, as `wv`, `wo` and the FFN's do. At
    the 64-4096 rows of training and prefill the same copy is noise and the
    fusions the barrier forbids are wanted: they keep `_project_qkv`."""
    B = x.shape[0]
    rep = cfg.n_heads // cfg.n_kv_heads
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)[:, 0]
    q, k, v = h @ p["wq"], h @ p["wk"], h @ p["wv"]
    q, k = jax.lax.optimization_barrier((q, k))
    # the tables [B, 1, hd/2] broadcast over (kvh, rep) as over (seq, heads)
    q = apply_rotary(q.reshape(B, cfg.n_kv_heads, rep, cfg.head_dim), cos, sin)
    k = apply_rotary(k.reshape(B, 1, cfg.n_kv_heads, cfg.head_dim), cos, sin)
    v = v.reshape(B, cfg.n_kv_heads, cfg.head_dim)
    return q, k[:, 0].astype(cfg.dtype), v.astype(cfg.dtype)


@functools.partial(jax.jit, static_argnames=("cfg", "attn_len"),
                   donate_argnums=(1, 2, 3))
def decode_step_fused(params: Dict, k_all: jax.Array, v_all: jax.Array,
                      lengths: jax.Array, tokens: jax.Array,
                      cfg: ModelConfig, attn_len: int):
    """The hot decode step: one token for every slot, greedy sampling fused
    on device, K/V/length buffers DONATED and updated in place (no
    [L, B, kvh, max_len, hd] reallocation, and no copy of one either:
    `_write_rows` says what that takes on the TPU, and moves only the
    blocks of the slots that hold something).

    The caches are READ-ONLY inside the layer scan — a scan that carries
    the cache through its ys gets double-buffered by XLA even when the
    final output aliases the input. Each layer reads its rows out of the
    whole cache, attention splits into (cache rows) + (current token's own
    K/V, which is not written yet — STRICT mask `< lengths`), and the
    per-layer K/V rows are written afterwards, outside the scan.

    `attn_len` is the static attention window (a power-of-2 bucket >= every
    active position): XLA compiles one executable per bucket. On the TPU it
    only bounds the kernel's list of items: each slot pays for the blocks
    of rows it holds (`ops.pallas.decode_attention`), an idle slot for
    none. On the CPU path every slot pays for the window.

    A slot with length 0 is IDLE: it computes its self term alone (finite
    garbage nobody reads), writes no row and stays at 0. The engine zeroes a
    retired slot's length (`ContinuousBatchingEngine._retire_slots`).

    Returns (k_all, v_all, lengths + 1 where a slot holds something,
    next_tokens [B] int32) — the caller keeps everything on device; only
    `next_tokens` is ever synced, one step late. `tokens` is NOT donated
    (the lookahead pipeline reads step N's token buffer after step N+1 is
    dispatched).
    """
    B = tokens.shape[0]
    hd = cfg.head_dim
    cos, sin = rotary_embedding(lengths[:, None], hd, cfg.rope_theta)
    x = _embed_lookup(params["embed"], tokens[:, None], cfg.dtype)  # [B,1,d]
    # one algorithm, two executions, chosen by what the code can see (as
    # `ops.attention.uses_flash_kernel` chooses): on a TPU at shapes that
    # tile, a kernel that reads each slot's live rows out of the whole
    # cache; elsewhere the einsums over the window of all slots
    kernel = decode_attention.uses_decode_kernel(k_all, attn_len)
    if kernel:
        items = decode_attention.live_items(lengths, attn_len)
    else:
        mask = jnp.arange(attn_len)[None, :] < lengths[:, None]  # [B, attn_len]
        win = (1, B, cfg.n_kv_heads, attn_len, hd)

    # named scopes are metadata only: they name the step's phases in a
    # device trace (`attention`, `mlp`, `cache_write`, `head`)
    def body(x, inputs):
        lp, layer = inputs
        lp = _deq_tree(lp, cfg.dtype)
        with jax.named_scope("attention"):
            q, k_cur, v_cur = _one_row_qkv(cfg, lp, x, cos, sin)
            if kernel:
                # the cache goes in WHOLE, the layer as a scalar: a sliced
                # window cannot fuse into a Mosaic call and would be copied
                attn = decode_attention.gqa_decode_attention(
                    q, k_cur, v_cur, k_all, v_all, layer, items, attn_len)
            else:
                # the layer's window, read straight out of the whole
                # (loop-invariant) cache: one dynamic_slice fuses into the
                # attention fusions; a scan over the caches followed by
                # `[:, :, :attn_len]` made XLA:TPU copy the layer's whole
                # [B, kvh, max_len, hd] first
                k_win = jax.lax.dynamic_slice(k_all, (layer, 0, 0, 0, 0), win)[0]
                v_win = jax.lax.dynamic_slice(v_all, (layer, 0, 0, 0, 0), win)[0]
                attn = _gqa_decode_attention(
                    q.reshape(B, cfg.n_heads, 1, hd), k_win, v_win, k_cur,
                    v_cur, mask)
            attn = attn.reshape(B, 1, cfg.n_heads * hd)
            x = x + (attn @ lp["wo"]).astype(x.dtype)
        with jax.named_scope("mlp"):
            x = x + _mlp(cfg, lp, x)[0].astype(x.dtype)
        return x, (k_cur, v_cur)

    x, (k_cur, v_cur) = jax.lax.scan(
        body, x, (params["layers"], jnp.arange(cfg.n_layers)))
    with jax.named_scope("cache_write"):
        k_all = _write_rows(k_all, k_cur, lengths)
        v_all = _write_rows(v_all, v_cur, lengths)
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = (x[:, 0] @ lm_head_weights(params, cfg)).astype(jnp.float32)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return k_all, v_all, lengths + (lengths > 0), nxt


class DenseKVCache:
    """The dense block's per-slot state: keys and values of every position,
    `k`, `v` [layers, slots, kv_heads, max_len, head_dim]. One of the two
    implementations of the engine's cache interface, and the engine's
    default; a configuration of another kind of model brings its own through
    `cfg.make_cache(num_slots, max_len)`. What the interface asks is in
    ARCHITECTURE.md, "The engine's cache interface":

        state                       the device arrays, a tree the model owns
        prefill(params, tokens, lens) -> (first tokens [nb], state rows)
        write(lengths, tokens, slots, rows, lens, first) -> (lengths, tokens)
        decode(params, lengths, tokens, attn_len, active) ->
            (lengths, next tokens [B], report [B x step_tokens + len(counters)])
        step_tokens                 the most tokens one step yields a slot
                                    (2 where the model drafts one and it
                                    holds). `report` opens with each slot's
                                    tokens of this step, slot-major, -1
                                    where it yielded fewer: how many a slot
                                    holds is its count, 1 for a cache that
                                    never drafts
        max_prefill_batch(bucket)   None = any
        prompt_bucket(n)            optional: the padded length a prompt of
                                    n tokens is passed at (a cache without
                                    one gets `_bucket_len`: a power of two)
        counters                    names of what `report` carries behind
                                    the tokens (summed into `engine.step`)
        step_args(positions, attn_len), prefill_args
                                    span arguments (of a step that
                                    dispatched a decode, from the busy
                                    slots' positions; of a prompt pass)
        programs                    the engine's list (`models/programs.py`):
                                    `prefill` and `decode` call their jitted
                                    program through it, under the key that
                                    tells it apart
        lowered(key, params, state, lengths, tokens)
                                    the programs of a key (an admission's
                                    two, a step's one), lowered from abstract
                                    arguments

    It calls the module's own jitted `prefill_slots`, `_write_slots` and
    `decode_step_fused`, so the dense model compiles to the programs it
    always compiled to."""

    counters: Tuple[str, ...] = ()
    step_tokens = 1
    prefill_args: Dict[str, int] = {}
    programs = programs_ahead.Direct

    def __init__(self, cfg: ModelConfig, num_slots: int, max_len: int):
        self.cfg, self.max_len = cfg, max_len
        shape = (cfg.n_layers, num_slots, cfg.n_kv_heads, max_len, cfg.head_dim)
        self.state = {"k": jnp.zeros(shape, cfg.dtype),
                      "v": jnp.zeros(shape, cfg.dtype)}

    def max_prefill_batch(self, bucket: int) -> Optional[int]:
        return None

    def prefill(self, params, tokens, lens):
        first, k_rows, v_rows = self.programs.run(
            ("admit",) + tokens.shape, prefill_slots, params, tokens, lens,
            self.cfg, self.max_len)
        return first, (k_rows, v_rows)

    def write(self, lengths, tokens, slots, rows, lens, first):
        s = self.state
        s["k"], s["v"], lengths, tokens = _write_slots(
            s["k"], s["v"], lengths, tokens, slots, rows[0], rows[1], lens, first)
        return lengths, tokens

    def decode(self, params, lengths, tokens, attn_len, active_slots):
        s = self.state
        s["k"], s["v"], lengths, nxt = self.programs.run(
            ("decode", attn_len), decode_step_fused,
            params, s["k"], s["v"], lengths, tokens, self.cfg, attn_len)
        return lengths, nxt, nxt

    def lowered(self, key, params, state, lengths, tokens):
        if key[0] == "decode":
            return [decode_step_fused.lower(params, state["k"], state["v"], lengths,
                                            tokens, self.cfg, key[1])]
        int32, (nb, bucket) = programs_ahead.int32, key[1:]
        prefill = prefill_slots.lower(params, int32(nb, bucket), int32(nb),
                                      self.cfg, self.max_len)
        first, k_rows, v_rows = programs_ahead.outputs(prefill, params)
        return [prefill, _write_slots.lower(
            state["k"], state["v"], lengths, tokens, int32(nb), k_rows, v_rows,
            int32(nb), first)]

    def step_args(self, positions: List[int], attn_len: int) -> Dict[str, int]:
        """The rows that hold a token, which the step has to read (of the
        span's `num_slots x attn_len` it read up to PR 28, and still reads
        on the CPU path); the slots whose block of rows
        `ops.cache.write_rows` moves are the span's `active`."""
        return {"live_rows": sum(positions)}


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


@dataclasses.dataclass
class _Request:
    request_id: int
    prompt: List[int]
    max_new_tokens: int
    generated: List[int] = dataclasses.field(default_factory=list)
    slot: int = -1
    done: bool = False
    # the request's stages (tracing.now_us() stamps) and the trace context
    # `submit()` ran in; they become its three spans in `_maybe_finish`
    trace_ctx: Optional[Tuple[str, str]] = None
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0
    t_reap: float = 0.0  # of the reap that appended its newest tokens
    waited_for_slot: bool = False  # an admission pass found no slot free
    bucket: int = 0                # of the prefill that admitted it,
    batch: int = 0                 # and how many requests rode that call


class ContinuousBatchingEngine:
    """Host-side slot manager over the jitted prefill/decode kernels.

    Locking: `_step_lock` serializes steppers (at most one step pipeline in
    flight); `_lock` guards only host bookkeeping and is NEVER held across
    a device wait — streaming `progress()` reads and `submit()` complete
    while a step is blocked on the device. `_cv` (on `_lock`) wakes waiters
    when tokens land and wakes the driver thread when work arrives.
    """

    def __init__(self, params: Dict, cfg, *, num_slots: int = 4,
                 max_len: int = 512, eos_token: Optional[int] = None,
                 quantize_weights: bool = False):
        if quantize_weights:
            params = quantize_model_params(params, cfg)
        self.params = params
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_len = max_len
        self.eos_token = eos_token
        # the model's per-slot state; the engine owns slots, lengths, tokens
        # a configuration that is not the dense model's brings its own
        self.cache = cfg.make_cache(num_slots, max_len) \
            if hasattr(cfg, "make_cache") else DenseKVCache(cfg, num_slots, max_len)
        # a cache may name its own prompt buckets (EVA: whole windows)
        self._prompt_bucket = getattr(
            self.cache, "prompt_bucket", lambda n: _bucket_len(n, max_len))
        self.lengths = jnp.zeros((num_slots,), jnp.int32)
        self.tokens = jnp.zeros((num_slots,), jnp.int32)
        self._free = list(range(num_slots))
        self._active: Dict[int, _Request] = {}   # slot -> request
        self._waiting: List[_Request] = []
        self._finished: Dict[int, _Request] = {}
        self._next_id = 0
        # host shadow of each slot's position: lets the dispatcher pick the
        # attention bucket without ever syncing `lengths` off the device
        self._slot_pos = [0] * num_slots
        # in-flight decode: (device tokens [B], {slot: request} captured at
        # dispatch time — attribution survives the slot being freed/reused)
        self._pending: Optional[Tuple[jax.Array, Dict[int, _Request]]] = None
        # admissions whose on-device first token hasn't been synced yet:
        # [(device first-tokens [nb_pad], [(row, request), ...])]
        self._pending_first: List[Tuple[jax.Array, List[Tuple[int, _Request]]]] = []
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._step_lock = threading.Lock()
        self._driver: Optional[threading.Thread] = None
        self._driver_stop = False
        self._driver_error: Optional[BaseException] = None
        self._attn_len = 0  # attention bucket of the last dispatched decode
        # of the step in progress (the stepper's own, under `_step_lock`):
        # seconds waited to enter `_lock`, and held it to hand out tokens
        self._lock_wait_s = 0.0
        self._bookkeep_s = 0.0
        self._step_args: Dict[str, int] = {}  # the cache's, of that decode
        tracing.record_compiles()
        # slots freed since the last step's end; their device lengths go to
        # 0 there (`_retire_slots`). Compiled HERE, ahead of time: a caller
        # that warms the step programs knows nothing of this one, and a
        # compiled executable cannot compile again under load whatever the
        # placement of the `lengths` it is handed
        self._retired: List[int] = []
        self._zero_lengths = jax.jit(_zero_lengths).lower(
            self.lengths, jax.ShapeDtypeStruct((num_slots,), jnp.bool_)).compile()
        # the programs this engine's last life called, loaded from here on
        # by threads of their own; this life's calls listed for the next
        path, header = programs_ahead.list_path(self.cache, cfg, num_slots, max_len)
        if path:
            cache, avals = self.cache, programs_ahead.abstract(
                (self.params, self.cache.state, self.lengths, self.tokens))
            cache.programs = programs_ahead.Programs(
                path, header, lambda key: cache.lowered(key, *avals))

    # the dense cache's two arrays by their old names (callers that warm or
    # inspect them: the benchmark's replica, tests)
    @property
    def k(self) -> jax.Array:
        return self.cache.state["k"]

    @k.setter
    def k(self, value: jax.Array) -> None:
        self.cache.state["k"] = value

    @property
    def v(self) -> jax.Array:
        return self.cache.state["v"]

    @v.setter
    def v(self, value: jax.Array) -> None:
        self.cache.state["v"] = value

    # ------------------------------------------------------------- requests
    def submit(self, prompt: List[int], *, max_new_tokens: int = 32) -> int:
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) >= self.max_len - 1:
            raise ValueError(
                f"prompt length {len(prompt)} must be < max_len-1 = "
                f"{self.max_len - 1}")
        with self._lock:
            req = _Request(self._next_id, list(prompt), max_new_tokens,
                           trace_ctx=tracing.current_ctx(),
                           t_submit=tracing.now_us())
            self._next_id += 1
            self._waiting.append(req)
            self._cv.notify_all()
            return req.request_id

    def _maybe_finish(self, req: _Request) -> None:
        hit_eos = self.eos_token is not None and req.generated and \
            req.generated[-1] == self.eos_token
        # a step writes up to `step_tokens` rows past the tokens a request
        # holds, and one more step is in flight when it finishes
        out_of_room = len(req.prompt) + len(req.generated) >= \
            self.max_len + 1 - 2 * self.cache.step_tokens
        if len(req.generated) >= req.max_new_tokens or hit_eos or out_of_room:
            req.done = True
            if req.slot >= 0:
                self._active.pop(req.slot, None)
                self._free.append(req.slot)
                self._retired.append(req.slot)
                self._slot_pos[req.slot] = 0
                req.slot = -1
            self._finished[req.request_id] = req
            self._record_request(req)

    def _record_request(self, req: _Request) -> None:
        """The finished request's three stages as spans of its trace."""
        tid, parent = req.trace_ctx or (None, None)
        for name, t0, t1, args in (
                ("engine.queue", req.t_submit, req.t_admit,
                 {"waited_for_slot": req.waited_for_slot}),
                ("engine.prefill", req.t_admit, req.t_first,
                 {"bucket": req.bucket, "batch": req.batch,
                  "prompt_len": len(req.prompt), **self.cache.prefill_args}),
                ("engine.decode", req.t_first, tracing.now_us(),
                 {"tokens": len(req.generated) - 1})):
            tracing.add_complete(name, "engine", t0, t1 - t0, trace_id=tid,
                                 parent_id=parent,
                                 request_id=req.request_id, **args)

    # ----------------------------------------------------------------- step
    @staticmethod
    def _to_host(arr: jax.Array) -> np.ndarray:
        """THE host sync point (device wait). Routed through one method so
        tests can instrument it; always called WITHOUT `_lock` held."""
        return np.asarray(arr)

    def step(self) -> int:
        """Admit waiting requests (batched, bucketed), dispatch the next
        decode step, then sync + bookkeep the PREVIOUS step's tokens while
        the new one runs on device. Returns sequences still active."""
        with self._step_lock:
            return self._step_inner()

    def _step_inner(self) -> int:
        # the span opens under `_step_lock`: lock wait is not counted
        with tracing.span("engine.step", "engine") as did:
            clock = time.perf_counter
            self._lock_wait_s = self._bookkeep_s = 0.0
            t_ask = clock()
            with self._lock:
                self._lock_wait_s += clock() - t_ask
                admissions = self._collect_admissions()
                did["waiting"] = len(self._waiting)  # left without a slot
            for bucket, reqs in admissions:
                with tracing.span("engine.prefill_dispatch", "engine",
                                  bucket=bucket, batch=len(reqs),
                                  tokens=sum(len(r.prompt) for r in reqs)):
                    self._dispatch_prefill(bucket, reqs)  # device enqueue only
            t_ask = clock()
            with self._lock:
                self._lock_wait_s += clock() - t_ask
                prev = self._pending
                self._pending = self._dispatch_decode()   # device enqueue only
            did.update(
                admitted=sum(len(reqs) for _, reqs in admissions),
                prefill_batches=len(admissions),
                active=len(self._pending[1]) if self._pending else 0,
                attn_len=self._attn_len if self._pending else 0,
                **(self._step_args if self._pending else {}))
            firsts = self._drain_pending_first()          # device wait, no _lock
            # the model's counters ride the token array, so they are those
            # of the step reaped here: the one dispatched a step earlier;
            # `tokens_out`: what the requests received inside this span
            reaped = self._reap(prev)                     # device wait, no _lock
            # the reaped step's device array goes HERE, inside the span: left
            # to the frame's end it is freed behind the span's back (0.4-0.5
            # ms a step on the chip, under neither of the driver's two spans)
            del prev
            did.update(reaped, tokens_out=firsts + reaped.get("tokens_out", 0))
            self._retire_slots()                          # device enqueue only
            t_ask = clock()
            with self._lock:
                self._lock_wait_s += clock() - t_ask
                left = len(self._active) + len(self._waiting)
            did.update(lock_wait_us=int(1e6 * self._lock_wait_s),
                       bookkeep_us=int(1e6 * self._bookkeep_s))
            return left

    def _collect_admissions(self):
        """Pop waiting requests into free slots, grouped by prompt bucket
        (one batched prefill per bucket). Caller holds `_lock`."""
        by_bucket: Dict[int, List[_Request]] = {}
        now = tracing.now_us()
        while self._waiting and self._free:
            req = self._waiting.pop(0)
            slot = self._free.pop()
            req.slot = slot
            req.t_admit = now
            self._active[slot] = req
            self._slot_pos[slot] = len(req.prompt)
            bucket = self._prompt_bucket(len(req.prompt))
            by_bucket.setdefault(bucket, []).append(req)
        for req in self._waiting:
            req.waited_for_slot = True
        for bucket, reqs in by_bucket.items():
            for req in reqs:
                req.bucket, req.batch = bucket, len(reqs)
        return sorted(by_bucket.items())

    def _dispatch_prefill(self, bucket: int, reqs: List[_Request]) -> None:
        """ONE `prefill_slots` call for every same-bucket admission; the
        prefix KV goes straight into the donated slot cache. The batch is
        padded to a power of 2 (padding rows scatter to an out-of-range
        slot and are dropped) so XLA compiles per (nb, bucket), not per
        admission count. First tokens stay on device until bookkeeping."""
        most = self.cache.max_prefill_batch(bucket)
        if most is not None and len(reqs) > most:  # the model bounds a call
            for i in range(0, len(reqs), most):
                self._dispatch_prefill(bucket, reqs[i:i + most])
            return
        nb = _pow2(len(reqs))
        # filled as ONE numpy array: `jnp.asarray` of nested Python lists
        # converts element by element (12-45 ms for a 4096-token prompt,
        # with the device idle behind it)
        rows = np.zeros((nb, bucket), np.int32)
        lens = np.ones((nb,), np.int32)
        slots = np.full((nb,), self.num_slots, np.int32)  # out of range -> dropped
        for i, r in enumerate(reqs):
            rows[i, :len(r.prompt)] = r.prompt
            lens[i], slots[i] = len(r.prompt), r.slot
        lens = jnp.asarray(lens)
        first, state_rows = self.cache.prefill(self.params, jnp.asarray(rows), lens)
        self.lengths, self.tokens = self.cache.write(
            self.lengths, self.tokens, jnp.asarray(slots), state_rows, lens, first)
        self._pending_first.append(
            (first, [(i, r) for i, r in enumerate(reqs)]))

    def _dispatch_decode(self):
        """Dispatch one fused decode step (no device wait). Captures the
        dispatch-time active set so tokens are attributed correctly even if
        a slot retires and is re-admitted before the sync. Caller holds
        `_lock`."""
        if not self._active:
            return None
        attn_len = _attn_bucket(
            max(self._slot_pos[s] for s in self._active), self.max_len)
        slot_map = dict(self._active)
        self._attn_len = attn_len
        self._step_args = self.cache.step_args(
            [self._slot_pos[s] for s in slot_map], attn_len)
        self.lengths, self.tokens, report = self.cache.decode(
            self.params, self.lengths, self.tokens, attn_len, slot_map)
        for s in slot_map:  # an upper bound until the step's count is reaped
            self._slot_pos[s] += self.cache.step_tokens
        return report, slot_map

    def _retire_slots(self) -> None:
        """Set the device length of every slot freed in this step to 0, so
        that the decode step stops reading its rows (an idle slot has
        length 0; the step dispatched above still read them: harmless).
        Runs in the stepper, after the step's own dispatches and before the
        next step's admissions, so it never lands after the `write` that
        gives a freed slot its next prompt."""
        t_ask = time.perf_counter()
        with self._lock:
            self._lock_wait_s += time.perf_counter() - t_ask
            freed, self._retired = self._retired, []
        if freed:
            retired = np.zeros((self.num_slots,), bool)
            retired[freed] = True
            self.lengths = self._zero_lengths(self.lengths, retired)

    def _drain_pending_first(self) -> int:
        """Sync admissions' on-device first tokens (deferred from dispatch
        so prefill overlaps the decode step queued behind it). Returns how
        many requests got theirs."""
        if not self._pending_first:
            return 0
        batches, self._pending_first = self._pending_first, []
        for first_dev, entries in batches:
            with tracing.span("engine.wait_device", "engine", what="first"):
                first = self._to_host(first_dev)  # device wait — no _lock held
            now = tracing.now_us()
            t_ask = time.perf_counter()
            with self._lock:
                t_in = time.perf_counter()
                for row, req in entries:
                    req.t_first = req.t_reap = now
                    req.generated.append(int(first[row]))
                    self._maybe_finish(req)
                self._cv.notify_all()
                self._lock_wait_s += t_in - t_ask
                self._bookkeep_s += time.perf_counter() - t_in
        return sum(len(entries) for _, entries in batches)

    def _reap(self, prev) -> Dict[str, int]:
        """Sync + bookkeep a previously dispatched step's tokens: as many
        for each slot as the step yielded it (`cache.step_tokens` at most;
        the ones past a request's end are dropped). Runs while the NEXT step
        computes on device (one-step lookahead). Returns the model's
        counters of that step (`cache.counters`: they sit behind the tokens
        in the one array that is synced) and `tokens_out`, the tokens the
        requests received."""
        if prev is None:
            return {}
        report_dev, slot_map = prev
        with tracing.span("engine.wait_device", "engine", what="decode"):
            nxt = self._to_host(report_dev)  # device wait — no _lock held
        most = self.cache.step_tokens
        yielded = nxt[:self.num_slots * most].reshape(self.num_slots, most)
        out = 0
        now = tracing.now_us()  # ONE stamp a reap: what a stream's lag is from
        t_ask = time.perf_counter()
        with self._lock:
            t_in = time.perf_counter()
            for slot, req in slot_map.items():
                kept = [int(t) for t in yielded[slot] if t >= 0]
                if self._active.get(slot) is req:  # the bound, made exact
                    self._slot_pos[slot] -= most - len(kept)
                for t in kept:
                    if req.done:
                        break  # finished at dispatch+1, or on the token before
                    req.t_reap = now
                    req.generated.append(t)
                    out += 1
                    self._maybe_finish(req)
            self._cv.notify_all()
            self._lock_wait_s += t_in - t_ask
            self._bookkeep_s += time.perf_counter() - t_in
        return {"tokens_out": out,
                **{name: int(nxt[self.num_slots * most + i])
                   for i, name in enumerate(self.cache.counters)}}

    def run_until_done(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if self.step() == 0 and not self._waiting:
                return

    # ------------------------------------------------------- driver thread
    def start_driver(self) -> None:
        """Background thread that steps the engine whenever there is work:
        callers then just `submit()` and `wait()`/stream. Used by serve
        replicas via the `__serve_start__` lifecycle hook."""
        with self._lock:
            if self._driver is not None:
                return
            self._driver_stop = False
            self._driver_error = None
            self._driver = threading.Thread(
                target=self._drive, name="engine-driver", daemon=True)
            self._driver.start()

    def stop_driver(self, timeout: float = 5.0) -> None:
        self.cache.programs.close()   # the loading threads, if any are left
        with self._lock:
            t = self._driver
            if t is None:
                return
            self._driver_stop = True
            self._cv.notify_all()
        t.join(timeout)
        with self._lock:
            self._driver = None

    def _has_work(self) -> bool:
        return bool(self._waiting or self._active or self._pending
                    or self._pending_first)

    def _drive(self) -> None:
        """`engine.step` and `engine.between_steps` tile this thread's life:
        the second is what passes from one `step()` returning to the next
        being called (the wait for `_lock` behind the streaming threads the
        reap woke, and the sleep when there is no work: `slept_us`, no
        cost)."""
        while True:
            with tracing.span("engine.between_steps", "engine") as gap:
                slept = 0.0
                with self._lock:
                    gap["had_work"] = self._has_work()
                    while not self._driver_stop and not self._has_work():
                        t_sleep = time.perf_counter()
                        self._cv.wait(0.1)
                        slept += time.perf_counter() - t_sleep
                    stop = self._driver_stop
                gap["slept_us"] = int(1e6 * slept)
            if stop:
                return
            try:
                self.step()
            except Exception as e:  # surface to waiters instead of hanging
                logger.exception("engine driver thread died")
                with self._lock:
                    self._driver_error = e
                    self._driver = None
                    self._cv.notify_all()
                return

    # -------------------------------------------------------------- results
    def _result_locked(self, req: _Request) -> List[int]:
        toks = req.prompt + req.generated
        if self.eos_token is not None and toks and toks[-1] == self.eos_token:
            toks = toks[:-1]
        return toks

    def result(self, request_id: int) -> Optional[List[int]]:
        with self._lock:
            req = self._finished.get(request_id)
            if req is None:
                return None
            return self._result_locked(req)

    def wait(self, request_id: int,
             timeout: Optional[float] = None) -> List[int]:
        """Block until `request_id` finishes (driver mode). Raises if the
        driver died or the timeout expires."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._lock:
            while request_id not in self._finished:
                if self._driver_error is not None:
                    raise self._driver_error
                if self._driver is None and not self._has_work():
                    raise RuntimeError(
                        "engine has no driver and no work in flight; "
                        "call step() or start_driver()")
                remaining = 0.1 if deadline is None else \
                    min(0.1, deadline - time.monotonic())
                if remaining <= 0:
                    raise TimeoutError(
                        f"request {request_id} not done within {timeout}s")
                self._cv.wait(remaining)
            return self._result_locked(self._finished[request_id])

    def _progress_locked(self, request_id: int):
        """(tokens so far, done, the stamp of the reap that appended the
        newest of them)."""
        req = self._finished.get(request_id)
        if req is not None:
            toks = list(req.generated)
            if (self.eos_token is not None and toks
                    and toks[-1] == self.eos_token):
                toks.pop()
            return toks, True, req.t_reap
        for req in list(self._active.values()) + self._waiting:
            if req.request_id == request_id:
                return list(req.generated), req.done, req.t_reap
        return [], True, 0.0  # unknown id

    def progress(self, request_id: int):
        """(tokens generated so far, done) — readable while decoding, for
        token streaming. Mirrors result(): a trailing EOS is stripped, so
        streamed output always equals the non-streamed suffix. Takes only
        the bookkeeping lock: never blocks behind a device wait."""
        with self._lock:
            return self._progress_locked(request_id)[:2]

    def generate(self, prompt: List[int], *, max_new_tokens: int = 32,
                 timeout: Optional[float] = None) -> List[int]:
        rid = self.submit(prompt, max_new_tokens=max_new_tokens)
        if self._driver is not None:
            return self.wait(rid, timeout=timeout)
        while self.result(rid) is None:
            if self.step() == 0 and self.result(rid) is None and \
                    not self._waiting:
                break
        return self.result(rid) or []

    def generate_stream(self, prompt: List[int], *,
                        max_new_tokens: int = 32):
        """Generator yielding tokens AS DECODED (continuous batching keeps
        serving other slots between yields) — the engine half of
        Serve token streaming (reference vLLM-style streaming generate)."""
        t_submit = tracing.now_us()
        rid = self.submit(prompt, max_new_tokens=max_new_tokens)
        if self._driver is not None:
            yield from self._stream_from_driver(rid, t_submit)
            return
        emitted = 0
        while True:
            active = self.step()
            toks, done = self.progress(rid)
            while emitted < len(toks):
                yield int(toks[emitted])
                emitted += 1
            if done:
                return
            if active == 0:
                return  # nothing left anywhere; request never finished

    def _stream_from_driver(self, rid: int, t_submit: float):
        """The consumer's side of a stream. One `engine.stream` span when the
        generator ends (exhausted, closed or raised), from this thread and
        under its trace context, `submit()` to the last `yield` returning;
        what a token cost on its way out is integers on this frame: `wakes`
        (returns from `_cv.wait` + the first pass), `deliver_lag_us_sum` (a
        batch of tokens in hand, less the stamp of the reap that appended
        it) and `lock_us_sum` (asking for `_lock` to releasing it; the
        re-acquire inside `_cv.wait` is in the lag, not here)."""
        clock = time.perf_counter
        ctx = tracing.current_ctx() or (None, None)
        emitted, wakes = 0, 1
        lag_sum = lock_s = 0.0
        try:
            while True:
                t_ask = clock()
                with self._lock:
                    while True:
                        toks, done, t_reap = self._progress_locked(rid)
                        if len(toks) > emitted or done:
                            break
                        if self._driver_error is not None:
                            raise self._driver_error
                        lock_s += clock() - t_ask
                        self._cv.wait(0.2)
                        t_ask = clock()
                        wakes += 1
                lock_s += clock() - t_ask
                if len(toks) > emitted:
                    lag_sum += tracing.now_us() - t_reap
                while emitted < len(toks):  # yield OUTSIDE the lock
                    emitted += 1  # first: a close at the yield counts it
                    yield int(toks[emitted - 1])
                if done:
                    return
        finally:
            tracing.add_complete(
                "engine.stream", "engine", t_submit, tracing.now_us() - t_submit,
                trace_id=ctx[0], parent_id=ctx[1], request_id=rid,
                tokens=emitted, wakes=wakes, deliver_lag_us_sum=int(lag_sum),
                lock_us_sum=int(1e6 * lock_s))


def LLMDeployment(params, cfg, *, num_slots: int = 4,
                  max_len: int = 512, eos_token: Optional[int] = None,
                  quantize_weights: bool = False):
    """A serve-ready callable class hosting one engine per replica.

    Usage:
        from ray_tpu import serve
        D = serve.deployment(LLMDeployment(params, cfg))
        handle = serve.run(D.bind())
        handle.remote({"prompt": [1, 2, 3], "max_new_tokens": 8})

    Inside a replica the `__serve_start__` lifecycle hook starts the
    engine's background driver thread, so concurrent requests all ride one
    continuously-batched decode loop (each caller blocks only on its own
    request); standalone (no hook) the engine self-steps in the caller.
    """

    class _LLM:
        def __init__(self):
            self.engine = ContinuousBatchingEngine(
                params, cfg, num_slots=num_slots, max_len=max_len,
                eos_token=eos_token, quantize_weights=quantize_weights)

        def __serve_start__(self):
            self.engine.start_driver()

        def __serve_stop__(self):
            self.engine.stop_driver()

        def __call__(self, payload):
            prompt = list(payload["prompt"])
            n = int(payload.get("max_new_tokens", 32))
            return self.engine.generate(prompt, max_new_tokens=n)

        def stream(self, payload):
            """Streaming entry: call through a stream handle
            (`handle.options(method_name='stream', stream=True)`) or HTTP
            `POST /<name>/stream?stream=1` — tokens arrive as generated."""
            prompt = list(payload["prompt"])
            n = int(payload.get("max_new_tokens", 32))
            yield from self.engine.generate_stream(prompt, max_new_tokens=n)

    _LLM.__name__ = "LLMDeployment"
    return _LLM
