"""Ask the TPU's compiler about the kernels of the main path, at b1 widths.

Nothing runs: the chip is described (`v5e:2x2`), not attached, so these say
what Mosaic and XLA:TPU accept — tiling, VMEM, HBM — and nothing about
results or speed. Interpret-mode tests cannot see any of that.

The topology is described inside a module-scoped fixture (only one process
may hold libtpu, and every xdist worker imports every test file), compiles
happen in the test's own process, and all of them live in this one file.
The code's device branches ask `ops.pallas._util.on_tpu()`; the tests steer
that one function instead of adding an option to the program.
"""

import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import ModelConfig
from ray_tpu.ops.pallas import _util

B1 = ModelConfig.b1()  # d 2048, 16 layers, 16/8 heads of 128, d_ff 8192
SEQ = 2048


@pytest.fixture(scope="module")
def topo():
    """Describing a chip loads libtpu, which by default lets ONE process on
    the machine do so (its lock file guards a real chip). Nothing is opened
    here, and under xdist several workers may each run part of this file,
    so the load is declared shareable for the length of this call."""
    from jax.experimental import topologies

    asked = {"TPU_LOG_DIR": "disabled", "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"}
    was = {k: os.environ.get(k) for k in asked}
    os.environ.update(asked)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        for k, v in was.items():
            os.environ.pop(k) if v is None else os.environ.update({k: v})


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip(one_chip, monkeypatch):
    """Shapes on the described chip, with the kernels' device branch steered
    to TPU and the persistent cache off (an entry written for a described
    chip cannot be read back without one, and warns)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(_util, "on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    yield shape
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernels(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _param_shapes(chip, cfg):
    """The model's parameter tree as shapes on the described chip."""
    from ray_tpu.models.transformer import init_params

    params = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(lambda s: chip(s.shape, s.dtype), params)


# `%name = dtype[dims]{layout} opcode(`: an instruction with one array result
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* ([a-z][\w\-]*)\(", re.M)


def _whole_cache_relayouts(compiled, cache) -> list:
    """Names of the compiled program's `copy` and `transpose` instructions
    whose result has as many elements as one `cache`: each reads and writes
    the whole cache once, whatever the step then does in place."""
    return [name for name, dims, opcode in _INSTRUCTION.findall(compiled.as_text())
            if opcode in ("copy", "transpose") and dims
            and math.prod(map(int, dims.split(","))) == cache.size]


# `%name = type opcode(`, the type an array's or a tuple's
_ANY_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\((.*)$", re.M)


def _weight_relayouts_in_the_scan(compiled, params) -> list:
    """Names of the instructions that write one layer of a stacked weight
    down, among the TOP-LEVEL instructions of the layer scan's body (the one
    `while`'s): a `copy`, a `transpose`, a slice, or a fusion other than a
    `kOutput` one (a product's: inside it a fused slice rightly lives, and
    reads the stack in place), whose result has the dimensions `[1, d, n]`
    or `[d, n]` of a matrix leaf `[L, d, n]` of `params["layers"]`. Each is
    a pass over a weight the step did not have to make: at one row a slot
    a product is bound by its weight's bytes."""
    hlo = compiled.as_text()
    body = re.search(r" while\(.*body=%([\w.\-]+)", hlo).group(1)
    layer = {",".join(map(str, dims)) for w in params["layers"].values()
             if len(w.shape) == 3 for dims in (w.shape[1:], (1,) + w.shape[1:])}
    found = []
    for name, result, opcode, rest in _ANY_INSTRUCTION.findall(
            "\n".join(_computations(hlo)[body])):
        writes = (opcode in ("copy", "transpose", "slice", "dynamic-slice")
                  or opcode == "fusion" and "kind=kOutput" not in rest)
        if writes and layer & set(re.findall(r"\w+\[([\d,]+)\]", result)):
            found.append(name)
    return found


def _assert_cache_stays_put(compiled, cache, temp_limit=256 * 2**20):
    assert _whole_cache_relayouts(compiled, cache) == []
    assert compiled.memory_analysis().temp_size_in_bytes < temp_limit


def _one_layer_bytes(cache) -> int:
    """[B, kvh, max_len, hd] of one cache: a decode step that holds a
    temporary this large has copied a layer's cache to read a window of it
    (as the buckets under max_len did up to PR 27: 24 such copies per
    buffer per step are one more pass over the whole cache)."""
    return cache.size * cache.dtype.itemsize // cache.shape[0]


def test_flash_attention_fwd_bwd_compiles(chip):
    from ray_tpu.ops.attention import attention

    def loss(q, k, v):
        return attention(q, k, v).astype(jnp.float32).sum()

    q = chip((4, B1.n_heads, SEQ, B1.head_dim))  # [64, 2048, 128] per call
    c = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).compile()
    assert _kernels(c) >= 3  # forward, dq, dkv


def test_fused_ffn_block_fwd_bwd_compiles(chip):
    from ray_tpu.ops.pallas.fused_ffn import ffn_block

    d, f = B1.d_model, B1.d_ff

    def loss(x, nw, wg, wu, wd):
        return ffn_block(x, nw, wg, wu, wd, B1.norm_eps).astype(jnp.float32).sum()

    args = (chip((2, SEQ, d)), chip((d,)), chip((d, f)), chip((d, f)),
            chip((f, d)))
    c = jax.jit(jax.grad(loss, argnums=(0, 2, 3, 4))).lower(*args).compile()
    assert _kernels(c) >= 1  # K3 (dW gate/up fused with the norm chain)


@pytest.mark.parametrize("dims", [(2 * SEQ, B1.d_model), (B1.d_model, B1.d_ff)])
def test_int8_quant_roundtrip_compiles(chip, dims):
    from ray_tpu.ops.pallas.quant import dequantize_int8, quantize_int8

    def roundtrip(x):
        return dequantize_int8(*quantize_int8(x))

    c = jax.jit(roundtrip).lower(chip(dims)).compile()
    assert _kernels(c) == 2


def test_adamw_leaf_update_compiles(chip):
    from ray_tpu.ops.pallas.adamw import _leaf_update

    leaf = (B1.n_layers, B1.d_model, B1.d_ff)  # the largest stacked leaf

    def update(p, g, mu, nu, scalars):
        return _leaf_update(p, g, mu, nu, scalars, b1=0.9, b2=0.95, eps=1e-8,
                            wd=0.1)

    c = jax.jit(update).lower(
        chip(leaf), chip(leaf), chip(leaf, jnp.float32),
        chip(leaf, jnp.float32), chip((1, 4), jnp.float32)).compile()
    assert _kernels(c) == 1


def _count(compiled, cache, opcode) -> int:
    """Instructions `opcode` of the compiled program whose result has the
    cache's dimensions."""
    dims = ",".join(map(str, cache.shape))
    return sum(1 for _, d, op in _INSTRUCTION.findall(compiled.as_text())
               if op == opcode and d == dims)


def _loops(compiled) -> int:
    return len(re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = .* while\(",
                          compiled.as_text(), re.M))


def _assert_attention_walks_its_items(c, n_calls=1):
    """The compiled text says which form of `gqa_decode_attention` is in:
    ONE call a layer body (the scan's `while` body holds one; a run of one
    layer is inlined, so Jamba's two attention layers are two calls), whose
    Mosaic module (the call's `body`, base64) starts and awaits its own
    copies inside a loop over the live (slot, block) items and carries
    neither a grid's `iteration_bounds` nor a pipelined block's
    `window_params`: up to PR 44 it was a grid of (slots, blocks of the
    window), a step each whether or not a block held a row."""
    import base64

    calls = [l for l in c.as_text().splitlines() if "gqa_decode_attention" in l
             and 'custom_call_target="tpu_custom_call"' in l]
    assert len(calls) == n_calls, len(calls)
    for call in calls:
        module = base64.b64decode(re.search(r'"body":"([^"]+)"', call).group(1))
        assert b"dma_start" in module and b"dma_wait" in module
        assert b"while" in module
        assert b"iteration_bounds" not in module and b"window_params" not in module


def _assert_decode_step_reads_live_rows_in_place(c, kv, n_kernels=1,
                                                 write_kernel=True):
    """The decode step on the chip: the length-aware attention kernel is in
    it (ONE Mosaic call, inside the layer scan; Mosaic has then accepted its
    blocks and its VMEM, or `.compile()` would have raised), both donated
    caches alias their outputs, no instruction relays a whole cache out, and
    the step's temporaries stay under one layer of one cache: the kernel
    takes the caches WHOLE, so nothing stands between the donated parameter
    and its consumer for XLA:TPU to copy. Since PR 33 the row write is a
    Mosaic call of its own per cache (`ops.cache.write_rows`), each a
    custom call whose result IS the cache: the layer scan is then the
    step's only loop (the parent had two more, over the slots), and no
    `dynamic-update-slice` of a cache's shape is left. Off the write
    kernel's shapes the loops and their updates are what remains."""
    n_writes = 2 if write_kernel else 0
    assert _kernels(c) == n_kernels + n_writes
    assert _count(c, kv, "custom-call") == n_writes
    assert _count(c, kv, "dynamic-update-slice") == 2 - n_writes
    assert _loops(c) == 1 + (2 - n_writes)
    assert c.memory_analysis().alias_size_in_bytes >= 2 * (kv.size * 2)
    _assert_cache_stays_put(c, kv, _one_layer_bytes(kv))


@pytest.mark.parametrize("attn_len", [64, 512])
def test_decode_step_fused_compiles_at_b1_8_slots(chip, attn_len):
    from ray_tpu.models.serving import decode_step_fused

    slots, max_len = 8, 512
    params = _param_shapes(chip, B1)
    kv = chip((B1.n_layers, slots, B1.n_kv_heads, max_len, B1.head_dim))
    ints = chip((slots,), jnp.int32)
    c = decode_step_fused.lower(params, kv, kv, ints, ints, B1, attn_len).compile()
    # weights + both caches in, caches updated in place (donated)
    assert c.memory_analysis().argument_size_in_bytes < 3 * 2**30
    _assert_decode_step_reads_live_rows_in_place(c, kv)
    assert _weight_relayouts_in_the_scan(c, params) == []


# InternLM2-1.8B as the serving cell runs it (perfbench/configs/internlm2-1.8b.json)
INTERNLM2 = ModelConfig(vocab_size=92544, d_model=2048, n_layers=24, n_heads=16,
                        n_kv_heads=8, d_ff=8192, rope_theta=1e6)
CELL_SLOTS, CELL_MAX_LEN = 32, 1024


def _cell_cache(chip):
    return chip((INTERNLM2.n_layers, CELL_SLOTS, INTERNLM2.n_kv_heads,
                 CELL_MAX_LEN, INTERNLM2.head_dim))


@pytest.mark.parametrize("attn_len", [64, 512, 1024])
def test_decode_step_keeps_the_cache_layout(chip, attn_len):
    """The donated caches alias their outputs either way; what the compiler
    does in between is the question. With the one-row window the step wrote
    its new K/V rows through up to PR 26, XLA:TPU prefers a cache layout
    with the window's dimensions minor-most ({4,2,0,3,1}), while parameters
    and aliased outputs are pinned to the default one, and this test fails
    on that parent with `copy.58`, `copy.61` (parameter -> {4,2,0,3,1}),
    `copy.64`, `copy.65` (-> the outputs), each bf16[24,32,8,1024,128],
    1.61 GB read and written, and `temp` 1.616 GB: 12.9 GB of HBM traffic in
    every step, 63% of the serving cell's device time (ledger, PR 26). The
    tile-aligned block write (`ops.cache.write_rows`) left two in-place
    `dynamic-update-slice` in the default layout, each in a loop over the
    slots (since PR 33 one aliased Mosaic call each). With that alone the
    buckets under max_len still fail here, on `temp` 0.068 GB: one layer's
    [32,8,1024,128] copied to read `[:, :, :attn_len]` of it; the window
    read by one `dynamic_slice` leaves 0.0006 GB. Since PR 29 the step reads
    each slot's live rows through a Mosaic kernel (the `chip` fixture steers
    the kernel branch on) that takes the whole caches and the layer index:
    a window sliced out first could not fuse into the call and would be
    copied, [32,8,attn_len,128] twice a layer.

    The weights stay put too (PR 35): every stacked weight of the scan is
    read by the product that uses it. The parent of PR 35 fails the last
    line with `constant_dynamic-slice_fusion.4` + `copy.45
    bf16[1,2048,2048]{1,2,0}` (`wq`) and `constant_dynamic-slice_fusion.5` +
    `copy.48 bf16[1,2048,1024]{1,2,0}` (`wk`): with the rotation's float32
    convert and head split fused into those two products XLA:TPU laid
    their output heads-major and wanted the weight transposed, so each
    layer's was sliced out of the stack, written down and copied, 0.72 ms
    of a 5.86 ms step (ledger, PR 34). `serving._one_row_qkv` says what
    keeps them flat."""
    from ray_tpu.models.serving import decode_step_fused

    params = _param_shapes(chip, INTERNLM2)
    kv = _cell_cache(chip)
    ints = chip((CELL_SLOTS,), jnp.int32)
    c = decode_step_fused.lower(params, kv, kv, ints, ints, INTERNLM2,
                                attn_len).compile()
    _assert_decode_step_reads_live_rows_in_place(c, kv)
    _assert_attention_walks_its_items(c)
    assert _weight_relayouts_in_the_scan(c, params) == []


def test_the_weight_helper_names_what_the_parent_of_pr35_compiled(chip, monkeypatch):
    """The same step around the shared `_project_qkv`, as up to PR 34 (the
    spelling is kept in `tests/test_serving.py`): the helper finds `wq` and
    `wk`, each sliced out of its stack and copied, and nothing else."""
    from test_serving import _decode_step_with, _project_qkv_as_the_parent_of_pr35

    params = _param_shapes(chip, INTERNLM2)
    kv = _cell_cache(chip)
    ints = chip((CELL_SLOTS,), jnp.int32)
    c = _decode_step_with(monkeypatch, _project_qkv_as_the_parent_of_pr35).lower(
        params, kv, kv, ints, ints, INTERNLM2, 512).compile()
    found = _weight_relayouts_in_the_scan(c, params)
    assert sorted(n.split(".")[0] for n in found) == [
        "constant_dynamic-slice_fusion"] * 2 + ["copy"] * 2, found


def test_decode_step_off_the_kernels_shapes_keeps_the_cache_layout(chip):
    """A window that does not tile into the kernel's blocks (a max_len that
    is no multiple of them) takes the einsums over one `dynamic_slice` of
    the whole cache, as every window did up to PR 28: no Mosaic call, and
    still no copy of a cache or of a layer of one."""
    from ray_tpu.models.serving import decode_step_fused
    from ray_tpu.ops import cache as cache_ops
    from ray_tpu.ops.pallas import decode_attention

    max_len = 1000
    kv = chip((INTERNLM2.n_layers, CELL_SLOTS, INTERNLM2.n_kv_heads, max_len,
               INTERNLM2.head_dim))
    assert not decode_attention.uses_decode_kernel(kv, max_len)
    ints = chip((CELL_SLOTS,), jnp.int32)
    c = decode_step_fused.lower(_param_shapes(chip, INTERNLM2), kv, kv, ints,
                                ints, INTERNLM2, max_len).compile()
    assert not cache_ops.uses_write_kernel(kv)  # 1000 rows: 62.5 blocks of 16
    _assert_decode_step_reads_live_rows_in_place(c, kv, n_kernels=0,
                                                 write_kernel=False)


def test_write_slots_keeps_the_cache_layout(chip):
    """Admission's scatter of whole prefix rows [L, nb, kvh, max_len, hd]
    is in place on the default layout (true before PR 27 too: a guard). Its
    temporary is a copy of the rows it writes (nb x 50 MB), not of a cache."""
    from ray_tpu.models.serving import _write_slots

    kv, nb = _cell_cache(chip), 4
    rows = chip((kv.shape[0], nb) + kv.shape[2:])
    ints, few = chip((CELL_SLOTS,), jnp.int32), chip((nb,), jnp.int32)
    c = _write_slots.lower(kv, kv, ints, ints, few, rows, rows, few, few).compile()
    _assert_cache_stays_put(c, kv)


def _kimi(chip, **cut):
    """(cfg, parameter shapes, slot-state shapes, slots) of the Kimi Linear
    cell; `cut` lays other values over its `HybridConfig`."""
    import json
    import sys

    from ray_tpu.models import hybrid

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from perfbench.lib import hybrid_model

    with open(os.path.join(root, "perfbench", "configs",
                           "kimi-linear-48b-a3b.1of4.json")) as f:
        conf = json.load(f)
    cfg = hybrid_model.model_config(conf, **cut)
    slots, max_len = conf["run"]["num_slots"], conf["run"]["max_len"]
    as_shapes = lambda tree: jax.tree_util.tree_map(
        lambda a: chip(a.shape, a.dtype), tree)
    params = as_shapes(jax.eval_shape(lambda k: hybrid.init_params(k, cfg),
                                      jax.random.PRNGKey(0)))
    state = as_shapes(jax.eval_shape(lambda: cfg.make_cache(slots, max_len).state))
    return cfg, params, state, slots


@pytest.mark.parametrize("attn_len", [1024, 8192])
def test_hybrid_decode_step_keeps_its_state_in_place(chip, attn_len):
    """The hybrid model's decode step at the benchmark cell's real shapes
    (Kimi-Linear widths, 9 layers, 64 held experts, 64 slots x 8192): every
    leaf of the slot state (KDA S, convolution tails, latent rows: 2.32 GB)
    aliases its output, and the step holds no copy of the latent cache. Three
    earlier forms failed here, all over rows stored in their 576 lanes: a
    latent cache kept as [layers, slots, max_len, 576] and reshaped around
    `write_rows` (`copy.265` / `copy.267` of the whole 1.2 GB cache, `temp`
    1.38 GB), scores taken as "bhc,blc->bhl" against a [slots, window, 576]
    slice (the window re-laid out with the positions minor-most: `temp`
    1.30 GB at 8192), and the Mosaic `write_rows` on a 576-lane block
    (XLA:TPU kept that array positions-minor and bridged to the call's
    row-major operand and back: `copy.498` / `copy.509` of the whole cache).
    Since PR 34 the row is stored in 640 lanes (`HybridConfig.latent_width`)
    and the step holds two Mosaic calls over it: the row write and the
    absorbed decode over live rows (`mla_decode_attention`), one each a
    layer."""
    from ray_tpu.models import hybrid
    from ray_tpu.ops import cache as cache_ops

    cfg, params, state, slots = _kimi(chip)
    ints = chip((slots,), jnp.int32)
    c = hybrid.decode_step.lower(params, state, ints, ints,
                                 chip((slots,), jnp.bool_), cfg, attn_len).compile()
    state_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(state))
    assert state_bytes > 2.3e9
    assert c.memory_analysis().alias_size_in_bytes >= state_bytes
    latent = state["latent"]
    assert latent.shape == (2, slots, 1, 8192, 640)
    assert cache_ops.uses_write_kernel(latent)
    assert "mla_decode_attention" in c.as_text()
    assert _count(c, latent, "dynamic-update-slice") == 0
    _assert_cache_stays_put(c, latent)


def test_hybrid_layer_bodies_are_inlined(chip):
    """The list form's layers are calls of one jitted body a kind
    (`models/hybrid.py`, "the list form's layer bodies"). At Kimi Linear's
    widths with two layers of each kind (KDA and MLA, each over a dense FFN
    and over an expert layer): the lowered step holds one private function a
    kind, two for the FFN half's two parameter trees; the COMPILED step
    holds no `call` (XLA inlined every body: the state still aliases its
    output) and the Mosaic calls its layers ask for: the absorbed decode once
    an MLA layer, one row write, and twelve of XLA's own for the three
    grouped products of an expert layer."""
    from ray_tpu.models import hybrid

    cfg, params, state, slots = _kimi(chip, n_layers=4, kda_layers=(1, 3),
                                      first_dense=2)
    assert cfg.layer_kinds() == (("kda", "dense"), ("mla", "dense"),
                                 ("kda", "moe"), ("mla", "moe"))
    ints = chip((slots,), jnp.int32)
    lowered = hybrid.decode_step.lower(params, state, ints, ints,
                                       chip((slots,), jnp.bool_), cfg, 1024)
    text = lowered.as_text()
    body = r"@(_kda_step|_mla_step|_ffn_rows)(?:_\d+)?\("
    assert sorted(re.findall(r"func\.func private " + body, text)) == [
        "_ffn_rows", "_ffn_rows", "_kda_step", "_mla_step"]
    assert sorted(re.findall(r"call " + body, text)) == \
        ["_ffn_rows"] * 4 + ["_kda_step"] * 2 + ["_mla_step"] * 2
    c = lowered.compile()
    assert not re.findall(r"^.* = .*\s(?:async-)?call(?:-start)?\(.*$",
                          c.as_text(), re.M)
    assert c.as_text().count("mla_decode_attention/pallas_call") >= 2
    assert _kernels(c) == 2 + 1 + 2 * 12
    state_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(state))
    assert c.memory_analysis().alias_size_in_bytes >= state_bytes


def _jamba(chip):
    """(cfg, parameter shapes, slot-state shapes, slots) of the Jamba cell."""
    import json
    import sys

    from ray_tpu.models import hybrid

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from perfbench.lib import jamba_model

    with open(os.path.join(root, "perfbench", "configs", "jamba2-3b.json")) as f:
        conf = json.load(f)
    cfg = jamba_model.model_config(conf)
    slots, max_len = conf["run"]["num_slots"], conf["run"]["max_len"]
    as_shapes = lambda tree: jax.tree_util.tree_map(
        lambda a: chip(a.shape, a.dtype), tree)
    params = as_shapes(jax.eval_shape(lambda k: hybrid.init_params(k, cfg),
                                      jax.random.PRNGKey(0)))
    state = as_shapes(jax.eval_shape(lambda: cfg.make_cache(slots, max_len).state))
    return cfg, params, state, slots


def test_jamba_decode_step_carries_its_state_in_place(chip):
    """The runs form's decode step at the benchmark cell's real shapes
    (Jamba2-3B whole: 28 layers as five scanned runs, 256 slots x 1024): the
    stacked SSM state of every run (2.39 GB float32) rides the scan as a
    carry, through the `selective_step` kernel that aliases it, and aliases
    its output, so the step holds no second copy (`temp` 2.6 MB; a scan that
    takes the state as xs and returns it as ys would hold 2.4 GB more), and
    both attention layers read the K/V cache through the live-rows kernel at
    ONE kv head and a head ratio of 20, and their rows are written by the
    `write_rows` kernel, a grid of 256 slots over blocks [2, 1, 16, 128]."""
    from ray_tpu.models import hybrid
    from ray_tpu.ops import cache as cache_ops

    cfg, params, state, slots = _jamba(chip)
    ints = chip((slots,), jnp.int32)
    c = hybrid.decode_step.lower(params, state, ints, ints,
                                 chip((slots,), jnp.bool_), cfg, 1024).compile()
    state_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(state))
    assert 2.6e9 < state_bytes < 2.7e9
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < 64e6
    text = c.as_text()
    assert "gqa_decode_attention" in text and "selective_step" in text
    _assert_attention_walks_its_items(c, n_calls=2)
    assert cache_ops.uses_write_kernel(state["k"])
    assert _count(c, state["k"], "custom-call") == 2   # K's and V's write
    assert _count(c, state["k"], "dynamic-update-slice") == 0
    _assert_cache_stays_put(c, state["k"])
    for ssm in state["ssm"]:   # the kernel's aliased operand is not copied
        assert _whole_cache_relayouts(c, ssm) == []


def test_jamba_prompt_pass_runs_the_scan_kernel(chip):
    """A prompt pass of 4 x 1023 (the longest bucket; the scan pads it to
    eight chunks of 128): Mosaic takes the `selective_scan` kernel at the
    published widths (blocks of 1024 of 5120 channels, 16 state columns on
    the sublanes), one call per Mamba run's scan body."""
    from ray_tpu.models import hybrid

    cfg, params, _, _ = _jamba(chip)
    c = hybrid._prefill_first.lower(params, chip((4, 1023), jnp.int32),
                                    chip((4,), jnp.int32), cfg).compile()
    assert c.as_text().count("selective_scan") >= 3
    assert c.memory_analysis().temp_size_in_bytes < 1.5e9


def _granite(chip):
    """(cfg, parameter shapes, slot-state shapes, slots) of the Granite cell."""
    import json
    import sys

    from ray_tpu.models import hybrid

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from perfbench.lib import granite_model

    with open(os.path.join(root, "perfbench", "configs",
                           "granite-4.0-h-small.1of2.json")) as f:
        conf = json.load(f)
    cfg = granite_model.model_config(conf)
    slots, max_len = conf["run"]["num_slots"], conf["run"]["max_len"]
    as_shapes = lambda tree: jax.tree_util.tree_map(
        lambda a: chip(a.shape, a.dtype), tree)
    params = as_shapes(jax.eval_shape(lambda k: hybrid.init_params(k, cfg),
                                      jax.random.PRNGKey(0)))
    state = as_shapes(jax.eval_shape(lambda: cfg.make_cache(slots, max_len).state))
    return cfg, params, state, slots


def test_granite_decode_step_walks_busy_slots_and_scans_expert_layers(chip):
    """The runs form with expert layers at the benchmark cell's real shapes
    (one period of Granite-4.0-H-Small: 5 Mamba-2, attention, 4 Mamba-2 as
    three scanned runs, 36 of 72 experts held in each, 32 slots x 16384):
    the stacked matrix state of both Mamba-2 runs (1.21 GB float32) rides
    the scans as a carry through the `ssd_step` kernel that aliases it, the
    attention layer reads K/V through the live-rows kernel under
    `attention_multiplier`, and the expert layers' grouped products compile
    INSIDE the scans (the routing table is made at trace time: as a scatter
    of constants in a loop body XLA:TPU's scatter emitter fails on it)."""
    from ray_tpu.models import hybrid

    cfg, params, state, slots = _granite(chip)
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(params))
    assert 9.4e9 < weights < 9.6e9                     # 4,757M parameters, bf16
    state_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(state))
    assert 3.3e9 < state_bytes < 3.45e9                # 32 x 105.3 MB
    ints = chip((slots,), jnp.int32)
    c = hybrid.decode_step.lower(params, state, ints, ints,
                                 chip((slots,), jnp.bool_), cfg, 16384).compile()
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < 1.0e9
    text = c.as_text()
    assert "ssd_step" in text and "gqa_decode_attention" in text
    assert "ragged" in text
    for ssm in state["ssm"]:   # the kernel's aliased operand is not copied
        assert _whole_cache_relayouts(c, ssm) == []


def test_granite_prompt_pass_fits_beside_weights_and_slots(chip):
    """The longest prompt bucket, 1 x 12288: Mosaic takes the `ssd_scan`
    kernel at the published widths (groups of 8 heads of 64, chunks of 256,
    x and C read in place out of the convolved [x | B | C]) and the flash
    kernel for the one attention layer; the expert layers take their tokens
    2048 at a time; what the pass needs beside 9.51 GB of weights and 3.37 GB
    of slots stays under 2.6 GB (it was 5.3 with the expert layer over all
    12288 tokens at once and dt x written down)."""
    from ray_tpu.models import hybrid

    cfg, params, _, _ = _granite(chip)
    c = hybrid._prefill_first.lower(params, chip((1, 12288), jnp.int32),
                                    chip((1,), jnp.int32), cfg).compile()
    text = c.as_text()
    assert text.count("ssd_scan") >= 2 and "flash" in text
    assert c.memory_analysis().temp_size_in_bytes < 2.6e9


def _pangu(chip):
    """(cfg, parameter shapes, slot-state shapes, slots) of the openPangu cell."""
    import json
    import sys

    from ray_tpu.models import hybrid

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from perfbench.lib import pangu_model

    with open(os.path.join(root, "perfbench", "configs",
                           "openpangu-ultra-moe-718b.1of32.json")) as f:
        conf = json.load(f)
    cfg = pangu_model.model_config(conf)
    slots, max_len = conf["run"]["num_slots"], conf["run"]["max_len"]
    as_shapes = lambda tree: jax.tree_util.tree_map(
        lambda a: chip(a.shape, a.dtype), tree)
    params = as_shapes(jax.eval_shape(lambda k: hybrid.init_params(k, cfg),
                                      jax.random.PRNGKey(0)))
    state = as_shapes(jax.eval_shape(lambda: cfg.make_cache(slots, max_len).state))
    return cfg, params, state, slots


def test_pangu_verify_step_reads_live_rows_in_place(chip):
    """The drafting decode step at the benchmark cell's real shapes
    (openPangu-Ultra-MoE widths: 128 heads, a cut of 1 dense + 5 expert
    layers + the prediction module, 8 held experts, 32 slots x 8192): the
    latent rows of 7 MLA layers, stored in 640 lanes (2.35 GB), alias their
    output and stay row-major: Mosaic takes the `mla_decode_attention`
    kernel at 256 query rows x 640 lanes, once a layer, and both new
    positions' rows are written by the `write_rows` kernel; the step holds
    no copy of the cache (`temp` 36 MB)."""
    from ray_tpu.models import hybrid
    from ray_tpu.ops import cache as cache_ops

    cfg, params, state, slots = _pangu(chip)
    ints = chip((slots,), jnp.int32)
    c = hybrid.decode_step.lower(params, state, ints, ints,
                                 chip((slots,), jnp.bool_), cfg, 8192).compile()
    latent = state["latent"]
    assert latent.shape == (7, 32, 1, 8192, 640)
    state_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(state))
    assert 2.3e9 < state_bytes < 2.4e9
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < 128e6
    text = c.as_text()
    assert text.count("mla_decode_attention") >= 7
    assert cache_ops.uses_write_kernel(latent)
    assert _count(c, latent, "custom-call") == 2       # one write a position
    assert _count(c, latent, "dynamic-update-slice") == 0
    _assert_cache_stays_put(c, latent)


def test_pangu_prompt_pass_fits_beside_the_weights(chip):
    """One prompt of the longest bucket (1 x 8191) through 7 MLA layers at
    128 heads: a group of heads at a time (`ops.mla.mla_prefill_attention`)
    and the expert layer's last tier without a gather of all T x 8 rows
    (`ops.moe.dropless_moe`), so that its temporaries stay under 4 GB
    beside 9.55 GB of weights and 2.35 GB of slots (all heads' scores at
    once and the gathered tier held 7.5 GB, which no chip has left)."""
    from ray_tpu.models import hybrid

    cfg, params, _, _ = _pangu(chip)
    c = hybrid._prefill_first.lower(params, chip((1, 8191), jnp.int32),
                                    chip((1,), jnp.int32), cfg).compile()
    mem = c.memory_analysis()
    assert mem.temp_size_in_bytes < 4.0e9
    assert 9.5e9 < mem.argument_size_in_bytes < 9.6e9


def _eva(chip):
    """(cfg, parameter shapes, slot-state shapes, slots) of the EvaByte cell."""
    import json
    import sys

    from ray_tpu.models import hybrid

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from perfbench.lib import eva_model

    with open(os.path.join(root, "perfbench", "configs",
                           "evabyte-6.5b.1of4.json")) as f:
        conf = json.load(f)
    cfg = eva_model.model_config(conf)
    slots, max_len = conf["run"]["num_slots"], conf["run"]["max_len"]
    as_shapes = lambda tree: jax.tree_util.tree_map(
        lambda a: chip(a.shape, a.dtype), tree)
    params = as_shapes(jax.eval_shape(lambda k: hybrid.init_params(k, cfg),
                                      jax.random.PRNGKey(0)))
    state = as_shapes(jax.eval_shape(lambda: cfg.make_cache(slots, max_len).state))
    return cfg, params, state, slots


def test_eva_decode_step_reads_live_rows_in_place(chip):
    """The EVA decode step at the benchmark cell's real shapes (EvaByte
    widths: 32 heads of 128 with keys of their own, 8 layers, 16 slots x
    32768): both tables [8, 16, 32, 2048 + 2048, 128] (8.59 GB) alias their
    output and no copy of either exists (`temp` 2 MB): Mosaic takes the
    `eva_decode_attention` kernel at blocks of 256 rows x 32 heads (2 MB of
    K and of V a step, under a raised VMEM limit) once a layer, and the
    position's row and the closed chunk's summary are written by the
    `write_rows` kernel at rows the step names, two calls a table."""
    from ray_tpu.models import hybrid
    from ray_tpu.ops import cache as cache_ops

    cfg, params, state, slots = _eva(chip)
    ints = chip((slots,), jnp.int32)
    c = hybrid.decode_step.lower(params, state, ints, ints,
                                 chip((slots,), jnp.bool_), cfg, 16384).compile()
    table = state["ek"]
    assert table.shape == (8, 16, 32, 4096, 128)
    state_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(state))
    assert 8.6e9 < state_bytes < 8.7e9
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < 64e6
    assert "eva_decode_attention" in c.as_text()
    assert cache_ops.uses_write_kernel(table)
    assert _count(c, table, "custom-call") == 4   # K's and V's row and summary
    assert _count(c, table, "dynamic-update-slice") == 0
    _assert_cache_stays_put(c, table)


def test_eva_prompt_pass_fits_beside_the_tables(chip):
    """The longest prompt bucket (1 x 28,672: 14 windows) walks the windows,
    each through all 8 layers, so its temporaries are those of ONE window
    (under 1.6 GB beside 11.88 GB of weights and tables; 2.9 GB with every
    window's hidden rows kept and the three projections' stacks copied
    transposed ahead of the loops), and the admission write puts the rows
    into the donated tables in place."""
    from ray_tpu.models import hybrid

    cfg, params, state, slots = _eva(chip)
    one, toks = chip((1,), jnp.int32), chip((1, 28672), jnp.int32)
    c = hybrid._prefill_first.lower(params, toks, one, cfg).compile()
    mem = c.memory_analysis()
    assert mem.temp_size_in_bytes < 1.6e9
    assert 3.2e9 < mem.argument_size_in_bytes < 3.3e9
    assert _loops(c) >= 2
    rows = jax.tree_util.tree_map(
        lambda a: chip(a.shape, a.dtype),
        jax.eval_shape(lambda p, t, n: hybrid._prefill_first(p, t, n, cfg),
                       params, toks, one)[1])
    assert rows["sum_k"].shape == (8, 1, 32, 1792, 128)
    ints = chip((slots,), jnp.int32)
    w = hybrid._write_state.lower(state, ints, ints, one, rows, one, one).compile()
    assert w.memory_analysis().temp_size_in_bytes < 256e6
    assert _whole_cache_relayouts(w, state["ek"]) == []


def _keye(chip):
    """(cfg, parameter shapes, slot-state shapes, slots) of the Keye cell."""
    import json
    import sys

    from ray_tpu.models import hybrid

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from perfbench.lib import keye_model

    with open(os.path.join(root, "perfbench", "configs",
                           "keye-vl-2.0-30b-a3b.1of8.json")) as f:
        conf = json.load(f)
    cfg = keye_model.model_config(conf)
    slots, max_len = conf["run"]["num_slots"], conf["run"]["max_len"]
    as_shapes = lambda tree: jax.tree_util.tree_map(
        lambda a: chip(a.shape, a.dtype), tree)
    params = as_shapes(jax.eval_shape(lambda k: hybrid.init_params(k, cfg),
                                      jax.random.PRNGKey(0)))
    state = as_shapes(jax.eval_shape(lambda: cfg.make_cache(slots, max_len).state))
    return cfg, params, state, slots


@pytest.mark.parametrize("attn_len", [16384, 32768])
def test_keye_decode_step_reads_indexer_keys_and_listed_rows(chip, attn_len):
    """The sparse-attention stack at the benchmark cell's real shapes (six
    layers of Keye-VL-2.0's decoder, all 128 experts, 8 slots x 32768): a
    layer's attention is TWO kernels and a sort, `dsa_scores` over the busy
    slots' live indexer keys and `dsa_rows` over each busy slot's list of
    2,048 positions, a DMA a position: no instruction of the step reads a
    layer's K/V cache whole (no copy, no slice, no gather of its size; the
    two tables, 3.62 GB, are aliased and stay put), and what the step holds
    beside them is a few MB."""
    from ray_tpu.models import hybrid

    cfg, params, state, slots = _keye(chip)
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(params))
    assert 8.74e9 < weights < 8.76e9                   # 4,374.6M parameters, bf16
    state_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(state))
    assert state_bytes == 8 * 32768 * 13824            # 3.62 GB
    ints = chip((slots,), jnp.int32)
    c = hybrid.decode_step.lower(params, state, ints, ints,
                                 chip((slots,), jnp.bool_), cfg, attn_len).compile()
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < 32 * 2**20
    text = c.as_text()
    assert "dsa_scores" in text and "dsa_rows" in text and "ragged" in text
    assert "write_rows" in text                        # the indexer keys' row
    assert f"f32[{slots},{attn_len}]" in text and " sort(" in text   # the exact top-k
    for table in state.values():
        assert _whole_cache_relayouts(c, table) == []
    # nothing of the shape of ONE layer's K/V table, or of its window, is
    # made, read out or gathered
    made = {dims for _, dims, _ in _INSTRUCTION.findall(text)}
    for rows in {attn_len, 32768}:
        assert not {f"{slots},{rows},8,128", f"1,{slots},{rows},8,128"} & made


def test_keye_prompt_pass_fits_beside_weights_and_slots(chip):
    """The longest prompt bucket, 1 x 32768: Mosaic takes `dsa_select` (a
    scratch of 16 MB of keys, the block's whole causal score row) and
    `dsa_attention` at the published widths; no [n, n] array exists outside
    them; what the pass needs beside 8.75 GB of weights and 3.62 GB of slots
    stays under 2 GB. With the chosen rows returned (the comparison's
    program) the words are n x n / 32, 0.8 GB over six layers."""
    from ray_tpu.models import hybrid

    cfg, params, _, _ = _keye(chip)
    n = 32768
    c = hybrid._prefill_first.lower(params, chip((1, n), jnp.int32),
                                    chip((1,), jnp.int32), cfg).compile()
    text = c.as_text()
    assert "dsa_select" in text and "dsa_attention" in text and "flash" not in text
    assert c.memory_analysis().temp_size_in_bytes < 2.0e9
    assert not re.search(rf"\[(\d+,)*{n},{n}\]", text)
    rows = hybrid.prefill.lower(params, chip((1, n), jnp.int32), chip((1,), jnp.int32),
                                cfg, with_routing=True).compile()
    assert f"s32[6,1,{n // 32},{n}]" in rows.as_text()
    assert rows.memory_analysis().temp_size_in_bytes < 2.1e9


def _cmda(chip):
    """(cfg, parameter shapes, slot-state shapes, slots, max_len) of the
    Command A+ cell."""
    import json
    import sys

    from ray_tpu.models import hybrid

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from perfbench.lib import cmda_model

    with open(os.path.join(root, "perfbench", "configs",
                           "command-a-plus-05-2026.1of8.json")) as f:
        conf = json.load(f)
    cfg = cmda_model.model_config(conf)
    slots, max_len = conf["run"]["num_slots"], conf["run"]["max_len"]
    as_shapes = lambda tree: jax.tree_util.tree_map(
        lambda a: chip(a.shape, a.dtype), tree)
    params = as_shapes(jax.eval_shape(lambda k: hybrid.init_params(k, cfg),
                                      jax.random.PRNGKey(0)))
    state = as_shapes(jax.eval_shape(lambda: cfg.make_cache(slots, max_len).state))
    return cfg, params, state, slots, max_len


def test_cmda_decode_step_walks_ring_and_full_rows_in_place(chip):
    """The window-and-full stack at the benchmark cell's real shapes (one
    period of Command A+'s layers, 16 of 128 experts, 12 slots x 49,152): ONE
    step program whatever the deepest slot (the kernel walks live rows), a
    decode kernel a layer over the ring (3 layers) or the full rows (1), both
    caches aliased and written by `write_rows`; nothing of the size of a
    layer's cache, or of its window, is copied, sliced or transposed. The
    same body with the logits returned is what the benchmark's check replays."""
    from ray_tpu.models import hybrid

    cfg, params, state, slots, max_len = _cmda(chip)
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(params))
    assert 9.46e9 < weights < 9.47e9                   # 4,733.3M parameters, bf16
    state_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(state))
    assert state_bytes == 12 * (49152 + 3 * 4096) * 4096            # 3.02 GB
    ints = chip((slots,), jnp.int32)
    c = hybrid.decode_step.lower(params, state, ints, ints,
                                 chip((slots,), jnp.bool_), cfg, max_len).compile()
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < 32 * 2**20
    text = c.as_text()
    assert "gqa_decode_attention" in text and "write_rows" in text and "ragged" in text
    for table in state.values():
        assert _whole_cache_relayouts(c, table) == []
    # nothing of the shape of ONE window layer's ring is made or read out (the
    # one full layer's cache IS a layer's: the relayouts above speak for it)
    made = {dims for _, dims, _ in _INSTRUCTION.findall(text)}
    assert not {f"{slots},8,4096,128", f"1,{slots},8,4096,128"} & made
    # the comparison's program: the step's body at the SAME attention length
    # (`SwaCache.step_len`), logits and every slot's choice of experts returned
    rows = hybrid.decode_logits.lower(params, state, ints, ints, None, cfg,
                                      max_len).compile()
    assert rows.memory_analysis().alias_size_in_bytes >= state_bytes
    assert rows.memory_analysis().temp_size_in_bytes < 32 * 2**20
    assert "gqa_decode_attention" in rows.as_text()


def test_cmda_prompt_pass_walks_its_chunks_beside_weights_and_slots(chip):
    """The ONE program of every prompt past a window, 1 x 49,152: Mosaic takes
    the banded flash kernel at 128 query heads on 8 key heads (the window
    layers' over the chunk before and the chunk, the full layer's over the
    whole cache, its chunk's place a scalar), the walk is a `while` whose trip
    count is data, no [chunk, keys] score exists outside the kernel, and what
    the pass needs beside 9.47 GB of weights and 3.02 GB of slots stays under
    2 GB. The same with every position's choice of experts returned (the
    comparison's program), and the shortest bucket's."""
    from ray_tpu.models import hybrid

    cfg, params, _, _, max_len = _cmda(chip)
    one = chip((1,), jnp.int32)
    c = hybrid._prefill_first.lower(params, chip((1, max_len), jnp.int32), one,
                                    cfg).compile()
    text = c.as_text()
    assert "flash_attention_banded" in text and " while(" in text
    assert c.memory_analysis().temp_size_in_bytes < 2.0e9
    assert not re.search(r"f32\[(\d+,)*4096,(8192|49152)\]", text)   # no scores in HBM
    assert not re.search(rf"\[(\d+,)*128,{max_len},128\]", text)      # no K / V a query head
    rows = hybrid.prefill.lower(params, chip((1, max_len), jnp.int32), one, cfg,
                                with_routing=True).compile()
    assert f"s32[4,1,{max_len},8]" in rows.as_text()
    assert rows.memory_analysis().temp_size_in_bytes < 2.1e9
    short = hybrid._prefill_first.lower(params, chip((1, 512), jnp.int32), one,
                                        cfg).compile()
    assert "flash_attention_banded" in short.as_text()
    assert " while(" in short.as_text()            # the runs' scans; no walk
    assert short.memory_analysis().temp_size_in_bytes < 0.6e9


def _sthink(chip):
    """(cfg, parameter shapes, slot-state shapes, slots, max_len) of the
    SmallThinker cell."""
    import json
    import sys

    from ray_tpu.models import hybrid

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from perfbench.lib import sthink_model

    with open(os.path.join(root, "perfbench", "configs",
                           "smallthinker-21b-a3b.12of52.json")) as f:
        conf = json.load(f)
    cfg = sthink_model.model_config(conf)
    slots, max_len = conf["run"]["num_slots"], conf["run"]["max_len"]
    as_shapes = lambda tree: jax.tree_util.tree_map(
        lambda a: chip(a.shape, a.dtype), tree)
    params = as_shapes(jax.eval_shape(lambda k: hybrid.init_params(k, cfg),
                                      jax.random.PRNGKey(0)))
    state = as_shapes(jax.eval_shape(lambda: cfg.make_cache(slots, max_len).state))
    return cfg, params, state, slots, max_len


def test_sthink_decode_step_routes_ahead_over_six_runs_in_place(chip):
    """SmallThinker's 12-layer stage at the benchmark cell's real shapes (six
    runs, the global layer first, 64 of 64 experts, 16 slots x 16,384): ONE
    step program; Mosaic takes the decode kernel at SEVEN query heads a key
    head (the wrapper pads the group to 8 sublanes) over the ring (9 layers)
    and the full rows (3); both caches aliased and written by `write_rows`;
    the experts' stacks are read in place (no copy of a layer's 755 MB of
    experts, nor of a run's); nothing of a layer's cache is relaid."""
    from ray_tpu.models import hybrid

    cfg, params, state, slots, max_len = _sthink(chip)
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(params))
    assert 11.12e9 < weights < 11.13e9                 # 5,561.4M parameters, bf16
    state_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(state))
    assert state_bytes == 16 * (3 * 16384 + 9 * 4096) * 2048          # 2.82 GB
    ints = chip((slots,), jnp.int32)
    c = hybrid.decode_step.lower(params, state, ints, ints,
                                 chip((slots,), jnp.bool_), cfg, max_len).compile()
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < 64 * 2**20
    text = c.as_text()
    assert "gqa_decode_attention" in text and "write_rows" in text and "ragged" in text
    assert f"bf16[{slots},4,8,128]" in text            # the group of 7, padded to 8
    for table in state.values():
        assert _whole_cache_relayouts(c, table) == []
    assert not re.search(r"= bf16\[(3,|1,)?64,2560,768\]\S* copy\(", text)
    rows = hybrid.decode_logits.lower(params, state, ints, ints, None, cfg,
                                      max_len).compile()
    assert rows.memory_analysis().alias_size_in_bytes >= state_bytes
    assert rows.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_sthink_prompt_pass_walks_its_chunks_beside_weights_and_slots(chip):
    """The ONE program of every prompt past a window, 1 x 16,384: Mosaic takes
    the banded flash kernel at 28 query heads on 4 key heads (a group of 7
    through the index map), the walk is a `while` whose trip count is data,
    no [chunk, keys] score exists outside the kernel, and what the pass needs
    beside 11.12 GB of weights and 2.82 GB of slots stays under 1.2 GB. The
    same with every position's choice of experts returned (the comparison's
    program), and a bucket inside one window."""
    from ray_tpu.models import hybrid

    cfg, params, _, _, max_len = _sthink(chip)
    one = chip((1,), jnp.int32)
    c = hybrid._prefill_first.lower(params, chip((1, max_len), jnp.int32), one,
                                    cfg).compile()
    text = c.as_text()
    assert "flash_attention_banded" in text and " while(" in text
    assert c.memory_analysis().temp_size_in_bytes < 1.2e9
    assert not re.search(r"f32\[(\d+,)*4096,(8192|16384)\]", text)   # no scores in HBM
    assert not re.search(rf"\[(\d+,)*28,{max_len},128\]", text)      # no K / V a query head
    rows = hybrid.prefill.lower(params, chip((1, max_len), jnp.int32), one, cfg,
                                with_routing=True).compile()
    assert f"s32[12,1,{max_len},6]" in rows.as_text()
    assert rows.memory_analysis().temp_size_in_bytes < 1.3e9
    short = hybrid._prefill_first.lower(params, chip((1, 2048), jnp.int32), one,
                                        cfg).compile()
    assert "flash_attention_banded" in short.as_text()
    assert short.memory_analysis().temp_size_in_bytes < 0.6e9


def test_prefill_slots_compiles_at_b1(chip):
    from ray_tpu.models.serving import prefill_slots

    params = _param_shapes(chip, B1)
    c = prefill_slots.lower(params, chip((4, 256), jnp.int32),
                            chip((4,), jnp.int32), B1, 512).compile()
    assert c.memory_analysis().argument_size_in_bytes < 3 * 2**30


def _lower_b1_step(topo, *, chips, mesh, batch, seq, optimizer, fused, cfg=B1):
    """The whole b1 train step, as `chip_smoke.py` builds it (or another
    `cfg`'s, as the training cells build theirs), lowered for `chips`
    described devices. `.compile()` is the question."""
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.train.step import batch_sharding, make_train_step

    cfg = dataclasses.replace(cfg, max_seq_len=seq, remat="dots", loss_chunk=0,
                              fused_ffn=fused, fused_attn=fused)
    mesh = make_mesh(MeshConfig(**mesh), topo.devices[:chips])
    step_fn, init_fn, shardings = make_train_step(cfg, mesh, optimizer)
    state = jax.tree_util.tree_map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
        jax.eval_shape(init_fn, jax.random.PRNGKey(0)), shardings)
    b_sh = batch_sharding(mesh)
    batch = {k: jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=b_sh[k])
             for k in ("inputs", "targets")}
    return step_fn.lower(state, batch)


# Mistral-7B-v0.3 widths, as the training cells run them
# (perfbench/configs/mistral-7b-v0.3.4chip.json: 22 layers, fsdp 2 x tp 2)
MISTRAL = ModelConfig(vocab_size=32768, d_model=4096, n_layers=22, n_heads=32,
                      n_kv_heads=8, d_ff=14336, rope_theta=1e6)
_COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                         r"collective-permute|collective-broadcast")


def _computations(hlo: str) -> dict:
    """name -> lines of each computation of a compiled module's text."""
    out, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\) -> .*\{$", line)
        if head:
            name = head.group(1)
            out[name] = []
        elif name is not None:
            out[name].append(line)
    return out


def _is_matmul(comps: dict, line: str) -> bool:
    """A fusion whose computation (or one it calls: the all-gather a matmul
    carries inside runs in a nested one) holds a convolution."""
    called = re.search(r" fusion\(.*calls=%([\w.\-]+)", line)
    body = "\n".join(comps.get(called.group(1), [])) if called else ""
    return " convolution(" in body or any(
        " convolution(" in "\n".join(comps.get(c, []))
        for c in re.findall(r"calls=%([\w.\-]+)", body))


_FSDP_PAIRS = "{{0,2},{2,0},{1,3},{3,1}}"   # fsdp 2 x tp 2: the ranks that share tp
_TP_PAIRS = "{{0,1},{1,0},{2,3},{3,2}}"     # ... and those that share fsdp


def _scan_bodies(comps: dict) -> tuple:
    """(forward, backward): the two `while` bodies of the layers' scan; the
    backward's holds more exchanges (its own and the weight gradients').
    (Of a kept excerpt, which has the bodies and no `while`: every
    computation is looked at.)"""
    called = {name for lines in comps.values() for l in lines
              for name in re.findall(r" while\(.*body=%([\w.\-]+)", l)}
    bodies = [comps[name] for name in sorted(called or comps)
              if sum(" collective-permute-start(" in l for l in comps[name]) >= 4]
    assert len(bodies) == 2, len(bodies)
    return tuple(sorted(bodies, key=lambda lines: sum(
        " collective-permute-start(" in l for l in lines)))


def _permutes(comps: dict, body: list, pairs: str) -> dict:
    """instruction name -> (shard's dims, what is scheduled between the start
    and its done that covers the transfer), for the body's permutes over
    `pairs`: "matmul" (a matmul fusion), else "kernel" (a Mosaic kernel: the
    attention forward that remat runs again), else None."""
    out = {}
    for i, l in enumerate(body):
        if " collective-permute-start(" in l and pairs in l:
            name = re.match(r"\s*%([\w.\-]+) =", l).group(1)
            done = next(j for j, d in enumerate(body)
                        if f"collective-permute-done(%{name})" in d)
            between = body[i + 1:done]
            cover = ("matmul" if any(_is_matmul(comps, m) for m in between)
                     else "kernel" if any("tpu_custom_call" in m for m in between)
                     else None)
            out[name] = (re.search(r"= \(\w+\[([\d,]*)\]", l).group(1), cover)
    return out


_WEIGHT_SHARDS = sorted(["2048,512"] * 2 + ["2048,2048"] * 2 + ["2048,7168"] * 2
                        + ["7168,2048"])  # wk wv | wq wo | w_gate w_up | w_down


def _assert_grad_exchange_runs_behind_the_backward(comps):
    """The compiled fsdp 2 x tp 2 step at the cell's widths. In the backward
    `while` body: no `all-reduce-scatter` fusion (the partitioner's form of
    the weight gradients' reduction over fsdp: the whole [4096,7168]
    gradient padded, all-reduced and sliced ON the compute stream, 0.76 ms
    each, seven a layer) and no all-reduce over the fsdp pairs {{0,2},{1,3}}
    at all; instead seven
    `collective-permute-start` ... `-done` pairs of exact shards
    ([1,2048,7168] x 2, [1,7168,2048], [1,2048,2048] x 2, [1,2048,512] x 2),
    each with a matmul fusion scheduled between its start and its done."""
    _, body = _scan_bodies(comps)
    assert not [l for l in body if "all-reduce-scatter" in l]
    assert not [l for l in body if " all-reduce(" in l and "{{0,2},{1,3}}" in l]
    exchanges = [v for v in _permutes(comps, body, _FSDP_PAIRS).values()
                 if v[0].count(",") == 2]
    assert sorted(dims for dims, _ in exchanges) == sorted(
        ["1,2048,7168"] * 2 + ["1,7168,2048"] + ["1,2048,2048"] * 2
        + ["1,2048,512"] * 2)
    assert all(cover == "matmul" for _, cover in exchanges), exchanges


def _assert_weights_ride_the_fsdp_ring(comps):
    """Since PR 38 the products of the tp route carry their weights' gathers
    over fsdp too (`fsdp.ring_products`): each scan body sends the seven
    weights' shards ([2048,512] x 2, [2048,2048] x 2, [2048,7168] x 2,
    [7168,2048]) round the fsdp pairs as permutes, started at the head of the
    body, every one of the fourteen with a matmul fusion between its start
    and its done (the backward's first, `w_down`'s, behind the product by
    the rank's own shard since PR 54: the case below), and NO
    `all-gather` is left in either body: the partitioner gathered them one at
    a time, each started where the one before was first used, and once the tp
    all-reduces had left the compute stream the step waited for them."""
    for body in _scan_bodies(comps):
        assert not [l for l in body if " all-gather(" in l]
        shards = [v for v in _permutes(comps, body, _FSDP_PAIRS).values()
                  if v[0].count(",") == 1]
        assert sorted(dims for dims, _ in shards) == _WEIGHT_SHARDS, shards
        assert [cover for _, cover in shards] == ["matmul"] * 7, shards


def _operands(line: str) -> list:
    """The names an instruction takes (what it calls is no operand)."""
    return re.findall(r"%([\w.\-]+)",
                      re.search(r" [a-z][a-z0-9\-]*\(([^)]*)\)", line).group(1))


def _assert_own_shard_first_in_the_backward(comps):
    """`w_down`'s shard [7168,2048] is the first thing a layer's backward
    sends, and no earlier product of the body hides its way. By OPERANDS,
    not by name: the matmul fusion that multiplies by the rank's own shard
    (the permute's operand) is scheduled between the permute's start and its
    done, and the first matmul fusion behind the done is the one that takes
    the done, the arrived shard's product, with none by the own shard left
    behind it. The compiler had it the other way round before PR 54: the sum
    of the two float32 products fused into the own shard's, which then ran
    last (`fsdp.ring_products`, `own_first`)."""
    _, body = _scan_bodies(comps)
    (start,) = [i for i, l in enumerate(body)
                if " collective-permute-start(" in l and _FSDP_PAIRS in l
                and re.search(r"= \(\w+\[7168,2048\]", l)]
    name = lambda l: re.match(r"\s*%([\w.\-]+) =", l).group(1)
    own, = _operands(body[start])
    done = next(i for i, l in enumerate(body)
                if f"collective-permute-done(%{name(body[start])})" in l)
    arrived = name(body[done])
    products = [(i, own in _operands(l), arrived in _operands(l))
                for i, l in enumerate(body) if _is_matmul(comps, l)]
    by_own = [i for i, o, a in products if o and not a]
    by_arrived = [i for i, o, a in products if a]
    assert by_own and by_arrived, (by_own, by_arrived)
    assert all(start < i < done for i in by_own), (start, by_own, done)
    behind = [i for i, _, _ in products if i > done]
    assert behind[0] == by_arrived[0], (behind[:2], by_arrived)


_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')


def _assert_dw_rings_taken_in_start_order(comps):
    """A layer's seven weight-gradient rings share the one `fsdp` link in one
    direction, and the link serves them in the order of their starts. So in
    the backward body, among the `fsdp` permutes whose operand is a chunk of
    a `dw` ([1, k, n]): no `-done` of a LATER start stands before the fusion
    that takes an EARLIER start's done (the kept half's product with the sum
    and the stack write fused in; for the 2 MB rings the sum and the write,
    behind the kept product), five of the seven are such products, and at
    every such done the bytes started no later
    than its own start and not yet taken, over XLA's own estimate of what
    stands between the oldest of those starts and the done (the cycles in
    each fusion's `backend_config`, at the v5e's 1.5 GHz), stay under
    35 GB/s, what PR 54 read the link carry. Left to the scheduler the
    smallest ring's done came first: `wk`'s 2 MB behind 67 MB, 45.6 GB/s
    (PERF.md section 6, PR 57: `fsdp.RingOrder`)."""
    _, body = _scan_bodies(comps)
    name = lambda l: re.match(r"\s*(?:ROOT )?%([\w.\-]+) =", l).group(1)
    ms = lambda l: int((_CYCLES.search(l) or [0, 0])[1]) / 1.5e6
    rings = []
    for at, l in enumerate(body):
        chunk = re.search(r"= \((\w+)\[1,(\d+),(\d+)\]", l)
        if " collective-permute-start(" in l and _FSDP_PAIRS in l and chunk:
            done = next(i for i, d in enumerate(body)
                        if f"collective-permute-done(%{name(l)})" in d)
            taken = next(i for i, d in enumerate(body) if i > done
                         and name(body[done]) in _operands(d))
            rings.append({"start": at, "done": done, "taken": taken,
                          "product": _is_matmul(comps, body[taken]),
                          "mb": 2 * int(chunk[2]) * int(chunk[3]) / 1e6})
    assert len(rings) == 7 and sum(r["product"] for r in rings) >= 5, rings
    for r in rings:
        later = [o for o in rings if o["start"] > r["start"]]
        assert all(o["done"] > r["taken"] for o in later), (r, later)
        ahead = [o for o in rings
                 if o["start"] <= r["start"] and o["done"] >= r["done"]]
        between = sum(ms(l) for l in
                      body[min(o["start"] for o in ahead) + 1:r["done"]])
        assert sum(o["mb"] for o in ahead) / between < 35, (r, ahead, between)


def _assert_no_tp_all_reduce_in_the_layers(comps):
    """Neither scan body is left a blocking `all-reduce` of the residual
    [1,2048,4096] (the partitioner's Megatron form had two in each, over the
    tp pairs, on the compute stream), and since PR 61 none stands outside
    them either: the head's `dx` was the last, once a step, and comes back
    rows-over-tp through the ring now (`_assert_head_rides_the_rings`)."""
    assert not [l for lines in comps.values() for l in lines
                if " all-reduce(" in l and "[1,2048,4096]" in l]


def _assert_tp_exchanges_run_behind_the_products(comps):
    """The residual stream's rows ride [1,1024,4096] a rank between the
    products; every gather and scatter over the tp pairs is a
    `collective-permute-start` ... `-done` of such a shard: four in the
    forward body (before qkv, behind `wo`, before gate | up, behind
    `w_down`), six in the backward's (their four transposes and the two
    re-gathers of the normalised rows for `dw`; the forward's sums are kept
    by name, not sent again). Each has a matmul fusion scheduled between its
    start and its done, but the gather of dy at the backward body's head,
    which travels behind the attention kernel that remat runs again."""
    for body, n, other in zip(_scan_bodies(comps), (4, 6), ("matmul", "kernel")):
        moved = _permutes(comps, body, _TP_PAIRS)
        assert [dims for dims, _ in moved.values()] == ["1,1024,4096"] * n, moved
        covers = [cover for _, cover in moved.values()]
        assert covers.count("matmul") >= n - 1, moved
        assert set(covers) <= {"matmul", other}, moved


_ALL_REDUCE = re.compile(r" all-reduce(-start)?\(")


def _assert_no_all_reduce_in_the_layers(comps):
    """Neither scan body holds an all-reduce of any kind. The last one was
    the two norm scales' gradients': a replicated [4096] leaf, each chip's
    partial sum of its own rows, all-reduced over all four chips in the
    backward's body, 8 KB a layer on the compute stream, where every chip
    waited for the slowest (13.3 ms of a 319 ms step; PERF.md section 6,
    PR 46). The scales ride once a rank now (`fsdp.scale_by_rank`), the
    scan stacks the partial sums, and the sum over the ranks stands ONCE in
    the module, outside the bodies: a [layers, 4096] operand a leaf (the
    compiler makes one all-reduce of the two, `final_norm`'s with them)."""
    bodies = _scan_bodies(comps)
    for body in bodies:
        assert not [l for l in body if _ALL_REDUCE.search(l)]
    sums = [dims for lines in comps.values()
            if not any(lines is body for body in bodies)
            for l in lines if _ALL_REDUCE.search(l)
            for dims in re.findall(rf"\w+\[(\d+),{MISTRAL.d_model}\]",
                                   _ALL_REDUCE.split(l)[0])]
    assert len(sums) == 2 and len(set(sums)) == 1, sums


def _assert_dw_twins_read_fast_memory(comps):
    """Gate's and up's kept weight-gradient products (the two matmul fusions
    of the backward body that write a `bf16[L,2048,7168]` stack: the same
    60.1 GFLOP, the same fused sum, rounding and stack write) are tiled
    alike: equal `output_window_bounds`, XLA's `estimated_cycles` within 5%
    of each other, and no operand of either is the EVICTION of what a
    permute delivered (a `copy-done` whose `copy-start` took a
    `collective-permute-done`). The tiler's window follows an operand's
    memory space. Up to PR 58 the half of `x` that arrives over `tp` at the
    body's head was evicted from fast memory at once, fetched back for the
    first twin only, and the second read the copy in HBM under half the
    window: 720,560 cycles against 479,938, 10.23 ms a step against 7.28 on
    the chip (PERF.md section 6, PR 59: `fsdp._reduce_scatter_dws`, `_staged`).

    What stays in fast memory is decided over the WHOLE scan body, and two
    layers' stacks fit fast memory themselves: the two-layer compile does
    not reproduce the eviction (there the parent's arrived half lies in HBM
    from its arrival on, and both twins read it there under unlike windows,
    128x4 and 32x4, 40% apart: this case fails on it all the same, by the
    windows). The eviction itself is held on the kept text of the parent's
    22-layer compile, the case below."""
    _, body = _scan_bodies(comps)
    by_name = {m.group(1): l for l in body
               for m in [re.match(r"\s*(?:ROOT )?%([\w.\-]+) =", l)] if m}
    twins = [l for l in body if _is_matmul(comps, l)
             and re.search(r"= \(?bf16\[(?!1,)\d+,2048,7168\]", l)]
    assert len(twins) == 2, [l[:80] for l in twins]
    windows = [re.search(r'"output_window_bounds":\[([^\]]*)\]', l).group(1)
               for l in twins]
    cycles = [int(_CYCLES.search(l).group(1)) for l in twins]
    evicted = [o for l in twins for o in _operands(l)
               if " copy-done(" in by_name.get(o, "")
               and any(" collective-permute-done(" in by_name.get(took, "")
                       for took in _operands(by_name[_operands(by_name[o])[0]]))]
    assert not evicted, f"EVICTED operands {evicted}: {windows} {cycles}"
    assert windows[0] == windows[1], (windows, cycles)
    assert max(cycles) <= 1.05 * min(cycles), (windows, cycles)


def _assert_head_rides_the_rings(comps):
    """The head's product is gate's and up's by shape, and since PR 61 it
    carries its exchanges as they do (`tp.gather_matmul_alone`). In the
    whole module: no `all-gather` yields `lm_head` whole over fsdp
    (`bf16[4096,16384]`: 67 MB over the link with nothing beside it, 1.44 ms
    a step) and no reduce-scatter of any spelling is left at its width (the
    partitioner's `all-reduce-scatter` fusion of the head's `[4096,16384]`
    gradient, 1.81 ms, blocking). In the computation that holds the two
    `while`s, between them: `lm_head`'s shard `bf16[2048,16384]` goes round
    the fsdp pairs ONCE a step, started behind the forward `while` (a
    permute spans no loop whose body holds permutes), the matmul fusion
    between its start and its done is the product by the rank's OWN shard
    over the whole sequence, 0.7 ms by XLA's estimate, and the first one
    behind the done takes the done; the backward sends no shard again (the
    arrived one is kept). The gradient's half for the neighbour,
    `bf16[1,2048,16384]`, is a product's result sent as it is, and its done
    stands before the backward `while` (`dx` is handed on behind it), with
    the kept half's product and a `dx` product between, 1.4 ms of matmul by
    XLA's estimate for 67 MB (the scheduler, left alone, keeps a permute
    open for the 0.9 ms IT gives the transfer, one `dx` product:
    `fsdp.weight_grads`, `alone`). An all-reduce, all-gather or all-to-all
    of the partitioner's that stands in that window waits on the link for
    what is still in flight (at the window's head the loss's sum over the
    chips waited 1.44 ms a step, PR 61's first tree): none stands there
    with less than those 1.4 ms of matmul behind the start."""
    forward, backward = _scan_bodies(comps)
    lines = [l for body in comps.values() for l in body]
    assert not [l for l in lines if " all-gather(" in l and "bf16[4096,16384]" in l]
    assert not [l for l in lines if "reduce-scatter" in l and "16384" in l]
    (main,) = [body for body in comps.values()
               if sum(" while(" in l for l in body) == 2]
    name = lambda l: re.match(r"\s*(?:ROOT )?%([\w.\-]+) =", l).group(1)
    body_of = lambda l: comps[re.search(r"body=%([\w.\-]+)", l).group(1)]
    whiles = {id(body_of(l)): i for i, l in enumerate(main) if " while(" in l}
    fwd_at, bwd_at = whiles[id(forward)], whiles[id(backward)]
    est_ms = lambda ls: sum(int(_CYCLES.search(l).group(1)) for l in ls
                            if _is_matmul(comps, l)) / 1.5e6

    def ring(shape):
        (start,) = [i for i, l in enumerate(main) if _FSDP_PAIRS in l
                    and " collective-permute-start(" in l and f"= ({shape}" in l]
        done = next(i for i, l in enumerate(main)
                    if f"collective-permute-done(%{name(main[start])})" in l)
        assert fwd_at < start < done < bwd_at, (shape, fwd_at, start, done, bwd_at)
        return start, done

    start, done = ring("bf16[2048,16384]")
    assert est_ms(main[start:done]) > 0.7, main[start:done]
    behind = next(l for l in main[done:] if _is_matmul(comps, l))
    assert name(main[done]) in _operands(behind), behind[:160]
    start, done = ring("bf16[1,2048,16384]")
    by_name = {name(l): l for l in main[:start] if re.match(r"\s*%", l)}
    sent = by_name[_operands(main[start])[0]]
    if " bitcast(" in sent:
        sent = by_name[_operands(sent)[0]]
    assert _is_matmul(comps, sent), sent[:160]
    assert est_ms(main[start:done]) > 1.4, main[start:done]
    for i in range(start, done):
        if re.search(r" all-(reduce|gather|to-all)(-start)?\(", main[i]):
            assert est_ms(main[start:i]) > 1.4, main[i][:160]


_CELL_STEP_ASSERTIONS = {
    "grad_exchange_behind_the_backward": _assert_grad_exchange_runs_behind_the_backward,
    "no_tp_all_reduce_in_the_layers": _assert_no_tp_all_reduce_in_the_layers,
    "tp_exchanges_behind_the_products": _assert_tp_exchanges_run_behind_the_products,
    "weights_ride_the_fsdp_ring": _assert_weights_ride_the_fsdp_ring,
    "no_all_reduce_in_the_layers": _assert_no_all_reduce_in_the_layers,
    "own_shard_first_in_the_backward": _assert_own_shard_first_in_the_backward,
    "dw_rings_taken_in_start_order": _assert_dw_rings_taken_in_start_order,
    "dw_twins_read_fast_memory": _assert_dw_twins_read_fast_memory,
    "head_rides_the_rings": _assert_head_rides_the_rings,
}
_TWO_LAYERS = {}  # the compiled text of one compile, for the cases below


@pytest.mark.parametrize("what", list(_CELL_STEP_ASSERTIONS))
def test_four_chip_cell_step_exchanges_behind_matmuls(topo, chip, what):
    """Two layers of the 4-chip cell's step (twenty seconds, compiled once
    for the nine cases; the whole 22 are the slow case below)."""
    from ray_tpu.train.step import default_optimizer

    if not _TWO_LAYERS:
        c = _lower_b1_step(topo, chips=4, mesh={"dp": 1, "fsdp": 2, "tp": 2},
                           batch=2, seq=SEQ, optimizer=default_optimizer(),
                           fused=False, cfg=dataclasses.replace(MISTRAL, n_layers=2)
                           ).compile()
        _TWO_LAYERS["comps"] = _computations(c.as_text())
    _CELL_STEP_ASSERTIONS[what](_TWO_LAYERS["comps"])


def _kept_text(name: str) -> str:
    """A 22-layer compile of the four-chip cell's step, cut to its scan
    bodies' products, permutes and copies (`ci/chip_calls/pr59/twins.py
    --excerpt`): what two layers do not reproduce, without a minute of
    compiling in tier-1."""
    with open(os.path.join(os.path.dirname(__file__), "compiled_text", name)) as f:
        return f.read()


@pytest.mark.parametrize("tree", ["parent_of_pr59", "pr59"])
def test_dw_twins_case_on_the_kept_22_layer_texts(tree):
    """PR 59's PARENT (commit 62ba75d): up's kept product reads
    `copy-done.102`, the eviction of `collective-permute-done.12`, under the
    window 32x10 where gate's has 64x7, half as many cycles again: the case
    fails, and names the operand. PR 59's own tree: it passes."""
    comps = _computations(_kept_text(f"four_chip_step_{tree}.txt"))
    if tree == "pr59":
        return _assert_dw_twins_read_fast_memory(comps)
    with pytest.raises(AssertionError, match=r"EVICTED operands \['copy-done.102'\]"
                       r".*720560"):
        _assert_dw_twins_read_fast_memory(comps)


def test_twins_reading_flags_the_evicted_operand():
    """`ci/chip_calls/pr59/twins.py` (no chip, no jax) on the same two kept
    texts: every matmul fusion of both scan bodies with its GFLOP, XLA's
    estimate, share of the bf16 peak, window and operands' memory spaces.
    The parent's backward body has ONE pair of equal products more than 10%
    apart, up's kept product 50% over gate's with an `EVICTED` operand; this
    tree's has none. The forward body's four gate / up products that write
    the saved result keep their spread in both (three at 0.218 ms, one at
    0.175: the float32 partial of the round before fits fast memory once)."""
    from ci.chip_calls.pr59 import twins

    parent, change = (twins.read(_kept_text(name)) for name in (
        "four_chip_step_parent_of_pr59.txt", "four_chip_step_pr59.txt"))
    (pair,) = parent["backward"]["apart"]
    assert [n for n, _ in pair] == ["constant_dynamic-update-slice_fusion.19",
                                    "constant_dynamic-update-slice_fusion.20"]
    assert pair[1][1] / pair[0][1] == pytest.approx(1.5, abs=0.01)
    rows = {r["name"]: r for r in parent["backward"]["products"]}
    slow = rows["constant_dynamic-update-slice_fusion.20"]
    assert slow["gflop"] == pytest.approx(60.13, abs=0.01)
    assert slow["window"] == ["32", "10"] and 0.63 < slow["peak_share"] < 0.64
    assert [o for o, _, gone in slow["operands"] if gone] == ["copy-done.102"]
    assert change["backward"]["apart"] == []
    assert not [o for r in change["backward"]["products"]
                for o, _, gone in r["operands"] if gone]
    kept = [r for r in change["backward"]["products"]
            if r["shape"] == "bf16[22,2048,7168]"]
    assert [r["window"] for r in kept] == [["64", "7"]] * 2
    assert parent["backward"]["matmul_ms"] - change["backward"]["matmul_ms"] \
        == pytest.approx(0.160, abs=0.005)
    for report in (parent, change):
        (four,) = report["forward"]["apart"]
        assert [t for _, t in four] == pytest.approx([0.175] + [0.218] * 3, abs=1e-3)


@pytest.mark.slow  # ten more seconds of five cores: see the note below
def test_one_chip_step_has_no_collective(topo, chip):
    """The 1-chip cell's step (fused blocks, two layers here): `fsdp` is 1,
    so the layer takes its weights as they are and the compiled program
    names no collective at all. (Tier-1 holds the same for the lowered
    program: `tests/test_engine_spans.py`, the one-device case.)"""
    from ray_tpu.train.step import default_optimizer

    c = _lower_b1_step(topo, chips=1, mesh={"dp": 1}, batch=2, seq=SEQ,
                       optimizer=default_optimizer(), fused=True,
                       cfg=dataclasses.replace(MISTRAL, n_layers=2)).compile()
    assert not _COLLECTIVE.search(c.as_text())
    assert _kernels(c) >= 4


@pytest.mark.slow
def test_four_chip_cell_step_compiles_and_fits(topo, chip):
    """The whole `mistral7b-train-4chip` step: the same two scan bodies, and
    `temp` within half a GB of the 7,835,362,816 B the partitioner's own
    reduction needed (PERF.md section 6, PR 30): the unreduced gradients in
    flight are one layer's."""
    from ray_tpu.train.step import default_optimizer

    c = _lower_b1_step(topo, chips=4, mesh={"dp": 1, "fsdp": 2, "tp": 2},
                       batch=2, seq=SEQ, optimizer=default_optimizer(),
                       fused=False, cfg=MISTRAL).compile()
    comps = _computations(c.as_text())
    for check in _CELL_STEP_ASSERTIONS.values():
        check(comps)
    assert c.memory_analysis().temp_size_in_bytes < 8.34e9


# The whole-program compiles below keep ~5 cores busy for ~10 s each, which
# starves the timing-sensitive runtime tests that share a tier-1 run: they
# are marked slow (`pytest -m slow tests/test_chip_compile.py`, ~40 s).
@pytest.mark.slow
@pytest.mark.parametrize("chips,mesh,fused", [
    (1, {"dp": 1}, True),                       # chip_smoke's one-chip step
    (4, {"dp": 1, "fsdp": 2, "tp": 2}, False),  # its four-chip step
])
def test_b1_train_step_compiles_and_fits(topo, chip, chips, mesh, fused):
    from ray_tpu.train.step import default_optimizer

    c = _lower_b1_step(topo, chips=chips, mesh=mesh, batch=2, seq=SEQ,
                       optimizer=default_optimizer(), fused=fused).compile()
    hlo = c.as_text()
    assert _kernels(c) >= 4  # flash fwd, dq, dkv + FFN K3 (fused) / under shard_map
    # a Mosaic kernel cannot be partitioned by the compiler: on a mesh the
    # flash kernel runs per device under shard_map, between collectives
    assert (hlo.count(" all-gather(") > 0) == (chips > 1)
    m = c.memory_analysis()
    # 1.14B params: f32 master + two moments + bf16 working copy, per chip
    assert m.argument_size_in_bytes < 9.5e9 / chips * 1.05


@pytest.mark.slow
@pytest.mark.parametrize("what", [
    # ROADMAP S3: FusedAdamW with PALLAS_LEAVES unlimited. Every leaf's kernel
    # is accepted; the program is refused for HBM, "Used 16.29G" (nu is f32).
    "fused_adamw_unlimited",
    # ROADMAP S3: b1 at seq 8192, batch 1: "Used 19.01G".
    "seq_8192",
])
def test_b1_step_the_compiler_refuses_for_hbm(topo, chip, monkeypatch, what):
    from ray_tpu.ops.pallas import adamw
    from ray_tpu.train.step import default_optimizer, fused_adamw_optimizer

    if what == "fused_adamw_unlimited":
        monkeypatch.setattr(adamw, "PALLAS_LEAVES", 10**9)
        lowered = _lower_b1_step(topo, chips=1, mesh={"dp": 1}, batch=2, seq=SEQ,
                                 optimizer=fused_adamw_optimizer(), fused=True)
    else:
        lowered = _lower_b1_step(topo, chips=1, mesh={"dp": 1}, batch=1, seq=8192,
                                 optimizer=default_optimizer(), fused=True)
    with pytest.raises(jax.errors.JaxRuntimeError,
                       match=r"RESOURCE_EXHAUSTED.*Used \d+\.\d+G of 15\.75G hbm"):
        lowered.compile()
