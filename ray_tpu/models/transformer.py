"""Flagship model: decoder-only transformer LM (Llama-3 family shapes).

Functional JAX, TPU-first:
  - parameters are a plain pytree with *logical axis* annotations
    (`param_logical_axes`) mapped to mesh axes by `ray_tpu.parallel.AxisRules`
    — dp/fsdp/tp shardings are data, not code;
  - layers are stacked on a leading axis and iterated with `lax.scan`
    (one compiled layer body regardless of depth — fast compiles, and
    `jax.checkpoint` on the body gives per-layer rematerialization);
  - bfloat16 activations/weights with fp32 RMSNorm statistics and fp32
    logits for the softmax-cross-entropy;
  - attention is the pallas flash kernel on TPU.

The reference has no model zoo of its own (it delegates to torch; SURVEY
§2.4) — this model is the equivalent of the torch models its Train/RLlib
examples wrap, built natively.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import (attention, attention_sharded,
                                   uses_flash_kernel)
from ray_tpu.ops.layers import apply_rotary, rms_norm, rotary_embedding, swiglu
from ray_tpu.parallel import fsdp, tp
from ray_tpu.parallel.mesh import DEFAULT_RULES


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 32768
    d_model: int = 2048
    n_layers: int = 16
    n_heads: int = 16
    n_kv_heads: int = 8
    d_ff: int = 8192
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: str = "full"          # "none" | "full" | "dots" (see maybe_remat)
    loss_chunk: int = 0          # >0: chunked cross-entropy (seq chunk size)
    tie_embeddings: bool = False
    # Mixture of Experts: n_experts > 0 replaces the dense FFN with a
    # top-2-gated MoE (ops/moe.py); experts shard over the "expert" axis.
    n_experts: int = 0
    capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # Fused FFN backward (ops/pallas/fused_ffn.py): the FFN block runs as a
    # custom_vjp with Pallas dW/dx kernels that fuse the swiglu/rmsnorm
    # chains into the matmuls; remat then covers only the attention half
    # (the block saves its own dots-policy-equivalent residuals, and a
    # custom_vjp inside jax.checkpoint would re-run its forward matmuls).
    # Dense-FFN path only.
    fused_ffn: bool = False
    # Fused attention backward (ops/pallas/fused_attn.py): the attention
    # half runs as a custom_vjp saving post-rotary q/k, v, the flash
    # output and its logsumexp, so the backward skips the rotary/transpose/
    # flash-forward recompute remat would do. Requires fused_ffn (the layer
    # then runs with no jax.checkpoint at all).
    fused_attn: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    # ---- presets ----
    @staticmethod
    def tiny() -> "ModelConfig":
        return ModelConfig(vocab_size=512, d_model=128, n_layers=2, n_heads=4,
                           n_kv_heads=2, d_ff=256, max_seq_len=256,
                           dtype=jnp.float32, remat="none")

    @staticmethod
    def b1() -> "ModelConfig":
        """~1.2B params: bench-scale for a single v5e chip."""
        return ModelConfig(vocab_size=32768, d_model=2048, n_layers=16,
                           n_heads=16, n_kv_heads=8, d_ff=8192)

    @staticmethod
    def tiny_moe() -> "ModelConfig":
        return ModelConfig(vocab_size=512, d_model=128, n_layers=2, n_heads=4,
                           n_kv_heads=2, d_ff=256, max_seq_len=256,
                           dtype=jnp.float32, remat="none", n_experts=4)

    @staticmethod
    def llama3_8b() -> "ModelConfig":
        """Llama-3-8B shapes (vocab rounded to a 128-multiple sharding unit)."""
        return ModelConfig(vocab_size=128256, d_model=4096, n_layers=32,
                           n_heads=32, n_kv_heads=8, d_ff=14336,
                           max_seq_len=8192)


# ---------------------------------------------------------------- params


def param_logical_axes(cfg: ModelConfig) -> Dict[str, Any]:
    """Logical axes per parameter leaf (layer-stacked leaves lead with
    'layers', which is never mesh-sharded)."""
    layers: Dict[str, Any] = {
        "attn_norm": ("layers", "embed_nosplit"),
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "heads"),
        "wv": ("layers", "embed", "heads"),
        "wo": ("layers", "heads", "embed"),
        "mlp_norm": ("layers", "embed_nosplit"),
    }
    if cfg.n_experts > 0:
        layers.update({
            "router": ("layers", "embed", None),
            "w_gate": ("layers", "expert", "embed", "mlp"),
            "w_up": ("layers", "expert", "embed", "mlp"),
            "w_down": ("layers", "expert", "mlp", "embed"),
        })
    else:
        layers.update({
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        })
    axes = {
        "embed": ("vocab", "embed"),
        "final_norm": ("embed_nosplit",),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def init_params(rng: jax.Array, cfg: ModelConfig) -> Dict[str, Any]:
    """Scaled-normal init; weights stored in cfg.dtype (bf16 master weights
    are avoided — the optimizer keeps fp32 state; see train.step)."""
    k_embed, k_layers, k_head = jax.random.split(rng, 3)
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    L = cfg.n_layers

    def norm_init(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(cfg.dtype)

    ks = jax.random.split(k_layers, 8)
    layers: Dict[str, Any] = {
        "attn_norm": jnp.ones((L, d), cfg.dtype),
        "wq": norm_init(ks[0], (L, d, nq * hd), d),
        "wk": norm_init(ks[1], (L, d, nkv * hd), d),
        "wv": norm_init(ks[2], (L, d, nkv * hd), d),
        "wo": norm_init(ks[3], (L, nq * hd, d), nq * hd),
        "mlp_norm": jnp.ones((L, d), cfg.dtype),
    }
    if cfg.n_experts > 0:
        E = cfg.n_experts
        layers.update({
            "router": (jax.random.normal(ks[7], (L, d, E), jnp.float32)
                       * 0.02).astype(cfg.dtype),
            "w_gate": norm_init(ks[4], (L, E, d, cfg.d_ff), d),
            "w_up": norm_init(ks[5], (L, E, d, cfg.d_ff), d),
            "w_down": norm_init(ks[6], (L, E, cfg.d_ff, d), cfg.d_ff),
        })
    else:
        layers.update({
            "w_gate": norm_init(ks[4], (L, d, cfg.d_ff), d),
            "w_up": norm_init(ks[5], (L, d, cfg.d_ff), d),
            "w_down": norm_init(ks[6], (L, cfg.d_ff, d), cfg.d_ff),
        })
    params: Dict[str, Any] = {
        "embed": (jax.random.normal(k_embed, (cfg.vocab_size, d), jnp.float32)
                  * 0.02).astype(cfg.dtype),
        "final_norm": jnp.ones((d,), cfg.dtype),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(k_head, (d, cfg.vocab_size),
                                               jnp.float32) * 0.02).astype(cfg.dtype)
    return params


def count_params(params: Any) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------- forward


def _deq(leaf: Any, dtype) -> Any:
    """Pass arrays through; dequantize `{"int8", "scale"}` leaves produced
    by `models.serving.quantize_model_params` (w8a16 serving: weights live
    in HBM as int8 + per-row fp32 scales; the cast happens on read, inside
    the scan body, so only one layer's bf16 copy is ever transient)."""
    if isinstance(leaf, dict) and "int8" in leaf:
        return (leaf["int8"].astype(jnp.float32) * leaf["scale"]).astype(dtype)
    return leaf


def _deq_tree(p: Dict[str, Any], dtype) -> Dict[str, Any]:
    return {k: _deq(v, dtype) for k, v in p.items()}


def _embed_lookup(emb: Any, tokens: jax.Array, dtype) -> jax.Array:
    """Token-embedding gather; for int8-quantized tables the gather happens
    in int8 (the bf16 [vocab, d] table never materializes)."""
    if isinstance(emb, dict) and "int8" in emb:
        return (emb["int8"][tokens].astype(jnp.float32)
                * emb["scale"][tokens]).astype(dtype)
    return emb[tokens].astype(dtype)


def _norm(x, scale, eps: float):
    """`rms_norm(x, scale)`; in training on a mesh the scale may come as an
    `fsdp.UnreducedScale` (each rank's own copy: `_exchanged_dims`)."""
    if isinstance(scale, fsdp.UnreducedScale):
        return scale.apply(functools.partial(rms_norm, eps=eps), x)
    return rms_norm(x, scale, eps)


def _project_qkv(cfg: ModelConfig, p, x, cos, sin, rows_mesh=None):
    """rmsnorm(x) -> q [b, s, heads, hd], k, v [b, s, kv_heads, hd], q and k
    rotated. The one spelling of the block's projections: training, prefill,
    the reference decode and the engine's decode step call it; what they do
    with q, k and v (the mixer) is their own. `rows_mesh`: the mesh over
    whose `tp` axis x's rows ride sharded (`_rows_mesh`), else None."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    h = _norm(x, p["attn_norm"], cfg.norm_eps)
    if rows_mesh is None:
        q, k, v = h @ p["wq"], h @ p["wk"], h @ p["wv"]
    else:  # one gather of the rows serves the three products
        q, k, v = tp.gather_matmul(h, (p["wq"], p["wk"], p["wv"]), rows_mesh)
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, s, cfg.n_kv_heads, hd)
    v = v.reshape(b, s, cfg.n_kv_heads, hd)
    q = apply_rotary(q, cos, sin)
    k = apply_rotary(k, cos, sin)
    return q, k, v


def _mlp(cfg: ModelConfig, p, x, rows_mesh=None):
    """rmsnorm(x) -> the FFN's output (no residual) and the experts'
    auxiliary loss (None for the dense FFN; only training reads it).
    `rows_mesh` as in `_project_qkv`."""
    h = _norm(x, p["mlp_norm"], cfg.norm_eps)
    if cfg.n_experts > 0:
        from ray_tpu.ops.moe import moe_ffn

        return moe_ffn(h, p["router"], p["w_gate"], p["w_up"], p["w_down"],
                       cfg.capacity_factor)
    if rows_mesh is None:
        return swiglu(h @ p["w_gate"], h @ p["w_up"]) @ p["w_down"], None
    # nothing between the gather and the scatter looks across rows: the
    # whole sequence stays a tuple of chunks, in each rank's own order
    gate, up = tp.gather_matmul(h, (p["w_gate"], p["w_up"]), rows_mesh,
                                chunks=True)
    return tp.matmul_scatter(tuple(map(swiglu, gate, up)), p["w_down"],
                             rows_mesh), None


def _attn_half(cfg: ModelConfig, mesh, x, p, cos, sin):
    """Attention sub-block: x + Wo(attn(rotary(qkv(rmsnorm(x)))))."""
    p = _deq_tree(p, cfg.dtype)
    b, s, _ = x.shape
    rows_mesh = _rows_mesh(cfg, mesh, b, s)
    q, k, v = _project_qkv(cfg, p, x, cos, sin, rows_mesh)
    # [b, heads, s, hd]
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    if mesh is not None and mesh.size > 1 and uses_flash_kernel(q):
        attn = attention_sharded(mesh, q, k, v, causal=True)
    else:
        attn = attention(q, k, v, causal=True)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.head_dim)
    out = (attn @ p["wo"] if rows_mesh is None
           else tp.matmul_scatter(attn, p["wo"], rows_mesh))
    return x + out.astype(x.dtype)


def _layer(cfg: ModelConfig, mesh, x, layer_params, cos, sin):
    """One transformer block. x: [b, s, d]."""
    x = _attn_half(cfg, mesh, x, layer_params, cos, sin)
    out, aux = _mlp(cfg, _deq_tree(layer_params, cfg.dtype), x,
                    _rows_mesh(cfg, mesh, *x.shape[:2]))
    return (x + out.astype(x.dtype),
            jnp.zeros((), jnp.float32) if aux is None else aux)


_NORM_SCALES = ("attn_norm", "mlp_norm")  # a layer's replicated leaves


def _exchanged_dims(cfg: ModelConfig, mesh, batch: int) -> Dict[str, int]:
    """The weights of one layer whose gradient's reduction over `fsdp` the
    program spells itself (parallel/fsdp.py) and does not leave to the
    partitioner, each with the dimension the rules shard over `fsdp` (the
    stacked leaf's, less the leading `layers`): every sharded one of the
    plain dense block, on a mesh whose `fsdp` axis is larger than 1 and
    whose (dp, fsdp) split the batch evenly. The expert layer (its `expert`
    axis IS `fsdp`) and the fused blocks (one chip only) keep the
    partitioner's program: none, as without a mesh."""
    if (fsdp.axis_size(mesh) == 1 or cfg.n_experts or cfg.fused_ffn
            or batch % math.prod(fsdp.batch_split(mesh))):
        return {}
    dims = {k: fsdp.sharded_dim(DEFAULT_RULES.spec(axes))
            for k, axes in param_logical_axes(cfg)["layers"].items()}
    return {k: d - 1 for k, d in dims.items() if d is not None}


def _rows_mesh(cfg: ModelConfig, mesh, batch: int, seq: int):
    """The mesh, where the residual stream's rows ride sharded over its `tp`
    axis between the block's products and each product carries its gather
    or scatter as ring permutes behind it (parallel/tp.py); else None, and
    the `tp` reductions are the partitioner's all-reduces: without a mesh or
    with `tp` 1, where `tp` does not divide the sequence, and wherever the
    layer's weights do not come exchanged (`_exchanged_dims`: fsdp 1, the
    expert layer, the fused blocks). The head's product follows the layers'
    (`head_exchanged`). The only form read on the chip is the
    one whose products carry the weights' shards round fsdp's ring as well;
    the rows over `tp` alone read no faster than the partitioner's program
    (PERF.md section 6, PR 38)."""
    if (tp.axis_size(mesh) == 1 or seq % tp.axis_size(mesh)
            or not _exchanged_dims(cfg, mesh, batch)):
        return None
    return mesh


def tp_exchanges_per_layer(cfg: ModelConfig, mesh, batch: int, seq: int) -> int:
    """How many gathers and scatters over `tp` a layer's forward moves as
    ring permutes behind its products: 4 (before qkv, behind `wo`, before
    gate | up, behind `w_down`) where `_rows_mesh` says so, else 0 (the
    train step's `xla.compile` spans carry it)."""
    return 0 if _rows_mesh(cfg, mesh, batch, seq) is None else 4


def ring_products_own_first(cfg: ModelConfig, mesh, batch: int, seq: int) -> int:
    """How many of a layer's products by a weight whose shards ride fsdp's
    ring have their order pinned, the rank's own shard's product before the
    arrived shard's: 1 where `_rows_mesh` says the products are
    parallel/tp.py's (the FFN's `w_down` in the backward, the first thing a
    layer's backward can run: `tp._matmul_scatter_bwd`), else 0 (the train
    step's `xla.compile` spans carry it)."""
    return 0 if _rows_mesh(cfg, mesh, batch, seq) is None else 1


def dw_rings_ordered(cfg: ModelConfig, mesh, batch: int, seq: int) -> int:
    """1 where a layer's weight-gradient rings are taken off the `fsdp` link
    in the order of their starts, each ring's kept product between
    (`fsdp.RingOrder`, handed from product to product): where `_rows_mesh`
    says the products are parallel/tp.py's, else 0 (the train step's
    `xla.compile` spans carry it)."""
    return 0 if _rows_mesh(cfg, mesh, batch, seq) is None else 1


# the head [d, vocab]'s dimension that the rules shard over `fsdp`
_HEAD_DIM = fsdp.sharded_dim(DEFAULT_RULES.spec(("embed", "vocab")))


def head_exchanged(cfg: ModelConfig, mesh, batch: int, seq: int) -> int:
    """1 where the head's product carries its exchanges as the layers'
    products do (the rows' other `tp` chunks and `lm_head`'s other `fsdp`
    shards arrive by permutes behind its own matmuls, its gradient leaves by
    fsdp.py's ring: `tp.gather_matmul_alone`): where `_rows_mesh` says the
    features come with their rows over `tp`; else 0, the partitioner's
    program, as under `loss_chunk` (the train step's `xla.compile` spans
    carry it)."""
    return int(_rows_mesh(cfg, mesh, batch, seq) is not None
               and not cfg.loss_chunk)


def grad_exchanges_per_layer(cfg: ModelConfig, mesh, batch: int) -> int:
    """How many of a layer's weight gradients this program exchanges over
    `fsdp` itself: 7 for the dense block on a mesh with fsdp > 1, else 0
    (the train step's `xla.compile` spans carry it)."""
    return len(_exchanged_dims(cfg, mesh, batch))


def norm_grad_reductions_in_layers(cfg: ModelConfig, mesh, batch: int) -> int:
    """How many of a layer's norm scales have their gradient summed over the
    ranks that split the residual's rows INSIDE the layers' backward, an
    all-reduce of the partitioner's on the compute stream: both on a mesh
    that splits the rows (`dp`, `fsdp`), but 0 where the layer's weights
    come exchanged (`_exchanged_dims`): there the scales ride once a rank
    (`fsdp.scale_by_rank`) and the sums are taken once a step behind the
    scan (the train step's `xla.compile` spans carry it)."""
    if mesh is None or _exchanged_dims(cfg, mesh, batch):
        return 0
    return len(_NORM_SCALES) if math.prod(fsdp.batch_split(mesh)) > 1 else 0


def maybe_remat(layer_fn, cfg: ModelConfig):
    """Wrap a layer body per cfg.remat: "full" recomputes everything in the
    backward pass; "dots" keeps matmul outputs resident and recomputes only
    the cheap elementwise/norm ops — most of full remat's memory win at a
    fraction of its recompute FLOPs."""
    if cfg.remat == "full":
        return jax.checkpoint(layer_fn)
    if cfg.remat == "dots":
        # (a product of parallel/tp.py is a matmul's result under its name)
        keep = jax.checkpoint_policies
        return jax.checkpoint(layer_fn, policy=keep.save_from_both_policies(
            keep.dots_with_no_batch_dims_saveable,
            keep.save_only_these_names(tp.SAVED)))
    if cfg.remat != "none":
        raise ValueError(f"unknown remat mode {cfg.remat!r}")
    return layer_fn


def lm_head_weights(params: Dict[str, Any], cfg: ModelConfig) -> jax.Array:
    """[d_model, vocab] output-projection weights in activation dtype."""
    head = (_deq(params["embed"], cfg.dtype).T if cfg.tie_embeddings
            else _deq(params["lm_head"], cfg.dtype))
    return head.astype(cfg.dtype)


# named scopes are metadata only: `forward` (here) and `head_loss` (loss_fn)
# name the phases of a train step in a device trace; the backward pass shows
# as their transposes, `transpose(jvp(forward))`
@jax.named_scope("forward")
def forward_features_with_aux(params: Dict[str, Any], tokens: jax.Array,
                              cfg: ModelConfig,
                              positions: Optional[jax.Array] = None, mesh=None):
    """tokens [b, s] -> (features [b, s, d] after final norm, moe_aux scalar).

    Pure sharding-annotation-driven SPMD, but for the sum of the dense
    block's weight gradients over `fsdp` (`_exchanged_dims`), its gathers
    and scatters over `tp` (`_rows_mesh`) and, wherever the first holds, the
    sums of its two norm scales' gradients over the ranks that split the
    rows, taken once a step behind the scan
    (`norm_grad_reductions_in_layers`): all three take `mesh` and without
    it are the partitioner's. Where `_rows_mesh` holds the features come as
    the layers leave them, rows over `tp`: the head's product takes them so
    (`_head_logits`).
    """
    if positions is None:
        positions = jnp.arange(tokens.shape[1])
    x = _embed_lookup(params["embed"], tokens, cfg.dtype)  # gather: [b, s, d]
    rows_mesh = _rows_mesh(cfg, mesh, *tokens.shape)
    if rows_mesh is not None:
        x = tp.shard_rows(x, rows_mesh)
    cos, sin = rotary_embedding(positions, cfg.head_dim, cfg.rope_theta)
    cos, sin = cos[None], sin[None]  # add batch dim

    layers = params["layers"]
    if cfg.fused_attn and not cfg.fused_ffn:
        raise ValueError("fused_attn requires fused_ffn")
    if cfg.fused_ffn:
        if cfg.n_experts > 0:
            raise ValueError("fused_ffn supports the dense path only")
        from ray_tpu.ops.pallas.fused_ffn import ffn_block

        if cfg.fused_attn:
            from ray_tpu.ops.pallas.fused_attn import attn_block

            def attn_fn(x, lp, cos, sin):
                return attn_block(x, lp["attn_norm"], lp["wq"], lp["wk"],
                                  lp["wv"], lp["wo"], cos, sin, cfg.n_heads,
                                  cfg.n_kv_heads, cfg.norm_eps)
        else:
            attn_fn = maybe_remat(functools.partial(_attn_half, cfg, mesh), cfg)

        def body(carry, lp):
            x, aux = carry
            x = attn_fn(x, lp, cos, sin)
            x = ffn_block(x, lp["mlp_norm"], lp["w_gate"], lp["w_up"],
                          lp["w_down"], cfg.norm_eps)
            return (x, aux), None
    else:
        layer = functools.partial(_layer, cfg, mesh)
        dims = _exchanged_dims(cfg, mesh, x.shape[0])
        if dims:
            # the norm scales ride once a rank of the axes that split x's
            # rows, so their gradients leave the scan as partial sums
            seq_axis = None if rows_mesh is None else tp.AXIS
            layers = {k: fsdp.scale_by_rank(v, mesh, seq_axis)
                      if k in _NORM_SCALES else v for k, v in layers.items()}

            def layer(x, lp, cos, sin):
                # (one a trace of the layer: its products hand it on)
                order = None if rows_mesh is None else fsdp.RingOrder()
                lp = {k: fsdp.ExchangedWeight(v, dims[k], mesh, order) if k in dims
                      else fsdp.UnreducedScale(v, mesh, seq_axis)
                      if k in _NORM_SCALES else v for k, v in lp.items()}
                return _layer(cfg, mesh, x, lp, cos, sin)
        layer_fn = maybe_remat(layer, cfg)

        def body(carry, lp):
            x, aux = carry
            x, layer_aux = layer_fn(x, lp, cos, sin)
            return (x, aux + layer_aux), None

    (x, aux_total), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), layers)
    return rms_norm(x, params["final_norm"], cfg.norm_eps), aux_total


def _head_logits(params: Dict[str, Any], x: jax.Array, cfg: ModelConfig, mesh):
    """features [b, s, d] -> logits [b, s, vocab] in float32. Where the
    layers' products carry their exchanges (`_rows_mesh`: x comes with its
    rows over `tp`) the head's does too: it is gate's and up's by shape."""
    head = lm_head_weights(params, cfg)
    if not head_exchanged(cfg, mesh, *x.shape[:2]):
        return (x @ head).astype(jnp.float32)
    w = fsdp.ExchangedWeight(head, _HEAD_DIM, mesh)
    return tp.gather_matmul_alone(x, w, mesh).astype(jnp.float32)


def forward_with_aux(params: Dict[str, Any], tokens: jax.Array, cfg: ModelConfig,
                     positions: Optional[jax.Array] = None, mesh=None):
    """tokens [b, s] -> (logits [b, s, vocab] fp32, moe_aux_loss scalar)."""
    x, aux_total = forward_features_with_aux(params, tokens, cfg, positions, mesh)
    return _head_logits(params, x, cfg, mesh), aux_total


def forward(params: Dict[str, Any], tokens: jax.Array, cfg: ModelConfig,
            positions: Optional[jax.Array] = None, mesh=None) -> jax.Array:
    return forward_with_aux(params, tokens, cfg, positions, mesh)[0]


def split_batch(batch: Dict[str, jax.Array]):
    """Normalize a batch to (inputs, targets, mask): accepts pre-shifted
    {"inputs", "targets"} or {"tokens": [b, s+1]}, optional "loss_mask"."""
    if "inputs" in batch:
        return batch["inputs"], batch["targets"], batch.get("loss_mask")
    tokens = batch["tokens"]
    mask = batch.get("loss_mask")
    return tokens[:, :-1], tokens[:, 1:], (None if mask is None else mask[:, 1:])


def token_nll(logits: jax.Array, targets: jax.Array,
              mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token NLL over [..., s, vocab] logits / [..., s] targets,
    masked if a [..., s] mask is given."""
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    target_logit = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - target_logit
    if mask is not None:
        maskf = mask.astype(jnp.float32)
        return jnp.sum(nll * maskf) / jnp.maximum(jnp.sum(maskf), 1.0)
    return jnp.mean(nll)


def chunked_token_nll(x: jax.Array, head: jax.Array, targets: jax.Array,
                      mask: Optional[jax.Array], chunk: int) -> jax.Array:
    """Mean NLL without materializing the full [b, s, vocab] fp32 logits.

    Scans the sequence in `chunk`-sized pieces; each piece's lm-head matmul
    + softmax runs under jax.checkpoint, so the backward pass recomputes a
    [b, chunk, vocab] tile at a time instead of holding ~b*s*vocab*4 bytes
    of logits (2+ GiB at 8x2048x32k) resident. The lm-head recompute is
    ~2dV/token extra FLOPs — under 10% of the model forward — traded for
    the HBM working set, which is what lets bigger batches fit.
    """
    b, s, d = x.shape
    n_chunks = s // chunk
    assert n_chunks * chunk == s, (s, chunk)
    xs = x.reshape(b, n_chunks, chunk, d).swapaxes(0, 1)      # [nc, b, c, d]
    ts = targets.reshape(b, n_chunks, chunk).swapaxes(0, 1)   # [nc, b, c]
    maskf = (mask.astype(jnp.float32) if mask is not None
             else jnp.ones_like(targets, jnp.float32))
    ms = maskf.reshape(b, n_chunks, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def chunk_nll(x_c, t_c, m_c):
        logits = (x_c @ head).astype(jnp.float32)             # [b, c, V]
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, t_c[..., None], axis=-1)[..., 0]
        return ((logz - tgt) * m_c).sum()

    def body(acc, xs_t):
        x_c, t_c, m_c = xs_t
        return acc + chunk_nll(x_c, t_c, m_c), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ts, ms))
    return total / jnp.maximum(maskf.sum(), 1.0)


def loss_fn(params: Dict[str, Any], batch: Dict[str, jax.Array],
            cfg: ModelConfig, mesh=None):
    """Next-token cross entropy.

    batch: either {"tokens": [b, s+1]} (shifted here) or pre-shifted
    {"inputs": [b, s], "targets": [b, s]}. Optional {"loss_mask": [b, s]}.
    """
    inputs, targets, mask = split_batch(batch)
    if cfg.loss_chunk and targets.shape[-1] % cfg.loss_chunk != 0:
        raise ValueError(
            f"loss_chunk={cfg.loss_chunk} must divide the target length "
            f"{targets.shape[-1]} (note {{'tokens'}} batches lose one "
            f"position to the shift)")
    x, moe_aux = forward_features_with_aux(params, inputs, cfg, mesh=mesh)
    with jax.named_scope("head_loss"):
        if cfg.loss_chunk:
            loss = chunked_token_nll(x, lm_head_weights(params, cfg),
                                     targets, mask, cfg.loss_chunk)
        else:
            loss = token_nll(_head_logits(params, x, cfg, mesh), targets, mask)
    if cfg.n_experts > 0:
        loss = loss + cfg.moe_aux_weight * moe_aux
    return loss, {"loss": loss, "ntokens": targets.size, "moe_aux": moe_aux}
