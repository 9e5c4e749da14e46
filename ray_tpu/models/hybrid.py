"""A decoder whose layers are of several kinds. Every block is
`x += Mixer(RMSNorm(x)); x += FFN(RMSNorm(x))` over a float32 residual
stream, or with `sandwich_norm` `x += RMSNorm(Mixer(RMSNorm(x)))` and the
same around the FFN: four norms a layer. The mixers: KDA linear attention
(`ops/kda.py`), latent attention (`ops/mla.py`) in two kinds, without
positions and with a full-rank query (Kimi-Linear's) or rotary with a
low-rank query (`rope_theta`, `q_lora_rank`: openPangu-Ultra-MoE's),
Mamba-1's selective scan (`ops/mamba.py`), Mamba-2's SSD recurrence
(`ops/ssd.py`: a scalar decay a head over a matrix state, one group, a gated
RMSNorm over all channels behind it; Granite-4.0-H's), plain grouped-query
attention without positions, EVA attention (`ops/eva.py`: the query's own
window of `eva_window` positions exactly, beside one learned summary a chunk
of `eva_chunk` positions of every earlier window; EvaByte's) and learned
sparse attention (`ops/dsa.py`: rotary grouped-query attention with an
RMSNorm a head on q and k, whose every query attends to the `dsa_topk` rows
an indexer of `dsa_heads` small heads scores highest, exactly;
Keye-VL-2.0's), and sliding-window beside full attention (grouped-query,
a window layer rotary under a band of `swa_window` positions, a full layer
without positions over every row; Command A+'s and SmallThinker's);
the FFNs: dense SwiGLU and a dropless top-k expert layer
(`ops/moe.py:dropless_moe`) that is told which experts it holds and how
their gate is activated (`gate_act`), beside a shared MLP or (`n_shared` 0)
none, routed by sigmoid scores + bias or by a softmax over the chosen
logits (`router`).

A list-form configuration with `n_predict` 1 carries a multi-token
prediction module (DeepSeek-V3's form): `h' = W_p [RMSNorm(h_i) ;
RMSNorm(Emb(t_(i+1)))]`, one expert-layer block with latent rows of its
own, a final norm, the main model's head: logits for t_(i+2). The decode
step runs it as a self-drafter: it verifies `[last token, draft]` through
the main layers, keeps one or two tokens, and drafts the next.

Two ways to hold and run the stack, by what the configuration lists:

- `kda_layers` (the Kimi-Linear family: KDA and MLA mixers, a leading dense
  layer, then expert layers; untied head): the layers are a LIST of
  per-layer dicts, `params["layers"]`, and the stack is unrolled (nine
  layers in the benchmark's cut); per-layer leaves let the decode step
  donate and rewrite each layer's state in place. Each layer is two calls
  of a jitted body chosen by KIND ("the list form's layer bodies", below):
  a program traces and lowers one body a kind, not one a layer.
- `mamba_layers` / `mamba2_layers` / `attn_layers` (the Jamba and Granite
  families: Mamba-1 or Mamba-2 and attention mixers, each over a dense FFN
  or an expert layer, head tied to the embedding): 28 layers unrolled would
  compile for minutes, and ten expert layers are past what the list form's
  nine compile in, so the stack is held and run as RUNS of like layers
  (alike in mixer AND FFN), `params["runs"]`, each run one `lax.scan` over
  weights stacked on a leading axis: a run of expert layers stacks its
  router, held experts and shared MLP like the rest. A run's recurrent
  state is stacked the same way and travels through the decode step's scan
  as a CARRY that each layer reads and rewrites in place (a scan that took
  it as xs and gave it back as ys would hold it twice); what an expert
  layer counts and chooses comes back as the scan's ys. Four scalars
  belong to this form (`embed_scale`, `residual_scale`, `attn_scale`,
  `logit_divisor`): x_0 = emb E[token]; x += res Mixer(.), x += res FFN(.);
  softmax(att q . k); logits = RMSNorm(x) E^T / lsc.
- `eva_layers` (the EvaByte family: every mixer EVA attention with rotary
  positions, dense FFNs, norms that scale by 1 + w, an untied head of
  `n_pred_heads` x vocabulary columns of which serving reads the first
  vocabulary's): the runs form with ONE run. Its prompt pass walks the
  sequence a WINDOW at a time, every window through all layers (a layer of
  window w needs only the summaries the same layer left for the windows
  before w), so its activations are those of `eva_window` positions
  whatever the prompt's length.
- `dsa_layers` (the Keye-VL-2.0 family: every mixer sparse attention over a
  scanned expert layer without a shared MLP, an untied head,
  `untied_head`): the runs form with ONE run. A prompt pass of more than
  `dsa_topk` positions scores, selects and attends by two kernels that never
  hold an [n, n] score in HBM; its prompts come in whole chunks.
- `swa_layers` / `full_layers` (the window form, the fifth: window and full
  attention mixers in any order, 3 : 1 in both models that have it, each
  over a scanned expert layer): the runs form with a run a stretch of like
  layers. What the window form owns is the cache, the walk and the ring; the
  BLOCK is the configuration's (`swa_block`, `swa_norm`, `swa_rotary`,
  `route_from`, `gate_act`). Command A+'s, the defaults: ONE LayerNorm a
  layer (mean-centred, no bias) whose rows mixer and FFN both read, `x +=
  Attn(h) + FFN(h)`, sigmoid scores beside the MEAN of the shared experts,
  interleaved rotary pairs, the head tied. SmallThinker's: the sequential
  RMSNorm block of every other form, `x += Attn(h); x += FFN(RMSNorm(x))`,
  whose router reads the ATTENTION's input h (`route_from` "mixer": the
  route is made before the attention and used behind it), sparse ReGLU
  experts and no shared one, half-rotation rotary, an untied head. Its
  prompt pass walks the sequence a CHUNK of one window at a time, every
  chunk through all layers, the rows the chunks leave as its carry
  (`_sequence_swa`); every prompt past a window passes by ONE program whose
  loop walks the chunks that hold a token.

Three call modes over the same weights:

`forward`       whole sequence -> logits (tests, offline scoring);
`prefill`       a right-padded bucket [nb, s] with true lengths -> logits at
                the last true position and the state each request leaves:
                per KDA layer S [nb, H, dk, dv] float32 and the convolution
                tail [nb, K-1, 3 H dk]; per MLA layer the latent rows
                [nb, s, rank + rope]. Positions past `true_len` leave S and
                the tail untouched;
`decode_step`   one token for every slot from the slots' state, greedy
                sampling on device, state DONATED and rewritten in place.

A slot's state is of five kinds. An attention keeps a row a position for
ever (K/V, or a latent row); a recurrent mixer keeps a state of fixed size
(KDA's S [H, dk, dv], Mamba-1's [d_state, d_inner], 0.33 MB a layer at
Jamba's widths, Mamba-2's [N, H P], a matrix a head, 4.19 MB a layer at
Granite's; each beside the last K-1 inputs of its convolution);
EVA keeps a table of two regions: the open window's K/V rows, which the
slot REUSES every `eva_window` positions (row n % W; stale rows are masked
by the length, never cleared), and a summary a closed chunk, a row every
`eva_chunk` positions, of which a query sees those of closed windows only.
Sparse attention keeps TWO rows a position for ever: the K/V block and,
beside it, a key for the selector (the indexer) that decides which K/V
blocks a later query reads: a decode step scores all n of a slot's indexer
keys and reads `dsa_topk` of its n K/V blocks. A stack of window and full
attention layers keeps rows that differ BY LAYER KIND: a full layer a row a
position for ever, a window layer a RING of `swa_window` rows (position n
at row n % W, written over the row that left), read ACROSS its wrap: the
min(n + 1, W) keys of the window whatever their places in the ring.

`HybridCache` (list form), `RunsCache`, `EvaCache`, `DsaCache` and `SwaCache` (runs form) are this
family's implementations of the engine's per-slot state interface
(`models/serving.py`, "the cache interface").
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import inspect
import threading
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import programs as programs_ahead
from ray_tpu.models.inference import _gqa_decode_attention
from ray_tpu.ops import dsa, eva, kda, mamba, mla, ssd
from ray_tpu.ops.attention import (attention, banded_attention,
                                   causal_attention_blocked)
from ray_tpu.ops.cache import write_rows
from ray_tpu.ops.layers import layer_norm, rms_norm, rotate_interleaved, swiglu
from ray_tpu.ops.moe import dropless_moe, route_softmax_top_k, route_top_k
from ray_tpu.ops.pallas import decode_attention, eva_decode
from ray_tpu.util import tracing

F32 = jnp.float32
_FFN_BLOCK = 2048     # tokens a scanned expert layer takes at a time (`_run_ffn`)


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 512
    d_model: int = 64
    n_layers: int = 4
    kda_layers: Tuple[int, ...] = (1, 2, 4)   # counted from 1; the rest are MLA
    first_dense: int = 1                      # leading layers with a dense FFN
    # KDA mixer
    kda_heads: int = 2
    kda_head_dim: int = 16
    conv_kernel: int = 4
    kda_rank: int = 16                        # low rank of the decay and gate
    kda_chunk: int = 64
    # MLA mixer
    n_heads: int = 2
    kv_lora_rank: int = 32
    qk_nope_dim: int = 16
    qk_rope_dim: int = 8
    v_head_dim: int = 16
    q_lora_rank: int = 0                      # 0: a full-rank query
    rope_theta: float = 0.0                   # 0: no positions
    sandwich_norm: bool = False               # a norm behind mixer and FFN too
    n_predict: int = 0                        # multi-token prediction modules
    # FFN
    d_ff: int = 128
    d_expert: int = 32
    n_experts: int = 8                        # the router's width
    experts_held: Tuple[int, ...] = tuple(range(8))
    top_k: int = 2
    n_shared: int = 1
    route_scale: float = 2.446
    renormalize: bool = True
    norm_eps: float = 1e-5
    dtype: Any = jnp.float32
    # the most tokens one prefill call takes (bounds its activations and the
    # number of (batch, bucket) programs): admission batches are split to it
    prefill_tokens: int = 4096
    # the runs form: Mamba-1 (Mamba-2: `mamba2_layers`, below) and
    # plain-attention mixers (layers counted from 1, as `kda_layers`); a
    # configuration that lists any is held and run as scanned runs, its FFNs
    # dense up to `first_dense` and expert layers past it, its head the
    # embedding
    mamba_layers: Tuple[int, ...] = ()
    attn_layers: Tuple[int, ...] = ()
    d_inner: int = 128
    d_state: int = 16
    dt_rank: int = 8
    mamba_chunk: int = 128
    n_kv_heads: int = 1
    head_dim: int = 16
    # EVA mixers (the runs form, one run: every layer is listed); they take
    # `rope_theta`, `n_heads` = `n_kv_heads` and `head_dim`
    eva_layers: Tuple[int, ...] = ()
    eva_window: int = 2048
    eva_chunk: int = 16
    n_pred_heads: int = 1                     # heads of vocab_size columns each
    norm_unit_offset: bool = False            # every RMSNorm scales by 1 + w
    # Mamba-2 (SSD) mixers (the runs form): `ssd_heads` heads of
    # `ssd_head_dim` channels over a state of `ssd_state` columns a channel,
    # ONE group (every head shares B and C); they take `conv_kernel`
    mamba2_layers: Tuple[int, ...] = ()
    ssd_heads: int = 4
    ssd_head_dim: int = 8
    ssd_state: int = 16
    ssd_chunk: int = 256
    # how an expert layer routes: "sigmoid" (`ops.moe.route_top_k`: score +
    # bias chooses, `route_scale`, `renormalize`) or "softmax" (top-k by
    # logit, a softmax over the chosen: `route_softmax_top_k`)
    router: str = "sigmoid"
    # four scalars of the runs form; each default leaves the lowered program
    # as it is without the field
    embed_scale: float = 1.0                  # x_0 = embed_scale * E[token]
    residual_scale: float = 1.0               # x += residual_scale * Mixer / FFN
    attn_scale: float = 0.0                   # softmax(attn_scale q . k); 0: hd^-1/2
    logit_divisor: float = 1.0                # logits = RMSNorm(x) E^T / logit_divisor
    # sparse-attention mixers (the runs form, one run: every layer is
    # listed): grouped-query attention (`n_heads`, `n_kv_heads`, `head_dim`,
    # `rope_theta` over the whole head, an RMSNorm a head on q and k) whose
    # queries attend to the `dsa_topk` rows an indexer of `dsa_heads` heads
    # of `dsa_head_dim` lanes scores highest (`ops/dsa.py`); the prompt pass
    # scores `dsa_chunk` queries at a time where no kernel runs
    dsa_layers: Tuple[int, ...] = ()
    dsa_topk: int = 2048
    dsa_heads: int = 16
    dsa_head_dim: int = 64
    dsa_chunk: int = 512
    untied_head: bool = False                 # the runs form: a head of its own
    # sliding-window and full attention mixers in ONE stack (the runs form's
    # window form: a configuration that lists `swa_layers` or `full_layers`
    # keeps a ring a window layer and a row a position a full layer, and its
    # prompt pass walks one window at a time): grouped-query attention
    # (`n_heads`, `n_kv_heads`, `head_dim`), the `swa_layers` rotary
    # (`rope_theta`) under a window of `swa_window` positions that counts
    # the query's own, the `full_layers` without positions over every row.
    # Every FFN is an expert layer (`router`: sigmoid scores without a bias,
    # or a softmax over the chosen logits)
    swa_layers: Tuple[int, ...] = ()
    full_layers: Tuple[int, ...] = ()
    swa_window: int = 4096
    # the window form's BLOCK, a model's own; each default is Command A+'s.
    # "parallel": ONE norm a layer that mixer and FFN both read, `x +=
    # Attn(h) + FFN(h)`, the shared MLP the MEAN of its `n_shared` experts;
    # "sequential": `x += Attn(N1(x)); x += FFN(N2(x))`, two norms a layer
    swa_block: str = "parallel"
    swa_norm: str = "layer"      # "layer": mean-centred, no bias; or "rms"
    # a window layer's rotary lanes: "interleaved" pairs (2i, 2i + 1) or
    # "half" (i, i + d/2; SmallThinker's by an ASSUMED reading of its
    # config, which seeded weights cannot tell from the other)
    swa_rotary: str = "interleaved"
    # what a sequential block's router reads: "ffn", the expert layer's own
    # normed input, or "mixer", the ATTENTION's normed input (the route is
    # made before the attention and used behind it; SmallThinker's)
    route_from: str = "ffn"
    # a routed expert's gate: silu (SwiGLU) or relu (ReGLU; the window
    # form's alone, and only where no shared MLP, which is SwiGLU, stands by)
    gate_act: str = "silu"

    def __post_init__(self):
        if self.gate_act != "silu" and not self.windowed:
            raise ValueError(f"gate_act {self.gate_act!r} is the window form's "
                             "routed experts'; every other FFN is SwiGLU")

    @staticmethod
    def tiny_hybrid() -> "HybridConfig":
        """dense layer + KDA, KDA, MLA, KDA; 8 experts, top-2, one shared."""
        return HybridConfig()

    @staticmethod
    def tiny_rotary() -> "HybridConfig":
        """dense layer + 2 expert layers, every mixer rotary MLA with a
        low-rank query, sandwich norms, one prediction module; a vocabulary
        small enough that a draft is sometimes right."""
        return HybridConfig(vocab_size=16, n_layers=3, kda_layers=(), n_heads=8,
                            q_lora_rank=24, rope_theta=1e4,
                            sandwich_norm=True, n_predict=1, route_scale=2.5)

    @staticmethod
    def tiny_runs() -> "HybridConfig":
        """Mamba x 2, attention, Mamba x 3, attention, Mamba: five runs."""
        return HybridConfig(n_layers=8, kda_layers=(), first_dense=8,
                            mamba_layers=(1, 2, 4, 5, 6, 8), attn_layers=(3, 7),
                            n_heads=4, norm_eps=1e-6, mamba_chunk=16)

    @staticmethod
    def tiny_granite() -> "HybridConfig":
        """Two periods of Mamba-2 x 3, attention; every FFN 8 experts of
        which the first 4 are held, top-3 by logit under a softmax over the
        chosen, beside a shared MLP of two experts' width; the four scalars
        away from 1."""
        return HybridConfig(n_layers=8, kda_layers=(), first_dense=0,
                            mamba2_layers=(1, 2, 3, 5, 6, 7), attn_layers=(4, 8),
                            n_heads=4, n_kv_heads=2, ssd_heads=8, ssd_head_dim=16,
                            ssd_chunk=16,
                            experts_held=(0, 1, 2, 3), top_k=3, n_shared=2,
                            router="softmax", embed_scale=3.0, residual_scale=0.5,
                            attn_scale=0.125, logit_divisor=4.0)

    @staticmethod
    def tiny_eva() -> "HybridConfig":
        """Three EVA layers, windows of 32 positions in chunks of 4, two
        prediction heads."""
        return HybridConfig(vocab_size=64, n_layers=3, kda_layers=(), first_dense=3,
                            eva_layers=(1, 2, 3), eva_window=32, eva_chunk=4,
                            n_heads=4, n_kv_heads=4, rope_theta=1e5,
                            n_pred_heads=2, norm_unit_offset=True)

    @staticmethod
    def tiny_dsa() -> "HybridConfig":
        """Three sparse-attention layers (8 query heads on 2 kv heads, an
        indexer of 4 heads of 8 lanes choosing 16 rows), every FFN 8 experts,
        top-2 by logit, NO shared MLP; an untied head."""
        return HybridConfig(vocab_size=96, n_layers=3, kda_layers=(), first_dense=0,
                            dsa_layers=(1, 2, 3), dsa_topk=16, dsa_heads=4,
                            dsa_head_dim=8, dsa_chunk=8, n_heads=8, n_kv_heads=2,
                            rope_theta=1e4, n_shared=0, router="softmax",
                            untied_head=True, norm_eps=1e-6)

    @staticmethod
    def tiny_swa() -> "HybridConfig":
        """Two periods of window x 3, full: a window of 8 positions, 8 query
        heads on 2 key heads, every FFN 8 experts (top-2 by sigmoid score,
        renormalised) beside the mean of 2 shared experts."""
        return HybridConfig(vocab_size=96, n_layers=8, kda_layers=(), first_dense=0,
                            swa_layers=(1, 2, 3, 5, 6, 7), full_layers=(4, 8),
                            swa_window=8, n_heads=8, n_kv_heads=2, rope_theta=5e4,
                            n_shared=2, route_scale=1.0)

    @staticmethod
    def tiny_smallthinker() -> "HybridConfig":
        """Two periods of full, window x 3 (the global layer FIRST): a window
        of 8 positions, 14 query heads on 2 key heads (a group of 7), the
        sequential RMSNorm block whose router reads the attention's input,
        8 ReGLU experts all held, top-3 by a softmax, no shared expert,
        half-rotation rotary, an untied head."""
        return HybridConfig(vocab_size=96, n_layers=8, kda_layers=(), first_dense=0,
                            swa_layers=(2, 3, 4, 6, 7, 8), full_layers=(1, 5),
                            swa_window=8, n_heads=14, n_kv_heads=2, rope_theta=1.5e6,
                            top_k=3, n_shared=0, router="softmax", norm_eps=1e-6,
                            untied_head=True, swa_block="sequential",
                            swa_norm="rms", swa_rotary="half",
                            route_from="mixer", gate_act="relu")

    def layer_kinds(self) -> Tuple[Tuple[str, str], ...]:
        def mixer(i):
            return ("kda" if i in self.kda_layers else
                    "mamba" if i in self.mamba_layers else
                    "mamba2" if i in self.mamba2_layers else
                    "attn" if i in self.attn_layers else
                    "eva" if i in self.eva_layers else
                    "dsa" if i in self.dsa_layers else
                    "swa" if i in self.swa_layers else
                    "full" if i in self.full_layers else "mla")
        return tuple((mixer(i), "dense" if i <= self.first_dense else "moe")
                     for i in range(1, self.n_layers + 1))

    @property
    def scanned(self) -> bool:
        return bool(self.mamba_layers or self.mamba2_layers or self.attn_layers
                    or self.eva_layers or self.dsa_layers or self.windowed)

    @property
    def windowed(self) -> bool:
        """A stack of window and full attention layers: the window form's
        cache, walk and ring, whatever the block."""
        return bool(self.swa_layers or self.full_layers)

    @property
    def parallel(self) -> bool:
        """The window form's parallel block (Command A+'s)."""
        return self.windowed and self.swa_block == "parallel"

    @property
    def layer_normed(self) -> bool:
        return self.windowed and self.swa_norm == "layer"

    @property
    def ssd_inner(self) -> int:
        return self.ssd_heads * self.ssd_head_dim

    def runs(self) -> Tuple[Tuple[str, int], ...]:
        """The runs form's stack: (mixer, how many like layers in a row). A
        run's layers are alike in mixer AND FFN (`run_ffns`)."""
        return tuple((mixer, k) for mixer, _, k in self._run_kinds())

    def run_ffns(self) -> Tuple[str, ...]:
        """"dense" or "moe" for each run of `runs()`."""
        return tuple(ffn for _, ffn, _ in self._run_kinds())

    def _run_kinds(self) -> Tuple[Tuple[str, str, int], ...]:
        kinds = self.layer_kinds()
        if self.eva_layers:
            # the prompt pass walks windows, each through the whole stack
            if set(kinds) != {("eva", "dense")} or self.n_kv_heads != self.n_heads \
                    or self.eva_window % self.eva_chunk:
                raise ValueError(
                    "EVA mixers make ONE run over dense FFNs, a key head a "
                    "query head, whole chunks a window; not "
                    f"{sorted(set(kinds))}, {self.n_heads}:{self.n_kv_heads} heads, "
                    f"{self.eva_window} / {self.eva_chunk}")
        elif self.dsa_layers:
            # one cache of `[k ; v]` blocks and indexer keys, one scan
            if len(set(kinds)) != 1 or kinds[0][0] != "dsa" or not self.rope_theta \
                    or self.n_heads % self.n_kv_heads:
                raise ValueError(
                    "sparse-attention (dsa) mixers make ONE run, every layer "
                    "over the same kind of FFN, with rotary positions and whole "
                    f"query groups; not {sorted(set(kinds))}, rope_theta "
                    f"{self.rope_theta}, {self.n_heads}:{self.n_kv_heads} heads")
        elif self.windowed:
            # two row caches (a ring a window layer, a row a position a full
            # layer) that the prompt pass walks a chunk at a time
            if not set(kinds) <= {("swa", "moe"), ("full", "moe")} \
                    or not self.rope_theta or self.n_heads % self.n_kv_heads \
                    or self.router not in ("sigmoid", "softmax"):
                raise ValueError(
                    "window (swa) and full attention mixers stand each over an "
                    "expert layer routed by sigmoid scores or a softmax, with "
                    "rotary positions and whole query groups; not "
                    f"{sorted(set(kinds))}, rope_theta {self.rope_theta}, "
                    f"{self.n_heads}:{self.n_kv_heads} heads, router {self.router}")
            form = (self.swa_block, self.swa_norm, self.swa_rotary, self.route_from,
                    self.gate_act)
            if self.swa_block not in ("parallel", "sequential") \
                    or self.swa_norm not in ("layer", "rms") \
                    or self.swa_rotary not in ("interleaved", "half") \
                    or self.route_from not in ("ffn", "mixer") \
                    or (self.parallel and self.route_from == "mixer") \
                    or self.gate_act not in ("silu", "relu") \
                    or (self.gate_act != "silu" and self.n_shared):
                raise ValueError(
                    "the window form's block is parallel or sequential, its norm "
                    "layer or rms, its rotary lanes interleaved or half, its "
                    "route read from the ffn's input or (sequential) the "
                    "mixer's, its routed experts' gate silu or (with no shared "
                    f"MLP, which is SwiGLU) relu; not {form}")
        elif any(m not in ("mamba", "mamba2", "attn") for m, _ in kinds) \
                or self.ssd_heads % 2:
            raise ValueError(
                "a stack of scanned runs holds Mamba-1, Mamba-2 (an even number "
                "of heads, one group) and attention mixers, each over a dense "
                "FFN or an expert layer, or ONE run of EVA or of "
                "sparse-attention (dsa) mixers, or window (swa) and full "
                f"attention mixers, not {sorted(set(kinds))} with "
                f"{self.ssd_heads} SSD heads")
        out: List[Tuple[str, str, int]] = []
        for kind in kinds:
            if out and out[-1][:2] == kind:
                out[-1] = kind + (out[-1][2] + 1,)
            else:
                out.append(kind + (1,))
        return tuple(out)

    @property
    def latent_width(self) -> int:
        """Lanes a latent row `[c, k_r]` is stored in: whole tiles of 128 (576
        values in 640). The cache then stays row-major on the TPU, where its
        row write and the decode's attention run as kernels over live rows."""
        return -(-(self.kv_lora_rank + self.qk_rope_dim) // 128) * 128

    def make_cache(self, num_slots: int, max_len: int):
        """This model's per-slot state for `ContinuousBatchingEngine`."""
        kind = EvaCache if self.eva_layers else DsaCache if self.dsa_layers \
            else SwaCache if self.windowed \
            else RunsCache if self.scanned else HybridCache
        return kind(self, num_slots, max_len)


# ---------------------------------------------------------------- params


def init_params(rng: jax.Array, cfg: HybridConfig) -> Dict[str, Any]:
    """Scaled-normal weights in cfg.dtype; small NON-ZERO values for the
    router's correction bias, `A_log` and `dt_bias` (fla's ranges: A in
    [1, 16], softplus(dt_bias) in [0.001, 0.1]), so that leaving one of
    them out of the computation changes the result. Pure: jit it to build a
    large model in one program. The runs form: `_init_runs`."""
    if cfg.scanned:
        return _init_runs(rng, cfg)
    d, dt = cfg.d_model, cfg.dtype
    H, dk, r = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_rank
    counter = iter(range(1 << 20))

    def w(shape, fan_in):
        key = jax.random.fold_in(rng, next(counter))
        return (jax.random.normal(key, shape, F32) * fan_in ** -0.5).astype(dt)

    def uniform(shape, lo, hi):
        key = jax.random.fold_in(rng, next(counter))
        return jax.random.uniform(key, shape, F32, lo, hi)

    def swiglu_w(width, lead=()):
        return {"w_gate": w(lead + (d, width), d), "w_up": w(lead + (d, width), d),
                "w_down": w(lead + (width, d), width)}

    def layer(mixer, ffn):
        p: Dict[str, Any] = {"mixer_norm": jnp.ones((d,), dt),
                             "ffn_norm": jnp.ones((d,), dt)}
        if cfg.sandwich_norm:
            p["mixer_post_norm"] = jnp.ones((d,), dt)
            p["ffn_post_norm"] = jnp.ones((d,), dt)
        if mixer == "kda":
            step = jnp.exp(uniform((H * dk,), np.log(1e-3), np.log(1e-1)))
            p["kda"] = {
                "w_qkv": w((d, 3 * H * dk), d),
                "conv": w((cfg.conv_kernel, 3 * H * dk), cfg.conv_kernel),
                "w_f1": w((d, r), d), "w_f2": w((r, H * dk), r),
                "A_log": jnp.log(uniform((H,), 1.0, 16.0)),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
                "w_b": w((d, H), d),
                "w_g1": w((d, r), d), "w_g2": w((r, H * dk), r),
                "o_norm": jnp.ones((dk,), dt), "wo": w((H * dk, d), H * dk)}
        else:
            nh, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                              cfg.v_head_dim)
            qr = cfg.q_lora_rank
            query = {"wq": w((d, nh * (dn + dr)), d)} if not qr else {
                "w_qa": w((d, qr), d), "q_norm": jnp.ones((qr,), dt),
                "w_qb": w((qr, nh * (dn + dr)), qr)}
            p["mla"] = {
                **query,
                "w_kva": w((d, cfg.kv_lora_rank + dr), d),
                "kv_norm": jnp.ones((cfg.kv_lora_rank,), dt),
                "w_kvb": w((cfg.kv_lora_rank, nh * (dn + dv)), cfg.kv_lora_rank),
                "wo": w((nh * dv, d), nh * dv)}
        if ffn == "dense":
            p["ffn"] = swiglu_w(cfg.d_ff)
        else:
            p["moe"] = {
                "router": w((d, cfg.n_experts), d),
                "bias": uniform((cfg.n_experts,), -0.05, 0.05),
                **swiglu_w(cfg.d_expert, (len(cfg.experts_held),))}
            if cfg.n_shared:
                p["moe"]["shared"] = swiglu_w(cfg.d_expert * cfg.n_shared)
        return p

    layers = [layer(mixer, ffn) for mixer, ffn in cfg.layer_kinds()]
    params = {
        "embed": (jax.random.normal(jax.random.fold_in(rng, next(counter)),
                                    (cfg.vocab_size, d), F32) * 0.02).astype(dt),
        "final_norm": jnp.ones((d,), dt), "layers": layers,
        "lm_head": (jax.random.normal(jax.random.fold_in(rng, next(counter)),
                                      (d, cfg.vocab_size), F32) * 0.02).astype(dt)}
    if cfg.n_predict:
        if cfg.n_predict != 1 or cfg.kda_layers:
            raise ValueError("one prediction module, over MLA layers only")
        # embedding and head are the main model's
        params["mtp"] = {"h_norm": jnp.ones((d,), dt), "e_norm": jnp.ones((d,), dt),
                         "proj": w((2 * d, d), 2 * d), "layer": layer("mla", "moe"),
                         "final_norm": jnp.ones((d,), dt)}
    return params


def _init_runs(rng: jax.Array, cfg: HybridConfig) -> Dict[str, Any]:
    """The runs form's weights: one dict per run, every leaf stacked on a
    leading axis of the run's length (made stacked: no copy of a 3B model
    is ever stacked from per-layer pieces). Mamba's published initial
    ranges, all non-zero so that leaving one out changes the result:
    `A_log` = log(1..d_state) per state column, `dt_bias` the inverse
    softplus of exp(uniform[ln 1e-3, ln 1e-1]), `D` = 1 + noise, the
    convolution's bias uniform in +-K^-1/2; the three inner norms ones. No
    `lm_head`: the head is the embedding. An EVA run: `phi` and `mu`, the
    two vectors a head that make a chunk's summary, uniform in +-1 and
    +-0.5 (non-zero, of the keys' own size, so that dropping either is an
    error a test sees), norm weights around zero (they scale by 1 + w) and
    an untied `lm_head` [d, n_pred_heads x vocab_size], head j's columns at
    [j vocab_size, (j + 1) vocab_size)."""
    d, dt = cfg.d_model, cfg.dtype
    di, n, r, K = cfg.d_inner, cfg.d_state, cfg.dt_rank, cfg.conv_kernel
    counter = iter(range(1 << 20))

    def key():
        return jax.random.fold_in(rng, next(counter))

    def w(shape, fan_in):
        return (jax.random.normal(key(), shape, F32) * fan_in ** -0.5).astype(dt)

    def norm(shape):
        if cfg.windowed:   # around 1: a norm that drops its weight shows
            return (1.0 + 0.1 * jax.random.normal(key(), shape, F32)).astype(dt)
        if not cfg.norm_unit_offset:
            return jnp.ones(shape, dt)
        return jax.random.uniform(key(), shape, F32, -0.1, 0.1).astype(dt)

    def swiglu_w(width, lead):
        return {"w_gate": w(lead + (d, width), d), "w_up": w(lead + (d, width), d),
                "w_down": w(lead + (width, d), width)}

    runs: List[Dict[str, Any]] = []
    for (mixer, k), ffn in zip(cfg.runs(), cfg.run_ffns()):
        p: Dict[str, Any] = {"mixer_norm": norm((k, d))}
        if not cfg.parallel:   # whose ONE norm a layer both halves read
            p["ffn_norm"] = norm((k, d))
        if ffn == "dense":
            p["ffn"] = swiglu_w(cfg.d_ff, (k,))
        else:
            if cfg.router != "softmax" and not cfg.windowed:
                raise ValueError("a scanned expert layer routes by a softmax "
                                 "over the chosen logits, or (window and full "
                                 "attention mixers) by sigmoid scores without "
                                 "a bias")
            p["moe"] = {"router": w((k, d, cfg.n_experts), d),
                        **swiglu_w(cfg.d_expert, (k, len(cfg.experts_held)))}
            if cfg.n_shared:
                p["moe"]["shared"] = swiglu_w(cfg.d_expert * cfg.n_shared, (k,))
        if mixer == "mamba2":
            H, ch = cfg.ssd_heads, cfg.ssd_inner + 2 * cfg.ssd_state
            step = jnp.exp(jax.random.uniform(key(), (k, H), F32,
                                              np.log(1e-3), np.log(1e-1)))
            p["mamba2"] = {
                # [z | x B C]; dt's H columns of the published in-projection
                # are a matrix of their own, for its float32 sums
                "w_in": w((k, d, cfg.ssd_inner + ch), d), "w_dt": w((k, d, H), d),
                "conv": w((k, K, ch), K),
                "conv_bias": jax.random.uniform(key(), (k, ch), F32,
                                                -K ** -0.5, K ** -0.5),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
                "A_log": jnp.log(jax.random.uniform(key(), (k, H), F32, 1.0, 16.0)),
                "D": 1.0 + 0.1 * jax.random.normal(key(), (k, H), F32),
                "norm": jnp.ones((k, cfg.ssd_inner), dt),
                "w_out": w((k, cfg.ssd_inner, d), cfg.ssd_inner)}
        elif mixer == "mamba":
            step = jnp.exp(jax.random.uniform(key(), (k, di), F32,
                                              np.log(1e-3), np.log(1e-1)))
            p["mamba"] = {
                "w_in": w((k, d, 2 * di), d), "conv": w((k, K, di), K),
                "conv_bias": jax.random.uniform(key(), (k, di), F32,
                                                -K ** -0.5, K ** -0.5),
                "w_x": w((k, di, r + 2 * n), di),
                "dt_norm": jnp.ones((k, r), dt), "b_norm": jnp.ones((k, n), dt),
                "c_norm": jnp.ones((k, n), dt),
                "w_dt": w((k, r, di), r),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
                # [d_state, d_inner]: channels minor (`ops/mamba.py`)
                "A_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, n + 1, dtype=F32))[None, :, None], (k, n, di)),
                "D": 1.0 + 0.1 * jax.random.normal(key(), (k, di), F32),
                "w_out": w((k, di, d), di)}
        else:
            H, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
            p[mixer] = {"wq": w((k, d, H * hd), d), "wk": w((k, d, kvh * hd), d),
                        "wv": w((k, d, kvh * hd), d), "wo": w((k, H * hd, d), H * hd)}
            if mixer == "dsa":
                J, di = cfg.dsa_heads, cfg.dsa_head_dim
                around = lambda shape, mid: (mid + 0.1 * jax.random.normal(
                    key(), shape, F32)).astype(dt)
                p["dsa"].update({
                    "q_norm": around((k, hd), 1.0), "k_norm": around((k, hd), 1.0),
                    "w_qi": w((k, d, J * di), d), "w_ki": w((k, d, di), d),
                    "ki_norm": around((k, di), 1.0), "ki_bias": around((k, di), 0.0),
                    "w_wi": w((k, d, J), d)})
            if mixer == "eva":
                p["eva"]["phi"] = jax.random.uniform(key(), (k, H, hd), F32, -1.0, 1.0)
                p["eva"]["mu"] = jax.random.uniform(key(), (k, H, hd), F32, -0.5, 0.5)
        runs.append(p)
    params = {"embed": (jax.random.normal(key(), (cfg.vocab_size, d), F32)
                        * 0.02).astype(dt),
              "final_norm": norm((d,)), "runs": runs}
    if cfg.eva_layers or cfg.untied_head:
        params["lm_head"] = (jax.random.normal(
            key(), (d, cfg.n_pred_heads * cfg.vocab_size), F32) * 0.02).astype(dt)
    return params


# ---------------------------------------------------------------- pieces


def _kda_inputs(cfg: HybridConfig, p, h, y):
    """From the normed residual h [..., d] and the convolved, SiLU'd
    projections y [..., 3 H dk]: q, k, v [..., H, dk], the log decay g
    [..., H, dk] and beta [..., H], all float32."""
    H, dk = cfg.kda_heads, cfg.kda_head_dim
    lead = h.shape[:-1]
    q, k, v = (a.reshape(lead + (H, dk)) for a in jnp.split(y, 3, axis=-1))
    q = kda.l2_norm(q) * dk ** -0.5
    k = kda.l2_norm(k)
    f = ((h @ p["w_f1"]) @ p["w_f2"]).astype(F32) + p["dt_bias"]
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(f.reshape(lead + (H, dk)))
    beta = jax.nn.sigmoid((h @ p["w_b"]).astype(F32))
    return q, k, v.astype(F32), g, beta


def _kda_output(cfg: HybridConfig, p, h, o):
    """o [..., H, dv] float32 -> the mixer's output [..., d]: per-head
    RMSNorm, the sigmoid gate, the output projection."""
    gate = jax.nn.sigmoid(((h @ p["w_g1"]) @ p["w_g2"]).astype(F32))
    o = rms_norm(o, p["o_norm"], cfg.norm_eps).reshape(gate.shape) * gate
    return o.astype(h.dtype) @ p["wo"]


def _mla_latent(cfg: HybridConfig, p, h, positions):
    """h [..., s, d] at positions [..., s] -> (q [..., s, H, dn + dr], latent
    row [..., s, latent_width]): `q_r` and the one shared `k_r` rotated
    where the configuration has positions, the row padded to its lanes."""
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_dim
    if "w_qa" in p:   # the low-rank query
        h_q = rms_norm(h @ p["w_qa"], p["q_norm"], cfg.norm_eps) @ p["w_qb"]
    else:
        h_q = h @ p["wq"]
    q = h_q.reshape(h.shape[:-1] + (cfg.n_heads, -1))
    ckr = h @ p["w_kva"]
    c, k_r = rms_norm(ckr[..., :r], p["kv_norm"], cfg.norm_eps), ckr[..., r:]
    if cfg.rope_theta:
        q = jnp.concatenate(
            [q[..., :dn], mla.rotate(q[..., dn:], positions, cfg.rope_theta)], -1)
        k_r = mla.rotate(k_r[..., None, :], positions, cfg.rope_theta)[..., 0, :]
    pad = jnp.zeros(h.shape[:-1] + (cfg.latent_width - ckr.shape[-1],), h.dtype)
    return q, jnp.concatenate([c, k_r, pad], axis=-1)


def _residual(cfg: HybridConfig, p, post_norm: str, x, y):
    """x + y on the float32 stream; with sandwich norms x + RMSNorm(y)."""
    y = y.astype(F32)
    if cfg.sandwich_norm:
        y = rms_norm(y, p[post_norm], cfg.norm_eps)
    return x + y


def _normed(cfg: HybridConfig, x, w):
    """The float32 residual x, normed: (in float32 for the router, in the
    weights' type for the matrix products). The window form norms by a
    LayerNorm where its configuration says so (`swa_norm`)."""
    h32 = layer_norm(x, w, cfg.norm_eps) if cfg.layer_normed \
        else rms_norm(x, w, cfg.norm_eps, cfg.norm_unit_offset)
    return h32, h32.astype(cfg.dtype)


def _route(cfg: HybridConfig, m, h32):
    """An expert layer's route from the float32 rows h32 [T, d] it reads:
    (the experts each token chose [T, k] int32, their weights [T, k]
    float32), by `cfg.router`."""
    if cfg.router == "softmax":
        return route_softmax_top_k(h32, m["router"], cfg.top_k)
    # (a router that holds no bias chooses by its scores alone)
    return route_top_k(
        h32, m["router"],
        m["bias"] if "bias" in m else jnp.zeros((cfg.n_experts,), F32),
        cfg.top_k, cfg.route_scale, cfg.renormalize)


def _ffn(cfg: HybridConfig, p, h32, h, valid, stacks=None, layer=None, route=None):
    """h [T, d] (and h32, the same in float32) -> (FFN output [T, d],
    assignments landed, experts touched, the experts each token chose
    [T, k] or None for a dense layer). `valid` [T] bool: tokens that are no
    padding and no idle slot; the others are routed nowhere (their rows of
    the result are not used). A scanned run hands its experts' weights as
    `stacks` (w_gate, w_up, w_down [k, Eh, ...], the whole run's) and names
    its `layer`, so that they are read in place (`dropless_moe`). `route`:
    (`_route`'s idx, w) made elsewhere, from rows that are not h32 (a block
    whose router reads the mixer's input); None: routed here, from h32."""
    if "ffn" in p:
        f = p["ffn"]
        zero = jnp.zeros((), jnp.int32)
        return (swiglu(h @ f["w_gate"], h @ f["w_up"]) @ f["w_down"], zero, zero,
                None)
    m = p["moe"]
    with jax.named_scope("moe"):
        # routed on the float32 activations, computed on the rounded ones
        idx, w = route or _route(cfg, m, h32)
        with jax.named_scope("experts") if route else contextlib.nullcontext():
            y, landed, touched = dropless_moe(
                h, idx, w, *(stacks or (m["w_gate"], m["w_up"], m["w_down"])),
                cfg.experts_held, cfg.n_experts, valid, layer,
                gate_act=cfg.gate_act)
    if "shared" in m:
        with jax.named_scope("shared_expert"):
            s = m["shared"]
            shared = swiglu(h @ s["w_gate"], h @ s["w_up"]) @ s["w_down"]
            if cfg.parallel:   # the MEAN of the shared experts, held as one MLP
                shared = shared * (1.0 / cfg.n_shared)
            y = y + shared
    return y, landed, touched, idx


# ------------------------------------------- the list form's layer bodies
#
# A list-form layer is a mixer half and an FFN half, each ONE module-level
# jitted body a kind: `_kda_seq` / `_kda_step`, `_mla_seq` / `_mla_step`,
# `_ffn_rows` (dense, or experts + shared: two parameter trees). Static:
# `cfg`, and `attn_len` for the MLA step alone; everything else is data (the
# half's own parameters, x, its state rows, the MLA layer's index into the
# latent table), so the layers of a kind, a prediction module's among them,
# are the same call to jax: it traces a body once per (kind, shapes) in a
# process, whichever program asks first, and lowers it once per program as
# a private function that the kind's other layers call. XLA inlines the
# calls: the compiled step holds none (`tests/test_chip_compile.py`).

class _Bodies(threading.local):
    traced = 0   # bodies this thread ever traced


_bodies = _Bodies()


def _layer_body(*static):
    """`jax.jit` for a half-layer's body (`cfg` static, and `static`); the
    Python body runs on a trace-cache miss only, and counts it."""
    def jitted(fn):
        @functools.wraps(fn)
        def body(*args, **kwargs):
            _bodies.traced += 1
            return fn(*args, **kwargs)
        return jax.jit(body, static_argnames=("cfg",) + static)
    return jitted


def _layered_program(fn):
    """For a jitted function that runs the stack: its `xla.compile` spans
    carry `layers` (the list form's, a prediction module's among them) and
    `layer_bodies_traced`, how many bodies THIS program traced anew. A layer
    whose parameters differ from its kind's in a shape, a type or a weak
    type misses the cache in silence; the count says so."""
    cfg_of = inspect.signature(fn).bind_partial

    @functools.wraps(fn)
    def program(*args, **kwargs):
        before = _bodies.traced
        out = fn(*args, **kwargs)
        cfg = cfg_of(*args, **kwargs).arguments["cfg"]
        if not cfg.scanned:
            tracing.note_compile(
                fn.__name__, layers=cfg.n_layers + cfg.n_predict,
                layer_bodies_traced=_bodies.traced - before)
        return out
    return program


_FFN_HALF = ("ffn_norm", "ffn_post_norm", "ffn", "moe")


def _halves(p):
    """A list-form layer's parameters -> (what its mixer half reads, what its
    FFN half reads): a body shares its trace among the layers that hand it
    the same tree, so a KDA layer's FFN is an MLA layer's."""
    ffn = {k: a for k, a in p.items() if k in _FFN_HALF}
    return {k: a for k, a in p.items() if k not in ffn}, ffn


@_layer_body()
def _ffn_rows(cfg: HybridConfig, p, x, valid):
    """The FFN half over rows: x [T, d] float32 -> (x + FFN(RMSNorm(x)),
    assignments landed, experts touched, the experts chosen [T, k] or None
    for a dense layer); `valid` [T] as `_ffn`."""
    h32, h = _normed(cfg, x, p["ffn_norm"])
    y, landed, touched, chosen = _ffn(cfg, p, h32, h, valid)
    return _residual(cfg, p, "ffn_post_norm", x, y), landed, touched, chosen


def _ffn_half(cfg: HybridConfig, p, x, valid):
    """The second half of a list-form block: x [..., d] float32 ->
    (x + FFN(RMSNorm(x)), assignments landed, experts touched, the experts
    chosen [..., k] or None for a dense layer); `valid` [...] as `_ffn`. The
    body sees rows: prompt passes of as many tokens (4 x 1024, 1 x 4096)
    share its trace."""
    lead, d = x.shape[:-1], x.shape[-1]
    y, landed, touched, chosen = _ffn_rows(cfg, p, x.reshape(-1, d),
                                           valid.reshape(-1))
    if chosen is not None:
        chosen = chosen.reshape(lead + (-1,))
    return y.reshape(x.shape), landed, touched, chosen


@_layer_body()
def _kda_seq(cfg: HybridConfig, p, x, valid, true_len):
    """The KDA mixer half over a whole sequence: x [b, s, d] float32 -> (x,
    the state after the last true position S [b, H, dk, dv] float32, the
    convolution tail [b, K-1, 3 H dk])."""
    _, h = _normed(cfg, x, p["mixer_norm"])
    with jax.named_scope("kda"):
        m = p["kda"]
        qkv = h @ m["w_qkv"]
        y = jax.nn.silu(kda.short_conv(qkv.astype(F32), m["conv"].astype(F32)))
        q, k, v, g, beta = _kda_inputs(cfg, m, h, y)
        # padding leaves the state alone: no decay, no write
        g = jnp.where(valid[..., None, None], g, 0.0)
        beta = jnp.where(valid[..., None], beta, 0.0)
        o, S = kda.kda_chunked(q, k, v, g, beta, chunk=cfg.kda_chunk)
        x = _residual(cfg, p, "mixer_post_norm", x, _kda_output(cfg, m, h, o))
        return x, S, kda.conv_tail(qkv, true_len, cfg.conv_kernel)


@_layer_body()
def _kda_step(cfg: HybridConfig, p, x, S, tail):
    """The KDA mixer half for one token a slot: x [B, 1, d] float32, the
    layer's state S [B, H, dk, dv] and tail [B, K-1, 3 H dk] -> (x, S, tail)."""
    _, h = _normed(cfg, x[:, 0], p["mixer_norm"])  # one position only
    with jax.named_scope("kda"):
        m = p["kda"]
        y, tail = kda.short_conv_step(h @ m["w_qkv"], tail, m["conv"])
        q, k, v, g, beta = _kda_inputs(cfg, m, h, jax.nn.silu(y))
        S, o = kda.kda_step(S, q, k, v, g, beta)
        x = _residual(cfg, p, "mixer_post_norm", x,
                      _kda_output(cfg, m, h, o)[:, None])
        return x, S, tail


@_layer_body()
def _mla_seq(cfg: HybridConfig, p, x, positions):
    """The MLA mixer half over a whole sequence: x [b, s, d] float32 at
    `positions` [s] -> (x, latent rows [b, s, latent_width])."""
    b, s, _ = x.shape
    _, h = _normed(cfg, x, p["mixer_norm"])
    with jax.named_scope("mla"):
        m = p["mla"]
        q, latent = _mla_latent(cfg, m, h, positions)
        attn = mla.mla_prefill_attention(q, latent, m["w_kvb"], cfg.kv_lora_rank,
                                         cfg.qk_nope_dim, cfg.v_head_dim)
        x = _residual(cfg, p, "mixer_post_norm", x, attn.reshape(b, s, -1) @ m["wo"])
        return x, latent


@_layer_body("attn_len")
def _mla_step(cfg: HybridConfig, p, x, latent, layer, lengths, positions, walk,
              attn_len: int):
    """The MLA mixer half, Q new positions a slot: x [B, Q, d] float32
    against `latent[layer]`, read-only, `layer` an int32 scalar (DATA: the
    layers of a step, the module's too, are one body) -> (x, the positions'
    own latent rows [B, Q, latent_width])."""
    B, Q, _ = x.shape
    _, h = _normed(cfg, x, p["mixer_norm"])
    with jax.named_scope("mla"):
        m = p["mla"]
        q, cur = _mla_latent(cfg, m, h, positions)
        cur = cur.astype(cfg.dtype)
        attn = mla.mla_decode_absorbed(
            q, latent, layer, cur, lengths, attn_len, m["w_kvb"],
            cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.v_head_dim, walk)
        x = _residual(cfg, p, "mixer_post_norm", x,
                      attn.reshape(B, Q, -1).astype(cfg.dtype) @ m["wo"])
        return x, cur


def _add(cfg: HybridConfig, x, y):
    """x + residual_scale * y on the float32 stream of a run: the scalar
    scales what the mixer or the FFN gives, never the stream."""
    y = y.astype(F32)
    if cfg.residual_scale != 1.0:
        y = y * cfg.residual_scale
    return x + y


def _dense_ffn(cfg: HybridConfig, lp, x):
    """x += SwiGLU(RMSNorm(x)) of one layer of a run (float32 residual)."""
    with jax.named_scope("mlp"):
        _, h = _normed(cfg, x, lp["ffn_norm"])
        f = lp["ffn"]
        return _add(cfg, x, swiglu(h @ f["w_gate"], h @ f["w_up"]) @ f["w_down"])


def _run_ffn(cfg: HybridConfig, lp, x, valid, stacks=None, layer=None):
    """The second half of one layer of a run: x [..., d] float32 -> (x +
    FFN(RMSNorm(x)), what an expert layer adds to the scan's ys: ([assignments
    landed, experts touched] int32, the experts chosen [..., k]); () for a
    dense layer). `valid` [...] bool as `_ffn`: the tokens that are no
    padding and no idle slot; `stacks`, `layer` as `_ffn` (`_expert_stacks`)."""
    if "ffn" in lp:
        return _dense_ffn(cfg, lp, x), ()
    y, *counted = _routed_ffn(cfg, lp, *_normed(cfg, x, lp["ffn_norm"]), valid,
                              stacks, layer)
    return _add(cfg, x, y), _routed_ys(x.shape[:-1], *counted)


def _routed_ys(lead, landed, touched, chosen):
    """What an expert layer adds to its scan's ys (`_run_ffn`)."""
    return jnp.stack([landed, touched]), chosen.reshape(lead + (-1,))


def _routed_ffn(cfg: HybridConfig, lp, h32, h, valid, stacks=None, layer=None,
                route=None):
    """A run's expert layer over the normed residual h [..., d] (h32: the
    same in float32) -> (its output [..., d], assignments landed, experts
    touched, the experts chosen [tokens, k]: `_routed_ys` makes the scan's
    ys of the three). `route`: `_route_ahead`'s, or None (`_ffn`)."""
    lead, d = h.shape[:-1], h.shape[-1]
    h32, h, ok = h32.reshape(-1, d), h.reshape(-1, d), valid.reshape(-1)
    T = h.shape[0]
    if T > _FFN_BLOCK and T % _FFN_BLOCK == 0:
        # a long prompt's tokens go a block at a time: the layer gathers and
        # weighs [block x k, d] rows, not [12288 x 10, 4096] (2 GB in float32)
        blocks = lambda a: a.reshape((T // _FFN_BLOCK, _FFN_BLOCK) + a.shape[1:])
        y, landed, touched, chosen = jax.lax.map(
            lambda t: _ffn(cfg, lp, *t[:3], stacks, layer, t[3]),
            (blocks(h32), blocks(h), blocks(ok),
             tuple(blocks(a) for a in route) if route else None))
        y, chosen = y.reshape(T, d), chosen.reshape(T, -1)
        # an expert counts as touched once a block (the decode step, whose
        # counters the engine reports, is one block)
        landed, touched = jnp.sum(landed), jnp.sum(touched)
    else:
        y, landed, touched, chosen = _ffn(cfg, lp, h32, h, ok, stacks, layer, route)
    return y.reshape(lead + (d,)), landed, touched, chosen


def _route_ahead(cfg: HybridConfig, lp, h32):
    """The route of a block whose router reads the MIXER's input (`route_from`
    "mixer"), from those rows h32 [..., d] float32, before the mixer runs:
    (idx, w [tokens, k]) for `_routed_ffn` behind it; None for every other
    block, whose expert layer routes from its own input."""
    if cfg.route_from != "mixer":
        return None
    with jax.named_scope("moe"), jax.named_scope("route_ahead"):
        return _route(cfg, lp["moe"], h32.reshape(-1, h32.shape[-1]))


def _swa_block(cfg: HybridConfig, lp, x, h32, h, mixed, route, valid, stacks, layer):
    """The window form's block behind its attention: the residual x [..., d]
    float32, its normed rows (h32, h) that the attention read, the
    attention's output `mixed` -> (x of the next layer, the scan's ys of the
    expert layer). Parallel: `x + mixed + FFN(h)`. Sequential: `x1 = x +
    mixed; x1 + FFN(N2(x1))`, by `route` where the router read h."""
    if cfg.parallel:
        routed, *counted = _routed_ffn(cfg, lp, h32, h, valid, stacks, layer)
        x = x + mixed.astype(F32) + routed.astype(F32)
    else:
        x = x + mixed.astype(F32)
        routed, *counted = _routed_ffn(cfg, lp, *_normed(cfg, x, lp["ffn_norm"]),
                                       valid, stacks, layer, route)
        x = x + routed.astype(F32)
    return x, _routed_ys(x.shape[:-1], *counted)


def _expert_stacks(rp):
    """A run's weights split for its scan: (what the scan slices a layer at a
    time, the experts' three stacks [k, Eh, ...] or None for a run of dense
    FFNs). The stacks stay whole beside the scan and every layer's grouped
    products read their own part in place (`dropless_moe`'s `layer`)."""
    if "moe" not in rp:
        return rp, None
    names = ("w_gate", "w_up", "w_down")
    return ({**rp, "moe": {n: a for n, a in rp["moe"].items() if n not in names}},
            tuple(rp["moe"][n] for n in names))


def _mamba2_dt(m, h):
    """The step size dt [..., H] after its softplus, float32, from the
    normed residual h [..., d]."""
    # float32 sums: dt feeds an exponent that compounds over the positions
    return jax.nn.softplus(jnp.dot(h, m["w_dt"], preferred_element_type=F32)
                           + m["dt_bias"])


def _mamba2_output(cfg: HybridConfig, m, y, z):
    """y [..., H P] float32 and the gate's input z -> the mixer's output
    [..., d]: the gate FIRST, then one RMSNorm over all the channels (one
    group), then the output projection."""
    with jax.named_scope("gated_norm"):
        g = rms_norm(y * jax.nn.silu(z.astype(F32)), m["norm"], cfg.norm_eps)
    return g.astype(cfg.dtype) @ m["w_out"]


def _mamba2_seq(cfg: HybridConfig, m, h, valid, true_len):
    """h [b, s, d] (normed) -> (the mixer's output [b, s, d], the state after
    the last true position [b, N, H P] float32, the convolution tail
    [b, K-1, H P + 2 N])."""
    z, pre = jnp.split(h @ m["w_in"], (cfg.ssd_inner,), axis=-1)
    with jax.named_scope("conv"):
        xbc = jax.nn.silu(kda.short_conv(pre.astype(F32), m["conv"].astype(F32))
                          + m["conv_bias"])
    with jax.named_scope("scan"):
        y, state = ssd.ssd_scan(xbc, _mamba2_dt(m, h), -jnp.exp(m["A_log"]), m["D"],
                                cfg.ssd_state, None, valid, cfg.ssd_chunk)
    return (_mamba2_output(cfg, m, y, z), state,
            kda.conv_tail(pre, true_len, cfg.conv_kernel))


def _mamba2_step(cfg: HybridConfig, m, h, ssm, layer, slots, busy, tail):
    """One token: h [B, d]; ssm the run's stacked state [k, B, N, H P], of
    which `layer` advances in place (`ssd.ssd_step_slots`); tail
    [B, K-1, H P + 2 N] -> (output [B, d], ssm, tail)."""
    z, pre = jnp.split(h @ m["w_in"], (cfg.ssd_inner,), axis=-1)
    with jax.named_scope("conv"):
        y, tail = kda.short_conv_step(pre, tail, m["conv"])
        xbc = jax.nn.silu(y + m["conv_bias"])
    x, B, C = jnp.split(xbc, (cfg.ssd_inner, cfg.ssd_inner + cfg.ssd_state), axis=-1)
    with jax.named_scope("step"):
        ssm, y = ssd.ssd_step_slots(
            ssm, layer, slots, busy,
            x.reshape(x.shape[:-1] + (cfg.ssd_heads, cfg.ssd_head_dim)),
            _mamba2_dt(m, h), -jnp.exp(m["A_log"]), B, C, m["D"])
    return _mamba2_output(cfg, m, y, z), ssm, tail


def _mamba_inputs(cfg: HybridConfig, m, u):
    """From the convolved, SiLU'd u [..., di] float32: the step size dt
    [..., di] after its softplus and the maps B, C [..., n], float32; each of
    the three through an RMSNorm of its own."""
    r, n = cfg.dt_rank, cfg.d_state
    # the two small products keep their float32 sums: dt feeds an exponent
    # that compounds over the positions, and B and C scale the whole state
    low, B, C = jnp.split(jnp.dot(u.astype(cfg.dtype), m["w_x"],
                                  preferred_element_type=F32), (r, r + n), axis=-1)
    low = rms_norm(low, m["dt_norm"], cfg.norm_eps).astype(cfg.dtype)
    B = rms_norm(B, m["b_norm"], cfg.norm_eps)
    C = rms_norm(C, m["c_norm"], cfg.norm_eps)
    dt = jnp.dot(low, m["w_dt"], preferred_element_type=F32) + m["dt_bias"]
    return jax.nn.softplus(dt), B, C


def _mamba_seq(cfg: HybridConfig, m, h, valid, true_len):
    """h [b, s, d] (normed) -> (the mixer's output [b, s, d], the state after
    the last true position [b, n, di] float32, the convolution tail
    [b, K-1, di])."""
    u_pre, z = jnp.split(h @ m["w_in"], 2, axis=-1)
    u = jax.nn.silu(kda.short_conv(u_pre.astype(F32), m["conv"].astype(F32))
                    + m["conv_bias"])
    dt, B, C = _mamba_inputs(cfg, m, u)
    with jax.named_scope("scan"):
        y, state = mamba.selective_scan(u, dt, -jnp.exp(m["A_log"]), B, C, m["D"],
                                        None, valid, cfg.mamba_chunk)
    out = (y * jax.nn.silu(z.astype(F32))).astype(cfg.dtype) @ m["w_out"]
    return out, state, kda.conv_tail(u_pre, true_len, cfg.conv_kernel)


def _mamba_step(cfg: HybridConfig, m, h, ssm, layer, slots, busy, tail):
    """One token: h [B, d]; ssm the run's stacked state [k, B, n, di], of
    which `layer` advances in place (`mamba.selective_step_slots`); tail
    [B, K-1, di] -> (output [B, d], ssm, tail)."""
    u_pre, z = jnp.split(h @ m["w_in"], 2, axis=-1)
    y, tail = kda.short_conv_step(u_pre, tail, m["conv"])
    u = jax.nn.silu(y + m["conv_bias"])
    dt, B, C = _mamba_inputs(cfg, m, u)
    with jax.named_scope("scan"):
        ssm, y = mamba.selective_step_slots(
            ssm, layer, slots, busy, u, dt, -jnp.exp(m["A_log"]), B, C, m["D"])
    return (y * jax.nn.silu(z.astype(F32))).astype(cfg.dtype) @ m["w_out"], ssm, tail


def _attn_qkv(cfg: HybridConfig, a, h):
    """h [..., d] -> q [..., H, hd], k, v [..., kvh, hd]; no positions."""
    lead, hd = h.shape[:-1], cfg.head_dim
    return ((h @ a["wq"]).reshape(lead + (cfg.n_heads, hd)),
            (h @ a["wk"]).reshape(lead + (cfg.n_kv_heads, hd)),
            (h @ a["wv"]).reshape(lead + (cfg.n_kv_heads, hd)))


def _causal_gqa(cfg: HybridConfig, q, k, v):
    """Causal grouped-query attention over EVERY row of a whole prompt:
    q [b, s, H, hd], k, v [b, s, kvh, hd] -> [b, s, H, hd]."""
    s, scale = q.shape[1], cfg.attn_scale or cfg.head_dim ** -0.5
    if s % 1024 == 0:
        # whole blocks of the flash kernel (a prompt of thousands of
        # positions: the blocked form's scores alone are 0.8 GB at
        # 32 heads x 512 x 12288)
        return jnp.moveaxis(attention(
            *(jnp.moveaxis(t, 1, 2) for t in (q, k, v)), sm_scale=scale), 1, 2)
    rep = cfg.n_heads // cfg.n_kv_heads
    return causal_attention_blocked(q, jnp.repeat(k, rep, axis=2),
                                    jnp.repeat(v, rep, axis=2), sm_scale=scale)


def _eva_qkv(cfg: HybridConfig, a, h, positions):
    """h [..., s, d] at positions [..., s] -> q, k, v [..., s, H, hd] in the
    configuration's type, q and k rotated over the whole head width."""
    # behind the barrier the three products stay flat and read their weight
    # in place (`serving._one_row_qkv` says why): left to fuse the rotation's
    # head split into them, XLA:TPU wants `wq`, `wk`, `wv` transposed, and
    # copies all three STACKS ahead of the prompt pass's loops (0.8 GB)
    lead, hd = h.shape[:-1], cfg.head_dim
    q, k, v = (t.reshape(lead + (cfg.n_heads, hd)) for t in
               jax.lax.optimization_barrier((h @ a["wq"], h @ a["wk"], h @ a["wv"])))
    return (mla.rotate(q, positions, cfg.rope_theta),
            mla.rotate(k, positions, cfg.rope_theta).astype(cfg.dtype),
            v.astype(cfg.dtype))


def _dsa_inputs(cfg: HybridConfig, a, h, positions):
    """h [..., s, d] at positions [..., s] -> q [..., s, H, hd], k, v
    [..., s, kvh, hd] (q and k through an RMSNorm a head, then rotated over
    the whole head) and the indexer's qi [..., s, J, di] (rotated), wi
    [..., s, J] float32 and ki [..., s, key_width]: ONE key a position
    through a LayerNorm, rotated, zero lanes behind its di."""
    lead, hd, eps = h.shape[:-1], cfg.head_dim, cfg.norm_eps
    J, di, theta = cfg.dsa_heads, cfg.dsa_head_dim, cfg.rope_theta
    # flat products that read their weight in place (`_eva_qkv` says why)
    q, k, v, qi, ki = jax.lax.optimization_barrier(
        tuple(h @ a[n] for n in ("wq", "wk", "wv", "w_qi", "w_ki")))
    q = mla.rotate(rms_norm(q.reshape(lead + (cfg.n_heads, hd)), a["q_norm"], eps),
                   positions, theta)
    k = mla.rotate(rms_norm(k.reshape(lead + (cfg.n_kv_heads, hd)), a["k_norm"], eps),
                   positions, theta)
    with jax.named_scope("index"):
        qi = mla.rotate(qi.reshape(lead + (J, di)), positions, theta)
        kf = ki.astype(F32)
        kf = kf - jnp.mean(kf, axis=-1, keepdims=True)
        kf = kf * jax.lax.rsqrt(jnp.mean(kf * kf, axis=-1, keepdims=True) + eps)
        kf = kf * a["ki_norm"].astype(F32) + a["ki_bias"].astype(F32)
        ki = mla.rotate(kf.astype(cfg.dtype)[..., None, :], positions, theta)[..., 0, :]
        ki = jnp.pad(ki, [(0, 0)] * len(lead) + [(0, dsa.key_width(di) - di)])
        # float32 sums: the weights scale scores whose order is the choice
        wi = jnp.dot(h, a["w_wi"], preferred_element_type=F32)
    return q, k, v.reshape(lead + (cfg.n_kv_heads, hd)), qi, wi, ki


def _swa_qkv(cfg: HybridConfig, a, h, positions):
    """h [..., s, d] -> q [..., s, H, hd], k, v [..., s, kvh, hd] in the
    configuration's type. A window layer hands its `positions` [..., s] and
    has q and k rotated there (`swa_rotary`: over interleaved pairs, or by
    halves); a full layer hands None and has none."""
    # flat products that read their weight in place (`_eva_qkv` says why)
    lead, hd = h.shape[:-1], cfg.head_dim
    q, k, v = jax.lax.optimization_barrier((h @ a["wq"], h @ a["wk"], h @ a["wv"]))
    q = q.reshape(lead + (cfg.n_heads, hd))
    k = k.reshape(lead + (cfg.n_kv_heads, hd))
    if positions is not None:
        rotate = rotate_interleaved if cfg.swa_rotary == "interleaved" else mla.rotate
        q = rotate(q, positions, cfg.rope_theta)
        k = rotate(k, positions, cfg.rope_theta)
    return q, k.astype(cfg.dtype), v.reshape(lead + (cfg.n_kv_heads, hd)).astype(cfg.dtype)


def _kv_row(k, v):
    """k, v [..., kvh, hd] -> the position's cache block [..., 2 kvh, hd]."""
    return jnp.concatenate([k, v], axis=-2)


# ---------------------------------------------------------------- sequence


def _mla_seq_layer(cfg: HybridConfig, p, x, valid, positions):
    """One layer with an MLA mixer over a whole sequence: x [b, s, d] float32
    -> (x, latent rows [b, s, latent_width], the experts chosen or None)."""
    mixer, ffn = _halves(p)
    x, latent = _mla_seq(cfg, mixer, x, positions)
    x, _, _, chosen = _ffn_half(cfg, ffn, x, valid)
    return x, latent, chosen


def _sequence(params, tokens, true_len, cfg: HybridConfig):
    """tokens [b, s] right-padded to true_len [b] -> (the stack's last hidden
    rows BEFORE the final norm [b, s, d] float32, state rows as `prefill`
    returns them, the experts chosen per expert layer)."""
    if cfg.scanned:
        return _sequence_runs(params, tokens, true_len, cfg)
    b, s = tokens.shape
    # the residual stream is float32 (weights and matmul inputs keep the
    # configuration's type): in bf16 its rounding at every add reaches 1%
    # after a few layers, and a router with near-ties (8 of 256 by score)
    # then picks another expert for a quarter of the tokens
    x = params["embed"][tokens].astype(F32)
    valid = jnp.arange(s)[None, :] < true_len[:, None]               # [b, s]
    positions = jnp.arange(s)
    S_rows, conv_rows, latent_rows, routing = [], [], [], []
    for p, (mixer, _) in zip(params["layers"], cfg.layer_kinds()):
        if mixer == "mla":
            x, latent, chosen = _mla_seq_layer(cfg, p, x, valid, positions)
            latent_rows.append(latent)
        else:
            mixer, ffn = _halves(p)
            x, S, tail = _kda_seq(cfg, mixer, x, valid, true_len)
            S_rows.append(S)
            conv_rows.append(tail)
            x, _, _, chosen = _ffn_half(cfg, ffn, x, valid)
        if chosen is not None:
            routing.append(chosen)
    rows = {"S": S_rows, "conv": conv_rows,
            "latent": jnp.stack(latent_rows) if latent_rows else
            jnp.zeros((0, b, s, cfg.latent_width), cfg.dtype)}
    return x, rows, routing


def _mtp_sequence(params, hidden, following, true_len, cfg: HybridConfig):
    """The prediction module over a whole sequence: hidden [b, s, d] float32
    (the main stack's, before its final norm) and `following` [b, s], the
    token AFTER each position -> (features after the module's final norm
    [b, s, d]: through the head, logits for the token two ahead; the
    module's latent rows [b, s, latent_width]; the experts it chose)."""
    m = params["mtp"]
    s = hidden.shape[1]
    valid = jnp.arange(s)[None, :] < true_len[:, None]
    with jax.named_scope("mtp"):
        e = params["embed"][following].astype(F32)
        both = jnp.concatenate([rms_norm(hidden, m["h_norm"], cfg.norm_eps),
                                rms_norm(e, m["e_norm"], cfg.norm_eps)], axis=-1)
        x = (both.astype(cfg.dtype) @ m["proj"]).astype(F32)
        x, latent, chosen = _mla_seq_layer(cfg, m["layer"], x, valid, jnp.arange(s))
        return _normed(cfg, x, m["final_norm"])[1], latent, chosen


def _sequence_runs(params, tokens, true_len, cfg: HybridConfig):
    """`_sequence` for the runs form: every run one `lax.scan` over its
    stacked weights. State rows: per Mamba run "ssm" ([k, b, n, di] float32,
    Mamba-2: [k, b, N, H P]) and "conv" [k, b, K-1, channels]; "k", "v"
    [attention layers, b, kvh, s, hd]. The experts chosen: per run of expert
    layers [k, b, s, top_k]."""
    if cfg.eva_layers:
        return _sequence_eva(params, tokens, true_len, cfg)
    if cfg.dsa_layers:
        return _sequence_dsa(params, tokens, true_len, cfg)
    if cfg.windowed:
        return _sequence_swa(params, tokens, true_len, cfg)
    b, s = tokens.shape
    H, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = params["embed"][tokens].astype(F32)
    if cfg.embed_scale != 1.0:
        x = x * cfg.embed_scale
    valid = jnp.arange(s)[None, :] < true_len[:, None]               # [b, s]

    def layer_of(mixer, stacks):
        """The scan body of one run: xs is the run's sliced weights, beside
        the layer's number in the run where it holds expert layers."""
        def mamba_layer(x, xs):
            lp, i = xs if stacks else (xs, None)
            with jax.named_scope("mamba" if mixer == "mamba" else "ssd"):
                _, h = _normed(cfg, x, lp["mixer_norm"])
                out, state, tail = (_mamba_seq if mixer == "mamba" else _mamba2_seq)(
                    cfg, lp[mixer], h, valid, true_len)
                x = _add(cfg, x, out)
            x, routed = _run_ffn(cfg, lp, x, valid, stacks, i)
            return x, (state, tail) + routed

        def attn_layer(x, xs):
            lp, i = xs if stacks else (xs, None)
            with jax.named_scope("attention"):
                _, h = _normed(cfg, x, lp["mixer_norm"])
                q, k, v = _attn_qkv(cfg, lp["attn"], h)
                attn = _causal_gqa(cfg, q, k, v)
                x = _add(cfg, x, attn.reshape(b, s, H * hd) @ lp["attn"]["wo"])
            x, routed = _run_ffn(cfg, lp, x, valid, stacks, i)
            return x, (jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2)) + routed

        return attn_layer if mixer == "attn" else mamba_layer

    rows = {"ssm": [], "conv": [], "k": [], "v": []}
    routing = []
    # ONE body a mixer for the runs of dense FFNs (jax then lowers a like
    # run's scan, and its kernel, once)
    dense = {mixer: layer_of(mixer, None) for mixer, _ in cfg.runs()}
    for rp, (mixer, k) in zip(params["runs"], cfg.runs()):
        rp, stacks = _expert_stacks(rp)
        x, ys = jax.lax.scan(layer_of(mixer, stacks) if stacks else dense[mixer], x,
                             (rp, jnp.arange(k)) if stacks else rp)
        for name, a in zip(("k", "v") if mixer == "attn" else ("ssm", "conv"), ys):
            rows[name].append(a)
        if len(ys) > 2:
            routing.append(ys[3])
    for name in ("k", "v"):
        rows[name] = jnp.concatenate(rows[name]) if rows[name] else \
            jnp.zeros((0, b, kvh, s, hd), cfg.dtype)
    return x, rows, routing


def _sequence_dsa(params, tokens, true_len, cfg: HybridConfig,
                  with_rows: bool = False):
    """`_sequence` for a stack of sparse-attention layers: ONE `lax.scan`
    over the stacked weights. A prompt of more than `dsa_topk` positions
    scores, selects and attends (`ops.dsa.prompt_attention`); a shorter one
    has every causal row chosen and runs plain causal attention. State rows:
    "kv" [layers, b, s, 2 kvh, hd], a position's `[k ; v]`, and "ik"
    [layers, b, 1, s, key_width], its indexer key; with `with_rows` also
    "chosen" [layers, b, ceil(s / 32), s] int32, the rows every query chose
    (`ops.dsa.pack_rows`; absent where every row is chosen)."""
    b, s = tokens.shape
    H, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = params["embed"][tokens].astype(F32)
    valid = jnp.arange(s)[None, :] < true_len[:, None]               # [b, s]
    positions = jnp.arange(s)
    scale = cfg.attn_scale or hd ** -0.5
    sparse = s > cfg.dsa_topk
    rp, stacks = _expert_stacks(params["runs"][0])

    def layer(x, xs):
        lp, i = xs
        with jax.named_scope("dsa"):
            _, h = _normed(cfg, x, lp["mixer_norm"])
            a = lp["dsa"]
            q, k, v, qi, wi, ki = _dsa_inputs(cfg, a, h, positions)
            chosen = None
            if sparse:
                attn, chosen = dsa.prompt_attention(
                    q, k, v, qi, wi, ki, cfg.dsa_topk, cfg.dsa_chunk, scale, with_rows)
            else:
                with jax.named_scope("attend"):
                    attn = _causal_gqa(cfg, q, k, v)
            x = _add(cfg, x, attn.reshape(b, s, H * hd) @ a["wo"])
        x, routed = _run_ffn(cfg, lp, x, valid, stacks, i if stacks else None)
        return x, (_kv_row(k, v), ki[:, None]) + routed \
            + (() if chosen is None else (chosen,))

    x, ys = jax.lax.scan(layer, x, (rp, jnp.arange(cfg.n_layers)))
    rows = {"kv": ys[0], "ik": ys[1]}
    if sparse and with_rows:
        rows["chosen"] = ys[-1]
    return x, rows, [ys[3]] if stacks else []


def _sequence_eva(params, tokens, true_len, cfg: HybridConfig,
                  last_only: bool = False):
    """`_sequence` for a stack of EVA layers: ONE `lax.scan` over the
    sequence's windows (the tokens padded to whole windows), each window
    through all layers (a scan over the stacked weights). Layer l of window
    w reads the summaries layer l left for the windows before w, out of a
    table the outer scan carries, and adds its own W / C. With `last_only`
    (the prompt pass) the hidden row at `true_len - 1` is carried too,
    returned alone [b, 1, d], and the windows' hidden rows are dropped as
    they go. State rows, all
    [layers, b, H, rows, hd] and zero wherever a row is not live (positions
    past `true_len` leave nothing): "win_k", "win_v" the rows of the window
    that position `true_len` lies in (none if it opens one); "sum_k",
    "sum_v" a row a CLOSED chunk; "ck", "cv" the open chunk's rows again,
    from its row 0 (the decode step makes the chunk's summary of them when
    it closes)."""
    b, s = tokens.shape
    W, C, H, hd = cfg.eva_window, cfg.eva_chunk, cfg.n_heads, cfg.head_dim
    n_win = -(-s // W)
    per = W // C                                  # summaries a window
    rp = params["runs"][0]
    L = rp["mixer_norm"].shape[0]
    toks = jnp.pad(tokens, ((0, 0), (0, n_win * W - s))).reshape(b, n_win, W)

    def window(carry, inputs):
        sum_k, sum_v, win_k, win_v, last = carry
        toks, w = inputs
        positions = w * W + jnp.arange(W)

        def layer(x, inputs):
            lp, seen_k, seen_v = inputs
            with jax.named_scope("eva"):
                _, h = _normed(cfg, x, lp["mixer_norm"])
                a = lp["eva"]
                q, k, v = _eva_qkv(cfg, a, h, positions)
                rows_k, rows_v = jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2)
                with jax.named_scope("summarise"):
                    new_k, new_v = eva.summarise(rows_k, rows_v, a["phi"], a["mu"],
                                                 C, hd ** -0.5)
                with jax.named_scope("attend"):
                    attn = eva.window_attention(q, k, v, seen_k, seen_v, w * per,
                                                hd ** -0.5)
                x = x + (attn.reshape(b, W, H * hd) @ a["wo"]).astype(F32)
            return _dense_ffn(cfg, lp, x), (new_k, new_v, rows_k, rows_v)

        x, (new_k, new_v, rows_k, rows_v) = jax.lax.scan(
            layer, params["embed"][toks].astype(F32), (rp, sum_k, sum_v))
        at = (0, 0, 0, w * per, 0)
        sum_k = jax.lax.dynamic_update_slice(sum_k, new_k, at)
        sum_v = jax.lax.dynamic_update_slice(sum_v, new_v, at)
        opens = (true_len // W == w)[None, :, None, None, None]
        carry = (sum_k, sum_v, jnp.where(opens, rows_k, win_k),
                 jnp.where(opens, rows_v, win_v))
        if not last_only:
            return carry + (last,), x
        here = jnp.take_along_axis(
            x, jnp.clip(true_len - 1 - w * W, 0, W - 1)[:, None, None], axis=1)
        return carry + (jnp.where(((true_len - 1) // W == w)[:, None, None],
                                  here, last),), None

    table = jnp.zeros((L, b, H, n_win * per, hd), cfg.dtype)
    rows = jnp.zeros((L, b, H, W, hd), cfg.dtype)
    (sum_k, sum_v, win_k, win_v, last), x = jax.lax.scan(
        window, (table, table, rows, rows, jnp.zeros((b, 1, cfg.d_model), F32)),
        (jnp.moveaxis(toks, 1, 0), jnp.arange(n_win)))
    x = last if last_only else jnp.moveaxis(x, 0, 1).reshape(b, n_win * W, -1)[:, :s]

    def live(a, n):   # rows [.., b, H, r, hd] of which the first n [b] stay
        return jnp.where((jnp.arange(a.shape[3])[None, :] < n[:, None]
                          )[None, :, None, :, None], a, 0)

    # the open chunk's rows, from the window's: C rows from the chunk's first
    first = (true_len // C * C) % W
    chunk = lambda a: jnp.take_along_axis(
        a, (first[:, None] + jnp.arange(C)[None, :])[None, :, None, :, None], axis=3)
    state = {"win_k": live(win_k, true_len % W), "win_v": live(win_v, true_len % W),
             "sum_k": live(sum_k, true_len // C), "sum_v": live(sum_v, true_len // C),
             "ck": live(chunk(win_k), true_len % C),
             "cv": live(chunk(win_v), true_len % C)}
    return x, state, []


def _sequence_swa(params, tokens, true_len, cfg: HybridConfig,
                  last_only: bool = False, with_routing: bool = False):
    """`_sequence` for a stack of window and full attention layers. The
    sequence goes a CHUNK of one window (`swa_window` positions) at a time,
    each chunk through every layer,
    the rows the chunks leave as the walk's carry: a full layer's chunk
    writes its rows into "k", "v" [full layers, b, kvh, s, hd] and attends
    every row up to its own; a window layer's chunk keeps the chunk before
    it beside itself, [window layers, b, kvh, 2 chunk, hd], and attends both
    under the band. Activations are those of one chunk whatever `s`. A
    sequence of no more than a chunk is one chunk and walks nothing.

    Without `last_only` (`forward`) every chunk of `s` is walked by a scan
    and the hidden rows come back [b, s, d]. With it (the prompt pass, ONE
    prompt a call where it walks) the walk is a loop over the chunks that
    hold a true position, so one program serves every prompt length of a
    bucket, and the hidden row at `true_len - 1` comes back alone [b, 1, d].

    State rows: "k", "v" as above (rows past `true_len` hold whatever the
    padding left: masked by the length); "wk", "wv" [window layers, b, kvh,
    W, hd]: the last W positions before `true_len`, position p at row p % W
    (a prompt shorter than W leaves the rows from `true_len` on stale).
    `with_routing` (with `last_only`): the experts every position chose,
    [layers, b, s, k]."""
    b, s = tokens.shape
    W, H, kvh, hd = cfg.swa_window, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    scale = cfg.attn_scale or hd ** -0.5
    runs = cfg.runs()
    n_swa = sum(k for m, k in runs if m == "swa")
    one = s <= W
    L = s if one else W                           # positions a chunk
    if s % L or (not one and last_only and b != 1):
        raise ValueError(f"a prompt pass of {b} x {s} positions walks whole "
                         f"chunks of {L}, one prompt a call")
    split = [_expert_stacks(rp) for rp in params["runs"]]

    def chunk(c, toks, caches):
        """Chunk c (tokens [b, L]) through every layer -> (hidden rows
        [b, L, d], the caches with its rows, the experts chosen
        [layers, b, L, k])."""
        positions = c * L + jnp.arange(L)
        valid = positions[None, :] < true_len[:, None]
        if not one:   # the chunk before moves to the first half
            caches = dict(caches, **{n: jnp.concatenate(
                [caches[n][:, :, :, L:], caches[n][:, :, :, :L]], axis=3)
                for n in ("wk", "wv")})

        def layer_of(mixer, stacks, first):
            window = mixer == "swa"
            # where the chunk's rows go in its cache = its first query's column
            at = 0 if one else L if window else c * L
            k_lo = jnp.where(c == 0, at, 0) if window else 0

            def layer(carry, xs):
                x, ck, cv = carry
                lp, i = xs
                with jax.named_scope("swa"), \
                        jax.named_scope("window" if window else "full"):
                    h32, h = _normed(cfg, x, lp["mixer_norm"])
                    route = _route_ahead(cfg, lp, h32)
                    a = lp[mixer]
                    q, k, v = _swa_qkv(cfg, a, h, positions if window else None)
                    put = lambda rows, new: jax.lax.dynamic_update_slice(
                        rows, jnp.moveaxis(new, 1, 2)[None], (first + i, 0, 0, at, 0))
                    ck, cv = put(ck, k), put(cv, v)
                    attn = banded_attention(
                        jnp.moveaxis(q, 1, 2), ck, cv, first + i, at, k_lo,
                        window=W if window else None, sm_scale=scale)
                    mixed = jnp.moveaxis(attn, 1, 2).reshape(b, L, H * hd) @ a["wo"]
                x, ys = _swa_block(cfg, lp, x, h32, h, mixed, route, valid, stacks, i)
                return (x, ck, cv), ys
            return layer

        x = params["embed"][toks].astype(F32)
        seen = {"swa": 0, "full": 0}
        chosen = []
        for (rp, stacks), (mixer, k) in zip(split, runs):
            names = ("wk", "wv") if mixer == "swa" else ("k", "v")
            (x, *rows), ys = jax.lax.scan(
                layer_of(mixer, stacks, seen[mixer]),
                (x, *(caches[n] for n in names)), (rp, jnp.arange(k)))
            caches = dict(caches, **dict(zip(names, rows)))
            seen[mixer] += k
            chosen.append(ys[1])
        return x, caches, jnp.concatenate(chosen)

    zeros = lambda layers, rows: jnp.zeros((layers, b, kvh, rows, hd), cfg.dtype)
    caches = {"k": zeros(cfg.n_layers - n_swa, s), "v": zeros(cfg.n_layers - n_swa, s),
              "wk": zeros(n_swa, L if one else 2 * L),
              "wv": zeros(n_swa, L if one else 2 * L)}
    routing = None
    if one:
        x, caches, routing = chunk(0, tokens, caches)
        if last_only:
            x = jnp.take_along_axis(x, (true_len - 1)[:, None, None], axis=1)
    elif not last_only:
        toks = jnp.moveaxis(tokens.reshape(b, s // L, L), 1, 0)
        caches, x = jax.lax.scan(
            lambda caches, t: chunk(t[1], t[0], caches)[1::-1], caches,
            (toks, jnp.arange(s // L)))
        x = jnp.moveaxis(x, 0, 1).reshape(b, s, -1)
        n_chunks = s // L
    else:
        n_chunks = jnp.max(-(-true_len // L))

        def walk(c, carry):
            caches, last, routing = carry
            x, caches, chosen = chunk(
                c, jax.lax.dynamic_slice(tokens, (0, c * L), (b, L)), caches)
            here = jnp.take_along_axis(
                x, jnp.clip(true_len - 1 - c * L, 0, L - 1)[:, None, None], axis=1)
            last = jnp.where(((true_len - 1) // L == c)[:, None, None], here, last)
            if with_routing:
                routing = jax.lax.dynamic_update_slice(routing, chosen, (0, 0, c * L, 0))
            return caches, last, routing

        caches, x, routing = jax.lax.fori_loop(
            0, n_chunks, walk,
            (caches, jnp.zeros((b, 1, cfg.d_model), F32),
             jnp.zeros((cfg.n_layers, b, s, cfg.top_k), jnp.int32)
             if with_routing else None))
    # the ring: row r takes the last position before `true_len` that is r
    # mod W; the window rows held are positions base, base + 1, ...
    base = 0 if one else (n_chunks - 2) * L
    r = jnp.arange(W)[None, :]
    p_of = true_len[:, None] - 1 - (true_len[:, None] - 1 - r) % W       # [b, W]
    at = jnp.clip(p_of - base, 0, caches["wk"].shape[3] - 1)[None, :, None, :, None]
    rows = {"k": caches["k"], "v": caches["v"],
            "wk": jnp.take_along_axis(caches["wk"], at, axis=3),
            "wv": jnp.take_along_axis(caches["wv"], at, axis=3)}
    return x, rows, [] if routing is None else [routing]


def _head(params, x, cfg: HybridConfig = None, all_heads: bool = False):
    """Features -> logits float32: the untied head, or the embedding. A head
    of several predictions (`n_pred_heads`: head j at position i scores the
    token at i + 1 + j, its columns at [j V, (j + 1) V)) gives the first
    head's V columns, read alone, the sum float32, or with `all_heads` all
    [..., n_pred_heads, V]."""
    if cfg is not None and cfg.n_pred_heads > 1:
        V = cfg.vocab_size
        w = params["lm_head"] if all_heads else params["lm_head"][:, :V]
        logits = jnp.dot(x, w, preferred_element_type=F32)
        return logits.reshape(x.shape[:-1] + (-1, V)) if all_heads else logits
    if "lm_head" in params:
        return (x @ params["lm_head"]).astype(F32)
    logits = jnp.einsum("...d,vd->...v", x, params["embed"]).astype(F32)
    if cfg is not None and cfg.logit_divisor != 1.0:
        logits = logits / cfg.logit_divisor
    return logits


def _following(tokens, true_len, first):
    """The token after each position of a right-padded prompt: the prompt
    shifted by one, `first` [b] (the token the model answered with) behind
    its last true position."""
    at = jnp.arange(tokens.shape[1])[None, :] == (true_len - 1)[:, None]
    return jnp.where(at, first[:, None], jnp.roll(tokens, -1, axis=1))


@functools.partial(jax.jit, static_argnames=("cfg", "with_mtp", "all_heads"))
@_layered_program
def forward(params, tokens, cfg: HybridConfig, with_mtp: bool = False,
            all_heads: bool = False):
    """tokens [b, s] -> logits [b, s, vocab] float32. `with_mtp`: beside
    them the prediction module's [b, s - 1, vocab]: from position i's hidden
    row and token i + 1, logits for token i + 2. `all_heads` (a head of
    `n_pred_heads` predictions): [b, s, n_pred_heads, vocab]."""
    full = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    x, _, _ = _sequence(params, tokens, full, cfg)
    with jax.named_scope("head"):
        logits = _head(params, _normed(cfg, x, params["final_norm"])[1], cfg,
                       all_heads)
    if not with_mtp:
        return logits
    feats, _, _ = _mtp_sequence(params, x[:, :-1], tokens[:, 1:], full - 1, cfg)
    with jax.named_scope("head"):
        return logits, _head(params, feats)


@functools.partial(jax.jit, static_argnames=("cfg", "with_routing"))
@_layered_program
def prefill(params, tokens, true_len, cfg: HybridConfig,
            with_routing: bool = False, first=None):
    """-> (logits at the last true position [nb, vocab] float32, state rows
    {"S": [per KDA layer [nb, H, dk, dv]], "conv": [per KDA layer
    [nb, K-1, 3 H dk]], "latent": [MLA layers, nb, s, latent_width]}; the
    runs form's rows: `_sequence_runs`). A configuration with a prediction
    module: its latent rows are the last of "latent" (it has seen the
    prompt shifted by one, the model's own first token behind it), and
    "draft" [nb] is its guess at the SECOND token of the answer.
    `with_routing` adds "routing" [expert layers, nb, s, k]: the experts
    every position chose (for a comparison that has to tell a near-tie in
    the router from an error; the engine never asks for it), with the
    module's layer last, and the module's logits at the last true position
    as "mtp_logits" [nb, vocab]. `first` [nb], where the caller knows the
    token each answer began with (a teacher-forced comparison), is fed to
    the module in place of the model's own choice."""
    pick = lambda a: jnp.take_along_axis(a, (true_len - 1)[:, None, None], axis=1)[:, 0]
    if cfg.eva_layers:
        x, rows, routing = _sequence_eva(params, tokens, true_len, cfg, last_only=True)
        last = x[:, 0]
    elif cfg.dsa_layers:
        x, rows, routing = _sequence_dsa(params, tokens, true_len, cfg, with_routing)
        last = pick(x)
    elif cfg.windowed:
        x, rows, routing = _sequence_swa(params, tokens, true_len, cfg,
                                         last_only=True, with_routing=with_routing)
        last = x[:, 0]
    else:
        x, rows, routing = _sequence(params, tokens, true_len, cfg)
        last = pick(x)
    with jax.named_scope("head"):
        logits = _head(params, _normed(cfg, last, params["final_norm"])[1], cfg)
    if cfg.n_predict and not cfg.scanned:
        if first is None:
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        feats, latent, chosen = _mtp_sequence(
            params, x, _following(tokens, true_len, first), true_len, cfg)
        with jax.named_scope("head"):
            mtp_logits = _head(params, pick(feats))
        rows["latent"] = jnp.concatenate([rows["latent"], latent[None]])
        rows["draft"] = jnp.argmax(mtp_logits, axis=-1).astype(jnp.int32)
        routing = routing + [chosen]
        if with_routing:
            rows["mtp_logits"] = mtp_logits
    if with_routing:   # the runs form: a run's expert layers come stacked
        rows["routing"] = jnp.concatenate(routing) if cfg.scanned \
            else jnp.stack(routing)
    return logits, rows


# ---------------------------------------------------------------- decode


def _mla_step_layer(cfg: HybridConfig, p, x, latent, layer: int, lengths,
                    positions, active, attn_len, walk):
    """One layer with an MLA mixer, Q new positions a slot: x [B, Q, d]
    float32 against `latent[layer]`, read-only -> (x, the positions' own
    latent rows [B, Q, latent_width], assignments landed, experts touched,
    the experts chosen [B, Q, k] or None)."""
    B, Q, _ = x.shape
    mixer, ffn = _halves(p)
    x, cur = _mla_step(cfg, mixer, x, latent, np.int32(layer), lengths, positions,
                       walk, attn_len)
    x, landed, touched, chosen = _ffn_half(
        cfg, ffn, x, jnp.broadcast_to(active[:, None], (B, Q)))
    return x, cur, landed, touched, chosen


def _decode(params, state, lengths, tokens, active, cfg: HybridConfig,
            attn_len: int, following=None):
    """Q new positions for every slot: tokens [B, Q] at positions
    `lengths[b] + 0 .. Q-1` (Q is 1, or 2 where a prediction module's draft
    rides behind the last token). Returns (state with the new rows written,
    logits [B, Q, vocab] float32, the prediction module's logits [B, Q,
    vocab] or None, [assignments landed, experts touched] summed over the
    expert layers, the experts chosen [expert layers, B, Q, k], the module's
    last).

    The module sees the main stack's hidden rows and the token AFTER each
    position: `following` [B, Q], or the main model's own greedy choice
    where none is given. The latent rows are read-only until every layer
    has run and are written once, the module's with them. A slot that is
    not `active` has length 0, reads nothing and writes nothing."""
    B, Q = tokens.shape
    x = params["embed"][tokens].astype(F32)        # [B, Q, d], float32 residual
    positions = lengths[:, None] + jnp.arange(Q)[None, :]
    latent = state["latent"]
    walk = mla.decode_walk(latent, lengths, attn_len, cfg.n_heads) \
        if latent.shape[0] else None
    S_new, conv_new, latent_cur, routing = [], [], [], []
    landed = touched = jnp.zeros((), jnp.int32)
    for p, (mixer, _) in zip(params["layers"], cfg.layer_kinds()):
        if mixer == "mla":
            x, cur, n_landed, n_touched, chosen = _mla_step_layer(
                cfg, p, x, latent, len(latent_cur), lengths, positions, active,
                attn_len, walk)
            latent_cur.append(cur)
        else:
            mixer, ffn = _halves(p)
            x, S, tail = _kda_step(cfg, mixer, x, state["S"][len(S_new)],
                                   state["conv"][len(S_new)])
            S_new.append(S)
            conv_new.append(tail)
            x, n_landed, n_touched, chosen = _ffn_half(cfg, ffn, x, active[:, None])
        landed, touched = landed + n_landed, touched + n_touched
        if chosen is not None:
            routing.append(chosen)
    with jax.named_scope("head"):
        logits = _head(params, _normed(cfg, x, params["final_norm"])[1])
    mtp_logits = None
    if cfg.n_predict:
        with jax.named_scope("mtp"):
            m = params["mtp"]
            if following is None:
                following = jnp.argmax(logits, axis=-1)
            e = params["embed"][following].astype(F32)
            both = jnp.concatenate([rms_norm(x, m["h_norm"], cfg.norm_eps),
                                    rms_norm(e, m["e_norm"], cfg.norm_eps)], -1)
            y = (both.astype(cfg.dtype) @ m["proj"]).astype(F32)
            y, cur, n_landed, n_touched, chosen = _mla_step_layer(
                cfg, m["layer"], y, latent, len(latent_cur), lengths, positions,
                active, attn_len, walk)
            latent_cur.append(cur)
            routing.append(chosen)
            landed, touched = landed + n_landed, touched + n_touched
            with jax.named_scope("head"):
                mtp_logits = _head(params, _normed(cfg, y, m["final_norm"])[1])
    if latent_cur:
        with jax.named_scope("state_write"):
            # the tile-aligned row write, with one "kv head"; position a of a
            # slot that holds nothing is still nothing (0, not a)
            rows = jnp.stack(latent_cur)                   # [L, B, Q, W]
            for a in range(Q):
                latent = write_rows(latent, rows[:, :, a, None],
                                    jnp.where(lengths > 0, lengths + a, 0))
    return ({"S": S_new, "conv": conv_new, "latent": latent}, logits, mtp_logits,
            jnp.stack([landed, touched]), routing)


def _decode_runs(params, state, lengths, tokens, cfg: HybridConfig,
                 attn_len: int):
    """`_decode` for the runs form -> (state, logits [B, vocab] float32,
    [assignments landed, experts touched] summed over the expert layers or
    None where every FFN is dense, the experts chosen: per run of expert
    layers [k, B, top_k]). A Mamba run's stacked state is the scan's CARRY:
    layer i reads its slice and writes it back in place. The K/V cache is
    read-only inside the scans (the current token's row joins the softmax as
    a term of its own, STRICT mask) and every attention layer's row is
    written once, afterwards. A slot of length 0 is idle: its attention
    reads nothing, it is routed to no expert and, on the TPU, its recurrent
    state is neither read nor written (elsewhere it runs on over whatever
    token the slot holds); it is replaced at admission."""
    B = tokens.shape[0]
    H, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = params["embed"][tokens].astype(F32)
    if cfg.embed_scale != 1.0:
        x = x * cfg.embed_scale
    k_all, v_all = state["k"], state["v"]
    # as the dense step chooses (`models/serving.py:decode_step_fused`): on a
    # TPU at shapes that tile, the kernel over each slot's live rows
    kernel = decode_attention.uses_decode_kernel(k_all, attn_len)
    if kernel:
        items = decode_attention.live_items(lengths, attn_len)
    else:
        mask = jnp.arange(attn_len)[None, :] < lengths[:, None]

    busy = lengths > 0
    step_ops = ssd if cfg.mamba2_layers else mamba
    slots = step_ops.live_slots(lengths) \
        if state["ssm"] and step_ops.uses_step_kernel(state["ssm"][0]) else None

    def mamba_layer(carry, inputs, stacks=None):
        x, ssm, conv = carry
        lp, i = inputs
        kind = "mamba2" if "mamba2" in lp else "mamba"
        with jax.named_scope("ssd" if kind == "mamba2" else "mamba"):
            _, h = _normed(cfg, x, lp["mixer_norm"])
            out, ssm, t_new = (_mamba2_step if kind == "mamba2" else _mamba_step)(
                cfg, lp[kind], h, ssm, i, slots, busy,
                jax.lax.dynamic_index_in_dim(conv, i, 0, keepdims=False))
            x = _add(cfg, x, out)
        with jax.named_scope("state_write"):
            conv = jax.lax.dynamic_update_index_in_dim(conv, t_new, i, 0)
        x, routed = _run_ffn(cfg, lp, x, busy, stacks, i)
        return (x, ssm, conv), routed or None

    def attn_layer(x, inputs, stacks=None, first=0):
        lp, layer = inputs            # `layer` among the attention layers
        with jax.named_scope("attention"):
            _, h = _normed(cfg, x, lp["mixer_norm"])
            q, k_cur, v_cur = _attn_qkv(cfg, lp["attn"], h)
            k_cur, v_cur = k_cur.astype(cfg.dtype), v_cur.astype(cfg.dtype)
            if kernel:
                attn = decode_attention.gqa_decode_attention(
                    q.reshape(B, kvh, H // kvh, hd), k_cur, v_cur, k_all, v_all,
                    layer, items, attn_len, cfg.attn_scale)
            else:
                win = (1, B, kvh, attn_len, hd)
                attn = _gqa_decode_attention(
                    q[:, :, None],
                    jax.lax.dynamic_slice(k_all, (layer, 0, 0, 0, 0), win)[0],
                    jax.lax.dynamic_slice(v_all, (layer, 0, 0, 0, 0), win)[0],
                    k_cur, v_cur, mask, cfg.attn_scale)
            x = _add(cfg, x, attn.reshape(B, H * hd) @ lp["attn"]["wo"])
        x, routed = _run_ffn(cfg, lp, x, busy, stacks,
                             layer - first if stacks else None)   # its number in the run
        return x, (k_cur, v_cur) + routed

    ssm_new, conv_new, k_cur, v_cur, counts, routing = [], [], [], [], [], []
    held, n_attn = iter(zip(state["ssm"], state["conv"])), 0
    for rp, (mixer, k) in zip(params["runs"], cfg.runs()):
        rp, stacks = _expert_stacks(rp)
        own = lambda fn, **at: functools.partial(fn, stacks=stacks, **at) \
            if stacks else fn
        if mixer != "attn":
            (x, ssm, conv), routed = jax.lax.scan(
                own(mamba_layer), (x, *next(held)), (rp, jnp.arange(k)))
            ssm_new.append(ssm)
            conv_new.append(conv)
        else:
            x, (kc, vc, *routed) = jax.lax.scan(
                own(attn_layer, first=n_attn), x, (rp, n_attn + jnp.arange(k)))
            k_cur.append(kc)
            v_cur.append(vc)
            n_attn += k
        if routed:
            counts.append(jnp.sum(routed[0], axis=0))
            routing.append(routed[1])
    if k_cur:
        with jax.named_scope("state_write"):
            k_all = write_rows(k_all, jnp.concatenate(k_cur), lengths)
            v_all = write_rows(v_all, jnp.concatenate(v_cur), lengths)
    with jax.named_scope("head"):
        _, x = _normed(cfg, x, params["final_norm"])
        logits = _head(params, x, cfg)
    return ({"ssm": ssm_new, "conv": conv_new, "k": k_all, "v": v_all}, logits,
            sum(counts) if counts else None, routing)


def _decode_eva(params, state, lengths, tokens, cfg: HybridConfig,
                attn_len: int):
    """`_decode_runs` for a stack of EVA layers -> (state, logits [B, vocab]
    float32 of the first prediction head, [chunks closed, windows closed]).
    A busy slot's token stands at position n = its length, in window n // W.
    Inside the scan the tables are read-only: each layer attends the slot's
    n % W live rows of the window region and the summaries of the n // W
    closed windows, its own row a term of its own (on the TPU a kernel over
    the live rows of the whole table, `ops/pallas/eva_decode.py`). Then every
    layer's row is written once, to row n % W (the region turns over: row 0
    again at n = W, the stale rows behind it masked by the length) and into
    the open chunk's rows; a slot whose chunk this position CLOSES
    ((n + 1) % C == 0) writes the chunk's summary to row W + n // C in the
    same step. The step at n % W == 0 so reads W / C more summaries and no
    window row. Slots close chunks and windows each at its own step; a slot
    of length 0 is idle and reads and writes nothing."""
    B = tokens.shape[0]
    W, C, H, hd = cfg.eva_window, cfg.eva_chunk, cfg.n_heads, cfg.head_dim
    x = params["embed"][tokens].astype(F32)
    k_all, v_all = state["ek"], state["ev"]
    busy = lengths > 0
    window_rows, summary_rows = lengths % W, lengths // W * (W // C)
    kernel = eva_decode.uses_decode_kernel(k_all, W, C)
    if kernel:
        blocks = eva_decode.live_blocks(lengths, W, C, attn_len)
    else:   # the window region and as many summaries as `attn_len` can show
        read = (1, B, H, W + min(k_all.shape[3] - W, -(-attn_len // C)), hd)

    def layer(x, inputs):
        lp, i = inputs
        with jax.named_scope("eva"):
            _, h = _normed(cfg, x, lp["mixer_norm"])
            a = lp["eva"]
            q, k_cur, v_cur = (t[:, 0] for t in _eva_qkv(
                cfg, a, h[:, None], lengths[:, None]))
            with jax.named_scope("attend"):
                if kernel:
                    attn = eva_decode.eva_decode_attention(
                        q, k_cur, v_cur, k_all, v_all, i, blocks, W, C, attn_len)
                else:
                    attn = eva.decode_attention(
                        q, k_cur, v_cur,
                        jax.lax.dynamic_slice(k_all, (i, 0, 0, 0, 0), read)[0],
                        jax.lax.dynamic_slice(v_all, (i, 0, 0, 0, 0), read)[0],
                        window_rows, summary_rows, W, hd ** -0.5)
            x = x + (attn.reshape(B, H * hd).astype(cfg.dtype) @ a["wo"]).astype(F32)
        return _dense_ffn(cfg, lp, x), (k_cur, v_cur)

    rp = params["runs"][0]
    x, (k_cur, v_cur) = jax.lax.scan(
        layer, x, (rp, jnp.arange(rp["mixer_norm"].shape[0])))
    closes = busy & ((lengths + 1) % C == 0)
    with jax.named_scope("eva"):
        with jax.named_scope("write"):
            k_all = write_rows(k_all, k_cur, window_rows, busy)
            v_all = write_rows(v_all, v_cur, window_rows, busy)
            mine = ((jnp.arange(C)[None, :] == (lengths % C)[:, None])
                    & busy[:, None])[None, :, None, :, None]
            ck = jnp.where(mine, k_cur[:, :, :, None], state["ck"])
            cv = jnp.where(mine, v_cur[:, :, :, None], state["cv"])
        with jax.named_scope("summarise"):
            sum_k, sum_v = jax.vmap(
                lambda k, v, phi, mu: eva.summarise(k, v, phi, mu, C, hd ** -0.5))(
                    ck, cv, rp["eva"]["phi"], rp["eva"]["mu"])
        with jax.named_scope("write"):
            at = W + lengths // C
            k_all = write_rows(k_all, sum_k[:, :, :, 0], at, closes)
            v_all = write_rows(v_all, sum_v[:, :, :, 0], at, closes)
    with jax.named_scope("head"):
        logits = _head(params, _normed(cfg, x, params["final_norm"])[1], cfg)
    closed = jnp.stack([jnp.sum(closes), jnp.sum(busy & ((lengths + 1) % W == 0))])
    return ({"ek": k_all, "ev": v_all, "ck": ck, "cv": cv}, logits,
            closed.astype(jnp.int32))


def _decode_dsa(params, state, lengths, tokens, cfg: HybridConfig,
                attn_len: int, with_rows: bool = False):
    """`_decode_runs` for a stack of sparse-attention layers -> (state,
    logits [B, vocab] float32, [assignments landed, experts touched] or
    None, the experts chosen [[layers, B, top_k]] or [], and with
    `with_rows` the lists (rows [layers, B, K], count [layers, B], own
    [layers, B]) of `ops.dsa.decode_select`). A busy slot's token stands at
    position n = its length. Inside the scan both tables are read-only:
    each layer scores the slot's n live indexer keys, lists the best
    `dsa_topk` of them (all n below that) and attends to the listed `[k ; v]`
    positions alone, its own row a term of its own where its score belongs to the
    best. Then every layer's row and key are written once, at row n. A slot
    of length 0 is idle: it scores and reads nothing."""
    B = tokens.shape[0]
    H, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = params["embed"][tokens].astype(F32)
    kv_all, ik_all = state["kv"], state["ik"]
    busy = lengths > 0
    scale = cfg.attn_scale or hd ** -0.5
    rp, stacks = _expert_stacks(params["runs"][0])

    def layer(x, xs):
        lp, i = xs
        with jax.named_scope("dsa"):
            _, h = _normed(cfg, x, lp["mixer_norm"])
            a = lp["dsa"]
            q, k, v, qi, wi, ki = (t[:, 0] for t in _dsa_inputs(
                cfg, a, h[:, None], lengths[:, None]))
            with jax.named_scope("select"):
                rows, count, own = dsa.decode_select(
                    qi, wi, ki, ik_all, i, lengths, attn_len, cfg.dsa_topk)
            with jax.named_scope("attend"):
                attn = dsa.decode_attention(
                    q.reshape(B, kvh, H // kvh, hd), k, v, kv_all, i, rows, count,
                    own, scale)
            x = _add(cfg, x, attn.reshape(B, H * hd).astype(cfg.dtype) @ a["wo"])
        x, routed = _run_ffn(cfg, lp, x, busy, stacks, i if stacks else None)
        return x, (_kv_row(k, v), ki[:, None]) + routed \
            + ((rows, count, own) if with_rows else ())

    x, ys = jax.lax.scan(layer, x, (rp, jnp.arange(cfg.n_layers)))
    with jax.named_scope("state_write"):
        kv_all = dsa.write_positions(kv_all, ys[0], lengths)
        ik_all = write_rows(ik_all, ys[1], lengths)
    with jax.named_scope("head"):
        logits = _head(params, _normed(cfg, x, params["final_norm"])[1], cfg)
    return ({"kv": kv_all, "ik": ik_all}, logits,
            jnp.sum(ys[2], axis=0) if stacks else None,
            [ys[3]] if stacks else [], ys[-3:] if with_rows else None)


def _decode_swa(params, state, lengths, tokens, cfg: HybridConfig,
                attn_len: int):
    """`_decode_runs` for a stack of window and full attention layers ->
    (state, logits [B, vocab] float32, [assignments landed, experts
    touched], the experts chosen [[layers, B, top_k]]). A busy slot's token
    stands at position n = its length. Inside the scans both caches are
    read-only and the token's own row is a term of its own: a full layer
    attends the slot's n rows; a window layer attends the min(n, W) live
    rows of the slot's RING but the one at n % W once the ring has wrapped
    (it holds position n - W, which leaves the window as n enters), so
    min(n + 1, W) keys in all. Rotated keys carry their positions, so the
    ring's order is free. Then every layer's row is written once: a full
    layer's at row n, a window layer's at row n % W, over the row that left.
    A slot of length 0 is idle: it reads and writes nothing."""
    B = tokens.shape[0]
    W, H, kvh, hd = cfg.swa_window, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = params["embed"][tokens].astype(F32)
    busy = lengths > 0
    held = jnp.minimum(lengths, W)
    left = jnp.where(lengths >= W, lengths % W, -1)     # the row that left
    kernel = decode_attention.uses_decode_kernel(state["k"], attn_len) \
        and decode_attention.uses_decode_kernel(state["wk"], W)
    if kernel:
        walks = {"full": decode_attention.live_items(lengths, attn_len),
                 "swa": decode_attention.live_items(held, W)}
    else:
        at = lambda n: jnp.arange(n)[None, :]
        masks = {"full": at(attn_len) < lengths[:, None],
                 "swa": (at(W) < held[:, None]) & (at(W) != left[:, None])}

    def layer_of(mixer, stacks, first):
        window = mixer == "swa"
        k_all, v_all = (state["wk"], state["wv"]) if window else (state["k"], state["v"])
        rows = W if window else attn_len

        def layer(x, xs):
            lp, i = xs
            with jax.named_scope("swa"), jax.named_scope("window" if window else "full"):
                h32, h = _normed(cfg, x, lp["mixer_norm"])
                route = _route_ahead(cfg, lp, h32)
                a = lp[mixer]
                q, k_cur, v_cur = (t[:, 0] for t in _swa_qkv(
                    cfg, a, h[:, None], lengths[:, None] if window else None))
                if kernel:
                    attn = decode_attention.gqa_decode_attention(
                        q.reshape(B, kvh, H // kvh, hd), k_cur, v_cur, k_all, v_all,
                        first + i, walks[mixer], rows, cfg.attn_scale,
                        skip=left if window else None)
                else:
                    read = (1, B, kvh, rows, hd)
                    attn = _gqa_decode_attention(
                        q[:, :, None],
                        jax.lax.dynamic_slice(k_all, (first + i, 0, 0, 0, 0), read)[0],
                        jax.lax.dynamic_slice(v_all, (first + i, 0, 0, 0, 0), read)[0],
                        k_cur, v_cur, masks[mixer], cfg.attn_scale)
                mixed = attn.reshape(B, H * hd).astype(cfg.dtype) @ a["wo"]
            x, ys = _swa_block(cfg, lp, x, h32, h, mixed, route, busy, stacks, i)
            return x, (k_cur, v_cur) + ys
        return layer

    cur = {"swa": ([], []), "full": ([], [])}
    seen = {"swa": 0, "full": 0}
    counts, routing = [], []
    for rp, (mixer, k) in zip(params["runs"], cfg.runs()):
        rp, stacks = _expert_stacks(rp)
        x, (k_cur, v_cur, landed, chosen) = jax.lax.scan(
            layer_of(mixer, stacks, seen[mixer]), x, (rp, jnp.arange(k)))
        seen[mixer] += k
        cur[mixer][0].append(k_cur)
        cur[mixer][1].append(v_cur)
        counts.append(jnp.sum(landed, axis=0))
        routing.append(chosen)
    with jax.named_scope("state_write"):
        new = {name: write_rows(state[name], jnp.concatenate(cur[mixer][j]), at, writes)
               if cur[mixer][j] else state[name]     # a kind without a layer
               for mixer, at, writes, names in (
                   ("full", lengths, None, ("k", "v")),
                   ("swa", lengths % W, busy, ("wk", "wv")))
               for j, name in enumerate(names)}
    with jax.named_scope("head"):
        logits = _head(params, _normed(cfg, x, params["final_norm"])[1], cfg)
    return new, logits, sum(counts), [jnp.concatenate(routing)]


@functools.partial(jax.jit, static_argnames=("cfg", "attn_len"),
                   donate_argnums=(1,))
@_layered_program
def decode_logits(params, state, lengths, tokens, active, cfg: HybridConfig,
                  attn_len: int):
    """The decode step for callers that need logits: the body of
    `decode_step` over the same slot state (DONATED) and the same `active`
    mask, returning (state, logits [B, vocab], the experts every slot chose
    [expert layers, B, k]) instead of sampling. The runs form has no
    routing ([0, B, 0]) unless it holds expert layers, and takes no `active`
    (a slot is busy iff its length is above 0). A configuration that drafts
    is checked through `verify_logits`."""
    if cfg.dsa_layers:
        state, logits, _, routing, lists = _decode_dsa(
            params, state, lengths, tokens, cfg, attn_len, with_rows=True)
        routing = jnp.concatenate(routing) if routing else \
            jnp.zeros((0, tokens.shape[0], 0), jnp.int32)
        return state, logits, routing, lists
    if cfg.scanned:
        if cfg.eva_layers:
            state, logits, _ = _decode_eva(params, state, lengths, tokens, cfg,
                                           attn_len)
        else:
            state, logits, _, routing = (
                _decode_swa if cfg.windowed else _decode_runs)(
                    params, state, lengths, tokens, cfg, attn_len)
            if routing:
                return state, logits, jnp.concatenate(routing)
        return state, logits, jnp.zeros((0, tokens.shape[0], 0), jnp.int32)
    state, logits, _, _, routing = _decode(params, state, lengths, tokens[:, None],
                                           active, cfg, attn_len)
    return state, logits[:, 0], jnp.stack(routing)[:, :, 0]


@functools.partial(jax.jit, static_argnames=("cfg", "attn_len"),
                   donate_argnums=(1,))
@_layered_program
def verify_logits(params, state, lengths, tokens, following, active,
                  cfg: HybridConfig, attn_len: int):
    """`decode_logits` for a configuration that drafts: the step's body over
    the slot state (DONATED) for the TWO positions `tokens` [B, 2] of every
    active slot, the module fed `following` [B, 2] (the token after each)
    -> (state with both positions' rows written, logits [B, 2, vocab], the
    module's logits [B, 2, vocab], the experts chosen [expert layers + 1,
    B, 2, k], the module's layer last). The caller advances `lengths` by 1
    or 2: a row past it is overwritten, as after a refused draft."""
    new, logits, mtp_logits, _, routing = _decode(
        params, state, lengths, tokens, active, cfg, attn_len, following)
    return {**new, "draft": state["draft"]}, logits, mtp_logits, jnp.stack(routing)


@functools.partial(jax.jit, static_argnames=("cfg", "attn_len"),
                   donate_argnums=(1, 2))
@_layered_program
def decode_step(params, state, lengths, tokens, active, cfg: HybridConfig,
                attn_len: int):
    """The hot decode step: state and lengths DONATED, greedy sampling on
    device. `active` [B] bool marks the slots that serve a request; an idle
    slot still computes (static shapes) but is routed to no expert, and its
    length stays 0. Returns (state, lengths + what each active slot kept,
    next tokens [B], report): ONE array crosses to the host per step, the
    tokens each slot yielded [B x T] slot-major (-1 where it yielded fewer
    than T: the count beside the tokens) followed by the counters.

    Without a prediction module T is 1. With one (T = 2) the step verifies
    `[tokens[b], state["draft"][b]]` through the main layers: the model's
    own choice after the last token is kept; if the draft was that choice,
    so is the choice after the draft. Either way the tokens are exactly the
    ones a step at a time would have chosen. The module then drafts from
    the last kept position, and the rows both positions wrote stay: `lengths`
    alone says what is live, and a row past it is overwritten. Counters
    behind the tokens: [assignments landed, experts touched, drafts
    proposed, drafts accepted].

    The runs form keeps the dense engine's rule for idle slots (length 0
    stays 0); over dense FFNs it has no counters and its report is the
    tokens, with expert layers it counts [assignments landed, experts
    touched] behind them as the list form does. A stack of EVA layers counts
    the [chunks, windows] its slots closed behind them."""
    if cfg.eva_layers:
        state, logits, closed = _decode_eva(params, state, lengths, tokens, cfg,
                                            attn_len)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return state, lengths + (lengths > 0), nxt, jnp.concatenate([nxt, closed])
    if cfg.scanned:
        state, logits, counters, *_ = (
            _decode_dsa if cfg.dsa_layers else _decode_swa if cfg.windowed
            else _decode_runs)(params, state, lengths, tokens, cfg, attn_len)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        report = nxt if counters is None else jnp.concatenate([nxt, counters])
        return state, lengths + (lengths > 0), nxt, report
    if not cfg.n_predict:
        state, logits, _, counters, _ = _decode(
            params, state, lengths, tokens[:, None], active, cfg, attn_len)
        nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        return (state, lengths + active.astype(jnp.int32), nxt,
                jnp.concatenate([nxt, counters]))
    draft = state["draft"]
    state, logits, mtp_logits, counters, _ = _decode(
        params, state, lengths, jnp.stack([tokens, draft], axis=1), active, cfg,
        attn_len)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)              # [B, 2]
    accepted = (nxt[:, 0] == draft) & active
    last = jnp.where(accepted, nxt[:, 1], nxt[:, 0])
    # the module's row at the last kept position guesses the token after `last`
    guess = jnp.argmax(mtp_logits, axis=-1).astype(jnp.int32)
    state["draft"] = jnp.where(accepted, guess[:, 1], guess[:, 0])
    kept = jnp.stack([nxt[:, 0], jnp.where(accepted, nxt[:, 1], -1)], axis=1)
    drafts = jnp.stack([jnp.sum(active), jnp.sum(accepted)]).astype(jnp.int32)
    return (state, lengths + jnp.where(active, 1 + accepted.astype(jnp.int32), 0),
            last, jnp.concatenate([kept.reshape(-1), counters, drafts]))


@functools.partial(jax.jit, static_argnames=("cfg",))
@_layered_program
def _prefill_first(params, tokens, true_len, cfg: HybridConfig):
    logits, rows = prefill(params, tokens, true_len, cfg)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), rows


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _write_state(state, lengths, tokens, slots, rows, true_len, first):
    """Admission: a prefill's state rows into the DONATED slot state (a
    slot's recurrent state is REPLACED, which is its reset). `slots`
    entries equal to the number of slots are batch padding and are
    dropped. `tokens` is not donated (the step in flight reads it)."""
    if "ik" in state:
        return _write_dsa(state, lengths, tokens, slots, rows, true_len, first)
    if "wk" in state:
        return _write_swa(state, lengths, tokens, slots, rows, true_len, first)
    if "ssm" in state:
        return _write_runs(state, lengths, tokens, slots, rows, true_len, first)
    if "ek" in state:
        return _write_eva(state, lengths, tokens, slots, rows, true_len, first)
    with jax.named_scope("state_write"):
        put = lambda whole, part: whole.at[slots].set(part, mode="drop")
        bucket = rows["latent"].shape[2]
        state = {**{k: put(state[k], rows[k]) for k in state if k == "draft"},
                 "S": [put(a, r) for a, r in zip(state["S"], rows["S"])],
                 "conv": [put(a, r) for a, r in zip(state["conv"], rows["conv"])],
                 "latent": state["latent"].at[:, slots, :, :bucket].set(
                     rows["latent"][:, :, None], mode="drop")}
    return (state, lengths.at[slots].set(true_len, mode="drop"),
            tokens.at[slots].set(first, mode="drop"))


def _write_runs(state, lengths, tokens, slots, rows, true_len, first):
    """`_write_state` for the runs form: slots on the second axis of every
    stacked leaf; the K/V rows of the prompt's bucket."""
    with jax.named_scope("state_write"):
        put = lambda whole, part: whole.at[:, slots].set(part, mode="drop")
        bucket = rows["k"].shape[3]
        kv = lambda whole, part: whole.at[:, slots, :, :bucket].set(part, mode="drop")
        state = {"ssm": [put(a, r) for a, r in zip(state["ssm"], rows["ssm"])],
                 "conv": [put(a, r) for a, r in zip(state["conv"], rows["conv"])],
                 "k": kv(state["k"], rows["k"]), "v": kv(state["v"], rows["v"])}
    return (state, lengths.at[slots].set(true_len, mode="drop"),
            tokens.at[slots].set(first, mode="drop"))


def _write_dsa(state, lengths, tokens, slots, rows, true_len, first):
    """`_write_state` for a stack of sparse-attention layers: the prompt's
    `[k ; v]` blocks and indexer keys over the first rows of the slots'
    tables. What the last occupant left behind them stays, masked by the
    length: a key is scored only below it."""
    with jax.named_scope("state_write"):
        kv, ik = rows["kv"], rows["ik"]
        state = {"kv": state["kv"].at[:, slots, :kv.shape[2]].set(kv, mode="drop"),
                 "ik": state["ik"].at[:, slots, :, :ik.shape[3]].set(ik, mode="drop")}
    return (state, lengths.at[slots].set(true_len, mode="drop"),
            tokens.at[slots].set(first, mode="drop"))


def _write_swa(state, lengths, tokens, slots, rows, true_len, first):
    """`_write_state` for a stack of window and full attention layers: a
    full layer's rows over the first rows of the slots' tables, a window
    layer's ring whole. What the last occupant left behind a short prompt
    stays, masked by the length until the ring has wrapped over it."""
    with jax.named_scope("state_write"):
        bucket = rows["k"].shape[3]
        kv = lambda whole, part: whole.at[:, slots, :, :bucket].set(part, mode="drop")
        ring = lambda whole, part: whole.at[:, slots].set(part, mode="drop")
        state = {"k": kv(state["k"], rows["k"]), "v": kv(state["v"], rows["v"]),
                 "wk": ring(state["wk"], rows["wk"]),
                 "wv": ring(state["wv"], rows["wv"])}
    return (state, lengths.at[slots].set(true_len, mode="drop"),
            tokens.at[slots].set(first, mode="drop"))


def _write_eva(state, lengths, tokens, slots, rows, true_len, first):
    """`_write_state` for a stack of EVA layers: the open window's rows over
    the table's window region, the prompt's summaries over the first rows of
    its summary region, the open chunk's rows. What the prompt left no row
    for arrives as zeros and is stale either way."""
    with jax.named_scope("eva"), jax.named_scope("write"):
        W, n_sum = rows["win_k"].shape[3], rows["sum_k"].shape[3]
        table = lambda whole, win, summ: whole.at[:, slots, :, :W].set(
            win, mode="drop").at[:, slots, :, W:W + n_sum].set(summ, mode="drop")
        put = lambda whole, part: whole.at[:, slots].set(part, mode="drop")
        state = {"ek": table(state["ek"], rows["win_k"], rows["sum_k"]),
                 "ev": table(state["ev"], rows["win_v"], rows["sum_v"]),
                 "ck": put(state["ck"], rows["ck"]),
                 "cv": put(state["cv"], rows["cv"])}
    return (state, lengths.at[slots].set(true_len, mode="drop"),
            tokens.at[slots].set(first, mode="drop"))


class HybridCache:
    """Per-slot state of the hybrid model for `ContinuousBatchingEngine`:
    per KDA layer the state S [slots, H, dk, dv] float32 and the convolution
    tail [slots, K-1, 3 H dk]; for the MLA layers, a prediction module's
    last, the latent rows [layers, slots, 1, max_len, latent_width]; with a
    module, the token it drafted for each slot [slots]."""

    programs = programs_ahead.Direct   # the engine's list, if it keeps one

    def __init__(self, cfg: HybridConfig, num_slots: int, max_len: int):
        self.cfg, self.num_slots, self.max_len = cfg, num_slots, max_len
        kinds = [m for m, _ in cfg.layer_kinds()]
        self.n_kda, self.n_mla = kinds.count("kda"), kinds.count("mla")
        self.n_latent = self.n_mla + cfg.n_predict
        H, dk = cfg.kda_heads, cfg.kda_head_dim
        self.state = {
            "S": [jnp.zeros((num_slots, H, dk, dk), F32) for _ in range(self.n_kda)],
            "conv": [jnp.zeros((num_slots, cfg.conv_kernel - 1, 3 * H * dk), cfg.dtype)
                     for _ in range(self.n_kda)],
            # one "kv head", so that `ops.cache.write_rows` takes it as is
            "latent": jnp.zeros((self.n_latent, num_slots, 1, max_len,
                                 cfg.latent_width), cfg.dtype)}
        self.prefill_args = {"state_layers": self.n_kda,
                             "latent_layers": self.n_latent}
        self.counters: Tuple[str, ...] = ("expert_assignments", "experts_touched")
        # the most tokens one step yields a slot: a draft that holds is a second
        self.step_tokens = 1 + cfg.n_predict
        if cfg.n_predict:
            self.state["draft"] = jnp.zeros((num_slots,), jnp.int32)
            self.counters += ("draft_proposed", "draft_accepted")

    def max_prefill_batch(self, bucket: int) -> int:
        return max(1, min(4, self.cfg.prefill_tokens // bucket))

    def prefill(self, params, tokens, lens):
        return self.programs.run(("admit",) + tokens.shape, _prefill_first,
                                 params, tokens, lens, self.cfg)

    def write(self, lengths, tokens, slots, rows, lens, first):
        self.state, lengths, tokens = _write_state(
            self.state, lengths, tokens, slots, rows, lens, first)
        return lengths, tokens

    def decode(self, params, lengths, tokens, attn_len, active_slots):
        active = np.zeros((self.num_slots,), bool)
        active[list(active_slots)] = True
        self.state, lengths, nxt, report = self.programs.run(
            ("decode", attn_len), decode_step,
            params, self.state, lengths, tokens, active, self.cfg, attn_len)
        return lengths, nxt, report

    def lowered(self, key, params, state, lengths, tokens):
        """The programs of a key of the engine's list, lowered from abstract
        arguments (`models/programs.py`): a step's one, or an admission's
        prompt pass and the write of its rows."""
        int32 = programs_ahead.int32
        if key[0] == "decode":
            active = jax.ShapeDtypeStruct((self.num_slots,), np.bool_)
            return [decode_step.lower(params, state, lengths, tokens, active,
                                      self.cfg, key[1])]
        nb, bucket = key[1:]
        prefill = _prefill_first.lower(params, int32(nb, bucket), int32(nb), self.cfg)
        first, rows = programs_ahead.outputs(prefill, params)
        return [prefill, _write_state.lower(state, lengths, tokens, int32(nb), rows,
                                            int32(nb), first)]

    def step_args(self, positions: List[int], attn_len: int) -> Dict[str, int]:
        """What one decode step moved, known on the host at dispatch, from
        the busy slots' `positions`: `state_slots` the busy slots whose KDA
        state it needs, `latent_rows` the live rows of the busy slots, which
        the attention has to read (of the span's `num_slots x attn_len`,
        which the einsum form reads; the row write moves the blocks of the
        span's `active` slots)."""
        return {"state_slots": len(positions) if self.n_kda else 0,
                "latent_rows": sum(positions) if self.n_latent else 0}


class RunsCache(HybridCache):
    """Per-slot state of the runs form: per Mamba run the SSM state
    (Mamba-1: [k, slots, d_state, d_inner], `ops/mamba.py`; Mamba-2:
    [k, slots, N, H P], a matrix a head, `ops/ssd.py`; float32, channels
    minor) and the convolution tail [k, slots, K-1, channels]; for the
    attention layers K and V [layers, slots, kv_heads, max_len, head_dim],
    written by `ops.cache.write_rows` and read by
    `ops.pallas.decode_attention` like the dense cache. Every leaf is donated
    whole to each call. The entry points are `HybridCache`'s: the jitted
    programs branch on the configuration. Both kinds of state here are the
    old two (a row a position for ever, a state of fixed size); a run of EVA
    layers keeps a third: `EvaCache`.

    A stack with expert layers reports the list form's two counters. One
    with Mamba-2 mixers names its prompt buckets (`prompt_bucket`): its
    prompts run to thousands of positions, where a power of two pads an
    8200-token prompt to 16383."""

    step_tokens = 1

    def __init__(self, cfg: HybridConfig, num_slots: int, max_len: int):
        self.cfg, self.num_slots, self.max_len = cfg, num_slots, max_len
        runs = cfg.runs()
        self.n_mamba = sum(k for m, k in runs if m in ("mamba", "mamba2"))
        self.n_attn = sum(k for m, k in runs if m == "attn")
        kv = (self.n_attn, num_slots, cfg.n_kv_heads, max_len, cfg.head_dim)
        ssm = {"mamba": (cfg.d_state, cfg.d_inner),
               "mamba2": (cfg.ssd_state, cfg.ssd_inner)}
        conv = {"mamba": cfg.d_inner, "mamba2": cfg.ssd_inner + 2 * cfg.ssd_state}
        self.state = {
            "ssm": [jnp.zeros((k, num_slots) + ssm[m], F32)
                    for m, k in runs if m in ssm],
            "conv": [jnp.zeros((k, num_slots, cfg.conv_kernel - 1, conv[m]),
                               cfg.dtype) for m, k in runs if m in conv],
            "k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype)}
        self.prefill_args = {"state_layers": self.n_mamba, "kv_layers": self.n_attn}
        self.counters: Tuple[str, ...] = ("expert_assignments", "experts_touched") \
            if "moe" in cfg.run_ffns() else ()
        if cfg.mamba2_layers:
            self.prompt_bucket = self._whole_blocks

    def _whole_blocks(self, n: int) -> int:
        """Powers of two up to 2048 positions, whole multiples of 2048 past
        them (eight chunks of the SSD scan, two blocks of the flash kernel)."""
        b = 8
        while b < min(n, 2048):
            b *= 2
        return min(b if n <= 2048 else -(-n // 2048) * 2048, self.max_len - 1)

    def max_prefill_batch(self, bucket: int) -> int:
        return max(1, min(8, self.cfg.prefill_tokens // bucket))

    def step_args(self, positions: List[int], attn_len: int) -> Dict[str, int]:
        """`state_slots`: the busy slots, whose recurrent state the step
        needs; `kv_rows`: the positions they hold, which every attention
        layer reads (`write_rows` moves the K/V blocks of the span's
        `active` slots)."""
        return {"state_slots": len(positions) if self.n_mamba else 0,
                "kv_rows": sum(positions) if self.n_attn else 0}


class EvaCache(RunsCache):
    """Per-slot state of a stack of EVA layers, the third kind: neither a
    row a position for ever nor a state of fixed size. Per layer and slot a
    table "ek", "ev" [layers, slots, H, W + max_len / C, hd] of two regions:
    rows [0, W) hold the open window's keys and values, position n at row
    n % W, so a slot REUSES the region every W positions (what lies past
    n % W is stale and masked by the length, never cleared); rows from W on
    hold a summary a closed chunk, one more every C positions, of which the
    n // W x W / C of closed windows are visible. `max_len` positions cost
    W + max_len / C rows, not max_len. Beside them "ck", "cv"
    [layers, slots, H, C, hd]: the open chunk's rows once more, from its
    row 0, which the step that closes the chunk summarises. Written by
    `ops.cache.write_rows` at the rows the step names, read by
    `ops.pallas.eva_decode` over the live rows."""

    counters = ("chunks_closed", "windows_closed")

    def __init__(self, cfg: HybridConfig, num_slots: int, max_len: int):
        self.cfg, self.num_slots, self.max_len = cfg, num_slots, max_len
        cfg.runs()    # one run of EVA layers, or an error
        L, H, hd = cfg.n_layers, cfg.n_heads, cfg.head_dim
        table = (L, num_slots, H, cfg.eva_window + -(-max_len // cfg.eva_chunk), hd)
        chunk = (L, num_slots, H, cfg.eva_chunk, hd)
        self.state = {"ek": jnp.zeros(table, cfg.dtype), "ev": jnp.zeros(table, cfg.dtype),
                      "ck": jnp.zeros(chunk, cfg.dtype), "cv": jnp.zeros(chunk, cfg.dtype)}
        self.prefill_args = {"eva_layers": L}

    def prompt_bucket(self, n: int) -> int:
        """Whole windows: the prompt pass walks them, so a prompt pays for
        the windows it has and not for a power of two."""
        W = self.cfg.eva_window
        return -(-n // W) * W

    def step_args(self, positions: List[int], attn_len: int) -> Dict[str, int]:
        """The rows a layer reads for the busy slots, summed: `window_rows`
        of the open windows (n % W a slot; its own row is not in the table
        yet), `summary_rows` of the closed windows' chunks (n // W x W / C)."""
        W, C = self.cfg.eva_window, self.cfg.eva_chunk
        return {"window_rows": sum(n % W for n in positions),
                "summary_rows": sum(n // W * (W // C) for n in positions)}


class DsaCache(RunsCache):
    """Per-slot state of a stack of sparse-attention layers, the fourth
    kind: TWO rows a position for ever, one for the attention and one for
    the selector that decides which of the first are read. "kv"
    [layers, slots, max_len, 2 kvh, hd] holds a position's keys and values
    as ONE block `[k ; v]` (at 4 kv heads of 128 an (8, 128) tile of bf16),
    so that a chosen position is one contiguous read a DMA can name; "ik"
    [layers, slots, 1, max_len, key_width] its indexer key in whole tiles
    of lanes. A decode step scores a slot's live keys (all n of them) and
    reads `dsa_topk` of its n K/V positions. Written at position n by
    `ops.dsa.write_positions` and `ops.cache.write_rows`, read by
    `ops.dsa.decode_select` / `decode_attention`. Prompts come in whole
    chunks (`prompt_bucket`)."""

    def __init__(self, cfg: HybridConfig, num_slots: int, max_len: int):
        self.cfg, self.num_slots, self.max_len = cfg, num_slots, max_len
        cfg.runs()    # one run of sparse-attention layers, or an error
        L = cfg.n_layers
        kv = (L, num_slots, max_len, 2 * cfg.n_kv_heads, cfg.head_dim)
        ik = (L, num_slots, 1, max_len, dsa.key_width(cfg.dsa_head_dim))
        self.state = {"kv": jnp.zeros(kv, cfg.dtype), "ik": jnp.zeros(ik, cfg.dtype)}
        self.prefill_args = {"dsa_layers": L}
        self.counters: Tuple[str, ...] = ("expert_assignments", "experts_touched") \
            if "moe" in cfg.run_ffns() else ()

    def prompt_bucket(self, n: int) -> int:
        """Powers of two up to eight chunks of `dsa_chunk` queries, whole
        multiples of eight chunks past them (4096 positions at the published
        512), up to the slot's whole length."""
        unit, b = 8 * self.cfg.dsa_chunk, 8
        while b < min(n, unit):
            b *= 2
        return min(b if n <= unit else -(-n // unit) * unit, self.max_len)

    def step_args(self, positions: List[int], attn_len: int) -> Dict[str, int]:
        """`kv_rows`: the positions the busy slots hold, what a step that
        read every row would read; `index_rows`: the indexer keys the step
        scores, a layer (the same positions); `selected_rows`: the K/V rows
        it reads, a layer: min(n, `dsa_topk`) a slot."""
        return {"kv_rows": sum(positions), "index_rows": sum(positions),
                "selected_rows": sum(min(n, self.cfg.dsa_topk) for n in positions)}


class SwaCache(RunsCache):
    """Per-slot state of a stack of window and full attention layers, the
    fifth kind: a slot's live rows differ BY LAYER KIND inside one step. A
    full layer keeps a row a position for ever, "k", "v" [full layers,
    slots, kvh, max_len, hd]; a window layer keeps a RING of `swa_window`
    rows, "wk", "wv" [window layers, slots, kvh, W, hd], position n at row
    n % W: the step writes row n over row n - W and reads the min(n + 1, W)
    rows of the window, ACROSS the wrap (rotated keys carry their positions,
    so the ring's order is free). A new occupant's prompt leaves its last
    min(n, W) rows at their ring places; what lies past a short prompt's
    rows is stale and masked by the length until the ring has wrapped over
    it. `max_len` positions cost a window layer W rows. Written by
    `ops.cache.write_rows`, read by `ops.pallas.decode_attention` (a window
    layer with the row that left named, `skip`).

    Prompts of more than one window all pass at ONE bucket, the slot's
    whole length, one prompt a call: the pass walks only the windows that
    hold a token (`_sequence_swa`), so one program serves them all. Shorter
    prompts take powers of two from 512. Where the decode kernel runs, the
    step's one program is that of the slot's whole length (the kernel walks
    live rows, whatever the bucket)."""

    def __init__(self, cfg: HybridConfig, num_slots: int, max_len: int):
        self.cfg, self.num_slots, self.max_len = cfg, num_slots, max_len
        runs = cfg.runs()
        self.n_swa = sum(k for m, k in runs if m == "swa")
        self.n_full = cfg.n_layers - self.n_swa
        rows = lambda layers, n: jnp.zeros(
            (layers, num_slots, cfg.n_kv_heads, n, cfg.head_dim), cfg.dtype)
        self.state = {"k": rows(self.n_full, max_len), "v": rows(self.n_full, max_len),
                      "wk": rows(self.n_swa, cfg.swa_window),
                      "wv": rows(self.n_swa, cfg.swa_window)}
        self.prefill_args = {"window_layers": self.n_swa, "full_layers": self.n_full,
                             "chunk": cfg.swa_window}
        self.counters = ("expert_assignments", "experts_touched")

    def prompt_bucket(self, n: int) -> int:
        W = self.cfg.swa_window
        if n > W:   # walked: whole windows of the slot's whole length
            return self.max_len // W * W
        b = min(512, W)
        while b < n:
            b *= 2
        return min(b, W)

    def max_prefill_batch(self, bucket: int) -> int:
        return 1

    def step_len(self, attn_len: int) -> int:
        """The attention length the step's program is built for: the slot's
        whole length where the decode kernel runs, else the bucket."""
        if decode_attention.uses_decode_kernel(self.state["k"], self.max_len):
            return self.max_len
        return attn_len

    def decode(self, params, lengths, tokens, attn_len, active_slots):
        return super().decode(params, lengths, tokens, self.step_len(attn_len),
                              active_slots)

    def step_args(self, positions: List[int], attn_len: int) -> Dict[str, int]:
        """The rows ONE layer of each kind reads for the busy slots, summed:
        `window_rows` min(n, W) a slot, `full_rows` n a slot (a stack of
        full layers would read `full_rows` in every layer); `wrapped_slots`:
        the busy slots whose length has reached the window, so that the row
        at n % W leaves the ring this step."""
        W = self.cfg.swa_window
        return {"window_rows": sum(min(n, W) for n in positions),
                "full_rows": sum(positions),
                "wrapped_slots": sum(n >= W for n in positions)}
