"""Transformer encoder-decoder (Transformer-base WMT capability).

Capability-equivalent of the reference's Transformer benchmark model
(benchmark/fluid/models/machine_translation.py + the dist_transformer.py
test model — built there from primitive fluid.layers ops; here a first-class
model family).

TPU-first design:
- Parameter names match `parallel.sharding.transformer_tp_rules`:
  q_proj/k_proj/v_proj/out_proj split on heads (tp axis), fc1/fc2 split on
  the hidden dim — Megatron-style TP falls out of the rule table with zero
  model changes.
- attention core routed through `paddle_tpu.kernels.attention` (Pallas
  flash attention on TPU, XLA reference path elsewhere); the sequence axis
  can be sharded for ring attention (parallel.ring).
- bf16-friendly: params fp32, compute dtype configurable.
- Decoding: `decode_step` exposes a KV-cache incremental step for beam
  search (ops/beam_search.py) — the capability of the reference's
  beam_search/beam_search_decode ops (operators/beam_search_op.cc).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.core.module import Context, Module, PARAMS
from paddle_tpu.kernels import attention as attn_kernel
from paddle_tpu.kernels import paged_attention as paged
from paddle_tpu.models.step_rows import ServedModel
from paddle_tpu.nn import initializers as I
from paddle_tpu.nn.layers import Dropout, Embedding, LayerNorm, Linear
from paddle_tpu.ops import functional as F
from paddle_tpu.ops.sequence import sequence_mask

NEG_INF = -1e9


def sinusoid_position_encoding(maxlen: int, dim: int) -> jnp.ndarray:
    pos = jnp.arange(maxlen, dtype=jnp.float32)[:, None]
    i = jnp.arange(dim // 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, 2 * i / dim)
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)],
                           axis=-1).astype(jnp.float32)


def init_kv_caches(layers, batch: int, max_len: int, dtype=None):
    """Zeroed per-layer KV caches for incremental decode: one
    {"k","v"} [B, max_len, Hkv, hd] dict per layer. `layers` are modules
    whose attention child exposes num_kv_heads/head_dim (DecoderLayer
    .self_attn, CausalBlock .attn). Shared by Transformer.init_cache
    and CausalLM.init_cache so the cache layout has one definition.

    Cache dtype follows the model's compute dtype (bf16 models decode
    from bf16 caches — fp32 caches doubled decode's HBM bill, and decode
    IS a cache-bandwidth workload). Softmax still runs f32 via the
    logits promotion in kernels/attention.py. Pass dtype to override."""
    first = layers[0]
    attn = getattr(first, "self_attn", None) or first.attn
    h, hd = attn.num_kv_heads, attn.head_dim
    dt = dtype if dtype is not None else attn.dtype
    return [{"k": jnp.zeros((batch, max_len, h, hd), dt),
             "v": jnp.zeros((batch, max_len, h, hd), dt)}
            for _ in layers]


class MultiHeadAttention(Module):
    """MHA with optional KV cache; names match transformer_tp_rules.

    fused_qkv=True packs the projections into one matmul (self-attention:
    [D, 3D] "qkv"; cross-attention: "q_proj" + packed [D, 2D] "kv") — the
    Megatron packing: fewer, wider matmuls tile the MXU better and halve
    dispatch count. Packing is HEAD-MAJOR (columns ordered [head, role,
    head_dim], role = q/k/v) so column-sharding the packed dim over tp
    keeps every head's q, k AND v on the same shard — a contiguous
    [q|k|v] layout would put all of q on the first shards and force
    resharding collectives at the split. Checkpoints are NOT
    interchangeable between fused and unfused layouts; default stays
    unfused."""

    def __init__(self, model_dim: int, num_heads: int, dropout: float = 0.1,
                 dtype=jnp.float32, fused_qkv: bool = False,
                 num_kv_heads: Optional[int] = None):
        """num_kv_heads < num_heads = grouped-query attention (GQA;
        num_kv_heads=1 = MQA): k/v project to fewer heads, shrinking the
        decode KV cache (and its per-token HBM read) by
        num_heads/num_kv_heads. Under tp, k_proj/v_proj column-shard —
        requires num_kv_heads*head_dim % tp == 0. Not combinable with
        fused_qkv (the packed [q|k|v] head-major layout assumes equal
        head counts)."""
        super().__init__()
        if model_dim % num_heads != 0:
            raise ValueError(
                f"model_dim {model_dim} not divisible by num_heads {num_heads}")
        self.model_dim = model_dim
        self.num_heads = num_heads
        self.head_dim = model_dim // num_heads
        self.num_kv_heads = num_kv_heads or num_heads
        if num_heads % self.num_kv_heads != 0:
            raise ValueError(
                f"num_heads {num_heads} not a multiple of num_kv_heads "
                f"{self.num_kv_heads}")
        self.fused_qkv = fused_qkv
        kv_dim = self.num_kv_heads * self.head_dim
        if fused_qkv and self.num_kv_heads != num_heads:
            raise ValueError(
                "fused_qkv packs equal-width q/k/v; use unfused "
                "projections with num_kv_heads")
        if fused_qkv:
            self.qkv = Linear(3 * model_dim, dtype=dtype)
            self.q_proj = Linear(model_dim, dtype=dtype)   # cross-attn q
            self.kv = Linear(2 * model_dim, dtype=dtype)   # cross-attn kv
        else:
            self.q_proj = Linear(model_dim, dtype=dtype)
            self.k_proj = Linear(kv_dim, dtype=dtype)
            self.v_proj = Linear(kv_dim, dtype=dtype)
        self.out_proj = Linear(model_dim, dtype=dtype)
        self.drop = Dropout(dropout)
        self.dtype = dtype

    def _split(self, x):
        b, t, _ = x.shape
        return x.reshape(b, t, self.num_heads, self.head_dim)

    def _split_kv(self, x):
        b, t, _ = x.shape
        return x.reshape(b, t, self.num_kv_heads, self.head_dim)

    def forward(self, cx: Context, q, kv=None, mask=None, causal=False,
                cache: Optional[Dict] = None, decode_pos=None,
                prefill: bool = False, segment_ids=None):
        """q: [B, Tq, D]; kv: [B, Tk, D] (None = self-attention).
        mask: broadcastable to [B, heads, Tq, Tk], True = attend.
        causal: block-wise causal masking — forwarded to the flash kernel
        (a dense causal mask would force the XLA reference path).
        segment_ids: [B, T] int32 packed-batch ids (or (q_seg, kv_seg)
        pair) — tokens attend only within their segment; handled
        block-wise by the flash kernel (kernels/flash.py), folded into a
        dense mask on the reference path. The TPU idiom for the
        reference's LoD ragged batches (lod_tensor.h:44-58).
        cache: {"k","v"} [B, Tmax, H, Hd] updated at decode_pos.
        prefill: write the cache but attend only over THIS call's
        [B, Tq] k/v (set causal=True) — the whole-prompt cache warmup.
        Attending over the full Tmax cache here would both force the
        dense path (explicit mask) and score the empty future rows:
        O(Tq x Tmax) f32, which cannot reach long contexts."""
        kv_in = q if kv is None else kv
        if self.fused_qkv and kv is None:
            b, t = q.shape[:2]
            x = self.qkv(cx, q).reshape(          # head-major: [H, 3, hd]
                b, t, self.num_heads, 3, self.head_dim)
            qh, kh, vh = x[..., 0, :], x[..., 1, :], x[..., 2, :]
        elif self.fused_qkv:
            qh = self._split(self.q_proj(cx, q))
            b, t = kv_in.shape[:2]
            x = self.kv(cx, kv_in).reshape(
                b, t, self.num_heads, 2, self.head_dim)
            kh, vh = x[..., 0, :], x[..., 1, :]
        else:
            qh = self._split(self.q_proj(cx, q))
            kh = self._split_kv(self.k_proj(cx, kv_in))
            vh = self._split_kv(self.v_proj(cx, kv_in))

        if cache is not None:
            # incremental decode: write this step's k/v at decode_pos
            k_all = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], kh.astype(cache["k"].dtype), decode_pos, axis=1)
            v_all = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], vh.astype(cache["v"].dtype), decode_pos, axis=1)
            cache = {"k": k_all, "v": v_all}
            if not prefill:
                kh, vh = k_all, v_all

        out = attn_kernel.mha(qh, kh, vh, mask=mask, causal=causal,
                              segment_ids=segment_ids,
                              dropout_rng=(cx.rng() if cx.training and
                                           self.drop.rate > 0 else None),
                              dropout_rate=(self.drop.rate if cx.training
                                            else 0.0))
        b, t = q.shape[0], q.shape[1]
        out = out.reshape(b, t, self.model_dim)
        out = self.out_proj(cx, out)
        return (out, cache) if cache is not None else (out, None)

    def ragged_step(self, cx: Context, x, kv_pool, batch, qpool=None):
        """Mixed prefill+decode step over the FLAT ragged packing
        (kernels/paged_attention.py ragged_paged_attention): x: [T_c, D]
        — the step's tokens at the compact width (`batch`, a
        `models.step_rows.StepBatch`), no batch axis. The step's k/v is
        scattered into the pool at the batch's slots [T_c] first (past
        the tokens into scratch block 0; in place when the caller
        donates the pool), then the queries are laid out in the flat
        packing's tiles and one attention launch reads the pool as it
        lies and serves every row. Returns (out [T_c, D], new_kv_pool).
        The batch's `tp` (parallel.serve_collective.ServeTP or None)
        routes the attention through an explicit shard_map island over
        the mesh's "tp" axis — heads/kv-heads device-local, metadata
        replicated; the projections around it stay GSPMD ops at global
        shapes. `qpool` = (kvq, k_scales, v_scales) threads this
        layer's int8 compressed tier into the launch: bias-encoded
        (negative) block-table entries read it in place. Writes always
        target the fp pool — slots never point at int8 blocks."""
        cx = cx.scope(self._name or type(self).__name__)  # see attend()
        t = x.shape[0]
        if self.fused_qkv:
            p = self.qkv(cx, x).reshape(       # head-major: [H, 3, hd]
                t, self.num_heads, 3, self.head_dim)
            qh, kh, vh = p[..., 0, :], p[..., 1, :], p[..., 2, :]
        else:
            qh = self.q_proj(cx, x).reshape(t, self.num_heads,
                                            self.head_dim)
            kh = self.k_proj(cx, x).reshape(t, self.num_kv_heads,
                                            self.head_dim)
            vh = self.v_proj(cx, x).reshape(t, self.num_kv_heads,
                                            self.head_dim)
        kv_pool = paged.write_kv(kv_pool, batch.slots, kh, vh)
        kvq, ksc, vsc = qpool if qpool is not None else (None,) * 3
        attend = (paged.ragged_paged_attention if batch.tp is None else
                  functools.partial(paged.ragged_paged_attention_tp,
                                    batch.tp.mesh))
        packing = batch.packing
        out = attend(packing.expand(qh), kv_pool, batch.block_tables,
                     batch.context_lens, batch.q_starts, batch.tile_rows,
                     batch.tile_offs,
                     groups=self.num_heads // self.num_kv_heads,
                     kvq_pool=kvq, k_scales=ksc,
                     v_scales=vsc)                         # [T, H, hd]
        out = self.out_proj(cx, packing.compact(out).reshape(
            t, self.model_dim))
        return out, kv_pool


class FeedForward(Module):
    def __init__(self, model_dim: int, hidden_dim: int, dropout: float = 0.1,
                 dtype=jnp.float32):
        super().__init__()
        self.fc1 = Linear(hidden_dim, dtype=dtype)
        self.fc2 = Linear(model_dim, dtype=dtype)
        self.drop = Dropout(dropout)

    def forward(self, cx: Context, x):
        return self.fc2(cx, self.drop(cx, F.relu(self.fc1(cx, x))))

    def forward_serve_tp(self, cx: Context, x, tp):
        """Megatron column-then-row MLP for the tensor-parallel serve
        step: fc1 runs as a GSPMD op with its weight column-sharded
        (activations come out feature-sharded, no collective), and the
        fc2 contraction is an explicit row-parallel island whose ONE
        allreduce uses the serving collective (int8-quantized wire by
        default, `PTPU_SERVE_ALLREDUCE=fp` for exact parity). The fc2
        bias is added AFTER the reduce — inside the island it would be
        summed tp times. Parameter paths are identical to forward()'s,
        so tp serving reads the same variables tree."""
        from paddle_tpu.parallel.serve_collective import row_parallel_matmul

        cx = cx.scope(self._name or type(self).__name__)
        h = self.drop(cx, F.relu(self.fc1(cx, x)))
        fc2 = self.fc2
        c2 = cx.scope(fc2._name or "fc2")
        w = c2.param("weight", (h.shape[-1], fc2.features),
                     fc2.kernel_init, fc2.param_dtype)
        y = row_parallel_matmul(h.astype(fc2.dtype), w.astype(fc2.dtype),
                                tp)
        if fc2.use_bias:
            b = c2.param("bias", (fc2.features,), fc2.bias_init,
                         fc2.param_dtype)
            y = y + b.astype(fc2.dtype)
        return y


class EncoderLayer(Module):
    def __init__(self, model_dim, num_heads, ffn_dim, dropout=0.1,
                 dtype=jnp.float32, fused_qkv=False):
        super().__init__()
        self.attn = MultiHeadAttention(model_dim, num_heads, dropout, dtype,
                                       fused_qkv=fused_qkv)
        self.ffn = FeedForward(model_dim, ffn_dim, dropout, dtype)
        self.ln1 = LayerNorm()
        self.ln2 = LayerNorm()
        self.drop = Dropout(dropout)

    def forward(self, cx: Context, x, mask=None, segment_ids=None):
        h, _ = self.attn(cx, self.ln1(cx, x), mask=mask,
                         segment_ids=segment_ids)
        x = x + self.drop(cx, h)
        x = x + self.drop(cx, self.ffn(cx, self.ln2(cx, x)))
        return x


class DecoderLayer(Module):
    def __init__(self, model_dim, num_heads, ffn_dim, dropout=0.1,
                 dtype=jnp.float32, fused_qkv=False):
        super().__init__()
        self.self_attn = MultiHeadAttention(model_dim, num_heads, dropout,
                                            dtype, fused_qkv=fused_qkv)
        self.cross_attn = MultiHeadAttention(model_dim, num_heads, dropout,
                                             dtype, fused_qkv=fused_qkv)
        self.ffn = FeedForward(model_dim, ffn_dim, dropout, dtype)
        self.ln1 = LayerNorm()
        self.ln2 = LayerNorm()
        self.ln3 = LayerNorm()
        self.drop = Dropout(dropout)

    def forward(self, cx: Context, x, memory, self_mask=None,
                self_causal=False, cross_mask=None, cache=None,
                decode_pos=None):
        h, new_cache = self.self_attn(cx, self.ln1(cx, x), mask=self_mask,
                                      causal=self_causal,
                                      cache=cache, decode_pos=decode_pos)
        x = x + self.drop(cx, h)
        h, _ = self.cross_attn(cx, self.ln2(cx, x), kv=memory,
                               mask=cross_mask)
        x = x + self.drop(cx, h)
        x = x + self.drop(cx, self.ffn(cx, self.ln3(cx, x)))
        return x, new_cache


class Transformer(Module):
    """Encoder-decoder Transformer-base (d=512, h=8, L=6, ffn=2048)."""

    def __init__(self, src_vocab: int, trg_vocab: int, model_dim: int = 512,
                 num_heads: int = 8, num_layers: int = 6, ffn_dim: int = 2048,
                 dropout: float = 0.1, max_len: int = 1024,
                 tie_embeddings: bool = False, dtype=jnp.float32,
                 fused_qkv: bool = False):
        super().__init__()
        self.model_dim = model_dim
        self.max_len = max_len
        self.dtype = dtype
        self.src_embed = Embedding(src_vocab, model_dim, dtype=dtype)
        self.trg_embed = (self.src_embed if tie_embeddings
                          else Embedding(trg_vocab, model_dim, dtype=dtype))
        self.enc_layers = [EncoderLayer(model_dim, num_heads, ffn_dim,
                                        dropout, dtype, fused_qkv)
                           for _ in range(num_layers)]
        self.dec_layers = [DecoderLayer(model_dim, num_heads, ffn_dim,
                                        dropout, dtype, fused_qkv)
                           for _ in range(num_layers)]
        self.enc_ln = LayerNorm()
        self.dec_ln = LayerNorm()
        self.head = Linear(trg_vocab, dtype=dtype)
        self.drop = Dropout(dropout)

    # -- encoder ----------------------------------------------------------
    def encode(self, cx: Context, src_tokens, src_lengths=None):
        t = src_tokens.shape[1]
        x = self.src_embed(cx, src_tokens) * math.sqrt(self.model_dim)
        x = x + sinusoid_position_encoding(t, self.model_dim).astype(x.dtype)
        x = self.drop(cx, x)
        mask = None
        segs = None
        if src_lengths is not None:
            valid = sequence_mask(src_lengths, t)
            mask = valid[:, None, None, :]       # cross-attn (dense, small)
            segs = valid.astype(jnp.int32)       # self-attn (flash-capable)
        for layer in self.enc_layers:
            x = layer(cx, x, segment_ids=segs)
        return self.enc_ln(cx, x), mask

    # -- decoder (teacher-forced training path) ---------------------------
    def decode_train(self, cx: Context, trg_tokens, memory, src_mask=None,
                     return_hidden: bool = False):
        t = trg_tokens.shape[1]
        x = self.trg_embed(cx, trg_tokens) * math.sqrt(self.model_dim)
        x = x + sinusoid_position_encoding(t, self.model_dim).astype(x.dtype)
        x = self.drop(cx, x)
        for layer in self.dec_layers:
            x, _ = layer(cx, x, memory, self_causal=True,
                         cross_mask=src_mask)
        x = self.dec_ln(cx, x)
        if return_hidden:
            # pre-head hidden states, for losses that fuse the vocab
            # projection (ops.fused_ce.linear_cross_entropy). Touch the
            # head params so init traces them even on this path.
            self.head(cx, x[:1, :1])
            return x
        return self.head(cx, x)

    def forward(self, cx: Context, src_tokens, trg_tokens, src_lengths=None,
                return_hidden: bool = False):
        memory, src_mask = self.encode(cx, src_tokens, src_lengths)
        return self.decode_train(cx, trg_tokens, memory, src_mask,
                                 return_hidden=return_hidden)

    # -- incremental decode (for beam search) ------------------------------
    def init_cache(self, batch: int, max_len: Optional[int] = None):
        return init_kv_caches(self.dec_layers, batch,
                              max_len or self.max_len)

    def decode_step(self, cx: Context, token, pos, memory, caches,
                    src_mask=None):
        """One decode step. token: [B] ids; pos: scalar int; returns
        (logits [B, V], new caches). Positions > pos are masked via the
        cache containing zeros + explicit length mask."""
        x = self.trg_embed(cx, token[:, None]) * math.sqrt(self.model_dim)
        pe = jax.lax.dynamic_slice_in_dim(
            sinusoid_position_encoding(self.max_len, self.model_dim),
            pos, 1, axis=0)
        x = x + pe.astype(x.dtype)[None]
        tmax = caches[0]["k"].shape[1]
        # attend only to positions <= pos
        smask = (jnp.arange(tmax)[None, None, None, :] <= pos)
        new_caches = []
        for layer, cache in zip(self.dec_layers, caches):
            x, nc = layer(cx, x, memory, self_mask=smask,
                          cross_mask=src_mask, cache=cache, decode_pos=pos)
            new_caches.append(nc)
        logits = self.head(cx, self.dec_ln(cx, x))
        return logits[:, 0], new_caches


class CausalBlock(Module):
    """Pre-LN causal self-attention + FFN block (decoder-only stack —
    no cross-attention, the GPT layer shape)."""

    def __init__(self, model_dim, num_heads, ffn_dim, dropout=0.1,
                 dtype=jnp.float32, fused_qkv=False, num_kv_heads=None):
        super().__init__()
        self.attn = MultiHeadAttention(model_dim, num_heads, dropout, dtype,
                                       fused_qkv=fused_qkv,
                                       num_kv_heads=num_kv_heads)
        self.ffn = FeedForward(model_dim, ffn_dim, dropout, dtype)
        self.ln1 = LayerNorm()
        self.ln2 = LayerNorm()
        self.drop = Dropout(dropout)

    def forward(self, cx: Context, x, mask=None, cache=None,
                decode_pos=None, prefill=False, segment_ids=None):
        # training/prefill: block-causal flash over this call's k/v;
        # decode: mask carries the <=pos constraint over the cache
        h, nc = self.attn(cx, self.ln1(cx, x), mask=mask,
                          causal=cache is None or prefill, cache=cache,
                          decode_pos=decode_pos, prefill=prefill,
                          segment_ids=segment_ids)
        x = x + self.drop(cx, h)
        x = x + self.drop(cx, self.ffn(cx, self.ln2(cx, x)))
        return x, nc

    def ragged_step(self, cx: Context, x, kv_pool, batch, qpool=None):
        cx = cx.scope(self._name or type(self).__name__)  # see attend()
        h, pools = self.attn.ragged_step(cx, self.ln1(cx, x), kv_pool,
                                         batch, qpool=qpool)
        x = x + self.drop(cx, h)
        f = (self.ffn.forward_serve_tp(cx, self.ln2(cx, x), batch.tp)
             if batch.tp is not None else self.ffn(cx, self.ln2(cx, x)))
        x = x + self.drop(cx, f)
        return x, pools


class CausalLM(ServedModel):
    """Decoder-only autoregressive LM (GPT-style).

    The reference's LM story tops out at RNN language models
    (stacked_dynamic_lstm benchmark, seq2seq book chapter); this is the
    modern-capability equivalent on the same stack the Transformer
    family uses — and the single-chip long-context flagship: causal
    attention dispatches to the Pallas flash kernel (kernels/flash.py,
    O(T) memory), and `return_hidden=True` pairs with
    ops.fused_ce.linear_cross_entropy so a [T, V] logits tensor never
    materializes — together they hold peak activation linear in T at
    16k+ token sequences.

    tie_embeddings=True (default) shares the token table with the
    output head (Embedding.attend). Served with a paged pool in every
    layer."""
    model_type = "causal_lm"

    def __init__(self, vocab: int, model_dim: int = 512,
                 num_heads: int = 8, num_layers: int = 6,
                 ffn_dim: int = 2048, dropout: float = 0.1,
                 max_len: int = 2048, tie_embeddings: bool = True,
                 dtype=jnp.float32, fused_qkv: bool = False,
                 num_kv_heads: Optional[int] = None):
        super().__init__()
        self.model_dim = model_dim
        self.max_len = max_len
        self.vocab = vocab
        self.tie_embeddings = tie_embeddings
        self.dtype = dtype
        self.embed = Embedding(vocab, model_dim, dtype=dtype)
        self.blocks = [CausalBlock(model_dim, num_heads, ffn_dim, dropout,
                                   dtype, fused_qkv,
                                   num_kv_heads=num_kv_heads)
                       for _ in range(num_layers)]
        self.ln_f = LayerNorm()
        if not tie_embeddings:
            self.head = Linear(vocab, dtype=dtype)
        self.drop = Dropout(dropout)
        self.cache_layout = [{"kind": "paged"}] * num_layers
        attn = self.blocks[0].attn
        self.kv_row = (attn.num_kv_heads, attn.head_dim)

    def serve_metadata(self) -> dict:
        """The manifest's `serve` block, flat (exports of every age
        carry this one): everything `from_serve_metadata` needs to
        rebuild the module and the engine to size its KV pools without
        touching the checkpoint."""
        attn = self.blocks[0].attn
        return {
            "model_type": "causal_lm",
            "vocab": self.vocab,
            "model_dim": self.model_dim,
            "num_heads": attn.num_heads,
            "num_kv_heads": attn.num_kv_heads,
            "head_dim": attn.head_dim,
            "num_layers": len(self.blocks),
            "ffn_dim": self.blocks[0].ffn.fc1.features,
            "max_len": self.max_len,
            "tie_embeddings": self.tie_embeddings,
            "fused_qkv": attn.fused_qkv,
            # compute dtype: the rebuilt model's activations AND the KV
            # pool's element type (a bf16 export must not come back
            # float32 with a pool twice the size)
            "dtype": jnp.dtype(self.dtype).name,
        }

    @classmethod
    def from_serve_metadata(cls, meta: dict):
        return cls(vocab=meta["vocab"], model_dim=meta["model_dim"],
                   num_heads=meta["num_heads"],
                   num_layers=meta["num_layers"], ffn_dim=meta["ffn_dim"],
                   dropout=0.0, max_len=meta["max_len"],
                   tie_embeddings=meta["tie_embeddings"],
                   fused_qkv=meta["fused_qkv"],
                   num_kv_heads=meta["num_kv_heads"],
                   # exports older than the field were all float32
                   dtype=jnp.dtype(meta.get("dtype", "float32")))

    def _head(self, cx: Context, x):
        return (self.embed.attend(cx, x) if self.tie_embeddings
                else self.head(cx, x))

    def forward(self, cx: Context, tokens, return_hidden: bool = False,
                segment_ids=None, positions=None):
        """tokens [B, T] -> logits [B, T, V] (or pre-head hidden [B, T, D]
        with return_hidden — feed ops.fused_ce.linear_cross_entropy with
        head_weights(variables)).

        segment_ids [B, T] int32: packed ragged batches — several
        documents share one row, attention never crosses a boundary (and
        the flash kernel SKIPS non-overlapping blocks, so the packed cost
        is ~sum(len_i^2), not T^2). Pair with `positions` [B, T] int32
        (position within each document) so the positional encoding
        restarts per document; defaults to global 0..T-1. The loss must
        zero-weight each document's final token (it would predict the
        next document's first token).
        """
        t = tokens.shape[1]
        if t > self.max_len:
            raise ValueError(f"sequence {t} exceeds max_len {self.max_len}")
        x = self.embed(cx, tokens) * math.sqrt(self.model_dim)
        pe = sinusoid_position_encoding(self.max_len, self.model_dim)
        if positions is not None:
            x = x + pe.astype(x.dtype)[positions]
        else:
            x = x + pe[:t].astype(x.dtype)
        x = self.drop(cx, x)
        for blk in self.blocks:
            x, _ = blk(cx, x, segment_ids=segment_ids)
        x = self.ln_f(cx, x)
        if return_hidden:
            self._head(cx, x[:1, :1])   # touch head params for init trace
            return x
        return self._head(cx, x)

    def head_weights(self, variables):
        """([D, V] weight, bias or None) for linear_cross_entropy — the
        tied table transposed, or the untied head params."""
        if self.tie_embeddings:
            return variables[PARAMS]["embed"]["weight"].T, None
        head = variables[PARAMS]["head"]
        return head["weight"], head["bias"]

    # -- incremental decode -------------------------------------------------
    def init_cache(self, batch: int, max_len: Optional[int] = None):
        return init_kv_caches(self.blocks, batch, max_len or self.max_len)

    def prefill(self, cx: Context, tokens, caches):
        """ONE parallel pass over a [B, T0] prompt that populates the KV
        caches (writes k/v for positions [0, T0) in a single
        dynamic_update_slice per layer) and returns the last position's
        logits — O(1) forwards instead of O(T0) decode_steps. Attention
        runs block-causal over the T0-length k/v (flash-capable — NOT a
        dense mask over the full cache), so prefill reaches the same
        sequence lengths training does."""
        t0 = tokens.shape[1]
        x = self.embed(cx, tokens) * math.sqrt(self.model_dim)
        pe = sinusoid_position_encoding(self.max_len, self.model_dim)[:t0]
        x = x + pe.astype(x.dtype)[None]
        new_caches = []
        for blk, cache in zip(self.blocks, caches):
            x, nc = blk(cx, x, cache=cache, decode_pos=0, prefill=True)
            new_caches.append(nc)
        return self._head(cx, self.ln_f(cx, x[:, -1:]))[:, 0], new_caches

    def trunk(self, cx: Context, batch, pools):
        """The serving step's layers (`models/step_rows.py` `serve_step`
        has the contract): the step's tokens at the compact width, one
        paged pool a layer. With the batch's int8 tier (`qpools` /
        `qscales`, the engine's in-device compressed tier; empty lists
        when compression is off) each layer's int8 pool and per-block
        scales join its attention launch, and bias-encoded block-table
        entries read them in place."""
        x = self.embed(cx, batch.tokens) * math.sqrt(self.model_dim)
        pe = sinusoid_position_encoding(self.max_len, self.model_dim)
        x = x + pe[jnp.clip(batch.positions, 0, self.max_len - 1)
                   ].astype(x.dtype)                             # [T_c, D]
        new_pools = []
        for li, (blk, kv_pool) in enumerate(zip(self.blocks, pools)):
            qpool = ((batch.qpools[li],) + tuple(batch.qscales[li])
                     if batch.qpools else None)
            x, kv_pool = blk.ragged_step(cx, x, kv_pool, batch, qpool=qpool)
            new_pools.append(kv_pool)
        return x, new_pools, None

    def logits(self, cx: Context, rows):
        return self._head(cx, self.ln_f(cx, rows))

    def decode_step(self, cx: Context, token, pos, caches):
        """One step: token [B] ids at position `pos` -> (logits [B, V],
        new caches). Mirrors Transformer.decode_step."""
        x = self.embed(cx, token[:, None]) * math.sqrt(self.model_dim)
        pe = jax.lax.dynamic_slice_in_dim(
            sinusoid_position_encoding(self.max_len, self.model_dim),
            pos, 1, axis=0)
        x = x + pe.astype(x.dtype)[None]
        tmax = caches[0]["k"].shape[1]
        smask = (jnp.arange(tmax)[None, None, None, :] <= pos)
        new_caches = []
        for blk, cache in zip(self.blocks, caches):
            x, nc = blk(cx, x, mask=smask, cache=cache, decode_pos=pos)
            new_caches.append(nc)
        return self._head(cx, self.ln_f(cx, x))[:, 0], new_caches

    def generate(self, variables, prompt, num_steps: int,
                 rng: Optional[jax.Array] = None,
                 temperature: float = 0.0) -> jax.Array:
        """KV-cached autoregressive continuation: [B, T0] prompt ->
        [B, T0+steps]. Greedy at temperature 0, else softmax sampling.
        One parallel `prefill` pass populates the caches for the whole
        prompt, then each continuation token is one O(T) decode_step
        (PipelinedLM.generate is the recompute variant; this is the
        serving-scale path)."""
        from paddle_tpu.core.module import _CtxCore
        b, t0 = prompt.shape
        if t0 < 1:
            raise ValueError("generate needs a non-empty prompt")
        total = t0 + num_steps
        if total > self.max_len:
            raise ValueError(f"prompt {t0} + steps {num_steps} exceeds "
                             f"max_len {self.max_len}")
        if temperature > 0.0 and rng is None:
            raise ValueError("sampling (temperature > 0) needs an rng")
        prompt = prompt.astype(jnp.int32)
        if num_steps == 0:
            return prompt

        def fresh_cx():
            return Context(_CtxCore(mode="apply", variables=variables,
                                    mutated={}, rng=None, rng_count=0,
                                    training=False))

        def sample(logits, i):
            # i = the position of the query that produced these logits
            if temperature > 0.0:
                return jax.random.categorical(
                    jax.random.fold_in(rng, i),
                    logits.astype(jnp.float32) / temperature
                ).astype(jnp.int32)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        logits0, caches = self.prefill(fresh_cx(), prompt,
                                       self.init_cache(b, total))
        tokens = jnp.zeros((b, total), jnp.int32).at[:, :t0].set(prompt)
        tokens = tokens.at[:, t0].set(sample(logits0, t0 - 1))

        def body(i, carry):        # i in [t0, total-1): extend by one
            tok, caches = carry
            logits, caches = self.decode_step(fresh_cx(), tok[:, i], i,
                                              caches)
            tok = jax.lax.dynamic_update_slice_in_dim(
                tok, sample(logits, i)[:, None], i + 1, axis=1)
            return tok, caches

        tokens, _ = jax.lax.fori_loop(t0, total - 1, body,
                                      (tokens, caches))
        return tokens


class BertEncoder(Module):
    """BERT-style encoder for masked-LM pretraining.

    The BASELINE.md BERT-base row ("pod-scale ICI allreduce, 8->32 chip
    scaling efficiency") — the reference itself has no BERT, so this is
    the pretraining proxy built from the same EncoderLayer stack the
    Transformer uses (q/k/v/out + fc1/fc2 names keep the tp rule table
    applicable; pre-LN layers, so LR-warmup dynamics differ from the
    original post-LN BERT). Learned position embeddings, MLM head tied
    to the token table via Embedding.attend.
    """

    def __init__(self, vocab: int = 30522, model_dim: int = 768,
                 num_heads: int = 12, num_layers: int = 12,
                 ffn_dim: int = 3072, max_len: int = 512,
                 dropout: float = 0.1, dtype=jnp.float32,
                 fused_qkv: bool = False):
        super().__init__()
        self.model_dim = model_dim
        self.dtype = dtype
        self.embed = Embedding(vocab, model_dim, dtype=dtype)
        self.pos_embed = Embedding(max_len, model_dim, dtype=dtype)
        self.layers = [EncoderLayer(model_dim, num_heads, ffn_dim,
                                    dropout, dtype, fused_qkv)
                       for _ in range(num_layers)]
        self.ln = LayerNorm()
        self.drop = Dropout(dropout)

    def forward(self, cx: Context, tokens, mask_positions=None,
                lengths=None):
        """Hidden states [B, T, D]; with `mask_positions` [B, K], tied-head
        MLM vocab logits [B, K, V] at those positions instead (static K
        keeps the pretraining step one compile)."""
        t = tokens.shape[1]
        x = self.embed(cx, tokens) + self.pos_embed(
            cx, jnp.arange(t, dtype=jnp.int32))[None]
        x = self.drop(cx, x)
        # Padding as segment ids (real=1, pad=0) rather than a dense
        # mask: keeps padded batches on the flash path (the kernel masks
        # block-wise). Pad rows attend pad rows instead of everything —
        # their outputs are garbage either way and are never selected by
        # mask_positions / pooled by callers.
        segs = None
        if lengths is not None:
            segs = sequence_mask(lengths, t).astype(jnp.int32)
        for layer in self.layers:
            x = layer(cx, x, segment_ids=segs)
        hidden = self.ln(cx, x)
        if mask_positions is None:
            return hidden
        # Pre-scoping-fix checkpoints carry a rogue "weight" param at THIS
        # module's scope (Embedding.attend once resolved in the PARENT
        # scope of embed — i.e. BertEncoder's own scope, the variables
        # root only when BertEncoder is the top-level module — so the
        # "tied" head trained an independent matrix). Silently ignoring
        # it would change this model's MLM logits — fail loudly instead.
        from paddle_tpu.core.module import _tree_get
        if _tree_get(cx._core.variables.get(PARAMS, {}),
                     cx.path + ("weight",)) is not None:
            from paddle_tpu.core.module import ModuleError
            raise ModuleError(
                "checkpoint has a root-level 'weight' param: it predates "
                "the Embedding.attend scoping fix and its MLM head was "
                "NOT tied. Migrate by renaming it into a dedicated head "
                "or folding it into params['embed']['weight'].")
        picked = jnp.take_along_axis(
            hidden, mask_positions[..., None].astype(jnp.int32), axis=1)
        return self.embed.attend(cx, picked)
