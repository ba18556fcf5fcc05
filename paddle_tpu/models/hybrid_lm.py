"""A decoder whose layers are of five kinds, read a layer from the
configuration, beside `CausalLM` and `LatentMoELM`: state-space layers,
differential attention over a window, over the whole context, and as
cross-attention over another layer's keys and values, and gated memory
units (the decoder-hybrid-decoder, arXiv:2507.06607).

    h = x + mixer_i(LN1(x));  x = h + FFN(LN2(h))

LayerNorm with scale and bias, FFN(y) = W2 (up . silu(gate)) with
[gate | up] = W1 y, no position encoding of any kind, a final LayerNorm
and the token table as the head. The mixer of layer i is `layer_kinds[i]`:

- "mamba": a Mamba-1 mixer. [u | z] = W_in y; u' = silu(conv(u)), a
  causal depthwise convolution with bias; [dt | B | C] = W_x u';
  delta = softplus(W_dt dt + b_dt); A = -exp(A_log); the selective scan
  (kernels/selective_scan.py) gives m; the output is W_out (m . silu(z)).
  The LAST mamba layer before the first "gmu" also hands its m (before
  the gate) to the gated memory units.
- "gmu": W_2 (m . silu(W_1 y)), m that memory at the same token. It
  holds no state.
- "window" / "full": differential attention (arXiv:2410.05258). Query
  heads pair up as (2p, 2p+1), key heads as (2j, 2j+1), the value of
  key pair j is [v_2j | v_2j+1], query pair p reads key pair
  p // (query pairs a key pair):
      o_p = Att(q1, k1, V) - lambda . Att(q2, k2, V)
      lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(i)
      lambda_init(i) = 0.8 - 0.6 exp(-0.3 i)
      o_p <- RMSNorm(o_p) . scale . (1 - lambda_init(i))
  then W_o with bias. "window" sees the `window` newest positions, its
  own among them; "full" the whole context.
- "cross": the same attention with a query projection of its own over
  the keys and values of the nearest "full" layer below it; it projects
  and caches none.

Served through the engine's one ragged step, the model DECLARES what
each layer keeps between steps (`cache_layout`, ENGINE.md "Cache
kinds"): a "full" layer a paged row, a "window" layer a window row, a
"mamba" layer recurrent state (the scan's state, float32, and the
convolution's tail), a "cross" layer reads its full layer's pool, a
"gmu" nothing. The cached row is a PAIR's: key pair j's [k_2j | k_2j+1]
as one key of twice the width, its value beside it, so the ragged
kernel runs unchanged at head_dim = 2 x the key width with each query
head zero on its partner's lanes (`kv_row`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from paddle_tpu.core.module import Context, Module
from paddle_tpu.kernels import paged_attention as paged
from paddle_tpu.kernels import selective_scan as scan
from paddle_tpu.models.shared_layers import PackedGatedFFN, dense
from paddle_tpu.models.step_rows import ServedModel
from paddle_tpu.nn import initializers as I
from paddle_tpu.nn.layers import Embedding, LayerNorm

KINDS = ("mamba", "window", "full", "gmu", "cross")


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


class Mamba(Module):
    """The Mamba-1 mixer. `state_shapes` is what one sequence keeps."""

    def __init__(self, model_dim, d_inner, d_state, d_conv, dt_rank, dtype,
                 param_dtype):
        super().__init__()
        self.model_dim, self.d_inner = model_dim, d_inner
        self.d_state, self.d_conv, self.dt_rank = d_state, d_conv, dt_rank
        self.dtype, self.param_dtype = dtype, param_dtype
        self.state_shapes = (
            ("ssm", (d_state, d_inner), jnp.dtype(jnp.float32)),
            ("conv", ((d_conv - 1) * d_inner,), jnp.dtype(dtype)))

    def _params(self, cx: Context):
        dn, n, pd = self.d_inner, self.d_state, self.param_dtype
        c = cx.scope("conv")
        return {
            "conv_w": c.param("weight", (self.d_conv, dn),
                              I.normal(0.0, 0.5), pd),
            "conv_b": c.param("bias", (dn,), I.normal(0.0, 0.02), pd),
            "a_log": cx.param("A_log", (dn, n), I.zeros, pd),
            "d": cx.param("D", (dn,), I.ones, pd),
        }

    def _pre(self, cx: Context, y):
        """[u | z] = W_in y, y [..., d]."""
        uz = dense(cx, "in_proj", y, 2 * self.d_inner, self.dtype,
                   self.param_dtype)
        return uz[..., :self.d_inner], uz[..., self.d_inner:]

    def _ssm_inputs(self, cx: Context, conv):
        """From the convolution's output (float32): u' in the compute
        dtype, delta, B, C in float32."""
        u = jax.nn.silu(conv).astype(self.dtype)
        return (u,) + self._selection(cx, u)

    def _selection(self, cx: Context, u):
        """delta, B, C (float32) from u'."""
        dbc = dense(cx, "x_proj", u, self.dt_rank + 2 * self.d_state,
                    self.dtype, self.param_dtype, out=jnp.float32)
        dt = dbc[..., :self.dt_rank]
        b = dbc[..., self.dt_rank:self.dt_rank + self.d_state]
        c = dbc[..., self.dt_rank + self.d_state:]
        delta = jax.nn.softplus(dense(
            cx, "dt_proj", dt, self.d_inner, self.dtype, self.param_dtype,
            bias=True, out=jnp.float32))
        return delta, b, c

    def _post(self, cx: Context, m, z):
        return dense(cx, "out_proj", m * jax.nn.silu(z), self.model_dim,
                     self.dtype, self.param_dtype)

    def forward(self, cx: Context, y):
        """y [B, T, d], whole sequences from position 0. Returns (the
        mixer's output, the memory m [B, T, d_inner])."""
        p = self._params(cx)
        u, z = self._pre(cx, y)
        k = self.d_conv
        w = p["conv_w"].astype(jnp.float32)
        uf = jnp.pad(u.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
        t = u.shape[1]
        conv = p["conv_b"].astype(jnp.float32) + sum(
            w[j] * uf[:, j:j + t] for j in range(k))
        u, delta, b, c = self._ssm_inputs(cx, conv)
        a = -jnp.exp(p["a_log"].astype(jnp.float32)).T          # [N, D]
        d = p["d"].astype(jnp.float32)

        def step(s, x):
            u_t, dt_t, b_t, c_t = x              # [B, D], [B, D], [B, N] x2
            s = (jnp.exp(dt_t[:, None, :] * a) * s
                 + (dt_t * u_t)[:, None, :] * b_t[:, :, None])
            return s, jnp.einsum("bnd,bn->bd", s, c_t) + d * u_t

        s0 = jnp.zeros((u.shape[0], self.d_state, self.d_inner), jnp.float32)
        _, m = jax.lax.scan(step, s0, tuple(
            jnp.swapaxes(v.astype(jnp.float32), 0, 1)
            for v in (u, delta, b, c)))
        m = jnp.swapaxes(m, 0, 1).astype(self.dtype)
        return self._post(cx, m, z), m

    def ragged_step(self, cx: Context, y, ssm, tails, meta, batch):
        """y [T_c, d], the step's tokens (`batch`, a
        `models.step_rows.StepBatch`; `meta` its `tile_meta`); the
        convolution and the scan run over the flat packing. Returns
        (output, memory m, new scan state, new tails)."""
        p = self._params(cx)
        packing = batch.packing
        slots, real, fresh, last = meta
        u, z = self._pre(cx, y)
        with jax.named_scope("ssm_scan"):
            conv, tails = scan.ragged_causal_conv(
                packing.expand(u), tails, p["conv_w"], p["conv_b"], slots,
                real, fresh, last, batch.tile_offs)
            # u' where the scan reads it, and compact for its products
            u = jax.nn.silu(conv).astype(self.dtype)
            delta, b, c = map(packing.expand,
                              self._selection(cx, packing.compact(u)))
            a = -jnp.exp(p["a_log"].astype(jnp.float32)).T
            m, ssm = scan.ragged_selective_scan(
                u, delta, a, b, c, p["d"], ssm, slots, real, fresh)
            m = packing.compact(m)
        return self._post(cx, m, z), m, ssm, tails


class GatedMemory(Module):
    def __init__(self, model_dim, d_inner, dtype, param_dtype):
        super().__init__()
        self.model_dim, self.d_inner = model_dim, d_inner
        self.dtype, self.param_dtype = dtype, param_dtype

    def forward(self, cx: Context, y, memory):
        with jax.named_scope("gated_memory"):
            g = dense(cx, "w1", y, self.d_inner, self.dtype,
                      self.param_dtype)
            return dense(cx, "w2", memory * jax.nn.silu(g), self.model_dim,
                         self.dtype, self.param_dtype)


class DiffAttention(Module):
    """Differential attention; `cross` gives it a query projection only
    (the keys and values are another layer's)."""

    def __init__(self, model_dim, num_heads, num_kv_heads, layer: int,
                 window, cross: bool, eps, dtype, param_dtype):
        super().__init__()
        if num_heads % 2 or num_kv_heads % 2 or \
                (num_heads // 2) % (num_kv_heads // 2):
            raise ValueError(
                f"differential attention pairs its heads: {num_heads} query "
                f"and {num_kv_heads} key heads do not pair up")
        self.model_dim, self.num_heads = model_dim, num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = model_dim // num_heads
        self.window, self.cross, self.eps = window, cross, eps
        self.lambda_init = lambda_init(layer)
        self.dtype, self.param_dtype = dtype, param_dtype
        self.scale = 1.0 / math.sqrt(self.head_dim)
        # the pair's row: kv_pairs "heads" of twice the key width
        self.kv_row = (num_kv_heads // 2, 2 * self.head_dim)
        self.groups = num_heads // self.kv_row[0]

    def _project(self, cx: Context, y):
        """y [..., d] -> (q [..., H, hd], k, v [..., Hkv, hd] or None)."""
        hd, kvd = self.head_dim, self.num_kv_heads * self.head_dim
        if self.cross:
            q = dense(cx, "q", y, self.model_dim, self.dtype,
                      self.param_dtype, bias=True)
            return q.reshape(y.shape[:-1] + (self.num_heads, hd)), None, None
        qkv = dense(cx, "qkv", y, self.model_dim + 2 * kvd, self.dtype,
                    self.param_dtype, bias=True)
        lead = y.shape[:-1]
        q = qkv[..., :self.model_dim].reshape(lead + (self.num_heads, hd))
        k = qkv[..., self.model_dim:self.model_dim + kvd].reshape(
            lead + (self.num_kv_heads, hd))
        v = qkv[..., self.model_dim + kvd:].reshape(
            lead + (self.num_kv_heads, hd))
        return q, k, v

    def _combine(self, cx: Context, att):
        """att [..., H, 2 hd]: each query head's attention over its
        pair's value. Subtract, normalise, project."""
        pd = self.param_dtype
        lam = [cx.param(n, (self.head_dim,), I.normal(0.0, 0.1), pd
                        ).astype(jnp.float32)
               for n in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")]
        lam = (jnp.exp(jnp.sum(lam[0] * lam[1]))
               - jnp.exp(jnp.sum(lam[2] * lam[3])) + self.lambda_init)
        lead = att.shape[:-2]
        pairs = att.astype(jnp.float32).reshape(
            lead + (self.num_heads // 2, 2, 2 * self.head_dim))
        o = pairs[..., 0, :] - lam * pairs[..., 1, :]
        var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
        scale = cx.scope("subln").param(
            "scale", (2 * self.head_dim,), I.ones, pd).astype(jnp.float32)
        o = (o * jax.lax.rsqrt(var + self.eps) * scale
             * (1.0 - self.lambda_init))
        return dense(cx, "o", o.reshape(lead + (self.model_dim,)),
                     self.model_dim, self.dtype, self.param_dtype, bias=True)

    def forward(self, cx: Context, y, kv=None):
        """Whole sequences y [B, T, d], the published two-call form.
        Returns (output, (k, v)): a cross layer is handed `kv`."""
        q, k, v = self._project(cx, y)
        if self.cross:
            k, v = kv
        b, t = y.shape[:2]
        pairs, kvp = self.num_heads // 2, self.num_kv_heads // 2
        hd = self.head_dim
        qp = q.reshape(b, t, pairs, 2, hd)
        kp = k.reshape(b, t, kvp, 2, hd)
        vp = v.reshape(b, t, kvp, 2 * hd)
        per = pairs // kvp
        kp = jnp.repeat(kp, per, axis=2)                 # pair p reads p // per
        vp = jnp.repeat(vp, per, axis=2)
        s = jnp.einsum("bqpcd,bkpcd->bpcqk", qp, kp).astype(jnp.float32) \
            * self.scale
        pos = jnp.arange(t)
        seen = pos[:, None] >= pos[None, :]
        if self.window is not None:
            seen = seen & (pos[None, :] > pos[:, None] - self.window)
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        att = jnp.einsum("bpcqk,bkpv->bqpcv", a.astype(vp.dtype), vp)
        att = att.reshape(b, t, self.num_heads, 2 * hd)
        return self._combine(cx, att), (k, v)

    def ragged_step(self, cx: Context, y, pool, table, slots, batch):
        """y [T_c, d], the step's tokens (`batch`, a
        `models.step_rows.StepBatch`), `pool` this layer's own or
        (cross) its full layer's, `table` the pool's block tables,
        `slots` the pool rows the tokens are written to (None: nothing
        to write); the kernel runs over the flat packing. Returns
        (output, pool)."""
        packing = batch.packing
        with jax.named_scope("diff_attention"):
            q, k, v = self._project(cx, y)
            t, hd = y.shape[0], self.head_dim
            kvp, width = self.kv_row
            if not self.cross:
                pool = paged.write_kv(pool, slots, k.reshape(t, kvp, width),
                                      v.reshape(t, kvp, width))
            # query head 2p sees the pair's first key, 2p+1 its second
            q = packing.expand(q)
            first = jnp.arange(self.num_heads)[:, None] % 2 == 0
            wide = jnp.concatenate([jnp.where(first, q, 0),
                                    jnp.where(first, 0, q)], axis=-1)
            att = paged.ragged_paged_attention(
                wide, pool, table, batch.context_lens, batch.q_starts,
                batch.tile_rows, batch.tile_offs, scale=self.scale,
                groups=self.groups,
                window=self.window,
                name="ragged_diff_attention")            # [T, H, 2 hd]
            out = self._combine(cx, packing.compact(att))
        return out, pool


class HybridBlock(Module):
    def __init__(self, kind: str, mixer: Module, ffn: PackedGatedFFN, eps,
                 param_dtype):
        super().__init__()
        self.kind = kind
        self.mixer = mixer
        self.ffn = ffn
        self.ln1 = LayerNorm(eps, param_dtype=param_dtype)
        self.ln2 = LayerNorm(eps, param_dtype=param_dtype)

    def finish(self, cx: Context, x, mixed):
        h = x + mixed.astype(x.dtype)
        return h + self.ffn(cx, self.ln2(cx, h))


class HybridLM(ServedModel):
    """Decoder-only LM of `HybridBlock`s, one a name of `layer_kinds`.
    A "gmu" needs a "mamba" below it and a "cross" a "full"; `window` is
    the window layers' width; d_inner, d_state, d_conv and dt_rank the
    state-space layers'. Tied head, float32 logits."""
    model_type = "hybrid_lm"

    def __init__(self, vocab: int, model_dim: int, num_heads: int,
                 num_kv_heads: int, ffn_dim: int, layer_kinds, window: int,
                 d_inner: int, d_state: int = 16, d_conv: int = 4,
                 dt_rank: int = None, eps: float = 1e-5,
                 max_len: int = 4096, dropout: float = 0.0,
                 dtype=jnp.float32, param_dtype=None):
        super().__init__()
        if dropout:
            raise ValueError("HybridLM has no dropout")
        kinds = tuple(layer_kinds)
        bad = [k for k in kinds if k not in KINDS]
        if bad:
            raise ValueError(f"unknown layer kinds {bad}; there are {KINDS}")
        dt_rank = dt_rank or -(-model_dim // 16)
        param_dtype = jnp.dtype(param_dtype if param_dtype is not None
                                else dtype)
        self.config = dict(
            vocab=vocab, model_dim=model_dim, num_heads=num_heads,
            num_kv_heads=num_kv_heads, ffn_dim=ffn_dim,
            layer_kinds=list(kinds), window=window, d_inner=d_inner,
            d_state=d_state, d_conv=d_conv, dt_rank=dt_rank, eps=eps,
            max_len=max_len)
        self.vocab, self.model_dim, self.max_len = vocab, model_dim, max_len
        self.dtype, self.param_dtype = dtype, param_dtype
        self.kinds = kinds
        self.embed = Embedding(vocab, model_dim, dtype=dtype,
                               param_dtype=param_dtype,
                               embedding_init=I.normal(0.0, 1.0))
        blocks, self.source = [], {}      # layer -> the layer it reads
        memory = full = None
        for i, kind in enumerate(kinds):
            if kind == "mamba":
                mixer = Mamba(model_dim, d_inner, d_state, d_conv, dt_rank,
                              dtype, param_dtype)
                if "gmu" not in kinds[:i]:
                    memory = i
            elif kind == "gmu":
                if memory is None:
                    raise ValueError(f"layer {i}: a gmu with no mamba "
                                     "layer below it")
                mixer = GatedMemory(model_dim, d_inner, dtype, param_dtype)
                self.source[i] = memory
            else:
                if kind == "cross" and full is None:
                    raise ValueError(f"layer {i}: a cross layer with no "
                                     "full layer below it")
                mixer = DiffAttention(
                    model_dim, num_heads, num_kv_heads, i,
                    window if kind == "window" else None, kind == "cross",
                    eps, dtype, param_dtype)
                if kind == "full":
                    full = i
                elif kind == "cross":
                    self.source[i] = full
            blocks.append(HybridBlock(
                kind, mixer,
                PackedGatedFFN(model_dim, ffn_dim, dtype, param_dtype),
                eps, param_dtype))
        self.blocks = blocks
        self.norm_f = LayerNorm(eps, param_dtype=param_dtype)
        attn = next((b.mixer for b in blocks
                     if isinstance(b.mixer, DiffAttention)), None)
        # what one cached row is: key pairs of [k1 k2 | v1 v2]
        self.kv_row = attn.kv_row if attn is not None else None
        self.cache_layout = [self._layout(i) for i in range(len(kinds))]

    def _layout(self, i: int) -> dict:
        """What layer i keeps between steps (ENGINE.md "Cache kinds")."""
        kind, mixer = self.kinds[i], self.blocks[i].mixer
        if kind == "mamba":
            return {"kind": "state", "arrays": mixer.state_shapes}
        if kind == "window":
            return {"kind": "window", "window": mixer.window}
        if kind == "full":
            return {"kind": "paged"}
        if kind == "cross":
            return {"kind": "reads", "layer": self.source[i]}
        return {"kind": "none"}

    def logits(self, cx: Context, x):
        h = self.norm_f(cx, x)
        table = cx.scope("embed").param(
            "weight", (self.vocab, self.model_dim), I.normal(0.0, 1.0),
            self.param_dtype)
        return jnp.matmul(h.astype(self.dtype), table.astype(self.dtype).T,
                          preferred_element_type=jnp.float32)

    def forward(self, cx: Context, tokens):
        """tokens [B, T] -> float32 logits [B, T, V]; the published form
        over whole sequences, nothing cached."""
        if tokens.shape[1] > self.max_len:
            raise ValueError(f"sequence {tokens.shape[1]} exceeds max_len "
                             f"{self.max_len}")
        x = self.embed(cx, tokens)
        memories, kvs = {}, {}
        for i, blk in enumerate(self.blocks):
            c = cx.scope(blk._name)
            y = blk.ln1(c, x)
            m = c.scope("mixer")
            if blk.kind == "mamba":
                mixed, memories[i] = blk.mixer.forward(m, y)
            elif blk.kind == "gmu":
                mixed = blk.mixer.forward(m, y, memories[self.source[i]])
            else:
                mixed, kv = blk.mixer.forward(m, y, kvs.get(self.source.get(i)))
                if blk.kind == "full":
                    kvs[i] = kv
            x = blk.finish(c, x, mixed)
        return self.logits(cx, x)

    def trunk(self, cx: Context, batch, pools):
        """The step's layers (`models/step_rows.py` `serve_step`).
        `pools` is the cache manager's list for this model's
        `cache_layout`: each layer's arrays in layer order (a paged or a
        window pool; a state layer's arrays in the order it declared
        them), then the manager's ROWS table, int32 [rows, 1 + ring]: a
        step row's state slot, and the pool blocks of its window ring
        (logical block b of a sequence lives in ring place b mod
        ring)."""
        *arrays, rows = pools
        arrays = iter(arrays)
        meta = batch.tile_meta(rows[:, 0])
        out_pools, memories, full = [], {}, {}
        x = self.embed(cx, batch.tokens)                         # [T_c, D]
        for i, blk in enumerate(self.blocks):
            c = cx.scope(blk._name)
            y = blk.ln1(c, x)
            m = c.scope("mixer")
            if blk.kind == "mamba":
                ssm, tails = next(arrays), next(arrays)
                mixed, memories[i], ssm, tails = blk.mixer.ragged_step(
                    m, y, ssm, tails, meta, batch)
                out_pools += [ssm, tails]
            elif blk.kind == "gmu":
                mixed = blk.mixer.forward(m, y, memories[self.source[i]])
            elif blk.kind == "cross":
                mixed, _ = blk.mixer.ragged_step(
                    m, y, out_pools[full[self.source[i]]],
                    batch.block_tables, None, batch)
            else:
                pool = next(arrays)
                if blk.kind == "window":
                    table, rows_at = batch.ring_rows(rows[:, 1:],
                                                     pool.shape[1])
                else:
                    table, rows_at = batch.block_tables, batch.slots
                mixed, pool = blk.mixer.ragged_step(m, y, pool, table,
                                                    rows_at, batch)
                if blk.kind == "full":
                    full[i] = len(out_pools)
                out_pools.append(pool)
            x = blk.finish(c, x, mixed)
        return x, out_pools + [rows], None
