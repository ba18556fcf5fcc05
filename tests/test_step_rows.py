"""The serving step's two widths (models/step_rows.py): every product,
norm and elementwise chain runs on the step's real tokens at the compact
width T_c = roundup(budget, tile_q) + B . spec_len, the tile kernels on
the flat packing's T rows. On the CPU, at toy widths: the map itself,
each of the six served blocks against its own `forward` through a step
that nearly fills T_c, the pad positions' tokens never read, and the
lowered step's products at T_c rows.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.engine.engine import ServeEngine
from paddle_tpu.models.step_rows import step_rows
from paddle_tpu.obs.metrics import MetricsRegistry

pytestmark = pytest.mark.serve

VOCAB = 61
POISON = VOCAB - 1
TOL = 2e-4


# -- the map ----------------------------------------------------------------

def test_the_map_takes_each_tile_s_real_prefix_in_order():
    """Tiles of 4 over three rows (a chunk of 6 from position 10, a
    decode row, a speculative row of 3) and the null row's pad tiles:
    the real positions in flat order, zeros after them, and back; the
    last indices as compact rows."""
    tq, b, s = 4, 3, 3
    t = 8 + b * 4                                  # budget 8, 3 rows
    tile_rows = jnp.asarray([0, 0, 1, 2, 3], jnp.int32)
    tile_offs = jnp.asarray([0, 4, 0, 0, 0], jnp.int32)
    q_starts = jnp.asarray([10, 7, 3, 0], jnp.int32)
    context_lens = jnp.asarray([16, 8, 6, 0], jnp.int32)
    last_idx = jnp.asarray([[5, 5, 5], [8, 8, 8], [12, 13, 14]], jnp.int32)
    packing = step_rows(tile_rows, tile_offs, q_starts, context_lens,
                        last_idx, t)
    want = [0, 1, 2, 3, 4, 5, 8, 12, 13, 14]
    assert packing.real.shape == (8 + b * s,) == (17,)
    assert packing.flat_of.tolist() == want + [t] * 7
    assert packing.compact_of.tolist() == [
        want.index(i) if i in want else 17 for i in range(t)]
    assert packing.real.tolist() == [True] * len(want) + [False] * 7
    assert np.flatnonzero(packing.flat_real).tolist() == want
    x = jnp.arange(t, dtype=jnp.float32)[:, None] + 1.0
    xc = packing.compact(x)
    assert xc[:, 0].tolist() == [w + 1.0 for w in want] + [0.0] * 7
    back = packing.expand(xc)
    assert back[:, 0].tolist() == [i + 1.0 if i in want else 0.0
                                   for i in range(t)]
    assert packing.last.tolist() == [[5, 5, 5], [6, 6, 6], [7, 8, 9]]


def test_a_width_with_no_padding_to_take_moves_nothing():
    """tile_q 1: T_c is T, the moves are the identity."""
    packing = step_rows(jnp.asarray([0, 1, 2], jnp.int32),
                        jnp.zeros((3,), jnp.int32),
                        jnp.asarray([0, 4, 0], jnp.int32),
                        jnp.asarray([1, 5, 0], jnp.int32),
                        jnp.asarray([0, 1], jnp.int32), 3)
    assert packing.flat_of is None and packing.compact_of is None
    assert packing.real.shape == (3,)
    x = jnp.ones((3, 2))
    assert packing.compact(x) is x and packing.expand(x) is x


# -- the six blocks against their forward -----------------------------------

def _models():
    from paddle_tpu.models.conv_moe_lm import ConvMoELM
    from paddle_tpu.models.hybrid_lm import HybridLM
    from paddle_tpu.models.latent_moe import LatentMoELM
    from paddle_tpu.models.parallel_hybrid_lm import ParallelHybridLM
    from paddle_tpu.models.sparse_linear_lm import SparseLinearLM
    from paddle_tpu.models.transformer import CausalLM
    from paddle_tpu.models.window_moe_lm import WindowMoELM
    sel = dict(dense_len=16, kernel=4, stride=2, block=4, init_blocks=1,
               local=8, topk=1)
    return {
        "causal_lm": lambda: CausalLM(
            vocab=VOCAB, model_dim=16, num_heads=4, num_layers=2,
            ffn_dim=32, dropout=0.0, max_len=64),
        "latent_moe": lambda: LatentMoELM(
            vocab=VOCAB, model_dim=16, num_heads=2, num_layers=3, q_rank=8,
            kv_rank=8, nope_dim=4, rope_dim=4, v_dim=4, dense_dim=32,
            expert_dim=8, num_experts=8, top_k=2, max_len=64),
        "hybrid": lambda: HybridLM(
            vocab=VOCAB, model_dim=16, num_heads=4, num_kv_heads=2,
            ffn_dim=32, layer_kinds=["mamba", "window", "mamba", "full",
                                     "gmu", "cross"],
            window=8, d_inner=32, d_state=4, d_conv=4, dt_rank=2,
            max_len=64),
        "sparse_linear": lambda: SparseLinearLM(
            vocab=VOCAB, model_dim=16, num_heads=4, num_kv_heads=2,
            head_dim=4, ffn_dim=32, mixer_types=["lightning-attn",
                                                 "minicpm4"],
            la_heads=2, la_head_dim=8, sparse=sel, max_len=64),
        "parallel_hybrid": lambda: ParallelHybridLM(
            vocab=VOCAB, model_dim=16, num_heads=4, num_kv_heads=2,
            head_dim=8, ffn_dim=32, num_layers=2, ssm_heads=4,
            ssm_head_dim=4, ssm_state=8, ssm_groups=2, max_len=64),
        "conv_moe": lambda: ConvMoELM(
            vocab=VOCAB, model_dim=16, num_heads=4, num_kv_heads=2,
            ffn_dim=32, expert_dim=8, num_experts=8, top_k=2,
            layer_types=["conv", "full_attention", "conv"],
            num_dense_layers=1, max_len=64),
        "window_moe": lambda: WindowMoELM(
            vocab=VOCAB, model_dim=16, num_heads=4, num_kv_heads=2,
            head_dim=8, ffn_dim=32, expert_dim=8, num_experts=4, top_k=2,
            layer_types=["sliding_attention", "full_attention",
                         "sliding_attention"],
            mlp_layer_types=["dense", "sparse", "sparse"], window=8,
            expert_shards=2, expert_rank=1, max_len=64),
    }


class _Repeats:
    """A drafter that always proposes its whole window: the last token
    again and again, so every decode row is a speculative one."""
    k = 2

    def propose(self, tokens, room):
        return [int(tokens[-1])] * room


def _spied(eng):
    """Record every step the engine launches: its operands, the pools it
    was handed and what it returned, as host arrays, with the plan's
    rows."""
    steps = []
    step_fn, launch = eng._step_fn, eng._launch

    def spy(variables, tokens, positions, pools, *rest):
        before = [np.asarray(p) for p in pools]
        out = step_fn(variables, tokens, positions, pools, *rest)
        steps.append(dict(
            operands=[np.asarray(tokens), np.asarray(positions)]
            + [jax.tree.map(np.asarray, r) for r in rest],
            before=before, after=[np.asarray(p) for p in out[1]],
            logits=np.asarray(out[0][0])))
        return out
    spy._cache_size = step_fn._cache_size

    def spied_launch():
        flight = launch()
        if flight is not None:
            steps[-1]["rows"] = [(row.req, row.start, row.length,
                                  row.decode) for row in flight.rows]
        return flight
    eng._step_fn, eng._launch = spy, spied_launch
    return steps, step_fn


def _real(step, t):
    """The flat positions of a recorded step that hold tokens."""
    context_lens, q_starts, tile_rows, tile_offs = step["operands"][5:9]
    return np.asarray(step_rows(
        *(jnp.asarray(a) for a in (tile_rows, tile_offs, q_starts,
                                   context_lens, step["operands"][-1])),
        t).flat_real)


@pytest.mark.parametrize("name,spec", [
    ("causal_lm", False), ("causal_lm", True), ("latent_moe", False),
    ("latent_moe", True), ("hybrid", False), ("sparse_linear", False),
    ("parallel_hybrid", False), ("conv_moe", False),
    ("window_moe", False)])
def test_a_full_step_is_the_model_s_forward(name, spec):
    """Three rows decode while a fourth's prompt fills the whole chunk
    budget: 8 + 3 of T_c's 12 rows (8 + 3 x 3 of 20 with every decode
    row speculative). Every row of every step is the model's `forward`
    over the same sequence at the same position, so the K/V rows and the
    state the steps wrote are what the next steps read; the full step run
    again with every pad position's token poisoned gives the same logits
    and pools bit for bit, and changed no pool row but its tokens' (and
    the scratch block's)."""
    model = _models()[name]()
    variables = model.init(jax.random.PRNGKey(3),
                           jnp.zeros((1, 4), jnp.int32))
    kw = dict(drafter=_Repeats()) if spec else {}
    eng = ServeEngine(model, variables, max_batch_size=4, block_size=4,
                      num_blocks=64, max_prefill_tokens=8, tile_q=4,
                      registry=MetricsRegistry(), **kw)
    assert eng.product_rows == 8 + 4 * eng.spec_len < eng.flat_tokens
    steps, step_fn = _spied(eng)
    rng = np.random.default_rng(11)
    first = [eng.add_request(rng.integers(0, POISON, n).tolist(),
                             max_new_tokens=12) for n in (3, 5, 2)]
    while not all(r.generated for r in first):
        eng.step()
    last = eng.add_request(rng.integers(0, POISON, 13).tolist(),
                           max_new_tokens=4)
    eng.run()
    assert eng._step_fn._cache_size() == 1
    eng.cache.assert_quiesced()

    seqs = {r.req_id: np.asarray(r.prompt + r.generated)
            for r in first + [last]}
    want = {rid: np.asarray(model.apply(variables, jnp.asarray(s)[None])[0])
            for rid, s in seqs.items()}
    for step in steps:
        flat, last_idx = step["operands"][0], step["operands"][-1]
        step["held"] = 0
        for i, (req, start, length, decode) in enumerate(step["rows"]):
            seq = seqs[req.req_id]
            # the row's first token: a chunk's last index is its last
            cursor = last_idx[i, 0] - (0 if decode else length - 1)
            held = False
            for pos in sorted({start + a - cursor for a in last_idx[i]}):
                # a rejected draft is not the sequence's: nothing to hold
                if not np.array_equal(flat[cursor:cursor + pos - start + 1],
                                      seq[start:pos + 1]):
                    continue
                j = list(last_idx[i]).index(cursor + pos - start)
                np.testing.assert_allclose(step["logits"][i, j],
                                           want[req.req_id][pos],
                                           atol=TOL, rtol=TOL)
                held = True
            step["held"] += held

    full = [s for s in steps if sum(row[2] for row in s["rows"])
            == 8 + 3 * (eng.spec_len if spec else 1)]
    assert full, [sum(row[2] for row in s["rows"]) for s in steps]
    assert all(s["held"] == len(s["rows"]) == 4 for s in full)
    assert sum(s["held"] for s in steps) >= len(steps)
    step = full[0]
    real = _real(step, eng.flat_tokens)
    poisoned = [a.copy() for a in step["operands"]]
    poisoned[0][~real] = POISON
    out = step_fn(eng.variables, *map(jnp.asarray, poisoned[:2]),
                  [jnp.asarray(p) for p in step["before"]],
                  *jax.tree.map(jnp.asarray, poisoned[2:]))
    np.testing.assert_array_equal(np.asarray(out[0][0]), step["logits"])
    for got, was in zip(out[1], step["after"]):
        np.testing.assert_array_equal(np.asarray(got), was)
    slots = step["operands"][9][real]
    for kind, before, after in zip(eng.cache.kinds, step["before"],
                                   step["after"]):
        if kind != "paged":
            continue
        changed = np.flatnonzero(np.any(
            before.reshape(-1, before.shape[-1])
            != after.reshape(-1, after.shape[-1]), axis=-1))
        bs = before.shape[1]
        assert set(changed[changed >= bs].tolist()) <= set(slots.tolist())


# -- the lowered step --------------------------------------------------------

def test_the_lowered_step_s_products_run_on_288_rows(monkeypatch):
    """A 512-row step (tile_q 8, chunk budget 256, batch 32), lowered on
    the CPU with the ragged kernel interpreted: no product outside the
    kernel has 512 rows, every one has 288 (or the head's 32 sampled
    rows), and the step still compiles once however the traffic goes."""
    from paddle_tpu.models.transformer import CausalLM
    monkeypatch.setitem(os.environ, "PTPU_PAGED_KERNEL", "interpret")
    model = CausalLM(vocab=VOCAB, model_dim=32, num_heads=4, num_layers=2,
                     ffn_dim=48, dropout=0.0, max_len=512)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    eng = ServeEngine(model, variables, max_batch_size=32, block_size=16,
                      num_blocks=64, max_prefill_tokens=256, tile_q=8,
                      registry=MetricsRegistry())
    t, nt, b = eng.flat_tokens, eng.num_tiles, eng.max_batch_size
    assert (t, eng.product_rows) == (512, 288)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)
    text = eng._step_fn.lower(
        eng.variables, i32(t), i32(t), eng.cache.pools, eng.cache.qpools,
        eng.cache.qscales, i32(b + 1, eng.max_blocks_per_seq), i32(b + 1),
        i32(b + 1), i32(nt), i32(nt), i32(t), i32(b, eng.spec_len)
    ).as_text()
    # the interpreted kernel's products are batched over heads: [4, 8, .]
    rows = [tuple(map(int, m.split("x")[:-1])) for m in re.findall(
        r"stablehlo\.dot_general[^\n]*->\s*tensor<([^>]*)>", text)]
    plain = [r for r in rows if len(r) == 2]
    assert plain and not any(512 in r for r in rows), rows
    assert {r[0] for r in plain} == {288, b}, rows
    monkeypatch.delitem(os.environ, "PTPU_PAGED_KERNEL")
    eng.generate([[1, 2, 3], list(range(5, 40))], max_new_tokens=3)
    eng.generate([[7] * 300], max_new_tokens=2)
    assert eng._step_fn._cache_size() == 1
