"""GQA/MQA decode sweep at the cache-bound point (bs 8, prompt 8192).

Decode at long prompts is KV-cache-bandwidth-bound (bench decode entry:
hbm_bound_frac ~0.4 at p8192), so shrinking the cache by
num_heads/num_kv_heads should convert almost directly into tokens/s —
this measures that claim on hardware. Measured v5e (2026-08-01,
steps=128, prefill amortized identically across rows):

    kv_heads=8 (MHA): 3.405 ms/token   2,349 tok/s   cache 818 MB
    kv_heads=2 (GQA): 1.367 ms/token   5,852 tok/s   cache 204 MB
    kv_heads=1 (MQA): 0.942 ms/token   8,493 tok/s   cache 102 MB

2.5x at GQA-4x compression, 3.6x at MQA — the cache-read roofline
moving exactly as designed (models/transformer.init_kv_caches).

Run: python tools/gqa_decode_sweep.py
"""

import _bootstrap  # noqa: F401  (repo path)

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.benchmark.harness import run_timed
from paddle_tpu.benchmark.models import LM_BASE, LM_VOCAB
from paddle_tpu.models.transformer import CausalLM


def main():
    bs, t0, steps = 8, 8192, 128
    rs = np.random.RandomState(0)
    tok = jnp.asarray(rs.randint(0, LM_VOCAB, (bs, t0)), jnp.int32)
    for kvh in (8, 2, 1):
        model = CausalLM(LM_VOCAB, max_len=t0 + steps, dtype=jnp.bfloat16,
                         num_kv_heads=kvh, **LM_BASE)
        variables = model.init(jax.random.key(0), tok[:, :64])
        gen = jax.jit(lambda v, pr: model.generate(v, pr, steps))

        def step(carry):
            # injective prompt chain (see bench._decode_bench: greedy
            # output collapses, and repeated dispatches get pool-cached)
            pr, i = carry
            o = gen(variables, pr)
            nxt = (o[:, -t0:].astype(jnp.int32) + pr + i) % LM_VOCAB
            return (nxt, i + 1), o

        sec, _, _ = run_timed(step, (tok, jnp.int32(1)), min_time=1.0)
        head_dim = LM_BASE["model_dim"] // LM_BASE["num_heads"]
        itemsize = jnp.dtype(jnp.bfloat16).itemsize
        cache_mb = (2 * LM_BASE["num_layers"] * (t0 + steps) * kvh
                    * head_dim * bs * itemsize / 1e6)
        print(f"kv_heads={kvh}: {sec / steps * 1e3:.3f} ms/token "
              f"(incl. amortized prefill), {bs * steps / sec:.0f} tok/s, "
              f"cache {cache_mb:.0f} MB")


if __name__ == "__main__":
    main()
