"""ResNet-50 non-conv-tail attack kit.

Round-3 device traces attributed ~8.1 ms of the 47.4 ms bs=128 train step
to non-conv work: ~5.8 ms loop fusions + ~2.3 ms layout copies. This tool
runs the two structured experiments the verdict asked for ON TPU:

1. **AUTO layouts on the donated train state**: compile the step with
   `Format(Layout.AUTO)` on state inputs AND outputs, then place the
   state in the compiler-chosen layouts. XLA then never has to
   canonicalize donated buffers between steps — the hypothesized source
   of the copy tail. Reports baseline vs AUTO ms/step.
2. **Copy/fusion census**: op_census of the compiled step (optimized
   HLO), counting copy/transpose/bitcast and fusion ops, so the copy
   tail is attributed before/after.

Usage: python tools/profile_resnet_tail.py [--bs 128] [--min-time 2.5]
"""

import argparse

import _bootstrap  # noqa: F401  (repo path)
import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bs", type=int, default=128)
    ap.add_argument("--min-time", type=float, default=2.5)
    args = ap.parse_args()

    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.layout import Format, Layout

    from paddle_tpu.benchmark.harness import run_timed
    from paddle_tpu.models import vision as V
    from paddle_tpu.ops import functional as F
    from paddle_tpu.utils.debug import census_from_text

    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu:
        print("WARNING: not on TPU — numbers are CPU smoke only")
    bs = args.bs if on_tpu else 4
    img = 224 if on_tpu else 64

    model = V.resnet50(1000, dtype=jnp.bfloat16)
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(bs, img, img, 3), jnp.float32)
    y = jnp.asarray(rs.randint(0, 1000, bs), jnp.int32)
    variables = model.init(jax.random.key(0), x)
    momentum = jax.tree.map(jnp.zeros_like, variables["params"])
    # host snapshot: each variant donates its own device copy
    state_host = jax.device_get(
        (variables["params"], variables["state"], momentum))

    def step(state, x, y):
        params, mstate, mom = state

        def loss_of(p):
            logits, mut = model.apply({"params": p, "state": mstate}, x,
                                      training=True, mutable=True)
            return jnp.mean(F.softmax_with_cross_entropy(
                logits.astype(jnp.float32), y)), mut.get("state", mstate)

        (loss, new_mstate), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params)
        new_mom = jax.tree.map(lambda m, g: 0.9 * m + g, mom, grads)
        new_params = jax.tree.map(lambda p, m: p - 0.1 * m, params, new_mom)
        return (new_params, new_mstate, new_mom), loss

    def census(compiled):
        full = census_from_text(compiled.as_text())
        keep = ("copy", "transpose", "bitcast", "fusion", "convolution")
        return {k: v for k, v in full.items() if k in keep}

    results = {}
    for name, fmt in (("baseline", None),
                      ("auto_layout", Format(Layout.AUTO))):
        if fmt is None:
            jitted = jax.jit(step, donate_argnums=0)
            state = jax.device_put(state_host)
            compiled = jitted.lower(state, x, y).compile()
            xx, yy = x, y
        else:
            jitted = jax.jit(
                step, donate_argnums=0,
                in_shardings=(fmt, fmt, fmt), out_shardings=(fmt, None))
            compiled = jitted.lower(state_host, x, y).compile()
            # place the state in the compiler-chosen input formats
            in_fmts = compiled.input_formats[0]
            state = jax.tree.map(jax.device_put, state_host, in_fmts[0])
            xx = jax.tree.map(jax.device_put, x, in_fmts[1])
            yy = jax.tree.map(jax.device_put, y, in_fmts[2])

        def timed(s):
            s2, loss = compiled(s, xx, yy)
            return s2, loss

        sec, steps, _ = run_timed(timed, state, min_time=args.min_time)
        results[name] = sec * 1e3
        print(f"{name:12s} {sec * 1e3:8.2f} ms/step "
              f"({bs / sec:8.1f} imgs/s)  census={census(compiled)}")

    delta = results["baseline"] - results["auto_layout"]
    print(f"\nauto-layout delta: {delta:+.2f} ms "
          f"({delta / results['baseline'] * 100:+.1f}% of step)")


if __name__ == "__main__":
    main()
