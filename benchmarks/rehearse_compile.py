"""Compile each cell's step at its real sizes for a described `v5e:2x2`
chip, here in the sandbox, with no chip attached: what the TPU's compiler
refuses (a kernel shape, a program that does not fit 16 GB) costs no chip
time. Run by hand, never imported by a test:

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_compile.py [cell ...]

A compile that passes is not a chip run: nothing here is a time.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks.common import build_model, load_json  # noqa: E402
from benchmarks.runners.serve_closed import lower_step  # noqa: E402

GIB = 2.0 ** 30


def report(name, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    print(f"{name}: arguments {m.argument_size_in_bytes / GIB:.2f} GiB, "
          f"outputs {m.output_size_in_bytes / GIB:.2f} GiB (aliased "
          f"{m.alias_size_in_bytes / GIB:.2f}), temporaries "
          f"{m.temp_size_in_bytes / GIB:.2f} GiB, total {total / GIB:.2f} GiB; "
          f"kernel in program: {'tpu_custom_call' in compiled.as_text()}")


def serve_step(config, chip):
    """The engine's one step with pools of the cell's size as arguments.
    The engine itself is built on the CPU with a few blocks: the step's
    program depends on the pools' shapes only through its arguments."""
    from paddle_tpu.engine.engine import ServeEngine
    s = config["serve"]
    model = build_model(config)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                               jnp.zeros((1, 4), jnp.int32))
    zeros = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), variables)
    eng = ServeEngine(model, zeros, max_batch_size=s["max_batch_size"],
                      block_size=s["block_size"], num_blocks=64,
                      max_prefill_tokens=s["max_prefill_tokens"],
                      tile_q=s["tile_q"], max_seq_len=config["n_positions"])

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    def pool(x):
        return jax.ShapeDtypeStruct((s["num_blocks"],) + x.shape[1:],
                                    x.dtype, sharding=chip)
    print(f"flat step width {eng.flat_tokens} rows, {eng.num_tiles} tiles")
    # this process sees the CPU, and the dispatcher would take its XLA
    # tier: steered here, in the script, to the tier the chip takes
    from unittest import mock

    from paddle_tpu.kernels import paged_attention
    with mock.patch.object(paged_attention, "_device_platform",
                           lambda: "tpu"):
        return lower_step(eng, on_chip, pool).compile()


def main(argv):
    bench = load_json("BENCHMARK.json")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    configs = {c["name"]: c for c in bench["configs"]}
    for cell in bench["workloads"]:
        if argv and cell["name"] not in argv:
            continue
        config = load_json(configs[cell["config"]]["file"])
        mix = load_json("benchmarks", "traffic", cell["traffic"] + ".json")
        if mix["runner"] == "serve_closed":
            report(cell["name"], serve_step(config, chip))
        else:
            print(f"{cell['name']}: the trainer places its own state on "
                  "jax.devices(); its step was compiled for the described "
                  "chip in PR 21 (PERF.md section 5) and is not rebuilt here")


if __name__ == "__main__":
    main(sys.argv[1:])
