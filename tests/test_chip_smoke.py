"""chip_smoke.py cannot rot between chip runs: its phase functions run
here at toy widths on the CPU, and its main() refuses to run off the
chip. On the CPU the Pallas tiers are not compiled in, so exactly the
two kernel-presence gates fail — which also shows that they fire.
The reader of whole-pool copies is shown to fire on a relayout's line."""

import os
import sys

import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = dict(vocab=211, model_dim=32, num_heads=4, num_layers=2, ffn_dim=64,
           max_len=128)


def test_phases_run_at_toy_widths_on_cpu(monkeypatch):
    # the smoke path must not lean on a native helper that may have
    # failed to build (LazyLib memoises that failure as None)
    from paddle_tpu.utils import native

    def no_native(self):
        raise AssertionError("chip_smoke asked for a native library")
    monkeypatch.setattr(native.LazyLib, "get", no_native)

    serve, artifacts = chip_smoke.serve_phase(
        TOY, dict(block_size=4, max_batch_size=4, max_prefill_tokens=32,
                  num_blocks=96),
        prompt_lens=(5, 9, 9, 40, 70), shared_prefix=16,
        pair_suffixes=(3, 6), new_tokens=6, logit_prompt_len=50,
        dtype=jnp.float32, seed=0)
    assert serve["failed"] == [
        "no Pallas kernel in the engine's step program"], serve
    assert serve["streams_complete"] == serve["requests"] == 7
    assert serve["engine_compiles"] == 1 and serve["mixed_steps"] > 0
    # the compiled step's own account: the donated pools updated in
    # place (the CPU compiles them so too; the relayout this guards
    # against is the TPU's: tests/test_chip_compile.py)
    assert serve["step_pool_sized_copies"] == 0
    assert serve["step_aliased_bytes"] >= serve["kv_pool_bytes_per_chip"] > 0
    assert serve["hit_tokens"] >= 12          # three full shared blocks
    assert serve["max_chunk_tokens"] <= 32    # the 40/70 prompts chunked
    # float32 on one backend: far inside the bf16 bound, and greedy
    # tokens equal model.generate's
    assert serve["logit_err_share_of_max"] < 1e-4
    assert serve["tokens_equal_to_generate"] == "12/12"
    assert all(len(t) == 6 for t in artifacts["tokens"].values())

    train = chip_smoke.train_phase(TOY, batch=2, seq=64, steps=5,
                                   dtype=jnp.float32, seed=0)
    assert train["failed"] == [
        "no flash kernel in the trainer's step program"], train
    assert train["train_compiles"] == 1
    assert train["losses"][-1] < train["losses"][0]


def test_pool_sized_copies_reads_a_relayout():
    """The two lines a [3072, 16, 16, 64] pool left in the step program
    (PR 25's tree, compiled for a described v5e), and lines that are no
    whole-pool copy."""
    text = """
  %copy.5 = bf16[3072,16,16,64]{3,2,1,0:T(8,128)(2,1)} copy(%pools_0__0_.1), sharding={replicated}
  %copy.22 = bf16[3072,16,16,64]{0,3,2,1:T(8,128)(2,1)} copy(%bitcast.4), backend_config={}
  %copy.7 = bf16[768,1024]{1,0:T(8,128)(2,1)} copy(%fusion.3)
  %scatter.1 = bf16[49152,2048]{1,0:T(8,128)(2,1)} scatter(%bitcast.9, %slots, %rows)
"""
    found = chip_smoke.pool_sized_copies(text, 3072 * 16 * 16 * 64)
    assert len(found) == 2 and all("copy(" in line for line in found)
    assert chip_smoke.pool_sized_copies(text, 3072 * 16 * 2048) == []


def test_main_refuses_to_run_off_the_chip(capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert out == ""                  # no result line off the chip
    assert "needs a TPU" in err


@pytest.mark.parametrize("placed", ["/some/dir", None])
def test_compile_cache_is_placed_from_outside(monkeypatch, placed):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code;
    otherwise one fixed directory inside the checkout."""
    import jax

    from paddle_tpu.utils import compile_cache
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        assert compile_cache.enable_compile_cache() == placed
        assert updates == []
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert updates == [("jax_compilation_cache_dir", want)]


def test_latent_phase_runs_at_toy_widths_on_cpu():
    cfg = chip_smoke._latent_config()
    cfg = {**cfg, **cfg["toy"]}
    line = chip_smoke.latent_phase(
        cfg, layers=2, num_blocks=67, prompt_lens=(50, 41), shared_prefix=32,
        new_tokens=4, seed=0)
    assert line["failed"] == [
        "no Pallas kernel in the engine's step program"], line
    assert line["engine_compiles"] == 1 and line["hit_tokens"] == 32
    assert line["max_chunk_tokens"] == 32        # the 50 in two chunks
    assert line["step_pool_sized_copies"] == 0
    # float32 on one backend: the served tokens are the reference's
    assert line["logit_err_share_of_max"] < 1e-4
    assert line["tokens_equal_to_reference"] == "8/8"
    assert line["expert_pairs"] == (50 + 9 + 2 * 3) * 2


def test_hybrid_phase_runs_at_toy_widths_on_cpu():
    cfg = chip_smoke._hybrid_config()
    cfg = {**cfg, **cfg["toy"]}
    line = chip_smoke.hybrid_phase(
        cfg, layer_kinds=chip_smoke.HYBRID["layer_kinds"], num_blocks=67,
        max_batch_size=2, prompt_lens=(60, 20), new_tokens=4, seed=0)
    assert line["failed"] == [
        "no Pallas kernel in the engine's step program"], line
    assert line["engine_compiles"] == 1
    assert line["max_chunk_tokens"] == 32        # the 60 in two chunks
    assert line["step_pool_or_state_sized_copies"] == 0
    # 60 + 3 positions computed at window 8, block 4: the next query at
    # 63 has 14 blocks behind its window, the 20-token prompt's at 23 has 4
    assert line["window_blocks_released"] == (63 - 7) // 4 + (23 - 7) // 4
    # float32 on one backend: the served tokens are the reference's
    assert line["logit_err_share_of_max"] < 1e-4
    assert line["tokens_equal_to_reference"] == "8/8"
