"""chip_smoke.py cannot rot between chip runs: its phase functions run
here at toy widths on the CPU, and its main() refuses to run off the
chip. On the CPU the Pallas tiers are not compiled in, so exactly the
two kernel-presence gates fail — which also shows that they fire."""

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
