"""CPU rehearsal of ``chip_smoke.py``: its phase functions at
``gpt_tiny_config`` (Pallas kernels in interpret mode, four of conftest's
virtual devices for the sharded comparison), its device gate, and the
compile-cache placement it relies on. The script itself has no option
that makes it smaller; this file is that option."""
import json
import os

import pytest

import chip_smoke
from paddle_tpu.models.gpt import gpt_tiny_config
from paddle_tpu.utils import compile_cache


def test_device_gate_refuses_cpu():
    with pytest.raises(SystemExit) as e:
        chip_smoke.device_gate()
    assert "not a TPU" in str(e.value) and e.value.code != 0


def test_train_phase_tiny(capsys):
    losses = chip_smoke.train_phase(gpt_tiny_config(), batch=4, seq=64,
                                    seed=0)
    assert len(losses) == 5 and losses[-1] < losses[0]
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["phase"] == "train" and row["flash_kernel_in_step"] is False
    assert "not a measurement" in row["note"]


def test_serve_phase_tiny(capsys):
    classic, chunked = chip_smoke.serve_phase(
        gpt_tiny_config(), seed=0, prompt_lens=(24, 40, 17, 33, 9),
        max_new=8, page_size=8, decode_buckets=(1, 2, 4),
        prefill_buckets=(32, 64, 128), chunk=16)
    # exact f32 arithmetic here: the classic engine and the generator
    # agree; the chunked engine serves the bf16 cast of the model
    assert [len(t) for t in chunked] == [len(t) for t in classic]
    rows = [json.loads(ln) for ln in
            capsys.readouterr().out.strip().splitlines()]
    serve, chunk = rows[-2], rows[-1]
    assert serve["phase"] == "serve" and serve["requests"] == 5
    assert serve["generator_tokens_agreeing_by_prompt_len"] \
        == {"9": 8, "17": 8, "24": 8}
    assert serve["pool_pages_in_use"] == 0
    assert chunk["rewrite_statuses"].get("ragged_prefill:fired", 0) >= 1
    assert chunk["pool_dtype"] == "bfloat16"
    assert chunk["kernel_vs_reference_max_abs_err"] <= chunk["tolerance"]


def test_serve_phase_fails_on_rewrite_error(monkeypatch):
    """A pass that errors is a failed phase, not a silent unfused run."""
    from paddle_tpu.kernels import paged_attention

    def boom(*a, **kw):
        raise RuntimeError("refused by the compiler")
    monkeypatch.setattr(paged_attention, "ragged_prefill_attention", boom)
    from paddle_tpu.analysis import rewrite
    monkeypatch.setattr(rewrite, "_KERNEL_PARITY_CACHE", {})
    with pytest.raises(RuntimeError, match="auto-fusion records"):
        chip_smoke.serve_phase(
            gpt_tiny_config(), seed=0, prompt_lens=(24, 17), max_new=2,
            page_size=8, decode_buckets=(1, 2), prefill_buckets=(32, 128),
            chunk=16)


def test_sharded_phase_four_virtual_devices(capsys):
    one, four = chip_smoke.sharded_phase(gpt_tiny_config(), batch=4,
                                         seq=64, seed=0)
    assert len(one) == len(four) == 3
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["phase"] == "sharded_train"
    assert len(row["param_bytes_per_device"]) == 4
    assert max(row["param_bytes_per_device"].values()) \
        < row["param_bytes_total"]


def test_compile_cache_leaves_a_placed_dir_alone(monkeypatch):
    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    import jax
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.enable_compile_cache()
        assert compile_cache.enable_compile_cache() == first
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert first == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
