"""bench.py harness invariants: a run lands on the chip or fails loudly
(no probe, no CPU fallback, no exit 0 over a failed config), and
stale/CPU numbers never become TPU baselines."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402


def test_load_prev_newest_round_wins(tmp_path):
    for n, val in ((3, 41000.0), (4, 43000.0)):
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps({
            "n": n, "rc": 0,
            "tail": json.dumps({
                "metric": "gpt_345m_tokens_per_sec_per_chip",
                "value": val, "unit": "t/s", "vs_baseline": 1.0,
                "extras": {"device": "TPU v5 lite"}}) + "\n",
            "parsed": None}))
    prev = bench._load_prev(str(tmp_path))
    assert prev["gpt_345m_tokens_per_sec_per_chip"] == 43000.0


def test_load_prev_skips_cpu_and_error_lines(tmp_path):
    lines = [
        {"metric": "resnet50_imgs_per_sec_per_chip_cpu_smoke",
         "value": 50.0, "unit": "i/s", "vs_baseline": 1.0, "extras": {}},
        {"metric": "bert_base_tokens_per_sec_per_chip", "value": 999.0,
         "unit": "t/s", "vs_baseline": 1.0, "extras": {"device": "cpu"}},
        {"metric": "ernie_moe_ERROR", "value": 0.0, "unit": "error",
         "vs_baseline": 0.0, "extras": {}},
        {"metric": "gpt_1p3b_SKIPPED", "value": 0.0, "unit": "skipped",
         "vs_baseline": 0.0, "extras": {}},
    ]
    (tmp_path / "BENCH_r09.json").write_text(json.dumps({
        "n": 9, "rc": 0,
        "tail": "\n".join(json.dumps(l) for l in lines), "parsed": None}))
    prev = bench._load_prev(str(tmp_path))
    # all four lines rejected -> fallback table survives untouched
    assert prev["resnet50_imgs_per_sec_per_chip"] == \
        bench._PREV_FALLBACK["resnet50_imgs_per_sec_per_chip"]
    assert prev["bert_base_tokens_per_sec_per_chip"] == \
        bench._PREV_FALLBACK["bert_base_tokens_per_sec_per_chip"]


def test_load_prev_tolerates_garbage_artifacts(tmp_path):
    (tmp_path / "BENCH_r02.json").write_text("not json at all{{{")
    (tmp_path / "BENCH_r03.json").write_text(json.dumps({
        "n": 3, "rc": 1, "tail": "Traceback ...", "parsed": None}))
    prev = bench._load_prev(str(tmp_path))
    assert prev == bench._PREV_FALLBACK


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_acquire_devices_refuses_a_non_tpu_platform(monkeypatch):
    """No chip, and the caller did not ask for the CPU: the measurement
    path fails — it does not fall back."""
    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("cpu", "cpu")])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit) as e:
        bench.acquire_devices()
    assert e.value.code not in (0, None) and "not a TPU" in str(e.value)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(SystemExit):
        bench.acquire_devices()


def test_acquire_devices_cpu_only_when_the_caller_asked(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("cpu", "cpu")])
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench.acquire_devices()[0].platform == "cpu"
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_Dev("tpu", "TPU v5 lite")])
    monkeypatch.delenv("JAX_PLATFORMS")
    assert bench.acquire_devices()[0].device_kind == "TPU v5 lite"


def test_bench_main_exits_nonzero_without_a_chip(monkeypatch, capsys):
    """main() takes the refusal as its exit: no skip rows, no predicted
    stand-ins, nothing printed that could be read as a result."""
    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("cpu", "cpu")])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def _quiet_sweep(monkeypatch, broken):
    """The default sweep with every config a no-op except ``broken``."""
    def boom(*a, **kw):
        raise RuntimeError("config exploded")
    for name in ("bench_resnet50", "bench_bert", "bench_ernie_moe",
                 "bench_gpt", "bench_gpt_13b_stage_proxy",
                 "bench_collective_compression", "bench_serving",
                 "bench_serving_fleet", "bench_serving_overload",
                 "bench_gpt_13b_compile", "emit_predicted_rows"):
        monkeypatch.setattr(bench, name, lambda *a, **kw: None)
    if broken:
        monkeypatch.setattr(bench, broken, boom)
    monkeypatch.setattr(sys, "argv", ["bench.py"])


def test_bench_main_nonzero_when_a_config_raises(monkeypatch, capsys):
    """One config raising leaves its *_ERROR row, lets the rest of the
    sweep run, and makes the exit code non-zero."""
    _quiet_sweep(monkeypatch, broken="bench_bert")
    assert bench.main() == 1
    out = capsys.readouterr().out
    recs = [json.loads(l) for l in out.splitlines() if l.startswith("{")]
    assert [r["metric"] for r in recs if r["metric"].endswith("_ERROR")] \
        == ["bert_ERROR"]
    # single-config mode: the raise goes straight through
    monkeypatch.setattr(sys, "argv", ["bench.py", "--model", "bert"])
    with pytest.raises(RuntimeError, match="config exploded"):
        bench.main()


def test_bench_main_zero_when_every_config_ran(monkeypatch):
    _quiet_sweep(monkeypatch, broken=None)
    assert bench.main() == 0


def test_bench_main_nonzero_when_a_config_times_out(monkeypatch, capsys):
    import time
    monkeypatch.setattr(bench, "bench_bert", lambda a: time.sleep(5))
    monkeypatch.setattr(sys, "argv", ["bench.py", "--model", "bert",
                                      "--per-model-timeout", "1"])
    assert bench.main() == 1
    assert "bert_TIMEOUT" in capsys.readouterr().out


def test_chip_specs_raises_on_an_unknown_device_kind(monkeypatch):
    from paddle_tpu.observability.instrument import chip_specs
    with pytest.raises(ValueError, match="no such chip"):
        chip_specs("no such chip")
    assert chip_specs("TPU v5 lite")["peak_flops"] == 197e12
    # the trace-only tools' explicit override still works
    monkeypatch.setenv("PADDLE_CHIP_KIND", "v5e")
    assert chip_specs()["name"] == "v5e"
    monkeypatch.setenv("PADDLE_CHIP_KIND", "tpu v9000")
    with pytest.raises(ValueError, match="v9000"):
        chip_specs()


def test_per_model_timeout_flushes_partial(capsys):
    """A config over its SIGALRM budget emits one *_TIMEOUT line and
    returns (the sweep continues) — a single wedged model can no longer
    turn the whole driver bench into rc=124 with zero artifacts."""
    import time

    calls = []

    def slow():
        calls.append("slow")
        time.sleep(5)
        calls.append("finished")  # must never happen

    bench.run_with_timeout("cfgx", slow, 1)
    bench.run_with_timeout("cfgy", lambda: bench.emit_skip("cfgy", "ok"),
                           30)
    out = capsys.readouterr().out
    recs = [json.loads(l) for l in out.splitlines() if l.startswith("{")]
    assert recs[0]["metric"] == "cfgx_TIMEOUT"
    assert recs[1]["metric"] == "cfgy_SKIPPED"
    assert calls == ["slow"]


def test_per_model_timeout_disabled_runs_to_completion():
    assert bench.run_with_timeout("cfg", lambda: 42, 0) == 42
    assert bench.run_with_timeout("cfg", lambda: 7, 30) == 7
