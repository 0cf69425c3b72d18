"""Resilient distributed checkpointing: manifests, sharded save/load,
async overlap, verified fallback resume, retention GC, dp-degree
resharding, preemption emergency save, and the paddle.save/.load
integrity surface.

Parity model: the reference's fleet checkpointing + auto_checkpoint
semantics, upgraded to the manifest-commit protocol this repo's
``distributed/checkpoint`` subsystem defines: a checkpoint is complete
iff its manifest exists, and resume may only land on a checkpoint whose
every byte matches its manifest.
"""
import os
import signal
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.io import CheckpointCorruptError
from paddle_tpu.distributed import checkpoint as ckpt
from paddle_tpu.distributed.checkpoint import (
    AsyncSaver, CheckpointManager, EMERGENCY_EXIT_CODE, PreemptionHandler,
    manifest as manifest_mod,
)
from paddle_tpu.distributed.checkpoint import preemption as preemption_mod
from paddle_tpu.observability import get_registry


def _state(seed=0, n=8):
    rng = np.random.RandomState(seed)
    return {
        "model/w": rng.randn(4, n).astype(np.float32),
        "model/b": rng.randn(n).astype(np.float32),
        "opt/global_step": seed,
    }


def _corrupt_file(path, offset=-8):
    with open(path, "r+b") as f:
        f.seek(offset, os.SEEK_END)
        b = f.read(1)
        f.seek(offset, os.SEEK_END)
        f.write(bytes([b[0] ^ 0xFF]))


# ===========================================================================
# manifest
# ===========================================================================
def test_manifest_round_trip(tmp_path):
    d = str(tmp_path / "step_00000001")
    os.makedirs(d)
    p = os.path.join(d, "shard_00000.pdparams")
    paddle.save(_state(), p)
    files = {"shard_00000.pdparams": {
        "bytes": os.path.getsize(p),
        "sha256": manifest_mod.sha256_file(p), "rank": 0,
        "keys": sorted(_state())}}
    assert not manifest_mod.is_complete(d)  # manifest not yet written
    written = manifest_mod.write_manifest(
        d, files, step=1, world_size=4,
        topology={"dp": 2, "pp": 2}, meta={"job": "t"})
    assert manifest_mod.is_complete(d)
    back = manifest_mod.read_manifest(d)
    assert back["step"] == 1 and back["world_size"] == 4
    assert back["topology"] == {"dp": 2, "pp": 2, "mp": 1, "sharding": 1}
    assert back["meta"] == {"job": "t"}
    assert back["files"] == written["files"]
    assert manifest_mod.verify(d) == []


def test_manifest_verify_detects_damage(tmp_path):
    d = str(tmp_path)
    p = os.path.join(d, "shard_00000.pdparams")
    paddle.save(_state(), p)
    files = {"shard_00000.pdparams": {
        "bytes": os.path.getsize(p),
        "sha256": manifest_mod.sha256_file(p), "rank": 0, "keys": []}}
    manifest_mod.write_manifest(d, files, step=0)
    assert manifest_mod.verify(d) == []
    # bit flip (size preserved): only the sha256 sweep can catch it
    _corrupt_file(p)
    problems = manifest_mod.verify(d)
    assert problems and "sha256 mismatch" in problems[0]
    assert manifest_mod.verify(d, checksum=False) == []  # size-only passes
    # truncation: the size check catches it even without checksums
    with open(p, "r+b") as f:
        f.truncate(os.path.getsize(p) // 2)
    problems = manifest_mod.verify(d, checksum=False)
    assert problems and "size mismatch" in problems[0]
    os.unlink(p)
    assert "missing" in manifest_mod.verify(d)[0]


# ===========================================================================
# sharded save/load (virtual multi-rank: ranks run sequentially in-process)
# ===========================================================================
def test_sharded_save_spreads_ownership(tmp_path):
    d = str(tmp_path / "step_00000005")
    state = {f"k{i}": np.full(3, float(i), np.float32) for i in range(8)}
    manifest = None
    for rank in (1, 2, 3, 0):  # rank 0 last: it must wait for the others
        m = ckpt.save_sharded(state, d, step=5, rank=rank, world_size=4,
                              topology={"dp": 4})
        manifest = m or manifest
    assert manifest is not None and manifest["world_size"] == 4
    # every key written exactly once, across 4 disjoint shards
    all_keys = [k for ent in manifest["files"].values()
                for k in ent["keys"]]
    assert sorted(all_keys) == sorted(state)
    assert len(manifest["files"]) == 4
    loaded, partitioned = ckpt.load_sharded(d)
    assert partitioned == {}
    assert sorted(loaded) == sorted(state)
    for k in state:
        np.testing.assert_array_equal(loaded[k], state[k])


def test_sharded_partitioned_keys(tmp_path):
    """ZeRO-style: every rank writes its own dim-0 slice of the same key."""
    d = str(tmp_path / "step_00000009")
    full = np.arange(16, dtype=np.float32).reshape(8, 2)
    for rank in (1, 0):
        sl = full[rank * 4:(rank + 1) * 4]
        ckpt.save_sharded({"opt/m": sl, "model/w": full}, d, step=9,
                          rank=rank, world_size=2,
                          partitions={"opt/m": (0, rank, 2)})
    state, partitioned = ckpt.load_sharded(d)
    np.testing.assert_array_equal(state["model/w"], full)
    assert sorted(p[1] for p in partitioned["opt/m"]) == [0, 1]
    np.testing.assert_array_equal(
        ckpt.merge_partitions(partitioned["opt/m"]), full)


def test_sharded_resave_ignores_stale_sidecars(tmp_path):
    """A torn dir reused after relaunch: rank 0's rendezvous must wait for
    the NEW generation's sidecars, not commit over the dead attempt's."""
    d = str(tmp_path / "step_00000007")
    state = {"a": np.ones(2, np.float32), "b": np.zeros(2, np.float32)}
    # generation-0 attempt: rank 1 landed its shard+sidecar, rank 0 died
    ckpt.save_sharded({"a": state["a"], "b": np.full(2, -9.0, np.float32)},
                      d, step=7, rank=1, world_size=2, save_token="0")
    assert not manifest_mod.is_complete(d)
    # generation-1 re-save: rank 0 with a fresh token must NOT rendezvous
    # with the stale gen-0 sidecar
    with pytest.raises(TimeoutError, match="token '1'"):
        ckpt.save_sharded(state, d, step=7, rank=0, world_size=2,
                          manifest_timeout=0.3, save_token="1")
    # once rank 1 re-saves under the new token, the commit goes through
    ckpt.save_sharded(state, d, step=7, rank=1, world_size=2,
                      save_token="1")
    manifest = ckpt.save_sharded(state, d, step=7, rank=0, world_size=2,
                                 save_token="1")
    assert manifest is not None
    assert manifest_mod.verify(d) == []
    loaded, _ = ckpt.load_sharded(d)
    np.testing.assert_array_equal(loaded["b"], state["b"])  # fresh bytes


def test_reshard_partitions_dp_degree_change():
    full = np.arange(24, dtype=np.float32).reshape(12, 2)
    parts4 = [(0, i, 4, full[i * 3:(i + 1) * 3]) for i in range(4)]
    # scale-down 4 → 2
    for idx in range(2):
        out = ckpt.reshard_partitioned({"m": list(reversed(parts4))}, 2, idx)
        np.testing.assert_array_equal(out["m"], full[idx * 6:(idx + 1) * 6])
    # scale-up 2 → 4 (regather then split finer)
    parts2 = [(0, i, 2, full[i * 6:(i + 1) * 6]) for i in range(2)]
    out = ckpt.reshard_partitioned({"m": parts2}, 4)
    assert len(out["m"]) == 4
    np.testing.assert_array_equal(np.concatenate(out["m"]), full)
    # gather to a single full array (degree-1 resume)
    np.testing.assert_array_equal(
        ckpt.gather_partitioned({"m": parts4})["m"], full)
    with pytest.raises(ValueError, match="missing partition"):
        ckpt.merge_partitions(parts4[:3])


def test_dp_resharded_resume_on_virtual_mesh(tmp_path):
    """The elastic scenario end to end: save at dp=4 through 4 per-rank
    managers, resume at dp=2 — each new rank gets the right half."""
    root = str(tmp_path)
    opt_full = np.arange(32, dtype=np.float32).reshape(16, 2)
    w = np.ones((4, 4), np.float32) * 7
    for rank in (3, 1, 2, 0):
        m = CheckpointManager(root, rank=rank, world_size=4,
                              topology={"dp": 4}, async_save=False)
        m.save({"model/w": w, "opt/m": opt_full[rank * 4:(rank + 1) * 4]},
               step=40, partitions={"opt/m": (0, rank, 4)})
    saved = manifest_mod.read_manifest(os.path.join(root, "step_00000040"))
    assert saved["topology"]["dp"] == 4
    for new_rank in range(2):
        m2 = CheckpointManager(root, rank=new_rank, world_size=2,
                               topology={"dp": 2})
        state, step = m2.load_latest(reshard_to=(new_rank, 2))
        assert step == 40
        np.testing.assert_array_equal(state["model/w"], w)
        np.testing.assert_array_equal(
            state["opt/m"], opt_full[new_rank * 8:(new_rank + 1) * 8])


# ===========================================================================
# async save
# ===========================================================================
def test_async_saver_serializes_and_propagates_errors():
    saver = AsyncSaver("t")
    order = []
    gate = threading.Event()

    def slow():
        time.sleep(0.15)
        order.append("first")

    def second():
        order.append("second")
        gate.set()

    saver.submit(slow)
    assert saver.in_flight
    saver.submit(second)   # must join `slow` first — no interleave
    assert gate.wait(5)
    saver.wait()
    assert order == ["first", "second"]
    assert not saver.in_flight

    saver.submit(lambda: (_ for _ in ()).throw(OSError("disk full")))
    time.sleep(0.05)
    with pytest.raises(RuntimeError, match="previous async save failed"):
        saver.wait()
    saver.wait()  # error consumed; saver is reusable


def test_async_save_overlap_writer_joined_before_next(tmp_path):
    """Two back-to-back manager saves: the second joins the first, both
    manifests land complete, and in-flight drains to idle."""
    m = CheckpointManager(str(tmp_path), async_save=True, keep=10)
    m.save(_state(1), 10)
    m.save(_state(2), 20)   # joins save(10) internally
    assert m.wait(timeout=30)
    assert m.complete_steps() == [10, 20]
    assert not m.save_in_flight
    for step in (10, 20):
        assert manifest_mod.verify(m.step_dir(step)) == []
    # the snapshot decouples the caller's arrays: mutating after save()
    # returns must not corrupt what was persisted
    st = _state(3)
    m.save(st, 30)
    st["model/w"][:] = -1.0
    m.wait()
    loaded, _ = ckpt.load_sharded(m.step_dir(30))
    assert not np.any(loaded["model/w"] == -1.0)


def test_maybe_save_interval_gating(tmp_path):
    m = CheckpointManager(str(tmp_path), interval=3, async_save=False)
    calls = []

    def state_fn():
        calls.append(1)
        return _state()

    for step in range(1, 10):
        m.maybe_save(state_fn, step)
    assert len(calls) == 3          # steps 3, 6, 9
    assert m.complete_steps() == [3, 6, 9]
    m.maybe_save(state_fn, 9)       # same step twice: no duplicate save
    assert len(calls) == 3


# ===========================================================================
# verified resume + retention
# ===========================================================================
def test_load_latest_falls_back_past_corrupt_and_torn(tmp_path):
    m = CheckpointManager(str(tmp_path), async_save=False, keep=10)
    m.save(_state(1), 100)
    m.save(_state(2), 200)
    m.save(_state(3), 300)
    # newest is TORN: shard written but no manifest (SIGKILL mid-save)
    torn = m.step_dir(400)
    os.makedirs(torn)
    paddle.save(_state(4), os.path.join(torn, "shard_00000.pdparams"))
    # step 300 is complete but CORRUPT: flip a byte in its shard
    _corrupt_file(os.path.join(m.step_dir(300), "shard_00000.pdparams"))
    reg = get_registry()
    state, step = m.load_latest()
    assert step == 200   # newest *verified* checkpoint
    np.testing.assert_array_equal(state["model/w"], _state(2)["model/w"])
    # and the outcome telemetry distinguishes the fallback
    snap = {(s["name"], tuple(sorted(s["labels"].items()))): s.get("value", 0)
            for s in reg.snapshot()}
    assert snap.get(("paddle_checkpoint_restores_total",
                     (("result", "corrupt"),)), 0) >= 1
    assert snap.get(("paddle_checkpoint_restores_total",
                     (("result", "fallback"),)), 0) >= 1


def test_load_latest_empty_root(tmp_path):
    m = CheckpointManager(str(tmp_path))
    assert m.load_latest() == (None, -1)


def test_save_rejects_negative_step(tmp_path):
    """step_-0000001 would be invisible to load_latest/GC forever — the
    contract is explicit instead of silently losing the save."""
    m = CheckpointManager(str(tmp_path), async_save=False)
    with pytest.raises(ValueError, match="step must be >= 0"):
        m.save(_state(), -1)


def test_preemption_before_first_step_skips_save(tmp_path, monkeypatch):
    """SIGTERM before any step completed: nothing trained, nothing saved
    — but the process still exits the emergency code for the controller."""
    exits = []
    monkeypatch.setattr(preemption_mod, "_exit", exits.append)
    m = CheckpointManager(str(tmp_path), async_save=False)
    handler = PreemptionHandler(m, lambda: (_state(), -1)).install()
    try:
        handler._handle(signal.SIGTERM, None)
    finally:
        handler.uninstall()
    assert exits == [EMERGENCY_EXIT_CODE]
    assert m.steps() == []   # no orphan dir


def test_retention_gc_keeps_last_n_and_fallback(tmp_path):
    m = CheckpointManager(str(tmp_path), async_save=False, keep=2)
    for step in (10, 20, 30, 40):
        m.save(_state(step), step)   # each save GCs
    assert m.complete_steps() == [30, 40]
    # a torn dir NEWER than the newest complete (in-flight save) survives
    torn = m.step_dir(50)
    os.makedirs(torn)
    m.gc()
    assert os.path.isdir(torn)
    # a torn dir OLDER than the newest complete is swept
    old_torn = m.step_dir(25)
    os.makedirs(old_torn)
    m.gc()
    assert not os.path.isdir(old_torn)
    # keep=1 can never delete the newest complete checkpoint itself
    m.keep = 1
    m.gc()
    assert m.complete_steps() == [40]
    assert m.load_latest()[1] == 40


# ===========================================================================
# preemption: SIGTERM → emergency save → distinct exit code
# ===========================================================================
def test_preemption_handler_emergency_save(tmp_path, monkeypatch):
    exits = []
    monkeypatch.setattr(preemption_mod, "_exit", exits.append)
    m = CheckpointManager(str(tmp_path), async_save=True, interval=1000)
    # an async save is in flight when the SIGTERM lands: the emergency
    # save must join it, not interleave with it
    m.save(_state(1), 10)
    handler = PreemptionHandler(m, lambda: (_state(2), 77)).install()
    try:
        assert signal.getsignal(signal.SIGTERM) == handler._handle
        handler._handle(signal.SIGTERM, None)
        assert handler.triggered
        assert exits == [EMERGENCY_EXIT_CODE]
        assert m.complete_steps() == [10, 77]
        assert manifest_mod.verify(m.step_dir(77)) == []
        state, step = m.load_latest()
        assert step == 77
        np.testing.assert_array_equal(state["model/w"],
                                      _state(2)["model/w"])
        handler._handle(signal.SIGTERM, None)  # double SIGTERM: no re-save
        assert exits == [EMERGENCY_EXIT_CODE]
    finally:
        handler.uninstall()
    assert signal.getsignal(signal.SIGTERM) != handler._handle


def test_controller_preemption_decision():
    """Exit-code contract, launcher-level: EMERGENCY_EXIT_CODE among
    otherwise-benign codes reads as preemption; a crash does not."""
    from paddle_tpu.distributed.launch import (
        PodLauncher, ElasticRelaunchController,
    )
    from paddle_tpu.distributed.fleet.elastic import ElasticManager
    from paddle_tpu.distributed.fleet.elastic.manager import _MemStore

    launcher = PodLauncher(["true"], nproc=2, job_id="pc")
    manager = ElasticManager(job_id="pc", np="1", store=_MemStore(),
                             fault_tolerance_level=1)
    c = ElasticRelaunchController(launcher, manager)
    assert EMERGENCY_EXIT_CODE in c.preemption_exit_codes
    launcher._codes = [EMERGENCY_EXIT_CODE, 0]
    assert c._is_preemption(EMERGENCY_EXIT_CODE)
    launcher._codes = [EMERGENCY_EXIT_CODE, -signal.SIGTERM]
    assert c._is_preemption(EMERGENCY_EXIT_CODE)  # teardown SIGTERM ok
    launcher._codes = [EMERGENCY_EXIT_CODE, -signal.SIGKILL]
    assert not c._is_preemption(EMERGENCY_EXIT_CODE)  # a real crash rode along
    launcher._codes = [1, 0]
    assert not c._is_preemption(1)


# ===========================================================================
# paddle.save / paddle.load integrity surface
# ===========================================================================
def test_load_truncated_raises_checkpoint_corrupt(tmp_path):
    p = str(tmp_path / "m.pdparams")
    paddle.save({"w": paddle.to_tensor(np.ones(64, np.float32))}, p)
    assert os.path.exists(p + ".sha256")
    full = os.path.getsize(p)
    with open(p, "r+b") as f:
        f.truncate(full // 2)
    with pytest.raises(CheckpointCorruptError) as ei:
        paddle.load(p)
    assert ei.value.path == p
    assert ei.value.expected_bytes == full
    assert ei.value.actual_bytes == full // 2
    assert "expected" in str(ei.value) and "actual" in str(ei.value)


def test_load_bitflip_and_unpicklable(tmp_path):
    p = str(tmp_path / "m.pdparams")
    paddle.save({"w": np.arange(32, dtype=np.float32)}, p)
    _corrupt_file(p)
    with pytest.raises(CheckpointCorruptError, match="sha256 mismatch"):
        paddle.load(p)
    # without the sidecar, the same damage surfaces as a clear
    # CheckpointCorruptError from the unpickle, not a bare UnpicklingError
    os.unlink(p + ".sha256")
    garbage = str(tmp_path / "g.pdparams")
    with open(garbage, "wb") as f:
        f.write(b"\x80\x04 this is not a pickle")
    with pytest.raises(CheckpointCorruptError, match="unpicklable"):
        paddle.load(garbage)


def test_save_checksum_opt_out(tmp_path):
    p = str(tmp_path / "m.pdparams")
    paddle.save({"x": 1}, p, checksum=False)
    assert not os.path.exists(p + ".sha256")
    assert paddle.load(p) == {"x": 1}


# ===========================================================================
# auto_checkpoint rebased on the manifest core
# ===========================================================================
def test_auto_checkpoint_falls_back_past_torn_epoch(tmp_path):
    import json
    from paddle_tpu.incubate.checkpoint.auto_checkpoint import _ACPManager
    from paddle_tpu import nn

    net = nn.Linear(2, 2)
    mgr = _ACPManager(run_id="fb", checkpoint_dir=str(tmp_path))
    mgr.add_save_vars(model=net)
    net.weight.set_value(np.full((2, 2), 5.0, np.float32))
    mgr.save_checkpoint(0)
    # epoch 1 crashed mid-save: files on disk, NO manifest; meta.json
    # (the legacy pointer) even points at it
    torn = os.path.join(mgr._run_dir(), "ckpt_1")
    os.makedirs(torn)
    paddle.save({"weight": np.zeros((2, 2), np.float32)},
                os.path.join(torn, "model.pdparams"))
    with open(mgr._meta_path(), "w") as f:
        json.dump({"epoch": 1, "dir": "ckpt_1"}, f)
    net.weight.set_value(np.zeros((2, 2), np.float32))
    assert mgr.restore() == 0   # fell back to the complete epoch
    np.testing.assert_array_equal(net.weight.numpy(),
                                  np.full((2, 2), 5.0, np.float32))


def test_auto_checkpoint_restores_legacy_meta_only_dirs(tmp_path):
    """Checkpoints written by the pre-manifest release (meta.json commit,
    no manifest.json anywhere) must still restore — an upgrade cannot
    silently restart a long job from epoch 0."""
    import json
    from paddle_tpu.incubate.checkpoint.auto_checkpoint import _ACPManager
    from paddle_tpu import nn

    net = nn.Linear(2, 2)
    mgr = _ACPManager(run_id="legacy", checkpoint_dir=str(tmp_path))
    mgr.add_save_vars(model=net)
    legacy = os.path.join(mgr._run_dir(), "ckpt_3")
    os.makedirs(legacy)
    paddle.save({"weight": np.full((2, 2), 9.0, np.float32),
                 "bias": np.zeros(2, np.float32)},
                os.path.join(legacy, "model.pdparams"))
    with open(mgr._meta_path(), "w") as f:
        json.dump({"epoch": 3, "dir": "ckpt_3"}, f)
    assert mgr.restore() == 3
    np.testing.assert_array_equal(net.weight.numpy(),
                                  np.full((2, 2), 9.0, np.float32))


# ===========================================================================
# TCPStore transient-error retry (satellite)
# ===========================================================================
def test_store_retry_on_transient_status(monkeypatch):
    from paddle_tpu.distributed.store import TCPStore

    master = TCPStore(is_master=True, world_size=1, timeout=5)
    try:
        master.set("k", b"v")
        real = master._request_once
        fails = {"n": 2}

        def flaky(cmd, key, val=b"", cap=1 << 20):
            if fails["n"] > 0:
                fails["n"] -= 1
                return -101, b""   # peer reset mid-response
            return real(cmd, key, val, cap)

        monkeypatch.setattr(master, "_request_once", flaky)
        monkeypatch.setenv("PADDLE_STORE_RETRY_BASE", "0.001")
        reg = get_registry()

        def retry_count():
            return sum(s["value"] for s in reg.snapshot()
                       if s["name"] == "paddle_store_retries_total")

        before = retry_count()
        assert master.get_nowait("k") == b"v"   # retried through the resets
        assert fails["n"] == 0
        assert retry_count() == before + 2
        # a non-transient status is NOT retried
        fails["n"] = 0
        assert master.get_nowait("absent") is None
        assert retry_count() == before + 2
        # bounded: with retries disabled the transient error surfaces
        monkeypatch.setenv("PADDLE_STORE_RETRIES", "0")
        fails["n"] = 99
        with pytest.raises(RuntimeError):
            master.set("k2", b"x")
        # ADD is non-idempotent: a short-read (-101, server may have
        # already applied the increment) must NOT be retried even with
        # retries enabled — double-counting would corrupt barriers
        monkeypatch.setenv("PADDLE_STORE_RETRIES", "4")
        fails["n"] = 1
        with pytest.raises(RuntimeError):
            master.add("cnt", 1)
        assert fails["n"] == 0   # exactly one attempt, no retry
    finally:
        master.close()


# ===========================================================================
# ParallelTrainStep integration: state round-trip + attached manager
# ===========================================================================
def test_train_step_checkpoint_roundtrip(tmp_path):
    from paddle_tpu import nn, optimizer as opt
    from paddle_tpu.distributed.fleet.train_step import ParallelTrainStep
    from paddle_tpu.distributed.mesh import HybridCommunicateGroup

    def loss_fn(model, x, y):
        return ((model(x) - y) ** 2).mean()

    def make_step(seed):
        paddle.seed(seed)
        net = nn.Linear(4, 2)
        o = opt.Momentum(learning_rate=0.1, momentum=0.9,
                         parameters=net.parameters())
        hcg = HybridCommunicateGroup(dp_degree=1, mp_degree=1, pp_degree=1,
                                     sharding_degree=1)
        return ParallelTrainStep(net, o, loss_fn, hcg=hcg)

    x = paddle.to_tensor(np.linspace(0, 1, 8).reshape(2, 4)
                         .astype(np.float32))
    y = paddle.to_tensor(np.ones((2, 2), np.float32))

    step = make_step(7)
    mgr = step.attach_checkpoint_manager(
        CheckpointManager(str(tmp_path), interval=2, async_save=True))
    for _ in range(4):
        step(x, y)
    mgr.wait()
    assert mgr.complete_steps() == [2, 4]   # interval-gated async saves
    # steps 5 and 6 are what the resumed step must replay: no save of
    # step 6, which would be the newest whenever it finished before the
    # resume below (it raced it: one of PR 35's two whole runs failed here)
    mgr.interval = 0
    loss_after_5 = float(step(x, y).numpy())
    loss_after_6 = float(step(x, y).numpy())

    # fresh process-equivalent: new model/opt (auto param names DIFFER —
    # the structural-key packing must still restore every accumulator),
    # resume from the newest verified checkpoint, continue exactly
    step2 = make_step(99)   # different init — must be fully overwritten
    restored = step2.resume_from_checkpoint(mgr)
    assert restored == 4
    assert float(step2(x, y).numpy()) == pytest.approx(loss_after_5,
                                                       rel=1e-6)
    # the SECOND post-resume loss depends on the restored Momentum
    # velocity — a silently-dropped accumulator diverges exactly here
    assert float(step2(x, y).numpy()) == pytest.approx(loss_after_6,
                                                       rel=1e-6)


# ===========================================================================
# hapi ResilientCheckpoint callback
# ===========================================================================
class _FakeFitModel:
    """The slice of hapi.Model the callback consumes."""

    def __init__(self, seed):
        from paddle_tpu import nn, optimizer as opt
        paddle.seed(seed)
        self.network = nn.Linear(3, 3)
        self._optimizer = opt.SGD(learning_rate=0.1,
                                  parameters=self.network.parameters())


def test_resilient_checkpoint_callback_saves_and_resumes(tmp_path):
    from paddle_tpu.hapi.callbacks import ResilientCheckpoint

    model = _FakeFitModel(1)
    cb = ResilientCheckpoint(save_dir=str(tmp_path), save_steps=2, keep=5)
    cb.set_model(model)
    cb.on_train_begin()
    assert cb.restored_step == -1
    for step in range(5):
        model.network.weight.set_value(
            np.full((3, 3), float(step), np.float32))
        cb.on_train_batch_end(step)
    cb.on_train_end()
    mgr = cb.manager
    assert mgr.latest_complete_step() == 5   # final sync save caught the tail
    assert manifest_mod.verify(mgr.step_dir(5)) == []

    model2 = _FakeFitModel(2)
    cb2 = ResilientCheckpoint(save_dir=str(tmp_path), save_steps=2)
    cb2.set_model(model2)
    cb2.on_train_begin()
    assert cb2.restored_step == 5
    np.testing.assert_array_equal(model2.network.weight.numpy(),
                                  np.full((3, 3), 4.0, np.float32))
