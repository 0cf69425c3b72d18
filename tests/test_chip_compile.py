"""The kernels of the main path, compiled at real widths for the chip.

The only test file that describes the chip. The TPU's compiler is
installed here and compiles for a v5e that is described, not attached
(``/opt/skills/guides/on-chip-measurement`` section 2): what it refuses
here it refuses there, at no chip time. Interpret mode — all the other
kernel tests — cannot see tiling, 64-bit indices or VMEM limits.

Only one process may load the TPU's library, and every xdist worker
imports every test file: the topology is described inside a
module-scoped fixture (never at import, in a ``skipif`` or in a
``parametrize`` argument), each test compiles in its own process, and
these tests stay in this one file. Each test flips the kernel module's
own ``_interpret`` gate — the program has no option for it.

Beside the kernels: the two serving programs that carry the KV pool
through their layer loop, whole and at the benchmark's size, for what
only the compiler can say of them — that the pool is aliased and the
temporaries are not a second pool (three compiles of a few seconds);
and the benchmark's two train steps, whole, for how many kernels they
hold (about ten seconds each).
"""
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp

import functools
import math
import re

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import paddle_tpu  # noqa: F401  (x64 on, as every user of the kernels has it)
from paddle_tpu.kernels import (flash_attention, grouped_matmul, int8_matmul,
                                moe_dispatch, paged_attention,
                                selective_scan, sparse_mla)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compiled(monkeypatch, module, fn, *args, donate=()):
    """``fn`` compiled for the described chip with ``module``'s kernels
    lowered by Mosaic; ``args`` are shapes that carry its sharding."""
    monkeypatch.setattr(module, "_interpret", lambda: False)
    # conftest.py asks for exact f32 matmuls (its oracles need them); the
    # chip runs the production default, and Mosaic refuses an fp32
    # contraction of bf16 operands
    with jax.default_matmul_precision("default"):
        return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


def _compile(monkeypatch, module, sharding, fn, *shapes):
    """Compile ``fn`` for the described chip and return the number of
    kernels in the program."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    return _compiled(monkeypatch, module, fn, *args).as_text().count(
        "tpu_custom_call")


BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8
_FLASH = ((96, 1024, 128), BF16)      # B12 x 8 heads, S1024, d128


def test_flash_fwd(monkeypatch, one_chip, no_compile_cache):
    def fwd(q, k, v):
        return flash_attention.flash_attention(q, k, v, causal=True)
    assert _compile(monkeypatch, flash_attention, one_chip, fwd,
                    _FLASH, _FLASH, _FLASH) == 1


def test_flash_fwd_bwd(monkeypatch, one_chip, no_compile_cache):
    def loss(q, k, v):
        return flash_attention.flash_attention(
            q, k, v, causal=True).astype(F32).sum()
    assert _compile(monkeypatch, flash_attention, one_chip,
                    jax.grad(loss, argnums=(0, 1, 2)),
                    _FLASH, _FLASH, _FLASH) == 3


def _covered_sums(text):
    """How many ``collective-permute`` pairs of the scheduled program have
    a matmul or a kernel between their start and their done."""
    covered = 0
    for comp in re.split(r"\n(?=\S.*\{\n)", text):
        open_, work = {}, False
        for line in comp.split("\n"):
            start = re.search(r"%(\S+) = .* collective-permute-start\(", line)
            done = re.search(r"collective-permute-done\(%(\S+?)\)", line)
            if start:
                open_[start.group(1)] = False
            elif done:
                covered += open_.pop(done.group(1))
            elif "flash_attention" in line or "dot_general" in line:
                open_ = dict.fromkeys(open_, True)
    return covered


# The benchmark's two train steps, whole, under remat="dots": the backward
# reads the flash forward's saved output and row statistics, so a layer
# calls three kernels, not four (the forward again inside the backward
# loop). The 345M step's temporaries hold that output, lane-padded, for
# 24 layers (12.66 GiB before, 13.81 with it; B14 no longer fits). On
# dp2·mp2 the layer scan runs two half-batch streams (gpt.scan_blocks):
# each calls the three kernels, and its five mp sums a layer (two forward,
# three backward) are collective-permute pairs, of which the scheduler
# covers seven of the ten with the other half's matmuls or kernels. It
# does so only while memory is not tight: at 9.37 GiB of temporaries, and
# none of the backward's six with about 1% of the device's memory less.
@pytest.mark.parametrize(
    "config,dp,mp,batch,seq,temp_gib,streams,permutes,covered", [
        ("gpt_345m_config", 1, 1, 12, 1024, 14.5, 1, 0, 0),
        ("gpt_1p3b_config", 2, 2, 8, 2048, 9.5, 2, 10, 7)])
def test_train_step_runs_the_flash_forward_once(
        monkeypatch, topo, no_compile_cache, config, dp, mp, batch, seq,
        temp_gib, streams, permutes, covered):
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.models import gpt
    monkeypatch.setattr(mesh_mod, "_global_mesh", mesh_mod._global_mesh)
    monkeypatch.setattr(mesh_mod, "_hcg", mesh_mod._hcg)
    hcg = mesh_mod.HybridCommunicateGroup(
        dp_degree=dp, mp_degree=mp,
        mesh=mesh_mod.build_mesh(dp=dp, mp=mp, devices=topo.devices))
    step = gpt.GPTHybridTrainStep.abstract(
        getattr(gpt, config)(), hcg, remat="dots", compute_dtype="bfloat16",
        use_flash=True)
    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    with jax.default_matmul_precision("default"):
        exe = step.lower_step(batch, seq).compile()
    mem = exe.memory_analysis()
    text = exe.as_text()
    assert text.count("tpu_custom_call") == 3 * streams
    assert text.count(" collective-permute-start(") == permutes
    assert _covered_sums(text) >= covered
    assert mem.temp_size_in_bytes < temp_gib * _GIB
    print(config, "arguments", mem.argument_size_in_bytes, "temporaries",
          mem.temp_size_in_bytes)


@pytest.mark.parametrize("nh,d", [(8, 128), (16, 64)])
def test_paged_decode(monkeypatch, one_chip, no_compile_cache, nh, d):
    pool = ((512, 64, nh, d), BF16)
    assert _compile(monkeypatch, paged_attention, one_chip,
                    paged_attention.paged_attention_decode,
                    ((8, nh, d), BF16), pool, pool,
                    ((8, 16), I32), ((8,), I32)) == 1


@pytest.mark.parametrize("nh,d", [(8, 128), (16, 64)])
def test_ragged_prefill_c256(monkeypatch, one_chip, no_compile_cache,
                             nh, d):
    pool = ((512, 64, nh, d), BF16)
    assert _compile(monkeypatch, paged_attention, one_chip,
                    paged_attention.ragged_prefill_attention,
                    ((1, 256, nh, d), BF16), pool, pool,
                    ((1, 16), I32), ((), I32)) == 1


# the serving programs hand the kernels the whole pool [L, P, ps, nkv, d]
# and a traced layer, which rides the scalar prefetch: 1.3B (16 x d128)
# and 345M (16 x d64) widths
@pytest.mark.parametrize("d", [128, 64])
def test_paged_decode_layer_of_pool(monkeypatch, one_chip, no_compile_cache,
                                    d):
    pool = ((4, 129, 64, 16, d), BF16)

    def decode(q, kp, vp, table, lens, layer):
        return paged_attention.paged_attention_decode(
            q, kp, vp, table, lens, layer=layer)
    assert _compile(monkeypatch, paged_attention, one_chip, decode,
                    ((8, 16, d), BF16), pool, pool,
                    ((8, 16), I32), ((8,), I32), ((), I32)) == 1


@pytest.mark.parametrize("d", [128, 64])
def test_ragged_prefill_c256_layer_of_pool(monkeypatch, one_chip,
                                           no_compile_cache, d):
    pool = ((4, 129, 64, 16, d), BF16)

    def prefill(q, kp, vp, table, off, layer):
        return paged_attention.ragged_prefill_attention(
            q, kp, vp, table, off, layer=layer)
    assert _compile(monkeypatch, paged_attention, one_chip, prefill,
                    ((1, 256, 16, d), BF16), pool, pool,
                    ((1, 16), I32), ((), I32), ((), I32)) == 1


# The whole serving programs of the benchmark's serving cell: GPT-1.3B in
# bf16, 32 slots, 32 pages of 64 a sequence, chunks of 256. In place
# means: the two donated pools are aliased to the outputs, and the
# program's temporaries are not another pool (they were 5.70 GiB beside
# a pool of 5.26 GiB while the layer loop stacked the pool).
_GIB = 2 ** 30


def _serving_program(one_chip, what, pool_tokens):
    """(step function, its abstract arguments, the two pools' bytes)."""
    from paddle_tpu.models.gpt import gpt_1p3b_config
    from paddle_tpu.serving import engine
    from paddle_tpu.serving.predict import _params_avals
    cfg = gpt_1p3b_config()

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        _params_avals(cfg, "bfloat16", None))
    pool = sds((cfg.num_layers, pool_tokens // 64 + 1, 64, cfg.num_heads,
                cfg.head_dim), BF16)
    key = jax.eval_shape(lambda: jax.random.key(0))
    key = sds(key.shape, key.dtype)
    kw = dict(eps=cfg.layer_norm_epsilon, temperature=0.0, top_k=0,
              use_kernel=True, compute_dtype="bfloat16")
    if what == "decode":
        # as the engine launches it: one packed int32 state, the base key
        fn = functools.partial(
            engine.decode_packed_fn,
            functools.partial(engine.decode_step_fn, **kw))
        args = (sds((32, 3 + 32), I32),)
    else:
        fn = functools.partial(engine.chunk_prefill_fn, **kw)
        args = (sds((1, 256), I32), sds((), I32), sds((), I32),
                sds((1, 32), I32), sds((256,), I32))
    pool_bytes = 2 * 2 * math.prod(pool.shape)
    return fn, (params, pool, pool) + args + (key,), pool_bytes


@pytest.mark.parametrize("what", ["decode", "chunk"])
def test_serving_program_updates_pool_in_place(monkeypatch, one_chip,
                                               no_compile_cache, what):
    fn, args, pool_bytes = _serving_program(one_chip, what, 28672)
    exe = _compiled(monkeypatch, paged_attention, fn, *args, donate=(1, 2))
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes > 5 * _GIB
    assert mem.temp_size_in_bytes < _GIB
    text = exe.as_text()
    assert text.count("tpu_custom_call") == 1
    # nothing of the pool's size is copied, cut, written back or filled
    pool = re.escape("bf16[%s]" % ",".join(map(str, args[1].shape)))
    pool_shaped = [ln for ln in text.splitlines() if re.search(
        rf"= {pool}\S* (copy|dynamic-slice|dynamic-update-slice|broadcast)\(",
        ln)]
    assert pool_shaped == []
    print(what, "arguments", mem.argument_size_in_bytes, "temporaries",
          mem.temp_size_in_bytes, "alias", mem.alias_size_in_bytes)


def test_decode_program_compiles_with_49k_token_pool(monkeypatch, one_chip,
                                                     no_compile_cache):
    """Refused while the program kept a second pool ("Used 20.85G of
    15.75G hbm"): 9.0 GiB of pool beside 2.45 GiB of weights."""
    fn, args, pool_bytes = _serving_program(one_chip, "decode", 49152)
    exe = _compiled(monkeypatch, paged_attention, fn, *args, donate=(1, 2))
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes > 9 * _GIB
    assert mem.temp_size_in_bytes < _GIB


# The block-diffusion programs of the benchmark's SDAR cell (7 layers at
# the published widths: 32 / 4 heads of 128, 128 experts of 768): the
# block pass of 64 slots hands the decode kernel 128 queries a sequence
# (32 a KV head: its MXU path), the chunk kernel takes grouped heads and
# the block rule; both carry the pool of 131,072 tokens in place, and
# the expert stacks are read where they lie (no 1.2 GB slice a layer) by
# the grouped-matmul kernel: 2,048 sorted rows in the block program of 64
# slots and in the chunk program, 32 (under one row tile) in that of 1.
def _sdar_program(one_chip, what):
    from paddle_tpu.models import sdar
    from paddle_tpu.serving import sdar_engine
    cfg = sdar.SdarMoeConfig(num_hidden_layers=7)

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(
        sds, sdar.sdar_weight_shapes(cfg),
        is_leaf=lambda x: isinstance(x, tuple))
    pool = sds((7, 2049, 64, 4, 128))
    if what.startswith("block"):
        fn = functools.partial(sdar_engine.sdar_block_step_fn, cfg=cfg)
        args = (sds((int(what[5:]), 2 * 4 + 2 + 32), I32),)
    else:
        fn = functools.partial(sdar_engine.sdar_chunk_prefill_fn, cfg=cfg)
        args = (sds((1, 256), I32), sds((), I32), sds((), I32),
                sds((1, 32), I32), sds((256,), I32))
    return fn, (params, pool, pool) + args, 2 * 2 * math.prod(pool.shape)


@pytest.mark.parametrize("what", ["block64", "chunk", "block1"])
def test_sdar_program_in_place_and_no_expert_slice(monkeypatch, one_chip,
                                                   no_compile_cache, what):
    fn, args, pool_bytes = _sdar_program(one_chip, what)
    monkeypatch.setattr(grouped_matmul, "_interpret", lambda: False)
    exe = _compiled(monkeypatch, paged_attention, fn, *args, donate=(1, 2))
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes > 1.7 * _GIB
    # the logits of 256 positions are 0.15 GiB; a layer's experts 1.1
    assert mem.temp_size_in_bytes < 0.25 * _GIB
    text = exe.as_text()
    # the layer loop holds three kernels: attention, and the grouped
    # product twice (gate and up, then down) under the name the
    # benchmark's roofline reads; XLA's own grouped product is gone
    assert text.count("tpu_custom_call") == 3
    assert sum("tpu_custom_call" in ln
               and ln.lstrip().startswith("%grouped_ragged-dot")
               for ln in text.splitlines()) == 2
    assert "ragged-dot-metadata" not in text and " ragged-dot(" not in text


def _phi4_program(one_chip, what, slots=128):
    """The hybrid engine's programs at the benchmark's sizes: 128 slots,
    a page pool of 393,216 tokens (one layer, pages of 128 rows of 1,280),
    a table of 48 pages, chunks of 256."""
    from paddle_tpu.models import phi4flash
    from paddle_tpu.serving import phi4flash_engine
    cfg = phi4flash.Phi4FlashConfig()

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(
        sds, phi4flash.phi4flash_weight_shapes(cfg),
        is_leaf=lambda x: isinstance(x, tuple))
    pool = sds((1, 3073, 128, 1280))
    ring = sds((8, (slots + 1) * 4, 128, 1280))
    state = (sds((9, slots + 1, 16, 5120), F32), sds((9, slots + 1, 3, 5120)),
             ring, ring)
    carried = 2 * 2 * math.prod(pool.shape) + sum(
        math.prod(a.shape) * a.dtype.itemsize for a in state)
    if what.startswith("decode"):
        fn = functools.partial(phi4flash_engine.phi4flash_decode_fn, cfg=cfg)
        args = (params, pool, pool) + state \
            + (sds((int(what[6:]), 3 + 48), I32),)
        donate = (1, 2, 3, 4, 5, 6)
    else:
        fn = functools.partial(phi4flash_engine.phi4flash_chunk_fn, cfg=cfg)
        args = (params, pool, pool, sds((1, 256), I32), sds((), I32),
                sds((), I32), sds((1, 48), I32), sds((256,), I32)) + state \
            + (sds((3,), I32),)
        donate = (1, 2, 8, 9, 10, 11)
    return fn, args, donate, carried


@pytest.mark.parametrize("what,temp_gib", [("decode128", 0.25),
                                           ("chunk", 0.1), ("decode1", 0.1)])
def test_phi4flash_program_updates_pool_and_state_in_place(
        monkeypatch, one_chip, no_compile_cache, what, temp_gib):
    """Whole, for the described v5e: the weights (7.18 GiB), the page
    pool and the four state arrays fit beside the temporaries; every
    carried array is aliased; the kernels are the decode kernel over rows
    under the engine's names (a window layer's rings in the loop; the
    full layer and the cross layers' loop) and, in the chunk program, the
    scan (the pairs' loop, the memory layer), the full layer's attention
    with a position a row, and the cross-decoder's kernel."""
    fn, args, donate, carried = _phi4_program(one_chip, what)
    monkeypatch.setattr(selective_scan, "_interpret", lambda: False)
    exe = _compiled(monkeypatch, paged_attention, fn, *args, donate=donate)
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= carried > 4.7 * _GIB
    assert 11.9 * _GIB < mem.argument_size_in_bytes < 12.0 * _GIB
    assert mem.temp_size_in_bytes < temp_gib * _GIB
    text = exe.as_text()
    calls = [ln.lstrip().split(" ", 1)[0] for ln in text.splitlines()
             if "tpu_custom_call" in ln]
    assert len(calls) == (4 if what == "chunk" else 3)
    named = lambda name: sum(c.startswith("%" + name) for c in calls)
    if what == "chunk":
        assert named("selective_scan_chunk") == 2
        assert named("shared_kv_attention_chunk") == 1
        assert named("shared_kv_attention_last") == 1
    else:
        assert named("window_attention_decode") == 1
        assert named("shared_kv_attention_decode") == 2


def _glm5_program(one_chip, what):
    """The sparse latent engine's programs at the benchmark's sizes: the
    five served layers at the published widths (routers in float32), a
    latent pool of 524,800 tokens (pages of 512 rows of 384 32-bit words,
    each a row of 768 bfloat16 packed) and two index-key arrays of 128
    beside it, a table of 132 pages, 16 slots, chunks of 2,048."""
    from paddle_tpu.models import glm5
    from paddle_tpu.serving import glm5_engine
    cfg = glm5.Glm5Config(vocab_size=19360)

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)
    leaf = lambda n, s: sds(s, F32 if n in glm5.FLOAT32_LEAVES else BF16)
    params = {"embed": sds((19360, 6144)), "norm": sds((6144,)),
              "head": sds((6144, 19360)),
              "layers": [{n: leaf(n, s) for n, s in
                          glm5.glm5_layer_shapes(cfg, l).items()}
                         for l in range(5)]}
    lat = sds((5, 1025, 512, 1, 384), jnp.uint32)
    idx = sds((2, 1025, 512, 128))
    carried = 4 * math.prod(lat.shape) + 2 * math.prod(idx.shape)
    if what == "decode16":
        fn = functools.partial(glm5_engine.glm5_decode_fn, cfg=cfg)
        args = (params, lat, idx, sds((16, 2 + 132), I32))
    else:
        fn = functools.partial(glm5_engine.glm5_chunk_fn, cfg=cfg)
        args = (params, lat, idx, sds((1, 2048), I32), sds((), I32),
                sds((), I32), sds((1, 132), I32), sds((2048,), I32))
    return fn, args, carried


@pytest.mark.parametrize("what,temp_gib", [("decode16", 0.1),
                                           ("chunk", 2.4)])
def test_glm5_program_updates_its_pools_in_place(
        monkeypatch, one_chip, no_compile_cache, what, temp_gib):
    """Whole, for the described v5e: the weights (7.24 GiB) and the
    latent and index pools (4.00 GiB) beside the temporaries (the chunk's
    are its index scores over the table's 67,584 positions, the passes of
    the selection and its mask); both pools aliased, and no copy of the
    latent pool (the row writes are a kernel's: XLA's scatter would lay
    the pool out in tiles of 8 rows and back); the kernels under the
    names the benchmark reads: the index score twice (the layers with an
    indexer), the row writes and the attention over the selected rows
    five times each, the held experts' grouped product twice in each of
    four expert layers."""
    fn, args, carried = _glm5_program(one_chip, what)
    monkeypatch.setattr(grouped_matmul, "_interpret", lambda: False)
    exe = _compiled(monkeypatch, sparse_mla, fn, *args, donate=(1, 2))
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= carried > 3.9 * _GIB
    assert 11.2 * _GIB < mem.argument_size_in_bytes < 11.3 * _GIB
    assert mem.temp_size_in_bytes < temp_gib * _GIB
    text = exe.as_text()
    assert not [ln for ln in text.splitlines() if " copy(" in ln
                and "u32[5,1025,512,1,384]" in ln]
    calls = [ln.lstrip().split(" ", 1)[0] for ln in text.splitlines()
             if "tpu_custom_call" in ln]
    kind = "decode" if what == "decode16" else "prefill"
    named = lambda name: sum(c.startswith("%" + name) for c in calls)
    assert named(f"mla_indexer_{kind}") == 2
    assert named(f"sparse_mla_{kind}") == 5
    assert named("latent_row_write") == 5
    assert named("grouped_ragged-dot") == 8
    assert len(calls) == 20


@pytest.mark.parametrize("ps", [64, 128])
def test_paged_decode_over_rows(monkeypatch, one_chip, no_compile_cache, ps):
    """The decode kernel over a pool of rows at the hybrid engine's
    widths: ten heads of 128 side by side, sixteen query rows a head."""
    def decode(q, kr, vr, table, lens, layer):
        return paged_attention.paged_attention_decode_rows(
            q, kr, vr, table, lens, scale=0.125, layer=layer)
    assert _compile(monkeypatch, paged_attention, one_chip, decode,
                    ((128, 10, 16, 128), BF16), ((8, 1032, ps, 1280), BF16),
                    ((8, 1032, ps, 1280), BF16), ((128, 512 // ps), I32),
                    ((128,), I32), ((), I32)) == 1


def test_selective_scan_chunk(monkeypatch, one_chip, no_compile_cache):
    """The chunk's recurrence at the published widths: 256 positions,
    5,120 channels, 16 states."""
    assert _compile(
        monkeypatch, selective_scan, one_chip,
        selective_scan.selective_scan_chunk,
        ((256, 5120), BF16), ((256, 5120), F32), ((16, 5120), F32),
        ((256, 16), F32), ((256, 16), F32), ((16, 5120), F32)) == 1


@pytest.mark.parametrize("m", [32, 1024, 2048])
def test_grouped_matmul(monkeypatch, one_chip, no_compile_cache, m):
    """The SDAR cell's two expert products alone (a bucket of 1, of 32,
    of 64 slots or a chunk) against its flat stacks of 7 x 128 experts:
    two kernels, and no temporary the size of a layer's experts."""
    def products(rows, gate_up, down, counts, layer):
        meta = grouped_matmul.group_rows(counts, m)
        gate, up = jnp.split(grouped_matmul.grouped_matmul(
            rows, gate_up, meta, layer), 2, axis=-1)
        return grouped_matmul.grouped_matmul(
            (jax.nn.silu(gate) * up).astype(rows.dtype), down, meta, layer)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((m, 2048), BF16), ((896, 2048, 1536), BF16),
        ((896, 768, 2048), BF16), ((128,), I32), ((), I32))]
    exe = _compiled(monkeypatch, grouped_matmul, products, *args)
    assert exe.as_text().count("tpu_custom_call") == 2
    assert exe.memory_analysis().temp_size_in_bytes < 16 << 20


@pytest.mark.parametrize("m,k,n", [(8, 1024, 4096), (8, 4096, 1024)])
def test_int8_matmul(monkeypatch, one_chip, no_compile_cache, m, k, n):
    assert _compile(monkeypatch, int8_matmul, one_chip,
                    int8_matmul.int8_matmul,
                    ((m, k), BF16), ((k, n), I8), ((n,), F32)) == 1


# ERNIE-MoE-base widths: T1024 tokens, 8 experts, M768, top-2, capacity
# 1.25 x T x k / E = 320
_MOE = dict(T=1024, E=8, M=768, K=2, C=320)


def test_moe_dispatch(monkeypatch, one_chip, no_compile_cache):
    T, E, M, K, C = (_MOE[k] for k in "TEMKC")

    def dispatch(x, gw, gb):
        return moe_dispatch.fused_moe_dispatch(
            x, gw, gb, num_expert=E, capacity=C, top_k=K,
            gate_kind="gshard")
    assert _compile(monkeypatch, moe_dispatch, one_chip, dispatch,
                    ((T, M), BF16), ((M, E), F32), ((E,), F32)) == 1


def test_moe_combine(monkeypatch, one_chip, no_compile_cache):
    T, E, M, K, C = (_MOE[k] for k in "TEMKC")
    assert _compile(monkeypatch, moe_dispatch, one_chip,
                    moe_dispatch.fused_moe_combine,
                    ((E * C, M), BF16), ((T, K), F32), ((T, K), I32)) == 1
