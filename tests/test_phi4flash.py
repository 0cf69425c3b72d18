"""Phi-4-mini-flash's layers (``models/phi4flash.py``) against the plain
reference (``models/phi4flash_reference.py``) on the tiny config: 12
layers (three Mamba/window pairs, the memory layer, the full layer, two
memory-unit/cross pairs), hidden 64, 4/2 heads of 16, window 8, 4 states,
vocabulary 128. Logits are compared, float32 at ``highest`` (conftest).
The engine's prefill and decode are in ``test_serving_hybrid.py``.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401
from paddle_tpu.kernels import selective_scan as scan
from paddle_tpu.models import phi4flash as M
from paddle_tpu.models import phi4flash_reference as ref

CFG = M.phi4flash_tiny_config()
TOL = 2e-5


@pytest.fixture(scope="module")
def weights():
    return M.init_phi4flash_weights(CFG, 3)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n)


# ------------------------------------------------------------ the layer map

def test_layer_map_at_the_published_depth_and_at_the_tiny_one():
    kinds = M.layer_kinds(M.Phi4FlashConfig())
    assert [l for l, k in enumerate(kinds) if k == "mamba"] \
        == list(range(0, 17, 2))
    assert [l for l, k in enumerate(kinds) if k == "window"] \
        == list(range(1, 16, 2))
    assert kinds[17] == "full"
    assert [l for l, k in enumerate(kinds) if k == "gmu"] \
        == list(range(18, 32, 2))
    assert [l for l, k in enumerate(kinds) if k == "cross"] \
        == list(range(19, 32, 2))
    assert M.layer_kinds(CFG) == ["mamba", "window"] * 3 \
        + ["mamba", "full"] + ["gmu", "cross"] * 2
    with pytest.raises(ValueError, match="multiple of 4"):
        M.Phi4FlashConfig(num_hidden_layers=4)


def test_published_widths_count_3853m_parameters():
    """ISSUE 36's arithmetic: 3,853 M = 7.18 GiB in bfloat16."""
    cfg = M.Phi4FlashConfig()
    shapes = jax.tree_util.tree_leaves(
        M.phi4flash_weight_shapes(cfg),
        is_leaf=lambda s: isinstance(s, tuple))
    n = sum(math.prod(s) for s in shapes)
    assert abs(n - 3853e6) < 2e6
    assert abs(2 * n / 2 ** 30 - 7.18) < 0.01
    assert (cfg.head_dim, cfg.d_inner, cfg.rank, cfg.pair_dim) \
        == (64, 5120, 160, 128)
    assert (cfg.n_self_pairs, cfg.n_mamba, cfg.n_cross_pairs) == (8, 9, 7)


def test_state_space_weights_start_as_published(weights):
    """A state that remembers: ``A = -(1..N)`` a channel, steps in [1e-3,
    1e-1], ``D`` near 1; with N(0, 0.02) everywhere a state would forget
    in two positions."""
    p = weights["l16"]
    A = np.exp(np.asarray(p["A_log"]))
    assert np.allclose(A.mean(1), np.arange(1, CFG.d_state + 1), rtol=0.05)
    dt = np.log1p(np.exp(np.asarray(p["b_dt"])))
    assert 0.9e-3 < dt.min() and dt.max() < 1.1e-1
    assert abs(np.asarray(p["D"]).mean() - 1) < 0.02
    assert 0.05 < np.asarray(weights["l17"]["lam"]).std() < 0.2
    # the slowest state of the widest step still holds 0.9 after a step
    assert np.exp(-dt.max() * A[0]).min() > 0.85


# --------------------------------------------------- full form == reference

@pytest.mark.parametrize("n", [1, 7, 8, 9, 37])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_full_sequence_form_agrees_with_the_reference(weights, n,
                                                      use_kernel):
    ids = _ids(n, seed=n)
    got = M.forward_full(weights, jnp.asarray(ids), CFG, use_kernel)
    want = ref.forward(weights, ids, CFG)
    assert got.shape == (n, CFG.vocab_size)
    assert float(jnp.abs(got - want).max()) < TOL


def test_the_window_is_eight_keys_with_the_querys_own(weights):
    """The reference at 7, 8 and 9 differ; the program agrees with 8."""
    ids = _ids(24, seed=5)
    got = M.forward_full(weights, jnp.asarray(ids), CFG)
    by = {w: ref.forward(weights, ids, CFG, window=w) for w in (7, 8, 9)}
    assert float(jnp.abs(got - by[8]).max()) < TOL
    for w in (7, 9):
        assert float(jnp.abs(by[w] - by[8]).max()) > 100 * TOL
        # up to the window's reach the three are one
        assert float(jnp.abs(by[w][:7] - by[8][:7]).max()) < TOL


def test_the_control_precision_moves_the_logits(weights):
    ids = _ids(20, seed=2)
    sound = ref.forward(weights, ids, CFG)
    for mode, least in (("bf16", 1e-4), ("fp8", 1e-3)):
        assert float(jnp.abs(ref.forward(weights, ids, CFG, mode=mode)
                             - sound).max()) > least


# ----------------------------------------------------------- the scan kernel

def _scan_inputs(C, Di, N, seed):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    return (f(C, Di), jnp.abs(f(C, Di)) * 0.1,
            -jnp.exp(f(N, Di) * 0.3), f(C, N), f(C, N), f(N, Di))


@pytest.mark.parametrize("C,Di,N", [(8, 128, 4), (16, 256, 16), (5, 128, 4),
                                    (24, 1024, 16)])
def test_selective_scan_kernel_is_the_recurrence(C, Di, N):
    args = _scan_inputs(C, Di, N, C + Di)
    y, s = scan.selective_scan_chunk(*args)
    y0, s0 = scan.selective_scan_reference(*args)
    assert y.shape == (C, Di) and s.shape == (N, Di)
    assert float(jnp.abs(y - y0).max()) < 1e-4
    assert float(jnp.abs(s - s0).max()) < 1e-4


def test_selective_scan_carries_the_state_and_skips_padding():
    x, dt, A, B, Cm, s0 = _scan_inputs(16, 128, 4, 1)
    y, s = scan.selective_scan_chunk(x, dt, A, B, Cm, s0)
    # two chunks of 8, the state handed across
    y1, s1 = scan.selective_scan_chunk(x[:8], dt[:8], A, B[:8], Cm[:8], s0)
    y2, s2 = scan.selective_scan_chunk(x[8:], dt[8:], A, B[8:], Cm[8:], s1)
    assert float(jnp.abs(jnp.concatenate([y1, y2]) - y).max()) < 1e-5
    assert float(jnp.abs(s2 - s).max()) < 1e-5
    # dt = 0 from row 11 on: the state is that of row 10
    masked = dt.at[11:].set(0)
    _, s_pad = scan.selective_scan_chunk(x, masked, A, B, Cm, s0)
    _, s_11 = scan.selective_scan_chunk(x[:11], dt[:11], A, B[:11], Cm[:11],
                                        s0)
    assert float(jnp.abs(s_pad - s_11).max()) < 1e-6
    assert scan.channel_tile(5120) == 512 and scan.channel_tile(128) == 128


# -------------------------------------------- pairs of heads as cache rows

def test_paired_queries_give_a1_and_a2_from_rows_of_one_width():
    """``[q1 ; 0]`` and ``[0 ; q2]`` against cache rows a pair of heads
    wide: plain attention over q, k and v rows of one width gives the two
    differential maps."""
    cfg = M.phi4flash_tiny_config(num_attention_heads=16,
                                  num_key_value_heads=8, hidden_size=128)
    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    T = 11
    q = f(3, cfg.q_pairs, cfg.pair_dim)
    k, v = f(T, cfg.kv_pairs, cfg.pair_dim), f(T, cfg.kv_pairs, cfg.pair_dim)
    a1, a2 = M.diff_attention_dense(q, k, v, jnp.ones((3, T), bool), cfg)
    rows = M.paired_queries(q, cfg)
    assert rows.shape == (3, cfg.kv_pairs, 16, cfg.pair_dim)
    assert float(jnp.abs(rows[:, :, 4:]).max()) == 0      # the fill
    s = jnp.einsum("nhgd,thd->nhgt", rows, k) / math.sqrt(cfg.head_dim)
    out = jnp.einsum("nhgt,thd->nhgd", jax.nn.softmax(s, -1), v)
    b1, b2 = M.unpair_outputs(out, cfg)
    assert float(jnp.abs(a1 - b1).max()) < 1e-5
    assert float(jnp.abs(a2 - b2).max()) < 1e-5


@pytest.mark.parametrize("g,ps", [(16, 8), (4, 4)])
def test_decode_kernel_over_a_pool_of_rows_is_the_reference(g, ps):
    """``paged_attention_decode_rows`` (interpret mode) against the XLA
    reference over the same rows cut into heads: ragged lengths, an idle
    slot, a layer picked by index."""
    from paddle_tpu.kernels import paged_attention as pa
    rng = np.random.default_rng(1)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    L, P, nkv, d, B, pages = 3, 13, 2, 32, 4, 3
    kr, vr = f(L, P, ps, nkv * d), f(L, P, ps, nkv * d)
    q = f(B, nkv, g, d)
    table = jnp.asarray(rng.permutation(np.arange(1, P))[:B * pages]
                        .reshape(B, pages), jnp.int32)
    lens = jnp.asarray([ps * pages, 1, 0, ps + 1], jnp.int32)
    for layer in (0, 2):
        got = pa.paged_attention_decode_rows(q, kr, vr, table, lens,
                                             layer=layer, name="rows_test")
        want = pa.paged_attention_decode_rows(q, kr, vr, table, lens,
                                              layer=layer, use_kernel=False)
        live = np.asarray(lens) > 0
        assert float(jnp.abs(got - want)[live].max()) < 1e-5
        assert bool(jnp.isfinite(got).all())
    with pytest.raises(ValueError, match="side by side"):
        pa.paged_attention_decode_rows(q[..., :16], kr, vr, table, lens)


@pytest.mark.parametrize("use_kernel", [True, False])
def test_decode_kernel_over_rows_hands_out_its_accumulator(use_kernel):
    """Served rows are bfloat16; the caller subtracts one head's output
    from another's, so it gets float32: what the accumulator held, not
    that rounded to the served type and widened again."""
    from paddle_tpu.kernels import paged_attention as pa
    rng = np.random.default_rng(2)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.bfloat16)
    kr, vr, q = f(1, 5, 8, 64), f(1, 5, 8, 64), f(2, 2, 16, 32)
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    lens = jnp.asarray([16, 11], jnp.int32)
    out = pa.paged_attention_decode_rows(q, kr, vr, table, lens,
                                         use_kernel=use_kernel)
    assert out.dtype == jnp.float32
    exact = pa.paged_attention_decode_rows(
        *(a.astype(jnp.float32) for a in (q, kr, vr)), table, lens,
        use_kernel=False)
    off = lambda a: float(jnp.abs(a.astype(jnp.float32) - exact).max())
    # (the kernel's weights meet the values in the served type, which
    # is what is left of its distance)
    assert off(out) < 0.6 * off(out.astype(jnp.bfloat16))


def test_window_chunks_over_a_ring_are_the_dense_window():
    """Chunks of 8 over a ring of 8 rows, the last one padded, against
    one dense pass under the window mask."""
    rng = np.random.default_rng(4)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    S, C, W = 21, 8, CFG.sliding_window
    q = f(S, CFG.q_pairs, CFG.pair_dim)
    k, v = f(S, CFG.kv_pairs, CFG.pair_dim), f(S, CFG.kv_pairs, CFG.pair_dim)
    pos = jnp.arange(S)
    mask = (pos[None] <= pos[:, None]) & (pos[None] > pos[:, None] - W)
    want1, want2 = M.diff_attention_dense(q, k, v, mask, CFG)
    rk = jnp.full((W,) + k.shape[1:], jnp.nan)     # a row unread until set
    rv = jnp.full((W,) + k.shape[1:], jnp.nan)
    pad = lambda a: jnp.concatenate(
        [a, jnp.zeros((C,) + a.shape[1:], a.dtype)])
    qp, kp, vp = pad(q), pad(k), pad(v)
    for off in range(0, S, C):
        n = min(C, S - off)
        a1, a2, rk, rv = M.window_chunk_attention(
            qp[off:off + C], kp[off:off + C], vp[off:off + C],
            jnp.nan_to_num(rk), jnp.nan_to_num(rv), off, n, CFG)
        assert float(jnp.abs(a1[:n] - want1[off:off + n]).max()) < 1e-5
        assert float(jnp.abs(a2[:n] - want2[off:off + n]).max()) < 1e-5
    held = np.asarray(M.ring_positions(S, W))
    assert sorted(held) == list(range(S - W, S))
    assert float(jnp.abs(rk - k[held]).max()) == 0


@pytest.mark.parametrize("kernel", ["paged_attention_decode",
                                    "ragged_prefill_attention"])
def test_paged_kernels_say_what_widths_they_take(kernel):
    from paddle_tpu.kernels import paged_attention as pa
    pool = jnp.zeros((3, 4, 2, 32))
    table = jnp.zeros((2, 2), jnp.int32)
    with pytest.raises(ValueError, match="rows of one width.*zero-filled"):
        if kernel == "paged_attention_decode":
            pa.paged_attention_decode(jnp.zeros((2, 4, 16)), pool, pool,
                                      table, jnp.ones((2,), jnp.int32))
        else:
            pa.ragged_prefill_attention(jnp.zeros((2, 8, 4, 16)), pool, pool,
                                        table, 0)
