"""The grouped-matmul kernel (interpret mode here) against a loop over
the groups: the row tile it reads from its shapes, group edges inside a
tile, groups with no row, rows under one tile and rows that fill no whole
number of tiles, a layer of a flat stack whose other layers must not be
read, and K over several k tiles. ``tests/test_chip_compile.py`` has the
chip's compiler on the same kernel at the benchmark's shapes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401  (x64 on, as every user of the kernels has it)
from paddle_tpu.kernels import grouped_matmul as gm

TOL = 5e-5      # float32 sums of up to 640 terms of size ~1 in another order


def _looped(lhs, rhs, counts, base):
    out = np.zeros((lhs.shape[0], rhs.shape[-1]), np.float64)
    off = 0
    for g, c in enumerate(counts):
        out[off:off + c] = lhs[off:off + c].astype(np.float64) \
            @ rhs[base + g].astype(np.float64)
        off += c
    return out


def _draw(kind, m, G, rng):
    if kind == "one":           # every row to one expert
        counts = np.zeros(G, np.int64)
        counts[G // 3] = m
    elif kind == "even":        # m no multiple of G: edges inside tiles
        counts = np.full(G, m // G)
        counts[:m % G] += 1
    else:                       # skewed: many experts with no row
        counts = rng.multinomial(m, rng.dirichlet(np.full(G, 0.2)))
    return counts.astype(np.int32)


def _check(m, K, N, G, counts, layers=1, layer=None, seed=0):
    rng = np.random.default_rng(seed)
    lhs = rng.standard_normal((m, K)).astype(np.float32)
    rhs = rng.standard_normal((layers * G, K, N)).astype(np.float32)
    base = 0 if layer is None else layer * G
    want = _looped(lhs, rhs, counts, base)
    if layer is not None:       # no other layer's weights may be read
        others = np.ones(layers * G, bool)
        others[base:base + G] = False
        rhs[others] = np.nan

    @jax.jit
    def run(lhs, rhs, counts, ly):
        rows = gm.group_rows(counts, m)
        return gm.grouped_matmul(lhs, rhs, rows,
                                 None if layer is None else ly), rows.visits
    got, visits = run(lhs, rhs, counts, np.int32(layer or 0))
    n = int(counts.sum())       # rows past the groups are undefined
    assert got.shape == (m, N) and got.dtype == jnp.float32
    assert np.abs(np.asarray(got)[:n] - want[:n]).max() < TOL * np.sqrt(K)
    assert int(visits[0]) == gm.tile_visits(counts, gm.row_tile(m, G))
    return int(visits[0])


@pytest.mark.parametrize("kind", ["one", "even", "skewed"])
@pytest.mark.parametrize("m", [32, 200, 1024, 2048])
def test_rows_and_draws_against_the_loop(m, kind):
    """The cell's row counts (a bucket of 1, of 32 and of 64 slots, a
    chunk) and one that fills no whole number of tiles, over 128 groups,
    read at layer 1 of a stack of 2."""
    G = 128
    counts = _draw(kind, m, G, np.random.default_rng(m))
    visits = _check(m, 64, 128, G, counts, layers=2, layer=1, seed=m)
    tiles = -(-m // 128)
    assert gm.row_tile(m, G) == 128
    if kind == "one":
        assert visits == tiles
    else:
        assert tiles <= visits <= tiles + np.count_nonzero(counts) - 1


@pytest.mark.parametrize("layer", [0, 2])
def test_a_layer_of_the_flat_stack_and_no_other(layer):
    counts = np.array([0, 5, 0, 130, 1, 0, 60, 0], np.int32)
    _check(256, 32, 256, 8, counts, layers=3, layer=layer)


def test_a_stack_of_one_layer_takes_no_layer():
    counts = np.array([3, 0, 125, 0], np.int32)
    _check(128, 32, 128, 4, counts)


def test_rows_past_the_groups_belong_to_none():
    """Counts that sum to fewer rows than there are: the visits end with
    the last group (rows 0-99, 100-159 over two tiles, 160-189; the
    third tile is never visited), and the rows they hold are right."""
    counts = np.array([100, 0, 60, 30], np.int32)
    assert _check(384, 32, 128, 4, counts) == 4


def test_k_over_several_tiles(monkeypatch):
    """The accumulator carries a visit across its k tiles."""
    monkeypatch.setattr(gm, "_WEIGHT_TILE_BYTES", 128 * 128 * 4)
    counts = np.array([70, 0, 200, 50], np.int32)
    _check(320, 640, 128, 4, counts)


def test_n_over_several_tiles(monkeypatch):
    monkeypatch.setattr(gm, "_TN_MAX", 128)
    counts = np.array([70, 0, 200, 50], np.int32)
    _check(320, 64, 384, 4, counts)


@pytest.mark.parametrize("m,G,tm", [
    (32, 128, 128), (1024, 128, 128), (2048, 128, 128), (2048, 8, 256),
    (4096, 8, 512), (65536, 8, 512), (255 * 128, 128, 128),
    (256 * 128, 128, 256)])
def test_row_tile_follows_rows_and_groups(m, G, tm):
    assert gm.row_tile(m, G) == tm


def test_bfloat16_operands_float32_accumulation():
    """bf16 in, bf16 out, the sum over K in float32: the result is the
    exact product of the bf16 operands, rounded once."""
    rng = np.random.default_rng(3)
    m, K, N, G = 256, 512, 128, 4
    counts = np.array([100, 0, 96, 60], np.int32)
    lhs = jnp.asarray(rng.standard_normal((m, K)), jnp.bfloat16)
    rhs = jnp.asarray(rng.standard_normal((G, K, N)), jnp.bfloat16)
    got = gm.grouped_matmul(lhs, rhs, gm.group_rows(jnp.asarray(counts), m))
    want = _looped(np.asarray(lhs, np.float32), np.asarray(rhs, np.float32),
                   counts, 0)
    assert got.dtype == jnp.bfloat16
    # half a bf16 step (at most 2**-8 of the value) and the float32
    # sum's own error
    assert (np.abs(np.asarray(got, np.float64) - want)
            <= np.abs(want) * 2.0 ** -8 * 1.01 + 1e-3).all()


def test_reference_path_is_the_same_product():
    rng = np.random.default_rng(4)
    counts = np.array([0, 40, 0, 88], np.int32)
    lhs = jnp.asarray(rng.standard_normal((128, 32)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((12, 32, 64)), jnp.float32)
    rows = gm.group_rows(jnp.asarray(counts), 128)
    got = gm.grouped_matmul(lhs, rhs, rows, 2)
    ref = gm.grouped_matmul_reference(lhs, rhs, jnp.asarray(counts), 2)
    assert np.abs(np.asarray(got) - np.asarray(ref)).max() < TOL


def test_shapes_that_do_not_fit_are_refused():
    rows = gm.group_rows(jnp.zeros((4,), jnp.int32), 128)
    with pytest.raises(ValueError):
        gm.grouped_matmul(jnp.zeros((128, 32)), jnp.zeros((4, 16, 8)), rows)
    with pytest.raises(ValueError):     # a stack of layers needs a layer
        gm.grouped_matmul(jnp.zeros((128, 32)), jnp.zeros((8, 32, 8)), rows)
    with pytest.raises(ValueError):     # more rows than the metadata holds
        gm.grouped_matmul(jnp.zeros((256, 32)), jnp.zeros((4, 32, 8)), rows)
