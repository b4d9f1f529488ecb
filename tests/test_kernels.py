"""Per-kernel correctness: sweep shapes/dtypes, assert_allclose vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# centroid_assign
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,M,D", [
    (1, 1, 8), (7, 13, 32), (64, 64, 128), (130, 257, 64), (256, 50, 512),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_centroid_assign_matches_ref(B, M, D, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(B * M + D))
    f = jax.random.normal(k1, (B, D), dtype)
    c = jax.random.normal(k2, (M, D), dtype)
    d2, j = ops.centroid_assign(f, c)
    d2r, jr = ref.centroid_assign_ref(f, c)
    np.testing.assert_array_equal(np.asarray(j), np.asarray(jr))
    np.testing.assert_allclose(np.asarray(d2), np.asarray(d2r),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-3)


@pytest.mark.parametrize("bb,bm", [(8, 8), (32, 16), (128, 128)])
def test_centroid_assign_block_shapes(bb, bm):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    f = jax.random.normal(k1, (100, 96))
    c = jax.random.normal(k2, (77, 96))
    d2, j = ops.centroid_assign(f, c, bb=bb, bm=bm)
    d2r, jr = ref.centroid_assign_ref(f, c)
    np.testing.assert_array_equal(np.asarray(j), np.asarray(jr))
    np.testing.assert_allclose(np.asarray(d2), np.asarray(d2r), atol=1e-4)


@pytest.mark.parametrize("B,M,D,T", [
    (7, 13, 32, 7.0), (64, 64, 128, 14.0), (130, 257, 64, 10.0),
])
def test_centroid_assign_fused_threshold_matches_ref(B, M, D, T):
    """The kernel-emitted matched mask == host-side d2 <= T**2 compare."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(B * M + D))
    f = jax.random.normal(k1, (B, D))
    c = jax.random.normal(k2, (M, D))
    d2, j, m = ops.centroid_assign(f, c, threshold=T)
    d2r, jr, mr = ref.centroid_assign_ref(f, c, threshold=T)
    np.testing.assert_array_equal(np.asarray(j), np.asarray(jr))
    np.testing.assert_allclose(np.asarray(d2), np.asarray(d2r), atol=1e-3)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(mr))
    assert np.asarray(m).dtype == np.bool_
    # threshold must actually discriminate in this draw
    assert 0 < np.asarray(m).sum() < B


def test_centroid_assign_threshold_none_keeps_two_outputs():
    f = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    c = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
    out = ops.centroid_assign(f, c)
    assert len(out) == 2


def test_centroid_assign_identical_rows():
    """Distance to an exact-duplicate centroid must be ~0 at the dup index."""
    f = jnp.tile(jnp.arange(32, dtype=jnp.float32)[None], (4, 1))
    c = jnp.stack([jnp.arange(32, dtype=jnp.float32) + 5,
                   jnp.arange(32, dtype=jnp.float32)])
    d2, j = ops.centroid_assign(f, c)
    assert (np.asarray(j) == 1).all()
    np.testing.assert_allclose(np.asarray(d2), 0.0, atol=1e-4)


# ---------------------------------------------------------------------------
# topk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,C,k", [
    (1, 10, 1), (4, 1000, 7), (9, 1000, 60), (130, 1000, 200), (32, 128, 128),
])
def test_topk_matches_ref(B, C, k):
    lg = jax.random.normal(jax.random.PRNGKey(B + C + k), (B, C))
    v, i = ops.topk(lg, k)
    vr, ir = ref.topk_ref(lg, k)
    # exact: both kernel and oracle copy the f32 inputs, no arithmetic
    np.testing.assert_array_equal(np.asarray(v), np.asarray(vr))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))


def test_topk_with_ties():
    lg = jnp.zeros((3, 50))
    v, i = ops.topk(lg, 5)
    # ties broken by lowest index, values all equal
    np.testing.assert_array_equal(np.asarray(i),
                                  np.tile(np.arange(5), (3, 1)))


@pytest.mark.parametrize("B", [1, 2, 5, 7])
def test_topk_tiny_batches_below_tile_floor(B):
    """B < 8: the row tile clamps to the 8-row VPU floor, the batch is
    padded up with -inf rows, and outputs are trimmed back to [:B]."""
    lg = jax.random.normal(jax.random.PRNGKey(B), (B, 37))
    v, i = ops.topk(lg, 3)
    vr, ir = ref.topk_ref(lg, 3)
    assert v.shape == (B, 3) and i.shape == (B, 3)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(vr))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))


def test_topk_k_equals_C_is_full_sort():
    lg = jax.random.normal(jax.random.PRNGKey(9), (5, 16))
    v, i = ops.topk(lg, 16)
    vr, ir = ref.topk_ref(lg, 16)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(vr))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
    # every column index appears exactly once per row (C-pad never leaks)
    np.testing.assert_array_equal(np.sort(np.asarray(i), axis=1),
                                  np.tile(np.arange(16), (5, 1)))


def test_topk_k_out_of_range_raises():
    lg = jax.random.normal(jax.random.PRNGKey(0), (4, 10))
    with pytest.raises(ValueError):
        ops.topk(lg, 11)          # k > C: only C classes exist to rank
    with pytest.raises(ValueError):
        ops.topk(lg, 0)


def test_topk_empty_batch():
    v, i = ops.topk(jnp.zeros((0, 12)), 4)
    assert v.shape == (0, 4) and i.shape == (0, 4)


def test_topk_oversized_bb_clamps_to_batch():
    """bb far larger than B degrades to one tile — results identical to a
    small explicit tile."""
    lg = jax.random.normal(jax.random.PRNGKey(4), (3, 40))
    v_big, i_big = ops.topk(lg, 5, bb=4096)
    v_small, i_small = ops.topk(lg, 5, bb=8)
    np.testing.assert_array_equal(np.asarray(i_big), np.asarray(i_small))
    np.testing.assert_allclose(np.asarray(v_big), np.asarray(v_small))


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 40), st.integers(2, 300), st.data())
def test_topk_property(B, C, data):
    k = data.draw(st.integers(1, C))
    lg = jax.random.normal(jax.random.PRNGKey(B * 31 + C), (B, C))
    v, i = ops.topk(lg, k)
    v, i = np.asarray(v), np.asarray(i)
    # descending order, indices valid, values match logits at indices
    assert (np.diff(v, axis=1) <= 1e-6).all()
    assert ((i >= 0) & (i < C)).all()
    np.testing.assert_allclose(np.take_along_axis(np.asarray(lg), i, 1), v,
                               atol=1e-6)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,dh,causal", [
    (16, 16, True), (64, 32, True), (64, 32, False), (128, 64, True),
    (50, 16, True), (96, 128, False),
])
def test_flash_attention_matches_ref(S, dh, causal):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(S + dh), 3)
    shape = (2, S, 3, dh)
    q = jax.random.normal(k1, shape)
    k = jax.random.normal(k2, shape)
    v = jax.random.normal(k3, shape)
    out = ops.flash_attention(q, k, v, causal=causal, bq=32, bk=32)
    expect = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("bq,bk", [(16, 16), (32, 8), (8, 32), (128, 128)])
def test_flash_attention_block_sweep(bq, bk):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k1, (1, 64, 2, 32))
    k = jax.random.normal(k2, (1, 64, 2, 32))
    v = jax.random.normal(k3, (1, 64, 2, 32))
    out = ops.flash_attention(q, k, v, causal=True, bq=bq, bk=bk)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)


def test_flash_attention_bf16():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(k1, (2, 32, 2, 32), jnp.bfloat16)
    k = jax.random.normal(k2, (2, 32, 2, 32), jnp.bfloat16)
    v = jax.random.normal(k3, (2, 32, 2, 32), jnp.bfloat16)
    out = ops.flash_attention(q, k, v, causal=True, bq=16, bk=16)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=3e-2)


def test_flash_attention_matches_model_attention():
    """The kernel plugs into multihead_attention (attn_impl="flash")."""
    from repro.models import layers as L
    rng = jax.random.PRNGKey(3)
    p = L.attn_init(rng, 64, 4, 4, jnp.float32)
    x = jax.random.normal(rng, (2, 32, 64))
    out_e = L.multihead_attention(p, x, n_heads=4, n_kv_heads=4, causal=True,
                                  attn_impl="einsum")
    out_f = L.multihead_attention(p, x, n_heads=4, n_kv_heads=4, causal=True,
                                  attn_impl="flash")
    np.testing.assert_allclose(np.asarray(out_e), np.asarray(out_f),
                               atol=1e-4)


# ---------------------------------------------------------------------------
# pixel_match
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Na,Nb,D", [
    (1, 1, 8), (7, 13, 48), (37, 19, 300), (64, 64, 192), (130, 257, 96),
])
def test_pixel_match_matches_ref(Na, Nb, D):
    k1, k2 = jax.random.split(jax.random.PRNGKey(Na * Nb + D))
    a = jax.random.uniform(k1, (Na, D))
    b = jax.random.uniform(k2, (Nb, D))
    m, d = ops.pixel_match(a, b, 0.2)
    mr, dr = ref.pixel_match_ref(a, b, 0.2)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(mr))
    np.testing.assert_allclose(np.asarray(d), np.asarray(dr), atol=1e-6)


@pytest.mark.parametrize("ba,bn", [(8, 8), (32, 16), (16, 64), (128, 128)])
def test_pixel_match_block_shapes(ba, bn):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.uniform(k1, (100, 96))
    b = jax.random.uniform(k2, (77, 96))
    m, d = ops.pixel_match(a, b, 0.25, ba=ba, bn=bn)
    mr, dr = ref.pixel_match_ref(a, b, 0.25)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(mr))
    np.testing.assert_allclose(np.asarray(d), np.asarray(dr), atol=1e-6)


def test_pixel_match_exact_duplicate_wins():
    rng = np.random.default_rng(0)
    a = rng.random((9, 64)).astype(np.float32)
    b = rng.random((5, 64)).astype(np.float32)
    b[3] = a[6]                              # exact duplicate
    m, d = ops.pixel_match(a, b, 1e-6)
    assert int(np.asarray(m)[6]) == 3
    assert float(np.asarray(d)[6]) == 0.0


def test_pixel_match_threshold_is_strict():
    """A min diff exactly AT the threshold must not match (host
    pixel_difference contract: < threshold, not <=)."""
    a = np.zeros((1, 16), np.float32)
    b = np.full((1, 16), 0.5, np.float32)    # mean abs diff exactly 0.5
    m, _ = ops.pixel_match(a, b, 0.5)
    assert int(np.asarray(m)[0]) == -1
    m, _ = ops.pixel_match(a, b, np.nextafter(np.float32(0.5),
                                              np.float32(1.0)))
    assert int(np.asarray(m)[0]) == 0


def test_pixel_match_tie_breaks_to_lowest_index():
    a = np.full((3, 8), 0.25, np.float32)
    b = np.stack([np.full(8, 0.5, np.float32)] * 4)   # all refs equidistant
    m, _ = ops.pixel_match(a, b, 1.0)
    np.testing.assert_array_equal(np.asarray(m), 0)


def test_pixel_match_empty_inputs():
    m, d = ops.pixel_match(np.zeros((0, 8), np.float32),
                           np.ones((3, 8), np.float32), 0.1)
    assert m.shape == (0,) and d.shape == (0,)
    m, d = ops.pixel_match(np.ones((3, 8), np.float32),
                           np.zeros((0, 8), np.float32), 0.1)
    assert (np.asarray(m) == -1).all()
    assert np.isinf(np.asarray(d)).all()


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 30), st.integers(1, 30), st.data())
def test_pixel_match_property(Na, Nb, data):
    D = data.draw(st.sampled_from([8, 33, 100]))
    thr = data.draw(st.floats(0.01, 0.5))
    k1, k2 = jax.random.split(jax.random.PRNGKey(Na * 31 + Nb))
    a = jax.random.uniform(k1, (Na, D))
    b = jax.random.uniform(k2, (Nb, D))
    m, d = ops.pixel_match(a, b, thr)
    mr, dr = ref.pixel_match_ref(a, b, thr)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(mr))
    np.testing.assert_allclose(np.asarray(d), np.asarray(dr), atol=1e-6)


# ---------------------------------------------------------------------------
# pixel_match_block / pixel_match_resident
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Na,Nb,D", [
    (8, 8, 48), (16, 40, 192), (32, 256, 96), (256, 1024, 48),
])
def test_pixel_match_block_equals_numpy_and_pixel_match(Na, Nb, D):
    """The block's distances equal the numpy block within float32
    rounding, and at each row's argmin they are ``pixel_match``'s
    ``min_d`` bit for bit; ``decide`` over the block is its match."""
    from repro.data.bgsub import decide, numpy_block
    r = np.random.default_rng(Na * Nb + D)
    a = r.random((Na, D)).astype(np.float32)
    b = r.random((Nb, D)).astype(np.float32)
    b[Nb // 2] = a[0]                        # an exact duplicate
    blk = np.asarray(ops.pixel_match_block(a, b))
    assert blk.shape == (Na, Nb) and blk.dtype == np.float32
    np.testing.assert_allclose(blk, numpy_block(a, b), rtol=1e-6,
                               atol=1e-7)
    np.testing.assert_allclose(blk, np.asarray(ref.pixel_match_block_ref(
        a, b)), rtol=1e-6, atol=1e-7)
    m, d = ops.pixel_match(a, b, 0.3)
    j = blk.argmin(1)
    np.testing.assert_array_equal(blk[np.arange(Na), j], np.asarray(d))
    np.testing.assert_array_equal(decide(blk, 0.3), np.asarray(m))
    assert decide(blk, 0.3)[0] == Nb // 2


@pytest.mark.parametrize("S,Na,nb", [(8, 8, 64), (24, 16, 64),
                                     (256, 8, 512), (256, 256, 512)])
def test_pixel_match_resident_pads_with_sentinels(S, Na, nb):
    """Against ``[store; crops]`` padded to ``nb`` rows: the trimmed block
    equals ``pixel_match_block`` on the unpadded references, and the
    sentinel rows, kept in the untrimmed kernel output, never win."""
    from repro.kernels import pixel_diff as _pd
    r = np.random.default_rng(S + Na)
    store = r.random((S, 48)).astype(np.float32)
    a = r.random((Na, 48)).astype(np.float32)
    got = np.asarray(ops.pixel_match_resident(store, a, nb))
    assert got.shape == (Na, S + Na)
    want = np.asarray(ops.pixel_match_block(a, np.concatenate([store, a])))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(got, np.asarray(
        ref.pixel_match_resident_ref(store, a)), rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(np.diag(got[:, S:]), 0.0)
    full = np.asarray(_pd._block(a, np.pad(
        np.concatenate([store, a]), ((0, nb - S - Na), (0, 0)),
        constant_values=_pd.PAD), 4096, 128, True))
    assert (full[:Na, S + Na:] > 1e17).all()
    assert (full[:Na].argmin(1) < S + Na).all()


def test_store_put_writes_and_drops_padding():
    store = np.zeros((16, 8), np.float32)
    rows = np.arange(64, dtype=np.float32).reshape(8, 8)
    out = np.asarray(ops.store_put(store, rows, np.array([3, 5, 0, 0]),
                                   np.array([2, 9, 16, 16])))
    want = np.zeros((16, 8), np.float32)
    want[2], want[9] = rows[3], rows[5]
    np.testing.assert_array_equal(out, want)


# ---------------------------------------------------------------------------
# motion_gate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("H,W,tile", [
    (8, 8, 8), (64, 64, 8), (70, 51, 8), (128, 128, 16), (33, 95, 8),
    (16, 24, 4),
])
def test_motion_gate_matches_ref(H, W, tile):
    k1, k2 = jax.random.split(jax.random.PRNGKey(H * W + tile))
    f = jax.random.uniform(k1, (H, W, 3))
    bg = jax.random.uniform(k2, (H, W, 3))
    nb, t, h = ops.motion_gate(f, bg, 0.05, 0.08, tile=tile)
    nbr, tr, hr = ref.motion_gate_ref(f, bg, 0.05, 0.08, tile)
    assert nb.shape == (H, W, 3)
    assert t.shape == (H // tile, W // tile)
    np.testing.assert_allclose(np.asarray(nb), np.asarray(nbr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(t), np.asarray(tr), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(hr))
    assert np.asarray(h).dtype == np.bool_


@pytest.mark.parametrize("bh", [8, 16, 64, 256])
def test_motion_gate_row_block_sweep(bh):
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    f = jax.random.uniform(k1, (100, 40, 3))
    bg = jax.random.uniform(k2, (100, 40, 3))
    nb, t, h = ops.motion_gate(f, bg, 0.1, 0.05, tile=8, bh=bh)
    nbr, tr, hr = ref.motion_gate_ref(f, bg, 0.1, 0.05, 8)
    np.testing.assert_allclose(np.asarray(nb), np.asarray(nbr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(t), np.asarray(tr), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(hr))


def test_motion_gate_smaller_than_one_tile():
    """ty == 0 or tx == 0: empty tile grid, background still updates."""
    f = np.full((4, 20, 3), 1.0, np.float32)
    bg = np.zeros((4, 20, 3), np.float32)
    nb, t, h = ops.motion_gate(f, bg, 0.5, 0.01, tile=8)
    assert t.shape == (0, 2) and h.shape == (0, 2)
    np.testing.assert_allclose(np.asarray(nb), 0.5, atol=1e-7)
    nb, t, h = ops.motion_gate(f[:, :4], bg[:, :4], 0.5, 0.01, tile=8)
    assert t.shape == (0, 0) and h.shape == (0, 0)


def test_motion_gate_static_frame_is_cold():
    """frame == bg -> zero diff everywhere, no hot tiles, bg unchanged."""
    f = np.random.default_rng(0).random((48, 48, 3)).astype(np.float32)
    nb, t, h = ops.motion_gate(f, f, 0.05, 0.0, tile=8)
    np.testing.assert_allclose(np.asarray(nb), f, atol=1e-7)
    np.testing.assert_allclose(np.asarray(t), 0.0, atol=1e-7)
    assert not np.asarray(h).any()   # strict >: exactly-zero is not hot


def test_motion_gate_threshold_is_strict():
    f = np.full((8, 8, 3), 0.5, np.float32)
    bg = np.zeros((8, 8, 3), np.float32)     # every tile mean is exactly 0.5
    _, _, h = ops.motion_gate(f, bg, 0.0, 0.5, tile=8)
    assert not np.asarray(h).any()
    _, _, h = ops.motion_gate(f, bg, 0.0, 0.4999, tile=8)
    assert np.asarray(h).all()


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 80), st.integers(1, 80), st.data())
def test_motion_gate_property(H, W, data):
    tile = data.draw(st.sampled_from([4, 8, 16]))
    alpha = data.draw(st.floats(0.0, 1.0))
    thr = data.draw(st.floats(0.0, 0.3))
    k1, k2 = jax.random.split(jax.random.PRNGKey(H * 97 + W))
    f = jax.random.uniform(k1, (H, W, 3))
    bg = jax.random.uniform(k2, (H, W, 3))
    nb, t, h = ops.motion_gate(f, bg, alpha, thr, tile=tile)
    nbr, tr, hr = ref.motion_gate_ref(f, bg, alpha, thr, tile)
    np.testing.assert_allclose(np.asarray(nb), np.asarray(nbr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(t), np.asarray(tr), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(hr))


# ---------------------------------------------------------------------------
# dequant_topk
# ---------------------------------------------------------------------------

def _quant_rows(M, C, dtype, seed):
    r = np.random.default_rng(seed)
    if dtype == np.uint8:
        q = r.integers(0, 256, (M, C)).astype(np.uint8)
    else:
        q = r.integers(-127, 128, (M, C)).astype(np.int8)
    scales = r.uniform(0.1, 2.0, M).astype(np.float32)
    return q, scales


@pytest.mark.parametrize("M,C,k", [
    (1, 1, 1), (7, 5, 3), (33, 16, 4), (64, 128, 128), (129, 200, 7),
    (130, 257, 60),
])
@pytest.mark.parametrize("dtype", [np.uint8, np.int8])
def test_dequant_topk_matches_ref(M, C, k, dtype):
    """Exact: kernel and oracle apply the identical f32 scale chain, so
    values match bitwise across non-multiple-of-block shapes."""
    q, scales = _quant_rows(M, C, dtype, M * C + k)
    v, i = ops.dequant_topk(q, scales, k, global_scale=1.0 / 255.0)
    vr, ir = ref.dequant_topk_ref(q, scales, k, global_scale=1.0 / 255.0)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(vr))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))


@pytest.mark.parametrize("bm", [8, 16, 128, 4096])
def test_dequant_topk_block_sweep(bm):
    q, scales = _quant_rows(100, 96, np.int8, 0)
    v, i = ops.dequant_topk(q, scales, 5, bm=bm)
    vr, ir = ref.dequant_topk_ref(q, scales, 5)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(vr))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))


def test_dequant_topk_ties_break_to_lowest_index():
    """Quantization collapses nearby probs into exact ties; rank order
    must still be deterministic (lowest column index first) to match the
    host-side _rank_rows and lax.top_k."""
    q = np.full((3, 50), 7, np.uint8)
    scales = np.ones(3, np.float32)
    v, i = ops.dequant_topk(q, scales, 5)
    np.testing.assert_array_equal(np.asarray(i),
                                  np.tile(np.arange(5), (3, 1)))
    np.testing.assert_array_equal(np.asarray(v), 7.0)


def test_dequant_topk_per_row_scale_applied():
    """Same quantized codes, different row scales -> scaled values; the
    ranking (within a row) is scale-invariant for positive scales."""
    q = np.tile(np.array([10, 30, 20], np.uint8), (2, 1))
    scales = np.array([1.0, 0.5], np.float32)
    v, i = ops.dequant_topk(q, scales, 3)
    np.testing.assert_array_equal(np.asarray(i),
                                  np.tile([1, 2, 0], (2, 1)))
    np.testing.assert_array_equal(np.asarray(v),
                                  [[30.0, 20.0, 10.0], [15.0, 10.0, 5.0]])


def test_dequant_topk_k_equals_C_never_leaks_pad():
    """C is padded to the 128-lane multiple with dtype-min; with k == C
    every real column must appear exactly once per row."""
    q, scales = _quant_rows(5, 16, np.int8, 9)
    v, i = ops.dequant_topk(q, scales, 16)
    vr, ir = ref.dequant_topk_ref(q, scales, 16)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(vr))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
    np.testing.assert_array_equal(np.sort(np.asarray(i), axis=1),
                                  np.tile(np.arange(16), (5, 1)))


def test_dequant_topk_uint8_zero_rows_with_pad():
    """All-zero uint8 rows tie with the column pad value (0); the pad
    columns sit at the highest indices so lowest-index ties keep them
    out for every k <= C."""
    q = np.zeros((4, 100), np.uint8)          # C=100 pads to 128
    scales = np.ones(4, np.float32)
    v, i = ops.dequant_topk(q, scales, 100)
    assert (np.asarray(i) < 100).all()
    np.testing.assert_array_equal(np.asarray(v), 0.0)


def test_dequant_topk_empty_rows():
    v, i = ops.dequant_topk(np.zeros((0, 12), np.uint8),
                            np.zeros(0, np.float32), 4)
    assert v.shape == (0, 4) and i.shape == (0, 4)
    assert v.dtype == np.float32 and i.dtype == np.int32


def test_dequant_topk_rejects_bad_inputs():
    q, scales = _quant_rows(4, 10, np.uint8, 1)
    with pytest.raises(ValueError):
        ops.dequant_topk(q, scales, 11)       # k > C
    with pytest.raises(ValueError):
        ops.dequant_topk(q, scales, 0)
    with pytest.raises(ValueError):
        ops.dequant_topk(q.astype(np.float32), scales, 3)   # use topk
    with pytest.raises(ValueError):
        ops.dequant_topk(q, scales[:2], 3)    # scales shape mismatch


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 40), st.integers(2, 300), st.data())
def test_dequant_topk_property(M, C, data):
    k = data.draw(st.integers(1, C))
    dtype = data.draw(st.sampled_from([np.uint8, np.int8]))
    gs = data.draw(st.sampled_from([1.0, 1.0 / 255.0, 1.0 / 127.0]))
    q, scales = _quant_rows(M, C, dtype, M * 31 + C)
    v, i = ops.dequant_topk(q, scales, k, global_scale=gs)
    vr, ir = ref.dequant_topk_ref(q, scales, k, global_scale=gs)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(vr))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))


# ---------------------------------------------------------------------------
# TPU tiles in interpret mode: the 128-row blocks the chip compiles, on
# block multiples, ragged shapes and empty inputs — exact against ref.py
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,M", [(128, 128), (256, 4096), (300, 130),
                                 (5, 77)])
def test_centroid_assign_tpu_tiles_exact(B, M):
    r = np.random.default_rng(B * 7 + M)
    f = r.normal(size=(B, 128)).astype(np.float32)
    c = r.normal(size=(M, 128)).astype(np.float32)
    c[M // 2] = f[B - 1]                    # an exact hit in a later block
    d2, j, m = ops.centroid_assign(f, c, bb=128, bm=128, threshold=14.0)
    d2r, jr, mr = ref.centroid_assign_ref(f, c, threshold=14.0)
    np.testing.assert_array_equal(np.asarray(j), np.asarray(jr))
    np.testing.assert_array_equal(np.asarray(m), np.asarray(mr))
    np.testing.assert_allclose(np.asarray(d2), np.asarray(d2r), atol=1e-3)
    assert int(np.asarray(j)[B - 1]) == M // 2


def test_centroid_assign_empty_inputs():
    c = np.ones((5, 128), np.float32)
    d2, j, m = ops.centroid_assign(np.zeros((0, 128), np.float32), c,
                                   bb=128, bm=128, threshold=1.0)
    assert d2.shape == j.shape == m.shape == (0,)
    assert len(ops.centroid_assign(np.zeros((0, 128), np.float32), c)) == 2
    with pytest.raises(ValueError):
        ops.centroid_assign(c, np.zeros((0, 128), np.float32))


@pytest.mark.parametrize("B,C,k", [(128, 1000, 10), (512, 1000, 10),
                                   (300, 7, 7), (5, 1000, 4)])
def test_topk_tpu_tiles_exact(B, C, k):
    lg = np.array(jax.random.normal(jax.random.PRNGKey(B + C), (B, C)))
    lg[:, 1] = lg[:, C - 1]                 # ties resolve to the low index
    v, i = ops.topk(lg, k, bb=128)
    vr, ir = ref.topk_ref(lg, k)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(vr))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))


@pytest.mark.parametrize("M,C,k,dtype", [
    (2048, 1000, 10, np.uint8), (300, 7, 4, np.int8),
    (5, 1000, 60, np.uint8), (128, 9, 9, np.int8),
])
def test_dequant_topk_tpu_tiles_exact(M, C, k, dtype):
    q, scales = _quant_rows(M, C, dtype, M + C + k)
    v, i = ops.dequant_topk(q, scales, k, global_scale=1.0 / 255.0,
                            bm=128)
    vr, ir = ref.dequant_topk_ref(q, scales, k, global_scale=1.0 / 255.0)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(vr))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))


@pytest.mark.parametrize("Na,Nb", [(256, 512), (300, 130), (5, 7)])
def test_pixel_match_tpu_tiles_exact(Na, Nb):
    """32 px crops (D = 3072) against a gate ring, 128-row blocks."""
    r = np.random.default_rng(Na + Nb)
    a = r.random((Na, 3072)).astype(np.float32)
    b = r.random((Nb, 3072)).astype(np.float32)
    b[Nb - 1] = a[0]
    b[Nb // 2] = a[0]                       # tie: the lower index wins
    m, d = ops.pixel_match(a, b, 0.3, ba=128, bn=128)
    mr, dr = ref.pixel_match_ref(a, b, 0.3)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(mr))
    np.testing.assert_array_equal(np.asarray(d), np.asarray(dr))
    assert int(np.asarray(m)[0]) == Nb // 2


def test_tpu_tiles_empty_inputs():
    v, i = ops.topk(np.zeros((0, 1000), np.float32), 10, bb=128)
    assert v.shape == i.shape == (0, 10)
    v, i = ops.dequant_topk(np.zeros((0, 1000), np.uint8),
                            np.zeros(0, np.float32), 10, bm=128)
    assert v.shape == i.shape == (0, 10)
    m, d = ops.pixel_match(np.zeros((0, 3072), np.float32),
                           np.ones((4, 3072), np.float32), 0.1,
                           ba=128, bn=128)
    assert m.shape == d.shape == (0,)
    m, _ = ops.pixel_match(np.ones((4, 3072), np.float32),
                           np.zeros((0, 3072), np.float32), 0.1,
                           ba=128, bn=128)
    assert (np.asarray(m) == -1).all()
