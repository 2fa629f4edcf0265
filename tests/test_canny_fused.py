"""Fused Canny megakernel: bit-exact parity with the jnp oracle.

All Pallas runs use interpret mode (CPU) — marked ``pallas`` so a TPU CI
lane can select them; they stay in tier-1 (fast, not ``slow``).  What the
TPU compiler accepts is checked in tests/test_tpu_compile.py.  The 2D
lane-tiled grid means there is no width limit any more: the cases below
cover lane tiling, the column halo, frames narrower than one lane tile,
widths straddling the tile boundary, a >4096-wide frame (the old
``MAX_WIDTH`` fallback territory), and the ragged pad-and-mask batch path.
"""
import numpy as np
import jax.numpy as jnp
import pytest
from _propcheck import given, settings, st

from repro.kernels.canny_fused import ref
from repro.kernels.canny_fused.canny_fused import (
    HALO, VMEM_BUDGET_BYTES, canny_edge_pallas, pick_tiles, tile_bytes)
from repro.kernels.canny_fused.ops import (bucket_shape, canny_edge,
                                           canny_edge_batch)

pytestmark = pytest.mark.pallas


def _rand(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).random(shape, np.float32))


@pytest.mark.parametrize("shape,tiles", [
    ((1, 32, 32), {}),                  # single program, whole frame
    ((3, 64, 64), {}),                  # batch, whole frame (the scene size)
    ((1, 96, 64), dict(tile_rows=32)),  # row-tiled: 3 even row tiles
    ((2, 40, 56), dict(tile_rows=16)),  # ragged last row tile
    ((1, 37, 41), dict(tile_rows=13)),  # odd, non-square
    ((1, 64, 200), dict(tile_rows=32, tile_lanes=64)),   # 2x4 lane grid
    ((2, 80, 600), dict(tile_rows=32, tile_lanes=256)),  # 3x3, ragged both
    ((1, 48, 31), dict(tile_lanes=64)),  # frame NARROWER than one lane tile
    ((1, 48, 65), dict(tile_lanes=64)),  # width = tile_lanes + 1
    ((1, 48, 63), dict(tile_lanes=64)),  # width = tile_lanes - 1
    ((1, 48, 64), dict(tile_lanes=64)),  # width exactly tile_lanes
])
def test_fused_bit_identical_to_oracle(shape, tiles):
    img = _rand(shape, seed=sum(shape))
    got = np.asarray(canny_edge_pallas(img, interpret=True, **tiles))
    want = np.asarray(ref.canny_edge(img))
    np.testing.assert_array_equal(got, want)


def test_frame_wider_than_old_limit_is_served():
    """w > 4096 used to raise in the row-tiled kernel and silently fall
    back to the staged oracle under impl='auto'; the 2D grid serves it."""
    img = _rand((1, 24, 4224), seed=11)
    got = np.asarray(canny_edge_pallas(img, tile_rows=24, tile_lanes=1024,
                                       interpret=True))
    np.testing.assert_array_equal(got, np.asarray(ref.canny_edge(img)))
    # and the dispatch wrapper has no width-based impl rewrite left
    got = np.asarray(canny_edge(img, impl="interpret", tile_rows=24,
                                tile_lanes=1024))
    np.testing.assert_array_equal(got, np.asarray(ref.canny_edge(img)))


def test_4k_frame_bit_identical():
    """The acceptance shape: one 2160x3840 frame, no width guard."""
    img = _rand((1, 2160, 3840), seed=4)
    got = np.asarray(canny_edge_pallas(img, tile_rows=1088, tile_lanes=1984,
                                       interpret=True))
    np.testing.assert_array_equal(got, np.asarray(ref.canny_edge(img)))


def test_1080p_frame_bit_identical_at_picked_tiles():
    """The tiles pick_tiles gives 1080p (one 1920-lane tile, 128 rows, a
    ragged last row tile) match the oracle exactly."""
    assert pick_tiles(1080, 1920) == (128, 1920)
    img = _rand((1, 1080, 1920), seed=8)
    got = np.asarray(canny_edge_pallas(img, interpret=True))
    np.testing.assert_array_equal(got, np.asarray(ref.canny_edge(img)))


def test_fused_thresholds_forwarded():
    img = _rand((1, 48, 48), seed=7)
    got = np.asarray(canny_edge_pallas(img, lo=0.2, hi=0.5, tile_rows=16,
                                       interpret=True))
    want = np.asarray(ref.canny_edge(img, lo=0.2, hi=0.5))
    np.testing.assert_array_equal(got, want)
    # different thresholds must actually change the map (guard against the
    # kernel silently ignoring lo/hi)
    assert got.any()
    assert not np.array_equal(got, np.asarray(ref.canny_edge(img)))


def test_tile_smaller_than_halo_is_an_error():
    with pytest.raises(ValueError, match="HALO"):
        canny_edge_pallas(_rand((1, 32, 32)), tile_rows=HALO - 1,
                          interpret=True)
    with pytest.raises(ValueError, match="HALO"):
        canny_edge_pallas(_rand((1, 32, 32)), tile_lanes=HALO - 1,
                          interpret=True)


def test_tile_the_tpu_cannot_take_fails_fast():
    """Outside interpret mode a tile off the (16, 128) grid raises instead
    of being rounded; interpret mode takes it."""
    img = _rand((1, 48, 64))
    with pytest.raises(ValueError, match="multiple of 128"):
        canny_edge_pallas(img, tile_lanes=64)
    with pytest.raises(ValueError, match="multiple of 16"):
        canny_edge_pallas(img, tile_rows=24, tile_lanes=128)
    canny_edge_pallas(img, tile_lanes=64, interpret=True)


def test_pick_tiles_respects_vmem_budget():
    """Auto-picked tiles fit the VMEM working-set model at every size the
    bench exercises, and never shrink below the halo."""
    for h, w in ((64, 64), (1080, 1920), (1440, 2560), (2160, 3840),
                 (4320, 7680), (17, 9)):
        tr, tl = pick_tiles(h, w)
        assert tr >= HALO and tl >= HALO
        assert tile_bytes(tr, tl) <= VMEM_BUDGET_BYTES
    # explicit tiles are honored untouched
    assert pick_tiles(256, 256, tile_rows=40, tile_lanes=72) == (40, 72)


def test_ragged_batch_parity_and_masking():
    """canny_edge_batch pads mixed frame sizes into buckets, serves each
    with ONE launch, and crops — every frame must match its solo oracle
    run exactly (the pad-and-mask plane leaks nothing across frames)."""
    rng = np.random.default_rng(9)
    shapes = [(37, 41), (64, 64), (40, 200), (64, 64)]
    frames = [rng.random(s, np.float32) for s in shapes]
    for impl in ("xla", "interpret"):
        maps = canny_edge_batch(frames, impl=impl)
        assert [m.shape for m in maps] == shapes
        for m, f in zip(maps, frames):
            want = np.asarray(ref.canny_edge(jnp.asarray(f)[None]))[0]
            np.testing.assert_array_equal(m, want)


def test_padded_region_output_is_false():
    """Out-of-frame output from the masked kernel is guaranteed False —
    the host crop merely drops it, it never hides garbage."""
    f = np.random.default_rng(10).random((37, 41), np.float32)
    dims = jnp.asarray([[37, 41]], jnp.int32)
    padded = np.zeros((1, 64, 128), np.float32)
    padded[0, :37, :41] = f
    out = np.asarray(canny_edge_pallas(jnp.asarray(padded), dims,
                                       tile_rows=16, tile_lanes=64,
                                       interpret=True))
    assert not out[0, 37:, :].any() and not out[0, :, 41:].any()


def test_bucket_shape_granularity():
    assert bucket_shape(1, 1) == (64, 128)
    assert bucket_shape(64, 128) == (64, 128)
    assert bucket_shape(65, 129) == (128, 256)
    assert bucket_shape(1080, 1920) == (1088, 1920)


def test_ops_dispatch():
    img = _rand((2, 32, 32), seed=3)
    want = np.asarray(ref.canny_edge(img))
    np.testing.assert_array_equal(
        np.asarray(canny_edge(img, impl="xla")), want)
    np.testing.assert_array_equal(
        np.asarray(canny_edge(img, impl="interpret")), want)


def test_staged_baseline_matches_fused_oracle():
    img = _rand((2, 48, 40), seed=5)
    np.testing.assert_array_equal(np.asarray(ref.canny_edge_staged(img)),
                                  np.asarray(ref.canny_edge(img)))


@settings(max_examples=8, deadline=None)
@given(h=st.integers(16, 70), w=st.integers(8, 70),
       tile=st.integers(HALO, 48), seed=st.integers(0, 10_000))
def test_fused_parity_property(h, w, tile, seed):
    """Any frame size (odd / non-square / non-tile-multiple) and any legal
    tile height produce bit-identical edge maps in interpret mode."""
    img = _rand((1, h, w), seed=seed)
    got = np.asarray(canny_edge_pallas(img, tile_rows=tile, interpret=True))
    np.testing.assert_array_equal(got, np.asarray(ref.canny_edge(img)))


@settings(max_examples=8, deadline=None)
@given(h=st.integers(13, 60), w=st.integers(8, 300),
       tile_r=st.integers(HALO, 32), tile_l=st.integers(HALO, 128),
       seed=st.integers(0, 10_000))
def test_fused_parity_property_2d(h, w, tile_r, tile_l, seed):
    """The 2D property: any (frame, tile) geometry — lane tiles narrower
    or wider than the frame, ragged in both dims — stays bit-identical."""
    img = _rand((1, h, w), seed=seed)
    got = np.asarray(canny_edge_pallas(img, tile_rows=tile_r,
                                       tile_lanes=tile_l, interpret=True))
    np.testing.assert_array_equal(got, np.asarray(ref.canny_edge(img)))
