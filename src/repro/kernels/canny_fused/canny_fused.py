"""Pallas TPU megakernel: the whole Canny gateway stage in one ``pallas_call``.

The gateway runs this on EVERY incoming frame, so the seed pipeline's shape —
one small Sobel kernel sandwiched between ~6 separate jnp stages, each a full
HBM round-trip of the frame — put a hard floor under per-frame latency.  This
kernel fuses gaussian blur -> Sobel -> direction-quantized NMS -> double
threshold -> fixed-iteration hysteresis into ONE launch: no intermediate
(blurred / magnitude / thinned) map ever round-trips to HBM — only the final
edge map is written back.

Tiling / halo scheme
--------------------
Grid = (batch, row_tiles, lane_tiles): each program owns a
``tile_rows x tile_lanes`` output tile.  It reads nine blocks of the frame:
the tile itself, a strip of ``hr`` rows above and below it, a strip of
``hl`` lanes left and right of it, and the four ``hr x hl`` corners.  The
strips are blocks of their own (index maps in units of ``hr``/``hl``, clamped
at the frame edges), so each program fetches its tile plus a thin halo, and
the pieces are stored at fixed offsets into a VMEM window of
``(tile_rows + 2*hr, tile_lanes + 2*hl)``.  On the TPU, ``hr = 16`` rows and
``hl = 128`` lanes, so every piece, store and slice is (8, 128)-aligned.
Interpret mode also takes tiles the TPU cannot (any tile >= HALO; the strip
is then the smallest divisor of the tile that is >= HALO).

The stencils shift the window with ``pltpu.roll``; what wraps round lands
within HALO of the window edge.  HALO = 12 rows/lanes per side is exactly
the receptive field of one output pixel in each dimension:

    2 (gaussian blur) + 1 (Sobel) + 1 (NMS) + 8 (hysteresis dilations) = 12

so every window pixel that influences an emitted pixel is computed from
real frame data as long as ``hr, hl >= HALO``.

Frame-boundary parity: the jnp oracle pads each stage differently (blur and
Sobel replicate their INPUT at the frame edge; NMS and hysteresis zero-pad),
and replicating the raw frame before blurring is NOT the same as replicating
the blurred frame before Sobel.  The kernel therefore re-applies the
per-stage semantics to the out-of-frame window pixels between stages — edge
rows/lanes re-replicated after blur, magnitudes zeroed outside the frame —
which makes the emitted pixels bit-identical to ``ref.canny_edge`` (tested
exactly, not to a tolerance, in tests/test_canny_fused.py).  The edge row
and lane are picked out with iota masks and a max-reduction, which the TPU
lowers (a dynamic slice it does not).  Both use the same arithmetic as
``ref.py``: Python-constant gaussian weights, squared magnitudes and
comparison-binned directions, so no transcendental enters either.

Ragged batches (pad-and-mask): the per-frame TRUE extent is carried by the
``dims`` scalar-prefetch operand (flattened [B, 2] int32 (height, width) per
frame, in SMEM), so a batch of mixed-resolution frames zero-padded to a
common bucket shape streams through ONE launch — every pixel at or beyond a
frame's true extent is out-of-frame for that frame (replicated for
blur/Sobel, zeroed for NMS/hysteresis) and the emitted map is False there,
so callers just crop.  When ``dims`` is omitted every frame spans the full
array.  Frames whose size is not a tile multiple are zero-padded to one
before the launch and cropped after it.

VMEM budget model (``pick_tiles``): ``tile_bytes`` counts ``_WINDOW_BUFFERS``
f32 window-sized live values plus the double-buffered input and output
blocks; the v5e compiler's own count has stayed below it at every tile
measured (12.6 MiB against a modeled 20.2 MiB at 128x2048).  When tile
sizes are not given, ``pick_tiles`` takes, of the aligned tiles whose model
fits ``VMEM_BUDGET_BYTES``, the one that computes the fewest window pixels
(1080p: 128x1920, 4K: 128x1920).  The kernel asks the compiler for
``VMEM_LIMIT_BYTES``; ``tests/test_tpu_compile.py`` compiles the picked
tiles at 96x96, 1080p and 4K for a v5e to keep the model honest.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import (BLUR_RADIUS, GAUSS_WEIGHTS, HYSTERESIS_ITERS,
                  direction_bins)

#: rows/lanes of neighbour context one output pixel depends on per side
#: (see module docstring)
HALO = BLUR_RADIUS + 1 + 1 + HYSTERESIS_ITERS

#: TPU tile granularities: halo strips of 16 rows (two f32 sublane tiles,
#: the first multiple of 8 >= HALO) and 128 lanes
ROW_STRIP = 16
LANE = 128

#: widest auto-picked lane tile
MAX_TILE_LANES = 16 * LANE

#: f32 window-sized values the model counts at the working-set peak (an
#: upper bound on the v5e compiler's own count; see the module docstring)
_WINDOW_BUFFERS = 12

#: working set pick_tiles fits the auto tiles into, and the scoped VMEM the
#: kernel asks for (v5e has 128 MiB per core)
VMEM_BUDGET_BYTES = 24 * 2 ** 20
VMEM_LIMIT_BYTES = 32 * 2 ** 20


def _round_up(x: int, q: int) -> int:
    return -(-x // q) * q


def halo_strips(tile_rows: int, tile_lanes: int) -> tuple[int, int]:
    """(hr, hl): halo strip height and width for a tile.  The TPU sizes
    (16, 128) when the tile is a multiple of them, else the smallest divisor
    of the tile that covers HALO (interpret mode only)."""
    def strip(tile, aligned):
        if tile % aligned == 0:
            return aligned
        return min((d for d in range(HALO, tile + 1) if tile % d == 0),
                   default=tile)
    return strip(tile_rows, ROW_STRIP), strip(tile_lanes, LANE)


def tile_bytes(tile_rows: int, tile_lanes: int) -> int:
    """Modeled VMEM working set of one program at these tile sizes."""
    hr, hl = halo_strips(tile_rows, tile_lanes)
    rows, cols = tile_rows + 2 * hr, tile_lanes + 2 * hl
    window = _WINDOW_BUFFERS * 4 * rows * cols
    blocks = 2 * 4 * rows * cols   # nine input pieces, double-buffered
    out = 2 * tile_rows * tile_lanes
    return window + blocks + out


def pick_tiles(h: int, w: int, *, tile_rows: int | None = None,
               tile_lanes: int | None = None,
               vmem_budget_bytes: int = VMEM_BUDGET_BYTES
               ) -> tuple[int, int]:
    """(tile_rows, tile_lanes) for an [h, w] frame: of the (16, 128)-aligned
    tiles up to (128, ``MAX_TILE_LANES``) whose modeled working set fits the
    budget, the one that computes the fewest window pixels over the frame
    (tiles times window area; ties go to the larger tile).  Explicit values
    are honored as-is, and a missing dimension is picked around the fixed
    one."""
    if tile_rows is not None and tile_lanes is not None:
        return tile_rows, tile_lanes
    rows = ([tile_rows] if tile_rows is not None else
            range(ROW_STRIP, 8 * ROW_STRIP + 1, ROW_STRIP))
    lanes = ([tile_lanes] if tile_lanes is not None else
             range(LANE, MAX_TILE_LANES + 1, LANE))

    def cost(t):
        tr, tl = t
        hr, hl = halo_strips(tr, tl)
        return (-(-h // tr) * (tr + 2 * hr) * -(-w // tl) * (tl + 2 * hl),
                -tr * tl)

    fits = [(tr, tl) for tr in rows for tl in lanes
            if tile_bytes(tr, tl) <= vmem_budget_bytes]
    return min(fits or [(min(rows), min(lanes))], key=cost)


def _check_tiles(tile_r: int, tile_l: int, interpret: bool) -> None:
    """Fail fast on tiles the kernel cannot take (never round them)."""
    for name, t in (("tile_rows", tile_r), ("tile_lanes", tile_l)):
        if t < HALO:
            raise ValueError(
                f"{name}={t} < HALO={HALO}: the halo must fit inside one "
                f"neighbouring block")
    if interpret:
        return
    for name, t, q in (("tile_rows", tile_r, ROW_STRIP),
                       ("tile_lanes", tile_l, LANE)):
        if t % q:
            raise ValueError(
                f"{name}={t} is not a multiple of {q}: the TPU kernel needs "
                f"{ROW_STRIP}-row, {LANE}-lane tiles (interpret mode takes "
                f"any tile >= HALO)")


def _shift(a, d: int, axis: int):
    """``out[..., x, ...] = a[..., x + d, ...]``, wrapping at the edges."""
    return pltpu.roll(a, (-d) % a.shape[axis], axis)


def _canny_kernel(dims_ref, c_ref, t_ref, b_ref, l_ref, r_ref,
                  tl_ref, tr_ref, bl_ref, br_ref, out_ref, win_ref, *,
                  tile_r: int, tile_l: int, hr: int, hl: int,
                  lo: float, hi: float):
    bi = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    h = dims_ref[2 * bi]       # this frame's TRUE extent (<= the array)
    w = dims_ref[2 * bi + 1]

    # assemble the window from the nine pieces at static offsets
    r1, c1 = hr + tile_r, hl + tile_l
    win_ref[:hr, :hl] = tl_ref[0]
    win_ref[:hr, hl:c1] = t_ref[0]
    win_ref[:hr, c1:] = tr_ref[0]
    win_ref[hr:r1, :hl] = l_ref[0]
    win_ref[hr:r1, hl:c1] = c_ref[0]
    win_ref[hr:r1, c1:] = r_ref[0]
    win_ref[r1:, :hl] = bl_ref[0]
    win_ref[r1:, hl:c1] = b_ref[0]
    win_ref[r1:, c1:] = br_ref[0]
    win = win_ref[...]
    rows, cols = win.shape

    # global frame row/lane of every window pixel; positions outside
    # [0, h) x [0, w) only occur in frame-edge tiles, tile padding past the
    # frame, or a ragged frame's pad region
    gr = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) + (i * tile_r - hr)
    gc = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1) + (j * tile_l - hl)
    oob_top, oob_bot = gr < 0, gr > h - 1
    oob_left, oob_right = gc < 0, gc > w - 1
    oob = (oob_top | oob_bot) | (oob_left | oob_right)

    def pick(a, at, axis):
        """The frame row (axis 0) or lane (axis 1) where ``at`` holds,
        broadcast along ``axis`` (-inf where the window does not hold it,
        which only happens when nothing in the window is in the frame)."""
        return jnp.max(jnp.where(at, a, -jnp.inf), axis=axis, keepdims=True)

    def replicate_frame_edges(a):
        top, bot = pick(a, gr == 0, 0), pick(a, gr == h - 1, 0)
        a = jnp.where(oob_bot, bot, jnp.where(oob_top, top, a))
        left, right = pick(a, gc == 0, 1), pick(a, gc == w - 1, 1)
        return jnp.where(oob_right, right, jnp.where(oob_left, left, a))

    # ---- gaussian blur (oracle pads the INPUT with edge replication); the
    # sums run in the oracle's order so the rounding matches
    r, k = BLUR_RADIUS, GAUSS_WEIGHTS
    win = replicate_frame_edges(win)
    blur_h = sum(_shift(win, t - r, 1) * k[t] for t in range(2 * r + 1))
    sm = sum(_shift(blur_h, t - r, 0) * k[t] for t in range(2 * r + 1))

    # ---- Sobel (oracle pads the BLURRED map with edge replication)
    sm = replicate_frame_edges(sm)
    up, down = _shift(sm, -1, 0), _shift(sm, 1, 0)
    tl, tc, tr = _shift(up, -1, 1), up, _shift(up, 1, 1)
    ml, mr = _shift(sm, -1, 1), _shift(sm, 1, 1)
    bl, bc, br = _shift(down, -1, 1), down, _shift(down, 1, 1)
    gx = (tr + 2 * mr + br) - (tl + 2 * ml + bl)
    gy = (bl + 2 * bc + br) - (tl + 2 * tc + tr)
    q = direction_bins(gx, gy)

    # ---- NMS on the squared magnitude (oracle zero-pads at the border)
    mag2 = jnp.where(oob, 0.0, gx * gx + gy * gy)
    m_up, m_down = _shift(mag2, -1, 0), _shift(mag2, 1, 0)
    neigh = [
        (_shift(mag2, 1, 1), _shift(mag2, -1, 1)),           # 0: E/W
        (_shift(m_down, 1, 1), _shift(m_up, -1, 1)),         # 1: SE/NW
        (m_down, m_up),                                      # 2: S/N
        (_shift(m_down, -1, 1), _shift(m_up, 1, 1)),         # 3: SW/NE
    ]
    keep = jnp.zeros(mag2.shape, jnp.bool_)
    for d, (a, b2) in enumerate(neigh):
        keep = keep | ((q == d) & (mag2 >= a) & (mag2 >= b2))
    thin2 = jnp.where(keep, mag2, 0.0)

    # ---- double threshold + hysteresis (zero-padded at the frame border:
    # out-of-frame pixels must stay off so growth matches the oracle); the
    # 3x3 dilation is separable, as 0/1 f32 maps
    strong = jnp.where((thin2 > hi * hi) & ~oob, 1.0, 0.0)
    weak = (thin2 > lo * lo) & ~oob
    for _ in range(HYSTERESIS_ITERS):
        d1 = jnp.maximum(strong, jnp.maximum(_shift(strong, -1, 1),
                                             _shift(strong, 1, 1)))
        dil = jnp.maximum(d1, jnp.maximum(_shift(d1, -1, 0),
                                          _shift(d1, 1, 0)))
        strong = jnp.where(weak, dil, 0.0)

    out_ref[0] = strong[hr:r1, hl:c1].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("lo", "hi", "tile_rows",
                                             "tile_lanes", "interpret"))
def canny_edge_pallas(img, dims=None, *, lo: float = 0.6, hi: float = 1.0,
                      tile_rows: int | None = None,
                      tile_lanes: int | None = None,
                      interpret: bool = False):
    """img [B,H,W] f32 -> edge map [B,H,W] bool, one fused pallas_call.

    ``tile_rows``/``tile_lanes`` pick the 2D tile (default: the VMEM budget
    model, ``pick_tiles``); any frame size works — heights AND widths that
    are odd, non-square, or non-multiples of the tile are padded up to the
    tile and cropped back.  Outside interpret mode an explicit tile must be
    a multiple of (16, 128), or this raises.  ``dims`` ([B, 2] int32
    (height, width) per frame, default whole-array) is the pad-and-mask
    plane for ragged batches: pixels at or beyond a frame's true extent come
    back False.
    """
    b, h, w = img.shape
    tile_r, tile_l = pick_tiles(h, w, tile_rows=tile_rows,
                                tile_lanes=tile_lanes)
    _check_tiles(tile_r, tile_l, interpret)
    hr, hl = halo_strips(tile_r, tile_l)
    if dims is None:
        dims = jnp.broadcast_to(jnp.asarray([h, w], jnp.int32), (b, 2))
    nr, nl = pl.cdiv(h, tile_r), pl.cdiv(w, tile_l)
    hp, wp = nr * tile_r, nl * tile_l
    if (hp, wp) != (h, w):
        img = jnp.pad(img, ((0, 0), (0, hp - h), (0, wp - w)))
    # tile index -> strip index: kr (kl) strips per tile; clamped strips
    # past the frame are out-of-frame pixels, masked in the kernel
    kr, kl = tile_r // hr, tile_l // hl
    last_r, last_l = hp // hr - 1, wp // hl - 1

    def above(i):
        return jnp.maximum(i * kr - 1, 0)

    def below(i):
        return jnp.minimum((i + 1) * kr, last_r)

    def left(j):
        return jnp.maximum(j * kl - 1, 0)

    def right(j):
        return jnp.minimum((j + 1) * kl, last_l)

    def spec(shape, f):
        return pl.BlockSpec((1,) + shape, lambda bi, i, j, _: (bi,) + f(i, j))

    in_specs = [
        spec((tile_r, tile_l), lambda i, j: (i, j)),
        spec((hr, tile_l), lambda i, j: (above(i), j)),
        spec((hr, tile_l), lambda i, j: (below(i), j)),
        spec((tile_r, hl), lambda i, j: (i, left(j))),
        spec((tile_r, hl), lambda i, j: (i, right(j))),
        spec((hr, hl), lambda i, j: (above(i), left(j))),
        spec((hr, hl), lambda i, j: (above(i), right(j))),
        spec((hr, hl), lambda i, j: (below(i), left(j))),
        spec((hr, hl), lambda i, j: (below(i), right(j))),
    ]
    kernel = functools.partial(_canny_kernel, tile_r=tile_r, tile_l=tile_l,
                               hr=hr, hl=hl, lo=lo, hi=hi)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nr, nl),
            in_specs=in_specs,
            out_specs=spec((tile_r, tile_l), lambda i, j: (i, j)),
            scratch_shapes=[pltpu.VMEM((tile_r + 2 * hr, tile_l + 2 * hl),
                                       jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, hp, wp), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(dims.astype(jnp.int32).reshape(-1), *([img] * 9))
    return out[:, :h, :w] != 0
