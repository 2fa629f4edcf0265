"""jnp parity oracle for the fused Canny gateway kernel.

``canny_edge`` is the SINGLE semantic definition of the gateway's edge-map
stage: gaussian blur -> Sobel gradients -> direction-quantized non-maximum
suppression -> double threshold -> fixed-iteration hysteresis.  The Pallas
megakernel (canny_fused.py) must reproduce it bit-for-bit; the detection
pipeline (detection/canny.py) routes through ops.canny_edge which picks the
oracle on CPU and the kernel on TPU.

The stages use only arithmetic that the kernel can repeat exactly on a TPU:
adds, multiplies, compares and selects.  There are no transcendentals, since
XLA and the Pallas TPU compiler each bring their own ``exp``/``sqrt`` (and
Pallas TPU has no ``arctan2`` at all):

* the gaussian weights are Python floats (``GAUSS_WEIGHTS``), computed once
  in float64;
* the gradient magnitude stays squared: NMS compares squared magnitudes, and
  the thresholds are squared (``lo**2``, ``hi**2``);
* the gradient direction is binned by comparing ``|gy|`` against
  ``tan(pi/8) * |gx|`` (``direction_bins``), not by rounding ``arctan2``.

``canny_edge_staged`` runs the SAME stages as separate jit calls with a
device sync between each — the "unfused" baseline benchmarks/run.py times
against the fused paths.  Stage-per-dispatch is how the seed pipeline
behaved from the scheduler's point of view: every stage a full HBM
round-trip of the frame.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HYSTERESIS_ITERS = 8

#: radius of the separable gaussian blur (5 taps)
BLUR_RADIUS = 2


def _gauss_weights(sigma: float = 1.0, radius: int = BLUR_RADIUS):
    k = [math.exp(-0.5 * (x / sigma) ** 2) for x in range(-radius, radius + 1)]
    return tuple(v / sum(k) for v in k)


#: normalized 5-tap gaussian, sigma = 1 (shared with the kernel)
GAUSS_WEIGHTS = _gauss_weights()

#: direction bin edge: |gy| <= tan(pi/8)|gx| is horizontal, and so on
TAN_PI_8 = math.tan(math.pi / 8)


def direction_bins(gx, gy):
    """Gradient direction quantized to 4 bins (0=E/W, 1=SE/NW, 2=S/N,
    3=SW/NE), by comparisons only; elementwise, so the kernel calls it too.
    A zero gradient is bin 0."""
    ax, ay = jnp.abs(gx), jnp.abs(gy)
    diag = jnp.where((gx > 0) ^ (gy > 0), 3, 1)
    return jnp.where(ay <= TAN_PI_8 * ax, 0,
                     jnp.where(ax <= TAN_PI_8 * ay, 2, diag)).astype(jnp.int32)


def gaussian_blur(img):
    """Separable 5-tap gaussian, batch [B,H,W] (horizontal then vertical)."""
    r, k = BLUR_RADIUS, GAUSS_WEIGHTS
    pad = jnp.pad(img, ((0, 0), (0, 0), (r, r)), mode="edge")
    h = sum(pad[:, :, i:i + img.shape[2]] * k[i] for i in range(2 * r + 1))
    padv = jnp.pad(h, ((0, 0), (r, r), (0, 0)), mode="edge")
    return sum(padv[:, i:i + img.shape[1], :] * k[i]
               for i in range(2 * r + 1))


def gradients(img):
    """img [B,H,W] -> (squared Sobel magnitude, direction bin) with the
    input replicated at the frame edge."""
    x = jnp.pad(img, ((0, 0), (1, 1), (1, 1)), mode="edge")
    tl = x[:, :-2, :-2]; tc = x[:, :-2, 1:-1]; tr = x[:, :-2, 2:]
    ml = x[:, 1:-1, :-2];                       mr = x[:, 1:-1, 2:]
    bl = x[:, 2:, :-2];  bc = x[:, 2:, 1:-1];  br = x[:, 2:, 2:]
    gx = (tr + 2 * mr + br) - (tl + 2 * ml + bl)
    gy = (bl + 2 * bc + br) - (tl + 2 * tc + tr)
    return gx * gx + gy * gy, direction_bins(gx, gy)


def nms(mag2, q):
    """Thin edges: keep pixels that are maxima along their quantized
    gradient direction (zero-padded neighbours at the frame border)."""
    h, w = mag2.shape[1], mag2.shape[2]
    p = jnp.pad(mag2, ((0, 0), (1, 1), (1, 1)))
    c = p[:, 1:h + 1, 1:w + 1]
    neigh = [
        (p[:, 1:h + 1, 2:], p[:, 1:h + 1, :w]),        # 0: E/W
        (p[:, 2:, 2:], p[:, :h, :w]),                  # 1: SE/NW
        (p[:, 2:, 1:w + 1], p[:, :h, 1:w + 1]),        # 2: S/N
        (p[:, 2:, :w], p[:, :h, 2:]),                  # 3: SW/NE
    ]
    keep = jnp.zeros_like(c, bool)
    for d, (a, b2) in enumerate(neigh):
        m = (q == d) & (c >= a) & (c >= b2)
        keep = keep | m
    return jnp.where(keep, mag2, 0.0)


def hysteresis(thin2, lo: float, hi: float):
    """Double threshold on the squared magnitude, then grow strong edges
    into weak ones for a fixed number of dilation rounds (zero-padded at the
    frame border)."""
    h, w = thin2.shape[1], thin2.shape[2]
    strong = thin2 > hi * hi
    weak = thin2 > lo * lo

    def grow(s, _):
        sp = jnp.pad(s, ((0, 0), (1, 1), (1, 1)))
        dil = (sp[:, :h, 1:w + 1] | sp[:, 2:, 1:w + 1] | sp[:, 1:h + 1, :w]
               | sp[:, 1:h + 1, 2:] | sp[:, :h, :w] | sp[:, :h, 2:]
               | sp[:, 2:, :w] | sp[:, 2:, 2:] | s)
        return dil & weak, None

    strong, _ = jax.lax.scan(grow, strong, None, length=HYSTERESIS_ITERS)
    return strong


@functools.partial(jax.jit, static_argnames=("lo", "hi"))
def canny_edge(img, lo: float = 0.6, hi: float = 1.0):
    """img [B,H,W] f32 -> edge map [B,H,W] bool (one fused XLA program)."""
    mag2, q = gradients(gaussian_blur(img))
    return hysteresis(nms(mag2, q), lo, hi)


# ------------------------------------------------- unfused benchmark baseline

_blur_jit = jax.jit(gaussian_blur)
_grad_jit = jax.jit(gradients)
_nms_jit = jax.jit(nms)
_hyst_jit = jax.jit(hysteresis, static_argnames=("lo", "hi"))


def canny_edge_staged(img, lo: float = 0.6, hi: float = 1.0):
    """Stage-per-dispatch Canny: same maths as ``canny_edge`` but each stage
    is its own jit call with a sync in between (the per-stage-HBM-round-trip
    cost model the fused paths eliminate).  Benchmark baseline only."""
    sm = jax.block_until_ready(_blur_jit(img))
    mag2, q = _grad_jit(sm)
    jax.block_until_ready(mag2)
    thin2 = jax.block_until_ready(_nms_jit(mag2, q))
    return jax.block_until_ready(_hyst_jit(thin2, lo, hi))
