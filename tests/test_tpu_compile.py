"""Compile the served path's TPU programs for a described TPU v5e chip.

Nothing runs: the TPU compiler, installed with JAX, compiles for a v5e that
is described, not attached, and refuses what the chip would refuse (tiles
that break the (8, 128) layout, ops with no Mosaic lowering, too much VMEM,
a program that does not fit HBM).  Interpret-mode parity
(tests/test_canny_fused.py) cannot see any of that.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and the test workers
must all collect the same tests.  Where it cannot be described the tests
skip.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.canny_fused.canny_fused import (
    VMEM_BUDGET_BYTES, canny_edge_pallas, pick_tiles, tile_bytes)
from repro.models import init_params
from repro.serving.engine import step_programs

#: v5e HBM per chip, rounded down
HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def one_chip():
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
    # a described chip's compiles cannot be read back from the persistent
    # cache, so keep them out of it
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)
    mp.undo()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("b,h,w", [(8, 96, 96), (4, 1088, 1920),
                                   (1, 2160, 3840)])
def test_canny_megakernel_compiles_at_picked_tiles(one_chip, b, h, w):
    """The picked tiles fit the VMEM model's budget and the compiler takes
    them, ragged ``dims`` included."""
    assert tile_bytes(*pick_tiles(h, w)) <= VMEM_BUDGET_BYTES
    img = jax.ShapeDtypeStruct((b, h, w), jnp.float32, sharding=one_chip)
    dims = jax.ShapeDtypeStruct((b, 2), jnp.int32, sharding=one_chip)
    compiled = canny_edge_pallas.lower(img, dims).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _fits_hbm(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 1e9:.2f} GB > {HBM_BYTES / 1e9} GB"
    return m


@pytest.fixture(scope="module")
def qwen(one_chip):
    """qwen2.5-3b at its published width and depth in bf16, as shapes."""
    cfg = dataclasses.replace(get_config("qwen2.5-3b"),
                              param_dtype="bfloat16")
    params = _on(one_chip, jax.eval_shape(
        lambda k: init_params(cfg, k), jax.random.PRNGKey(0)))
    return cfg, params


def _entry_copies_of(hlo_text, params):
    """The copies (``copy``, ``copy-start``) in the entry computation
    whose operand is one of the entry parameters numbered ``params``."""
    entry = hlo_text[hlo_text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    names = {m[1] for m in re.finditer(r"%(\S+) = \S+ parameter\((\d+)\)",
                                       entry) if int(m[2]) in params}
    return [m[0] for m in re.finditer(r"%\S+ = .*? copy(?:-start)?\(%([^,)]+)\)",
                                      entry) if m[1] in names]


def test_qwen_prefill_and_decode_compile_and_fit_one_chip(one_chip, qwen):
    """The serving steps chip_smoke.py runs (batch 4, 512-token prompts,
    a 1024-token cache) compile for one v5e and fit its HBM, and the
    decode step writes into the cache it is given: the whole cache is
    aliased to the output, and no copy of it is made on entry."""
    cfg, params = qwen
    b, s, max_seq = 4, 512, 1024
    step, decode = step_programs(cfg, max_seq)
    tokens = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one_chip)
    m = _fits_hbm(step.lower(params, tokens, None).compile())
    assert m.argument_size_in_bytes > 6e9     # all 36 layers, in bf16
    _, cache = jax.eval_shape(step, params, tokens, None)
    token = jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=one_chip)
    compiled = decode.lower(params, token, _on(one_chip, cache)).compile()
    m = _fits_hbm(compiled)
    cache_leaves = jax.tree.leaves(cache)
    assert m.alias_size_in_bytes >= sum(x.size * x.dtype.itemsize
                                        for x in cache_leaves)
    # entry parameters: the weights, the token, then the cache's leaves;
    # the scalar position may still be copied into scalar memory
    first = len(jax.tree.leaves(params)) + 1
    arrays = {first + i for i, x in enumerate(cache_leaves) if x.ndim}
    assert len(arrays) == 3                   # k, v, pos_buf
    assert _entry_copies_of(compiled.as_text(), arrays) == []
