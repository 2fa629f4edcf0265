"""Serving engine + TPU pool routing tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.profiles import ProfileEntry, ProfileTable
from repro.data.tokens import modality_inputs
from repro.models import decode_step
from repro.serving.engine import Backend, Request
from repro.serving.pool import (LENGTH_BUCKETS, ServingPool, bucket_of,
                                capability_score)


def test_bucket_of():
    assert bucket_of(10) == 0
    assert bucket_of(513) == 1
    assert bucket_of(8193) == 3
    assert bucket_of(600_000) == 4


def test_capability_saturation():
    small = capability_score(3_000_000_000, False, 0)
    big = capability_score(34_000_000_000, False, 0)
    assert big - small < 5.0  # short prompts: capacity saturates
    small4 = capability_score(3_000_000_000, True, 4)
    big4 = capability_score(34_000_000_000, True, 4)
    assert big4 - small4 > 10.0  # long prompts discriminate
    # full-attention pays a long-context quality penalty
    assert capability_score(10**10, True, 4) > capability_score(10**10, False, 4)


def test_pool_routing_prefers_cheap_for_short():
    entries = []
    for arch, score_base, energy in (("small", 70.0, 1.0), ("big", 90.0, 5.0)):
        for _, _, b in LENGTH_BUCKETS:
            cap = {0: 72.0, 1: 78.0, 2: 84.0, 3: 92.0, 4: 99.0}[b]
            entries.append(ProfileEntry(arch, "pod", b,
                                        min(score_base, cap), 1.0, energy))
    pool = ServingPool(ProfileTable(entries), delta=5.0)
    assert pool.route(100).arch == "small"   # bucket 0: both ~70/72 -> cheap
    assert pool.route(40_000).arch == "big"  # bucket 4: 90 vs 70 -> big only


def test_backend_serve_batch():
    cfg = get_config("qwen2.5-3b").reduced()
    be = Backend("qwen", cfg, max_seq=64)
    reqs = [Request(uid=i, prompt=np.arange(5 + i), max_new_tokens=3)
            for i in range(2)]
    results = be.serve_batch(reqs)
    assert len(results) == 2
    for r in results:
        assert r.tokens.shape == (3,)
        assert r.prefill_s > 0 and r.decode_s >= 0
        assert (r.tokens >= 0).all() and (r.tokens < cfg.vocab_size).all()


def test_backend_stateful_families():
    cfg = get_config("mamba2-370m").reduced()
    be = Backend("mamba", cfg, max_seq=64)
    res = be.serve_batch([Request(uid=0, prompt=np.arange(7),
                                  max_new_tokens=4)])[0]
    assert res.tokens.shape == (4,)


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "mamba2-370m",
                                  "recurrentgemma-2b", "whisper-small"])
def test_decode_step_consumes_its_cache(arch):
    """The served decode step takes its cache as a donated buffer, for
    the dense, SSM, hybrid and encoder-decoder families: the cache passed
    in is deleted, and ``serve_batch``'s greedy tokens are those of the
    same step jitted without donation."""
    cfg = get_config(arch).reduced()
    be = Backend(arch, cfg, max_seq=32, seed=3)
    reqs = [Request(uid=i, prompt=np.arange(6) + 7 * i, max_new_tokens=5)
            for i in range(2)]
    served = np.stack([r.tokens for r in be.serve_batch(reqs)])

    # the same prefill, modality inputs (the backend's first draw) and
    # greedy loop, through an undonated decode step
    tokens = jnp.asarray(np.stack([r.prompt for r in reqs]), jnp.int32)
    pe = modality_inputs(cfg, len(reqs),
                         np.random.default_rng(3)).get("prefix_embeds")
    undonated = jax.jit(lambda p, t, c: decode_step(p, cfg, t, c))
    logits, cache = be._prefill(be.params, tokens, pe)
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    want = [tok]
    for _ in range(4):
        logits, cache = undonated(be.params, tok, cache)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        want.append(tok)
    np.testing.assert_array_equal(served, np.concatenate(want, axis=1))

    passed = jax.tree.leaves(cache)
    be._decode(be.params, tok, cache)
    assert passed and all(x.is_deleted() for x in passed)


def test_backend_commits_weights_to_the_default_device():
    """A backend commits its weights to the device that is the default
    when it is built, so its steps run there from any thread."""
    cfg = get_config("qwen2.5-3b").reduced()
    dev = jax.devices()[-1]
    with jax.default_device(dev):
        be = Backend("qwen", cfg, max_seq=32)
    leaves = jax.tree.leaves(be.params)
    assert all(x.committed and x.devices() == {dev} for x in leaves)
    res = be.serve_batch([Request(uid=0, prompt=np.arange(4),
                                  max_new_tokens=2)])[0]
    assert res.tokens.shape == (2,)
