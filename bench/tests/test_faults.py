"""Each cell's comparison catches a broken timed path.

These tests drive the rest of a run (the harness's rehearsal mode skips
its look for a chip and runs on the CPU at a tiny size) with the program
broken underneath, and check that ``correct`` comes out false: a token or
an answer altered where it is produced, half of each batch left out, and
a step that leaves its state unchanged.  (The cells run on one chip, so
there is no exchange between chips to leave out.)  One sound run of each
cell checks that the same run comes out correct.

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests -q
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import pathlib
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def run_cell(workload, seed, *, seconds=3.0, control=False):
    from bench.harness import common

    manifest, cell, cfg, traffic = common.find_cell(workload)
    from bench.harness import llm as face

    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=0, rehearse=True, control=control)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        face.run(args, manifest, cell, cfg, traffic,
                 common.Clock(time.perf_counter()))
    return json.loads(out.getvalue().strip().splitlines()[-1])


def _alter_tokens(monkeypatch):
    from repro.serving import engine

    orig = engine.Backend.serve_batch

    def serve(self, requests):
        out = orig(self, requests)
        for r in out:
            r.tokens = np.asarray(r.tokens).copy()
            r.tokens[0] = (r.tokens[0] + 1) % self.cfg.vocab_size
        return out
    monkeypatch.setattr(engine.Backend, "serve_batch", serve)


def _half_llm(monkeypatch):
    from repro.serving import engine

    orig = engine.Backend.serve_batch
    monkeypatch.setattr(engine.Backend, "serve_batch",
                        lambda self, reqs: orig(self, reqs)[:len(reqs) // 2])


def _stale_decode(monkeypatch):
    from repro.serving import engine

    orig = engine.decode_step

    def stale(params, cfg, token, cache):
        logits, _ = orig(params, cfg, token, cache)
        return logits, cache
    monkeypatch.setattr(engine, "decode_step", stale)


@pytest.mark.parametrize("fault", [_alter_tokens, _half_llm, _stale_decode])
def test_llm_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = run_cell("qwen2.5-3b.offline", 2147483800, seconds=2.0)
    assert out["correct"] is False, out["compared"]


@pytest.mark.parametrize("workload", ["qwen2.5-3b.offline",
                                      "qwen2.5-3b.code"])
def test_sound_run_is_correct(workload):
    out = run_cell(workload, 2147483802)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
