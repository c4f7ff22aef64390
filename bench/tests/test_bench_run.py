"""Whole runs of the harness on the CPU at a tiny size, judged by the chip
configuration's limits: a sound run is correct, the control (the references
one precision lower in the program's place) is not, and a run whose timed
path is broken underneath is not."""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

import smoke

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def shared(tmp_path_factory):
    # one checkout for the module, so later runs find the compiled programs
    return tmp_path_factory.mktemp("bench_runs")


def _failed(checks):
    return [k for k, c in checks.items() if c["value"] > c["limit"]]


def test_sound_run_is_correct(shared, monkeypatch):
    res = smoke.run_tiny(shared, monkeypatch, seed=2**31 + 11)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 20 and res["failed"] == 0
    assert set(res["metrics"]) == {"hit_p50_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    assert "search_err" in res["checks"] and "control" not in res


def test_control_is_not_correct(shared, monkeypatch):
    """The control goes through the same numbers, limits and verdict."""
    res = smoke.run_tiny(shared, monkeypatch, seed=2**31 + 11, control=1)
    assert res["control"] == {"encoder": "fp8", "search": "high"}
    assert not res["correct"]
    assert _failed(res["checks"]), res["checks"]


def _alter_answers(monkeypatch):
    from repro.core.generative_cache import GenerativeCache

    orig = GenerativeCache._materialize_one

    def altered(self, *a, **k):
        r, ins = orig(self, *a, **k)
        if r.hit and r.response:
            r.response = r.response + "."
        return r, ins

    monkeypatch.setattr(GenerativeCache, "_materialize_one", altered)


def _alter_scores(monkeypatch):
    import repro.core.read_path as read_path

    orig = read_path.fused_read

    def altered(*a, **k):
        dec = orig(*a, **k)
        return dataclasses.replace(dec, scores=dec.scores + 1e-4)

    monkeypatch.setattr(read_path, "fused_read", altered)


def _alter_embeddings(monkeypatch):
    import repro.core.embeddings as emb

    orig = emb._encoder_forward

    def altered(params, cfg, ids, mask):
        v = orig(params, cfg, ids, mask)
        v = v + 0.1 * jnp.roll(v, 1, axis=-1)
        return v / jnp.linalg.norm(v, axis=-1, keepdims=True)

    monkeypatch.setattr(emb, "_encoder_forward", altered)


@pytest.mark.parametrize("fault,number", [
    (_alter_answers, "answer_faults"),
    (_alter_scores, "search_err"),
    (_alter_embeddings, "embed_err"),
])
def test_broken_timed_path_is_not_correct(shared, monkeypatch, fault, number):
    fault(monkeypatch)
    res = smoke.run_tiny(shared, monkeypatch, seed=5)
    assert not res["correct"]
    assert number in _failed(res["checks"]), res["checks"]


def test_mixed_sound_run_is_correct(shared, monkeypatch):
    """Misses reach the backend: their generations are judged against the
    decoder's plain reference, and the rate of their tokens is reported."""
    res = smoke.run_tiny(shared, monkeypatch, seed=2**31 + 12, seconds=3.0,
                         cell="tiny.mixed")
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"gen_tokens_per_s", "setup_s"}
    assert {"gen_gap", "logit_err"} <= set(res["checks"])


def test_mixed_control_is_not_correct(shared, monkeypatch):
    res = smoke.run_tiny(shared, monkeypatch, seed=2**31 + 12, seconds=3.0,
                         cell="tiny.mixed", control=1)
    assert res["control"]["backend"] == "fp8"
    assert not res["correct"]
    assert _failed(res["checks"]), res["checks"]


def test_altered_token_is_not_correct(shared, monkeypatch):
    """Every generation's first token, altered where the engine produces it,
    lies far below the reference's best."""
    from repro.serving.engine import ServingEngine

    orig = ServingEngine._admit

    def altered(self):
        before = set(self.active)
        orig(self)
        for rid, req in self.active.items():
            if rid not in before:
                req.out_tokens[0] = (req.out_tokens[0] + 1) % self.cfg.vocab_size

    monkeypatch.setattr(ServingEngine, "_admit", altered)
    res = smoke.run_tiny(shared, monkeypatch, seed=2**31 + 13, seconds=3.0,
                         cell="tiny.mixed")
    assert not res["correct"]
    assert "gen_gap" in _failed(res["checks"]), res["checks"]


def test_no_chip_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", "hits-zipf.1m",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_only_benchmark_files_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files: the
    program is not there, and the run fails without a result."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hits-zipf.1m", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
