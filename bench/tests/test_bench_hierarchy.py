"""The hierarchy from the configuration: a replicated L1 over an L2
key-sharded over four chips (``shards``, ``l2_rows``), built and filled by
``deploy`` and run by the harness, on four virtual CPU devices in a child
process (this one may have fewer). A sound run is correct; the control, a
run whose candidate exchange between chips is left out, and a run whose
L2 scores are altered are not."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

TESTS = Path(__file__).resolve().parent
ROOT = TESTS.parents[1]

CHILD = r'''
import json, sys
from pathlib import Path
sys.path[:0] = [sys.argv[1], str(Path(sys.argv[1]).parent), str(Path(sys.argv[1]).parents[1] / "src")]
import dataclasses
import numpy as np, pytest, jax
import smoke, deploy, weights as W

out = {}
mp = pytest.MonkeyPatch()
smoke.small_program(mp)
tmp = Path(sys.argv[2])
root = smoke.make_root(tmp)
cfg = json.loads((root / "bench" / "configs" / "tiny-ctr-qwen-l2x4.json").read_text())
arch = W.load_arch(root / "bench", cfg["backend"]["model_type"])
stack = deploy.build(cfg, arch)
corpus = [f"cached prompt {i} about things" for i in range(40)]
n_fill = deploy.fill(stack, 5, corpus, [f"answer {i}" for i in range(40)])
l2 = stack.hierarchy.l2.store
out["build"] = {
    "n_fill": n_fill, "l1_live": len(stack.cache.store), "l2_live": len(l2),
    "l2_capacity": l2.capacity, "shards": l2.n_shards,
    "devices": len({a.device for a in l2.bank.buf.addressable_shards}),
    "shard_rows": np.asarray(l2.bank.valid).sum(axis=1).tolist(),
    "filled": deploy.filled_store(stack) is l2,
    "levels": [lv.name for lv in deploy.levels_of(stack)],
}
del stack, l2
seed = 2**31 + 21
out["sound"] = smoke.run_tiny(tmp, mp, seed=seed, cell="tiny.l2", trace=1)
out["control"] = smoke.run_tiny(tmp, mp, seed=seed, cell="tiny.l2", control=1)

import repro.distributed.sharded_read as sr
sr._build_sharded_program.cache_clear()
mp2 = pytest.MonkeyPatch()
mp2.setattr(sr, "all_gather_merge_topk", lambda axes, gs, gi, k, hierarchical=True: (gs, gi))
out["no_exchange"] = smoke.run_tiny(tmp, mp, seed=seed + 1, cell="tiny.l2")
mp2.undo()
sr._build_sharded_program.cache_clear()

orig = sr.ShardedReadBank.fused_read
def altered(self, *a, **k):
    dec = orig(self, *a, **k)
    s = dec.scores.copy()
    s[:, 1] += 1e-4
    return dataclasses.replace(dec, scores=s)
mp.setattr(sr.ShardedReadBank, "fused_read", altered)
out["l2_scores"] = smoke.run_tiny(tmp, mp, seed=seed + 2, cell="tiny.l2")
print("RESULT " + json.dumps(out))
'''


@pytest.fixture(scope="module")
def child(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=os.environ.get("XLA_FLAGS", "")
               + " --xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", CHILD, str(TESTS),
                        str(tmp_path_factory.mktemp("hier"))],
                       env=env, capture_output=True, text=True, timeout=900)
    line = [x for x in p.stdout.splitlines() if x.startswith("RESULT ")]
    assert p.returncode == 0 and line, p.stderr[-4000:]
    return json.loads(line[-1][len("RESULT "):])


def _failed(res):
    return [k for k, c in res["checks"].items() if c["value"] > c["limit"]]


def test_build_and_fill_the_hierarchy(child):
    b = child["build"]
    assert b["levels"] == ["L1", "L2"] and b["filled"]
    assert b["shards"] == 4 and b["devices"] == 4
    assert b["l2_capacity"] == 1024 and b["l2_live"] == 1024
    assert b["n_fill"] == 1024 - 40
    assert b["shard_rows"] == [256] * 4
    assert b["l1_live"] == 0  # left to the promotions


def test_sound_hierarchy_run_is_correct(child):
    res = child["sound"]
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4 and res["failed"] == 0
    assert set(child["control"]["metrics"]) == {"hit_p50_ms", "setup_s"}
    assert res["checks"]["shard_faults"]["value"] == 0
    assert list(res)[-1] == "checks"
    m = res["metrics"]
    assert m["compiles_in_window"]["value"] == 0
    # uniform repeats of 256 prompts over a 64-row L1: most win in the L2
    assert m["l2_win_share"]["value"] > 50


@pytest.mark.parametrize("run,number", [
    ("control", None),
    ("no_exchange", "top4_gap"),
    ("l2_scores", "search_err"),
])
def test_broken_hierarchy_is_not_correct(child, run, number):
    res = child[run]
    assert not res["correct"]
    failed = _failed(res)
    assert failed and (number is None or number in failed), res["checks"]
