"""A run of the harness on the CPU at a tiny size, for the tests.

The program is steered to small models from here (its published-width
configs are swapped for its own smoke configs); the harness runs as on the
chip, less its look for a TPU.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
FIX = Path(__file__).resolve().parent / "fixtures"
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

CELL = "tiny.hits"
CONFIGS = BENCH / "configs"


def make_root(tmp: Path) -> Path:
    """A checkout-like tree: the fixture's BENCHMARK.json, configs and mixes,
    and the benchmark's own decoders, metric readers and peak table (with the
    CPU in it). Each config takes the precision, control and limits of the
    chip configuration it names (``chip_config``), so the tests judge by
    the same numbers as the chip."""
    root = tmp / "root"
    if root.exists():  # made by an earlier run: keep its compile cache
        return root
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir(parents=True)
    shutil.copytree(BENCH / "metrics", root / "bench" / "metrics")
    shutil.copytree(BENCH / "archs", root / "bench" / "archs",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(FIX / "BENCHMARK.json", root / "BENCHMARK.json")
    spec = json.loads((FIX / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        cfg = json.loads((FIX / Path(c["file"]).name).read_text())
        chip = json.loads((CONFIGS / f"{cfg['chip_config']}.json").read_text())
        cfg.update({k: chip[k] for k in ("precision", "control", "limits")})
        (root / c["file"]).write_text(json.dumps(cfg))
    for w in spec["workloads"]:
        shutil.copy(FIX / f"{w['traffic']}.json", root / "bench" / "traffic")
    peaks = json.loads((BENCH / "peaks.json").read_text())
    import jax

    peaks[jax.devices()[0].device_kind] = {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "source": "test only"}
    (root / "bench" / "peaks.json").write_text(json.dumps(peaks))
    return root


def small_program(monkeypatch) -> None:
    """Point the program's published-width configs at its smoke ones."""
    import repro.configs.contriever as cc
    import repro.launch.serve as serve
    from repro.configs import get_config

    fix = json.loads((FIX / "tiny-ctr-qwen.json").read_text())["embedder"]
    enc = cc.EncoderConfig(
        name="contriever-tiny", num_layers=fix["num_hidden_layers"],
        d_model=fix["hidden_size"], num_heads=fix["num_attention_heads"],
        d_ff=fix["intermediate_size"], vocab_size=fix["vocab_size"],
        max_seq_len=fix["max_position_embeddings"])
    monkeypatch.setattr(cc, "CONTRIEVER_MSMARCO", enc)
    monkeypatch.setattr(serve, "get_config", lambda arch, smoke=False: get_config(arch, smoke=True))


def run_tiny(tmp: Path, monkeypatch, seed: int = 7, seconds: float = 2.0, trace: int = 0,
             control: int = 0, cell: str = CELL) -> dict:
    import run as harness

    small_program(monkeypatch)
    root = make_root(tmp)
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds, trace=trace,
                              rate=None, control=control)
    return harness.run(args, root=root, require_tpu=False)
