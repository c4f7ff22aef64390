"""Bring-up check on the chip: the served path at full width, end to end.

    python chip_smoke.py              # one chip: phases (a)-(d) below
    python chip_smoke.py --chips 4    # four chips: the sharded-L2 path only

One chip. (a) JAX must see a TPU and the kernels must be compiled, not
interpreted. (b) ``repro.launch.serve.build_stack`` builds the served stack
at published widths: qwen1.5-0.5b (24 x 1024) behind contriever-msmarco
(12 x 768, traced into the fused read program) over an L1 store of 65,536
live rows searched by the compiled top-k kernel, with a host-RAM tier 1.
Text entries go in through ``insert_batch``; the rest of the store is seeded
unit rows. Eight "cold" entries are evicted to tier 1 by the fill. (c) A few
dozen ``CacheRequest``s go through ``CacheService``: exact repeats,
paraphrases, cold repeats (tier-1 promotions), novel prompts and
``force_fresh`` prompts (misses, generated by the full-width engine), then a
second wave repeating what the first one generated or promoted. Every hit
must return its answer byte for byte. (d) The fused read program's winners
and answer classes over the final store must equal a plain f32 reference:
numpy top-k over the same rows plus the host-side decide, same thresholds.

Four chips. A replicated L1 over a key-sharded L2 of 262,144 live rows on a
4-device cache mesh serves the same request mix through the one collective
read program; its decisions and counter deltas must equal
``host_reference_read``, and the L2 buffer must sit on 4 distinct devices.

Thresholds: with random weights the encoder puts unrelated prompts at
cosine 0.6-0.98, so the paper's t_s=0.6 would make every prompt a hit. The
smoke serves with t_s=0.99, t_single=0.95, t_combined=1.9: an exact repeat
hits, and the rest lands where the reference decide puts it.

The last line of stdout is ``{"ok": true, "device": {...}}``; any failure
exits non-zero without it. Without a TPU (e.g. ``JAX_PLATFORMS=cpu``) the
script refuses to run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from contextlib import contextmanager
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen1.5-0.5b"
SEED = 0  # data; the weights are random from the stack's own fixed seed
T_S, T_SINGLE, T_COMBINED = 0.99, 0.95, 1.9
MAX_TOKENS = 16  # a miss: prefill + 15 decode steps

# (full, smoke) sizes
L1_ROWS = (65536, 2048)
L2_ROWS = (262144, 4096)
TIER1_ROWS = (1024, 64)
N_HOT, N_COLD, N_NOVEL, N_FRESH = 16, 8, 8, 4

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(Exception):
    """A check of the smoke failed."""


def require(cond, what="") -> None:
    """A check of the smoke; unlike ``assert`` it also holds under ``-O``."""
    if not cond:
        raise SmokeFailure(what)


class Clock:
    """Per-phase wall, compile (XLA backend, persistent-cache sensitive) and
    trace+lower seconds, from JAX's own compile events."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.trace_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.compile_s += duration
        elif event in _TRACE_EVENTS:
            self.trace_s += duration

    @contextmanager
    def phase(self, name: str):
        c0, t0, w0 = self.compile_s, self.trace_s, time.perf_counter()
        yield
        wall = time.perf_counter() - w0
        comp, trace = self.compile_s - c0, self.trace_s - t0
        log(f"phase {name}: wall_s={wall:.3f} compile_s={comp:.3f} "
            f"trace_s={trace:.3f} steady_s={wall - comp - trace:.3f}")


def _words(rng, n: int) -> str:
    letters = list("abcdefghijklmnopqrstuvwxyz")
    return " ".join("".join(rng.choice(letters, 7)) for _ in range(n))


def make_mix():
    """The request mix: hot QA clusters (inserted canonical question +
    a paraphrase), cold prompts (inserted, then evicted to tier 1), novel
    and force_fresh prompts (never inserted)."""
    import numpy as np

    from repro.data.synthetic import squad_like_qa

    qa = squad_like_qa(n_clusters=N_HOT, paraphrases=2, seed=SEED)
    hot_q = [qa[2 * c][0] for c in range(N_HOT)]
    hot_a = [qa[2 * c][1] for c in range(N_HOT)]
    para_q = [qa[2 * c + 1][0] for c in range(N_HOT)]
    rng = np.random.default_rng(SEED + 1)
    cold_q = [_words(rng, 24) for _ in range(N_COLD)]
    cold_a = [f"Cold answer {i}: {_words(rng, 6)}." for i in range(N_COLD)]
    novel_q = [_words(rng, 24) for _ in range(N_NOVEL)]
    fresh_q = [_words(rng, 24) for _ in range(N_FRESH)]
    # never served: the reference batch's guaranteed first-level misses,
    # so the lower levels' candidate touches show in the counter deltas
    probe_q = [_words(rng, 24) for _ in range(N_NOVEL)]
    return dict(hot_q=hot_q, hot_a=hot_a, para_q=para_q, cold_q=cold_q,
                cold_a=cold_a, novel_q=novel_q, fresh_q=fresh_q, probe_q=probe_q)


def reference_texts(mix):
    return (mix["hot_q"] + mix["para_q"] + mix["cold_q"] + mix["novel_q"]
            + mix["fresh_q"] + mix["probe_q"])


def filler(n: int, dim: int):
    """Seeded unit rows (bulk, host) with their payload texts."""
    import numpy as np

    v = np.random.default_rng(SEED + 2).standard_normal((n, dim), np.float32)
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    return v, [f"filler {i}" for i in range(n)], [f"filler answer {i}" for i in range(n)]


def print_widths(stack) -> None:
    c = stack.cfg
    emb = stack.cache.embedder
    log(f"model {c.name}: layers={c.num_layers} d_model={c.d_model} "
        f"heads={c.num_heads} kv_heads={c.num_kv_heads} d_ff={c.d_ff} "
        f"vocab={c.vocab_size}")
    ecfg = getattr(emb, "cfg", None)
    if ecfg is not None:
        log(f"embedder {ecfg.name}: layers={ecfg.num_layers} d_model={ecfg.d_model} "
            f"heads={ecfg.num_heads} d_ff={ecfg.d_ff} vocab={ecfg.vocab_size}")
    else:
        log(f"embedder {emb.name}: dim={emb.dim} (host)")


def serve_mix(stack, mix) -> dict:
    """Phase (c): two waves of CacheRequests through CacheService; the
    second replays what the first generated or promoted. Returns the
    responses by group."""
    from repro.core import CacheRequest
    from repro.serving.service import CacheService

    def req(p, **kw):
        return CacheRequest(p, max_tokens=MAX_TOKENS, **kw)

    groups1 = {
        "hot": [req(p) for p in mix["hot_q"]],
        "para": [req(p) for p in mix["para_q"]],
        "cold": [req(p) for p in mix["cold_q"]],
        "novel": [req(p) for p in mix["novel_q"]],
        "fresh": [req(p, force_fresh=True) for p in mix["fresh_q"]],
    }
    groups2 = {
        "novel2": [req(p) for p in mix["novel_q"]],
        "fresh2": [req(p) for p in mix["fresh_q"]],
        "cold2": [req(p) for p in mix["cold_q"]],
    }
    out = {}
    with CacheService(stack.client, max_batch=64, max_wait_ms=20.0) as service:
        for groups in (groups1, groups2):
            order = [(g, r) for g, rs in groups.items() for r in rs]
            futs = service.submit_many([r for _, r in order])
            resps = [f.result(timeout=900) for f in futs]
            for (g, _), r in zip(order, resps):
                out.setdefault(g, []).append(r)
    return out


def check_mix(stack, mix, out) -> None:
    """The invariants of phase (c)."""
    from collections import Counter

    classes = Counter()
    for g, rs in out.items():
        for r in rs:
            require(r.status in ("hit", "generated"), (g, r.status))
            classes[r.cache_status] += 1
            if r.status == "generated":
                lr = r.llm_response
                require(lr is not None and lr.tokens_out == MAX_TOKENS, (g, lr))
    log("requests by class: " + " ".join(f"{k}={v}" for k, v in sorted(classes.items()))
        + f" total={sum(classes.values())}")
    for r, a in zip(out["hot"], mix["hot_a"]):
        require(r.status == "hit" and r.text == a, ("hot", r.cache_status, r.text))
    for r, a in zip(out["cold"] + out["cold2"], mix["cold_a"] * 2):
        require(r.status == "hit" and r.text == a, ("cold", r.cache_status, r.text))
    for r in out["fresh"]:
        require(r.status == "generated", ("fresh", r.status))
    # what wave 1 generated (or synthesized) was backfilled: wave 2 replays it
    for g1, g2 in (("novel", "novel2"), ("fresh", "fresh2")):
        for r1, r2 in zip(out[g1], out[g2]):
            require(r2.status == "hit" and r2.text == r1.text, (g2, r2.cache_status))
    tier1 = stack.cache.store.tier1
    if tier1 is not None:
        require(tier1.promotions >= 1, "no tier-1 promotion ran")
        require(sum(r.cache_status == "tier1" for r in out["cold"]) >= 1)
        log(f"tier1: demotions={tier1.demotions} promotions={tier1.promotions}")
    m = stack.engine.metrics
    require(m["decode_steps"] >= 8 and m["prefill_tokens"] > 0, m)
    log(f"engine: {m}")


def check_embeddings(embedder, texts, vecs) -> None:
    """The in-program encoder against a plain f32 forward at highest matmul
    precision (the program itself runs at the TPU default)."""
    import jax
    import numpy as np

    from repro.core.embeddings import ContrieverEncoder, _encoder_forward

    if not isinstance(embedder, ContrieverEncoder):
        return
    ids, mask = embedder.tok.encode_batch(list(texts))
    cfg = embedder.cfg
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, i, m: _encoder_forward(p, cfg, i, m))(
            embedder.params, ids, mask
        )
    ref = np.asarray(ref, np.float64)
    v = np.asarray(vecs, np.float64)
    cos = np.sum(ref * v, -1) / (np.linalg.norm(ref, axis=-1) * np.linalg.norm(v, axis=-1))
    log(f"embedding vs f32 highest-precision reference: min_cos={cos.min():.6f} "
        f"max_abs_diff={np.abs(ref - v).max():.3e}")
    require(cos.min() >= 0.999, cos.min())


def reference_check_one(stack, texts) -> None:
    """Phase (d): the fused program (Pallas lanes kernel) against numpy
    top-k plus the host decide over the same bank, same thresholds."""
    import numpy as np

    from repro.core import read_path
    from repro.distributed.sharded_read import _np_decide

    cache = stack.cache
    store = cache.store
    bank = store._bank
    n = len(texts)
    k = min(cache._solo_k(), store.capacity)
    spec = read_path.level_spec(cache, k)
    thr = np.full((n, 1), T_S, np.float32)
    dec = read_path.fused_read(bank, cache.embedder, texts, thr, (spec,))
    buf = np.asarray(bank.buf[0])
    valid = np.asarray(bank.valid[0])
    q = dec.vecs / np.maximum(np.linalg.norm(dec.vecs, axis=1, keepdims=True), 1e-9)
    s = np.where(valid[None, :], q @ buf.T, -np.inf).astype(np.float32)
    order = np.argsort(-s, axis=1, kind="stable")[:, :k]
    ts = np.take_along_axis(s, order, 1)
    winner, hit, gen, _ = _np_decide((spec,), k, ts[:, None, :], thr)
    require((dec.winner == winner).all(), "winner mismatch")
    require((dec.hit == hit).all() and (dec.generative == gen).all(), "class mismatch")
    require((dec.idx[:, 0, :] == order).all(), "top-k slot mismatch")
    err = float(np.abs(dec.scores[:, 0, :] - ts).max())
    log(f"reference check: {n} queries x top-{k} over {int(valid.sum())} rows: "
        f"winners, classes and slots equal; max_score_diff={err:.3e} "
        f"hits={int(hit.sum())} generative={int(gen.sum())}")
    require(err <= 1e-5, err)
    check_embeddings(cache.embedder, texts, dec.vecs)


def peak_bytes() -> str:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def run_one_chip(clock: Clock, smoke: bool = False) -> None:
    from repro.launch.serve import build_stack

    i = int(smoke)
    mix = make_mix()
    with clock.phase("b build"):
        stack = build_stack(
            ARCH, smoke=smoke, threshold=T_S, t_single=T_SINGLE,
            t_combined=T_COMBINED, capacity=L1_ROWS[i], tier1_rows=TIER1_ROWS[i],
        )
    print_widths(stack)
    cache, store = stack.cache, stack.cache.store
    with clock.phase("b fill"):
        # cold first: the fill evicts exactly these (least recently used)
        cache.insert_batch(mix["cold_q"], mix["cold_a"])
        cache.insert_batch(mix["hot_q"], mix["hot_a"])
        v, fq, fa = filler(store.capacity - N_HOT, store.dim)
        store.add_batch(v, fq, fa)
        store._bank.buf.block_until_ready()
    live = int(store._bank.valid.sum())
    log(f"L1 rows: live={live} capacity={store.capacity} dim={store.dim} "
        f"tier1={len(store.tier1)} use_pallas={store.use_pallas}")
    require(live == store.capacity and len(store.tier1) == N_COLD)
    with clock.phase("c serve"):
        out = serve_mix(stack, mix)
    check_mix(stack, mix, out)
    texts = reference_texts(mix)
    with clock.phase("d reference"):
        reference_check_one(stack, texts)
    log(f"peak_bytes_in_use={peak_bytes()}")


def reference_check_four(stack, texts) -> None:
    """Decisions and counter deltas of the collective program against
    ``host_reference_read`` over the same banks."""
    import numpy as np

    from repro.core import read_path
    from repro.distributed.sharded_read import host_reference_read

    h = stack.hierarchy
    srb = h.ensure_sharded_bank()
    require(srb is not None, "hierarchy is not on the collective read program")
    levels = h._levels()
    n = len(texts)
    specs = [read_path.level_spec(c, min(c.max_sources, c.store.capacity))
             for _, c in levels]
    thr = np.full((n, len(levels)), T_S, np.float32)
    rb, sh = srb.rep_bank, srb.sh_stores[0]
    before = (np.asarray(rb.d_access_count), np.asarray(sh.bank.d_access_count))
    dec = srb.fused_read(h.l1.embedder, texts, thr, specs)
    after = (np.asarray(rb.d_access_count), np.asarray(sh.bank.d_access_count))
    ref = host_reference_read(srb, dec.vecs, thr, specs)
    require((dec.winner == ref["winner"]).all(), "winner mismatch")
    require((dec.hit == ref["hit"]).all(), "hit mismatch")
    require((dec.generative == ref["generative"]).all(), "class mismatch")
    fin = np.isfinite(ref["scores"])
    require((dec.idx[fin] == ref["idx"][fin]).all(), "top-k slot mismatch")
    exp_rep = np.zeros_like(before[0])
    exp_sh = np.zeros_like(before[1])
    tm = ref["tmask"]
    for li, (kind, lane) in enumerate(srb.layout):
        slots = ref["idx"][:, li][tm[:, li]]
        if kind == "rep":
            np.add.at(exp_rep, (lane, slots), 1)
        else:
            np.add.at(exp_sh, (slots // sh.cap_local, slots % sh.cap_local), 1)
    require(((after[0] - before[0]) == exp_rep).all(), "L1 counter delta mismatch")
    require(((after[1] - before[1]) == exp_sh).all(), "L2 counter delta mismatch")
    err = float(np.abs(np.where(fin, dec.scores - ref["scores"], 0)).max())
    devices = {s.device for s in sh.bank.buf.addressable_shards}
    log(f"reference check (4 chips): {n} queries: winners, classes, slots and "
        f"counter deltas ({int(exp_rep.sum())} L1, {int(exp_sh.sum())} L2 bumps) "
        f"equal host_reference_read; max_score_diff={err:.3e}; L2 shards on "
        f"{len(devices)} devices")
    require(err <= 1e-5, err)
    require(len(devices) == srb.n_shards, devices)
    check_embeddings(h.l1.embedder, texts, dec.vecs)


def run_four_chips(clock: Clock, smoke: bool = False) -> None:
    from repro.launch.serve import build_stack

    i = int(smoke)
    mix = make_mix()
    n_hot = N_HOT + N_COLD
    with clock.phase("4 build"):
        stack = build_stack(
            ARCH, smoke=smoke, threshold=T_S, t_single=T_SINGLE,
            t_combined=T_COMBINED, capacity=4096 if not smoke else 256,
            shards=4, l2_capacity=L2_ROWS[i] + 4096,
        )
    print_widths(stack)
    h = stack.hierarchy
    l2 = h.l2.store
    with clock.phase("4 fill"):
        h.insert_batch(mix["cold_q"] + mix["hot_q"], mix["cold_a"] + mix["hot_a"],
                       cache_l1=False)
        v, fq, fa = filler(L2_ROWS[i], l2.dim)
        l2.add_batch(v, fq, fa)
        l2.bank.buf.block_until_ready()
    log(f"L2 rows: live={len(l2)} capacity={l2.capacity} shards={l2.n_shards} "
        f"rows_per_shard={l2.cap_local} dim={l2.dim}; L1 capacity="
        f"{stack.cache.store.capacity}")
    require(len(l2) == L2_ROWS[i] + n_hot)
    with clock.phase("4 serve"):
        out = serve_mix(stack, mix)
    check_mix(stack, mix, out)
    texts = reference_texts(mix)
    with clock.phase("4 reference"):
        reference_check_four(stack, texts)
    log(f"peak_bytes_in_use(device 0)={peak_bytes()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the replicated-L1 / sharded-L2 path")
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: FAIL: the repo's src/ is not beside this script ({e})",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    import jax

    from repro.kernels.backend import resolve_interpret

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: FAIL: no TPU; JAX sees {len(devs)} {dev.platform} "
              "device(s)", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: FAIL: --chips {args.chips} but JAX sees {len(devs)}",
              file=sys.stderr)
        return 2
    interpret = resolve_interpret()
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
        f"interpret={interpret}; compile cache: {cache_dir}")
    if interpret:
        print("chip_smoke: FAIL: kernels resolve to interpret mode", file=sys.stderr)
        return 2
    clock = Clock()
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(clock)
    else:
        run_one_chip(clock)
    log(f"total: wall_s={time.perf_counter() - t0:.3f} "
        f"compile_s={clock.compile_s:.3f} trace_s={clock.trace_s:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
