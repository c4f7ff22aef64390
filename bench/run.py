"""The benchmark's one command: one cell, one seed, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; it names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``). The metrics are the readers
``bench/metrics/<metric>.py`` named in ``BENCHMARK.json``: the end-to-end
ones with ``--trace 0``, the per-layer ones with ``--trace 1``.

A run builds the served stack (``repro.launch.serve.build_stack``) with the
seed's weights, fills the cache, warms every shape the traffic uses, sends a
warm-up stream, then sends the window's requests through
``CacheService.submit`` at their due times, open loop, for ``--seconds``.
Latency runs from a request's due time to the moment its future resolves.
After the window the program's state is freed and the plain references
check what it served. The last line on stdout is one JSON object; the
compared numbers, each beside its limit, are the last lines on stderr.

Needs a TPU and as many chips as the cell asks for: without them it exits
non-zero and prints no result. ``--rate`` overrides the mix's rate (the knee
sweep). ``--control 1`` puts the control (the references one precision lower,
as the configuration's ``control`` states) in the program's place: the same
numbers and limits then decide ``correct``, which has to come out false; the
program's own numbers go to stderr.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()


def _process_age() -> float:
    """Seconds since this process started (Linux), else 0."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        import os as _os

        return max(0.0, up - start_ticks / _os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


AGE_AT_START = _process_age()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import inspect  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT / "src"))

DRAIN_S = 60.0  # answers due in the window may come this long after it
READ_SAMPLE = 256  # reads compared with the references
TRACE_S = 5.0  # seconds of the window a --trace 1 run profiles, at the least
TRACE_REQUESTS = 40  # and as many as the mix's rate needs to bring this many
_COMPILE = "/jax/core/compile/backend_compile_duration"


class BenchError(Exception):
    """The run cannot be made as asked (no chip, unknown cell, bad file)."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- what BENCHMARK.json names ---------------------------------------------------


def load_spec(root: Path, cell: str) -> dict:
    path = root / "BENCHMARK.json"
    if not path.exists():
        raise BenchError(f"no {path}")
    spec = json.loads(path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if cell not in cells:
        raise BenchError(f"unknown workload {cell!r}; known: {sorted(cells)}")
    w = cells[cell]
    cfgs = {c["name"]: c for c in spec["configs"]}
    return {"spec": spec, "cell": w, "config": cfgs[w["config"]]}


def metric_names(spec: dict, cell: str, trace: bool) -> List[dict]:
    """The cell's end-to-end metrics (trace off) or per-layer metrics (on):
    those whose ``workloads`` list the cell, or that have none."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_reader(root: Path, name: str):
    """``bench/metrics/<name>.py``: a module with ``UNIT`` and ``read(run)``.
    A metric split by the end-to-end metric it moves, one entry for each
    group of cells (``compiles_in_window`` and ``compiles_in_window.gen``),
    shares one reader: ``<name>`` up to its first dot names the file where
    no file of the whole name is there."""
    metrics = root / "bench" / "metrics"
    path = metrics / f"{name}.py"
    if not path.exists() and "." in name:
        path = metrics / f"{name.split('.')[0]}.py"
    if not path.exists():
        raise BenchError(f"no reader {metrics / name}.py")
    sp = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    if not path.exists():
        raise BenchError(f"no {path}")
    return json.loads(path.read_text())


# -- the run's record, as the readers see it -------------------------------------


@dataclass
class Run:
    """Everything a metric reader may read. Times are ``perf_counter``
    seconds; ``t0`` is the window's first due arrival."""

    cfg: dict
    mix: dict
    peak: dict
    t0: float
    seconds: float
    setup_s: float
    served: list  # check.Served, with t_due added
    counters0: dict
    counters1: dict
    compiles_in_window: int
    live_rows: int
    dim: int
    calls: list  # deploy.Capture of the window's reads
    trace: object = None  # xtrace.Trace of the traced span, or None
    giveup: float = 0.0  # a failed request's latency ends here
    gc_pauses: list = field(default_factory=list)  # (generation, seconds)
    shard_rows: list = field(default_factory=list)  # live rows per shard of a sharded L2

    def latencies(self, classes) -> List[float]:
        """Seconds from due to resolved for requests answered in one of
        ``classes``; a request that failed counts, later than any answer, in
        the hit classes if it repeated a cached prompt and in ``miss`` if it
        was novel."""
        out = []
        for s in self.served:
            if s.status is None:
                cls = "miss" if s.kind == "novel" else "hit"
                if cls in classes:
                    out.append(self.giveup - s.t_due)
            elif s.status in classes:
                out.append(s.t_done - s.t_due)
        return out


# -- the window ------------------------------------------------------------------


def drive(service, requests, t0: float, on_done=None) -> list:
    """Send ``requests`` open loop: each at ``t0 + t_due``, whatever the
    state of the earlier ones. Returns their Served records."""
    from check import Served
    from repro.core import CacheRequest

    out = []
    for r in requests:
        due = t0 + r.t_due
        now = time.perf_counter()
        if due > now:
            time.sleep(due - now)
        s = Served(r.prompt, r.kind, r.max_tokens, time.perf_counter(), due)
        out.append(s)
        try:
            fut = service.submit(CacheRequest(r.prompt, max_tokens=r.max_tokens))
        except Exception as e:  # noqa: BLE001 — a refused request is a failed one
            s.error = f"{type(e).__name__}: {e}"
            continue
        s.fut, s.sent = fut, True
        fut.add_done_callback(lambda f, s=s: _resolve(s, f, on_done))
    return out


def _resolve(s, fut, on_done) -> None:
    t = time.perf_counter()
    try:
        _record(s, fut, t)
    finally:
        s.fut = None  # the response is not kept past its record
    if on_done is not None and s.status is not None:
        on_done(s)


def _record(s, fut, t: float) -> None:
    try:
        resp = fut.result()
    except Exception as e:  # noqa: BLE001 — recorded as a failure
        s.error = f"{type(e).__name__}: {e}"
        return
    if resp.status == "deadline_exceeded":
        s.error = "deadline_exceeded"
        return
    s.t_done = t
    s.text = resp.text
    s.status = resp.cache_status
    s.cost = float(resp.cost_usd)
    cr = resp.cache_result
    if cr is not None and s.status != "miss":
        s.sources = tuple((float(sc), e.query) for sc, e in cr.sources)


def wait_all(served, until: float) -> None:
    for s in served:
        fut = s.fut
        if fut is None:
            continue
        left = until - time.perf_counter()
        if left <= 0:
            break
        try:
            fut.result(timeout=left)
        except Exception:  # noqa: BLE001 — recorded by the callback
            pass
    for s in served:  # the callback may still be finishing on its thread
        fut = s.fut
        if fut is not None and fut.done() and s.status is None and s.error is None:
            _resolve(s, fut, None)


def backlog(served, t: float) -> int:
    """Requests submitted by ``t`` and not answered by then."""
    return sum(s.t_submit <= t and (s.t_done is None or s.t_done > t) for s in served)


def inserted(s) -> bool:
    """Whether answering ``s`` put its prompt in the cache: a generation the
    backend was paid for is backfilled (a follower that rode another's
    generation, at no cost, is not), and a generative hit's synthesized
    answer is inserted under its prompt."""
    return (s.status == "miss" and s.cost > 0) or s.status == "generative"


def sweep(service, requests, keep, seconds: float, rate: float) -> None:
    """One window at a swept rate, reported on stderr and not checked."""
    from readings import percentile

    t0 = time.perf_counter() + 0.05
    served = drive(service, requests, t0, keep)
    wait_all(served, t0 + seconds + DRAIN_S)
    while service.inflight and time.perf_counter() < t0 + seconds + 4 * DRAIN_S:
        time.sleep(0.1)  # the next window starts on an empty queue
    hit = [s.t_done - s.t_due for s in served if s.status in ("hit", "generative", "tier1")]
    miss = [s.t_done - s.t_due for s in served if s.status == "miss"]
    in_win = sum(s.t_done is not None and s.t_done <= t0 + seconds for s in served)
    fmt = lambda v: "-" if v is None else f"{v * 1e3:.1f}"  # noqa: E731
    log(f"sweep rate={rate:g}: {len(served)} sent, {in_win} answered in the window, "
        f"{sum(s.status is None for s in served)} failed; hit p50 "
        f"{fmt(percentile(hit, 50))} p95 {fmt(percentile(hit, 95))} ms; miss p50 "
        f"{fmt(percentile(miss, 50))} p95 {fmt(percentile(miss, 95))} ms; backlog: "
        + " ".join(str(backlog(served, t0 + seconds * f / 10)) for f in range(1, 11)))


def counters(service, stack) -> dict:
    lk, dp = service.scheduler_stats
    return {
        "lookup_batches": lk.batches if lk else 0,
        "lookup_items": lk.batched_items if lk else 0,
        "miss_batches": dp.batches if dp else 0,
        "miss_items": dp.batched_items if dp else 0,
        "engine": dict(stack.engine.metrics),
        "engine_slots": stack.engine.max_batch,
    }


class GcPauses:
    """``gc.callbacks`` hook: each collection of the Python heap while ``on``,
    as (generation, seconds)."""

    def __init__(self):
        self.on = False
        self.pauses: List[tuple] = []
        self._t = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self.on:
            self.pauses.append((info["generation"], time.perf_counter() - self._t))


def pick_sample(caps: dict, served: list, seed: int) -> List[int]:
    """A sample drawn from the seed of the window's requests that have a
    read, with the longest prompt among them."""
    import numpy as np

    matched = sorted(caps)
    if not matched:
        return []
    rng = np.random.default_rng([seed, 0xC4EC])
    sample = set(rng.choice(matched, min(READ_SAMPLE, len(matched)), replace=False).tolist())
    sample.add(max(matched, key=lambda i: len(served[i].prompt)))
    return sorted(sample)


class TraceThread(threading.Thread):
    """Marks ``span`` seconds of the window from ``lead`` seconds in, then
    stops the profiler, on its own thread so that the load generator keeps
    its schedule. The profiler itself starts in set-up (``start_profiler``):
    starting it inside the window would stall the generator."""

    def __init__(self, t0: float, lead: float, span: float):
        super().__init__(daemon=True)
        self.t0, self.lead, self.span = t0, lead, span
        self.error = None

    def run(self):
        import jax

        from xtrace import WINDOW

        try:
            time.sleep(max(0.0, self.t0 + self.lead - time.perf_counter()))
            with jax.profiler.TraceAnnotation(WINDOW):
                time.sleep(self.span)
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — reported, the run goes on
            self.error = e


def start_profiler(logdir: str) -> None:
    """Device ops and the runtime's own host spans; no Python function
    tracing, which would slow the host it is meant to observe."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(logdir, profiler_options=opts)


# -- one run ---------------------------------------------------------------------


def run(args, root: Path = ROOT, require_tpu: bool = True) -> dict:
    picked = load_spec(root, args.workload)
    spec, cell, cfg_entry = picked["spec"], picked["cell"], picked["config"]
    cfg = load_json(root / cfg_entry["file"])
    mix = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    metrics = metric_names(spec, cell["name"], bool(args.trace))
    readers = {m["name"]: load_reader(root, m["name"]) for m in metrics}
    peaks = load_json(root / "bench" / "peaks.json")

    import jax

    # the compile cache lives at a fixed path inside the checkout
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX sees {len(devs)} {devs[0].platform} device(s)")
    if len(devs) < cell["chips"]:
        raise BenchError(f"{cell['name']} needs {cell['chips']} chips; JAX sees {len(devs)}")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    if require_tpu:
        from repro.kernels.backend import resolve_interpret

        if resolve_interpret():
            raise BenchError("kernels resolve to interpret mode on this device")

    compile_times: List[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, dur, **_: compile_times.append(time.perf_counter()) if ev == _COMPILE else None)

    import numpy as np

    import check
    import deploy
    import loadgen
    import reference as R
    import weights as W
    from check import served_ids
    from repro.serving.service import CacheService

    rates = [float(r) for r in args.rate.split(",")] if args.rate else [None]
    try:
        wl = loadgen.make_workload(mix, args.seed, args.seconds, rates[-1])
    except ValueError as e:
        raise BenchError(f"traffic {cell['traffic']}: {e}") from e
    try:
        arch = W.load_arch(root / "bench", cfg["backend"]["model_type"])
    except FileNotFoundError as e:
        raise BenchError(str(e)) from e
    enc_w, dec_w = W.make_weights(args.seed, cfg["embedder"], cfg["backend"], arch)
    stack = deploy.build(cfg, arch)
    deploy.install_weights(stack, enc_w, dec_w)
    t = time.perf_counter()
    n_fill = deploy.fill(stack, args.seed, wl.corpus, wl.answers)
    log(f"fill: {deploy.filled_store(stack).capacity} rows in "
        f"{time.perf_counter() - t:.1f} s, {t - T_START:.1f} s after start")
    # the scheduler's own batch limit bounds every batch the window forms
    max_batch = inspect.signature(CacheService).parameters["max_batch"].default
    extra_fill = [int(t.split()[1]) for t in
                  deploy.warm_inserts(stack, args.seed, max_batch)]
    deploy.warm_reads(stack, wl.corpus, max_batch)
    log(f"warm: inserts and reads done {time.perf_counter() - T_START:.1f} s after start, "
        f"{len(compile_times)} compiles so far")
    store = deploy.filled_store(stack)
    answers = dict(zip(wl.corpus, wl.answers))
    levels = deploy.levels_of(stack)
    recorder = deploy.ReadRecorder(levels).install()
    if args.trace:
        recorder.watch_engine(stack.engine)
    service = CacheService(stack.client)

    def keep(s):  # what the cache now holds for this prompt
        if inserted(s):
            answers[s.prompt] = s.text

    # warm-up stream: the served path end to end, at the cell's rate
    tw = time.perf_counter() + 0.05
    warm = drive(service, wl.warmup, tw, keep)
    wait_all(warm, time.perf_counter() + DRAIN_S)
    warm_failed = sum(s.status is None for s in warm)
    if warm_failed:
        raise BenchError(f"{warm_failed} of {len(warm)} warm-up requests failed")

    for rate in rates[:-1]:
        sweep(service, loadgen.make_workload(mix, args.seed, args.seconds, rate).window,
              keep, args.seconds, rate)

    # the window, on a settled heap: what set-up left for the collector to
    # walk (the fill's million entries among it) is walked here, not inside
    gc.collect()
    pauses = GcPauses()
    gc.callbacks.append(pauses)
    c0 = counters(service, stack)
    tracer = None
    if args.trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        start_profiler(tdir)
        recorder.tracing = True
    recorder.on = pauses.on = True
    t0 = time.perf_counter() + 0.05
    setup_s = t0 - T_START + AGE_AT_START
    n_setup_compiles = len(compile_times)
    if args.trace:
        lead = min(2.0, 0.2 * args.seconds)
        span = min(max(TRACE_S, TRACE_REQUESTS / wl.meta["rate"]), 0.5 * args.seconds)
        tracer = TraceThread(t0, lead, span)
        tracer.start()
    window_backfill: Dict[str, tuple] = {}

    def keep_window(s):
        if inserted(s):
            answers[s.prompt] = s.text
            window_backfill[s.prompt] = s.t_done

    served = drive(service, wl.window, t0, keep_window)
    close = t0 + args.seconds
    giveup = close + DRAIN_S
    wait_all(served, giveup)
    if tracer is not None:
        tracer.join(timeout=120)
    c1 = counters(service, stack)
    compiles = sum(1 for tc in compile_times if t0 <= tc <= time.perf_counter())
    recorder.on = pauses.on = False
    gc.callbacks.remove(pauses)
    used = devs[:cell["chips"]]
    peak_bytes = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                     for d in used)
    live_rows = int(len(store))
    shard_rows = []
    shard_devices = 0
    if stack.hierarchy is not None:
        sh = stack.hierarchy.l2.store
        shard_rows = [int(x) for x in np.asarray(sh.bank.valid).sum(axis=1)]
        shard_devices = len({a.device for a in sh.bank.buf.addressable_shards})
    dim = store.dim
    service.close(timeout=30)
    # what the comparison needs from the program's state: the reads joined to
    # the entries, the rows of the sampled reads' candidates, the L1's
    # entries at each sampled read of a hierarchy, and the engine's logits
    # for a sample of the window's generations, served again
    calls = recorder.join()
    caps, read_faults = check.match_reads(served, calls)
    sample = pick_sample(caps, served, args.seed)
    cands = {i: caps[i][0].cands[caps[i][1]] for i in sample}
    rows_back = [lv.rows(c[2] for per in cands.values() for c in per[li]
                         if c[1] is not None and c[3])
                 for li, lv in enumerate(levels)]
    l1_at = None
    if len(levels) > 1:
        l1_at = {i: recorder.present(0, caps[i][0].t0, caps[i][0].t1, caps[i][0].thread)
                 for i in sample}
    vocab = cfg["backend"]["vocab_size"]
    gen_pick = check.pick_generations(served, vocab, args.seed)
    prompt_ids = {i: R.backend_ids(served[i].prompt, vocab) for i in gen_pick}
    resent = []
    if gen_pick:
        t_re = time.perf_counter()
        with deploy.EngineLogits(stack.engine) as cap:
            resent = cap.generate([np.asarray(prompt_ids[i], np.int32) for i in gen_pick],
                                  [served[i].max_tokens for i in gen_pick])
        log(f"served again: {len(gen_pick)} generations in "
            f"{time.perf_counter() - t_re:.1f} s")
    recorder.uninstall()
    for lv in levels:
        lv.store = None
    del service, stack, store, recorder, levels
    gc.collect()
    jax.clear_caches()

    trace = None
    if tracer is not None:
        import glob

        import xtrace

        if tracer.error is not None:
            raise BenchError(f"profiler failed: {tracer.error}")
        files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
        if not files:
            raise BenchError("the profiler wrote no trace")
        t_tr = time.perf_counter()
        trace = xtrace.load(files[0])
        log(f"trace: read in {time.perf_counter() - t_tr:.1f} s")
        import shutil

        shutil.rmtree(tdir, ignore_errors=True)

    # -- correctness --------------------------------------------------------
    t_ref = time.perf_counter()
    read_end = {}
    for c in calls:
        for tx in c.texts:
            read_end.setdefault(tx, c.t1)
    known = check.Known(
        answers, args.seed,
        {p: (read_end.get(p, 0.0), done) for p, done in window_backfill.items()})
    faults = check.answer_faults(served, known, vocab, caps, pool=l1_at is not None)
    for f in faults[:5]:
        log(f"answer fault: {f}")
    if l1_at is not None:  # a hierarchy's warm inserts went to its L1
        extra_fill = []
    ref = check.Reference(cfg, enc_w, known, n_fill, dim, extra_fill,
                          [served[i].prompt for i in sample], l1_at)
    numbers = ref.program(caps, sample, cands, rows_back)
    log(f"reference reads: {time.perf_counter() - t_ref:.1f} s")
    numbers.update(read_faults=float(read_faults), answer_faults=float(len(faults)))
    if shard_rows:
        numbers["shard_faults"] = float(cell["chips"] - shard_devices)
    served_seqs = [(prompt_ids[i], served_ids(served[i].text, vocab)) for i in gen_pick]
    if gen_pick:
        # the re-sent generations are compared where they were produced; as a
        # rule they repeat the served tokens, and one forward judges both
        same = all(list(t) == list(r[0]) for (_, t), r in zip(served_seqs, resent))
        seqs = [(p, t, r[1] if same else None) for (p, t), r in zip(served_seqs, resent)]
        numbers["gen_gap"], err = check.gen_numbers(arch, dec_w, cfg["backend"], seqs)
        if not same:
            _, err = check.gen_numbers(arch, dec_w, cfg["backend"],
                                       [(p, r[0], r[1]) for (p, _), r in
                                        zip(served_seqs, resent)])
        numbers["logit_err"] = err
        numbers["resent_differs"] = float(not same)
    limits = cfg["limits"]
    judged = numbers
    if args.control:
        ctl = ref.control(caps, sample)
        if gen_pick:
            ctl["gen_gap"], ctl["logit_err"] = check.gen_numbers(
                arch, dec_w, cfg["backend"], [(p, t, None) for p, t in served_seqs],
                quant=cfg["control"]["backend"])
        judged = dict(numbers, **ctl)
        log("program: " + " ".join(f"{k}={v:.6g}" for k, v in numbers.items()))
    ref_s = time.perf_counter() - t_ref
    checks = {k: {"value": v, "limit": limits[k]} for k, v in judged.items() if k in limits}
    correct = bool(caps) and all(c["value"] <= c["limit"] for c in checks.values())
    log(f"reference: {len(sample)} reads, {len(gen_pick)} generations, {ref_s:.1f} s; "
        f"set-up {setup_s:.1f} s with {n_setup_compiles} compiles")

    # -- metrics ------------------------------------------------------------
    for s in served:
        if s.status is None and s.error is None:
            s.error = "unresolved"
    record = Run(cfg, mix, peaks[kind], t0, args.seconds, setup_s, served, c0, c1,
                 compiles, live_rows, dim, calls, trace, giveup, pauses.pauses,
                 shard_rows)
    out_metrics = {}
    for m in metrics:
        v = readers[m["name"]].read(record)
        if v is not None:
            out_metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind, "count": cell["chips"],
              "memory_peak_bytes": peak_bytes}
    result = {
        "correct": correct,
        "attempted": len(served),
        "failed": sum(s.status is None for s in served),
        "metrics": out_metrics,
        "device": device,
    }
    if trace is not None:
        import xtrace

        w0, w1 = trace.window()
        device["busy_s"] = xtrace.busy(trace)
        device["window_s"] = w1 - w0
        result["breakdown"] = {"device_ops": xtrace.device_ops(trace),
                               "idle_gaps": xtrace.idle_gaps(trace)}
    if args.control:
        result["control"] = cfg["control"]
    result["checks"] = checks
    log("backlog (window tenths): " + " ".join(
        str(backlog(served, t0 + args.seconds * f / 10)) for f in range(1, 11))
        + f"; answered {sum(s.status is not None for s in served)} of {len(served)}")
    gen2 = [d for g, d in pauses.pauses if g == 2]
    log(f"gc in the window: {len(pauses.pauses)} collections, "
        f"{sum(d for _, d in pauses.pauses):.3f} s; full: {len(gen2)}, longest "
        f"{max(gen2, default=0.0):.3f} s")
    extra = {k: v for k, v in judged.items() if k not in limits}
    log(f"compared ({len(sample)} reads): {json.dumps(extra)}")
    log(f"run: {time.perf_counter() - T_START:.1f} s")
    for k, c in checks.items():  # the compared numbers close stderr
        log(f"check {k}: {c['value']:.6g} (limit {c['limit']:.6g})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", default=None,
                    help="requests/s instead of the mix's; a comma-separated "
                         "list sweeps: one window at each rate, the last one "
                         "measured and checked (finding the knee)")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the control in the program's place")
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except BenchError as e:
        log(f"bench: FAIL: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
