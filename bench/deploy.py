"""Build the served stack for one configuration, put the seed's weights and
rows in it, and warm every shape the cell's traffic uses.

The stack is the program's own (``repro.launch.serve.build_stack``) with its
defaults for every knob that is not a property of the deployment. What comes
from here: the weights (``weights.py``), the filler rows, the cached corpus,
and the recording of what the fused read program returned to the host.
"""
from __future__ import annotations

import itertools
import threading
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

import weights as W
from xtrace import DECODE, READ

FILL_BLOCK = 4096  # filler rows per upload; one compiled scatter for all
INSERT_BLOCK = 256  # cached prompts per insert_batch (one embed bucket)


@dataclass
class Capture:
    """One call of a fused read program, as the host received it."""

    seq: int
    t0: float
    t1: float
    texts: List[str]
    vecs: np.ndarray  # [n, D]
    # per query, per level, best first: (score, cached prompt or None where
    # the slot's entry at read time is not known, slot, whether the slot's
    # row is still the one read: the slot was not written again after)
    cands: List[List[List[Tuple[float, Optional[str], int, bool]]]]
    cls: List[str]  # per query, the program's decision: hit, generative or miss
    level: List[int]  # per query, the level that answered it (0 = L1); the
    # number of levels for a miss
    thread: int = 0  # the thread that made the read


UNSURE_S = 1.0  # another thread's write this close before a read is unsure


@dataclass
class Level:
    """One cache level as the recorder sees it: its store, the bank lane(s)
    its rows live in, and a log of the writes to its slots."""

    name: str
    store: object
    sharded: bool
    initial: list = field(default_factory=list)  # slot -> its entry at install
    writes: Dict[int, list] = field(default_factory=dict)  # slot -> [(t, text, thread)]

    @property
    def bank(self):
        return self.store.bank if self.sharded else self.store._bank

    def slot(self, lane: int, within: int) -> Optional[int]:
        """The level's flat slot of a bank lane's row; None for another
        level's lane in a shared bank."""
        if self.sharded:
            return lane * self.store.cap_local + within
        return within if lane == self.store._lane else None

    def entries(self) -> list:
        """The store's slot -> entry list as it stands (a shallow copy: the
        store replaces an entry on a write, it never changes one)."""
        return list(self.store.payloads if self.sharded else self.store._entries)

    def text(self, entry) -> Optional[str]:
        if entry is None:
            return None
        return entry[0] if self.sharded else entry.query

    def text_now(self, slot: int) -> Optional[str]:
        return self.text((self.store.payloads if self.sharded else self.store._entries)[slot])

    def rows(self, slots) -> Dict[int, np.ndarray]:
        slots = sorted(set(slots))
        if not slots:
            return {}
        s = np.asarray(slots)
        if self.sharded:
            c = self.store.cap_local
            got = np.asarray(self.bank.buf[s // c, s % c])
        else:
            got = np.asarray(self.bank.buf[self.store._lane, s])
        return dict(zip(slots, got))

    def at(self, slot: int, t0: float, t1: float, thread: int) -> Tuple[Optional[str], bool, bool]:
        """(text at the read, whether it is known, whether the row is still
        the one read) for a read over [t0, t1] on ``thread``. A write on the
        read's own thread before t0 came before the read; another thread's
        write is certain only UNSURE_S before it."""
        log = self.writes.get(slot)
        if not log:
            return self.text(self.initial[slot]), True, True
        text, k = self.text(self.initial[slot]), -1
        for j, (t, tx, th) in enumerate(log):
            if t < t0:
                text, k = tx, j
            else:
                break
        sure = all(not (t0 - UNSURE_S <= t <= t1 and th != thread) and not (t0 <= t <= t1)
                   for t, _, th in log)
        return text, sure, k == len(log) - 1


@dataclass
class ReadRecorder:
    """Wraps the fused read programs (``repro.core.read_path.fused_read``,
    and ``ShardedReadBank.fused_read`` for a sharded hierarchy) to keep what
    each call returned, and logs every write to each level's slots with the
    entry it wrote. In the window a read costs a reference kept and a
    timestamp; the join of slots to entries (``join``) waits until the
    window has closed."""

    levels: List[Level]
    on: bool = False
    tracing: bool = False  # open a profiler span per read, named by its seq
    reads: list = field(default_factory=list)  # (seq, t0, t1, texts, decision, thread)
    seq: object = field(default_factory=itertools.count)

    def _wrap(self, orig):
        def wrapped(*a, **k):
            if not self.on:
                return orig(*a, **k)
            seq = next(self.seq)
            span = (jax.profiler.TraceAnnotation(f"{READ}{seq}") if self.tracing
                    else nullcontext())
            t0 = time.perf_counter()
            with span:
                dec = orig(*a, **k)
            texts = a[2] if len(a) > 2 else k["texts"]
            self.reads.append((seq, t0, time.perf_counter(), texts, dec,
                               threading.get_ident()))
            return dec
        return wrapped

    def install(self):
        from repro.core import read_path
        from repro.distributed.sharded_read import ShardedReadBank

        self._orig = (read_path.fused_read, ShardedReadBank.fused_read)
        read_path.fused_read = self._wrap(self._orig[0])
        orig_sh = self._wrap(self._orig[1])
        ShardedReadBank.fused_read = lambda bank, *a, **k: orig_sh(bank, *a, **k)
        for lv in self.levels:
            lv.initial = lv.entries()
            self._log_writes(lv)
        return self

    def _log_writes(self, lv: Level) -> None:
        bank = lv.bank
        note_insert, free_slots = bank.note_insert, bank.free_slots

        def log(lane, within, freed):
            slot = lv.slot(int(lane), int(within))
            if slot is not None:
                lv.writes.setdefault(slot, []).append(
                    (time.perf_counter(), None if freed else lv.text_now(slot),
                     threading.get_ident()))

        def logged_insert(lane, idx, *a, **k):
            log(lane, idx, False)
            return note_insert(lane, idx, *a, **k)

        def logged_free(lanes, idxs):
            for ln, i in zip(lanes, idxs):
                log(ln, i, True)
            return free_slots(lanes, idxs)

        bank.note_insert, bank.free_slots = logged_insert, logged_free

    def watch_engine(self, engine) -> None:
        """While tracing, open a profiler span around each call of the
        engine's jitted decode step, so the trace's readers can tell its
        module from others of the same name."""
        decode = engine._decode
        steps = itertools.count()

        def traced(*a):
            if not (self.on and self.tracing):
                return decode(*a)
            with jax.profiler.TraceAnnotation(f"{DECODE}{next(steps)}"):
                return decode(*a)

        engine._decode = traced
        self._engine = (engine, decode)

    def uninstall(self):
        from repro.core import read_path
        from repro.distributed.sharded_read import ShardedReadBank

        if getattr(self, "_engine", None) is not None:
            engine, decode = self._engine
            engine._decode = decode
        read_path.fused_read, ShardedReadBank.fused_read = self._orig
        for lv in self.levels:
            for name in ("note_insert", "free_slots"):
                lv.bank.__dict__.pop(name, None)

    def join(self) -> List[Capture]:
        """The window's reads, each candidate's slot joined to the entry it
        held at the read."""
        out = []
        for seq, t0, t1, texts, dec, thread in sorted(self.reads, key=lambda r: r[0]):
            L = dec.scores.shape[1]
            cands, cls, level = [], [], []
            for j in range(len(texts)):
                per = []
                for li, lv in enumerate(self.levels):
                    row = []
                    for sc, i in zip(dec.scores[j, li].tolist(), dec.idx[j, li].tolist()):
                        if not np.isfinite(sc) or not 0 <= i < lv.store.capacity:
                            continue
                        text, sure, stable = lv.at(i, t0, t1, thread)
                        if text is None and sure:
                            continue  # an empty slot: no entry to join
                        row.append((sc, text if sure else None, i, stable and sure))
                    per.append(row)
                cands.append(per)
                w = int(dec.winner[j])
                level.append(min(w, L))
                cls.append("miss" if w >= L else
                           ("generative" if dec.generative[j, w] else "hit"))
            out.append(Capture(seq, t0, t1, list(texts), dec.vecs, cands, cls, level,
                               thread))
        return out

    def present(self, li: int, t0: float, t1: float, thread: int) -> Tuple[List[str], bool]:
        """The entries level ``li`` held over a read, and whether every slot
        is known."""
        lv = self.levels[li]
        got, sure_all = [], True
        for slot in range(lv.store.capacity):
            text, sure, _ = lv.at(slot, t0, t1, thread)
            sure_all &= sure
            if sure and text is not None:
                got.append(text)
        return got, sure_all


def levels_of(stack) -> List[Level]:
    """The stack's cache levels in read order: L1, then a sharded L2."""
    if stack.hierarchy is None:
        return [Level("L1", stack.cache.store, False)]
    return [Level("L1", stack.cache.store, False),
            Level("L2", stack.hierarchy.l2.store, True)]


def build(cfg: dict, arch):
    """The program's stack at the configuration's sizes: an L1 of ``l1_rows``,
    and with ``shards`` a replicated L1 over an L2 of ``l2_rows`` key-sharded
    over that many chips. Fails when the program's model sizes differ from
    what the configuration states."""
    from repro.launch.serve import build_stack

    c = cfg
    stack = build_stack(
        cfg["backend"]["arch"], threshold=c["t_s"], t_single=c["t_single"],
        t_combined=c["t_combined"], capacity=c["l1_rows"],
        tier1_rows=c["tier1_rows"], shards=c.get("shards", 0),
        l2_capacity=c.get("l2_rows", 4096),
    )
    dec, enc, m, e = cfg["backend"], cfg["embedder"], stack.cfg, stack.cache.embedder
    have = {
        "backend": arch.program_sizes(m),
        "embedder": (e.cfg.num_layers, e.cfg.d_model, e.cfg.num_heads, e.cfg.d_ff,
                     e.cfg.vocab_size),
    }
    want = {
        "backend": arch.sizes(dec),
        "embedder": (enc["num_hidden_layers"], enc["hidden_size"],
                     enc["num_attention_heads"], enc["intermediate_size"],
                     enc["vocab_size"]),
    }
    if have != want:
        raise RuntimeError(f"the program builds {have}, the configuration states {want}")
    if stack.hierarchy is not None and stack.hierarchy.l2.store.capacity != c["l2_rows"]:
        raise RuntimeError(f"the program's L2 holds {stack.hierarchy.l2.store.capacity} "
                           f"rows, the configuration states {c['l2_rows']}")
    return stack


def _same_layout(mine, theirs, what: str) -> None:
    a = jax.tree_util.tree_flatten_with_path(mine)[0]
    b = jax.tree_util.tree_flatten_with_path(theirs)[0]
    sa = [(jax.tree_util.keystr(p), x.shape, x.dtype) for p, x in a]
    sb = [(jax.tree_util.keystr(p), x.shape, x.dtype) for p, x in b]
    if sa != sb:
        diff = [(x, y) for x, y in zip(sa, sb) if x != y][:3]
        raise RuntimeError(f"{what}: weight layout differs from the program's: {diff}")


def install_weights(stack, enc_w, dec_w) -> None:
    """Swap the seed's weights in for the ones the stack made itself."""
    emb = stack.cache.embedder
    _same_layout(enc_w, emb.params, "encoder")
    _same_layout(dec_w, stack.engine.params, "decoder")
    emb.params = enc_w
    stack.engine.params = dec_w


def filled_store(stack):
    """The store that set-up fills: the L2 of a hierarchy (the L1 is left to
    the promotions), else the L1."""
    return stack.hierarchy.l2.store if stack.hierarchy is not None else stack.cache.store


def fill(stack, seed: int, corpus: List[str], answers: List[str]) -> int:
    """Filler rows first, then the cached corpus: the first evictions then take
    filler (least recently used), never the working set. Returns the number
    of filler rows, for the reference."""
    store = filled_store(stack)
    n_fill = store.capacity - len(corpus)
    dim = store.dim
    for s in range(0, n_fill, FILL_BLOCK):
        n = min(FILL_BLOCK, n_fill - s)
        rows = np.asarray(W.filler_rows(seed, s, FILL_BLOCK, dim))[:n]
        store.add_batch(rows, [f"filler {s + i}" for i in range(n)],
                        [f"filler answer {s + i}" for i in range(n)])
    order = sorted(range(len(corpus)), key=lambda i: len(corpus[i].split()))
    for s in range(0, len(order), INSERT_BLOCK):
        part = order[s:s + INSERT_BLOCK]
        prompts, ans = [corpus[i] for i in part], [answers[i] for i in part]
        if stack.hierarchy is not None:
            stack.hierarchy.insert_batch(prompts, ans, cache_l1=False)
        else:
            stack.cache.insert_batch(prompts, ans)
    jax.block_until_ready(levels_of(stack)[-1].bank.buf)
    return n_fill


def warm_reads(stack, corpus: List[str], max_batch: int) -> int:
    """One lookup per (batch bucket, length bucket) the traffic can form,
    with cached prompts, so every read program compiles in set-up. In a
    single-level cache none of these lookups changes what it holds; in a
    hierarchy they promote their prompts into the L1, as the traffic does."""
    from reference import n_tokens

    target = stack.hierarchy if stack.hierarchy is not None else stack.cache
    by_bucket: Dict[int, List[str]] = {}
    for p in corpus:
        n = n_tokens(p)
        b = 8
        while b < n:
            b *= 2
        by_bucket.setdefault(b, []).append(p)
    calls = 0
    for lb, prompts in sorted(by_bucket.items()):
        shorter = [p for p in corpus if n_tokens(p) <= lb][: max_batch]
        B = 1
        while B <= max_batch:
            batch = [prompts[0]] + [p for p in shorter if p != prompts[0]][: B - 1]
            target.lookup_batch(batch)
            calls += 1
            B *= 2
    return calls


def warm_inserts(stack, seed: int, max_batch: int) -> List[str]:
    """L1 insert scatters (backfills, promotions) at every row bucket a batch
    can form. The rows are seeded random unit vectors (never near a prompt),
    numbered past the filler that set-up made; returns their texts."""
    if stack.hierarchy is not None:
        # the L1 joins the read program's replicated bank first, as the
        # first lookup would: its inserts then compile for that bank
        stack.hierarchy.ensure_sharded_bank()
    store = stack.cache.store
    texts = []
    n, start = 1, filled_store(stack).capacity
    while n <= max_batch:
        rows = np.asarray(W.filler_rows(seed, start, FILL_BLOCK, store.dim))[:n]
        t = [f"filler {start + i}" for i in range(n)]
        store.add_batch(rows, t, [f"filler answer {start + i}" for i in range(n)])
        texts += t
        start += n
        n *= 2
    return texts


class EngineLogits:
    """Captures the logits of the engine's own jitted prefill and decode for
    the requests it serves while installed: per request, one ``[V]`` row for
    each token it produced (the prefill's row predicts the first). The rows
    stay on the device.

    It reaches into the engine's internals (``_decode``, ``_prefill_fn``,
    ``_submit_req``, ``active``, a request's ``slot``), and it takes the
    prefills to run one request at a time in submission order. Each prefill
    is checked against the request it is credited to (its token ids), and
    each request's count of rows against its count of tokens, so an engine
    that works otherwise stops the run instead of crediting logits to the
    wrong request."""

    def __init__(self, engine):
        self.engine = engine
        self.by_rid: Dict[int, list] = {}
        # (rid, token ids) in the order the prefills are to run
        self._order: List[Tuple[int, np.ndarray]] = []

    def __enter__(self):
        eng = self.engine
        decode, prefill_fn = eng._decode, eng._prefill_fn

        def captured_decode(*a):
            logits, cache = decode(*a)
            for req in eng.active.values():
                self.by_rid.setdefault(req.rid, []).append(logits[req.slot])
            return logits, cache

        def captured_prefill_fn(length):
            fn = prefill_fn(length)

            def run(params, tokens, *rest):
                rid, ids = self._order.pop(0)
                if not np.array_equal(np.asarray(tokens).reshape(-1), ids):
                    raise RuntimeError(f"the engine prefilled other tokens than request "
                                       f"{rid}'s, the next one submitted")
                logits, cache = fn(params, tokens, *rest)
                self.by_rid.setdefault(rid, []).append(logits[0])
                return logits, cache
            return run

        self._decode = decode
        eng._decode, eng._prefill_fn = captured_decode, captured_prefill_fn
        return self

    def __exit__(self, *exc):
        self.engine._decode = self._decode
        del self.engine._prefill_fn  # the class's method again
        return False

    def generate(self, prompts: List[np.ndarray], max_tokens: List[int]) -> List[tuple]:
        """Serve ``prompts`` (backend token ids), each asking for its own
        number of tokens, through the engine's continuous batching in one
        pass; returns per prompt (tokens, logits rows)."""
        eng = self.engine
        reqs = [eng._submit_req(p, max_new_tokens=m) for p, m in zip(prompts, max_tokens)]
        self._order = [(r.rid, np.asarray(r.tokens).reshape(-1)) for r in reqs]
        eng.run()
        out = [(list(r.out_tokens), self.by_rid.pop(r.rid, [])) for r in reqs]
        for r, (toks, rows) in zip(reqs, out):
            if len(rows) != len(toks):
                raise RuntimeError(f"request {r.rid}: {len(rows)} logits rows credited "
                                   f"to its {len(toks)} tokens")
        return out
