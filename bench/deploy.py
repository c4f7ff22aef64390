"""Build the served stack for one configuration, put the seed's weights and
rows in it, and warm every shape the cell's traffic uses.

The stack is the program's own (``repro.launch.serve.build_stack``) with its
defaults for every knob that is not a property of the deployment. What comes
from here: the weights (``weights.py``), the filler rows, the cached corpus,
and the recording of what the fused read program returned to the host.
"""
from __future__ import annotations

import itertools
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

import weights as W
from xtrace import READ

FILL_BLOCK = 4096  # filler rows per upload; one compiled scatter for all
INSERT_BLOCK = 256  # cached prompts per insert_batch (one embed bucket)


@dataclass
class Capture:
    """One call of the fused read program, as the host received it."""

    seq: int
    t0: float
    t1: float
    texts: List[str]
    vecs: np.ndarray  # [n, D]
    # per query, best first: (score, cached prompt or None where the slot's
    # entry at read time is not known, slot)
    cands: List[List[Tuple[float, Optional[str], int]]]
    hit: np.ndarray  # [n]
    generative: np.ndarray  # [n]


UNSURE_S = 1.0  # a slot written from this long before a read on is left out


@dataclass
class ReadRecorder:
    """Wraps ``repro.core.read_path.fused_read`` to keep what each call
    returned, and logs when each slot of the store is written. In the window
    a read costs a reference kept and a timestamp; the join of slots to
    entries (``join``) waits until the window has closed."""

    store: object
    on: bool = False
    tracing: bool = False  # open a profiler span per read, named by its seq
    reads: list = field(default_factory=list)  # (seq, t0, t1, texts, decision)
    writes: list = field(default_factory=list)  # (time, slot)
    seq: object = field(default_factory=itertools.count)

    def install(self):
        from repro.core import read_path

        orig = read_path.fused_read

        def fused_read(bank, embedder, texts, thresholds, specs, vecs=None):
            if not self.on:
                return orig(bank, embedder, texts, thresholds, specs, vecs=vecs)
            seq = next(self.seq)
            span = (jax.profiler.TraceAnnotation(f"{READ}{seq}") if self.tracing
                    else nullcontext())
            t0 = time.perf_counter()
            with span:
                dec = orig(bank, embedder, texts, thresholds, specs, vecs=vecs)
            self.reads.append((seq, t0, time.perf_counter(), texts, dec))
            return dec

        bank = self.store._bank
        note_insert, free_slots = bank.note_insert, bank.free_slots

        def logged_insert(lane, idx, *a, **k):
            self.writes.append((time.perf_counter(), int(idx)))
            return note_insert(lane, idx, *a, **k)

        def logged_free(lanes, idxs):
            t = time.perf_counter()
            self.writes.extend((t, int(i)) for i in idxs)
            return free_slots(lanes, idxs)

        read_path.fused_read = fused_read
        bank.note_insert, bank.free_slots = logged_insert, logged_free
        self._orig = orig
        return self

    def uninstall(self):
        from repro.core import read_path

        read_path.fused_read = self._orig
        del self.store._bank.note_insert, self.store._bank.free_slots

    def join(self) -> List[Capture]:
        """The window's reads, their slots joined to the entries the store
        holds now. A slot written since shortly before the read may have held
        another entry then: its candidate keeps its score and slot and gets
        no prompt."""
        store = self.store
        last: Dict[int, float] = {}
        for t, i in self.writes:
            last[i] = max(last.get(i, t), t)
        out = []
        for seq, t0, t1, texts, dec in sorted(self.reads, key=lambda r: r[0]):
            cands = []
            for srow, irow in zip(dec.scores[:, 0], dec.idx[:, 0]):
                row = []
                for sc, i in zip(srow.tolist(), irow.tolist()):
                    e = store._entries[i] if i < store.capacity else None
                    if not np.isfinite(sc) or e is None:
                        continue
                    known = last.get(i, -np.inf) < t0 - UNSURE_S
                    row.append((sc, e.query if known else None, i))
                cands.append(row)
            out.append(Capture(seq, t0, t1, list(texts), dec.vecs, cands,
                               dec.hit[:, 0], dec.generative[:, 0]))
        return out

    def rows(self, slots) -> Dict[int, np.ndarray]:
        """The stored rows at ``slots``, read back from the device."""
        slots = sorted(set(slots))
        if not slots:
            return {}
        got = np.asarray(self.store._bank.buf[self.store._lane, np.asarray(slots)])
        return dict(zip(slots, got))


def build(cfg: dict):
    """The program's stack at the configuration's sizes; fails when the
    program's model sizes differ from what the configuration states."""
    from repro.launch.serve import build_stack

    c = cfg
    stack = build_stack(
        cfg["backend"]["arch"], threshold=c["t_s"], t_single=c["t_single"],
        t_combined=c["t_combined"], capacity=c["l1_rows"],
        tier1_rows=c["tier1_rows"],
    )
    dec, enc, m, e = cfg["backend"], cfg["embedder"], stack.cfg, stack.cache.embedder
    have = {
        "backend": (m.num_layers, m.d_model, m.num_heads, m.num_kv_heads, m.d_ff,
                    m.vocab_size),
        "embedder": (e.cfg.num_layers, e.cfg.d_model, e.cfg.num_heads, e.cfg.d_ff,
                     e.cfg.vocab_size),
    }
    want = {
        "backend": (dec["num_hidden_layers"], dec["hidden_size"],
                    dec["num_attention_heads"], dec["num_key_value_heads"],
                    dec["intermediate_size"], dec["vocab_size"]),
        "embedder": (enc["num_hidden_layers"], enc["hidden_size"],
                     enc["num_attention_heads"], enc["intermediate_size"],
                     enc["vocab_size"]),
    }
    if have != want:
        raise RuntimeError(f"the program builds {have}, the configuration states {want}")
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


def fill(stack, seed: int, corpus: List[str], answers: List[str]) -> Dict[str, int]:
    """Filler rows first, then the cached corpus: the first evictions then take
    filler (least recently used), never the working set. Returns the filler's
    text -> row index, for the reference."""
    store = stack.cache.store
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
        stack.cache.insert_batch([corpus[i] for i in part], [answers[i] for i in part])
    jax.block_until_ready(store._bank.buf)
    return n_fill


def warm_reads(stack, corpus: List[str], max_batch: int) -> int:
    """One lookup per (batch bucket, length bucket) the traffic can form,
    with cached prompts, so every read program compiles in set-up and none
    of these lookups changes what the cache holds."""
    from reference import n_tokens

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
            stack.cache.lookup_batch(batch)
            calls += 1
            B *= 2
    return calls


def warm_inserts(stack, seed: int, max_batch: int) -> List[str]:
    """Backfill scatters at every row bucket a miss batch can form. The rows
    are seeded random unit vectors (never near a prompt); returns their
    texts."""
    store = stack.cache.store
    texts = []
    n, start = 1, store.capacity  # filler indices past the fill: fresh rows
    while n <= max_batch:
        rows = np.asarray(W.filler_rows(seed, start, FILL_BLOCK, store.dim))[:n]
        t = [f"filler {start + i}" for i in range(n)]
        store.add_batch(rows, t, [f"filler answer {start + i}" for i in range(n)])
        texts += t
        start += n
        n *= 2
    return texts
