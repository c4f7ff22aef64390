"""The comparison that decides ``correct``: what the timed path produced,
against the plain references in ``reference.py`` and the decoder's file
under ``bench/archs/``.

Numbers compared, each against its limit in the configuration's ``limits``:

- ``read_faults``: window requests whose read the recorder did not see once.
- ``answer_faults``: every served answer, byte for byte: a hit returns the
  answer recorded for the entry it names; a generative hit the template over
  its sources; a generation exactly the tokens asked for; the served class
  agrees with the read program's decision.
- ``embed_err``: the widest distance between the read program's embedding
  and the reference encoder's, over a seeded sample of reads.
- ``score_err``: the widest gap between a score the read program returned and
  the reference score of the same (query, cached entry) pair, on any level.
- ``top4_gap``: the widest amount by which a candidate the program ranked
  j-th on a level lies below the reference's j-th best there, in reference
  scores. The reference's candidates of a level are the entries it held at
  the read: the filled store's from the seed and the window's backfills;
  a hierarchy's L1 from the log of the writes to its slots.
- ``class_faults``: sampled reads whose hit / generative / miss decision
  differs from the reference's walk over the levels (the first level that
  answers wins), where no reference score lies within the ``score_err``
  limit of a threshold it is compared with.
- ``search_err``: the widest gap between a score the read program returned
  and the exact (float64) product of the query it searched with and the row
  read back from the level's slot: the search alone, at the precision the
  configuration states for it.
- ``shard_faults`` (a sharded L2): shards that do not sit on a chip of
  their own.
- ``gen_gap`` (cells with misses): over the tokens the timed path served
  for a seeded sample of the window's generations, the widest amount by
  which the decoder reference's logit for the served token lies below its
  best logit at that step, the prompt teacher-forced with the served tokens.
- ``logit_err`` (cells with misses): the same prompts served again after the
  window through the engine's own jitted prefill and decode, with their
  logits kept: the widest gap between those logits and the reference's, at
  every position.

The control (``--control 1``) puts the references one step below the
configuration's precision in the program's place (``control`` in the
configuration: the encoder with fp8 weights and bfloat16 compute, the search
at ``high``, the decoder with fp8 weights and bfloat16 compute, whose
``gen_gap`` is that of the token it puts first) and goes through the same
numbers and limits.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import reference as R
import weights as W


_TOK = re.compile(r"^t(\d+)$")
PRECISION = {"highest": R.HIGHEST, "high": jax.lax.Precision.HIGH}


@dataclass(slots=True)
class Served:
    """One window request as the client saw it. It holds plain values only,
    no response object, so that the window's records add little for the
    Python collector to walk."""

    prompt: str
    kind: str
    max_tokens: int
    t_submit: float
    t_due: float = 0.0
    t_done: Optional[float] = None
    cost: float = 0.0
    fut: object = None  # until it resolves
    sent: bool = False  # reached the scheduler (not refused at submit)
    status: Optional[str] = None  # CacheResponse.cache_status, or None
    text: Optional[str] = None
    sources: Tuple[Tuple[float, str], ...] = ()
    error: Optional[str] = None


@dataclass
class Known:
    """Everything the cache can hold and what set it there."""

    answers: Dict[str, str]  # cached prompt -> answer
    filler_seed: int
    dynamic: Dict[str, Tuple[float, float]]  # window backfill -> (read end, resolved)


def served_ids(text: str, vocab: int) -> Optional[List[int]]:
    out = []
    for w in (text or "").split(" "):
        m = _TOK.match(w)
        if not m or int(m.group(1)) >= vocab:
            return None
        out.append(int(m.group(1)))
    return out


def known_answer(known: Known, text: str) -> Optional[str]:
    if text.startswith("filler "):
        return "filler answer " + text.split()[1]
    return known.answers.get(text)


def answer_faults(served: List[Served], known: Known, vocab: int,
                  caps: Dict[int, tuple], pool: bool = False) -> List[str]:
    """Every served answer against what the cache was given. ``pool``: the
    cache is a hierarchy, whose host pools candidates across levels."""
    faults = []
    for i, s in enumerate(served):
        if s.status is None:
            continue  # failed or never answered: late, not wrong
        if s.status in ("hit", "tier1", "stale"):
            if not s.sources:
                faults.append(f"{i}: {s.status} without a source")
                continue
            want = known_answer(known, s.sources[0][1])
            if want is None or s.text != want:
                faults.append(f"{i}: {s.status} answer differs from the cached one")
        elif s.status == "generative":
            parts = []
            for sc, q in s.sources:
                a = known_answer(known, q)
                if a is None:
                    break
                parts.append((sc, q, a))
            if len(parts) != len(s.sources) or s.text != R.combine_template(parts):
                faults.append(f"{i}: generative answer is not its sources' template")
        else:  # miss: the backend's generation
            ids = served_ids(s.text, vocab)
            if ids is None or len(ids) != s.max_tokens:
                faults.append(f"{i}: generated {s.text!r:.40} for {s.max_tokens} tokens")
        cap = caps.get(i)
        if cap is not None:
            prog = cap[0].cls[cap[1]]
            served_cls = {"tier1": "miss", "stale": "miss"}.get(s.status, s.status)
            # a hierarchy pools the levels' candidates of a read that no level
            # answered on the host: a generative answer over a miss decision
            pooled = pool and prog == "miss" and served_cls == "generative"
            if prog != served_cls and not pooled:
                faults.append(f"{i}: read decided {prog}, served {s.status}")
    return faults


def match_reads(served: List[Served], calls) -> Tuple[Dict[int, tuple], int]:
    """Pair window requests with the read that served them: each request
    text's reads, in the order they were made, go to its requests in the
    order they were submitted. Returns request -> (call, row) and the number
    of answered requests with no read, plus reads that no request asked
    for."""
    by_text: Dict[str, List[tuple]] = {}
    for c in calls:
        for j, t in enumerate(c.texts):
            by_text.setdefault(t, []).append((c, j))
    # a refused request never reached the scheduler, so it has no read
    order = sorted((i for i in range(len(served)) if served[i].sent),
                   key=lambda i: served[i].t_submit)
    out, faults = {}, 0
    for i in order:
        q = by_text.get(served[i].prompt)
        if q:
            out[i] = q.pop(0)
        elif served[i].status is not None:
            faults += 1
    faults += sum(len(v) for v in by_text.values())
    return out, faults


def _block_top_impl(q, rows, nvalid, k, prec):
    s = jnp.matmul(q, rows.T, precision=prec)
    s = jnp.where(jnp.arange(rows.shape[0])[None] < nvalid, s, -jnp.inf)
    return jax.lax.top_k(s, k)


_block_top = jax.jit(_block_top_impl, static_argnames=("k", "prec"))
FILL_SCAN = 65536


class RefBank:
    """The cache's rows as a reference computes them: the seeded filler rows
    (made again on the device) and the encoder's embedding of every cached
    prompt. Prompt scores are taken in float64 on the host; filler rows are
    scanned on the device in blocks."""

    def __init__(self, enc_w, enc: dict, known: Known, n_fill: int, dim: int,
                 extra_fill: List[int], quant: str = "", prec=R.HIGHEST):
        self.prompts = list(known.answers)
        self.pidx = {t: i for i, t in enumerate(self.prompts)}
        self.P = R.bert_embed(enc_w, self.prompts, enc, quant=quant).astype(np.float64)
        self.prec = prec
        self.seed, self.dim = known.filler_seed, dim
        self.n_fill = n_fill
        self.extra_fill = list(extra_fill)

    def fill_rows(self, idx) -> np.ndarray:
        out = np.zeros((len(idx), self.dim), np.float64)
        for k, i in enumerate(idx):
            blk = (int(i) // 4096) * 4096
            out[k] = np.asarray(W.filler_rows(self.seed, blk, 4096, self.dim))[int(i) - blk]
        return out

    def fill_top(self, q: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """Top-k filler scores per query: [n, k] scores and filler indices."""
        qd = jnp.asarray(q, jnp.float32)
        cs, ci = [], []
        for s in range(0, self.n_fill, FILL_SCAN):
            rows = W.filler_rows(self.seed, s, FILL_SCAN, self.dim)
            sc, ix = _block_top(qd, rows, self.n_fill - s, k=k, prec=self.prec)
            cs.append(np.asarray(sc, np.float64))
            ci.append(np.asarray(ix) + s)
        if self.extra_fill:
            ex = self.fill_rows(self.extra_fill)
            cs.append(q.astype(np.float64) @ ex.T)
            ci.append(np.broadcast_to(np.asarray(self.extra_fill), (len(q), len(ex))))
        cs, ci = np.concatenate(cs, 1), np.concatenate(ci, 1)
        o = np.argsort(-cs, 1, kind="stable")[:, :k]
        return np.take_along_axis(cs, o, 1), np.take_along_axis(ci, o, 1)

    def entry_vec(self, text: str) -> np.ndarray:
        if text.startswith("filler "):
            return self.fill_rows([int(text.split()[1])])[0]
        return self.P[self.pidx[text]]


def decide_walk(levels: List[np.ndarray], t_s: float, t1: float, tc: float,
                k: int) -> str:
    """The hierarchy's decision from each level's best-first scores: the
    first level (L1, then L2) that answers, or a miss."""
    for s in levels:
        d = R.decide(s, t_s, t1, tc, k)
        if d != "miss":
            return d
    return "miss"


def _top(scores: np.ndarray, k: int) -> np.ndarray:
    return np.sort(scores)[::-1][:k]


def read_numbers(caps: Dict[int, tuple], sample: List[int], ref_bank: RefBank,
                 qref: np.ndarray, qprog: Dict[int, np.ndarray],
                 progcands: Dict[int, list], got: Dict[int, str],
                 known: Known, cache: dict, delta: float,
                 l1: Optional[Dict[int, tuple]] = None) -> Dict[str, float]:
    """embed_err, score_err, top4_gap and class_faults over ``sample``.
    ``qprog``, ``progcands`` and ``got`` are the program's (or the control's)
    embeddings, ranked candidates per level (score, prompt or None, key, ...)
    and decisions; ``qref`` the reference's embeddings of the same prompts.
    The last level is the filled store (``ref_bank``); in a hierarchy ``l1``
    gives, per sampled request, the L1's entries at its read (row indices
    into ``ref_bank.l1_vecs``) and whether all of them are known."""
    k = cache["max_sources"]
    t_s, t1, tc = cache["t_s"], cache["t_single"], cache["t_combined"]
    embed_err = score_err = top4_gap = 0.0
    e_sum = s_sum = 0.0
    s_n = 0
    class_faults = 0
    fs, fi = ref_bank.fill_top(qref, k)
    sp = qref.astype(np.float64) @ ref_bank.P.T  # [n, prompts]
    for n, i in enumerate(sample):
        v = qprog[i] / max(np.linalg.norm(qprog[i]), 1e-12)
        e = float(np.linalg.norm(v - qref[n]))
        embed_err = max(embed_err, e)
        e_sum += e
        c_read = caps[i][0]
        # which window backfills this read could have seen
        ok = np.ones(len(ref_bank.prompts), bool)
        cand_txt = {c[1] for c in progcands[i][-1]}
        for t, (r_end, done) in known.dynamic.items():
            j = ref_bank.pidx.get(t)
            if j is None:
                continue
            present = done <= c_read.t0
            absent = c_read.t1 <= r_end
            if absent or (not present and t not in cand_txt):
                ok[j] = False
        ps = np.where(ok, sp[n], -np.inf)
        top = np.argsort(-ps, kind="stable")[:k]
        ref_levels = [_top(np.concatenate([ps[top], fs[n]]), k)]
        sure = True
        if l1 is not None:
            rows, sure = l1[i]
            s1 = ref_bank.l1_vecs[rows] @ qref[n].astype(np.float64)
            ref_levels.insert(0, _top(s1, k))
        for li, prog in enumerate(progcands[i]):
            for j, c in enumerate(prog[:k]):
                sc, t = c[0], c[1]
                if t is None:
                    continue
                r = float(qref[n] @ ref_bank.entry_vec(t))
                score_err = max(score_err, abs(sc - r))
                s_sum += abs(sc - r)
                s_n += 1
                if sure or li == len(progcands[i]) - 1:
                    gap = ref_levels[li][j] if j < len(ref_levels[li]) else -np.inf
                    top4_gap = max(top4_gap, float(gap - r))
        want = decide_walk(ref_levels, t_s, t1, tc, k)
        if (want != got[i] and sure
                and not any(R.ambiguous(s, t_s, t1, tc, k, delta) for s in ref_levels)):
            class_faults += 1
    return {"embed_err": embed_err, "score_err": score_err, "top4_gap": top4_gap,
            "class_faults": float(class_faults),
            "embed_err_mean": e_sum / max(len(sample), 1),
            "score_err_mean": s_sum / max(s_n, 1)}


def search_err(cands: Dict[int, list], q: Dict[int, np.ndarray], row) -> float:
    """The widest gap between a candidate's score and the float64 product of
    the query ``q[i]`` the search used and the candidate's row,
    ``row(level, key)`` for each level's (score, prompt, key, ...): the
    search's own rounding."""
    worst = 0.0
    for i, per in cands.items():
        for li, cs in enumerate(per):
            for c in cs:
                worst = max(worst, abs(c[0] - float(q[i] @ row(li, c[2]))))
    return worst


def control_cands(qc: np.ndarray, bank_c: RefBank, sample: List[int], caps,
                  known: Known, k: int, l1: Optional[Dict[int, tuple]] = None
                  ) -> Dict[int, list]:
    """The control's ranked candidates per level, (score, prompt, prompt):
    its embeddings against its own rows (prompts embedded by the control
    encoder, filler as seeded), scored at the bank's precision, over the
    entries the program's read could see."""
    fs, fi = bank_c.fill_top(qc, k)
    sp = np.asarray(R.scores(bank_c.P.astype(np.float32), qc, bank_c.prec), np.float64)
    s1 = None
    if l1 is not None:
        s1 = np.asarray(R.scores(bank_c.l1_vecs.astype(np.float32), qc, bank_c.prec),
                        np.float64)
    out = {}
    for n, i in enumerate(sample):
        ps = sp[n].copy()
        for t, (r_end, done) in known.dynamic.items():
            j = bank_c.pidx.get(t)
            if j is not None and not done <= caps[i][0].t0:
                ps[j] = -np.inf
        top = np.argsort(-ps, kind="stable")[:k]
        c = [(float(ps[j]), bank_c.prompts[j]) for j in top]
        c += [(float(s), f"filler {int(x)}") for s, x in zip(fs[n], fi[n])]
        levels = [sorted(((sc, t, t) for sc, t in c), key=lambda x: -x[0])[:k]]
        if l1 is not None:
            rows = l1[i][0]
            c1 = [(float(s1[n, r]), bank_c.l1_texts[r]) for r in rows]
            levels.insert(0, sorted(((sc, t, t) for sc, t in c1), key=lambda x: -x[0])[:k])
        out[i] = levels
    return out


def known_cands(cands: Dict[int, list]) -> Dict[int, list]:
    """Candidates whose entry at read time is known and whose row was not
    written again since: the rows read back after the window are theirs."""
    return {i: [[c for c in cs if c[1] is not None and c[3]] for cs in per]
            for i, per in cands.items()}


class Reference:
    """The plain references over the sampled reads: the reference encoder's
    embeddings of their prompts and of every cached prompt, and the seeded
    filler. ``program`` compares the program's reads with them; ``control``
    puts the control in the program's place. In a hierarchy ``l1`` gives
    each sampled request's L1 entries at its read: (texts, all known)."""

    def __init__(self, cfg: dict, enc_w, known: Known, n_fill: int, dim: int,
                 extra_fill: List[int], prompts: List[str],
                 l1: Optional[Dict[int, tuple]] = None):
        self.cfg, self.enc_w, self.known = cfg, enc_w, known
        self.fill = (n_fill, dim, extra_fill)
        self.prompts = prompts
        self.l1_texts = sorted({t for texts, _ in (l1 or {}).values() for t in texts})
        pos = {t: r for r, t in enumerate(self.l1_texts)}
        self.l1 = None if l1 is None else {
            i: (np.asarray([pos[t] for t in texts], np.int64), sure)
            for i, (texts, sure) in l1.items()}
        self.bank = self._bank()
        self.q = R.bert_embed(enc_w, prompts, cfg["embedder"])

    def _bank(self, quant: str = "", prec=R.HIGHEST) -> RefBank:
        b = RefBank(self.enc_w, self.cfg["embedder"], self.known, *self.fill,
                    quant=quant, prec=prec)
        b.l1_texts = self.l1_texts
        b.l1_vecs = (np.stack([b.entry_vec(t) for t in self.l1_texts])
                     if self.l1_texts else np.zeros((0, b.dim)))
        return b

    def _numbers(self, caps, sample, qs, cands, got) -> Dict[str, float]:
        return read_numbers(caps, sample, self.bank, self.q, qs, cands, got, self.known,
                            self.cfg, self.cfg["limits"]["score_err"], self.l1)

    def program(self, caps, sample: List[int], cands: Dict[int, list],
                rows_back: List[Dict[int, np.ndarray]]) -> Dict[str, float]:
        qs = {i: caps[i][0].vecs[caps[i][1]] for i in sample}
        got = {i: caps[i][0].cls[caps[i][1]] for i in sample}
        out = self._numbers(caps, sample, qs, cands, got)
        # the search takes the query made unit again, and the row
        unit = {i: v.astype(np.float64) / np.linalg.norm(v) for i, v in qs.items()}
        out["search_err"] = search_err(
            known_cands(cands), unit,
            lambda li, slot: rows_back[li][slot].astype(np.float64))
        return out

    def control(self, caps, sample: List[int]) -> Dict[str, float]:
        c, ctl = self.cfg, self.cfg["control"]
        qc = R.bert_embed(self.enc_w, self.prompts, c["embedder"], quant=ctl["encoder"])
        bank = self._bank(ctl["encoder"], PRECISION[ctl["search"]])
        cands = control_cands(qc, bank, sample, caps, self.known, c["max_sources"],
                              self.l1)
        got = {i: decide_walk([np.array([x[0] for x in lv]) for lv in cands[i]],
                              c["t_s"], c["t_single"], c["t_combined"], c["max_sources"])
               for i in sample}
        qs = {i: qc[n] for n, i in enumerate(sample)}
        out = self._numbers(caps, sample, qs, cands, got)
        out["search_err"] = search_err(cands, {i: v.astype(np.float64) for i, v in qs.items()},
                                       lambda li, t: bank.entry_vec(t))
        return out


# -- the backend's generations ---------------------------------------------------


GEN_SAMPLE = 32  # generations compared, and the longest one besides
GEN_BATCH = 4  # sequences per reference forward


def pick_generations(served: List[Served], vocab: int, seed: int) -> List[int]:
    """A sample drawn from the seed of the window's generations (misses the
    backend was paid for), with the one of the most tokens in it."""
    gens = [i for i, s in enumerate(served)
            if s.status == "miss" and s.cost > 0 and served_ids(s.text, vocab)]
    if not gens:
        return []
    rng = np.random.default_rng([seed, 0x6E11])
    pick = set(rng.choice(gens, min(GEN_SAMPLE, len(gens)), replace=False).tolist())
    pick.add(max(gens, key=lambda i: served[i].max_tokens))
    return sorted(pick)


def _seq_len(n: int) -> int:
    b = 64
    while b < n:
        b *= 2
    return b


@jax.jit
def _gaps(ref, rows, tokens, valid, has_rows):
    """Over the positions ``valid``: the widest amount by which the
    reference's logit for ``tokens`` lies below its best (where a token is
    -1, for the token that ``rows`` puts first), and the widest gap between
    ``rows`` and the reference's logits where ``has_rows``."""
    pick = jnp.where(tokens < 0, jnp.argmax(rows, -1), tokens)
    gap = jnp.max(ref, -1) - jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
    err = jnp.max(jnp.abs(rows - ref), -1)
    return (jnp.max(jnp.where(valid, gap, 0.0)),
            jnp.max(jnp.where(valid & has_rows[:, None], err, 0.0)))


def gen_numbers(arch, params, dec: dict, seqs: List[tuple], quant: str = "") -> tuple:
    """(gap, err) over ``seqs`` of (prompt ids, tokens, logits rows or None).

    The reference runs once over each prompt followed by its tokens (teacher
    forced); each position that predicts one of the tokens gives ``gap``,
    how far the reference's logit for that token lies below its best, and,
    where the sequence has rows, ``err``, the widest gap between a row and
    the reference's logits there. With ``quant`` the control's forward over
    the same ids gives the rows, and ``gap`` is that of the token the
    control puts first. Returns the widest of each over all positions."""
    worst_gap = worst_err = 0.0
    P = len(seqs[0][0])
    S = _seq_len(max(P + len(t) - 1 for _, t, _ in seqs))
    for b in range(0, len(seqs), GEN_BATCH):
        part = seqs[b:b + GEN_BATCH]
        ids = np.zeros((GEN_BATCH, S), np.int32)
        tok = np.full((GEN_BATCH, S - P + 1), -1 if quant else 0, np.int32)
        valid = np.zeros((GEN_BATCH, S - P + 1), bool)
        has_rows = np.zeros((GEN_BATCH,), bool)
        for j, (prompt, t, _) in enumerate(part):
            ids[j, :P] = prompt
            ids[j, P:P + len(t) - 1] = t[:-1]
            if not quant:
                tok[j, :len(t)] = t
            valid[j, :len(t)] = True
        ref = arch.forward(params, ids, dec)[:, P - 1:]
        if quant:
            rows = arch.forward(params, ids, dec, quant=quant)[:, P - 1:]
            has_rows[:len(part)] = True
        else:
            # every stack has the same operands' count and shapes, so these
            # compile once
            T, zero = S - P + 1, jnp.zeros_like(ref[0, 0])
            per = []
            for j in range(GEN_BATCH):
                r = part[j][2] if j < len(part) else None
                has_rows[j] = r is not None
                r = list(r or [])
                per.append(jnp.stack([x.astype(jnp.float32) for x in r]
                                     + [zero] * (T - len(r))))
            rows = jnp.stack(per)
        gap, err = _gaps(ref, rows, jnp.asarray(tok), jnp.asarray(valid),
                         jnp.asarray(has_rows))
        worst_gap, worst_err = max(worst_gap, float(gap)), max(worst_err, float(err))
        del ref, rows
    return worst_gap, worst_err
