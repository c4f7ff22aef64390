"""The comparison that decides ``correct``: what the timed path produced,
against the plain references in ``reference.py``.

Numbers compared, each against its limit in ``limits.json``:

- ``read_faults``: window requests whose read the recorder did not see once.
- ``answer_faults``: every served answer, byte for byte: a hit returns the
  answer recorded for the entry it names; a generative hit the template over
  its sources; a generation exactly the tokens asked for; the served class
  agrees with the read program's decision.
- ``embed_err``: the widest distance between the read program's embedding
  and the reference encoder's, over a seeded sample of reads.
- ``score_err``: the widest gap between a score the read program returned and
  the reference score of the same (query, cached entry) pair.
- ``top4_gap``: the widest amount by which a candidate the program ranked
  j-th lies below the reference's j-th best, in reference scores.
- ``class_faults``: sampled reads whose hit / generative / miss decision
  differs from the reference decide, where no reference score lies within
  the ``score_err`` limit of a threshold it is compared with.
- ``search_err``: the widest gap between a score the read program returned
  and the exact (float64) product of the query it searched with and the row
  read back from the cache's slot: the search alone, at the precision the
  configuration states for it.

The control (``--control 1``) puts the references one step below the
configuration's precision in the program's place (``control`` in the
configuration: the encoder with fp8 weights and bfloat16 compute, the search
at ``high``) and goes through the same numbers and limits.
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
                  caps: Dict[int, tuple]) -> List[str]:
    """Every served answer against what the cache was given."""
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
            prog = "generative" if cap[3] else ("hit" if cap[2] else "miss")
            served_cls = {"tier1": "miss", "stale": "miss"}.get(s.status, s.status)
            if prog != served_cls:
                faults.append(f"{i}: read decided {prog}, served {s.status}")
    return faults


def match_reads(served: List[Served], calls) -> Tuple[Dict[int, tuple], int]:
    """Pair window requests with the read that served them: each request
    text's reads, in the order they were made, go to its requests in the
    order they were submitted. Returns request -> (call, row, hit,
    generative) and the number of answered requests with no read, plus reads
    that no request asked for."""
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
            c, j = q.pop(0)
            out[i] = (c, j, bool(c.hit[j]), bool(c.generative[j]))
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


def read_numbers(caps: Dict[int, tuple], sample: List[int], ref_bank: RefBank,
                 qref: np.ndarray, qprog: Dict[int, np.ndarray],
                 progcands: Dict[int, list], got: Dict[int, str],
                 known: Known, cache: dict, delta: float) -> Dict[str, float]:
    """embed_err, score_err, top4_gap and class_faults over ``sample``.
    ``qprog``, ``progcands`` and ``got`` are the program's (or the control's)
    embeddings, ranked candidates (score, prompt or None, key) and decisions;
    ``qref`` the reference's embeddings of the same prompts."""
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
        cand_txt = {t for _, t, _ in progcands[i]}
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
        ref_s = np.concatenate([ps[top], fs[n]])
        ref_sorted = np.sort(ref_s)[::-1][:k]
        prog = progcands[i]
        for j, (sc, t, _) in enumerate(prog[:k]):
            if t is None:
                continue
            r = float(qref[n] @ ref_bank.entry_vec(t))
            score_err = max(score_err, abs(sc - r))
            s_sum += abs(sc - r)
            s_n += 1
            top4_gap = max(top4_gap, float(ref_sorted[j] - r))
        want = R.decide(ref_sorted, t_s, t1, tc, k)
        if want != got[i] and not R.ambiguous(ref_sorted, t_s, t1, tc, k, delta):
            class_faults += 1
    return {"embed_err": embed_err, "score_err": score_err, "top4_gap": top4_gap,
            "class_faults": float(class_faults),
            "embed_err_mean": e_sum / max(len(sample), 1),
            "score_err_mean": s_sum / max(s_n, 1)}


def search_err(cands: Dict[int, list], q: Dict[int, np.ndarray], row) -> float:
    """The widest gap between a candidate's score and the float64 product of
    the query ``q[i]`` the search used and the candidate's row,
    ``row(key)`` for the candidate's (score, prompt, key): the search's own
    rounding."""
    worst = 0.0
    for i, cs in cands.items():
        for sc, _, key in cs:
            worst = max(worst, abs(sc - float(q[i] @ row(key))))
    return worst


def control_cands(qc: np.ndarray, bank_c: RefBank, sample: List[int], caps,
                  known: Known, k: int) -> Dict[int, List[Tuple[float, str, str]]]:
    """The control's ranked candidates, (score, prompt, prompt): its
    embeddings against its own rows (prompts embedded by the control
    encoder, filler as seeded), scored at the bank's precision, over the
    entries the program's read could see."""
    fs, fi = bank_c.fill_top(qc, k)
    sp = np.asarray(R.scores(bank_c.P.astype(np.float32), qc, bank_c.prec), np.float64)
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
        c = [(sc, t, t) for sc, t in c]
        out[i] = sorted(c, key=lambda x: -x[0])[:k]
    return out


def known_cands(cands: Dict[int, list]) -> Dict[int, list]:
    """Candidates whose entry at read time is known."""
    return {i: [c for c in cs if c[1] is not None] for i, cs in cands.items()}


class Reference:
    """The plain references over the sampled reads: the reference encoder's
    embeddings of their prompts and of every cached prompt, and the seeded
    filler. ``program`` compares the program's reads with them; ``control``
    puts the control in the program's place."""

    def __init__(self, cfg: dict, enc_w, known: Known, n_fill: int, dim: int,
                 extra_fill: List[int], prompts: List[str]):
        self.cfg, self.enc_w, self.known = cfg, enc_w, known
        self.fill = (n_fill, dim, extra_fill)
        self.prompts = prompts
        self.bank = RefBank(enc_w, cfg["embedder"], known, n_fill, dim, extra_fill)
        self.q = R.bert_embed(enc_w, prompts, cfg["embedder"])

    def _numbers(self, caps, sample, qs, cands, got) -> Dict[str, float]:
        return read_numbers(caps, sample, self.bank, self.q, qs, cands, got, self.known,
                            self.cfg, self.cfg["limits"]["score_err"])

    def program(self, caps, sample: List[int], cands: Dict[int, list],
                rows_back: Dict[int, np.ndarray]) -> Dict[str, float]:
        qs = {i: caps[i][0].vecs[caps[i][1]] for i in sample}
        got = {i: "generative" if caps[i][3] else ("hit" if caps[i][2] else "miss")
               for i in sample}
        out = self._numbers(caps, sample, qs, cands, got)
        # the kernel searches with the query made unit again, and the row
        unit = {i: v.astype(np.float64) / np.linalg.norm(v) for i, v in qs.items()}
        out["search_err"] = search_err(known_cands(cands), unit,
                                       lambda slot: rows_back[slot].astype(np.float64))
        return out

    def control(self, caps, sample: List[int]) -> Dict[str, float]:
        c, ctl = self.cfg, self.cfg["control"]
        enc = c["embedder"]
        qc = R.bert_embed(self.enc_w, self.prompts, enc, quant=ctl["encoder"])
        bank = RefBank(self.enc_w, enc, self.known, *self.fill, quant=ctl["encoder"],
                       prec=PRECISION[ctl["search"]])
        cands = control_cands(qc, bank, sample, caps, self.known, c["max_sources"])
        got = {i: R.decide(np.array([x for x, _, _ in cands[i]]), c["t_s"], c["t_single"],
                           c["t_combined"], c["max_sources"]) for i in sample}
        qs = {i: qc[n] for n, i in enumerate(sample)}
        out = self._numbers(caps, sample, qs, cands, got)
        out["search_err"] = search_err(cands, {i: v.astype(np.float64) for i, v in qs.items()},
                                       bank.entry_vec)
        return out
