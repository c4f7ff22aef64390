"""A decoder is a file: ``bench/archs/<model_type>.py``, found by the
configuration's ``backend.model_type``. Its plain reference agrees with the
program's engine, and one added file is all a new decoder needs."""
import json
from pathlib import Path

import jax
import numpy as np
import pytest

import check
import deploy
import reference as R
import weights as W

BENCH = Path(__file__).resolve().parents[1]
FIX = Path(__file__).resolve().parent / "fixtures"
# bf16 weights and activations through 3 layers at width 64, against the
# float32 reference: logits of unit scale agree to a few hundredths (the
# chip's limit at the published widths is in ctr-qwen05b-64k.json)
LOGIT_TOL = 0.1


@pytest.fixture(scope="module")
def tiny():
    from repro.configs import get_config
    from repro.serving.engine import ServingEngine

    dec = json.loads((FIX / "tiny-ctr-qwen.json").read_text())["backend"]
    arch = W.load_arch(BENCH, dec["model_type"])
    m = get_config(dec["arch"], smoke=True)
    params = jax.jit(lambda k: arch.weights(k, dec))(W.seed_key(2**31 + 3))
    return dec, arch, m, ServingEngine(m, params=params, max_batch=4, max_seq=64), params


def test_sizes_match_the_program(tiny):
    dec, arch, m, _, _ = tiny
    assert arch.sizes(dec) == arch.program_sizes(m)
    assert arch.sizes(dict(dec, hidden_size=32)) != arch.program_sizes(m)


def test_qwen2_forward_matches_engine_prefill_and_decode(tiny):
    dec, arch, _, engine, params = tiny
    rng = np.random.default_rng(5)
    prompts = [rng.integers(dec["vocab_size"], size=R.BACKEND_PROMPT_TOKENS).astype(np.int32)
               for _ in range(5)]
    with deploy.EngineLogits(engine) as cap:
        got = cap.generate(prompts, [6, 6, 9, 3, 6])
    assert [len(t) for t, _ in got] == [6, 6, 9, 3, 6]
    assert all(len(rows) == len(t) for t, rows in got)
    assert "_prefill_fn" not in vars(engine)
    seqs = [(p, t, rows) for p, (t, rows) in zip(prompts, got)]
    gap, err = check.gen_numbers(arch, params, dec, seqs)
    assert err < LOGIT_TOL and gap < LOGIT_TOL
    # the control (fp8 weights, bfloat16 compute) lies further off
    _, err_c = check.gen_numbers(arch, params, dec, [(p, t, None) for p, t, _ in seqs],
                                 quant="fp8")
    assert err_c > 3 * err
    # a served token the engine did not put first is far below the best
    t0 = list(got[0][0])
    t0[2] = int(np.argmin(np.asarray(got[0][1][2])))
    gap_bad, _ = check.gen_numbers(arch, params, dec, [(prompts[0], t0, None)])
    assert gap_bad > 1.0


def test_backend_ids_match_the_program():
    from repro.serving.engine import ModelBackend

    class _E:
        class cfg:
            vocab_size, modality = 512, "text"

    mb = ModelBackend("x", _E())
    for text in ["a b c", " ".join(f"w{i}" for i in range(40)), ""]:
        assert R.backend_ids(text, 512) == mb._tokenize(text).tolist()


STUB = '''
"""A stand-in decoder for the test."""
def weights(key, backend):
    return {"w": key}


def forward(params, ids, backend, quant=""):
    return ids


def sizes(backend):
    return (backend["hidden_size"],)


def program_sizes(m):
    return (m.d_model,)
'''


def test_a_decoder_is_found_by_model_type(tmp_path):
    (tmp_path / "bench" / "archs").mkdir(parents=True)
    (tmp_path / "bench" / "archs" / "stubarch.py").write_text(STUB)
    arch = W.load_arch(tmp_path / "bench", "stubarch")
    assert arch.sizes({"hidden_size": 7}) == (7,)
    with pytest.raises(FileNotFoundError):
        W.load_arch(tmp_path / "bench", "qwen2")
    # the shipped configurations name decoders that are there
    for p in (BENCH / "configs").glob("*.json"):
        cfg = json.loads(p.read_text())
        assert W.load_arch(BENCH, cfg["backend"]["model_type"]).sizes(cfg["backend"])


def test_engine_logits_refuse_a_prefill_out_of_order(tiny):
    """A prefill that runs for another request than the next one submitted
    stops the capture instead of crediting its logits to the wrong request."""
    from repro.serving.engine import ServingEngine

    dec, _, m, _, params = tiny
    engine = ServingEngine(m, params=params, max_batch=4, max_seq=64)

    def last_first():
        with engine._lock:
            return engine.pending.pop() if engine.pending else None

    engine._pop_pending = last_first
    rng = np.random.default_rng(7)
    prompts = [rng.integers(dec["vocab_size"], size=R.BACKEND_PROMPT_TOKENS).astype(np.int32)
               for _ in range(2)]
    with deploy.EngineLogits(engine) as cap, pytest.raises(RuntimeError, match="prefilled"):
        cap.generate(prompts, [3, 3])
