"""chip_smoke.py: its phases at a tiny size on the CPU (Pallas kernels in
interpret mode), and the script itself refusing a CPU backend."""
from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def trained(smoke):
    from repro.data.synthetic import lda_corpus
    corpus = lda_corpus(num_docs=24, num_words=60, num_topics=4,
                        avg_doc_len=30, seed=3)
    return corpus, smoke.train_phase(corpus, 128, 3)


def test_train_phase_pallas_matches_sq(trained):
    corpus, out = trained
    for sampler in ("pallas", "sq"):
        lls = out[sampler].ll_per_token
        assert len(lls) == 3 and lls[-1] > lls[0]
    # interpret mode on the CPU: the kernel draws exactly like the sq scan
    assert out["agree_first"] == 1.0 and out["agree_last"] == 1.0
    np.testing.assert_array_equal(np.asarray(out["pallas"].state.z),
                                  np.asarray(out["sq"].state.z))


def test_serve_phase_pallas_matches_xla(smoke, trained, tmp_path):
    from repro.serve.snapshot import load_snapshot
    corpus, out = trained
    res = out["pallas"]
    snap = load_snapshot(smoke.publish(res.state, corpus, res.cfg,
                                       str(tmp_path)))
    docs = [d[:40] for d in smoke.serving_docs(corpus.num_words, 6, 1)]
    got = smoke.serve(snap, docs, "pallas", 0, 64)
    want = smoke.serve(snap, docs, "xla", 0, 64)
    smoke.same_results(got, want, "pallas vs xla")
    assert len(got) == 6 and all(r["theta"].shape == (128,) for r in got)


def test_same_results_catches_a_difference(smoke):
    a = [dict(theta=np.ones(4), top_topics=np.arange(2))]
    b = [dict(theta=np.ones(4) * 2, top_topics=np.arange(2))]
    with pytest.raises(smoke.SmokeFailure):
        smoke.same_results(a, b, "planted")


def test_train_phase_rejects_non_improving_ll(smoke, monkeypatch):
    import repro.train as train_mod
    from repro.data.synthetic import lda_corpus

    class Flat:
        ll_per_token = [-7.0, -7.5]
        tokens_per_sec = [1.0, 1.0]
        compile_sec = 0.0

    monkeypatch.setattr(train_mod, "fit", lambda *a, **k: Flat())
    corpus = lda_corpus(num_docs=4, num_words=10, num_topics=2,
                        avg_doc_len=5, seed=0)
    with pytest.raises(smoke.SmokeFailure, match="did not improve"):
        smoke.train_phase(corpus, 8, 2)


def _run_script(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_script_refuses_cpu_backend():
    out = _run_script(ROOT, ROOT / "chip_smoke.py")
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


def test_script_alone_fails(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(ROOT / "chip_smoke.py", lone)
    out = _run_script(tmp_path, lone)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_scale_below_full_width_refused(smoke, capsys):
    """A scale that would cut V below NYTimes' 101,636 is refused up front."""
    with pytest.raises(SystemExit) as e:
        smoke.main(["--scale", "0.01"])
    assert e.value.code != 0
    assert '"ok": true' not in capsys.readouterr().out


def test_corpus_width_checked(smoke, monkeypatch):
    from repro.configs import lda_nytimes
    from repro.data.synthetic import lda_corpus

    narrow = lda_corpus(num_docs=4, num_words=10, num_topics=2,
                        avg_doc_len=5, seed=0)
    monkeypatch.setattr(lda_nytimes, "scaled", lambda *a: narrow)
    with pytest.raises(smoke.SmokeFailure, match="not NYTimes"):
        smoke.nytimes_corpus(0.05, 0, 1)
