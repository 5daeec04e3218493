"""repro.analysis: each checker must report its known-bad fixture and stay
quiet on the fixed version (and on the real tree), plus the runtime
sanitizers and the --sanitize wiring."""
import json
import textwrap
import threading

import numpy as np
import pytest

from repro.analysis import cli, runtime
from repro.analysis import locks as locks_mod
from repro.analysis import prng as prng_mod
from repro.analysis.contracts import ContractCase, KernelContract, Operand
from repro.analysis.jit_cache import JitAudit, audit_one
from repro.analysis.kernel_contract import CONTRACT_MODULES, check_contract
from repro.analysis.report import Finding, build_report

ROOT = cli._default_root()


def prng_codes(src: str, relpath: str = "src/repro/launch/x.py"):
    return [f.code for f in prng_mod.check_source(textwrap.dedent(src),
                                                  relpath)]


# ---------------------------------------------------------------------------
# prng-discipline fixtures
# ---------------------------------------------------------------------------

class TestPrngChecker:
    def test_key_reuse_flagged(self):
        codes = prng_codes("""
            import jax

            def draw(key):
                a = jax.random.uniform(key, (4,))
                b = jax.random.normal(key, (4,))
                return a + b
        """)
        assert "PRNG001" in codes

    def test_consume_then_derive_flagged(self):
        codes = prng_codes("""
            import jax

            def draw(key):
                a = jax.random.uniform(key, (4,))
                k1, k2 = jax.random.split(key)
                return a, k1, k2
        """)
        assert "PRNG001" in codes

    def test_split_then_draw_clean(self):
        codes = prng_codes("""
            import jax

            def draw(key):
                k1, k2 = jax.random.split(key)
                a = jax.random.uniform(k1, (4,))
                b = jax.random.normal(k2, (4,))
                return a + b
        """)
        assert codes == []

    def test_fold_in_chain_clean(self):
        # the trainer's idiom: derive a fresh child per iteration, consume
        # only children
        codes = prng_codes("""
            import jax

            def loop(key, n):
                out = []
                for i in range(n):
                    k = jax.random.fold_in(key, i)
                    out.append(jax.random.uniform(k, ()))
                return out
        """)
        assert codes == []

    def test_split_discard_flagged(self):
        assert "PRNG002" in prng_codes("""
            import jax

            def one(key):
                k, _ = jax.random.split(key)
                return jax.random.uniform(k, ())
        """)
        assert "PRNG002" in prng_codes("""
            import jax

            def one(key):
                return jax.random.uniform(jax.random.split(key, 3)[0], ())
        """)

    def test_double_split_flagged(self):
        codes = prng_codes("""
            import jax

            def fork(key):
                a, b = jax.random.split(key)
                c, d = jax.random.split(key)
                return a, b, c, d
        """)
        assert "PRNG004" in codes

    def test_raw_draw_in_sampling_module_flagged(self):
        src = """
            import jax

            def sample_sweep(key, t):
                return jax.random.uniform(key, (t, 2))
        """
        assert "PRNG003" in prng_codes(src, "src/repro/core/sampler.py")
        # the same draw inside a shared helper is the contract, not a leak
        helper = """
            import jax

            def tile_uniforms(key, t):
                return jax.random.uniform(key, (t, 2))
        """
        assert prng_codes(helper, "src/repro/core/sampler.py") == []
        # and outside the sampling modules raw draws are fine
        assert prng_codes(src, "src/repro/launch/x.py") == []

    def test_real_tree_clean(self):
        findings = prng_mod.run(ROOT)
        assert findings == [], [
            f"{f.path}:{f.line} {f.code} {f.message}" for f in findings]


# ---------------------------------------------------------------------------
# lock-discipline fixtures
# ---------------------------------------------------------------------------

BAD_ENGINE = """
import threading

class Engine:
    def __init__(self):
        self._lock = threading.Lock()
        self._pending = 0

    def submit(self):
        with self._lock:
            self._pending = self._pending + 1

    def leak_write(self):
        self._pending = 0

    def leak_read(self):
        return self._pending
"""

GOOD_ENGINE = """
import threading

class Engine:
    def __init__(self):
        self._lock = threading.Lock()
        self._pending = 0

    def submit(self):
        with self._lock:
            self._pending = self._pending + 1

    def drain(self):
        with self._lock:
            n, self._pending = self._pending, 0
        return n
"""


class TestLockChecker:
    def test_unguarded_accesses_flagged(self):
        found = locks_mod.check_source(BAD_ENGINE, "x.py")
        codes = sorted(f.code for f in found)
        assert codes == ["LD001", "LD002"]
        scopes = {f.scope for f in found}
        assert scopes == {"Engine.leak_write", "Engine.leak_read"}

    def test_guarded_class_clean(self):
        assert locks_mod.check_source(GOOD_ENGINE, "x.py") == []

    def test_closure_inside_lock_not_held(self):
        # a callback built under the lock runs later, unlocked
        src = BAD_ENGINE.replace(
            "    def leak_write(self):\n        self._pending = 0\n",
            "    def leak_write(self):\n"
            "        with self._lock:\n"
            "            cb = lambda: setattr(self, 'x', self._pending)\n"
            "        return cb\n")
        found = locks_mod.check_source(src, "x.py")
        assert "LD002" in {f.code for f in found}

    def test_real_engine_clean(self):
        findings = locks_mod.run(ROOT)
        assert findings == [], [
            f"{f.path}:{f.line} {f.code} {f.message}" for f in findings]


# ---------------------------------------------------------------------------
# kernel-contract: planted violations + the real contracts
# ---------------------------------------------------------------------------

class _Spec:
    """Stand-in for pl.BlockSpec: just block_shape + index_map."""

    def __init__(self, block_shape, index_map):
        self.block_shape = block_shape
        self.index_map = index_map


def _planted(grid, index_map, budget=1 << 20, coverage=("out",)):
    out = Operand("out", (8, 4), np.int32, _Spec((4, 4), index_map))
    return KernelContract(
        kernel="planted", vmem_budget_bytes=budget,
        cases=(ContractCase(name="case", grid=grid, inputs=(),
                            outputs=(out,), coverage=coverage),))


class TestKernelContract:
    def test_index_map_overrun_flagged(self):
        # grid point 2 maps to row block 2 of a 2-block array
        found = check_contract(_planted((3,), lambda i: (i, 0)), "x.py")
        assert [f.code for f in found] == ["KC002"]

    def test_coverage_gap_flagged(self):
        found = check_contract(_planted((1,), lambda i: (i, 0)), "x.py")
        assert [f.code for f in found] == ["KC003"]
        assert "(1, 0)" in found[0].message

    def test_vmem_budget_flagged(self):
        found = check_contract(
            _planted((2,), lambda i: (i, 0), budget=1), "x.py")
        assert "KC001" in [f.code for f in found]

    def test_well_formed_contract_clean(self):
        assert check_contract(_planted((2,), lambda i: (i, 0)), "x.py") == []

    @pytest.mark.parametrize("modname", CONTRACT_MODULES)
    def test_real_contracts_clean(self, modname):
        import importlib
        mod = importlib.import_module(modname)
        found = check_contract(mod.contract(), modname)
        assert found == [], [f"{f.code} [{f.scope}] {f.message}"
                             for f in found]


# ---------------------------------------------------------------------------
# jit-cache: audit_one semantics on synthetic caches
# ---------------------------------------------------------------------------

class TestJitCacheAudit:
    def _audit(self, run, cache_size, budget):
        return JitAudit(name="fake", path="x.py", cache_size=cache_size,
                        run=run, max_compiles=budget)

    def test_budget_overrun_flagged(self):
        state = {"size": 0}

        def run():
            state["size"] = 5  # cold pass compiles 5, repeat compiles 0

        found = audit_one(self._audit(run, lambda: state["size"], budget=2))
        assert [f.code for f in found] == ["JIT001"]

    def test_trace_leak_flagged(self):
        state = {"size": 0}

        def run():
            state["size"] += 1  # every identical pass compiles again

        found = audit_one(self._audit(run, lambda: state["size"], budget=2))
        assert [f.code for f in found] == ["JIT002"]

    def test_unhashable_static_flagged(self):
        def run():
            raise TypeError("unhashable type: 'list'")

        found = audit_one(self._audit(run, lambda: 0, budget=1))
        assert [f.code for f in found] == ["JIT003"]

    def test_within_budget_clean(self):
        state = {"size": 0}

        def run():
            state["size"] = 2

        assert audit_one(self._audit(run, lambda: state["size"],
                                     budget=2)) == []


# ---------------------------------------------------------------------------
# report / baseline / CLI
# ---------------------------------------------------------------------------

def _finding(code="PRNG001", line=10):
    return Finding(checker="prng-discipline", code=code, path="src/x.py",
                   line=line, scope="f", message="m")


class TestReportAndCli:
    def test_fingerprint_is_line_free(self, tmp_path):
        # moving a finding to another line must not invalidate a suppression
        base = tmp_path / "b.json"
        rep = build_report([_finding(line=10)], ["prng-discipline"], base)
        fp = rep["findings"][0]["fingerprint"]
        assert fp == "prng-discipline:PRNG001:src/x.py:f#0"
        rep2 = build_report([_finding(line=99)], ["prng-discipline"], base)
        assert rep2["findings"][0]["fingerprint"] == fp

    def test_baseline_suppression_and_staleness(self, tmp_path):
        base = tmp_path / "b.json"
        rep = build_report([_finding()], ["prng-discipline"], base)
        fp = rep["findings"][0]["fingerprint"]
        base.write_text(json.dumps({
            "schema": "repro-analysis-baseline/v1",
            "suppressions": [{"fingerprint": fp, "reason": "known"},
                             {"fingerprint": "gone:X:y#1", "reason": "old"}],
        }))
        rep = build_report([_finding()], ["prng-discipline"], base)
        # the stale entry gates as an unsuppressible BASE001 finding
        assert rep["summary"] == {"total": 2, "suppressed": 1,
                                  "unsuppressed": 1}
        assert rep["stale_suppressions"] == ["gone:X:y#1"]
        stale_rows = [r for r in rep["findings"] if r["code"] == "BASE001"]
        assert len(stale_rows) == 1
        assert stale_rows[0]["checker"] == "baseline"
        assert not stale_rows[0]["suppressed"]
        assert "gone:X:y#1" in stale_rows[0]["message"]

    def test_cli_fast_checkers_gate_green(self, tmp_path):
        out = tmp_path / "report.json"
        rc = cli.main(["--checks", "prng-discipline", "lock-discipline",
                       "--root", str(ROOT), "--json", str(out)])
        assert rc == 0
        rep = json.loads(out.read_text())
        assert rep["schema"] == "repro-analysis/v1"
        assert rep["checks"] == ["prng-discipline", "lock-discipline"]
        assert rep["summary"]["unsuppressed"] == 0

    def test_cli_update_baseline_roundtrip(self, tmp_path):
        # plant a bad file under a fake root so the checker finds something
        root = tmp_path / "repo"
        (root / "src" / "repro").mkdir(parents=True)
        (root / "src" / "repro" / "bad.py").write_text(textwrap.dedent("""
            import jax

            def f(key):
                a = jax.random.uniform(key, ())
                b = jax.random.uniform(key, ())
                return a + b
        """))
        base = root / "analysis-baseline.json"
        args = ["--checks", "prng-discipline", "--root", str(root),
                "--baseline", str(base)]
        assert cli.main(args) == 1            # unsuppressed -> red
        assert cli.main(args + ["--update-baseline"]) == 0
        assert cli.main(args) == 0            # suppressed by the baseline
        doc = json.loads(base.read_text())
        assert doc["schema"] == "repro-analysis-baseline/v1"
        assert len(doc["suppressions"]) == 1

    def test_committed_baseline_is_empty(self):
        doc = json.loads((ROOT / "analysis-baseline.json").read_text())
        assert doc == {"schema": "repro-analysis-baseline/v1",
                       "suppressions": []}

    def test_base001_stale_baseline_cli_roundtrip(self, tmp_path):
        # fix the finding but keep its suppression -> BASE001 gates red;
        # --update-baseline drops the stale entry -> green again
        root = tmp_path / "repo"
        bad = root / "src" / "repro" / "bad.py"
        bad.parent.mkdir(parents=True)
        bad.write_text(textwrap.dedent("""
            import jax

            def f(key):
                a = jax.random.uniform(key, ())
                b = jax.random.uniform(key, ())
                return a + b
        """))
        base = root / "analysis-baseline.json"
        out = tmp_path / "rep.json"
        args = ["--checks", "prng-discipline", "--root", str(root),
                "--baseline", str(base)]
        assert cli.main(args + ["--update-baseline"]) == 0
        bad.unlink()                       # "fix" the finding
        assert cli.main(args + ["--json", str(out)]) == 1
        rep = json.loads(out.read_text())
        assert [r["code"] for r in rep["findings"]] == ["BASE001"]
        assert cli.main(args + ["--update-baseline"]) == 0
        assert json.loads(base.read_text())["suppressions"] == []
        assert cli.main(args) == 0

    def test_report_timings_and_budget(self, tmp_path):
        out = tmp_path / "rep.json"
        args = ["--checks", "prng-discipline", "lock-discipline",
                "--root", str(ROOT), "--json", str(out)]
        assert cli.main(args + ["--max-seconds", "240"]) == 0
        rep = json.loads(out.read_text())
        assert set(rep["timings"]) == {"prng-discipline", "lock-discipline",
                                       "total"}
        assert all(v >= 0 for v in rep["timings"].values())
        # an exceeded budget fails the run even with zero findings
        assert cli.main(args + ["--max-seconds", "0"]) == 1


# ---------------------------------------------------------------------------
# collective-contract fixtures
# ---------------------------------------------------------------------------

from repro.analysis import collectives as coll_mod  # noqa: E402


def _coll_codes(src: str, contracts, tmp_path):
    mod = tmp_path / "planted.py"
    mod.write_text(textwrap.dedent(src))
    return [f.code for f in coll_mod.scan_module(mod, "src/x.py", contracts)]


class TestCollectiveChecker:
    def test_cc001_axis_mismatch_flagged(self, tmp_path):
        codes = _coll_codes("""
            import jax

            def f(x):
                return jax.lax.psum(x, "rogue_axis")
        """, {"f": frozenset({"ax"})}, tmp_path)
        assert codes == ["CC001"]

    def test_cc001_missing_axis_flagged(self, tmp_path):
        codes = _coll_codes("""
            import jax

            def f(x):
                return jax.lax.psum(x)
        """, {"f": frozenset({"ax"})}, tmp_path)
        assert codes == ["CC001"]

    def test_cc002_undeclared_scope_flagged(self, tmp_path):
        codes = _coll_codes("""
            import jax

            def rogue(x):
                return jax.lax.all_gather(x, "data")
        """, {}, tmp_path)
        assert codes == ["CC002"]

    def test_declared_scope_and_axis_clean(self, tmp_path):
        codes = _coll_codes("""
            import jax

            def f(x, axes):
                i = jax.lax.axis_index("data")
                return jax.lax.psum(x, tuple(axes)) + i
        """, {"f": frozenset({"axes", "data"})}, tmp_path)
        assert codes == []

    def test_cc003_lossy_routing_flagged(self):
        from repro.distributed.partition import route_buckets

        def lossy(owner, payload, num_shards, capacity):
            send, src = route_buckets(owner, payload, num_shards, capacity)
            # drop the first slot of every bucket
            return send, src.at[:, 0].set(owner.shape[0])

        fs = coll_mod.check_route_roundtrip(
            route_fn=lossy, shard_counts=(2,), batches=((4, 16),))
        assert fs and all(f.code == "CC003" for f in fs)
        assert any("lossy" in f.message for f in fs)

    def test_cc003_real_routing_clean(self):
        assert coll_mod.check_route_roundtrip() == []

    def test_cc004_state_spec_drift_flagged(self):
        from jax.sharding import PartitionSpec as P

        from repro.core import trainer as core_trainer

        specs = core_trainer.LDAState(
            z=P(("data", "model")),
            phi_vk=P(("data",)),               # doc-sharded phi: the bug
            phi_sum=P(), iteration=P())
        fs = coll_mod.check_state_spec_table(
            specs, {"tile_word": P(("data", "model"))}, "2d",
            ("data",), ("model",))
        assert fs and all(f.code == "CC004" for f in fs)
        assert any("phi_vk" in f.message and "doc axes" in f.message
                   for f in fs)

    def test_cc004_serving_spec_drift_flagged(self):
        fs = coll_mod.check_shard_map_specs(
            [{0: ("shards",)}, {0: ("shards",)}, {}],
            [{0: ("shards",)}], "shards", "psum")
        assert fs and all(f.code == "CC004" for f in fs)

    def test_cc005_byte_drift_flagged(self):
        fs = coll_mod.check_serving_comm(
            overrides=dict(a2a_bytes=1, psum_bytes=1))
        assert [f.code for f in fs] == ["CC005", "CC005"]
        assert all("bytes" in f.message for f in fs)

    def test_serving_comm_clean(self):
        assert coll_mod.check_serving_comm() == []

    def test_partition_contracts_clean(self):
        assert coll_mod.check_partition_contracts() == []

    def test_real_tree_clean(self):
        assert coll_mod.run(ROOT) == []


# ---------------------------------------------------------------------------
# dtype-flow fixtures
# ---------------------------------------------------------------------------

from repro.analysis import dtypes as dtypes_mod  # noqa: E402


def _dtype_findings(src: str, tmp_path, declared=None):
    mod = tmp_path / "planted_dt.py"
    mod.write_text(textwrap.dedent(src))
    events = dtypes_mod.scan_module(mod)
    return dtypes_mod.apply_declarations(events, "src/x.py", declared or {})


class TestDtypeChecker:
    def test_dt001_narrowing_flagged(self, tmp_path):
        fs, _ = _dtype_findings("""
            import jax.numpy as jnp

            def f(z):
                return z.astype(jnp.int16)
        """, tmp_path)
        assert [f.code for f in fs] == ["DT001"]

    def test_dt001_dynamic_width_flagged(self, tmp_path):
        fs, _ = _dtype_findings("""
            def g(z, ref):
                return z.astype(ref.dtype)

            def h(z, cfg):
                return z.astype(cfg.topic_dtype)
        """, tmp_path)
        assert [f.code for f in fs] == ["DT001", "DT001"]
        assert {f.scope for f in fs} == {"g", "h"}

    def test_dt001_declared_site_clean(self, tmp_path):
        declared = {("src/x.py", "f", "DT001"): "some-witness"}
        fs, matched = _dtype_findings("""
            import jax.numpy as jnp

            def f(z):
                return z.astype(jnp.int16)
        """, tmp_path, declared)
        assert fs == []
        assert matched == set(declared)

    def test_dt001_widening_clean(self, tmp_path):
        fs, _ = _dtype_findings("""
            import jax.numpy as jnp

            def f(z):
                return z.astype(jnp.int32) + z.astype(jnp.float32)
        """, tmp_path)
        assert fs == []

    def test_dt002_downcast_chain_flagged(self, tmp_path):
        fs, _ = _dtype_findings("""
            import jax.numpy as jnp

            def f(x):
                return x.astype(jnp.int64).astype(jnp.int16)
        """, tmp_path)
        assert "DT002" in [f.code for f in fs]

    def test_dt002_fires_even_when_declared(self, tmp_path):
        declared = {("src/x.py", "f", "DT001"): "w",
                    ("src/x.py", "f", "DT002"): "w"}
        fs, _ = _dtype_findings("""
            import jax.numpy as jnp

            def f(x):
                return x.astype(jnp.int64).astype(jnp.int16)
        """, tmp_path, declared)
        assert [f.code for f in fs] == ["DT002"]

    def test_dt003_flattened_index_flagged(self, tmp_path):
        fs, _ = _dtype_findings("""
            def f(b, B, i, arr, c, C):
                k = b * B + i
                return arr[c * C], k
        """, tmp_path)
        assert [f.code for f in fs] == ["DT003", "DT003"]

    def test_dt004_float_scatter_flagged(self, tmp_path):
        fs, _ = _dtype_findings("""
            import jax.numpy as jnp

            def f(i):
                acc = jnp.zeros((4, 4), jnp.float32)
                return acc.at[i].add(1)

            def g(i):
                return jnp.zeros((4, 4), jnp.float32).at[i].add(1)

            def ok(i):
                acc = jnp.zeros((4, 4), jnp.int32)
                return acc.at[i].add(1)
        """, tmp_path)
        assert [f.code for f in fs] == ["DT004", "DT004"]
        assert {f.scope for f in fs} == {"f", "g"}

    def test_witnesses_clear_real_tree(self):
        for code, rel, scope, wid, fn in dtypes_mod.WITNESSES:
            assert fn() == [], f"witness {wid} reported problems"

    def test_real_tree_clean(self):
        assert dtypes_mod.run(ROOT) == []


# ---------------------------------------------------------------------------
# runtime sanitizers + --sanitize wiring
# ---------------------------------------------------------------------------

@pytest.fixture()
def lock_sanitizer():
    runtime.enable_lock_sanitizer(True)
    yield
    runtime.enable_lock_sanitizer(False)


class TestRuntimeSanitizers:
    def test_assert_lock_held_noop_when_disabled(self):
        assert not runtime.lock_sanitizer_enabled()
        runtime.assert_lock_held(threading.Lock())  # free lock, no raise

    def test_assert_lock_held(self, lock_sanitizer):
        lock = threading.Lock()
        with pytest.raises(runtime.LockNotHeldError):
            runtime.assert_lock_held(lock)
        assert not lock.locked()  # the probe releases what it acquired
        with lock:
            runtime.assert_lock_held(lock)

    def test_sanitize_guards_disallow_transfers(self):
        import jax.numpy as jnp

        with runtime.sanitize_guards(False):
            jnp.ones(3) + np.ones(3)  # no-op guard: transfers fine
        x = jnp.ones(3)
        with runtime.sanitize_guards(True):
            x + x  # device-only math is fine
            with pytest.raises(Exception, match="[Dd]isallow"):
                x + np.ones(3)  # implicit host-to-device transfer

    def test_engine_serves_under_sanitize(self):
        import jax.numpy as jnp
        from repro.serve import (EngineConfig, HotSwapModel, InferConfig,
                                 LDAServeEngine, ModelSnapshot)

        V, K = 64, 8
        phi = np.zeros((V, K), np.int32)
        for k in range(K):
            phi[k * 8:(k + 1) * 8, k] = 200
        snap = ModelSnapshot(phi_vk=jnp.asarray(phi),
                             phi_sum=jnp.asarray(phi.sum(0)),
                             alpha=0.1, beta=0.01, num_words_total=V)
        eng = LDAServeEngine(
            HotSwapModel(snap),
            EngineConfig(max_batch=4, max_delay_ms=50.0,
                         length_buckets=(32,),
                         infer=InferConfig(burn_in=2, samples=2),
                         sanitize=True))
        try:
            assert runtime.lock_sanitizer_enabled()
            res = eng.infer([3, 4, 5, 3, 4, 3])
            assert int(res["theta"].argmax()) == 0
        finally:
            eng.stop()
            runtime.enable_lock_sanitizer(False)

    def test_trainer_runs_under_sanitize(self):
        from repro.core import trainer
        from repro.data.synthetic import lda_corpus

        corpus = lda_corpus(num_docs=24, num_words=64, num_topics=4,
                            avg_doc_len=16, seed=3)
        cfg = trainer.LDAConfig(num_topics=4, tile_tokens=64,
                                tiles_per_step=8, seed=3)
        res = trainer.train(corpus, cfg, 2, eval_every=2, sanitize=True)
        assert res.ll_per_token and np.isfinite(res.ll_per_token[-1])

    def test_launchers_expose_sanitize_flag(self):
        from repro.launch import serve_lda

        ap = serve_lda.build_argparser()
        args = ap.parse_args(["--snapshot", "x.npz", "--sanitize"])
        assert args.sanitize
        assert not ap.parse_args(["--snapshot", "x.npz"]).sanitize


# ---------------------------------------------------------------------------
# regressions for the true findings the suite caught
# ---------------------------------------------------------------------------

class TestPrngFixRegressions:
    def test_init_cache_k_v_decorrelated(self):
        # found by prng-discipline: k and v were drawn from the SAME key,
        # making the stand-in prefill caches identical tensors
        import jax
        from repro.configs.archs import smoke
        from repro.models.attention import init_cache

        cfg = smoke("gemma2-27b")
        cache = init_cache(cfg, batch=1, max_len=8, key=jax.random.key(0))
        assert not np.array_equal(np.asarray(cache.k), np.asarray(cache.v))
