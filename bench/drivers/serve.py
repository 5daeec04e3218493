"""Serving cells: an open loop of planted-model documents through
``LDAServeEngine``.

Set-up builds the snapshot, the planted model's expected topic-word
counts, on the device from the seed (``generator.expected_phi``), starts
the engine the configuration describes, and runs every (batch, length)
bucket the engine can form once through ``infer.fold_in_request``, the
function its batches call, and a few requests through the engine itself.

The traffic file fixes the offered rate.  A run offers
``rate * seconds`` documents on one schedule for every seed: the
inter-arrival gaps come from the traffic's ``gap_seed`` and the lengths
from the configuration, in a fixed order, and the seed draws the words.
So every seed offers the same work at the same moments (an order drawn
from the seed moved whole bursts of long documents together, and the
tail with them, far more than the runs of one seed differ).  One thread submits each document at its due
time on an absolute schedule; a request's latency runs from its due time
to its result, so a stalled submitter shows up in the latency of the
requests behind it.  A request that fails, or is not answered within a
minute of the last due time, counts as missing and is given the time the
run waited for it.

The check folds in a sample of the answered documents, drawn from the
seed and always holding the longest, with ``bench.reference``, on the same
randoms the engine drew: each executed batch takes the next seed of the
engine's generator, and the engine's own ``pack`` spans give each batch's
(B, L) and size in order, so each request's batch seed and slot follow
from the order it was submitted in.
"""
from __future__ import annotations

import functools
import time

import numpy as np

from bench import generator, reference

CHECK_REQUESTS = 64     # answered requests folded in by the reference
WAIT_S = 60.0           # how long past the last due time a request may take
WARM_ENGINE_DOCS = 64   # requests through the engine during set-up


def engine_config(cfg: dict):
    from repro.serve.engine import EngineConfig
    from repro.serve.infer import InferConfig

    s = dict(cfg["serve"])
    infer = InferConfig(**s.pop("infer"))
    s["length_buckets"] = tuple(s["length_buckets"])
    return EngineConfig(infer=infer, **s)


def schedule(traffic: dict, seconds: float):
    """Due times (s from the window's start) of ``rate * seconds``
    requests with Poisson gaps drawn from the traffic's ``gap_seed``."""
    rate = float(traffic["rate_docs_per_s"])
    n = max(1, int(round(rate * seconds)))
    return np.cumsum(generator.rng_for(int(traffic["gap_seed"]), n)
                     .exponential(1.0 / rate, n))


def engine_seed(seed: int) -> int:
    return int(generator.rng_for(seed, 6).integers(2**31))


def setup(ctx):
    import jax

    from repro.obs import Observability
    from repro.serve import infer
    from repro.serve.engine import LDAServeEngine
    from repro.serve.snapshot import HotSwapModel, ModelSnapshot

    cfg = ctx.config
    V, K = int(cfg["num_words"]), int(cfg["num_topics"])
    with ctx.span("bench.snapshot"):
        phi, phi_sum = generator.expected_phi(
            cfg, ctx.seed, int(cfg["published"]["num_tokens"]))
        snap = ModelSnapshot(phi_vk=phi, phi_sum=phi_sum,
                             alpha=float(cfg["alpha"]),
                             beta=float(cfg["beta"]), num_words_total=V)
    ecfg = engine_config(cfg)
    due = schedule(ctx.traffic, ctx.seconds)
    with ctx.span("bench.generate"):
        docs = generator.request_docs(cfg, len(due), ctx.seed)
        warm = generator.request_docs(cfg, WARM_ENGINE_DOCS, ctx.seed + 1)
    with ctx.span("bench.warmup"):
        longest = sorted(warm, key=len)[-1]
        for L in ecfg.length_buckets:
            for B in ecfg.batch_buckets():
                buf = jax.device_put(infer.pack_request_buffer(
                    [longest[:L]] * B, B, L, 0))
                res = infer.fold_in_request(snap, buf, ecfg.infer)
            res.theta.block_until_ready()
        obs = Observability.default(trace=True, annotate=ctx.trace,
                                    max_events=1 << 20)
        eseed = engine_seed(ctx.seed)
        engine = LDAServeEngine(HotSwapModel(snap), ecfg, seed=eseed,
                                obs=obs)
        engine.infer_many(warm, timeout=600.0)
    ctx.log(f"snapshot phi {tuple(phi.shape)}; {len(due)} requests at "
            f"{ctx.traffic['rate_docs_per_s']} docs/s; lengths "
            f"{min(map(len, docs))}-{max(map(len, docs))}")
    return dict(cfg=cfg, ecfg=ecfg, snap=snap, engine=engine, obs=obs,
                docs=docs, warm=warm, due=due, engine_seed=eseed, V=V, K=K)


def counters(engine) -> dict:
    reg = engine.obs.registry
    wait = reg.histogram("repro_serve_queue_wait_ms")
    return dict(requests=reg.counter("repro_serve_requests_total").value,
                batches=reg.counter("repro_serve_batches_total").value,
                queue_wait_ms_sum=wait.sum, queue_wait_n=wait.count)


def window(st, ctx, seconds: float) -> dict:
    engine, docs, due = st["engine"], st["docs"], st["due"]
    before = counters(engine)
    reqs, late = [], np.zeros(len(due))
    t0 = time.perf_counter() + 0.01
    with ctx.span("serve.open_loop"):
        for i, d in enumerate(docs):
            now = time.perf_counter()
            if t0 + due[i] > now:
                time.sleep(t0 + due[i] - now)
            late[i] = time.perf_counter() - (t0 + due[i])
            reqs.append(engine.submit(d))
        give_up = t0 + due[-1] + WAIT_S
        for r in reqs:
            r.event.wait(max(give_up - time.perf_counter(), 0.0))
    done = np.full(len(reqs), np.nan)
    for i, r in enumerate(reqs):
        res = r.result
        if res is not None and "theta" in res:
            done[i] = r.t_submit + res["latency_ms"] / 1e3
    ok = ~np.isnan(done)
    latency = np.where(ok, done - (t0 + due), give_up - (t0 + due))
    t_end = float(np.nanmax(done)) if ok.any() else give_up
    after = counters(engine)
    st.update(reqs=reqs)
    ctx.log(f"generator lateness: mean {late.mean() * 1e3:.3f} ms, max "
            f"{late.max() * 1e3:.3f} ms")
    d = {k: after[k] - before[k] for k in after}
    return dict(
        metrics={"serve_p95_ms": float(np.percentile(latency, 95)) * 1e3,
                 "serve_docs_per_s": float(ok.sum()) / (t_end - t0)},
        attempted=len(reqs), failed=int((~ok).sum()), window_s=t_end - t0,
        counters=d, batches=d["batches"], late_max_s=float(late.max()))


def release(st) -> None:
    st["engine"].stop()
    events = st["obs"].tracer.to_chrome()["traceEvents"]
    st["packs"] = [e["args"] for e in events if e.get("name") == "pack"]
    snap = st.pop("snap")
    st["phi_sum"] = np.asarray(snap.phi_sum)
    st["phi"] = snap.phi_vk
    del snap
    st.pop("engine")


def batches_of(st) -> list[tuple[int, int, int, int]]:
    """(batch seed, B, L, slot) of every request, in submission order:
    warm-up requests first, then the window's."""
    rng = np.random.default_rng(st["engine_seed"])
    out = []
    for p in st["packs"]:
        s = int(rng.integers(2**31))
        out += [(s, int(p["B"]), int(p["L"]), i) for i in range(int(p["n"]))]
    return out


@functools.lru_cache(maxsize=None)
def _randoms_fn(B: int, L: int, K: int, sweeps: int):
    import jax
    import jax.numpy as jnp

    def draw(seed):
        k_init, k_sweeps = jax.random.split(jax.random.key(seed))
        z0 = jax.random.randint(k_init, (B, L), 0, K, jnp.int32)
        keys = jax.random.split(k_sweeps, sweeps)
        u = jax.vmap(lambda k: jax.random.uniform(
            k, (B, L, 2), jnp.float32))(keys)
        return z0, u

    return jax.jit(draw)


def randoms(seed: int, B: int, L: int, K: int, sweeps: int):
    """The fold-in's randoms of a batch: initial topics (B, L) and
    uniforms (sweeps, B, L, 2), drawn as the engine draws them, on the
    host's CPU device (threefry gives the same bits on every backend, and
    a small program compiles there in a fraction of the time)."""
    import jax

    with jax.default_device(jax.devices("cpu")[0]):
        z0, u = _randoms_fn(B, L, K, sweeps)(np.int32(seed))
    return np.asarray(z0), np.asarray(u)


def check_sample(st, seed: int) -> list[int]:
    """Indices of answered window requests to fold in: a sample drawn from
    the seed and the longest answered request."""
    reqs = st["reqs"]
    ok = [i for i, r in enumerate(reqs)
          if r.result is not None and "theta" in r.result]
    if not ok:
        return []
    pick = generator.rng_for(seed, 8).choice(
        ok, min(CHECK_REQUESTS, len(ok)), replace=False)
    longest = max(ok, key=lambda i: len(reqs[i].tokens))
    return sorted(set(int(i) for i in pick) | {longest})


def reference_answers(st, idx: list[int], dtype) -> list[np.ndarray]:
    """The reference's (K,) sum of kept-sweep topic counts of each request."""
    cfg, ecfg = st["cfg"], st["ecfg"]
    ic = ecfg.infer
    sweeps = ic.burn_in + ic.samples
    placed = batches_of(st)[len(st["warm"]):]
    out, cache = [], {}
    phi = st["phi"]
    for i in idx:
        s, B, L, slot = placed[i]
        if (s, B, L) not in cache:
            cache = {(s, B, L): randoms(s, B, L, st["K"], sweeps)}
        z0, u = cache[(s, B, L)]
        toks = st["reqs"][i].tokens
        n = len(toks)
        rows = np.asarray(phi[np.asarray(toks)])
        out.append(reference.fold_in(
            rows, st["phi_sum"], z0[slot, :n], u[:, slot, :n],
            float(cfg["alpha"]), float(cfg["beta"]), st["V"], ic.burn_in,
            ic.samples, dtype))
    return out


def served_counts(st, i: int) -> np.ndarray | None:
    """The kept-sweep topic-count sum the served theta encodes, or None
    when theta is not such a sum: theta = (tsum / samples + alpha) / norm."""
    cfg, ic = st["cfg"], st["ecfg"].infer
    r = st["reqs"][i]
    n, K, a = len(r.tokens), st["K"], float(cfg["alpha"])
    theta = np.asarray(r.result["theta"], np.float64)
    t = ic.samples * (theta * (n + K * a) - a)
    tsum = np.rint(t)
    if np.abs(t - tsum).max() > 0.05 or tsum.sum() != ic.samples * n:
        return None
    return tsum.astype(np.int64)


def differs(st, i: int, tsum_ref: np.ndarray) -> bool:
    got = served_counts(st, i)
    if got is None or not np.array_equal(got, tsum_ref):
        return True
    kk = len(st["reqs"][i].result["top_topics"])
    order = np.lexsort((np.arange(len(tsum_ref)), -tsum_ref))[:kk]
    return not np.array_equal(order, np.asarray(
        st["reqs"][i].result["top_topics"]))


def check(st, ctx) -> list[tuple[str, float]]:
    reqs = st["reqs"]
    unanswered = sum(1 for r in reqs
                     if r.result is None or "theta" not in r.result)
    idx = check_sample(st, ctx.seed)
    want = reference_answers(st, idx, np.float64)
    bad = sum(differs(st, i, t) for i, t in zip(idx, want))
    return [("unanswered", unanswered),
            ("answer_mismatch_share", bad / max(len(idx), 1))]


def control(st, ctx) -> list[tuple[str, float]]:
    """The control: the reference in bfloat16 in the program's place."""
    import ml_dtypes

    idx = check_sample(st, ctx.seed)
    want = reference_answers(st, idx, np.float64)
    low = reference_answers(st, idx, ml_dtypes.bfloat16)
    bad = sum(not np.array_equal(a, b) for a, b in zip(want, low))
    return [("answer_mismatch_share", bad / max(len(idx), 1))]


def work_counts(st) -> dict:
    return {}


def hlo_texts(st) -> list[str]:
    """None: the window runs one fold-in program per (batch, length)
    bucket, with clashing instruction names; ops that carry no op path of
    their own stay outside every scope."""
    return []
