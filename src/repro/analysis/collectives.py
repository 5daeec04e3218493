"""Collective-contract checker (CC001-CC005).

Every ``jax.lax`` collective in the tree must run under a shard_map whose
mesh actually binds the axis it names — a mismatch is invisible on a
single host (tests run tiny meshes where every axis exists) and explodes
only at scale.  This checker pins the contract two ways:

*  **Declared** (AST): ``SCOPE_CONTRACTS`` lists, per module, the dotted
   scopes allowed to issue collectives and the axis *expressions* each may
   name.  A collective in an undeclared scope is CC002; an axis token
   outside the declared binding set is CC001.
*  **Executed** (trace): device-free ``AbstractMesh``es let us trace the
   real shard_map'd entry points without hardware.  CC003 round-trips the
   all2all routing over a shard-count x batch matrix (losslessness +
   capacity bounds), CC004 checks the partition-spec tables (phi never
   doc-sharded, replication invariants per mode, serving in_specs), and
   CC005 cross-checks the byte accounting ``TokenRoutingPlan`` publishes
   against the collectives a trace of the serving path *actually*
   contains (operand shapes priced with ring/all-to-all formulas).

Rules
-----
CC001  collective names an axis outside its declared/traceable binding,
       or a traced entry point fails to trace at all
CC002  collective issued from an undeclared scope
CC003  routing round-trip loses/corrupts tokens or violates capacity
CC004  partition-spec drift (replication invariant broken)
CC005  comm-byte accounting disagrees with the traced collectives
"""
from __future__ import annotations

import ast
from pathlib import Path

import numpy as np

from repro.analysis.astutil import ScopedVisitor, dotted, leaf_name
from repro.analysis.report import Finding

CHECKER = "collective-contract"

# collective primitive -> positional index of its axis-name argument
_COLLECTIVES = {
    "psum": 1, "pmean": 1, "pmax": 1, "pmin": 1, "psum_scatter": 1,
    "all_gather": 1, "all_to_all": 1, "ppermute": 1, "axis_index": 0,
}
_AXIS_KWARGS = ("axis_name", "axis")

# module -> {dotted scope: allowed axis-expression tokens}.  The tokens are
# the *names/strings* that may appear in the axis argument — the executed
# checks below verify those names resolve on the real meshes.
SCOPE_CONTRACTS: dict[str, dict[str, frozenset[str]]] = {
    "src/repro/distributed/partition.py": {
        "DistributedLDA.__init__._step": frozenset({"all_ax"}),
        "DistributedLDA.__init__.fold_axes": frozenset({"ax"}),
    },
    "src/repro/serve/infer.py": {
        "_sharded_fold_in_fns.inner_psum": frozenset({"axis"}),
        "_sharded_fold_in_fns.inner_a2a": frozenset({"axis"}),
    },
    "src/repro/serve/engine.py": {},          # host engine: no collectives
    "src/repro/core/trainer.py": {
        "lda_iteration": frozenset({"ax"}),
    },
    "src/repro/core/sync.py": {
        "maybe_psum": frozenset({"axes"}),
        "compressed_sync_phi": frozenset({"axes"}),
    },
}


# --------------------------------------------------------------------------
# AST pass: CC001 (axis token) / CC002 (scope)
# --------------------------------------------------------------------------

def _axis_tokens(node: ast.AST) -> set[str]:
    """Names / string literals reachable from an axis expression.

    ``tuple(axes)`` contributes ``axes`` (call args recurse, callee names do
    not); ``("data", "model")`` contributes both strings."""
    out: set[str] = set()

    def rec(n: ast.AST) -> None:
        if isinstance(n, ast.Name):
            out.add(n.id)
        elif isinstance(n, ast.Constant):
            if isinstance(n.value, str):
                out.add(n.value)
        elif isinstance(n, ast.Attribute):
            out.add(dotted(n) or n.attr)
        elif isinstance(n, (ast.Tuple, ast.List, ast.Set)):
            for e in n.elts:
                rec(e)
        elif isinstance(n, ast.Call):
            for a in n.args:
                rec(a)
        elif isinstance(n, ast.BinOp):
            rec(n.left)
            rec(n.right)
        elif isinstance(n, ast.BoolOp):
            for v in n.values:
                rec(v)
        elif isinstance(n, ast.IfExp):
            rec(n.body)
            rec(n.orelse)
        elif isinstance(n, ast.Starred):
            rec(n.value)

    rec(node)
    return out


class _CollectiveVisitor(ScopedVisitor):
    def __init__(self, rel: str, contracts: dict[str, frozenset[str]]):
        super().__init__()
        self.rel = rel
        self.contracts = contracts
        self.findings: list[Finding] = []

    def visit_Call(self, node: ast.Call) -> None:
        leaf = leaf_name(node.func)
        if leaf in _COLLECTIVES:
            self._check(node, leaf)
        self.generic_visit(node)

    def _axis_arg(self, node: ast.Call, leaf: str) -> ast.AST | None:
        pos = _COLLECTIVES[leaf]
        if len(node.args) > pos:
            return node.args[pos]
        for kw in node.keywords:
            if kw.arg in _AXIS_KWARGS:
                return kw.value
        return None

    def _check(self, node: ast.Call, leaf: str) -> None:
        scope = self.scope
        if scope not in self.contracts:
            self.findings.append(Finding(
                CHECKER, "CC002", self.rel, node.lineno,
                f"collective {leaf}() in undeclared scope — add the scope "
                "to SCOPE_CONTRACTS with its shard_map axis bindings",
                scope=scope or "<module>"))
            return
        allowed = self.contracts[scope]
        axis = self._axis_arg(node, leaf)
        if axis is None:
            self.findings.append(Finding(
                CHECKER, "CC001", self.rel, node.lineno,
                f"collective {leaf}() has no axis argument", scope=scope))
            return
        for tok in sorted(_axis_tokens(axis) - allowed):
            self.findings.append(Finding(
                CHECKER, "CC001", self.rel, node.lineno,
                f"collective {leaf}() names axis {tok!r}, outside the "
                f"declared bindings {sorted(allowed)} for this scope",
                scope=scope))


def scan_module(path: Path, rel: str,
                contracts: dict[str, frozenset[str]]) -> list[Finding]:
    try:
        tree = ast.parse(path.read_text(), filename=str(path))
    except SyntaxError as exc:
        return [Finding(CHECKER, "CC002", rel, exc.lineno or 0,
                        f"unparseable module: {exc.msg}", scope="<module>")]
    v = _CollectiveVisitor(rel, contracts)
    v.visit(tree)
    return v.findings


# --------------------------------------------------------------------------
# traced-jaxpr utilities (shared by CC004/CC005)
# --------------------------------------------------------------------------

def abstract_mesh(axes: dict[str, int]):
    """Device-free mesh for tracing, across jax versions (the ctor changed:
    0.4/0.5 take ((name, size), ...); 0.6+ take (sizes, names))."""
    from jax.sharding import AbstractMesh
    names, sizes = tuple(axes), tuple(axes.values())
    try:
        return AbstractMesh(tuple(zip(names, sizes)))
    except TypeError:
        return AbstractMesh(sizes, names)


def iter_eqns(jaxpr):
    """All equations, recursing into sub-jaxprs (pjit/shard_map/scan/...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def _sub_jaxprs(params: dict) -> list:
    out = []

    def rec(v) -> None:
        if hasattr(v, "eqns"):
            out.append(v)
        elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
            out.append(v.jaxpr)
        elif isinstance(v, (list, tuple)):
            for e in v:
                rec(e)

    for v in params.values():
        rec(v)
    return out


def comm_bytes(jaxpr, num_shards: int):
    """Price every traced collective with the standard ring / pairwise
    formulas, counting off-device traffic only (matches the accounting
    ``TokenRoutingPlan`` documents):

    *  all_to_all operand (S is the split dim): each device keeps its own
       slice -> itemsize * prod(shape) * (S-1) / S per device, * S devices.
    *  all_gather operand x: every device sends its x to S-1 peers ->
       itemsize * S * (S-1) * prod(x).
    *  psum (ring reduce-scatter + all-gather): 2 * (S-1)/S of the operand
       per device, * S devices.

    Returns (a2a, gather, psum, counts-by-primitive)."""
    S = num_shards
    a2a = gather = psum = 0
    counts: dict[str, int] = {}
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name == "all_to_all":
            v = eqn.invars[0].aval
            a2a += v.dtype.itemsize * int(np.prod(v.shape)) * (S - 1)
        elif name == "all_gather":
            v = eqn.invars[0].aval
            gather += v.dtype.itemsize * S * (S - 1) * int(np.prod(v.shape))
        elif name.startswith("psum"):
            for var in eqn.invars:
                v = var.aval
                psum += v.dtype.itemsize * 2 * (S - 1) * int(np.prod(v.shape))
        else:
            continue
        counts[name] = counts.get(name, 0) + 1
    return a2a, gather, psum, counts


def shard_map_eqns(jaxpr) -> list:
    return [e for e in iter_eqns(jaxpr) if "shard_map" in e.primitive.name]


def _entry_axes(entry) -> set[str]:
    """Axis names an in/out-names entry ({dim: (axes,)}) or PartitionSpec
    shards over."""
    s: set[str] = set()
    if hasattr(entry, "items"):
        for axes in entry.values():
            if isinstance(axes, str):
                s.add(axes)
            else:
                s.update(axes)
        return s
    try:
        elements = tuple(entry)
    except TypeError:
        return s
    for el in elements:
        if el is None:
            continue
        if isinstance(el, str):
            s.add(el)
        else:
            s.update(el)
    return s


def _spec_axes(spec) -> set[str]:
    return _entry_axes(spec)


# --------------------------------------------------------------------------
# CC003: executed routing round-trip
# --------------------------------------------------------------------------

_ROUTE_SHARDS = (1, 2, 3, 4, 8)
_ROUTE_BATCHES = ((1, 8), (4, 16), (5, 12), (8, 32))
_ROUTE_REL = "src/repro/distributed/partition.py"


def check_route_roundtrip(route_fn=None, shard_counts=_ROUTE_SHARDS,
                          batches=_ROUTE_BATCHES) -> list[Finding]:
    """CC003: ``route_buckets`` must deliver every real token exactly once,
    into its owner's bucket, within the capacity ``plan_token_routing``
    fixed — executed over a shard-count x batch matrix (pure jnp, no mesh).

    ``route_fn`` is injectable so the planted-violation tests can feed a
    lossy router through the same harness."""
    import jax.numpy as jnp

    from repro.distributed import partition

    route_fn = route_fn or partition.route_buckets
    findings: list[Finding] = []
    rng = np.random.default_rng(7)
    V, K = 64, 16
    for S in shard_counts:
        shard_of = rng.integers(0, S, V).astype(np.int32)
        # skew half the vocabulary onto few shards to stress capacity
        shard_of[: V // 2] = rng.integers(0, max(1, S // 2), V // 2)
        for B, L in batches:
            scope = f"route:S{S}:B{B}x{L}"
            tokens = rng.integers(0, V, (B, L)).astype(np.int32)
            lens = rng.integers(0, L + 1, B)
            lens[0] = L
            mask = np.arange(L)[None, :] < lens[:, None]
            plan = partition.plan_token_routing(shard_of, tokens, mask, S, K)
            starts, per = partition.doc_slice_bounds(B, S)
            if not 1 <= plan.capacity <= per * L:
                findings.append(Finding(
                    CHECKER, "CC003", _ROUTE_REL, 0,
                    f"planned capacity {plan.capacity} outside [1, "
                    f"slice_tokens={per * L}]", scope=scope))
                continue
            for s in range(S):
                sl = slice(int(starts[s]), int(starts[s]) + per)
                tok = tokens[sl].reshape(-1)
                msk = mask[sl].reshape(-1)
                T = tok.size
                owner = np.where(msk, shard_of[tok], S).astype(np.int32)
                bucket = np.bincount(owner[msk], minlength=S) if msk.any() \
                    else np.zeros(S, np.int64)
                if int(bucket.max(initial=0)) > plan.capacity:
                    findings.append(Finding(
                        CHECKER, "CC003", _ROUTE_REL, 0,
                        f"shard {s}: max bucket {int(bucket.max())} exceeds "
                        f"planned capacity {plan.capacity}", scope=scope))
                payload = np.arange(T, dtype=np.int32) + 1000
                send, src = (np.asarray(x) for x in route_fn(
                    jnp.asarray(owner), jnp.asarray(payload), S,
                    plan.capacity))
                filled = src < T
                got = np.sort(src[filled])
                want = np.sort(np.nonzero(msk)[0])
                if not np.array_equal(got, want):
                    findings.append(Finding(
                        CHECKER, "CC003", _ROUTE_REL, 0,
                        f"shard {s}: lossy routing — {got.size} slots filled "
                        f"for {want.size} real tokens", scope=scope))
                    continue
                if not np.array_equal(send[filled], payload[src[filled]]):
                    findings.append(Finding(
                        CHECKER, "CC003", _ROUTE_REL, 0,
                        f"shard {s}: payload corrupted in transit",
                        scope=scope))
                row_owner = np.broadcast_to(
                    np.arange(S, dtype=np.int32)[:, None], send.shape)
                if not np.array_equal(row_owner[filled], owner[src[filled]]):
                    findings.append(Finding(
                        CHECKER, "CC003", _ROUTE_REL, 0,
                        f"shard {s}: slot landed in the wrong owner bucket",
                        scope=scope))
    return findings


# --------------------------------------------------------------------------
# CC004/CC005: executed serving trace + byte cross-check
# --------------------------------------------------------------------------

_INFER_REL = "src/repro/serve/infer.py"
_SERVE_GEOM = dict(S=4, V=40, K=16, B=6, L=10)


def check_shard_map_specs(in_entries, out_entries, axis: str, comm: str) \
        -> list[Finding]:
    """CC004 (serving): the traced shard_map must shard exactly ONE input —
    the stacked phi blocks — over exactly ``axis``, and replicate every
    other operand and all outputs.  (Position-independent: tracing prepends
    closure constants as extra replicated inputs.)  Any other layout
    silently changes which phi rows a shard can see."""
    findings: list[Finding] = []
    scope = f"serve:{comm}:specs"

    def fail(msg: str) -> None:
        findings.append(Finding(CHECKER, "CC004", _INFER_REL, 0, msg,
                                scope=scope))

    sharded = [(i, _entry_axes(e)) for i, e in enumerate(in_entries)
               if _entry_axes(e)]
    if len(sharded) != 1:
        fail(f"{len(sharded)} shard_map inputs are sharded "
             f"({[(i, sorted(a)) for i, a in sharded]}); exactly one — the "
             "phi blocks — may shard")
    for i, axes in sharded:
        if axes != {axis}:
            fail(f"input {i} sharded over {sorted(axes)}, want exactly "
                 f"[{axis!r}]")
    for i, entry in enumerate(out_entries or ()):
        if _entry_axes(entry):
            fail(f"output {i} sharded over {sorted(_entry_axes(entry))}; "
                 "fold-in results must come back replicated")
    return findings


def check_serving_comm(overrides: dict | None = None) -> list[Finding]:
    """CC005 + CC004 + executed CC001 on the serving path: trace both comm
    strategies of the V-sharded fold-in on a device-free mesh, then require
    the plan's published byte counters to equal what :func:`comm_bytes`
    prices the traced collectives at.

    ``overrides`` may replace geometry keys or plant stale plan numbers
    (``a2a_bytes`` / ``psum_bytes``) for the fixture tests."""
    import jax
    import jax.numpy as jnp

    from repro.distributed import partition
    from repro.serve import infer

    g = dict(_SERVE_GEOM)
    g.update(overrides or {})
    S, V, K, B, L = g["S"], g["V"], g["K"], g["B"], g["L"]

    findings: list[Finding] = []
    rng = np.random.default_rng(3)
    shard_of = rng.integers(0, S, V).astype(np.int32)
    local_id = np.zeros(V, np.int32)
    for s in range(S):
        m = shard_of == s
        local_id[m] = np.arange(int(m.sum()))
    Vs = int(np.bincount(shard_of, minlength=S).max())
    tokens = rng.integers(0, V, (B, L)).astype(np.int32)
    lens = rng.integers(1, L + 1, B)
    mask = np.arange(L)[None, :] < lens[:, None]
    plan = partition.plan_token_routing(shard_of, tokens, mask, S, K)
    plan_a2a = g.get("a2a_bytes", plan.a2a_bytes)
    plan_psum = g.get("psum_bytes", plan.psum_bytes)

    mesh = abstract_mesh({"shards": S})
    args = (jnp.zeros((S, Vs, K), jnp.int32), jnp.zeros((K,), jnp.int32),
            jnp.asarray(shard_of), jnp.asarray(local_id), jnp.asarray(tokens),
            jnp.asarray(mask), jax.random.key(0),
            jnp.zeros(2, jnp.float32))

    for comm, capacity in (("psum", None), ("all2all", plan.capacity)):
        run_tokens, _ = infer._sharded_fold_in_fns(
            mesh, "shards", V, 2, 1, 4, None, "xla", False, comm, capacity)
        try:
            jaxpr = jax.make_jaxpr(run_tokens)(*args).jaxpr
        except Exception as exc:  # trace failure IS the finding
            findings.append(Finding(
                CHECKER, "CC001", _INFER_REL, 0,
                f"tracing the sharded fold-in ({comm}) failed: {exc!r}",
                scope=f"serve:{comm}"))
            continue
        a2a, gather, psum, counts = comm_bytes(jaxpr, S)
        scope = f"serve:{comm}:bytes"
        if comm == "psum":
            if a2a or gather:
                findings.append(Finding(
                    CHECKER, "CC005", _INFER_REL, 0,
                    f"psum strategy traced unexpected a2a/gather collectives "
                    f"{counts}", scope=scope))
            if psum != plan_psum:
                findings.append(Finding(
                    CHECKER, "CC005", _INFER_REL, 0,
                    f"traced psum moves {psum} bytes; the plan accounts "
                    f"{plan_psum}", scope=scope))
        else:
            if psum:
                findings.append(Finding(
                    CHECKER, "CC005", _INFER_REL, 0,
                    f"all2all strategy traced unexpected psum collectives "
                    f"{counts}", scope=scope))
            if a2a + gather != plan_a2a:
                findings.append(Finding(
                    CHECKER, "CC005", _INFER_REL, 0,
                    f"traced all_to_all+all_gather move {a2a + gather} bytes "
                    f"({counts}); the plan accounts {plan_a2a}", scope=scope))
        for eqn in shard_map_eqns(jaxpr):
            ins = eqn.params.get("in_names") or eqn.params.get("in_specs")
            outs = eqn.params.get("out_names") or eqn.params.get("out_specs")
            if ins is None:    # unknown jax internals: skip, don't guess
                continue
            findings.extend(check_shard_map_specs(ins, outs, "shards", comm))
    return findings


# --------------------------------------------------------------------------
# CC004 + executed CC001: training partition modes
# --------------------------------------------------------------------------

_PARTITION_REL = "src/repro/distributed/partition.py"


def check_state_spec_table(state_specs, corpus_specs, mode: str,
                           doc_axes, word_axes) -> list[Finding]:
    """CC004: replication invariants of the declared PartitionSpec table.

    phi_vk is replicated in 1d and sharded over exactly the word axes in 2d
    — never over a doc axis (that would psum partial counts into garbage);
    phi_sum/iteration are always replicated; z and every corpus field shard
    over all lead axes."""
    findings: list[Finding] = []
    lead = set(doc_axes) | set(word_axes)
    scope = f"train:{mode}:specs"

    def fail(msg: str) -> None:
        findings.append(Finding(CHECKER, "CC004", _PARTITION_REL, 0, msg,
                                scope=scope))

    phi_ax = _spec_axes(state_specs.phi_vk)
    if phi_ax & set(doc_axes):
        fail(f"phi_vk sharded over doc axes {sorted(phi_ax & set(doc_axes))}"
             " — per-shard partial counts would never be reduced")
    want_phi = set() if mode == "1d" else set(word_axes)
    if phi_ax != want_phi:
        fail(f"phi_vk spec drifted: shards over {sorted(phi_ax)}, the {mode}"
             f" contract wants {sorted(want_phi)}")
    if _spec_axes(state_specs.phi_sum):
        fail("phi_sum must be replicated (global per-topic totals)")
    if _spec_axes(state_specs.iteration):
        fail("iteration counter must be replicated")
    if _spec_axes(state_specs.z) != lead:
        fail(f"z shards over {sorted(_spec_axes(state_specs.z))}, want all "
             f"lead axes {sorted(lead)}")
    for name, spec in corpus_specs.items():
        if _spec_axes(spec) != lead:
            fail(f"corpus field {name!r} shards over "
                 f"{sorted(_spec_axes(spec))}, want all lead axes "
                 f"{sorted(lead)}")
    return findings


def check_partition_contracts() -> list[Finding]:
    """Executed CC001/CC004 over the partition-mode x sampler matrix: build
    DistributedLDA on device-free meshes (1d data=4; 2d data=2 x model=2,
    compressed sync on so the heavy-row int32 path traces too; pallas
    variants with micro_chunks + sync_overlap so the per-chunk sync
    collective traces too), check the spec tables, and
    eval_shape init -> step -> likelihood; any trace failure means a
    collective's axis does not resolve on that mesh."""
    import dataclasses

    import jax

    from repro.core import trainer as core_trainer
    from repro.core.corpus import Corpus
    from repro.distributed import partition

    rng = np.random.default_rng(1)
    D, V, per_doc = 12, 20, 20
    doc_ids = np.repeat(np.arange(D, dtype=np.int32), per_doc)
    word_ids = rng.integers(0, V, D * per_doc).astype(np.int32)
    corpus = Corpus(doc_ids, word_ids, D, V)
    cfg = core_trainer.LDAConfig(num_topics=8, tile_tokens=16,
                                 compressed_sync=True)
    # the mesh-sharded fused sweep: the overlapped per-micro-chunk
    # phi_delta sync replaces the end-of-iteration collective
    cfg_pallas = dataclasses.replace(cfg, sampler="pallas", micro_chunks=2,
                                     sync_overlap=True)

    findings: list[Finding] = []
    modes = (
        ("1d", "1d", cfg, {"data": 4}, {}),
        ("2d", "2d", cfg, {"data": 2, "model": 2},
         dict(doc_axes=("data",), word_axes=("model",))),
        ("1d-pallas", "1d", cfg_pallas, {"data": 4}, {}),
        ("2d-pallas", "2d", cfg_pallas, {"data": 2, "model": 2},
         dict(doc_axes=("data",), word_axes=("model",))),
    )
    for label, mode, case_cfg, axes, kwargs in modes:
        mesh = abstract_mesh(axes)
        try:
            dl = partition.DistributedLDA(case_cfg, mesh, corpus, mode=mode,
                                          **kwargs)
        except Exception as exc:
            findings.append(Finding(
                CHECKER, "CC001", _PARTITION_REL, 0,
                f"DistributedLDA({label}) failed on a device-free mesh: "
                f"{exc!r}", scope=f"train:{label}"))
            continue
        findings.extend(check_state_spec_table(
            dl.state_specs, dl.corpus_specs, mode, dl.plan.doc_axes,
            dl.plan.word_axes))
        try:
            key = jax.random.key(0)
            state = jax.eval_shape(dl._init_fn, dl.stacked, key)
            jax.eval_shape(dl._step_fn, dl.stacked, dl._heavy, state, key)
            jax.eval_shape(dl._ll_fn, dl.stacked, state)
        except Exception as exc:
            findings.append(Finding(
                CHECKER, "CC001", _PARTITION_REL, 0,
                f"tracing the {label} init/step/likelihood failed: {exc!r}",
                scope=f"train:{label}"))
    return findings


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------

def run(root: Path) -> list[Finding]:
    findings: list[Finding] = []
    for rel, contracts in SCOPE_CONTRACTS.items():
        path = root / rel
        if path.exists():
            findings.extend(scan_module(path, rel, contracts))
    findings.extend(check_route_roundtrip())
    findings.extend(check_serving_comm())
    findings.extend(check_partition_contracts())
    return findings
