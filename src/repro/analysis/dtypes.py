"""Dtype-flow checker (DT001-DT004).

C7 stores topic assignments in int16 and syncs count *deltas* in int16 —
narrow integer widths are a deliberate, paper-motivated bandwidth
optimization, which makes silent wraparound the single most likely way
this codebase corrupts counts at paper scale while staying green on toy
tests.  This pass walks ``core/`` and ``kernels/`` flow-sensitively at the
AST level and pins every narrow-width decision to an **executed witness**
evaluated at Table-3 geometry (NYTimes / PubMed sizes from
``configs/``):

*  every narrowing or dynamic-width ``astype`` must be a declared site
   (``DECLARED``) whose witness proves the value range fits (DT001);
*  chained ``astype`` casts that lose width mid-chain are flat errors
   (DT002);
*  flattened index arithmetic (``b_idx * B + in_b``, 128-lane
   block slices) must be declared against a bound witness showing the
   product stays under 2^31 at full corpus scale (DT003);
*  count scatters must accumulate in integers — float32 is exact only to
   2^24, far below both corpora's token counts (DT004).

The witnesses run unconditionally (they *clear* the real tree, and keep
clearing it only while the guards they probe — the LDAConfig topic-dtype
check, the heavy-row int32 sync path — stay wired).
"""
from __future__ import annotations

import ast
import dataclasses
import functools
from pathlib import Path

import numpy as np

from repro.analysis.astutil import ScopedVisitor, dotted, leaf_name
from repro.analysis.report import Finding

CHECKER = "dtype-flow"

TARGET_DIRS = ("src/repro/core", "src/repro/kernels")

_WIDTH = {
    "int8": 8, "int16": 16, "int32": 32, "int64": 64,
    "uint8": 8, "uint16": 16, "uint32": 32, "uint64": 64,
    "float16": 16, "bfloat16": 16, "float32": 32, "float64": 64,
}
_NARROW = {"int8", "int16", "uint8", "uint16"}
_INTS = {t for t in _WIDTH if t.startswith(("int", "uint"))}
_ARRAY_CTORS = {"zeros", "ones", "full", "empty"}


@dataclasses.dataclass(frozen=True)
class Event:
    """One AST-level dtype event, pre-declaration-filtering."""
    code: str
    line: int
    scope: str
    message: str


# (module, dotted scope, rule) -> witness id.  A narrowing/index event at a
# declared site is vouched for by its witness; anywhere else it is a
# finding.  Declarations that no longer match any event are reported too
# (dead vouchers hide future regressions).
DECLARED: dict[tuple[str, str, str], str] = {
    # topic ids: values in [0, K); LDAConfig.__post_init__ guarantees K-1
    # fits topic_dtype, so every topic-id narrowing shares one witness
    ("src/repro/core/trainer.py", "init_state", "DT001"):
        "topic-id-fits-dtype",
    ("src/repro/core/sampler.py", "sample_one_tile", "DT001"):
        "topic-id-fits-dtype",
    ("src/repro/core/dense_sampler.py", "sample_one_tile_dense", "DT001"):
        "topic-id-fits-dtype",
    ("src/repro/kernels/lda_sample/ops.py", "lda_sample", "DT001"):
        "topic-id-fits-dtype",
    # int16 delta sync: exact below the flux bound, int32 heavy-row path
    # above it — the witness executes both
    ("src/repro/core/sync.py", "compressed_sync_phi", "DT001"):
        "compressed-flux-int32-path",
    # two-level search flattening: b_idx * B + in_b == k < K, and the
    # kernels' 128-lane block slices of the K topics / P ELL entries
    ("src/repro/core/sampler.py", "blocked_draw", "DT003"):
        "index-topic-bound",
    ("src/repro/kernels/lanes.py", "dense_draw", "DT003"):
        "index-topic-bound",
    ("src/repro/kernels/lanes.py", "search_rows", "DT003"):
        "index-topic-bound",
    ("src/repro/kernels/lanes.py", "gather_lanes", "DT003"):
        "index-topic-bound",
    ("src/repro/kernels/lda_sample/kernel.py", "table_rows", "DT003"):
        "index-topic-bound",
}


# --------------------------------------------------------------------------
# AST pass
# --------------------------------------------------------------------------

class _DtypeVisitor(ScopedVisitor):
    def __init__(self) -> None:
        super().__init__()
        self._envs: list[dict[str, tuple[str, str]]] = [{}]
        self.events: list[Event] = []

    # fresh (inherited) alias env per nested scope
    def _push(self, name: str, node: ast.AST) -> None:
        self._envs.append(dict(self._envs[-1]))
        super()._push(name, node)
        self._envs.pop()

    @property
    def _env(self) -> dict[str, tuple[str, str]]:
        return self._envs[-1]

    def _emit(self, code: str, node: ast.AST, message: str) -> None:
        self.events.append(Event(code, getattr(node, "lineno", 0),
                                 self.scope or "<module>", message))

    # -- dtype token resolution -------------------------------------------
    def _dtype_token(self, node: ast.AST) -> str | None:
        """'int16' etc. for static dtypes, 'dynamic' for ``x.dtype`` /
        ``*.topic_dtype`` style inherited widths, None for unknown."""
        if isinstance(node, ast.Attribute):
            last = node.attr
            if last in _WIDTH:
                return last
            if last == "dtype" or last.lower().endswith("topic_dtype"):
                return "dynamic"
            return None
        if isinstance(node, ast.Name):
            if node.id.lower().endswith("topic_dtype"):
                return "dynamic"
            kind_tok = self._env.get(node.id)
            if kind_tok and kind_tok[0] == "dtype":
                return kind_tok[1]
            return None
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value if node.value in _WIDTH else None
        if isinstance(node, ast.IfExp):
            a = self._dtype_token(node.body)
            b = self._dtype_token(node.orelse)
            return a if a == b else None
        return None

    def _array_dtype(self, node: ast.AST) -> str | None:
        """dtype token of a ``jnp.zeros/ones/full/empty`` constructor call."""
        if not (isinstance(node, ast.Call) and
                leaf_name(node.func) in _ARRAY_CTORS):
            return None
        for kw in node.keywords:
            if kw.arg == "dtype":
                return self._dtype_token(kw.value)
        for arg in node.args[1:]:
            tok = self._dtype_token(arg)
            if tok:
                return tok
        return None

    # -- alias tracking ----------------------------------------------------
    def visit_Assign(self, node: ast.Assign) -> None:
        if len(node.targets) == 1 and isinstance(node.targets[0], ast.Name):
            name = node.targets[0].id
            tok = self._dtype_token(node.value)
            if tok and tok != "dynamic":
                self._env[name] = ("dtype", tok)
            else:
                arr = self._array_dtype(node.value)
                if arr:
                    self._env[name] = ("array", arr)
                else:
                    self._env.pop(name, None)
        self.generic_visit(node)

    # -- events ------------------------------------------------------------
    def visit_Call(self, node: ast.Call) -> None:
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr == "astype" and node.args:
            self._check_astype(node, f)
        elif (isinstance(f, ast.Attribute) and f.attr == "add"
              and isinstance(f.value, ast.Subscript)
              and isinstance(f.value.value, ast.Attribute)
              and f.value.value.attr == "at"):
            self._check_scatter(node, f.value.value.value)
        self.generic_visit(node)

    def _check_astype(self, node: ast.Call, f: ast.Attribute) -> None:
        tok = self._dtype_token(node.args[0])
        if tok in _NARROW:
            self._emit("DT001", node,
                       f"narrowing astype({tok}) — values outside "
                       f"{tok} range wrap silently; needs a declared range "
                       "witness")
        elif tok == "dynamic":
            src = dotted(node.args[0]) or ast.unparse(node.args[0])
            self._emit("DT001", node,
                       f"dynamic-width astype({src}) inherits int16 under "
                       "the default topic_dtype; needs a declared range "
                       "witness")
        inner = f.value
        if (isinstance(inner, ast.Call)
                and isinstance(inner.func, ast.Attribute)
                and inner.func.attr == "astype" and inner.args):
            tok0 = self._dtype_token(inner.args[0])
            if (tok in _INTS and tok0 in _INTS
                    and _WIDTH[tok] < _WIDTH[tok0]):
                self._emit("DT002", node,
                           f"cast chain astype({tok0}).astype({tok}) "
                           f"silently drops {_WIDTH[tok0] - _WIDTH[tok]} "
                           "bits — cast once at the final width")

    def _check_scatter(self, node: ast.Call, acc: ast.AST) -> None:
        tok = self._array_dtype(acc)
        if tok is None and isinstance(acc, ast.Name):
            kind_tok = self._env.get(acc.id)
            if kind_tok and kind_tok[0] == "array":
                tok = kind_tok[1]
        if tok and tok.startswith(("float", "bfloat")):
            self._emit("DT004", node,
                       f"count scatter accumulates in {tok}: exact only to "
                       "2^24, below both Table-3 corpora's token counts — "
                       "accumulate in int32 and cast at the end")

    def visit_BinOp(self, node: ast.BinOp) -> None:
        if (isinstance(node.op, ast.Add)
                and isinstance(node.left, ast.BinOp)
                and isinstance(node.left.op, ast.Mult)
                and all(isinstance(x, (ast.Name, ast.Attribute))
                        for x in (node.left.left, node.left.right))):
            self._emit("DT003", node,
                       f"flattened index {ast.unparse(node)!r} — int32 "
                       "products overflow at 2^31; needs a declared bound "
                       "witness at Table-3 scale")
        self.generic_visit(node)

    def visit_Subscript(self, node: ast.Subscript) -> None:
        for sub in ast.walk(node.slice):
            if isinstance(sub, ast.BinOp) and isinstance(sub.op, ast.Mult):
                self._emit("DT003", node,
                           "index arithmetic inside subscript "
                           f"{ast.unparse(node.slice)!r}; needs a declared "
                           "bound witness at Table-3 scale")
                break
        self.generic_visit(node)


def scan_module(path: Path) -> list[Event]:
    tree = ast.parse(path.read_text(), filename=str(path))
    v = _DtypeVisitor()
    v.visit(tree)
    return v.events


def apply_declarations(events: list[Event], rel: str,
                       declared: dict | None = None) -> \
        tuple[list[Finding], set[tuple[str, str, str]]]:
    """Events -> findings: DT002/DT004 always fire; DT001/DT003 only at
    undeclared sites.  Returns (findings, matched declaration keys)."""
    declared = DECLARED if declared is None else declared
    findings: list[Finding] = []
    matched: set[tuple[str, str, str]] = set()
    for ev in events:
        key = (rel, ev.scope, ev.code)
        if ev.code in ("DT001", "DT003") and key in declared:
            matched.add(key)
            continue
        findings.append(Finding(CHECKER, ev.code, rel, ev.line, ev.message,
                                scope=ev.scope))
    return findings, matched


# --------------------------------------------------------------------------
# executed witnesses (Table-3 geometry from configs/)
# --------------------------------------------------------------------------

def _corpora():
    from repro.configs import lda_nytimes, lda_pubmed
    return (("nytimes", lda_nytimes), ("pubmed", lda_pubmed))


def _w_topic_fits() -> list[str]:
    """Topic ids fit topic_dtype for the shipped configs, and LDAConfig
    *rejects* a K that would not (the guard is what every topic-id astype
    site leans on)."""
    import jax.numpy as jnp

    from repro.core.trainer import LDAConfig

    probs = []
    for name, mod in _corpora():
        cfg = mod.CONFIG
        mx = int(jnp.iinfo(cfg.topic_dtype).max)
        if cfg.num_topics - 1 > mx:
            probs.append(f"{name}: K-1={cfg.num_topics - 1} exceeds "
                         f"topic_dtype max {mx}")
    try:
        LDAConfig(num_topics=(1 << 15) + 1)
        probs.append("LDAConfig accepts num_topics=32769 with the int16 "
                     "default topic_dtype — init_state would wrap topic ids "
                     "silently")
    except ValueError:
        pass
    try:
        LDAConfig(num_topics=(1 << 15) + 1, topic_dtype=jnp.int32)
    except ValueError as exc:
        probs.append(f"int32 escape hatch rejected: {exc}")
    return probs


def _w_compressed_flux() -> list[str]:
    """Execute the int16 delta sync on a real 1-device mesh: a planted
    per-entry flux of 40000 (> 2^15) must wrap on the plain path — that
    wrap is *why* the heavy-row path exists — and come back exact through
    ``heavy_rows``; and the trainer must actually thread heavy rows in."""
    import inspect

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from repro.core import sync
    from repro.core import trainer as core_trainer
    from repro.distributed import partition

    probs = []
    if partition.INT16_FLUX_BOUND != 1 << 15:
        probs.append("INT16_FLUX_BOUND moved off 2^15 — the exactness "
                     "argument in sync.compressed_sync_phi no longer holds")
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    delta = (jnp.zeros((4, 3), jnp.int32)
             .at[1, 2].set(40000).at[2, 0].set(-30000))
    heavy = jnp.asarray([1, 2], jnp.int32)

    def wrap16(d):
        return sync.compressed_sync_phi(d, ("data",))

    def fixed(d):
        return sync.compressed_sync_phi(d, ("data",), heavy)

    sm = functools.partial(jax.shard_map, mesh=mesh, in_specs=P(),
                           out_specs=P(), check_vma=False)
    wrapped = np.asarray(jax.jit(sm(wrap16))(delta))
    exact = np.asarray(jax.jit(sm(fixed))(delta))
    if wrapped[1, 2] == 40000:
        probs.append("planted 40000 delta survived the plain int16 path — "
                     "the wrap this witness guards against did not "
                     "reproduce; witness is stale")
    if not np.array_equal(exact, np.asarray(delta)):
        probs.append(f"heavy-row int32 correction not exact: entry (1,2) "
                     f"came back {int(exact[1, 2])}, want 40000")
    if "heavy_rows" not in inspect.signature(
            core_trainer.lda_iteration).parameters:
        probs.append("lda_iteration has no heavy_rows parameter — the "
                     "heavy-word int32 path is not wired into training")
    if not hasattr(partition, "heavy_word_rows"):
        probs.append("partition.heavy_word_rows missing — DistributedLDA "
                     "cannot derive the int32-sync rows")
    return probs


def _w_index_topic() -> list[str]:
    """b_idx * B + in_b reconstructs k exactly and stays under both int32
    and topic_dtype bounds at the shipped K."""
    import jax.numpy as jnp

    from repro.core import sampler

    probs = []
    for name, mod in _corpora():
        K = mod.CONFIG.num_topics
        Bb = sampler.pick_search_block(K)
        bound = (-(-K // Bb) - 1) * Bb + (Bb - 1)
        if bound >= 1 << 31:
            probs.append(f"{name}: flattened search index bound {bound} "
                         "overflows int32")
        if (-(-K // Bb) - 1) * Bb + (K - 1) % Bb != K - 1:
            probs.append(f"{name}: block decomposition does not "
                         f"reconstruct k=K-1 (K={K}, B={Bb})")
        mx = int(jnp.iinfo(mod.CONFIG.topic_dtype).max)
        if K - 1 > mx:
            probs.append(f"{name}: topic id bound {K - 1} exceeds "
                         f"topic_dtype max {mx}")
    return probs


def _w_count_scatter() -> list[str]:
    """Count accumulators are integer-typed (float32 is exact only to 2^24
    < both corpora's T) and int32 still covers the Table-3 token counts."""
    import jax
    import jax.numpy as jnp

    from repro.core import updates

    probs = []
    z = jax.ShapeDtypeStruct((2, 3), jnp.int16)
    idx = jax.ShapeDtypeStruct((2,), jnp.int32)
    doc = jax.ShapeDtypeStruct((2, 3), jnp.int32)
    msk = jax.ShapeDtypeStruct((2, 3), jnp.bool_)
    phi = jax.eval_shape(lambda a, b, c: updates.phi_from_z(a, b, c, 4, 8),
                         z, idx, msk)
    theta = jax.eval_shape(
        lambda a, b, c: updates.theta_from_z(a, b, c, 4, 8), z, doc, msk)
    for name, aval in (("phi_from_z", phi), ("theta_from_z", theta)):
        if not jnp.issubdtype(aval.dtype, jnp.integer):
            probs.append(f"updates.{name} accumulates counts in "
                         f"{aval.dtype} — non-integer scatter accumulation")
    for name, mod in _corpora():
        T = mod.FULL["num_tokens"]
        if T >= 1 << 31:
            probs.append(f"{name}: T={T} no longer fits the int32 count "
                         "accumulators")
        if T <= 1 << 24:
            # then float32 would coincidentally be exact and this witness
            # would stop meaning anything — flag so the rule gets revisited
            probs.append(f"{name}: T={T} under 2^24; DT004's premise needs "
                         "revisiting")
    return probs


# (rule, anchor module, anchor scope, witness id, fn) — all run on every
# checker invocation; each returned problem string becomes a finding.
WITNESSES = (
    ("DT001", "src/repro/core/trainer.py", "init_state",
     "topic-id-fits-dtype", _w_topic_fits),
    ("DT001", "src/repro/core/sync.py", "compressed_sync_phi",
     "compressed-flux-int32-path", _w_compressed_flux),
    ("DT003", "src/repro/core/sampler.py", "blocked_draw",
     "index-topic-bound", _w_index_topic),
    ("DT004", "src/repro/core/updates.py", "phi_from_z",
     "count-scatter-int32", _w_count_scatter),
)


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------

def run(root: Path) -> list[Finding]:
    findings: list[Finding] = []
    matched: set[tuple[str, str, str]] = set()
    for target in TARGET_DIRS:
        base = root / target
        if not base.is_dir():
            continue
        for path in sorted(base.rglob("*.py")):
            rel = path.relative_to(root).as_posix()
            try:
                events = scan_module(path)
            except SyntaxError as exc:
                findings.append(Finding(
                    CHECKER, "DT001", rel, exc.lineno or 0,
                    f"unparseable module: {exc.msg}", scope="<module>"))
                continue
            fs, m = apply_declarations(events, rel)
            findings.extend(fs)
            matched.update(m)

    known_witnesses = {w[3] for w in WITNESSES}
    for key, witness in sorted(DECLARED.items()):
        rel, scope, code = key
        if key not in matched:
            findings.append(Finding(
                CHECKER, code, rel, 0,
                f"declared {code} site matched no event — the code moved; "
                "drop or update the declaration", scope=scope))
        if witness not in known_witnesses:
            findings.append(Finding(
                CHECKER, code, rel, 0,
                f"declaration names unknown witness {witness!r}",
                scope=scope))

    for code, rel, scope, wid, fn in WITNESSES:
        try:
            probs = fn()
        except Exception as exc:
            probs = [f"witness {wid!r} crashed: {exc!r}"]
        findings.extend(Finding(CHECKER, code, rel, 0,
                                f"[{wid}] {p}", scope=scope)
                        for p in probs)
    return findings
