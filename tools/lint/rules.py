"""The eight tracecheck rules (TC001–TC008).

Each rule is a function ``rule(project) -> list[Finding]``.  The module
also carries :data:`EXPLAIN` — the ``--explain`` text, which doubles as
the rule documentation linked from docs/ARCHITECTURE.md.
"""

from __future__ import annotations

import ast

from tools.lint.engine import (
    Finding,
    Project,
    SourceFile,
    dotted,
    is_jax_jit,
    jit_call_info,
)

EXPLAIN: dict[str, str] = {
    "TC001": """\
TC001 — no jit construction inside function or loop bodies.

`jax.jit(...)` / `functools.partial(jax.jit, ...)` evaluated inside a
function body builds a FRESH compilation cache on every call: the program
recompiles each time, silently turning a microseconds hot path into a
seconds one (the single-compile guarantee in ROADMAP "Standing
invariants").  Jits must be module-level (`_run_scenarios_jit =
jax.jit(_run_scenarios_body, ...)`) or built inside a
`functools.lru_cache`/`functools.cache`-decorated factory, which gives
each distinct configuration exactly one cache.

Scope: src/ and benchmarks/.  tests/ are exempt: a per-test jit dies with
the process, and tests deliberately build throwaway jits to probe retrace
behavior.  Benchmarks that *measure* cold compiles suppress the rule
inline with a reason.

Fix: hoist the jit to module level, or wrap the constructing factory in
`functools.lru_cache`.
""",
    "TC002": """\
TC002 — no concretization of traced values in jit-reachable code.

`float(x)`, `int(x)`, `bool(x)`, `x.item()`, `x.tolist()` and
`np.asarray(x)` force a traced value onto the host.  Under `jax.jit` they
raise `TracerConversionError` at best; under `vmap`/`scan` composition
they can silently constant-fold a value that should vary per lane.  The
jit entry points and their static parameters are declared in
tools/lint/entrypoints.py (JIT_ENTRYPOINTS + auto-discovered
`jax.jit(...)` sites); every function reachable from an entry point is
checked, and every non-static parameter of such a function is treated as
traced.  Shape/dtype access (`x.shape`, `x.ndim`, `x.dtype`) is static
metadata and never flagged; `jnp.asarray` stays on device and is fine.

Limitation (by design): only *parameters* are tracked, not locals derived
from them — the contract is enforced at function boundaries, where review
happens.

Fix: keep the math in jnp (`jnp.asarray`, `jnp.where`), or declare the
parameter static in the entry registry if it genuinely is.
""",
    "TC003": """\
TC003 — no Python `if`/`while` on traced values.

Python control flow on a traced value concretizes it (see TC002) — under
jit it raises, and in the batched scenario engine it would fork the
single compiled program per lane, breaking the single-compile guarantee
for (caps x shifts x policies x topologies) grids.  Branchless
alternatives: `jnp.where`, `lax.cond`, `lax.select`, score-table gathers
(the PR-2 policy kernel pattern).

Presence checks (`x is None` / `x is not None`) are structural — they
pick the compiled program, not a traced branch — and are never flagged;
neither are `isinstance(...)`, `len(...)` or `.shape`/`.ndim`/`.dtype`
tests.  Parameters follow the same traced/static classification as TC002.

Fix: rewrite the branch with `jnp.where`/`lax.cond`, or declare the
parameter static in tools/lint/entrypoints.py.
""",
    "TC004": """\
TC004 — a buffer passed to a donating jit must not be read afterwards.

`jax.jit(fn, donate_argnums=...)` invalidates the donated argument's
buffers: XLA reuses them for the output.  Reading the old reference
afterwards raises `RuntimeError: Array has been deleted` — but only at
runtime, and only on platforms where donation is honored, which is how
the PR-7 optimizer bug shipped (fixed by the host-snapshot pattern:
`jax.tree.map(np.asarray, x)` *before* the donating call).

The donating jits are auto-discovered from `jax.jit(...,
donate_argnums=...)` module-level assignments plus the explicit
DONATING_JITS registry.  Safe patterns: rebind the name in the same
statement (`state, out = twin_step_jit(state, ...)`) or never touch the
old reference again.  Flagged patterns: reading the variable after the
call, or passing it un-rebound from inside a loop (the second iteration
reads a donated buffer).

Fix: rebind the carry, or snapshot to host first.
""",
    "TC005": """\
TC005 — bf16 casts only in the allow-listed readout leaves.

The precision policy (PR 7, pinned by tests/golden/readout_bf16.npz):
bfloat16 is permitted exactly where the f64 oracle tolerance allows it —
the derived performance leaves (tflops, efficiency) inside the fused DES
readout.  Sustainability math (power, energy, gCO2, cost) stays f32: a
bf16 ulp on a power sum is megawatt-hours of drift over a fleet-year.
Any `.astype(jnp.bfloat16)`, `astype("bfloat16")` or `jnp.bfloat16`
reference outside BF16_ALLOWED_FILES (tools/lint/entrypoints.py) is
flagged.  A "bfloat16" string outside `astype` (a dtype name in a
config) is not a cast and is not flagged.

Fix: keep the cast inside src/repro/kernels/des_readout.py behind the
`precision="bf16"` knob, or extend the allow-list in review.
""",
    "TC006": """\
TC006 — optional dependencies are imported guarded, never bare.

ROADMAP "Optional-dependency policy": heavy/non-vendored packages
(zstandard, hypothesis) are try-imported with a stdlib fallback
(repro/core/codec.py) or gated by `pytest.importorskip`; CI runs without
them installed, so one bare import breaks collection everywhere — the
seed suite died exactly this way (6 collection errors, fixed in PR 1).

Allowed forms: `import zstandard` inside a `try:` block, or any import
lexically after a `pytest.importorskip("zstandard")` call in the same
file (module-level or inside the function).

Fix: wrap in try/except ImportError with a fallback, or importorskip.
""",
    "TC007": """\
TC007 — no ambient nondeterminism in the deterministic core.

src/repro/core/, src/repro/kernels/ and src/repro/runtime/ are the
bit-for-bit heart of the twin: goldens, the oracle cross-check and the
scenario cache keys all assume that the same inputs give the same
outputs.  Calls to wall clocks (`time.time`, `time.monotonic`, ...),
ambient RNGs (`np.random.*` unseeded, stdlib `random`), `uuid4`,
`os.urandom` and ambient device discovery (`jax.devices()` as a hidden
default) smuggle environment state into that core.

*References* are fine — `clock: Callable = time.time` as an injectable
default is the sanctioned pattern (the orchestrator's Clock); only calls
are flagged.  `np.random.default_rng(seed)` with an explicit seed is
deterministic and allowed; `jax.random.*` is always keyed and never
flagged.  The I/O-shell allow-list (NONDETERMINISM_ALLOWED) covers
orchestrator pacing (`time.sleep` — wall-clock pacing is its job, paper
section 2.3); platform-dispatch sites suppress inline with a reason.

Fix: inject the clock/rng/devices from the caller.
""",
    "TC008": """\
TC008 — heavy test loops carry the `slow` marker.

pytest.ini runs tier 1 with `-m "not slow"`; heavy tests belong to the
tier2-slow CI job (ROADMAP test tiers).  Flagged: hypothesis
`@settings(max_examples=N)` with N > 50 on a test without
`@pytest.mark.slow` (module-level `pytestmark` counts), and golden-file
writes (`np.savez*` into tests/golden) from unmarked test functions —
golden regeneration belongs in tools/capture_*.py scripts, not in the
fast tier.

Fix: mark the test `slow`, shrink the example budget, or move the regen
into a tools/ script.
""",
}

_CONCRETIZERS = {"float", "int", "bool", "complex"}
_CONCRETIZE_FUNCS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}
_CONCRETIZE_METHODS = {"item", "tolist"}
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size"}

_NONDET_CALLS = {
    "time.time", "time.time_ns", "time.monotonic", "time.monotonic_ns",
    "time.perf_counter", "time.perf_counter_ns", "time.sleep",
    "datetime.datetime.now", "datetime.datetime.utcnow", "datetime.now",
    "os.urandom", "uuid.uuid1", "uuid.uuid4", "secrets.token_bytes",
    "secrets.token_hex", "jax.devices", "jax.local_devices",
}


def _in_scope(sf: SourceFile, prefixes) -> bool:
    return any(sf.path.startswith(p) for p in prefixes)


# -- TC001 --------------------------------------------------------------------

def _cached_factory(sf: SourceFile, fn: ast.AST) -> bool:
    """Is this function decorated with functools.lru_cache / cache?"""
    for dec in fn.decorator_list:  # type: ignore[union-attr]
        target = dec.func if isinstance(dec, ast.Call) else dec
        if dotted(target) in ("functools.lru_cache", "functools.cache",
                              "lru_cache", "cache"):
            return True
    return False


def rule_tc001(project: Project) -> list[Finding]:
    out: list[Finding] = []
    for sf in project.files:
        if not _in_scope(sf, project.registry.JIT_HYGIENE_DIRS):
            continue
        for node in ast.walk(sf.tree):
            jit_site = None
            if isinstance(node, ast.Call):
                info = jit_call_info(node, sf)
                if info is not None:
                    jit_site = node
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # bare @jax.jit decorator on a *nested* function
                for dec in node.decorator_list:
                    if not isinstance(dec, ast.Call) and is_jax_jit(dec, sf):
                        if sf.enclosing_function(node) is not None:
                            jit_site = dec
            if jit_site is None:
                continue
            enc = sf.enclosing_function(jit_site)
            if enc is None:
                continue                      # module level: fine
            if jit_site in getattr(enc, "decorator_list", []) \
                    and sf.enclosing_function(enc) is None:
                continue                      # decorator of a top-level def
            # allowed inside an lru_cache'd factory anywhere up the chain
            cur = enc
            cached = False
            while cur is not None:
                if _cached_factory(sf, cur):
                    cached = True
                    break
                cur = sf.enclosing_function(cur)
            if cached:
                continue
            out.append(Finding(
                "TC001", sf.path, jit_site.lineno,
                f"jax.jit constructed inside '{enc.name}' — a fresh "
                "compilation cache per call (recompile hazard); hoist to "
                "module level or an lru_cache'd factory"))
    return out


# -- TC002 / TC003 ------------------------------------------------------------

def _traced_name_of(expr: ast.AST, traced: set[str]) -> str | None:
    """Name of the traced parameter an expression is rooted at, if any.

    Walks down Attribute/Subscript chains; chains touching static metadata
    (`.shape`, `.ndim`, `.dtype`, `.size`) are never traced.
    """
    cur = expr
    while True:
        if isinstance(cur, ast.Attribute):
            if cur.attr in _STATIC_ATTRS:
                return None
            cur = cur.value
        elif isinstance(cur, ast.Subscript):
            cur = cur.value
        else:
            break
    if isinstance(cur, ast.Name) and cur.id in traced:
        return cur.id
    return None


def _function_defs(fi_node: ast.AST):
    """(def, params-of-def) for the function and every nested def inside."""
    for node in ast.walk(fi_node):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            yield node


def _traced_params_for_def(project: Project, fi, node) -> set[str]:
    if node is fi.node:
        return project.traced_params(fi)
    # nested def / lambda inside a reachable function: its params are traced
    # too (vmapped lane bodies, scan bodies) minus the conventional statics
    a = node.args
    out = set()
    for p in a.posonlyargs + a.args + a.kwonlyargs:
        if p.arg in project.registry.STATIC_PARAM_NAMES or p.arg in fi.statics:
            continue
        if not isinstance(node, ast.Lambda) and Project._static_annotation(p):
            continue
        out.add(p.arg)
    return out


def _owning_def(sf: SourceFile, node: ast.AST, fi) -> ast.AST | None:
    """Nearest def/lambda ancestor of node that is within fi.node."""
    cur = getattr(node, "_tc_parent", None)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            return cur
        if cur is fi.node:
            return fi.node
        cur = getattr(cur, "_tc_parent", None)
    return None


def rule_tc002(project: Project) -> list[Finding]:
    out: list[Finding] = []
    for q, fi in sorted(project.reachable.items()):
        sf = fi.sf
        for node in ast.walk(fi.node):
            if not isinstance(node, ast.Call):
                continue
            owner = _owning_def(sf, node, fi)
            if owner is None:
                continue
            traced = _traced_params_for_def(project, fi, owner)
            target: ast.AST | None = None
            what = None
            fname = dotted(node.func)
            if isinstance(node.func, ast.Name) \
                    and node.func.id in _CONCRETIZERS and len(node.args) == 1:
                target, what = node.args[0], f"{node.func.id}()"
            elif fname is not None and (
                    fname in _CONCRETIZE_FUNCS
                    or project.resolve_call(sf, node) in _CONCRETIZE_FUNCS):
                if node.args:
                    target, what = node.args[0], f"{fname}()"
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _CONCRETIZE_METHODS \
                    and not node.args:
                target, what = node.func.value, f".{node.func.attr}()"
            if target is None:
                continue
            name = _traced_name_of(target, traced)
            if name is None:
                continue
            out.append(Finding(
                "TC002", sf.path, node.lineno,
                f"{what} concretizes traced parameter '{name}' in "
                f"'{q.rsplit('.', 1)[-1]}' (jit-reachable); keep it in jnp "
                "or declare the parameter static in the entry registry"))
    return out


class _BranchNames(ast.NodeVisitor):
    """Collect Names in a branch test, skipping structural checks."""

    def __init__(self):
        self.names: list[ast.Name] = []

    def visit_Compare(self, node: ast.Compare):
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
            return                      # presence check: structural
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call):
        if isinstance(node.func, ast.Name) \
                and node.func.id in ("isinstance", "hasattr", "len",
                                     "callable", "getattr"):
            return                      # structural predicates
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute):
        if node.attr in _STATIC_ATTRS:
            return                      # x.shape / x.ndim / x.dtype: static
        self.generic_visit(node)

    def visit_Name(self, node: ast.Name):
        self.names.append(node)


def rule_tc003(project: Project) -> list[Finding]:
    out: list[Finding] = []
    for q, fi in sorted(project.reachable.items()):
        sf = fi.sf
        for node in ast.walk(fi.node):
            if not isinstance(node, (ast.If, ast.While, ast.IfExp)):
                continue
            owner = _owning_def(sf, node, fi)
            if owner is None:
                continue
            traced = _traced_params_for_def(project, fi, owner)
            v = _BranchNames()
            v.visit(node.test)
            hits = sorted({n.id for n in v.names if n.id in traced})
            if not hits:
                continue
            kind = {ast.If: "if", ast.While: "while",
                    ast.IfExp: "conditional expression"}[type(node)]
            out.append(Finding(
                "TC003", sf.path, node.lineno,
                f"Python {kind} on traced parameter(s) "
                f"{', '.join(repr(h) for h in hits)} in "
                f"'{q.rsplit('.', 1)[-1]}' (jit-reachable); use "
                "jnp.where/lax.cond or declare the parameter static"))
    return out


# -- TC004 --------------------------------------------------------------------

def _stmt_of(node: ast.AST):
    cur = node
    while cur is not None and not isinstance(cur, ast.stmt):
        cur = getattr(cur, "_tc_parent", None)
    return cur


def _assign_targets(stmt: ast.stmt) -> set[str]:
    names: set[str] = set()
    targets = []
    if isinstance(stmt, ast.Assign):
        targets = stmt.targets
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        targets = [stmt.target]
    for t in targets:
        stack = [t]
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.Tuple, ast.List)):
                stack.extend(n.elts)
            else:
                d = dotted(n)
                if d:
                    names.add(d)
    return names


def rule_tc004(project: Project) -> list[Finding]:
    out: list[Finding] = []
    for sf in project.files:
        for fn in ast.walk(sf.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for call in ast.walk(fn):
                if not isinstance(call, ast.Call):
                    continue
                target = project.resolve_call(sf, call)
                donate = project.donating.get(target or "")
                if not donate:
                    continue
                stmt = _stmt_of(call)
                if stmt is None:
                    continue
                rebound = _assign_targets(stmt)
                for pos in donate:
                    if pos >= len(call.args):
                        continue
                    argname = dotted(call.args[pos])
                    if argname is None:
                        continue        # expression arg: nothing to re-read
                    if argname in rebound:
                        continue        # state, out = f(state, ...): safe
                    # un-rebound donation inside a loop: next iteration
                    # passes (= reads) the donated buffer again
                    in_loop = any(isinstance(a, (ast.For, ast.While))
                                  for a in sf.ancestors(stmt)
                                  if sf.enclosing_function(a) is
                                  sf.enclosing_function(stmt))
                    reused_line = None
                    if in_loop:
                        reused_line = call.lineno
                    else:
                        end = stmt.end_lineno or stmt.lineno
                        events = []
                        for n in ast.walk(fn):
                            line = getattr(n, "lineno", None)
                            if line is None or line <= end:
                                continue
                            if isinstance(n, (ast.Name, ast.Attribute)) \
                                    and dotted(n) == argname:
                                is_store = isinstance(
                                    getattr(n, "ctx", None), ast.Store)
                                events.append(
                                    (line, n.col_offset, is_store))
                        if events:
                            # first touch after the call: a read means the
                            # donated buffer is used; a store re-binds it
                            _, _, first_is_store = min(events)
                            if not first_is_store:
                                reused_line = min(events)[0]
                    if reused_line is None:
                        continue
                    out.append(Finding(
                        "TC004", sf.path, call.lineno,
                        f"'{argname}' is donated to "
                        f"{(target or '?').rsplit('.', 1)[-1]} (arg {pos}) "
                        "and read again afterwards — the buffer is "
                        "invalidated by donation; rebind it from the "
                        "result or snapshot to host first"))
    return out


# -- TC005 --------------------------------------------------------------------

def rule_tc005(project: Project) -> list[Finding]:
    out: list[Finding] = []
    for sf in project.files:
        if sf.path in project.registry.BF16_ALLOWED_FILES:
            continue
        for node in ast.walk(sf.tree):
            line = None
            if isinstance(node, ast.Attribute) and node.attr == "bfloat16":
                line = node.lineno
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "astype" and node.args \
                    and isinstance(node.args[0], ast.Constant) \
                    and node.args[0].value in ("bfloat16", "bf16"):
                line = node.lineno
            if line is None:
                continue
            out.append(Finding(
                "TC005", sf.path, line,
                "bfloat16 cast outside the precision-policy allow-list — "
                "bf16 is legal only on the tflops/efficiency leaves in "
                "src/repro/kernels/des_readout.py (golden-pinned)"))
    return out


# -- TC006 --------------------------------------------------------------------

def rule_tc006(project: Project) -> list[Finding]:
    out: list[Finding] = []
    optional = project.registry.OPTIONAL_MODULES
    for sf in project.files:
        skip_lines = [n.lineno for n in ast.walk(sf.tree)
                      if isinstance(n, ast.Call)
                      and dotted(n.func) == "pytest.importorskip"
                      and n.args and isinstance(n.args[0], ast.Constant)]
        for node in ast.walk(sf.tree):
            mods: list[str] = []
            if isinstance(node, ast.Import):
                mods = [a.name.split(".")[0] for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.module:
                mods = [node.module.split(".")[0]]
            hits = [m for m in mods if m in optional]
            if not hits:
                continue
            if any(isinstance(a, ast.Try) for a in sf.ancestors(node)):
                continue
            if any(line < node.lineno for line in skip_lines):
                continue
            out.append(Finding(
                "TC006", sf.path, node.lineno,
                f"bare import of optional dependency "
                f"{'/'.join(sorted(set(hits)))} — CI runs without it; "
                "try-import with a stdlib fallback or pytest.importorskip "
                "(ROADMAP optional-dependency policy)"))
    return out


# -- TC007 --------------------------------------------------------------------

def rule_tc007(project: Project) -> list[Finding]:
    out: list[Finding] = []
    allowed = project.registry.NONDETERMINISM_ALLOWED
    for sf in project.files:
        if not _in_scope(sf, project.registry.DETERMINISTIC_DIRS):
            continue
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            d = project.resolve_call(sf, node) or ""
            src = None
            if d in _NONDET_CALLS:
                src = d
            elif d.startswith(("numpy.random.", "np.random.")):
                if d.rsplit(".", 1)[-1] == "default_rng" and node.args:
                    src = None          # explicitly seeded: deterministic
                else:
                    src = d
            elif d.startswith("random.") or d == "random":
                src = d
            if src is None:
                continue
            short = src.replace("numpy.", "np.")
            if (sf.path, short) in allowed or (sf.path, src) in allowed:
                continue
            out.append(Finding(
                "TC007", sf.path, node.lineno,
                f"nondeterminism source {short}() called in the "
                "deterministic core — inject it (clock/rng/devices "
                "parameter) or add an allow-list entry with a reason"))
    return out


# -- TC008 --------------------------------------------------------------------

def _has_slow_marker(sf: SourceFile, fn: ast.AST) -> bool:
    for dec in fn.decorator_list:  # type: ignore[union-attr]
        d = dotted(dec.func if isinstance(dec, ast.Call) else dec)
        if d in ("pytest.mark.slow", "mark.slow"):
            return True
    for stmt in sf.tree.body:
        if isinstance(stmt, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "pytestmark"
                for t in stmt.targets):
            for n in ast.walk(stmt.value):
                if dotted(n) in ("pytest.mark.slow", "mark.slow"):
                    return True
    return False


def _max_examples_of(sf: SourceFile, dec: ast.Call) -> int | None:
    kwargs = list(dec.keywords)
    for kw in list(kwargs):
        if kw.arg is None and isinstance(kw.value, ast.Name):
            # @settings(**SETTINGS): resolve the module-level dict(...)
            for stmt in sf.tree.body:
                if isinstance(stmt, ast.Assign) and any(
                        isinstance(t, ast.Name) and t.id == kw.value.id
                        for t in stmt.targets) \
                        and isinstance(stmt.value, ast.Call):
                    kwargs.extend(stmt.value.keywords)
    for kw in kwargs:
        if kw.arg == "max_examples" and isinstance(kw.value, ast.Constant) \
                and isinstance(kw.value.value, int):
            return kw.value.value
    return None


def rule_tc008(project: Project) -> list[Finding]:
    out: list[Finding] = []
    budget = project.registry.MAX_FAST_EXAMPLES
    for sf in project.files:
        if not sf.path.startswith("tests/"):
            continue
        for fn in ast.walk(sf.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            slow = _has_slow_marker(sf, fn)
            if slow:
                continue
            for dec in fn.decorator_list:
                if isinstance(dec, ast.Call) \
                        and dotted(dec.func) == "settings":
                    n = _max_examples_of(sf, dec)
                    if n is not None and n > budget:
                        out.append(Finding(
                            "TC008", sf.path, dec.lineno,
                            f"hypothesis max_examples={n} > {budget} "
                            f"on unmarked '{fn.name}' — mark it "
                            "@pytest.mark.slow or shrink the budget "
                            "(tier-1 runs -m 'not slow')"))
            if fn.name.startswith("test_"):
                for node in ast.walk(fn):
                    if isinstance(node, ast.Call) and (
                            dotted(node.func) or "").endswith(
                            ("np.savez", "np.savez_compressed",
                             "numpy.savez", "numpy.savez_compressed")):
                        out.append(Finding(
                            "TC008", sf.path, node.lineno,
                            f"golden write (savez) inside unmarked "
                            f"'{fn.name}' — golden regeneration belongs "
                            "in tools/capture_*.py, not the fast tier"))
    return out


ALL_RULES = (rule_tc001, rule_tc002, rule_tc003, rule_tc004,
             rule_tc005, rule_tc006, rule_tc007, rule_tc008)
