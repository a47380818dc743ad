"""Built-in reprolint rules — one per documented hazard class.

Each rule's docstring names the historical bug it encodes; the catalogue
with reproduction snippets lives in ``tools/analysis/README.md`` and the
known-bad/known-good fixture corpus in ``tests/test_analysis.py``.
"""

from __future__ import annotations

import ast
import re
from typing import Iterator

from tools.analysis.reprolint import Finding, Rule, SourceFile, register


def _terminal_name(func: ast.AST) -> str:
    """Rightmost identifier of a callee expression (``a.b.c(...)`` -> c)."""
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return ""


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted path of an attribute chain (``np.random.rand``)."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    else:
        parts.append("?")
    return ".".join(reversed(parts))


# ----------------------------------------------------------------------
# 1. async-aliasing — the PR 6 host-buffer race
# ----------------------------------------------------------------------
@register
class AsyncAliasingRule(Rule):
    """A mutable host numpy buffer passed into an overlapped/async jitted
    dispatch without ``.copy()``.

    Historical bug (PR 6): CPU jit wraps host numpy buffers zero-copy, so
    the asynchronously executing fused decode+sample step read ``last_np``
    / ``block`` concurrently with the in-place mutations the drain/spill
    code performed — nondeterministic token corruption under tiered
    preempt/resume.  The fix snapshots every mutable numpy arg at dispatch.
    """

    name = "async-aliasing"
    description = ("mutable host buffer passed to an overlapped dispatch "
                   "without .copy()")
    hint = ("snapshot the buffer at dispatch: pass `self.X.copy()` — the "
            "async step otherwise reads it concurrently with later in-place "
            "mutations (CPU jit aliases numpy inputs zero-copy)")

    # callees that dispatch work asynchronously w.r.t. the host loop
    DISPATCH_RE = re.compile(
        r"(decode_sample|dispatch_async|async_dispatch|overlap_step)")
    # host-side numpy mirrors the serving loop mutates in place
    HOST_BUFFERS = {"last_np", "block", "_wave_last_np", "slot_len",
                    "lens_np", "tok_np_host"}

    def check(self, src: SourceFile) -> Iterator[Finding]:
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            if not self.DISPATCH_RE.search(_terminal_name(node.func)):
                continue
            exprs = list(node.args) + [kw.value for kw in node.keywords]
            for expr in exprs:
                yield from self._scan(src, node, expr)

    def _scan(self, src: SourceFile, call: ast.Call,
              expr: ast.AST) -> Iterator[Finding]:
        """Find bare `self.<host buffer>` loads inside an argument
        expression, descending through container displays but NOT into
        `.copy()` calls (those are the sanctioned snapshot)."""
        stack = [expr]
        while stack:
            node = stack.pop()
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "copy"):
                continue  # snapshotted subtree — safe by construction
            if (isinstance(node, ast.Attribute)
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "self"
                    and node.attr in self.HOST_BUFFERS):
                yield Finding(
                    src.path, node.lineno, self.name,
                    f"host buffer `self.{node.attr}` passed to overlapped "
                    f"dispatch `{_terminal_name(call.func)}(...)` without "
                    f".copy()",
                    self.hint)
                continue
            stack.extend(ast.iter_child_nodes(node))


# ----------------------------------------------------------------------
# 2. boolean-select-trap — the plan_remesh / w4a16 / arrival_s class
# ----------------------------------------------------------------------
@register
class BooleanSelectTrapRule(Rule):
    """``x or <numeric default>`` (or ``a and b or c``) used as a *value*,
    where a falsy-but-valid ``0``/``0.0`` LHS silently selects the default.

    Historical bugs: ``elastic.plan_remesh`` used ``cond and a or b``
    (ternary emulation that mis-selects when ``a`` is falsy, fixed PR 8);
    ``w4a16_gemv``'s tile clamp carried a dead `` or 0`` bounce that
    inflated padding 2x (fixed PR 9); ``scheduler._abs_deadline`` used
    ``(req.arrival_s or 0.0)``, conflating ``None`` with a legitimate
    ``0.0`` arrival instant (fixed by this rule's introduction PR).
    """

    name = "boolean-select-trap"
    description = ("`x or <numeric/sentinel default>` value expression "
                   "conflates falsy-but-valid 0/0.0 with None")
    hint = ("spell the intent out: `default if x is None else x` (or an "
            "explicit if/else for and/or chains) — `or` also fires on a "
            "legitimate 0/0.0/empty LHS")

    def check(self, src: SourceFile) -> Iterator[Finding]:
        for node in ast.walk(src.tree):
            if not (isinstance(node, ast.BoolOp)
                    and isinstance(node.op, ast.Or)):
                continue
            if self._in_boolean_context(src, node):
                continue
            # `a and b or c`: pre-ternary idiom, wrong when b is falsy
            if any(isinstance(v, ast.BoolOp) and isinstance(v.op, ast.And)
                   for v in node.values):
                yield Finding(
                    src.path, node.lineno, self.name,
                    "`a and b or c` used as a value mis-selects c whenever "
                    "b is falsy — use a real conditional expression",
                    self.hint)
                continue
            default = node.values[-1]
            if self._risky_default(default):
                yield Finding(
                    src.path, node.lineno, self.name,
                    f"`... or {ast.unparse(default)}` used as a value: a "
                    f"falsy-but-valid LHS (0, 0.0) silently selects the "
                    f"default",
                    self.hint)

    @staticmethod
    def _risky_default(node: ast.AST) -> bool:
        # numeric constants are the trap class (0 vs None conflation);
        # ALL-CAPS names are sentinel constants (e.g. `x or _NO_BUDGET`)
        # where a valid 0 LHS selects a wildly different value
        if isinstance(node, ast.Constant) and isinstance(
                node.value, (int, float)) and not isinstance(node.value, bool):
            return True
        if isinstance(node, ast.Name) and node.id.isupper():
            return True
        return False

    @staticmethod
    def _in_boolean_context(src: SourceFile, node: ast.AST) -> bool:
        """True when the or-expression only feeds a truth test (if/while/
        assert/not/bool()/comprehension-if/ternary test) — no value escapes,
        so the select trap cannot bite."""
        child = node
        parent = src.parents.get(child)
        while parent is not None:
            if isinstance(parent, (ast.If, ast.While)):
                return child is parent.test
            if isinstance(parent, ast.IfExp):
                return child is parent.test
            if isinstance(parent, ast.Assert):
                return child is parent.test
            if isinstance(parent, ast.comprehension):
                return child in parent.ifs
            if isinstance(parent, ast.UnaryOp) and isinstance(
                    parent.op, ast.Not):
                return True
            if (isinstance(parent, ast.Call)
                    and _terminal_name(parent.func) == "bool"):
                return True
            if isinstance(parent, ast.BoolOp):
                child, parent = parent, src.parents.get(parent)
                continue
            return False
        return False


# ----------------------------------------------------------------------
# 3. donation-use-after — reading a buffer after donating it
# ----------------------------------------------------------------------
@register
class DonationUseAfterRule(Rule):
    """A variable read after being passed through a ``donate_argnums``
    position of a jitted call in the same scope.

    Hazard class behind the overlapped loop's donated cache buffers
    (``_jit_decode_sample*`` donate the cache): XLA reuses a
    donated buffer in place, so any later host read of the old reference
    sees freed/overwritten memory.  Best-effort static check: it follows
    ``name = jax.jit(fn, donate_argnums=...)`` bindings and flags later
    reads of variables passed in donated positions of ``name(...)`` calls.
    """

    name = "donation-use-after"
    description = ("buffer read after being passed through a "
                   "donate_argnums position")
    hint = ("a donated buffer is invalidated at dispatch: rebind the "
            "result (`x = step(x)`) or drop the old reference before "
            "reading; if the read is intentional, copy before the call")

    def check(self, src: SourceFile) -> Iterator[Finding]:
        scopes = [n for n in ast.walk(src.tree)
                  if isinstance(n, (ast.Module, ast.FunctionDef,
                                    ast.AsyncFunctionDef))]
        for scope in scopes:
            yield from self._check_scope(src, scope)

    def _check_scope(self, src: SourceFile, scope: ast.AST):
        donating: dict[str, tuple[int, ...]] = {}
        # pass 1: `name = jax.jit(..., donate_argnums=...)` bindings
        for node in ast.walk(scope):
            if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and isinstance(node.value, ast.Call)
                    and _terminal_name(node.value.func) == "jit"):
                continue
            for kw in node.value.keywords:
                if kw.arg in ("donate_argnums", "donate_argnames"):
                    positions = self._const_positions(kw.value)
                    if positions:
                        donating[node.targets[0].id] = positions
        if not donating:
            return
        # pass 2: donated Name args of calls to those bindings
        donated: dict[str, int] = {}  # var -> lineno of the donating call
        for node in ast.walk(scope):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in donating):
                for pos in donating[node.func.id]:
                    if pos < len(node.args) and isinstance(
                            node.args[pos], ast.Name):
                        var = node.args[pos].id
                        donated[var] = min(node.lineno,
                                           donated.get(var, 1 << 30))
        if not donated:
            return
        # pass 3: later loads (unless rebound first)
        rebinds: dict[str, int] = {}
        for node in ast.walk(scope):
            if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                flat: list[ast.AST] = []
                while targets:
                    t = targets.pop()
                    if isinstance(t, (ast.Tuple, ast.List)):
                        targets.extend(t.elts)  # `out, cache = step(...)`
                    else:
                        flat.append(t)
                for t in flat:
                    if isinstance(t, ast.Name) and t.id in donated:
                        # >= : rebinding on the donating call's own line is
                        # the sanctioned `x = step(x)` idiom
                        if node.lineno >= donated[t.id]:
                            rebinds[t.id] = min(
                                node.lineno, rebinds.get(t.id, 1 << 30))
        for node in ast.walk(scope):
            if (isinstance(node, ast.Name)
                    and isinstance(node.ctx, ast.Load)
                    and node.id in donated
                    and node.lineno > donated[node.id]
                    and node.lineno < rebinds.get(node.id, 1 << 30)):
                yield Finding(
                    src.path, node.lineno, self.name,
                    f"`{node.id}` read after being donated to a jitted call "
                    f"on line {donated[node.id]} (donate_argnums)",
                    self.hint)

    @staticmethod
    def _const_positions(node: ast.AST) -> tuple[int, ...]:
        if isinstance(node, ast.Constant) and isinstance(node.value, int):
            return (node.value,)
        if isinstance(node, (ast.Tuple, ast.List)):
            out = []
            for e in node.elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    out.append(e.value)
            return tuple(out)
        return ()


# ----------------------------------------------------------------------
# 4. wire-field-drift — serving dataclasses vs the fleet codec manifest
# ----------------------------------------------------------------------
@register
class WireFieldDriftRule(Rule):
    """A serving dataclass field not covered by ``WIRE_FIELDS`` in
    ``fleet/wire.py`` (or a manifest entry for a field that no longer
    exists).

    Hazard: the wire codec round-trips the serving dataclasses as
    field-name maps with unknown-field skip, so a field added to
    ``Request`` / ``SamplingParams`` / ``RequestOutput`` / ``SlotSnapshot``
    without thinking about codec coverage would silently drop on the wire
    (an older decoder skips it; a non-defaulted field breaks decode).  The
    manifest makes the drift a lint failure instead; the runtime half is
    ``sanitize.check_wire_manifest`` (``REPRO_SANITIZE=1``).
    """

    name = "wire-field-drift"
    description = ("serving dataclass fields out of sync with the fleet "
                   "wire manifest (WIRE_FIELDS)")
    hint = ("add the field to WIRE_FIELDS in serving/fleet/wire.py (new "
            "fields go LAST in the dataclass, defaulted, so older peers "
            "keep decoding) — or remove the stale manifest entry")
    project = True

    def check_project(self, files) -> Iterator[Finding]:
        manifest = None
        manifest_src = None
        manifest_line = 1
        for path, src in files.items():
            found = self._find_manifest(src)
            if found is not None:
                manifest, manifest_line = found
                manifest_src = src
                break
        wire_files = [p for p in files
                      if p.replace("\\", "/").endswith("fleet/wire.py")]
        if manifest is None:
            for p in wire_files:
                yield Finding(
                    p, 1, self.name,
                    "fleet/wire.py has no WIRE_FIELDS manifest — dataclass "
                    "field drift cannot be checked", self.hint)
            return
        classes = self._find_dataclasses(files, set(manifest))
        for cls_name, listed in manifest.items():
            if cls_name not in classes:
                continue  # class not in the scanned set: nothing to compare
            path, lineno, actual = classes[cls_name]
            for field in actual:
                if field not in listed:
                    yield Finding(
                        path, lineno, self.name,
                        f"field `{field}` of {cls_name} is missing from the "
                        f"fleet wire manifest (WIRE_FIELDS) — it would "
                        f"silently drop on the wire", self.hint)
            for field in listed:
                if field not in actual:
                    yield Finding(
                        manifest_src.path, manifest_line, self.name,
                        f"WIRE_FIELDS lists `{cls_name}.{field}` but the "
                        f"dataclass has no such field (stale manifest "
                        f"entry)", self.hint)

    @staticmethod
    def _find_manifest(src: SourceFile):
        for node in ast.walk(src.tree):
            if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id == "WIRE_FIELDS"
                    and isinstance(node.value, ast.Dict)):
                continue
            manifest: dict[str, tuple[str, ...]] = {}
            for k, v in zip(node.value.keys, node.value.values):
                if not (isinstance(k, ast.Constant)
                        and isinstance(k.value, str)):
                    continue
                names = []
                if isinstance(v, (ast.Tuple, ast.List)):
                    for e in v.elts:
                        if (isinstance(e, ast.Constant)
                                and isinstance(e.value, str)):
                            names.append(e.value)
                manifest[k.value] = tuple(names)
            return manifest, node.lineno
        return None

    @staticmethod
    def _find_dataclasses(files, wanted: set[str]):
        out: dict[str, tuple[str, int, tuple[str, ...]]] = {}
        for path, src in files.items():
            if path.replace("\\", "/").endswith("fleet/wire.py"):
                continue  # fixture manifests may restate names
            for node in ast.walk(src.tree):
                if not (isinstance(node, ast.ClassDef)
                        and node.name in wanted):
                    continue
                if not any("dataclass" in ast.unparse(d)
                           for d in node.decorator_list):
                    continue
                fields = tuple(
                    stmt.target.id for stmt in node.body
                    if isinstance(stmt, ast.AnnAssign)
                    and isinstance(stmt.target, ast.Name)
                    and not stmt.target.id.startswith("_")
                    and "ClassVar" not in ast.unparse(stmt.annotation))
                out[node.name] = (path, node.lineno, fields)
        return out


# ----------------------------------------------------------------------
# 5. nondeterminism — unseeded randomness / wall-clock in hot paths
# ----------------------------------------------------------------------
@register
class NondeterminismRule(Rule):
    """``np.random`` / ``time.time()`` / time-seeded ``PRNGKey`` in the
    serving/model hot paths.

    The repo's signature oracle is BIT-identity (sync vs overlapped,
    warm vs cold prefix, failover replay vs undisturbed stream, migration
    before vs after): any nondeterminism on those paths silently breaks
    every one of them.  Per-request randomness must flow through the
    seeded sampler contract (``fold_in(PRNGKey(seed), output_index)``);
    wall-clock stats belong to ``time.monotonic`` (allowed), not
    ``time.time`` (NTP steps under the serving loop).
    """

    name = "nondeterminism"
    description = ("np.random / time.time() / time-seeded PRNGKey on a "
                   "bit-identity-pinned hot path")
    hint = ("route randomness through the seeded sampler contract "
            "(fold_in(PRNGKey(seed), i)) and clocks through "
            "time.monotonic; bit-identity oracles break otherwise")
    paths = ("src/repro/serving", "src/repro/models")

    _NONDET_CALLS = {"time", "time_ns", "urandom", "random", "randint",
                     "rand", "randn"}

    def check(self, src: SourceFile) -> Iterator[Finding]:
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Attribute):
                dotted = _dotted(node)
                if dotted.startswith(("np.random.", "numpy.random.")) or \
                        dotted in ("np.random", "numpy.random"):
                    parent = src.parents.get(node)
                    if isinstance(parent, ast.Attribute):
                        continue  # report the full chain once
                    yield Finding(
                        src.path, node.lineno, self.name,
                        f"`{dotted}` used on a serving/model hot path",
                        self.hint)
            elif isinstance(node, ast.Call):
                dotted = _dotted(node.func)
                if dotted == "time.time":
                    yield Finding(
                        src.path, node.lineno, self.name,
                        "`time.time()` on a serving/model hot path (NTP "
                        "can step it mid-trace)", self.hint)
                elif _terminal_name(node.func) == "PRNGKey":
                    for sub in ast.walk(node):
                        if (isinstance(sub, ast.Call) and sub is not node
                                and _terminal_name(sub.func)
                                in self._NONDET_CALLS):
                            yield Finding(
                                src.path, node.lineno, self.name,
                                f"PRNGKey seeded from nondeterministic "
                                f"`{_dotted(sub.func)}(...)`", self.hint)
                            break


# ----------------------------------------------------------------------
# 6. jit-in-loop — retrace explosion
# ----------------------------------------------------------------------
@register
class JitInLoopRule(Rule):
    """``jax.jit`` / ``pl.pallas_call`` / ``jax.pmap`` constructed inside a
    loop body.

    Each construction is a fresh callable with a fresh trace cache: a
    per-step loop rebuilds and recompiles every iteration (the retrace
    explosion the engine's ``lru_cache``-shared ``_jit_*`` factories and
    power-of-two bucketing exist to prevent).  The runtime twin is the
    sanitizer's retrace budget (``REPRO_SANITIZE=1``).
    """

    name = "jit-in-loop"
    description = "jax.jit / pl.pallas_call constructed inside a loop body"
    hint = ("hoist the jit/pallas_call out of the loop (module level or an "
            "lru_cache'd factory keyed on the static config) so the trace "
            "cache is shared across iterations")

    _CTORS = {"jit", "pallas_call", "pmap"}

    def check(self, src: SourceFile) -> Iterator[Finding]:
        for node in ast.walk(src.tree):
            if not (isinstance(node, ast.Call)
                    and _terminal_name(node.func) in self._CTORS):
                continue
            loop = self._enclosing_loop(src, node)
            if loop is not None:
                yield Finding(
                    src.path, node.lineno, self.name,
                    f"`{_dotted(node.func)}(...)` constructed inside the "
                    f"loop at line {loop.lineno} — every iteration "
                    f"rebuilds the trace cache and recompiles", self.hint)

    @staticmethod
    def _enclosing_loop(src: SourceFile, node: ast.AST):
        cur = src.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.For, ast.AsyncFor, ast.While)):
                return cur
            cur = src.parents.get(cur)
        return None


# ----------------------------------------------------------------------
# 7. mutable-default — shared mutable default arguments
# ----------------------------------------------------------------------
@register
class MutableDefaultRule(Rule):
    """A mutable default argument (list/dict/set display, or an array
    constructor) — evaluated ONCE and shared across calls."""

    name = "mutable-default"
    description = "mutable default argument shared across calls"
    hint = ("default to None and construct inside the body (or use "
            "dataclasses.field(default_factory=...))")

    _CTOR_NAMES = {"list", "dict", "set", "bytearray", "zeros", "ones",
                   "empty", "array", "defaultdict", "OrderedDict", "deque"}

    def check(self, src: SourceFile) -> Iterator[Finding]:
        for node in ast.walk(src.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Lambda)):
                continue
            args = node.args
            for default in list(args.defaults) + [
                    d for d in args.kw_defaults if d is not None]:
                if isinstance(default, (ast.List, ast.Dict, ast.Set,
                                        ast.ListComp, ast.DictComp,
                                        ast.SetComp)) or (
                        isinstance(default, ast.Call)
                        and _terminal_name(default.func) in self._CTOR_NAMES):
                    label = (node.name if not isinstance(node, ast.Lambda)
                             else "<lambda>")
                    yield Finding(
                        src.path, default.lineno, self.name,
                        f"mutable default `{ast.unparse(default)}` in "
                        f"`{label}` is evaluated once and shared by every "
                        f"call", self.hint)


# ----------------------------------------------------------------------
# 9. silent-except — swallowed failures
# ----------------------------------------------------------------------
@register
class SilentExceptRule(Rule):
    """A bare ``except:`` (catches KeyboardInterrupt/SystemExit) or a
    broad ``except Exception/BaseException`` whose body only ``pass``es —
    the failure disappears without a trace.  Narrow typed handlers with
    ``pass`` (best-effort close paths) are accepted."""

    name = "silent-except"
    description = "bare except, or broad except swallowed with pass"
    hint = ("catch the narrowest exception type that the cleanup path "
            "really expects, or at least record the failure before "
            "continuing")

    _BROAD = {"Exception", "BaseException"}

    def check(self, src: SourceFile) -> Iterator[Finding]:
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if node.type is None:
                yield Finding(
                    src.path, node.lineno, self.name,
                    "bare `except:` also swallows KeyboardInterrupt and "
                    "SystemExit", self.hint)
                continue
            body_silent = all(
                isinstance(s, ast.Pass)
                or (isinstance(s, ast.Expr)
                    and isinstance(s.value, ast.Constant)
                    and s.value.value is Ellipsis)
                for s in node.body)
            if body_silent and _terminal_name(node.type) in self._BROAD:
                yield Finding(
                    src.path, node.lineno, self.name,
                    f"`except {ast.unparse(node.type)}: pass` swallows "
                    f"every failure silently", self.hint)
