"""AST linter: repo-specific tracing-discipline rules (no jax import).

The rules encode invariants that runtime counters can't check statically
and reviewers forget (docs/static_analysis.md):

  * ``host-sync`` — host-synchronizing calls (``.item()``, ``float()``,
    ``jax.device_get``, ``block_until_ready``, ``np.asarray`` on traced
    arguments, ``print``, wall clocks) inside code that is jit-traced.
    One stray ``.item()`` in a hot loop serializes every dispatch.
  * ``banned-api`` — APIs the code must not reach for: the legacy
    ``auto=`` kwarg of ``shard_map`` (say ``axis_names=``),
    ``ragged_all_to_all`` outside the MoE transport probe (no CPU
    thunk), ``jax.experimental.shard_map`` imports (the public
    ``jax.shard_map`` is the one spelling), and the deprecated
    ``jax.experimental.host_callback``.
  * ``internal-api`` — ``jax._src`` imports/attributes outside an
    allowlisted site (internals drift between jax versions; every use
    must name its fallback behavior).
  * ``broad-except`` — bare/``except Exception`` handlers without a
    reasoned allowlist comment (they have hidden real crashes here
    before; see PR 2's load_params_only).
  * ``traced-branch`` — Python ``if``/``while`` on values that are
    traced arrays (annotated ``jnp.ndarray``/``jax.Array`` parameters
    or ``jnp.*``/``jax.lax.*`` call results) inside traced code; use
    ``lax.cond``/``jnp.where``.

Traced code is detected statically: functions decorated with
``jax.jit`` (incl. ``partial(jax.jit, ...)``), functions or lambdas
passed to ``jax.jit``/``jax.shard_map`` by name in the same module,
everything nested inside those, and — transitively — same-module
functions they call.

Allowlisting: append ``# jaxlint: disable=<rule>[,<rule>] - <reason>``
to the offending line (or the line above). A reason is REQUIRED — a
bare disable does not suppress. ``broad-except`` also accepts the
existing ``# noqa: BLE001 - <reason>`` convention. A whole file can opt
out of one rule with ``# jaxlint: disable-file=<rule> - <reason>``.

Stdlib-only by design: ``tools/jaxlint.py`` loads this module by file
path, so the CLI (and any pre-commit hook) never pays a jax import.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import re
import tokenize
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

RULES = {
    "host-sync": "host-synchronizing call inside jit-traced code",
    "banned-api": "API the code must not reach for (legacy or CPU-less)",
    "internal-api": "jax._src internals outside an allowlisted shim",
    "broad-except": "bare/broad except without a reasoned allowlist comment",
    "traced-branch": "Python branch on a traced array value",
}

#: meta-rule for linter self-diagnostics (syntax errors, unreadable
#: files, reasonless disable comments). Always on: not selectable via
#: ``rules=`` and not suppressible by an allowlist comment.
META_RULE = "lint-error"

#: dotted call names that synchronize (or would crash) under tracing
_HOST_SYNC_FUNCS = {
    "jax.device_get",
    "jax.block_until_ready",
    "time.time",
    "time.monotonic",
    "time.perf_counter",
}
#: method calls that synchronize regardless of receiver
_HOST_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
#: numpy converters — host syncs only when fed a traced value, so these
#: are flagged only when the argument is a parameter of a traced function
#: (host-side trace-time constants like np.asarray([0, 1]) stay legal)
_NUMPY_CONVERTERS = {"np.asarray", "np.array", "numpy.asarray",
                     "numpy.array"}

#: jax namespaces whose call results are traced arrays (for traced-branch)
_ARRAY_NAMESPACES = ("jnp.", "jax.lax.", "jax.numpy.", "jax.random.",
                     "jax.nn.")
_ARRAY_ANNOTATION = re.compile(
    r"(jnp\.ndarray|jax\.Array|jnp\.array|ndarray|Array\b)")

_DISABLE_RE = re.compile(
    r"jaxlint:\s*disable=([\w,-]+)\s*(?:[-—:]\s*)?(.*)")
_DISABLE_FILE_RE = re.compile(
    r"jaxlint:\s*disable-file=([\w,-]+)\s*(?:[-—:]\s*)?(.*)")
_NOQA_BLE_RE = re.compile(r"noqa:\s*BLE001\s*(?:[-—:]\s*)?(.*)")


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    line: int
    col: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: [{self.rule}] {self.message}"


def _dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _comments_by_line(src: str) -> Tuple[Dict[int, str], Set[int]]:
    """(line -> comment text, lines that hold ONLY a comment)."""
    out: Dict[int, str] = {}
    comment_only: Set[int] = set()
    try:
        for tok in tokenize.generate_tokens(io.StringIO(src).readline):
            if tok.type == tokenize.COMMENT:
                line = tok.start[0]
                out[line] = tok.string
                if not tok.line[:tok.start[1]].strip():
                    comment_only.add(line)
    except tokenize.TokenError:
        pass  # torn tail (unterminated string being edited) — lint the AST anyway
    return out, comment_only


class _Allowlist:
    """Inline / file-level suppression with mandatory reasons."""

    def __init__(self, comments: Dict[int, str],
                 comment_only: Optional[Set[int]] = None):
        self._comment_only = comment_only or set()
        self._by_line: Dict[int, Set[str]] = {}
        self.file_rules: Set[str] = set()
        self.bad: List[Tuple[int, str]] = []  # disables missing a reason
        for line, text in comments.items():
            m = _DISABLE_FILE_RE.search(text)
            if m:
                rules, reason = m.group(1), m.group(2)
                if not re.search(r"[A-Za-z]", reason):
                    self.bad.append((line, text.strip()))
                else:
                    self.file_rules |= set(rules.split(","))
                continue
            m = _DISABLE_RE.search(text)
            if m:
                rules, reason = m.group(1), m.group(2)
                if not re.search(r"[A-Za-z]", reason):
                    self.bad.append((line, text.strip()))
                else:
                    self._by_line.setdefault(line, set()).update(
                        rules.split(","))
            m = _NOQA_BLE_RE.search(text)
            if m and re.search(r"[A-Za-z]", m.group(1)):
                self._by_line.setdefault(line, set()).add("broad-except")

    def allows(self, rule: str, line: int) -> bool:
        if rule in self.file_rules:
            return True
        if rule in self._by_line.get(line, ()):
            return True
        # a disable in the comment block immediately above applies: walk
        # up through contiguous comment-only lines
        ln = line - 1
        while ln > 0 and ln in self._comment_only:
            if rule in self._by_line.get(ln, ()):
                return True
            ln -= 1
        return False


def _is_jit_decorator(dec: ast.AST) -> bool:
    name = _dotted(dec)
    if name in ("jax.jit", "jit"):
        return True
    if isinstance(dec, ast.Call):
        fn = _dotted(dec.func)
        if fn in ("jax.jit", "jit"):
            return True
        if fn in ("partial", "functools.partial") and dec.args:
            return _dotted(dec.args[0]) in ("jax.jit", "jit")
    return False


_FuncNode = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


class _ModuleIndex:
    """Function defs, nesting, and the traced-region closure."""

    def __init__(self, tree: ast.Module):
        self.parents: Dict[ast.AST, ast.AST] = {}
        self.defs_by_name: Dict[str, List[ast.AST]] = {}
        self.funcs: List[ast.AST] = []
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node
        for node in ast.walk(tree):
            if isinstance(node, _FuncNode):
                self.funcs.append(node)
                if not isinstance(node, ast.Lambda):
                    self.defs_by_name.setdefault(node.name, []).append(node)
        self.traced: Set[ast.AST] = set()
        self._find_roots(tree)
        self._close_over_nesting()
        self._propagate_calls()

    def _find_roots(self, tree: ast.Module) -> None:
        for node in self.funcs:
            if not isinstance(node, ast.Lambda) and any(
                    _is_jit_decorator(d) for d in node.decorator_list):
                self.traced.add(node)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = _dotted(node.func)
            if fn not in ("jax.jit", "jit", "jax.shard_map", "shard_map"):
                continue
            for arg in list(node.args[:1]) + [
                    kw.value for kw in node.keywords if kw.arg in ("f", "fun")]:
                if isinstance(arg, ast.Lambda):
                    self.traced.add(arg)
                elif isinstance(arg, ast.Name):
                    for d in self.defs_by_name.get(arg.id, ()):
                        self.traced.add(d)

    def _close_over_nesting(self) -> None:
        for node in self.funcs:
            cur = self.parents.get(node)
            while cur is not None:
                if cur in self.traced:
                    self.traced.add(node)
                    break
                cur = self.parents.get(cur)

    def _propagate_calls(self) -> None:
        """Same-module call-graph closure: helpers called from traced
        code run under the same trace."""
        changed = True
        while changed:
            changed = False
            for node in list(self.traced):
                for call in ast.walk(node):
                    if not isinstance(call, ast.Call):
                        continue
                    if isinstance(call.func, ast.Name):
                        for d in self.defs_by_name.get(call.func.id, ()):
                            if d not in self.traced:
                                self.traced.add(d)
                                changed = True
            # re-close nesting for newly traced functions
            before = len(self.traced)
            self._close_over_nesting()
            changed = changed or len(self.traced) != before

    def enclosing_traced_params(self, node: ast.AST) -> Set[str]:
        """Parameter names of `node` and every enclosing traced func."""
        out: Set[str] = set()
        cur: Optional[ast.AST] = node
        while cur is not None:
            if cur in self.traced and isinstance(cur, _FuncNode):
                args = cur.args
                for a in (args.posonlyargs + args.args + args.kwonlyargs
                          + ([args.vararg] if args.vararg else [])
                          + ([args.kwarg] if args.kwarg else [])):
                    out.add(a.arg)
            cur = self.parents.get(cur)
        return out

    def array_annotated(self, node: ast.AST) -> Set[str]:
        """Parameters annotated as arrays in `node` + enclosing traced."""
        out: Set[str] = set()
        cur: Optional[ast.AST] = node
        while cur is not None:
            if cur in self.traced and isinstance(
                    cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for a in (cur.args.posonlyargs + cur.args.args
                          + cur.args.kwonlyargs):
                    if a.annotation is not None:
                        try:
                            txt = ast.unparse(a.annotation)
                        except Exception:  # noqa: BLE001 - unparse gap on odd nodes; skip annotation
                            continue
                        if _ARRAY_ANNOTATION.search(txt):
                            out.add(a.arg)
            cur = self.parents.get(cur)
        return out


def lint_source(src: str, path: str = "<string>",
                rules: Optional[Iterable[str]] = None) -> List[Finding]:
    """Lint one module's source. Returns findings sorted by position."""
    active = set(rules) if rules is not None else set(RULES)
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Finding(path, e.lineno or 0, e.offset or 0, META_RULE,
                        f"syntax error prevents linting: {e.msg}")]
    allow = _Allowlist(*_comments_by_line(src))
    idx = _ModuleIndex(tree)
    findings: List[Finding] = []

    def emit(rule: str, node: ast.AST, msg: str) -> None:
        line = getattr(node, "lineno", 0)
        if rule in active and not allow.allows(rule, line):
            findings.append(Finding(path, line,
                                    getattr(node, "col_offset", 0), rule, msg))

    for line, text in allow.bad:
        findings.append(Finding(
            path, line, 0, META_RULE,
            f"jaxlint disable comment without a reason: {text!r} — "
            "allowlists must say why"))

    _module_rules(tree, emit)
    _traced_rules(idx, emit)

    # dedupe (nested traced functions are reachable from several roots)
    uniq = {(f.path, f.line, f.col, f.rule, f.message): f for f in findings}
    return sorted(uniq.values(), key=lambda f: (f.path, f.line, f.col, f.rule))


def _module_rules(tree: ast.Module, emit) -> None:
    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler):
            t = node.type
            names = []
            if t is None:
                names = ["<bare>"]
            elif isinstance(t, ast.Tuple):
                names = [_dotted(e) or "?" for e in t.elts]
            else:
                names = [_dotted(t) or "?"]
            broad = t is None or any(
                n in ("Exception", "BaseException") for n in names)
            if broad:
                emit("broad-except", node,
                     f"except {', '.join(names)} swallows everything — "
                     "narrow it, or allowlist with '# noqa: BLE001 - reason'")
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod.startswith("jax.experimental.shard_map"):
                emit("banned-api", node,
                     "jax.experimental.shard_map is the legacy spelling — "
                     "use the public jax.shard_map")
            if mod.startswith("jax.experimental.host_callback"):
                emit("banned-api", node,
                     "jax.experimental.host_callback is deprecated; use "
                     "jax.pure_callback/io_callback (and keep them out of "
                     "hot-loop steps)")
            if mod.startswith("jax._src"):
                emit("internal-api", node,
                     f"jax._src import ({mod}) — internals drift between jax "
                     "versions; allowlist with the documented fallback")
        elif isinstance(node, (ast.Attribute, ast.Name)):
            name = _dotted(node)
            if name is None:
                continue
            if name.endswith("ragged_all_to_all"):
                emit("banned-api", node,
                     "ragged_all_to_all has no XLA:CPU thunk on the baked "
                     "toolchain — gate behind a transport probe and "
                     "allowlist the gated site")
            if name.startswith("jax._src"):
                emit("internal-api", node,
                     f"{name} — jax internals; allowlist with the "
                     "documented fallback")
        elif isinstance(node, ast.Call):
            fn = _dotted(node.func)
            if fn in ("jax.shard_map", "shard_map", "jax.experimental."
                      "shard_map.shard_map"):
                for kw in node.keywords:
                    if kw.arg == "auto":
                        emit("banned-api", kw.value,
                             "partial-auto shard_map through the legacy "
                             "auto= kwarg — name the manual axes with "
                             "axis_names= instead")


def _traced_rules(idx: _ModuleIndex, emit) -> None:
    for fn in idx.traced:
        params = idx.enclosing_traced_params(fn)
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        for stmt in body:
            for node in ast.walk(stmt):
                if isinstance(node, ast.Call):
                    _check_traced_call(node, params, emit)
                elif isinstance(node, (ast.If, ast.While)):
                    _check_traced_branch(node, idx.array_annotated(fn), emit)


def _check_traced_call(node: ast.Call, params: Set[str], emit) -> None:
    fn = _dotted(node.func)
    if isinstance(node.func, ast.Attribute) and not fn:
        # method on an arbitrary expression, e.g. metrics["loss"].item()
        if node.func.attr in _HOST_SYNC_METHODS and not node.args:
            emit("host-sync", node,
                 f".{node.func.attr}() synchronizes the host inside traced "
                 "code — return the array and sync outside the step")
        return
    if fn is None:
        return
    tail = fn.split(".")[-1]
    if fn in _HOST_SYNC_FUNCS:
        emit("host-sync", node,
             f"{fn}() inside traced code — host sync/wall clock has no "
             "meaning under tracing; hoist it out of the jitted step")
    elif tail in _HOST_SYNC_METHODS and fn not in ("jax.block_until_ready",):
        if not node.args and isinstance(node.func, ast.Attribute):
            emit("host-sync", node,
                 f".{tail}() synchronizes the host inside traced code")
    elif fn in _NUMPY_CONVERTERS:
        if any(isinstance(a, ast.Name) and a.id in params
               for a in node.args):
            emit("host-sync", node,
                 f"{fn}(<traced arg>) forces a device->host transfer inside "
                 "traced code — use jnp.asarray or keep it on device")
    elif fn in ("float", "int") and len(node.args) == 1:
        a = node.args[0]
        if isinstance(a, ast.Name) and a.id in params:
            emit("host-sync", node,
                 f"{fn}({a.id}) concretizes a traced value — it syncs (or "
                 "raises) under tracing; keep it an array")
    elif fn == "print":
        emit("host-sync", node,
             "print() inside traced code runs at trace time only — use "
             "jax.debug.print for runtime values")


def _check_traced_branch(node, array_names: Set[str], emit) -> None:
    hits: List[str] = []

    def scan(sub: ast.AST) -> None:
        # `x is None` / `x is not None` are trace-time static idioms —
        # skip those comparison subtrees wherever they appear in the test
        if isinstance(sub, ast.Compare) and any(
                isinstance(op, (ast.Is, ast.IsNot)) for op in sub.ops):
            return
        if isinstance(sub, ast.Name) and sub.id in array_names:
            hits.append(sub.id)
        elif isinstance(sub, ast.Call):
            fn = _dotted(sub.func) or ""
            if fn.startswith(_ARRAY_NAMESPACES):
                hits.append(fn)
        for child in ast.iter_child_nodes(sub):
            scan(child)

    scan(node.test)
    if hits:
        kind = "while" if isinstance(node, ast.While) else "if"
        emit("traced-branch", node,
             f"Python {kind} on traced value(s) {sorted(set(hits))} — "
             "use lax.cond / lax.while_loop / jnp.where")


def lint_paths(paths: Sequence[str],
               rules: Optional[Iterable[str]] = None) -> List[Finding]:
    """Lint files / directory trees (``*.py``, recursively)."""
    findings: List[Finding] = []
    files: List[Path] = []
    for p in paths:
        path = Path(p)
        if path.is_dir():
            files.extend(sorted(path.rglob("*.py")))
        else:
            files.append(path)
    for f in files:
        try:
            src = f.read_text()
        except OSError as e:
            findings.append(Finding(str(f), 0, 0, META_RULE,
                                    f"unreadable: {e}"))
            continue
        findings.extend(lint_source(src, str(f), rules=rules))
    return findings
