"""tdnlint core: project index, findings, suppressions, baseline, runner.

The analyzer is stdlib-only (``ast`` + ``tokenize``-free line scans) and
deliberately project-shaped: it knows this repo's idioms (the
``RuntimeSampler`` tick, ``MetricsServer`` route mounting, the metric
registry) so its five rules can encode invariants a generic linter
cannot express. See docs/STATIC_ANALYSIS.md for the rule catalog and
the suppression / baseline workflow.

Vocabulary the rules share:

* **Finding** — one violation: rule id, file, line, enclosing symbol,
  a stable ``detail`` discriminator, and a human message. Its
  ``fingerprint`` (rule:path:symbol:detail) is deliberately
  line-number-free so a baseline survives unrelated edits to the file.
* **Suppression** — ``# tdnlint: disable=<rule>[,<rule>...]`` (or
  ``disable=all``) on the first line of the flagged statement.
* **Baseline** — ``baseline.json`` next to this package: grandfathered
  findings, each with a one-line justification. Non-baselined findings
  fail the run; stale entries (matching nothing) are reported so the
  file cannot rot.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import re

class LintError(Exception):
    """A scan target could not be read or parsed. Raised (not
    SystemExit) so library callers — run_lint from cli.py and the
    tests — can degrade instead of dying; only
    tdnlint.main() converts it to an exit code."""


_DISABLE_RE = re.compile(r"#\s*tdnlint:\s*disable=([A-Za-z0-9_,\- ]+)")
_GUARDED_RE = re.compile(r"#\s*guarded-by:\s*([A-Za-z_][A-Za-z0-9_]*)")
_HOLDS_RE = re.compile(r"#\s*caller-holds:\s*([A-Za-z_][A-Za-z0-9_]*)")


@dataclasses.dataclass
class Finding:
    rule: str
    path: str  # scan-root-relative, posix separators
    line: int
    symbol: str  # enclosing qualname ("Autoscaler.tick", "<module>")
    detail: str  # stable discriminator (attr name, family name, ...)
    message: str

    @property
    def fingerprint(self) -> str:
        return f"{self.rule}:{self.path}:{self.symbol}:{self.detail}"

    def render(self) -> str:
        return (
            f"{self.path}:{self.line}: [{self.rule}] {self.message}"
        )


@dataclasses.dataclass
class FuncInfo:
    """One function or method (nested functions included, with
    ``parent.<locals>.name`` qualnames)."""

    name: str
    qualname: str
    node: ast.AST  # FunctionDef | AsyncFunctionDef
    module: "Module"
    class_name: str | None = None  # owning class for methods


@dataclasses.dataclass
class ClassInfo:
    name: str
    node: ast.ClassDef
    module: "Module"
    bases: list  # base-class name strings (best effort)
    methods: dict  # name -> FuncInfo
    # lock-discipline annotations: attr name -> lock name
    guarded: dict = dataclasses.field(default_factory=dict)


class Module:
    """One parsed source file plus its line-keyed comment directives."""

    def __init__(self, path: str, relpath: str, source: str):
        self.path = path
        self.relpath = relpath.replace(os.sep, "/")
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        # line -> set of disabled rule ids ("all" disables every rule)
        self.disable: dict[int, set] = {}
        # line -> "guarded-by" lock name / "caller-holds" lock name
        self.guarded_by_line: dict[int, str] = {}
        self.holds_by_line: dict[int, str] = {}
        for i, text in enumerate(self.lines, start=1):
            if "#" not in text:
                continue
            m = _DISABLE_RE.search(text)
            if m:
                self.disable[i] = {
                    r.strip() for r in m.group(1).split(",") if r.strip()
                }
            m = _GUARDED_RE.search(text)
            if m:
                self.guarded_by_line[i] = m.group(1)
            m = _HOLDS_RE.search(text)
            if m:
                self.holds_by_line[i] = m.group(1)
        # import map: local name -> ("module", "pkg.mod") for
        # ``import pkg.mod [as name]``, ("symbol", "pkg.mod", "sym")
        # for ``from pkg.mod import sym [as name]``.
        self.imports: dict[str, tuple] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    local = alias.asname or alias.name.split(".")[0]
                    self.imports[local] = ("module", alias.name)
            elif isinstance(node, ast.ImportFrom) and node.module:
                for alias in node.names:
                    local = alias.asname or alias.name
                    self.imports[local] = (
                        "symbol", node.module, alias.name
                    )
        self.functions: dict[str, FuncInfo] = {}
        self.classes: dict[str, ClassInfo] = {}
        self._index()

    def _index(self) -> None:
        def walk_func(node, qual_prefix, class_name):
            qual = (
                f"{qual_prefix}.{node.name}" if qual_prefix else node.name
            )
            info = FuncInfo(node.name, qual, node, self, class_name)
            self.functions[qual] = info
            for child in node.body:
                if isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    walk_func(child, f"{qual}.<locals>", None)
            return info

        for node in self.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                walk_func(node, "", None)
            elif isinstance(node, ast.ClassDef):
                bases = []
                for b in node.bases:
                    if isinstance(b, ast.Name):
                        bases.append(b.id)
                    elif isinstance(b, ast.Attribute):
                        bases.append(b.attr)
                ci = ClassInfo(node.name, node, self, bases, {})
                self.classes[node.name] = ci
                for child in node.body:
                    if isinstance(
                        child, (ast.FunctionDef, ast.AsyncFunctionDef)
                    ):
                        fi = walk_func(child, node.name, node.name)
                        ci.methods[child.name] = fi
                self._collect_guarded(ci)

    def _collect_guarded(self, ci: ClassInfo) -> None:
        """Attach ``# guarded-by:`` annotations to the attributes whose
        (first) assignment line carries them — class-body attributes
        and ``self.X = ...`` statements in any method both count."""

        def note(stmt, attr_names):
            # Trailing comment on the assignment's first line, or a
            # PURE comment line directly above it (multi-target
            # assigns) — a previous statement's trailing comment must
            # not leak onto the next attribute.
            lock = self.guarded_by_line.get(stmt.lineno)
            if not lock:
                above = stmt.lineno - 1
                if 1 <= above <= len(self.lines) and self.lines[
                    above - 1
                ].strip().startswith("#"):
                    lock = self.guarded_by_line.get(above)
            if lock:
                for a in attr_names:
                    ci.guarded.setdefault(a, lock)

        for node in ast.walk(ci.node):
            if isinstance(node, ast.Assign):
                attrs = []
                for t in node.targets:
                    if isinstance(t, ast.Attribute) and isinstance(
                        t.value, ast.Name
                    ) and t.value.id in ("self", "cls"):
                        attrs.append(t.attr)
                    elif isinstance(t, ast.Name):
                        attrs.append(t.id)  # class-body attribute
                if attrs:
                    note(node, attrs)
            elif isinstance(node, ast.AnnAssign):
                t = node.target
                if isinstance(t, ast.Attribute) and isinstance(
                    t.value, ast.Name
                ) and t.value.id in ("self", "cls"):
                    note(node, [t.attr])
                elif isinstance(t, ast.Name):
                    note(node, [t.id])

    def suppressed(self, rule: str, line: int) -> bool:
        rules = self.disable.get(line)
        return bool(rules) and (rule in rules or "all" in rules)


class Project:
    """Every module under the scan roots, plus cross-module indexes."""

    def __init__(self, roots):
        self.modules: list[Module] = []
        self.by_modname: dict[str, Module] = {}
        for root in roots:
            root = os.path.abspath(root)
            base = os.path.basename(root.rstrip(os.sep))
            if os.path.isfile(root):
                self._load(root, base)
                continue
            for dirpath, dirnames, filenames in os.walk(root):
                dirnames[:] = sorted(
                    d for d in dirnames
                    if d != "__pycache__" and not d.startswith(".")
                )
                for fname in sorted(filenames):
                    if not fname.endswith(".py"):
                        continue
                    path = os.path.join(dirpath, fname)
                    self._load(
                        path,
                        os.path.join(base, os.path.relpath(path, root)),
                    )
        # method name -> [(ClassInfo, FuncInfo)] across the project
        self.method_index: dict[str, list] = {}
        # class name -> [ClassInfo]
        self.class_index: dict[str, list] = {}
        for mod in self.modules:
            for ci in mod.classes.values():
                self.class_index.setdefault(ci.name, []).append(ci)
                for name, fi in ci.methods.items():
                    self.method_index.setdefault(name, []).append(
                        (ci, fi)
                    )

    def _load(self, path: str, rel: str) -> None:
        try:
            with open(path, encoding="utf-8") as f:
                source = f.read()
            mod = Module(path, rel, source)
        except (OSError, SyntaxError, ValueError) as e:
            raise LintError(f"cannot parse {path}: {e}") from e
        self.modules.append(mod)
        # dotted module name guess from the relpath (import resolution)
        dotted = mod.relpath[:-3].replace("/", ".")
        if dotted.endswith(".__init__"):
            dotted = dotted[: -len(".__init__")]
        self.by_modname[dotted] = mod

    def resolve_module(self, dotted: str) -> Module | None:
        """A project module by dotted name, tolerating the scan root
        being a package prefix (``tpu_dist_nn.obs.slo`` resolves when
        the scan indexed ``tpu_dist_nn/obs/slo.py``)."""
        if dotted in self.by_modname:
            return self.by_modname[dotted]
        for name, mod in self.by_modname.items():
            if dotted.endswith("." + name) or name.endswith("." + dotted):
                return mod
        return None

    def resolve_imported_function(self, mod: Module,
                                  local: str) -> FuncInfo | None:
        """``from pkg.mod import f`` -> the project FuncInfo for f."""
        entry = mod.imports.get(local)
        if not entry or entry[0] != "symbol":
            return None
        target = self.resolve_module(entry[1])
        if target is None:
            return None
        return target.functions.get(entry[2])

    def resolve_imported_class(self, mod: Module,
                               local: str) -> ClassInfo | None:
        entry = mod.imports.get(local)
        if not entry or entry[0] != "symbol":
            return None
        target = self.resolve_module(entry[1])
        if target is None:
            return None
        return target.classes.get(entry[2])


# --------------------------------------------------------------- helpers


def call_name(node: ast.Call):
    """-> ("name", n) | ("attr", receiver_node, attr) | None."""
    f = node.func
    if isinstance(f, ast.Name):
        return ("name", f.id)
    if isinstance(f, ast.Attribute):
        return ("attr", f.value, f.attr)
    return None


def attr_root(node) -> str | None:
    """Leftmost Name of an attribute chain (``a.b.c`` -> "a")."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def enclosing_symbol(mod: Module, line: int) -> str:
    """Qualname of the innermost function/class containing ``line``."""
    best = "<module>"
    best_span = None
    for qual, fi in mod.functions.items():
        node = fi.node
        end = getattr(node, "end_lineno", node.lineno)
        if node.lineno <= line <= end:
            span = end - node.lineno
            if best_span is None or span < best_span:
                best, best_span = qual, span
    if best == "<module>":
        for name, ci in mod.classes.items():
            end = getattr(ci.node, "end_lineno", ci.node.lineno)
            if ci.node.lineno <= line <= end:
                return name
    return best


def iter_body_nodes(func_node, *, skip_nested: bool = True):
    """Walk a function body; by default do NOT descend into nested
    function/lambda bodies (they execute later — off the path being
    analyzed — and get edges only when called by name)."""
    stack = list(ast.iter_child_nodes(func_node))
    while stack:
        node = stack.pop()
        if skip_nested and isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        ):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def local_bindings(func_node) -> dict:
    """Name -> the ast node it was last assigned from (Call nodes kept;
    everything else maps to None, meaning "locally bound, type
    unknown"). For-targets, comprehension targets, and with-as targets
    all count as local bindings."""
    out: dict[str, ast.AST | None] = {}
    for node in iter_body_nodes(func_node):
        if isinstance(node, ast.Assign):
            value = node.value if isinstance(node.value, ast.Call) \
                else None
            for t in node.targets:
                if isinstance(t, ast.Name):
                    out.setdefault(t.id, value)
                elif isinstance(t, (ast.Tuple, ast.List)):
                    for e in t.elts:
                        if isinstance(e, ast.Name):
                            out.setdefault(e.id, None)
        elif isinstance(node, ast.AnnAssign) and isinstance(
            node.target, ast.Name
        ):
            out.setdefault(
                node.target.id,
                node.value if isinstance(node.value, ast.Call) else None,
            )
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            t = node.target
            names = [t] if isinstance(t, ast.Name) else (
                t.elts if isinstance(t, (ast.Tuple, ast.List)) else []
            )
            for e in names:
                if isinstance(e, ast.Name):
                    out.setdefault(e.id, None)
        elif isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                if isinstance(item.optional_vars, ast.Name):
                    out.setdefault(item.optional_vars.id, None)
        elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                               ast.GeneratorExp)):
            for gen in node.generators:
                if isinstance(gen.target, ast.Name):
                    out.setdefault(gen.target.id, None)
    return out


# --------------------------------------------------------------- baseline


def load_baseline(path: str) -> dict:
    """-> {fingerprint: justification}; empty file/missing = empty."""
    if not path or not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    out = {}
    for entry in doc.get("findings", ()):
        out[entry["fingerprint"]] = entry.get("justification", "")
    return out


def save_baseline(path: str, findings, old: dict) -> None:
    entries = []
    seen = set()
    for f in findings:
        if f.fingerprint in seen:
            continue
        seen.add(f.fingerprint)
        entries.append({
            "fingerprint": f.fingerprint,
            "justification": old.get(
                f.fingerprint, "TODO: justify this grandfathered finding"
            ),
        })
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"version": 1, "findings": entries}, f, indent=2)
        f.write("\n")


# ----------------------------------------------------------------- runner


def run_lint(paths, *, rules=None, baseline_path: str | None = None):
    """Parse ``paths``, run every (or the named) rules, split findings
    against the baseline. -> dict with ``new``, ``baselined``,
    ``stale_baseline``, ``suppressed_total``, ``files``."""
    from . import rules as rules_mod

    project = Project(paths)
    selected = rules_mod.RULES if rules is None else {
        k: v for k, v in rules_mod.RULES.items() if k in rules
    }
    raw: list[Finding] = []
    for rule_id, rule_fn in selected.items():
        raw.extend(rule_fn(project))
    findings = []
    suppressed = 0
    mod_by_rel = {m.relpath: m for m in project.modules}
    for f in sorted(raw, key=lambda f: (f.path, f.line, f.rule)):
        mod = mod_by_rel.get(f.path)
        if mod is not None and mod.suppressed(f.rule, f.line):
            suppressed += 1
            continue
        findings.append(f)
    baseline = load_baseline(baseline_path) if baseline_path else {}
    new = [f for f in findings if f.fingerprint not in baseline]
    matched = {f.fingerprint for f in findings} & set(baseline)
    stale = sorted(set(baseline) - matched)
    return {
        "new": new,
        "all": findings,
        "baselined": sorted(matched),
        "baseline": baseline,
        "stale_baseline": stale,
        "suppressed_total": suppressed,
        "files": len(project.modules),
    }
