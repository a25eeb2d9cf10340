"""Pass 1 of the two-pass analyzer: a project-wide symbol table and
approximate call graph (docs/ANALYSIS.md, "The call-graph engine").

`ProjectIndex.build(root)` summarizes every `.py` file under the
default scope (minio_tpu/) into a compact, JSON-serializable per-file
summary:

- module identity + import map (module-qualified def/use resolution);
- function definitions with their raw call targets;
- lock creation sites (threading.Lock/RLock/Condition bound to module
  globals or `self.<attr>`), `with <lock>:` regions with the calls and
  nested acquisitions inside them, and blocking `fcntl.flock` acquires
  (file locks are graph nodes too — MTPU007);
- parameter escape summaries: which params a function stores into an
  attribute or attribute-rooted container, and which it forwards to
  other calls (MTPU008's interprocedural sink check);
- `MTPU_*` environment reads with their static defaults (MTPU010);
- closed protocol registries (`*_OPS` / `*_RECORD_TYPES` /
  `*_REGISTRY` dict literals) and every module-qualified reference to
  their members (MTPU009).

The index is cached two ways so a warm `python -m tools.check` holds its
10 s budget and `--changed` stays a ~seconds pre-commit lane:

- on disk at `<root>/.mtpu-check-cache.json` keyed by each file's
  (mtime_ns, size) — only files that actually changed re-summarize;
- in process, memoized per root and revalidated by re-stat.

Resolution model (the documented approximations — see
docs/ANALYSIS.md for the full list):

- calls resolve through plain names (same-module defs, `from x import
  f`), import aliases (`mod.f`), `self.method` (same class only — no
  inheritance walk), and `ClassName.method` in the same module;
  anything receiver-typed (`self.drive.f()`, call results) does not
  resolve and contributes no edges;
- nested function bodies are skipped everywhere (deferred execution,
  same choice MTPU002 makes);
- a blocking `fcntl.flock(.., LOCK_EX)` with no later `LOCK_UN` in the
  same function marks the function as *returning while holding* that
  file lock; callers treat the rest of their body after such a call as
  running under it (until a `LOCK_UN` of their own). `LOCK_NB`
  acquires are trylocks and contribute no order edges.
"""

from __future__ import annotations

import ast
import json
import os
import re
from pathlib import Path

CACHE_NAME = ".mtpu-check-cache.json"
CACHE_VERSION = 5

_LOCK_CTORS = {"Lock", "RLock", "Condition"}
_REG_NAME_RE = re.compile(
    r"^[A-Z0-9_]*(?:_OPS|_RECORD_TYPES|_REGISTRY|_REASONS)$")
_REG_MEMBER_RE = re.compile(r"^(?:OP|REC|REASON|STATUS)_[A-Z0-9_]+$")
_ENV_NAME_RE = re.compile(r"^MTPU_[A-Z0-9_]*$")

_MEMO: dict[str, tuple[dict, "ProjectIndex"]] = {}


def _module_name(rel: str) -> str:
    parts = rel[:-3].split("/")  # strip .py
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


def _dotted(node: ast.expr) -> str | None:
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _call_target(func: ast.expr) -> tuple[str | None, str] | None:
    """(base, name) for a call target: `f()` -> (None, "f"),
    `mod.f()` -> ("mod", "f"), `self.a.f()` -> ("self.a", "f")."""
    if isinstance(func, ast.Name):
        return None, func.id
    if isinstance(func, ast.Attribute):
        base = _dotted(func.value)
        if base is None:
            return None
        return base, func.attr
    return None


def _lock_ctor_kind(node: ast.AST) -> str | None:
    """"Lock"/"RLock"/"Condition" when the value is a lock
    constructor call, else None."""
    if not isinstance(node, ast.Call):
        return None
    tgt = _call_target(node.func)
    if tgt is None:
        return None
    base, name = tgt
    if name in _LOCK_CTORS and base in (None, "threading"):
        return name
    return None


def _walk_skip_defs(body):
    stack = list(body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _lock_ref(expr: ast.expr) -> tuple[str, str] | None:
    """("self"|""|base, attr_or_name) for a with-item that could be a
    lock; None when the expression is not a name/attribute."""
    if isinstance(expr, ast.Name):
        return "", expr.id
    if isinstance(expr, ast.Attribute):
        base = _dotted(expr.value)
        if base is None:
            return None
        return base, expr.attr
    return None


def _line_text(lines: list[str], lineno: int) -> str:
    if 1 <= lineno <= len(lines):
        return lines[lineno - 1].strip()
    return ""


def _flock_kind(call: ast.Call) -> str | None:
    """"acquire" for a blocking LOCK_EX/LOCK_SH flock, "try" for
    LOCK_NB, "release" for LOCK_UN, None for non-flock calls."""
    tgt = _call_target(call.func)
    if tgt is None or tgt[1] != "flock":
        return None
    if len(call.args) < 2:
        return None
    names = {n.attr if isinstance(n, ast.Attribute) else getattr(n, "id", "")
             for n in ast.walk(call.args[1]) if isinstance(n, (ast.Attribute,
                                                               ast.Name))}
    if "LOCK_UN" in names:
        return "release"
    if "LOCK_NB" in names:
        return "try"
    if "LOCK_EX" in names or "LOCK_SH" in names:
        return "acquire"
    return None


_ENV_GETTERS = ("os.environ.get", "environ.get", "os.getenv", "getenv")


def _env_read(call: ast.Call,
              aliases: set[str]) -> tuple[dict, str | None] | None:
    """(name_spec, default_src) when the call reads an env var via
    os.environ.get / os.getenv / a local `env = os.environ.get` alias;
    None otherwise. name_spec is from _env_arg."""
    d = _dotted(call.func)
    is_get = (d in _ENV_GETTERS
              or (d is not None and d.endswith(".environ.get"))
              or (isinstance(call.func, ast.Name)
                  and call.func.id in aliases))
    if not is_get or not call.args:
        return None
    spec = _env_arg(call.args[0])
    if spec is None:
        return None
    default = None
    if len(call.args) > 1:
        try:
            default = ast.unparse(call.args[1])
        except Exception:  # pragma: no cover - unparse is total
            default = None
    return spec, default


def _env_arg(arg: ast.expr) -> dict | None:
    """Env-name argument: {"name": ..} for an MTPU_* str constant,
    {"name": .., "prefix": True} for an f-string whose leading literal
    names the MTPU_ prefix (a dynamic family like
    MTPU_DRIVE_DEADLINE_{cls}), {"ref": ..} for a name/attribute
    holding the knob's name (`ENABLE_ENV`-style constants, resolved
    against the project's string constants by the index)."""
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        if _ENV_NAME_RE.match(arg.value):
            return {"name": arg.value, "prefix": False}
        return None
    if isinstance(arg, ast.JoinedStr) and arg.values:
        head = arg.values[0]
        if isinstance(head, ast.Constant) and isinstance(head.value, str) \
                and head.value.startswith("MTPU_"):
            return {"name": head.value, "prefix": True}
        return None
    if isinstance(arg, ast.Name):
        return {"ref": arg.id}
    if isinstance(arg, ast.Attribute):
        return {"ref": arg.attr}
    return None


class _FileSummarizer:
    """One pass over a parsed module producing the summary dict."""

    def __init__(self, rel: str, tree: ast.Module, src: str):
        self.rel = rel
        self.tree = tree
        self.lines = src.splitlines()
        self.summary: dict = {
            "module": _module_name(rel),
            "imports": {},        # alias -> dotted module
            "from_imports": {},   # symbol -> dotted module it came from
            "classes": {},        # cls -> {"lock_attrs": {attr: line}}
            "functions": {},      # qual -> fn summary
            "module_locks": {},   # name -> line
            "env_reads": [],
            "registries": {},     # name -> {member: value}
            "registry_lines": {},
            "int_consts": {},     # NAME -> line (module level int literals)
            "str_consts": {},     # NAME -> "MTPU_..." (env-name consts)
            "reg_refs": [],
        }

    def run(self) -> dict:
        self._imports()
        self._module_level()
        for node in self.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._function(node, cls=None)
            elif isinstance(node, ast.ClassDef):
                self._class(node)
        self._reg_refs()
        self._env(self.tree.body, scope="")
        return self.summary

    # -- imports --------------------------------------------------------

    def _imports(self) -> None:
        pkg = self.summary["module"].rsplit(".", 1)[0] \
            if "." in self.summary["module"] else ""
        if self.rel.endswith("/__init__.py"):
            pkg = self.summary["module"]
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.asname:
                        self.summary["imports"][a.asname] = a.name
                    else:
                        head = a.name.split(".")[0]
                        self.summary["imports"][head] = head
            elif isinstance(node, ast.ImportFrom):
                if node.level:
                    base_parts = pkg.split(".") if pkg else []
                    up = node.level - 1
                    base_parts = base_parts[:len(base_parts) - up] \
                        if up else base_parts
                    mod = ".".join(base_parts + (
                        node.module.split(".") if node.module else []))
                else:
                    mod = node.module or ""
                for a in node.names:
                    if a.name == "*":
                        continue
                    local = a.asname or a.name
                    # `from a.b import c` binds c: either module a.b.c
                    # or a symbol defined in a.b — record both guesses,
                    # resolution tries module first.
                    self.summary["imports"][local] = f"{mod}.{a.name}"
                    self.summary["from_imports"][local] = mod

    # -- module level ---------------------------------------------------

    def _module_level(self) -> None:
        for node in self.tree.body:
            if not isinstance(node, ast.Assign):
                continue
            kind = _lock_ctor_kind(node.value)
            if kind is not None:
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        self.summary["module_locks"][tgt.id] = \
                            [node.lineno, kind]
            if (isinstance(node.value, ast.Constant)
                    and isinstance(node.value.value, int)
                    and not isinstance(node.value.value, bool)):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        self.summary["int_consts"][tgt.id] = node.lineno
            if (isinstance(node.value, ast.Constant)
                    and isinstance(node.value.value, str)
                    and node.value.value.startswith("MTPU_")):
                # `ENABLE_ENV = "MTPU_..."` knob-name constants: env
                # reads through them resolve via the index.
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        self.summary["str_consts"][tgt.id] = \
                            node.value.value
            if isinstance(node.value, ast.Dict) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and _REG_NAME_RE.match(node.targets[0].id):
                reg = self._parse_registry(node.value)
                if reg:
                    self.summary["registries"][node.targets[0].id] = reg
                    self.summary["registry_lines"][node.targets[0].id] = \
                        node.lineno
        # Tuple-unpack int consts (`A, B = 1, 2`) count too.
        for node in self.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Tuple) \
                    and isinstance(node.value, ast.Tuple) \
                    and len(node.targets[0].elts) == len(node.value.elts):
                for t, v in zip(node.targets[0].elts, node.value.elts):
                    if isinstance(t, ast.Name) and isinstance(v, ast.Constant) \
                            and isinstance(v.value, int) \
                            and not isinstance(v.value, bool):
                        self.summary["int_consts"][t.id] = node.lineno

    def _parse_registry(self, d: ast.Dict) -> dict | None:
        out: dict[str, int] = {}
        for k, v in zip(d.keys, d.values):
            if not (isinstance(k, ast.Constant) and isinstance(k.value, str)
                    and _REG_MEMBER_RE.match(k.value)):
                return None
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                out[k.value] = v.value
            elif isinstance(v, ast.Name):
                out[k.value] = -1  # resolved lazily; identity is the key
            else:
                return None
        return out or None

    # -- classes / functions --------------------------------------------

    def _class(self, node: ast.ClassDef) -> None:
        info = {"lock_attrs": {}, "line": node.lineno}
        self.summary["classes"][node.name] = info
        for stmt in node.body:
            if isinstance(stmt, ast.Assign):
                kind = _lock_ctor_kind(stmt.value)
                if kind is not None:
                    for tgt in stmt.targets:
                        if isinstance(tgt, ast.Name):
                            info["lock_attrs"][tgt.id] = \
                                [stmt.lineno, kind]
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for sub in _walk_skip_defs(stmt.body):
                    if isinstance(sub, ast.Assign):
                        kind = _lock_ctor_kind(sub.value)
                        if kind is None:
                            continue
                        for tgt in sub.targets:
                            if isinstance(tgt, ast.Attribute) \
                                    and isinstance(tgt.value, ast.Name) \
                                    and tgt.value.id == "self":
                                info["lock_attrs"][tgt.attr] = \
                                    [sub.lineno, kind]
                self._function(stmt, cls=node.name)

    def _function(self, node, cls: str | None) -> None:
        qual = f"{cls}.{node.name}" if cls else node.name
        args = node.args
        params = [a.arg for a in args.posonlyargs + args.args]
        fn: dict = {
            "line": node.lineno,
            "cls": cls or "",
            "params": params,
            "calls": [],          # [base|None, name, line]
            "regions": [],        # with-lock regions
            "flocks": [],         # [line, text]
            "flock_rel_line": None,  # first release (LOCK_UN) line
            "returns_holding": False,
            "param_stores": [],   # direct indices stored into attr/cont
            "param_passes": [],   # [param_idx, base|None, name, arg_idx]
        }
        self.summary["functions"][qual] = fn
        pidx = {p: i for i, p in enumerate(params)}

        last_acquire = None
        for sub in _walk_skip_defs(node.body):
            if isinstance(sub, ast.Call):
                fk = _flock_kind(sub)
                if fk == "acquire":
                    fn["flocks"].append(
                        [sub.lineno, _line_text(self.lines, sub.lineno)])
                    last_acquire = sub.lineno
                elif fk == "release":
                    if fn["flock_rel_line"] is None:
                        fn["flock_rel_line"] = sub.lineno
                tgt = _call_target(sub.func)
                if tgt is not None:
                    fn["calls"].append([tgt[0], tgt[1], sub.lineno])
                    for ai, a in enumerate(sub.args):
                        if isinstance(a, ast.Name) and a.id in pidx:
                            fn["param_passes"].append(
                                [pidx[a.id], tgt[0], tgt[1], ai])
            if isinstance(sub, ast.Assign):
                for tgt in sub.targets:
                    stored = None
                    if isinstance(sub.value, ast.Name) \
                            and sub.value.id in pidx:
                        stored = pidx[sub.value.id]
                    if stored is None:
                        continue
                    if isinstance(tgt, (ast.Attribute, ast.Subscript)):
                        if stored not in fn["param_stores"]:
                            fn["param_stores"].append(stored)
            if isinstance(sub, ast.Call) \
                    and isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr in ("append", "add", "insert",
                                          "appendleft", "setdefault"):
                recv = _dotted(sub.func.value)
                if recv and (recv.startswith("self.") or "." in recv):
                    for a in sub.args:
                        if isinstance(a, ast.Name) and a.id in pidx \
                                and pidx[a.id] not in fn["param_stores"]:
                            fn["param_stores"].append(pidx[a.id])
            if isinstance(sub, (ast.With, ast.AsyncWith)):
                self._with_region(sub, fn)
        release = fn["flock_rel_line"]
        if last_acquire is not None and (release is None
                                         or release < last_acquire):
            fn["returns_holding"] = True
        if fn["flocks"]:
            label = ""
            for sub in _walk_skip_defs(node.body):
                if isinstance(sub, ast.Constant) \
                        and isinstance(sub.value, str) \
                        and sub.value.endswith(".lock"):
                    label = sub.value
                    break
            fn["flock_label"] = label or qual
        self._env(node.body, scope=qual)

    def _with_region(self, node, fn: dict) -> None:
        for item in node.items:
            ref = _lock_ref(item.context_expr)
            if ref is None:
                continue
            region = {
                "lock": list(ref),
                "line": node.lineno,
                "text": _line_text(self.lines, node.lineno),
                "inner_locks": [],   # [[base, name], line, text]
                "inner_calls": [],   # [base|None, name, line]
                "inner_flocks": [],  # [line, text]
            }
            for sub in _walk_skip_defs(node.body):
                if isinstance(sub, (ast.With, ast.AsyncWith)):
                    for it in sub.items:
                        r2 = _lock_ref(it.context_expr)
                        if r2 is not None:
                            region["inner_locks"].append(
                                [list(r2), sub.lineno,
                                 _line_text(self.lines, sub.lineno)])
                elif isinstance(sub, ast.Call):
                    if _flock_kind(sub) == "acquire":
                        region["inner_flocks"].append(
                            [sub.lineno, _line_text(self.lines, sub.lineno)])
                    tgt = _call_target(sub.func)
                    if tgt is not None:
                        region["inner_calls"].append(
                            [tgt[0], tgt[1], sub.lineno])
            fn["regions"].append(region)

    # -- env reads ------------------------------------------------------

    def _env(self, body, scope: str) -> None:
        # Local `env = os.environ.get` aliases (hot-path idiom in
        # batcher/tier config loaders) make calls through the alias
        # env reads too.
        aliases: set[str] = set()
        for sub in _walk_skip_defs(body):
            if isinstance(sub, ast.Assign):
                d = _dotted(sub.value) if isinstance(
                    sub.value, ast.Attribute) else None
                if d in _ENV_GETTERS or (
                        d is not None and d.endswith(".environ.get")):
                    for tgt in sub.targets:
                        if isinstance(tgt, ast.Name):
                            aliases.add(tgt.id)

        def note(spec: dict | None, default, lineno: int) -> None:
            if spec is None:
                return
            spec = dict(spec)
            spec.update({"default": default, "line": lineno,
                         "text": _line_text(self.lines, lineno)})
            self.summary["env_reads"].append(spec)

        for sub in _walk_skip_defs(body):
            if isinstance(sub, ast.Call):
                got = _env_read(sub, aliases)
                if got is not None:
                    note(got[0], got[1], sub.lineno)
            elif isinstance(sub, ast.Subscript):
                if _dotted(sub.value) in ("os.environ", "environ") \
                        and isinstance(sub.ctx, ast.Load):
                    note(_env_arg(sub.slice), None, sub.lineno)
            elif isinstance(sub, ast.Compare) \
                    and len(sub.ops) == 1 \
                    and isinstance(sub.ops[0], (ast.In, ast.NotIn)) \
                    and _dotted(sub.comparators[0]) in ("os.environ",
                                                        "environ"):
                note(_env_arg(sub.left), None, sub.lineno)

    # -- registry references --------------------------------------------

    def _reg_refs(self) -> None:
        test_lines: set[int] = set()
        # Mark registry-member names appearing as Compare comparators /
        # match patterns ("dispatch tests").
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Compare):
                for cmp_ in [node.left] + list(node.comparators):
                    for n in ast.walk(cmp_):
                        nm = self._member_name(n)
                        if nm:
                            test_lines.add(id(n))
            if isinstance(node, ast.match_case):
                for n in ast.walk(node.pattern):
                    nm = self._member_name(n)
                    if nm:
                        test_lines.add(id(n))
        dict_keys: dict[int, int] = {}  # id(node) -> dict lineno
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Dict):
                for k in node.keys:
                    if k is None:
                        continue
                    for n in ast.walk(k):
                        if self._member_name(n):
                            dict_keys[id(n)] = node.lineno

        scopes: list[tuple[str, ast.AST]] = []
        for node in self.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scopes.append((node.name, node))
            elif isinstance(node, ast.ClassDef):
                for stmt in node.body:
                    if isinstance(stmt, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                        scopes.append((f"{node.name}.{stmt.name}", stmt))

        seen: set[int] = set()
        for qual, scope_node in scopes:
            for n in _walk_skip_defs(scope_node.body):
                self._note_ref(n, qual, test_lines, dict_keys, seen)
        for n in _walk_skip_defs(self.tree.body):
            self._note_ref(n, "", test_lines, dict_keys, seen)

    def _member_name(self, n: ast.AST) -> str | None:
        if isinstance(n, ast.Name) and _REG_MEMBER_RE.match(n.id):
            return n.id
        if isinstance(n, ast.Attribute) and _REG_MEMBER_RE.match(n.attr) \
                and _dotted(n.value) is not None:
            return n.attr
        return None

    def _note_ref(self, n: ast.AST, qual: str, test_ids: set[int],
                  dict_keys: dict[int, int], seen: set[int]) -> None:
        nm = self._member_name(n)
        if nm is None or id(n) in seen:
            return
        if isinstance(n, ast.Attribute) and not isinstance(
                n.ctx, ast.Load):
            return
        seen.add(id(n))
        base = None
        if isinstance(n, ast.Attribute):
            base = _dotted(n.value)
        kind = "plain"
        if id(n) in test_ids:
            kind = "test"
        elif id(n) in dict_keys:
            kind = "dictkey"
        self.summary["reg_refs"].append(
            {"base": base, "name": nm, "scope": qual,
             "line": n.lineno, "text": _line_text(self.lines, n.lineno),
             "kind": kind,
             "dict_line": dict_keys.get(id(n))})


def summarize_file(rel: str, src: str,
                   tree: ast.Module | None = None) -> dict:
    if tree is None:
        tree = ast.parse(src, filename=rel)
    return _FileSummarizer(rel, tree, src).run()


class ProjectIndex:
    """The cross-file view pass-2 rules resolve against."""

    def __init__(self, root: Path, files: dict[str, dict]):
        self.root = Path(root)
        self.files = files  # rel -> summary
        self._by_module: dict[str, str] = {
            s["module"]: rel for rel, s in files.items()}
        self._acq_memo: dict[str, frozenset] = {}
        self._store_memo: dict[tuple[str, str, int], bool] = {}

    # -- construction ---------------------------------------------------

    @classmethod
    def build(cls, root: Path, rels: list[str] | None = None,
              trees: dict[str, ast.Module] | None = None,
              use_cache: bool = True) -> "ProjectIndex":
        from tools.check import discover_files

        root = Path(root).resolve()
        if rels is None:
            rels = discover_files(root, None)
        stamps: dict[str, list] = {}
        for rel in rels:
            try:
                st = os.stat(root / rel)
                stamps[rel] = [st.st_mtime_ns, st.st_size]
            except OSError:
                continue

        key = str(root)
        memo = _MEMO.get(key)
        if use_cache and memo is not None and memo[0] == stamps:
            return memo[1]

        cache = cls._load_cache(root) if use_cache else {}
        files: dict[str, dict] = {}
        dirty = False
        for rel, stamp in stamps.items():
            row = cache.get(rel)
            if row is not None and row.get("stamp") == stamp:
                files[rel] = row["summary"]
                continue
            try:
                src = (root / rel).read_text()
                tree = (trees or {}).get(rel)
                files[rel] = summarize_file(rel, src, tree)
            except (OSError, SyntaxError, UnicodeDecodeError):
                continue  # run() reports parse errors on its own pass
            cache[rel] = {"stamp": stamp, "summary": files[rel]}
            dirty = True
        if use_cache and (dirty or set(cache) - set(stamps)):
            for gone in set(cache) - set(stamps):
                del cache[gone]
            cls._save_cache(root, cache)
        index = cls(root, files)
        if use_cache:
            _MEMO[key] = (stamps, index)
        return index

    @staticmethod
    def _cache_path(root: Path) -> Path:
        return Path(root) / CACHE_NAME

    @classmethod
    def _load_cache(cls, root: Path) -> dict:
        try:
            data = json.loads(cls._cache_path(root).read_text())
        except (OSError, ValueError):
            return {}
        if data.get("version") != CACHE_VERSION:
            return {}
        return data.get("files", {})

    @classmethod
    def _save_cache(cls, root: Path, cache: dict) -> None:
        try:
            cls._cache_path(root).write_text(
                json.dumps({"version": CACHE_VERSION, "files": cache}))
        except OSError:
            return  # cache is an optimization, never a requirement

    # -- resolution -----------------------------------------------------

    def module_file(self, dotted: str) -> str | None:
        return self._by_module.get(dotted)

    def resolve_module(self, rel: str, base: str) -> str | None:
        """The file a local name refers to when it names a module
        (import alias or from-import of a submodule)."""
        s = self.files.get(rel)
        if s is None:
            return None
        head = base.split(".")[0]
        dotted = s["imports"].get(head) or s["imports"].get(base)
        if dotted is None:
            return None
        if head != base and dotted == s["imports"].get(head):
            dotted = dotted + "." + ".".join(base.split(".")[1:])
        return self.module_file(dotted)

    def resolve_call(self, rel: str, cls: str, base: str | None,
                     name: str) -> tuple[str, str] | None:
        """(file, qual) of the called function, or None when the target
        does not resolve under the documented approximations."""
        s = self.files.get(rel)
        if s is None:
            return None
        if base is None:
            if name in s["functions"]:
                return rel, name
            src_mod = s["from_imports"].get(name)
            if src_mod is not None:
                src_rel = self.module_file(src_mod)
                if src_rel and name in self.files[src_rel]["functions"]:
                    return src_rel, name
            return None
        if base == "self" and cls:
            qual = f"{cls}.{name}"
            if qual in s["functions"]:
                return rel, qual
            return None
        if base in s["classes"]:
            qual = f"{base}.{name}"
            if qual in s["functions"]:
                return rel, qual
            # ClassName(...) constructor call resolves to __init__ via
            # the bare-name path below.
        mod_rel = self.resolve_module(rel, base)
        if mod_rel is not None:
            tgt = self.files[mod_rel]["functions"].get(name)
            if tgt is not None and not tgt["cls"]:
                return mod_rel, name
            if name in self.files[mod_rel]["classes"]:
                qual = f"{name}.__init__"
                if qual in self.files[mod_rel]["functions"]:
                    return mod_rel, qual
        return None

    def resolve_ctor(self, rel: str, name: str) -> tuple[str, str] | None:
        """`Name(...)` as a constructor: the class's __init__."""
        s = self.files.get(rel)
        if s is None:
            return None
        if name in s["classes"]:
            qual = f"{name}.__init__"
            if qual in s["functions"]:
                return rel, qual
        src_mod = s["from_imports"].get(name)
        if src_mod is not None:
            src_rel = self.module_file(src_mod)
            if src_rel and name in self.files[src_rel]["classes"]:
                qual = f"{name}.__init__"
                if qual in self.files[src_rel]["functions"]:
                    return src_rel, qual
        return None

    # -- locks ----------------------------------------------------------

    def _unique_lock_attr(self, attr: str) -> str | None:
        """Lock node id when exactly one class in the project creates a
        lock under this attribute name; None when absent or ambiguous."""
        hits = []
        for rel, s in self.files.items():
            for cls, info in s["classes"].items():
                if attr in info["lock_attrs"]:
                    hits.append(f"{rel}:{cls}.{attr}")
        return hits[0] if len(hits) == 1 else None

    def resolve_lock(self, rel: str, cls: str,
                     ref: tuple[str, str]) -> str | None:
        """Node id `file:Class.attr` / `file:name` for a lock
        reference, or None when it is not a known lock."""
        base, name = ref
        s = self.files.get(rel)
        if s is None:
            return None
        if base == "":
            if name in s["module_locks"]:
                return f"{rel}:{name}"
            return None
        if base == "self":
            if cls and name in s["classes"].get(cls, {}).get(
                    "lock_attrs", {}):
                return f"{rel}:{cls}.{name}"
            return self._unique_lock_attr(name)
        # `other._mu`: resolve only when the attribute name is a lock
        # attr of exactly one project class (documented approximation).
        return self._unique_lock_attr(name)

    def lock_kind(self, node: str) -> str | None:
        """"Lock"/"RLock"/"Condition" for a resolved lock node id."""
        rel, _, ident = node.partition(":")
        s = self.files.get(rel)
        if s is None:
            return None
        if "." in ident:
            cls, attr = ident.split(".", 1)
            row = s["classes"].get(cls, {}).get("lock_attrs", {}) \
                .get(attr)
        else:
            row = s["module_locks"].get(ident)
        return row[1] if row else None

    def flock_node(self, rel: str, qual: str) -> str:
        """File-lock node identity: labeled by the `.lock`-suffixed
        string constant the function mentions (the lock file it opens),
        else by the function itself."""
        s = self.files.get(rel)
        fn = s["functions"].get(qual) if s else None
        label = (fn or {}).get("flock_label") or qual
        return f"{rel}:flock({label})"

    def transitive_acquires(self, rel: str, qual: str,
                            depth: int = 4) -> frozenset:
        """Lock nodes this function may acquire, following resolved
        call edges to bounded depth. Memoized."""
        key = f"{rel}::{qual}"
        memo = self._acq_memo.get(key)
        if memo is not None:
            return memo
        self._acq_memo[key] = frozenset()  # cycle guard
        out: set[str] = set()
        s = self.files.get(rel)
        fn = s["functions"].get(qual) if s else None
        if fn is None:
            return frozenset()
        for region in fn["regions"]:
            node = self.resolve_lock(rel, fn["cls"],
                                     tuple(region["lock"]))
            if node:
                out.add(node)
        if fn["flocks"]:
            out.add(self.flock_node(rel, qual))
        if depth > 0:
            for base, name, _line in fn["calls"]:
                tgt = self.resolve_call(rel, fn["cls"], base, name) \
                    or (self.resolve_ctor(rel, name) if base is None
                        else None)
                if tgt is not None:
                    out |= self.transitive_acquires(tgt[0], tgt[1],
                                                    depth - 1)
        result = frozenset(out)
        self._acq_memo[key] = result
        return result

    # -- parameter escapes (MTPU008) ------------------------------------

    def param_escapes(self, rel: str, qual: str, idx: int,
                      depth: int = 3) -> bool:
        """True when param `idx` of the function is stored into an
        attribute or attribute-rooted container, directly or through a
        resolved forwarding call (bounded depth)."""
        key = (rel, qual, idx)
        memo = self._store_memo.get(key)
        if memo is not None:
            return memo
        self._store_memo[key] = False  # cycle guard
        s = self.files.get(rel)
        fn = s["functions"].get(qual) if s else None
        if fn is None:
            return False
        if idx in fn["param_stores"]:
            self._store_memo[key] = True
            return True
        if depth > 0:
            for pi, base, name, ai in fn["param_passes"]:
                if pi != idx:
                    continue
                tgt = self.resolve_call(rel, fn["cls"], base, name) \
                    or (self.resolve_ctor(rel, name) if base is None
                        else None)
                if tgt is None:
                    continue
                # Methods' self occupies param 0.
                callee = self.files[tgt[0]]["functions"][tgt[1]]
                shift = 1 if callee["cls"] and base != tgt[1].split(
                    ".")[0] else 0
                if self.param_escapes(tgt[0], tgt[1], ai + shift,
                                      depth - 1):
                    self._store_memo[key] = True
                    return True
        return False

    # -- env reads (MTPU010) --------------------------------------------

    def env_reads(self):
        """Yield (rel, read) for every resolved MTPU_* env read: reads
        through a name constant (`ENABLE_ENV`-style) resolve against
        the defining module's string constants first, then against a
        project-unique constant name."""
        global_consts: dict[str, str | None] = {}
        for s in self.files.values():
            for cname, val in s["str_consts"].items():
                if cname in global_consts and global_consts[cname] != val:
                    global_consts[cname] = None  # ambiguous
                else:
                    global_consts[cname] = val
        for rel in sorted(self.files):
            s = self.files[rel]
            for read in s["env_reads"]:
                if "ref" in read:
                    val = s["str_consts"].get(read["ref"]) \
                        or global_consts.get(read["ref"])
                    if val is None:
                        continue  # not provably an MTPU_* knob
                    read = {**read, "name": val, "prefix": False}
                yield rel, read

    # -- registries (MTPU009) -------------------------------------------

    def registries(self) -> dict[str, tuple[str, dict]]:
        """registry name -> (defining file, {member: value})."""
        out: dict[str, tuple[str, dict]] = {}
        for rel, s in self.files.items():
            for name, members in s["registries"].items():
                out[name] = (rel, members)
        return out

    def member_home(self, rel: str, base: str | None,
                    name: str) -> str | None:
        """The registry-defining file a member reference resolves to,
        or None for same-named constants from unrelated modules."""
        s = self.files.get(rel)
        if s is None:
            return None
        target_rel: str | None = None
        if base is None:
            src_mod = s["from_imports"].get(name)
            target_rel = self.module_file(src_mod) if src_mod else rel
        else:
            target_rel = self.resolve_module(rel, base)
        if target_rel is None:
            return None
        ts = self.files.get(target_rel)
        if ts is None:
            return None
        for members in ts["registries"].values():
            if name in members:
                return target_rel
        return None
