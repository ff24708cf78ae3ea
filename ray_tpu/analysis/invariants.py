"""Cross-module invariant engine — shardlint v2.

The per-file AST rules in `astlint` catch bugs a single screenful of
code can prove. The invariants here are different: each one is a
repo-wide convention whose violation is only visible when you look at
SEVERAL sites (or several modules) at once — the way race detectors and
aliasing analyses work in mature runtimes. Three rule families:

- **lock-discipline** (warning, per class): in a class that guards
  state with ``with self._lock`` (or a Condition wrapping it), every
  mutation of a ``self._*`` attribute must happen under the lock. An
  attribute mutated at least once under the lock and at least once
  outside it is a data race candidate: the finding cites both sites.
  ``__init__``/``__new__`` are exempt (no concurrent aliases exist
  yet), as are helpers that document the convention — a docstring
  containing "must hold" / "caller holds" naming the lock, or a
  ``*_locked`` name suffix. Deliberate lock-free reads/writes (e.g.
  monotonic counters read for telemetry) suppress with
  ``# shardlint: ok=lock-free`` plus a one-line justification.

- **env-knob registry** (warnings): every ``RAY_TPU_*`` environment
  read in the package, cross-referenced. Three rules:
  ``env-knob-inconsistent-default`` — one knob parsed with different
  literal defaults at different sites (the two sites WILL disagree
  someday); ``env-knob-hot-path`` — a knob parsed lexically inside a
  loop, or inside a same-module function that is called from inside a
  loop, without the cached-env pattern (``util/envknobs.py`` or an
  ``lru_cache``-decorated accessor); ``env-knob-undocumented`` — a
  knob missing from the README knob table. ``knob_table()`` emits the
  canonical registry (the README table is generated from it).

- **undonated-jit-pool-arg** (warning): the donation auditor,
  extending ``undonated-pool-write``. A jitted function that takes a
  pool/cache/slab/arena-shaped argument and builds an updated
  full-size copy (``arg.at[...].set``, ``dynamic_update_slice(arg,
  ...)``) without ``donate_argnums``/``donate_argnames`` pays an
  O(pool) copy per call; donation lets XLA update O(row) in place.

Pure stdlib (``ast`` + ``re``), no imports of the linted code — broken
or dependency-heavy modules still lint. Per-file families
(lock-discipline, undonated-jit-pool-arg) also run under
``astlint.lint_source``; the cross-module families run from
``analyze_invariants(package_root)`` — the ``ray_tpu analyze
--invariants`` CLI mode and the tier-1 self-lint suite.

Suppression works exactly like astlint: append ``# shardlint: ok``
(optionally ``ok=<reason>``, e.g. ``ok=lock-free``) or ``# shardlint:
disable=<rule-id>`` to the cited line.
"""
from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .findings import Finding, WARNING

# ------------------------------------------------------- lock-discipline

# Attribute names that denote a mutual-exclusion guard when used as
# `with self.<attr>`: locks, reentrant locks, conditions, mutexes.
_LOCKISH_RE = re.compile(r"lock|mutex|^_cv$|^cv$|cond", re.IGNORECASE)

# Method calls that mutate their receiver in place (list/set/dict/deque
# surface) — `self._xs.append(...)` is as much a write as `self._xs = `.
_MUTATOR_METHODS = frozenset({
    "append", "appendleft", "extend", "extendleft", "insert", "add",
    "remove", "discard", "pop", "popleft", "popitem", "clear", "update",
    "setdefault", "sort", "reverse",
})

# A helper documented to run under the caller's lock: its writes are
# locked by convention, not lexically.
_HOLDS_LOCK_RE = re.compile(r"must hold|caller holds|holding self\._",
                            re.IGNORECASE)


def _self_attr(expr: ast.AST) -> Optional[str]:
    """`self.<attr>` -> attr name, else None."""
    if isinstance(expr, ast.Attribute) and \
            isinstance(expr.value, ast.Name) and expr.value.id == "self":
        return expr.attr
    return None


def _self_attr_base(expr: ast.AST) -> Optional[str]:
    """The `self._x` at the root of a subscript/attribute chain:
    `self._d[k]`, `self._d[k][j]` -> `_d`."""
    while isinstance(expr, ast.Subscript):
        expr = expr.value
    return _self_attr(expr)


def _with_lock_items(node: ast.With, lockish: Set[str],
                     cond_aliases: Set[str]) -> bool:
    """True when any context manager of this With is a recognized lock:
    `self.<lockish>` or a local Condition alias bound from one."""
    for item in node.items:
        ctx = item.context_expr
        attr = _self_attr(ctx)
        if attr is not None and attr in lockish:
            return True
        if isinstance(ctx, ast.Name) and ctx.id in cond_aliases:
            return True
        # `self._lock.acquire()`-style context or `self._cv` wait forms
        if isinstance(ctx, ast.Call):
            recv = _self_attr(ctx.func.value) if isinstance(
                ctx.func, ast.Attribute) else None
            if recv is not None and recv in lockish:
                return True
    return False


@dataclass
class _AttrSites:
    locked: List[int] = field(default_factory=list)
    unlocked: List[int] = field(default_factory=list)


def _method_holds_lock_by_convention(fn: ast.AST) -> bool:
    name = getattr(fn, "name", "")
    if name.endswith("_locked"):
        return True
    doc = ast.get_docstring(fn) if isinstance(
        fn, (ast.FunctionDef, ast.AsyncFunctionDef)) else None
    return bool(doc and _HOLDS_LOCK_RE.search(doc))


def _collect_mutations(fn: ast.AST, lockish: Set[str],
                       cond_aliases: Set[str],
                       sites: Dict[str, _AttrSites]) -> None:
    """Walk one method, recording every `self._*` mutation with whether
    it is lexically under a recognized lock context."""

    def visit(node: ast.AST, locked: bool) -> None:
        if isinstance(node, ast.With):
            inner = locked or _with_lock_items(node, lockish,
                                               cond_aliases)
            for child in node.body:
                visit(child, inner)
            return
        attrs_lines: List[Tuple[str, int]] = []
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for tgt in targets:
                for sub in ast.walk(tgt):
                    attr = _self_attr_base(sub)
                    if attr is None and isinstance(sub, ast.Attribute):
                        attr = _self_attr(sub)
                    if attr is not None and attr.startswith("_") \
                            and attr not in lockish:
                        attrs_lines.append((attr, node.lineno))
        elif isinstance(node, ast.Delete):
            for tgt in node.targets:
                attr = _self_attr_base(tgt)
                if attr is not None and attr.startswith("_") \
                        and attr not in lockish:
                    attrs_lines.append((attr, node.lineno))
        elif isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr in _MUTATOR_METHODS:
            attr = _self_attr_base(node.func.value)
            if attr is not None and attr.startswith("_") \
                    and attr not in lockish:
                attrs_lines.append((attr, node.lineno))
        for attr, line in attrs_lines:
            rec = sites.setdefault(attr, _AttrSites())
            (rec.locked if locked else rec.unlocked).append(line)
        for child in ast.iter_child_nodes(node):
            visit(child, locked)

    if getattr(fn, "name", "") in ("__init__", "__new__"):
        return  # no concurrent aliases exist during construction
    held = _method_holds_lock_by_convention(fn)
    for child in ast.iter_child_nodes(fn):
        visit(child, held)


def lint_lock_discipline(tree: ast.AST, path: str) -> List[Finding]:
    """Per-class dataflow over `self._*` mutations in lock-using
    classes: any attribute mutated both under and outside the class's
    lock is a race candidate. One finding per unlocked site, citing a
    locked site, so each can be individually suppressed
    (`# shardlint: ok=lock-free`) with its own justification."""
    findings: List[Finding] = []
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        methods = [n for n in cls.body
                   if isinstance(n, (ast.FunctionDef,
                                     ast.AsyncFunctionDef))]
        # lock attrs: assigned a threading lock OR used as `with self.x`
        lockish: Set[str] = set()
        cond_aliases: Set[str] = set()
        for node in ast.walk(cls):
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Call):
                fname = node.value.func
                callee = fname.attr if isinstance(fname, ast.Attribute) \
                    else (fname.id if isinstance(fname, ast.Name)
                          else "")
                if callee in ("Lock", "RLock", "Condition", "Semaphore",
                              "BoundedSemaphore"):
                    for tgt in node.targets:
                        attr = _self_attr(tgt)
                        if attr is not None:
                            lockish.add(attr)
                        # local alias: cv = threading.Condition(self._l)
                        elif isinstance(tgt, ast.Name) and any(
                                _self_attr(a) is not None
                                for a in node.value.args):
                            cond_aliases.add(tgt.id)
            if isinstance(node, ast.With):
                for item in node.items:
                    attr = _self_attr(item.context_expr)
                    if attr is not None and _LOCKISH_RE.search(attr):
                        lockish.add(attr)
        guarded = {a for a in lockish if _LOCKISH_RE.search(a)}
        if not guarded:
            continue  # not a lock-disciplined class
        sites: Dict[str, _AttrSites] = {}
        for fn in methods:
            _collect_mutations(fn, lockish, cond_aliases, sites)
        for attr in sorted(sites):
            rec = sites[attr]
            if not rec.locked or not rec.unlocked:
                continue
            locked_at = min(rec.locked)
            for line in sorted(set(rec.unlocked)):
                findings.append(Finding(
                    "lock-discipline", WARNING, f"{path}:{line}",
                    f"{cls.name}.{attr} is mutated under the lock at "
                    f"{path}:{locked_at} but WITHOUT it here — a "
                    "concurrent caller can observe or lose this write",
                    "wrap the mutation in `with self._lock:` (or move "
                    "it into a locked helper); a deliberate lock-free "
                    "path suppresses with `# shardlint: ok=lock-free` "
                    "+ a one-line justification"))
    return findings


# ------------------------------------------------- undonated-jit-pool-arg

_POOLISH_ARG_RE = re.compile(r"pool|cache|slab|arena")


def lint_donation_audit(tree: ast.AST, aliases, path: str
                        ) -> List[Finding]:
    """Donation auditor: a jitted function taking a pool/cache/slab/
    arena-shaped argument and building an updated full-size copy of it
    without donate_argnums pays an O(pool) device copy every call —
    the same latent cost `undonated-pool-write` catches outside jits,
    now audited INSIDE the jit boundary where the donation belongs."""
    from .astlint import _is_donating_jit, _jitted_functions

    findings: List[Finding] = []
    for fn in _jitted_functions(tree, aliases):
        if any(_is_donating_jit(d, aliases) for d in fn.decorator_list):
            continue
        params = {a.arg for a in (fn.args.posonlyargs + fn.args.args +
                                  fn.args.kwonlyargs)
                  if _POOLISH_ARG_RE.search(a.arg.lower())}
        if not params:
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            # <param>.at[...].set/add(...)
            if isinstance(f, ast.Attribute) and f.attr in ("set", "add") \
                    and isinstance(f.value, ast.Subscript) \
                    and isinstance(f.value.value, ast.Attribute) \
                    and f.value.value.attr == "at" \
                    and isinstance(f.value.value.value, ast.Name) \
                    and f.value.value.value.id in params:
                pname = f.value.value.value.id
            # dynamic_update_slice(<param>, ...)
            elif ((isinstance(f, ast.Attribute)
                   and f.attr == "dynamic_update_slice")
                  or (isinstance(f, ast.Name)
                      and f.id == "dynamic_update_slice")) \
                    and node.args \
                    and isinstance(node.args[0], ast.Name) \
                    and node.args[0].id in params:
                pname = node.args[0].id
            else:
                continue
            findings.append(Finding(
                "undonated-jit-pool-arg", WARNING,
                f"{path}:{node.lineno}",
                f"jitted '{fn.name}' updates pool-shaped arg "
                f"'{pname}' without donating it — XLA materializes a "
                "full O(pool) copy per call instead of an in-place "
                "O(row) write",
                "add donate_argnums=<index of "
                f"'{pname}'> (functools.partial(jax.jit, "
                "donate_argnums=...)) and never reuse the donated "
                "buffer after the call"))
    return findings


# ------------------------------------------------------- env-knob registry

@dataclass(frozen=True)
class EnvRead:
    """One RAY_TPU_* environment read site."""

    knob: str
    path: str
    line: int
    default: Optional[str]     # literal default repr, None = no default
    required: bool             # os.environ[...] form (raises if unset)
    hot: bool                  # lexically in a loop / loop-called fn
    cached: bool               # lru_cache'd accessor or envknobs module


_CACHED_DECORATORS = frozenset({"lru_cache", "cache", "cached_property"})


def _is_cached_fn(fn: ast.AST) -> bool:
    for dec in getattr(fn, "decorator_list", []):
        node = dec.func if isinstance(dec, ast.Call) else dec
        name = node.attr if isinstance(node, ast.Attribute) else (
            node.id if isinstance(node, ast.Name) else "")
        if name in _CACHED_DECORATORS:
            return True
    return False


_KNOB_ACCESSORS = frozenset(
    {"get_str", "get_int", "get_float", "get_bool"})


def _env_key(node: ast.Call
             ) -> Optional[Tuple[str, Optional[str], bool, bool]]:
    """(knob, default_repr, required, cached) for env-read call forms:
    os.environ.get(K[, d]) / os.getenv(K[, d]), plus the cached
    util/envknobs accessors get_str/get_int/get_float/get_bool(K[, d])
    — recognizing the accessor keeps a migrated knob in the registry
    and marks the site as following the cached-env pattern."""
    f = node.func
    is_get = (isinstance(f, ast.Attribute) and f.attr == "get"
              and isinstance(f.value, ast.Attribute)
              and f.value.attr == "environ")
    is_getenv = (isinstance(f, ast.Attribute) and f.attr == "getenv")
    fname = f.attr if isinstance(f, ast.Attribute) else (
        f.id if isinstance(f, ast.Name) else "")
    is_knob_accessor = fname in _KNOB_ACCESSORS
    if not (is_get or is_getenv or is_knob_accessor) or not node.args:
        return None
    key = node.args[0]
    if not (isinstance(key, ast.Constant) and isinstance(key.value, str)
            and key.value.startswith("RAY_TPU_")):
        return None
    default: Optional[str] = None
    if len(node.args) > 1:
        d = node.args[1]
        default = repr(d.value) if isinstance(d, ast.Constant) \
            else "<dynamic>"
    return key.value, default, False, is_knob_accessor


def scan_env_reads(tree: ast.AST, path: str) -> List[EnvRead]:
    """Every RAY_TPU_* environment read in one module, annotated with
    loop/hot-path and caching context. Hot = lexically inside a
    for/while loop, or inside a function that the SAME module calls
    from inside a loop (one-hop: the `while not stop.wait(interval())`
    pattern)."""
    module_is_cache = path.replace(os.sep, "/").endswith(
        "util/envknobs.py")
    # names called from inside any loop body in this module
    loop_called: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    f = sub.func
                    name = f.id if isinstance(f, ast.Name) else (
                        f.attr if isinstance(f, ast.Attribute) else "")
                    if name:
                        loop_called.add(name)
    reads: List[EnvRead] = []

    def visit(node: ast.AST, in_loop: bool, cached: bool) -> None:
        if isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
            in_loop = True
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            cached = cached or _is_cached_fn(node)
            in_loop = node.name in loop_called
        if isinstance(node, ast.Call):
            hit = _env_key(node)
            if hit is not None:
                knob, default, required, via_accessor = hit
                reads.append(EnvRead(
                    knob, path, node.lineno, default, required,
                    hot=in_loop,
                    cached=cached or module_is_cache or via_accessor))
        elif isinstance(node, ast.Subscript):
            base, key = node.value, node.slice
            if isinstance(base, ast.Attribute) \
                    and base.attr == "environ" \
                    and isinstance(key, ast.Constant) \
                    and isinstance(key.value, str) \
                    and key.value.startswith("RAY_TPU_") \
                    and not isinstance(getattr(node, "ctx", None),
                                       (ast.Store, ast.Del)):
                reads.append(EnvRead(
                    key.value, path, node.lineno, None, True,
                    hot=in_loop, cached=cached or module_is_cache))
        for child in ast.iter_child_nodes(node):
            visit(child, in_loop, cached)

    visit(tree, False, False)
    return reads


def check_env_knobs(reads: Sequence[EnvRead],
                    readme_text: Optional[str] = None) -> List[Finding]:
    """Cross-module knob rules over the collected read sites."""
    findings: List[Finding] = []
    by_knob: Dict[str, List[EnvRead]] = {}
    for r in reads:
        by_knob.setdefault(r.knob, []).append(r)
    for knob in sorted(by_knob):
        sites = by_knob[knob]
        # inconsistent literal defaults across sites
        defaults = {}
        for r in sites:
            if r.default is not None and r.default != "<dynamic>" \
                    and not r.required:
                defaults.setdefault(r.default, r)
        if len(defaults) > 1:
            first = min(defaults.values(), key=lambda r: (r.path, r.line))
            cited = ", ".join(
                f"{r.path}:{r.line} default={d}"
                for d, r in sorted(defaults.items(), key=lambda kv: (
                    kv[1].path, kv[1].line)))
            findings.append(Finding(
                "env-knob-inconsistent-default", WARNING,
                f"{first.path}:{first.line}",
                f"{knob} is parsed with {len(defaults)} different "
                f"defaults: {cited} — whichever site runs first wins, "
                "silently",
                "route every read through ONE cached accessor in "
                "util/envknobs.py carrying the canonical default"))
        # hot-path parse without the cached-env pattern
        for r in sites:
            if r.hot and not r.cached:
                findings.append(Finding(
                    "env-knob-hot-path", WARNING, f"{r.path}:{r.line}",
                    f"{knob} is parsed inside a loop / per-tick path — "
                    "an environ dict probe plus str parse on every "
                    "iteration",
                    "hoist the read, or use the util/envknobs.py "
                    "cached accessor (parse memoized on the raw "
                    "string, still live-retunable)"))
        # knob absent from the README knob table
        if readme_text is not None and knob not in readme_text:
            first = min(sites, key=lambda r: (r.path, r.line))
            findings.append(Finding(
                "env-knob-undocumented", WARNING,
                f"{first.path}:{first.line}",
                f"{knob} is read here but appears nowhere in the "
                "README — an operator cannot discover it",
                "add it to the README environment-knob table "
                "(`ray_tpu analyze --invariants --knob-table` emits "
                "the canonical rows)"))
    return findings


def knob_table(reads: Sequence[EnvRead]) -> List[Dict[str, object]]:
    """The canonical env-knob registry: one row per knob with its
    default(s) and read sites — `analyze --invariants --json` embeds
    this, and the README table is generated from it."""
    by_knob: Dict[str, List[EnvRead]] = {}
    for r in reads:
        by_knob.setdefault(r.knob, []).append(r)
    rows = []
    for knob in sorted(by_knob):
        sites = by_knob[knob]
        defaults = sorted({r.default for r in sites
                           if r.default not in (None, "<dynamic>")})
        rows.append({
            "knob": knob,
            "default": defaults[0] if len(defaults) == 1 else (
                "(required)" if all(r.required for r in sites)
                else " / ".join(defaults) if defaults else "(unset)"),
            "required": all(r.required for r in sites),
            "sites": sorted({f"{r.path}:{r.line}" for r in sites}),
            "modules": sorted({os.path.basename(r.path)
                               for r in sites}),
        })
    return rows


def format_knob_table(rows: Sequence[Dict[str, object]],
                      root: Optional[str] = None) -> str:
    """Markdown knob table (the generated README section)."""
    out = ["| knob | default | read from |", "|---|---|---|"]
    for row in rows:
        mods = ", ".join(f"`{m}`" for m in row["modules"])
        out.append(f"| `{row['knob']}` | `{row['default']}` | {mods} |")
    return "\n".join(out)


# ---------------------------------------------------------------- driver

_SKIP_DIRS = frozenset({"__pycache__", "node_modules", "venv", "build",
                        "dist", "site-packages", "egg-info"})


def _iter_package_sources(package_root: str):
    for dirpath, dirnames, filenames in os.walk(package_root):
        dirnames[:] = [d for d in sorted(dirnames)
                       if d not in _SKIP_DIRS and not d.startswith(".")
                       and not d.endswith(".egg-info")]
        for name in sorted(filenames):
            if not name.endswith(".py"):
                continue
            full = os.path.join(dirpath, name)
            with open(full, encoding="utf-8", errors="replace") as fh:
                yield full, fh.read()


def _find_readme(package_root: str) -> Optional[str]:
    for base in (os.path.dirname(os.path.abspath(package_root)),
                 package_root):
        candidate = os.path.join(base, "README.md")
        if os.path.isfile(candidate):
            with open(candidate, encoding="utf-8",
                      errors="replace") as fh:
                return fh.read()
    return None


def collect_env_reads(package_root: str) -> List[EnvRead]:
    reads: List[EnvRead] = []
    for path, src in _iter_package_sources(package_root):
        try:
            tree = ast.parse(src)
        except SyntaxError:
            continue
        reads.extend(scan_env_reads(tree, path))
    return reads


def analyze_invariants(package_root: str,
                       readme_text: Optional[str] = None
                       ) -> List[Finding]:
    """Run the cross-module family over a package tree: the env-knob
    registry. (The per-file families — lock-discipline and the donation
    auditor — already run under `lint_path`/`lint_source`; running
    them here too would double-report.) Suppression comments on the
    cited lines are honored."""
    from .astlint import _suppressions

    findings: List[Finding] = []
    readme = readme_text if readme_text is not None \
        else _find_readme(package_root)
    findings.extend(check_env_knobs(collect_env_reads(package_root),
                                    readme))
    # honor per-line suppressions at each finding's cited site
    out: List[Finding] = []
    suppress_cache: Dict[str, Dict[int, Optional[Set[str]]]] = {}
    for f in findings:
        try:
            path, line_s = f.location.rsplit(":", 1)
            line = int(line_s)
        except ValueError:
            out.append(f)
            continue
        if path not in suppress_cache:
            try:
                with open(path, encoding="utf-8",
                          errors="replace") as fh:
                    suppress_cache[path] = _suppressions(fh.read())
            except OSError:
                suppress_cache[path] = {}
        rules = suppress_cache[path].get(line, "absent")
        if rules == "absent" or (rules is not None
                                 and f.rule not in rules):
            out.append(f)
    return out


__all__ = [
    "EnvRead", "analyze_invariants", "check_env_knobs",
    "collect_env_reads", "format_knob_table", "knob_table",
    "lint_donation_audit", "lint_lock_discipline", "scan_env_reads",
]
