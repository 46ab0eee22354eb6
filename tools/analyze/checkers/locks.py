"""locks: shared mutable state must be read/written under its lock.

The serving tier shares one re-entrant memo lock
(``repro.core.memo.MEMO_LOCK``) across every costing-stack cache, and
the per-object locks of ``_SessionState`` / ``ScoringShardPool`` guard
their session/shard bookkeeping.  PR 4/8 established the discipline;
this checker makes it mechanical:

* inside a guarded class, every ``self.<field>`` access of a registered
  shared field must sit lexically inside ``with <lock>:``;
* the guarded module globals (``memo.REGISTRY``; devicecost's interning
  tables and shard-threshold state, writes only — their unlocked reads
  are deliberate CPython-safe fast paths) must be accessed under
  ``MEMO_LOCK``; the span table ``spans._TOTALS`` under ``spans._LOCK``.

``__init__`` is exempt (no concurrent aliases exist yet).  A genuinely
safe unlocked access carries ``# lint: unlocked(<reason>)`` — the
reason is mandatory and shows up in review.

Scope is honest: dominance is *lexical* (a ``with`` in the same
function).  Helpers called only under a caller's lock document that
with a suppression, e.g. service.py's ``_engine_state``.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set

from tools.analyze.core import Finding, ModuleRecord
from tools.analyze.dataflow import (build_parents, dotted,
                                    enclosing_function, under_lock)

NAME = "locks"

RULES = {
    "unlocked": "shared field/global accessed outside its lock",
}

#: spellings of the shared memo lock across modules
_MEMO_LOCKS = {"MEMO_LOCK", "memo.MEMO_LOCK", "memo_module.MEMO_LOCK"}

#: class name -> (lock spellings, guarded instance fields)
GUARDED_CLASSES: Dict[str, Dict] = {
    "DictCache": {"locks": _MEMO_LOCKS,
                  "fields": {"_data", "_hits", "_misses"}},
    "_SessionState": {"locks": {"self._lock"},
                      "fields": {"frontiers"}},
    "ScoringShardPool": {"locks": {"self._lock"},
                         "fields": {"_counters", "events", "_state",
                                    "_lost", "_epoch", "_pool"}},
    "DesignCalculatorService": {"locks": {"self._lock"},
                                "fields": {"_engine_health", "_sessions",
                                           "_stats"}},
}

#: guarded module-level globals: bare name -> config.  The bare-name rule
#: applies in the owner module and anywhere the name is imported from it;
#: the dotted spellings apply everywhere.
GUARDED_GLOBALS: Dict[str, Dict] = {
    "REGISTRY": {"owner": "repro.core.memo", "locks": _MEMO_LOCKS,
                 "writes_only": False,
                 "dotted": {"memo.REGISTRY", "memo_module.REGISTRY"}},
    "_MODEL_IDS": {"owner": "repro.core.devicecost", "locks": _MEMO_LOCKS,
                   "writes_only": True,
                   "dotted": {"devicecost._MODEL_IDS"}},
    "_MODEL_NAMES": {"owner": "repro.core.devicecost",
                     "locks": _MEMO_LOCKS, "writes_only": True,
                     "dotted": {"devicecost._MODEL_NAMES"}},
    "_TOTALS": {"owner": "repro.core.spans",
                "locks": {"_LOCK", "spans._LOCK"}, "writes_only": False,
                "dotted": {"spans._TOTALS"}},
}

#: container-method calls that mutate the receiver
_MUTATORS = {"append", "extend", "insert", "remove", "pop", "popitem",
             "clear", "update", "setdefault", "move_to_end",
             "appendleft", "add", "discard"}


def _owner_module(relpath: str) -> str:
    """``src/repro/core/memo.py`` -> ``repro.core.memo`` (best effort)."""
    p = relpath[:-3] if relpath.endswith(".py") else relpath
    parts = p.split("/")
    if parts and parts[0] == "src":
        parts = parts[1:]
    return ".".join(parts)


def _imported_from(tree: ast.Module) -> Dict[str, str]:
    """imported name -> source module, for ``from X import a, b``."""
    out: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            for alias in node.names:
                out[alias.asname or alias.name] = node.module
    return out


def _is_write(node: ast.AST, parents: Dict[ast.AST, ast.AST]) -> bool:
    """Does this Name/Attribute access mutate the referenced object?

    Store/Del contexts, stores through a subscript (``X[k] = v``), and
    mutating method calls (``X.append(...)``) count as writes."""
    ctx = getattr(node, "ctx", None)
    if isinstance(ctx, (ast.Store, ast.Del)):
        return True
    parent = parents.get(node)
    if isinstance(parent, ast.Subscript) and parent.value is node and \
            isinstance(parent.ctx, (ast.Store, ast.Del)):
        return True
    if isinstance(parent, ast.Attribute) and parent.value is node and \
            parent.attr in _MUTATORS:
        grand = parents.get(parent)
        if isinstance(grand, ast.Call) and grand.func is parent:
            return True
    return False


def _check_class(cls: ast.ClassDef, cfg: Dict, mod: ModuleRecord,
                 parents: Dict[ast.AST, ast.AST]) -> Iterable[Finding]:
    fields: Set[str] = cfg["fields"]
    locks: Set[str] = cfg["locks"]
    for method in cls.body:
        if not isinstance(method, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if method.name == "__init__":
            continue   # no concurrent aliases during construction
        for node in ast.walk(method):
            if not (isinstance(node, ast.Attribute)
                    and node.attr in fields
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "self"):
                continue
            # nested defs (executor thunks, callbacks) are still methods
            # of the same object: the lock requirement stands
            if under_lock(node, parents, locks):
                continue
            kind = "write of" if _is_write(node, parents) else "read of"
            yield Finding(
                mod.relpath, node.lineno, NAME, "unlocked",
                f"{kind} {cls.name}.{node.attr} outside "
                f"'with {sorted(locks)[0]}:' in {method.name}()")


def _check_globals(mod: ModuleRecord,
                   parents: Dict[ast.AST, ast.AST]) -> Iterable[Finding]:
    imports = _imported_from(mod.tree)
    this_module = _owner_module(mod.relpath)
    active: Dict[str, Dict] = {}       # accessible spelling -> config
    for bare, cfg in GUARDED_GLOBALS.items():
        if this_module == cfg["owner"] or imports.get(bare) == cfg["owner"]:
            active[bare] = cfg
        owner_parent = ".".join(cfg["owner"].split(".")[:-1])
        for dotted_name in cfg["dotted"]:
            prefix = dotted_name.split(".")[0]
            if imports.get(prefix) == owner_parent \
                    or this_module == cfg["owner"]:
                active[dotted_name] = cfg
    if not active:
        return
    for node in ast.walk(mod.tree):
        name = None
        if isinstance(node, ast.Name) and node.id in active:
            name = node.id
        elif isinstance(node, ast.Attribute):
            d = dotted(node)
            if d in active:
                # skip inner Attribute of a longer guarded chain
                parent = parents.get(node)
                if isinstance(parent, ast.Attribute) and \
                        dotted(parent) in active:
                    continue
                name = d
        if name is None:
            continue
        cfg = active[name]
        if enclosing_function(node, parents) is None:
            continue   # module-level init runs before any concurrency
        if cfg["writes_only"] and not _is_write(node, parents):
            continue
        if under_lock(node, parents, cfg["locks"]):
            continue
        kind = "write of" if _is_write(node, parents) else "read of"
        yield Finding(
            mod.relpath, node.lineno, NAME, "unlocked",
            f"{kind} guarded global {name} outside "
            f"'with {sorted(cfg['locks'])[0]}:'")


def check_module(mod: ModuleRecord) -> Iterable[Finding]:
    parents = build_parents(mod.tree)
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ClassDef) and node.name in GUARDED_CLASSES:
            yield from _check_class(node, GUARDED_CLASSES[node.name],
                                    mod, parents)
    yield from _check_globals(mod, parents)
