#!/usr/bin/env python
"""tfs_lint — the repo self-lint tier (round 17, ISSUE 12c).

Rounds 1–16 accumulated cross-cutting invariants that were enforced only
by reviewer memory; this AST-based checker makes them CI-enforced
(``run_tests.sh lint``).  Stdlib-only, no jax import, runs in ~a second.

Rules (each violation prints ``file:line: [rule] message``):

* **env-routing** — inside ``tensorframes_tpu/``, every ``os.environ``
  read of a ``TFS_*`` knob must go through :mod:`tensorframes_tpu.envutil`
  (``env_raw``/``env_int``/``env_float``/``env_bytes``/...), so the
  clamp-and-fallback semantics cannot fork per module.  Reads of
  non-``TFS_`` keys (``JAX_*``, cluster discovery in
  ``parallel/multihost.py``) are exempt; a read whose key the linter
  cannot resolve is a violation unless the file is in the documented
  allowlist.
* **knob-docs** / **knob-pins** — every ``TFS_*`` knob the package reads
  (string literals fed to ``envutil.env_*``, plus ``ENV_* = "TFS_..."``
  module constants) must appear in ``docs/COMPONENTS.md`` (the operator
  knob reference) AND in ``tests/conftest.py`` (the absence-default pin
  block that keeps the main suite's trace/compile fences deterministic).
* **counter-decl** — every counter key ``observability._bump`` (or a
  literal dict given to ``_bump_many``) is called with must be declared
  in the ``_counters`` init dict, the one place a counter is declared
  (``counters_delta`` and ``metrics_text`` read their keys from it); no
  registered gauge name may collide with a counter family
  (``tfs_<name>_total``) — the ``metrics_text`` no-dup-family rule,
  enforced at the source instead of scrape time.
* **span-name** — inside ``tensorframes_tpu/``, the name given to
  ``observability.span`` / ``instant`` is never built at run time (an
  f-string, ``%``, ``+``, ``.format``): the always-on span table keeps
  one entry a name for the life of the process, so what varies rides in
  the arguments.
* **block-kind** — inside ``tensorframes_tpu/bridge/``, no attribute
  read of a model block's kind (``.block.attention``, ``.block.mixer``,
  ``.block.window``, ``.block.layer_types``, ``.block.stateless``): what a
  block keeps and how its executables take it is ``models/kv_pager.py``'s
  to say, so the decode scheduler serves a new kind unchanged.
* **checkpoint-coverage** — in ``ops/block_loop.py`` (the engine's one
  block loop), ``ops/engine.py`` (its chunk loops) and
  ``ops/pipeline.py``, every block-dispatch loop (a ``for``/``while``
  whose body dispatches blocks: ``run_streamed`` / ``session.run(...)``
  / ``_split_range``)
  must call ``cancellation.checkpoint()`` inside the loop, so a bridge
  deadline/cancel can cut a verb at the next block boundary (the PR 6
  cooperative-cancellation contract).  Prefetch staging lanes are NOT
  block loops — they deliberately never checkpoint (cancellation.py).

Exit status: 0 clean, 1 violations, 2 usage/internal error.
``--root`` points at an alternate tree (the lint's own tests use it).
"""

from __future__ import annotations

import argparse
import ast
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

PKG = "tensorframes_tpu"

# files allowed to read os.environ with keys the linter cannot resolve
# (non-TFS cluster discovery loops); keep this list SHORT and commented
ENV_READ_ALLOWLIST = {
    # iterates JAX_COORDINATOR_ADDRESS / CLOUD_TPU_TASK_ID / ... —
    # multihost auto-detection, no TFS_* keys involved
    os.path.join(PKG, "parallel", "multihost.py"),
}

# block-dispatch markers for checkpoint-coverage: a loop calling any of
# these executes verbs block-by-block on the consumer thread
DISPATCH_ATTRS = {"run_streamed", "_run_block_streamed", "_split_range"}
DISPATCH_RECEIVER_RUN = "session"  # session.run(bi, ...) — the FT wrapper


class Violation:
    def __init__(self, path: str, line: int, rule: str, msg: str):
        self.path, self.line, self.rule, self.msg = path, line, rule, msg

    def __str__(self):
        return f"{self.path}:{self.line}: [{self.rule}] {self.msg}"


def _iter_py(root: str, sub: str) -> List[str]:
    out = []
    base = os.path.join(root, sub)
    for dirpath, dirnames, filenames in os.walk(base):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in sorted(filenames):
            if f.endswith(".py"):
                out.append(os.path.join(dirpath, f))
    return out


def _rel(root: str, path: str) -> str:
    return os.path.relpath(path, root)


def _module_str_constants(tree: ast.Module) -> Dict[str, str]:
    """Module-level NAME = "literal" assignments (ENV_VAR style)."""
    out: Dict[str, str] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and isinstance(
            node.value, ast.Constant
        ) and isinstance(node.value.value, str):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    out[t.id] = node.value.value
    return out


def _is_environ(node: ast.AST) -> bool:
    """``os.environ`` / ``_os.environ`` attribute expressions."""
    return (
        isinstance(node, ast.Attribute)
        and node.attr == "environ"
        and isinstance(node.value, ast.Name)
        and node.value.id in ("os", "_os")
    )


def _env_key(node: ast.AST, consts: Dict[str, str]) -> Optional[str]:
    """Resolve the key expression of an environ access, or None."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.Name):
        return consts.get(node.id)
    return None


def check_env_routing(root: str) -> List[Violation]:
    out: List[Violation] = []
    for path in _iter_py(root, PKG):
        rel = _rel(root, path)
        if rel == os.path.join(PKG, "envutil.py"):
            continue
        tree = ast.parse(open(path).read())
        consts = _module_str_constants(tree)
        for node in ast.walk(tree):
            key_node = None
            if isinstance(node, ast.Call) and isinstance(
                node.func, ast.Attribute
            ) and _is_environ(node.func.value):
                # os.environ.get(...) / .setdefault(...) / .pop(...)
                key_node = node.args[0] if node.args else None
            elif isinstance(node, ast.Subscript) and _is_environ(
                node.value
            ):
                key_node = node.slice
            else:
                continue
            key = _env_key(key_node, consts) if key_node is not None else None
            if key is None:
                if rel not in ENV_READ_ALLOWLIST:
                    out.append(Violation(
                        rel, node.lineno, "env-routing",
                        "os.environ access with an unresolvable key; "
                        "route TFS_* knobs through envutil (or add the "
                        "file to the documented allowlist if no TFS_* "
                        "key can reach it)",
                    ))
            elif key.startswith("TFS_"):
                out.append(Violation(
                    rel, node.lineno, "env-routing",
                    f"raw os.environ access for knob {key!r}; every "
                    f"TFS_* read must go through envutil (env_raw for "
                    f"bespoke grammars)",
                ))
    return out


def collect_knobs(root: str) -> Dict[str, Tuple[str, int]]:
    """TFS_* knobs the package reads: string literals passed to
    envutil.env_* calls, plus module constants whose value matches and
    which are passed to envutil calls or environ accesses (we take every
    ``TFS_``-matching module constant — a constant nobody reads through
    is dead and SHOULD fail the docs check until removed)."""
    knobs: Dict[str, Tuple[str, int]] = {}
    pat = re.compile(r"^TFS_[A-Z0-9_]+$")
    for path in _iter_py(root, PKG):
        rel = _rel(root, path)
        tree = ast.parse(open(path).read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                fn = node.func
                fname = (
                    fn.attr if isinstance(fn, ast.Attribute)
                    else fn.id if isinstance(fn, ast.Name) else ""
                )
                # env_raw/env_int/... plus local wrappers (_env_bytes)
                is_envutil = "env_" in fname
                if not is_envutil or not node.args:
                    continue
                a = node.args[0]
                if isinstance(a, ast.Constant) and isinstance(
                    a.value, str
                ) and pat.match(a.value):
                    knobs.setdefault(a.value, (rel, node.lineno))
            elif isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Constant
            ) and isinstance(node.value.value, str) and pat.match(
                node.value.value
            ):
                knobs.setdefault(node.value.value, (rel, node.lineno))
    return knobs


def check_knobs(root: str) -> List[Violation]:
    out: List[Violation] = []
    knobs = collect_knobs(root)
    docs_path = os.path.join(root, "docs", "COMPONENTS.md")
    conftest_path = os.path.join(root, "tests", "conftest.py")
    docs = open(docs_path).read() if os.path.exists(docs_path) else ""
    pins = (
        open(conftest_path).read()
        if os.path.exists(conftest_path) else ""
    )
    for knob, (rel, line) in sorted(knobs.items()):
        # word-boundary match: TFS_ANALYZE must not pass on the back of
        # TFS_ANALYZE_XCHECK's entry ("_" is a word char, so \b rejects
        # a longer-knob substring hit)
        present = re.compile(rf"\b{re.escape(knob)}\b")
        if not present.search(docs):
            out.append(Violation(
                rel, line, "knob-docs",
                f"{knob} is read by the package but not documented in "
                f"docs/COMPONENTS.md",
            ))
        if not present.search(pins):
            out.append(Violation(
                rel, line, "knob-pins",
                f"{knob} is read by the package but has no "
                f"absence-default pin in tests/conftest.py (the main "
                f"suite's deterministic baseline)",
            ))
    return out


def check_counters(root: str) -> List[Violation]:
    out: List[Violation] = []
    path = os.path.join(root, PKG, "observability.py")
    if not os.path.exists(path):
        return out
    rel = _rel(root, path)
    tree = ast.parse(open(path).read())

    declared: Dict[str, int] = {}
    bumps: List[Tuple[str, int]] = []
    gauge_names: List[Tuple[str, int]] = []

    # _counters init dict
    for node in tree.body:
        if isinstance(node, ast.AnnAssign) and isinstance(
            node.target, ast.Name
        ) and node.target.id == "_counters" and isinstance(
            node.value, ast.Dict
        ):
            for k in node.value.keys:
                if isinstance(k, ast.Constant) and isinstance(
                    k.value, str
                ):
                    declared[k.value] = k.lineno
        if isinstance(node, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == "_counters"
            for t in node.targets
        ) and isinstance(node.value, ast.Dict):
            for k in node.value.keys:
                if isinstance(k, ast.Constant) and isinstance(
                    k.value, str
                ):
                    declared[k.value] = k.lineno

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fn = node.func
            name = fn.id if isinstance(fn, ast.Name) else (
                fn.attr if isinstance(fn, ast.Attribute) else None
            )
            if name == "_bump" and node.args and isinstance(
                node.args[0], ast.Constant
            ) and isinstance(node.args[0].value, str):
                bumps.append((node.args[0].value, node.lineno))
            if name == "_bump_many" and node.args and isinstance(
                node.args[0], ast.Dict
            ):
                for k in node.args[0].keys:
                    if isinstance(k, ast.Constant) and isinstance(
                        k.value, str
                    ):
                        bumps.append((k.value, k.lineno))
        if isinstance(node, ast.FunctionDef) and node.name == (
            "metrics_text"
        ):
            for inner in ast.walk(node):
                if isinstance(inner, ast.Constant) and isinstance(
                    inner.value, str
                ) and inner.value.startswith("tfs_"):
                    gauge_names.append((inner.value, inner.lineno))

    if not declared:
        out.append(Violation(rel, 1, "counter-decl",
                             "could not locate the _counters init dict"))
        return out
    for key, line in bumps:
        if key not in declared:
            out.append(Violation(
                rel, line, "counter-decl",
                f"_bump({key!r}) has no declaration in the _counters "
                f"init dict",
            ))
    families = {f"tfs_{k}_total" for k in declared}
    for name, line in gauge_names:
        if name in families:
            out.append(Violation(
                rel, line, "counter-decl",
                f"gauge {name!r} collides with a counter family "
                f"(metrics_text no-dup-family rule)",
            ))
    return out


def check_span_names(root: str) -> List[Violation]:
    out: List[Violation] = []
    for path in _iter_py(root, PKG):
        for node in ast.walk(ast.parse(open(path).read())):
            if not (isinstance(node, ast.Call) and node.args):
                continue
            fn = node.func
            called = fn.id if isinstance(fn, ast.Name) else (
                fn.attr
                if isinstance(fn, ast.Attribute)
                and isinstance(fn.value, ast.Name)
                and fn.value.id == "observability"
                else None
            )
            if called not in ("span", "instant"):
                continue
            name = node.args[0]
            built = isinstance(name, (ast.JoinedStr, ast.BinOp)) or (
                isinstance(name, ast.Call)
                and isinstance(name.func, ast.Attribute)
                and name.func.attr == "format"
            )
            if built:
                out.append(Violation(
                    _rel(root, path), node.lineno, "span-name",
                    f"the name of {called}() is built at run time; span "
                    f"names are stable literals (the span table keeps an "
                    f"entry a name), what varies is an argument",
                ))
    return out


# what a model block says of its kind; the serving layer asks kv_pager
BLOCK_KIND_FIELDS = {"attention", "mixer", "window", "layer_types", "stateless"}


def check_block_kind(root: str) -> List[Violation]:
    out: List[Violation] = []
    for path in _iter_py(root, os.path.join(PKG, "bridge")):
        for node in ast.walk(ast.parse(open(path).read())):
            if (
                isinstance(node, ast.Attribute)
                and node.attr in BLOCK_KIND_FIELDS
                and isinstance(node.value, ast.Attribute)
                and node.value.attr == "block"
            ):
                out.append(Violation(
                    _rel(root, path), node.lineno, "block-kind",
                    f"the bridge reads .block.{node.attr}; a block's kind is "
                    f"models/kv_pager.py's to say (its pool and functions)",
                ))
    return out


def _walk_own_body(loop: ast.AST):
    """Yield the loop's nodes EXCLUDING nested For/While subtrees —
    nested loops are each checked on their own, so an inner loop's
    dispatch must not force an outer checkpoint (and an inner loop's
    checkpoint, which may run zero times, must not satisfy the outer
    loop's requirement)."""
    stack = [loop]
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.For, ast.While)):
                continue  # reported by its own visit
            stack.append(child)


def _loop_dispatches(loop: ast.AST) -> Optional[int]:
    """Line of the first block-dispatch call directly inside the loop
    (nested loops excluded), else None."""
    for node in _walk_own_body(loop):
        if isinstance(node, ast.Call) and isinstance(
            node.func, ast.Attribute
        ):
            fn = node.func
            if fn.attr in DISPATCH_ATTRS:
                return node.lineno
            if fn.attr == "run" and isinstance(
                fn.value, ast.Name
            ) and fn.value.id == DISPATCH_RECEIVER_RUN:
                return node.lineno
    return None


def _loop_checkpoints(loop: ast.AST) -> bool:
    for node in _walk_own_body(loop):
        if isinstance(node, ast.Call) and isinstance(
            node.func, ast.Attribute
        ) and node.func.attr == "checkpoint":
            return True
    return False


def check_checkpoints(root: str) -> List[Violation]:
    out: List[Violation] = []
    for sub in (os.path.join(PKG, "ops", "block_loop.py"),
                os.path.join(PKG, "ops", "engine.py"),
                os.path.join(PKG, "ops", "pipeline.py")):
        path = os.path.join(root, sub)
        if not os.path.exists(path):
            continue
        tree = ast.parse(open(path).read())
        for node in ast.walk(tree):
            if not isinstance(node, (ast.For, ast.While)):
                continue
            line = _loop_dispatches(node)
            if line is not None and not _loop_checkpoints(node):
                out.append(Violation(
                    sub, node.lineno, "checkpoint-coverage",
                    f"block-dispatch loop (dispatch at line {line}) "
                    f"never calls cancellation.checkpoint(); deadlines "
                    f"and cancels could not cut this verb at a block "
                    f"boundary",
                ))
    return out


def run(root: str) -> List[Violation]:
    checks = (
        check_env_routing,
        check_knobs,
        check_counters,
        check_span_names,
        check_block_kind,
        check_checkpoints,
    )
    out: List[Violation] = []
    for c in checks:
        out.extend(c(root))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--root", default=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        ),
        help="repo root to lint (default: this checkout)",
    )
    ap.add_argument(
        "--list-knobs", action="store_true",
        help="print the knob inventory and exit",
    )
    args = ap.parse_args(argv)
    if args.list_knobs:
        for knob, (rel, line) in sorted(collect_knobs(args.root).items()):
            print(f"{knob}\t{rel}:{line}")
        return 0
    violations = run(args.root)
    for v in violations:
        print(v)
    if violations:
        print(f"tfs_lint: {len(violations)} violation(s)")
        return 1
    print("tfs_lint: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
