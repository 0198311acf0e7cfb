"""AST lint rules over the package source.

Each rule encodes an invariant that Python cannot enforce at runtime until
it is too late on hardware: a host sync inside a hot loop stalls the
dispatch pipeline for a full device round trip, a silent recompile costs
seconds per occurrence, a float64 op doubles memory and falls off the MXU,
an unregistered fault site silently drops out of the chaos sweep, and an
unlocked write to lock-guarded state is a data race waiting for a thread
interleaving. The rules are deliberately conservative approximations —
they flag the syntactic patterns that produce those failures, and a
deliberate exception is silenced in place with

    # r2d2: disable=<rule>[,<rule>...]          (same line or line above)

so every suppression is visible in the diff it rides in on.

Lock-discipline exceptions have a PRECISE variant: instead of muting the
rule, an annotation asserts WHICH lock protects the write —

    self.count += 1  # r2d2: guarded-by(lock)   (this write: caller holds
                                                 self.lock)
    def _account(self):  # r2d2: guarded-by(lock)
        ...                                     (whole function runs with
                                                 self.lock held — the
                                                 caller-holds-lock contract)

A guarded-by annotation silences `lock-discipline` for the covered lines
exactly like a disable comment would, but unlike a disable it feeds the
interprocedural concurrency pass (analysis/concurrency.py), which treats
the named lock as held there and CHECKS the assertion's consequences
(lock-order edges, cross-thread guard consistency) instead of going blind.

Rule catalog (ids, severities — the table in ARCHITECTURE.md mirrors this):

- host-sync-in-hot-path  (warning)  `.item()` / `jax.device_get` /
  `np.asarray` / `np.array` / `float(x)` / `bool(x)` inside a for/while
  body in the hot-path modules (learner.py, collect.py, megastep.py):
  each call can force a device->host sync per iteration. The serving
  plane graduated to its own rule (below).
- blocking-host-sync-in-serve-step (warning)  the serve-pipeline variant,
  covering serve/* files: the same loop-body flags as
  host-sync-in-hot-path, PLUS function-wide (not just loop-body) coverage
  of `np.asarray` / `np.array` / `jax.device_get` / `.item()` /
  `.block_until_ready()` inside the pipeline's stage/dispatch bodies
  (`_run_batch`, `_serve_iteration`, `_stage*`, `_dispatch*`) — one
  blocking materialization there stalls the whole depth-2 overlap, so the
  serve thread must never wait on the device. Completion-side functions
  (`_complete*`) and `warmup*` are exempt: materializing is their job.
- jit-in-loop            (error)    `jax.jit(...)` called inside a
  for/while body — a fresh jit wrapper per iteration retraces every call.
- unhashable-static-arg  (error)    a jit static parameter whose default
  is a mutable literal (list/dict/set): jit's cache key hashes static
  args, so the first call raises (or, with a custom __hash__, silently
  retraces).
- shape-branch-in-jit    (warning)  an `if` on `.shape` inside a jitted
  function whose body does real work (not just a guard `raise`): each new
  shape traces a new program variant. Guard-raises are exempt — shape
  validation at trace time is the idiom.
- float64-op             (error)    device-plane float64: `jnp.float64`,
  a float64 dtype passed to a jnp/jax constructor, or enabling
  jax_enable_x64. Host-side numpy float64 (sum-tree prefix sums, env
  reward accumulators) is fine and not flagged.
- unknown-fault-site     (error)    `fault_point("site")` whose literal is
  not registered in faults.KNOWN_SITES — the site would be invisible to
  chaos sweeps and the R2D2_FAULTS operator surface.
- dynamic-fault-site     (warning)  `fault_point(expr)` with a non-literal
  argument — statically uncheckable, and sweeps cannot enumerate it.
- snapshot-missing-topology (error) a `save_replay(...)` call site in the
  package without an explicit `topology=` manifest: the writer relies on
  the callee's default, and a snapshot written without a manifest cannot
  be resharded onto a changed device/host layout (replay/reshard.py) or
  asserted by the runs/ chain guards.
- lock-discipline        (warning)  a class that guards attribute writes
  with `with self.<lock>:` in one method but writes the same attributes
  bare in another (non-__init__) method — the trainer/serve/watcher
  threads share these objects, so the bare write races the guarded one.
- host-tree-in-hot-loop  (warning)  a host `SumTree` method call
  (`.tree.sample(...)`, `.tree.update(...)`, ...) inside a for/while body
  in the learner hot-path modules: under priority_plane='device' the sum
  tree lives in HBM and sampling/write-back run in-jit inside the
  superstep (megastep.make_priority_superstep), so a host-tree call here
  both stalls the dispatch pipeline per iteration and silently forks the
  host tree away from the device tree. The in-jit device ops
  (replay/device_sum_tree.py module functions) are not flagged.
- raw-shard-map-import   (error)    a shard_map import from jax itself
  (`from jax import shard_map`, or the removed `jax.experimental.
  shard_map`) anywhere outside parallel/jax_compat.py: every shard_map
  must come through that one wrapper, and the manual tp×fsdp train step
  depends on its axis_names=None -> fully-manual defaulting.
- codec-decode-in-hot-loop (warning) a block-codec decode
  (`decode_field` / `decode_block` / `read_block`) or an mmap page-in
  (`np.memmap` / `mmap.mmap`) inside a for/while body in the learner
  hot-path modules or serve/*: the disk replay tier's contract is that
  decompression and first-touch page faults happen on the replay staging
  thread (tiered_store._fill_disk_rows), never on the learner or serve
  step — one zlib inflate per iteration there erases the overlap the
  three-tier design buys.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from r2d2_tpu.analysis.findings import Finding
from r2d2_tpu.utils.faults import KNOWN_SITES

ALL_RULES = (
    "host-sync-in-hot-path",
    "blocking-host-sync-in-serve-step",
    "jit-in-loop",
    "unhashable-static-arg",
    "shape-branch-in-jit",
    "float64-op",
    "unknown-fault-site",
    "dynamic-fault-site",
    "snapshot-missing-topology",
    "lock-discipline",
    "host-tree-in-hot-loop",
    "raw-shard-map-import",
    "codec-decode-in-hot-loop",
)

# hot-path modules for the host-sync rule: the learner/collection dispatch
# loops. The serving plane moved to blocking-host-sync-in-serve-step,
# which adds function-wide stage/dispatch coverage on top of the same
# loop-body checks.
HOT_BASENAMES = {"learner.py", "collect.py", "megastep.py"}
HOT_DIRNAMES: Set[str] = set()

# the serve rule's scope + its pipeline-role name conventions
# (serve/server.py): stage/dispatch bodies must never block on the
# device; completion/warmup bodies exist to block on it
SERVE_DIRNAMES = {"serve"}
_SERVE_STEP_NAMES = {"_run_batch", "_serve_iteration"}
_SERVE_STEP_PREFIXES = ("_stage", "_dispatch")
_SERVE_EXEMPT_PREFIXES = ("_complete", "warmup")

_SYNC_CALLS = {
    "np.asarray": "np.asarray",
    "np.array": "np.array",
    "numpy.asarray": "np.asarray",
    "numpy.array": "np.array",
    "jax.device_get": "jax.device_get",
}

_DISABLE_RE = re.compile(r"#\s*r2d2:\s*disable=([A-Za-z0-9_,\s-]+)")
_GUARDED_BY_RE = re.compile(r"#\s*r2d2:\s*guarded-by\(([A-Za-z0-9_.\s,]+)\)")

_MUTABLE_LITERALS = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp, ast.SetComp)


def is_hot_path(path: str) -> bool:
    parts = path.replace(os.sep, "/").split("/")
    return parts[-1] in HOT_BASENAMES or bool(HOT_DIRNAMES & set(parts[:-1]))


def is_serve_path(path: str) -> bool:
    parts = path.replace(os.sep, "/").split("/")
    return bool(SERVE_DIRNAMES & set(parts[:-1]))


def _dotted(node: ast.AST) -> Optional[str]:
    """'jax.numpy.float64' for nested Attribute/Name chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _suppressions(src_lines: Sequence[str]) -> Dict[int, Set[str]]:
    """Line -> suppressed rule set. A trailing `# r2d2: disable=` comment
    covers its own line; a comment-ONLY line covers itself and the line
    below (so it can sit above a long statement without leaking onto
    unrelated neighbors)."""
    out: Dict[int, Set[str]] = {}
    for i, line in enumerate(src_lines, start=1):
        m = _DISABLE_RE.search(line)
        if not m:
            continue
        rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
        targets = (i, i + 1) if line.lstrip().startswith("#") else (i,)
        for target in targets:
            out.setdefault(target, set()).update(rules)
    return out


def _guarded_by_comments(src_lines: Sequence[str]) -> Dict[int, Set[str]]:
    """Line -> lock names asserted held there by `# r2d2: guarded-by(X)`
    annotations. Same placement rules as _suppressions: a trailing comment
    covers its own line, a comment-only line covers itself and the line
    below."""
    out: Dict[int, Set[str]] = {}
    for i, line in enumerate(src_lines, start=1):
        m = _GUARDED_BY_RE.search(line)
        if not m:
            continue
        names = {n.strip() for n in m.group(1).split(",") if n.strip()}
        targets = (i, i + 1) if line.lstrip().startswith("#") else (i,)
        for target in targets:
            out.setdefault(target, set()).update(names)
    return out


def guarded_by_map(tree: ast.AST, src_lines: Sequence[str]) -> Dict[int, Set[str]]:
    """The full guarded-by map for one file: per-line annotations, with a
    def-line annotation expanded over the whole function body (the
    caller-holds-lock contract — every statement in the function runs
    with the named lock held)."""
    out = _guarded_by_comments(src_lines)
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        names = out.get(node.lineno)
        if names:
            for ln in range(node.lineno, (node.end_lineno or node.lineno) + 1):
                out.setdefault(ln, set()).update(names)
    return out


def _is_float64(node: ast.AST) -> bool:
    d = _dotted(node)
    if d in ("np.float64", "numpy.float64", "jnp.float64", "jax.numpy.float64"):
        return True
    return isinstance(node, ast.Constant) and node.value == "float64"


# ---------------------------------------------------------------- the rules


def _rule_host_sync(tree: ast.AST, path: str) -> List[Finding]:
    if not is_hot_path(path):
        return []
    out: List[Finding] = []
    seen: Set[Tuple[int, int]] = set()

    def flag(node: ast.AST, what: str) -> None:
        key = (node.lineno, node.col_offset)
        if key in seen:
            return
        seen.add(key)
        out.append(
            Finding(
                rule="host-sync-in-hot-path",
                severity="warning",
                path=path,
                line=node.lineno,
                col=node.col_offset,
                message=f"{what} inside a hot-path loop body forces a "
                "device->host sync per iteration",
                hint="hoist the transfer out of the loop (batch it), or "
                "mark a deliberate readback with "
                "`# r2d2: disable=host-sync-in-hot-path`",
            )
        )

    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.While)):
            continue
        for stmt in list(loop.body) + list(loop.orelse):
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call):
                    continue
                d = _dotted(node.func)
                if d in _SYNC_CALLS:
                    flag(node, f"{_SYNC_CALLS[d]}(...)")
                elif (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item"
                    and not node.args
                ):
                    flag(node, ".item()")
                elif (
                    isinstance(node.func, ast.Name)
                    and node.func.id in ("float", "bool")
                    and len(node.args) == 1
                    and not isinstance(node.args[0], ast.Constant)
                ):
                    flag(node, f"{node.func.id}(...) on a possible device value")
    return out


def _own_nodes(root: ast.AST) -> List[ast.AST]:
    """All descendant nodes of `root` that belong to ITS scope — nested
    function/class definitions are skipped (they get their own scope
    decision when the caller iterates over them directly)."""
    out: List[ast.AST] = []
    stack = list(ast.iter_child_nodes(root))
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            continue
        out.append(n)
        stack.extend(ast.iter_child_nodes(n))
    return out


def _rule_serve_step_host_sync(tree: ast.AST, path: str) -> List[Finding]:
    """Serve-plane host-sync discipline (the depth-2 pipeline's contract):

    - everywhere in serve/* except completion/warmup bodies, the classic
      loop-body checks apply (a sync per iteration stalls the batch);
    - inside stage/dispatch bodies (`_run_batch`, `_serve_iteration`,
      `_stage*`, `_dispatch*`) the blocking calls are banned FUNCTION-WIDE
      — np.asarray / np.array / jax.device_get / `.item()` /
      `.block_until_ready()` anywhere there serializes the serve thread
      against the device and collapses the stage/step overlap. float()/
      bool() stay loop-only (scalar host math at stage time is fine).
    """
    if not is_serve_path(path):
        return []
    out: List[Finding] = []
    seen: Set[Tuple[int, int]] = set()

    def flag(node: ast.AST, what: str, where: str) -> None:
        key = (node.lineno, node.col_offset)
        if key in seen:
            return
        seen.add(key)
        out.append(
            Finding(
                rule="blocking-host-sync-in-serve-step",
                severity="warning",
                path=path,
                line=node.lineno,
                col=node.col_offset,
                message=f"{what} {where} blocks the serve thread on the "
                "device and stalls the stage/dispatch pipeline",
                hint="materialize on the completion side (_complete*), or "
                "mark a deliberate sync with "
                "`# r2d2: disable=blocking-host-sync-in-serve-step`",
            )
        )

    def _blocking(node: ast.Call) -> Optional[str]:
        d = _dotted(node.func)
        if d in _SYNC_CALLS:
            return f"{_SYNC_CALLS[d]}(...)"
        if d == "jax.block_until_ready":
            return "jax.block_until_ready(...)"
        if isinstance(node.func, ast.Attribute):
            if node.func.attr == "item" and not node.args:
                return ".item()"
            if node.func.attr == "block_until_ready":
                return ".block_until_ready()"
        return None

    def check_loops(scope: ast.AST) -> None:
        own = _own_nodes(scope)
        own_set = set(map(id, own))
        for loop in own:
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            for stmt in list(loop.body) + list(loop.orelse):
                for node in ast.walk(stmt):
                    if not isinstance(node, ast.Call) or id(node) not in own_set:
                        continue
                    what = _blocking(node)
                    if what is not None:
                        flag(node, what, "inside a serve loop body")
                    elif (
                        isinstance(node.func, ast.Name)
                        and node.func.id in ("float", "bool")
                        and len(node.args) == 1
                        and not isinstance(node.args[0], ast.Constant)
                    ):
                        flag(
                            node,
                            f"{node.func.id}(...) on a possible device value",
                            "inside a serve loop body",
                        )

    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if fn.name.startswith(_SERVE_EXEMPT_PREFIXES):
            continue
        if fn.name in _SERVE_STEP_NAMES or fn.name.startswith(_SERVE_STEP_PREFIXES):
            for node in _own_nodes(fn):
                if isinstance(node, ast.Call):
                    what = _blocking(node)
                    if what is not None:
                        flag(node, what, f"in stage/dispatch body {fn.name}()")
        check_loops(fn)
    check_loops(tree)
    return out


# host SumTree API surface (replay/sum_tree.py + the control plane's tree
# attribute) and the receiver names that conventionally hold a HOST tree.
# The device plane's ops are module functions (dst.tree_update(...)) so
# their receiver chain never matches.
_HOST_TREE_METHODS = {
    "sample", "update", "sample_indices", "update_priorities",
    "priorities_of", "leaves",
}
_HOST_TREE_NAMES = {"tree", "sum_tree", "host_tree"}


def _rule_host_tree_in_hot_loop(tree: ast.AST, path: str) -> List[Finding]:
    if not is_hot_path(path):
        return []
    out: List[Finding] = []
    seen: Set[Tuple[int, int]] = set()
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.While)):
            continue
        for stmt in list(loop.body) + list(loop.orelse):
            for node in ast.walk(stmt):
                if not (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _HOST_TREE_METHODS
                ):
                    continue
                recv = node.func.value
                recv_d = _dotted(recv) or ""
                # jax.tree.leaves / jax.tree_util & friends are pytree ops
                if recv_d.startswith(("jax.", "jnp.", "tree_util.")):
                    continue
                last = (
                    recv.attr
                    if isinstance(recv, ast.Attribute)
                    else recv.id if isinstance(recv, ast.Name) else ""
                )
                if last not in _HOST_TREE_NAMES:
                    continue
                key = (node.lineno, node.col_offset)
                if key in seen:
                    continue
                seen.add(key)
                out.append(
                    Finding(
                        rule="host-tree-in-hot-loop",
                        severity="warning",
                        path=path,
                        line=node.lineno,
                        col=node.col_offset,
                        message=f"host SumTree call {recv_d or last}."
                        f"{node.func.attr}(...) inside a hot-loop body: "
                        "under priority_plane='device' sampling and "
                        "priority write-back run in-jit over the HBM tree "
                        "(megastep superstep); a host-tree call here syncs "
                        "per iteration and forks the host tree from the "
                        "device tree",
                        hint="use the device ops "
                        "(replay/device_sum_tree.py) or the control "
                        "plane's _tree_write funnel; mark a deliberate "
                        "host-plane path with "
                        "`# r2d2: disable=host-tree-in-hot-loop`",
                    )
                )
    return out


# several rules ask the same pure questions of the same module tree; the
# one-entry memo (keyed on tree identity, holding a strong ref so ids are
# never reused under it) makes each question one walk per module instead
# of one per rule
_TREE_MEMO: Dict[str, Tuple[ast.AST, object]] = {}


def _memo_per_tree(name: str, tree: ast.AST, build):
    ent = _TREE_MEMO.get(name)
    if ent is not None and ent[0] is tree:
        return ent[1]
    res = build()
    _TREE_MEMO[name] = (tree, res)
    return res


def _jit_calls(tree: ast.AST) -> List[ast.Call]:
    """Every `jax.jit(...)` call, including the `functools.partial(jax.jit,
    ...)` decorator form (the partial call itself is returned)."""

    def build() -> List[ast.Call]:
        out = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func)
            if d == "jax.jit":
                out.append(node)
            elif d in ("functools.partial", "partial") and node.args:
                if _dotted(node.args[0]) == "jax.jit":
                    out.append(node)
        return out

    return _memo_per_tree("jit_calls", tree, build)


def _rule_jit_in_loop(tree: ast.AST, path: str) -> List[Finding]:
    out: List[Finding] = []
    jit_positions = {(c.lineno, c.col_offset) for c in _jit_calls(tree)}
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.While)):
            continue
        for stmt in list(loop.body) + list(loop.orelse):
            for node in ast.walk(stmt):
                if (
                    isinstance(node, ast.Call)
                    and (node.lineno, node.col_offset) in jit_positions
                ):
                    out.append(
                        Finding(
                            rule="jit-in-loop",
                            severity="error",
                            path=path,
                            line=node.lineno,
                            col=node.col_offset,
                            message="jax.jit called inside a loop body: each "
                            "iteration builds a fresh wrapper with an empty "
                            "trace cache",
                            hint="build the jitted callable once outside the "
                            "loop and reuse it",
                        )
                    )
    return out


def _function_defs(tree: ast.AST) -> Dict[str, ast.FunctionDef]:
    def build() -> Dict[str, ast.FunctionDef]:
        defs: Dict[str, ast.FunctionDef] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs[node.name] = node
        return defs

    return _memo_per_tree("function_defs", tree, build)


def _static_params(call: ast.Call, fn: ast.FunctionDef) -> List[ast.arg]:
    """Parameters of `fn` marked static by a jit call's static_argnames /
    static_argnums keywords (literal values only)."""
    params = list(fn.args.posonlyargs) + list(fn.args.args)
    out: List[ast.arg] = []
    for kw in call.keywords:
        if kw.arg == "static_argnames" and isinstance(kw.value, (ast.Tuple, ast.List)):
            names = {
                e.value
                for e in kw.value.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)
            }
            out.extend(p for p in params if p.arg in names)
        elif kw.arg == "static_argnames" and isinstance(kw.value, ast.Constant):
            out.extend(p for p in params if p.arg == kw.value.value)
        elif kw.arg == "static_argnums":
            nums = []
            if isinstance(kw.value, ast.Constant) and isinstance(kw.value.value, int):
                nums = [kw.value.value]
            elif isinstance(kw.value, (ast.Tuple, ast.List)):
                nums = [
                    e.value
                    for e in kw.value.elts
                    if isinstance(e, ast.Constant) and isinstance(e.value, int)
                ]
            out.extend(params[n] for n in nums if 0 <= n < len(params))
    return out


def _param_default(fn: ast.FunctionDef, param: ast.arg) -> Optional[ast.AST]:
    params = list(fn.args.posonlyargs) + list(fn.args.args)
    defaults = list(fn.args.defaults)
    offset = len(params) - len(defaults)
    for i, p in enumerate(params):
        if p is param and i >= offset:
            return defaults[i - offset]
    for p, d in zip(fn.args.kwonlyargs, fn.args.kw_defaults):
        if p is param and d is not None:
            return d
    return None


def _jitted_defs(tree: ast.AST) -> List[Tuple[ast.Call, ast.FunctionDef]]:
    """(jit call, wrapped FunctionDef) pairs resolvable statically: a bare
    `jax.jit(name, ...)` over a same-module def, or a decorator (`@jax.jit`
    / `@functools.partial(jax.jit, ...)`)."""
    defs = _function_defs(tree)
    calls = _jit_calls(tree)
    pairs: List[Tuple[ast.Call, ast.FunctionDef]] = []
    for call in calls:
        target = None
        if _dotted(call.func) == "jax.jit" and call.args:
            if isinstance(call.args[0], ast.Name):
                target = defs.get(call.args[0].id)
        elif call.args and len(call.args) >= 1:
            # partial(jax.jit, ...) form: the decorated def is found below
            pass
        if target is not None:
            pairs.append((call, target))
    for fn in defs.values():
        for dec in fn.decorator_list:
            if _dotted(dec) == "jax.jit":
                pairs.append((ast.Call(func=dec, args=[], keywords=[]), fn))
            elif isinstance(dec, ast.Call) and dec in calls:
                pairs.append((dec, fn))
    return pairs


def _rule_unhashable_static_arg(tree: ast.AST, path: str) -> List[Finding]:
    out: List[Finding] = []
    for call, fn in _jitted_defs(tree):
        for param in _static_params(call, fn):
            default = _param_default(fn, param)
            if default is not None and isinstance(default, _MUTABLE_LITERALS):
                out.append(
                    Finding(
                        rule="unhashable-static-arg",
                        severity="error",
                        path=path,
                        line=param.lineno,
                        col=param.col_offset,
                        message=f"static jit parameter {param.arg!r} defaults "
                        "to a mutable (unhashable) literal: jit hashes static "
                        "args for its cache key",
                        hint="use a tuple / frozen value, or drop the "
                        "parameter from static_argnames",
                    )
                )
    return out


def _rule_shape_branch_in_jit(tree: ast.AST, path: str) -> List[Finding]:
    out: List[Finding] = []
    seen: Set[Tuple[int, int]] = set()
    for _, fn in _jitted_defs(tree):
        for node in ast.walk(fn):
            if not isinstance(node, ast.If):
                continue
            has_shape = any(
                isinstance(sub, ast.Attribute) and sub.attr == "shape"
                for sub in ast.walk(node.test)
            )
            if not has_shape:
                continue
            # guard-raise idiom (shape validation at trace time) is exempt
            if all(isinstance(stmt, ast.Raise) for stmt in node.body) and not node.orelse:
                continue
            key = (node.lineno, node.col_offset)
            if key in seen:
                continue
            seen.add(key)
            out.append(
                Finding(
                    rule="shape-branch-in-jit",
                    severity="warning",
                    path=path,
                    line=node.lineno,
                    col=node.col_offset,
                    message="shape-dependent branch inside a jitted function: "
                    "every distinct shape traces (and compiles) a new variant",
                    hint="pad to a fixed shape, lift the branch to the "
                    "builder, or keep only a guard `raise`",
                )
            )
    return out


def _rule_float64(tree: ast.AST, path: str) -> List[Finding]:
    out: List[Finding] = []
    seen: Set[Tuple[int, int]] = set()

    def flag(node: ast.AST, message: str, hint: str) -> None:
        key = (node.lineno, node.col_offset)
        if key in seen:
            return
        seen.add(key)
        out.append(
            Finding(
                rule="float64-op",
                severity="error",
                path=path,
                line=node.lineno,
                col=node.col_offset,
                message=message,
                hint=hint,
            )
        )

    for node in ast.walk(tree):
        d = _dotted(node) if isinstance(node, ast.Attribute) else None
        if d in ("jnp.float64", "jax.numpy.float64"):
            flag(
                node,
                "jnp.float64 violates the precision policy (x64 is off; the "
                "op silently produces f32 or, with x64 on, doubles memory "
                "and falls off the MXU)",
                "use jnp.float32; host-side accumulation may use np.float64",
            )
        elif isinstance(node, ast.Call):
            cd = _dotted(node.func)
            if (
                cd == "jax.config.update"
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and node.args[0].value == "jax_enable_x64"
                and len(node.args) > 1
                and isinstance(node.args[1], ast.Constant)
                and node.args[1].value is True
            ):
                flag(
                    node,
                    "enabling jax_enable_x64 turns every default float into "
                    "f64 device-wide",
                    "keep x64 off; widen individual host-side numpy arrays "
                    "instead",
                )
            elif cd is not None and cd.split(".")[0] in ("jnp", "jax"):
                for arg in list(node.args) + [k.value for k in node.keywords]:
                    if _is_float64(arg):
                        flag(
                            arg,
                            f"float64 dtype passed to {cd}: device arrays "
                            "must stay <= 32-bit under the precision policy",
                            "use float32 (or bf16 via config.precision)",
                        )
    return out


def _rule_fault_sites(tree: ast.AST, path: str) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)
        if d is None or d.split(".")[-1] != "fault_point":
            continue
        if not node.args:
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            if arg.value not in KNOWN_SITES:
                out.append(
                    Finding(
                        rule="unknown-fault-site",
                        severity="error",
                        path=path,
                        line=node.lineno,
                        col=node.col_offset,
                        message=f"fault site {arg.value!r} is not registered "
                        "in faults.KNOWN_SITES: chaos sweeps and the "
                        "R2D2_FAULTS operator surface cannot see it",
                        hint="add the site to KNOWN_SITES (utils/faults.py) "
                        "or fix the typo",
                    )
                )
        else:
            out.append(
                Finding(
                    rule="dynamic-fault-site",
                    severity="warning",
                    path=path,
                    line=node.lineno,
                    col=node.col_offset,
                    message="fault_point called with a non-literal site name: "
                    "statically uncheckable and unenumerable by sweeps",
                    hint="pass a string literal registered in KNOWN_SITES",
                )
            )
    return out


def _rule_snapshot_topology(tree: ast.AST, path: str) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)
        if d is None or d.split(".")[-1] != "save_replay":
            continue
        # kw.arg is None for a **kwargs splat: statically unverifiable,
        # give it the benefit of the doubt rather than false-positive
        if any(kw.arg == "topology" or kw.arg is None for kw in node.keywords):
            continue
        out.append(
            Finding(
                rule="snapshot-missing-topology",
                severity="error",
                path=path,
                line=node.lineno,
                col=node.col_offset,
                message="save_replay call without an explicit topology= "
                "manifest: a snapshot written without one cannot be "
                "resharded onto a changed device/host layout "
                "(replay/reshard.py) or asserted by the runs/ chain guards",
                hint="pass topology=snapshot_topology(replay, tp=cfg.tp_size)",
            )
        )
    return out


def _lock_attrs(cls: ast.ClassDef) -> Set[str]:
    locks: Set[str] = set()
    for node in ast.walk(cls):
        if not isinstance(node, ast.Assign):
            continue
        if not (
            isinstance(node.value, ast.Call)
            and _dotted(node.value.func) in ("threading.Lock", "threading.RLock")
        ):
            continue
        for t in node.targets:
            if (
                isinstance(t, ast.Attribute)
                and isinstance(t.value, ast.Name)
                and t.value.id == "self"
            ):
                locks.add(t.attr)
    return locks


def _self_attr_writes(node: ast.AST) -> List[Tuple[str, ast.AST]]:
    """(attr name, node) for every `self.X = / self.X op= / self.X[...] =`
    in the subtree, NOT descending into nested function defs."""
    out: List[Tuple[str, ast.AST]] = []

    def targets_of(stmt) -> List[ast.AST]:
        if isinstance(stmt, ast.Assign):
            return list(stmt.targets)
        if isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
            return [stmt.target]
        return []

    def visit(n: ast.AST) -> None:
        for child in ast.iter_child_nodes(n):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                continue
            for t in targets_of(child):
                base = t
                if isinstance(base, ast.Subscript):
                    base = base.value
                if (
                    isinstance(base, ast.Attribute)
                    and isinstance(base.value, ast.Name)
                    and base.value.id == "self"
                ):
                    out.append((base.attr, child))
            visit(child)

    visit(node)
    return out


def _rule_lock_discipline(tree: ast.AST, path: str) -> List[Finding]:
    out: List[Finding] = []
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        locks = _lock_attrs(cls)
        if not locks:
            continue
        methods = [
            n
            for n in cls.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]

        def lock_blocks(method) -> List[ast.With]:
            blocks = []
            for node in ast.walk(method):
                if not isinstance(node, ast.With):
                    continue
                for item in node.items:
                    ctx = item.context_expr
                    if isinstance(ctx, ast.Call):  # e.g. lock.acquire-style wrappers
                        ctx = ctx.func
                    if (
                        isinstance(ctx, ast.Attribute)
                        and isinstance(ctx.value, ast.Name)
                        and ctx.value.id == "self"
                        and ctx.attr in locks
                    ):
                        blocks.append(node)
                        break
            return blocks

        guarded: Set[str] = set()
        per_method_blocks: Dict[str, List[ast.With]] = {}
        for m in methods:
            blocks = lock_blocks(m)
            per_method_blocks[m.name] = blocks
            for b in blocks:
                for attr, _ in _self_attr_writes(b):
                    guarded.add(attr)
        guarded -= locks
        if not guarded:
            continue

        for m in methods:
            if m.name == "__init__":
                continue
            locked_nodes: Set[int] = set()
            for b in per_method_blocks[m.name]:
                for sub in ast.walk(b):
                    locked_nodes.add(id(sub))
            for attr, node in _self_attr_writes(m):
                if attr in guarded and id(node) not in locked_nodes:
                    out.append(
                        Finding(
                            rule="lock-discipline",
                            severity="warning",
                            path=path,
                            line=node.lineno,
                            col=node.col_offset,
                            message=f"self.{attr} is written under "
                            f"`with self.<lock>` elsewhere in "
                            f"{cls.name} but bare here: the write races "
                            "the guarded ones across threads",
                            hint="take the lock, or mark a single-threaded "
                            "phase with `# r2d2: disable=lock-discipline`",
                        )
                    )
    return out


def _rule_raw_shard_map_import(tree: ast.Module, path: str) -> List[Finding]:
    """Every shard_map must come through parallel/jax_compat.shard_map —
    the one wrapper that states the manual-axis convention (the tp×fsdp
    manual train step depends on axis_names=None meaning FULLY manual).
    A `from jax import shard_map` anywhere else skips that defaulting; a
    `jax.experimental.shard_map` import is the removed older API
    (check_rep/auto) on top of that."""
    norm = path.replace(os.sep, "/")
    if norm.endswith("parallel/jax_compat.py"):
        return []
    out: List[Finding] = []

    def flag(node: ast.AST, what: str) -> None:
        out.append(
            Finding(
                rule="raw-shard-map-import",
                severity="error",
                path=path,
                line=node.lineno,
                col=node.col_offset,
                message=f"{what} bypasses the parallel/jax_compat wrapper "
                "and its manual-axis defaulting (jax.experimental."
                "shard_map is, besides, the removed check_rep/auto API)",
                hint="from r2d2_tpu.parallel.jax_compat import shard_map",
            )
        )

    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod.startswith("jax.experimental.shard_map"):
                flag(node, f"`from {mod} import ...`")
            elif mod in ("jax", "jax.experimental") and any(
                a.name == "shard_map" for a in node.names
            ):
                flag(node, f"`from {mod} import shard_map`")
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith("jax.experimental.shard_map"):
                    flag(node, f"`import {a.name}`")
    return out


# block-codec decode / disk page-in surface (replay/codec.py +
# replay/disk_tier.py). Method-style receivers (x.decode_field(...)) and
# bare names (decode_field(...)) both match: the contract is positional
# ("not on the learner/serve step"), not receiver-typed.
_DECODE_CALL_NAMES = {"decode_field", "decode_block", "read_block"}
_MMAP_CALLS = {"np.memmap", "numpy.memmap", "mmap.mmap"}


def _rule_codec_decode_in_hot_loop(tree: ast.AST, path: str) -> List[Finding]:
    if not (is_hot_path(path) or is_serve_path(path)):
        return []
    out: List[Finding] = []
    seen: Set[Tuple[int, int]] = set()
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.While)):
            continue
        for stmt in list(loop.body) + list(loop.orelse):
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call):
                    continue
                d = _dotted(node.func) or ""
                last = d.split(".")[-1]
                if d in _MMAP_CALLS:
                    what = f"{d}(...)"
                elif last in _DECODE_CALL_NAMES:
                    what = f"{d or last}(...)"
                else:
                    continue
                key = (node.lineno, node.col_offset)
                if key in seen:
                    continue
                seen.add(key)
                out.append(
                    Finding(
                        rule="codec-decode-in-hot-loop",
                        severity="warning",
                        path=path,
                        line=node.lineno,
                        col=node.col_offset,
                        message=f"{what} inside a hot-loop body: block-codec "
                        "inflate / mmap page-in belongs on the replay "
                        "staging thread (tiered_store._fill_disk_rows), not "
                        "the learner/serve step — a per-iteration decode "
                        "erases the three-tier overlap",
                        hint="sample through TieredReplayBuffer (the staging "
                        "thread decodes behind the prefetch queue), or mark "
                        "a deliberate cold-path decode with "
                        "`# r2d2: disable=codec-decode-in-hot-loop`",
                    )
                )
    return out


_RULES = (
    _rule_host_sync,
    _rule_serve_step_host_sync,
    _rule_jit_in_loop,
    _rule_unhashable_static_arg,
    _rule_shape_branch_in_jit,
    _rule_float64,
    _rule_fault_sites,
    _rule_snapshot_topology,
    _rule_lock_discipline,
    _rule_host_tree_in_hot_loop,
    _rule_raw_shard_map_import,
    _rule_codec_decode_in_hot_loop,
)


# ---------------------------------------------------------------- driver


def analyze_source(
    text: str, path: str
) -> Tuple[List[Finding], List[Finding]]:
    """Run every AST rule over one file's source. Returns
    (findings, suppressed) — suppressed findings matched a
    `# r2d2: disable=` comment and do not gate."""
    try:
        tree = ast.parse(text)
    except SyntaxError as e:
        return (
            [
                Finding(
                    rule="syntax-error",
                    severity="error",
                    path=path,
                    line=e.lineno or 0,
                    col=e.offset or 0,
                    message=f"file does not parse: {e.msg}",
                )
            ],
            [],
        )
    src_lines = text.splitlines()
    suppress = _suppressions(src_lines)
    guards = guarded_by_map(tree, src_lines)
    findings: List[Finding] = []
    suppressed: List[Finding] = []
    for rule_fn in _RULES:
        for f in rule_fn(tree, path):
            rules_here = suppress.get(f.line, set())
            if f.rule in rules_here or "all" in rules_here:
                suppressed.append(f)
            elif f.rule == "lock-discipline" and guards.get(f.line):
                # a guarded-by annotation asserts the named lock is held
                # at this write (caller-holds-lock contract); the
                # concurrency pass checks the assertion interprocedurally
                suppressed.append(f)
            else:
                findings.append(f)
    return findings, suppressed


def collect_py_files(paths: Iterable[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(
                    d for d in dirs if d != "__pycache__" and not d.startswith(".")
                )
                out.extend(
                    os.path.join(root, f) for f in sorted(files) if f.endswith(".py")
                )
        elif p.endswith(".py") and os.path.exists(p):
            out.append(p)
    return sorted(dict.fromkeys(out))


def analyze_paths(
    paths: Iterable[str],
) -> Tuple[List[Finding], List[Finding]]:
    """AST-lint every .py file under `paths` (files or directories).
    Returns (findings, suppressed), stable-sorted."""
    findings: List[Finding] = []
    suppressed: List[Finding] = []
    for path in collect_py_files(paths):
        with open(path, encoding="utf-8") as fh:
            text = fh.read()
        f, s = analyze_source(text, path)
        findings.extend(f)
        suppressed.extend(s)
    findings.sort(key=Finding.sort_key)
    suppressed.sort(key=Finding.sort_key)
    return findings, suppressed
