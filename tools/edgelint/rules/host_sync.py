"""EDG002 — tracer/host-sync hygiene in device contexts and pane loops.

A ``.item()``, ``float()``/``int()``/``bool()``, ``np.asarray``, or
``jax.block_until_ready`` applied to a jnp-derived value is a silent
device→host synchronization: inside a jitted/pallas/shard_map function it
either fails at trace time or (worse) forces a constant-fold; inside the
per-pane host loop it serializes the stream — every pane blocks on the
previous pane's device work, killing async dispatch.

Device contexts are detected structurally:

* functions decorated with ``jit`` / ``pallas_call`` / ``shard_map``
  (including ``partial(jax.jit, ...)`` forms);
* functions passed by name to a jit-wrapping call in the same module
  (``jax.jit(run)``, ``self._compiled(plan, run, ...)``, ``shard_map``);
* the repo's pane-loop hot paths (``StreamSession.step/run/_emit``,
  ``EdgeCloudPipeline.run_stream``) plus any function whose ``def`` line
  carries a ``# edgelint: pane-loop`` marker.

``float(...)``/``int(...)``/``bool(...)`` over host-side expressions —
literals, ``getattr(...)`` window attributes, ``len()``, pure-python
``min``/``max``/``sum`` — are exempt; everything else in a device context
is assumed jnp-derived (the conservative default for a hot path).
Intentional sync boundaries (checkpoint saves, controller readback) get an
inline ``# edgelint: ignore[EDG002] <reason>``.
"""

from __future__ import annotations

import ast
import re
from typing import Iterator

from ..framework import (
    Finding,
    Module,
    Project,
    Rule,
    call_name,
    dotted_name,
    is_constant,
    register_rule,
)

# callables that turn a function into (or wrap it for) device execution
JIT_WRAPPERS = {
    "jit",
    "pallas_call",
    "shard_map",
    "_compiled",  # EdgeCloudPipeline._compiled: jit or shard_map+jit
}

# repo pane-loop hot paths: the host side of the continuous-query stream
PANE_LOOP_FUNCTIONS = {
    "src/repro/core/session.py": {
        "step",
        "run",
        "_emit",
        "_emit_due",
        "_emit_batch",
        "emit_all",
    },
    "src/repro/core/pipeline.py": {"run_stream"},
    # the async runtime's dispatch path must stay sync-free un-suppressed;
    # its one blocking boundary (_retire) and the deferred event readback
    # (_read_score) are deliberately *not* pane-loop functions
    "src/repro/core/runtime.py": {
        "run",
        "process",
        "_consume",
        "_stage",
        "_dispatch",
        "flush",
        "_pump",
        "offer",
    },
}

PANE_LOOP_MARK = re.compile(r"#\s*edgelint:\s*pane-loop\b")

SYNC_CALLS = {
    "np.asarray",
    "np.array",
    "numpy.asarray",
    "numpy.array",
    "jax.device_get",
    "jax.block_until_ready",
}

CASTS = {"float", "int", "bool"}

# host-side expressions a cast may consume without touching the device
HOST_CALLS = {"getattr", "len", "min", "max", "sum", "abs", "round", "time.time"}


def _base_callable(node: ast.AST) -> str | None:
    """Last dotted component of a call target (``jax.jit`` -> ``jit``)."""
    name = dotted_name(node)
    if name is None:
        return None
    return name.rsplit(".", 1)[-1]


def _decorated_device(fn: ast.FunctionDef | ast.AsyncFunctionDef) -> bool:
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        if _base_callable(target) in JIT_WRAPPERS:
            return True
        # @partial(jax.jit, ...) and friends
        if isinstance(dec, ast.Call) and _base_callable(dec.func) == "partial":
            if dec.args and _base_callable(dec.args[0]) in JIT_WRAPPERS:
                return True
    return False


def _names_passed_to_wrappers(tree: ast.Module) -> set[str]:
    """Function names handed (directly or via ``partial``) to a jit wrapper."""
    out: set[str] = set()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and _base_callable(node.func) in JIT_WRAPPERS):
            continue
        args = list(node.args) + [kw.value for kw in node.keywords]
        for arg in args:
            if isinstance(arg, ast.Name):
                out.add(arg.id)
            elif isinstance(arg, ast.Call) and _base_callable(arg.func) == "partial":
                if arg.args and isinstance(arg.args[0], ast.Name):
                    out.add(arg.args[0].id)
    return out


def _is_host_expr(node: ast.AST) -> bool:
    """Expressions that provably never hold a device value."""
    if is_constant(node):
        return True
    if isinstance(node, ast.Call):
        name = call_name(node)
        if name in HOST_CALLS:
            return True
        # pure-python reductions over host containers, e.g. sum(genexpr)
        if name in ("min", "max", "sum"):
            return True
    if isinstance(node, ast.BinOp):
        return _is_host_expr(node.left) and _is_host_expr(node.right)
    if isinstance(node, (ast.GeneratorExp, ast.ListComp)):
        return True
    return False


class HostSyncRule(Rule):
    code = "EDG002"
    name = "host-sync-hygiene"
    guarantee = (
        "no silent device->host syncs inside jitted/pallas/shard_map functions "
        "or the per-pane hot loop; sync boundaries are explicit and justified"
    )

    def check(self, project: Project) -> Iterator[Finding]:
        for mod in project.modules:
            wrapped = _names_passed_to_wrappers(mod.tree)
            pane_names = PANE_LOOP_FUNCTIONS.get(mod.relpath, set())
            lines = mod.source.splitlines()
            # collect device-context functions, then scan their bodies
            # (including nested defs — a closure inside a jitted fn traces)
            contexts = []
            for node in ast.walk(mod.tree):
                if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                marked = node.lineno <= len(lines) and PANE_LOOP_MARK.search(
                    lines[node.lineno - 1]
                )
                if (
                    _decorated_device(node)
                    or node.name in wrapped
                    or node.name in pane_names
                    or marked
                ):
                    contexts.append(node)
            seen: set[int] = set()
            for fn in contexts:
                for node in ast.walk(fn):
                    if isinstance(node, ast.Call) and id(node) not in seen:
                        seen.add(id(node))
                        yield from self._check_call(mod, fn, node)

    def _check_call(
        self, mod: Module, fn: ast.AST, node: ast.Call
    ) -> Iterator[Finding]:
        def finding(msg: str) -> Finding:
            return Finding(
                self.code,
                f"{msg} (inside device context/pane loop `{fn.name}`)",
                mod.relpath,
                node.lineno,
                node.col_offset,
            )

        name = call_name(node)
        if isinstance(node.func, ast.Attribute):
            if node.func.attr == "item" and not node.args:
                yield finding("`.item()` forces a device->host sync")
                return
            if node.func.attr == "block_until_ready" and not node.args:
                yield finding("`.block_until_ready()` blocks the dispatch stream")
                return
        if name in SYNC_CALLS:
            yield finding(f"`{name}` materializes device values on the host")
            return
        if name in CASTS and len(node.args) == 1 and not _is_host_expr(node.args[0]):
            yield finding(
                f"`{name}(...)` on a (possibly) jnp-derived value is a silent "
                "host sync; keep the value on device or sync once at the "
                "window/checkpoint boundary"
            )


register_rule(HostSyncRule())
