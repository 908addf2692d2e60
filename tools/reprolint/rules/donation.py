"""Donation-safety rules.

At pod scale the round step's flatP/optimizer buffers dominate HBM;
a `jax.jit` entry point that shards its inputs but never donates them
doubles peak memory (the ShardedEngine step donates (0, 1, 2) for this
reason).  And donation has teeth: touching a donated argument after the
call reads from a deleted buffer.

`jit-no-donate`: a jit with `in_shardings=` (or wrapping one of the
round/phase/step builders) that passes no `donate_argnums`/
`donate_argnames`.

`use-after-donate`: a name passed at a donated position of a jitted
call and then used again in the same straight-line body.

`params-closure`: an engine step/round/phase function in the engine
trees (core/, federated/, launch/) that *closes over* the backbone
`params` instead of taking it as an argument.  A closed-over backbone is
baked into the trace as a constant: it can't be given an in_shardings
entry (so FSDP/TP storage sharding silently degrades to replication),
it escapes the donation audit, and every re-trace re-embeds it.  Every
round takes it explicitly, as its leading argument
(`fedround.make_round_fn` returns `pfn(params, flatP, ...)`).
"""
from __future__ import annotations

import ast
import re
from typing import Iterator

from tools.reprolint.core import Finding, Module, Project, Rule, register_rule
from tools.reprolint.rules import _util as u

ENTRY_FN_RE = re.compile(r"^(make|build)_\w*(round|phase|step)\w*$")
DONATE_KWS = {"donate_argnums", "donate_argnames"}

# params-closure scope: the engine trees whose step functions feed jits
STEP_TOKENS = {"step", "round", "rounds", "phase"}
PARAM_SCOPES = ("src/repro/core/", "src/repro/federated/",
                "src/repro/launch/")


def _jit_calls(tree) -> Iterator[ast.Call]:
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and u.call_name(node) == "jax.jit":
            yield node


@register_rule("jit-no-donate")
class JitNoDonate(Rule):
    """Sharded / round-step jit entry points without donation."""

    def check(self, mod: Module, project: Project) -> Iterator[Finding]:
        if not mod.rel.startswith("src/"):
            return
        for call in _jit_calls(mod.tree):
            kws = {k.arg for k in call.keywords}
            if kws & DONATE_KWS:
                continue
            if "in_shardings" in kws:
                yield Finding(
                    mod.rel, call.lineno, self.name,
                    "jax.jit with in_shardings= but no donate_argnums — "
                    "params/optimizer buffers are duplicated at pod "
                    "scale; donate them (or justify why not)")
                continue
            if call.args and isinstance(call.args[0], ast.Call):
                inner = u.call_name(call.args[0]) or ""
                short = inner.rsplit(".", 1)[-1]
                if ENTRY_FN_RE.match(short):
                    yield Finding(
                        mod.rel, call.lineno, self.name,
                        f"jax.jit({inner}(...)) compiles a round/phase "
                        "entry point without donate_argnums — state "
                        "buffers are copied every call; donate (or "
                        "justify why the backend ignores donation)")


@register_rule("use-after-donate")
class UseAfterDonate(Rule):
    """A donated argument referenced after the donating call."""

    def _donating_jits(self, fn):
        """name -> set of donated positional indices, for
        `f = jax.jit(..., donate_argnums=<literal>)` assignments."""
        out = {}
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)
                    and u.call_name(node.value) == "jax.jit"):
                continue
            donated = None
            for k in node.value.keywords:
                if k.arg == "donate_argnums":
                    if isinstance(k.value, ast.Constant) and \
                            isinstance(k.value.value, int):
                        donated = {k.value.value}
                    elif isinstance(k.value, (ast.Tuple, ast.List)):
                        elts = k.value.elts
                        if all(isinstance(e, ast.Constant) and
                               isinstance(e.value, int) for e in elts):
                            donated = {e.value for e in elts}
            if donated:
                for nm in u.assigned_names(node):
                    out[nm] = donated
        return out

    def check(self, mod: Module, project: Project) -> Iterator[Finding]:
        if not mod.rel.startswith("src/"):
            return
        for fn in u.walk_functions(mod.tree):
            body = getattr(fn, "body", None)
            if not isinstance(body, list):
                continue
            jits = self._donating_jits(fn)
            if jits:
                yield from self._scan_body(body, jits, mod)

    def _scan_body(self, body, jits, mod) -> Iterator[Finding]:
        donated_names = {}   # arg name -> line it was donated on
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            for node in ast.walk(stmt):
                if isinstance(node, ast.Name) and \
                        isinstance(node.ctx, ast.Load) and \
                        node.id in donated_names:
                    yield Finding(
                        mod.rel, node.lineno, self.name,
                        f"`{node.id}` was donated on line "
                        f"{donated_names[node.id]} — its buffer may "
                        "already be deleted; rebind the call's result "
                        "instead of reusing the input")
            for node in ast.walk(stmt):
                if isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Name) and \
                        node.func.id in jits:
                    for i in jits[node.func.id]:
                        if i < len(node.args) and \
                                isinstance(node.args[i], ast.Name):
                            donated_names[node.args[i].id] = node.lineno
            for nm in u.assigned_names(stmt):
                donated_names.pop(nm, None)


def _own_scope(fn) -> Iterator[ast.AST]:
    """Nodes of `fn`'s own scope: nested function bodies are skipped
    (their loads belong to their own scope — `walk_functions` visits
    each of them separately)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, u.FUNC_TYPES):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


@register_rule("params-closure")
class ParamsClosure(Rule):
    """An engine step/round/phase function closing over the backbone
    `params` instead of taking it as an explicit argument."""

    def check(self, mod: Module, project: Project) -> Iterator[Finding]:
        if not mod.rel.startswith(PARAM_SCOPES):
            return
        for fn in u.walk_functions(mod.tree):
            name = u.func_name(fn)
            if not STEP_TOKENS & set(name.lower().split("_")):
                continue
            bound = set(u.arg_names(fn))
            loads = []
            for node in _own_scope(fn):
                if isinstance(node, ast.Name) and node.id == "params":
                    if isinstance(node.ctx, ast.Load):
                        loads.append(node)
                    else:
                        bound.add(node.id)
            if loads and "params" not in bound:
                yield Finding(
                    mod.rel, loads[0].lineno, self.name,
                    f"`{name}` closes over `params` instead of taking it "
                    "as an argument — a closed-over backbone is baked "
                    "into the trace as a constant: no in_shardings entry "
                    "(FSDP/TP storage sharding degrades to replication), "
                    "invisible to the donation audit, re-embedded on "
                    "every re-trace; take it as the leading argument, "
                    "like fedround.make_round_fn's pfn(params, flatP, ...)")
