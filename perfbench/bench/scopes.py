"""Device time by the named scopes of the round program (`fedround.*`).

The round program traces its phases under `jax.named_scope`
(`fedround.clients`, `fedround.upload`, `fedround.aggregate`,
`fedround.server_update`).  A scope survives compilation only in each
HLO instruction's `metadata={op_name="..."}`: the recorded TPU trace
carries no `op_name` on its `XLA Ops` events.  So `scopes` reads the
compiled programs' HLO texts, the way `tracing.kernel_names` reads their
kernels, and `scope_seconds` joins the result with a `tracing.Summary`'s
op table:

- an instruction's scope is the last `fedround.<name>` in its `op_name`
  (the innermost: `fedround.clients/vmap(fedround.upload)/...` is
  `fedround.upload`);
- a fusion takes the scope of its fused computation's root, or else its
  own;
- an instruction with none (one the compiler made, such as a cumulative
  sum's `reduce-window`, or a copy) takes the scope of its first operand
  that has one, or else that of the instruction that calls its
  computation (a loop body's ops that of the loop);
- the op table names a Pallas call by its kernel function, and one
  kernel may run in several scopes (the selector's kernels in the
  server's download mask and in every client's upload).  Its time is
  shared among them in proportion to the elements of each call's
  largest array: the kernels read and write memory at a rate that
  barely depends on the call.
"""
from __future__ import annotations

import collections
import functools
import re
from typing import Dict, List, Optional, Tuple

from bench import tracing

_SCOPE = re.compile(r"fedround\.[A-Za-z_]+")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply)=(%[\w.\-]+)")
_CALL_SETS = re.compile(
    r"\b(?:branch_computations|called_computations)=\{([^}]*)\}")
_ARRAY = re.compile(r"\b(?:pred|bf16|[fsuc]\d+|f8\w*)\[([\d,]*)\]")


def _scope(op_name: Optional[str]) -> Optional[str]:
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else None


def _elements(line: str) -> int:
    """Elements of the largest array an instruction names (result and
    operands; the backend config and metadata left out)."""
    head = re.split(r", (?:backend_config|metadata|custom_call_target)=",
                    line)[0]
    best = 1
    for dims in _ARRAY.findall(head):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        best = max(best, n)
    return best


def _operands(line: str) -> List[str]:
    """The instructions an HLO instruction line reads, in order."""
    rhs = line.split(" = ", 1)[1]
    start = re.search(r"\b[a-z][a-z0-9\-]*\(", rhs)
    if start is None:
        return []
    depth, i = 0, start.end() - 1
    for j in range(i, len(rhs)):
        depth += {"(": 1, ")": -1}.get(rhs[j], 0)
        if depth == 0:
            return re.findall(r"%[\w.\-]+", rhs[i:j])
    return []


def _module_scopes(text: str) -> Dict[str, Tuple[Optional[str], int]]:
    """{instruction: (scope, elements)} for one HLO module."""
    comp = None
    own: Dict[str, Optional[str]] = {}
    comp_of: Dict[str, str] = {}
    root: Dict[str, str] = {}
    caller: Dict[str, str] = {}
    calls: Dict[str, str] = {}          # fusion -> its fused computation
    size: Dict[str, int] = {}
    operands: Dict[str, List[str]] = {}
    for line in text.splitlines():
        if not line.startswith(" "):
            if line.rstrip().endswith("{"):
                comp = line.replace("ENTRY ", "").split(" ", 1)[0]
            continue
        if " = " not in line or comp is None:
            continue
        instr = line.strip().split(" = ")[0]
        is_root = instr.startswith("ROOT ")
        instr = instr.replace("ROOT ", "")
        if is_root:
            root[comp] = instr
        comp_of[instr] = comp
        m = _OP_NAME.search(line)
        own[instr] = _scope(m.group(1) if m else None)
        size[instr] = _elements(line)
        operands[instr] = _operands(line)
        called = _CALLS.findall(line)
        for group in _CALL_SETS.findall(line):
            called += [c.strip() for c in group.split(",") if c.strip()]
        for c in called:
            caller.setdefault(c, instr)
        if " fusion(" in line and called:
            calls[instr] = called[0]

    @functools.lru_cache(maxsize=None)
    def scope_of(instr: str) -> Optional[str]:
        fused = calls.get(instr)
        s = own.get(fused and root.get(fused)) if fused else None
        s = s or own.get(instr)
        for op in operands[instr]:
            if s is not None:
                break
            if op in operands:
                s = scope_of(op)
        if s is None and comp_of.get(instr) in caller:
            s = scope_of(caller[comp_of[instr]])
        return s

    return {i: (scope_of(i), size[i]) for i in own}


def scopes(hlo_texts: List[str]) -> Dict[Tuple[str, str], str]:
    """{(program, instruction): scope} for every instruction of the
    compiled programs' HLO texts that lies in a `fedround.*` scope."""
    out = {}
    for text in hlo_texts:
        m = _MODULE.search(text)
        module = m.group(1) if m else ""
        for instr, (s, _) in _module_scopes(text).items():
            if s is not None:
                out[(module, instr)] = s
    return out


@functools.lru_cache(maxsize=2)
def _shares(hlo_texts: Tuple[str, ...]
            ) -> Dict[Tuple[str, str], Dict[str, float]]:
    """{(program, op name as the op table has it): {scope: share}}."""
    kernels = tracing.kernel_names(list(hlo_texts))
    weights: Dict[Tuple[str, str], Dict[str, float]] = \
        collections.defaultdict(lambda: collections.defaultdict(float))
    for text in hlo_texts:
        m = _MODULE.search(text)
        module = m.group(1) if m else ""
        for instr, (s, n) in _module_scopes(text).items():
            if s is None:
                continue
            label = kernels.get((module, instr), instr)
            weights[(module, label)][s] += n
    out = {}
    for key, by_scope in weights.items():
        total = sum(by_scope.values())
        out[key] = {s: w / total for s, w in by_scope.items()}
    return out


def scope_seconds(summary, hlo_texts: List[str], name: str
                  ) -> Optional[float]:
    """Device self time of the ops whose innermost scope is `name`, or
    None where the programs carry no `fedround.*` scope at all."""
    shares = _shares(tuple(hlo_texts))
    if not shares:
        return None
    total = 0.0
    for key, (seconds, _) in summary.ops.items():
        module, label = key.split(":", 1)
        total += seconds * shares.get((module, label), {}).get(name, 0.0)
    return total


def per_round_ms(run, name: str) -> Optional[float]:
    """`scope_seconds` per traced round of a federated cell, in ms."""
    units = run.traced.get("units", 0.0) if run.traced else 0.0
    if run.kind != "federated" or run.trace is None or units <= 0:
        return None
    s = scope_seconds(run.trace, run.hlo_texts, name)
    return None if s is None else 1e3 * s / units
