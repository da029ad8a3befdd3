"""Per-layer time from the program's own names.

The round function runs each layer under a ``jax.named_scope``.  The
compiled program keeps the scope in each instruction's
``metadata={op_name="..."}``: a path such as
``jit(chunk)/while/body/fl_local_sgd/transpose(jvp(...))/dot_general`` or,
where the scope opens inside a transform, ``.../vmap(fl_local_sgd)/...``.
``instruction_scopes`` maps every instruction of the compiled program's
text to the innermost ``fl_*`` component of its path, with the
``vmap(``/``jvp(``/``transpose(`` wrappers stripped; a fusion takes its
own instruction's metadata.  The compiler leaves some instructions
without a scope of their own (layout copies, the pieces it splits a
concatenation into, fusions whose metadata it dropped).  Such a fusion
takes its fused root's scope; an unscoped instruction takes the scope of
the loop or call that runs its computation (so everything inside local
SGD's step loop is local SGD); one with no metadata at all, failing
that, takes the scope its users share.  A trace operation is matched to
its instruction by ``traces.instruction``.

The chunk executors mark every chunk with the host spans named below;
``host_spans`` gives the window's spans of one name.  A reading that
finds nothing under its names raises ``MissingOp``: it never reads 0.
A program that carries none of these names at all (one older than
them) gives nothing to read: the reading is None, and the result line
leaves the metric out.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from bench.traces import CONTAINERS, MissingOp, _union, instruction, opcode

# device scopes, one per layer of a round (repro.core.engine)
SAMPLE = "fl_sample"
AVAILABILITY = "fl_availability"
COHORT_GATHER = "fl_cohort_gather"
LOCAL_SGD = "fl_local_sgd"
AGGREGATE = "fl_aggregate"
COHORT_SCATTER = "fl_cohort_scatter"
SCOPES = (SAMPLE, AVAILABILITY, COHORT_GATHER, LOCAL_SGD, AGGREGATE,
          COHORT_SCATTER)

# host spans of every chunk executor: the step, then its parts in order
CHUNK = "fl_chunk"
DISPATCH = "fl_chunk_dispatch"
FETCH = "fl_chunk_fetch"
RECORDS = "fl_chunk_records"
HOOKS = "fl_chunk_hooks"
SPANS = (CHUNK, DISPATCH, FETCH, RECORDS, HOOKS)

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9_\-]*)\(")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")
_WRAPPER = re.compile(r"^(?:vmap|jvp|transpose)\(")
_SCOPE = re.compile(r"^fl_\w+$")


def scope_of(op_name: str) -> Optional[str]:
    """The innermost ``fl_*`` component of an ``op_name`` path."""
    found = None
    for part in op_name.split("/"):
        while _WRAPPER.match(part):
            part = _WRAPPER.sub("", part, count=1)
        part = part.rstrip(")")
        if _SCOPE.match(part):
            found = part
    return found


def instruction_scopes(hlo_text: str) -> Dict[str, Optional[str]]:
    """Every instruction of a compiled program's ``as_text()`` -> its
    scope (None: under no ``fl_*`` scope)."""
    own, home, fused, root, caller = {}, {}, {}, {}, {}
    bare, users = set(), {}             # no metadata; name -> its users
    comp = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head and " = " not in line:
            comp = head.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name, rest = m.group(1), line[m.end():]
        op_name = _OP_NAME.search(rest)
        own[name] = scope_of(op_name.group(1)) if op_name else None
        if not op_name:
            bare.add(name)
        for operand in _OPERAND.findall(rest.split(", metadata=")[0]):
            users.setdefault(operand, []).append(name)
        home[name] = comp
        if line.lstrip().startswith("ROOT "):
            root[comp] = name
        called = _CALLED.findall(rest) + [
            c.strip().lstrip("%") for group in _BRANCHES.findall(rest)
            for c in group.split(",")]
        opcode = _OPCODE.search(rest)
        if opcode and opcode.group(1) == "fusion" and called:
            fused[name] = called[0]
        for c in called:
            caller.setdefault(c, name)

    def base(name):
        # the instruction's own scope, or a fusion's fused root's
        while own.get(name) is None and name in fused:
            name = root.get(fused[name])
        return own.get(name)

    memo: Dict[str, Optional[str]] = {}

    def scope(name):
        if name not in memo:
            memo[name] = None           # guards the loops' back edges
            up = caller.get(home[name])
            found = base(name) or (scope(up) if up else None)
            if found is None and name in bare:
                shared = {scope(u) for u in users.get(name, ())}
                found = shared.pop() if len(shared) == 1 else None
            memo[name] = found
        return memo[name]

    return {name: scope(name) for name in own}


def scope_map(run) -> Dict[str, Optional[str]]:
    """``run.hlo`` is the compiled program's text; a recorded run keeps
    the map it gives in its place."""
    return run.hlo if isinstance(run.hlo, dict) else \
        instruction_scopes(run.hlo)


def _timed(name: str) -> bool:
    # a loop's or call's time is its body's: count the body
    return opcode(name) not in CONTAINERS


def seconds(trace, smap, names) -> float:
    """Device seconds of the window's operations under any of the scopes
    ``names``, over all chips."""
    names = set(names)
    total, _ = trace.op_seconds(
        lambda op: _timed(op) and smap.get(instruction(op)) in names,
        required=f"under {' or '.join(sorted(names))}")
    return total


def ms_per_round(run, *names) -> Optional[float]:
    """``seconds`` per round and chip, in ms; None for a program with no
    scope at all."""
    smap = scope_map(run)
    if not any(smap.values()):
        return None
    return 1e3 * seconds(run.trace, smap, names) / (run.rounds * run.chips)


def coverage(trace, smap) -> float:
    """Share of the chips' busy time in which an operation under some
    ``fl_*`` scope ran (the union over time, averaged over chips)."""
    covered = 0.0
    for d in trace.devices:
        scoped = [(s, e) for s, e, op in trace.ops[d]
                  if _timed(op) and smap.get(instruction(op))]
        covered += sum(e - s for s, e in _union(scoped, *trace.window))
    return covered / len(trace.devices) / trace.busy_s()


def unscoped(trace, smap, top: int = 10) -> List[Tuple[str, float]]:
    """The operations under no scope that took most device time inside
    the window, per chip on average."""
    lo, hi = trace.window
    per_op: Dict[str, float] = {}
    for d in trace.devices:
        for s, e, op in trace.ops[d]:
            if lo <= 0.5 * (s + e) <= hi and _timed(op) and \
                    not smap.get(instruction(op)):
                per_op[op] = per_op.get(op, 0.0) + e - s
    n = len(trace.devices)
    return sorted(((k, v / n) for k, v in per_op.items()),
                  key=lambda kv: -kv[1])[:top]


def host_spans(trace, name: str) -> List[Tuple[float, float]]:
    """The window's host spans of ``name``, in time order (a span belongs
    to the window when its middle does)."""
    lo, hi = trace.window
    return sorted((s, e) for s, e, n in trace.host
                  if n == name and lo <= 0.5 * (s + e) <= hi)


def chunk_host_gaps(trace) -> Optional[List[float]]:
    """Per chunk boundary of the window, the host seconds from the end
    of one chunk's ``fl_chunk_fetch`` to the end of the next
    ``fl_chunk_dispatch``: building records, hooks, the next dispatch.
    None for a trace with no chunk span at all."""
    if not any(n in SPANS for _, _, n in trace.host):
        return None
    dispatch_ends = [e for _, e in host_spans(trace, DISPATCH)]
    out = []
    for _, fetched in host_spans(trace, FETCH):
        nxt = next((e for e in dispatch_ends if e > fetched), None)
        if nxt is not None:
            out.append(nxt - fetched)
    if not out:
        raise MissingOp(f"no {FETCH!r} span followed by a {DISPATCH!r} "
                        "span in the window")
    return out
