"""How the chip's trace names the operations that per-layer metrics read.

An ``XLA Ops`` event is named after its HLO instruction
(``bench.traces.compact_name``: ``<instruction> = <opcode>`` and the
``kind``/``calls``/``custom_call_target`` attributes).  A fusion's name does
not say what it computes, so the convolutions are found in the compiled
program's HLO text: every instruction whose opcode is ``convolution`` or
whose called computations hold one.
"""
import re

from bench.traces import MissingOp, instruction, opcode  # noqa: F401

COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_CALLED = re.compile(r"\bcalls=%?([\w.\-]+)")


def instructions_with(hlo_text: str, op: str) -> set:
    """Names of the instructions of ``hlo_text`` (a compiled program's
    ``as_text()``) that run opcode ``op`` themselves or inside the
    computations they call (fusions, at any depth).  Loops are not
    followed: a ``while`` runs its body's instructions, which the trace
    times one by one."""
    pattern = re.compile(r" " + re.escape(op) + r"\(")
    called, owner, hits, holds = {}, {}, set(), set()
    comp = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head and " = " not in line:
            comp = head.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name, body = m.group(1), " " + line[m.end():]
        owner[name] = comp
        called[name] = set(_CALLED.findall(body))
        if pattern.search(body):
            hits.add(name)
            holds.add(comp)
    grew = True
    while grew:
        grew = False
        for name, comps in called.items():
            if owner[name] not in holds and comps & holds:
                holds.add(owner[name])
                grew = True
    return {n for n, comps in called.items() if n in hits or comps & holds}


def is_echo_kernel(name: str) -> bool:
    """The fused FedAWE aggregation is the chunk program's one Mosaic
    kernel."""
    return "custom_call_target=tpu_custom_call" in name


def is_collective(name: str) -> bool:
    op = opcode(name)
    return any(op == c or op.startswith(c + "-") for c in COLLECTIVES)
