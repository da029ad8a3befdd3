"""How the chip's trace names the operations that per-layer metrics read.

An ``XLA Ops`` event is named after its HLO instruction
(``bench.traces.compact_name``: ``<instruction> = <opcode>`` and the
``kind``/``calls``/``custom_call_target`` attributes).
"""
from bench.traces import MissingOp, instruction, opcode  # noqa: F401

COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter")


def is_echo_kernel(name: str) -> bool:
    """The fused FedAWE aggregation is the chunk program's one Mosaic
    kernel."""
    return "custom_call_target=tpu_custom_call" in name


def is_collective(name: str) -> bool:
    op = opcode(name)
    return any(op == c or op.startswith(c + "-") for c in COLLECTIVES)
