"""Device time of the convolutions (local SGD's forward and backward
through the CNN) per round and chip: the operations that run a
``convolution``, directly or inside a fusion of the compiled program."""
from bench import opnames


def read(run):
    convs = opnames.instructions_with(run.hlo, "convolution")
    total, _ = run.trace.op_seconds(
        lambda name: opnames.instruction(name) in convs,
        required="a convolution")
    return 1e3 * total / (run.rounds * run.chips)
