"""Temporary HBM of the compiled chunk program, from the compiler's memory
analysis (argument, output and alias sizes are printed on an earlier
line of the run)."""


def read(run):
    return run.memory["temp_size_in_bytes"] / 1e9
