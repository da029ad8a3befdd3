"""The fused FedAWE echo kernel's share of its HBM roofline: the least
time its bytes need at the chip's bandwidth (``flops.echo_kernel_bytes``:
the unpadded f32 start and end stacks of the trained rows, the old and the
new global) over the kernel's device time."""
from bench import flops, opnames


def read(run):
    dep = run.cell.cfg["deployment"]
    if not dep["echo_kernel"]:
        return None
    seconds, calls = run.trace.op_seconds(
        lambda name: opnames.is_echo_kernel(name),
        required="the echo kernel")
    expected = run.rounds * run.seeds
    if calls != expected:
        raise opnames.MissingOp(f"{calls} echo kernel executions for "
                                f"{expected} rounds")
    rows = dep["c_max"] or dep["m"]
    nbytes = flops.echo_kernel_bytes(rows, run.n_trainable)
    least = calls * nbytes / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
