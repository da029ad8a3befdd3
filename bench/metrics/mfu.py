"""The whole step's share of the chips' bf16 peak: the FLOPs the rounds
require (each trained client's s local steps of a batch through the
model's forward and backward pass, ``train_flops_per_sample`` of the
configuration's model module) over window time x chips x peak.  Clients
that did not train (masked out, or deferred past the cohort cap) count for
nothing, even where the program computes their rows."""
from bench import models


def read(run):
    cfg = run.cell.cfg
    per_sample = models.of(cfg).train_flops_per_sample(cfg["model"])
    per_client = cfg["training"]["s"] * cfg["training"]["batch"] * per_sample
    # the program's n_active counts the clients that trained: deferred
    # clients are already left out
    trained = sum(r["n_active"] for h in run.histories for r in h)
    peak = run.peaks["bf16_flops_per_s"] * run.chips
    return 100.0 * trained * per_client / (run.window_s * peak)
