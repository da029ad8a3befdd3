"""Rows the chips ran local SGD for per client that trained, over the
window: the program's ``n_computed`` over its ``n_active``.  Under the
seed ``vmap`` every replicate runs as many rows as the one that runs the
most, so a round counts the largest ``n_computed`` of its seeds once for
each seed.  1.0 where only trained rows compute; a dense round that
computes all m rows reads m / n_active.  None where the program does not
count the rows it computes."""


def read(run):
    hists = run.histories
    if not hists or any("n_computed" not in r for h in hists for r in h):
        return None
    trained = sum(r["n_active"] for h in hists for r in h)
    if not trained:
        return None
    computed = sum(len(hists) * max(r["n_computed"] for r in rounds)
                   for rounds in zip(*hists))
    return computed / trained
