"""Nodes the program served, as a share of the nodes of the documents
sent, over the requests completed in the window (the serving record's
``nodes`` against each pool document's node count)."""
from served import records


def read(run):
    got = records(run)
    if got is None:
        return None
    sent = sum(run.sizes[run.requests.pool_idx[i]][0] for i, _ in got)
    return 100.0 * sum(s["nodes"] for _, s in got) / sent
