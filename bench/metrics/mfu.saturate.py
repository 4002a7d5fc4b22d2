"""Operations the completed predictions needed (their real nodes and
edges through the whole PMGNS forward, ``counts.model_flops``) over the
window, the chips and the chip's bf16 peak."""
from window import completed_in_window


def read(run):
    if run.peaks is None:
        return None
    flops = sum(run.counts.model_flops(run.model, *run.sizes[
        run.requests.pool_idx[i]]) for i in completed_in_window(run))
    if not flops:
        return None
    return 100.0 * flops / (run.seconds * run.chips
                            * run.peaks["bf16_flops_per_s"])
