"""Share of the node rows sent to the device that were padding
(``EngineStats`` node-slot deltas across the window)."""


def read(run):
    total = run.deltas["node_slots_total"]
    if not total:
        return None
    return 100.0 * (1.0 - run.deltas["node_slots_real"] / total)
