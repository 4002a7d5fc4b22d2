"""Program shapes compiled inside the window (``EngineStats.recompiles``)."""


def read(run):
    return run.deltas["recompiles"]
