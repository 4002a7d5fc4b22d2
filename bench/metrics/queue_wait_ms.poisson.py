"""Mean queue wait, from enqueue to the batcher's drain, of the requests
completed in the window (the serving record's ``queue_wait_ms``)."""
from served import records


def read(run):
    got = records(run)
    if got is None:
        return None
    return sum(s["queue_wait_ms"] for _, s in got) / len(got)
