"""Host microseconds inside ``PredictionService.submit_json`` (parse,
fingerprint, featurise, enqueue) per node served, on the benchmark's
clock, over the requests completed in the window."""
from served import records


def read(run):
    got = records(run)
    if got is None:
        return None
    rec = run.requests
    host_s = sum(rec.submitted[i] - rec.sent[i] for i, _ in got)
    return 1e6 * host_s / sum(s["nodes"] for _, s in got)
