"""Host milliseconds per request inside ``PredictionService.submit_json``
(parse, fingerprint, featurise, enqueue), on the benchmark's clock."""
from window import submit_ms


def read(run):
    return submit_ms(run)
