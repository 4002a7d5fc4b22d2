"""Names of the serving path's trace spans.

Every span is a ``jax.profiler.TraceAnnotation``: with no profiler
session running it records nothing and costs about a microsecond, and
while one runs (``jax.profiler.trace(dir)``) it lands on the host plane
of the profiler's trace, one line per Python thread, on the same clock
as the device operations. Spans are opened once per request, drain or
bin, never per node or edge. Keyword arguments become the event's
stats. ``dippm.submit`` carries the service-wide request id ``req``;
``dippm.drain`` carries ``req_first``/``req_last``, the smallest and
largest id in the drain, so a request can be followed from the
caller's thread onto the batcher's.
"""
from jax.profiler import TraceAnnotation

__all__ = ["TraceAnnotation", "SUBMIT", "PARSE", "FINGERPRINT",
           "FEATURISE", "ENQUEUE", "BATCHER_WAIT", "DRAIN", "PLAN", "STAGE",
           "RUN", "FETCH", "COMPILE", "RESOLVE"]

#: One request in ``submit``/``submit_json``, caller's thread (``req``).
SUBMIT = "dippm.submit"
#: The parse of the request's document: ``path`` is ``"arrays"``
#: (``frontends.from_json_arrays``) or ``"objects"``
#: (``frontends.from_json``); ``nodes``.
PARSE = "dippm.parse"
#: ``fingerprint()`` of the parsed graph (cache and quarantine key;
#: ``nodes``).
FINGERPRINT = "dippm.fingerprint"
#: ``sample_from_graph``: node features, static features, padding
#: (``nodes``).
FEATURISE = "dippm.featurise"
#: ``RequestQueue.put``/``put_many``, queue lock included.
ENQUEUE = "dippm.enqueue"
#: The batcher blocked in ``RequestQueue.wait_batch``.
BATCHER_WAIT = "dippm.batcher.wait"
#: One drained batch (``requests``, ``queue_wait_ms``, ``req_first``,
#: ``req_last``).
DRAIN = "dippm.drain"
#: ``plan_bins`` of a drain (``bins``).
PLAN = "dippm.plan"
#: Host staging of one bin (``graphs``; packed ``p``, ``q``, ``g``).
STAGE = "dippm.stage"
#: One bin's device call through its result on the host (``graphs``;
#: the shape's layer plan, ``kernel_layers`` and ``segment_layers``).
RUN = "dippm.run"
#: The device-to-host copy of a bin's result, blocking on the device.
FETCH = "dippm.fetch"
#: In place of ``dippm.run``: the first call of a shape, which compiles
#: (the shape; ``kernel_layers``, ``segment_layers``).
COMPILE = "dippm.compile"
#: Decode and resolve a drain's futures and their callbacks
#: (``requests``).
RESOLVE = "dippm.resolve"
