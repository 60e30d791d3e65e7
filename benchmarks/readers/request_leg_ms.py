"""One leg of a streamed request's way to its first token, ms, median over
the requests ADMITTED IN THE TRACED WINDOW (``lib/cluster_spans``: the join of
the window's ``engine.admit`` spans to the run's cluster trace by
``trace_id``).  ``leg``: ``ingress`` (``serve.http.stream`` opens ->
``engine.stream`` starts, which is ``add_request``), ``queue`` (-> admitted),
``prefill`` (-> the loop put the first token into the mailbox),
``egress_first`` (-> the proxy's first write returned), the two halves of
``ingress``, ``proxy_to_replica`` / ``replica_to_engine``, and the stream's
end: ``first_to_last_write`` and ``egress_last`` (``engine.stream``'s end ->
the proxy's last write of a chunk returned).  All on one host's wall clock.  ``None`` when there is no file, the file has holes, the two
clocks disagree by more than the check allows, or no such request has both its
ends in the file."""

import statistics

from benchmarks.lib import cluster_spans as cs


def read(ctx, leg):
    requests = cs.window_requests(ctx)
    values = [r.legs_ms()[leg] for r in requests or ()
              if leg in r.legs_ms()]
    return statistics.median(values) if values else None
