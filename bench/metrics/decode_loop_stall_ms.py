"""What the decode loop's rare long device-idle gaps (a host stall
inside one request) add per output token: the idle given to
``engine_decode`` over all the warm ``generate`` requests in the traced
window, per their output tokens, less the median request's rate
(``decode_loop_idle_ms``).  About 0 without a stall, and a little below
0 when long requests idle less per token than the median one; with
``decode_loop_idle_ms`` and ``serve_sync_idle_ms`` it sums to all the
idle inside those requests (``progtrace``)."""

from progtrace import (DECODE_LOOP, idle_per_request, median_idle_ms,
                       pooled_idle_ms)


def read(run):
    requests = idle_per_request(run)
    if requests is None:
        return None
    return (pooled_idle_ms(requests, (DECODE_LOOP,))
            - median_idle_ms(requests, (DECODE_LOOP,)))
