"""Device-idle time per output token outside the host's decode loop:
as ``decode_loop_idle_ms``, the idle given to ``engine_prefill``,
``engine_route`` (the router-load read-back), ``engine_readback`` or
``engine_serve`` itself, summed over the requests and divided by their
output tokens (``progtrace``)."""

from progtrace import SERVE_SYNC, idle_per_request, pooled_idle_ms


def read(run):
    requests = idle_per_request(run)
    return None if requests is None else pooled_idle_ms(requests,
                                                        SERVE_SYNC)
