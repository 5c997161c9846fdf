"""Device-idle time per output token that falls in the host's decode
loop, for the typical request: over the warm ``generate`` requests whose
``engine_serve`` span lies in the traced part of the window, each
instant of device idle goes to the innermost program span open then;
this is the median over those requests of the idle given to
``engine_decode`` per the request's output tokens (``progtrace``).
What rare long stalls add is ``decode_loop_stall_ms``."""

from progtrace import DECODE_LOOP, idle_per_request, median_idle_ms


def read(run):
    requests = idle_per_request(run)
    return None if requests is None else median_idle_ms(requests,
                                                        (DECODE_LOOP,))
