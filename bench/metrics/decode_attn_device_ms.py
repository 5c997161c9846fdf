"""Mean device time, per run of the decode program (``jit_decode_next``),
of the ops under the ``attn`` scope (``models/model.py``): the union of
their intervals, container ops left out, in the traced part of the
window (``progtrace``)."""

from progtrace import decode_scope_ms


def read(run):
    return decode_scope_ms(run, "attn")
