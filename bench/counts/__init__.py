"""Operations and bytes of a step program, from a configuration's sizes.

One module per architecture, named in a configuration file's ``counts``
key.  Each gives ``param_bytes(sizes)``, ``decode_work(sizes, lanes)`` for
one fused decode dispatch (``lanes``: each live lane's cache length before
the dispatch and the steps it decodes) and ``chunk_work(sizes, lanes)`` for
one prefill-chunk program (``lanes``: each lane's offset and chunk length).
Both return ``(flops, bytes)``: the least the step needs.  Only live lanes
and live cache positions count, whatever the program pads or masks, and the
weights are read once per decode step and once per chunk program.
"""


def vocab_padded(v: int, multiple: int = 256) -> int:
    return -(-v // multiple) * multiple
