"""Prefill chunks: 95th percentile, over the window's requests that got a
first token, of the wait from the request's seating in a slot (its first
SEAT event) to its first token (``Request.first_token_s``, stamped after
the host sync of the chunk that made it).  The rest of the TTFT tail after
``queue_wait_p95_s``."""
from harness.context import percentile
from harness.spans import prefill_seconds


def read(ctx):
    return percentile(prefill_seconds(ctx), 95)
