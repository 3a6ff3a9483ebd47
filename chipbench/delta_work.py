"""The work one training step requires of the chunked gated delta rule (Kimi
delta attention's recurrence), from shapes alone, for ``kda_scan_roofline``.

The chunk is fixed here at :data:`CHUNK` positions and is never read from
the program, so that a later kernel with another chunk, or none, is held to
the same yardstick. A chunk of ``C`` positions and one head requires,
forward, ``2 d_k`` a pair for ``K K^T`` over the ``C (C - 1) / 2`` pairs
below the diagonal and for ``Q K^T`` over the ``C (C + 1) / 2`` on and below
it, ``2 (d_k + d_v)`` a pair below the diagonal for the triangular solve of
``[W | U']``, ``2 d_v`` a pair on and below it for ``P U``, and ``2 C d_k
d_v`` for each of ``W S``, ``(Q e^G) S`` and ``K^T U``; the backward twice
the forward. Bytes, float32: q, k, v, g, beta and o read or written once
forward, the same and their cotangents once backward, and the state at each
chunk's start written forward and read backward.
"""

CHUNK = 64
BYTES = 4


def delta_rule_work(seq, batch, heads, dk, dv, layers):
    """``{"flops", "bytes"}`` of one training step over ``layers`` delta
    layers of ``heads`` heads, ``batch`` sequences of ``seq`` positions."""
    chunks = -(-seq // CHUNK)
    below, on_and_below = CHUNK * (CHUNK - 1) // 2, CHUNK * (CHUNK + 1) // 2
    forward = (2 * dk * (below + on_and_below) + 2 * (dk + dv) * below
               + 2 * dv * on_and_below + 3 * 2 * CHUNK * dk * dv)
    rows = layers * batch * heads
    # q, k, g (d_k deep), v, o (d_v deep) and beta, a position
    values = seq * (3 * dk + 2 * dv + 1)
    return {"flops": 3 * rows * chunks * forward,
            "bytes": BYTES * rows * (3 * values + 2 * chunks * dk * dv)}
