"""Operations and bytes of the keyed length-window ring step, from the
deployment's shapes and never from the implementation's buffers: a block
of n events over P keys with a ring of L prices each, in each of Q
queries, must

  - read and write the carry planes once (Q rings of P x L, and per key a
    position, a count and a running sum);
  - read the n events' columns once (lane, price, timestamp);
  - write the rows out once (lane, timestamp, sum, avg, count);
  - per admitted event, one evict-subtract, one add, one divide, one
    compare.
"""


def cost(shape, blocks, events, rows):
    """blocks: one per query and delivered block (each steps one query's
    carry); events: the window's; rows: over all queries."""
    carry = shape["keys"] * (shape["length"] * shape["ring_bytes_per_entry"]
                             + shape["carry_bytes_per_key"])
    return {
        "bytes": 2 * carry * blocks + shape["event_bytes"] * events
        + shape["row_bytes"] * rows,
        "flops": shape["flops_per_event"] * shape["queries"] * events,
    }
