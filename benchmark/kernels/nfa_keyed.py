"""Operations and bytes of the keyed-NFA step, from the deployment's
shapes and never from the implementation's buffers: whatever implements
the step later, a block of n events over P keys with K pending slots in
each of Q queries must

  - read and write the carry planes once (Q x P x K slots, each a state, a
    start timestamp and the captured price);
  - read the n events' columns once (lane, price, kind, timestamp);
  - write the rows out once (timestamp, p1, p2);
  - compare each event with its key's K slots in every query.
"""


def cost(shape, blocks, events, rows):
    """blocks: one per query and delivered block (each steps one query's
    carry); events: the window's; rows: over all queries."""
    carry = shape["keys"] * shape["slots"] * shape["carry_bytes_per_slot"]
    return {
        "bytes": 2 * carry * blocks + shape["event_bytes"] * events
        + shape["row_bytes"] * rows,
        "flops": shape["flops_per_event_slot"] * shape["slots"]
        * shape["queries"] * events,
    }
