"""Operations and bytes of the keyed window-join step, from the
deployment's shapes and never from the implementation's buffers:
whatever implements the step later, a block of n events over P keys with
a ring of K entries a key on the windowed side must

  - read and write the ring once (P x K entries, each a timestamp, a
    live flag and the columns the windowed side carries);
  - read the placed events' columns once (lane, side, timestamp and the
    columns a row takes from the event; an event that passes neither
    side's filter is not placed);
  - write the rows out once (timestamp, lane and the selected columns);
  - compare each placed event with its lane's K entries.
"""


def cost(shape, blocks, events, rows):
    """blocks: one per delivered block (each steps the query's ring);
    events: the window's, of which `placed_share` are placed; rows: the
    window's."""
    ring = shape["keys"] * shape["slots"] * shape["ring_bytes_per_entry"]
    placed = shape["placed_share"] * events
    return {
        "bytes": 2 * ring * blocks + shape["event_bytes"] * placed
        + shape["row_bytes"] * rows,
        "flops": shape["flops_per_event_slot"] * shape["slots"]
        * shape["queries"] * placed,
    }
