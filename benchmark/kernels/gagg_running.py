"""Operations and bytes of the running grouped-aggregation step and its
row gather, from the deployment's shapes and never from the
implementation's buffers: whatever implements the step later, a block of
n events over P groups, each group holding its aggregates, must

  - read and write every group's aggregates once (P x the state a group
    holds: its count, range counts, min, max and sum);
  - read the placed events' columns once (the group and the value; an
    event that fails the filter is not placed);
  - write each placed event's row out once (the group's aggregates after
    the event);
  - per placed event, the compares and adds its aggregates take.
"""


def cost(shape, blocks, events, rows):
    """blocks: one per delivered block (each steps the query's groups);
    events: the window's, of which `placed_share` are placed; rows: the
    window's."""
    state = shape["keys"] * shape["state_bytes_per_key"]
    placed = shape["placed_share"] * events
    return {
        "bytes": 2 * state * blocks + shape["event_bytes"] * placed
        + shape["row_bytes"] * rows,
        "flops": shape["ops_per_event"] * shape["queries"] * placed,
    }
