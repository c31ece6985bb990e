"""Keyed window join: the device step.

One lane per join key (the key → lane map is the host's, as for the
keyed NFA and the window rings), and per windowed side of the join a ring
of ``K`` slots a lane: the event's timestamp, the columns the select or
the residual condition reads, a live flag and the lane's insertion count
at its arrival (``seq``, which is both the slot, ``seq % K``, and the
arrival order of the rows).  A block is the dense ``[P, T]`` scatter of
``ops/nfa.pack_blocks``; its ``__stream`` plane holds the sides an event
belongs to (``LEFT | RIGHT`` bits: the sides of a self-join meet in one
chunk).  The int planes of the columns the step reads off an event (a
ring's 64-bit halves and string codes) come up as compact rows, only
for the events that carry them, and are scattered into their dense
planes on the device before the scan.  The step scans the ``T`` ticks;
at each tick every lane

  (a) expires the entries of both rings with ``ts + window <= now`` of
      the arriving event (upstream's TimeWindowProcessor; a lane is only
      ever expired by its own events, which is all a probe can see);
  (b) as a left event, if the left side triggers: matches the live
      entries of the right ring under the residual; then enters the
      left ring;
  (c) the same as a right event, against the left ring (which by then
      holds the event itself, where it is on both sides: the order in
      which upstream's junction hands an event to the two receivers of a
      self-join).

Time entries expire in arrival order, so the live entries of a lane are
the last ones inserted and slot ``n % K`` is free unless the ring is
full of live entries; then the step raises ``overflow`` and the host
doubles ``K`` and replays the block from the carry it started from:
nothing is ever dropped.

Everything is laid out with the lanes minor (``[K, P]``, ``[T, P]``): a
tick is elementwise work over ``K`` sublanes by ``P`` lanes, with no
gather and no scatter.  Matches leave as masks ``[T, K, P]`` per probing
direction; ``ops/compact.compact_indices`` finds their flat indices, and
the matched entries' columns are gathered from the per-tick snapshots of
the ring as they lie.  The per-lane counters (``JOIN_CTR``) ride the
carry and are summed into the egress tail: no read of their own.
"""
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .compact import compact_indices

#: the side bits of an event, in a block's ``__stream`` plane
LEFT, RIGHT = 1, 2

#: the per-lane counters, in the order of the carry's ``ctr`` rows:
#: probing events that met a windowed side; those of them that found a
#: row; rows; events that entered a ring; entries expired
JOIN_CTR = ("probes", "probe_hits", "rows", "inserted", "expired")


class Ring(NamedTuple):
    """One windowed side: its window and the planes it carries, as
    ``(name, "f" | "i")`` (float32 or int32)."""
    window_ms: int
    planes: Tuple[Tuple[str, str], ...]


class JoinSpec(NamedTuple):
    """``rings[s]``: side ``s``'s ring, None without a window;
    ``triggers[s]``: do its events probe; ``residual(left values, right
    values)`` -> a mask, or None."""
    rings: Tuple[Optional[Ring], Optional[Ring]]
    triggers: Tuple[bool, bool]
    residual: Optional[Callable]


def _dtype(kind: str):
    return jnp.float32 if kind == "f" else jnp.int32


def make_carry(spec: JoinSpec, n_lanes: int, n_slots: int) -> Dict[str, Any]:
    def ring(r: Ring):
        kp = (n_slots, n_lanes)
        return {"ts": jnp.zeros(kp, jnp.int32),
                "live": jnp.zeros(kp, bool),
                "seq": jnp.zeros(kp, jnp.int32),
                "n": jnp.zeros((n_lanes,), jnp.int32),
                "cols": {name: jnp.zeros(kp, _dtype(kind))
                         for name, kind in r.planes}}
    return {"ring": tuple(None if r is None else ring(r)
                          for r in spec.rings),
            "ctr": jnp.zeros((len(JOIN_CTR), n_lanes), jnp.int32)}


def grow_lanes(carry: Dict[str, Any], n_lanes: int) -> Dict[str, Any]:
    """Fresh lanes behind the ones there are (the lanes are minor)."""
    return jax.tree_util.tree_map(
        lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 1) +
                          [(0, n_lanes - a.shape[-1])]), carry)


def double_slots(carry: Dict[str, Any]) -> Dict[str, Any]:
    """Rings of ``2K`` slots: an entry's slot is ``seq % 2K``, which is
    its old one or ``K`` above it, by bit ``K`` of its ``seq``."""
    def ring(r):
        if r is None:
            return None
        K = r["ts"].shape[0]
        up = (r["seq"] & K) != 0
        twice = lambda a: jnp.concatenate([a, a], axis=0)
        out = jax.tree_util.tree_map(twice, {k: r[k] for k in
                                             ("ts", "seq", "cols")})
        out["live"] = jnp.concatenate([r["live"] & ~up, r["live"] & up],
                                      axis=0)
        out["n"] = r["n"]
        return out
    return {"ring": tuple(ring(r) for r in carry["ring"]),
            "ctr": carry["ctr"]}


def _bits(a):
    return a if a.dtype == jnp.int32 else \
        jax.lax.bitcast_convert_type(a, jnp.int32)


def build_step(spec: JoinSpec, present: Tuple[bool, bool],
               groups: Tuple[Tuple[str, ...], ...] = ()):
    """-> ``step(carry, block, cap)`` -> ``(carry, rows, tail)`` for the
    blocks of one input stream, which hold events of the sides
    ``present``.  ``block``: ``ts`` and ``side`` ``[P, T]`` int32, per
    float column ``f:<name>`` ``[P, T]``, and ``rows``: per group of
    ``groups`` (the names of its int planes) a pair ``(idx, vals)``:
    ``idx`` ``[R]`` int32 the flat cell ``tick * P + lane`` of each row
    (``P * T`` and beyond: padding, dropped), ``vals`` ``[n, R]`` int32
    the group's planes.  A cell holds one event, so the scatter is
    exact.  ``rows`` (the result): ``[cap, 2 + C]`` int32, per match its
    flat index over ``[D, T, K, P]`` (``D`` the probing directions of
    ``directions(spec, present)``, in their order), the matched entry's
    ``seq`` and its planes' bits; ``tail`` int32: the true row count,
    the lanes whose ring overflowed, then ``JOIN_CTR`` summed over the
    lanes."""
    dirs = directions(spec, present)
    width = max([len(spec.rings[1 - s].planes) for s in dirs], default=0)

    def tick(c, x):
        now, side = x["ts"], x["side"]
        ev = {k[2:]: v for k, v in x.items() if k[1] == ":"}
        on = ((side & LEFT) != 0, (side & RIGHT) != 0)
        rings = list(c["ring"])
        ctr = c["ctr"]
        grew = jnp.zeros_like(on[0])
        gone_n = jnp.zeros_like(now)
        for s, r in enumerate(rings):
            if r is None:
                continue
            gone = r["live"] & (side != 0)[None] & \
                (r["ts"] <= (now - spec.rings[s].window_ms)[None])
            rings[s] = dict(r, live=r["live"] & ~gone)
            gone_n = gone_n + jnp.sum(gone, axis=0, dtype=jnp.int32)
        add = {"expired": gone_n}
        ys = {}
        for s in (0, 1):
            if not present[s]:
                continue
            o = 1 - s
            if s in dirs:
                ring = rings[o]
                m = ring["live"] & on[s][None]
                if spec.residual is not None:
                    mine = {k: v[None] for k, v in ev.items()}
                    vals = (mine, ring["cols"]) if s == 0 else \
                        (ring["cols"], mine)
                    m = m & jnp.broadcast_to(
                        jnp.asarray(spec.residual(*vals), bool), m.shape)
                found = jnp.sum(m, axis=0, dtype=jnp.int32)
                add["probes"] = add.get("probes", 0) + on[s]
                add["probe_hits"] = add.get("probe_hits", 0) + (found > 0)
                add["rows"] = add.get("rows", 0) + found
                ys[s] = {"m": m, "seq": ring["seq"],
                         "cols": [_bits(ring["cols"][name]) for name, _k
                                  in spec.rings[o].planes]}
            r = rings[s]
            if r is not None:
                K = r["ts"].shape[0]
                at = (jnp.arange(K, dtype=jnp.int32)[:, None] ==
                      (r["n"] % K)[None]) & on[s][None]
                grew = grew | jnp.any(at & r["live"], axis=0)
                put = lambda new, old: jnp.where(at, new[None], old)
                rings[s] = {
                    "ts": put(now, r["ts"]), "live": r["live"] | at,
                    "seq": put(r["n"], r["seq"]), "n": r["n"] + on[s],
                    "cols": {k: put(ev[k], v)
                             for k, v in r["cols"].items()}}
                add["inserted"] = add.get("inserted", 0) + on[s]
        ctr = ctr + jnp.stack([
            jnp.asarray(add.get(name, 0), jnp.int32) +
            jnp.zeros_like(now) for name in JOIN_CTR])
        return {"ring": tuple(rings), "ctr": ctr}, (ys, grew)

    def keyed_join_step(carry, block, cap):
        P, T = block["ts"].shape
        xs = {k: v.T for k, v in block.items() if k != "rows"}  # lanes minor
        for names, (idx, vals) in zip(groups, block["rows"]):
            dense = jnp.zeros((len(names), T * P), jnp.int32).at[:, idx] \
                .set(vals, mode="drop").reshape(len(names), T, P)
            xs.update({f"i:{name}": dense[j] for j, name in enumerate(names)})
        carry, (ys, grew) = jax.lax.scan(tick, carry, xs)
        tail = [jnp.zeros((), jnp.int32),
                jnp.sum(jnp.any(grew, axis=0), dtype=jnp.int32)]
        if dirs:
            mask = jnp.stack([ys[s]["m"] for s in dirs])   # [D, T, K, P]
            idx, tail[0] = compact_indices(mask, cap)
            safe = jnp.maximum(idx, 0)
            per = mask[0].size
            d, at = safe // per, safe % per

            def gathered(get):
                """A plane of the matched entries: from the snapshots of
                the direction each row belongs to."""
                out = None
                for i, s in enumerate(dirs):
                    a = get(ys[s])
                    v = jnp.zeros_like(at) if a is None else \
                        a.reshape(-1)[at]
                    out = v if out is None else jnp.where(d == i, v, out)
                return out
            cols = [idx, gathered(lambda y: y["seq"])] + [
                gathered(lambda y, j=j: y["cols"][j]
                         if j < len(y["cols"]) else None)
                for j in range(width)]
            rows = jnp.stack(cols, axis=1)
        else:
            rows = jnp.full((cap, 2), -1, jnp.int32)
        tail = jnp.concatenate([jnp.stack(tail),
                                jnp.sum(carry["ctr"], axis=1)])
        return carry, rows, tail

    return keyed_join_step


def directions(spec: JoinSpec, present: Tuple[bool, bool]) -> Tuple[int, ...]:
    """The probing sides of a block that holds the sides ``present``, in
    the order of the step's mask: a left event's rows before its right
    ones."""
    return tuple(s for s in (0, 1) if present[s] and spec.triggers[s]
                 and spec.rings[1 - s] is not None)
