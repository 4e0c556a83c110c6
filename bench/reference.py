"""The plain reference of one analysis, and the comparison that decides
``correct``.

The reference follows the semantics of a postmortem analysis (paper §4.1)
with nothing of the program: expand each profile's tree with its binary's
structure (below), unify the profiles' trees by (parent, kind, name),
number the unified tree in depth-first preorder with children in (kind,
name) order, sum each profile's duplicate (context, metric) values
(exclusive), add every value to all of its context's ancestors
(inclusive, bit 15 of the metric id), and take summary statistics of each
(context, metric) over the profiles that hold it.  The context-major copy
is the same values ordered by (context, metric, profile).

Structure (paper §4.1.1, the "edit" of Fig. 3, and §4.1.3), as the fleet's
structure files state it, one entry per op: its routes, each a path of
lexical scopes and a call-sample weight.

* An op the structure names with one route is placed under that route's
  scopes, below the op's parent in the profile.
* An op with several routes is a call site "in superposition": it becomes a
  placeholder, ``<op>@superposition`` of kind ``KIND_ROUTE``, under its
  parent, and one op leaf hangs under each route's scopes, below the same
  parent.  A value measured on the op goes to the leaves as
  ``v * w / sum(w)`` before the exclusive combine, so the placeholder holds
  no exclusive value of its own.
* A profile without structure is unified as it is.

Departures, where the paper does not say: the contexts below a routed op
in the profile (its lines) hang under the placeholder and keep their own
values, which are not split; the scopes of a route start at the op's
parent in the profile, not at the root.

``build(fleet)`` computes it in float64; ``build(fleet, precision)`` computes
the controls in a precision below the float32 the device path keeps at
``Precision.HIGHEST``: ``"bfloat16"`` rounds every input and every sum to
bfloat16, ``"high"`` gives every input the 16 significant bits that a
three-pass bfloat16 product with 1.0 keeps and sums in float32.
``compare(ref, db)`` reads a database against it and returns the numbers
that the configuration's ``limits`` bound.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

INCLUSIVE_BIT = 1 << 15
KIND_OP, KIND_ROUTE = 4, 6
U = 2.0 ** -24          # float32 unit roundoff: the gaps are in units of u

# the numbers compared, each with its limit in the configuration file
NUMBERS = ("tree_mismatch", "order_errors", "count_excess", "repeat_mismatch",
           "pms_gap_u", "excl_gap_u", "stats_gap_u", "cms_gap_u")


@dataclass
class Reference:
    parent: np.ndarray            # unified tree in preorder
    kind: np.ndarray
    names: list[str]
    keys: np.ndarray              # (p, ctx, packed mid) packed, sorted
    vals: np.ndarray
    scale: np.ndarray             # (P, n_metrics) sum |exclusive| per metric
    stats: dict[str, np.ndarray]  # key (ctx << 16 | mid) sorted, sum, ...
    n_profiles: int

    @property
    def n_exclusive(self) -> int:
        return int(np.count_nonzero((self.keys & 0xFFFF) < INCLUSIVE_BIT))

    @property
    def n_inclusive(self) -> int:
        return int(self.keys.size) - self.n_exclusive


def _pack(p, ctx, mid):
    return (np.asarray(p, np.int64) << 40) | (np.asarray(ctx, np.int64) << 16) \
        | np.asarray(mid, np.int64)


def _round(v: np.ndarray, dtype) -> np.ndarray:
    return np.asarray(v, np.float64).astype(dtype).astype(np.float64)


def _bf16(v: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return np.asarray(v, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


def _three_pass(v: np.ndarray) -> np.ndarray:
    """float32 values as ``Precision.HIGH`` multiplies them by 1.0: the
    bfloat16 high part plus the bfloat16 of the remainder."""
    x = np.asarray(v, np.float64).astype(np.float32)
    hi = _bf16(x)
    return (hi + _bf16(x - hi)).astype(np.float64)


def _precision(name: str):
    """(input rounding, sum dtype) of a precision."""
    if name == "float64":
        return (lambda v: _round(v, np.float64)), np.float64
    if name == "bfloat16":
        import ml_dtypes

        return (lambda v: _round(v, ml_dtypes.bfloat16)), ml_dtypes.bfloat16
    if name == "high":
        return _three_pass, np.float32
    raise ValueError(f"unknown precision {name!r}")


@dataclass
class Expanded:
    """A profile's tree with its structure applied: ``where[local id]`` is
    the node that takes the context's values, ``routes[placeholder]`` the
    leaves and weights a placeholder's values go to."""
    parent: np.ndarray
    kind: np.ndarray
    names: list[str]
    where: np.ndarray
    routes: dict[int, tuple[np.ndarray, np.ndarray]]

    def __len__(self) -> int:
        return int(self.parent.size)


def expand(tree, structure) -> Expanded:
    """``tree`` with the scopes and placeholders of ``structure`` (a table
    op name -> routes) inserted; nodes may repeat, unification merges
    them."""
    if structure is None:
        return Expanded(tree.parent, tree.kind, tree.names,
                        np.arange(len(tree)), {})
    parent, kind, names = [-1], [int(tree.kind[0])], [tree.names[0]]

    def add(up: int, k: int, name: str) -> int:
        parent.append(up)
        kind.append(k)
        names.append(name)
        return len(parent) - 1

    where = np.zeros(len(tree), np.int64)
    routes = {}
    tp, tk = tree.parent.tolist(), tree.kind.tolist()
    for i in range(1, len(tree)):
        up, k, name = int(where[tp[i]]), tk[i], tree.names[i]
        entries = structure.get(name) if k == KIND_OP else None
        if not entries:
            where[i] = add(up, k, name)
            continue
        leaves = []
        for e in entries:
            node = up
            for sk, sn in e["path"]:
                node = add(node, int(sk), str(sn))
            leaves.append(add(node, KIND_OP, name))
        if len(leaves) == 1:
            where[i] = leaves[0]
        else:
            where[i] = add(up, KIND_ROUTE, f"{name}@superposition")
            routes[int(where[i])] = (
                np.asarray(leaves, np.int64),
                np.asarray([e["weight"] for e in entries], np.float64))
    return Expanded(np.asarray(parent, np.int64), np.asarray(kind, np.uint8),
                    names, where, routes)


def split_routes(ctx, mid, val, routes):
    """Values measured on a placeholder go to its route leaves, each
    ``v * w / sum(w)``; the others stay where they are."""
    if not routes:
        return ctx, mid, val
    at = np.isin(ctx, np.fromiter(routes, np.int64))
    c, m, v = [ctx[~at]], [mid[~at]], [val[~at]]
    for i in np.flatnonzero(at):
        leaves, w = routes[int(ctx[i])]
        c.append(leaves)
        m.append(np.full(leaves.size, mid[i]))
        v.append(val[i] * w / w.sum())
    return np.concatenate(c), np.concatenate(m), np.concatenate(v)


def unify(trees) -> tuple[np.ndarray, np.ndarray, list[str], list[np.ndarray]]:
    """Unified tree in canonical preorder, and each tree's local -> preorder
    id map."""
    ids: dict[tuple[int, int, str], int] = {}
    parent, kind, names = [-1], [0], ["<root>"]
    local_maps, seen = [], {}
    for t in trees:
        if id(t) in seen:          # the same tree object maps the same way
            local_maps.append(seen[id(t)])
            continue
        m = np.zeros(len(t), np.int64)
        tp, tk = t.parent.tolist(), t.kind.tolist()
        for i in range(1, len(t)):
            key = (int(m[tp[i]]), tk[i], t.names[i])
            u = ids.get(key)
            if u is None:
                u = ids[key] = len(parent)
                parent.append(key[0])
                kind.append(key[1])
                names.append(key[2])
            m[i] = u
        seen[id(t)] = m
        local_maps.append(m)
    n = len(parent)
    kids: list[list[int]] = [[] for _ in range(n)]
    for c in range(1, n):
        kids[parent[c]].append(c)
    pos = np.empty(n, np.int64)
    order = []
    stack = [0]
    while stack:
        node = stack.pop()
        pos[node] = len(order)
        order.append(node)
        stack.extend(sorted(kids[node], key=lambda c: (kind[c], names[c]),
                            reverse=True))
    order = np.asarray(order, np.int64)
    pre_parent = np.where(order == 0, -1,
                          pos[np.maximum(np.asarray(parent)[order], 0)])
    return (pre_parent, np.asarray(kind, np.uint8)[order],
            [names[o] for o in order], [pos[m] for m in local_maps])


def _combine(c, m, v, dtype):
    key = (c << 16) | m
    uk, inv = np.unique(key, return_inverse=True)
    s = _round(np.bincount(inv, weights=v, minlength=uk.size), dtype)
    keep = s != 0.0
    return uk[keep] >> 16, uk[keep] & 0xFFFF, s[keep]


def _profile_plane(c, m, v, parent, depth, inputs, dtype):
    """Exclusive values, then inclusive ones by adding each level's sums to
    the level above, deepest first."""
    ec, em, ev = _combine(c, m, inputs(v), dtype)
    out_c, out_m, out_v = [ec], [em], [ev]
    cc, cm, cv = ec, em, ev
    for d in range(int(depth[cc].max(initial=0)), -1, -1):
        at = depth[cc] == d
        ic, im, iv = _combine(cc[at], cm[at], cv[at], dtype)
        out_c.append(ic)
        out_m.append(im | INCLUSIVE_BIT)
        out_v.append(iv)
        up = parent[ic] >= 0
        cc = np.concatenate([cc[~at], parent[ic[up]]])
        cm = np.concatenate([cm[~at], im[up]])
        cv = np.concatenate([cv[~at], iv[up]])
    return np.concatenate(out_c), np.concatenate(out_m), np.concatenate(out_v)


def _stats(keys: np.ndarray, vals: np.ndarray, dtype) -> dict[str, np.ndarray]:
    sk = keys & ((1 << 40) - 1)          # drop the profile: ctx << 16 | mid
    uk, inv = np.unique(sk, return_inverse=True)
    cnt = np.bincount(inv, minlength=uk.size).astype(np.float64)
    s = np.bincount(inv, weights=vals, minlength=uk.size)
    dev = vals - (s / cnt)[inv]          # population std, two passes
    std = np.sqrt(np.bincount(inv, weights=dev * dev, minlength=uk.size) / cnt)
    vmin = np.full(uk.size, np.inf)
    vmax = np.full(uk.size, -np.inf)
    np.minimum.at(vmin, inv, vals)
    np.maximum.at(vmax, inv, vals)
    return {"key": uk, "count": cnt, "sum": _round(s, dtype),
            "mean": _round(s / cnt, dtype), "min": vmin, "max": vmax,
            "std": _round(std, dtype)}


def build(fleet, precision: str = "float64") -> Reference:
    inputs, dtype = _precision(precision)
    profiles = fleet.profiles
    expanded, trees = {}, []    # one expansion per (tree, structure) pair
    for prof in profiles:
        key = (id(prof.tree), id(prof.structure))
        if key not in expanded:
            expanded[key] = expand(prof.tree, prof.structure and
                                   prof.structure.ops)
        trees.append(expanded[key])
    parent, kind, names, maps = unify(trees)
    depth = np.zeros(parent.size, np.int64)
    for i in range(1, parent.size):       # parents precede children
        depth[i] = depth[parent[i]] + 1
    n_metrics = fleet.shape.n_metrics
    scale = np.zeros((len(profiles), n_metrics))
    keys, vals = [], []
    for p, prof in enumerate(profiles):
        t = trees[p]
        c, m, v = split_routes(t.where[prof.ctx], prof.mid, prof.val, t.routes)
        c, m, v = _profile_plane(maps[p][c], m, v, parent, depth, inputs,
                                 dtype)
        keys.append(_pack(p, c, m))
        vals.append(v)
        excl = m < INCLUSIVE_BIT
        np.add.at(scale[p], m[excl], np.abs(v[excl]))
    keys, vals = np.concatenate(keys), np.concatenate(vals)
    order = np.argsort(keys)
    keys, vals = keys[order], vals[order]
    return Reference(parent, kind, names, keys, vals, scale,
                     _stats(keys, vals, dtype), len(profiles))


# -- the comparison ----------------------------------------------------------

def _scales(ref: Reference, keys: np.ndarray) -> np.ndarray:
    """Per key: the profile's sum of |exclusive| of that metric, else the
    largest over profiles, else 1."""
    p = np.minimum(keys >> 40, ref.n_profiles - 1)
    m = np.minimum(keys & (INCLUSIVE_BIT - 1), ref.scale.shape[1] - 1)
    own = ref.scale[p, m]
    widest = ref.scale.max(axis=0)[m]
    return np.where(own > 0, own, np.where(widest > 0, widest, 1.0))


def _align(ref_keys, ref_vals, keys, vals):
    """Both sides' values over the union of their keys (0 where absent)."""
    allk = np.union1d(ref_keys, keys)
    rv = np.zeros(allk.size)
    pv = np.zeros(allk.size)
    rv[np.searchsorted(allk, ref_keys)] = ref_vals
    np.add.at(pv, np.searchsorted(allk, keys), vals)
    return allk, rv, pv


def _keyed_gap(ref: Reference, keys, vals):
    """Widest |program - reference| over the union of keys, in u of the
    profile's metric total, with the keys found on one side only."""
    allk, rv, pv = _align(ref.keys, ref.vals, keys, vals)
    gap = np.abs(pv - rv) / _scales(ref, allk) / U
    in_ref = np.isin(allk, ref.keys, assume_unique=True)
    in_db = np.isin(allk, keys)
    return float(gap.max(initial=0.0)), allk[in_ref & ~in_db], allk[in_db & ~in_ref]


def _exclusive_gap(ref: Reference, keys, vals) -> float:
    """Widest |program - reference| / |reference| over the exclusive values
    (a key on one side only reads 1), in u.  Exclusive values are sums of
    a few raw values with no cancellation, so this is the precision of
    the combine itself, value by value."""
    rx = (ref.keys & 0xFFFF) < INCLUSIVE_BIT
    px = (keys & 0xFFFF) < INCLUSIVE_BIT
    _, rv, pv = _align(ref.keys[rx], ref.vals[rx], keys[px], vals[px])
    size = np.where(rv != 0, np.abs(rv), np.abs(pv))
    gap = np.abs(pv - rv) / np.where(size > 0, size, 1.0) / U
    return float(gap.max(initial=0.0))


def _order_errors(ctx, mid, prof=None) -> int:
    """Adjacent entries not strictly increasing in (ctx, mid[, prof])."""
    key = (np.asarray(ctx, np.int64) << 16) | np.asarray(mid, np.int64)
    if prof is not None:
        key = (key << 20) | np.asarray(prof, np.int64)
    return int(np.count_nonzero(np.diff(key) <= 0))


def compare(ref: Reference, db) -> dict[str, float | None]:
    """The numbers of :data:`NUMBERS` except ``repeat_mismatch``."""
    n = min(ref.parent.size, db.parent.size)
    tree = abs(ref.parent.size - db.parent.size) + int(np.count_nonzero(
        (ref.parent[:n] != db.parent[:n]) | (ref.kind[:n] != db.kind[:n])
        | (np.asarray(ref.names[:n], object) != np.asarray(db.names[:n], object))))

    order = 0
    keys, vals = [], []
    for p, (c, m, v) in enumerate(db.planes):
        order += _order_errors(c, m)
        keys.append(_pack(p, c, m))
        vals.append(v)
    keys = np.concatenate(keys) if keys else np.empty(0, np.int64)
    vals = np.concatenate(vals) if vals else np.empty(0)
    pms_gap, lost, stray = _keyed_gap(ref, keys, vals)
    excl_gap = _exclusive_gap(ref, keys, vals)

    cms_gap = None
    if db.cms is not None:
        c, m, p, v = db.cms
        order += _order_errors(c, m, p)
        cms_gap = _keyed_gap(ref, _pack(p, c, m), v)[0]

    count_excess, stats_gap = _stats_gaps(ref, db.stats, lost, stray)
    return {"tree_mismatch": float(tree), "order_errors": float(order),
            "count_excess": count_excess, "pms_gap_u": pms_gap,
            "excl_gap_u": excl_gap, "stats_gap_u": stats_gap,
            "cms_gap_u": cms_gap}


def _stats_gaps(ref: Reference, st: dict, lost, stray) -> tuple[float, float]:
    """Counts, which may differ from the reference's by exactly the values
    that are on one side only, and the widest gap of sum (over the union of
    keys, in u of the count times the metric's largest total) and of mean,
    min, max and std (where the counts agree, in u of that total)."""
    fields = ("count", "sum", "mean", "min", "max", "std")
    skey = (np.asarray(st.get("ctx", []), np.int64) << 16) | np.asarray(
        st.get("mid", []), np.int64)
    o = np.argsort(skey, kind="stable")
    skey = skey[o]
    ps = {f: np.asarray(st.get(f, np.zeros(o.size)), np.float64)[o]
          for f in fields}
    rs = ref.stats
    allk = np.union1d(rs["key"], skey)
    ir, ip = np.searchsorted(allk, rs["key"]), np.searchsorted(allk, skey)
    col = {}
    for side, idx, src in (("r", ir, rs), ("p", ip, ps)):
        for f in ("count", "sum"):
            col[side + f] = np.zeros(allk.size)
            np.add.at(col[side + f], idx, src[f])
    drift = np.zeros(allk.size)
    low = (1 << 40) - 1
    np.add.at(drift, np.searchsorted(allk, lost & low), 1.0)
    np.add.at(drift, np.searchsorted(allk, stray & low), -1.0)
    duplicates = int(np.count_nonzero(np.diff(skey) == 0))
    count_excess = float(np.abs(col["rcount"] - col["pcount"] - drift).sum()
                         + duplicates)

    wide = ref.scale.max(axis=0)
    tm = wide[np.minimum(allk & (INCLUSIVE_BIT - 1), wide.size - 1)]
    tm = np.where(tm > 0, tm, 1.0)
    n = np.maximum(np.maximum(col["rcount"], col["pcount"]), 1.0)
    gaps = [np.abs(col["psum"] - col["rsum"]) / (n * tm)]
    common, jr, jp = np.intersect1d(rs["key"], skey, return_indices=True)
    full = rs["count"][jr] == ps["count"][jp]
    tc = tm[np.searchsorted(allk, common)]
    for f in ("mean", "min", "max", "std"):
        gaps.append((np.abs(ps[f][jp] - rs[f][jr]) / tc)[full])
    return count_excess, max(float(g.max(initial=0.0)) for g in gaps) / U


def control_database(ref_lo: Reference):
    """The reference computed in a narrower precision, laid out as a
    database: what the control puts in the program's place."""
    from bench.dbread import Database

    planes = []
    p_of = ref_lo.keys >> 40
    for p in range(ref_lo.n_profiles):
        k = ref_lo.keys[p_of == p]
        planes.append(((k >> 16) & ((1 << 24) - 1), k & 0xFFFF,
                       ref_lo.vals[p_of == p]))
    c = (ref_lo.keys >> 16) & ((1 << 24) - 1)
    m = ref_lo.keys & 0xFFFF
    p = ref_lo.keys >> 40
    o = np.lexsort((p, m, c))
    st = ref_lo.stats
    stats = {"ctx": st["key"] >> 16, "mid": st["key"] & 0xFFFF,
             **{f: st[f] for f in ("count", "sum", "mean", "min", "max", "std")}}
    return Database(ref_lo.parent, ref_lo.kind, ref_lo.names, planes, stats,
                    (c[o], m[o], p[o], ref_lo.vals[o]))


def verdict(numbers: dict, limits: dict) -> bool:
    """Every number present and within its limit."""
    return all(numbers.get(k) is not None and math.isfinite(numbers[k])
               and numbers[k] <= limits[k] for k in limits)
