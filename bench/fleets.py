"""Profile fleets from a configuration file and a seed.

A fleet is the set of measurement profiles one analysis job aggregates:
per-rank CPU profiles and per-stream GPU profiles shaped like a row of the
paper's Table 1 or Table 2 (arXiv:2108.04002).  The shape is that of the
repository's ``benchmarks/workloads.generate``, drawn in bulk:

* one application tree shared by every profile: root -> 3 phases -> 24
  modules -> ops (until 60% of ``n_ctx``) -> lines, each op under a uniform
  module and each line under a uniform op;
* with ``n_private``, each profile adds ``worker`` (phase) -> ``rank<p>``
  (module) -> ``n_private`` private lines, all of them live;
* context density: ``int(n_ctx * ctx_density)`` shared contexts drawn
  without replacement are live in each profile;
* metric density: even profiles draw from the CPU metrics, odd ones from
  the GPU metrics when there are any; each live context carries
  ``k = int(|pool| * min(met_density * n_metrics / |pool|, 1))`` (at least
  1) distinct metrics drawn uniformly from its pool;
* values are exponential costs (mean 1).

The profiles are written in the ``.rprf`` format that ``repro.launch.analyze``
reads (magic ``RPRF``, version 1, a JSON block, the tree arrays, an empty
trace and the sparse metrics), by this module alone.  :class:`Fleet` keeps
every profile's tree and triplets in memory for the plain reference.
"""
from __future__ import annotations

import json
import math
import os
import struct
from dataclasses import dataclass

import numpy as np

KIND_ROOT, KIND_PHASE, KIND_MODULE, KIND_OP, KIND_LINE = 0, 1, 2, 4, 5
N_PHASES, N_MODULES = 3, 24
PHASES = ("main", "solve", "comm")

SHAPE_KEYS = ("n_profiles", "n_ctx", "n_cpu_metrics", "n_gpu_metrics",
              "ctx_density", "met_density", "n_private")


@dataclass(frozen=True)
class FleetShape:
    name: str
    n_profiles: int
    n_ctx: int
    n_cpu_metrics: int
    n_gpu_metrics: int
    ctx_density: float
    met_density: float
    n_private: int = 0

    @classmethod
    def from_config(cls, cfg: dict) -> "FleetShape":
        return cls(cfg["name"], *(cfg[k] for k in SHAPE_KEYS))

    @property
    def n_metrics(self) -> int:
        return self.n_cpu_metrics + self.n_gpu_metrics

    def is_gpu(self, p: int) -> bool:
        return p % 2 == 1 and self.n_gpu_metrics > 0

    def metrics_per_context(self, pool: int) -> int:
        return max(int(pool * min(self.met_density * self.n_metrics / pool,
                                  1.0)), 1)


@dataclass
class Tree:
    """A context tree as arrays: ``parent`` (-1 at the root), ``kind`` and
    one name per node (every generated node has a name of its own)."""
    parent: np.ndarray
    kind: np.ndarray
    names: list[str]

    def __len__(self) -> int:
        return int(self.parent.size)


@dataclass
class Profile:
    tree: Tree
    ctx: np.ndarray    # (x,) int64 local context ids, sorted with mid
    mid: np.ndarray    # (x,) int64 metric ids
    val: np.ndarray    # (x,) float64, non-zero
    identity: dict


@dataclass
class Fleet:
    shape: FleetShape
    profiles: list[Profile]
    paths: list[str]

    @property
    def widths(self) -> list[int]:
        """Distinct metrics per profile: the columns its propagation takes."""
        return [int(np.unique(p.mid).size) for p in self.profiles]


def app_tree(n_ctx: int, rng: np.random.Generator) -> Tree:
    """Application-shaped tree: phases -> modules -> ops -> lines."""
    n_fixed = 1 + N_PHASES + N_MODULES
    n_ops = max(math.ceil(n_ctx * 0.6) - n_fixed, 0)
    n_lines = max(n_ctx - n_fixed - n_ops, 0)
    if n_lines and not n_ops:
        raise ValueError(f"n_ctx={n_ctx} leaves no op to hang lines under")
    mod_ids = 1 + N_PHASES + np.arange(N_MODULES)
    op_ids = n_fixed + np.arange(n_ops)
    parent = np.concatenate([
        [-1], np.zeros(N_PHASES, np.int64),
        1 + np.arange(N_MODULES) % N_PHASES,
        mod_ids[rng.integers(0, N_MODULES, n_ops)],
        op_ids[rng.integers(0, max(n_ops, 1), n_lines)],
    ]).astype(np.int64)
    kind = np.concatenate([
        [KIND_ROOT], np.full(N_PHASES, KIND_PHASE),
        np.full(N_MODULES, KIND_MODULE), np.full(n_ops, KIND_OP),
        np.full(n_lines, KIND_LINE)]).astype(np.uint8)
    first_line = n_fixed + n_ops
    names = (["<root>", *PHASES] + [f"mod{i}" for i in range(N_MODULES)]
             + [f"fn{i}" for i in range(n_ops)]
             + [f"line{first_line + i}" for i in range(n_lines)])
    return Tree(parent, kind, names)


def _profile_tree(shared: Tree, p: int, n_private: int) -> tuple[Tree, np.ndarray]:
    """The shared tree plus profile ``p``'s private call paths; returns the
    tree and the private line ids."""
    if not n_private:
        return shared, np.empty(0, np.int64)
    s = len(shared)
    parent = np.concatenate([shared.parent, [0, s],
                             np.full(n_private, s + 1)]).astype(np.int64)
    kind = np.concatenate([shared.kind, [KIND_PHASE, KIND_MODULE],
                           np.full(n_private, KIND_LINE)]).astype(np.uint8)
    names = shared.names + ["worker", f"rank{p}"] + [
        f"p{p}.{i}" for i in range(n_private)]
    return Tree(parent, kind, names), s + 2 + np.arange(n_private)


def _draw_metrics(rng, n_live: int, pool: np.ndarray, k: int) -> np.ndarray:
    """``k`` distinct metrics per live context, uniform over ``pool``:
    shape (n_live, k)."""
    if k >= pool.size:
        return np.broadcast_to(pool, (n_live, pool.size))
    if k == 1:
        return pool[rng.integers(0, pool.size, n_live)][:, None]
    return pool[np.argsort(rng.random((n_live, pool.size)), axis=1)[:, :k]]


def make_fleet(shape: FleetShape, seed: int) -> list[Profile]:
    """Every profile of ``shape`` drawn from ``seed``, in memory."""
    rng = np.random.default_rng(seed)
    shared = app_tree(shape.n_ctx, rng)
    n_live = max(int(len(shared) * shape.ctx_density), 1)
    profiles = []
    for p in range(shape.n_profiles):
        tree, private = _profile_tree(shared, p, shape.n_private)
        gpu = shape.is_gpu(p)
        pool = (np.arange(shape.n_cpu_metrics, shape.n_metrics) if gpu
                else np.arange(shape.n_cpu_metrics))
        k = shape.metrics_per_context(pool.size)
        live = np.concatenate([
            rng.choice(len(shared), size=n_live, replace=False), private])
        mids = _draw_metrics(rng, live.size, pool, k)
        ctx = np.repeat(live, mids.shape[1])
        mid = mids.reshape(-1).astype(np.int64)
        val = rng.exponential(1.0, ctx.size)
        order = np.lexsort((mid, ctx))
        ctx, mid, val = ctx[order].astype(np.int64), mid[order], val[order]
        keep = val != 0.0
        profiles.append(Profile(
            tree, ctx[keep], mid[keep], val[keep],
            {"rank": p // 2, "stream": p % 2, "kind": "gpu" if gpu else "cpu"}))
    return profiles


# -- the .rprf writer --------------------------------------------------------

_CODES = {np.dtype(np.uint8): b"u8  ", np.dtype(np.uint16): b"u16 ",
          np.dtype(np.uint32): b"u32 ", np.dtype(np.uint64): b"u64 ",
          np.dtype(np.int64): b"i64 ", np.dtype(np.float64): b"f64 "}


def _array(a: np.ndarray) -> bytes:
    a = np.ascontiguousarray(a)
    return (_CODES[a.dtype] + struct.pack("<B", a.ndim)
            + struct.pack(f"<{a.ndim}Q", *a.shape) + a.tobytes())


def _json(obj) -> bytes:
    data = json.dumps(obj, separators=(",", ":")).encode("utf-8")
    return struct.pack("<I", len(data)) + data


def encode_profile(prof: Profile, app: str, n_metrics: int) -> bytes:
    t = prof.tree
    names = np.frombuffer("\x00".join(t.names).encode("utf-8"), np.uint8)
    ctx, first = np.unique(prof.ctx, return_index=True)
    start = np.append(first, prof.ctx.size).astype(np.uint64)
    return b"".join([
        b"RPRF", struct.pack("<I", 1),
        _json({"environment": {"app": app, "n_metrics": n_metrics},
               "identity": prof.identity, "file_paths": []}),
        _array(t.parent.astype(np.int64)), _array(t.kind.astype(np.uint8)),
        _array(np.arange(len(t), dtype=np.uint32)), _array(names),
        _array(np.empty(0, np.float64)), _array(np.empty(0, np.uint32)),
        _array(ctx.astype(np.uint32)), _array(start),
        _array(prof.mid.astype(np.uint16)), _array(prof.val.astype(np.float64)),
    ])


def write_fleet(shape: FleetShape, seed: int, out_dir: str) -> Fleet:
    """Draw the fleet and write one ``.rprf`` per profile under ``out_dir``."""
    os.makedirs(out_dir, exist_ok=True)
    profiles = make_fleet(shape, seed)
    paths = []
    for p, prof in enumerate(profiles):
        path = os.path.join(out_dir, f"{shape.name}.{p:04d}.rprf")
        with open(path, "wb") as f:
            f.write(encode_profile(prof, shape.name, shape.n_metrics))
        paths.append(path)
    return Fleet(shape, profiles, paths)
