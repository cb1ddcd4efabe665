"""Bring-up smoke: the graph path on one TPU chip, checked against numpy.

    python chip_smoke.py [--scale 21] [--tile-scale 14] [--seed 0]

Three phases run through the public ``repro.Graph`` entry points, each
checked against a plain numpy reference defined in this file:

  (a) device-resident: an RMAT graph (Graph500 initiator, edge factor 16,
      symmetrized) at ``--scale``; an 8-root batched BFS under
      ``backend='scan'`` and ``'compact'``, then ``pagerank()``;
  (b) ``residency='host'`` on the same graph: a one-root BFS, bitwise
      equal to phase (a), with no edge bytes on the device;
  (c) the compiled Pallas tile kernels: RMAT at ``--tile-scale`` under
      ``backend='blocked'`` and ``'blocked_compact'``, compared with
      ``'scan'``.

Earlier lines report per-phase wall time (compilation included),
``memory_report()`` and the device's ``peak_bytes_in_use``.  The last line
is ``{"ok": true, "device": {...}}``.  Without a TPU, or when any check
fails, the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# The target is RMAT scale 22 (about 128M directed edges, 3.1 GB of chunk
# stores and CSR).  On a one-chip v5e host that run took about 1,170 s, too
# close to a 1,200 s budget, so the default is cut one step.
TARGET_SCALE = 22
DEFAULT_SCALE = 21
UNREACHED = np.iinfo(np.int32).max
DAMPING = 0.85
PR_TOL = 1e-3
N_ROOTS = 8


# --------------------------------------------------------------------------
# numpy references (independent of repro)
# --------------------------------------------------------------------------
def bfs_reference(indptr: np.ndarray, indices: np.ndarray,
                  root: int) -> np.ndarray:
    """Level-synchronous BFS over CSR out-edges: int32 hop counts,
    ``UNREACHED`` where the root never arrives."""
    n = indptr.shape[0] - 1
    dist = np.full(n, UNREACHED, np.int32)
    dist[root] = 0
    frontier = np.array([root], np.int64)
    level = 0
    while frontier.size:
        level += 1
        starts, counts = indptr[frontier], np.diff(indptr)[frontier]
        offsets = np.repeat(starts - np.cumsum(counts) + counts, counts)
        nbrs = indices[offsets + np.arange(counts.sum())]
        seen = np.zeros(n, bool)
        seen[nbrs] = True
        frontier = np.flatnonzero(seen & (dist == UNREACHED))
        dist[frontier] = level
    return dist


def pagerank_residual(indptr: np.ndarray, indices: np.ndarray,
                      rank: np.ndarray) -> float:
    """``||T(R) - R||_1`` for ``T(R) = (1-c)/n + c * A^T D^-1 R`` (dangling
    vertices send nothing).  T is a c-contraction in L1, so the distance to
    the exact fixed point is at most this residual over ``1 - c``."""
    n = indptr.shape[0] - 1
    deg = np.diff(indptr)
    r = rank.astype(np.float64)
    contrib = np.where(deg > 0, r / np.maximum(deg, 1), 0.0)
    acc = np.bincount(indices, weights=np.repeat(contrib, deg), minlength=n)
    return float(np.abs((1.0 - DAMPING) / n + DAMPING * acc - r).sum())


# Delta-push PageRank stops once every pending residual p is below tol/n,
# and keeps T(R) - R = c * A^T D^-1 p, so the residual is at most c * tol.
# The slack covers float32 accumulation of ranks that sum to about 1.
PR_RESIDUAL_BOUND = DAMPING * PR_TOL + 1e-5


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------
def _peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _pick_roots(host, rng) -> np.ndarray:
    """Graph500-style search keys: distinct vertices with an edge."""
    has_edge = np.flatnonzero(np.diff(host.indptr) > 0)
    return np.sort(rng.choice(has_edge, N_ROOTS, replace=False)).astype(np.int32)


def _check_bfs(name: str, got, ref: np.ndarray) -> None:
    got = np.asarray(got)
    if got.shape != ref.shape or not np.array_equal(got, ref):
        bad = int(np.sum(got != ref)) if got.shape == ref.shape else -1
        raise AssertionError(
            f"{name}: BFS levels differ from the numpy reference "
            f"(shape {got.shape} vs {ref.shape}, {bad} vertices differ)")


def _check_pagerank(name: str, host, values, log) -> np.ndarray:
    rank = np.asarray(values)
    if rank.shape != (host.n,) or not np.all(np.isfinite(rank)):
        raise AssertionError(f"{name}: PageRank is not finite of shape "
                             f"({host.n},): {rank.shape}")
    res = pagerank_residual(host.indptr, host.indices, rank)
    log(f"{name}: residual {res!r} (bound {PR_RESIDUAL_BOUND!r}), "
        f"sum {float(rank.sum())!r}")
    if not res <= PR_RESIDUAL_BOUND:
        raise AssertionError(f"{name}: PageRank residual {res!r} exceeds "
                             f"{PR_RESIDUAL_BOUND!r}")
    return rank


def run_phases(scale: int, *, tile_scale: int = 14, seed: int = 0,
               compiled: bool = True, log=print) -> dict:
    """Run phases (a)-(c); raise ``AssertionError`` on any mismatch.

    ``compiled=True`` also requires the blocked backends' lowered program
    to hold the Pallas kernel as a TPU custom call (no interpret mode)."""
    import jax
    import jax.numpy as jnp

    import repro
    from repro.graph.generators import rmat
    from repro.kernels.spmv import blocked_spmv

    def policy(backend, **kw):
        # BFS's own default (no point-to-point arm) with the backend swapped.
        return repro.ExecutionPolicy(backend=backend, switch_fraction=None, **kw)

    out: dict = {"scale": scale, "tile_scale": tile_scale}

    # ---- (a) device-resident -------------------------------------------
    t0 = time.perf_counter()
    host = rmat(scale, edge_factor=16, seed=seed, symmetrize=True)
    t_gen = time.perf_counter() - t0
    log(f"graph: rmat scale {scale}, n={host.n}, m={host.m} directed edges, "
        f"generated in {t_gen:.1f} s")
    rng = np.random.default_rng(seed)
    roots = _pick_roots(host, rng)
    t0 = time.perf_counter()
    ref = np.stack([bfs_reference(host.indptr, host.indices, r)
                    for r in roots], axis=1)
    log(f"numpy BFS reference for roots {roots.tolist()}: "
        f"{time.perf_counter() - t0:.1f} s, max level "
        f"{int(ref[ref != UNREACHED].max())}")

    t0 = time.perf_counter()
    g = repro.Graph(host)
    dist = {}
    for backend in ("scan", "compact"):
        tb = time.perf_counter()
        res = g.bfs(roots, policy=policy(backend))
        dist[backend] = np.asarray(res.values)
        _check_bfs(f"(a) bfs {backend}", dist[backend], ref)
        log(f"(a) bfs {backend}: {int(res.supersteps)} supersteps, "
            f"{time.perf_counter() - tb:.1f} s, levels match numpy")
    tb = time.perf_counter()
    pr = g.pagerank()
    _check_pagerank("(a) pagerank", host, pr.values, log)
    log(f"(a) pagerank: {int(pr.supersteps)} supersteps, "
        f"{time.perf_counter() - tb:.1f} s")
    out["a_seconds"] = time.perf_counter() - t0
    out["a_memory"] = g.memory_report()
    log(f"(a) {out['a_seconds']:.1f} s; memory_report "
        f"{json.dumps(out['a_memory'])}; peak_bytes_in_use {_peak_bytes()}")

    # ---- (b) residency='host' on the same graph -------------------------
    t0 = time.perf_counter()
    hg = repro.Graph(host)
    host_pol = policy("scan", residency="host")
    res = hg.bfs(int(roots[0]), policy=host_pol)
    got = np.asarray(res.values)
    if not np.array_equal(got, dist["scan"][:, 0]):
        raise AssertionError("(b) host-residency BFS is not bitwise equal "
                             "to the device-resident run")
    out["b_memory"] = hg.memory_report(host_pol)
    if out["b_memory"]["device_edge_total"] != 0:
        raise AssertionError("(b) residency='host' left edge bytes on the "
                             f"device: {out['b_memory']}")
    out["b_seconds"] = time.perf_counter() - t0
    log(f"(b) host-residency bfs: bitwise equal to (a), "
        f"{out['b_seconds']:.1f} s; memory_report "
        f"{json.dumps(out['b_memory'])}; peak_bytes_in_use {_peak_bytes()}")

    # ---- (c) blocked Pallas kernels ------------------------------------
    t0 = time.perf_counter()
    th = rmat(tile_scale, edge_factor=16, seed=seed, symmetrize=True)
    tg = repro.Graph(th)
    t_roots = _pick_roots(th, rng)
    t_ref = np.stack([bfs_reference(th.indptr, th.indices, r)
                      for r in t_roots], axis=1)
    scan_pr = _check_pagerank("(c) pagerank scan", th,
                              tg.pagerank(policy=policy("scan")).values, log)
    _check_bfs("(c) bfs scan", tg.bfs(t_roots, policy=policy("scan")).values,
               t_ref)
    for backend in ("blocked", "blocked_compact"):
        vals = tg.bfs(t_roots, policy=policy(backend)).values
        _check_bfs(f"(c) bfs {backend}", vals, t_ref)
        rank = _check_pagerank(f"(c) pagerank {backend}", th,
                               tg.pagerank(policy=policy(backend)).values, log)
        diff = float(np.abs(rank.astype(np.float64) - scan_pr).sum())
        log(f"(c) {backend}: bfs levels match numpy; pagerank L1 distance "
            f"to scan {diff!r}")
        if not diff <= 2 * PR_RESIDUAL_BOUND / (1 - DAMPING):
            raise AssertionError(f"(c) pagerank {backend} is {diff!r} from "
                                 "scan in L1")
    bg = tg.device(blocked=True).out_blocked
    out["tiles"] = bg.num_tiles
    if compiled:
        x = jnp.ones((th.n, N_ROOTS), jnp.float32)
        active = jnp.ones(th.n, bool)
        for compact in (False, True):
            text = jax.jit(lambda x, a: blocked_spmv(
                bg, x, a, compact=compact)[0]).lower(x, active).as_text()
            if "tpu_custom_call" not in text:
                raise AssertionError(
                    f"blocked_spmv(compact={compact}) lowered without the "
                    "Pallas TPU kernel (interpret mode?)")
    out["c_seconds"] = time.perf_counter() - t0
    out["c_memory"] = tg.memory_report()
    log(f"(c) {bg.num_tiles} tiles of {bg.bd}x{bg.bs}: "
        f"{out['c_seconds']:.1f} s; memory_report "
        f"{json.dumps(out['c_memory'])}; peak_bytes_in_use {_peak_bytes()}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=int, default=DEFAULT_SCALE)
    ap.add_argument("--tile-scale", type=int, default=14)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from bench.compile_cache import use_compile_cache

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {jax.default_backend()!r}",
              file=sys.stderr)
        return 2
    # JAX reads JAX_COMPILATION_CACHE_DIR itself where it is set.
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or use_compile_cache()
    print(f"# compile cache: {cache}", flush=True)
    if args.scale < TARGET_SCALE:
        print(f"# scale cut: RMAT scale {TARGET_SCALE} -> {args.scale}",
              flush=True)
    dev = jax.devices()[0]
    print(f"# device: {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
          f"jax {jax.__version__}", flush=True)
    try:
        run_phases(args.scale, tile_scale=args.tile_scale, seed=args.seed,
                   log=lambda s: print(f"# {s}", flush=True))
    except Exception:  # noqa: BLE001 - any failed phase fails the smoke
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
