#!/usr/bin/env python
"""The certificate fixup's select on one TPU: the threshold block filter
(``knn_fused.fixup_select``) against the flat ``top_k`` over the whole
``[F, M]`` distance tile.

Two parts, in one process on one chip:

- ``sweep``: the select alone at the fixup tiles of the exact cells,
  ``[16, 8388608]`` k=100 (one chip of ``bigann32m-exact4``) and
  ``[16, 1048576]`` k=64 (``sift1m-exact``), flat against the filter at
  each block width B in ``--blocks`` (nb the power of two >= 2k); then
  the same inside the tile's program (f32 HIGHEST scores from the rows
  and their stored norms, the select after it), with the blocked view
  scored directly and copied from the flat tile. Results are checked
  bit for bit against the flat top_k.
- ``cells``: one chip's share of ``bigann32m-exact4`` (its first
  8,388,608 rows, drawn as the benchmark draws them; 2048-row batches
  as 4 micro-batches of 512, k=100) and a 1,000,000-row base (2048-row
  batches, k=64), each through ``knn_fused`` of this tree and, with
  ``--parent DIR``, of the tree at DIR (its ``knn_fused.py`` loaded
  beside this one's), timed in the order parent, change, change,
  parent, then one traced window each. The trace's op events are
  summed by name and operand shape, and for each whether it ran inside
  a ``conditional`` (the certificate fixup's cascade).

Each part prints a line per shape or cell; ``--out PATH`` writes all
of it (traces reduced) as JSON; the last stdout line is the summary.
``JAX_PLATFORMS=cpu`` runs a tiny rehearsal and writes nothing.

    python benchmarks/bench_fixup_select.py --parent DIR --out PATH
"""

from __future__ import annotations

import argparse
import collections
import glob
import importlib.util
import json
import os
import statistics
import sys
import tempfile
import time
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmarks._common import gate  # noqa: E402


def _timed(fn, *args, reps: int):
    """Median seconds of ``fn(*args)`` to its last output, after a warm
    call."""
    import jax

    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def _same(a, b) -> bool:
    import numpy as np

    return all(np.array_equal(np.asarray(x).view(np.int32),
                              np.asarray(y).view(np.int32))
               for x, y in zip(a, b))


def sweep(shapes, blocks, reps: int):
    import jax
    import jax.numpy as jnp

    from raft_tpu.distance.knn_fused import blocked_top_k, fixup_select

    hi = jax.lax.Precision.HIGHEST
    out = []
    for F, M, k in shapes:
        nb = 1 << (2 * k - 1).bit_length()
        y = jax.random.normal(jax.random.key(M + k), (M, 128), jnp.float32)
        yy = jnp.sum(y * y, axis=1)
        x = y[:F] + 0.5 * jax.random.normal(jax.random.key(1), (F, 128))

        def tile(x, y, yy, B=None):
            # as the fixup scores its rows: flat [F, M] or blocked
            # [M/B, F, B]
            xs = jnp.sum(x * x, 1)
            if B is None:
                s = jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                                        precision=hi)
                return jnp.maximum(xs[:, None] + yy[None] - 2.0 * s, 0.0)
            s = jnp.einsum("fd,jbd->jfb", x, y.reshape(M // B, B, 128),
                           precision=hi)
            return jnp.maximum(xs[None, :, None]
                               + yy.reshape(M // B, 1, B) - 2.0 * s, 0.0)

        def flat_select(d2):
            nt, ni = jax.lax.top_k(-d2, k)
            return -nt, ni

        def composite(x, y, yy, cand, B=None, relayout=False):
            if B is None:
                return flat_select(tile(x, y, yy))
            if relayout:     # the flat tile, copied into the blocked view
                d2b = tile(x, y, yy).reshape(F, M // B, B).transpose(
                    1, 0, 2)
            else:
                d2b = tile(x, y, yy, B)
            v, i, exact = fixup_select(d2b, cand, k, nb)
            return jax.lax.cond(exact, lambda _: (v, i),
                                lambda d: blocked_top_k(d, k), d2b)

        d2 = jax.jit(tile)(x, y, yy)
        # a failed row's candidates: its true top-k with the k-th
        # swapped for the (k+8)-th, so t lies 8 columns past the k-th
        _, ids = jax.lax.top_k(-d2, k + 8)
        cand = ids[:, :k].at[:, k - 1].set(ids[:, k + 7])
        flat = jax.jit(flat_select)
        want = flat(d2)
        row = {"F": F, "M": M, "k": k, "nb": nb,
               "flat_ms": 1e3 * _timed(flat, d2, reps=reps),
               "composite_flat_ms": 1e3 * _timed(jax.jit(composite), x, y,
                                                 yy, cand, reps=reps)}
        for B in blocks:
            if M % B or M <= nb * B:
                continue
            d2b = d2.reshape(F, M // B, B).transpose(1, 0, 2)
            f = jax.jit(partial(fixup_select, k=k, nb=nb))
            got = f(d2b, cand)
            c = jax.jit(partial(composite, B=B))
            fb = jax.jit(partial(blocked_top_k, k=k))
            row[f"B{B}"] = {
                "ms": 1e3 * _timed(f, d2b, cand, reps=reps),
                "fallback_ms": 1e3 * _timed(fb, d2b, reps=reps),
                "fallback_same": _same(fb(d2b), want),
                "composite_ms": 1e3 * _timed(c, x, y, yy, cand, reps=reps),
                "composite_relayout_ms": 1e3 * _timed(
                    jax.jit(partial(composite, B=B, relayout=True)), x, y,
                    yy, cand, reps=reps),
                "same": _same(got[:2], want), "exact": bool(got[2]),
                "composite_same": _same(c(x, y, yy, cand), want)}
            del d2b
        print(json.dumps(row), flush=True)
        out.append(row)
        del y, yy, x, d2, ids, cand
    return out


def _load_knn_fused(tree: str):
    """``knn_fused.py`` of another tree as its own module, beside this
    tree's (everything it imports is this tree's). It takes this tree's
    ``KnnIndex``, so one prepared index serves both."""
    from raft_tpu.distance import knn_fused

    path = os.path.join(tree, "raft_tpu", "distance", "knn_fused.py")
    spec = importlib.util.spec_from_file_location(
        "raft_tpu.distance._knn_fused_parent", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.KnnIndex = knn_fused.KnnIndex
    return mod


def _reduce_trace(log_dir: str) -> dict:
    """Device seconds per (op name, operand shapes), each split into the
    part inside a ``conditional`` event and the part outside, summed
    over the device planes."""
    import jax

    from bench_suite.trace import op_name

    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    data = jax.profiler.ProfileData.from_file(path)
    acc = collections.defaultdict(lambda: [0.0, 0.0, 0])
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            evs = [(int(e.start_ns), int(e.end_ns), e.name)
                   for e in line.events]
            conds = [(s, e) for s, e, n in evs
                     if op_name(n) == "conditional"]
            for s, e, n in evs:
                name = op_name(n)
                if name == "conditional":
                    continue
                sig = n.split("=", 1)[1].strip()[:150] if "=" in n else ""
                inside = any(cs <= s and e <= ce for cs, ce in conds)
                a = acc[f"{name} | {sig}"]
                a[1 if inside else 0] += (e - s) / 1e9
                a[2] += 1
    top = sorted(acc.items(), key=lambda kv: -(kv[1][0] + kv[1][1]))[:30]
    return {k: {"outside_s": round(v[0], 6), "in_cond_s": round(v[1], 6),
                "n": v[2]} for k, v in top}


def _counters(site: str) -> dict:
    from raft_tpu.observability import get_registry, quality

    quality.drain()
    out = {}
    for m in get_registry().collect():
        if m.labels.get("site") != site:
            continue
        if m.name in (quality.CERT_CHECKS, quality.CERT_FIXUPS,
                      quality.FIXUP_OVERFLOW):
            out[m.name] = int(m.value)
    return out


def cells(parent: str, n_rows: int, small_rows: int, batch: int,
          micro: int, reps: int, seed: int):
    import jax
    import jax.numpy as jnp

    from bench_suite import reference
    from raft_tpu.distance import knn_fused as change

    with open(os.path.join(ROOT, "bench_suite", "configs",
                           "bigann32m-exact4.json")) as f:
        data = dict(json.load(f)["data"], n_rows=n_rows)
    dev = jax.devices()[0]
    base, pool = reference.make_data(seed, data, [dev])
    small = base[:small_rows]
    sides = {"change": change}
    if parent:
        sides["parent"] = _load_knn_fused(parent)
    order = (["parent", "change", "change", "parent"] if parent
             else ["change"])
    out = {}
    for label, k, rows, mb in (("exact4_chip", 100, n_rows, micro),
                               ("sift1m_like", 64, small_rows, 1)):
        y = base if label == "exact4_chip" else small
        idx = change.prepare_knn_index(y)
        jax.block_until_ready(idx.yp)
        if label == "exact4_chip":
            del base, y
        step = batch // mb
        qs = [pool[i * batch:(i + 1) * batch]
              for i in range(min(reps, pool.shape[0] // batch))]

        def run(mod, q):
            return [mod.knn_fused(q[b * step:(b + 1) * step], idx, k)
                    for b in range(mb)]

        res = {"M": int(idx.yyh_k.shape[1]), "k": k, "rows": rows,
               "micro_batches": mb}
        want = None
        for name in sides:
            got = jax.block_until_ready(run(sides[name], qs[0]))
            if want is None:
                want = got
            res[f"{name}_same_as_first"] = all(
                _same(a, b) for a, b in zip(got, want))
            _counters("distance.knn_fused")
        times = collections.defaultdict(list)
        for name in order:
            mod = sides[name]
            before = _counters("distance.knn_fused")
            for q in qs:
                t0 = time.perf_counter()
                jax.block_until_ready(run(mod, q))
                times[name].append(time.perf_counter() - t0)
            after = _counters("distance.knn_fused")
            res.setdefault(f"{name}_counters", collections.Counter())
            res[f"{name}_counters"].update(
                {kk: after.get(kk, 0) - before.get(kk, 0) for kk in after})
        for name, ts in times.items():
            res[f"{name}_ms_per_batch"] = 1e3 * statistics.median(ts)
            res[f"{name}_ms_all"] = [round(1e3 * t, 3) for t in ts]
        for name in sides:
            with tempfile.TemporaryDirectory() as d:
                with jax.profiler.trace(d):
                    for q in qs[:3]:
                        jax.block_until_ready(run(sides[name], q))
                res[f"{name}_trace_3_batches"] = _reduce_trace(d)
        print(json.dumps({label: {kk: v for kk, v in res.items()
                                  if "trace" not in kk}}), flush=True)
        out[label] = res
        del idx
        jax.clear_caches()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default="",
                    help="a tree whose knn_fused.py to compare against")
    ap.add_argument("--parts", default="sweep,cells")
    ap.add_argument("--blocks", default="128,256,512,1024,2048")
    ap.add_argument("--seed", type=int, default=2718281829)
    ap.add_argument("--out", default="",
                    help="write the results here as JSON")
    args = ap.parse_args(argv)
    dry = gate()
    import jax

    if dry:
        shapes, blocks, reps = [(16, 8192, 8), (16, 4096, 4)], [128, 256], 2
        n_rows, small_rows, batch, micro = 4096, 2048, 64, 2
        from bench_suite import reference
        reference.ROW_BLOCK = 1024
    else:
        shapes = [(16, 8388608, 100), (16, 1048576, 64)]
        blocks = [int(b) for b in args.blocks.split(",")]
        reps = 8
        n_rows, small_rows, batch, micro = 8388608, 1000000, 2048, 4
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": jax.device_count()}}
    parts = args.parts.split(",")
    if "sweep" in parts:
        out["sweep"] = sweep(shapes, blocks, reps)
    if "cells" in parts:
        out["cells"] = cells(args.parent, n_rows, small_rows, batch, micro,
                             reps, args.seed)
    if args.out and not dry:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps({"ok": True, "device": out["device"],
                      "parts": parts}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
