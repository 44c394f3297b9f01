"""Database-sharded exact k-NN as a batch caller runs it over a host's
chips: ``distance.prepare_knn_index_sharded`` once, over the base as the
chips already hold it (rows split over the mesh), then
``distance.knn_fused_sharded`` per batch at its defaults, answers copied
to the host.

The build runs with device-to-host copies disallowed: the base stays
where it was made, and a program that would pull it to the host fails at
once instead of running out of memory later."""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

#: the site under which the sharded search records its certificates
SITE = "distance.knn_fused_sharded"


@partial(jax.jit, static_argnames=("rows",))
def _take_rows(pool, start, rows: int):
    return jax.lax.dynamic_slice_in_dim(pool, start, rows, 0)


class System:
    """Synchronous: ``submit`` runs the batch and returns its answers."""

    def __init__(self, cfg: dict, base, pool, max_rows: int):
        from raft_tpu import distance
        from raft_tpu.observability import span

        self._distance, self._span = distance, span
        self.k = int(cfg["k"])
        self.mesh = base.sharding.mesh
        self.axis = base.sharding.spec[0]
        t0 = time.perf_counter()
        with jax.transfer_guard_device_to_host("disallow"):
            self.index = distance.prepare_knn_index_sharded(
                base, mesh=self.mesh, axis=self.axis)
        jax.block_until_ready([self.index.yp_s, self.index.y_hi_s,
                               self.index.y_lo_s, self.index.yyh_s,
                               self.index.yy_s])
        #: set-up seconds by step, printed by the harness
        self.setup_split = {"index_prepare": time.perf_counter() - t0}
        # the pool with its head repeated, so a run that wraps is one
        # slice; replicated on every chip, as the search reads it
        self.pool = jnp.concatenate([pool, pool[:max_rows]])

    def submit(self, start: int, rows: int):
        q = _take_rows(self.pool, np.int32(start), rows)
        d, i = self._distance.knn_fused_sharded(
            q, self.index, k=self.k, mesh=self.mesh, axis=self.axis)
        with self._span("bench.answers_copy"):
            return np.asarray(d), np.asarray(i)

    def wait(self, handle, timeout: float):
        return handle

    def stats(self) -> dict:
        """The sharded search's certificate counters (query rows checked
        on each shard, and those that took the exact fixup), and the
        collectives traced into its programs (counted when a program is
        traced, so a window that compiles nothing adds none)."""
        from raft_tpu.observability import get_registry, quality
        from raft_tpu.observability.hooks import COMMS_CALLS

        quality.drain()
        out = {"cert_checks": 0, "cert_fixups": 0, "collectives_traced": 0}
        for m in get_registry().collect():
            if m.name == COMMS_CALLS:
                out["collectives_traced"] += int(m.value)
            elif m.labels.get("site") != SITE:
                continue
            elif m.name == quality.CERT_CHECKS:
                out["cert_checks"] = int(m.value)
            elif m.name == quality.CERT_FIXUPS:
                out["cert_fixups"] = int(m.value)
        return out

    def close(self) -> None:
        self.index = self.pool = None
