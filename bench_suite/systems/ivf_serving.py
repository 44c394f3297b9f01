"""IVF-Flat behind ``ServingEngine(algorithm="ivf_flat")``, with the
engine's default bucket ladder, flush interval and telemetry."""

from __future__ import annotations

import time

import numpy as np


class System:
    """Asynchronous: ``submit`` returns the engine's future."""

    def __init__(self, cfg: dict, base, pool, max_rows: int):
        import raft_tpu
        from raft_tpu.serving.engine import ServingEngine

        eng = cfg["engine"]
        t0 = time.perf_counter()
        self.res = raft_tpu.DeviceResources(seed=0)
        self.engine = ServingEngine(base, k=int(cfg["k"]), res=self.res,
                                    algorithm="ivf_flat",
                                    n_lists=int(eng["n_lists"]),
                                    n_probes=int(eng["n_probes"]))
        t1 = time.perf_counter()
        self.engine.start()
        #: set-up seconds by step, printed by the harness
        self.setup_split = {"index_build": t1 - t0,
                            "engine_start": time.perf_counter() - t1}
        pool = np.asarray(pool)
        self.pool = np.concatenate([pool, pool[:max_rows]])

    def submit(self, start: int, rows: int):
        return self.engine.submit(self.pool[start:start + rows])

    def wait(self, handle, timeout: float):
        return handle.result(timeout)

    def stats(self) -> dict:
        s = self.engine.stats()
        return {key: s[key] for key in ("batches", "padded_rows",
                                        "compile_misses") if key in s}

    def close(self) -> None:
        self.engine.stop()
        self.engine = None
