"""A cell shrunk to what the CPU holds, for the rehearsal tests: the
same harness, traffic generator, systems, check and metric readers as
a chip run, at a few thousand rows."""

from __future__ import annotations

import copy

from bench_suite import spec

#: stand-in peaks for the CPU rehearsal (never written as a device number)
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12,
             "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}


def shrink(cell: spec.Cell, n_rows: int = 4096) -> spec.Cell:
    cell = copy.deepcopy(cell)
    cfg, traffic = cell.config, cell.traffic
    cfg["data"].update(n_rows=n_rows, n_pool=512, n_centers=32)
    if "n_lists" in cfg.get("engine", {}):
        cfg["engine"].update(n_lists=16, n_probes=4)
    rows = traffic["rows"]
    rows["max"] = min(rows["max"], 64)
    rows["min"] = min(rows["min"], rows["max"])
    traffic["warm_s"] = 0.5
    return cell
