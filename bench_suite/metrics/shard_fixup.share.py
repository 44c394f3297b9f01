"""Share of the sharded search's certificate checks that took the exact
fixup over the window: ``cert_fixups / cert_checks`` of the system's
counters (``raft_tpu_certificate_fixups_total`` over
``raft_tpu_certificate_checks_total`` at the site
``distance.knn_fused_sharded``). Each shard checks every query row of a
batch, so this is the share of (query row, shard) pairs whose shard's
candidates were not certified exact. Nothing where the program counted
no checks."""


def read(run):
    checks = run.stats_delta.get("cert_checks")
    if not checks:
        return None
    return run.stats_delta.get("cert_fixups", 0) / checks
