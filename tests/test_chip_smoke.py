"""chip_smoke.py's phases at a tiny size on the CPU (interpret-mode
kernels) — the rehearsal that catches wrong paths and arguments before
a chip call. The script itself refuses to run without a TPU."""

import subprocess
import sys

import numpy as np
import pytest

import chip_smoke

TINY = chip_smoke.Config(n_rows=8192, dim=128, n_clusters=4,
                         n_queries=64, k=16, n_check=16, n_lists=16,
                         n_probes=4, n_requests=6, max_request=7,
                         sharded_rows=4096)


@pytest.fixture()
def list_major(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_IVF_FINE_SCAN", "list")


def test_exact_and_served_phases_pass_tiny(res, list_major):
    X, centers, index, exact = chip_smoke.exact_phase(res, TINY,
                                                      require_tpu=False)
    assert exact["worst_rel_err"] <= TINY.rtol
    served = chip_smoke.serve_phase(res, TINY, X, centers, index)
    assert served["recall"] >= TINY.recall_floor


def test_sharded_phase_passes_on_virtual_devices(res):
    out = chip_smoke.sharded_phase(res, TINY)
    assert out["worst_rel_err"] <= TINY.rtol


def test_compare_knn_accepts_only_ties():
    X = np.array([[0.0], [1.0], [-1.0], [3.0]], np.float32)
    Q = np.zeros((1, 1), np.float32)
    d_ref = np.array([[0.0, 1.0]])
    # id 2 for id 1: the same distance (a tie at the k-th) — accepted
    chip_smoke.compare_knn(d_ref, np.array([[0, 2]]), d_ref,
                           np.array([[0, 1]]), X, Q, 1e-4)
    # id 3 is not a tie — rejected
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.compare_knn(d_ref, np.array([[0, 3]]), d_ref,
                               np.array([[0, 1]]), X, Q, 1e-4)


def test_device_phase_refuses_the_cpu():
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.device_phase(1)


def test_script_fails_without_a_tpu_and_prints_no_result(tmp_path):
    r = subprocess.run([sys.executable, chip_smoke.__file__],
                       capture_output=True, text=True, timeout=300,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                            "HOME": str(tmp_path),
                            "JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr
