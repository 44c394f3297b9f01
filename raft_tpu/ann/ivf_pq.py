"""IVF-PQ: the product-quantized compressed tier over the IVF slab.

(ref: neighbors/ivf_pq.cuh — the reference ecosystem's flagship
billion-vector index, migrated to cuVS as ``ivf_pq::build/search`` +
its ``refine`` step. The int8 slab (PR 9) halves database bytes and
the list-major fine scan (PR 14) kills the gather overread; product
quantization is the ~16–32× rung: serving 100M–1B vectors from one
chip's HBM means the scanned representation must shrink past what any
scalar quantizer gives.)

Index (:class:`IvfPqIndex`, built by :func:`build_ivf_pq`): the PR-8
IVF-Flat padded ragged slab UNCHANGED (coarse balanced k-means, f32
slab retained — it is the mandatory exact-rescore plane), plus the
compressed sidecar packed into the same
:class:`~raft_tpu.mutable.layout.IndexLayout` geometry:

- ``pq_dim`` subspaces of width ``d / pq_dim``; per-subspace codebooks
  of ``2^pq_bits`` codewords trained with the PR-8
  :func:`~raft_tpu.cluster.kmeans_fit` on RESIDUALS to the coarse
  centroid (the cuVS ``by_residual`` shape);
- a codes slab ``[R, pq_dim]`` (8-bit, stored biased) or
  ``[R, pq_dim/2]`` (4-bit, two codes per byte) laid out row-for-row
  with the f32 slab, plus the 4-byte reconstructed-norm sidecar
  ``‖ŷ‖²`` — the ONLY bytes the compressed scan streams;
- per-subspace quantization-error bounds recorded at build
  (generalizing the PR-9 per-group ``Eq`` argument: ``pq_eq_sub[s]``
  envelopes every encoded row's subspace residual norm, and the
  per-row/per-list roll-ups widen the completeness certificate).

Search (:func:`search_ivf_pq`): coarse probe → the PR-14 list-major
schedule (``build_list_schedule`` reused verbatim) → the
:func:`~raft_tpu.ops.pq_scan_pallas.pq_scan_list_major` ADC kernel —
per-query ``[pq_dim, 2^pq_bits]`` lookup tables computed on entry and
held VMEM-resident while code blocks stream through the 2-slot DMA
pipeline — → pooled candidates MANDATORILY exact-rescored from the
f32 slab under a PER-QUERY ADAPTIVE completeness certificate: the
kernel folds each streamed row's certified true-distance lower bound
``(max(√d2_adc − Eq_row, 0))²`` (the recorded per-row round-trip
error, streamed as a 4-byte sidecar), so the pooled rest-min is
compared against ``θ`` plus only the kernel-precision envelope — no
per-list worst-case ``Eq`` widening. Certificate failures climb a
three-rung ladder: (1) certified as-is, (2) the ``pq_widen`` rung
re-runs the ADC scan with a 2×/4× deeper candidate pool and
re-certifies, (3) the exact f32 rerun. A device failure at the
``pq_scan`` site (injected, or classified as a ``DeviceError``)
degrades to the f32/int8 query-major scan — so returned id sets NEVER
degrade below the flat scan's, whatever the compression does to the
approximate scores. A kernel the compiler refuses propagates.

``pq_mode`` picks the quantizer: ``"plain"`` trains codebooks on raw
residuals; ``"opq"`` learns an orthogonal rotation first (OPQ
alternating minimization — orthogonal Procrustes against the current
reconstruction, codebooks re-trained on the rotated residuals — ref:
Ge et al., and cuVS' codebook options); ``"opq_aniso"`` additionally
assigns codewords under a score-aware anisotropic loss (ScaNN-style:
the residual component parallel to the data point is weighted η×).
The rotation is stored as ``pq_rot`` (also on the shared
``IndexLayout``), applied to QUERIES at ADC-table build and to
RESIDUALS at encode — norms are preserved, so every certificate and
sidecar stays exactly as recorded.

``n_probes ≥ n_lists`` (or ``k`` past the probed capacity) degrades
to certified-exact search over the f32 slab exactly like IVF-Flat —
:class:`IvfPqIndex` IS an :class:`~raft_tpu.ann.ivf_flat.IvfFlatIndex`
and inherits the whole degenerate/exact/layout machinery.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.core import env
from raft_tpu.core.error import DeviceError, expects
from raft_tpu.core.resources import ensure_resources
from raft_tpu.observability import explain, instrument
from raft_tpu.observability.quality import (record_certificate,
                                            record_pq_rungs)
from raft_tpu.observability.timeline import emit_marker
from raft_tpu.resilience import fault_point
from raft_tpu.resilience.policy import record_degradation

from raft_tpu.ann.ivf_flat import (_FINE_TILE, _LIST_K_MAX,
                                   _coarse_probe, _exact_search,
                                   _fine_scan, _list_host,
                                   _pad_kernel_operands,
                                   _query_major_chunk, IvfFlatIndex,
                                   build_ivf_flat, build_list_schedule)

#: PQ schedule choices: "pq" = the list-major ADC kernel over the
#: codes slab, "flat" = the uncompressed IVF-Flat fine scan (query- or
#: list-major per its own chooser), "auto" = the resolve_pq_scan
#: cost-model crossover. Env: RAFT_TPU_IVF_PQ_SCAN.
PQ_SCANS = ("auto", "pq", "flat")

#: quantizer modes: "plain" = codebooks on raw residuals, "opq" = the
#: learned orthogonal rotation (OPQ alternating minimization),
#: "opq_aniso" = OPQ + score-aware anisotropic codeword assignment.
#: Env default: RAFT_TPU_ANN_PQ_MODE.
PQ_MODES = ("plain", "opq", "opq_aniso")

#: anisotropic assignment weight: the residual component PARALLEL to
#: the data point costs this much more than the orthogonal one
#: (ScaNN's score-aware loss, fixed-η form)
_PQ_ANISO_ETA = 4.0

#: multiplicative headroom on every recorded f32 error bound — covers
#: the f32 norm/summation rounding between the recorded bound and the
#: true (f64) round-trip error, same spirit as the PR-9 _Q8_ERR slack
_PQ_EQ_HEADROOM = 1.0 + 2.0 ** -10
#: additive headroom, scaled by the row/subspace magnitude: a row
#: whose residual is EXACTLY a codeword records an f32 error of 0
#: while the true round-trip still carries the f32 representation
#: error of the reconstruction arithmetic (~ULPs of the magnitudes
#: involved) — the relative term alone cannot cover a zero
_PQ_EQ_ABS = 2.0 ** -16


def _default_pq_dim(d: int) -> int:
    """Largest divisor of ``d`` not exceeding ``d // 4`` — the 4-byte-
    per-subspace default (~16× at 8-bit codes) that still tiles the
    feature width exactly."""
    target = max(1, d // 4)
    for cand in range(target, 0, -1):
        if d % cand == 0:
            return cand
    return 1


def pack_pq_codes(codes, pq_bits: int):
    """Host-side code packing: 8-bit codes store BIASED (code − 128)
    int8 so the full 0..255 range fits; 4-bit codes pack two per byte
    (low nibble = even subspace). Mirrors the kernel's
    ``_decode_subspaces``."""
    codes = np.asarray(codes, np.int64)
    if pq_bits == 8:
        return (codes - 128).astype(np.int8)
    expects(codes.shape[1] % 2 == 0,
            "pack_pq_codes: 4-bit packing needs an even pq_dim")
    low = codes[:, 0::2]
    high = codes[:, 1::2]
    return (low | (high << 4)).astype(np.uint8).view(np.int8)


def unpack_pq_codes(packed, pq_dim: int, pq_bits: int):
    """Inverse of :func:`pack_pq_codes` (tests / the mutable plane)."""
    packed = np.asarray(packed)
    if pq_bits == 8:
        return packed.astype(np.int64) + 128
    vu = packed.view(np.uint8).astype(np.int64)
    out = np.empty((packed.shape[0], pq_dim), np.int64)
    out[:, 0::2] = vu % 16
    out[:, 1::2] = vu // 16
    return out


class IvfPqIndex(IvfFlatIndex):
    """IVF-Flat slab + the product-quantized sidecar. Inherits every
    flat plane (degenerate-exact search, layout, schedule builder,
    sharding geometry); adds the codebooks, the packed codes slab, the
    reconstructed norms and the recorded error bounds."""

    def __init__(self, *args, pq_dim: int = 0, pq_bits: int = 8,
                 codebooks=None, codes=None, yy_pq=None,
                 pq_eq_rows=None, pq_eq_sub=None, pq_eq_list=None,
                 pq_rhat_list=None, pq_mode: str = "plain",
                 pq_rot=None, pq_eq_qlist=None,
                 pq_resid_med: float = 0.0, **kw):
        super().__init__(*args, **kw)
        self.pq_dim = int(pq_dim)            # subspace count S
        self.pq_bits = int(pq_bits)          # 4 or 8
        self.codebooks = codebooks           # [S, K, dsub] f32
        self.codes = codes                   # [R, S or S/2] int8 packed
        self.yy_pq = yy_pq                   # [R, 1] f32 ‖ŷ‖² (pads 0)
        self.pq_eq_rows = pq_eq_rows         # [R] f32 ‖y − ŷ‖ bound
        self.pq_eq_sub = pq_eq_sub           # [S] f32 subspace envelope
        self.pq_eq_list = pq_eq_list         # [L] f32 per-list max
        self.pq_rhat_list = pq_rhat_list     # [L] f32 max ‖r̂‖ per list
        self.pq_mode = str(pq_mode)          # plain | opq | opq_aniso
        self.pq_rot = pq_rot                 # [d, d] f32 or None
        self.pq_eq_qlist = pq_eq_qlist       # [L, 3] q50/q90/max sketch
        self.pq_resid_med = float(pq_resid_med)  # median ‖y − c‖
        self._pq_views = None                # lazy ADC kernel views

    @property
    def dsub(self) -> int:
        return self.d_orig // self.pq_dim

    @property
    def pq_k(self) -> int:
        return 1 << self.pq_bits

    @property
    def code_bytes(self) -> int:
        """Streamed code bytes per row."""
        return self.pq_dim if self.pq_bits == 8 else self.pq_dim // 2

    @property
    def pq_kernel_views(self):
        """(codes, ‖ŷ‖², Eq) in the layout the ADC kernel streams
        (:func:`~raft_tpu.ops.pq_scan_pallas.kernel_layout` — built
        once; the slab is immutable)."""
        if self._pq_views is None:
            from raft_tpu.ops.pq_scan_pallas import kernel_layout

            self._pq_views = kernel_layout(self.codes, self.yy_pq,
                                           self.pq_eq_rows)
        return self._pq_views

    def __repr__(self):
        return (f"IvfPqIndex(n_rows={self.n_rows}, "
                f"n_lists={self.n_lists}, d={self.d_orig}, "
                f"pq_dim={self.pq_dim}, pq_bits={self.pq_bits}, "
                f"window={self.probe_window})")

    def layout(self):
        """The shared :class:`~raft_tpu.mutable.layout.IndexLayout`
        with the PQ sidecar packed alongside the f32 slab — the codes
        ride the same padded-ragged geometry every plane shares."""
        lay = super().layout()
        lay.pq_codes = self.codes
        lay.pq_yy = self.yy_pq
        lay.pq_eq_rows = self.pq_eq_rows
        lay.pq_rot = self.pq_rot
        lay.pq_meta = {"pq_dim": self.pq_dim, "pq_bits": self.pq_bits,
                       "pq_mode": self.pq_mode,
                       "codebooks": self.codebooks}
        return lay


def _opq_rotation(res, train, S: int, dsub: int, K: int, seed: int,
                  n_iters: int = 3, train_iters: int = 3):
    """OPQ alternating minimization over the residual TRAIN sample:
    (codebooks | rotation) → encode → orthogonal Procrustes (the SVD
    of ``trainᵀ · recon`` — min ‖train·R − recon‖ over orthogonal R)
    → re-train codebooks on the re-rotated residuals, warm-started via
    ``kmeans_fit(init_centroids=…)``. Returns ``(R [d,d] f32, warm
    per-subspace codebooks)`` — the caller runs the final full-budget
    codebook train on ``train @ R`` seeded with the warm books.
    Orthogonality is exact to f32 rounding (the SVD runs in f64)."""
    from raft_tpu.cluster import kmeans_fit, kmeans_predict

    d = train.shape[1]
    rot = np.eye(d, dtype=np.float32)
    cbs = [None] * S
    for _ in range(max(1, int(n_iters))):
        tr = (train @ rot).astype(np.float32)
        recon = np.empty_like(tr)
        for s in range(S):
            sl = slice(s * dsub, (s + 1) * dsub)
            km = kmeans_fit(res, tr[:, sl], K, max_iter=train_iters,
                            seed=seed + 211 + s, balanced=False,
                            init_centroids=cbs[s])
            cbs[s] = np.asarray(km.centroids, np.float32)
            code = np.asarray(kmeans_predict(res, km.centroids,
                                             tr[:, sl]))
            recon[:, sl] = cbs[s][code]
        u, _, vt = np.linalg.svd(
            train.astype(np.float64).T @ recon.astype(np.float64))
        rot = (u @ vt).astype(np.float32)
    return rot, cbs


def _aniso_assign(sub, cb, eta: float = _PQ_ANISO_ETA):
    """Score-aware codeword assignment for one subspace (ScaNN's
    anisotropic loss, fixed-η form): pick ``argmin_c ‖r − c‖² +
    (η − 1)·((r − c)·r/‖r‖)²`` — quantization error PARALLEL to the
    residual (which perturbs the dot-product score directly) costs η×
    the orthogonal error. Codebook centroids stay the k-means fit;
    only the assignment is re-weighted. Chunked [rows × K] host
    sweep."""
    sub = np.asarray(sub, np.float32)
    cb = np.asarray(cb, np.float32)
    n = sub.shape[0]
    out = np.empty(n, np.int32)
    cc = np.sum(cb * cb, axis=1)
    step = 65536
    for s0 in range(0, n, step):
        r = sub[s0:s0 + step]
        rn2 = np.sum(r * r, axis=1, keepdims=True)       # [n, 1]
        rn = np.sqrt(rn2)
        rc = r @ cb.T                                    # [n, K]
        base = rn2 + cc[None, :] - 2.0 * rc
        par = (rn - rc / np.maximum(rn, 1e-30)) ** 2
        par = np.where(rn > 0.0, par, 0.0)
        out[s0:s0 + step] = np.argmin(base + (eta - 1.0) * par,
                                      axis=1)
    return out


@instrument("ann.build_ivf_pq")
def build_ivf_pq(res, y, n_lists: int, pq_dim: Optional[int] = None,
                 pq_bits: Optional[int] = None,
                 n_probes: Optional[int] = None, max_iter: int = 10,
                 pq_max_iter: int = 8, seed: int = 0,
                 balanced: bool = True,
                 row_quantum: Optional[int] = None,
                 max_train_rows: Optional[int] = None,
                 pq_train_rows: Optional[int] = None,
                 pq_mode: Optional[str] = None,
                 opq_iters: int = 3) -> IvfPqIndex:
    """Build an :class:`IvfPqIndex` over ``y`` [m, d].

    (ref: ivf_pq::build — coarse train, per-subspace codebooks on
    residuals, encode.) The coarse stage IS :func:`~raft_tpu.ann.
    build_ivf_flat` (balanced k-means + the padded ragged slab; the
    f32 slab stays resident as the exact-rescore plane). Then, per
    subspace ``s`` of width ``d / pq_dim``:

    1. a ``2^pq_bits``-codeword codebook is trained with the PR-8
       :func:`~raft_tpu.cluster.kmeans_fit` on a ≤ ``pq_train_rows``
       sub-sample of the RESIDUALS ``y − c_assigned`` (default cap
       ``max(32·2^pq_bits, 4096)``);
    2. every slab row's residual subvector is assigned to its nearest
       codeword (the fusedL2NN argmin sweep) → the packed codes slab;
    3. the recorded error bounds: ``pq_eq_sub[s]`` = the max subspace
       round-trip ``‖resid_s − cb_s[code]‖`` over the encoded rows
       (× the ``(1 + 2⁻¹⁰)`` f32 headroom — the envelope the property
       tests attack), ``pq_eq_rows`` the exact per-row ``‖y − ŷ‖``
       and ``pq_eq_list`` its per-list max (the certificate inputs).

    ``pq_mode`` ∈ :data:`PQ_MODES` (default the
    ``RAFT_TPU_ANN_PQ_MODE`` knob): ``"opq"`` learns an orthogonal
    rotation by alternating minimization before the codebook train
    (applied to residuals at encode and to queries at ADC-table
    build); ``"opq_aniso"`` additionally assigns codewords under the
    score-aware anisotropic loss. ``pq_bits`` defaults to
    ``RAFT_TPU_ANN_PQ_BITS`` (8). Carries the ``pq_train`` and
    ``opq_train`` fault sites — a failing codebook/rotation train must
    surface at build, never as a silently-flat index."""
    from raft_tpu.cluster import kmeans_fit, kmeans_predict

    res = ensure_resources(res)
    y = np.asarray(y, np.float32)
    m, d = y.shape
    if pq_mode is None:
        pq_mode = env.get("RAFT_TPU_ANN_PQ_MODE")
    expects(pq_mode in PQ_MODES,
            "build_ivf_pq: pq_mode must be one of %s, got %r",
            PQ_MODES, pq_mode)
    if pq_bits is None:
        pq_bits = env.get("RAFT_TPU_ANN_PQ_BITS")
    pq_bits = int(pq_bits)
    expects(pq_bits in (4, 8),
            "build_ivf_pq: pq_bits must be 4 or 8, got %d", pq_bits)
    S = int(pq_dim) if pq_dim else _default_pq_dim(d)
    expects(S >= 1 and d % S == 0,
            "build_ivf_pq: pq_dim=%d must divide d=%d", S, d)
    expects(pq_bits == 8 or S % 2 == 0,
            "build_ivf_pq: 4-bit codes pack two per byte — pq_dim=%d "
            "must be even", S)
    K = 1 << pq_bits
    expects(m >= K,
            "build_ivf_pq: %d rows < 2^pq_bits = %d codewords — "
            "shrink pq_bits or use IVF-Flat", m, K)
    dsub = d // S

    flat = build_ivf_flat(res, y, n_lists=n_lists, n_probes=n_probes,
                          max_iter=max_iter, seed=seed,
                          balanced=balanced, row_quantum=row_quantum,
                          max_train_rows=max_train_rows)
    L = flat.n_lists
    padded = np.asarray(flat.padded_sizes)
    gid = np.repeat(np.arange(L, dtype=np.int32), padded)
    slab = np.asarray(flat.slab)
    ids = np.asarray(flat.ids)
    valid = ids >= 0
    cents = np.asarray(flat.centroids)
    resid = slab - cents[gid]                       # [R, d] residuals
    R = slab.shape[0]

    # --- per-subspace codebooks on the residual sub-sample ------------
    fault_point("pq_train")
    n_valid = int(valid.sum())
    cap = pq_train_rows or max(32 * K, 4096)
    vrows = np.nonzero(valid)[0]
    if n_valid > cap:
        rng = np.random.default_rng(seed + 17)
        vrows = rng.choice(vrows, cap, replace=False)
    train = resid[vrows]
    expects(train.shape[0] >= K,
            "build_ivf_pq: %d valid rows < %d codewords", n_valid, K)
    rot = None
    warm_cb = [None] * S
    if pq_mode != "plain":
        # the learned rotation: OPQ alternating minimization over the
        # train sample, then the full-budget codebook train below runs
        # in the ROTATED residual space (warm-started from the OPQ
        # books)
        fault_point("opq_train")
        rot, warm_cb = _opq_rotation(res, train, S, dsub, K, seed,
                                     n_iters=opq_iters,
                                     train_iters=max(
                                         1, pq_max_iter // 2))
        train = (train @ rot).astype(np.float32)
        resid_enc = (resid @ rot).astype(np.float32)
    else:
        resid_enc = resid
    codebooks = np.zeros((S, K, dsub), np.float32)
    codes = np.zeros((R, S), np.int32)
    for s in range(S):
        sub = train[:, s * dsub:(s + 1) * dsub]
        km = kmeans_fit(res, sub, K, max_iter=pq_max_iter,
                        seed=seed + 101 + s, balanced=False,
                        init_centroids=warm_cb[s])
        codebooks[s] = np.asarray(km.centroids)
        sub_all = resid_enc[:, s * dsub:(s + 1) * dsub]
        if pq_mode == "opq_aniso":
            codes[:, s] = _aniso_assign(sub_all, codebooks[s])
        else:
            codes[:, s] = np.asarray(kmeans_predict(
                res, km.centroids, sub_all))

    # --- reconstruction + the recorded error envelopes ----------------
    # (with a rotation: codes encode the ROTATED residual r' = r·R, so
    # the reconstructed row is c + r̂'·Rᵀ — norms preserved, every
    # envelope below is computed on the ACTUAL reconstruction)
    recon = cents[gid].copy()
    if rot is None:
        for s in range(S):
            recon[:, s * dsub:(s + 1) * dsub] += \
                codebooks[s][codes[:, s]]
    else:
        recon_rot = np.zeros((R, d), np.float32)
        for s in range(S):
            recon_rot[:, s * dsub:(s + 1) * dsub] = \
                codebooks[s][codes[:, s]]
        recon += recon_rot @ rot.T
    err = (slab - recon) * valid[:, None].astype(np.float32)
    # magnitude scales for the additive float-arithmetic headroom
    mag_sub = (np.sqrt(np.sum(slab.reshape(R, S, dsub) ** 2, axis=2))
               + np.sqrt(np.sum(recon.reshape(R, S, dsub) ** 2,
                                axis=2))) * valid[:, None]
    mag_row = (np.sqrt(np.sum(slab ** 2, axis=1))
               + np.sqrt(np.sum(recon ** 2, axis=1))) * valid
    e_sub = np.sqrt(np.maximum(
        np.sum(err.reshape(R, S, dsub) ** 2, axis=2), 0.0))
    eq_sub = ((e_sub.max(axis=0) if R else np.zeros(S))
              * _PQ_EQ_HEADROOM
              + _PQ_EQ_ABS * (mag_sub.max(axis=0) if R
                              else np.zeros(S)))
    eq_rows = (np.sqrt(np.maximum(np.sum(err ** 2, axis=1), 0.0))
               * _PQ_EQ_HEADROOM + _PQ_EQ_ABS * mag_row)
    # per-list certificate sidecars: the max row error bound and the
    # max reconstructed-RESIDUAL norm (the ADC kernel's hi/lo split
    # error scales with ‖x‖·‖r̂‖, so the envelope stays tight even for
    # data living far from the origin)
    rhat = recon - cents[gid]
    rhat_norm = np.sqrt(np.maximum(np.sum(rhat * rhat, axis=1), 0.0)) \
        * valid.astype(np.float32)
    eq_list = np.zeros(L, np.float32)
    rhat_list = np.zeros(L, np.float32)
    # per-list quantile sketch of the row error bounds (q50/q90/max
    # over the VALID rows) — the chooser's expected-rerun model and
    # the explain plane read it; the certificate itself rides the
    # exact per-row sidecar
    eq_qlist = np.zeros((L, 3), np.float32)
    offs = np.asarray(flat.offsets)
    for l in range(L):
        w = int(padded[l])
        if w:
            o = int(offs[l])
            eq_list[l] = eq_rows[o:o + w].max()
            rhat_list[l] = rhat_norm[o:o + w].max()
            seg = eq_rows[o:o + w][valid[o:o + w]]
            if seg.size:
                eq_qlist[l] = np.quantile(seg, (0.5, 0.9, 1.0))
    resid_norm = np.sqrt(np.maximum(np.sum(resid * resid, axis=1),
                                    0.0))
    resid_med = float(np.median(resid_norm[valid])) if n_valid else 0.0
    yy_pq = np.where(valid, np.sum(recon * recon, axis=1), 0.0)

    idx = IvfPqIndex(
        centroids=flat.centroids, slab=flat.slab, ids=flat.ids,
        yy_slab=flat.yy_slab, offsets=flat.offsets, sizes=flat.sizes,
        padded_sizes=flat.padded_sizes, n_rows=m, d_orig=d,
        row_quantum=flat.row_quantum,
        n_probes_default=flat.n_probes_default, Qb=flat.Qb,
        kmeans_iters=flat.kmeans_iters, balanced=balanced,
        pq_dim=S, pq_bits=pq_bits,
        codebooks=jnp.asarray(codebooks),
        codes=jnp.asarray(pack_pq_codes(codes, pq_bits)),
        yy_pq=jnp.asarray(yy_pq.astype(np.float32).reshape(R, 1)),
        pq_eq_rows=jnp.asarray(eq_rows.astype(np.float32)),
        pq_eq_sub=np.asarray(eq_sub, np.float32),
        pq_eq_list=jnp.asarray(eq_list),
        pq_rhat_list=jnp.asarray(rhat_list),
        pq_mode=pq_mode,
        pq_rot=None if rot is None else jnp.asarray(rot),
        pq_eq_qlist=np.asarray(eq_qlist, np.float32),
        pq_resid_med=resid_med)
    emit_marker("pq_build", n_rows=m, n_lists=L, pq_dim=S,
                pq_bits=pq_bits, pq_mode=pq_mode,
                code_bytes_per_row=idx.code_bytes,
                eq_row_max=round(float(eq_rows.max()) if R else 0.0, 6),
                eq_sub_max=round(float(eq_sub.max()), 6),
                resid_med=round(resid_med, 6),
                compression=round(4.0 * d / (idx.code_bytes + 8), 2))
    return idx


# ------------------------------------------------------------- search
def _pq_certify(bound, theta, widen):
    """certified ⇔ no probed row outside the candidate pool can beat
    the exact k-th value. ``bound`` is the kernel's pooled rest-min of
    the PER-ROW certified lower bounds ``(max(√d2_adc − Eq_row, 0))²``
    (the adaptive certificate — each row is widened by ITS OWN
    recorded error, not the probed lists' worst case), so ``widen``
    carries only the kernel-precision envelope. Module-level so the
    certificate-failure tests can force the widen/rerun rungs."""
    return bound >= theta + widen


def _pq_pool_finish(x, xx, rows, slab, ids, yy_slab, starts_qm, psizes,
                    k: int, P: int, W: int):
    """Exact-rescore the pooled candidate rows from the f32 slab with
    the query-major scorer's own formula, reorder into query-major
    candidate order (probe slot × window column — ties break exactly
    like :func:`~raft_tpu.ann.ivf_flat._fine_scan`) and select top-k.
    Unlike the flat `_pool_finish`, rows whose id is MASKED (−1 —
    tombstones on the mutable plane) score +inf: the codes slab keeps
    serving after a delete without a repack."""
    valid = rows >= 0
    rc = jnp.maximum(rows, 0)
    cid = jnp.where(valid, jnp.take(ids, rc), -1)
    valid = valid & (cid >= 0)
    yc = jnp.take(slab, rc, axis=0)                    # [nq, C2, d]
    d2 = (xx + jnp.take(yy_slab, rc)
          - 2.0 * jnp.einsum("qd,qcd->qc", x, yc,
                             precision=jax.lax.Precision.HIGHEST))
    d2 = jnp.where(valid, jnp.maximum(d2, 0.0), jnp.inf)
    w = rows[:, :, None] - starts_qm[:, None, :]       # [nq, C2, P]
    match = ((w >= 0) & (w < psizes[:, None, :])
             & valid[:, :, None])
    slot = jnp.argmax(match, axis=2).astype(jnp.int32)
    col = jnp.take_along_axis(w, slot[:, :, None], axis=2)[:, :, 0]
    key = jnp.where(jnp.any(match, axis=2),
                    slot * W + col.astype(jnp.int32), P * W)
    order = jnp.argsort(key, axis=1)
    d2s = jnp.take_along_axis(d2, order, axis=1)
    cids = jnp.take_along_axis(cid, order, axis=1)
    neg, pos = jax.lax.top_k(-d2s, k)
    vals = -neg
    out_ids = jnp.take_along_axis(cids, pos, axis=1)
    return vals, jnp.where(jnp.isfinite(vals), out_ids, -1)


def _pq_lut(x, codebooks, S: int, dsub: int):
    """The per-query ADC table: ``lut[q, s·K + j] = x_{q,s} ·
    cb_s[j]`` — f32 HIGHEST, flattened subspace-major for the kernel's
    one-hot contraction."""
    nq = x.shape[0]
    xr = x.reshape(nq, S, dsub)
    lut = jnp.einsum("qsd,skd->qsk", xr, codebooks,
                     precision=jax.lax.Precision.HIGHEST)
    return lut.reshape(nq, -1)


def pq_scan_chunk(index: IvfPqIndex, xs, probes_np, pr, st, ps,
                  k: int, P: int, W: int, ids=None,
                  pool_depth: int = 2):
    """One list-major ADC chunk → (vals, ids, certified, margin).
    ``ids`` overrides the slab id map (the mutable plane passes its
    tombstone-masked ``ids_live``); the certificate compares against
    the same masked oracle, so a failure's rerun returns identical id
    sets. ``pool_depth`` ∈ (2, 4, 8) sizes the per-lane-class
    candidate pool (the ``pq_widen`` rung re-runs at 4/8). ``margin``
    (bound − θ − e_k, pre-rerun) feeds the explain plane.

    The certificate is PER-QUERY ADAPTIVE: the kernel pools each
    streamed row's certified true-distance lower bound
    ``(max(√d2_adc − Eq_row, 0))²`` (its own recorded round-trip
    error, streamed as a sidecar), so the pooled rest-min needs only
    the kernel-precision envelope ``e_k`` on top of ``θ`` — the
    per-list worst-case ``2√θ·Eq + Eq²`` widening the pre-adaptive
    certificate paid survives only as the explain plane's
    ``pq_margin_adaptive_gain`` delta."""
    from raft_tpu.ops.fine_scan_pallas import pad_window
    from raft_tpu.ops.pq_scan_pallas import pq_scan_list_major

    if ids is None:
        ids = index.ids
    nq, d = xs.shape
    S, dsub = index.pq_dim, index.dsub
    Wk = pad_window(W)
    sched = build_list_schedule(index, probes_np)
    xx = jnp.sum(xs * xs, axis=1, keepdims=True)
    xp, pp, nqp = _pad_kernel_operands(xs, pr)
    xxp = jnp.concatenate(
        [xx, jnp.zeros((nqp - nq, 1), jnp.float32)]) if nqp > nq else xx
    # the learned rotation applies to the QUERY side of the ADC table
    # only: codes encode r·R, and x·(r̂'Rᵀ) = (x·R)·r̂' — the centroid
    # cross term and the exact rescore stay in the original basis
    xq = xp if index.pq_rot is None else jnp.matmul(
        xp, index.pq_rot, precision=jax.lax.Precision.HIGHEST)
    lut = _pq_lut(xq, index.codebooks, S, dsub)
    lids = jnp.maximum(jnp.asarray(sched.sched[3]), 0)
    cents = jnp.take(index.centroids, lids, axis=0)     # [Lp, d]
    cdot = jnp.einsum("qd,ld->ql", xp, cents,
                      precision=jax.lax.Precision.HIGHEST)
    pool = pq_scan_list_major(
        jnp.asarray(sched.sched), xxp, pp, cdot, lut,
        *index.pq_kernel_views, Wk=Wk, pq_bits=index.pq_bits,
        pool_depth=pool_depth)
    rows = jnp.concatenate(
        [pool[2 * t + 1][:nq] for t in range(pool_depth)], axis=1)
    vals, out_ids = _pq_pool_finish(xs, xx, rows, index.slab, ids,
                                    index.yy_slab, st, ps, k, P, W)
    # adaptive completeness certificate: every probed row OUTSIDE the
    # pool has certified lower bound ≥ the pooled rest-min, so only
    # the ADC kernel's numeric term over the score magnitudes widens θ
    theta = vals[:, k - 1]
    bound = jnp.min(pool[2 * pool_depth][:nq], axis=1)
    host = _list_host(index)
    eq_w = jnp.max(jnp.take(index.pq_eq_list, pr), axis=1)
    yymax = jnp.max(jnp.take(host["yy_lmax"], pr), axis=1)
    rhat_w = jnp.max(jnp.take(index.pq_rhat_list, pr), axis=1)
    # kernel-precision envelope: the ADC table's bf16 hi/lo two-pass
    # split carries ≤ ~2⁻¹⁷ relative error per entry against a
    # magnitude bounded by ‖x‖·‖r̂‖ (Cauchy-Schwarz over the subspace
    # concatenation — the RESIDUAL norm, not the row norm, which is
    # what keeps this tight for data far from the origin), plus the
    # f32 adds/accumulation over the full score magnitude. The
    # lower-bound map z ↦ (max(√z − Eq, 0))² is 1-Lipschitz, so the
    # same envelope bounds the pooled certificate scores.
    xnorm = jnp.sqrt(xx[:, 0])
    span = (xnorm + jnp.sqrt(yymax) + eq_w) ** 2
    e_k = (2.0 ** -15 * xnorm * rhat_w
           + (2.0 ** -20 + d * 2.0 ** -24) * span)
    certified = _pq_certify(bound, theta, e_k)
    if explain.active() is not None:
        # what the pre-adaptive per-list worst-case certificate would
        # have ADDED to the widening — the adaptive margin gain
        sq_t = jnp.sqrt(jnp.maximum(theta, 0.0))
        gain = 2.0 * sq_t * eq_w + eq_w * eq_w
        explain.note(pq_margin_adaptive_gain=round(
            float(jnp.mean(gain)), 6))
    return vals, out_ids, certified, bound - (theta + e_k)


def expected_pq_rerun_frac(index: IvfPqIndex, probes_np=None
                           ) -> Tuple[float, str]:
    """Measured-or-modeled expected certificate-rerun fraction for
    ``index`` — the number the chooser folds into the ADC-vs-flat
    byte comparison (the PR-15 blind spot: best-case codes bytes hid
    the exact-rerun cost on hard data).

    MEASURED wins when the quality plane has seen enough checks at the
    ``ann.search_ivf_pq`` site this process. Otherwise the MODEL reads
    the build-time per-list quantile sketch (``pq_eq_qlist``,
    restricted to the probed lists when given): when a typical row's
    recorded quantization error approaches the median residual norm,
    ADC ordering is noise at the margin scale and the certificate
    reruns — the prior is ``min(1, (q90_Eq / median‖y − c‖)²)``.
    Returns ``(frac, source)`` with source ∈ ("measured", "modeled",
    "unmodeled")."""
    from raft_tpu.observability.quality import measured_rerun_frac

    m = measured_rerun_frac("ann.search_ivf_pq")
    if m is not None:
        return float(m), "measured"
    q = getattr(index, "pq_eq_qlist", None)
    med = float(getattr(index, "pq_resid_med", 0.0) or 0.0)
    if q is None or med <= 0.0:
        return 0.0, "unmodeled"
    q = np.asarray(q)
    if probes_np is not None and q.ndim == 2 and q.shape[0]:
        lists = np.unique(np.asarray(probes_np).ravel())
        lists = lists[(lists >= 0) & (lists < q.shape[0])]
        if lists.size:
            q = q[lists]
    live = q[q[:, 2] > 0.0] if q.size else q
    if not live.size:
        return 0.0, "unmodeled"
    q90 = float(np.median(live[:, 1]))
    ratio = q90 / med
    return float(min(1.0, ratio * ratio)), "modeled"


def resolve_pq_scan(index: IvfPqIndex, nq: int, k: int, P: int, W: int,
                    requested: Optional[str] = None,
                    probes_np=None, chunk: Optional[int] = None) -> str:
    """EFFECTIVE schedule for one :func:`search_ivf_pq` call — the
    ``resolve_fine_scan``-style chooser. ``None`` reads
    ``RAFT_TPU_IVF_PQ_SCAN`` (default ``auto``).

    Envelope (outside it every request runs the flat scan, with a
    logged downgrade for an explicit ``pq``): the slab must cover one
    kernel window, ``k`` the 256-slot candidate pool, the probe count
    the 128-lane probe table, the ADC cell the scoped-VMEM budget, and
    on real TPUs the flattened table width ``pq_dim · 2^pq_bits`` must
    be lane-aligned.

    ``auto`` consults the schema-7 ``pq`` tune-table column
    (:func:`raft_tpu.tune.ivf.pq_scan_config`, mode-aware) first,
    then the cost-model crossover (:func:`~raft_tpu.observability.
    costmodel.choose_pq_scan` over the pq-aware traffic model on the
    index's actual list-size histogram) — priced at the EXPECTED
    bytes including the measured-or-modeled certificate-rerun
    fraction (:func:`expected_pq_rerun_frac`), with a logged
    downgrade when the rerun pricing flips the best-case pick."""
    from raft_tpu.observability.costmodel import (choose_pq_scan,
                                                  ivf_traffic_model)
    from raft_tpu.ops.fine_scan_pallas import pad_window
    from raft_tpu.ops.fused_l2_topk_pallas import vmem_budget
    from raft_tpu.ops.pq_scan_pallas import (kernel_rows, pq_window,
                                             pq_scan_vmem_footprint)
    from raft_tpu.ops.utils import interpret_mode

    req = requested if requested is not None \
        else env.get("RAFT_TPU_IVF_PQ_SCAN")
    if req not in PQ_SCANS:
        raise ValueError(f"pq_scan must be one of {PQ_SCANS}, "
                         f"got {req!r}")
    if req == "flat":
        return "flat"
    Wk = pad_window(W)
    S, K = index.pq_dim, index.pq_k
    nqp = -(-min(nq, chunk or nq) // 8) * 8
    from raft_tpu.ann.ivf_flat import _list_cells, _max_entries
    from raft_tpu.ops.fine_scan_pallas import LISTS_PER_CELL

    Lp = _list_cells(_max_entries(index, min(nq, chunk or nq) * P),
                     _max_entries(index)) * LISTS_PER_CELL
    reason = None
    if kernel_rows(index.slab_rows) < pq_window(Wk):
        reason = (f"slab rows {index.slab_rows} < kernel window "
                  f"{pq_window(Wk)}")
    elif k > _LIST_K_MAX:
        reason = f"k={k} > {_LIST_K_MAX} exceeds the candidate pool"
    elif P > 128:
        reason = f"n_probes={P} > 128 exceeds the probe table"
    elif pq_scan_vmem_footprint(Wk, nqp, S, K, Lp,
                                index.pq_bits) > vmem_budget():
        reason = "ADC cell footprint over the scoped-VMEM budget"
    elif not interpret_mode() and (S * K) % 128:
        reason = (f"ADC table width {S}x{K} is not lane-aligned on a "
                  f"real TPU")
    if reason is not None:
        if req == "pq":
            from raft_tpu.core.logger import log_warn

            log_warn("pq_scan='pq' outside the ADC envelope (%s) — "
                     "using the flat scan for this call", reason)
        return "flat"
    if req == "pq":
        return "pq"
    # auto — tuned table first, then the cost-model crossover at the
    # rerun-aware expected bytes
    from raft_tpu.tune.ivf import pq_scan_config

    tuned = pq_scan_config(index.n_lists, P, index.pq_bits,
                           pq_mode=getattr(index, "pq_mode", "plain"))
    if tuned in ("pq", "flat"):
        return tuned
    frac, src = expected_pq_rerun_frac(index, probes_np)
    model = ivf_traffic_model(
        nq, index.n_rows, index.d_orig, k, index.n_lists, P, W,
        index.slab_rows, list_sizes=index._np_sizes,
        padded_sizes=index._np_padded, pq_dim=S,
        pq_bits=index.pq_bits, pq_rerun_frac=frac)
    pick = choose_pq_scan(model)
    if pick == "flat" and choose_pq_scan(model, rerun_frac=0.0) == "pq":
        from raft_tpu.core.logger import log_warn

        log_warn("pq_scan auto: expected certificate-rerun fraction "
                 "%.2f (%s) prices the ADC scan above the flat scan "
                 "— downgrading to flat for this call", frac, src)
        emit_marker("pq_chooser_downgrade",
                    rerun_frac=round(frac, 4), source=src)
        explain.note(pq_chooser_downgrade={
            "rerun_frac": round(frac, 4), "source": src})
    return pick


@instrument("ann.search_ivf_pq")
def search_ivf_pq(res, index: IvfPqIndex, queries, k: int,
                  n_probes: Optional[int] = None,
                  pq_scan: Optional[str] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Approximate top-k against an :class:`IvfPqIndex`.

    (ref: ivf_pq::search + its refine step — ADC over the compressed
    lists, then exact re-ranking of the shortlist.) Returns (d2
    [nq, k] ascending, global ids [nq, k]) like ``search_ivf_flat``;
    the returned values are EXACT f32 distances (every candidate is
    rescored from the retained f32 slab — the mandatory refine), and
    the id set is certified identical to the flat scan's over the same
    probe lists: a failed completeness certificate reruns the exact
    f32 scan for that chunk, and a device failure at the kernel
    dispatch (a ``DeviceError``, fault site ``pq_scan``) degrades to
    the f32/int8 query-major scan with a recorded degradation; any
    other error propagates.

    ``pq_scan`` ∈ :data:`PQ_SCANS` picks the schedule (``None`` reads
    ``RAFT_TPU_IVF_PQ_SCAN``); ``n_probes ≥ n_lists`` (or ``k`` past
    the probed capacity) degrades to certified-EXACT search exactly
    like IVF-Flat."""
    fault_point("ivf_search")
    res = ensure_resources(res)
    expects(isinstance(index, IvfPqIndex),
            "search_ivf_pq: index must be an IvfPqIndex (got %s)",
            type(index).__name__)
    x = jnp.asarray(queries, jnp.float32)
    expects(x.ndim == 2 and x.shape[1] == index.d_orig,
            "search_ivf_pq: query width %s != index %d",
            x.shape[1:], index.d_orig)
    expects(k >= 1, "search_ivf_pq: k must be >= 1")
    expects(k <= index.n_rows,
            "search_ivf_pq: k=%d > index size %d", k, index.n_rows)
    nq = x.shape[0]
    if nq == 0:
        return (jnp.zeros((0, k), jnp.float32),
                jnp.zeros((0, k), jnp.int32))
    L = index.n_lists
    if n_probes is None:
        from raft_tpu.ann.ivf_flat import _env_int

        P = _env_int("RAFT_TPU_ANN_NPROBES", index.n_probes_default)
    else:
        P = int(n_probes)
    expects(P >= 1, "search_ivf_pq: n_probes must be >= 1, got %d", P)
    W = index.probe_window
    reason = None
    if P >= L:
        reason = f"n_probes={P} >= n_lists={L}"
    elif k > P * W:
        reason = (f"k={k} exceeds the probed candidate capacity "
                  f"{P}x{W}={P * W}")
    if reason is not None:
        from raft_tpu.core.logger import log_warn

        log_warn("search_ivf_pq: %s — degrading to exact search over "
                 "the f32 slab for this call", reason)
        emit_marker("ivf_exact_degrade", reason=reason, k=k,
                    n_probes=P, n_lists=L)
        explain.note(plane="ivf_pq", exact_degrade=reason,
                     n_probes=P, n_lists=L, k=k)
        return _exact_search(res, index, x, k)

    probes = _coarse_probe(res, index.centroids, x, P)       # [nq, P]
    probes_host = np.asarray(probes)
    starts = jnp.take(index.offsets[:-1], probes)
    psizes = jnp.take(index.padded_sizes, probes)
    d = x.shape[1]
    chunk = max(8, _FINE_TILE // max(1, P * W * max(d, 1)))
    schedule = resolve_pq_scan(index, nq, k, P, W, pq_scan,
                               probes_np=probes_host, chunk=chunk)
    emit_marker("ivf_pq_search", nq=nq, k=k, n_probes=P, n_lists=L,
                pq_dim=index.pq_dim, pq_bits=index.pq_bits,
                schedule=schedule)
    if explain.active() is not None:
        sz = np.asarray(index.sizes)[probes_host]
        explain.note(plane="ivf_pq", n_probes=P, n_lists=L, k=k,
                     pq_bits=index.pq_bits, pq_dim=index.pq_dim,
                     pq_scan=schedule,
                     probed_lists=probes_host[0].tolist(),
                     probed_rows=int(sz.sum()),
                     probed_size_hist={
                         "min": int(sz.min()), "p50": float(
                             np.percentile(sz, 50)),
                         "max": int(sz.max())},
                     pool_width=256)
    if schedule == "pq":
        try:
            fault_point("pq_scan")
            return _search_pq(res, index, x, probes, probes_host,
                              starts, psizes, k, P, W, chunk)
        except DeviceError as e:
            # injected faults and classified device failures only: a
            # kernel that fails to compile or lower propagates
            from raft_tpu.core.logger import log_warn

            record_degradation("pq_scan", "flat")
            emit_marker("pq_scan_degrade",
                        reason=f"{type(e).__name__}: {e}"[:160])
            explain.note(pq_scan_degrade=f"{type(e).__name__}"[:64])
            log_warn("PQ ADC scan failed (%s: %s) — degrading to the "
                     "flat fine scan for this call",
                     type(e).__name__, e)
    # the flat rung: the uncompressed f32 (or int8) fine scan — the
    # degradation target and the chooser's "flat" pick share one path
    if nq <= chunk:
        return _query_major_chunk(index, x, starts, psizes, k, P, W)
    outs = [_query_major_chunk(index, x[s:s + chunk],
                               starts[s:s + chunk],
                               psizes[s:s + chunk], k, P, W)
            for s in range(0, nq, chunk)]
    return (jnp.concatenate([o[0] for o in outs]),
            jnp.concatenate([o[1] for o in outs]))


def _search_pq(res, index: IvfPqIndex, x, probes, probes_host, starts,
               psizes, k: int, P: int, W: int, chunk: int):
    """The ADC driver: per chunk, run :func:`pq_scan_chunk`, walk any
    certificate-failing rows down the widen rungs (2x / 4x candidate
    pool, re-ADC, re-certify), and rerun whatever still fails through
    the exact f32 scan — returned id sets match the flat scan's over
    the same probes in EVERY case."""
    from raft_tpu.ann.ivf_flat import _list_cells, _max_entries
    from raft_tpu.ops.fine_scan_pallas import (LISTS_PER_CELL,
                                               pad_window)
    from raft_tpu.ops.fused_l2_topk_pallas import vmem_budget
    from raft_tpu.ops.pq_scan_pallas import pq_scan_vmem_footprint

    nq = x.shape[0]
    widen_cap = int(env.get("RAFT_TPU_ANN_PQ_WIDEN"))
    try:
        res.profiler.capture_fn(
            "ann.pq_scan", _pq_lut, x[:min(nq, chunk)],
            index.codebooks, index.pq_dim, index.dsub)
    except Exception:
        pass

    def run_chunk(s0: int, s1: int):
        xs, pr = x[s0:s1], probes[s0:s1]
        st, ps = starts[s0:s1], psizes[s0:s1]
        nq_c = int(xs.shape[0])
        vals, ids_c, ok, margin = pq_scan_chunk(
            index, xs, probes_host[s0:s1], pr, st, ps, k, P, W)
        explain.note_margin("ann.search_ivf_pq", margin)
        n_fail0 = n_fail = int(jnp.sum(~ok))
        depth_used = 2
        if n_fail:
            # the widen rung: before escalating to the exact scan,
            # re-run the ADC with a deeper candidate pool (256 -> 512
            # -> 1024 slots) and re-certify — on margin-starved rows
            # the pooled rest-min usually clears theta + e_k once the
            # pool holds the near-boundary candidates
            Wk = pad_window(W)
            nqp = -(-nq_c // 8) * 8
            Lp = _list_cells(_max_entries(index, nq_c * P),
                             _max_entries(index)) * LISTS_PER_CELL
            for factor in (2, 4):
                if factor > widen_cap or not n_fail:
                    break
                depth = 2 * factor
                if pq_scan_vmem_footprint(
                        Wk, nqp, index.pq_dim, index.pq_k, Lp,
                        index.pq_bits,
                        pool_depth=depth) > vmem_budget():
                    break
                try:
                    fault_point("pq_widen")
                    wv, wi, wok, _wm = pq_scan_chunk(
                        index, xs, probes_host[s0:s1], pr, st, ps,
                        k, P, W, pool_depth=depth)
                except DeviceError as e:
                    # injected / classified device failures only
                    from raft_tpu.core.logger import log_warn

                    record_degradation("pq_widen", "exact")
                    emit_marker("pq_widen_degrade",
                                reason=f"{type(e).__name__}: "
                                       f"{e}"[:160])
                    log_warn("PQ widen rung x%d failed (%s: %s) — "
                             "escalating straight to the exact "
                             "rerun", factor, type(e).__name__, e)
                    break
                okc = ok[:, None]
                vals = jnp.where(okc, vals, wv)
                ids_c = jnp.where(okc, ids_c, wi)
                ok = ok | wok
                depth_used = depth
                n_fail = int(jnp.sum(~ok))
        # same host sync the certified gather paths already pay — the
        # PQ slice of the certificate/fixup evidence plane
        record_certificate("ann.search_ivf_pq",
                           n_queries=nq_c, n_fail=n_fail,
                           pool_width=128 * depth_used,
                           fixup_rows=n_fail or None,
                           rerun=bool(n_fail), pq_bits=index.pq_bits,
                           n_probes=P)
        record_pq_rungs("ann.search_ivf_pq",
                        certified=nq_c - n_fail0,
                        widened=n_fail0 - n_fail, exact_rerun=n_fail)
        if explain.active() is not None:
            explain.note(pq_rungs={
                "certified": nq_c - n_fail0,
                "widened": n_fail0 - n_fail, "exact_rerun": n_fail})
        if n_fail:
            # the true top-k (or a tie) may hide outside the pooled
            # candidates: rerun the chunk through the exact f32 scan
            # and keep certified rows — bytes saved stand, correctness
            # never rides on the margin
            emit_marker("pq_cert_fallback", n_fail=n_fail, nq=nq_c)
            explain.note(rerun="pq_exact", rerun_rows=n_fail)
            fv, fi = _fine_scan(xs, index.slab, index.ids,
                                index.yy_slab, st, ps, k=k, P=P, W=W)
            okc = ok[:, None]
            vals = jnp.where(okc, vals, fv)
            ids_c = jnp.where(okc, ids_c, fi)
        return vals, ids_c

    if nq <= chunk:
        return run_chunk(0, nq)
    outs = [run_chunk(s, min(s + chunk, nq))
            for s in range(0, nq, chunk)]
    return (jnp.concatenate([o[0] for o in outs]),
            jnp.concatenate([o[1] for o in outs]))


def warm_pq_scan(res, index: IvfPqIndex, nq: int, k: int,
                 n_probes: int) -> int:
    """Pre-compile every program a serving bucket of ``nq`` queries
    can reach on the PQ plane: the flat fallback/degradation programs
    (through the public entry, so the chunking and rerun programs warm
    too) and one ADC program per (power-of-two schedule-cell rung x
    certification pool depth — the widen ladder up to
    ``RAFT_TPU_ANN_PQ_WIDEN``) — mirrors
    :func:`~raft_tpu.ann.ivf_flat.warm_fine_scan` so a live request
    never pays a compile whichever way the chooser (or the
    certificate) lands. Returns the warmed ADC program count (0 =
    outside the ADC envelope)."""
    from raft_tpu.ann.ivf_flat import _max_entries
    from raft_tpu.ops.fine_scan_pallas import (LISTS_PER_CELL,
                                               pad_window)
    from raft_tpu.ops.pq_scan_pallas import pq_scan_list_major

    P = min(max(1, int(n_probes)), index.n_lists)
    if P >= index.n_lists or nq < 1:
        return 0            # the degenerate-exact plane — one schedule
    W = index.probe_window
    Wk = pad_window(W)
    d = index.d_orig
    x0 = np.zeros((nq, d), np.float32)
    out = search_ivf_pq(res, index, x0, k, n_probes=P, pq_scan="flat")
    jax.block_until_ready(out)
    if resolve_pq_scan(index, nq, k, P, W, "pq") != "pq":
        return 0
    chunk = max(8, _FINE_TILE // max(1, P * W * max(d, 1)))
    sizes = sorted({min(nq, chunk), nq % chunk or min(nq, chunk)})
    cap = max(1, -(-_max_entries(index) // LISTS_PER_CELL))
    rungs = sorted({min(1 << b, cap)
                    for b in range(cap.bit_length() + 1)})
    widen_cap = int(env.get("RAFT_TPU_ANN_PQ_WIDEN"))
    depths = [2] + [2 * f for f in (2, 4) if f <= widen_cap]
    S, K = index.pq_dim, index.pq_k
    warmed = 0
    for nq_c in sizes:
        nqp = -(-nq_c // 8) * 8
        xx0 = jnp.zeros((nqp, 1), jnp.float32)
        pp0 = jnp.full((nqp, 128), -2, jnp.int32)
        lut0 = jnp.zeros((nqp, S * K), jnp.float32)
        for cells in rungs:
            Lp = cells * LISTS_PER_CELL
            sched = np.zeros((4, Lp), np.int32)
            sched[3, :] = -1
            for depth in depths:
                out = pq_scan_list_major(
                    jnp.asarray(sched), xx0, pp0,
                    jnp.zeros((nqp, Lp), jnp.float32), lut0,
                    *index.pq_kernel_views,
                    Wk=Wk, pq_bits=index.pq_bits, pool_depth=depth)
                jax.block_until_ready(out)
                warmed += 1
    return warmed
