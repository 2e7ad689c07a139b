"""Plain reference of what the timed path computes, for the check of `correct`.

It imports nothing of the program under test. From the paper (arXiv:2312.11820)
and the repository's documented semantics it rebuilds, in NumPy:

* the design space of TABLE I and its normalised encoding;
* the analytical SoC cost model (the flow) that scores a design on a DNN
  workload: latency (ms), power (mW), area (mm^2);
* Algorithm 1's ICD importance and Algorithm 2's pruning and ICD transform;
* one Bayesian-optimisation round of Algorithm 3: the per-objective GP fit
  (exact marginal likelihood, Adam, the documented hyperpriors and clamps),
  the posterior over the pool, the sampled Pareto-frontier maxima of the MES
  acquisition (Eqs. 7-9) and the acquisition of every candidate.

Everything is computed in float64 (`Arith("f64")`). The control computes the
same formulas one precision step lower: the GP algebra in float32 with every
matrix product at the TPU's `high` precision (three bfloat16 passes), and the
cost model with every intermediate rounded to bfloat16. `Arith("f32")`, the
GP algebra in float32 with exact float32 products (the precision the
configurations state), is a witness of what float32 rounding alone does.

Where a configuration leaves a setting of the driver or the engine at its
default, the reference takes it from :data:`DEFAULTS`, the defaults that
``soc_tuner`` and ``BOEngine`` document.

The random numbers of a round (frontier subset, standard normals of the joint
frontier draw, ICD trial rows) are drawn with `jax.random` from the round's
key, following the driver's documented key schedule: the reference needs the
same draws to judge a pick, just as a language-model reference needs the same
prompt. `jax.random` is part of JAX, not of the program.
"""
from __future__ import annotations

import math

import ml_dtypes
import numpy as np
from scipy.linalg import cho_solve, solve_triangular
from scipy.special import ndtr

#: ``soc_tuner``'s and ``BOEngine``'s documented defaults for every setting
#: the reference replays (``warm_steps`` None: max(10, gp_steps // 10)).
DEFAULTS = {"T": 40, "n": 30, "mu": 0.1, "b": 20, "v_th": 0.07,
            "s_frontiers": 10, "frontier_subset": 512, "gp_steps": 150,
            "incremental": False, "warm_steps": None, "drift_tol": 1.0}


def settings(protocol: dict, engine: dict) -> dict:
    """A configuration's protocol and engine settings over :data:`DEFAULTS`."""
    unknown = (set(protocol) | set(engine)) - set(DEFAULTS) - {"pool_chunk"}
    if unknown:
        raise ValueError(f"settings the reference does not replay: "
                         f"{sorted(unknown)}")
    out = {**DEFAULTS, **protocol, **engine}
    if out["warm_steps"] is None:
        out["warm_steps"] = max(10, int(out["gp_steps"]) // 10)
    return out


# ------------------------------------------------------------ precision
_BF16 = ml_dtypes.bfloat16


def round_bf16(x) -> np.ndarray:
    """Round to the nearest bfloat16 and return float32."""
    return np.asarray(x, np.float32).astype(_BF16).astype(np.float32)


class Arith:
    """The precision one reference pass computes in.

    ``f64``: the reference. ``f32``: the GP algebra in float32 with exact
    float32 matmuls (the witness). ``f32_high``: the GP control, float32
    with matmuls as three bfloat16 passes (the TPU's `high`). ``bf16``: the
    flow control, every cost-model intermediate rounded to bfloat16.
    """

    def __init__(self, name: str):
        if name not in ("f64", "f32", "f32_high", "bf16"):
            raise ValueError(f"unknown precision {name!r}")
        self.name = name
        self.dtype = np.float64 if name == "f64" else np.float32

    def a(self, x) -> np.ndarray:
        return np.asarray(x, self.dtype)

    def q(self, x):
        """Cost-model rounding point: identity except in ``bf16``."""
        if self.name == "bf16":
            return round_bf16(x)
        return x

    def mm(self, a, b) -> np.ndarray:
        if self.name != "f32_high":
            return np.matmul(a, b)
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        ah, bh = round_bf16(a), round_bf16(b)
        al, bl = round_bf16(a - ah), round_bf16(b - bh)
        return (ah @ bl + al @ bh) + ah @ bh


F64 = Arith("f64")

# -------------------------------------------------- design space (TABLE I)
#: (name, candidate values, categorical) — TABLE I of the paper.
FEATURES = (
    ("HostCore", (0, 1, 2), True), ("L2Bank", (1, 2, 4), False),
    ("L2Way", (4, 8, 16), False), ("L2Capa", (128, 256, 512), False),
    ("TileRow", (1, 2, 4, 8), False), ("TileCol", (1, 2, 4, 8), False),
    ("MeshRow", (8, 16, 32, 64), False), ("MeshCol", (8, 16, 32, 64), False),
    ("Dataflow", (0, 1, 2), True), ("InputType", (8, 16, 32), False),
    ("AccType", (8, 16, 32), False), ("OutType", (8, 20, 32), False),
    ("SpBank", (4, 8, 16, 32), False), ("SpCapa", (64, 128, 256, 512), False),
    ("AccBank", (1, 2, 4, 8), False), ("AccCapa", (64, 128, 256, 512), False),
    ("LdQueue", (2, 4, 8, 16), False), ("StQueue", (2, 4, 8, 16), False),
    ("ExQueue", (2, 4, 8, 16), False), ("LdRes", (2, 4, 8, 16), False),
    ("StRes", (2, 4, 8, 16), False), ("ExRes", (2, 4, 8, 16), False),
    ("MemReq", (16, 32, 64), False), ("DMABus", (32, 64, 128), False),
    ("DMABytes", (32, 64, 128), False), ("TLBSize", (4, 8, 16), False),
)
D = len(FEATURES)
FI = {name: i for i, (name, _, _) in enumerate(FEATURES)}
T_CAND = np.array([len(v) for _, v, _ in FEATURES])


def _norm_table() -> np.ndarray:
    """Normalised candidate values: log2 scale for numeric features,
    ordinal for categorical ones, each into [0, 1]."""
    out = np.zeros((D, int(T_CAND.max())))
    for i, (_, vals, cat) in enumerate(FEATURES):
        v = np.asarray(vals, np.float64)
        if cat:
            x = v / max(1.0, v.max())
        else:
            lv = np.log2(v)
            x = (lv - lv.min()) / max(lv.max() - lv.min(), 1e-9)
        out[i, : len(vals)] = x
    return out


NORM = _norm_table()
VALUES = np.zeros((D, int(T_CAND.max())))
for _i, (_, _v, _) in enumerate(FEATURES):
    VALUES[_i, : len(_v)] = _v


def encode(idx: np.ndarray) -> np.ndarray:
    idx = np.asarray(idx)
    return NORM[np.arange(D), idx]


def raw_values(idx: np.ndarray) -> np.ndarray:
    idx = np.asarray(idx)
    return VALUES[np.arange(D), idx]


def sample_pool(key_seed: int, n: int) -> np.ndarray:
    """``n`` distinct designs drawn uniformly from TABLE I with NumPy."""
    rng = np.random.default_rng(key_seed)
    out = np.empty((0, D), np.int32)
    while out.shape[0] < n:
        draw = rng.integers(0, T_CAND, size=(n, D)).astype(np.int32)
        both = np.concatenate([out, draw], 0)
        _, first = np.unique(both, axis=0, return_index=True)
        out = both[np.sort(first)]
    return out[:n]


# --------------------------------------------------------- DNN workloads
def _l(M, K, N, reps=1, kind=0):
    return [float(M), float(K), float(N), float(reps), float(kind)]


def _resnet50():
    L = [_l(112 * 112, 3 * 49, 64)]
    c_in = 64
    for c_mid, c_out, blocks, hw in [(64, 256, 3, 56), (128, 512, 4, 28),
                                     (256, 1024, 6, 14), (512, 2048, 3, 7)]:
        for b in range(blocks):
            m = hw * hw
            L.append(_l(m, c_in if b == 0 else c_out, c_mid))
            L.append(_l(m, 9 * c_mid, c_mid))
            L.append(_l(m, c_mid, c_out))
            if b == 0:
                L.append(_l(m, c_in, c_out))
        c_in = c_out
    L.append(_l(1, 2048, 1000))
    return L


def _mobilenet():
    L = [_l(112 * 112, 27, 32)]
    plan = [(32, 64, 1), (64, 128, 2), (128, 128, 1), (128, 256, 2),
            (256, 256, 1), (256, 512, 2)] + [(512, 512, 1)] * 5 \
        + [(512, 1024, 2), (1024, 1024, 1)]
    hw = 112
    for cin, cout, s in plan:
        hw //= s
        L.append(_l(hw * hw, 9, 1, reps=cin, kind=2))
        L.append(_l(hw * hw, cin, cout))
    L.append(_l(1, 1024, 1000))
    return L


def _transformer(seq=128, d=512, heads=8, ffn=2048, blocks=6):
    hd = d // heads
    L = []
    for _ in range(blocks):
        L += [_l(seq, d, 3 * d), _l(seq, hd, seq, reps=heads, kind=1),
              _l(seq, seq, hd, reps=heads, kind=1), _l(seq, d, d),
              _l(seq, d, ffn), _l(seq, ffn, d)]
    return L


WORKLOADS = {"resnet50": _resnet50, "mobilenet": _mobilenet,
             "transformer": _transformer}


def workload(name: str) -> np.ndarray:
    return np.asarray(WORKLOADS[name](), np.float64)


# ------------------------------------------------------------- cost model
C = dict(freq_hz=1.0e9, dram_lat=120.0, l2_hit_lat=24.0, tlb_miss_cost=40.0,
         page_bytes=4096.0, dma_fixed_overhead=16.0,
         core_issue=(2.0, 5.0, 8.0), core_energy=(0.35, 0.18, 0.12),
         core_area=(1.10, 0.35, 0.22), layer_launch_cmds=24.0, e_mac8=0.25,
         e_spad_byte=0.45, e_dram_byte=18.0, leak_mw_per_mm2=0.6, base_mw=2.0,
         a_pe8=1.6e-4, a_sram_mb=0.90, a_acc_sram_mb=1.35, a_l2_mb=1.05,
         a_queue_entry=6.0e-4, a_dma_per_byte_lane=2.0e-3, a_tlb_entry=1.0e-3,
         noc_overhead=1.08)


def soc_metrics(idx: np.ndarray, layers: np.ndarray,
                ar: Arith = F64) -> np.ndarray:
    """[n, 3] latency_ms, power_mw, area_mm2 of designs ``idx`` [n, 26]."""
    q = ar.q
    v = raw_values(idx)
    g = lambda name: v[:, FI[name]][:, None]                    # [n, 1]
    M, K, N, reps, kind = (layers[:, i][None, :] for i in range(5))
    R, Cc = q(g("TileRow") * g("MeshRow")), q(g("TileCol") * g("MeshCol"))
    ib, ab, ob = g("InputType") / 8, g("AccType") / 8, g("OutType") / 8
    spad = q(g("SpBank") * g("SpCapa") * Cc * ib)
    acc_rows = q(g("AccBank") * g("AccCapa"))
    acc_bytes = q(acc_rows * Cc * ab)
    l2_bytes = q(g("L2Bank") * g("L2Capa") * 1024.0)
    cdiv = lambda a, b: q(np.ceil(q(a / b)))

    # weight-stationary
    Mb = np.minimum(M, acc_rows)
    Kt, Nt, Mt = cdiv(K, R), cdiv(N, Cc), cdiv(M, Mb)
    comp_ws = q(reps * q(Kt * Nt * q(Mt * Mb + R) + Nt * Cc))
    w_fits = q(K * N * ib) <= q(0.5 * spad)
    a_fits = q(Mb * K * ib) <= q(0.5 * spad)
    dram_ws = q(reps * q(q(K * N * ib * np.where(w_fits, 1.0, Mt))
                         + q(M * K * ib * np.where(a_fits, 1.0, Nt))
                         + q(M * N * ob)))
    stream_ws = q(reps * q(q(Kt * Nt * Mt * q(Mb * R * ib + R * Cc * ib))
                           + q(M * N * ob)))
    # output-stationary
    Mt2, Nt2 = cdiv(M, R), cdiv(N, Cc)
    comp_os = q(reps * q(Mt2 * Nt2 * (K + R + Cc)))
    a_fits2 = q(M * K * ib) <= q(0.5 * spad)
    dram_os = q(reps * q(q(K * N * ib * np.where(w_fits, 1.0, Mt2))
                         + q(M * K * ib * np.where(a_fits2, 1.0, Nt2))
                         + q(M * N * ob)))
    stream_os = q(reps * q(q(Mt2 * Nt2 * K * (R + Cc) * ib) + q(M * N * ob)))

    df = g("Dataflow")
    use_os = (df == 1.0) | ((df == 2.0) & (comp_os < comp_ws))
    compute = np.where(use_os, comp_os, comp_ws)
    dram = np.where(use_os, dram_os, dram_ws)
    stream = np.where(use_os, stream_os, stream_ws)
    n_tiles = q(np.where(use_os, Mt2 * Nt2, Mt * Kt * Nt) * reps)
    dram = np.where(kind == 1.0, q(dram + q(0.15 * K * N * ib * reps)), dram)
    macs = q(reps * M * K * N)

    n_layers = layers.shape[0]
    working = q(dram.sum(axis=1))
    l2w = g("L2Way")[:, 0]
    l2_hit = q(np.clip(q(3.0 * l2_bytes[:, 0] / q(working / n_layers + 1.0)),
                       0.0, 0.85) * q(1.0 + 0.05 * np.log2(l2w / 4.0)))
    mem_lat = q(l2_hit * C["l2_hit_lat"] + (1.0 - l2_hit) * C["dram_lat"])
    dmab = g("DMABytes")[:, 0]
    eff = q(dmab / (dmab + C["dma_fixed_overhead"]))
    bw = q(np.minimum(g("DMABus")[:, 0] / 8.0,
                      q(g("MemReq")[:, 0] * dmab / mem_lat)) * eff)
    pages = q(dram / C["page_bytes"])
    tlb_miss = np.maximum(q(pages - g("TLBSize") * 8.0), 0.0)
    dma_cycles = q(q(dram / bw[:, None]) + q(tlb_miss * C["tlb_miss_cost"]))

    core = g("HostCore")
    pick = lambda table: np.choose(core.astype(int), table)
    issue = pick(C["core_issue"])
    q_eff = np.minimum(np.minimum(g("LdQueue"), g("LdRes")),
                       np.minimum(g("ExQueue"), g("ExRes")))
    cmds = q(4.0 * n_tiles + C["layer_launch_cmds"])
    host = q(cmds * issue * q(1.0 + 2.0 / q_eff))

    three = np.stack([compute, dma_cycles, host], -1)
    hi = three.max(-1)
    rest = q(q(three.sum(-1)) - hi)
    buf = q(np.clip((g("SpBank") - 4.0) / 12.0, 0.0, 1.0) * 0.8
            + np.clip((g("AccBank") - 1.0) / 7.0, 0.0, 1.0) * 0.2)
    layer_cycles = q(hi + q(q(1.0 - buf) * 0.5 * rest) + 400.0 * issue)
    cycles = q(layer_cycles.sum(axis=1))
    latency_ms = q(cycles / C["freq_hz"] * 1e3)

    e_mac = q(C["e_mac8"] * ib[:, 0] ** 1.7)
    pj = q(q(q(macs.sum(1)) * e_mac) + q(q(stream.sum(1)) * C["e_spad_byte"])
           + q(q(dram.sum(1)) * C["e_dram_byte"]))
    nj = q(pj * 1e-3 + q(q(host.sum(1)) * pick(C["core_energy"])[:, 0]))

    dfl = df[:, 0]
    pe = q(C["a_pe8"] * ib[:, 0] ** 1.25 * (1.0 + 0.25 * ab[:, 0] / 4.0))
    arr = q(R[:, 0] * Cc[:, 0] * pe * np.where(
        dfl == 2.0, 1.12, np.where(dfl == 1.0, 1.05, 1.0)))
    mb = 1.0 / (1024.0 * 1024.0)
    sram = q(q(spad[:, 0] * mb * C["a_sram_mb"])
             + q(acc_bytes[:, 0] * mb * C["a_acc_sram_mb"])
             + q(l2_bytes[:, 0] * mb * C["a_l2_mb"]
                 * (1.0 + 0.02 * np.log2(l2w / 4.0))))
    queues = sum(g(k)[:, 0] for k in ("LdQueue", "StQueue", "ExQueue",
                                      "LdRes", "StRes", "ExRes")) \
        * C["a_queue_entry"]
    dma = q(g("DMABus")[:, 0] / 8.0 * C["a_dma_per_byte_lane"]
            + g("TLBSize")[:, 0] * C["a_tlb_entry"])
    area = q(q(arr + sram + queues + dma + pick(C["core_area"])[:, 0])
             * C["noc_overhead"])
    power = q(q(q(nj * 1e-9) / q(cycles / C["freq_hz"]) * 1e3)
              + C["base_mw"] + q(C["leak_mw_per_mm2"] * area))
    return np.stack([latency_ms, power, area], 1).astype(np.float64)


def pareto_front(y: np.ndarray) -> np.ndarray:
    """Rows of ``y`` (minimised) that no other row dominates."""
    y = np.asarray(y, np.float64)
    keep = np.ones(len(y), bool)
    for i in range(len(y)):
        if keep[i]:
            dom = np.all(y <= y[i], 1) & np.any(y < y[i], 1)
            if dom.any():
                keep[i] = False
    return y[keep]


# -------------------------------------------- Algorithms 1 and 2 (prologue)
def icd_importance(idx: np.ndarray, y: np.ndarray) -> np.ndarray:
    """Algorithm 1: mean pairwise centroid distance per feature, L2-normed."""
    y = np.asarray(y, np.float64)
    yn = (y - y.mean(0)) / (y.std(0) + 1e-12)
    v = np.zeros(D)
    for i in range(D):
        cents = [yn[idx[:, i] == j].mean(0) for j in range(T_CAND[i])
                 if np.any(idx[:, i] == j)]
        k = len(cents)
        if k >= 2:
            M = np.asarray(cents)
            d = np.linalg.norm(M[:, None] - M[None], axis=-1)
            v[i] = d[np.triu_indices(k, 1)].sum() / (k * (k - 1) / 2)
    s = np.linalg.norm(v)
    return v / s if s > 0 else np.full(D, 1.0 / math.sqrt(D))


def icd_transform(pool_idx: np.ndarray, v: np.ndarray, v_th: float
                  ) -> np.ndarray:
    """Algorithm 2 lines 1-2: pin features with v < v_th to their median
    candidate, then scale the normalised encoding by v / max(v)."""
    idx = np.array(pool_idx)
    for i in range(D):
        if v[i] < v_th:
            idx[:, i] = (T_CAND[i] - 1) // 2
    return encode(idx) * (v / max(v.max(), 1e-12))[None, :]


# ------------------------------------------------------------------- GP
JITTER = 1e-5
BUCKET = 8
CLAMP = {"log_ls": (-3.0, 3.5), "log_var": (-3.0, 3.0), "log_noise": (-7.0, 2.0)}


def default_params(m: int, d: int) -> dict:
    return {"log_ls": np.full((m, d), -0.5), "log_var": np.zeros(m),
            "log_noise": np.full(m, -4.0)}


def padded_training(x: np.ndarray, y: np.ndarray):
    """Pad to a multiple of 8 rows with inert rows (last row shifted by 10 in
    every input, its target repeated, silenced by a 1e6 noise), and
    standardise the negated targets over the real rows."""
    n = x.shape[0]
    pad = (-n) % BUCKET
    mask = np.concatenate([np.zeros(n), np.ones(pad)])
    x = np.concatenate([x, np.repeat(x[-1:] + 10.0, pad, 0)], 0)
    t = -np.asarray(y, np.float64)
    t = np.concatenate([t, np.repeat(t[-1:], pad, 0)], 0)
    w = (1.0 - mask)[:, None]
    mean = (t * w).sum(0) / w.sum()
    std = np.sqrt((((t - mean) ** 2) * w).sum(0) / w.sum()) + 1e-9
    return x, (t - mean) / std, mean, std, mask


def _sqdist(ar: Arith, a, b):
    aa = np.einsum("ij,ij->i", a, a)
    bb = np.einsum("ij,ij->i", b, b)
    d = np.array(ar.mm(a, b.T))
    d *= -2.0
    d += aa[:, None]
    d += bb[None, :]
    return np.maximum(d, 0.0, out=d)


def _kmat(ar: Arith, log_ls, log_var, a, b):
    ls = np.exp(log_ls)
    k = _sqdist(ar, a / ls, b / ls)
    k *= -0.5
    np.exp(k, out=k)
    k *= np.exp(log_var)
    return k


def _kxx(ar, p, j, x, mask):
    P = x.shape[0]
    Kf = _kmat(ar, p["log_ls"][j], p["log_var"][j], x, x)
    K = Kf + (np.exp(p["log_noise"][j]) + JITTER) * np.eye(P, dtype=ar.dtype) \
        + np.diag(1e6 * mask).astype(ar.dtype)
    return Kf, K


def fit(ar: Arith, params: dict, x, yn, mask, steps: int,
        lr: float = 5e-2) -> dict:
    """Adam on each objective's exact negative log marginal likelihood plus
    the weak log-normal hyperpriors, moments reset at each call, parameters
    clamped after every step."""
    p = {k: ar.a(v).copy() for k, v in params.items()}
    x, yn, mask = ar.a(x), ar.a(yn), ar.a(mask)
    m = yn.shape[1]
    P = x.shape[0]
    diff2 = ((x[:, None, :] - x[None, :, :]) ** 2).reshape(P * P, -1)
    mo = {k: np.zeros_like(v) for k, v in p.items()}
    ve = {k: np.zeros_like(v) for k, v in p.items()}
    b1, b2, eps = 0.9, 0.999, 1e-8
    for t in range(steps):
        g = {k: np.zeros_like(v) for k, v in p.items()}
        for j in range(m):
            Kf, K = _kxx(ar, p, j, x, mask)
            L = np.linalg.cholesky(K)
            Li = solve_triangular(L, np.eye(len(L), dtype=ar.dtype),
                                  lower=True)
            Kinv = Li.T @ Li
            alpha = Kinv @ yn[:, j]
            W = np.outer(alpha, alpha) - Kinv                 # d nll = -.5 tr(W dK)
            WK = W * Kf
            ls2 = np.exp(2.0 * p["log_ls"][j])
            g["log_ls"][j] = (-0.5 * (WK.reshape(-1) @ diff2) / ls2
                              + 0.1 * p["log_ls"][j])
            g["log_var"][j] = -0.5 * WK.sum() + 0.1 * p["log_var"][j]
            g["log_noise"][j] = (-0.5 * np.exp(p["log_noise"][j]) * np.trace(W)
                                 + 0.1 * (p["log_noise"][j] + 4.0))
        for k in p:
            mo[k] = b1 * mo[k] + (1 - b1) * g[k]
            ve[k] = b2 * ve[k] + (1 - b2) * g[k] * g[k]
            mh = mo[k] / (1 - b1 ** (t + 1.0))
            vh = ve[k] / (1 - b2 ** (t + 1.0))
            p[k] = np.clip(p[k] - lr * mh / (np.sqrt(vh) + eps), *CLAMP[k])
    return p


class Posterior:
    """Exact GP posterior of every objective under ``params``."""

    def __init__(self, ar: Arith, params: dict, x, yn, mask):
        self.ar, self.p = ar, params
        self.x = ar.a(x)
        self.m = yn.shape[1]
        self.L, self.alpha = [], []
        for j in range(self.m):
            _, K = _kxx(ar, params, j, self.x, ar.a(mask))
            L = np.linalg.cholesky(K)
            self.L.append(L)
            self.alpha.append(cho_solve((L, True), ar.a(yn[:, j])))

    def moments(self, xq, block: int = 16384):
        """Standardised posterior mean and std [q, m] at ``xq``."""
        ar = self.ar
        xq = ar.a(xq)
        mean = np.zeros((len(xq), self.m), ar.dtype)
        std = np.zeros_like(mean)
        for j in range(self.m):
            for s in range(0, len(xq), block):
                Ks = _kmat(ar, self.p["log_ls"][j], self.p["log_var"][j],
                           self.x, xq[s:s + block])
                Vs = solve_triangular(self.L[j], Ks, lower=True)
                mean[s:s + block, j] = ar.mm(Ks.T, self.alpha[j][:, None])[:, 0]
                var = np.exp(self.p["log_var"][j]) - np.einsum(
                    "ij,ij->j", Vs, Vs)
                std[s:s + block, j] = np.sqrt(np.maximum(var, 1e-10))
        return mean, std

    def frontier_ystar(self, xq, eps, y_mean, y_std):
        """[s, m] maxima of joint posterior draws at ``xq`` [q, d], from the
        standard normals ``eps`` [m, q, s], de-standardised."""
        ar = self.ar
        xq = ar.a(xq)
        out = []
        for j in range(self.m):
            lv = self.p["log_var"][j]
            Ks = _kmat(ar, self.p["log_ls"][j], lv, self.x, xq)
            Vs = solve_triangular(self.L[j], Ks, lower=True)
            mean = ar.mm(Ks.T, self.alpha[j][:, None])[:, 0]
            cov = _kmat(ar, self.p["log_ls"][j], lv, xq, xq) - ar.mm(Vs.T, Vs)
            jit = 1e-4 * np.exp(lv) + 1e-6
            Lq = np.linalg.cholesky(cov + jit * np.eye(len(xq), dtype=ar.dtype))
            samp = mean[:, None] + ar.mm(Lq, ar.a(eps[j]))          # [q, s]
            out.append(samp.max(0) * y_std[j] + y_mean[j])
        return np.stack(out, 1)


def mes(mean, std, ystar) -> np.ndarray:
    """Eqs. 7-9: sum over objectives of the mean over frontier samples of
    gamma*pdf/(2*cdf) - log cdf, gamma = (y* - mean) / std."""
    gamma = (ystar[:, None, :] - mean[None]) / std[None]
    pdf = np.exp(-0.5 * gamma * gamma) / math.sqrt(2 * math.pi)
    cdf = np.clip(ndtr(gamma), 1e-9, 1.0)
    return (gamma * pdf / (2.0 * cdf) - np.log(cdf)).mean(0).sum(-1)
