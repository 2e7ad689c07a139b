"""Deterministic SoC performance/power/area model — the VLSI-flow surrogate.

The paper's ground truth is Chipyard RTL → ASAP7 Hammer → Verilator. That flow
is a hardware gate in this container, so the evaluator is replaced by a
physically-grounded analytical model of the same SoC (Fig. 1): a Gemmini-style
systolic array with scratchpad/accumulator SRAMs, a RoCC-attached host core
(BOOM/Rocket variants), shared L2, and a DMA engine. Unlike the "simplified
analytical tools" the paper criticizes ([6]-[8]) — reimplemented in
``simplified.py`` for the Fig. 4(c) gap experiment — this model captures the
cross-component interactions the paper says matter:

* WS/OS dataflow changes both compute cycles and DRAM traffic;
* scratchpad capacity decides operand re-fetch multiplicity (tiling);
* accumulator rows bound the output block, forcing weight re-loads;
* DMA bus width / burst length / in-flight requests / TLB reach bound the
  achievable memory bandwidth, with L2 shortening miss latency;
* the host core's RoCC issue rate and the load/store/execute queue + ROB
  depths bound the command rate — an accelerator can starve on control.

All constants are calibrated plausibly for ~1 GHz ASAP7-class silicon and are
*documented fiction*: the shapes of the interactions (cliffs at capacity
boundaries, bandwidth saturation, control starvation) are what the exploration
algorithms are evaluated against, exactly as in the paper's study.

Everything is pure ``jnp`` and broadcast over (designs × layers), so a
2500-design sweep is one XLA program — see ``kernels/systolic_eval`` for the
Pallas-tiled variant of the hot loop.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.core.space import TABLE_I

__all__ = ["soc_metrics", "soc_metrics_multi", "decode_design", "FEATI",
           "CONST"]

# Feature name -> column index in the design-value matrix.
FEATI = {f.name: i for i, f in enumerate(TABLE_I)}

# ------------------------------------------------------------------ constants
CONST = dict(
    freq_hz=1.0e9,
    # memory system
    dram_lat=120.0,           # cycles, L2 miss
    l2_hit_lat=24.0,          # cycles
    tlb_miss_cost=40.0,       # cycles per missed page walk
    page_bytes=4096.0,
    dma_fixed_overhead=16.0,  # burst setup bytes-equivalent
    # host core: issue cycles per RoCC command; dynamic energy per cycle (nJ)
    core_issue=(2.0, 5.0, 8.0),        # c1 LargeBoom, c2 LargeRocket, c3 MedRocket
    core_energy=(0.35, 0.18, 0.12),    # nJ / cycle
    core_area=(1.10, 0.35, 0.22),      # mm²
    layer_launch_cmds=24.0,   # config/fence commands per layer
    # energy (pJ)
    e_mac8=0.25,              # pJ per 8-bit MAC; scales ^1.7 with byte width
    e_spad_byte=0.45,
    e_acc_byte=0.9,
    e_dram_byte=18.0,
    leak_mw_per_mm2=0.6,
    base_mw=2.0,
    # area (mm²)
    a_pe8=1.6e-4,             # 8-bit PE; scales ^1.25 with input bytes
    a_sram_mb=0.90,           # per MiB
    a_acc_sram_mb=1.35,       # wider ports
    a_l2_mb=1.05,
    a_queue_entry=6.0e-4,
    a_dma_per_byte_lane=2.0e-3,
    a_tlb_entry=1.0e-3,
    noc_overhead=1.08,
)


def decode_design(vals: jnp.ndarray) -> dict[str, jnp.ndarray]:
    """Design-value matrix [n, 26] -> named physical quantities (each [n])."""
    g = lambda name: vals[..., FEATI[name]]
    R = g("TileRow") * g("MeshRow")
    C = g("TileCol") * g("MeshCol")
    ib = g("InputType") / 8.0
    ab = g("AccType") / 8.0
    ob = g("OutType") / 8.0
    spad_bytes = g("SpBank") * g("SpCapa") * C * ib  # row = C elements
    acc_rows = g("AccBank") * g("AccCapa")
    acc_bytes = acc_rows * C * ab
    l2_bytes = g("L2Bank") * g("L2Capa") * 1024.0
    return dict(
        core=g("HostCore"), R=R, C=C, ib=ib, ab=ab, ob=ob,
        dataflow=g("Dataflow"),
        spad_bytes=spad_bytes, spad_banks=g("SpBank"),
        acc_rows=acc_rows, acc_bytes=acc_bytes, acc_banks=g("AccBank"),
        l2_bytes=l2_bytes, l2_way=g("L2Way"),
        ldq=g("LdQueue"), stq=g("StQueue"), exq=g("ExQueue"),
        ldr=g("LdRes"), str_=g("StRes"), exr=g("ExRes"),
        memreq=g("MemReq"), dmabus=g("DMABus"), dmabytes=g("DMABytes"),
        tlb=g("TLBSize"),
    )


def _select(core_idx: jnp.ndarray, table: tuple[float, ...]) -> jnp.ndarray:
    # where-chain on python floats (not a gather from a constant array) so
    # the same code traces inside a Pallas kernel body without captures
    out = jnp.full(core_idx.shape, table[0], jnp.float32)
    for i, v in enumerate(table[1:], start=1):
        out = jnp.where(core_idx == float(i), v, out)
    return out


def _ceil_div(a, b):
    """``ceil(a / b)`` for integer-valued ``a, b > 0``, exact even where the
    f32 divide is not correctly rounded (a TPU's is not): a quotient of
    exact multiples that lands an ulp above the integer would otherwise
    ceil one tile too far. Where the divide is exact the two corrections
    never fire, so the value is ``jnp.ceil(a / b)`` bit for bit."""
    q = jnp.ceil(a / b)
    return q - ((q - 1.0) * b >= a) + (q * b < a)


def _layer_cost(d: dict[str, jnp.ndarray], M, K, N, reps, kind):
    """Cycles / DRAM bytes / on-chip stream bytes / host commands for one
    (design, layer) pair. All inputs broadcastable; returns dict of scalars."""
    R, C = d["R"], d["C"]
    ib, ob = d["ib"], d["ob"]
    ceil = _ceil_div

    is_act_b = (kind == 1.0)  # B operand is an activation (attention)
    # ---------------- WS dataflow ----------------
    Mb = jnp.minimum(M, d["acc_rows"])            # output rows resident in acc
    Kt, Nt, Mt = ceil(K, R), ceil(N, C), ceil(M, Mb)
    # per weight tile: R cycles array load; stream Mb rows; C drain at end
    compute_ws = reps * (Kt * Nt * (Mt * Mb + R) + Nt * C)
    w_fits = (K * N * ib) <= 0.5 * d["spad_bytes"]
    a_fits = (Mb * K * ib) <= 0.5 * d["spad_bytes"]
    w_dma_ws = K * N * ib * jnp.where(w_fits, 1.0, Mt)
    a_dma_ws = M * K * ib * jnp.where(a_fits, 1.0, Nt)
    dram_ws = reps * (w_dma_ws + a_dma_ws + M * N * ob)
    stream_ws = reps * (Kt * Nt * Mt * (Mb * R * ib + R * C * ib) + M * N * ob)

    # ---------------- OS dataflow ----------------
    Mt2, Nt2 = ceil(M, R), ceil(N, C)
    compute_os = reps * (Mt2 * Nt2 * (K + R + C))
    w_dma_os = K * N * ib * jnp.where(w_fits, 1.0, Mt2)
    a_fits2 = (M * K * ib) <= 0.5 * d["spad_bytes"]
    a_dma_os = M * K * ib * jnp.where(a_fits2, 1.0, Nt2)
    dram_os = reps * (w_dma_os + a_dma_os + M * N * ob)
    stream_os = reps * (Mt2 * Nt2 * K * (R + C) * ib + M * N * ob)

    # ---------------- dataflow select ----------------
    df = d["dataflow"]
    # OS if forced (1), or if adaptive (2) and cheaper — written as logic,
    # not a select between masks, which the TPU kernel compiler refuses
    use_os = jnp.logical_or(df == 1.0, jnp.logical_and(
        df == 2.0, compute_os < compute_ws))
    compute = jnp.where(use_os, compute_os, compute_ws)
    dram = jnp.where(use_os, dram_os, dram_ws)
    stream = jnp.where(use_os, stream_os, stream_ws)
    n_tiles = jnp.where(use_os, Mt2 * Nt2, Mt * Kt * Nt) * reps
    # attention: "weights" are activations — same traffic, no resident reuse
    dram = jnp.where(is_act_b, dram + 0.15 * K * N * ib * reps, dram)

    macs = reps * M * K * N
    return dict(compute=compute, dram=dram, stream=stream,
                n_tiles=n_tiles, macs=macs)


@jax.jit
def soc_metrics(vals: jnp.ndarray, layers: jnp.ndarray) -> jnp.ndarray:
    """Evaluate designs on a workload.

    ``vals``   [n, 26] raw design values (from ``DesignSpace.values``).
    ``layers`` [L, 5]  rows (M, K, N, reps, kind); kind 0=GEMM weights-from-
               DRAM, 1=activation×activation (attention), 2=depthwise-style
               low-utilization GEMM (reps channels of tiny GEMMs).
    Returns [n, 3]: latency_ms, power_mw, area_mm2.
    """
    return _metrics_tile(jnp.asarray(vals, jnp.float32),
                         jnp.asarray(layers, jnp.float32))


@jax.jit
def soc_metrics_multi(vals: jnp.ndarray, layers: jnp.ndarray,
                      layer_mask: jnp.ndarray) -> jnp.ndarray:
    """Evaluate ``W`` workloads against ``W`` design batches in ONE program.

    ``vals``       [W, n, 26]   per-workload design-value batches
    ``layers``     [W, Lmax, 5] layer lists padded to a common length (use
                                ``repro.soc.workloads.pad_workloads``)
    ``layer_mask`` [W, Lmax]    1.0 on real layers, 0.0 on padding
    Returns [W, n, 3]. This is the fleet runner's cross-scenario fused path:
    the surrogate broadcasts over designs × layers, so vmapping the workload
    axis on top yields a single XLA program for the whole fleet's pending
    evaluations instead of one dispatch per workload."""
    return jax.vmap(_metrics_tile)(jnp.asarray(vals, jnp.float32),
                                   jnp.asarray(layers, jnp.float32),
                                   jnp.asarray(layer_mask, jnp.float32))


def _metrics_tile(vals: jnp.ndarray, layers: jnp.ndarray,
                  layer_mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Un-jitted evaluation body.

    ``layer_mask`` [L] (optional) zeroes out padded layer rows so workloads of
    different depth can be stacked on a common Lmax (``soc_metrics_multi``);
    ``None`` keeps the exact original single-workload computation."""
    return _metrics_cols(vals, tuple(layers[:, i][None, :] for i in range(5)),
                         layer_mask)


def _metrics_cols(vals, cols, layer_mask=None):
    """``_metrics_tile`` on the layer table's five columns, each given as a
    ``[1, L]`` row (M, K, N, reps, kind) — shared verbatim with the Pallas
    ``systolic_eval`` kernel (one design tile per grid step), so kernel and
    oracle cannot drift apart."""
    d = decode_design(vals)

    M, K, N, reps, kind = cols
    # Broadcast designs [n,1] against layers [1,L].
    dd = {k: v[:, None] for k, v in d.items()}
    c = _layer_cost(dd, M, K, N, reps, kind)
    if layer_mask is None:
        n_layers = M.shape[1]
    else:
        # Padded rows carry reps=0 so their traffic/MAC terms are already 0;
        # the mask silences the per-layer launch constants below and keeps
        # the mean-working-set denominator honest.
        c = {k: v * layer_mask[None, :] for k, v in c.items()}
        n_layers = jnp.maximum(jnp.sum(layer_mask), 1.0)

    # ----- memory bandwidth (bytes / cycle), per design -----
    working = jnp.sum(c["dram"], axis=1)  # total DRAM traffic per design
    l2_hit = jnp.clip(3.0 * d["l2_bytes"] / (working / n_layers + 1.0),
                      0.0, 0.85) * (1.0 + 0.05 * jnp.log2(d["l2_way"] / 4.0))
    mem_lat = l2_hit * CONST["l2_hit_lat"] + (1.0 - l2_hit) * CONST["dram_lat"]
    eff = d["dmabytes"] / (d["dmabytes"] + CONST["dma_fixed_overhead"])
    bw = jnp.minimum(d["dmabus"] / 8.0,
                     d["memreq"] * d["dmabytes"] / mem_lat) * eff  # B/cyc

    # TLB reach: pages touched per layer vs TLB entries.
    pages = c["dram"] / CONST["page_bytes"]
    tlb_miss = jnp.maximum(pages - d["tlb"][:, None] * 8.0, 0.0)
    dma_cycles = c["dram"] / bw[:, None] + tlb_miss * CONST["tlb_miss_cost"]

    # ----- host / RoCC control -----
    issue = _select(d["core"], CONST["core_issue"])[:, None]
    q_eff = jnp.minimum(jnp.minimum(d["ldq"], d["ldr"]),
                        jnp.minimum(d["exq"], d["exr"]))[:, None]
    cmds = 4.0 * c["n_tiles"] + CONST["layer_launch_cmds"]
    host_cycles = cmds * issue * (1.0 + 2.0 / q_eff)
    if layer_mask is not None:  # no launch commands for padded layers
        host_cycles = host_cycles * layer_mask[None, :]

    # ----- overlap: double-buffered spad/acc overlaps DMA with compute -----
    three = jnp.stack([c["compute"], dma_cycles, host_cycles], axis=-1)
    hi = jnp.max(three, axis=-1)
    rest = jnp.sum(three, axis=-1) - hi
    buf = jnp.clip((d["spad_banks"][:, None] - 4.0) / 12.0, 0.0, 1.0) * 0.8 \
        + jnp.clip((d["acc_banks"][:, None] - 1.0) / 7.0, 0.0, 1.0) * 0.2
    layer_cycles = hi + (1.0 - buf) * 0.5 * rest + 400.0 * issue
    if layer_mask is not None:
        layer_cycles = layer_cycles * layer_mask[None, :]

    cycles = jnp.sum(layer_cycles, axis=1)
    latency_ms = cycles / CONST["freq_hz"] * 1e3

    # ----- energy / power -----
    e_mac = CONST["e_mac8"] * d["ib"] ** 1.7  # pJ
    pj = (jnp.sum(c["macs"], axis=1) * e_mac
          + jnp.sum(c["stream"], axis=1) * CONST["e_spad_byte"]
          + jnp.sum(c["dram"], axis=1) * CONST["e_dram_byte"])
    host_total = jnp.sum(host_cycles, axis=1)
    nj = pj * 1e-3 + host_total * _select(d["core"], CONST["core_energy"])
    area = _area(d)
    power_mw = (nj * 1e-9) / (cycles / CONST["freq_hz"]) * 1e3 \
        + CONST["base_mw"] + CONST["leak_mw_per_mm2"] * area
    return jnp.stack([latency_ms, power_mw, area], axis=1)


def _area(d: dict[str, jnp.ndarray]) -> jnp.ndarray:
    pe = CONST["a_pe8"] * d["ib"] ** 1.25 * (1.0 + 0.25 * d["ab"] / 4.0)
    arr = d["R"] * d["C"] * pe
    arr = arr * jnp.where(d["dataflow"] == 2.0, 1.12,
                          jnp.where(d["dataflow"] == 1.0, 1.05, 1.0))
    mb = 1.0 / (1024.0 * 1024.0)
    sram = (d["spad_bytes"] * mb * CONST["a_sram_mb"]
            + d["acc_bytes"] * mb * CONST["a_acc_sram_mb"]
            + d["l2_bytes"] * mb * CONST["a_l2_mb"]
            * (1.0 + 0.02 * jnp.log2(d["l2_way"] / 4.0)))
    queues = (d["ldq"] + d["stq"] + d["exq"] + d["ldr"] + d["str_"] + d["exr"]) \
        * CONST["a_queue_entry"]
    dma = d["dmabus"] / 8.0 * CONST["a_dma_per_byte_lane"] \
        + d["tlb"] * CONST["a_tlb_entry"]
    core = _select(d["core"], CONST["core_area"])
    return (arr + sram + queues + dma + core) * CONST["noc_overhead"]


def area_breakdown(vals: jnp.ndarray) -> dict[str, np.ndarray]:
    """Component-wise area (mm²) for Fig. 7(b)."""
    d = decode_design(jnp.asarray(vals, jnp.float32))
    pe = CONST["a_pe8"] * d["ib"] ** 1.25 * (1.0 + 0.25 * d["ab"] / 4.0)
    arr = d["R"] * d["C"] * pe * jnp.where(
        d["dataflow"] == 2.0, 1.12, jnp.where(d["dataflow"] == 1.0, 1.05, 1.0))
    mb = 1.0 / (1024.0 * 1024.0)
    out = {
        "systolic_array": arr,
        "scratchpad": d["spad_bytes"] * mb * CONST["a_sram_mb"],
        "accumulator": d["acc_bytes"] * mb * CONST["a_acc_sram_mb"],
        "l2_cache": d["l2_bytes"] * mb * CONST["a_l2_mb"]
        * (1.0 + 0.02 * jnp.log2(d["l2_way"] / 4.0)),
        "host_core": _select(d["core"], CONST["core_area"]),
        "ctrl_queues": (d["ldq"] + d["stq"] + d["exq"] + d["ldr"] + d["str_"]
                        + d["exr"]) * CONST["a_queue_entry"],
        "dma_tlb": d["dmabus"] / 8.0 * CONST["a_dma_per_byte_lane"]
        + d["tlb"] * CONST["a_tlb_entry"],
    }
    return {k: np.asarray(v) for k, v in out.items()}
