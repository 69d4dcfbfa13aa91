"""Analytic performance model (paper Sec. III) with hardware constants.

The paper's bottleneck model::

    T_tot ∝ max( D_chk / BW_intc,
                 (D_chk + W_halo * S_TB) / BW_dmem * S_TB )

generalizes per engine via :class:`TransferStats` produced by the engines in
:mod:`repro.core.oocore`.  Because this container is CPU-only, kernel-phase
*wall* times on the TPU target are modeled, not measured; benchmarks label
every number as measured (CPU) or modeled (TPU model).

A TPU stencil kernel is VPU-bound, not MXU-bound (neighbour FMAs are vector
ops): the compute term uses ``peak_vpu_flops``.  LM workloads elsewhere in
the repo use ``peak_mxu_flops``.
"""
from __future__ import annotations

import dataclasses

__all__ = ["Hardware", "TPU_V5E", "RTX3080_PAPER", "EngineTimes",
           "model_times", "times_from_plan"]


@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str
    bw_intc: float        # host<->device interconnect, bytes/s
    bw_dmem: float        # off-chip (device/HBM) memory, bytes/s
    c_dmem: int           # off-chip capacity, bytes
    peak_vpu_flops: float  # vector unit peak (stencil FMAs), FLOP/s
    peak_mxu_flops: float  # matrix unit peak (bf16), FLOP/s
    bw_ici: float = 0.0   # per-link inter-chip interconnect, bytes/s
    n_streams: int = 3    # paper fixes N_strm = 3 (double buffering + compute)
    c_vmem: int = 0       # on-chip scratch (VMEM/shared mem), bytes; 0 = unmodeled
    t_ici_latency: float = 0.0  # per collective phase launch overhead, s
    c_dev: int = 0        # per-device working-set budget, bytes; 0 = c_dmem
    device_kind: str = ""  # jax Device.device_kind these constants describe

    def __post_init__(self):
        # the hierarchical planner budgets a shard's resident working set
        # against c_dev; it defaults to the device-memory capacity so the
        # existing constants need no new numbers
        if self.c_dev == 0:
            object.__setattr__(self, "c_dev", self.c_dmem)


# The paper's experimental machine (Table II) — used to sanity-check the
# model against the paper's own reported numbers.
RTX3080_PAPER = Hardware(
    name="rtx3080-pcie3",
    bw_intc=12.0e9,          # PCIe gen3 x16 effective
    bw_dmem=760.0e9,
    c_dmem=10 * 1024**3,
    peak_vpu_flops=29.8e12,  # fp32 CUDA-core peak
    peak_mxu_flops=119e12,   # TC fp16 (unused for stencils)
)

# The reproduction target (assignment hardware constants).
TPU_V5E = Hardware(
    name="tpu-v5e",
    bw_intc=25.0e9,          # host DRAM <-> HBM (PCIe-class on v5e hosts)
    bw_dmem=819.0e9,         # HBM
    c_dmem=16 * 1024**3,
    peak_vpu_flops=3.9e12,   # fp32 vector peak (8 lanes*128 sublanes-ish * 2 * clock)
    peak_mxu_flops=197.0e12,  # bf16 MXU peak (assignment constant)
    bw_ici=50.0e9,           # per ICI link (assignment constant)
    c_vmem=128 * 1024**2,    # v5e VMEM per core (kernels request less)
    t_ici_latency=1e-5,      # collective launch overhead per exchange phase
    device_kind="TPU v5 lite",
)


@dataclasses.dataclass(frozen=True)
class EngineTimes:
    """Modeled phase times, seconds (paper Fig. 7 breakdown categories)."""

    h2d: float
    d2h: float
    odc: float      # on-device copies (region-sharing buffer traffic)
    kernel: float
    kernel_mem: float      # HBM-traffic component of the kernel phase
    kernel_compute: float  # VPU component of the kernel phase

    @property
    def total_serial(self) -> float:
        return self.h2d + self.d2h + self.odc + self.kernel

    def total_overlapped(self, n_streams: int = 3) -> float:
        """With >=3 streams, copies overlap kernels (paper Sec. II/V.D):
        the pipeline settles at max(transfer, kernel+odc) plus ramp-up,
        which we approximate by the max (the paper's Sec. III model)."""
        if n_streams >= 3:
            return max(self.h2d + self.d2h, self.kernel + self.odc)
        if n_streams == 2:
            return max(self.h2d, self.d2h + self.kernel + self.odc)
        return self.total_serial


def model_times(stats, hw: Hardware) -> EngineTimes:
    """Convert engine :class:`TransferStats` into modeled phase times.

    Kernel phase: every kernel invocation streams its input band once from
    HBM and writes its output once (on-chip reuse makes neighbour taps
    free), so ``kernel_mem = hbm_bytes / bw_dmem``; compute is
    ``flops / peak_vpu``.  The two overlap on real hardware:
    ``kernel = max(mem, compute)`` per the roofline.

    Transfer phases are charged at *wire* bytes — what actually crosses
    the interconnect after a codec (arXiv 2204.11315) — which equal the
    raw bytes on uncompressed plans.  Hand-built stats that never set the
    wire fields fall back to raw bytes.
    """
    h2d_wire = getattr(stats, "h2d_wire_bytes", 0) or stats.h2d_bytes
    d2h_wire = getattr(stats, "d2h_wire_bytes", 0) or stats.d2h_bytes
    k_mem = stats.kernel_hbm_bytes / hw.bw_dmem
    k_cmp = stats.flops / hw.peak_vpu_flops
    return EngineTimes(
        h2d=h2d_wire / hw.bw_intc,
        d2h=d2h_wire / hw.bw_intc,
        odc=stats.buffer_bytes / hw.bw_dmem,
        kernel=max(k_mem, k_cmp),
        kernel_mem=k_mem,
        kernel_compute=k_cmp,
    )


def times_from_plan(plan, hw: Hardware) -> EngineTimes:
    """Model phase times straight off a compiled
    :class:`~repro.core.plan.ExecutionPlan`.

    The Sec. III terms map 1:1 onto the plan's op categories (H2D/D2H ->
    interconnect, BufferRead/Write -> off-chip copies, FusedKernel ->
    kernel roofline), so the model input *is* the planned byte count —
    there is no second accounting path to drift from."""
    return model_times(plan.stats(), hw)
