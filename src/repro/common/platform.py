"""Platform profiles — the hardware constants the analytical model and the
roofline analysis are parameterized by.

FILCO's framework takes "platform information and DDR profiling results" as
input (paper §3.1, Fig. 6).  We keep that contract: every latency estimate in
``repro.core.analytical`` and every roofline term in ``repro.analysis`` reads
from a :class:`PlatformProfile`, never from hard-coded constants.

Two profiles ship:

* ``VCK190``  — the paper's evaluation board (AMD Versal ACAP, 150 MHz PL,
  1 GHz AIE).  Used by the paper-faithful benchmarks (fig8–fig11) so the
  reproduced numbers are commensurate with the paper's.
* ``TPU_V5E`` — the deployment target of this framework (per-chip numbers).
  Used by the dry-run roofline analysis and the TPU-side DSE.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class PlatformProfile:
    name: str
    # -- compute ---------------------------------------------------------
    peak_flops: float          # peak FLOP/s per chip (bf16 for TPU, fp32 for AIE)
    atom_shape: tuple          # (m, k, n) of the atomic matmul the ISA issues
    atom_cycles: float         # pipelined cycles per atomic matmul
    compute_clock_hz: float    # clock of the compute array
    num_compute_units: int     # AIEs per device / MXU passes available
    # -- memory ----------------------------------------------------------
    hbm_bytes: int             # off-chip (DDR / HBM) capacity per chip
    hbm_bw: float              # off-chip bandwidth, bytes/s per chip
    onchip_bytes: int          # on-chip SRAM (PL URAM+BRAM / VMEM) per chip
    onchip_bw: float           # on-chip stream bandwidth, bytes/s
    # -- interconnect ----------------------------------------------------
    ici_bw: float              # per-link inter-chip bandwidth, bytes/s (0 = N/A)
    ici_links: int             # links per chip participating in a collective
    # -- control ---------------------------------------------------------
    instr_bytes: int           # bytes per instruction word
    reconfig_cycles: float     # cycles to decode+apply one runtime instruction
    bitstream_reload_s: float  # full reconfiguration cost (bitstream / recompile)

    @property
    def atom_flops(self) -> float:
        m, k, n = self.atom_shape
        return 2.0 * m * k * n

    def matmul_atoms(self, m: int, k: int, n: int) -> int:
        """Number of atomic ops for an (m,k,n) matmul, ceil-padded per axis."""
        am, ak, an = self.atom_shape
        ceil = lambda x, a: -(-x // a)
        return ceil(m, am) * ceil(k, ak) * ceil(n, an)


def _ceil(x: int, a: int) -> int:
    return -(-x // a)


# ---------------------------------------------------------------------------
# AMD Versal VCK190 (paper's board).  AIE: 400 tiles @ 1 GHz, fp32 MM intrinsics
# issue one 2x8x8 MAC-block per cycle when fully pipelined (paper §2.2 packs a
# 2x8x8 tiled MM as the atomic operation).  PL at 150 MHz moves data between
# FMUs (URAM/BRAM) and the AIE array over AXI streams (paper §4: 150 MHz PL,
# 1 GHz AIE).  DDR4 bandwidth on the board is ~25.6 GB/s.
# ---------------------------------------------------------------------------
VCK190 = PlatformProfile(
    name="vck190",
    peak_flops=400 * (2 * 8 * 8 * 2) * 1.0e9,   # 400 AIEs x 256 FLOP/atom x 1 GHz
    atom_shape=(2, 8, 8),
    atom_cycles=1.0,
    compute_clock_hz=1.0e9,
    num_compute_units=400,
    hbm_bytes=8 << 30,
    hbm_bw=25.6e9,
    onchip_bytes=(130 << 20) // 8,               # ~16 MB URAM+BRAM usable
    onchip_bw=150e6 * 128 * 4,                   # 150 MHz x 128 B ports x 4 chans
    ici_bw=0.0,
    ici_links=0,
    instr_bytes=32,
    reconfig_cycles=8.0,                         # decode a few bytes of instr
    bitstream_reload_s=1.0,                      # full PDI reload ~seconds
)

# ---------------------------------------------------------------------------
# TPU v5e (deployment target).  197 TFLOP/s bf16, 16 GiB HBM @ 819 GB/s,
# ~50 GB/s per ICI link (hardware constants given by the assignment).  The MXU
# atom on v5e is a 128x128 systolic pass fed 8 sublanes at a time: we model the
# ISA atom as (8, 128, 128) — one VREG row-block against a loaded weight tile —
# which is the granularity our Pallas ``filco_mm`` kernel predicates on.
# "Bitstream reload" on TPU = an XLA recompile (measured O(10s) for big
# programs); "instruction decode" = scalar-prefetch SMEM read (O(10) cycles).
# ---------------------------------------------------------------------------
TPU_V5E = PlatformProfile(
    name="tpu_v5e",
    peak_flops=197e12,
    atom_shape=(8, 128, 128),
    atom_cycles=8.0,                             # 8 rows through the MXU
    compute_clock_hz=0.94e9,
    num_compute_units=4,                         # MXUs per chip
    hbm_bytes=16 << 30,
    hbm_bw=819e9,
    onchip_bytes=128 << 20,                      # VMEM
    onchip_bw=22e12,                             # VMEM bandwidth (approx)
    ici_bw=50e9,
    ici_links=4,
    instr_bytes=32,
    reconfig_cycles=16.0,
    bitstream_reload_s=10.0,
)

PROFILES = {p.name: p for p in (VCK190, TPU_V5E)}

# ``device_kind`` as JAX reports it -> the profile of that chip.  A kind
# missing here is an error, never a default: pricing another chip with v5e
# peaks would skew every split the serving policy picks.
DEVICE_PROFILES = {
    "TPU v5 lite": TPU_V5E,
}


def get_profile(name: str) -> PlatformProfile:
    return PROFILES[name]


def device_profile(device=None) -> PlatformProfile:
    """Profile of ``device`` (default: JAX's first device).

    TPU kinds resolve through ``DEVICE_PROFILES`` and an unknown kind
    raises.  The CPU backend (tests, reduced runs) has no peaks of its own
    to price: there the model prices the deployment target, ``TPU_V5E``."""
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform == "cpu":
        return TPU_V5E
    try:
        return DEVICE_PROFILES[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no PlatformProfile for device kind {device.device_kind!r} "
            f"({device.platform}); add its peaks to DEVICE_PROFILES in "
            f"repro/common/platform.py") from None
