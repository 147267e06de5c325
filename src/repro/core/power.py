"""TPU energy/power model — the paper's power meter, adapted.

The paper reads whole-node watts from IPMI during verification trials.  This
container compiles for TPU v5e but runs on CPU, so power is *modeled* from
the same counters the roofline uses:

    E = FLOPs*e_flop + HBM_bytes*e_hbm + ICI_bytes*e_ici + t*P_static
    W = E / t

Constants are explicit model parameters (the paper itself notes the
evaluation formula "needs to be set differently for each business operator").
Calibration targets: a roofline-balanced v5e chip ~ 160 W, idle ~ 65 W.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_flops: float          # FLOP/s bf16 per chip
    hbm_bw: float              # B/s per chip
    hbm_bytes: float           # capacity per chip
    ici_bw: float              # B/s per link
    # energy constants
    e_flop: float              # J/FLOP
    e_hbm: float               # J/B
    e_ici: float               # J/B
    p_static: float            # W per chip (idle + host share)


V5E = HardwareSpec(
    name="tpu_v5e",
    peak_flops=197e12,
    hbm_bw=819e9,
    hbm_bytes=16 * 2**30,
    ici_bw=50e9,
    e_flop=0.35e-12,
    e_hbm=35e-12,
    e_ici=15e-12,
    p_static=65.0,
)

#: modeled spec per ``jax.Device.device_kind`` (a v5e reports "TPU v5 lite")
SPECS_BY_KIND = {"TPU v5 lite": V5E}


def modeled_spec(device=None) -> HardwareSpec:
    """The modeled hardware spec of ``device`` (default: jax's first).

    A TPU whose kind is not in ``SPECS_BY_KIND`` raises rather than
    borrowing another chip's watts.  Any other platform (the CPU that
    runs the tests) models the v5e this repository targets: its watts
    are *modeled* v5e watts, not a reading of the host."""
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform != "tpu":
        return V5E
    try:
        return SPECS_BY_KIND[device.device_kind]
    except KeyError:
        raise ValueError(f"no modeled hardware spec for TPU kind "
                         f"{device.device_kind!r}: add it to "
                         f"repro.core.power.SPECS_BY_KIND") from None

# The paper's evaluated node (Dell R740 + Arria10 FPGA): used by the MRI-Q
# reproduction to cross-check the *measured* numbers of Fig. 5.
@dataclass(frozen=True)
class NodeSpec:
    name: str
    p_idle: float              # W, whole node at rest
    p_cpu_active: float        # W, node during CPU-only compute
    p_accel_active: float      # W, node during accelerator compute


R740_ARRIA10 = NodeSpec("r740_arria10", p_idle=105.0, p_cpu_active=121.0,
                        p_accel_active=111.0)


@dataclass
class PowerModel:
    hw: HardwareSpec = V5E

    def energy(self, flops: float, hbm_bytes: float, ici_bytes: float,
               seconds: float, chips: int = 1) -> float:
        """Joules for a program phase across `chips` devices.

        flops/hbm_bytes/ici_bytes are TOTALS across chips; `seconds` is the
        wall time of the phase.
        """
        dyn = (flops * self.hw.e_flop + hbm_bytes * self.hw.e_hbm
               + ici_bytes * self.hw.e_ici)
        return dyn + seconds * self.hw.p_static * chips

    def watts(self, flops: float, hbm_bytes: float, ici_bytes: float,
              seconds: float, chips: int = 1) -> float:
        # zero-duration phases draw the static floor, not inf (inf would
        # poison downstream fitness averaging)
        if seconds <= 0:
            return self.hw.p_static * chips
        return self.energy(flops, hbm_bytes, ici_bytes, seconds, chips) / seconds

    # -- roofline time terms (per the §Roofline formulas) --------------------

    def compute_term(self, flops: float, chips: int) -> float:
        return flops / (chips * self.hw.peak_flops)

    def memory_term(self, hbm_bytes: float, chips: int) -> float:
        return hbm_bytes / (chips * self.hw.hbm_bw)

    def collective_term(self, coll_bytes: float, chips: int) -> float:
        return coll_bytes / (chips * self.hw.ici_bw)

    def step_time(self, flops: float, hbm_bytes: float, coll_bytes: float,
                  chips: int, overlap: float = 0.0) -> float:
        """Roofline wall-time estimate.

        overlap in [0,1]: fraction of the collective term hidden behind
        compute (the collective-overlap plan gene raises it).
        """
        tc = self.compute_term(flops, chips)
        tm = self.memory_term(hbm_bytes, chips)
        tcoll = self.collective_term(coll_bytes, chips) * (1.0 - overlap)
        return max(tc, tm) + tcoll
